"""Flash-attention kernel parity tests (interpret mode on the CPU backend)."""

import functools
import importlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops.flash_attention import (
    BLOCK_Q, flash_attention, operands_in_place, tiles_worked,
)
from predictionio_tpu.ops.rope_layout import rotate
from predictionio_tpu.parallel.ring_attention import plain_attention


def _inputs(b=2, t=50, h=2, d=8, seed=0, masked=True):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    if masked:
        # left-padded histories, SASRec-style: first `pad` keys invalid
        pads = rng.integers(0, t // 2, size=b)
        mask = jnp.asarray(np.arange(t)[None, :] >= pads[:, None])
    else:
        mask = None
    return q, k, v, mask


def _rows_with_valid_keys(mask, t, causal=True):
    """Valid query rows that have >=1 valid causal key (the rows where the
    kernel and plain_attention are held to each other: an invalid position
    is no query to the kernel, and comes back 0)."""
    if mask is None:
        return np.ones(t, bool)
    m = np.asarray(mask)
    tri = np.tril(np.ones((t, t), bool)) if causal else np.ones((t, t), bool)
    return (tri & m[:, None, :]).any(axis=-1) & m  # [B, T]


class TestForwardParity:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("masked", [True, False])
    def test_matches_plain(self, causal, masked):
        q, k, v, mask = _inputs(masked=masked)
        got = flash_attention(q, k, v, mask, causal=causal, interpret=True)
        want = plain_attention(q, k, v, causal=causal, mask=mask)
        valid = _rows_with_valid_keys(mask, q.shape[1], causal)
        if mask is None:
            np.testing.assert_allclose(got, want, atol=2e-5)
        else:
            for b in range(q.shape[0]):
                np.testing.assert_allclose(
                    np.asarray(got)[b][valid[b]],
                    np.asarray(want)[b][valid[b]],
                    atol=2e-5,
                )

    def test_long_sequence_multi_block(self):
        # T > BLOCK_Q exercises the online-softmax carry across key blocks
        q, k, v, mask = _inputs(b=1, t=300, h=1, d=8, masked=True)
        got = flash_attention(q, k, v, mask, causal=True, interpret=True)
        want = plain_attention(q, k, v, causal=True, mask=mask)
        valid = _rows_with_valid_keys(mask, 300)
        np.testing.assert_allclose(
            np.asarray(got)[0][valid[0]], np.asarray(want)[0][valid[0]], atol=3e-5
        )


class TestBackwardParity:
    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_match_plain(self, causal):
        q, k, v, mask = _inputs(b=1, t=40, h=2, d=8, masked=True)
        # loss only over defined rows (fully-masked rows differ by design)
        valid = jnp.asarray(_rows_with_valid_keys(mask, 40, causal))
        w = jnp.asarray(
            np.random.default_rng(1).normal(size=(1, 40, 2, 8)), jnp.float32
        ) * valid[..., None, None]

        def loss_flash(q, k, v):
            return (flash_attention(q, k, v, mask, causal=causal, interpret=True) * w).sum()

        def loss_plain(q, k, v):
            return (plain_attention(q, k, v, causal=causal, mask=mask) * w).sum()

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_plain = jax.grad(loss_plain, argnums=(0, 1, 2))(q, k, v)
        for gf, gp, name in zip(g_flash, g_plain, "qkv"):
            np.testing.assert_allclose(
                np.asarray(gf), np.asarray(gp), atol=5e-5,
                err_msg=f"d{name} mismatch",
            )

    def test_sasrec_flash_apply_matches_plain(self):
        """Same params, same input: SASRec forward with attention='flash'
        must match attention='plain' (integration of the kernel into MHA)."""
        from predictionio_tpu.models.sequence.model import SASRec, SASRecConfig

        base = dict(num_items=20, max_len=12, embed_dim=8, num_heads=2,
                    num_blocks=1, ffn_dim=16)
        plain_model = SASRec(SASRecConfig(**base, attention="plain"))
        flash_model = SASRec(SASRecConfig(**base, attention="flash"))
        rng = np.random.default_rng(0)
        seqs = jnp.asarray(
            np.concatenate(
                [np.zeros((3, 4), np.int32),  # left padding
                 rng.integers(1, 21, size=(3, 8)).astype(np.int32)], axis=1
            )
        )
        params = plain_model.init(jax.random.PRNGKey(0), seqs)["params"]
        out_plain = plain_model.apply({"params": params}, seqs)
        out_flash = flash_model.apply({"params": params}, seqs)
        # padding rows differ by design (flash zeroes fully-masked rows);
        # compare the real positions
        np.testing.assert_allclose(
            np.asarray(out_plain)[:, 4:], np.asarray(out_flash)[:, 4:],
            atol=2e-4,
        )

    def test_grads_multi_block(self):
        q, k, v, _ = _inputs(b=1, t=256, h=1, d=8, masked=False)

        def loss_flash(q, k, v):
            return flash_attention(q, k, v, None, causal=True, interpret=True).sum()

        def loss_plain(q, k, v):
            return plain_attention(q, k, v, causal=True).sum()

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_plain = jax.grad(loss_plain, argnums=(0, 1, 2))(q, k, v)
        for gf, gp in zip(g_flash, g_plain):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gp), atol=1e-4)


# -- tiles: the programs work only those that can hold a counting pair ------

def _layout(name: str, t: int) -> np.ndarray:
    """One row's validity [t] by name (t is two or three 128-wide blocks)."""
    pos = np.arange(t)
    return {
        "events_first": pos < 150,            # the preparator's layout
        "events_first_short": pos < 70,       # under half a two-block row
        "padding_first": pos >= t - 150,      # the layout of the tests above
        "block_edge": pos < 128,              # ends exactly on a block edge
        "one_event": pos == 0,
        "one_event_last": pos == t - 1,
        "none": np.zeros(t, bool),
        "full": np.ones(t, bool),
        "hole": (pos < 200) & ~((pos >= 40) & (pos < 90)),
        "block_hole": (pos < 100) | (pos >= 2 * 128 + 10) if t > 256
                      else (pos < 100) | (pos >= 128 + 60),
    }[name]


LAYOUTS = ("events_first", "events_first_short", "padding_first", "block_edge",
           "one_event", "one_event_last", "none", "full", "hole", "block_hole")


def _counting_pairs(valid: np.ndarray, causal: bool) -> np.ndarray:
    """[T, T] (query, key): both valid and, under causal, key not after."""
    pairs = valid[:, None] & valid[None, :]
    return np.tril(pairs) if causal else pairs


def _assert_matches_plain_at_valid(valid, h, causal, seed):
    """Forward and dq, dk, dv of ``valid`` [B, T] rows of ``h`` heads against
    plain_attention at the valid positions; exactly 0, output and gradient,
    at the invalid ones. The cotangent at invalid positions is NOT zeroed for
    the kernel: it has to stop it itself."""
    rng = np.random.default_rng(seed)
    mask = jnp.asarray(valid)
    q, k, v, w = (jnp.asarray(rng.normal(size=(*valid.shape, h, 8)), jnp.float32)
                  for _ in range(4))
    out, vjp = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, mask, causal=causal,
                                        interpret=True), q, k, v)
    want, want_vjp = jax.vjp(
        lambda q, k, v: plain_attention(q, k, v, causal=causal, mask=mask),
        q, k, v)
    np.testing.assert_allclose(np.asarray(out)[valid], np.asarray(want)[valid],
                               atol=3e-5)
    assert not np.asarray(out)[~valid].any()
    for got, ref, name in zip(vjp(w), want_vjp(w * mask[:, :, None, None]), "qkv"):
        got, ref = np.asarray(got), np.asarray(ref)
        np.testing.assert_allclose(got[valid], ref[valid], atol=1e-4,
                                   err_msg=f"d{name}")
        assert not got[~valid].any(), f"d{name} at an invalid position"


class TestTilesSkipped:
    @pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
    @pytest.mark.parametrize("t", [256, 300], ids=["two_blocks", "three_blocks"])
    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_forward_and_grads_match_plain_at_valid(self, layout, t, causal):
        """A row of ``layout`` beside a full row (so the bounds differ by row)."""
        valid = np.stack([_layout(layout, t), np.ones(t, bool)])
        _assert_matches_plain_at_valid(valid, 2, causal, LAYOUTS.index(layout) + t)

    def test_no_mask_is_every_position(self):
        q, k, v, _ = _inputs(b=1, t=300, h=1, d=8, masked=False)
        got = flash_attention(q, k, v, None, causal=True, interpret=True)
        want = plain_attention(q, k, v, causal=True)
        np.testing.assert_allclose(got, want, atol=3e-5)

    def test_a_new_batch_compiles_nothing(self):
        """The bounds are traced values: another mask, the same program."""
        fn = jax.jit(lambda q, k, v, m: flash_attention(
            q, k, v, m, causal=True, interpret=True))
        q, k, v, _ = _inputs(b=2, t=256, h=1, d=8, masked=False)
        for layout in ("events_first", "full", "none"):
            fn(q, k, v, jnp.asarray(np.stack([_layout(layout, 256)] * 2)))
        assert fn._cache_size() == 1

    @pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
    @pytest.mark.parametrize("t", [256, 300, 64], ids=["t256", "t300", "t64"])
    def test_tiles_worked_is_the_brute_force_count(self, t, causal):
        """Over the layouts whose valid blocks are contiguous the kernel's
        rule is the count of tiles that hold a counting pair; a wholly invalid
        block between two valid ones is walked (its tiles contribute zeros)."""
        n = -(-t // BLOCK_Q)
        for layout in LAYOUTS:
            valid = np.zeros(n * BLOCK_Q, bool)
            valid[:t] = _layout(layout, max(t, 256))[:t]
            pairs = _counting_pairs(valid, causal)
            brute = int(pairs.reshape(n, BLOCK_Q, n, BLOCK_Q).any(axis=(1, 3)).sum())
            worked, tiles = tiles_worked(valid[None, :t], causal)
            assert tiles == n * n
            if layout == "block_hole" and t == 300:
                assert worked > brute
            else:
                assert worked == brute, layout

    def test_bounds_are_the_rule_tiles_worked_counts(self):
        """The device's bounds and the host's count are one rule."""
        from predictionio_tpu.ops.flash_attention import _block_bounds

        t = 3 * BLOCK_Q
        valid = np.stack([_layout(name, 300) for name in LAYOUTS])
        valid = np.pad(valid, ((0, 0), (0, t - 300)))
        first, last = (np.asarray(x) for x in _block_bounds(jnp.asarray(valid), BLOCK_Q))
        for row, (lo, hi) in enumerate(zip(first, last)):
            width = max(hi - lo + 1, 0)
            assert tiles_worked(valid[row:row + 1], True)[0] == width * (width + 1) // 2
            assert tiles_worked(valid[row:row + 1], False)[0] == width * width
            held = valid[row].reshape(3, BLOCK_Q).any(axis=1)
            assert (lo, hi) == ((3, -1) if not held.any() else
                                (held.argmax(), 2 - held[::-1].argmax()))

    def test_cell_length_law_works_two_tiles_in_five(self):
        """``ouro-2.6b-d8``: MovieLens-20M's history lengths, events first,
        rows of 256: 40.3% of the tiles at block 128."""
        import json
        import os

        from benchmarks import seeded

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "benchmarks/configs/ouro-2.6b-d8.json")) as f:
            data = json.load(f)["data"]
        lengths = np.minimum(
            seeded.degree_sequence(data["users"], data["events"],
                                   data["user_degrees"]), 256)
        valid = np.arange(256)[None, :] < lengths[:, None]
        worked, tiles = tiles_worked(valid, True)
        assert tiles == 4 * data["users"]
        assert BLOCK_Q == 128 and abs(100.0 * worked / tiles - 40.3) <= 0.1


class TestHeadsAProgram:
    """A program works 4, 2 or 1 heads of a row (they share the row's bounds)."""

    @pytest.mark.parametrize("h,want", [(1, 1), (2, 2), (3, 1), (4, 4), (6, 2), (8, 4)])
    def test_forward_and_grads_whatever_the_grouping(self, h, want):
        from predictionio_tpu.ops.flash_attention import _heads_per_program

        assert _heads_per_program(h, 256, 8, 4, BLOCK_Q) == want
        valid = np.stack([_layout("events_first_short", 256), _layout("hole", 256)])
        _assert_matches_plain_at_valid(valid, h, True, h)

    def test_long_rows_keep_a_head_a_program(self):
        """The blocks of the dkv program stay within the budget: at 2,048
        positions of 64 a head works alone, at the cell's shape four."""
        from predictionio_tpu.ops.flash_attention import _heads_per_program

        assert _heads_per_program(4, 2048, 64, 4, BLOCK_Q) == 1
        assert _heads_per_program(16, 256, 128, 4, BLOCK_Q) == 4
        assert _heads_per_program(2, 512, 32, 4, BLOCK_Q) == 2


# -- blocks of the projections' own arrays, the rotation inside the programs ----

FLASH = importlib.import_module("predictionio_tpu.ops.flash_attention")
RESULTS = ("out", "lse", "dq", "dk", "dv")


def _rows(case: str) -> np.ndarray:
    """Two rows' validity [2, T]: a padding mask (events first, the second
    block of one row empty), a mask with a hole, a ``T`` that is no multiple of
    the block."""
    t = 200 if case == "ragged_t" else 256
    first = {"padding": "events_first_short", "hole": "hole", "ragged_t": "events_first"}[case]
    return np.stack([_layout(first, 256)[:t], _layout("full", 256)[:t]])


def _operands(case: str, d: int):
    """``(q, k, v, w)`` [2, T, 2, d] and the case's mask."""
    valid = _rows(case)
    rng = np.random.default_rng(len(case) + d)
    return (*(jnp.asarray(rng.normal(size=(*valid.shape, 2, d)), jnp.float32)
              for _ in range(4)), jnp.asarray(valid))


@functools.lru_cache(maxsize=None)
def _everything(transposed: bool = False):
    """``(q, k, v, w, mask, rope) -> (out, lse, dq, dk, dv)`` of one causal
    attention with the cotangent ``w``, one program for the three calls (a
    case of the same shapes compiles nothing); ``transposed``: through
    [B, H, T, D] whatever the heads' width, the rule patched out here."""
    def run(q, k, v, w, mask, rope=None):
        rule = (lambda head_dim: False) if transposed else FLASH.operands_in_place
        with mock.patch.object(FLASH, "operands_in_place", rule):
            out, res = FLASH._flash_fwd(q, k, v, mask, True, None, True, rope)
            return (out, res[-1], *FLASH._flash_bwd(True, None, True, res, w)[:3])

    return jax.jit(run)


def _program_operand_ranks(fn, *args) -> set:
    """The ranks of the q-sized float32 operands of the Pallas programs in
    ``fn``: 3 in place, 4 transposed."""
    def programs(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from programs(sub)

    found = list(programs(jax.make_jaxpr(fn)(*args).jaxpr))
    assert len(found) == 3
    return {len(v.aval.shape) for eqn in found for v in eqn.invars
            if v.aval.dtype == jnp.float32 and v.aval.size >= args[0].size}


def _assert_close_at_valid(got, want, mask, atol):
    """The kernel's ``RESULTS`` against another attention's at the valid
    positions (the logsumexp where the other has one); the kernel's exactly 0
    at the others."""
    valid = np.asarray(mask)
    for mine, other, name in zip(got, want, RESULTS):
        if name == "lse":
            if other is not None:
                np.testing.assert_allclose(mine, other, atol=atol, err_msg=name)
            continue
        mine, other = np.asarray(mine), np.asarray(other)
        np.testing.assert_allclose(mine[valid], other[valid], atol=atol, err_msg=name)
        assert not mine[~valid].any(), f"{name} at an invalid position"


@functools.lru_cache(maxsize=None)
def _plain_everything():
    """``(q, k, v, w, mask) -> (out, None, dq, dk, dv)`` of ``plain_attention``,
    the cotangent stopped at the invalid positions."""
    def run(q, k, v, w, mask):
        out, vjp = jax.vjp(lambda q, k, v: plain_attention(q, k, v, causal=True, mask=mask),
                           q, k, v)
        return (out, None, *vjp(w * mask[:, :, None, None]))

    return jax.jit(run)


class TestOperandsInPlace:
    """Heads of whole lane tiles are blocks of [B, T, H x D] as the projections
    wrote it; any other width goes through [B, H, T, D]: the width alone says."""

    @pytest.mark.parametrize("d", [128, 16])
    def test_the_heads_width_alone_chooses_the_blocks(self, d):
        q, k, v, w, mask = _operands("ragged_t", d)
        assert operands_in_place(d) == (d == 128)
        assert _program_operand_ranks(_everything(), q, k, v, w, mask) == (
            {3} if d == 128 else {4})
        assert _program_operand_ranks(_everything(transposed=True), q, k, v, w, mask) == {4}

    @pytest.mark.parametrize("case", ["padding", "hole", "ragged_t"])
    @pytest.mark.parametrize("d", [128, 16])
    def test_either_layout_gives_the_plain_results(self, d, case):
        """At 128 the in-place blocks give what the transposed blocks give on
        the same inputs, to the bit (the same arithmetic, other addresses); at
        either width what ``plain_attention`` gives at the valid positions."""
        q, k, v, w, mask = _operands(case, d)
        got = _everything()(q, k, v, w, mask)
        if d == 128:
            for mine, other, name in zip(
                    got, _everything(transposed=True)(q, k, v, w, mask), RESULTS):
                np.testing.assert_array_equal(mine, other, err_msg=name)
        _assert_close_at_valid(got, _plain_everything()(q, k, v, w, mask), mask, 1e-4)

    @pytest.mark.parametrize("case", ["padding", "hole", "ragged_t"])
    def test_the_programs_rotate_as_rotate_does(self, case):
        """``rope=``: the output, and the gradients with respect to the
        unrotated q and k, of ``blocks.rotate`` followed by the kernel without
        it, and of ``plain_attention`` at the valid positions."""
        from predictionio_tpu.models.sequence.blocks import rope_tables

        q, k, v, w, mask = _operands(case, 128)
        rope = rope_tables(q.shape[1], 128, 1e4)
        got = _everything()(q, k, v, w, mask, rope)
        (rq, rk), unrotated = jax.vjp(lambda q, k: (rotate(q, *rope), rotate(k, *rope)), q, k)
        for attention, atol in ((_everything(), 2e-5), (_plain_everything(), 1e-4)):
            out, lse, dq, dk, dv = attention(rq, rk, v, w, mask)
            _assert_close_at_valid(got, (out, lse, *unrotated((dq, dk)), dv), mask, atol)

    def test_a_table_the_programs_cannot_turn_by_is_refused(self):
        from predictionio_tpu.models.sequence.blocks import rope_tables

        q, k, v, _ = _inputs(b=1, t=40, h=2, d=16, masked=False)
        with pytest.raises(ValueError, match="rope"):
            flash_attention(q, k, v, None, interpret=True, rope=rope_tables(40, 16, 1e4))
        q, k, v, _ = _inputs(b=1, t=40, h=1, d=128, masked=False)
        with pytest.raises(ValueError, match="rope"):
            flash_attention(q, k, v, None, interpret=True, rope=rope_tables(40, 64, 1e4))
