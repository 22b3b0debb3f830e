"""The window backbone of the sequence template (layers of full and of window
attention with their own head counts and rotary tables, a sigmoid gate a head,
a dense first layer, then experts chosen by sigmoid scores beside an ungated
shared expert) against its plain reference (``benchmarks/reference_laguna.py``)
at a small size with seeded weights: the banded attention programs against
their plain twin for windows below, at, across and past a tile and past the
row, at 6 and at 8 query heads a key-value head; the loss's two terms, every
gradient and one Adam step, in float32 and with bfloat16 matmul inputs, with
padded rows; the YaRN table against the formula in float64; the shares of an
expert-parallel deployment, the shared expert counted once, add up to the
uncut layer; the published lists group and a toy of their pattern trains; the
engine takes the backbone by name."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_laguna as ref
from benchmarks import seeded_window
from predictionio_tpu.models.sequence import blocks, experts as experts_module, window_moe
from predictionio_tpu.models.sequence.model import (
    fit_attrs, make_fit, score_next_items_batch, train_sasrec,
)
from predictionio_tpu.models.sequence.window_moe import FULL, WINDOW, WindowMoEConfig
from predictionio_tpu.ops import sparse_attention as sa

VOCAB, T, ROWS = 256, 80, 3
KINDS = (FULL, WINDOW, WINDOW, FULL, WINDOW)          # first, one period of two, a tail of one
MLPS = ("dense", "sparse", "sparse", "sparse", "sparse")
HEADS = (6, 8, 8, 6, 8)
#: the configuration file's keys at the test's size, as ``seeded_window`` reads them
FILE = dict(hidden_size=32, intermediate_size=48, num_hidden_layers=5, layer_types=list(KINDS),
            mlp_layer_types=list(MLPS), num_attention_heads_per_layer=list(HEADS),
            num_key_value_heads=2, head_dim=32, num_experts=8, moe_intermediate_size=24,
            shared_expert_intermediate_size=24)
BALANCE = 1e-2
ROPE = dict(theta=1e4, factor=4.0, original_len=64, beta_fast=4.0, beta_slow=1.0,
            attention_factor=1.2, rotary_fraction=0.5)
DIMS = dict(head_dim=32, num_kv_heads=2, window=16, full_rope=ROPE, window_rope_theta=1e3,
            experts_per_token=2, experts_held=(2, 6), routed_scale=2.5, balance_coef=BALANCE,
            rms_eps=1e-6, query_block=16)


def _config(**kw) -> WindowMoEConfig:
    base = dict(num_items=VOCAB - 1, max_len=T, hidden_size=32, layer_types=KINDS,
                mlp_layer_types=MLPS, heads_per_layer=HEADS, num_kv_heads=2, head_dim=32,
                window=16, ffn_dim=48, expert_dim=24, num_experts=8, experts_per_token=2,
                experts_held=(2, 6), shared_expert_dim=24, balance_coef=BALANCE,
                full_rope_theta=1e4, full_rope_factor=4.0, full_rope_original_len=64,
                full_rope_beta_fast=4.0, full_rope_beta_slow=1.0,
                full_rope_attention_factor=1.2, full_rotary_fraction=0.5,
                window_rope_theta=1e3, compute_dtype="float32", attention="plain",
                head_chunk=64, moe_chunk=64)
    base.update(kw)
    return WindowMoEConfig(**base)


def _mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "seq"))


def _expert_layers(tree) -> list:
    return [tree["periods"]["window"], tree["periods"]["full"], tree["tail"]]


@pytest.fixture(scope="module")
def params():
    drawn = seeded_window.make_params(seeded_window.param_shapes(FILE, VOCAB, 4), 5, 10)
    assert jax.tree_util.tree_map(np.shape, drawn) == window_moe.param_shapes(_config())
    for layer in _expert_layers(drawn):
        layer["router"] = layer["router"] * 10     # no choice near a tie
    for layer in [drawn["first"]] + _expert_layers(drawn):
        layer["wg"] = layer["wg"] * 20             # gates well away from one half
    return drawn


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(11)
    seq = rng.integers(1, VOCAB, (ROWS, T)).astype(np.int32)
    seq[1, 50:] = 0  # padded tails: they are routed nowhere and have no target
    seq[2, 7:] = 0
    targets = np.zeros_like(seq)
    targets[:, :-1] = seq[:, 1:]
    return seq, targets


def _reference(params, batch, dims=DIMS, how=ref.SOUND):
    seq, targets = (jnp.asarray(a) for a in batch)
    return jax.jit(lambda p: ref.loss_and_grads(p, seq, targets, dims, how))(params)


@pytest.fixture(scope="module")
def sound(params, batch):
    return _reference(params, batch)


def _flat(tree):
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {".".join(k.key for k in path): np.asarray(a) for path, a in leaves}


def _feed(batch):
    return {"seq": jnp.asarray(batch[0]), "target": jnp.asarray(batch[1])}


def _step(config, params, batch):
    loss_fn = window_moe.make_loss(config, _mesh())
    return jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(params, _feed(batch), None)


@pytest.fixture(scope="module")
def plain_step(params, batch):
    return _step(_config(), params, batch)


# ---- the banded programs -------------------------------------------------------

def _qkv(heads, kv, t=96, d=24, dv=16, seed=0):
    rng = np.random.default_rng(seed)
    q, k, v, weight = (jnp.asarray(rng.standard_normal(s), jnp.float32) for s in (
        (2, t, heads, d), (2, t, kv, d), (2, t, kv, dv), (2, t, heads, dv)))
    return q, k, v, weight


def _grads(fn, q, k, v, weight):
    return jax.grad(lambda *a: (fn(*a) * weight).sum(), (0, 1, 2))(q, k, v)


#: tiles of 32 queries and 48 keys on a row of 96: below a tile, at the tile of
#: queries, across, at the tile of keys, past both, the row less one
WINDOWS = (1, 5, 32, 40, 48, 70, 95)


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("group", [6, 8])
def test_the_banded_programs_match_their_plain_twin(group, window):
    """Forward and all three gradients, interpreted, against the plain twin
    and against dense attention written out with the band's mask; the grid's
    key axis is as long as the most key blocks a query block's band touches."""
    kv, t = 2, 96
    q, k, v, weight = _qkv(group * kv, kv)

    def dense(q, k, v):
        k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
        s = jnp.einsum("bqhd,bshd->bhqs", q, k) / np.sqrt(q.shape[-1])
        at = np.arange(t)
        on = (at[None, :] <= at[:, None]) & (at[None, :] > at[:, None] - window)
        return jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(jnp.where(on, s, -1e30), -1), v)

    programs = lambda q, k, v: sa.causal_attention(q, k, v, 32, 48, True, window)  # noqa: E731
    plain = lambda q, k, v: sa.causal_attention_plain(q, k, v, window)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want, want_grads = dense(q, k, v), _grads(dense, q, k, v, weight)
        for have in (programs, plain):
            assert np.abs(np.asarray(have(q, k, v) - want)).max() < 1e-5
            for name, a, g in zip("qkv", _grads(have, q, k, v, weight), want_grads):
                # a window of one reads itself alone: its dq is zero
                assert np.abs(np.asarray(a - g)).max() < 1e-4 * np.abs(np.asarray(g)).max() + 1e-5, name
    traced = jax.make_jaxpr(lambda q, k, v: sa._forward(q, k, v, None, 32, 48, False, window))(
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16))
    (grid,) = [e.params["grid_mapping"].grid for e in traced.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    at = np.arange(t)
    on = (at[None, :] <= at[:, None]) & (at[None, :] > at[:, None] - window)
    touched = on.reshape(3, 32, 2, 48).any(axis=(1, 3)).sum(axis=1)   # key blocks a query block
    assert grid == (2, 2, 3, touched.max()) == (2, 2, 3, sa.band_key_blocks(t, 32, 48, window))
    assert sa.band_tiles(t, 32, 48, window) == touched.sum()
    assert sa.band_pairs(t, window) == on.sum()


@pytest.mark.parametrize("window", [None, 96, 200])
def test_no_window_and_a_window_past_the_row_are_the_causal_programs_bit_for_bit(window):
    """``window=None`` is what the accepted cells call: the same outputs and
    gradients to the bit as the masked programs over the causal mask (which
    this file's change leaves as they were; ``tests/test_tpu_compile.py`` pins
    the cells' compiled steps by digest), the row's whole key axis a grid; a
    window that holds the row is no window."""
    q, k, v, weight = _qkv(12, 2)
    causal = jnp.asarray(np.tril(np.ones((96, 96), np.int8))[None].repeat(2, 0))
    masked = lambda q, k, v: sa.sparse_attention(q, k, v, causal, 32, 48, True)  # noqa: E731
    programs = lambda q, k, v: sa.causal_attention(q, k, v, 32, 48, True, window)  # noqa: E731
    assert np.array_equal(np.asarray(programs(q, k, v)), np.asarray(masked(q, k, v)))
    for a, b in zip(_grads(programs, q, k, v, weight), _grads(masked, q, k, v, weight)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert sa.band_of(window, 96) is None and sa.band_key_blocks(96, 32, 48, None) == 2
    with pytest.raises(ValueError, match="want at least 1"):
        sa.causal_attention(q, k, v, 32, 48, True, 0)


def test_the_band_walks_an_eighth_of_the_triangles_tiles_at_the_cells_shape():
    """The counts alone, at 8,192 positions and a window of 512: 4.06 M pairs
    of the triangle's 33.6 M; tiles of 256 by 512 walk two key blocks a query
    block where the triangle walks up to sixteen, half full; no ``[T, T]``
    array is an operand of the program."""
    assert sa.band_pairs(8192, 512) == 4_063_488 and sa.band_pairs(8192, None) == 33_558_528
    assert sa.band_key_blocks(8192, 256, 512, 512) == 2
    assert sa.band_key_blocks(8192, 256, 512, None) == 16
    assert sa.band_tiles(8192, 256, 512, 512) == 62 and sa.band_tiles(8192, 256, 512, None) == 272
    assert sa.band_key_blocks(8192, 256, 256, 512) == 3
    assert sa.tiles_of(8, 8, 128, 128, 8192, 2) == ((256, 512), (512, 512))
    q = jax.ShapeDtypeStruct((2, 8192, 64, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 8192, 8, 128), jnp.bfloat16)
    traced = jax.make_jaxpr(lambda q, k, v: sa._forward(
        q, k, v, None, sa.BLOCK_Q, sa.BLOCK_K, False, 512))(q, kv, kv)
    (call,) = [e for e in traced.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (2, 8, 32, 2)
    assert len(call.invars) == 3 and all(
        v.aval.shape.count(8192) == 1 for v in call.invars)


# ---- the tables -----------------------------------------------------------------

def test_the_yarn_table_is_the_formula_and_the_plain_table_at_factor_one():
    """Against NumPy float64 at the published settings over 8,192 positions
    (past the 4,096 the blend starts from): 32 frequencies over the first 64
    dimensions, the fast ones kept, the slow ones divided by 64, a linear ramp
    between, ``cos`` and ``sin`` scaled by the attention factor."""
    theta, factor, orig, dim, scale = 5e5, 64.0, 4096, 64, 1.4158883083359672
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2 * i / dim)
    c = lambda n: dim * math.log(orig / (2 * math.pi * n)) / (2 * math.log(theta))  # noqa: E731
    low, high = max(math.floor(c(64.0)), 0), min(math.ceil(c(1.0)), dim - 1)
    assert (low, high) == (5, 16)
    r = np.clip((i - low) / (high - low), 0, 1)
    inv = f / factor * r + f * (1 - r)
    have = np.asarray(window_moe.yarn_frequencies(dim, theta, factor, orig, 64.0, 1.0))
    assert np.abs(have / inv - 1).max() < 1e-5
    assert have[0] == 1.0 and abs(have[-1] * 64 / f[-1] - 1) < 1e-5
    cos, sin = window_moe.yarn_tables(8192, dim, theta, factor, orig, 64.0, 1.0, scale)
    assert cos.shape == sin.shape == (8192, dim)
    at = np.array([0, 1, 77, 4096, 8191])
    angle = at[:, None] * have.astype(np.float64)[None, :]
    assert np.abs(np.asarray(cos)[at, :32] - scale * np.cos(angle)).max() < 2e-3
    assert np.abs(np.asarray(sin)[at, 32:] - scale * np.sin(angle)).max() < 2e-3
    assert np.array_equal(np.asarray(cos)[:, :32], np.asarray(cos)[:, 32:])
    # at factor 1 the blend is of a frequency with itself, but for float32's rounding,
    # which an angle multiplies by its position: a short table
    plain = blocks.rope_tables(128, dim, theta)
    for a, b in zip(window_moe.yarn_tables(128, dim, theta, 1.0, orig, 64.0, 1.0, 1.0), plain):
        assert np.abs(np.asarray(a - b)).max() < 5e-5
    # the reference's own table is the same formula written apart
    rope = dict(theta=theta, factor=factor, original_len=orig, beta_fast=64.0, beta_slow=1.0)
    assert np.abs(np.asarray(ref.full_frequencies(rope, dim, ref.SOUND)) / inv - 1).max() < 1e-5


def test_each_kind_of_layer_turns_by_its_own_table():
    config = _config()
    rope = window_moe.rope_of(config, T)
    assert rope[FULL][0].shape == (T, 16) and rope[WINDOW][0].shape == (T, 32)
    want_full = ref.table_of(ref.FULL, T, DIMS, ref.SOUND)
    want_window = ref.table_of(ref.WINDOW, T, DIMS, ref.SOUND)
    assert np.abs(np.asarray(rope[FULL][0][:, :8] - want_full[0])).max() < 1e-6
    assert np.abs(np.asarray(rope[WINDOW][1][:, 16:] - want_window[1])).max() < 1e-6
    x = jnp.asarray(np.random.default_rng(3).standard_normal((T, 3, 32)), jnp.float32)
    have = jnp.concatenate([blocks.rotate(x[None, ..., :16], *rope[FULL])[0], x[..., 16:]], -1)
    assert np.abs(np.asarray(have - ref.rotated(x, *want_full))).max() < 1e-5
    assert np.array_equal(np.asarray(have[..., 16:]), np.asarray(x[..., 16:]))


# ---- the backbone against the reference -------------------------------------------

TRAINED = [".".join(k.key for k in path) for path, _ in jax.tree_util.tree_flatten_with_path(
    window_moe.param_shapes(_config()), is_leaf=lambda x: isinstance(x, tuple))[0]]


@pytest.mark.parametrize("attention", ["plain", "flash"])
def test_both_loss_terms_and_every_gradient_match_the_reference(
        params, batch, sound, plain_step, attention):
    """float32 throughout; "flash" is the attention programs, a full layer's
    and a window layer's band, interpreted. Two rows have padded tails."""
    (loss, aux), grads = (plain_step if attention == "plain" else
                          _step(_config(attention=attention), params, batch))
    want, want_aux, want_grads = sound
    assert abs(float(loss) - float(want)) < 2e-5
    for term in ("ce", "balance"):
        assert abs(float(aux[term]) - float(want_aux[term])) < 2e-5, term
    assert float(want_aux["balance"]) > 0.5
    have, want_flat = _flat(grads), _flat(want_grads)
    assert sorted(have) == sorted(want_flat) == sorted(TRAINED)
    for name in TRAINED:
        scale = np.abs(want_flat[name]).max()
        assert scale > 0, name
        assert np.abs(have[name] - want_flat[name]).max() < 2e-3 * scale, name
    real = int((batch[0] > 0).sum())
    assert int(aux["moe_assignments"]) == 2 * 4 * real      # K x four expert layers
    assert int(aux["moe_held_assignments"]) == int(np.asarray(want_aux["load"])[:, 2:6].sum())
    assert int(aux["moe_dropped"]) == 0


def test_bfloat16_matmul_inputs_stay_near_the_reference(params, batch, sound):
    """As the cell runs it: bfloat16 into every product, float32 out of it."""
    (loss, aux), grads = _step(_config(compute_dtype="bfloat16"), params, batch)
    want, want_aux, want_grads = sound
    assert abs(float(loss) - float(want)) < 5e-3
    assert abs(float(aux["balance"]) - float(want_aux["balance"])) < 1e-3
    have, want_flat = _flat(grads), _flat(want_grads)
    for name in ("first.wq", "first.wg", "first.w_down", "periods.window.wq", "periods.window.wk",
                 "periods.window.wg", "periods.full.wq", "periods.full.wo", "tail.s_down",
                 "periods.window.w_down", "head"):
        rel = np.linalg.norm(have[name] - want_flat[name]) / np.linalg.norm(want_flat[name])
        assert rel < 0.05, (name, rel)


@pytest.mark.parametrize("control,tensor,least", [
    ({"window": "none"}, "periods.window.wk", 1e-1),
    ({"window": "off_by_one"}, "periods.window.wk", 1e-2),
    ({"tables": "one"}, "periods.window.wq", 1e-1),
    ({"yarn": False}, "periods.full.wq", 1e-2),
    ({"rope_scaled": False}, "periods.full.wq", 1e-2),
    ({"gate": False}, "periods.window.wg", 0.99),
    ({"router": "softmax"}, "periods.window.router", 1e-1),
    ({"scaled": False}, "periods.window.w_down", 1e-1),
    ({"precision": "bfloat16"}, "periods.window.router", 1e-3)])
def test_each_control_of_the_reference_reads_other_gradients(
        params, batch, sound, control, tensor, least):
    """What the benchmark's ``--control 1`` plants, at this size: each moves a
    gradient of the path it touches by far more than the program differs from
    the reference (1e-6 of the gradient's norm in float32)."""
    right = _flat(sound[2])[tensor]
    wrong = _flat(_reference(params, batch, how={**ref.SOUND, **control})[2])[tensor]
    assert np.linalg.norm(wrong - right) > least * np.linalg.norm(right), (
        np.linalg.norm(wrong - right) / np.linalg.norm(right))


@pytest.mark.parametrize("case", ["no-remat", "head-whole", "experts-in-chunks-of-32"])
def test_remat_and_chunks_change_nothing(params, batch, plain_step, case):
    how = {"no-remat": dict(remat=False), "head-whole": dict(head_chunk=0),
           "experts-in-chunks-of-32": dict(moe_chunk=32)}[case]
    (loss, _), grads = plain_step
    (other, _), other_grads = _step(_config(**how), params, batch)
    assert abs(float(loss) - float(other)) < 1e-5
    for name, g in _flat(grads).items():
        assert np.abs(_flat(other_grads)[name] - g).max() < 1e-4 * max(np.abs(g).max(), 1e-8), name


def test_a_step_is_adams_update_from_the_references_gradients(params, batch, sound):
    """One program (``make_fit``'s step): two moments for every leaf, and the
    first step's change of every leaf is Adam's from the reference's
    gradient."""
    config = _config()
    _, place, step_fn, _ = make_fit(config, _mesh())
    placed, opt_state = place(params)
    moments = [a for a in jax.tree_util.tree_leaves(opt_state) if a.ndim]
    assert sum(a.size for a in moments) == 2 * window_moe.count_params(config)
    assert window_moe.count_params(config) == sum(a.size for a in _flat(params).values())
    after, _, loss, aux = step_fn(placed, opt_state, _feed(batch), jax.random.PRNGKey(0))
    assert abs(float(loss) - float(sound[0])) < 2e-5 and int(aux["moe_dropped"]) == 0
    before, after, grads = _flat(params), _flat(after), _flat(sound[2])
    for name in TRAINED:
        g = grads[name].astype(np.float64)
        want = -config.learning_rate * g / (np.abs(g) + 1e-8)
        moved = after[name].astype(np.float64) - before[name]
        assert (moved != 0).any(), name
        assert np.abs(moved - want).max() < 2e-2 * config.learning_rate, name


def test_the_gate_is_one_sigmoid_a_head_before_the_output_projection(params, batch):
    """With ``W_g`` at zero every gate is one half: the layer's attention
    output is halved, which is the reference's with ``W_g`` at zero too and not
    its ungated control's."""
    zeroed = jax.tree_util.tree_map_with_path(
        lambda path, a: np.zeros_like(a) if path[-1].key == "wg" else a, params)
    (loss, _), _ = _step(_config(), zeroed, batch)
    want = _reference(zeroed, batch)[0]
    ungated = _reference(zeroed, batch, how={**ref.SOUND, "gate": False})[0]
    assert abs(float(loss) - float(want)) < 2e-5 < abs(float(want) - float(ungated))


# ---- the experts ------------------------------------------------------------------

def test_the_shares_and_the_shared_expert_once_add_up_to_the_whole_layer():
    """Sixteen programs, each holding 2 of 32 experts with the same router and
    the same shared expert: their routed parts, and the shared expert's
    counted once, add up to the reference's uncut expert layer."""
    import functools

    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((1, 96, 32)), jnp.float32)
    real = jnp.asarray(np.arange(96) < 90)[None]
    shapes = seeded_window.param_shapes({**FILE, "num_experts": 32}, VOCAB, 32)
    drawn = seeded_window.make_params(shapes["tail"], 9, 10)
    drawn = {k: v[0] for k, v in drawn.items()}
    drawn = {k: v * 4 for k, v in drawn.items()}       # parts well over the tolerances
    dims = {**DIMS, "experts_per_token": 4, "experts_held": (0, 32)}
    route = functools.partial(experts_module.sigmoid_route, rows=1)
    with jax.default_matmul_precision("highest"):
        want = ref.experts_mlp(drawn, x[0], real[0], dims, ref.SOUND)[0] - x[0]
        routed, shared, held = 0.0, None, 0
        for lo in range(0, 32, 2):
            config = _config(num_experts=32, experts_per_token=4, experts_held=(lo, lo + 2))
            share = {**drawn, **{k: drawn[k][lo:lo + 2] for k in ("w_gate", "w_up", "w_down")}}
            with_shared, stats = experts_module.expert_half(
                config, "cpu", x, share, real, route=route)
            alone = {**share, "s_down": jnp.zeros_like(drawn["s_down"])}
            without, _ = experts_module.expert_half(config, "cpu", x, alone, real, route=route)
            assert int(stats["dropped"]) == 0
            held += int(stats["held_assignments"])
            routed = routed + (without - x)
            mine = with_shared - without
            assert shared is None or np.abs(np.asarray(mine - shared)).max() < 1e-5
            shared = mine
    assert held == int(stats["assignments"]) == int(stats["load"].sum()) == 4 * 90
    assert np.abs(np.asarray(shared)).max() > 1e-3
    assert np.abs(np.asarray(routed[0] + shared[0] - want)).max() < 1e-5


def test_the_router_takes_the_largest_scores_and_scales_their_share():
    rng = np.random.default_rng(6)
    u = jnp.asarray(rng.standard_normal((24, 32)), jnp.float32)
    p = {"router": jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)}
    config = _config(experts_held=(0, 8), experts_per_token=3)
    chosen, gates, stats = experts_module.sigmoid_route(
        config, u, p, jnp.ones((24,), bool), rows=2)
    scores = np.asarray(jax.nn.sigmoid(jnp.matmul(u, p["router"], precision="highest")))
    order = np.argsort(-scores, axis=-1)[:, :3]
    assert np.array_equal(np.sort(np.asarray(chosen), -1), np.sort(order, -1))
    assert np.allclose(np.asarray(gates).sum(-1), 2.5, atol=1e-5)
    picked = np.take_along_axis(scores, np.asarray(chosen), -1)
    assert np.allclose(np.asarray(gates), 2.5 * picked / picked.sum(-1, keepdims=True), atol=1e-6)
    assert int(stats["load"].sum()) == 24 * 3 == int(stats["assignments"])


# ---- the stack's grouping ---------------------------------------------------------

PUBLISHED_KINDS = (FULL,) + (WINDOW, WINDOW, WINDOW, FULL) * 9 + (WINDOW,) * 3
PUBLISHED_MLPS = ("dense",) + ("sparse",) * 39
PUBLISHED_HEADS = tuple(48 if k == FULL else 64 for k in PUBLISHED_KINDS)


def _toy(kinds, mlps=None, heads=None, **kw) -> WindowMoEConfig:
    mlps = mlps or ("dense",) + ("sparse",) * (len(kinds) - 1)
    heads = heads or tuple(6 if k == FULL else 8 for k in kinds)
    base = dict(num_items=12, max_len=8, hidden_size=32, layer_types=kinds, mlp_layer_types=mlps,
                heads_per_layer=heads, num_kv_heads=2, head_dim=8, window=3, ffn_dim=64,
                expert_dim=16, num_experts=4, experts_per_token=2, experts_held=(0, 4),
                shared_expert_dim=16, full_rope_original_len=4, attention="plain")
    base.update(kw)
    return WindowMoEConfig(**base)


def test_the_published_forty_layers_group_into_a_first_nine_periods_and_a_tail_of_three():
    assert len(PUBLISHED_KINDS) == 40
    config = _toy(PUBLISHED_KINDS, PUBLISHED_MLPS, PUBLISHED_HEADS, num_kv_heads=8)
    grouped = window_moe.grouping(config)
    assert grouped == window_moe.Grouping(9, 3, 3, 48, 64)
    assert (grouped.window_layers, grouped.full_layers, config.num_layers) == (30, 10, 40)
    shapes = window_moe.param_shapes(config)
    assert shapes["first"]["wq"] == (32, 48 * 8) and shapes["first"]["w_down"] == (64, 32)
    assert shapes["periods"]["window"]["wq"] == (9, 3, 32, 64 * 8)
    assert shapes["periods"]["full"]["wo"] == (9, 48 * 8, 32)
    assert shapes["tail"]["wg"] == (3, 32, 64)
    assert seeded_window.groups_of(dict(
        layer_types=PUBLISHED_KINDS, mlp_layer_types=PUBLISHED_MLPS, num_hidden_layers=40)) == (
            9, 3, 3)
    # the cell's cut: the first five entries, one whole period and no tail
    cut = _toy(PUBLISHED_KINDS[:5], PUBLISHED_MLPS[:5], PUBLISHED_HEADS[:5], num_kv_heads=8)
    assert window_moe.grouping(cut) == window_moe.Grouping(1, 3, 0, 48, 64)
    assert "tail" not in window_moe.param_shapes(cut)


@pytest.mark.parametrize("why,kinds,mlps,heads", [
    ("layer 0 is the full, dense one", (WINDOW, WINDOW, FULL), None, None),
    ("layer 0 is the full, dense one", (FULL, WINDOW, FULL), ("sparse",) * 3, None),
    ("every later layer sparse", (FULL, WINDOW, FULL), ("dense", "sparse", "dense"), None),
    ("no more than a period's", (FULL, WINDOW, FULL, WINDOW, WINDOW), None, None),
    ("window layers alone", (FULL, WINDOW, WINDOW, FULL, WINDOW, FULL), None, None),
    ("a window layer at least", (FULL, FULL, FULL), None, None),
    ("the same heads", (FULL, WINDOW, WINDOW, FULL), None, (6, 8, 4, 6)),
    ("one entry a layer", (FULL, WINDOW, FULL), ("dense", "sparse"), None),
    ("'full_attention' or 'sliding_attention'", (FULL, "linear_attention"), None, None)])
def test_a_pattern_that_does_not_group_is_refused_with_its_lists(why, kinds, mlps, heads):
    with pytest.raises(ValueError, match=why) as refused:
        _toy(kinds, mlps, heads)
    text = str(refused.value)
    assert "layer_types=[" in text and "mlp_layer_types=[" in text and "heads_per_layer=[" in text
    assert all(kind in text for kind in kinds)


def _cyclic(n_items=12, t=8, rows=96, seed=0):
    starts = np.random.default_rng(seed).integers(0, n_items, rows)
    return ((starts[:, None] + np.arange(t)[None, :]) % n_items + 1).astype(np.int32)


def test_a_nine_layer_toy_of_the_pattern_trains_and_reports_its_fit(caplog):
    """A first layer, two periods of two window layers and a full one, and a
    tail of two: the published pattern at a toy's depth, through
    ``train_sasrec``."""
    import logging

    from predictionio_tpu.obs.trace import global_tracer

    kinds = (FULL, WINDOW, WINDOW, FULL, WINDOW, WINDOW, FULL, WINDOW, WINDOW)
    config = _toy(kinds, learning_rate=0.01, batch_size=32, epochs=12)
    assert window_moe.grouping(config) == window_moe.Grouping(2, 2, 2, 6, 8)
    with caplog.at_level(logging.INFO, logger="pio.sequence"):
        trained, losses = train_sasrec(config, _cyclic(), _mesh(), log_every=1)
    assert losses[-1] < 0.6 * losses[0]
    hits = 0
    for start in range(12):
        prefix = (start + np.arange(4)) % 12 + 1
        scores = score_next_items_batch(trained, config, [prefix])[0]
        hits += int(np.argmax(scores) == (start + 4) % 12)
    assert hits >= 10
    attrs = next(s for tr in global_tracer().snapshot(limit=50)["recent"]
                 for s in tr["spans"] if s["op"] == "seq.fit")["attrs"]
    assert attrs["backbone"] == "window_moe" and attrs["layers"] == 9
    assert (attrs["window"], attrs["window_layers"], attrs["full_layers"], attrs["heads_window"],
            attrs["heads_full"], attrs["rope_tables"], attrs["experts_shared"]) == (
                3, 6, 3, 8, 6, 2, 1)
    assert (attrs["window_pairs"], attrs["causal_pairs"]) == (21, 36)
    assert attrs["moe_dropped"] == 0 and attrs["moe_held_assignments"] == attrs["moe_assignments"]
    assert attrs["balance"] > 0 and attrs["ce"] > 0
    line = next(r.getMessage() for r in caplog.records if "seq_fit:" in r.getMessage())
    for word in ("backbone=window_moe", "window=3", "window_layers=6", "full_layers=3",
                 "heads_window=8", "heads_full=6", "window_pairs=21", "causal_pairs=36",
                 "window_tiles_walked=", "window_tiles_needed=", "rope_tables=2",
                 "window_attention_backward_heads_per_step=", "moe_dropped=0"):
        assert word in line, (word, line)


# ---- the engine ---------------------------------------------------------------------

ENGINE_JSON = os.path.join(os.path.dirname(__file__), "..", "examples", "sequence",
                           "engine-window-moe.json")


def test_the_engine_takes_the_backbone_at_the_cells_sizes():
    from predictionio_tpu.controller.base import Params
    from predictionio_tpu.models.sequence.engine import SASRecAlgorithm

    config = SASRecAlgorithm(Params({
        "backbone": "window_moe", "hiddenSize": 2048, "layerTypes": list(PUBLISHED_KINDS[:5]),
        "mlpLayerTypes": list(PUBLISHED_MLPS[:5]),
        "numAttentionHeadsPerLayer": list(PUBLISHED_HEADS[:5]), "numKvHeads": 8, "headDim": 128,
        "slidingWindow": 512, "ffnDim": 8192, "expertDim": 512, "numExperts": 256,
        "expertsPerToken": 8, "expertsHeld": [0, 16], "sharedExpertDim": 512,
        "batchSize": 2}))._config(12543, 8192)
    assert isinstance(config, WindowMoEConfig) and config.held == 16
    assert (config.num_layers, config.rotary_dim, config.window) == (5, 64, 512)
    # ISSUE 44's table, row by row: 51,380,224 + 2,048 + 79,794,176 + 3 x 91,885,568 + 83,464,192
    assert window_moe.count_params(config) == 490_297_344
    assert experts_module.moe_chunk_of(config) >= 16384
    assert sa.heads_per_step(8, 6) == sa.heads_per_step(8, 8) == 1
    assert window_moe.attention_backward_heads_per_step(config, FULL) == 1
    assert window_moe.attention_backward_heads_per_step(config, WINDOW) == 1
    attrs = fit_attrs(config, 4, 8, 2, "tpu")
    assert (attrs["backbone"], attrs["layers"], attrs["window"], attrs["window_layers"],
            attrs["full_layers"], attrs["heads_window"], attrs["heads_full"],
            attrs["attention_backward_programs"], attrs["rope_tables"]) == (
                "window_moe", 5, 512, 3, 2, 64, 48, 1, 2)
    assert (attrs["window_pairs"], attrs["causal_pairs"]) == (4_063_488, 33_558_528)
    assert attrs["window_tile"] == "256x512"
    # the forward program's 62 tiles and the backward program's 31 of twice the size
    assert attrs["window_tiles_walked"] == 62 + 2 * 31
    assert 0.45 < attrs["window_tiles_needed"] / attrs["window_tiles_walked"] <= 0.51
    assert fit_attrs(config, 4, 8, 2, "cpu")["attention_backward_programs"] == 0
    whole = SASRecAlgorithm(Params({"backbone": "window_moe", "numExperts": 16}))._config(12, 64)
    assert whole.experts_held == (0, 16) and whole.num_layers == 5
    with pytest.raises(ValueError, match="'latent_moe', 'window_moe'"):
        SASRecAlgorithm(Params({"backbone": "mamba"}))._config(12, 64)
    with pytest.raises(ValueError, match="do not group"):
        SASRecAlgorithm(Params({"backbone": "window_moe",
                                "layerTypes": [WINDOW, FULL]}))._config(12, 64)


def test_engine_parameters_round_trip_from_engine_json():
    from predictionio_tpu.controller.base import Params
    from predictionio_tpu.models.sequence.engine import SASRecAlgorithm

    with open(ENGINE_JSON) as f:
        engine = json.load(f)
    written = engine["algorithms"][0]["params"]
    max_len = engine["preparator"]["params"]["maxLen"]
    config = SASRecAlgorithm(Params(written))._config(40, max_len)
    assert isinstance(config, WindowMoEConfig) and config.max_len == max_len
    names = {**window_moe.ENGINE_PARAMS, "learningRate": "learning_rate",
             "batchSize": "batch_size", "epochs": "epochs"}
    for ours, theirs in names.items():
        have = getattr(config, theirs)
        assert written[ours] == (list(have) if isinstance(have, tuple) else have), ours
    # every parameter of the backbone is in the example
    assert set(names) | {"backbone", "attention"} == set(written)
    assert window_moe.grouping(config) == window_moe.Grouping(1, 3, 0, 6, 8)


SCOPES = "jit(train_step)/transpose(jvp(seq.pass1))/"


@pytest.mark.parametrize("name,stage,place", [
    ("layers/while/body/while/body/closed_call/checkpoint/rematted_computation/"
     "window_attention/kernel/pallas_call", "layers", ("window", "kernel")),
    ("layers/while/body/while/body/checkpoint/window_attention/qkv/dot_general", "layers",
     ("window", "qkv")),
    ("layers/while/body/checkpoint/attention/kernel/pallas_call", "attention", None),
    ("layers/checkpoint/attention/rope/mul", "attention", None),
    ("layers/checkpoint/mlp/dot_general", "mlp", None)])
def test_the_readers_keep_the_two_kinds_of_layer_apart(name, stage, place):
    """A window layer's mixer is one scope component, ``window_attention``:
    the accepted readers of ``attention`` leave it to ``layers`` and read the
    full layers alone; ``scopes_window`` reads it by leaf."""
    from benchmarks import scopes_leaf, scopes_seq, scopes_window

    assert scopes_seq.parse_scope(SCOPES + name) == ("pass1", stage)
    assert scopes_leaf.place_of(SCOPES + name).stage == stage
    assert scopes_window.place_of(SCOPES + name) == place
