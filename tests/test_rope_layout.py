"""The two Pallas programs that write the streamed attention's operands
(``ops/rope_layout.py``), interpreted, against the plain expression they
replace (rotate, cast, scale, cast, heads first) at the three callers' head
counts and widths and toy lengths: ``qs``, ``k`` and ``v``; the transpose from
the cotangents the attention's backward program writes (float32, heads-first)
and from ``jax.vjp``'s (the compute dtype); rows no tile divides; a table over
part of the head and a head of 256. And the attention programs' heads-first
entry point against the ``[B, T, H, D]`` ones on the same inputs, values and
gradients, with a mask, without one and with a window; the two together
against the path they replace; the tile from the shapes, and the fits'
``rope_block``.

**To the bit, and where.** The programs round where the plain expression
rounds, so on the chip the two are equal to the bit. XLA's CPU backend, which
runs both sides here, contracts a product and a sum into one rounding inside a
compiled loop, one product or the other as it sees fit, so two compiled forms
of ``x cos + t sin`` may differ in float32's last place. The cases marked
``exact`` draw the inputs, the cotangents and the table from numbers of eight
bits (bfloat16's): then every product is exact in float32, a sum of two is
rounded once whichever is contracted, and the comparison is to the bit for
every rounding to the compute dtype and every float32 sum. The cases marked
``real`` draw float32 inputs and take the real table: the compute dtype's
values to one step of it (a float32 last place can tip a rounding), float32
sums to 1e-6 of their size."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models.sequence import blocks, hybrid, latent_moe, sparse_moe, window_moe
from predictionio_tpu.models.sequence.model import fit_attrs
from predictionio_tpu.ops import rope_layout as rl, sparse_attention as sa

#: (H, KV, D, DV, rd): the window backbone's two kinds of layer, the sparse
#: backbone's, the hybrid backbone's full layer (a head of 256, a quarter of it
#: turned), and toys: a head narrower than a lane tile, half of it turned, and
#: values narrower than the scores
CALLERS = {
    "window": (64, 8, 128, 128, 128), "full": (48, 8, 128, 128, 64),
    "sparse": (32, 4, 128, 128, 128), "hybrid": (16, 2, 256, 256, 64),
    "toy": (4, 2, 16, 16, 8), "narrow-values": (6, 2, 32, 16, 32),
}
bf16 = jnp.bfloat16


def _eight_bits(x):
    return jnp.asarray(x, jnp.float32).astype(bf16).astype(jnp.float32)


def _inputs(case: str, t: int, draw: str, rows: int = 2, seed: int = 0):
    h, kv, d, dv, rd = CALLERS[case]
    rng = np.random.default_rng(seed + t + h)
    q, k, v = (jnp.asarray(rng.standard_normal((rows, t, n), dtype=np.float32))
               for n in (h * d, kv * d, kv * dv))
    cos, sin = (1.3 * x for x in blocks.rope_tables(t, rd, 1e4))   # scaled, as a YaRN table is
    if draw == "exact":
        q, k, v, cos, sin = map(_eight_bits, (q, k, v, cos, sin))
    return (q, k, v, cos, sin), (h, kv)


def _cotangents(outs, dtype, draw: str = "exact", seed: int = 1):
    rng = np.random.default_rng(seed)
    cts = (jnp.asarray(rng.standard_normal(o.shape, dtype=np.float32)) for o in outs)
    return tuple((_eight_bits(ct) if draw == "exact" else ct).astype(dtype) for ct in cts)


def _programs_cotangents(case: str, t: int):
    """float32 and heads-first, as the attention's backward program writes them:
    sums, so no eight bits here, but every rounding to the compute dtype comes
    before any product of the way back."""
    h, kv, d, dv, _ = CALLERS[case]
    return _cotangents([jax.ShapeDtypeStruct((2, n, t, w), jnp.float32)
                        for n, w in ((h, d), (kv, d), (kv, dv))], jnp.float32, "real")


def _hf(x):
    return jnp.transpose(x, (0, 2, 1, 3))


def _assert_equal(have, want):
    assert len(have) == len(want)
    for n, (a, b) in enumerate(zip(have, want)):
        assert a.shape == b.shape and a.dtype == b.dtype, n
        assert np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32)), n


def _assert_equal_to_rounding(have, want):
    """float32 to 1e-6 of the array's size, the compute dtype to a step of it."""
    assert len(have) == len(want)
    for n, (a, b) in enumerate(zip(have, want)):
        assert a.shape == b.shape and a.dtype == b.dtype, n
        step = 1e-6 if a.dtype == jnp.float32 else 2.0 ** -7
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= step * np.abs(b).max(), n


SAME = {"exact": _assert_equal, "real": _assert_equal_to_rounding}


@pytest.mark.parametrize("t,block,draw", [(48, None, "exact"), (80, 32, "exact"),
                                          (200, None, "exact"), (80, 32, "real")],
                         ids=["48-exact", "80-exact", "200-exact", "80-real"])
@pytest.mark.parametrize("case", list(CALLERS))
def test_the_program_writes_the_plain_expressions_operands(case, t, block, draw):
    """48: one block longer than the row; 80 in blocks of 32: two and a half;
    200: blocks of 128 from the shapes, the second past the row."""
    (q, k, v, cos, sin), heads = _inputs(case, t, draw)
    assert t % (block or rl.tile_of(heads, *CALLERS[case][2:4], t)[0])
    have = jax.jit(lambda *a: rl.rope_layout(*a, heads, "bfloat16", True, block))(q, k, v, cos, sin)
    want = jax.jit(lambda *a: rl.rope_layout_plain(*a, heads, "bfloat16"))(q, k, v, cos, sin)
    assert [x.dtype for x in have] == [bf16] * 3
    SAME[draw](have, want)
    _assert_equal(have[2:], want[2:])          # v is a rounding and no sum


def _parents_transpose(cos, sin, heads, d: int, dtype):
    """What the parent's passes make of the backward program's float32
    heads-first ``dq``, ``dk``, ``dv``: the scale, the cast and the transpose
    (``ops/sparse_attention._bwd``), then the transposes of the casts and of
    the rotation, to the projections' float32 cotangents."""
    h, kv = heads

    def run(dq, dk, dv):
        given = ((_hf(dq) * d ** -0.5).astype(dtype), _hf(dk).astype(dtype), _hf(dv).astype(dtype))
        b, t = given[0].shape[:2]
        at = [jnp.zeros((b, t, n, g.shape[3]), jnp.float32) for n, g in zip((h, kv, kv), given)]
        back = jax.vjp(lambda q, k, v: (rl.rotate(q, cos, sin).astype(dtype),
                                        rl.rotate(k, cos, sin).astype(dtype), v.astype(dtype)),
                       *at)[1](given)
        return tuple(x.reshape(b, t, -1) for x in back)

    return run


@pytest.mark.parametrize("draw,dtype", [("exact", "bfloat16"), ("real", "bfloat16"),
                                        ("real", "float32")])
@pytest.mark.parametrize("case,t,block", [
    ("window", 48, None), ("full", 80, 32), ("sparse", 48, None), ("hybrid", 80, 32),
    ("toy", 200, None), ("narrow-values", 80, 32)], ids=lambda v: str(v))
def test_the_transpose_takes_the_backward_programs_cotangents_as_the_parents_passes_did(
        case, t, block, draw, dtype):
    (q, k, v, cos, sin), heads = _inputs(case, t, draw)
    h, kv, d, dv, rd = CALLERS[case]
    cts = _programs_cotangents(case, t)
    have = jax.jit(lambda *c: rl._layout_bwd(heads, dtype, True, block, (cos, sin), c))(*cts)
    want = jax.jit(_parents_transpose(cos, sin, heads, d, jnp.dtype(dtype)))(*cts)
    assert have[3:] == (None, None)                    # the table has no cotangent
    SAME[draw](have[:3], want)
    # a rounding and no sum: v, and the lanes of q and k past the table
    _assert_equal(have[2:3], want[2:])
    if rd < d:
        past = lambda x: x.reshape(2, t, -1, d)[..., rd:]  # noqa: E731
        _assert_equal([past(x) for x in have[:2]], [past(x) for x in want[:2]])


@pytest.mark.parametrize("case", ["window", "hybrid", "toy"])
def test_with_a_table_that_turns_nothing_the_transpose_is_the_roundings_to_the_bit(case):
    """cos 1 and sin 0: what is left is ``r(dq D ** -0.5)``, ``r(dk)``, ``r(dv)``."""
    t = 48
    (q, k, v, cos, sin), heads = _inputs(case, t, "real")
    h, kv, d, dv, _ = CALLERS[case]
    cos, sin = jnp.ones_like(cos), jnp.zeros_like(sin)
    cts = _programs_cotangents(case, t)
    have = jax.jit(lambda *c: rl._layout_bwd(heads, "bfloat16", True, None, (cos, sin), c))(*cts)
    want = ((_hf(cts[0]) * d ** -0.5).astype(bf16), _hf(cts[1]).astype(bf16),
            _hf(cts[2]).astype(bf16))
    _assert_equal(have[:3], [x.astype(jnp.float32).reshape(2, t, -1) for x in want])


@pytest.mark.parametrize("draw", list(SAME))
@pytest.mark.parametrize("case,t,block", [("window", 48, None), ("full", 80, 32),
                                          ("hybrid", 48, None), ("toy", 80, 32)],
                         ids=lambda v: str(v))
def test_the_vjp_is_the_plain_expressions(case, t, block, draw):
    """``jax.vjp`` of both, cotangents in the compute dtype."""
    (q, k, v, cos, sin), heads = _inputs(case, t, draw)

    def pulled(layout):
        def run(q, k, v, cts):
            return jax.vjp(lambda q, k, v: layout(q, k, v, cos, sin), q, k, v)[1](cts)
        return jax.jit(run)

    program = lambda *a: rl.rope_layout(*a, heads, "bfloat16", True, block)  # noqa: E731
    plain = lambda *a: rl.rope_layout_plain(*a, heads, "bfloat16")  # noqa: E731
    cts = _cotangents(jax.eval_shape(plain, q, k, v, cos, sin), bf16, draw)
    have, want = pulled(program)(q, k, v, cts), pulled(plain)(q, k, v, cts)
    SAME[draw](have, want)
    _assert_equal(have[2:], want[2:])


def test_rotate_over_part_of_the_head_passes_the_rest():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 24, 3, 32), dtype=np.float32))
    cos, sin = blocks.rope_tables(24, 16, 1e4)
    have = blocks.rotate(x, cos, sin)
    want = jnp.concatenate([blocks.rotate(x[..., :16], cos, sin), x[..., 16:]], axis=-1)
    assert np.array_equal(have, want) and not np.array_equal(have[..., :16], x[..., :16])


# ---- the attention programs on operands laid heads-first -------------------------

PAIRS = {"mask": (True, None), "causal": (False, None), "window": (False, 24)}


@pytest.mark.parametrize("pairs,heads,d,dv", [
    ("mask", (6, 2), 32, 32), ("causal", (6, 2), 32, 32), ("window", (6, 2), 32, 32),
    ("causal", (4, 4), 24, 16)], ids=["mask", "causal", "window", "ungrouped-192-128-like"])
def test_the_heads_first_entry_point_is_the_programs_of_the_positions_first_ones(
        pairs, heads, d, dv):
    """Values and gradients: the same programs on the same operands, so equal
    to the bit once the positions-first entry point's passes are applied to
    what the heads-first one hands on (float32, heads-first)."""
    masked, window = PAIRS[pairs]
    (h, kv), t, rng = heads, 64, np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.standard_normal((2, t, n, w), dtype=np.float32), bf16)
               for n, w in ((h, d), (kv, d), (kv, dv)))
    mask = jnp.asarray(np.tril(rng.random((2, t, t)) < 0.6) | np.eye(t, dtype=bool), jnp.int8)
    g_out = jnp.asarray(rng.standard_normal((2, t, h, dv), dtype=np.float32), bf16)
    if masked:
        old = lambda q, k, v: sa.sparse_attention(q, k, v, mask, 32, 32, True)  # noqa: E731
    else:
        old = lambda q, k, v: sa.causal_attention(q, k, v, 32, 32, True, window)  # noqa: E731
    new = lambda qs, k, v: sa.heads_first_attention(  # noqa: E731
        qs, k, v, mask if masked else None, 32, 32, True, window)
    scale = d ** -0.5
    qs = _hf((q.astype(jnp.float32) * scale).astype(bf16))
    want, pull_old = jax.vjp(old, q, k, v)
    have, pull_new = jax.vjp(new, qs, _hf(k), _hf(v))
    _assert_equal([have], [want])
    dq, dk, dv_ = pull_new(g_out)
    assert [x.dtype for x in (dq, dk, dv_)] == [jnp.float32] * 3      # as the program wrote them
    assert dq.shape == qs.shape and dk.shape == (2, kv, t, d) and dv_.shape == (2, kv, t, dv)
    _assert_equal([(_hf(dq) * scale).astype(bf16), _hf(dk).astype(bf16), _hf(dv_).astype(bf16)],
                  pull_old(g_out))


def test_a_mask_and_a_window_together_are_refused():
    x = jnp.zeros((1, 2, 32, 16), bf16)
    with pytest.raises(ValueError, match="no window beside it"):
        sa.heads_first_attention(x, x, x, jnp.ones((1, 32, 32), jnp.int8), 32, 32, True, 8)


@pytest.mark.parametrize("pairs,case", [("mask", "toy"), ("causal", "toy"), ("window", "toy"),
                                        ("window", "narrow-values")])
def test_the_two_ops_together_are_the_path_they_replace(pairs, case):
    """From the projections' float32 outputs to the attention's output and back
    to their cotangents: the operands' programs and the heads-first attention
    against the rotation, the casts and the positions-first attention, on an
    exact draw: the same operands, so the same programs' output and gradients,
    rounded to the compute dtype before any product of the way back."""
    masked, window = PAIRS[pairs]
    t = 64
    (q, k, v, cos, sin), heads = _inputs(case, t, "exact")
    h, kv, d, dv, _ = CALLERS[case]
    rng = np.random.default_rng(5)
    mask = (jnp.asarray(np.tril(rng.random((2, t, t)) < 0.6) | np.eye(t, dtype=bool), jnp.int8)
            if masked else None)
    g_out = jnp.asarray(rng.standard_normal((2, t, h, dv), dtype=np.float32), bf16)

    def new(q, k, v):
        ops = rl.rope_layout(q, k, v, cos, sin, heads, "bfloat16", True)
        return sa.heads_first_attention(*ops, mask, 32, 32, True, window)

    def old(q, k, v):
        q, k, v = (x.reshape(2, t, n, -1) for x, n in zip((q, k, v), (h, kv, kv)))
        q, k = (rl.rotate(x, cos, sin).astype(bf16) for x in (q, k))
        if masked:
            return sa.sparse_attention(q, k, v.astype(bf16), mask, 32, 32, True)
        return sa.causal_attention(q, k, v.astype(bf16), 32, 32, True, window)

    both = [jax.jit(lambda q, k, v, f=f: (lambda out, pull: (out, *pull(g_out)))(
        *jax.vjp(f, q, k, v)))(q, k, v) for f in (new, old)]
    _assert_equal(*both)


# ---- the tile and the fits --------------------------------------------------------

def test_the_tile_comes_from_the_shapes_and_keeps_the_blocks_inside_the_budget():
    """The four layers of the cells at 8,192 positions; eight query heads a
    step where the heads are whole lane tiles, all heads where they are not."""
    want = {"window": (512, 1024, 1), "full": (1024, 768, 1), "sparse": (512, 1024, 1),
            "hybrid": (256, 2048, 1)}
    for case, tile in want.items():
        h, kv, d, dv, _ = CALLERS[case]
        bt, lanes, s = rl.tile_of((h, kv), d, dv, 8192)
        assert (bt, lanes, s) == tile, case
        held = 2 * (4 + 4) * bt * (lanes + s * (d + dv))      # float32 in and out, two buffers
        assert held <= rl.BLOCK_VMEM_BYTES < 2 * held, case
    assert rl.tile_of((4, 2), 16, 16, 200) == (128, 64, 2)     # no more than the row in chunks
    assert rl.tile_of((4, 2), 16, 16, 20) == (20, 64, 2)       # a row shorter than a chunk
    assert rl.tile_of((32, 32), 128, 128, 64) == (64, 1024, 8)  # ungrouped: eight heads a step


def test_the_fits_say_which_tile_wrote_the_operands():
    sparse = sparse_moe.SparseMoEConfig(num_items=50, max_len=8192, num_heads=32, num_kv_heads=4,
                                        head_dim=128)
    full = hybrid.HybridConfig(num_items=50, max_len=8192, num_heads=16, num_kv_heads=2,
                               head_dim=256)
    window = window_moe.WindowMoEConfig(
        num_items=50, max_len=8192, heads_per_layer=(48, 64, 64, 64, 48), num_kv_heads=8,
        head_dim=128, window=512)
    for config, want in ((sparse, {"rope_block": "512x1024"}), (full, {"rope_block": "256x2048"}),
                         (window, {"rope_block": "1024x768", "window_rope_block": "512x1024"})):
        on_chip, on_host = (fit_attrs(config, 4, 8, 2, platform) for platform in ("tpu", "cpu"))
        assert {k: on_chip[k] for k in want} == want
        assert {k: on_host[k] for k in want} == dict.fromkeys(want, "plain")


# ---- latent attention's operands: pairs interleaved, one rotary key ----------------

#: (H, dn, dr, dv): the latent cell's heads (128 + 64 scored, 128 carried: a
#: head of q is one and a half lane tiles), one head, and toys: four heads
#: narrower than a lane tile, and values narrower than the scores' nope part
LATENT = {"cell": (32, 128, 64, 128), "one-head": (1, 16, 8, 16), "toy": (4, 16, 8, 16),
          "narrow-values": (6, 32, 16, 16)}


def _latent_inputs(case: str, t: int, draw: str, seed: int = 0):
    h, dn, dr, dv = LATENT[case]
    rng = np.random.default_rng(seed + t + h)
    q, kv, k_r = (jnp.asarray(rng.standard_normal((2, t, n), dtype=np.float32))
                  for n in (h * (dn + dr), h * (dn + dv), dr))
    cos, sin = latent_moe.rope_tables(t, dr, 1e4)
    if draw == "exact":
        q, kv, k_r, cos, sin = map(_eight_bits, (q, kv, k_r, cos, sin))
    return (q, kv, k_r, cos, sin), h


def _assert_the_keys_sum_is_close(have, want):
    """``dk_r`` is a float32 sum over the heads in the program's own order."""
    assert have.shape == want.shape and have.dtype == want.dtype == jnp.float32
    assert np.abs(np.asarray(have) - np.asarray(want)).max() <= 1e-6 * np.abs(want).max()


def _latent_same(draw: str, have, want):
    """``(., ., dk_r)``: the first two as the draw allows, the key's sum to
    float32 rounding."""
    SAME[draw](have[:2], want[:2])
    _assert_the_keys_sum_is_close(have[2], want[2])


#: 20: a row shorter than ``ROWS``, one block; 48: one block longer than the
#: row; 80 in blocks of 32: two and a half; 200: blocks of 128 from the shapes
LATENT_ROWS = [(20, None, "exact"), (48, None, "exact"), (80, 32, "exact"), (200, None, "exact"),
               (80, 32, "real")]


@pytest.mark.parametrize("t,block,draw", LATENT_ROWS,
                         ids=["20-exact", "48-exact", "80-exact", "200-exact", "80-real"])
@pytest.mark.parametrize("case", list(LATENT))
def test_the_latent_program_writes_the_plain_expressions_operands(case, t, block, draw):
    """``[q_nope | turn(q_rope)]`` scaled, ``[k_nope | turn(k_r)]`` with the
    one key in every head, and ``v`` out of ``W_kvb``'s heads."""
    (q, kv, k_r, cos, sin), h = _latent_inputs(case, t, draw)
    _, dn, dr, dv = LATENT[case]
    have = jax.jit(lambda *a: rl.latent_rope_layout(*a, h, "bfloat16", True, block))(
        q, kv, k_r, cos, sin)
    want = jax.jit(lambda *a: rl.latent_rope_layout_plain(*a, h, "bfloat16"))(q, kv, k_r, cos, sin)
    assert [x.dtype for x in have] == [bf16] * 3
    assert [x.shape for x in have] == [(2, h, t, dn + dr), (2, h, t, dn + dr), (2, h, t, dv)]
    SAME[draw](have, want)
    # roundings and no sum: v, the lanes of k without a position, and of q to the scale's
    _assert_equal([have[2], have[1][..., :dn]], [want[2], want[1][..., :dn]])
    # the key is every head's
    assert all(np.array_equal(have[1][:, 0, :, dn:], have[1][:, a, :, dn:]) for a in range(h))


def _latent_parents_transpose(cos, sin, h: int, dn: int, dv: int, dtype):
    """What the parent's passes make of the backward program's float32
    heads-first ``dq``, ``dk``, ``dv``: the scale, the cast and the transpose
    (``ops/sparse_attention._bwd``), then the transposes of the casts, the
    concatenations, the broadcast (a sum over the heads) and the rotations
    (``latent_moe._attention`` at the parent), to the cotangents of ``W_qb``'s,
    ``W_kvb``'s and the rotary key's outputs."""
    dr = cos.shape[1]

    def run(dq, dk, dv_):
        given = ((_hf(dq) * (dn + dr) ** -0.5).astype(dtype), _hf(dk).astype(dtype),
                 _hf(dv_).astype(dtype))
        b, t = given[0].shape[:2]

        def operands(q, kv, k_r):
            return tuple(x.astype(dtype) for x in rl.latent_operands(q, kv, k_r, cos, sin, h))

        at = [jnp.zeros((b, t, n), jnp.float32) for n in (h * (dn + dr), h * (dn + dv), dr)]
        return jax.vjp(operands, *at)[1](given)

    return run


@pytest.mark.parametrize("draw,dtype", [("exact", "bfloat16"), ("real", "bfloat16"),
                                        ("real", "float32")])
@pytest.mark.parametrize("case,t,block", [("cell", 48, None), ("one-head", 20, None),
                                          ("toy", 80, 32), ("narrow-values", 200, None)],
                         ids=lambda v: str(v))
def test_the_latent_transpose_takes_the_backward_programs_cotangents_as_the_parents_passes_did(
        case, t, block, draw, dtype):
    """``dq`` and ``dkv`` are roundings and, on the rotary lanes, a sum of two
    products; ``dk_r`` is the sum over the heads of the rounded rotary part of
    ``dk``, turned back."""
    (q, kv, k_r, cos, sin), h = _latent_inputs(case, t, draw)
    _, dn, dr, dv = LATENT[case]
    rng = np.random.default_rng(1)
    cts = tuple(jnp.asarray(rng.standard_normal((2, h, t, w), dtype=np.float32))
                for w in (dn + dr, dn + dr, dv))
    have = jax.jit(lambda *c: rl._latent_bwd(h, dtype, True, block, (cos, sin), c))(*cts)
    want = jax.jit(_latent_parents_transpose(cos, sin, h, dn, dv, jnp.dtype(dtype)))(*cts)
    assert have[3:] == (None, None)                    # the table has no cotangent
    _latent_same(draw, have[:3], want)
    # a rounding and no sum: all of dkv, and the lanes of q without a position
    _assert_equal([have[1], have[0].reshape(2, t, h, -1)[..., :dn]],
                  [want[1], want[0].reshape(2, t, h, -1)[..., :dn]])
    if h == 1:                                         # one head: no sum in dk_r either
        SAME[draw](have[2:3], want[2:])


@pytest.mark.parametrize("draw", list(SAME))
@pytest.mark.parametrize("case,t,block", [("cell", 48, None), ("one-head", 80, 32),
                                          ("toy", 80, 32), ("narrow-values", 20, None)],
                         ids=lambda v: str(v))
def test_the_latent_vjp_is_the_plain_expressions(case, t, block, draw):
    """``jax.vjp`` of both, cotangents in the compute dtype."""
    (q, kv, k_r, cos, sin), h = _latent_inputs(case, t, draw)

    def pulled(layout):
        def run(q, kv, k_r, cts):
            return jax.vjp(lambda q, kv, k_r: layout(q, kv, k_r, cos, sin), q, kv, k_r)[1](cts)
        return jax.jit(run)

    program = lambda *a: rl.latent_rope_layout(*a, h, "bfloat16", True, block)  # noqa: E731
    plain = lambda *a: rl.latent_rope_layout_plain(*a, h, "bfloat16")  # noqa: E731
    cts = _cotangents(jax.eval_shape(plain, q, kv, k_r, cos, sin), bf16, draw)
    have, want = pulled(program)(q, kv, k_r, cts), pulled(plain)(q, kv, k_r, cts)
    _latent_same(draw, have, want)
    _assert_equal(have[1:2], want[1:2])                # dkv: roundings alone


@pytest.mark.parametrize("case", ["one-head", "toy", "narrow-values"])
def test_the_latent_ops_together_are_the_path_they_replace(case):
    """From the three projections' float32 outputs to the attention's output
    and back to their cotangents: the operands' programs and the heads-first
    attention against what ``latent_moe._attention`` did at the parent (the
    rotations, the concatenations, the casts, ``causal_attention``), on an
    exact draw."""
    t = 64
    (q, kv, k_r, cos, sin), h = _latent_inputs(case, t, "exact")
    dv = LATENT[case][3]
    g_out = jnp.asarray(np.random.default_rng(5).standard_normal((2, t, h, dv), dtype=np.float32),
                        bf16)

    def new(q, kv, k_r):
        ops = rl.latent_rope_layout(q, kv, k_r, cos, sin, h, "bfloat16", True)
        return sa.heads_first_attention(*ops, None, 32, 32, True)

    def old(q, kv, k_r):
        q, k, v = (x.astype(bf16) for x in rl.latent_operands(q, kv, k_r, cos, sin, h))
        return sa.causal_attention(q, k, v, 32, 32, True)

    (out, dq, dkv, dk_r), (want_out, want_dq, want_dkv, want_dk_r) = (
        jax.jit(lambda q, kv, k_r, f=f: (lambda out, pull: (out, *pull(g_out)))(
            *jax.vjp(f, q, kv, k_r)))(q, kv, k_r) for f in (new, old))
    _assert_equal([out, dq, dkv], [want_out, want_dq, want_dkv])
    _assert_the_keys_sum_is_close(dk_r, want_dk_r)


def test_the_latent_tile_comes_from_the_shapes_and_the_fit_says_it():
    """The cell's layer at 8,192 positions: four heads a step, half of what the
    attention programs take (six lane tiles of q, eight of kv); a head that is
    not whole lane tiles takes all the heads a step."""
    bt, lanes, s = rl.latent_tile_of(32, 128, 64, 128, 8192)
    assert (bt, lanes, s) == (256, 768, 4)
    held = 2 * 4 * bt * (s * (256 + 256 + 128) + 768 + 1024 + 128)    # float32, two buffers
    assert held <= rl.BLOCK_VMEM_BYTES < 2 * held
    assert rl.latent_tile_of(4, 16, 8, 16, 200) == (128, 96, 4)
    assert rl.latent_tile_of(4, 16, 8, 16, 20) == (20, 96, 4)        # a row shorter than a chunk
    assert rl.latent_tile_of(1, 128, 64, 128, 64) == (64, 192, 1)    # one head: not whole tiles
    config = latent_moe.LatentMoEConfig(
        num_items=50, max_len=8192, num_heads=32, nope_dim=128, rope_dim=64, value_dim=128)
    on_chip, on_host = (fit_attrs(config, 4, 8, 2, platform) for platform in ("tpu", "cpu"))
    assert (on_chip["rope_block"], on_host["rope_block"]) == ("256x768", "plain")
