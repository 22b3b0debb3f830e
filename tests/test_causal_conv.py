"""The causal conv's two Pallas programs (``ops/causal_conv.py``), interpreted,
against the plain expression: the forward pass and the gradients of ``x`` and
``weight`` on rows no tile divides, one and several blocks of channels, two
widths of the conv, the channels split into one, two and three arrays, rows
with empty positions at their start and in their middle, and a cotangent that
reaches a tile only through the rows after it; the tile comes from the shapes
and keeps the blocks inside the budget."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import causal_conv as cc


def _inputs(t: int, widths, taps: int, seed: int = 0, rows: int = 2, dtypes=None):
    rng = np.random.default_rng(seed)
    c = sum(widths)
    x = rng.standard_normal((rows, t, c)).astype(np.float32)
    weight = rng.uniform(-0.5, 0.5, (c, taps)).astype(np.float32)
    real = np.ones((rows, t), bool)
    real[0, :5] = False                      # an empty start
    real[-1, t // 2:t // 2 + 7] = False      # a hole in the middle, over a chunk's edge
    dys = tuple(jnp.asarray(rng.standard_normal((rows, t, w)).astype(np.float32), dtype)
                for w, dtype in zip(widths, dtypes or ("float32",) * len(widths)))
    return jnp.asarray(x), jnp.asarray(weight), jnp.asarray(real), dys


def _both(x, weight, real, dys):
    """``(*ys, dx, dw)`` of the programs and of the plain expression."""
    split = tuple(dy.shape[-1] for dy in dys), tuple(dy.dtype.name for dy in dys)
    out = []
    for conv in (lambda x, w: cc.causal_conv_silu(x, w, real, *split, True),
                 lambda x, w: cc.causal_conv_silu_plain(x, w, real, *split)):
        ys, pull = jax.vjp(conv, x, weight)
        out.append((*ys, *pull(dys)))
    return out


def _assert_equal_to_rounding(have, want):
    """To float32's rounding; an array in bfloat16 to one step of its own."""
    assert len(have) == len(want)
    for name, (a, b) in enumerate(zip(have, want)):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        step = 2e-6 if a.dtype == jnp.float32 else 2.0 ** -7
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= step * max(np.abs(b).max(), 1.0), name


#: positions (80: three chunks, a tile of 64 and a part of one; 200: a tile of
#: 128 and 96 rows of the next, 24 of them padding) by the widths of the split
#: and the block of channels they give: a toy's q, k, v (no lane width divides
#: them: a step holds all 96 channels), one array of one block, q, k, v of one,
#: one and two blocks of 128, and of two, two and four blocks of 256
SPLITS = {(16, 16, 64): 96, (256,): 256, (128, 128, 256): 128, (512, 512, 1024): 512,
          (256, 768): 256}


@pytest.mark.parametrize("taps", [2, 4])
@pytest.mark.parametrize("widths", list(SPLITS), ids=lambda w: "+".join(map(str, w)))
@pytest.mark.parametrize("t", [80, 200])
def test_the_programs_match_the_plain_expression_forward_and_gradients(t, widths, taps):
    bt, bc = cc.block_of(t, widths)
    assert t % bt and bc == SPLITS[widths]
    have, want = _both(*_inputs(t, widths, taps, seed=t + sum(widths) + taps))
    _assert_equal_to_rounding(have, want)
    empty = np.asarray(have[-2])[0, :5]
    assert not empty.any()                   # an empty position's input moves nothing


@pytest.mark.parametrize("widths", [(16, 16, 64), (128, 128, 256)],
                         ids=lambda w: "+".join(map(str, w)))
def test_an_array_of_the_split_leaves_in_the_dtype_its_reader_takes(widths):
    """As the hybrid backbone asks with bfloat16 matmul inputs: q and k in
    float32, v in bfloat16, its cotangent in bfloat16 too, read with the 16
    rows after a tile (a bfloat16 sublane tile) of which 8 count."""
    dtypes = ("float32", "float32", "bfloat16")
    have, want = _both(*_inputs(200, widths, 4, seed=3, dtypes=dtypes))
    assert [a.dtype.name for a in have[:3]] == list(dtypes)
    _assert_equal_to_rounding(have, want)
    # one step of bfloat16 at most, and that where float32's rounding of the silu decides
    v, plain = (np.asarray(a[2], np.float32) for a in (have, want))
    assert (v != plain).mean() < 1e-3


@pytest.mark.parametrize("taps", [2, 4, 8])
def test_a_cotangent_in_a_tiles_last_rows_reaches_the_tile_before_and_no_further(taps):
    """A tile of 64 rows and half of one; the cotangent is zero but for the first rows of
    the second tile and of a chunk: ``dx`` in the rows before them comes
    through the rows a step holds after its tile, or after its chunk."""
    x, weight, real, dys = _inputs(96, (128, 256), taps, seed=taps)
    assert cc.block_of(96, (128, 256)) == (64, 128)
    at = np.zeros((2, 96, 1), bool)
    at[:, 64:66] = at[:, 32:33] = True
    dys = tuple(jnp.where(at, dy, 0.0) for dy in dys)
    have, want = _both(x, weight, jnp.ones_like(real), dys)
    _assert_equal_to_rounding(have, want)
    dx = np.asarray(have[-2])
    assert dx[:, 64 - (taps - 1):64].any() and not dx[:, 66:].any()
    assert not dx[:, :32 - (taps - 1)].any()


def test_the_tile_comes_from_the_shapes_and_keeps_the_blocks_inside_the_budget():
    cell = (2048, 2048, 4096)                # the hybrid cell's [2, 8192, 8192] as q, k, v
    bt, bc = cc.block_of(8192, cell)
    assert (bt, bc) == (1024, 512)
    # the backward program's blocks, double-buffered: x, dx and three dy; the rows
    # beside them, the mask's lane (held as 128), the taps and the partial dw
    tiles = 2 * 5 * bt * bc * 4
    beside = 2 * (5 * cc.HALO * bc + (bt + 2 * cc.HALO) * 128 + 2 * cc.HALO * bc) * 4
    assert tiles <= cc.BLOCK_VMEM_BYTES < 2 * tiles
    assert tiles + beside < cc.VMEM_LIMIT_BYTES
    for t, widths in ((8192, cell), (2048, cell), (100, (16, 16, 64)), (31, (640,)),
                      (5000, (50_000, 50_000)), (8192, (128, 65_536))):
        bt, bc = cc.block_of(t, widths)
        assert bt % cc.ROWS == 0 and bt <= t + -t % cc.ROWS
        assert bc == sum(widths) or (bc % 128 == 0 and not any(w % bc for w in widths))
        assert bt == cc.ROWS or 2 * (2 + len(widths)) * bt * bc * 4 <= cc.BLOCK_VMEM_BYTES


def test_a_conv_wider_than_the_rows_a_step_holds_is_refused():
    x, weight, real, _ = _inputs(64, (128,), 9)
    with pytest.raises(ValueError, match="9 taps"):
        cc.causal_conv_silu(x, weight, real, (128,), None, True)
    with pytest.raises(ValueError, match="do not add up"):
        cc.causal_conv_silu(x[..., :4], weight[:4, :4], real, (128,), None, True)
