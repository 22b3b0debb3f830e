"""A depthwise causal convolution and its silu for TPU, one program a phase.

For ``x`` ``[B, T, C]`` float32, ``weight`` ``[C, K]`` and ``real`` ``[B, T]``
(a position that holds an event), with ``xm = where(real, x, 0)``:

    y[b, t, c] = silu(sum_i weight[c, i] xm[b, t - (K - 1) + i, c]),  zero before the row

and ``y`` handed back as the arrays its channels are split into (``widths``:
a linear mixer's q, k and v), each cast to the dtype its reader takes it in
(``dtypes``: v enters every product of the delta rule as bfloat16, so the
float32 copy of it need not exist). :func:`causal_conv_silu_plain` is that expression
as XLA works it (a padded copy, ``K`` shifted slices, the silu, the split:
separate passes over ``[B, T, C]``, and a transpose of each for the backward
pass), the path off the TPU and the tests' twin. :func:`causal_conv_silu` is
the same work as two Pallas programs, each reading its inputs once and writing
its outputs once:

- **forward**, grid ``(B, T / bt, C / bc)``: a step holds a ``[bt, bc]`` tile
  of ``x`` and the 8 rows before it (a second ``BlockSpec`` on the same array,
  clamped at the row's start and zeroed there: the padding), masks both, adds
  the ``K`` taps in the plain expression's order, applies the silu and writes
  the tile into the one of the split's arrays that its channels lie in;
- **backward**, the same grid: ``x`` with the 8 rows before and after, the
  cotangent of the array the step's channels lie in with the 8 rows after; the
  pre-activation is formed again in VMEM (the residual is ``x``, ``weight``
  and ``real``: what a ``jax.checkpoint`` of the plain expression keeps),
  ``dpre = dy silu'(pre)``, ``dx`` the transposed taps under the mask, and
  ``dw`` summed over the tile's rows, which leaves as a partial sum a
  ``(b, t-block)`` ``[B, T / bt, 8, C]`` that XLA adds up.

**The split costs no pass.** ``bc`` divides every width, so a block of
channels lies in one array of the split. Each array has its own ``BlockSpec``
whose block of channels is the step's, clamped into the array: while the steps
walk another array's channels its block index stands still, so nothing of it
is moved (an output block is written back when its index moves on, an input
block fetched when it does), and the step reads or writes the one it is in
under ``pl.when``. Where no lane width divides the widths (a toy's) a step
holds all the row's channels and every array's whole width.

Inside a step the tile is walked ``ROWS`` rows at a time, so that a chunk's
intermediates stay in registers. A shift along the rows is a sublane rotation
of the chunk with the 8 rows before (or after) it, aligned loads only. The
backward program walks the chunks last to first and carries the first 8 rows
of ``dpre`` to the chunk before, which needs them for its ``dx``.

Everything is worked in float32, as the plain expression; the taps are added
in its order, so the two differ by the rounding of the silu alone. An array
asked for in another dtype is cast as it is stored, and its cotangent, which
arrives in that dtype, as it is loaded. ``K <= 8``: the rows a step holds
beside its tile are one sublane tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from predictionio_tpu.utils.jax_compat import pallas as pl, pallas_tpu as pltpu

#: rows beside a tile that a step holds: one float32 sublane tile
HALO = 8
#: rows of a tile worked at once
ROWS = 32
#: what the backward program's tiles (``x``, ``dx`` and a ``dy`` of every array
#: of the split), double-buffered, may take of VMEM, and what a program may
#: hold in all
BLOCK_VMEM_BYTES = 24 << 20
VMEM_LIMIT_BYTES = 48 << 20


def _causal_conv(x, weight):
    """``y[b, t, c] = sum_i weight[c, i] x[b, t - (K - 1) + i, c]``, zero before
    the row: ``x`` [B, T, C], ``weight`` [C, K]."""
    t, width = x.shape[1], weight.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, i:i + t] * weight[:, i] for i in range(width))


def _bounds(widths) -> list[tuple[int, int]]:
    """The channels ``[lo, hi)`` of each array of the split."""
    edges = [sum(widths[:i]) for i in range(len(widths) + 1)]
    return list(zip(edges[:-1], edges[1:]))


def _dtypes(widths, dtypes):
    return tuple(jnp.dtype(d) for d in dtypes or ("float32",) * len(widths))


def causal_conv_silu_plain(x, weight, real, widths, dtypes=None):
    """The expression above in XLA's own operations. Kept for the backward
    pass: the masked input alone; the products and the silu are worked again."""
    y = jax.checkpoint(lambda x, w: jax.nn.silu(_causal_conv(x, w)))(
        jnp.where(real[..., None], x, 0.0), weight)
    return tuple(y[..., lo:hi].astype(dtype)
                 for (lo, hi), dtype in zip(_bounds(widths), _dtypes(widths, dtypes)))


def block_of(t: int, widths) -> tuple[int, int]:
    """``(bt, bc)``, the tile of both programs for a row of ``t`` positions
    whose channels are split into ``widths``, from the shapes alone: ``bc`` the
    most of 512, 256, 128 lanes that divide every width (all the channels
    where none does), ``bt`` the most rows, a power of two times ``ROWS``, that
    keep the backward program's tiles, double-buffered, within
    ``BLOCK_VMEM_BYTES`` and do not pass the row in whole chunks."""
    bc = next((lanes for lanes in (512, 256, 128) if not any(w % lanes for w in widths)),
              sum(widths))
    bt = ROWS
    while (2 * (2 + len(widths)) * (2 * bt) * bc * 4 <= BLOCK_VMEM_BYTES
           and 2 * bt <= t + -t % ROWS):
        bt *= 2
    return bt, bc


# ---- inside a step -------------------------------------------------------------

class _Part:
    """A step's channels in the arrays of the split: the block of the one array
    they lie in, or (a step that holds all the row's channels) every array's
    side by side."""

    def __init__(self, refs, bounds):
        self.refs, self.bounds = refs, bounds

    def load(self, rows, first=None):
        """The rows ``rows`` as float32, of them the ``first`` (a whole block of
        a 16-bit array is loaded and cast before any of it is taken)."""
        found = [ref[rows, :].astype(jnp.float32)[:first] for ref in self.refs]
        return found[0] if len(found) == 1 else jnp.concatenate(found, axis=1)

    def store(self, rows, value):
        for ref, (lo, hi) in zip(self.refs, self.bounds):
            ref[rows, :] = value[:, lo:hi].astype(ref.dtype)


def _in_its_part(bounds, bc: int, groups, work):
    """``work(*parts)``, a :class:`_Part` of each group of refs (one ref an
    array of the split), for the array this step's block of channels lies in."""
    if bounds[-1][1] == bc:
        work(*(_Part(refs, bounds) for refs in groups))
        return
    at = pl.program_id(2) * bc
    for o, (lo, hi) in enumerate(bounds):
        pl.when((at >= lo) & (at < hi))(
            lambda o=o: work(*(_Part(refs[o:o + 1], [(0, bc)]) for refs in groups)))


def _masked(x, real, held=True):
    return jnp.where((real > 0.0) & held, x, 0.0)


def _shifted(chunk, by: int):
    """``chunk`` a row's positions later: ``out[j] = chunk[j - by]``, the first
    ``by`` rows those that wrap."""
    return chunk if by % chunk.shape[0] == 0 else pltpu.roll(chunk, by % chunk.shape[0], 0)


def _pre(full, w_ref, taps: int):
    """``(pre, terms)`` for the rows of ``full`` past its first ``HALO``:
    ``terms[i]`` is the input ``K - 1 - i`` positions back."""
    terms = [_shifted(full, taps - 1 - i)[HALO:] for i in range(taps)]
    return sum(term * w_ref[i:i + 1, :] for i, term in enumerate(terms)), terms


def _chunk_of(x_ref, real_ref, before, at, first):
    """The masked rows ``[at - HALO, at + ROWS)`` of a tile, ``before`` the
    rows that lead the tile."""
    lead = pl.multiple_of(jnp.maximum(at - HALO, 0), HALO)
    prev = jnp.where(first, before,
                     _masked(x_ref[pl.ds(lead, HALO), :], real_ref[pl.ds(lead, HALO), :]))
    cur = _masked(x_ref[pl.ds(at, ROWS), :], real_ref[pl.ds(at, ROWS), :])
    return jnp.concatenate([prev, cur], axis=0)


def _fwd_kernel(x_ref, before_ref, real_ref, real_before_ref, w_ref, *y_refs,
                taps: int, bounds):
    before = _masked(before_ref[...], real_before_ref[...], pl.program_id(1) > 0)

    def walk(y):
        def chunk(n, carry):
            at = pl.multiple_of(n * ROWS, ROWS)
            pre, _ = _pre(_chunk_of(x_ref, real_ref, before, at, n == 0), w_ref, taps)
            y.store(pl.ds(at, ROWS), pre * jax.nn.sigmoid(pre))
            return carry

        jax.lax.fori_loop(0, x_ref.shape[0] // ROWS, chunk, None)

    _in_its_part(bounds, x_ref.shape[1], [y_refs], walk)


def _dsilu(pre):
    s = jax.nn.sigmoid(pre)
    return s * (1.0 + pre * (1.0 - s))


def _bwd_kernel(x_ref, before_ref, after_ref, real_ref, real_before_ref, real_after_ref,
                w_ref, *refs, taps: int, length: int, bounds):
    parts = len(bounds)
    dy_refs, dy_after_refs, (dx_ref, dw_ref) = refs[:parts], refs[parts:2 * parts], refs[2 * parts:]
    bt = x_ref.shape[0]
    tile = pl.program_id(1)
    inner = tile < pl.num_programs(1) - 1          # a tile with rows after it
    # the chunks of this tile inside the row (whole chunks: the row is padded to
    # ``ROWS``); past them the blocks hold anything, and nothing is read or kept
    chunks = jnp.minimum(bt, length - tile * bt) // ROWS
    before = _masked(before_ref[...], real_before_ref[...], tile > 0)
    # the pre-activation of the 8 rows after the tile reads the tile's last rows
    tail = _masked(x_ref[pl.ds(bt - HALO, HALO), :], real_ref[pl.ds(bt - HALO, HALO), :])
    after = _masked(after_ref[...], real_after_ref[...])
    pre_after, _ = _pre(jnp.concatenate([tail, after], axis=0), w_ref, taps)

    def walk(dy, dy_after):
        def chunk(n, carry):
            dp_next, sums = carry
            n = chunks - 1 - n
            at = pl.multiple_of(n * ROWS, ROWS)
            pre, terms = _pre(_chunk_of(x_ref, real_ref, before, at, n == 0), w_ref, taps)
            dp = dy.load(pl.ds(at, ROWS)) * _dsilu(pre)
            both = jnp.concatenate([dp, dp_next], axis=0)
            dx = sum(_shifted(both, -(taps - 1 - i))[:ROWS] * w_ref[i:i + 1, :]
                     for i in range(taps))
            dx_ref[pl.ds(at, ROWS), :] = _masked(dx, real_ref[pl.ds(at, ROWS), :])
            sums = tuple(
                total + sum((dp * term)[g * HALO:(g + 1) * HALO] for g in range(ROWS // HALO))
                for total, term in zip(sums, terms))
            return dp[:HALO], sums

        dp_after = jnp.where(inner, dy_after.load(slice(None), HALO) * _dsilu(pre_after), 0.0)
        zeros = jnp.zeros((HALO, x_ref.shape[1]), jnp.float32)
        _, sums = jax.lax.fori_loop(0, chunks, chunk, (dp_after, (zeros,) * taps))
        dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.float32)
        for i, total in enumerate(sums):
            dw_ref[i:i + 1, :] = total.sum(axis=0, keepdims=True)

    _in_its_part(bounds, x_ref.shape[1], [dy_refs, dy_after_refs], walk)


# ---- the two programs ----------------------------------------------------------

#: the blocks of channels in order: an array of the split that the steps are
#: not in keeps its block where it is
_PARAMS = dict(dimension_semantics=("parallel", "parallel", "arbitrary"),
               vmem_limit_bytes=VMEM_LIMIT_BYTES)


class _Blocks:
    """The ``BlockSpec`` s of a row of ``t`` positions (whole chunks) split
    into ``widths``: a tile of ``bt`` positions, or the ``HALO`` positions
    before or after it (blocks of the same array, clamped into the row), by
    ``bc`` channels of ``x``, the one lane of the mask, or an array of the
    split's block that the step's channels lie in, clamped into the array
    (the rows beside a tile are a sublane tile of the array's dtype: 16 of a
    bfloat16 one, of which the step reads the nearest 8)."""

    def __init__(self, t: int, widths, dtypes):
        self.t, self.widths, self.dtypes = t, widths, dtypes
        self.bt, self.bc = block_of(t, widths)
        self.bounds = _bounds(widths)
        self.grid = (-(-t // self.bt), sum(widths) // self.bc)

    def _spec(self, rows: str, lanes: int, block, itemsize: int = 4):
        halo = HALO * 4 // itemsize
        per, last = self.bt // halo, self.t // halo - 1
        height, row = {"tile": (self.bt, lambda i: i),
                       "before": (halo, lambda i: jnp.maximum(i * per - 1, 0)),
                       "after": (halo, lambda i: jnp.minimum((i + 1) * per, last))}[rows]
        return pl.BlockSpec((None, height, lanes), lambda b, i, j: (b, row(i), block(j)))

    def x(self, rows: str = "tile"):
        return self._spec(rows, self.bc, lambda j: j)

    def mask(self, rows: str = "tile"):
        return self._spec(rows, 1, lambda j: 0)

    def split(self, rows: str = "tile"):
        if self.grid[1] == 1:
            return [self._spec(rows, width, lambda j: 0, dtype.itemsize)
                    for width, dtype in zip(self.widths, self.dtypes)]
        return [self._spec(rows, self.bc, lambda j, lo=lo, hi=hi: jnp.clip(
                    j - lo // self.bc, 0, (hi - lo) // self.bc - 1), dtype.itemsize)
                for (lo, hi), dtype in zip(self.bounds, self.dtypes)]

    def taps(self):
        return pl.BlockSpec((HALO, self.bc), lambda b, i, j: (0, j))


def _operands(x, weight, real, widths, dtypes):
    """``x`` and the mask (float32 ``[B, T, 1]``) padded to whole chunks of
    positions, the weights as ``[8, C]`` rows of taps, and the blocks."""
    taps = weight.shape[1]
    if taps > HALO:
        raise ValueError(f"a conv of {taps} taps: the programs hold {HALO} rows beside a tile")
    if sum(widths) != x.shape[2]:
        raise ValueError(f"widths {tuple(widths)} do not add up to the {x.shape[2]} channels")
    pad = -x.shape[1] % ROWS
    x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
    real = jnp.pad(real, ((0, 0), (0, pad))).astype(jnp.float32)[..., None]
    w = jnp.pad(weight.astype(jnp.float32).T, ((0, HALO - taps), (0, 0)))
    return x, real, w, _Blocks(x.shape[1], widths, _dtypes(widths, dtypes))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def causal_conv_silu(x, weight, real, widths, dtypes=None, interpret=False):
    """The Pallas form of :func:`causal_conv_silu_plain`, with its transpose."""
    return _conv_fwd(x, weight, real, widths, dtypes, interpret)[0]


def _conv_fwd(x, weight, real, widths, dtypes, interpret):
    length = x.shape[1]
    xp, mask, w, blocks = _operands(x, weight, real, widths, dtypes)
    b, t, _ = xp.shape
    ys = pl.pallas_call(
        functools.partial(_fwd_kernel, taps=weight.shape[1], bounds=blocks.bounds),
        grid=(b,) + blocks.grid,
        in_specs=[blocks.x(), blocks.x("before"), blocks.mask(), blocks.mask("before"),
                  blocks.taps()],
        out_specs=blocks.split(),
        out_shape=[jax.ShapeDtypeStruct((b, t, width), dtype)
                   for width, dtype in zip(widths, blocks.dtypes)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
    )(xp, xp, mask, mask, w)
    return tuple(y[:, :length] for y in ys), (x, weight, real)


def _conv_bwd(widths, dtypes, interpret, res, dys):
    x, weight, real = res
    length, taps = x.shape[1], weight.shape[1]
    xp, mask, w, blocks = _operands(x, weight, real, widths, dtypes)
    b, t, c = xp.shape
    dys = [jnp.pad(dy, ((0, 0), (0, t - length), (0, 0))) for dy in dys]
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, length=t, bounds=blocks.bounds),
        grid=(b,) + blocks.grid,
        in_specs=[blocks.x(), blocks.x("before"), blocks.x("after"), blocks.mask(),
                  blocks.mask("before"), blocks.mask("after"), blocks.taps(),
                  *blocks.split(), *blocks.split("after")],
        out_specs=[blocks.x(), pl.BlockSpec((None, None, HALO, blocks.bc),
                                            lambda b, i, j: (b, i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(xp.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, blocks.grid[0], HALO, c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
    )(xp, xp, xp, mask, mask, mask, w, *dys, *dys)
    dw = dw.sum(axis=(0, 1))[:taps].T
    return dx[:, :length].astype(x.dtype), dw.astype(weight.dtype), None


causal_conv_silu.defvjp(_conv_fwd, _conv_bwd)
