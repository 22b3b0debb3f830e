"""The streamed attention programs' operands for TPU, written once: rotary
positions, the score's scale, the cast to the compute dtype and the heads-first
layout, one program a phase.

For the projections' float32 outputs ``q`` ``[B, T, H x D]``, ``k``
``[B, T, KV x D]``, ``v`` ``[B, T, KV x DV]`` and a table ``cos, sin``
``[T, rd]`` (``rd <= D``: the first ``rd`` dimensions of a head are turned in
the rotate-half convention, the rest pass), with ``r(x)`` a rounding to the
compute dtype:

    qs = r(r(rotate(q)) D ** -0.5)   [B, H, T, D]    (the scores' scale on q)
    k  = r(rotate(k))                [B, KV, T, D]
    v  = r(v)                        [B, KV, T, DV]

which is what ``ops/sparse_attention.py``'s programs read
(:func:`~predictionio_tpu.ops.sparse_attention.heads_first_attention`).
:func:`rope_layout_plain` is that expression as XLA works it, the tests' twin:
slices, a negation and a concatenate a rotation, two products and a sum in
float32, a cast, the scale in float32 and a second cast, a transpose, each a
pass or part of one over ``[B, T, H, D]``, and every one of them transposed
again for the backward pass. :func:`rope_layout` is the same work as two
Pallas programs, each reading its inputs once and writing its outputs once:

- **forward**, grid ``(B, T / bt, KV / s)``: a step holds ``bt`` positions of
  the lanes of ``s`` key-value heads and their ``s G`` query heads out of the
  ``[B, T, H x D]`` views (the transpose is the blocks' addresses: a head's
  ``[bt, D]`` leaves as its own block of ``[B, H, T, D]``) and walks them
  ``ROWS`` rows at a time in registers. The half-turn is a lane rotation: of a
  head's first ``W`` lanes (``rd`` in whole lane tiles) lane ``j < rd / 2``
  takes lane ``j + rd / 2`` and lane ``rd / 2 <= j < rd`` lane ``j - rd / 2``,
  one rotation where ``rd`` is the ``W`` lanes and two under a lane mask where
  it is fewer; the partner's sign goes onto ``sin`` (negated over the first
  half, once a chunk of rows), and past ``rd`` the table is padded with
  ``cos`` 1 and ``sin`` 0;
- **backward**, the same grid: from the cotangents of ``qs``, ``k``, ``v``
  heads-first, float32 as the attention's backward program writes them, to
  the cotangents of the three projections, float32 and positions-first:
  ``r(dqs D ** -0.5)``, ``r(dk)``, ``r(dv)`` (the roundings the casts'
  transposes make), then the rotation's transpose, ``g cos`` plus the
  half-turn of ``g sin`` with the same lanes and signs.

It rounds where the plain expression rounds and nowhere else: float32
rotations, the compute dtype's roundings where the casts and their transposes
stand. A compiler may work the plain expression more loosely than it is
written (XLA contracts a product and a sum into one rounding on a CPU, and,
allowed excess precision, skips a rounding to the compute dtype between two
operations it fuses), so the two are equal to the bit where it does neither
and to a rounding where it does; ``tests/test_rope_layout.py`` has both. The
tile comes from the shapes alone (:func:`tile_of`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from predictionio_tpu.ops.sparse_attention import heads_per_step
from predictionio_tpu.utils.jax_compat import pallas as pl, pallas_tpu as pltpu

#: rows of a tile worked at once: a head's ``[ROWS, 128]`` is four registers
ROWS = 32
#: what the backward program's blocks (float32 in and out), double-buffered,
#: may take of VMEM, and what a program may hold in all
BLOCK_VMEM_BYTES = 16 << 20
VMEM_LIMIT_BYTES = 48 << 20


def rotate(x, cos, sin):
    """Rotary positions in the rotate-half convention over the first ``rd`` of
    a head: ``x`` ``[B, T, H, hd]`` float32, ``cos``, ``sin`` ``[T, rd]``."""
    rd = cos.shape[-1]
    if rd < x.shape[-1]:
        return jnp.concatenate([rotate(x[..., :rd], cos, sin), x[..., rd:]], axis=-1)
    half = rd // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + turned * sin[None, :, None, :]


def rope_layout_plain(q, k, v, cos, sin, heads, dtype):
    """The expression above in XLA's own operations: ``(qs, k, v)`` heads-first
    in ``dtype``; ``heads`` is ``(H, KV)``."""
    (h, kv), (b, t, _) = heads, q.shape
    q, k, v = (x.reshape(b, t, n, -1) for x, n in ((q, h), (k, kv), (v, kv)))
    q, k = (rotate(x, cos, sin).astype(dtype) for x in (q, k))
    qs = (q.astype(jnp.float32) * q.shape[-1] ** -0.5).astype(dtype)
    return tuple(jnp.transpose(x, (0, 2, 1, 3)) for x in (qs, k, v.astype(dtype)))


def tile_of(heads, d: int, dv: int, t: int) -> tuple[int, int, int]:
    """``(bt, lanes, s)``: the positions a grid step of both programs holds,
    the lanes of ``q`` it holds them over and the key-value heads it works,
    from the shapes alone. ``s`` as the attention programs take them (as many
    as bring the step's query heads to eight; all of them where a head is not
    whole lane tiles, so that a block is the array's width); ``bt`` the most
    rows, a power of two times ``ROWS``, that keep the backward program's
    blocks, double-buffered, within ``BLOCK_VMEM_BYTES`` and do not pass the
    row (a row shorter than ``ROWS`` is one block)."""
    h, kv = heads
    s = heads_per_step(kv, h // kv) if d % 128 == 0 and dv % 128 == 0 else kv
    lanes = s * (h // kv) * d
    if t <= ROWS:
        return t, lanes, s
    bt = ROWS
    row = (4 + 4) * (lanes + s * (d + dv))          # a position of q, k, v in and out, float32
    while 2 * row * (2 * bt) <= BLOCK_VMEM_BYTES and 2 * bt <= t + -t % ROWS:
        bt *= 2
    return bt, lanes, s


def _tables(cos, sin, d: int):
    """``cos`` and ``sin`` over a head's first ``W`` lanes (``rd`` in whole
    lane tiles, or the head): past ``rd`` ``cos`` 1 and ``sin`` 0. A table
    that is whole lane tiles already is passed as it is."""
    rd = cos.shape[1]
    past = ((0, 0), (0, min(d, rd + -rd % 128) - rd))
    if not past[1][1]:
        return cos, sin
    return jnp.pad(cos, past, constant_values=1.0), jnp.pad(sin, past)


# ---- inside a step -------------------------------------------------------------

def _lane(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _signed(sin, rd: int):
    """``sin`` with the sign of a lane's partner: the first half of ``rd``
    takes ``-x[j + rd / 2]``, the second ``x[j - rd / 2]``."""
    return jnp.where(_lane(sin.shape) < rd // 2, -sin, sin)


def _partners(x, rd: int):
    """Every lane's partner of the half-turn: lane ``j < rd / 2`` of ``x``
    ``[R, W]`` takes lane ``j + rd / 2``, lane ``rd / 2 <= j < rd`` lane
    ``j - rd / 2``, a lane past ``rd`` nothing."""
    w, half = x.shape[1], rd // 2
    back = pltpu.roll(x, half, 1)                    # back[j] = x[j - half]
    if rd == w:
        return back                                  # and x[j + half]: the same lanes
    ahead = pltpu.roll(x, w - half, 1)               # ahead[j] = x[j + half]
    lane = _lane(x.shape)
    return jnp.where(lane < half, ahead, jnp.where(lane < rd, back, 0.0))


def _over_the_turned_lanes(x, w: int, work):
    """``work`` on the first ``w`` lanes of ``x``, the rest as they are."""
    if w == x.shape[1]:
        return work(x)
    return jnp.concatenate([work(x[:, :w]), x[:, w:]], axis=1)


def _rounded(x, dtype, scale=None):
    """``x`` as the compute dtype holds it; with a ``scale`` rounded, scaled
    in float32 and rounded again, as the plain expression does."""
    x = x.astype(dtype)
    return x if scale is None else (x.astype(jnp.float32) * scale).astype(dtype)


def _chunks(rows: int, work):
    """``work(at)`` for every chunk of ``ROWS`` rows of a block of ``rows``."""
    step = min(ROWS, rows)

    def chunk(n, carry):
        work(pl.ds(pl.multiple_of(n * step, step), step))
        return carry

    jax.lax.fori_loop(0, rows // step, chunk, None)


def _fwd_kernel(q_ref, k_ref, v_ref, cos_ref, sin_ref, qs_ref, ko_ref, vo_ref,
                *, rd: int, scale: float):
    d, dv, dtype = qs_ref.shape[2], vo_ref.shape[2], qs_ref.dtype

    def walk(at):
        cos, sin = cos_ref[at, :], _signed(sin_ref[at, :], rd)
        turn = lambda x: x * cos + _partners(x, rd) * sin  # noqa: E731
        for h in range(qs_ref.shape[0]):
            x = _over_the_turned_lanes(q_ref[at, h * d:(h + 1) * d], cos.shape[1], turn)
            qs_ref[h, at, :] = _rounded(x, dtype, scale)
        for h in range(ko_ref.shape[0]):
            x = _over_the_turned_lanes(k_ref[at, h * d:(h + 1) * d], cos.shape[1], turn)
            ko_ref[h, at, :] = _rounded(x, dtype)
            vo_ref[h, at, :] = _rounded(v_ref[at, h * dv:(h + 1) * dv], dtype)

    _chunks(q_ref.shape[0], walk)


def _bwd_kernel(dqs_ref, dk_ref, dv_ref, cos_ref, sin_ref, dq_ref, dko_ref, dvo_ref,
                *, rd: int, scale: float, dtype):
    d, dv = dqs_ref.shape[2], dv_ref.shape[2]

    def walk(at):
        cos, sin = cos_ref[at, :], _signed(sin_ref[at, :], rd)
        back = lambda g: g * cos + _partners(g * sin, rd)  # noqa: E731

        def cotangent(g, scale=None):
            g = g.astype(jnp.float32)
            return _rounded(g if scale is None else g * scale, dtype).astype(jnp.float32)

        for h in range(dqs_ref.shape[0]):
            dq_ref[at, h * d:(h + 1) * d] = _over_the_turned_lanes(
                cotangent(dqs_ref[h, at, :], scale), cos.shape[1], back)
        for h in range(dk_ref.shape[0]):
            dko_ref[at, h * d:(h + 1) * d] = _over_the_turned_lanes(
                cotangent(dk_ref[h, at, :]), cos.shape[1], back)
            dvo_ref[at, h * dv:(h + 1) * dv] = cotangent(dv_ref[h, at, :])

    _chunks(dq_ref.shape[0], walk)


# ---- the two programs ----------------------------------------------------------

def _specs(heads, d: int, dv: int, t: int, w: int, block):
    """``(grid, positions-first, heads-first, table)``: the grid of both
    programs after the batch, and the blocks of q, k, v in either layout."""
    h, kv = heads
    bt, lanes, s = tile_of(heads, d, dv, t)
    bt = block or bt
    flat = [pl.BlockSpec((None, bt, n), lambda b, i, j: (b, i, j))
            for n in (lanes, s * d, s * dv)]
    first = [pl.BlockSpec((None, n, bt, width), lambda b, i, j: (b, j, i, 0))
             for n, width in ((lanes // d, d), (s, d), (s, dv))]
    table = pl.BlockSpec((bt, w), lambda b, i, j: (i, 0))
    return (-(-t // bt), kv // s), flat, first, table


_PARAMS = dict(dimension_semantics=("parallel", "parallel", "parallel"),
               vmem_limit_bytes=VMEM_LIMIT_BYTES)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def rope_layout(q, k, v, cos, sin, heads, dtype, interpret=False, block=None):
    """The Pallas form of :func:`rope_layout_plain`, with its transpose.
    ``block``: the positions a step holds, for the tests (a multiple of
    ``ROWS``); None takes :func:`tile_of`'s."""
    return _layout_fwd(q, k, v, cos, sin, heads, dtype, interpret, block)[0]


def _layout_fwd(q, k, v, cos, sin, heads, dtype, interpret, block):
    (h, kv), (b, t, _) = heads, q.shape
    d, dv, dtype = q.shape[2] // h, v.shape[2] // kv, jnp.dtype(dtype)
    tables = _tables(cos, sin, d)
    grid, flat, first, table = _specs(heads, d, dv, t, tables[0].shape[1], block)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, rd=cos.shape[1], scale=d ** -0.5),
        grid=(b,) + grid,
        in_specs=flat + [table, table],
        out_specs=first,
        out_shape=[jax.ShapeDtypeStruct((b, n, t, width), dtype)
                   for n, width in ((h, d), (kv, d), (kv, dv))],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
    )(q, k, v, *tables)
    return tuple(out), (cos, sin)


def _layout_bwd(heads, dtype, interpret, block, res, cts):
    (h, kv), (cos, sin) = heads, res
    b, _, t, d = cts[0].shape
    dv = cts[2].shape[3]
    tables = _tables(cos, sin, d)
    grid, flat, first, table = _specs(heads, d, dv, t, tables[0].shape[1], block)
    grads = pl.pallas_call(
        functools.partial(_bwd_kernel, rd=cos.shape[1], scale=d ** -0.5, dtype=jnp.dtype(dtype)),
        grid=(b,) + grid,
        in_specs=first + [table, table],
        out_specs=flat,
        out_shape=[jax.ShapeDtypeStruct((b, t, n), jnp.float32)
                   for n in (h * d, kv * d, kv * dv)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
    )(*cts, *tables)
    return (*grads, None, None)


rope_layout.defvjp(_layout_fwd, _layout_bwd)
