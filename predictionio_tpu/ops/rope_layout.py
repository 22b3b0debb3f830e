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

**Latent attention's operands** (:func:`latent_rope_layout`, its twin
:func:`latent_rope_layout_plain` on :func:`latent_operands`, its tile
:func:`latent_tile_of`) are a pair
of programs of their own, because every need differs: for the projections'
float32 outputs ``q`` ``[B, T, H x (dn + dr)]`` (a head's ``[q_nope |
q_rope]``), ``kv`` ``[B, T, H x (dn + dv)]`` (a head's ``[k_nope | v]``), the
one rotary key ``k_r`` ``[B, T, dr]`` all heads share and a table ``[T, dr]``,
with ``turn`` the rotation of interleaved pairs ``(2i, 2i + 1)``
(:func:`rotate_pairs`):

    qs = r(r([q_nope | turn(q_rope)]) (dn + dr) ** -0.5)   [B, H, T, dn + dr]
    k  = r([k_nope | turn(k_r)])                           [B, H, T, dn + dr]
    v  = r(v)                                              [B, H, T, dv]

- **forward**, grid ``(B, T / bt, H / s)``: a step holds ``bt`` positions of
  ``s`` heads' lanes of ``q`` and ``kv`` and the key's ``dr``; a lane's partner
  is lane ``j ^ 1`` (a rotation by one lane either way and a choice by
  parity, the sign on ``sin`` by parity); the key is turned and rounded once
  a chunk of rows and laid beside every head's ``k_nope``;
- **backward**, the same grid, the head axis innermost and ``arbitrary``: the
  cotangents' roundings and the rotation's transpose as above, a head's
  ``[dq_nope | dq_rope]`` and ``[dk_nope | dv]`` written where the projections'
  backward products read them, and ``dk_r`` the sum over the heads of the
  rounded rotary part of ``dk``: a step's heads summed in registers and added
  into a block that stays in VMEM over the head axis, turned back at its last
  step. That float32 sum's order is the program's own, so ``dk_r`` equals the
  twin's to float32 rounding, everything else to the bit.
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


def _cotangent(g, dtype, scale=None):
    """A cotangent as the cast's transpose hands it on: scaled where the
    operand was, rounded to the compute dtype, float32 again."""
    g = g.astype(jnp.float32)
    return _rounded(g if scale is None else g * scale, dtype).astype(jnp.float32)


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

        for h in range(dqs_ref.shape[0]):
            dq_ref[at, h * d:(h + 1) * d] = _over_the_turned_lanes(
                _cotangent(dqs_ref[h, at, :], dtype, scale), cos.shape[1], back)
        for h in range(dk_ref.shape[0]):
            dko_ref[at, h * d:(h + 1) * d] = _over_the_turned_lanes(
                _cotangent(dk_ref[h, at, :], dtype), cos.shape[1], back)
            dvo_ref[at, h * dv:(h + 1) * dv] = _cotangent(dv_ref[h, at, :], dtype)

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


# ---- latent attention's operands: pairs interleaved, one rotary key --------------

def rotate_pairs(x, cos, sin):
    """Rotary positions on ``x`` [B, T, H, dim], pairs interleaved: ``(x[2i],
    x[2i + 1])`` turns by the position's angle ``i`` (``cos``, ``sin``
    ``[T, dim]``, a pair's two lanes the same). The pair's other member comes
    by a roll either way and a choice by parity: no strided access."""
    even = jnp.arange(x.shape[-1]) % 2 == 0
    turned = jnp.where(even, -jnp.roll(x, -1, axis=-1), jnp.roll(x, 1, axis=-1))
    return x * cos[None, :, None, :] + turned * sin[None, :, None, :]


def latent_operands(q, kv, k_r, cos, sin, heads: int):
    """Latent attention's ``(q, k, v)`` ``[B, T, H, .]`` in float32, positions
    turned and nothing else: from ``q`` ``[B, T, H x (dn + dr)]`` a head's
    ``[q_nope | q_rope]``, ``kv`` ``[B, T, H x (dn + dv)]`` a head's
    ``[k_nope | v]`` and ``k_r`` ``[B, T, dr]`` every head's rotary key."""
    (b, t, _), dr = q.shape, cos.shape[1]
    q, kv = (x.reshape(b, t, heads, -1) for x in (q, kv))
    dn = q.shape[3] - dr
    q = jnp.concatenate([q[..., :dn], rotate_pairs(q[..., dn:], cos, sin)], axis=-1)
    k_r = rotate_pairs(k_r[:, :, None, :], cos, sin)      # one key a position, every head's
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (b, t, heads, dr))], axis=-1)
    return q, k, kv[..., dn:]


def latent_rope_layout_plain(q, kv, k_r, cos, sin, heads: int, dtype):
    """:func:`latent_operands` as the attention programs read them, in XLA's
    own operations: ``(qs, k, v)`` heads-first in ``dtype``, ``qs`` scaled."""
    q, k, v = (x.astype(dtype) for x in latent_operands(q, kv, k_r, cos, sin, heads))
    qs = (q.astype(jnp.float32) * q.shape[3] ** -0.5).astype(dtype)
    return tuple(jnp.transpose(x, (0, 2, 1, 3)) for x in (qs, k, v))


def latent_tile_of(heads: int, dn: int, dr: int, dv: int, t: int) -> tuple[int, int, int]:
    """``(bt, lanes, s)`` as :func:`tile_of` gives them, for the latent pair.
    ``s`` heads a step: half of what the attention programs take (four of 32
    ungrouped heads: a program's body is unrolled over its step's heads and a
    step of the model traces and lowers it nine times, and four heads move
    their bytes as fast as eight, ``PERF.md`` section 6, PR 49), where ``s``
    heads of ``q`` and of ``kv`` are whole lane tiles (a head of 128 + 64 is
    one and a half), else all of them; ``bt`` by the backward program's blocks,
    a width held in VMEM as whole lane tiles."""
    s = heads_per_step(heads, 2)
    if s * (dn + dr) % 128 or s * (dn + dv) % 128:
        s = heads
    lanes = s * (dn + dr)
    if t <= ROWS:
        return t, lanes, s
    held = lambda w: w + -w % 128  # noqa: E731
    # a position of dqs, dk, dv heads-first in, of dq, dkv and dk_r out, float32
    row = 4 * (s * (2 * held(dn + dr) + held(dv)) + held(lanes) + held(s * (dn + dv)) + held(dr))
    bt = ROWS
    while 2 * row * (2 * bt) <= BLOCK_VMEM_BYTES and 2 * bt <= t + -t % ROWS:
        bt *= 2
    return bt, lanes, s


def _pair_signed(sin):
    """``sin`` with the sign of a lane's partner: lane ``2i`` takes
    ``-x[2i + 1]``, lane ``2i + 1`` ``x[2i]``."""
    return jnp.where(_lane(sin.shape) % 2 == 0, -sin, sin)


def _pair_partners(x):
    """Every lane's partner of an interleaved pair: lane ``j`` of ``x``
    ``[R, W]`` takes lane ``j ^ 1`` (``W`` even, so no lane reads past an end)."""
    w = x.shape[1]
    ahead, back = pltpu.roll(x, w - 1, 1), pltpu.roll(x, 1, 1)   # x[j + 1], x[j - 1]
    return jnp.where(_lane(x.shape) % 2 == 0, ahead, back)


def _latent_fwd_kernel(q_ref, kv_ref, kr_ref, cos_ref, sin_ref, qs_ref, ko_ref, vo_ref,
                       *, scale: float):
    (s, _, d), dv, dtype = qs_ref.shape, vo_ref.shape[2], qs_ref.dtype
    dn = d - kr_ref.shape[1]

    def walk(at):
        cos, sin = cos_ref[at, :], _pair_signed(sin_ref[at, :])
        turn = lambda x: x * cos + _pair_partners(x) * sin  # noqa: E731
        key = _rounded(turn(kr_ref[at, :]), dtype)
        for h in range(s):
            at_q, at_kv = h * d, h * (dn + dv)
            qs_ref[h, at, :dn] = _rounded(q_ref[at, at_q:at_q + dn], dtype, scale)
            qs_ref[h, at, dn:] = _rounded(turn(q_ref[at, at_q + dn:at_q + d]), dtype, scale)
            ko_ref[h, at, :dn] = _rounded(kv_ref[at, at_kv:at_kv + dn], dtype)
            ko_ref[h, at, dn:] = key
            vo_ref[h, at, :] = _rounded(kv_ref[at, at_kv + dn:at_kv + dn + dv], dtype)

    _chunks(q_ref.shape[0], walk)


def _latent_bwd_kernel(dqs_ref, dk_ref, dv_ref, cos_ref, sin_ref, dq_ref, dkv_ref, dkr_ref,
                       *, scale: float, dtype):
    (s, _, d), dv = dqs_ref.shape, dv_ref.shape[2]
    dn = d - dkr_ref.shape[1]
    step, last = pl.program_id(2), pl.num_programs(2) - 1

    def walk(at):
        cos, sin = cos_ref[at, :], _pair_signed(sin_ref[at, :])
        back = lambda g: g * cos + _pair_partners(g * sin)  # noqa: E731
        # the key is every head's: its cotangent is the heads' sum, this step's
        # added to the steps' before it, which the block holds
        shared = jnp.where(step == 0, 0.0, dkr_ref[at, :])
        for h in range(s):
            at_q, at_kv = h * d, h * (dn + dv)
            dq_ref[at, at_q:at_q + dn] = _cotangent(dqs_ref[h, at, :dn], dtype, scale)
            dq_ref[at, at_q + dn:at_q + d] = back(_cotangent(dqs_ref[h, at, dn:], dtype, scale))
            dkv_ref[at, at_kv:at_kv + dn] = _cotangent(dk_ref[h, at, :dn], dtype)
            dkv_ref[at, at_kv + dn:at_kv + dn + dv] = _cotangent(dv_ref[h, at, :], dtype)
            shared = shared + _cotangent(dk_ref[h, at, dn:], dtype)
        dkr_ref[at, :] = jnp.where(step == last, back(shared), shared)

    _chunks(dq_ref.shape[0], walk)


def _latent_specs(heads: int, dn: int, dr: int, dv: int, t: int, block):
    """``(grid, positions-first, heads-first, table)`` of the latent pair: the
    grid after the batch, the blocks of ``q``, ``kv``, ``k_r`` and of ``qs``,
    ``k``, ``v``. The key's block and the table's stay over the head axis."""
    bt, lanes, s = latent_tile_of(heads, dn, dr, dv, t)
    bt = block or bt
    flat = [pl.BlockSpec((None, bt, lanes), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((None, bt, s * (dn + dv)), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((None, bt, dr), lambda b, i, j: (b, i, 0))]
    first = [pl.BlockSpec((None, s, bt, width), lambda b, i, j: (b, j, i, 0))
             for width in (dn + dr, dn + dr, dv)]
    table = pl.BlockSpec((bt, dr), lambda b, i, j: (i, 0))
    return (-(-t // bt), heads // s), flat, first, table


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def latent_rope_layout(q, kv, k_r, cos, sin, heads: int, dtype, interpret=False, block=None):
    """The Pallas form of :func:`latent_rope_layout_plain`, with its
    transpose. ``block`` as :func:`rope_layout`'s."""
    return _latent_fwd(q, kv, k_r, cos, sin, heads, dtype, interpret, block)[0]


def _latent_fwd(q, kv, k_r, cos, sin, heads, dtype, interpret, block):
    (b, t, _), dr, dtype = q.shape, cos.shape[1], jnp.dtype(dtype)
    dn = q.shape[2] // heads - dr
    dv = kv.shape[2] // heads - dn
    grid, flat, first, table = _latent_specs(heads, dn, dr, dv, t, block)
    out = pl.pallas_call(
        functools.partial(_latent_fwd_kernel, scale=(dn + dr) ** -0.5),
        grid=(b,) + grid,
        in_specs=flat + [table, table],
        out_specs=first,
        out_shape=[jax.ShapeDtypeStruct((b, heads, t, width), dtype)
                   for width in (dn + dr, dn + dr, dv)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
    )(q, kv, k_r, cos, sin)
    return tuple(out), (cos, sin)


def _latent_bwd(heads, dtype, interpret, block, res, cts):
    (cos, sin), (b, _, t, d), dv = res, cts[0].shape, cts[2].shape[3]
    dr = cos.shape[1]
    grid, flat, first, table = _latent_specs(heads, d - dr, dr, dv, t, block)
    grads = pl.pallas_call(
        functools.partial(_latent_bwd_kernel, scale=d ** -0.5, dtype=jnp.dtype(dtype)),
        grid=(b,) + grid,
        in_specs=first + [table, table],
        out_specs=flat,
        out_shape=[jax.ShapeDtypeStruct((b, t, n), jnp.float32)
                   for n in (heads * d, heads * (d - dr + dv), dr)],
        compiler_params=pltpu.CompilerParams(**{
            **_PARAMS, "dimension_semantics": ("parallel", "parallel", "arbitrary")}),
        interpret=interpret,
    )(*cts, cos, sin)
    return (*grads, None, None)


latent_rope_layout.defvjp(_latent_fwd, _latent_bwd)
