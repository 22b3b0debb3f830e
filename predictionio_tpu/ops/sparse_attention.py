"""Learned sparse attention for TPU: an indexer's scores, the k-th largest of
every query's scores, and attention over the selected keys with grouped
key-value heads, K and V streamed a block at a time.

Three pieces, each a Pallas program with a plain ``jax.numpy`` twin (the twin
is what runs off the TPU and what the tests hold the programs to):

- :func:`index_scores`: ``I[b, t, s] = sum_j w[b, t, j] relu(qI[b, t, j] . kI[b, s])``
  over the causal triangle, a ``[BQ, BK]`` tile a grid step, tiles above the
  diagonal neither fetched nor computed (their values are never read);
- :func:`select_topk`: per query the ``topk`` causal keys with the largest
  score, as an int8 mask ``[B, T, T]`` (all of them while a query has at most
  ``topk``; ties go to the earlier position, as ``jax.lax.top_k`` breaks
  them). A program holds one block of queries' scores in VMEM as integers in
  the scores' order and finds each query's k-th largest by bisection on the
  integer's 32 bits (a count of ``>=`` a pass, no sort); ties at the threshold
  are cut at a position found by a second bisection, run only where a query
  of the block has more ties than places;
- :func:`sparse_attention`: softmax attention of ``q`` ``[B, T, H, D]`` on
  ``k``, ``v`` ``[B, T, KV, D]`` (``H // KV`` query heads a key-value head)
  over the pairs the mask selects, with the matching custom VJP: a forward
  program and one backward program. The grid of both is
  ``(B, KV, query blocks, key blocks)``: a step holds one block of K and V and
  one tile of the mask for all the query heads of the group, and blocks
  above the diagonal are neither fetched nor worked. Forward, the running
  (max, sum, acc) live in VMEM scratch across the key blocks. Backward, a
  tile's scores, probabilities, ``dp = do v'`` and ``ds = p (dp - delta)``
  are formed once and all three gradients taken from them, five dots a
  tile: ``dq`` is the query block's output, summed over its key blocks;
  ``dk`` and ``dv`` of the step's key-value heads are **held in VMEM over the
  whole row** (float32 ``[T, D]`` and ``[T, DV]`` a head, one buffer each), a
  tile adding into its key block's rows, and go to HBM once, when the grid
  moves to the next key-value heads. Every accumulator is added to in the
  order two separate programs would take: keys ascending for ``dq``, query
  blocks ascending and heads ascending inside for ``dk`` and ``dv``. Matmul
  inputs are bfloat16 with float32 accumulation; the softmax is float32.

The mask is the whole contract of which pairs count: causality is in it (the
selection takes causal keys only) and nothing else masks a pair.
:func:`causal_attention` is the same two programs with no mask operand:
every causal pair counts, and a tile's mask is made from its positions; with a
``window`` the pairs are those of the band ``t - window < s <= t`` and the
programs walk the band's tiles alone (the grid's key axis is as long as the
most key blocks a query block's band touches, counted from the band's first key
block and clamped at the diagonal's: a tile outside the band is neither fetched
nor worked). Rows are
left-aligned, so a padded position follows every event of its row and no
real query can select it; a padded query's output is never read.

``q`` and ``k`` share the width the scores are taken over, ``v`` and the output
the width that is carried: the two may differ (latent attention scores over
192 and carries 128). A grid step works ``heads_per_step`` key-value heads
with their query heads: one where a key-value head serves eight query heads or
more, several where each serves few (with a key and value of its own for every
query head, a step of one head would be mostly its own overhead, and its
per-query scalars one lane wide). The backward program takes its own count,
:func:`backward_heads_per_step`, from the shapes alone: the most of the same
candidates whose ``dk`` and ``dv`` over the row fit ``VMEM_LIMIT_BYTES`` beside
the step's blocks (:func:`backward_step_bytes`); at 8,192 positions one head
of 128 + 128 or 256 + 256, four ungrouped heads of 192 + 128 where the forward
program takes eight. Its tile of queries is twice the forward program's where
that still fits (:func:`backward_query_block`: 512 in the first two cases, 256
in the third). A row too long for one head's pair (past some 57,000 positions at
128 + 128, 26,000 at 256 + 256) is refused when the step is traced, with the
numbers.

Layout (see ``flash_attention.py`` for what the hardware asks): tensors are
``[B, H, T, D]`` at the Pallas boundary; per-query scalars (logsumexp, delta)
are ``[B, KV / S, T, S G]`` for ``S`` key-value heads a step of ``G`` query
heads each, the step's heads on the lanes, so a head's column is a static lane
slice. **Who writes the operands.** :func:`heads_first_attention` takes
``q`` (scaled by ``D ** -0.5``), ``k`` and ``v`` as the programs read them,
heads-first in the compute dtype, keeps them as its residuals and hands the
backward program's ``dq``, ``dk``, ``dv`` on as it wrote them (float32,
heads-first): the sparse, hybrid, window, compressed-convolution and latent
backbones call it on what ``ops/rope_layout.py``'s one program a phase wrote
from the projections' outputs (``rope_layout``; for the latent backbone
``latent_rope_layout``, which lays the one rotary key beside every head's
``k_nope``), so no XLA pass lies between the projections and the programs in
either direction. :func:`sparse_attention` and :func:`causal_attention` take
``[B, T, H, D]`` in the compute dtype and are the same programs behind XLA's
passes (the scale and its two roundings, three transposes, and all of them
again for the backward program and after it): no backbone calls them since
PR 49, the tests hold the heads-first entry point and the twins to them.
The output, its cotangent, ``delta`` and the logsumexp are XLA's either way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.utils.jax_compat import pallas as pl, pallas_tpu as pltpu

_NEG = -1e30
_INT_MIN = np.int32(-(2 ** 31))

BLOCK_Q = 256          # queries a tile
BLOCK_K = 512          # keys a tile
SELECT_ROWS = 128      # queries a select program holds
SELECT_CHUNK = 512     # keys a pass of its loops takes
VMEM_LIMIT_BYTES = 64 << 20
STEP_HEADS = 8         # query heads a grid step of the attention programs aims at


def _block(block: int, t: int) -> int:
    """The tile edge for a length ``t``: ``block``, or ``t`` where shorter."""
    if t <= block:
        return t
    if t % block:
        raise ValueError(f"length {t} is not a multiple of the block {block}")
    return block


def _dot(a, b, contract_a: int, contract_b: int):
    return jax.lax.dot_general(
        a, b, (((contract_a,), (contract_b,)), ((), ())),
        preferred_element_type=jnp.float32)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _last_key_block(qi, bq: int, bk: int):
    """The last key block that holds a causal pair with query block ``qi``."""
    return (qi * bq + bq - 1) // bk


def _first_key_block(qi, bq: int, bk: int, window):
    """The first key block that holds a pair of query block ``qi``'s band
    (``t - window < s``); 0 with no window. ``qi`` traced or a Python int."""
    if window is None:
        return 0
    reach = qi * bq - (window - 1)
    return (jnp.maximum(reach, 0) if isinstance(reach, jax.Array) else max(reach, 0)) // bk


def _key_block(qi, j, bq: int, bk: int, window):
    """The key block step ``j`` of the grid's key axis works for query block
    ``qi``: ``j`` itself with no window, else counted from the band's first."""
    return j if window is None else _first_key_block(qi, bq, bk, window) + j


def band_key_blocks(t: int, bq: int, bk: int, window) -> int:
    """The length of the grid's key axis: the most key blocks the pairs of one
    query block touch: the row's with no window, else the band's."""
    return max(_last_key_block(qi, bq, bk) - _first_key_block(qi, bq, bk, window) + 1
               for qi in range(t // bq))


def tiles_of(kv: int, g: int, d: int, dv: int, t: int, itemsize: int) -> tuple:
    """``((queries, keys) of the forward program's tile, of the backward
    program's)`` for a call with no mask operand on a row of ``t``, from the
    shapes alone, at the module's ``BLOCK_Q`` and ``BLOCK_K``."""
    bq, bk = _block(BLOCK_Q, t), _block(BLOCK_K, t)
    shape = (g, d, dv, t, itemsize, False)
    s = backward_heads_per_step(kv, *shape, bq, bk)
    return (bq, bk), (backward_query_block(s, *shape, bq, bk), bk)


def band_tiles(t: int, bq: int, bk: int, window) -> int:
    """The tiles a program works on a row of ``t`` a head: every query block's
    key blocks from its band's first to the diagonal's."""
    return sum(_last_key_block(qi, bq, bk) - _first_key_block(qi, bq, bk, window) + 1
               for qi in range(t // bq))


def band_pairs(t: int, window) -> int:
    """The pairs ``t' - window < s <= t'`` of a row of ``t`` positions (the
    causal triangle's with no window)."""
    w = t if window is None else min(window, t)
    return w * (w + 1) // 2 + (t - w) * w


# ---- index scores -----------------------------------------------------------

def index_scores_plain(q_idx, k_idx, w):
    """``[B, T, T]`` float32: q_idx [B, T, HI, DI], k_idx [B, T, DI], w
    [B, T, HI]; the matmul in the inputs' dtype, accumulated in float32.
    Every pair is scored; the selection reads the causal ones."""
    s = jnp.einsum("bthd,bsd->bhts", q_idx, k_idx, preferred_element_type=jnp.float32)
    return jnp.einsum("bhts,bth->bts", jnp.maximum(s, 0.0), w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _index_kernel(q_ref, k_ref, w_ref, out_ref, *, bq: int, bk: int):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki <= _last_key_block(qi, bq, bk))
    def _():
        k = k_ref[0]
        acc = jnp.zeros(out_ref.shape[1:], jnp.float32)
        for j in range(q_ref.shape[1]):
            acc = acc + w_ref[0, j] * jnp.maximum(_dot(q_ref[0, j], k, 1, 1), 0.0)
        out_ref[0] = acc


def index_scores(q_idx, k_idx, w, *, block_q=BLOCK_Q, block_k=BLOCK_K, interpret=False):
    """The Pallas form of :func:`index_scores_plain` on the causal tiles;
    what a tile above the diagonal holds is undefined."""
    b, t, hi, di = q_idx.shape
    bq, bk = _block(block_q, t), _block(block_k, t)
    q = jnp.transpose(q_idx, (0, 2, 1, 3))                               # [B, HI, T, DI]
    wt = jnp.transpose(w.astype(jnp.float32), (0, 2, 1))[..., None]      # [B, HI, T, 1]
    key = lambda bb, qi, ki: (bb, jnp.minimum(ki, _last_key_block(qi, bq, bk)), 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_index_kernel, bq=bq, bk=bk),
        grid=(b, t // bq, t // bk),
        in_specs=[
            pl.BlockSpec((1, hi, bq, di), lambda bb, qi, ki: (bb, 0, qi, 0)),
            pl.BlockSpec((1, bk, di), key),
            pl.BlockSpec((1, hi, bq, 1), lambda bb, qi, ki: (bb, 0, qi, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, bq, bk),
            lambda bb, qi, ki: (bb, qi, jnp.minimum(ki, _last_key_block(qi, bq, bk)))),
        out_shape=jax.ShapeDtypeStruct((b, t, t), jnp.float32),
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(q, k_idx, wt)


# ---- the k-th largest -------------------------------------------------------

def select_topk_plain(scores, topk: int):
    """int8 ``[B, T, T]``: 1 where key ``s <= t`` is among query ``t``'s
    ``topk`` largest scores (every causal key while ``t < topk``); of equal
    scores at the threshold the earlier positions are taken."""
    t = scores.shape[-1]
    pos = jnp.arange(t)
    causal = pos[None, :] <= pos[:, None]
    masked = jnp.where(causal, scores, -jnp.inf)
    if topk >= t:
        return jnp.broadcast_to(causal, scores.shape).astype(jnp.int8)
    kth = jax.lax.top_k(masked, topk)[0][..., -1:]
    above = masked > kth
    ties = (masked == kth) & causal
    places = topk - above.sum(axis=-1, keepdims=True)
    taken = ties & (jnp.cumsum(ties, axis=-1) <= places)
    return ((above | taken) & causal).astype(jnp.int8)


def _ordered(x):
    """float32 -> int32 with the same order (finite values and infinities)."""
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0.0, 0.0, x), jnp.int32)  # -0.0 is 0.0
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _select_kernel(scores_ref, mask_ref, keys_ref, *, topk: int, rows: int, chunk: int):
    qi = pl.program_id(1)
    t = scores_ref.shape[2]
    chunks = t // chunk
    used = jnp.minimum((qi * rows + rows - 1) // chunk + 1, chunks)   # hold causal keys
    q_pos = qi * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)

    def k_pos(c):
        return c * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)

    def fill(c, carry):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        keys_ref[:, at] = jnp.where(k_pos(c) <= q_pos, _ordered(scores_ref[0, :, at]),
                                    _INT_MIN)
        return carry

    jax.lax.fori_loop(0, used, fill, 0)

    def count(pred):
        """Per query, how many of its held keys satisfy ``pred(keys, c)``."""
        def body(c, n):
            at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
            hit = pred(keys_ref[:, at], c)
            return n + jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)

        return jax.lax.fori_loop(0, used, body, jnp.zeros((rows, 1), jnp.int32))

    # the largest integer v with at least topk keys >= v, a bit at a time from
    # the top; u is v's offset from INT_MIN, so v = u ^ INT_MIN as bits. With
    # fewer than topk causal keys no bit is ever set and v stays INT_MIN
    def bit(i, u):
        trial = u | (jnp.int32(1) << (31 - i))
        v = trial ^ _INT_MIN
        enough = count(lambda keys, c: keys >= v) >= topk
        return jnp.where(enough, trial, u)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros((rows, 1), jnp.int32)) ^ _INT_MIN
    def tie(keys, c):
        return (keys == kth) & (k_pos(c) <= q_pos)

    places = topk - count(lambda keys, c: keys > kth)       # for the keys == kth
    tied = count(tie)

    # where a query has more ties than places, the ties up to a position: the
    # largest p with fewer than ``places`` ties before p, again bit by bit
    def cut_of():
        bits = t.bit_length()

        def bit_p(i, p):
            trial = p | (jnp.int32(1) << (bits - 1 - i))
            before = count(lambda keys, c: tie(keys, c) & (k_pos(c) < trial))
            return jnp.where(before < places, trial, p)

        return jax.lax.fori_loop(0, bits, bit_p, jnp.zeros((rows, 1), jnp.int32))

    cut = jax.lax.cond(jnp.max(tied - places) > 0, cut_of,
                       lambda: jnp.full((rows, 1), t, jnp.int32))

    def emit(c, carry):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        keys, pos = keys_ref[:, at], k_pos(c)
        taken = ((keys > kth) | ((keys == kth) & (pos <= cut))) & (pos <= q_pos)
        mask_ref[0, :, at] = taken.astype(jnp.int32).astype(jnp.int8)
        return carry

    def blank(c, carry):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        mask_ref[0, :, at] = jnp.zeros((rows, chunk), jnp.int8)
        return carry

    jax.lax.fori_loop(0, used, emit, 0)
    jax.lax.fori_loop(used, chunks, blank, 0)


def select_topk(scores, topk: int, *, rows=SELECT_ROWS, chunk=SELECT_CHUNK,
                interpret=False):
    """The Pallas form of :func:`select_topk_plain`; reads the causal part of
    ``scores`` only."""
    b, t, _ = scores.shape
    rows, chunk = _block(rows, t), _block(chunk, t)
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, rows=rows, chunk=chunk),
        grid=(b, t // rows),
        in_specs=[pl.BlockSpec((1, rows, t), lambda bb, qi: (bb, qi, 0))],
        out_specs=pl.BlockSpec((1, rows, t), lambda bb, qi: (bb, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, t, t), jnp.int8),
        scratch_shapes=[pltpu.VMEM((rows, t), jnp.int32)],
        compiler_params=_params("parallel", "parallel"),
        interpret=interpret,
    )(scores)


# ---- attention over the selected pairs --------------------------------------

def sparse_attention_plain(q, k, v, mask):
    """Softmax attention over the pairs ``mask`` [B, T, T] selects: q
    [B, T, H, D], k [B, T, KV, D], v [B, T, KV, DV] -> [B, T, H, DV]; float32
    softmax, the matmuls in the inputs' dtype accumulated in float32."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, t, kv, h // kv, d)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k,
                   preferred_element_type=jnp.float32) * d ** -0.5
    on = (mask != 0)[:, None, None]
    s = jnp.where(on, s, _NEG)
    p = jnp.where(on, jnp.exp(s - s.max(axis=-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-20)
    out = jnp.einsum("bkgts,bskd->btkgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, t, h, -1).astype(q.dtype)


def _tile(mask_ref, qi, ki, bq: int, bk: int, window=None):
    """The pairs of tile ``(qi, ki)`` that count: the mask's, or with no mask
    operand the causal ones, of them with a window those of the band
    (``t - window < s <= t``), from the tile's positions."""
    if mask_ref is not None:
        return mask_ref[0].astype(jnp.int32) != 0
    key = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    query = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    return key <= query if window is None else (key <= query) & (key > query - window)


def _kernel(kernel, masked, **static):
    """``kernel`` with its static sizes bound; for a call with no mask operand
    (``masked`` empty) the mask's place, after q, k and v, holds None."""
    if masked:
        return functools.partial(kernel, **static)
    return lambda q_ref, k_ref, v_ref, *rest: kernel(q_ref, k_ref, v_ref, None, *rest, **static)


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, out_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, bq: int, bk: int, window=None):
    qi, step = pl.program_id(2), pl.program_id(3)
    ki = _key_block(qi, step, bq, bk, window)
    heads, group = q_ref.shape[1], q_ref.shape[1] // k_ref.shape[1]

    @pl.when(step == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, _NEG, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(ki <= _last_key_block(qi, bq, bk))
    def _():
        for j in range(k_ref.shape[1]):
            k, v, on = k_ref[0, j], v_ref[0, j], _tile(mask_ref, qi, ki, bq, bk, window)
            for h in range(j * group, (j + 1) * group):
                s = jnp.where(on, _dot(q_ref[0, h], k, 1, 1), _NEG)
                m_old = m_scr[h]
                m_new = jnp.maximum(m_old, s.max(axis=1, keepdims=True))
                p = jnp.where(on, jnp.exp(s - m_new), 0.0)
                scale = jnp.exp(m_old - m_new)
                l_scr[h] = l_scr[h] * scale + p.sum(axis=1, keepdims=True)
                acc_scr[h] = acc_scr[h] * scale + _dot(p.astype(v.dtype), v, 1, 0)
                m_scr[h] = m_new

    @pl.when(step == pl.num_programs(3) - 1)
    def _():
        for h in range(heads):
            l = jnp.maximum(l_scr[h], 1e-20)
            out_ref[0, h] = (acc_scr[h] / l).astype(out_ref.dtype)
            lse_ref[0, 0, :, h:h + 1] = m_scr[h] + jnp.log(l)


def _bwd_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, *, bq: int, bk: int, window=None):
    """One tile's scores, probabilities and ``ds``, and all three gradients
    from them. ``dq_ref`` is the query block's sum over the key blocks;
    ``dk_ref`` and ``dv_ref`` hold the step's key-value heads over the whole
    row, a tile adding into its key block's rows."""
    qi, step = pl.program_id(2), pl.program_id(3)
    ki = _key_block(qi, step, bq, bk, window)
    group = q_ref.shape[1] // k_ref.shape[1]

    @pl.when((qi == 0) & (step == 0))
    def _():
        dk_ref[...] = jnp.zeros(dk_ref.shape, jnp.float32)
        dv_ref[...] = jnp.zeros(dv_ref.shape, jnp.float32)

    @pl.when(step == 0)
    def _():
        dq_ref[...] = jnp.zeros(dq_ref.shape, jnp.float32)

    @pl.when(ki <= _last_key_block(qi, bq, bk))
    def _():
        rows = pl.ds(pl.multiple_of(ki * bk, bk), bk)
        for j in range(k_ref.shape[1]):
            k, v, on = k_ref[0, j], v_ref[0, j], _tile(mask_ref, qi, ki, bq, bk, window)
            dk, dv = dk_ref[0, j, rows], dv_ref[0, j, rows]
            for h in range(j * group, (j + 1) * group):
                q, do = q_ref[0, h], do_ref[0, h]
                s = _dot(q, k, 1, 1)
                p = jnp.where(on, jnp.exp(s - lse_ref[0, 0, :, h:h + 1]), 0.0)
                dv = dv + _dot(p.astype(do.dtype), do, 0, 0)
                ds = (p * (_dot(do, v, 1, 1) - delta_ref[0, 0, :, h:h + 1])).astype(q.dtype)
                dk = dk + _dot(ds, q, 0, 0)
                dq_ref[0, h] = dq_ref[0, h] + _dot(ds, k, 1, 0)
            dk_ref[0, j, rows], dv_ref[0, j, rows] = dk, dv


def _step_heads(kv: int, g: int) -> list:
    """The counts of key-value heads a grid step may work, most first: those
    that divide ``kv`` and bring its query heads to at most ``STEP_HEADS``."""
    return [s for s in range(min(kv, max(1, STEP_HEADS // g)), 0, -1) if kv % s == 0]


def heads_per_step(kv: int, g: int) -> int:
    """Key-value heads a grid step works, from the shapes alone: as many as
    bring its query heads to ``STEP_HEADS``, of those that divide ``kv``."""
    return _step_heads(kv, g)[0]


def backward_step_bytes(s: int, g: int, d: int, dv: int, t: int, itemsize: int,
                        masked: bool, bq: int = BLOCK_Q, bk: int = BLOCK_K) -> int:
    """VMEM a step of the backward program holds for ``s`` key-value heads of
    ``g`` query heads each over a row of ``t``: ``dk`` and ``dv`` of the whole
    row once (float32), the step's other blocks twice (the pipeline's two
    buffers), and the float32 tiles of its working. A width fills whole lane
    tiles of 128, so a score width of 192 is held as 256."""
    lanes = lambda w: -(-w // 128) * 128  # noqa: E731
    d, dv, bq, bk = lanes(d), lanes(dv), min(bq, t), min(bk, t)
    resident = 4 * s * t * (d + dv)
    blocks = (itemsize * s * (g * bq * (d + dv) + bk * (d + dv))      # q, do; k, v
              + 2 * 4 * bq * lanes(s * g)                             # logsumexp, delta
              + 4 * s * g * bq * d                                    # dq
              + masked * bq * bk)
    return resident + 2 * blocks + 6 * 4 * bq * bk


def backward_heads_per_step(kv: int, g: int, d: int, dv: int, t: int, itemsize: int,
                            masked: bool = False, bq: int = BLOCK_Q, bk: int = BLOCK_K) -> int:
    """Key-value heads a grid step of the backward program works, from the
    shapes alone: the most of :func:`heads_per_step`'s candidates whose
    ``dk`` and ``dv`` over the whole row fit VMEM beside the step's blocks."""
    held = lambda s: backward_step_bytes(s, g, d, dv, t, itemsize, masked, bq, bk)  # noqa: E731
    for s in _step_heads(kv, g):
        if held(s) <= VMEM_LIMIT_BYTES:
            return s
    raise ValueError(
        f"a row of {t} positions is too long for the attention's backward program: dk and dv of"
        f" one key-value head (widths {d} and {dv}) with its blocks take {held(1):,} bytes of"
        f" VMEM, over the {VMEM_LIMIT_BYTES:,} a program may hold")


def backward_query_block(s: int, g: int, d: int, dv: int, t: int, itemsize: int,
                         masked: bool, bq: int, bk: int) -> int:
    """Queries a tile of the backward program, from the shapes alone: twice
    the forward program's where the row divides into them and the step still
    fits VMEM (a key block, its mask's tile and its rows of ``dk`` and ``dv``
    are then read and written half as often), else the forward program's."""
    wide = 2 * bq
    fits = backward_step_bytes(s, g, d, dv, t, itemsize, masked, wide, bk) <= VMEM_LIMIT_BYTES
    return wide if t % wide == 0 and fits else bq


def _specs(s: int, g: int, d: int, dv: int, bq: int, bk: int, t: int, window=None):
    """Block specs of one call for ``s`` key-value heads a step of ``g`` query
    heads each, scores over ``d`` and values of ``dv``, on the grid
    (b, kv / s, qi, ki) with the key block clamped to the last one under the
    diagonal; with a window the key axis counts from the band's first key
    block. A clamped step names the block the step before it held: nothing
    is fetched. ``dk`` and ``dv`` are the step's heads over the whole row, one
    buffer each, in VMEM while (b, kv / s) stands and written when it moves."""
    at = lambda qi, ki: (qi, jnp.minimum(_key_block(qi, ki, bq, bk, window),  # noqa: E731
                                         _last_key_block(qi, bq, bk)))

    def spec(block, index, **kw):
        return pl.BlockSpec(block, lambda b, kv, i, j: index(b, kv, *at(i, j)), **kw)

    by_query = lambda b, kv, qi, ki: (b, kv, qi, 0)  # noqa: E731
    by_keys = lambda b, kv, qi, ki: (b, kv, ki, 0)  # noqa: E731
    whole = lambda b, kv, qi, ki: (b, kv, 0, 0)  # noqa: E731
    once = pl.Buffered(1)
    return {
        "q": spec((1, s * g, bq, d), by_query), "o": spec((1, s * g, bq, dv), by_query),
        "k": spec((1, s, bk, d), by_keys), "v": spec((1, s, bk, dv), by_keys),
        "mask": spec((1, bq, bk), lambda b, kv, qi, ki: (b, qi, ki)),
        "row": spec((1, 1, bq, s * g), by_query),
        "dk": spec((1, s, t, d), whole, pipeline_mode=once),
        "dv": spec((1, s, t, dv), whole, pipeline_mode=once),
    }


def _heads_first(x):
    return jnp.transpose(x, (0, 2, 1, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def sparse_attention(q, k, v, mask, block_q=BLOCK_Q, block_k=BLOCK_K, interpret=False):
    """q [B, T, H, D], k [B, T, KV, D], v [B, T, KV, DV], ``mask`` int8
    [B, T, T] (the selection: the pairs that count, causal) -> [B, T, H, DV]."""
    return _forward(q, k, v, mask, block_q, block_k, interpret)[0]


def _forward(q, k, v, mask, block_q, block_k, interpret, window=None):
    scaled = (q.astype(jnp.float32) * q.shape[3] ** -0.5).astype(q.dtype)
    return _forward_programs(_heads_first(scaled), _heads_first(k), _heads_first(v), mask,
                             block_q, block_k, interpret, window)


def _forward_programs(qs, k, v, mask, block_q, block_k, interpret, window=None):
    """The forward program on operands laid heads-first, ``qs`` scaled:
    ``(out [B, T, H, DV], logsumexp)``."""
    b, h, t, d = qs.shape
    kv, dv = k.shape[1], v.shape[3]
    g = h // kv
    s = heads_per_step(kv, g)
    bq, bk = _block(block_q, t), _block(block_k, t)
    sp = _specs(s, g, d, dv, bq, bk, t, window)
    masked = (mask,) if mask is not None else ()
    out, lse = pl.pallas_call(
        _kernel(_fwd_kernel, masked, bq=bq, bk=bk, window=window),
        grid=(b, kv // s, t // bq, band_key_blocks(t, bq, bk, window)),
        in_specs=[sp["q"], sp["k"], sp["v"]] + [sp["mask"]] * len(masked),
        out_specs=[sp["o"], sp["row"]],
        out_shape=[jax.ShapeDtypeStruct((b, h, t, dv), qs.dtype),
                   jax.ShapeDtypeStruct((b, kv // s, t, s * g), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((s * g, bq, 1), jnp.float32),
                        pltpu.VMEM((s * g, bq, 1), jnp.float32),
                        pltpu.VMEM((s * g, bq, dv), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(qs, k, v, *masked)
    return _heads_first(out), lse


def _fwd(q, k, v, mask, block_q, block_k, interpret):
    out, lse = _forward(q, k, v, mask, block_q, block_k, interpret)
    return out, (q, k, v, mask, out, lse)


def _bwd(block_q, block_k, interpret, res, g_out, window=None):
    q, k, v, mask, out, lse = res
    scale = q.shape[3] ** -0.5
    qs = _heads_first((q.astype(jnp.float32) * scale).astype(q.dtype))
    dq, dk, dv = _backward_programs(qs, _heads_first(k), _heads_first(v), mask, out, lse, g_out,
                                    block_q, block_k, interpret, window)
    # dq was taken against the scaled q; dk already carries the scale
    grads = ((_heads_first(dq) * scale).astype(q.dtype), _heads_first(dk).astype(k.dtype),
             _heads_first(dv).astype(v.dtype))
    return grads + ((np.zeros(mask.shape, jax.dtypes.float0),) if mask is not None else ())


def _backward_programs(qs, k, v, mask, out, lse, g_out, block_q, block_k, interpret, window=None):
    """The backward program on operands laid heads-first, ``qs`` scaled, the
    output and its cotangent ``[B, T, H, DV]``: ``dq`` (against ``qs``),
    ``dk``, ``dv`` as it writes them, float32 and heads-first."""
    b, h, t, d = qs.shape
    kv, dv = k.shape[1], v.shape[3]
    g = h // kv
    masked = (mask,) if mask is not None else ()
    bq, bk = _block(block_q, t), _block(block_k, t)
    shape = (g, d, dv, t, qs.dtype.itemsize, bool(masked))
    s = backward_heads_per_step(kv, *shape, bq, bk)
    bq = backward_query_block(s, *shape, bq, bk)
    # delta[b, t, h] = rowsum(dO o O); it and the logsumexp laid out for the
    # heads this program takes a step, which may be fewer than the forward's
    delta = jnp.einsum("bthd,bthd->bth", g_out.astype(jnp.float32),
                       out.astype(jnp.float32))
    lse = jnp.transpose(lse, (0, 2, 1, 3)).reshape(b, t, h)
    lse, delta = (jnp.transpose(x.reshape(b, t, kv // s, s * g), (0, 2, 1, 3))
                  for x in (lse, delta))
    do = _heads_first(g_out.astype(qs.dtype))
    sp = _specs(s, g, d, dv, bq, bk, t, window)
    return pl.pallas_call(
        _kernel(_bwd_kernel, masked, bq=bq, bk=bk, window=window),
        grid=(b, kv // s, t // bq, band_key_blocks(t, bq, bk, window)),
        in_specs=([sp["q"], sp["k"], sp["v"]] + [sp["mask"]] * len(masked)
                  + [sp["o"], sp["row"], sp["row"]]),
        out_specs=[sp["q"], sp["dk"], sp["dv"]],
        out_shape=[jax.ShapeDtypeStruct((b, h, t, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, kv, t, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, kv, t, dv), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
    )(qs, k, v, *masked, do, lse, delta)


sparse_attention.defvjp(_fwd, _bwd)


def causal_attention_plain(q, k, v, window=None):
    """:func:`sparse_attention_plain` over every causal pair, with a
    ``window`` those of the band ``t - window < s <= t``."""
    at = jnp.arange(q.shape[1])
    on = at[None, :] <= at[:, None]
    if window is not None:
        on = on & (at[None, :] > at[:, None] - window)
    return sparse_attention_plain(
        q, k, v, jnp.broadcast_to(on, (q.shape[0],) + (q.shape[1],) * 2))


def band_of(window, t: int):
    """``window`` as the programs take it: None where it holds the whole row."""
    if window is not None and window < 1:
        raise ValueError(f"window={window}: want at least 1 (a query reads itself)")
    return None if window is None or window >= t else int(window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def causal_attention(q, k, v, block_q=BLOCK_Q, block_k=BLOCK_K, interpret=False, window=None):
    """:func:`sparse_attention` over every causal pair, with no mask operand:
    q [B, T, H, D], k [B, T, KV, D], v [B, T, KV, DV] -> [B, T, H, DV]. A
    padded position follows its row's events, so no real query reads it.
    ``window``: a query reads itself and the ``window - 1`` positions before
    it (``t - window < s <= t``) and the programs walk the band's tiles only:
    the grid's key axis is as long as the most key blocks a query block's band
    touches (:func:`band_key_blocks`), counted from the band's first."""
    return _forward(q, k, v, None, block_q, block_k, interpret, band_of(window, q.shape[1]))[0]


def _causal_fwd(q, k, v, block_q, block_k, interpret, window):
    out, lse = _forward(q, k, v, None, block_q, block_k, interpret, band_of(window, q.shape[1]))
    return out, (q, k, v, None, out, lse)


def _causal_bwd(block_q, block_k, interpret, window, res, g_out):
    return _bwd(block_q, block_k, interpret, res, g_out, band_of(window, res[0].shape[1]))


causal_attention.defvjp(_causal_fwd, _causal_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def heads_first_attention(qs, k, v, mask=None, block_q=BLOCK_Q, block_k=BLOCK_K,
                          interpret=False, window=None):
    """The same two programs on operands laid as they read them
    (``ops/rope_layout.py`` writes them so): ``qs`` [B, H, T, D] **scaled by
    ``D ** -0.5``**, k [B, KV, T, D], v [B, KV, T, DV] -> [B, T, H, DV], over
    the pairs ``mask`` (int8 [B, T, T]) selects or, with None, every causal
    pair, with a ``window`` those of the band. No pass lies between the
    operands and the programs, forward or backward: the residuals are the
    operands, and the cotangents of ``qs`` (against the scaled q), ``k`` and
    ``v`` are handed on as the backward program wrote them, float32 and
    heads-first, for the operands' writer to round and turn back."""
    return _heads_first_fwd(qs, k, v, mask, block_q, block_k, interpret, window)[0]


def _heads_first_fwd(qs, k, v, mask, block_q, block_k, interpret, window):
    if mask is not None and window is not None:
        raise ValueError("a mask is the whole contract of which pairs count: no window beside it")
    out, lse = _forward_programs(qs, k, v, mask, block_q, block_k, interpret,
                                 band_of(window, qs.shape[2]))
    return out, (qs, k, v, mask, out, lse)


def _heads_first_bwd(block_q, block_k, interpret, window, res, g_out):
    # the cotangents leave in float32 for operands of the compute dtype: JAX
    # hands a cotangent on as a rule returns it, and the writer's rule rounds
    *operands, out, lse = res
    grads = _backward_programs(*operands, out, lse, g_out, block_q, block_k, interpret,
                               band_of(window, out.shape[1]))
    mask = operands[3]
    return (*grads, None if mask is None else np.zeros(mask.shape, jax.dtypes.float0))


heads_first_attention.defvjp(_heads_first_fwd, _heads_first_bwd)
