"""Fused Pallas gather->Gram half-step kernels for ALS.

The XLA path of the ALS half-step tail (``parallel/als.py``) materializes
the gathered opposite-side factors as a ``[rows, L, K]`` HBM intermediate
before reducing it to a ``[K, K]`` Gram and ``[K]`` rhs per row, and on a
TPU that intermediate is lane-padded: a gathered row of K < 128 values takes
a whole 128-lane row, 8 times its bytes at rank 16. This kernel streams
padded-CSR row blocks through VMEM and performs the gather with
double-buffered row DMAs from the HBM-resident factor table, accumulating
each row's Gram/rhs in f32 on-chip; the ``[rows, L, K]`` intermediate never
exists in HBM.

What that buys is MEMORY, not time. The gather is one row DMA a slot,
started and waited for on the scalar core: 31 ns a slot on a v5e whatever
the rank or the bytes (PERF.md, PR 24), which made an ML-20M iteration 14
times slower than the einsums (PERF.md, PR 25: 882.8 against 63.5 ms). So
"auto" (``parallel.als.block_solver``) runs the einsum tail, and keeps this kernel
for the blocks whose intermediate cannot fit the chip: the recommendation
template's default packing (one bucket, no cap) makes a ``[3712, 23832]``
item block at MovieLens-1M, 45.3 GB of gathered rows, which the TPU
compiler refuses and this kernel runs in under 1.5 GB. ``alsSolver:
"pallas"`` still forces it for every block.

Contract (shared with the XLA path -- ``parallel.als`` padding invariant):

- ``indices[r, l]`` selects a row of ``factors``; padding slots (and, in
  model-sharded mode, out-of-shard hits) point at a trailing ZERO row, so
  every padding contribution dies through the gathered zeros -- no mask
  stream crosses HBM.
- ``factors`` is ``[S + 1, K]`` (zero row appended), f32 or bf16; Gram and
  rhs accumulate f32 regardless (the ALX mixed-precision recipe).
- explicit mode:  gram[r] = sum_l y y^T,          rhs[r] = sum_l v * y
- implicit mode:  gram[r] = sum_l (alpha v) y y^T, rhs[r] = sum_l (1 + alpha v) y
  (the YtY global term, the ridge, and the solve stay OUTSIDE the kernel:
  they are [K, K]-small and shared with the XLA path bit-for-bit).

Layout/VMEM budget (mirrors the hard-won notes in ``ops/flash_attention``):

- Blocks keep their last two dims equal to the array dims (K is far below
  a lane, so (BR, K, K) / (BR, K) output blocks are exact-dim blocks).
- The gather table is laid out for the DMA engine inside ``gram_rhs``: f32,
  each row padded to a whole number of 128-lane vectors ([S + 1, KP]). A
  one-row DMA needs a 32-bit row a lane multiple wide: out of a [S + 1, K]
  table Mosaic refuses it (K < 128: "Slice shape along dimension 1 must be
  aligned to tiling (128)"; bf16 at any K: rows pack in pairs per sublane,
  "dimension 0 must be aligned to tiling (8)"). The upcast is exact and the
  pad lanes are sliced off before the Gram, so the result is what the
  [S + 1, K] table defines; a bf16 table still folds in one bf16 MXU pass.
  The price is table memory ((S + 1) * KP * 4 bytes per half-step, 71 MB at
  138k rows) and 512-byte row reads where K * itemsize would do.
- The index block rides SMEM -- DMA source addressing is scalar work; a
  [BR, L] i32 block is BR*L*4 bytes (8 KB at BR=8, L=256).
- VMEM per program ~= BR*L*4 (values) + 2*BR*C*KP*4 (double-buffered
  gather scratch) + BR*(K*K + K)*4 (accumulator blocks): ~2.1 MB at the
  bench shape (BR=8, L=256, C=256, KP=128) -- under the ~16 MB/core
  budget, leaving the auto-pipeliner room to double-buffer the idx/val
  streams across grid steps.
- The gather itself is one row-DMA per (row, l) slot: the DMA engine keeps
  BR*C descriptors in flight per chunk while the MXU folds the PREVIOUS
  chunk (classic two-slot double buffering over the L dimension). Each
  descriptor moves one KP*4-byte row and costs a start and a wait on the
  scalar core -- what this buys over XLA is not a faster gather but the
  intermediate that never hits HBM.
- On CPU meshes the kernels run in interpret mode (the
  ``ops/flash_attention`` precedent), so tier-1 CPU tests exercise this
  exact kernel code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from predictionio_tpu.utils.jax_compat import (
    pallas as pl,
    pallas_tpu as pltpu,
    shape_struct,
)
from predictionio_tpu.utils.platform import note_kernel

#: the kernel's name: in ``device_report`` and, as the custom call's name, in
#: the compiled program and a profiler trace
KERNEL_NAME = "als_gram_rhs"

#: rows per grid step (a CAP: the largest power of two <= this that divides
#: the block's rows is used, so a 24-row block split over a 2-device data
#: axis -- 12 rows per device -- runs at BR=4 instead of failing). 8 keeps
#: the [BR, C, K] gather scratch small on the aligned common case.
BLOCK_ROWS = 8

#: gather chunk (columns of the L dimension folded per double-buffer slot);
#: the largest of these dividing L is used, so L only needs 8-alignment.
_CHUNKS = (256, 128, 64, 32, 16, 8)

#: lanes of one vector register row: the unit a row DMA must be a multiple of
_LANES = 128

#: longest run of the L dimension one grid step takes. The index block rides
#: SMEM double-buffered (BR * tile * 4 bytes * 2 = 128 KB here, of 1 MB), and
#: the chunk loop unrolls tile/chunk times. A block longer than this (the
#: recommendation template's single-bucket item side at the ML-1M shape is
#: [3712, 23832]) is walked by a second grid axis that accumulates into the
#: same output block; without it the chip's compiler refuses the program:
#: "Allocation (size=1531904) would exceed memory (size=1048576) ... smem".
MAX_TILE_LEN = 2048


def _pick_chunk(pad_len: int) -> int:
    for cand in _CHUNKS:
        if cand <= pad_len and pad_len % cand == 0:
            return cand
    raise ValueError(
        f"padded length {pad_len} is not a multiple of 8 (pack_padded_csr "
        "guarantees len_multiple=8)"
    )


def _gram_rhs_kernel(
    idx_ref,    # SMEM [BR, L] i32
    val_ref,    # VMEM [BR, L] f32
    alpha_ref,  # SMEM [1, 1]  f32 (ignored in explicit mode)
    table_ref,  # ANY  [S + 1, KP] f32, lane-padded (stays in HBM)
    gram_ref,   # VMEM [BR, K, K] f32 out
    rhs_ref,    # VMEM [BR, K] f32 out
    gathered,   # VMEM scratch [2, BR, C, KP] f32
    sem,        # DMA semaphores [2] (one per buffer slot)
    *,
    implicit: bool,
    chunk: int,
    bf16_exact: bool,
    pad_len: int,
):
    br, tile = idx_ref.shape
    n_chunks = tile // chunk
    k = gram_ref.shape[1]
    # the last L tile of a long block may hang over the block's end: its
    # window then holds stale slots, which are pointed at the zero row (and
    # their values zeroed) exactly like packed padding
    ragged = pad_len % tile != 0
    base = pl.program_id(1) * tile
    zero_row = table_ref.shape[0] - 1

    def dma(slot: int, ci: int, p):
        r, cl = p // chunk, p % chunk
        idx = idx_ref[r, ci * chunk + cl]
        if ragged:
            idx = jnp.where(base + ci * chunk + cl < pad_len, idx, zero_row)
        return pltpu.make_async_copy(
            table_ref.at[pl.ds(idx, 1)],
            gathered.at[slot, r, pl.ds(cl, 1)],
            sem.at[slot],
        )

    def issue(ci: int) -> None:
        slot = ci % 2

        def start(p, carry):
            dma(slot, ci, p).start()
            return carry

        jax.lax.fori_loop(0, br * chunk, start, None)

    def drain(ci: int) -> None:
        slot = ci % 2

        def wait(p, carry):
            dma(slot, ci, p).wait()
            return carry

        jax.lax.fori_loop(0, br * chunk, wait, None)

    issue(0)
    gram_acc = jnp.zeros((br, k, k), jnp.float32)
    rhs_acc = jnp.zeros((br, k), jnp.float32)
    # n_chunks is static: the chunk loop unrolls, keeping the double-buffer
    # slot index STATIC (Mosaic cannot dynamically index the sublane-major
    # scratch on the compute side; the DMA .at[] indices may stay dynamic)
    for ci in range(n_chunks):
        if ci + 1 < n_chunks:
            issue(ci + 1)  # next chunk's DMAs fly while this one folds
        drain(ci)
        g = gathered[ci % 2][:, :, :k]                        # [BR, C, K]
        v = val_ref[:, ci * chunk : (ci + 1) * chunk]         # [BR, C]
        if ragged:
            col = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
            v = jnp.where(base + ci * chunk + col < pad_len, v, 0.0)
        if implicit:
            w = alpha_ref[0, 0] * v
            gram_w, rhs_w = w, 1.0 + w
        else:
            gram_w, rhs_w = None, v
        lhs = g if gram_w is None else g * gram_w[..., None]
        if bf16_exact:
            # the table's values ARE bf16 (upcast for the gather only): one
            # MXU pass with f32 accumulation is exact
            lhs, rhs_op, precision = (
                lhs.astype(jnp.bfloat16), g.astype(jnp.bfloat16), None
            )
        else:
            # f32 operands: the same "highest" the XLA path asks for, or
            # the MXU would round them to bf16
            rhs_op, precision = g, jax.lax.Precision.HIGHEST
        gram_acc = gram_acc + jax.lax.dot_general(
            lhs, rhs_op,
            dimension_numbers=(((1,), (1,)), ((0,), (0,))),
            precision=precision,
            preferred_element_type=jnp.float32,
        )
        rhs_acc = rhs_acc + jnp.sum(g * rhs_w[..., None], axis=1)

    @pl.when(pl.program_id(1) == 0)
    def _():
        gram_ref[...] = jnp.zeros_like(gram_ref)
        rhs_ref[...] = jnp.zeros_like(rhs_ref)

    gram_ref[...] += gram_acc
    rhs_ref[...] += rhs_acc


def gram_rhs(
    indices,
    values,
    factors,
    alpha=0.0,
    *,
    implicit: bool = False,
    interpret: bool = False,
    block_rows: int = BLOCK_ROWS,
):
    """Fused gather->Gram/rhs over one padded-CSR block.

    ``indices`` i32 [R, L] (padding -> the trailing zero factor row),
    ``values`` f32 [R, L], ``factors`` [S + 1, K] f32/bf16 (zero row
    appended). Returns ``(gram [R, K, K] f32, rhs [R, K] f32)``; the
    caller adds ridge/YtY and solves (``ops.linalg.batched_spd_solve``).
    ``alpha`` may be a traced scalar (implicit mode's confidence scale).
    """
    r, pad_len = indices.shape
    k = factors.shape[1]
    br = min(block_rows, r)
    while br > 1 and r % br:
        br //= 2  # e.g. 12 rows/device under a 2-way data split -> BR=4
    tile = min(pad_len, MAX_TILE_LEN)
    chunk = _pick_chunk(tile)
    note_kernel(KERNEL_NAME, interpret)
    # gather layout the chip admits (see module docstring): 32-bit rows a
    # whole number of lanes wide. The upcast is exact and the pad lanes are
    # never read, so Gram/rhs are what the [S + 1, K] table defines.
    kp = -(-k // _LANES) * _LANES
    table = jnp.pad(factors.astype(jnp.float32), ((0, 0), (0, kp - k)))
    alpha_arr = jnp.asarray(alpha, jnp.float32).reshape(1, 1)
    kernel = functools.partial(
        _gram_rhs_kernel, implicit=implicit, chunk=chunk,
        # explicit mode multiplies bf16 values by themselves only; implicit
        # mode weights one operand in f32 first
        bf16_exact=factors.dtype == jnp.bfloat16 and not implicit,
        pad_len=pad_len,
    )
    return pl.pallas_call(
        kernel,
        # rows, then L tiles: the output block is revisited along the
        # second axis and accumulates there
        grid=(r // br, -(-pad_len // tile)),
        in_specs=[
            pl.BlockSpec((br, tile), lambda i, j: (i, j),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((br, tile), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((br, k, k), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((br, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            shape_struct((r, k, k), jnp.float32, indices),
            shape_struct((r, k), jnp.float32, indices),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, br, chunk, kp), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
        name=KERNEL_NAME,
    )(jnp.asarray(indices, jnp.int32), values, alpha_arr, table)


def half_step_bytes(
    rows: int, pad_len: int, rank: int, itemsize: int, fused: bool
) -> float:
    """HBM bytes one half-step tail moves over a [rows, pad_len] block.

    The bytes-moved model behind the ``als_half_step_gbps`` bench metric
    (the half-step is bandwidth-bound, so GB/s -- not the misleading MFU
    number -- is the efficiency axis):

    shared streams: indices (i32) + values (f32) read once; Gram + rhs
    (f32) written once. The factor-table source reads are counted as the
    gather's random-read pass (rows*L*K*itemsize in expectation); the
    table's cold first touch is shared by both paths and not modeled
    per block.

    - fused: the gather's random read is the ONLY [rows, L, K]-sized pass;
      the result accumulates in VMEM.
    - unfused (XLA): the same random read, PLUS the gathered [rows, L, K]
      intermediate written to HBM once and read back by the Gram and rhs
      einsums (2 passes) -> 4 gather-sized passes in total.
    """
    streams = rows * pad_len * (4 + 4)            # indices + values
    outs = rows * (rank * rank + rank) * 4        # gram + rhs, f32
    gather_pass = rows * pad_len * rank * itemsize
    passes = 1 if fused else 4
    return float(streams + outs + passes * gather_pass)
