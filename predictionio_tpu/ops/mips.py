"""Fused quantized MIPS top-k: two-stage sub-linear retrieval on device.

Serving today is a full scan: ``batch_score_known_users`` materializes a
host-side ``[rows, items]`` f32 score buffer and argpartitions it per
request -- O(items) memory traffic per query, which caps the catalog far
below production scale. This module is the scale tentpole that replaces
the scan with the ALX-style device-resident layout (arxiv 2112.02194):

- **Stage 1** (``mips_block_topk``, a Pallas kernel in the
  ``ops/flash_attention`` house style): scan the int8
  block-quantized item table (``ops/quantize``) tile by tile, fusing the
  dequantize, the query dot-product, and a per-tile top-R selection. The
  ``[B, items]`` score matrix lives only as one ``[BB, block_items]``
  VMEM tile per grid step -- it NEVER exists in HBM; what leaves the
  kernel is ``[B, num_blocks, R]`` candidates, ``items * R/block_items``
  entries instead of ``items``.
- **Stage 2** (``RetrievalIndex.search``): merge the per-block candidates
  with one ``top_k`` over the small candidate tensor, sort the shortlist
  by catalog index (so downstream stable ranking tie-breaks by global
  index, like the scan), and re-score exactly in f32 against the resident
  table. Responses format through the existing ``topk_order`` /
  ``topk_item_scores`` tail, so whenever the shortlist contains the true
  top-k the bytes on the wire are identical to scan mode.

Containment contract: a tile's top-R is selected on the QUANTIZED scores
with padding rows masked below any real score (their zero rows would
otherwise outrank real negative scores), so the quantized global
top-``min(R, shortlist)`` is always inside the candidate set (the global
top-k of any score vector is contained in the union of per-tile top-k
for R >= k). Recall vs the exact scan is then
bounded only by quantization reorderings inside the
``score_error_bound`` window, which the shortlist margin oversamples
against -- measured >= 0.99 recall@10 at 1M items with the defaults
(bench ``mips_topk``).

Layout/VMEM budget:

- Query block ``[BB, K]`` f32 and item tile ``[BI, K]`` int8 are
  exact-dim blocks (K is far below a lane and pads internally); the
  per-tile scale is a ``[1, 1, 1]`` VMEM block and the candidates leave
  tile-major (``[nb, B, R]``), the block shapes the TPU lowering admits.
- VMEM per program ~= BB*K*4 + BI*K*1 + BB*BI*4 (the score tile) +
  BB*R*8 (outputs): ~25 KB at the defaults (BB=8, BI=512, K=16, R=16) --
  far under the ~16 MB/core budget, leaving the auto-pipeliner room to
  stream tiles ahead of the VPU selection.
- The top-R selection is R unrolled max/first-match-argmin passes over
  the VMEM score tile (pure VPU ops: Mosaic has no in-kernel sort);
  R is static so the loop unrolls.
- On CPU meshes the kernel runs in interpret mode (the
  ``ops/flash_attention`` precedent), so tier-1 CPU tests exercise this
  exact kernel code.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

from predictionio_tpu.ops.quantize import (
    BLOCK_ITEMS,
    PackedFactors,
    pack_int8_blockwise,
)

#: query rows per grid step (f32 sublane multiple)
BLOCK_QUERIES = 8

#: the stage-1 kernel's name in ``device_report``, the compiled program and
#: a profiler trace
KERNEL_NAME = "mips_block_topk"

#: device scopes of ``_search_program`` (``jax.named_scope``, metadata only)
SCOPE_STAGE1 = "mips.stage1"
SCOPE_MERGE = "mips.merge"
SCOPE_RERANK = "mips.rerank"

#: matches plain_attention/flash_attention's finite masked-score constant:
#: masking stays finite inside the kernel; -inf sentinels are applied at
#: the (host/XLA) merge where they are cheap and safe. Padding rows mask
#: to _NEG; already-selected columns mask STRICTLY BELOW it (_SEL), so
#: once real scores are exhausted the selection drains distinct padding
#: columns (-> merge sentinels) instead of re-emitting a selected column
#: as a duplicate candidate with a real catalog index.
_NEG = -1e30
_SEL = -2e30


def mips_block_topk(
    queries,
    q_table,
    scales,
    *,
    block_topk: int,
    num_items: int,
    interpret: bool = False,
):
    """Stage 1: per-quantization-block top-``block_topk`` candidates.

    ``queries`` f32 [B, K] (B a ``BLOCK_QUERIES`` multiple), ``q_table``
    int8 [padded_items, K], ``scales`` f32 [num_blocks, 1]. Returns
    ``(scores [B, num_blocks * R] f32, indices [B, num_blocks * R] i32)``
    with indices already global catalog indices. Padding rows of the last
    block (global index >= ``num_items``) are masked to ``_NEG`` BEFORE
    the per-tile selection: their dequantized score is exactly 0, which
    would otherwise outrank real items with negative scores and evict
    them from the candidate set, breaking the containment contract. They
    can still surface as candidates when the tile holds fewer than R real
    rows -- the merge maps any remaining index >= num_items to the
    ``(num_items, -inf)`` sentinel.
    """
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.utils.jax_compat import pallas as pl, shape_struct
    from predictionio_tpu.utils.platform import note_kernel

    note_kernel(KERNEL_NAME, interpret)
    b, k = queries.shape
    padded_items = q_table.shape[0]
    nb = scales.shape[0]
    bi = padded_items // nb
    if b % BLOCK_QUERIES:
        raise ValueError(
            f"batch {b} must be a multiple of {BLOCK_QUERIES} "
            "(RetrievalIndex.search pads)"
        )
    r = block_topk
    if not 0 < r <= bi:
        raise ValueError(f"block_topk {r} must be in [1, {bi}]")
    if not 0 < num_items <= padded_items:
        raise ValueError(
            f"num_items {num_items} must be in [1, {padded_items}]"
        )

    def kernel(
        q_ref,       # VMEM [BB, K] f32
        table_ref,   # VMEM [BI, K] int8 (one quantization block)
        scale_ref,   # VMEM [1, 1, 1] f32
        score_ref,   # VMEM [1, BB, R] f32 out
        idx_ref,     # VMEM [1, BB, R] i32 out
    ):
        bb = q_ref.shape[0]
        g = table_ref[...].astype(jnp.float32) * scale_ref[0]     # [BI, K]
        s = jax.lax.dot_general(
            q_ref[...], g,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                         # [BB, BI]
        col = jax.lax.broadcasted_iota(jnp.int32, (bb, bi), 1)
        slot = jax.lax.broadcasted_iota(jnp.int32, (bb, r), 1)
        base = pl.program_id(1) * bi
        # padding rows dequantize to score 0, which would outrank real
        # negative scores -- mask them below any real score pre-selection
        s = jnp.where(base + col < num_items, s, _NEG)
        top_s = jnp.zeros((bb, r), jnp.float32)
        top_i = jnp.zeros((bb, r), jnp.int32)
        # R unrolled select-and-mask passes (pure VPU: Mosaic has no
        # in-kernel sort); first-match (min index) argmax so ties inside
        # a tile resolve to the lowest catalog index, like argsort. Every
        # value stays 2-D and the [BB, R] result is stored once.
        for step in range(r):
            m = jnp.max(s, axis=1, keepdims=True)                 # [BB, 1]
            local = jnp.min(
                jnp.where(s == m, col, bi), axis=1, keepdims=True
            )                                                     # [BB, 1]
            top_s = jnp.where(slot == step, m, top_s)
            top_i = jnp.where(slot == step, base + local, top_i)
            s = jnp.where(col == local, _SEL, s)
        score_ref[0] = top_s
        idx_ref[0] = top_i

    # block shapes the TPU lowering admits: the per-tile scale is a
    # [1, 1, 1] VMEM block (an SMEM (1, 1) block of a [nb, 1] array is
    # refused), and the candidates leave tile-major, [nb, B, R] in
    # (1, BB, R) blocks, because a (BB, 1, R) block of [B, nb, R] is
    # neither 8-divisible nor whole in its second-minor dimension
    scores, idx = pl.pallas_call(
        kernel,
        grid=(b // BLOCK_QUERIES, nb),
        in_specs=[
            pl.BlockSpec((BLOCK_QUERIES, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bi, k), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j: (j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, BLOCK_QUERIES, r), lambda i, j: (j, i, 0)),
            pl.BlockSpec((1, BLOCK_QUERIES, r), lambda i, j: (j, i, 0)),
        ],
        out_shape=[
            shape_struct((nb, b, r), jnp.float32, queries),
            shape_struct((nb, b, r), jnp.int32, queries),
        ],
        interpret=interpret,
        name=KERNEL_NAME,
    )(queries, q_table, scales.reshape(nb, 1, 1))
    scores = jnp.swapaxes(scores, 0, 1)
    idx = jnp.swapaxes(idx, 0, 1)
    return scores.reshape(b, nb * r), idx.reshape(b, nb * r)


def _search_program(
    queries,
    q_table,
    scales,
    table_f32,
    *,
    block_topk: int,
    shortlist: int,
    num_items: int,
    interpret: bool,
):
    """Stage 1 + merge + stage-2 exact re-rank, one jitted program.

    When the whole catalog fits the stage-2 budget (``num_items <=
    shortlist``) stage 1 is skipped entirely: the shortlist IS the
    catalog and retrieval is exact by construction. Without this
    degeneration, tiny catalogs inherit stage 1's per-block candidate
    cap (``num_blocks * block_topk``, e.g. 16 for a single-block
    catalog), and a query whose seen/blackList filters eat into those
    candidates comes back short -- the replay eval's scan-vs-mips guard
    caught exactly that.
    """
    import jax
    import jax.numpy as jnp

    def rerank(sel):
        with jax.named_scope(SCOPE_RERANK):
            gathered = table_f32[jnp.clip(sel, 0, num_items - 1)]  # [B, S, K]
            # "highest": the re-rank is the EXACT stage; at the MXU's default
            # the f32 operands would be rounded to bf16
            exact = jnp.einsum(
                "bk,bsk->bs", queries, gathered,
                precision="highest", preferred_element_type=jnp.float32,
            )
            return jnp.where(sel < num_items, exact, -jnp.inf)

    if num_items <= shortlist:
        with jax.named_scope(SCOPE_MERGE):
            width = min(shortlist, q_table.shape[0])
            base = jnp.arange(width, dtype=jnp.int32)
            sel = jnp.where(base < num_items, base, num_items)
            sel = jnp.broadcast_to(sel, (queries.shape[0], width))
        return sel, rerank(sel)

    with jax.named_scope(SCOPE_STAGE1):
        cand_s, cand_i = mips_block_topk(
            queries, q_table, scales,
            block_topk=block_topk, num_items=num_items, interpret=interpret,
        )
    with jax.named_scope(SCOPE_MERGE):
        valid = cand_i < num_items
        cand_s = jnp.where(valid, cand_s, -jnp.inf)
        cand_i = jnp.where(valid, cand_i, num_items)   # sentinel sorts last
        s = min(shortlist, cand_s.shape[1])
        _, pos = jax.lax.top_k(cand_s, s)
        sel = jnp.take_along_axis(cand_i, pos, axis=1)
        # ascending catalog order: the host tail's stable ranking then
        # breaks score ties by global index, byte-matching the full scan's
        # order
        sel = jnp.sort(sel, axis=1)
    return sel, rerank(sel)


@dataclass(frozen=True)
class RetrievalConfig:
    """The ``retrieval`` engine-param block (``docs/templates.md``).

    ``mode``: "scan" (full [rows, items] host matmul, the default) or
    "mips" (this module). ``shortlist`` is the stage-2 candidate count per
    query -- the recall margin over ``num``; ``block_items`` the
    quantization/tile granularity; ``block_topk`` the per-tile candidates
    (must stay >= the largest ``num`` served for the containment
    contract). Catalogs no larger than ``shortlist`` skip stage 1 and
    retrieve exactly (the shortlist is the catalog), so the containment
    caveats only bind past that size.
    """

    mode: str = "scan"
    shortlist: int = 512
    block_items: int = BLOCK_ITEMS
    block_topk: int = 16

    def __post_init__(self) -> None:
        if self.mode not in ("scan", "mips"):
            raise ValueError(
                f"retrieval.mode must be 'scan' or 'mips', got {self.mode!r}"
            )
        if self.shortlist < 1:
            raise ValueError("retrieval.shortlist must be >= 1")
        if self.block_topk < 1:
            raise ValueError("retrieval.blockTopk must be >= 1")

    @staticmethod
    def from_params(raw) -> "RetrievalConfig":
        """Parse the engine.json ``"retrieval": {...}`` block (camelCase
        knobs, template convention); None/{} -> scan defaults."""
        if not raw:
            return RetrievalConfig()
        if not isinstance(raw, dict):
            raise ValueError(
                f'"retrieval" must be an object like {{"mode": "mips"}}, '
                f"got {raw!r}"
            )
        known = {"mode", "shortlist", "blockItems", "blockTopk"}
        unknown = set(raw) - known
        if unknown:
            raise ValueError(
                f"unknown retrieval params {sorted(unknown)}; "
                f"expected a subset of {sorted(known)}"
            )
        return RetrievalConfig(
            mode=raw.get("mode", "scan"),
            shortlist=int(raw.get("shortlist", 512)),
            block_items=int(raw.get("blockItems", BLOCK_ITEMS)),
            block_topk=int(raw.get("blockTopk", 16)),
        )


class RetrievalIndex:
    """Device-resident two-stage retrieval index over one factor table.

    Holds the int8 packed table, its scales, and the f32 re-rank table on
    device, plus the jitted stage-1 + stage-2 program. Built lazily at
    serving time (models pickle without device state) and cached per
    (table, config) by ``models/_als_common.retrieval_index``.
    """

    def __init__(
        self,
        factors: np.ndarray,
        config: RetrievalConfig,
        *,
        interpret: bool | None = None,
    ) -> None:
        import jax

        self.config = config
        packed = pack_int8_blockwise(
            np.asarray(factors, np.float32), config.block_items
        )
        self.num_items = packed.num_items
        self.packed_bytes = packed.packed_bytes
        device = jax.devices()[0]
        if interpret is None:
            # decided by the device the tables are placed on, like every
            # other kernel here: anything but a TPU runs the same kernel
            # code through the Pallas interpreter
            interpret = device.platform != "tpu"
        self._q = jax.device_put(packed.q, device)
        self._scales = jax.device_put(packed.scales, device)
        self._table = jax.device_put(np.asarray(factors, np.float32), device)
        self._program = jax.jit(
            functools.partial(
                _search_program,
                block_topk=config.block_topk,
                shortlist=config.shortlist,
                num_items=self.num_items,
                interpret=interpret,
            )
        )

    def search(self, queries: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Top-``shortlist`` candidates for each query row.

        Returns ``(indices [B, S] i32 ascending per row, exact_scores
        [B, S] f32)``; slots past the catalog (tiny catalogs, padding)
        come back as ``(num_items, -inf)`` and drop in the format tail.
        Batches pad to the next power-of-two block multiple so serving
        sees a bounded set of compiled shapes (the micro-batching
        precedent).
        """
        queries = np.atleast_2d(np.asarray(queries, np.float32))
        b = queries.shape[0]
        padded = BLOCK_QUERIES
        while padded < b:
            padded *= 2
        if padded != b:
            queries = np.concatenate(
                [queries, np.zeros((padded - b, queries.shape[1]), np.float32)]
            )
        idx, scores = self._program(queries, self._q, self._scales, self._table)
        return np.asarray(idx[:b]), np.asarray(scores[:b])


def reference_shortlist(
    factors: np.ndarray, queries: np.ndarray, config: RetrievalConfig
) -> np.ndarray:
    """Numpy reference of the two-stage candidate selection: the same
    quantized stage-1 arithmetic and merge the kernel fuses, as plain
    host math. This is the recall oracle -- the bench's off-hardware
    recall@k measurement runs through it (timing the interpret-mode
    kernel at catalog scale would benchmark the Pallas interpreter) and
    the slow tier-2 test checks the
    1M-item recall contract against it. Returns ``[B, shortlist]``
    ascending candidate catalog indices (padding slots carry
    ``padded_items`` sentinels past tiny catalogs)."""
    packed = pack_int8_blockwise(
        np.asarray(factors, np.float32), config.block_items
    )
    if packed.num_items <= config.shortlist:
        # mirror the program's exhaustive degeneration: the shortlist is
        # the catalog (sentinels normalized to num_items, like search)
        width = min(config.shortlist, packed.q.shape[0])
        base = np.arange(width, dtype=np.int32)
        sel = np.where(base < packed.num_items, base, packed.num_items)
        return np.broadcast_to(
            sel, (np.atleast_2d(queries).shape[0], width)
        ).copy()
    deq = packed.q.astype(np.float32) * np.repeat(
        packed.scales[:, 0], config.block_items
    )[:, None]
    qs = np.asarray(queries, np.float32) @ deq.T          # [B, padded]
    b, padded = qs.shape
    # mirror the kernel: padding rows masked BEFORE per-tile selection,
    # so they never evict real negative-scored items from the candidates
    qs = np.where(np.arange(padded)[None, :] < packed.num_items, qs, _NEG)
    nb = packed.num_blocks
    r = min(config.block_topk, config.block_items)
    tiles = qs.reshape(b, nb, config.block_items)
    if r < config.block_items:
        part = np.argpartition(-tiles, r - 1, axis=2)[:, :, :r]
    else:
        part = np.broadcast_to(
            np.arange(config.block_items), tiles.shape
        )[:, :, :r]
    cand_i = (
        part + (np.arange(nb) * config.block_items)[None, :, None]
    ).reshape(b, -1)
    cand_s = np.take_along_axis(qs, cand_i, axis=1)
    cand_s = np.where(cand_i < packed.num_items, cand_s, -np.inf)
    s = min(config.shortlist, cand_s.shape[1])
    if s < cand_s.shape[1]:
        top = np.argpartition(-cand_s, s - 1, axis=1)[:, :s]
    else:
        top = np.broadcast_to(np.arange(cand_s.shape[1]), cand_s.shape)
    return np.sort(np.take_along_axis(cand_i, top, axis=1), axis=1)


def mips_bytes(
    num_items: int,
    rank: int,
    batch: int,
    block_items: int = BLOCK_ITEMS,
    block_topk: int = 16,
    shortlist: int = 512,
) -> float:
    """HBM bytes the two-stage path moves for one query batch (the bench
    ``mips_topk`` GB/s denominator; the scan is bandwidth-bound, so GB/s
    on the PACKED table is the efficiency axis).

    Stage 1 reads the int8 table + scales once and re-reads the query
    block per item tile; it writes the [B, nb, R] candidate pair. Stage 2
    gathers shortlist f32 rows and writes the [B, S] pair.
    """
    padded = -(-num_items // block_items) * block_items
    nb = padded // block_items
    stage1 = (
        padded * rank                      # int8 table, one pass
        + nb * 4                           # scales
        + batch * rank * 4 * nb            # query block per tile
        + batch * nb * block_topk * 8      # candidate scores + indices
    )
    shortlist_rows = min(shortlist, nb * block_topk)
    stage2 = batch * shortlist_rows * (rank * 4 + 8 + 4)
    return float(stage1 + stage2)


def scan_bytes(num_items: int, rank: int, batch: int) -> float:
    """The full-scan counterpart: one f32 table pass plus the [B, items]
    score buffer write + the selection's read-back."""
    return float(
        num_items * rank * 4 + batch * rank * 4 + 2 * batch * num_items * 4
    )
