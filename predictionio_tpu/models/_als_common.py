"""Shared pieces of the ALS-backed templates (recommendation, e-commerce).

One source of truth for the behaviors both ALS templates must agree on:
mesh-aware CSR packing, the fingerprinted step-checkpoint wiring
(preemption safety, SURVEY §5.4), the seen-items map, and the rank+format
tail of their ``itemScores`` responses (predict and the vectorized batch
path must rank identically). The cooccurrence-based templates keep their
own tails: their exclusion sentinel is 0, not -inf.
"""

from __future__ import annotations

import hashlib
import logging

import numpy as np

from predictionio_tpu.parallel.als import (
    ALSConfig,
    ALSModel,
    als_fit,
    als_fit_streamed,
    block_paths,
    build_als_data,
)

logger = logging.getLogger("pio.als")


def prepare_als_data(
    ctx,
    params,
    users: np.ndarray,
    items: np.ndarray,
    values: np.ndarray,
    num_users: int,
    num_items: int,
    times: np.ndarray,
):
    """Pack COO interactions into padded CSR blocks sized for ctx's mesh.

    Rows pad to multiples of 8 * data_axis * model_axis: a model axis of
    1 (the default mesh) reproduces the historical layout, and a model
    axis > 1 makes the blocks ready for the ALX factor-sharded mode the
    fit side auto-selects on such meshes (resolve_factor_sharding).
    """
    config = ALSConfig(
        max_len=params.get_or("maxEventsPerUser", None),
        # length-bucketed packing: engine.json "buckets" (default 1 keeps
        # the single-block layout; the ML-20M bench uses 4)
        buckets=params.get_or("buckets", 1),
    )
    num_shards, model_shards = 1, 1
    try:
        num_shards = ctx.mesh.shape.get("data", 1)
        model_shards = ctx.mesh.shape.get("model", 1)
    except Exception:
        pass  # no devices available (pure-host tests)
    from predictionio_tpu.obs.trace import global_tracer

    with global_tracer().span("als.pack") as span:
        data = build_als_data(
            users,
            items,
            values,
            num_users,
            num_items,
            config,
            times=times,
            num_shards=num_shards,
            model_shards=model_shards,
        )
        # counts the packer has already: no pass over the data for them
        edges = len(users)
        span.set_attr("edges", edges)
        span.set_attr("data_shards", num_shards)
        span.set_attr("model_shards", model_shards)
        for name, side in (("by_row", data.by_row), ("by_col", data.by_col)):
            span.set_attr(name, {
                "retained_edges": edges - side.truncated,
                "padded_slots": side.padded_slots,
                "buckets": len(side.blocks),
            })
    return data


#: packing knobs the PREPARATOR consumes; a natural mistake is putting
#: them in the algorithm block (the reference template had no preparator
#: params), where they would be silently ignored
PACKING_PARAM_KEYS = ("maxEventsPerUser", "buckets")


def warn_misplaced_packing_params(algo_params, template: str) -> None:
    misplaced = [
        k for k in PACKING_PARAM_KEYS
        if algo_params.get_or(k, None) is not None
    ]
    if misplaced:
        logger.warning(
            "%s: %s configure the PREPARATOR (put them under "
            '"preparator": {"params": {...}} in engine.json); they are '
            "ignored in the algorithm block",
            template, ", ".join(misplaced),
        )


def resolve_factor_sharding(config: ALSConfig, mesh) -> ALSConfig:
    """Resolve ``factor_sharding="auto"`` against the actual mesh.

    On a pure-ALS template a model axis > 1 has exactly one use -- ALX
    factor sharding -- so "auto" (the template default) selects it
    whenever ``pio.mesh_shape`` configures such an axis, and plain data
    parallelism otherwise. Explicit "replicated"/"model" pass through to
    the library untouched (als_fit validates them).
    """
    import dataclasses

    if config.factor_sharding != "auto":
        return config
    try:
        model = mesh.shape.get("model", 1) if mesh is not None else 1
    except Exception:
        model = 1
    return dataclasses.replace(
        config, factor_sharding="model" if model > 1 else "replicated"
    )


def build_seen(users: np.ndarray, items: np.ndarray) -> dict[int, set[int]]:
    """user index -> set of interacted item indices (serving-time filter).

    Sorted-split construction: one stable argsort + one ``np.unique``
    boundary scan, so Python time is O(distinct users), not O(events)
    -- this runs on EVERY model build and the per-event Python loop it
    replaces was a measurable slice of large builds. The dict-of-sets
    return type is the serving contract (``_seen_indices`` and fold-in
    both mutate copies of it)."""
    users = np.asarray(users)
    if users.size == 0:
        return {}
    order = np.argsort(users, kind="stable")
    sorted_users = users[order]
    sorted_items = np.asarray(items)[order]
    uniq, starts = np.unique(sorted_users, return_index=True)
    bounds = np.append(starts[1:], sorted_users.size)
    return {
        int(u): set(sorted_items[s:e].tolist())
        for u, s, e in zip(uniq.tolist(), starts.tolist(), bounds.tolist())
    }


def score_buffer_rows(num_items: int, floor: int = 64, cap: int | None = None) -> int:
    """Rows per batch-predict slice so the host [rows, items] score buffer
    stays ~200 MB f32 regardless of catalog size (a fixed row count would
    scale memory with num_items). One definition for every template's
    batch path."""
    rows = max(floor, 50_000_000 // max(num_items, 1))
    return min(rows, cap) if cap else rows


def partition_user_queries(user_index: dict[str, int], queries):
    """Split (qid, query) pairs into known-user rows [(qid, q, user_idx)]
    and fallback pairs [(qid, q)] -- the shared head of every template's
    batch_predict."""
    user_rows, fallback = [], []
    for qid, q in queries:
        user_idx = (
            user_index.get(str(q["user"]))
            if isinstance(q, dict) and "user" in q
            else None
        )
        if user_idx is None:
            fallback.append((qid, q))
        else:
            user_rows.append((qid, q, user_idx))
    return user_rows, fallback


class Shortlist:
    """Compact view of one request's score vector: the stage-2 contract
    of the two-stage MIPS retrieval path (``ops/mips``).

    ``indices`` are ascending catalog indices, ``scores`` their EXACT f32
    re-ranked scores (writable copy -- the seen/blackList filters write
    -inf through ``__setitem__``). The ascending order is load-bearing:
    ``topk_order``'s stable sort over the compact array then breaks score
    ties by global catalog index, byte-matching the full scan whenever
    the shortlist contains the true top-k. Items outside the shortlist
    silently absorb filter writes (they were not going to be served) and
    never appear in responses.
    """

    __slots__ = ("indices", "scores", "num_items")

    def __init__(self, indices: np.ndarray, scores: np.ndarray, num_items: int):
        self.indices = np.asarray(indices)
        self.scores = np.array(scores)  # writable copy: filters mutate it
        self.num_items = num_items

    @property
    def shape(self) -> tuple:
        """Mimics the dense score vector so mask-building code
        (``scores.shape[0]``) is retrieval-mode agnostic."""
        return (self.num_items,)

    def __setitem__(self, idx: int, value) -> None:
        pos = int(np.searchsorted(self.indices, idx))
        if pos < self.indices.size and self.indices[pos] == idx:
            self.scores[pos] = value

    def where_allowed(self, allowed: np.ndarray, sentinel=-np.inf) -> "Shortlist":
        """Apply a dense [num_items] bool mask (whiteList/categories)
        compactly: O(shortlist), never materializing dense scores.

        ``indices`` may carry ``num_items`` sentinels (search padding,
        guaranteed on catalogs smaller than the candidate budget) which
        are out of range for the dense mask -- they clamp to a valid row
        for the gather and always mask to ``sentinel``."""
        valid = self.indices < self.num_items
        safe = np.minimum(self.indices, max(self.num_items - 1, 0))
        self.scores = np.where(valid & allowed[safe], self.scores, sentinel)
        return self

    def copy(self) -> "Shortlist":
        return Shortlist(self.indices, self.scores, self.num_items)


def resolve_retrieval(params):
    """Parse the algorithm-params ``"retrieval"`` block into a
    ``RetrievalConfig`` (raising on unknown modes/knobs -- validated at
    train time so a typo fails the build, not the first query)."""
    from predictionio_tpu.ops.mips import RetrievalConfig

    return RetrievalConfig.from_params(params.get_or("retrieval", None))


def retrieval_index(als_model: ALSModel, retrieval, kind: str = "dot"):
    """The lazily-built, model-cached device ``RetrievalIndex`` for mips
    mode, or None for scan mode (callers fall through to the host
    matmul). ``kind="cosine"`` indexes the norm-normalized item factors
    so similar-items queries run as MIPS over unit vectors (sum of anchor
    cosines == dot with the summed normalized anchors). The cache lives
    on the model object (the ``_item_norms`` precedent) and never
    pickles; fold-in publishes a NEW ALSModel, so swapped factor tables
    can never serve a stale index."""
    if retrieval is None or retrieval.mode != "mips":
        return None
    from predictionio_tpu.ops.mips import RetrievalIndex

    cache = getattr(als_model, "_retrieval_cache", None)
    if cache is None:
        cache = {}
        als_model._retrieval_cache = cache
    key = (kind, retrieval)
    index = cache.get(key)
    if index is None:
        if kind == "cosine":
            norms = np.maximum(als_model.item_norms, 1e-12)
            table = als_model.item_factors / norms[:, None]
        else:
            table = als_model.item_factors
        index = RetrievalIndex(table, retrieval)
        cache[key] = index
    return index


def score_known_user(als_model: ALSModel, user_idx: int, retrieval=None):
    """One user's item scores: the dense vector (scan) or the stage-2
    ``Shortlist`` (mips). The unbatched predict path and
    ``batch_score_known_users`` both route through the same index, so
    batched and unbatched responses rank identically in either mode.

    Mips re-ranks on the HOST: the device search picks the shortlist, but
    the response scores come from the same gathered-row matvec the scan
    path runs (``score_items_for_user``'s einsum, whose per-row reduction
    is height-independent), so they are bitwise the full product at those
    rows -- a shortlist that contains the true top-k yields a
    byte-identical response, ULP ties included."""
    index = retrieval_index(als_model, retrieval)
    if index is None:
        return als_model.score_items_for_user(user_idx)
    idx, _ = index.search(als_model.user_factors[user_idx][None, :])
    return _host_rerank(als_model, idx[0], user_idx)


def _host_rerank(als_model: ALSModel, short: np.ndarray, user_idx: int) -> "Shortlist":
    """Exact scores for one user's shortlist, as the scan path computes
    them: a gathered-row f32 matvec, bitwise equal to
    ``score_items_for_user`` at the shortlisted rows. Sentinel slots
    (index == num_items, search padding) stay -inf and drop in the
    format tail."""
    num_items = als_model.item_factors.shape[0]
    in_range = short < num_items
    vals = np.einsum(
        "ik,k->i",
        als_model.item_factors[short[in_range]],
        als_model.user_factors[user_idx],
    )
    scores = np.full(short.shape, -np.inf, vals.dtype)
    scores[in_range] = vals
    return Shortlist(short, scores, num_items)


def similar_item_scores(als_model: ALSModel, anchors: list[int], retrieval=None):
    """Summed cosine similarity of all items against the anchors: dense
    (scan) or a ``Shortlist`` through the cosine index (mips), where the
    stage-1 query is the sum of the anchors' unit vectors -- the same
    ranking objective, one packed-table scan instead of one dense pass
    per anchor. The shortlist then re-ranks on the host by replaying the
    scan path's per-anchor arithmetic (``similar_items`` gathered to the
    shortlist rows, summed in anchor order), so the response is bitwise
    the scan response whenever the shortlist holds the true top-k."""
    index = retrieval_index(als_model, retrieval, kind="cosine")
    if index is None:
        sims = None
        for idx in anchors:
            s = als_model.similar_items(idx)
            sims = s if sims is None else sims + s
        return sims
    norms = np.maximum(als_model.item_norms[anchors], 1e-12)
    query = (als_model.item_factors[anchors] / norms[:, None]).sum(axis=0)
    idx, _ = index.search(query[None, :])
    short = idx[0]
    num_items = als_model.item_factors.shape[0]
    in_range = short < num_items
    rows = short[in_range]
    sims = None
    for a in anchors:
        v = als_model.item_factors[a]
        row_norms = als_model.item_norms[rows] * (als_model.item_norms[a] + 1e-12)
        s = np.einsum("ik,k->i", als_model.item_factors[rows], v) / np.maximum(
            row_norms, 1e-12
        )
        sims = s if sims is None else sims + s
    scores = np.full(short.shape, -np.inf, sims.dtype if sims is not None else np.float32)
    if sims is not None:
        scores[in_range] = sims
    return Shortlist(short, scores, num_items)


def batch_score_known_users(
    als_model: ALSModel, user_rows, respond, *, retrieval=None
) -> list:
    """Score known users in bounded slices over the host-cached factors;
    ``respond(scores_row, qid, query, user_idx)`` builds each response.
    One definition for every ALS-factor batch path.

    Scan mode materializes [rows, items] f32 matmul slices; mips mode
    (``retrieval: {"mode": "mips"}``) runs the device-resident two-stage
    kernel and hands ``respond`` a ``Shortlist`` per row -- peak host
    score memory drops from O(items) to O(shortlist) per row, which is
    what lifts the catalog cap (ISSUE 16 / ALX arxiv 2112.02194).
    """
    out = []
    index = retrieval_index(als_model, retrieval)
    if index is not None:
        # the buffer is [rows, shortlist] now; budget rows against it
        rows_per_slice = score_buffer_rows(index.config.shortlist)
        for start in range(0, len(user_rows), rows_per_slice):
            part = user_rows[start : start + rows_per_slice]
            idxs = np.fromiter((u for _, _, u in part), dtype=np.int64)
            short_idx, _ = index.search(als_model.user_factors[idxs])
            # host re-rank per row with the single-query matvec shape:
            # batched mips responses stay bitwise equal to unbatched ones
            # (scan's batched sgemm drifts a ULP from its own sgemv path)
            out.extend(
                respond(
                    _host_rerank(als_model, short_idx[row], user_idx),
                    qid, q, user_idx,
                )
                for row, (qid, q, user_idx) in enumerate(part)
            )
        return out
    rows_per_slice = score_buffer_rows(als_model.item_factors.shape[0])
    for start in range(0, len(user_rows), rows_per_slice):
        part = user_rows[start : start + rows_per_slice]
        idxs = np.fromiter((u for _, _, u in part), dtype=np.int64)
        # einsum, not sgemm: BLAS results depend on matrix shape, so the
        # batched product would sit a ULP off ``score_items_for_user`` and
        # off the mips host re-rank -- einsum's per-row reduction makes
        # every scoring path (scan/mips, batched/unbatched) bitwise equal,
        # at ~2x sgemm for the k=16 contraction on scan-sized catalogs
        scores = np.einsum(
            "bk,ik->bi", als_model.user_factors[idxs], als_model.item_factors
        )
        out.extend(
            respond(scores[row], qid, q, user_idx)
            for row, (qid, q, user_idx) in enumerate(part)
        )
    return out


def topk_order(scores: np.ndarray, num: int) -> np.ndarray:
    """Indices of the top-``num`` scores, descending, ties by ascending
    position -- a pure function of the (score, position) multiset.

    Selection is O(items) argpartition + O(num log num) sort instead of a
    full O(items log items) argsort: this runs once PER REQUEST on the
    serving hot path, and at large catalogs it is what the batched
    scorer's amortized matmul would otherwise hide behind. The canonical
    tie order matters beyond aesthetics: argpartition permutes its input
    arbitrarily, so "stable sort of the partitioned slice" would order
    equal scores differently for a dense vector than for a mips
    ``Shortlist`` holding the same values -- threshold ties are therefore
    re-selected by position explicitly. NaN/-inf sentinels rank after
    every finite score. ONE definition for every template's ranking
    tail -- batched, unbatched, scan, and mips responses must tie-break
    identically.
    """
    n = scores.shape[0]
    if 0 < num < n:
        cand = np.argpartition(-scores, num - 1)[:num]
        vals = scores[cand]
        if not np.isnan(vals).any():
            t = vals.min()
            head = np.flatnonzero(scores > t)
            # lowest positions among scores == t fill the remaining slots
            ties = np.flatnonzero(scores == t)[: num - head.size]
            cand = np.concatenate([head, ties])
            return cand[np.lexsort((cand, -scores[cand]))]
        # NaN reached the top slice: fall through to the full stable sort
        # (argsort ranks NaN last; ascending-position ties come free)
    return np.argsort(-scores, kind="stable")[:num]


def topk_item_scores(item_ids: list[str], scores, num: int) -> dict:
    """Rank + format tail shared by every template response: descending
    top-``num``, excluded entries carried as -inf and dropped here. A
    ``Shortlist`` ranks over its compact arrays (same ``topk_order``, so
    mips- and scan-mode responses tie-break identically whenever the
    shortlist holds the true top-k); the finite mask is one vectorized
    pass over the top-k slice, not a per-item ``np.isfinite`` call."""
    if isinstance(scores, Shortlist):
        order = topk_order(scores.scores, num)
        finite = np.isfinite(scores.scores[order])
        return {
            "itemScores": [
                {"item": item_ids[int(scores.indices[j])],
                 "score": float(scores.scores[j])}
                for j, ok in zip(order, finite)
                if ok
            ]
        }
    order = topk_order(scores, num)
    finite = np.isfinite(scores[order])
    return {
        "itemScores": [
            {"item": item_ids[j], "score": float(scores[j])}
            for j, ok in zip(order, finite)
            if ok
        ]
    }


def _vocab_hash(ids: list[str]) -> str:
    h = hashlib.sha256()
    for s in ids:
        h.update(s.encode())
        h.update(b"\x00")
    return h.hexdigest()[:16]


def fit_with_checkpoint(
    ctx,
    als_data,
    config: ALSConfig,
    mesh,
    *,
    user_ids: list[str],
    item_ids: list[str],
    interval: int,
    name: str = "als",
) -> ALSModel:
    """``als_fit`` wrapped in fingerprinted step checkpoints.

    Checkpointed factors are only meaningful against the id vocabularies
    they were trained on. Events ingested between crash and resume change
    num_users/num_items -- restoring would crash on shape mismatch or
    silently misalign factor rows with the new vocabulary. Counts alone
    are not enough (delete one user + add another keeps the count but
    renumbers rows), so the vocabularies themselves are hashed too. A
    mismatch discards the checkpoints and trains fresh with a warning.

    ``interval`` <= 0 disables checkpointing entirely.

    With ``pio train --profile`` (runtime conf ``pio.profile``) a per-step
    telemetry journal (``<profile-dir>/<name>-telemetry.jsonl``: wall
    time, edges/sec, achieved GB/s against the bytes-moved model,
    recompile count) is written alongside the ``jax.profiler`` trace the
    workflow captures -- the cheap always-parseable view vs the deep one.
    """
    config = resolve_factor_sharding(config, mesh)
    telemetry = _build_telemetry(ctx, als_data, config, mesh, name)
    checkpoint = ctx.checkpoint_manager(name) if interval > 0 else None
    init, start_iteration, callback = None, 0, None
    if checkpoint is not None:
        num_users, num_items = len(user_ids), len(item_ids)
        fingerprint = {
            "num_users": num_users,
            "num_items": num_items,
            "user_vocab": _vocab_hash(user_ids),
            "item_vocab": _vocab_hash(item_ids),
            "rank": config.rank,
        }
        latest = checkpoint.latest_step()
        if latest is not None:  # only a --resume run can see a step here
            meta = checkpoint.read_meta()
            if meta != fingerprint:
                logger.warning(
                    "%s checkpoint fingerprint %s does not match current"
                    " dataset %s (events changed between crash and resume?);"
                    " discarding checkpoints and training fresh",
                    name,
                    meta,
                    fingerprint,
                )
                checkpoint.reset()
            else:
                state = checkpoint.restore(
                    {
                        "users": np.zeros((num_users, config.rank), np.float32),
                        "items": np.zeros((num_items, config.rank), np.float32),
                        "iteration": 0,
                    }
                )
                init = (state["users"], state["items"])
                start_iteration = int(state["iteration"]) + 1
        checkpoint.write_meta(fingerprint)

        def callback(it, users_np, items_np):
            checkpoint.save(
                it, {"users": users_np, "items": items_np, "iteration": it}
            )

    from predictionio_tpu.obs.trace import global_tracer
    from predictionio_tpu.parallel.stream import StreamedALSData

    # alsFeed "streamed": the preparator handed a disk block store, not
    # resident edge arrays -- train through ALX device-resident epochs.
    # Same checkpoints, same callback contract, bit-identical factors at
    # equal shapes (als_fit_streamed's own invariant).
    fit = (
        als_fit_streamed if isinstance(als_data, StreamedALSData) else als_fit
    )
    # which half-step path each block of the program about to be built takes
    paths = block_paths(als_data, config, mesh)
    try:
        with global_tracer().span(
            "als.fit",
            attrs={"name": name, "iterations": config.iterations,
                   **_layout_attrs(paths, config, mesh)},
        ):
            model = fit(
                als_data,
                config,
                mesh,
                callback=callback,
                callback_interval=interval,
                init=init,
                start_iteration=start_iteration,
                telemetry=telemetry,
            )
    finally:
        if telemetry is not None:
            telemetry.close()
    if checkpoint is not None:
        checkpoint.close()
    return model


def _layout_attrs(paths: dict, config: ALSConfig, mesh) -> dict:
    """What the span ``als.fit`` and the ``--profile`` journal's meta line say
    of the program's layout: the mesh, where the factors live, and how the
    blocks are worked (``block_paths``)."""
    return {
        "mesh_data": mesh.shape["data"],
        "mesh_model": mesh.shape.get("model", 1),
        "factor_sharding": config.factor_sharding,
        "blocks": paths["blocks"],
        "blocks_chunked": paths["chunked"],
        "max_chunks": paths["max_chunks"],
        "blocked_solve": paths["blocked_solve"],
        "dual_solve": paths["dual_solve"],
    }


def _build_telemetry(ctx, als_data, config: ALSConfig, mesh, name: str):
    """A ``TrainTelemetry`` journal when the run is profiled
    (``pio.profile`` runtime conf), else None (the un-profiled loop must
    not pay per-step device syncs)."""
    import os

    profile_dir = (getattr(ctx, "runtime_conf", None) or {}).get("pio.profile")
    if not profile_dir:
        return None
    try:
        from predictionio_tpu.obs.telemetry import TrainTelemetry
        from predictionio_tpu.parallel.als import (
            modeled_bytes_per_iteration,
            real_edges,
        )

        from predictionio_tpu.parallel.mesh import local_mesh

        mesh = mesh or local_mesh(1, 1)  # as als_fit defaults it
        paths = block_paths(als_data, config, mesh)
        itemsize = 2 if config.dtype == "bfloat16" else 4
        return TrainTelemetry(
            os.path.join(str(profile_dir), f"{name}-telemetry.jsonl"),
            edges=real_edges(als_data),
            modeled_bytes_per_iter=modeled_bytes_per_iteration(
                als_data, config.rank, itemsize
            ),
            meta={
                "name": name,
                "rank": config.rank,
                **_layout_attrs(paths, config, mesh),
                "platform": mesh.devices.flat[0].platform,
                "dtype": config.dtype,
                "iterations": config.iterations,
            },
        )
    except Exception:
        # telemetry must never fail a training run
        logger.warning("profile telemetry setup failed", exc_info=True)
        return None
