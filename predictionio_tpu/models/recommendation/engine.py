"""DASE components of the recommendation template.

Query contract (reference template quickstart):
``{"user": "u1", "num": 4}`` -> ``{"itemScores": [{"item": ..., "score": ...}]}``
plus item-based queries ``{"items": [...], "num": k}`` for similarity.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass

import numpy as np

from predictionio_tpu.controller import (
    DataSource,
    Engine,
    EvalInfo,
    FirstServing,
    Preparator,
    TPUAlgorithm,
)
from predictionio_tpu.controller.base import SanityCheck
from predictionio_tpu.data.store import PEventStore
from predictionio_tpu.models._als_common import (
    batch_score_known_users,
    build_seen,
    fit_with_checkpoint,
    partition_user_queries,
    prepare_als_data,
    resolve_retrieval,
    retrieval_index,
    score_known_user,
    similar_item_scores,
    topk_item_scores,
    warn_misplaced_packing_params,
)
from predictionio_tpu.models._streaming import (
    StreamingHandle,
    build_streaming_handle,
    streaming_handle_or_none,
)
from predictionio_tpu.parallel.als import ALSConfig, ALSModel

logger = logging.getLogger("pio.recommendation")


@dataclass
class RatingsData(SanityCheck):
    """COO interactions + id vocabularies."""

    users: np.ndarray       # int indices
    items: np.ndarray
    ratings: np.ndarray     # float32
    times: np.ndarray       # float64 epoch seconds
    user_ids: list[str]
    item_ids: list[str]
    #: carried for serving-time live event-store reads (seenFilter "live")
    app_name: str = ""
    event_names: list[str] = None
    #: True when built by the streaming sharded reader: edge arrays are
    #: empty (only the vocabularies are materialized)
    streamed: bool = False
    channel_name: str = None   # non-default channel the data came from
    #: True for read_eval's fold copies: live seen-filtering is downgraded
    #: to the trained-in map there (the held-out events still exist in the
    #: store, and a live read would exclude every 'actual' item)
    eval_fold: bool = False

    def sanity_check(self) -> None:
        if self.users.size == 0:
            raise ValueError(
                "no rating events found -- check appName and eventNames"
            )

    @property
    def num_users(self) -> int:
        return len(self.user_ids)

    @property
    def num_items(self) -> int:
        return len(self.item_ids)


#: the sharded-reader training handle (see models/_streaming): the
#: preparator streams the chunked scan and each process retains only its
#: data-shard's edges; requires seenFilter "live"
StreamingRatings = StreamingHandle


class RecommendationDataSource(DataSource):
    """Reads rating-like events into COO form.

    Params: ``appName`` (required), ``eventNames`` (default ["rate", "buy"]),
    ``ratingKey`` (property holding the rating; "buy"-style events without it
    score 1.0), ``evalK``/``evalFolds`` for read_eval; ``"reader":
    "streaming"`` switches read_training to the retention-bounded sharded
    reader (see StreamingRatings).
    """

    def _read(self) -> RatingsData:
        event_names = self.params.get_or("eventNames", ["rate", "buy"])
        ds = PEventStore.dataset(
            self.params.appName,
            rating_key=self.params.get_or("ratingKey", "rating"),
            event_names=event_names,
            target_entity_type="item",
        )
        ratings = np.nan_to_num(ds.ratings, nan=1.0)  # implicit events -> 1.0
        valid = ds.target_entity_ids >= 0
        return RatingsData(
            users=ds.entity_ids[valid],
            items=ds.target_entity_ids[valid],
            ratings=ratings[valid],
            times=ds.event_times[valid],
            user_ids=ds.entity_id_vocab,
            item_ids=ds.target_entity_id_vocab,
            app_name=self.params.appName,
            event_names=list(event_names),
        )

    def read_training(self, ctx):
        handle = streaming_handle_or_none(
            self.params, ["rate", "buy"],
            empty_message="no rating events found -- check appName and "
            "eventNames",
        )
        return handle if handle is not None else self._read()

    def online_handle(self):
        """The continuous-learning loop's scan descriptor: same identity
        (app/channel/event names/rating key) as the training read, so the
        snapshot the loop refreshes is the one training replays."""
        return build_streaming_handle(
            self.params, ["rate", "buy"],
            empty_message="no rating events found -- check appName and "
            "eventNames",
        )

    def read_eval(self, ctx):
        """Time-ordered k-fold: hold out each fold's interactions as
        (query, actual) pairs asking for top-`evalK` recommendations."""
        data = self._read()
        folds = self.params.get_or("evalFolds", 3)
        eval_k = self.params.get_or("evalK", 10)
        out = []
        for f in range(folds):
            test_mask = (np.arange(data.users.size) % folds) == f
            train = RatingsData(
                users=data.users[~test_mask],
                items=data.items[~test_mask],
                ratings=data.ratings[~test_mask],
                times=data.times[~test_mask],
                user_ids=data.user_ids,
                item_ids=data.item_ids,
                app_name=data.app_name,
                event_names=data.event_names,
                eval_fold=True,
            )
            qa = {}
            for u, i in zip(data.users[test_mask], data.items[test_mask]):
                qa.setdefault(u, set()).add(i)
            pairs = [
                (
                    {"user": data.user_ids[u], "num": eval_k},
                    [data.item_ids[i] for i in items],
                )
                for u, items in qa.items()
            ]
            out.append((train, EvalInfo(fold=f), pairs))
        return out

    def _read_replay_source(self, ctx) -> RatingsData:
        """``_read()``, served from a pinned snapshot generation's memmap
        columns when ``--snapshot-mode`` enables it: the whole replay eval
        (prefix training included) then does zero SQL scans, and reruns
        against the same generation replay identical bytes. Snapshot
        misses degrade to the direct store read, never fail the eval."""
        from predictionio_tpu.data.snapshot import snapshot_settings
        from predictionio_tpu.models._streaming import snapshot_ratings_arrays

        runtime_conf = getattr(ctx, "runtime_conf", None) or {}
        mode, _root = snapshot_settings(runtime_conf)
        if mode != "off":
            handle = build_streaming_handle(
                self.params, ["rate", "buy"],
                empty_message="no rating events found -- check appName and "
                "eventNames",
            )
            arrays = snapshot_ratings_arrays(handle, runtime_conf)
            if arrays is not None:
                users, items, ratings, times, user_ids, item_ids = arrays
                return RatingsData(
                    users=users, items=items, ratings=ratings, times=times,
                    user_ids=user_ids, item_ids=item_ids,
                    app_name=self.params.appName,
                    event_names=list(
                        self.params.get_or("eventNames", ["rate", "buy"])
                    ),
                    channel_name=self.params.get_or("channelName", None),
                )
            logger.warning(
                "replay snapshot unavailable; falling back to the direct"
                " store scan"
            )
        return self._read()

    def read_replay(self, ctx, spec):
        """Time-travel replay fold (``pio eval --replay``): train on
        ratings strictly before the boundary, ask for each held-out
        user's top-``spec.k`` (cold holdout users -- no training events
        -- stay in the fold and score as misses). The fold carries
        ``eval_fold=True`` so a ``seenFilter: "live"`` variant downgrades
        to the trained-in map, exactly like the k-fold path. With
        ``--snapshot-mode use`` the prefix replays a pinned snapshot
        generation's memmaps instead of the SQL store (PR 17's gap)."""
        from predictionio_tpu.eval.split import ReplayFold, split_interactions

        data = self._read_replay_source(ctx)
        cut = split_interactions(data.users, data.items, data.times, spec)
        train = RatingsData(
            users=data.users[cut.train_mask],
            items=data.items[cut.train_mask],
            ratings=data.ratings[cut.train_mask],
            times=data.times[cut.train_mask],
            user_ids=data.user_ids,
            item_ids=data.item_ids,
            app_name=data.app_name,
            event_names=data.event_names,
            eval_fold=True,
        )
        pairs = [
            (
                {"user": data.user_ids[u], "num": spec.k},
                [data.item_ids[int(i)] for i in items],
            )
            for u, items in cut.holdout.items()
        ]
        return ReplayFold(train, pairs, cut.bounds)


class RecommendationPreparator(Preparator):
    """Packs COO ratings into padded CSR blocks sized for the mesh.

    Preparator params: ``buckets`` (length-bucketed packing),
    ``maxEventsPerUser`` (history cap). A StreamingRatings handle (the
    DataSource's ``"reader": "streaming"`` mode) routes through the
    retention-bounded sharded reader instead of full host arrays.
    """

    def prepare(self, ctx, training_data):
        if isinstance(training_data, StreamingRatings):
            return self._prepare_streaming(ctx, training_data)
        als_data = prepare_als_data(
            ctx,
            self.params,
            training_data.users,
            training_data.items,
            training_data.ratings,
            training_data.num_users,
            training_data.num_items,
            times=training_data.times,
        )
        return training_data, als_data

    def _prepare_streaming(self, ctx, src: StreamingRatings):
        from predictionio_tpu.models._streaming import build_streaming_als

        users_enc, items_enc, als_data = build_streaming_als(
            src, self.params, ctx.mesh, runtime_conf=ctx.runtime_conf
        )
        # vocabularies materialized by the scan; edge arrays stay empty --
        # the whole point of the streaming path
        ratings_like = RatingsData(
            users=np.empty(0, np.int64),
            items=np.empty(0, np.int64),
            ratings=np.empty(0, np.float32),
            times=np.empty(0, np.float64),
            user_ids=users_enc.ids,
            item_ids=items_enc.ids,
            app_name=src.app_name,
            event_names=src.event_names,
            streamed=True,
            channel_name=src.channel_name,
        )
        return ratings_like, als_data


@dataclass
class RecommendationModel:
    """Host-side serving model: factor matrices + vocab maps.

    Factors are cached host-side for sub-ms top-k scoring (SURVEY.md
    section 7.3: avoid per-request host<->device copies for factor lookups).
    """

    als: ALSModel
    user_index: dict[str, int]
    item_ids: list[str]
    item_index: dict[str, int]
    seen: dict[int, set[int]]  # user -> rated item indices (for filtering)
    #: "model": the seen map above (O(edges) host memory, zero-latency).
    #: "live": per-query event-store read (the e-commerce template's
    #: pattern) -- the serving model stays O(entities), which is what a
    #: sharded-reader-scale catalog needs. Old pickled blobs predate
    #: these fields; readers go through getattr with defaults.
    seen_mode: str = "model"
    app_name: str = ""
    event_names: list[str] = None
    channel_name: str = None


def _seen_indices(model: "RecommendationModel", query, user_idx: int,
                  cache: dict | None = None) -> set[int]:
    """The user's already-interacted item indices for the unseenOnly filter.

    "model" mode reads the trained-in seen map. "live" mode queries the
    event store per request (the e-commerce template's pattern): the
    serving model stays O(entities) -- required at sharded-reader catalog
    scale, where no single host can hold an O(edges) map -- and newly
    ingested interactions filter immediately without a retrain. A store
    error degrades to "nothing seen" with a log line (serving must not
    500 because a backend blinked).
    """
    if getattr(model, "seen_mode", "model") != "live":
        return model.seen.get(user_idx, set())
    from predictionio_tpu.models._streaming import live_seen_indices

    return live_seen_indices(model, str(query.get("user")), cache)


class ALSAlgorithm(TPUAlgorithm):
    """ALS on the device mesh (MLlib ALS / ALS.trainImplicit parity).

    Params: rank, numIterations, lambda, alpha, implicitPrefs, seed,
    checkpointInterval (iterations between step checkpoints; 0 disables --
    the preemption-safety net `pio train --resume` continues from), and
    ``retrieval`` (``{"mode": "scan"|"mips", ...}``: scan is the full
    [rows, items] host matmul; mips serves through the device-resident
    two-stage quantized top-k of ``ops/mips`` -- docs/templates.md lists
    the knobs and the recall contract).
    """

    @property
    def _retrieval(self):
        conf = getattr(self, "_retrieval_conf", None)
        if conf is None:
            conf = resolve_retrieval(self.params)
            self._retrieval_conf = conf
        return conf

    def _config(self) -> ALSConfig:
        p = self.params
        return ALSConfig(
            rank=p.get_or("rank", 16),
            iterations=p.get_or("numIterations", 10),
            reg=p.get_or("lambda", 0.1),
            alpha=p.get_or("alpha", 40.0),
            implicit=p.get_or("implicitPrefs", False),
            seed=p.get_or("seed", 0),
            # "bfloat16" halves factor HBM/ICI traffic on TPU (ALX-style
            # mixed precision: f32 Grams + solve, bf16 storage/gathers)
            dtype=p.get_or("factorDtype", "float32"),
            # "auto": ALX model-sharded factors whenever pio.mesh_shape
            # configures a model axis > 1 (resolve_factor_sharding)
            factor_sharding=p.get_or("factorSharding", "auto"),
            # a vestige (``ALSConfig.solver``): an engine.json that still
            # says "pallas" fails loudly; goes with benchmarks/drivers/
            # als_train.py:92 and als_train_sharded.py:125 (ROADMAP.md)
            solver=p.get_or("alsSolver", "auto"),
        )

    def train(self, ctx, prepared) -> RecommendationModel:
        ratings_data, als_data = prepared
        warn_misplaced_packing_params(self.params, "recommendation")
        self._retrieval  # a retrieval typo fails the build, not a query
        streamed = getattr(ratings_data, "streamed", False)
        seen_mode = self.params.get_or(
            "seenFilter", "live" if streamed else "model"
        )
        if seen_mode not in ("model", "live"):
            raise ValueError(
                f"seenFilter must be 'model' or 'live', got {seen_mode!r}"
            )
        if streamed and seen_mode == "model":
            raise ValueError(
                "the streaming reader materializes no edges, so there is "
                'no O(edges) seen map to train in; use "seenFilter": "live"'
            )
        if seen_mode == "live" and getattr(ratings_data, "eval_fold", False):
            # a live read sees the WHOLE store -- including the held-out
            # test events -- and would score every 'actual' item -inf,
            # collapsing fold metrics to zero. Evaluation folds carry
            # their train-edge arrays, so the trained-in map is both
            # correct and available.
            logger.info(
                "seenFilter 'live' downgraded to 'model' for this "
                "evaluation fold (a live read would exclude held-out items)"
            )
            seen_mode = "model"
        model = fit_with_checkpoint(
            ctx,
            als_data,
            self._config(),
            self.mesh_or_none(ctx),
            user_ids=ratings_data.user_ids,
            item_ids=ratings_data.item_ids,
            interval=self.params.get_or("checkpointInterval", 5),
        )
        # "live" keeps the serving model O(entities): no O(edges) seen map
        seen = (
            build_seen(ratings_data.users, ratings_data.items)
            if seen_mode == "model" else {}
        )
        return RecommendationModel(
            als=model,
            user_index={uid: idx for idx, uid in enumerate(ratings_data.user_ids)},
            item_ids=ratings_data.item_ids,
            item_index={iid: idx for idx, iid in enumerate(ratings_data.item_ids)},
            seen=seen,
            seen_mode=seen_mode,
            app_name=ratings_data.app_name,
            event_names=ratings_data.event_names,
            # without this, a streaming build on a non-default channel
            # serves live seen-filter lookups against the DEFAULT channel
            # (finds nothing, silently stops excluding seen items)
            channel_name=getattr(ratings_data, "channel_name", None),
        )

    def warm_up(self, model: RecommendationModel) -> None:
        model.als.item_norms  # build the similar-items norm cache at deploy
        # mips mode: pack + compile the retrieval index at deploy, not on
        # the first query (dot for user scoring, cosine for similar-items)
        retrieval_index(model.als, self._retrieval)
        retrieval_index(model.als, self._retrieval, kind="cosine")

    supports_fold_in = True

    def shard_model(
        self, model: RecommendationModel, shard: int, num_shards: int
    ) -> RecommendationModel:
        """Keep only the user rows ``shardmap.shard_of`` assigns to
        ``shard``; item factors, item vocab, and the norm caches'
        inputs are replicated untouched.

        Row scoring is per-row (einsum over one user's factor vector), so
        compacting the user table cannot change a kept user's scores by a
        bit. Users filtered OUT of this shard simply miss ``user_index``
        -- the cold-user path -- which is correct because the frontend
        routes their queries to the owning shard; a userless or
        misrouted query sees only replicated state and answers exactly
        as every sibling would.
        """
        if num_shards <= 1:
            return model
        from predictionio_tpu.serving.shardmap import shard_of

        # original row order preserved: renumbering must be a pure
        # compaction, never a reorder
        by_row = sorted(model.user_index.items(), key=lambda kv: kv[1])
        kept = [
            (uid, row) for uid, row in by_row
            if shard_of(uid, num_shards) == shard
        ]
        rank = model.als.user_factors.shape[1] if model.als.user_factors.ndim == 2 else 0
        if kept:
            rows = np.asarray([row for _, row in kept], dtype=np.int64)
            user_factors = np.ascontiguousarray(model.als.user_factors[rows])
        else:
            user_factors = np.empty(
                (0, rank), dtype=model.als.user_factors.dtype
            )
        seen = {
            new_row: model.seen[old_row]
            for new_row, (_, old_row) in enumerate(kept)
            if old_row in model.seen
        }
        return RecommendationModel(
            als=ALSModel(
                user_factors=user_factors,
                item_factors=model.als.item_factors,
            ),
            user_index={uid: new for new, (uid, _) in enumerate(kept)},
            item_ids=model.item_ids,
            item_index=model.item_index,
            seen=seen,
            seen_mode=getattr(model, "seen_mode", "model"),
            app_name=getattr(model, "app_name", ""),
            event_names=getattr(model, "event_names", None),
            channel_name=getattr(model, "channel_name", None),
        )

    def fold_in(self, model: RecommendationModel, delta) -> RecommendationModel | None:
        """Continuous-learning hook (``pio retrain --follow``): re-solve
        the delta window's touched user rows against the frozen item
        factors (``online.foldin``), extend vocabularies for new
        users/items (new items carry zero factors until the next full
        retrain -- the staleness budget bounds how long that lasts), and
        absorb the window into a trained-in seen map. Returns a NEW model;
        the serving swap protocol relies on the old one staying intact."""
        from predictionio_tpu.online.foldin import fold_in_als_model

        result = fold_in_als_model(
            model.als,
            model.user_index,
            model.item_ids,
            model.item_index,
            delta,
            self._config(),
            # the training read scores property-less events 1.0
            rating_default=1.0,
        )
        if result is None:
            return None
        seen = model.seen
        if getattr(model, "seen_mode", "model") == "model" and result.window_pairs is not None:
            seen = {u: set(s) for u, s in model.seen.items()}
            for u, i in result.window_pairs.tolist():
                seen.setdefault(int(u), set()).add(int(i))
        return RecommendationModel(
            als=result.als,
            user_index=result.user_index,
            item_ids=result.item_ids,
            item_index=result.item_index,
            seen=seen,
            seen_mode=getattr(model, "seen_mode", "model"),
            app_name=getattr(model, "app_name", ""),
            event_names=getattr(model, "event_names", None),
            channel_name=getattr(model, "channel_name", None),
        )

    def predict(self, model: RecommendationModel, query) -> dict:
        num = int(query.get("num", 10))
        if "user" in query:
            return self._recommend_for_user(model, query, num)
        if "items" in query:
            return self._similar_items(model, query, num)
        raise ValueError("query must contain 'user' or 'items'")

    def batch_predict(self, model: RecommendationModel, queries):
        """Vectorized bulk scoring: all known-user recommendation queries in
        one chunk score as a SINGLE [B, K] @ [K, items] matmul instead of B
        gemvs + python per query (the reference's P2LAlgorithm.batchPredict
        parallelism, as one MXU-shaped product). Cold users and
        item-similarity queries fall back to predict(); malformed queries
        raise predict()'s normal error (the batch-predict workflow converts
        those to per-row error records)."""
        user_rows, fallback = partition_user_queries(model.user_index, queries)
        # live seen-filter: one store lookup per DISTINCT user for the
        # whole bulk run, not one per row (the scoring itself is still a
        # single matmul; batch-heavy deployments preferring zero lookups
        # should train with seenFilter "model")
        seen_memo: dict = {}

        def seen_for(q, user_idx):
            return _seen_indices(model, q, user_idx, cache=seen_memo)

        out = batch_score_known_users(
            model.als,
            user_rows,
            lambda scores, qid, q, user_idx: (
                qid,
                self._topk_response(
                    model, scores, q, int(q.get("num", 10)), user_idx,
                    seen=seen_for(q, user_idx),
                ),
            ),
            retrieval=self._retrieval,
        )
        out.extend((qid, self.predict(model, q)) for qid, q in fallback)
        return out

    @staticmethod
    def _topk_response(
        model: RecommendationModel, scores: np.ndarray, query, num: int,
        user_idx: int, seen: set | None = None,
    ) -> dict:
        """Shared filter + top-k over one user's item scores (predict and
        the vectorized batch path must rank identically). ``seen`` lets
        the batch path pass a memoized lookup; None resolves per call."""
        # blackList always applies; the seen-items filter is opt-out
        exclude = {
            model.item_index[b]
            for b in (query.get("blackList") or [])
            if b in model.item_index
        }
        if query.get("unseenOnly", True):
            exclude |= (
                seen if seen is not None
                else _seen_indices(model, query, user_idx)
            )
        for idx in exclude:
            scores[idx] = -np.inf
        return topk_item_scores(model.item_ids, scores, num)

    def _recommend_for_user(self, model: RecommendationModel, query, num: int) -> dict:
        user_idx = model.user_index.get(str(query["user"]))
        if user_idx is None:
            return {"itemScores": []}  # cold user: reference returns empty
        scores = score_known_user(model.als, user_idx, self._retrieval)
        return self._topk_response(model, scores, query, num, user_idx)

    def _similar_items(self, model: RecommendationModel, query, num: int) -> dict:
        anchors = [
            model.item_index[str(item)]
            for item in query["items"]
            if str(item) in model.item_index
        ]
        if not anchors:
            return {"itemScores": []}
        sims = similar_item_scores(model.als, anchors, self._retrieval)
        for idx in anchors:
            sims[idx] = -np.inf
        return topk_item_scores(model.item_ids, sims, num)


def engine_factory() -> Engine:
    return Engine(
        data_source_class=RecommendationDataSource,
        preparator_class=RecommendationPreparator,
        algorithm_class_map={"als": ALSAlgorithm},
        serving_class=FirstServing,
    )
