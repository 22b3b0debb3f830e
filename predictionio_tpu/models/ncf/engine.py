"""DASE components of the Neural-CF template.

Query contract matches the recommendation template:
``{"user": "u1", "num": 4}`` -> ``{"itemScores": [...]}``.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field

import numpy as np

from predictionio_tpu.controller import Engine, FirstServing, TPUAlgorithm
from predictionio_tpu.models._als_common import (
    partition_user_queries,
    score_buffer_rows,
    topk_item_scores,
)
from predictionio_tpu.models.ncf.kernel import (
    make_all_items_scorer,
    make_batch_scorer,
)
from predictionio_tpu.models.ncf.model import (
    NCFConfig,
    make_implicit_batches,
    train_ncf,
)
from predictionio_tpu.models.recommendation.engine import (
    RatingsData,
    RecommendationDataSource,
)
from predictionio_tpu.controller.base import Preparator


#: guards first-query scorer construction across serving threads
#: (reentrant: scorer() builds through batch_scorer() under the same lock)
_SCORER_BUILD_LOCK = threading.RLock()


class NCFPreparator(Preparator):
    """NCF consumes the COO directly; no CSR packing needed."""

    def prepare(self, ctx, training_data: RatingsData):
        from predictionio_tpu.models._streaming import StreamingHandle

        if isinstance(training_data, StreamingHandle):
            # NCF shares RecommendationDataSource, whose '"reader":
            # "streaming"' mode hands back a handle with no edge arrays;
            # NCF's SGD needs the materialized COO. Fail here with the
            # template named instead of an opaque AttributeError downstream.
            raise ValueError(
                "the NCF template does not support the streaming sharded "
                'reader; remove "reader": "streaming" from the datasource '
                "params (NCF training consumes the materialized COO arrays)"
            )
        return training_data


@dataclass
class NCFModel:
    params: dict
    user_index: dict[str, int]
    item_ids: list[str]
    item_index: dict[str, int]
    seen: dict[int, set[int]]
    use_pallas: bool
    #: "model": the trained-in seen map; "live": per-query event-store
    #: read (O(entities) serving model; fresh interactions filter with no
    #: retrain). Old pickles predate these; readers use getattr defaults.
    seen_mode: str = "model"
    app_name: str = ""
    channel_name: str = None
    event_names: list[str] = None
    #: lazily-built device-resident scorer (tables uploaded once); holds
    #: device buffers and a jit closure, so it must never be pickled into
    #: the model blob -- __getstate__ strips it and deploy rebuilds it via
    #: NCFAlgorithm.warm_up (a cold query would otherwise pay the build)
    _scorer: object = field(default=None, init=False, repr=False, compare=False)
    _batch_scorer: object = field(
        default=None, init=False, repr=False, compare=False
    )

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_scorer"] = None
        state["_batch_scorer"] = None
        return state

    def __setstate__(self, state):
        # blobs pickled by older releases predate the scorer fields;
        # dataclass unpickling bypasses __init__, so default them here or
        # every access raises AttributeError
        state.setdefault("_scorer", None)
        state.setdefault("_batch_scorer", None)
        self.__dict__.update(state)

    def scorer(self):
        # the query server is a ThreadingHTTPServer: concurrent first
        # queries must not each upload the tables and compile the kernel
        # (double-checked under a module lock; a per-model lock would not
        # survive pickling)
        if self._scorer is None:
            with _SCORER_BUILD_LOCK:
                if self._scorer is None:
                    if self.use_pallas:
                        # the kernel or nothing: a build or lowering
                        # failure fails the deploy (warm_up), it never
                        # swaps in another path. Interpreted only where
                        # every kernel here is: off the TPU.
                        import jax

                        self._scorer = make_all_items_scorer(
                            self.params, len(self.item_ids),
                            interpret=jax.devices()[0].platform != "tpu",
                        )
                    else:
                        # route single queries through the SAME jitted
                        # program family the micro-batched path uses
                        # (bucket of 1): batched and unbatched serving
                        # answers stay numerically identical
                        batch = self.batch_scorer()
                        self._scorer = lambda u: batch(
                            np.asarray([u], np.int32)
                        )[0]
        return self._scorer

    def batch_scorer(self):
        if self._batch_scorer is None:
            with _SCORER_BUILD_LOCK:
                if self._batch_scorer is None:
                    self._batch_scorer = make_batch_scorer(
                        self.params, len(self.item_ids)
                    )
        return self._batch_scorer


class NCFAlgorithm(TPUAlgorithm):
    """Params: embedDim, hidden, learningRate, epochs, batchSize, implicit,
    negatives, seed, usePallas (serving kernel; auto-off on CPU)."""

    def train(self, ctx, data: RatingsData) -> NCFModel:
        import jax

        p = self.params
        config = NCFConfig(
            num_users=data.num_users,
            num_items=data.num_items,
            embed_dim=p.get_or("embedDim", 32),
            hidden=tuple(p.get_or("hidden", [64, 32])),
            learning_rate=p.get_or("learningRate", 0.01),
            implicit=p.get_or("implicit", False),
            negatives=p.get_or("negatives", 4),
            batch_size=p.get_or("batchSize", 4096),
            epochs=p.get_or("epochs", 5),
            seed=p.get_or("seed", 0),
        )
        users, items, labels = data.users, data.items, data.ratings
        if config.implicit:
            users, items, labels = make_implicit_batches(
                users, items, data.num_items, config.negatives,
                np.random.default_rng(config.seed),
            )
        checkpoint = None
        if p.get_or("checkpoint", True):
            # keyed on the workflow's stable run_key (variant+params hash),
            # so `pio train --resume` after preemption finds the crashed
            # attempt's epochs -- the round-1 instance-id key could not
            checkpoint = ctx.checkpoint_manager("ncf")
        seen_mode = p.get_or("seenFilter", "model")
        if seen_mode not in ("model", "live"):
            # before the (expensive) training run, not after
            raise ValueError(
                f"seenFilter must be 'model' or 'live', got {seen_mode!r}"
            )
        params, _ = train_ncf(
            config, users, items, labels, ctx.mesh, checkpoint=checkpoint
        )
        if seen_mode == "live" and getattr(data, "eval_fold", False):
            # a live read would -inf every held-out item (they still exist
            # in the store) and zero eval metrics; fold data carries its
            # train edges, so the trained-in map is correct there
            seen_mode = "model"
        seen: dict[int, set[int]] = {}
        if seen_mode == "model":
            for u, i in zip(data.users, data.items):
                seen.setdefault(int(u), set()).add(int(i))
        backend = jax.devices()[0].platform
        return NCFModel(
            params=params,
            user_index={uid: j for j, uid in enumerate(data.user_ids)},
            item_ids=data.item_ids,
            item_index={iid: j for j, iid in enumerate(data.item_ids)},
            seen=seen,
            use_pallas=p.get_or("usePallas", backend == "tpu"),
            seen_mode=seen_mode,
            app_name=getattr(data, "app_name", ""),
            channel_name=getattr(data, "channel_name", None),
            event_names=getattr(data, "event_names", None),
        )

    def warm_up(self, model: NCFModel) -> None:
        """Build both serving scorers at deploy (tables upload + kernel
        compile), not on the first unlucky query: /queries.json serves
        through scorer(), the batch-predict workflow through
        batch_scorer() -- prepare_deploy precedes both. The single-query
        scorer is called once, because jit compiles on the first call: a
        kernel the device refuses fails the deploy here."""
        model.scorer()(0)
        model.batch_scorer()

    @staticmethod
    def _seen(model: NCFModel, query, user_idx, cache=None) -> set[int]:
        if getattr(model, "seen_mode", "model") != "live":
            return model.seen.get(user_idx, set())
        from predictionio_tpu.models._streaming import live_seen_indices

        return live_seen_indices(model, str(query.get("user")), cache)

    @staticmethod
    def _topk_response(model: NCFModel, scores: np.ndarray, query, user_idx,
                       seen_cache=None) -> dict:
        """Shared exclusion + ranking tail (predict and batch_predict must
        rank identically)."""
        exclude = {
            model.item_index[str(b)]
            for b in (query.get("blackList") or [])
            if str(b) in model.item_index
        }
        if query.get("unseenOnly", True):
            exclude |= NCFAlgorithm._seen(model, query, user_idx, seen_cache)
        scores = scores.astype(np.float64)
        for j in exclude:
            scores[j] = -np.inf
        return topk_item_scores(
            model.item_ids, scores, int(query.get("num", 10))
        )

    def predict(self, model: NCFModel, query) -> dict:
        user_idx = model.user_index.get(str(query.get("user")))
        if user_idx is None:
            return {"itemScores": []}
        return self._topk_response(model, model.scorer()(user_idx), query, user_idx)

    def batch_predict(self, model: NCFModel, queries):
        """Vectorized bulk scoring: chunks of known users score against the
        full catalog in ONE device program each (make_batch_scorer),
        instead of a 2-round-trip dispatch per query -- the reference's
        P2LAlgorithm broadcast batchPredict, as XLA batching. Cold users
        and malformed queries fall through to predict()."""
        user_rows, fallback = partition_user_queries(model.user_index, queries)
        out = []
        if user_rows:
            # bound the host [rows, items] score buffer (the device-side
            # pair budget caps only the on-device intermediates)
            rows_per_slice = score_buffer_rows(len(model.item_ids))
            scorer = model.batch_scorer()
            seen_cache: dict = {}
            for start in range(0, len(user_rows), rows_per_slice):
                part = user_rows[start : start + rows_per_slice]
                scores = scorer(
                    np.fromiter((u for _, _, u in part), dtype=np.int32)
                )
                out.extend(
                    (qid, self._topk_response(model, scores[row], q, user_idx,
                                              seen_cache=seen_cache))
                    for row, (qid, q, user_idx) in enumerate(part)
                )
        out.extend((qid, self.predict(model, q)) for qid, q in fallback)
        return out


def engine_factory() -> Engine:
    # NCF shares RecommendationDataSource, so it inherits the time-travel
    # replay hook (read_replay) and works with `pio eval --replay` as-is:
    # the replay fold is a RatingsData slice, which NCFPreparator re-reads
    # with implicit weights exactly like the train path.
    return Engine(
        data_source_class=RecommendationDataSource,
        preparator_class=NCFPreparator,
        algorithm_class_map={"ncf": NCFAlgorithm},
        serving_class=FirstServing,
    )
