"""DASE components of the sequential-recommendation template.

Per-user event histories -> next-item prediction. Query contracts:
``{"user": "u1", "num": 4}`` (recommend from the user's stored history) and
``{"items": ["i3", "i9"], "num": 4}`` (session-based: recommend from an
explicit prefix). Response: ``{"itemScores": [{"item", "score"}, ...]}``.

The reference has no sequence model (nothing in MLlib's template zoo is
sequential beyond MarkovChain in ``e2``); this family is the long-context
path of the rebuild (SURVEY.md section 5.7): histories can exceed one chip
via the ``seq`` mesh axis + ring attention.
"""

from __future__ import annotations

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
from predictionio_tpu.models._als_common import score_buffer_rows, topk_item_scores
from predictionio_tpu.ops.flash_attention import tiles_worked
from predictionio_tpu.models.sequence.model import (
    BACKBONES,
    score_next_items,
    score_next_items_batch,
    train_sasrec,
)


@dataclass
class SequencesData(SanityCheck):
    """Per-user time-ordered item-index sequences + vocabularies.

    Item indices are 0-based here; the model shifts by +1 (0 = padding).
    """

    sequences: list[np.ndarray]
    user_ids: list[str]
    item_ids: list[str]
    #: carried for serving-time live history reads (historyMode "live")
    app_name: str = ""
    channel_name: str = None
    event_names: list[str] = None

    def sanity_check(self) -> None:
        if not self.sequences:
            raise ValueError("no event sequences found -- check appName/eventNames")

    @property
    def num_items(self) -> int:
        return len(self.item_ids)


class SequenceDataSource(DataSource):
    """Groups item-interaction events per user, ordered by event time.

    Params: ``appName`` (required), ``eventNames`` (default
    ``["view", "buy", "rate"]``), ``minSeqLen`` (drop shorter histories,
    default 2), ``evalFolds``/``evalK`` for read_eval.
    """

    def _read(self) -> SequencesData:
        ds = PEventStore.dataset(
            self.params.appName,
            event_names=self.params.get_or("eventNames", ["view", "buy", "rate"]),
            target_entity_type="item",
        )
        valid = ds.target_entity_ids >= 0
        users = ds.entity_ids[valid]
        items = ds.target_entity_ids[valid]
        times = ds.event_times[valid]
        min_len = self.params.get_or("minSeqLen", 2)
        # one vectorized (user, time) sort, then a grouped scan -- the same
        # grouping idiom as the similar-product / UR templates
        sequences, seq_user_ids = [], []
        if users.size:
            order = np.lexsort((times, users))
            users, items = users[order], items[order]
            boundaries = np.flatnonzero(np.diff(users)) + 1
            for hist, u in zip(
                np.split(items, boundaries), users[np.r_[0, boundaries]]
            ):
                if len(hist) >= min_len:
                    sequences.append(hist.astype(np.int64))
                    seq_user_ids.append(ds.entity_id_vocab[int(u)])
        return SequencesData(
            sequences=sequences,
            user_ids=seq_user_ids,
            item_ids=ds.target_entity_id_vocab,
            app_name=self.params.appName,
            channel_name=self.params.get_or("channelName", None),
            event_names=self.params.get_or(
                "eventNames", ["view", "buy", "rate"]
            ),
        )

    def read_training(self, ctx) -> SequencesData:
        return self._read()

    def read_eval(self, ctx):
        """Leave-one-out per fold: hold out each user's last item as the
        actual, query on the preceding history (the SASRec protocol)."""
        data = self._read()
        folds = self.params.get_or("evalFolds", 1)
        eval_k = self.params.get_or("evalK", 10)
        out = []
        for f in range(folds):
            train_seqs, pairs, users = [], [], []
            for uid, seq in zip(data.user_ids, data.sequences):
                if len(seq) < 3:
                    train_seqs.append(seq)
                    users.append(uid)
                    continue
                cut = len(seq) - 1 - (f % max(len(seq) - 2, 1))
                train_seqs.append(seq[:cut])
                users.append(uid)
                pairs.append(
                    (
                        {"items": [data.item_ids[i] for i in seq[:cut]],
                         "num": eval_k},
                        [data.item_ids[seq[cut]]],
                    )
                )
            out.append(
                (
                    SequencesData(train_seqs, users, data.item_ids),
                    EvalInfo(fold=f),
                    pairs,
                )
            )
        return out


@dataclass
class PackedSequences(SanityCheck):
    matrix: np.ndarray            # [N, max_len] int32, ids shifted +1, 0 = pad
    data: SequencesData

    def sanity_check(self) -> None:
        self.data.sanity_check()


class SequencePreparator(Preparator):
    """Pad/left-truncate histories to maxLen and shift ids (+1, 0 = pad).

    Params: ``maxLen`` (default 64; must be divisible by the mesh seq-axis
    size when sequence parallelism is on).
    """

    def prepare(self, ctx, data: SequencesData) -> PackedSequences:
        from predictionio_tpu.obs.trace import global_tracer

        max_len = self.params.get_or("maxLen", 64)
        with global_tracer().span("seq.pack") as span:
            matrix = np.zeros((len(data.sequences), max_len), np.int32)
            for row, seq in enumerate(data.sequences):
                tail = seq[-max_len:] + 1
                matrix[row, : len(tail)] = tail
            span.set_attr("users", matrix.shape[0])
            span.set_attr("slots", matrix.size)
            span.set_attr("filled_slots", int(np.count_nonzero(matrix)))
            # what the flash kernel walks of these rows (causal, its block)
            worked, tiles = tiles_worked(matrix > 0, causal=True)
            span.set_attr("attention_tiles", tiles)
            span.set_attr("attention_tiles_worked", worked)
        return PackedSequences(matrix=matrix, data=data)


@dataclass
class SASRecModel:
    params: dict
    config: object                     # the configuration of the backbone it was trained with
    item_ids: list[str]
    item_index: dict[str, int]
    histories: dict[str, np.ndarray]   # user id -> shifted (+1) id sequence
    #: "model": queries continue the TRAINED-IN history above; "live":
    #: per-query event-store read -- session-based serving: events
    #: ingested after training extend the sequence the model continues,
    #: with no retrain, and the model stays O(entities). Old pickles
    #: predate these fields; readers use getattr defaults.
    history_mode: str = "model"
    app_name: str = ""
    channel_name: str = None
    event_names: list[str] = None


#: the engine parameters every backbone reads -> their field: the fit's, and
#: the two selectors of how attention is worked
FIT_PARAMS = {"learningRate": "learning_rate", "batchSize": "batch_size", "epochs": "epochs",
              "seed": "seed", "seqParallel": "seq_parallel", "attention": "attention"}


class SASRecAlgorithm(TPUAlgorithm):
    """Params: ``backbone`` (one of ``BACKBONES``; "sasrec" is the default),
    maxLen (must match the preparator's), historyMode ("model" | "live"), the
    fit's (``FIT_PARAMS``) and the backbone's own widths (its module's
    ``ENGINE_PARAMS``). ``docs/templates.md`` lists every name, backbone by
    backbone, with what it means."""

    BACKBONES = tuple(BACKBONES)

    def _config(self, num_items: int, max_len: int):
        """The backbone's configuration: each engine parameter it reads, cast
        to the type of its field's default, which is also what a parameter
        left out takes."""
        p = self.params
        backbone = p.get_or("backbone", "sasrec")
        if backbone not in self.BACKBONES:
            raise ValueError(
                f"backbone={backbone!r}: want one of "
                + ", ".join(repr(b) for b in self.BACKBONES))
        module = BACKBONES[backbone]
        defaults = module.CONFIG(num_items=num_items)
        fields = {"num_items": num_items, "max_len": max_len}
        for name, field in {**module.ENGINE_PARAMS, **FIT_PARAMS}.items():
            default = getattr(defaults, field)
            if field == "experts_held":       # all of the numExperts given
                default = (0, fields["num_experts"])
            fields[field] = type(default)(p.get_or(name, default))
        return module.CONFIG(**fields)

    def train(self, ctx, prepared: PackedSequences) -> SASRecModel:
        p = self.params
        data = prepared.data
        max_len = p.get_or("maxLen", None)
        if max_len is not None and max_len != prepared.matrix.shape[1]:
            raise ValueError(
                f"algorithm maxLen={max_len} != preparator maxLen="
                f"{prepared.matrix.shape[1]}; set both to the same value "
                "(or drop the algorithm's)"
            )
        config = self._config(data.num_items, prepared.matrix.shape[1])
        history_mode = self.params.get_or("historyMode", "model")
        if history_mode not in ("model", "live"):
            # before the (expensive) training run, not after
            raise ValueError(
                f"historyMode must be 'model' or 'live', got {history_mode!r}"
            )
        params, _ = train_sasrec(config, prepared.matrix, ctx.mesh)
        # live mode: O(entities) model; queries read fresh histories
        histories = {} if history_mode == "live" else {
            uid: seq + 1 for uid, seq in zip(data.user_ids, data.sequences)
        }
        return SASRecModel(
            params=params,
            config=config,
            item_ids=data.item_ids,
            item_index={iid: j for j, iid in enumerate(data.item_ids)},
            histories=histories,
            history_mode=history_mode,
            app_name=data.app_name,
            channel_name=data.channel_name,
            event_names=data.event_names,
        )

    @staticmethod
    def _resolve_prefix(model: SASRecModel, query):
        """The sequence to continue: explicit ``items`` anchor or the user's
        training history. None/empty means a cold query (empty response)."""
        if query.get("items"):
            return np.asarray(
                [
                    model.item_index[str(i)] + 1
                    for i in query["items"]
                    if str(i) in model.item_index
                ],
                np.int32,
            )
        user = str(query.get("user"))
        if getattr(model, "history_mode", "model") != "live":
            return model.histories.get(user)
        from predictionio_tpu.models._streaming import live_target_events

        # time-ASCENDING: the sequence the model continues; keep the tail
        events = sorted(
            live_target_events(model, user), key=lambda e: e.event_time
        )
        seq = [
            model.item_index[e.target_entity_id] + 1
            for e in events
            if e.target_entity_id in model.item_index
        ]
        if not seq:
            return None
        # FULL history, untruncated: the unseenOnly exclusion must cover
        # everything the user saw (model mode passes full sequences too);
        # the scorer itself keeps only the max_len tail
        return np.asarray(seq, np.int32)

    @staticmethod
    def _topk_response(model: SASRecModel, scores: np.ndarray, query, prefix) -> dict:
        """Shared exclusion + ranking tail (predict and batch_predict must
        rank identically)."""
        scores = scores.astype(np.float64)
        exclude = (
            {int(i) - 1 for i in prefix} if query.get("unseenOnly", True) else set()
        )
        exclude |= {
            model.item_index[str(b)]
            for b in (query.get("blackList") or [])
            if str(b) in model.item_index
        }
        for j in exclude:
            scores[j] = -np.inf
        return topk_item_scores(model.item_ids, scores, int(query.get("num", 10)))

    def predict(self, model: SASRecModel, query) -> dict:
        prefix = self._resolve_prefix(model, query)
        if prefix is None or len(prefix) == 0:
            return {"itemScores": []}
        scores = score_next_items(model.params, model.config, prefix)
        return self._topk_response(model, scores, query, prefix)

    def batch_predict(self, model: SASRecModel, queries):
        """Vectorized bulk scoring: fixed-size slices of prefixes run the
        transformer forward + vocab projection as ONE device program per
        slice (score_next_items_batch) instead of two dispatches per
        query. Cold/malformed queries fall through to predict()."""
        resolved, fallback = [], []
        for qid, q in queries:
            prefix = self._resolve_prefix(model, q) if isinstance(q, dict) else None
            if prefix is None or len(prefix) == 0:
                fallback.append((qid, q))
            else:
                resolved.append((qid, q, prefix))
        out = []
        if resolved:
            # bound the host [rows, vocab] buffer like the other batch
            # paths; score_next_items_batch pads each slice to a power of
            # two internally, so round DOWN to one so full slices don't
            # overshoot the buffer budget (625 -> 1024 would)
            rows = score_buffer_rows(len(model.item_ids), floor=16, cap=1024)
            rows = 1 << (rows.bit_length() - 1)
            for start in range(0, len(resolved), rows):
                part = resolved[start : start + rows]
                scores = score_next_items_batch(
                    model.params, model.config, [p for _, _, p in part]
                )
                out.extend(
                    (qid, self._topk_response(model, scores[row], q, prefix))
                    for row, (qid, q, prefix) in enumerate(part)
                )
        out.extend((qid, self.predict(model, q)) for qid, q in fallback)
        return out


def engine_factory() -> Engine:
    return Engine(
        data_source_class=SequenceDataSource,
        preparator_class=SequencePreparator,
        algorithm_class_map={"sasrec": SASRecAlgorithm},
        serving_class=FirstServing,
    )
