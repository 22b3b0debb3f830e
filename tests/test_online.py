"""Continuous-learning subsystem tests (``predictionio_tpu/online``).

Covers the ISSUE-9 acceptance surface: WAL tail + durable cursor, the
versioned model registry (CRC, rollback, GC), fold-in parity against the
exact per-row normal-equation solve, the query server's swap-epoch
protocol under concurrent load (zero errors, every response attributable
to exactly ONE model version), SIGKILL-mid-fold-in recovery (cursor not
advanced past an unswapped model, second run converges), the ingest ->
visible-in-query freshness bound, and the `pio deploy --model-version` /
`pio top` satellites.
"""

from __future__ import annotations

import datetime as _dt
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

APP_ID = 1


def env_pythonpath() -> str:
    return os.environ.get("PYTHONPATH", "")


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _post(url: str, path: str, obj, timeout: float = 20.0):
    req = urllib.request.Request(
        f"{url}{path}",
        data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, dict(resp.headers), json.loads(
                resp.read().decode() or "null"
            )
    except urllib.error.HTTPError as exc:
        return exc.code, dict(exc.headers), json.loads(
            exc.read().decode() or "null"
        )


def _insert_ratings(le, n=300, users=20, items=10, seed=3, app_id=APP_ID):
    from predictionio_tpu.data import DataMap, Event

    rng = np.random.default_rng(seed)
    base = _dt.datetime.now(_dt.timezone.utc) - _dt.timedelta(hours=1)
    le.batch_insert(
        [
            Event(
                event="rate",
                entity_type="user",
                entity_id=f"u{rng.integers(0, users)}",
                target_entity_type="item",
                target_entity_id=f"i{rng.integers(0, items)}",
                properties=DataMap({"rating": float(rng.integers(1, 6))}),
                event_time=base + _dt.timedelta(milliseconds=11 * k),
            )
            for k in range(n)
        ],
        app_id=app_id,
    )


def _recommendation_variant(storage_env, tmp_path, app="OnlineApp", **algo):
    """App + events + a trained tiny recommendation engine instance."""
    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.workflow.core_workflow import run_train
    from predictionio_tpu.workflow.json_extractor import load_engine_variant

    storage_env.get_meta_data_apps().insert(App(name=app))
    le = storage_env.get_l_events()
    le.init_channel(APP_ID)
    _insert_ratings(le)
    params = {"rank": 4, "numIterations": 2, "seed": 7,
              "checkpointInterval": 0, **algo}
    path = tmp_path / "engine.json"
    path.write_text(json.dumps({
        "id": "online-test",
        "engineFactory":
            "predictionio_tpu.models.recommendation.engine.engine_factory",
        "datasource": {"params": {"appName": app}},
        "algorithms": [{"name": "als", "params": params}],
    }))
    variant = load_engine_variant(str(path))
    run_train(variant)
    return variant


def _ingest_via_wal(wal, le, user: str, item: str, rating: float = 5.0,
                    event_time=None, app_id=APP_ID) -> int:
    """The event server's durable cycle, inlined: WAL append + fsync ->
    storage flush -> checkpoint. Returns the record's seqno."""
    from predictionio_tpu.data import DataMap, Event
    from predictionio_tpu.data.ingest import wal_payload

    event = Event(
        event="rate", entity_type="user", entity_id=user,
        target_entity_type="item", target_entity_id=item,
        properties=DataMap({"rating": rating}),
        **({"event_time": event_time} if event_time else {}),
    ).with_id()
    seqno = wal.append(wal_payload(event, app_id, None))
    wal.sync()
    le.insert_batch([(event, app_id, None)], on_duplicate="ignore")
    wal.checkpoint(seqno)
    return seqno


def _train_fake(storage_env, tmp_path, app="SwapApp"):
    """Tiny no-jax fake engine (tests/fake_engine.py) trained once."""
    from predictionio_tpu.data import DataMap, Event
    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.workflow.core_workflow import run_train
    from predictionio_tpu.workflow.json_extractor import load_engine_variant

    tests_dir = os.path.dirname(os.path.abspath(__file__))
    if tests_dir not in sys.path:
        sys.path.insert(0, tests_dir)
    app_id = storage_env.get_meta_data_apps().insert(App(name=app))
    le = storage_env.get_l_events()
    le.init_channel(app_id)
    le.batch_insert(
        [
            Event(event="rate", entity_type="user", entity_id=f"u{k % 4}",
                  target_entity_type="item", target_entity_id=f"i{k}",
                  properties=DataMap({"rating": 3.0}))
            for k in range(8)
        ],
        app_id=app_id,
    )
    path = tmp_path / "engine.json"
    path.write_text(json.dumps({
        "id": "swap-test",
        "engineFactory": "fake_engine.engine_factory",
        "datasource": {"params": {"appName": app}},
        "algorithms": [{"name": "mean", "params": {}}],
    }))
    variant = load_engine_variant(str(path))
    instance = run_train(variant)
    return variant, instance


def _publish_mean_versions(variant, instance, means):
    """One registry version per mean value (distinguishable responses =
    per-response version attribution without trusting any header)."""
    from fake_engine import MeanModel

    from predictionio_tpu.online.registry import ModelRegistry
    from predictionio_tpu.workflow.context import RuntimeContext
    from predictionio_tpu.workflow.core_workflow import (
        engine_params_from_instance,
    )
    from predictionio_tpu.workflow.json_extractor import build_engine

    engine = build_engine(variant)
    engine_params = engine_params_from_instance(instance)
    ctx = RuntimeContext(instance.runtime_conf)
    registry = ModelRegistry.for_variant(variant)
    versions = {}
    for mean in means:
        blob = engine.serialize_models(
            ctx, engine_params, instance.id, [MeanModel(mean)]
        )
        v = registry.publish(blob, meta={
            "source": "test",
            "instance_id": instance.id,
            "engine_params": engine_params.to_json_obj(),
        })
        versions[v.version] = mean
    return registry, versions


# ---------------------------------------------------------------------------
# follower: cursor + WAL tail
# ---------------------------------------------------------------------------

class TestFollower:
    def test_cursor_roundtrip_and_atomicity(self, tmp_path):
        from predictionio_tpu.online.follower import TailCursor

        path = str(tmp_path / "state" / "cursor.json")
        c = TailCursor(path)
        assert (c.seqno, c.until_ms, c.snapshot_rows) == (0, 0, 0)
        c.advance(7, 123_456, 42)
        again = TailCursor(path)
        assert (again.seqno, again.until_ms, again.snapshot_rows) == (7, 123_456, 42)
        # advance never regresses seqno/until (replay windows only shrink)
        again.advance(5, 100, 50)
        assert again.seqno == 7 and again.until_ms == 123_456
        # a torn cursor file falls back to zero (pure replay, never loss)
        with open(path, "w") as f:
            f.write("{not json")
        assert TailCursor(path).seqno == 0

    def test_tail_respects_checkpoint_and_filters(self, tmp_path):
        from predictionio_tpu.data import DataMap, Event
        from predictionio_tpu.data.ingest import wal_payload
        from predictionio_tpu.data.wal import WriteAheadLog
        from predictionio_tpu.online.follower import WalTail

        wal = WriteAheadLog(str(tmp_path / "wal"))
        seqs = []
        for k in range(5):
            ev = Event(
                event="rate" if k % 2 == 0 else "view",
                entity_type="user", entity_id=f"u{k}",
                target_entity_type="item", target_entity_id=f"i{k}",
                properties=DataMap({}),
            ).with_id()
            # record 4 goes to another app entirely
            seqs.append(wal.append(wal_payload(ev, APP_ID if k < 4 else 9, None)))
        wal.sync()
        tail = WalTail(str(tmp_path / "wal"), APP_ID, None, ["rate"])
        # nothing checkpointed yet: records are acked but not yet in SQL,
        # so the follower must not act on them
        batch = tail.poll(0)
        assert batch.empty and batch.records == 0
        wal.checkpoint(seqs[2])
        batch = tail.poll(0)
        assert batch.last_seqno == seqs[2]
        assert batch.records == 2  # k=0 and k=2 are "rate" in the followed app
        assert batch.touched_users == {"u0", "u2"}
        # resume from the cursor: only the not-yet-seen slice, and the
        # filters still apply (k=3 is "view", k=4 is another app)
        wal.checkpoint(seqs[4])
        batch2 = tail.poll(batch.last_seqno)
        assert batch2.records == 0
        assert batch2.last_seqno == seqs[4]
        wal.close()

    def test_tail_reports_gc_gap(self, tmp_path):
        from predictionio_tpu.data.wal import WriteAheadLog, _segment_name
        from predictionio_tpu.online.follower import WalTail

        wal_dir = tmp_path / "wal"
        wal = WriteAheadLog(str(wal_dir))
        for _ in range(3):
            wal.append(b"{}")
        wal.sync()
        wal.close()
        # simulate GC: the only segment starts at seqno 1; rename it to
        # start at 100 so a cursor at 0 trails the oldest retained record
        seg = next(p for p in os.listdir(wal_dir) if p.endswith(".log"))
        os.rename(wal_dir / seg, wal_dir / _segment_name(100))
        tail = WalTail(str(wal_dir), APP_ID)
        assert tail.poll(0, upto_seqno=200).gap is True


class TestTailFixture:
    def test_touched_users_exact(self, tmp_path):
        """Re-pin the filter semantics with unambiguous data."""
        from predictionio_tpu.data import DataMap, Event
        from predictionio_tpu.data.ingest import wal_payload
        from predictionio_tpu.data.wal import WriteAheadLog
        from predictionio_tpu.online.follower import WalTail

        wal = WriteAheadLog(str(tmp_path / "wal"))
        for name, user in (("rate", "a"), ("view", "b"), ("rate", "c")):
            ev = Event(event=name, entity_type="user", entity_id=user,
                       target_entity_type="item", target_entity_id="x",
                       properties=DataMap({})).with_id()
            last = wal.append(wal_payload(ev, APP_ID, None))
        wal.sync()
        wal.checkpoint(last)
        batch = WalTail(str(tmp_path / "wal"), APP_ID, None, ["rate"]).poll(0)
        assert batch.touched_users == {"a", "c"}
        assert batch.touched_items == {"x"}
        assert batch.records == 2
        assert batch.lag_seconds() >= 0.0
        wal.close()

    def test_set_records_tracked_not_counted(self, tmp_path):
        """$set/$unset property records pass the event-name filter into
        their own channel: a fold-in must learn the category aggregate
        changed, but property events are not interactions -- they stay out
        of records/touched_users and out of the snapshot-window clock."""
        from predictionio_tpu.data import DataMap, Event
        from predictionio_tpu.data.ingest import wal_payload
        from predictionio_tpu.data.wal import WriteAheadLog
        from predictionio_tpu.online.follower import WalTail

        wal = WriteAheadLog(str(tmp_path / "wal"))
        evs = [
            Event(event="$set", entity_type="item", entity_id="i1",
                  properties=DataMap({"categories": ["a"]})),
            Event(event="$unset", entity_type="user", entity_id="u1",
                  properties=DataMap({"plan": None})),
            Event(event="view", entity_type="user", entity_id="u2",
                  target_entity_type="item", target_entity_id="i1",
                  properties=DataMap({})),
        ]
        for ev in evs:
            last = wal.append(wal_payload(ev.with_id(), APP_ID, None))
        wal.sync()
        wal.checkpoint(last)
        batch = WalTail(str(tmp_path / "wal"), APP_ID, None, ["view"]).poll(0)
        assert batch.records == 1 and batch.touched_users == {"u2"}
        assert batch.set_records == 2
        assert batch.touched_set_types == {"item", "user"}
        wal.close()
        # a $set-ONLY window is NOT empty: the loop must run a cycle so
        # property-derived indexes (e-commerce categories) can refresh
        from predictionio_tpu.online.follower import TailBatch

        only_set = TailBatch(set_records=1, touched_set_types={"item"})
        assert not only_set.empty
        assert only_set.lag_seconds() == 0.0


class TestPartitionedFollower:
    def test_partition_tails_discovers_layout_off_disk(self, tmp_path):
        from predictionio_tpu.data.wal import PartitionedWal, partition_dirs
        from predictionio_tpu.online.follower import partition_tails

        d = str(tmp_path / "wal")
        PartitionedWal(d, partitions=4).close()
        tails = partition_tails(d, APP_ID, None, ["rate"])
        assert [t.directory for t in tails] == partition_dirs(d)
        assert len(tails) == 4
        assert all(t.app_id == APP_ID for t in tails)
        # a flat (P=1) log yields exactly one tail on the root -- and so
        # does a directory that does not exist yet
        flat = str(tmp_path / "flat")
        assert [t.directory for t in partition_tails(flat, APP_ID)] == [flat]

    def test_merge_batches_unions_deltas(self):
        from predictionio_tpu.online.follower import TailBatch, merge_batches

        b0 = TailBatch(
            last_seqno=5, records=2,
            touched_users={"a", "b"}, touched_items={"x"},
            min_event_ms=100, max_event_ms=200,
        )
        b1 = TailBatch(
            last_seqno=9, records=1, set_records=1,
            touched_users={"b", "c"}, touched_items={"y"},
            touched_set_types={"item"},
            min_event_ms=50, max_event_ms=150,
        )
        m = merge_batches([b0, b1])
        assert m.records == 3 and m.set_records == 1
        assert m.touched_users == {"a", "b", "c"}
        assert m.touched_items == {"x", "y"}
        assert m.touched_set_types == {"item"}
        # the window spans the WIDEST bounds across partitions
        assert (m.min_event_ms, m.max_event_ms) == (50, 200)
        # seqno spaces are independent; the merged value is diagnostic max
        assert m.last_seqno == 9
        assert m.gap is False

    def test_merge_batches_none_bounds_and_empty(self):
        from predictionio_tpu.online.follower import TailBatch, merge_batches

        assert merge_batches([]).empty
        # an all-empty merge stays empty (idle cycle)
        assert merge_batches([TailBatch(), TailBatch()]).empty
        # a partition with no interactions contributes no bounds
        m = merge_batches(
            [TailBatch(), TailBatch(records=1, min_event_ms=7, max_event_ms=9)]
        )
        assert (m.min_event_ms, m.max_event_ms) == (7, 9)

    def test_merge_batches_gap_poisons_the_merge(self):
        from predictionio_tpu.online.follower import TailBatch, merge_batches

        m = merge_batches([TailBatch(records=3), TailBatch(gap=True)])
        assert m.gap is True
        assert not m.empty  # a gap alone forces a resync cycle


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

class TestModelRegistry:
    def _registry(self, tmp_path, keep=5):
        from predictionio_tpu.online.registry import ModelRegistry

        return ModelRegistry(str(tmp_path / "registry"), "k" * 16, keep=keep)

    def test_publish_latest_get_roundtrip(self, tmp_path):
        reg = self._registry(tmp_path)
        v1 = reg.publish(b"model-one", meta={"source": "train"})
        v2 = reg.publish(b"model-two", meta={"source": "foldin"})
        assert (v1.version, v2.version) == (1, 2)
        assert reg.latest().version == 2
        assert reg.get(1).load_blob() == b"model-one"
        assert reg.get(2).source == "foldin"
        assert [v.version for v in reg.versions()] == [1, 2]

    def test_missing_version_is_actionable(self, tmp_path):
        from predictionio_tpu.online.registry import RegistryError

        reg = self._registry(tmp_path)
        reg.publish(b"x")
        with pytest.raises(RegistryError, match="version 9 not found"):
            reg.get(9)

    def test_corrupt_blob_rejected(self, tmp_path):
        from predictionio_tpu.online.registry import RegistryError

        reg = self._registry(tmp_path)
        v = reg.publish(b"good model bytes")
        blob_path = os.path.join(v.path, "model.bin")
        data = bytearray(open(blob_path, "rb").read())
        data[0] ^= 0xFF
        with open(blob_path, "wb") as f:
            f.write(bytes(data))
        with pytest.raises(RegistryError, match="CRC mismatch"):
            reg.get(v.version).load_blob()
        # truncation is caught at validation (size vs manifest)
        with open(blob_path, "wb") as f:
            f.write(b"short")
        with pytest.raises(RegistryError, match="torn/truncated"):
            reg.get(v.version)

    def test_gc_keeps_rollback_window(self, tmp_path):
        reg = self._registry(tmp_path, keep=2)
        for k in range(4):
            reg.publish(f"m{k}".encode())
        kept = [v.version for v in reg.versions()]
        assert kept == [3, 4]
        assert reg.latest().load_blob() == b"m3"


# ---------------------------------------------------------------------------
# fold-in math
# ---------------------------------------------------------------------------

class TestFoldinParity:
    """Fold-in == the exact per-row normal-equation solution against the
    same frozen item factors -- which is what a full retrain's final user
    half-step computes. Documented tolerance: 1e-4 (f32 accumulation
    order differs between the batched device solve and numpy)."""

    def _data(self, seed=0, U=30, I=12, E=300, K=4):
        rng = np.random.default_rng(seed)
        return (
            rng.integers(0, U, E),
            rng.integers(0, I, E),
            rng.integers(1, 6, E).astype(np.float32),
            U, I, K,
        )

    def _touched_coo(self, users, items, vals, touched):
        rows, cols, vv = [], [], []
        for t, u in enumerate(touched):
            m = users == u
            rows += [t] * int(m.sum())
            cols += items[m].tolist()
            vv += vals[m].tolist()
        return np.array(rows), np.array(cols), np.array(vv, np.float32)

    @pytest.mark.parametrize("implicit", [False, True])
    def test_parity_vs_normal_equations(self, implicit):
        from predictionio_tpu.online.foldin import fold_in_users
        from predictionio_tpu.parallel.als import ALSConfig, als_fit, build_als_data

        users, items, vals, U, I, K = self._data()
        cfg = ALSConfig(rank=K, iterations=2, reg=0.1, alpha=5.0,
                        implicit=implicit)
        data = build_als_data(users, items, vals, U, I, cfg)
        model = als_fit(data, cfg)
        touched = [0, 5, 11]
        rows, cols, vv = self._touched_coo(users, items, vals, touched)
        out = fold_in_users(model.item_factors, rows, cols, vv, len(touched), cfg)
        yty = model.item_factors.T @ model.item_factors
        for t, u in enumerate(touched):
            m = users == u
            Y = model.item_factors[items[m]]
            if implicit:
                c1 = cfg.alpha * vals[m]
                G = yty + (Y * c1[:, None]).T @ Y + cfg.reg * np.eye(K)
                r = Y.T @ (1.0 + c1)
            else:
                G = Y.T @ Y + cfg.reg * int(m.sum()) * np.eye(K)
                r = Y.T @ vals[m]
            ref = np.linalg.solve(G, r)
            assert np.abs(ref - out[t]).max() < 1e-4

    def test_rows_folded_in_chunks_equal_the_rows_folded_whole(self, monkeypatch):
        """More touched rows than one chunk may hold (a budget of 64 KiB: the
        pow2 ladder's 16 rows go in two chunks of 8): the same folded rows,
        bit for bit, as under the budget that takes them whole."""
        from predictionio_tpu.online.foldin import fold_in_users
        from predictionio_tpu.parallel import als
        from predictionio_tpu.parallel.als import ALSConfig, als_fit, build_als_data

        users, items, vals, U, I, K = self._data(seed=2, U=20, I=10, E=200)
        cfg = ALSConfig(rank=K, iterations=2)
        data = build_als_data(users, items, vals, U, I, cfg)
        model = als_fit(data, cfg)
        touched = list(range(11))
        rows, cols, vv = self._touched_coo(users, items, vals, touched)
        whole = fold_in_users(model.item_factors, rows, cols, vv, len(touched), cfg)
        monkeypatch.setattr(als, "EINSUM_GATHER_BUDGET_BYTES", 1 << 16)
        pad_len = 1 << int(np.ceil(np.log2(max(np.bincount(rows).max(), 8))))
        assert als.block_plan("cpu", 16, pad_len, K, 4) > 1
        cut = fold_in_users(model.item_factors, rows, cols, vv, len(touched), cfg)
        np.testing.assert_array_equal(cut, whole)

    def test_replay_idempotence(self):
        """Folding the same window twice converges to the same factors --
        the property the crash-recovery contract stands on."""
        from predictionio_tpu.online.foldin import fold_in_users
        from predictionio_tpu.parallel.als import ALSConfig, als_fit, build_als_data

        users, items, vals, U, I, K = self._data(seed=4)
        cfg = ALSConfig(rank=K, iterations=2)
        data = build_als_data(users, items, vals, U, I, cfg)
        model = als_fit(data, cfg)
        rows, cols, vv = self._touched_coo(users, items, vals, [2, 9])
        once = fold_in_users(model.item_factors, rows, cols, vv, 2, cfg)
        twice = fold_in_users(model.item_factors, rows, cols, vv, 2, cfg)
        np.testing.assert_array_equal(once, twice)


class TestStalenessBudget:
    def test_thresholds(self):
        from predictionio_tpu.online.foldin import (
            StalenessBudget,
            StalenessExceeded,
        )

        b = StalenessBudget(max_touched_frac=0.5, max_item_growth_frac=0.1)
        b.check(touched_users=4, known_users=10, new_users=1, new_items=0,
                known_items=10)
        with pytest.raises(StalenessExceeded, match="touched-user"):
            b.check(touched_users=6, known_users=10, new_users=0,
                    new_items=0, known_items=10)
        with pytest.raises(StalenessExceeded, match="item-vocab"):
            b.check(touched_users=1, known_users=10, new_users=0,
                    new_items=2, known_items=10)


class _FakeSnapshot:
    """Snapshot-shaped test double: columns + vocabs from COO arrays."""

    def __init__(self, users, items, names, times, ratings, uvocab, ivocab,
                 nvocab):
        self._cols = {
            "users": np.asarray(users, np.int64),
            "items": np.asarray(items, np.int64),
            "names": np.asarray(names, np.int32),
            "times": np.asarray(times, np.float64),
            "ratings": np.asarray(ratings, np.float64),
        }
        self._vocabs = {"users": uvocab, "items": ivocab, "names": nvocab}
        tmax = self._cols["times"].max() if len(self._cols["times"]) else 0.0
        self.manifest = {"until_ms": int(tmax * 1000) + 1}

    def column(self, name):
        return self._cols[name]

    def vocab(self, which):
        return self._vocabs[which]

    def __len__(self):
        return len(self._cols["users"])


class TestAlgorithmFoldIn:
    def _trained_model(self, seed=0):
        """A RecommendationModel trained directly (no storage)."""
        from predictionio_tpu.models.recommendation.engine import (
            RecommendationModel,
        )
        from predictionio_tpu.models._als_common import build_seen
        from predictionio_tpu.parallel.als import (
            ALSConfig, als_fit, build_als_data,
        )

        rng = np.random.default_rng(seed)
        U, I, E = 10, 6, 120
        users = rng.integers(0, U, E)
        items = rng.integers(0, I, E)
        vals = rng.integers(1, 6, E).astype(np.float32)
        cfg = ALSConfig(rank=4, iterations=2)
        model = als_fit(build_als_data(users, items, vals, U, I, cfg), cfg)
        uid = [f"u{k}" for k in range(U)]
        iid = [f"i{k}" for k in range(I)]
        return RecommendationModel(
            als=model,
            user_index={u: k for k, u in enumerate(uid)},
            item_ids=iid,
            item_index={i: k for k, i in enumerate(iid)},
            seen=build_seen(users, items),
            seen_mode="model",
            app_name="App",
            event_names=["rate"],
        ), (users, items, vals, uid, iid)

    def _delta(self, uid, iid, new_rows, window_start_ms, budget=None):
        """A FoldinDelta whose snapshot holds old vocab + new_rows."""
        from predictionio_tpu.online.foldin import FoldinDelta, StalenessBudget

        uvocab, ivocab = list(uid), list(iid)
        users, items, times, ratings = [], [], [], []
        t0 = window_start_ms / 1000.0
        for k, (u, i, r) in enumerate(new_rows):
            if u not in uvocab:
                uvocab.append(u)
            if i not in ivocab:
                ivocab.append(i)
            users.append(uvocab.index(u))
            items.append(ivocab.index(i))
            times.append(t0 + 1 + k)
            ratings.append(r)
        snap = _FakeSnapshot(
            users, items, [0] * len(users), times, ratings,
            uvocab, ivocab, ["rate"],
        )
        return FoldinDelta(
            snapshot=snap,
            window_start_ms=window_start_ms,
            budget=budget or StalenessBudget(
                max_touched_frac=1.0, max_item_growth_frac=1.0,
                max_user_growth_frac=10.0,
            ),
        )

    def _algorithm(self):
        from predictionio_tpu.controller.base import Params
        from predictionio_tpu.models.recommendation.engine import ALSAlgorithm

        return ALSAlgorithm(Params({"rank": 4, "numIterations": 2}))

    def test_fold_extends_vocab_and_updates_seen(self):
        model, (_, _, _, uid, iid) = self._trained_model()
        algo = self._algorithm()
        window_ms = int(time.time() * 1000)
        delta = self._delta(
            uid, iid,
            [("newuser", "i1", 5.0), ("newuser", "newitem", 4.0),
             ("u3", "i0", 1.0)],
            window_ms,
        )
        out = algo.fold_in(model, delta)
        assert out is not None and out is not model
        # vocab extension: one new user row, one zero-factor item row
        assert out.user_index["newuser"] == len(uid)
        assert out.item_index["newitem"] == len(iid)
        assert out.als.user_factors.shape[0] == len(uid) + 1
        assert out.als.item_factors.shape[0] == len(iid) + 1
        assert np.all(out.als.item_factors[-1] == 0.0)
        # the folded new user actually scores
        assert np.abs(out.als.user_factors[-1]).max() > 0
        # window pairs landed in the seen map; the OLD model is untouched
        assert out.item_index["i0"] in out.seen[out.user_index["u3"]]
        assert out.user_index["newuser"] in out.seen
        assert "newuser" not in model.user_index  # old model untouched
        # untouched users keep their factors bit-for-bit
        u5 = model.user_index["u5"]
        np.testing.assert_array_equal(
            out.als.user_factors[u5], model.als.user_factors[u5]
        )

    def test_fold_returns_none_on_empty_window(self):
        model, (_, _, _, uid, iid) = self._trained_model()
        algo = self._algorithm()
        window_ms = int(time.time() * 1000)
        from predictionio_tpu.online.foldin import FoldinDelta

        snap = _FakeSnapshot([], [], [], [], [], list(uid), list(iid), [])
        snap.manifest = {"until_ms": window_ms}
        assert algo.fold_in(model, FoldinDelta(snap, window_ms)) is None

    def test_fold_escalates_on_budget(self):
        from predictionio_tpu.online.foldin import (
            StalenessBudget,
            StalenessExceeded,
        )

        model, (_, _, _, uid, iid) = self._trained_model()
        algo = self._algorithm()
        window_ms = int(time.time() * 1000)
        delta = self._delta(
            uid, iid, [(f"u{k}", "i0", 3.0) for k in range(9)], window_ms,
            budget=StalenessBudget(max_touched_frac=0.2),
        )
        with pytest.raises(StalenessExceeded):
            algo.fold_in(model, delta)


class TestECommerceCategoryRefresh:
    """The fold-in path must rescan the ``$set`` category aggregate when
    the window's touched events include item property records -- before
    this, a category change served stale until the next full retrain."""

    def _ecomm_model(self):
        from predictionio_tpu.models.ecommerce.engine import ECommerceModel
        from predictionio_tpu.parallel.als import (
            ALSConfig, als_fit, build_als_data,
        )

        rng = np.random.default_rng(1)
        U, I, E = 8, 5, 60
        users = rng.integers(0, U, E)
        items = rng.integers(0, I, E)
        cfg = ALSConfig(rank=4, iterations=2, implicit=True)
        als = als_fit(
            build_als_data(users, items, np.ones(E, np.float32), U, I, cfg),
            cfg,
        )
        uid = [f"u{k}" for k in range(U)]
        iid = [f"i{k}" for k in range(I)]
        return ECommerceModel(
            als=als,
            app_name="Shop",
            user_index={u: k for k, u in enumerate(uid)},
            item_ids=iid,
            item_index={i: k for k, i in enumerate(iid)},
            seen={},
            category_items={"old": np.asarray([0], np.int64)},
            similar_events=["view"],
            seen_mode="model",
        ), uid, iid

    def _algo(self):
        from predictionio_tpu.controller.base import Params
        from predictionio_tpu.models.ecommerce.engine import ECommAlgorithm

        return ECommAlgorithm(Params({"rank": 4, "numIterations": 2}))

    def _empty_delta(self, uid, iid, set_types):
        from predictionio_tpu.online.foldin import FoldinDelta

        window_ms = int(time.time() * 1000)
        snap = _FakeSnapshot([], [], [], [], [], list(uid), list(iid), [])
        snap.manifest = {"until_ms": window_ms}
        return FoldinDelta(
            snap, window_ms, set_entity_types=set_types or None
        )

    def test_set_only_window_refreshes_categories(self, monkeypatch):
        from predictionio_tpu.models.ecommerce import engine as ecomm

        model, uid, iid = self._ecomm_model()
        monkeypatch.setattr(
            ecomm, "_load_categories",
            lambda app, channel_name=None: {"i1": ["fresh"], "i3": ["fresh"]},
        )
        out = self._algo().fold_in(
            model, self._empty_delta(uid, iid, {"item"})
        )
        # a $set-only window still publishes: same factor core, new index
        assert out is not None
        assert out.als is model.als
        assert set(out.category_items) == {"fresh"}
        np.testing.assert_array_equal(
            out.category_items["fresh"], np.asarray([1, 3], np.int64)
        )
        # the served (old) model object is untouched
        assert set(model.category_items) == {"old"}

    def test_non_item_set_records_do_not_rescan(self, monkeypatch):
        from predictionio_tpu.models.ecommerce import engine as ecomm

        model, uid, iid = self._ecomm_model()

        def boom(app, channel_name=None):
            raise AssertionError("category aggregate must not be rescanned")

        monkeypatch.setattr(ecomm, "_load_categories", boom)
        # $set on users (or an empty window with no $set at all) -> the
        # old behavior: nothing to fold, nothing published
        assert self._algo().fold_in(
            model, self._empty_delta(uid, iid, {"user"})
        ) is None
        assert self._algo().fold_in(
            model, self._empty_delta(uid, iid, None)
        ) is None

    def test_interactions_and_set_fold_together(self, monkeypatch):
        """A window carrying both a new-item interaction AND an item $set:
        the rescanned index must be built against the EXTENDED item
        vocabulary, so the brand-new item is filterable immediately."""
        from predictionio_tpu.models.ecommerce import engine as ecomm
        from predictionio_tpu.online.foldin import FoldinDelta, StalenessBudget

        model, uid, iid = self._ecomm_model()
        window_ms = int(time.time() * 1000)
        t0 = window_ms / 1000.0
        snap = _FakeSnapshot(
            [0, 0], [len(iid), 1], [0, 0], [t0 + 1, t0 + 2], [np.nan, np.nan],
            list(uid), list(iid) + ["inew"], ["view"],
        )
        monkeypatch.setattr(
            ecomm, "_load_categories",
            lambda app, channel_name=None: {"inew": ["fresh"], "i1": ["fresh"]},
        )
        delta = FoldinDelta(
            snap, window_ms,
            budget=StalenessBudget(1.0, 1.0, 1.0),
            set_entity_types={"item"},
        )
        out = self._algo().fold_in(model, delta)
        assert out is not None and out.als is not model.als
        new_idx = out.item_index["inew"]
        np.testing.assert_array_equal(
            out.category_items["fresh"],
            np.asarray(sorted([1, new_idx]), np.int64),
        )


# ---------------------------------------------------------------------------
# swap under load
# ---------------------------------------------------------------------------

class TestSwapUnderLoad:
    def test_concurrent_queries_across_three_hot_swaps(
        self, storage_env, tmp_path
    ):
        """Concurrent clients across >= 3 hot swaps: zero errors, zero
        dropped requests, and EVERY response attributable to exactly one
        model version -- cross-checked two ways (the x-pio-model-version
        header AND the response body's value, which differs per version by
        construction)."""
        from predictionio_tpu.workflow.create_server import create_query_server

        variant, instance = _train_fake(storage_env, tmp_path)
        registry, versions = _publish_mean_versions(
            variant, instance, [100.0, 200.0, 300.0, 400.0]
        )
        mean_of_version = dict(versions)
        thread, service = create_query_server(
            variant, host="127.0.0.1", port=0, model_version=1
        )
        thread.start()
        url = f"http://127.0.0.1:{thread.port}"
        stop = threading.Event()
        results: list[tuple] = []
        errors: list = []
        lock = threading.Lock()

        def client(k: int) -> None:
            while not stop.is_set():
                try:
                    status, headers, body = _post(
                        url, "/queries.json", {"user": f"u{k}"}
                    )
                    with lock:
                        if status != 200:
                            errors.append((status, body))
                        else:
                            results.append(
                                (headers.get("x-pio-model-version"),
                                 body["rating"])
                            )
                except Exception as exc:  # dropped request
                    with lock:
                        errors.append(("exc", repr(exc)))

        clients = [
            threading.Thread(target=client, args=(k,), daemon=True)
            for k in range(6)
        ]
        try:
            for c in clients:
                c.start()
            for target in (2, 3, 4):  # three hot swaps under live traffic
                time.sleep(0.25)
                status, _, body = _post(
                    url, "/models/swap",
                    {"version": target, "foldinLagSeconds": 0.5},
                )
                assert status == 200 and body["modelVersion"] == target
            time.sleep(0.25)
        finally:
            stop.set()
            for c in clients:
                c.join(timeout=10)
            thread.stop()
            service.close()
        assert not errors, errors[:5]
        assert len(results) > 50  # the clients really ran under the swaps
        seen_versions = set()
        for header_version, rating in results:
            # attribution: header and body must AGREE on one version
            assert header_version is not None
            v = int(header_version)
            assert rating == mean_of_version[v], (v, rating)
            seen_versions.add(v)
        assert len(seen_versions) >= 3  # traffic spanned the swaps

    def test_swap_missing_version_is_404_and_keeps_serving(
        self, storage_env, tmp_path
    ):
        from predictionio_tpu.workflow.create_server import create_query_server

        variant, instance = _train_fake(storage_env, tmp_path, app="Swap404")
        _publish_mean_versions(variant, instance, [10.0])
        thread, service = create_query_server(
            variant, host="127.0.0.1", port=0, model_version=1
        )
        thread.start()
        url = f"http://127.0.0.1:{thread.port}"
        try:
            status, _, body = _post(url, "/models/swap", {"version": 42})
            assert status == 404 and "not found" in body["message"]
            status, _, body = _post(url, "/queries.json", {"user": "u1"})
            assert status == 200 and body["rating"] == 10.0
            status, _, body = _post(url, "/models/lag",
                                    {"foldinLagSeconds": 3.5})
            assert status == 200
            metrics = urllib.request.urlopen(
                f"{url}/metrics", timeout=10
            ).read().decode()
            assert "pio_model_version 1" in metrics
            assert "pio_foldin_lag_seconds 3.5" in metrics
            listing = json.loads(urllib.request.urlopen(
                f"{url}/models.json", timeout=10
            ).read())
            assert listing["currentVersion"] == 1
            assert [v["version"] for v in listing["versions"]] == [1]
        finally:
            thread.stop()
            service.close()


# ---------------------------------------------------------------------------
# deploy --model-version
# ---------------------------------------------------------------------------

class TestDeployModelVersion:
    def test_pinned_version_serves_and_rolls_back(self, storage_env, tmp_path):
        from predictionio_tpu.workflow.create_server import create_query_server

        variant, instance = _train_fake(storage_env, tmp_path, app="PinApp")
        _publish_mean_versions(variant, instance, [11.0, 22.0])
        # pin the OLDER version: rollback via redeploy
        thread, service = create_query_server(
            variant, host="127.0.0.1", port=0, model_version=1
        )
        thread.start()
        url = f"http://127.0.0.1:{thread.port}"
        try:
            status, headers, body = _post(url, "/queries.json", {"user": "x"})
            assert status == 200 and body["rating"] == 11.0
            assert headers.get("x-pio-model-version") == "1"
            info = json.loads(
                urllib.request.urlopen(f"{url}/", timeout=10).read()
            )
            assert info["modelVersion"] == 1
        finally:
            thread.stop()
            service.close()

    def test_missing_and_corrupt_versions_fail_loudly(
        self, storage_env, tmp_path
    ):
        from predictionio_tpu.online.registry import (
            ModelRegistry,
            RegistryError,
        )
        from predictionio_tpu.workflow.create_server import QueryService

        variant, instance = _train_fake(storage_env, tmp_path, app="BadApp")
        registry, _ = _publish_mean_versions(variant, instance, [5.0])
        with pytest.raises(RegistryError, match="not found"):
            QueryService(variant, model_version=77)
        v = registry.get(1)
        with open(os.path.join(v.path, "model.bin"), "r+b") as f:
            f.write(b"\xff")
        with pytest.raises(RegistryError, match="CRC mismatch"):
            QueryService(variant, model_version=1)

    def test_cli_flags_parse(self):
        from predictionio_tpu.tools.cli import build_parser

        parser = build_parser()
        args = parser.parse_args(["deploy", "--model-version", "3"])
        assert args.model_version == 3
        args = parser.parse_args(
            ["retrain", "--follow", "--interval", "0.5", "--max-cycles", "2",
             "--notify", "http://localhost:1234"]
        )
        assert args.follow and args.max_cycles == 2
        assert args.notify == ["http://localhost:1234"]


# ---------------------------------------------------------------------------
# the loop end-to-end: freshness + SIGKILL recovery
# ---------------------------------------------------------------------------

class TestRetrainLoopE2E:
    def test_freshness_under_concurrent_load(self, storage_env, tmp_path):
        """Acceptance: an event ingested at t is reflected in
        /queries.json within 10 s under concurrent serving load, across
        >= 3 fold-in hot swaps, with zero client errors."""
        from predictionio_tpu.data.wal import WriteAheadLog
        from predictionio_tpu.online.loop import RetrainConfig, RetrainLoop
        from predictionio_tpu.workflow.create_server import create_query_server

        variant = _recommendation_variant(storage_env, tmp_path)
        thread, service = create_query_server(variant, host="127.0.0.1", port=0)
        thread.start()
        url = f"http://127.0.0.1:{thread.port}"
        wal = WriteAheadLog(str(tmp_path / "wal"))
        loop = RetrainLoop(
            variant,
            RetrainConfig(
                interval_s=0.1, notify_urls=[url],
                wal_dir=str(tmp_path / "wal"),
            ),
        )
        loop_thread = threading.Thread(target=loop.run_follow, daemon=True)
        loop_thread.start()
        stop = threading.Event()
        load_errors: list = []

        def load_client(k: int) -> None:
            while not stop.is_set():
                try:
                    status, _, _ = _post(url, "/queries.json",
                                         {"user": f"u{k % 10}", "num": 2})
                    if status != 200:
                        load_errors.append(status)
                except Exception as exc:
                    load_errors.append(repr(exc))

        clients = [
            threading.Thread(target=load_client, args=(k,), daemon=True)
            for k in range(3)
        ]
        freshness = []
        try:
            for c in clients:
                c.start()
            le = storage_env.get_l_events()
            for k in range(3):  # three probes -> three fold-in swaps
                user = f"fresh{k}"
                _ingest_via_wal(wal, le, user, f"i{k % 5}")
                t0 = time.perf_counter()
                deadline = t0 + 10.0
                visible = None
                while time.perf_counter() < deadline:
                    status, _, body = _post(
                        url, "/queries.json", {"user": user, "num": 3}
                    )
                    if status == 200 and body.get("itemScores"):
                        visible = time.perf_counter()
                        break
                    time.sleep(0.05)
                assert visible is not None, (
                    f"probe {k}: event not reflected within 10s"
                )
                freshness.append(visible - t0)
        finally:
            stop.set()
            loop.stop()
            loop_thread.join(timeout=30)
            for c in clients:
                c.join(timeout=10)
            thread.stop()
            service.close()
            wal.close()
        assert not load_errors, load_errors[:5]
        assert loop.cycles.get("foldin", 0) >= 3
        assert max(freshness) < 10.0

    def test_sigkill_mid_fold_in_recovers(self, storage_env, tmp_path):
        """SIGKILL between fold-in and publish: the cursor must NOT have
        advanced past the unswapped model, the registry must hold no torn
        version, and a second run must converge (publish + reflect the
        events)."""
        from predictionio_tpu.data.wal import WriteAheadLog
        from predictionio_tpu.online.loop import RetrainConfig, RetrainLoop
        from predictionio_tpu.online.registry import ModelRegistry

        variant = _recommendation_variant(
            storage_env, tmp_path, app="KillApp"
        )
        wal = WriteAheadLog(str(tmp_path / "wal"))
        le = storage_env.get_l_events()
        seqno = _ingest_via_wal(wal, le, "killuser", "i2")
        wal.close()

        script = tmp_path / "killable.py"
        script.write_text(
            "import sys\n"
            "from predictionio_tpu.workflow.json_extractor import"
            " load_engine_variant\n"
            "from predictionio_tpu.online.loop import RetrainConfig,"
            " RetrainLoop\n"
            "variant = load_engine_variant(sys.argv[1])\n"
            "loop = RetrainLoop(variant, RetrainConfig(notify_urls=[],"
            f" wal_dir={str(tmp_path / 'wal')!r}))\n"
            "print(loop.run_once())\n"
        )
        marker = tmp_path / "holding.marker"
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "PIO_FS_BASEDIR": str(tmp_path),
            "PIO_ONLINE_TEST_HOLD_S": "120",
            "PIO_ONLINE_TEST_HOLD_FILE": str(marker),
            "PIO_LOCKWATCH": "0",
            # `python script.py` puts the SCRIPT's dir on sys.path, not cwd
            "PYTHONPATH": repo_root + os.pathsep + env_pythonpath()
            if env_pythonpath()
            else repo_root,
        }
        proc = subprocess.Popen(
            [sys.executable, str(script), str(tmp_path / "engine.json")],
            env=env, cwd=repo_root,
        )
        try:
            deadline = time.time() + 120
            while not marker.exists():
                assert proc.poll() is None, "loop process died before hold"
                assert time.time() < deadline, "never reached the hold window"
                time.sleep(0.1)
            # mid-fold-in (model folded, nothing published): SIGKILL
            os.kill(proc.pid, signal.SIGKILL)
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()

        registry = ModelRegistry.for_variant(variant)
        cursor_path = os.path.join(registry.dir, "follow", "cursor.json")
        # cursor not advanced past an unswapped model
        if os.path.exists(cursor_path):
            state = json.load(open(cursor_path))
            assert state.get("seqno", 0) < seqno
        assert registry.latest() is None  # no torn version published

        # second run (in-process, no hold) converges
        loop = RetrainLoop(
            variant,
            RetrainConfig(notify_urls=[], wal_dir=str(tmp_path / "wal")),
        )
        result = loop.run_once()
        assert result == "foldin"
        assert loop.cursor.seqno == seqno
        v = registry.latest()
        assert v is not None and v.source == "foldin"
        # the published model reflects the event: the folded user exists
        import pickle

        entries = pickle.loads(v.load_blob())
        kind, payload = entries[0]
        model = pickle.loads(payload)
        assert "killuser" in model.user_index
        assert (
            np.abs(
                model.als.user_factors[model.user_index["killuser"]]
            ).max()
            > 0
        )
        # third run: idle (nothing new), cursor stable
        assert loop.run_once() == "idle"


class TestRetrainLoopEdges:
    def _loop(self, storage_env, tmp_path, app, **cfg_kw):
        from predictionio_tpu.online.loop import RetrainConfig, RetrainLoop

        variant = _recommendation_variant(storage_env, tmp_path, app=app)
        loop = RetrainLoop(
            variant,
            RetrainConfig(
                notify_urls=[], wal_dir=str(tmp_path / "wal"), **cfg_kw
            ),
        )
        return variant, loop

    def test_future_dated_event_defers_then_folds(self, storage_env, tmp_path):
        """A record dated slightly ahead of the wall clock (client skew)
        must not be skipped: the cursor defers until its event time passes,
        then the record folds normally."""
        from predictionio_tpu.data.wal import WriteAheadLog

        _, loop = self._loop(storage_env, tmp_path, "SkewApp")
        wal = WriteAheadLog(str(tmp_path / "wal"))
        future = _dt.datetime.now(_dt.timezone.utc) + _dt.timedelta(seconds=1.5)
        seqno = _ingest_via_wal(
            wal, storage_env.get_l_events(), "skewuser", "i1",
            event_time=future,
        )
        assert loop.run_once() == "deferred"
        assert loop.cursor.seqno < seqno  # not advanced past the record
        time.sleep(1.6)
        assert loop.run_once() == "foldin"
        assert loop.cursor.seqno == seqno
        wal.close()

    def test_gap_without_full_retrain_stays_put(self, storage_env, tmp_path):
        """A WAL GC gap with escalation disabled must neither advance the
        cursor nor publish (the delta is unknown)."""
        from predictionio_tpu.data.wal import WriteAheadLog, _segment_name

        _, loop = self._loop(
            storage_env, tmp_path, "GapApp", allow_full_retrain=False
        )
        wal = WriteAheadLog(str(tmp_path / "wal"))
        _ingest_via_wal(wal, storage_env.get_l_events(), "gapuser", "i0")
        wal.close()
        seg = next(
            p for p in os.listdir(tmp_path / "wal") if p.endswith(".log")
        )
        os.rename(
            tmp_path / "wal" / seg, tmp_path / "wal" / _segment_name(50)
        )
        with open(tmp_path / "wal" / "wal.ckpt", "w") as f:
            f.write("60")
        assert loop.run_once() == "noop"
        assert loop.cursor.seqno == 0
        assert loop.registry.latest() is None

    def test_budget_escalation_runs_full_retrain(self, storage_env, tmp_path):
        """max_touched_frac=0 forces every delta through the full-retrain
        path: a 'train'-sourced version publishes, the cursor advances,
        and the loop's params are re-derived from the NEW instance."""
        from predictionio_tpu.data.wal import WriteAheadLog
        from predictionio_tpu.online.foldin import StalenessBudget

        _, loop = self._loop(
            storage_env, tmp_path, "EscApp",
            budget=StalenessBudget(max_touched_frac=0.0),
        )
        wal = WriteAheadLog(str(tmp_path / "wal"))
        seqno = _ingest_via_wal(wal, storage_env.get_l_events(), "escuser", "i1")
        wal.close()
        assert loop.run_once() == "full_retrain"
        assert loop.cursor.seqno == seqno
        v = loop.registry.latest()
        assert v is not None and v.source == "train"
        assert v.instance_id == loop.instance.id
        # the retrained model includes the new user (full read covers it)
        assert any(
            "escuser" in getattr(m, "user_index", {}) for m in loop.models
        )


class TestPartitionedLoop:
    """The retrain loop against a P>1 WAL: one tail + one durable cursor
    per partition, merged fold-ins, and partition-failure isolation (the
    'one dead follower' chaos case: siblings advance, the dead partition's
    window is excluded from the publish, recovery/restart converges)."""

    def _partitioned_loop(self, storage_env, tmp_path, app, partitions=2):
        from predictionio_tpu.data.wal import PartitionedWal
        from predictionio_tpu.online.loop import RetrainConfig, RetrainLoop

        variant = _recommendation_variant(storage_env, tmp_path, app=app)
        # the WAL must exist first: the loop discovers the layout off disk
        pwal = PartitionedWal(str(tmp_path / "wal"), partitions=partitions)
        loop = RetrainLoop(
            variant,
            RetrainConfig(notify_urls=[], wal_dir=str(tmp_path / "wal")),
        )
        return variant, pwal, loop

    def _ingest_routed(self, pwal, le, user, item):
        """One durable ingest into the partition the user hashes to (the
        event server's routing rule); returns (partition, seqno)."""
        from predictionio_tpu.utils.stablehash import stable_bucket

        part = stable_bucket(user, pwal.partitions)
        return part, _ingest_via_wal(pwal.part(part), le, user, item)

    def _users_covering(self, partitions, prefix="pfresh"):
        """New user ids, one hashing into EACH partition."""
        from predictionio_tpu.utils.stablehash import stable_bucket

        found = {}
        i = 0
        while len(found) < partitions:
            user = f"{prefix}-{i}"
            found.setdefault(stable_bucket(user, partitions), user)
            i += 1
        return [found[k] for k in range(partitions)]

    def test_cycle_merges_partitions_and_advances_each_cursor(
        self, storage_env, tmp_path
    ):
        variant, pwal, loop = self._partitioned_loop(
            storage_env, tmp_path, "PartLoopApp"
        )
        assert loop.partitions == 2
        le = storage_env.get_l_events()
        u0, u1 = self._users_covering(2)
        p0, s0 = self._ingest_routed(pwal, le, u0, "i1")
        p1, s1 = self._ingest_routed(pwal, le, u1, "i2")
        assert (p0, p1) == (0, 1)
        assert loop.run_once() == "foldin"
        # each partition's cursor advanced to ITS seqno space's head
        assert loop.cursors[0].seqno == s0
        assert loop.cursors[1].seqno == s1
        follow = os.path.join(loop.registry.dir, "follow")
        assert os.path.exists(os.path.join(follow, "cursor-p00000.json"))
        assert os.path.exists(os.path.join(follow, "cursor-p00001.json"))
        # ONE merged publish: both partitions' users folded into one model
        assert loop.registry.latest().source == "foldin"
        for user in (u0, u1):
            assert any(
                user in getattr(m, "user_index", {}) for m in loop.models
            )
        assert loop.run_once() == "idle"
        pwal.close()

    def test_partition_failure_isolated_then_converges(
        self, storage_env, tmp_path, monkeypatch
    ):
        variant, pwal, loop = self._partitioned_loop(
            storage_env, tmp_path, "PartFailApp"
        )
        le = storage_env.get_l_events()
        u0, u1 = self._users_covering(2, prefix="pkill")
        _, s0 = self._ingest_routed(pwal, le, u0, "i1")
        _, s1 = self._ingest_routed(pwal, le, u1, "i2")

        # partition 1's follower "dies" mid-cycle: its sibling still folds
        # and publishes; the dead partition's cursor holds its window
        monkeypatch.setenv("PIO_ONLINE_TEST_FAIL_PART", "1")
        assert loop.run_once() == "foldin"
        assert loop.cursors[0].seqno == s0
        assert loop.cursors[1].seqno == 0
        assert loop.cycles["part_failures"] >= 1
        generation = loop.registry.latest().version
        assert any(u0 in getattr(m, "user_index", {}) for m in loop.models)
        # the dead partition's WINDOW stays excluded from the cycle's
        # seqno accounting (cursor at 0 above): its records are only in
        # the publish because the SQL-exact snapshot already flushed them;
        # change DETECTION for that partition replays on recovery

        # recovery: the held window replays and folds; the cursor catches
        # up and a newer generation publishes
        monkeypatch.delenv("PIO_ONLINE_TEST_FAIL_PART")
        assert loop.run_once() == "foldin"
        assert loop.cursors[1].seqno == s1
        assert loop.registry.latest().version > generation
        assert any(u1 in getattr(m, "user_index", {}) for m in loop.models)

        # a RESTARTED follower (fresh loop, cursors re-read from disk)
        # agrees the world converged: nothing pending anywhere
        from predictionio_tpu.online.loop import RetrainConfig, RetrainLoop

        loop2 = RetrainLoop(
            variant,
            RetrainConfig(notify_urls=[], wal_dir=str(tmp_path / "wal")),
        )
        assert loop2.partitions == 2
        assert [c.seqno for c in loop2.cursors] == [s0, s1]
        assert loop2.run_once() == "idle"
        pwal.close()


# ---------------------------------------------------------------------------
# pio top
# ---------------------------------------------------------------------------

class TestTopOnlineColumns:
    def _snap(self, t, extra=""):
        from predictionio_tpu.obs.top import parse_prometheus

        text = (
            'pio_http_requests_total{method="POST",route="/queries.json",'
            'status="200"} 100\n' + extra
        )
        return {"url": "http://qs:8000", "time": t,
                "metrics": parse_prometheus(text), "traces": None}

    def test_stats_and_render(self):
        from predictionio_tpu.obs.top import compute_stats, render

        now_ts = time.time()
        extra = (
            "pio_model_version 7\n"
            f"pio_model_last_swap_timestamp_seconds {now_ts - 30:.3f}\n"
            "pio_foldin_lag_seconds 2.5\n"
        )
        stats = compute_stats(self._snap(100.0), self._snap(102.0, extra))
        assert stats["model_version"] == 7
        assert 25.0 <= stats["swap_age_s"] <= 60.0
        assert stats["foldin_lag_s"] == 2.5
        frame = render([stats], [self._snap(102.0, extra)])
        assert "MODEL" in frame and "LAG" in frame
        assert "7" in frame and "2.5s" in frame

    def test_absent_gauges_render_dashes(self):
        from predictionio_tpu.obs.top import compute_stats, render

        stats = compute_stats(self._snap(100.0), self._snap(102.0))
        assert "model_version" not in stats
        frame = render([stats], [self._snap(102.0)])
        assert "MODEL" in frame  # column exists, value is "-"
