"""Ingest pipeline tests: group commit, WAL-durable acks, backpressure,
drain-on-shutdown, startup replay idempotence, the batch wire contract
under the pipeline, hash-partitioned routing and per-partition replay,
and SIGKILL crash-replay integration cycles (flat and partitioned)."""

import json
import threading
import time

import pytest
import requests

from predictionio_tpu.data import wal as wal_mod
from predictionio_tpu.data.api.eventserver import (
    EventService,
    create_event_server,
)
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.ingest import (
    IngestConfig,
    IngestOverload,
    IngestPipeline,
    PartitionedIngestPipeline,
    partition_of,
    replay_partitioned_wal,
    replay_wal_into_storage,
    wal_parse,
)
from predictionio_tpu.data.storage.base import AccessKey, App
from predictionio_tpu.data.wal import PartitionedWal, WriteAheadLog
from predictionio_tpu.utils.http import Request
from predictionio_tpu.utils.stablehash import stable_bucket

VALID = {"event": "rate", "entityType": "user", "entityId": "u1",
         "targetEntityType": "item", "targetEntityId": "i1",
         "properties": {"rating": 4}}


def _mk_event(i: int = 0, **over) -> Event:
    obj = {**VALID, "entityId": f"u{i}", **over}
    return Event.from_json_obj(obj)


def _poll(fn, timeout=5.0, interval=0.01):
    """Group-commit acks precede the storage flush by design; reads that
    follow a write poll briefly instead of racing it."""
    deadline = time.monotonic() + timeout
    while True:
        result = fn()
        if result or time.monotonic() >= deadline:
            return result
        time.sleep(interval)


# -- pipeline unit tests ------------------------------------------------------

class TestPipeline:
    def test_group_commit_batches_and_stores_all(self, storage_env, tmp_path):
        l_events = storage_env.get_l_events()
        l_events.init_channel(1)
        calls = []

        class _Counting:
            def insert_batch(self, items, on_duplicate="error"):
                calls.append(len(items))
                return l_events.insert_batch(items, on_duplicate=on_duplicate)

        wal = WriteAheadLog(str(tmp_path / "wal"))
        pipe = IngestPipeline(
            wal, l_events=lambda: _Counting(), group_commit_ms=20.0
        ).start()
        futures = [pipe.submit(_mk_event(i), 1, None) for i in range(40)]
        ids = [f.result(timeout=10) for f in futures]
        pipe.stop()
        wal.close()
        assert len(set(ids)) == 40
        stored = {e.event_id for e in l_events.find(app_id=1, limit=None)}
        assert stored == set(ids)
        # grouped: far fewer storage transactions than events
        assert sum(calls) == 40 and len(calls) < 40

    def test_backpressure_raises_overload(self, storage_env, tmp_path):
        release = threading.Event()

        class _Stalled:
            def insert_batch(self, items, on_duplicate="error"):
                release.wait(10)
                return [ev.event_id for ev, _, _ in items]

        wal = WriteAheadLog(str(tmp_path / "wal"))
        pipe = IngestPipeline(
            wal, l_events=lambda: _Stalled(), queue_size=2, max_batch=1,
            group_commit_ms=1.0,
        ).start()
        try:
            pipe.submit(_mk_event(0), 1, None)  # writer takes this, stalls
            time.sleep(0.1)
            pipe.submit(_mk_event(1), 1, None)
            pipe.submit(_mk_event(2), 1, None)
            with pytest.raises(IngestOverload):
                pipe.submit(_mk_event(3), 1, None)
        finally:
            release.set()
            pipe.stop()
            wal.close()

    def test_stop_drains_queue(self, storage_env, tmp_path):
        l_events = storage_env.get_l_events()
        l_events.init_channel(1)
        wal = WriteAheadLog(str(tmp_path / "wal"))
        pipe = IngestPipeline(wal, group_commit_ms=50.0, max_batch=8).start()
        futures = [pipe.submit(_mk_event(i), 1, None) for i in range(30)]
        pipe.stop(drain=True)
        wal.close()
        assert all(f.done() for f in futures)
        assert sum(1 for _ in l_events.find(app_id=1, limit=None)) == 30

    def test_storage_failure_acks_and_replay_recovers(self, storage_env, tmp_path):
        """Crash-window semantics without a crash: the flush fails after the
        WAL ack; a 'restart' replay applies the events exactly once."""
        l_events = storage_env.get_l_events()
        l_events.init_channel(1)

        class _Broken:
            def insert_batch(self, items, on_duplicate="error"):
                raise RuntimeError("storage down")

        wal_dir = str(tmp_path / "wal")
        wal = WriteAheadLog(wal_dir)
        pipe = IngestPipeline(wal, l_events=lambda: _Broken()).start()
        futures = [pipe.submit(_mk_event(i), 1, None) for i in range(5)]
        ids = [f.result(timeout=10) for f in futures]  # acked: WAL-durable
        pipe.stop()
        wal.close()
        assert sum(1 for _ in l_events.find(app_id=1, limit=None)) == 0

        wal2 = WriteAheadLog(wal_dir)
        assert replay_wal_into_storage(wal2) == 5
        stored = {e.event_id for e in l_events.find(app_id=1, limit=None)}
        assert stored == set(ids)
        # second restart: idempotent, nothing left past the checkpoint
        assert replay_wal_into_storage(wal2) == 0
        wal2.close()
        assert sum(1 for _ in l_events.find(app_id=1, limit=None)) == 5

    def test_transient_storage_failure_recovers_in_process(self, storage_env, tmp_path):
        """A later healthy batch must NOT checkpoint past an earlier failed
        one (that would strand, then GC, acked records); the writer re-flushes
        the failed batch in order and reads see it without a restart."""
        l_events = storage_env.get_l_events()
        l_events.init_channel(1)
        fail_once = {"armed": True}

        class _Flaky:
            def insert_batch(self, items, on_duplicate="error"):
                if fail_once["armed"]:
                    fail_once["armed"] = False
                    raise RuntimeError("transient outage")
                return l_events.insert_batch(items, on_duplicate=on_duplicate)

        wal_dir = str(tmp_path / "wal")
        wal = WriteAheadLog(wal_dir)
        pipe = IngestPipeline(
            wal, l_events=lambda: _Flaky(), group_commit_ms=1.0
        ).start()
        first = pipe.submit(_mk_event(0), 1, None)
        assert first.result(timeout=10)  # acked; flush failed and parked
        second = pipe.submit(_mk_event(1), 1, None)
        assert second.result(timeout=10)
        stored = _poll(
            lambda: (
                {e.event_id for e in l_events.find(app_id=1, limit=None)}
                if sum(1 for _ in l_events.find(app_id=1, limit=None)) == 2
                else None
            )
        )
        pipe.stop()
        wal.close()
        assert stored == {first.result(), second.result()}
        # checkpoint caught up through BOTH batches: a restart replays nothing
        wal2 = WriteAheadLog(wal_dir)
        assert replay_wal_into_storage(wal2) == 0
        wal2.close()

    def test_client_supplied_duplicate_id_does_not_poison_batch(
        self, storage_env, tmp_path
    ):
        l_events = storage_env.get_l_events()
        l_events.init_channel(1)
        wal = WriteAheadLog(str(tmp_path / "wal"))
        pipe = IngestPipeline(wal, group_commit_ms=20.0).start()
        dup = _mk_event(0).with_id("fixed-id")
        futures = [pipe.submit(dup, 1, None)]
        futures += [pipe.submit(_mk_event(i), 1, None) for i in range(1, 9)]
        futures.append(pipe.submit(_mk_event(0).with_id("fixed-id"), 1, None))
        ids = [f.result(timeout=10) for f in futures]
        pipe.stop()
        wal.close()
        assert ids[0] == ids[-1] == "fixed-id"
        stored = [e.event_id for e in l_events.find(app_id=1, limit=None)]
        # batchmates all landed; the duplicate deduped instead of aborting
        # the shared transaction
        assert sorted(stored) == sorted(set(ids))

    def test_insert_batch_duplicate_modes(self, storage_env):
        l_events = storage_env.get_l_events()
        l_events.init_channel(1)
        ev = _mk_event(0).with_id()
        l_events.insert_batch([(ev, 1, None)])
        # ignore: replay-idempotence mode skips the duplicate silently
        l_events.insert_batch([(ev, 1, None)], on_duplicate="ignore")
        assert sum(1 for _ in l_events.find(app_id=1, limit=None)) == 1
        # error: the append-only contract surfaces the caller bug
        with pytest.raises(Exception):
            l_events.insert_batch([(ev, 1, None)])


# -- partitioned pipeline -----------------------------------------------------

class TestPartitionedPipeline:
    def test_routes_by_entity_hash_and_stores_all(self, storage_env, tmp_path):
        """Every frame must land in the partition its entity hashes to --
        the shardmap rule -- and the full stream must store exactly once."""
        l_events = storage_env.get_l_events()
        l_events.init_channel(1)
        wal = PartitionedWal(str(tmp_path / "wal"), partitions=4)
        pipe = PartitionedIngestPipeline(wal, group_commit_ms=5.0).start()
        events = [_mk_event(i) for i in range(64)]
        futures = [pipe.submit(ev, 1, None) for ev in events]
        ids = [f.result(timeout=10) for f in futures]
        pipe.stop()
        assert len(set(ids)) == 64
        stored = {e.event_id for e in l_events.find(app_id=1, limit=None)}
        assert stored == set(ids)
        seen_parts = set()
        for k, part in enumerate(wal.parts):
            for _seqno, payload in wal_mod.iter_log_records(part.directory):
                ev, _app, _chan, _trace = wal_parse(payload)
                assert stable_bucket(ev.entity_id, 4) == k
                seen_parts.add(k)
        assert seen_parts == {0, 1, 2, 3}  # 64 entities cover every partition
        wal.close()

    def test_same_entity_always_same_partition(self, storage_env, tmp_path):
        """Per-entity ordering rides on routing stability: one entity, one
        partition, one seqno line."""
        l_events = storage_env.get_l_events()
        l_events.init_channel(1)
        wal = PartitionedWal(str(tmp_path / "wal"), partitions=4)
        pipe = PartitionedIngestPipeline(wal, group_commit_ms=2.0).start()
        futures = [pipe.submit(_mk_event(7), 1, None) for _ in range(12)]
        for f in futures:
            f.result(timeout=10)
        pipe.stop()
        home = partition_of(_mk_event(7), 4)
        counts = [
            sum(1 for _ in wal_mod.iter_log_records(p.directory))
            for p in wal.parts
        ]
        assert counts[home] == 12
        assert sum(counts) == 12
        wal.close()

    def test_p1_inner_pipeline_is_unlabeled(self, storage_env, tmp_path):
        """P=1 must be observably identical to the pre-partitioning
        pipeline: no part label, original writer-thread name."""
        wal1 = PartitionedWal(str(tmp_path / "w1"), partitions=1)
        pipe1 = PartitionedIngestPipeline(wal1)
        assert pipe1.partitions == 1
        assert pipe1.pipes[0].part is None
        wal4 = PartitionedWal(str(tmp_path / "w4"), partitions=4)
        pipe4 = PartitionedIngestPipeline(wal4)
        assert [p.part for p in pipe4.pipes] == [0, 1, 2, 3]
        wal1.close()
        wal4.close()

    def test_depth_of_and_aggregates(self, storage_env, tmp_path):
        release = threading.Event()

        class _Stalled:
            def insert_batch(self, items, on_duplicate="error"):
                release.wait(10)
                return [ev.event_id for ev, _, _ in items]

        wal = PartitionedWal(str(tmp_path / "wal"), partitions=2)
        pipe = PartitionedIngestPipeline(
            wal, l_events=lambda: _Stalled(), group_commit_ms=1.0
        ).start()
        try:
            # park both writers, then queue one more per partition
            first = [_mk_event(i) for i in range(8)]
            for ev in first:
                pipe.submit(ev, 1, None)
            time.sleep(0.15)
            queued = [_mk_event(i) for i in range(8, 16)]
            for ev in queued:
                pipe.submit(ev, 1, None)
            assert pipe.depth() == sum(
                pipe.depth_of(k) for k in range(pipe.partitions)
            )
        finally:
            release.set()
            pipe.stop()
            wal.close()

    def test_partitioned_replay_exactly_once(self, storage_env, tmp_path):
        """Acked-but-unflushed events recover independently per partition;
        a second restart replays nothing anywhere."""
        l_events = storage_env.get_l_events()
        l_events.init_channel(1)

        class _Broken:
            def insert_batch(self, items, on_duplicate="error"):
                raise RuntimeError("storage down")

        wal_dir = str(tmp_path / "wal")
        wal = PartitionedWal(wal_dir, partitions=4)
        pipe = PartitionedIngestPipeline(wal, l_events=lambda: _Broken()).start()
        futures = [pipe.submit(_mk_event(i), 1, None) for i in range(24)]
        ids = [f.result(timeout=10) for f in futures]  # acked: WAL-durable
        pipe.stop()
        wal.close()
        assert sum(1 for _ in l_events.find(app_id=1, limit=None)) == 0

        wal2 = PartitionedWal(wal_dir)  # layout adopted from the marker
        assert wal2.partitions == 4
        assert replay_partitioned_wal(wal2) == 24
        stored = {e.event_id for e in l_events.find(app_id=1, limit=None)}
        assert stored == set(ids)
        assert replay_partitioned_wal(wal2) == 0
        wal2.close()

    def test_eventserver_exposes_partition_gauges(self, storage_env):
        apps = storage_env.get_meta_data_apps()
        app_id = apps.insert(App(name="PartApp"))
        key = storage_env.get_meta_data_access_keys().insert(
            AccessKey(key="", app_id=app_id)
        )
        storage_env.get_l_events().init_channel(app_id)
        svc = create_event_server(
            host="127.0.0.1",
            port=0,
            stats=True,
            ingest_config=IngestConfig(
                mode="wal", group_commit_ms=2.0, wal_partitions=3
            ),
        ).start()
        base = f"http://127.0.0.1:{svc.port}"
        try:
            r = requests.post(
                f"{base}/events.json", params={"accessKey": key}, json=VALID
            )
            assert r.status_code == 201
            # the 201 is the WAL ack; the commit histogram is observed by
            # the writer after the storage flush that follows it, so poll
            # instead of racing it (lost on a loaded 2-core box)
            deadline = time.time() + 10.0
            while True:
                text = requests.get(f"{base}/metrics").text
                if 'pio_ingest_commit_seconds_count{part="' in text:
                    break
                assert time.time() < deadline, text[-600:]
                time.sleep(0.05)
        finally:
            svc.stop()
        assert "pio_ingest_partitions 3" in text
        for k in range(3):
            assert f'pio_ingest_partition_depth{{part="{k}"}}' in text
        # commit-latency histogram carries the partition label once the
        # routed partition has committed
        assert 'pio_ingest_commit_seconds_count{part="' in text


# -- event server in WAL mode -------------------------------------------------

@pytest.fixture()
def wal_server(storage_env, tmp_path):
    apps = storage_env.get_meta_data_apps()
    app_id = apps.insert(App(name="WalApp"))
    key = storage_env.get_meta_data_access_keys().insert(
        AccessKey(key="", app_id=app_id)
    )
    storage_env.get_l_events().init_channel(app_id)
    svc = create_event_server(
        host="127.0.0.1",
        port=0,
        stats=True,
        ingest_config=IngestConfig(mode="wal", group_commit_ms=2.0),
    ).start()
    base = f"http://127.0.0.1:{svc.port}"
    yield base, key
    svc.stop()


class TestWalServer:
    def test_wire_contract_bit_compatible(self, wal_server):
        base, key = wal_server
        r = requests.post(f"{base}/events.json", params={"accessKey": key}, json=VALID)
        assert r.status_code == 201
        eid = r.json()["eventId"]
        got = _poll(
            lambda: requests.get(
                f"{base}/events/{eid}.json", params={"accessKey": key}
            ).json().get("event")
        )
        assert got == "rate"

    def test_batch_item_isolation_and_cap_under_pipeline(self, wal_server):
        base, key = wal_server
        batch = [VALID, {"event": "$bad", "entityType": "u", "entityId": "1"}, VALID]
        r = requests.post(
            f"{base}/batch/events.json", params={"accessKey": key}, json=batch
        )
        assert r.status_code == 200
        results = r.json()
        assert [x["status"] for x in results] == [201, 400, 201]
        assert "eventId" in results[0] and "message" in results[1]
        r = requests.post(
            f"{base}/batch/events.json", params={"accessKey": key}, json=[VALID] * 51
        )
        assert r.status_code == 400
        r = requests.post(
            f"{base}/batch/events.json", params={"accessKey": key},
            json={"not": "array"},
        )
        assert r.status_code == 400

    def test_concurrent_writers_all_stored_and_ordered(self, wal_server):
        base, key = wal_server
        writers, per_writer = 8, 10

        def post(w):
            for i in range(per_writer):
                body = {
                    **VALID,
                    "entityId": f"w{w}",
                    "eventTime": f"2024-01-{w + 1:02d}T00:{i:02d}:00Z",
                }
                r = requests.post(
                    f"{base}/events.json", params={"accessKey": key}, json=body
                )
                assert r.status_code == 201

        threads = [
            threading.Thread(target=post, args=(w,)) for w in range(writers)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = writers * per_writer
        events = _poll(
            lambda: (
                lambda got: got if len(got) == total else None
            )(
                requests.get(
                    f"{base}/events.json",
                    params={"accessKey": key, "limit": "-1"},
                ).json()
            )
        )
        assert events is not None and len(events) == total
        times = [e["eventTime"] for e in events]
        assert times == sorted(times)  # find() is time-ordered across writers

    def test_queue_full_yields_429_with_retry_after(self, storage_env, tmp_path):
        """Service-level: a stalled store + tiny queue must reject with the
        backpressure contract (429 + Retry-After), not park threads."""
        release = threading.Event()

        class _Stalled:
            def insert_batch(self, items, on_duplicate="error"):
                release.wait(10)
                return [ev.event_id for ev, _, _ in items]

        key = storage_env.get_meta_data_access_keys().insert(
            AccessKey(key="", app_id=1)
        )
        service = EventService()
        wal = WriteAheadLog(str(tmp_path / "wal"))
        pipe = IngestPipeline(
            wal, l_events=lambda: _Stalled(), queue_size=1, max_batch=1,
            group_commit_ms=1.0,
        ).start()
        service.ingest = pipe
        try:
            # writer takes the first event (WAL-acks it) and stalls in the
            # storage flush; wait until it has left the queue
            fut = pipe.submit(_mk_event(0), 1, None)
            assert fut.result(timeout=10)
            assert _poll(lambda: pipe.depth() == 0)
            pipe.submit(_mk_event(1), 1, None)  # fills the 1-slot queue

            resp = service.handle_create_event(
                Request(
                    method="POST",
                    path="/events.json",
                    query={"accessKey": key},
                    headers={},
                    body=json.dumps(VALID).encode(),
                    path_params={},
                )
            )
            assert resp.status == 429
            assert resp.headers.get("Retry-After")
        finally:
            release.set()
            pipe.stop(drain=False)
            wal.close()


def test_ack_waits_for_fsync_despite_lock_free_sync(storage_env, tmp_path, monkeypatch):
    """Regression for the C002 fix (fsync moved outside the WAL writer
    lock): the group-commit ack ordering is preserved -- a submit's future
    must not resolve until the WAL fsync for its batch completes, and acks
    still arrive in submit order."""
    import os as _os

    l_events = storage_env.get_l_events()
    l_events.init_channel(1)
    in_fsync = threading.Event()
    release = threading.Event()
    real_fsync = _os.fsync

    def gated_fsync(fd):
        in_fsync.set()
        assert release.wait(timeout=10)
        return real_fsync(fd)

    monkeypatch.setattr(_os, "fsync", gated_fsync)
    wal = WriteAheadLog(str(tmp_path / "wal"), fsync_policy="always")
    pipe = IngestPipeline(wal, group_commit_ms=5.0).start()
    try:
        # pre-assigned ids so the futures' results are comparable directly
        events = [_mk_event(i).with_id() for i in range(4)]
        futures = [pipe.submit(ev, 1, None) for ev in events]
        assert in_fsync.wait(timeout=5)
        time.sleep(0.05)
        # durability gate still closed: nothing may be acked yet
        assert not any(f.done() for f in futures)
        release.set()
        ids = [f.result(timeout=10) for f in futures]
        # each ack resolves to ITS event's id, in submit order
        assert ids == [ev.event_id for ev in events]
        assert len(set(ids)) == 4
    finally:
        release.set()
        monkeypatch.undo()
        pipe.stop()
        wal.close()


# -- crash-replay integration -------------------------------------------------

def test_crash_replay_exactly_once(tmp_path):
    """Kill -9 the ingest process after WAL acks; restart-replay must land
    every acknowledged event exactly once (CI-sized run of the same cycle
    ingest_bench ships)."""
    from predictionio_tpu.tools.ingest_bench import run_crash_cycle

    rep = run_crash_cycle(str(tmp_path / "crash"), min_acked=48, timeout_s=90.0)
    assert rep["acked"] >= 48
    assert rep["lost"] == 0
    assert rep["duplicated"] == 0
    assert rep["second_replay_records"] == 0
    assert rep["second_replay_delta"] == 0
    assert rep["exactly_once"] is True


def test_crash_replay_exactly_once_partitioned(tmp_path):
    """Kill -9 the ingest process mid-group-commit at P=4: every
    acknowledged event must recover exactly once IN ITS OWN partition --
    per-partition replay counts, zero cross-partition duplication (the
    routing audit), and an idempotent second restart in every partition."""
    from predictionio_tpu.tools.ingest_bench import run_crash_cycle

    rep = run_crash_cycle(
        str(tmp_path / "crash"), min_acked=48, timeout_s=90.0, partitions=4
    )
    assert rep["partitions"] == 4
    assert rep["acked"] >= 48
    assert rep["lost"] == 0
    assert rep["duplicated"] == 0
    assert rep["misrouted"] == 0
    assert len(rep["replayed_per_partition"]) == 4
    assert rep["second_replay_records"] == 0
    assert rep["second_replay_delta"] == 0
    assert rep["exactly_once"] is True


@pytest.mark.slow
def test_ingest_partition_sweep(tmp_path):
    """The --wal-partitions 1,2,4 sweep harness (bench.py's
    ingest_partitioned_eps secondary): every arm stores the full load and
    the report carries eps + scaling per partition count."""
    from predictionio_tpu.tools.ingest_bench import run_sweep

    rep = run_sweep(
        partitions=(1, 2, 4),
        clients=8,
        events_per_client=10,
        crash_partitions=None,
        workdir=str(tmp_path / "sweep"),
    )
    for p in ("1", "2", "4"):
        arm = rep["partitions"][p]
        assert arm["stored"] == 8 * 10
        assert arm["failures"] == 0
        assert arm["eps"] > 0
        assert arm["scaling_vs_first"] is not None
    assert isinstance(rep["monotonic"], bool)


@pytest.mark.slow
def test_ingest_bench_ab(tmp_path):
    """Full A/B harness (bench.py's ingest_eps secondary): group commit must
    beat durable per-request commits; the crash cycle must be exactly-once."""
    from predictionio_tpu.tools.ingest_bench import run_ab

    rep = run_ab(
        clients=16,
        events_per_client=20,
        crash_events=100,
        workdir=str(tmp_path / "bench"),
    )
    assert rep["sync"]["stored"] == 16 * 20
    assert rep["wal"]["stored"] == 16 * 20
    assert rep["sync"]["failures"] == 0 and rep["wal"]["failures"] == 0
    assert rep["speedup"] is not None and rep["speedup"] > 1.0
    assert rep["crash_cycle"]["exactly_once"] is True
