"""Multi-process serving tier: shm ring primitives, the frontend worker's
HTTP loop (keep-alive, pipelining, parse errors), the scorer bridge's
failure modes (SIGKILL respawn, graceful drain, ring-full 429
backpressure), the cross-process metrics aggregation, and byte-identity
of multi-process vs single-process responses through a real engine."""

import json
import os
import signal
import socket
import tempfile
import threading
import time
import urllib.error
import urllib.request

import pytest

from predictionio_tpu.serving import shmring
from predictionio_tpu.serving.procserver import FrontendConfig, ScorerBridge
from predictionio_tpu.utils.http import (
    HTTPParseError,
    RequestParser,
    Response,
    Router,
    instrumented_router,
)


# -- ring primitives ----------------------------------------------------------

class TestMessageRing:
    def _ring(self, tmp_path, slots=4, slot_bytes=256):
        return shmring.RingFile.create(
            str(tmp_path / "t.ring"), slots, slot_bytes, generation=1
        )

    def test_roundtrip_and_fifo_order(self, tmp_path):
        ring = self._ring(tmp_path)
        ring.requests.push({"i": 1}, b"a")
        ring.requests.push({"i": 2}, b"bb")
        assert ring.requests.pending() == 2
        assert ring.requests.pop() == ({"i": 1}, b"a")
        assert ring.requests.pop() == ({"i": 2}, b"bb")
        assert ring.requests.pop() is None

    def test_full_ring_raises_and_recovers(self, tmp_path):
        ring = self._ring(tmp_path, slots=2)
        ring.requests.push({"i": 1})
        ring.requests.push({"i": 2})
        with pytest.raises(shmring.RingFull):
            ring.requests.push({"i": 3})
        assert ring.requests.pop()[0] == {"i": 1}
        ring.requests.push({"i": 3})  # slot freed -> accepted again

    def test_wraparound_past_slot_count(self, tmp_path):
        ring = self._ring(tmp_path, slots=3)
        for i in range(20):  # > 6 wraps
            ring.requests.push({"i": i}, bytes([i]))
            assert ring.requests.pop() == ({"i": i}, bytes([i]))

    def test_oversize_message_spills_and_unlinks(self, tmp_path):
        ring = self._ring(tmp_path, slot_bytes=128)
        big = os.urandom(4096)
        ring.completions.push({"i": 7, "k": "v"}, big)
        spills = [p for p in os.listdir(tmp_path) if p.endswith(".spill")]
        assert len(spills) == 1
        meta, body = ring.completions.pop()
        assert meta == {"i": 7, "k": "v"} and body == big
        assert not [p for p in os.listdir(tmp_path) if p.endswith(".spill")]

    def test_attach_shares_state_and_rejects_garbage(self, tmp_path):
        ring = self._ring(tmp_path)
        ring.requests.push({"i": 9}, b"x")
        other = shmring.RingFile.attach(str(tmp_path / "t.ring"))
        assert other.requests.pop() == ({"i": 9}, b"x")
        assert ring.requests.pending() == 0  # tail advanced in both views
        junk = tmp_path / "junk.ring"
        junk.write_bytes(b"\x00" * 8192)
        with pytest.raises(ValueError):
            shmring.RingFile.attach(str(junk))

    def test_stats_seqlock_roundtrip(self, tmp_path):
        ring = self._ring(tmp_path)
        assert ring.read_stats() is None  # never written
        ring.write_stats({"counters": [["a", [], 1.0]]})
        assert ring.read_stats() == {"counters": [["a", [], 1.0]]}
        ring.write_stats({"counters": [["a", [], 2.0]]})
        assert ring.read_stats()["counters"][0][2] == 2.0

    def test_wakeup_signal_wait_drain(self, tmp_path):
        wake = shmring.Wakeup.create(str(tmp_path), "w")
        try:
            assert wake.wait(0.01) is False
            wake.signal()
            assert wake.wait(1.0) is True
            # drained: a second wait times out instead of re-firing
            assert wake.wait(0.01) is False
        finally:
            wake.close()


# -- incremental HTTP parser --------------------------------------------------

class TestRequestParser:
    REQ = (
        b"POST /queries.json HTTP/1.1\r\nHost: x\r\n"
        b"Content-Type: application/json\r\nContent-Length: 7\r\n\r\n"
        b'{"a":1}'
    )

    def test_single_request(self):
        p = RequestParser()
        p.feed(self.REQ)
        req = p.next_request()
        assert (req.method, req.target) == ("POST", "/queries.json")
        assert req.body == b'{"a":1}' and req.keep_alive is True
        assert p.next_request() is None

    def test_byte_at_a_time_delivery(self):
        p = RequestParser()
        for i in range(len(self.REQ) - 1):
            p.feed(self.REQ[i:i + 1])
            if i < len(self.REQ) - 2:
                assert p.next_request() is None
        p.feed(self.REQ[-1:])
        assert p.next_request().body == b'{"a":1}'

    def test_pipelined_requests_come_out_in_order(self):
        p = RequestParser()
        p.feed(self.REQ + self.REQ.replace(b'{"a":1}', b'{"b":2}'))
        assert p.next_request().body == b'{"a":1}'
        assert p.next_request().body == b'{"b":2}'
        assert p.next_request() is None

    def test_connection_close_and_http10(self):
        p = RequestParser()
        p.feed(
            b"GET / HTTP/1.1\r\nConnection: close\r\n\r\n"
            b"GET / HTTP/1.0\r\n\r\n"
            b"GET / HTTP/1.0\r\nConnection: keep-alive\r\n\r\n"
        )
        assert p.next_request().keep_alive is False
        assert p.next_request().keep_alive is False  # 1.0 default
        assert p.next_request().keep_alive is True

    @pytest.mark.parametrize(
        "raw,status",
        [
            (b"NONSENSE\r\n\r\n", 400),
            (b"GET / HTTP/2.0\r\n\r\n", 505),
            (b"GET / HTTP/1.1\r\nContent-Length: x\r\n\r\n", 400),
            (b"GET / HTTP/1.1\r\nContent-Length: -5\r\n\r\n", 400),
            (b"GET / HTTP/1.1\r\nTransfer-Encoding: chunked\r\n\r\n", 501),
            (b"GET / HTTP/1.1\r\nContent-Length: 99999999999\r\n\r\n", 413),
        ],
    )
    def test_malformed_requests_carry_status(self, raw, status):
        p = RequestParser()
        p.feed(raw)
        with pytest.raises(HTTPParseError) as exc:
            p.next_request()
        assert exc.value.status == status

    def test_oversized_header_block_rejected_incrementally(self):
        p = RequestParser()
        p.feed(b"GET / HTTP/1.1\r\n" + b"X-A: " + b"y" * 70000)
        with pytest.raises(HTTPParseError) as exc:
            p.next_request()
        assert exc.value.status == 431


# -- scorer-bridge harness ----------------------------------------------------

def _bridge(router, workers=1, **cfg):
    config = FrontendConfig(
        workers=workers, stats_flush_s=0.02,
        **{k: v for k, v in cfg.items()},
    )
    return ScorerBridge(router, "127.0.0.1", 0, config)


def _post(port, obj, timeout=20, path="/queries.json", headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read(), dict(resp.headers)
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read(), dict(exc.headers)


class TestScorerBridge:
    def test_echo_roundtrip_and_keepalive(self):
        """One connection, several requests: the frontend's keep-alive
        loop reuses the socket (one accept), bodies round-trip through
        the ring, and responses carry the scorer's status/headers."""
        router = Router()
        router.add(
            "POST", "/queries.json",
            lambda r: Response(200, {"echo": r.json(), "q": r.query}),
        )
        bridge = _bridge(router).start()
        try:
            import http.client

            conn = http.client.HTTPConnection(
                "127.0.0.1", bridge.port, timeout=10
            )
            for k in range(4):
                conn.request(
                    "POST", f"/queries.json?k={k}",
                    json.dumps({"n": k}),
                    {"Content-Type": "application/json"},
                )
                resp = conn.getresponse()
                body = json.loads(resp.read())
                assert resp.status == 200
                assert body == {"echo": {"n": k}, "q": {"k": str(k)}}
            conn.close()

            def accepted() -> float:
                return sum(
                    v for snap in bridge.metric_snapshots()
                    for name, _k, v in snap.get("counters", [])
                    if name == "pio_frontend_connections_total"
                )

            deadline = time.monotonic() + 5
            while accepted() < 1 and time.monotonic() < deadline:
                time.sleep(0.05)  # stats publish on the worker's flush tick
            assert accepted() == 1  # keep-alive: one accept, four requests
        finally:
            bridge.stop()

    def test_parse_error_answered_at_frontend(self):
        bridge = _bridge(Router()).start()
        try:
            sock = socket.create_connection(
                ("127.0.0.1", bridge.port), timeout=10
            )
            sock.sendall(b"BOGUS\r\n\r\n")
            data = sock.recv(65536)
            assert b"400" in data.split(b"\r\n", 1)[0]
            assert b"malformed request line" in data
            sock.close()
        finally:
            bridge.stop()

    def test_oversize_request_and_response_spill(self):
        """Messages larger than a ring slot spill to one-off files and
        round-trip intact in both directions."""
        blob = os.urandom(90_000)
        router = Router()
        router.add(
            "POST", "/queries.json",
            lambda r: Response(
                200, r.body, content_type="application/octet-stream"
            ),
        )
        bridge = _bridge(router, slot_bytes=4096).start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{bridge.port}/queries.json",
                data=blob, method="POST",
            )
            with urllib.request.urlopen(req, timeout=20) as resp:
                assert resp.read() == blob
        finally:
            bridge.stop()

    def test_backpressure_429_parity_with_ingest_contract(self):
        """A wedged scorer fills the request ring; overflow answers 429
        with Retry-After -- the ingest pipeline's bounded-queue contract
        at the serving tier -- and service resumes once unwedged."""
        gate = threading.Event()
        router = Router()

        def handler(r):
            gate.wait(20)
            return Response(200, {"ok": True})

        router.add("POST", "/queries.json", handler)
        bridge = _bridge(
            router, ring_slots=4, max_inflight=2
        ).start()
        try:
            results = []
            lock = threading.Lock()

            def worker():
                out = _post(bridge.port, {"x": 1}, timeout=30)
                with lock:
                    results.append(out)

            threads = [
                threading.Thread(target=worker) for _ in range(12)
            ]
            for t in threads:
                t.start()
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                with lock:
                    if any(status == 429 for status, _, _ in results):
                        break
                time.sleep(0.05)
            gate.set()
            for t in threads:
                t.join(timeout=30)
            statuses = [status for status, _, _ in results]
            assert statuses.count(200) >= 2  # admitted work completed
            rejected = [
                (body, headers)
                for status, body, headers in results if status == 429
            ]
            assert rejected, f"no 429s under a wedged scorer: {statuses}"
            body, headers = rejected[0]
            assert json.loads(body) == {
                "message": "serving queue full, retry later"
            }
            assert headers.get("Retry-After") == "1"
        finally:
            gate.set()
            bridge.stop()

    def test_sigkill_frontend_respawns_under_load(self):
        """SIGKILL one of two frontends mid-traffic: the supervisor
        respawns it (fresh generation), no request AFTER the kill fails,
        and the respawn is visible in the scorer's gauges."""
        router, registry = instrumented_router(tracing=False)
        router.add("POST", "/queries.json", lambda r: Response(200, {"ok": 1}))
        config = FrontendConfig(workers=2, stats_flush_s=0.02)
        bridge = ScorerBridge(
            router, "127.0.0.1", 0, config, registry=registry
        ).start()
        try:
            for _ in range(8):
                assert _post(bridge.port, {"x": 1})[0] == 200
            victim = bridge._workers[0].proc
            os.kill(victim.pid, signal.SIGKILL)
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                with bridge._lock:
                    gen = bridge._workers[0].generation
                if gen > 1 and bridge._workers[0].ring.state == shmring.STATE_READY:
                    break
                time.sleep(0.05)
            else:
                pytest.fail("killed frontend was not respawned")
            # post-kill traffic must succeed (new connections route to
            # live listeners; the respawned worker rejoins the group)
            for _ in range(12):
                status, body, _ = _post(bridge.port, {"x": 2}, timeout=20)
                assert status == 200, body
            assert "pio_frontend_respawns_total 1" in registry.exposition()
        finally:
            bridge.stop()

    def test_graceful_drain_answers_inflight(self):
        """stop() while requests are mid-scorer: every in-flight request
        is answered (zero dropped), then the workers exit."""
        release = threading.Event()
        router = Router()

        def handler(r):
            release.wait(10)
            return Response(200, {"done": True})

        router.add("POST", "/queries.json", handler)
        bridge = _bridge(router, workers=2).start()
        results = [None] * 6
        try:
            def worker(k):
                results[k] = _post(bridge.port, {"k": k}, timeout=30)

            threads = [
                threading.Thread(target=worker, args=(k,)) for k in range(6)
            ]
            for t in threads:
                t.start()
            time.sleep(0.5)  # all six are parked inside the scorer

            stopper = threading.Thread(target=bridge.stop)
            stopper.start()
            time.sleep(0.3)
            release.set()
            stopper.join(timeout=30)
            assert not stopper.is_alive()
            for t in threads:
                t.join(timeout=10)
            assert all(r is not None and r[0] == 200 for r in results), results
        finally:
            release.set()
            bridge.stop()  # idempotent

    def test_metrics_aggregate_across_workers(self):
        """The scorer's /metrics exposes per-worker counters merged from
        every frontend's published snapshot, alongside the scorer's own
        series -- one aggregated view of the whole process tier, via the
        same ``extra_snapshots`` hook the query service wires."""
        cell: list = []
        router, registry = instrumented_router(
            tracing=False,
            extra_snapshots=lambda: (
                cell[0].metric_snapshots() if cell else []
            ),
        )
        router.add("POST", "/queries.json", lambda r: Response(200, {"ok": 1}))
        config = FrontendConfig(workers=2, stats_flush_s=0.01)
        bridge = ScorerBridge(
            router, "127.0.0.1", 0, config, registry=registry
        ).start()
        cell.append(bridge)
        try:
            n = 10
            for k in range(n):
                assert _post(bridge.port, {"k": k})[0] == 200

            def forwarded(text: str) -> float:
                return sum(
                    float(line.rsplit(" ", 1)[1])
                    for line in text.splitlines()
                    if line.startswith("pio_frontend_requests_total")
                )

            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{bridge.port}/metrics", timeout=10
                ) as resp:
                    text = resp.read().decode()
                # the scrape itself rides a worker too: >= n forwarded
                if forwarded(text) >= n:
                    break
                time.sleep(0.1)
            assert forwarded(text) >= n
            assert "pio_frontend_workers 2" in text
            assert "pio_http_requests_total" in text  # scorer's own series
        finally:
            bridge.stop()


# -- byte-identity through a real engine --------------------------------------

class TestMultiprocQueryServer:
    def test_responses_byte_identical_and_plugins_survive(
        self, storage_env, tmp_path
    ):
        """The multi-process server answers byte-for-byte what the
        single-process server answers (same scorer router produces every
        body), the info page advertises the process tier, /metrics
        aggregates, and plugin output blockers still reject."""
        from predictionio_tpu.workflow.create_server import (
            EngineServerPlugin,
            ServerRejection,
            create_multiproc_query_server,
            create_query_server,
        )
        from predictionio_tpu.workflow.microbatch import BatchConfig
        from test_microbatch import _train_fake_engine

        variant = _train_fake_engine(
            storage_env, tmp_path, app="ProcServeApp"
        )

        class Blocker(EngineServerPlugin):
            def output_blocker(self, query, prediction):
                if isinstance(query, dict) and query.get("blocked"):
                    raise ServerRejection("blocked by plugin")

        batching = BatchConfig(window_ms=20, max_batch_size=8)
        thread, sp_service = create_query_server(
            variant, host="127.0.0.1", port=0,
            batching=batching, plugins=[Blocker()],
        )
        thread.start()
        handle, mp_service = create_multiproc_query_server(
            variant, host="127.0.0.1", port=0, frontend=2,
            batching=batching, plugins=[Blocker()],
        )
        handle.start()
        # the dispatcher-pool model, same engine: async (the default
        # above) vs sync responses must be byte-identical too -- the
        # dispatcherless dispatch may not change one byte
        sync_handle, sync_service = create_multiproc_query_server(
            variant, host="127.0.0.1", port=0,
            frontend=FrontendConfig(
                workers=2, dispatch="sync", stats_flush_s=0.02
            ),
            batching=batching, plugins=[Blocker()],
        )
        sync_handle.start()
        try:
            queries = [{"user": f"u{k % 4}", "num": 3} for k in range(8)]
            bodies = {}
            for label, port in (
                ("sp", thread.port), ("mp", handle.port),
                ("mp_sync", sync_handle.port),
            ):
                results = [None] * len(queries)

                def worker(k, port=port, out=results):
                    out[k] = _post(port, queries[k])

                threads = [
                    threading.Thread(target=worker, args=(k,))
                    for k in range(len(queries))
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                assert all(r[0] == 200 for r in results), results
                bodies[label] = [r[1] for r in results]
            assert bodies["mp"] == bodies["sp"]
            assert bodies["mp_sync"] == bodies["sp"]

            # plugin rejection parity through the ring
            status, body, _ = _post(handle.port, {"blocked": True})
            assert status == 403 and b"blocked by plugin" in body

            with urllib.request.urlopen(
                f"http://127.0.0.1:{handle.port}/", timeout=10
            ) as resp:
                info = json.load(resp)
            assert info["frontend"]["workers"] == 2

            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{handle.port}/metrics", timeout=10
                ) as resp:
                    text = resp.read().decode()
                if "pio_frontend_requests_total" in text:
                    break
                time.sleep(0.1)
            assert "pio_frontend_requests_total" in text
            assert "pio_frontend_workers 2" in text
            assert "pio_serving_batch_size_count" in text
        finally:
            thread.stop()
            sp_service.close()
            handle.stop()
            mp_service.close()
            sync_handle.stop()
            sync_service.close()


# -- async fast path: dispatcherless dispatch ---------------------------------

def _serve_multiproc(storage_env, tmp_path, app, dispatch="async",
                     workers=2, window_ms=30, **kw):
    """A trained fake engine behind the multi-process tier; returns
    (handle, service, url)."""
    from predictionio_tpu.serving.procserver import FrontendConfig
    from predictionio_tpu.workflow.create_server import (
        create_multiproc_query_server,
    )
    from predictionio_tpu.workflow.microbatch import BatchConfig
    from test_microbatch import _train_fake_engine

    variant = _train_fake_engine(storage_env, tmp_path, app=app)
    handle, service = create_multiproc_query_server(
        variant, host="127.0.0.1", port=0,
        frontend=FrontendConfig(
            workers=workers, dispatch=dispatch, stats_flush_s=0.02
        ),
        batching=BatchConfig(window_ms=window_ms, max_batch_size=8),
        **kw,
    )
    handle.start()
    return handle, service, f"http://127.0.0.1:{handle.port}"


def _gauge(url: str, name: str) -> float | None:
    with urllib.request.urlopen(f"{url}/metrics", timeout=10) as resp:
        for line in resp.read().decode().splitlines():
            if line.startswith(name + " "):
                return float(line.rsplit(" ", 1)[1])
    return None


class TestAsyncFastPath:
    def test_wakeup_gauges_and_zero_dispatch_threads(
        self, storage_env, tmp_path
    ):
        """The 5-to-2 claim as a measured gauge, not a code comment:
        under async dispatch, sequential queries cost <= 2 cross-thread
        wakeups each (consumer eventfd wake + completion signal) and
        ZERO dispatcher threads serve the query path. The sync arm on
        the same engine shows the dispatcher chain: a thread pool on the
        query path and > 2 wakeups/request."""
        handle, service, url = _serve_multiproc(
            storage_env, tmp_path, app="AsyncGaugeApp", dispatch="async",
            window_ms=2,
        )
        try:
            for k in range(24):
                status, body, _ = _post(
                    handle.port, {"user": f"u{k % 4}", "num": 3}
                )
                assert status == 200, body
            assert _gauge(url, "pio_scorer_dispatch_threads") == 0.0
            wpr = _gauge(url, "pio_scorer_wakeups_per_request")
            assert wpr is not None and 0.0 < wpr <= 2.0, wpr
            stats = handle.bridge.wakeup_stats()
            assert stats["handoffs"] == 0  # nothing pooled on the query path
            assert stats["query_requests"] >= 24
        finally:
            handle.stop()
            service.close()

        handle, service, url = _serve_multiproc(
            storage_env, tmp_path, app="SyncGaugeApp", dispatch="sync",
            window_ms=2,
        )
        try:
            for k in range(24):
                status, body, _ = _post(handle.port,
                                        {"user": f"u{k % 4}", "num": 3})
                assert status == 200, body
            assert _gauge(url, "pio_scorer_dispatch_threads") == 16.0
            wpr = _gauge(url, "pio_scorer_wakeups_per_request")
            assert wpr is not None and wpr > 2.0, wpr
            assert handle.bridge.wakeup_stats()["handoffs"] >= 24
        finally:
            handle.stop()
            service.close()

    def test_graceful_drain_answers_inflight_async(
        self, storage_env, tmp_path
    ):
        """stop() while queries are parked inside the micro-batcher on
        the async path: every in-flight request is answered through the
        flusher callback (zero dropped), then the tier exits."""
        handle, service, url = _serve_multiproc(
            storage_env, tmp_path, app="AsyncDrainApp", window_ms=5,
        )
        gate = threading.Event()
        orig = service._batcher._execute

        def gated(queries):
            gate.wait(15)
            return orig(queries)

        service._batcher._execute = gated
        results = [None] * 6
        try:
            def worker(k):
                results[k] = _post(
                    handle.port, {"user": f"u{k % 4}", "num": 3}, timeout=30
                )

            threads = [
                threading.Thread(target=worker, args=(k,)) for k in range(6)
            ]
            for t in threads:
                t.start()
            time.sleep(0.6)  # all six parked in the batcher
            stopper = threading.Thread(target=handle.stop)
            stopper.start()
            time.sleep(0.3)
            gate.set()
            stopper.join(timeout=40)
            assert not stopper.is_alive()
            for t in threads:
                t.join(timeout=10)
            assert all(r is not None and r[0] == 200 for r in results), results
        finally:
            gate.set()
            handle.stop()
            service.close()

    def test_wedged_batch_answers_503_and_recovers(
        self, storage_env, tmp_path
    ):
        """The sync path's bounded future wait, preserved off-thread: a
        batch execute that blows the wait budget gets a 503 "batched
        predict timed out" from the watchdog (releasing its admission
        permit) instead of holding the permit until the wedge clears --
        and when it does clear, the late future callback is a no-op (the
        claim gate) and fresh traffic serves normally."""
        handle, service, url = _serve_multiproc(
            storage_env, tmp_path, app="AsyncWedgeApp", window_ms=2,
        )
        gate = threading.Event()
        orig = service._batcher._execute

        def gated(queries):
            gate.wait(30)
            return orig(queries)

        service._batcher._execute = gated
        service._async_timeout_s = 1.0
        try:
            t0 = time.monotonic()
            status, body, _ = _post(
                handle.port, {"user": "u1", "num": 3}, timeout=30
            )
            assert status == 503, (status, body)
            assert b"batched predict timed out" in body
            # the watchdog sweeps at 1 Hz: answered in ~2-3 s, not the
            # frontend's 35 s forward timeout
            assert time.monotonic() - t0 < 10.0
            gate.set()  # the wedge clears; the late callback must no-op
            service._batcher._execute = orig
            service._async_timeout_s = 32.0
            for k in range(4):
                status, body, _ = _post(
                    handle.port, {"user": f"u{k % 4}", "num": 3}, timeout=20
                )
                assert status == 200, body
        finally:
            gate.set()
            handle.stop()
            service.close()

    def test_sigkill_frontend_mid_callback(self, storage_env, tmp_path):
        """SIGKILL a frontend while its queries are mid-batcher: the
        stale-generation completions are dropped in the callback (dead
        check under cmp_lock), the flusher never stalls, the supervisor
        respawns the worker, and post-kill traffic is answered."""
        handle, service, url = _serve_multiproc(
            storage_env, tmp_path, app="AsyncKillApp", window_ms=5,
        )
        gate = threading.Event()
        orig = service._batcher._execute

        def gated(queries):
            gate.wait(15)
            return orig(queries)

        service._batcher._execute = gated
        results = [None] * 6
        try:
            def worker(k):
                try:
                    results[k] = _post(
                        handle.port, {"user": f"u{k % 4}", "num": 3},
                        timeout=20,
                    )
                except Exception as exc:  # victim's clients die with it
                    results[k] = exc

            threads = [
                threading.Thread(target=worker, args=(k,)) for k in range(6)
            ]
            for t in threads:
                t.start()
            time.sleep(0.6)  # in-flight inside the gated batcher
            victims = [w.proc for w in handle.bridge._workers]
            os.kill(victims[0].pid, signal.SIGKILL)
            time.sleep(0.2)
            gate.set()  # callbacks now fire; victim's completions drop
            for t in threads:
                t.join(timeout=30)
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                with handle.bridge._lock:
                    gen = handle.bridge._workers[0].generation
                if gen > 1 and (
                    handle.bridge._workers[0].ring.state
                    == shmring.STATE_READY
                ):
                    break
                time.sleep(0.05)
            else:
                pytest.fail("killed frontend was not respawned")
            # the flusher survived the dead-worker completions: fresh
            # traffic keeps being answered through the async path
            for k in range(8):
                status, body, _ = _post(
                    handle.port, {"user": f"u{k % 4}", "num": 3}, timeout=20
                )
                assert status == 200, body
        finally:
            gate.set()
            handle.stop()
            service.close()


# -- completion-ring-full retry queue -----------------------------------------

class TestCompletionRetry:
    def _bridge(self, tmp_path, slots=2):
        """A ScorerBridge skeleton with one fake worker and a live retry
        thread -- no processes, no sockets; the unit under test is the
        non-blocking delivery path."""
        from predictionio_tpu.serving.procserver import (
            FrontendConfig,
            ScorerBridge,
            _Worker,
        )

        bridge = ScorerBridge(
            Router(), "127.0.0.1", 0, FrontendConfig(workers=1)
        )
        ring = shmring.RingFile.create(
            str(tmp_path / "w.ring"), slots, 256, generation=1
        )
        bridge._wakes[0] = (
            shmring.Wakeup.create(str(tmp_path), "req-0"),
            shmring.Wakeup.create(str(tmp_path), "cmp-0"),
            shmring.Wakeup.create(str(tmp_path), "stop-0"),
        )
        w = _Worker(0, 1, ring, proc=None)
        bridge._workers.append(w)
        bridge._retry.start()
        return bridge, w

    def _teardown(self, bridge, w):
        bridge._retry.stop()
        w.ring.close()
        for wake in bridge._wakes[0]:
            wake.close()

    def test_full_ring_parks_then_delivers_without_blocking(self, tmp_path):
        bridge, w = self._bridge(tmp_path)
        try:
            w.ring.completions.push({"i": 1}, b"a")
            w.ring.completions.push({"i": 2}, b"b")  # ring now full
            t0 = time.perf_counter()
            bridge._deliver(w, {"i": 9}, b"parked", is_query=True)
            # the delivering (flusher-shaped) thread returned immediately
            assert time.perf_counter() - t0 < 0.5
            assert bridge._retry.depth() == 1
            assert w.ring.completions.pop()[0] == {"i": 1}  # worker drains
            deadline = time.monotonic() + 5
            while bridge._retry.depth() and time.monotonic() < deadline:
                time.sleep(0.005)
            assert bridge._retry.depth() == 0
            assert w.ring.completions.pop()[0] == {"i": 2}
            meta, body = w.ring.completions.pop()
            assert meta == {"i": 9} and body == b"parked"
            assert bridge.wakeup_stats()["completion_signals"] == 1
        finally:
            self._teardown(bridge, w)

    def test_deadline_expiry_drops_and_releases_permit(self, tmp_path):
        bridge, w = self._bridge(tmp_path)
        try:
            bridge._retry._DEADLINE_S = 0.05
            w.ring.completions.push({"i": 1}, b"a")
            w.ring.completions.push({"i": 2}, b"b")
            bridge._inflight.acquire()
            before = bridge._inflight._value
            bridge._deliver(w, {"i": 9}, b"doomed", is_query=True)
            deadline = time.monotonic() + 5
            # the retry thread takes the entry off its queue and then gives the
            # permit back: wait for both, not for the queue alone
            while ((bridge._retry.depth() or bridge._inflight._value == before)
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            assert bridge._retry.depth() == 0
            # dropped, not delivered -- and the admission permit came back
            assert w.ring.completions.pending() == 2
            assert bridge._inflight._value == before + 1
        finally:
            self._teardown(bridge, w)

    def test_dead_worker_entry_dropped(self, tmp_path):
        bridge, w = self._bridge(tmp_path)
        try:
            w.ring.completions.push({"i": 1}, b"a")
            w.ring.completions.push({"i": 2}, b"b")
            bridge._deliver(w, {"i": 9}, b"x", is_query=True)
            assert bridge._retry.depth() == 1
            with w.cmp_lock:
                w.dead = True  # the supervisor's respawn protocol
            deadline = time.monotonic() + 5
            while bridge._retry.depth() and time.monotonic() < deadline:
                time.sleep(0.005)
            assert bridge._retry.depth() == 0
            assert w.ring.completions.pending() == 2  # never delivered
        finally:
            self._teardown(bridge, w)


# -- worker-count sweep (real multi-core rounds; slow-marked) -----------------

@pytest.mark.slow
class TestWorkerSweep:
    def test_pinned_sweep_sync_vs_async(self):
        """The ROADMAP's re-measure-on-real-cores prerequisite as a
        runnable artifact: 1/2/4/8 pinned workers, sync vs async
        dispatch, wakeup gauges recorded per arm. On the 2-core box this
        mostly exercises plumbing (workers share one core); on real
        multi-core hardware it is the scaling measurement."""
        from predictionio_tpu.tools.serving_bench import run_multiproc_ab

        rep = run_multiproc_ab(
            "recommendation",
            concurrency=8,
            requests=240,
            workers=(1, 2, 4, 8),
            users=50,
            items=2_000,
            events=4_000,
            dispatch=("sync", "async"),
            pin_cpus=True,
        )
        assert rep["responses_identical"], rep
        for n in (1, 2, 4, 8):
            assert f"workers_{n}_sync" in rep
            assert f"workers_{n}_async" in rep
        async2 = rep["workers_2_async"]
        assert async2["dispatch_threads"] == 0
        assert async2["wakeups_per_request"] <= 2.0
        assert rep["workers_2_sync"]["dispatch_threads"] > 0
