"""End-to-end tracing + telemetry (``predictionio_tpu/obs``): span model,
traceparent propagation, batch fan-out, WAL-replay trace survival,
ring-buffer tail keep, the tracing-off zero-allocation contract, the
slow-op log, structured logging, the training telemetry journal, and the
``pio top`` view."""

import json
import logging
import threading
import time
import urllib.error
import urllib.request

import pytest

from predictionio_tpu.obs import trace as trace_mod
from predictionio_tpu.obs.trace import (
    NULL_SPAN,
    Tracer,
    current_context,
    format_traceparent,
    parse_traceparent,
)


def _pc() -> float:
    return time.perf_counter()


class TestTraceparent:
    def test_roundtrip(self):
        trace_id, span_id = "ab" * 16, "cd" * 8
        header = format_traceparent(trace_id, span_id)
        assert parse_traceparent(header) == (trace_id, span_id, True)

    def test_sampled_flag_parsed(self):
        trace_id, span_id = "ab" * 16, "cd" * 8
        assert parse_traceparent(f"00-{trace_id}-{span_id}-00")[2] is False
        assert parse_traceparent(f"00-{trace_id}-{span_id}-03")[2] is True

    @pytest.mark.parametrize(
        "bad",
        [
            None,
            "",
            "garbage",
            "00-short-abcd-01",
            "00-" + "0" * 32 + "-" + "cd" * 8 + "-01",  # all-zero trace id
            "00-" + "ab" * 16 + "-" + "0" * 16 + "-01",  # all-zero span id
            "zz-" + "ab" * 16 + "-" + "cd" * 8 + "-01",
        ],
    )
    def test_malformed_headers_start_fresh(self, bad):
        assert parse_traceparent(bad) is None


class TestTracerCore:
    def test_nesting_links_parent_and_trace(self):
        tracer = Tracer()
        with tracer.span("outer") as outer:
            with tracer.span("inner") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == outer.span_id
                assert current_context() == (inner.trace_id, inner.span_id)
            assert current_context() == (outer.trace_id, outer.span_id)
        assert current_context() is None
        snap = tracer.snapshot()
        assert len(snap["recent"]) == 1
        tr = snap["recent"][0]
        assert tr["op"] == "outer"
        assert sorted(s["op"] for s in tr["spans"]) == ["inner", "outer"]

    def test_remote_root_joins_callers_trace(self):
        tracer = Tracer()
        trace_id, parent = "ab" * 16, "cd" * 8
        with tracer.start_remote("op", format_traceparent(trace_id, parent)) as sp:
            assert sp.trace_id == trace_id
            assert sp.parent_id == parent
        assert tracer.snapshot()["recent"][0]["traceId"] == trace_id

    def test_disabled_tracer_allocates_no_spans(self):
        tracer = Tracer(enabled=False)
        # the off path hands out ONE shared singleton -- no per-call objects
        assert tracer.span("a") is NULL_SPAN
        assert tracer.span("b") is tracer.span("c")
        with tracer.span("a") as sp:
            sp.set_attr("k", "v")  # all no-ops
            assert current_context() is None
        assert tracer.record_span("t" * 32, "x", 0.0, 1.0) is None
        snap = tracer.snapshot()
        assert snap["enabled"] is False
        assert snap["recent"] == [] and snap["slowest"] == []

    def test_exception_marks_span_and_trace_error(self):
        tracer = Tracer()
        with pytest.raises(ValueError):
            with tracer.span("boom"):
                raise ValueError("nope")
        tr = tracer.snapshot()["recent"][0]
        assert tr["status"] == "error"
        assert "ValueError" in tr["spans"][0]["attrs"]["error"]

    def test_record_span_into_live_trace_and_shared_ids(self):
        tracer = Tracer()
        done = threading.Event()
        captured = {}

        def request_thread():
            with tracer.span("root") as sp:
                captured["ctx"] = (sp.trace_id, sp.span_id)
                done.wait(5)

        t = threading.Thread(target=request_thread)
        t.start()
        while "ctx" not in captured:
            time.sleep(0.001)
        trace_id, parent = captured["ctx"]
        t0 = _pc()
        shared = tracer.record_span(
            trace_id, "batch.execute", t0, t0 + 0.001, parent_id=parent
        )
        done.set()
        t.join()
        tr = tracer.snapshot()["recent"][0]
        by_op = {s["op"]: s for s in tr["spans"]}
        assert by_op["batch.execute"]["spanId"] == shared
        assert by_op["batch.execute"]["parentId"] == parent

    def test_record_span_without_live_trace_is_standalone(self):
        tracer = Tracer()
        t0 = _pc()
        tracer.record_span("ef" * 16, "wal.replay", t0, t0 + 0.002)
        tr = tracer.snapshot()["recent"][0]
        assert tr["traceId"] == "ef" * 16
        assert tr["spans"][0]["op"] == "wal.replay"

    def test_ring_eviction_keeps_slow_and_error_traces(self):
        tracer = Tracer(recent_cap=8, keep_cap=4)
        # one slow trace (explicit long duration) and one error trace...
        t0 = _pc()
        tracer.record_span("aa" * 16, "slow_op", t0 - 5.0, t0)
        tracer.record_span("bb" * 16, "bad_op", t0, t0 + 0.001, status="error")
        # ...washed out of the recent ring by fast traffic
        for k in range(50):
            with tracer.span(f"fast{k % 3}"):
                pass
        snap = tracer.snapshot(limit=100)
        recent_ids = {t["traceId"] for t in snap["recent"]}
        assert "aa" * 16 not in recent_ids  # evicted from the plain ring
        assert "aa" * 16 in {t["traceId"] for t in snap["slowest"]}
        assert "bb" * 16 in {t["traceId"] for t in snap["errors"]}

    def test_snapshot_filters_by_op_and_duration(self):
        tracer = Tracer()
        t0 = _pc()
        tracer.record_span("aa" * 16, "alpha", t0 - 1.0, t0)
        tracer.record_span("bb" * 16, "beta", t0, t0 + 0.0001)
        snap = tracer.snapshot(op="alpha")
        assert [t["op"] for t in snap["recent"]] == ["alpha"]
        snap = tracer.snapshot(min_ms=500.0)
        assert [t["op"] for t in snap["recent"]] == ["alpha"]

    def test_live_trace_cap_bounds_memory(self):
        tracer = Tracer(live_cap=4)
        spans = [tracer.span(f"leak{k}").__enter__() for k in range(10)]
        assert len(tracer._live) <= 4
        for sp in reversed(spans):
            sp.__exit__(None, None, None)


class TestSampling:
    def test_sampled_out_root_suppresses_children_and_retains_nothing(self):
        from predictionio_tpu.obs.trace import NULL_SPAN, current_context

        tracer = Tracer(sample=0.0)
        with tracer.span("root") as root:
            assert root.trace_id is None
            # nested spans must NOT open their own root traces
            child = tracer.span("child")
            assert child is NULL_SPAN
            with child:
                assert current_context() is None
        # suppression ends with the root: a direct Tracer at sample=1.0
        # semantics resumes for the next root on this thread
        assert tracer.snapshot()["recent"] == []
        full = Tracer(sample=1.0)
        with full.span("after") as sp:
            assert sp.trace_id is not None
        assert [t["op"] for t in full.snapshot()["recent"]] == ["after"]

    def test_remote_traceparent_bypasses_sampling(self):
        tracer = Tracer(sample=0.0)
        trace_id = "ab" * 16
        with tracer.start_remote(
            "op", format_traceparent(trace_id, "cd" * 8)
        ) as sp:
            assert sp.trace_id == trace_id
        assert tracer.snapshot()["recent"][0]["traceId"] == trace_id
        # headerless start_remote samples like span()
        with tracer.start_remote("op2", None) as sp:
            assert sp.trace_id is None

    def test_sampled_out_request_emits_no_traceparent(self):
        from predictionio_tpu.utils.http import (
            Request,
            Response,
            instrumented_router,
        )

        router, _ = instrumented_router(tracing=True, trace_sample=0.0)
        router.add("GET", "/ok", lambda r: Response(200, {"ok": True}))
        router.add("GET", "/err", lambda r: Response(418, {"message": "t"}))
        resp = router.dispatch(Request("GET", "/ok", {}, {}, b"", {}))
        assert resp.status == 200
        assert "traceparent" not in resp.headers
        resp = router.dispatch(Request("GET", "/err", {}, {}, b"", {}))
        assert "traceId" not in resp.body
        assert router.tracer.snapshot()["recent"] == []
        # a traceparent'd request through the same router still traces
        trace_id = "ef" * 16
        resp = router.dispatch(Request(
            "GET", "/ok", {},
            {"traceparent": format_traceparent(trace_id, "aa" * 8)},
            b"", {},
        ))
        assert parse_traceparent(resp.headers["traceparent"])[0] == trace_id

    def test_unsampled_traceparent_subject_to_local_sampling(self):
        # flags-00 (the caller explicitly decided NOT to sample) must not
        # force tracing: a mesh proxy stamping every request with ``-00``
        # would otherwise defeat head-sampling entirely
        trace_id = "ab" * 16
        header = f"00-{trace_id}-{'cd' * 8}-00"
        tracer = Tracer(sample=0.0)
        with tracer.start_remote("op", header) as sp:
            assert sp.trace_id is None
        assert tracer.snapshot()["recent"] == []
        # sampled in locally: joins the caller's ids so logs correlate
        tracer = Tracer(sample=1.0)
        with tracer.start_remote("op", header) as sp:
            assert sp.trace_id == trace_id

    def test_sample_default_env(self, monkeypatch):
        from predictionio_tpu.obs.trace import (
            DEFAULT_SAMPLE,
            tracing_sample_default,
        )

        monkeypatch.delenv("PIO_TRACE_SAMPLE", raising=False)
        assert tracing_sample_default() == DEFAULT_SAMPLE
        monkeypatch.setenv("PIO_TRACE_SAMPLE", "1")
        assert tracing_sample_default() == 1.0
        monkeypatch.setenv("PIO_TRACE_SAMPLE", "2.5")
        assert tracing_sample_default() == 1.0  # clamped
        monkeypatch.setenv("PIO_TRACE_SAMPLE", "nope")
        assert tracing_sample_default() == DEFAULT_SAMPLE

    def test_sampled_ingest_commit_still_fans_out_to_traced_requests(self):
        """A sampled-out ingest.commit root must not stop traced requests
        from receiving their shared WAL spans (fresh shared ids)."""
        from predictionio_tpu.data.ingest import IngestPipeline

        class _FakeWal:
            def __init__(self):
                self.seq = 0

            def append(self, payload):
                self.seq += 1
                return self.seq

            def sync(self):
                pass

            def checkpoint(self, seqno):
                pass

        class _FakeEvents:
            def insert_batch(self, items, on_duplicate="error"):
                return [it[0].event_id for it in items]

        tracer = Tracer(sample=0.0)  # every commit root sampled out
        pipe = IngestPipeline(
            wal=_FakeWal(), l_events=_FakeEvents, tracer=tracer,
            group_commit_ms=1.0,
        ).start()
        try:
            from predictionio_tpu.data.event import Event

            futures = []
            # the root stays open until the acks resolve -- the server
            # handler's shape (it parks on the future inside its span)
            with tracer.start_remote(
                "POST /events.json", format_traceparent("9a" * 16, "bb" * 8)
            ):
                for k in range(2):
                    futures.append(pipe.submit(
                        Event(event="e", entity_type="u", entity_id=str(k)),
                        app_id=1, channel_id=None,
                    ))
                for f in futures:
                    f.result(10)
        finally:
            pipe.stop()
        snap = tracer.snapshot(limit=100)
        trace = next(
            t for t in snap["recent"] if t["traceId"] == "9a" * 16
        )
        ops = [s["op"] for s in trace["spans"]]
        assert "wal.append" in ops and "wal.fsync" in ops
        # no stray standalone traces from the suppressed commit root
        assert not any(
            t["op"] == "ingest.commit" for t in snap["recent"]
        )


class TestSlowOpLog:
    def test_slow_trace_logs_exactly_one_record(self, caplog):
        tracer = Tracer()
        tracer.set_slow_threshold("slow.op", 0.01)
        with caplog.at_level(logging.WARNING, logger="pio.trace"):
            with tracer.span("slow.op"):
                with tracer.span("child"):
                    time.sleep(0.03)
        records = [r for r in caplog.records if "slow op" in r.message]
        assert len(records) == 1
        assert "slow.op" in records[0].message
        assert "child" in records[0].message  # span summary included

    def test_slow_injected_handler_produces_exactly_one_record(self, caplog):
        """The satellite regression shape: a handler made artificially
        slow, a threshold below its latency, exactly one log record."""
        from predictionio_tpu.utils.http import (
            Request,
            Response,
            instrumented_router,
        )

        router, _ = instrumented_router(tracing=True, trace_sample=1.0)
        router.tracer.set_slow_threshold("GET /slow", 0.01)

        def slow(request: Request) -> Response:
            time.sleep(0.03)
            return Response(200, {"ok": True})

        router.add("GET", "/slow", slow)
        router.add("GET", "/fast", lambda r: Response(200, {"ok": True}))
        with caplog.at_level(logging.WARNING, logger="pio.trace"):
            resp = router.dispatch(Request("GET", "/slow", {}, {}, b"", {}))
            assert resp.status == 200
            router.dispatch(Request("GET", "/fast", {}, {}, b"", {}))
        records = [r for r in caplog.records if "slow op" in r.message]
        assert len(records) == 1
        assert "GET /slow" in records[0].message

    def test_fast_trace_logs_nothing(self, caplog):
        tracer = Tracer()
        tracer.set_slow_threshold("slow.op", 10.0)
        with caplog.at_level(logging.WARNING, logger="pio.trace"):
            with tracer.span("slow.op"):
                pass
            with tracer.span("unthresholded"):
                time.sleep(0.02)
        assert not [r for r in caplog.records if "slow op" in r.message]


class TestMicroBatcherFanout:
    def test_batch_spans_shared_across_coalesced_requests(self):
        from predictionio_tpu.workflow.microbatch import BatchConfig, MicroBatcher

        tracer = Tracer()
        gate = threading.Event()

        def execute(queries):
            return [q * 10 for q in queries]

        mb = MicroBatcher(
            execute,
            BatchConfig(window_ms=150.0, idle_ms=100.0, max_batch_size=2),
            tracer=tracer,
        )
        results = {}

        def client(k):
            with tracer.span(f"request{k}") as sp:
                results[k] = (sp.trace_id, mb.submit(k).result(10))
                gate.wait(5)

        threads = [threading.Thread(target=client, args=(k,)) for k in (1, 2)]
        for t in threads:
            t.start()
        # both submitted within the window -> one batch (size flush at 2)
        time.sleep(0.05)
        gate.set()
        for t in threads:
            t.join()
        mb.close()
        assert results[1][1] == 10 and results[2][1] == 20
        snap = tracer.snapshot()
        traces = {t["traceId"]: t for t in snap["recent"]}
        t1, t2 = traces[results[1][0]], traces[results[2][0]]
        for tr in (t1, t2):
            ops = [s["op"] for s in tr["spans"]]
            assert "batch.queue_wait" in ops
            assert "batch.assemble" in ops
            assert "batch.execute" in ops

        def span_id(tr, op):
            return next(s["spanId"] for s in tr["spans"] if s["op"] == op)

        # the batch-level spans are SHARED: same span id in both traces
        assert span_id(t1, "batch.execute") == span_id(t2, "batch.execute")
        assert span_id(t1, "batch.assemble") == span_id(t2, "batch.assemble")
        # but each request's queue wait is its own span
        assert span_id(t1, "batch.queue_wait") != span_id(t2, "batch.queue_wait")
        exec_attrs = next(
            s["attrs"] for s in t1["spans"] if s["op"] == "batch.execute"
        )
        assert exec_attrs["batch_size"] == 2

    def test_untraced_submit_records_nothing(self):
        from predictionio_tpu.workflow.microbatch import BatchConfig, MicroBatcher

        tracer = Tracer(enabled=False)
        mb = MicroBatcher(
            lambda qs: list(qs), BatchConfig(window_ms=5.0), tracer=tracer
        )
        assert mb.submit(7).result(10) == 7
        mb.close()
        assert tracer.snapshot()["recent"] == []

    def _run_coalesced_pair(self, tracer, execute, catch=False):
        """Two concurrent traced submits forming one size-2 batch; returns
        {k: trace_id} after the batcher fully drains."""
        from predictionio_tpu.workflow.microbatch import BatchConfig, MicroBatcher

        mb = MicroBatcher(
            execute,
            BatchConfig(window_ms=150.0, idle_ms=100.0, max_batch_size=2),
            tracer=tracer,
        )
        gate = threading.Event()
        trace_ids = {}

        def client(k):
            with tracer.span(f"request{k}") as sp:
                trace_ids[k] = sp.trace_id
                try:
                    mb.submit(k).result(10)
                except Exception:
                    if not catch:
                        raise
                gate.wait(5)

        threads = [threading.Thread(target=client, args=(k,)) for k in (1, 2)]
        for t in threads:
            t.start()
        time.sleep(0.05)
        gate.set()
        for t in threads:
            t.join()
        mb.close()
        return trace_ids

    def test_batch_level_spans_bridge_once_per_batch(self):
        # one device batch must count ONCE in pio_span_duration_seconds,
        # not once per coalesced request; queue_wait never bridges (its
        # native pio_serving_batch_queue_wait_seconds histogram covers it)
        bridged = []
        tracer = Tracer(on_spans=bridged.extend)
        self._run_coalesced_pair(tracer, lambda qs: [q * 10 for q in qs])
        ops = [r.op for r in bridged]
        assert ops.count("batch.execute") == 1
        assert ops.count("batch.assemble") == 1
        assert ops.count("batch.queue_wait") == 0
        assert ops.count("request1") == 1 and ops.count("request2") == 1

    def test_wholesale_execute_failure_still_fans_out(self):
        # an execute() that fails wholesale produces exactly the traces
        # the error tail-keep exists for: they must still carry their
        # queue-wait and batch spans, with execute marked as the failure
        tracer = Tracer()

        def boom(queries):
            raise RuntimeError("device fell over")

        trace_ids = self._run_coalesced_pair(tracer, boom, catch=True)
        snap = tracer.snapshot()
        traces = {t["traceId"]: t for t in snap["recent"]}
        t1, t2 = traces[trace_ids[1]], traces[trace_ids[2]]
        for tr in (t1, t2):
            assert tr["status"] == "error"
            by_op = {s["op"]: s for s in tr["spans"]}
            assert "batch.queue_wait" in by_op
            assert by_op["batch.assemble"]["status"] == "error"
            assert by_op["batch.execute"]["status"] == "error"
        # still one SHARED batch-level span across the failed batch
        assert (
            next(s for s in t1["spans"] if s["op"] == "batch.execute")["spanId"]
            == next(s for s in t2["spans"] if s["op"] == "batch.execute")["spanId"]
        )
        # and both land in the eviction-proof error keep
        err_ids = {t["traceId"] for t in snap["errors"]}
        assert trace_ids[1] in err_ids and trace_ids[2] in err_ids


def _get_json(url: str):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return json.loads(resp.read())


class TestHttpTracing:
    @pytest.fixture()
    def server(self):
        from predictionio_tpu.utils.http import (
            Request,
            Response,
            ServiceThread,
            instrumented_router,
            make_server,
        )

        router, registry = instrumented_router(tracing=True, trace_sample=1.0)

        def ok(request: Request) -> Response:
            return Response(200, {"ok": True})

        def teapot(request: Request) -> Response:
            return Response(418, {"message": "teapot"})

        def boom(request: Request) -> Response:
            raise RuntimeError("handler exploded")

        router.add("GET", "/ok", ok)
        router.add("GET", "/teapot", teapot)
        router.add("GET", "/boom", boom)
        svc = ServiceThread(
            make_server(router, "127.0.0.1", 0, "pio-test")
        ).start()
        yield f"http://127.0.0.1:{svc.port}", router
        svc.stop()

    def test_traceparent_roundtrip_and_traces_json(self, server):
        url, router = server
        trace_id = "12" * 16
        req = urllib.request.Request(
            f"{url}/ok",
            headers={"traceparent": format_traceparent(trace_id, "ab" * 8)},
        )
        with urllib.request.urlopen(req, timeout=10) as resp:
            out = resp.headers.get("traceparent")
        assert out is not None and parse_traceparent(out)[0] == trace_id
        snap = _get_json(f"{url}/traces.json?op=/ok")
        assert snap["enabled"] is True
        assert snap["recent"][0]["traceId"] == trace_id
        assert snap["recent"][0]["op"] == "GET /ok"

    def test_error_responses_carry_trace_id(self, server):
        url, _ = server
        try:
            urllib.request.urlopen(f"{url}/teapot", timeout=10)
            assert False, "expected 418"
        except urllib.error.HTTPError as exc:
            body = json.loads(exc.read())
            assert exc.code == 418
        assert len(body["traceId"]) == 32
        # handler exceptions 500 with the trace id too
        try:
            urllib.request.urlopen(f"{url}/boom", timeout=10)
            assert False, "expected 500"
        except urllib.error.HTTPError as exc:
            body = json.loads(exc.read())
            assert exc.code == 500
        assert body["message"] == "internal server error"
        assert len(body["traceId"]) == 32
        snap = _get_json(f"{url}/traces.json?op=/boom")
        assert snap["errors"][0]["status"] == "error"

    def test_observability_endpoints_not_traced(self, server):
        url, _ = server
        for _ in range(3):
            _get_json(f"{url}/traces.json")
            urllib.request.urlopen(f"{url}/metrics", timeout=10).read()
        snap = _get_json(f"{url}/traces.json?limit=100")
        ops = {t["op"] for t in snap["recent"]}
        assert not any("/metrics" in op or "/traces.json" in op for op in ops)

    def test_build_info_gauge_on_metrics(self, server):
        url, _ = server
        text = urllib.request.urlopen(f"{url}/metrics", timeout=10).read().decode()
        line = next(l for l in text.splitlines() if l.startswith("pio_build_info{"))
        assert 'version="' in line
        assert "jax_version=" in line
        assert "backend=" in line
        assert "legacy_jax=" not in line
        assert line.rstrip().endswith(" 1")

    def test_span_histogram_bridge(self, server):
        url, _ = server
        urllib.request.urlopen(f"{url}/ok", timeout=10).read()
        text = urllib.request.urlopen(f"{url}/metrics", timeout=10).read().decode()
        assert 'pio_span_duration_seconds_count{op="GET /ok"}' in text

    def test_unmatched_route_span_op_is_bounded(self, server):
        # scanner traffic (distinct 404 paths) must not mint one
        # pio_span_duration_seconds{op} series per raw path
        url, _ = server
        for path in ("/wp-admin", "/secret-probe-1", "/secret-probe-2"):
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                urllib.request.urlopen(f"{url}{path}", timeout=10)
            assert exc_info.value.code == 404
        snap = _get_json(f"{url}/traces.json?limit=100")
        ops_404 = [
            t["op"] for t in snap["recent"] if "probe" in t["op"] or "<unmatched>" in t["op"]
        ]
        assert ops_404 and all(op == "GET <unmatched>" for op in ops_404)
        text = urllib.request.urlopen(f"{url}/metrics", timeout=10).read().decode()
        assert 'pio_span_duration_seconds_count{op="GET <unmatched>"}' in text
        assert "probe" not in text and "wp-admin" not in text
        # a 405 re-ops to the matched route pattern, still bounded
        req = urllib.request.Request(f"{url}/ok", method="DELETE")
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            urllib.request.urlopen(req, timeout=10)
        assert exc_info.value.code == 405
        snap = _get_json(f"{url}/traces.json?op=DELETE")
        assert snap["recent"][0]["op"] == "DELETE /ok"

    def test_tracing_disabled_router_emits_no_traceparent(self):
        from predictionio_tpu.utils.http import (
            Request,
            Response,
            ServiceThread,
            instrumented_router,
            make_server,
        )

        router, _ = instrumented_router(tracing=False)
        router.add("GET", "/ok", lambda r: Response(200, {"ok": True}))
        svc = ServiceThread(
            make_server(router, "127.0.0.1", 0, "pio-test")
        ).start()
        try:
            url = f"http://127.0.0.1:{svc.port}"
            with urllib.request.urlopen(f"{url}/ok", timeout=10) as resp:
                assert resp.headers.get("traceparent") is None
            assert _get_json(f"{url}/traces.json")["enabled"] is False
        finally:
            svc.stop()


class TestIngestTracing:
    @pytest.fixture()
    def server(self, storage_env, tmp_path):
        from predictionio_tpu.data.api.eventserver import create_event_server
        from predictionio_tpu.data.ingest import IngestConfig
        from predictionio_tpu.data.storage.base import AccessKey, App

        app_id = storage_env.get_meta_data_apps().insert(App(name="ObsApp"))
        key = storage_env.get_meta_data_access_keys().insert(
            AccessKey(key="", app_id=app_id)
        )
        storage_env.get_l_events().init_channel(app_id)
        svc = create_event_server(
            host="127.0.0.1",
            port=0,
            ingest_config=IngestConfig(
                mode="wal", wal_dir=str(tmp_path / "wal"), group_commit_ms=2.0
            ),
            tracing=True,
            trace_sample=1.0,
        ).start()
        yield f"http://127.0.0.1:{svc.port}", key
        svc.stop()

    EVENT = {
        "event": "rate", "entityType": "user", "entityId": "u1",
        "targetEntityType": "item", "targetEntityId": "i1",
        "properties": {"rating": 4},
    }

    def test_ingest_trace_covers_wal_append_and_group_fsync(self, server):
        url, key = server
        trace_id = "fe" * 16
        req = urllib.request.Request(
            f"{url}/events.json?accessKey={key}",
            data=json.dumps(self.EVENT).encode(),
            headers={
                "Content-Type": "application/json",
                "traceparent": format_traceparent(trace_id, "aa" * 8),
            },
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=15) as resp:
            assert resp.status == 201
            assert parse_traceparent(resp.headers["traceparent"])[0] == trace_id
        # the fan-out runs just after the ack: poll for the WAL spans
        tr = self._await_trace_span(url, trace_id, "wal.fsync")
        ops = [s["op"] for s in tr["spans"]]
        for expected in (
            "ingest.parse", "ingest.queue_wait", "wal.append", "wal.fsync",
        ):
            assert expected in ops, f"{expected} missing from {ops}"
        # the writer's own group-commit trace exists too, with storage flush
        deadline = time.time() + 5
        while time.time() < deadline:
            snap = _get_json(f"{url}/traces.json?op=ingest.commit&limit=100")
            if snap["recent"]:
                break
            time.sleep(0.05)
        commit = snap["recent"][0]
        commit_ops = [s["op"] for s in commit["spans"]]
        assert "wal.append" in commit_ops and "wal.fsync" in commit_ops
        assert "storage.flush" in commit_ops

    def test_batch_requests_share_commit_spans(self, server):
        url, key = server
        trace_id = "dd" * 16
        req = urllib.request.Request(
            f"{url}/batch/events.json?accessKey={key}",
            data=json.dumps([self.EVENT, dict(self.EVENT, entityId="u2")]).encode(),
            headers={
                "Content-Type": "application/json",
                "traceparent": format_traceparent(trace_id, "bb" * 8),
            },
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=15) as resp:
            statuses = [r["status"] for r in json.loads(resp.read())]
        assert statuses == [201, 201]
        tr = self._await_trace_span(url, trace_id, "wal.fsync")
        fsyncs = [s for s in tr["spans"] if s["op"] == "wal.fsync"]
        # both events rode ONE group commit: a single shared fsync span
        assert len({s["spanId"] for s in fsyncs}) == 1

    @staticmethod
    def _await_trace_span(url: str, trace_id: str, op: str):
        """The post-ack fan-out lands WAL spans microseconds after the
        HTTP response: poll the trace until ``op`` appears."""
        deadline = time.time() + 5
        tr = None
        while time.time() < deadline:
            snap = _get_json(f"{url}/traces.json?limit=100")
            tr = next(
                (t for t in snap["recent"] if t["traceId"] == trace_id), None
            )
            if tr is not None and any(s["op"] == op for s in tr["spans"]):
                return tr
            time.sleep(0.05)
        assert tr is not None, f"trace {trace_id} never appeared"
        return tr

    def test_wal_spans_bridge_once_per_commit(self):
        """One physical WAL append/fsync must count ONCE in the span
        histogram per group commit, not once per coalesced request --
        the same once-per-batch invariant the micro-batcher holds."""
        from predictionio_tpu.data.event import Event
        from predictionio_tpu.data.ingest import IngestPipeline

        class _FakeWal:
            seq = 0

            def append(self, payload):
                self.seq += 1
                return self.seq

            def sync(self):
                pass

            def checkpoint(self, seqno):
                pass

        class _FakeEvents:
            def insert_batch(self, items, on_duplicate="error"):
                return [it[0].event_id for it in items]

        bridged = []
        tracer = Tracer(sample=1.0, on_spans=bridged.extend)
        pipe = IngestPipeline(
            wal=_FakeWal(), l_events=_FakeEvents, tracer=tracer,
            group_commit_ms=100.0,
        ).start()
        t1, t2 = "8a" * 16, "8b" * 16
        try:
            # two requests, two TRACES, one group commit; both roots stay
            # open until the acks resolve (the server handler's shape)
            with tracer.start_remote(
                "POST /events.json", format_traceparent(t1, "aa" * 8)
            ):
                f1 = pipe.submit(
                    Event(event="e", entity_type="u", entity_id="1"),
                    app_id=1, channel_id=None,
                )
                with tracer.start_remote(
                    "POST /events.json", format_traceparent(t2, "aa" * 8)
                ):
                    f2 = pipe.submit(
                        Event(event="e", entity_type="u", entity_id="2"),
                        app_id=1, channel_id=None,
                    )
                    f1.result(10)
                    f2.result(10)
        finally:
            pipe.stop()
        ops = [r.op for r in bridged]
        assert ops.count("wal.fsync") == 1
        assert ops.count("wal.append") == 1
        # queue-wait is genuinely per request
        assert ops.count("ingest.queue_wait") == 2
        # both request traces still carry the SHARED WAL span ids
        traces = {t["traceId"]: t for t in tracer.snapshot(limit=100)["recent"]}
        fsync_ids = {
            s["spanId"]
            for tid in (t1, t2)
            for s in traces[tid]["spans"] if s["op"] == "wal.fsync"
        }
        assert len(fsync_ids) == 1
        commit = next(
            t for t in traces.values() if t["op"] == "ingest.commit"
        )
        assert fsync_ids == {
            s["spanId"] for s in commit["spans"] if s["op"] == "wal.fsync"
        }

    def test_wal_metrics_exposed(self, server):
        url, key = server
        req = urllib.request.Request(
            f"{url}/events.json?accessKey={key}",
            data=json.dumps(self.EVENT).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        urllib.request.urlopen(req, timeout=15).read()
        text = urllib.request.urlopen(f"{url}/metrics", timeout=10).read().decode()
        assert "pio_wal_appends_total" in text
        assert "pio_wal_fsyncs_total" in text


class TestWalReplayTraceSurvival:
    def test_replay_attaches_span_to_original_trace(self, storage_env, tmp_path):
        """A trace acked into the WAL before a crash gains a ``wal.replay``
        span when the un-checkpointed tail is replayed at next startup --
        the trace survives the durability boundary."""
        from predictionio_tpu.data.event import Event
        from predictionio_tpu.data.ingest import (
            _wal_payload,
            replay_wal_into_storage,
        )
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.data.wal import WriteAheadLog

        app_id = storage_env.get_meta_data_apps().insert(App(name="ReplayApp"))
        storage_env.get_l_events().init_channel(app_id)
        trace_id = "ce" * 16
        wal_dir = str(tmp_path / "wal")
        wal = WriteAheadLog(wal_dir)
        event = Event(
            event="rate", entity_type="user", entity_id="u1",
            target_entity_type="item", target_entity_id="i1",
        ).with_id()
        # acked into the WAL, never flushed to storage (the crash window)
        wal.append(_wal_payload(event, app_id, None, trace_id))
        wal.sync()
        wal.close()

        # "restart": fresh WAL handle + fresh tracer (new process state)
        tracer = Tracer()
        wal2 = WriteAheadLog(wal_dir)
        replayed = replay_wal_into_storage(wal2, tracer=tracer)
        wal2.close()
        assert replayed == 1
        assert storage_env.get_l_events().get(event.event_id, app_id) is not None
        tr = next(
            t for t in tracer.snapshot()["recent"] if t["traceId"] == trace_id
        )
        assert tr["spans"][0]["op"] == "wal.replay"
        # idempotent second replay: checkpoint advanced, no more records
        wal3 = WriteAheadLog(wal_dir)
        assert replay_wal_into_storage(wal3, tracer=tracer) == 0
        wal3.close()

    def test_payload_without_trace_id_still_parses(self):
        """Pre-observability WAL records (no "t" key) replay unchanged."""
        from predictionio_tpu.data.event import Event
        from predictionio_tpu.data.ingest import _wal_parse

        payload = json.dumps(
            {
                "e": Event(
                    event="rate", entity_type="user", entity_id="u1"
                ).with_id().to_json_obj(),
                "a": 7,
                "c": None,
            },
            separators=(",", ":"),
        ).encode()
        event, app_id, channel_id, trace_id = _wal_parse(payload)
        assert app_id == 7 and channel_id is None and trace_id is None


class TestStructuredLogs:
    def test_json_formatter_includes_trace_ids_under_span(self):
        from predictionio_tpu.obs.logs import JsonLogFormatter

        fmt = JsonLogFormatter()
        tracer = Tracer()
        record = logging.LogRecord(
            "pio.test", logging.INFO, __file__, 1, "hello %s", ("world",), None
        )
        with tracer.span("op") as sp:
            line = fmt.format(record)
        obj = json.loads(line)
        assert obj["message"] == "hello world"
        assert obj["trace_id"] == sp.trace_id
        assert obj["span_id"] == sp.span_id
        assert obj["level"] == "INFO" and obj["logger"] == "pio.test"

    def test_json_formatter_omits_ids_without_span(self):
        from predictionio_tpu.obs.logs import JsonLogFormatter

        record = logging.LogRecord(
            "pio.test", logging.WARNING, __file__, 1, "plain", (), None
        )
        obj = json.loads(JsonLogFormatter().format(record))
        assert "trace_id" not in obj

    def test_configure_logging_json_and_reset(self):
        from predictionio_tpu.obs.logs import JsonLogFormatter, configure_logging

        root = logging.getLogger()
        prior_handlers, prior_level = root.handlers[:], root.level
        try:
            configure_logging("json")
            assert len(root.handlers) == 1
            assert isinstance(root.handlers[0].formatter, JsonLogFormatter)
            with pytest.raises(ValueError):
                configure_logging("xml")
        finally:
            root.handlers[:] = prior_handlers
            root.setLevel(prior_level)

    def test_cli_flag_registered_on_service_verbs(self):
        from predictionio_tpu.tools.cli import build_parser

        parser = build_parser()
        args = parser.parse_args(["eventserver", "--log-format", "json"])
        assert args.log_format == "json"
        args = parser.parse_args(["deploy", "--log-format", "json"])
        assert args.log_format == "json"
        args = parser.parse_args(["dashboard"])
        assert args.log_format == "text"


class TestTrainTelemetry:
    def test_journal_lines(self, tmp_path):
        from predictionio_tpu.obs.telemetry import TrainTelemetry

        path = str(tmp_path / "t.jsonl")
        with TrainTelemetry(
            path, edges=1000, modeled_bytes_per_iter=2e9, meta={"rank": 16}
        ) as tel:
            tel.record_step(0, 0.5, recompile_count=1)
            tel.record_step(1, 0.25, recompile_count=1)
        lines = [json.loads(l) for l in open(path)]
        assert lines[0]["event"] == "meta" and lines[0]["rank"] == 16
        assert lines[1]["edges_per_sec"] == 2000.0
        assert lines[1]["achieved_gbps"] == 4.0
        assert lines[2]["step"] == 1 and lines[2]["recompile_count"] == 1

    def test_als_fit_with_telemetry(self, tmp_path):
        import numpy as np

        from predictionio_tpu.obs.telemetry import TrainTelemetry
        from predictionio_tpu.parallel.als import (
            ALSConfig,
            als_fit,
            build_als_data,
            modeled_bytes_per_iteration,
            real_edges,
        )

        rng = np.random.default_rng(0)
        users = rng.integers(0, 40, 300)
        items = rng.integers(0, 25, 300)
        vals = rng.integers(1, 6, 300).astype(np.float32)
        config = ALSConfig(rank=4, iterations=3)
        data = build_als_data(users, items, vals, 40, 25, config)
        path = str(tmp_path / "als.jsonl")
        tel = TrainTelemetry(
            path,
            edges=real_edges(data),
            modeled_bytes_per_iter=modeled_bytes_per_iteration(data, 4, 4),
        )
        model = als_fit(data, config, telemetry=tel)
        tel.close()
        assert model.user_factors.shape == (40, 4)
        steps = [
            json.loads(l)
            for l in open(path)
            if json.loads(l).get("event") == "step"
        ]
        assert [s["step"] for s in steps] == [0, 1, 2]
        from predictionio_tpu.utils.metrics import global_registry

        for s in steps:
            assert s["edges_per_sec"] > 0
            assert "achieved_gbps" in s
            assert s["recompile_count"] >= 1
        # steady state: no recompile churn after the first step
        assert steps[1]["recompile_count"] == steps[2]["recompile_count"]
        # the journal reads the process's counter, not a private jax API
        assert steps[2]["recompile_count"] <= global_registry().counter_value(
            "pio_jit_compiles_total"
        )

    def test_the_profiled_train_journals_achieved_gbps(self, tmp_path):
        """``_build_telemetry`` always hands the journal the bytes model
        (``modeled_bytes_per_iteration``: padded slots through the gathered
        intermediate), so every step line carries ``achieved_gbps``."""
        import numpy as np

        from predictionio_tpu.models._als_common import _build_telemetry
        from predictionio_tpu.parallel.als import ALSConfig, build_als_data

        class Ctx:
            runtime_conf = {"pio.profile": str(tmp_path)}

        config = ALSConfig(rank=4)
        data = build_als_data(
            np.arange(12) % 5, np.arange(12) % 3, np.ones(12, np.float32), 5, 3, config
        )
        with _build_telemetry(Ctx(), data, config, None, "als") as tel:
            step = tel.record_step(0, 0.5)
        assert "achieved_gbps" in step  # rounded to 0.0 at this toy size
        assert step["edges_per_sec"] > 0

    def test_train_profile_cli_flag(self):
        from predictionio_tpu.tools.cli import build_parser

        parser = build_parser()
        args = parser.parse_args(["train", "--profile"])
        assert args.profile == "__default__"
        args = parser.parse_args(["train", "--profile", "/tmp/x"])
        assert args.profile == "/tmp/x"
        args = parser.parse_args(["train"])
        assert args.profile is None

    def test_run_train_profile_writes_xplane_and_journal(
        self, storage_env, tmp_path
    ):
        """``pio train --profile`` on the bundled recommendation template:
        a loadable jax.profiler trace (xplane) AND a per-step telemetry
        journal with edges/sec + achieved GB/s land in the profile dir."""
        import glob

        from predictionio_tpu.data import DataMap, Event
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.workflow.core_workflow import run_train
        from predictionio_tpu.workflow.json_extractor import load_engine_variant

        app_id = storage_env.get_meta_data_apps().insert(App(name="ProfApp"))
        le = storage_env.get_l_events()
        le.init_channel(app_id)
        le.batch_insert(
            [
                Event(
                    event="rate", entity_type="user", entity_id=f"u{k % 12}",
                    target_entity_type="item", target_entity_id=f"i{k % 9}",
                    properties=DataMap({"rating": float(1 + k % 5)}),
                )
                for k in range(80)
            ],
            app_id=app_id,
        )
        variant_path = tmp_path / "engine.json"
        variant_path.write_text(json.dumps({
            "id": "prof-test",
            "engineFactory":
                "predictionio_tpu.models.recommendation.engine.engine_factory",
            "datasource": {"params": {"appName": "ProfApp"}},
            "algorithms": [{
                "name": "als",
                "params": {
                    "rank": 4, "numIterations": 2, "checkpointInterval": 0,
                },
            }],
        }))
        variant = load_engine_variant(str(variant_path))
        profile_dir = str(tmp_path / "prof")
        variant.runtime_conf["pio.profile"] = profile_dir
        instance = run_train(variant)
        assert instance.status == "COMPLETED"
        xplane = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)
        assert xplane, "jax.profiler trace missing"
        journal = f"{profile_dir}/als-telemetry.jsonl"
        steps = [
            json.loads(l)
            for l in open(journal)
            if json.loads(l).get("event") == "step"
        ]
        assert len(steps) == 2
        assert all("edges_per_sec" in s and "achieved_gbps" in s for s in steps)


class TestCompileCounters:
    """``utils.platform.count_compiles``: jax's compile events as
    ``pio_jit_*`` counters of the global registry."""

    NAMES = (
        "pio_jit_trace_seconds_total", "pio_jit_lower_seconds_total",
        "pio_jit_compile_seconds_total", "pio_jit_compiles_total",
        "pio_jit_cache_hits_total", "pio_jit_cache_misses_total",
    )

    @staticmethod
    def _read():
        from predictionio_tpu.utils.metrics import global_registry

        return {
            name: global_registry().counter_value(name)
            for name in TestCompileCounters.NAMES
        }

    def test_one_compile_for_a_fresh_function_and_none_for_its_second_call(self):
        import jax
        import numpy as np

        from predictionio_tpu.utils.platform import count_compiles

        count_compiles()
        count_compiles()  # registering again must not count an event twice
        start = self._read()
        assert all(value is not None for value in start.values())  # there from 0

        @jax.jit
        def fresh(x):
            return x * 3.0 + 1.0

        x = np.arange(7, dtype=np.float32)  # numpy in: no helper program compiles
        fresh(x).block_until_ready()
        first = self._read()
        assert first["pio_jit_compiles_total"] == start["pio_jit_compiles_total"] + 1
        for name in self.NAMES[:3]:
            assert first[name] > start[name]
        fresh(x).block_until_ready()
        assert self._read() == first
        fresh(np.arange(9, dtype=np.float32)).block_until_ready()  # a new shape
        assert (
            self._read()["pio_jit_compiles_total"]
            == first["pio_jit_compiles_total"] + 1
        )

    def test_the_events_are_the_installed_jaxs(self):
        """The listener matches on event names; a jax that renames one would
        leave its counter at 0 without an error anywhere else."""
        from jax._src import dispatch

        from predictionio_tpu.utils import platform

        assert set(platform._DURATION_COUNTERS) == {
            dispatch.JAXPR_TRACE_EVENT,
            dispatch.JAXPR_TO_MLIR_MODULE_EVENT,
            dispatch.BACKEND_COMPILE_EVENT,
        }

    def test_cache_hits_and_misses_count_in_a_process_with_the_cache_on(self, tmp_path):
        """The suite runs with the persistent cache off; a child with its
        own cache directory compiles (a miss, written), then loads (a hit)."""
        import os
        import subprocess
        import sys

        code = (
            "import json, sys, numpy as np\n"
            "from predictionio_tpu.utils.platform import ensure_backend\n"
            "from predictionio_tpu.utils.metrics import global_registry\n"
            "ensure_backend()\n"
            "import jax\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)\n"
            "f = jax.jit(lambda x: (x @ x.T).sum() * 3.0)\n"
            "f(np.ones((64, 64), np.float32)).block_until_ready()\n"
            "print(json.dumps({n: global_registry().counter_value(n) for n in"
            " ('pio_jit_cache_hits_total', 'pio_jit_cache_misses_total',"
            " 'pio_jit_compiles_total')}))\n"
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="true",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
        runs = []
        for _ in range(2):
            proc = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                                  capture_output=True, timeout=300)
            assert proc.returncode == 0, proc.stderr[-2000:]
            runs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
        cold, warm = runs
        assert cold["pio_jit_cache_misses_total"] >= 1
        assert cold["pio_jit_cache_hits_total"] == 0
        assert warm["pio_jit_cache_hits_total"] >= 1
        assert warm["pio_jit_cache_misses_total"] == 0
        assert warm["pio_jit_compiles_total"] == cold["pio_jit_compiles_total"]

    def test_query_server_metrics_show_a_recompile(self, storage_env, tmp_path):
        """``/metrics`` of a deployed query server carries the counters, and a
        micro-batch of a size not seen before (more queries than one query
        block) raises ``pio_jit_compiles_total``."""
        from concurrent.futures import ThreadPoolExecutor

        from predictionio_tpu.data import DataMap, Event
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.workflow.core_workflow import run_train
        from predictionio_tpu.workflow.create_server import create_query_server
        from predictionio_tpu.workflow.json_extractor import load_engine_variant
        from predictionio_tpu.workflow.microbatch import BatchConfig

        app_id = storage_env.get_meta_data_apps().insert(App(name="JitApp"))
        le = storage_env.get_l_events()
        le.init_channel(app_id)
        le.batch_insert(
            [
                Event(
                    event="rate", entity_type="user", entity_id=f"u{k % 16}",
                    target_entity_type="item", target_entity_id=f"i{(k * 7) % 11}",
                    properties=DataMap({"rating": float(1 + k % 5)}),
                )
                for k in range(120)
            ],
            app_id=app_id,
        )
        variant_path = tmp_path / "engine.json"
        variant_path.write_text(json.dumps({
            "id": "jit-test",
            "engineFactory":
                "predictionio_tpu.models.recommendation.engine.engine_factory",
            "datasource": {"params": {"appName": "JitApp"}},
            "algorithms": [{
                "name": "als",
                "params": {
                    "rank": 4, "numIterations": 2, "checkpointInterval": 0,
                    "retrieval": {"mode": "mips"},
                },
            }],
        }))
        variant = load_engine_variant(str(variant_path))
        run_train(variant)
        thread, _ = create_query_server(
            variant, host="127.0.0.1", port=0,
            batching=BatchConfig(max_batch_size=64, window_ms=500.0, idle_ms=200.0),
        )
        thread.start()
        base = f"http://127.0.0.1:{thread.port}"

        def compiles() -> float:
            text = urllib.request.urlopen(f"{base}/metrics", timeout=10).read().decode()
            for name in self.NAMES:
                assert f"# TYPE {name} counter" in text
            line = next(l for l in text.splitlines()
                        if l.startswith("pio_jit_compiles_total "))
            return float(line.split()[1])

        def query(user: int) -> int:
            req = urllib.request.Request(
                f"{base}/queries.json",
                data=json.dumps({"user": f"u{user}", "num": 3}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=120) as resp:
                return resp.status

        try:
            assert query(0) == 200
            assert query(1) == 200
            steady = compiles()
            assert query(2) == 200
            assert compiles() == steady  # the same batch shape: nothing compiles
            with ThreadPoolExecutor(12) as pool:  # one micro-batch of 12 > 8 rows
                assert list(pool.map(query, range(12))) == [200] * 12
            assert compiles() > steady
        finally:
            thread.stop()


class TestCompileTimeline:
    """``utils.platform``'s listeners keep what JAX tells them: each trace,
    lowering and compile a span under the thread's active span, and a column
    of the program's row in ``compile_report()``."""

    CHILD = (
        "import json, numpy as np\n"
        "from predictionio_tpu.utils.platform import (\n"
        "    compile_report, device_report, ensure_backend)\n"
        "from predictionio_tpu.utils.metrics import global_registry\n"
        "from predictionio_tpu.obs.trace import global_tracer\n"
        "ensure_backend()\n"
        "import jax\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)\n"
        "def timeline_probe(x):\n"
        "    return (x @ x.T).sum() * 3.0\n"
        "with global_tracer().span('train.run'):\n"
        "    jax.jit(timeline_probe)(np.ones((64, 64), np.float32)).block_until_ready()\n"
        "print(json.dumps({\n"
        "    'rows': compile_report(), 'programs': device_report()['programs'],\n"
        "    'totals': {n: global_registry().counter_value(n) for n in (\n"
        "        'pio_jit_trace_seconds_total', 'pio_jit_lower_seconds_total',\n"
        "        'pio_jit_compile_seconds_total', 'pio_jit_compiles_total')},\n"
        "    'ops': [[s['op'], s.get('attrs', {})] for t in\n"
        "            global_tracer().snapshot()['recent'] for s in t['spans']]}))\n"
    )

    @staticmethod
    def _child(**env) -> dict:
        import os
        import subprocess
        import sys

        proc = subprocess.run(
            [sys.executable, "-c", TestCompileTimeline.CHILD], text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu", **env),
            capture_output=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    @staticmethod
    def _probe_row(out: dict) -> dict:
        (row,) = [r for r in out["rows"] if r["program"] == "jit(timeline_probe)"]
        return row

    @staticmethod
    def _fresh(name: str):
        import jax

        def fn(x):
            return x * 5.0 - 2.0

        fn.__name__ = fn.__qualname__ = name
        return jax.jit(fn)

    def test_a_compile_under_an_open_span_leaves_three_children(self):
        import numpy as np

        from predictionio_tpu.utils.platform import count_compiles

        count_compiles()
        tracer = Tracer()
        with tracer.span("query.predict") as root:
            self._fresh("under_a_span")(np.arange(5, dtype=np.float32)).block_until_ready()
        (trace,) = tracer.snapshot()["recent"]
        children = [s for s in trace["spans"] if s["op"].startswith("jit.")]
        assert [s["op"] for s in children] == ["jit.trace", "jit.lower", "jit.compile"]
        assert all(s["parentId"] == root.span_id for s in children)
        assert [s["attrs"]["program"] for s in children] == [
            "under_a_span", "jit(under_a_span)", "jit(under_a_span)"]
        assert children[2]["attrs"]["cache"] == "none"  # the suite's cache is off
        # laid end to end inside their parent, on its clock
        assert 0 <= children[0]["offsetMs"] <= children[1]["offsetMs"] <= children[2]["offsetMs"]
        assert children[2]["offsetMs"] + children[2]["durationMs"] <= trace["durationMs"] + 1.0

    def test_with_no_span_open_the_row_is_in_the_table_and_the_ring_gains_no_trace(self):
        import numpy as np

        from predictionio_tpu.obs.trace import global_tracer
        from predictionio_tpu.utils.platform import compile_report, count_compiles

        count_compiles()
        traces = len(global_tracer().snapshot(limit=1000)["recent"])
        fn = self._fresh("with_no_span")
        fn(np.arange(5, dtype=np.float32)).block_until_ready()
        assert len(global_tracer().snapshot(limit=1000)["recent"]) == traces
        (row,) = [r for r in compile_report() if r["program"] == "jit(with_no_span)"]
        assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["compile_s"] > 0
        assert row["cache"] == "none"
        assert row["end_s"] - row["start_s"] >= row["trace_s"] + row["lower_s"] + row["compile_s"]
        assert abs(row["end_s"] - time.time()) < 60.0  # epoch seconds, the spans' axis
        # a new shape is a new program: a second row, the first left as it was
        fn(np.arange(6, dtype=np.float32)).block_until_ready()
        again = [r for r in compile_report() if r["program"] == "jit(with_no_span)"]
        assert len(again) == 2 and again[0] == row

    def test_the_row_says_what_the_cache_said(self, tmp_path):
        """As ``TestCompileCounters``'s child: a cache of its own, the threshold
        at 0. Cold it compiles and writes (``miss``), warm it loads (``hit``)."""
        env = {"JAX_ENABLE_COMPILATION_CACHE": "true",
               "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
        cold, warm = self._child(**env), self._child(**env)
        assert self._probe_row(cold)["cache"] == "miss"
        assert self._probe_row(warm)["cache"] == "hit"
        assert ["jit.compile", {"program": "jit(timeline_probe)", "cache": "hit"}] in warm["ops"]
        longest = {p["program"]: p["cache"] for p in warm["programs"]["longest_compiles"]}
        assert longest["jit(timeline_probe)"] == "hit"

    def test_with_the_cache_off_the_totals_are_the_sum_of_the_rows(self):
        out = self._child(JAX_ENABLE_COMPILATION_CACHE="false")
        assert self._probe_row(out)["cache"] == "none"
        rows, totals = out["rows"], out["totals"]
        assert len(rows) == totals["pio_jit_compiles_total"] == out["programs"]["count"]
        # ``x @ x.T`` traces a helper inside the probe's trace: the totals count
        # its seconds twice, the row holds them apart
        assert self._probe_row(out)["nested_s"] > 0
        summed = sum(r[c] for r in rows for c in ("trace_s", "lower_s", "compile_s", "nested_s"))
        assert summed == pytest.approx(
            sum(v for n, v in totals.items() if n.endswith("seconds_total")), rel=1e-9)
        assert sum(r["compile_s"] for r in rows) == pytest.approx(
            totals["pio_jit_compile_seconds_total"], rel=1e-9)
        # the first span of the process (the snapshot's last: newest first),
        # and the compiles under train.run
        op, attrs = out["ops"][-1]
        assert op == "backend.init"
        assert attrs["platform"] == "cpu" and attrs["devices"] >= 1 and attrs["cache_dir"]
        assert ["jit.trace", {"program": "timeline_probe"}] in out["ops"]

    def test_tracing_off_leaves_the_totals_counting_and_records_nothing(self):
        out = self._child(PIO_TRACING="0", JAX_ENABLE_COMPILATION_CACHE="false")
        assert out["rows"] == [] and out["ops"] == []
        assert out["programs"] == {"count": 0, "trace_s": 0, "lower_s": 0, "compile_s": 0,
                                   "longest_compiles": []}
        assert out["totals"]["pio_jit_compiles_total"] >= 1
        assert out["totals"]["pio_jit_compile_seconds_total"] > 0

    def test_300_compiles_fill_the_table_and_leave_the_ring_alone(self):
        """A sequence cell with its reference makes hundreds of compile events
        with no span open; the readers of ``als.pack`` and ``seq.fit`` walk the
        ring of 128 traces after them."""
        from jax import monitoring
        from jax._src import dispatch

        from benchmarks.layer_metrics._program import span
        from predictionio_tpu.obs.trace import global_tracer
        from predictionio_tpu.utils import platform

        platform.count_compiles()
        with global_tracer().span("als.pack", attrs={"edges": 300}):
            pass
        for k in range(300):
            now = time.time()
            for event in (dispatch.JAXPR_TRACE_EVENT, dispatch.JAXPR_TO_MLIR_MODULE_EVENT,
                          dispatch.BACKEND_COMPILE_EVENT):
                monitoring.record_scalar(event, now, fun_name=f"jit(flood_{k})")
                monitoring.record_event_time_span(event, now, now + 0.001,
                                                  fun_name=f"jit(flood_{k})")
        assert span("als.pack")["attrs"] == {"edges": 300}
        rows = platform.compile_report()
        assert len(rows) == platform.PROGRAM_ROWS < 300
        assert rows[-1]["program"] == "jit(flood_299)" and rows[-1]["compile_s"] > 0
        assert rows[0]["program"] == f"jit(flood_{300 - platform.PROGRAM_ROWS})"

    def test_the_public_clock_is_the_spans_own(self):
        tracer = Tracer()
        pc = _pc()
        with tracer.span("clocked"):
            pass
        record = tracer._recent[-1][0]
        assert trace_mod.epoch_seconds(pc) == pytest.approx(record.start_s, abs=1e-3)
        assert trace_mod.epoch_seconds(pc) == pytest.approx(time.time(), abs=1.0)

    def test_a_thread_with_no_span_records_under_nothing(self):
        assert trace_mod.record_under_current("jit.compile", _pc(), _pc()) is False


class TestProgramSpans:
    def test_als_pack_carries_the_packers_counts(self):
        import numpy as np

        from predictionio_tpu.controller.base import Params
        from predictionio_tpu.models._als_common import prepare_als_data
        from predictionio_tpu.obs.trace import global_tracer

        rng = np.random.default_rng(3)
        users = rng.integers(0, 30, 900)
        items = rng.integers(0, 12, 900)
        vals = rng.integers(1, 6, 900).astype(np.float32)

        class Ctx:
            mesh = None

        data = prepare_als_data(
            Ctx(), Params({"maxEventsPerUser": 24, "buckets": 2}),
            users, items, vals, 30, 12, times=np.arange(900),
        )
        newest = global_tracer().snapshot(op="als.pack")["recent"][0]
        span = next(s for s in newest["spans"] if s["op"] == "als.pack")
        assert span["attrs"]["edges"] == 900
        for name, side in (("by_row", data.by_row), ("by_col", data.by_col)):
            counts = span["attrs"][name]
            assert counts["retained_edges"] == sum(int(b.mask.sum()) for b in side.blocks)
            assert counts["padded_slots"] == side.padded_slots
            assert counts["buckets"] == len(side.blocks)
        # the cap bites on the item side (900 / 12 > 24): retained < edges
        assert span["attrs"]["by_col"]["retained_edges"] == 12 * 24 < 900

    def test_a_span_is_a_host_event_of_a_profiler_trace(self, tmp_path):
        import glob

        import jax
        import jax.numpy as jnp
        from jax.profiler import ProfileData

        tracer = Tracer()
        with tracer.span("outside.any.session") as before:
            assert before._annotation is None  # no session: one check, no annotation
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tracer.span("als.pack"):
                with tracer.span("als.transfer"):
                    jnp.ones(8).block_until_ready()
            assert trace_mod.NULL_TRACER.span("never") is NULL_SPAN  # disabled: untouched
        finally:
            jax.profiler.stop_trace()
        (xplane,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
        events = {
            ev.name: ev.duration_ns
            for plane in ProfileData.from_file(xplane).planes
            if plane.name.startswith("/host:")
            for line in plane.lines
            for ev in line.events
        }
        assert events["als.pack"] >= events["als.transfer"] > 0
        assert "outside.any.session" not in events
        recorded = {s["op"] for t in tracer.snapshot()["recent"] for s in t["spans"]}
        assert {"als.pack", "als.transfer", "outside.any.session"} <= recorded

    def test_a_process_without_jax_opens_no_annotation(self):
        import subprocess
        import sys

        code = (
            "import sys\n"
            "from predictionio_tpu.obs.trace import Tracer\n"
            "tracer = Tracer()\n"
            "with tracer.span('train.algorithm') as span:\n"
            "    assert span._annotation is None\n"
            "assert 'jax' not in sys.modules\n"
            "assert tracer.snapshot()['recent'][0]['op'] == 'train.algorithm'\n"
        )
        proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                              text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]


class TestPioTop:
    PROM = """\
# TYPE pio_http_requests_total counter
pio_http_requests_total{method="POST",route="/queries.json",status="200"} %d
pio_http_requests_total{method="POST",route="/queries.json",status="429"} %d
# TYPE pio_http_request_duration_seconds histogram
pio_http_request_duration_seconds_bucket{route="/queries.json",le="0.001"} %d
pio_http_request_duration_seconds_bucket{route="/queries.json",le="0.01"} %d
pio_http_request_duration_seconds_bucket{route="/queries.json",le="+Inf"} %d
# TYPE pio_ingest_queue_depth gauge
pio_ingest_queue_depth 5
# TYPE pio_serving_batch_size histogram
pio_serving_batch_size_sum %d
pio_serving_batch_size_count %d
"""

    def _snap(self, t, ok, err, b1, b10, binf, bsum, bcount):
        from predictionio_tpu.obs.top import parse_prometheus

        return {
            "url": "http://x:1",
            "time": t,
            "metrics": parse_prometheus(
                self.PROM % (ok, err, b1, b10, binf, bsum, bcount)
            ),
            "traces": None,
        }

    def test_frontend_worker_stats_and_render(self):
        """The multi-process tier's aggregated series reach the `pio top`
        view: worker count in the WKR column, frontend qps from the
        per-worker counter deltas, and the serving queue gauge folded
        into QUEUE."""
        from predictionio_tpu.obs.top import (
            compute_stats,
            parse_prometheus,
            render,
        )

        tmpl = (
            "pio_frontend_workers 2\n"
            'pio_frontend_requests_total{status="2xx",worker="0"} %d\n'
            'pio_frontend_requests_total{status="2xx",worker="1"} %d\n'
            "pio_serving_queue_depth 3\n"
            "pio_scorer_wakeups_per_request 2.0\n"
        )

        def snap(t, a, b):
            return {
                "url": "http://x:1",
                "time": t,
                "metrics": parse_prometheus(tmpl % (a, b)),
                "traces": None,
            }

        stats = compute_stats(snap(100.0, 100, 50), snap(102.0, 200, 150))
        assert stats["frontend_workers"] == 2
        # (100 + 100) forwarded requests over 2 s, summed across workers
        assert stats["frontend_qps"] == pytest.approx(100.0)
        assert stats["ingest_queue_depth"] == 3
        assert stats["wakeups_per_request"] == pytest.approx(2.0)
        frame = render([stats], [snap(102.0, 200, 150)])
        assert "WKR" in frame and "WAKE" in frame
        row = next(l for l in frame.splitlines() if "http://x:1" in l)
        # WKR sits 7th from the end: SHARD (dash here -- not a fabric),
        # PART (dash -- unpartitioned ingest), WAKE (scorer
        # wakeups/request) and the continuous-learning columns
        # (MODEL/SWAP/LAG, dashes here) landed after it
        assert row.split()[-7] == "2"
        assert row.split()[-6] == "-"  # SHARD: unsharded service
        assert row.split()[-5] == "-"  # PART: unpartitioned ingest
        assert row.split()[-4] == "2.0"  # the measured wakeup budget

    def test_shard_fabric_stats_and_render(self):
        """The shard fabric's gauges reach the `pio top` view: shard
        count in the SHARD column, and MODEL aggregated as the max over
        the per-shard ``pio_model_version{shard=}`` series."""
        from predictionio_tpu.obs.top import (
            compute_stats,
            parse_prometheus,
            render,
        )

        text = (
            "pio_frontend_workers 1\n"
            "pio_scorer_shard_count 4\n"
            'pio_model_version{shard="0"} 7\n'
            'pio_model_version{shard="1"} 7\n'
            'pio_model_version{shard="2"} 6\n'
            'pio_model_version{shard="3"} 7\n'
        )

        def snap(t):
            return {
                "url": "http://x:1",
                "time": t,
                "metrics": parse_prometheus(text),
                "traces": None,
            }

        stats = compute_stats(snap(100.0), snap(102.0))
        assert stats["scorer_shards"] == 4
        # mid-swap skew: MODEL shows the leading version (max), bounded
        # to one swap window by the fabric's per-shard protocol
        assert stats["model_version"] == 7
        frame = render([stats], [snap(102.0)])
        assert "SHARD" in frame
        row = next(l for l in frame.splitlines() if "http://x:1" in l)
        assert row.split()[-6] == "4"  # SHARD
        assert row.split()[-3] == "7"  # MODEL

    def test_ingest_partitions_stats_and_render(self):
        """A partitioned event server's gauges reach the `pio top` view:
        partition count in the PART column, queue depth still the summed
        aggregate (the per-partition depth series is /metrics-only)."""
        from predictionio_tpu.obs.top import (
            compute_stats,
            parse_prometheus,
            render,
        )

        text = (
            "pio_ingest_partitions 4\n"
            "pio_ingest_queue_depth 6\n"
            'pio_ingest_partition_depth{part="0"} 1\n'
            'pio_ingest_partition_depth{part="1"} 0\n'
            'pio_ingest_partition_depth{part="2"} 3\n'
            'pio_ingest_partition_depth{part="3"} 2\n'
        )

        def snap(t):
            return {
                "url": "http://x:1",
                "time": t,
                "metrics": parse_prometheus(text),
                "traces": None,
            }

        stats = compute_stats(snap(100.0), snap(102.0))
        assert stats["wal_partitions"] == 4
        assert stats["ingest_queue_depth"] == 6
        frame = render([stats], [snap(102.0)])
        assert "PART" in frame
        row = next(l for l in frame.splitlines() if "http://x:1" in l)
        assert row.split()[-5] == "4"  # PART
        assert row.split()[-6] == "-"  # SHARD (not a scorer fabric)

    def test_parse_prometheus(self):
        from predictionio_tpu.obs.top import parse_prometheus

        parsed = parse_prometheus(self.PROM % (10, 1, 5, 9, 10, 40, 10))
        series = parsed["pio_http_requests_total"]
        assert series[
            (("method", "POST"), ("route", "/queries.json"), ("status", "200"))
        ] == 10.0
        assert parsed["pio_ingest_queue_depth"][()] == 5.0

    def test_compute_stats_uses_deltas(self):
        from predictionio_tpu.obs.top import compute_stats

        prev = self._snap(100.0, 100, 0, 50, 90, 100, 400, 100)
        cur = self._snap(102.0, 300, 10, 150, 280, 310, 1240, 310)
        stats = compute_stats(prev, cur)
        assert stats["qps"] == pytest.approx(105.0)  # 210 requests / 2s
        assert stats["error_rate"] == pytest.approx(10 / 210, abs=1e-4)
        assert stats["ingest_queue_depth"] == 5
        # batch occupancy: (1240-400)/(310-100) = 4.0
        assert stats["batch_occupancy"] == 4.0
        assert 0 < stats["p50_ms"] <= 10.0
        assert stats["p99_ms"] is not None

    PROM_SELF = """\
pio_http_requests_total{method="GET",route="/metrics",status="200"} %d
pio_http_requests_total{method="GET",route="/traces.json",status="200"} %d
pio_http_request_duration_seconds_bucket{route="/metrics",le="0.001"} %d
pio_http_request_duration_seconds_bucket{route="/metrics",le="+Inf"} %d
"""

    def test_self_poll_routes_excluded_from_stats(self):
        # `pio top` polls /metrics + /traces.json every interval; on an
        # idle service those must not masquerade as qps/latency
        from predictionio_tpu.obs.top import compute_stats, parse_prometheus

        def snap(t, n):
            return {
                "url": "http://x:1",
                "time": t,
                "metrics": parse_prometheus(self.PROM_SELF % (n, n, n, n)),
                "traces": None,
            }

        stats = compute_stats(snap(100.0, 1), snap(102.0, 3))
        assert stats["qps"] == 0.0
        assert stats["error_rate"] == 0.0
        assert stats["p50_ms"] is None and stats["p99_ms"] is None

    def test_render_contains_table_and_slowest(self):
        from predictionio_tpu.obs.top import compute_stats, render

        prev = self._snap(0.0, 0, 0, 0, 0, 0, 0, 0)
        cur = self._snap(1.0, 100, 0, 60, 95, 100, 300, 100)
        cur["traces"] = {
            "slowest": [
                {
                    "traceId": "ab" * 16,
                    "op": "POST /queries.json",
                    "durationMs": 45.6,
                    "status": "ok",
                    "spans": [{"op": "batch.execute", "durationMs": 40.0}],
                }
            ]
        }
        frame = render([compute_stats(prev, cur)], [cur])
        assert "SERVICE" in frame and "QPS" in frame and "P99MS" in frame
        assert "http://x:1" in frame
        assert "SLOWEST TRACES" in frame
        assert "POST /queries.json" in frame
        assert "batch.execute" in frame

    def test_run_top_against_live_service(self):
        from predictionio_tpu.obs.top import run_top
        from predictionio_tpu.utils.http import (
            Response,
            ServiceThread,
            instrumented_router,
            make_server,
        )

        router, _ = instrumented_router(tracing=True)
        router.add("GET", "/ping", lambda r: Response(200, {"ok": True}))
        svc = ServiceThread(
            make_server(router, "127.0.0.1", 0, "pio-test")
        ).start()
        try:
            url = f"http://127.0.0.1:{svc.port}"
            urllib.request.urlopen(f"{url}/ping", timeout=10).read()
            frames = []
            run_top(
                [url], interval=0.05, iterations=1, clear=False,
                out=frames.append,
            )
            assert len(frames) == 1
            assert url in frames[0]
            assert "unreachable" not in frames[0]
        finally:
            svc.stop()

    def test_unreachable_service_renders_error_row(self):
        from predictionio_tpu.obs.top import compute_stats, fetch_snapshot, render

        snap = fetch_snapshot("http://127.0.0.1:1", timeout=0.2)
        stats = compute_stats(snap, snap)
        frame = render([stats], [snap])
        assert "unreachable" in frame

    def test_top_cli_registered(self):
        from predictionio_tpu.tools.cli import build_parser

        args = build_parser().parse_args(
            ["top", "http://h:1", "--iterations", "2", "--no-clear"]
        )
        assert args.urls == ["http://h:1"]
        assert args.iterations == 2


class TestQueryServerTracing:
    def test_traced_query_covers_full_path(self, storage_env, tmp_path):
        """Acceptance: one traced query's spans cover queue-wait -> batch
        assembly -> device compute -> respond, and concurrent coalesced
        queries share the batch-level span."""
        import os
        import sys

        from predictionio_tpu.data import DataMap, Event
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.workflow.core_workflow import run_train
        from predictionio_tpu.workflow.create_server import create_query_server
        from predictionio_tpu.workflow.json_extractor import load_engine_variant
        from predictionio_tpu.workflow.microbatch import BatchConfig

        tests_dir = os.path.dirname(os.path.abspath(__file__))
        if tests_dir not in sys.path:
            sys.path.insert(0, tests_dir)
        app_id = storage_env.get_meta_data_apps().insert(App(name="TraceApp"))
        le = storage_env.get_l_events()
        le.init_channel(app_id)
        le.batch_insert(
            [
                Event(
                    event="rate", entity_type="user", entity_id=f"u{k % 4}",
                    target_entity_type="item", target_entity_id=f"i{k}",
                    properties=DataMap({"rating": float(1 + k % 5)}),
                )
                for k in range(20)
            ],
            app_id=app_id,
        )
        variant_path = tmp_path / "engine.json"
        variant_path.write_text(json.dumps({
            "id": "default",
            "engineFactory": "fake_engine.engine_factory",
            "datasource": {"params": {"appName": "TraceApp"}},
            "algorithms": [{"name": "mean", "params": {}}],
        }))
        variant = load_engine_variant(str(variant_path))
        run_train(variant)
        thread, service = create_query_server(
            variant, host="127.0.0.1", port=0, tracing=True,
            batching=BatchConfig(window_ms=100, idle_ms=50, max_batch_size=4),
        )
        thread.start()
        url = f"http://127.0.0.1:{thread.port}"
        try:
            trace_ids = ["a1" * 16, "b2" * 16]
            results = [None, None]

            def worker(k):
                req = urllib.request.Request(
                    f"{url}/queries.json",
                    data=json.dumps({"user": f"u{k}", "num": 3}).encode(),
                    headers={
                        "Content-Type": "application/json",
                        "traceparent": format_traceparent(
                            trace_ids[k], "cc" * 8
                        ),
                    },
                    method="POST",
                )
                with urllib.request.urlopen(req, timeout=30) as resp:
                    results[k] = (
                        resp.status, resp.headers.get("traceparent")
                    )

            threads = [
                threading.Thread(target=worker, args=(k,)) for k in (0, 1)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for k, (status, tp_out) in enumerate(results):
                assert status == 200
                assert parse_traceparent(tp_out)[0] == trace_ids[k]
            snap = _get_json(f"{url}/traces.json?limit=100")
            traces = {t["traceId"]: t for t in snap["recent"]}
            for tid in trace_ids:
                ops = [s["op"] for s in traces[tid]["spans"]]
                for expected in (
                    "query.parse", "batch.queue_wait", "batch.assemble",
                    "batch.execute", "query.respond",
                ):
                    assert expected in ops, f"{expected} missing from {ops}"
                assert traces[tid]["op"] == "POST /queries.json"
            # both queries coalesced (the window is generous): the batch
            # span is one shared span across the two traces
            exec_ids = {
                next(
                    s["spanId"]
                    for s in traces[tid]["spans"]
                    if s["op"] == "batch.execute"
                )
                for tid in trace_ids
            }
            if len(exec_ids) == 2:
                # the wave did not coalesce (scheduling); per-trace spans
                # still must be complete -- assert via batch_size instead
                sizes = {
                    next(
                        s["attrs"]["batch_size"]
                        for s in traces[tid]["spans"]
                        if s["op"] == "batch.execute"
                    )
                    for tid in trace_ids
                }
                assert sizes  # spans carried their batch metadata
            else:
                assert len(exec_ids) == 1
        finally:
            thread.stop()
            service.close()

    def test_traceparent_survives_the_frontend_ring(
        self, storage_env, tmp_path
    ):
        """Multi-process regression: a traceparent'd query enters through
        an SO_REUSEPORT frontend process, crosses the shared-memory ring,
        and its queue-wait/assemble/execute spans still land in the
        ORIGINAL trace -- plus a ``frontend.ring_wait`` span stitched
        from the frontend's clock across the process boundary. Two
        coalesced queries keep sharing one batch-level span id exactly as
        in the single-process tier."""
        import os
        import sys

        from predictionio_tpu.data import DataMap, Event
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.workflow.core_workflow import run_train
        from predictionio_tpu.workflow.create_server import (
            create_multiproc_query_server,
        )
        from predictionio_tpu.workflow.json_extractor import (
            load_engine_variant,
        )
        from predictionio_tpu.workflow.microbatch import BatchConfig

        tests_dir = os.path.dirname(os.path.abspath(__file__))
        if tests_dir not in sys.path:
            sys.path.insert(0, tests_dir)
        app_id = storage_env.get_meta_data_apps().insert(
            App(name="RingTraceApp")
        )
        le = storage_env.get_l_events()
        le.init_channel(app_id)
        le.batch_insert(
            [
                Event(
                    event="rate", entity_type="user", entity_id=f"u{k % 4}",
                    target_entity_type="item", target_entity_id=f"i{k}",
                    properties=DataMap({"rating": float(1 + k % 5)}),
                )
                for k in range(20)
            ],
            app_id=app_id,
        )
        variant_path = tmp_path / "engine.json"
        variant_path.write_text(json.dumps({
            "id": "default",
            "engineFactory": "fake_engine.engine_factory",
            "datasource": {"params": {"appName": "RingTraceApp"}},
            "algorithms": [{"name": "mean", "params": {}}],
        }))
        variant = load_engine_variant(str(variant_path))
        run_train(variant)
        handle, service = create_multiproc_query_server(
            variant, host="127.0.0.1", port=0, frontend=2, tracing=True,
            batching=BatchConfig(window_ms=100, idle_ms=50, max_batch_size=4),
        )
        handle.start()
        url = f"http://127.0.0.1:{handle.port}"
        try:
            trace_ids = ["3a" * 16, "4b" * 16]
            results = [None, None]

            def worker(k):
                req = urllib.request.Request(
                    f"{url}/queries.json",
                    data=json.dumps({"user": f"u{k}", "num": 3}).encode(),
                    headers={
                        "Content-Type": "application/json",
                        "traceparent": format_traceparent(
                            trace_ids[k], "cc" * 8
                        ),
                    },
                    method="POST",
                )
                with urllib.request.urlopen(req, timeout=30) as resp:
                    results[k] = (
                        resp.status, resp.headers.get("traceparent")
                    )

            threads = [
                threading.Thread(target=worker, args=(k,)) for k in (0, 1)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for k, (status, tp_out) in enumerate(results):
                assert status == 200
                # the response traceparent rode the ring back out and
                # still joins the CLIENT's trace
                assert parse_traceparent(tp_out)[0] == trace_ids[k]
            snap = _get_json(f"{url}/traces.json?limit=100")
            traces = {t["traceId"]: t for t in snap["recent"]}
            for tid in trace_ids:
                assert tid in traces, (
                    f"client trace {tid} missing from the scorer's "
                    f"retention: {sorted(traces)}"
                )
                spans = traces[tid]["spans"]
                ops = [s["op"] for s in spans]
                for expected in (
                    "frontend.ring_wait", "query.parse",
                    "batch.queue_wait", "batch.assemble", "batch.execute",
                    "query.respond",
                ):
                    assert expected in ops, f"{expected} missing from {ops}"
                assert traces[tid]["op"] == "POST /queries.json"
                ring_span = next(
                    s for s in spans if s["op"] == "frontend.ring_wait"
                )
                # stitched from the frontend process's perf_counter: a
                # sane non-negative duration and the worker's identity
                assert ring_span["durationMs"] >= 0.0
                assert ring_span["attrs"]["worker"] in ("0", "1")
                # the async fast path: the root span is an explicit
                # handle -- started on the ring consumer, FINISHED from
                # the micro-batcher's flusher via the future callback
                root_span = next(
                    s for s in spans if s["op"] == "POST /queries.json"
                )
                assert root_span["thread"] == "pio-microbatcher"
            exec_ids = {
                next(
                    s["spanId"]
                    for s in traces[tid]["spans"]
                    if s["op"] == "batch.execute"
                )
                for tid in trace_ids
            }
            if len(exec_ids) == 2:
                # the wave did not coalesce (scheduling); per-trace spans
                # must still be complete with their batch metadata
                sizes = {
                    next(
                        s["attrs"]["batch_size"]
                        for s in traces[tid]["spans"]
                        if s["op"] == "batch.execute"
                    )
                    for tid in trace_ids
                }
                assert sizes
            else:
                assert len(exec_ids) == 1
        finally:
            handle.stop()
            service.close()
