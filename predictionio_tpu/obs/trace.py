"""Low-overhead span tracer with W3C ``traceparent`` propagation.

Design constraints, in order:

1. **The tracing-off path allocates nothing.** ``Tracer.span`` on a
   disabled tracer returns one shared no-op singleton; hot paths can be
   instrumented unconditionally.
2. **Bounded memory.** Finished traces land in a ring buffer
   (``recent``); eviction there must not lose the traces an operator
   actually wants, so slow and error traces are ALSO retained in two
   small tail-keep buffers (top-N by duration, last-N errors) that fast
   traffic cannot wash out.
3. **Cross-thread fan-out.** The batching tiers (micro-batcher, ingest
   group commit) do one unit of device/disk work for many coalesced
   requests. ``record_span`` writes an explicitly-timed span into ANY
   live trace, and a shared ``span_id`` lets one batch-level span appear
   in every participating request's trace (the "which batch did my
   request ride" join).

Context propagation is a module-global thread-local stack shared by all
tracers: one thread has one active span, regardless of which service's
tracer opened it, so log records (``obs.logs``) can resolve ids without a
tracer reference. Remote context arrives/leaves as the W3C trace-context
``traceparent`` header (``00-<trace32>-<span16>-<flags>``).

Durations come from ``time.perf_counter()``; wall-clock display times are
derived once via a process-constant offset so spans timed on different
threads line up on one axis.
"""

from __future__ import annotations

import heapq
import logging
import os
import random
import re
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field

logger = logging.getLogger("pio.trace")

#: perf_counter -> epoch-seconds offset, captured once at import so every
#: span in the process shares one time axis
_PC_TO_WALL = time.time() - time.perf_counter()


def epoch_seconds(pc: float) -> float:
    """A ``time.perf_counter()`` reading on the spans' axis: the epoch
    seconds a ``SpanRecord.start_s`` taken at that reading would hold. A
    reader that timed something with its own ``perf_counter`` (a benchmark
    driver's window, ``utils.platform``'s compile table) places it among
    the spans with this."""
    return pc + _PC_TO_WALL

_TRACEPARENT_RE = re.compile(
    r"^[0-9a-f]{2}-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$"
)

#: spans retained per live trace; a runaway instrumentation loop must cap
#: at this, not grow without bound
MAX_SPANS_PER_TRACE = 256

_tls = threading.local()


def _stack() -> list:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def current_context() -> "tuple[str, str] | None":
    """(trace_id, span_id) of the calling thread's active span, or None.
    Module-level (not per-tracer) so log formatters need no tracer ref."""
    stack = getattr(_tls, "stack", None)
    if not stack:
        return None
    span = stack[-1]
    return (span.trace_id, span.span_id)


#: id source: a Mersenne twister seeded once from the OS. ``os.urandom``
#: per id costs a syscall (~15us on sandboxed kernels -- measured 25x the
#: rest of the span lifecycle combined); ids need collision resistance,
#: not unpredictability. getrandbits is one C call, atomic under the GIL.
_id_rand = random.Random(os.urandom(16))


def record_under_current(op: str, start_pc: float, end_pc: float,
                         attrs: dict | None = None) -> bool:
    """Record an explicitly timed span (``time.perf_counter()`` readings)
    as a child of the calling thread's active span, in that span's own
    tracer, whichever service opened it. False where the thread has no
    active span: nothing is recorded, and no standalone trace is made."""
    stack = getattr(_tls, "stack", None)
    if not stack:
        return False
    parent = stack[-1]
    parent._tracer.record_span(parent.trace_id, op, start_pc, end_pc,
                               parent_id=parent.span_id, attrs=attrs)
    return True


def new_trace_id() -> str:
    return f"{_id_rand.getrandbits(128) or 1:032x}"  # all-zero id is invalid


def new_span_id() -> str:
    return f"{_id_rand.getrandbits(64) or 1:016x}"


def format_traceparent(trace_id: str, span_id: str) -> str:
    return f"00-{trace_id}-{span_id}-01"


def parse_traceparent(header: str | None) -> tuple[str, str, bool] | None:
    """(trace_id, parent_span_id, sampled) from a W3C traceparent header,
    or None for anything malformed (a bad header must start a fresh
    trace, never error a request). ``sampled`` is the trace-flags 01
    bit: the caller's own sampling decision."""
    if not header:
        return None
    m = _TRACEPARENT_RE.match(header.strip().lower())
    if m is None:
        return None
    trace_id, span_id = m.group(1), m.group(2)
    # all-zero ids are explicitly invalid per the spec
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id, bool(int(m.group(3), 16) & 0x01)


@dataclass(slots=True)
class SpanRecord:
    """One finished span (immutable once recorded)."""

    trace_id: str
    span_id: str
    parent_id: str | None
    op: str
    start_s: float      # epoch seconds
    duration_s: float
    status: str = "ok"  # ok | error
    attrs: dict = field(default_factory=dict)
    thread: str = ""
    #: True when this record must NOT flow through the span->histogram
    #: bridge at root finish: per-request ``batch.queue_wait`` already
    #: aggregates natively as ``pio_serving_batch_queue_wait_seconds``,
    #: and the shared batch-level spans are bridged exactly once per
    #: batch by ``record_fanout`` -- bridging the per-trace copies too
    #: would count one device batch N times
    bridged: bool = False

    def to_json_obj(self, trace_start_s: float) -> dict:
        obj = {
            "op": self.op,
            "spanId": self.span_id,
            "parentId": self.parent_id,
            "offsetMs": round((self.start_s - trace_start_s) * 1000.0, 3),
            "durationMs": round(self.duration_s * 1000.0, 3),
            "status": self.status,
            "thread": self.thread,
        }
        if self.attrs:
            obj["attrs"] = self.attrs
        return obj


class _NullSpan:
    """The shared no-op span: disabled tracers hand this out so the
    tracing-off hot path allocates no objects at all."""

    __slots__ = ()
    trace_id = None
    span_id = None

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_op(self, op: str) -> None:
        pass

    def set_attr(self, key: str, value) -> None:
        pass

    def set_status(self, status: str) -> None:
        pass

    def attach(self) -> "_NullSpan":
        return self

    def detach(self) -> None:
        pass

    def finish(self) -> None:
        pass


NULL_SPAN = _NullSpan()


class _SampledOutRoot:
    """The shared root handed out when a headerless root loses the
    sampling coin flip. Entering it raises a thread-local suppression
    flag so every nested ``span()`` call returns the no-op singleton
    instead of opening a fresh root trace of its own -- the whole
    request costs one boolean, no allocations. Only roots sample (a
    suppressed thread cannot open a second root before exiting), so one
    shared instance is safe."""

    __slots__ = ()
    trace_id = None
    span_id = None

    def __enter__(self) -> "_SampledOutRoot":
        _tls.suppress = True
        return self

    def __exit__(self, *exc) -> bool:
        _tls.suppress = False
        return False

    def set_op(self, op: str) -> None:
        pass

    def set_attr(self, key: str, value) -> None:
        pass

    def set_status(self, status: str) -> None:
        pass

    def attach(self) -> "_SampledOutRoot":
        """Handle-style suppression (the async fast path carries this
        sentinel across threads like a real Span): raise the calling
        thread's suppress flag so nested ``span()`` calls stay no-ops
        instead of opening fresh roots. Pair with ``detach()``."""
        _tls.suppress = True
        return self

    def detach(self) -> None:
        _tls.suppress = False

    def finish(self) -> None:
        pass


SAMPLED_OUT_ROOT = _SampledOutRoot()


class Span:
    """A live span: context manager that pushes itself on the thread's
    context stack and reports to its tracer on exit.

    Spans are also EXPLICIT HANDLES for code whose request does not stay
    on one thread (the async scorer fast path): ``attach()``/``detach()``
    manage the calling thread's context stack without ending the span,
    and ``finish()`` records the end from ANY thread -- start a root on
    the ring consumer, attach around the micro-batcher submit so
    ``current_context()`` captures it, detach, and finish from the
    flusher's ``Future.add_done_callback``. ``__enter__``/``__exit__``
    are ``attach()`` + (``detach()``; ``finish()``), and while a
    ``jax.profiler`` session runs (``pio train --profile``, a traced
    benchmark window) they also open a ``TraceAnnotation`` under the op
    the span was started with: the span then lies on the profiler's host
    plane, on the device's clock. Handle-style spans get none (an
    annotation closes on the thread that opened it), and explicitly timed
    records (``record_span``, ``record_fanout``) stay host-clock only."""

    __slots__ = (
        "_tracer", "op", "trace_id", "span_id", "parent_id", "attrs",
        "status", "_start_pc", "_root", "_finished", "_annotation",
    )

    def __init__(self, tracer: "Tracer", op: str, trace_id: str,
                 parent_id: str | None, root: bool, attrs: dict | None):
        self._tracer = tracer
        self.op = op
        self.trace_id = trace_id
        self.span_id = new_span_id()
        self.parent_id = parent_id
        self.attrs = attrs
        self.status = "ok"
        self._root = root
        self._finished = False
        self._annotation = None
        if root:
            # register the trace as live IMMEDIATELY: record_span from
            # another thread can attach to it for the root's whole lifetime
            with tracer._lock:
                tracer._begin_trace(trace_id)
        self._start_pc = time.perf_counter()

    def set_op(self, op: str) -> None:
        self.op = op

    def set_attr(self, key: str, value) -> None:
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value

    def set_status(self, status: str) -> None:
        self.status = status

    def attach(self) -> "Span":
        """Push this span onto the CALLING thread's context stack (so
        ``current_context()`` and nested ``tracer.span()`` calls see it)
        without affecting its lifetime. Pair with ``detach()``."""
        _stack().append(self)
        return self

    def detach(self) -> None:
        """Pop this span off the calling thread's context stack WITHOUT
        finishing it -- the span stays live and can be finished later
        from another thread."""
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:  # defensive: mis-nested exits must not corrupt
            stack.remove(self)

    def finish(self) -> None:
        """Record the span's end. Thread-agnostic and idempotent (a
        double finish records once); does NOT touch any context stack --
        callers that attached must detach themselves."""
        if self._finished:
            return
        self._finished = True
        end_pc = time.perf_counter()
        record = SpanRecord(
            trace_id=self.trace_id,
            span_id=self.span_id,
            parent_id=self.parent_id,
            op=self.op,
            start_s=self._start_pc + _PC_TO_WALL,
            duration_s=end_pc - self._start_pc,
            status=self.status,
            attrs=self.attrs or {},
            thread=threading.current_thread().name,
        )
        self._tracer._span_finished(record, self._root)

    def __enter__(self) -> "Span":
        # only where jax is in the process already (obs never imports it);
        # with no session running this is one dictionary lookup and one call
        profiler = sys.modules.get("jax.profiler")
        if profiler is not None and profiler.TraceAnnotation.is_enabled():
            self._annotation = profiler.TraceAnnotation(self.op)
            self._annotation.__enter__()
        return self.attach()

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        self.detach()
        if exc_type is not None:
            self.status = "error"
            if self.attrs is None:
                self.attrs = {}
            self.attrs.setdefault("error", f"{exc_type.__name__}: {exc}")
        self.finish()
        return False


class Tracer:
    """Span factory + bounded trace retention + ``/traces.json`` source.

    ``on_spans(records)`` runs OUTSIDE the tracer lock with a LIST of
    finished spans (the span->histogram bridge; see
    ``utils.metrics.span_bridge``). It fires once per COMPLETED trace
    with every span of that trace, and once per standalone record --
    batching matters: per-span bridge calls meant one metrics-lock
    round-trip per span, and on a GIL-bound serving box the resulting
    lock convoy across 32 handler threads cost more than the spans
    themselves."""

    def __init__(
        self,
        enabled: bool = True,
        recent_cap: int = 128,
        keep_cap: int = 32,
        live_cap: int = 512,
        on_spans=None,
        sample: float = 1.0,
    ):
        self.enabled = enabled
        #: head-sampling rate for SELF-INITIATED roots (no inbound
        #: traceparent): full per-request tracing costs ~0.4 ms of python
        #: on the GIL-bound serving path (~10% qps on the 2-core box),
        #: so the service routers default to a sampled rate
        #: (``tracing_sample_default``) while remote-initiated requests
        #: -- where the caller already decided to trace -- always record.
        #: Direct construction (tests, training) defaults to 1.0.
        self.sample = min(max(float(sample), 0.0), 1.0)
        self.on_spans = on_spans
        self._lock = threading.Lock()
        #: trace_id -> list[SpanRecord] for traces whose root is still open
        self._live: dict[str, list] = {}
        self._live_cap = live_cap
        #: every finished trace, newest last (plain ring: fast traffic
        #: evicts old entries)
        self._recent: deque = deque(maxlen=recent_cap)
        #: tail-based keep: top-N slowest traces ever (min at index 0)
        self._slow: list = []
        self._slow_cap = keep_cap
        self._seq = 0
        #: last-N error traces (eviction-proof like _slow)
        self._errors: deque = deque(maxlen=keep_cap)
        #: (op_prefix, seconds) slow-log thresholds, longest prefix wins
        self._slow_log: list[tuple[str, float]] = []

    # -- span creation ------------------------------------------------------
    def span(self, op: str, attrs: dict | None = None):
        """Start a child of the calling thread's active span (or a new
        root trace). Returns the shared no-op singleton when disabled."""
        if not self.enabled:
            return NULL_SPAN
        if getattr(_tls, "suppress", False):
            return NULL_SPAN
        stack = getattr(_tls, "stack", None)
        if stack:
            parent = stack[-1]
            return Span(self, op, parent.trace_id, parent.span_id, False, attrs)
        if self.sample < 1.0 and _id_rand.random() >= self.sample:
            return SAMPLED_OUT_ROOT
        return Span(self, op, new_trace_id(), None, True, attrs)

    def start_remote(self, op: str, traceparent: str | None,
                     attrs: dict | None = None):
        """Root span for an inbound request: joins the caller's trace when
        a valid ``traceparent`` header arrived with the sampled flag set
        (ALWAYS recorded -- the caller decided to trace; sampling is
        theirs). A header with the flag CLEAR (e.g. a mesh proxy that
        stamps every request with ``-00``) must not force 100% tracing:
        it is subject to this tracer's ``sample`` rate like a headerless
        request, though a sampled-in trace still joins the caller's ids
        so logs correlate. No header starts a fresh sampled trace."""
        if not self.enabled:
            return NULL_SPAN
        remote = parse_traceparent(traceparent)
        if remote is not None and remote[2]:
            return Span(self, op, remote[0], remote[1], True, attrs)
        if self.sample < 1.0 and _id_rand.random() >= self.sample:
            return SAMPLED_OUT_ROOT
        if remote is not None:
            return Span(self, op, remote[0], remote[1], True, attrs)
        return Span(self, op, new_trace_id(), None, True, attrs)

    def record_span(
        self,
        trace_id: str,
        op: str,
        start_pc: float,
        end_pc: float,
        *,
        parent_id: str | None = None,
        span_id: str | None = None,
        attrs: dict | None = None,
        status: str = "ok",
    ) -> str | None:
        """Record an explicitly-timed span (timestamps from
        ``time.perf_counter()``) into a trace by id -- the cross-thread
        fan-out primitive. Passing the same ``span_id`` into several
        traces makes them share one batch-level span. If the trace is not
        live (e.g. WAL replay of a trace from a previous process) the
        span is retained as a standalone single-span trace. Returns the
        span id, or None when disabled."""
        if not self.enabled:
            return None
        record = SpanRecord(
            trace_id=trace_id,
            span_id=span_id or new_span_id(),
            parent_id=parent_id,
            op=op,
            start_s=start_pc + _PC_TO_WALL,
            duration_s=max(end_pc - start_pc, 0.0),
            status=status,
            attrs=attrs or {},
            thread=threading.current_thread().name,
        )
        if not self._attach(record) and self.on_spans is not None:
            # attached to a live trace -> bridged at that trace's root
            # finish; retained standalone -> bridge it now
            try:
                self.on_spans([record])
            except Exception:
                logger.warning("span bridge failed", exc_info=True)
        return record.span_id

    def live_spans(self, trace_id: str) -> "list | None":
        """The live span list for ``trace_id``, or None. A batch tier
        captures this AT SUBMIT (while the request's root is guaranteed
        open) and hands it to ``record_fanout`` AFTER resolving the
        request -- appends to the captured list still land in the right
        trace even once the root has finished, because retention keeps
        the SAME list object."""
        if not self.enabled:
            return None
        return self._live.get(trace_id)

    def record_fanout(
        self,
        items: "list[tuple[tuple[str, str], float, list | None]]",
        exec_ops: "list[tuple]",
        attrs: dict | None = None,
        status: str = "ok",
        queue_op: str = "batch.queue_wait",
        bridge_queue: bool = False,
        extra: "tuple[str, str | None, list | None] | None" = None,
    ) -> None:
        """The batch-tier fan-out, amortized and OFF the latency path:
        for every coalesced request ``((trace_id, parent_id),
        enqueued_pc, live_spans(trace_id))`` write one per-request
        ``queue_op`` span plus the shared batch-level spans ``(op,
        start_pc, end_pc[, attrs])`` -- each with ONE span id shared
        across the whole batch. This runs on the flusher thread after
        the batch's futures resolve, appending into the span lists
        captured at submit: no tracer lock, no liveness race with roots
        that already finished. Per-span ``record_span`` before
        resolution cost ~100us of ack latency per request (lock
        round-trips plus flusher-thread work ahead of the future
        wake-up). The per-trace copies are marked ``bridged``
        (queue-wait aggregates natively as
        ``pio_serving_batch_queue_wait_seconds``, or set
        ``bridge_queue`` to histogram it once per request); the shared
        batch-level spans bridge into ``pio_span_duration_seconds{op}``
        exactly ONCE PER BATCH here, so dashboards can trend
        assemble/execute (or one physical WAL fsync) without one batch
        counting N times. ``extra`` -- ``(trace_id, parent_id,
        live_spans)`` -- additionally lands the shared spans in a
        flusher-owned trace (the ingest writer's commit root)."""
        if not self.enabled or not exec_ops or (not items and extra is None):
            return
        shared = [
            SpanRecord(
                trace_id=items[0][0][0] if items else extra[0],
                span_id=new_span_id(),
                parent_id=None,
                op=e[0],
                start_s=e[1] + _PC_TO_WALL,
                duration_s=max(e[2] - e[1], 0.0),
                status=status,
                attrs=(e[3] if len(e) > 3 else attrs) or {},
                bridged=True,
            )
            for e in exec_ops
        ]
        bridge = list(shared)
        flush_pc = exec_ops[0][1]

        def _copies(trace_id: str, parent_id: "str | None") -> list:
            return [SpanRecord(
                trace_id=trace_id,
                span_id=rep.span_id,
                parent_id=parent_id,
                op=rep.op,
                start_s=rep.start_s,
                duration_s=rep.duration_s,
                status=status,
                attrs=rep.attrs,
                bridged=True,
            ) for rep in shared]

        def _land(records: list, spans: "list | None") -> None:
            if spans is not None:
                if len(spans) < MAX_SPANS_PER_TRACE:
                    spans.extend(records)
            else:
                for record in records:
                    self._attach(record)

        for (trace_id, parent_id), enqueued_pc, spans in items:
            queue_rec = SpanRecord(
                trace_id=trace_id,
                span_id=new_span_id(),
                parent_id=parent_id,
                op=queue_op,
                start_s=enqueued_pc + _PC_TO_WALL,
                duration_s=max(flush_pc - enqueued_pc, 0.0),
                bridged=True,
            )
            if bridge_queue:
                bridge.append(queue_rec)
            _land([queue_rec] + _copies(trace_id, parent_id), spans)
        if extra is not None:
            _land(_copies(extra[0], extra[1]), extra[2])
        if self.on_spans is not None:
            try:
                self.on_spans(bridge)
            except Exception:
                logger.warning("span bridge failed", exc_info=True)

    def _attach(self, record: SpanRecord) -> bool:
        """Append a finished span to its live trace (returns True), or
        retain it as a standalone trace when none is live (e.g. WAL
        replay of a trace from a previous process; returns False). The
        live-trace path is LOCK-FREE: ``dict.get`` and ``list.append``
        are each atomic under the GIL, entries are only ever removed by
        the root's finish (which retains the SAME list object, so a
        straggler append still lands in the retained trace), and the
        spans-per-trace cap is deliberately approximate -- two racing
        appends at the cap cost two extra records, not corruption."""
        spans = self._live.get(record.trace_id)
        if spans is not None:
            if len(spans) < MAX_SPANS_PER_TRACE:
                spans.append(record)
            return True
        with self._lock:
            self._retain_locked(record, [record])
        return False

    # -- retention ----------------------------------------------------------
    def _span_finished(self, record: SpanRecord, root: bool) -> None:
        if not root:
            # the serving hot path: every child span in every handler
            # thread lands here, so it must not take the tracer lock; a
            # live attach defers the bridge to the trace's root finish
            if self._attach(record):
                return
            bridge, slow_entry = [record], None
        else:
            slow_entry = None
            with self._lock:
                spans = self._live.pop(record.trace_id, [])
                spans.append(record)
                self._retain_locked(record, spans)
                slow_s = self._slow_log_threshold(record.op)
                if slow_s is not None and record.duration_s >= slow_s:
                    slow_entry = (record, spans)
            # slice-copy: a straggler child append (lock-free _attach)
            # must not resize the list while the bridge iterates it
            bridge = [s for s in spans[:] if not s.bridged]
        if self.on_spans is not None:
            try:
                self.on_spans(bridge)
            except Exception:
                logger.warning("span bridge failed", exc_info=True)
        if slow_entry is not None:
            # exactly one record per slow trace, emitted outside the lock
            root_rec, spans = slow_entry
            logger.warning(
                "slow op: %s took %.1f ms (trace=%s, %d span(s): %s)",
                root_rec.op,
                root_rec.duration_s * 1000.0,
                root_rec.trace_id,
                len(spans),
                ", ".join(
                    f"{s.op}={s.duration_s * 1000.0:.1f}ms"
                    for s in spans[:8]
                ),
            )

    def _trace_obj(self, root: SpanRecord, spans: list) -> dict:
        """Serialize one retained trace -- called at snapshot time only;
        the hot path retains raw records."""
        # slice-copy first: a straggler child finishing after its root
        # appends to this very list lock-free (see _attach)
        spans = spans[:]
        start = min(s.start_s for s in spans)
        status = "error" if any(s.status == "error" for s in spans) else "ok"
        return {
            "traceId": root.trace_id,
            "op": root.op,
            "startTime": time.strftime(
                "%Y-%m-%dT%H:%M:%S", time.gmtime(start)
            ) + f".{int((start % 1) * 1000):03d}Z",
            "durationMs": round(root.duration_s * 1000.0, 3),
            "status": status,
            "spans": [s.to_json_obj(start) for s in spans],
        }

    def _retain_locked(self, root: SpanRecord, spans: list) -> None:
        entry = (root, spans)
        self._recent.append(entry)
        if root.status == "error" or any(s.status == "error" for s in spans):
            self._errors.append(entry)
        self._seq += 1
        heap_entry = (root.duration_s, self._seq, entry)
        if len(self._slow) < self._slow_cap:
            heapq.heappush(self._slow, heap_entry)
        elif self._slow and heap_entry > self._slow[0]:
            heapq.heapreplace(self._slow, heap_entry)

    def _begin_trace(self, trace_id: str) -> None:
        if len(self._live) >= self._live_cap:
            # drop the oldest live trace (dict preserves insertion order):
            # a leaked root must not grow memory forever
            self._live.pop(next(iter(self._live)), None)
        self._live.setdefault(trace_id, [])

    # -- slow-op log --------------------------------------------------------
    def set_slow_threshold(self, op_prefix: str, seconds: float | None) -> None:
        """Log one summary line for any finished trace whose root op
        starts with ``op_prefix`` and whose duration >= ``seconds``
        (None removes the threshold)."""
        with self._lock:
            self._slow_log = [
                (p, s) for p, s in self._slow_log if p != op_prefix
            ]
            if seconds is not None:
                self._slow_log.append((op_prefix, float(seconds)))
                self._slow_log.sort(key=lambda e: -len(e[0]))  # longest first

    def _slow_log_threshold(self, op: str) -> float | None:
        for prefix, seconds in self._slow_log:
            if op.startswith(prefix):
                return seconds
        return None

    # -- exposure -----------------------------------------------------------
    def snapshot(
        self,
        op: str | None = None,
        min_ms: float | None = None,
        limit: int = 50,
    ) -> dict:
        """The ``/traces.json`` payload: recent + slowest + error traces,
        filterable by root-op substring and minimum duration. Retained
        entries are raw records; serialization happens here (poll rate),
        never on the request path."""
        with self._lock:
            recent = list(self._recent)
            slow = [e for _, _, e in sorted(self._slow, reverse=True)]
            errors = list(self._errors)

        def keep(root: SpanRecord) -> bool:
            if op and op not in root.op:
                return False
            if min_ms is not None and root.duration_s * 1000.0 < min_ms:
                return False
            return True

        def serialize(entries) -> list[dict]:
            return [
                self._trace_obj(root, spans)
                for root, spans in entries
                if keep(root)
            ][:limit]

        return {
            "enabled": self.enabled,
            "recent": serialize(reversed(recent)),
            "slowest": serialize(slow),
            "errors": serialize(reversed(errors)),
        }


#: the always-off tracer: code paths take ``tracer or NULL_TRACER`` and
#: instrument unconditionally without None checks
NULL_TRACER = Tracer(enabled=False)

_global_lock = threading.Lock()
_global: Tracer | None = None


def global_tracer() -> Tracer:
    """Process-wide tracer for code that runs outside any service router
    (training loops, CLI verbs). Enabled unless ``PIO_TRACING=0``; spans
    bridge into ``utils.metrics.global_registry()``."""
    global _global
    with _global_lock:
        if _global is None:
            from predictionio_tpu.utils.metrics import global_registry, span_bridge

            _global = Tracer(
                enabled=tracing_enabled_default(),
                on_spans=span_bridge(global_registry()),
            )
        return _global


def tracing_enabled_default() -> bool:
    """The process default: on, unless ``PIO_TRACING=0`` opts out."""
    return os.environ.get("PIO_TRACING", "1") != "0"


#: default head-sampling rate for service routers: 1-in-8 headerless
#: roots. Full tracing costs ~0.4 ms/request of python, ~10% qps on the
#: GIL-bound 2-core box; 1/8 lands it under the 2% acceptance bar while
#: /traces.json stays live even at dev-traffic rates
DEFAULT_SAMPLE = 0.125


def tracing_sample_default() -> float:
    """Service-router sampling default: ``PIO_TRACE_SAMPLE`` (0..1, e.g.
    ``1`` = trace everything, ``0.125`` = 1-in-8 headerless roots), falling
    back to :data:`DEFAULT_SAMPLE`. Malformed values fall back rather than
    erroring -- a bad env var must not take a service down."""
    try:
        rate = float(os.environ.get("PIO_TRACE_SAMPLE", DEFAULT_SAMPLE))
    except ValueError:
        return DEFAULT_SAMPLE
    return min(max(rate, 0.0), 1.0)
