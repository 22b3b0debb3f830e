"""JAX backend resolution shared by every entry point that runs a program.

One rule: the platform is resolved ONCE -- explicit argument (the
``pio.platform`` runtime conf), else ``PIO_PLATFORM``, else whatever JAX is
configured for (``JAX_PLATFORMS``) -- and a backend that does not come up is
an error. Nothing here retries another platform. The CPU is something a user
asks for (``PIO_PLATFORM=cpu`` or ``JAX_PLATFORMS=cpu``), never something the
program settles for: a train or deploy that quietly ran on the host would be
read as a chip result.

``ensure_backend`` also places the persistent compile cache, so ``pio
train``, ``pio deploy``, scorer shards, bench children and ``chip_smoke.py``
children all share one, and starts the ``pio_jit_*`` counters and the table of
programs (``count_compiles``, ``compile_report``).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque

from predictionio_tpu.obs.trace import (
    NULL_SPAN, epoch_seconds, global_tracer, record_under_current,
    tracing_enabled_default)

logger = logging.getLogger("pio.platform")

#: programs that took at least this long to compile are written to the cache
#: (JAX's default of 1 s would skip the small serving programs)
CACHE_MIN_COMPILE_SECS = 0.1


def checkout_root() -> str:
    """The directory that holds the ``predictionio_tpu`` package."""
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and no other
    path is set in code. Unset: ``<checkout>/.jax_cache``, a fixed path
    derived from the package's own location -- the path is part of the cache
    key's world, so it must not move between processes (no tempfile, pid or
    time in it).
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(checkout_root(), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", CACHE_MIN_COMPILE_SECS
    )
    # JAX's key leaves an instruction's names out (``op_name``, source lines), so
    # a cache written before a ``jax.named_scope`` was added serves a program
    # whose trace still reads under the old names: the one-chip ALS cell's
    # iteration came back without this PR's leaves on a machine whose cache an
    # earlier commit had filled (PERF.md section 6, PR 35). The key covers them
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path


#: ``jax.monitoring`` duration events -> the counter of seconds each feeds
#: (names as jax 0.9.0's ``jax/_src/dispatch.py`` has them). The third fires
#: once for every program compiled OR loaded from the persistent cache.
_DURATION_COUNTERS = {
    "/jax/core/compile/jaxpr_trace_duration": (
        "pio_jit_trace_seconds_total",
        "Seconds spent tracing Python functions to jaxprs",
    ),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": (
        "pio_jit_lower_seconds_total",
        "Seconds spent lowering jaxprs to MLIR modules",
    ),
    "/jax/core/compile/backend_compile_duration": (
        "pio_jit_compile_seconds_total",
        "Seconds spent compiling programs or loading them from the"
        " persistent cache",
    ),
}
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: the persistent cache's own events (``jax/_src/compiler.py``,
#: ``compilation_cache.py``). A miss is counted where a compiled program is
#: written to the cache: one that compiled in under CACHE_MIN_COMPILE_SECS is
#: never written, and is neither a hit nor a miss.
_EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits": (
        "pio_jit_cache_hits_total",
        "Programs loaded from the persistent compilation cache",
    ),
    "/jax/compilation_cache/cache_misses": (
        "pio_jit_cache_misses_total",
        "Programs compiled and written to the persistent compilation cache",
    ),
}
_COMPILES_TOTAL = (
    "pio_jit_compiles_total",
    "Programs compiled or loaded from the persistent cache",
)

#: a duration event -> the span it becomes and the column of its seconds in
#: a program's row, in the order a program goes through them
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": ("jit.trace", "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("jit.lower", "lower_s"),
    _COMPILE_EVENT: ("jit.compile", "compile_s"),
}
_COLUMNS = tuple(column for _, column in _PHASES.values())
_CACHE_VERDICTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}

#: rows ``compile_report`` holds; the oldest go first. A benchmark cell makes
#: a few dozen programs, its reference included
PROGRAM_ROWS = 256
_PROGRAMS: deque = deque(maxlen=PROGRAM_ROWS)
#: per thread: ``flight``, the events JAX has entered and not left, outermost
#: first, and ``row``, the row of the program the thread worked on last
_thread = threading.local()

_counting = False


def _on_span_clock(start_time: float, end_time: float) -> tuple[float, float]:
    """JAX's interval, two ``time.time()`` readings, as ``perf_counter``
    readings, the clock of ``obs.trace``'s spans: the one place that converts,
    by how long ago the event ended on JAX's own clock."""
    end_pc = time.perf_counter() - (time.time() - end_time)
    return end_pc - (end_time - start_time), end_pc


def _entered(event: str, _value, **_) -> None:
    """JAX records a scalar as it enters a timed event: the event is in
    flight on this thread until its time span closes."""
    if event in _PHASES:
        flight = getattr(_thread, "flight", None)
        if flight is None:
            flight = _thread.flight = []
        flight.append({"event": event, "cache": "none", "nested_s": 0.0})


def _left(event: str, start_time: float, end_time: float,
          fun_name: str = "", **_) -> None:
    """A time span closed: a span under the thread's active span, and a
    column of the program's row. An event inside another (a helper traced
    while its caller is traced or lowered) is neither: its caller's interval
    holds it, a step's program makes a thousand of them, and its seconds go
    to the caller's ``nested_s``."""
    if event not in _PHASES:
        return
    op, column = _PHASES[event]
    flight = getattr(_thread, "flight", None) or []
    entry = (flight.pop() if flight and flight[-1]["event"] == event
             else {"cache": "none", "nested_s": 0.0})
    if flight:
        flight[0]["nested_s"] += end_time - start_time + entry["nested_s"]
        return
    start_pc, end_pc = _on_span_clock(start_time, end_time)
    attrs = {"program": fun_name}
    if column == "compile_s":
        attrs["cache"] = entry["cache"]
    record_under_current(op, start_pc, end_pc, attrs)

    # trace, lowering and compile of one program follow each other on one
    # thread, the first under the function's name, the others under
    # ``jit(<name>)`` (``pmap(<name>)``): one row, unless a phase is there
    # already (a new shape of the same function) or another program came between
    row = getattr(_thread, "row", None)
    if (row is None
            or any(row[c] for c in _COLUMNS[_COLUMNS.index(column):])
            or not (fun_name == row["program"]
                    or fun_name.endswith(f"({row['program']})"))):
        row = _thread.row = {
            "program": fun_name, **dict.fromkeys(_COLUMNS, 0.0), "nested_s": 0.0,
            "cache": None, "start_s": epoch_seconds(start_pc), "end_s": 0.0,
        }
        _PROGRAMS.append(row)
    row["program"] = fun_name
    row[column] = end_time - start_time
    row["nested_s"] += entry["nested_s"]
    row["end_s"] = epoch_seconds(end_pc)
    if column == "compile_s":
        row["cache"] = entry["cache"]


def count_compiles() -> None:
    """Feed JAX's compile events into ``utils.metrics.global_registry()``,
    which every service's ``/metrics`` merges in: a query server that
    recompiles on a batch shape it has not seen shows as a counter that
    climbs. Registers once a process; the counters start at 0 so that a
    scrape shows them before the first compile.

    Where tracing is on (not ``PIO_TRACING=0``) and the installed JAX tells
    an event's interval and its entry, each trace, lowering and compile is
    also a span (``jit.trace``, ``jit.lower``, ``jit.compile``; attribute
    ``program``, and ``cache`` on the third: ``hit``, ``miss``, or ``none``
    for a program compiled and never written) under the thread's active span,
    and a column of the program's row in ``compile_report()``."""
    global _counting
    if _counting:
        return
    _counting = True
    from jax import monitoring

    from predictionio_tpu.utils.metrics import global_registry

    timeline = tracing_enabled_default() and all(
        hasattr(monitoring, name) for name in
        ("register_scalar_listener", "register_event_time_span_listener"))

    registry = global_registry()
    for name, help_ in (*_DURATION_COUNTERS.values(),
                        *_EVENT_COUNTERS.values(), _COMPILES_TOTAL):
        registry.inc(name, amount=0.0, help=help_)

    def on_duration(event: str, secs: float, **_) -> None:
        counter = _DURATION_COUNTERS.get(event)
        if counter is None:
            return
        registry.inc(counter[0], amount=secs)
        if event == _COMPILE_EVENT:
            registry.inc(_COMPILES_TOTAL[0])

    def on_event(event: str, **_) -> None:
        counter = _EVENT_COUNTERS.get(event)
        if counter is not None:
            registry.inc(counter[0])
            # the cache speaks on the compiling thread, inside the compile
            flight = getattr(_thread, "flight", None)
            if flight:
                flight[-1]["cache"] = _CACHE_VERDICTS[event]

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    if timeline:
        monitoring.register_scalar_listener(_entered)
        monitoring.register_event_time_span_listener(_left)


def compile_report() -> list[dict]:
    """The programs this process traced, lowered and compiled or loaded, in
    the order they began, the newest ``PROGRAM_ROWS`` of them: ``program``
    (JAX's name for it), ``trace_s``, ``lower_s``, ``compile_s`` (0 for a
    phase that did not run), ``cache`` (``hit``: loaded from the persistent
    cache; ``miss``: compiled and written to it; ``none``: compiled and not
    written, for want of a cache or of ``CACHE_MIN_COMPILE_SECS``; None: not
    compiled), ``start_s`` and ``end_s`` in the epoch seconds of ``obs.trace``'s
    spans, and ``nested_s``: seconds of the events inside this program's own,
    which its columns hold already and the ``pio_jit_*_seconds_total`` count
    once more. Empty under ``PIO_TRACING=0``."""
    return [dict(row) for row in list(_PROGRAMS)]


_backend_up = False


def ensure_backend(platform: str | None = None) -> str:
    """Initialise the configured JAX backend; returns its platform name.

    Raises ``RuntimeError`` naming the platform when it does not come up,
    and when nothing named a platform and JAX's own default landed on the
    CPU (no accelerator was found and nobody asked for the host).
    """
    # the first call's span is the set-up between the interpreter's start and
    # the first program: the import, the cache's place, the backend's start
    global _backend_up
    span = NULL_SPAN if _backend_up else global_tracer().span("backend.init")
    _backend_up = True
    with span:
        import jax

        want = platform or os.environ.get("PIO_PLATFORM")
        source = "pio.platform" if platform else "PIO_PLATFORM"
        if want:
            jax.config.update("jax_platforms", want)
        else:
            want = jax.config.jax_platforms
            source = "JAX_PLATFORMS"
        cache_dir = configure_compile_cache()
        count_compiles()
        try:
            devices = jax.devices()
        except RuntimeError as exc:
            raise RuntimeError(
                f"JAX platform {want or 'default'!r} (from {source}) did not"
                f" initialise: {exc}"
            ) from exc
        device = devices[0]
        span.set_attr("platform", device.platform)
        span.set_attr("devices", len(devices))
        span.set_attr("cache_dir", cache_dir)
    if not want and device.platform == "cpu":
        raise RuntimeError(
            "no accelerator found: JAX's default resolved to the CPU and no"
            " platform was named. Set PIO_PLATFORM=cpu (or JAX_PLATFORMS=cpu)"
            " to run on the host on purpose."
        )
    return device.platform


#: Pallas kernels this process has built, name -> "compiled" | "interpreted".
#: Written at trace time by the kernel wrappers in ``ops/`` and
#: ``models/ncf/kernel.py``; read by ``device_report``.
_KERNELS: dict[str, str] = {}


def note_kernel(name: str, interpret: bool) -> None:
    """Record that a Pallas kernel was built, and whether it runs through
    the interpreter (a CPU mesh) or as a compiled Mosaic call."""
    _KERNELS[name] = "interpreted" if interpret else "compiled"


#: blocked Cholesky solves (``ops/linalg.py``) traced by this process: one for
#: each block of an ALS program above rank 32 on a TPU mesh, none elsewhere
_SOLVES = {"blocked_solve": 0}


def note_blocked_solve() -> None:
    """Record that a program traced the blocked Cholesky solve."""
    _SOLVES["blocked_solve"] += 1


def device_report() -> dict:
    """What this process runs on: the device as JAX reports it, the Pallas
    kernels built, the blocked solves traced and the programs compiled so
    far (``programs``: of ``compile_report()``'s rows the count, the seconds
    by phase and the five longest compiles, each with what the cache said).
    ``pio train`` prints it, the query server serves it under ``GET /``, and
    ``chip_smoke.py`` takes its verdict from it -- a run on the wrong
    platform cannot pass for a chip run, and a deploy that compiled for half
    a minute says which program did."""
    import jax

    devices = jax.devices()
    rows = compile_report()
    compiled = sorted((row for row in rows if row["cache"] is not None),
                      key=lambda row: -row["compile_s"])
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "kernels": dict(_KERNELS),
        **_SOLVES,
        "programs": {
            "count": len(rows),
            **{c: round(sum(row[c] for row in rows), 3) for c in _COLUMNS},
            "longest_compiles": [
                {"program": row["program"], "compile_s": round(row["compile_s"], 3),
                 "cache": row["cache"]} for row in compiled[:5]],
        },
    }
