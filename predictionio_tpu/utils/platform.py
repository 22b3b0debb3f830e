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
children all share one, and starts the ``pio_jit_*`` counters
(``count_compiles``).
"""

from __future__ import annotations

import logging
import os

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

_counting = False


def count_compiles() -> None:
    """Feed JAX's compile events into ``utils.metrics.global_registry()``,
    which every service's ``/metrics`` merges in: a query server that
    recompiles on a batch shape it has not seen shows as a counter that
    climbs. Registers once a process; the counters start at 0 so that a
    scrape shows them before the first compile."""
    global _counting
    if _counting:
        return
    _counting = True
    from jax import monitoring

    from predictionio_tpu.utils.metrics import global_registry

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

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def ensure_backend(platform: str | None = None) -> str:
    """Initialise the configured JAX backend; returns its platform name.

    Raises ``RuntimeError`` naming the platform when it does not come up,
    and when nothing named a platform and JAX's own default landed on the
    CPU (no accelerator was found and nobody asked for the host).
    """
    import jax

    want = platform or os.environ.get("PIO_PLATFORM")
    source = "pio.platform" if platform else "PIO_PLATFORM"
    if want:
        jax.config.update("jax_platforms", want)
    else:
        want = jax.config.jax_platforms
        source = "JAX_PLATFORMS"
    configure_compile_cache()
    count_compiles()
    try:
        device = jax.devices()[0]
    except RuntimeError as exc:
        raise RuntimeError(
            f"JAX platform {want or 'default'!r} (from {source}) did not"
            f" initialise: {exc}"
        ) from exc
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
    kernels built and the blocked solves traced so far. ``pio train`` prints
    it, the query server serves it under ``GET /``, and ``chip_smoke.py``
    takes its verdict from it -- a run on the wrong platform cannot pass for
    a chip run."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "kernels": dict(_KERNELS),
        **_SOLVES,
    }
