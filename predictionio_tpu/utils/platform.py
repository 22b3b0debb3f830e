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
children all share one.
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
    return path


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


def device_report() -> dict:
    """What this process runs on: the device as JAX reports it and the
    Pallas kernels built so far. ``pio train`` prints it, the query server
    serves it under ``GET /``, and ``chip_smoke.py`` takes its verdict from
    it -- a run on the wrong platform cannot pass for a chip run."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "kernels": dict(_KERNELS),
    }
