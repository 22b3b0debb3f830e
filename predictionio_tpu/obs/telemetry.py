"""Per-step training telemetry journal (``pio train --profile``).

The ALX paper (arxiv 2112.02194) treats per-step achieved bandwidth as
the primary training metric; the ``jax.profiler`` trace gives the deep
view but needs tensorboard/xprof to open. This journal is the cheap,
always-parseable companion: one JSON line per training step with wall
time, edges/sec, and the achieved HBM GB/s implied by the bytes-moved
model (``parallel.als.modeled_bytes_per_iteration``), plus the process's
count of compilations (``pio_jit_compiles_total``) so a shape-instability
regression (recompiling every step) is visible as a climbing integer
instead of a mysteriously slow run.

Lines are flushed as written: a crashed or preempted run keeps every
completed step's record.
"""

from __future__ import annotations

import json
import os
import time


class TrainTelemetry:
    """JSONL step journal. First line is a ``meta`` record (edge count,
    modeled bytes/iter, run shape); each ``record_step`` appends a
    ``step`` record. Single-writer (the training loop)."""

    def __init__(
        self,
        path: str,
        *,
        edges: int | None = None,
        modeled_bytes_per_iter: float | None = None,
        meta: dict | None = None,
    ):
        self.path = path
        self.edges = edges
        self.modeled_bytes_per_iter = modeled_bytes_per_iter
        self.steps = 0
        # a loop that was not started through ``ensure_backend`` still gets
        # its compilations counted from the journal's first line on
        from predictionio_tpu.utils.platform import count_compiles

        count_compiles()
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._f = open(path, "w")
        self._write(
            {
                "event": "meta",
                "edges": edges,
                "modeled_bytes_per_iter": modeled_bytes_per_iter,
                **(meta or {}),
            }
        )

    def _write(self, obj: dict) -> None:
        obj["ts"] = round(time.time(), 3)
        self._f.write(json.dumps(obj) + "\n")
        self._f.flush()

    def record_step(
        self,
        step: int,
        wall_s: float,
        *,
        recompile_count: int | None = None,
        extra: dict | None = None,
    ) -> dict:
        """Append one step record; returns the object written."""
        obj: dict = {
            "event": "step",
            "step": int(step),
            "wall_s": round(float(wall_s), 6),
        }
        if self.edges is not None and wall_s > 0:
            obj["edges_per_sec"] = round(self.edges / wall_s, 1)
        if self.modeled_bytes_per_iter is not None and wall_s > 0:
            obj["achieved_gbps"] = round(
                self.modeled_bytes_per_iter / wall_s / 1e9, 3
            )
        if recompile_count is not None:
            obj["recompile_count"] = int(recompile_count)
        if extra:
            obj.update(extra)
        self._write(obj)
        self.steps += 1
        return obj

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None

    def __enter__(self) -> "TrainTelemetry":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def compiles_so_far() -> int:
    """Programs this process has compiled or loaded from the persistent
    cache since its counters started (``pio_jit_compiles_total``): the
    journal's ``recompile_count``."""
    from predictionio_tpu.utils.metrics import global_registry

    return int(global_registry().counter_value("pio_jit_compiles_total"))
