"""RuntimeContext: the SparkContext replacement.

Behavioral model: reference ``core/.../workflow/WorkflowContext.scala`` +
``WorkflowParams.scala`` (apache/predictionio layout, unverified -- SURVEY.md
section 2.3 #24). Where the reference builds a SparkContext from ``sparkConf``
passthrough, we build a :class:`jax.sharding.Mesh` from the engine.json
runtime section (kept under the ``sparkConf`` key for byte-compatibility,
also accepted as ``runtimeConf``).

Mesh conventions: axes named ``("data", "model")``. ``mesh_shape`` of
``[-1, 1]`` (default) puts all devices on the data axis. Multi-host entry
uses ``jax.distributed.initialize`` when ``PIO_COORDINATOR`` is set.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Any, Mapping

logger = logging.getLogger("pio.workflow")


def _maybe_int(value) -> int | None:
    return None if value is None else int(value)


@dataclass
class WorkflowParams:
    """Train-workflow knobs (reference WorkflowParams)."""

    batch: str = ""
    verbose: int = 2
    skip_sanity_check: bool = False
    stop_after_read: bool = False
    stop_after_prepare: bool = False
    #: `pio train --resume`: reuse the variant's latest non-COMPLETED
    #: EngineInstance and continue from its step checkpoints instead of
    #: starting over (SURVEY.md section 5.3/5.4 -- the reference has no
    #: mid-training resume; on TPU preemption safety requires it)
    resume: bool = False


class RuntimeContext:
    """Carries the device mesh + runtime conf through DASE calls.

    Built lazily: importing jax is deferred until a mesh is actually needed
    so storage/CLI paths stay fast.
    """

    def __init__(
        self,
        runtime_conf: Mapping[str, Any] | None = None,
        instance_id: str | None = None,
        run_key: str | None = None,
        resume: bool = False,
    ):
        self.runtime_conf: dict[str, Any] = dict(runtime_conf or {})
        #: engine-instance id of the current run (set by the train workflow)
        self.instance_id = instance_id
        #: stable checkpoint key: hash of (variant id, version, params) --
        #: UNLIKE instance_id it survives re-running `pio train`, so a
        #: resumed run finds the crashed run's checkpoints
        self.run_key = run_key
        #: True on `pio train --resume`: checkpoint_manager keeps existing
        #: checkpoints; a fresh train wipes them (stale checkpoints must not
        #: silently short-circuit a from-scratch retrain)
        self.resume = resume
        #: per-stage wall-clock seconds, filled by Engine.train (the
        #: observability the reference delegated to the Spark UI, SURVEY 5.1)
        self.timings: dict[str, float] = {}
        self._mesh = None

    def checkpoint_manager(self, name: str):
        """Step-checkpoint manager for an algorithm (orbax-backed), or None.

        Keyed on the stable run_key so `pio train --resume` after a crash
        finds the previous attempt's checkpoints. On a NON-resume run any
        existing checkpoints under the key are deleted first. Contexts
        without a run key (evaluation grid candidates, ad-hoc programmatic
        trains) get None -- those runs are not resumable, and a shared
        fallback key would make concurrent trains race on one directory.
        Programmatic callers who want checkpoints pass an explicit
        ``run_key`` to RuntimeContext.
        """
        key = self.run_key or self.instance_id
        if key is None:
            return None
        from predictionio_tpu.parallel.distributed import launch_process_id

        if launch_process_id(self.runtime_conf) != 0:
            # multi-process launch: rank 0 owns the checkpoint dir; a
            # second writer on the same key would corrupt its steps
            return None
        from predictionio_tpu.workflow.checkpoint import CheckpointManager

        return CheckpointManager(f"{name}-{key}", fresh=not self.resume)

    # -- mesh construction --------------------------------------------------
    @property
    def mesh(self):
        if self._mesh is None:
            self._mesh = self._build_mesh()
        return self._mesh

    def _build_mesh(self):
        from predictionio_tpu.parallel.distributed import build_mesh, init_distributed

        # multi-host pod: one process per host, coordinator from runtime
        # conf (-- --coordinator host:port) or PIO_COORDINATOR env; XLA
        # collectives over ICI/DCN (parallel.distributed)
        init_distributed(
            coordinator=self.runtime_conf.get("pio.coordinator"),
            num_processes=_maybe_int(self.runtime_conf.get("pio.num_processes")),
            process_id=_maybe_int(self.runtime_conf.get("pio.process_id")),
        )
        from predictionio_tpu.utils.platform import ensure_backend

        # the configured platform comes up or the train fails: no fallback
        ensure_backend(self.runtime_conf.get("pio.platform"))
        return build_mesh(
            self.runtime_conf.get("pio.mesh_shape", [-1, 1]),
            tuple(self.runtime_conf.get("pio.mesh_axes", ("data", "model"))),
            dcn_mesh_shape=self.runtime_conf.get("pio.dcn_mesh_shape"),
        )

    @property
    def num_devices(self) -> int:
        return self.mesh.devices.size

    def conf(self, key: str, default: Any = None) -> Any:
        return self.runtime_conf.get(key, default)
