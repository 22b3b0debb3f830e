"""Continuous learning: WAL tail -> snapshot refresh -> fold-in -> hot swap.

The batch stack can ingest durably (``data/ingest``), replay training data
at memmap speed (``data/snapshot``), solve ALS half-steps on the device
(``parallel/als``), and serve through a supervised process tier
(``serving/``) -- but an event ingested now is invisible to queries until
someone reruns ``pio train`` and redeploys. This package closes that loop
as ``pio retrain --follow``:

- :mod:`online.follower` tails the ingest WAL from a durable cursor, so
  "did anything new land, and for whom?" never rescans SQL;
- :mod:`online.foldin` solves ONLY the touched user rows against frozen
  item factors (ALX, arxiv 2112.02194: the per-row ALS solve is cheap
  enough to run over just the delta), with a staleness budget that
  escalates to a full retrain when drift gets too large;
- :mod:`online.registry` stores every produced model as an immutable,
  CRC-guarded, monotonically versioned generation with instant rollback;
- :mod:`online.loop` orchestrates the cycle and hot-swaps each version
  into running query servers with zero dropped or mixed-version requests
  (the swap-epoch protocol in ``workflow/create_server``).

Crash anywhere recovers from the cursor + registry manifests: the cursor
only advances past records whose model version was published AND swapped,
and fold-in re-derives touched users' factors from their FULL history, so
overlapping replay windows are harmless by construction.
"""

from predictionio_tpu.online.follower import TailCursor, WalTail
from predictionio_tpu.online.foldin import FoldinDelta, StalenessBudget, fold_in_users
from predictionio_tpu.online.registry import ModelRegistry, RegistryError
from predictionio_tpu.online.loop import RetrainConfig, RetrainLoop

__all__ = [
    "FoldinDelta",
    "ModelRegistry",
    "RegistryError",
    "RetrainConfig",
    "RetrainLoop",
    "StalenessBudget",
    "TailCursor",
    "WalTail",
    "fold_in_users",
]
