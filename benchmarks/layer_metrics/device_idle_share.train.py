"""Share of the traced window in which no operation ran on the device."""

from benchmarks.layer_metrics._idle import idle_share_pct as read  # noqa: F401
