"""``device_idle_share``, read in a cell whose window is prefill-bound, where the
same quantity moves time to first token rather than the decode rate."""

from bench.metrics.device_idle_share import read  # noqa: F401
