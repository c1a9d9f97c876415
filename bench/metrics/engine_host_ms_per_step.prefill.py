"""``engine_host_ms_per_step``, read in a cell whose window is prefill-bound, where the
same quantity moves time to first token rather than the decode rate."""

from bench.metrics.engine_host_ms_per_step import read  # noqa: F401
