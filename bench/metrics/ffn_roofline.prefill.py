"""``ffn_roofline``, read in a cell whose window is prefill-bound, where the
same quantity moves time to first token rather than the decode rate."""

from bench.metrics.ffn_roofline import read  # noqa: F401
