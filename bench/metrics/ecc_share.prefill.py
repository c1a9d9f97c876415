"""``ecc_share``, read in a cell whose window is prefill-bound, where the
same quantity moves time to first token rather than the decode rate."""

from bench.metrics.ecc_share import read  # noqa: F401
