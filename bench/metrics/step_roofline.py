"""Whole-step share of the roofline: the least time each step of the
window could take on this chip (``bench/counts.py``: the larger of its
operations over the bf16 peak and its bytes over HBM bandwidth), summed
over the steps, divided by the device's busy time in the trace."""

from bench import counts


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0 or not run.steps:
        return None
    least = sum(counts.least_seconds(run.sizes, s.chunks, s.sampled,
                                     run.peaks) for s in run.steps)
    return 100.0 * least / run.trace["busy_s"]
