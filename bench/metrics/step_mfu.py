"""Model FLOP/s utilization of the whole step: the operations the
window's steps need, from shapes (``bench/counts.py``), over the window's
seconds and the chip's bf16 peak."""

from bench import counts


def read(run):
    if not run.steps:
        return None
    flops = sum(counts.step_flops(run.sizes, s.chunks, s.sampled)
                for s in run.steps)
    return 100.0 * flops / (run.window_s * run.peaks["bf16_flops"])
