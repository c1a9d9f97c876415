"""Model FLOP/s utilization of the sparse-expert step: the operations the
window's steps need, from shapes and token counts alone
(``bench/moe_counts.py``: GQA projections and attention products, the
router, top-k assignments through three expert matrices, ``lm_head`` for
sampled tokens), over the window's seconds and the chip's bf16 peak.
None for a configuration with no expert bank."""

from bench import moe_counts


def read(run):
    sz = moe_counts.full_sizes(run.config)
    if sz is None or not run.steps:
        return None
    flops = sum(moe_counts.step_flops(sz, s.chunks, s.sampled)
                for s in run.steps)
    return 100.0 * flops / (run.window_s * run.peaks["bf16_flops"])
