"""The routed experts' share of their roofline: the least time the
window's expert products could take on this chip
(``bench/moe_counts.py``: the larger of the assignments' operations over
the bf16 peak and the distinct routed experts' int8 weights, parity and
scales over HBM bandwidth, from the program's counters
``engine_moe_assignments_total`` and ``engine_moe_experts_routed_total``),
over the device time under the ``experts`` scope while the measured
steps ran (``bench/moe_scopes.py``; its nested ECC included)."""

from bench import moe_counts, moe_scopes


def read(run):
    sz = moe_counts.full_sizes(run.config)
    red = moe_scopes.of_run(run)
    if sz is None or red is None or not red["scopes"].get("experts"):
        return None
    assigned = run.counter_delta("engine_moe_assignments_total")
    routed = run.counter_delta("engine_moe_experts_routed_total")
    if assigned <= 0 or routed <= 0:
        return None
    least = moe_counts.expert_least_seconds(sz, assigned, routed, run.peaks)
    return 100.0 * least / red["scopes"]["experts"]
