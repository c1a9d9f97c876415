"""Device time under the sparse-expert step's scopes, from a profiler trace.

``bench/scopes.py`` reduces a trace by the scopes it names; the MoE layer
adds two of its own (``models/moe.py``, ``serving/engine.py``): ``route``
(router logits, top-k, the sort of assignments by slab row, the gather of
their inputs and the gate-weighted combine) and ``experts`` (the routed
experts' weight reads, their inline ECC, and the grouped products). This
module reads the same trace over the same measured steps with those two
scopes added, and keeps the result on the run view. It gives None where
the program recorded neither scope (a program that predates them).

The TPU compiler rewrites each ``lax.ragged_dot`` into custom calls
(``ragged-dot-metadata``, ``ragged-dot-none``) whose op_name has lost the
scope path; the program's only ragged dots are the expert products, so
an op so named with no MoE scope on its path counts under ``experts``.
"""
from __future__ import annotations

from pathlib import Path

from bench import scopes, trace_reduce

MOE_SCOPES = ("route", "experts")
TRACES = scopes.TRACES


def reduce(devices: list[dict], window_ns: tuple[float, float]) -> dict:
    """``busy_s`` and ``scopes``: seconds under each of ``MOE_SCOPES``
    (nested scopes counted in each enclosing one), means over devices."""
    if not devices:
        raise ValueError("trace has no device op events")
    lo, hi = window_ns
    busy = 0.0
    out: dict[str, float] = {}
    for plane in devices:
        events = plane["events"]
        ivals = [(max(s, lo), min(s + d, hi)) for _, s, d in events
                 if min(s + d, hi) > max(s, lo)]
        busy += sum(b - a for a, b in trace_reduce.union(ivals))
        for op, t in trace_reduce.self_times(events, lo, hi).items():
            parts = set(plane["paths"].get(op, "").split("/"))
            if "ragged-dot" in op and not parts & set(MOE_SCOPES):
                parts = {"experts"}
            for name in MOE_SCOPES:
                if name in parts:
                    out[name] = out.get(name, 0.0) + t
    n = len(devices)
    return {"busy_s": busy / n / 1e9,
            "scopes": {k: v / n / 1e9 for k, v in out.items()}}


def of_run(run, traces: Path = TRACES) -> dict | None:
    """The MoE scope reduction of the run's trace over the span of its
    measured steps, or None (no trace, no step annotation, no MoE scope)."""
    if "moe_scopes" not in run.__dict__:
        red = None
        if getattr(run, "trace", None) is not None:
            try:
                trace = scopes.load(str(traces))
                window = scopes.step_window(
                    trace["steps"], run.steps,
                    int(run.server_before.get("engine_steps_total", -1)))
                if window is not None:
                    red = reduce(trace["devices"], window)
            except (FileNotFoundError, ValueError):
                red = None
        if red is not None and not ({"route", "experts"}
                                    & set(red["scopes"])):
            red = None
        run.moe_scopes = red
    return run.moe_scopes
