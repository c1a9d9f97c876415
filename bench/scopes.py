"""Device time by the serving step's named scopes, from a profiler trace.

The program names the parts of its compiled step with ``jax.named_scope``
(``serving/engine.py``, ``models/dense.py``, ``core/ecc.py``): ``embed``;
``layers``, the scan over layers, holding ``attn`` (``qkv``, ``core``,
``out``) and ``ffn``; ``ecc`` wherever a flash-tier weight is checked, so it
nests under the matmul that reads the weight; then ``lm_head``, ``sample``,
``kv_write`` and ``alg2``. XLA keeps an op's scope path in its ``op_name``
metadata, and the profile keeps each module's optimized HLO, metadata
included (``bench/xspace.py``). Each engine step is a ``serve_step`` step
annotation on the profiler's host clock.

* ``load`` reads the newest ``.xplane.pb`` under a directory into plain data
  (the form the recorded fixture keeps): ``devices``, for each device plane
  its op events ``(op, start_ns, duration_ns)`` and ``paths``, op ->
  ``op_name``; and ``steps``, the ``(start_ns, end_ns, step_num)`` of every
  ``serve_step`` annotation;
* ``step_window`` places the run's measured steps on the profiler's clock:
  the first is the annotation numbered as the engine's step count read just
  before the window (``engine_steps_total``; the engine is idle then), and
  the others have to start where their annotations do;
* ``reduce`` gives busy time and self time per scope in a window: an op's
  self time (its duration less that of the ops nested in it, as a loop
  holds its body's ops) counts toward every scope on its path, ``top``
  splits busy time by the outermost scope, and an op on no scope's path
  counts as ``unscoped``;
* ``of_run`` does all three once for a ``--trace 1`` run, over the span of
  its measured steps, and keeps the result on the run view for the other
  readers. It gives None where the program recorded no step annotation or
  no scope.
"""
from __future__ import annotations

import bisect
import glob
import os
from pathlib import Path

from bench import trace_reduce, xspace

SCOPES = ("embed", "layers", "attn", "qkv", "core", "out", "ffn", "ecc",
          "lm_head", "sample", "kv_write", "alg2")
UNSCOPED = "unscoped"
STEP_ANNOTATION = "serve_step"
MODULES_LINE = "XLA Modules"
TRACES = Path(__file__).resolve().parents[1] / ".bench_runs"
MATCH_NS = 1e6          # a run's step start lies within 1 ms of its marker


def scope_chain(path: str) -> list[str]:
    """The scopes on an op's path, outermost first, each once."""
    out: list[str] = []
    for part in path.split("/"):
        if part in SCOPES and part not in out:
            out.append(part)
    return out


def _device_plane(plane, op_names: dict) -> dict | None:
    lines = {line.name: line for line in plane.lines}
    if trace_reduce.OPS_LINE not in lines:
        return None
    mods = sorted((float(ev.start_ns), ev.name)
                  for ev in (lines[MODULES_LINE].events
                             if MODULES_LINE in lines else ()))
    starts = [s for s, _ in mods]
    events, paths = [], {}
    for ev in lines[trace_reduce.OPS_LINE].events:
        start = float(ev.start_ns)
        i = bisect.bisect_right(starts, start) - 1
        module = mods[i][1] if i >= 0 else ""
        short = trace_reduce.op_name(ev.name)
        op = f"{module}/{short}"
        if op not in paths:
            paths[op] = op_names.get(module, {}).get(short.lstrip("%"), "")
        events.append((op, start, float(ev.duration_ns)))
    return {"name": plane.name, "events": events, "paths": paths}


def load(trace_dir: str) -> dict:
    """``{"devices": [...], "steps": [...]}`` of the newest ``.xplane.pb``
    under ``trace_dir``. An op is named ``<module>/<short op name>`` (short
    op names repeat across modules), its module the ``XLA Modules`` event
    it starts in."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(files[-1], "rb") as f:
        raw = f.read()
    op_names = xspace.hlo_op_names(raw)
    devices, steps = [], []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            dev = _device_plane(plane, op_names)
            if dev is not None:
                devices.append(dev)
            continue
        for line in plane.lines:
            steps.extend((float(ev.start_ns), float(ev.end_ns),
                          int(dict(ev.stats).get("step_num", -1)))
                         for ev in line.events
                         if ev.name == STEP_ANNOTATION)
    return {"devices": sorted(devices, key=lambda p: p["name"]),
            "steps": sorted(steps)}


def step_window(marked: list[tuple[float, float, int]], run_steps,
                first_num: int, probe: int = 20
                ) -> tuple[float, float] | None:
    """``(start, end)`` on the profiler's clock of the span of
    ``run_steps`` (host ``perf_counter`` seconds), the first of which is
    engine step ``first_num``: its ``serve_step`` annotation in ``marked``
    gives the clock offset, and at least half of the first ``probe`` run
    steps must then start within ``MATCH_NS`` of an annotation. None
    otherwise."""
    first = next((s for s, _, n in marked if n == first_num), None)
    if first is None or not run_steps:
        return None
    offset = first - run_steps[0].t0 * 1e9
    starts = [s for s, _, _ in marked]
    hits = 0
    for step in run_steps[:probe]:
        t = step.t0 * 1e9 + offset
        i = bisect.bisect_left(starts, t - MATCH_NS)
        hits += i < len(starts) and starts[i] <= t + MATCH_NS
    if 2 * hits < min(len(run_steps), probe):
        return None
    return (first, run_steps[-1].t1 * 1e9 + offset)


def reduce(devices: list[dict], window_ns: tuple[float, float]) -> dict:
    """``busy_s``, ``scopes`` (seconds under each scope, nested scopes
    counted in each enclosing one, plus ``unscoped``) and ``top`` (busy
    seconds by outermost scope), each a mean over the devices, inside
    ``window_ns``."""
    if not devices:
        raise ValueError("trace has no device op events")
    lo, hi = window_ns
    busy = 0.0
    scopes: dict[str, float] = {}
    top: dict[str, float] = {}
    for plane in devices:
        events = plane["events"]
        ivals = [(max(s, lo), min(s + d, hi)) for _, s, d in events
                 if min(s + d, hi) > max(s, lo)]
        busy += sum(b - a for a, b in trace_reduce.union(ivals))
        for op, t in trace_reduce.self_times(events, lo, hi).items():
            chain = scope_chain(plane["paths"].get(op, ""))
            for name in chain or [UNSCOPED]:
                scopes[name] = scopes.get(name, 0.0) + t
            outer = chain[0] if chain else UNSCOPED
            top[outer] = top.get(outer, 0.0) + t
    n = len(devices)
    return {"busy_s": busy / n / 1e9,
            "scopes": {k: v / n / 1e9 for k, v in scopes.items()},
            "top": {k: v / n / 1e9 for k, v in top.items()}}


def of_run(run, traces: Path = TRACES) -> dict | None:
    """The scope reduction of the run's trace (the newest under
    ``traces``) over the span of its measured steps, or None where there is
    no trace, no step annotation to place the steps by, or no scope."""
    if "scopes" not in run.__dict__:
        red = None
        if getattr(run, "trace", None) is not None:
            try:
                trace = load(str(traces))
                window = step_window(
                    trace["steps"], run.steps,
                    int(run.server_before.get("engine_steps_total", -1)))
                if window is not None:
                    red = reduce(trace["devices"], window)
            except (FileNotFoundError, ValueError):
                red = None
        if red is not None and set(red["scopes"]) <= {UNSCOPED}:
            red = None
        run.scopes = red
    return run.scopes
