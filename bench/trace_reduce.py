"""Reduce a profiler trace of the measured window to device metrics.

``load_xplane`` turns the JAX profiler's ``.xplane.pb`` into plain data:
planes of lines of ``(name, start_ns, duration_ns)`` events. ``reduce``
works on that data only, so a small recorded trace kept as a test fixture
checks it without a chip.

* busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), inside the
  window, averaged over the devices used;
* top operations by summed self time (an op's time less that of the ops
  nested in it, as a loop holds its body's ops), so the list adds up to
  busy time;
* idle gaps: the window minus busy, each gap labelled by the host span
  that overlaps it most (the program's step-phase spans, put on the
  profiler's clock through a marker annotation taken at a known host
  ``perf_counter`` reading), summed per label.
"""
from __future__ import annotations

import bisect
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
NO_SPAN = "no step span"


def load_xplane(trace_dir: str) -> list[dict]:
    """Planes -> lines -> events of the newest ``.xplane.pb`` under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name,
                          "events": [(ev.name, float(ev.start_ns),
                                      float(ev.duration_ns))
                                     for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def find_marker(planes: list[dict], name: str) -> float | None:
    """Start (profiler ns) of the first host event called ``name``."""
    for plane in planes:
        if plane["name"].startswith(DEVICE_PREFIX):
            continue
        for line in plane["lines"]:
            for ev_name, start, _ in line["events"]:
                if ev_name == name:
                    return start
    return None


def op_name(name: str) -> str:
    """An HLO op event's short name: ``%fusion.12 = f32[...] ...`` ->
    ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def self_times(events, lo: float, hi: float) -> dict[str, float]:
    """Summed self time per short op name inside [lo, hi): each event's
    clipped duration less the clipped durations of events nested in it."""
    out: dict[str, float] = {}
    stack: list[list] = []          # [end, name, self]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        a, b = max(s, lo), min(s + d, hi)
        while stack and stack[-1][0] <= s:
            _, n, t = stack.pop()
            out[n] = out.get(n, 0.0) + t
        own = max(b - a, 0.0)
        if stack:
            stack[-1][2] -= own
        stack.append([s + d, op_name(name), own])
    for _, n, t in stack:
        out[n] = out.get(n, 0.0) + t
    return {n: t for n, t in out.items() if t > 0}


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _gaps(busy, lo: float, hi: float):
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


class _Spans:
    """Host spans sorted by start, for the most-overlapping lookup."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda s: s[1])
        self.starts = [s[1] for s in self.spans]
        self.longest = max((b - a for _, a, b in self.spans), default=0.0)

    def label(self, gap) -> str:
        best, best_overlap = NO_SPAN, 0.0
        i = bisect.bisect_left(self.starts, gap[1])
        while i > 0:
            i -= 1
            name, a, b = self.spans[i]
            if a < gap[0] - self.longest:
                break
            ov = min(b, gap[1]) - max(a, gap[0])
            if ov > best_overlap:
                best, best_overlap = name, ov
        return best


def device_ops(planes: list[dict]) -> list[list[tuple[str, float, float]]]:
    """The op events of each device plane, devices in plane-name order."""
    devs = sorted((p for p in planes if p["name"].startswith(DEVICE_PREFIX)),
                  key=lambda p: p["name"])
    out = []
    for plane in devs:
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                out.append(line["events"])
                break
    return out


def reduce(planes: list[dict], window_ns: tuple[float, float],
           host_spans: list[tuple[str, float, float]] = (),
           n_devices: int = 1, top: int = 10) -> dict:
    """Device busy seconds, top ops and labelled idle gaps in the window.

    ``host_spans`` are ``(name, start_ns, end_ns)`` on the profiler clock.
    Returns ``busy_s`` (mean over the first ``n_devices`` devices),
    ``window_s``, ``device_ops`` and ``idle_gaps`` as ``[name, seconds]``
    lists of at most ``top`` entries each."""
    lo, hi = window_ns
    per_dev = device_ops(planes)[:n_devices]
    if not per_dev:
        raise ValueError(f"trace has no {OPS_LINE!r} line on a "
                         f"{DEVICE_PREFIX}* plane")
    busy_total = 0.0
    op_time: dict[str, float] = {}
    gap_time: dict[str, float] = {}
    spans = _Spans([(n, a, b) for n, a, b in host_spans if b > lo and a < hi])
    for events in per_dev:
        ivals = _clip([(s, s + d) for _, s, d in events], lo, hi)
        busy = union(ivals)
        busy_total += sum(b - a for a, b in busy)
        for name, t in self_times(events, lo, hi).items():
            op_time[name] = op_time.get(name, 0.0) + t
        for gap in _gaps(busy, lo, hi):
            label = spans.label(gap)
            gap_time[label] = gap_time.get(label, 0.0) + (gap[1] - gap[0])
    n = len(per_dev)

    def ranked(d):
        items = sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns / n / 1e9] for name, ns in items]

    return {"busy_s": busy_total / n / 1e9, "window_s": (hi - lo) / 1e9,
            "device_ops": ranked(op_time), "idle_gaps": ranked(gap_time)}
