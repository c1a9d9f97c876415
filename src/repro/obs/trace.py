"""Chrome ``trace_event`` span tracer for the serving stack.

The paper's pipelining story — group l+1's NAND pages streaming while
group l's compute runs, pool uploads riding the prefetch worker, router
bitmaps syncing mid-step — is an OVERLAP claim, and overlap is only
checkable on a timeline. This tracer records spans onto fixed tracks
(compute / stream / pool / NAND / requests) and exports them in the
Chrome trace-event JSON format, loadable in ``chrome://tracing`` or
Perfetto: stacked "X" (complete) events per track, named via "M"
(metadata) events.

Design points:

  * Disabled by default (``Tracer(enabled=False)``): ``complete()``
    returns immediately — the hot path pays an attribute check.
  * The program times its own spans and hands them over pre-timed
    (``complete()``, a ``perf_counter`` start and a duration): engine step
    phases also open a ``jax.profiler.TraceAnnotation`` (serving/engine.py),
    so under a profiler session the same phases land in the device trace's
    clock, and this module stays free of jax.
  * Bounded: events land in a ``deque(maxlen=...)`` ring, so a
    long-lived server traces the LAST N events, never unbounded memory.
  * The exported file is a JSON array written ONE EVENT PER LINE — valid
    Chrome/Perfetto trace JSON and line-greppable (the CI schema check
    parses it whole, then validates every event dict).
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque

__all__ = ["Tracer", "default_tracer", "set_default_tracer",
           "TID_COMPUTE", "TID_STREAM", "TID_POOL", "TID_NAND",
           "TID_REQUEST0"]

# Fixed track ids (Chrome "tid"): one per serving-stack plane. Requests
# get their own rolling band so concurrent requests render side by side.
TID_COMPUTE = 1          # engine step phases (host dispatch view)
TID_STREAM = 2           # streamer / prefetcher fetch work
TID_POOL = 3             # page-pool staged uploads (per-shard)
TID_NAND = 4             # PageStore page reads (per-plane args)
TID_REQUEST0 = 100       # request lifecycle spans: 100 + (rid % width)

_TRACK_NAMES = {
    TID_COMPUTE: "engine.compute",
    TID_STREAM: "weight.stream",
    TID_POOL: "pool.upload",
    TID_NAND: "nand.read",
}
_REQUEST_TRACKS = 8      # rid % 8 request lanes


class Tracer:
    """Bounded, thread-safe trace-event recorder (one per process by
    default — ``default_tracer()``)."""

    def __init__(self, enabled: bool = False, max_events: int = 200_000):
        self.enabled = bool(enabled)
        self._events: deque = deque(maxlen=int(max_events))
        self._lock = threading.Lock()
        # one origin for the whole trace: perf_counter is monotonic but
        # epoch-free, so every ts is relative to tracer creation.
        self._t0 = time.perf_counter()

    # --- recording -----------------------------------------------------------

    def _us(self, t: float) -> float:
        return (t - self._t0) * 1e6

    def complete(self, name: str, t0: float, dur_s: float,
                 tid: int = TID_COMPUTE, cat: str = "",
                 args: dict | None = None):
        """Record a pre-timed span (Chrome "X" event). ``t0`` is a
        ``perf_counter`` reading; ``dur_s`` seconds."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "X", "pid": 0, "tid": int(tid),
              "ts": self._us(t0), "dur": max(dur_s, 0.0) * 1e6}
        if cat:
            ev["cat"] = cat
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def request_tid(self, rid: int) -> int:
        return TID_REQUEST0 + int(rid) % _REQUEST_TRACKS

    # --- export --------------------------------------------------------------

    def _meta_events(self) -> list[dict]:
        names = dict(_TRACK_NAMES)
        for i in range(_REQUEST_TRACKS):
            names[TID_REQUEST0 + i] = f"requests.{i}"
        return [{"name": "thread_name", "ph": "M", "pid": 0, "tid": tid,
                 "ts": 0, "args": {"name": label}}
                for tid, label in sorted(names.items())]

    def events(self) -> list[dict]:
        """Snapshot: metadata (track-name) events + recorded events in
        arrival order."""
        with self._lock:
            recorded = list(self._events)
        return self._meta_events() + recorded

    def export(self, path: str) -> int:
        """Write the Chrome trace JSON (array form, one event per line).
        Returns the number of events written (metadata included)."""
        events = self.events()
        with open(path, "w") as f:
            f.write("[\n")
            for i, ev in enumerate(events):
                tail = "," if i + 1 < len(events) else ""
                f.write(json.dumps(ev, sort_keys=True) + tail + "\n")
            f.write("]\n")
        return len(events)

    def clear(self):
        with self._lock:
            self._events.clear()
        self._t0 = time.perf_counter()


_default_lock = threading.Lock()
_default: Tracer | None = None


def default_tracer() -> Tracer:
    """The process-wide tracer the stack records into. DISABLED until
    something (``serve --trace-out``, a test) enables it — tracing is a
    debugging tool, not an always-on cost."""
    global _default
    with _default_lock:
        if _default is None:
            _default = Tracer(enabled=False)
        return _default


def set_default_tracer(tr: Tracer) -> Tracer:
    global _default
    with _default_lock:
        prev, _default = _default, tr
    return prev if prev is not None else Tracer()
