"""Trace reduction: busy share, top operations and labelled idle gaps."""
from __future__ import annotations

import json

import pytest

from bench_helpers import ROOT
from bench import trace_reduce as tr

FIXTURE = ROOT / "tests" / "bench" / "fixtures" / "trace_v5e_decode.json"


def _planes(ops, marker_ns=1000.0):
    return [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [(tr_marker(), marker_ns, 5.0)]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [("jit_step", 0.0, 1e9)]},
            {"name": "XLA Ops", "events": ops}]},
    ]


def tr_marker():
    return "bench_clock_marker"


def test_synthetic_trace():
    # ops (name, start, dur) in ns; window [100, 1100)
    ops = [("fusion.1", 100.0, 150.0), ("dot.2", 250.0, 100.0),
           ("fusion.1", 500.0, 100.0), ("copy.3", 1050.0, 250.0),
           ("dot.2", 0.0, 50.0)]                      # before the window
    spans = [("plan", 340.0, 480.0), ("sync", 600.0, 990.0)]
    out = tr.reduce(_planes(ops), (100.0, 1100.0), spans)
    # busy: [100,350) + [500,600) + [1050,1100) = 400 ns of 1000
    assert out["busy_s"] == pytest.approx(400e-9)
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["device_ops"] == [["fusion.1", pytest.approx(250e-9)],
                                 ["dot.2", pytest.approx(100e-9)],
                                 ["copy.3", pytest.approx(50e-9)]]
    # gaps [350,500) -> plan (130 ns overlap), [600,1050) -> sync
    assert dict(out["idle_gaps"]) == {"plan": pytest.approx(150e-9),
                                      "sync": pytest.approx(450e-9)}
    assert tr.find_marker(_planes(ops), tr_marker()) == 1000.0


def test_nested_ops_count_self_time():
    # a loop [0, 100) holding two body ops; busy 100, loop's own time 40
    ops = [("%while", 0.0, 100.0), ("%fusion.1", 10.0, 30.0),
           ("%fusion.2", 50.0, 30.0), ("%copy", 150.0, 10.0)]
    out = tr.reduce(_planes(ops), (0.0, 200.0), [])
    assert out["busy_s"] == pytest.approx(110e-9)
    assert dict(out["device_ops"]) == {"%while": pytest.approx(40e-9),
                                       "%fusion.1": pytest.approx(30e-9),
                                       "%fusion.2": pytest.approx(30e-9),
                                       "%copy": pytest.approx(10e-9)}


def test_gap_with_no_span_is_labelled_so():
    out = tr.reduce(_planes([("a", 0.0, 10.0)]), (0.0, 100.0), [])
    assert out["idle_gaps"] == [[tr.NO_SPAN, pytest.approx(90e-9)]]


def test_trace_without_device_ops_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce([{"name": "/host:CPU", "lines": []}], (0.0, 1.0))


def test_recorded_chip_trace():
    """A slice of a traced window of ``opt-1.3b.decode`` on a TPU v5e."""
    planes = json.loads(FIXTURE.read_text())
    ops = tr.device_ops(planes)[0]
    lo = min(s for _, s, _ in ops)
    hi = max(s + d for _, s, d in ops)
    marker = tr.find_marker(planes, tr_marker())
    assert marker is not None
    out = tr.reduce(planes, (lo, hi), [("step", lo, hi)])
    busy = sum(b - a for a, b in tr.union([(s, s + d) for _, s, d in ops]))
    assert out["busy_s"] == pytest.approx(busy / 1e9)
    assert 0 < out["busy_s"] <= out["window_s"]
    gaps = sum(s for _, s in out["idle_gaps"])
    assert gaps == pytest.approx(out["window_s"] - out["busy_s"])
    assert [n for n, _ in out["idle_gaps"]] in ([], ["step"])
    assert all(" = " not in n and n.startswith("%")
               for n, _ in out["device_ops"])
    times = [t for _, t in out["device_ops"]]
    assert times == sorted(times, reverse=True) and len(times) <= 10
    # self times add up to busy time when every op nests in the ops line
    all_self = tr.self_times(ops, lo, hi)
    assert sum(all_self.values()) / 1e9 == pytest.approx(out["busy_s"],
                                                         rel=1e-6)
