"""ObsPlane inside the serving stack: step phases on the profiler's clock,
named scopes in the compiled step, request-lifecycle waits, and the
per-step scheduler and KV counters (at opt-tiny, on the CPU)."""
from __future__ import annotations

import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs.paper_models import OPT_TINY
from repro.models import dense
from repro.serving.engine import Engine
from repro.serving.server import ServeFront

SCOPES = ("embed", "layers", "attn", "qkv", "core", "out", "ffn", "ecc",
          "lm_head", "sample", "kv_write", "alg2")
PHASES = ("plan", "h2d", "dispatch", "sync")


@pytest.fixture(scope="module")
def params():
    return dense.init(OPT_TINY, jax.random.PRNGKey(0))


def _engine(params, registry=None, **kw):
    return Engine(OPT_TINY, params, max_slots=2, max_seq=96, rber=0.0,
                  registry=registry, **kw)


def _host_events(trace_dir):
    """(name, start_ns, end_ns, stats) of every host event of the newest
    profile under ``trace_dir``."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.name, ev.start_ns, ev.end_ns, dict(ev.stats)))
    return out


def test_step_phases_land_in_the_profile(params, tmp_path):
    """Under a profiler session every step is a ``serve_step`` step
    annotation holding its ``plan``/``h2d``/``dispatch``/``sync`` phases,
    and each phase starts where the Tracer's span of it starts, once the
    two clocks are aligned on one marker."""
    eng = _engine(params)
    for n in (20, 9):
        eng.submit(list(range(1, n + 1)), max_new=6)
    eng.step()                                   # compile outside the trace
    tracer = obs.Tracer(enabled=True)
    prev = obs.set_default_tracer(tracer)
    try:
        with jax.profiler.trace(str(tmp_path)):
            with jax.profiler.TraceAnnotation("clock_marker"):
                t_marker = time.perf_counter()
            tracer.complete("clock_marker", t_marker, 0.0)
            for _ in range(3):
                assert eng.step() > 0
    finally:
        obs.set_default_tracer(prev)
    host = _host_events(str(tmp_path))
    marker_ns = next(s for n, s, _, _ in host if n == "clock_marker")
    spans = [e for e in tracer.events() if e["ph"] == "X"]
    ts_marker = next(e["ts"] for e in spans if e["name"] == "clock_marker")

    def on_profiler_clock(ev):
        return marker_ns + (ev["ts"] - ts_marker) * 1e3

    steps = sorted((s, e, st) for n, s, e, st in host if n == "serve_step")
    assert len(steps) == 3
    assert [int(st["step_num"]) for _, _, st in steps] == [1, 2, 3]
    for name in PHASES:
        events = sorted(s for n, s, _, _ in host if n == name)
        mine = sorted(on_profiler_clock(e) for e in spans
                      if e["name"] == name and e["tid"] == obs.TID_COMPUTE)
        assert len(events) == len(mine) == 3
        for (lo, hi, _), start, traced in zip(steps, events, mine):
            assert lo <= start <= hi
            assert abs(start - traced) < 1e6                  # 1 ms


def _step_args(eng):
    n, t = eng.pool.n_slots, eng.admission_cfg.chunk_tokens
    state = dict(eng.pool.device_state(), bitmap=eng.bitmap,
                 prev_cycles=eng._prev_cycles)
    return (eng.params, eng.attn_flash, state,
            jnp.zeros((n, t), jnp.int32), jnp.ones((n,), jnp.int32),
            jnp.ones((n,), bool), eng.pool.block_tables_dev(),
            jax.random.PRNGKey(0))


def test_compiled_step_names_every_scope(params, monkeypatch):
    monkeypatch.setenv("REPRO_SERVE_ECC", "inline")
    eng = _engine(params)
    hlo = eng._step_fn.lower(*_step_args(eng)).compile().as_text()
    assert "jit_serve_step" in hlo.splitlines()[0]
    paths = set()
    for chunk in hlo.split('op_name="')[1:]:
        paths.add(chunk.split('"', 1)[0])
    parts = [set(p.split("/")) for p in paths]
    for scope in SCOPES:
        assert any(scope in ps for ps in parts), scope
    nested = [p.split("/") for p in paths if "ecc" in p.split("/")]
    assert any("ffn" in p and p.index("ffn") < p.index("ecc")
               for p in nested)
    assert any("layers" in p and p.index("layers") < p.index("attn")
               for p in (q.split("/") for q in paths)
               if "attn" in p)


def test_submit_wait_covers_a_step_holding_the_engine_lock(params):
    """A request that arrives while the loop thread runs a step waits in
    ``add_request`` until the step lets the engine lock go; the waits into
    admission and first token are observed once per request."""
    reg = obs.MetricsRegistry()
    eng = _engine(params, registry=reg)
    stepping, released = threading.Event(), []
    real_step_fn = eng._step_fn

    def slow_step(*args):
        out = real_step_fn(*args)
        stepping.set()
        time.sleep(0.3)
        released.append(time.perf_counter())
        return out

    eng._step_fn = slow_step
    front = ServeFront(eng, registry=reg)
    try:
        first = front.add_request(list(range(1, 12)), max_new=3)
        assert stepping.wait(60)
        t_call = time.perf_counter()
        second = front.add_request(list(range(3, 9)), max_new=3)
        first.result(timeout=60)
        second.result(timeout=60)
    finally:
        front.close(timeout=60)
    waits = reg.histogram("serve_submit_wait_seconds").snapshot()
    assert waits.count == 2
    # the second wait began a few microseconds after t_call
    assert waits.sum >= released[0] - t_call - 1e-3 > 0.2
    for name in ("engine_admission_wait_seconds", "engine_prefill_seconds"):
        assert reg.histogram(name).snapshot().count == 2, name


def test_lifecycle_spans_share_the_request_id(params):
    tracer = obs.Tracer(enabled=True)
    prev = obs.set_default_tracer(tracer)
    try:
        eng = _engine(params)
        front = ServeFront(eng)
        try:
            h = front.add_request(list(range(1, 10)), max_new=2)
            h.result(timeout=60)
        finally:
            front.close(timeout=60)
    finally:
        obs.set_default_tracer(prev)
    track = tracer.request_tid(h.rid)
    spans = {e["name"]: e for e in tracer.events()
             if e["ph"] == "X" and e["tid"] == track}
    for name in ("submit_wait", "queue", "prefill"):
        assert spans[name]["args"] == {"rid": h.rid}
    assert f"req{h.rid}" in spans


def test_step_budget_and_kv_counters(params):
    """Each step observes its token budget once and adds the active
    requests' reserved and held KV rows to the two counters."""
    reg = obs.MetricsRegistry()
    eng = _engine(params, registry=reg)
    eng.submit(list(range(1, 40)), max_new=5)
    eng.submit(list(range(1, 7)), max_new=5)
    reserved = used = steps = 0
    while eng.pool.active:
        for slot, rid in eng.pool.active.items():
            reserved += eng.requests[rid].kv_rows
            used += int(eng.pool.lengths[slot])
        assert eng.step() > 0
        steps += 1
    assert reg.counter("engine_kv_rows_reserved_total").value() == reserved
    assert reg.counter("engine_kv_rows_used_total").value() == used
    assert 0 < used < reserved
    budget = reg.histogram("engine_step_token_budget").snapshot()
    assert budget.count == steps
    assert 1 <= budget.sum / steps <= eng.admission_cfg.token_budget
