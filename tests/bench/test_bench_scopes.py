"""Device time by named scope, the kernel counts, and the readers of the
program's own spans and counters."""
from __future__ import annotations

import pytest

from bench_helpers import ROOT  # noqa: F401
from bench import counts, layer_counts, run, scopes, trace_reduce

SIZES = run.sizes_of(run.resolve(run.load_spec(),
                                 "opt-1.3b.decode")["config"])
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
STEP = "jit(serve_step)/jit(main)"


def _plane(events, paths):
    return {"name": "/device:TPU:0", "events": events, "paths": paths}


def test_scope_chain_keeps_scopes_in_order():
    assert scopes.scope_chain(f"{STEP}/layers/while/body/ffn/ecc/reduce_sum") \
        == ["layers", "ffn", "ecc"]
    assert scopes.scope_chain(f"{STEP}/embed/embed/gather") == ["embed"]
    assert scopes.scope_chain(f"{STEP}/convert_element_type") == []
    assert scopes.scope_chain("") == []


def test_synthetic_scope_reduction():
    """A loop holding an FFN matmul and its ECC pass, an attention op, and
    an op with no scope; self times, nesting and ``unscoped``."""
    events = [("m/%while", 0.0, 100.0), ("m/%dot.1", 10.0, 30.0),
              ("m/%syndrome", 40.0, 20.0), ("m/%attn.2", 60.0, 30.0),
              ("m/%copy", 150.0, 10.0), ("m/%dot.1", 200.0, 30.0)]
    paths = {"m/%while": f"{STEP}/layers/while",
             "m/%dot.1": f"{STEP}/layers/while/body/ffn/dot_general",
             "m/%syndrome": f"{STEP}/layers/while/body/ffn/ecc/reduce_sum",
             "m/%attn.2": f"{STEP}/layers/while/body/attn/core/dot_general"}
    out = scopes.reduce([_plane(events, paths)], (0.0, 300.0))
    ns = 1e-9
    assert out["busy_s"] == pytest.approx(140 * ns)
    assert out["scopes"] == {"layers": pytest.approx(130 * ns),
                             "ffn": pytest.approx(80 * ns),
                             "ecc": pytest.approx(20 * ns),
                             "attn": pytest.approx(30 * ns),
                             "core": pytest.approx(30 * ns),
                             "unscoped": pytest.approx(10 * ns)}
    assert out["top"] == {"layers": pytest.approx(130 * ns),
                          "unscoped": pytest.approx(10 * ns)}
    assert sum(out["top"].values()) == pytest.approx(out["busy_s"])
    # a window clips each op to it
    win = scopes.reduce([_plane(events, paths)], window_ns=(50.0, 210.0))
    assert win["scopes"]["ecc"] == pytest.approx(10 * ns)
    assert win["scopes"]["ffn"] == pytest.approx(20 * ns)
    assert win["busy_s"] == pytest.approx(70 * ns)


def test_no_device_ops_is_an_error():
    with pytest.raises(ValueError):
        scopes.reduce([], (0.0, 1.0))


def test_step_window_places_the_run_on_the_profiler_clock():
    """The run's first step is the annotation numbered as the engine's
    step count before the window, whatever the clocks' offset; steps of
    the trace before and after the window, and one unmarked, change
    nothing."""
    offset = 7.25e12
    host = [run.Step(100.0 + 0.12 * i, 100.1 + 0.12 * i, [(0, 1)], 1)
            for i in range(30)]
    window = host[5:25]
    marked = [(s.t0 * 1e9 + offset + 2e3, s.t1 * 1e9 + offset, 40 + i)
              for i, s in enumerate(host) if i != 9]
    lo, hi = scopes.step_window(marked, window, first_num=45)
    assert lo == pytest.approx(window[0].t0 * 1e9 + offset + 2e3)
    assert hi == pytest.approx(window[-1].t1 * 1e9 + offset + 2e3)
    assert scopes.step_window(marked, window, first_num=99) is None
    assert scopes.step_window(marked, [], first_num=45) is None
    # a step number whose annotation does not start the window's steps
    shifted = [(s + 0.05e9, e, n) for s, e, n in marked]
    shifted[5] = marked[5]
    assert scopes.step_window(shifted, window, first_num=45) is None


# --- counts of the FFN and attention kernels ---------------------------------

@pytest.mark.parametrize("chunks,sampled", [
    ([(100, 1), (200, 1), (300, 1), (400, 1)], 4),     # decode
    ([(0, 16), (48, 16)], 1),                           # prefill chunks
    ([(500, 1), (1000, 16), (0, 8)], 2),                # mixed
    ([(1984, 1)], 1),
])
def test_kernel_counts_add_up_to_the_step(chunks, sampled):
    assert (layer_counts.ffn_bytes(SIZES, chunks)
            + layer_counts.attn_bytes(SIZES, chunks)
            + layer_counts.rest_bytes(SIZES, chunks)
            == counts.step_bytes(SIZES, chunks))
    assert (layer_counts.ffn_flops(SIZES, chunks)
            + layer_counts.attn_flops(SIZES, chunks)
            + layer_counts.rest_flops(SIZES, chunks, sampled)
            == counts.step_flops(SIZES, chunks, sampled))


def test_kernel_counts_by_hand():
    d, f, n_l = 2048, 8192, 24
    chunks = [(100, 1), (200, 1)]
    ffn = n_l * 2 * (d * f + d * f // 8) + n_l * 4 * (f + d)
    assert layer_counts.ffn_bytes(SIZES, chunks) == ffn
    assert layer_counts.ffn_flops(SIZES, chunks) == 2 * 2 * n_l * 2 * d * f
    kv_row = 2 * n_l * d * 2
    assert layer_counts.attn_bytes(SIZES, chunks) \
        == n_l * 4 * d * d * 2 + (300 + 2) * kv_row
    # decode at 4 tokens a step is weight-read bound in both kernels
    assert layer_counts.ffn_least_seconds(SIZES, chunks, PEAKS) \
        == ffn / PEAKS["hbm_bytes_s"]
    assert 1e-3 < layer_counts.ffn_least_seconds(SIZES, chunks, PEAKS) < 2e-3


# --- the readers --------------------------------------------------------------

def _run(before=None, after=None, trace=None, steps=(), scope_red="unset"):
    view = run.Run(records=[], window_s=10.0, server_before=before or {},
                   server_after=after or {}, steps=list(steps), trace=trace,
                   sizes=SIZES, peaks=PEAKS, config={})
    if scope_red != "unset":
        view.scopes = scope_red
    return view


COUNTERS = {
    "submit_wait_ms": ("serve_submit_wait_seconds", 1e3),
    "admission_wait_ms": ("engine_admission_wait_seconds", 1e3),
    "prefill_ms": ("engine_prefill_seconds", 1e3),
    "step_token_budget": ("engine_step_token_budget", 1.0),
}


@pytest.mark.parametrize("name", sorted(COUNTERS))
def test_histogram_mean_readers(name):
    hist, scale = COUNTERS[name]
    read = run.load_reader(name)
    before = {f"{hist}_sum": 1.0, f"{hist}_count": 4}
    after = {f"{hist}_sum": 4.0, f"{hist}_count": 10}
    assert read(_run(before, after)) == pytest.approx(3.0 / 6 * scale)
    assert read(_run(before, before)) is None
    assert read(_run()) is None


def test_kv_used_share_reader():
    read = run.load_reader("kv_used_share")
    after = {"engine_kv_rows_reserved_total": 400.0,
             "engine_kv_rows_used_total": 300.0}
    before = {"engine_kv_rows_reserved_total": 200.0,
              "engine_kv_rows_used_total": 200.0}
    assert read(_run(before, after)) == pytest.approx(50.0)
    assert read(_run(after, after)) is None


RED = {"busy_s": 50.0, "scopes": {"layers": 45.0, "ffn": 30.0, "ecc": 15.0,
                                  "attn": 10.0, "unscoped": 2.0},
       "top": {"layers": 45.0, "unscoped": 2.0, "lm_head": 3.0}}
TRACE = {"busy_s": 50.0, "window_s": 55.0, "device_ops": [], "idle_gaps": []}
STEPS = [run.Step(0.0, 0.1, [(100, 1), (200, 1)], 2)] * 10


@pytest.mark.parametrize("name", ["ecc_share", "ecc_share.prefill"])
def test_ecc_share_reader(name):
    read = run.load_reader(name)
    assert read(_run(trace=TRACE, scope_red=RED)) == pytest.approx(30.0)
    assert read(_run(trace=TRACE, scope_red=None)) is None
    no_ecc = dict(RED, scopes={"ffn": 30.0})
    assert read(_run(trace=TRACE, scope_red=no_ecc)) is None


@pytest.mark.parametrize("name,scope,least", [
    ("ffn_roofline", "ffn", layer_counts.ffn_least_seconds),
    ("ffn_roofline.prefill", "ffn", layer_counts.ffn_least_seconds),
    ("attn_roofline", "attn", layer_counts.attn_least_seconds),
    ("attn_roofline.prefill", "attn", layer_counts.attn_least_seconds),
])
def test_kernel_roofline_readers(name, scope, least):
    read = run.load_reader(name)
    want = 100.0 * 10 * least(SIZES, STEPS[0].chunks, PEAKS) \
        / RED["scopes"][scope]
    got = read(_run(trace=TRACE, steps=STEPS, scope_red=RED))
    assert got == pytest.approx(want)
    assert 0 < got <= 100
    assert read(_run(trace=TRACE, steps=STEPS, scope_red=None)) is None
    assert read(_run(trace=TRACE, steps=(), scope_red=RED)) is None
    assert read(_run(trace=None, steps=STEPS)) is None
    no_scope = dict(RED, scopes={"ecc": 15.0})
    assert read(_run(trace=TRACE, steps=STEPS, scope_red=no_scope)) is None


def test_run_without_trace_file_reads_none(tmp_path):
    """A traced run whose trace directory holds no profile, or a profile
    that names no scope, reads None rather than failing."""
    view = _run(trace=TRACE, steps=STEPS)
    assert scopes.of_run(view, traces=tmp_path) is None
    assert run.load_reader("ecc_share")(view) is None


# --- op names from the profile's own HLO --------------------------------------

def test_hlo_op_names_from_a_profile(tmp_path):
    """The metadata plane of a real profile (CPU here) holds each module's
    optimized HLO; every instruction maps to the ``op_name`` that carries
    its named scopes."""
    import glob

    import jax
    import jax.numpy as jnp

    from bench import xspace

    def serve_step(x):
        with jax.named_scope("ffn"):
            with jax.named_scope("ecc"):
                y = jnp.sin(x) * 3.0
            return jnp.tanh(y @ x)

    step = jax.jit(serve_step)
    x = jnp.ones((32, 32))
    step(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        step(x).block_until_ready()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    with open(path, "rb") as f:
        names = xspace.hlo_op_names(f.read())
    (module,) = [m for m in names if m.startswith("jit_serve_step(")]
    chains = {tuple(scopes.scope_chain(p)) for p in names[module].values()}
    assert ("ffn", "ecc") in chains and ("ffn",) in chains


# --- a recorded chip trace ----------------------------------------------------

SCOPED = ROOT / "tests" / "bench" / "fixtures" / "trace_v5e_scoped.json"


def _scoped_fixture():
    """Two engine steps of a traced ``opt-1.3b.decode`` window on a TPU v5e:
    the device's op events with each op's ``op_name``, and the program's
    ``serve_step`` annotations (ops stored once, events by index)."""
    import json
    fx = json.loads(SCOPED.read_text())
    devices = [{"name": d["name"], "paths": dict(zip(d["ops"], d["paths"])),
                "events": [(d["ops"][i], float(s), float(t))
                           for i, s, t in d["events"]]}
               for d in fx["devices"]]
    return devices, [tuple(s) for s in fx["steps"]]


def test_recorded_scoped_trace():
    devices, marked = _scoped_fixture()
    window = (marked[0][0], marked[-1][1])
    out = scopes.reduce(devices, window)
    events = devices[0]["events"]
    busy = sum(b - a for a, b in trace_reduce.union(
        [(max(s, window[0]), min(s + d, window[1])) for _, s, d in events
         if min(s + d, window[1]) > max(s, window[0])]))
    assert out["busy_s"] == pytest.approx(busy / 1e9)
    assert sum(out["top"].values()) == pytest.approx(out["busy_s"])
    sc = out["scopes"]
    # greedy sampling fuses into an op of the lm_head on the chip, so no op
    # of its own carries ``sample``
    for name in set(scopes.SCOPES) - {"sample"}:
        assert sc.get(name, 0.0) > 0, name
    assert "sample" not in sc
    assert sc["attn"] + sc["ffn"] <= sc["layers"] * (1 + 1e-9)
    assert sc["qkv"] + sc["core"] + sc["out"] <= sc["attn"] * (1 + 1e-9)
    # ECC runs inside the matmuls that read flash-tier weights
    chains = [scopes.scope_chain(p) for p in devices[0]["paths"].values()]
    assert any(c[:3] == ["layers", "ffn", "ecc"] for c in chains)
    assert any(c[:2] == ["lm_head", "ecc"] for c in chains)
    # a run whose two steps are these, numbered as the trace numbers them,
    # is placed on them whatever the host clock reads
    host = [run.Step((s - 3e12) / 1e9, (e - 3e12) / 1e9, [(0, 1)], 1)
            for s, e, _ in marked]
    lo, hi = scopes.step_window(marked, host, first_num=marked[0][2])
    assert (lo, hi) == (pytest.approx(window[0]), pytest.approx(window[1]))


def test_unreadable_profile_is_a_value_error():
    from bench import xspace
    with pytest.raises(ValueError):
        xspace.hlo_op_names(b"\x0a\x05\x12\x02")       # plane cut short
    with pytest.raises(ValueError):
        list(xspace.fields(memoryview(b"\x0f")))        # wire type 7
    assert xspace.hlo_op_names(b"") == {}
