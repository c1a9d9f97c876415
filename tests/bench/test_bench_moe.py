"""The Qwen3-MoE cell at a tiny size on the CPU: the seeded weights the
reference regenerates a layer at a time, the tie between the benchmark's
configuration and the model the repository lists, the comparison that
decides ``correct`` (prefill then decode through the paged cache via
``Engine``, against the reference's full forward pass), its controls, and
the counts and readers of the three MoE metrics."""
from __future__ import annotations

import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_helpers import ROOT, TINY_MIX
from bench import moe_check, moe_counts, moe_scopes, run
from bench.arch import qwen3_moe, qwen3_moe_reference

TINY = {"hidden_size": 64, "ffn_dim": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32, "num_experts": 16,
        "num_experts_per_tok": 4, "num_hidden_layers": 2, "vocab_size": 512,
        "max_position_embeddings": 256}
# The tiny cell's limit, set as the cells' are: on the CPU over 12 seeds the
# program read at most 0.0770 and the controls at least 1.205 (fp8
# activations) and 0.199 (int4 experts).
TINY_LIMIT = 0.1
CELL = "qwen3-moe-30b-a3b.decode"


def _conf():
    return json.loads((ROOT / "bench" / "configs"
                       / "qwen3-moe-30b-a3b.json").read_text())


@pytest.fixture
def tiny_ctx(tmp_path, monkeypatch):
    """A checkout-shaped tree holding one tiny Qwen3-MoE cell; the arch
    module reads the shape keys the harness does not pass from its file."""
    bench = tmp_path / "bench"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir(parents=True)
    conf = dict(_conf(), **TINY, name="tinymoe")
    conf["engine"] = dict(conf["engine"], max_seq=256)
    conf["check"] = {"max_logit_gap": TINY_LIMIT, "min_tokens": 64,
                     "max_requests": 8}
    path = bench / "configs" / "tinymoe.json"
    path.write_text(json.dumps(conf))
    (bench / "traffic" / "tmix.json").write_text(json.dumps(TINY_MIX))
    spec = run.load_spec()
    spec["configs"] = [{"name": "tinymoe", "source": conf["source"],
                        "file": "bench/configs/tinymoe.json",
                        "reduced": [], "why": "tiny"}]
    spec["workloads"] = [{"name": "tinymoe.t", "config": "tinymoe",
                          "traffic": "tmix", "chips": 1, "why": "tiny"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(qwen3_moe, "CONF_FILE", path)
    ctx = run.resolve(run.load_spec(tmp_path), "tinymoe.t", bench=bench)
    ctx["t_process"] = time.time()
    return ctx


def test_layer_regeneration_matches_the_stack():
    whole = qwen3_moe.make_params(TINY, 2**40 + 3)
    for i in range(TINY["num_hidden_layers"]):
        one = qwen3_moe.layer_params(TINY, 2**40 + 3, i)
        want = jax.tree.map(lambda x: x[i], whole["layers"])
        assert jax.tree.structure(one) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(want)):
            assert a.dtype == jnp.bfloat16
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    top = qwen3_moe.top_params(TINY, 2**40 + 3)
    for k in ("embed", "lm_head", "final_norm"):
        np.testing.assert_array_equal(np.asarray(top[k]),
                                      np.asarray(whole[k]))


def test_params_have_the_program_layout():
    from repro.models import moe
    cfg = qwen3_moe.program_config(dict(_conf(), **TINY))
    want = jax.eval_shape(lambda k: moe.init(cfg, k), jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: qwen3_moe.make_params(TINY, 5))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_cell_config_is_the_listed_model():
    """The benchmark's configuration, through ``program_config``, is the
    repository's ``qwen3-moe-30b-a3b`` in every field but the depth (the
    first of eight pipeline stages) and the context it is served at."""
    from repro.configs.qwen3_moe_30b_a3b import CONFIG
    got = qwen3_moe.program_config(_conf())
    for f in dataclasses.fields(CONFIG):
        if f.name in ("n_layers", "max_seq"):
            continue
        assert getattr(got, f.name) == getattr(CONFIG, f.name), f.name
    assert got.n_layers == 6 and CONFIG.n_layers == 48
    assert _conf()["published_num_hidden_layers"] == CONFIG.n_layers


def test_full_sizes_fill_the_keys_the_harness_does_not_pass():
    six = run.sizes_of(_conf())
    assert set(qwen3_moe.SHAPE_KEYS).isdisjoint(six)
    full = qwen3_moe.full_sizes(six)
    assert full["num_key_value_heads"] == 4 and full["head_dim"] == 128
    assert full["num_experts"] == 128 and full["num_experts_per_tok"] == 8
    assert qwen3_moe.full_sizes(TINY)["num_experts"] == 16


def test_program_refuses_another_norm_epsilon():
    with pytest.raises(ValueError, match="eps"):
        qwen3_moe.program_config(dict(_conf(), rms_norm_eps=1e-5))


def test_reference_agrees_with_itself_greedy():
    prompt = list(range(5, 25))
    seq = list(prompt)
    for _ in range(4):
        row = qwen3_moe_reference.logits_rows(TINY, 11, [seq + [0]],
                                              [len(seq)])
        seq.append(int(jnp.argmax(row[0])))
    gaps = qwen3_moe_reference.served_gaps(TINY, 11, [seq], [len(prompt)])
    assert gaps.shape == (4,) and float(gaps.max()) == 0.0


def test_reference_rounds_where_the_program_does():
    """With the prompt prefilled in one chunk, the reference at the stated
    precision (bfloat16 activations) puts first every token the compiled
    program serves on the CPU; the float32 model does not."""
    from repro.core.scheduler import AdmissionConfig
    from repro.serving.engine import Engine
    from repro.serving.sampler import SampleConfig
    seed = 8
    eng = Engine(qwen3_moe.program_config(dict(_conf(), **TINY)),
                 qwen3_moe.make_params(TINY, seed), max_slots=1,
                 max_seq=256, sample_cfg=SampleConfig(temperature=0.0),
                 seed=1, admission_cfg=AdmissionConfig(
                     chunk_tokens=64, token_budget=64, adaptive=False))
    prompt = np.random.default_rng(seed).integers(1, 512, 40).tolist()
    rid = eng.submit(prompt, max_new=30)
    seq = prompt + list(eng.run()[rid])
    ref = qwen3_moe_reference
    assert float(ref.served_gaps(TINY, seed, [seq], [40]).max()) == 0.0
    f32 = ref.logits_rows(TINY, seed, [seq], [40], "f32")
    assert np.any(np.asarray(jnp.argmax(f32, axis=-1))
                  != np.asarray(seq[40:]))


def test_sound_run_is_correct(tiny_ctx):
    out = run.run(tiny_ctx, 3000000007, 2.0, False)
    assert out["correct"] is True, out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) >= {"decode_tok_s", "itl_p95_ms", "setup_s"}


@pytest.mark.parametrize("mode", list(qwen3_moe_reference.CONTROLS))
def test_control_reads_above_the_limit(tiny_ctx, mode):
    r = moe_check.readings(tiny_ctx, 21, 2.0)
    assert r["tokens"] > 0
    assert r["program"]["correct"] is True
    assert r[mode]["correct"] is False
    assert r[mode]["max_gap"] > 3 * max(r["program"]["max_gap"], 1e-3)
    ties = r["near_tie"]
    assert 0.0 <= ties["flipped_share"] <= ties["share"] <= 1.0
    assert ties["near"]["rows"] + ties["other"]["rows"] == r["tokens"]


# --- counts and readers --------------------------------------------------------

SZ = moe_counts.full_sizes(_conf())
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}


def test_counts_by_hand():
    assert SZ["num_hidden_layers"] == 6 and SZ["num_experts"] == 128
    per = 2 * (2048 * 768 + 256 * 768 + 4 * 768) + (768 * 2048 + 96 * 2048
                                                     + 4 * 2048)
    assert moe_counts.expert_bytes(SZ) == per
    # one decode token at context 100, sampled
    d, qw, kvw = 2048, 4096, 512
    want = (2 * 6 * (2 * d * qw + 2 * d * kvw) + 4 * qw * 101 * 6
            + 2 * 6 * d * 128 + 6 * 8 * 6 * d * 768 + 2 * d * 151936)
    assert moe_counts.step_flops(SZ, [(100, 1)], 1) == want
    least = moe_counts.expert_least_seconds(SZ, 32 * 6, 29 * 6, PEAKS)
    assert least == pytest.approx(29 * 6 * per / 819e9)
    assert moe_counts.full_sizes(json.loads(
        (ROOT / "bench" / "configs" / "opt-1.3b.json").read_text())) is None


def _run(**kw):
    view = run.Run(config=_conf(), peaks=PEAKS, window_s=50.0,
                   server_before={}, server_after={}, steps=[], trace=None)
    view.__dict__.update(kw)
    return view


STEPS = [run.Step(0.0, 0.03, [(300, 1)] * 4, 4)] * 100
TRACE = {"busy_s": 40.0, "window_s": 50.0, "device_ops": [], "idle_gaps": []}
RED = {"busy_s": 40.0, "scopes": {"route": 2.0, "experts": 20.0}}
COUNTERS = ({"engine_moe_assignments_total": 1000.0,
             "engine_moe_experts_routed_total": 500.0},
            {"engine_moe_assignments_total": 1000.0 + 100 * 4 * 8 * 6,
             "engine_moe_experts_routed_total": 500.0 + 100 * 29 * 6})


def test_step_mfu_moe_reader():
    read = run.load_reader("step_mfu.moe")
    want = 100.0 * 100 * moe_counts.step_flops(SZ, [(300, 1)] * 4, 4) \
        / (50.0 * 197e12)
    assert read(_run(steps=STEPS)) == pytest.approx(want)
    assert read(_run(steps=[])) is None
    opt = json.loads((ROOT / "bench" / "configs" / "opt-1.3b.json")
                     .read_text())
    assert read(_run(steps=STEPS, config=opt)) is None


def test_expert_roofline_reader():
    read = run.load_reader("expert_roofline")
    before, after = COUNTERS
    view = _run(steps=STEPS, trace=TRACE, moe_scopes=RED,
                server_before=before, server_after=after)
    want = 100.0 * 100 * 29 * 6 * moe_counts.expert_bytes(SZ) / 819e9 / 20.0
    got = read(view)
    assert got == pytest.approx(want) and 0 < got <= 100
    assert read(_run(steps=STEPS, trace=TRACE, moe_scopes=None,
                     server_before=before, server_after=after)) is None
    assert read(_run(steps=STEPS, trace=TRACE, moe_scopes=RED)) is None
    assert read(_run(steps=STEPS, trace=TRACE,
                     moe_scopes=dict(RED, scopes={"route": 2.0}),
                     server_before=before, server_after=after)) is None


def test_route_share_reader():
    read = run.load_reader("route_share")
    assert read(_run(trace=TRACE, moe_scopes=RED)) == pytest.approx(5.0)
    assert read(_run(trace=TRACE, moe_scopes=None)) is None
    assert read(_run(trace=TRACE,
                     moe_scopes=dict(RED, scopes={"experts": 1.0}))) is None


def test_synthetic_moe_scope_reduction():
    """A step loop holding the router, a routed-expert read with its ECC
    pass inside a branch, a grouped product whose custom call lost its
    scope path, and the combine; nesting and a clipped window."""
    step = "jit(serve_step)/jit(main)/layers/while/body"
    events = [("m/%while", 0.0, 100.0), ("m/%topk", 5.0, 5.0),
              ("m/%cond", 10.0, 60.0), ("m/%gather", 12.0, 10.0),
              ("m/%syndrome", 22.0, 20.0),
              ("m/%ragged-dot-none.3", 42.0, 25.0),
              ("m/%combine", 75.0, 5.0), ("m/%lm_head", 120.0, 10.0)]
    paths = {"m/%topk": f"{step}/route/top_k",
             "m/%cond": f"{step}/ffn/cond",
             "m/%gather": f"{step}/ffn/experts/while/dynamic_slice",
             "m/%syndrome": f"{step}/ffn/experts/ecc/reduce",
             "m/%ragged-dot-none.3": "ragged-dot-none",
             "m/%combine": f"{step}/ffn/route/mul",
             "m/%lm_head": "jit(serve_step)/jit(main)/lm_head/dot"}
    plane = {"name": "/device:TPU:0", "events": events, "paths": paths}
    out = moe_scopes.reduce([plane], (0.0, 200.0))
    ns = 1e-9
    assert out["busy_s"] == pytest.approx(110 * ns)
    assert out["scopes"] == {"route": pytest.approx(10 * ns),
                             "experts": pytest.approx(55 * ns)}
    win = moe_scopes.reduce([plane], (30.0, 50.0))
    assert win["scopes"]["experts"] == pytest.approx(20 * ns)


def test_run_without_trace_reads_none(tmp_path):
    view = _run(trace=TRACE, steps=STEPS)
    assert moe_scopes.of_run(view, traces=tmp_path) is None
    assert run.load_reader("route_share")(view) is None


def test_cell_resolves_with_its_metrics():
    ctx = run.resolve(run.load_spec(), CELL)
    names = {m["name"] for m in ctx["per_layer"]}
    assert names == {"step_mfu.moe", "expert_roofline", "route_share"}
    assert {m["name"] for m in ctx["end_to_end"]} == {
        "decode_tok_s", "ttft_p50_ms", "itl_p95_ms", "peak_hbm_gib",
        "setup_s"}
    assert ctx["mix"] == json.loads((ROOT / "bench" / "traffic"
                                     / "decode.json").read_text())
