"""The comparison that decides ``correct``, driven at a tiny size on the
CPU with the look for a chip skipped: a sound run passes, a run whose
served tokens are altered where they are produced fails, and the control
(the reference one precision step lower) reads far above the program."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_helpers import write_tiny_bench
from bench import run
from bench.arch import opt, opt_reference


def _ctx(tmp_path, **kw):
    bench = write_tiny_bench(tmp_path, **kw)
    ctx = run.resolve(run.load_spec(bench.parent), "tiny.t", bench=bench)
    ctx["t_process"] = time.time()
    return ctx


def test_layer_regeneration_matches_the_stack():
    sizes = dict(run.sizes_of(_tiny_conf()))
    whole = opt.make_params(sizes, 2**40 + 3)
    for i in range(sizes["num_hidden_layers"]):
        one = opt.layer_params(sizes, 2**40 + 3, i)
        for a, b in zip(jax.tree.leaves(one),
                        jax.tree.leaves(jax.tree.map(lambda x: x[i],
                                                     whole["layers"]))):
            assert a.dtype == jnp.bfloat16
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    top = opt.top_params(sizes, 2**40 + 3)
    np.testing.assert_array_equal(np.asarray(top["lm_head"]),
                                  np.asarray(whole["lm_head"]))


def _tiny_conf():
    from bench_helpers import TINY_SIZES
    return dict(TINY_SIZES)


def test_reference_agrees_with_itself_greedy():
    sizes = _tiny_conf()
    prompt = list(range(5, 25))
    seq = list(prompt)
    for _ in range(4):
        row = opt_reference.logits_rows(sizes, 11, [seq + [0]], [len(seq)])
        seq.append(int(jnp.argmax(row[0])))
    gaps = opt_reference.served_gaps(sizes, 11, [seq], [len(prompt)])
    assert gaps.shape == (4,) and float(gaps.max()) == 0.0


def test_sound_run_is_correct(tmp_path):
    out = run.run(_ctx(tmp_path), 3000000007, 2.0, False)
    assert out["correct"] is True, out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "check"
    assert set(out["metrics"]) >= {"decode_tok_s", "itl_p95_ms", "setup_s",
                                   "peak_hbm_gib"}


def test_altered_token_is_not_correct(tmp_path, monkeypatch):
    from repro.serving import engine

    def off_by_one(logits, key, cfg):
        top = jnp.argmax(logits, axis=-1)
        return ((top + 1) % logits.shape[-1]).astype(jnp.int32)

    monkeypatch.setattr(engine, "sample", off_by_one)
    out = run.run(_ctx(tmp_path), 5, 2.0, False)
    assert out["correct"] is False
    gap = out["check"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("mode", ["fp8_act", "int4_flash"])
def test_control_reads_above_the_limit(tmp_path, mode):
    """The controls, kept at a size a test run holds (the chip readings at
    the cells' sizes are in PERF.md), are judged not correct by the run's
    own test, where the program on the same sample is. The int8 DRAM tier
    is no control: it reads what the program reads, which already runs
    attention columns from their int8 flash copy under Algorithm 2."""
    from bench import control
    ctx = _ctx(tmp_path)
    r = control.readings(ctx, 21, 2.0)
    assert r["tokens"] > 0
    assert r["program"]["correct"] is True
    assert r[mode]["correct"] is False
    assert r[mode]["max_gap"] > 3 * max(r["program"]["max_gap"], 1e-3)
