"""Serving engine + KV cache + sampler + Alg. 2 integration."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import OPT_TINY
from repro.models import dense
from repro.serving.engine import Engine
from repro.serving.kvcache import PagedKVPool
from repro.serving.sampler import SampleConfig, sample


@pytest.fixture(scope="module")
def engine():
    params = dense.init(OPT_TINY, jax.random.PRNGKey(0))
    return Engine(OPT_TINY, params, max_slots=3, max_seq=96, rber=1e-4)


def test_paged_pool_slot_alloc_release():
    pool = PagedKVPool(2, 3, 16, 2, 4, block_size=4)
    s1 = pool.alloc(100, need_tokens=10)
    s2 = pool.alloc(101, need_tokens=10)
    assert s1 != s2
    assert pool.alloc(102, need_tokens=10) is not None
    assert pool.alloc(103, need_tokens=10) is None          # slots full
    pool.release(s1)
    assert pool.alloc(104, need_tokens=10) == s1


def test_paged_pool_blocks_map_lazily_and_free_restores():
    pool = PagedKVPool(1, 2, 16, 2, 4, block_size=4, n_blocks=9)
    free0 = pool.n_free_blocks                               # 8 real blocks
    s = pool.alloc(0, need_tokens=10)                        # reserves 3
    assert pool.n_free_blocks == free0 - 3
    assert pool.n_mapped(s) == 0                             # nothing mapped yet
    pool.ensure(s, 5)                                        # 2 blocks
    assert pool.n_mapped(s) == 2 and pool.capacity(s) == 8
    assert all(b != 0 for b in pool.block_tables[s, :2])     # 0 = dump block
    pool.ensure(s, 5)                                        # idempotent
    assert pool.n_mapped(s) == 2
    pool.release(s)
    assert pool.n_free_blocks == free0
    assert np.count_nonzero(pool.block_tables[s]) == 0


def test_paged_pool_release_is_zero_device_work():
    """Completing a request must not touch the device pool: stale KV is
    unreachable (no table maps it; length masks bound reads), so release
    is O(1) host bookkeeping — the seed pool's two full-pool zeroing
    scatters are gone."""
    pool = PagedKVPool(2, 2, 32, 2, 4)
    s = pool.alloc(0, need_tokens=20)
    pool.ensure(s, 20)
    k_buf, v_buf, len_buf = pool.k, pool.v, pool.lengths_dev
    pool.release(s)
    assert pool.k is k_buf and pool.v is v_buf
    assert pool.lengths_dev is len_buf, "release dispatched a device write"


def test_pool_admission_respects_block_budget():
    """With fewer physical blocks than slots x max_blocks, admission is
    bounded by the BLOCK reservation, not just slot count."""
    pool = PagedKVPool(1, 4, 16, 2, 4, block_size=4, n_blocks=7)  # 6 real
    s1 = pool.alloc(0, need_tokens=16)                       # 4 blocks
    assert s1 is not None
    assert pool.alloc(1, need_tokens=16) is None             # only 2 left
    assert pool.alloc(2, need_tokens=8) is not None          # 2 fit


def test_sampler_greedy_and_topk(key):
    logits = jnp.asarray([[0.0, 5.0, 1.0, -2.0]])
    assert int(sample(logits, key, SampleConfig())[0]) == 1
    out = sample(jnp.tile(logits, (64, 1)), key,
                 SampleConfig(temperature=1.0, top_k=2))
    assert set(np.asarray(out).tolist()) <= {1, 2}
    out_p = sample(jnp.tile(logits, (64, 1)), key,
                   SampleConfig(temperature=1.0, top_p=0.5))
    assert set(np.asarray(out_p).tolist()) <= {1}


def test_engine_continuous_batching(engine):
    r1 = engine.submit([1, 2, 3, 4], max_new=5)
    r2 = engine.submit([7, 8], max_new=3)
    out = engine.run()
    assert len(out[r1]) == 5 and len(out[r2]) == 3
    # slots were freed -> a new request is admitted
    r3 = engine.submit([5], max_new=2)
    out = engine.run()
    assert len(out[r3]) == 2


def test_submit_oversubscribed_enqueues_and_completes():
    """Regression: submit beyond slot capacity must ENQUEUE (waiting ->
    running admission), not raise — the seed engine errored with
    'no free slots'. Every request completes with its full token count."""
    params = dense.init(OPT_TINY, jax.random.PRNGKey(3))
    eng = Engine(OPT_TINY, params, max_slots=2, max_seq=64, rber=0.0)
    rids = [eng.submit([i + 1, i + 2, i + 3], max_new=4) for i in range(6)]
    assert len(eng.waiting) == 4                 # 2 admitted, 4 queued
    out = eng.run()
    assert all(len(out[r]) == 4 for r in rids)
    assert not eng.waiting and eng.step_traces == 1


def test_engine_matches_model_decode(key):
    """The engine's layer-by-layer path must match the packaged model
    (same tiered params, greedy sampling, single request)."""
    params = dense.init(OPT_TINY, jax.random.PRNGKey(0))
    eng = Engine(OPT_TINY, params, max_slots=1, max_seq=64, rber=0.0,
                 kv_aware=False)
    prompt = [3, 14, 15, 9, 2]
    rid = eng.submit(prompt, max_new=4)
    out_engine = eng.run()[rid]

    tiered = eng.params
    toks = jnp.asarray([prompt], jnp.int32)
    last, cache = dense.prefill(OPT_TINY, tiered, {"tokens": toks}, pad_to=64)
    toks_out = [int(jnp.argmax(last, -1)[0])]
    for i in range(3):
        lg, cache = dense.decode_step(
            OPT_TINY, tiered, cache,
            {"token": jnp.asarray([toks_out[-1]], jnp.int32),
             "kv_len": jnp.int32(len(prompt) + i)})
        toks_out.append(int(jnp.argmax(lg, -1)[0]))
    assert out_engine == toks_out


def test_kv_aware_offload_under_long_context():
    """Alg. 2 must move column groups off the NPU as the KV cache grows."""
    import repro.core.scheduler as sched
    params = dense.init(OPT_TINY, jax.random.PRNGKey(1))
    cfg = sched.SchedulerConfig(page_buffer_bytes=128, column_bytes=128,
                                c_npu_per_column=16, h=8)   # c_th=16
    eng = Engine(OPT_TINY, params, max_slots=1, max_seq=160, rber=0.0,
                 sched_cfg=cfg, kv_aware=True)
    eng.submit(list(range(1, 60)), max_new=64)
    eng.run()
    fr = [s["npu_fraction"] for s in eng.stats]
    assert fr[-1] < fr[0], "bitmap should offload under KV growth"
    assert all(b - a < 1e-9 for a, b in zip(fr, fr[1:])), "monotone offload"


def test_engine_rber_still_decodes():
    params = dense.init(OPT_TINY, jax.random.PRNGKey(2))
    clean = Engine(OPT_TINY, params, max_slots=1, max_seq=64, rber=0.0)
    # rber chosen so every corrupted codeword has a SINGLE bit flip (at
    # 1e-4 this seed deterministically leaves 2 double-bit weights SEC-DED
    # cannot repair, and greedy equality would ride on near-tie argmax).
    noisy = Engine(OPT_TINY, params, max_slots=1, max_seq=64, rber=1e-5)
    # premise first, so a failure pinpoints ECC vs numerics: SEC-DED must
    # restore the flash tier EXACTLY — the engines then run bit-identical
    # weights and greedy equality below is deterministic, not a near-tie.
    from repro.core import ecc
    is_fw = lambda x: hasattr(x, "parity")
    flat = lambda e: (
        [l for l in jax.tree.leaves(e.params, is_leaf=is_fw) if is_fw(l)]
        + [l for l in jax.tree.leaves(e.plane.attn_flash, is_leaf=is_fw)
           if is_fw(l)])
    for c, n in zip(flat(clean), flat(noisy)):
        qc = jnp.asarray(c.q).reshape(-1, c.q.shape[-1])
        qn = jnp.asarray(n.q).reshape(-1, n.q.shape[-1])
        pn = jnp.asarray(n.parity).reshape(-1, n.parity.shape[-1])
        corr, _, _ = ecc.check_and_correct(ecc.weights_to_bytes(qn), pn)
        np.testing.assert_array_equal(
            np.asarray(ecc.bytes_to_weights(corr)), np.asarray(qc),
            err_msg="uncorrectable (multi-bit) codeword at this rber/seed")
    p = [5, 6, 7]
    r1 = clean.submit(p, max_new=6)
    out1 = clean.run()[r1]
    r2 = noisy.submit(p, max_new=6)
    out2 = noisy.run()[r2]
    # ECC repairs single-bit errors: greedy decode matches the clean engine
    assert out1 == out2
