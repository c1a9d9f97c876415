"""Expert-paged MoE serving (ISSUE 5): the engine must serve the MoE smoke
configs streamed from the PageStore — only ROUTED experts crossing to the
device — token-identical to the fully-resident MoE engine, through exactly
three compiled traces (head [embed + attn/router(0)] + fused expert/attn
handoff + tail [last experts + finish])."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import scheduler as sched
from repro.models import moe
from repro.serving.engine import Engine
from repro.store import PageStore, StreamConfig

MAX_SEQ = 96
CFG = get_config("qwen3-moe-30b-a3b", smoke=True)


@pytest.fixture(scope="module")
def params():
    return moe.init(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def resident_tokens(params):
    """Greedy reference outputs from the fully-resident compiled engine."""
    eng = Engine(CFG, params, max_slots=2, max_seq=MAX_SEQ)
    eng.submit(list(range(1, 20)), max_new=8)     # chunked prefill
    eng.submit([9, 8, 7], max_new=8)
    return eng.run()


def _submit_pair(eng):
    eng.submit(list(range(1, 20)), max_new=8)
    eng.submit([9, 8, 7], max_new=8)


def _streamed(params, **stream_kw):
    store = PageStore(n_planes=8)
    eng = Engine(CFG, params, max_slots=2, max_seq=MAX_SEQ,
                 weight_store=store, stream_cfg=StreamConfig(**stream_kw))
    return eng, store


# --- serving math units -------------------------------------------------------

def test_serve_route_topk_normalized():
    router = jax.random.normal(jax.random.PRNGKey(0), (16, 8), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 16), jnp.bfloat16)
    gates, idx = moe.serve_route(router, x, top_k=2)
    assert gates.shape == (2, 3, 2) and idx.shape == (2, 3, 2)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-5)
    assert int(np.asarray(idx).max()) < 8


def test_serve_expert_ffn_slab_matches_full_bank():
    """THE parity property expert paging leans on: a partial slab holding
    only the routed experts (any row order) produces bit-identical outputs
    to the full bank."""
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    e, d, f = 6, 16, 24
    bank = {"w_gate": jax.random.normal(ks[0], (e, d, f), jnp.bfloat16),
            "w_up": jax.random.normal(ks[1], (e, d, f), jnp.bfloat16),
            "w_down": jax.random.normal(ks[2], (e, f, d), jnp.bfloat16)}
    x = jax.random.normal(ks[3], (2, 4, d), jnp.bfloat16)
    gates, idx = moe.serve_route(
        jax.random.normal(ks[4], (d, e), jnp.float32), x, top_k=2)
    full = moe.serve_expert_ffn(bank, x, gates, idx)
    routed = sorted(set(np.asarray(idx).ravel().tolist()))
    perm = routed[::-1]                          # arbitrary slab order
    slab = {k: v[jnp.asarray(perm)] for k, v in bank.items()}
    slab_map = np.full((e,), -1, np.int32)
    for row, ex in enumerate(perm):
        slab_map[ex] = row
    part = moe.serve_expert_ffn(slab, x, gates, idx, jnp.asarray(slab_map))
    np.testing.assert_array_equal(np.asarray(full), np.asarray(part))


def test_serve_expert_ffn_unmapped_expert_contributes_zero():
    e, d, f = 4, 8, 8
    bank = {k: jnp.ones((1, d, f) if k != "w_down" else (1, f, d),
                        jnp.bfloat16) for k in ("w_gate", "w_up", "w_down")}
    x = jnp.ones((1, 1, d), jnp.bfloat16)
    gates = jnp.ones((1, 1, 1), jnp.float32)
    idx = jnp.zeros((1, 1, 1), jnp.int32)
    out = moe.serve_expert_ffn(bank, x, gates, idx,
                               jnp.full((e,), -1, jnp.int32))
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_routed_experts_filters_padding_lanes():
    idx = np.array([[[0, 1], [2, 3], [4, 5]],
                    [[6, 7], [6, 7], [6, 7]]])
    q_lens = np.array([2, 0])                    # slot 1 idle this step
    assert sched.routed_experts(idx, q_lens).tolist() == [0, 1, 2, 3]
    assert sched.routed_experts(idx, np.array([0, 0])).size == 0


# --- engine: resident MoE -----------------------------------------------------

def test_resident_eager_matches_compiled(params, resident_tokens):
    eng = Engine(CFG, params, max_slots=2, max_seq=MAX_SEQ, compiled=False)
    _submit_pair(eng)
    assert eng.run() == resident_tokens


def test_engine_rejects_unknown_family(params):
    import dataclasses
    bad = dataclasses.replace(CFG, family="rwkv6")
    with pytest.raises(ValueError, match="family"):
        Engine(bad, params)


# --- engine: streamed MoE (expert paging) -------------------------------------

def test_streamed_matches_resident(params, resident_tokens):
    eng, store = _streamed(params)
    _submit_pair(eng)
    assert eng.run() == resident_tokens
    st = eng.expert_stats()
    assert st["expert_hit_rate"] > 0
    assert store.pages_read > 0 and store.nand_seconds() > 0


def test_streamed_under_budget_smaller_than_flash_tier(params,
                                                       resident_tokens):
    """THE acceptance property: a device budget SMALLER than the MoE flash
    tier still serves with token parity, fetching only routed experts."""
    from repro.core.tiering import deploy
    probe = PageStore()
    deploy(params, store=probe)
    budget = int(probe.total_bytes * 0.8)
    eng, store = _streamed(params, device_budget_bytes=budget)
    assert store.total_bytes > budget            # model > device memory
    _submit_pair(eng)
    assert eng.run() == resident_tokens
    st = eng.expert_stats()
    assert st["expert_bytes_fetched"] > 0
    assert st["expert_bytes_per_token"] < st["all_experts_bytes_per_token"]
    # the cache respects its residual capacity at all times
    assert eng.plane.expert_cache.bytes_used <= eng.plane.expert_cache.capacity


def test_streamed_pin_all_matches_resident(params, resident_tokens):
    """pin_all degenerates to the fully-resident engine: every expert
    pinned at init, zero bytes fetched during serving."""
    eng, _ = _streamed(params, pin_all=True)
    _submit_pair(eng)
    assert eng.run() == resident_tokens
    st = eng.expert_stats()
    assert st["expert_bytes_fetched"] == 0
    assert st["expert_hit_rate"] == 1.0 and st["misroute_stalls"] == 0


def test_streamed_three_traces_across_churn(params):
    """head (embed + attn/router(0)) + ONE fused expert/attn handoff trace
    + tail (last experts + finish) == 3 traces, stable across slot churn,
    layers, and step count."""
    eng, _ = _streamed(params)
    r1 = eng.submit([1, 2, 3], max_new=2)
    eng.submit([5, 6, 7, 8, 9], max_new=10)
    while not eng.requests[r1].done:
        eng.step()
    assert eng.step_traces == 3
    eng.submit(list(range(1, 20)), max_new=4)    # admit into freed slot
    eng.run()
    assert eng.step_traces == 3, "expert paging or churn retraced"


def test_streamed_group_size_must_be_one(params):
    with pytest.raises(ValueError, match="group_size"):
        _streamed(params, group_size=2)


def test_streamed_rejects_impossible_budget(params):
    with pytest.raises(ValueError, match="device_budget"):
        _streamed(params, device_budget_bytes=1024)


def test_preprogrammed_image_serves(params, resident_tokens, tmp_path):
    """A persisted MoE die image (write-once) serves read-only: StoreRefs
    rebuilt from the page table, DRAM tier supplied separately."""
    from repro.core.tiering import dram_tier
    _, store = _streamed(params)                 # programs the store
    img = str(tmp_path / "moe.img")
    store.save(img)
    opened = PageStore.open(img)
    eng = Engine(CFG, dram_tier(params), max_slots=2, max_seq=MAX_SEQ,
                 weight_store=opened, stream_cfg=StreamConfig())
    assert eng.plane.store_preprogrammed
    _submit_pair(eng)
    assert eng.run() == resident_tokens


def test_expert_stats_requires_moe_stream(params):
    eng = Engine(CFG, params, max_slots=2, max_seq=MAX_SEQ)
    with pytest.raises(ValueError, match="expert_stats"):
        eng.expert_stats()


def test_close_stops_prefetch_worker(params):
    """close() joins the prefetch worker (its fetch closure pins the
    plane, so nothing is reclaimed without it) and is idempotent —
    including on engines that never had a prefetcher."""
    eng, _ = _streamed(params)
    eng.submit([1, 2, 3], max_new=2)
    eng.run()
    worker = eng.plane.prefetcher._thread
    eng.close()
    assert not worker.is_alive()
    eng.close()                                  # idempotent
    Engine(CFG, params, max_slots=2, max_seq=MAX_SEQ).close()  # no-op


def test_serve_route_grouped_bounds_expert_set():
    """Group-limited routing (DeepSeek-V2 discipline): every token's top-k
    lands inside its topk_groups best groups, bounding the distinct-expert
    set the streamed engine must page per token."""
    e, n_groups, topk_groups = 8, 4, 2
    gsz = e // n_groups
    router = jax.random.normal(jax.random.PRNGKey(0), (16, e), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 16), jnp.bfloat16)
    gates, idx = moe.serve_route(router, x, top_k=2, n_groups=n_groups,
                                 topk_groups=topk_groups)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-5)
    groups_per_token = np.asarray(idx) // gsz
    for s in range(2):
        for t in range(5):
            assert len(set(groups_per_token[s, t].tolist())) <= topk_groups
    # topk_groups in {0, n_groups} disables the restriction entirely
    g0, i0 = moe.serve_route(router, x, top_k=2)
    g1, i1 = moe.serve_route(router, x, top_k=2, n_groups=n_groups,
                             topk_groups=n_groups)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    with pytest.raises(ValueError, match="n_expert_groups"):
        moe.serve_route(router, x, top_k=2, n_groups=3, topk_groups=2)


def test_streamed_grouped_routing_matches_resident(params):
    """The engine threads n_expert_groups/topk_expert_groups through both
    the resident and streamed routers — parity holds under the restricted
    routing too (params are shape-identical; only routing changes)."""
    import dataclasses
    gcfg = dataclasses.replace(CFG, n_expert_groups=4, topk_expert_groups=2)
    ref = Engine(gcfg, params, max_slots=2, max_seq=MAX_SEQ)
    _submit_pair(ref)
    want = ref.run()
    store = PageStore(n_planes=8)
    eng = Engine(gcfg, params, max_slots=2, max_seq=MAX_SEQ,
                 weight_store=store, stream_cfg=StreamConfig())
    _submit_pair(eng)
    assert eng.run() == want
    assert eng.step_traces == 3


def test_streamed_pin_shared_experts(params, resident_tokens):
    """pin_shared_experts pins the first N experts of every layer at init:
    they are cache-resident (and pinned) for the whole run, and parity is
    untouched."""
    eng, _ = _streamed(params, pin_shared_experts=2)
    _submit_pair(eng)
    assert eng.run() == resident_tokens
    for li in range(CFG.n_layers):
        for e in range(2):
            assert (li, e) in eng.plane.expert_cache
            assert eng.plane.expert_cache._entries[(li, e)].pinned


def test_streamed_per_slot_stats(params):
    """Per-slot router histories: expert_stats() reports one hit rate per
    decode slot plus the observed max routed-set size."""
    eng, _ = _streamed(params)
    _submit_pair(eng)
    eng.run()
    st = eng.expert_stats()
    assert len(st["slot_hit_rates"]) == 2
    assert all(0.0 <= r <= 1.0 for r in st["slot_hit_rates"])
    assert any(r > 0.0 for r in st["slot_hit_rates"])
    assert 0 < st["max_routed_seen"] <= st["expert_slab"]
    assert st["pool_uploads"] >= 0 and st["pool_pages"] > 0


def test_auto_expert_budget_returns_dead_slab_rows(params):
    """Misroute-stall-aware budget re-split: the one-shot retune returns
    the slab reservation's unused rows (e_slab vs observed max routed) to
    the expert cache's capacity — and never fires twice."""
    from repro.core.tiering import deploy
    probe = PageStore()
    deploy(params, store=probe)
    budget = int(probe.total_bytes * 0.8)
    eng, _ = _streamed(params, device_budget_bytes=budget,
                       auto_expert_budget=True, auto_depth_after=2)
    cap0 = eng.plane.expert_cache.capacity
    # drive the mechanism deterministically (the end-to-end flag is
    # covered below): observed routing used 3 of e_slab rows, and at
    # least one misroute stalled
    eng.plane._max_routed_seen = 3
    eng.plane.expert_cache.note_stall(0.001)
    eng.plane.after_step(5)                      # 5 steps done
    assert eng.plane._auto_expert_done
    grown = (eng.plane._e_slab - 3) * eng.plane._max_expert_bytes
    assert eng.plane.expert_cache.capacity == cap0 + grown
    eng.plane.after_step(6)                      # one-shot: no double-grow
    assert eng.plane.expert_cache.capacity == cap0 + grown
    # end-to-end: the flag flips during a real run and serving still works
    eng2, _ = _streamed(params, device_budget_bytes=budget,
                        auto_expert_budget=True, auto_depth_after=2)
    _submit_pair(eng2)
    eng2.run()
    assert eng2.expert_stats()["expert_budget_retuned"]
    cache = eng2.plane.expert_cache
    if cache.capacity != cap0:                   # retune actually fired
        assert cache.capacity > cap0
        assert cache.bytes_used <= cache.capacity


def test_spec_streamed_moe_parity(params):
    """Speculative decoding composes with expert paging: verify lanes ride
    the chunk path, their routed experts enter the slab through the
    superset lane bound, and the greedy stream is unchanged."""
    from repro.serving.spec import SpecConfig
    ref = Engine(CFG, params, max_slots=1, max_seq=MAX_SEQ, kv_aware=False)
    rid = ref.submit([7] * 6, max_new=10)
    want = ref.run()[rid]
    store = PageStore(n_planes=8)
    eng = Engine(CFG, params, max_slots=1, max_seq=MAX_SEQ, kv_aware=False,
                 weight_store=store, stream_cfg=StreamConfig(),
                 spec_cfg=SpecConfig(k=3))
    rid = eng.submit([7] * 6, max_new=10)
    assert eng.run()[rid] == want
    assert eng.step_traces == 3
