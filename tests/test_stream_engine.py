"""Streamed serving (FlashStore weight tier): the engine must serve a model
whose flash tier exceeds the device weight budget, token-identical to the
fully-resident engine, through exactly three compiled traces (ISSUE 3)."""
from __future__ import annotations

import jax
import pytest

from repro.configs.paper_models import OPT_TINY
from repro.models import dense
from repro.serving.engine import Engine
from repro.store import PageStore, StreamConfig

MAX_SEQ = 96


@pytest.fixture(scope="module")
def params():
    return dense.init(OPT_TINY, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def resident_tokens(params):
    """Greedy reference outputs from the fully-resident compiled engine."""
    eng = Engine(OPT_TINY, params, max_slots=2, max_seq=MAX_SEQ, rber=0.0)
    eng.submit(list(range(1, 30)), max_new=8)     # chunked prefill
    eng.submit([9, 8], max_new=8)
    return eng.run()


def _streamed(params, **stream_kw):
    store = PageStore(n_planes=8)
    eng = Engine(OPT_TINY, params, max_slots=2, max_seq=MAX_SEQ, rber=0.0,
                 weight_store=store, stream_cfg=StreamConfig(**stream_kw))
    return eng, store


def test_streamed_matches_resident(params, resident_tokens):
    eng, _ = _streamed(params, group_size=1)
    eng.submit(list(range(1, 30)), max_new=8)
    eng.submit([9, 8], max_new=8)
    assert eng.run() == resident_tokens


def test_streamed_under_budget_smaller_than_flash_tier(params,
                                                       resident_tokens):
    """THE acceptance property: a device weight budget SMALLER than the
    flash tier still serves, with token parity, and actually streams."""
    probe = PageStore()                 # programming populates total_bytes
    Engine(OPT_TINY, params, max_slots=2, max_seq=MAX_SEQ,
           weight_store=probe, stream_cfg=StreamConfig(pin_edges=False))
    budget = int(probe.total_bytes * 0.7)
    eng, store = _streamed(params, group_size=1, prefetch_depth=2,
                           device_budget_bytes=budget)
    assert store.total_bytes > budget            # model > device memory
    eng.submit(list(range(1, 30)), max_new=8)
    eng.submit([9, 8], max_new=8)
    assert eng.run() == resident_tokens
    st = eng.stream_stats()
    assert st["bytes_streamed"] > 0 and st["groups_streamed"] > 0
    assert st["pages_read"] > 0 and st["nand_seconds"] > 0


def test_streamed_pin_all_matches_resident(params, resident_tokens):
    """pin_all=True degenerates to the fully-resident engine: everything
    cached at init, zero bytes streamed during serving."""
    eng, _ = _streamed(params, group_size=2, pin_all=True)
    eng.submit(list(range(1, 30)), max_new=8)
    eng.submit([9, 8], max_new=8)
    assert eng.run() == resident_tokens
    st = eng.stream_stats()
    assert st["bytes_streamed"] == 0
    assert st["cache_hits"] > 0 and st["cache_misses"] == 0


def test_streamed_three_traces_across_churn(params):
    """embed + ONE shared group trace + finish == 3 traces, stable across
    slot churn, chunked prefill, group count, and step count."""
    eng, _ = _streamed(params, group_size=1)     # 4 groups per step
    r1 = eng.submit([1, 2, 3], max_new=2)
    eng.submit([5, 6, 7, 8, 9], max_new=10)
    while not eng.requests[r1].done:
        eng.step()
    assert eng.step_traces == 3
    eng.submit(list(range(1, 20)), max_new=4)    # admit into freed slot
    eng.run()
    assert eng.step_traces == 3, "layer groups or churn retraced"


def test_streamed_hot_pins(params):
    """lm_head and the first/last layer groups are pinned when the budget
    allows; the middle streams and the pinned edges hit every step."""
    eng, _ = _streamed(params, group_size=1)     # unbounded budget
    rid = eng.submit([3, 1, 4], max_new=4)
    eng.run()
    cache = eng.plane.cache
    assert "lm_head" in cache
    assert 0 in cache and eng.plane.n_groups - 1 in cache
    st = eng.stream_stats()
    assert st["cache_hits"] > 0                  # pinned edges re-used
    assert len(eng.requests[rid].out) == 4


def test_streamed_rejects_impossible_budget(params):
    with pytest.raises(ValueError, match="device_budget"):
        _streamed(params, group_size=1, device_budget_bytes=1024)


def test_streamed_requires_compiled(params):
    store = PageStore()
    with pytest.raises(ValueError, match="compiled"):
        Engine(OPT_TINY, params, compiled=False, weight_store=store)


def test_streamed_group_size_must_divide_layers(params):
    with pytest.raises(ValueError, match="group_size"):
        _streamed(params, group_size=3)          # OPT_TINY has 4 layers


def test_stall_heavy_engine_shrinks_prefill_share(params):
    """Residency-aware admission: the engine's measured stall fraction
    contracts the step token budget (scheduler.step_token_budget), so a
    stall-heavy streamed engine plans SMALLER prefill chunks than a
    stall-free one while decoders keep their lanes."""
    import repro.core.scheduler as sched

    def prefill_first_step(stall_frac):
        eng, _ = _streamed(params, group_size=1)
        eng.submit([5, 6], max_new=30)
        for _ in range(3):
            eng.step()                       # slot 0 is decoding now
        eng._stall_frac = stall_frac         # the signal under test
        eng.submit(list(range(1, 40)), max_new=4)    # 39-token prompt
        eng.step()
        return eng.stats[-1]["prefill_tokens"]

    free = prefill_first_step(0.0)
    stalled = prefill_first_step(0.95)
    assert stalled < free, "stall fraction must contract the prefill share"
    assert stalled >= 0 and free > 0
    # and the engine actually RECORDS a stall fraction every streamed step
    eng, _ = _streamed(params, group_size=1)
    eng.submit([1, 2, 3], max_new=3)
    eng.run()
    assert all(0.0 <= s["stall_frac"] <= 1.0 for s in eng.stats)
    # the budget function itself is covered in tests/test_scheduler.py
    assert sched.step_token_budget(sched.AdmissionConfig(), 1.0, 0.9) < \
        sched.step_token_budget(sched.AdmissionConfig(), 1.0, 0.0)


def test_auto_depth_retunes_prefetch_from_telemetry(params):
    """Overlap-depth auto-tuning: after the first measured steps the
    engine re-picks prefetch_depth from stall/stream telemetry, within
    what the device budget affords, and re-splits window vs cache bytes."""
    probe = PageStore()
    Engine(OPT_TINY, params, max_slots=2, max_seq=MAX_SEQ,
           weight_store=probe, stream_cfg=StreamConfig(pin_edges=False))
    budget = int(probe.total_bytes * 0.7)
    eng, _ = _streamed(params, group_size=1, prefetch_depth=1,
                       device_budget_bytes=budget, auto_depth=True,
                       auto_depth_after=3)
    eng.submit(list(range(1, 20)), max_new=12)
    eng.run()
    pl = eng.plane
    assert pl._auto_depth_done, "auto-tune never ran"
    depth = pl.streamer.prefetch_depth
    assert depth >= 1
    if pl.stream_cfg.device_budget_bytes is not None:
        afford = (budget - pl.cache.pinned_bytes) // pl._group_bytes
        assert depth <= max(afford, 1)
        # budget re-split: window bytes + cache capacity never exceed it
        if not pl.stream_cfg.pin_all and depth != 1:
            assert pl.cache.capacity + depth * pl._group_bytes <= budget \
                or pl.cache.capacity == pl.cache.pinned_bytes
        # and RESIDENT bytes were trimmed to the new capacity eagerly —
        # a deeper window reclaims its bytes at retune time, not at some
        # future insert (the device budget holds at every moment)
        if pl.cache.capacity is not None:
            assert pl.cache.bytes_used <= max(pl.cache.capacity,
                                              pl.cache.pinned_bytes)
    # parity is untouched by depth choices (greedy, same prompts)
    ref = Engine(OPT_TINY, params, max_slots=2, max_seq=MAX_SEQ, rber=0.0)
    rid = ref.submit(list(range(1, 20)), max_new=12)
    want = ref.run()[rid]
    got = next(iter(eng.requests.values())).out
    assert got == want


def test_serve_from_persisted_die_image(params, resident_tokens, tmp_path):
    """ROADMAP "serve from the persisted die image": a deploy-written image
    (flash tier + attn flash copies) opened READ-ONLY serves with StoreRefs
    rebuilt from its page table and nothing re-programmed — token-identical
    to the resident engine."""
    from repro.core.tiering import dram_tier
    # program an image the way deploy --store does: deploy entries + the
    # per-layer attn flash copies with the engine's seed derivation
    _, store = _streamed(params, group_size=1)
    img = str(tmp_path / "nand.img")
    store.save(img)
    opened = PageStore.open(img)
    eng = Engine(OPT_TINY, dram_tier(params), max_slots=2, max_seq=MAX_SEQ,
                 weight_store=opened, stream_cfg=StreamConfig(group_size=1))
    assert eng.plane.store_preprogrammed
    assert opened.n_pages == store.n_pages        # nothing was programmed
    eng.submit(list(range(1, 30)), max_new=8)
    eng.submit([9, 8], max_new=8)
    assert eng.run() == resident_tokens
    assert eng.step_traces == 3


def test_read_only_image_without_attn_copies_rejected(params, tmp_path):
    """An image lacking the attn flash copies cannot be fixed read-only:
    the engine must say so instead of dying inside NAND programming."""
    from repro.core.tiering import deploy, dram_tier
    store = PageStore()
    deploy(params, store=store)                   # no attn copies emitted
    img = str(tmp_path / "bare.img")
    store.save(img)
    with pytest.raises(ValueError, match="attn flash copies"):
        Engine(OPT_TINY, dram_tier(params), max_slots=2, max_seq=MAX_SEQ,
               weight_store=PageStore.open(img),
               stream_cfg=StreamConfig(group_size=1))


# --- fault plane: streamer worker failure isolation (ISSUE 9) -----------------

def test_transient_fetch_failure_recovers_with_token_parity(params,
                                                            resident_tokens):
    """A window fetch that fails ONCE (flaky NAND channel) is retried by
    the streamer worker with backoff — serving completes with tokens
    bit-identical to the fault-free run, and the retry is counted."""
    eng, _ = _streamed(params, group_size=1)
    eng.plane.streamer.retry_backoff_s = 0.001
    orig = eng.plane.streamer._window
    state = {"calls": 0}

    def flaky(g):
        state["calls"] += 1
        if state["calls"] == 3:              # one mid-stream hiccup
            raise IOError("injected transient channel fault")
        return orig(g)

    eng.plane.streamer._window = flaky
    eng.submit(list(range(1, 30)), max_new=8)
    eng.submit([9, 8], max_new=8)
    assert eng.run() == resident_tokens      # no token divergence
    st = eng.plane.streamer.stats()
    assert st["fetch_retries"] == 1 and st["fetch_faults"] == 0


def test_persistent_fetch_failure_raises_typed_storefault(params):
    """A fetch that fails past the retry budget surfaces as a typed
    StoreFault out of Engine.step (not a hang, not a bare worker death);
    the stream queue drains and close() returns promptly."""
    import threading

    from repro.store.faults import StoreFault

    eng, _ = _streamed(params, group_size=1)
    eng.plane.streamer.retry_backoff_s = 0.001
    eng.plane.streamer.max_fetch_retries = 1

    def dead(g):
        raise IOError("dead channel")

    eng.submit([1, 2, 3], max_new=2)
    eng.plane.streamer._window = dead
    with pytest.raises(StoreFault) as ei:
        eng.step()
    assert isinstance(ei.value.__cause__, IOError)
    assert eng.plane.streamer.stats()["fetch_faults"] == 1
    assert eng.plane.streamer.stats()["fetch_retries"] == 1
    t = threading.Thread(target=eng.close)   # must not hang on the queue
    t.start()
    t.join(timeout=30)
    assert not t.is_alive(), "Engine.close() hung after a streamer fault"
