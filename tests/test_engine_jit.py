"""Compiled serving path: the jitted mixed-batch (chunked prefill + decode)
step must be token-identical to the eager reference, stay at ONE trace
across slot churn / chunked prefills / oversubscribed admission, and honor
per-slot decode positions (the seed `positions[:1]` bug)."""
from __future__ import annotations

import functools

import jax
import numpy as np
import pytest

from repro.configs.paper_models import OPT_TINY
from repro.core.erdpe import ExecMode
from repro.core.scheduler import AdmissionConfig
from repro.models import dense
from repro.serving.engine import Engine, _step_impl
from repro.serving.spec import SpecConfig


@pytest.fixture(scope="module")
def params():
    return dense.init(OPT_TINY, jax.random.PRNGKey(0))


def _engine(params, compiled, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq", 96)
    return Engine(OPT_TINY, params, rber=0.0, compiled=compiled, **kw)


def test_jitted_matches_eager_heterogeneous_batch(params):
    """Token-for-token identity on a two-slot continuous batch with
    different prompt lengths — one long enough to prefill in chunks
    (greedy, fixed seed)."""
    outs = {}
    for compiled in (False, True):
        eng = _engine(params, compiled)
        r1 = eng.submit(list(range(1, 30)), max_new=8)   # 29 tokens: 2 chunks
        r2 = eng.submit([9, 8], max_new=8)
        res = eng.run()
        outs[compiled] = (res[r1], res[r2])
    assert outs[True] == outs[False]


def test_decode_positions_are_per_slot(params):
    """Regression for the seed bug where Engine.step passed positions[:1],
    broadcasting slot 0's position to every slot: a short request decoded
    next to a longer one must produce the same tokens as the same request
    decoded alone (requests are independent under greedy sampling)."""
    solo = _engine(params, True, kv_aware=False)
    r_solo = solo.submit([9, 8], max_new=6)
    want = solo.run()[r_solo]

    both = _engine(params, True, kv_aware=False)
    both.submit([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11], max_new=6)
    r2 = both.submit([9, 8], max_new=6)
    got = both.run()[r2]
    assert got == want, "short slot must decode at ITS position, not slot 0's"


def test_single_trace_across_slot_churn(params):
    """Engine.step is exactly one compiled call per decode step: slot
    release + mid-run admission must not retrace (static shapes)."""
    eng = _engine(params, True)
    r1 = eng.submit([1, 2, 3], max_new=2)
    r2 = eng.submit([5, 6, 7, 8, 9], max_new=12)
    while not eng.requests[r1].done:
        eng.step()
    assert eng.step_traces == 1
    # r1's slot was released; admit a new request into it mid-run
    r3 = eng.submit([2, 2], max_new=4)
    out = eng.run()
    assert len(out[r2]) == 12 and len(out[r3]) == 4
    assert eng.step_traces == 1, "slot churn retraced the decode step"


def test_single_trace_mixed_workload(params):
    """Acceptance (ISSUE 2): a workload mixing prompt lengths, chunked
    prefills, slot churn, AND oversubscribed admission replays exactly one
    compiled trace."""
    eng = _engine(params, True,
                  admission_cfg=AdmissionConfig(chunk_tokens=8,
                                                token_budget=16))
    prompts = [[7], list(range(1, 9)), list(range(1, 21)),
               list(range(1, 30)), [3, 1, 4]]              # 5 reqs, 2 slots
    rids = [eng.submit(p, max_new=4 + i) for i, p in enumerate(prompts)]
    out = eng.run()
    assert [len(out[r]) for r in rids] == [4, 5, 6, 7, 8]
    assert eng.step_traces == 1, "mixed workload retraced the serving step"
    # chunked prefill actually happened (20/29-token prompts, 8-wide chunks)
    assert any(s["prefill_tokens"] and s["decode_tokens"] for s in eng.stats)


def test_decode_continues_during_prefill(params):
    """Chunked prefill must not block concurrent decoders: while a long
    prompt prefills over several steps, an already-decoding slot keeps
    producing a token every step."""
    eng = _engine(params, True,
                  admission_cfg=AdmissionConfig(chunk_tokens=8,
                                                token_budget=16))
    r1 = eng.submit([5, 6], max_new=40)
    for _ in range(3):
        eng.step()                                 # r1 is decoding now
    before = len(eng.requests[r1].out)
    r2 = eng.submit(list(range(1, 41)), max_new=4)  # 40 tokens: 5 chunks
    prefill_steps = 0
    while eng.requests[r2].prefilling:
        eng.step()
        prefill_steps += 1
    assert prefill_steps >= 5
    gained = len(eng.requests[r1].out) - before
    assert gained >= prefill_steps, "decode stalled behind a prefill"


def test_realloc_matches_eager(params):
    """Slot release/realloc mid-run: compiled and eager engines agree."""
    outs = {}
    for compiled in (False, True):
        eng = _engine(params, compiled)
        r1 = eng.submit([4, 4, 4], max_new=2)
        r2 = eng.submit([5, 6, 7], max_new=9)
        while not eng.requests[r1].done:
            eng.step()
        r3 = eng.submit([2, 2], max_new=4)
        res = eng.run()
        outs[compiled] = (res[r1], res[r2], res[r3])
    assert outs[True] == outs[False]


def _written_rows(args, block_size):
    """Every (block, offset) a step called with ``args`` may write outside
    the dump block: each slot's lanes ``t < q_lens`` (plus its draft lanes,
    up to ``draft_cap``, in a verify step) at position ``lengths + t``."""
    _, _, state, _, q_lens, _, tables = args[:7]
    lanes = np.asarray(q_lens).copy()
    if len(args) > 8:                    # (hist, hist_lens, draft_cap, ...)
        lanes += np.asarray(args[10])
    lengths, tables = np.asarray(state["lengths"]), np.asarray(tables)
    rows = set()
    for b, n in enumerate(lanes):
        for t in range(int(n)):
            pos = int(lengths[b]) + t
            rows.add((int(tables[b, pos // block_size]), pos % block_size))
    return rows


@pytest.mark.parametrize("spec_k", [None, 2], ids=["plain", "spec"])
def test_compiled_step_matches_eager_pool_rows(params, spec_k):
    """Over a mixed run (chunked prefill, decode, a slot released and
    re-admitted, and with ``spec_k`` speculative verify steps), every step
    of the compiled engine is replayed by the eager per-layer loop that
    ``compiled=False`` runs: the same tokens, lengths and pool at every
    real block. A step changes pool rows outside the dump block 0 only at
    its valid lanes' positions."""
    eng = _engine(params, True,
                  admission_cfg=AdmissionConfig(chunk_tokens=8,
                                                token_budget=16),
                  spec_cfg=SpecConfig(k=spec_k) if spec_k else None)
    eager = functools.partial(_step_impl, OPT_TINY, eng.sched_cfg,
                              eng.sample_cfg, eng.kv_aware, ExecMode.XLA,
                              True, eng.proposer, spec_k)
    compiled = eng._step_fn
    seen = {"continued_chunk": 0, "verify": 0}
    bs = eng.pool.block_size

    def both(*args):
        got = compiled(*args)
        want = eager(*args)
        state_in, q_lens = args[2], np.asarray(args[4])
        lengths = np.asarray(state_in["lengths"])
        seen["continued_chunk"] += int(np.any((q_lens > 1) & (lengths > 0)))
        if spec_k:
            seen["verify"] += int(np.any(np.asarray(args[10]) > 0))
        *toks_got, st_got, _ = got
        *toks_want, st_want, _ = want
        for a, b in zip(toks_got, toks_want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(st_got["lengths"]),
                                      np.asarray(st_want["lengths"]))
        allowed = _written_rows(args, bs)
        for name in ("k", "v"):
            new = np.asarray(st_got[name], np.float32)
            np.testing.assert_array_equal(
                new[:, 1:], np.asarray(st_want[name], np.float32)[:, 1:],
                err_msg=f"pool {name}: compiled != eager")
            old = np.asarray(state_in[name], np.float32)
            changed = np.any(new != old, axis=-1)       # (L, blocks, rows)
            _, blks, offs = np.nonzero(changed[:, 1:])
            stray = {(int(b) + 1, int(o)) for b, o in zip(blks, offs)} \
                - allowed
            assert not stray, f"pool {name} written off the valid lanes"
        return got

    eng._step_fn = both
    r1 = eng.submit([4, 4, 4], max_new=2)
    r2 = eng.submit([5, 6, 7, 5, 6, 7, 5, 6, 7, 5, 6, 7, 5, 6, 7, 5, 6, 7,
                     5, 6], max_new=12)                      # 3 chunks
    while not eng.requests[r1].done:
        eng.step()
    r3 = eng.submit([2, 3, 2, 3, 2, 3, 2, 3, 2, 3], max_new=8)  # r1's slot
    out = eng.run()
    assert [len(out[r]) for r in (r1, r2, r3)] == [2, 12, 8]
    assert eng.requests[r3].slot == eng.requests[r1].slot
    assert seen["continued_chunk"] > 0, "no chunk continued a prefill"
    if spec_k:
        assert seen["verify"] > 0, "no verify step drafted"


def test_pallas_decode_attention_end_to_end(params):
    """exec_mode=PALLAS (slot-paged decode-attention kernel, interpret on
    CPU) decodes the same greedy tokens as the XLA fallback."""
    xla = _engine(params, True)
    r_x = xla.submit([3, 1, 4, 1, 5], max_new=4)
    want = xla.run()[r_x]
    pal = _engine(params, True, exec_mode=ExecMode.PALLAS)
    r_p = pal.submit([3, 1, 4, 1, 5], max_new=4)
    got = pal.run()[r_p]
    assert got == want


def test_device_lengths_track_host_mirror(params):
    eng = _engine(params, True)
    eng.submit([1, 2, 3, 4], max_new=3)
    eng.submit([7, 7], max_new=5)
    eng.step()
    np.testing.assert_array_equal(np.asarray(eng.pool.lengths_dev),
                                  eng.pool.lengths)
    eng.run()
    np.testing.assert_array_equal(np.asarray(eng.pool.lengths_dev),
                                  eng.pool.lengths)


def test_padding_lanes_never_poison_the_pool():
    """Regression: a request decoding near the position-table boundary puts
    PADDING lanes past the table; an out-of-bounds jnp.take fills NaN under
    jit, and 0*NaN products in the intra-chunk term would poison valid
    lanes. The step must steer padding lanes to a safe table row."""
    import dataclasses as dc

    import jax.numpy as jnp

    cfg = dc.replace(OPT_TINY, max_seq=64)       # learned-position table: 64
    p = dense.init(cfg, jax.random.PRNGKey(1))
    eng = Engine(cfg, p, max_slots=2, max_seq=64, rber=0.0, compiled=True)
    rid = eng.submit(list(range(1, 41)), max_new=25)   # needs all 64 rows
    out = eng.run()[rid]
    assert len(out) == 25
    # every real (non-dump) pool block must stay finite: pre-fix, the NaN
    # embeddings of padding lanes reached valid lanes' attention outputs
    # (0 * NaN in the intra-chunk PV product) and were scattered into the
    # pool. (Exact token parity across DIFFERENT chunk widths is not
    # asserted — reordering the f32 accumulation can flip a near-tie
    # greedy argmax.)
    real = jnp.arange(1, eng.pool.n_blocks)
    assert not bool(jnp.any(jnp.isnan(
        eng.pool.k[:, real].astype(jnp.float32))))
    assert not bool(jnp.any(jnp.isnan(
        eng.pool.v[:, real].astype(jnp.float32))))


def test_submit_rejects_over_capacity(params):
    """Admission control: a request whose KV footprint exceeds max_seq must
    be rejected up front (the in-graph scatter would silently drop rows)."""
    eng = _engine(params, True, max_seq=16)
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit([1, 2, 3, 4], max_new=14)      # needs 17 rows > 16
    eng.submit([1, 2, 3, 4], max_new=13)          # exactly 16 rows: admitted


def test_submit_cap_is_exact_max_seq_not_block_rounded(params):
    """Regression: with max_seq not a multiple of block_size, the cap must
    stay the EXACT max_seq — rounding up to block granularity would admit
    valid lanes past the learned-position table (NaN fill under jit)."""
    eng = _engine(params, True, max_seq=60)       # table cap: 4 blocks = 64
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(list(range(1, 41)), max_new=25)   # 64 rows > 60
    eng.submit(list(range(1, 41)), max_new=21)        # 60 rows: admitted


def test_submit_caps_at_learned_position_table(params):
    """Regression: a pool sized past the learned-position table must not
    admit requests whose VALID lanes would jnp.take past the table (NaN
    fill under jit — unreachable by the padding-lane steering)."""
    import dataclasses as dc
    cfg = dc.replace(OPT_TINY, max_seq=32)        # 32-row pos_embed table
    p = dense.init(cfg, jax.random.PRNGKey(2))
    eng = Engine(cfg, p, max_slots=2, max_seq=64, rber=0.0)
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(list(range(1, 30)), max_new=5)     # 33 rows > 32-row table
    rid = eng.submit(list(range(1, 30)), max_new=4)   # 32 rows: admitted
    assert len(eng.run()[rid]) == 4


def test_submit_rejects_degenerate_requests(params):
    """Empty prompts would crash the decode lane (no token to feed) and
    max_new=0 would still sample one token past its bound — both are
    API-contract errors, rejected at submit."""
    eng = _engine(params, True, max_seq=64)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit([], max_new=4)
    with pytest.raises(ValueError, match="max_new"):
        eng.submit([1, 2, 3], max_new=0)
    assert not eng.requests and not eng.waiting   # nothing half-registered
