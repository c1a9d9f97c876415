"""Block-paged attention (kernels/paged_attn.py) vs the contiguous-pool
reference on ragged lengths, plus block-allocator property tests."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hyp_compat import given, settings, st

from repro.core.erdpe import ExecMode
from repro.kernels import ops
from repro.models import common as cm
from repro.serving.kvcache import PagedKVPool


N_LAYERS, LAYER = 3, 1       # the stacked pool's depth and the layer read


def _scatter_to_pool(k_ctx, v_ctx, ctx_lens, block_size, max_blocks, seed=0):
    """Scatter contiguous (B, S, KV, Dh) caches into layer ``LAYER`` of a
    stacked (L, n_blocks, block_size, KV * Dh) pool with a SCRAMBLED block
    assignment (physical layout must not matter); every other layer and
    every unmapped block holds garbage."""
    b, s, n_kv, dh = k_ctx.shape
    n_blocks = 1 + b * max_blocks
    rng = np.random.default_rng(seed)
    perm = rng.permutation(np.arange(1, n_blocks))
    tables = np.zeros((b, max_blocks), np.int32)
    shape = (N_LAYERS, n_blocks, block_size, n_kv * dh)
    k_pool = rng.normal(size=shape)                     # garbage fill
    v_pool = rng.normal(size=shape)
    pi = 0
    for i in range(b):
        for j in range(-(-int(ctx_lens[i]) // block_size)):
            blk = int(perm[pi]); pi += 1
            tables[i, j] = blk
            lo, hi = j * block_size, min((j + 1) * block_size, s)
            k_pool[LAYER, blk, :hi - lo] = np.asarray(k_ctx)[i, lo:hi] \
                .reshape(hi - lo, -1)
            v_pool[LAYER, blk, :hi - lo] = np.asarray(v_ctx)[i, lo:hi] \
                .reshape(hi - lo, -1)
    return (jnp.asarray(k_pool, k_ctx.dtype), jnp.asarray(v_pool, v_ctx.dtype),
            jnp.asarray(tables))


def _mk(key, b, s, t, h, n_kv, dh, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    mk = lambda k, shape: jax.random.normal(k, shape, jnp.float32).astype(dtype)
    return (mk(ks[0], (b, t, h, dh)), mk(ks[1], (b, s, n_kv, dh)),
            mk(ks[2], (b, s, n_kv, dh)), mk(ks[3], (b, t, n_kv, dh)),
            mk(ks[4], (b, t, n_kv, dh)))


@pytest.mark.parametrize("b,t,h,n_kv,dh,block_size", [
    (1, 1, 4, 4, 32, 16),       # MHA decode (T=1)
    (3, 5, 4, 2, 32, 8),        # GQA chunk, ragged lengths
    (2, 7, 8, 1, 16, 4),        # MQA, tiny blocks
])
def test_paged_state_matches_contiguous_reference(b, t, h, n_kv, dh,
                                                  block_size):
    """Pallas (interpret) and XLA paged context states both equal the
    contiguous-pool masked-softmax reference on ragged lengths — the paging
    indirection must be invisible."""
    s, max_blocks = 32, 32 // block_size
    q, kc, vc, _, _ = _mk(jax.random.PRNGKey(b * t + h), b, s, t, h, n_kv, dh)
    ctx = jnp.asarray([(11 * (i + 1)) % (s + 1) for i in range(b)], jnp.int32)
    k_pool, v_pool, tables = _scatter_to_pool(kc, vc, ctx, block_size,
                                              max_blocks)
    # contiguous reference: same grouped-query math over the padded cache
    qg = ops._group_chunk_queries(q, n_kv, kc.dtype)
    scores = jnp.einsum("bktd,bskd->bkts", qg, kc,
                        preferred_element_type=jnp.float32)
    valid = (jnp.arange(s)[None, :] < ctx[:, None])[:, None, None, :]
    scores = jnp.where(valid, scores, -jnp.inf)
    m_ref = jnp.max(scores, axis=-1)
    p = jnp.where(valid, jnp.exp(scores - jnp.where(
        jnp.isfinite(m_ref), m_ref, 0.0)[..., None]), 0.0)
    acc_ref = jnp.einsum("bkts,bskd->bktd", p.astype(kc.dtype), vc,
                         preferred_element_type=jnp.float32)
    l_ref = jnp.sum(p, axis=-1)

    for impl, (acc, m, l) in {
        "xla": ops.paged_attention_state_xla(q, k_pool, v_pool, LAYER,
                                             tables, ctx),
        "pallas": ops.paged_attention_state(q, k_pool, v_pool, LAYER, tables,
                                            ctx, interpret=True),
    }.items():
        np.testing.assert_allclose(np.asarray(m), np.asarray(m_ref),
                                   rtol=1e-5, atol=1e-5, err_msg=impl)
        np.testing.assert_allclose(np.asarray(l), np.asarray(l_ref),
                                   rtol=1e-5, atol=1e-5, err_msg=impl)
        np.testing.assert_allclose(np.asarray(acc), np.asarray(acc_ref),
                                   rtol=1e-5, atol=1e-5, err_msg=impl)


@pytest.mark.parametrize("mode", [ExecMode.XLA, ExecMode.PALLAS])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chunk_attention_matches_full_causal(mode, dtype):
    """End-to-end chunk attention (paged context + intra-chunk causal,
    merged) == full causal attention over [context ; chunk], per slot with
    ragged context lengths including an EMPTY context (fresh prefill)."""
    b, s, t, h, n_kv, dh, bs = 3, 24, 5, 4, 2, 16, 8
    q, kc, vc, kn, vn = _mk(jax.random.PRNGKey(9), b, s, t, h, n_kv, dh,
                            dtype)
    ctx = jnp.asarray([0, 7, 24], jnp.int32)
    k_pool, v_pool, tables = _scatter_to_pool(kc, vc, ctx, bs, s // bs)
    got = cm.chunk_attention_paged(q, k_pool, v_pool, LAYER, tables, ctx,
                                   kn, vn, mode=mode)
    outs = []
    for i in range(b):
        c = int(ctx[i])
        kk = jnp.concatenate([kc[i:i + 1, :c], kn[i:i + 1]], axis=1)
        vv = jnp.concatenate([vc[i:i + 1, :c], vn[i:i + 1]], axis=1)
        outs.append(cm.chunked_attention(q[i:i + 1], kk, vv, causal=True,
                                         q_offset=c))
    want = jnp.concatenate(outs, 0)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 else \
        dict(rtol=2e-2, atol=2e-2)
    got32, want32 = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got32, want32, **tol)
    assert not np.any(np.isnan(got32))


def test_decode_is_chunk_of_one():
    """The T=1 chunk case reproduces decode_attention_incremental on the
    equivalent contiguous cache (the engine's decode lane IS this case)."""
    b, s, h, n_kv, dh, bs = 2, 32, 4, 2, 16, 8
    q, kc, vc, kn, vn = _mk(jax.random.PRNGKey(3), b, s, 1, h, n_kv, dh)
    ctx = jnp.asarray([5, 32], jnp.int32)
    k_pool, v_pool, tables = _scatter_to_pool(kc, vc, ctx, bs, s // bs)
    got = cm.chunk_attention_paged(q, k_pool, v_pool, LAYER, tables, ctx,
                                   kn, vn)
    want = cm.decode_attention_incremental(q, kc, vc, ctx, kn, vn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --- block allocator properties ----------------------------------------------


@settings(max_examples=30, deadline=None)
@given(ops_seq=st.lists(
    st.tuples(st.integers(0, 3),                 # slot id
              st.sampled_from(["alloc", "grow", "release"]),
              st.integers(1, 24)),               # need / grow-to tokens
    min_size=1, max_size=40))
def test_block_allocator_invariants(ops_seq):
    """No double-alloc, free restores capacity, block-table/lengths stay
    consistent under arbitrary alloc/grow/release interleavings."""
    pool = PagedKVPool(1, 4, 24, 2, 4, block_size=4, n_blocks=13)
    total = pool.n_blocks - 1                    # block 0 is the dump block
    live: dict[int, int] = {}                    # slot -> target length
    for rid, (slot_hint, op, n) in enumerate(ops_seq):
        if op == "alloc" and slot_hint not in live:
            s = pool.alloc(rid, need_tokens=n)
            if s is not None:
                live[s] = n
        elif op == "grow" and live:
            s = sorted(live)[slot_hint % len(live)]
            new_len = min(n, live[s])            # never past the reservation
            pool.ensure(s, new_len)
            pool.lengths[s] = max(pool.lengths[s], new_len)
        elif op == "release" and live:
            s = sorted(live)[slot_hint % len(live)]
            pool.release(s)
            del live[s]
        # -- invariants after every operation --------------------------------
        mapped = pool.block_tables[np.nonzero(pool.block_tables)]
        assert len(set(mapped.tolist())) == len(mapped), "double-mapped block"
        assert 0 not in mapped, "dump block handed out"
        assert np.all(pool.ref_count[np.asarray(mapped, int)] == 1)
        # mapped + free + reserved always accounts for every real block
        assert (len(mapped) + len(pool.free_blocks) - (pool.n_blocks - 1)
                == 0), "blocks leaked or duplicated"
        assert pool.n_free_blocks >= 0, "reservations oversubscribed"
        for s in live:
            assert pool.capacity(s) >= pool.lengths[s], \
                "lengths ran past the mapped block table"
    for s in list(live):
        pool.release(s)
    assert len(pool.free_blocks) == total and pool.n_free_blocks == total


def test_allocator_no_double_alloc_exhaustive():
    pool = PagedKVPool(1, 2, 16, 2, 4, block_size=4, n_blocks=5)  # 4 real
    s1 = pool.alloc(0, need_tokens=8)
    s2 = pool.alloc(1, need_tokens=8)
    pool.ensure(s1, 8)
    pool.ensure(s2, 8)
    used = set(pool.block_tables[s1, :2].tolist()) \
        | set(pool.block_tables[s2, :2].tolist())
    assert len(used) == 4 and 0 not in used
    assert pool.n_free_blocks == 0
    assert pool.alloc(2, need_tokens=4) is None  # exhausted, not corrupted
    pool.release(s1)
    assert pool.alloc(3, need_tokens=8) is not None
