"""Pallas TPU kernel: block-paged attention over the serving KV pool.

The pool is stacked over layers, ``(L, n_blocks, block_size, KV * Dh)``
(serving/kvcache.py: the heads folded into a lane-dense minor dimension),
and each call reads one ``layer`` of it. A slot's logical KV sequence is
scattered across blocks named by its block table — the
nano-vLLM paged layout, matching NVLLM's page-granular tiering (a pool
block is the software analogue of a NAND/DRAM page). The kernel computes
each slot's CHUNK of queries against that slot's cached CONTEXT only
(``kv_pos < ctx_len``): context tokens strictly precede every chunk query,
so the mask is uniform across the chunk and one kernel covers both decode
(1 query token) and chunked prefill (T_chunk query tokens). Causality
*within* the chunk is the caller's intra-chunk term, merged via the shared
online-softmax merge (models/common.chunk_attention_paged).

Mechanics (flash-decoding-style online softmax):

  * grid = (slots, max_blocks); the block axis is innermost so K/V tiles
    stream HBM->VMEM while per-slot accumulator state lives in revisited
    output blocks.
  * the layer, the block table and per-slot context lengths are SCALAR-
    PREFETCHED (``pltpu.PrefetchScalarGridSpec``): the K/V BlockSpec index
    maps read ``tbl[i, j]`` to fetch the j-th logical block of slot i of
    that layer from wherever it physically lives — the paging indirection
    costs one SMEM read, not a gather, and no layer slice of the pool is
    ever materialised.
  * blocks past the live length are skipped entirely (``pl.when``), so a
    short slot in a long-table batch costs no extra compute passes.
  * GQA folds (T, rep) into one query axis: with the uniform context mask
    the chunk case is literally the decode kernel at rep' = T * rep. Both
    contractions are one MXU ``dot_general`` per KV head, over that head's
    static lane slice of the tile, with f32 accumulation over the
    raw-dtype (bf16) pool, matching the XLA reference below (same dtype
    discipline as kernels/decode_attn.py).

Returns the UNNORMALIZED accumulator plus the (m, l) state so the caller
can merge the intra-chunk causal term before normalizing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _paged_attn_kernel(
    layer_ref, tbl_ref, len_ref, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
    *, block_size: int
):
    """Grid = (slots, max_blocks); the block axis innermost."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[i]                          # this slot's cached context

    @pl.when(j * block_size < length)            # dead blocks cost nothing
    def _block():
        q = q_ref[0]                             # (KV, TR, Dh), pool dtype
        n_kv, _, dh = q.shape
        # K/V tiles are (block_size, KV * Dh): head h is lanes
        # [h * Dh, (h + 1) * Dh), read as a static lane slice.
        heads = [(k_ref[:, pl.ds(h * dh, dh)], v_ref[:, pl.ds(h * dh, dh)])
                 for h in range(n_kv)]
        # scores (KV, TR, block_size): contract Dh, one MXU dot per head.
        s = jnp.stack([
            jax.lax.dot_general(q[h], kh, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for h, (kh, _) in enumerate(heads)])
        kv_pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, block_size), 2)
        mask = kv_pos < length
        s = jnp.where(mask, s, -jnp.inf)

        m_prev = m_ref[0]                        # (KV, TR)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        # guard fully-masked blocks (m_new = -inf) against NaN
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_ref[0] = l_ref[0] * alpha + jnp.sum(p, axis=-1)
        # p is scores-sized; cast to the pool dtype for the MXU PV
        # contraction (same choice as the XLA reference), accumulate f32.
        p = p.astype(v_ref.dtype)
        pv = jnp.stack([
            jax.lax.dot_general(p[h], vh, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for h, (_, vh) in enumerate(heads)])
        acc_ref[0] = acc_ref[0] * alpha[..., None] + pv
        m_ref[0] = m_new


def paged_attn_pallas(
    q: jnp.ndarray,             # (B, KV, TR, Dh) — pre-scaled, pool dtype
    k_pool: jnp.ndarray,        # (L, n_blocks, block_size, KV * Dh)
    v_pool: jnp.ndarray,
    layer,                      # int32 scalar — the layer to read
    block_tables: jnp.ndarray,  # (B, max_blocks) int32; 0 = unmapped
    ctx_lens: jnp.ndarray,      # (B,) int32 — cached context per slot
    *,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Raw pallas_call. Returns (acc, m, l): unnormalized online-softmax
    state, each f32 — acc (B, KV, TR, Dh); m, l (B, KV, TR)."""
    b, n_kv, tr, dh = q.shape
    n_layers, n_blocks, block_size, _ = k_pool.shape
    assert k_pool.shape == v_pool.shape == (
        n_layers, n_blocks, block_size, n_kv * dh), (
        q.shape, k_pool.shape, v_pool.shape)
    max_blocks = block_tables.shape[1]
    assert block_tables.shape == (b, max_blocks), block_tables.shape
    assert ctx_lens.shape == (b,), ctx_lens.shape

    kernel = functools.partial(_paged_attn_kernel, block_size=block_size)
    f32 = jnp.float32
    kv_spec = pl.BlockSpec(
        (None, None, block_size, n_kv * dh),
        lambda i, j, li, tbl, lens: (li[0], tbl[i, j], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,         # layer, block tables, context lengths
        grid=(b, max_blocks),
        in_specs=[
            pl.BlockSpec((1, n_kv, tr, dh),
                         lambda i, j, li, tbl, lens: (i, 0, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, n_kv, tr, dh),
                         lambda i, j, li, tbl, lens: (i, 0, 0, 0)),
            pl.BlockSpec((1, n_kv, tr), lambda i, j, li, tbl, lens: (i, 0, 0)),
            pl.BlockSpec((1, n_kv, tr), lambda i, j, li, tbl, lens: (i, 0, 0)),
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, n_kv, tr, dh), f32),
            jax.ShapeDtypeStruct((b, n_kv, tr), f32),
            jax.ShapeDtypeStruct((b, n_kv, tr), f32),
        ],
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      block_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      q, k_pool, v_pool)
    return acc, m, l


def paged_attn_xla(
    q: jnp.ndarray,             # (B, KV, TR, Dh) — pre-scaled, pool dtype
    k_pool: jnp.ndarray,        # (L, n_blocks, block_size, KV * Dh)
    v_pool: jnp.ndarray,
    layer,                      # int32 scalar — the layer to read
    block_tables: jnp.ndarray,  # (B, max_blocks) int32
    ctx_lens: jnp.ndarray,      # (B,) int32
    *,
    window: int | None = None,
    q_positions: jnp.ndarray | None = None,   # (B, TR) abs positions (window)
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The reference implementation: gather the layer's blocks through the
    table straight from the stacked pool (one gather, no layer slice), then
    plain masked online-softmax state — one source of truth the Pallas
    kernel is tested against, and the fallback for windowed attention
    (which needs a per-query mask the uniform-mask kernel does not carry).
    """
    b, n_kv, tr, dh = q.shape
    block_size = k_pool.shape[2]
    max_blocks = block_tables.shape[1]
    s_pad = max_blocks * block_size
    cdt = k_pool.dtype
    kg = k_pool[layer, block_tables].reshape(b, s_pad, n_kv, dh)
    vg = v_pool[layer, block_tables].reshape(b, s_pad, n_kv, dh)
    scores = jnp.einsum("bktd,bskd->bkts", q.astype(cdt), kg,
                        preferred_element_type=jnp.float32)
    pos = jnp.arange(s_pad)
    valid = (pos[None, :] < ctx_lens[:, None])[:, None, :]     # (B, 1, S)
    if window is not None:
        assert q_positions is not None, "windowed context needs q_positions"
        valid = valid & (pos[None, None, :]
                         > q_positions[:, :, None] - window)   # (B, TR, S)
    valid = valid[:, None]                                     # (B,1,1|TR,S)
    scores = jnp.where(valid, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1)                  # -inf for empty contexts
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(scores - m_safe[..., None])
    p = jnp.where(valid, p, 0.0)
    acc = jnp.einsum("bkts,bskd->bktd", p.astype(cdt), vg,
                     preferred_element_type=jnp.float32)
    l = jnp.sum(p, axis=-1)
    return acc, m, l
