"""Pallas TPU kernel: decode attention over a slot-contiguous KV cache.

One decode step attends each sequence's single query against that
sequence's contiguous (S_max, KV, Dh) cache region, masked by its live
length — the models' own decode path (``models/common.decode_attention``;
the serving engine's block-paged pool is kernels/paged_attn.py's). The
kernel is a flash-decoding-style online softmax:

  * grid = (slots, S blocks); the S axis is innermost so the (block_s, KV,
    Dh) K/V tiles stream HBM->VMEM through the Pallas pipeline while the
    per-slot accumulator state lives in revisited output blocks.
  * per-slot lengths ride in SMEM (scalar control, no VMEM traffic) and
    drive the validity mask `kv_pos < length` — slots never see each
    other's tokens and padding rows cost no extra passes.
  * GQA is computed natively in grouped (KV, rep, Dh) layout; both
    contractions are MXU `dot_general`s batched over KV heads with f32
    accumulation over the raw-dtype (bf16) cache, matching the XLA
    fallback's dtype discipline (models/common.decode_attention).

The kernel returns the UNNORMALIZED accumulator plus the (m, l) online-
softmax state so the caller can either normalize (plain decode attention)
or merge the current token's self-term analytically (the incremental form
used inside the models' decode scans, where the contiguous cache is
read-only; the serving engine's paged pool is read by
kernels/paged_attn.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _decode_attn_kernel(
    len_ref, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, *, block_s: int
):
    """Grid = (slots, s_blocks); s innermost (online-softmax accumulation)."""
    s_idx = pl.program_id(1)

    @pl.when(s_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[0]                          # this slot's live KV length
    q = q_ref[0]                                 # (KV, rep, Dh), pool dtype
    k = k_ref[0]                                 # (block_s, KV, Dh)
    v = v_ref[0]

    # scores (KV, rep, block_s): contract Dh, batch over KV heads (GQA).
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32,
    )
    kv_pos = s_idx * block_s + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, block_s), 2)
    mask = kv_pos < length
    s = jnp.where(mask, s, -jnp.inf)

    m_prev = m_ref[0]                            # (KV, rep)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    # guard fully-masked blocks (m_new = -inf) against NaN
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_ref[0] = l_ref[0] * alpha + jnp.sum(p, axis=-1)
    # p is scores-sized; cast to the cache dtype for the MXU PV contraction
    # (same choice as the XLA fallback) and accumulate in f32.
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32,
    )
    acc_ref[0] = acc_ref[0] * alpha[..., None] + pv
    m_ref[0] = m_new


def decode_attn_pallas(
    q: jnp.ndarray,          # (B, KV, rep, Dh) — pre-scaled, pool dtype
    k_pool: jnp.ndarray,     # (B, S_max, KV, Dh)
    v_pool: jnp.ndarray,     # (B, S_max, KV, Dh)
    lengths: jnp.ndarray,    # (B,) int32 — live prefix per slot
    *,
    block_s: int = 512,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Raw pallas_call. Returns (acc, m, l): unnormalized online-softmax
    state, each f32 — acc (B, KV, rep, Dh); m, l (B, KV, rep).

    ``S_max % block_s == 0`` required; ops.decode_attention_state picks a
    legal block.
    """
    b, n_kv, n_rep, dh = q.shape
    _, s_max, _, _ = k_pool.shape
    assert k_pool.shape == v_pool.shape == (b, s_max, n_kv, dh), (
        q.shape, k_pool.shape, v_pool.shape)
    assert lengths.shape == (b,), lengths.shape
    assert s_max % block_s == 0, (s_max, block_s)

    grid = (b, s_max // block_s)
    kernel = functools.partial(_decode_attn_kernel, block_s=block_s)
    f32 = jnp.float32
    acc, m, l = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i, j: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, n_kv, n_rep, dh), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((1, block_s, n_kv, dh), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, block_s, n_kv, dh), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n_kv, n_rep, dh), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((1, n_kv, n_rep), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, n_kv, n_rep), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n_kv, n_rep, dh), f32),
            jax.ShapeDtypeStruct((b, n_kv, n_rep), f32),
            jax.ShapeDtypeStruct((b, n_kv, n_rep), f32),
        ],
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k_pool, v_pool)
    return acc, m, l
