"""Shared functional layers for the model zoo.

Pure functions over explicit param pytrees (no module framework). Attention
is memory-bounded via KV-block-chunked online softmax so 32k-prefill /
4k-train shapes never materialize (S, S) score matrices. FFN-type matmuls
route through ``core.erdpe.maybe_flash_matmul`` so the same forward code
serves bf16 training params and flash-tier (INT8+ECC) deployed params.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.erdpe import ExecMode, maybe_flash_matmul

Params = Any
DEFAULT_Q_BLOCK = 512
DEFAULT_KV_BLOCK = 1024


import os as _os

# Sequence-sharded residual stream between layers (the Megatron-SP analogue):
# the layer-scan activation stash shards its seq dim over "model", cutting
# stash HBM by the model-axis width; XLA inserts the all-gather before
# attention and the reduce-scatter after wo. Toggle for §Perf ablations.
# Default OFF: measured on llama3-405b train_4k, seq-sharding the residual
# cuts the stash 16x but makes XLA materialize *unsharded* f32 weight grads
# (collective term 299s -> 3193s). Kept as a knob for §Perf ablations.
SEQ_SHARD_RESIDUAL = _os.environ.get("REPRO_SEQ_SHARD", "0") != "0"


def pin_layer_grads(lp):
    """Pin every weight cotangent of a (sliced) layer pytree to its rule
    sharding, INSIDE the layer-scan body.

    Pinning only the stacked params outside the scan constrains the stacked
    dW after accumulation; the per-iteration dW inside the loop is still
    materialized unsharded and all-reduced (measured 1.1 TB/chip/step of
    expert-grad all-reduce on qwen3-moe train_4k). No-op outside a mesh.
    """
    import jax.tree_util as jtu
    from repro.launch import sharding as sh
    env_mesh = jax.sharding.get_abstract_mesh()
    if env_mesh.empty:
        return lp

    def one(path, w):
        if w.ndim < 2:
            return w
        spec = sh.spec_for_param(sh._path_str(path), w.shape, env_mesh)
        return sh.pin_grad(w, tuple(spec))

    return jtu.tree_map_with_path(one, lp)


def pin_batch(x):
    """Pin activation sharding at the top of every layer-scan body.

    Without it XLA is free to drop the batch sharding of the scan carry,
    which replicates the activation stash across the data axis (observed
    16x temp blowup on llama3-405b train_4k — EXPERIMENTS.md §Perf).
    With SEQ_SHARD_RESIDUAL the seq dim additionally shards over "model"
    (full-sequence forwards only). No-op outside a mesh.
    """
    from repro.launch.sharding import constrain
    # The barrier stops XLA from sinking the rms_norm f32 upcast into the
    # layer-scan stash, which would store the carry TWICE (bf16 + f32):
    # measured -33.8 GB/chip on llama3-405b train_4k (EXPERIMENTS.md §Perf).
    x = jax.lax.optimization_barrier(x)
    if SEQ_SHARD_RESIDUAL and x.ndim >= 3 and x.shape[1] > 1:
        return constrain(x, ("pod", "data"), "model",
                         *([None] * (x.ndim - 2)))
    return constrain(x, ("pod", "data"), *([None] * (x.ndim - 1)))


# --- initializers -----------------------------------------------------------

def dense_init(key, k, n, dtype=jnp.bfloat16):
    scale = (2.0 / (k + n)) ** 0.5
    return (jax.random.normal(key, (k, n), jnp.float32) * scale).astype(dtype)


def embed_init(key, v, d, dtype=jnp.bfloat16):
    return (jax.random.normal(key, (v, d), jnp.float32) * 0.02).astype(dtype)


# --- norms ------------------------------------------------------------------

def rms_norm(x, gamma, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * (1.0 + gamma.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, gamma, beta, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(x.dtype)


# --- rotary -----------------------------------------------------------------

def rope_freqs(head_dim: int, base: float = 10000.0) -> jnp.ndarray:
    return 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, base: float = 10000.0):
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, base)                                   # (Dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs      # (..., S, Dh/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --- chunked attention (online softmax over KV blocks) -----------------------

def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B, S, KV, Dh) -> (B, S, KV*n_rep, Dh) for GQA."""
    if n_rep == 1:
        return k
    b, s, kv, dh = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, dh)).reshape(
        b, s, kv * n_rep, dh)


def chunked_attention(
    q: jnp.ndarray,            # (B, Sq, H, Dh)
    k: jnp.ndarray,            # (B, Skv, KV, Dh)
    v: jnp.ndarray,            # (B, Skv, KV, Dh)
    causal: bool = True,
    q_offset: int | jnp.ndarray = 0,
    window: int | None = None,
    kv_block: int = DEFAULT_KV_BLOCK,
) -> jnp.ndarray:
    """Online-softmax attention, scanning KV blocks; memory O(Sq * kv_block).

    ``q_offset``: absolute position of q[0] (prefill: 0; decode: kv_len-1).
    ``window``: local attention window (RecurrentGemma); None = global.
    """
    b, sq, h, dh = q.shape
    _, skv, n_kv, _ = k.shape
    n_rep = h // n_kv
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = dh ** -0.5
    # contractions run in the INPUT dtype with f32 accumulation (MXU-native
    # for bf16 models): upcasting K/V to f32 materializes 2x copies of the
    # whole sequence per layer (same pathology as decode, §Perf C4).
    cdt = k.dtype
    qf = (q.astype(jnp.float32) * scale).astype(cdt).transpose(0, 2, 1, 3)

    nblk = -(-skv // kv_block)
    pad = nblk * kv_block - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.transpose(0, 2, 1, 3).reshape(b, h, nblk, kv_block, dh)
    vb = v.transpose(0, 2, 1, 3).reshape(b, h, nblk, kv_block, dh)

    q_pos = jnp.asarray(q_offset) + jnp.arange(sq)                 # (Sq,)

    def body(carry, blk):
        m, l, acc = carry
        kblk, vblk, blk_idx = blk
        kv_pos = blk_idx * kv_block + jnp.arange(kv_block)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kblk,
                       preferred_element_type=jnp.float32)
        if causal:
            mask = kv_pos[None, :] <= q_pos[:, None]
        else:
            mask = jnp.ones((sq, kv_block), bool)
        mask = mask & (kv_pos[None, :] < skv)
        if window is not None:
            mask = mask & (kv_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows (m_new = -inf) against NaN
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        # p is scores-sized (>> V block): keep it f32 and upcast the small V
        # block instead — the opposite choice from decode, where the cache
        # dwarfs the probabilities (§Perf C4).
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32))
        return (m_safe, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (kb.transpose(2, 0, 1, 3, 4), vb.transpose(2, 0, 1, 3, 4),
         jnp.arange(nblk)),
    )
    out = acc / jnp.maximum(l[..., None], 1e-20)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)               # (B,Sq,H,Dh)


def merge_attn_states(acc1, m1, l1, acc2, m2, l2):
    """Merge two unnormalized online-softmax states over disjoint key sets
    and normalize: acc (..., Dh) f32, m/l (...) f32 (m may be -inf where a
    state saw only masked keys). The single source of the merge algebra —
    decode's self-term, chunked prefill's intra-chunk term, and the paged
    context state all combine through here, so the math can't
    desynchronize between exec modes or phases."""
    m = jnp.maximum(m1, m2)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    a1 = jnp.where(jnp.isfinite(m1), jnp.exp(m1 - m_safe), 0.0)
    a2 = jnp.where(jnp.isfinite(m2), jnp.exp(m2 - m_safe), 0.0)
    acc = acc1 * a1[..., None] + acc2 * a2[..., None]
    l = l1 * a1 + l2 * a2
    return acc / jnp.maximum(l[..., None], 1e-20)


def _merge_self_term(acc, m, l, s_self, v_self):
    """Merge the current token's self-term into unnormalized online-softmax
    state and normalize: acc (B, KV, R, Dh) f32, m/l (B, KV, R) (m may be
    -inf for empty caches), s_self (B, KV, R) scores, v_self (B, KV, Dh)
    f32. The self token is a one-key state (m2 = s_self, l2 = 1,
    acc2 = v_self) fed to the shared ``merge_attn_states``."""
    acc_self = jnp.broadcast_to(v_self[:, :, None, :], acc.shape)
    return merge_attn_states(acc, m, l, acc_self, s_self,
                             jnp.ones_like(s_self))


def decode_attention_incremental(
    q: jnp.ndarray,            # (B, 1, H, Dh)
    k_cache: jnp.ndarray,      # (B, S, KV, Dh) — READ-ONLY (token t absent)
    v_cache: jnp.ndarray,
    kv_len,                    # scalar or (B,) — valid prefix length
    k_new: jnp.ndarray,        # (B, 1, KV, Dh) — this token's K/V
    v_new: jnp.ndarray,
    window: int | None = None,
    mode: ExecMode = ExecMode.XLA,
) -> jnp.ndarray:
    """Decode attention over cache[0:kv_len] + the new token, WITHOUT
    writing the cache: the self-token term is combined analytically
    (online-softmax merge). Keeping the cache read-only inside the layer
    scan avoids per-layer full-cache rewrites (EXPERIMENTS.md §Perf).

    ``mode=ExecMode.PALLAS`` routes the cache half to the slot-paged Pallas
    kernel (kernels/decode_attn.py; global attention only) and merges the
    self-term into the kernel's returned online-softmax state.
    """
    b, s, n_kv, dh = k_cache.shape
    h = q.shape[2]
    n_rep = h // n_kv
    scale = dh ** -0.5
    # bf16 x bf16 -> f32 contractions (MXU-native): casting the cache to f32
    # materializes a 2x-sized copy of the whole cache per layer on the
    # non-fusing path (measured 24 GB/step at 32k — EXPERIMENTS.md §Perf).
    cdt = k_cache.dtype
    qf = ((q.astype(jnp.float32)[:, 0] * scale)
          .reshape(b, n_kv, n_rep, dh).astype(cdt))
    s_self = jnp.einsum("bkrd,bkd->bkr", qf, k_new[:, 0].astype(cdt),
                        preferred_element_type=jnp.float32)     # (B,KV,R)
    if mode == ExecMode.PALLAS and window is None:
        from repro.kernels import ops
        lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))
        acc, m, l = ops.decode_attention_state(q[:, 0], k_cache, v_cache, lens)
    else:
        scores = jnp.einsum("bkrd,bskd->bkrs", qf, k_cache,
                            preferred_element_type=jnp.float32)
        pos = jnp.arange(s)
        valid = pos[None, :] < jnp.reshape(jnp.asarray(kv_len), (-1, 1))
        if window is not None:
            valid = valid & (pos[None, :]
                             >= jnp.reshape(jnp.asarray(kv_len), (-1, 1)) - window + 1)
        scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
        m = jnp.max(scores, axis=-1)                  # -inf for empty caches
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        p_old = jnp.exp(scores - m_safe[..., None])
        p_old = jnp.where(valid[:, None, None, :], p_old, 0.0)
        acc = jnp.einsum("bkrs,bskd->bkrd", p_old.astype(cdt), v_cache,
                         preferred_element_type=jnp.float32)
        l = jnp.sum(p_old, axis=-1)
    out = _merge_self_term(acc, m, l, s_self,
                           v_new.astype(jnp.float32)[:, 0])
    return out.reshape(b, 1, h, dh).astype(q.dtype)


def decode_attention(
    q: jnp.ndarray,            # (B, 1, H, Dh)
    k_cache: jnp.ndarray,      # (B, S, KV, Dh)
    v_cache: jnp.ndarray,
    kv_len: jnp.ndarray,       # (B,) or scalar — valid prefix length
    window: int | None = None,
    mode: ExecMode = ExecMode.XLA,
) -> jnp.ndarray:
    """Single-token decode attention over a (padded) KV cache.

    ``mode`` mirrors the erdpe.flash_matmul split: PALLAS runs the
    slot-paged online-softmax kernel (kernels/decode_attn.py; global
    attention only — windowed callers fall back to XLA), XLA the plain
    masked-softmax math below.
    """
    b, s, n_kv, dh = k_cache.shape
    h = q.shape[2]
    n_rep = h // n_kv
    scale = dh ** -0.5
    cdt = k_cache.dtype
    if mode == ExecMode.PALLAS and window is None:
        from repro.kernels import ops
        lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))
        acc, _, l = ops.decode_attention_state(q[:, 0], k_cache, v_cache, lens)
        out = acc / jnp.maximum(l[..., None], 1e-20)
        return out.reshape(b, 1, h, dh).astype(q.dtype)
    qf = ((q.astype(jnp.float32)[:, 0] * scale)
          .reshape(b, n_kv, n_rep, dh).astype(cdt))
    scores = jnp.einsum("bkrd,bskd->bkrs", qf, k_cache,
                        preferred_element_type=jnp.float32)
    pos = jnp.arange(s)
    valid = pos[None, :] < jnp.reshape(jnp.asarray(kv_len), (-1, 1))
    if window is not None:
        valid = valid & (pos[None, :] >= jnp.reshape(jnp.asarray(kv_len), (-1, 1)) - window)
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkrs,bskd->bkrd", p.astype(cdt), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, dh).astype(q.dtype)


def chunk_attention_paged(
    q: jnp.ndarray,             # (B, T, H, Dh) — this step's chunk queries
    k_pool: jnp.ndarray,        # (L, n_blocks, block_size, KV * Dh)
    v_pool: jnp.ndarray,
    layer,                      # int32 scalar — the layer to read
    block_tables: jnp.ndarray,  # (B, max_blocks) int32; 0 = unmapped
    ctx_lens,                   # (B,) int32 — tokens already in the pool
    k_new: jnp.ndarray,         # (B, T, KV, Dh) — this chunk's K/V
    v_new: jnp.ndarray,
    window: int | None = None,
    mode: ExecMode = ExecMode.XLA,
) -> jnp.ndarray:
    """Mixed-batch attention of one layer over a block-paged KV pool.

    Chunk query t sits at absolute position ``ctx_lens[b] + t`` and splits
    its keys in two: (1) the CONTEXT — everything already in the pool, all
    of which precedes the whole chunk, so the mask ``kv_pos < ctx_len`` is
    uniform across the chunk and the paged kernel / XLA reference
    (kernels/paged_attn.py) needs no per-query state; (2) the INTRA-CHUNK
    causal term over the chunk's own freshly-computed K/V (a small (T, T)
    block, computed inline). The two online-softmax states combine through
    the shared ``merge_attn_states`` — decode is exactly the T=1 case, so
    one code path serves prefilling and decoding slots in the same batch.

    The context is read from the stacked pool at ``layer`` directly (one
    gather through the block tables, or the kernel's scalar-prefetched
    index maps), so the caller can carry the whole pool through its layer
    scan and write this chunk's rows after the call: rows at or past
    ``ctx_len`` are masked here, and the chunk's own keys come from
    ``k_new``/``v_new``, so the write never changes what this call reads.

    ``mode=ExecMode.PALLAS`` routes the context half to the paged Pallas
    kernel (global attention only); windowed callers and XLA mode share
    the gather-based reference.
    """
    from repro.kernels import ops
    b, t, h, dh = q.shape
    n_kv = k_new.shape[2]
    n_rep = h // n_kv
    ctx = jnp.broadcast_to(jnp.asarray(ctx_lens, jnp.int32), (b,))
    # --- context half: paged pool, uniform mask ------------------------------
    if mode == ExecMode.PALLAS and window is None:
        acc1, m1, l1 = ops.paged_attention_state(
            q, k_pool, v_pool, layer, block_tables, ctx)
    else:
        q_pos = ctx[:, None] + jnp.arange(t) if window is not None else None
        acc1, m1, l1 = ops.paged_attention_state_xla(
            q, k_pool, v_pool, layer, block_tables, ctx,
            window=window, q_positions=q_pos)
    # (B, KV, T*rep, ...) -> (B, KV, T, rep, ...)
    acc1 = acc1.reshape(b, n_kv, t, n_rep, dh)
    m1 = m1.reshape(b, n_kv, t, n_rep)
    l1 = l1.reshape(b, n_kv, t, n_rep)
    # --- intra-chunk causal half (T is small; plain masked softmax) ----------
    cdt = k_pool.dtype
    qf = ((q.astype(jnp.float32) * dh ** -0.5)
          .reshape(b, t, n_kv, n_rep, dh).astype(cdt))
    s2 = jnp.einsum("btkrd,bukd->bktru", qf, k_new.astype(cdt),
                    preferred_element_type=jnp.float32)   # (B, KV, T, rep, U)
    tt = jnp.arange(t)
    mask = tt[None, :] <= tt[:, None]                     # key u <= query t
    if window is not None:
        mask = mask & (tt[None, :] > tt[:, None] - window)
    mask = mask[None, None, :, None, :]
    s2 = jnp.where(mask, s2, -jnp.inf)
    m2 = jnp.max(s2, axis=-1)                 # finite: the self key survives
    p2 = jnp.exp(s2 - m2[..., None])
    p2 = jnp.where(mask, p2, 0.0)
    acc2 = jnp.einsum("bktru,bukd->bktrd", p2.astype(cdt), v_new.astype(cdt),
                      preferred_element_type=jnp.float32)
    l2 = jnp.sum(p2, axis=-1)
    out = merge_attn_states(acc1, m1, l1, acc2, m2, l2)   # (B, KV, T, rep, Dh)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, t, h, dh).astype(q.dtype)


# --- attention block ---------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    rope_base: float = 10000.0
    use_rope: bool = True
    window: int | None = None


def attn_init(key, cfg: AttnConfig, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 4)
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": dense_init(ks[0], d, h * dh, dtype),
        "wk": dense_init(ks[1], d, kv * dh, dtype),
        "wv": dense_init(ks[2], d, kv * dh, dtype),
        "wo": dense_init(ks[3], h * dh, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((cfg.head_dim,), dtype)
        p["k_norm"] = jnp.zeros((cfg.head_dim,), dtype)
    return p


def qkv_project(p: Params, x: jnp.ndarray, cfg: AttnConfig, positions):
    """x: (B, S, D) -> q (B,S,H,Dh), k/v (B,S,KV,Dh) with rope + qk-norm."""
    b, s, _ = x.shape
    q = maybe_flash_matmul(x, p["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = maybe_flash_matmul(x, p["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = maybe_flash_matmul(x, p["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_base)
        k = apply_rope(k, positions, cfg.rope_base)
    return q, k, v


# --- FFN variants ------------------------------------------------------------

def swiglu_init(key, d, f, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], d, f, dtype),
        "w_up": dense_init(ks[1], d, f, dtype),
        "w_down": dense_init(ks[2], f, d, dtype),
    }


def swiglu_apply(p: Params, x: jnp.ndarray,
                 axis_name: str | None = None) -> jnp.ndarray:
    """``axis_name``: run the FFN tensor-parallel inside a shard_map —
    gate/up are column-parallel (each shard owns d_ff/n_shards columns, no
    collective), down is row-parallel over the SAME column slice, so ONE
    psum per FFN completes the contraction (erdpe.flash_matmul does it in
    f32 before the bf16 cast)."""
    g = maybe_flash_matmul(x, p["w_gate"])
    u = maybe_flash_matmul(x, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
    return maybe_flash_matmul(h.astype(x.dtype), p["w_down"],
                              axis_name=axis_name)


def gelu_ffn_init(key, d, f, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 2)
    return {"w_up": dense_init(ks[0], d, f, dtype),
            "w_down": dense_init(ks[1], f, d, dtype)}


def gelu_ffn_apply(p: Params, x: jnp.ndarray,
                   axis_name: str | None = None) -> jnp.ndarray:
    h = jax.nn.gelu(maybe_flash_matmul(x, p["w_up"]).astype(jnp.float32))
    return maybe_flash_matmul(h.astype(x.dtype), p["w_down"],
                              axis_name=axis_name)


# --- losses ------------------------------------------------------------------

def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean token cross-entropy; logits (B,S,V) any float dtype; labels (B,S)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
