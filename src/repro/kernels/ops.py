"""Jit'd public wrappers around the Pallas kernels.

``ecdp_matmul`` is the operation the rest of the framework calls; it picks
legal block shapes, dispatches to the Pallas kernel (interpret=True on CPU so
the kernel body is validated everywhere), and applies per-channel scales.

``ecdp_matmul_xla`` is the same computation expressed as plain XLA ops — used
inside large SPMD graphs (dry-run / roofline) where a per-shard Pallas call
is not the object under study; it keeps data movement identical.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import ecc
from repro.kernels import paged_ffn
from repro.kernels.decode_attn import decode_attn_pallas
from repro.kernels.ecdp import ecdp_matmul_pallas
from repro.kernels.paged_attn import paged_attn_pallas, paged_attn_xla
from repro.kernels.paged_ffn import paged_ecdp_matmul_xla  # noqa: F401 (public)


def _pick_block(dim: int, target: int, mult: int) -> int:
    """Largest divisor of ``dim`` that is <= target and a multiple of ``mult``
    (falls back to the largest divisor that is a multiple of mult, else dim)."""
    best = None
    for b in range(mult, dim + 1, mult):
        if dim % b == 0 and b <= target:
            best = b
    if best is not None:
        return best
    return dim


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_k", "block_n", "ecc_enabled", "interpret"),
)
def ecdp_matmul(
    a: jnp.ndarray,
    wq: jnp.ndarray,
    parity: jnp.ndarray,
    scales: jnp.ndarray,
    *,
    block_m: int = 8,
    block_k: int = 512,
    block_n: int = 512,
    ecc_enabled: bool = True,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Error-corrected quantized matmul: (M,K) x (K,N)int8 -> (M,N) f32.

    a: activations (M, K) (bf16/f32); wq raw int8 weights; parity (K//8, N)
    uint8; scales (1, N) f32. Output matches kernels.ref.ecdp_reference.
    """
    m, k = a.shape
    _, n = wq.shape
    bm = _pick_block(m, block_m, 1)
    bk = _pick_block(k, block_k, 8)
    bn = _pick_block(n, block_n, 1)
    interp = _on_cpu() if interpret is None else interpret
    out = ecdp_matmul_pallas(
        a, wq, parity,
        block_m=bm, block_k=bk, block_n=bn,
        ecc_enabled=ecc_enabled, interpret=interp,
    )
    return out * scales.astype(jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("kn", "block_m", "ecc_enabled", "interpret"))
def paged_ecdp_matmul(
    a: jnp.ndarray,
    pool: jnp.ndarray,
    q_tbl: jnp.ndarray,
    p_slots: jnp.ndarray,
    s_slots: jnp.ndarray,
    kn: tuple,
    *,
    block_m: int = 8,
    ecc_enabled: bool = True,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Paged ECDP matmul: (M, K) x pool-paged (K, N) int8 -> (M, N) f32.

    The Pallas twin of ``paged_ecdp_matmul_xla``: q tiles are consumed
    straight out of the device page pool through the scalar-prefetched page
    table; the flat-run parity planes (an eighth of the bytes) are gathered
    dense in-graph first. Activations are zero-padded to the tile grid and
    the output sliced back — padded weight tiles are stored zeroed, so they
    contribute exactly zero."""
    m, k = a.shape
    kt, nt = q_tbl.shape
    n = kn[1]
    kp, np_ = kt * paged_ffn.TILE, nt * paged_ffn.TILE
    a_p = a if k == kp else jnp.pad(a, ((0, 0), (0, kp - k)))
    if ecc_enabled:
        parity = paged_ffn.gather_parity(pool, p_slots, k, n)
        parity_p = jnp.zeros((kp // 8, np_), jnp.uint8
                             ).at[:k // 8, :n].set(parity)
    else:
        parity_p = jnp.zeros((kp // 8, np_), jnp.uint8)
    bm = _pick_block(m, 8, 1)
    interp = _on_cpu() if interpret is None else interpret
    out = paged_ffn.paged_ecdp_matmul_pallas(
        a_p, pool, q_tbl, parity_p,
        block_m=bm, ecc_enabled=ecc_enabled, interpret=interp,
    )[:, :n]
    return out * paged_ffn.gather_scale(pool, s_slots, n).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention_state(
    q: jnp.ndarray,          # (B, H, Dh) — one query token per slot, UNscaled
    k_pool: jnp.ndarray,     # (B, S_max, KV, Dh)
    v_pool: jnp.ndarray,
    lengths: jnp.ndarray,    # (B,) int32 — live prefix per slot
    *,
    block_s: int = 512,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Slot-paged decode attention (Pallas), returning online-softmax state.

    Returns (acc, m, l) f32 with acc (B, KV, rep, Dh) UNNORMALIZED and
    m/l (B, KV, rep): callers either normalize (``acc / l``) or merge the
    current token's self-term before normalizing (the engine's incremental
    form). Scaling and the GQA (KV, rep) grouping are applied here so the
    kernel sees the same dtype discipline as the XLA fallback.
    """
    b, h, dh = q.shape
    _, s_max, n_kv, _ = k_pool.shape
    n_rep = h // n_kv
    cdt = k_pool.dtype
    qg = ((q.astype(jnp.float32) * dh ** -0.5)
          .reshape(b, n_kv, n_rep, dh).astype(cdt))
    bs = _pick_block(s_max, block_s, 1)
    interp = _on_cpu() if interpret is None else interpret
    return decode_attn_pallas(
        qg, k_pool, v_pool, lengths.astype(jnp.int32),
        block_s=bs, interpret=interp,
    )


def _group_chunk_queries(q: jnp.ndarray, n_kv: int, cdt) -> jnp.ndarray:
    """(B, T, H, Dh) unscaled -> (B, KV, T*rep, Dh) scaled, pool dtype.

    With the context mask uniform across a chunk (every cached token
    precedes every chunk query), folding (T, rep) into one query axis makes
    the chunk case identical to decode at rep' = T*rep — both the Pallas
    kernel and the XLA reference consume this layout. TR index = t*rep + r.
    """
    b, t, h, dh = q.shape
    n_rep = h // n_kv
    qg = (q.astype(jnp.float32) * dh ** -0.5).reshape(b, t, n_kv, n_rep, dh)
    return qg.transpose(0, 2, 1, 3, 4).reshape(b, n_kv, t * n_rep, dh).astype(cdt)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_state(
    q: jnp.ndarray,             # (B, T, H, Dh) — chunk queries, UNscaled
    k_pool: jnp.ndarray,        # (L, n_blocks, block_size, KV * Dh)
    v_pool: jnp.ndarray,
    layer,                      # int32 scalar — the layer to read
    block_tables: jnp.ndarray,  # (B, max_blocks) int32
    ctx_lens: jnp.ndarray,      # (B,) int32 — cached context per slot
    *,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Block-paged context attention (Pallas) over one layer of the stacked
    pool, returning online-softmax state: (acc, m, l) f32 with acc
    (B, KV, T*rep, Dh) UNNORMALIZED and m/l (B, KV, T*rep). Covers decode
    (T=1) and chunked prefill (T>1); the caller merges the intra-chunk
    causal term (models/common.chunk_attention_paged) before normalizing."""
    n_kv = k_pool.shape[-1] // q.shape[-1]
    qg = _group_chunk_queries(q, n_kv, k_pool.dtype)
    interp = _on_cpu() if interpret is None else interpret
    return paged_attn_pallas(
        qg, k_pool, v_pool, layer, block_tables.astype(jnp.int32),
        ctx_lens.astype(jnp.int32), interpret=interp)


def paged_attention_state_xla(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    layer,
    block_tables: jnp.ndarray,
    ctx_lens: jnp.ndarray,
    *,
    window: int | None = None,
    q_positions: jnp.ndarray | None = None,   # (B, T) abs positions (window)
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """XLA-native equivalent (one gather through the block table straight
    from the stacked pool, same math and dtype discipline) — the reference
    the kernel is tested against, and the windowed-attention fallback."""
    b, t, h, dh = q.shape
    n_kv = k_pool.shape[-1] // dh
    qg = _group_chunk_queries(q, n_kv, k_pool.dtype)
    if q_positions is not None:
        q_positions = jnp.repeat(q_positions, h // n_kv, axis=1)   # (B, TR)
    return paged_attn_xla(
        qg, k_pool, v_pool, layer, block_tables.astype(jnp.int32),
        ctx_lens.astype(jnp.int32), window=window, q_positions=q_positions)


def ecdp_matmul_xla(
    a: jnp.ndarray,
    wq: jnp.ndarray,
    parity: jnp.ndarray,
    scales: jnp.ndarray,
    *,
    ecc_enabled: bool = False,
) -> jnp.ndarray:
    """XLA-native equivalent (same math, no pallas_call) for SPMD graphs."""
    if ecc_enabled:
        raw = ecc.weights_to_bytes(wq)
        corrected, _, _ = ecc.check_and_correct(raw, parity)
        w = ecc.bytes_to_weights(corrected)
    else:
        w = wq
    out = jnp.dot(
        a.astype(jnp.float32), w.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return out * scales.astype(jnp.float32)
