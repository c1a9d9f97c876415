"""Dense decoder-only transformer family.

Covers: qwen3-32b (qk-norm), granite-8b, mistral-nemo-12b, llama3-405b
(SwiGLU+RMSNorm+RoPE), llava-next-34b (dense backbone + prepended patch
embeddings), and the paper's OPT family (LayerNorm + GELU + learned
positions) / LLaMA2-7B evaluation models.

Layers are scan-stacked: params carry a leading (L,) dim and the forward is
a single jax.lax.scan (keeps HLO size O(1) in depth and enables per-layer
remat). Cache layout: K/V (L, B, S, KV, Dh).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.erdpe import maybe_flash_matmul
from repro.models import common as cm


def _norm(cfg, x, p, name):
    if cfg.norm_type == "layer":
        return cm.layer_norm(x, p[f"{name}_g"], p[f"{name}_b"])
    return cm.rms_norm(x, p[name])


def _norm_init(cfg, dtype):
    if cfg.norm_type == "layer":
        return lambda name: {f"{name}_g": jnp.ones((cfg.d_model,), dtype),
                             f"{name}_b": jnp.zeros((cfg.d_model,), dtype)}
    return lambda name: {name: jnp.zeros((cfg.d_model,), dtype)}


def attn_cfg(cfg) -> cm.AttnConfig:
    return cm.AttnConfig(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, qk_norm=cfg.qk_norm, rope_base=cfg.rope_base,
        use_rope=cfg.use_rope, window=cfg.local_window,
    )


def layer_init(cfg, key) -> dict:
    k1, k2 = jax.random.split(key)
    dtype = jnp.bfloat16
    p = {"attn": cm.attn_init(k1, attn_cfg(cfg), dtype)}
    if cfg.ffn_type == "swiglu":
        p["ffn"] = cm.swiglu_init(k2, cfg.d_model, cfg.d_ff, dtype)
    else:
        p["ffn"] = cm.gelu_ffn_init(k2, cfg.d_model, cfg.d_ff, dtype)
    ninit = _norm_init(cfg, dtype)
    p.update(ninit("ln1"))
    p.update(ninit("ln2"))
    return p


def init(cfg, key) -> dict:
    ke, kl, kh = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    layers = jax.vmap(partial(layer_init, cfg))(layer_keys)
    dtype = jnp.bfloat16
    params = {
        "embed": cm.embed_init(ke, cfg.vocab_size, cfg.d_model, dtype),
        "layers": layers,
        "final_norm": (jnp.zeros((cfg.d_model,), dtype) if cfg.norm_type == "rms"
                       else {"g": jnp.ones((cfg.d_model,), dtype),
                             "b": jnp.zeros((cfg.d_model,), dtype)}),
        "lm_head": cm.dense_init(kh, cfg.d_model, cfg.vocab_size, dtype),
    }
    if not cfg.use_rope:  # OPT-style learned positions
        params["pos_embed"] = cm.embed_init(
            jax.random.fold_in(ke, 1), cfg.max_seq, cfg.d_model, dtype)
    return params


@jax.named_scope("ffn")
def _ffn_apply(cfg, p, x, axis_name=None):
    if cfg.ffn_type == "swiglu":
        return cm.swiglu_apply(p, x, axis_name=axis_name)
    return cm.gelu_ffn_apply(p, x, axis_name=axis_name)


def _layer_fwd(cfg, x, lp, positions, collect_kv=True):
    """Full-sequence layer forward; returns (x, (k, v) or None).

    ``collect_kv=False`` (training) avoids stacking the per-layer K/V as
    scan outputs — a pure memory waste when no cache is wanted.
    """
    x = cm.pin_batch(x)
    lp = cm.pin_layer_grads(lp)
    h = _norm(cfg, x, lp, "ln1")
    q, k, v = cm.qkv_project(lp["attn"], h, attn_cfg(cfg), positions)
    attn = cm.chunked_attention(q, k, v, causal=True, window=cfg.local_window)
    b, s, _, _ = attn.shape
    attn = maybe_flash_matmul(attn.reshape(b, s, -1), lp["attn"]["wo"])
    x = x + attn
    x = x + _ffn_apply(cfg, lp["ffn"], _norm(cfg, x, lp, "ln2"))
    return x, ((k, v) if collect_kv else None)


def _embed(cfg, params, tokens, positions, extra_embeds=None):
    x = jnp.take(params["embed"], tokens, axis=0)
    if not cfg.use_rope and "pos_embed" in params:
        x = x + jnp.take(params["pos_embed"], positions.astype(jnp.int32), axis=0)
    if extra_embeds is not None:  # VLM: prepend patch embeddings (stub frontend)
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    return x


def forward(cfg, params, tokens, extra_embeds=None, remat=True, return_cache=False):
    """Train/prefill forward. tokens (B, S) -> logits (B, S_tot, V)."""
    b, s = tokens.shape
    n_extra = extra_embeds.shape[1] if extra_embeds is not None else 0
    positions = jnp.arange(s + n_extra)
    x = _embed(cfg, params, tokens, positions[n_extra:], extra_embeds)

    def body(x, lp):
        return _layer_fwd(cfg, x, lp, positions, collect_kv=return_cache)

    g = cfg.remat_groups
    if remat and not return_cache and g > 1 and cfg.n_layers % g == 0:
        # sqrt-remat: outer scan stashes G carries; the inner scan of L/G
        # layers is itself checkpointed, so its stash exists only while its
        # group's backward runs. Peak stash ~ (G + L/G) slices, not L.
        grouped = jax.tree.map(
            lambda a: a.reshape((g, cfg.n_layers // g) + a.shape[1:]),
            params["layers"])

        def inner(x, lps):
            ib = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
            x, _ = jax.lax.scan(ib, x, lps)
            return x, None

        outer = jax.checkpoint(
            inner, policy=jax.checkpoint_policies.nothing_saveable)
        x, _ = jax.lax.scan(outer, x, grouped)
        ks = vs = None
    else:
        if remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        x, kv_out = jax.lax.scan(body, x, params["layers"])
        ks, vs = kv_out if return_cache else (None, None)
    if cfg.norm_type == "rms":
        x = cm.rms_norm(x, params["final_norm"])
    else:
        x = cm.layer_norm(x, params["final_norm"]["g"], params["final_norm"]["b"])
    logits = maybe_flash_matmul(x, params["lm_head"], out_dtype=jnp.float32)
    if return_cache:
        return logits, {"k": ks, "v": vs}
    return logits


def train_loss(cfg, params, batch):
    extra = batch.get("patch_embeds")
    logits = forward(cfg, params, batch["tokens"], extra_embeds=extra, remat=True)
    n_extra = extra.shape[1] if extra is not None else 0
    return cm.softmax_xent(logits[:, n_extra:], batch["labels"])


def prefill(cfg, params, batch, pad_to: int | None = None):
    """Returns (last_logits (B, V), cache). Cache padded to ``pad_to``."""
    extra = batch.get("patch_embeds")
    logits, cache = forward(
        cfg, params, batch["tokens"], extra_embeds=extra, remat=True,
        return_cache=True)
    if pad_to is not None:
        s = cache["k"].shape[2]
        pad = [(0, 0), (0, 0), (0, pad_to - s), (0, 0), (0, 0)]
        cache = {k: jnp.pad(v, pad) for k, v in cache.items()}
    return logits[:, -1], cache


def decode_step(cfg, params, cache, batch):
    """One decode step. batch: {token (B,), kv_len scalar int32}.

    cache: {"k"/"v": (L, B, Smax, KV, Dh)}. Returns (logits (B, V), cache).

    The cache rides in the scan CARRY and only the new token's row is
    dynamic-update-sliced (a (1,B,1,KV,Dh) write). Passing the cache as
    scan xs/ys instead makes XLA materialize a full-cache select per layer
    (measured 185 GB/step of spurious traffic at 32k — EXPERIMENTS.md §Perf).
    """
    tokens = batch["token"][:, None]                      # (B, 1)
    kv_len = batch["kv_len"]                              # scalar: filled prefix
    positions = jnp.reshape(kv_len, (1,))
    x = _embed(cfg, params, tokens, positions)

    def body(x, layer):
        lp, k_cache, v_cache = layer                      # read-only slices
        h = _norm(cfg, x, lp, "ln1")
        q, k, v = cm.qkv_project(lp["attn"], h, attn_cfg(cfg), positions)
        attn = cm.decode_attention_incremental(
            q, k_cache, v_cache, kv_len, k, v, window=cfg.local_window)
        b = attn.shape[0]
        attn = maybe_flash_matmul(attn.reshape(b, 1, -1), lp["attn"]["wo"])
        x = x + attn
        x = x + _ffn_apply(cfg, lp["ffn"], _norm(cfg, x, lp, "ln2"))
        return x, (k, v)                                  # tiny per-layer K/V

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"]))
    # single batched write of all layers' new K/V rows at position kv_len
    zero = jnp.int32(0)
    ks = jax.lax.dynamic_update_slice(
        cache["k"], k_new.astype(cache["k"].dtype),
        (zero, zero, kv_len, zero, zero))
    vs = jax.lax.dynamic_update_slice(
        cache["v"], v_new.astype(cache["v"].dtype),
        (zero, zero, kv_len, zero, zero))
    if cfg.norm_type == "rms":
        x = cm.rms_norm(x, params["final_norm"])
    else:
        x = cm.layer_norm(x, params["final_norm"]["g"], params["final_norm"]["b"])
    logits = maybe_flash_matmul(x[:, 0], params["lm_head"], out_dtype=jnp.float32)
    return logits, {"k": ks, "v": vs}


def cache_shape(cfg, batch: int, max_seq: int) -> dict:
    kv = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jax.ShapeDtypeStruct(kv, jnp.bfloat16),
            "v": jax.ShapeDtypeStruct(kv, jnp.bfloat16)}
