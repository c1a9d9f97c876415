"""Seeded Qwen3-MoE weights, made on the device in the type they are served in.

``make_params`` builds the whole parameter tree in one jitted call from
``--seed``; ``layer_params`` rebuilds one layer of it, bit for bit, so the
reference can regenerate the model a layer at a time without ever holding
what the program made. Every tensor is drawn from its own key, folded from
the seed by a fixed path, so the two agree by construction (a test checks).
Layers are made one after another (``lax.map``), so no more than one
layer's float32 draws are live at once.

The tree follows the program's moe-family layout (``embed``,
``layers/{attn/{wq,wk,wv,wo,q_norm,k_norm},moe/{router,experts/{w_gate,
w_up,w_down}},ln1,ln2}``, ``final_norm``, ``lm_head``). Projections, the
router and every expert matrix follow Glorot, the embedding 0.02; RMSNorm
gains are stored as the program stores them, as ``gamma`` of
``x * (1 + gamma)``, drawn near 0 so the affine part is exercised.

``bench/run.py`` hands the arch module six size keys (``sizes_of``);
``full_sizes`` takes the others (KV heads, head size, experts, top-k, RoPE
base, norm epsilon) from ``sizes`` where present, else from this
configuration's file, so tests can pass tiny shapes whole.
"""
from __future__ import annotations

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp

DTYPE = jnp.bfloat16
CONF_FILE = Path(__file__).resolve().parents[1] / "configs" \
    / "qwen3-moe-30b-a3b.json"
SHAPE_KEYS = ("num_key_value_heads", "head_dim", "num_experts",
              "num_experts_per_tok", "rope_theta", "rms_norm_eps")
SIZE_KEYS = ("hidden_size", "ffn_dim", "num_attention_heads",
             "num_hidden_layers", "vocab_size", "max_position_embeddings")
PROGRAM_EPS = 1e-6          # models/common.rms_norm


def full_sizes(sizes: dict) -> dict:
    """``sizes`` with the shape keys the harness does not pass filled in
    from the configuration file."""
    base = json.loads(CONF_FILE.read_text())
    out = {k: base[k] for k in SHAPE_KEYS}
    out.update(sizes)
    return out


def root_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(DTYPE)


def _glorot(key, shape):
    k, n = shape[-2:]
    return _normal(key, shape, (2.0 / (k + n)) ** 0.5)


def _gain(key, n):
    return _normal(key, (n,), 0.05)


def _layer(sz: dict, key) -> dict:
    d, f = sz["hidden_size"], sz["ffn_dim"]
    h, kv, dh = (sz["num_attention_heads"], sz["num_key_value_heads"],
                 sz["head_dim"])
    e = sz["num_experts"]
    ks = jax.random.split(key, 12)
    return {
        "attn": {"wq": _glorot(ks[0], (d, h * dh)),
                 "wk": _glorot(ks[1], (d, kv * dh)),
                 "wv": _glorot(ks[2], (d, kv * dh)),
                 "wo": _glorot(ks[3], (h * dh, d)),
                 "q_norm": _gain(ks[4], dh), "k_norm": _gain(ks[5], dh)},
        "moe": {"router": _glorot(ks[6], (d, e)),
                "experts": {"w_gate": _glorot(ks[7], (e, d, f)),
                            "w_up": _glorot(ks[8], (e, d, f)),
                            "w_down": _glorot(ks[9], (e, f, d))}},
        "ln1": _gain(ks[10], d), "ln2": _gain(ks[11], d),
    }


def _layer_key(seed_key, i):
    return jax.random.fold_in(jax.random.fold_in(seed_key, 1), i)


def _top(sz: dict, seed_key) -> dict:
    d, v = sz["hidden_size"], sz["vocab_size"]
    ks = jax.random.split(jax.random.fold_in(seed_key, 0), 3)
    return {"embed": _normal(ks[0], (v, d), 0.02),
            "lm_head": _glorot(ks[1], (d, v)),
            "final_norm": _gain(ks[2], d)}


def _sizes_key(sizes: dict) -> tuple:
    sz = full_sizes(sizes)
    return tuple((k, sz[k]) for k in SIZE_KEYS + SHAPE_KEYS)


@functools.partial(jax.jit, static_argnums=0)
def _make(sizes_key: tuple, seed_key):
    sz = dict(sizes_key)
    idx = jnp.arange(sz["num_hidden_layers"])
    layers = jax.lax.map(lambda i: _layer(sz, _layer_key(seed_key, i)), idx)
    return {**_top(sz, seed_key), "layers": layers}


@functools.partial(jax.jit, static_argnums=0)
def _make_layer(sizes_key: tuple, seed_key, i):
    return _layer(dict(sizes_key), _layer_key(seed_key, i))


@functools.partial(jax.jit, static_argnums=0)
def _make_top(sizes_key: tuple, seed_key):
    return _top(dict(sizes_key), seed_key)


def make_params(sizes: dict, seed: int) -> dict:
    """The whole bf16 parameter tree, layers stacked on a leading axis."""
    return _make(_sizes_key(sizes), root_key(seed))


def layer_params(sizes: dict, seed: int, i: int) -> dict:
    """Layer ``i`` of ``make_params(sizes, seed)``, alone."""
    return _make_layer(_sizes_key(sizes), root_key(seed), jnp.int32(i))


def top_params(sizes: dict, seed: int) -> dict:
    """Embedding, final norm and lm_head of ``make_params(sizes, seed)``."""
    return _make_top(_sizes_key(sizes), root_key(seed))


def program_config(conf: dict):
    """The program's ``ArchConfig`` for a Qwen3-MoE configuration file."""
    from repro.configs.base import ArchConfig
    sz = full_sizes(conf)
    if float(sz["rms_norm_eps"]) != PROGRAM_EPS:
        raise ValueError(f"the program's RMSNorm uses eps {PROGRAM_EPS}, "
                         f"the configuration states {sz['rms_norm_eps']}")
    if not conf.get("norm_topk_prob", True):
        raise ValueError("the program renormalizes the top-k gates")
    return ArchConfig(
        name=conf["name"], family="moe",
        n_layers=int(sz["num_hidden_layers"]), d_model=int(sz["hidden_size"]),
        n_heads=int(sz["num_attention_heads"]),
        n_kv_heads=int(sz["num_key_value_heads"]),
        head_dim=int(sz["head_dim"]), d_ff=int(sz["ffn_dim"]),
        vocab_size=int(sz["vocab_size"]), qk_norm=True,
        rope_base=float(sz["rope_theta"]),
        tie_embeddings=bool(conf.get("tie_word_embeddings", False)),
        n_experts=int(sz["num_experts"]),
        top_k=int(sz["num_experts_per_tok"]),
        max_seq=int(sz["max_position_embeddings"]))
