"""Seeded OPT weights, made on the device in the type they are served in.

``make_params`` builds the whole parameter tree in one jitted call from
``--seed``; ``layer_params`` rebuilds one layer of it, bit for bit, so the
reference can regenerate the model a layer at a time without ever holding
what the program made. Every tensor is drawn from its own key, folded from
the seed by a fixed path, so the two agree by construction (a test checks).

The tree follows the program's dense-family layout (``embed``,
``pos_embed``, ``layers/{attn,ffn,ln1_*,ln2_*}``, ``final_norm``,
``lm_head``); the scales follow Glorot for projections and 0.02 for the
embeddings, with LayerNorm gains and shifts drawn near (1, 0) so that the
affine part of every norm is exercised.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

DTYPE = jnp.bfloat16


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


def _glorot(key, k, n):
    return _normal(key, (k, n), (2.0 / (k + n)) ** 0.5)


def _norm_pair(key, d):
    kg, kb = jax.random.split(key)
    return (1.0 + _normal(kg, (d,), 0.05).astype(jnp.float32)).astype(DTYPE), \
        _normal(kb, (d,), 0.02)


def _layer(sizes: dict, key) -> dict:
    d, f = sizes["hidden_size"], sizes["ffn_dim"]
    ks = jax.random.split(key, 8)
    g1, b1 = _norm_pair(ks[6], d)
    g2, b2 = _norm_pair(ks[7], d)
    return {
        "attn": {"wq": _glorot(ks[0], d, d), "wk": _glorot(ks[1], d, d),
                 "wv": _glorot(ks[2], d, d), "wo": _glorot(ks[3], d, d)},
        "ffn": {"w_up": _glorot(ks[4], d, f), "w_down": _glorot(ks[5], f, d)},
        "ln1_g": g1, "ln1_b": b1, "ln2_g": g2, "ln2_b": b2,
    }


def _layer_key(seed_key, i):
    return jax.random.fold_in(jax.random.fold_in(seed_key, 1), i)


def _top(sizes: dict, seed_key) -> dict:
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    k = jax.random.fold_in(seed_key, 0)
    ks = jax.random.split(k, 4)
    g, b = _norm_pair(ks[3], d)
    return {"embed": _normal(ks[0], (v, d), 0.02),
            "pos_embed": _normal(ks[1], (sizes["max_position_embeddings"], d),
                                 0.02),
            "lm_head": _glorot(ks[2], d, v),
            "final_norm": {"g": g, "b": b}}


def _sizes_key(sizes: dict) -> tuple:
    keys = ("hidden_size", "ffn_dim", "vocab_size", "max_position_embeddings",
            "num_hidden_layers")
    return tuple((k, int(sizes[k])) for k in keys)


@functools.partial(jax.jit, static_argnums=0)
def _make(sizes_key: tuple, seed_key):
    sizes = dict(sizes_key)
    idx = jnp.arange(sizes["num_hidden_layers"])
    layers = jax.vmap(lambda i: _layer(sizes, _layer_key(seed_key, i)))(idx)
    return {**_top(sizes, seed_key), "layers": layers}


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
    """Embeddings, final norm and lm_head of ``make_params(sizes, seed)``."""
    return _make_top(_sizes_key(sizes), root_key(seed))


def program_config(conf: dict):
    """The program's ``ArchConfig`` for an OPT configuration file."""
    from repro.configs.base import ArchConfig
    d, h = int(conf["hidden_size"]), int(conf["num_attention_heads"])
    return ArchConfig(
        name=conf["name"], family="dense",
        n_layers=int(conf["num_hidden_layers"]), d_model=d, n_heads=h,
        n_kv_heads=h, head_dim=d // h, d_ff=int(conf["ffn_dim"]),
        vocab_size=int(conf["vocab_size"]), norm_type="layer",
        ffn_type="gelu", use_rope=False,
        max_seq=int(conf["max_position_embeddings"]))
