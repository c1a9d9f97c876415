"""Rule-based PartitionSpecs over param/batch/cache pytrees (DESIGN.md §5).

Rules are pattern-matched on pytree path strings; every produced spec passes
a **divisibility guard** that drops any axis whose mesh extent does not
divide the corresponding dim (logged, so the roofline pass can see what got
replicated). This is what makes every (arch x shape x mesh) cell lower.

Roles:
  embeddings / lm_head : vocab -> "model"
  attention wq/wk/wv   : out (heads*dh) -> "model";  wo: in -> "model"
  FFN in-projections   : hidden -> "model";  out-projections: in -> "model"
  MoE expert banks     : expert dim -> "model" (expert parallelism)
  RWKV / RG-LRU        : channel projections like FFN
  batch leading dim    : ("pod","data")
  KV cache             : batch -> data axes, seq -> "model" (sequence-
                         parallel decode: partial-softmax combine is derived
                         by SPMD from the sharded softmax/contraction)

``fsdp=True`` additionally shards the weights' other matrix dim over the
data axes (ZeRO-3/FSDP: per-layer all-gather inside the layer scan);
``zero1=True`` shards *optimizer moments only* over data (ZeRO-1).
"""
from __future__ import annotations

import logging
import re
from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.tiering import FlashWeight

log = logging.getLogger("repro.sharding")

MODEL = "model"


# --- divisibility guard -----------------------------------------------------


def _axis_size(mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        out = 1
        for a in axis:
            out *= _axis_size(mesh, a)
        return out
    return mesh.shape[axis]


def guard(shape, spec: P, mesh, path: str = "?") -> P:
    """Drop spec axes that don't divide the dim (or don't exist in mesh)."""
    names = set(mesh.axis_names)
    out = []
    for i, axis in enumerate(spec):
        if axis is None:
            out.append(None)
            continue
        axes = axis if isinstance(axis, tuple) else (axis,)
        axes = tuple(a for a in axes if a in names)
        # progressively drop trailing axes until divisible
        while axes and shape[i] % _axis_size(mesh, axes) != 0:
            axes = axes[:-1]
        if tuple(axes) != (axis if isinstance(axis, tuple) else (axis,)):
            log.debug("guard: %s dim %d (%d) %s -> %s",
                      path, i, shape[i], spec[i], axes)
        orig = tuple(axis) if isinstance(axis, (tuple, list)) else (axis,)
        if not axes:
            out.append(None)
        elif tuple(axes) == orig:
            # untouched: keep the rule's form — P(("data",)) and P("data")
            # shard identically but don't compare equal
            out.append(axis)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(tuple(axes))
    # pad to rank
    out += [None] * (len(shape) - len(out))
    return P(*out)


# --- param rules --------------------------------------------------------------

# (path regex, spec over the LAST TWO dims, fsdp dim index or None)
# fsdp_dim: which of the last-two dims receives the data axes under fsdp.
_RULES: tuple[tuple[str, tuple, int | None], ...] = (
    (r".*(embed|pos_embed)$", (MODEL, None), 1),             # (V, D)
    (r".*lm_head(/[012])?$", (None, MODEL), 0),              # (D, V)
    (r".*attn/w[qkv]$", (None, MODEL), 0),
    (r".*cross/w[qkv]$", (None, MODEL), 0),
    (r".*(attn|cross)/wo$", (MODEL, None), 1),
    (r".*(w_gate|w_up|w_in_x|w_in_y)(/[012])?$", (None, MODEL), 0),
    (r".*(w_down|w_out)(/[012])?$", (MODEL, None), 1),
    (r".*tmix/w_[rkvg](/[012])?$", (None, MODEL), 0),
    (r".*tmix/w_o(/[012])?$", (MODEL, None), 1),
    (r".*channel_mix/w_rgate(/[012])?$", (None, MODEL), 0),
    (r".*router$", (None, None), None),
)

_EXPERT_RE = re.compile(r".*experts/.*")


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def spec_for_param(path: str, shape, mesh, fsdp: bool = False,
                   data_axes: tuple = ("data",)) -> P:
    """PartitionSpec for one (possibly layer-stacked) param leaf."""
    ndim = len(shape)
    if ndim == 0:
        return P()
    if _EXPERT_RE.match(path):
        # (L, E, K, N) or (E, K, N): expert dim -> model; fsdp on K.
        lead = [None] * (ndim - 3)
        spec = lead + [MODEL, tuple(data_axes) if fsdp else None, None]
        return guard(shape, P(*spec), mesh, path)
    for pat, last2, fsdp_dim in _RULES:
        if re.fullmatch(pat, path):
            if ndim == 1:
                return P(None)
            lead = [None] * (ndim - 2)
            last = list(last2)
            if fsdp and fsdp_dim is not None:
                if last[fsdp_dim] is None:
                    last[fsdp_dim] = tuple(data_axes)
            return guard(shape, P(*(lead + last)), mesh, path)
    # default: replicate small/1-D; shard last dim of big 2D+ on model as a
    # fallback only for clearly-matrix leaves we know nothing about.
    return P(*([None] * ndim))


def param_specs(params: Any, mesh, fsdp: bool = False) -> Any:
    """Pytree of PartitionSpec matching ``params`` (arrays or SDS)."""
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def one(path, leaf):
        return spec_for_param(_path_str(path), leaf.shape, mesh,
                              fsdp=fsdp, data_axes=data_axes)

    return jax.tree_util.tree_map_with_path(one, params)


def named(specs: Any, mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# --- tensor-parallel streamed serving (sharded page store) --------------------

def tp_shard_axis(entry: str) -> int | None:
    """Which (K, N) axis of a PageStore entry shards across the "model"
    mesh axis for tensor-parallel STREAMED serving — derived from the same
    ``_RULES`` the training specs use, so the serving shards and the
    training shards agree by construction:

      * ``(None, MODEL)`` rules (w_gate / w_up / wq...) -> axis 1 (the
        N / d_ff column axis — Megatron column-parallel);
      * ``(MODEL, None)`` rules (w_down / w_out / wo) -> axis 0 (the K
        row axis — row-parallel, one psum after the matmul);
      * anything else (``attn_flash/*`` copies, router, lm_head) -> None
        (replicated on every shard's pool).

    ``entry`` is a store entry name (``layers/ffn/w_gate@3``,
    ``layers/moe/experts/w_down@1.5``); the ``@idx`` suffix is ignored.
    """
    base = entry.partition("@")[0]
    if base.startswith("attn_flash/"):
        return None                      # Alg.2 attn copies stay replicated
    for pat, last2, _ in _RULES:
        if re.fullmatch(pat, base):
            if last2 == (None, MODEL):
                return 1
            if last2 == (MODEL, None):
                return 0
            return None
    if _EXPERT_RE.match(base):
        # expert bank slices keep their per-matrix TP axis (the leading
        # expert dim is already split into per-entry store slices)
        leaf = base.rsplit("/", 1)[-1]
        if leaf in ("w_gate", "w_up"):
            return 1
        if leaf in ("w_down",):
            return 0
    return None


def stream_window_specs(mesh) -> dict:
    """PartitionSpecs for the streamed group step under ``shard_map``:
    the pool buffer splits its page rows over "model"; page tables, DRAM
    params, activations and KV stay replicated (attention + router are
    computed redundantly per shard — the canonical 1-collective TP FFN
    leaves exactly one psum per layer)."""
    return {"pool": P(MODEL, None), "replicated": P()}


# --- batch / cache rules ---------------------------------------------------------


def batch_spec(shape, mesh, path: str = "batch") -> P:
    """Leading dim over ("pod","data"); scalars replicated."""
    if len(shape) == 0:
        return P()
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    spec = [data_axes] + [None] * (len(shape) - 1)
    return guard(shape, P(*spec), mesh, path)


def batch_specs(batch: Any, mesh) -> Any:
    def one(path, leaf):
        return batch_spec(leaf.shape, mesh, _path_str(path))
    return jax.tree_util.tree_map_with_path(one, batch)


def cache_spec(path: str, shape, mesh) -> P:
    """(L, B, S, KV, Dh) KV caches / (L, B, ...) recurrent states."""
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    ndim = len(shape)
    if ndim >= 3 and re.search(r"(^|/)(k|v|ck|cv)$", path):
        # (L, B, S, KV, Dh): batch -> data, seq -> model (sequence-parallel)
        spec = [None, data_axes, MODEL] + [None] * (ndim - 3)
        return guard(shape, P(*spec), mesh, path)
    if ndim >= 2:
        spec = [None, data_axes] + [None] * (ndim - 2)
        return guard(shape, P(*spec), mesh, path)
    return P(*([None] * ndim))


def cache_specs(cache: Any, mesh) -> Any:
    def one(path, leaf):
        return cache_spec(_path_str(path), leaf.shape, mesh)
    return jax.tree_util.tree_map_with_path(one, cache)


def opt_state_specs(opt_state, pspecs, mesh, zero1: bool = False):
    """AdamWState(step, m, v): moments shadow the param specs; ZeRO-1 adds
    the data axes on the first unsharded dim of each moment."""
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def momspec(spec: P, leaf):
        if not zero1:
            return guard(leaf.shape, spec, mesh, "opt")
        s = list(spec) + [None] * (len(leaf.shape) - len(spec))
        used = {a for e in s if e is not None
                for a in (e if isinstance(e, tuple) else (e,))}
        free = tuple(a for a in data_axes if a not in used)
        if free:
            for i, a in enumerate(s):
                if a is None and leaf.shape[i] > 1:
                    s[i] = free
                    break
        return guard(leaf.shape, P(*s), mesh, "opt")

    m = jax.tree.map(momspec, pspecs, opt_state.m,
                     is_leaf=lambda x: isinstance(x, P))
    v = jax.tree.map(momspec, pspecs, opt_state.v,
                     is_leaf=lambda x: isinstance(x, P))
    return type(opt_state)(step=P(), m=m, v=v)


# --- in-graph hints ----------------------------------------------------------------


def data_group_count(n_tokens: int) -> int:
    """Size of the data-parallel axis group for hierarchical MoE dispatch
    (1 outside a mesh context). Halved until it divides ``n_tokens``."""
    env_mesh = jax.sharding.get_abstract_mesh()
    if env_mesh.empty:
        return 1
    g = 1
    for a in ("pod", "data"):
        if a in env_mesh.axis_names:
            g *= env_mesh.shape[a]
    while g > 1 and n_tokens % g:
        g //= 2
    return max(g, 1)


def constrain_spec(x, spec: P):
    """with_sharding_constraint against an explicit P (guarded, mesh-aware)."""
    return constrain(x, *spec) if len(spec) else x


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def pin_grad(w, spec: tuple):
    """Identity on the primal; constrains the COTANGENT to ``spec``.

    Applied to every weight at the top of the train step: without it XLA
    materializes each per-layer dW unsharded in f32 and all-reduces the full
    matrix (measured 54 TB/chip/step on qwen3-moe train_4k); with the
    cotangent pinned to the parameter sharding, the partitioner computes the
    shard-local partial dW and reduce-scatters (EXPERIMENTS.md §Perf)."""
    return w


def _pin_grad_fwd(w, spec):
    return w, None


def _pin_grad_bwd(spec, _, dw):
    return (constrain(dw, *spec),)


pin_grad.defvjp(_pin_grad_fwd, _pin_grad_bwd)


def constrain(x, *spec):
    """with_sharding_constraint that degrades to identity outside a mesh
    context (``jax.set_mesh``) and respects the divisibility guard. Models
    call this to hint activation sharding (e.g. MoE dispatch buffers)
    without knowing the mesh.
    """
    env_mesh = jax.sharding.get_abstract_mesh()
    if env_mesh.empty:
        return x
    p = guard(x.shape, P(*spec), env_mesh, "constraint")
    return jax.lax.with_sharding_constraint(x, p)
