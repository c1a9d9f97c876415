"""Mixture-of-Experts decoder family (qwen3-moe-30b-a3b, phi3.5-moe-42b).

Same scan-stacked skeleton as dense.py; the FFN is replaced by a top-k MoE
with **sorted capacity dispatch** (static shapes, jit/SPMD-safe):

  1. top-k routing per token, flatten to T*k (token, expert, gate) triples;
  2. stable-sort by expert id; rank-within-expert from exclusive cumsum of
     per-expert counts; assignments with rank >= capacity go to a trash row;
  3. scatter tokens into an (E, C+1, D) buffer, run all experts batched
     (einsum over the expert dim — shardable over the "model"/expert axis),
     gather back, unsort, gate-weight and sum the k copies.

Expert banks (E, D, F) are flash-tier (NVLLM's best-fit case: 97 % of params
page-streamed, read sparsely by top-k — DESIGN.md §4).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import ecc
from repro.core.erdpe import maybe_flash_matmul, serve_ecc_mode
from repro.core.tiering import FlashWeight, PagedWeight
from repro.kernels import paged_ffn
from repro.models import common as cm
from repro.models import dense


def moe_init(cfg, key) -> dict:
    ks = jax.random.split(key, 4)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    dtype = jnp.bfloat16

    def bank(k, kk, nn):
        keys = jax.random.split(k, e)
        return jax.vmap(lambda kx: cm.dense_init(kx, kk, nn, dtype))(keys)

    return {
        "router": cm.dense_init(ks[0], d, e, dtype),
        "experts": {
            "w_gate": bank(ks[1], d, f),
            "w_up": bank(ks[2], d, f),
            "w_down": bank(ks[3], f, d),
        },
    }


def layer_init(cfg, key) -> dict:
    k1, k2 = jax.random.split(key)
    dtype = jnp.bfloat16
    p = {"attn": cm.attn_init(k1, dense.attn_cfg(cfg), dtype),
         "moe": moe_init(cfg, k2)}
    ninit = dense._norm_init(cfg, dtype)
    p.update(ninit("ln1"))
    p.update(ninit("ln2"))
    return p


def init(cfg, key) -> dict:
    ke, kl, kh = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    layers = jax.vmap(partial(layer_init, cfg))(layer_keys)
    dtype = jnp.bfloat16
    return {
        "embed": cm.embed_init(ke, cfg.vocab_size, cfg.d_model, dtype),
        "layers": layers,
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
        "lm_head": cm.dense_init(kh, cfg.d_model, cfg.vocab_size, dtype),
    }


def _expert_matmul(x, w, out_dtype=None):
    """x: (G, E, C, K) @ w: (E, K, N) -> (G, E, C, N); flash-tier aware.
    ``out_dtype=float32`` keeps PARTIAL products full-precision for a
    tensor-parallel psum (summing bf16-rounded partials doubles error);
    None = the legacy dtype (bf16 on flash tiers, x.dtype on arrays)."""
    g, e, c, k = x.shape
    if isinstance(w, FlashWeight):
        # Per-expert ERDPE over the stacked bank, checked on read under
        # REPRO_SERVE_ECC=inline as every other flash-tier read is.
        from repro.kernels import ops
        xe = x.transpose(1, 0, 2, 3).reshape(e, g * c, k).astype(jnp.float32)
        ecc_inline = serve_ecc_mode() == "inline"

        def one(xg, qe, pe, se):
            return ops.ecdp_matmul_xla(xg, qe, pe, se,
                                       ecc_enabled=ecc_inline)

        out = jax.vmap(one)(xe, w.q, w.parity, w.scale)
        n = out.shape[-1]
        return out.reshape(e, g, c, n).transpose(1, 0, 2, 3).astype(
            out_dtype or jnp.bfloat16)
    out = jnp.einsum("geck,ekn->gecn", x, w.astype(x.dtype))
    return out if out_dtype is None else out.astype(out_dtype)


def _dispatch_group(cfg, xt, router, capacity_factor, dtype):
    """Capacity dispatch for ONE token group. xt: (Tg, D).

    Returns (buf (E, C+1, D), combine metadata). Runs entirely shard-local
    when the group axis is data-sharded (sort/scatter never cross shards).
    """
    tg, d = xt.shape
    e, k = cfg.n_experts, cfg.top_k
    logits = jnp.dot(xt.astype(jnp.float32), router.astype(jnp.float32))
    gates, idx = jax.lax.top_k(logits, k)                     # (Tg, k)
    gates = jax.nn.softmax(gates, axis=-1)

    flat_e = idx.reshape(-1)                                  # (Tg*k,)
    flat_tok = jnp.repeat(jnp.arange(tg), k)
    cap = max(int(tg * k / e * capacity_factor), 1)

    order = jnp.argsort(flat_e, stable=True)
    e_sorted = flat_e[order]
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts                      # exclusive
    rank = jnp.arange(tg * k) - starts[e_sorted]
    slot = jnp.minimum(rank, cap)                             # cap -> trash row

    buf = jnp.zeros((e, cap + 1, d), dtype)
    buf = buf.at[e_sorted, slot].set(xt[flat_tok[order]].astype(dtype))
    buf = buf.at[:, cap].set(0)                               # clear trash

    # unsort the (expert, slot) ADDRESSES (i32), not the D-wide vectors: the
    # combine is then a pure gather — no (T*k, D) scatter (see moe_apply).
    inv = jnp.zeros((tg * k,), jnp.int32).at[order].set(
        jnp.arange(tg * k, dtype=jnp.int32))
    e_un = e_sorted[inv]
    slot_un = slot[inv]
    rank_un = rank[inv]
    return buf, (gates, e_un, slot_un, rank_un, cap)


def _combine_group(out_buf, meta, d):
    """Gather-based combine for one group. out_buf: (E, C+1, D)."""
    gates, e_un, slot_un, rank_un, cap = meta
    tg, k = gates.shape
    gathered = out_buf[e_un, jnp.minimum(slot_un, cap)]       # (Tg*k, D)
    gathered = jnp.where((rank_un >= cap)[:, None], 0.0,
                         gathered.astype(jnp.float32))
    weighted = gathered * gates.reshape(-1)[:, None]
    return weighted.reshape(tg, k, d).sum(axis=1)


def moe_apply(cfg, p, x, capacity_factor: float = 1.25):
    """x: (B, S, D) -> (B, S, D).

    Hierarchical dispatch (§Perf, EXPERIMENTS.md): tokens are split into G
    data-sharded groups; sort/scatter/gather run shard-LOCAL per group
    (vmapped), and only the compact (G, E, C, D) expert buffer crosses
    shards — the all-to-all of classical expert parallelism — instead of
    the (T*k, D) global scatter that XLA lowers to full all-reduces
    (measured 54 TB/chip/step before this restructure).
    """
    from repro.launch.sharding import constrain, data_group_count
    b, s, d = x.shape
    t = b * s
    g = data_group_count(t)
    xt = constrain(x.reshape(g, t // g, d), ("pod", "data"), None, None)

    buf, meta = jax.vmap(
        partial(_dispatch_group, cfg, router=p["router"],
                capacity_factor=capacity_factor, dtype=x.dtype))(xt)
    # expert-parallel compute: reshard group-sharded buf -> expert-sharded
    buf = constrain(buf, None, "model", None, None)

    h_g = _expert_matmul(buf, p["experts"]["w_gate"])
    h_u = _expert_matmul(buf, p["experts"]["w_up"])
    h = (jax.nn.silu(h_g.astype(jnp.float32))
         * h_u.astype(jnp.float32)).astype(x.dtype)
    out_buf = _expert_matmul(h, p["experts"]["w_down"])       # (G, E, C+1, D)
    out_buf = constrain(out_buf, ("pod", "data"), None, None, None)

    out = jax.vmap(partial(_combine_group, d=d))(out_buf, meta)
    return out.reshape(b, s, d).astype(x.dtype)


# --- serving-engine MoE FFN (DESIGN.md §9) -----------------------------------
#
# The serving engine's mixed batch is tiny ((n_slots, chunk_tokens) lanes),
# so the capacity-dispatch machinery above (built for sharded training
# shapes) gives way to a LOSSLESS ROUTED-ONLY dispatch: the experts that
# valid assignments route to are gathered into a compact slab (ascending
# expert id, a static row bound picked by ``lax.switch``), the assignments
# are sorted by slab row, and one grouped product per weight kind
# (``lax.ragged_dot``) does work in proportion to the assignments. Each
# expert's computation is independent of the bank's composition, so a
# partial SLAB holding only the routed experts (plus a row map) produces
# bit-identical outputs to the full resident bank — what makes
# streamed-vs-resident greedy parity exact.


def serve_route(router, x, top_k: int, n_groups: int = 1,
                topk_groups: int = 0):
    """Top-k routing for a (S, T, D) serving chunk batch.

    Returns (gates (S, T, k) f32 — softmax over the selected logits, the
    same normalization as ``_dispatch_group`` — and idx (S, T, k) i32).
    The idx array is the step's EXPERT-ID BITMAP: the streamed engine ships
    it to the host (the MoE analog of Algorithm 2's plane bitmap) and only
    those experts' pages cross to the device.

    GROUP-LIMITED routing (``ArchConfig.n_expert_groups`` /
    ``topk_expert_groups``, the DeepSeek-V2 discipline): experts are split
    into ``n_groups`` contiguous groups; each token may only route within
    its ``topk_groups`` best groups (scored by the group's max logit). This
    BOUNDS the distinct-expert set a token touches to ``topk_groups *
    (E / n_groups)`` — for the streamed engine, a smaller per-step page
    upload and a tighter expert-slab bound. ``topk_groups`` in
    {0, n_groups} disables the restriction."""
    logits = jnp.einsum("std,de->ste", x.astype(jnp.float32),
                        router.astype(jnp.float32))
    if n_groups > 1 and 0 < topk_groups < n_groups:
        e = logits.shape[-1]
        if e % n_groups:
            raise ValueError(f"n_experts={e} not divisible by "
                             f"n_expert_groups={n_groups}")
        gsz = e // n_groups
        gl = logits.reshape(logits.shape[:-1] + (n_groups, gsz)).max(-1)
        _, gidx = jax.lax.top_k(gl, topk_groups)          # (S, T, kg)
        keep = jax.nn.one_hot(gidx, n_groups).sum(-2) > 0  # (S, T, G)
        keep = jnp.repeat(keep, gsz, axis=-1)              # (S, T, E)
        logits = jnp.where(keep, logits, -jnp.inf)
    gates, idx = jax.lax.top_k(logits, top_k)
    return jax.nn.softmax(gates, axis=-1), idx.astype(jnp.int32)


def slab_bounds(n_experts: int, top_k: int) -> tuple[int, ...]:
    """Static row counts of the routed-expert slab: the powers of two from
    the least that holds one token's ``top_k`` experts up to below
    ``n_experts``, then ``n_experts`` — one compiled branch each (5 for
    128 experts, top-8)."""
    b = 1
    while b < top_k:
        b *= 2
    out = []
    while b < n_experts:
        out.append(b)
        b *= 2
    return tuple(out) + (n_experts,)


def expert_counts(idx, ok, n_experts: int):
    """(n_experts,) i32: the ``ok`` assignments routed to each expert
    (a one-hot sum: no scatter for the compiler to merge with another)."""
    hit = jax.nn.one_hot(idx.reshape(-1), n_experts, dtype=jnp.int32)
    return jnp.sum(hit * ok.reshape(-1, 1).astype(jnp.int32), axis=0)


def slab_ids(present, bound: int):
    """The ``bound`` expert ids a slab holds: every routed (``present``)
    expert in ascending id order, then unrouted fillers; the whole bank in
    id order when ``bound`` is the expert count."""
    n = present.shape[0]
    if bound == n:
        return jnp.arange(n, dtype=jnp.int32)
    return jnp.argsort(~present, stable=True)[:bound].astype(jnp.int32)


def _slab_weight(w, rows, ecc_inline: bool, layer=None):
    """(int8-valued weights (B, K, N) bf16, scales (B, 1, N) f32 or None)
    of bank ``rows`` — the only expert bytes the step reads. A deployed
    bank is ECC-checked on read when ``ecc_inline``; a pool-paged slab
    keeps the store's host-side check. ``layer`` indexes a deployed bank
    still stacked over layers (L, E, K, N), so that a layer's whole bank
    is never sliced out before its routed rows are."""
    if isinstance(w, PagedWeight):
        k, n = w.kn
        q = jax.vmap(lambda t: paged_ffn.gather_q(w.pool, t, k, n))(
            w.q_tbl[rows])
        scale = jax.vmap(lambda t: paged_ffn.gather_scale(w.pool, t, n))(
            w.s_slots[rows])
        return q.astype(jnp.bfloat16), scale
    if isinstance(w, FlashWeight):
        lead = () if layer is None else (layer,)

        def read(q, parity):
            if not ecc_inline:
                return q
            raw, _, _ = ecc.check_and_correct(ecc.weights_to_bytes(q), parity)
            return ecc.bytes_to_weights(raw)

        if rows.shape[0] == w.q.shape[-3]:         # the whole bank, id order
            q = jax.vmap(read)(w.q[lead], w.parity[lead])
            scale = w.scale[lead]
        else:
            # one dynamic slice per routed row, checked as it is read: a
            # row gather over the stacked bank lowers to a pass over all of
            # it, and checking the joined rows copies them first
            q = jnp.stack([read(w.q[lead + (r,)], w.parity[lead + (r,)])
                           for r in rows])
            scale = jnp.stack([w.scale[lead + (r,)] for r in rows])
        return q.astype(jnp.bfloat16), scale
    return w[rows].astype(jnp.bfloat16), None


def _grouped(xs, w, grp, group_sizes, out_dtype):
    """Rows of ``xs`` (sorted by slab row) times their expert's weight."""
    q, scale = w
    out = jax.lax.ragged_dot(xs.astype(jnp.bfloat16), q, group_sizes,
                             preferred_element_type=jnp.float32)
    if scale is not None:
        out = out * scale[grp, 0]
    return out.astype(out_dtype)


def routed_ffn(bank, x_flat, flat_e, ok, counts, slab_map, *, bound: int,
               top_k: int, ecc_inline: bool, down_dtype=jnp.bfloat16,
               layer=None):
    """One slab bound of ``serve_expert_ffn``: the (A, D) f32 expert
    outputs, in assignment order (0 where not ``ok``), reading only the
    ``bound`` slab rows. ``x_flat`` (tokens, D); ``flat_e``/``ok`` (A,)
    with A = tokens * top_k; ``counts`` from ``expert_counts``."""
    n_exp = counts.shape[0]
    with jax.named_scope("route"):
        present = counts > 0
        ids = slab_ids(present, bound)
        # slab row of each expert: its id in the whole bank, else its rank
        # among the routed ones
        pos = (jnp.arange(n_exp, dtype=jnp.int32) if bound == n_exp
               else jnp.cumsum(present.astype(jnp.int32)) - 1)
        key = jnp.where(ok, pos[flat_e], bound)
        order = jnp.argsort(key, stable=True)
        grp = jnp.minimum(key[order], bound - 1)
        xs = x_flat[order // top_k]
        sizes = counts[ids]
    with jax.named_scope("experts"):
        rows = ids if slab_map is None else jnp.maximum(slab_map[ids], 0)
        w = {name: _slab_weight(bank[name], rows, ecc_inline, layer)
             for name in ("w_gate", "w_up", "w_down")}
        h_g = _grouped(xs, w["w_gate"], grp, sizes, jnp.bfloat16)
        h_u = _grouped(xs, w["w_up"], grp, sizes, jnp.bfloat16)
        h = (jax.nn.silu(h_g.astype(jnp.float32))
             * h_u.astype(jnp.float32)).astype(x_flat.dtype)
        y = _grouped(h, w["w_down"], grp, sizes, down_dtype)
    with jax.named_scope("route"):
        y = jnp.where(ok[order][:, None], y.astype(jnp.float32), 0.0)
        return y[jnp.argsort(order)]                   # back to assignments


def serve_expert_ffn(bank, x, gates, idx, slab_map=None, axis_name=None,
                     valid=None, layer=None, with_counts=False):
    """Routed-only SwiGLU over a full or partial expert bank.

    bank     : {"w_gate","w_up","w_down"} each (E_bank, K, N) FlashWeight
               (deployed), PagedWeight (pool-paged slab) or plain array;
               E_bank = n_experts for the resident engine, the device slab
               size for the streamed one.
    x        : (S, T, D) normed FFN input; gates/idx: (S, T, k).
    slab_map : (n_experts,) i32 expert-id -> bank row, -1 = not resident
               (those assignments contribute 0). None = identity (bank row
               e holds expert e).
    axis_name: tensor-parallel expert FFN inside a shard_map — each shard's
               slab holds the expert's d_ff/n_shards columns (gate/up
               column-parallel, down row-parallel over the same slice), so
               the down output is PARTIAL; kept f32 through the gate-
               weighted combine (all linear) and completed by ONE psum.
    valid    : (S, T) bool lanes that carry a token; padding lanes route
               nothing (None = every lane).
    layer    : index into a deployed bank stacked over layers, (L, E, K, N)
               (the resident step passes the whole stack: a layer sliced
               out before the branch that reads it is copied whole).
    with_counts: also return ``expert_counts`` of the valid assignments,
               (n_experts,) i32 — the step's MoE counters read them.

    Only the experts that valid assignments route to are read: their rows
    are gathered into a slab of ``slab_bounds(n_experts, k)`` rows (the
    least bound holding them, chosen in-graph), a deployed bank's rows
    are ECC-checked there under ``REPRO_SERVE_ECC=inline``, and each
    weight kind is one grouped product over the assignments sorted by
    slab row.
    """
    s, t, d = x.shape
    k = idx.shape[-1]
    a = s * t * k
    n_exp = bank["w_gate"].shape[-3] if slab_map is None \
        else slab_map.shape[0]
    flat_e = idx.reshape(a)
    ok = jnp.ones((a,), bool) if valid is None else jnp.repeat(
        valid.reshape(s * t), k)
    if slab_map is not None:
        ok = ok & (slab_map[flat_e] >= 0)
    ecc_inline = serve_ecc_mode() == "inline"
    down_dtype = jnp.float32 if axis_name is not None else jnp.bfloat16
    x_flat = x.reshape(s * t, d)

    with jax.named_scope("route"):
        counts = expert_counts(flat_e, ok, n_exp)
        bounds = slab_bounds(n_exp, k)
        pick = jnp.searchsorted(jnp.asarray(bounds, jnp.int32),
                                jnp.sum((counts > 0).astype(jnp.int32)))

    def branch(bound):
        return lambda _: routed_ffn(
            bank, x_flat, flat_e, ok, counts, slab_map, bound=bound,
            top_k=k, ecc_inline=ecc_inline, down_dtype=down_dtype,
            layer=layer)

    if isinstance(pick, jax.core.Tracer):
        out_a = jax.lax.switch(pick, [branch(b) for b in bounds], None)
    else:                        # eager: trace only the bound in use
        out_a = branch(bounds[int(pick)])(None)
    with jax.named_scope("route"):
        out = (out_a * gates.reshape(a)[:, None]).reshape(s, t, k, d).sum(
            axis=2)
        if axis_name is not None:
            out = jax.lax.psum(out, axis_name)
        out = out.astype(x.dtype)
    return (out, counts) if with_counts else out


def _layer_fwd(cfg, x, lp, positions, collect_kv=True):
    x = cm.pin_batch(x)
    lp = cm.pin_layer_grads(lp)
    h = dense._norm(cfg, x, lp, "ln1")
    q, kk, v = cm.qkv_project(lp["attn"], h, dense.attn_cfg(cfg), positions)
    attn = cm.chunked_attention(q, kk, v, causal=True)
    b, s, _, _ = attn.shape
    x = x + maybe_flash_matmul(attn.reshape(b, s, -1), lp["attn"]["wo"])
    x = x + moe_apply(cfg, lp["moe"], dense._norm(cfg, x, lp, "ln2"))
    return x, ((kk, v) if collect_kv else None)


def forward(cfg, params, tokens, remat=True, return_cache=False):
    b, s = tokens.shape
    positions = jnp.arange(s)
    x = jnp.take(params["embed"], tokens, axis=0)

    def body(x, lp):
        return _layer_fwd(cfg, x, lp, positions, collect_kv=return_cache)

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, kv_out = jax.lax.scan(body, x, params["layers"])
    ks, vs = kv_out if return_cache else (None, None)
    x = cm.rms_norm(x, params["final_norm"])
    logits = maybe_flash_matmul(x, params["lm_head"], out_dtype=jnp.float32)
    if return_cache:
        return logits, {"k": ks, "v": vs}
    return logits


def train_loss(cfg, params, batch):
    logits = forward(cfg, params, batch["tokens"], remat=True)
    return cm.softmax_xent(logits, batch["labels"])


def prefill(cfg, params, batch, pad_to=None):
    logits, cache = forward(cfg, params, batch["tokens"], return_cache=True)
    if pad_to is not None:
        s = cache["k"].shape[2]
        pad = [(0, 0), (0, 0), (0, pad_to - s), (0, 0), (0, 0)]
        cache = {k: jnp.pad(v, pad) for k, v in cache.items()}
    return logits[:, -1], cache


def decode_step(cfg, params, cache, batch):
    tokens = batch["token"][:, None]
    kv_len = batch["kv_len"]
    positions = jnp.reshape(kv_len, (1,))
    x = jnp.take(params["embed"], tokens, axis=0)

    def body(x, layer):
        lp, k_cache, v_cache = layer                      # read-only slices
        h = dense._norm(cfg, x, lp, "ln1")
        q, kk, v = cm.qkv_project(lp["attn"], h, dense.attn_cfg(cfg), positions)
        attn = cm.decode_attention_incremental(
            q, k_cache, v_cache, kv_len, kk, v)
        b = attn.shape[0]
        x = x + maybe_flash_matmul(attn.reshape(b, 1, -1), lp["attn"]["wo"])
        x = x + moe_apply(cfg, lp["moe"], dense._norm(cfg, x, lp, "ln2"),
                          capacity_factor=2.0)
        return x, (kk, v)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"]))
    zero = jnp.int32(0)
    ks = jax.lax.dynamic_update_slice(
        cache["k"], k_new.astype(cache["k"].dtype),
        (zero, zero, kv_len, zero, zero))
    vs = jax.lax.dynamic_update_slice(
        cache["v"], v_new.astype(cache["v"].dtype),
        (zero, zero, kv_len, zero, zero))
    x = cm.rms_norm(x, params["final_norm"])
    logits = maybe_flash_matmul(x[:, 0], params["lm_head"], out_dtype=jnp.float32)
    return logits, {"k": ks, "v": vs}


cache_shape = dense.cache_shape
