"""The serving step programs (DESIGN.md §6): pure functions that jax
traces, holding no host state and importing no plane. ``serving/engine.py``
(the resident plane) and the store-backed planes (``serving/streamed.py``,
``serving/expert_paged.py``) partial and jit them.

``_step_impl`` is the resident data plane: ONE static-shape MIXED-BATCH
step. Every step, each slot contributes up to ``chunk_tokens`` lanes of a
(n_slots, chunk_tokens) token batch — prefilling slots a chunk of their
prompt, decoding slots their single last-sampled token — and the step
embeds, runs a lax.scan over the stacked layer weights that CARRIES the
whole K/V pool: each layer reads its context with one block-PAGED gather
straight from the stacked pool (models/common.chunk_attention_paged), then
writes its chunk's K/V rows in place at ``[layer, blk, off]``. After the
scan the step evaluates lm_head ONLY at each slot's last valid lane,
samples, bumps per-slot lengths, and folds the Algorithm 2 bitmap update
into the same graph. Zero mid-step host syncs; KV buffers are donated.
Invalid lanes write to the pool's reserved dump block, so every write is
unconditional and static. The pool is lane-dense, ``(L, n_blocks, block,
KV * Dh)`` (serving/kvcache.py), and passes through the scan as carry,
never as a scanned operand: with a 64-wide ``Dh`` minor dimension, or with
the pool sliced per layer as ``xs`` and scattered once after the scan, XLA
re-lays the whole pool out around the scatter every step.

The streamed planes compose the same layer math from pieces: embed ->
``_stream_group_impl`` per layer group -> ``_finish_step`` (dense), and
``_moe_head_impl`` -> ``_moe_fused_impl`` per layer handoff ->
``_moe_tail_impl`` (expert-paged MoE).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import scheduler as sched
from repro.core.erdpe import flash_matmul
from repro.core.tiering import PagedWeight
from repro.models import common as cm
from repro.models import dense
from repro.models import moe as moe_mod
from repro.serving import spec as spec_mod
from repro.serving.kvcache import row_slots, write_rows
from repro.serving.sampler import last_valid_hidden, sample

# Every serving program rounds to bfloat16 exactly where it says so: XLA
# may otherwise keep a fused intermediate wider, so that the served tokens
# would hang on fusion decisions — and a sparse-expert router, which flips
# on the last bit of a near tie, would route as no stated precision does.
_jit = functools.partial(
    jax.jit, compiler_options={"xla_allow_excess_precision": False})


def _proj(x, w_dram, w_flash, bitmap):
    """Bitmap-dispatched projection: NPU bf16 vs flash ERDPE (Alg. 2)."""
    if w_flash is None or bitmap is None:
        return jnp.dot(x.astype(jnp.float32),
                       w_dram.astype(jnp.float32)).astype(jnp.bfloat16)
    flash_out = flash_matmul(x, w_flash, out_dtype=jnp.float32)
    return sched.split_projection(x, w_dram, flash_out, bitmap).astype(jnp.bfloat16)


@jax.named_scope("qkv")
def _qkv(cfg, lp, fl, x, positions, bitmap):
    """Shared QKV block (norm -> bitmap-dispatched projections -> qk-norm ->
    rope). Only wq is bitmap-dispatched (Alg. 2 rebalances the query path;
    K/V stay on the NPU as in the seed engine)."""
    ap = lp["attn"]
    b, s, _ = x.shape
    h = dense._norm(cfg, x, lp, "ln1")
    q = _proj(h, ap["wq"], None if fl is None else fl["wq"], bitmap).reshape(
        b, s, cfg.n_heads, cfg.head_dim)
    k = _proj(h, ap["wk"], None, None).reshape(
        b, s, cfg.n_kv_heads, cfg.head_dim)
    v = _proj(h, ap["wv"], None, None).reshape(
        b, s, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = cm.rms_norm(q, ap["q_norm"])
        k = cm.rms_norm(k, ap["k_norm"])
    if cfg.use_rope:
        q = cm.apply_rope(q, positions, cfg.rope_base)
        k = cm.apply_rope(k, positions, cfg.rope_base)
    return q, k, v


def _chunk_layer(cfg, exec_mode, bitmap, lengths, positions, block_tables,
                 x, layer, axis_name=None):
    """One mixed-batch layer over all slots' chunk lanes. ``layer`` =
    (params slice, flash attn copy slice, the stacked K/V pools, layer
    index). Attention reads this layer's context from the stacked pools
    (rows before each slot's ``lengths``); the chunk's own K/V enters
    through the intra-chunk causal term of chunk_attention_paged and is
    returned, for the caller to write: the resident step writes it in the
    scan right after this call, the streamed planes once per step in
    ``_finish_step``.

    ``axis_name`` = tensor-parallel FFN (DESIGN.md §11): attention and the
    bitmap-dispatched projections run REPLICATED (every shard holds the
    DRAM tier and the attn flash copies whole), the FFN consumes the
    shard-LOCAL page tables and finishes with ONE psum."""
    lp, fl, k_pool, v_pool, li = layer
    ap = lp["attn"]
    b, t, _ = x.shape                                    # t == chunk_tokens
    with jax.named_scope("attn"):
        q, k, v = _qkv(cfg, lp, fl, x, positions, bitmap)
        with jax.named_scope("core"):
            attn = cm.chunk_attention_paged(
                q, k_pool, v_pool, li, block_tables, lengths, k, v,
                window=cfg.local_window, mode=exec_mode)
        with jax.named_scope("out"):
            out = _proj(attn.reshape(b, t, -1), ap["wo"], fl["wo"], bitmap)
    x = x + out
    x = x + dense._ffn_apply(cfg, lp["ffn"], dense._norm(cfg, x, lp, "ln2"),
                             axis_name=axis_name)
    return x, (k, v)


def _moe_attn_router_body(cfg, exec_mode, lengths, positions, block_tables,
                          x, lp, k_pool, v_pool, li):
    """Attention + router for one MoE layer — the SINGLE definition both
    data planes compose (resident scan body and streamed router half), so
    the streamed-vs-resident parity the benchmark gates on holds by
    construction. MoE keeps Q/K/V/O on the NPU — the in-flash engine
    serves the EXPERT BANKS, the paper's best-fit case (DESIGN.md §9).
    Attention reads layer ``li`` of the stacked pools. Returns the
    post-attention residual, the normed FFN input, the router's
    (gates, idx), and the layer's fresh K/V."""
    b, t, _ = x.shape
    with jax.named_scope("attn"):
        q, k, v = _qkv(cfg, lp, None, x, positions, None)
        with jax.named_scope("core"):
            attn = cm.chunk_attention_paged(
                q, k_pool, v_pool, li, block_tables, lengths, k, v,
                window=cfg.local_window, mode=exec_mode)
        with jax.named_scope("out"):
            out = _proj(attn.reshape(b, t, -1), lp["attn"]["wo"], None,
                        None)
    x = x + out
    h = dense._norm(cfg, x, lp, "ln2")
    with jax.named_scope("route"):
        gates, idx = moe_mod.serve_route(
            lp["moe"]["router"], h, cfg.top_k,
            n_groups=getattr(cfg, "n_expert_groups", 1),
            topk_groups=getattr(cfg, "topk_expert_groups", 0))
    return x, h, gates, idx, k, v


def _chunk_layer_moe(cfg, exec_mode, lengths, positions, block_tables,
                     valid, experts, x, layer):
    """One mixed-batch MoE layer (resident data plane): the shared
    attention+router body + the routed-only expert FFN over the deployed
    bank (``slab_map=None`` — the streamed expert half's degenerate case).
    ``layer`` = (params slice without the expert bank, the stacked K/V
    pools, layer index); ``experts`` is the bank stacked over all layers,
    indexed by the layer only where routed rows are read. ``valid``
    (slots, T) marks the lanes that carry a token — padding lanes route
    nothing. Returns the layer's fresh K/V for the caller to write, as
    ``_chunk_layer`` does, and the layer's valid assignments and the
    distinct experts they route to (the step's MoE counters)."""
    lp, k_pool, v_pool, li = layer
    x, h, gates, idx, k, v = _moe_attn_router_body(
        cfg, exec_mode, lengths, positions, block_tables, x, lp, k_pool,
        v_pool, li)
    with jax.named_scope("ffn"):
        y, counts = moe_mod.serve_expert_ffn(experts, h, gates, idx,
                                             valid=valid, layer=li,
                                             with_counts=True)
    routed = jnp.sum((counts > 0).astype(jnp.int32))
    return x + y, (k, v, jnp.sum(counts), routed)


def _moe_attn_router_impl(cfg, exec_mode, layers_dram, k_pool, v_pool, x,
                          positions, ctx_lens, block_tables, lo):
    """STREAMED wrapper of the shared attention+router body. ``lo`` — the
    layer index — is a traced scalar, so every layer of every step replays
    ONE trace. The returned ``idx`` is the top-k EXPERT-ID BITMAP the
    engine ships to the host streamer (the MoE analog of Algorithm 2's
    plane bitmap)."""
    def sl(a):
        return jax.lax.dynamic_slice_in_dim(a, lo, 1, axis=0)[0]

    lp = jax.tree.map(sl, layers_dram)
    return _moe_attn_router_body(cfg, exec_mode, ctx_lens, positions,
                                 block_tables, x, lp, k_pool, v_pool, lo)


def _moe_expert_paged_impl(kn, x, h, gates, idx, slab, slab_map, pool_buf,
                           axis_name=None):
    """Pool-paged expert half: the slab is only PAGE TABLES (e_slab,)-
    stacked per param; the expert weights stay raw store pages in
    ``pool_buf`` and the batched-expert FFN gathers them in place —
    no per-layer slab re-stack, no host assembly. ``kn`` carries the
    static per-param (K, N) — shard-LOCAL under tensor parallelism, where
    ``axis_name`` closes each expert's contraction with one psum."""
    bank = {name: _paged(pool_buf, t, kn[name]) for name, t in slab.items()}
    with jax.named_scope("ffn"):
        return x + moe_mod.serve_expert_ffn(bank, h, gates, idx, slab_map,
                                            axis_name=axis_name)


def _moe_fused_impl(cfg, exec_mode, kn, layers_dram, k_pool, v_pool, x, h,
                    gates, idx, slab, slab_map, pool_buf, positions,
                    ctx_lens, block_tables, lo, axis_name=None):
    """FUSED streamed-MoE trace: the EXPERT half of layer ``lo - 1``
    chained into the attention+router half of layer ``lo`` — one jitted
    dispatch where the per-layer loop used to make two. The host expert-id
    handoff still sits between consecutive fused calls (layer ``lo``'s
    routing leaves this call, its expert set enters the next), so nothing
    about the expert-bitmap discipline changes — only the dispatch count
    halves. ``lo`` ranges over 1..L-1: layer 0's attention+router rides
    the HEAD trace (fused with the embed, ``_moe_head_impl``) and the
    last layer's expert half rides the TAIL trace (fused with the
    finish, ``_moe_tail_impl``), so a step is L+1 dispatches over three
    traces."""
    x = _moe_expert_paged_impl(kn, x, h, gates, idx, slab, slab_map,
                               pool_buf, axis_name=axis_name)
    # Barrier between the halves: without it XLA fuses the expert combine
    # into the attention prologue and carries the residual in f32 past the
    # bf16 handoff, drifting one ulp per layer off the split-dispatch plane
    # (and off the resident engine's greedy tokens). The barrier pins the
    # boundary activation to its stated dtype, keeping fused == split
    # bit-exact at half the dispatch count.
    x = jax.lax.optimization_barrier(x)
    return _moe_attn_router_impl(cfg, exec_mode, layers_dram, k_pool,
                                 v_pool, x, positions, ctx_lens,
                                 block_tables, lo)


def _moe_head_impl(cfg, proposer, spec_k, exec_mode, layers_dram, k_pool,
                   v_pool, params, lengths, tokens, q_lens, block_tables,
                   hist=None, hist_lens=None, draft_cap=None):
    """HEAD trace of the streamed-MoE plane: token embed (speculative
    drafting included) fused into layer 0's attention+router half — the
    embed/layer boundary folded into the adjacent jit, replacing the
    zero-expert-slab dispatch the old 4-trace plane paid for layer 0.
    Consumes no pool pages, so it jits plain even under tensor
    parallelism (everything it reads is replicated). The barrier pins
    the embed output to bf16 at the fusion seam, exactly like the
    expert→attention seam inside the fused trace — the head must stay
    bit-identical to the split embed-then-router dispatch it replaces."""
    if spec_k is None:
        x, positions, ctx_lens = _embed_chunk(cfg, params, lengths, tokens,
                                              q_lens)
        extras = ()
    else:
        x, positions, ctx_lens, q_lens, drafts, n_draft = _embed_spec(
            cfg, proposer, spec_k, params, lengths, tokens, q_lens, hist,
            hist_lens, draft_cap)
        extras = (q_lens, drafts, n_draft)
    x = jax.lax.optimization_barrier(x)
    x, h, gates, idx, k, v = _moe_attn_router_impl(
        cfg, exec_mode, layers_dram, k_pool, v_pool, x, positions,
        ctx_lens, block_tables, jnp.int32(0))
    return (x, h, gates, idx, k, v, positions, ctx_lens) + extras


def _moe_tail_impl(cfg, sched_cfg, sample_cfg, kv_aware, spec_k, kn,
                   final_norm, lm_head, state, x, h, gates, idx, slab,
                   slab_map, pool_buf, k_new, v_new, q_lens, admitted,
                   positions, block_tables, key, drafts=None, n_draft=None,
                   is_decode=None, axis_name=None):
    """TAIL trace of the streamed-MoE plane: the LAST layer's expert half
    fused into the finish step (final norm, sampling/verification, paged
    KV scatter, Algorithm 2) — the layer/finish boundary folded into one
    jitted dispatch, mirroring the head. The pool buffer is its only
    sharded operand under tensor parallelism; the barrier keeps the
    residual handoff bf16-exact (see ``_moe_fused_impl``)."""
    x = _moe_expert_paged_impl(kn, x, h, gates, idx, slab, slab_map,
                               pool_buf, axis_name=axis_name)
    x = jax.lax.optimization_barrier(x)
    return _finish_step(cfg, sched_cfg, sample_cfg, kv_aware, spec_k,
                        final_norm, lm_head, state, x, k_new, v_new,
                        q_lens, admitted, positions, block_tables, key,
                        drafts=drafts, n_draft=n_draft,
                        is_decode=is_decode)


@jax.named_scope("embed")
def _embed_chunk(cfg, params, lengths, tokens, q_lens):
    """Token embedding + lane bookkeeping — the head of the serving step,
    shared by the monolithic and streamed data planes.

    Returns (x, positions, ctx_lens) for the (slots, T) chunk batch."""
    t_chunk = tokens.shape[1]
    # absolute position of each chunk lane: cached context + lane offset
    lane = jnp.arange(t_chunk)[None, :]
    positions = lengths[:, None] + lane
    x = jnp.take(params["embed"], tokens, axis=0)
    if "pos_embed" in params:
        # padding lanes can point past the learned-position table, and an
        # out-of-bounds jnp.take fills NaN under jit — which would poison
        # VALID lanes through the intra-chunk 0*NaN products. Steer them
        # to row 0 (their K/V is causally masked and scatters to the dump
        # block, so the value never matters — it just must stay finite).
        emb_pos = jnp.where(lane < q_lens[:, None], positions, 0)
        x = x + jnp.take(params["pos_embed"], emb_pos, axis=0)

    # slots with no lanes this step keep stale/irrelevant lengths (O(1)
    # release never writes the device array); zero their attention context
    # so the paged kernel's dead-block skip holds — no valid query reads it.
    ctx_lens = jnp.where(q_lens > 0, lengths, 0)
    return x, positions, ctx_lens


def _finish_step(cfg, sched_cfg, sample_cfg, kv_aware, spec_k, final_norm,
                 lm_head, state, x, k_new, v_new, q_lens, admitted,
                 positions, block_tables, key, drafts=None, n_draft=None,
                 is_decode=None):
    """Everything after the layer stack — final norm, last-lane sampling,
    the length bump, in-graph Algorithm 2 — shared by the monolithic and
    streamed data planes.

    ``k_new``/``v_new`` (L, slots, T, KV, Dh) are the streamed planes'
    rows, which carry no pool through a scan: they are written here in ONE
    batched paged scatter for all layers. The resident step has written
    its rows inside the layer scan already and passes None.

    ``spec_k`` (static) switches on the speculative verify tail: lm_head
    is additionally evaluated on the first ``spec_k + 1`` lanes of every
    slot, ``spec.verify_lanes`` runs the in-graph accept/reject scan over
    decoding slots' draft lanes (``is_decode``), and the KV length
    advances by ``n_accept + 1`` instead of by the lanes written — the
    in-graph half of the KV rewind (rejected rows stay in place,
    unreachable past the length, overwritten by later steps). Returns
    ``(tokens (slots, spec_k+1), n_emit (slots,), state, stats)`` instead
    of the vanilla ``(tokens (slots,), state, stats)``.
    """
    lengths = state["lengths"]
    with jax.named_scope("lm_head"):
        if cfg.norm_type == "rms":
            x = cm.rms_norm(x, final_norm)
        else:
            x = cm.layer_norm(x, final_norm["g"], final_norm["b"])
        # lm_head ONLY at each slot's last valid lane — mid-prompt
        # positions never sample, so the (T-1) other vocab projections are
        # skipped.
        x_last = last_valid_hidden(x, q_lens)
        logits = flash_matmul(x_last, lm_head, out_dtype=jnp.float32)
        if spec_k is not None:
            # verify lanes: lm_head over the k+1 spec lanes (a decoding
            # slot's last valid lane is always among them).
            lane_logits = flash_matmul(x[:, :spec_k + 1], lm_head,
                                       out_dtype=jnp.float32)
    with jax.named_scope("sample"):
        if spec_k is None:
            toks = sample(logits, key, sample_cfg)
            n_emit = None
            adv = q_lens
        else:
            # accept/reject the verify lanes in-graph
            k_verify, k_last = jax.random.split(key)
            toks_v, n_accept = spec_mod.verify_lanes(
                lane_logits, drafts, n_draft, k_verify, sample_cfg)
            tok_last = sample(logits, k_last, sample_cfg)  # prefill ends
            toks = jnp.where(is_decode[:, None], toks_v, tok_last[:, None])
            n_emit = jnp.where(is_decode, n_accept + 1, 1).astype(jnp.int32)
            adv = jnp.where(is_decode, n_emit, q_lens)     # length REWIND

    # --- streamed planes: ONE batched write for all layers/slots/lanes -----
    kd, vd = state["k"], state["v"]
    if k_new is not None:
        with jax.named_scope("kv_write"):
            blk, off = row_slots(positions, q_lens, block_tables,
                                 kd.shape[2])
            kd = write_rows(kd, slice(None), blk, off, k_new)
            vd = write_rows(vd, slice(None), blk, off, v_new)
    new_lengths = lengths + adv

    # --- Algorithm 2: KV-cache-aware rebalance, in-graph -------------------
    # admitted (not worked): a budget-starved prefill slot's cached KV
    # still sets the attention-latency picture Algorithm 2 reacts to.
    # Speculative lengths count ACCEPTED rows only (the rewound length is
    # the attention context every later step actually reads).
    with jax.named_scope("alg2"):
        kv_len = jnp.max(jnp.where(admitted, new_lengths, 0))
        new_bitmap, new_prev, delta = sched.kv_aware_step(
            state["bitmap"], state["prev_cycles"], kv_len,
            cfg.d_model, cfg.n_kv_heads, cfg.head_dim, sched_cfg, kv_aware)
        npu_frac = sched.npu_fraction(new_bitmap)

    new_state = {"k": kd, "v": vd, "lengths": new_lengths,
                 "bitmap": new_bitmap, "prev_cycles": new_prev}
    stats = {"kv_len": kv_len, "delta_cycles": delta,
             "npu_fraction": npu_frac}
    if spec_k is None:
        return toks, new_state, stats
    dec = is_decode
    stats["spec_drafted"] = jnp.sum(jnp.where(dec, n_draft, 0))
    stats["spec_accepted"] = jnp.sum(jnp.where(dec, n_accept, 0))
    stats["spec_emitted"] = jnp.sum(jnp.where(dec, n_emit, 0))
    # per-slot drafted/accepted: the adaptive-k acceptance EMA's signal
    stats["spec_draft_slots"] = jnp.where(dec, n_draft, 0)
    stats["spec_accept_slots"] = jnp.where(dec, n_accept, 0)
    return toks, n_emit, new_state, stats


@jax.named_scope("embed")
def _embed_spec(cfg, proposer, spec_k, params, lengths, tokens, q_lens,
                hist, hist_lens, draft_cap):
    """Speculative head of the serving step: IN-GRAPH drafting + embedding.

    The drafter proposes up to ``spec_k`` tokens per slot from its token
    history; lanes 1..n_draft of decoding slots (``draft_cap > 0`` only
    there) are filled with the proposals and the slot's lane count grows
    to ``1 + n_draft`` — the verify pass then treats them like any other
    chunk lanes (the paged chunk path already handles T > 1 causal).
    Returns the vanilla embed tuple plus (q_lens, drafts, n_draft)."""
    drafts, n_avail = proposer.propose(hist, hist_lens)
    n_draft = jnp.minimum(n_avail, draft_cap).astype(jnp.int32)
    lane = jnp.arange(tokens.shape[1])[None, :]
    dpad = jnp.zeros_like(tokens).at[:, 1:spec_k + 1].set(drafts)
    use = (lane >= 1) & (lane <= n_draft[:, None])
    tokens = jnp.where(use, dpad, tokens)
    q_lens = q_lens + n_draft            # draft_cap == 0 off the decode path
    x, positions, ctx_lens = _embed_chunk(cfg, params, lengths, tokens, q_lens)
    return x, positions, ctx_lens, q_lens, drafts, n_draft


def _step_impl(cfg, sched_cfg, sample_cfg, kv_aware, exec_mode, unroll,
               proposer, spec_k, params, attn_flash, state, tokens, q_lens,
               admitted, block_tables, key, hist=None, hist_lens=None,
               draft_cap=None, is_decode=None):
    """One mixed prefill/decode step for ALL pool slots — the data plane.

    state  : {"k","v": (L, n_blocks, block_size, KV * Dh),
              "lengths": (slots,) i32, "bitmap": (H,) i32,
              "prev_cycles": i32} — donated when jitted.
    tokens : (slots, T) i32 chunk lanes per slot (don't-care past q_lens).
    q_lens : (slots,) i32 valid lanes per slot (0 = no work this step).
    admitted : (slots,) bool — slot holds a live request (it may still get
             0 lanes when the token budget starves it; its cached KV must
             keep counting toward Algorithm 2's kv_len).
    block_tables : (slots, max_blocks) i32; entry 0 = unmapped/dump.

    Returns (sampled (slots,) i32, new state, stats scalars) — or, with
    ``spec_k`` set, (tokens (slots, spec_k+1), n_emit, state, stats).
    Everything — drafting (spec), layer scan, paged attention, paged KV
    writes, length bump/rewind, Algorithm 2, sampling/verification — is
    one graph; idle slots compute garbage that is steered into the
    reserved dump block, so slot churn, ragged chunks, and admission churn
    never change shapes or retrace. The scan carries the K/V pools (module
    docstring): each layer gathers its context from them, then writes its
    chunk rows at ``[layer, blk, off]``, with ``(blk, off)`` computed once
    a step.
    """
    bitmap = state["bitmap"] if kv_aware else None
    if spec_k is None:
        drafts = n_draft = None
        x, positions, ctx_lens = _embed_chunk(cfg, params, state["lengths"],
                                              tokens, q_lens)
    else:
        x, positions, ctx_lens, q_lens, drafts, n_draft = _embed_spec(
            cfg, proposer, spec_k, params, state["lengths"], tokens, q_lens,
            hist, hist_lens, draft_cap)
    n_l = cfg.n_layers
    if cfg.family == "moe":
        # MoE projections stay on the NPU (no flash attn copy to dispatch
        # to), so the resident layer body drops the bitmap/flash operands.
        valid = jnp.arange(tokens.shape[1])[None, :] < q_lens[:, None]
        layers = params["layers"]
        experts = layers["moe"]["experts"]
        layers = {**layers, "moe": {k: v for k, v in layers["moe"].items()
                                    if k != "experts"}}
        layer_fn = functools.partial(_chunk_layer_moe, cfg, exec_mode,
                                     ctx_lens, positions, block_tables,
                                     valid, experts)
        xs = (layers, jnp.arange(n_l))
    else:
        layer_fn = functools.partial(_chunk_layer, cfg, exec_mode, bitmap,
                                     ctx_lens, positions, block_tables)
        xs = (params["layers"], attn_flash, jnp.arange(n_l))
    blk, off = row_slots(positions, q_lens, block_tables,
                         state["k"].shape[2])

    def body(carry, layer):
        x, k_pool, v_pool = carry
        *lp, li = layer
        x, out = layer_fn(x, (*lp, k_pool, v_pool, li))
        with jax.named_scope("kv_write"):
            k_pool = write_rows(k_pool, li, blk, off, out[0])
            v_pool = write_rows(v_pool, li, blk, off, out[1])
        return (x, k_pool, v_pool), out[2:]

    carry = (x, state["k"], state["v"])
    with jax.named_scope("layers"):
        if unroll:
            # eager reference: interpreted Python loop over layers
            outs = []
            for li in range(n_l):
                carry, out = body(carry, jax.tree.map(lambda a: a[li], xs))
                outs.append(out)
            per_layer = jax.tree.map(lambda *a: jnp.stack(a), *outs)
        else:
            carry, per_layer = jax.lax.scan(body, carry, xs)
    x, k_pool, v_pool = carry
    state = {**state, "k": k_pool, "v": v_pool}

    out = _finish_step(cfg, sched_cfg, sample_cfg, kv_aware, spec_k,
                       params["final_norm"], params["lm_head"], state, x,
                       None, None, q_lens, admitted, positions,
                       block_tables, key, drafts=drafts, n_draft=n_draft,
                       is_decode=is_decode)
    if cfg.family == "moe":
        # summed over layers, read with the step's one stats transfer
        out[-1]["moe_assignments"] = jnp.sum(per_layer[0])
        out[-1]["moe_experts_routed"] = jnp.sum(per_layer[1])
    return out


def _paged(pool_buf, tbl, kn):
    """Bind one page-table dict (q_tbl/p_slots/s_slots) to the pool
    snapshot as a PagedWeight — the flash weight the ERDPE consumes IN
    PLACE, no host slab ever assembled."""
    return PagedWeight(pool=pool_buf, q_tbl=tbl["q_tbl"],
                       p_slots=tbl["p_slots"], s_slots=tbl["s_slots"],
                       kn=tuple(kn))


def _stream_group_impl(cfg, exec_mode, kv_aware, group_size, shapes,
                       layers_dram, window, pool_buf, k_pool, v_pool, x,
                       positions, ctx_lens, block_tables, bitmap, lo,
                       axis_name=None):
    """One STREAMED layer group — the same per-layer math as the monolithic
    step's scan, but the flash-tier params arrive as PAGE TABLES into
    ``pool_buf`` (the device page pool the LayerStreamer fills from the
    PageStore — raw 16 KiB store pages, consumed in place by the paged
    ERDPE). ``shapes`` carries each param's static (K, N); ``lo`` — the
    group's first layer — is a traced scalar, so every group of every step
    replays ONE trace.

    Under tensor parallelism (DESIGN.md §11) this body runs inside a
    ``shard_map``: ``pool_buf`` is the shard-LOCAL page rows, ``shapes``
    the shard-LOCAL (K, N), and ``axis_name`` closes each layer's FFN
    with one psum."""
    bm = bitmap if kv_aware else None

    def sl(a):
        return jax.lax.dynamic_slice_in_dim(a, lo, group_size, axis=0)

    lp_g = jax.tree.map(sl, layers_dram)

    def body(x, layer):
        lp_d, tf_ffn, tf_attn, li = layer
        # graft the pool-paged flash FFN weights into the DRAM layer
        # params: the merged dict is exactly what the resident scan sees.
        lp = dict(lp_d)
        lp["ffn"] = {**lp.get("ffn", {}),
                     **{k: _paged(pool_buf, t, shapes["ffn"][k])
                        for k, t in tf_ffn.items()}}
        fl_attn = {k: _paged(pool_buf, t, shapes["attn"][k])
                   for k, t in tf_attn.items()}
        return _chunk_layer(cfg, exec_mode, bm, ctx_lens, positions,
                            block_tables, x,
                            (lp, fl_attn, k_pool, v_pool, li),
                            axis_name=axis_name)

    with jax.named_scope("layers"):
        x, (k_new, v_new) = jax.lax.scan(
            body, x, (lp_g, window["ffn"], window["attn"],
                      lo + jnp.arange(group_size)))
    return x, k_new, v_new
