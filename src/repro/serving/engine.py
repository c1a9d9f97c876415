"""NVLLM serving engine: the paper's end-to-end dataflow (§3.5) at request
level, with the KV-cache-aware scheduler (Algorithm 2) in the loop.

Execution model (dense decoder families — the paper's OPT/LLaMA models):

  prefill  : consumed in CHUNKS through the same step as decode — Q/K/V/O
             split between "NAND CMOS" (ERDPE over flash-tier INT8+ECC
             weights) and "NPU" (bf16 DRAM weights) by the Alg. 2 bitmap;
             attention + KV write on the NPU side; FFN fully in flash.
  decode   : attention on the NPU over the DRAM KV pool; FFN via ERDPE.
             Algorithm 2 compares the attention-latency increment against
             C_th and flips bitmap bits, moving Q/K/V/O column-groups to the
             flash engine — the projection matmuls are *dispatched by the
             bitmap* via scheduler.split_projection.

The engine is split control-plane / data-plane (DESIGN.md §6):

  * data plane — ``_step_impl``: ONE jax.jit-compiled, static-shape MIXED-
    BATCH step per engine. Every step, each slot contributes up to
    ``chunk_tokens`` lanes of a (n_slots, chunk_tokens) token batch —
    prefilling slots a chunk of their prompt, decoding slots their single
    last-sampled token — and the step embeds, runs a lax.scan over the
    stacked layer weights that CARRIES the whole K/V pool: each layer reads
    its context with one block-PAGED gather straight from the stacked pool
    (models/common.chunk_attention_paged), then writes its chunk's K/V rows
    in place at ``[layer, blk, off]``. After the scan the step evaluates
    lm_head ONLY at each slot's last valid lane, samples, bumps per-slot
    lengths, and folds the Algorithm 2 bitmap update into the same graph.
    Zero mid-step host syncs; KV buffers are donated. Invalid lanes write
    to the pool's reserved dump block, so every write is unconditional and
    static. The pool is lane-dense, ``(L, n_blocks, block, KV * Dh)``
    (serving/kvcache.py), and passes through the scan as carry, never as a
    scanned operand: with a 64-wide ``Dh`` minor dimension, or with the
    pool sliced per layer as ``xs`` and scattered once after the scan, XLA
    re-lays the whole pool out around the scatter every step.
  * control plane — the Python ``Engine``: a waiting->running admission
    queue (submit ENQUEUES; slots and worst-case block reservations are
    claimed at admission), per-step chunk planning under the Alg.2-coupled
    token budget (core/scheduler.plan_chunks), completion, O(1) slot
    release, stats. It feeds the step plain (n_slots, chunk_tokens) token
    arrays plus the block tables, so slot churn, ragged prompts, and
    oversubscribed admission never retrace the compiled step.

``compiled=False`` keeps the seed-style eager reference: the *same* per-
layer math driven by an interpreted Python loop over layers (the benchmark
baseline and correctness oracle for benchmarks/serve_{decode,mixed}.py).

``spec_cfg`` adds SPECULATIVE serving (DESIGN.md §8) on top of either data
plane: decoding slots pack ``[last_token, d_1 .. d_k]`` draft proposals
into their chunk lanes, one pass — one weight-stream window rotation in
streamed mode — verifies all k in-graph, and the step emits
``n_accept + 1`` tokens with a KV length rewind over the rejected lanes.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import itertools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core import scheduler as sched
from repro.core.erdpe import ExecMode, flash_matmul
from repro.core.tiering import (ATTN_FLASH_KEYS, FlashWeight, PagedWeight,
                                deploy, encode_flash, program_attn_flash)
from repro.models import common as cm
from repro.models import dense
from repro.models import moe as moe_mod
from repro.serving import spec as spec_mod
from repro.serving.kvcache import PagedKVPool, row_slots, write_rows
from repro.serving.prefix import PrefixIndex, block_hashes
from repro.serving.sampler import SampleConfig, last_valid_hidden, sample


# Every serving program rounds to bfloat16 exactly where it says so: XLA
# may otherwise keep a fused intermediate wider, so that the served tokens
# would hang on fusion decisions — and a sparse-expert router, which flips
# on the last bit of a near tie, would route as no stated precision does.
_jit = functools.partial(
    jax.jit, compiler_options={"xla_allow_excess_precision": False})


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int
    pos: int = 0                     # prompt tokens consumed (chunked prefill)
    slot: int | None = None          # None while waiting for admission
    out: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    cancelled: bool = False          # set by Engine.cancel; done implies no
                                     # more tokens, cancelled implies no
                                     # prefix retain and an unread stream
    cached_len: int = 0              # prompt tokens adopted from the prefix
                                     # cache at admission (pos starts here)
    t_queued: float = 0.0            # perf_counter: entered ``waiting``
    t_admitted: float = 0.0          # perf_counter: given a slot

    @property
    def prefilling(self) -> bool:
        return self.pos < len(self.prompt)

    @property
    def kv_rows(self) -> int:
        """Worst-case KV footprint: every prompt token plus every decode
        step writes one row; the LAST sampled token is never written back
        (prefill always writes the whole prompt, so max_new=0 still needs
        len(prompt) rows). Admission validates and reserves this count."""
        return len(self.prompt) + max(self.max_new - 1, 0)


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


def _moe_expert_impl(x, h, gates, idx, slab, slab_map):
    """Expert half of one STREAMED MoE layer: the routed-only expert FFN
    over the device SLAB holding only the routed (resident/fetched)
    experts. Per-expert computation is independent of bank composition,
    so slab-vs-full-bank parity is exact."""
    with jax.named_scope("ffn"):
        return x + moe_mod.serve_expert_ffn(slab, h, gates, idx, slab_map)


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


class Engine:
    """cfg must be a dense-family ArchConfig (the paper's model families).

    ``compiled=True`` (default) serves prefill AND decode through the single
    jitted mixed-batch step; ``compiled=False`` runs the identical math as
    an interpreted per-layer loop (seed-style eager reference).
    ``exec_mode`` picks the paged-attention backend (PALLAS kernel vs XLA),
    mirroring erdpe.flash_matmul's split. ``block_size``/``n_blocks`` size
    the paged KV pool; ``admission_cfg`` sets the chunk width and the
    Alg.2/stall-coupled per-step token budget.

    ``spec_cfg`` turns on SPECULATIVE serving (DESIGN.md §8): decoding
    slots pack ``[last_token, d_1 .. d_k]`` into their chunk lanes, one
    forward pass — one weight-stream window rotation in streamed mode —
    verifies all k proposals, and each verify step emits ``n_accept + 1``
    tokens. ``drafter='model'`` additionally takes a small resident draft
    model (``draft_cfg``/``draft_params``, dense family, kept bf16).
    """

    def __init__(self, cfg, params, max_slots: int = 4, max_seq: int = 256,
                 sample_cfg: SampleConfig = SampleConfig(),
                 sched_cfg: sched.SchedulerConfig | None = None,
                 kv_aware: bool = True, rber: float = 0.0, seed: int = 0,
                 compiled: bool = True, exec_mode: ExecMode = ExecMode.XLA,
                 block_size: int = 16, n_blocks: int | None = None,
                 admission_cfg: sched.AdmissionConfig | None = None,
                 weight_store=None, stream_cfg=None,
                 spec_cfg: spec_mod.SpecConfig | None = None,
                 draft_cfg=None, draft_params=None,
                 prefix_cache: bool = False,
                 max_waiting: int | None = None,
                 registry: "obs.MetricsRegistry | None" = None):
        if cfg.family not in ("dense", "moe"):
            raise ValueError("engine serves dense- and moe-family archs "
                             f"(got {cfg.family!r})")
        self.cfg = cfg
        self.sample_cfg = sample_cfg
        self.kv_aware = kv_aware
        self.compiled = compiled
        self.admission_cfg = admission_cfg or sched.AdmissionConfig()
        self.store = weight_store
        self.streamed = weight_store is not None
        self.streamed_moe = self.streamed and cfg.family == "moe"
        if self.streamed and not compiled:
            raise ValueError("streamed mode runs through the compiled data "
                             "plane (compiled=False has no layer groups)")
        self.spec_cfg = spec_cfg
        if spec_cfg is not None:
            if not compiled:
                raise ValueError("speculative decoding runs through the "
                                 "compiled data plane (compiled=False has "
                                 "no verify lanes)")
            if spec_cfg.k + 1 > self.admission_cfg.chunk_tokens:
                raise ValueError(
                    f"spec k={spec_cfg.k} needs k+1 <= chunk_tokens="
                    f"{self.admission_cfg.chunk_tokens} verify lanes")
            self.proposer = spec_mod.DraftProposer(spec_cfg, draft_cfg,
                                                   draft_params)
        else:
            self.proposer = None
        # DRAM tier: bf16 attention weights (copied once at init, §3.5);
        # flash tier: INT8+ECC FFN / lm_head AND (dense) a flash copy of
        # Q/K/V/O so the bitmap can offload projection columns to the
        # in-flash engine. MoE keeps attention DRAM-only: the flash engine
        # serves the EXPERT BANKS (DESIGN.md §9). With a ``weight_store``
        # the flash tier is serialized into the host-resident PageStore
        # instead (its leaves become StoreRefs) and streamed under compute
        # (DESIGN.md §7) — or, MoE, expert-paged by the router (§9).
        # A weight_store that ALREADY holds a page table is preprogrammed —
        # opened from a persisted die image (``serve --store-image``).
        # NAND programming is write-once, so the flash tier is rebuilt from
        # the page table instead of re-deployed, and ``params`` is expected
        # to be the DRAM tier only (the checkpoint deploy --store wrote).
        self.store_preprogrammed = self.streamed and len(weight_store.table) > 0
        if self.store_preprogrammed:
            from repro.store.pagestore import graft_store_refs
            if rber > 0.0:
                raise ValueError(
                    "rber applies at flash-programming time; a preprogrammed "
                    "store already carries its own error injection (re-run "
                    "deploy --store with --rber instead)")
            # cast the DRAM tier bf16 exactly as deploy() would: callers may
            # hand raw init params (or reuse a programmed store), and an f32
            # DRAM tier would silently diverge from every deployed engine.
            dram = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
            refs = weight_store.param_refs(exclude_prefixes=("attn_flash/",))
            self.params = graft_store_refs(dram, refs)
            self.tier_map = {p: "flash" for p in refs}
        else:
            self.params, self.tier_map = deploy(params, rber=rber, seed=seed,
                                                store=weight_store)
        if self.streamed:
            from repro.store.streamer import StreamConfig
            self.stream_cfg = stream_cfg or StreamConfig()
            self.mesh = self._make_mesh(exec_mode)
            self._entry_plans: dict = {}
            self.attn_flash = None
            if self.streamed_moe:
                self._init_streamed_moe(max_slots)
            else:
                self._init_streamed(params, rber, seed)
        else:
            self.stream_cfg = None
            self.mesh = None
            self.attn_flash = (None if cfg.family == "moe"
                               else self._flash_attn_copy(params, rber, seed))
        h = sched_cfg.h if sched_cfg else 32
        while cfg.n_heads * cfg.head_dim % h:
            h //= 2
        self.sched_cfg = sched_cfg or sched.SchedulerConfig(
            column_bytes=cfg.d_model, h=h)
        self.bitmap = sched.init_bitmap(self.sched_cfg)
        self.pool = PagedKVPool(cfg.n_layers, max_slots, max_seq,
                                cfg.n_kv_heads, cfg.head_dim,
                                block_size=block_size, n_blocks=n_blocks)
        # admission cap on a request's KV rows: the exact max_seq, the
        # physical pool minus the dump block, and (learned positions) the
        # embedding table — shared by submit() and the verify-lane cap.
        kv_cap = min(self.pool.max_seq,
                     (self.pool.n_blocks - 1) * self.pool.block_size)
        if "pos_embed" in self.params:
            kv_cap = min(kv_cap, self.params["pos_embed"].shape[0])
        self._kv_cap = kv_cap
        # hash-based prefix caching (DESIGN.md §12): completed requests
        # retain their full prompt blocks under a chain hash; admission
        # adopts the longest cached chain copy-free (ref bump only).
        self.prefix = PrefixIndex(self.pool) if prefix_cache else None
        self._prefix_tokens_saved = 0
        # control-plane lock: submit/cancel-sweep/step/close mutate the
        # queues and the pool from different threads when a serving
        # frontend drives the engine. An RLock (step re-enters _admit)
        # with a Condition for the bounded-submit wait; ``cancel`` stays
        # LOCK-FREE (flag flips only) so a disconnect never blocks behind
        # a running step.
        self._mu = threading.RLock()
        self._cv = threading.Condition(self._mu)
        self._closed = False
        # close() is idempotent AND thread-safe: the first caller does the
        # work (and blocks behind any in-flight step via _cv — the clean
        # join), later/concurrent callers are a no-op.
        self._close_lock = threading.Lock()
        self._close_done = False
        self.max_waiting = max_waiting
        self.requests: dict[int, Request] = {}
        self.waiting: collections.deque[Request] = collections.deque()
        self._next_rid = 0
        self._key = jax.random.PRNGKey(seed)
        self._prev_cycles = jnp.int32(0)
        # sharded planes return the serving state replicated over the
        # mesh: start it that way, or step 2 retraces every jit for the
        # changed input sharding
        self.pool.set_device_state(
            self._put_replicated(self.pool.device_state()))
        self.bitmap, self._prev_cycles = self._put_replicated(
            (self.bitmap, self._prev_cycles))
        self._npu_frac = 1.0             # host view of the Alg. 2 bitmap
        self._stall_frac = 0.0           # EMA of streamer stall per step
        self._steps_done = 0
        self._auto_depth_done = False
        self.stats: list[dict] = []
        # ObsPlane (DESIGN.md §14): per-step phase histogram + timeline
        # ring. The registry defaults to the process-wide one; disabled
        # registries hand out no-op instruments, so the per-step cost of
        # a dark plane is a few perf_counter reads.
        self.obs = registry if registry is not None \
            else obs.default_registry()
        self.timeline = obs.StepTimeline(256)
        self._h_step = self.obs.histogram(
            "engine_step_seconds", "serving step host wall time by phase",
            label_names=("phase",))
        self._c_step_tokens = self.obs.counter(
            "engine_tokens_total", "tokens processed by the step loop",
            label_names=("kind",))
        # request lifecycle (split of time to first token) and the
        # scheduler/KV state each step plans under
        self._h_admit_wait = self.obs.histogram(
            "engine_admission_wait_seconds",
            "request enters the waiting queue -> admitted to a slot")
        self._h_prefill = self.obs.histogram(
            "engine_prefill_seconds",
            "admission -> the step whose sync hands over the first token")
        self._h_budget = self.obs.histogram(
            "engine_step_token_budget",
            "per-step token budget from scheduler.step_token_budget",
            buckets=obs.log_buckets(1.0, 1024.0, 4))
        self._c_kv_reserved = self.obs.counter(
            "engine_kv_rows_reserved_total",
            "per step, KV rows the active requests reserved at admission")
        self._c_kv_used = self.obs.counter(
            "engine_kv_rows_used_total",
            "per step, KV rows the active requests hold")
        self._c_moe_assign = self.obs.counter(
            "engine_moe_assignments_total",
            "token->expert assignments of valid lanes, summed over layers")
        self._c_moe_routed = self.obs.counter(
            "engine_moe_experts_routed_total",
            "distinct experts routed by valid lanes, summed over layers")
        self._phases: dict[str, float] = {}
        # per-slot token histories feeding the in-graph drafter (spec mode)
        if spec_cfg is not None:
            self._hist = np.zeros((max_slots, max_seq + 1), np.int32)
            self._hist_lens = np.zeros((max_slots,), np.int32)
            self._spec_totals = {"verify_steps": 0, "drafted": 0,
                                 "accepted": 0, "emitted": 0}
            # per-slot acceptance-rate EMA driving the adaptive verify-lane
            # count (SpecConfig.adaptive_k); reset to optimistic full depth
            # when a slot is re-admitted.
            self._accept_ema = np.ones((max_slots,), np.float64)
        step = functools.partial(
            _step_impl, cfg, self.sched_cfg, sample_cfg, kv_aware,
            exec_mode, not compiled, self.proposer,
            spec_cfg.k if spec_cfg else None)
        self._trace_count = 0
        if self.streamed_moe:
            self._build_stream_fns_moe(exec_mode)
        elif self.streamed:
            self._build_stream_fns(exec_mode)
        elif compiled:
            def serve_step(*args):
                # Python body only runs while jax traces; compiled replays
                # skip it — so this counts traces, not steps. The name is
                # the compiled module's in a profile (``jit_serve_step``).
                self._trace_count += 1
                return step(*args)

            # donate the KV pool + scheduler state: the step is an in-place
            # update of device-resident serving state. (CPU ignores donation
            # and warns, so only donate where it lands.)
            donate = (2,) if jax.default_backend() != "cpu" else ()
            self._step_fn = _jit(serve_step, donate_argnums=donate)
        else:
            self._step_fn = step

    def _flash_attn_copy(self, params, rber, seed):
        """Per-layer flash (INT8+ECC) copies of Q/K/V/O, stacked along a
        leading layer axis so the compiled step can lax.scan over them."""
        layers = params["layers"]["attn"]
        n_l = layers["wq"].shape[0]
        per_layer = [
            {k: encode_flash(layers[k][li], rber=rber, seed=seed + li)
             for k in ("wq", "wk", "wv", "wo")}
            for li in range(n_l)
        ]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)

    # --- streamed mode (FlashStore weight tier, DESIGN.md §7) -----------------

    _ATTN_FLASH_KEYS = ATTN_FLASH_KEYS   # shared with deploy --store

    # --- tensor-parallel streamed serving (DESIGN.md §11) ---------------------

    def _make_mesh(self, exec_mode):
        """The "model" mesh behind ``StreamConfig.n_shards`` (None when
        unsharded). Sharded serving runs the XLA data plane: the paged
        Pallas kernel has no shard_map lowering yet."""
        sc = self.stream_cfg
        if sc.n_shards <= 1:
            return None
        if exec_mode == ExecMode.PALLAS:
            raise ValueError(
                "n_shards > 1 serves through the XLA data plane "
                "(exec_mode=XLA); the paged Pallas kernel has no shard_map "
                "lowering yet")
        from repro.launch.mesh import make_model_mesh
        return make_model_mesh(sc.n_shards)

    def _entry_plan(self, name: str):
        """ShardPlan for one store entry (sharded mode only), memoized —
        the same plan the ShardedWeightPagePool derives, computed here too
        because pool SIZING needs per-shard page counts before the pool
        exists."""
        plan = self._entry_plans.get(name)
        if plan is None:
            from repro.launch.sharding import tp_shard_axis
            plan = self.store.shard_entry(name, self.stream_cfg.n_shards,
                                          tp_shard_axis(name))
            self._entry_plans[name] = plan
        return plan

    def _entry_pages_local(self, name: str) -> int:
        """Physical pool pages entry ``name`` occupies PER SHARD."""
        if self.mesh is None:
            return self.store.entry_pages(name)
        p = self._entry_plan(name)
        pb = self.store.page_bytes
        return (len(p.q_pages[0]) + -(-p.parity_nbytes // pb)
                + -(-p.scale_nbytes // pb))

    def _entry_nbytes_local(self, name: str) -> int:
        """Payload bytes entry ``name`` occupies PER SHARD."""
        if self.mesh is None:
            return self.store.entry_nbytes(name)
        return self._entry_plan(name).local_payload_bytes

    def _entry_kn(self, name: str) -> tuple:
        """The (K, N) the data plane binds for entry ``name`` — the full
        matrix unsharded, the shard-LOCAL partition under TP."""
        if self.mesh is None:
            return tuple(self.store.table[name]["q"].shape)
        return tuple(self._entry_plan(name).local_kn)

    def _make_wpool(self, n_pages: int):
        """The device weight page pool — shard-partitioned over the mesh
        when TP serving is on (``n_pages`` is then PER-SHARD slots)."""
        from repro.store.page_pool import (ShardedWeightPagePool,
                                           WeightPagePool)
        if self.mesh is None:
            return WeightPagePool(self.store, n_pages, donate=True)
        return ShardedWeightPagePool(self.store, n_pages, self.mesh,
                                     donate=True)

    def _put_replicated(self, tree):
        """Commit a pytree replicated over the mesh. The mesh jits reject
        arrays COMMITTED to a single device, and leaving persistent inputs
        uncommitted would re-replicate them every call — so everything the
        step reads every step (DRAM tier, lm_head) lands here once."""
        if self.mesh is None:
            return tree
        sh = NamedSharding(self.mesh, P())
        return jax.tree.map(lambda a: jax.device_put(a, sh), tree)

    def _check_shardable(self, names):
        """Refuse silent replication of entries the TP rules say must
        shard: the FFN psum is unconditional under TP, so a replicated
        w_gate/w_up/w_down would overcount the product n_shards times."""
        if self.mesh is None:
            return
        from repro.launch.sharding import tp_shard_axis
        bad = sorted({n.partition("@")[0] for n in names
                      if tp_shard_axis(n) is not None
                      and self._entry_plan(n).axis is None})
        if bad:
            s = self.stream_cfg.n_shards
            raise ValueError(
                f"n_shards={s} cannot partition {bad}: the sharded matrix "
                f"dim must divide into {s} whole 128-wide tile columns/rows "
                "(make d_ff/d_model a multiple of 128*n_shards, or lower "
                "n_shards)")

    def _init_streamed(self, raw_params, rber, seed):
        """Flash tier lives in the PageStore: program the per-layer attn
        flash copies next to deploy()'s FFN/lm_head entries, split the DRAM
        remainder out of the tiered pytree, and stand up the residency
        cache + layer streamer under the device weight budget."""
        from repro.store.pagestore import StoreRef, drop_store_refs
        from repro.store.streamer import LayerStreamer, ResidencyCache

        cfg, sc = self.cfg, self.stream_cfg
        if cfg.n_layers % sc.group_size:
            raise ValueError(f"group_size={sc.group_size} must divide "
                             f"n_layers={cfg.n_layers}")
        # per-layer flash Q/K/V/O copies, same seed derivation as the
        # resident engine's _flash_attn_copy (numerically identical tiers).
        # A preprogrammed store (die image) normally carries them already —
        # deploy --store emits them — so only the MISSING copies are
        # programmed; a read-only image without them cannot be fixed here.
        if f"attn_flash/{self._ATTN_FLASH_KEYS[0]}@0" not in self.store.table:
            if isinstance(self.store._data, np.memmap):
                raise ValueError(
                    "die image lacks the per-layer attn flash copies and is "
                    "read-only; re-run launch/deploy.py --store (it emits "
                    "them) or serve from a writable store")
            program_attn_flash(self.store, raw_params["layers"]["attn"],
                               cfg.n_layers, rber=rber, seed=seed)
        self._ffn_refs = {k: v for k, v in self.params["layers"]["ffn"].items()
                          if isinstance(v, StoreRef)}
        stray = [p for p, t in self.tier_map.items()
                 if t == "flash" and p != "lm_head"
                 and not p.startswith("layers/ffn/")]
        if stray:
            raise ValueError("streamed mode expects the dense flash layout "
                             f"(layers/ffn/* + lm_head); stray flash leaves "
                             f"would silently never be fetched: {stray}")
        # DRAM-resident halves of the tiered pytree, fed to the jitted fns
        self._layers_dram = self._put_replicated(
            drop_store_refs(self.params["layers"]))
        self._dram_params = self._put_replicated(
            {k: self.params[k]
             for k in ("embed", "pos_embed", "final_norm")
             if k in self.params})
        self.n_groups = cfg.n_layers // sc.group_size
        self._check_shardable(self._group_entries(0))

        group_bytes = max(
            sum(self.store.entry_nbytes(n) for n in self._group_entries(g))
            for g in range(self.n_groups))
        self._group_bytes = group_bytes      # depth auto-tuning re-budgets
        lm_bytes = self.store.entry_nbytes("lm_head")
        # the rotating window holds up to prefetch_depth groups in flight;
        # whatever budget remains is residency-cache capacity.
        window_bytes = sc.prefetch_depth * group_bytes
        if sc.device_budget_bytes is None or sc.pin_all:
            cache_cap = None
        else:
            cache_cap = sc.device_budget_bytes - window_bytes
            if cache_cap < lm_bytes:
                raise ValueError(
                    f"device_budget_bytes={sc.device_budget_bytes} cannot "
                    f"hold {sc.prefetch_depth} prefetch windows "
                    f"({window_bytes}B) + pinned lm_head ({lm_bytes}B)")
        # device weight page pool: windows upload as ONE staged transfer
        # each and compute consumes the raw store pages in place. Sized in
        # PHYSICAL pages (padded tiles inflate small params past their
        # payload bytes): worst payload->page ratio over the streamed tier
        # converts the cache's payload budget, plus in-flight windows and
        # one retiring transient; capped at the whole tier. Budget
        # ACCOUNTING stays payload-byte everywhere — this only sizes the
        # physical backing (with _grow as the overflow valve).
        # Sharded serving: page counts/bytes below are PER-SHARD (the pool
        # is shard-local backing) while the cache budget stays AGGREGATE;
        # the clamp below then re-bounds the cache so each shard's backing
        # pages fit its ~budget/n_shards share (StreamConfig.n_shards).
        group_names = [self._group_entries(g) for g in range(self.n_groups)]
        group_pages = [sum(self._entry_pages_local(n) for n in names)
                       for names in group_names]
        tier_pages = sum(group_pages)
        pb = self.store.page_bytes
        # LOCAL pool pages per GLOBAL cached payload byte, at WINDOW
        # granularity: the cache charges aggregate payload bytes per
        # window, and a shard's backing pages don't split evenly —
        # replicated entries (attention) keep their FULL pages on every
        # shard — so the conversion uses whole-window local-pages /
        # global-bytes ratios, never a 1/n_shards budget split (which
        # undersizes the pool, and a mid-run grow costs a retrace).
        worst = max(gp * pb
                    / max(sum(self.store.entry_nbytes(n) for n in names), 1)
                    for gp, names in zip(group_pages, group_names))
        # trace-static reservation: in-flight prefetch windows + one
        # retiring transient, in (local) pool pages — surfaced in
        # stream_stats so budget gates can separate it from cache bytes
        self._pool_reserve_pages = \
            (sc.prefetch_depth + 1) * max(group_pages)
        if cache_cap is not None and sc.n_shards > 1:
            # the per-DEVICE bound the mesh divides (each device holds
            # ~budget/n_shards): clamp the cache's payload capacity so one
            # shard's cache-backing pages fit its budget share — the local
            # pool then never exceeds budget/n_shards + the reserve above.
            cache_cap = min(cache_cap, int(sc.device_budget_bytes
                                           / (sc.n_shards * worst)))
            if cache_cap < lm_bytes:
                raise ValueError(
                    f"device_budget_bytes={sc.device_budget_bytes} over "
                    f"{sc.n_shards} shards leaves a per-device share too "
                    f"small for the pinned lm_head ({lm_bytes}B); raise "
                    "the budget")
        if cache_cap is None:
            n_pages = tier_pages
        else:
            n_pages = min(tier_pages,
                          -(-int(worst * cache_cap) // pb)
                          + self._pool_reserve_pages)
        self.wpool = self._make_wpool(n_pages)
        self._win_shapes = {
            "ffn": {k: self._entry_kn(ref.entry(0))
                    for k, ref in self._ffn_refs.items()},
            "attn": {k: self._entry_kn(f"attn_flash/{k}@0")
                     for k in self._ATTN_FLASH_KEYS},
        }
        self.cache = ResidencyCache(cache_cap, on_evict=self._evict_window)
        self.streamer = LayerStreamer(self.n_groups, self._fetch_group,
                                      self.cache, sc.prefetch_depth,
                                      discard=self._discard_window)
        # hot pins: lm_head is read EVERY step (sampling); first/last layer
        # groups bound the stream's cold start and tail when they fit.
        # lm_head stays a device FlashWeight (finish_fn reads it whole every
        # step — residency, not rotation, so it skips the pool).
        self._lm_head = self._put_replicated(self.store.get("lm_head"))
        self.cache.insert("lm_head", self._lm_head, lm_bytes, pin=True)
        if sc.pin_all:
            for g in range(self.n_groups):
                self.streamer.pin(g)
        elif sc.pin_edges:
            for g in dict.fromkeys((0, self.n_groups - 1)):
                self.streamer.pin(g)
        # init-time reads (lm_head fetch, pinned-group fetches) are
        # deployment, not serving: start the NAND/page accounting clean so
        # stream_stats reports what SERVING actually read.
        self.store.reset_counters()
        self.wpool.reset_counters()

    def _evict_window(self, key, value):
        """ResidencyCache/ExpertCache eviction hook: hand an evicted
        window's pool pages back to the allocator (safe immediately —
        eviction never fires on ref-held/pinned entries, and any dispatched
        compute holds its own pool-buffer snapshot)."""
        if isinstance(value, dict) and "slots" in value:
            self.wpool.free(value["slots"])

    def _discard_window(self, value):
        """Streamer/prefetcher cleanup for a fetched window the cache did
        not keep: free its transient pool pages (called after the consumer
        retired the window)."""
        if isinstance(value, dict) and "slots" in value:
            self.wpool.free(value["slots"])

    def _group_entries(self, g: int) -> list[str]:
        """Store entry names backing layer group ``g``'s device window."""
        lo = g * self.stream_cfg.group_size
        names = []
        for li in range(lo, lo + self.stream_cfg.group_size):
            names += [ref.entry(li) for ref in self._ffn_refs.values()]
            names += [f"attn_flash/{k}@{li}" for k in self._ATTN_FLASH_KEYS]
        return names

    def _fetch_group(self, g: int):
        """Upload one layer group's pages into the device page pool — ONE
        staged transfer for the whole window (the pool reads every entry's
        pages into one contiguous host staging buffer, one device_put, one
        scatter) — and assemble the window of (G,)-stacked PAGE TABLES the
        group trace binds to the pool. No host detiling, no per-param
        stacks, no per-param device_puts. Runs on the streamer's worker
        thread."""
        sc = self.stream_cfg
        lis = range(g * sc.group_size, (g + 1) * sc.group_size)
        tbls = self.wpool.upload(self._group_entries(g))

        def stack(names):
            ts = [tbls[n] for n in names]
            return {k: jnp.asarray(np.stack([t[k] for t in ts]))
                    for k in ("q_tbl", "p_slots", "s_slots")}

        win = {
            "ffn": {k: stack([ref.entry(li) for li in lis])
                    for k, ref in self._ffn_refs.items()},
            "attn": {k: stack([f"attn_flash/{k}@{li}" for li in lis])
                     for k in self._ATTN_FLASH_KEYS},
            # host bookkeeping: the hand-back token for pool free on
            # eviction/discard (stripped before the jitted group fn)
            "slots": np.concatenate([t["slots"] for t in tbls.values()]),
        }
        nbytes = sum(self.store.entry_nbytes(n) for n in self._group_entries(g))
        return win, nbytes

    # --- streamed MoE mode (ExpertStore expert paging, DESIGN.md §9) ----------

    def _init_streamed_moe(self, max_slots: int):
        """MoE flash tier: the per-(layer, expert) bank slices live in the
        PageStore (``deploy`` splits stacked ``(L, E, K, N)`` banks at
        ``name@li.ei`` — the store's per-leading-index split IS expert
        granularity); router/attention/norms stay DRAM. Stands up the
        ``ExpertCache`` (byte-budgeted (layer, expert) residency) and the
        router-history prefetcher under the device budget; the rotating
        per-layer expert SLAB is budget-accounted like the dense prefetch
        windows."""
        from repro.store.expert_cache import ExpertCache, ExpertPrefetcher
        from repro.store.pagestore import StoreRef, drop_store_refs

        cfg, sc = self.cfg, self.stream_cfg
        if sc.group_size != 1:
            raise ValueError(
                f"group_size={sc.group_size}: MoE streaming is per-layer "
                "(group_size=1) — each layer's routing depends on the "
                "previous layer's experts, so a multi-layer group cannot "
                "know its expert set up front")
        experts = self.params["layers"]["moe"]["experts"]
        self._expert_refs = {k: v for k, v in experts.items()
                             if isinstance(v, StoreRef)}
        if set(self._expert_refs) != {"w_gate", "w_up", "w_down"}:
            raise ValueError("MoE streamed mode expects the expert bank "
                             "(w_gate/w_up/w_down) in the store, got "
                             f"{sorted(self._expert_refs)}")
        for ref in self._expert_refs.values():
            if ref.lead != (cfg.n_layers, cfg.n_experts):
                raise ValueError(
                    f"expert bank {ref.name!r} is split {ref.lead}, expected "
                    f"(n_layers, n_experts)=({cfg.n_layers}, {cfg.n_experts})")
        stray = [p for p, t in self.tier_map.items()
                 if t == "flash" and p != "lm_head"
                 and not p.startswith("layers/moe/experts/")]
        if stray:
            raise ValueError("MoE streamed mode expects the expert flash "
                             "layout (layers/moe/experts/* + lm_head); stray "
                             f"flash leaves would never be fetched: {stray}")
        self._layers_dram = self._put_replicated(
            drop_store_refs(self.params["layers"]))
        self._dram_params = self._put_replicated(
            {k: self.params[k]
             for k in ("embed", "pos_embed", "final_norm")
             if k in self.params})
        self._check_shardable(
            [ref.entry(0, 0) for ref in self._expert_refs.values()])
        self._expert_nbytes = [
            [sum(self.store.entry_nbytes(ref.entry(li, e))
                 for ref in self._expert_refs.values())
             for e in range(cfg.n_experts)]
            for li in range(cfg.n_layers)]
        max_expert = max(max(r) for r in self._expert_nbytes)
        self._max_expert_bytes = max_expert
        # fetch generation counter + per-layer device-slab memo (see
        # _acquire_experts): both must exist before the pin loops fetch.
        self._fetch_gen = itertools.count(1)
        self._slab_memo: dict = {}
        worst_routed = min(cfg.n_experts,
                           max_slots * self.admission_cfg.chunk_tokens
                           * cfg.top_k)
        self._e_slab = max(1, int(sc.expert_slab or worst_routed))
        lm_bytes = self.store.entry_nbytes("lm_head")
        slab_bytes = self._e_slab * max_expert
        if sc.device_budget_bytes is None or sc.pin_all:
            cache_cap = None
        else:
            cache_cap = sc.device_budget_bytes - lm_bytes - slab_bytes
            if cache_cap < max_expert:
                raise ValueError(
                    f"device_budget_bytes={sc.device_budget_bytes} cannot "
                    f"hold the pinned lm_head ({lm_bytes}B) + the "
                    f"{self._e_slab}-row expert slab ({slab_bytes}B) + at "
                    f"least one cacheable expert ({max_expert}B); raise the "
                    "budget or shrink StreamConfig.expert_slab")
        # device weight page pool, sized like the dense path: payload
        # budget converted at the worst payload->page ratio, plus in-flight
        # slack for the slab's misroute fetches and prefetcher traffic,
        # capped at the whole expert tier.
        # (sharded: LOCAL pages per expert against the AGGREGATE expert-
        # cache budget — like the dense plane, the conversion ratio is
        # local-pages / global-bytes per whole expert, so replicated
        # fallback entries are covered and the pool never grows mid-run)
        expert_pages = [
            [sum(self._entry_pages_local(ref.entry(li, e))
                 for ref in self._expert_refs.values())
             for e in range(cfg.n_experts)]
            for li in range(cfg.n_layers)]
        tier_pages = sum(sum(r) for r in expert_pages)
        max_ep = max(max(r) for r in expert_pages)
        pb = self.store.page_bytes
        worst = max(expert_pages[li][e] * pb
                    / max(self._expert_nbytes[li][e], 1)
                    for li in range(cfg.n_layers)
                    for e in range(cfg.n_experts))
        # trace-static reservation: slab misroute fetches + prefetcher
        # in-flight traffic, in (local) pool pages (see the dense twin)
        self._pool_reserve_pages = 2 * self._e_slab * max_ep
        if cache_cap is not None and sc.n_shards > 1:
            # per-device bound, as in the dense plane: each shard's cache-
            # backing pages must fit its ~budget/n_shards share
            cache_cap = min(cache_cap, int(sc.device_budget_bytes
                                           / (sc.n_shards * worst)))
            if cache_cap < max_expert:
                raise ValueError(
                    f"device_budget_bytes={sc.device_budget_bytes} over "
                    f"{sc.n_shards} shards leaves a per-device share too "
                    f"small for one cacheable expert ({max_expert}B); "
                    "raise the budget or shrink StreamConfig.expert_slab")
        if cache_cap is None:
            n_pages = tier_pages
        else:
            n_pages = min(tier_pages,
                          -(-int(worst * cache_cap) // pb)
                          + self._pool_reserve_pages)
        self.wpool = self._make_wpool(n_pages)
        self._expert_kn = {
            name: self._entry_kn(ref.entry(0, 0))
            for name, ref in self._expert_refs.items()}
        self.expert_cache = ExpertCache(cache_cap, cfg.n_layers,
                                        cfg.n_experts, n_slots=max_slots,
                                        on_evict=self._evict_window)
        self.cache = self.expert_cache
        self.streamer = None             # dense group streamer unused here
        self._lm_head = self._put_replicated(self.store.get("lm_head"))
        if sc.pin_all:                   # fully-resident parity baseline
            for li in range(cfg.n_layers):
                for e in range(cfg.n_experts):
                    val, nb = self._fetch_expert(li, e)
                    if not self.expert_cache.insert((li, e), val, nb,
                                                    pin=True):
                        self._discard_window(val)
        elif sc.pin_shared_experts > 0:
            # shared experts (satellite of grouped routing): the first
            # pin_shared_experts experts of every layer are always-routed
            # DeepSeek-style shared experts — pin them so they never pay a
            # page upload or a misroute stall.
            for li in range(cfg.n_layers):
                for e in range(min(sc.pin_shared_experts, cfg.n_experts)):
                    val, nb = self._fetch_expert(li, e)
                    if not self.expert_cache.insert((li, e), val, nb,
                                                    pin=True):
                        self._discard_window(val)
        self.prefetcher = ExpertPrefetcher(self.expert_cache,
                                           self._fetch_expert,
                                           discard=self._discard_window,
                                           batch_fetch=self._fetch_expert_batch)
        # misroute-stall-aware budget retune (auto_expert_budget) state
        self._auto_expert_done = False
        self._max_routed_seen = 0
        # init-time reads (lm_head, pins) are deployment, not serving
        self.store.reset_counters()
        self.expert_cache.reset_counters()
        self.wpool.reset_counters()

    def _fetch_expert(self, li: int, e: int):
        """Upload ONE (layer, expert) weight set's pages (w_gate/w_up/
        w_down) into the device page pool — one staged transfer — and
        return its page tables. Runs on the compute path (misroute stall)
        or on the prefetch worker thread; batched misroutes go through
        ``_fetch_experts`` instead (one transfer for the whole missing
        set)."""
        return (self._fetch_experts(li, [e])[e],
                self._expert_nbytes[li][e])

    def _fetch_experts(self, li: int, es):
        """Upload SEVERAL of one layer's experts in ONE staged transfer;
        returns {expert: table-dict} with per-expert ``slots``."""
        sets = self._fetch_expert_sets([(li, e) for e in es])
        return {e: v for (_, e), v in sets.items()}

    def _fetch_expert_sets(self, keys):
        """Upload SEVERAL (layer, expert) weight sets — any mix of layers
        — in ONE staged transfer; returns {(layer, expert): table-dict}."""
        tbls = self.wpool.upload(
            [ref.entry(li, e) for li, e in keys
             for ref in self._expert_refs.values()])
        out = {}
        for li, e in keys:
            val = {name: tbls[ref.entry(li, e)]
                   for name, ref in self._expert_refs.items()}
            val["slots"] = np.concatenate(
                [val[name]["slots"] for name in self._expert_refs])
            # generation stamp: the slab memo keys on it, so a re-fetch
            # (new pool slots) can never alias a stale memoized slab.
            # next() on itertools.count is atomic — this runs on both the
            # compute path and the prefetch worker.
            val["gen"] = next(self._fetch_gen)
            out[(li, e)] = val
        return out

    def _fetch_expert_batch(self, keys):
        """Prefetch-worker batch hook: the whole drained queue in one
        staged transfer. Returns {key: (value, nbytes)}."""
        sets = self._fetch_expert_sets(keys)
        return {k: (v, self._expert_nbytes[k[0]][k[1]])
                for k, v in sets.items()}

    def _acquire_experts(self, li: int, routed):
        """Gather one layer's ROUTED experts into the slab's page tables.

        Cache hits are acquired ref-held; misses are MISROUTE STALLS —
        the whole missing set is uploaded in ONE staged transfer, then
        hold-inserted (an insert the budget rejects leaves a TRANSIENT
        whose pages are freed after dispatch). Returns (slab page-table
        bank with (e_slab,)-leading tables, slab_map (n_experts,) i32 with
        -1 = not resident, held keys to release after dispatch, transient
        slot arrays to free after dispatch, missing expert-id set)."""
        routed = [int(e) for e in routed] or [0]
        if len(routed) > self._e_slab:
            raise ValueError(
                f"layer {li} routed {len(routed)} distinct experts > "
                f"expert_slab={self._e_slab}; raise StreamConfig.expert_slab")
        cache = self.expert_cache
        held, transients, vals = [], [], {}
        missing = []
        for e in routed:
            key = (li, e)
            val = cache.acquire(key)
            if val is None and self.prefetcher.in_flight(key):
                # the worker is already reading this expert's pages: wait
                # for it (bounded) instead of double-reading — double
                # fetches would also double-count the headline telemetry.
                t0 = time.perf_counter()
                deadline = t0 + 1.0
                while (self.prefetcher.in_flight(key)
                       and time.perf_counter() < deadline):
                    time.sleep(0.0005)
                val = cache.acquire(key)
                cache.note_stall(time.perf_counter() - t0)
            if val is None:
                missing.append(e)
            else:
                held.append(key)
                vals[e] = val
        if missing:
            t0 = time.perf_counter()
            fetched = self._fetch_experts(li, missing)
            dt = time.perf_counter() - t0
            for e in missing:
                val, nb = fetched[e], self._expert_nbytes[li][e]
                cache.note_fetch(nb)
                cache.note_stall(dt / len(missing))
                prior = (cache.acquire((li, e))
                         if (li, e) in cache else None)
                if prior is not None:
                    # the prefetch worker landed this expert between our
                    # miss and the batched fetch: use its copy, ours is a
                    # transient (freed after dispatch).
                    held.append((li, e))
                    transients.append(val["slots"])
                    vals[e] = prior
                elif cache.insert((li, e), val, nb, hold=True):
                    held.append((li, e))
                    vals[e] = val
                else:
                    transients.append(val["slots"])
                    vals[e] = val
        rows = [vals[e] for e in routed]
        # slab memo: in steady decode a layer routes the SAME expert set
        # step after step, and the page tables only move when an expert is
        # re-fetched into new pool slots (a new generation stamp). Keying
        # on (routed order, generations) lets those steps reuse the
        # device-resident slab outright — no re-stack, no device_put.
        memo_key = (tuple(routed), tuple(r["gen"] for r in rows))
        memo = self._slab_memo.get(li)
        if memo is not None and memo[0] == memo_key:
            slab, dev_map = memo[1], memo[2]
        else:
            slab_map = np.full((self.cfg.n_experts,), -1, np.int32)
            for r, e in enumerate(routed):
                slab_map[e] = r
            rows += [rows[0]] * (self._e_slab - len(rows))    # static rows
            # the slab is only PAGE TABLES (a few KB of i32): the weights
            # themselves stay in the pool and the expert trace gathers
            # them in place — the per-layer jnp.stack slab re-assembly is
            # gone.
            slab = {name: {k: jnp.asarray(np.stack(
                        [r[name][k] for r in rows]))
                           for k in ("q_tbl", "p_slots", "s_slots")}
                    for name in self._expert_refs}
            dev_map = jnp.asarray(slab_map)
            self._slab_memo[li] = (memo_key, slab, dev_map)
        return slab, dev_map, held, transients, set(missing)

    def _build_stream_fns(self, exec_mode):
        """The streamed data plane: three jitted pieces (embed -> layer
        groups x N -> finish) instead of one monolithic step. The group fn
        takes its layer offset as a TRACED scalar, so all groups share one
        trace; steady state is exactly 3 traces total — speculative mode
        included (drafting folds into the embed trace, verification into
        the finish trace).

        Sharded (``StreamConfig.n_shards > 1``, DESIGN.md §11): the group
        fn runs under ``shard_map`` — the pool buffer splits its page rows
        over "model", everything else stays replicated, and the FFN's one
        psum per layer is the step's only collective. Every jit pins its
        outputs replicated so the carried serving state stays mesh-legal."""
        cfg = self.cfg
        spec_k = self.spec_cfg.k if self.spec_cfg else None
        proposer = self.proposer
        group = functools.partial(_stream_group_impl, cfg, exec_mode,
                                  self.kv_aware, self.stream_cfg.group_size,
                                  self._win_shapes)
        finish = functools.partial(_finish_step, cfg, self.sched_cfg,
                                   self.sample_cfg, self.kv_aware, spec_k)

        if spec_k is None:
            def embed_fn(params, lengths, tokens, q_lens):
                self._trace_count += 1    # runs only while jax traces
                return _embed_chunk(cfg, params, lengths, tokens, q_lens)
        else:
            def embed_fn(params, lengths, tokens, q_lens, hist, hist_lens,
                         draft_cap):
                self._trace_count += 1
                return _embed_spec(cfg, proposer, spec_k, params, lengths,
                                   tokens, q_lens, hist, hist_lens,
                                   draft_cap)

        jit_kw = {}
        if self.mesh is not None:
            from repro.launch.mesh import MODEL_AXIS
            from repro.launch.sharding import stream_window_specs
            specs = stream_window_specs(self.mesh)
            rspec, pspec = specs["replicated"], specs["pool"]
            # group args: (layers_dram, window, pool_buf, k, v, x,
            # positions, ctx_lens, block_tables, bitmap, lo) — the pool
            # buffer (index 2) is the only sharded operand.
            group = jax.shard_map(
                functools.partial(group, axis_name=MODEL_AXIS),
                mesh=self.mesh,
                in_specs=(rspec, rspec, pspec) + (rspec,) * 8,
                out_specs=rspec, check_vma=False)
            jit_kw = {"out_shardings": NamedSharding(self.mesh, P())}

        def group_fn(*args):
            self._trace_count += 1
            return group(*args)

        def finish_fn(*args):
            self._trace_count += 1
            return finish(*args)

        donate = (2,) if jax.default_backend() != "cpu" else ()
        self._embed_fn = _jit(embed_fn, **jit_kw)
        self._group_fn = _jit(group_fn, **jit_kw)
        self._finish_fn = _jit(finish_fn, donate_argnums=donate,
                                  **jit_kw)
        self._step_fn = self._streamed_step

    def _streamed_step(self, params, attn_flash, state, tokens, q_lens,
                       admitted, block_tables, key, hist=None,
                       hist_lens=None, draft_cap=None, is_decode=None):
        """Streamed data plane: the flash tier never sits device-resident
        as a whole — the streamer fills group l+1's window while group l's
        asynchronously-dispatched compute runs. In speculative mode the
        layer pass is shared by ALL of a slot's verify lanes: one window
        rotation per step amortizes over every accepted token."""
        del params, attn_flash                       # store-resident tier
        with self._phase("embed"):
            if self.spec_cfg is None:
                drafts = n_draft = None
                x, positions, ctx_lens = self._embed_fn(
                    self._dram_params, state["lengths"], tokens, q_lens)
            else:
                x, positions, ctx_lens, q_lens, drafts, n_draft = \
                    self._embed_fn(self._dram_params, state["lengths"],
                                   tokens, q_lens, hist, hist_lens,
                                   draft_cap)
        ks, vs = [], []
        # manual iteration so the window-queue wait (the stream-wait
        # stall) times separately from the group's compute dispatch
        it = self.streamer.stream()
        while True:
            with self._phase("stream_wait"):
                item = next(it, None)
            if item is None:
                break
            g, window = item
            with self._phase("group_dispatch"):
                lo = jnp.int32(g * self.stream_cfg.group_size)
                # dispatch under the pool lock: the window's liveness ref
                # guarantees its slots are mapped, and the lock keeps the
                # worker's donating (in-place) uploads from deleting the
                # buffer handle mid-dispatch.
                win = {"ffn": window["ffn"], "attn": window["attn"]}
                x, k_g, v_g = self.wpool.dispatch(lambda buf: self._group_fn(
                    self._layers_dram, win, buf, state["k"],
                    state["v"], x, positions, ctx_lens, block_tables,
                    state["bitmap"], lo))
            ks.append(k_g)
            vs.append(v_g)
        with self._phase("finish"):
            k_new = jnp.concatenate(ks, axis=0)      # (L, slots, T, KV, Dh)
            v_new = jnp.concatenate(vs, axis=0)
            args = (self._dram_params["final_norm"], self._lm_head, state,
                    x, k_new, v_new, q_lens, admitted, positions,
                    block_tables, key)
            if self.spec_cfg is not None:
                args += (drafts, n_draft, is_decode)
            return self._finish_fn(*args)

    def _build_stream_fns_moe(self, exec_mode):
        """The expert-paged MoE data plane: THREE jitted pieces (HEAD
        [embed + attention+router(0)] → FUSED[expert(l-1) + attention+
        router(l)] × (L-1) → TAIL[expert(L-1) + finish]). The router must
        run before its layer's expert weights can be NAMED, so the trace
        splits around the host expert-bitmap handoff — but every pair of
        device halves that STRADDLE a boundary fuses into one jitted
        call: interior handoffs ride the fused trace, and the embed/
        finish boundaries fold into the adjacent traces (head and tail),
        so a step is L+1 dispatches (vs the split plane's 2L + 2) over
        exactly 3 steady-state traces (asserted in
        tests/test_moe_serving.py). The fused trace takes the layer
        index as a traced scalar.

        Sharded (``StreamConfig.n_shards > 1``, DESIGN.md §11): the two
        pool-consuming traces (fused, tail) run under ``shard_map`` with
        the pool's page rows split over "model"; each expert's
        down-projection psum is the only collective. The head consumes
        no pool pages and jits plain."""
        cfg = self.cfg
        spec_k = self.spec_cfg.k if self.spec_cfg else None
        n_extra = 0 if spec_k is None else 3        # drafts/n_draft/is_decode
        head = functools.partial(_moe_head_impl, cfg, self.proposer,
                                 spec_k, exec_mode)
        fused = functools.partial(_moe_fused_impl, cfg, exec_mode,
                                  self._expert_kn)
        tail = functools.partial(_moe_tail_impl, cfg, self.sched_cfg,
                                 self.sample_cfg, self.kv_aware, spec_k,
                                 self._expert_kn)

        jit_kw = {}
        if self.mesh is not None:
            from repro.launch.mesh import MODEL_AXIS
            from repro.launch.sharding import stream_window_specs
            specs = stream_window_specs(self.mesh)
            rspec, pspec = specs["replicated"], specs["pool"]
            # fused args: (layers_dram, k, v, x, h, gates, idx, slab,
            # slab_map, pool_buf, positions, ctx_lens, block_tables, lo);
            # tail args: (final_norm, lm_head, state, x, h, gates, idx,
            # slab, slab_map, pool_buf, k_new, v_new, q_lens, admitted,
            # positions, block_tables, key[, drafts, n_draft, is_decode])
            # — the pool buffer is the only sharded operand of either.
            fused = jax.shard_map(
                functools.partial(fused, axis_name=MODEL_AXIS),
                mesh=self.mesh,
                in_specs=(rspec,) * 9 + (pspec,) + (rspec,) * 4,
                out_specs=rspec, check_vma=False)
            tail = jax.shard_map(
                functools.partial(tail, axis_name=MODEL_AXIS),
                mesh=self.mesh,
                in_specs=(rspec,) * 9 + (pspec,)
                + (rspec,) * (7 + n_extra),
                out_specs=rspec, check_vma=False)
            jit_kw = {"out_shardings": NamedSharding(self.mesh, P())}

        def head_fn(*args):
            self._trace_count += 1        # runs only while jax traces
            return head(*args)

        def fused_fn(*args):
            self._trace_count += 1
            return fused(*args)

        def tail_fn(*args):
            self._trace_count += 1
            return tail(*args)

        donate = (2,) if jax.default_backend() != "cpu" else ()
        self._head_fn = _jit(head_fn, **jit_kw)
        self._fused_fn = _jit(fused_fn, **jit_kw)
        self._tail_fn = _jit(tail_fn, donate_argnums=donate, **jit_kw)
        self._step_fn = self._streamed_step_moe

    def _streamed_step_moe(self, params, attn_flash, state, tokens, q_lens,
                           admitted, block_tables, key, hist=None,
                           hist_lens=None, draft_cap=None, is_decode=None):
        """Expert-paged MoE data plane (DESIGN.md §9): per layer, the
        attention+router half runs on device, the top-k expert-id bitmap
        syncs to the host (the step's only mid-step sync — a few hundred
        bytes, the MoE analog of Algorithm 2's plane-bitmap handoff), the
        routed experts are gathered from the ExpertCache (miss = misroute
        stall), and the expert half consumes the assembled device slab.
        The expert half of layer *l* dispatches FUSED with the attention+
        router half of layer *l+1* (one jitted call per handoff instead of
        two); layer 0's attention+router rides the HEAD trace with the
        embed, the last layer's experts ride the TAIL trace with the
        finish — L+1 dispatches over exactly three compiled traces. While
        layer *l* computes, the prefetch worker fetches the router-history
        predictor's picks for layer *l+1* (wrapping to layer 0 for the
        next step)."""
        del params, attn_flash                       # store-resident tier
        cfg, cache = self.cfg, self.expert_cache
        head_args = (self._layers_dram, state["k"], state["v"],
                     self._dram_params, state["lengths"], tokens, q_lens,
                     block_tables)
        with self._phase("head_dispatch"):
            if self.spec_cfg is None:
                drafts = n_draft = None
                x, h, gates, idx, k_l, v_l, positions, ctx_lens = \
                    self._head_fn(*head_args)
                lane_bound = self._host_q_lens
            else:
                (x, h, gates, idx, k_l, v_l, positions, ctx_lens, q_lens,
                 drafts, n_draft) = self._head_fn(*head_args, hist,
                                                  hist_lens, draft_cap)
                # verify lanes grow q_lens IN-GRAPH (by n_draft <=
                # draft_cap); the host-side routed-expert filter uses the
                # superset bound so a draft lane's routing is never
                # dropped from the slab.
                lane_bound = self._host_q_lens + self._host_draft_cap
            # whole-step prefetch lead: the per-layer request below gives
            # the worker only one layer's compute (~ms) to land its
            # fetches — on fast layers the compute path wins the race and
            # every miss is a synchronous stall. The per-slot router
            # histories already know each layer's likely experts, so queue
            # EVERY layer's predictions up front (one batched transfer in
            # the worker) and let the layer loop's requests merely top up
            # with the freshest signal.
            active = [s for s in range(len(lane_bound)) if lane_bound[s] > 0]
            if self._steps_done > 0:
                for li in range(cfg.n_layers):
                    self._request_prefetch(li, self._e_slab, slots=active)
        # layer 0's attention+router already ran inside the head trace
        # (no pool operand — embed/attn weights are DRAM-resident).
        ks, vs = [k_l], [v_l]
        out = None
        for li in range(cfg.n_layers):
            with self._phase("route_sync"):
                idx_host = np.asarray(idx)           # layer li's routing
            by_slot = sched.routed_experts_by_slot(idx_host, lane_bound)
            routed = sched.routed_experts(idx_host, lane_bound)
            cache.observe(li, routed)
            for s, ids in by_slot.items():
                cache.observe_slot(s, li, ids)
            self._max_routed_seen = max(self._max_routed_seen, len(routed))
            self._request_prefetch((li + 1) % cfg.n_layers, len(routed),
                                   slots=by_slot.keys())
            with self._phase("expert_acquire"):
                slab, slab_map, held, transients, missing = \
                    self._acquire_experts(li, routed)
            for s, ids in by_slot.items():
                cache.note_slot_route(s, len(ids),
                                      sum(1 for e in ids
                                          if int(e) in missing))
            # dispatch under the pool lock: the prefetch worker's donating
            # (in-place) uploads delete the buffer handle they consume, so
            # snapshot-and-dispatch must be atomic against them.
            if li + 1 < cfg.n_layers:
                # layer li's experts fused with layer li+1's attn+router
                with self._phase("fused_dispatch"):
                    x, h, gates, idx, k_l, v_l = self.wpool.dispatch(
                        lambda buf: self._fused_fn(
                            self._layers_dram, state["k"], state["v"], x,
                            h, gates, idx, slab, slab_map, buf, positions,
                            ctx_lens, block_tables, jnp.int32(li + 1)))
                ks.append(k_l)
                vs.append(v_l)
            else:        # last layer: experts fused with the finish step
                k_new = jnp.stack(ks, axis=0)    # (L, slots, T, KV, Dh)
                v_new = jnp.stack(vs, axis=0)
                pre = (self._dram_params["final_norm"], self._lm_head,
                       state, x, h, gates, idx, slab, slab_map)
                post = (k_new, v_new, q_lens, admitted, positions,
                        block_tables, key)
                if self.spec_cfg is not None:
                    post += (drafts, n_draft, is_decode)
                with self._phase("tail_dispatch"):
                    out = self.wpool.dispatch(
                        lambda buf: self._tail_fn(*pre, buf, *post))
            # dispatch has captured the pool buffer: NOW the held
            # entries can release and the rejected transients can free.
            for hk in held:
                cache.release(hk)
            for slots in transients:
                self.wpool.free(slots)
        return out

    def _request_prefetch(self, layer: int, breadth: int, slots=None):
        """Enqueue predicted experts for ``layer`` — gated by the cache's
        score-aware admission (``would_admit``), so speculative fetches
        never read pages the cache would immediately reject: a prediction
        lands in free space or by displacing strictly COLDER experts,
        never by thrashing the resident hot set. ``slots`` — the decode
        slots active this step — switches the predictor to the per-slot
        histories (max-combined), so a slot whose routing phase diverges
        from the batch mean still gets its experts prefetched."""
        cache = self.expert_cache
        want = breadth + self.stream_cfg.prefetch_experts_margin
        picks = [(layer, e) for e in cache.predict(layer, want, slots=slots)
                 if cache.would_admit((layer, e),
                                      self._expert_nbytes[layer][e])]
        if picks:
            self.prefetcher.request(picks)

    def expert_stats(self, *, strict: bool = True) -> dict:
        """ExpertCache telemetry for the expert-paged MoE engine: hit rate
        over routed-expert acquires, fetched bytes (prefetch included) and
        bytes/token vs the DENSE-EQUIVALENT all-experts-streamed cost
        (what rotating every expert of every layer through the window —
        the PR-3 discipline — would have fetched), and misroute stalls
        (routed experts not resident when their layer needed them).
        ``strict=False`` returns ``{}`` instead of raising when the engine
        is not serving a store-backed MoE model (the one ``*_stats``
        wrong-mode convention; see ``telemetry``)."""
        if not self.streamed_moe:
            if not strict:
                return {}
            raise ValueError("expert_stats: engine is not serving a "
                             "store-backed MoE model")
        c = self.expert_cache.stats()
        toks = sum(s["prefill_tokens"] + s["decode_tokens"]
                   for s in self.stats)
        bank_total = sum(sum(r) for r in self._expert_nbytes)
        return {
            "expert_hits": c["hits"], "expert_misses": c["misses"],
            "expert_hit_rate": c["hits"] / max(c["hits"] + c["misses"], 1),
            "expert_bytes_fetched": c["bytes_fetched"],
            "expert_fetches": c["fetches"],
            "expert_prefetches": c["prefetches"],
            "expert_prefetched_bytes": c["prefetched_bytes"],
            "misroute_stalls": c["misroute_stalls"],
            "misroute_stall_s": c["misroute_stall_s"],
            "expert_cache_entries": c["entries"],
            "expert_cache_bytes": c["bytes_used"],
            "expert_slab": self._e_slab,
            "steps": self._steps_done, "tokens": toks,
            "expert_bytes_per_token": c["bytes_fetched"] / max(toks, 1),
            "all_experts_bytes_per_token":
                self._steps_done * bank_total / max(toks, 1),
            "slot_hit_rates": c.get("slot_hit_rates", []),
            "max_routed_seen": self._max_routed_seen,
            "expert_budget_retuned": self._auto_expert_done,
            "pool_reserve_bytes":
                self._pool_reserve_pages * self.store.page_bytes,
            **self.prefetcher.stats(),
            **self.wpool.stats(),
        }

    def _maybe_retune_expert_budget(self):
        """Misroute-stall-aware expert budget re-split (``StreamConfig.
        auto_expert_budget``) — the expert-paged analog of ``auto_depth``:
        once, after the first measured steps, if routed experts actually
        stalled, return the slab reservation's UNUSED rows (worst-case
        e_slab sizing vs the observed max routed set) to the expert
        cache's capacity. The device budget invariant is preserved — the
        slab's trace shape is fixed at init, so the dead reservation is
        pure headroom the cache can spend on residency."""
        sc = self.stream_cfg
        if (not self.streamed_moe or not sc.auto_expert_budget
                or self._auto_expert_done
                or self._steps_done < sc.auto_depth_after):
            return
        self._auto_expert_done = True
        cache = self.expert_cache
        if (cache.misroute_stalls == 0 or cache.capacity is None
                or self._max_routed_seen >= self._e_slab):
            return
        unused = self._e_slab - max(self._max_routed_seen, 1)
        cache.resize(cache.capacity + unused * self._max_expert_bytes)

    @contextlib.contextmanager
    def _phase(self, name: str):
        """One step phase (ObsPlane), timed around its body, on every
        plane: a ``jax.profiler.TraceAnnotation`` puts it in the profiler's
        trace on the device trace's clock (a no-op outside a profiler
        session), its seconds add to this step's breakdown (the
        ``engine_step_seconds`` histogram, the timeline), and an armed
        Tracer records it on the compute track."""
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            yield
            dt = time.perf_counter() - t0
        self._phases[name] = self._phases.get(name, 0.0) + dt
        tracer = obs.default_tracer()
        if tracer.enabled:
            tracer.complete(name, t0, dt, tid=obs.TID_COMPUTE, cat="step")

    def _stream_stall_s(self) -> float:
        """Seconds the compute path has spent blocked on the weight stream:
        the window-queue stall (dense groups) or the cumulative misroute
        stall (MoE expert paging) — the residency signal the admission
        budget contracts with."""
        if not self.streamed:
            return 0.0
        if self.streamed_moe:
            return self.expert_cache.misroute_stall_s
        return self.streamer.stall_s

    def _maybe_autotune_depth(self):
        """Overlap-depth auto-tuning (``StreamConfig.auto_depth``): once,
        after the first measured steps, re-pick ``prefetch_depth`` from the
        observed stall/stream ratio — a consumer that still stalls wants
        more windows in flight; one that never does returns the budget to
        the residency cache. The device budget invariant is preserved by
        re-splitting it: window bytes grow/shrink, cache capacity moves the
        other way (never below the pinned floor)."""
        sc = self.stream_cfg
        if (self.streamer is None or not sc.auto_depth
                or self._auto_depth_done
                or self._steps_done < sc.auto_depth_after):
            return
        self._auto_depth_done = True
        st = self.streamer
        if st.stream_s <= 0:
            return                       # nothing streamed: no signal
        ratio = st.stall_s / st.stream_s
        depth = st.prefetch_depth
        want = depth
        if ratio > 0.10:
            want = depth + max(1, round(depth * min(ratio, 1.0)))
        elif ratio < 0.02 and depth > 1:
            want = depth - 1
        if sc.device_budget_bytes is not None:
            afford = int(sc.device_budget_bytes - self.cache.pinned_bytes) \
                // max(self._group_bytes, 1)
            want = min(want, max(afford, 1))
        want = max(1, int(want))
        if want == depth:
            return
        st.prefetch_depth = want
        if sc.device_budget_bytes is not None and not sc.pin_all:
            # eager trim: a deeper window must RECLAIM its bytes from the
            # cache now, not at some future insert — resident + in-flight
            # window bytes must never exceed the device budget.
            self.cache.resize(max(
                self.cache.pinned_bytes,
                sc.device_budget_bytes - want * self._group_bytes))

    def stream_stats(self, *, strict: bool = True) -> dict:
        """Streamer + residency-cache + page-store counters (streamed mode):
        stall/stream seconds, streamed bytes, cache hit/miss, per-plane page
        reads and the analytical NAND seconds they imply, the (possibly
        auto-tuned) prefetch depth, and — in speculative mode — the
        acceptance-rate / tokens-per-verify-step telemetry. Page counters
        cover SERVING only (init-time programming/pin reads are reset).
        ``strict=False`` returns ``{}`` on a non-streamed engine."""
        if not self.streamed:
            if not strict:
                return {}
            raise ValueError("stream_stats: engine is not in streamed mode")
        if self.streamed_moe:
            out = {**self.expert_stats(), **self.store.stats()}
        else:
            out = {**self.streamer.stats(), **self.store.stats(),
                   **self.wpool.stats(),
                   "pool_reserve_bytes":
                       self._pool_reserve_pages * self.store.page_bytes,
                   "prefetch_depth": self.streamer.prefetch_depth}
        if self.spec_cfg is not None:
            out.update(self.spec_stats())
        return out

    def spec_stats(self, *, strict: bool = True) -> dict:
        """Speculative-decode telemetry: how much one weight pass amortizes.

        ``spec_tokens_per_step`` is emitted tokens per VERIFY step (steps
        with >= 1 decoding slot) — in streamed mode, tokens bought per
        window rotation; ``spec_acceptance_rate`` is accepted / drafted.
        ``strict=False`` returns ``{}`` on a non-speculative engine."""
        if self.spec_cfg is None:
            if not strict:
                return {}
            raise ValueError("spec_stats: engine is not in speculative mode")
        t = self._spec_totals
        out = {"spec_verify_steps": t["verify_steps"],
               "spec_drafted": t["drafted"],
               "spec_accepted": t["accepted"],
               "spec_emitted": t["emitted"],
               "spec_acceptance_rate": t["accepted"] / max(t["drafted"], 1),
               "spec_tokens_per_step": t["emitted"]
               / max(t["verify_steps"], 1)}
        if self.spec_cfg.adaptive_k:
            k = self.spec_cfg.k
            out["spec_accept_ema"] = [float(v) for v in self._accept_ema]
            out["spec_adaptive_k"] = [max(1, int(round(float(v) * k)))
                                      for v in self._accept_ema]
        return out

    # --- request management (control plane) -----------------------------------

    def submit(self, prompt: list[int], max_new: int = 16,
               timeout: float | None = None) -> int:
        """Enqueue a request and return its id. Admission (slot +
        worst-case block reservation) happens when capacity frees up —
        oversubscription waits, it never errors. Thread-safe; with
        ``max_waiting`` set, a full waiting queue BLOCKS the caller
        (backpressure) until space frees, ``timeout`` seconds expire
        (TimeoutError) or the engine closes (RuntimeError) — a dying
        server never hangs a producer on a full queue."""
        if not prompt:
            raise ValueError("empty prompt (a request needs >= 1 token)")
        if max_new < 1:
            raise ValueError("max_new must be >= 1 (every request samples "
                             "at least the token after its prompt)")
        with self._cv:
            if self._closed:
                raise RuntimeError("submit: engine is closed")
            if self.max_waiting is not None:
                deadline = None if timeout is None \
                    else time.monotonic() + timeout
                while len(self.waiting) >= self.max_waiting \
                        and not self._closed:
                    remaining = None
                    if deadline is not None:
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            raise TimeoutError(
                                "submit: waiting queue full "
                                f"(max_waiting={self.max_waiting})")
                    self._cv.wait(remaining)
                if self._closed:
                    raise RuntimeError("submit: engine is closed")
            # a request that can never fit the per-slot table or the whole
            # pool is rejected up front.
            rid = self._next_rid
            self._next_rid += 1
            req = Request(rid, list(prompt), max_new)
            # bound by the EXACT max_seq (rounding up to block granularity
            # would admit valid lanes past the learned-position table), by
            # the physical pool minus the dump block, and — for learned-
            # position models — by the table itself (a valid lane's
            # out-of-bounds jnp.take would fill NaN under jit). Computed
            # once in __init__; the speculative verify-lane cap shares it.
            cap = self._kv_cap
            if req.kv_rows > cap:
                self._next_rid = rid
                raise ValueError(
                    f"request needs {req.kv_rows} KV rows > max_seq={cap}")
            self.requests[rid] = req
            req.t_queued = time.perf_counter()
            self.waiting.append(req)
            self._admit()
            return rid

    def cancel(self, rid: int) -> bool:
        """Cancel a waiting OR running request (client disconnect). LOCK-
        FREE — flips flags only, so a disconnect handler never blocks
        behind a running compiled step. The resources come back through
        the normal control-plane paths: a waiting request is dropped at
        the queue head by ``_admit``/the step sweep, a running slot
        releases (all its KV blocks to the free list) within ONE ``step``
        call. Returns False if the request is unknown or already done."""
        req = self.requests.get(rid)
        if req is None or req.done:
            return False
        req.cancelled = True
        req.done = True
        return True

    def forget(self, rid: int) -> bool:
        """Drop a finished request's bookkeeping (ServeFront calls this
        once a handle's stream has drained, so ``requests`` doesn't grow
        without bound). Refuses — returns False — while the request is
        live or its slot has not been swept yet."""
        with self._mu:
            req = self.requests.get(rid)
            if req is None or not req.done:
                return False
            if req.slot is not None \
                    and self.pool.active.get(req.slot) == rid:
                return False             # cancelled mid-step; not yet swept
            if req in self.waiting:
                self.waiting.remove(req)
            del self.requests[rid]
            return True

    def _admit(self):
        """waiting -> running, FCFS: claim a slot and reserve the request's
        worst-case block count so lazily-growing slots never deadlock on an
        exhausted pool mid-flight. With prefix caching on, admission first
        adopts the longest cached prefix copy-free (ref bump on shared
        blocks; only the tail is reserved/prefilled), evicting cold fully-
        released chains when the tail reservation is short."""
        while self.waiting:
            req = self.waiting[0]
            if req.done:                 # cancelled while waiting
                self.waiting.popleft()
                self._cv.notify_all()
                continue
            shared, hashes = (), None
            if self.prefix is not None:
                bs = self.pool.block_size
                # cap: >= 1 prompt token always prefills — every request
                # must sample from its own last prompt lane.
                hashes = block_hashes(req.prompt, bs,
                                      limit=(len(req.prompt) - 1) // bs)
                shared = self.prefix.lookup(hashes)
            slot = self.pool.alloc(req.rid, req.kv_rows,
                                   shared_blocks=shared)
            if slot is None and self.prefix is not None \
                    and self.pool.free_slots:
                need = self.pool.blocks_for(req.kv_rows) - len(shared)
                short = need - self.pool.n_free_blocks
                if short > 0 and self.prefix.evict(short) > 0:
                    # eviction may have reclaimed part of the hit chain
                    # itself (LRU doesn't pin this lookup) — re-resolve.
                    shared = self.prefix.lookup(hashes)
                    slot = self.pool.alloc(req.rid, req.kv_rows,
                                           shared_blocks=shared)
            if slot is None:
                break
            req.slot = slot
            req.t_admitted = time.perf_counter()
            self._lifecycle(self._h_admit_wait, "queue", req, req.t_queued,
                            req.t_admitted)
            if shared:
                req.cached_len = len(shared) * self.pool.block_size
                req.pos = req.cached_len
                self._prefix_tokens_saved += req.cached_len
            if self.spec_cfg is not None:
                # a recycled slot must not inherit the previous request's
                # acceptance history; start optimistic (full draft depth)
                self._accept_ema[slot] = 1.0
            self.waiting.popleft()
            self._cv.notify_all()

    def _lifecycle(self, hist, name: str, req: Request, t0: float,
                   t1: float):
        """One wait in a request's life (ObsPlane): its seconds into
        ``hist`` and, with tracing armed, a span on the request's track
        (``t0``/``t1`` are ``perf_counter`` readings)."""
        hist.observe(t1 - t0)
        tracer = obs.default_tracer()
        if tracer.enabled:
            tracer.complete(name, t0, t1 - t0,
                            tid=tracer.request_tid(req.rid), cat="request",
                            args={"rid": req.rid})

    def _sweep_cancelled(self):
        """Reclaim cancelled requests' resources (under the lock, at the
        top of every step): running slots release — O(1), every KV block
        back on the free list — and cancelled waiting requests drop out of
        the queue. No prefix retain: a cancelled stream was never fully
        read, so its tail blocks are not certified shareable."""
        for slot, rid in list(self.pool.active.items()):
            req = self.requests[rid]
            if req.done and req.cancelled:
                self.pool.release(slot)
        if any(r.done for r in self.waiting):
            self.waiting = collections.deque(
                r for r in self.waiting if not r.done)
            self._cv.notify_all()

    def _finish_request(self, req: Request, slot: int):
        """Completion path: retain the request's full prompt blocks in the
        prefix index (ref bump BEFORE the slot's release drops its own
        refs), then release the slot."""
        if self.prefix is not None:
            bs = self.pool.block_size
            hashes = block_hashes(req.prompt, bs)
            if hashes:
                blocks = [int(b) for b in
                          self.pool.block_tables[slot, :len(hashes)]]
                self.prefix.insert(hashes, blocks)
        self.pool.release(slot)          # O(1): no device work

    def prefix_stats(self, *, strict: bool = True) -> dict:
        """Prefix-cache telemetry: index entries/hits/misses/evictions
        plus the total prefill tokens admission skipped via cache hits.
        ``strict=False`` returns ``{}`` when prefix caching is disabled."""
        if self.prefix is None:
            if not strict:
                return {}
            raise ValueError("prefix_stats: prefix caching is disabled "
                             "(construct with prefix_cache=True)")
        return {**self.prefix.stats(),
                "prefix_prefill_tokens_saved": self._prefix_tokens_saved}

    def telemetry(self) -> dict:
        """Every applicable ``*_stats`` family merged, wrong-mode families
        silently absent (``strict=False`` everywhere). This is the ONE
        aggregate the serving frontend snapshots — callers that want a
        loud failure on a wrong-mode query keep the per-family accessors.

        CAUTION: streamed-mode families read under the streamer/pool
        locks, so this can wait behind an in-flight upload; ServeFront
        therefore refreshes its cached copy from the loop thread rather
        than calling this per HTTP request."""
        out = {"steps": self._steps_done,
               "free_kv_blocks": int(self.pool.n_free_blocks),
               "active_slots": len(self.pool.active),
               "waiting": len(self.waiting)}
        out.update(self.stream_stats(strict=False))
        out.update(self.spec_stats(strict=False))
        out.update(self.prefix_stats(strict=False))
        return out

    def obs_samples(self):
        """ObsPlane scrape samples for the engine and every subsystem it
        owns (lock-free counter reads — safe to pull from a scrape thread
        while a step holds the streamer/pool locks)."""
        from repro.obs.registry import Sample
        yield Sample("engine_steps_total", "counter",
                     float(self._steps_done))
        yield Sample("engine_free_kv_blocks", "gauge",
                     float(self.pool.n_free_blocks))
        yield Sample("engine_active_slots", "gauge",
                     float(len(self.pool.active)))
        yield Sample("engine_waiting_requests", "gauge",
                     float(len(self.waiting)))
        if self.streamed:
            yield Sample("engine_stall_frac", "gauge",
                         float(self._stall_frac))
            yield from self.store.obs_samples()
            yield from self.wpool.obs_samples()
            if self.streamed_moe:
                yield from self.expert_cache.obs_samples()
                yield from self.prefetcher.obs_samples()
            else:
                yield from self.streamer.obs_samples()
        if self.spec_cfg is not None:
            from repro.serving.spec import spec_obs_samples
            yield from spec_obs_samples(self._spec_totals)
        if self.prefix is not None:
            yield from self.prefix.obs_samples()

    # --- the serving step (one compiled call; mixed prefill/decode) -----------

    def _draft_cap(self, req: Request) -> int:
        """Verify lanes this decoding request can use: bounded by spec k
        (per-slot ADAPTIVE when ``SpecConfig.adaptive_k`` — scaled by the
        slot's recent acceptance-rate EMA, so a slot whose drafts never
        land stops wasting lm_head lanes and KV scatter width while
        keeping ONE probe lane to recover through), by the tokens it still
        owes (a draft past max_new is pure waste — and capping by
        ``remaining - 1`` keeps every speculative KV write inside the
        admission reservation), by the pool/table row cap, and by the
        static chunk width."""
        k_want = self.spec_cfg.k
        if self.spec_cfg.adaptive_k:
            k_want = max(1, int(round(self._accept_ema[req.slot] * k_want)))
        remaining = req.max_new - len(req.out)
        room = self._kv_cap - int(self.pool.lengths[req.slot]) - 1
        return max(0, min(k_want, remaining - 1, room,
                          self.admission_cfg.chunk_tokens - 1))

    def step(self) -> int:
        """One continuous-batching step over all running slots: decoding
        slots advance (one token — or, speculatively, ``n_accept + 1``
        tokens through ONE forward pass), prefilling slots consume a
        prompt chunk under the Alg.2/stall-coupled token budget. Returns
        tokens processed (prompt lanes + emitted decode tokens).
        Thread-safe — one step at a time, producers interleave between
        steps; cancelled requests are swept FIRST, so a disconnect's KV
        blocks are back on the free list within one call."""
        # the profiler's step marker; not a Tracer span, so an idle gap is
        # labelled by the phase inside the step that overlaps it
        with jax.profiler.StepTraceAnnotation("serve_step",
                                              step_num=self._steps_done):
            with self._cv:
                self._sweep_cancelled()
                n = self._step_locked()
                self._cv.notify_all()
                return n

    def _step_locked(self) -> int:
        t_plan0 = time.perf_counter()
        self._phases = {}                # this step's ObsPlane breakdown
        with self._phase("plan"):
            self._admit()
            spec = self.spec_cfg is not None
            decode_slots, prefill_slots = [], []
            # ARRIVAL order (rid), not slot order: recycled slot ids would
            # otherwise let a later prompt monopolize the prefill budget
            # ahead of an earlier one (plan_chunks funds prefill FCFS as
            # given).
            for slot, rid in sorted(self.pool.active.items(),
                                    key=lambda kv: kv[1]):
                req = self.requests[rid]
                if req.done:
                    continue
                if req.prefilling:
                    prefill_slots.append((slot, len(req.prompt) - req.pos))
                elif spec:
                    decode_slots.append((slot, 1 + self._draft_cap(req)))
                else:
                    decode_slots.append(slot)
            budget = sched.step_token_budget(self.admission_cfg,
                                             self._npu_frac,
                                             self._stall_frac)
            # snapshot AFTER list-building: a lock-free cancel() landing
            # since the req.done filter above must not be granted lanes or
            # budget.
            cancelled = {slot for slot, rid in self.pool.active.items()
                         if self.requests[rid].done}
            plan = sched.plan_chunks(decode_slots, prefill_slots, budget,
                                     self.admission_cfg.chunk_tokens,
                                     cancelled=cancelled)
            if not plan:
                return 0
            self._h_budget.observe(budget)
            reserved = used = 0
            for slot, rid in self.pool.active.items():
                reserved += self.requests[rid].kv_rows
                used += int(self.pool.lengths[slot])
            self._c_kv_reserved.inc(reserved)
            self._c_kv_used.inc(used)
            n, t_chunk = self.pool.n_slots, self.admission_cfg.chunk_tokens
            tokens = np.zeros((n, t_chunk), np.int32)
            q_lens = np.zeros((n,), np.int32)
            admitted = np.zeros((n,), bool)
            if spec:
                draft_cap = np.zeros((n,), np.int32)
                is_decode = np.zeros((n,), bool)
            for slot, _ in prefill_slots:
                admitted[slot] = True
            admitted[[s if isinstance(s, int) else s[0]
                      for s in decode_slots]] = True
            for slot, cnt in plan.items():
                req = self.requests[self.pool.active[slot]]
                if req.prefilling:
                    chunk = req.prompt[req.pos:req.pos + cnt]
                    tokens[slot, :len(chunk)] = chunk
                    q_lens[slot] = len(chunk)
                else:
                    tokens[slot, 0] = req.out[-1]
                    q_lens[slot] = 1      # + n_draft lanes added in-graph
                    if spec:
                        is_decode[slot] = True
                        # budget-clamped verify lanes
                        draft_cap[slot] = cnt - 1
                        seq = req.prompt + req.out
                        hl = min(len(seq), self._hist.shape[1])
                        self._hist[slot, :hl] = seq[-hl:]
                        self._hist_lens[slot] = hl
                # map physical blocks for this step's writes — ALL lanes,
                # draft lanes included (host control plane; draws on the
                # admission reservation, so it cannot fail)
                self.pool.ensure(slot, int(self.pool.lengths[slot]) + cnt)
            self._key, sk = jax.random.split(self._key)
            if self.streamed_moe:
                # host-side lane bounds for the routed-expert filter (spec
                # verify lanes are added in-graph; the filter uses the
                # superset bound q_lens + draft_cap)
                self._host_q_lens = q_lens.copy()
                self._host_draft_cap = draft_cap.copy() if spec else None
            state = dict(self.pool.device_state(),
                         bitmap=self.bitmap, prev_cycles=self._prev_cycles)
        t_step0 = time.perf_counter()
        stall0 = self._stream_stall_s()
        with self._phase("h2d"):
            args = (self.params, self.attn_flash, state,
                    jnp.asarray(tokens), jnp.asarray(q_lens),
                    jnp.asarray(admitted), self.pool.block_tables_dev(), sk)
            if spec:
                args += (jnp.asarray(self._hist),
                         jnp.asarray(self._hist_lens),
                         jnp.asarray(draft_cap), jnp.asarray(is_decode))
        # monolithic plane: the whole jitted call is one dispatch (streamed
        # planes time their embed/group/finish pieces themselves)
        with (contextlib.nullcontext() if self.streamed
              else self._phase("dispatch")):
            out = self._step_fn(*args)
        with self._phase("sync"):
            if spec:
                toks, n_emit, state, stats = out
                n_emit_host = np.asarray(n_emit)
            else:
                toks, state, stats = out
            self.pool.set_device_state(state)
            self.bitmap = state["bitmap"]
            self._prev_cycles = state["prev_cycles"]
            # the step's only device->host syncs: sampled tokens + stat
            # scalars
            toks_host = np.asarray(toks)  # (slots,) — or (slots, k+1) spec
            t_toks = time.perf_counter()
            n_processed = n_prefill = 0
            for slot in plan:
                req = self.requests[self.pool.active[slot]]
                cnt = int(q_lens[slot])
                if req.prefilling:
                    n_processed += cnt
                    n_prefill += cnt
                    self.pool.bump(slot, cnt)
                    req.pos += cnt
                    if not req.prefilling:
                        # just-completed prefill sampled one token at its
                        # last lane
                        req.out.append(int(toks_host[slot, 0] if spec
                                           else toks_host[slot]))
                        self._lifecycle(self._h_prefill, "prefill", req,
                                        req.t_admitted, t_toks)
                elif spec:
                    # verify step: n_accept + 1 tokens emitted; the pool
                    # length REWINDS to the accepted rows (host mirror here —
                    # device lengths advanced by the same amount in-graph;
                    # rejected lanes' K/V stays in place, unreachable,
                    # overwritten later)
                    ne = int(n_emit_host[slot])
                    new_len = int(self.pool.lengths[slot]) + ne
                    take = min(ne, req.max_new - len(req.out))
                    req.out.extend(int(t) for t in toks_host[slot, :take])
                    self.pool.rewind(slot, new_len)
                    n_processed += ne
                else:
                    self.pool.bump(slot, cnt)
                    req.out.append(int(toks_host[slot]))
                    n_processed += cnt
                if req.cancelled:
                    # cancel() landed mid-step: reclaim NOW (the "within
                    # one step" guarantee); the unread output is discarded.
                    self.pool.release(slot)
                elif not req.prefilling and len(req.out) >= req.max_new:
                    req.done = True
                    self._finish_request(req, slot)
            st = jax.device_get(stats)
        self._npu_frac = float(st["npu_fraction"])
        if "moe_assignments" in st:
            self._c_moe_assign.inc(int(st["moe_assignments"]))
            self._c_moe_routed.inc(int(st["moe_experts_routed"]))
        entry = {
            "kv_len": int(st["kv_len"]),
            "delta_cycles": int(st["delta_cycles"]),
            "npu_fraction": self._npu_frac,
            "prefill_tokens": n_prefill,
            "decode_tokens": n_processed - n_prefill,
        }
        if spec:
            entry["spec_drafted"] = int(st["spec_drafted"])
            entry["spec_accepted"] = int(st["spec_accepted"])
            if bool(is_decode.any()):
                t = self._spec_totals
                t["verify_steps"] += 1
                t["drafted"] += int(st["spec_drafted"])
                t["accepted"] += int(st["spec_accepted"])
                t["emitted"] += int(st["spec_emitted"])
                if self.spec_cfg.adaptive_k:
                    nd = np.asarray(st["spec_draft_slots"])
                    na = np.asarray(st["spec_accept_slots"])
                    a = self.spec_cfg.ema_alpha
                    for slot in np.nonzero(is_decode & (nd > 0))[0]:
                        rate = float(na[slot]) / float(nd[slot])
                        self._accept_ema[slot] = \
                            (1.0 - a) * self._accept_ema[slot] + a * rate
        stall_s = 0.0
        if self.streamed:
            # stall fraction of step wall time (EMA): the residency signal
            # the admission budget contracts with (scheduler.step_token_
            # budget) — a weight-stream-bound engine sheds prefill share.
            dt = time.perf_counter() - t_step0
            stall_s = max(self._stream_stall_s() - stall0, 0.0)
            frac = stall_s / max(dt, 1e-9)
            self._stall_frac = 0.5 * self._stall_frac \
                + 0.5 * min(max(frac, 0.0), 1.0)
            entry["stall_frac"] = self._stall_frac
        for name, dt_p in self._phases.items():
            self._h_step.observe(dt_p, labels={"phase": name})
        self._h_step.observe(time.perf_counter() - t_plan0,
                             labels={"phase": "total"})
        if n_prefill:
            self._c_step_tokens.inc(n_prefill, labels={"kind": "prefill"})
        if n_processed - n_prefill:
            self._c_step_tokens.inc(n_processed - n_prefill,
                                    labels={"kind": "decode"})
        self.timeline.record(self._steps_done, self._phases,
                             tokens=n_processed, stall_s=stall_s)
        self.stats.append(entry)
        self._steps_done += 1
        if self.streamed:
            self._maybe_autotune_depth()
            self._maybe_retune_expert_budget()
        self._admit()                    # freed slots host waiting requests
        return n_processed

    def close(self):
        """Mark the engine closed — wakes every ``submit`` blocked on
        backpressure (they raise RuntimeError instead of hanging on a
        dying server) — and release background resources: the MoE expert
        prefetcher's worker thread (whose fetch closure pins this engine —
        without an explicit close, neither the thread nor the device-
        resident expert cache is ever reclaimed). Idempotent and
        thread-safe: a second (or concurrent) close is a no-op, and a
        close racing an in-flight step joins it cleanly — taking ``_cv``
        waits for the running ``step()`` to finish (regression-tested in
        tests/test_server.py)."""
        with self._close_lock:
            if self._close_done:
                return
            self._close_done = True
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        p = getattr(self, "prefetcher", None)
        if p is not None:
            p.stop()

    @property
    def step_traces(self) -> int:
        """Times the serving data plane was traced/compiled. A fully static
        monolithic path stays at 1 regardless of slot churn, chunked
        prefills, and oversubscribed admission; the streamed path stays at
        3 — dense: embed + ONE group trace shared by every layer group +
        finish; expert-paged MoE: head (embed + layer-0 attn/router) + ONE
        fused expert/attn handoff trace + tail (last experts + finish);
        -1 for eager engines."""
        return self._trace_count if self.compiled else -1

    def run(self, max_steps: int = 1000) -> dict[int, list[int]]:
        for _ in range(max_steps):
            if self.step() == 0:
                break
        return {r.rid: r.out for r in self.requests.values()}
