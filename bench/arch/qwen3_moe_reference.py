"""Plain reference of the served Qwen3-MoE model, and its controls.

The reference imports nothing of the program. It regenerates the weights
from the seed (``bench/arch/qwen3_moe.py``) one layer at a time and runs
the model as the configuration states it, in straightforward ``jax.numpy``
at ``Precision.HIGHEST`` (float32 products and sums):

* pre-RMSNorm blocks (eps from the configuration, gain ``1 + gamma``);
* attention: q/k/v projections, a per-head RMSNorm on q and k before
  rotate-half RoPE (base ``rope_theta``), grouped-query attention (query
  head ``h`` reads KV head ``h // (heads / kv_heads)``), causal softmax
  scaled by ``head_dim ** -0.5``, the output projection;
* the router: a softmax over all expert logits, the top ``k`` kept and
  renormalized to sum to 1; each token's FFN is the gate-weighted sum of
  its ``k`` SwiGLU experts (``down(silu(gate(x)) * up(x))``), computed
  here for every expert over every row, each row then taking its ``k``,
  so no dispatch is shared with the program;
* an untied ``lm_head``;
* the precision the configuration states: weights of the DRAM tier
  (attention, router, embedding) in bfloat16, upcast exactly; the flash
  tier (experts and ``lm_head``) in int8 with one symmetric scale per
  output channel, ``scale = max|w| / 127`` over the reduction axis,
  ``q = clip(round(w / scale), -127, 127)``, each product scaled after
  the sum over ``q``; activations in bfloat16, rounded where the program
  keeps them in bfloat16 (``_act``): each norm's output, the projections,
  the RoPE outputs, the scaled queries, the attention probabilities
  before they weight the values, the attention output, the expert
  products and residual sums; the router logits, the softmax sums and
  the final logits stay float32. Each row's experts are summed in the
  router's order.

ECC is the identity on clean flash (``rber`` 0), so it has no term here.

Rounding activations where the configuration says so matters for a
sparse-expert model: bfloat16 moves router logits by more than the margin
between the k-th and (k+1)-th expert on a large share of rows, so a
float32 model routes differently there and its logits are not what a
sound bfloat16 model serves. ``f32`` is that float32 model, no control:
``check_readings`` reports how often its routing differs.

The controls are the reference with one precision one step below what
the configuration states, one at a time (``CONTROLS``): the activations
in float8 e4m3 wherever they are stated bfloat16 (``fp8_act``), or the
experts in int4 (stated int8; same per-channel rule, 7 levels a side).
``int4_flash`` puts the whole flash tier (experts and ``lm_head``) in
int4, the form of the OPT cell's flash-tier control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.arch import qwen3_moe as weights

HIGHEST = jax.lax.Precision.HIGHEST
# mode -> (activation type, expert bits, lm_head bits): float32, bfloat16
# or float8 activations (ACTS), handed to the compiled layer as numbers so
# that every mode runs one program
ACTS = ("float32", "bfloat16", "float8_e4m3")
MODES = {"reference": ("bfloat16", 8, 8),
         "f32": ("float32", 8, 8),
         "fp8_act": ("float8_e4m3", 8, 8),
         "int4_experts": ("bfloat16", 4, 8),
         "int4_flash": ("bfloat16", 4, 4)}
CONTROLS = ("fp8_act", "int4_experts")


def _precision(mode: str) -> jnp.ndarray:
    """(activation type's index in ACTS, expert levels a side, lm_head
    levels a side) of ``mode``."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {tuple(MODES)}, got {mode!r}")
    act, experts, head = MODES[mode]
    return jnp.asarray([ACTS.index(act), 2 ** (experts - 1) - 1,
                        2 ** (head - 1) - 1], jnp.float32)


def quantize(w, qmax):
    """Symmetric per-output-channel quantization over axis -2 (the
    reduction axis of a (..., in, out) matrix) to ``qmax`` levels a side,
    127 (int8) or 7 (int4): (levels, scales), both float32,
    ``w ~ levels * scales``."""
    w32 = w.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(w32), axis=-2, keepdims=True), 1e-8)
    # a division by a constant, as a quantizer written for one width has
    scale = jnp.where(qmax == 127, amax / 127.0, amax / 7.0)
    return jnp.clip(jnp.round(w32 / scale), -qmax, qmax), scale


def _act(prec, x):
    """An activation as the mode keeps it: float32, or rounded to bfloat16
    (as stated) or to float8 e4m3, in float32 (``reduce_precision``: a
    round trip through the narrow type may be dropped by the compiler)."""
    bf16 = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    fp8 = jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
    return jnp.where(prec[0] == 1, bf16, jnp.where(prec[0] == 2, fp8, x))


def _dot(x, w):
    """``x @ w``; a flash-tier weight, (levels, scales), is multiplied by
    its levels and the product scaled per output channel."""
    if isinstance(w, tuple):
        return jnp.dot(x, w[0], precision=HIGHEST) * w[1]
    return jnp.dot(x, w, precision=HIGHEST)


def _mm(prec, x, w):
    """A projection whose output is an activation."""
    return _act(prec, _dot(x, w))


def _rms(x, g, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g.astype(jnp.float32))


def _attend(prec, q, k, v, scale, start):
    """Causal softmax attention over (S, H, Dh). Queries are scaled, then
    rounded as an activation. Keys weigh in by their unnormalized
    probabilities, each rounded before it weights its value and the sum
    divided by their float32 total. A served token's row (from ``start``
    on) meets its cached keys apart from its own: those are rounded as
    ``exp`` of their score less their own maximum, then rescaled to the
    row's, and its own key enters unrounded."""
    s = q.shape[0]
    scores = jnp.einsum("qhd,khd->hqk", _act(prec, q * scale), k,
                        precision=HIGHEST)
    rows = jnp.arange(s)
    causal = (rows[None, :] <= rows[:, None])[None]
    own = ((rows[:, None] == rows[None, :]) & (rows[:, None] >= start))[None]
    scores = jnp.where(causal, scores, -jnp.inf)
    top = jnp.max(scores, axis=-1, keepdims=True)
    cached = jnp.max(jnp.where(own, -jnp.inf, scores), axis=-1, keepdims=True)
    part = jnp.where(own, scores, jnp.where(jnp.isfinite(cached), cached, top))
    p = _act(prec, jnp.exp(scores - part)) * jnp.exp(part - top)
    total = jnp.sum(jnp.exp(scores - top), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
    return _act(prec, o / total.T[..., None])


def _rope(x, pos, base):
    """Rotate-half RoPE over (S, heads, Dh) at positions (S,)."""
    dh = x.shape[-1]
    freqs = 1.0 / (base ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def _route(logits, top_k):
    """(gates (S, k), experts (S, k)): the k largest router logits' experts
    and their probabilities renormalized over the k (a softmax over all
    logits, the top k kept and divided by their sum, is the softmax over
    the k kept logits)."""
    top, idx = jax.lax.top_k(logits, top_k)
    return jax.nn.softmax(top, axis=-1), idx


def _experts(prec, bank, h, gates, idx):
    """Every expert over every row; each row sums its k experts' outputs,
    weighted by its gates, in the router's order."""
    def one(_, e):
        wg, wu, wd = (quantize(bank[n][e], prec[1])
                      for n in ("w_gate", "w_up", "w_down"))
        inner = _act(prec, jax.nn.silu(_mm(prec, h, wg)) * _mm(prec, h, wu))
        return None, _mm(prec, inner, wd)

    _, ys = jax.lax.scan(one, None, jnp.arange(bank["w_gate"].shape[0]))
    picked = ys[idx, jnp.arange(h.shape[0])[:, None]]           # (S, k, D)
    return _act(prec, jnp.sum(picked * gates[..., None], axis=-2))


@functools.partial(jax.jit, static_argnames=("sz",))
def _layer(lp, xs, starts, positions, prec, *, sz: tuple):
    """One block over (B, S, D) float32 hidden states, a sequence at a time
    (``lax.map``), so one (H, S, S) score block is live at once. Returns
    the new states and each row's router logits."""
    sz = dict(sz)
    h_n, kv, dh = (sz["num_attention_heads"], sz["num_key_value_heads"],
                   sz["head_dim"])
    eps, top_k = sz["rms_norm_eps"], sz["num_experts_per_tok"]
    att = {k: lp["attn"][k].astype(jnp.float32)
           for k in ("wq", "wk", "wv", "wo")}
    router = lp["moe"]["router"].astype(jnp.float32)

    def one(args):
        x, start = args
        s, d = x.shape
        h = _act(prec, _rms(x, lp["ln1"], eps))
        q = _mm(prec, h, att["wq"]).reshape(s, h_n, dh)
        k = _mm(prec, h, att["wk"]).reshape(s, kv, dh)
        v = _mm(prec, h, att["wv"]).reshape(s, kv, dh)
        q = _act(prec, _rope(_act(prec, _rms(q, lp["attn"]["q_norm"], eps)),
                             positions, sz["rope_theta"]))
        k = _act(prec, _rope(_act(prec, _rms(k, lp["attn"]["k_norm"], eps)),
                             positions, sz["rope_theta"]))
        k = jnp.repeat(k, h_n // kv, axis=1)
        v = jnp.repeat(v, h_n // kv, axis=1)
        o = _attend(prec, q, k, v, dh ** -0.5, start)
        x = _act(prec, x + _mm(prec, o.reshape(s, h_n * dh), att["wo"]))
        h = _act(prec, _rms(x, lp["ln2"], eps))
        logits = _dot(h, router)
        x = _act(prec, x + _experts(prec, lp["moe"]["experts"], h,
                                    *_route(logits, top_k)))
        return x, logits

    return jax.lax.map(one, (xs, starts))


@jax.jit
def _embed(top, tokens, prec):
    return _act(prec, top["embed"][tokens].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(top, rows, prec, *, eps: float):
    h = _act(prec, _rms(rows, top["final_norm"], eps))
    return _dot(h, quantize(top["lm_head"], prec[2]))


def _pad_len(n: int, block: int = 128) -> int:
    return -(-n // block) * block


def _rows(seqs, starts):
    """(sequence, position) of the rows that predicted each served token."""
    idx_b = np.concatenate([np.full(len(s) - st, b, np.int32)
                            for b, (s, st) in enumerate(zip(seqs, starts))])
    idx_s = np.concatenate([np.arange(st - 1, len(s) - 1, dtype=np.int32)
                            for s, st in zip(seqs, starts)])
    return idx_b, idx_s


def _forward(sizes: dict, seed: int, seqs, starts, mode: str,
             keep_router: bool = False):
    prec = _precision(mode)
    sz = weights.full_sizes(sizes)
    s_pad = _pad_len(max(len(s) for s in seqs))
    tokens = np.zeros((len(seqs), s_pad), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    top = weights.top_params(sizes, seed)
    xs = _embed(top, jnp.asarray(tokens), prec)
    positions = jnp.arange(s_pad, dtype=jnp.int32)
    first = jnp.asarray(starts, jnp.int32)
    idx_b, idx_s = _rows(seqs, starts)
    routers = []
    key = tuple(sorted(sz.items()))
    for i in range(int(sz["num_hidden_layers"])):
        lp = weights.layer_params(sizes, seed, i)
        xs, router = _layer(lp, xs, first, positions, prec, sz=key)
        del lp
        if keep_router:
            routers.append(np.asarray(router[idx_b, idx_s]))
        del router
    rows = xs[jnp.asarray(idx_b), jnp.asarray(idx_s)]
    del xs
    logits = _logits(top, rows, prec, eps=float(sz["rms_norm_eps"]))
    return logits, routers


def logits_rows(sizes: dict, seed: int, seqs: list[list[int]],
                starts: list[int], mode: str = "reference"):
    """Logits of ``mode`` at the positions that predicted each sequence's
    tokens from ``starts[i]`` on: rows ``starts[i] - 1 .. len(seqs[i]) - 2``
    of sequence i, concatenated in order. Returns a (rows, vocab) float32
    device array."""
    return _forward(sizes, seed, seqs, starts, mode)[0]


@jax.jit
def _gaps(ref_logits, chosen):
    best = jnp.max(ref_logits, axis=-1)
    at = jnp.take_along_axis(ref_logits, chosen[:, None], axis=-1)[:, 0]
    return best - at


def _chosen(seqs, starts):
    return jnp.asarray(np.concatenate([np.asarray(s[st:], np.int32)
                                       for s, st in zip(seqs, starts)]))


def served_gaps(sizes: dict, seed: int, seqs: list[list[int]],
                starts: list[int]) -> np.ndarray:
    """Per served token: how far the reference's logit of the token the
    program served lies below the reference's best logit at that position.
    ``seqs[i]`` is prompt + served tokens, ``starts[i]`` the prompt length."""
    ref = logits_rows(sizes, seed, seqs, starts, "reference")
    return np.asarray(_gaps(ref, _chosen(seqs, starts)))


def check_readings(sizes: dict, seed: int, seqs: list[list[int]],
                   starts: list[int], controls=CONTROLS) -> dict:
    """What a comparison limit is set from, at the rows that predicted the
    served tokens (``seqs``/``starts`` as in ``served_gaps``):

    * ``program``: the served gaps against the reference;
    * ``program_vs_f32``: the served gaps against the ``f32`` model;
    * each control: the gap of the token its lower precision puts first;
    * ``near_tie`` / ``flipped`` (rows): in some layer the ``f32`` model's
      margin between its k-th and (k+1)-th router logits lies under twice
      the reference's router-logit error over those two experts / the
      reference routes a different top-k set than the ``f32`` model;
    * ``margin`` (rows, layers): those margins."""
    sz = weights.full_sizes(sizes)
    k = int(sz["num_experts_per_tok"])
    chosen = _chosen(seqs, starts)
    ref, ref_r = _forward(sizes, seed, seqs, starts, "reference", True)
    f32, f32_r = _forward(sizes, seed, seqs, starts, "f32", True)
    out = {"program": np.asarray(_gaps(ref, chosen)),
           "program_vs_f32": np.asarray(_gaps(f32, chosen))}
    del f32
    for mode in controls:
        top = jnp.argmax(logits_rows(sizes, seed, seqs, starts, mode),
                         axis=-1).astype(jnp.int32)
        out[mode] = np.asarray(_gaps(ref, top))
    near = np.zeros(chosen.shape[0], bool)
    flipped = np.zeros_like(near)
    margins = []
    for r, b in zip(f32_r, ref_r):
        order = np.argsort(-r, axis=-1)
        kth = np.take_along_axis(r, order[:, k - 1:k], -1)[:, 0]
        nxt = np.take_along_axis(r, order[:, k:k + 1], -1)[:, 0]
        err = np.max(np.abs(np.take_along_axis(b - r, order[:, k - 1:k + 1],
                                               -1)), axis=-1)
        margins.append(kth - nxt)
        near |= (kth - nxt) < 2 * err
        top_r = np.sort(order[:, :k], axis=-1)
        top_b = np.sort(np.argsort(-b, axis=-1)[:, :k], axis=-1)
        flipped |= np.any(top_r != top_b, axis=-1)
    out.update(near_tie=near, flipped=flipped,
               margin=np.stack(margins, axis=-1))
    return out
