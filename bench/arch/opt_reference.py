"""Plain float32 reference of the served OPT model, and its controls.

The reference imports nothing of the program. It regenerates the weights
from the seed (``bench/arch/opt.py``) one layer at a time and runs the
model as the configuration states it, in straightforward ``jax.numpy`` at
``Precision.HIGHEST``:

* pre-LayerNorm blocks (eps 1e-5), learned positions added to the token
  embedding, no biases, tanh-approximated GELU, causal softmax attention
  scaled by ``head_dim ** -0.5``, an untied ``lm_head``;
* the DRAM tier (attention projections) in bfloat16, upcast exactly;
* the flash tier (FFN and ``lm_head``) in int8 with one symmetric scale
  per output channel, ``scale = max|w| / 127`` over the reduction axis,
  ``q = clip(round(w / scale), -127, 127)``, dequantized as ``q * scale``.

ECC is the identity on clean flash (``rber`` 0), so it has no term here.
Algorithm 2 may run some query/output columns from the int8 flash copy of
the attention weights; the reference keeps the stated bfloat16 tier for
all of them, and that difference is part of what the lower reading of the
comparison covers.

The controls are the same model with one precision one step below what
the configuration states, the step a later change might be tempted to
take, one at a time (``CONTROLS``): matmul inputs cast through
float8_e4m3fn (activations stated bfloat16), or the flash tier in int4
(stated int8; same per-channel rule, 7 levels a side). The DRAM tier in
int8 is no control: Algorithm 2 already runs attention columns from
their int8 flash copy, so that step is within what the program reads.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.arch import opt as weights

HIGHEST = jax.lax.Precision.HIGHEST
# mode -> (DRAM-tier bits or None for bfloat16, flash-tier bits,
#          matmul inputs through float8)
MODES = {"reference": (None, 8, False),
         "fp8_act": (None, 8, True),
         "int4_flash": (None, 4, False)}
CONTROLS = tuple(m for m in MODES if m != "reference")


def quantize(w, bits: int):
    """Symmetric per-output-channel quantization over axis 0 (the reduction
    axis of an (in, out) matrix), returned dequantized in float32."""
    qmax = 2 ** (bits - 1) - 1
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=0, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    return jnp.clip(jnp.round(w32 / scale), -qmax, qmax) * scale


def _weights_for(mode: str, dram, flash):
    """(attention weights, flash weights) in the precision of ``mode``."""
    dram_bits, flash_bits, _ = MODES[mode]
    return ({k: w.astype(jnp.float32) if dram_bits is None
             else quantize(w, dram_bits) for k, w in dram.items()},
            {k: quantize(w, flash_bits) for k, w in flash.items()})


def _act(mode: str, x):
    if not MODES[mode][2]:
        return x
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _mm(mode, x, w):
    return jnp.dot(_act(mode, x), w, precision=HIGHEST)


def _ln(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + 1e-5) * g.astype(jnp.float32)
            + b.astype(jnp.float32))


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("n_heads", "mode"))
def _layer(lp, xs, *, n_heads: int, mode: str):
    """One block over (B, S, D) float32 hidden states, a sequence at a time
    (``lax.map``) so that one (H, S, S) score block is live at once."""
    att, ffn = _weights_for(mode, lp["attn"],
                            {k: lp["ffn"][k] for k in ("w_up", "w_down")})

    def one(x):
        s, d = x.shape
        dh = d // n_heads
        h = _ln(x, lp["ln1_g"], lp["ln1_b"])
        q, k, v = (_mm(mode, h, att[n]).reshape(s, n_heads, dh).transpose(1, 0, 2)
                   for n in ("wq", "wk", "wv"))
        scores = jnp.einsum("hqd,hkd->hqk", q, k, precision=HIGHEST) * dh ** -0.5
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("hqk,hkd->hqd", p, v, precision=HIGHEST)
        x = x + _mm(mode, o.transpose(1, 0, 2).reshape(s, d), att["wo"])
        h = _ln(x, lp["ln2_g"], lp["ln2_b"])
        return x + _mm(mode, _gelu_tanh(_mm(mode, h, ffn["w_up"])),
                       ffn["w_down"])

    return jax.lax.map(one, xs)


@jax.jit
def _embed(top, tokens, positions):
    return (top["embed"][tokens].astype(jnp.float32)
            + top["pos_embed"][positions].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("mode",))
def _logits(top, rows, *, mode: str):
    _, flash = _weights_for(mode, {}, {"lm_head": top["lm_head"]})
    h = _ln(rows, top["final_norm"]["g"], top["final_norm"]["b"])
    return _mm(mode, h, flash["lm_head"])


def _pad_len(n: int, block: int = 128) -> int:
    return -(-n // block) * block


def logits_rows(sizes: dict, seed: int, seqs: list[list[int]],
                starts: list[int], mode: str = "reference"):
    """Logits of ``mode`` at the positions that predicted each sequence's
    tokens from ``starts[i]`` on: rows ``starts[i] - 1 .. len(seqs[i]) - 2``
    of sequence i, concatenated in order. Returns a (rows, vocab) float32
    device array."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {tuple(MODES)}, got {mode!r}")
    s_pad = _pad_len(max(len(s) for s in seqs))
    tokens = np.zeros((len(seqs), s_pad), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    positions = np.broadcast_to(np.arange(s_pad, dtype=np.int32), tokens.shape)
    top = weights.top_params(sizes, seed)
    xs = _embed(top, jnp.asarray(tokens), jnp.asarray(positions))
    for i in range(int(sizes["num_hidden_layers"])):
        lp = weights.layer_params(sizes, seed, i)
        xs = _layer(lp, xs, n_heads=int(sizes["num_attention_heads"]),
                    mode=mode)
        del lp
    idx_b = np.concatenate([np.full(len(s) - st, b, np.int32)
                            for b, (s, st) in enumerate(zip(seqs, starts))])
    idx_s = np.concatenate([np.arange(st - 1, len(s) - 1, dtype=np.int32)
                            for s, st in zip(seqs, starts)])
    rows = xs[jnp.asarray(idx_b), jnp.asarray(idx_s)]
    del xs
    return _logits(top, rows, mode=mode)


@jax.jit
def _gaps(ref_logits, chosen):
    best = jnp.max(ref_logits, axis=-1)
    at = jnp.take_along_axis(ref_logits, chosen[:, None], axis=-1)[:, 0]
    return best - at


def served_gaps(sizes: dict, seed: int, seqs: list[list[int]],
                starts: list[int]) -> np.ndarray:
    """Per served token: how far the reference's logit of the token the
    program served lies below the reference's best logit at that position.
    ``seqs[i]`` is prompt + served tokens, ``starts[i]`` the prompt length."""
    chosen = np.concatenate([np.asarray(s[st:], np.int32)
                             for s, st in zip(seqs, starts)])
    ref = logits_rows(sizes, seed, seqs, starts, "reference")
    return np.asarray(_gaps(ref, jnp.asarray(chosen)))


def control_gaps(sizes: dict, seed: int, seqs: list[list[int]],
                 starts: list[int], controls=CONTROLS
                 ) -> tuple[np.ndarray, dict[str, np.ndarray]]:
    """(served gaps, {control: gaps}) at the same positions: a control's
    gap is that of the token its lower precision puts first."""
    chosen = np.concatenate([np.asarray(s[st:], np.int32)
                             for s, st in zip(seqs, starts)])
    ref = logits_rows(sizes, seed, seqs, starts, "reference")
    served = np.asarray(_gaps(ref, jnp.asarray(chosen)))
    out = {}
    for mode in controls:
        top = jnp.argmax(logits_rows(sizes, seed, seqs, starts, mode),
                         axis=-1).astype(jnp.int32)
        out[mode] = np.asarray(_gaps(ref, top))
    return served, out
