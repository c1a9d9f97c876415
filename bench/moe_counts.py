"""Operations and bytes of a sparse-expert (Qwen3-MoE) serving step, from
shapes alone.

Counted from the configuration's sizes, the step's token counts and the
program's routing counters, never from the compiled program:

* operations, two per multiply-add: the GQA projections (``wq`` and
  ``wo`` at the query width heads x head size, ``wk``/``wv`` at the KV
  width), the score and value products over each query's context at the
  query width, the router over every expert, the ``top-k`` assignments of
  every token through three expert matrices, and ``lm_head`` only where a
  token is sampled;
* expert bytes: each distinct routed expert's three matrices once, as the
  flash tier holds them — int8 weights, a Hamming parity byte per eight
  weights, one float32 scale per output channel.

``full_sizes`` gives the keys these need from a configuration file, or
None for a configuration with no expert bank.
"""
from __future__ import annotations

KEYS = ("hidden_size", "ffn_dim", "num_attention_heads",
        "num_key_value_heads", "head_dim", "num_experts",
        "num_experts_per_tok", "num_hidden_layers", "vocab_size")


def full_sizes(conf: dict) -> dict | None:
    if not all(k in conf for k in KEYS):
        return None
    return {k: int(conf[k]) for k in KEYS}


def _flash_matrix_bytes(k: int, n: int) -> int:
    return k * n + (k // 8) * n + 4 * n


def expert_bytes(sz: dict) -> int:
    """Flash-tier bytes of one expert (gate, up and down)."""
    d, f = sz["hidden_size"], sz["ffn_dim"]
    return 2 * _flash_matrix_bytes(d, f) + _flash_matrix_bytes(f, d)


def assignment_flops(sz: dict) -> int:
    """Operations of one token->expert assignment (three matrices)."""
    return 2 * 3 * sz["hidden_size"] * sz["ffn_dim"]


def step_flops(sz: dict, chunks: list[tuple[int, int]], sampled: int) -> int:
    d, n_l = sz["hidden_size"], sz["num_hidden_layers"]
    qw = sz["num_attention_heads"] * sz["head_dim"]
    kvw = sz["num_key_value_heads"] * sz["head_dim"]
    tokens = sum(n for _, n in chunks)
    proj = 2 * tokens * n_l * (2 * d * qw + 2 * d * kvw)
    # query j (1-based) of a chunk at context c attends to c + j keys
    keys = sum(n * ctx + n * (n + 1) // 2 for ctx, n in chunks)
    attn = 4 * qw * keys * n_l
    router = 2 * tokens * n_l * d * sz["num_experts"]
    experts = tokens * n_l * sz["num_experts_per_tok"] * assignment_flops(sz)
    head = 2 * sampled * d * sz["vocab_size"]
    return proj + attn + router + experts + head


def expert_least_seconds(sz: dict, assignments: float, routed: float,
                         peaks: dict) -> float:
    """The least time the chip could take for expert products over
    ``assignments`` token->expert assignments that read ``routed`` distinct
    experts: the larger of their operations over the bf16 peak and the
    routed experts' bytes over HBM bandwidth."""
    return max(assignments * assignment_flops(sz) / peaks["bf16_flops"],
               routed * expert_bytes(sz) / peaks["hbm_bytes_s"])
