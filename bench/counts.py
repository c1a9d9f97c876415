"""Operations and bytes a serving step needs, from shapes alone.

Counted from the configuration's sizes and the step's token counts, never
from the compiled program, so no change to the program's path moves them:

* weights: every weight once per step, at the precision of its tier —
  the DRAM tier (attention projections, norms) in bfloat16, the flash
  tier (FFN and ``lm_head``) as int8 plus its Hamming(72,64) parity byte
  per eight weights and one float32 scale per output channel; embedding
  and position rows for the tokens of the step;
* KV: every context row each query reads (K and V, bfloat16), and every
  new row written;
* operations: two per multiply-add of every projection for every token,
  ``lm_head`` only where a token is sampled, and the attention products
  (scores and weighted values) over each query's context.

A step is a list of chunks ``(context rows, new tokens)``, one per sequence
that worked in it, and the number of tokens sampled.
"""
from __future__ import annotations

BF16 = 2


def _flash_matrix_bytes(k: int, n: int) -> int:
    return k * n + (k // 8) * n + 4 * n


def weight_bytes(sizes: dict) -> int:
    """Bytes of weights one step reads, each weight once."""
    d, f = sizes["hidden_size"], sizes["ffn_dim"]
    v, n_l = sizes["vocab_size"], sizes["num_hidden_layers"]
    attn = 4 * d * d * BF16
    norms = 4 * d * BF16
    ffn = _flash_matrix_bytes(d, f) + _flash_matrix_bytes(f, d)
    head = _flash_matrix_bytes(d, v) + 2 * d * BF16
    return n_l * (attn + norms + ffn) + head


def kv_row_bytes(sizes: dict) -> int:
    """K and V of one position in every layer (KV heads x head size = d)."""
    return 2 * sizes["num_hidden_layers"] * sizes["hidden_size"] * BF16


def step_bytes(sizes: dict, chunks: list[tuple[int, int]]) -> int:
    d = sizes["hidden_size"]
    tokens = sum(n for _, n in chunks)
    kv_read = sum(ctx for ctx, _ in chunks) * kv_row_bytes(sizes)
    kv_write = tokens * kv_row_bytes(sizes)
    embed_rows = 2 * tokens * d * BF16
    return weight_bytes(sizes) + kv_read + kv_write + embed_rows


def step_flops(sizes: dict, chunks: list[tuple[int, int]],
               sampled: int) -> int:
    d, f = sizes["hidden_size"], sizes["ffn_dim"]
    v, n_l = sizes["vocab_size"], sizes["num_hidden_layers"]
    tokens = sum(n for _, n in chunks)
    proj = 2 * tokens * n_l * (4 * d * d + 2 * d * f)
    # query j (1-based) of a chunk at context c attends to c + j keys
    keys = sum(n * ctx + n * (n + 1) // 2 for ctx, n in chunks)
    attn = 4 * d * keys * n_l
    head = 2 * sampled * d * v
    return proj + attn + head


def least_seconds(sizes: dict, chunks, sampled: int, peaks: dict) -> float:
    """The least time the chip could take for the step: the larger of its
    operations over the bf16 peak and its bytes over HBM bandwidth."""
    return max(step_flops(sizes, chunks, sampled) / peaks["bf16_flops"],
               step_bytes(sizes, chunks) / peaks["hbm_bytes_s"])
