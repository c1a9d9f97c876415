"""A serving step's operations and bytes, split by the kernels that do them.

The split of ``bench/counts.py``'s whole-step counts, from the same shapes:

* FFN: both flash-tier matrices of every layer (int8 weights, a Hamming
  parity byte per eight weights, a float32 scale per output channel), read
  once a step, and two operations per multiply-add for every token;
* attention: the bfloat16 ``wq``/``wk``/``wv``/``wo`` of every layer, the KV
  rows each query reads and every new row written, the projections'
  operations and the score and value products. The int8 flash copy of the
  projections that Algorithm 2 may run instead is not counted: computing a
  column twice is waste, not work;
* the rest: layer norms, the embedding and position rows of the step's
  tokens, and ``lm_head`` with its operations for each sampled token.

The three add up to ``counts.step_bytes`` and ``counts.step_flops`` exactly.
"""
from __future__ import annotations

from bench import counts
from bench.counts import BF16


def _tokens(chunks) -> int:
    return sum(n for _, n in chunks)


def ffn_bytes(sizes: dict, chunks) -> int:
    d, f = sizes["hidden_size"], sizes["ffn_dim"]
    return sizes["num_hidden_layers"] * (counts._flash_matrix_bytes(d, f)
                                         + counts._flash_matrix_bytes(f, d))


def ffn_flops(sizes: dict, chunks) -> int:
    d, f = sizes["hidden_size"], sizes["ffn_dim"]
    return 2 * _tokens(chunks) * sizes["num_hidden_layers"] * 2 * d * f


def attn_bytes(sizes: dict, chunks) -> int:
    d = sizes["hidden_size"]
    rows = sum(ctx for ctx, _ in chunks) + _tokens(chunks)
    return (sizes["num_hidden_layers"] * 4 * d * d * BF16
            + rows * counts.kv_row_bytes(sizes))


def attn_flops(sizes: dict, chunks) -> int:
    d, n_l = sizes["hidden_size"], sizes["num_hidden_layers"]
    # query j (1-based) of a chunk at context c attends to c + j keys
    keys = sum(n * ctx + n * (n + 1) // 2 for ctx, n in chunks)
    return 2 * _tokens(chunks) * n_l * 4 * d * d + 4 * d * keys * n_l


def rest_bytes(sizes: dict, chunks) -> int:
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    norms = sizes["num_hidden_layers"] * 4 * d * BF16
    head = counts._flash_matrix_bytes(d, v) + 2 * d * BF16
    return norms + head + 2 * _tokens(chunks) * d * BF16


def rest_flops(sizes: dict, chunks, sampled: int) -> int:
    return 2 * sampled * sizes["hidden_size"] * sizes["vocab_size"]


def _least(flops: int, nbytes: int, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_s"])


def ffn_least_seconds(sizes: dict, chunks, peaks: dict) -> float:
    """The least time the chip could take for the step's FFNs."""
    return _least(ffn_flops(sizes, chunks), ffn_bytes(sizes, chunks), peaks)


def attn_least_seconds(sizes: dict, chunks, peaks: dict) -> float:
    """The least time the chip could take for the step's attention."""
    return _least(attn_flops(sizes, chunks), attn_bytes(sizes, chunks),
                  peaks)
