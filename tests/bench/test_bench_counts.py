"""Operations and bytes from shapes, against hand arithmetic at OPT-1.3B."""
from __future__ import annotations

from bench_helpers import ROOT  # noqa: F401
from bench import counts, run

SIZES = run.sizes_of(run.resolve(run.load_spec(),
                                 "opt-1.3b.decode")["config"])
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}


def test_weight_bytes_by_hand():
    d, f, v, n_l = 2048, 8192, 50272, 24
    attn = 4 * d * d * 2                       # bf16 DRAM tier
    norms = 4 * d * 2
    ffn = 2 * (d * f + d * f // 8) + 4 * (f + d)   # int8 + parity + scales
    head = d * v + d * v // 8 + 4 * v + 2 * d * 2
    assert counts.weight_bytes(SIZES) == n_l * (attn + norms + ffn) + head
    # ~0.8 GB of bf16 attention, ~0.9 GB int8+parity FFN, ~0.12 GB lm_head
    assert 1.8e9 < counts.weight_bytes(SIZES) < 1.9e9


def test_decode_step_by_hand():
    # 4 sequences decoding one token each at contexts 100..400
    chunks = [(100, 1), (200, 1), (300, 1), (400, 1)]
    d, f, v, n_l = 2048, 8192, 50272, 24
    kv_row = 2 * n_l * d * 2
    want_bytes = (counts.weight_bytes(SIZES) + 1000 * kv_row + 4 * kv_row
                  + 2 * 4 * d * 2)
    assert counts.step_bytes(SIZES, chunks) == want_bytes
    proj = 2 * 4 * n_l * (4 * d * d + 2 * d * f)
    attn = 4 * d * (101 + 201 + 301 + 401) * n_l
    head = 2 * 4 * d * v
    assert counts.step_flops(SIZES, chunks, 4) == proj + attn + head
    # weight-read bound: bytes over bandwidth beat ops over peak
    least = counts.least_seconds(SIZES, chunks, 4, PEAKS)
    assert least == want_bytes / PEAKS["hbm_bytes_s"]
    assert 2e-3 < least < 3e-3


def test_prefill_chunk_attention_is_triangular():
    # one 16-token chunk at context 32: queries see 33..48 keys
    d, n_l = 2048, 24
    flops = counts.step_flops(SIZES, [(32, 16)], 0)
    proj = 2 * 16 * n_l * (4 * d * d + 2 * d * 8192)
    assert flops - proj == 4 * d * n_l * sum(range(33, 49))


def test_long_prefill_is_compute_bound():
    chunks = [(0, 2048)] * 4
    flops = counts.step_flops(SIZES, chunks, 4)
    least = counts.least_seconds(SIZES, chunks, 4, PEAKS)
    assert least == flops / PEAKS["bf16_flops"]
    assert least > counts.step_bytes(SIZES, chunks) / PEAKS["hbm_bytes_s"]
