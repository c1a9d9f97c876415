"""The traffic generator: deterministic in the seed, same sizes for all."""
from __future__ import annotations

import collections
import json

from bench_helpers import ROOT
from bench import traffic

DECODE = json.loads((ROOT / "bench/traffic/decode.json").read_text())


def test_same_seed_same_requests():
    big = 2**33 + 12345
    a = traffic.Traffic(DECODE, big, 50272)
    b = traffic.Traffic(DECODE, big, 50272)
    for c in range(a.clients):
        for i in range(10):
            assert a.request(c, i) == b.request(c, i)


def test_other_seed_same_sizes_same_order_other_ids():
    n = DECODE["levels"]
    a = traffic.Traffic(DECODE, 1, 50272)
    b = traffic.Traffic(DECODE, 2**40 + 9, 50272)
    for c in range(a.clients):
        assert [a.level(c, i) for i in range(2 * n)] == \
            [b.level(c, i) for i in range(2 * n)]
    assert a.request(0, 0).prompt != b.request(0, 0).prompt
    assert len(a.request(0, 0).prompt) == len(b.request(0, 0).prompt)


def test_first_requests_span_the_range():
    t = traffic.Traffic(DECODE, 5, 50272)
    first = sorted(t.level(c, 0) for c in range(t.clients))
    assert first == sorted(t.levels[j * 2] for j in range(t.clients))
    assert t.stagger_s > 0


def test_levels_span_the_ranges():
    lv = traffic.levels(DECODE["prompt_tokens"], 8)
    assert 128 <= min(lv) and max(lv) <= 512 and lv == sorted(lv)
    ratios = [b / a for a, b in zip(lv, lv[1:])]
    assert max(ratios) - min(ratios) < 0.05          # log-uniform spacing
    out = traffic.levels(DECODE["output_tokens"], 8)
    assert 128 <= min(out) and max(out) <= 256
    assert traffic.levels({"min": 8, "max": 8}, 3) == [8, 8, 8]


def test_every_client_cycles_through_every_level():
    t = traffic.Traffic(DECODE, 7, 50272)
    n = DECODE["levels"]
    want = collections.Counter(traffic.levels(DECODE["prompt_tokens"], n))
    for c in range(t.clients):
        got = collections.Counter(len(t.request(c, i).prompt)
                                  for i in range(n, 2 * n))
        assert got == want
    ids = [tok for i in range(n) for tok in t.request(0, i).prompt]
    assert min(ids) >= 1 and max(ids) < 50272


def test_mixes_fit_their_cells():
    from bench import run
    spec = run.load_spec()
    for cell in spec["workloads"]:
        ctx = run.resolve(spec, cell["name"])
        t = traffic.Traffic(ctx["mix"], 0, ctx["config"]["vocab_size"])
        assert t.max_seq <= ctx["config"]["engine"]["max_seq"]
        assert t.max_seq <= ctx["config"]["max_position_embeddings"]
