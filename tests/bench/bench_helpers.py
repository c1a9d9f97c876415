"""Shared by the benchmark tests: the repository root on the path so
``bench`` imports, and a tiny cell laid out the way ``BENCHMARK.json``
lays out the real ones."""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_SIZES = {"hidden_size": 128, "ffn_dim": 512, "num_attention_heads": 4,
              "num_hidden_layers": 2, "vocab_size": 512,
              "max_position_embeddings": 256}
TINY_MIX = {"loop": "closed", "clients": 2, "think_s": 0,
            "prompt_tokens": {"min": 20, "max": 60, "dist": "log_uniform"},
            "output_tokens": {"min": 8, "max": 16, "dist": "uniform"},
            "levels": 4, "token_low": 1}


# The tiny cell's limit, set as the cells' are: on the CPU the program read
# at most 0.0059 over 12 seeds, and the fp8-input control, the smallest
# control that separates at this size, at least 0.0319.
TINY_LIMIT = 0.02


def write_tiny_bench(root: Path, limit: float = TINY_LIMIT) -> Path:
    """A checkout-shaped tree under ``root`` holding one tiny OPT cell,
    ``tiny.t``: its BENCHMARK.json, configuration and mix. Returns the
    tree's ``bench`` directory."""
    bench = root / "bench"
    (bench / "configs").mkdir(parents=True, exist_ok=True)
    (bench / "traffic").mkdir(parents=True, exist_ok=True)
    conf = json.loads((ROOT / "bench" / "configs" / "opt-1.3b.json")
                      .read_text())
    conf.update(TINY_SIZES, name="tiny")
    conf["engine"] = dict(conf["engine"], max_seq=256)
    conf["check"] = {"max_logit_gap": limit, "min_tokens": 64,
                     "max_requests": 8}
    (bench / "configs" / "tiny.json").write_text(json.dumps(conf))
    (bench / "traffic" / "tmix.json").write_text(json.dumps(TINY_MIX))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": conf["source"],
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "tiny"}]
    spec["workloads"] = [{"name": "tiny.t", "config": "tiny",
                          "traffic": "tmix", "chips": 1, "why": "tiny"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)          # the tiny cell reports them all
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return bench
