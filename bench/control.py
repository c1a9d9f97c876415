"""Readings that a cell's comparison limit is set from, on the chip.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

In one process, for each seed: set up the cell, drive it for ``--seconds``
at its own load exactly as ``bench/run.py`` does, free the program, and on
the same seed-drawn sample of served requests read, against the float32
reference, the widest gap of a token the program served (the lower
reading) and, for each control (the reference with one precision one step
below what the configuration states, ``CONTROLS`` of the reference
module), the widest gap of the token the control puts first (the upper
readings). Each reading is judged by ``bench/run.py``'s own test against
the configuration's limit. One JSON line per seed. The benchmark's own
runs never run the controls.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run  # noqa: E402


def readings(ctx: dict, seed: int, seconds: float) -> dict:
    conf = ctx["config"]
    rec = run.serve_window(ctx, seed, seconds, trace=False,
                           log_fn=lambda *a: None)
    records = rec["records"]
    chosen = run.sample_for_check(records, seed, conf["check"])
    if not chosen:
        raise RuntimeError(f"seed {seed}: no served request to compare")
    _, ref = run.arch_modules(conf)
    seqs = [list(r.prompt) + r.tokens for r in chosen]
    starts = [len(r.prompt) for r in chosen]
    limit = float(conf["check"]["max_logit_gap"])
    attempted, failed = len(records), sum(r.failed for r in records)
    t = time.time()
    served, ctrls = ref.control_gaps(run.sizes_of(conf), seed, seqs, starts)
    out = {"seed": seed, "requests": len(chosen), "tokens": int(served.size),
           "attempted": attempted, "failed": failed, "limit": limit}
    for mode, gaps in {"program": served, **ctrls}.items():
        out[mode] = {"max_gap": float(gaps.max()),
                     "mean_gap": float(gaps.mean()),
                     "flips": int((gaps > 0).sum()),
                     "correct": run.judge(float(gaps.max()), limit,
                                          attempted, failed)}
    out["reference_s"] = time.time() - t
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    ctx = run.resolve(run.load_spec(), args.workload)
    run.require_chips(int(ctx["cell"]["chips"]))
    run.enable_compile_cache()
    for seed in args.seeds:
        ctx["t_process"] = ctx["t_ready"] = time.time()
        print(json.dumps(readings(ctx, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
