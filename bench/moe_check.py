"""Readings for a sparse-expert cell's comparison limit, on the chip.

    python bench/moe_check.py --workload <cell> --seconds <s> --seed <n>

A served window at the seed, then at the compared rows: the program's widest
gap against the reference (what decides ``correct``) and against the
float32 model, each control's widest gap, and where the gaps sit — rows at
which some layer's routing is near-tied (the float32 model's margin between
its k-th and (k+1)-th router logits under the router-logit error that
bfloat16 activations bring; ``check_readings`` of the reference module)
against the other rows, as one JSON line. One seed a process, as the
benchmark runs: a second set-up in the same process does not find the
device memory the first left free. The benchmark's own runs never run
this.
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


def _part(gaps):
    if gaps.size == 0:
        return {"rows": 0}
    return {"rows": int(gaps.size), "max_gap": float(gaps.max()),
            "sum_gap": float(gaps.sum())}


def readings(ctx: dict, seed: int, seconds: float) -> dict:
    import numpy as np
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
    sizes = run.sizes_of(conf)
    limit = float(conf["check"]["max_logit_gap"])
    attempted, failed = len(records), sum(r.failed for r in records)
    t = time.time()
    ctrls = ref.CONTROLS + ("int4_flash",)
    got = ref.check_readings(sizes, seed, seqs, starts, ctrls)
    served = got["program"]
    out = {"seed": seed, "requests": len(chosen), "tokens": int(served.size),
           "attempted": attempted, "failed": failed, "limit": limit}
    for mode in ("program", "program_vs_f32") + ctrls:
        gaps = got[mode]
        out[mode] = {"max_gap": float(gaps.max()),
                     "mean_gap": float(gaps.mean()),
                     "flips": int((gaps > 0).sum()),
                     "correct": run.judge(float(gaps.max()), limit,
                                          attempted, failed)}
    near, flipped = got["near_tie"], got["flipped"]
    out["near_tie"] = {"share": float(near.mean()),
                       "flipped_share": float(flipped.mean()),
                       "median_margin": float(np.median(got["margin"])),
                       "near": _part(served[near]),
                       "other": _part(served[~near]),
                       "near_vs_f32": _part(got["program_vs_f32"][near]),
                       "other_vs_f32": _part(got["program_vs_f32"][~near])}
    out["reference_s"] = time.time() - t
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    ctx = run.resolve(run.load_spec(), args.workload)
    run.require_chips(int(ctx["cell"]["chips"]))
    run.enable_compile_cache()
    ctx["t_process"] = ctx["t_ready"] = time.time()
    print(json.dumps(readings(ctx, args.seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
