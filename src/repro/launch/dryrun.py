"""Multi-pod dry-run (deliverable e): lower + compile every cell.

The dry-run lowers onto 512 VIRTUAL CPU devices, so it pins itself to the
CPU platform (on a machine with an accelerator it must not claim the chip)
and forces the device count. Both MUST come before ANY other import —
jax locks the platform and device count on first init:
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse       # noqa: E402
import json           # noqa: E402
import time           # noqa: E402
import traceback      # noqa: E402
from functools import partial  # noqa: E402
from pathlib import Path       # noqa: E402

import jax            # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import (ARCHS, SHAPES, applicable, batch_specs,  # noqa: E402
                           cache_specs, get_config)
from repro.core.tiering import deploy  # noqa: E402
from repro.launch import hlo_cost  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch import sharding as sh  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import (make_decode_step, make_prefill_step,  # noqa: E402
                                make_train_step)
from repro.models import family_module  # noqa: E402
from repro.optim.adamw import AdamW  # noqa: E402

# Per-arch training memory knobs (DESIGN.md §5): FSDP + bf16 moments +
# gradient accumulation for the capacity-stress cases.
TRAIN_KNOBS: dict[str, dict] = {
    "llama3-405b": dict(fsdp=True, moment_dtype="bfloat16", n_micro=16,
                        accum_dtype="bfloat16"),
    "llava-next-34b": dict(fsdp=True, moment_dtype="float32", n_micro=4),
    "qwen3-32b": dict(fsdp=True, moment_dtype="float32", n_micro=4),
    "qwen3-moe-30b-a3b": dict(fsdp=True, moment_dtype="float32", n_micro=4),
    "phi3.5-moe-42b-a6.6b": dict(fsdp=True, moment_dtype="float32", n_micro=4),
    "mistral-nemo-12b": dict(fsdp=True, n_micro=2),
    "granite-8b": dict(fsdp=True, n_micro=2),
    "recurrentgemma-9b": dict(fsdp=True, n_micro=2),
    "rwkv6-3b": dict(fsdp=True, n_micro=2),
    "seamless-m4t-medium": dict(n_micro=1),
}

SERVE_INT8 = True     # paper §4.1: all models quantized INT8 for serving


def _mem_dict(ma) -> dict:
    if ma is None:
        return {}
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes")
    return {f: getattr(ma, f, None) for f in fields}


def _eval_params(cfg, tiered: bool):
    mod = family_module(cfg.family)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(partial(mod.init, cfg), key)
    if tiered:
        params = jax.eval_shape(lambda p: deploy(p)[0], params)
    return params


def build_cell(arch: str, shape_name: str, mesh, smoke: bool = False):
    """Returns (step_fn, args, in_shardings, out_shardings, donate)."""
    cfg = get_config(arch, smoke=smoke)
    shape = SHAPES[shape_name]
    knobs = TRAIN_KNOBS.get(arch, {}) if not smoke else {}
    batch = batch_specs(cfg, shape, smoke=smoke)
    bspecs = sh.named(sh.batch_specs(batch, mesh), mesh)

    if shape.kind == "train":
        params = _eval_params(cfg, tiered=False)
        pspecs_p = sh.param_specs(params, mesh, fsdp=knobs.get("fsdp", False))
        pspecs = sh.named(pspecs_p, mesh)
        opt = AdamW(moment_dtype=knobs.get("moment_dtype", "float32"))
        opt_state = jax.eval_shape(opt.init, params)
        ospecs = sh.named(
            sh.opt_state_specs(opt_state, pspecs_p, mesh, zero1=True), mesh)
        n_micro = knobs.get("n_micro", 1) if not smoke else 1
        # each microbatch must still divide the data axes or its sharding is
        # dropped wholesale (measured 6x temp blowup on llama multi-pod)
        data_extent = 1
        for a in ("pod", "data"):
            if a in mesh.axis_names:
                data_extent *= mesh.shape[a]
        while n_micro > 1 and (shape.global_batch // n_micro) % data_extent:
            n_micro //= 2
        import jax.numpy as jnp_
        accum = jnp_.dtype(knobs.get("accum_dtype", "float32"))
        step = make_train_step(cfg, opt, n_micro=n_micro,
                               grad_specs=pspecs_p, accum_dtype=accum)
        return (step, (params, opt_state, batch),
                (pspecs, ospecs, bspecs), (pspecs, ospecs, None), (0, 1))

    if shape.kind == "prefill":
        params = _eval_params(cfg, tiered=SERVE_INT8)
        pspecs = sh.named(sh.param_specs(params, mesh), mesh)
        step = make_prefill_step(cfg)
        return step, (params, batch), (pspecs, bspecs), None, ()

    # decode
    params = _eval_params(cfg, tiered=SERVE_INT8)
    pspecs = sh.named(sh.param_specs(params, mesh), mesh)
    cache = cache_specs(cfg, shape, smoke=smoke)
    cspecs = sh.named(sh.cache_specs(cache, mesh), mesh)
    step = make_decode_step(cfg)
    return (step, (params, cache, batch),
            (pspecs, cspecs, bspecs), None, (1,))


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             smoke: bool = False) -> dict:
    cfg = get_config(arch, smoke=smoke)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
            "kind": shape.kind}
    ok, reason = applicable(cfg, shape)
    if not ok:
        return {**cell, "status": "skipped", "reason": reason}

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        step, args, in_sh, out_sh, donate = build_cell(
            arch, shape_name, mesh, smoke=smoke)
        with jax.set_mesh(mesh):
            jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            ma = compiled.memory_analysis()
            xla_cost = compiled.cost_analysis() or {}
            text = compiled.as_text()
        cost = hlo_cost.analyze(text)       # trip-count-aware (launch/hlo_cost)
        n_chips = mesh.devices.size
        terms = rl.roofline_terms(cost.flops, cost.bytes, cost.wire, n_chips,
                                  rl.model_flops(get_config(arch), shape))
        return {
            **cell, "status": "ok",
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
            "memory": _mem_dict(ma),
            "xla_cost_once_per_comp": {k: xla_cost.get(k)
                                       for k in ("flops", "bytes accessed")},
            "n_collectives": cost.n_collectives,
            "roofline": terms,
        }
    except Exception as e:  # a failure here is a bug in the system
        return {**cell, "status": "error", "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-2000:]}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(ARCHS) + ["all"], default="all")
    ap.add_argument("--shape", choices=list(SHAPES) + ["all"], default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs (CI sanity, not the deliverable)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    pods = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                res = run_cell(arch, shape, mp, smoke=args.smoke)
                tag = f"{arch}_{shape}_{res['mesh']}" + (
                    "_smoke" if args.smoke else "")
                (outdir / f"{tag}.json").write_text(json.dumps(res, indent=1))
                dom = res.get("roofline", {}).get("dominant", "-")
                rf = res.get("roofline", {}).get("roofline_fraction", 0)
                print(f"[{res['status']:7s}] {tag:60s} "
                      f"compile={res.get('compile_s', 0):7.1f}s "
                      f"dom={dom:12s} roofline={rf:.3f}"
                      + (f"  ERR {res.get('error', '')[:120]}"
                         if res["status"] == "error" else ""),
                      flush=True)
                n_fail += res["status"] == "error"
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
