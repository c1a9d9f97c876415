"""Compile a streamed configuration's step programs for a described TPU v5e.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/aot_stream.py \
        [--layers 8] [--budget-groups 2.5]

Rehearses, without a chip, the three programs the streamed plane runs
each step at OPT-13B's published widths (embed, one layer group, finish),
with the depth cut to ``--layers``, lowered from abstract shapes and compiled by the TPU compiler for
one chip of a ``v5e:2x2`` topology, and prints each one's
``memory_analysis``. Nothing is allocated at the target widths. It also
prints the set-up reckoning from shapes: the bf16 parameters the
benchmark makes, the f32 transient of encoding the largest stack, the
flash tier the page store holds, and the device weight budget.
"""
from __future__ import annotations

import argparse
import functools
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import counts  # noqa: E402
from bench.arch import opt  # noqa: E402

S = jax.ShapeDtypeStruct
GIB = 2**30
# facebook/opt-13b's published widths; the engine as the benchmark's cells
# build it, at 4 slots of 1024 positions
OPT_13B = {"name": "opt-13b", "hidden_size": 5120, "ffn_dim": 20480,
           "num_attention_heads": 40, "num_hidden_layers": 40,
           "vocab_size": 50272, "max_position_embeddings": 2048}
SLOTS, MAX_SEQ, KV_AWARE = 4, 1024, True


def window_bytes(sizes: dict) -> int:
    """Flash bytes one streamed layer window carries: the FFN and the int8
    flash copies of the four attention projections."""
    d, f = sizes["hidden_size"], sizes["ffn_dim"]
    return (counts._flash_matrix_bytes(d, f) + counts._flash_matrix_bytes(f, d)
            + 4 * counts._flash_matrix_bytes(d, d))


def _on(sharding, tree):
    return jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=sharding),
                        tree)


def _compile(name, fn, args, donate=()):
    t0 = time.perf_counter()
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    ma = compiled.memory_analysis()
    print(f"{name}: compiled in {time.perf_counter() - t0:.1f} s; args "
          f"{ma.argument_size_in_bytes / GIB:.3f} GiB, temp "
          f"{ma.temp_size_in_bytes / GIB:.3f} GiB, output "
          f"{ma.output_size_in_bytes / GIB:.3f} GiB", flush=True)


def main():
    from repro.core import scheduler as sched
    from repro.core.erdpe import ExecMode
    from repro.core.tiering import ATTN_FLASH_KEYS, FlashWeight, deploy
    from repro.models import dense
    from repro.serving import engine as eng_mod
    from repro.serving.kvcache import PagedKVPool
    from repro.serving.sampler import SampleConfig
    from repro.store.pagestore import PAGE_BYTES, TILE

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--budget-groups", type=float, default=2.5,
                    help="device weight budget in layer windows, besides "
                    "the pinned lm_head")
    args = ap.parse_args()
    sizes = dict(OPT_13B, num_hidden_layers=args.layers)
    cfg = opt.program_config(sizes)
    slots, max_seq = SLOTS, MAX_SEQ
    chunk = sched.AdmissionConfig().chunk_tokens
    d, f, v, n_l = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_layers

    group_b = window_bytes(sizes)
    lm_b = counts._flash_matrix_bytes(d, v)
    budget = int(args.budget_groups * group_b + lm_b)
    params_b = 2 * (n_l * (4 * d * d + 2 * d * f + 4 * d) + 2 * v * d
                    + sizes["max_position_embeddings"] * d + 2 * d)
    print(f"opt-13b widths: {n_l} layers, d_model {d}, d_ff {f}, vocab {v}")
    print(f"reckoning: bf16 params {params_b / GIB:.3f} GiB; f32 copy of "
          f"one ({n_l}, {d}, {f}) FFN stack {4 * n_l * d * f / GIB:.3f} GiB;"
          f" flash tier in the store {(n_l * group_b + lm_b) / GIB:.3f} GiB"
          f" ({group_b / GIB:.3f} GiB a layer window, lm_head "
          f"{lm_b / GIB:.3f} GiB); device weight budget "
          f"{budget / GIB:.3f} GiB")

    h = 32
    while cfg.n_heads * cfg.head_dim % h:
        h //= 2
    sched_cfg = sched.SchedulerConfig(column_bytes=d, h=h)
    raw = jax.eval_shape(lambda k: dense.init(cfg, k), S((2,), jnp.uint32))
    tiered = jax.eval_shape(lambda p: deploy(p)[0], raw)
    layers_dram = {k: {n: w for n, w in val.items()
                       if not isinstance(w, FlashWeight)}
                   if isinstance(val, dict) else val
                   for k, val in tiered["layers"].items()}
    box = []

    def kv_build():
        box.append(PagedKVPool(n_l, slots, max_seq, cfg.n_kv_heads,
                               cfg.head_dim))
        return box[0].device_state()
    kv = jax.eval_shape(kv_build)
    bt = S(box[0].block_tables.shape, jnp.int32)

    def table(k, n):
        kt, nt = -(-k // TILE), -(-n // TILE)
        return {"q_tbl": S((1, kt, nt), jnp.int32),
                "p_slots": S((1, -(-(k // 8) * n // PAGE_BYTES)), jnp.int32),
                "s_slots": S((1, -(-4 * n // PAGE_BYTES)), jnp.int32)}

    shapes = {"ffn": {"w_up": (d, f), "w_down": (f, d)},
              "attn": {n: (d, d) for n in ATTN_FLASH_KEYS}}
    window = {part: {n: table(*kn) for n, kn in shapes[part].items()}
              for part in shapes}
    group_pages = sum(int(jnp.prod(jnp.array(t["q_tbl"].shape)))
                      + t["p_slots"].shape[1] + t["s_slots"].shape[1]
                      for part in window.values() for t in part.values())
    n_pages = -(-budget // PAGE_BYTES) + 3 * group_pages

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    print(f"target: {topo.devices[0].device_kind}; pool {n_pages} pages "
          f"({n_pages * PAGE_BYTES / GIB:.3f} GiB)")
    dram = {k: tiered[k] for k in ("embed", "pos_embed", "final_norm")}
    x = S((slots, chunk, d), jnp.bfloat16)
    i32 = functools.partial(S, dtype=jnp.int32)
    _compile("embed", functools.partial(eng_mod._embed_chunk, cfg),
             _on(chip, (dram, i32((slots,)), i32((slots, chunk)),
                        i32((slots,)))))
    group = functools.partial(eng_mod._stream_group_impl, cfg, ExecMode.XLA,
                              KV_AWARE, 1,
                              shapes)
    _compile("group", group, _on(chip, (
        layers_dram, window, S((n_pages, PAGE_BYTES), jnp.int8), kv["k"],
        kv["v"], x, i32((slots, chunk)), i32((slots,)), bt,
        i32((sched_cfg.h,)), i32(()))))
    state = dict(kv, bitmap=i32((sched_cfg.h,)), prev_cycles=i32(()))
    kv_new = S((n_l, slots, chunk, cfg.n_kv_heads, cfg.head_dim),
               jnp.bfloat16)
    finish = functools.partial(eng_mod._finish_step, cfg, sched_cfg,
                               SampleConfig(temperature=0.0),
                               KV_AWARE, None)
    _compile("finish", finish, _on(chip, (
        tiered["final_norm"], tiered["lm_head"], state, x, kv_new, kv_new,
        i32((slots,)), S((slots,), jnp.bool_), i32((slots, chunk)), bt,
        S((2,), jnp.uint32))), donate=(2,))


if __name__ == "__main__":
    main()
