"""Compile the serving steps for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python scripts/aot_compile_steps.py \
        [--arch opt-1.3b] [--shards 4]

Rehearses what ``chip_smoke.py`` runs: the resident plane's monolithic
step and one streamed layer group (the dense streamed plane's hot trace)
are lowered from abstract shapes and compiled by the TPU compiler for one
chip of a ``v5e:2x2`` topology, then their ``memory_analysis`` is printed.
Nothing is allocated at the target widths: parameters come from
``jax.eval_shape``. The abstract argument builders are first checked
against the arguments a real ``opt-tiny`` engine passes, so they cannot
drift from the engine unnoticed.
"""
from __future__ import annotations

import argparse
import functools
import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402

from repro.core import scheduler as sched                   # noqa: E402
from repro.core.erdpe import ExecMode                       # noqa: E402
from repro.core.tiering import (ATTN_FLASH_KEYS, FlashWeight,  # noqa: E402
                                deploy)
from repro.launch.mesh import MODEL_AXIS                    # noqa: E402
from repro.launch.serve import resolve_config               # noqa: E402
from repro.launch.sharding import stream_window_specs, tp_shard_axis  # noqa
from repro.models import dense                              # noqa: E402
from repro.serving import step as step_mod                  # noqa: E402
from repro.serving.engine import flash_attn_copy            # noqa: E402
from repro.serving.kvcache import PagedKVPool               # noqa: E402
from repro.serving.sampler import SampleConfig              # noqa: E402
from repro.store.pagestore import PAGE_BYTES, TILE          # noqa: E402

S = jax.ShapeDtypeStruct
# the shapes chip_smoke.py serves: build_engine's slots and max_seq, one
# 128-lane chunk per slot, a 256 MiB streaming budget as the pool size
SLOTS, MAX_SEQ, CHUNK, POOL_MIB = 4, 256, 128, 256


def _sched_cfg(cfg):
    h = 32                                   # Engine's SchedulerConfig rule
    while cfg.n_heads * cfg.head_dim % h:
        h //= 2
    return sched.SchedulerConfig(column_bytes=cfg.d_model, h=h)


def _raw_params(cfg):
    return jax.eval_shape(lambda k: dense.init(cfg, k), S((2,), jnp.uint32))


def _kv_state(cfg):
    box = []

    def build():
        box.append(PagedKVPool(cfg.n_layers, SLOTS, MAX_SEQ, cfg.n_kv_heads,
                               cfg.head_dim))
        return box[0].device_state()
    state = jax.eval_shape(build)
    return state, box[0].block_tables.shape


def resident_args(cfg):
    """Abstract ``ResidentPlane._step_fn`` arguments."""
    raw = _raw_params(cfg)
    params = jax.eval_shape(lambda p: deploy(p)[0], raw)
    attn_flash = jax.eval_shape(
        lambda p: flash_attn_copy(p, 0.0, 0), raw)
    kv, bt_shape = _kv_state(cfg)
    state = dict(kv, bitmap=S((_sched_cfg(cfg).h,), jnp.int32),
                 prev_cycles=S((), jnp.int32))
    return (params, attn_flash, state, S((SLOTS, CHUNK), jnp.int32),
            S((SLOTS,), jnp.int32), S((SLOTS,), jnp.bool_),
            S(bt_shape, jnp.int32), S((2,), jnp.uint32))


def _page_table(k, n):
    """One layer group's (G=1) page tables for a (K, N) store entry."""
    kt, nt = -(-k // TILE), -(-n // TILE)
    return {"q_tbl": S((1, kt, nt), jnp.int32),
            "p_slots": S((1, -(-(k // 8) * n // PAGE_BYTES)), jnp.int32),
            "s_slots": S((1, -(-4 * n // PAGE_BYTES)), jnp.int32)}


def _local_kn(kn, axis, shards):
    if axis is None:
        return kn
    return tuple(d // shards if i == axis else d for i, d in enumerate(kn))


def group_args(cfg, n_pages, shards=1):
    """Abstract ``StreamedPlane._group_fn`` arguments and
    the window's static (K, N) shapes — shard-LOCAL under ``shards``, with
    ``n_pages`` pool rows per shard."""
    raw = _raw_params(cfg)
    layers = jax.eval_shape(lambda p: deploy(p)[0], raw)["layers"]
    layers_dram = {k: {n: w for n, w in v.items()
                       if not isinstance(w, FlashWeight)}
                   if isinstance(v, dict) else v for k, v in layers.items()}
    shapes = {"ffn": {n: _local_kn(tuple(w.q.shape[1:]),
                                   tp_shard_axis(f"layers/ffn/{n}@0"), shards)
                      for n, w in layers["ffn"].items()
                      if isinstance(w, FlashWeight)},
              "attn": {n: tuple(raw["layers"]["attn"][n].shape[1:])
                       for n in ATTN_FLASH_KEYS}}
    window = {part: {n: _page_table(*kn)
                     for n, kn in shapes[part].items()} for part in shapes}
    kv, bt_shape = _kv_state(cfg)
    args = (layers_dram, window, S((shards * n_pages, PAGE_BYTES), jnp.int8),
            kv["k"], kv["v"], S((SLOTS, CHUNK, cfg.d_model), jnp.bfloat16),
            S((SLOTS, CHUNK), jnp.int32), S((SLOTS,), jnp.int32),
            S(bt_shape, jnp.int32), S((_sched_cfg(cfg).h,), jnp.int32),
            S((), jnp.int32))
    return args, shapes


def _signature(tree):
    return jax.tree.map(lambda a: (tuple(a.shape), jnp.dtype(a.dtype)), tree)


def check_against_engine():
    """The builders above must match what a real opt-tiny engine passes."""
    from repro.launch.serve import build_engine
    kw = {"admission_cfg": sched.AdmissionConfig(chunk_tokens=CHUNK),
          "sample_cfg": SampleConfig()}
    seen = {}

    def spy(name, fn):
        def call(*args):
            seen.setdefault(name, args)
            return fn(*args)
        return call

    cfg = resolve_config("opt-tiny")
    eng = build_engine("opt-tiny", **kw)
    eng.plane._step_fn = spy("step", eng.plane._step_fn)
    eng.submit([1, 2, 3], max_new=1)
    eng.run()
    want = _signature(resident_args(cfg))
    assert _signature(seen["step"]) == want, "resident args drifted"
    eng = build_engine("opt-tiny", stream=True, **kw)
    eng.plane._group_fn = spy("group", eng.plane._group_fn)
    eng.submit([1, 2, 3], max_new=1)
    eng.run()
    got = seen["group"]
    args, shapes = group_args(cfg, got[2].shape[0])
    assert _signature(got) == _signature(args), "group args drifted"
    assert shapes == eng.plane._win_shapes, "window shapes drifted"


def _on(sharding, tree):
    return jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=sharding),
                        tree)


def compile_for_chip(name, fn, args, donate=(), **jit_kw):
    t0 = time.perf_counter()
    compiled = jax.jit(fn, donate_argnums=donate, **jit_kw).lower(
        *args).compile()
    ma = compiled.memory_analysis()
    print(f"{name}: compiled in {time.perf_counter() - t0:.1f}s; "
          f"args {ma.argument_size_in_bytes / 2**30:.3f} GiB, "
          f"temp {ma.temp_size_in_bytes / 2**30:.3f} GiB, "
          f"output {ma.output_size_in_bytes / 2**30:.3f} GiB", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arch", default="opt-1.3b")
    ap.add_argument("--shards", type=int, default=0,
                    help="also compile the tensor-parallel streamed group "
                         "over this many chips of the described host")
    args = ap.parse_args()
    check_against_engine()
    print("abstract argument builders match a real opt-tiny engine")

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    cfg = resolve_config(args.arch)
    print(f"{args.arch}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size} -> "
          f"{topo.devices[0].device_kind}")
    step = functools.partial(
        step_mod._step_impl, cfg, _sched_cfg(cfg), SampleConfig(), False,
        ExecMode.XLA, False, None, None)
    compile_for_chip("resident step", step, _on(chip, resident_args(cfg)),
                     donate=(2,))
    n_pages = POOL_MIB * 2**20 // PAGE_BYTES
    gargs, shapes = group_args(cfg, n_pages)
    group = functools.partial(step_mod._stream_group_impl, cfg, ExecMode.XLA,
                              False, 1, shapes)
    compile_for_chip("streamed group", group, _on(chip, gargs))
    if args.shards:
        compile_sharded_group(cfg, topo, args.shards, n_pages)


def compile_sharded_group(cfg, topo, shards, n_pages):
    """The streamed group under ``shard_map`` over a "model" mesh of
    described chips, exactly as ``StreamedPlane._build_programs`` wraps it."""
    from jax.sharding import Mesh, NamedSharding
    mesh = Mesh(np.array(topo.devices[:shards]), (MODEL_AXIS,))
    specs = stream_window_specs(mesh)
    rspec, pspec = specs["replicated"], specs["pool"]
    gargs, shapes = group_args(cfg, n_pages // shards, shards=shards)
    in_specs = (rspec, rspec, pspec) + (rspec,) * 8
    gargs = tuple(_on(NamedSharding(mesh, sp), a)
                  for sp, a in zip(in_specs, gargs))
    group = jax.shard_map(
        functools.partial(step_mod._stream_group_impl, cfg, ExecMode.XLA,
                          False, 1, shapes, axis_name=MODEL_AXIS),
        mesh=mesh, in_specs=in_specs, out_specs=rspec, check_vma=False)
    compile_for_chip(f"streamed group x{shards} shards", group, gargs,
                     out_shardings=NamedSharding(mesh, rspec))


if __name__ == "__main__":
    main()
