"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e
chip at OPT-1.3B widths (FFN K=2048, N=8192, M=8; attention B=4, KV=32,
Dh=64, 16-token blocks). No chip is needed: the TPU compiler compiles for
a topology that is described, not attached, and refuses what the chip
would refuse — which interpret-mode tests cannot show.

Kernels the compiler refuses today are strict xfails that name the error,
so a fix (an XPASS) or a different refusal fails the suite."""
from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.decode_attn import decode_attn_pallas
from repro.kernels.ecdp import ecdp_matmul_pallas
from repro.kernels.paged_attn import paged_attn_pallas
from repro.kernels.paged_ffn import (TILE, paged_ecdp_matmul_pallas,
                                     paged_ecdp_matmul_xla)
from repro.store.pagestore import PAGE_BYTES

M, K, N = 8, 2048, 8192                         # OPT-1.3B FFN up-projection
B, KV, DH, BLOCK, MAX_SEQ, T = 4, 32, 64, 16, 256, 16
KT, NT = K // TILE, N // TILE
N_PAGES = 2048                                  # >= q + parity + scale pages


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001 - skip cause
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _ffn_paged(ecc_enabled):
    return (functools.partial(paged_ecdp_matmul_xla, kn=(K, N),
                              ecc_enabled=ecc_enabled),
            [((M, K), jnp.float32), ((N_PAGES, PAGE_BYTES), jnp.int8),
             ((KT, NT), jnp.int32), ((K // 8 * N // PAGE_BYTES,), jnp.int32),
             ((-(-4 * N // PAGE_BYTES),), jnp.int32)])


def _ffn_resident(fn, **kw):
    return (functools.partial(fn, **kw),
            [((M, K), jnp.float32), ((K, N), jnp.int8),
             ((K // 8, N), jnp.uint8)]
            + ([((1, N), jnp.float32)] if fn is ops.ecdp_matmul_xla else []))


def _ffn_paged_pallas(ecc_enabled):
    return (functools.partial(paged_ecdp_matmul_pallas, block_m=M,
                              ecc_enabled=ecc_enabled, interpret=False),
            [((M, K), jnp.float32), ((N_PAGES, PAGE_BYTES), jnp.int8),
             ((KT, NT), jnp.int32), ((K // 8, N), jnp.uint8)])


def _paged_attn():
    n_blocks = B * MAX_SEQ // BLOCK + 1                 # + the dump block
    pool = ((2, n_blocks, BLOCK, KV * DH), jnp.bfloat16)  # two layers
    return (functools.partial(paged_attn_pallas, interpret=False),
            [((B, KV, T, DH), jnp.bfloat16), pool, pool, ((), jnp.int32),
             ((B, MAX_SEQ // BLOCK), jnp.int32), ((B,), jnp.int32)])


def _decode_attn():
    pool = ((B, MAX_SEQ, KV, DH), jnp.bfloat16)
    return (functools.partial(decode_attn_pallas, block_s=MAX_SEQ,
                              interpret=False),
            [((B, KV, 1, DH), jnp.bfloat16), pool, pool, ((B,), jnp.int32)])


CASES = {
    "paged_ecdp_matmul_xla_ecc": lambda: _ffn_paged(True),
    "ecdp_matmul_xla_ecc": lambda: _ffn_resident(ops.ecdp_matmul_xla,
                                                 ecc_enabled=True),
    "paged_ecdp_matmul_pallas_no_ecc": lambda: _ffn_paged_pallas(False),
    "paged_attn_pallas": _paged_attn,
}

# name -> the compiler's refusal today (a regex on the error text)
REFUSED = {
    "paged_ecdp_matmul_pallas_ecc": (
        lambda: _ffn_paged_pallas(True),
        "Unimplemented primitive in Pallas TPU lowering .*: reduce"),
    "ecdp_matmul_pallas_ecc": (
        lambda: _ffn_resident(ecdp_matmul_pallas, block_m=M, block_k=512,
                              block_n=512, ecc_enabled=True,
                              interpret=False),
        "Unimplemented primitive in Pallas TPU lowering .*: reduce"),
    "decode_attn_pallas": (
        _decode_attn,
        "requires that rank 1 block shapes"),
}


class KnownRefusal(Exception):
    """The compiler refused a kernel with the error named in REFUSED."""


def _compile(build, sharding):
    fn, shapes = build()
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("name", sorted(CASES))
def test_main_path_kernel_compiles_for_v5e(name, one_chip):
    compiled = _compile(CASES[name], one_chip)
    if "pallas" in name:
        assert "tpu_custom_call" in compiled.as_text()


# `bytes accessed` of the ecdp_matmul_xla_ecc compile when the check built
# (G,7,8,N) masked bytes, summed them in int32 and placed the flip with a
# (G,64,N) one-hot compare; the one-pass check reads under half of it.
EXPANDED_ECC_BYTES = 470_571_008


def test_ecc_matmul_reads_weight_bytes_once_for_v5e(one_chip):
    compiled = _compile(CASES["ecdp_matmul_xla_ecc"], one_chip)
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] < EXPANDED_ECC_BYTES / 2, cost
    text = compiled.as_text()
    assert not re.search(r"\bu8\[[\d,]*\b7,8\b", text), "(G,7,8,N) bytes"
    assert not re.search(r"\bs32\[[\d,]*\b7,", text), "(G,7,N) int32 sums"


@pytest.mark.parametrize("name", [
    pytest.param(n, marks=pytest.mark.xfail(strict=True, raises=KnownRefusal,
                                            reason=msg))
    for n, (_, msg) in sorted(REFUSED.items())])
def test_kernel_refused_for_v5e(name, one_chip):
    build, msg = REFUSED[name]
    try:
        _compile(build, one_chip)
    except Exception as e:                       # noqa: BLE001 - classified
        if re.search(msg, str(e)):
            raise KnownRefusal(msg) from e
        raise


# --- the Qwen3-MoE serving step at the benchmark cell's sizes ------------------

def _moe_step_args(sharding):
    """Abstract resident-step arguments of ``qwen3-moe-30b-a3b`` as the
    cell serves it: 6 layers, 4 slots, 4096 positions, 16-lane chunks."""
    import dataclasses
    from repro.configs.qwen3_moe_30b_a3b import CONFIG
    from repro.core import scheduler as sched
    from repro.core.tiering import deploy
    from repro.models import moe
    from repro.serving.kvcache import PagedKVPool
    cfg = dataclasses.replace(CONFIG, n_layers=6, max_seq=4096)
    S = jax.ShapeDtypeStruct
    raw = jax.eval_shape(lambda k: moe.init(cfg, k), S((2,), jnp.uint32))
    params = jax.eval_shape(lambda p: deploy(p)[0], raw)
    box = []

    def kv():
        box.append(PagedKVPool(6, 4, 4096, cfg.n_kv_heads, cfg.head_dim))
        return box[0].device_state()
    state = dict(jax.eval_shape(kv), bitmap=S((32,), jnp.int32),
                 prev_cycles=S((), jnp.int32))
    args = (params, None, state, S((4, 16), jnp.int32), S((4,), jnp.int32),
            S((4,), jnp.bool_), S(box[0].block_tables.shape, jnp.int32),
            S((2,), jnp.uint32))
    on = jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=sharding), args)
    return cfg, sched.SchedulerConfig(column_bytes=cfg.d_model, h=32), on


def test_moe_step_compiles_for_v5e_at_cell_sizes(one_chip):
    """The whole resident step (one program: 5 slab bounds per layer under
    ``lax.switch``) compiles for the chip and fits its 16 GB beside the
    deployed model; the decode bound's expert read is a fraction of the
    whole bank's."""
    from repro.core.erdpe import ExecMode
    from repro.models import moe
    from repro.serving import engine as eng_mod
    from repro.serving.sampler import SampleConfig
    cfg, sched_cfg, args = _moe_step_args(one_chip)
    step = functools.partial(eng_mod._step_impl, cfg, sched_cfg,
                             SampleConfig(), True, ExecMode.XLA, False, None,
                             None)
    compiled = eng_mod._jit(step, donate_argnums=(2,)).lower(*args) \
        .compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 8 * 2**30
    assert compiled.as_text().count("ragged-dot") >= 3 * 5
    bank = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape[1:], a.dtype, sharding=one_chip),
        args[0]["layers"]["moe"]["experts"])
    S = jax.ShapeDtypeStruct
    rest = [S((64, 2048), jnp.bfloat16, sharding=one_chip),
            S((512,), jnp.int32, sharding=one_chip),
            S((512,), jnp.bool_, sharding=one_chip),
            S((128,), jnp.int32, sharding=one_chip)]
    read = {}
    for bound in (32, 128):
        fn = functools.partial(moe.routed_ffn, slab_map=None, bound=bound,
                               top_k=8, ecc_inline=True)
        cost = jax.jit(fn).lower(bank, *rest).compile().cost_analysis()
        read[bound] = (cost[0] if isinstance(cost, list)
                       else cost)["bytes accessed"]
    assert read[32] < 0.5 * read[128], read


# --- the resident step keeps the K/V pool in place -----------------------------

def _resident_step(cfg, sharding, slots=4, max_seq=256):
    """The resident ``_step_impl`` compiled as the engine jits it (donated
    state, inline ECC), with its abstract K/V state."""
    from repro.core import scheduler as sched
    from repro.core.erdpe import ExecMode
    from repro.core.tiering import deploy
    from repro.models import dense, moe
    from repro.serving import engine as eng_mod
    from repro.serving.kvcache import PagedKVPool
    from repro.serving.sampler import SampleConfig
    S = jax.ShapeDtypeStruct
    init = moe.init if cfg.family == "moe" else dense.init
    raw = jax.eval_shape(lambda k: init(cfg, k), S((2,), jnp.uint32))
    params = jax.eval_shape(lambda p: deploy(p)[0], raw)
    attn_flash = None if cfg.family == "moe" else jax.eval_shape(
        lambda p: eng_mod.Engine._flash_attn_copy(None, p, 0.0, 0), raw)
    box = []

    def kv():
        box.append(PagedKVPool(cfg.n_layers, slots, max_seq, cfg.n_kv_heads,
                               cfg.head_dim))
        return box[0].device_state()
    kv_state = jax.eval_shape(kv)
    state = dict(kv_state, bitmap=S((32,), jnp.int32),
                 prev_cycles=S((), jnp.int32))
    chunk = sched.AdmissionConfig().chunk_tokens
    args = (params, attn_flash, state, S((slots, chunk), jnp.int32),
            S((slots,), jnp.int32), S((slots,), jnp.bool_),
            S(box[0].block_tables.shape, jnp.int32), S((2,), jnp.uint32))
    args = jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=sharding),
                        args)
    step = functools.partial(
        eng_mod._step_impl, cfg,
        sched.SchedulerConfig(column_bytes=cfg.d_model, h=32),
        SampleConfig(temperature=0.0), True, ExecMode.XLA, False, None, None)
    compiled = eng_mod._jit(step, donate_argnums=(2,)).lower(*args).compile()
    return compiled, kv_state["k"]


def _bf16_ops(text: str):
    """(op kind, element count) of every bfloat16 result in the HLO text."""
    for m in re.finditer(r"%\S+ = bf16\[([\d,]*)\]\{[^}]*\} ([\w-]+)\(",
                         text):
        n = 1
        for d in filter(None, m.group(1).split(",")):
            n *= int(d)
        yield m.group(2), n


def _pool_widths(name):
    """The configuration at its published attention widths, cut to two
    layers and a 4 x 256-position pool; Qwen3's expert count and
    vocabulary are cut as well, which the pool never sees, to keep the
    compile short."""
    import dataclasses
    from repro.configs.paper_models import OPT_1_3B
    from repro.configs.qwen3_moe_30b_a3b import CONFIG
    if name == "opt-1.3b":
        return dataclasses.replace(OPT_1_3B, n_layers=2, max_seq=256)
    return dataclasses.replace(CONFIG, n_layers=2, max_seq=256,
                               n_experts=16, vocab_size=4096)


@pytest.mark.parametrize("name", ["opt-1.3b", "qwen3-moe-30b-a3b"])
def test_resident_step_writes_pool_in_place_for_v5e(name, one_chip):
    """At the cells' attention widths (OPT: 32 KV heads of 64; Qwen3: 4 of
    128; 16-row blocks) the compiled step neither copies the stacked pool
    or a layer of it nor materialises a layer slice, and the donated pool
    comes back unpadded: its bytes are its logical bytes."""
    cfg = _pool_widths(name)
    compiled, pool = _resident_step(cfg, one_chip)
    assert pool.shape[-1] == cfg.n_kv_heads * cfg.head_dim
    whole = pool.size
    layer = whole // pool.shape[0]
    ops_ = list(_bf16_ops(compiled.as_text()))
    assert ops_, "no bfloat16 op parsed from the HLO"
    copies = [(k, n) for k, n in ops_
              if k in ("copy", "copy-start") and n in (whole, layer)]
    assert not copies, f"pool-shaped copies: {copies}"
    slices = [(k, n) for k, n in ops_ if k == "fusion" and n == layer]
    assert not slices, f"fusions output a layer slice: {slices}"
    pool_bytes = 2 * pool.size * jnp.dtype(pool.dtype).itemsize
    out = compiled.memory_analysis().output_size_in_bytes
    assert pool_bytes <= out < pool_bytes + 2**16, (out, pool_bytes)
