"""Sharded page store + tensor-parallel streamed serving (ISSUE 7).

The partitioner properties run everywhere; the mesh-parallel tests need 4
devices and skip unless the host supplies them (CI forces virtual CPU
devices via ``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.paper_models import OPT_TINY
from repro.core.scheduler import shard_planes
from repro.core.tiering import encode_flash
from repro.launch.mesh import make_model_mesh
from repro.launch.sharding import tp_shard_axis
from repro.serving.engine import Engine
from repro.store import PageStore, StreamConfig, WeightPagePool
from repro.store.page_pool import ShardedWeightPagePool
from repro.store.pagestore import shard_tiles
from tests.hyp_compat import given, settings, st

MAX_SEQ = 96
N_DEV = len(jax.devices())
needs_mesh = pytest.mark.skipif(
    N_DEV < 4, reason="needs 4 devices (XLA_FLAGS="
                      "--xla_force_host_platform_device_count=4)")


# --- shard partitioner properties ----------------------------------------


@settings(max_examples=50, deadline=None)
@given(kt=st.integers(1, 8), nt=st.integers(1, 8),
       s=st.sampled_from([1, 2, 4]), axis=st.sampled_from([0, 1]))
def test_shard_tiles_exact_cover(kt, nt, s, axis):
    """Every tile lands in exactly one shard; shard loads are equal."""
    grid = (kt * s, nt) if axis == 0 else (kt, nt * s)
    parts, local = shard_tiles(grid, s, axis)
    assert len(parts) == s
    flat = np.concatenate(parts)
    assert sorted(flat.tolist()) == list(range(grid[0] * grid[1]))
    assert all(len(p) == len(parts[0]) for p in parts)
    assert local == ((grid[0] // s, grid[1]) if axis == 0
                     else (grid[0], grid[1] // s))


def test_shard_tiles_rejects_uneven():
    with pytest.raises(ValueError, match="divisible"):
        shard_tiles((3, 4), 2, 0)
    with pytest.raises(ValueError, match="axis"):
        shard_tiles((4, 4), 2, 2)


@settings(max_examples=10, deadline=None)
@given(kt=st.integers(1, 3), nt=st.integers(1, 3),
       s=st.sampled_from([2, 4]), seed=st.integers(0, 2**31 - 1))
def test_shard_entry_partitions_pages(kt, nt, s, seed):
    """ShardPlan properties over real store entries: the q pages are an
    exact disjoint cover, per-shard byte balance is exact (equal page
    counts — within one page of ideal trivially), and the parity/scale
    runs split with their tiles."""
    k, n = kt * 128, nt * 128 * s                 # divisible on axis 1
    w = jax.random.normal(jax.random.PRNGKey(seed), (k, n), jnp.float32)
    store = PageStore(n_planes=8)
    store.put("w", encode_flash(w, rber=1e-3, seed=seed))
    plan = store.shard_entry("w", s, 1)
    assert plan.axis == 1 and plan.n_shards == s
    assert plan.kn == (k, n) and plan.local_kn == (k, n // s)
    allp = np.concatenate(plan.q_pages)
    assert sorted(allp.tolist()) == \
        sorted(np.asarray(store.table["w"]["q"].pages).tolist())
    assert all(len(p) == len(plan.q_pages[0]) for p in plan.q_pages)
    # byte runs follow their tiles
    comp = store.table["w"]
    assert plan.parity_nbytes * s == comp["parity"].nbytes
    assert plan.scale_nbytes * s == comp["scale"].nbytes
    # host slices reassemble the full parity run: tile column c of the
    # full array is local column c // s on shard c % s (round-robin)
    slices = store.shard_host_slices("w", plan)
    full = store._get_flat(comp["parity"])
    cols = [slices[c % s][0].reshape(k // 8, n // s)
            [:, (c // s) * 128:(c // s + 1) * 128]
            for c in range(n // 128)]
    np.testing.assert_array_equal(np.concatenate(cols, axis=1), full)


def test_shard_entry_fallback_replicates():
    """A dim that cannot split into whole 128-tile columns replicates:
    every shard stages the full entry."""
    w = jnp.ones((128, 192), jnp.float32)         # 192 % 128 != 0
    store = PageStore(n_planes=8)
    store.put("w", encode_flash(w, rber=0.0, seed=0))
    plan = store.shard_entry("w", 4, 1)
    assert plan.axis is None
    assert plan.local_kn == (128, 192)
    for p in plan.q_pages:
        assert sorted(p.tolist()) == \
            sorted(np.asarray(store.table["w"]["q"].pages).tolist())


@settings(max_examples=10, deadline=None)
@given(s=st.sampled_from([1, 2, 4]))
def test_save_open_roundtrip_preserves_partition(s, tmp_path_factory):
    """The round-robin partition survives save/open: the reopened store
    hands back the identical ShardPlan and page bytes."""
    path = str(tmp_path_factory.mktemp("img") / "die.img")
    w = jax.random.normal(jax.random.PRNGKey(s), (128, 512), jnp.float32)
    store = PageStore(n_planes=8)
    store.put("w", encode_flash(w, rber=1e-3, seed=s))
    plan = store.shard_entry("w", s, 1)
    store.save(path, n_shards=s)
    re = PageStore.open(path, n_shards=s)
    rplan = re.shard_entry("w", s, 1)
    assert (rplan.axis, rplan.kn, rplan.local_kn, rplan.local_grid) == \
        (plan.axis, plan.kn, plan.local_kn, plan.local_grid)
    for a, b in zip(rplan.q_pages, plan.q_pages):
        np.testing.assert_array_equal(a, b)
    for pg in np.concatenate(plan.q_pages):
        np.testing.assert_array_equal(re.read_pages([int(pg)]),
                                      store.read_pages([int(pg)]))


def test_open_rejects_shard_mismatch(tmp_path):
    path = str(tmp_path / "die.img")
    store = PageStore(n_planes=8)
    store.put("w", encode_flash(jnp.ones((128, 128)), rber=0.0, seed=0))
    store.save(path, n_shards=2)
    with pytest.raises(ValueError, match="n_shards=2.*n_shards=4"):
        PageStore.open(path, n_shards=4)
    # unsharded images serve any mesh: the partition is computed late
    store.save(path, n_shards=1)
    assert PageStore.open(path, n_shards=4).n_shards == 4


def test_save_validates_plane_group_divisibility(tmp_path):
    store = PageStore(n_planes=8)
    store.put("w", encode_flash(jnp.ones((128, 128)), rber=0.0, seed=0))
    with pytest.raises(ValueError, match="plane-group"):
        store.save(str(tmp_path / "die.img"), n_shards=3)
    with pytest.raises(ValueError, match="plane-group"):
        shard_planes(8, 5)
    assert shard_planes(8, 4).shape == (4, 2)


# --- pinned staging (satellite: transfer path) ---------------------------


def test_staging_buffer_grows_geometrically():
    """The reusable host staging buffer doubles instead of reallocating
    per transfer (on CPU the upload path never arms it, so exercise
    ``_stage_host`` directly)."""
    store = PageStore(n_planes=4)
    store.put("w", encode_flash(jnp.ones((128, 128)), rber=0.0, seed=0))
    pool = WeightPagePool(store, 8)
    a = pool._stage_host(4)
    assert a.shape == (4, store.page_bytes) and pool.staging_allocs == 1
    b = pool._stage_host(3)               # fits: same buffer, no realloc
    assert b.base is a.base or b is a or pool.staging_allocs == 1
    c = pool._stage_host(6)               # grows to max(6, 2*4) = 8 rows
    assert pool.staging_allocs == 2
    assert pool._staging.shape[0] == 8
    d = pool._stage_host(8)               # exactly capacity: reuse
    assert pool.staging_allocs == 2
    del c, d
    assert pool.stats()["pool_staging_allocs"] == 2


def test_accelerator_without_pinned_space_raises(monkeypatch):
    """Off the CPU, a device with no pinned_host memory space is an error:
    the pinned staging path is never silently downgraded."""
    from repro.store import page_pool

    class Device:
        device_kind = "accelerator without pinned host memory"

        def addressable_memories(self):
            return [type("Memory", (), {"kind": "device"})()]

    monkeypatch.setattr(page_pool.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(page_pool.jax, "local_devices", lambda: [Device()])
    with pytest.raises(RuntimeError, match="no pinned_host memory space"):
        page_pool.pinned_host_sharding()


def test_pinned_staging_upload_reconstructs_pages():
    """The accelerator transfer path — host rows bounced through a
    pinned_host buffer, then moved to device memory — armed by hand on
    the CPU backend, which also exposes a pinned_host space."""
    from jax.sharding import SingleDeviceSharding
    store = PageStore(n_planes=4)
    store.put("w", encode_flash(jnp.ones((128, 256)), rber=1e-3, seed=1))
    pool = WeightPagePool(store, 2 * store.entry_pages("w"), donate=True)
    pool._pinned = SingleDeviceSharding(jax.devices()[0],
                                        memory_kind="pinned_host")
    for _ in range(2):                    # second upload reuses the rows
        tbl = pool.upload(["w"])["w"]
        buf = np.asarray(pool.buffer).astype(np.uint8)
        got = buf[np.asarray(tbl["q_tbl"]).reshape(-1)]
        np.testing.assert_array_equal(
            got, store.read_pages(np.asarray(store.table["w"]["q"].pages)))
        pool.free(tbl["slots"])
    st = pool.stats()
    assert st["pool_pinned_uploads"] == st["pool_uploads"] == 2
    assert st["pool_staging_allocs"] == 1
    assert pool.buffer.sharding.memory_kind == "device"


def test_cpu_fallback_keeps_upload_correct():
    """On the CPU backend there is no pinned_host space: the pinned
    counter stays zero, the one-shot device_put path serves, and the
    uploaded bytes still reconstruct the store pages exactly."""
    store = PageStore(n_planes=4)
    store.put("w", encode_flash(jnp.ones((128, 256)), rber=1e-3, seed=1))
    pool = WeightPagePool(store, store.entry_pages("w"))
    tbl = pool.upload(["w"])["w"]
    if jax.default_backend() == "cpu":
        assert pool.stats()["pool_pinned_uploads"] == 0
    pages = np.asarray(store.table["w"]["q"].pages)
    buf = np.asarray(pool.buffer).astype(np.uint8)
    got = buf[np.asarray(tbl["q_tbl"]).reshape(-1)]
    np.testing.assert_array_equal(got, store.read_pages(pages))


# --- mesh-parallel planes (4 virtual devices) ----------------------------


def _tp_ffn_reference(x, w_gate_fw, w_down_fw):
    from repro.kernels import ops
    y = ops.ecdp_matmul_xla(x, w_gate_fw.q, w_gate_fw.parity,
                            w_gate_fw.scale, ecc_enabled=True)
    return ops.ecdp_matmul_xla(y, w_down_fw.q, w_down_fw.parity,
                               w_down_fw.scale, ecc_enabled=True)


@needs_mesh
@pytest.mark.parametrize("rber", [0.0, 2e-3])
def test_paged_ffn_psum_parity(rber):
    """The canonical 1-collective TP FFN over the SHARDED pool: gate
    column-parallel (no collective), down row-parallel closed by one psum
    — bit-comparable to the resident ECDP chain under rber+ECC."""
    from jax.sharding import PartitionSpec as P
    from repro.kernels.paged_ffn import paged_ecdp_matmul_xla

    k, dff = 128, 512
    wg = jax.random.normal(jax.random.PRNGKey(0), (k, dff), jnp.float32)
    wd = jax.random.normal(jax.random.PRNGKey(1), (dff, k), jnp.float32)
    gfw = encode_flash(wg, rber=rber, seed=0)
    dfw = encode_flash(wd, rber=rber, seed=1)
    store = PageStore(n_planes=8)
    store.put("gate", gfw)
    store.put("down", dfw)
    mesh = make_model_mesh(4)
    axis_of = {"gate": 1, "down": 0}.get
    pool = ShardedWeightPagePool(
        store, (store.entry_pages("gate") + store.entry_pages("down")) // 4,
        mesh, axis_of=axis_of)
    tbls = pool.upload(["gate", "down"])
    g, d = tbls["gate"], tbls["down"]
    kn_g = pool.plan("gate").local_kn
    kn_d = pool.plan("down").local_kn

    def body(x, buf):
        y = paged_ecdp_matmul_xla(x, buf, jnp.asarray(g["q_tbl"]),
                                  jnp.asarray(g["p_slots"]),
                                  jnp.asarray(g["s_slots"]), kn_g)
        return paged_ecdp_matmul_xla(y, buf, jnp.asarray(d["q_tbl"]),
                                     jnp.asarray(d["p_slots"]),
                                     jnp.asarray(d["s_slots"]), kn_d,
                                     axis_name="model")

    x = jax.random.normal(jax.random.PRNGKey(2), (4, k), jnp.float32)
    fn = jax.jit(jax.shard_map(body, mesh=mesh,
                               in_specs=(P(), P("model", None)),
                               out_specs=P(), check_vma=False))
    out = pool.dispatch(lambda buf: fn(x, buf))
    want = _tp_ffn_reference(x, gfw, dfw)
    # per-shard partials are bit-exact (int8 + ECC corrections are local);
    # the one psum reassociates the f32 K-sum, so allow summation noise
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-3)
    assert pool.stats()["pool_shard_transfers"] == 4  # one per shard


@needs_mesh
def test_sharded_dense_engine_token_parity():
    """StreamConfig(n_shards=4) serves greedy-token-identical to the
    single-device streamed engine, with a quarter of the window bytes per
    device and one staged transfer per shard per rotation."""
    from repro.models import dense
    params = dense.init(OPT_TINY, jax.random.PRNGKey(0))
    prompts = [list(range(1, 30)), [9, 8]]

    def run(n_shards):
        eng = Engine(OPT_TINY, params, max_slots=2, max_seq=MAX_SEQ,
                     rber=0.0, weight_store=PageStore(n_planes=8),
                     stream_cfg=StreamConfig(n_shards=n_shards))
        for p in prompts:
            eng.submit(p, max_new=8)
        toks = eng.run()
        return toks, eng.stream_stats(), eng.step_traces

    t1, st1, tr1 = run(1)
    t4, st4, tr4 = run(4)
    assert t4 == t1                                  # greedy parity
    assert tr4 == tr1                                # no trace churn
    assert st4["pool_shards"] == 4
    assert st4["pool_shard_transfers"] == 4 * st4["pool_uploads"]
    # each device holds ~1/4 of the unsharded pool (attn replicates, so
    # allow headroom above the exact quarter)
    assert st4["pool_local_pages"] < st1["pool_pages"]


@needs_mesh
def test_sharded_moe_engine_token_parity():
    """The expert-paged MoE plane under 4 shards: routed experts fetch
    only their shard's pages on each device, tokens stay identical."""
    from repro.models import moe
    cfg = dataclasses.replace(get_config("qwen3-moe-30b-a3b", smoke=True),
                              d_ff=512)
    params = moe.init(cfg, jax.random.PRNGKey(0))
    prompts = [list(range(1, 20)), [9, 8, 7]]

    def run(n_shards):
        eng = Engine(cfg, params, max_slots=2, max_seq=MAX_SEQ, rber=0.0,
                     weight_store=PageStore(n_planes=8),
                     stream_cfg=StreamConfig(n_shards=n_shards))
        for p in prompts:
            eng.submit(p, max_new=8)
        toks = eng.run()
        st_ = eng.expert_stats()
        eng.close()
        return toks, st_, eng.step_traces

    t1, _, tr1 = run(1)
    t4, st4, tr4 = run(4)
    assert t4 == t1
    assert tr4 == tr1 == 3                     # head+fused+tail steady state
    assert st4["pool_shards"] == 4
    assert st4["pool_shard_transfers"] == 4 * st4["pool_uploads"]


@needs_mesh
def test_sharded_rejects_unshardable_ffn():
    """d_ff too small for whole-tile columns per shard must fail LOUDLY at
    init (a silent replicate would double-count the FFN psum)."""
    cfg = get_config("qwen3-moe-30b-a3b", smoke=True)   # d_ff=32 < 128*4
    from repro.models import moe
    params = moe.init(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="cannot partition"):
        Engine(cfg, params, max_slots=2, max_seq=MAX_SEQ, rber=0.0,
               weight_store=PageStore(n_planes=8),
               stream_cfg=StreamConfig(n_shards=4))


def test_tp_shard_axis_rules():
    assert tp_shard_axis("layers/ffn/w_gate") == 1
    assert tp_shard_axis("layers/ffn/w_up@3") == 1
    assert tp_shard_axis("layers/ffn/w_down") == 0
    assert tp_shard_axis("layers/moe/experts/w_gate@1.5") == 1
    assert tp_shard_axis("layers/moe/experts/w_down") == 0
    # Alg.2 attention copies stream replicated on every shard's pool
    assert tp_shard_axis("attn_flash/wq@3") is None
    assert tp_shard_axis("layers/moe/router") is None
    # lm_head follows the training rule (column-parallel) but never
    # enters the pool — the engine serves it replicated from DRAM
    assert tp_shard_axis("lm_head") == 1
