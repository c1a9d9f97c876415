"""Paged-weight ECDP: the pool-backed kernel/fallback against the resident
ERDPE — the parity chain the streamed engines now rest on.

The weight never leaves its raw 16 KiB store pages: ``WeightPagePool``
uploads them, and the paged matmul (Pallas scalar-prefetch kernel or XLA
gather fallback) consumes them in place through the page table. Every test
here pins that against the RESIDENT path (``ecdp_matmul_xla`` over the
original FlashWeight): same bytes, same math, same corrections.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.erdpe import ExecMode, flash_matmul
from repro.core.tiering import PagedWeight, encode_flash
from repro.kernels import ops
from repro.kernels.paged_ffn import (gather_parity, gather_q, gather_scale,
                                     paged_ecdp_matmul_xla)
from repro.store import PageStore, WeightPagePool


def _paged(key, k, n, rber=0.0, n_pages=None):
    """One (K, N) weight: resident FlashWeight + its pool-paged twin."""
    w = jax.random.normal(key, (k, n), jnp.float32)
    fw = encode_flash(w, rber=rber, seed=3)
    store = PageStore(n_planes=4)
    store.put("w", fw)
    pool = WeightPagePool(store, n_pages or store.entry_pages("w"))
    tbl = pool.upload(["w"])["w"]
    pw = PagedWeight(pool=pool.buffer, q_tbl=jnp.asarray(tbl["q_tbl"]),
                     p_slots=jnp.asarray(tbl["p_slots"]),
                     s_slots=jnp.asarray(tbl["s_slots"]), kn=(k, n))
    return fw, pw, pool


SHAPES = [(1, 128, 128), (4, 256, 128), (3, 200, 72), (8, 64, 384),
          (5, 640, 256)]


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_gathers_rebuild_resident_arrays(m, k, n):
    """The page-table gathers reproduce the exact resident q/parity/scale
    arrays — detiling and flat-run slicing agree with the store's layout."""
    fw, pw, pool = _paged(jax.random.PRNGKey(m + k + n), k, n, rber=1e-3)
    q = gather_q(pw.pool, pw.q_tbl, k, n)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(fw.q))
    par = gather_parity(pw.pool, pw.p_slots, k, n)
    np.testing.assert_array_equal(np.asarray(par), np.asarray(fw.parity))
    sc = gather_scale(pw.pool, pw.s_slots, n)
    np.testing.assert_allclose(np.asarray(sc).reshape(-1),
                               np.asarray(fw.scale).reshape(-1))


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("rber", [0.0, 2e-3])
def test_xla_fallback_matches_resident(m, k, n, rber):
    fw, pw, _ = _paged(jax.random.PRNGKey(7 * m + k + n), k, n, rber=rber)
    a = jax.random.normal(jax.random.PRNGKey(1), (m, k), jnp.float32)
    out = paged_ecdp_matmul_xla(a, pw.pool, pw.q_tbl, pw.p_slots,
                                pw.s_slots, (k, n))
    want = ops.ecdp_matmul_xla(a, fw.q, fw.parity, fw.scale,
                                ecc_enabled=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("rber", [0.0, 2e-3])
def test_pallas_kernel_matches_resident(m, k, n, rber):
    """The scalar-prefetch Pallas kernel (interpret on CPU) — block-table
    index map reading the page table directly — against the resident ECDP,
    corrections included."""
    fw, pw, _ = _paged(jax.random.PRNGKey(11 * m + k + n), k, n, rber=rber)
    a = jax.random.normal(jax.random.PRNGKey(2), (m, k), jnp.float32)
    out = ops.paged_ecdp_matmul(a, pw.pool, pw.q_tbl, pw.p_slots,
                                pw.s_slots, (k, n))
    want = ops.ecdp_matmul_xla(a, fw.q, fw.parity, fw.scale,
                                ecc_enabled=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("mode", [ExecMode.XLA, ExecMode.PALLAS])
def test_flash_matmul_dispatches_paged(mode):
    """erdpe.flash_matmul serves a PagedWeight through either path and
    restores leading batch dims like the FlashWeight path."""
    k, n = 192, 80
    fw, pw, _ = _paged(jax.random.PRNGKey(0), k, n, rber=1e-3)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 3, k), jnp.float32)
    out = flash_matmul(x, pw, mode=mode)
    want = flash_matmul(x, fw, mode=ExecMode.XLA)
    assert out.shape == (2, 3, n)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-1)


def test_stacked_paged_weight_rejected():
    fw, pw, pool = _paged(jax.random.PRNGKey(5), 128, 128)
    stacked = PagedWeight(pool=pw.pool, q_tbl=pw.q_tbl[None],
                          p_slots=pw.p_slots[None],
                          s_slots=pw.s_slots[None], kn=(128, 128))
    assert stacked.lead == (1,)
    with pytest.raises(ValueError, match="PagedWeight"):
        flash_matmul(jnp.ones((2, 128)), stacked)


def test_moe_expert_slab_parity():
    """A pool-paged expert slab (streamed plane) reads the same int8
    weights and scales as the resident FlashWeight bank, row for row —
    bank composition must not change math."""
    from repro.models.moe import _slab_weight
    e, k, n = 3, 128, 64
    ws = [jax.random.normal(jax.random.PRNGKey(i), (k, n), jnp.float32)
          for i in range(e)]
    fws = [encode_flash(w, rber=1e-3, seed=i) for i, w in enumerate(ws)]
    store = PageStore(n_planes=4)
    for i, fw in enumerate(fws):
        store.put(f"w{i}", fw)
    pool = WeightPagePool(store, sum(store.entry_pages(f"w{i}")
                                     for i in range(e)))
    tbls = pool.upload([f"w{i}" for i in range(e)])
    pw = PagedWeight(
        pool=pool.buffer,
        q_tbl=jnp.asarray(np.stack([tbls[f"w{i}"]["q_tbl"]
                                    for i in range(e)])),
        p_slots=jnp.asarray(np.stack([tbls[f"w{i}"]["p_slots"]
                                      for i in range(e)])),
        s_slots=jnp.asarray(np.stack([tbls[f"w{i}"]["s_slots"]
                                      for i in range(e)])),
        kn=(k, n))
    bank = jax.tree.map(lambda *xs: jnp.stack(xs), *fws)
    rows = jnp.asarray([2, 0], jnp.int32)
    q_p, s_p = _slab_weight(pw, rows, ecc_inline=False)
    q_b, s_b = _slab_weight(bank, rows, ecc_inline=False)
    np.testing.assert_array_equal(np.asarray(q_p, np.float32),
                                  np.asarray(q_b, np.float32))
    np.testing.assert_array_equal(np.asarray(s_p), np.asarray(s_b))
