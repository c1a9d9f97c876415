"""Routed-only expert reads of the resident MoE plane: the grouped product
over a slab of only the routed experts equals every-expert dispatch at
every slab bound (padding lanes routing nothing), a step gathers exactly
the routed experts' bytes, a flipped bit in a routed expert is corrected
inline, and the step's MoE counters equal a host recount of the routing."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import ArchConfig
from repro.core import ecc
from repro.core.tiering import FlashWeight, deploy, encode_flash
from repro.models import moe
from repro.serving import engine as eng_mod
from repro.serving.engine import Engine
from repro.serving.sampler import SampleConfig

E, D, F, K = 16, 64, 32, 4
S, T = 4, 8                                  # slots x chunk lanes
CFG = ArchConfig(name="qwen3-moe-tiny", family="moe", n_layers=2,
                 d_model=64, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=32,
                 vocab_size=512, qk_norm=True, rope_base=1e6, n_experts=E,
                 top_k=K, max_seq=256)


def _bank(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    raw = {"w_gate": jax.random.normal(ks[0], (E, D, F), jnp.float32) * 0.2,
           "w_up": jax.random.normal(ks[1], (E, D, F), jnp.float32) * 0.2,
           "w_down": jax.random.normal(ks[2], (E, F, D), jnp.float32) * 0.2}
    return {k: encode_flash(v) for k, v in raw.items()}


def _dequant(fw):
    return fw.q.astype(jnp.float32) * fw.scale


def _every_expert(bank, x, gates, idx, valid):
    """Every expert over every token, then each assignment picks its own:
    the full-bank dispatch, with the served FFN's bf16 roundings."""
    w = {k: _dequant(v) for k, v in bank.items()}
    xf = x.astype(jnp.float32)
    hg = jnp.einsum("std,edf->stef", xf, w["w_gate"]).astype(jnp.bfloat16)
    hu = jnp.einsum("std,edf->stef", xf, w["w_up"]).astype(jnp.bfloat16)
    h = (jax.nn.silu(hg.astype(jnp.float32))
         * hu.astype(jnp.float32)).astype(jnp.bfloat16)
    y = jnp.einsum("stef,efd->sted", h.astype(jnp.float32),
                   w["w_down"]).astype(jnp.bfloat16)
    picked = jnp.take_along_axis(y, idx[..., None], axis=2)   # (S,T,k,D)
    out = jnp.sum(picked.astype(jnp.float32) * gates[..., None], axis=2)
    return jnp.where(valid[..., None], out, 0.0)


def _routing(n_routed, seed=1):
    """(x, gates, idx, valid): valid lanes route to exactly ``n_routed``
    distinct experts (cycling through them, so each lane's ids are
    distinct once ``n_routed >= K``); padding lanes route to experts no
    valid lane uses, where any are left."""
    rng = np.random.default_rng(seed)
    valid = np.zeros((S, T), bool)
    valid[0, :6] = valid[1, :1] = valid[3, :3] = True
    used = rng.permutation(E)[:n_routed]
    spare = np.setdiff1d(np.arange(E), used)
    idx = np.zeros((S, T, K), np.int32)
    for i, (s, t) in enumerate(np.argwhere(valid)):
        idx[s, t] = used[(i * K + np.arange(K)) % n_routed]
    for s, t in np.argwhere(~valid):
        pool = spare if spare.size >= K else np.arange(E)
        idx[s, t] = rng.choice(pool, K, replace=False)
    x = jax.random.normal(jax.random.PRNGKey(seed), (S, T, D), jnp.bfloat16)
    gates = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(seed + 1),
                                             (S, T, K)), axis=-1)
    return x, gates, jnp.asarray(idx), jnp.asarray(valid)


@pytest.mark.parametrize("n_routed", [1, 2, 3, 5, 8, 11, 16])
def test_routed_ffn_matches_every_expert_dispatch(n_routed):
    bank = _bank()
    x, gates, idx, valid = _routing(n_routed)
    routed = set(np.asarray(idx)[np.asarray(valid)].ravel().tolist())
    assert len(routed) == n_routed
    got = moe.serve_expert_ffn(bank, x, gates, idx, valid=valid)
    want = _every_expert(bank, x, gates, idx, valid)
    # one bf16 rounding of the output apart at most
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=2 ** -7, atol=1e-6)
    assert not np.any(np.asarray(got)[~np.asarray(valid)])


def test_slab_bounds_and_ids():
    assert moe.slab_bounds(128, 8) == (8, 16, 32, 64, 128)
    assert moe.slab_bounds(12, 4) == (4, 8, 12)
    assert moe.slab_bounds(16, 3) == (4, 8, 16)
    assert moe.slab_bounds(8, 8) == (8,)
    present = jnp.zeros((16,), bool).at[jnp.array([9, 2, 14])].set(True)
    ids = np.asarray(moe.slab_ids(present, 4))
    assert ids[:3].tolist() == [2, 9, 14] and ids[3] not in (2, 9, 14)
    assert np.asarray(moe.slab_ids(present, 16)).tolist() == list(range(16))


@pytest.mark.parametrize("n_routed,bound", [(3, 4), (5, 8), (16, 16)])
def test_step_reads_only_the_routed_experts_bytes(n_routed, bound):
    """What a step gathers is the routed experts' rows at its slab bound,
    and every grouped product of a branch reads a slab of that branch's
    bound — never the whole (E, K, N) bank below the top bound."""
    bank = _bank()
    x, gates, idx, valid = _routing(n_routed)
    ok = jnp.repeat(valid.reshape(-1), K)
    present = moe.expert_counts(idx.reshape(-1), ok, E) > 0
    ids = moe.slab_ids(present, bound)
    routed = sorted(set(np.asarray(idx)[np.asarray(valid)].ravel().tolist()))
    assert np.asarray(ids)[:n_routed].tolist() == routed
    read = 0
    for name, fw in bank.items():
        q, scale = moe._slab_weight(fw, ids, ecc_inline=True)
        assert q.shape[0] == bound
        np.testing.assert_array_equal(np.asarray(q[:n_routed], np.float32),
                                      np.asarray(fw.q[jnp.asarray(routed)],
                                                 np.float32))
        read += bound * (fw.q[0].size + fw.parity[0].size
                         + 4 * fw.scale[0].size)
    per_expert = sum(fw.nbytes() for fw in bank.values()) // E
    assert read == bound * per_expert

    jaxpr = jax.make_jaxpr(lambda *a: moe.serve_expert_ffn(*a, valid=valid))(
        bank, x, gates, idx)
    conds = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    for b, branch in zip(moe.slab_bounds(E, K), conds[0].params["branches"]):
        rhs = [e.invars[1].aval.shape for e in branch.jaxpr.eqns
               if e.primitive.name == "ragged_dot_general"]
        assert len(rhs) == 3 and all(r[0] == b for r in rhs), (b, rhs)


def test_flipped_bit_in_routed_expert_is_corrected(monkeypatch):
    bank = _bank()
    x, gates, idx, valid = _routing(3)
    e = int(np.asarray(idx)[0, 0, 0])
    raw = np.asarray(ecc.weights_to_bytes(bank["w_up"].q)).copy()
    raw[e, 5, 7] ^= np.uint8(1 << 3)                # one data bit
    bad = dict(bank, w_up=FlashWeight(
        q=ecc.bytes_to_weights(jnp.asarray(raw)),
        parity=bank["w_up"].parity, scale=bank["w_up"].scale))
    clean = moe.serve_expert_ffn(bank, x, gates, idx, valid=valid)
    monkeypatch.setenv("REPRO_SERVE_ECC", "inline")
    fixed = moe.serve_expert_ffn(bad, x, gates, idx, valid=valid)
    np.testing.assert_array_equal(np.asarray(fixed), np.asarray(clean))
    monkeypatch.setenv("REPRO_SERVE_ECC", "load")
    raw_read = moe.serve_expert_ffn(bad, x, gates, idx, valid=valid)
    assert not np.array_equal(np.asarray(raw_read), np.asarray(clean))


@pytest.fixture(scope="module")
def params():
    return moe.init(CFG, jax.random.PRNGKey(3))


def _serve(params, **kw):
    eng = Engine(CFG, params, max_slots=4, max_seq=128,
                 sample_cfg=SampleConfig(temperature=0.0), seed=1, **kw)
    eng.submit(list(range(3, 40)), max_new=6)           # chunked prefill
    eng.submit([7, 1, 9], max_new=10)
    eng.submit(list(range(50, 61)), max_new=4)
    return eng, eng.run()


def test_engine_corrects_flash_bit_errors_in_experts(params, monkeypatch):
    """With raw bit errors programmed into the flash tier (experts and
    lm_head), the inline check serves what the clean deployment serves."""
    monkeypatch.setenv("REPRO_SERVE_ECC", "inline")
    _, clean = _serve(params)
    eng, noisy = _serve(params, rber=2e-4)
    flipped = int(np.sum(np.asarray(eng.params["layers"]["moe"]["experts"]
                                    ["w_gate"].q)
                         != np.asarray(deploy(params)[0]["layers"]["moe"]
                                       ["experts"]["w_gate"].q)))
    assert flipped > 0
    assert noisy == clean


def test_moe_counters_equal_host_recount(params, monkeypatch):
    seen = []
    real = moe.serve_expert_ffn

    def spy(bank, x, gates, idx, slab_map=None, **kw):
        seen.append((np.asarray(idx), np.asarray(kw["valid"])))
        return real(bank, x, gates, idx, slab_map, **kw)

    monkeypatch.setattr(eng_mod.moe_mod, "serve_expert_ffn", spy)
    reg = obs.MetricsRegistry()
    _serve(params, compiled=False, registry=reg)
    assert seen
    assign = sum(int(v.sum()) * CFG.top_k for _, v in seen)
    routed = sum(len(set(i[v].ravel().tolist())) for i, v in seen)
    assert reg.counter("engine_moe_assignments_total").value() == assign
    assert reg.counter("engine_moe_experts_routed_total").value() == routed
    assert 0 < routed < assign


def test_resident_step_compiles_once_across_churn(params):
    eng, _ = _serve(params)
    eng.submit([4, 4, 4, 4], max_new=3)
    eng.run()
    assert eng.step_traces == 1
