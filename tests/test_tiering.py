"""Tier policy (paper C1): placement, encode/decode, RBER robustness."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ecc
from repro.core.erdpe import maybe_flash_matmul
from repro.core.quant import dequantize_int8
from repro.core.tiering import (FlashWeight, deploy, encode_flash,
                                flash_bytes, tier_of)


def test_tier_policy_paths():
    flash = ["layers/ffn/w_gate", "layers/ffn/w_up", "layers/ffn/w_down",
             "lm_head", "layers/moe/experts/w_up",
             "blocks/r1/mix/w_in_x", "blocks/r2/mix/w_out",
             "layers/tmix/w_r", "layers/channel_mix/w_up"]
    dram = ["embed", "pos_embed", "layers/attn/wq", "layers/attn/wo",
            "layers/ln1", "layers/moe/router", "layers/tmix/mu",
            "layers/channel_mix/mu_k", "final_norm",
            "dec/cross/wk", "layers/attn/q_norm"]
    for p in flash:
        assert tier_of(p) == "flash", p
    for p in dram:
        assert tier_of(p) == "dram", p


def test_encode_flash_roundtrip():
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (64, 32), jnp.float32)
    fw = encode_flash(w)
    assert fw.q.shape == (64, 32)
    assert fw.parity.shape == (8, 32)
    assert fw.scale.shape == (1, 32)
    deq = dequantize_int8(fw.q, fw.scale, jnp.float32)
    assert float(jnp.max(jnp.abs(deq - w))) < float(jnp.max(fw.scale)) * 0.51


def test_encode_flash_stacked_layers():
    key = jax.random.PRNGKey(1)
    w = jax.random.normal(key, (3, 64, 16), jnp.float32)   # (L, K, N)
    fw = encode_flash(w)
    assert fw.q.shape == (3, 64, 16)
    assert fw.parity.shape == (3, 8, 16)
    assert fw.scale.shape == (3, 1, 16)
    # each layer's parity is independently valid
    for li in range(3):
        raw = ecc.weights_to_bytes(fw.q[li])
        _, dirty, _ = ecc.check_and_correct(raw, fw.parity[li])
        assert int(dirty.sum()) == 0


@pytest.mark.parametrize("shape", [(3, 64, 16), (2, 3, 64, 32)])
def test_slice_wise_encode_is_bit_identical(shape):
    """A stacked leaf is encoded a leading slice at a time; the result is
    the whole-stack encoding bit for bit (quantization is per output
    channel within a matrix), parity stored one byte per codeword."""
    from repro.core.quant import quantize_int8
    w = jax.random.normal(jax.random.PRNGKey(7), shape, jnp.bfloat16)
    fw = encode_flash(w)
    q, scale = quantize_int8(w, axis=-2)
    raw = ecc.weights_to_bytes(q)
    mats = raw.reshape((-1,) + raw.shape[-2:])
    parity = jnp.stack([ecc.encode(m) for m in mats]).reshape(
        shape[:-2] + (shape[-2] // 8, shape[-1]))
    assert fw.parity.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(fw.q), np.asarray(q))
    np.testing.assert_array_equal(np.asarray(fw.parity), np.asarray(parity))
    np.testing.assert_array_equal(np.asarray(fw.scale).view(np.uint32),
                                  np.asarray(scale).view(np.uint32))


def test_deploy_and_forward_with_rber():
    from repro.configs import get_config
    from repro.models import dense
    cfg = get_config("granite-8b", smoke=True)
    params = dense.init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    clean = dense.forward(cfg, params, tokens)

    tiered, tier_map = deploy(params, rber=0.0)
    quant_out = dense.forward(cfg, tiered, tokens)
    # INT8 deployment: close to bf16 in logit space
    base = np.abs(np.asarray(clean)).mean()
    err0 = np.abs(np.asarray(quant_out) - np.asarray(clean)).mean()
    assert err0 < 0.25 * base

    # with errors + ECC: same result as rber=0 (all single-bit repaired at 1e-5)
    tiered_rber, _ = deploy(params, rber=1e-5, seed=9)
    out_rber = dense.forward(cfg, tiered_rber, tokens)
    err_vs_clean_enc = np.abs(np.asarray(out_rber)
                              - np.asarray(quant_out)).mean()
    assert err_vs_clean_enc < 0.02 * base

    assert tier_map["layers/ffn/w_gate"] == "flash"
    assert tier_map["layers/attn/wq"] == "dram"
    fb, db = flash_bytes(tiered)
    assert fb > 0 and db > 0


def test_serve_ecc_env_is_late_binding(monkeypatch):
    """Regression: REPRO_SERVE_ECC used to be read ONCE at import, so a
    test/benchmark toggling inline-vs-load ECC after `import repro` was
    silently ignored. maybe_flash_matmul must honor the env per call:
    with a single stored bit flipped, inline mode corrects it (output
    matches the clean encoding) while load mode serves the raw bytes."""
    from repro.core import erdpe
    key = jax.random.PRNGKey(7)
    w = jax.random.normal(key, (64, 16), jnp.float32)
    fw = encode_flash(w)
    raw = np.asarray(fw.q).view(np.uint8).copy()
    raw[0, 0] ^= np.uint8(0x40)                  # one bit: correctable
    bad = FlashWeight(q=jnp.asarray(raw.view(np.int8)),
                      parity=fw.parity, scale=fw.scale)
    x = jnp.ones((2, 64), jnp.bfloat16)
    clean = np.asarray(maybe_flash_matmul(x, fw, ecc_enabled=True), np.float32)

    monkeypatch.setenv("REPRO_SERVE_ECC", "inline")
    assert erdpe.serve_ecc_mode() == "inline"
    got_inline = np.asarray(maybe_flash_matmul(x, bad), np.float32)
    np.testing.assert_allclose(got_inline, clean)   # error repaired

    monkeypatch.setenv("REPRO_SERVE_ECC", "load")
    assert erdpe.serve_ecc_mode() == "load"
    got_load = np.asarray(maybe_flash_matmul(x, bad), np.float32)
    assert not np.allclose(got_load, clean), \
        "load mode must serve raw bytes (env change was ignored)"


def test_maybe_flash_dispatch():
    key = jax.random.PRNGKey(3)
    w = jax.random.normal(key, (32, 16), jnp.float32)
    x = jax.random.normal(key, (4, 32), jnp.bfloat16)
    plain = maybe_flash_matmul(x, w.astype(jnp.bfloat16))
    flash = maybe_flash_matmul(x, encode_flash(w))
    assert plain.shape == flash.shape == (4, 16)
    np.testing.assert_allclose(np.asarray(plain, np.float32),
                               np.asarray(flash, np.float32),
                               rtol=0.1, atol=0.3)
