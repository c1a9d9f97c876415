"""Property tests for the Hamming(72,64) SEC-DED codec (paper §3.2)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hyp_compat import given, settings, st

from repro.core import ecc


def _random_bytes(rng, k, n):
    return jnp.asarray(rng.integers(0, 256, (k, n), endpoint=False),
                       jnp.uint8)


def test_clean_roundtrip():
    rng = np.random.default_rng(0)
    raw = _random_bytes(rng, 64, 16)
    parity = ecc.encode(raw)
    corrected, dirty, unc = ecc.check_and_correct(raw, parity)
    assert bool(jnp.all(corrected == raw))
    assert int(dirty.sum()) == 0
    assert int(unc.sum()) == 0


@settings(max_examples=40, deadline=None)
@given(codeword=st.integers(0, 7), byte=st.integers(0, 7),
       bit=st.integers(0, 7), seed=st.integers(0, 2**16))
def test_single_data_bit_error_corrected(codeword, byte, bit, seed):
    rng = np.random.default_rng(seed)
    raw = np.asarray(_random_bytes(rng, 64, 4))
    parity = ecc.encode(jnp.asarray(raw))
    bad = raw.copy()
    col = rng.integers(0, 4)
    bad[codeword * 8 + byte, col] ^= np.uint8(1 << bit)
    corrected, dirty, unc = ecc.check_and_correct(jnp.asarray(bad), parity)
    assert bool(jnp.all(corrected == jnp.asarray(raw))), "single-bit repair"
    assert bool(dirty[codeword, col]), "detector must flag the codeword"
    assert int(unc.sum()) == 0


@settings(max_examples=40, deadline=None)
@given(bit=st.integers(0, 7), seed=st.integers(0, 2**16))
def test_single_parity_bit_error_no_corruption(bit, seed):
    """A flip in the PARITY byte must not corrupt data."""
    rng = np.random.default_rng(seed)
    raw = _random_bytes(rng, 32, 3)
    parity = np.asarray(ecc.encode(raw))
    bad_parity = parity.copy()
    g, col = rng.integers(0, 4), rng.integers(0, 3)
    bad_parity[g, col] ^= np.uint8(1 << bit)
    corrected, dirty, unc = ecc.check_and_correct(
        raw, jnp.asarray(bad_parity))
    assert bool(jnp.all(corrected == raw))
    assert int(unc.sum()) == 0


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_double_bit_error_detected(seed):
    rng = np.random.default_rng(seed)
    raw = np.asarray(_random_bytes(rng, 16, 2))
    parity = ecc.encode(jnp.asarray(raw))
    bad = raw.copy()
    g, col = rng.integers(0, 2), rng.integers(0, 2)
    p1, p2 = rng.choice(64, 2, replace=False)
    bad[g * 8 + p1 // 8, col] ^= np.uint8(1 << (p1 % 8))
    bad[g * 8 + p2 // 8, col] ^= np.uint8(1 << (p2 % 8))
    _, dirty, unc = ecc.check_and_correct(jnp.asarray(bad), parity)
    assert bool(dirty[g, col])
    assert bool(unc[g, col]), "double error must be flagged uncorrectable"


def test_rber_injection_rate():
    rng_bytes = np.zeros((1024, 64), np.uint8)
    out, nflip = ecc.inject_bit_errors_np(rng_bytes, 1e-3, seed=1)
    nbits = out.size * 8
    assert abs(nflip / nbits - 1e-3) < 3e-4
    assert int(np.unpackbits(out).sum()) == nflip


def test_low_rber_full_recovery():
    """At realistic RBER (~1e-4) nearly every codeword is 0/1-bit dirty."""
    rng = np.random.default_rng(3)
    raw = np.asarray(_random_bytes(rng, 512, 32))
    parity = ecc.encode(jnp.asarray(raw))
    bad, _ = ecc.inject_bit_errors_np(raw, 1e-4, seed=7)
    corrected, dirty, unc = ecc.check_and_correct(jnp.asarray(bad), parity)
    # everything not double-hit must be repaired exactly
    ok = np.asarray(corrected) == raw
    unc_np = np.asarray(unc)
    cw_ok = ok.reshape(-1, 8, ok.shape[1]).all(axis=1)
    assert bool(np.all(cw_ok | unc_np))
    assert unc_np.mean() < 1e-3



_RANDOM_CASES = [((k, n), nflips, None)
                 for k, n in [(8, 128), (64, 384), (2048, 256)]
                 for nflips in [0, 1, 2, 3, 64, 5000]]
# Each of the 72 bits of codeword (0, column 5) of an (8, 128) matrix: bit
# b*8+i < 64 is bit i of row b, bits 64..71 are the parity byte's.
_SWEEP_CASES = [((8, 128), 1, bit) for bit in range(72)]


@pytest.mark.parametrize(
    "shape,nflips,bit", _RANDOM_CASES + _SWEEP_CASES,
    ids=[f"{k}x{n}-flips{f}" for (k, n), f, _ in _RANDOM_CASES]
    + [f"sweep-bit{b}" for _, _, b in _SWEEP_CASES])
def test_check_and_correct_bit_identical_to_np(shape, nflips, bit):
    """The device codec agrees with the host port on corrected bytes,
    ``dirty`` and ``uncorrectable``: random flips over data and parity
    bytes, and each single bit of one codeword (``bit``)."""
    k, n = shape
    rng = np.random.default_rng(k * 7 + n + nflips)
    raw = rng.integers(0, 256, (k, n), dtype=np.uint8)
    parity = np.asarray(ecc.encode(jnp.asarray(raw))).astype(np.uint8)
    # Bit positions over the data bytes followed by the parity bytes.
    if bit is None:
        pos = rng.choice((raw.size + parity.size) * 8, nflips, replace=False)
    elif bit < 64:
        pos = np.array([(bit // 8 * n + 5) * 8 + bit % 8])
    else:
        pos = np.array([(raw.size + 5) * 8 + bit - 64])
    flat = np.concatenate([raw.reshape(-1), parity.reshape(-1)])
    np.bitwise_xor.at(flat, pos // 8, (1 << (pos % 8)).astype(np.uint8))
    bad = flat[:raw.size].reshape(raw.shape)
    bad_parity = flat[raw.size:].reshape(parity.shape)

    want = ecc.check_and_correct_np(bad, bad_parity)
    got = ecc.check_and_correct(jnp.asarray(bad), jnp.asarray(bad_parity))
    for w, g, what in zip(want, got, ("corrected", "dirty", "uncorrectable")):
        np.testing.assert_array_equal(np.asarray(g), w, err_msg=what)
    if bit is not None:                   # one flip: repaired and flagged
        np.testing.assert_array_equal(want[0], raw)
        assert int(want[1].sum()) == 1 and not want[2].any()
