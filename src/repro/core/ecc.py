"""Hamming(72,64) SEC-DED codec over INT8 weight streams.

This is the error model behind NVLLM's ERDPE (paper §3.2-3.3, Algorithm 1):
weights are stored as raw NAND pages whose reads exhibit a non-zero RBER; an
inline *detector* flags dirty codewords cheaply and a *corrector* repairs them
off the critical path.

Layout
------
A codeword protects 8 consecutive INT8 weights along the reduction (K) axis:
64 data bits + one parity byte (7 Hamming bits + 1 overall bit) = 12.5 %
storage overhead, i.e. an L(72,64) code in the paper's notation.

For a weight matrix ``W`` of shape (K, N) stored as uint8 "raw bytes", the
parity plane has shape (K//8, N).

All functions here are pure jnp (no gathers, no dynamic shapes). ``encode``
computes parity with shift-XOR folds. ``check_and_correct`` reads each
codeword once: every byte maps to an 8-bit contribution (7 masked-byte
parities by ``population_count`` plus the byte's parity), the 8 contributions
XOR-reduce to the codeword's computed parity byte, and the flipped bit's
place comes from the syndrome by arithmetic (``clz``), so no intermediate is
larger than the weight. Mosaic does not lower the XOR ``reduce`` (nor the
codeword reshape), so the ECC Pallas kernels run in interpret mode only.

Semantics (verified by property tests in tests/test_ecc.py):
  * any single flipped bit per codeword (data OR parity byte) -> corrected
  * any two flipped bits per codeword -> detected as uncorrectable
  * ``dirty`` flags every codeword whose received bits differ from encoded
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

# --- constant tables -------------------------------------------------------
# Logical Hamming positions 1..71; powers of two are parity positions.
_PARITY_POS = np.array([1, 2, 4, 8, 16, 32, 64], dtype=np.int32)
_DATA_POS = np.array(
    [p for p in range(1, 72) if p not in set(_PARITY_POS.tolist())], dtype=np.int32
)  # (64,) logical position of physical data bit i
assert _DATA_POS.shape == (64,)

# PHYS_MASK[k][b] : uint8 mask over data byte b selecting bits that feed
# Hamming parity k (bit i of byte b is data bit b*8+i).
_PHYS_MASK = np.zeros((7, 8), dtype=np.uint8)
for _k in range(7):
    for _i in range(64):
        if (_DATA_POS[_i] >> _k) & 1:
            _PHYS_MASK[_k, _i // 8] |= np.uint8(1 << (_i % 8))

PHYS_MASK = jnp.asarray(_PHYS_MASK)                     # (7, 8) uint8

PARITY_OVERHEAD = 1.0 / 8.0  # parity bytes per weight byte


def tables() -> tuple[np.ndarray, np.ndarray]:
    """(phys_mask (7,8) u8, data_pos (64,) i32) as numpy, for passing into
    Pallas kernels (which cannot close over array constants)."""
    return _PHYS_MASK.copy(), _DATA_POS.copy()


def _bit_weights() -> jnp.ndarray:
    """LSB-first packing weights [1,2,4,...,128], built inline (Pallas-safe)."""
    return (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8)).astype(jnp.uint8)


def _byte_parity(x: jnp.ndarray) -> jnp.ndarray:
    """Per-byte parity (popcount mod 2) of a uint8 array, returns uint8 0/1."""
    x = x ^ (x >> 4)
    x = x ^ (x >> 2)
    x = x ^ (x >> 1)
    return x & jnp.uint8(1)


def _as_codewords(raw_bytes: jnp.ndarray) -> jnp.ndarray:
    """(K, N) uint8 -> (K//8, 8, N) codeword view."""
    k, n = raw_bytes.shape
    if k % 8:
        raise ValueError(f"K={k} must be a multiple of 8 (codeword = 8 bytes)")
    return raw_bytes.reshape(k // 8, 8, n)


def encode(raw_bytes: jnp.ndarray, phys_mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Compute the parity plane for (K, N) uint8 weight bytes -> (K//8, N) uint8."""
    if phys_mask is None:
        phys_mask = PHYS_MASK
    cw = _as_codewords(raw_bytes)                                  # (G, 8, N)
    # Hamming parity bits: parity over (codeword bytes & mask_k); phys_mask is
    # (7, 8) -> broadcast to (G, 7, 8, N).
    masked = cw[:, None, :, :] & phys_mask[None, :, :, None]
    pk = jnp.sum(_byte_parity(masked).astype(jnp.int32), axis=2) & 1   # (G, 7, N)
    hamming = jnp.sum(
        pk.astype(jnp.uint8) << jnp.arange(7, dtype=jnp.uint8)[None, :, None], axis=1
    )                                                               # (G, N)
    data_par = jnp.sum(_byte_parity(cw).astype(jnp.int32), axis=1) & 1  # (G, N)
    par_par = jnp.sum(pk, axis=1) & 1
    overall = ((data_par + par_par) & 1).astype(jnp.uint8) << jnp.uint8(7)
    return (hamming | overall).astype(jnp.uint8)


@jax.named_scope("ecc")        # nests under the matmul that reads the weight
def check_and_correct(
    raw_bytes: jnp.ndarray,
    parity: jnp.ndarray,
    phys_mask: jnp.ndarray | None = None,
    data_pos: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Detect + correct single-bit errors per codeword.

    One pass over the bytes: each byte maps to an 8-bit contribution (bit
    j < 7: parity of ``byte & phys_mask[j, row]``; bit 7: parity of the
    byte), the 8 contributions of a codeword XOR-reduce to its computed
    parity byte, and the flipped bit's place follows from the syndrome by
    arithmetic. Nothing larger than the weight is materialised.

    Args:
      raw_bytes: (K, N) uint8 received weight bytes (possibly corrupted).
      parity:    (K//8, N) uint8 received parity plane (possibly corrupted).
      phys_mask/data_pos: optional codec tables (see ``tables()``); passed
        explicitly when called inside a Pallas kernel. ``data_pos`` is
        implied by the arithmetic corrector and unused.
    Returns:
      corrected: (K, N) uint8 — data with single-bit errors repaired.
      dirty:     (K//8, N) bool — codeword had a detected error (incl. parity-only).
      uncorrectable: (K//8, N) bool — double-bit (or worse) error detected.
    """
    del data_pos
    if phys_mask is None:
        phys_mask = PHYS_MASK
    k, n = raw_bytes.shape
    cw = _as_codewords(raw_bytes)                                    # (G, 8, N)
    parity = parity.astype(jnp.uint8)
    one = jnp.uint8(1)
    contrib = (lax.population_count(cw) & one) << 7
    for j in range(7):
        masked = cw & phys_mask[j][None, :, None]
        contrib = contrib | ((lax.population_count(masked) & one) << j)
    computed = lax.reduce(contrib, jnp.uint8(0), lax.bitwise_xor, (1,))  # (G, N)
    syndrome = (computed ^ parity) & jnp.uint8(0x7F)                 # (G, N) 0..127
    # Overall parity over data, stored Hamming bits and stored overall bit.
    dq = lax.population_count((computed & jnp.uint8(0x80)) ^ parity) & one
    is_err = dq.astype(bool)
    is_power = (syndrome & (syndrome - one)) == 0                    # incl. syndrome==0
    # dq==1 and a data position (not a power of two, <= 71): flip that bit.
    # Logical position p holds physical data bit p - floor(log2 p) - 2.
    data_hit = is_err & ~is_power & (syndrome <= jnp.uint8(71))
    log2 = jnp.uint8(7) - lax.clz(syndrome)
    bit = syndrome - log2 - jnp.uint8(2)
    row = jnp.where(data_hit, bit >> 3, jnp.uint8(8))                # 8: no flip
    rows = jnp.arange(8, dtype=jnp.uint8)[None, :, None]
    flip = jnp.where(row[:, None, :] == rows,
                     one << (bit & jnp.uint8(7))[:, None, :], jnp.uint8(0))
    corrected = (cw ^ flip).reshape(k, n)

    # dq==1: correctable iff syndrome hits a data position, a parity position
    # (power of two) or 0 (overall-bit flip). dq==0 & syndrome!=0: double error.
    uncorrectable = (~is_err & (syndrome != 0)) | (is_err & ~data_hit & ~is_power)
    dirty = is_err | (syndrome != 0)
    return corrected, dirty, uncorrectable


def check_and_correct_np(
    raw_bytes: np.ndarray, parity: np.ndarray
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side (numpy) port of ``check_and_correct`` for the store's
    read-retry path: the PageStore verifies a freshly-read page against
    its parity WITHOUT a device round-trip, so detected-uncorrectable
    pages can re-read / relocate before any bytes reach the pool.

    Same contract and return shapes as ``check_and_correct``:
    (corrected (K, N) u8, dirty (K//8, N) bool, uncorrectable bool).
    Bit-identical to the jnp path (tests/test_faultplane.py cross-checks).
    """
    k, n = raw_bytes.shape
    if k % 8:
        raise ValueError(f"K={k} must be a multiple of 8")
    cw = raw_bytes.reshape(k // 8, 8, n)                            # (G, 8, N)

    def byte_parity(x):
        x = x ^ (x >> 4)
        x = x ^ (x >> 2)
        x = x ^ (x >> 1)
        return x & np.uint8(1)

    masked = cw[:, None, :, :] & _PHYS_MASK[None, :, :, None]       # (G,7,8,N)
    pk = np.sum(byte_parity(masked).astype(np.int32), axis=2) & 1   # (G, 7, N)
    stored_pk = (parity[:, None, :]
                 >> np.arange(7, dtype=np.uint8)[None, :, None]) & 1
    s_bits = pk.astype(np.uint8) ^ stored_pk.astype(np.uint8)
    syndrome = np.sum(
        s_bits.astype(np.int32)
        << np.arange(7, dtype=np.int32)[None, :, None], axis=1)     # (G, N)
    data_par = np.sum(byte_parity(cw).astype(np.int32), axis=1) & 1
    stored_hamming_par = np.sum(stored_pk.astype(np.int32), axis=1) & 1
    overall_recv = ((parity >> np.uint8(7)) & 1).astype(np.int32)
    dq = (data_par + stored_hamming_par + overall_recv) & 1

    is_err = dq.astype(bool)
    onehot = is_err[:, None, :] \
        & (syndrome[:, None, :] == _DATA_POS[None, :, None])
    weights = (np.uint8(1) << np.arange(8, dtype=np.uint8))
    flip = np.sum(
        onehot.reshape(k // 8, 8, 8, n).astype(np.uint8)
        * weights[None, None, :, None], axis=2).astype(np.uint8)
    corrected = (cw ^ flip).reshape(k, n)

    is_power = (syndrome & (syndrome - 1)) == 0
    data_hit = np.any(onehot, axis=1)
    uncorrectable = (~is_err & (syndrome != 0)) \
        | (is_err & ~data_hit & ~is_power)
    dirty = is_err | (syndrome != 0)
    return corrected, dirty, uncorrectable


def weights_to_bytes(w_int8: jnp.ndarray) -> jnp.ndarray:
    return lax.bitcast_convert_type(w_int8, jnp.uint8)


def bytes_to_weights(b_uint8: jnp.ndarray) -> jnp.ndarray:
    return lax.bitcast_convert_type(b_uint8, jnp.int8)


# --- RBER injection ---------------------------------------------------------

def inject_bit_errors_np(
    raw_bytes: np.ndarray, rber: float, seed: int
) -> tuple[np.ndarray, int]:
    """Flip each bit independently with probability ``rber`` (numpy, deploy-scale).

    Returns (corrupted_bytes, n_flipped_bits). Deterministic in ``seed``.
    """
    rng = np.random.default_rng(seed)
    out = raw_bytes.copy()
    flat = out.reshape(-1)
    # Sample flip count then positions: avoids materializing bits for large arrays.
    nbits = flat.size * 8
    nflip = rng.binomial(nbits, rber)
    if nflip:
        pos = rng.choice(nbits, size=nflip, replace=False)
        np.bitwise_xor.at(flat, pos // 8, (1 << (pos % 8)).astype(raw_bytes.dtype))
    return out, int(nflip)


def inject_bit_errors(raw_bytes: jnp.ndarray, rber: float, key) -> jnp.ndarray:
    """jnp version for test-scale arrays: per-bit Bernoulli flips."""
    import jax

    bits = jax.random.bernoulli(key, rber, raw_bytes.shape + (8,))
    flip = jnp.sum(
        bits.astype(jnp.uint8) * _bit_weights()[(None,) * raw_bytes.ndim], axis=-1
    ).astype(jnp.uint8)
    return raw_bytes ^ flip
