"""Tiered weight placement (paper contribution C1).

NVLLM stores FFN weights (and the final output projection) in 3D NAND flash
and keeps attention Q/K/V/O weights, embeddings and norms in DRAM (§3.5:
"Q/K/V/O weights are copied once into DRAM at initialization").

Here the *flash tier* is represented by ``FlashWeight``: INT8 codewords +
Hamming(72,64) parity planes + per-channel scales, laid out in 16 KiB pages
(128x128 int8 tiles). ``deploy`` converts a trained bf16/f32 param pytree
into its tiered NVLLM form — the "flash programming" step. Programming is
write-once (endurance-friendly, §2.2); optional RBER injection emulates raw
NAND reads.
"""
from __future__ import annotations

import dataclasses
import functools
import re
import zlib
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ecc
from repro.core.quant import quantize_int8

FLASH = "flash"
DRAM = "dram"

# Paper placement: FFN + final output projection -> flash; attention Q/K/V/O,
# embeddings, norms, routers, recurrences -> DRAM. RWKV's channel-mix and
# time-mix *projections* are FFN-like weight-stationary GEMVs -> flash
# (DESIGN.md §4); its decay/state params stay DRAM-side.
# Strict weight-name matches: a stacked 1-D param (L, D) must never be
# mistaken for a (K, N) matrix (it would be ECC-encoded along the layer dim).
DEFAULT_FLASH_PATTERNS = (
    r".*lm_head$",
    r".*(w_gate|w_up|w_down|w_in|w_out)$",     # FFN / MoE expert banks
    r".*mix/w_in_[xy]$", r".*mix/w_out$",      # RG-LRU recurrent projections
    r".*tmix/w_[rkvgo]$",                      # RWKV time-mix projections
    r".*channel_mix/w_rgate$",
)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FlashWeight:
    """A flash-tier weight matrix: raw INT8 pages + parity + dequant scale."""
    q: jnp.ndarray        # (..., K, N) int8 raw codeword bytes (as weights)
    parity: jnp.ndarray   # (..., K//8, N) uint8
    scale: jnp.ndarray    # (..., 1, N) float32

    def tree_flatten(self):
        return (self.q, self.parity, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.q.shape

    def nbytes(self) -> int:
        return self.q.size + self.parity.size + self.scale.size * 4


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedWeight:
    """A flash-tier weight consumed IN PLACE from the device page pool.

    The streamed serving engine's pool-backed twin of ``FlashWeight``: no
    dense q/parity/scale arrays — just the shared ``(n_pages, 16 KiB)``
    int8 pool buffer plus the page tables naming which pool slots hold
    this weight's tiles (q) and flat byte runs (parity/scale), exactly as
    ``store/page_pool.WeightPagePool.upload`` built them. The logical
    (K, N) shape is pytree AUX DATA — static under jit, so kernels can pad
    and slice around the 128-multiple tile grid without retracing.

    Leading dims on the tables (e.g. the MoE expert-slab row axis) play the
    same stacking role as FlashWeight's leading dims.
    """
    pool: jnp.ndarray      # (n_pages, PAGE_BYTES) int8 — pool snapshot
    q_tbl: jnp.ndarray     # (..., k_tiles, n_tiles) i32 pool page slots
    p_slots: jnp.ndarray   # (..., n_parity_pages) i32
    s_slots: jnp.ndarray   # (..., n_scale_pages) i32
    kn: tuple = ()         # logical (K, N) — static

    def tree_flatten(self):
        return ((self.pool, self.q_tbl, self.p_slots, self.s_slots),
                tuple(self.kn))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, kn=tuple(aux))

    @property
    def lead(self) -> tuple:
        return tuple(self.q_tbl.shape[:-2])

    @property
    def shape(self) -> tuple:
        return self.lead + tuple(self.kn)


def is_flash_path(path: str, patterns=DEFAULT_FLASH_PATTERNS) -> bool:
    return any(re.fullmatch(p, path) for p in patterns)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def tier_of(path: str, patterns=DEFAULT_FLASH_PATTERNS) -> str:
    return FLASH if is_flash_path(path, patterns) else DRAM


@jax.jit
def _encode_matrices(w: jnp.ndarray):
    """Quantize + parity of a stack of matrices (..., K, N), one matrix at a
    time for the parity pass (``lax.map``), so no codec intermediate is
    larger than one matrix's."""
    q, scale = quantize_int8(w, axis=-2)
    raw = ecc.weights_to_bytes(q)
    flat = raw.reshape((-1,) + raw.shape[-2:])
    parity = jax.lax.map(ecc.encode, flat)
    return q, parity.reshape(raw.shape[:-2] + parity.shape[-2:]), scale


@functools.partial(jax.jit, donate_argnums=0)
def _put_slice(out, i, w):
    """Encode ``w[i]`` into slice ``i`` of the preallocated ``out``, in
    place (``out`` is donated)."""
    part = _encode_matrices(w[i])
    return tuple(o.at[i].set(p) for o, p in zip(out, part))


def encode_flash(w: jnp.ndarray, rber: float = 0.0, seed: int = 0) -> FlashWeight:
    """Quantize + ECC-encode one weight matrix (leading dims = layer stack).

    A stacked leaf is encoded one leading slice at a time into outputs
    allocated once and updated in place. Quantization is per output
    channel within a matrix, so the slices are independent and the result
    is the whole-stack encoding bit for bit, while deploy never holds more
    than one slice in float32."""
    if w.ndim < 2:
        raise ValueError("flash tier holds matrices")
    if w.ndim == 2:
        q, parity, scale = _encode_matrices(w)
    else:
        k, n = w.shape[-2:]
        lead = w.shape[:-2]
        out = (jnp.zeros(w.shape, jnp.int8),
               jnp.zeros(lead + (k // 8, n), jnp.uint8),
               jnp.zeros(lead + (1, n), jnp.float32))
        for i in range(lead[0]):
            out = _put_slice(out, jnp.int32(i), w)
        q, parity, scale = out
    if rber > 0.0:
        raw = ecc.weights_to_bytes(q)
        corrupted, _ = ecc.inject_bit_errors_np(np.asarray(raw), rber, seed)
        q = ecc.bytes_to_weights(jnp.asarray(corrupted))
    return FlashWeight(q=q, parity=parity, scale=scale)


def deploy(
    params: Any,
    patterns=DEFAULT_FLASH_PATTERNS,
    rber: float = 0.0,
    seed: int = 0,
    predicate: Callable[[str, jnp.ndarray], bool] | None = None,
    store: Any = None,
) -> tuple[Any, dict[str, str]]:
    """Convert a param pytree to tiered NVLLM deployment form.

    Returns (tiered_params, tier_map). Flash-tier leaves become FlashWeight;
    DRAM-tier leaves are cast to bf16.

    ``store`` (a ``repro.store.pagestore.PageStore``) redirects the flash
    tier into a HOST-RESIDENT page store instead of device arrays: each
    flash leaf is encoded exactly as in the device path (same quant, parity,
    RBER seed derivation) but then serialized into 16 KiB plane-interleaved
    pages, and the returned pytree carries a lightweight ``StoreRef``
    placeholder in its place. This is the paper's deployment shape — FFN
    weights live in the NAND array, never in DRAM (§3.5) — and what the
    streamed serving engine consumes.
    """
    tier_map: dict[str, str] = {}

    def convert(path, leaf):
        p = _path_str(path)
        flash = (
            predicate(p, leaf) if predicate is not None
            else (is_flash_path(p, patterns) and leaf.ndim >= 2)
        )
        tier_map[p] = FLASH if flash else DRAM
        if flash:
            # crc32, NOT hash(): Python string hashing is randomized per
            # process (PYTHONHASHSEED), which made the injected bit-error
            # positions — and thus every rber>0 engine — nondeterministic
            # across runs despite the documented "deterministic in seed".
            fw = encode_flash(leaf,
                              rber=rber,
                              seed=seed + zlib.crc32(p.encode()) % (2**31))
            if store is not None:
                return store.put_param(p, fw)
            return fw
        return leaf.astype(jnp.bfloat16)

    tiered = jax.tree_util.tree_map_with_path(convert, params)
    return tiered, tier_map


def tile_parity(parity: np.ndarray, k_tile: int, n_tile: int,
                tile: int = 128) -> np.ndarray:
    """The parity slice protecting ONE (tile, tile) q page of a flash
    param: rows ``k_tile*tile/8 .. +tile/8``, cols ``n_tile*tile .. +tile``
    of the (K//8, N) parity plane, zero-padded to the full page grid.

    Valid because codewords are LOCAL to 8-row groups within a column
    (the (72,64) layout) and the page grid pads K/N up to tile multiples:
    K is a multiple of 8, tile is a multiple of 8, so no codeword ever
    straddles real and padded rows — and the parity byte of an all-zero
    padded codeword is exactly 0, which is what the zero-fill provides.
    The PageStore's read-retry path uses this to verify pages host-side
    without re-reading the whole entry."""
    rows = tile // 8
    out = np.zeros((rows, tile), np.uint8)
    pr = parity[k_tile * rows:(k_tile + 1) * rows,
                n_tile * tile:(n_tile + 1) * tile]
    out[:pr.shape[0], :pr.shape[1]] = pr
    return out


# Per-layer flash Q/K/V/O copies (Alg. 2's in-flash projection targets).
# ONE definition of the store entry names and the per-layer seed derivation,
# shared by the streamed engine and deploy --store: if the two ever diverged,
# deploy-written images would silently carry attn weights that no longer
# match the resident engine's flash copies (parity breaks with no error).
ATTN_FLASH_KEYS = ("wq", "wk", "wv", "wo")


def program_attn_flash(store: Any, attn_layers: Any, n_layers: int,
                       rber: float = 0.0, seed: int = 0) -> None:
    """Program the per-layer attn flash copies into ``store`` under
    ``attn_flash/{key}@{layer}`` — numerically identical to the resident
    engine's ``_flash_attn_copy`` tier (same quant/parity/RBER seeds)."""
    for li in range(n_layers):
        for k in ATTN_FLASH_KEYS:
            store.put(f"attn_flash/{k}@{li}",
                      encode_flash(attn_layers[k][li], rber=rber,
                                   seed=seed + li))


def dram_tier(params: Any, patterns=DEFAULT_FLASH_PATTERNS) -> Any:
    """The DRAM-tier remainder of a raw param pytree WITHOUT encoding the
    flash tier: flash-pattern leaves are dropped, everything else is cast
    bf16 — structurally identical to ``drop_store_refs(deploy(params,
    store=...))``, so it is the restore TEMPLATE for the DRAM checkpoint
    ``launch/deploy.py --store`` writes (``serve --store-image``)."""
    def rec(tree, prefix):
        if not isinstance(tree, dict):
            return tree
        out = {}
        for k, v in tree.items():
            p = f"{prefix}/{k}" if prefix else str(k)
            if isinstance(v, dict):
                out[k] = rec(v, p)
            elif is_flash_path(p, patterns) and v.ndim >= 2:
                continue
            else:
                out[k] = v.astype(jnp.bfloat16)
        return out
    return rec(params, "")


def flash_bytes(tiered: Any) -> tuple[int, int]:
    """(flash_tier_bytes, dram_tier_bytes) of a deployed pytree. Handles
    both deployment shapes: device-resident FlashWeight leaves and
    store-resident StoreRef placeholders (``deploy(store=...)``)."""
    fb = db = 0
    for leaf in jax.tree_util.tree_leaves(
        tiered, is_leaf=lambda x: isinstance(x, FlashWeight)
    ):
        if isinstance(leaf, FlashWeight):
            fb += leaf.nbytes()
        elif getattr(leaf, "is_store_ref", False):
            fb += leaf.nbytes
        else:
            db += leaf.size * leaf.dtype.itemsize
    return fb, db
