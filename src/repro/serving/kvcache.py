"""Block-paged, device-resident KV pool for continuous batching.

The DRAM tier of NVLLM ("attention weights and KV cache stay in DRAM", §3)
is carved into fixed-size BLOCKS instead of per-slot contiguous regions —
the nano-vLLM block-manager design, and the software analogue of the
paper's NAND/DRAM page granularity: a block is the unit the tier manager
moves and the unit the paged-attention kernel streams.

Layout (DESIGN.md §6):

  * ``k`` / ``v``: ``(n_layers, n_blocks, block_size, KV * Dh)`` on device.
    The KV heads are folded into the minor dimension so it is lane-dense on
    the TPU: a separate ``Dh`` = 64 minor dimension fills only half of a
    128-lane tile, and XLA then lays the pool out with the block dimension
    minor-most and re-lays the whole pool around every step's scatter.
    Block 0 is a RESERVED dump block — never allocated, never read (length
    masks exclude it); padded block-table entries and the compiled step's
    out-of-range scatter lanes land there, which keeps every write
    unconditional and jit-static.
  * ``block_tables``: host ``(n_slots, max_blocks)`` int32 mapping a slot's
    logical block index to a pool block id (0 = unmapped). Uploaded to the
    compiled step each call (a few hundred bytes; never retraces).
  * ``lengths_dev`` flows through the compiled step as donated device state
    (the step bumps it in-graph); ``lengths`` is the host MIRROR the control
    plane keeps in sync without device syncs.

The allocator is host-side control plane: a free list plus per-block ref
counts (ref counts > 1 are reserved for prefix sharing). Admission RESERVES
a request's worst-case block count up front, so lazily growing slots can
never deadlock on an exhausted pool mid-flight; physical blocks are still
mapped on demand (``ensure``), one chunk ahead of the writes.

``release`` is O(1) host bookkeeping: freed blocks keep their stale K/V
(already unreachable — no live block table maps them and length masks
bound every read) so completing a request issues ZERO device work.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def row_slots(positions, q_lens, block_tables, block_size: int):
    """Pool coordinates ``(blk, off)``, each (slots, T), of every chunk
    lane's K/V row: lane ``t`` of slot ``b`` holds absolute position
    ``positions[b, t]``. Lanes past ``q_lens`` (and any unmapped table hit)
    go to the dump block 0, so a write through them is unconditional."""
    lane = jnp.arange(positions.shape[1])[None, :]
    valid = lane < q_lens[:, None]
    blk_idx = jnp.clip(positions // block_size, 0, block_tables.shape[1] - 1)
    blk = jnp.take_along_axis(block_tables, blk_idx, axis=1)
    return (jnp.where(valid, blk, 0),
            jnp.where(valid, positions % block_size, 0))


def write_rows(pool, layer, blk, off, rows):
    """Scatter chunk rows into the pool at ``[layer, blk, off]``.

    ``rows`` is (slots, T, KV, Dh) for one ``layer`` (an index, traced or
    not), or (L, slots, T, KV, Dh) with ``layer = slice(None)`` for every
    layer at once."""
    rows = rows.reshape(rows.shape[:-2] + (-1,)).astype(pool.dtype)
    return pool.at[layer, blk, off].set(rows)


@dataclasses.dataclass
class PagedKVPool:
    n_layers: int
    n_slots: int
    max_seq: int
    n_kv_heads: int
    head_dim: int
    dtype: type = jnp.bfloat16
    block_size: int = 16
    n_blocks: int | None = None          # total pool blocks incl. dump block

    def __post_init__(self):
        self.max_blocks = cdiv(self.max_seq, self.block_size)
        if self.n_blocks is None:
            # fully provisioned by default; pass fewer to actually page
            self.n_blocks = self.n_slots * self.max_blocks + 1
        assert self.n_blocks >= 2, "need at least the dump block + one real"
        shape = (self.n_layers, self.n_blocks, self.block_size,
                 self.n_kv_heads * self.head_dim)
        self.k = jnp.zeros(shape, self.dtype)
        self.v = jnp.zeros(shape, self.dtype)
        self.lengths = np.zeros((self.n_slots,), np.int32)
        self.lengths_dev = jnp.zeros((self.n_slots,), jnp.int32)
        self.block_tables = np.zeros((self.n_slots, self.max_blocks), np.int32)
        self.ref_count = np.zeros((self.n_blocks,), np.int32)
        self.free_blocks = list(range(1, self.n_blocks))[::-1]  # 0 = dump
        self.free_slots = list(range(self.n_slots))[::-1]
        self.reserved = np.zeros((self.n_slots,), np.int32)  # unmapped claim
        self.active: dict[int, int] = {}                     # slot -> rid

    # --- capacity arithmetic -------------------------------------------------

    def blocks_for(self, n_tokens: int) -> int:
        return cdiv(max(n_tokens, 0), self.block_size)

    @property
    def n_free_blocks(self) -> int:
        """Blocks neither mapped nor reserved by an admitted request."""
        return len(self.free_blocks) - int(self.reserved.sum())

    def n_mapped(self, slot: int) -> int:
        return int(np.count_nonzero(self.block_tables[slot]))

    def capacity(self, slot: int) -> int:
        """Tokens the slot's mapped blocks can hold."""
        return self.n_mapped(slot) * self.block_size

    # --- slot lifecycle ------------------------------------------------------

    def alloc(self, request_id: int, need_tokens: int,
              shared_blocks=()) -> int | None:
        """Admit a request: claim a slot and RESERVE its worst-case block
        count (``need_tokens`` KV rows). Returns the slot, or None when no
        slot is free or the reservation would oversubscribe the pool.

        ``shared_blocks`` is the prefix-cache hit: already-populated pool
        blocks the request ADOPTS copy-free — they map at the head of the
        slot's table with a ref bump each (never drawn from the free
        list), the slot's length starts past them, and only the tail of
        the worst case is reserved. The caller (the prefix index) must
        hold its own ref on every shared block, so adoption can never
        race a concurrent free."""
        need_blocks = self.blocks_for(need_tokens)
        if need_blocks > self.max_blocks:
            raise ValueError(
                f"request needs {need_tokens} KV rows > "
                f"max_seq={self.max_blocks * self.block_size}")
        n_shared = len(shared_blocks)
        assert n_shared < max(need_blocks, 1), \
            "shared prefix must leave >= 1 tail block to prefill/decode"
        if not self.free_slots or need_blocks - n_shared > self.n_free_blocks:
            return None
        slot = self.free_slots.pop()
        self.active[slot] = request_id
        self.reserved[slot] = need_blocks - n_shared
        for i, blk in enumerate(shared_blocks):
            blk = int(blk)
            assert self.ref_count[blk] > 0, "adopting an unreferenced block"
            self.ref_count[blk] += 1
            self.block_tables[slot, i] = blk
        cached_len = n_shared * self.block_size
        self.lengths[slot] = cached_len
        self.lengths_dev = self.lengths_dev.at[slot].set(cached_len)
        return slot

    def ensure(self, slot: int, new_len: int):
        """Map physical blocks so the slot can hold ``new_len`` tokens,
        drawing from its admission reservation."""
        want = self.blocks_for(new_len)
        have = self.n_mapped(slot)
        for i in range(have, want):
            assert self.reserved[slot] > 0, "grew past admission reservation"
            blk = self.free_blocks.pop()
            assert self.ref_count[blk] == 0
            self.ref_count[blk] = 1
            self.block_tables[slot, i] = blk
            self.reserved[slot] -= 1

    def release(self, slot: int):
        """O(1) bookkeeping, ZERO device work: stale K/V in freed blocks is
        unreachable (no table maps it; length masks bound every read), so
        nothing is zeroed (the seed pool's two full-pool ``.at[].set(0)``
        writes per completed request are gone — benchmarks/serve_mixed.py
        asserts k/v/lengths buffers are all untouched). Even the slot's
        length stays stale — an idle slot is excluded from every read by
        its zero lane count, and ``alloc`` resets both length views before
        the slot is reused."""
        self.active.pop(slot, None)
        for i in range(self.max_blocks):
            blk = int(self.block_tables[slot, i])
            if blk == 0:
                continue
            self.ref_count[blk] -= 1
            if self.ref_count[blk] == 0:
                self.free_blocks.append(blk)
            self.block_tables[slot, i] = 0
        self.reserved[slot] = 0
        self.free_slots.append(slot)

    # --- prefix-cache ref plumbing (serving/prefix.py) ------------------------

    def ref(self, blk: int):
        """Take one ref on a LIVE block (the prefix index retaining a
        completed request's prompt blocks before its slot releases)."""
        assert blk != 0 and self.ref_count[blk] > 0, \
            "prefix retain of a free/dump block"
        self.ref_count[blk] += 1

    def deref(self, blk: int) -> bool:
        """Drop one ref; frees the block at zero. Returns True if freed."""
        assert self.ref_count[blk] > 0, "deref underflow"
        self.ref_count[blk] -= 1
        if self.ref_count[blk] == 0:
            self.free_blocks.append(blk)
            return True
        return False

    def bump(self, slot: int, n: int = 1):
        """Advance the HOST mirror after a step (the device lengths were
        already bumped in-graph by the compiled step)."""
        self.lengths[slot] += n

    def rewind(self, slot: int, new_len: int):
        """Speculative-decode KV rollback: set the slot's accepted length.

        A verify step writes K/V for ALL its lanes (last token + k drafts)
        but only ``n_accept + 1`` of those rows become part of the
        sequence; the rollback is a LENGTH rewind only — host mirror here,
        device lengths in-graph by the verify step — because the rejected
        rows are unreachable (every read is bounded by the length) and are
        overwritten in place by later steps before they ever become valid.
        Blocks mapped for the rejected lanes STAY mapped and ref-counted:
        the slot's length will grow back through them, so unmapping would
        just churn the free list (invariants property-tested in
        tests/test_spec.py)."""
        assert slot in self.active, "rewind of an inactive slot"
        assert 0 <= new_len <= self.capacity(slot), \
            f"rewind to {new_len} outside mapped capacity {self.capacity(slot)}"
        self.lengths[slot] = new_len

    # --- device-facing views --------------------------------------------------

    def device_state(self) -> dict:
        """The pool's device-resident half, as fed to the compiled step."""
        return {"k": self.k, "v": self.v, "lengths": self.lengths_dev}

    def set_device_state(self, state: dict):
        self.k, self.v = state["k"], state["v"]
        self.lengths_dev = state["lengths"]

    def block_tables_dev(self) -> jnp.ndarray:
        return jnp.asarray(self.block_tables)
