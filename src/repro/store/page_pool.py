"""Device-resident weight page pool: NAND pages to compute, no host slabs.

The streamed data planes used to reassemble whole windows on the host —
per-name ``get_host`` detiling, per-param ``np.stack``, a ``device_put``
per FlashWeight, and (MoE) a per-layer ``jnp.stack`` re-slab — small-op
dispatch that cost a measured 7x against the resident engine. This module
is the fix, mirroring the paged KV pool (serving/kvcache.py) on the weight
side:

  * ONE device buffer ``(n_pages, 16 KiB) int8`` holds raw store pages —
    the same bytes the PageStore serialized, untouched (q tiles, parity
    runs, scale runs).
  * ``upload(names)`` moves a whole window in ONE staged transfer: one
    contiguous ``read_pages`` into a host staging buffer, one
    ``device_put``, one scatter into free pool slots — then returns the
    per-name PAGE TABLES (q tile grid + parity/scale runs) that
    ``core.tiering.PagedWeight`` / ``kernels/paged_ffn.py`` consume in
    place.
  * the allocator is host-side control plane: a free-slot list with O(1)
    release and double-free/leak guards (property-tested in
    tests/test_page_pool.py). ENTRY lifecycle — ref counts, pin, LRU/score
    eviction — stays in the ``ResidencyCache``/``ExpertCache`` layer, which
    frees an entry's slots through its eviction hook; the pool deliberately
    owns pages, not policies.

Two update disciplines, chosen at construction:

  * ``donate=False`` (default): every upload rebinds ``self.data`` to a
    NEW buffer (``.at[slots].set``), so any snapshot a dispatched
    computation captured stays valid forever. Simple, but the copy is
    O(pool bytes) per upload.
  * ``donate=True``: the scatter DONATES the pool buffer, so XLA writes
    the new pages in place — O(new pages) per upload, the 170x cheaper
    path the serving engine runs. The runtime orders the in-place write
    after every in-flight reader (PJRT usage events), but the OLD python
    handle dies at the donation, so consumers must snapshot-and-dispatch
    atomically against concurrent uploads via ``dispatch(fn)`` (same
    lock as the allocator). Slot reuse stays safe for the same reason as
    before: a freed slot is unreachable from every live table, and the
    one buffer everyone shares always holds the latest upload.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.obs.registry import Sample
from repro.obs.trace import TID_POOL, default_tracer

# In-place page scatter for donate=True pools: donating the buffer lets
# XLA write only the new rows (measured ~170x cheaper than the functional
# copy at serving pool sizes, CPU backend included). Module-level so every
# pool shares one jit cache (retraces only on a new staged-page count).
_scatter_donate = jax.jit(lambda buf, slots, pages: buf.at[slots].set(pages),
                          donate_argnums=(0,))


def pinned_host_sharding():
    """The page-locked host staging target for upload H2D, or None on CPU.

    Accelerators expose a ``pinned_host`` memory space; staging the window
    there turns the device copy into an async DMA out of locked memory
    (the classic memcpy-into-pinned + async-H2D pipeline). The CPU backend
    has no DMA to hide, so it keeps the plain ``device_put``. An
    accelerator WITHOUT the space is an error, not a silent downgrade:
    the streamed planes' transfer path would quietly change."""
    if jax.default_backend() == "cpu":
        return None
    dev = jax.local_devices()[0]
    kinds = {m.kind for m in dev.addressable_memories()}
    if "pinned_host" not in kinds:
        raise RuntimeError(
            f"{dev.device_kind} exposes no pinned_host memory space "
            f"(has {sorted(kinds)}): cannot arm pinned upload staging")
    return jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host")


class WeightPagePool:
    """Device page pool + host slot allocator over a ``PageStore``."""

    def __init__(self, store: Any, n_pages: int, donate: bool = False):
        self.store = store
        self.donate = bool(donate)
        self.page_bytes = int(store.page_bytes)
        self.n_pages = max(int(n_pages), 1)
        self.data = jnp.zeros((self.n_pages, self.page_bytes), jnp.int8)
        self._free: list[int] = list(range(self.n_pages))[::-1]
        self._allocated: set[int] = set()
        self._lock = threading.Lock()
        self.grows = 0
        self._init_staging()
        self.reset_counters()

    def _init_staging(self):
        """Pinned-staging transfer state: a REUSABLE host staging buffer
        (grown geometrically, never shrunk) that ``read_pages`` fills in
        place, bounced through page-locked memory so the device copy is an
        async DMA. Reusing the buffer is only safe once the bytes have
        landed in jax-owned pinned memory (the bounce blocks on that
        host-side memcpy; the H2D out of it stays async). On the CPU
        backend the upload path is the one-shot ``device_put``."""
        self._pinned = pinned_host_sharding()
        self._staging: np.ndarray | None = None
        self.staging_allocs = 0

    def reset_counters(self):
        """Zero the transfer counters (init-time pin uploads are deployment,
        not serving — mirrors PageStore.reset_counters)."""
        with self._lock:
            self.uploads = 0
            self.pages_staged = 0
            self.bytes_staged = 0
            self.pinned_uploads = 0

    def _stage_host(self, n_rows: int) -> np.ndarray:
        """First ``n_rows`` page rows of the reusable staging buffer."""
        if self._staging is None or self._staging.shape[0] < n_rows:
            cap = max(n_rows, 2 * (0 if self._staging is None
                                   else self._staging.shape[0]))
            self._staging = np.empty((cap, self.page_bytes), np.uint8)
            self.staging_allocs += 1
        return self._staging[:n_rows]

    def _read_staged(self, ids: np.ndarray) -> jnp.ndarray:
        """Store pages -> device array, through the pinned bounce when one
        is armed. The pinned hop blocks only on the host->pinned memcpy
        (making the staging rows reusable immediately); the pinned->device
        DMA is dispatched async and the scatter orders after it."""
        if self._pinned is None:
            return jax.device_put(self.store.read_pages(ids).view(np.int8))
        rows = self._stage_host(len(ids))
        staged = self.store.read_pages(ids, out=rows).view(np.int8)
        locked = jax.device_put(staged, self._pinned)
        locked.block_until_ready()
        self.pinned_uploads += 1
        # the target names its memory kind: a bare device would keep the
        # source's pinned_host kind and be refused
        return jax.device_put(locked, self._pinned.with_memory_kind("device"))

    # --- allocator -----------------------------------------------------------

    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def used_pages(self) -> int:
        with self._lock:
            return len(self._allocated)

    def _grow(self, need: int):
        """Reallocate the device buffer (under the lock). Sized-at-init
        pools should never hit this in steady state — a grow REBINDS the
        buffer shape and costs the jitted consumers a retrace."""
        cap = max(2 * self.n_pages, self.n_pages + need)
        self.data = jnp.zeros((cap, self.page_bytes), jnp.int8
                              ).at[:self.n_pages].set(self.data)
        self._free.extend(range(self.n_pages, cap))
        self.n_pages = cap
        self.grows += 1

    def free(self, slots: Iterable[int]):
        """O(1)-per-slot release. Stale page bytes stay in place — already
        unreachable: no live entry's table names the slot (and under
        ``donate=False``, any snapshot holding the old table also holds
        the old buffer)."""
        with self._lock:
            for s in slots:
                s = int(s)
                if s not in self._allocated:
                    raise ValueError(f"free of unallocated pool slot {s}")
                self._allocated.remove(s)
                self._free.append(s)

    # --- the one staged transfer ---------------------------------------------

    def upload(self, names: Iterable[str]) -> dict[str, dict]:
        """Upload every page of ``names`` (store entry names) in ONE staged
        transfer and return per-name page tables:

          {name: {"q_tbl" (kt, nt) i32, "p_slots" (np,) i32,
                  "s_slots" (ns,) i32, "kn" (K, N), "slots" (all,) i32}}

        ``slots`` is the hand-back token for ``free``. Runs on the streamer
        worker, the expert prefetcher, or the compute path — the lock
        serializes the rebind of ``self.data``."""
        names = list(names)
        plan: list[tuple[str, str, list[int]]] = []   # (name, comp, page_ids)
        for name in names:
            entry = self.store.table[name]
            for comp in ("q", "parity", "scale"):
                plan.append((name, comp, entry[comp].pages))
        ids = np.concatenate([np.asarray(p, np.int64) for _, _, p in plan])
        tracer = default_tracer()
        t0 = time.perf_counter() if tracer.enabled else 0.0
        with self._lock:
            if len(ids) > len(self._free):
                self._grow(len(ids) - len(self._free))
            slots = np.array([self._free.pop() for _ in range(len(ids))],
                             np.int32)
            self._allocated.update(int(s) for s in slots)
            # one contiguous host staging read, one (possibly pinned-
            # bounced) device transfer, one scatter. A FAULTED read (the
            # injector's transient IOError, a dying mmap) must hand the
            # window's slots back before propagating — a retried upload
            # re-allocates; a leaked slot is gone for the process.
            try:
                staged = self._read_staged(ids)
            except Exception:
                self._allocated.difference_update(int(s) for s in slots)
                self._free.extend(int(s) for s in slots)
                raise
            if self.donate:
                # in-place: the runtime sequences the write after every
                # in-flight reader; the lock orders it against dispatch()
                self.data = _scatter_donate(self.data, jnp.asarray(slots),
                                            staged)
            else:
                self.data = self.data.at[jnp.asarray(slots)].set(staged)
            self.uploads += 1
            self.pages_staged += int(ids.size)
            self.bytes_staged += int(ids.size) * self.page_bytes
        tracer.complete("pool.upload", t0, time.perf_counter() - t0,
                        tid=TID_POOL, cat="pool",
                        args={"pages": int(ids.size),
                              "bytes": int(ids.size) * self.page_bytes})
        out: dict[str, dict] = {}
        off = 0
        for name, comp, pages in plan:
            n = len(pages)
            span = slots[off:off + n]
            off += n
            tbl = out.setdefault(name, {})
            if comp == "q":
                kt, nt = self.store.table[name]["q"].grid
                tbl["q_tbl"] = span.reshape(kt, nt).copy()
                tbl["kn"] = tuple(self.store.table[name]["q"].shape)
            elif comp == "parity":
                tbl["p_slots"] = span.copy()
            else:
                tbl["s_slots"] = span.copy()
        for name, tbl in out.items():
            tbl["slots"] = np.concatenate(
                [tbl["q_tbl"].reshape(-1), tbl["p_slots"], tbl["s_slots"]])
        return out

    # --- device-facing view ---------------------------------------------------

    @property
    def buffer(self) -> jnp.ndarray:
        """The CURRENT pool snapshot. With ``donate=False`` it is safe to
        capture at dispatch time for any entry whose slots are live —
        later uploads/frees only rebind FUTURE buffers. With
        ``donate=True`` the handle dies at the next upload: use
        ``dispatch`` so the snapshot-and-dispatch is atomic."""
        return self.data

    def dispatch(self, fn):
        """Run ``fn(buffer)`` under the pool lock and return its result —
        the REQUIRED dispatch discipline for ``donate=True`` pools: a
        concurrent upload donates (deletes) the python handle ``fn`` would
        otherwise race to capture. ``fn`` should only DISPATCH device
        compute (async), never block on results, or prefetch uploads
        queue behind it."""
        with self._lock:
            return fn(self.data)

    def stats(self) -> dict:
        with self._lock:
            return {"pool_pages": self.n_pages,
                    "pool_free_pages": len(self._free),
                    "pool_used_pages": len(self._allocated),
                    "pool_uploads": self.uploads,
                    "pool_pages_staged": self.pages_staged,
                    "pool_bytes_staged": self.bytes_staged,
                    "pool_pinned_uploads": self.pinned_uploads,
                    "pool_staging_allocs": self.staging_allocs,
                    "pool_grows": self.grows}

    def obs_samples(self):
        """ObsPlane scrape samples. LOCK-FREE by design: ``upload`` holds
        the pool lock across a whole staged transfer, so a locked read
        here would make /v1/metrics wait behind a device upload."""
        yield Sample("pool_pages", "gauge", float(self.n_pages))
        yield Sample("pool_free_pages", "gauge", float(len(self._free)))
        yield Sample("pool_uploads_total", "counter", float(self.uploads))
        yield Sample("pool_pages_staged_total", "counter",
                     float(self.pages_staged))
        yield Sample("pool_bytes_staged_total", "counter",
                     float(self.bytes_staged))
        yield Sample("pool_pinned_uploads_total", "counter",
                     float(self.pinned_uploads))
        yield Sample("pool_grows_total", "counter", float(self.grows))


class ShardedWeightPagePool(WeightPagePool):
    """The tensor-parallel pool: ONE logical pool whose pages live sharded
    across the mesh's "model" axis, ``n_pages`` LOCAL slots per device.

    The decisive simplification is SYMMETRIC slots: every shard uses the
    same local slot ids for the same entry (per-shard page counts are equal
    by the divisibility rule in ``PageStore.shard_entry``), so ONE host
    free-list allocates for all shards at once and the returned page
    tables are ordinary replicated host arrays in the exact unsharded
    format — ``q_tbl`` over the shard-LOCAL grid with the shard-LOCAL
    ``kn``, consumed unchanged by ``kernels/paged_ffn.py`` inside a
    ``shard_map`` whose pool in_spec is ``P("model", None)``.

    ``upload`` rotates a window as ONE staged transfer PER SHARD: one host
    staging assembly ``(n_shards, n_slots, page_bytes)``, one sharded
    ``device_put`` (XLA issues exactly one H2D per device), one donated
    ``shard_map`` scatter. ``shard_transfers`` counts them — the benchmark
    gate asserts transfers == n_shards x rotations.

    Which entries split, and along which axis, is ``axis_of`` (default
    ``launch.sharding.tp_shard_axis``): w_gate/w_up tile-column round-robin
    (column-parallel), w_down tile-rows (row-parallel), attention copies /
    routers replicated. Parity and scale runs follow their tiles
    (``PageStore.shard_host_slices``)."""

    def __init__(self, store: Any, n_pages: int, mesh,
                 axis_of: Callable[[str], int | None] | None = None,
                 donate: bool = True):
        self.store = store
        self.mesh = mesh
        self.n_shards = int(mesh.shape["model"])
        self.donate = bool(donate)
        self.page_bytes = int(store.page_bytes)
        self.n_pages = max(int(n_pages), 1)        # LOCAL slots per shard
        if axis_of is None:
            from repro.launch.sharding import tp_shard_axis
            axis_of = tp_shard_axis
        self._axis_of = axis_of
        self._plans: dict[str, Any] = {}           # ShardPlan memo per entry
        self._sh2 = NamedSharding(mesh, P("model", None))
        self._sh3 = NamedSharding(mesh, P("model", None, None))
        self.data = jax.device_put(
            np.zeros((self.n_shards * self.n_pages, self.page_bytes),
                     np.int8), self._sh2)
        self._free = list(range(self.n_pages))[::-1]
        self._allocated = set()
        self._lock = threading.Lock()
        self.grows = 0
        # per-mesh jits (module-level sharing would leak meshes across tests)
        self._scatter = jax.jit(
            jax.shard_map(lambda buf, slots, pages: buf.at[slots[0]].set(
                pages[0]),
                mesh=mesh,
                in_specs=(P("model", None), P("model", None),
                          P("model", None, None)),
                out_specs=P("model", None), check_vma=False),
            donate_argnums=(0,) if self.donate else ())
        self._copy_grow = jax.jit(
            jax.shard_map(lambda nb, ob: nb.at[:ob.shape[0]].set(ob),
                      mesh=mesh,
                      in_specs=(P("model", None), P("model", None)),
                      out_specs=P("model", None), check_vma=False),
            donate_argnums=(0,))
        self._init_staging()
        self.reset_counters()

    def reset_counters(self):
        super().reset_counters()
        with self._lock:
            self.shard_transfers = 0

    def _grow(self, need: int):
        """Grow every shard's partition in lockstep (slot symmetry must
        survive). Costs the jitted consumers a retrace, like the base."""
        cap = max(2 * self.n_pages, self.n_pages + need)
        new = jax.device_put(
            np.zeros((self.n_shards * cap, self.page_bytes), np.int8),
            self._sh2)
        self.data = self._copy_grow(new, self.data)
        self._free.extend(range(self.n_pages, cap))
        self.n_pages = cap
        self.grows += 1

    def plan(self, name: str):
        """The (memoized) ShardPlan for one entry — the page table is
        write-once, so the round-robin partition never changes."""
        p = self._plans.get(name)
        if p is None:
            p = self._plans[name] = self.store.shard_entry(
                name, self.n_shards, self._axis_of(name))
        return p

    def upload(self, names: Iterable[str]) -> dict[str, dict]:
        """Sharded window rotation: same contract as the base ``upload``
        but the returned tables are shard-LOCAL (local ``q_tbl`` grid,
        local ``kn``) and the transfer is one staged put per shard."""
        names = list(names)
        S = self.n_shards
        rows_plan: list[tuple[str, str, int]] = []  # (name, comp, n_pages)
        for name in names:
            p = self.plan(name)
            rows_plan += [
                (name, "q", len(p.q_pages[0])),
                (name, "parity", -(-p.parity_nbytes // self.page_bytes)),
                (name, "scale", -(-p.scale_nbytes // self.page_bytes))]
        n_slots = sum(n for _, _, n in rows_plan)
        tracer = default_tracer()
        t0 = time.perf_counter() if tracer.enabled else 0.0
        with self._lock:
            if n_slots > len(self._free):
                self._grow(n_slots - len(self._free))
            slots = np.array([self._free.pop() for _ in range(n_slots)],
                             np.int32)
            self._allocated.update(int(s) for s in slots)
            # same slot-leak guard as the base upload: a faulted staged
            # read returns the rotation's slots before propagating
            try:
                host = self._stage_shards(names, rows_plan, n_slots)
            except Exception:
                self._allocated.difference_update(int(s) for s in slots)
                self._free.extend(int(s) for s in slots)
                raise
            staged = jax.device_put(host.view(np.int8), self._sh3)
            slot_rows = jax.device_put(np.tile(slots[None], (S, 1)),
                                       self._sh2)
            self.data = self._scatter(self.data, slot_rows, staged)
            self.uploads += 1
            self.shard_transfers += S
            self.pages_staged += n_slots * S
            self.bytes_staged += n_slots * S * self.page_bytes
        tracer.complete("pool.upload_sharded", t0,
                        time.perf_counter() - t0, tid=TID_POOL, cat="pool",
                        args={"shards": S, "pages": n_slots * S,
                              "bytes": n_slots * S * self.page_bytes})
        out: dict[str, dict] = {}
        off = 0
        for name, comp, n in rows_plan:
            span = slots[off:off + n]
            off += n
            p = self.plan(name)
            tbl = out.setdefault(name, {})
            if comp == "q":
                tbl["q_tbl"] = span.reshape(p.local_grid).copy()
                tbl["kn"] = tuple(p.local_kn)
            elif comp == "parity":
                tbl["p_slots"] = span.copy()
            else:
                tbl["s_slots"] = span.copy()
        for name, tbl in out.items():
            tbl["slots"] = np.concatenate(
                [tbl["q_tbl"].reshape(-1), tbl["p_slots"], tbl["s_slots"]])
        return out

    def _stage_shards(self, names: list[str], rows_plan, n_slots: int
                      ) -> np.ndarray:
        """Assemble the (n_shards, n_slots, page_bytes) host staging for
        one rotation. q pages read per shard (distinct global pages, each
        read once); parity/scale sliced host-side by shard_host_slices
        (pages read once, not once per shard); replicated entries read
        once and broadcast into every shard's rows."""
        S = self.n_shards
        host = np.zeros((S, n_slots, self.page_bytes), np.uint8)
        slices = {n: self.store.shard_host_slices(n, self.plan(n))
                  for n in names}
        off = 0
        for name, comp, n in rows_plan:
            p = self.plan(name)
            if comp == "q":
                if p.axis is None:
                    host[:, off:off + n] = self.store.read_pages(
                        p.q_pages[0])[None]
                else:
                    for s in range(S):
                        self.store.read_pages(p.q_pages[s],
                                              out=host[s, off:off + n])
            else:
                idx = 0 if comp == "parity" else 1
                for s in range(S):
                    flat = np.frombuffer(slices[name][s][idx].tobytes(),
                                         np.uint8)
                    host[s, off:off + n].reshape(-1)[:flat.size] = flat
            off += n
        return host

    def stats(self) -> dict:
        base = super().stats()
        with self._lock:
            base.update({
                "pool_shards": self.n_shards,
                "pool_shard_transfers": self.shard_transfers,
                "pool_local_pages": self.n_pages,
                "pool_local_bytes": self.n_pages * self.page_bytes})
        return base

    def obs_samples(self):
        yield from super().obs_samples()
        yield Sample("pool_shards", "gauge", float(self.n_shards))
        yield Sample("pool_shard_transfers_total", "counter",
                     float(self.shard_transfers))
