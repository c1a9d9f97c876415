"""Store-backed data planes (DESIGN.md §7, §11): the flash tier lives in the
host-resident PageStore and reaches the device through a page pool.
``StorePlane`` holds what both such planes share (deploy into the store,
the "model" mesh, per-shard entry sizes, the device weight page pool, the
DRAM halves and the pinned lm_head); ``StreamedPlane`` rotates dense layer
groups through a prefetch window under a residency cache. The expert-paged
MoE plane builds on ``StorePlane`` in ``serving/expert_paged.py``.

The control plane (``serving/engine.py``) calls its one plane — which
never holds the engine — through ``step`` (``lanes`` bounds each slot's
valid lanes on the host), ``stall_s`` (seconds blocked on the weight
stream, which the admission budget contracts with), ``after_step``
(one-shot retunes), ``stream_stats``/``expert_stats`` (None where they do
not apply), ``obs_samples``, ``replicate`` and ``close``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.erdpe import ExecMode
from repro.core.tiering import ATTN_FLASH_KEYS, deploy, program_attn_flash
from repro.serving.step import (_embed_chunk, _embed_spec, _finish_step,
                                _jit, _stream_group_impl)
from repro.store.pagestore import StoreRef, drop_store_refs
from repro.store.streamer import StreamConfig


class StorePlane:
    """The flash tier in ``store``, the mesh and the device page pool."""

    def __init__(self, cfg, params, store, stream_cfg, *, rber, seed,
                 exec_mode, kv_aware, sched_cfg, sample_cfg, proposer,
                 spec_k, phase):
        self.cfg, self.store = cfg, store
        self.stream_cfg = sc = stream_cfg or StreamConfig()
        self.kv_aware, self.sched_cfg = kv_aware, sched_cfg
        self.sample_cfg, self.proposer = sample_cfg, proposer
        self._spec_k, self._exec_mode, self._phase = spec_k, exec_mode, phase
        self.traces = 0
        self._steps_done = 0             # the engine's count, at after_step
        # A store that ALREADY holds a page table is preprogrammed — opened
        # from a persisted die image (``serve --store-image``). NAND
        # programming is write-once, so the flash tier is rebuilt from the
        # page table instead of re-deployed, and ``params`` is expected to
        # be the DRAM tier only (the checkpoint deploy --store wrote).
        self.store_preprogrammed = len(store.table) > 0
        if self.store_preprogrammed:
            from repro.store.pagestore import graft_store_refs
            if rber > 0.0:
                raise ValueError(
                    "rber applies at flash-programming time; a preprogrammed "
                    "store already carries its own error injection (re-run "
                    "deploy --store with --rber instead)")
            # cast the DRAM tier bf16 exactly as deploy() would: callers may
            # hand raw init params (or reuse a programmed store), and an f32
            # DRAM tier would silently diverge from every deployed engine.
            dram = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
            refs = store.param_refs(exclude_prefixes=("attn_flash/",))
            self.params = graft_store_refs(dram, refs)
            self.tier_map = {p: "flash" for p in refs}
        else:
            self.params, self.tier_map = deploy(params, rber=rber, seed=seed,
                                                store=store)
        # the "model" mesh (None when unsharded). Sharded serving runs the
        # XLA data plane: the paged Pallas kernel has no shard_map lowering.
        self.mesh = None
        if sc.n_shards > 1:
            if exec_mode == ExecMode.PALLAS:
                raise ValueError(
                    "n_shards > 1 serves through the XLA data plane "
                    "(exec_mode=XLA); the paged Pallas kernel has no "
                    "shard_map lowering yet")
            from repro.launch.mesh import make_model_mesh
            self.mesh = make_model_mesh(sc.n_shards)
        self._entry_plans: dict = {}

    def _split_dram(self):
        """DRAM-resident halves of the tiered pytree, fed to the jitted
        programs, and the pinned lm_head (read whole every step)."""
        self._layers_dram = self.replicate(
            drop_store_refs(self.params["layers"]))
        self._dram_params = self.replicate(
            {k: self.params[k] for k in ("embed", "pos_embed", "final_norm")
             if k in self.params})
        self._lm_head = self.replicate(self.store.get("lm_head"))

    def _stray_flash(self, prefix: str, layout: str):
        """Refuse flash leaves the plane would never fetch."""
        stray = [p for p, t in self.tier_map.items()
                 if t == "flash" and p != "lm_head"
                 and not p.startswith(prefix)]
        if stray:
            raise ValueError(f"{layout} expects the flash layout ({prefix}* "
                             f"+ lm_head); stray flash leaves would silently "
                             f"never be fetched: {stray}")

    # --- tensor-parallel sizing (DESIGN.md §11) ------------------------------

    def _entry_plan(self, name: str):
        """ShardPlan for one store entry (sharded mode only), memoized —
        the same plan the ShardedWeightPagePool derives, computed here too
        because pool SIZING needs per-shard page counts before the pool
        exists."""
        plan = self._entry_plans.get(name)
        if plan is None:
            from repro.launch.sharding import tp_shard_axis
            plan = self.store.shard_entry(name, self.stream_cfg.n_shards,
                                          tp_shard_axis(name))
            self._entry_plans[name] = plan
        return plan

    def _entry_pages_local(self, name: str) -> int:
        """Physical pool pages entry ``name`` occupies PER SHARD."""
        if self.mesh is None:
            return self.store.entry_pages(name)
        p = self._entry_plan(name)
        pb = self.store.page_bytes
        return (len(p.q_pages[0]) + -(-p.parity_nbytes // pb)
                + -(-p.scale_nbytes // pb))

    def _entry_kn(self, name: str) -> tuple:
        """The (K, N) the data plane binds for entry ``name`` — the full
        matrix unsharded, the shard-LOCAL partition under TP."""
        if self.mesh is None:
            return tuple(self.store.table[name]["q"].shape)
        return tuple(self._entry_plan(name).local_kn)

    def _check_shardable(self, names):
        """Refuse silent replication of entries the TP rules say must
        shard: the FFN psum is unconditional under TP, so a replicated
        w_gate/w_up/w_down would overcount the product n_shards times."""
        if self.mesh is None:
            return
        from repro.launch.sharding import tp_shard_axis
        bad = sorted({n.partition("@")[0] for n in names
                      if tp_shard_axis(n) is not None
                      and self._entry_plan(n).axis is None})
        if bad:
            s = self.stream_cfg.n_shards
            raise ValueError(
                f"n_shards={s} cannot partition {bad}: the sharded matrix "
                f"dim must divide into {s} whole 128-wide tile columns/rows "
                "(make d_ff/d_model a multiple of 128*n_shards, or lower "
                "n_shards)")

    def replicate(self, tree):
        """Commit a pytree replicated over the mesh. The mesh jits reject
        arrays COMMITTED to a single device, and leaving persistent inputs
        uncommitted would re-replicate them every call — so everything the
        step reads every step (DRAM tier, lm_head, serving state) lands
        here once."""
        if self.mesh is None:
            return tree
        sh = NamedSharding(self.mesh, P())
        return jax.tree.map(lambda a: jax.device_put(a, sh), tree)

    def _make_wpool(self, cache_cap, floor, what, tier_pages, worst):
        """Build the device weight page pool; returns the cache capacity,
        clamped per device when sharded. Windows upload as ONE staged
        transfer each and compute consumes the raw store pages in place.
        Sized in PHYSICAL pages (padded tiles inflate small params past
        their payload bytes): the cache's payload budget at ``worst`` local
        pages per global payload byte, plus the trace-static
        ``_pool_reserve_pages``, capped at the whole tier; budget
        ACCOUNTING stays payload-byte. Sharded, pages are PER-SHARD while
        the cache budget stays AGGREGATE: the clamp re-bounds the cache so
        one shard's pages fit its ~budget/n_shards share."""
        from repro.store.page_pool import (ShardedWeightPagePool,
                                           WeightPagePool)
        sc, pb = self.stream_cfg, self.store.page_bytes
        if cache_cap is not None and sc.n_shards > 1:
            cache_cap = min(cache_cap, int(sc.device_budget_bytes
                                           / (sc.n_shards * worst)))
            if cache_cap < floor:
                raise ValueError(
                    f"device_budget_bytes={sc.device_budget_bytes} over "
                    f"{sc.n_shards} shards leaves a per-device share too "
                    f"small for {what} ({floor}B); raise the budget")
        n_pages = tier_pages if cache_cap is None else min(
            tier_pages, -(-int(worst * cache_cap) // pb)
            + self._pool_reserve_pages)
        if self.mesh is None:
            self.wpool = WeightPagePool(self.store, n_pages, donate=True)
        else:
            self.wpool = ShardedWeightPagePool(self.store, n_pages,
                                               self.mesh, donate=True)
        return cache_cap

    def _free_window(self, *key_value):
        """Cache eviction hook ``(key, value)`` and streamer discard hook
        ``(value)``: hand a window's pool pages back to the allocator (safe
        immediately — eviction never fires on ref-held/pinned entries, a
        discard comes after the consumer retired the window, and any
        dispatched compute holds its own pool-buffer snapshot)."""
        value = key_value[-1]
        if isinstance(value, dict) and "slots" in value:
            self.wpool.free(value["slots"])

    # --- the jitted pieces ----------------------------------------------------

    def _over_mesh(self, fn, n_args, pool_arg):
        """``fn`` under ``shard_map`` over "model": the pool buffer
        (argument ``pool_arg``) splits its page rows, every other operand
        stays replicated, and ``axis_name`` closes each FFN with a psum."""
        from repro.launch.mesh import MODEL_AXIS
        from repro.launch.sharding import stream_window_specs
        specs = stream_window_specs(self.mesh)
        in_specs = [specs["replicated"]] * n_args
        in_specs[pool_arg] = specs["pool"]
        return jax.shard_map(functools.partial(fn, axis_name=MODEL_AXIS),
                             mesh=self.mesh, in_specs=tuple(in_specs),
                             out_specs=specs["replicated"], check_vma=False)

    def _program(self, name, fn, donate_state=False):
        """``fn`` jitted as ``jit_<name>``, its traces counted (the Python
        body only runs while jax traces). Under the mesh every output is
        pinned replicated so the carried serving state stays mesh-legal;
        ``donate_state`` donates argument 2, the serving state (CPU ignores
        donation and warns, so only donate where it lands)."""
        def traced(*args):
            self.traces += 1
            return fn(*args)
        traced.__name__ = name
        kw = {}
        if self.mesh is not None:
            kw["out_shardings"] = NamedSharding(self.mesh, P())
        if donate_state and jax.default_backend() != "cpu":
            kw["donate_argnums"] = (2,)
        return _jit(traced, **kw)

    def obs_samples(self):
        yield from self.store.obs_samples()
        yield from self.wpool.obs_samples()

    def close(self):
        pass


class StreamedPlane(StorePlane):
    """Dense streamed plane (DESIGN.md §7): the flash tier never sits
    device-resident as a whole — the streamer fills group l+1's window
    while group l's asynchronously-dispatched compute runs. Init programs
    the per-layer attn flash copies next to deploy()'s FFN/lm_head entries
    and stands up the residency cache + layer streamer under the device
    weight budget."""

    def __init__(self, cfg, params, store, stream_cfg, **kw):
        from repro.store.streamer import LayerStreamer, ResidencyCache
        super().__init__(cfg, params, store, stream_cfg, **kw)
        sc = self.stream_cfg
        if cfg.n_layers % sc.group_size:
            raise ValueError(f"group_size={sc.group_size} must divide "
                             f"n_layers={cfg.n_layers}")
        # per-layer flash Q/K/V/O copies, same seed derivation as the
        # resident plane's flash_attn_copy (numerically identical tiers).
        # A preprogrammed store (die image) normally carries them already —
        # deploy --store emits them — so only the MISSING copies are
        # programmed; a read-only image without them cannot be fixed here.
        if f"attn_flash/{ATTN_FLASH_KEYS[0]}@0" not in store.table:
            if isinstance(store._data, np.memmap):
                raise ValueError(
                    "die image lacks the per-layer attn flash copies and is "
                    "read-only; re-run launch/deploy.py --store (it emits "
                    "them) or serve from a writable store")
            program_attn_flash(store, params["layers"]["attn"],
                               cfg.n_layers, rber=kw["rber"], seed=kw["seed"])
        self._ffn_refs = {k: v for k, v in self.params["layers"]["ffn"].items()
                          if isinstance(v, StoreRef)}
        self._stray_flash("layers/ffn/", "streamed mode")
        self._split_dram()
        self.n_groups = cfg.n_layers // sc.group_size
        self._check_shardable(self._group_entries(0))

        group_names = [self._group_entries(g) for g in range(self.n_groups)]
        group_nbytes = [sum(store.entry_nbytes(n) for n in names)
                        for names in group_names]
        self._group_bytes = max(group_nbytes)  # depth auto-tuning re-budgets
        lm_bytes = store.entry_nbytes("lm_head")
        # the rotating window holds up to prefetch_depth groups in flight;
        # whatever budget remains is residency-cache capacity.
        window_bytes = sc.prefetch_depth * self._group_bytes
        if sc.device_budget_bytes is None or sc.pin_all:
            cache_cap = None
        else:
            cache_cap = sc.device_budget_bytes - window_bytes
            if cache_cap < lm_bytes:
                raise ValueError(
                    f"device_budget_bytes={sc.device_budget_bytes} cannot "
                    f"hold {sc.prefetch_depth} prefetch windows "
                    f"({window_bytes}B) + pinned lm_head ({lm_bytes}B)")
        group_pages = [sum(self._entry_pages_local(n) for n in names)
                       for names in group_names]
        # local pool pages per global cached payload byte at WINDOW
        # granularity: the cache charges aggregate payload bytes per
        # window, and a shard's backing pages don't split evenly —
        # replicated entries (attention) keep their FULL pages on every
        # shard — so a 1/n_shards budget split would undersize the pool,
        # and a mid-run grow costs a retrace.
        worst = max(gp * store.page_bytes / max(nb, 1)
                    for gp, nb in zip(group_pages, group_nbytes))
        # trace-static reservation: in-flight prefetch windows + one
        # retiring transient, in (local) pool pages — surfaced in
        # stream_stats so budget gates can separate it from cache bytes
        self._pool_reserve_pages = \
            (sc.prefetch_depth + 1) * max(group_pages)
        cache_cap = self._make_wpool(cache_cap, lm_bytes, "the pinned lm_head",
                                     sum(group_pages), worst)
        self._win_shapes = {
            "ffn": {k: self._entry_kn(ref.entry(0))
                    for k, ref in self._ffn_refs.items()},
            "attn": {k: self._entry_kn(f"attn_flash/{k}@0")
                     for k in ATTN_FLASH_KEYS},
        }
        self.cache = ResidencyCache(cache_cap, on_evict=self._free_window)
        self.streamer = LayerStreamer(self.n_groups, self._fetch_group,
                                      self.cache, sc.prefetch_depth,
                                      discard=self._free_window)
        # hot pins: lm_head is read EVERY step (sampling); first/last layer
        # groups bound the stream's cold start and tail when they fit.
        # lm_head stays a device FlashWeight (finish reads it whole every
        # step — residency, not rotation, so it skips the pool).
        self.cache.insert("lm_head", self._lm_head, lm_bytes, pin=True)
        if sc.pin_all:
            for g in range(self.n_groups):
                self.streamer.pin(g)
        elif sc.pin_edges:
            for g in dict.fromkeys((0, self.n_groups - 1)):
                self.streamer.pin(g)
        # init-time reads (lm_head fetch, pinned-group fetches) are
        # deployment, not serving: start the NAND/page accounting clean so
        # stream_stats reports what SERVING actually read.
        store.reset_counters()
        self.wpool.reset_counters()
        self._build_programs()
        self._auto_depth_done = False

    def _group_entries(self, g: int) -> list[str]:
        """Store entry names backing layer group ``g``'s device window."""
        lo = g * self.stream_cfg.group_size
        names = []
        for li in range(lo, lo + self.stream_cfg.group_size):
            names += [ref.entry(li) for ref in self._ffn_refs.values()]
            names += [f"attn_flash/{k}@{li}" for k in ATTN_FLASH_KEYS]
        return names

    def _fetch_group(self, g: int):
        """Upload one layer group's pages into the device page pool — ONE
        staged transfer for the whole window (the pool reads every entry's
        pages into one contiguous host staging buffer, one device_put, one
        scatter) — and assemble the window of (G,)-stacked PAGE TABLES the
        group trace binds to the pool. No host detiling, no per-param
        stacks, no per-param device_puts. Runs on the streamer's worker
        thread."""
        sc = self.stream_cfg
        lis = range(g * sc.group_size, (g + 1) * sc.group_size)
        tbls = self.wpool.upload(self._group_entries(g))

        def stack(names):
            ts = [tbls[n] for n in names]
            return {k: jnp.asarray(np.stack([t[k] for t in ts]))
                    for k in ("q_tbl", "p_slots", "s_slots")}

        win = {
            "ffn": {k: stack([ref.entry(li) for li in lis])
                    for k, ref in self._ffn_refs.items()},
            "attn": {k: stack([f"attn_flash/{k}@{li}" for li in lis])
                     for k in ATTN_FLASH_KEYS},
            # host bookkeeping: the hand-back token for pool free on
            # eviction/discard (stripped before the jitted group fn)
            "slots": np.concatenate([t["slots"] for t in tbls.values()]),
        }
        nbytes = sum(self.store.entry_nbytes(n) for n in self._group_entries(g))
        return win, nbytes

    def _build_programs(self):
        """Three jitted pieces (embed -> layer groups x N -> finish) instead
        of one monolithic step. The group fn takes its layer offset as a
        TRACED scalar, so all groups share one trace; steady state is
        exactly 3 traces total — speculative mode included (drafting folds
        into the embed trace, verification into the finish trace).

        Sharded (``StreamConfig.n_shards > 1``, DESIGN.md §11): the group
        fn runs under ``shard_map`` — the pool buffer splits its page rows
        over "model", everything else stays replicated, and the FFN's one
        psum per layer is the step's only collective."""
        group = functools.partial(_stream_group_impl, self.cfg,
                                  self._exec_mode, self.kv_aware,
                                  self.stream_cfg.group_size, self._win_shapes)
        if self.mesh is not None:
            # group args: (layers_dram, window, pool_buf, k, v, x,
            # positions, ctx_lens, block_tables, bitmap, lo)
            group = self._over_mesh(group, 11, 2)
        finish = functools.partial(_finish_step, self.cfg, self.sched_cfg,
                                   self.sample_cfg, self.kv_aware,
                                   self._spec_k)
        embed = (functools.partial(_embed_chunk, self.cfg)
                 if self._spec_k is None else functools.partial(
                     _embed_spec, self.cfg, self.proposer, self._spec_k))
        self._embed_fn = self._program("embed_fn", embed)
        self._group_fn = self._program("group_fn", group)
        self._finish_fn = self._program("finish_fn", finish,
                                        donate_state=True)

    def step(self, lanes, state, tokens, q_lens, admitted, block_tables,
             key, hist=None, hist_lens=None, draft_cap=None, is_decode=None):
        """In speculative mode the layer pass is shared by ALL of a slot's
        verify lanes: one window rotation per step amortizes over every
        accepted token."""
        with self._phase("embed"):
            if self._spec_k is None:
                drafts = n_draft = None
                x, positions, ctx_lens = self._embed_fn(
                    self._dram_params, state["lengths"], tokens, q_lens)
            else:
                x, positions, ctx_lens, q_lens, drafts, n_draft = \
                    self._embed_fn(self._dram_params, state["lengths"],
                                   tokens, q_lens, hist, hist_lens,
                                   draft_cap)
        ks, vs = [], []
        # manual iteration so the window-queue wait (the stream-wait
        # stall) times separately from the group's compute dispatch
        it = self.streamer.stream()
        while True:
            with self._phase("stream_wait"):
                item = next(it, None)
            if item is None:
                break
            g, window = item
            with self._phase("group_dispatch"):
                lo = jnp.int32(g * self.stream_cfg.group_size)
                # dispatch under the pool lock: the window's liveness ref
                # guarantees its slots are mapped, and the lock keeps the
                # worker's donating (in-place) uploads from deleting the
                # buffer handle mid-dispatch.
                win = {"ffn": window["ffn"], "attn": window["attn"]}
                x, k_g, v_g = self.wpool.dispatch(lambda buf: self._group_fn(
                    self._layers_dram, win, buf, state["k"],
                    state["v"], x, positions, ctx_lens, block_tables,
                    state["bitmap"], lo))
            ks.append(k_g)
            vs.append(v_g)
        with self._phase("finish"):
            k_new = jnp.concatenate(ks, axis=0)      # (L, slots, T, KV, Dh)
            v_new = jnp.concatenate(vs, axis=0)
            args = (self._dram_params["final_norm"], self._lm_head, state,
                    x, k_new, v_new, q_lens, admitted, positions,
                    block_tables, key)
            if self._spec_k is not None:
                args += (drafts, n_draft, is_decode)
            return self._finish_fn(*args)

    def stall_s(self) -> float:
        return self.streamer.stall_s             # the window-queue stall

    def after_step(self, steps_done: int):
        """Overlap-depth auto-tuning (``StreamConfig.auto_depth``): once,
        after the first measured steps, re-pick ``prefetch_depth`` from the
        observed stall/stream ratio — a consumer that still stalls wants
        more windows in flight; one that never does returns the budget to
        the residency cache. The device budget invariant is preserved by
        re-splitting it: window bytes grow/shrink, cache capacity moves the
        other way (never below the pinned floor)."""
        sc = self.stream_cfg
        if (not sc.auto_depth or self._auto_depth_done
                or steps_done < sc.auto_depth_after):
            return
        self._auto_depth_done = True
        st = self.streamer
        if st.stream_s <= 0:
            return                       # nothing streamed: no signal
        ratio = st.stall_s / st.stream_s
        depth = st.prefetch_depth
        want = depth
        if ratio > 0.10:
            want = depth + max(1, round(depth * min(ratio, 1.0)))
        elif ratio < 0.02 and depth > 1:
            want = depth - 1
        if sc.device_budget_bytes is not None:
            afford = int(sc.device_budget_bytes - self.cache.pinned_bytes) \
                // max(self._group_bytes, 1)
            want = min(want, max(afford, 1))
        want = max(1, int(want))
        if want == depth:
            return
        st.prefetch_depth = want
        if sc.device_budget_bytes is not None and not sc.pin_all:
            # eager trim: a deeper window must RECLAIM its bytes from the
            # cache now, not at some future insert — resident + in-flight
            # window bytes must never exceed the device budget.
            self.cache.resize(max(
                self.cache.pinned_bytes,
                sc.device_budget_bytes - want * self._group_bytes))

    def stream_stats(self, tokens: int) -> dict:
        return {**self.streamer.stats(), **self.store.stats(),
                **self.wpool.stats(),
                "pool_reserve_bytes":
                    self._pool_reserve_pages * self.store.page_bytes,
                "prefetch_depth": self.streamer.prefetch_depth}

    def expert_stats(self, tokens: int):
        return None

    def obs_samples(self):
        yield from super().obs_samples()
        yield from self.streamer.obs_samples()
