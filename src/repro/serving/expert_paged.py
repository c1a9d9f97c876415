"""Expert-paged MoE data plane (DESIGN.md §9): the per-(layer, expert) bank
slices live in the PageStore and only the ROUTED experts reach the device,
through a byte-budgeted ``ExpertCache`` and a router-history prefetcher.
The plane contract is ``serving/streamed.py``'s."""
from __future__ import annotations

import functools
import itertools
import time

import jax.numpy as jnp
import numpy as np

from repro.core import scheduler as sched
from repro.serving.step import _moe_fused_impl, _moe_head_impl, _moe_tail_impl
from repro.serving.streamed import StorePlane
from repro.store.pagestore import StoreRef


class ExpertPagedPlane(StorePlane):
    """Router/attention/norms stay DRAM; ``deploy`` splits stacked
    ``(L, E, K, N)`` banks at ``name@li.ei`` — the store's per-leading-index
    split IS expert granularity. The rotating per-layer expert SLAB is
    budget-accounted like the dense prefetch windows."""

    def __init__(self, cfg, params, store, stream_cfg, *, max_slots,
                 chunk_tokens, **kw):
        from repro.store.expert_cache import ExpertCache, ExpertPrefetcher
        super().__init__(cfg, params, store, stream_cfg, **kw)
        sc = self.stream_cfg
        if sc.group_size != 1:
            raise ValueError(
                f"group_size={sc.group_size}: MoE streaming is per-layer "
                "(group_size=1) — each layer's routing depends on the "
                "previous layer's experts, so a multi-layer group cannot "
                "know its expert set up front")
        experts = self.params["layers"]["moe"]["experts"]
        self._expert_refs = {k: v for k, v in experts.items()
                             if isinstance(v, StoreRef)}
        if set(self._expert_refs) != {"w_gate", "w_up", "w_down"}:
            raise ValueError("MoE streamed mode expects the expert bank "
                             "(w_gate/w_up/w_down) in the store, got "
                             f"{sorted(self._expert_refs)}")
        for ref in self._expert_refs.values():
            if ref.lead != (cfg.n_layers, cfg.n_experts):
                raise ValueError(
                    f"expert bank {ref.name!r} is split {ref.lead}, expected "
                    f"(n_layers, n_experts)=({cfg.n_layers}, {cfg.n_experts})")
        self._stray_flash("layers/moe/experts/", "MoE streamed mode")
        self._split_dram()
        self._check_shardable(
            [ref.entry(0, 0) for ref in self._expert_refs.values()])
        n_l, n_e = cfg.n_layers, cfg.n_experts
        self._expert_nbytes = [
            [sum(store.entry_nbytes(ref.entry(li, e))
                 for ref in self._expert_refs.values()) for e in range(n_e)]
            for li in range(n_l)]
        max_expert = max(max(r) for r in self._expert_nbytes)
        self._max_expert_bytes = max_expert
        # fetch generation counter + per-layer device-slab memo (see
        # _acquire_experts): both must exist before the pin loops fetch.
        self._fetch_gen = itertools.count(1)
        self._slab_memo: dict = {}
        worst_routed = min(n_e, max_slots * chunk_tokens * cfg.top_k)
        self._e_slab = max(1, int(sc.expert_slab or worst_routed))
        lm_bytes = store.entry_nbytes("lm_head")
        slab_bytes = self._e_slab * max_expert
        if sc.device_budget_bytes is None or sc.pin_all:
            cache_cap = None
        else:
            cache_cap = sc.device_budget_bytes - lm_bytes - slab_bytes
            if cache_cap < max_expert:
                raise ValueError(
                    f"device_budget_bytes={sc.device_budget_bytes} cannot "
                    f"hold the pinned lm_head ({lm_bytes}B) + the "
                    f"{self._e_slab}-row expert slab ({slab_bytes}B) + at "
                    f"least one cacheable expert ({max_expert}B); raise the "
                    "budget or shrink StreamConfig.expert_slab")
        # pool sized like the dense plane's, at whole-expert granularity
        # (LOCAL pages per expert against the AGGREGATE expert-cache
        # budget, so replicated fallback entries are covered)
        expert_pages = [
            [sum(self._entry_pages_local(ref.entry(li, e))
                 for ref in self._expert_refs.values()) for e in range(n_e)]
            for li in range(n_l)]
        worst = max(expert_pages[li][e] * store.page_bytes
                    / max(self._expert_nbytes[li][e], 1)
                    for li in range(n_l) for e in range(n_e))
        # trace-static reservation: slab misroute fetches + prefetcher
        # in-flight traffic, in (local) pool pages (see the dense twin)
        self._pool_reserve_pages = \
            2 * self._e_slab * max(max(r) for r in expert_pages)
        cache_cap = self._make_wpool(cache_cap, max_expert,
                                     "one cacheable expert",
                                     sum(sum(r) for r in expert_pages), worst)
        self._expert_kn = {
            name: self._entry_kn(ref.entry(0, 0))
            for name, ref in self._expert_refs.items()}
        self.expert_cache = ExpertCache(cache_cap, n_l, n_e,
                                        n_slots=max_slots,
                                        on_evict=self._free_window)
        # pin_all: every expert pinned, the fully-resident parity baseline.
        # pin_shared_experts: the first N experts of every layer are
        # always-routed DeepSeek-style shared experts — pinned so they never
        # pay a page upload or a misroute stall.
        n_pin = n_e if sc.pin_all else min(sc.pin_shared_experts, n_e)
        for li in range(n_l):
            for e in range(n_pin):
                val, nb = self._fetch_expert(li, e)
                if not self.expert_cache.insert((li, e), val, nb, pin=True):
                    self._free_window(val)
        self.prefetcher = ExpertPrefetcher(self.expert_cache,
                                           self._fetch_expert,
                                           discard=self._free_window,
                                           batch_fetch=self._fetch_expert_sets)
        # misroute-stall-aware budget retune (auto_expert_budget) state
        self._auto_expert_done = False
        self._max_routed_seen = 0
        # init-time reads (lm_head, pins) are deployment, not serving
        store.reset_counters()
        self.expert_cache.reset_counters()
        self.wpool.reset_counters()
        self._build_programs()

    def _fetch_expert(self, li: int, e: int):
        """Upload ONE (layer, expert) weight set's pages (w_gate/w_up/
        w_down) into the device page pool — one staged transfer — and
        return (its page tables, its payload bytes)."""
        return self._fetch_expert_sets([(li, e)])[(li, e)]

    def _fetch_expert_sets(self, keys):
        """Upload SEVERAL (layer, expert) weight sets — any mix of layers
        — in ONE staged transfer (a misroute's whole missing set, or the
        prefetch worker's drained queue); returns {(layer, expert):
        (table-dict with per-expert ``slots``, payload bytes)}."""
        tbls = self.wpool.upload(
            [ref.entry(li, e) for li, e in keys
             for ref in self._expert_refs.values()])
        out = {}
        for li, e in keys:
            val = {name: tbls[ref.entry(li, e)]
                   for name, ref in self._expert_refs.items()}
            val["slots"] = np.concatenate(
                [val[name]["slots"] for name in self._expert_refs])
            # generation stamp: the slab memo keys on it, so a re-fetch
            # (new pool slots) can never alias a stale memoized slab.
            # next() on itertools.count is atomic — this runs on both the
            # compute path and the prefetch worker.
            val["gen"] = next(self._fetch_gen)
            out[(li, e)] = val, self._expert_nbytes[li][e]
        return out

    def _acquire_experts(self, li: int, routed):
        """Gather one layer's ROUTED experts into the slab's page tables.

        Cache hits are acquired ref-held; misses are MISROUTE STALLS —
        the whole missing set is uploaded in ONE staged transfer, then
        hold-inserted (an insert the budget rejects leaves a TRANSIENT
        whose pages are freed after dispatch). Returns (slab page-table
        bank with (e_slab,)-leading tables, slab_map (n_experts,) i32 with
        -1 = not resident, held keys to release after dispatch, transient
        slot arrays to free after dispatch, missing expert-id set)."""
        routed = [int(e) for e in routed] or [0]
        if len(routed) > self._e_slab:
            raise ValueError(
                f"layer {li} routed {len(routed)} distinct experts > "
                f"expert_slab={self._e_slab}; raise StreamConfig.expert_slab")
        cache = self.expert_cache
        held, transients, vals = [], [], {}
        missing = []
        for e in routed:
            key = (li, e)
            val = cache.acquire(key)
            if val is None and self.prefetcher.in_flight(key):
                # the worker is already reading this expert's pages: wait
                # for it (bounded) instead of double-reading — double
                # fetches would also double-count the headline telemetry.
                t0 = time.perf_counter()
                deadline = t0 + 1.0
                while (self.prefetcher.in_flight(key)
                       and time.perf_counter() < deadline):
                    time.sleep(0.0005)
                val = cache.acquire(key)
                cache.note_stall(time.perf_counter() - t0)
            if val is None:
                missing.append(e)
            else:
                held.append(key)
                vals[e] = val
        if missing:
            t0 = time.perf_counter()
            fetched = self._fetch_expert_sets([(li, e) for e in missing])
            dt = time.perf_counter() - t0
            for e in missing:
                val, nb = fetched[(li, e)]
                cache.note_fetch(nb)
                cache.note_stall(dt / len(missing))
                prior = (cache.acquire((li, e))
                         if (li, e) in cache else None)
                if prior is not None:
                    # the prefetch worker landed this expert between our
                    # miss and the batched fetch: use its copy, ours is a
                    # transient (freed after dispatch).
                    held.append((li, e))
                    transients.append(val["slots"])
                    vals[e] = prior
                elif cache.insert((li, e), val, nb, hold=True):
                    held.append((li, e))
                    vals[e] = val
                else:
                    transients.append(val["slots"])
                    vals[e] = val
        rows = [vals[e] for e in routed]
        # slab memo: in steady decode a layer routes the SAME expert set
        # step after step, and the page tables only move when an expert is
        # re-fetched into new pool slots (a new generation stamp). Keying
        # on (routed order, generations) lets those steps reuse the
        # device-resident slab outright — no re-stack, no device_put.
        memo_key = (tuple(routed), tuple(r["gen"] for r in rows))
        memo = self._slab_memo.get(li)
        if memo is not None and memo[0] == memo_key:
            slab, dev_map = memo[1], memo[2]
        else:
            slab_map = np.full((self.cfg.n_experts,), -1, np.int32)
            for r, e in enumerate(routed):
                slab_map[e] = r
            rows += [rows[0]] * (self._e_slab - len(rows))    # static rows
            # the slab is only PAGE TABLES (a few KB of i32): the weights
            # themselves stay in the pool and the expert trace gathers
            # them in place — no per-layer slab re-assembly.
            slab = {name: {k: jnp.asarray(np.stack(
                        [r[name][k] for r in rows]))
                           for k in ("q_tbl", "p_slots", "s_slots")}
                    for name in self._expert_refs}
            dev_map = jnp.asarray(slab_map)
            self._slab_memo[li] = (memo_key, slab, dev_map)
        return slab, dev_map, held, transients, set(missing)

    def _build_programs(self):
        """THREE jitted pieces (HEAD [embed + attention+router(0)] →
        FUSED[expert(l-1) + attention+router(l)] × (L-1) → TAIL[expert(L-1)
        + finish]). The router must run before its layer's expert weights
        can be NAMED, so the trace splits around the host expert-bitmap
        handoff — but every pair of device halves that STRADDLE a boundary
        fuses into one jitted call: interior handoffs ride the fused trace,
        and the embed/finish boundaries fold into the adjacent traces (head
        and tail), so a step is L+1 dispatches (vs the split plane's 2L + 2)
        over exactly 3 steady-state traces (asserted in
        tests/test_moe_serving.py). The fused trace takes the layer index
        as a traced scalar.

        Sharded (``StreamConfig.n_shards > 1``, DESIGN.md §11): the two
        pool-consuming traces (fused, tail) run under ``shard_map`` with
        the pool's page rows split over "model"; each expert's
        down-projection psum is the only collective. The head consumes no
        pool pages and jits plain."""
        cfg, spec_k = self.cfg, self._spec_k
        head = functools.partial(_moe_head_impl, cfg, self.proposer, spec_k,
                                 self._exec_mode)
        fused = functools.partial(_moe_fused_impl, cfg, self._exec_mode,
                                  self._expert_kn)
        tail = functools.partial(_moe_tail_impl, cfg, self.sched_cfg,
                                 self.sample_cfg, self.kv_aware, spec_k,
                                 self._expert_kn)
        if self.mesh is not None:
            # fused args: (layers_dram, k, v, x, h, gates, idx, slab,
            # slab_map, pool_buf, positions, ctx_lens, block_tables, lo);
            # tail args: (final_norm, lm_head, state, x, h, gates, idx,
            # slab, slab_map, pool_buf, k_new, v_new, q_lens, admitted,
            # positions, block_tables, key[, drafts, n_draft, is_decode])
            fused = self._over_mesh(fused, 14, 9)
            tail = self._over_mesh(tail, 17 if spec_k is None else 20, 9)
        self._head_fn = self._program("head_fn", head)
        self._fused_fn = self._program("fused_fn", fused)
        self._tail_fn = self._program("tail_fn", tail, donate_state=True)

    def step(self, lanes, state, tokens, q_lens, admitted, block_tables,
             key, hist=None, hist_lens=None, draft_cap=None, is_decode=None):
        """Per layer, the attention+router half runs on device, the top-k
        expert-id bitmap syncs to the host (the step's only mid-step sync —
        a few hundred bytes, the MoE analog of Algorithm 2's plane-bitmap
        handoff), the routed experts are gathered from the ExpertCache
        (miss = misroute stall), and the expert half consumes the
        assembled device slab, fused with the next half as
        ``_build_programs`` says. While layer *l* computes, the prefetch
        worker fetches the router-history predictor's picks for layer
        *l+1* (wrapping to layer 0 for the next step).

        ``lanes`` bounds each slot's valid lanes on the host: verify lanes
        grow q_lens IN-GRAPH (by n_draft <= draft_cap), so the engine
        passes the superset q_lens + draft_cap and a draft lane's routing
        is never dropped from the slab."""
        cfg, cache = self.cfg, self.expert_cache
        head_args = (self._layers_dram, state["k"], state["v"],
                     self._dram_params, state["lengths"], tokens, q_lens,
                     block_tables)
        with self._phase("head_dispatch"):
            if self._spec_k is None:
                drafts = n_draft = None
                x, h, gates, idx, k_l, v_l, positions, ctx_lens = \
                    self._head_fn(*head_args)
            else:
                (x, h, gates, idx, k_l, v_l, positions, ctx_lens, q_lens,
                 drafts, n_draft) = self._head_fn(*head_args, hist,
                                                  hist_lens, draft_cap)
            # whole-step prefetch lead: the per-layer request below gives
            # the worker only one layer's compute (~ms) to land its
            # fetches — on fast layers the compute path wins the race and
            # every miss is a synchronous stall. The per-slot router
            # histories already know each layer's likely experts, so queue
            # EVERY layer's predictions up front (one batched transfer in
            # the worker) and let the layer loop's requests merely top up
            # with the freshest signal.
            active = [s for s in range(len(lanes)) if lanes[s] > 0]
            if self._steps_done > 0:
                for li in range(cfg.n_layers):
                    self._request_prefetch(li, self._e_slab, slots=active)
        # layer 0's attention+router already ran inside the head trace
        # (no pool operand — embed/attn weights are DRAM-resident).
        ks, vs = [k_l], [v_l]
        out = None
        for li in range(cfg.n_layers):
            with self._phase("route_sync"):
                idx_host = np.asarray(idx)           # layer li's routing
            by_slot = sched.routed_experts_by_slot(idx_host, lanes)
            routed = sched.routed_experts(idx_host, lanes)
            cache.observe(li, routed)
            for s, ids in by_slot.items():
                cache.observe_slot(s, li, ids)
            self._max_routed_seen = max(self._max_routed_seen, len(routed))
            self._request_prefetch((li + 1) % cfg.n_layers, len(routed),
                                   slots=by_slot.keys())
            with self._phase("expert_acquire"):
                slab, slab_map, held, transients, missing = \
                    self._acquire_experts(li, routed)
            for s, ids in by_slot.items():
                cache.note_slot_route(s, len(ids),
                                      sum(1 for e in ids
                                          if int(e) in missing))
            # dispatch under the pool lock: the prefetch worker's donating
            # (in-place) uploads delete the buffer handle they consume, so
            # snapshot-and-dispatch must be atomic against them.
            if li + 1 < cfg.n_layers:
                # layer li's experts fused with layer li+1's attn+router
                with self._phase("fused_dispatch"):
                    x, h, gates, idx, k_l, v_l = self.wpool.dispatch(
                        lambda buf: self._fused_fn(
                            self._layers_dram, state["k"], state["v"], x,
                            h, gates, idx, slab, slab_map, buf, positions,
                            ctx_lens, block_tables, jnp.int32(li + 1)))
                ks.append(k_l)
                vs.append(v_l)
            else:        # last layer: experts fused with the finish step
                k_new = jnp.stack(ks, axis=0)    # (L, slots, T, KV, Dh)
                v_new = jnp.stack(vs, axis=0)
                pre = (self._dram_params["final_norm"], self._lm_head,
                       state, x, h, gates, idx, slab, slab_map)
                post = (k_new, v_new, q_lens, admitted, positions,
                        block_tables, key)
                if self._spec_k is not None:
                    post += (drafts, n_draft, is_decode)
                with self._phase("tail_dispatch"):
                    out = self.wpool.dispatch(
                        lambda buf: self._tail_fn(*pre, buf, *post))
            # dispatch has captured the pool buffer: NOW the held
            # entries can release and the rejected transients can free.
            for hk in held:
                cache.release(hk)
            for slots in transients:
                self.wpool.free(slots)
        return out

    def _request_prefetch(self, layer: int, breadth: int, slots=None):
        """Enqueue predicted experts for ``layer`` — gated by the cache's
        score-aware admission (``would_admit``), so speculative fetches
        never read pages the cache would immediately reject: a prediction
        lands in free space or by displacing strictly COLDER experts,
        never by thrashing the resident hot set. ``slots`` — the decode
        slots active this step — switches the predictor to the per-slot
        histories (max-combined), so a slot whose routing phase diverges
        from the batch mean still gets its experts prefetched."""
        cache = self.expert_cache
        want = breadth + self.stream_cfg.prefetch_experts_margin
        picks = [(layer, e) for e in cache.predict(layer, want, slots=slots)
                 if cache.would_admit((layer, e),
                                      self._expert_nbytes[layer][e])]
        if picks:
            self.prefetcher.request(picks)

    def stall_s(self) -> float:
        return self.expert_cache.misroute_stall_s    # cumulative misroutes

    def after_step(self, steps_done: int):
        """Misroute-stall-aware expert budget re-split (``StreamConfig.
        auto_expert_budget``) — the expert-paged analog of ``auto_depth``:
        once, after the first measured steps, if routed experts actually
        stalled, return the slab reservation's UNUSED rows (worst-case
        e_slab sizing vs the observed max routed set) to the expert
        cache's capacity. The device budget invariant is preserved — the
        slab's trace shape is fixed at init, so the dead reservation is
        pure headroom the cache can spend on residency."""
        self._steps_done = steps_done
        sc = self.stream_cfg
        if (not sc.auto_expert_budget or self._auto_expert_done
                or steps_done < sc.auto_depth_after):
            return
        self._auto_expert_done = True
        cache = self.expert_cache
        if (cache.misroute_stalls == 0 or cache.capacity is None
                or self._max_routed_seen >= self._e_slab):
            return
        unused = self._e_slab - max(self._max_routed_seen, 1)
        cache.resize(cache.capacity + unused * self._max_expert_bytes)

    def expert_stats(self, tokens: int) -> dict:
        """Hit rate over routed-expert acquires, fetched bytes (prefetch
        included) and bytes/token vs the DENSE-EQUIVALENT all-experts-
        streamed cost (what rotating every expert of every layer through
        the window would have fetched), and misroute stalls (routed experts
        not resident when their layer needed them)."""
        c = self.expert_cache.stats()
        bank_total = sum(sum(r) for r in self._expert_nbytes)
        return {
            "expert_hits": c["hits"], "expert_misses": c["misses"],
            "expert_hit_rate": c["hits"] / max(c["hits"] + c["misses"], 1),
            "expert_bytes_fetched": c["bytes_fetched"],
            "expert_fetches": c["fetches"],
            "expert_prefetches": c["prefetches"],
            "expert_prefetched_bytes": c["prefetched_bytes"],
            "misroute_stalls": c["misroute_stalls"],
            "misroute_stall_s": c["misroute_stall_s"],
            "expert_cache_entries": c["entries"],
            "expert_cache_bytes": c["bytes_used"],
            "expert_slab": self._e_slab,
            "steps": self._steps_done, "tokens": tokens,
            "expert_bytes_per_token": c["bytes_fetched"] / max(tokens, 1),
            "all_experts_bytes_per_token":
                self._steps_done * bank_total / max(tokens, 1),
            "slot_hit_rates": c.get("slot_hit_rates", []),
            "max_routed_seen": self._max_routed_seen,
            "expert_budget_retuned": self._auto_expert_done,
            "pool_reserve_bytes":
                self._pool_reserve_pages * self.store.page_bytes,
            **self.prefetcher.stats(),
            **self.wpool.stats(),
        }

    def stream_stats(self, tokens: int) -> dict:
        return {**self.expert_stats(tokens), **self.store.stats()}

    def obs_samples(self):
        yield from super().obs_samples()
        yield from self.expert_cache.obs_samples()
        yield from self.prefetcher.obs_samples()

    def close(self):
        """Stop the prefetch worker, whose fetch closure pins this plane:
        without it neither the thread nor the device-resident expert cache
        is ever reclaimed."""
        self.prefetcher.stop()
