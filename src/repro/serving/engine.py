"""NVLLM serving engine: the paper's end-to-end dataflow (§3.5) at request
level, with the KV-cache-aware scheduler (Algorithm 2) in the loop.

Execution model (dense decoder families — the paper's OPT/LLaMA models):

  prefill  : consumed in CHUNKS through the same step as decode — Q/K/V/O
             split between "NAND CMOS" (ERDPE over flash-tier INT8+ECC
             weights) and "NPU" (bf16 DRAM weights) by the Alg. 2 bitmap;
             attention + KV write on the NPU side; FFN fully in flash.
  decode   : attention on the NPU over the DRAM KV pool; FFN via ERDPE.
             Algorithm 2 compares the attention-latency increment against
             C_th and flips bitmap bits, moving Q/K/V/O column-groups to the
             flash engine — the projection matmuls are *dispatched by the
             bitmap* via scheduler.split_projection.

``Engine`` is the CONTROL PLANE over one DATA-PLANE object, chosen once in
``__init__`` (DESIGN.md §6). Imports point one way:

  serving/engine.py   control plane + ResidentPlane
    -> serving/streamed.py, serving/expert_paged.py   store-backed planes
    -> serving/step.py   the jitted step programs
    -> models/, core/, serving/kvcache.py

  * control plane — a waiting->running admission queue (submit ENQUEUES;
    slots and worst-case block reservations are claimed at admission),
    per-step chunk planning under the Alg.2/stall-coupled token budget
    (core/scheduler.plan_chunks), completion, O(1) slot release, stats. It
    hands the plane plain (n_slots, chunk_tokens) token arrays plus the
    block tables, so slot churn, ragged prompts, and oversubscribed
    admission never retrace the compiled step.
  * data plane — ``ResidentPlane`` (below; ONE jitted ``serve_step``)
    when the whole model sits in device memory; with a ``weight_store``,
    ``StreamedPlane`` (dense layer groups streamed from the PageStore, §7,
    §11) or ``ExpertPagedPlane`` (a MoE's routed experts paged, §9). The
    contract every plane keeps is in ``serving/streamed.py``.

``compiled=False`` keeps the seed-style eager reference: the *same* per-
layer math driven by an interpreted Python loop over layers (the benchmark
baseline and correctness oracle for benchmarks/serve_{decode,mixed}.py).

``spec_cfg`` adds SPECULATIVE serving (DESIGN.md §8) on top of any compiled
data plane: decoding slots pack ``[last_token, d_1 .. d_k]`` draft
proposals into their chunk lanes, one pass — one weight-stream window
rotation in streamed mode — verifies all k in-graph, and the step emits
``n_accept + 1`` tokens with a KV length rewind over the rejected lanes.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import sys
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import scheduler as sched
from repro.core.erdpe import ExecMode
from repro.core.tiering import ATTN_FLASH_KEYS, deploy, encode_flash
from repro.serving import spec as spec_mod
from repro.serving import step as step_mod
from repro.serving.expert_paged import ExpertPagedPlane
from repro.serving.kvcache import PagedKVPool
from repro.serving.prefix import PrefixIndex, block_hashes
from repro.serving.sampler import SampleConfig
# bench/aot_stream.py compiles _embed_chunk, _stream_group_impl and
# _finish_step under these names; ``sample`` is the forwarded hook below.
from repro.serving.step import (_embed_chunk, _finish_step, _jit,  # noqa: F401
                                _step_impl, _stream_group_impl, sample)
from repro.serving.streamed import StreamedPlane


class _EngineModule(types.ModuleType):
    """tests/bench/test_bench_check.py patches ``sample`` on this module to
    corrupt the served tokens; the step programs call it from
    ``serving/step.py``, so a patch here is forwarded there."""

    def __setattr__(self, name, value):
        if name == "sample":
            setattr(step_mod, name, value)
        super().__setattr__(name, value)


sys.modules[__name__].__class__ = _EngineModule


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int
    pos: int = 0                     # prompt tokens consumed (chunked prefill)
    slot: int | None = None          # None while waiting for admission
    out: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    cancelled: bool = False          # set by Engine.cancel; done implies no
                                     # more tokens, cancelled implies no
                                     # prefix retain and an unread stream
    cached_len: int = 0              # prompt tokens adopted from the prefix
                                     # cache at admission (pos starts here)
    t_queued: float = 0.0            # perf_counter: entered ``waiting``
    t_admitted: float = 0.0          # perf_counter: given a slot

    @property
    def prefilling(self) -> bool:
        return self.pos < len(self.prompt)

    @property
    def kv_rows(self) -> int:
        """Worst-case KV footprint: every prompt token plus every decode
        step writes one row; the LAST sampled token is never written back
        (prefill always writes the whole prompt, so max_new=0 still needs
        len(prompt) rows). Admission validates and reserves this count."""
        return len(self.prompt) + max(self.max_new - 1, 0)


def flash_attn_copy(params, rber, seed):
    """Per-layer flash (INT8+ECC) copies of Q/K/V/O, stacked along a
    leading layer axis so the compiled step can lax.scan over them."""
    attn = params["layers"]["attn"]
    per_layer = [{k: encode_flash(attn[k][li], rber=rber, seed=seed + li)
                  for k in ATTN_FLASH_KEYS}
                 for li in range(attn["wq"].shape[0])]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)


class ResidentPlane:
    """The whole model in device memory, stepped by ONE call of
    ``serve_step`` (``compiled=False``: the same math as an interpreted
    per-layer loop). DRAM tier: bf16 attention weights (copied once at
    init, §3.5); flash tier: INT8+ECC FFN / lm_head and, dense, a flash
    copy of Q/K/V/O so the bitmap can offload projection columns to the
    in-flash engine. MoE keeps attention DRAM-only: the flash engine
    serves the EXPERT BANKS (DESIGN.md §9). Nothing streams: no stall, no
    retune, no stream or expert stats."""

    store = None

    def __init__(self, cfg, params, *, compiled, rber, seed, exec_mode,
                 kv_aware, sched_cfg, sample_cfg, proposer, spec_k, phase):
        self.params = deploy(params, rber=rber, seed=seed)[0]
        self.attn_flash = (None if cfg.family == "moe"
                           else flash_attn_copy(params, rber, seed))
        self._phase = phase
        self.traces = 0
        step = functools.partial(_step_impl, cfg, sched_cfg, sample_cfg,
                                 kv_aware, exec_mode, not compiled, proposer,
                                 spec_k)
        if not compiled:
            self._step_fn = step
            return

        def serve_step(*args):
            # Python body only runs while jax traces; compiled replays
            # skip it — so this counts traces, not steps. The name is
            # the compiled module's in a profile (``jit_serve_step``).
            self.traces += 1
            return step(*args)

        # donate the KV pool + scheduler state: the step is an in-place
        # update of device-resident serving state. (CPU ignores donation
        # and warns, so only donate where it lands.)
        donate = (2,) if jax.default_backend() != "cpu" else ()
        self._step_fn = _jit(serve_step, donate_argnums=donate)

    def step(self, lanes, *args):
        """The whole jitted call is one dispatch."""
        with self._phase("dispatch"):
            return self._step_fn(self.params, self.attn_flash, *args)

    def stall_s(self) -> float:
        return 0.0

    def after_step(self, steps_done: int):
        pass

    def stream_stats(self, tokens: int):
        return None

    def expert_stats(self, tokens: int):
        return None

    def obs_samples(self):
        return iter(())

    def replicate(self, tree):
        return tree

    def close(self):
        pass


def _absent(strict: bool, msg: str) -> dict:
    """The ``*_stats`` wrong-mode rule (see ``Engine.telemetry``)."""
    if strict:
        raise ValueError(msg)
    return {}


class Engine:
    """cfg must be a dense- or moe-family ArchConfig.

    ``compiled=True`` (default) serves prefill AND decode through the single
    jitted mixed-batch step; ``compiled=False`` runs the identical math as
    an interpreted per-layer loop (seed-style eager reference).
    ``exec_mode`` picks the paged-attention backend (PALLAS kernel vs XLA),
    mirroring erdpe.flash_matmul's split. ``block_size``/``n_blocks`` size
    the paged KV pool; ``admission_cfg`` sets the chunk width and the
    Alg.2/stall-coupled per-step token budget. ``weight_store`` (a
    PageStore) and ``stream_cfg`` put the flash tier in the host store and
    pick the streamed or expert-paged plane; a store that already holds a
    page table is opened as a persisted die image.

    ``spec_cfg`` turns on SPECULATIVE serving (DESIGN.md §8): decoding
    slots pack ``[last_token, d_1 .. d_k]`` into their chunk lanes, one
    forward pass — one weight-stream window rotation in streamed mode —
    verifies all k proposals, and each verify step emits ``n_accept + 1``
    tokens. ``drafter='model'`` additionally takes a small resident draft
    model (``draft_cfg``/``draft_params``, dense family, kept bf16).
    """

    def __init__(self, cfg, params, max_slots: int = 4, max_seq: int = 256,
                 sample_cfg: SampleConfig = SampleConfig(),
                 sched_cfg: sched.SchedulerConfig | None = None,
                 kv_aware: bool = True, rber: float = 0.0, seed: int = 0,
                 compiled: bool = True, exec_mode: ExecMode = ExecMode.XLA,
                 block_size: int = 16, n_blocks: int | None = None,
                 admission_cfg: sched.AdmissionConfig | None = None,
                 weight_store=None, stream_cfg=None,
                 spec_cfg: spec_mod.SpecConfig | None = None,
                 draft_cfg=None, draft_params=None,
                 prefix_cache: bool = False,
                 max_waiting: int | None = None,
                 registry: "obs.MetricsRegistry | None" = None):
        if cfg.family not in ("dense", "moe"):
            raise ValueError("engine serves dense- and moe-family archs "
                             f"(got {cfg.family!r})")
        self.cfg = cfg
        self.sample_cfg = sample_cfg
        self.kv_aware = kv_aware
        self.compiled = compiled
        self.admission_cfg = admission_cfg or sched.AdmissionConfig()
        if weight_store is not None and not compiled:
            raise ValueError("streamed mode runs through the compiled data "
                             "plane (compiled=False has no layer groups)")
        self.spec_cfg = spec_cfg
        if spec_cfg is not None:
            if not compiled:
                raise ValueError("speculative decoding runs through the "
                                 "compiled data plane (compiled=False has "
                                 "no verify lanes)")
            if spec_cfg.k + 1 > self.admission_cfg.chunk_tokens:
                raise ValueError(
                    f"spec k={spec_cfg.k} needs k+1 <= chunk_tokens="
                    f"{self.admission_cfg.chunk_tokens} verify lanes")
            self.proposer = spec_mod.DraftProposer(spec_cfg, draft_cfg,
                                                   draft_params)
        else:
            self.proposer = None
        h = sched_cfg.h if sched_cfg else 32
        while cfg.n_heads * cfg.head_dim % h:
            h //= 2
        self.sched_cfg = sched_cfg or sched.SchedulerConfig(
            column_bytes=cfg.d_model, h=h)
        # the data plane, chosen once; it deploys the weights
        kw = dict(rber=rber, seed=seed, exec_mode=exec_mode,
                  kv_aware=kv_aware, sched_cfg=self.sched_cfg,
                  sample_cfg=sample_cfg, proposer=self.proposer,
                  spec_k=spec_cfg.k if spec_cfg else None, phase=self._phase)
        if weight_store is None:
            self.plane = ResidentPlane(cfg, params, compiled=compiled, **kw)
        elif cfg.family == "moe":
            self.plane = ExpertPagedPlane(
                cfg, params, weight_store, stream_cfg, max_slots=max_slots,
                chunk_tokens=self.admission_cfg.chunk_tokens, **kw)
        else:
            self.plane = StreamedPlane(cfg, params, weight_store, stream_cfg,
                                       **kw)
        self.params = self.plane.params
        self.pool = PagedKVPool(cfg.n_layers, max_slots, max_seq,
                                cfg.n_kv_heads, cfg.head_dim,
                                block_size=block_size, n_blocks=n_blocks)
        # admission cap on a request's KV rows: the exact max_seq, the
        # physical pool minus the dump block, and (learned positions) the
        # embedding table — shared by submit() and the verify-lane cap.
        kv_cap = min(self.pool.max_seq,
                     (self.pool.n_blocks - 1) * self.pool.block_size)
        if "pos_embed" in self.params:
            kv_cap = min(kv_cap, self.params["pos_embed"].shape[0])
        self._kv_cap = kv_cap
        # hash-based prefix caching (DESIGN.md §12): completed requests
        # retain their full prompt blocks under a chain hash; admission
        # adopts the longest cached chain copy-free (ref bump only).
        self.prefix = PrefixIndex(self.pool) if prefix_cache else None
        self._prefix_tokens_saved = 0
        # control-plane lock: submit/cancel-sweep/step/close mutate the
        # queues and the pool from different threads when a serving
        # frontend drives the engine. An RLock (step re-enters _admit)
        # with a Condition for the bounded-submit wait; ``cancel`` stays
        # LOCK-FREE (flag flips only) so a disconnect never blocks behind
        # a running step.
        self._mu = threading.RLock()
        self._cv = threading.Condition(self._mu)
        self._closed = False
        # close() is idempotent AND thread-safe: the first caller does the
        # work (and blocks behind any in-flight step via _cv — the clean
        # join), later/concurrent callers are a no-op.
        self._close_lock = threading.Lock()
        self._close_done = False
        self.max_waiting = max_waiting
        self.requests: dict[int, Request] = {}
        self.waiting: collections.deque[Request] = collections.deque()
        self._next_rid = 0
        self._key = jax.random.PRNGKey(seed)
        # sharded planes return the serving state replicated over the
        # mesh: start it that way, or step 2 retraces every jit for the
        # changed input sharding
        self.pool.set_device_state(
            self.plane.replicate(self.pool.device_state()))
        self.bitmap, self._prev_cycles = self.plane.replicate(
            (sched.init_bitmap(self.sched_cfg), jnp.int32(0)))
        self._npu_frac = 1.0             # host view of the Alg. 2 bitmap
        self._stall_frac = 0.0           # EMA of weight-stream stall per step
        self._steps_done = 0
        self._tokens_done = 0            # prompt lanes + emitted tokens
        self.stats: list[dict] = []
        # ObsPlane (DESIGN.md §14): per-step phase histogram + timeline
        # ring. The registry defaults to the process-wide one; disabled
        # registries hand out no-op instruments, so the per-step cost of
        # a dark plane is a few perf_counter reads.
        self.obs = registry if registry is not None \
            else obs.default_registry()
        self.timeline = obs.StepTimeline(256)
        self._h_step = self.obs.histogram(
            "engine_step_seconds", "serving step host wall time by phase",
            label_names=("phase",))
        self._c_step_tokens = self.obs.counter(
            "engine_tokens_total", "tokens processed by the step loop",
            label_names=("kind",))
        # request lifecycle (split of time to first token) and the
        # scheduler/KV state each step plans under
        self._h_admit_wait = self.obs.histogram(
            "engine_admission_wait_seconds",
            "request enters the waiting queue -> admitted to a slot")
        self._h_prefill = self.obs.histogram(
            "engine_prefill_seconds",
            "admission -> the step whose sync hands over the first token")
        self._h_budget = self.obs.histogram(
            "engine_step_token_budget",
            "per-step token budget from scheduler.step_token_budget",
            buckets=obs.log_buckets(1.0, 1024.0, 4))
        self._c_kv_reserved = self.obs.counter(
            "engine_kv_rows_reserved_total",
            "per step, KV rows the active requests reserved at admission")
        self._c_kv_used = self.obs.counter(
            "engine_kv_rows_used_total",
            "per step, KV rows the active requests hold")
        self._c_moe_assign = self.obs.counter(
            "engine_moe_assignments_total",
            "token->expert assignments of valid lanes, summed over layers")
        self._c_moe_routed = self.obs.counter(
            "engine_moe_experts_routed_total",
            "distinct experts routed by valid lanes, summed over layers")
        self._phases: dict[str, float] = {}
        # per-slot token histories feeding the in-graph drafter (spec mode)
        if spec_cfg is not None:
            self._hist = np.zeros((max_slots, max_seq + 1), np.int32)
            self._hist_lens = np.zeros((max_slots,), np.int32)
            self._spec_totals = {"verify_steps": 0, "drafted": 0,
                                 "accepted": 0, "emitted": 0}
            # per-slot acceptance-rate EMA driving the adaptive verify-lane
            # count (SpecConfig.adaptive_k); reset to optimistic full depth
            # when a slot is re-admitted.
            self._accept_ema = np.ones((max_slots,), np.float64)

    @contextlib.contextmanager
    def _phase(self, name: str):
        """One step phase (ObsPlane), timed around its body, on every
        plane: a ``jax.profiler.TraceAnnotation`` puts it in the profiler's
        trace on the device trace's clock (a no-op outside a profiler
        session), its seconds add to this step's breakdown (the
        ``engine_step_seconds`` histogram, the timeline), and an armed
        Tracer records it on the compute track."""
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            yield
            dt = time.perf_counter() - t0
        self._phases[name] = self._phases.get(name, 0.0) + dt
        tracer = obs.default_tracer()
        if tracer.enabled:
            tracer.complete(name, t0, dt, tid=obs.TID_COMPUTE, cat="step")

    def stream_stats(self, *, strict: bool = True) -> dict:
        """The store-backed plane's weight-stream counters (SERVING only:
        init-time programming/pin reads are reset), plus ``spec_stats`` in
        speculative mode. ``strict=False`` returns ``{}`` on a non-streamed
        engine."""
        out = self.plane.stream_stats(self._tokens_done)
        if out is None:
            return _absent(strict,
                           "stream_stats: engine is not in streamed mode")
        if self.spec_cfg is not None:
            out.update(self.spec_stats())
        return out

    def expert_stats(self, *, strict: bool = True) -> dict:
        """``ExpertPagedPlane.expert_stats``; ``strict=False`` returns ``{}``
        when the engine is not serving a store-backed MoE model."""
        out = self.plane.expert_stats(self._tokens_done)
        if out is None:
            return _absent(strict, "expert_stats: engine is not serving a "
                           "store-backed MoE model")
        return out

    def spec_stats(self, *, strict: bool = True) -> dict:
        """Speculative-decode telemetry: how much one weight pass amortizes.

        ``spec_tokens_per_step`` is emitted tokens per VERIFY step (steps
        with >= 1 decoding slot) — in streamed mode, tokens bought per
        window rotation; ``spec_acceptance_rate`` is accepted / drafted.
        ``strict=False`` returns ``{}`` on a non-speculative engine."""
        if self.spec_cfg is None:
            return _absent(strict,
                           "spec_stats: engine is not in speculative mode")
        t = self._spec_totals
        out = {"spec_verify_steps": t["verify_steps"],
               "spec_drafted": t["drafted"],
               "spec_accepted": t["accepted"],
               "spec_emitted": t["emitted"],
               "spec_acceptance_rate": t["accepted"] / max(t["drafted"], 1),
               "spec_tokens_per_step": t["emitted"]
               / max(t["verify_steps"], 1)}
        if self.spec_cfg.adaptive_k:
            k = self.spec_cfg.k
            out["spec_accept_ema"] = [float(v) for v in self._accept_ema]
            out["spec_adaptive_k"] = [max(1, int(round(float(v) * k)))
                                      for v in self._accept_ema]
        return out

    # --- request management (control plane) -----------------------------------

    def submit(self, prompt: list[int], max_new: int = 16,
               timeout: float | None = None) -> int:
        """Enqueue a request and return its id. Admission (slot +
        worst-case block reservation) happens when capacity frees up —
        oversubscription waits, it never errors. Thread-safe; with
        ``max_waiting`` set, a full waiting queue BLOCKS the caller
        (backpressure) until space frees, ``timeout`` seconds expire
        (TimeoutError) or the engine closes (RuntimeError) — a dying
        server never hangs a producer on a full queue."""
        if not prompt:
            raise ValueError("empty prompt (a request needs >= 1 token)")
        if max_new < 1:
            raise ValueError("max_new must be >= 1 (every request samples "
                             "at least the token after its prompt)")
        with self._cv:
            if self._closed:
                raise RuntimeError("submit: engine is closed")
            if self.max_waiting is not None:
                deadline = None if timeout is None \
                    else time.monotonic() + timeout
                while len(self.waiting) >= self.max_waiting \
                        and not self._closed:
                    remaining = None
                    if deadline is not None:
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            raise TimeoutError(
                                "submit: waiting queue full "
                                f"(max_waiting={self.max_waiting})")
                    self._cv.wait(remaining)
                if self._closed:
                    raise RuntimeError("submit: engine is closed")
            # a request that can never fit the per-slot table or the whole
            # pool is rejected up front.
            rid = self._next_rid
            self._next_rid += 1
            req = Request(rid, list(prompt), max_new)
            # bound by the EXACT max_seq (rounding up to block granularity
            # would admit valid lanes past the learned-position table), by
            # the physical pool minus the dump block, and — for learned-
            # position models — by the table itself (a valid lane's
            # out-of-bounds jnp.take would fill NaN under jit). Computed
            # once in __init__; the speculative verify-lane cap shares it.
            cap = self._kv_cap
            if req.kv_rows > cap:
                self._next_rid = rid
                raise ValueError(
                    f"request needs {req.kv_rows} KV rows > max_seq={cap}")
            self.requests[rid] = req
            req.t_queued = time.perf_counter()
            self.waiting.append(req)
            self._admit()
            return rid

    def cancel(self, rid: int) -> bool:
        """Cancel a waiting OR running request (client disconnect). LOCK-
        FREE — flips flags only, so a disconnect handler never blocks
        behind a running compiled step. The resources come back through
        the normal control-plane paths: a waiting request is dropped at
        the queue head by ``_admit``/the step sweep, a running slot
        releases (all its KV blocks to the free list) within ONE ``step``
        call. Returns False if the request is unknown or already done."""
        req = self.requests.get(rid)
        if req is None or req.done:
            return False
        req.cancelled = True
        req.done = True
        return True

    def forget(self, rid: int) -> bool:
        """Drop a finished request's bookkeeping (ServeFront calls this
        once a handle's stream has drained, so ``requests`` doesn't grow
        without bound). Refuses — returns False — while the request is
        live or its slot has not been swept yet."""
        with self._mu:
            req = self.requests.get(rid)
            if req is None or not req.done:
                return False
            if req.slot is not None \
                    and self.pool.active.get(req.slot) == rid:
                return False             # cancelled mid-step; not yet swept
            if req in self.waiting:
                self.waiting.remove(req)
            del self.requests[rid]
            return True

    def _admit(self):
        """waiting -> running, FCFS: claim a slot and reserve the request's
        worst-case block count so lazily-growing slots never deadlock on an
        exhausted pool mid-flight. With prefix caching on, admission first
        adopts the longest cached prefix copy-free (ref bump on shared
        blocks; only the tail is reserved/prefilled), evicting cold fully-
        released chains when the tail reservation is short."""
        while self.waiting:
            req = self.waiting[0]
            if req.done:                 # cancelled while waiting
                self.waiting.popleft()
                self._cv.notify_all()
                continue
            shared, hashes = (), None
            if self.prefix is not None:
                bs = self.pool.block_size
                # cap: >= 1 prompt token always prefills — every request
                # must sample from its own last prompt lane.
                hashes = block_hashes(req.prompt, bs,
                                      limit=(len(req.prompt) - 1) // bs)
                shared = self.prefix.lookup(hashes)
            slot = self.pool.alloc(req.rid, req.kv_rows,
                                   shared_blocks=shared)
            if slot is None and self.prefix is not None \
                    and self.pool.free_slots:
                need = self.pool.blocks_for(req.kv_rows) - len(shared)
                short = need - self.pool.n_free_blocks
                if short > 0 and self.prefix.evict(short) > 0:
                    # eviction may have reclaimed part of the hit chain
                    # itself (LRU doesn't pin this lookup) — re-resolve.
                    shared = self.prefix.lookup(hashes)
                    slot = self.pool.alloc(req.rid, req.kv_rows,
                                           shared_blocks=shared)
            if slot is None:
                break
            req.slot = slot
            req.t_admitted = time.perf_counter()
            self._lifecycle(self._h_admit_wait, "queue", req, req.t_queued,
                            req.t_admitted)
            if shared:
                req.cached_len = len(shared) * self.pool.block_size
                req.pos = req.cached_len
                self._prefix_tokens_saved += req.cached_len
            if self.spec_cfg is not None:
                # a recycled slot must not inherit the previous request's
                # acceptance history; start optimistic (full draft depth)
                self._accept_ema[slot] = 1.0
            self.waiting.popleft()
            self._cv.notify_all()

    def _lifecycle(self, hist, name: str, req: Request, t0: float,
                   t1: float):
        """One wait in a request's life (ObsPlane): its seconds into
        ``hist`` and, with tracing armed, a span on the request's track
        (``t0``/``t1`` are ``perf_counter`` readings)."""
        hist.observe(t1 - t0)
        tracer = obs.default_tracer()
        if tracer.enabled:
            tracer.complete(name, t0, t1 - t0,
                            tid=tracer.request_tid(req.rid), cat="request",
                            args={"rid": req.rid})

    def _sweep_cancelled(self):
        """Reclaim cancelled requests' resources (under the lock, at the
        top of every step): running slots release — O(1), every KV block
        back on the free list — and cancelled waiting requests drop out of
        the queue. No prefix retain: a cancelled stream was never fully
        read, so its tail blocks are not certified shareable."""
        for slot, rid in list(self.pool.active.items()):
            req = self.requests[rid]
            if req.done and req.cancelled:
                self.pool.release(slot)
        if any(r.done for r in self.waiting):
            self.waiting = collections.deque(
                r for r in self.waiting if not r.done)
            self._cv.notify_all()

    def _finish_request(self, req: Request, slot: int):
        """Completion path: retain the request's full prompt blocks in the
        prefix index (ref bump BEFORE the slot's release drops its own
        refs), then release the slot."""
        if self.prefix is not None:
            bs = self.pool.block_size
            hashes = block_hashes(req.prompt, bs)
            if hashes:
                blocks = [int(b) for b in
                          self.pool.block_tables[slot, :len(hashes)]]
                self.prefix.insert(hashes, blocks)
        self.pool.release(slot)          # O(1): no device work

    def prefix_stats(self, *, strict: bool = True) -> dict:
        """Prefix-cache telemetry: index entries/hits/misses/evictions
        plus the total prefill tokens admission skipped via cache hits.
        ``strict=False`` returns ``{}`` when prefix caching is disabled."""
        if self.prefix is None:
            return _absent(strict, "prefix_stats: prefix caching is disabled "
                           "(construct with prefix_cache=True)")
        return {**self.prefix.stats(),
                "prefix_prefill_tokens_saved": self._prefix_tokens_saved}

    def telemetry(self) -> dict:
        """Every applicable ``*_stats`` family merged, wrong-mode families
        silently absent (``strict=False`` everywhere). This is the ONE
        aggregate the serving frontend snapshots — callers that want a
        loud failure on a wrong-mode query keep the per-family accessors.

        CAUTION: streamed-mode families read under the streamer/pool
        locks, so this can wait behind an in-flight upload; ServeFront
        therefore refreshes its cached copy from the loop thread rather
        than calling this per HTTP request."""
        out = {"steps": self._steps_done,
               "free_kv_blocks": int(self.pool.n_free_blocks),
               "active_slots": len(self.pool.active),
               "waiting": len(self.waiting)}
        out.update(self.stream_stats(strict=False))
        out.update(self.spec_stats(strict=False))
        out.update(self.prefix_stats(strict=False))
        return out

    def obs_samples(self):
        """ObsPlane scrape samples for the engine and every subsystem it
        owns (lock-free counter reads — safe to pull from a scrape thread
        while a step holds the streamer/pool locks)."""
        from repro.obs.registry import Sample
        yield Sample("engine_steps_total", "counter",
                     float(self._steps_done))
        yield Sample("engine_free_kv_blocks", "gauge",
                     float(self.pool.n_free_blocks))
        yield Sample("engine_active_slots", "gauge",
                     float(len(self.pool.active)))
        yield Sample("engine_waiting_requests", "gauge",
                     float(len(self.waiting)))
        yield Sample("engine_stall_frac", "gauge", float(self._stall_frac))
        yield from self.plane.obs_samples()
        if self.spec_cfg is not None:
            from repro.serving.spec import spec_obs_samples
            yield from spec_obs_samples(self._spec_totals)
        if self.prefix is not None:
            yield from self.prefix.obs_samples()

    # --- the serving step (one compiled call; mixed prefill/decode) -----------

    def _draft_cap(self, req: Request) -> int:
        """Verify lanes this decoding request can use: bounded by spec k
        (per-slot ADAPTIVE when ``SpecConfig.adaptive_k`` — scaled by the
        slot's recent acceptance-rate EMA, so a slot whose drafts never
        land stops wasting lm_head lanes and KV scatter width while
        keeping ONE probe lane to recover through), by the tokens it still
        owes (a draft past max_new is pure waste — and capping by
        ``remaining - 1`` keeps every speculative KV write inside the
        admission reservation), by the pool/table row cap, and by the
        static chunk width."""
        k_want = self.spec_cfg.k
        if self.spec_cfg.adaptive_k:
            k_want = max(1, int(round(self._accept_ema[req.slot] * k_want)))
        remaining = req.max_new - len(req.out)
        room = self._kv_cap - int(self.pool.lengths[req.slot]) - 1
        return max(0, min(k_want, remaining - 1, room,
                          self.admission_cfg.chunk_tokens - 1))

    def step(self) -> int:
        """One continuous-batching step over all running slots: decoding
        slots advance (one token — or, speculatively, ``n_accept + 1``
        tokens through ONE forward pass), prefilling slots consume a
        prompt chunk under the Alg.2/stall-coupled token budget. Returns
        tokens processed (prompt lanes + emitted decode tokens).
        Thread-safe — one step at a time, producers interleave between
        steps; cancelled requests are swept FIRST, so a disconnect's KV
        blocks are back on the free list within one call."""
        # the profiler's step marker; not a Tracer span, so an idle gap is
        # labelled by the phase inside the step that overlaps it
        with jax.profiler.StepTraceAnnotation("serve_step",
                                              step_num=self._steps_done):
            with self._cv:
                self._sweep_cancelled()
                n = self._step_locked()
                self._cv.notify_all()
                return n

    def _step_locked(self) -> int:
        t_plan0 = time.perf_counter()
        self._phases = {}                # this step's ObsPlane breakdown
        with self._phase("plan"):
            self._admit()
            spec = self.spec_cfg is not None
            decode_slots, prefill_slots = [], []
            # ARRIVAL order (rid), not slot order: recycled slot ids would
            # otherwise let a later prompt monopolize the prefill budget
            # ahead of an earlier one (plan_chunks funds prefill FCFS as
            # given).
            for slot, rid in sorted(self.pool.active.items(),
                                    key=lambda kv: kv[1]):
                req = self.requests[rid]
                if req.done:
                    continue
                if req.prefilling:
                    prefill_slots.append((slot, len(req.prompt) - req.pos))
                elif spec:
                    decode_slots.append((slot, 1 + self._draft_cap(req)))
                else:
                    decode_slots.append(slot)
            budget = sched.step_token_budget(self.admission_cfg,
                                             self._npu_frac,
                                             self._stall_frac)
            # snapshot AFTER list-building: a lock-free cancel() landing
            # since the req.done filter above must not be granted lanes or
            # budget.
            cancelled = {slot for slot, rid in self.pool.active.items()
                         if self.requests[rid].done}
            plan = sched.plan_chunks(decode_slots, prefill_slots, budget,
                                     self.admission_cfg.chunk_tokens,
                                     cancelled=cancelled)
            if not plan:
                return 0
            self._h_budget.observe(budget)
            reserved = used = 0
            for slot, rid in self.pool.active.items():
                reserved += self.requests[rid].kv_rows
                used += int(self.pool.lengths[slot])
            self._c_kv_reserved.inc(reserved)
            self._c_kv_used.inc(used)
            n, t_chunk = self.pool.n_slots, self.admission_cfg.chunk_tokens
            tokens = np.zeros((n, t_chunk), np.int32)
            q_lens = np.zeros((n,), np.int32)
            admitted = np.zeros((n,), bool)
            if spec:
                draft_cap = np.zeros((n,), np.int32)
                is_decode = np.zeros((n,), bool)
            for slot, _ in prefill_slots:
                admitted[slot] = True
            admitted[[s if isinstance(s, int) else s[0]
                      for s in decode_slots]] = True
            for slot, cnt in plan.items():
                req = self.requests[self.pool.active[slot]]
                if req.prefilling:
                    chunk = req.prompt[req.pos:req.pos + cnt]
                    tokens[slot, :len(chunk)] = chunk
                    q_lens[slot] = len(chunk)
                else:
                    tokens[slot, 0] = req.out[-1]
                    q_lens[slot] = 1      # + n_draft lanes added in-graph
                    if spec:
                        is_decode[slot] = True
                        # budget-clamped verify lanes
                        draft_cap[slot] = cnt - 1
                        seq = req.prompt + req.out
                        hl = min(len(seq), self._hist.shape[1])
                        self._hist[slot, :hl] = seq[-hl:]
                        self._hist_lens[slot] = hl
                # map physical blocks for this step's writes — ALL lanes,
                # draft lanes included (host control plane; draws on the
                # admission reservation, so it cannot fail)
                self.pool.ensure(slot, int(self.pool.lengths[slot]) + cnt)
            self._key, sk = jax.random.split(self._key)
            # host bound on each slot's valid lanes: verify lanes are
            # added in-graph, at most draft_cap of them
            lanes = q_lens + draft_cap if spec else q_lens
            state = dict(self.pool.device_state(),
                         bitmap=self.bitmap, prev_cycles=self._prev_cycles)
        t_step0 = time.perf_counter()
        stall0 = self.plane.stall_s()
        with self._phase("h2d"):
            args = (state, jnp.asarray(tokens), jnp.asarray(q_lens),
                    jnp.asarray(admitted), self.pool.block_tables_dev(), sk)
            if spec:
                args += (jnp.asarray(self._hist),
                         jnp.asarray(self._hist_lens),
                         jnp.asarray(draft_cap), jnp.asarray(is_decode))
        # each plane times its own dispatch phases
        out = self.plane.step(lanes, *args)
        with self._phase("sync"):
            if spec:
                toks, n_emit, state, stats = out
                n_emit_host = np.asarray(n_emit)
            else:
                toks, state, stats = out
            self.pool.set_device_state(state)
            self.bitmap = state["bitmap"]
            self._prev_cycles = state["prev_cycles"]
            # the step's only device->host syncs: sampled tokens + stat
            # scalars
            toks_host = np.asarray(toks)  # (slots,) — or (slots, k+1) spec
            t_toks = time.perf_counter()
            n_processed = n_prefill = 0
            for slot in plan:
                req = self.requests[self.pool.active[slot]]
                cnt = int(q_lens[slot])
                if req.prefilling:
                    n_processed += cnt
                    n_prefill += cnt
                    self.pool.bump(slot, cnt)
                    req.pos += cnt
                    if not req.prefilling:
                        # just-completed prefill sampled one token at its
                        # last lane
                        req.out.append(int(toks_host[slot, 0] if spec
                                           else toks_host[slot]))
                        self._lifecycle(self._h_prefill, "prefill", req,
                                        req.t_admitted, t_toks)
                elif spec:
                    # verify step: n_accept + 1 tokens emitted; the pool
                    # length REWINDS to the accepted rows (host mirror here —
                    # device lengths advanced by the same amount in-graph;
                    # rejected lanes' K/V stays in place, unreachable,
                    # overwritten later)
                    ne = int(n_emit_host[slot])
                    new_len = int(self.pool.lengths[slot]) + ne
                    take = min(ne, req.max_new - len(req.out))
                    req.out.extend(int(t) for t in toks_host[slot, :take])
                    self.pool.rewind(slot, new_len)
                    n_processed += ne
                else:
                    self.pool.bump(slot, cnt)
                    req.out.append(int(toks_host[slot]))
                    n_processed += cnt
                if req.cancelled:
                    # cancel() landed mid-step: reclaim NOW (the "within
                    # one step" guarantee); the unread output is discarded.
                    self.pool.release(slot)
                elif not req.prefilling and len(req.out) >= req.max_new:
                    req.done = True
                    self._finish_request(req, slot)
            st = jax.device_get(stats)
        self._npu_frac = float(st["npu_fraction"])
        if "moe_assignments" in st:
            self._c_moe_assign.inc(int(st["moe_assignments"]))
            self._c_moe_routed.inc(int(st["moe_experts_routed"]))
        entry = {
            "kv_len": int(st["kv_len"]),
            "delta_cycles": int(st["delta_cycles"]),
            "npu_fraction": self._npu_frac,
            "prefill_tokens": n_prefill,
            "decode_tokens": n_processed - n_prefill,
        }
        if spec:
            entry["spec_drafted"] = int(st["spec_drafted"])
            entry["spec_accepted"] = int(st["spec_accepted"])
            if bool(is_decode.any()):
                t = self._spec_totals
                t["verify_steps"] += 1
                t["drafted"] += int(st["spec_drafted"])
                t["accepted"] += int(st["spec_accepted"])
                t["emitted"] += int(st["spec_emitted"])
                if self.spec_cfg.adaptive_k:
                    nd = np.asarray(st["spec_draft_slots"])
                    na = np.asarray(st["spec_accept_slots"])
                    a = self.spec_cfg.ema_alpha
                    for slot in np.nonzero(is_decode & (nd > 0))[0]:
                        rate = float(na[slot]) / float(nd[slot])
                        self._accept_ema[slot] = \
                            (1.0 - a) * self._accept_ema[slot] + a * rate
        # stall fraction of step wall time (EMA): the residency signal the
        # admission budget contracts with (scheduler.step_token_budget) — a
        # weight-stream-bound engine sheds prefill share; 0 when resident.
        dt = time.perf_counter() - t_step0
        stall_s = max(self.plane.stall_s() - stall0, 0.0)
        self._stall_frac = 0.5 * self._stall_frac \
            + 0.5 * min(stall_s / max(dt, 1e-9), 1.0)
        entry["stall_frac"] = self._stall_frac
        for name, dt_p in self._phases.items():
            self._h_step.observe(dt_p, labels={"phase": name})
        self._h_step.observe(time.perf_counter() - t_plan0,
                             labels={"phase": "total"})
        if n_prefill:
            self._c_step_tokens.inc(n_prefill, labels={"kind": "prefill"})
        if n_processed - n_prefill:
            self._c_step_tokens.inc(n_processed - n_prefill,
                                    labels={"kind": "decode"})
        self.timeline.record(self._steps_done, self._phases,
                             tokens=n_processed, stall_s=stall_s)
        self.stats.append(entry)
        self._steps_done += 1
        self._tokens_done += n_processed
        self.plane.after_step(self._steps_done)
        self._admit()                    # freed slots host waiting requests
        return n_processed

    def close(self):
        """Mark the engine closed — wakes every ``submit`` blocked on
        backpressure (they raise RuntimeError instead of hanging on a
        dying server) — and release the plane's background resources (the
        expert-paged plane's prefetch worker). Idempotent and
        thread-safe: a second (or concurrent) close is a no-op, and a
        close racing an in-flight step joins it cleanly — taking ``_cv``
        waits for the running ``step()`` to finish (regression-tested in
        tests/test_server.py)."""
        with self._close_lock:
            if self._close_done:
                return
            self._close_done = True
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        self.plane.close()

    @property
    def step_traces(self) -> int:
        """Times the serving data plane was traced/compiled. A fully static
        monolithic path stays at 1 regardless of slot churn, chunked
        prefills, and oversubscribed admission; the streamed path stays at
        3 — dense: embed + ONE group trace shared by every layer group +
        finish; expert-paged MoE: head (embed + layer-0 attn/router) + ONE
        fused expert/attn handoff trace + tail (last experts + finish);
        -1 for eager engines."""
        return self.plane.traces if self.compiled else -1

    def run(self, max_steps: int = 1000) -> dict[int, list[int]]:
        for _ in range(max_steps):
            if self.step() == 0:
                break
        return {r.rid: r.out for r in self.requests.values()}
