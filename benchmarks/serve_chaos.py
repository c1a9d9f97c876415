"""Chaos benchmark (ISSUE 9): the serving stack under injected NAND
faults — stuck-UECC pages, transient read-disturb flips, slow reads,
channel IOErrors, a forced streamer-worker crash, and a forced
persistently-faulted step — must DEGRADE, never crash.

Two phases over the ServeFront frontend (direct handles, no HTTP):

  * streamed DENSE: a fault-free run records the token baseline, then
    the same prompts replay against an injector-armed store while the
    chaos schedule fires. The contracts: every corrected-read request's
    tokens are bit-identical to the fault-free run (host-side SEC-DED +
    read-retry ship exact bytes; step retries are exact re-executions);
    the one sacrificial request under the persistent step fault finishes
    ``finish_reason="error"`` — not hung — and the SAME front serves the
    recovery request right after; zero KV blocks leak.
  * streamed MoE (expert-paged): the same injector modes ride the expert
    prefetcher/compute fetch paths; greedy parity against the fault-free
    expert-paged run.

Overall: >= 95 % of requests finish "length", the remainder finish
"error"/"timeout" (never hung), the engine/server never crashes, and
/v1/health reports degraded-but-200. scripts/bench_gate.py re-checks the
recorded counters in CI (--section serve_chaos).

    PYTHONPATH=src python -m benchmarks.serve_chaos
    PYTHONPATH=src REPRO_SMOKE=1 python benchmarks/serve_chaos.py   # CI
"""
from __future__ import annotations

import os
import sys

if __package__ in (None, ""):                            # direct invocation
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

import jax

from benchmarks.common import Report, write_bench_json
from benchmarks.serve_moe import SERVE_MOE_BENCH
from repro.configs.paper_models import OPT_TINY
from repro.models import dense, moe
from repro.runtime.fault import StepFault
from repro.serving.engine import Engine
from repro.serving.server import ServeFront
from repro.store import PageStore, StreamConfig
from repro.store.faults import FaultConfig, FaultInjector

SMOKE = os.environ.get("REPRO_SMOKE", "0") != "0"
MAX_NEW = 4 if SMOKE else 8
N_DENSE = 20                             # normal dense requests (phase A)
MAX_SEQ = 96
# the chaos schedule: stuck rate comfortably past the 1e-3 floor the gate
# holds, slow reads + transient flips at rates that FIRE on this store
# size, IOErrors rare enough that in-worker retries absorb them.
CHAOS = FaultConfig(seed=3, read_rber=2e-5, stuck_page_rate=5e-3,
                    slow_read_every=9, slow_read_s=0.001,
                    io_error_every=97, io_error_burst=1)
MOE_CHAOS = FaultConfig(seed=5, read_rber=2e-5, stuck_page_rate=5e-3,
                        slow_read_every=9, slow_read_s=0.001)


BUDGET_FRACTION = 0.6                    # dense device weight budget


def _dense_engine(params, budget):
    # bounded budget: groups EVICT and re-read every step, so the armed
    # store sees continuous read traffic (unbounded, the residency cache
    # would absorb all reads after the first pass and no faults fire).
    store = PageStore(n_planes=8)
    eng = Engine(OPT_TINY, params, max_slots=2, max_seq=MAX_SEQ, rber=0.0,
                 weight_store=store,
                 stream_cfg=StreamConfig(device_budget_bytes=budget,
                                         group_size=1, prefetch_depth=2))
    return eng, store


def _moe_engine():
    cfg = SERVE_MOE_BENCH
    params = moe.init(cfg, jax.random.PRNGKey(0))
    store = PageStore(n_planes=8)
    eng = Engine(cfg, params, max_slots=3, max_seq=160,
                 weight_store=store,
                 stream_cfg=StreamConfig(expert_slab=8))
    return eng, store


def _dense_prompts():
    return [[(7 * i + j) % 400 + 1 for j in range(5 + i % 4)]
            for i in range(N_DENSE)]


MOE_PROMPTS = [[55] * 8, [25] * 8, [200] * 8]


def _serve_all(front, prompts, max_new=MAX_NEW):
    handles = [front.add_request(p, max_new=max_new) for p in prompts]
    return [h.result(timeout=600) for h in handles], handles


def run() -> Report:
    rep = Report("Chaos: serving under injected NAND faults "
                 f"(streamed dense x{N_DENSE + 2} + expert-paged MoE "
                 f"x{len(MOE_PROMPTS)}, stuck={CHAOS.stuck_page_rate}, "
                 f"rber={CHAOS.read_rber})")

    # --- fault-free baselines (token ground truth) ---------------------------
    params = dense.init(OPT_TINY, jax.random.PRNGKey(0))
    probe = PageStore()
    Engine(OPT_TINY, params, max_slots=2, max_seq=MAX_SEQ,
           weight_store=probe, stream_cfg=StreamConfig(group_size=1))
    budget = int(probe.total_bytes * BUDGET_FRACTION)

    eng0, _ = _dense_engine(params, budget)
    front0 = ServeFront(eng0)
    base_dense, _ = _serve_all(front0, _dense_prompts())
    base_recovery, _ = _serve_all(front0, [[11, 22, 33]])
    front0.close()

    meng0, _ = _moe_engine()
    mfront0 = ServeFront(meng0)
    base_moe, _ = _serve_all(mfront0, MOE_PROMPTS)
    mfront0.close()

    finished = failed = 0

    # --- dense under chaos ---------------------------------------------------
    eng, store = _dense_engine(params, budget)
    store.attach_injector(FaultInjector(CHAOS))
    step_fault = {"arm": False, "n": 0}

    def hook(step, retries):
        if step_fault["arm"]:
            step_fault["n"] += 1
            raise StepFault("forced persistent step fault")

    front = ServeFront(eng, poll_s=0.01, step_fault_hook=hook)

    # forced streamer-worker crash: the next TWO window fetches fail ->
    # the worker's in-fetch retry budget (1) exhausts -> typed StoreFault
    # -> the step faults -> the front's step retry re-runs it exactly.
    eng.plane.streamer.max_fetch_retries = 1
    eng.plane.streamer.retry_backoff_s = 0.001
    crash = {"left": 0}
    orig_window = eng.plane.streamer._window

    def window(g):
        if crash["left"] > 0:
            crash["left"] -= 1
            raise IOError("forced NAND channel crash")
        return orig_window(g)

    eng.plane.streamer._window = window

    # phase A: normal traffic; mid-phase, force the worker crash
    prompts = _dense_prompts()
    handles = [front.add_request(p, max_new=MAX_NEW) for p in prompts]
    handles[0].result(timeout=600)       # serving is under way
    crash["left"] = 2                    # > in-fetch retry budget
    got_dense = [h.result(timeout=600) for h in handles]
    parity_dense = got_dense == base_dense
    finished += sum(h.finish_reason == "length" for h in handles)

    # phase B: one sacrificial request under a PERSISTENT step fault —
    # it must fail structured ("error"), never hang the server
    step_fault["arm"] = True
    sac = front.add_request([9, 9, 9], max_new=MAX_NEW)
    sac._done.wait(600)
    step_fault["arm"] = False
    failed += int(sac.finish_reason == "error")

    # phase C: the SAME front recovers and serves bit-exact again
    got_rec, rh = _serve_all(front, [[11, 22, 33]])
    parity_recovery = got_rec == base_recovery
    finished += sum(h.finish_reason == "length" for h in rh)

    import time
    deadline = time.monotonic() + 60
    while front.stats()["live_handles"] and time.monotonic() < deadline:
        time.sleep(0.02)
    leaked_kv = eng.pool.n_blocks - 1 - len(eng.pool.free_blocks)
    survived = front.error is None and front._loop.is_alive()
    health_code, health = front.health()
    st = front.stats()
    sstats = store.stats()
    fstats = eng.plane.streamer.stats()
    front.close()

    # --- MoE under chaos -----------------------------------------------------
    meng, mstore = _moe_engine()
    mstore.attach_injector(FaultInjector(MOE_CHAOS))
    mfront = ServeFront(meng, poll_s=0.01)
    got_moe, mh = _serve_all(mfront, MOE_PROMPTS)
    parity_moe = got_moe == base_moe
    finished += sum(h.finish_reason == "length" for h in mh)
    mleaked = meng.pool.n_blocks - 1 - len(meng.pool.free_blocks)
    msurvived = mfront.error is None and mfront._loop.is_alive()
    msstats = mstore.stats()
    mfront.close()

    total = N_DENSE + 1 + 1 + len(MOE_PROMPTS)
    success_frac = finished / total

    # fault-activity floors hold on the COMBINED dense+MoE stores: which
    # phase a given stuck page lands in is a function of store layout, but
    # the chaos run as a whole must exercise every escalation path.
    uecc = sstats["uecc_detected"] + msstats["uecc_detected"]
    retries = sstats["read_retries"] + msstats["read_retries"]
    relocs = sstats["relocations"] + msstats["relocations"]
    slow = sstats["fault_slow_reads"] + msstats["fault_slow_reads"]

    rep.note(f"  dense: {sstats['uecc_detected']} UECC events, "
             f"{sstats['read_retries']} read retries "
             f"({sstats['retry_corrected']} corrected on retry), "
             f"{sstats['relocations']} relocations, "
             f"{sstats['ecc_corrected_pages']} pages ECC-corrected inline, "
             f"{sstats['fault_slow_reads']} slow reads, "
             f"{sstats['fault_io_errors']} channel IOErrors")
    rep.note(f"  worker: {fstats['fetch_retries']} fetch retries, "
             f"{fstats['fetch_faults']} StoreFaults; front: "
             f"{st['step_retries']} step retries, {st['step_faults']} "
             f"persistent step faults -> {st['requests_failed']} failed, "
             f"health {health_code} {health['status']!r}")
    rep.note(f"  moe  : {msstats['uecc_detected']} UECC events, "
             f"{msstats['relocations']} relocations, "
             f"{msstats['ecc_corrected_pages']} pages corrected, "
             f"prefetch failures "
             f"{meng.expert_stats().get('prefetch_failures', 0)}")
    rep.note(f"  {finished}/{total} requests finished 'length' "
             f"({100 * success_frac:.1f}%), {failed} failed 'error', "
             f"0 hung")

    rep.add("requests finishing length/stop (frac, >= 0.95)",
            success_frac, 0.95, 1.0)
    rep.add("corrected-read dense tokens == fault-free run",
            int(parity_dense), 1, 1)
    rep.add("post-fault recovery tokens == fault-free run",
            int(parity_recovery), 1, 1)
    rep.add("expert-paged MoE tokens == fault-free run",
            int(parity_moe), 1, 1)
    rep.add("UECC pages detected under chaos", uecc, 1, float("inf"))
    rep.add("read retries fired", retries, 1, float("inf"))
    rep.add("stuck pages escalated (relocations)", relocs, 1, float("inf"))
    rep.add("slow reads injected", slow, 1, float("inf"))
    rep.add("forced streamer-worker crash escalated (StoreFaults)",
            fstats["fetch_faults"], 1, float("inf"))
    rep.add("step retries absorbed transient faults", st["step_retries"],
            1, float("inf"))
    rep.add("forced persistent step fault fired", st["step_faults"],
            1, float("inf"))
    rep.add("sacrificial request failed structured (finish_reason=error)",
            st["requests_failed"], 1, 1)
    rep.add("KV blocks leaked (dense)", leaked_kv, 0, 0)
    rep.add("KV blocks leaked (moe)", mleaked, 0, 0)
    rep.add("server survived all faults (loop alive, no fatal error)",
            int(survived and msurvived), 1, 1)
    rep.add("health endpoint: 200 degraded under chaos",
            int(health_code == 200 and health["status"] == "degraded"),
            1, 1)

    write_bench_json("serve_chaos", {
        "n_requests": total, "max_new": MAX_NEW,
        "stuck_page_rate": CHAOS.stuck_page_rate,
        "read_rber": CHAOS.read_rber,
        "success_frac": success_frac,
        "parity_dense": parity_dense, "parity_recovery": parity_recovery,
        "parity_moe": parity_moe,
        "uecc_detected": uecc,
        "read_retries": retries,
        "retry_corrected": sstats["retry_corrected"]
        + msstats["retry_corrected"],
        "relocations": relocs,
        "ecc_corrected_pages": sstats["ecc_corrected_pages"]
        + msstats["ecc_corrected_pages"],
        "slow_reads": slow,
        "io_errors": sstats["fault_io_errors"] + msstats["fault_io_errors"],
        "fetch_retries": fstats["fetch_retries"],
        "fetch_faults": fstats["fetch_faults"],
        "step_retries": st["step_retries"],
        "step_faults": st["step_faults"],
        "requests_failed": st["requests_failed"],
        "leaked_kv_dense": leaked_kv, "leaked_kv_moe": mleaked,
        "survived": bool(survived and msurvived),
        "health_code": health_code, "health_status": health["status"],
        "moe_uecc_detected": msstats["uecc_detected"],
        "moe_relocations": msstats["relocations"],
    })
    return rep


def main():
    rep = run()
    print(rep.render())
    sys.exit(0 if rep.ok else 1)


if __name__ == "__main__":
    main()
