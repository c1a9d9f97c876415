"""Run one benchmark cell once on the chip this process holds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) pairs a
configuration (``bench/configs/<config>.json``) with a traffic mix
(``bench/traffic/<mix>.json``). The run

1. builds the engine the way ``launch/serve.build_engine`` does, at the
   configuration's sizes, with weights made on the device from the seed,
   and puts it behind ``ServeFront`` and ``make_http_server`` on a local
   port;
2. warms up the programs the window uses (set-up ends there: ``setup_s``
   is wall time from process start to the first timed request);
3. drives it for ``--seconds`` with the mix's closed-loop clients over
   real sockets (SSE), every time taken on the client's side;
4. with ``--trace 1`` profiles that window and reads the per-layer metrics
   (``bench/metrics/<metric>.py``) instead of the end-to-end ones;
5. frees the program and compares what it served against the plain
   reference (``bench/arch/<arch>_reference.py``) on a sample of requests
   drawn from the seed.

The last stdout line is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, and with ``--trace 1`` ``breakdown``,
then ``check``: each compared number beside its limit). Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_IMPORT = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import NamedTuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import client, traffic as traffic_mod  # noqa: E402

MARKER = "bench_clock_marker"
RUN_DIR = ROOT / ".bench_runs"


def log(*args):
    print(*args, file=sys.stderr, flush=True)


def process_start() -> float:
    """Wall-clock time this process started (Linux), else import time."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return min(time.time() - (uptime - started), T_IMPORT)
    except (OSError, ValueError, IndexError):
        return T_IMPORT


# --- finding things by name ---------------------------------------------------

def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def resolve(spec: dict, workload: str, bench: Path = BENCH) -> dict:
    """The cell ``workload`` with its configuration, mix and metrics, each
    found by the name ``BENCHMARK.json`` gives it."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; cells: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    confs = {c["name"]: c for c in spec["configs"]}
    entry = confs[cell["config"]]
    with open(bench.parent / entry["file"]) as f:
        conf = json.load(f)
    with open(bench / "traffic" / f"{cell['traffic']}.json") as f:
        mix = json.load(f)

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {"cell": cell, "config": conf, "mix": mix,
            "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
            "per_layer": [m for m in spec["per_layer"] if applies(m)]}


def load_reader(name: str, bench: Path = BENCH):
    """``bench/metrics/<name>.py``'s ``read(run)``."""
    path = bench / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def load_peaks(kind: str, bench: Path = BENCH) -> dict:
    with open(bench / "peaks.json") as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json (known: {sorted(peaks)})")
    return peaks[kind]


def arch_modules(conf: dict):
    """(model module, reference module) of the configuration's ``arch``."""
    arch = conf["arch"]
    return (importlib.import_module(f"bench.arch.{arch}"),
            importlib.import_module(f"bench.arch.{arch}_reference"))


def sizes_of(conf: dict) -> dict:
    keys = ("hidden_size", "ffn_dim", "num_attention_heads",
            "num_hidden_layers", "vocab_size", "max_position_embeddings")
    return {k: int(conf[k]) for k in keys}


# --- the chip -------------------------------------------------------------------

def require_chips(count: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {devs[0].platform} "
                     f"({devs[0].device_kind})")
    if len(devs) < count:
        raise SystemExit(f"bench: cell needs {count} TPU chips, found "
                     f"{len(devs)}")


def enable_compile_cache(root: Path = ROOT) -> str:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``,
    else ``.jax_cache/`` in the checkout), made to keep every program
    however quick, so that ``deploy``'s small eager programs are cached
    too."""
    import jax
    from repro.launch.serve import enable_compile_cache as program_cache
    path = program_cache(root)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def peak_bytes() -> int:
    """Process-lifetime peak of device memory on the fullest chip."""
    import jax
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices()))


# --- the system under test ------------------------------------------------------

def build_engine(conf: dict, arch, params, seed: int):
    """An ``Engine`` as ``launch/serve.build_engine`` builds one, at the
    configuration's engine settings."""
    from repro.core.scheduler import AdmissionConfig
    from repro.serving.engine import Engine
    from repro.serving.sampler import SampleConfig
    eng_conf = conf["engine"]
    if eng_conf.get("sampling") != "greedy":
        raise SystemExit("the comparison needs greedy sampling")
    os.environ["REPRO_SERVE_ECC"] = eng_conf.get("serve_ecc", "inline")
    if eng_conf["plane"] != "resident":
        raise SystemExit(f"unknown plane {eng_conf['plane']!r}")
    return Engine(arch.program_config(conf), params,
                  max_slots=int(eng_conf["max_slots"]),
                  max_seq=int(eng_conf["max_seq"]),
                  rber=float(eng_conf.get("rber", 0.0)),
                  sample_cfg=SampleConfig(temperature=0.0),
                  kv_aware=bool(eng_conf.get("kv_aware", True)),
                  seed=int(seed) % 2**31, admission_cfg=AdmissionConfig())


class Step(NamedTuple):
    t0: float                       # host perf_counter at step start
    t1: float
    chunks: list                    # (context rows, new tokens) per sequence
    sampled: int                    # tokens sampled in the step


class StepLog:
    """Wraps ``engine.step`` from the benchmark's side: for each step its
    host start and end, and per sequence that worked in it the context it
    read and the tokens it added, from the engine's slot lengths."""

    def __init__(self, engine):
        self.engine = engine
        self.steps: list[Step] = []
        self._step = engine.step
        engine.step = self.step

    def _view(self):
        eng = self.engine
        out = {}
        for slot, rid in list(eng.pool.active.items()):
            req = eng.requests.get(rid)
            out[rid] = (int(eng.pool.lengths[slot]),
                        len(req.out) if req is not None else 0)
        return out

    def step(self):
        before = self._view()
        t0 = time.perf_counter()
        n = self._step()
        t1 = time.perf_counter()
        after = self._view()
        chunks, sampled = [], 0
        for rid, (length, n_out) in after.items():
            b_len, b_out = before.get(rid, (0, 0))
            if length > b_len:
                chunks.append((b_len, length - b_len))
            sampled += n_out - b_out
        for rid, (b_len, _) in before.items():
            if rid not in after:             # finished on a decode token
                chunks.append((b_len, 1))
                sampled += 1
        if chunks:
            self.steps.append(Step(t0, t1, chunks, sampled))
        return n


class Run:
    """What a run recorded, as the metric readers see it."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def counter_delta(self, key: str) -> float:
        return self.server_after.get(key, 0.0) \
            - self.server_before.get(key, 0.0)


def end_to_end(records, t0: float, t1: float) -> dict:
    """Client-side metrics over the window [t0, t1]."""
    import numpy as np
    toks = sum(1 for r in records for t in r.t_tokens if t0 <= t <= t1)
    ttft = [(r.t_tokens[0] - r.t_send) * 1e3 for r in records if r.t_tokens]
    gaps = [(b - a) * 1e3 for r in records
            for a, b in zip(r.t_tokens, r.t_tokens[1:]) if b <= t1]
    out = {"decode_tok_s": toks / (t1 - t0)}
    if ttft:
        out["ttft_p50_ms"] = float(statistics.median(ttft))
    if gaps:
        out["itl_p95_ms"] = float(np.percentile(gaps, 95))
    return out


def sample_for_check(records, seed: int, check: dict) -> list:
    """Requests to compare: the longest served, then others drawn from the
    seed, until ``min_tokens`` served tokens or ``max_requests``."""
    import numpy as np
    cands = [r for r in records if r.tokens and not r.failed]
    if not cands:
        return []
    cands.sort(key=lambda r: (-(len(r.prompt) + len(r.tokens)),
                              r.client, r.index))
    rest = cands[1:]
    order = np.random.default_rng([int(seed) & (2**64 - 1), 7]).permutation(
        len(rest))
    chosen, served = [cands[0]], len(cands[0].tokens)
    for i in order:
        if served >= check["min_tokens"] \
                or len(chosen) >= check["max_requests"]:
            break
        chosen.append(rest[i])
        served += len(rest[i].tokens)
    return chosen


def free_device() -> int:
    """Drop compiled programs (their closures hold the engine) and collect;
    returns the device bytes still live."""
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()
    return sum(a.nbytes for a in jax.live_arrays())


def trace_host_spans(tracer, t_tracer0: float, offset_ns: float):
    """The program's step-phase spans on the profiler clock."""
    from repro.obs.trace import TID_COMPUTE
    out = []
    for ev in tracer.events():
        if ev.get("ph") != "X" or ev.get("tid") != TID_COMPUTE:
            continue
        start = (t_tracer0 + ev["ts"] / 1e6) * 1e9 + offset_ns
        out.append((ev["name"], start, start + ev["dur"] * 1e3))
    return out


def serve_window(ctx: dict, seed: int, seconds: float, trace: bool,
                 log_fn=log) -> dict:
    """Set up, warm up and drive the cell; returns what the run recorded
    (the program is freed before this returns)."""
    import jax
    from chip_smoke import CompileClock
    conf, mix = ctx["config"], ctx["mix"]
    arch, _ = arch_modules(conf)
    sizes = sizes_of(conf)
    clock = CompileClock()
    t_start = ctx.get("t_process", time.time())
    t_import = ctx.get("t_import", t_start)
    t_chip = ctx.get("t_chip", t_start)
    t_ready = ctx.get("t_ready", t_start)
    t = time.time()
    params = arch.make_params(sizes, seed)
    jax.block_until_ready(params)
    eng = build_engine(conf, arch, params, seed)
    del params
    gc.collect()
    t_build = time.time() - t
    from repro.serving.server import ServeFront, make_http_server
    front = ServeFront(eng, max_waiting=4 * int(mix["clients"]))
    server = make_http_server(front, 0)
    http = threading.Thread(target=server.serve_forever, daemon=True,
                            name="bench-http")
    http.start()
    port = server.server_address[1]
    steps = StepLog(eng)
    try:
        t = time.time()
        traf = traffic_mod.Traffic(mix, seed, sizes["vocab_size"])
        if traf.max_seq > int(conf["engine"]["max_seq"]):
            raise SystemExit(f"mix needs {traf.max_seq} positions, engine "
                             f"holds {conf['engine']['max_seq']}")
        warm = traf.warmup_request()
        warm_out: list = []
        warmers = [threading.Thread(
            target=lambda: warm_out.append(client.generate(port, warm)))
            for _ in range(traf.clients)]
        for th in warmers:
            th.start()
        for th in warmers:
            th.join()
        bad = [r for r in warm_out if r.failed]
        if bad:
            raise RuntimeError(f"warm-up request failed: {bad[0].error} "
                               f"{bad[0].finish_reason}")
        client.get(port, "/v1/metrics")
        t_warm = time.time() - t
        compile_setup = clock.lap()
        setup_s = time.time() - t_start
        log_fn(f"setup: {setup_s:.3f} s = interpreter start "
               f"{t_import - t_start:.3f} s + jax import and chip init "
               f"{t_chip - t_import:.3f} s + program "
               f"imports {t_ready - t_chip:.3f} s + init+deploy "
               f"{t_build:.3f} s + warm-up {t_warm:.3f} s + other "
               f"{setup_s - (t_ready - t_start) - t_build - t_warm:.3f} s; "
               f"backend compile {compile_setup:.3f} s of it")
        tracer = None
        trace_dir = RUN_DIR / f"trace-{ctx['cell']['name']}"
        if trace:
            from repro import obs
            shutil.rmtree(trace_dir, ignore_errors=True)
            t_a = time.perf_counter()
            tracer = obs.Tracer(enabled=True)
            t_tracer0 = (t_a + time.perf_counter()) / 2
            obs.set_default_tracer(tracer)
            jax.profiler.start_trace(str(trace_dir))
            with jax.profiler.TraceAnnotation(MARKER):
                t_marker = time.perf_counter()
        before = client.parse_prometheus(client.get(port, "/v1/metrics"))
        t0 = time.perf_counter() + 0.01
        t1 = t0 + seconds
        records = client.run_closed_loop(port, traf, t0, t1)
        after = client.parse_prometheus(client.get(port, "/v1/metrics"))
        window_compile_s = clock.lap()
        trace_red = None
        if trace:
            jax.profiler.stop_trace()
        peak = peak_bytes()
    finally:
        server.shutdown()
        server.server_close()
        front.close(drain=False, timeout=60)
        http.join(timeout=60)
        eng.close()
        clock.close()
    if trace:
        from repro import obs
        from bench import trace_reduce
        planes = trace_reduce.load_xplane(str(trace_dir))
        marker_ns = trace_reduce.find_marker(planes, MARKER)
        if marker_ns is None:
            raise RuntimeError(f"trace has no {MARKER} annotation")
        offset = marker_ns - t_marker * 1e9
        spans = trace_host_spans(tracer, t_tracer0, offset)
        obs.set_default_tracer(obs.Tracer(enabled=False))
        trace_red = trace_reduce.reduce(
            planes, (t0 * 1e9 + offset, t1 * 1e9 + offset), spans,
            n_devices=int(ctx["cell"]["chips"]))
        del planes
    window_steps = [s for s in steps.steps if s.t0 >= t0 and s.t1 <= t1]
    del steps, front, eng, server
    log_fn(f"program freed: {free_device()} device bytes still live")
    return {"records": records, "t0": t0, "t1": t1, "setup_s": setup_s,
            "compile_setup_s": compile_setup,
            "window_compile_s": window_compile_s, "before": before,
            "after": after, "steps": window_steps, "trace": trace_red,
            "peak_bytes": peak, "sizes": sizes}


def compare(ctx: dict, seed: int, records) -> dict:
    """Served tokens of a seed-drawn sample against the reference."""
    import numpy as np
    conf = ctx["config"]
    _, ref = arch_modules(conf)
    chosen = sample_for_check(records, seed, conf["check"])
    if not chosen:
        return {"requests": 0, "tokens": 0, "max_logit_gap": None}
    seqs = [list(r.prompt) + r.tokens for r in chosen]
    starts = [len(r.prompt) for r in chosen]
    gaps = ref.served_gaps(sizes_of(conf), seed, seqs, starts)
    return {"requests": len(chosen), "tokens": int(gaps.size),
            "max_logit_gap": float(np.max(gaps))}


def judge(gap, limit: float, attempted: int, failed: int) -> bool:
    """``correct``: requests were served, none failed, and the widest gap
    of a compared token is within the configuration's limit."""
    return bool(attempted > 0 and failed == 0 and gap is not None
                and gap <= limit)


def device_info() -> dict:
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def run(ctx: dict, seed: int, seconds: float, trace: bool) -> dict:
    """One run of the cell: the result line as a dict."""
    conf = ctx["config"]
    rec = serve_window(ctx, seed, seconds, trace)
    records = rec["records"]
    window = rec["t1"] - rec["t0"]
    attempted = len(records)
    failed = sum(r.failed for r in records)
    device = {**device_info(), "memory_peak_bytes": rec["peak_bytes"]}
    step_s = sorted(s.t1 - s.t0 for s in rec["steps"]) or [0.0]
    log(f"window: {window:.3f} s, {attempted} requests ({failed} failed), "
        f"{sum(len(r.tokens) for r in records)} tokens served, "
        f"{len(rec['steps'])} steps (host median {step_s[len(step_s) // 2]}"
        f" s, max {step_s[-1]} s), {rec['window_compile_s']} s of backend "
        f"compile inside the window")
    metrics = {}
    e2e = end_to_end(records, rec["t0"], rec["t1"])
    e2e["setup_s"] = rec["setup_s"]
    e2e["peak_hbm_gib"] = rec["peak_bytes"] / 2**30
    breakdown = None
    if trace:
        peaks = load_peaks(device["kind"])
        view = Run(records=records, window_s=window,
                   server_before=rec["before"], server_after=rec["after"],
                   steps=rec["steps"], trace=rec["trace"],
                   sizes=rec["sizes"], peaks=peaks, config=conf)
        for m in ctx["per_layer"]:
            value = load_reader(m["name"])(view)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        breakdown = {"device_ops": rec["trace"]["device_ops"],
                     "idle_gaps": rec["trace"]["idle_gaps"]}
    else:
        for m in ctx["end_to_end"]:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}
    t = time.time()
    cmp = compare(ctx, seed, records)
    limit = float(conf["check"]["max_logit_gap"])
    gap = cmp["max_logit_gap"]
    correct = judge(gap, limit, attempted, failed)
    log(f"reference: {cmp['requests']} requests, {cmp['tokens']} served "
        f"tokens compared in {time.time() - t:.3f} s")
    check = {"max_logit_gap": {"value": gap, "limit": limit},
             "failed_requests": {"value": failed, "limit": 0}}
    for name, c in check.items():
        log(f"check {name}: {c['value']} limit {c['limit']}")
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = check
    return out


def main(argv=None) -> int:
    t_process = process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    ctx = resolve(load_spec(), args.workload)
    ctx["t_process"], ctx["t_import"] = t_process, T_IMPORT
    require_chips(int(ctx["cell"]["chips"]))
    ctx["t_chip"] = time.time()
    cache = enable_compile_cache()
    ctx["t_ready"] = time.time()
    log(f"device: {device_info()}, compile cache {cache}")
    out = run(ctx, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
