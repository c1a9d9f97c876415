"""Chip smoke: serve OPT-1.3B at published widths on one TPU through the
normal entry points (``build_engine`` / ``Engine`` / ``ServeFront``).

    python chip_smoke.py [--seed N]       # one chip (what CI-on-chip runs)
    python chip_smoke.py --four-chip      # four chips: tensor-parallel plane

One chip, one process, three phases, weights random from ``--seed``:

  * resident — ``ServeFront`` behind ``make_http_server`` on a local port;
    ``N_REQUESTS`` prompts of 32-128 tokens go over real sockets (SSE),
    each asking for ``MAX_NEW`` greedy tokens. A ``finish_reason`` other
    than "length", or any step fault/retry the frontend absorbed, fails.
  * reference — ``dense.prefill`` on the resident engine's deployed params,
    on the TPU and on the host CPU; the max-abs logit error must stay
    within ``REL_TOL`` of the CPU logits' scale.
  * streamed — ``serve(stream=True)`` under a ``STREAM_BUDGET_MIB`` device
    budget (well under the flash tier, so windows rotate every step): pool
    uploads > 0, every upload through pinned-host staging, zero fetch
    retries/faults, greedy tokens identical to the resident phase.

``--four-chip`` runs only the tensor-parallel streamed plane
(``StreamConfig(n_shards=4)``) and the one-chip streamed plane on device 0
it is compared with. Every failure exits non-zero; the last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Times printed here are smoke timings (cold or warm compile cache), not
benchmark numbers.
"""
from __future__ import annotations

import argparse
import functools
import gc
import http.client
import json
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "opt-1.3b"
N_REQUESTS = 4
MAX_NEW = 16
PROMPT_LEN = (32, 128)               # inclusive bounds, drawn from the seed
STREAM_BUDGET_MIB = 256              # flash tier of OPT-1.3B is ~1.5 GiB
FOUR_CHIP_BUDGET_MIB = 512           # lm_head must fit each shard's share
# TPU vs host-CPU prefill logits. Both sides keep bf16 activations and the
# int8 weights are exact in bf16, so they differ only where f32
# accumulation order or a transcendental flips a bf16 rounding (2**-8
# relative) and the flips compound over the layers: allow 5% of the
# logits' max magnitude. A wrong kernel or layout errs by O(100%).
REL_TOL = 0.05
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    pass


class CompileClock:
    """Backend-compile seconds of this process (JAX's monitoring events):
    ``lap()`` returns the seconds compiled since the previous lap."""

    def __init__(self):
        self.total = self._mark = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event == BACKEND_COMPILE:
            self.total += secs

    def lap(self) -> float:
        dt, self._mark = self.total - self._mark, self.total
        return dt

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on)


def serving_kw() -> dict:
    """Engine settings shared by every phase. Greedy sampling and clean
    flash (rber 0) make tokens comparable across planes. Algorithm 2 is
    off: its bitmap follows the batch, so the socket phase's arrival
    timing would decide which wq columns run quantized. One chunk holds a
    whole prompt, so the prefill split does not depend on arrival either."""
    from repro.core.scheduler import AdmissionConfig
    from repro.serving.sampler import SampleConfig
    return {"rber": 0.0, "kv_aware": False,
            "sample_cfg": SampleConfig(temperature=0.0),
            "admission_cfg": AdmissionConfig(
                chunk_tokens=PROMPT_LEN[1],
                token_budget=N_REQUESTS * PROMPT_LEN[1], adaptive=False)}


def make_prompts(vocab_size: int, seed: int, n: int = N_REQUESTS
                 ) -> list[list[int]]:
    import numpy as np
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, n)
    return [rng.integers(1, vocab_size, int(n_tok)).tolist() for n_tok in lens]


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _generate(port: int, prompt: list[int], max_new: int
              ) -> tuple[list[int], str | None]:
    """POST /v1/generate and read the SSE stream to ``[DONE]``."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    try:
        body = json.dumps({"prompt": prompt, "max_new": max_new,
                           "stream": True})
        conn.request("POST", "/v1/generate", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise SmokeFailure(f"/v1/generate -> HTTP {resp.status}")
        tokens, reason = [], None
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            data = line[len("data: "):]
            if data == "[DONE]":
                break
            frame = json.loads(data)
            if "token" in frame:
                tokens.append(frame["token"])
            else:
                reason = frame["finish_reason"]
        return tokens, reason
    finally:
        conn.close()


def resident_phase(arch: str, seed: int, prompts, max_new: int = MAX_NEW):
    """Serve ``prompts`` through ServeFront over real sockets. Returns
    (tokens per prompt, the engine — closed, its params still live)."""
    from repro.launch.serve import build_engine
    from repro.serving.server import ServeFront, make_http_server
    eng = build_engine(arch, seed=seed, **serving_kw())
    front = ServeFront(eng, max_waiting=len(prompts))
    server = make_http_server(front, 0)
    loop = threading.Thread(target=server.serve_forever, daemon=True,
                            name="smoke-http")
    loop.start()
    try:
        port = server.server_address[1]
        with ThreadPoolExecutor(len(prompts)) as ex:
            results = list(ex.map(
                functools.partial(_generate, port, max_new=max_new), prompts))
        stats = front.stats()
    finally:
        server.shutdown()
        server.server_close()
        front.close(drain=True)
        loop.join(timeout=60)
    for i, (toks, reason) in enumerate(results):
        if reason != "length" or len(toks) != max_new:
            raise SmokeFailure(f"resident request {i}: finish_reason="
                               f"{reason!r} after {len(toks)} tokens")
    faults = {k: stats[k] for k in ("step_faults", "step_retries",
                                    "step_watchdog", "requests_failed")}
    if any(faults.values()):
        raise SmokeFailure(f"resident frontend absorbed faults: {faults} "
                           f"(last: {stats['last_fault']})")
    return [toks for toks, _ in results], eng


def reference_phase(cfg, params, prompt) -> tuple[float, float]:
    """``dense.prefill`` on the deployed params, on the default device and
    on the host CPU. Returns (max-abs logit error, max-abs CPU logit)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.models import dense
    prefill = jax.jit(functools.partial(dense.prefill, cfg))
    batch = {"tokens": jnp.asarray([prompt], jnp.int32)}
    dev_logits = np.asarray(prefill(params, batch)[0])
    cpu = jax.devices("cpu")[0]
    cpu_logits = np.asarray(prefill(jax.device_put(params, cpu),
                                    jax.device_put(batch, cpu))[0])
    if not (np.isfinite(dev_logits).all() and np.isfinite(cpu_logits).all()):
        raise SmokeFailure("non-finite prefill logits")
    err = float(np.max(np.abs(dev_logits - cpu_logits)))
    scale = float(np.max(np.abs(cpu_logits)))
    if err > REL_TOL * scale:
        raise SmokeFailure(f"prefill logits: max|dev - cpu| = {err} > "
                           f"{REL_TOL} * {scale}")
    return err, scale


def streamed_phase(arch: str, seed: int, prompts, budget_mib: float,
                   shards: int = 1, max_new: int = MAX_NEW):
    """Serve ``prompts`` through ``serve(stream=True)`` under a device
    budget. Returns (tokens per prompt, stream stats)."""
    from repro.launch.serve import resolve_config, serve
    out = serve(arch, seed=seed, stream=True, device_budget_mib=budget_mib,
                shards=shards, prompts=prompts, max_new=max_new,
                **serving_kw())
    st = out["stream"]
    faults = {k: st[k] for k in ("fetch_retries", "fetch_faults")}
    if any(faults.values()):
        raise SmokeFailure(f"streamed plane fetch faults: {faults}")
    if st["pool_uploads"] == 0:
        raise SmokeFailure("streamed plane made no pool uploads")
    # one layer per group: more windows than layers = fetched again later
    n_layers = resolve_config(arch).n_layers
    if st["groups_streamed"] <= n_layers:
        raise SmokeFailure(f"windows did not rotate: {st['groups_streamed']}"
                           f" streamed for {n_layers} layer groups")
    if shards == 1 and jax.default_backend() != "cpu" \
            and st["pool_pinned_uploads"] != st["pool_uploads"]:
        raise SmokeFailure(f"{st['pool_uploads']} uploads, only "
                           f"{st['pool_pinned_uploads']} pinned-staged")
    if shards > 1 \
            and st["pool_shard_transfers"] != shards * st["pool_uploads"]:
        raise SmokeFailure(f"{st['pool_shard_transfers']} shard transfers "
                           f"for {st['pool_uploads']} uploads x {shards}")
    return [out["outputs"][i] for i in range(len(prompts))], st


def _same_tokens(label: str, want, got):
    if got != want:
        diff = [i for i, (a, b) in enumerate(zip(want, got)) if a != b]
        raise SmokeFailure(f"{label}: tokens differ for requests {diff}: "
                           f"want {[want[i] for i in diff]}, "
                           f"got {[got[i] for i in diff]}")


def run_one_chip(arch: str = ARCH, seed: int = 0,
                 budget_mib: float = STREAM_BUDGET_MIB, log=print) -> dict:
    """Resident (sockets) -> reference -> streamed. Raises SmokeFailure."""
    from repro.launch.serve import resolve_config
    cfg = resolve_config(arch)
    prompts = make_prompts(cfg.vocab_size, seed)
    clock = CompileClock()
    try:
        t0 = time.perf_counter()
        res_tokens, eng = resident_phase(arch, seed, prompts)
        log(f"resident: {len(prompts)} socket requests, prompt lengths "
            f"{[len(p) for p in prompts]}, {sum(map(len, res_tokens))} "
            f"tokens, smoke timing {time.perf_counter() - t0:.1f}s wall, "
            f"{clock.lap():.1f}s compile, peak {peak_bytes()} B")
        t0 = time.perf_counter()
        err, scale = reference_phase(cfg, eng.params, prompts[0])
        log(f"reference: prefill max|tpu - cpu| logit = {err:.6g} "
            f"(tolerance {REL_TOL} x max|logit| {scale:.6g} = "
            f"{REL_TOL * scale:.6g}), smoke timing "
            f"{time.perf_counter() - t0:.1f}s wall, "
            f"{clock.lap():.1f}s compile")
        del eng
        gc.collect()
        t0 = time.perf_counter()
        str_tokens, st = streamed_phase(arch, seed, prompts, budget_mib)
        log(f"streamed: budget {budget_mib} MiB, {st['groups_streamed']} "
            f"windows streamed ({st['bytes_streamed'] / 2**20:.0f} MiB), "
            f"{st['pool_uploads']} uploads / {st['pool_pinned_uploads']} "
            f"pinned, {st['pool_grows']} pool grows, {st['fetch_faults']} "
            f"fetch faults, "
            f"{sum(map(len, str_tokens))} tokens, smoke timing "
            f"{time.perf_counter() - t0:.1f}s wall, {clock.lap():.1f}s "
            f"compile, peak {peak_bytes()} B")
        _same_tokens("streamed vs resident", res_tokens, str_tokens)
        log(f"streamed tokens == resident tokens for all {len(prompts)} "
            "requests")
    finally:
        clock.close()
    return {"tokens": res_tokens, "stream": st}


def run_four_chip(arch: str = ARCH, seed: int = 0,
                  budget_mib: float = FOUR_CHIP_BUDGET_MIB, log=print
                  ) -> dict:
    """The tensor-parallel streamed plane on 4 devices vs the one-chip
    streamed plane on device 0, same budget and prompts."""
    from repro.launch.serve import resolve_config
    prompts = make_prompts(resolve_config(arch).vocab_size, seed)
    clock = CompileClock()
    try:
        out = {}
        for shards in (1, 4):
            t0 = time.perf_counter()
            toks, st = streamed_phase(arch, seed, prompts, budget_mib,
                                      shards=shards)
            out[shards] = toks
            log(f"streamed x{shards}: budget {budget_mib} MiB, "
                f"{st['groups_streamed']} windows, {st['pool_uploads']} "
                f"uploads, {st.get('pool_shard_transfers', '-')} shard "
                f"transfers, smoke timing {time.perf_counter() - t0:.1f}s "
                f"wall, {clock.lap():.1f}s compile")
        _same_tokens("4-shard vs 1-chip streamed", out[1], out[4])
        log(f"4-shard tokens == 1-chip tokens for all {len(prompts)} "
            "requests")
    finally:
        clock.close()
    return out


def require_tpu(count: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, found "
                         f"{len(devs)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the 4-way tensor-parallel streamed plane "
                         "and the one-chip plane it is compared with")
    args = ap.parse_args(argv)
    platforms = jax.config.jax_platforms
    if platforms and "cpu" not in platforms.split(","):
        # the reference phase needs the host CPU beside the chip
        jax.config.update("jax_platforms", f"{platforms},cpu")
    require_tpu(4 if args.four_chip else 1)
    from repro.launch.serve import device_label, enable_compile_cache
    cache = enable_compile_cache()
    print(f"device: {device_label()}, compile cache {cache}", flush=True)
    log = functools.partial(print, flush=True)
    if args.four_chip:
        run_four_chip(seed=args.seed, log=log)
    else:
        run_one_chip(seed=args.seed, log=log)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
