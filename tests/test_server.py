"""ServeFront (ISSUE 8): the async continuous-batching frontend — token
streaming parity with the bare engine loop, mid-generation cancellation
returning every KV block within one step, bounded-queue backpressure on
both the frontend and ``Engine.submit``, threaded producer/consumer
stress with random disconnects on the streamed dense AND expert-paged
MoE planes, and the stdlib HTTP frontend end to end (SSE streaming,
shared-prefix reuse, mid-stream disconnect)."""
from __future__ import annotations

import http.client
import json
import threading
import time

import jax
import pytest

from repro.configs import get_config
from repro.configs.paper_models import OPT_TINY
from repro.models import dense, moe
from repro.serving.engine import Engine
from repro.serving.server import ServeFront, make_http_server
from repro.store import PageStore, StreamConfig

MAX_SEQ = 96
BS = 16
MOE_CFG = get_config("qwen3-moe-30b-a3b", smoke=True)


@pytest.fixture(scope="module")
def params():
    return dense.init(OPT_TINY, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def moe_params():
    return moe.init(MOE_CFG, jax.random.PRNGKey(0))


def _engine(params, **kw):
    return Engine(OPT_TINY, params, max_slots=2, max_seq=MAX_SEQ, rber=0.0,
                  **kw)


def _streamed(params, **kw):
    return _engine(params, weight_store=PageStore(n_planes=8),
                   stream_cfg=StreamConfig(), **kw)


def _free_and_cached(eng):
    cached = 0 if eng.prefix is None else len(eng.prefix)
    return len(eng.pool.free_blocks) + cached


# --- ServeFront core ----------------------------------------------------------


def test_front_streams_identical_to_engine_loop(params):
    """The frontend's per-token stream equals the bare submit/step loop's
    greedy output, for interleaved requests of different lengths."""
    ref = _engine(params)
    r1 = ref.submit(list(range(1, 30)), max_new=8)
    r2 = ref.submit([9, 8], max_new=8)
    want = ref.run()

    front = ServeFront(_engine(params))
    h1 = front.add_request(list(range(1, 30)), max_new=8)
    h2 = front.add_request([9, 8], max_new=8)
    got1 = list(h1)                      # blocking per-token iterator
    assert got1 == want[r1]
    assert h2.result(timeout=60) == want[r2]
    assert front.stats()["finished"] == 2
    front.close()


def test_front_async_stream(params):
    """atokens(): the async generator yields the same stream."""
    import asyncio

    ref = _engine(params)
    rid = ref.submit([3, 1, 4, 1, 5], max_new=6)
    want = ref.run()[rid]
    front = ServeFront(_engine(params))
    h = front.add_request([3, 1, 4, 1, 5], max_new=6)

    async def drain():
        return [t async for t in h.atokens()]

    assert asyncio.run(drain()) == want
    front.close()


def test_cancel_mid_generation_reclaims_blocks(params):
    """Mid-decode disconnect: the stream terminates immediately and every
    KV block the request held is back on the free list within one step."""
    eng = _engine(params)
    total_free = len(eng.pool.free_blocks)
    front = ServeFront(eng)
    h = front.add_request(list(range(1, 40)), max_new=48)
    it = iter(h)
    first = next(it)                     # generation is underway
    assert isinstance(first, int)
    steps_before = eng._steps_done
    assert h.cancel()
    assert list(it) == []                # stream ends promptly
    deadline = time.monotonic() + 30
    while len(eng.pool.free_blocks) < total_free:
        assert time.monotonic() < deadline, "cancelled KV blocks leaked"
        time.sleep(0.01)
    # reclaim took effect within one engine step of the cancel
    assert eng._steps_done <= steps_before + 2
    assert not h.cancel()                # idempotent
    st = front.stats()
    assert st["cancelled"] == 1 and st["finished"] == 0
    front.close()


def test_cancel_waiting_request(params):
    """A request cancelled while still queued never touches the pool."""
    eng = _engine(params)
    front = ServeFront(eng, max_waiting=8)
    holders = [front.add_request(list(range(1, 30)), max_new=16)
               for _ in range(2)]        # occupy both slots
    waiter = front.add_request([5, 6, 7], max_new=16)
    assert waiter.cancel()
    assert list(waiter) == []
    for h in holders:
        h.result(timeout=60)
    assert front.stats()["cancelled"] == 1
    front.close()
    assert len(eng.pool.free_blocks) == eng.pool.n_blocks - 1


def test_front_backpressure_timeout(params):
    front = ServeFront(_engine(params), max_waiting=1)
    h = front.add_request(list(range(1, 30)), max_new=32)
    with pytest.raises(TimeoutError, match="capacity"):
        front.add_request([1, 2], max_new=4, timeout=0.05)
    h.result(timeout=60)
    front.close()


def test_front_close_rejects_and_drains(params):
    front = ServeFront(_engine(params))
    h = front.add_request([2, 3, 4], max_new=6)
    front.close(drain=True)              # serves the live request out
    assert h.done and len(h.tokens) == 6
    with pytest.raises(RuntimeError, match="closed"):
        front.add_request([1], max_new=1)


def test_front_close_no_drain_cancels(params):
    eng = _engine(params)
    front = ServeFront(eng)
    h = front.add_request(list(range(1, 30)), max_new=64)
    next(iter(h))
    front.close(drain=False)
    assert h.done and len(h.tokens) < 64
    assert len(eng.pool.free_blocks) == eng.pool.n_blocks - 1


# --- Engine.submit backpressure (the oversubscription-wait fix) ---------------


def test_engine_submit_timeout_on_full_queue(params):
    eng = _engine(params, max_waiting=1)
    eng.submit(list(range(1, 30)), max_new=4)
    eng.submit(list(range(1, 30)), max_new=4)
    eng.submit(list(range(1, 30)), max_new=4)   # fills the bounded queue
    t0 = time.monotonic()
    with pytest.raises(TimeoutError, match="queue full"):
        eng.submit([1, 2], max_new=2, timeout=0.1)
    assert time.monotonic() - t0 < 5
    eng.run()
    eng.close()


def test_engine_submit_wait_interrupted_by_close(params):
    """A producer blocked on a full queue must NOT hang a dying server:
    close() wakes it with RuntimeError."""
    eng = _engine(params, max_waiting=1)
    eng.submit(list(range(1, 30)), max_new=4)
    eng.submit(list(range(1, 30)), max_new=4)
    eng.submit(list(range(1, 30)), max_new=4)
    err = []

    def blocked():
        try:
            eng.submit([1, 2], max_new=2)        # no timeout: waits
        except RuntimeError as e:
            err.append(e)

    t = threading.Thread(target=blocked)
    t.start()
    time.sleep(0.2)
    assert t.is_alive()                  # genuinely blocked
    eng.close()
    t.join(timeout=10)
    assert not t.is_alive() and err, "close() did not wake the submitter"


def test_engine_submit_unblocks_when_queue_drains(params):
    eng = _engine(params, max_waiting=1)
    eng.submit(list(range(1, 30)), max_new=2)
    eng.submit(list(range(1, 30)), max_new=2)
    eng.submit(list(range(1, 30)), max_new=2)
    got = []

    def blocked():
        got.append(eng.submit([1, 2], max_new=2, timeout=30))

    t = threading.Thread(target=blocked)
    t.start()
    eng.run()                            # steps drain the waiting queue
    t.join(timeout=10)
    assert got, "submit never unblocked"
    eng.run()
    assert eng.requests[got[0]].done
    eng.close()


# --- threaded producer/consumer stress with random disconnects ----------------


def _stress(eng, n_producers=4, n_requests=3, cancel_every=3):
    """Concurrent producers streaming from ``eng`` through a ServeFront,
    cancelling every ``cancel_every``-th request mid-stream; afterwards
    every non-cancelled stream is non-empty and exactly the engine's
    recorded output, and zero KV blocks leak."""
    front = ServeFront(eng, max_waiting=16)
    results, errors = [], []

    def producer(pid):
        try:
            rng_tok = (pid * 7 + 3) % 50 + 1
            for i in range(n_requests):
                # one full shared system block + a per-request tail, so
                # prefix caching (when on) sees insertable/hittable chains
                prompt = [2] * BS \
                    + [rng_tok + (i * 13 + j) % 40 for j in range(9)]
                h = front.add_request(prompt, max_new=8, timeout=120)
                if (pid + i) % cancel_every == 0:
                    got = []
                    for t in h:
                        got.append(t)
                        h.cancel()       # disconnect mid-stream
                    results.append(("cancelled", h, got))
                else:
                    results.append(("served", h, list(h)))
        except BaseException as e:       # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=producer, args=(p,))
               for p in range(n_producers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not errors, errors
    assert len(results) == n_producers * n_requests
    n_cancelled = sum(1 for kind, _, _ in results if kind == "cancelled")
    assert n_cancelled > 0
    for kind, h, got in results:
        if kind == "served":
            assert len(got) == 8 and got == h.tokens
    front.close()
    # zero leaks: every block free again (or retained by the prefix index)
    assert _free_and_cached(eng) == eng.pool.n_blocks - 1
    return front


def test_stress_streamed_dense(params):
    front = _stress(_streamed(params))
    assert front.stats()["finished"] > 0


def test_stress_streamed_moe(moe_params):
    eng = Engine(MOE_CFG, moe_params, max_slots=2, max_seq=MAX_SEQ,
                 weight_store=PageStore(n_planes=8),
                 stream_cfg=StreamConfig())
    _stress(eng, n_producers=3, n_requests=2)
    assert eng.step_traces == 3          # churn + cancels never retrace


@pytest.mark.parametrize("plane,families", [
    ("resident", set()), ("streamed", {"stream"}),
    ("expert_paged", {"stream", "experts"})])
def test_stats_nest_the_plane_families(params, moe_params, plane, families):
    """/v1/stats nests exactly the stat families the engine's plane has."""
    if plane == "resident":
        eng = _engine(params)
    elif plane == "streamed":
        eng = _streamed(params)
    else:
        eng = Engine(MOE_CFG, moe_params, max_slots=2, max_seq=MAX_SEQ,
                     weight_store=PageStore(n_planes=8),
                     stream_cfg=StreamConfig())
    front = ServeFront(eng)
    try:
        assert families == {"stream", "experts"} & set(front.stats())
    finally:
        front.close()


def test_stress_prefix_cache_on(params):
    eng = _engine(params, prefix_cache=True)
    _stress(eng)
    assert eng.prefix_stats()["prefix_inserted"] > 0


# --- the stdlib HTTP frontend -------------------------------------------------


@pytest.fixture()
def http_server(params):
    eng = _engine(params, prefix_cache=True)
    front = ServeFront(eng, max_waiting=8)
    server = make_http_server(front, 0)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    yield server, front, eng
    server.shutdown()
    server.server_close()
    front.close()


def _post(port, payload):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/v1/generate", json.dumps(payload),
                 {"Content-Type": "application/json"})
    return conn, conn.getresponse()


def _sse_tokens(resp, want_reason=False):
    toks, reason = [], None
    for line in resp.read().decode().splitlines():
        if line.startswith("data: ") and line != "data: [DONE]":
            frame = json.loads(line[len("data: "):])
            if "token" in frame:
                toks.append(frame["token"])
            else:
                reason = frame.get("finish_reason")
    return (toks, reason) if want_reason else toks


def test_http_end_to_end(params, http_server):
    """THE acceptance flow: a client streams tokens over SSE; a second
    client sharing a >= 2-block system prompt gets the identical output
    while admission skips the cached-prefix prefill; a mid-stream
    disconnect returns every KV block; /v1/stats reports it all."""
    server, front, eng = http_server
    port = server.server_address[1]
    system = list(range(1, 40))          # 2 full blocks + tail

    ref = _engine(params)
    rid = ref.submit(system + [50, 51], max_new=8)
    want = ref.run()[rid]

    conn, resp = _post(port, {"prompt": system + [50, 51], "max_new": 8})
    assert resp.status == 200
    assert resp.getheader("Content-Type") == "text/event-stream"
    assert _sse_tokens(resp) == want
    conn.close()

    saved0 = eng.prefix_stats()["prefix_prefill_tokens_saved"]
    conn, resp = _post(port, {"prompt": system + [50, 51], "max_new": 8})
    assert _sse_tokens(resp) == want     # EXACT parity on the cache hit
    conn.close()
    assert eng.prefix_stats()["prefix_prefill_tokens_saved"] \
        == saved0 + 2 * BS               # cached blocks never prefilled

    # mid-stream disconnect -> cancellation -> blocks reclaimed
    conn, resp = _post(port, {"prompt": system + [70], "max_new": 48})
    resp.fp.readline()                   # first SSE frame is flowing
    resp.close()                         # drop the socket mid-stream
    conn.close()
    deadline = time.monotonic() + 30
    while front.stats()["cancelled"] < 1 \
            or _free_and_cached(eng) != eng.pool.n_blocks - 1:
        assert time.monotonic() < deadline, "disconnect leaked KV blocks"
        time.sleep(0.02)

    c = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    c.request("GET", "/v1/stats")
    st = json.loads(c.getresponse().read())
    c.close()
    assert st["finished"] == 2 and st["cancelled"] == 1
    assert st["prefix_hits"] >= 2 and st["live_handles"] == 0


def test_http_non_streaming_and_errors(params, http_server):
    server, front, _ = http_server
    port = server.server_address[1]
    conn, resp = _post(port, {"prompt": [4, 5, 6], "max_new": 5,
                              "stream": False})
    body = json.loads(resp.read())
    assert resp.status == 200 and len(body["tokens"]) == 5
    conn.close()

    conn, resp = _post(port, {"max_new": 5})         # no prompt
    assert resp.status == 400
    conn.close()
    conn, resp = _post(port, {"prompt": []})         # empty prompt
    assert resp.status == 400
    conn.close()

    c = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    c.request("GET", "/nope")
    assert c.getresponse().status == 404
    c.close()


# --- fault plane: survive step faults, deadlines, health (ISSUE 9) ------------


def test_close_is_idempotent_and_thread_safe(params):
    """Exactly one caller shuts down; double, concurrent, and post-close
    calls all return without hanging, re-joining, or re-raising."""
    front = ServeFront(_engine(params))
    h = front.add_request([1, 2, 3], max_new=2)
    h.result(timeout=60)
    errs = []

    def closer():
        try:
            front.close(drain=True, timeout=60)
        except BaseException as e:       # noqa: BLE001 - recorded for assert
            errs.append(e)

    threads = [threading.Thread(target=closer) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads), "close() hung"
    assert errs == []
    front.close()                        # post-close call: plain no-op
    assert front.stats()["closed"]


def test_engine_close_is_idempotent_and_thread_safe(params):
    eng = _engine(params)
    eng.submit([1, 2], max_new=1)
    eng.run()
    threads = [threading.Thread(target=eng.close) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads), "Engine.close() hung"
    eng.close()                          # and again, after it's done


def test_finish_reason_length_and_cancelled(params):
    front = ServeFront(_engine(params))
    try:
        h = front.add_request([1, 2, 3], max_new=3)
        assert h.result(timeout=60) and h.finish_reason == "length"
        h2 = front.add_request([1, 2, 3, 4], max_new=48)
        it = iter(h2)
        next(it)                         # generation is under way
        h2.cancel()
        h2._done.wait(30)
        assert h2.finish_reason == "cancelled"
    finally:
        front.close(drain=False)


def test_step_fault_fails_requests_but_server_survives(params):
    """THE degradation contract: a persistently-faulted step fails the
    in-flight requests with finish_reason="error" — consumers unblock,
    KV blocks come back — and the SAME front serves the next request."""
    from repro.runtime.fault import FaultPolicy

    boom = {"arm": False}

    def hook(step, retries):
        if boom["arm"]:
            raise RuntimeError("injected persistent step fault")

    front = ServeFront(_engine(params), poll_s=0.01,
                       fault_policy=FaultPolicy(
                           max_retries=1, retry_on=(Exception,),
                           straggler_tolerance=10 ** 9),
                       step_fault_hook=hook)
    eng = front.engine
    try:
        h0 = front.add_request([1, 2, 3], max_new=2)
        assert h0.result(timeout=60) and h0.finish_reason == "length"

        boom["arm"] = True
        h = front.add_request([4, 5, 6], max_new=32)
        h._done.wait(60)
        assert h.done and h.finish_reason == "error"
        assert front.step_faults >= 1 and front.requests_failed == 1
        assert front.stats()["step_retries"] >= 1

        boom["arm"] = False              # fault clears: serving resumes
        h2 = front.add_request([7, 8, 9], max_new=2)
        toks = h2.result(timeout=60)
        assert len(toks) == 2 and h2.finish_reason == "length"
        deadline = time.monotonic() + 30
        while front.stats()["live_handles"] and time.monotonic() < deadline:
            time.sleep(0.02)
        assert _free_and_cached(eng) == eng.pool.n_blocks - 1  # no leaks
        code, payload = front.health()
        assert code == 200 and payload["status"] == "degraded"
    finally:
        front.close(drain=False)


def test_request_deadline_times_out(params):
    """max_time_s bounds a request's wall clock: it finishes with
    finish_reason="timeout", keeps the tokens sampled so far, and its
    KV blocks are reclaimed."""
    front = ServeFront(_engine(params), poll_s=0.01)
    eng = front.engine
    try:
        h = front.add_request([1, 2, 3], max_new=MAX_SEQ - 4,
                              max_time_s=0.5)
        h._done.wait(60)
        assert h.done and h.finish_reason == "timeout"
        assert front.n_timeout == 1
        deadline = time.monotonic() + 30
        while front.stats()["live_handles"] and time.monotonic() < deadline:
            time.sleep(0.02)
        assert _free_and_cached(eng) == eng.pool.n_blocks - 1
    finally:
        front.close(drain=False)


def test_http_health_endpoint(params, http_server):
    server, front, _ = http_server
    port = server.server_address[1]
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    c.request("GET", "/v1/health")
    resp = c.getresponse()
    body = json.loads(resp.read())
    assert resp.status == 200
    assert body["status"] in ("ok", "degraded")
    assert body["step_faults"] == 0 and body["requests_failed"] == 0
    c.close()


def test_http_finish_reason_frame(params, http_server):
    """The SSE stream ends with a finish_reason frame before [DONE], and
    the non-streaming body carries the same field."""
    server, front, _ = http_server
    port = server.server_address[1]
    conn, resp = _post(port, {"prompt": [1, 2, 3], "max_new": 4})
    toks, reason = _sse_tokens(resp, want_reason=True)
    assert len(toks) == 4 and reason == "length"
    conn.close()
    conn, resp = _post(port, {"prompt": [1, 2, 3], "max_new": 4,
                              "stream": False})
    body = json.loads(resp.read())
    assert body["finish_reason"] == "length"
    conn.close()
