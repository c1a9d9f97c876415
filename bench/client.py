"""Closed-loop clients over real sockets, timed on the client's side.

Each client thread sends its next request as soon as the previous one has
ended (no think time), reads the SSE stream token by token, and records
when it sent the request and when each token arrived. At the close of the
window a client stops sending and hangs up on the request in flight; the
tokens it already received stay served.
"""
from __future__ import annotations

import dataclasses
import http.client
import json
import threading
import time


@dataclasses.dataclass
class Record:
    client: int
    index: int
    prompt: tuple[int, ...]
    max_new: int
    t_send: float = 0.0
    t_tokens: list[float] = dataclasses.field(default_factory=list)
    tokens: list[int] = dataclasses.field(default_factory=list)
    finish_reason: str | None = None
    error: str | None = None
    cut: bool = False              # hung up at the close of the window

    @property
    def failed(self) -> bool:
        """A request that errored or ended other than by its length."""
        if self.cut:
            return self.error is not None
        return self.error is not None or self.finish_reason != "length" \
            or len(self.tokens) != self.max_new


def generate(port: int, req, deadline: float | None = None,
             timeout: float = 600.0) -> Record:
    """POST /v1/generate with SSE streaming; stop reading at ``deadline``
    (a ``perf_counter`` reading) if the stream is still open then."""
    rec = Record(req.client, req.index, req.prompt, req.max_new)
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        body = json.dumps({"prompt": list(req.prompt),
                           "max_new": req.max_new, "stream": True})
        rec.t_send = time.perf_counter()
        conn.request("POST", "/v1/generate", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec.error = f"HTTP {resp.status}"
            return rec
        for raw in resp:
            now = time.perf_counter()
            line = raw.decode().strip()
            if line.startswith("data: "):
                data = line[len("data: "):]
                if data == "[DONE]":
                    break
                frame = json.loads(data)
                if "token" in frame:
                    rec.tokens.append(int(frame["token"]))
                    rec.t_tokens.append(now)
                else:
                    rec.finish_reason = frame.get("finish_reason")
            if deadline is not None and now >= deadline \
                    and rec.finish_reason is None:
                rec.cut = True
                break
    except (OSError, ValueError) as e:
        rec.error = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    return rec


def get(port: int, path: str) -> str:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        body = resp.read().decode()
        if resp.status != 200:
            raise RuntimeError(f"GET {path} -> HTTP {resp.status}")
        return body
    finally:
        conn.close()


def _sleep_until(t: float):
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.001))


def run_closed_loop(port: int, traffic, t_start: float, t_end: float
                    ) -> list[Record]:
    """Client ``c`` sends back to back from ``t_start + c * stagger_s``
    until ``t_end``."""
    out: list[list[Record]] = [[] for _ in range(traffic.clients)]

    def client(c: int):
        i = 0
        _sleep_until(t_start + c * traffic.stagger_s)
        while time.perf_counter() < t_end:
            out[c].append(generate(port, traffic.request(c, i),
                                   deadline=t_end))
            i += 1
            if traffic.think_s > 0:
                time.sleep(traffic.think_s)

    threads = [threading.Thread(target=client, args=(c,), daemon=True,
                                name=f"bench-client-{c}")
               for c in range(traffic.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [r for recs in out for r in recs]


def parse_prometheus(text: str) -> dict[str, float]:
    """Sample name (with its label set) -> value, from exposition text."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        try:
            out[name] = float(value)
        except ValueError:
            continue
    return out
