"""One traffic generator for every mix file under ``bench/traffic``.

A mix is data: the number of closed-loop clients, the prompt and output
length ranges with their distribution, and how many length levels it
spans. Level ``i`` pairs the ``i``-th prompt length with an output length
by a fixed permutation. With ``n`` levels and ``c`` clients, client ``j``
sends levels ``j*n/c, j*n/c + 1, ...`` (modulo ``n``), so the clients'
first requests together span the whole range, and client ``j`` starts
``j * stagger_s`` after the window opens, so the server admits them in a
fixed order. Every seed therefore sends the same sizes in the same order;
the seed draws the token ids (and, in ``bench/run.py``, the weights). A
closed loop whose arrival order moved with the seed would make the seed,
not the system, the largest source of run-to-run spread: on a TPU v5e the
median time to first token of the decode cell moved by 7% across seeds
when the seed chose the order, and by 0.1% between two runs of one seed.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    client: int
    index: int            # position in the client's sequence
    prompt: tuple[int, ...]
    max_new: int


def levels(spec: dict, n: int) -> list[int]:
    """``n`` lengths at the mid-quantiles of ``spec``'s distribution."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if lo == hi:
        return [lo] * n
    qs = [(i + 0.5) / n for i in range(n)]
    dist = spec.get("dist", "uniform")
    if dist == "uniform":
        return [int(round(lo + q * (hi - lo))) for q in qs]
    if dist == "log_uniform":
        a, b = math.log(lo), math.log(hi)
        return [int(round(math.exp(a + q * (b - a)))) for q in qs]
    raise ValueError(f"unknown length distribution {dist!r}")


def _rng(seed: int, *path: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**64 - 1), *path])


class Traffic:
    """The request sequence of each client of ``mix`` under ``seed``."""

    def __init__(self, mix: dict, seed: int, vocab_size: int):
        if mix.get("loop") != "closed":
            raise ValueError("the generator drives closed-loop mixes only")
        self.mix = mix
        self.seed = int(seed)
        self.vocab_size = int(vocab_size)
        self.clients = int(mix["clients"])
        self.think_s = float(mix.get("think_s", 0.0))
        self.stagger_s = float(mix.get("stagger_s", 0.0))
        n = int(mix["levels"])
        if n % self.clients:
            raise ValueError(f"levels ({n}) must be a multiple of clients "
                             f"({self.clients})")
        prompts = levels(mix["prompt_tokens"], n)
        outputs = levels(mix["output_tokens"], n)
        pair = np.random.default_rng(0).permutation(n)
        self.levels = [(prompts[i], outputs[pair[i]]) for i in range(n)]
        self._low = int(mix.get("token_low", 1))

    @property
    def max_seq(self) -> int:
        return max(p + o for p, o in self.levels)

    def level(self, client: int, index: int) -> tuple[int, int]:
        """(prompt length, output length) of a client's ``index``-th
        request."""
        n, c = len(self.levels), self.clients
        return self.levels[(index + client * (n // c)) % n]

    def request(self, client: int, index: int) -> Request:
        p_len, out = self.level(client, index)
        rng = _rng(self.seed, client, index, 1)
        prompt = rng.integers(self._low, self.vocab_size, p_len)
        return Request(client, index, tuple(int(t) for t in prompt), out)

    def warmup_request(self) -> Request:
        """A short request that drives the same compiled programs."""
        rng = _rng(self.seed, 2**31)
        prompt = rng.integers(self._low, self.vocab_size, 24)
        return Request(-1, 0, tuple(int(t) for t in prompt), 2)
