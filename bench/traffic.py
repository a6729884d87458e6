"""The one traffic generator: reads a mix's parameters from a data file.

A mix (``bench/traffic/<name>.json``) states the loop and the lengths:

* ``"loop": "closed"`` — ``clients`` callers, each sending its next request
  when the previous one has finished (sent at the end of the step that
  emitted its last token).
* ``"loop": "open"`` — requests due on a schedule whatever the system does:
  ``rate`` requests/s on average, modulated by ``phases`` (a repeating
  list of ``{"seconds", "rate_x"}``). Each phase holds a fixed number of
  arrivals (its share of the expected count) at uniform random times within
  it: a Poisson process conditioned on its count.
* ``prompt`` / ``gen``: lognormal ``median`` and ``sigma``, clipped to
  ``[min, max]``. ``sizes`` requests' lengths are the distribution's
  stratified quantiles, paired by a fixed permutation, so every seed draws
  the same set of sizes.
* ``preroll_steps`` (default 0): engine steps the mix drives before the
  window opens, so that the window sees the loop under way rather than its
  cold start. Times are counted from the pre-roll's start.

Closed loops send their sizes in waves of ``clients`` requests. Each wave is
a stratified slice of the set (every ``sizes / clients``-th prompt
quantile), in a fixed order inside the wave and from wave to wave. So every
seed sends the same requests in the same order, and the work a window holds
does not depend on the seed; what the seed changes is the prompts' tokens
and the weights (or the K/V bytes). An open loop's seed draws its arrival
times and the order of its sizes.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Item:
    req_id: int
    prompt_len: int
    gen: int
    due: float                # seconds after the window opened
    client: int = -1


def load(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def _quantiles(d: dict, n: int) -> list[int]:
    nd = NormalDist()
    out = []
    for i in range(n):
        x = d["median"] * math.exp(d["sigma"] * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(x), d["min"]), d["max"])))
    return out


def size_set(spec: dict) -> list[tuple[int, int]]:
    """``(prompt_len, gen)`` of the mix's ``sizes`` requests, in prompt
    quantile order; the same for every seed."""
    n = int(spec["sizes"])
    prompts = _quantiles(spec["prompt"], n)
    gens = _quantiles(spec["gen"], n)
    pair = np.random.default_rng(0).permutation(n)
    return [(prompts[i], gens[int(pair[i])]) for i in range(n)]


def max_context(spec: dict) -> tuple[int, int]:
    """The longest prompt and output the mix can send: the deployment's
    context limit (``ServeConfig.prompt_len`` + ``gen``)."""
    return int(spec["prompt"]["max"]), int(spec["gen"]["max"])


def prompt_tokens(seed: int, req_id: int, n: int, vocab: int) -> np.ndarray:
    return np.random.default_rng([seed, req_id]).integers(
        0, vocab, n, dtype=np.int32)


class ClosedLoop:
    """``clients`` callers that each wait for their previous reply."""

    def __init__(self, spec: dict):
        self.sizes = size_set(spec)
        self.clients = int(spec["clients"])
        n = len(self.sizes)
        if n % self.clients:
            raise ValueError(f"sizes={n} is not a multiple of "
                             f"clients={self.clients}")
        self.waves = [list(range(w, n, n // self.clients))
                      for w in range(n // self.clients)]
        self.rng = np.random.default_rng(0)
        self.order: list[int] = []
        self.idle = {c: 0.0 for c in range(self.clients)}
        self.sent = 0

    def _next_size(self) -> tuple[int, int]:
        if not self.order:
            for wave in self.waves:
                self.order.extend(wave[i] for i in self.rng.permutation(len(wave)))
        return self.sizes[self.order.pop(0)]

    def ready(self, now: float) -> list[Item]:
        out = []
        for c, since in sorted(self.idle.items(), key=lambda kv: (kv[1], kv[0])):
            if since <= now:
                p, g = self._next_size()
                out.append(Item(self.sent, p, g, since, c))
                self.sent += 1
                del self.idle[c]
        return out

    def done(self, item: Item, now: float) -> None:
        self.idle[item.client] = now

    def next_due(self) -> float | None:
        return min(self.idle.values()) if self.idle else None


class OpenLoop:
    """Requests due on a fixed schedule, whatever the system does. The
    schedule is drawn one cycle of ``phases`` at a time, as the clock
    reaches it."""

    def __init__(self, spec: dict, seed: int):
        self.sizes = size_set(spec)
        self.rng = np.random.default_rng(seed)
        self.rate = float(spec["rate"])
        self.phases = spec.get("phases") or [{"seconds": 1.0, "rate_x": 1.0}]
        self.items: list[Item] = []
        self.order: list[int] = []
        self.t = self.expected = 0.0
        self.next = 0

    def _extend(self) -> None:
        for ph in self.phases:
            dur = float(ph["seconds"])
            before = math.floor(self.expected)
            self.expected += self.rate * float(ph["rate_x"]) * dur
            for d in sorted(self.t + dur * self.rng.random(math.floor(self.expected) - before)):
                if not self.order:
                    self.order = [int(i) for i in self.rng.permutation(len(self.sizes))]
                i = len(self.items)
                self.items.append(Item(i, *self.sizes[self.order.pop(0)], float(d)))
            self.t += dur

    def _due(self) -> float:
        while self.next >= len(self.items):
            self._extend()
        return self.items[self.next].due

    def ready(self, now: float) -> list[Item]:
        out = []
        while self._due() <= now:
            out.append(self.items[self.next])
            self.next += 1
        return out

    def done(self, item: Item, now: float) -> None:
        pass

    def next_due(self) -> float | None:
        return self._due()


def make(spec: dict, seed: int):
    if spec["loop"] == "closed":
        return ClosedLoop(spec)
    if spec["loop"] == "open":
        return OpenLoop(spec, seed)
    raise ValueError(f"unknown loop {spec['loop']!r}")
