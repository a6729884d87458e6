"""Unified counter/histogram registry + the single percentile ladder.

Before this module existed the repo had two independent percentile ladders
(``repro.core.metrics`` for the trace simulator, ``repro.fabric.metrics``
for the event engine) and every driver hand-rolled its own
``time.perf_counter()`` bracketing. This module is the one implementation
all of them now delegate to (DESIGN.md §8.4):

* :func:`percentile_ladder` — p50–p99.9 + avg/max over a sample, with an
  explicit ``n`` field and ``NaN`` (not 0.0) for the empty sample, so "no
  data" can never masquerade as "zero latency" in a downstream report.
* :class:`Registry` — named monotonically increasing counters and
  latency/size histograms; one registry per run, summarized once at the
  end. ``launch/serve.py`` builds its per-request TTFT + token-latency
  report on it.
* :meth:`Registry.span` — a host span: a ``jax.profiler.TraceAnnotation``
  for its duration, so it lands on the profiler's clock beside the device
  ops whenever a trace is being recorded, and a histogram sample of its
  wall time. JAX dispatch is async, so by default the span times the
  *enqueue*; a body that sets the handle's ``sync`` makes the span block
  on that result (``jax.block_until_ready``) before the clock stops, so
  the duration covers the device work. :meth:`Registry.phase` is the
  annotation alone, for a block no histogram reader needs.

Everything here is host-side Python — nothing in this module is jitted or
traced, and nothing touches the hot data path.
"""

from __future__ import annotations

import math
import time

import numpy as np

DEFAULT_QS = (50.0, 90.0, 99.0, 99.9)


def percentile_ladder(samples, qs=DEFAULT_QS) -> dict:
    """``{p50, ..., avg, max, n}`` of a sample; NaNs when ``n == 0``.

    The empty-sample contract is deliberate: an all-zeros ladder is
    indistinguishable from a genuinely zero-latency run, so empty samples
    report ``NaN`` for every statistic plus ``n=0`` — callers that want to
    render something print the ``n`` field or skip the row.
    """
    keys = [f"p{q:g}" for q in qs]
    if samples is None or len(samples) == 0:
        return {k: math.nan for k in keys} | {"avg": math.nan,
                                              "max": math.nan, "n": 0}
    arr = np.asarray(samples, dtype=np.float64)
    out = {k: float(np.percentile(arr, q)) for k, q in zip(keys, qs)}
    out["avg"] = float(arr.mean())
    out["max"] = float(arr.max())
    out["n"] = int(arr.size)
    return out


class Counter:
    """A named monotone counter."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0

    def add(self, n: int = 1) -> None:
        self.value += int(n)


class Histogram:
    """A named sample accumulator summarized as a percentile ladder."""

    __slots__ = ("name", "samples")

    def __init__(self, name: str):
        self.name = name
        self.samples: list[float] = []

    def observe(self, v: float) -> None:
        self.samples.append(float(v))

    def extend(self, vs) -> None:
        self.samples.extend(float(v) for v in vs)

    @property
    def total(self) -> float:
        return float(sum(self.samples))

    def ladder(self, qs=DEFAULT_QS) -> dict:
        return percentile_ladder(self.samples, qs)


class _Span:
    """One open :meth:`Registry.span`: the profiler annotation, the start
    time and the ``sync`` box its body may park a device result in.

    Setting ``sync`` to a jax array/pytree makes the span block on it
    before stopping the clock, so the measured wall time includes the
    device work rather than just its dispatch.
    """

    __slots__ = ("sync", "_hist", "_ann", "_t0")

    def __init__(self, hist: Histogram, ann):
        self.sync = None
        self._hist = hist
        self._ann = ann

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        try:
            if self.sync is not None:
                import jax
                jax.block_until_ready(self.sync)
            self._hist.observe(time.perf_counter() - self._t0)
        finally:
            self._ann.__exit__(*exc)


class Registry:
    """Named counters + histograms for one run; summarized at the end.

    ``prefix`` is put before every span's name in the profiler trace (the
    histogram keeps the bare name): the serving engine's registry uses
    ``"engine."``, so its ``tiered_sweep`` span is the histogram
    ``tiered_sweep`` and the trace annotation ``engine.tiered_sweep``.
    """

    def __init__(self, prefix: str = ""):
        self.prefix = prefix
        self._counters: dict[str, Counter] = {}
        self._hists: dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        if name not in self._counters:
            self._counters[name] = Counter(name)
        return self._counters[name]

    def histogram(self, name: str) -> Histogram:
        if name not in self._hists:
            self._hists[name] = Histogram(name)
        return self._hists[name]

    def span(self, name: str, **meta) -> _Span:
        """Time a block into histogram ``name`` (seconds) and annotate it
        in the profiler trace as ``prefix + name``, with ``meta`` as the
        annotation's metadata (e.g. ``req_id=``). When no trace is being
        recorded the annotation costs about a microsecond.

        >>> with reg.span("attention", req_id=3) as sp:
        ...     out = attention(...)
        ...     sp.sync = out          # block on the device result
        """
        from jax.profiler import TraceAnnotation

        return _Span(self.histogram(name),
                     TraceAnnotation(self.prefix + name, **meta))

    def phase(self, name: str, **meta):
        """Annotate a block in the profiler trace as ``prefix + name``, with
        ``meta`` as its metadata, and keep no histogram: for a block whose
        time only the trace reads. Costs what :meth:`span`'s annotation
        does."""
        from jax.profiler import TraceAnnotation

        return TraceAnnotation(self.prefix + name, **meta)

    def summary(self, qs=DEFAULT_QS) -> dict:
        """``{"counters": {name: int}, "histograms": {name: ladder}}``."""
        return {
            "counters": {n: c.value for n, c in sorted(self._counters.items())},
            "histograms": {n: h.ladder(qs)
                           for n, h in sorted(self._hists.items())},
        }
