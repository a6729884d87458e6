"""From a profiler trace to the benchmark's device numbers.

The JAX profiler writes an ``.xplane.pb``; :class:`Reduced` reads it with
``jax.profiler.ProfileData`` and nothing else:

* the traced window: the ``bench.window`` host annotation the recorder
  holds open while it traces (without one, the first to the last
  ``engine_step`` annotation the harness wraps around each engine step);
* busy time per chip: the union of the intervals in which an operation ran
  on that device (its ``XLA Ops`` line, or every line of a plane without
  one), clipped to the window;
* device time per program: the executions on a device's ``XLA Modules``
  line (or every line of a plane without one), by program name;
* idle gaps: the stretches of the window with no operation on the device,
  each labelled by the innermost host annotation (``engine.*``,
  ``executor.*``, ``bench.*``, ``engine_step``) that covers its middle.
"""

from __future__ import annotations

import re
import shutil
import tempfile
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP = "engine_step"
WINDOW = "bench.window"
HOST_LABELS = ("engine.", "executor.", "bench.", STEP)


def _events(line):
    for ev in line.events:
        yield ev.start_ns, ev.start_ns + ev.duration_ns, ev.name


def module_name(name: str) -> str:
    """``jit_paged_attention(123)`` -> ``jit_paged_attention``."""
    return re.sub(r"\(\d+\)$", "", name)


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


class Reduced:
    """The numbers of one traced window; ``chips`` device planes are read
    (the first ``chips`` TPU planes by id)."""

    def __init__(self, pd, chips: int = 1):
        planes = sorted((p for p in pd.planes if DEVICE_PLANE.match(p.name)),
                        key=lambda p: int(p.name.rsplit(":", 1)[1]))[:chips]
        self.devices = []
        for p in planes:
            lines = {ln.name: list(_events(ln)) for ln in p.lines}
            every = [ev for evs in lines.values() for ev in evs]
            self.devices.append({"ops": lines.get(OPS_LINE, every),
                                 "modules": lines.get(MODULES_LINE, every)})
        host = [ev for p in pd.planes if not p.name.startswith("/device:")
                for ln in p.lines for ev in _events(ln)
                if ev[2].startswith(HOST_LABELS)]
        self.host = [ev for ev in host if ev[2] != WINDOW]
        steps = [ev for ev in self.host if ev[2] == STEP]
        span = [ev for ev in host if ev[2] == WINDOW] or steps
        if span:
            self.lo = min(a for a, _, _ in span)
            self.hi = max(b for _, b, _ in span)
        else:
            self.lo = self.hi = 0.0
        self.window_s = (self.hi - self.lo) * 1e-9
        self.steps = len(steps)
        busy = [merge(clip([(a, b) for a, b, _ in d["ops"]], self.lo, self.hi))
                for d in self.devices]
        self._busy = busy
        self.busy_s = (sum(b - a for iv in busy for a, b in iv) * 1e-9 / len(busy)
                       if busy else 0.0)

    @property
    def idle_share(self) -> float | None:
        if self.window_s <= 0 or not self.devices:
            return None
        return 1.0 - self.busy_s / self.window_s

    def program_time(self, patterns) -> tuple[float, int]:
        """Mean over chips of the device seconds, and the executions, of
        the programs whose name matches one of ``patterns`` (regexes on the
        name without its ``(id)``), inside the window."""
        regs = [re.compile(p) for p in patterns]
        secs = n = 0
        for d in self.devices:
            for a, b, name in d["modules"]:
                if b > self.lo and a < self.hi and any(
                        r.fullmatch(module_name(name)) for r in regs):
                    secs += (b - a) * 1e-9
                    n += 1
        k = max(len(self.devices), 1)
        return secs / k, n / k

    def idle_gaps(self) -> list[tuple[str, float]]:
        """Idle seconds of the window by what the host was doing, summed
        per label and averaged over chips, longest first."""
        host = sorted(self.host)
        total: dict[str, float] = defaultdict(float)
        for busy in self._busy:
            edges = [self.lo] + [x for iv in busy for x in iv] + [self.hi]
            gaps = sorted(((a + b) / 2, b - a) for a, b in zip(edges[::2], edges[1::2])
                          if b > a)
            active: list = []
            j = 0
            for mid, length in gaps:
                while j < len(host) and host[j][0] <= mid:
                    active.append(host[j])
                    j += 1
                active = [e for e in active if e[1] >= mid]
                label = (min(active, key=lambda e: e[1] - e[0])[2] if active
                         else "host:other")
                total[label] += length * 1e-9
        k = max(len(self._busy), 1)
        return sorted(((n, s / k) for n, s in total.items()), key=lambda x: -x[1])

    def device_ops(self) -> list[tuple[str, float]]:
        """Device seconds per program, averaged over chips, longest first."""
        total: dict[str, float] = defaultdict(float)
        for d in self.devices:
            for a, b, name in clip_named(d["modules"], self.lo, self.hi):
                total[module_name(name)] += (b - a) * 1e-9
        k = max(len(self.devices), 1)
        return sorted(((n, s / k) for n, s in total.items()), key=lambda x: -x[1])

    def breakdown(self, top: int = 10) -> dict:
        return {"device_ops": [[n, s] for n, s in self.device_ops()[:top]],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps()[:top]]}


def clip_named(events, lo, hi):
    return [(max(a, lo), min(b, hi), n) for a, b, n in events if b > lo and a < hi]


def load(path: Path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def find_xplane(root: Path) -> Path:
    found = sorted(Path(root).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]


class Recorder:
    """Starts and stops the profiler around the end of a window, into a
    temporary directory that :meth:`reduce` reads and removes."""

    def __init__(self, jax):
        self.jax = jax
        self.on = False
        self.t0 = self.t1 = None
        self.dir = Path(tempfile.mkdtemp(prefix="bench_trace_"))

    def start(self, now: float) -> None:
        self.jax.profiler.start_trace(str(self.dir))
        self._window = self.jax.profiler.TraceAnnotation(WINDOW)
        self._window.__enter__()
        self.on, self.t0 = True, now

    def stop(self, now: float) -> None:
        self._window.__exit__(None, None, None)
        self.jax.profiler.stop_trace()
        self.on, self.t1 = False, now

    def reduce(self, chips: int) -> Reduced:
        try:
            return Reduced(load(find_xplane(self.dir)), chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
