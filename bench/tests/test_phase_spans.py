"""The readers of the program's own phase spans and request stamps.

``data/trace_phases.pbtxt`` is two engine steps of a traced window (1000 to
20000 ns) with the program's ``engine.*`` spans nested inside the harness's
wrappers, worked out by hand below. The smoke runs drive the real harness
on the CPU with the profiler on."""

import json
import statistics
import time
from pathlib import Path
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

import _smoke
from bench import harness, traffic
from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
NS = 1e-9
METRICS = harness.BENCH / "metrics"
#: the committed cell each smoke cell stands for
CELL = {"model": "qwen2_5_3b.decode_heavy", "kv": "qwen2_5_3b_kvpath.longctx"}


def _reader(name):
    return harness._load_module(METRICS / f"{name}.py").read


def _reduced(name):
    return tr.Reduced(ProfileData.from_text_proto((DATA / name).read_text()), chips=1)


@pytest.fixture(scope="module")
def phases():
    return _reduced("trace_phases.pbtxt")


def test_idle_gaps_fall_under_the_innermost_program_span(phases):
    # device busy: scatter 2200..2600 and 11000..11400, sweep 5000..6500
    # and 14000..15500, attention 7000..7300 and 16000..16300: 4400 ns of
    # the 19000 ns window. Each idle gap goes to the shortest span over its
    # middle:
    #   1000..2200  (mid 1600)  engine.kv_write, inside engine.write_tokens
    #   2600..5000  (mid 3800)  engine.invalidate
    #   6500..7000  (mid 6750)  engine.tiered_sweep, inside sweep_and_pin
    #   7300..11000 (mid 9150)  engine.step (after the pin, before the sync)
    #   11400..14000 (mid 12700) engine.invalidate
    #   15500..16000 (mid 15750) engine.tiered_sweep
    #   16300..20000 (mid 18150) bench.sync
    # so the harness's own labels (engine.write_tokens,
    # engine.sweep_and_pin, engine_step) keep none of it
    assert phases.steps == 2
    assert phases.window_s == pytest.approx(19000 * NS)
    assert phases.busy_s == pytest.approx(4400 * NS)
    assert dict(phases.idle_gaps()) == {
        "engine.invalidate": pytest.approx(5000 * NS),
        "engine.step": pytest.approx(3700 * NS),
        "bench.sync": pytest.approx(3700 * NS),
        "engine.kv_write": pytest.approx(1200 * NS),
        "engine.tiered_sweep": pytest.approx(1000 * NS)}


def test_kv_write_ms_by_hand(phases):
    # engine.kv_write 600..1200 (clipped to the window: 200), 1550..3400
    # (1850) and 10550..12400 (1850); engine.invalidate 3500..4000 and
    # 12500..13000 (500 each): 4900 ns over 2 steps
    run = harness.Context(trace=phases)
    assert _reader("kv_write_ms")(run) == pytest.approx(2450 * 1e-6)


def test_kv_write_ms_reads_nothing_from_a_program_without_the_spans():
    run = harness.Context(trace=_reduced("trace_small.pbtxt"))
    assert _reader("kv_write_ms")(run) is None
    assert _reader("kv_write_ms")(harness.Context(trace=None)) is None


ORIGIN_NS = 7_000_000_000     # the harness's clock origin on perf_counter_ns


def _rec(due, late_ns=None, wait_ns=None):
    """A request due at ``due`` s, pushed ``late_ns`` after it and admitted
    ``wait_ns`` after its push (``-1``: still queued)."""
    req = SimpleNamespace()
    if late_ns is not None:
        req.t_enqueued_ns = ORIGIN_NS + int(due * 1e9) + late_ns
        req.t_admitted_ns = -1 if wait_ns < 0 else req.t_enqueued_ns + wait_ns
    return SimpleNamespace(due=due, req=req)


def test_queue_wait_ms_by_hand():
    read = _reader("queue_wait_ms")
    # window (1.0, 4.0]; the 0.5 s request is pushed on time, so the
    # stamps put the close at ORIGIN_NS + 4.0 s
    recs = [_rec(0.5, 0, 9_000_000),            # due before the window
            _rec(1.0, 40_000, 2_000_000),       # 2 ms
            _rec(1.5, 10_000, 500_000),         # 0.5 ms
            _rec(2.0, 70_000, 7_000_000),       # 7 ms
            _rec(3.9995, 500, -1),              # queued at the close: 0.0 ms
            _rec(2.5, 1_000, -1)]               # queued at the close: 1499 ms
    run = harness.Context(recs=recs, t_open=1.0, window_s=3.0)
    # waits 0.5, 2, 7, 1499 ms and 0.0 (4.0 s - 3.9995 s - 500 ns)
    assert read(run) == pytest.approx(2.0)
    recs.append(_rec(3.0, 2_000, -1))           # 998 ms: the median moves
    assert read(run) == pytest.approx(0.5 * (2.0 + 7.0))
    # a program whose requests carry no stamps gives nothing
    assert read(harness.Context(recs=[_rec(1.0), _rec(2.0)], t_open=1.0,
                                window_s=3.0)) is None


def test_queue_wait_ms_on_the_open_loop_smoke_cell(monkeypatch):
    """Bursts of the open loop queue requests behind three slots; the
    reader's median is that of the window's requests' waits, a request
    still queued at the close counted to the close, which lies after every
    stamp the window took and before the run returned."""
    seen = []
    real = harness.Context

    def keep(**kw):
        seen.append(kw)
        return real(**kw)

    monkeypatch.setattr(harness, "Context", keep)
    out = _smoke.run("kv", seconds=2.0, trace=True)
    returned_ns = time.perf_counter_ns()
    (ctx,) = seen
    end = ctx["t_open"] + ctx["window_s"]
    mine = [r.req for r in ctx["recs"] if ctx["t_open"] <= r.due <= end]
    stamps = [t for r in ctx["recs"] for t in (r.req.t_enqueued_ns, r.req.t_admitted_ns)]

    def median_wait(close_ns):
        return statistics.median(
            1e-6 * ((r.t_admitted_ns if r.t_admitted_ns >= 0 else close_ns)
                    - r.t_enqueued_ns) for r in mine)

    assert len(mine) >= 3 and all(r.t_enqueued_ns > 0 for r in mine)
    got = out["metrics"]["queue_wait_ms"]
    assert got["unit"] == "ms" and got["value"] > 0
    assert median_wait(max(stamps)) <= got["value"] <= median_wait(returned_ns)


def _with_device_plane(pd):
    """The trace's host planes, plus a TPU plane (the CPU profile has none)
    whose programs run exactly inside the program's own spans:
    ``jit__sweep_fn`` in ``engine.tiered_sweep``, ``jit_paged_attention`` in
    ``engine.attention`` and ``engine.pin``."""
    program = {"engine.tiered_sweep": "jit__sweep_fn(1)",
               "engine.attention": "jit_paged_attention(2)",
               "engine.pin": "jit_paged_attention(2)"}
    ev = lambda a, d, n: SimpleNamespace(start_ns=a, duration_ns=d, name=n)
    runs = [ev(e.start_ns, e.duration_ns, program[e.name]) for p in pd.planes
            for ln in p.lines for e in ln.events if e.name in program]
    tpu = SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name=tr.OPS_LINE, events=runs),
        SimpleNamespace(name=tr.MODULES_LINE, events=runs)])
    return SimpleNamespace(planes=list(pd.planes) + [tpu], profile=pd)


@pytest.mark.parametrize("kind", ["model", "kv"])
def test_traced_smoke_run_reports_every_per_layer_metric(monkeypatch, kind):
    """A ``--trace 1`` run reports, non-null, every per-layer metric that
    ``BENCHMARK.json`` asks of the cell the smoke cell stands for, with a
    v5e's peaks and a device plane laid beside the CPU's host trace. Its
    requests span several pages, so the sweep issues prefetches."""
    monkeypatch.setattr(tr, "load", lambda path: _with_device_plane(
        ProfileData.from_file(str(path))))
    monkeypatch.setattr(harness, "_device_info", lambda jax, chips: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 0})
    spec = _smoke.spec(kind)
    spec["traffic"] = traffic.load(DATA / "smoke_pages.json")
    out = harness.run(f"smoke.{kind}", 2**33 + 17, 2.0, True, spec=spec,
                      log=lambda **_: None)
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["per_layer"]
            if CELL[kind] in m.get("workloads", [CELL[kind]])}
    assert {"kv_write_ms"} <= want
    assert want <= set(out["metrics"]), want - set(out["metrics"])
    assert all(out["metrics"][m]["value"] is not None for m in want)
    assert 0 < out["metrics"]["idle_share"]["value"] < 100
    assert out["metrics"]["kv_write_ms"]["value"] > 0
