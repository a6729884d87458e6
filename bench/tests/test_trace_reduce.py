"""The trace reduction on a small hand-written trace: one TPU plane (two
programs, two ops) and one host thread (the recorder's window, one engine
step, one sweep span), with every number worked out by hand."""

from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import trace_reduce as tr

TRACE = Path(__file__).resolve().parent / "data" / "trace_small.pbtxt"
NS = 1e-9


def test_merge_and_clip():
    assert tr.merge([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert tr.clip([(0, 2), (3, 8), (9, 10)], 1, 5) == [(1, 2), (3, 5)]
    assert tr.module_name("jit_paged_attention(42)") == "jit_paged_attention"


@pytest.fixture(scope="module")
def reduced():
    return tr.Reduced(ProfileData.from_text_proto(TRACE.read_text()), chips=1)


def test_window_and_busy_time(reduced):
    # window: bench.window 1000..11000 ns; ops 1000..3000 and 6000..7000
    assert reduced.steps == 1
    assert reduced.window_s == pytest.approx(10000 * NS)
    assert reduced.busy_s == pytest.approx(3000 * NS)
    assert reduced.idle_share == pytest.approx(0.7)


def test_idle_gaps_are_labelled_by_the_innermost_host_span(reduced):
    # 3000..6000 lies in engine.sweep_and_pin; 7000..11000 in engine_step
    # (its middle, 9000, is inside the step that ends at 10000)
    assert reduced.idle_gaps() == [
        ("engine_step", pytest.approx(4000 * NS)),
        ("engine.sweep_and_pin", pytest.approx(3000 * NS))]


def test_programs_by_name(reduced):
    assert reduced.device_ops() == [("jit_paged_attention", pytest.approx(2000 * NS)),
                                    ("jit__sweep_fn", pytest.approx(1000 * NS))]
    secs, calls = reduced.program_time([r"jit_paged_attention"])
    assert secs == pytest.approx(2000 * NS) and calls == 1
    assert reduced.program_time([r"no_such_program"]) == (0.0, 0.0)
    b = reduced.breakdown(top=1)
    assert len(b["device_ops"]) == 1 and len(b["idle_gaps"]) == 1
