"""Continuous-batching serving engine: lifecycle, conservation, equivalence.

Three layers of coverage for :mod:`repro.serving` (DESIGN.md §10):

* **Control plane** — request state-machine edges, capacity-reserving
  admission, and the conservation invariants (every admitted request
  finishes or is queued, no slot double-occupancy, pages allocated ==
  pages recycled, allocator occupancy back to baseline) driven over random
  arrival/finish schedules — a seeded deterministic loop always runs, and
  a hypothesis property widens the net when the library is installed.
* **Model plane** — chunked prefill through per-request batch-1
  ``decode_step`` states produces the same first-token logits as the
  one-shot ``model.prefill`` (5e-3 model tolerance), for any chunking.
* **Observability** — allocator seq-stamps (recycled pages re-allocated
  to a new request never alias the previous owner's trace events), seeded
  :class:`ArrivalProcess` determinism, and the per-request lifecycle
  Perfetto track + JSONL round trip.
"""

import functools
import json
from pathlib import Path

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as hst
    HAVE_HYPOTHESIS = True
except ImportError:                       # deterministic tests still run
    HAVE_HYPOTHESIS = False

from repro.fabric.tenants import ArrivalProcess, TenantSpec
from repro.obs.export import (read_request_jsonl, to_chrome_trace,
                              write_request_jsonl)
from repro.obs.trace import RequestPhase
from repro.paging.kv_cache import PageAllocator
from repro.serving import (AdmissionQueue, Request, ServeConfig,
                           ServingEngine, SlotScheduler, SyntheticExecutor)
from repro.serving.engine import build_executor, gate_failures
from repro.serving.request import DECODE, FINISHED, PREFILL


# --------------------------------------------------------------------------
# request state machine
# --------------------------------------------------------------------------
class TestRequestLifecycle:
    def test_happy_path_edges(self):
        r = Request(0, prompt_len=5, gen=3, arrival_step=3)
        r.to(PREFILL, 4)
        assert r.admit_step == 4
        assert r.advance_prefill(3, 5) == 3
        assert r.state == PREFILL and r.ttft_steps == -1
        r.advance_prefill(8, 6)          # clamped to the 2 remaining tokens
        assert r.prefilled == 5 and r.state == DECODE
        assert r.decoded == 1            # prefill emits the first token
        assert r.first_token_step == 6 and r.ttft_steps == 3
        assert not r.advance_decode(7)
        assert r.advance_decode(8)       # quota reached
        r.to(FINISHED, 8)
        assert r.finish_step == 8

    def test_illegal_edges_rejected(self):
        r = Request(0, prompt_len=2, gen=1)
        with pytest.raises(ValueError):
            r.to(DECODE, 0)              # WAITING -> DECODE skips PREFILL
        with pytest.raises(ValueError):
            r.advance_decode(0)          # not decoding yet
        r.to(PREFILL, 0)
        with pytest.raises(ValueError):
            r.to(FINISHED, 0)            # PREFILL -> FINISHED skips DECODE

    def test_page_demand(self):
        r = Request(0, prompt_len=5, gen=3)
        assert r.max_len == 8
        assert r.pages_needed(page_size=4) == 2
        assert r.pages_needed(page_size=3) == 3


# --------------------------------------------------------------------------
# scheduler conservation over random arrival/finish schedules
# --------------------------------------------------------------------------
def drive_schedule(seed: int, n_requests: int, n_slots: int, page_size: int,
                   slack_pages: int, gang: bool) -> None:
    """Run a full random schedule through the control plane and assert the
    conservation invariants. Pure Python — no JAX, no model."""
    rng = np.random.default_rng(seed)
    reqs = [Request(i, prompt_len=int(rng.integers(1, 12)),
                    gen=int(rng.integers(1, 6)),
                    arrival_step=int(rng.integers(0, 20)))
            for i in range(n_requests)]
    n_pages = max(r.pages_needed(page_size) for r in reqs) + slack_pages
    alloc = PageAllocator(n_pages)
    sched = SlotScheduler(n_slots, alloc, page_size, gang=gang)
    queue = AdmissionQueue(reqs)
    finished: list[Request] = []
    t = 0
    while len(queue) or sched.active():
        assert t < 10_000, "schedule livelocked"
        sched.admit_ready(queue, t)
        occupants = [r.req_id for r in sched.active()]
        assert len(occupants) == len(set(occupants)), "slot double-occupancy"
        assert sched.reserved >= 0
        assert alloc.in_use + alloc.free_count == n_pages
        for req in list(sched.active()):
            if req.state == PREFILL:
                n = min(int(rng.integers(1, 5)),
                        req.prompt_len - req.prefilled)
                for pos in range(req.prefilled, req.prefilled + n):
                    sched.page_for_position(req, pos)
                req.advance_prefill(n, t)
                if req.state == DECODE and req.decoded >= req.gen:
                    sched.finish(req, t)
                    finished.append(req)
            elif req.state == DECODE:
                sched.page_for_position(req,
                                        req.prefilled + req.decoded - 1)
                if req.advance_decode(t):
                    sched.finish(req, t)
                    finished.append(req)
        t += 1
    # conservation: every request finished exactly once, pool at baseline
    assert sorted(r.req_id for r in finished) == list(range(n_requests))
    assert all(r.state == FINISHED for r in finished)
    assert sched.pages_allocated == sched.pages_recycled > 0
    assert alloc.in_use == 0 and alloc.alive() == ()
    assert alloc.occupancy() == 0.0
    assert sched.reserved == 0
    assert sched.active() == [] and len(queue) == 0


class TestSchedulerConservation:
    @pytest.mark.parametrize("seed", range(8))
    def test_random_schedules_conserve(self, seed):
        rng = np.random.default_rng(1000 + seed)
        drive_schedule(seed,
                       n_requests=int(rng.integers(1, 14)),
                       n_slots=int(rng.integers(1, 5)),
                       page_size=int(rng.integers(1, 6)),
                       slack_pages=int(rng.integers(0, 9)),
                       gang=bool(seed % 2))

    def test_admission_waits_on_memory_not_slots(self):
        """A tight pool stalls admission even with free slots, and the
        head-of-line request enters once pages recycle."""
        alloc = PageAllocator(4)
        sched = SlotScheduler(4, alloc, page_size=1)
        a = Request(0, prompt_len=2, gen=2)          # needs all 4 pages
        b = Request(1, prompt_len=2, gen=2)
        queue = AdmissionQueue([a, b])
        assert sched.admit_ready(queue, 0) == [a]    # b does not fit
        assert sched.free_slots() and len(queue) == 1
        assert sched.headroom() == 0
        # drive a to completion; b admits only after a's pages recycle
        for pos in range(2):
            sched.page_for_position(a, pos)
        a.advance_prefill(2, 0)
        assert sched.admit_ready(queue, 1) == []
        sched.page_for_position(a, 2)
        a.advance_decode(1)
        sched.finish(a, 1)
        assert sched.admit_ready(queue, 2) == [b]

    def test_gang_admission_waits_for_empty_slots(self):
        alloc = PageAllocator(64)
        sched = SlotScheduler(2, alloc, page_size=4, gang=True)
        reqs = [Request(i, prompt_len=4, gen=1, arrival_step=0)
                for i in range(3)]
        queue = AdmissionQueue(reqs)
        assert len(sched.admit_ready(queue, 0)) == 2     # first gang
        assert sched.admit_ready(queue, 1) == []         # slots busy
        for r in list(sched.active()):
            for pos in range(4):
                sched.page_for_position(r, pos)
            r.advance_prefill(4, 1)
            sched.finish(r, 1)
        assert len(sched.admit_ready(queue, 2)) == 1     # next gang

    @pytest.mark.skipif(not HAVE_HYPOTHESIS,
                        reason="hypothesis not installed")
    def test_conservation_property(self):
        @settings(max_examples=60, deadline=None)
        @given(seed=hst.integers(0, 2**31 - 1),
               n_requests=hst.integers(1, 16),
               n_slots=hst.integers(1, 5),
               page_size=hst.integers(1, 6),
               slack_pages=hst.integers(0, 10),
               gang=hst.booleans())
        def prop(seed, n_requests, n_slots, page_size, slack_pages, gang):
            drive_schedule(seed, n_requests, n_slots, page_size,
                           slack_pages, gang)

        prop()


# --------------------------------------------------------------------------
# allocator seq-stamps: recycled pages never alias their previous life
# --------------------------------------------------------------------------
class TestAllocatorStamps:
    def test_recycled_pages_get_strictly_greater_stamps(self):
        a = PageAllocator(8)
        first = a.alloc_seq(1, 4)
        gen1 = {p: a.stamp_of(p) for p in first}
        assert all(s > 0 for s in gen1.values())
        assert a.alive() == (1,) and a.occupancy() == 0.5
        assert a.owner_of(first[0]) == 1
        a.recycle(first)
        assert a.alive() == () and a.in_use == 0
        # free-list determinism re-hands the same physical pages to the
        # next request — the aliasing hazard this guard exists for
        second = a.alloc_seq(2, 4)
        reused = set(first) & set(second)
        assert reused, "free-list should recycle the same physical pages"
        for p in reused:
            assert a.stamp_of(p) > gen1[p]
        assert a.owner_of(second[0]) == 2

    def test_stamps_monotone_across_many_generations(self):
        a = PageAllocator(2)
        last = {0: 0, 1: 0}
        for turn in range(5):
            pages = a.alloc_seq(turn, 2)
            for p in pages:
                assert a.stamp_of(p) > last[p]
                last[p] = a.stamp_of(p)
            a.recycle(pages)

    def test_never_allocated_page_has_zero_stamp(self):
        a = PageAllocator(4)
        a.alloc_seq(0, 1)
        allocated = a.owned[0][0]
        for p in range(4):
            if p != allocated:
                assert a.stamp_of(p) == 0
                assert a.owner_of(p) is None


# --------------------------------------------------------------------------
# arrival process: seeded determinism, shared with fabric tenants
# --------------------------------------------------------------------------
class TestArrivalProcess:
    def test_seeded_determinism(self):
        ap = ArrivalProcess(kind="bursty", think_time=50.0, burst_len=3,
                            idle_time=400.0)
        t1 = ap.arrival_times(32, seed=7)
        t2 = ap.arrival_times(32, seed=7)
        np.testing.assert_array_equal(t1, t2)
        t3 = ap.arrival_times(32, seed=8)
        assert not np.array_equal(t1, t3)
        s1 = ap.arrival_steps(32, seed=7, step_us=100.0)
        s2 = ap.arrival_steps(32, seed=7, step_us=100.0)
        np.testing.assert_array_equal(s1, s2)

    def test_constant_kind_is_exact(self):
        ap = ArrivalProcess(kind="constant", think_time=10.0)
        np.testing.assert_allclose(ap.arrival_times(5, seed=0),
                                   [0.0, 10.0, 20.0, 30.0, 40.0])

    def test_bursty_gaps_only_at_burst_boundaries(self):
        ap = ArrivalProcess(kind="bursty", think_time=1.0, burst_len=4,
                            idle_time=1000.0)
        gaps = np.diff(ap.arrival_times(16, seed=3))
        idx = np.arange(1, 16)
        assert (gaps[idx % 4 != 0] == 1.0).all()
        assert (gaps[idx % 4 == 0] > 1.0).all()

    def test_churn_adds_downtime_and_restart(self):
        ap = ArrivalProcess(kind="churn", think_time=1.0, churn_every=5,
                            churn_downtime=99.0)
        rng = np.random.default_rng(0)
        gap, restart = ap.gap(rng, 5, 20)
        assert restart and gap == 100.0
        gap, restart = ap.gap(rng, 6, 20)
        assert not restart and gap == 1.0

    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError):
            ArrivalProcess(kind="poissonish")

    def test_tenant_spec_builds_matching_process(self):
        spec = TenantSpec(name="t", trace=[0, 1, 2], arrival="bursty",
                          think_time=5.0, burst_len=2, idle_time=77.0)
        ap = spec.arrival_process()
        assert ap.kind == "bursty" and ap.burst_len == 2
        assert ap.idle_time == 77.0 and ap.think_time == 5.0


# --------------------------------------------------------------------------
# chunked prefill == one-shot prefill (model plane)
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=1)
def _smoke_model_executor():
    from repro import configs as cfglib
    from repro.serving.executor import ModelExecutor
    return ModelExecutor(cfglib.get_smoke_config("qwen2_5_3b"), seed=0)


def _chunked_first_logits(ex, req_id: int, prompt_len: int, chunk: int):
    req = Request(req_id, prompt_len=prompt_len, gen=2)
    req.to(PREFILL, 0)
    ex.begin(req)
    while req.state == PREFILL:
        n = min(chunk, req.prompt_len - req.prefilled)
        ex.prefill_chunk(req, n)
        req.advance_prefill(n, 0)
    chunked = np.asarray(ex.last_logits[req.req_id], np.float32)
    oneshot = np.asarray(ex.oneshot_prefill_logits(req), np.float32)
    ex.end(req)
    return chunked, oneshot


class TestChunkedPrefillEquivalence:
    @pytest.mark.parametrize("chunk", [1, 3, 7])
    def test_matches_oneshot_fixed_chunks(self, chunk):
        ex = _smoke_model_executor()
        chunked, oneshot = _chunked_first_logits(ex, 100 + chunk,
                                                 prompt_len=7, chunk=chunk)
        np.testing.assert_allclose(chunked, oneshot, rtol=5e-3, atol=5e-3)
        # and greedy decoding agrees on the actual first token
        assert int(chunked.argmax()) == int(oneshot.argmax())

    @pytest.mark.skipif(not HAVE_HYPOTHESIS,
                        reason="hypothesis not installed")
    def test_matches_oneshot_property(self):
        @settings(max_examples=6, deadline=None)
        @given(prompt_len=hst.integers(2, 9), chunk=hst.integers(1, 9))
        def prop(prompt_len, chunk):
            ex = _smoke_model_executor()
            chunked, oneshot = _chunked_first_logits(
                ex, 1000 + prompt_len * 16 + chunk, prompt_len, chunk)
            np.testing.assert_allclose(chunked, oneshot, rtol=5e-3,
                                       atol=5e-3)

        prop()


def test_model_executor_gives_the_engine_its_query_heads():
    """The engine draws its query with the executor's own ``n_q_heads``,
    so a GQA model's query has ``n_heads`` heads, not ``n_kv_heads``."""
    ex = _smoke_model_executor()
    assert ex.n_q_heads == ex.cfg.n_heads != ex.n_kv_heads
    eng = ServingEngine(ServeConfig(requests=0, slots=1, prompt_len=4, gen=2,
                                    page_size=4), ex)
    assert eng.hq == ex.cfg.n_heads


def test_build_executor_refuses_encdec():
    """An encdec model is refused, never served as PRNG bytes."""
    with pytest.raises(ValueError, match="encdec"):
        build_executor("seamless_m4t_medium", smoke=True)


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """The environment's cache dir wins untouched; else one fixed path in
    the checkout, the same on every call."""
    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    default = Path(__file__).resolve().parents[1] / ".jax_cache"
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        assert got == compile_cache.enable_compile_cache()
        assert got == (str(tmp_path) if from_env else str(default))
        assert jax.config.jax_compilation_cache_dir == (
            was if from_env else str(default))
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


# --------------------------------------------------------------------------
# engine end-to-end (synthetic executor: real data path + pins, no model)
# --------------------------------------------------------------------------
def _run_engine(**overrides):
    cfg = ServeConfig(requests=5, slots=2, prompt_len=8, gen=4, page_size=4,
                      prefill_chunk=4, arrival="bursty", burst_len=2,
                      think_time=1000.0, idle_time=3000.0, seed=3,
                      **overrides)
    ex = SyntheticExecutor(n_kv_heads=2, head_dim=8, seed=0)
    eng = ServingEngine(cfg, ex)
    return eng, eng.run()


class TestEngineEndToEnd:
    def test_continuous_run_drains_clean(self):
        eng, report = _run_engine(trace=True)
        assert report["tiered_equiv_ok"]
        assert report["requests_finished"] == 5
        assert report["alloc_in_use_end"] == 0
        assert report["pages_allocated"] == report["pages_recycled"] > 0
        assert report["trace_totals_ok"]
        assert report["ttft_steps"]["n"] == 5
        # every request leaves a full lifecycle on the request track
        kinds_by_req = {}
        for p in eng.phases:
            kinds_by_req.setdefault(p.req, set()).add(p.kind)
        assert set(kinds_by_req) == set(range(5))
        for kinds in kinds_by_req.values():
            assert kinds == {"admit", "prefill_chunk", "decode", "evict"}
        # the run's own gates pass, and every emitted token is kept
        assert gate_failures(report, 5) == []
        assert sorted(r.req_id for r in eng.finished) == list(range(5))
        assert all(len(r.tokens) == r.decoded == r.gen for r in eng.finished)

    @pytest.mark.parametrize("key,value,needle", [
        ("tiered_equiv_ok", False, "mismatch"),
        ("requests_finished", 4, "4/5 requests"),
        ("alloc_in_use_end", 3, "page leak"),
        ("pages_recycled", 6, "conservation"),
        ("trace_totals_ok", False, "trace event totals"),
    ])
    def test_each_gate_names_its_failure(self, key, value, needle):
        report = {"tiered_equiv_ok": True, "requests_finished": 5,
                  "alloc_in_use_end": 0, "pages_allocated": 7,
                  "pages_recycled": 7}
        assert gate_failures(report, 5) == []
        fails = gate_failures({**report, key: value}, 5)
        assert len(fails) == 1 and needle in fails[0], fails

    def test_gang_ttft_never_beats_continuous(self):
        _, cont = _run_engine()
        _, gang = _run_engine(gang=True)
        assert gang["tiered_equiv_ok"] and cont["tiered_equiv_ok"]
        assert cont["mean_ttft_steps"] <= gang["mean_ttft_steps"]
        assert gang["steps"] >= cont["steps"]

    @pytest.mark.parametrize("mode", ["fused", "fused_async", "kernel"])
    def test_fused_attn_kernel_pin_over_dynamic_batches(self, mode):
        """The per-step §6.4 flat pin holds with the fused hot-slot kernel
        across the engine's dynamic batch compositions — including steps
        where some slots are idle (all -1 page rows, length 0) and the
        fused kernel must mask, not read, their slots."""
        eng, report = _run_engine(attn_kernel=mode)
        assert report["tiered_equiv_ok"]
        assert report["requests_finished"] == 5
        assert report["alloc_in_use_end"] == 0
        # requests (5) > slots (2): the run necessarily hit partial batches
        assert report["steps"] > 0


# --------------------------------------------------------------------------
# engine phase spans (profiler clock), syncs, request stamps, report keys
# --------------------------------------------------------------------------
#: the ``engine.*`` phase spans every engine step can open; ``lifecycle``
#: only with the §12 mirror on
PHASES = ("admit", "prefill", "decode", "kv_write", "invalidate",
          "sweep_inputs", "tiered_sweep", "attention", "pin", "evict")
#: the phases opened once per request and step, tagged with its ``req_id``
PER_REQUEST = ("prefill", "kv_write", "evict")
REPORT_KEYS = {"requests", "slots", "arrival", "admission", "steps",
               "wall_s", "tiered_equiv_ok", "requests_finished",
               "tokens_decoded", "ttft_steps", "mean_ttft_steps",
               "token_latency", "mean_decode_rows", "pages_allocated",
               "pages_recycled",
               "alloc_in_use_end", "alloc_occupancy_peak",
               "prefetch_hits_total", "deferred_total"}


def _engine_events(trace_dir):
    """``(name, start_ns, end_ns, stats)`` of every host annotation named
    ``engine*`` in the profiler trace written under ``trace_dir``."""
    from jax.profiler import ProfileData
    (path,) = Path(trace_dir).rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in pd.planes if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("engine")]


class TestEngineSpans:
    @pytest.mark.filterwarnings("ignore:builtin type event_stats")
    @pytest.mark.parametrize("migration", [None, "lifecycle"])
    def test_phase_spans_nest_under_step_on_the_profiler_clock(
            self, tmp_path, migration):
        from repro.paging.lifecycle import MigrationCfg
        mig = (None if migration is None else
               MigrationCfg(mig_per_stream=2, lead=1, cooldown=8))
        _run_engine(migration=mig)                 # compile outside the trace
        jax.profiler.start_trace(str(tmp_path))
        try:
            eng, report = _run_engine(migration=mig)
        finally:
            jax.profiler.stop_trace()
        assert report["tiered_equiv_ok"]
        events = _engine_events(tmp_path)
        names = {n for n, *_ in events}
        assert "engine_step" not in names          # the harness's step label
        steps = [(a, b) for n, a, b, _ in events if n == "engine.step"]
        assert len(steps) == report["steps"]
        want = PHASES + (("lifecycle",) if migration else ())
        for phase in want:
            spans = [e for e in events if e[0] == f"engine.{phase}"]
            assert spans, phase
            for _, a, b, stats in spans:
                assert any(lo <= a and b <= hi for lo, hi in steps), phase
                if phase in PER_REQUEST:
                    assert stats["req_id"] in range(5), (phase, stats)
        # the spans of one request share its id
        for phase in PER_REQUEST:
            ids = {e[3]["req_id"] for e in events if e[0] == f"engine.{phase}"}
            assert ids == set(range(5)), phase
        # one decode span per step with decoding slots, tagged with their
        # count: every decode of every request, once
        rows = [e[3]["rows"] for e in events if e[0] == "engine.decode"]
        assert all(1 <= n <= 2 for n in rows), rows
        assert sum(rows) == sum(r.gen - 1 for r in eng.finished)
        assert not any(n.startswith("engine.") and n[7:] not in
                       want + ("step",) for n in names)

    def test_decoding_step_blocks_once_from_tiered_sweep(self, monkeypatch):
        cfg = ServeConfig(requests=3, slots=2, prompt_len=8, gen=4,
                          page_size=4, prefill_chunk=4, arrival="constant",
                          think_time=1000.0, seed=3)
        eng = ServingEngine(cfg, SyntheticExecutor(n_kv_heads=2, head_dim=8))
        calls = []
        real = jax.block_until_ready
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: calls.append(x) or real(x))
        sweeps = eng.reg.histogram("tiered_sweep").samples
        t = decoding_steps = 0
        while len(eng.queue) or eng.sched.active():
            n_calls, n_sweeps = len(calls), len(sweeps)
            eng._step(t)
            swept = len(sweeps) - n_sweeps
            assert len(calls) - n_calls == swept, t
            decoding_steps += swept
            t += 1
        assert decoding_steps > 0
        assert "tiered_attention" not in eng.reg._hists
        assert "engine_step" not in eng.reg._hists

    def test_only_spans_a_report_reads_keep_histograms(self):
        """Every other phase is a trace annotation alone: a run's registry
        holds the sweep (``sweep_ms``), the batched decode call
        (``token_latency``) with its row count (``mean_decode_rows``) and
        the TTFT steps, one sample per call and no more."""
        eng, report = _run_engine()
        assert set(eng.reg._hists) == {"tiered_sweep", "decode",
                                       "decode_rows", "ttft_steps"}
        rows = eng.reg.histogram("decode_rows").samples
        assert len(eng.reg.histogram("decode").samples) == len(rows)
        assert len(rows) == len(eng.reg.histogram("tiered_sweep").samples)
        assert sum(rows) == sum(r.gen - 1 for r in eng.finished)
        assert report["mean_decode_rows"] == round(float(np.mean(rows)), 3)
        assert len(eng.reg.histogram("ttft_steps").samples) == 5

    def test_request_stamps_are_ordered(self):
        eng, report = _run_engine()
        assert len(eng.finished) == 5
        for r in eng.finished:
            assert 0 < r.t_enqueued_ns <= r.t_admitted_ns, r.req_id
        # and a pushed request is stamped when it is pushed
        q = AdmissionQueue()
        req = Request(req_id=9, prompt_len=2, gen=1)
        assert req.t_enqueued_ns == -1
        q.push(req)
        assert req.t_enqueued_ns > 0

    @pytest.mark.parametrize("trace", [False, True])
    def test_report_keys_unchanged(self, trace):
        eng, report = _run_engine(trace=trace)
        extra = {"trace_totals_ok", "trace_events"} if trace else set()
        assert set(report) == REPORT_KEYS | extra
        # one token latency per batched decode call: one per step that
        # decodes, the same steps that sweep
        assert report["token_latency"]["n"] == len(
            eng.reg.histogram("tiered_sweep").samples)
        assert 1 <= report["mean_decode_rows"] <= eng.cfg.slots


# --------------------------------------------------------------------------
# request-lifecycle export: JSONL round trip + Perfetto track
# --------------------------------------------------------------------------
class TestRequestPhaseExport:
    PHASES = [
        RequestPhase("admit", 0, 0, 2, slot=1),
        RequestPhase("prefill_chunk", 0, 2, 3, slot=1, tokens=4),
        RequestPhase("decode", 0, 3, 7, slot=1, tokens=4),
        RequestPhase("evict", 0, 7, 7, slot=1),
        RequestPhase("admit", 1, 1, 1, slot=0),
    ]

    def test_jsonl_round_trip_lossless(self, tmp_path):
        path = str(tmp_path / "req.jsonl")
        write_request_jsonl(path, self.PHASES)
        assert read_request_jsonl(path) == self.PHASES

    def test_unknown_phase_kind_rejected(self):
        with pytest.raises(ValueError):
            RequestPhase("warmup", 0, 0, 1)

    def test_chrome_trace_request_track_keyed_by_request_id(self):
        doc = to_chrome_trace([], request_phases=self.PHASES)
        json.dumps(doc)                   # serializable
        ev = doc["traceEvents"]
        procs = {e["args"]["name"] for e in ev
                 if e.get("ph") == "M" and e["name"] == "process_name"}
        assert "requests" in procs
        rows = [e for e in ev if e.get("pid") == 2 and e.get("ph") != "M"]
        # spans keyed by request id (tid == req), not slot
        assert {e["tid"] for e in rows} == {0, 1}
        span = next(e for e in rows if e["cat"] == "decode")
        assert span["ph"] == "X" and span["dur"] == 4 * 1000.0
        assert span["args"]["slot"] == 1
        instant = next(e for e in rows if e["cat"] == "evict")
        assert instant["ph"] == "i"       # zero-width phase -> instant
