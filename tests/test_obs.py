"""Observability (DESIGN.md §8): decoders, trace diff, registry, export.

The load-bearing pins:

* **Decode contract** — expanding the jitted info arrays into page-lifecycle
  events and folding them back (``events_to_counts``) reproduces
  ``pool_stats`` exactly, on both data planes; the §4.3 decomposition
  ``issued == prefetch_hits + pollution + inflight_at_end + resident_unused``
  holds at *event* granularity (hypothesis-driven over random schedules,
  ring sizes, arrival delays and link budgets).
* **Trace equivalence** — the decoded jitted trace and the lock-step twin's
  recorded trace have no divergent event (``first_divergence is None``),
  for both the single-link and the sharded fabric.
* **Divergence localization** — plant a single corrupted event in an
  otherwise-identical trace and the differ names its exact
  ``(step, stream, kind)`` (and page, when page-level).
"""

import dataclasses
import json
import math

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as hst
except ImportError:                       # deterministic tests still run
    class _StrategyStub:
        def __getattr__(self, name):
            return lambda *a, **k: None

    def settings(*a, **k):
        return lambda fn: fn

    def given(*a, **k):
        return lambda fn: pytest.mark.skip(
            reason="hypothesis not installed")(fn)

    hst = _StrategyStub()

from repro.fabric.linkstep import run_linkstep
from repro.fabric.shardstep import run_shardstep
from repro.obs import (Event, Registry, TraceRecorder, assert_traces_equal,
                       decode_stream_events, decode_sweep_events,
                       events_to_counts, first_divergence, percentile_ladder,
                       read_jsonl, summary_events, to_chrome_trace,
                       write_chrome_trace, write_jsonl)
from repro.paging.prefetch_serving import (PrefetchedStream,
                                           multi_stream_consume,
                                           stream_stats_at)

N_PAGES = 64
POOL = jnp.arange(N_PAGES * 4, dtype=jnp.float32).reshape(N_PAGES, 4)
GEOM = PrefetchedStream(n_pages=N_PAGES, n_slots=N_PAGES, page_elems=4,
                        ring_size=8)
INF = 1 << 20

#: counters both ``pool_stats`` and ``events_to_counts`` report.
PINNED = ("hits", "misses", "partial_hits", "prefetch_hits",
          "prefetch_issued", "deferred", "ring_drops", "pollution")


def _scheds(T=40, S=3, seed=7):
    rng = np.random.default_rng(seed)
    rows = [np.arange(T) % N_PAGES,
            (np.arange(T) * 3 + 11) % N_PAGES,
            rng.integers(0, N_PAGES, T)]
    return jnp.asarray(np.stack(rows[:S]), jnp.int32)


def _run(scheds, budget, geom=GEOM):
    return multi_stream_consume(POOL, scheds, geom, async_datapath=True,
                                link_budget=INF if budget is None else budget)


def _decode(scheds, st, info, geom=GEOM, **kw):
    stats = [stream_stats_at(st, i) for i in range(scheds.shape[0])]
    return decode_stream_events(scheds, info, n_pages=geom.n_pages,
                                final_stats=stats, **kw), stats


class TestEventSchema:
    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError, match="unknown event kind"):
            Event("teleport", 0, 0)

    def test_events_to_counts_by_hand(self):
        ev = [Event("hit", 0, 0), Event("hit", 1, 0, pref=True),
              Event("partial", 2, 0, pref=True), Event("miss", 3, 0),
              Event("issue", 0, 0, count=4), Event("land", 1, 0, count=2),
              Event("drop", -1, 0, count=3), Event("evict", -1, 0)]
        c = events_to_counts(ev, 1)[0]
        assert c["hits"] == 3                     # partial counts as a hit
        assert c["prefetch_hits"] == 2 and c["partial_hits"] == 1
        assert c["misses"] == 1 and c["prefetch_issued"] == 4
        assert c["landed"] == 2 and c["ring_drops"] == 3
        assert c["pollution"] == 1


class TestRegistry:
    def test_counters_and_histograms(self):
        reg = Registry()
        reg.counter("faults").add(3)
        reg.counter("faults").add(2)
        reg.histogram("lat").extend([1.0, 2.0, 3.0])
        s = reg.summary()
        assert s["counters"]["faults"] == 5
        assert s["histograms"]["lat"]["n"] == 3
        assert s["histograms"]["lat"]["max"] == 3.0

    def test_span_blocks_on_device_result(self):
        reg = Registry()
        with reg.span("work") as sp:
            sp.sync = jnp.arange(8).sum()        # forces block_until_ready
        assert reg.histogram("work").samples[0] > 0.0

    def test_empty_ladder_is_nan(self):
        lad = percentile_ladder([])
        assert lad["n"] == 0 and math.isnan(lad["p50"])


class TestDecodePinsCounters:
    """events_to_counts(decode(info)) == pool_stats, both data planes."""

    @pytest.mark.parametrize("budget", [None, 1, 3])
    def test_stream_decode_matches_pool_stats(self, budget):
        scheds = _scheds()
        st, _, info = _run(scheds, budget)
        events, stats = _decode(scheds, st, info)
        counts = events_to_counts(events, scheds.shape[0])
        for i, ps in enumerate(stats):
            assert {k: counts[i][k] for k in PINNED} == \
                {k: ps[k] for k in PINNED}, f"stream {i}, budget {budget}"

    @pytest.mark.parametrize("budget", [2, INF])
    def test_decomposition_at_event_granularity(self, budget):
        """§4.3 identity walked over *events*, not end counters."""
        scheds = _scheds(T=50)
        st, _, info = _run(scheds, budget)
        events, stats = _decode(scheds, st, info)
        for i, ps in enumerate(stats):
            mine = [e for e in events if e.stream == i]
            issued = sum(e.count for e in mine if e.kind == "issue")
            pref_hits = sum(e.count for e in mine
                            if e.kind in ("hit", "partial") and e.pref)
            evicted = sum(e.count for e in mine if e.kind == "evict")
            assert issued == (pref_hits + evicted + ps["inflight_at_end"]
                              + ps["resident_unused"]), f"stream {i}"
            landed = sum(e.count for e in mine if e.kind == "land")
            partials = sum(e.count for e in mine if e.kind == "partial")
            assert issued == landed + partials + ps["inflight_at_end"]

    def test_sweep_decode_matches_tiered_stats(self):
        from repro.paging.kv_cache import linear_page_table
        from repro.paging.tiered_kv import (TieredKV, tiered_init,
                                            tiered_min_slots, tiered_stats,
                                            tiered_sweep)
        B, npps, ps = 4, 8, 4
        geom = TieredKV(B * npps, 1, ps, 2, 8, chunk=2, pw_max=4,
                        ring_size=8, use_kernel=False)
        geom = dataclasses.replace(
            geom, n_slots=tiered_min_slots(npps, geom))
        k = jnp.arange(B * npps * ps * 2 * 8,
                       dtype=jnp.float32).reshape(B * npps, 2, ps, 8)
        cold = {"k": k, "v": k + 1.0}
        pt = linear_page_table(B, npps)
        st = tiered_init(geom, B, jnp.float32)
        events = []
        n_chunks = -(-npps // geom.chunk)
        for sweep in range(2):
            st, info = tiered_sweep(st, cold, pt, geom, async_datapath=True)
            events.extend(decode_sweep_events(
                info, step_offset=sweep * n_chunks))
        stats = [tiered_stats(st, i) for i in range(B)]
        events.extend(summary_events(stats))
        counts = events_to_counts(events, B)
        for i, ps_ in enumerate(stats):
            assert {k: counts[i][k] for k in PINNED} == \
                {k: ps_[k] for k in PINNED}, f"stream {i}"


class TestTraceEquivalence:
    """Decoded jitted trace == lock-step twin's recorded trace."""

    @pytest.mark.parametrize("budget", [1, 3])
    def test_linkstep_twin_has_no_divergence(self, budget):
        scheds = _scheds(T=60)
        st, _, info = _run(scheds, budget)
        jit_events, _ = _decode(scheds, st, info)
        rec = TraceRecorder()
        run_linkstep(np.asarray(scheds), N_PAGES, budget,
                     ring_size=GEOM.ring_size,
                     arrival_delay=GEOM.arrival_delay, pw_max=GEOM.pw_max,
                     h_size=GEOM.h_size, n_split=GEOM.n_split, recorder=rec)
        assert_traces_equal(jit_events, rec.events,
                            context=f"budget={budget}")

    def test_shardstep_twin_has_no_divergence(self):
        from repro.paging.sharded_pool import (ShardedPoolCfg,
                                               sharded_multi_stream_consume)
        scheds = _scheds(T=50)
        fab = ShardedPoolCfg(n_shards=2, placement="interleave",
                             link_budget=2, near_delay=1, far_delay=2)
        st, _, info = sharded_multi_stream_consume(POOL, scheds, GEOM, fab)
        jit_events, _ = _decode(scheds, st, info, n_shards=2,
                                placement="interleave")
        rec = TraceRecorder()
        run_shardstep(np.asarray(scheds), N_PAGES, 2, "interleave", 2,
                      ring_size=GEOM.ring_size, near_delay=1, far_delay=2,
                      pw_max=GEOM.pw_max, h_size=GEOM.h_size,
                      n_split=GEOM.n_split, recorder=rec)
        assert_traces_equal(jit_events, rec.events, context="sharded")


def _twin_trace(budget=2):
    scheds = _scheds(T=60)
    rec = TraceRecorder()
    run_linkstep(np.asarray(scheds), N_PAGES, budget,
                 ring_size=GEOM.ring_size, arrival_delay=GEOM.arrival_delay,
                 pw_max=GEOM.pw_max, h_size=GEOM.h_size,
                 n_split=GEOM.n_split, recorder=rec)
    return rec.events


class TestPlantedDivergence:
    """A single corrupted event must be named by exact coordinates."""

    def test_flipped_page_is_localized(self):
        a = _twin_trace(budget=6)        # ample budget: full hits + lands
        idx, victim = next((i, e) for i, e in enumerate(a)
                           if e.kind == "hit" and e.step > 5)
        b = list(a)
        b[idx] = dataclasses.replace(victim, page=(victim.page + 1) % N_PAGES)
        d = first_divergence(a, b)
        assert d is not None
        assert (d.step, d.stream, d.kind) == (victim.step, victim.stream,
                                              "hit")
        assert d.pages is not None       # page-level: names the exact page
        only_a, only_b = d.pages
        assert any(p == victim.page for p, _ in only_a)
        with pytest.raises(AssertionError, match=f"step {victim.step}"):
            assert_traces_equal(a, b)

    def test_dropped_land_event_is_localized(self):
        a = _twin_trace(budget=6)
        idx, victim = next((i, e) for i, e in enumerate(a)
                           if e.kind == "land" and e.step > 5)
        b = a[:idx] + a[idx + 1:]
        d = first_divergence(a, b)
        assert d is not None
        assert (d.step, d.stream, d.kind) == (victim.step, victim.stream,
                                              "land")
        assert d.count_a == d.count_b + 1

    def test_identical_traces_have_no_divergence(self):
        a = _twin_trace()
        assert first_divergence(a, list(a)) is None


class TestFabricEngineRecorder:
    def test_event_engine_trace_matches_tenant_report(self):
        """The continuous-time engine's recorded events reproduce the
        per-tenant report counters (hits incl. partials; §8)."""
        from repro.fabric.sim import FabricScenario, run_fabric
        from repro.fabric.tenants import TenantSpec
        specs = [TenantSpec(f"t{i}", (np.arange(200) * (i + 1)) % 64,
                            cache_capacity=32) for i in range(2)]
        rec = TraceRecorder()
        report = run_fabric(FabricScenario(specs, seed=1), recorder=rec)
        counts = events_to_counts(rec.events, 2)
        for i, ten in enumerate(report.tenants):
            assert counts[i]["hits"] == ten.cache_hits, f"tenant {i}"
            assert counts[i]["misses"] == ten.misses, f"tenant {i}"
            assert counts[i]["hits"] + counts[i]["misses"] == ten.faults
        assert any(e.kind == "issue" for e in rec.events)
        assert any(e.kind == "land" for e in rec.events)


class TestExport:
    def _events(self):
        scheds = _scheds(T=20)
        st, _, info = _run(scheds, 2)
        events, _ = _decode(scheds, st, info)
        return events

    def test_chrome_trace_structure(self):
        events = self._events()
        doc = to_chrome_trace(events, counters={"link": [1, 2, 3]})
        assert "traceEvents" in doc
        phases = {e["ph"] for e in doc["traceEvents"]}
        assert phases <= {"M", "X", "C", "i"}
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert xs and all("ts" in e and "dur" in e for e in xs)
        assert any(e["ph"] == "C" and e["name"] == "link"
                   for e in doc["traceEvents"])

    def test_chrome_trace_file_is_json(self, tmp_path):
        p = str(tmp_path / "trace.json")
        write_chrome_trace(p, self._events())
        with open(p) as f:
            doc = json.load(f)
        assert doc["traceEvents"]

    def test_jsonl_roundtrip(self, tmp_path):
        events = self._events()
        p = str(tmp_path / "trace.jsonl")
        write_jsonl(p, events)
        assert read_jsonl(p) == events


# -- hypothesis: the decode contract over random geometry --------------------
@settings(max_examples=12, deadline=None)
@given(seed=hst.integers(0, 2**31 - 1),
       ring=hst.sampled_from([2, 4, 8]),
       delay=hst.sampled_from([1, 2, 3]),
       budget=hst.sampled_from([0, 1, 2, 4, INF]))
def test_event_log_pins_counters_property(seed, ring, delay, budget):
    """Random schedules/geometry: decoded events reproduce pool_stats and
    the §4.3 decomposition holds at event granularity."""
    geom = dataclasses.replace(GEOM, ring_size=ring, arrival_delay=delay)
    rng = np.random.default_rng(seed)
    scheds = jnp.asarray(rng.integers(0, N_PAGES, (2, 24)), jnp.int32)
    st, _, info = multi_stream_consume(POOL, scheds, geom,
                                       async_datapath=True,
                                       link_budget=budget)
    events, stats = _decode(scheds, st, info, geom=geom)
    counts = events_to_counts(events, 2)
    for i, ps in enumerate(stats):
        assert {k: counts[i][k] for k in PINNED} == \
            {k: ps[k] for k in PINNED}, f"stream {i}"
        mine = [e for e in events if e.stream == i]
        issued = sum(e.count for e in mine if e.kind == "issue")
        pref_hits = sum(e.count for e in mine
                        if e.kind in ("hit", "partial") and e.pref)
        evicted = sum(e.count for e in mine if e.kind == "evict")
        assert issued == (pref_hits + evicted + ps["inflight_at_end"]
                          + ps["resident_unused"])
