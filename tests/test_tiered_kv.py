"""Tiered paged-KV serving (DESIGN.md §6).

Headline equivalence pin: decode attention served from the Leap-managed hot
pool (chunked demand sweep + remapped slot table) is bit-identical to the
flat-pool ``paged_decode_attention`` across hot-fraction {small, full},
ring {0, 8} and sequential + strided page layouts, on both the sync batched
and async issue/wait data paths. Plus the pool-level building blocks:
multi-page demand batches (``pool_wait_batch``) and write-coherence
invalidation (``pool_invalidate``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pool import (pool_init, pool_invalidate, pool_issue,
                             pool_stats, pool_wait_batch, ring_init)
from repro.paging.kv_cache import linear_page_table, paged_decode_attention
from repro.kernels.paged_attention import paged_attention_hot_slots
from repro.paging.tiered_kv import (TieredKV, tiered_attention,
                                    tiered_decode_step, tiered_init,
                                    tiered_invalidate, tiered_min_slots,
                                    tiered_slot_table_local, tiered_stats,
                                    tiered_sweep)

B, NPPS, PS, HKV, HQ, DH = 4, 8, 4, 2, 4, 8
N_PAGES = B * NPPS


def _cold(seed=0):
    k = jax.random.normal(jax.random.PRNGKey(seed), (N_PAGES, HKV, PS, DH),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (N_PAGES, HKV, PS, DH), jnp.float32)
    return {"k": k, "v": v}


def _flat(q, cold, pt, lengths):
    pool = {"k": cold["k"][None], "v": cold["v"][None]}
    return paged_decode_attention(q, pool, jnp.int32(0), pt, lengths)


def _geom(n_slots, ring=8, chunk=2, use_kernel=True):
    return TieredKV(N_PAGES, n_slots, PS, HKV, DH, chunk=chunk, pw_max=4,
                    ring_size=ring, use_kernel=use_kernel)


def _qlen(seed=2):
    q = jax.random.normal(jax.random.PRNGKey(seed), (B, 1, HQ, DH),
                          jnp.float32)
    lengths = jnp.asarray([29, 17, 32, 5], jnp.int32)
    return q, lengths


class TestEquivalencePin:
    """Tiered logits == flat-pool logits, bitwise, for every geometry."""

    @pytest.mark.parametrize("stride", [1, 3])
    @pytest.mark.parametrize("ring,async_dp", [(0, False), (0, True),
                                               (8, False), (8, True)])
    @pytest.mark.parametrize("hot", ["small", "full"])
    def test_bit_identical_to_flat_pool(self, stride, ring, async_dp, hot):
        cold = _cold()
        pt = linear_page_table(B, NPPS, stride)
        q, lengths = _qlen()
        small = tiered_min_slots(NPPS, _geom(1, ring=ring))
        geom = _geom(small if hot == "small" else N_PAGES, ring=ring)
        assert hot == "full" or geom.n_slots < N_PAGES  # genuinely tiered
        st = tiered_init(geom, B, jnp.float32)
        st, out, info, resident = tiered_decode_step(
            st, cold, q, pt, lengths, geom, async_datapath=async_dp)
        assert bool(resident)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(_flat(q, cold, pt, lengths)))
        # the sweep really fetched the rows through the hot tier
        assert int(info["fetched"].sum()) > 0

    def test_second_sweep_all_hits_and_prefetch_covers_first(self):
        cold = _cold()
        pt = linear_page_table(B, NPPS)
        geom = _geom(tiered_min_slots(NPPS, _geom(1)))
        st = tiered_init(geom, B, jnp.float32)
        st, info1 = tiered_sweep(st, cold, pt, geom, async_datapath=True)
        assert int(info1["pref_hit"].sum()) > 0      # Leap ran ahead
        st, info2 = tiered_sweep(st, cold, pt, geom, async_datapath=True)
        assert int(info2["hit"].sum()) == B * NPPS   # fully resident now
        assert int(info2["fetched"].sum()) == 0
        s = tiered_stats(st, 0)
        assert s["prefetch_issued"] == (s["prefetch_hits"] + s["pollution"]
                                        + s["inflight_at_end"]
                                        + s["resident_unused"])

    def test_ragged_chunking_and_jnp_fallback_match_kernel(self):
        cold = _cold()
        pt = linear_page_table(B, NPPS)
        q, lengths = _qlen()
        flat = _flat(q, cold, pt, lengths)
        for chunk, use_kernel in ((3, True), (3, False), (5, False)):
            geom = _geom(N_PAGES, chunk=chunk, use_kernel=use_kernel)
            st = tiered_init(geom, B, jnp.float32)
            st, out, _, resident = tiered_decode_step(
                st, cold, q, pt, lengths, geom, async_datapath=True)
            assert bool(resident)
            np.testing.assert_array_equal(np.asarray(out), np.asarray(flat))

    def test_undersized_hot_pool_rejected(self):
        geom = _geom(4)
        st = tiered_init(geom, B, jnp.float32)
        with pytest.raises(ValueError, match="tiered_min_slots"):
            tiered_sweep(st, _cold(), linear_page_table(B, NPPS), geom)


class TestFusedEquivalencePin:
    """Fused in-place hot-slot attention == unfused stacked path == flat
    pool, bitwise, on the same swept state (§6.4 extended to the fused
    consumer — all three run the identical per-page op sequence)."""

    @pytest.mark.parametrize("async_dp", [False, True])
    @pytest.mark.parametrize("hot", ["small", "full"])
    @pytest.mark.parametrize("mode", ["fused", "fused_async"])
    def test_fused_unfused_flat_bitwise(self, async_dp, hot, mode):
        cold = _cold()
        pt = linear_page_table(B, NPPS)
        q, lengths = _qlen()
        small = tiered_min_slots(NPPS, _geom(1))
        geom = _geom(small if hot == "small" else N_PAGES)
        st = tiered_init(geom, B, jnp.float32)
        st, _ = tiered_sweep(st, cold, pt, geom, async_datapath=async_dp)
        fused, ok_f = tiered_attention(q, st, pt, lengths, attn_kernel=mode)
        unfused, ok_u = tiered_attention(q, st, pt, lengths,
                                         attn_kernel="kernel")
        assert bool(ok_f) and bool(ok_u)
        pool = {"k": cold["k"][None], "v": cold["v"][None]}
        flat = paged_decode_attention(q, pool, jnp.int32(0), pt, lengths,
                                      use_kernel=True)
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(flat))

    def test_fused_decode_step_modes(self):
        """tiered_decode_step threads the attn_kernel mode through."""
        cold = _cold()
        pt = linear_page_table(B, NPPS)
        q, lengths = _qlen()
        geom = _geom(tiered_min_slots(NPPS, _geom(1)))
        outs = []
        for mode in ("kernel", "fused", "fused_async"):
            st = tiered_init(geom, B, jnp.float32)
            st, out, _, resident = tiered_decode_step(
                st, cold, q, pt, lengths, geom, async_datapath=True,
                attn_kernel=mode)
            assert bool(resident)
            outs.append(np.asarray(out))
        assert all((o == outs[0]).all() for o in outs[1:])

    @pytest.mark.parametrize("mode", ["fused", "fused_async"])
    def test_non_resident_pages_masked(self, mode):
        """A partially swept context (some pages never made hot) trips the
        all_resident guard, and the fused kernel masks the missing pages —
        matching the masked exact-softmax oracle, deterministically — rather
        than silently reading whatever lives in an unrelated slot."""
        cold = _cold()
        pt = linear_page_table(B, NPPS)
        q, lengths = _qlen()
        geom = _geom(tiered_min_slots(NPPS, _geom(1)))
        st = tiered_init(geom, B, jnp.float32)
        # sweep only the first half of every context row
        st, _ = tiered_sweep(st, cold, pt[:, :NPPS // 2], geom)
        table, resident = tiered_slot_table_local(st, pt)
        assert not bool(resident)
        assert (np.asarray(table) < 0).any()         # genuinely missing
        out, ok = tiered_attention(q, st, pt, lengths, attn_kernel=mode)
        assert not bool(ok)
        hot = st["hot"]
        ref = paged_attention_hot_slots(q, hot["k"], hot["v"], table,
                                        lengths, use_kernel=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        out2, _ = tiered_attention(q, st, pt, lengths, attn_kernel=mode)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


class TestWriteCoherence:
    def test_append_then_invalidate_stays_bit_identical(self):
        cold = _cold()
        pt = linear_page_table(B, NPPS)
        q, lengths = _qlen()
        geom = _geom(tiered_min_slots(NPPS, _geom(1)))
        st = tiered_init(geom, B, jnp.float32)
        st, _ = tiered_sweep(st, cold, pt, geom, async_datapath=True)
        # mutate page 3 of request 0's context (in range of length 29)
        new_page = jax.random.normal(jax.random.PRNGKey(9), (HKV, PS, DH))
        cold2 = {"k": cold["k"].at[3].set(new_page), "v": cold["v"]}
        # stale hot copy without invalidation -> shows the bug the API fixes
        st_stale, _ = tiered_sweep(st, cold2, pt, geom, async_datapath=True)
        out_stale, _ = tiered_attention(q, st_stale, pt, lengths)
        flat2 = _flat(q, cold2, pt, lengths)
        assert not np.array_equal(np.asarray(out_stale), np.asarray(flat2))
        # invalidate + resweep -> coherent again
        st = tiered_invalidate(st, jnp.full((B, 1), 3, jnp.int32))
        st, _ = tiered_sweep(st, cold2, pt, geom, async_datapath=True)
        out, resident = tiered_attention(q, st, pt, lengths)
        assert bool(resident)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(flat2))

    def test_pool_invalidate_keeps_decomposition(self):
        st, ring = pool_init(32, 8), ring_init(4)
        # one in-flight prefetch + one landed unconsumed prefetch
        st, ring = pool_issue(st, ring, jnp.asarray([5, 9], jnp.int32),
                              jnp.ones((2,), bool), jnp.int32(0),
                              jnp.int32(1))
        pool = jnp.arange(32 * 2, dtype=jnp.float32).reshape(32, 2)
        hot = jnp.zeros((8, 2))
        st, ring, hot, _, info = pool_wait_batch(
            st, ring, hot, pool, jnp.asarray([-1], jnp.int32),
            jnp.zeros((1,), bool), jnp.int32(1))
        # page 5, 9 both landed; invalidate 5 (resident) and 7 (absent)
        st2, ring2 = pool_invalidate(st, ring,
                                     jnp.asarray([5, 7], jnp.int32),
                                     jnp.ones((2,), bool))
        s = pool_stats(st2, ring2)
        assert s["pollution"] == 1 and s["prefetch_issued"] == 2
        assert s["prefetch_issued"] == (s["prefetch_hits"] + s["pollution"]
                                        + s["inflight_at_end"]
                                        + s["resident_unused"])
        # invalidating an in-flight entry also keeps the sum
        st3, ring3 = pool_issue(st2, ring2, jnp.asarray([11], jnp.int32),
                                jnp.ones((1,), bool), jnp.int32(1),
                                jnp.int32(1))
        st3, ring3 = pool_invalidate(st3, ring3,
                                     jnp.asarray([11], jnp.int32),
                                     jnp.ones((1,), bool))
        s3 = pool_stats(st3, ring3)
        assert s3["inflight_at_end"] == 0
        assert s3["prefetch_issued"] == (s3["prefetch_hits"] + s3["pollution"]
                                         + s3["inflight_at_end"]
                                         + s3["resident_unused"])


class TestPoolWaitBatch:
    def _setup(self, ring_cap=4):
        st, ring = pool_init(64, 8), ring_init(ring_cap)
        pool = jnp.arange(64 * 4, dtype=jnp.float32).reshape(64, 4)
        hot = jnp.zeros((8, 4))
        return st, ring, hot, pool

    def test_chunk_of_demands_served_in_one_call(self):
        st, ring, hot, pool = self._setup()
        pages = jnp.asarray([3, 4, 5], jnp.int32)
        st, ring, hot, slots, info = pool_wait_batch(
            st, ring, hot, pool, pages, jnp.ones((3,), bool), jnp.int32(0),
            lazy=True)
        assert bool(info["fetched"].all()) and not bool(info["hit"].any())
        for i, p in enumerate([3, 4, 5]):
            np.testing.assert_array_equal(np.asarray(hot[slots[i]]),
                                          np.asarray(pool[p]))
        # lazy retention: all three still mapped after the call
        assert int(jnp.sum(st["page_slot"] >= 0)) == 3

    def test_landings_and_partials_reported_per_demand(self):
        st, ring, hot, pool = self._setup()
        st, ring = pool_issue(st, ring, jnp.asarray([7, 8], jnp.int32),
                              jnp.ones((2,), bool), jnp.int32(0),
                              jnp.int32(1))
        # at now=1 both land; demand [7, 9]: 7 = prefetched hit, 9 = miss
        st, ring, hot, slots, info = pool_wait_batch(
            st, ring, hot, pool, jnp.asarray([7, 9], jnp.int32),
            jnp.ones((2,), bool), jnp.int32(1), lazy=True)
        assert int(info["landed"].sum()) == 2
        landed = set(np.asarray(info["landed_pages"])[
            np.asarray(info["landed"])].tolist())
        assert landed == {7, 8}
        assert bool(info["prefetched_hit"][0]) and bool(info["fetched"][1])
        # at now=0 the same demand would have been a partial hit instead
        st2, ring2, hot2, pool2 = self._setup()
        st2, ring2 = pool_issue(st2, ring2, jnp.asarray([7], jnp.int32),
                                jnp.ones((1,), bool), jnp.int32(0),
                                jnp.int32(1))
        st2, ring2, hot2, slots2, info2 = pool_wait_batch(
            st2, ring2, hot2, pool2, jnp.asarray([7], jnp.int32),
            jnp.ones((1,), bool), jnp.int32(0), lazy=True)
        assert bool(info2["partial_hit"][0])
        np.testing.assert_array_equal(np.asarray(hot2[slots2[0]]),
                                      np.asarray(pool2[7]))

    def test_invalid_entries_touch_nothing(self):
        st, ring, hot, pool = self._setup()
        st, ring, hot, slots, info = pool_wait_batch(
            st, ring, hot, pool, jnp.full((3,), -1, jnp.int32),
            jnp.zeros((3,), bool), jnp.int32(0), lazy=True)
        s = pool_stats(st, ring)
        assert s["faults"] == 0 and int(slots.min()) == -1


class TestBudgetedTieredSweep:
    def test_link_budget_defers_but_stays_correct(self):
        cold = _cold()
        pt = linear_page_table(B, NPPS)
        q, lengths = _qlen()
        geom = _geom(N_PAGES, chunk=1)
        st = tiered_init(geom, B, jnp.float32)
        st, out, info, resident = tiered_decode_step(
            st, cold, q, pt, lengths, geom, async_datapath=True,
            link_budget=1)
        assert bool(resident)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(_flat(q, cold, pt, lengths)))
        assert int(info["deferred"].sum()) > 0       # budget actually bound
        # a huge budget never defers
        st2 = tiered_init(geom, B, jnp.float32)
        st2, info2 = tiered_sweep(st2, cold, pt, geom, async_datapath=True,
                                  link_budget=10_000)
        assert int(info2["deferred"].sum()) == 0


class TestTraceDiff:
    """§8 wiring: the sweep's decoded event log pins its counters, and two
    identical sweeps decode to identical traces — any nondeterminism is
    localized by ``first_divergence`` to an exact (chunk step, stream)."""

    def test_sweep_trace_pins_counters_and_is_deterministic(self):
        from repro.obs import (assert_traces_equal, decode_sweep_events,
                               events_to_counts, summary_events)
        cold = _cold()
        pt = linear_page_table(B, NPPS)
        geom = _geom(tiered_min_slots(NPPS, _geom(1)))
        traces = []
        for _ in range(2):
            st = tiered_init(geom, B, jnp.float32)
            st, info = tiered_sweep(st, cold, pt, geom, async_datapath=True)
            ev = decode_sweep_events(info)
            stats = [tiered_stats(st, i) for i in range(B)]
            counts = events_to_counts(ev + summary_events(stats), B)
            for i, s in enumerate(stats):
                for k in ("hits", "misses", "partial_hits", "prefetch_hits",
                          "prefetch_issued", "deferred", "ring_drops",
                          "pollution"):
                    assert counts[i][k] == s[k], (i, k)
            traces.append(ev)
        assert_traces_equal(traces[0], traces[1], "run A", "run B",
                            context="tiered sweep determinism")
