"""Mesh-sharded cold pool (DESIGN.md §7).

Pins the four contracts of the sharded fabric:

* **Placement metadata** — ``page_home``/``page_local`` are inverse to the
  home-major permutation ``place_perm``, and the Python mirror
  (``fabric.shardstep.home_of``) agrees with the jitted helpers.
* **shards=1 reduction** — the sharded consume with one shard is
  bit-equivalent to the flat ``multi_stream_consume`` paths (the finite-
  budget reduction is structural: §5 now *delegates* here, so
  ``tests/test_link_budget.py`` gates it too; the unbudgeted case is
  pinned against the vmap path directly), and ``link_grants_sharded``
  with one shard equals ``link_grants``.
* **Fabric mirror** — for shards > 1, per-stream hit / partial / deferred
  / drop counts match the lock-step sharded reference
  (``repro.fabric.run_shardstep``) exactly across placements × budgets ×
  sequential/strided/random traffic, and served bytes stay correct.
* **shard_map data plane** — run in a subprocess with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4``: the collective
  ring-permute gather produces bit-identical hot pools, sums and counters
  to the flat data plane, for both the stream consume and the tiered
  sweep (whose logits stay bit-identical to the flat-pool attention).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pool import (link_grants, link_grants_sharded, page_home,
                             page_local, ring_init)
from repro.fabric.shardstep import home_of, run_shardstep
from repro.obs import (TraceRecorder, assert_traces_equal,
                       decode_stream_events)
from repro.paging.prefetch_serving import (PrefetchedStream,
                                           multi_stream_consume,
                                           stream_consume, stream_stats_at)
from repro.paging.sharded_pool import (ShardedPoolCfg, place_perm,
                                       sharded_multi_stream_consume)

N_PAGES = 128
POOL = jnp.arange(N_PAGES * 4, dtype=jnp.float32).reshape(N_PAGES, 4)
GEOM = PrefetchedStream(n_pages=N_PAGES, n_slots=N_PAGES, page_elems=4,
                        ring_size=8)


def _scheds(T: int = 60) -> jnp.ndarray:
    rng = np.random.default_rng(3)
    return jnp.asarray(np.stack([
        np.arange(T) % N_PAGES,
        (np.arange(T) * 3 + 7) % N_PAGES,
        (np.arange(T) * 2 + 50) % N_PAGES,
        rng.integers(0, N_PAGES, T),
    ]), jnp.int32)


class TestPlacement:
    @pytest.mark.parametrize("placement", ["block", "interleave"])
    @pytest.mark.parametrize("n_shards", [1, 2, 4])
    def test_home_local_invert_place_perm(self, placement, n_shards):
        fab = ShardedPoolCfg(n_shards=n_shards, placement=placement)
        perm = place_perm(N_PAGES, fab)
        assert sorted(perm.tolist()) == list(range(N_PAGES))  # a permutation
        pages = jnp.arange(N_PAGES, dtype=jnp.int32)
        home = np.asarray(page_home(pages, N_PAGES, n_shards, placement))
        local = np.asarray(page_local(pages, N_PAGES, n_shards, placement))
        pps = N_PAGES // n_shards
        assert (local < pps).all()
        # placed[home * pps + local] holds exactly page p
        np.testing.assert_array_equal(perm[home * pps + local],
                                      np.arange(N_PAGES))
        # python mirror agrees
        assert [home_of(p, N_PAGES, n_shards, placement)
                for p in range(N_PAGES)] == home.tolist()

    def test_bad_placement_rejected(self):
        with pytest.raises(ValueError, match="placement"):
            ShardedPoolCfg(n_shards=2, placement="striped")
        with pytest.raises(ValueError, match="placement"):
            page_home(jnp.arange(4), 4, 2, "striped")

    def test_indivisible_pool_rejected(self):
        fab = ShardedPoolCfg(n_shards=3)
        with pytest.raises(ValueError, match="divisible"):
            place_perm(N_PAGES, fab)
        with pytest.raises(ValueError, match="divisible"):
            sharded_multi_stream_consume(POOL, _scheds(8), GEOM, fab)


class TestShardsOneReduction:
    def test_one_shard_unbudgeted_matches_vmap_path(self):
        """G=1, budget=None: bit-equivalent to vmap(stream_consume) (modulo
        the ring ``seq`` stamps only the arbiter-capable path assigns)."""
        scheds = _scheds()
        fab = ShardedPoolCfg(n_shards=1, link_budget=None,
                             near_delay=1, far_delay=1)
        st_s, sums_s, info_s = sharded_multi_stream_consume(
            POOL, scheds, GEOM, fab)
        st_v, sums_v, info_v = jax.vmap(
            lambda s: stream_consume(POOL, s, GEOM, async_datapath=True)
        )(scheds)
        np.testing.assert_array_equal(np.asarray(sums_s), np.asarray(sums_v))
        for k in info_v:
            np.testing.assert_array_equal(np.asarray(info_s[k]),
                                          np.asarray(info_v[k]), err_msg=k)
        for k, v in st_v["pool_meta"].items():
            np.testing.assert_array_equal(np.asarray(st_s["pool_meta"][k]),
                                          np.asarray(v), err_msg=k)
        for k, v in st_v["ring"].items():
            if k == "seq":
                continue
            np.testing.assert_array_equal(np.asarray(st_s["ring"][k]),
                                          np.asarray(v), err_msg=k)
        np.testing.assert_array_equal(np.asarray(st_s["hot"]),
                                      np.asarray(st_v["hot"]))

    def test_one_shard_budgeted_is_the_link_budget_path(self):
        """The §5 budgeted path *is* the one-shard fabric (delegation)."""
        scheds = _scheds()
        fab = ShardedPoolCfg(n_shards=1, link_budget=3,
                             near_delay=1, far_delay=1)
        st_s, sums_s, info_s = sharded_multi_stream_consume(
            POOL, scheds, GEOM, fab)
        st_b, sums_b, info_b = multi_stream_consume(
            POOL, scheds, GEOM, async_datapath=True, link_budget=3)
        np.testing.assert_array_equal(np.asarray(sums_s), np.asarray(sums_b))
        for k in info_b:
            np.testing.assert_array_equal(np.asarray(info_s[k]),
                                          np.asarray(info_b[k]), err_msg=k)

    def test_link_grants_sharded_one_shard_equals_link_grants(self):
        ring = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (3,) + x.shape).copy(),
            ring_init(6))
        rng = np.random.default_rng(0)
        ring = dict(ring)
        ring["page"] = jnp.asarray(rng.integers(-1, 40, (3, 6)), jnp.int32)
        ring["deadline"] = jnp.asarray(rng.integers(0, 5, (3, 6)), jnp.int32)
        ring["seq"] = jnp.asarray(rng.permutation(18).reshape(3, 6),
                                  jnp.int32)
        now = jnp.full((3,), 3, jnp.int32)
        for cap in (0, 1, 2, 5, 100):
            a = link_grants(ring, now, jnp.int32(cap))
            b = link_grants_sharded(ring, now,
                                    jnp.asarray([cap], jnp.int32),
                                    jnp.zeros((3, 6), jnp.int32))
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"cap={cap}")


class TestShardstepCrossValidation:
    """Jitted sharded counts == lock-step sharded fabric, per stream."""

    @pytest.mark.parametrize("placement", ["block", "interleave"])
    @pytest.mark.parametrize("n_shards", [2, 4])
    @pytest.mark.parametrize("budget", [None, 1, 3])
    def test_counts_match_shardstep(self, placement, n_shards, budget):
        scheds = _scheds()
        fab = ShardedPoolCfg(n_shards=n_shards, placement=placement,
                             link_budget=budget, near_delay=1, far_delay=2)
        st, sums, info = sharded_multi_stream_consume(POOL, scheds, GEOM, fab)
        # served bytes stay correct whatever the topology
        np.testing.assert_allclose(np.asarray(sums),
                                   np.asarray(POOL[scheds].sum(-1)))
        rec = TraceRecorder()
        rep = run_shardstep(np.asarray(scheds), N_PAGES, n_shards, placement,
                            budget, ring_size=GEOM.ring_size,
                            near_delay=1, far_delay=2, pw_max=GEOM.pw_max,
                            h_size=GEOM.h_size, n_split=GEOM.n_split,
                            recorder=rec)
        for i in range(scheds.shape[0]):
            j = stream_stats_at(st, i)
            r = rep.stream_summary(i)
            if {k: j[k] for k in r} != r:
                # §8: name the first divergent event before failing on totals
                assert_traces_equal(
                    decode_stream_events(scheds, info, n_pages=N_PAGES,
                                         n_shards=n_shards,
                                         placement=placement),
                    rec.events,
                    context=f"{placement}, G={n_shards}, budget {budget}")
            assert {k: j[k] for k in r} == r, \
                f"stream {i}, {placement}, G={n_shards}, budget {budget}"

    def test_per_shard_demand_totals_account_every_fetch(self):
        scheds = _scheds()
        fab = ShardedPoolCfg(n_shards=4, placement="interleave",
                             link_budget=2)
        _, _, info = sharded_multi_stream_consume(POOL, scheds, GEOM, fab)
        shard = np.asarray(info["shard_demand_fetches"])    # [T, G]
        assert shard.shape[1] == 4
        np.testing.assert_array_equal(shard.sum(1),
                                      np.asarray(info["link_demand_fetches"]))
        np.testing.assert_array_equal(
            shard.sum(0).sum(), np.asarray(info["fetched"]).sum())

    def test_far_pages_hide_less_latency(self):
        """Longer far_delay -> more prefetches still in flight at first use
        (partial hits), never more full hits; deferred stays 0 unbudgeted."""
        scheds = _scheds()
        partials = []
        for far in (1, 3):
            fab = ShardedPoolCfg(n_shards=2, placement="interleave",
                                 link_budget=None, near_delay=1,
                                 far_delay=far)
            st, _, info = sharded_multi_stream_consume(POOL, scheds, GEOM,
                                                       fab)
            assert int(np.asarray(info["deferred"]).sum()) == 0
            partials.append(int(np.asarray(info["partial_hit"]).sum()))
        assert partials[1] > partials[0]


class TestShardMapDataPlane:
    """Real multi-device run: forced 4-CPU-device subprocess, collective
    ring-permute gather pinned bit-equal to the flat data plane."""

    SCRIPT = textwrap.dedent("""
        import jax, numpy as np, jax.numpy as jnp
        assert jax.device_count() == 4, jax.device_count()
        from repro.paging.prefetch_serving import PrefetchedStream
        from repro.paging.sharded_pool import (ShardedPoolCfg,
                                               sharded_multi_stream_consume)
        from repro.paging.kv_cache import (linear_page_table,
                                           paged_decode_attention)
        from repro.paging.tiered_kv import (TieredKV, tiered_attention,
                                            tiered_init, tiered_min_slots,
                                            tiered_sweep)

        N = 64
        pool = jnp.arange(N * 4, dtype=jnp.float32).reshape(N, 4)
        geom = PrefetchedStream(n_pages=N, n_slots=N, page_elems=4,
                                ring_size=8)
        T = 30
        scheds = jnp.asarray(np.stack([np.arange(T) % N,
                                       (np.arange(T) * 3 + 7) % N]),
                             jnp.int32)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("fabric",))
        for placement in ("block", "interleave"):
            fab = ShardedPoolCfg(n_shards=4, placement=placement,
                                 link_budget=2)
            sf, sums_f, info_f = sharded_multi_stream_consume(
                pool, scheds, geom, fab)
            sm, sums_m, info_m = sharded_multi_stream_consume(
                pool, scheds, geom, fab, mesh=mesh)
            np.testing.assert_array_equal(np.asarray(sums_f),
                                          np.asarray(sums_m))
            for k in info_f:
                np.testing.assert_array_equal(np.asarray(info_f[k]),
                                              np.asarray(info_m[k]),
                                              err_msg=k)
            np.testing.assert_array_equal(np.asarray(sf["hot"]),
                                          np.asarray(sm["hot"]))

        # tiered sweep: sharded cold KV, logits bit-identical to flat pool
        B, NPPS, PS, HKV, HQ, DH = 2, 8, 4, 2, 4, 8
        NP = B * NPPS
        k = jax.random.normal(jax.random.PRNGKey(0), (NP, HKV, PS, DH))
        v = jax.random.normal(jax.random.PRNGKey(1), (NP, HKV, PS, DH))
        cold = {"k": k, "v": v}
        q = jax.random.normal(jax.random.PRNGKey(2), (B, 1, HQ, DH))
        lengths = jnp.asarray([29, 17], jnp.int32)
        pt = linear_page_table(B, NPPS, 3)
        tg = TieredKV(NP, tiered_min_slots(
            NPPS, TieredKV(NP, 1, PS, HKV, DH, chunk=2, pw_max=4)),
            PS, HKV, DH, chunk=2, pw_max=4, ring_size=8)
        fab = ShardedPoolCfg(n_shards=4, placement="interleave",
                             link_budget=1)
        st = tiered_init(tg, B, jnp.float32)
        st, info = tiered_sweep(st, cold, pt, tg, async_datapath=True,
                                fabric=fab, mesh=mesh)
        out, ok = tiered_attention(q, st, pt, lengths)
        assert bool(ok)
        flat = paged_decode_attention(q, {"k": k[None], "v": v[None]},
                                      jnp.int32(0), pt, lengths)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(flat))

        # sharded engine: the sweep leaves the hot tier replicated over
        # the mesh, and attention (a Mosaic kernel on a TPU, which XLA
        # cannot partition) reads it from one device
        from repro.serving import ServeConfig, ServingEngine, SyntheticExecutor
        from repro.serving.engine import gate_failures
        eng = ServingEngine(ServeConfig(requests=3, slots=2, prompt_len=8,
                                        gen=3, page_size=4, shards=4,
                                        attn_kernel="fused_async",
                                        async_datapath=True),
                            SyntheticExecutor(n_kv_heads=2, head_dim=8))
        assert gate_failures(eng.run(), 3) == []
        devs = lambda t: {len(x.sharding.device_set)
                          for x in jax.tree.leaves(t)}
        assert devs(eng.tstate) == {4}, devs(eng.tstate)
        assert devs(eng.attention_state()) == {1}

        # serving 'pages' rule: preference order — one axis, never a
        # fabric x data product (that would split a shard's home slice)
        from jax.sharding import PartitionSpec
        from repro.distributed.sharding import RULES_SERVE, named_sharding_for
        m2 = make_mesh((2, 2), ("fabric", "data"))
        sh = named_sharding_for(("pages", None), (64, 4), m2, RULES_SERVE)
        assert sh.spec == PartitionSpec("fabric", None), sh.spec
        m3 = make_mesh((2, 2), ("data", "model"))
        sh = named_sharding_for(("pages", None), (64, 4), m3, RULES_SERVE)
        assert sh.spec == PartitionSpec("data", None), sh.spec
        print("SHARDED-OK")
    """)

    def test_shard_map_bit_equal_in_forced_multidevice_subprocess(self):
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=4")
        env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), os.pardir,
                                          "src")
                             + os.pathsep + env.get("PYTHONPATH", ""))
        r = subprocess.run([sys.executable, "-c", self.SCRIPT], env=env,
                           capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, r.stdout + "\n" + r.stderr
        assert "SHARDED-OK" in r.stdout


class TestTieredFabricComposition:
    """Tiered sweep under a sharded fabric (flat data plane, metadata model):
    the equivalence pin survives every placement/budget and tight per-NIC
    budgets actually defer."""

    def test_tiered_pin_and_deferral_across_fabrics(self):
        from repro.paging.kv_cache import (linear_page_table,
                                           paged_decode_attention)
        from repro.paging.tiered_kv import (TieredKV, tiered_attention,
                                            tiered_init, tiered_min_slots,
                                            tiered_sweep)
        B, NPPS, PS, HKV, HQ, DH = 4, 8, 4, 2, 4, 8
        NP = B * NPPS
        k = jax.random.normal(jax.random.PRNGKey(0), (NP, HKV, PS, DH))
        v = jax.random.normal(jax.random.PRNGKey(1), (NP, HKV, PS, DH))
        cold = {"k": k, "v": v}
        q = jax.random.normal(jax.random.PRNGKey(2), (B, 1, HQ, DH))
        lengths = jnp.asarray([29, 17, 32, 5], jnp.int32)
        pt = linear_page_table(B, NPPS, 3)
        flat = paged_decode_attention(q, {"k": k[None], "v": v[None]},
                                      jnp.int32(0), pt, lengths)
        geom = TieredKV(NP, tiered_min_slots(
            NPPS, TieredKV(NP, 1, PS, HKV, DH, chunk=1, pw_max=4)),
            PS, HKV, DH, chunk=1, pw_max=4, ring_size=8)
        saw_deferral = False
        for placement in ("block", "interleave"):
            for budget in (None, 1):
                fab = ShardedPoolCfg(n_shards=4, placement=placement,
                                     link_budget=budget, near_delay=1,
                                     far_delay=2)
                st = tiered_init(geom, B, jnp.float32)
                st, info = tiered_sweep(st, cold, pt, geom,
                                        async_datapath=True, fabric=fab)
                out, ok = tiered_attention(q, st, pt, lengths)
                assert bool(ok), (placement, budget)
                np.testing.assert_array_equal(np.asarray(out),
                                              np.asarray(flat))
                if budget == 1:
                    saw_deferral |= int(
                        np.asarray(info["deferred"]).sum()) > 0
        assert saw_deferral   # a 1-page/NIC budget must actually bind
