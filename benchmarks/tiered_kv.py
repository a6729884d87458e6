"""Tiered paged-KV decode: sweep overlap + the fused attention consumer.

Two suites over the DESIGN.md §6 serving path:

**Sweep overlap** (rows with a ``path`` column): with decode attention fed
from the Leap-managed hot pool, the *sync* tiered sweep fetches every
prefetch candidate inside the chunk step that issued it (blocking the
sweep), while the *async* issue/wait sweep lands candidates during the
next chunk step — same controller, same demand schedule, so the hit rates
match and the difference is what sits on the sweep's critical path:

* sync:  demand misses AND every issued candidate (blocking batch);
* async: demand misses, plus the residual transfer of partial hits.

The consume-latency column prices those critical-path pages with the
``rdma_lean`` model (as ``datapath_overlap``), crossed over hot-fraction
{small, full} x {sync, async}, with the tiered/flat bit-equivalence pin
checked on every configuration.

**Fused consumer** (rows with an ``attn`` column): prices the attention
consumer itself — the unfused stacked path re-materializes the whole
``[S, n_slots, ...] -> [S*n_slots, ...]`` hot pool (k and v, read+write)
every decode step before the flat kernel reads the context, while the
fused ``paged_attention_hot_slots`` kernel reads the hot slots in place
through the slot table, moving only the context pages. Per point
(hot-fraction x S x npps) the suite reports the analytic per-step
bytes-moved for each path, the time those bytes cost at the HBM roofline
(``benchmarks.roofline.HBM_BW`` — wall-clock on the CPU interpret path is
reported but not asserted), the fusion-blind jaxpr bytes
(``flop_count.count_fn``), and a jaxpr structure check that the
``[S*n_slots, ...]`` stacked reshape exists on the unfused trace and is
**absent** on the fused one. Both consumers are pinned bit-identical to
the flat-pool kernel on every point.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.simulator import LATENCY_MODELS
from repro.paging.kv_cache import linear_page_table, paged_decode_attention
from repro.paging.tiered_kv import (TieredKV, tiered_attention, tiered_init,
                                    tiered_min_slots, tiered_stats,
                                    tiered_sweep)

from .common import sized, write_csv
from .flop_count import count_fn
from .roofline import HBM_BW

B, PS, HKV, HQ, DH = 2, 4, 2, 4, 8
NPPS = sized(24, 6)
DECODE_STEPS = sized(4, 2)
N_PAGES = B * NPPS
MODEL = LATENCY_MODELS["rdma_lean"]

# fused-consumer sweep axes (engine-default sweep geometry: chunk=4,
# pw_max=8, ring=8 — the npps=8/12 points are the small-context serving
# shape where the stacked copy dominates hardest)
FUSED_NPPS = sized((8, 12, 24), (6,))
FUSED_S = sized((2, 4), (2,))
FUSED_REPS = sized(5, 2)


def _consume_us_per_access(s: dict, sync: bool) -> float:
    full_hits = s["hits"] - s["partial_hits"]
    blocking = s["misses"] + (s["prefetch_issued"] if sync else 0)
    us = (full_hits * MODEL.t_hit
          + s["partial_hits"] * (MODEL.t_hit + 0.5 * MODEL.t_fabric)
          + blocking * MODEL.t_fabric)
    return us / max(s["faults"], 1)


def _run_one(cold, pt, q, lengths, flat, geom, async_dp):
    st = tiered_init(geom, B, jnp.float32)
    equiv = True
    dt = 0.0
    for _ in range(DECODE_STEPS):
        # time only the serving path; the pin check runs off the clock
        t0 = time.perf_counter()
        st, info = tiered_sweep(st, cold, pt, geom, async_datapath=async_dp)
        out, resident = tiered_attention(q, st, pt, lengths)
        jax.block_until_ready(out)
        dt += time.perf_counter() - t0
        equiv &= bool(resident) and bool(
            (np.asarray(out) == np.asarray(flat)).all())
    agg: dict = {}
    for s in (tiered_stats(st, i) for i in range(B)):
        for k, v in s.items():
            agg[k] = agg.get(k, 0) + (v if isinstance(v, int) else 0)
    return agg, equiv, dt


def _has_stacked_reshape(jaxpr, stacked_dim: int) -> bool:
    """Recursively scan a jaxpr (through pjit/scan/cond sub-jaxprs) for a
    reshape whose output is a pool-like ``[stacked_dim, ...]`` array —
    the stacked hot-pool materialization signature."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "reshape":
            shp = eqn.outvars[0].aval.shape
            if len(shp) >= 3 and shp[0] == stacked_dim:
                return True
        for p in eqn.params.values():
            sub = getattr(p, "jaxpr", None)
            if sub is not None and _has_stacked_reshape(sub, stacked_dim):
                return True
            if isinstance(p, (list, tuple)):
                for b in p:
                    sub = getattr(b, "jaxpr", None)
                    if sub is not None and _has_stacked_reshape(sub,
                                                                stacked_dim):
                        return True
    return False


def _time_consumer(fn, q, reps: int) -> float:
    jax.block_until_ready(fn(q))                     # compile off the clock
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(q)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _fused_point(hot_name: str, S: int, npps: int) -> dict:
    """One fused-vs-unfused point: sweep once to residency, then price the
    two attention consumers on the same hot state."""
    geom0 = TieredKV(1 << 30, 1, PS, HKV, DH)        # engine-default knobs
    floor = tiered_min_slots(npps, geom0)
    n_pages = 2 * S * npps + 2 * floor               # headroom: small < full
    n_slots = floor if hot_name == "small" else n_pages
    geom = TieredKV(n_pages, n_slots, PS, HKV, DH)
    cold = {"k": jax.random.normal(jax.random.PRNGKey(0),
                                   (n_pages, HKV, PS, DH), jnp.float32),
            "v": jax.random.normal(jax.random.PRNGKey(1),
                                   (n_pages, HKV, PS, DH), jnp.float32)}
    pt = linear_page_table(S, npps)
    q = jax.random.normal(jax.random.PRNGKey(2), (S, 1, HQ, DH), jnp.float32)
    lengths = jnp.full((S,), npps * PS - 3, jnp.int32)
    st = tiered_init(geom, S, jnp.float32)
    st, _ = tiered_sweep(st, cold, pt, geom)

    flat = paged_decode_attention(
        q, {"k": cold["k"][None], "v": cold["v"][None]}, jnp.int32(0), pt,
        lengths, use_kernel=True)
    unfused = lambda qq: tiered_attention(qq, st, pt, lengths,
                                          attn_kernel="kernel")[0]
    fused = lambda qq: tiered_attention(qq, st, pt, lengths,
                                        attn_kernel="fused")[0]
    bit_ok = all(bool((np.asarray(f(q)) == np.asarray(flat)).all())
                 for f in (unfused, fused))

    # analytic per-step bytes at the consumer: the unfused path pays the
    # stacked k+v hot-pool copy (read + write) before the context read;
    # the fused path reads only the context pages through the slot table
    pb = PS * HKV * DH * 4                           # bytes per f32 page
    ctx = 2 * S * npps * pb                          # k+v context read
    stack = 4 * S * n_slots * pb                     # k+v copy, rd+wr
    unf_us = (stack + ctx) / HBM_BW * 1e6
    fus_us = ctx / HBM_BW * 1e6

    return {
        "attn": "fused_vs_unfused", "hot": hot_name, "S": S, "npps": npps,
        "n_slots": n_slots,
        "hot_frac": round(S * n_slots / n_pages, 2),
        "bit_identical": bit_ok,
        "unfused_bytes_per_step": stack + ctx,
        "fused_bytes_per_step": ctx,
        "bytes_saved": stack,
        "hot_pool_bytes": 2 * S * n_slots * pb,      # k+v payload
        "unfused_roofline_us": round(unf_us, 3),
        "fused_roofline_us": round(fus_us, 3),
        "roofline_speedup": round(unf_us / fus_us, 2),
        "unfused_jaxpr_bytes": int(count_fn(unfused, q)["bytes"]),
        "fused_jaxpr_bytes": int(count_fn(fused, q)["bytes"]),
        "stacked_reshape_unfused": _has_stacked_reshape(
            jax.make_jaxpr(unfused)(q).jaxpr, S * n_slots),
        "stacked_reshape_fused": _has_stacked_reshape(
            jax.make_jaxpr(fused)(q).jaxpr, S * n_slots),
        "unfused_wall_us": round(1e6 * _time_consumer(unfused, q,
                                                      FUSED_REPS), 1),
        "fused_wall_us": round(1e6 * _time_consumer(fused, q,
                                                    FUSED_REPS), 1),
    }


def run() -> tuple[list[dict], dict]:
    cold = {"k": jax.random.normal(jax.random.PRNGKey(0),
                                   (N_PAGES, HKV, PS, DH), jnp.float32),
            "v": jax.random.normal(jax.random.PRNGKey(1),
                                   (N_PAGES, HKV, PS, DH), jnp.float32)}
    pt = linear_page_table(B, NPPS)
    q = jax.random.normal(jax.random.PRNGKey(2), (B, 1, HQ, DH), jnp.float32)
    lengths = jnp.full((B,), NPPS * PS - 3, jnp.int32)
    flat = paged_decode_attention(
        q, {"k": cold["k"][None], "v": cold["v"][None]}, jnp.int32(0), pt,
        lengths)

    rows, derived, consume, hitrate = [], {}, {}, {}
    small = tiered_min_slots(NPPS, TieredKV(N_PAGES, 1, PS, HKV, DH,
                                            chunk=2, pw_max=4))
    for hot_name, n_slots in (("small", small), ("full", N_PAGES)):
        for path, async_dp in (("sync", False), ("async", True)):
            geom = TieredKV(N_PAGES, n_slots, PS, HKV, DH, chunk=2,
                            pw_max=4, ring_size=8)
            s, equiv, dt = _run_one(cold, pt, q, lengths, flat, geom,
                                    async_dp)
            c = _consume_us_per_access(s, sync=not async_dp)
            consume[(hot_name, path)] = c
            hitrate[(hot_name, path)] = s["hits"] / max(s["faults"], 1)
            rows.append({
                "hot": hot_name, "path": path,
                "hot_frac": round(B * n_slots / N_PAGES, 2),
                "hit_rate": round(hitrate[(hot_name, path)], 3),
                "prefetch_hits": s["prefetch_hits"],
                "partial_hits": s["partial_hits"],
                "pollution": s["pollution"],
                "bit_identical": equiv,
                "consume_us_per_access": round(c, 2),
                "wall_ms_per_decode_step": round(1e3 * dt / DECODE_STEPS, 1),
            })

    for hot_name in ("small", "full"):
        sync_c, async_c = consume[(hot_name, "sync")], consume[(hot_name,
                                                                "async")]
        derived[f"{hot_name}_hit_rate_sync"] = round(
            hitrate[(hot_name, "sync")], 3)
        derived[f"{hot_name}_hit_rate_async"] = round(
            hitrate[(hot_name, "async")], 3)
        derived[f"{hot_name}_consume_sync_us"] = round(sync_c, 2)
        derived[f"{hot_name}_consume_async_us"] = round(async_c, 2)
        derived[f"{hot_name}_async_speedup"] = round(sync_c / async_c, 2)
        derived[f"{hot_name}_async_strictly_faster"] = bool(async_c < sync_c)
    # -- fused attention consumer: hot-fraction x S x npps ------------------
    fused_rows = [_fused_point(hot_name, S, npps)
                  for hot_name in ("small", "full")
                  for S in FUSED_S
                  for npps in FUSED_NPPS]
    rows.extend(fused_rows)
    small_rows = [r for r in fused_rows if r["hot"] == "small"]
    derived["fused_strictly_faster_all_points"] = all(
        r["fused_roofline_us"] < r["unfused_roofline_us"]
        and r["fused_jaxpr_bytes"] < r["unfused_jaxpr_bytes"]
        for r in fused_rows)
    derived["fused_speedup_small_min"] = min(
        r["roofline_speedup"] for r in small_rows)
    # headline: >=5x on the small-context serving shape (the configuration
    # the stacked copy hurt most)
    derived["fused_speedup_small_max"] = max(
        r["roofline_speedup"] for r in small_rows)
    derived["fused_speedup_max"] = max(
        r["roofline_speedup"] for r in fused_rows)
    # bytes saved per step == the stacked k+v hot-pool copy (read + write),
    # i.e. exactly 2x the hot-pool payload the unfused path re-materializes
    derived["fused_bytes_saved_over_hot_pool"] = round(
        float(np.mean([r["bytes_saved"] / r["hot_pool_bytes"]
                       for r in fused_rows])), 2)
    derived["fused_stacked_reshape_gone"] = all(
        r["stacked_reshape_unfused"] and not r["stacked_reshape_fused"]
        for r in fused_rows)
    derived["all_bit_identical"] = all(r["bit_identical"] for r in rows)
    write_csv("tiered_kv", rows[:len(rows) - len(fused_rows)])
    write_csv("tiered_kv_fused", fused_rows)
    return rows, derived
