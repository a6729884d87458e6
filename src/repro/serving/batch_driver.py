"""Lock-step batch serving: the fixed-batch tiered replay + chaos sidecar.

The original serving loop (pre-continuous-batching): every request in the
batch prefills together, decodes together, finishes together. The tiered
replay here is still the reference data-path driver — it mirrors the
model's *real* decoded K/V into the cold pool and pins tiered/flat
bit-identity every step — and the continuous engine
(:mod:`repro.serving.engine`) is benchmarked against its gang-admission
discipline. ``launch/serve.py`` dispatches here for ``--arrival batch``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.export import write_chrome_trace, write_jsonl
from repro.obs.metrics import Registry
from repro.obs.trace import (Event, decode_sweep_events, events_to_counts,
                             summary_events)
from repro.paging.kv_cache import (append_kv, init_paged_kv,
                                   linear_page_table, paged_decode_attention)
from repro.paging.sharded_pool import ShardedPoolCfg
from repro.paging.tiered_kv import (TieredKV, normalize_attn_kernel,
                                    tiered_attention, tiered_init,
                                    tiered_invalidate, tiered_min_slots,
                                    tiered_stats, tiered_sweep)

#: event-type totals that must reproduce the pool counters bit-exactly
#: whenever a trace is written (DESIGN.md §8.2)
PINNED_COUNTERS = ("hits", "misses", "partial_hits", "prefetch_hits",
                   "prefetch_issued", "deferred", "ring_drops", "pollution")


def find_dense_kv(state) -> tuple[jax.Array, jax.Array] | tuple[None, None]:
    """Pull one attention block's dense KV cache out of a decode state.

    Returns ``(k, v)`` each ``[B, T, Hkv, dh]`` (first attention layer of
    the scan period / the self-attention stack), or ``(None, None)`` for
    cache-free families (pure mamba/xlstm) — the caller then mirrors
    synthetic KV so the tiered data path is still exercised end to end.
    """
    cands = []
    if isinstance(state, dict):
        cands.extend(b for b in state.get("blocks", ()) if isinstance(b, dict))
        if isinstance(state.get("self_kv"), dict):
            cands.append(state["self_kv"])
    for b in cands:
        if "k" in b and "v" in b and getattr(b["k"], "ndim", 0) == 5:
            return b["k"][0], b["v"][0]
    return None, None


def serve_batch_tiered(cfg, state, args, B: int, prompt_len: int,
                       max_len: int, reg: Registry | None = None,
                       trace_path: str | None = None) -> dict:
    """Replay the decode window through the tiered paged-KV data path.

    Mirrors the model's real decoded K/V into the cold paged pool, then per
    decode step: append the step's KV (``append_kv``), invalidate the
    written page in every stream's hot tier, demand-sweep each request's
    context pages through its hot pool, and serve attention from hot slots
    — asserting bit-identity against the flat pool every step.

    With ``trace_path`` the per-sweep info arrays are decoded host-side
    (after the timed window — the jitted path is untouched) into the
    page-lifecycle event log on the global chunk-step clock, written as a
    Chrome trace + JSONL, and the event-type totals are pinned bit-exact
    against the final pool counters.
    """
    ps = args.page_size
    npps = -(-max_len // ps)
    n_pages = B * npps
    hkv, hq, dh = cfg.n_kv_heads, cfg.n_heads, cfg.head_dim
    n_streams = args.streams if args.streams > 1 else B

    kd, vd = find_dense_kv(state)
    if kd is None:
        # cache-free family: synthetic KV, the data path is still real
        kd = jax.random.normal(jax.random.PRNGKey(7),
                               (B, max_len, hkv, dh), jnp.dtype(cfg.dtype))
        vd = jax.random.normal(jax.random.PRNGKey(8),
                               (B, max_len, hkv, dh), jnp.dtype(cfg.dtype))

    def pad_to(x, T):
        if x.shape[1] >= T:
            return x[:, :T]
        return jnp.concatenate(
            [x, jnp.zeros((B, T - x.shape[1]) + x.shape[2:], x.dtype)], 1)

    kd, vd = pad_to(kd, npps * ps), pad_to(vd, npps * ps)
    pt_full = linear_page_table(B, npps)

    # Cold tier: mirror the prompt prefix now; decode positions are appended
    # step by step inside the replay loop (the real write path).
    pool = init_paged_kv(1, n_pages, ps, hkv, dh, kd.dtype)
    pos_ids = jnp.arange(npps * ps)
    prefix = lambda x: jnp.where((pos_ids < prompt_len)[None, :, None, None],
                                 x, 0)
    to_pages = lambda x: x.reshape(B * npps, ps, hkv, dh).swapaxes(1, 2)
    pool = {"k": pool["k"].at[0, pt_full.reshape(-1)].set(
                to_pages(prefix(kd))),
            "v": pool["v"].at[0, pt_full.reshape(-1)].set(
                to_pages(prefix(vd)))}

    # n_slots derived from the sweep geometry (the documented residency
    # floor), not a hardcoded constant that ignores pw_max/ring.
    proto = TieredKV(n_pages, 1, ps, hkv, dh, chunk=args.chunk,
                     ring_size=args.ring_size)
    geom = TieredKV(n_pages, tiered_min_slots(npps, proto), ps, hkv, dh,
                    chunk=args.chunk, ring_size=args.ring_size)
    tstate = tiered_init(geom, n_streams, kd.dtype)
    rows = jnp.stack([pt_full[s % B] for s in range(n_streams)])

    fabric = mesh = None
    if args.shards > 1:
        from repro.launch.mesh import make_fabric_mesh
        if n_pages % args.shards:
            raise SystemExit(f"--shards {args.shards} must divide the "
                             f"{n_pages}-page cold pool")
        fabric = ShardedPoolCfg(n_shards=args.shards,
                                placement=args.placement,
                                link_budget=args.link_budget,
                                near_delay=1, far_delay=args.far_delay)
        mesh = make_fabric_mesh(args.shards)
        # append_kv mutates the cold pool every step, so tiered_sweep
        # re-places the whole pool home-major per call — fine for this
        # pin-every-step smoke driver (which also recomputes the flat
        # reference each step); a production loop would keep the pool
        # permanently placed and route append_kv writes through place_perm

    reg = reg if reg is not None else Registry()
    attn_mode = normalize_attn_kernel(getattr(args, "attn_kernel", "ref"))
    n_chunks = -(-npps // geom.chunk)      # global clock: chunk steps
    events = [] if trace_path else None
    link_hist, shard_hist = [], []
    equiv_ok = True
    first_bad_step = None
    deferred = partials = 0
    shard_demand = np.zeros(args.shards, np.int64)
    for t in range(args.gen - 1):
        pos = prompt_len + t
        pool = append_kv(pool, jnp.int32(0), kd[:, pos], vd[:, pos],
                         pt_full, jnp.int32(pos))
        written = pt_full[:, pos // ps]                      # [B]
        inv_pages = jnp.stack([written[s % B] for s in range(n_streams)])
        tstate = tiered_invalidate(tstate, inv_pages[:, None])
        cold = {"k": pool["k"][0], "v": pool["v"][0]}
        lengths = jnp.full((n_streams,), pos + 1, jnp.int32)
        q = jax.random.normal(jax.random.PRNGKey(100 + t),
                              (n_streams, 1, hq, dh), jnp.dtype(cfg.dtype))
        # timed window covers only the serving path (sweep + attention);
        # the flat-pool reference, the bitwise pin check and the host-side
        # event decode all run outside it
        with reg.span("tiered_sweep") as sp:
            tstate, info = tiered_sweep(tstate, cold, rows, geom,
                                        async_datapath=args.async_datapath,
                                        link_budget=args.link_budget,
                                        fabric=fabric, mesh=mesh)
            sp.sync = info
        # one copy of the mesh-replicated hot tier for the Mosaic attention
        # kernel, which XLA cannot partition
        hot = (tstate if mesh is None
               else jax.device_put(tstate, pool["k"].sharding))
        with reg.span("tiered_attention") as sp:
            tiered, resident = tiered_attention(q, hot, rows, lengths,
                                                attn_kernel=attn_mode)
            sp.sync = tiered
        flat = paged_decode_attention(
            q, pool, jnp.int32(0), rows, lengths,
            use_kernel=(attn_mode != "ref"))
        step_ok = bool(resident) and bool(
            (np.asarray(tiered) == np.asarray(flat)).all())
        if not step_ok and first_bad_step is None:
            first_bad_step = t
        equiv_ok &= step_ok
        deferred += int(np.asarray(info["deferred"]).sum())
        partials += int(np.asarray(info["partial_hit"]).sum())
        if fabric is not None:
            shard_demand += np.asarray(info["shard_demand_fetches"]).sum(0)
        if events is not None:
            step0 = t * n_chunks           # each sweep advances the stream
            inv_np = np.asarray(inv_pages)  # clock by n_chunks steps
            events.extend(Event("invalidate", step0, s, page=int(inv_np[s]))
                          for s in range(n_streams))
            events.extend(decode_sweep_events(info, step_offset=step0))
            link_hist.append(np.asarray(info["link_demand_fetches"]))
            shard_hist.append(np.asarray(info["shard_demand_fetches"]))

    per = [tiered_stats(tstate, s) for s in range(n_streams)]
    t_tiered = (reg.histogram("tiered_sweep").total
                + reg.histogram("tiered_attention").total)
    out = {
        "tiered_equiv_ok": equiv_ok,
        "tiered_attn_kernel": attn_mode,
        "tiered_streams": n_streams,
        "tiered_n_slots": geom.n_slots,
        "tiered_hot_frac": round(n_streams * geom.n_slots / n_pages, 3),
        "tiered_decode_s": round(t_tiered, 3),
        "paged_prefetch_hit_rate": round(
            float(np.mean([p["coverage"] for p in per])), 3),
        "paged_pollution": sum(p["pollution"] for p in per),
        "paged_ring_drops": sum(p["ring_drops"] for p in per),
    }
    if args.async_datapath:
        out["paged_partial_hits"] = partials
        out["paged_latency_hidden_frac"] = round(
            float(np.mean([p["latency_hidden_frac"] for p in per])), 3)
    if args.link_budget is not None:
        out["paged_link_budget"] = args.link_budget
        out["paged_deferred"] = deferred
    if args.shards > 1:
        out["paged_shards"] = args.shards
        out["paged_placement"] = args.placement
        out["paged_shard_demand"] = shard_demand.tolist()
    if first_bad_step is not None:
        out["tiered_first_bad_step"] = first_bad_step
    spans = reg.summary()["histograms"]
    out["span_sweep_ms"] = round(spans["tiered_sweep"]["avg"] * 1e3, 3)
    out["span_attention_ms"] = round(spans["tiered_attention"]["avg"] * 1e3, 3)
    if events is not None:
        events.extend(summary_events(per))
        cnts = events_to_counts(events, n_streams)
        totals_ok = all(cnts[s][k] == per[s][k] for s in range(n_streams)
                        for k in PINNED_COUNTERS)
        counters = {"link_demand_fetches": np.concatenate(link_hist)}
        if args.shards > 1:
            counters["shard_demand_fetches"] = np.concatenate(shard_hist)
        write_chrome_trace(trace_path, events, counters)
        write_jsonl(trace_path + ".jsonl", events)
        out["trace_path"] = trace_path
        out["trace_events"] = len(events)
        out["trace_totals_ok"] = totals_ok
    if args.chaos:
        out.update(chaos_sidecar(args, rows, n_pages, n_streams))
    return out


def chaos_sidecar(args, rows, n_pages: int, n_streams: int) -> dict:
    """Replay the requests' context-page schedules under a ChaosSpec.

    The sidecar drives the chaos-enabled sharded consume path
    (DESIGN.md §9) over the same physical pages the tiered path serves:
    each stream walks its context pages cyclically, the spec's faults
    (stragglers / budget cuts / node loss / grant churn) hit the fabric
    model, and the report compares the adaptive-deadline EWMA's per-shard
    delay estimate against the true (dilated) delay at the end of the run
    — the operator-facing "is my deadline model tracking the fabric"
    signal.
    """
    from repro.fabric.chaos import EST_ONE, ChaosSpec, compile_chaos
    from repro.paging.prefetch_serving import (PrefetchedStream,
                                               stream_stats_at)
    from repro.paging.sharded_pool import sharded_multi_stream_consume

    with open(args.chaos) as f:
        spec = ChaosSpec.from_json(f.read())
    G = max(args.shards, 1)
    if n_pages % G:
        raise SystemExit(f"--chaos sidecar: {n_pages}-page pool not "
                         f"divisible by {G} shards")
    npps = rows.shape[1]
    T = min(max(4 * npps, 48), 256)
    rows_np = np.asarray(rows)
    scheds = np.stack([rows_np[s][np.arange(T) % npps]
                       for s in range(n_streams)]).astype(np.int32)
    geom = PrefetchedStream(n_pages=n_pages, n_slots=n_pages, page_elems=4,
                            ring_size=args.ring_size)
    fab = ShardedPoolCfg(n_shards=G, placement=args.placement,
                         link_budget=args.link_budget,
                         near_delay=1, far_delay=args.far_delay)
    cold = jnp.arange(n_pages * 4, dtype=jnp.float32).reshape(n_pages, 4)
    st, _, info = sharded_multi_stream_consume(
        cold, jnp.asarray(scheds), geom, fab, chaos=spec)
    per = [stream_stats_at(st, s) for s in range(n_streams)]
    faults = sum(p["faults"] for p in per)
    hits = sum(p["prefetch_hits"] for p in per)
    deferred = sum(p["deferred"] for p in per)
    cz = compile_chaos(spec, n_steps=T, n_streams=n_streams, n_shards=G,
                       n_pages=n_pages, placement=args.placement,
                       base_budget=args.link_budget)
    # final per-shard delay: estimate (stream-averaged EWMA, steps) vs the
    # true dilated delay at the last step (stream-averaged near/far base)
    est = np.asarray(info["est_q"], dtype=np.float64) / EST_ONE
    home = np.arange(n_streams) % G
    base = np.where(np.arange(G)[None, :] == home[:, None],
                    1, args.far_delay)
    true = base * np.asarray(cz["dilation"][-1], dtype=np.float64)[None, :]
    return {
        "chaos_spec": args.chaos,
        "chaos_steps": T,
        "chaos_shards": G,
        "chaos_faults": faults,
        "chaos_prefetch_hits": hits,
        "chaos_deferred": deferred,
        "chaos_timely_rate": round((hits - deferred) / max(1, faults), 3),
        "chaos_pollution": sum(p["pollution"] for p in per),
        "chaos_est_delay": [round(float(v), 2) for v in est.mean(0)],
        "chaos_true_delay": [round(float(v), 2) for v in true.mean(0)],
        "chaos_adaptive_deadline": spec.adaptive_deadline,
    }
