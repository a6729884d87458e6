"""Tiered paged-KV serving: a Leap-managed HBM hot pool feeding decode attention.

This is the application-integrated data path the paper argues for (§4.2-4.4):
instead of a stand-alone page-stream simulator running beside the model, the
KV pages that decode attention actually reads live in a two-tier hierarchy —

* **cold tier**: the existing paged KV pool layer slice
  (``{"k","v"}: [n_pages, Hkv, page_size, dh]``, the mesh-shardable
  disaggregated side, :mod:`repro.paging.kv_cache`);
* **hot tier**: a small HBM-resident pool of slots *per request stream*
  (``{"k","v"}: [n_streams, n_slots, Hkv, page_size, dh]`` — the k and v
  leaves of a slot always move together), managed by the per-stream Leap
  controller exactly like the kernel-space page cache of the paper.

Access model (DESIGN.md §6): each decode step, every request *sweeps* its
context pages through the hot pool in chunks of ``geom.chunk`` pages — the
multi-page demand batch of :func:`repro.core.pool.pool_wait_batch` /
:func:`repro.core.pool.pool_access`. The sweep feeds the Leap controller,
whose candidates run ahead of the sweep frontier; on the async path they ride
the issue/wait in-flight ring and their DMA overlaps the next chunk's
compute. The hot tier retains pages under the *lazy* (LRU) eviction policy —
the residency window a consumer that reads pages **after** the sweep needs —
and once the sweep completes, attention runs directly over hot slots through
a remapped page table (:func:`tiered_slot_table`) into
:func:`repro.kernels.paged_attention.paged_attention`. Because the remapped
gather reads bit-identical bytes in the same logical order, tiered decode
logits are **bit-identical** to the flat-pool
:func:`repro.paging.kv_cache.paged_decode_attention` (pinned in
``tests/test_tiered_kv.py``).

The metadata transactions are metadata-only pool calls (``hot=None``); the
actual bytes move through the :mod:`repro.kernels.gather_pages` kernels —
the pipelined gather on the sync path, the explicit
``make_async_copy`` issue/wait double-buffer (:func:`gather_pages_async`) on
the async path — one batched kernel call per chunk step over all streams.

Write coherence: the serving loop appends new K/V into cold pages
(``append_kv``) every decode step; :func:`tiered_invalidate` must drop the
written page from each stream's hot tier (and in-flight ring) so a stale hot
copy never serves attention.

Streams advance in lock-step over chunk steps, so a finite ``link_budget``
composes with the DESIGN.md §5 arbitration unchanged: demand chunk fetches
complete in-step, leftover budget lands in-flight prefetches across all
streams in global issue order, the surplus defers in the ring.

The sweep also composes with the **mesh-sharded cold pool** (DESIGN.md §7,
:mod:`repro.paging.sharded_pool`): hot pools stay local per stream, the
cold ``{"k","v"}`` pool shards over the mesh's ``fabric`` axis. Pass a
:class:`repro.paging.sharded_pool.ShardedPoolCfg` (and a mesh) to
:func:`tiered_sweep` — the per-chunk budget becomes *per NIC* (one §5
arbiter per home shard), prefetch deadlines gain the near/far asymmetry,
and the chunk copy plans gather cross-shard pages with ``lax.ppermute``
ring rotations under ``shard_map``. ``shards=1`` (or no fabric) reduces
bit-exactly to the single-link path above.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.leap_jax import leap_init, leap_step
from repro.core.pool import (NO_PAGE, link_grants_sharded, page_home,
                             page_local, pool_access, pool_init,
                             pool_invalidate, pool_issue, pool_wait_batch,
                             ring_init)
from repro.core.window import DEFAULT_PW_MAX
from repro.kernels.gather_pages import gather_pages, gather_pages_async
from repro.kernels.paged_attention import (paged_attention,
                                           paged_attention_hot_slots)
from repro.paging.prefetch_serving import stream_stats_at
from repro.paging.sharded_pool import (ShardedPoolCfg, cached_shard_map,
                                       check_fabric_topology,
                                       fabric_ring_gather, place_cold,
                                       scatter_hot, stream_homes)


@dataclasses.dataclass(frozen=True)
class TieredKV:
    """Static geometry of the tiered paged-KV cache.

    Attributes:
      n_pages:    cold-tier pages (shared by all streams; page ids are the
                  *physical* page-table values).
      n_slots:    hot slots per stream; must be at least
                  :func:`tiered_min_slots` of the sweep length so every
                  swept page is still resident when attention reads it.
      page_size:  tokens per KV page.
      n_kv_heads / head_dim: KV page payload shape (head-major
                  ``[Hkv, page_size, dh]``).
      chunk:      demand pages per sweep step (the multi-page demand batch).
      pw_max / h_size / n_split: Leap controller knobs (see
                  :class:`repro.paging.prefetch_serving.PrefetchedStream`).
      ring_size:  async in-flight ring capacity; ``0`` degenerates the async
                  path to the sync one (same convention as the stream layer).
      arrival_delay: chunk steps between prefetch issue and arrival.
      use_kernel: move bytes through the Pallas gather kernels (True) or the
                  jnp reference gather (False — identical bytes, no kernel).
    """
    n_pages: int
    n_slots: int
    page_size: int
    n_kv_heads: int
    head_dim: int
    chunk: int = 4
    pw_max: int = DEFAULT_PW_MAX
    h_size: int = 32
    n_split: int = 8
    ring_size: int = 8
    arrival_delay: int = 1
    use_kernel: bool = True

    @property
    def page_shape(self) -> tuple[int, int, int]:
        return (self.n_kv_heads, self.page_size, self.head_dim)


def tiered_min_slots(npps: int, geom: TieredKV) -> int:
    """Hot-slot floor for a sweep of ``npps`` pages per decode step.

    The whole swept row must stay resident until attention reads it, plus
    headroom for one chunk's demand staging, the prefetch frontier running
    past the row, and in-flight landings — below this floor the lazy LRU
    can cannibalize the sweep and break the equivalence pin. Capped at
    ``n_pages``: a fully hot tier can never evict at all.
    """
    return min(npps + geom.chunk + max(geom.pw_max, geom.ring_size) + 2,
               geom.n_pages)


def tiered_init(geom: TieredKV, n_streams: int, dtype=jnp.bfloat16) -> dict:
    """Stacked per-stream tiered state (leading ``[n_streams]`` axis).

    Keys per stream: ``leap`` (controller), ``pool_meta``
    (:func:`repro.core.pool.pool_init`), ``ring``
    (:func:`repro.core.pool.ring_init`) and the hot payload
    ``hot = {"k","v"}: [n_slots, Hkv, page_size, dh]`` of ``dtype``.
    """
    kv = jnp.zeros((geom.n_slots,) + geom.page_shape, dtype)
    one = {
        "leap": leap_init(geom.h_size),
        "pool_meta": pool_init(geom.n_pages, geom.n_slots),
        "ring": ring_init(geom.ring_size),
        "hot": {"k": kv, "v": kv},
    }
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_streams,) + x.shape).copy(), one)


def _apply_copies(hot: dict, cold: dict, src: jax.Array, dst: jax.Array,
                  mask: jax.Array, *, asynchronous: bool, use_kernel: bool,
                  fabric: ShardedPoolCfg | None = None,
                  sharded: bool = False, n_pages: int = 0) -> dict:
    """Data plane: move ``cold[src] -> hot[dst]`` where ``mask``, k+v together.

    ``src``/``dst``/``mask`` are ``[S, K]`` (per-stream copy plans from the
    metadata transactions); the cold tier is shared, so all streams' copies
    ride **one** gather kernel call per leaf — ``gather_pages`` (pipelined
    double-buffered DMA) on the sync path, ``gather_pages_async`` (explicit
    issue/wait pairs) on the async path — scattered into the stacked hot
    pool. Masked-out entries scatter out of bounds and are dropped.

    ``sharded=True`` (inside ``shard_map``, cold leaves ``[pps, ...]``
    home-major): the gather becomes a ring of ``lax.ppermute`` rotations
    over the ``fabric`` axis — each rotation runs the same gather kernel
    against the visiting shard's slice at :func:`repro.core.pool.page_local`
    indices and keeps the pages homed there (DESIGN.md §7). Bytes are
    bit-identical to the flat gather.
    """
    S = src.shape[0]
    gfn = gather_pages_async if asynchronous else gather_pages
    if not sharded:
        flat_src = jnp.maximum(src, 0).reshape(-1)
        gather = lambda c: gfn(c, flat_src, use_kernel=use_kernel)
    else:
        G = fabric.n_shards
        pps = n_pages // G
        homes = page_home(src, n_pages, G, fabric.placement).reshape(-1)
        local = jnp.clip(page_local(src, n_pages, G, fabric.placement),
                         0, pps - 1).reshape(-1)
        gather = lambda c: fabric_ring_gather(
            c, local, homes, G,
            lambda b, ix: gfn(b, ix, use_kernel=use_kernel))

    data = jax.tree.map(
        lambda c: gather(c).reshape((S, -1) + c.shape[1:]), cold)
    return scatter_hot(hot, data, dst, mask)


def _leap_chunk(leap: dict, pages: jax.Array, feedback: jax.Array,
                valid: jax.Array, geom: TieredKV):
    """Feed one chunk of demand accesses through the controller.

    Every valid page updates the tracker (history + FINDTREND + window);
    the emitted candidates are the *frontier's* — the last valid page of
    the chunk — so prefetching runs ahead of the sweep, not inside it.
    Returns ``(leap, candidates[pw_max], cand_valid[pw_max])``.
    """
    C = pages.shape[0]

    def body(lp, inp):
        page, fb, v = inp
        lp2, cands, cvalid = leap_step(lp, jnp.maximum(page, 0), fb,
                                       n_split=geom.n_split,
                                       pw_max=geom.pw_max)
        lp = jax.tree.map(lambda a, b: jnp.where(v, b, a), lp, lp2)
        return lp, (cands, cvalid & v)

    leap, (cands_all, cvalid_all) = jax.lax.scan(
        body, leap, (pages, feedback, valid))
    last = jnp.maximum(
        jnp.argmax(jnp.where(valid, jnp.arange(C, dtype=jnp.int32), -1)), 0)
    return leap, cands_all[last], cvalid_all[last] & jnp.any(valid)


def _chunk_sync(leap: dict, meta: dict, pages: jax.Array, geom: TieredKV):
    """One sync chunk step for one stream: controller first, then one
    blocking batched transaction carrying the chunk's demands *and* the
    frontier candidates (mirrors :func:`stream_step`, metadata-only)."""
    C = pages.shape[0]
    valid_d = pages >= 0
    p_safe = jnp.clip(pages, 0, geom.n_pages - 1)
    slot0 = meta["page_slot"][p_safe]
    s_safe = jnp.maximum(slot0, 0)
    was_pref = (valid_d & (slot0 >= 0) & meta["slot_prefetched"][s_safe]
                & ~meta["slot_consumed"][s_safe])
    leap, cands, cvalid = _leap_chunk(leap, pages, was_pref, valid_d, geom)

    req = jnp.concatenate([pages, cands])
    is_pf = jnp.concatenate([jnp.zeros((C,), bool),
                             jnp.ones((geom.pw_max,), bool)])
    val = jnp.concatenate(
        [valid_d, cvalid & (cands >= 0) & (cands < geom.n_pages)])
    meta, _, slots, info = pool_access(meta, None, None, req, is_pf, val,
                                       lazy=True)
    issued = jnp.sum(info["fetched"][C:].astype(jnp.int32))
    return leap, meta, slots, info, req, issued


def _chunk_async(leap: dict, meta: dict, ring: dict, pages: jax.Array,
                 land_ok: jax.Array, seq: jax.Array, home_s: jax.Array,
                 geom: TieredKV, fabric: ShardedPoolCfg, home_tab=None,
                 comp_tab=None, mig_delay: int = 0):
    """One async chunk step for one stream: wait (land + serve the chunk's
    demands), controller, issue (mirrors :func:`stream_step_async`,
    metadata-only). ``home_s`` is the stream's home shard — candidates
    homed there get ``fabric.near_delay`` deadlines, cross-shard ones
    ``fabric.far_delay`` (DESIGN.md §7; degenerate at one shard).

    ``home_tab`` (``int32[n_pages]``, the §12 lifecycle's time-varying home
    map) replaces the static placement formula for deadline routing;
    ``comp_tab`` (``bool[n_pages]``) adds the ``mig_delay`` decompress
    surcharge to candidates sitting in the compressed cold tier (the
    promote-from-compressed cost). Both ``None`` is the exact two-tier
    path."""
    now = ring["now"]
    valid_d = pages >= 0
    deferred0 = meta["n_deferred"]
    issued0 = meta["n_prefetch_issued"]
    meta, ring, _, slots, winfo = pool_wait_batch(
        meta, ring, None, None, pages, valid_d, now, lazy=True,
        land_ok=land_ok)
    fb = winfo["prefetched_hit"] | winfo["partial_hit"]
    leap, cands, cvalid = _leap_chunk(leap, pages, fb, valid_d, geom)
    cval = cvalid & (cands >= 0) & (cands < geom.n_pages)
    if home_tab is None:
        homes_c = page_home(cands, geom.n_pages, fabric.n_shards,
                            fabric.placement)
    else:
        homes_c = home_tab[jnp.clip(cands, 0, geom.n_pages - 1)]
    delay = jnp.where(homes_c == home_s, jnp.int32(fabric.near_delay),
                      jnp.int32(fabric.far_delay))
    if comp_tab is not None:
        delay = delay + jnp.where(
            comp_tab[jnp.clip(cands, 0, geom.n_pages - 1)],
            jnp.int32(mig_delay), jnp.int32(0))
    meta, ring = pool_issue(meta, ring, cands, cval, now, delay, seq=seq)
    ring = dict(ring)
    ring["now"] = now + 1
    issued = meta["n_prefetch_issued"] - issued0
    deferred = meta["n_deferred"] - deferred0
    return leap, meta, ring, slots, winfo, issued, deferred


def _sweep_fn(state: dict, cold: dict, sched: jax.Array, geom: TieredKV,
              async_datapath: bool, fabric: ShardedPoolCfg, sharded: bool,
              lifecycle: dict | None = None, mig_delay: int = 0):
    """Lock-step sweep over ``sched [n_chunks, S, chunk]``.

    ``fabric`` is always present: the single-link path is the degenerate
    one-shard fabric (whole budget on one NIC, every page near — reduces
    bit-exactly to the pre-§7 behavior). ``sharded=True`` means the
    function runs inside ``shard_map`` with ``cold`` leaves holding the
    local ``[pps, ...]`` home slice.

    ``lifecycle`` (``{"home": int32[n_pages], "comp": bool[n_pages]}``, the
    §12 tier maps the serving engine's :class:`PageLifecycle` maintains
    between steps) reroutes *scheduling* — budget arbitration, near/far
    deadlines (+``mig_delay`` on compressed pages), per-NIC demand
    accounting — while the data plane keeps gathering from the static
    placement (migration is scheduling metadata only, which is what keeps
    the flat and shard_map planes bit-equal).
    """
    n_chunks, S, C = sched.shape
    G = fabric.n_shards
    stream_ids = jnp.arange(S, dtype=jnp.int32)
    homes_s = stream_homes(S, G)
    home_tab = None if lifecycle is None else lifecycle["home"]
    comp_tab = None if lifecycle is None else lifecycle.get("comp")
    _homes = (lambda p: page_home(p, geom.n_pages, G, fabric.placement)) \
        if home_tab is None else \
        (lambda p: home_tab[jnp.clip(p, 0, geom.n_pages - 1)])

    def body(carry, pages):
        state, d_prev = carry                # pages: [S, C]; d_prev int32[G]
        leap, meta = state["leap"], state["pool_meta"]
        ring, hot = state["ring"], state["hot"]
        if async_datapath:
            now = ring["now"]                                # int32[S]
            if fabric.link_budget is not None:
                # per-NIC leftover budget: shard g's demand traffic last
                # chunk step comes off shard g's landing capacity
                caps = jnp.maximum(jnp.int32(fabric.link_budget) - d_prev, 0)
                homes_ring = _homes(ring["page"])
                ok = link_grants_sharded(ring, now, caps, homes_ring)
            else:
                ok = jnp.ones(ring["page"].shape, bool)
            # seq rides the persistent per-stream clock (not the per-call
            # chunk index) so entries surviving across tiered_sweep calls —
            # deferred or issued on the last chunk step — keep their global
            # FIFO rank and no two live entries ever share a stamp.
            seq = ((now * S + stream_ids)[:, None] * geom.pw_max
                   + jnp.arange(geom.pw_max, dtype=jnp.int32)[None, :])
            leap, meta, ring, slots, info, issued, deferred = jax.vmap(
                functools.partial(_chunk_async, geom=geom, fabric=fabric,
                                  home_tab=home_tab, comp_tab=comp_tab,
                                  mig_delay=mig_delay))(
                leap, meta, ring, pages, ok, seq, homes_s)
            # copy plan: landings first, then demand fetches (internal order)
            src = jnp.concatenate(
                [info["landed_pages"],
                 jnp.where(info["fetched"], pages, NO_PAGE)], axis=1)
            dst = jnp.concatenate([info["landed_slots"], slots], axis=1)
            mask = jnp.concatenate([info["landed"], info["fetched"]], axis=1)
            landed = jnp.sum(info["landed"].astype(jnp.int32), axis=1)
        else:
            leap, meta, slots, info, req, issued = jax.vmap(
                functools.partial(_chunk_sync, geom=geom))(leap, meta, pages)
            src, dst, mask = req, slots, info["fetched"]
            info = {"hit": info["hit"][:, :C],
                    "prefetched_hit": info["prefetched_hit"][:, :C],
                    "partial_hit": jnp.zeros((S, C), bool),
                    "fetched": info["fetched"][:, :C]}
            deferred = jnp.zeros((S,), jnp.int32)
            landed = issued      # sync: candidates land in their own chunk step
        hot = _apply_copies(hot, cold, src, dst, mask,
                            asynchronous=async_datapath,
                            use_kernel=geom.use_kernel,
                            fabric=fabric, sharded=sharded,
                            n_pages=geom.n_pages)
        state = {"leap": leap, "pool_meta": meta, "ring": ring, "hot": hot}
        cnt = lambda m: jnp.sum(m.astype(jnp.int32), axis=1)  # [S]
        d_t = cnt(info["fetched"])
        homes_d = _homes(pages)
        d_t_shard = jnp.zeros((G,), jnp.int32).at[homes_d.reshape(-1)].add(
            info["fetched"].reshape(-1).astype(jnp.int32), mode="drop")
        outs = (cnt(info["hit"]), cnt(info["prefetched_hit"]),
                cnt(info["partial_hit"]), d_t, issued, landed, deferred,
                jnp.sum(d_t), d_t_shard)
        return (state, d_t_shard), outs

    (state, _), (hit, pref, part, fetched, issued, landed, deferred, link_d,
                 shard_d) = jax.lax.scan(
        body, (state, jnp.zeros((G,), jnp.int32)), sched)
    info = {"hit": hit.T, "pref_hit": pref.T, "partial_hit": part.T,
            "fetched": fetched.T, "issued": issued.T, "landed": landed.T,
            "deferred": deferred.T,
            "link_demand_fetches": link_d,
            "shard_demand_fetches": shard_d}                  # [n_chunks, G]
    return state, info


_sweep_impl = jax.jit(_sweep_fn, static_argnames=("geom", "async_datapath",
                                                  "fabric", "sharded",
                                                  "mig_delay"))

def _sweep_sharded(mesh, geom: TieredKV, async_datapath: bool,
                   fabric: ShardedPoolCfg, with_lifecycle: bool = False,
                   mig_delay: int = 0):
    """The jitted shard_map sweep for one topology (memoized through
    :func:`repro.paging.sharded_pool.cached_shard_map`: cold sharded over
    the mesh's ``fabric`` axis, everything else replicated — including the
    §12 lifecycle maps, which only steer scheduling)."""
    from jax.sharding import PartitionSpec as P

    if with_lifecycle:
        return cached_shard_map(
            (mesh, "tiered_sweep_mig", geom, async_datapath, fabric,
             mig_delay),
            lambda: lambda state, cold, sched, lifecycle: _sweep_fn(
                state, cold, sched, geom, async_datapath, fabric, True,
                lifecycle, mig_delay),
            (P(), P("fabric"), P(), P()))
    return cached_shard_map(
        (mesh, "tiered_sweep", geom, async_datapath, fabric),
        lambda: functools.partial(_sweep_fn, geom=geom,
                                  async_datapath=async_datapath,
                                  fabric=fabric, sharded=True),
        (P(), P("fabric"), P()))


def tiered_sweep(state: dict, cold: dict, page_rows: jax.Array,
                 geom: TieredKV, *, async_datapath: bool = False,
                 link_budget: int | None = None,
                 fabric: ShardedPoolCfg | None = None,
                 mesh=None, home_map: jax.Array | None = None,
                 comp_map: jax.Array | None = None,
                 decompress_delay: int = 0) -> tuple[dict, dict]:
    """Sweep every stream's context pages through its hot pool, chunked.

    Args:
      state: stacked tiered state from :func:`tiered_init`.
      cold:  ``{"k","v"}: [n_pages, Hkv, page_size, dh]`` cold tier (one
             layer slice of the paged KV pool), in original page-id order.
      page_rows: ``int32[S, npps]`` physical page ids per stream (the
             page-table rows of the requests each stream serves; ``-1``
             entries are skipped).
      async_datapath: sync batched vs issue/wait chunk steps.
             ``geom.ring_size == 0`` degenerates async to sync (same
             convention as the stream layer).
      link_budget: optional pages/step the shared link moves across all
             streams' prefetches (DESIGN.md §5); demand chunks always
             complete in-step. Ignored when ``fabric`` is given (its
             ``link_budget`` — *per NIC* — takes over).
      fabric: optional :class:`repro.paging.sharded_pool.ShardedPoolCfg` —
             the cold pool is sharded over ``fabric.n_shards`` home shards
             (DESIGN.md §7): per-NIC §5 budgets, near/far prefetch
             deadlines (stream s homed on shard ``s % n_shards``).
      mesh:  optional ``jax.sharding.Mesh`` with a ``"fabric"`` axis of
             size ``fabric.n_shards``; the sweep then runs under
             ``shard_map`` with each device owning its home slice of
             ``cold`` and cross-shard chunk copies riding ``lax.ppermute``
             ring rotations. Without a mesh the same fabric scheduling
             model runs against the local cold pool (bit-identical).
      home_map: optional ``int32[n_pages]`` time-varying page→shard map
             (DESIGN.md §12, e.g. :meth:`PageLifecycle.home_map`): budget
             arbitration, near/far prefetch deadlines and per-NIC demand
             accounting read it instead of the static placement formula.
             The data plane still gathers from the static placement —
             migration is scheduling metadata only. ``None`` (default) is
             the exact pre-§12 path.
      comp_map: optional ``bool[n_pages]`` compressed-tier membership;
             prefetch candidates sitting compressed pay ``decompress_delay``
             extra chunk steps on their arrival deadline (the
             promote-from-compressed cost).

    Returns ``(state, info)`` with per-stream ``int32[S, n_chunks]`` counts
    ``hit`` / ``pref_hit`` / ``partial_hit`` / ``fetched`` / ``issued`` /
    ``landed`` / ``deferred`` plus the shared ``link_demand_fetches
    [n_chunks]`` and per-NIC ``shard_demand_fetches [n_chunks, n_shards]``
    (the count-granularity wire format
    :func:`repro.obs.trace.decode_sweep_events` expands into the
    page-lifecycle event log, DESIGN.md §8). After
    the sweep every valid page of ``page_rows`` is hot-resident, so
    :func:`tiered_attention` can serve decode attention from hot slots.
    """
    S, npps = page_rows.shape
    if geom.n_slots < tiered_min_slots(npps, geom):
        raise ValueError(
            f"n_slots={geom.n_slots} below tiered_min_slots("
            f"{npps} pages) = {tiered_min_slots(npps, geom)}: the swept row "
            "would not stay resident for attention")
    if async_datapath and geom.ring_size == 0:
        async_datapath = False
    if fabric is None:
        # degenerate one-shard fabric: whole budget on one NIC, every page
        # near — bit-exact reduction to the pre-§7 single-link sweep
        delay = max(geom.arrival_delay, 1)
        fabric = ShardedPoolCfg(
            n_shards=1, placement="interleave",
            link_budget=None if link_budget is None else int(link_budget),
            near_delay=delay, far_delay=delay)
    check_fabric_topology(geom.n_pages, fabric, mesh)
    C = geom.chunk
    n_chunks = -(-npps // C)
    pad = n_chunks * C - npps
    sched = jnp.concatenate(
        [page_rows.astype(jnp.int32),
         jnp.full((S, pad), NO_PAGE, jnp.int32)], axis=1)
    sched = sched.reshape(S, n_chunks, C).transpose(1, 0, 2)
    lifecycle = None
    if home_map is not None or comp_map is not None:
        if home_map is None:
            home_map = page_home(jnp.arange(geom.n_pages, dtype=jnp.int32),
                                 geom.n_pages, fabric.n_shards,
                                 fabric.placement)
        lifecycle = {"home": jnp.asarray(home_map, jnp.int32)}
        if comp_map is not None:
            lifecycle["comp"] = jnp.asarray(comp_map, bool)
    if mesh is not None and fabric.n_shards > 1:
        placed = place_cold(cold, geom.n_pages, fabric)
        if lifecycle is not None:
            return _sweep_sharded(mesh, geom, async_datapath, fabric,
                                  with_lifecycle=True,
                                  mig_delay=int(decompress_delay))(
                state, placed, sched, lifecycle)
        return _sweep_sharded(mesh, geom, async_datapath, fabric)(
            state, placed, sched)
    return _sweep_impl(state, cold, sched, geom, async_datapath, fabric,
                       False, lifecycle, int(decompress_delay))


def tiered_slot_table_local(state: dict, page_rows: jax.Array
                            ) -> tuple[jax.Array, jax.Array]:
    """Remap physical page ids to *per-stream* hot-slot ids.

    Returns ``(slot_table int32[S, npps], all_resident bool)``:
    ``slot_table[s, j]`` indexes stream s's own hot pool
    ``[n_slots, Hkv, page, dh]``, with ``-1`` for invalid page-table
    entries **and** non-resident pages — the form the fused
    :func:`repro.kernels.paged_attention.paged_attention_hot_slots` kernel
    consumes directly (its residency mask folds the ``all_resident`` guard
    into the softmax: a ``-1`` entry is masked, never silently read).
    ``all_resident`` is True iff every valid page of ``page_rows`` is
    hot-resident (a properly sized sweep guarantees it).
    """
    meta = state["pool_meta"]
    n_pages = meta["page_slot"].shape[-1]
    safe = jnp.clip(page_rows, 0, n_pages - 1)
    slots = jnp.take_along_axis(meta["page_slot"], safe, axis=1)
    valid = page_rows >= 0
    all_resident = jnp.all((slots >= 0) | ~valid)
    return jnp.where(valid, slots, -1).astype(jnp.int32), all_resident


def tiered_slot_table(state: dict, page_rows: jax.Array
                      ) -> tuple[jax.Array, jax.Array]:
    """Remap physical page ids to stacked-hot-pool slot ids.

    Returns ``(slot_table int32[S, npps], all_resident bool)``:
    ``slot_table[s, j]`` indexes the flattened ``[S * n_slots]`` hot pool
    (stream s's slots live at ``s * n_slots + slot``) — the unfused
    stacked-pool form. ``all_resident`` is the equivalence guard — True
    iff every valid page of ``page_rows`` is hot-resident (a properly
    sized sweep guarantees it; attention output for non-resident pages
    would read unrelated slot bytes).
    """
    slots, all_resident = tiered_slot_table_local(state, page_rows)
    n_slots = jax.tree.leaves(state["hot"])[0].shape[1]
    S = page_rows.shape[0]
    gslots = (jnp.arange(S, dtype=jnp.int32)[:, None] * n_slots
              + jnp.maximum(slots, 0))
    return gslots.astype(jnp.int32), all_resident


ATTN_KERNEL_MODES = ("ref", "kernel", "fused", "fused_async")


def normalize_attn_kernel(mode) -> str:
    """Normalize an ``attn_kernel`` selector to one of
    :data:`ATTN_KERNEL_MODES`. Accepts the legacy bools (``False`` →
    ``"ref"``, ``True`` → ``"kernel"``) and CLI spellings
    (``"fused-async"`` → ``"fused_async"``)."""
    if mode is True:
        return "kernel"
    if mode is False or mode is None:
        return "ref"
    m = str(mode).replace("-", "_")
    if m not in ATTN_KERNEL_MODES:
        raise ValueError(
            f"attn_kernel={mode!r} not in {ATTN_KERNEL_MODES}")
    return m


def tiered_attention(q: jax.Array, state: dict, page_rows: jax.Array,
                     lengths: jax.Array, *,
                     attn_kernel: str | bool = "ref",
                     use_kernel: bool | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """Decode attention served from the hot tier.

    ``q [S, 1, Hq, dh]``, ``lengths int32[S]``; ``attn_kernel`` selects the
    consumer (``use_kernel`` is the legacy bool alias):

    * ``"ref"`` / ``"kernel"`` — the **unfused** stacked path: the
      per-stream hot pools are copied into one flattened
      ``[S * n_slots, Hkv, page, dh]`` pool every call (a full hot-pool
      materialization) and attention runs through the remapped global
      table — identical shapes and identical bytes as the flat-pool
      :func:`repro.paging.kv_cache.paged_decode_attention`.
    * ``"fused"`` / ``"fused_async"`` — the **fused** path: attention
      reads the stacked per-stream hot pools *in place* through the local
      slot table (the ``[S, npps] → slot`` indirection composed inside the
      kernel's BlockSpec index maps), so no ``[S * n_slots, ...]`` pool is
      ever materialized; ``fused_async`` double-buffers K/V page tiles
      with explicit ``make_async_copy`` issue/wait pairs. Non-resident
      pages are masked in-kernel.

    All kernel modes execute the same per-page online-softmax op sequence,
    so on resident bytes their outputs are **bit-identical** to each other
    and to the flat-pool kernel (the tentpole equivalence pin). Returns
    ``(out [S, 1, Hq, dh], all_resident)``.
    """
    mode = normalize_attn_kernel(use_kernel if use_kernel is not None
                                 else attn_kernel)
    hot = state["hot"]
    if mode in ("fused", "fused_async"):
        table, ok = tiered_slot_table_local(state, page_rows)
        return paged_attention_hot_slots(
            q, hot["k"], hot["v"], table, lengths,
            async_copy=(mode == "fused_async")), ok
    table, ok = tiered_slot_table(state, page_rows)
    S, n_slots = hot["k"].shape[:2]
    hk = hot["k"].reshape((S * n_slots,) + hot["k"].shape[2:])
    hv = hot["v"].reshape((S * n_slots,) + hot["v"].shape[2:])
    return paged_attention(q, hk, hv, table, lengths,
                           use_kernel=(mode == "kernel")), ok


def tiered_decode_step(state: dict, cold: dict, q: jax.Array,
                       page_rows: jax.Array, lengths: jax.Array,
                       geom: TieredKV, *, async_datapath: bool = False,
                       link_budget: int | None = None,
                       fabric: ShardedPoolCfg | None = None, mesh=None,
                       attn_kernel: str | bool = False,
                       home_map: jax.Array | None = None,
                       comp_map: jax.Array | None = None,
                       decompress_delay: int = 0):
    """One tiered decode step: demand-sweep the context, attend over hot.

    ``attn_kernel`` is any :data:`ATTN_KERNEL_MODES` selector (or the
    legacy bool). Returns ``(state, out, info, all_resident)`` — see
    :func:`tiered_sweep` and :func:`tiered_attention`.
    """
    state, info = tiered_sweep(state, cold, page_rows, geom,
                               async_datapath=async_datapath,
                               link_budget=link_budget, fabric=fabric,
                               mesh=mesh, home_map=home_map,
                               comp_map=comp_map,
                               decompress_delay=decompress_delay)
    out, ok = tiered_attention(q, state, page_rows, lengths,
                               attn_kernel=attn_kernel)
    return state, out, info, ok


def tiered_invalidate(state: dict, pages: jax.Array) -> dict:
    """Drop ``pages int32[S, P]`` from each stream's hot tier + ring.

    Call after writing a cold page (``append_kv`` into the active tail
    page) so no stale hot copy or in-flight fetch of the old bytes serves
    a later attention read (write coherence, DESIGN.md §6). ``-1`` entries
    are ignored.
    """
    meta, ring = jax.vmap(lambda m, r, p: pool_invalidate(m, r, p, p >= 0))(
        state["pool_meta"], state["ring"], pages)
    return {**state, "pool_meta": meta, "ring": ring}


def tiered_reset_stream(state: dict, i: int, geom: TieredKV,
                        dtype=jnp.bfloat16) -> dict:
    """Return ``state`` with stream ``i`` cold-reset to a fresh init.

    The continuous-batching slot scheduler calls this when a finished
    sequence's slot is handed to a new request (DESIGN.md §10): the slot's
    Leap controller, pool metadata, in-flight ring and hot payload all
    restart from :func:`tiered_init` state so no stale page residency,
    in-flight fetch or trend history from the previous occupant can leak
    into the new request's stream. Other streams are untouched.
    """
    fresh = tiered_init(geom, 1, dtype)
    return jax.tree.map(lambda cur, f: cur.at[i].set(f[0]), state, fresh)


def tiered_stats(state: dict, i: int) -> dict:
    """Host-side :func:`repro.core.pool.pool_stats` of stream ``i``.

    The tiered state stacks the same ``pool_meta``/``ring`` keys as the
    multi-stream layer, so this is just
    :func:`repro.paging.prefetch_serving.stream_stats_at`.
    """
    return stream_stats_at(state, i)
