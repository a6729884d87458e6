"""Mesh-sharded cold pool: per-shard NICs, placement, near/far asymmetry.

Until now the serving path pretended the cold tier is one flat local array
behind one link. Rack-scale disaggregation has real topology: the cold pool
is *sharded* over a device mesh's ``fabric`` axis — each device owns a
``[n_pages / n_shards, ...]`` slice of every payload leaf behind its own
NIC — and a page's cost depends on *where it lives* (DESIGN.md §7):

* **Placement** maps each page id to a home shard
  (:func:`repro.core.pool.page_home`): ``"block"`` keeps contiguous id
  ranges together, ``"interleave"`` round-robins consecutive ids across
  shards. Placement is a policy knob precisely because it changes contention:
  strided multi-stream traffic hammers one block shard while interleave
  spreads the same accesses over every NIC (``benchmarks/sharded_pool.py``).
* **Per-shard link budgets** replace the single global link of §5: each
  shard's NIC moves ``link_budget`` pages/step, arbitrated demand-first by
  :func:`repro.core.pool.link_grants_sharded` — the same discipline as
  :func:`repro.core.pool.link_grants`, ranked and capped per home shard.
* **Near/far delay asymmetry**: a prefetch of a page homed on the
  consuming stream's own shard arrives after ``near_delay`` steps; a
  cross-shard prefetch rides the fabric and arrives after ``far_delay``.
  The per-candidate delay vector threads straight into
  :func:`repro.core.pool.pool_issue` deadlines.

Two data planes move the same bytes (pinned bit-equal in
``tests/test_sharded_pool.py``):

* **Flat** (no mesh): the cold pool is a local array, pages are gathered by
  plain indexing — placement/budgets/delays still shape the *metadata*
  (what lands when), so the scheduling model runs anywhere, single-device
  CPU included.
* **Sharded** (mesh with a ``fabric`` axis): the whole consume scan runs
  under ``shard_map``; each device holds its home slice
  (:func:`place_cold` permutes pages home-major so ``P('fabric')`` on the
  page axis lands every page on its home shard) and cross-shard pages move
  via a ring of ``lax.ppermute`` collective permutes — shard slices rotate
  around the fabric and every consumer picks up the pages homed on the
  currently-visiting shard.

``n_shards=1`` reduces bit-exactly to the §5 single-link path:
``repro.paging.prefetch_serving.multi_stream_consume(..., link_budget=B)``
now *delegates* here with the degenerate config, so the existing
``tests/test_link_budget.py`` pins (vmap equivalence, linkstep
cross-validation) gate this module too. The lock-step fabric mirror for
``n_shards > 1`` is :func:`repro.fabric.shardstep.run_shardstep`.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.leap_jax import leap_step_batched
from repro.core.pool import (NO_PAGE, PLACEMENTS, link_grants_sharded,
                             page_home, page_local, pool_invalidate,
                             pool_issue, pool_wait, tier_demote,
                             tier_heat_decay, tier_init, tier_migrate,
                             tier_promote, tier_touch)
from repro.paging.lifecycle import (MigrationCfg, propose_migrations,
                                    resolve, revalidate_proposals,
                                    select_demotions)


@dataclasses.dataclass(frozen=True)
class ShardedPoolCfg:
    """Static fabric topology of the sharded cold pool.

    Attributes:
      n_shards:    devices the cold pool's page axis is sharded over (one
                   NIC each). ``1`` is the degenerate single-link fabric.
      placement:   page -> home shard policy, ``"block"`` or
                   ``"interleave"`` (:func:`repro.core.pool.page_home`).
      link_budget: pages/step *each shard's NIC* can move (demand-first,
                   DESIGN.md §5 per shard). ``None`` = infinite NICs —
                   only the delay asymmetry is modeled.
      near_delay:  prefetch arrival delay (steps) from the consumer's own
                   shard.
      far_delay:   arrival delay for cross-shard prefetches (>= near).
    """
    n_shards: int = 1
    placement: str = "interleave"
    link_budget: int | None = None
    near_delay: int = 1
    far_delay: int = 2

    def __post_init__(self):
        if self.placement not in PLACEMENTS:
            raise ValueError(f"placement must be one of {PLACEMENTS}, "
                             f"got {self.placement!r}")
        if self.n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        if not 1 <= self.near_delay <= self.far_delay:
            raise ValueError("need 1 <= near_delay <= far_delay "
                             f"(got {self.near_delay}/{self.far_delay})")


def stream_homes(n_streams: int, n_shards: int) -> jax.Array:
    """Home shard of each stream: ``s % n_shards`` (fixed round-robin —
    the lock-step mirror uses the same mapping)."""
    return jnp.mod(jnp.arange(n_streams, dtype=jnp.int32), n_shards)


def place_perm(n_pages: int, fabric: ShardedPoolCfg) -> np.ndarray:
    """Permutation putting pages in home-major order.

    ``placed[i] = cold[perm[i]]``: shard g's slice ``[g*pps, (g+1)*pps)``
    of the placed array holds exactly the pages homed on g, each at its
    :func:`repro.core.pool.page_local` index — so sharding the placed
    array's page axis over the ``fabric`` mesh axis gives every page to
    its home shard.
    """
    if n_pages % fabric.n_shards:
        raise ValueError(f"n_pages={n_pages} not divisible by "
                         f"n_shards={fabric.n_shards}")
    pages = np.arange(n_pages)
    pps = n_pages // fabric.n_shards
    if fabric.placement == "interleave":
        home, local = pages % fabric.n_shards, pages // fabric.n_shards
    else:
        home, local = pages // pps, pages % pps
    perm = np.empty(n_pages, np.int64)
    perm[home * pps + local] = pages
    return perm


def place_cold(cold, n_pages: int, fabric: ShardedPoolCfg):
    """Permute every payload leaf's page axis into home-major order."""
    perm = jnp.asarray(place_perm(n_pages, fabric))
    return jax.tree.map(lambda c: c[perm], cold)


def check_fabric_topology(n_pages: int, fabric: ShardedPoolCfg,
                          mesh=None) -> None:
    """Shared entry-point validation: the pool must split evenly over the
    shards, and a mesh (if given) must carry a matching ``fabric`` axis.
    One implementation so every §7 entry point rejects with the same
    message."""
    if n_pages % fabric.n_shards:
        raise ValueError(f"n_pages={n_pages} not divisible by "
                         f"n_shards={fabric.n_shards}")
    if mesh is not None and fabric.n_shards > 1 \
            and mesh.shape.get("fabric") != fabric.n_shards:
        raise ValueError(f"mesh fabric axis {mesh.shape.get('fabric')} != "
                         f"n_shards {fabric.n_shards}")


# --------------------------------------------------------------------------
# data planes
# --------------------------------------------------------------------------
def _gather_flat(cold, pages: jax.Array):
    """Plain local gather (single-device cold pool, original page order)."""
    safe = jnp.maximum(pages, 0)
    return jax.tree.map(lambda c: c[safe], cold)


def fabric_ring_gather(buf: jax.Array, local: jax.Array, homes: jax.Array,
                       n_shards: int, pick) -> jax.Array:
    """One-leaf collective gather over the ``fabric`` axis (inside shard_map).

    Ring algorithm: the home slice ``buf`` rotates one hop per round via
    ``lax.ppermute``; at round r every device is visited by shard
    ``(me - r) % n_shards``'s slice and keeps the entries homed there
    (``homes``), read at their within-shard ``local`` indices by
    ``pick(buf, local)`` — a plain ``buf[local]`` for jnp gathers, or one
    of the :mod:`repro.kernels.gather_pages` kernels so the bytes still
    move through the DMA-pipelined gather within each round. After
    ``n_shards`` rounds every device holds all requested entries — the
    replicated result the (replicated) metadata scan consumes, bit-
    identical to the flat gather on the unplaced pool. This is the single
    implementation of the §7 ring discipline — the stream consume and the
    tiered sweep both ride it, so their bit-equivalence pins share one
    rotation order.
    """
    me = jax.lax.axis_index("fabric")
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    out = None
    for r in range(n_shards):
        take = homes == jnp.mod(me - r, n_shards)
        picked = pick(buf, local)
        mask = take.reshape(take.shape + (1,) * (picked.ndim - take.ndim))
        out = jnp.where(mask, picked, 0 if out is None else out)
        if r < n_shards - 1:
            buf = jax.lax.ppermute(buf, "fabric", perm)
    return out


def _gather_fabric(cold_local, pages: jax.Array, n_pages: int,
                   fabric: ShardedPoolCfg):
    """Collective gather of ``pages`` from the sharded cold pool: the
    :func:`fabric_ring_gather` ring with plain indexing per leaf."""
    G = fabric.n_shards
    pps = n_pages // G
    home = page_home(pages, n_pages, G, fabric.placement)
    local = jnp.clip(page_local(pages, n_pages, G, fabric.placement),
                     0, pps - 1)
    return jax.tree.map(
        lambda c: fabric_ring_gather(c, local, home, G,
                                     lambda b, ix: b[ix]), cold_local)


def scatter_hot(hot, data, dst: jax.Array, mask: jax.Array):
    """Scatter gathered page payloads (leaves ``[S, K, ...page]``) into the
    stacked ``[S, n_slots, ...]`` hot pool at per-stream slots ``dst
    [S, K]``; masked-out entries scatter out of bounds and drop. The single
    OOB-drop scatter discipline — the stream consume and the tiered sweep
    both apply their copy plans through it."""
    S, n_slots = jax.tree.leaves(hot)[0].shape[:2]
    gdst = (jnp.arange(S, dtype=jnp.int32)[:, None] * n_slots
            + jnp.maximum(dst, 0)).reshape(-1)
    gdst = jnp.where(mask.reshape(-1), gdst, S * n_slots)

    def one(h, d):
        flat = h.reshape((S * n_slots,) + h.shape[2:])
        d = d.reshape((-1,) + d.shape[2:])
        return flat.at[gdst].set(d.astype(h.dtype),
                                 mode="drop").reshape(h.shape)

    return jax.tree.map(one, hot, data)


# --------------------------------------------------------------------------
# the sharded consume scan
# --------------------------------------------------------------------------
def _consume_impl(cold, schedules: jax.Array, geom, fabric: ShardedPoolCfg,
                  sharded: bool, chaos=None, migration=None):
    """Lock-step multi-stream consume over the (possibly sharded) cold pool.

    Generalizes the §5 budgeted scan (DESIGN.md §5 -> §7): per-step,

    1. **Grant** — shard g's NIC moved last step's demand fetches homed on
       g first, so its prefetch landing capacity is
       ``max(0, link_budget - demand_on_g[t-1])``; grants go to due ring
       entries homed on g in ascending global ``seq``
       (:func:`repro.core.pool.link_grants_sharded`).
    2. **Wait/serve** — per-stream metadata-only
       :func:`repro.core.pool.pool_wait` with the grant mask; the copy
       plan (landings + demand fetch) is applied by the data plane (flat
       gather, or ring-``ppermute`` collective gather when ``sharded``).
    3. **Issue** — controllers emit candidates; each is stamped with the
       global ``seq`` and a *distance-dependent* deadline: ``near_delay``
       if its home shard is the stream's own, else ``far_delay``.

    ``fabric.n_shards == 1`` with ``near_delay == geom.arrival_delay``
    reduces bit-exactly to the single-link §5 scan.

    ``chaos`` (a static :class:`repro.fabric.chaos.ChaosSpec`, DESIGN.md §9)
    injects faults without touching the clean path (``None`` compiles the
    exact scan above). With a spec, the step order becomes: node-death
    invalidation -> per-shard grants against the *per-step* budget table ->
    wait -> EWMA estimator update from this step's landings -> demand
    accounting and issue against the re-homed page->shard map, with
    physical delays dilated by the slowdown table, deadlines either static
    or estimator-driven, and issues capped by the elastic grant table. The
    estimator state ``est_q int32[S, G]`` rides the scan carry and is
    returned as ``info["est_q"]``.

    ``migration`` (a static :class:`repro.paging.lifecycle.MigrationCfg`,
    DESIGN.md §12) turns on the three-tier lifecycle: the page->home map
    becomes the time-varying ``tier["home"]`` table riding the scan carry,
    and each step grows the phases

    * **heat decay** then, at the grant phase, **migration grants**: last
      step's trend-driven proposals are re-validated (cooldown, still
      cross-shard, lowest-seq-wins dedupe) and granted out of each source
      NIC's capacity *left after every prefetch grant* — the third, lowest
      §5 class (:func:`repro.core.pool.link_grants_sharded`). A grant
      re-homes the page immediately, so this step's issues already see it
      near. Like chaos re-homing, migration moves *scheduling metadata
      only* — the data plane keeps gathering from the static physical
      placement.
    * **promote** after the wait: any landing or demand fetch of a
      compressed page clears its compressed bit (counted against the
      start-of-step snapshot, per stream); **heat touch** on the demand
      pages.
    * **issue** charges ``decompress_delay`` extra steps on candidates
      whose cold bytes are compressed; after the issue, capacity-driven
      **demotion** compresses the coldest eligible pages while the
      uncompressed population exceeds ``far_capacity``, and the updated
      trend proposes next step's migrations.

    With chaos node loss, death re-homes the *dynamic* table (every page
    currently homed on the dead shard, migrated-in pages included, is
    invalidated and re-homed by the §9 rule) and carried proposals
    targeting the dead shard are dropped and pollution-counted.
    ``migration=None`` compiles the exact two-tier scan above.
    """
    from repro.paging.prefetch_serving import stream_init

    S, T = schedules.shape
    K = geom.pw_max
    G = fabric.n_shards
    n_pages = geom.n_pages
    budget = fabric.link_budget
    homes_s = stream_homes(S, G)
    stream_ids = jnp.arange(S, dtype=jnp.int32)
    gather = (functools.partial(_gather_fabric, n_pages=n_pages,
                                fabric=fabric) if sharded else _gather_flat)

    mig = resolve(migration)

    cz = None
    if chaos is not None:
        from repro.fabric.chaos import (EST_ONE, compile_chaos, est_init,
                                        est_step)
        cz = compile_chaos(chaos, n_steps=T, n_streams=S, n_shards=G,
                           n_pages=n_pages, placement=fabric.placement,
                           base_budget=budget)
        dil_t = jnp.asarray(cz["dilation"])        # [T, G]
        bud_t = jnp.asarray(cz["budget"])          # [T, G]
        grant_t = jnp.asarray(cz["grant"])         # [T, S]
        home_tab = jnp.asarray(cz["home"])         # [2, n_pages]
        t_fail = cz["t_fail"]
        dead = (jnp.asarray(cz["dead_pages"]) if t_fail is not None else None)
        est0 = jnp.asarray(est_init(S, G, fabric.near_delay,
                                    fabric.far_delay))

    if mig is not None:
        tier0 = tier_init(n_pages, G, fabric.placement)
        M = mig.mig_per_stream
        pend0 = (jnp.zeros((S, M), jnp.int32), jnp.zeros((S, M), jnp.int32),
                 jnp.zeros((S, M), jnp.bool_), jnp.zeros((S, M), jnp.int32))
        dead_g = rehome_vec = None
        if cz is not None and cz["t_fail"] is not None:
            from repro.fabric.chaos import rehome_shard
            dead_g = int(chaos.node_loss[0])
            rehome_vec = jnp.asarray(np.array(
                [rehome_shard(p, dead_g, dead_g, G) for p in range(n_pages)],
                np.int32))

    # payload_like trailing shapes are per-page, hence shard-invariant —
    # the local [pps, ...] slice seeds the same hot-buffer layout the full
    # [n_pages, ...] pool would.
    one = (stream_init(geom, cold.dtype) if isinstance(cold, jax.Array)
           else stream_init(geom, payload_like=cold))
    state0 = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (S,) + x.shape), one)

    def _wait(meta, ring, page, now, ok):
        return pool_wait(meta, ring, None, None, page, now, land_ok=ok)

    def _issue(meta, ring, cands, val, now, seq, delay):
        return pool_issue(meta, ring, cands, val, now, delay, seq=seq)

    def _issue_chaos(meta, ring, cands, val, now, seq, delay, true_delay,
                     quota):
        return pool_issue(meta, ring, cands, val, now, delay, seq=seq,
                          true_delay=true_delay, quota=quota)

    def body(carry, xs):
        if mig is not None:
            carry, tier, pend = carry[:-2], carry[-2], carry[-1]
        if cz is None:
            state, d_prev = carry                  # d_prev: int32[G]
        else:
            state, d_prev, est_q = carry           # est_q: int32[S, G]
        t, pages = xs
        meta, ring, hot = state["pool_meta"], state["ring"], state["hot"]
        now = ring["now"]                          # int32[S], == t

        if mig is not None:
            # Dynamic scheduling home map. Chaos node death re-homes the
            # *current* table (migrated-in pages included) by the §9 rule
            # and invalidates everything homed on the dying shard; the data
            # plane still gathers from the static physical placement.
            if cz is not None and cz["t_fail"] is not None:
                on_dead = tier["home"] == dead_g
                kill = jnp.broadcast_to(t == cz["t_fail"],
                                        (n_pages,)) & on_dead
                all_pages = jnp.arange(n_pages, dtype=jnp.int32)
                meta, ring = jax.vmap(
                    lambda m, r: pool_invalidate(m, r, all_pages, kill))(
                        meta, ring)
                tier = dict(tier)
                tier["home"] = jnp.where(kill, rehome_vec, tier["home"])
            tier = tier_heat_decay(tier)
            comp_pre = tier["comp"]                # start-of-step snapshot

            def _home(x):
                # Reads the *current* binding of ``tier``: the grant phase
                # below rebinds it, so homes seen after the migration grant
                # (demand accounting, issue delays) already reflect this
                # step's grants — the twin mirrors this order.
                return tier["home"][jnp.clip(x, 0, n_pages - 1)]
        elif cz is None:
            def _home(x):
                return page_home(x, n_pages, G, fabric.placement)
        else:
            # Scheduling home map, re-homed from the death step on. The
            # data plane below keeps gathering from the physical placement
            # (the survivor serves a replica): re-homing is metadata only.
            if cz["t_fail"] is None:
                hv = home_tab[0]
            else:
                hv = jnp.where(t >= cz["t_fail"], home_tab[1], home_tab[0])

            def _home(x):
                return hv[jnp.clip(x, 0, n_pages - 1)]

            if cz["t_fail"] is not None:
                # Node death at the top of the step: the dead shard's
                # resident prefetches and in-flight fetches are lost
                # (pollution); freed slots recycle through the free stack.
                kill = jnp.broadcast_to(t == cz["t_fail"], dead.shape)
                meta, ring = jax.vmap(
                    lambda m, r: pool_invalidate(m, r, dead, kill))(meta, ring)

        # --- per-shard landing grants (leftover NIC budget, global seq) -----
        if mig is not None:
            # Prefetch grants rank against the pre-grant home map; granted
            # migrations re-home immediately, so everything downstream
            # (demand accounting, issue delays) sees the post-grant map.
            mp, md, mv0, msq = pend
            mv, msrc = revalidate_proposals(mp, md, mv0, msq, tier, t, mig)
            if cz is not None and cz["t_fail"] is not None:
                # Carried proposals that crossed the death step targeting
                # the dead shard: dropped and pollution-counted (per
                # proposing stream), like any other wasted transfer.
                dead_hit = mv & (md == dead_g) & (t >= cz["t_fail"])
                meta = dict(meta)
                meta["n_pollution"] = meta["n_pollution"] + jnp.sum(
                    dead_hit.astype(jnp.int32), axis=1)
                mv = mv & ~dead_hit
            if cz is not None:
                caps = jnp.maximum(bud_t[t] - d_prev, 0)
            elif budget is not None:
                caps = jnp.maximum(jnp.int32(budget) - d_prev, 0)
            else:
                caps = None
            homes_ring = _home(ring["page"])
            if caps is None:
                allowed = jnp.ones(ring["page"].shape, bool)
                mig_ok = mv
                pf_on_g = jnp.zeros((G,), jnp.int32)
            else:
                allowed, mig_ok = link_grants_sharded(
                    ring, now, caps, homes_ring, msrc, mv, msq)
                pf_on_g = jnp.zeros((G,), jnp.int32).at[
                    jnp.clip(homes_ring.reshape(-1), 0, G - 1)].add(
                        allowed.reshape(-1).astype(jnp.int32))
            tier = tier_migrate(tier, mp.reshape(-1), md.reshape(-1),
                                mig_ok.reshape(-1), t)
            migrated_s = jnp.sum(mig_ok.astype(jnp.int32), axis=1)
            mig_on_g = jnp.zeros((G,), jnp.int32).at[
                jnp.clip(msrc.reshape(-1), 0, G - 1)].add(
                    mig_ok.reshape(-1).astype(jnp.int32))
        elif cz is not None:
            caps = jnp.maximum(bud_t[t] - d_prev, 0)
            allowed = link_grants_sharded(ring, now, caps, _home(ring["page"]))
        elif budget is None:
            allowed = jnp.ones(ring["page"].shape, bool)
        else:
            caps = jnp.maximum(jnp.int32(budget) - d_prev, 0)
            homes_ring = page_home(ring["page"], n_pages, G, fabric.placement)
            allowed = link_grants_sharded(ring, now, caps, homes_ring)
        # --- wait/serve (metadata-only; copy plan applied below) ------------
        deferred0 = meta["n_deferred"]
        meta, ring, _, slot, _, winfo = jax.vmap(_wait)(
            meta, ring, pages, now, allowed)
        if cz is not None:
            # EWMA update from this step's landings: obs = realized delay,
            # bucketed per (stream, home shard), order-independent batch
            # form (DESIGN.md §9) so the Python twin folds identically.
            lp, li = winfo["landed_pages"], winfo["landed_issued"]
            lmask = lp >= 0
            homes_l = jnp.where(lmask, _home(lp), G)     # G = drop row
            rows = jnp.broadcast_to(stream_ids[:, None], lp.shape)
            obs = jnp.where(lmask, now[:, None] - li, 0).astype(jnp.int32)
            obs_sum = jnp.zeros((S, G), jnp.int32).at[rows, homes_l].add(
                obs, mode="drop")
            cnt = jnp.zeros((S, G), jnp.int32).at[rows, homes_l].add(
                lmask.astype(jnp.int32), mode="drop")
            est_q = jnp.where(cnt > 0,
                              est_step(est_q, obs_sum, jnp.maximum(cnt, 1)),
                              est_q)
        homes_d = _home(pages)
        d_t = jnp.zeros((G,), jnp.int32).at[homes_d].add(
            winfo["fetched"].astype(jnp.int32), mode="drop")
        # --- promote on bytes moved + demand heat (DESIGN.md §12) -----------
        if mig is not None:
            if mig.compressed:
                # Any landing or demand fetch of a compressed page promotes
                # it; counted against the start-of-step snapshot so the
                # per-stream attribution is order-independent (clearing the
                # bit is idempotent).
                lp = winfo["landed_pages"]
                prom_land = (winfo["landed"]
                             & comp_pre[jnp.clip(lp, 0, n_pages - 1)])
                prom_dem = (winfo["fetched"]
                            & comp_pre[jnp.clip(pages, 0, n_pages - 1)])
                promoted_s = (jnp.sum(prom_land.astype(jnp.int32), axis=1)
                              + prom_dem.astype(jnp.int32))
                moved = jnp.concatenate([lp.reshape(-1), pages])
                moved_ok = jnp.concatenate(
                    [winfo["landed"].reshape(-1), winfo["fetched"]])
                tier, _ = tier_promote(tier, moved, moved_ok, comp_pre)
            else:
                promoted_s = jnp.zeros((S,), jnp.int32)
            tier = tier_touch(tier, pages, (pages >= 0) & (pages < n_pages),
                              mig.heat_access)
        # --- controllers + globally ordered, distance-delayed issue ---------
        pref_feedback = winfo["prefetched_hit"] | winfo["partial_hit"]
        new_leap, cands, valid = leap_step_batched(
            state["leap"], pages, pref_feedback,
            n_split=geom.n_split, pw_max=geom.pw_max)
        val = valid & (cands >= 0) & (cands < n_pages)
        seq = ((t * S + stream_ids)[:, None] * K
               + jnp.arange(K, dtype=jnp.int32)[None, :])
        homes_c = _home(cands)
        base = jnp.where(homes_c == homes_s[:, None],
                         jnp.int32(fabric.near_delay),
                         jnp.int32(fabric.far_delay))
        if mig is not None and mig.compressed:
            # Promote-from-compressed pays the codec: extra steps on top of
            # the wire delay (dilation multiplies the wire only).
            sur = (tier["comp"][jnp.clip(cands, 0, n_pages - 1)]
                   .astype(jnp.int32) * jnp.int32(mig.decompress_delay))
        else:
            sur = None
        issued0 = meta["n_prefetch_issued"]
        if cz is None:
            delay_v = base if sur is None else base + sur
            meta, ring = jax.vmap(_issue)(meta, ring, cands, val, now, seq,
                                          delay_v)
        else:
            true_delay = base * dil_t[t][homes_c]
            if sur is not None:
                true_delay = true_delay + sur
            if chaos.adaptive_deadline:
                rows_c = jnp.broadcast_to(stream_ids[:, None], homes_c.shape)
                eg = est_q[rows_c, homes_c]
                deadline = jnp.maximum(1, (eg + EST_ONE // 2) // EST_ONE)
            else:
                deadline = base if sur is None else base + sur
            # Elastic grant: cap the stream's unconsumed-resident +
            # in-flight footprint; issues beyond the cap are drops.
            res_unused = jnp.sum((meta["slot_page"] >= 0)
                                 & meta["slot_prefetched"]
                                 & ~meta["slot_consumed"], axis=1)
            occ = jnp.sum(ring["page"] >= 0, axis=1)
            quota = jnp.maximum(grant_t[t] - res_unused - occ, 0)
            meta, ring = jax.vmap(_issue_chaos)(
                meta, ring, cands, val, now, seq, deadline, true_delay, quota)
        ring = dict(ring)
        ring["now"] = now + 1
        issued_s = meta["n_prefetch_issued"] - issued0
        deferred_s = meta["n_deferred"] - deferred0
        # --- demote the coldest + propose next step's migrations ------------
        if mig is not None:
            if mig.compressed:
                dpages, dok = select_demotions(tier, t, mig)
                tier = tier_demote(tier, dpages, dok, t)
                demoted_t = jnp.sum(dok.astype(jnp.int32))
            else:
                demoted_t = jnp.int32(0)
            mp2, md2, mv2, msq2 = propose_migrations(
                new_leap, pages, homes_s, tier, t, n_pages, K, mig)
            if cz is not None and cz["t_fail"] is not None:
                mv2 = mv2 & ~((md2 == dead_g) & (t >= cz["t_fail"]))
            pend = (mp2, md2, mv2, msq2)
        landed_s = jnp.sum(winfo["landed"].astype(jnp.int32), axis=1)
        # --- data plane: replay the copy plan (landings, then demand) -------
        src = jnp.concatenate(
            [winfo["landed_pages"],
             jnp.where(winfo["fetched"], pages, NO_PAGE)[:, None]], axis=1)
        dst = jnp.concatenate([winfo["landed_slots"], slot[:, None]], axis=1)
        msk = jnp.concatenate([winfo["landed"],
                               winfo["fetched"][:, None]], axis=1)
        data = gather(cold, src)                   # [S, R+1, ...page]
        hot = scatter_hot(hot, data, dst, msk)
        served = jax.tree.map(
            lambda h: h[stream_ids, jnp.maximum(slot, 0)], hot)
        sums = sum(jax.tree.leaves(jax.tree.map(
            lambda d: d.reshape(S, -1).sum(-1), served)))
        state = {"leap": new_leap, "pool_meta": meta, "hot": hot,
                 "ring": ring}
        outs = (sums, winfo["hit"], winfo["prefetched_hit"],
                winfo["partial_hit"], winfo["fetched"], issued_s, landed_s,
                deferred_s, d_t, jnp.sum(issued_s), jnp.sum(deferred_s))
        carry = ((state, d_t) if cz is None else (state, d_t, est_q))
        if mig is not None:
            carry = carry + (tier, pend)
            outs = outs + (migrated_s, promoted_s, demoted_t, mig_on_g,
                           pf_on_g)
        return carry, outs

    xs = (jnp.arange(T, dtype=jnp.int32), schedules.T)
    carry0 = ((state0, jnp.zeros((G,), jnp.int32)) if cz is None
              else (state0, jnp.zeros((G,), jnp.int32), est0))
    if mig is not None:
        carry0 = carry0 + (tier0, pend0)
    final, outs = jax.lax.scan(body, carry0, xs)
    (sums, hit, pref, part, fetched, issued, landed, deferred,
     shard_d, link_i, link_def) = outs[:11]
    state = final[0]
    info = {"hit": hit.T, "pref_hit": pref.T, "partial_hit": part.T,
            "fetched": fetched.T, "issued": issued.T, "landed": landed.T,
            "deferred": deferred.T,
            "shard_demand_fetches": shard_d,           # [T, G]
            "link_demand_fetches": shard_d.sum(axis=1),
            "link_prefetch_issued": link_i, "link_deferred": link_def}
    if cz is not None:
        info["est_q"] = final[2]                       # int32[S, G]
    if mig is not None:
        migd, promd, demd, mig_g, pf_g = outs[11:]
        info["migrated"] = migd.T                      # [S, T]
        info["promoted"] = promd.T                     # [S, T]
        info["demoted"] = demd                         # [T]
        info["mig_on_shard"] = mig_g                   # [T, G]
        info["pf_on_shard"] = pf_g                     # [T, G]
        state = dict(state, tier=final[len(final) - 2])
    return state, sums.T, info


@functools.partial(jax.jit,
                   static_argnames=("geom", "fabric", "chaos", "migration"))
def _consume_flat(cold, schedules, geom, fabric, chaos=None, migration=None):
    return _consume_impl(cold, schedules, geom, fabric, sharded=False,
                         chaos=chaos, migration=migration)


_SHARD_MAP_CACHE: dict = {}


def cached_shard_map(key: tuple, make_fn, in_specs):
    """Memoized ``jax.jit(shard_map(...))`` wrapper for one static topology.

    The single implementation of the §7 wrap idiom (cold sharded over the
    ``fabric`` axis, every other input and all outputs replicated,
    ``check_vma=False`` because the replication of the metadata scan is by
    construction, not provable) — the stream consume and the tiered sweep
    both build their mesh runners through it. ``key`` must start with the
    mesh and include a caller tag plus every static config the wrapped
    ``make_fn()`` closes over; entries live for the process, like jit's
    own executable cache.
    """
    from jax.sharding import PartitionSpec as P

    if key not in _SHARD_MAP_CACHE:
        _SHARD_MAP_CACHE[key] = jax.jit(jax.shard_map(
            make_fn(), mesh=key[0], in_specs=in_specs, out_specs=P(),
            check_vma=False))
    return _SHARD_MAP_CACHE[key]


def _consume_sharded_fn(mesh, geom, fabric: ShardedPoolCfg, chaos=None,
                        migration=None):
    """The jitted shard_map consume for one topology (memoized)."""
    from jax.sharding import PartitionSpec as P

    return cached_shard_map(
        (mesh, "consume", geom, fabric, chaos, migration),
        lambda: functools.partial(_consume_impl, geom=geom, fabric=fabric,
                                  sharded=True, chaos=chaos,
                                  migration=migration),
        (P("fabric"), P()))


def sharded_multi_stream_consume(cold, schedules: jax.Array, geom,
                                 fabric: ShardedPoolCfg, mesh=None,
                                 chaos=None, migration=None):
    """Concurrent streams over a mesh-sharded cold pool.

    Args:
      cold: ``[n_pages, page_elems]`` payload array or pytree of
        ``[n_pages, ...]`` leaves, in *original page-id order* (placement
        permutation is internal).
      schedules: ``int32[n_streams, T]`` demand page ids per stream.
      geom: :class:`repro.paging.prefetch_serving.PrefetchedStream`; the
        async issue/wait path is implied (``ring_size`` must be > 0) —
        per-NIC budgets arbitrate *landings*, which only exist with a ring.
      fabric: :class:`ShardedPoolCfg` topology.
      mesh: optional ``jax.sharding.Mesh`` with a ``"fabric"`` axis of size
        ``fabric.n_shards``; when given (and ``n_shards > 1``) the scan
        runs under ``shard_map`` — each device owns its home slice of
        ``cold`` and cross-shard pages move by ``lax.ppermute`` ring
        rotations. Without a mesh the same scheduling model runs against a
        local cold pool (bit-identical results, pinned).
      chaos: optional static :class:`repro.fabric.chaos.ChaosSpec` fault
        schedule (DESIGN.md §9). Adds ``info["est_q"] int32[S, n_shards]``
        (final Q8 deadline estimates). ``None`` = the clean fabric.
      migration: optional static
        :class:`repro.paging.lifecycle.MigrationCfg` (DESIGN.md §12) —
        turns on the three-tier lifecycle (online migration under the
        third §5 grant class, optionally a compressed cold tier). Adds
        ``info`` keys ``migrated``/``promoted`` ``int32[S, T]``,
        ``demoted int32[T]``, ``mig_on_shard``/``pf_on_shard``
        ``int32[T, n_shards]`` (per-NIC migration / prefetch grants — the
        demand-never-displaced witness), and the final lifecycle tables as
        ``state["tier"]``. ``None`` (or ``enabled=False``) compiles the
        exact two-tier path.

    Returns ``(state, data_sums, info)`` exactly like the §5 budgeted
    ``multi_stream_consume`` with additionally ``info["shard_demand_fetches"]
    int32[T, n_shards]`` (per-NIC demand traffic). Stream s is homed on
    shard ``s % n_shards`` (:func:`stream_homes`).
    """
    if geom.ring_size <= 0:
        raise ValueError("sharded consume needs the async issue/wait ring "
                         "(geom.ring_size > 0)")
    check_fabric_topology(geom.n_pages, fabric, mesh)
    migration = resolve(migration)
    if mesh is not None and fabric.n_shards > 1:
        placed = place_cold(cold, geom.n_pages, fabric)
        return _consume_sharded_fn(mesh, geom, fabric, chaos,
                                   migration)(placed, schedules)
    return _consume_flat(cold, schedules, geom, fabric, chaos, migration)
