"""Continuous-batching serving engine over the tiered paged-KV data path.

The step executor that turns every prior subsystem into a servable engine
(DESIGN.md §10). One engine step:

1. **Admit** — arrived requests enter free slots under the capacity-
   reserving policy (:class:`repro.serving.scheduler.SlotScheduler`);
   arrivals come from a seeded :class:`repro.fabric.tenants.ArrivalProcess`
   (constant / bursty / churn), quantized onto the step clock.
2. **Model work** — PREFILL slots consume up to ``prefill_chunk`` prompt
   tokens (chunked prefill: long prompts never stall in-flight decode);
   DECODE slots emit one token. Every produced K/V lands in the cold paged
   pool at its request's allocator-assigned page (incremental page growth).
3. **Data path** — written pages are invalidated in every stream's hot
   tier (write coherence, §6), then all decoding slots sweep their context
   pages through the Leap-managed hot pools in one
   :func:`repro.paging.tiered_kv.tiered_sweep` over the *dynamic* batch
   composition (idle slots sweep nothing — fixed shapes, ``-1`` rows), and
   hot-slot attention is pinned **bit-identical** to the flat-pool
   reference for every active row (§6.4 — the pin survives dynamic
   batches because both sides read the same page table rows and lengths).
4. **Evict** — finished requests recycle their pages through
   ``PageAllocator.recycle``, their slot's stream state cold-resets
   (:func:`tiered_reset_stream`), and their counters fold into the
   per-slot base so the §8 event-totals pin spans slot reuse.

Per-request TTFT and token-latency ladders ride
:class:`repro.obs.metrics.Registry`; the request lifecycle is exported as
its own Perfetto track keyed by request id (slot-reuse-proof), next to the
per-stream page-lifecycle tracks.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.fabric.tenants import ArrivalProcess
from repro.obs.metrics import Registry
from repro.obs.trace import (Event, RequestPhase, decode_sweep_events,
                             events_to_counts, summary_events)
from repro.paging.kv_cache import (PageAllocator, init_paged_kv,
                                   paged_decode_attention)
from repro.paging.sharded_pool import ShardedPoolCfg
from repro.paging.tiered_kv import (TieredKV, normalize_attn_kernel,
                                    tiered_attention, tiered_init,
                                    tiered_invalidate, tiered_min_slots,
                                    tiered_reset_stream, tiered_stats,
                                    tiered_sweep)

from .request import DECODE, PREFILL, Request
from .scheduler import AdmissionQueue, SlotScheduler

#: event-type totals pinned bit-exact against the pool counters whenever a
#: trace is decoded (DESIGN.md §8.2) — same contract as the batch driver
PINNED_COUNTERS = ("hits", "misses", "partial_hits", "prefetch_hits",
                   "prefetch_issued", "deferred", "ring_drops", "pollution")


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Static configuration of one continuous-batching serving run."""

    requests: int = 8
    slots: int = 4
    prompt_len: int = 32
    gen: int = 16
    #: per-request length heterogeneity: request i draws prompt/gen
    #: uniformly from [ceil(len*(1-jitter)), len] (seeded). 0 = uniform.
    length_jitter: float = 0.0
    #: tokens per KV page; a multiple of 16 keeps each bf16 (page, dh)
    #: tile whole under the TPU tiling the Pallas kernels DMA
    page_size: int = 16
    prefill_chunk: int = 8        # prompt tokens per engine step per slot
    chunk: int = 4                # sweep demand pages per chunk step
    ring_size: int = 8
    async_datapath: bool = False
    link_budget: int | None = None
    shards: int = 1
    placement: str = "interleave"
    far_delay: int = 2
    use_kernel: bool = True
    #: decode-attention consumer: "ref" | "kernel" (unfused stacked hot
    #: pool) | "fused" | "fused_async" (in-place hot-slot kernel — no
    #: stacked-pool copy). The §6.4 flat pin runs against the matching
    #: flat-pool implementation (ref vs ref, kernel vs kernel) so the
    #: comparison stays bit-identical.
    attn_kernel: str = "ref"
    # arrival process (request-level, quantized to the step clock)
    arrival: str = "bursty"       # constant | bursty | churn
    think_time: float = 1000.0    # µs between arrivals
    burst_len: int = 4
    idle_time: float = 4000.0
    churn_every: int = 3
    churn_downtime: float = 6000.0
    step_us: float = 1000.0
    seed: int = 0
    # admission mode: False = continuous; True = lock-step gang admission
    # (the fixed-batch baseline benchmarks/serving.py compares against)
    gang: bool = False
    pool_pages: int | None = None
    trace: bool = False
    #: three-tier page lifecycle (DESIGN.md §12): a
    #: repro.paging.lifecycle.MigrationCfg, or None / enabled=False for the
    #: exact two-tier engine. The host-side PageLifecycle mirror runs
    #: between steps: trend-driven hot-ward migration re-homes pages toward
    #: their consumer's shard (scheduling only — budgets, deadlines, NIC
    #: accounting), and with cfg.compressed the coldest pages round-trip
    #: through the int8 page codec at demote time (stale hot copies
    #: invalidated, so the §6.4 flat/tiered bit-identity pin keeps holding
    #: — both sides read the same post-roundtrip cold bytes).
    migration: object = None

    def arrival_process(self) -> ArrivalProcess:
        return ArrivalProcess(kind=self.arrival, think_time=self.think_time,
                              burst_len=self.burst_len,
                              idle_time=self.idle_time,
                              churn_every=self.churn_every,
                              churn_downtime=self.churn_downtime)


class ServingEngine:
    """Request-lifecycle serving over the tiered paged-KV data path.

    ``executor`` is a :class:`repro.serving.executor.ModelExecutor` or
    :class:`repro.serving.executor.SyntheticExecutor`; the engine only
    assumes ``begin/end``, ``prefill_chunk``, ``decode`` and the
    ``n_kv_heads / head_dim / dtype`` payload attributes.
    """

    def __init__(self, config: ServeConfig, executor, mesh=None):
        self.cfg = config
        self.ex = executor
        c = config
        self.npps = -(-(c.prompt_len + c.gen) // c.page_size)
        hkv, dh = executor.n_kv_heads, executor.head_dim
        # the sweep's residency floor, uncapped (a pool smaller than this
        # cannot host a hot tier the lazy LRU won't cannibalize mid-batch)
        floor = tiered_min_slots(
            self.npps, TieredKV(1 << 30, 1, c.page_size, hkv, dh,
                                chunk=c.chunk, ring_size=c.ring_size))
        if c.pool_pages is not None and c.pool_pages < floor:
            raise ValueError(f"pool_pages={c.pool_pages} is below the "
                             f"tiered residency floor ({floor} pages)")
        n_pages = max(c.pool_pages or c.slots * self.npps, floor)
        n_pages = -(-n_pages // c.shards) * c.shards      # shardable pool
        self.n_pages = n_pages
        self.allocator = PageAllocator(n_pages)
        self.sched = SlotScheduler(c.slots, self.allocator, c.page_size,
                                   gang=c.gang)
        arrivals = c.arrival_process().arrival_steps(
            c.requests, seed=c.seed, step_us=c.step_us)
        lrng = np.random.default_rng(c.seed + 17)

        def draw(base: int) -> int:
            if c.length_jitter <= 0:
                return base
            lo = max(1, int(round(base * (1 - c.length_jitter))))
            return int(lrng.integers(lo, base + 1))

        self.queue = AdmissionQueue(
            Request(req_id=i, prompt_len=draw(c.prompt_len),
                    gen=draw(c.gen), arrival_step=int(arrivals[i]))
            for i in range(c.requests))
        self.dtype = jnp.dtype(executor.dtype)
        self.hq = getattr(executor, "n_q_heads", hkv)
        self.geom = TieredKV(n_pages, min(floor, n_pages), c.page_size,
                             hkv, dh, chunk=c.chunk, ring_size=c.ring_size,
                             use_kernel=c.use_kernel)
        self.tstate = tiered_init(self.geom, c.slots, self.dtype)
        self.pool = init_paged_kv(1, n_pages, c.page_size, hkv, dh,
                                  self.dtype)
        self.fabric = self.mesh = None
        if c.shards > 1:
            self.fabric = ShardedPoolCfg(
                n_shards=c.shards, placement=c.placement,
                link_budget=c.link_budget, near_delay=1,
                far_delay=c.far_delay)
            if mesh is None:
                from repro.launch.mesh import make_fabric_mesh
                mesh = make_fabric_mesh(c.shards)
            self.mesh = mesh
        self.reg = Registry()
        self.phases: list[RequestPhase] = []
        self.events: list[Event] | None = [] if c.trace else None
        self.link_hist: list[np.ndarray] = []
        self.shard_hist: list[np.ndarray] = []
        # per-slot counter base: stats of previous occupants folded in at
        # each stream reset, so the §8 totals pin spans slot reuse
        self.counter_base = [dict.fromkeys(PINNED_COUNTERS, 0)
                             for _ in range(c.slots)]
        from repro.paging.lifecycle import PageLifecycle, resolve
        mig = resolve(c.migration)
        self.lifecycle = None if mig is None else PageLifecycle(
            n_pages, max(c.shards, 1), c.placement, mig)
        self.equiv_ok = True
        self.first_bad_step: int | None = None
        self.occupancy_peak = 0.0
        self._chunk_clock = 0
        self._n_chunks = -(-self.npps // c.chunk)
        self._inv_width = c.slots * max(c.prefill_chunk, 1)
        self.finished: list[Request] = []

    # -- device helpers ------------------------------------------------------
    def _write_tokens(self, req: Request, k, v, start: int) -> list[int]:
        """Mirror ``[n, Hkv, dh]`` K/V into the cold pool at positions
        ``start..start+n-1``; returns the distinct pages written."""
        n = k.shape[0]
        pages = [self.sched.page_for_position(req, start + j)
                 for j in range(n)]
        ps = self.cfg.page_size
        pg = jnp.asarray(pages, jnp.int32)
        off = (start + jnp.arange(n, dtype=jnp.int32)) % ps
        self.pool = _scatter_tokens(self.pool, pg, off, k, v)
        return sorted(set(pages))

    def attention_state(self) -> dict:
        """The tiered state as the attention consumer reads it. With a
        fabric mesh the sweep's shard_map leaves it replicated over the
        mesh, and the attention kernel (Mosaic, which XLA cannot
        partition) reads one copy beside the pool."""
        if self.mesh is None:
            return self.tstate
        return jax.device_put(self.tstate, self.pool["k"].sharding)

    def _sweep_and_pin(self, t: int, decoding: list[Request]) -> None:
        S, npps = self.cfg.slots, self.npps
        rows = np.full((S, npps), -1, np.int32)
        lengths = np.zeros((S,), np.int32)
        for req in decoding:
            rows[req.slot, :len(req.pages)] = req.pages
            lengths[req.slot] = req.prefilled + req.decoded - 1
        rows_j = jnp.asarray(rows)
        lengths_j = jnp.asarray(lengths)
        sweep_kw = {}
        lc = self.lifecycle
        if lc is not None:
            # drive the §12 lifecycle mirror between steps: decay + heat,
            # trend-driven hot-ward migration, capacity demotion. All of it
            # is scheduling metadata except demotion, which round-trips the
            # victim's cold bytes once (both the flat reference and the
            # tiered path then read the same post-roundtrip bytes, so the
            # §6.4 pin holds) and drops any stale hot copy.
            lc.begin_step()
            lc.touch(rows[rows >= 0])
            trend = np.asarray(self.tstate["leap"]["trend"])
            has = np.asarray(self.tstate["leap"]["has_trend"])
            G = max(self.cfg.shards, 1)
            for req in decoding:
                s = req.slot
                if G <= 1 or not has[s] or not trend[s]:
                    continue
                frontier = int(req.pages[-1])
                cands = [frontier + int(trend[s])
                         * (self.geom.pw_max + lc.cfg.lead + j)
                         for j in range(lc.cfg.mig_per_stream)]
                moved = lc.migrate_toward(cands, s % G)
                if moved and self.events is not None:
                    self.events.append(Event("migrate", self._chunk_clock,
                                             s, count=moved))
            victims = lc.demote_victims()
            if victims:
                vict = jnp.asarray(victims, jnp.int32)
                self.pool = _roundtrip_pages(self.pool, vict)
                inv = jnp.broadcast_to(vict[None], (S, len(victims)))
                self.tstate = tiered_invalidate(self.tstate, inv)
                if self.events is not None:
                    self.events.append(Event("demote", self._chunk_clock,
                                             0, count=len(victims)))
            sweep_kw["home_map"] = lc.home_map()
            if lc.cfg.compressed:
                sweep_kw["comp_map"] = lc.comp_map()
                sweep_kw["decompress_delay"] = lc.cfg.decompress_delay
        cold = {"k": self.pool["k"][0], "v": self.pool["v"][0]}
        q = jax.random.normal(jax.random.PRNGKey(1000 + t),
                              (S, 1, self.hq, self.ex.head_dim), self.dtype)
        with self.reg.span("tiered_sweep") as sp:
            self.tstate, info = tiered_sweep(
                self.tstate, cold, rows_j, self.geom,
                async_datapath=self.cfg.async_datapath,
                link_budget=self.cfg.link_budget,
                fabric=self.fabric, mesh=self.mesh, **sweep_kw)
            sp.sync = info
        mode = normalize_attn_kernel(self.cfg.attn_kernel)
        with self.reg.span("tiered_attention") as sp:
            tiered, resident = tiered_attention(q, self.attention_state(),
                                                rows_j, lengths_j,
                                                attn_kernel=mode)
            sp.sync = tiered
        flat = paged_decode_attention(q, self.pool, jnp.int32(0), rows_j,
                                      lengths_j,
                                      use_kernel=(mode != "ref"))
        act = [r.slot for r in decoding]
        step_ok = bool(resident) and bool(
            (np.asarray(tiered)[act] == np.asarray(flat)[act]).all())
        if not step_ok:
            self.equiv_ok = False
            if self.first_bad_step is None:
                self.first_bad_step = t
        if self.events is not None:
            self.events.extend(
                decode_sweep_events(info, step_offset=self._chunk_clock))
            self.link_hist.append(np.asarray(info["link_demand_fetches"]))
            self.shard_hist.append(np.asarray(info["shard_demand_fetches"]))
        self._chunk_clock += self._n_chunks

    # -- one engine step -----------------------------------------------------
    def _step(self, t: int) -> None:
        for req in self.sched.admit_ready(self.queue, t):
            self.ex.begin(req)
            self.phases.append(RequestPhase("admit", req.req_id,
                                            req.arrival_step, t, req.slot))
        written: list[tuple[int, int]] = []       # (slot, page)
        decoding: list[Request] = []
        finishers: list[Request] = []
        for req in sorted(self.sched.active(), key=lambda r: r.slot):
            if req.state == PREFILL:
                n = min(self.cfg.prefill_chunk,
                        req.prompt_len - req.prefilled)
                k, v, tok = self.ex.prefill_chunk(req, n)
                if tok is not None:
                    req.tokens.append(tok)
                pages = self._write_tokens(req, k, v, req.prefilled)
                written.extend((req.slot, p) for p in pages)
                req.advance_prefill(n, t)
                self.phases.append(RequestPhase("prefill_chunk", req.req_id,
                                                t, t + 1, req.slot, n))
                if req.state == DECODE:           # prompt done: TTFT token
                    self.reg.histogram("ttft_steps").observe(req.ttft_steps)
                    if req.decoded >= req.gen:
                        finishers.append(req)
            elif req.state == DECODE:
                pos = req.prefilled + req.decoded - 1
                with self.reg.span("token_latency") as sp:
                    k, v, tok = self.ex.decode(req)
                    sp.sync = k
                req.tokens.append(tok)
                pages = self._write_tokens(req, k[None], v[None], pos)
                written.extend((req.slot, p) for p in pages)
                done = req.advance_decode(t)
                decoding.append(req)
                if done:
                    finishers.append(req)
        if written and self.lifecycle is not None:
            # freshly written bytes are uncompressed by construction: clear
            # the comp bit (else a recycled page would charge a decompress
            # surcharge — and dodge its roundtrip — on stale state)
            n_prom = self.lifecycle.promote([p for _, p in written])
            if n_prom and self.events is not None:
                self.events.append(Event("promote", self._chunk_clock, 0,
                                         count=n_prom))
        if written:
            inv = np.full((self._inv_width,), -1, np.int32)
            inv[:len(written)] = [p for _, p in written]
            inv_j = jnp.broadcast_to(jnp.asarray(inv)[None],
                                     (self.cfg.slots, self._inv_width))
            self.tstate = tiered_invalidate(self.tstate, inv_j)
            if self.events is not None:
                self.events.extend(
                    Event("invalidate", self._chunk_clock, s, page=p,
                          seq=self.allocator.stamp_of(p))
                    for s, p in written)
        if decoding:
            self._sweep_and_pin(t, decoding)
        self.occupancy_peak = max(self.occupancy_peak,
                                  self.allocator.occupancy())
        for req in finishers:
            self._evict(req, t)

    def _evict(self, req: Request, t: int) -> None:
        self.phases.append(RequestPhase("decode", req.req_id,
                                        req.first_token_step, t, req.slot,
                                        req.decoded))
        slot = req.slot
        stats = tiered_stats(self.tstate, slot)
        base = self.counter_base[slot]
        for key in PINNED_COUNTERS:
            base[key] += int(stats[key])
        self.tstate = tiered_reset_stream(self.tstate, slot, self.geom,
                                          self.dtype)
        self.sched.finish(req, t)
        self.ex.end(req)
        self.finished.append(req)
        self.phases.append(RequestPhase("evict", req.req_id, t, t, slot))

    # -- run -----------------------------------------------------------------
    def run(self) -> dict:
        c = self.cfg
        last_arrival = max((r.arrival_step for r in self.queue._pending),
                           default=0)
        per_req = -(-c.prompt_len // c.prefill_chunk) + c.gen + 2
        max_steps = last_arrival + (c.requests + 1) * per_req + 10
        t = 0
        t0 = time.perf_counter()
        while len(self.queue) or self.sched.active():
            if t > max_steps:
                raise RuntimeError(
                    f"engine livelock: {len(self.queue)} queued / "
                    f"{len(self.sched.active())} active after {t} steps")
            with self.reg.span("engine_step"):
                self._step(t)
            t += 1
        wall = time.perf_counter() - t0
        return self._report(t, wall)

    def _report(self, steps: int, wall: float) -> dict:
        c = self.cfg
        totals = []
        for s in range(c.slots):
            cur = tiered_stats(self.tstate, s)
            totals.append({k: self.counter_base[s][k] + int(cur[k])
                           for k in PINNED_COUNTERS})
        trace_totals_ok = True
        if self.events is not None:
            self.events.extend(summary_events(totals))
            cnts = events_to_counts(self.events, c.slots)
            trace_totals_ok = all(
                cnts[s][k] == totals[s][k]
                for s in range(c.slots) for k in PINNED_COUNTERS)
        rnd = lambda d: {k: round(v, 5) if isinstance(v, float) else v
                         for k, v in d.items()}
        ttfts = self.reg.histogram("ttft_steps")
        out = {
            "requests": c.requests,
            "slots": c.slots,
            "arrival": c.arrival,
            "admission": "gang" if c.gang else "continuous",
            "steps": steps,
            "wall_s": round(wall, 3),
            "tiered_equiv_ok": self.equiv_ok,
            "requests_finished": len(self.finished),
            "tokens_decoded": sum(r.decoded for r in self.finished),
            "ttft_steps": rnd(ttfts.ladder()),
            "mean_ttft_steps": round(float(np.mean(ttfts.samples)), 3)
            if ttfts.samples else float("nan"),
            "token_latency": rnd(self.reg.histogram("token_latency").ladder()),
            "pages_allocated": self.sched.pages_allocated,
            "pages_recycled": self.sched.pages_recycled,
            "alloc_in_use_end": self.allocator.in_use,
            "alloc_occupancy_peak": round(self.occupancy_peak, 3),
            "prefetch_hits_total": sum(tt["prefetch_hits"] for tt in totals),
            "deferred_total": sum(tt["deferred"] for tt in totals),
        }
        if self.first_bad_step is not None:
            out["tiered_first_bad_step"] = self.first_bad_step
        if self.events is not None:
            out["trace_totals_ok"] = trace_totals_ok
            out["trace_events"] = len(self.events)
        if c.shards > 1:
            out["shards"] = c.shards
            out["placement"] = c.placement
        if self.lifecycle is not None:
            out["residency"] = self.lifecycle.report()
        return out


@jax.jit
def _roundtrip_pages(pool: dict, pages) -> dict:
    """Apply the lossy int8 page round trip to layer 0's ``pages`` in
    place — one scale per page (demotion to the compressed tier)."""
    from repro.runtime.compression import page_roundtrip

    def rt(buf):
        return buf.at[0, pages].set(jax.vmap(page_roundtrip)(buf[0, pages]))

    return {"k": rt(pool["k"]), "v": rt(pool["v"])}


@jax.jit
def _scatter_tokens(pool: dict, pages, offs, k_new, v_new) -> dict:
    """Write ``n`` tokens' K/V ``[n, Hkv, dh]`` at ``(pages[j], offs[j])``
    of layer 0 (head-major pool ``[1, n_pages, Hkv, page, dh]``)."""
    def wr(buf, new):
        return buf.at[0, pages, :, offs].set(new.astype(buf.dtype))

    return {"k": wr(pool["k"], k_new), "v": wr(pool["v"], v_new)}


def gate_failures(report: dict, requests: int) -> list[str]:
    """The engine's own run gates over a :meth:`ServingEngine.run` report:
    the §6.4 flat/tiered pin, every request finished, no page leak, page
    conservation, and (when traced) the §8.2 event totals. Empty = pass."""
    out = []
    if not report["tiered_equiv_ok"]:
        out.append("tiered/flat decode attention mismatch under continuous "
                   f"batching (first bad step "
                   f"{report.get('tiered_first_bad_step')})")
    if report["requests_finished"] != requests:
        out.append(f"{report['requests_finished']}/{requests} requests "
                   "finished")
    if report["alloc_in_use_end"] != 0:
        out.append(f"page leak: {report['alloc_in_use_end']} pages still "
                   "allocated after drain")
    if report["pages_allocated"] != report["pages_recycled"]:
        out.append("page conservation violated: "
                   f"{report['pages_allocated']} allocated vs "
                   f"{report['pages_recycled']} recycled")
    if not report.get("trace_totals_ok", True):
        out.append("trace event totals diverge from pool counters (decode "
                   "contract violation, DESIGN.md §8.2)")
    return out


def serve_continuous(config: ServeConfig, executor=None, arch: str = None,
                     smoke: bool = True) -> dict:
    """Build an executor (real model or synthetic), run the engine once.

    ``arch=None`` uses the synthetic executor — real scheduling, paging and
    pins over PRNG K/V bytes.
    """
    if executor is None:
        executor = build_executor(arch, smoke=smoke, seed=config.seed)
    return ServingEngine(config, executor).run()


def build_executor(arch: str | None, smoke: bool = True, seed: int = 0):
    """The real :class:`ModelExecutor` for ``arch``; ``arch=None`` is the
    :class:`SyntheticExecutor`. Families the engine cannot serve (encdec)
    raise — never a silent PRNG stand-in under a model's name."""
    from .executor import ModelExecutor, SyntheticExecutor

    if arch is None:
        return SyntheticExecutor(n_kv_heads=2, head_dim=8, seed=seed)
    from repro import configs as cfglib
    cfg = cfglib.get_smoke_config(arch) if smoke else cfglib.get_config(arch)
    return ModelExecutor(cfg, seed=seed)
