"""Serving front-end: request lifecycle CLI over :mod:`repro.serving`.

Two serving disciplines behind one CLI:

* ``--arrival batch`` (default) — the legacy lock-step loop: prefill the
  whole batch, greedy-decode ``--gen`` tokens, and with ``--paged`` replay
  the decode window through the tiered paged-KV data path
  (:func:`repro.serving.batch_driver.serve_batch_tiered`) with the §6.4
  flat/tiered bit-identity pin every step.
* ``--arrival constant|bursty|churn`` — the **continuous-batching engine**
  (:class:`repro.serving.engine.ServingEngine`): requests arrive on a
  seeded :class:`repro.fabric.tenants.ArrivalProcess`, are admitted into
  slots as capacity frees up, prefill in chunks interleaved with in-flight
  decodes, and recycle their pages on finish. The same §6.4 pin runs every
  step over the dynamic batch composition, and the report carries
  per-request TTFT + p50–p99.9 token-latency ladders
  (:mod:`repro.obs.metrics`). ``--gang`` flips admission to the lock-step
  baseline (all slots drain before the next gang enters) for A/B runs.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2_5_3b --smoke \
      --batch 4 --prompt-len 32 --gen 16 --paged --async-datapath
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2_5_3b --smoke \
      --arrival bursty --requests 8 --paged --async-datapath
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as cfglib
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.obs.export import (write_chrome_trace, write_jsonl,
                              write_request_jsonl)
from repro.obs.metrics import Registry
from repro.runtime.straggler import StepTimeMonitor
from repro.serving.batch_driver import serve_batch_tiered
from repro.serving.engine import (ServeConfig, ServingEngine, build_executor,
                                  gate_failures)

ARRIVALS = ("batch", "constant", "bursty", "churn")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_5_3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--paged", action="store_true",
                    help="serve decode attention through the tiered paged-KV "
                         "cache (Leap-managed hot pool over the cold paged "
                         "pool) and pin it bit-identical to the flat pool")
    ap.add_argument("--async-datapath", action="store_true",
                    help="with --paged: sweep context pages through the "
                         "issue/wait in-flight ring so prefetch DMA "
                         "overlaps the next chunk instead of blocking this "
                         "one; reports partial hits (DESIGN.md §4/§6)")
    ap.add_argument("--ring-size", type=int, default=8,
                    help="in-flight ring capacity for --async-datapath")
    ap.add_argument("--chunk", type=int, default=4,
                    help="context pages demanded per sweep step (the "
                         "multi-page demand batch of the tiered cache)")
    ap.add_argument("--streams", type=int, default=1,
                    help="with --paged: number of per-request page streams "
                         "(stream s sweeps request s %% batch). Default/1 = "
                         "one stream per request in the batch")
    ap.add_argument("--link-budget", type=int, default=None,
                    help="with --paged: pages/step the shared fabric link "
                         "can move across all streams' prefetches; demand "
                         "chunks are arbitrated first and surplus "
                         "prefetches arrive late (reported as deferred — "
                         "DESIGN.md §5). With --shards > 1 the budget is "
                         "*per shard NIC* (one §5 arbiter each, DESIGN.md "
                         "§7). Default: private infinite links")
    ap.add_argument("--shards", type=int, default=1,
                    help="with --paged: shard the cold paged-KV pool over "
                         "this many devices on a 'fabric' mesh axis "
                         "(DESIGN.md §7): each page lives on a home shard "
                         "behind its own NIC, the sweep runs under "
                         "shard_map, and cross-shard pages move by "
                         "collective permutes. Needs >= this many devices "
                         "(CPU: XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N). Default 1 = flat cold pool")
    ap.add_argument("--placement", choices=("block", "interleave"),
                    default="interleave",
                    help="with --shards: page -> home-shard policy "
                         "(interleave spreads consecutive pages across "
                         "NICs; block keeps contiguous ranges together)")
    ap.add_argument("--far-delay", type=int, default=2,
                    help="with --shards: prefetch arrival delay in chunk "
                         "steps for cross-shard pages (near pages take 1)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (a multiple of 16 keeps the "
                         "bf16 page tiles whole for the TPU kernels)")
    ap.add_argument("--attn-kernel", default="ref",
                    choices=("ref", "kernel", "fused", "fused-async"),
                    help="with --paged: decode-attention consumer. "
                         "ref/kernel run over the stacked hot pool (a full "
                         "hot-pool copy per step); fused/fused-async read "
                         "the per-stream hot slots in place through the "
                         "slot table inside the Pallas kernel (fused-async "
                         "adds explicit make_async_copy double-buffering). "
                         "The flat-pool bit-identity pin runs every step "
                         "in all modes")
    ap.add_argument("--chaos", default=None, metavar="SPEC.json",
                    help="with --paged: inject faults from a ChaosSpec JSON "
                         "file (DESIGN.md §9) into a chaos sidecar run over "
                         "the requests' context-page schedules — per-shard "
                         "slowdown, NIC budget degradation, node loss with "
                         "page re-homing, elastic tenant grants. Reports "
                         "per-shard estimated vs true delay (the adaptive-"
                         "deadline EWMA) plus timely-hit counters")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="with --paged: decode the sweep info arrays into "
                         "the page-lifecycle event log and write a Chrome "
                         "trace-event JSON (Perfetto-loadable; per-stream "
                         "tracks + link/NIC counter tracks) plus a .jsonl "
                         "sibling. Decoding is host-side and post-hoc: the "
                         "jitted serving path is unchanged (DESIGN.md §8). "
                         "Continuous-batching runs additionally emit the "
                         "per-request lifecycle track (admit/prefill/"
                         "decode/evict, keyed by request id) and a "
                         ".requests.jsonl sibling")
    # -- continuous-batching engine (DESIGN.md §10) --------------------------
    ap.add_argument("--arrival", choices=ARRIVALS, default="batch",
                    help="request arrival discipline. 'batch' = legacy "
                         "lock-step full-batch loop; the rest drive the "
                         "continuous-batching engine with the named "
                         "fabric/tenants.py arrival process")
    ap.add_argument("--requests", type=int, default=8,
                    help="continuous engine: total requests to serve")
    ap.add_argument("--slots", type=int, default=None,
                    help="continuous engine: concurrent serving slots "
                         "(default: --batch)")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="continuous engine: prompt tokens consumed per "
                         "engine step per slot (chunked prefill)")
    ap.add_argument("--length-jitter", type=float, default=0.0,
                    help="continuous engine: per-request length "
                         "heterogeneity — prompt/gen drawn uniformly from "
                         "[len*(1-jitter), len] (seeded)")
    ap.add_argument("--think-time", type=float, default=1000.0,
                    help="continuous engine: arrival-process mean gap (µs)")
    ap.add_argument("--gang", action="store_true",
                    help="continuous engine: lock-step gang admission "
                         "(the fixed-batch baseline) instead of continuous")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="continuous engine: cold-pool pages (default "
                         "slots * pages-per-request; smaller values make "
                         "admission wait on memory)")
    ap.add_argument("--synthetic", action="store_true",
                    help="continuous engine: synthetic executor (PRNG K/V, "
                         "no model) — real scheduling + data path + pins")
    # -- three-tier page lifecycle (DESIGN.md §12) ---------------------------
    ap.add_argument("--migration", action="store_true",
                    help="continuous engine: online hot/cold page migration "
                         "(DESIGN.md §12). The Leap trend re-homes each "
                         "stream's upcoming pages toward its shard between "
                         "steps; re-homing steers budgets/deadlines/NIC "
                         "accounting only (the data plane is unchanged, so "
                         "all bit-identity pins keep holding). The report "
                         "gains a per-tier residency section")
    ap.add_argument("--compressed-tier", type=int, default=None,
                    metavar="PAGES",
                    help="continuous engine: cap the *uncompressed* far "
                         "tier at PAGES; the coldest pages beyond it are "
                         "demoted through the lossy int8 page codec (one "
                         "roundtrip at demote time) and pay a decompress "
                         "surcharge on promote. Implies --migration")
    ap.add_argument("--mig-cooldown", type=int, default=16,
                    help="with --migration: hysteresis window in steps — a "
                         "page neither re-homes nor demotes again within "
                         "this many steps of its last tier transition")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main(argv=None) -> dict:
    ap = build_parser()
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.trace and not (args.paged or args.arrival != "batch"):
        ap.error("--trace requires --paged (only the tiered data path "
                 "emits the page-lifecycle info arrays)")
    if args.chaos and not args.paged:
        ap.error("--chaos requires --paged (faults are injected into the "
                 "paged-KV sweep's fabric model)")
    if (args.migration or args.compressed_tier is not None) \
            and args.arrival == "batch":
        ap.error("--migration/--compressed-tier need the continuous engine "
                 "(--arrival constant|bursty|churn): the page lifecycle is "
                 "driven between engine steps")
    if args.arrival != "batch":
        return _main_continuous(args)
    return _main_batch(args)


def _main_batch(args) -> dict:
    """Legacy lock-step path: batched prefill + decode (+ tiered replay)."""
    cfg = (cfglib.get_smoke_config(args.arch) if args.smoke
           else cfglib.get_config(args.arch))
    model = build_model(cfg)
    params, _ = model.init_params(jax.random.PRNGKey(0))
    B, prompt_len = args.batch, args.prompt_len
    max_len = prompt_len + args.gen
    rng = jax.random.PRNGKey(1)
    prompts = jax.random.randint(rng, (B, prompt_len), 0, cfg.vocab_size)
    batch = {"tokens": prompts}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(rng, (B, prompt_len, cfg.d_model),
                                            jnp.dtype(cfg.dtype))

    reg = Registry()
    decode = jax.jit(model.decode_step)
    with reg.span("prefill") as sp:
        logits, state = model.prefill(params, batch, max_len)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        sp.sync = tok
    t_prefill = reg.histogram("prefill").samples[-1]

    out = [tok]
    # per-step wall-time straggler detection (runtime satellite): the same
    # EWMA monitor every host runs on a pod feeds off the decode loop here,
    # so compilation stalls / CPU contention show up as flagged steps
    mon = StepTimeMonitor()
    t0 = time.perf_counter()
    for _ in range(args.gen - 1):
        # span-timed per token (device-sync'd) — feeds the p50–p99.9
        # token-latency ladder in the final report
        with reg.span("token_latency") as sp:
            logits, state = decode(params, tok, state)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            sp.sync = tok
        mon.record(reg.histogram("token_latency").samples[-1])
        out.append(tok)
    t_decode = time.perf_counter() - t0
    tokens = np.stack([np.asarray(t) for t in out], 1)
    tok_ladder = reg.histogram("token_latency").ladder()
    result = {
        "prefill_s": round(t_prefill, 3),
        # TTFT: the first token is emitted by prefill's final logits
        "ttft_s": round(t_prefill, 3),
        "decode_tok_per_s": round(B * (args.gen - 1) / max(t_decode, 1e-9), 1),
        "token_latency": {k: round(v, 5) if isinstance(v, float) else v
                          for k, v in tok_ladder.items()},
        "tokens_shape": list(tokens.shape),
        "step_time_monitor": {k: round(v, 5) if isinstance(v, float) else v
                              for k, v in mon.summary().items()},
    }

    if args.paged:
        result.update(serve_batch_tiered(cfg, state, args, B, prompt_len,
                                         max_len, reg=reg,
                                         trace_path=args.trace))
        if not result["tiered_equiv_ok"]:
            print(result)
            msg = "tiered/flat decode attention mismatch"
            if args.trace:
                msg += (f" (first bad decode step "
                        f"{result['tiered_first_bad_step']}; event trace "
                        f"dumped to {args.trace} — diff it against a good "
                        f"run with repro.obs.diff)")
            raise SystemExit(msg)
        if args.trace and not result["trace_totals_ok"]:
            print(result)
            raise SystemExit("trace event totals diverge from pool counters "
                             "(decode contract violation, DESIGN.md §8.2)")

    print(result)
    return result


def _main_continuous(args) -> dict:
    """Continuous-batching path: request lifecycle over the serving engine."""
    migration = None
    if args.migration or args.compressed_tier is not None:
        from repro.paging.lifecycle import MigrationCfg
        migration = MigrationCfg(
            cooldown=args.mig_cooldown,
            compressed=args.compressed_tier is not None,
            far_capacity=args.compressed_tier)
    scfg = ServeConfig(
        requests=args.requests,
        slots=args.slots if args.slots is not None else args.batch,
        prompt_len=args.prompt_len, gen=args.gen,
        length_jitter=args.length_jitter,
        page_size=args.page_size, prefill_chunk=args.prefill_chunk,
        chunk=args.chunk, ring_size=args.ring_size,
        async_datapath=args.async_datapath, link_budget=args.link_budget,
        shards=args.shards, placement=args.placement,
        far_delay=args.far_delay,
        attn_kernel=args.attn_kernel.replace("-", "_"),
        arrival=args.arrival,
        think_time=args.think_time, seed=args.seed, gang=args.gang,
        pool_pages=args.pool_pages, trace=bool(args.trace),
        migration=migration)
    executor = build_executor(None if args.synthetic else args.arch,
                              smoke=args.smoke, seed=args.seed)
    engine = ServingEngine(scfg, executor)
    result = engine.run()

    if args.trace:
        counters = None
        if engine.link_hist:
            counters = {"link_demand_fetches": np.concatenate(engine.link_hist)}
            if args.shards > 1:
                counters["shard_demand_fetches"] = np.concatenate(
                    engine.shard_hist)
        write_chrome_trace(args.trace, engine.events, counters,
                           request_phases=engine.phases)
        write_jsonl(args.trace + ".jsonl", engine.events)
        write_request_jsonl(args.trace + ".requests.jsonl", engine.phases)
        result["trace_path"] = args.trace

    failures = gate_failures(result, args.requests)
    if failures:
        print(result)
        raise SystemExit("; ".join(failures))
    print(result)
    return result


if __name__ == "__main__":
    main()
