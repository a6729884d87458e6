#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU: qwen2.5-3b at full width.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # the sharded cold pool, four chips

One chip: builds one ``ModelExecutor`` for ``qwen2_5_3b`` at its published
widths (36 layers, d_model 2048, 16 q / 2 kv heads, head_dim 128, vocab
151,936, bf16; random weights from ``--seed``) and drives ``ServingEngine``
through a bursty workload (4 requests, 4 slots, prompt 128, gen 16, paged
KV in 16-token pages) twice on that one executor:

* ``fused``       — sync Leap sweep, fused hot-slot attention kernel;
* ``fused_async`` — issue/wait sweep, double-buffered hot-slot kernel.

Each phase must pass the engine's own gates (``gate_failures``: the §6.4
flat/tiered pin, every request finished, no page leak, page conservation)
and its sweep and attention programs must hold compiled Pallas kernels
(``tpu_custom_call``), not the interpreter. Then one request's
chunked-prefill first-token logits are compared with one-shot
``model.prefill``: on the served bf16 model (a gross check, ``BF16_RTOL``)
and on an f32 twin at the same widths (a precision check, ``F32_RTOL``).

``--four-chips`` runs only the sharded phase: the same workload with the
cold pool homed on 4 chips (``shards=4``, interleave placement, shard_map +
ppermute gathers) against ``shards=1``. Tokens must be identical, both pins
must hold, every shard must serve demand fetches, and the sweep must carry
collective permutes.

Everything runs in this one process. Any failure, or a first device that is
not a TPU, exits non-zero without a result line. On success the last line
of stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
The compile cache is ``JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/.jax_cache`` (see ``repro.launch.compile_cache``).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "qwen2_5_3b"
#: the bursty serving workload both modes share
WORKLOAD = dict(requests=4, slots=4, prompt_len=128, gen=16, page_size=16,
                prefill_chunk=8, arrival="bursty")
#: relative L2 gap allowed between chunked-prefill and one-shot first-token
#: logits of the served model. Both paths run the same bf16 weights
#: through different programs (per-token decode over the dense cache vs
#: blocked prefill; 1-row vs 128-row matmuls, each one bf16 MXU pass), so
#: they agree to bf16 rounding, not bitwise: 1.65-1.70e-2 on a v5e, the
#: same on every run. Over 36 random-weight layers any bf16-sized change
#: saturates near that floor, so this bound only catches gross breakage
#: (a lost, repeated or misplaced prompt token moves the logits by O(1)).
BF16_RTOL = 2e-2
#: the same gap on an f32 twin of the served model (same widths, depth cut
#: to ``TWIN_LAYERS``, f32 weights and activations, every matmul at
#: ``HIGHEST``): the two programs then differ only in f32 summation order
#: (~1e-6), while one bf16 rounding anywhere on either path (norm
#: statistics, softmax, a cache or matmul in bf16) is ~4e-3 per element
#: and opens a gap above 1e-3. The bound sits between the two.
F32_RTOL = 1e-4
TWIN_LAYERS = 4
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, process-wide."""

    def __init__(self, jax):
        self.secs, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.secs += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def since(self, mark: tuple) -> dict:
        return {"compile_s": self.secs - mark[0],
                "cache_hits": self.hits - mark[1],
                "cache_misses": self.misses - mark[2]}

    def mark(self) -> tuple:
        return (self.secs, self.hits, self.misses)


def program_texts(engine, cfg) -> dict:
    """Compiled HLO of the sweep, the tiered attention and the flat pin
    attention, at this engine's shapes."""
    import jax
    import jax.numpy as jnp

    from repro.paging.kv_cache import paged_decode_attention
    from repro.paging.tiered_kv import tiered_attention, tiered_sweep

    S, npps = cfg.slots, engine.npps
    rows = jnp.asarray(np.arange(S * npps).reshape(S, npps) % engine.n_pages,
                       jnp.int32)
    lengths = jnp.full((S,), npps * cfg.page_size, jnp.int32)
    q = jnp.zeros((S, 1, engine.hq, engine.ex.head_dim), engine.dtype)
    cold = {"k": engine.pool["k"][0], "v": engine.pool["v"][0]}
    sweep = jax.jit(lambda st, c, r: tiered_sweep(
        st, c, r, engine.geom, async_datapath=cfg.async_datapath,
        fabric=engine.fabric, mesh=engine.mesh))
    attn = jax.jit(lambda qq, st, r, ln: tiered_attention(
        qq, st, r, ln, attn_kernel=cfg.attn_kernel)[0])
    flat = jax.jit(lambda qq, p, r, ln: paged_decode_attention(
        qq, p, jnp.int32(0), r, ln, use_kernel=True))
    lowered = {"sweep": sweep.lower(engine.tstate, cold, rows),
               "attention": attn.lower(q, engine.attention_state(), rows,
                                       lengths),
               "flat_pin": flat.lower(q, engine.pool, rows, lengths)}
    return {k: v.compile().as_text() for k, v in lowered.items()}


def serve_phase(name: str, ex, meter: CompileMeter, **overrides):
    """One ServingEngine run; gates, kernel presence and timings."""
    from repro.serving.engine import ServeConfig, ServingEngine, gate_failures

    cfg = ServeConfig(**{**WORKLOAD, **overrides})
    mark = meter.mark()
    t0 = time.perf_counter()
    engine = ServingEngine(cfg, ex)
    report = engine.run()
    wall = time.perf_counter() - t0
    timing = meter.since(mark)
    texts = program_texts(engine, cfg)
    kernels = {k: t.count("tpu_custom_call") for k, t in texts.items()}
    emit(phase=name, wall_s=wall, **timing, engine_steps=report["steps"],
         requests_finished=report["requests_finished"],
         tokens_decoded=report["tokens_decoded"],
         tiered_equiv_ok=report["tiered_equiv_ok"],
         pages_allocated=report["pages_allocated"],
         pages_recycled=report["pages_recycled"],
         tpu_custom_calls=kernels)
    fails = gate_failures(report, cfg.requests)
    check(not fails, f"{name}: " + "; ".join(fails))
    check(report["tokens_decoded"] == cfg.requests * cfg.gen,
          f"{name}: decoded {report['tokens_decoded']} tokens, want "
          f"{cfg.requests * cfg.gen}")
    for prog, n in kernels.items():
        check(n > 0, f"{name}: no compiled Pallas kernel (tpu_custom_call) "
                     f"in the {prog} program")
    return engine, report, texts


def logits_check(name: str, ex, rtol: float) -> None:
    """Chunked-prefill first-token logits vs one-shot ``model.prefill``,
    for one request of the smoke workload's prompt length and chunk."""
    from repro.serving.request import PREFILL, Request

    req = Request(req_id=10_000, prompt_len=WORKLOAD["prompt_len"], gen=1)
    req.to(PREFILL, 0)
    ex.begin(req)
    while req.state == PREFILL:
        n = min(WORKLOAD["prefill_chunk"], req.prompt_len - req.prefilled)
        ex.prefill_chunk(req, n)
        req.advance_prefill(n, 0)
    chunked = np.asarray(ex.last_logits[req.req_id], np.float32)
    oneshot = np.asarray(ex.oneshot_prefill_logits(req), np.float32)
    ex.end(req)
    check(chunked.shape == oneshot.shape == (ex.cfg.padded_vocab,),
          f"logits shapes {chunked.shape} vs {oneshot.shape}")
    check(bool(np.isfinite(chunked).all() and np.isfinite(oneshot).all()),
          "non-finite logits")
    diff = chunked - oneshot
    rel_l2 = float(np.linalg.norm(diff) / np.linalg.norm(oneshot))
    top2 = np.sort(oneshot)[-2:]
    emit(phase=name, shape=list(oneshot.shape), rel_l2=rel_l2,
         rtol=rtol, max_abs_diff=float(np.abs(diff).max()),
         ref_max_abs=float(np.abs(oneshot).max()),
         argmax_equal=int(chunked.argmax()) == int(oneshot.argmax()),
         ref_top2_margin=float(top2[1] - top2[0]))
    check(rel_l2 <= rtol, f"{name}: chunked vs one-shot logits: rel L2 "
                          f"{rel_l2:.3e} > {rtol}")


def build(jax, meter: CompileMeter, seed: int, twin: bool = False):
    """The served executor, or (``twin``) its f32 twin of ``TWIN_LAYERS``
    layers at the same widths."""
    from repro import configs as cfglib
    from repro.serving.engine import build_executor
    from repro.serving.executor import ModelExecutor

    mark = meter.mark()
    t0 = time.perf_counter()
    if twin:
        ex = ModelExecutor(dataclasses.replace(
            cfglib.get_config(ARCH), n_layers=TWIN_LAYERS, dtype="float32"),
            seed=seed)
    else:
        ex = build_executor(ARCH, smoke=False, seed=seed)
    jax.block_until_ready(ex.params)
    check(isinstance(ex, ModelExecutor),
          f"build_executor gave {type(ex).__name__}, not ModelExecutor")
    cfg = ex.cfg
    leaves = jax.tree.leaves(ex.params)
    emit(phase="build_f32_twin" if twin else "build_executor", wall_s=time.perf_counter() - t0,
         **meter.since(mark), model=cfg.name, n_layers=cfg.n_layers,
         d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
         head_dim=cfg.head_dim, vocab=cfg.vocab_size,
         params=sum(x.size for x in leaves),
         param_bytes=sum(x.nbytes for x in leaves),
         param_dtypes=sorted({str(x.dtype) for x in leaves}))
    return ex


def one_chip(jax, meter: CompileMeter, seed: int) -> None:
    ex = build(jax, meter, seed)
    serve_phase("fused", ex, meter, attn_kernel="fused", seed=seed)
    serve_phase("fused_async", ex, meter, attn_kernel="fused_async",
                async_datapath=True, seed=seed)
    logits_check("prefill_logits_bf16", ex, BF16_RTOL)
    with jax.default_matmul_precision("highest"):
        twin = build(jax, meter, seed, twin=True)
        logits_check("prefill_logits_f32", twin, F32_RTOL)


def four_chips(jax, meter: CompileMeter, seed: int) -> None:
    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, JAX sees {len(jax.devices())}")
    ex = build(jax, meter, seed)
    base = dict(attn_kernel="fused_async", async_datapath=True, trace=True,
                seed=seed)
    flat, flat_rep, _ = serve_phase("flat_1_shard", ex, meter, **base)
    shard, shard_rep, texts = serve_phase(
        "sharded_4_shards", ex, meter, shards=4, placement="interleave",
        **base)
    tokens = lambda e: {r.req_id: list(r.tokens) for r in e.finished}
    per_shard = np.concatenate(shard.shard_hist).sum(0)
    mesh_devices = sorted(d.id for d in shard.mesh.devices.flat)
    permutes = texts["sweep"].count("collective-permute")
    emit(phase="sharded_vs_flat", tokens_equal=tokens(flat) == tokens(shard),
         per_shard_demand_fetches=per_shard.tolist(),
         mesh_devices=mesh_devices, sweep_collective_permutes=permutes)
    check(tokens(flat) == tokens(shard),
          "sharded run emitted different tokens than the flat run")
    check(flat_rep["tiered_equiv_ok"] and shard_rep["tiered_equiv_ok"],
          "§6.4 pin differs between flat and sharded runs")
    check(per_shard.shape == (4,) and bool((per_shard > 0).all()),
          f"a shard served no demand fetches: {per_shard.tolist()}")
    check(len(set(mesh_devices)) == 4, f"fabric mesh on {mesh_devices}")
    check(permutes > 0, "sharded sweep holds no collective-permute")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-flat cold pool phase on "
                         "four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: first JAX device is {dev.platform!r}, not a TPU",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache = Path(cache_dir)
    n_cached = len(list(cache.iterdir())) if cache.is_dir() else 0
    emit(phase="start", device_kind=dev.device_kind, devices=len(devices),
         jax=jax.__version__, compile_cache=cache_dir,
         cache_entries_before=n_cached)
    meter = CompileMeter(jax)
    t0 = time.perf_counter()
    try:
        (four_chips if args.four_chips else one_chip)(jax, meter, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    emit(phase="total", wall_s=time.perf_counter() - t0, compile_s=meter.secs,
         cache_hits=meter.hits, cache_misses=meter.misses)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
