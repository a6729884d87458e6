"""The benchmark harness: one cell, one seed, one process.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``). :func:`run` builds the cell from the
seed, warms every program the window will use, drives the serving engine
for ``seconds`` and returns the result line. Nothing here names a cell: a
new cell is new data files and entries.

Entry the window drives: ``ServingEngine._step(t)``, the engine's only
per-step entry. Requests are pushed into ``engine.queue`` at their due
times; every step ends with ``block_until_ready`` on the engine's state,
and a token's time is the end of the step that emitted it.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import sys
import time
from contextlib import contextmanager, nullcontext
from pathlib import Path

import numpy as np

from bench import traffic as traffic_mod

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: seconds of the window, at its end, that a ``--trace 1`` run records
TRACE_SECONDS = 4.0
#: steps of the window whose attention output the K/V-path check reads
ATTN_SAMPLES = 8
#: served tokens the model check aims for (all requests are compared
#: while their tokens stay under this; the longest always is)
MODEL_CHECK_TOKENS = 4096


# -- the spec ------------------------------------------------------------------
def load_spec(workload: str) -> dict:
    """The workload's entry with its configuration and traffic loaded, and
    the metrics ``BENCHMARK.json`` asks of it."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    applies = lambda m: workload in m.get("workloads", [workload])
    return {"workload": wl,
            "config": json.loads((ROOT / cfg_entry["file"]).read_text()),
            "traffic": traffic_mod.load(BENCH / "traffic" / f"{wl['traffic']}.json"),
            "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
            "per_layer": [m for m in spec["per_layer"] if applies(m)]}


def peak_of(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json")
    return table[device_kind]


# -- the one place every percentile and rate is computed -----------------------
def _pct(xs: list[float], q: float) -> float | None:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else None


def window_metrics(recs: list, window_s: float, start: float = 0.0) -> dict:
    """End-to-end numbers over every sample of the whole window
    ``(start, start + window_s]``.

    ``recs`` carry ``due`` and ``token_times`` (end of the emitting step),
    on the clock ``start`` is read on. ``output_tok_s`` is every token
    emitted in the window over ``window_s``; ``itl`` every gap between
    consecutive tokens of one request whose later token falls in the
    window; ``ttft`` every request due in the window, counted to its first
    token or, without one, to the window's end.
    """
    end = start + window_s
    tokens = 0
    itl, ttft = [], []
    for r in recs:
        ts = [t for t in r.token_times if t <= end]
        inside = [t for t in ts if t > start]
        tokens += len(inside)
        itl.extend(b - a for a, b in zip(ts, ts[1:]) if b > start)
        if start <= r.due <= end:
            ttft.append((ts[0] if ts else end) - r.due)
    ms = lambda v: None if v is None else 1e3 * v
    return {"output_tok_s": tokens / window_s if window_s > 0 else None,
            "itl_p95_ms": ms(_pct(itl, 95)),
            "ttft_p50_ms": ms(_pct(ttft, 50)),
            "ttft_p95_ms": ms(_pct(ttft, 95)),
            "tokens": tokens, "itl_samples": len(itl), "ttft_samples": len(ttft)}


# -- compile accounting ----------------------------------------------------------
class CompileCounter:
    """Programs lowered and backend compiles, process-wide."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.lowered = self.compiled = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.LOWER:
            self.lowered += 1
        elif event == self.BACKEND:
            self.compiled += 1
            self.compile_s += duration

    def mark(self) -> tuple:
        return (self.lowered, self.compiled, self.compile_s)

    def since(self, mark: tuple) -> dict:
        return {"programs_lowered": self.lowered - mark[0],
                "backend_compiles": self.compiled - mark[1],
                "compile_s": self.compile_s - mark[2]}


# -- per-request record ----------------------------------------------------------
@dataclasses.dataclass
class Rec:
    item: traffic_mod.Item
    req: object
    prompt: np.ndarray | None
    token_times: list = dataclasses.field(default_factory=list)
    seen_prefilled: int = 0

    @property
    def due(self) -> float:
        return self.item.due


@dataclasses.dataclass
class StepRec:
    t0: float
    t1: float
    decode_lengths: list      # context length each decoding row attends over
    fed: list                 # (start position, tokens) the model consumed


# -- the cell --------------------------------------------------------------------
def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(cfg: dict):
    return _load_module(BENCH / "reference" / f"{cfg['reference']}.py")


def _seed32(seed: int) -> int:
    return int(seed) % (2**31 - 8)


def _bench_key(jax, seed: int):
    s = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0x7FFFFFFF), (s >> 31) & 0x7FFFFFFF)


def program_model_config(cfg: dict):
    """The program's ``ModelConfig`` for a model configuration file: the
    registry entry named by ``program_config`` with every size the file
    states taken from the file."""
    from repro import configs as cfglib
    base = cfglib.get_config(cfg["program_config"])
    kw = dict(n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
              n_heads=cfg["num_attention_heads"],
              n_kv_heads=cfg["num_key_value_heads"],
              d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
              rope_theta=float(cfg["rope_theta"]),
              norm_eps=float(cfg["rms_norm_eps"]),
              tie_embeddings=bool(cfg["tie_word_embeddings"]),
              dtype=cfg["torch_dtype"])
    if cfg.get("head_dim"):
        kw["d_head"] = cfg["head_dim"]
    return dataclasses.replace(base, **kw)


class Cell:
    """Executor, engine configuration and (for a model) its weights."""

    def __init__(self, spec: dict, seed: int, chips: int):
        import jax

        from repro.serving.engine import ServeConfig
        self.jax = jax
        self.cfg = cfg = spec["config"]
        self.traffic = spec["traffic"]
        self.seed = seed
        pmax, gmax = traffic_mod.max_context(self.traffic)
        self.serve_cfg = ServeConfig(
            requests=0, slots=int(self.traffic["slots"]), prompt_len=pmax,
            gen=gmax, page_size=int(cfg["page_size"]),
            shards=chips if chips > 1 else 1, seed=_seed32(seed))
        self.hq = int(cfg["num_attention_heads"])
        self.hkv = int(cfg["num_key_value_heads"])
        self.dh = int(cfg.get("head_dim") or cfg["hidden_size"] // self.hq)
        self.dtype = cfg["torch_dtype"]
        self.kind = cfg["executor"]
        self.ex = self._build_executor()
        self.ex.n_q_heads = self.hq

    def _build_executor(self):
        from repro.serving import executor as exmod
        if self.kind == "synthetic":
            return exmod.SyntheticExecutor(self.hkv, self.dh, self.dtype,
                                           seed=_seed32(self.seed))
        if self.kind != "model":
            raise ValueError(f"unknown executor {self.kind!r}")
        ref = reference_module(self.cfg)
        pcfg = program_model_config(self.cfg)
        orig = exmod.build_model
        jax = self.jax
        cfg = self.cfg

        def with_bench_weights(mcfg):
            model = orig(mcfg)
            want = jax.eval_shape(lambda k: model.init_params(k)[0],
                                  jax.random.PRNGKey(0))
            mine = jax.eval_shape(lambda k: ref.to_program(ref.make_weights(cfg, k)),
                                  jax.random.PRNGKey(0))
            if jax.tree.structure(want) != jax.tree.structure(mine) or any(
                    (a.shape, a.dtype) != (b.shape, b.dtype)
                    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(mine))):
                raise RuntimeError("the benchmark's weights do not match the "
                                   "program's parameter layout")
            return dataclasses.replace(model, init_params=lambda k: (
                ref.to_program(ref.make_weights(cfg, _bench_key(jax, self.seed))), None))

        exmod.build_model = with_bench_weights
        try:
            ex = exmod.ModelExecutor(pcfg, seed=_seed32(self.seed))
        finally:
            exmod.build_model = orig
        jax.block_until_ready(ex.params)
        return ex

    def free_program(self) -> None:
        """Drop the executor (weights, decode states) so the reference runs
        on a chip that holds none of the program's state."""
        self.ex = None
        gc.collect()

    def new_engine(self, executor=None):
        from repro.serving.engine import ServingEngine
        return ServingEngine(self.serve_cfg, executor or self.ex)

    def give_prompt(self, req, tokens: np.ndarray) -> None:
        if self.kind == "model":
            self.ex._prompts[req.req_id] = self.jax.numpy.asarray(tokens)

    # -- warm-up -----------------------------------------------------------------
    def warm_up(self, sizes: list[tuple[int, int]]) -> None:
        """Compile (or load from the persistent cache) every program the
        window runs: the engine's per-step programs at this cell's shapes,
        every prefill chunk length 1..prefill_chunk, and, for a model, the
        decode program of every request length the mix can send (its decode
        state is shaped by the request's prompt + output)."""
        from repro.serving.executor import SyntheticExecutor
        from repro.serving.request import PREFILL, Request
        jax = self.jax
        c = self.serve_cfg
        pc = c.prefill_chunk
        if self.kind == "model":
            lengths = sorted({p + g for p, g in sizes})
            for i in range(max(len(lengths), pc)):
                L, n = lengths[i % len(lengths)], i % pc + 1
                req = Request(req_id=-1 - i, prompt_len=n, gen=L - n)
                req.to(PREFILL, 0)
                self.give_prompt(req, np.zeros(n, np.int32))
                self.ex.begin(req)
                self.ex.prefill_chunk(req, n)
                req.advance_prefill(n, 0)
                self.ex.decode(req)
                self.ex.end(req)
        # the engine's own programs, driven by a model-free executor of the
        # same K/V geometry (scatter/K/V for every chunk length, sweep,
        # attention, pin, eviction and stream reset)
        syn = SyntheticExecutor(self.hkv, self.dh, self.dtype, seed=1)
        syn.n_q_heads = self.hq
        eng = self.new_engine(syn)
        for i in range(pc):
            eng.queue.push(Request(req_id=i, prompt_len=pc + i + 1, gen=2))
        t = 0
        while len(eng.queue) or eng.sched.active():
            eng._step(t)
            t += 1
        jax.block_until_ready((eng.tstate, eng.pool))
        del eng
        gc.collect()


# -- instrumentation the harness installs around the program ------------------
class AttentionCapture:
    """Wraps the engine module's ``tiered_attention`` to keep the output of
    the steps the harness asks for (no sync, no change to the call)."""

    def __init__(self):
        self.want: list | None = None      # [(slot, req_id, length)] to keep
        self.kept: list = []

    @contextmanager
    def installed(self):
        import repro.serving.engine as engmod
        orig = engmod.tiered_attention

        def wrapped(q, *a, **kw):
            out = orig(q, *a, **kw)
            if self.want is not None:
                self.kept.append((q, out[0], self.want))
                self.want = None
            return out

        engmod.tiered_attention = wrapped
        try:
            yield self
        finally:
            engmod.tiered_attention = orig


def _annotate(name: str, fn):
    import jax

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return wrapped


def _instrument(engine, ex) -> None:
    """Host annotations for a traced run, so the trace's idle gaps can be
    attributed to what the host was doing."""
    for name in ("_write_tokens", "_sweep_and_pin", "_evict"):
        setattr(engine, name, _annotate(f"engine.{name.strip('_')}", getattr(engine, name)))
    for name in ("prefill_chunk", "decode", "begin"):
        setattr(ex, name, _annotate(f"executor.{name}", getattr(ex, name)))


# -- correctness -------------------------------------------------------------------
def engine_gates(engine) -> int:
    """Failures of the engine's own invariants at the window's close: the
    tiered/flat attention pin of every step, and page conservation (pages
    allocated - recycled = pages in use = pages the active requests own)."""
    fails = 0 if engine.equiv_ok else 1
    s = engine.sched
    owned = sum(len(r.pages) for r in s.active())
    if not (s.pages_allocated - s.pages_recycled == engine.allocator.in_use == owned):
        fails += 1
    for r in engine.finished:
        if r.decoded != r.gen or len(r.tokens) != r.gen:
            fails += 1
    return fails


def holding_pages(recs: list):
    """``(request, positions written)`` of every request that still holds
    its pages at the window's close (a finished one has recycled them)."""
    for r in recs:
        req = r.req
        if req.slot < 0 or req.state not in ("PREFILL", "DECODE"):
            continue
        written = req.prefilled + max(req.decoded - 1, 0)
        if written:
            yield req, written


def pool_rows(engine, recs: list) -> dict:
    """Layer 0's K/V bytes in the paged pool of every request that still
    holds pages, ``req_id -> (k, v)`` as float32 ``[written, Hkv, dh]``."""
    out = {}
    for req, written in holding_pages(recs):
        pages = np.asarray(req.pages, np.int32)
        _, _, hkv, _, dh = engine.pool["k"].shape
        out[req.req_id] = tuple(
            np.asarray(engine.pool[n][0, pages].swapaxes(1, 2), np.float32)
            .reshape(-1, hkv, dh)[:written] for n in ("k", "v"))
    return out


def _worse(a: float, b: float) -> float:
    """The larger of two readings; NaN if either is, so a NaN reading
    fails its limit instead of dropping out of the maximum."""
    return float(np.maximum(a, b))


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def check_model(cell: Cell, recs: list, pool: dict, rng, controls: tuple = ()) -> dict:
    """Widest gap of a served token's logit below the reference's best, over
    a seeded sample of the requests that emitted tokens, the longest and
    one still holding pages in;
    and the pool's layer-0 K/V of the sampled requests still holding pages
    against the reference's (relative L2, the worst request). The
    reference's weights are made anew from the seed, once the program's
    are freed."""
    jax = cell.jax
    ref = reference_module(cell.cfg)
    served = sorted((r for r in recs if r.req.tokens), key=lambda r: -len(r.req.tokens))
    # the longest, then the requests whose pages the pool still holds, then
    # the rest, each group in a seeded order; the longest and one holder
    # always, the others while the served tokens stay under the budget
    holders = [r for r in served[1:] if r.req.req_id in pool]
    others = [r for r in served[1:] if r.req.req_id not in pool]
    order = served[:1] + [holders[i] for i in rng.permutation(len(holders))] \
        + [others[i] for i in rng.permutation(len(others))]
    pick, total = [], 0
    for r in order:
        first = not pick or (r.req.req_id in pool and not any(p.req.req_id in pool for p in pick))
        if not first and total + len(r.req.tokens) > MODEL_CHECK_TOKENS:
            continue
        pick.append(r)
        total += len(r.req.tokens)
    cfg = cell.cfg
    w = jax.jit(lambda k: ref.make_weights(cfg, k))(_bench_key(jax, cell.seed))
    worst = {"logit_gap": 0.0, "kv_rel_err": 0.0}
    for q in controls:
        worst[f"control_logit_gap.{q}"] = worst[f"control_kv_rel_err.{q}"] = 0.0
    kv_rows = 0
    # beside the widest gap: the gaps' sum and the positions whose token is
    # not the reference's best, over every compared position
    spread = {q: [0.0, 0] for q in (None,) + tuple(controls)}
    for r in pick:
        toks = list(r.req.tokens)
        for q in (None,) + tuple(controls):
            got = ref.compare(w, cell.cfg, r.prompt, toks, q)
            pre = "" if q is None else "control_"
            post = "" if q is None else f".{q}"
            g = got["gap" if q is None else "ctl_gap"]
            worst[f"{pre}logit_gap{post}"] = _worse(worst[f"{pre}logit_gap{post}"], g.max())
            spread[q][0] += float(g.sum())
            spread[q][1] += int((g > 0).sum())
            if r.req.req_id in pool:
                pk, pv = pool[r.req.req_id]
                k0, v0 = (got["k0"], got["v0"]) if q is None else (got["ctl_k0"], got["ctl_v0"])
                # the program's bytes against the reference; a control's
                # reference-at-lower-precision against the reference
                a = (pk, pv) if q is None else (k0, v0)
                err = _worse(_rel(a[0], got["k0"]), _rel(a[1], got["v0"]))
                worst[f"{pre}kv_rel_err{post}"] = _worse(worst[f"{pre}kv_rel_err{post}"], err)
                kv_rows += q is None
    for q, (s, n) in spread.items():
        post = "" if q is None else f".{q}"
        worst[f"logit_gap_mean{post}"] = s / max(total, 1)
        worst[f"token_miss_share{post}"] = n / max(total, 1)
    return {**worst, "compared_tokens": total, "compared_requests": len(pick),
            "kv_requests": kv_rows}


def check_kv_path(cell: Cell, engine, recs: list, capture: AttentionCapture,
                  controls: tuple = ()) -> dict:
    """K/V bytes in the paged pool for every request still holding pages,
    and the sampled steps' tiered attention output, against the plain
    reference."""
    jax = cell.jax
    jnp = jax.numpy
    ref = _load_module(BENCH / "reference" / "kv_attention.py")
    key = jax.random.PRNGKey(_seed32(cell.seed))
    T = engine.npps * cell.serve_cfg.page_size
    kv_cache: dict[int, tuple] = {}

    def ref_kv(req_id: int):
        if req_id not in kv_cache:
            kv_cache[req_id] = ref.kv(key, req_id, T, cell.hkv, cell.dh, cell.dtype)
        return kv_cache[req_id]

    mismatched = checked = requests = 0
    for req, written in holding_pages(recs):
        pages = np.zeros(engine.npps, np.int32)
        pages[:len(req.pages)] = req.pages
        k, v = ref_kv(req.req_id)
        mismatched += int(ref.mismatch(engine.pool["k"], engine.pool["v"],
                                       jnp.asarray(pages), written, k, v))
        checked += 2 * written * cell.hkv * cell.dh
        requests += 1
    worst = 0.0
    ctl = dict.fromkeys(controls, 0.0)
    rows = 0
    steps = len(capture.kept)
    for q, out, want in capture.kept:
        q = np.asarray(q.astype(jnp.float32))
        out = np.asarray(out.astype(jnp.float32))
        for slot, req_id, length in want:
            k, v = ref_kv(req_id)
            qs = jnp.asarray(q[slot, 0])
            exp = ref.attend(qs, k, v, length)
            worst = _worse(worst, ref.rel_err(out[slot, 0], exp))
            for c in controls:
                ctl[c] = _worse(ctl[c], ref.rel_err(ref.attend(qs, k, v, length, quant=c), exp))
            rows += 1
    out = {"kv_mismatch": mismatched, "kv_checked": checked, "kv_requests": requests,
           "attn_rel_err": worst, "attn_rows": rows, "attn_steps": steps}
    out.update({f"control_attn_rel_err.{c}": v for c, v in ctl.items()})
    return out


# -- the run -----------------------------------------------------------------------
def _device_info(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class Context:
    """What a per-layer metric reader sees (``bench/metrics/<name>.py``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        spec: dict | None = None, t_start: float | None = None,
        fault=None, controls: tuple = (), log=None) -> dict:
    """Run one cell once; returns the result line as a dict, with
    ``checks`` last. ``fault`` (tests) is called with the built cell and
    engine before the traffic starts, to break the timed path.
    ``controls`` (``"int8"``, ``"fp8"``) add the control reading of each
    compared number at those precisions (``bench/control.py``)."""
    import jax
    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda **kw: print(json.dumps(kw), flush=True))
    spec = spec or load_spec(workload)
    chips = int(spec["workload"]["chips"])
    counter = CompileCounter(jax)
    cell = Cell(spec, seed, chips)
    sizes = traffic_mod.size_set(cell.traffic)
    cell.warm_up(sizes)
    engine = cell.new_engine()
    if fault is not None:
        fault(cell, engine)
    gen = traffic_mod.make(cell.traffic, seed)
    preroll = int(cell.traffic.get("preroll_steps", 0))
    capture = AttentionCapture()
    rng = np.random.default_rng([int(seed), 7])
    # steps whose attention output is checked: drawn over the first 90% of
    # the window, so the last is never cut off by its close
    sample_at = sorted(rng.uniform(0, 0.9 * seconds, ATTN_SAMPLES))
    tracer = None
    if trace:
        from bench import trace_reduce
        tracer = trace_reduce.Recorder(jax)
        _instrument(engine, cell.ex)
    recs: dict[int, Rec] = {}
    steps: list[StepRec] = []
    lateness: list[float] = []
    vocab = int(cell.cfg["vocab_size"])
    mark = counter.mark()
    gc.collect()
    gc.disable()
    from repro.serving.request import DECODE, Request
    annotate = (jax.profiler.TraceAnnotation if trace else lambda name: nullcontext())
    with capture.installed():
        # one clock from the pre-roll's start; the window is
        # (t_open, t_open + seconds] on it
        t0 = time.perf_counter()
        t_open = t_end = None
        trace_at = None
        step = n_fin = 0
        while True:
            now = time.perf_counter() - t0
            if t_open is None and step >= preroll:
                log(phase="preroll", steps=step, seconds=now, **counter.since(mark))
                n_sweep = len(engine.reg.histogram("tiered_sweep").samples)
                counters0 = _counter_totals(engine)
                mark = counter.mark()
                now = t_open = time.perf_counter() - t0
                setup_s = t0 + now - t_start
                sample_at = [t_open + x for x in sample_at]
                if tracer is not None:
                    trace_at = t_open + max(seconds - TRACE_SECONDS, 0.0)
            if trace_at is not None and now >= trace_at:
                tracer.start(now)
                trace_at = None
            if t_open is not None and now >= t_open + seconds:
                break
            with annotate("bench.push"):
                for item in gen.ready(now):
                    req = Request(req_id=item.req_id, prompt_len=item.prompt_len,
                                  gen=item.gen, arrival_step=step)
                    prompt = (traffic_mod.prompt_tokens(seed, item.req_id,
                                                        item.prompt_len, vocab)
                              if cell.kind == "model" else None)
                    if prompt is not None:
                        cell.give_prompt(req, prompt)
                    engine.queue.push(req)
                    recs[item.req_id] = Rec(item, req, prompt)
                    lateness.append(now - item.due)
            active = engine.sched.active()
            if not active and not len(engine.queue):
                wake = [t for t in (gen.next_due(), trace_at) if t is not None]
                if t_open is not None:
                    wake.append(t_open + seconds)
                time.sleep(max(min(wake) - now, 0.0) if wake else 0.0)
                continue
            decode_lengths = [r.prefilled + r.decoded for r in active if r.state == DECODE]
            if sample_at and t_open is not None and now >= sample_at[0] and decode_lengths:
                sample_at.pop(0)
                capture.want = [(r.slot, r.req_id, r.prefilled + r.decoded)
                                for r in active if r.state == DECODE]
            fed = [(r.prefilled + r.decoded - 1, 1) for r in active if r.state == DECODE]
            with jax.profiler.StepTraceAnnotation("engine_step", step_num=step) \
                    if trace else nullcontext():
                engine._step(step)
                with annotate("bench.sync"):
                    jax.block_until_ready((engine.tstate, engine.pool))
            t_end = time.perf_counter() - t0
            with annotate("bench.record"):
                done = engine.finished[n_fin:]
                n_fin = len(engine.finished)
                for req in list(engine.sched.active()) + done:
                    rec = recs[req.req_id]
                    if req.prefilled > rec.seen_prefilled:
                        fed.append((rec.seen_prefilled, req.prefilled - rec.seen_prefilled))
                        rec.seen_prefilled = req.prefilled
                    new = len(req.tokens) - len(rec.token_times)
                    rec.token_times.extend([t_end] * new)
                for req in done:
                    gen.done(recs[req.req_id].item, t_end)
                if t_open is not None:
                    steps.append(StepRec(now, t_end, decode_lengths, fed))
            step += 1
        window_s = max(t_end - t_open, seconds) if steps else seconds
        if tracer is not None and tracer.on:
            tracer.stop(time.perf_counter() - t0)
    gc.enable()
    all_recs = list(recs.values())
    log(phase="window", seconds=window_s, steps=len(steps),
        requests_sent=sum(t_open <= r.due for r in all_recs),
        requests_finished=sum(bool(r.token_times) and r.req.state == "FINISHED"
                              and r.token_times[-1] > t_open for r in all_recs),
        **counter.since(mark),
        generator_late_s_max=max(lateness, default=0.0),
        generator_late_s_p50=_pct(lateness, 50) or 0.0)
    device = _device_info(jax, chips)
    counters = {k: v - counters0.get(k, 0) for k, v in _counter_totals(engine).items()}
    sweep = list(engine.reg.histogram("tiered_sweep").samples)[n_sweep:]
    gates = engine_gates(engine)
    limits = cell.cfg["limits"]
    if cell.kind == "model":
        pool = pool_rows(engine, all_recs)
        del engine
        cell.free_program()
        got = check_model(cell, all_recs, pool, rng, tuple(controls))
    else:
        got = check_kv_path(cell, engine, all_recs, capture, tuple(controls))
        del engine
    got["gate_failures"] = gates
    log(phase="check", **got)
    # each number against its limit: "max" limits what may go wrong,
    # "min" what must have been compared
    checks = {k: (got[k], lim, "max") for k, lim in limits["max"].items()}
    checks.update({k: (got[k], lim, "min") for k, lim in limits["min"].items()})
    correct = all(v <= lim if kind == "max" else v >= lim
                  for v, lim, kind in checks.values())
    summary = window_metrics(all_recs, window_s, t_open)
    if not trace:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in spec["end_to_end"]:
            if m["name"] in summary and summary[m["name"]] is not None:
                metrics[m["name"]] = {"value": summary[m["name"]], "unit": m["unit"]}
    else:
        reduced = tracer.reduce(chips)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        ctx = Context(cell=cell, cfg=cell.cfg, traffic=cell.traffic, chips=chips,
                      steps=steps, recs=all_recs, window_s=window_s, t_open=t_open,
                      trace=reduced, trace_t0=tracer.t0, trace_t1=tracer.t1,
                      sweep_samples=sweep, counters=counters,
                      peak=peak_of(device["kind"]) if device["platform"] == "tpu" else None,
                      work=_load_module(BENCH / "work.py"))
        metrics = {}
        for m in spec["per_layer"]:
            v = _load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": len(all_recs), "failed": 0,
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = reduced.breakdown()
    out["checks"] = {k: {"value": v, kind: lim} for k, (v, lim, kind) in checks.items()}
    return out


def _counter_totals(engine) -> dict:
    """Pool counters summed over every stream, previous occupants folded in
    (read after the window: it syncs)."""
    from repro.paging.tiered_kv import tiered_stats
    tot: dict[str, int] = {}
    for s in range(engine.cfg.slots):
        cur = tiered_stats(engine.tstate, s)
        for k, base in engine.counter_base[s].items():
            tot[k] = tot.get(k, 0) + int(base) + int(cur[k])
    return tot


def print_result(out: dict) -> None:
    for k, c in out["checks"].items():
        kind = "max" if "max" in c else "min"
        print(f"check {k}: {c['value']!r} ({kind} {c[kind]!r})", file=sys.stderr)
    print(json.dumps(out), flush=True)
