"""Batched decode: one ``decode_step`` over every decoding row per engine
step, against the batch-1 path it replaces.

* ``decode_step`` with a position per row matches one batch-1 step per row
  (logits and cache), and leaves idle rows (negative position) untouched;
* the engine over the smoke ``ModelExecutor`` serves the same tokens and
  mirrors the same K/V as the batch-1 path, with one batched call per
  decoding step, and lowers no program for request lengths it has not seen.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as cfglib
from repro.models.model import build_model
from repro.serving import ServeConfig, ServingEngine
from repro.serving.executor import ModelExecutor

#: a position per live row, staggered; the SWA config's window (8) is
#: passed by the last one, so its rolling cache wraps
POSITIONS = (3, 7, 12)
MAX_LEN = 16


def _row(tree, b):
    """Row ``b`` of a decode state's blocks (leaves ``[n_periods, B, ...]``)."""
    return jax.tree.map(lambda x: x[:, b:b + 1], tree)


@pytest.mark.parametrize("arch", ["qwen2_5_3b", "h2o_danube3_4b",
                                  "jamba_v01_52b"])
def test_decode_step_rows_match_batch_one(arch):
    """Attention (qwen2.5), sliding-window attention (danube3) and a
    mamba + MoE hybrid (jamba): rows at staggered positions decode as each
    would alone; a fourth row at position -1 keeps its state bit for bit."""
    cfg = cfglib.get_smoke_config(arch)
    model = build_model(cfg)
    params, _ = model.init_params(jax.random.PRNGKey(0))
    step = jax.jit(model.decode_step)
    prefill = jax.jit(model.prefill, static_argnums=2)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, max(POSITIONS) + 1),
                              0, cfg.vocab_size, jnp.int32)
    # each row's state after its own prompt; the idle row has one too
    ones = [prefill(params, {"tokens": toks[b:b + 1, :p]}, MAX_LEN)[1]
            for b, p in enumerate(POSITIONS + (5,))]
    blocks = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1),
                          *[s["blocks"] for s in ones])
    pos = jnp.asarray(POSITIONS + (-1,), jnp.int32)
    tok = toks[:, -1]
    logits, out = step(params, tok, {"blocks": blocks, "pos": pos})

    assert out["pos"].tolist() == [p + 1 for p in POSITIONS] + [-1]
    for b in range(len(POSITIONS)):
        want_logits, want = step(params, tok[b:b + 1], ones[b])
        assert int(want["pos"]) == POSITIONS[b] + 1
        np.testing.assert_allclose(logits[b:b + 1], want_logits,
                                   rtol=1e-5, atol=1e-5)
        for got_leaf, want_leaf in zip(jax.tree.leaves(_row(out["blocks"], b)),
                                       jax.tree.leaves(want["blocks"])):
            np.testing.assert_allclose(got_leaf, want_leaf,
                                       rtol=1e-5, atol=1e-5)
    for got_leaf, was in zip(jax.tree.leaves(_row(out["blocks"], 3)),
                             jax.tree.leaves(ones[3]["blocks"])):
        assert np.array_equal(np.asarray(got_leaf), np.asarray(was))
    assert np.isfinite(np.asarray(logits)).all()


# -- the engine over the smoke model ----------------------------------------
def _engine(ex, seed, batched=True):
    """An engine whose requests outnumber its slots, with staggered prompt
    and output lengths, their prompts given as the benchmark gives them.
    ``batched=False`` keeps the executor on the batch-1 path (its own
    decode state per request, one ``decode_step`` per request)."""
    if not batched:
        ex.set_geometry = lambda slots, max_len: None
    cfg = ServeConfig(requests=6, slots=3, prompt_len=6, gen=9, page_size=4,
                      prefill_chunk=1, length_jitter=0.6, arrival="bursty",
                      burst_len=4, seed=seed)
    eng = ServingEngine(cfg, ex)
    rng = np.random.default_rng(seed)
    for r in eng.queue._pending:
        ex._prompts[r.req_id] = jnp.asarray(
            rng.integers(0, ex.cfg.vocab_size, r.prompt_len), jnp.int32)
    return eng


def _drain(eng):
    t = 0
    while len(eng.queue) or eng.sched.active():
        eng._step(t)
        t += 1
    return eng._report(t, 0.0)


def _lengths(eng):
    return {r.max_len for r in eng.queue._pending}


def test_engine_batched_decode_matches_batch_one():
    cfg = cfglib.get_smoke_config("qwen2_5_3b")
    ex = ModelExecutor(cfg, seed=0)
    eng = _engine(ex, seed=3)
    calls = []
    real = ex._decode

    def counted(params, tok, state):
        calls.append(state["pos"].ndim)
        return real(params, tok, state)
    ex._decode = counted
    rep = _drain(eng)
    ref = _engine(ModelExecutor(cfg, seed=0), seed=3, batched=False)
    ref_rep = _drain(ref)

    assert rep["requests_finished"] == ref_rep["requests_finished"] == 6
    assert {r.req_id: r.tokens for r in eng.finished} == \
        {r.req_id: r.tokens for r in ref.finished}
    for n in ("k", "v"):
        np.testing.assert_allclose(eng.pool[n], ref.pool[n],
                                   rtol=1e-5, atol=1e-5)
    # one batched call per step that decodes (the steps that sweep), none
    # at batch 1 beyond the prompts' tokens
    decoding_steps = len(eng.reg.histogram("tiered_sweep").samples)
    assert calls.count(1) == decoding_steps > 0
    assert calls.count(0) == sum(r.prompt_len for r in eng.finished)
    assert rep["mean_decode_rows"] > 1

    # a second engine, whose requests bring lengths the first never had,
    # lowers no program once it is built: every shape is the engine's
    # geometry, and building it ran each model program once
    lowered = []

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(duration)
    again = _engine(ex, seed=11)
    assert _lengths(again) - _lengths(eng)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        rep = _drain(again)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert rep["requests_finished"] == 6
    assert lowered == []
