"""``correct`` comes out false for the control and for each fault the
cells can have: at smoke size on the CPU, through the real harness with
the timed path broken underneath, under the committed configurations'
upper limits."""

import contextlib

import jax.numpy as jnp
import pytest

import repro.serving.engine as engmod
import _smoke
from bench.reference import dense_gqa, kv_attention


def _failed(out, number):
    c = out["checks"][number]
    return c["value"] > c["max"] if "max" in c else c["value"] < c["min"]


def test_sound_runs_are_correct():
    for kind in ("model", "kv"):
        out = _smoke.run(kind)
        assert out["correct"], out["checks"]


# -- the control, put in the program's place ------------------------------------
def _weights_rounded_to(quant):
    """The program serves from its weight matrices rounded to ``quant``, one
    scale per output channel (the tied embedding per row)."""
    def fault(cell, engine):
        p = cell.ex.params
        rnd = lambda w, axis: dense_gqa._round(w.astype(jnp.float32), quant, axis).astype(w.dtype)
        for group in ("mix", "ff"):
            mats = p["period"][0][group]
            for k in [k for k in mats if k.startswith("w")]:
                mats[k] = rnd(mats[k], -2)
        p["embed"] = rnd(p["embed"], -1)
    return fault


def _kv_rounded_to(quant):
    """The model-free executor's K/V bytes rounded to ``quant`` (an int8 or
    fp8 KV cache), one scale per token and head."""
    def fault(cell, engine):
        orig = cell.ex._kv

        def kv(req, start, n):
            k, v = orig(req, start, n)
            return tuple(kv_attention._round(x.astype(jnp.float32), quant).astype(x.dtype)
                         for x in (k, v))
        cell.ex._kv = kv
    return fault


@pytest.mark.parametrize("kind,fault,number", [
    ("model", _weights_rounded_to("fp8"), "kv_rel_err"),
    ("kv", _kv_rounded_to("int8"), "kv_mismatch"),
    ("kv", _kv_rounded_to("fp8"), "attn_rel_err"),
])
def test_control_in_the_programs_place_is_not_correct(kind, fault, number):
    out = _smoke.run(kind, fault=fault)
    assert not out["correct"]
    assert _failed(out, number), out["checks"]


# -- faults of the timed path ----------------------------------------------------
def _token_altered(cell, engine):
    orig = cell.ex.decode

    def decode(req):
        k, v, tok = orig(req)
        return k, v, (tok + 1) % int(cell.cfg["vocab_size"])
    cell.ex.decode = decode


def _state_unchanged(cell, engine):
    orig = cell.ex._decode

    def step(params, tok, state):
        logits, _, k, v = orig(params, tok, state)
        return logits, state, k, v
    cell.ex._decode = step


def test_model_faults_are_caught():
    for fault in (_token_altered, _state_unchanged):
        out = _smoke.run("model", fault=fault)
        assert not out["correct"], (fault.__name__, out["checks"])
        assert _failed(out, "logit_gap")


def test_kv_byte_altered_is_caught(monkeypatch):
    orig = engmod._scatter_tokens

    def scatter(pool, pages, offs, k, v):
        return orig(pool, pages, offs, k.at[..., 0].add(1), v)
    out = _smoke.run("kv", fault=lambda c, e: monkeypatch.setattr(engmod, "_scatter_tokens", scatter))
    assert not out["correct"]
    assert _failed(out, "kv_mismatch")


def test_attention_output_altered_is_caught(monkeypatch):
    orig = engmod.tiered_attention

    def attn(*a, **kw):
        out, ok = orig(*a, **kw)
        return out * jnp.asarray(1.05, out.dtype), ok
    out = _smoke.run("kv", fault=lambda c, e: monkeypatch.setattr(engmod, "tiered_attention", attn))
    assert not out["correct"]
    assert _failed(out, "attn_rel_err")


def test_attention_output_nan_is_caught(monkeypatch):
    """A NaN answer fails its limit; it does not drop out of the maximum."""
    orig = engmod.tiered_attention

    def attn(*a, **kw):
        out, ok = orig(*a, **kw)
        return out.at[0].set(jnp.nan), ok
    out = _smoke.run("kv", fault=lambda c, e: monkeypatch.setattr(engmod, "tiered_attention", attn))
    assert not out["correct"]
    assert not out["checks"]["attn_rel_err"]["value"] <= out["checks"]["attn_rel_err"]["max"]


def test_half_of_the_batch_left_out_is_caught(monkeypatch):
    """Attention computed for the first half of the slots; the rest get
    the first half's rows."""
    orig = engmod.tiered_attention

    def attn(*a, **kw):
        out, ok = orig(*a, **kw)
        half = out.shape[0] // 2
        return out.at[half:].set(out[:out.shape[0] - half]), ok
    out = _smoke.run("kv", fault=lambda c, e: monkeypatch.setattr(engmod, "tiered_attention", attn))
    assert not out["correct"]
    assert _failed(out, "attn_rel_err")


def test_sweep_returning_its_state_unchanged_is_caught(monkeypatch):
    orig = engmod.tiered_sweep

    def sweep(state, *a, **kw):
        _, info = orig(state, *a, **kw)
        return state, info
    out = _smoke.run("kv", fault=lambda c, e: monkeypatch.setattr(engmod, "tiered_sweep", sweep))
    assert not out["correct"]


def test_nothing_compared_is_not_correct(monkeypatch):
    """A consumer called under another name leaves the attention check with
    nothing to read; that fails, it does not pass."""
    monkeypatch.setattr(_smoke.harness.AttentionCapture, "installed",
                        lambda self: contextlib.nullcontext(self))
    out = _smoke.run("kv")
    assert not out["correct"]
    assert _failed(out, "attn_steps")
