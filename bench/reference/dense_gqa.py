"""Plain float32 reference of a dense grouped-query-attention decoder.

The Qwen2 / Llama block as the Hugging Face ``config.json`` states it:
RMSNorm, Q/K/V projections (with biases where ``model_type`` is ``qwen2``
or ``attention_bias`` is set), rotary embeddings on the half-split pairs,
causal softmax attention with ``num_key_value_heads`` shared K/V heads, a
SwiGLU MLP, a final RMSNorm and the (tied) LM head. Every matmul runs in
float32 at ``HIGHEST`` precision. No cache, no paging, no batching, no
kernel: one sequence at a time through the whole causal forward. It imports
nothing of the program under test.

The benchmark also makes the weights here (:func:`make_weights`), from the
seed, in the dtype the configuration serves (``torch_dtype``), and hands
the program the same arrays in its own layout (:func:`to_program`).

:func:`compare` gives the numbers that decide ``correct`` for a served
model: for each served token, how far its reference logit lies below the
reference's best logit at that position, and the reference's layer-0 K/V,
which the paged pool mirrors. A ``quant`` of ``"int8"`` or
``"fp8"`` runs the same forward with every weight matrix rounded to that
precision (per output channel), the control of the comparison: its gap is
read at the token the lower precision puts first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

#: the weight matrices of a layer (rounded by a control)
_MATS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"L": cfg["num_hidden_layers"], "D": d, "Hq": hq, "Hkv": hkv,
            "dh": int(cfg.get("head_dim") or d // hq),
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "bias": bool(cfg.get("attention_bias", cfg.get("model_type") == "qwen2")),
            "tied": bool(cfg.get("tie_word_embeddings", False))}


def _shapes(m: dict) -> dict:
    L, D, Hq, Hkv, dh, F = m["L"], m["D"], m["Hq"], m["Hkv"], m["dh"], m["F"]
    layer = {"ln1": (L, D), "wq": (L, D, Hq * dh), "wk": (L, D, Hkv * dh),
             "wv": (L, D, Hkv * dh), "wo": (L, Hq * dh, D), "ln2": (L, D),
             "wg": (L, D, F), "wu": (L, D, F), "wd": (L, F, D)}
    if m["bias"]:
        layer.update(bq=(L, Hq * dh), bk=(L, Hkv * dh), bv=(L, Hkv * dh))
    out = {"embed": (m["V"], D), "final_norm": (D,), "layers": layer}
    if not m["tied"]:
        out["lm_head"] = (D, m["V"])
    return out


def make_weights(cfg: dict, key) -> dict:
    """Random weights from ``key``, in ``torch_dtype``: matrices
    N(0, 1/fan_in), biases N(0, 0.1^2), norm scales 1 + N(0, 0.1^2),
    embedding N(0, 1/hidden_size), so that the tied head's logits have
    unit spread at any width (0.022 at Qwen2.5-3B's 2048, near its
    published initializer range of 0.02). Trace it inside one
    ``jax.jit``."""
    m = dims(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])
    shapes = _shapes(m)
    flat, tree = jax.tree.flatten(shapes, is_leaf=lambda s: isinstance(s, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))[0]]
    leaves = []
    for i, (shape, path) in enumerate(zip(flat, paths)):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        name = path.split("'")[-2]
        if name in ("ln1", "ln2", "final_norm"):
            x = 1.0 + 0.1 * z
        elif name in ("bq", "bk", "bv"):
            x = 0.1 * z
        elif name == "embed":
            x = z / np.sqrt(shape[-1])
        else:
            x = z / np.sqrt(shape[-2])
        leaves.append(x.astype(dtype))
    return jax.tree.unflatten(tree, leaves)


def to_program(w: dict) -> dict:
    """The same arrays in the layout of ``repro.models.transformer``
    (one period position, leaves stacked over layers)."""
    lw = w["layers"]
    mix = {k: lw[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv") if k in lw}
    block = {"norm1": {"scale": lw["ln1"]}, "mix": mix,
             "norm2": {"scale": lw["ln2"]},
             "ff": {"wg": lw["wg"], "wu": lw["wu"], "wd": lw["wd"]}}
    out = {"embed": w["embed"], "period": (block,),
           "final_norm": {"scale": w["final_norm"]}}
    if "lm_head" in w:
        out["lm_head"] = w["lm_head"]
    return out


def from_program(p: dict) -> dict:
    """Inverse of :func:`to_program` (no copies)."""
    (block,) = p["period"]
    lw = {"ln1": block["norm1"]["scale"], "ln2": block["norm2"]["scale"],
          **block["mix"], **block["ff"]}
    out = {"embed": p["embed"], "final_norm": p["final_norm"]["scale"], "layers": lw}
    if "lm_head" in p:
        out["lm_head"] = p["lm_head"]
    return out


def _e4m3(y: jax.Array) -> jax.Array:
    """``y`` (float32, within +-448) rounded to the nearest float8 e4m3fn
    value, ties to even, in float32 arithmetic: no cast the compiler could
    fold away. Three mantissa bits; below 2^-6 the step stays 2^-9."""
    _, ex = jnp.frexp(y)
    step = jnp.exp2(jnp.maximum(ex - 1, -6).astype(jnp.float32) - 3.0)
    return jnp.round(y / step) * step


def _round(w: jax.Array, quant: str | None, axis: int) -> jax.Array:
    """``w`` (float32) rounded to ``quant`` with one scale per slice along
    ``axis`` (the output channel), returned in float32."""
    if quant is None:
        return w
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    if quant == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(w / s), -127, 127) * s
    if quant == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return _e4m3(jnp.clip(w / s, -448.0, 448.0)) * s
    raise ValueError(f"unknown quant {quant!r}")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    dh = x.shape[-1]
    half = dh // 2
    ang = pos[:, None].astype(jnp.float32) * theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _mm(x, w):
    return jnp.dot(x, w, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _logits(w: dict, tokens: jax.Array, m: tuple, quant: str | None):
    """Causal forward of one sequence ``tokens [T]`` -> logits ``[T, V]``
    and layer 0's K (after RoPE) and V, ``[T, Hkv, dh]`` each."""
    m = dict(m)
    T = tokens.shape[0]
    Hq, Hkv, dh = m["Hq"], m["Hkv"], m["dh"]
    G = Hq // Hkv
    pos = jnp.arange(T)
    f32 = lambda a: a.astype(jnp.float32)
    embed = f32(w["embed"])
    h = embed[tokens]
    causal = pos[None, :] <= pos[:, None]

    def layer(h, lw):
        lw = {k: f32(v) for k, v in lw.items()}
        for k in _MATS:
            lw[k] = _round(lw[k], quant, axis=0)
        x = _rms(h, lw["ln1"], m["eps"])
        q, k, v = _mm(x, lw["wq"]), _mm(x, lw["wk"]), _mm(x, lw["wv"])
        if m["bias"]:
            q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
        q = _rope(q.reshape(T, Hq, dh), pos, m["theta"]).reshape(T, Hkv, G, dh)
        k = _rope(k.reshape(T, Hkv, dh), pos, m["theta"])
        v = v.reshape(T, Hkv, dh)
        s = jnp.einsum("tkgd,skd->kgts", q, k, precision=HIGHEST) / np.sqrt(dh)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("kgts,skd->tkgd", p, v, precision=HIGHEST).reshape(T, Hq * dh)
        h = h + _mm(o, lw["wo"])
        x = _rms(h, lw["ln2"], m["eps"])
        h = h + _mm(jax.nn.silu(_mm(x, lw["wg"])) * _mm(x, lw["wu"]), lw["wd"])
        return h, (k, v)

    h, (ks, vs) = jax.lax.scan(layer, h, w["layers"])
    h = _rms(h, f32(w["final_norm"]), m["eps"])
    head = f32(w["lm_head"]) if "lm_head" in w else embed.T
    return _mm(h, _round(head, quant, axis=0)), ks[0], vs[0]


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _compare(w, tokens, targets, m, quant):
    """Per position: the reference's best logit minus its logit of
    ``targets`` (the served token); layer 0's K/V. With ``quant``, the same
    gap at the token the rounded forward puts first, and its layer-0 K/V."""
    ref, k0, v0 = _logits(w, tokens, m, None)
    best = ref.max(-1)
    gap = lambda tok: best - jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]
    out = {"gap": gap(targets), "k0": k0, "v0": v0}
    if quant is not None:
        ctl, ck, cv = _logits(w, tokens, m, quant)
        out.update(ctl_gap=gap(ctl.argmax(-1)), ctl_k0=ck, ctl_v0=cv)
    return out


def bucket(n: int, step: int = 512) -> int:
    return -(-n // step) * step


def compare(w: dict, cfg: dict, prompt: np.ndarray, served: list[int],
            quant: str | None = None) -> dict:
    """Reference readings of one request: ``prompt`` then the ``served``
    tokens fed back (teacher forcing), so position ``len(prompt) - 1 + i``
    predicts ``served[i]``. Returns numpy ``gap`` over the served positions
    and layer 0's ``k0``/``v0`` over the fed positions (prompt and all
    served tokens but the last); with ``quant`` also ``ctl_gap``,
    ``ctl_k0``, ``ctl_v0``. The sequence is padded to a bucket of 512
    (causal: padding after a position never reaches it), so few programs
    serve every length."""
    n = len(served)
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    T = bucket(len(seq))
    tokens = np.zeros(T, np.int32)
    tokens[:len(seq)] = seq
    targets = np.zeros(T, np.int32)
    p0 = len(prompt) - 1
    targets[p0:p0 + n] = served
    m = tuple(sorted(dims(cfg).items()))
    got = _compare(w, jnp.asarray(tokens), jnp.asarray(targets), m, quant)
    out = {}
    for k, v in got.items():
        v = np.asarray(v)
        out[k] = v[p0:p0 + n] if k.endswith("gap") else v[:len(seq)]
    return out
