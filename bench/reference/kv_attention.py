"""Plain reference of the K/V data path under a model-free executor.

The model-free executor stands in for a model by writing deterministic
bytes: the K and V of request ``r`` at position ``p`` are the two halves of
``normal(fold_in(fold_in(PRNGKey(seed), r), p), (2, Hkv, dh))`` in the
served dtype. This module makes the same bytes from the same seed, with no
pool, page table, hot tier or kernel, and computes decode attention over
them as a plain float32 softmax (query head ``h`` reads K/V head
``h // (Hq / Hkv)``). It imports nothing of the program under test.

``quant="int8"`` (or ``"fp8"``) is the control: the same attention with K
and V rounded to that precision, one scale per token and head (an int8 or
fp8 KV cache).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("n", "hkv", "dh", "dtype"))
def kv(key, req_id, n: int, hkv: int, dh: int, dtype: str):
    """K, V ``[n, Hkv, dh]`` of positions ``0..n-1`` of request ``req_id``."""
    def one(pos):
        kk = jax.random.fold_in(jax.random.fold_in(key, req_id), pos)
        x = jax.random.normal(kk, (2, hkv, dh), jnp.dtype(dtype))
        return x[0], x[1]

    return jax.vmap(one)(jnp.arange(n, dtype=jnp.int32))


@jax.jit
def mismatch(pool_k, pool_v, pages, written, k, v):
    """Elements of positions ``0..written-1`` whose bytes in a head-major
    paged pool (layer 0, ``pages`` in order) differ from ``k``/``v``."""
    T, hkv, dh = k.shape
    valid = (jnp.arange(T) < written)[:, None, None]

    def one(pool, want):
        got = pool[0, pages].swapaxes(1, 2).reshape(T, hkv, dh)
        return jnp.sum((got != want) & valid)

    return one(pool_k, k) + one(pool_v, v)


def _e4m3(y: jax.Array) -> jax.Array:
    """``y`` (float32, within +-448) rounded to the nearest float8 e4m3fn
    value, ties to even, in float32 arithmetic: no cast the compiler could
    fold away. Three mantissa bits; below 2^-6 the step stays 2^-9."""
    _, ex = jnp.frexp(y)
    step = jnp.exp2(jnp.maximum(ex - 1, -6).astype(jnp.float32) - 3.0)
    return jnp.round(y / step) * step


def _round(x: jax.Array, quant: str) -> jax.Array:
    """``x`` rounded to ``quant`` with one scale per token and head."""
    amax = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-30)
    if quant == "int8":
        s = amax / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if quant == "fp8":
        s = amax / 448.0
        return _e4m3(jnp.clip(x / s, -448.0, 448.0)) * s
    raise ValueError(f"unknown quant {quant!r}")


@functools.partial(jax.jit, static_argnames=("quant",))
def attend(q: jax.Array, k: jax.Array, v: jax.Array, length,
           quant: str | None = None) -> jax.Array:
    """``q [Hq, dh]`` over the first ``length`` rows of ``k``/``v``
    ``[T, Hkv, dh]`` -> ``[Hq, dh]`` float32."""
    Hq, dh = q.shape
    Hkv = k.shape[1]
    q = q.astype(jnp.float32).reshape(Hkv, Hq // Hkv, dh)
    k, v = k.astype(jnp.float32), v.astype(jnp.float32)
    if quant is not None:
        k, v = _round(k, quant), _round(v, quant)
    s = jnp.einsum("kgd,tkd->kgt", q, k, precision=HIGHEST) / np.sqrt(dh)
    s = jnp.where(jnp.arange(k.shape[0]) < length, s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("kgt,tkd->kgd", p, v, precision=HIGHEST).reshape(Hq, dh)


def rel_err(out, ref) -> float:
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(out - ref) / max(np.linalg.norm(ref), 1e-30))
