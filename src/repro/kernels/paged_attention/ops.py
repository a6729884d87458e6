"""Jit'd wrappers for paged decode attention ([B,1,Hq,dh] model layout)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import (
    paged_attention_fwd,
    paged_attention_hot_slots_async_fwd,
    paged_attention_hot_slots_fwd,
)
from .ref import paged_attention_hot_slots_ref, paged_attention_ref


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def paged_attention(q, k_pool, v_pool, page_table, lengths, *,
                    interpret: bool | None = None, use_kernel: bool = True):
    """q [B,1,Hq,dh] (model layout) vs head-major pools
    [n_pages,Hkv,page,dh] -> [B,1,Hq,dh].

    Invalid page-table entries (< 0 or >= n_pages) are masked out of the
    softmax by both the kernel and the ref — a poisoned table never
    silently contributes page 0's bytes.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, one, Hq, dh = q.shape
    Hkv = k_pool.shape[1]
    G = Hq // Hkv
    qg = q[:, 0].reshape(B, Hkv, G, dh)
    fn = paged_attention_fwd if use_kernel else paged_attention_ref
    kw = {"interpret": interpret} if use_kernel else {}
    o = fn(qg, k_pool, v_pool, page_table.astype(jnp.int32),
           lengths.astype(jnp.int32), sm_scale=1.0 / (dh ** 0.5), **kw)
    return o.reshape(B, 1, Hq, dh)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "use_kernel", "async_copy"))
def paged_attention_hot_slots(q, k_hot, v_hot, slot_table, lengths, *,
                              interpret: bool | None = None,
                              use_kernel: bool = True,
                              async_copy: bool = False):
    """Fused hot-slot decode attention: q [S,1,Hq,dh] (model layout) vs the
    tiered hot pools [S,n_slots,Hkv,page,dh] read in place through the
    *per-stream* slot_table [S,npps] — no stacked [S*n_slots,...] pool.

    Entries < 0 or >= n_slots (non-resident / poisoned) are masked out of
    the softmax. ``async_copy=True`` selects the explicit make_async_copy
    double-buffered kernel; both kernel variants are bit-identical to each
    other and to the flat-pool kernel on equivalent bytes.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    S, one, Hq, dh = q.shape
    Hkv = k_hot.shape[2]
    G = Hq // Hkv
    qg = q[:, 0].reshape(S, Hkv, G, dh)
    if use_kernel:
        fn = (paged_attention_hot_slots_async_fwd if async_copy
              else paged_attention_hot_slots_fwd)
        kw = {"interpret": interpret}
    else:
        fn, kw = paged_attention_hot_slots_ref, {}
    o = fn(qg, k_hot, v_hot, slot_table.astype(jnp.int32),
           lengths.astype(jnp.int32), sm_scale=1.0 / (dh ** 0.5), **kw)
    return o.reshape(S, 1, Hq, dh)
