"""Jit'd wrapper for the page-gather kernel (arbitrary page payload shape)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import gather_pages_async_fwd, gather_pages_fwd
from .ref import gather_pages_ref


def _page_view(pool: jax.Array) -> jax.Array:
    """``[n_pages, ...page]`` -> ``[n_pages, rows, lanes]``: the page's
    trailing dim over everything else (a flat page is one row), so the
    kernels' page block spans both trailing dims whole."""
    if pool.ndim == 2:
        return pool[:, None, :]
    return pool.reshape(pool.shape[0], -1, pool.shape[-1])


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def gather_pages(pool: jax.Array, indices: jax.Array, *,
                 interpret: bool | None = None,
                 use_kernel: bool = True) -> jax.Array:
    """pool [n_pages, ...page shape], indices [K] -> [K, ...page shape].

    Synchronous pipelined gather: the Pallas emitter double-buffers the
    HBM->VMEM page DMAs behind the scenes. ``interpret=None`` auto-selects
    interpret mode off-TPU; ``use_kernel=False`` falls back to the jnp
    oracle. Out-of-range indices are clamped.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not use_kernel:
        return gather_pages_ref(pool.reshape(pool.shape[0], -1),
                                indices).reshape((indices.shape[0],)
                                                 + pool.shape[1:])
    out = gather_pages_fwd(_page_view(pool), indices.astype(jnp.int32),
                           interpret=interpret)
    return out.reshape((indices.shape[0],) + pool.shape[1:])


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def gather_pages_async(pool: jax.Array, indices: jax.Array, *,
                       interpret: bool | None = None,
                       use_kernel: bool = True) -> jax.Array:
    """Issue/wait gather: explicit ``make_async_copy`` pairs in the kernel.

    Same contract as :func:`gather_pages` (same shapes, dtypes, clamping);
    the difference is *who* overlaps the copies — the kernel issues the DMA
    for page k+1 before waiting on page k, the depth-2 collapse of the
    async data path's in-flight ring (DESIGN.md §4). Off-TPU
    (``interpret=None``) this runs in interpret mode, which emulates the
    semaphore waits — semantics preserved, no real overlap.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not use_kernel:
        return gather_pages_ref(pool.reshape(pool.shape[0], -1),
                                indices).reshape((indices.shape[0],)
                                                 + pool.shape[1:])
    out = gather_pages_async_fwd(_page_view(pool),
                                 indices.astype(jnp.int32),
                                 interpret=interpret)
    return out.reshape((indices.shape[0],) + pool.shape[1:])
