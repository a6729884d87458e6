"""Pallas TPU page-gather: Leap's lean data path, kernel form.

The paper's C4 contribution — bypass the block layer's staging/batching and
stream pages directly with per-core async queues — maps on TPU to a
scalar-prefetch-driven gather: the page-index list (what Leap's prefetcher
decided to fetch) is a scalar-prefetch operand, so the BlockSpec index_map
redirects each grid step's HBM->VMEM DMA straight at the requested page.
Pallas' pipeline emitter double-buffers those DMAs: page i+1 is in flight
while page i is written out — the "async RDMA queue" analogue, with zero
intermediate staging in HBM.

Block = one page, ``[rows, lanes]``: the wrapper views each page as its
trailing dim over everything else, so a head-major KV page
``[Hkv, page_size, dh]`` is the block ``(Hkv * page_size, dh)`` — both
trailing dims span the whole array, which the TPU tiling always admits.
VMEM per step = 2 pages in flight x page bytes; a 32 KB KV page (16 tok x
8 kv-heads x 128 dim x 2 B) uses 64 KB — far under v5e's ~16 MB VMEM, so
the pipeline stays DMA-bound, which is the point (roofline: pure memory
term).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(idx_ref, pool_ref, out_ref):
    # idx_ref is scalar-prefetch (drives the index_map); body is a pure copy.
    out_ref[...] = pool_ref[...]


def _gather_async_kernel(idx_ref, pool_ref, out_ref, sem_ref):
    """Manual issue/wait gather: explicit double-buffered async copies.

    ``pool_ref`` and ``out_ref`` both stay in HBM (memory_space=ANY); each
    requested page is DMA'd straight from its pool row into its output row
    via ``pltpu.make_async_copy``. The copy for page k+1 is *issued* before
    the copy for page k is *waited* on — the in-flight ring of the async
    data path (DESIGN.md §4) collapsed to depth 2, one DMA semaphore per
    ring slot.
    """
    K = out_ref.shape[0]

    def get_dma(slot, k):
        return pltpu.make_async_copy(
            pool_ref.at[idx_ref[k]],     # HBM page row
            out_ref.at[k],               # HBM output row
            sem_ref.at[slot])

    get_dma(0, 0).start()                # warm-up: issue page 0

    def body(k, carry):
        @pl.when(k + 1 < K)
        def _():
            get_dma(jax.lax.rem(k + 1, 2), k + 1).start()  # issue k+1

        get_dma(jax.lax.rem(k, 2), k).wait()  # wait: k's page has landed
        return carry

    jax.lax.fori_loop(0, K, body, None)


def gather_pages_fwd(pool: jax.Array, indices: jax.Array, *,
                     interpret: bool = True) -> jax.Array:
    """pool [n_pages, R, L], indices [K] int32 -> out [K, R, L].

    Out-of-range indices are clamped (callers mask invalid requests; the
    Leap controller emits candidates that may run off the pool edge).
    """
    n_pages, R, L = pool.shape
    K = indices.shape[0]
    idx = jnp.clip(indices, 0, n_pages - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(K,),
        in_specs=[pl.BlockSpec((1, R, L),
                               lambda i, idx_ref: (idx_ref[i], 0, 0))],
        out_specs=pl.BlockSpec((1, R, L), lambda i, idx_ref: (i, 0, 0)),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((K, R, L), pool.dtype),
        interpret=interpret,
    )(idx, pool)


def gather_pages_async_fwd(pool: jax.Array, indices: jax.Array, *,
                           interpret: bool = True) -> jax.Array:
    """pool [n_pages, R, L], indices [K] int32 -> out [K, R, L], issue/wait
    form.

    Functionally identical to :func:`gather_pages_fwd` (out-of-range indices
    clamped) but the page copies are explicit HBM->HBM
    ``pltpu.make_async_copy`` issue/wait pairs driven by the kernel itself,
    not the pipeline emitter — the kernel-level mirror of the
    ``pool_issue``/``pool_wait`` data path. No VMEM staging: 2 DMAs in
    flight.
    """
    n_pages, R, L = pool.shape
    K = indices.shape[0]
    idx = jnp.clip(indices, 0, n_pages - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((2,))],
    )
    return pl.pallas_call(
        _gather_async_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((K, R, L), pool.dtype),
        interpret=interpret,
    )(idx, pool)
