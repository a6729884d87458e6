"""Pallas TPU paged-attention decode: one query token vs a paged KV pool.

vLLM-style paged KV adapted to TPU: KV lives in a head-major page pool
[n_pages, Hkv, page_size, dh]; each sequence's logical context is a
page_table row. Head-major keeps every (page, kv-head) tile's last two dims
``(page_size, dh)``, which is what the TPU tiling admits: the DMA'd block
is a whole ``(page_size, dh)`` tile (a multiple of 16 tokens for bf16),
never a one-row slice of a small ``Hkv`` axis. The kernel fuses Leap's
data path with the consumer: the page_table is a scalar-prefetch operand,
so each (batch, kv-head, page) grid step DMAs exactly the page the table
names — gather and attention in one pass, no [B, T, ...] contiguous cache
ever materializes (that contiguous copy is the "block layer" overhead this
kernel deletes).

Online softmax state (m, l, acc) for the G grouped q-heads lives in VMEM
scratch across the page sweep (pages innermost). Padded/unused trailing
pages are masked by the sequence length (also scalar-prefetched); invalid
page-table entries (negative, or past the pool edge) are masked the same
way — the DMA is clamped onto a real page so it stays well-formed, but the
masked scores guarantee those bytes never reach the output (no silent
garbage reads from a poisoned table).

VMEM per step: k/v page tiles 2 x page_size x dh x 2 B (bf16, + q tile
G x dh) — page_size 64, dh 128 ≈ 32 KB: DMA-latency-bound, exactly the
regime where prefetch-ahead (issuing the next page's DMA early) pays,
mirroring the paper's timeliness axis.

Three entry points share one per-page online-softmax update
(:func:`_attend_page` — identical op sequence, which is what keeps their
outputs **bit-identical** on the same bytes):

* :func:`paged_attention_fwd` — flat pool ``[n_pages, Hkv, page, dh]``.
* :func:`paged_attention_hot_slots_fwd` — the tiered hot tier
  ``[S, n_slots, Hkv, page, dh]`` read *in place* through a per-stream
  slot table: the BlockSpec index map composes the ``[S, npps] -> slot``
  indirection (stream s, slot ``slot_table[s, j]``) so the demand sweep
  lands pages and attention consumes them with **no stacked
  ``[S * n_slots, ...]`` hot-pool materialization** (the per-step copy the
  unfused path pays). Non-resident entries (slot < 0) are masked, never
  silently read.
* :func:`paged_attention_hot_slots_async_fwd` — same contract, but the
  hot pools stay in HBM (memory_space=ANY) and the kernel itself
  double-buffers the K/V page tiles with explicit ``pltpu.make_async_copy``
  issue/wait pairs in the style of ``gather_pages_async``: page j+1's
  tiles are in flight while page j is attended.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attend_page(q, k, v, mask, m_prev, l_prev, acc_prev):
    """One page's online-softmax update for G grouped q-heads.

    ``q [G, dh]`` (pre-scaled), ``k/v [page_size, dh]`` (float32),
    ``mask [G, page_size]``; returns the updated ``(m, l, acc)``. Every
    kernel variant funnels through this exact op sequence, so two variants
    fed the same bytes in the same page order produce bit-identical
    outputs — the property the tiered/flat equivalence pin leans on.
    """
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [G, page_size]
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
    corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_safe))
    l_new = l_prev * corr + p.sum(-1, keepdims=True)
    acc_new = (acc_prev * corr
               + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
    return m_new, l_new, acc_new


def _page_mask(shape, j, page_size, length, valid):
    """Token mask for page j: inside the sequence length AND a valid table
    entry (``valid`` False masks the whole page — poisoned/non-resident)."""
    tpos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (tpos < length) & valid


def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *,
                  sm_scale: float, page_size: int, n_pages_per_seq: int,
                  n_pages: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * sm_scale       # [G, dh]
    k = k_ref[0, 0].astype(jnp.float32)                  # [page_size, dh]
    v = v_ref[0, 0].astype(jnp.float32)
    pt = pt_ref[b * n_pages_per_seq + j]
    mask = _page_mask((q.shape[0], page_size), j, page_size, len_ref[b],
                      (pt >= 0) & (pt < n_pages))
    m_scr[...], l_scr[...], acc_scr[...] = _attend_page(
        q, k, v, mask, m_scr[...], l_scr[...], acc_scr[...])

    @pl.when(j == n_pages_per_seq - 1)
    def _write():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                       ).astype(o_ref.dtype)


def paged_attention_fwd(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        page_table: jax.Array, lengths: jax.Array, *,
                        sm_scale: float | None = None,
                        interpret: bool = True) -> jax.Array:
    """q [B,Hkv,G,dh]; pools [n_pages,Hkv,page_size,dh];
    page_table [B,n_pages_per_seq] int32; lengths [B] int32 -> [B,Hkv,G,dh].

    Invalid table entries (< 0 or >= n_pages) are masked out of the
    softmax; the in-range DMA clamp only keeps the access well-formed.
    """
    B, Hkv, G, dh = q.shape
    n_pages, page_size = k_pool.shape[0], k_pool.shape[2]
    npps = page_table.shape[1]
    pt_flat = page_table.reshape(-1)          # raw: the body masks invalid

    def q_map(b, h, j, pt, ln):
        return (b, h, 0, 0)

    def kv_map(b, h, j, pt, ln):
        return (jnp.clip(pt[b * npps + j], 0, n_pages - 1), h, 0, 0)

    kernel = functools.partial(
        _paged_kernel, sm_scale=sm_scale or 1.0 / (dh ** 0.5),
        page_size=page_size, n_pages_per_seq=npps, n_pages=n_pages)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, npps),
        in_specs=[
            pl.BlockSpec((1, 1, G, dh), q_map),
            pl.BlockSpec((1, 1, page_size, dh), kv_map),
            pl.BlockSpec((1, 1, page_size, dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, G, dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, dh), q.dtype),
        interpret=interpret,
    )(pt_flat, lengths.astype(jnp.int32), q, k_pool, v_pool)


# --------------------------------------------------------------------------
# Fused hot-slot variants: attention straight through the tiered hot pool
# --------------------------------------------------------------------------
def _hot_slots_kernel(st_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                      m_scr, l_scr, acc_scr, *,
                      sm_scale: float, page_size: int, n_pages_per_seq: int,
                      n_slots: int):
    s = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * sm_scale       # [G, dh]
    k = k_ref[0, 0, 0].astype(jnp.float32)               # [page_size, dh]
    v = v_ref[0, 0, 0].astype(jnp.float32)
    slot = st_ref[s * n_pages_per_seq + j]
    mask = _page_mask((q.shape[0], page_size), j, page_size, len_ref[s],
                      (slot >= 0) & (slot < n_slots))
    m_scr[...], l_scr[...], acc_scr[...] = _attend_page(
        q, k, v, mask, m_scr[...], l_scr[...], acc_scr[...])

    @pl.when(j == n_pages_per_seq - 1)
    def _write():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                       ).astype(o_ref.dtype)


def paged_attention_hot_slots_fwd(q: jax.Array, k_hot: jax.Array,
                                  v_hot: jax.Array, slot_table: jax.Array,
                                  lengths: jax.Array, *,
                                  sm_scale: float | None = None,
                                  interpret: bool = True) -> jax.Array:
    """q [S,Hkv,G,dh]; hot pools [S,n_slots,Hkv,page_size,dh];
    slot_table [S,npps] int32 *per-stream* slot ids; lengths [S] int32
    -> [S,Hkv,G,dh].

    The BlockSpec index map composes the slot indirection — grid step
    (s, h, j) DMAs hot tile ``[s, slot_table[s, j], h]`` straight out
    of the stacked per-stream hot pool, so no flattened ``[S*n_slots, ...]``
    pool is ever materialized. Non-resident entries (slot < 0, or past the
    slot count) are masked out of the softmax, never silently read.
    """
    S, Hkv, G, dh = q.shape
    n_slots, page_size = k_hot.shape[1], k_hot.shape[3]
    npps = slot_table.shape[1]
    st_flat = slot_table.reshape(-1)          # raw: the body masks invalid

    def q_map(s, h, j, st, ln):
        return (s, h, 0, 0)

    def kv_map(s, h, j, st, ln):
        return (s, jnp.clip(st[s * npps + j], 0, n_slots - 1), h, 0, 0)

    kernel = functools.partial(
        _hot_slots_kernel, sm_scale=sm_scale or 1.0 / (dh ** 0.5),
        page_size=page_size, n_pages_per_seq=npps, n_slots=n_slots)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, Hkv, npps),
        in_specs=[
            pl.BlockSpec((1, 1, G, dh), q_map),
            pl.BlockSpec((1, 1, 1, page_size, dh), kv_map),
            pl.BlockSpec((1, 1, 1, page_size, dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, G, dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hkv, G, dh), q.dtype),
        interpret=interpret,
    )(st_flat, lengths.astype(jnp.int32), q, k_hot, v_hot)


def _hot_slots_async_kernel(st_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                            k_scr, v_scr, sem_ref, *,
                            sm_scale: float, page_size: int,
                            n_pages_per_seq: int, n_slots: int):
    """Manual issue/wait hot-slot attention (``gather_pages_async`` style).

    ``k_ref``/``v_ref`` stay in HBM; each page tile ``[page_size, dh]`` is
    DMA'd into one of two VMEM slots via ``pltpu.make_async_copy``, and the
    copy for page j+1 is *issued* before page j's is *waited* on — the
    in-flight ring collapsed to depth 2, so page j's attend overlaps page
    j+1's transfer. Softmax state rides the fori_loop carry (pages are a
    loop here, not a grid dim), through the same :func:`_attend_page`
    update as every other variant.
    """
    s = pl.program_id(0)
    h = pl.program_id(1)
    G, dh = q_ref.shape[2], q_ref.shape[3]
    npps = n_pages_per_seq

    def dma(hbm, scr, buf, j, which):
        slot = jnp.clip(st_ref[s * npps + j], 0, n_slots - 1)
        return pltpu.make_async_copy(hbm.at[s, slot, h],
                                     scr.at[buf], sem_ref.at[buf, which])

    dma(k_ref, k_scr, 0, 0, 0).start()       # warm-up: issue page 0
    dma(v_ref, v_scr, 0, 0, 1).start()
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale       # [G, dh]

    def body(j, carry):
        m_prev, l_prev, acc_prev = carry
        cur = jax.lax.rem(j, 2)
        nxt = jax.lax.rem(j + 1, 2)

        @pl.when(j + 1 < npps)
        def _():
            dma(k_ref, k_scr, nxt, j + 1, 0).start()  # prefetch page j+1
            dma(v_ref, v_scr, nxt, j + 1, 1).start()

        dma(k_ref, k_scr, cur, j, 0).wait()           # page j has landed
        dma(v_ref, v_scr, cur, j, 1).wait()
        k = k_scr[cur].astype(jnp.float32)            # [page_size, dh]
        v = v_scr[cur].astype(jnp.float32)
        slot = st_ref[s * npps + j]
        mask = _page_mask((G, page_size), j, page_size, len_ref[s],
                          (slot >= 0) & (slot < n_slots))
        return _attend_page(q, k, v, mask, m_prev, l_prev, acc_prev)

    m0 = jnp.full((G, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((G, 1), jnp.float32)
    acc0 = jnp.zeros((G, dh), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, npps, body, (m0, l0, acc0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_attention_hot_slots_async_fwd(q: jax.Array, k_hot: jax.Array,
                                        v_hot: jax.Array,
                                        slot_table: jax.Array,
                                        lengths: jax.Array, *,
                                        sm_scale: float | None = None,
                                        interpret: bool = True) -> jax.Array:
    """Same contract as :func:`paged_attention_hot_slots_fwd`, issue/wait
    form: the hot pools stay in HBM and the kernel double-buffers K/V page
    tiles with explicit ``make_async_copy`` pairs. VMEM footprint: 4 page
    tiles in flight (k+v, double-buffered) + the q/o blocks.
    """
    S, Hkv, G, dh = q.shape
    n_slots, page_size = k_hot.shape[1], k_hot.shape[3]
    npps = slot_table.shape[1]
    st_flat = slot_table.reshape(-1)

    def q_map(s, h, st, ln):
        return (s, h, 0, 0)

    kernel = functools.partial(
        _hot_slots_async_kernel, sm_scale=sm_scale or 1.0 / (dh ** 0.5),
        page_size=page_size, n_pages_per_seq=npps, n_slots=n_slots)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, Hkv),
        in_specs=[
            pl.BlockSpec((1, 1, G, dh), q_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, G, dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, page_size, dh), k_hot.dtype),
            pltpu.VMEM((2, page_size, dh), v_hot.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hkv, G, dh), q.dtype),
        interpret=interpret,
    )(st_flat, lengths.astype(jnp.int32), q, k_hot, v_hot)
