"""Paged KV cache: pool + page table + append + attention.

Layout per layer stack: ``k_pool/v_pool [n_pages, Hkv, page_size, dh]``
(head-major, so each page's per-head tile is a whole ``(page_size, dh)``
TPU tile) with the page dim shardable over the mesh — pages of a
sequence's context live round-robin across chips, which *is* the
disaggregated memory pool of the paper (each chip contributes "remote
memory" for everyone else's sequences).
``page_table [B, n_pages_per_seq]`` maps logical to physical pages.

Two allocators:
* :func:`linear_page_table` — static round-robin layout for fixed-shape
  serving (dry-run / benchmarks): physical page = b * npps + j, interleaved
  so consecutive logical pages land on different shards.
* :class:`PageAllocator` — host-side free-list for the dynamic serving loop
  (continuous batching): O(1) alloc/free per page, no device sync.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import paged_attention


def init_paged_kv(n_layers: int, n_pages: int, page_size: int, n_kv_heads: int,
                  head_dim: int, dtype=jnp.bfloat16) -> dict:
    """Zeroed KV pool: ``{"k","v"}`` each ``[L, n_pages, Hkv, page, dh]``
    of ``dtype`` (default bf16). The page dim is the mesh-shardable
    disaggregated tier (see :func:`kv_pool_specs`)."""
    sh = (n_layers, n_pages, n_kv_heads, page_size, head_dim)
    return {"k": jnp.zeros(sh, dtype), "v": jnp.zeros(sh, dtype)}


def kv_pool_specs(n_layers: int) -> dict:
    """Logical axes: page dim sharded (the disaggregated tier)."""
    ax = ("layers", "pages", "kv_heads_s", None, None)
    return {"k": ax, "v": ax}


def linear_page_table(batch: int, n_pages_per_seq: int,
                      stride: int = 1) -> jax.Array:
    """Static allocation: seq b's logical page j -> b*npps + (j*stride % npps).

    ``stride`` spreads a sequence's logical pages over its physical range
    (consecutive logical pages land ``stride`` physical pages apart, e.g. on
    different shards). ``j -> j*stride % npps`` is a permutation of
    ``[0, npps)`` only when ``gcd(stride, npps) == 1``; any other stride
    collides physical pages within the sequence (stride=2, npps=4 maps
    logical pages to 0,2,0,2 — two logical pages silently sharing storage),
    so non-coprime strides are rejected.

    Returns ``int32[batch, n_pages_per_seq]`` of physical page ids.
    """
    if math.gcd(stride, n_pages_per_seq) != 1:
        raise ValueError(
            f"stride={stride} is not coprime with n_pages_per_seq="
            f"{n_pages_per_seq}: j*stride % npps would collide physical "
            "pages within a sequence")
    base = jnp.arange(batch)[:, None] * n_pages_per_seq
    return (base + (jnp.arange(n_pages_per_seq)[None, :] * stride)
            % n_pages_per_seq).astype(jnp.int32)


def append_kv(pool: dict, layer: jax.Array, k_new: jax.Array, v_new: jax.Array,
              page_table: jax.Array, pos: jax.Array) -> dict:
    """Write one token's K/V for every sequence at position ``pos``.

    ``k_new``/``v_new`` are ``[B, Hkv, dh]`` (cast to the pool dtype); pool
    leaves are ``[L, n_pages, Hkv, page, dh]``; ``layer``/``pos`` are scalar
    int32. Returns the updated pool dict (functional, jit/scan-safe).
    """
    page_size = pool["k"].shape[3]
    B = k_new.shape[0]
    logical = pos // page_size
    offset = pos % page_size
    phys = page_table[jnp.arange(B), logical]            # [B]

    def write(buf, new):
        return buf.at[layer, phys, :, offset].set(new.astype(buf.dtype))

    return {"k": write(pool["k"], k_new), "v": write(pool["v"], v_new)}


def paged_decode_attention(q: jax.Array, pool: dict, layer: jax.Array,
                           page_table: jax.Array, lengths: jax.Array, *,
                           use_kernel: bool = False) -> jax.Array:
    """Decode attention: ``q [B,1,Hq,dh]`` against layer ``layer``.

    ``page_table`` is ``int32[B, npps]``, ``lengths`` ``int32[B]`` valid
    context tokens per sequence. Returns ``[B, 1, Hq, dh]`` in q's dtype.
    """
    k_pool = pool["k"][layer]
    v_pool = pool["v"][layer]
    return paged_attention(q, k_pool, v_pool, page_table, lengths,
                           use_kernel=use_kernel)


@dataclasses.dataclass
class PageAllocator:
    """Host-side page free-list (control plane for continuous batching).

    Besides the free list it keeps two pieces of bookkeeping the serving
    engine's admission/eviction discipline leans on:

    * **occupancy introspection** — :meth:`alive` (live sequence ids),
      :attr:`free_count` and :meth:`occupancy`, so an admission policy can
      reserve capacity without poking at internals.
    * **reuse seq-stamps** — every allocation event bumps a monotone
      generation counter and stamps the handed-out pages with it
      (:meth:`stamp_of`). A physical page recycled from a finished request
      and re-allocated to a new one therefore carries a *different* stamp;
      trace events keyed by ``(page, stamp)`` can never alias the previous
      owner's lifecycle (the slot-reuse aliasing guard of DESIGN.md §10).
    """

    n_pages: int

    def __post_init__(self):
        self.free = list(range(self.n_pages - 1, -1, -1))
        self.owned: dict[int, list[int]] = {}
        self._stamp = [0] * self.n_pages
        self._next_stamp = 1

    def alloc_seq(self, seq_id: int, n: int) -> list[int]:
        if len(self.free) < n:
            raise MemoryError(f"pool exhausted: need {n}, have {len(self.free)}")
        pages = [self.free.pop() for _ in range(n)]
        self.owned.setdefault(seq_id, []).extend(pages)
        for p in pages:
            self._stamp[p] = self._next_stamp
        self._next_stamp += 1
        return pages

    def extend_seq(self, seq_id: int, n: int = 1) -> list[int]:
        return self.alloc_seq(seq_id, n)

    def free_seq(self, seq_id: int) -> int:
        pages = self.owned.pop(seq_id, [])
        self.free.extend(reversed(pages))
        return len(pages)

    def recycle(self, pages) -> int:
        """Forcibly reclaim ``pages`` from whichever sequences own them.

        The node-death path (DESIGN.md §9): when a shard dies, the pages it
        physically held are yanked out from under their sequences and
        returned to the free list so re-homed replacements can be allocated.
        Pages that are already free (or unknown) are skipped. Returns the
        number of pages actually reclaimed; the free list is extended in
        descending page order so subsequent allocs stay deterministic.
        """
        want = set(int(p) for p in pages) - set(self.free)
        reclaimed = []
        for seq_id, owned in self.owned.items():
            keep = [p for p in owned if p not in want]
            reclaimed.extend(p for p in owned if p in want)
            owned[:] = keep
        self.owned = {s: o for s, o in self.owned.items() if o}
        self.free.extend(sorted(reclaimed, reverse=True))
        return len(reclaimed)

    @property
    def in_use(self) -> int:
        return self.n_pages - len(self.free)

    @property
    def free_count(self) -> int:
        return len(self.free)

    def occupancy(self) -> float:
        """Fraction of the pool currently allocated (0.0 at baseline)."""
        return self.in_use / self.n_pages

    def alive(self) -> tuple[int, ...]:
        """Sequence ids that currently own at least one page, sorted."""
        return tuple(sorted(self.owned))

    def owner_of(self, page: int) -> int | None:
        """Sequence id owning ``page``, or None if free/unknown."""
        for seq_id, pages in self.owned.items():
            if page in pages:
                return seq_id
        return None

    def stamp_of(self, page: int) -> int:
        """Allocation-generation stamp of ``page`` (0 = never allocated).

        Strictly increases every time the page is handed out again, so a
        recycled page re-allocated to a new request never shares a stamp
        with its previous life.
        """
        return self._stamp[page]
