"""Pure-jnp oracle for paged decode attention: gather pages, exact softmax.

Both oracles mask *invalid table entries* (negative, or past the pool/slot
edge) out of the softmax, matching the kernels: the gather index is clipped
only so it stays in range, but a poisoned entry contributes nothing to the
output instead of silently reading page 0's bytes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _masked_softmax_attend(q, k, v, mask, sm_scale):
    """q [B,Hkv,G,dh]; k/v [B,T,Hkv,dh] f32; mask [B,T] -> [B,Hkv,G,dh]."""
    s = jnp.einsum("bhgd,bthd->bhgt", q.astype(jnp.float32), k) * sm_scale
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgt,bthd->bhgd", p, v).astype(q.dtype)


def paged_attention_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        page_table: jax.Array, lengths: jax.Array,
                        sm_scale: float | None = None) -> jax.Array:
    """Same contract as kernel.paged_attention_fwd."""
    B, Hkv, G, dh = q.shape
    n_pages, page_size = k_pool.shape[0], k_pool.shape[2]
    valid = (page_table >= 0) & (page_table < n_pages)   # [B, npps]
    pt = jnp.clip(page_table, 0, n_pages - 1)
    k = k_pool[pt].swapaxes(2, 3)                   # [B,npps,page,Hkv,dh]
    v = v_pool[pt].swapaxes(2, 3)
    B_, npps = pt.shape
    T = npps * page_size
    k = k.reshape(B, T, Hkv, dh).astype(jnp.float32)
    v = v.reshape(B, T, Hkv, dh).astype(jnp.float32)
    mask = jnp.arange(T)[None, :] < lengths[:, None]
    mask = mask & jnp.repeat(valid, page_size, axis=1)
    return _masked_softmax_attend(q, k, v, mask,
                                  sm_scale or 1.0 / (dh ** 0.5))


def paged_attention_hot_slots_ref(q: jax.Array, k_hot: jax.Array,
                                  v_hot: jax.Array, slot_table: jax.Array,
                                  lengths: jax.Array,
                                  sm_scale: float | None = None) -> jax.Array:
    """Same contract as kernel.paged_attention_hot_slots_fwd.

    q [S,Hkv,G,dh]; hot pools [S,n_slots,Hkv,page,dh]; slot_table [S,npps]
    per-stream slot ids (-1 or out-of-range = masked); lengths [S].
    """
    S, Hkv, G, dh = q.shape
    n_slots, page_size = k_hot.shape[1], k_hot.shape[3]
    valid = (slot_table >= 0) & (slot_table < n_slots)   # [S, npps]
    st = jnp.clip(slot_table, 0, n_slots - 1)
    k = jnp.take_along_axis(k_hot, st[:, :, None, None, None],
                            axis=1).swapaxes(2, 3)
    v = jnp.take_along_axis(v_hot, st[:, :, None, None, None],
                            axis=1).swapaxes(2, 3)
    S_, npps = st.shape
    T = npps * page_size
    k = k.reshape(S, T, Hkv, dh).astype(jnp.float32)
    v = v.reshape(S, T, Hkv, dh).astype(jnp.float32)
    mask = jnp.arange(T)[None, :] < lengths[:, None]
    mask = mask & jnp.repeat(valid, page_size, axis=1)
    return _masked_softmax_attend(q, k, v, mask,
                                  sm_scale or 1.0 / (dh ** 0.5))
