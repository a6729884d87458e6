"""Pallas kernel sweeps vs pure-jnp oracles (interpret=True on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.gather_pages import gather_pages
from repro.kernels.paged_attention import (paged_attention,
                                           paged_attention_hot_slots)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


class TestFlashAttention:
    @pytest.mark.parametrize("B,Sq,Sk,Hq,Hkv,dh", [
        (1, 32, 32, 4, 4, 32),        # MHA
        (2, 64, 64, 8, 2, 64),        # GQA 4:1
        (1, 16, 48, 4, 1, 32),        # MQA, Sq != Sk
        (1, 64, 64, 4, 2, 120),       # non-128 head dim (danube)
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_shapes_dtypes_vs_oracle(self, B, Sq, Sk, Hq, Hkv, dh, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, Sq, Hq, dh), dtype)
        k = jax.random.normal(ks[1], (B, Sk, Hkv, dh), dtype)
        v = jax.random.normal(ks[2], (B, Sk, Hkv, dh), dtype)
        a = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
        b = flash_attention(q, k, v, use_kernel=False)
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=_tol(dtype), rtol=_tol(dtype))

    @pytest.mark.parametrize("causal,window", [(True, 0), (True, 8),
                                               (False, 0)])
    def test_masks(self, causal, window):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 32, 2, 32))
        k = jax.random.normal(ks[1], (1, 32, 2, 32))
        v = jax.random.normal(ks[2], (1, 32, 2, 32))
        a = flash_attention(q, k, v, causal=causal, window=window,
                            block_q=8, block_k=8, interpret=True)
        b = flash_attention(q, k, v, causal=causal, window=window,
                            use_kernel=False)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)

    def test_q_offset_decode_tail(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (1, 8, 2, 32))
        k = jax.random.normal(ks[1], (1, 64, 2, 32))
        v = jax.random.normal(ks[2], (1, 64, 2, 32))
        a = flash_attention(q, k, v, q_offset=56, block_q=8, block_k=16,
                            interpret=True)
        b = flash_attention(q, k, v, q_offset=56, use_kernel=False)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


class TestGatherPages:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
    def test_exact_gather(self, dtype):
        pool = jnp.arange(32 * 6, dtype=jnp.float32).reshape(32, 6).astype(dtype)
        idx = jnp.array([0, 31, 7, 7, 13], jnp.int32)
        out = gather_pages(pool, idx, interpret=True)
        assert (np.asarray(out) == np.asarray(pool)[np.asarray(idx)]).all()

    def test_clamps_out_of_range(self):
        pool = jnp.arange(16.0).reshape(8, 2)
        out = gather_pages(pool, jnp.array([-5, 100], jnp.int32),
                           interpret=True)
        assert (np.asarray(out[0]) == np.asarray(pool[0])).all()
        assert (np.asarray(out[1]) == np.asarray(pool[7])).all()

    def test_multidim_pages(self):
        pool = jax.random.normal(jax.random.PRNGKey(0), (16, 4, 2, 8))
        idx = jnp.array([3, 0, 15], jnp.int32)
        out = gather_pages(pool, idx, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(pool)[np.asarray(idx)])


class TestGatherPagesAsync:
    """Issue/wait double-buffered gather == the pipelined/oracle gather."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
    def test_matches_ref(self, dtype):
        from repro.kernels.gather_pages import gather_pages_async
        pool = jnp.arange(32 * 6, dtype=jnp.float32).reshape(32, 6).astype(dtype)
        idx = jnp.array([0, 31, 7, 7, 13, 1], jnp.int32)
        out = gather_pages_async(pool, idx, interpret=True)
        assert (np.asarray(out) == np.asarray(pool)[np.asarray(idx)]).all()

    def test_clamps_and_multidim(self):
        from repro.kernels.gather_pages import gather_pages_async
        pool = jax.random.normal(jax.random.PRNGKey(0), (16, 4, 2, 8))
        idx = jnp.array([3, -5, 100], jnp.int32)
        out = gather_pages_async(pool, idx, interpret=True)
        expect = np.asarray(pool)[np.clip(np.asarray(idx), 0, 15)]
        np.testing.assert_allclose(np.asarray(out), expect)

    def test_single_page(self):
        from repro.kernels.gather_pages import gather_pages_async
        pool = jnp.arange(8.0).reshape(4, 2)
        out = gather_pages_async(pool, jnp.array([2], jnp.int32),
                                 interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(pool[2:3]))


class TestPagedAttention:
    @pytest.mark.parametrize("B,Hq,Hkv,dh,ps,npps", [
        (2, 8, 2, 64, 16, 4),
        (1, 4, 4, 32, 8, 8),
        (3, 4, 1, 128, 32, 2),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_vs_oracle(self, B, Hq, Hkv, dh, ps, npps, dtype):
        npages = npps * B + 4
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (B, 1, Hq, dh), dtype)
        kp = jax.random.normal(ks[1], (npages, Hkv, ps, dh), dtype)
        vp = jax.random.normal(ks[2], (npages, Hkv, ps, dh), dtype)
        pt = jax.random.randint(ks[3], (B, npps), 0, npages)
        ln = jnp.asarray(np.random.default_rng(0).integers(1, ps * npps + 1,
                                                           B), jnp.int32)
        a = paged_attention(q, kp, vp, pt, ln, interpret=True)
        b = paged_attention(q, kp, vp, pt, ln, use_kernel=False)
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=_tol(dtype), rtol=_tol(dtype))

    def test_matches_dense_decode_attention(self):
        """Paged == contiguous decode attention when pages are linear."""
        from repro.models.attention import decode_attention
        B, Hq, Hkv, dh, ps, npps = 2, 4, 2, 32, 8, 4
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (B, 1, Hq, dh))
        kd = jax.random.normal(ks[1], (B, ps * npps, Hkv, dh))
        vd = jax.random.normal(ks[2], (B, ps * npps, Hkv, dh))
        kp = kd.reshape(B * npps, ps, Hkv, dh).swapaxes(1, 2)
        vp = vd.reshape(B * npps, ps, Hkv, dh).swapaxes(1, 2)
        pt = jnp.arange(B * npps, dtype=jnp.int32).reshape(B, npps)
        ln = jnp.array([20, 32], jnp.int32)
        a = paged_attention(q, kp, vp, pt, ln, interpret=True)
        b = decode_attention(q, kd, vd, ln)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)

    @pytest.mark.parametrize("use_kernel", [True, False])
    def test_poisoned_table_masks_not_page0(self, use_kernel):
        """Regression: an invalid table entry *inside* lengths must be
        masked out of the softmax, not silently read as page 0's bytes
        (the old clip-into-range behavior)."""
        B, Hq, Hkv, dh, ps, npps = 2, 4, 2, 16, 4, 4
        npages = 8
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        q = jax.random.normal(ks[0], (B, 1, Hq, dh))
        kp = jax.random.normal(ks[1], (npages, Hkv, ps, dh))
        vp = jax.random.normal(ks[2], (npages, Hkv, ps, dh))
        ln = jnp.full((B,), ps * npps, jnp.int32)   # poison inside lengths
        pt = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
        pois = pt.at[0, 1].set(-1).at[1, 2].set(npages + 50)
        out = paged_attention(q, kp, vp, pois, ln, interpret=True,
                              use_kernel=use_kernel)
        clean = paged_attention(q, kp, vp, pt, ln, interpret=True,
                                use_kernel=use_kernel)
        # the poisoned pages changed the output (they're gone, not read)
        assert (np.asarray(out) != np.asarray(clean)).any()
        # oracle: the same rows with the poisoned page excised by length
        # masking on an explicitly re-packed table
        pack = jnp.asarray([[0, 2, 3, 0], [4, 5, 7, 0]], jnp.int32)
        ln2 = jnp.full((B,), ps * (npps - 1), jnp.int32)
        expect = paged_attention(q, kp, vp, pack, ln2, interpret=True,
                                 use_kernel=use_kernel)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   atol=2e-6)
        # and definitely NOT equal to clip-to-page-0 / clip-to-last reads
        sub0 = pt.at[0, 1].set(0).at[1, 2].set(npages - 1)
        old = paged_attention(q, kp, vp, sub0, ln, interpret=True,
                              use_kernel=use_kernel)
        assert (np.asarray(out) != np.asarray(old)).any()


class TestPagedAttentionHotSlots:
    """Fused hot-slot kernel: in-place slot indirection == stacked flat pool.

    The three kernel variants (pipelined fused, async fused, flat) share one
    per-page online-softmax update, so on the same bytes their outputs are
    *bitwise* equal — the property the tiered §6.4 pin leans on.
    """

    def _mk(self, S, n_slots, ps, Hkv, Hq, dh, npps, dtype, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        q = jax.random.normal(ks[0], (S, 1, Hq, dh), dtype)
        kh = jax.random.normal(ks[1], (S, n_slots, Hkv, ps, dh), dtype)
        vh = jax.random.normal(ks[2], (S, n_slots, Hkv, ps, dh), dtype)
        st = jax.random.randint(ks[3], (S, npps), 0, n_slots, jnp.int32)
        ln = jnp.asarray(np.random.default_rng(seed).integers(
            1, ps * npps + 1, S), jnp.int32)
        return q, kh, vh, st, ln

    @pytest.mark.parametrize("S,Hq,Hkv,dh,ps,npps", [
        (2, 8, 2, 64, 16, 4),         # GQA 4:1
        (1, 4, 4, 32, 8, 8),          # MHA
        (3, 4, 1, 128, 32, 2),        # MQA, non-trivial page size
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("async_copy", [False, True])
    def test_bitwise_flat_equivalence(self, S, Hq, Hkv, dh, ps, npps,
                                      dtype, async_copy):
        n_slots = npps + 3
        q, kh, vh, st, ln = self._mk(S, n_slots, ps, Hkv, Hq, dh, npps,
                                     dtype)
        out = paged_attention_hot_slots(q, kh, vh, st, ln, interpret=True,
                                        async_copy=async_copy)
        # flat oracle: same bytes via the stacked pool + global table
        fk = kh.reshape((S * n_slots,) + kh.shape[2:])
        fv = vh.reshape((S * n_slots,) + vh.shape[2:])
        gt = st + jnp.arange(S, dtype=jnp.int32)[:, None] * n_slots
        flat = paged_attention(q, fk, fv, gt, ln, interpret=True)
        assert (np.asarray(out) == np.asarray(flat)).all()

    @pytest.mark.parametrize("async_copy", [False, True])
    def test_vs_exact_softmax_ref(self, async_copy):
        q, kh, vh, st, ln = self._mk(2, 6, 8, 2, 4, 32, 4, jnp.float32)
        a = paged_attention_hot_slots(q, kh, vh, st, ln, interpret=True,
                                      async_copy=async_copy)
        b = paged_attention_hot_slots(q, kh, vh, st, ln, use_kernel=False)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)

    @pytest.mark.parametrize("async_copy", [False, True])
    def test_non_resident_masked_not_read(self, async_copy):
        """A non-resident (-1 / out-of-range) slot entry is masked out of
        the softmax — never silently read as slot 0's bytes — and only the
        poisoned streams' outputs change."""
        S, n_slots, ps, Hkv, Hq, dh, npps = 3, 8, 4, 2, 4, 16, 4
        q, kh, vh, st, _ = self._mk(S, n_slots, ps, Hkv, Hq, dh, npps,
                                    jnp.float32, seed=1)
        ln = jnp.full((S,), ps * npps, jnp.int32)
        clean = paged_attention_hot_slots(q, kh, vh, st, ln, interpret=True,
                                          async_copy=async_copy)
        pois = st.at[0, 2].set(-1).at[1, 3].set(n_slots + 9)
        out = paged_attention_hot_slots(q, kh, vh, pois, ln, interpret=True,
                                        async_copy=async_copy)
        ref = paged_attention_hot_slots(q, kh, vh, pois, ln,
                                        use_kernel=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        assert (np.asarray(out)[:2] != np.asarray(clean)[:2]).any()
        assert (np.asarray(out)[2] == np.asarray(clean)[2]).all()
        # sync and async kernels agree bitwise on the poisoned table too
        other = paged_attention_hot_slots(q, kh, vh, pois, ln,
                                          interpret=True,
                                          async_copy=not async_copy)
        assert (np.asarray(out) == np.asarray(other)).all()
