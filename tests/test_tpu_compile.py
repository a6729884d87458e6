"""The serving path's Pallas kernels compile for a described TPU v5e.

Nothing runs: each kernel is lowered and compiled through its public
wrapper at ``interpret=False`` for one chip of a ``v5e:2x2`` topology that
is described, not attached, at qwen2.5-3b widths (16 q / 2 kv heads,
head_dim 128, bf16) and the engine's default 16-token page. That catches
what interpret mode cannot: blocks and DMA slices that the TPU tiling
refuses. The compiled program must hold the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import, so only the
worker that runs this file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.gather_pages import gather_pages, gather_pages_async
from repro.kernels.paged_attention import (paged_attention,
                                           paged_attention_hot_slots)

# qwen2.5-3b attention widths; engine default page; smoke-sized pools
HQ, HKV, DH, PAGE = 16, 2, 128, 16
S, NPPS, N_PAGES, N_SLOTS, K = 4, 9, 64, 32, 16
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _attention_args(sh, pool_shape):
    return (_sds(sh, (S, 1, HQ, DH)), _sds(sh, pool_shape),
            _sds(sh, pool_shape), _sds(sh, (S, NPPS), jnp.int32),
            _sds(sh, (S,), jnp.int32))


CASES = {
    "paged_attention": (
        lambda q, k, v, t, n: paged_attention(q, k, v, t, n,
                                              interpret=False),
        lambda sh: _attention_args(sh, (N_PAGES, HKV, PAGE, DH))),
    "hot_slots": (
        lambda q, k, v, t, n: paged_attention_hot_slots(
            q, k, v, t, n, interpret=False),
        lambda sh: _attention_args(sh, (S, N_SLOTS, HKV, PAGE, DH))),
    "hot_slots_async": (
        lambda q, k, v, t, n: paged_attention_hot_slots(
            q, k, v, t, n, interpret=False, async_copy=True),
        lambda sh: _attention_args(sh, (S, N_SLOTS, HKV, PAGE, DH))),
    "gather_pages": (
        lambda p, i: gather_pages(p, i, interpret=False),
        lambda sh: (_sds(sh, (N_PAGES, HKV, PAGE, DH)),
                    _sds(sh, (K,), jnp.int32))),
    "gather_pages_async": (
        lambda p, i: gather_pages_async(p, i, interpret=False),
        lambda sh: (_sds(sh, (N_PAGES, HKV, PAGE, DH)),
                    _sds(sh, (K,), jnp.int32))),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = CASES[name]
    compiled = jax.jit(fn).lower(*args(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
