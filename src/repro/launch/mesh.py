"""Production mesh builders (functions — importing never touches devices).

Single pod: (data=16, model=16) = 256 chips (one v5e pod). Multi-pod adds a
leading DCN-class 'pod' axis: (pod=2, data=16, model=16) = 512 chips. The
'model' axis is the ICI-bandwidth-rich TP/EP axis; 'data' carries FSDP +
batch; 'pod' carries pure DP (gradient all-reduce over DCN — the axis
gradient compression targets).

The 'fabric' axis (``make_fabric_mesh``) is the disaggregated-memory
dimension (DESIGN.md §7): the paged cold-KV pool's page axis shards over
it, one NIC per fabric shard, and the sharded sweep's collective permutes
ride it. Serving composes it orthogonally to the compute mesh — a chip can
sit on ('fabric',) for the cold tier while the model runs data/model
parallel; on CPU CI the fabric devices come from
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with ``Auto`` axes. Every caller here places
    arrays by ``PartitionSpec`` — ``shard_map`` specs, the pipeline, the
    logical-axis sharding rules under ``jit`` — and lets GSPMD propagate the
    rest, which ``Explicit`` axes (``make_mesh``'s default) refuse outside
    a ``jax.set_mesh`` context."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_fabric_mesh(n_shards: int):
    """1-D ('fabric',) mesh over ``n_shards`` devices — the sharded cold
    pool's home shards (:mod:`repro.paging.sharded_pool`).

    Raises with a hint about ``--xla_force_host_platform_device_count``
    when the process doesn't expose enough devices (the CPU-CI situation).
    """
    if jax.device_count() < n_shards:
        raise ValueError(
            f"need {n_shards} devices for a {n_shards}-shard fabric mesh, "
            f"have {jax.device_count()} — on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_shards}")
    return make_mesh((n_shards,), ("fabric",))


def make_host_mesh(model: int = 1):
    """Tiny mesh on whatever devices exist (tests / CPU examples)."""
    n = jax.device_count()
    model = min(model, n)
    return make_mesh((n // model, model), ("data", "model"))
