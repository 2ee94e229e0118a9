"""Production meshes + per-(arch, mesh, workload) sharding rules.

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS first).
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType, Mesh

from repro.distributed.sharding import ShardingRules
from repro.models.config import ModelConfig


def _auto_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: shardings propagate
    through gathers and reshapes (GSPMD) instead of demanding an explicit
    ``out_sharding`` at each op, which is what the serving and TM paths
    are written against."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: 16x16 = 256 chips (data, model).  Multi-pod: 2 pods of
    256 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2) -> Mesh:
    """Small mesh for multi-device CPU tests (8 forced host devices)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))


def make_pipeline_mesh(stages: int = 4) -> Mesh:
    """Pipeline-parallel demo mesh (see distributed/pipeline.py)."""
    return _auto_mesh((stages,), ("pipe",))


def make_replica_mesh(n_replica: int, n_batch: int = 1) -> Mesh:
    """Serving mesh for sharded replica pools: ``("replica", "batch")``.

    The ``replica`` axis splits a pool's programmed ``[R, C, L]`` stack
    (one shard of chips per device); the optional ``batch`` axis splits
    request rows for data-parallel reads.  Consumed by
    ``ReplicaPool.shard`` via ``distributed.sharding.replica_rules``."""
    return _auto_mesh((n_replica, n_batch), ("replica", "batch"))


def parse_mesh_spec(spec: str) -> Mesh:
    """``"8"`` or ``"2x4"`` -> a replica[xbatch] serving mesh.

    The product must not exceed ``jax.device_count()`` (force host
    devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    before jax initializes, or ``--host-devices`` on the CLIs)."""
    parts = spec.lower().split("x")
    if not 1 <= len(parts) <= 2:
        raise ValueError(f"bad mesh spec {spec!r}; want 'R' or 'RxB'")
    try:
        dims = [int(p) for p in parts]
    except ValueError:
        raise ValueError(f"bad mesh spec {spec!r}; want 'R' or 'RxB'")
    n_replica, n_batch = dims[0], dims[1] if len(dims) == 2 else 1
    if n_replica < 1 or n_batch < 1:
        raise ValueError(f"mesh axes must be >= 1, got {spec!r}")
    if n_replica * n_batch > jax.device_count():
        raise ValueError(
            f"mesh {spec!r} needs {n_replica * n_batch} devices but only "
            f"{jax.device_count()} are visible; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N before jax init")
    return make_replica_mesh(n_replica, n_batch)


def batch_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def rules_for(cfg: ModelConfig, mesh: Mesh, *,
              global_batch: Optional[int] = None,
              pure_dp: bool = False) -> ShardingRules:
    """Sharding rules adapted to arch + mesh + workload.

    - batch shards over (pod, data) — dropped entirely if the global batch
      doesn't divide (long_500k has batch 1: sequence sharding only);
    - tensor/expert axes stay on "model";
    - sequence parallelism is always declared; constraint sites apply it
      to boundary activations when cfg.seq_parallel;
    - ``pure_dp``: sub-1B archs waste the model axis on tensor
      parallelism (2 activation all-reduces per layer for matmuls that
      fit one chip) — instead fold "model" into the batch axes and keep
      parameters FSDP over (pod, data) (§Perf iter X1).
    """
    if pure_dp and "model" in mesh.shape:
        b_axes = tuple(a for a in ("data", "model") if a in mesh.shape)
        n = 1
        for a in b_axes:
            n *= mesh.shape[a]
        if global_batch is None or global_batch % n == 0:
            return ShardingRules(
                batch=b_axes, seq=None, embed=None, heads=None,
                kv_seq=None, expert=None, vocab=None, mlp=None,
                fsdp="data", tensor=None)
    b_axes = batch_axes(mesh)
    if global_batch is not None:
        n = 1
        for a in b_axes:
            n *= mesh.shape[a]
        if global_batch % n:
            b_axes = ()
    return ShardingRules(
        batch=b_axes if b_axes else None,
        seq="model" if cfg.seq_parallel else None,
        embed=None,
        heads="model",
        kv_seq="model",
        expert="model",
        vocab="model",
        mlp="model",
        fsdp="data" if "data" in mesh.shape else None,
        tensor="model" if "model" in mesh.shape else None,
    )
