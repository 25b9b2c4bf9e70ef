"""Device mesh construction and window-batch sharding.

The canonical layout is a 1-D "cells" mesh axis: a window batch is sharded
across devices on its point dimension. :func:`shard_batch` shards the batch
CONTIGUOUSLY (arrival order) — any permutation is *correct*, because every
kernel is a cell-oblivious masked reduction; there is no per-cell state to
co-locate, unlike the reference's ``keyBy(gridID)`` window operators.

:func:`cell_hash_order` provides the keyBy-style cell bucketing as an
explicit host-side pre-permutation for callers that want it. Measured
(round 4, 1M points, 8-device virtual CPU mesh): bucketing sped the
distributed range kernel up ~28% and kNN ~3% on CPU (branchy vector
backend), but costs a host argsort+gather per window (~100ms at 1M rows) —
more than the kernel saving — and the TPU kernels are mask-vectorized with
no data-dependent branching, so contiguous sharding remains the default.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

CELL_AXIS = "cells"
# Cross-host axis: shards ride DCN between slices, while CELL_AXIS collectives
# stay on ICI within a slice (SURVEY §2.5 "distributed communication backend";
# BASELINE config 5's multi-host data-parallel windows).
DCN_AXIS = "hosts"


def make_mesh(n_devices: Optional[int] = None, axis: str = CELL_AXIS) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} devices, only {len(devs)} available")
    return Mesh(np.array(devs[:n]), (axis,))


def make_mesh_2d(n_outer: Optional[int] = None,
                 n_inner: Optional[int] = None) -> Mesh:
    """(DCN_AXIS, CELL_AXIS) mesh: outer axis across hosts/slices, inner axis
    across the chips of a slice.

    On a real multi-host deployment the outer axis is laid out so its
    collectives cross DCN and the inner axis stays on ICI
    (``mesh_utils.create_hybrid_device_mesh``); single-process (tests, the
    virtual CPU mesh) falls back to a reshape of the local devices, which
    keeps the same program semantics.
    """
    devs = jax.devices()
    if n_outer is None:
        n_outer = max(1, jax.process_count())
    if n_inner is None:
        n_inner = len(devs) // n_outer
    if n_inner < 1 or n_outer * n_inner > len(devs):
        raise ValueError(
            f"requested {n_outer}x{n_inner} devices, only {len(devs)} available")
    if jax.process_count() > 1:
        from jax.experimental import mesh_utils

        # granule choice: real TPU multi-host has per-slice slice_index; a
        # multi-process CPU run (the DCN test harness) has one slice, so the
        # process is the DCN granule instead
        slice_ids = {getattr(d, "slice_index", 0)
                     for d in devs[: n_outer * n_inner]}
        arr = mesh_utils.create_hybrid_device_mesh(
            (1, n_inner), (n_outer, 1), devices=devs[: n_outer * n_inner],
            process_is_granule=len(slice_ids) <= 1)
    else:
        arr = np.array(devs[: n_outer * n_inner]).reshape(n_outer, n_inner)
    return Mesh(arr, (DCN_AXIS, CELL_AXIS))


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Bring up the cross-host runtime (``jax.distributed.initialize``),
    after which ``jax.devices()`` spans every host and 2-D meshes place the
    outer axis across DCN. No-op when already initialized or single-process
    with no coordinator configured (local dev / tests)."""
    import jax.distributed as jd

    if jd.is_initialized():
        return
    if coordinator_address is None and "JAX_COORDINATOR_ADDRESS" not in os.environ:
        return  # single-process mode
    jd.initialize(coordinator_address=coordinator_address,
                  num_processes=num_processes, process_id=process_id)


def shard_batch(batch, mesh: Mesh, axis=CELL_AXIS):
    """Place a window batch with its leading (point) dim sharded over the mesh.

    ``axis`` may be one mesh axis name or a tuple of names (2-D meshes shard
    the point dim over both, e.g. ``("hosts", "cells")``). Capacity must
    divide the product of the named axes' sizes — guaranteed when bucket
    sizes are powers of two >= the device count.
    """
    sharding = NamedSharding(mesh, P(axis))
    return jax.device_put(batch, sharding)


def cell_hash_order(cell: np.ndarray, n_shards: int) -> np.ndarray:
    """Host-side permutation placing whole cells on the same shard (stable
    within a cell). Returns indices; apply with ``tree.map(lambda a: a[idx])``
    before :func:`shard_batch`.

    This mirrors keyBy(gridID)'s co-location property for callers that want
    per-shard cell locality (e.g. per-cell aggregations). It is NOT applied
    by default: results are permutation-invariant (kernels are masked
    reductions), and the host argsort+gather costs more per window than the
    measured kernel saving (module docstring has the numbers).
    """
    shard = np.where(cell >= 0, cell % n_shards, n_shards - 1)
    return np.argsort(shard, kind="stable")
