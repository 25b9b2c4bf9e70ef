"""The chip's compiler accepts the main-path kernels at real widths.

Ahead-of-time compiles for one chip of a described ``v5e:2x2`` topology
(no chip attached): what interpret mode cannot show — tiling, SMEM/VMEM
limits, Mosaic lowering of the Pallas kernel — fails here at no chip time.
Nothing runs, so these say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every xdist worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from spatialflink_tpu.models import PointBatch
from spatialflink_tpu.ops.knn import _approx_verified_fast, knn_point
from spatialflink_tpu.ops.pallas_kernels import _pip_pallas
from spatialflink_tpu.ops.range import range_filter_point

N_1M = 1 << 20
# the full-sort fallback inside approx_verified compiles super-linearly in
# the window (v5e: ~1 s at 4,096 points, ~39 s at 65,536), so the whole
# strategy is compiled small and its fast path (approx_min_k + the
# exactness certificate) at the real width
N_KNN_FULL = 1 << 12
GRID_N = 100


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _batch(n, sharding) -> PointBatch:
    f, i = jnp.float32, jnp.int32
    return PointBatch(x=_spec((n,), f, sharding), y=_spec((n,), f, sharding),
                      obj_id=_spec((n,), i, sharding),
                      ts=_spec((n,), i, sharding),
                      cell=_spec((n,), i, sharding),
                      valid=_spec((n,), jnp.bool_, sharding))


def _scalar(dtype, sharding):
    return _spec((), dtype, sharding)


@pytest.mark.parametrize("edges", [64, 1536])
def test_pip_pallas_compiles_for_v5e(one_chip, edges):
    """1M points against one query ring; 1,536 edges run three SMEM chunks
    of the edge grid."""
    px = _spec((N_1M,), jnp.float32, one_chip)
    e = _spec((edges, 4), jnp.float32, one_chip)
    m = _spec((edges,), jnp.bool_, one_chip)
    lowered = _pip_pallas.lower(px, px, e, m, interpret=False)
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_range_filter_point_compiles_for_v5e(one_chip):
    f, i = jnp.float32, jnp.int32
    compiled = range_filter_point.lower(
        _batch(N_1M, one_chip), _scalar(f, one_chip), _scalar(f, one_chip),
        _scalar(i, one_chip), _scalar(f, one_chip), _scalar(i, one_chip),
        _scalar(i, one_chip), n=GRID_N).compile()
    assert compiled.memory_analysis() is not None


def test_knn_approx_verified_compiles_for_v5e(one_chip):
    f, i = jnp.float32, jnp.int32
    compiled = knn_point.lower(
        _batch(N_KNN_FULL, one_chip), _scalar(f, one_chip),
        _scalar(f, one_chip), _scalar(i, one_chip), _scalar(f, one_chip),
        _scalar(i, one_chip), n=GRID_N, k=50,
        strategy="approx_verified").compile()
    assert compiled.memory_analysis() is not None


def test_knn_approx_verified_fast_path_compiles_for_v5e(one_chip):
    """The path a 1M window takes whenever the certificate holds (the
    ``m`` is ``topk_by_distance``'s for k = 50)."""
    fast = jax.jit(lambda o, d, e: _approx_verified_fast(o, d, e, 50, 800))
    compiled = fast.lower(_spec((N_1M,), jnp.int32, one_chip),
                          _spec((N_1M,), jnp.float32, one_chip),
                          _spec((N_1M,), jnp.bool_, one_chip)).compile()
    assert compiled.memory_analysis() is not None


def test_knn_mesh_stats_compiles_for_v5e_2x2(topo):
    """The point kNN's mesh program over the host's four chips: the
    per-shard kernel, the all-gather of the k-sized partials and the psum
    of the candidate count, in one program (a small window, for the
    full-sort fallback's compile time)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from spatialflink_tpu.parallel.mesh import CELL_AXIS
    from spatialflink_tpu.parallel.ops import knn_mesh_stats

    mesh = Mesh(np.array(topo.devices[:4]), (CELL_AXIS,))
    shards, rep = NamedSharding(mesh, P(CELL_AXIS)), NamedSharding(mesh, P())
    f, i = jnp.float32, jnp.int32
    compiled = knn_mesh_stats.lower(
        _batch(N_KNN_FULL, shards), _scalar(f, rep), _scalar(f, rep),
        _scalar(i, rep), _scalar(f, rep), mesh=mesh, nb_layers=24,
        n=GRID_N, k=50, strategy="approx_verified").compile()
    # the TPU compiler writes the all-gather as an all-reduce of
    # partition-placed slices, beside the psum's
    assert "all-reduce" in compiled.as_text()
    assert compiled.memory_analysis() is not None
