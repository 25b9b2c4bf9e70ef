"""Distributed windowed kNN over a device mesh.

Runs the SAME operator once single-device and once sharded over every
available device (`QueryConfiguration(devices=N)`), and shows the outputs
match bit-for-bit — the per-shard top-k partials are re-merged with an
all-gather tree instead of the reference's parallelism-1 `windowAll` stage.

Run: python examples/distributed_knn.py
(on the CPU, ``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8`` gives an 8-device mesh)
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import Point
from spatialflink_tpu.operators import (
    PointPointKNNQuery,
    QueryConfiguration,
    QueryType,
)


def main() -> int:
    n_dev = len(jax.devices())
    # mesh width must be a power of two (batch capacities are 2^k buckets)
    devices = 1 << (n_dev.bit_length() - 1)
    grid = UniformGrid(115.50, 117.60, 39.60, 41.10, num_grid_partitions=100)
    rng = np.random.default_rng(0)
    t0 = 1_700_000_000_000
    pts = [Point.create(float(rng.uniform(116, 117)),
                        float(rng.uniform(40, 41)), grid,
                        obj_id=f"veh{i % 200}", timestamp=t0 + i * 10)
           for i in range(5000)]
    query = Point.create(116.5, 40.5, grid)

    def run(n_devices, hosts=None):
        conf = QueryConfiguration(QueryType.WindowBased, 10_000, 5_000,
                                  devices=n_devices, hosts=hosts)
        return list(PointPointKNNQuery(conf, grid).run(
            iter(pts), query, radius=0.5, k=10))

    single = run(None)
    sharded = run(devices)
    assert len(single) == len(sharded)
    for a, b in zip(single, sharded):
        assert a.records == b.records, "mesh result diverged!"
    print(f"{len(single)} windows; {devices}-device mesh output matches "
          "single-device bit-for-bit")
    if devices >= 4:
        # the multi-host shape: 2-D (hosts x chips) mesh, two-level merge
        # (ICI within a slice, k-sized partials per slice over DCN)
        two_d = run(devices, hosts=2)
        for a, b in zip(single, two_d):
            assert a.records == b.records, "2-D mesh result diverged!"
        print(f"2-D mesh (2 hosts x {devices // 2} chips) matches too")
    for w in single[:3]:
        top = ", ".join(f"{o}@{d:.4f}" for o, d in w.records[:3])
        print(f"  window [{w.window_start}, {w.window_end}) top-3: {top}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
