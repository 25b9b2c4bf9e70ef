"""Attribute the headline kNN window's latency: device time vs dispatch.

This script measures, for one 1M-point kNN (k=50) window on the current
backend:

- per-window DEVICE time via the slope method (index-dependent fori_loop at
  two iteration counts — fixed dispatch overhead cancels);
- single-window WALL time (dispatch -> readback, what a realtime caller
  sees);
- their difference = per-dispatch overhead (dispatch->readback + host
  sync);

and optionally captures a ``jax.profiler`` trace of one window when
``SPATIALFLINK_PROFILE_DIR`` is set. Prints one JSON line.

Usage: python benchmarks/profile_knn.py [strategy]
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    strategy = sys.argv[1] if len(sys.argv) > 1 else "auto"
    n_points, k, radius = 1_000_000, 50, 0.5

    import jax
    import jax.numpy as jnp

    from spatialflink_tpu.index import UniformGrid
    from spatialflink_tpu.models import PointBatch
    from spatialflink_tpu.ops.knn import knn_point

    grid = UniformGrid(115.50, 117.60, 39.60, 41.10, num_grid_partitions=100)
    rng = np.random.default_rng(0)
    batch = jax.device_put(PointBatch.from_arrays(
        rng.uniform(grid.min_x, grid.max_x, n_points),
        rng.uniform(grid.min_y, grid.max_y, n_points),
        grid=grid,
        obj_id=rng.integers(0, n_points // 4, n_points).astype(np.int32)))
    qx, qy = 116.5, 40.5
    qc = jnp.int32(grid.assign_cell(qx, qy)[0])
    layers = grid.candidate_layers(radius)

    @jax.jit
    def run_n(b, iters):
        def body(i, acc):
            r = knn_point(b, qx + i * 1e-7, qy, qc, radius, layers,
                          n=grid.n, k=k, strategy=strategy)
            return acc + r.dist[0]
        return jax.lax.fori_loop(0, iters, body, jnp.float32(0))

    # the escalating slope helper lives in bench_configs (same directory);
    # run_n(b, iters) already matches its dynamic-iters contract
    from bench_configs import _slope_time, _p50_latency_ms

    device_ms = _slope_time(lambda it: run_n(batch, it), lo=2, hi=42) * 1e3

    win = jax.jit(lambda b: knn_point(b, qx, qy, qc, radius, layers,
                                      n=grid.n, k=k, strategy=strategy))
    wall_ms = _p50_latency_ms(lambda: win(batch), n=11)

    prof_dir = os.environ.get("SPATIALFLINK_PROFILE_DIR")
    if prof_dir:
        from spatialflink_tpu.utils.metrics import profile_to

        with profile_to(prof_dir):
            jax.block_until_ready(win(batch))

    print(json.dumps({
        "backend": jax.default_backend(),
        "strategy": strategy,
        "device_ms_per_window": round(device_ms, 3),
        "p50_wall_ms_per_window": round(wall_ms, 3),
        "dispatch_overhead_ms": round(wall_ms - device_ms, 3),
        "note": ("wall - device = per-dispatch overhead (dispatch->readback "
                 "+ host sync); a streaming pipeline with pipeline_depth>=2 "
                 "pays device time only"),
        "trace_dir": prof_dir,
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
