"""Sweep kNN top-k selection strategies on the current backend.

Usage: python benchmarks/sweep_knn.py [N_POINTS]

Times each strategy (sort / grouped at several group counts / prefilter at
several m / approx) on the headline window shape with the slope method
(index-dependent on-device fori_loop at two iteration counts), and prints a
table. Use the results to set ops.knn._DEFAULT_GROUPS/_GROUPED_MIN_N and the
prefilter m, and to pick bench.py's strategy on real hardware.
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    n_points = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    k = 50

    import jax
    import jax.numpy as jnp

    from spatialflink_tpu.index import UniformGrid
    from spatialflink_tpu.models import PointBatch
    from spatialflink_tpu.ops import knn as Kn
    from spatialflink_tpu.ops import distances as D
    from spatialflink_tpu.ops.range import cheb_layers

    grid = UniformGrid(115.50, 117.60, 39.60, 41.10, num_grid_partitions=100)
    rng = np.random.default_rng(0)
    xs = rng.uniform(grid.min_x, grid.max_x, n_points)
    ys = rng.uniform(grid.min_y, grid.max_y, n_points)
    oid = rng.integers(0, n_points // 4, n_points).astype(np.int32)
    batch = jax.device_put(PointBatch.from_arrays(xs, ys, grid=grid, obj_id=oid))
    qx, qy = 116.5, 40.5
    qc = jnp.int32(grid.assign_cell(qx, qy)[0])
    layers = grid.candidate_layers(0.5)

    # slope measurement is shared with bench_configs: dynamic loop-count jit
    # arg + ×5 escalation until the gap clears the RTT-jitter floor (a fixed
    # 40-window gap is ~2ms for the approx_min_k path — it produced
    # physically impossible rows on the first round-4 TPU pass). Override
    # the starting window via SPATIALFLINK_SWEEP_ITERS=lo,hi.
    from bench_configs import _slope_time_ex

    lo, hi0 = (int(v) for v in os.environ.get(
        "SPATIALFLINK_SWEEP_ITERS", "2,42").split(","))

    def slope_ms(select):
        """-> (ms/window, ok); ok=False marks a row whose gap never cleared
        the noise floor even at the cap — the table itself carries the flag
        so redirected stdout can't record an impossible number unmarked."""
        @jax.jit
        def run_n(b, iters):
            def body(i, acc):
                lay = cheb_layers(b.cell, qc, grid.n)
                elig = b.valid & (lay <= layers)
                d = D.pp_dist(b.x, b.y, qx + i * 1e-7, qy)
                r = select(b.obj_id, d, elig)
                return acc + r.dist[0]
            return jax.lax.fori_loop(0, iters, body, jnp.float32(0))

        per, ok = _slope_time_ex(lambda it: run_n(batch, it), lo=lo, hi=hi0)
        return per * 1e3, ok

    rows = [("sort", lambda o, d, e: Kn._topk_full_sort(o, d, e, k))]
    for g in (64, 128, 256, 512, 1024):
        rows.append((f"grouped g={g}",
                     lambda o, d, e, g=g: Kn._topk_grouped(o, d, e, k, g)))
    for m in (512, 1024, 2048, 4096):
        rows.append((f"prefilter m={m}",
                     lambda o, d, e, m=m: Kn._topk_prefiltered(o, d, e, k, m)))
    for m in (800, 1600, 3200):
        rows.append((f"approx_ver m={m}",
                     lambda o, d, e, m=m: Kn._topk_approx_verified(o, d, e, k, m)))
    rows.append(("approx m=1600",
                 lambda o, d, e: Kn._topk_approx(o, d, e, k, 1600)))

    print(f"# backend={jax.default_backend()} n={n_points} k={k}")
    print(f"{'strategy':<18}{'ms/window':>12}{'Mpts/s':>12}")
    for name, fn in rows:
        ms, ok = slope_ms(fn)
        flag = "" if ok else "  UNRELIABLE (gap under noise floor at cap)"
        print(f"{name:<18}{ms:>12.3f}{n_points / ms / 1e3:>12.1f}{flag}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
