"""Multi-query batching benchmark: Q continuous kNN queries answered in ONE
window dispatch (``ops.knn.knn_point_multi``) vs Q single-query dispatches.

The reference runs one continuous query per Flink job
(``StreamingJob.java:470``), so Q queries cost Q jobs each re-reading the
stream; here they share one device residency of the window and one fused
pass. The interesting number is per-QUERY cost as Q grows: near-flat
per-dispatch time means the query axis is almost free until compute
saturates.

Usage: python benchmarks/bench_multi_query.py [--n N] [--qs 1,8,64,256]
       [--strategy S] [--out PATH]

One JSON line per Q, plus a single-query-loop baseline row (q=1 kernel
dispatched Q_max times) for the speedup denominator.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.bench_configs import _grid, _points, _slope_time  # noqa: E402

RADIUS = 0.5
K = 50


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=None,
                    help="window points (default 1M, 262k on CPU)")
    ap.add_argument("--qs", default="1,8,64,256")
    ap.add_argument("--strategy", default="auto")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from spatialflink_tpu.ops.knn import knn_point, knn_point_multi

    backend = jax.default_backend()
    n = args.n or (1_000_000 if backend == "tpu" else 262_144)
    qs = [int(x) for x in args.qs.split(",")]

    grid = _grid()
    batch = jax.device_put(_points(grid, n, seed=0))
    nb = grid.candidate_layers(RADIUS)
    rng = np.random.default_rng(1)
    q_max = max(qs)
    qx_all = rng.uniform(116.0, 117.0, q_max).astype(np.float32)
    qy_all = rng.uniform(40.0, 41.0, q_max).astype(np.float32)
    qc_all = np.asarray([grid.assign_cell(float(x), float(y))[0]
                         for x, y in zip(qx_all, qy_all)], np.int32)

    rows = []

    # baseline: one iteration = one single-query kernel, under EXACTLY the
    # multi rows' dispatch conditions — the query is a hoisted constant with
    # the same i*1e-7 anti-hoist perturbation, no per-iteration gather (a
    # dynamic qx[i % Q] indexing made the round-1 version of this baseline
    # ~1.9x slower than the q=1 multi row, i.e. the "speedup" measured the
    # harness, not the batching)
    def run_single_loop(iters):
        qx0, qy0 = float(qx_all[0]), float(qy_all[0])
        qc0 = jnp.int32(qc_all[0])

        def body(i, acc):
            r = knn_point(batch, qx0 + i * 1e-7, qy0, qc0, RADIUS, nb,
                          n=grid.n, k=K, strategy=args.strategy)
            return acc + r.dist[0]
        return jax.lax.fori_loop(0, iters, body, jnp.float32(0))

    per_query_single = _slope_time(run_single_loop, lo=2, hi=10)
    row = dict(mode="single_loop", queries=1,
               per_query_us=round(per_query_single * 1e6, 2),
               points_x_queries_per_sec=round(n / per_query_single),
               backend=backend, n=n, strategy=args.strategy)
    print(json.dumps(row), flush=True)
    rows.append(row)

    for q in qs:
        qx = jnp.asarray(qx_all[:q])
        qy = jnp.asarray(qy_all[:q])
        qc = jnp.asarray(qc_all[:q])

        def run_n(iters, qx=qx, qy=qy, qc=qc):
            def body(i, acc):
                r = knn_point_multi(batch, qx + i * 1e-7, qy, qc, RADIUS,
                                    nb, n=grid.n, k=K,
                                    strategy=args.strategy)
                return acc + r.dist[0, 0]
            return jax.lax.fori_loop(0, iters, body, jnp.float32(0))

        per = _slope_time(run_n, lo=2, hi=10)  # seconds per multi-dispatch
        per_query = per / q
        row = dict(mode="multi", queries=q,
                   per_dispatch_ms=round(per * 1e3, 3),
                   per_query_us=round(per_query * 1e6, 2),
                   points_x_queries_per_sec=round(n * q / per),
                   speedup_vs_single_loop=round(per_query_single / per_query,
                                                2),
                   backend=backend, n=n, strategy=args.strategy)
        print(json.dumps(row), flush=True)
        rows.append(row)

    out = args.out or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"RESULTS_multiquery_{backend}.json")
    with open(out, "w") as f:
        json.dump({"backend": backend, "n": n, "k": K,
                   "strategy": args.strategy, "rows": rows}, f, indent=1)
    print(f"# wrote {out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
