"""Skew sweep: uniform vs skew-adaptive grid on Zipfian/clustered streams.

The uniform grid is the system's one fixed assumption, and this is the
workload where it degrades: a clustered stream parks most records in a few
cells, so candidate-cell pruning at base granularity passes nearly
everything and the kernels pay for records a finer partition would have
excluded. The sweep drives the REAL pipeline head (chunk-vectorized decode
-> ``assemble`` windows -> range kernels) over a standing-query fleet
(``run_multi`` — the Q-axis serving shape BASELINE.md's multi-query rows
measure) at several skew levels, in three modes per level:

- ``uniform``   — the plain grid, no prefilter (the pre-PR pipeline);
- ``static``    — the adaptive layer at BASE granularity (no splits): the
  pre-kernel candidate prefilter alone, i.e. what a non-adaptive candidate
  gate would buy;
- ``adaptive``  — the full skew-adaptive grid: the repartition controller
  splits the hot cells mid-run and the refined leaf masks gate the batch.

Columns: end-to-end records/s, ratio vs uniform, candidate-set SELECTIVITY
(prefilter kept/records — the number that explains where the win comes
from: at high skew the static gate keeps the whole hot cluster because the
cluster shares the queries' base cells, while the refined masks exclude
the sub-cells outside each query's candidate set), split count, and a
WINDOW-TABLE IDENTITY assertion on every row (adaptive results must equal
uniform results bit-for-bit).

Acceptance (checked by --check, wired into BASELINE.md):
- adaptive >= 1.5x uniform records/s on the high-skew rows;
- adaptive >= 1/1.05 uniform records/s on the no-skew row (<=5% regression).

``--shard-order-ab`` additionally re-measures parallel.mesh's round-4
cell-bucketed-sharding claim under the adaptive grid on the clustered
stream (8-way virtual CPU mesh) — the verdict lives in BASELINE.md.

Usage:
    python benchmarks/bench_skew.py [--n N] [--queries Q] [--check]
                                    [--out PATH] [--shard-order-ab]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HOT_SHARES = (0.0, 0.5, 0.8, 0.95)
HIGH_SKEW = 0.8  # rows at or above this share must show the adaptive win


def _setup(n, hot_share):
    import numpy as np

    from spatialflink_tpu.config import StreamConfig
    from spatialflink_tpu.index import UniformGrid
    from spatialflink_tpu.models import Point
    from spatialflink_tpu.streams.synthetic import clustered_lines

    grid = UniformGrid(115.5, 117.6, 39.6, 41.1, num_grid_partitions=100)
    cfg = StreamConfig(format="CSV", date_format=None,
                       csv_tsv_schema=[0, 1, 2, 3])
    lines = clustered_lines(grid, n, hot_share, seed=7, fmt="csv", dt_ms=1)
    rng = np.random.default_rng(1)
    return grid, cfg, lines, rng, Point


def _queries(grid, rng, q, Point, monitors: int = 8):
    # a standing-query fleet spread over the service area, plus a handful
    # of HOTSPOT MONITORS inside the cluster box (real fleets watch
    # downtown) — the interesting case: the monitors' base-granularity
    # candidate sets swallow the whole hot cluster, so only the refined
    # (split-cell) masks can exclude the cluster records outside each
    # monitor's actual candidate neighborhood
    xs = rng.uniform(grid.min_x, grid.max_x, q)
    ys = rng.uniform(grid.min_y, grid.max_y, q)
    hx = (grid.min_x + grid.max_x) / 2 + grid.cell_length / 3
    hy = (grid.min_y + grid.max_y) / 2 + grid.cell_length / 3
    span = 2.0 * grid.cell_length  # the clustered_xy default cluster box
    m = min(monitors, q)
    xs[:m] = hx + rng.uniform(-span / 2, span / 2, m)
    ys[:m] = hy + rng.uniform(-span / 2, span / 2, m)
    return [Point.create(float(x), float(y), grid) for x, y in zip(xs, ys)]


def _run_once(grid, cfg, lines, qpts, radius, window_ms, slide_ms,
              mode, repartition_every, shard_order="arrival", devices=None,
              refine=8):
    """One full pipeline pass; returns (canon windows, seconds, stats)."""
    import dataclasses

    from spatialflink_tpu import driver
    from spatialflink_tpu.index import AdaptiveGrid
    from spatialflink_tpu.operators import (PointPointRangeQuery,
                                            QueryConfiguration, QueryType)
    from spatialflink_tpu.runtime.repartition import RepartitionController
    from spatialflink_tpu.utils.metrics import scoped_registry

    conf = QueryConfiguration(QueryType.WindowBased,
                              window_size_ms=window_ms, slide_ms=slide_ms,
                              devices=devices, shard_order=shard_order)
    ctl = None
    if mode != "uniform":
        ag = AdaptiveGrid(grid, refine=refine)
        conf = dataclasses.replace(conf, adaptive_grid=ag)
        if mode == "adaptive":
            ctl = RepartitionController(
                ag, interval_records=repartition_every)
    with scoped_registry() as reg:
        op = PointPointRangeQuery(conf, grid)
        stream = driver.decode_stream(iter(lines), cfg, grid)
        if ctl is not None:
            ctl.install()
        try:
            t0 = time.perf_counter()
            out = [(w.window_start,
                    tuple(len(recs) for recs in w.records))
                   for w in op.run_multi(stream, qpts, radius)]
            dt = time.perf_counter() - t0
        finally:
            if ctl is not None:
                ctl.uninstall()
        kept = reg.counter("prefilter-kept").count
        total = reg.counter("prefilter-records").count
        stats = {
            "selectivity": round(kept / total, 4) if total else None,
            "splits": (len(conf.adaptive_grid.split_cells())
                       if conf.adaptive_grid is not None else 0),
            "grid_version": (conf.adaptive_grid.version
                             if conf.adaptive_grid is not None else 0),
        }
    return out, dt, stats


def sweep(n, q, radius=0.002, window_ms=40_000, slide_ms=5_000,
          repartition_every=25_000):
    grid0, cfg, _, rng, Point = _setup(n, 0.0)
    qpts = _queries(grid0, rng, q, Point)
    rows = []
    for hot in HOT_SHARES:
        grid, cfg, lines, _, _ = _setup(n, hot)
        results = {}
        times = {}
        stats = {}
        for mode in ("uniform", "static", "adaptive"):
            _run_once(grid, cfg, lines, qpts, radius, window_ms, slide_ms,
                      mode, repartition_every)  # jit/layout warm pass
            results[mode], times[mode], stats[mode] = _run_once(
                grid, cfg, lines, qpts, radius, window_ms, slide_ms,
                mode, repartition_every)
        # identity on EVERY row: the adaptive (and static) pipelines must
        # produce the uniform grid's window tables bit-for-bit
        assert results["static"] == results["uniform"], \
            f"static-prefilter window table diverged at hot={hot}"
        assert results["adaptive"] == results["uniform"], \
            f"adaptive window table diverged at hot={hot}"
        for mode in ("uniform", "static", "adaptive"):
            rows.append({
                "bench": "skew_sweep",
                "hot_share": hot,
                "mode": mode,
                "records": n,
                "queries": q,
                "radius": radius,
                "rps": round(n / times[mode]),
                "ratio_vs_uniform": round(times["uniform"] / times[mode], 3),
                "selectivity": stats[mode]["selectivity"],
                "splits": stats[mode]["splits"],
                "grid_version": stats[mode]["grid_version"],
                "identity": "ok",
            })
            print(json.dumps(rows[-1]), flush=True)
    return rows


def check(rows) -> int:
    """The acceptance gates over a finished sweep."""
    bad = []
    for r in rows:
        if r.get("mode") != "adaptive":
            continue
        if r["hot_share"] >= HIGH_SKEW and r["ratio_vs_uniform"] < 1.5:
            bad.append(f"hot={r['hot_share']}: adaptive only "
                       f"{r['ratio_vs_uniform']}x (need >= 1.5x)")
        if r["hot_share"] == 0.0 and r["ratio_vs_uniform"] < 1 / 1.05:
            bad.append(f"no-skew row regressed: {r['ratio_vs_uniform']}x "
                       "(need >= 0.952x)")
    for msg in bad:
        print(f"FAIL: {msg}", file=sys.stderr)
    if not bad:
        print("# acceptance: high-skew adaptive >= 1.5x, no-skew "
              "regression <= 5% — PASS", file=sys.stderr)
    return 1 if bad else 0


def shard_order_ab(n, q, radius=0.002):
    """Re-measure parallel.mesh.cell_hash_order's round-4 claim under the
    adaptive grid on the clustered stream: distributed (8-way virtual CPU
    mesh) range over arrival-order vs cell-bucketed shards. Prints one row
    per order; the verdict goes in BASELINE.md."""
    grid, cfg, lines, rng, Point = _setup(n, 0.8)
    qpts = _queries(grid, rng, q, Point)
    rows = []
    for order in ("arrival", "cell"):
        _run_once(grid, cfg, lines, qpts, radius, 40_000, 5_000,
                  "adaptive", 25_000, shard_order=order, devices=8)
        out, dt, stats = _run_once(grid, cfg, lines, qpts, radius,
                                   40_000, 5_000, "adaptive", 25_000,
                                   shard_order=order, devices=8)
        rows.append({"bench": "shard_order_ab", "order": order,
                     "records": n, "queries": q, "devices": 8,
                     "rps": round(n / dt),
                     "selectivity": stats["selectivity"]})
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--queries", type=int, default=128,
                    help="standing-query fleet size (the Q axis)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless the acceptance gates pass")
    ap.add_argument("--shard-order-ab", action="store_true",
                    help="also run the --shard-order arrival-vs-cell A/B "
                         "on an 8-way virtual CPU mesh")
    args = ap.parse_args()

    if args.shard_order_ab:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()

    import jax

    backend = jax.default_backend()
    rows = sweep(args.n, args.queries)
    for r in rows:
        r["backend"] = backend
    if args.shard_order_ab:
        if len(jax.devices()) >= 8:
            rows += shard_order_ab(args.n, args.queries)
        else:
            print("# shard-order A/B skipped: need 8 devices "
                  f"(have {len(jax.devices())})", file=sys.stderr)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"backend": backend, "rows": rows}, f, indent=1)
    if args.check:
        return check(rows)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
