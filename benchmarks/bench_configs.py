"""BASELINE.md benchmark ledger: all five canonical configs + p50 latency.

Usage: python benchmarks/bench_configs.py [--scale small|full] [--out PATH]

Emits one JSON line per config and writes the full table to
``benchmarks/RESULTS_<backend>.json``. Configs (BASELINE.md):

1. Point-Point range, Beijing 100x100 grid, r=0.5, 1M-point window
2. Point-Point kNN k=50, 1M-point window  (the bench.py headline)
3. Stream-stream join, grid-cell hash join (a sharded x b replicated lattice)
4. Point-Polygon range, 10k-polygon query set, batched point-in-polygon
5. Polygon-Polygon range over data-parallel windows on an 8-device mesh
   (virtual CPU mesh here; the multi-host SHAPE, not a hardware number)

Throughput uses the slope method (index-dependent fori_loop timed at two
iteration counts — isolates steady-state per-window device time from
dispatch overhead; see bench.py). p50 window latency is the dispatch->
readback wall clock of a single window, the latency a realtime caller sees.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BEIJING = (115.50, 117.60, 39.60, 41.10)

# shared escalation constants (bench.py keeps its own copy by design — the
# driver runs it standalone at round end; keep the values in sync)
SLOPE_MIN_GAP_S = 0.2
SLOPE_MAX_HI = 40_000


def _slope_time_ex(run_n, lo=2, hi=10):
    """Steady-state (seconds per iteration, gap_cleared_floor) of run_n(iters).

    ``run_n`` must take the loop count as a DYNAMIC (traced) argument so one
    compile covers every count (warm-up runs once, not per count). The high
    count escalates (×5) until the timed gap clears the dispatch->readback
    jitter — a fixed 4-8 window gap is a few ms for the fast kernels, well
    inside that jitter (the "non-positive slope" failure mode).
    ``ok=False`` marks a measurement whose gap never cleared the floor even
    at the cap; callers must surface it (sweep rows, warnings)."""
    import jax
    import jax.numpy as jnp

    warmed = False

    def timed(iters):
        nonlocal warmed
        it = jnp.int32(iters)
        if not warmed:  # compile + warm, once
            jax.block_until_ready(run_n(it))
            warmed = True
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(run_n(it))
            best = min(best, time.perf_counter() - t0)
        return best

    t_lo = timed(lo)
    while True:
        t_hi = timed(hi)
        gap = t_hi - t_lo
        if gap >= SLOPE_MIN_GAP_S or hi >= SLOPE_MAX_HI:
            break
        hi = min(hi * 5, SLOPE_MAX_HI)
    per = gap / (hi - lo)
    return (per if per > 0 else t_hi / hi), gap >= SLOPE_MIN_GAP_S


def _slope_time(run_n, lo=2, hi=10) -> float:
    per, ok = _slope_time_ex(run_n, lo=lo, hi=hi)
    if not ok:
        print("warning: slope gap stayed below the floor at the window cap; "
              "result may be noise-dominated", file=sys.stderr)
    return per


def _p50_latency_ms(dispatch, n=21) -> float:
    """p50 of single-window dispatch->readback wall clock."""
    import jax

    jax.block_until_ready(dispatch())  # compile
    lats = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(dispatch())
        lats.append((time.perf_counter() - t0) * 1000)
    return float(np.percentile(lats, 50))


def _grid():
    from spatialflink_tpu.index import UniformGrid

    return UniformGrid(BEIJING[0], BEIJING[1], BEIJING[2], BEIJING[3],
                       num_grid_partitions=100)


def _points(grid, n, seed=0, oid_mod=None):
    from spatialflink_tpu.models import PointBatch

    rng = np.random.default_rng(seed)
    return PointBatch.from_arrays(
        rng.uniform(grid.min_x, grid.max_x, n),
        rng.uniform(grid.min_y, grid.max_y, n),
        grid=grid,
        obj_id=rng.integers(0, oid_mod or max(4, n // 4), n).astype(np.int32),
    )


def bench_config1_range(scale) -> dict:
    import jax
    import jax.numpy as jnp

    from spatialflink_tpu.ops.range import range_filter_point

    grid = _grid()
    n = 1_000_000 if scale == "full" else 262_144
    batch = jax.device_put(_points(grid, n))
    qx, qy = 116.5, 40.5
    qc = jnp.int32(grid.assign_cell(qx, qy)[0])
    r = 0.5
    gn, cn = grid.guaranteed_layers(r), grid.candidate_layers(r)

    @jax.jit
    def run_n(iters):
        def body(i, acc):
            mask, _ = range_filter_point(
                batch, qx + i * 1e-7, qy, qc, r, gn, cn, n=grid.n)
            return acc + jnp.sum(mask, dtype=jnp.int32)
        return jax.lax.fori_loop(0, iters, body, jnp.int32(0))

    per = _slope_time(run_n)
    # batch must be a traced ARGUMENT: a zero-arg jit closure is all
    # constants and XLA folds the whole window at compile time
    win = jax.jit(lambda b: range_filter_point(b, qx, qy, qc, r, gn, cn,
                                               n=grid.n)[0])
    p50 = _p50_latency_ms(lambda: win(batch))
    return dict(config=1, name="pp_range_r0.5", window_points=n,
                points_per_sec=round(n / per), p50_window_latency_ms=round(p50, 3))


def bench_config3_join(scale) -> dict:
    import jax
    import jax.numpy as jnp

    from spatialflink_tpu.ops.join import join_counts

    grid = _grid()
    na = 262_144 if scale == "full" else 65_536
    nb = 1_024
    a = jax.device_put(_points(grid, na, seed=1))
    b = jax.device_put(_points(grid, nb, seed=2))
    r = 0.05
    layers = grid.candidate_layers(r)
    cx = grid.min_x + grid.cell_length * grid.n / 2
    cy = grid.min_y + grid.cell_length * grid.n / 2

    @jax.jit
    def run_n(iters):
        def body(i, acc):
            per_a, total = join_counts(a, b, r + i * 1e-9, layers, cx, cy,
                                       n=grid.n)
            return acc + total
        return jax.lax.fori_loop(0, iters, body, jnp.int32(0))

    per = _slope_time(run_n)
    win = jax.jit(lambda aa, bb: join_counts(aa, bb, r, layers, cx, cy,
                                             n=grid.n)[1])
    p50 = _p50_latency_ms(lambda: win(a, b))
    return dict(config=3, name="pp_join_lattice", a_points=na, b_points=nb,
                pair_tests_per_sec=round(na * nb / per),
                a_points_per_sec=round(na / per),
                p50_window_latency_ms=round(p50, 3))


def bench_config4_pip(scale) -> dict:
    import jax
    import jax.numpy as jnp

    from spatialflink_tpu.models import Polygon
    from spatialflink_tpu.models.batches import EdgeGeomBatch
    from spatialflink_tpu.ops.geom import points_to_geoms_dist

    grid = _grid()
    n = 65_536 if scale == "full" else 8_192
    g = 10_240 if scale == "full" else 1_024
    rng = np.random.default_rng(3)
    polys = []
    for i in range(g):
        cx = rng.uniform(grid.min_x + 0.1, grid.max_x - 0.1)
        cy = rng.uniform(grid.min_y + 0.1, grid.max_y - 0.1)
        w, h = rng.uniform(0.01, 0.05, 2)
        polys.append(Polygon.create(
            [[(cx - w, cy - h), (cx + w, cy - h), (cx + w, cy + h),
              (cx - w, cy + h), (cx - w, cy - h)]], grid))
    gb = jax.device_put(EdgeGeomBatch.from_objects(polys, grid))
    pts = jax.device_put(_points(grid, n, seed=4))

    @jax.jit
    def run_n(iters):
        def body(i, acc):
            d = points_to_geoms_dist(
                pts._replace(x=pts.x + i * 1e-9), gb)
            return acc + jnp.sum(d <= 0.0)
        return jax.lax.fori_loop(0, iters, body, jnp.int32(0))

    per = _slope_time(run_n, lo=2, hi=6)
    win = jax.jit(points_to_geoms_dist)
    p50 = _p50_latency_ms(lambda: win(pts, gb))
    return dict(config=4, name="point_polygon_pip", points=n, polygons=g,
                pip_tests_per_sec=round(n * g / per),
                points_per_sec=round(n / per),
                p50_window_latency_ms=round(p50, 3))


def bench_config5_multidevice(scale) -> dict:
    """Data-parallel windows over a mesh: polygon-polygon range THROUGH THE
    OPERATOR (``GeomGeomRangeQuery`` with conf.devices — the same path
    ``run_option(option=21, parallelism=N)`` drives; VERDICT r3 missing #3).
    On CPU this validates the SHAPE on 8 virtual devices (not a hardware
    number); on a real multi-chip slice the same code is the measurement."""
    import jax
    import jax.numpy as jnp

    from spatialflink_tpu.models import Polygon
    from spatialflink_tpu.operators import (
        PolygonPolygonRangeQuery,
        QueryConfiguration,
        QueryType,
    )

    n_dev = len(jax.devices())
    grid = _grid()
    g = 8_192 if scale == "full" else 2_048
    rng = np.random.default_rng(5)
    polys = []
    for i in range(g):
        cx = rng.uniform(grid.min_x + 0.1, grid.max_x - 0.1)
        cy = rng.uniform(grid.min_y + 0.1, grid.max_y - 0.1)
        w, h = rng.uniform(0.01, 0.05, 2)
        polys.append(Polygon.create(
            [[(cx - w, cy - h), (cx + w, cy - h), (cx + w, cy + h),
              (cx - w, cy + h), (cx - w, cy - h)]], grid,
            obj_id=f"g{i}", timestamp=1_700_000_000_000 + i))
    q = Polygon.create([[(116.2, 40.2), (117.0, 40.2), (117.0, 40.9),
                         (116.2, 40.9), (116.2, 40.2)]], grid)
    r = 0.5

    conf = QueryConfiguration(QueryType.WindowBased, 10_000, 10_000,
                              devices=n_dev)
    op = PolygonPolygonRangeQuery(conf, grid)
    # sanity: the full driver-reachable path emits the window
    n_matched = sum(len(w.records) for w in op.run(iter(polys), q, r))

    # steady-state timing over the operator's own kernels: the same
    # mask_stats closure + mesh dispatch run() uses, on its own geom batch
    mask_stats = op._mask_stats_fn(q, r)
    gb = op._shard(op._geom_batch(polys, 1_700_000_000_000))

    @jax.jit
    def run_n(iters):
        def body(i, acc):
            m, _gn, _ev = op._filter_stream(
                gb._replace(edges=gb.edges + i * 1e-9), mask_stats)
            return acc + jnp.sum(m, dtype=jnp.int32)
        return jax.lax.fori_loop(0, iters, body, jnp.int32(0))

    per = _slope_time(run_n, lo=2, hi=6)
    win = jax.jit(lambda b: op._filter_stream(b, mask_stats)[0])
    p50 = _p50_latency_ms(lambda: win(gb))
    return dict(config=5, name="polygon_polygon_range_mesh_operator",
                polygons=g, devices=n_dev, matched=n_matched,
                geoms_per_sec=round(g / per),
                p50_window_latency_ms=round(p50, 3))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=("small", "full"), default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--configs", default="1,3,4,5",
                    help="comma-separated subset")
    args = ap.parse_args()

    import jax

    backend = jax.default_backend()
    scale = args.scale or ("full" if backend == "tpu" else "small")
    fns = {1: bench_config1_range, 3: bench_config3_join,
           4: bench_config4_pip, 5: bench_config5_multidevice}
    rows = []
    for c in (int(x) for x in args.configs.split(",")):
        row = fns[c](scale)
        row["backend"] = backend
        row["scale"] = scale
        print(json.dumps(row), flush=True)
        rows.append(row)
    # a SUBSET run must not silently replace the full ledger (compare the
    # parsed sets — order/whitespace in --configs must not matter)
    requested = {int(x) for x in args.configs.split(",")}
    name = (f"RESULTS_{backend}.json" if requested >= set(fns)
            else f"RESULTS_{backend}_partial.json")
    out = args.out or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), name)
    with open(out, "w") as f:
        json.dump({"backend": backend, "scale": scale, "rows": rows}, f,
                  indent=1)
    print(f"# wrote {out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
