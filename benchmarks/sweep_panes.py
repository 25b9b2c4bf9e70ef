"""Window-size scaling sweep for the pane-incremental engine (VERDICT #4):
kNN and join throughput at growing stream sizes and sliding overlaps
(window = overlap * slide), panes on vs off, window-table identity asserted
per configuration.

- kNN rides the served windowed pipeline (chunked decode + window
  assembly + kernels; the decode is identical in both modes).
- join rides the record-path windowed pipeline (pane-pair blocks are a
  record-path feature); its stream sizes default to 1/16 of the kNN sizes
  because the O(Na x Nb) pair lattice, not the pane engine, dominates
  large CPU joins.

Usage:
    python benchmarks/sweep_panes.py [--sizes 1000000,4000000,16000000]
        [--overlaps 1,4,8] [--families knn,join] [--join-divisor 16]
        [--out PATH]

Emits one JSON line per (family, size, overlap, panes) and writes the
table to ``benchmarks/RESULTS_panes_<backend>.json`` — the BASELINE.md
pane-scaling ledger's source. Overlap 1 is the tumbling control: the pane
cache bypasses (overlap 1 shares nothing), so on/off rows there should
measure noise, not speedup.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.bench_e2e import SLIDE_S, _params, _served_run, _write_stream


def _canon_pairs(results) -> list:
    return [(r.window_start, r.window_end,
             sorted(((a.obj_id, a.timestamp), (b.obj_id, b.timestamp))
                    for a, b in r.records))
            for r in results]


def sweep_knn(path: str, n: int, overlaps, rows: list, backend: str) -> None:
    from spatialflink_tpu import driver

    p = _params(51)
    spec = driver.CASES[51]
    q = driver._query_object(p, p.grids()[0], "Point")

    for overlap in overlaps:
        p.window.interval_s = SLIDE_S * overlap
        p.window.step_s = SLIDE_S

        def run(panes: bool):
            p.query.panes = panes
            t0 = time.perf_counter()
            table = _served_run(p, spec, path, q)
            return table, time.perf_counter() - t0

        run(False)  # warm BOTH modes' jit shapes outside the timed rows
        run(True)   # (full-window buckets differ per overlap; pane shapes too)
        t_off, dt_off = run(False)
        t_on, dt_on = run(True)
        assert t_on == t_off, f"knn n={n} overlap={overlap}: table diverged"
        for panes, dt in (("off", dt_off), ("on", dt_on)):
            row = dict(family="knn", records=n, overlap=overlap, panes=panes,
                       windows=len(t_off), wall_s=round(dt, 3),
                       records_per_sec=round(n / dt), identical=True,
                       backend=backend)
            if panes == "on":
                row["speedup_vs_panes_off"] = round(dt_off / dt_on, 2)
            print(json.dumps(row), flush=True)
            rows.append(row)


def sweep_join(path: str, path2: str, n: int, overlaps, rows: list,
               backend: str) -> None:
    from spatialflink_tpu import driver
    from spatialflink_tpu.operators import PointPointJoinQuery
    from spatialflink_tpu.streams.bulk import bulk_parse_csv

    p = _params(101)
    # sparse-join radius: at bench_e2e's r=0.5 over this extent ~23% of all
    # pairs survive, so O(survivor) host pair materialization — identical in
    # both modes — swamps the lattice kernels the pane blocks reuse. 0.05
    # is the realistic-selectivity regime where the lattice dominates.
    p.query.radius = 0.05
    u_grid, _ = p.grids()
    schema = driver._schema4(p.input1)
    with open(path, "rb") as f:
        pts_a = bulk_parse_csv(f.read(), schema=schema,
                               date_format=None).to_points(u_grid)
    with open(path2, "rb") as f:
        pts_b = bulk_parse_csv(f.read(), schema=schema,
                               date_format=None).to_points(u_grid)

    for overlap in overlaps:
        p.window.interval_s = SLIDE_S * overlap
        p.window.step_s = SLIDE_S

        def run(panes: bool):
            p.query.panes = panes
            conf = driver._query_conf(p, driver.CASES[101])
            op = PointPointJoinQuery(conf, u_grid, u_grid)
            t0 = time.perf_counter()
            table = _canon_pairs(op.run(iter(pts_a), iter(pts_b),
                                        p.query.radius))
            return table, time.perf_counter() - t0

        run(False)  # warm both modes outside the timed rows
        run(True)
        t_off, dt_off = run(False)
        t_on, dt_on = run(True)
        assert t_on == t_off, f"join n={n} overlap={overlap}: table diverged"
        for panes, dt in (("off", dt_off), ("on", dt_on)):
            row = dict(family="join", records=n, overlap=overlap,
                       panes=panes, windows=len(t_off), wall_s=round(dt, 3),
                       records_per_sec=round(n / dt), identical=True,
                       backend=backend)
            if panes == "on":
                row["speedup_vs_panes_off"] = round(dt_off / dt_on, 2)
            print(json.dumps(row), flush=True)
            rows.append(row)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="1000000,4000000,16000000",
                    help="comma-separated stream sizes (kNN; join divides "
                         "by --join-divisor)")
    ap.add_argument("--overlaps", default="1,4,8")
    ap.add_argument("--families", default="knn,join")
    ap.add_argument("--join-divisor", type=int, default=16,
                    help="join stream size = size // divisor (the pair "
                         "lattice, not the pane engine, dominates large "
                         "CPU joins)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax

    backend = jax.default_backend()
    sizes = [int(x) for x in args.sizes.split(",")]
    overlaps = [int(x) for x in args.overlaps.split(",")]
    families = args.families.split(",")

    rows: list = []
    with tempfile.TemporaryDirectory() as td:
        for n in sizes:
            path = os.path.join(td, f"s{n}.csv")
            _write_stream(path, n, seed=0)
            if "knn" in families:
                sweep_knn(path, n, overlaps, rows, backend)
            if "join" in families:
                nj = max(n // args.join_divisor, 1)
                pj = os.path.join(td, f"j{nj}.csv")
                pj2 = os.path.join(td, f"j2{nj}.csv")
                _write_stream(pj, nj, seed=0)
                _write_stream(pj2, max(nj // 64, 1), seed=1)
                sweep_join(pj, pj2, nj, overlaps, rows, backend)
            os.unlink(path)

    out = args.out or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"RESULTS_panes_{backend}.json")
    with open(out, "w") as f:
        json.dump({"backend": backend, "rows": rows}, f, indent=1)
    print(f"# wrote {out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
