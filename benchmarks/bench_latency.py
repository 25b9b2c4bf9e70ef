"""Throughput/latency Pareto harness for the latency-decomposition plane.

ROADMAP item 3 (latency-tiered serving) names its bench bar: the
record→emit p50/p99 vs throughput Pareto curve. Emission granularity is
one decode chunk (``driver.decode_chunks``), so the decode chunk size is
the latency/throughput knob the future adaptive controller will turn —
smaller chunks seal windows sooner (lower record→emit latency), larger
chunks amortize the per-chunk parse/assign/dispatch cost (higher
throughput). This harness SWEEPS that knob (the ``SPATIALFLINK_DECODE_CHUNK``
axis) × query family × pipeline depth and reads record→emit p50/p99 off
the latency plane (``utils.latencyplane`` — the same numbers ``GET
/latency`` serves), producing the Pareto table in
``RESULTS_latency_<backend>.json`` and BASELINE.md.

Window-table identity is asserted across every chunk size / depth of a
family (the knob must never change results), and an ``overhead_plane``
row re-measures the full-plane cost (telemetry session + latency plane
vs the uninstrumented loop) so the plane's own budget stays on the PR 10
bar (≈ noise).

Usage:
    python benchmarks/bench_latency.py [--n N] [--chunks 512,2048,4096,8192]
        [--depths 1,2] [--families range,knn] [--out PATH]
        [--require-backend cpu|tpu]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _lines(n: int):
    rng = np.random.default_rng(0)
    t0 = 1_700_000_000_000
    # 100 s of event time: 10s/5s sliding windows -> 21 windows, most
    # sealing MID-stream (the record→emit number is dominated by steady
    # state, not the end-of-stream flush tail)
    ts = t0 + (np.arange(n) * 100_000 // max(n, 1))
    return [f"v{int(i) % 97},{int(t)},"
            f"{115.5 + rng.random() * 2:.6f},{39.6 + rng.random() * 1.5:.6f}"
            for i, t in enumerate(ts)]


def _cfg_grid():
    from spatialflink_tpu.config import StreamConfig
    from spatialflink_tpu.index import UniformGrid

    return (StreamConfig(format="CSV", date_format=None,
                         csv_tsv_schema=[0, 1, 2, 3]),
            UniformGrid(115.5, 117.6, 39.6, 41.1, num_grid_partitions=100))


def _paced(lines, rate: float):
    """Yield ``lines`` at ``rate`` records/s (batched sleeps): the LIVE
    shape of the latency question. On an unpaced replay record→emit is
    decode-bound (a window's latency ≈ its span's records / throughput,
    so the big-chunk amortization wins both axes); under a fixed input
    rate the chunk knob shows its real trade — chunk-fill wait (up to
    chunk/rate, bounded by the decoder's 0.2 s age flush) against
    per-chunk amortization."""
    t0 = time.perf_counter()
    sent = 0
    step = 256
    for i in range(0, len(lines), step):
        batch = lines[i:i + step]
        dt = sent / rate - (time.perf_counter() - t0)
        if dt > 0:
            time.sleep(dt)
        yield from batch
        sent += len(batch)


def _run_once(family: str, lines, cfg, grid, chunk: int, depth: int,
              session: bool):
    """(window_table, wall_s, emit_hist|None) for one configuration."""
    from spatialflink_tpu import driver
    from spatialflink_tpu.models import Point
    from spatialflink_tpu.operators import (PointPointKNNQuery,
                                            PointPointRangeQuery,
                                            QueryConfiguration, QueryType)
    from spatialflink_tpu.utils.telemetry import telemetry_session

    conf = QueryConfiguration(QueryType.WindowBased, 10_000, 5_000,
                              pipeline_depth=depth)
    qp = Point.create(116.5, 40.3, grid, obj_id="q")

    def pipeline():
        stream = driver.decode_stream(iter(lines), cfg, grid, chunk=chunk)
        if family == "knn":
            op = PointPointKNNQuery(conf, grid)
            return [(r.window_start, tuple(sorted(o for o, _ in r.records)))
                    for r in op.run(stream, qp, 0.5, 10)]
        op = PointPointRangeQuery(conf, grid)
        return [(r.window_start, len(r.records))
                for r in op.run(stream, qp, 0.5)]

    if not session:
        t0 = time.perf_counter()
        table = pipeline()
        return table, time.perf_counter() - t0, None
    with telemetry_session() as tel:
        t0 = time.perf_counter()
        table = pipeline()
        wall = time.perf_counter() - t0
        emit = tel.latency.record_emit
        assert tel.latency.max_residual_ms < 1.0, (
            "stage budget no longer sums to record→emit "
            f"(max residual {tel.latency.max_residual_ms} ms)")
        return table, wall, emit.to_dict()


def measure(n: int, chunks, depths, families):
    cfg, grid = _cfg_grid()
    lines = _lines(n)
    rows = []
    for family in families:
        # jit warm + the identity reference (default chunk, depth 2)
        ref, _, _ = _run_once(family, lines, cfg, grid, 4096, 2, False)
        for depth in depths:
            for chunk in chunks:
                table, wall, emit = _run_once(family, lines, cfg, grid,
                                              chunk, depth, True)
                assert table == ref, (
                    f"{family}: window table diverged at chunk={chunk} "
                    f"depth={depth} — the latency knob must never change "
                    "results")
                rows.append({
                    "path": "pareto", "family": family, "chunk": chunk,
                    "depth": depth, "records": n,
                    "wall_s": round(wall, 3),
                    "records_per_sec": int(n / wall),
                    "windows": len(table),
                    "emit_p50_ms": emit.get("p50"),
                    "emit_p99_ms": emit.get("p99"),
                    "emit_count": emit.get("count"),
                })
                print(json.dumps(rows[-1]), flush=True)
    # paced sweep: the live half of the Pareto — a fixed input rate, so
    # record→emit isolates the PIPELINE-ADDED latency (chunk fill + seal
    # queue + dispatch + merge) instead of the replay's decode-bound fill
    from spatialflink_tpu import driver
    from spatialflink_tpu.models import Point
    from spatialflink_tpu.operators import (PointPointRangeQuery,
                                            QueryConfiguration, QueryType)
    from spatialflink_tpu.utils.telemetry import telemetry_session

    rate = 100_000.0
    n_paced = min(len(lines), 30_000)
    paced_lines = lines[:n_paced]
    conf = QueryConfiguration(QueryType.WindowBased, 10_000, 5_000,
                              pipeline_depth=2)
    qp = Point.create(116.5, 40.3, grid, obj_id="q")
    for chunk in chunks:
        with telemetry_session() as tel:
            op = PointPointRangeQuery(conf, grid)
            stream = driver.decode_stream(_paced(paced_lines, rate), cfg,
                                          grid, chunk=chunk)
            t0 = time.perf_counter()
            n_win = sum(1 for _ in op.run(stream, qp, 0.5))
            wall = time.perf_counter() - t0
            emit = tel.latency.record_emit.to_dict()
        rows.append({
            "path": "paced", "family": "range", "chunk": chunk, "depth": 2,
            "records": n_paced, "rate_rps": int(rate),
            "achieved_rps": int(n_paced / wall), "windows": n_win,
            "emit_p50_ms": emit.get("p50"),
            "emit_p99_ms": emit.get("p99"),
        })
        print(json.dumps(rows[-1]), flush=True)
    # full-plane overhead at the default operating point: the latency
    # plane rides every session, so this is the PR 10 "full plane" cost
    # re-measured with the new per-window budget chain in it
    fam = families[0]
    _run_once(fam, lines, cfg, grid, 4096, 2, False)  # warm
    _, off_wall, _ = _run_once(fam, lines, cfg, grid, 4096, 2, False)
    _, on_wall, _ = _run_once(fam, lines, cfg, grid, 4096, 2, True)
    rows.append({
        "path": "overhead_plane", "family": fam, "chunk": 4096, "depth": 2,
        "records": n, "wall_off_s": round(off_wall, 3),
        "wall_on_s": round(on_wall, 3),
        "overhead_pct": round((on_wall - off_wall) / off_wall * 100, 1),
    })
    print(json.dumps(rows[-1]), flush=True)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=None,
                    help="records (default 1M on TPU, 60k on CPU)")
    ap.add_argument("--chunks", default="512,2048,4096,8192")
    ap.add_argument("--depths", default="1,2")
    ap.add_argument("--families", default="range,knn")
    ap.add_argument("--out", default=None)
    ap.add_argument("--require-backend", default=None,
                    choices=("cpu", "tpu", "gpu"),
                    help="refuse to measure on any other backend (exit 2)")
    args = ap.parse_args()

    import jax

    from spatialflink_tpu.utils import deviceplane

    backend = jax.default_backend()
    if args.require_backend and backend != args.require_backend:
        print(f"bench_latency: --require-backend {args.require_backend} "
              f"but the process landed on '{backend}'; refusing to measure",
              file=sys.stderr)
        return 2
    n = args.n or (1_000_000 if backend == "tpu" else 60_000)
    chunks = [int(c) for c in args.chunks.split(",") if c]
    depths = [int(d) for d in args.depths.split(",") if d]
    families = [f for f in args.families.split(",") if f]

    prov = deviceplane.backend_provenance()
    rows = measure(n, chunks, depths, families)
    for r in rows:
        r["backend"] = backend
        r["device_kind"] = prov["device_kind"]
        r["valid_for_target"] = prov["valid_for_target"]

    out = args.out or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"RESULTS_latency_{backend}.json")
    with open(out, "w") as f:
        json.dump({"backend": backend, "n": n, "rows": rows}, f, indent=1)
    print(f"# wrote {out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
