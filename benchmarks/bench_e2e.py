"""End-to-end streaming pipeline benchmark: sustained records/s through the
WHOLE pipeline — host ingest -> watermarks -> window assembly -> device
kernel -> results — not just the device hot loop.

The kernel benches (bench.py, bench_configs.py) isolate per-window device
time; bench_ingest.py isolates the parsers. This harness measures what the
reference's Kafka->Flink jobs were actually measured by (throughput meters
wrapping the live pipeline, ``spatialObjects/Point.java:237-253``): wall
clock from the first raw record entering deserialization to the last window
sealed, for the driver path a user runs:

- ``record``: raw lines -> ``driver.run_option`` (chunked native decode,
  columnar windowing, the served path of every mode)

Usage: python benchmarks/bench_e2e.py [--n N] [--options 1,51,101]
       [--out PATH]

Emits one JSON line per (option, path) and writes the table to
``benchmarks/RESULTS_e2e_<backend>.json``.
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

BEIJING = (115.50, 117.60, 39.60, 41.10)
WINDOW_S, SLIDE_S = 10, 5
SPAN_S = 100  # event time spanned by the stream -> ~20 sliding windows


def _write_stream(path: str, n: int, seed: int = 0) -> None:
    """CSV point rows ``oid,ts_ms,x,y`` spanning SPAN_S of event time,
    timestamps nondecreasing (in-order stream; lateness is the lateness
    tests' concern, throughput is this bench's)."""
    rng = np.random.default_rng(seed)
    xs = rng.uniform(BEIJING[0], BEIJING[1], n)
    ys = rng.uniform(BEIJING[2], BEIJING[3], n)
    oid = rng.integers(0, max(n // 4, 1), n)
    t0 = 1_700_000_000_000
    ts = t0 + (np.arange(n) * (SPAN_S * 1000) // max(n, 1))
    with open(path, "w") as f:
        for i in range(n):
            f.write(f"v{oid[i]},{ts[i]},{xs[i]:.6f},{ys[i]:.6f}\n")


def _params(option: int):
    from spatialflink_tpu.config import Params

    conf = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "conf", "spatialflink-conf.yml")
    p = Params.from_yaml(conf)
    p.query.option = option
    p.query.radius = 0.5
    p.query.k = 50
    p.input1.format = "CSV"
    p.input1.date_format = None  # epoch-millisecond timestamps
    p.input2.format = "CSV"
    p.input2.date_format = None
    p.window.interval_s = WINDOW_S
    p.window.step_s = SLIDE_S
    return p


def _drain(it) -> int:
    windows = 0
    for _ in it:
        windows += 1
    return windows


def bench_option(option: int, path: str, path2, n: int) -> list:
    from spatialflink_tpu import driver

    needs2 = driver.CASES[option].family == "join"

    def run() -> tuple:
        p = _params(option)
        with open(path) as f1:
            streams = [f1]
            if needs2:
                streams.append(open(path2))
            try:
                t0 = time.perf_counter()
                windows = _drain(driver.run_option(p, *streams))
                return windows, time.perf_counter() - t0
            finally:
                for s in streams[1:]:
                    s.close()

    # an untimed pass first warms the jit cache, so the row measures
    # steady-state host cost, not compiles
    run()
    windows, dt = run()
    return [dict(option=option, path="record", records=n, windows=windows,
                 wall_s=round(dt, 3), records_per_sec=round(n / dt))]


def _window_table(results, option: int) -> list:
    """Canonical (start, end, sorted-records) table for the pane identity
    check: range windows carry points, kNN windows (objID, distance)
    pairs."""
    table = []
    for r in results:
        recs = list(r.records)
        if recs and isinstance(recs[0], tuple):
            recs = [(o, round(float(d), 6)) for o, d in recs]
        else:
            recs = [(p.obj_id, p.timestamp) for p in recs]
        table.append((r.window_start, r.window_end, sorted(recs)))
    return table


def _served_run(p, spec, path: str, q):
    """One served run of a range/kNN case over the replay file: chunked
    decode + the operator's windowed pipeline."""
    from spatialflink_tpu import driver

    u_grid, _ = p.grids()
    op = driver._operator_class(spec)(driver._query_conf(p, spec), u_grid)
    with open(path) as f:
        stream = driver.decode_stream(f, p.input1, u_grid)
        if spec.family == "range":
            return _window_table(op.run(stream, q, p.query.radius),
                                 p.query.option)
        return _window_table(op.run(stream, q, p.query.radius, p.query.k),
                             p.query.option)


def bench_panes(option: int, path: str, n: int, overlap: int) -> list:
    """Pane-incremental vs full-recompute at sliding overlap ``overlap``
    (window = overlap * slide), same backend, same replay — with window-
    table IDENTITY asserted in the same run (panes are an execution
    strategy, not a semantics change). Both modes drive the served
    pipeline over the replay: the rows measure decode + window assembly +
    kernels + readback, with the decode identical in both modes. The
    on-row carries the measured speedup."""
    from spatialflink_tpu import driver

    p = _params(option)
    p.window.interval_s = SLIDE_S * overlap
    p.window.step_s = SLIDE_S
    spec = driver.CASES[option]
    q = driver._query_object(p, p.grids()[0], spec.query)

    def run(panes: bool):
        p.query.panes = panes
        t0 = time.perf_counter()
        table = _served_run(p, spec, path, q)
        return table, time.perf_counter() - t0

    run(False)  # warm the jit caches both modes share
    run(True)   # (pane batches have their own bucketed shapes)
    table_off, dt_off = run(False)
    table_on, dt_on = run(True)
    assert table_on == table_off, (
        f"option {option} overlap {overlap}: pane window table diverged "
        "from full recompute")
    base = dict(option=option, overlap=overlap, records=n,
                windows=len(table_off), identical=True)
    return [
        dict(base, path="panes_off", wall_s=round(dt_off, 3),
             records_per_sec=round(n / dt_off)),
        dict(base, path="panes_on", wall_s=round(dt_on, 3),
             records_per_sec=round(n / dt_on),
             speedup_vs_panes_off=round(dt_off / dt_on, 2)),
    ]


def bench_pane_state(option: int, path: str, n: int, overlap: int) -> list:
    """Device-resident vs host-merged pane state (the --pane-merge A/B) at
    sliding overlap ``overlap``: same replay, same backend, window-table
    identity asserted in-run. Device mode keeps pane kernel partials in
    device memory and merges each window ON device (one merged readback per
    window); host mode resolves every partial to host and merges there.
    Rows carry the measured per-slide readback bytes/transfers from the
    always-on registry counters (the same numbers the bytes_moved cost
    profile accumulates), so the data-motion contract is part of the
    ledger. Runs unchanged on any backend — on the TPU each readback saved
    is also a dispatch->readback sync, not just bytes."""
    from spatialflink_tpu import driver
    from spatialflink_tpu.utils.metrics import REGISTRY, scoped_registry

    p = _params(option)
    p.window.interval_s = SLIDE_S * overlap
    p.window.step_s = SLIDE_S
    p.query.panes = True
    spec = driver.CASES[option]
    q = driver._query_object(p, p.grids()[0], spec.query)

    def run(device: bool):
        p.query.pane_device_merge = device
        with scoped_registry() as reg:
            t0 = time.perf_counter()
            table = _served_run(p, spec, path, q)
            dt = time.perf_counter() - t0
            snap = reg.snapshot()
        return table, dt, snap

    run(True)   # warm both modes' jit shapes outside the timed rows
    run(False)
    t_dev, dt_dev, snap_dev = run(True)
    t_host, dt_host, snap_host = run(False)
    assert t_dev == t_host, (
        f"option {option} overlap {overlap}: device pane merge diverged "
        "from host merge")
    slides = max(len(t_dev), 1)
    base = dict(option=option, overlap=overlap, records=n,
                windows=len(t_dev), identical=True)

    def row(path_name, dt, snap):
        rb_b = int(snap.get("pane-partial-readback-bytes", 0)
                   + snap.get("pane-merged-readback-bytes", 0))
        rb_n = int(snap.get("pane-partial-readbacks", 0)
                   + snap.get("pane-merged-readbacks", 0))
        return dict(base, path=path_name, wall_s=round(dt, 3),
                    records_per_sec=round(n / dt),
                    pane_readback_bytes=rb_b, pane_readbacks=rb_n,
                    readback_bytes_per_slide=round(rb_b / slides, 1))

    r_host = row("panes_host_merge", dt_host, snap_host)
    r_dev = row("panes_device_merge", dt_dev, snap_dev)
    r_dev["speedup_vs_host_merge"] = round(dt_host / dt_dev, 2)
    r_dev["readback_bytes_vs_host"] = round(
        r_dev["pane_readback_bytes"] / max(r_host["pane_readback_bytes"], 1),
        3)
    return [r_host, r_dev]


def bench_checkpoint(option: int, path: str, n: int, every: int) -> list:
    """Coordinated-checkpoint overhead (the robustness cost BASELINE.md
    tracks): the record path with checkpointing OFF vs a coordinator
    snapshotting every ``every`` windows — sustained throughput plus the
    per-window latency distribution (a checkpoint writes at a window
    barrier, so its cost lands on individual windows' p99, not the mean)."""
    import shutil

    from spatialflink_tpu import driver

    def run(ckpt_dir):
        p = _params(option)
        if ckpt_dir is not None:
            from spatialflink_tpu.runtime.checkpoint import (
                CheckpointCoordinator)

            p.checkpointer = CheckpointCoordinator(
                ckpt_dir, every_batches=every, job="bench")
        lat = []
        with open(path) as f1:
            t0 = time.perf_counter()
            it = iter(driver.run_option(p, f1))
            while True:
                w0 = time.perf_counter()
                try:
                    next(it)
                except StopIteration:
                    break
                lat.append(time.perf_counter() - w0)
            dt = time.perf_counter() - t0
        return dt, lat

    def pct(lat, q):
        return round(float(np.percentile(np.asarray(lat) * 1e3, q)), 2)

    run(None)  # warm the jit caches both modes share
    dt_off, lat_off = run(None)
    td = tempfile.mkdtemp(prefix="bench-ckpt-")
    try:
        dt_on, lat_on = run(td)
        n_ckpt = len([f for f in os.listdir(td) if f.endswith(".npz")])
    finally:
        shutil.rmtree(td, ignore_errors=True)
    base = dict(option=option, records=n, windows=len(lat_off),
                checkpoint_every=every)
    return [
        dict(base, path="checkpoint_off", wall_s=round(dt_off, 3),
             records_per_sec=round(n / dt_off),
             window_latency_ms=dict(p50=pct(lat_off, 50),
                                    p99=pct(lat_off, 99))),
        dict(base, path="checkpoint_on", wall_s=round(dt_on, 3),
             records_per_sec=round(n / dt_on),
             checkpoints_written=n_ckpt,
             window_latency_ms=dict(p50=pct(lat_on, 50),
                                    p99=pct(lat_on, 99)),
             overhead_vs_off=round(dt_on / dt_off - 1.0, 4)),
    ]


def bench_live_plane(option: int, path: str, n: int) -> list:
    """Overhead of the live operations plane on the record path, four
    configurations over the same replay: plane OFF, a bound-but-UNQUERIED
    status server with no telemetry session (the contract is a
    byte-identical record loop — snapshots are built per HTTP request
    only, so this must be ~0), the full plane (telemetry session +
    status server + live-stats digest thread at an interval longer than
    the run — the session's per-record instrumentation is the cost), and
    the full plane WITH window trace lineage on (``--trace-dir``'s
    recording cost: per-WINDOW trace notes + per-record cost-profile
    pending accumulation — the trace-on overhead row BASELINE.md
    tracks)."""
    from spatialflink_tpu import driver
    from spatialflink_tpu.runtime.opserver import LiveStats, OpServer
    from spatialflink_tpu.utils.telemetry import telemetry_session

    def run():
        p = _params(option)
        with open(path) as f1:
            t0 = time.perf_counter()
            windows = _drain(driver.run_option(p, f1))
            return windows, time.perf_counter() - t0

    run()  # warm the jit caches all four configurations share
    windows, dt_off = run()
    srv = OpServer(port=0).start()
    try:
        _, dt_srv = run()
    finally:
        srv.close()

    def run_plane(trace: bool):
        from spatialflink_tpu.utils import deviceplane

        with telemetry_session(trace=trace) as tel:
            srv = OpServer(port=0).start()
            live = LiveStats(interval_s=3600.0).start()
            dp = deviceplane.registry()
            dp.begin_run()
            dp.mark_warm("bench live-plane (pre-warmed shapes)")
            try:
                dt = run()[1]
            finally:
                dp.end_run()
                live.close()
                srv.close()
            # the device-truth fields the full-plane ledger row carries:
            # post-warmup compiles (0 = the sentinel stayed silent) and
            # the per-window dispatch→ready overlap distribution
            h = tel.histograms.get("dispatch-overlap-ratio")
            overlap = h.to_dict() if h is not None else {"count": 0}
            return dt, dp.run_recompiles, overlap

    dt_full, rc_full, ovl_full = run_plane(trace=False)
    dt_trace, _rc_t, _ovl_t = run_plane(trace=True)
    base = dict(option=option, records=n, windows=windows)
    return [
        dict(base, path="live_plane_off", wall_s=round(dt_off, 3),
             records_per_sec=round(n / dt_off)),
        dict(base, path="status_server_idle", wall_s=round(dt_srv, 3),
             records_per_sec=round(n / dt_srv),
             overhead_vs_off=round(dt_srv / dt_off - 1.0, 4)),
        dict(base, path="live_plane_full", wall_s=round(dt_full, 3),
             records_per_sec=round(n / dt_full),
             overhead_vs_off=round(dt_full / dt_off - 1.0, 4),
             post_warmup_compiles=rc_full,
             dispatch_overlap=ovl_full),
        dict(base, path="live_plane_trace", wall_s=round(dt_trace, 3),
             records_per_sec=round(n / dt_trace),
             overhead_vs_off=round(dt_trace / dt_off - 1.0, 4),
             overhead_vs_full=round(dt_trace / dt_full - 1.0, 4)),
    ]


def bench_multi_vs_jobs(option: int, path: str, n: int, q: int) -> list:
    """ONE multiQuery pipeline vs Q sequential single-query pipelines over
    the same replay — the end-to-end form of the 'Q standing queries cost Q
    reference jobs re-reading the stream' claim. The served path for both
    sides."""
    from spatialflink_tpu import driver

    hotspots = [(116.0 + 0.9 * i / max(q - 1, 1),
                 40.0 + 0.9 * i / max(q - 1, 1)) for i in range(q)]

    def _drain_served(p):
        with open(path) as f:
            return _drain(driver.run_option(p, f))

    def run_multi():
        p = _params(option)
        p.query.multi_query = True
        p.query.query_points = hotspots
        return _drain_served(p)

    def run_jobs():
        for hx, hy in hotspots:
            p = _params(option)
            p.query.query_points = [(hx, hy)]
            _drain_served(p)

    # warm both sides (jit compiles; the sequential side would otherwise
    # free-ride on kernels the single-query rows above already compiled
    # while the (Q,)-shaped multi kernels compile inside the timed region)
    run_multi()
    run_jobs()
    t0 = time.perf_counter()
    windows = run_multi()
    dt_multi = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_jobs()
    dt_jobs = time.perf_counter() - t0

    return [dict(option=option, path="multi_query", queries=q, records=n,
                 windows=windows, wall_s=round(dt_multi, 3),
                 record_x_queries_per_sec=round(n * q / dt_multi),
                 speedup_vs_sequential_jobs=round(dt_jobs / dt_multi, 2)),
            dict(option=option, path="sequential_jobs", queries=q, records=n,
                 wall_s=round(dt_jobs, 3),
                 record_x_queries_per_sec=round(n * q / dt_jobs))]


def bench_query_plane(path: str, n: int, q: int = 32) -> list:
    """Standing-query control plane rows (ISSUE 10):

    - ``query_plane_static``  a Q-query fleet served through the DYNAMIC
                              registry path with no churn — the control
                              plane's baseline cost over run_multi
    - ``query_plane_churn``   the same fleet with one admit + one retire
                              per window interval (fleet size constant, so
                              every change repads within the same size
                              bucket) — admission churn must not collapse
                              throughput
    - ``query_plane_q<Q>``    Q-sweep amortization THROUGH the registry:
                              registry fleet vs Q dedicated single-query
                              pipelines re-reading the stream
    """
    from spatialflink_tpu import driver
    from spatialflink_tpu.config import StreamConfig
    from spatialflink_tpu.models import Point
    from spatialflink_tpu.operators import (PointPointRangeQuery,
                                            QueryConfiguration, QueryType)
    from spatialflink_tpu.runtime.queryplane import QueryRegistry

    import numpy as np

    with open(path) as f:
        lines = f.read().splitlines()
    cfg = StreamConfig(format="CSV", date_format=None,
                       csv_tsv_schema=[0, 1, 2, 3])
    grid = _params(1).grids()[0]
    conf = QueryConfiguration(QueryType.WindowBased,
                              int(WINDOW_S * 1000), int(SLIDE_S * 1000))
    rng = np.random.default_rng(5)
    radius = 0.5

    def mkpts(m):
        return [(float(grid.min_x + rng.random() * (grid.max_x - grid.min_x)),
                 float(grid.min_y + rng.random() * (grid.max_y - grid.min_y)))
                for _ in range(m)]

    def mkreg(pts):
        reg = QueryRegistry("range", radius=radius)
        for i, (x, y) in enumerate(pts):
            reg.admit({"id": f"q{i}", "x": x, "y": y})
        reg.apply()
        return reg

    def run_registry(pts, churn=False):
        reg = mkreg(pts)
        op = PointPointRangeQuery(conf, grid)
        stream = driver.decode_stream(iter(lines), cfg, grid)
        seq = {"i": 0}
        results = op.run_dynamic(stream, reg, radius)
        windows = 0
        t0 = time.perf_counter()
        for _w in results:
            windows += 1
            if churn:
                # one admit + one retire per emitted window: constant
                # fleet size — every change repads within the same bucket
                i = seq["i"]
                reg.admit({"id": f"churn{i}",
                           "x": float(grid.min_x + (i % 10) * 0.1),
                           "y": float(grid.min_y + (i % 10) * 0.1)})
                live = [e.id for e in reg.active_entries()]
                reg.retire(live[0])
                seq["i"] += 1
        dt = time.perf_counter() - t0
        return windows, dt, reg

    def run_jobs(pts):
        t0 = time.perf_counter()
        for x, y in pts:
            op = PointPointRangeQuery(conf, grid)
            stream = driver.decode_stream(iter(lines), cfg, grid)
            for _ in op.run(stream, Point.create(x, y, grid), radius):
                pass
        return time.perf_counter() - t0

    rows = []
    pts = mkpts(q)
    run_registry(pts)  # warm the bucket's jit shapes
    windows, dt_static, _ = run_registry(pts)
    w2, dt_churn, reg = run_registry(pts, churn=True)
    from spatialflink_tpu.ops.range import range_filter_point_multi_masks
    compiles_before = range_filter_point_multi_masks._cache_size()
    _w3, _dt3, _ = run_registry(pts, churn=True)
    recompiles = (range_filter_point_multi_masks._cache_size()
                  - compiles_before)
    rows.append(dict(path="query_plane_static", queries=q, records=n,
                     windows=windows, wall_s=round(dt_static, 3),
                     records_per_sec=round(n / dt_static)))
    rows.append(dict(path="query_plane_churn", queries=q, records=n,
                     windows=w2, wall_s=round(dt_churn, 3),
                     records_per_sec=round(n / dt_churn),
                     churn_per_interval="1 admit + 1 retire per window",
                     fleet_repads=reg.repads.count,
                     xla_recompiles_in_bucket=recompiles,
                     churn_vs_static=round(dt_static / dt_churn, 2)))
    # Q-sweep amortization through the registry path
    for m in (1, 8, q):
        spts = mkpts(m)
        run_registry(spts)
        _wn, dt_reg, _ = run_registry(spts)
        dt_jobs = run_jobs(spts)
        rows.append(dict(
            path=f"query_plane_q{m}", queries=m, records=n,
            wall_s=round(dt_reg, 3),
            record_x_queries_per_sec=round(n * m / dt_reg),
            speedup_vs_sequential_jobs=round(dt_jobs / dt_reg, 2)))
    return rows


def bench_tenant_plane(path: str, n: int, q: int = 8) -> list:
    """Tenant accounting plane rows (ISSUE 20): the same Q-query dynamic
    registry fleet (two tenants, Q/2 queries each) over the same replay
    with the ledger OFF (no telemetry session — the gated hot path) vs
    ON (a telemetry session: per-dispatch ``note_dispatch`` + the
    proportional ``resolve`` split). Window-table identity is asserted
    in the same run — attribution is bookkeeping, never a semantics
    change — and the on-row carries the ledger's own conservation stats
    (resolved == dispatched, max residual from the exact-split fold)."""
    from spatialflink_tpu import driver
    from spatialflink_tpu.config import StreamConfig
    from spatialflink_tpu.operators import (PointPointRangeQuery,
                                            QueryConfiguration, QueryType)
    from spatialflink_tpu.runtime.queryplane import QueryRegistry
    from spatialflink_tpu.utils import telemetry as _telemetry
    from spatialflink_tpu.utils.telemetry import telemetry_session

    import numpy as np

    with open(path) as f:
        lines = f.read().splitlines()
    cfg = StreamConfig(format="CSV", date_format=None,
                       csv_tsv_schema=[0, 1, 2, 3])
    grid = _params(1).grids()[0]
    conf = QueryConfiguration(QueryType.WindowBased,
                              int(WINDOW_S * 1000), int(SLIDE_S * 1000))
    rng = np.random.default_rng(9)
    pts = [(float(grid.min_x + rng.random() * (grid.max_x - grid.min_x)),
            float(grid.min_y + rng.random() * (grid.max_y - grid.min_y)))
           for _ in range(q)]

    def run():
        reg = QueryRegistry("range", radius=0.5)
        for i, (x, y) in enumerate(pts):
            reg.admit({"id": f"q{i}", "x": x, "y": y,
                       "tenant": "acme" if i % 2 == 0 else "free"})
        reg.apply()
        op = PointPointRangeQuery(conf, grid)
        stream = driver.decode_stream(iter(lines), cfg, grid)
        t0 = time.perf_counter()
        table = [(w.window_start, tuple(len(r) for r in w.records))
                 for w in op.run_dynamic(stream, reg, 0.5)]
        return table, time.perf_counter() - t0

    run()  # warm the Q-bucket's jit shapes both configurations share
    assert _telemetry.active() is None
    table_off, dt_off = run()
    with telemetry_session() as tel:
        table_on, dt_on = run()
        ledger = tel.tenants.to_dict()
    assert table_on == table_off, (
        "tenant ledger changed the window table — attribution must be "
        "bookkeeping, not semantics")
    assert ledger["resolved"] > 0 and ledger["pending"] == 0
    assert ledger["max_residual_ms"] < 1e-6, ledger["max_residual_ms"]
    base = dict(records=n, queries=q, windows=len(table_off),
                identical=True)
    return [
        dict(base, path="tenant_plane_off", wall_s=round(dt_off, 3),
             records_per_sec=round(n / dt_off)),
        dict(base, path="tenant_plane_on", wall_s=round(dt_on, 3),
             records_per_sec=round(n / dt_on),
             overhead_vs_off=round(dt_on / dt_off - 1.0, 4),
             tenants=sorted(ledger["tenants"]),
             dispatches_resolved=ledger["resolved"],
             max_residual_ms=ledger["max_residual_ms"],
             fairness=ledger["fairness"]),
    ]


def bench_fleet(n: int) -> list:
    """Supervised multi-worker fleet rows (``--fleet``): wall clock and
    records/s for N=1/2/4 worker fleets over the 95%-hot clustered
    GeoJSON stream, plus the plain single-process run of the same replay
    as the overhead reference (``fleet_solo``). Merged-digest identity is
    asserted across every N — the exactly-once global merge — and each
    fleet row carries the supervisor's restart and post-warmup-recompile
    ledger fields. On a one-host CPU box these rows are honest about the
    supervision price: spawn + per-line routing dominate, so N>1 buys
    fault isolation, not throughput (BASELINE.md). A final
    ``fleet_plane_overhead`` row prices the observability plane at N=2
    (plane on vs ``--fleet-plane off``) with the merged digest asserted
    identical either way, and a ``fleet_rescale`` row prices a live
    mid-run scale-out (N=2 -> 4 via ``--fleet-rescale``) with the merged
    digest asserted identical to the fixed-N runs — the fenced
    exactly-once rescale contract, end-to-end."""
    import contextlib
    import io

    from benchmarks._common import fleet_refusal
    from spatialflink_tpu.driver import main as driver_main
    from spatialflink_tpu.runtime import fleet as fleet_mod
    from spatialflink_tpu.streams.synthetic import clustered_lines

    refused = fleet_refusal("fleet")
    if refused:
        return [refused]
    conf = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "conf", "spatialflink-conf.yml")
    grid = _params(1).grids()[0]
    lines = clustered_lines(grid, n, 0.95, seed=7, fmt="geojson", dt_ms=1)
    rows = []
    # workers are fresh processes: the driver's checkout compile cache lets
    # the per-N warm run actually warm the measured one
    with tempfile.TemporaryDirectory(prefix="bench-fleet-") as td:
        path1 = os.path.join(td, "in.geojson")
        with open(path1, "w") as f:
            f.write("\n".join(lines) + "\n")

        def solo():
            t0 = time.perf_counter()
            with contextlib.redirect_stdout(io.StringIO()):
                rc = driver_main(["--config", conf, "--option", "1",
                                  "--input1", path1])
            dt = time.perf_counter() - t0
            assert rc == 0
            return dt

        def fleet(workers, tag, *extra):
            fdir = os.path.join(td, f"fleet-{tag}")
            t0 = time.perf_counter()
            with contextlib.redirect_stdout(sys.stderr):
                rc = driver_main([
                    "--config", conf, "--option", "1", "--input1", path1,
                    "--fleet", str(workers), "--fleet-dir", fdir,
                    # no mid-run rebalance inside a timed row
                    "--fleet-epoch-records", str(10**9)] + list(extra))
            dt = time.perf_counter() - t0
            assert rc == 0
            res = fleet_mod.read_json(os.path.join(fdir,
                                                   fleet_mod.RESULT_FILE))
            return res, dt

        solo()  # warm the in-process jit shapes
        dt_solo = solo()
        rows.append(dict(path="fleet_solo", workers=0, records=n,
                         wall_s=round(dt_solo, 3),
                         records_per_sec=round(n / dt_solo)))
        digest = None
        dt_f1 = None
        for workers in (1, 2, 4):
            fleet(workers, f"warm{workers}")  # per-N padding buckets
            res, dt = fleet(workers, f"n{workers}")
            if digest is None:
                digest = res["digest"]
                dt_f1 = dt
            else:
                assert res["digest"] == digest, (
                    f"fleet N={workers} merged digest diverged — the "
                    "exactly-once global merge is partition-dependent")
            row = dict(path=f"fleet_n{workers}", workers=workers,
                       records=n, wall_s=round(dt, 3),
                       records_per_sec=round(n / dt),
                       merged_windows=res["merged_windows"],
                       merged_digest=res["digest"],
                       restarts=sum(int(v)
                                    for v in res["restarts"].values()),
                       post_warmup_compiles=res["post_warmup_compiles"],
                       overhead_vs_solo=round(dt / dt_solo, 2))
            if workers > 1:
                row["speedup_vs_fleet1"] = round(dt_f1 / dt, 2)
            rows.append(row)
        # fleet observability plane overhead at N=2: sidecar + monitor +
        # timeline harvesting + lineage vs --fleet-plane off. The merged
        # digest is asserted identical — the plane must be invisible to
        # exactly-once identity, so this row prices it and nothing else
        res_on, dt_on = fleet(2, "plane-on")
        res_off, dt_off = fleet(2, "plane-off", "--fleet-plane", "off")
        assert res_on["digest"] == res_off["digest"] == digest, (
            "fleet observability plane changed the merged digest — the "
            "lineage sidecar leaked into exactly-once identity")
        rows.append(dict(
            path="fleet_plane_overhead", workers=2, records=n,
            wall_s=round(dt_on, 3), wall_s_plane_off=round(dt_off, 3),
            records_per_sec=round(n / dt_on),
            overhead_vs_plane_off=round(dt_on / dt_off, 2),
            merged_p99_ms=((res_on.get("latency") or {})
                           .get("record_emit") or {}).get("p99"),
            sum_check_windows=((res_on.get("latency") or {})
                               .get("sum_check") or {}).get("windows"),
            digest_identical=True))
        # live rescale: start at N=2, scale out to N=4 mid-run at an
        # epoch boundary. The merged digest is asserted identical to the
        # fixed-N runs above — a fenced rescale must be invisible to
        # exactly-once identity — and the supervisor's rescale ledger
        # rides along. Epoch cadence is re-enabled here (the sibling rows
        # pin it huge) so the threshold can actually be consumed.
        res_rs, dt_rs = fleet(
            2, "rescale",
            "--fleet-rescale", f"{max(1, n // 3)}:4",
            "--fleet-epoch-records", str(max(1, n // 8)))
        assert res_rs["digest"] == digest, (
            "fleet_rescale merged digest diverged from the fixed-N runs "
            "— the fenced rescale leaked into exactly-once identity")
        rows.append(dict(
            path="fleet_rescale", workers=2,
            workers_final=res_rs.get("workers_final"),
            records=n, wall_s=round(dt_rs, 3),
            records_per_sec=round(n / dt_rs),
            rescales=[[r["n_from"], r["n_to"]]
                      for r in res_rs.get("rescales", [])],
            merged_windows=res_rs["merged_windows"],
            restarts=sum(int(v) for v in res_rs["restarts"].values()),
            post_warmup_compiles=res_rs["post_warmup_compiles"],
            overhead_vs_fleet1=round(dt_rs / dt_f1, 2),
            digest_identical=True))
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=None,
                    help="records per stream (default 1M, 100k on CPU)")
    ap.add_argument("--options", default="1,51,101",
                    help="comma-separated driver queryOptions")
    ap.add_argument("--multi", type=int, default=8,
                    help="query count for the multi-query-vs-sequential-"
                         "jobs rows (values < 2 disable them — a 1-query "
                         "'batch' measures nothing the single rows don't)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="coordinated-checkpoint overhead rows (record "
                         "path, checkpointing off vs every N windows) over "
                         "the range option. 0 (default) disables them")
    ap.add_argument("--live-plane", action="store_true",
                    help="live-operations-plane overhead rows (record "
                         "path: plane off vs an idle --status-port server "
                         "vs the full server+session+--live-stats plane) "
                         "over the range option")
    ap.add_argument("--pane-overlap", type=int, default=0,
                    help="sliding overlap (window = overlap * slide) for "
                         "the pane-incremental vs full-recompute rows over "
                         "the range/kNN options; window-table identity is "
                         "asserted in the same run. 0 (default) disables "
                         "the pane rows")
    ap.add_argument("--pane-state-overlap", type=int, default=0,
                    help="sliding overlap for the device-resident vs "
                         "host-merged pane-state rows (--pane-merge A/B "
                         "over the kNN option, identity asserted in-run, "
                         "per-slide readback bytes attached). 0 (default) "
                         "disables them")
    ap.add_argument("--query-plane", type=int, default=0, metavar="Q",
                    help="standing-query control plane rows: a Q-query "
                         "dynamic registry fleet static vs under "
                         "1-admit+1-retire-per-window churn (rec/s, fleet "
                         "repads, in-bucket XLA recompiles — must be 0), "
                         "plus a Q-sweep amortization row through the "
                         "registry path vs dedicated per-query pipelines. "
                         "0 (default) disables them")
    ap.add_argument("--tenant-plane", action="store_true",
                    help="tenant accounting plane overhead rows: the same "
                         "two-tenant dynamic registry fleet with the "
                         "per-dispatch cost ledger off (no telemetry "
                         "session) vs on, window-table identity asserted "
                         "in-run; the on-row carries the ledger's "
                         "conservation stats")
    ap.add_argument("--fleet", action="store_true",
                    help="supervised multi-worker fleet rows: a single-"
                         "process reference run vs --fleet N=1/2/4 worker "
                         "fleets over a 95%%-hot clustered stream "
                         "(merged-digest identity asserted across every "
                         "N; rows carry restart + post-warmup-recompile "
                         "ledger fields), plus a live mid-run N=2->4 "
                         "rescale row with the digest asserted identical "
                         "to the fixed-N runs")
    ap.add_argument("--require-backend", choices=("cpu", "tpu", "gpu"),
                    default=None,
                    help="fail fast (exit 2) when the process would run on "
                         "any other backend — the BENCH r05 silent-CPU-"
                         "fallback condition becomes a refusal instead of "
                         "an invalid ledger row")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax

    from spatialflink_tpu.utils import deviceplane

    backend = jax.default_backend()
    if args.require_backend and backend != args.require_backend:
        print(f"bench_e2e: --require-backend {args.require_backend} but "
              f"the process landed on '{backend}' "
              f"({deviceplane.backend_provenance()['device_kind']}); "
              "refusing to measure — run python -m spatialflink_tpu.doctor "
              "--preflight for the readiness breakdown", file=sys.stderr)
        return 2
    n = args.n or (1_000_000 if backend == "tpu" else 100_000)

    from benchmarks._common import bench_telemetry

    # backend provenance on EVERY row (not just the file header): a ledger
    # row must carry its own device truth so bench_diff can refuse
    # cross-backend pairings and a CPU fallback is visible per row
    prov = deviceplane.backend_provenance()

    def _stamp(row: dict) -> dict:
        row["backend"] = backend
        row["device_kind"] = prov["device_kind"]
        row["valid_for_target"] = prov["valid_for_target"]
        return row

    rows = []
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "stream1.csv")
        path2 = os.path.join(td, "stream2.csv")
        _write_stream(path, n, seed=0)
        _write_stream(path2, max(n // 64, 1), seed=1)  # small query stream
        for opt in (int(x) for x in args.options.split(",")):
            # one telemetry session — and ONE snapshot — per option: the
            # snapshot is cumulative across the option's rows, so attaching
            # the same object (not one per row) keeps the output honest
            # about that and avoids N near-identical copies in the file
            with bench_telemetry() as tel:
                opt_rows = list(bench_option(opt, path, path2, n))
                snap = tel.snapshot()
            for row in opt_rows:
                row["telemetry"] = snap
                _stamp(row)
                print(json.dumps(row), flush=True)
                rows.append(row)
        if args.multi > 1:
            for opt in (1, 51):
                if opt not in [int(x) for x in args.options.split(",")]:
                    continue
                for row in bench_multi_vs_jobs(opt, path, n, args.multi):
                    _stamp(row)
                    print(json.dumps(row), flush=True)
                    rows.append(row)
        if args.checkpoint_every > 0:
            for opt in (1,):
                if opt not in [int(x) for x in args.options.split(",")]:
                    continue
                for row in bench_checkpoint(opt, path, n,
                                            args.checkpoint_every):
                    _stamp(row)
                    print(json.dumps(row), flush=True)
                    rows.append(row)
        if args.live_plane:
            for opt in (1,):
                if opt not in [int(x) for x in args.options.split(",")]:
                    continue
                for row in bench_live_plane(opt, path, n):
                    _stamp(row)
                    print(json.dumps(row), flush=True)
                    rows.append(row)
        if args.pane_state_overlap > 1:
            for opt in (51,):
                if opt not in [int(x) for x in args.options.split(",")]:
                    continue
                for row in bench_pane_state(opt, path, n,
                                            args.pane_state_overlap):
                    _stamp(row)
                    print(json.dumps(row), flush=True)
                    rows.append(row)
        if args.query_plane > 1:
            for row in bench_query_plane(path, n, args.query_plane):
                _stamp(row)
                print(json.dumps(row), flush=True)
                rows.append(row)
        if args.tenant_plane:
            for row in bench_tenant_plane(path, n):
                _stamp(row)
                print(json.dumps(row), flush=True)
                rows.append(row)
        if args.fleet:
            for row in bench_fleet(n):
                _stamp(row)
                print(json.dumps(row), flush=True)
                rows.append(row)
        if args.pane_overlap > 1:
            for opt in (1, 51):
                if opt not in [int(x) for x in args.options.split(",")]:
                    continue
                for row in bench_panes(opt, path, n, args.pane_overlap):
                    _stamp(row)
                    print(json.dumps(row), flush=True)
                    rows.append(row)

    out = args.out or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"RESULTS_e2e_{backend}.json")
    with open(out, "w") as f:
        json.dump({"backend": backend, "n": n, "rows": rows}, f, indent=1)
    print(f"# wrote {out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
