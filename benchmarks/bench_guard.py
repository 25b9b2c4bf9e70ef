"""Micro-bench regression GATE for the batched hot path.

The batched-everywhere rebuild (chunk-vectorized decode + ``assign_bulk``
window assignment + columnar window payloads) is a performance claim with
no flag guarding it — a regression back to per-record cost would be
silent. This harness measures the hot-path speedup RATIOS (batched vs the
seed scalar loop, same machine, same run — ratios are robust to machine
speed in a way absolute rec/s is not) and ``--check`` diffs them against
the checked-in conservative floors in ``GUARD_baseline.json`` via
``bench_diff`` (metric ``speedup``, >25% below a floor fails). Wired into
tier-1 by ``tests/test_bench_guard.py``, so this PR's wins can't rot
unnoticed.

Rows (identity field ``path``):

- ``window_assign``     chunked ``WindowAssembler.assemble`` vs per-record
                        ``add`` (assignment + seal sweep only)
- ``decode_columnar``   ``driver.decode_chunks`` native columnar CSV parse
                        vs the seed per-record ``parse_spatial`` loop
- ``windowed_pipeline`` windowed range end-to-end (decode -> windows ->
                        kernel -> selection) on the batched path vs the
                        same operator fed the scalar-decoded record stream
- ``skew_adaptive``     multi-query windowed range over a high-skew
                        clustered stream: skew-adaptive grid (hot-cell
                        split prefilter, repartition controller live) vs
                        the uniform grid — the ISSUE 9 win, gated so
                        skew-adaptivity regressions fail tier-1 like the
                        batched-path ratios (window-table identity
                        asserted in-run)
- ``query_plane``       a Q=8 standing-query fleet served through the
                        DYNAMIC registry path (one padded Q-axis dispatch
                        per window) vs Q dedicated single-query pipelines
                        re-reading the stream — the ISSUE 10 contract:
                        the control plane must preserve run_multi's
                        amortization (per-query identity asserted)
- ``controller_pareto`` the chunk governor's closed loop vs a fixed-chunk
                        sweep: the gated ratio is the Pareto composite
                        (min over fixed chunks of the better of the
                        throughput ratio and the p99 ratio) — >= 1 means
                        no fixed chunk dominates the governed run on both
                        axes (window-table identity asserted; the full
                        per-class frontier lives in bench_control.py)
- ``realtime_vectorized``  the rebuilt realtime mode (columnar
                        MicroBatcher through the batched drive loop) vs
                        the pre-rebuild scalar ``_micro_batches`` branch,
                        fire-table identity asserted

plus one LOWER-IS-BETTER row gated by a second ``bench_diff`` pass
(``--metric p99_ms --lower-is-better`` against the ``latency_rows``
ceilings in the same baseline file):

and one fleet overhead row gated by a third lower-is-better pass
(``--metric overhead_x`` against the ``fleet_rows`` ceiling):

- ``fleet_scaling``     absolute wall clock of a single-worker supervised
                        fleet (supervisor routing -> worker subprocess ->
                        exactly-once merge) at a PINNED record count —
                        the supervision machinery's cost ceiling (metric
                        ``wall_fleet1_s``); the overhead-vs-single-process
                        ratio and the N=2 scaling ratio ride along
- ``fleet_rescale``     absolute wall clock of an N=2 fleet that scales
                        out to N=4 mid-run at an epoch boundary
                        (``--fleet-rescale``), pinned record count,
                        merged digest asserted identical to a fixed-N=2
                        oracle in the same run — the fenced exactly-once
                        rescale's cost ceiling (carried under the shared
                        fleet metric key ``wall_fleet1_s``)
                        ungated (a one-host CPU box is spawn/routing-
                        dominated — BASELINE.md carries the honest
                        numbers) and merged-digest identity across
                        N=1/N=2 is asserted in-run

- ``latency_record_emit``  record→emit p99 (the latency plane's budget
                        chain) of a windowed range run at the DEFAULT
                        decode chunk, at a PINNED record count so the
                        workload is fixed; window-table identity vs the
                        uninstrumented run is asserted, and the ceiling
                        carries a 3x margin (absolute ms is machine-
                        sensitive in a way the speedup ratios are not)

and one tenant-ledger overhead row gated by a fourth lower-is-better
pass (``--metric overhead_vs_off_x`` against the ``tenant_rows``
ceiling):

- ``tenant_plane``      the per-dispatch tenant cost ledger on vs off
                        over the same two-tenant dynamic fleet replay
                        (pinned record count): the gated ratio is the
                        on/off wall, window-table identity AND the
                        ledger's conservation invariants asserted in-run

Usage:
    python benchmarks/bench_guard.py [--n N] [--out PATH]
    python benchmarks/bench_guard.py --check          # exit 1 on regression
    python benchmarks/bench_guard.py --write-baseline # refresh the floors
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

BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "GUARD_baseline.json")
#: floors are written at measured/MARGIN so box-to-box variance does not
#: flap the gate; the 25% diff threshold sits on top
MARGIN = 2.0
#: per-row margin overrides: the skew-adaptive ratio sits closer to 1 than
#: the batched-vs-scalar ratios, so the default /2 floor would degenerate
#: to 1.0 and the gate could never catch a silently-broken prefilter
#: (ratio ~1.0); a tighter margin keeps the floor meaningfully above it
MARGIN_BY_PATH = {"skew_adaptive": 1.3}
#: the latency row's CEILING margin (lower-is-better: ceiling = measured x
#: margin) — generous because absolute milliseconds vary box to box where
#: the speedup ratios cancel machine speed out
LATENCY_MARGIN = 3.0
#: the fleet row's CEILING margin on absolute single-worker-fleet wall
#: seconds (lower-is-better, like the latency row: ceiling = measured x
#: margin) — worker process spawn and the supervisor's per-line routing
#: are machine-sensitive absolute costs, so the margin is generous
FLEET_MARGIN = 3.0
#: the tenant row's CEILING margin on the ledger-on/ledger-off wall
#: ratio (lower-is-better): the measured overhead sits near 1.0, so the
#: ceiling multiplies a ratio, not an absolute, and stays tight enough
#: that a ledger regressed to per-record cost fails the gate
TENANT_MARGIN = 1.5


def _lines(n: int):
    rng = np.random.default_rng(0)
    t0 = 1_700_000_000_000
    ts = t0 + (np.arange(n) * 100_000 // max(n, 1))  # 100 s span
    return [f"v{int(i) % 97},{int(t)},"
            f"{115.5 + rng.random() * 2:.6f},{39.6 + rng.random() * 1.5:.6f}"
            for i, t in enumerate(ts)]


def _cfg():
    from spatialflink_tpu.config import StreamConfig

    return StreamConfig(format="CSV", date_format=None,
                        csv_tsv_schema=[0, 1, 2, 3])


def _grid():
    from spatialflink_tpu.index import UniformGrid

    return UniformGrid(115.5, 117.6, 39.6, 41.1, num_grid_partitions=100)


def _scalar_decode(lines, cfg, grid):
    """The SEED per-record decoder (parse_spatial per record) — kept here
    and in tests/oracles.py as the reference the ratios divide by."""
    from spatialflink_tpu.streams.formats import parse_spatial

    return [parse_spatial(ln, cfg.format, grid, delimiter=cfg.delimiter,
                          schema=cfg.csv_tsv_schema, geometry="Point")
            for ln in lines]


def bench_window_assign(n: int) -> dict:
    import types

    from spatialflink_tpu.runtime.windows import WindowAssembler, WindowSpec

    rng = np.random.default_rng(0)
    ts = (1_700_000_000_000 + np.sort(rng.integers(0, 100_000, n))).tolist()
    recs = [types.SimpleNamespace(timestamp=t) for t in ts]
    spec = WindowSpec.sliding(40_000, 5_000)  # overlap 8

    def per_record():
        wa = WindowAssembler(spec)
        out = []
        for r in recs:
            out += [(s, e, len(rr)) for s, e, rr in wa.add(r.timestamp, r)]
        out += [(s, e, len(rr)) for s, e, rr in wa.flush()]
        return out

    def chunked():
        wa = WindowAssembler(spec)
        return [(s, e, len(rr)) for s, e, rr in wa.assemble(iter(recs))]

    per_record(), chunked()  # warm
    t0 = time.perf_counter()
    ref = per_record()
    dt_rec = time.perf_counter() - t0
    t0 = time.perf_counter()
    fast = chunked()
    dt_chunk = time.perf_counter() - t0
    assert fast == ref, "chunked assignment diverged from per-record add"
    return dict(path="window_assign", records=n,
                speedup=round(dt_rec / dt_chunk, 2))


def bench_decode_columnar(n: int) -> dict:
    from spatialflink_tpu import driver

    lines = _lines(n)
    cfg, grid = _cfg(), _grid()

    def batched():
        return sum(len(c) for c in driver.decode_chunks(iter(lines), cfg,
                                                        grid))

    batched()
    _scalar_decode(lines[:2048], cfg, grid)  # warm both import paths
    t0 = time.perf_counter()
    total = batched()
    dt_b = time.perf_counter() - t0
    assert total == n
    t0 = time.perf_counter()
    objs = _scalar_decode(lines, cfg, grid)
    dt_s = time.perf_counter() - t0
    assert len(objs) == n
    return dict(path="decode_columnar", records=n,
                speedup=round(dt_s / dt_b, 2))


def bench_windowed_pipeline(n: int) -> dict:
    from spatialflink_tpu import driver
    from spatialflink_tpu.models import Point
    from spatialflink_tpu.operators import (PointPointRangeQuery,
                                            QueryConfiguration, QueryType)

    lines = _lines(n)
    cfg, grid = _cfg(), _grid()
    conf = QueryConfiguration(QueryType.WindowBased, 10_000, 5_000)
    qp = Point.create(116.5, 40.3, grid, obj_id="q")
    scalar_objs = _scalar_decode(lines, cfg, grid)

    def run_batched():
        op = PointPointRangeQuery(conf, grid)
        stream = driver.decode_stream(iter(lines), cfg, grid)
        return [(r.window_start, len(r.records))
                for r in op.run(stream, qp, 0.5)]

    def run_scalar():
        op = PointPointRangeQuery(conf, grid)
        return [(r.window_start, len(r.records))
                for r in op.run(iter(scalar_objs), qp, 0.5)]

    run_batched(), run_scalar()  # warm jit shapes both paths share
    t0 = time.perf_counter()
    tb = run_batched()
    dt_b = time.perf_counter() - t0
    t0 = time.perf_counter()
    ts_ = run_scalar()
    dt_s = time.perf_counter() - t0
    assert tb == ts_, "batched pipeline window table diverged"
    # the scalar side here ALREADY skips the per-record parse (pre-decoded
    # objects), so the ratio under-counts the full win — a conservative
    # guard by construction
    dt_s += 0.0
    return dict(path="windowed_pipeline", records=n,
                speedup=round((dt_s) / dt_b, 2))


def bench_skew_adaptive(n: int) -> dict:
    """Adaptive-vs-uniform grid ratio on the skewed clustered stream — the
    compact tier-1 form of ``bench_skew.py``'s high-skew row: a
    standing-query fleet (Q=128, eight hotspot monitors) over a 95%-hot
    clustered stream, repartition controller live, identity asserted."""
    import dataclasses

    import numpy as np

    from spatialflink_tpu import driver
    from spatialflink_tpu.index import AdaptiveGrid
    from spatialflink_tpu.models import Point
    from spatialflink_tpu.operators import (PointPointRangeQuery,
                                            QueryConfiguration, QueryType)
    from spatialflink_tpu.runtime.repartition import RepartitionController
    from spatialflink_tpu.streams.synthetic import clustered_lines

    # the ratio needs enough windows for the kernel share to dominate the
    # jit-warm/decode fraction: pin the row's own record count so a small
    # --n (the tier-1 run) cannot wash the gate out
    n = max(n, 120_000)
    cfg, grid = _cfg(), _grid()
    lines = clustered_lines(grid, n, 0.95, seed=7, fmt="csv", dt_ms=1)
    rng = np.random.default_rng(101)
    q = 128  # the Q-axis serving fleet bench_skew.py sweeps; at small Q the
    # kernel no longer dominates and the ratio loses its gating power
    xs = rng.uniform(grid.min_x, grid.max_x, q)
    ys = rng.uniform(grid.min_y, grid.max_y, q)
    hx = (grid.min_x + grid.max_x) / 2 + grid.cell_length / 3
    hy = (grid.min_y + grid.max_y) / 2 + grid.cell_length / 3
    span = 2.0 * grid.cell_length
    xs[:8] = hx + rng.uniform(-span / 2, span / 2, 8)
    ys[:8] = hy + rng.uniform(-span / 2, span / 2, 8)
    qpts = [Point.create(float(x), float(y), grid)
            for x, y in zip(xs, ys)]
    conf = QueryConfiguration(QueryType.WindowBased, 40_000, 5_000)

    def run(adaptive: bool):
        c, ctl = conf, None
        if adaptive:
            ag = AdaptiveGrid(grid, refine=8)
            c = dataclasses.replace(conf, adaptive_grid=ag)
            ctl = RepartitionController(ag,
                                        interval_records=max(1000, n // 8))
        op = PointPointRangeQuery(c, grid)
        stream = driver.decode_stream(iter(lines), cfg, grid)
        if ctl is not None:
            ctl.install()
        try:
            t0 = time.perf_counter()
            out = [(w.window_start, tuple(len(r) for r in w.records))
                   for w in op.run_multi(stream, qpts, 0.002)]
            dt = time.perf_counter() - t0
        finally:
            if ctl is not None:
                ctl.uninstall()
        return out, dt

    run(False), run(True)  # warm jit shapes + the adapted layouts
    ref, dt_u = run(False)
    got, dt_a = run(True)
    assert got == ref, "adaptive window table diverged from uniform"
    return dict(path="skew_adaptive", records=n,
                speedup=round(dt_u / dt_a, 2))


def bench_query_plane(n: int) -> dict:
    """Standing-query control plane ratio: a Q=8 DYNAMIC fleet served
    through the registry path (one padded Q-axis dispatch per window,
    admissions applied at window boundaries) vs Q dedicated single-query
    pipelines re-reading the stream — the reference's one-Flink-job-per-
    query shape. The registry path must preserve run_multi's amortization
    ON TOP of its lifecycle machinery; per-query window-table identity is
    asserted so a silently-wrong demux can never pass the gate."""
    from spatialflink_tpu import driver
    from spatialflink_tpu.models import Point
    from spatialflink_tpu.operators import (PointPointRangeQuery,
                                            QueryConfiguration, QueryType)
    from spatialflink_tpu.runtime.queryplane import QueryRegistry

    lines = _lines(n)
    cfg, grid = _cfg(), _grid()
    conf = QueryConfiguration(QueryType.WindowBased, 10_000, 5_000)
    rng = np.random.default_rng(3)
    q = 8
    pts = [(115.5 + rng.random() * 2, 39.6 + rng.random() * 1.5)
           for _ in range(q)]

    def registry():
        reg = QueryRegistry("range", radius=0.5)
        for i, (x, y) in enumerate(pts):
            reg.admit({"id": f"q{i}", "x": x, "y": y})
        reg.apply()
        return reg

    def run_dynamic():
        op = PointPointRangeQuery(conf, grid)
        stream = driver.decode_stream(iter(lines), cfg, grid)
        return [(w.window_start, tuple(len(r) for r in w.records))
                for w in op.run_dynamic(stream, registry(), 0.5)]

    def run_dedicated():
        out = []
        for x, y in pts:
            op = PointPointRangeQuery(conf, grid)
            stream = driver.decode_stream(iter(lines), cfg, grid)
            out.append([(w.window_start, len(w.records))
                        for w in op.run(stream,
                                        Point.create(x, y, grid), 0.5)])
        return out

    run_dynamic(), run_dedicated()  # warm jit shapes on both sides
    t0 = time.perf_counter()
    dyn = run_dynamic()
    dt_d = time.perf_counter() - t0
    t0 = time.perf_counter()
    ded = run_dedicated()
    dt_s = time.perf_counter() - t0
    for i in range(q):
        assert [(ws, c[i]) for ws, c in dyn] == ded[i], \
            f"dynamic fleet query {i} diverged from its dedicated run"

    # recompile-sentinel gate over the PR 9 churn acceptance shape (ISSUE
    # 12): a Q=32 fleet with ONE admit + ONE retire per emitted window —
    # every change repads within the same power-of-two bucket, so after
    # the warmup pass the sentinel must record 0 post-warmup XLA compiles
    from spatialflink_tpu.utils import deviceplane

    q32 = 32
    pts32 = [(115.5 + rng.random() * 2, 39.6 + rng.random() * 1.5)
             for _ in range(q32)]

    def run_churn():
        reg = QueryRegistry("range", radius=0.5)
        for i, (x, y) in enumerate(pts32):
            reg.admit({"id": f"q{i}", "x": x, "y": y})
        reg.apply()
        op = PointPointRangeQuery(conf, grid)
        stream = driver.decode_stream(iter(lines), cfg, grid)
        i = 0
        for _w in op.run_dynamic(stream, reg, 0.5):
            reg.admit({"id": f"churn{i}", "x": 115.5 + (i % 10) * 0.1,
                       "y": 39.6 + (i % 10) * 0.1})
            reg.retire([e.id for e in reg.active_entries()][0])
            i += 1

    run_churn()  # warm the Q=32 bucket's shapes
    dp = deviceplane.registry()
    dp.begin_run()
    dp.mark_warm("bench_guard query-plane churn (shapes pre-warmed)")
    try:
        run_churn()
        post_warm = dp.run_recompiles
    finally:
        dp.end_run()
    assert post_warm == 0, (
        f"recompile sentinel fired {post_warm}x across the Q={q32} "
        "admit/retire-per-window churn run — in-bucket repadding must "
        "never recompile (the PR 9 contract, now device-truth-asserted)")
    return dict(path="query_plane", records=n, queries=q,
                speedup=round(dt_s / dt_d, 2),
                churn_post_warmup_compiles=post_warm)


def bench_controller_pareto(n: int) -> dict:
    """Closed-loop governor Pareto gate (ISSUE 18): the GOVERNED windowed
    range run (decode chunk driven live by the ChunkGovernor off the
    latency plane's buckets) against a FIXED-chunk sweep of the same
    pipeline. The gated ``speedup`` is the Pareto composite

        min over fixed chunks c of max(gov_rps / rps_c, p99_c / gov_p99)

    — >= 1 means no fixed chunk dominates the governor on BOTH axes
    (throughput and record→emit p99), the bench bar's "meet or beat every
    fixed size on the frontier" stated as one machine-robust ratio (each
    axis covers the other's noise; per-axis p99 over ~21 windows flaps).
    Window-table identity across every fixed chunk AND the governed run
    is asserted, so a governor that bought its numbers by changing
    results can never pass. ``benchmarks/bench_control.py`` carries the
    full per-latency-class frontier incl. --chaos; this row is its
    tier-1 sentinel."""
    from spatialflink_tpu import driver
    from spatialflink_tpu.models import Point
    from spatialflink_tpu.operators import (PointPointRangeQuery,
                                            QueryConfiguration, QueryType)
    from spatialflink_tpu.runtime.control import ChunkGovernor
    from spatialflink_tpu.utils.telemetry import telemetry_session

    lines = _lines(n)
    cfg, grid = _cfg(), _grid()
    conf = QueryConfiguration(QueryType.WindowBased, 10_000, 5_000)
    qp = Point.create(116.5, 40.3, grid, obj_id="q")

    def ticking(tel):
        # the reporter thread normally closes buckets; a replay bench
        # drives the same maybe_tick from the feed (time-gated, so the
        # cadence is the plane's tick_interval_s, not the loop count)
        for i in range(0, len(lines), 256):
            yield from lines[i:i + 256]
            tel.latency.maybe_tick(tel)

    def run(chunk, gov=None):
        with telemetry_session() as tel:
            tel.latency.tick_interval_s = 0.05
            if gov is not None:
                gov.install()
            try:
                op = PointPointRangeQuery(conf, grid)
                s = driver.decode_stream(ticking(tel), cfg, grid,
                                         chunk=chunk)
                t0 = time.perf_counter()
                table = [(r.window_start, len(r.records))
                         for r in op.run(s, qp, 0.5)]
                wall = time.perf_counter() - t0
                p99 = tel.latency.record_emit.percentile(99)
            finally:
                if gov is not None:
                    gov.uninstall()
        return table, n / wall, p99

    run(4096)  # warm
    ref = None
    fixed = {}
    for c in (512, 2048, 8192):
        table, rps, p99 = run(c)
        if ref is None:
            ref = table
        assert table == ref, f"fixed chunk {c} changed the window table"
        fixed[c] = (rps, p99)
    gov = ChunkGovernor()
    table, gov_rps, gov_p99 = run(gov.chunk_callback(), gov)
    assert table == ref, "governed run changed the window table"
    st = gov.status()
    score = min(max(gov_rps / rps, p99 / gov_p99)
                for rps, p99 in fixed.values())
    return dict(path="controller_pareto", records=n,
                speedup=round(score, 2),
                gov_rps=int(gov_rps), gov_p99_ms=round(gov_p99, 3),
                gov_final_chunk=st["chunk"], gov_ticks=st["ticks"],
                gov_steps=st["grows"] + st["shrinks"],
                fixed={str(c): dict(rps=int(r), p99_ms=round(p, 3))
                       for c, (r, p) in fixed.items()})


def bench_realtime_vectorized(n: int) -> dict:
    """Realtime-on-the-vectorized-path gate (ISSUE 18): throughput of the
    rebuilt realtime mode (tumbling count micro-windows cut by the
    columnar MicroBatcher, driven through the batched pipeline) vs the
    pre-rebuild scalar branch — per-record flatten into ``_micro_batches``
    feeding the same drive loop (kept in-tree as the trajectory-family
    helper, so the oracle is the actual old code, not a reconstruction).
    Fire-table identity is asserted: same bounds, same selections."""
    from spatialflink_tpu import driver
    from spatialflink_tpu.models import Point
    from spatialflink_tpu.operators import (PointPointRangeQuery,
                                            QueryConfiguration, QueryType)

    lines = _lines(n)
    cfg, grid = _cfg(), _grid()
    conf = QueryConfiguration(QueryType.RealTime, realtime_batch_size=512)
    qp = Point.create(116.5, 40.3, grid, obj_id="q")

    def run_new():
        op = PointPointRangeQuery(conf, grid)
        s = driver.decode_stream(iter(lines), cfg, grid)
        return [(r.window_start, r.window_end, len(r.records))
                for r in op.run(s, qp, 0.5)]

    def run_scalar():
        op = PointPointRangeQuery(conf, grid)
        stream = iter(driver.decode_stream(iter(lines), cfg, grid))
        batched = ((r[0].timestamp, r[-1].timestamp, r)
                   for r in op._micro_batches(stream) if r)
        mask_cache = op._leaf_mask_cache(
            lambda: op.conf.adaptive_grid.neighboring_leaf_mask(
                0.5, qp.cell, point=(qp.x, qp.y)))
        return [(r.window_start, r.window_end, len(r.records))
                for r in op._drive_batched(
                    batched,
                    lambda recs, tsb: op._eval(recs, qp, 0.5, tsb,
                                               mask_cache),
                    realtime=True)]

    run_new(), run_scalar()  # warm both paths' jit shapes
    t0 = time.perf_counter()
    new = run_new()
    dt_new = time.perf_counter() - t0
    t0 = time.perf_counter()
    old = run_scalar()
    dt_old = time.perf_counter() - t0
    assert new == old, "vectorized realtime diverged from the scalar oracle"
    return dict(path="realtime_vectorized", records=n, fires=len(new),
                speedup=round(dt_old / dt_new, 2))


def bench_latency_record_emit(n: int) -> dict:
    """Record→emit p99 (ms) through the latency-decomposition plane on a
    windowed range replay at the DEFAULT decode chunk — the tier-1 gate on
    the record-to-emission hot path (a regression here is a latency-tier
    regression even when throughput holds). The record count is PINNED so
    the absolute-ms ceiling compares a fixed workload; the sum invariant
    and window-table identity vs the uninstrumented run are asserted so a
    silently-miswired budget chain can never pass."""
    from spatialflink_tpu import driver
    from spatialflink_tpu.models import Point
    from spatialflink_tpu.operators import (PointPointRangeQuery,
                                            QueryConfiguration, QueryType)
    from spatialflink_tpu.utils.telemetry import telemetry_session

    n = 60_000  # pinned: an absolute-ms ceiling needs a fixed workload
    lines = _lines(n)
    cfg, grid = _cfg(), _grid()
    conf = QueryConfiguration(QueryType.WindowBased, 10_000, 5_000)
    qp = Point.create(116.5, 40.3, grid, obj_id="q")

    def run():
        op = PointPointRangeQuery(conf, grid)
        stream = driver.decode_stream(iter(lines), cfg, grid)
        return [(r.window_start, len(r.records))
                for r in op.run(stream, qp, 0.5)]

    run()  # warm
    ref = run()  # uninstrumented reference (identity)
    with telemetry_session() as tel:
        got = run()
        plane = tel.latency
        p99 = plane.record_emit.percentile(99)
        assert plane.record_emit.count == len(got) > 0
        assert plane.max_residual_ms < 1.0, (
            "stage budget no longer sums to record→emit "
            f"(max residual {plane.max_residual_ms} ms)")
    assert got == ref, "instrumented run diverged from uninstrumented"
    return dict(path="latency_record_emit", records=n,
                p99_ms=round(p99, 3))


def bench_fleet_scaling(n: int) -> dict:
    """Supervised-fleet overhead gate (lower-is-better): wall clock of a
    single-worker fleet (supervisor routing -> worker subprocess ->
    exactly-once global merge) over the SAME replay run single-process —
    the price of the supervision machinery, which must stay bounded. The
    N=2 scaling ratio rides along informationally: spatial partitioning
    on a one-host CPU box is spawn/routing-dominated at this scale, so it
    is NOT gated (BASELINE.md carries the honest numbers). Merged-digest
    identity across N=1 and N=2 — the exactly-once contract — is asserted
    in the same run.

    The GATED metric is the absolute single-worker-fleet wall
    (``wall_fleet1_s``) at the pinned record count, against a generous
    x3 ceiling: the overhead-vs-single-process ratio divides by a
    sub-second batched run and would flap on denominator noise."""
    import contextlib
    import io
    import shutil

    from benchmarks._common import fleet_refusal
    from spatialflink_tpu.driver import main as driver_main
    from spatialflink_tpu.runtime import fleet as fleet_mod
    from spatialflink_tpu.streams.synthetic import clustered_lines

    refused = fleet_refusal("fleet_scaling")
    if refused:
        return refused
    n = 30_000  # pinned: the overhead ratio mixes fixed (spawn) and
    # per-record (routing) cost, so the ceiling needs a fixed workload
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    conf = os.path.join(root, "conf", "spatialflink-conf.yml")
    lines = clustered_lines(_grid(), n, 0.95, seed=7, fmt="geojson",
                            dt_ms=1)
    # workers are fresh processes: the warm runs below warm the measured
    # ones through the driver's checkout compile cache
    td = tempfile.mkdtemp(prefix="bench-fleet-")
    try:
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

        def fleet(workers, tag):
            fdir = os.path.join(td, f"fleet-{tag}")
            t0 = time.perf_counter()
            with contextlib.redirect_stdout(sys.stderr):
                rc = driver_main([
                    "--config", conf, "--option", "1", "--input1", path1,
                    "--fleet", str(workers), "--fleet-dir", fdir,
                    # no mid-run rebalance: a shape change would compile
                    # inside the timed region
                    "--fleet-epoch-records", str(10**9)])
            dt = time.perf_counter() - t0
            assert rc == 0
            res = fleet_mod.read_json(os.path.join(fdir,
                                                   fleet_mod.RESULT_FILE))
            return res, dt

        solo()          # warm the in-process jit shapes
        fleet(1, "w1")  # warm the workers' persistent cache: full-window
        fleet(2, "w2")  # and split-window padding buckets compile here
        dt_solo = solo()
        r1, dt_f1 = fleet(1, "n1")
        r2, dt_f2 = fleet(2, "n2")
        assert r1["digest"] == r2["digest"], \
            "fleet merged digest diverged between N=1 and N=2 workers"
        assert r1["merged_windows"] > 0
        return dict(path="fleet_scaling", records=n, workers=2,
                    merged_windows=r1["merged_windows"],
                    wall_solo_s=round(dt_solo, 3),
                    wall_fleet1_s=round(dt_f1, 3),
                    wall_fleet2_s=round(dt_f2, 3),
                    scaling_n2=round(dt_f1 / dt_f2, 2),
                    overhead_x=round(dt_f1 / dt_solo, 2))
    finally:
        shutil.rmtree(td, ignore_errors=True)


def bench_fleet_rescale(n: int) -> dict:
    """Live-rescale cost gate (lower-is-better): wall clock of an N=2
    fleet that scales OUT to N=4 mid-run at an epoch boundary
    (``--fleet-rescale``), at a pinned record count. Merged-digest
    identity against a fixed-N=2 oracle run of the same replay is
    asserted in the same run — the fenced exactly-once rescale contract:
    a live worker-set change must be invisible to the merged output.

    The GATED metric is the rescaling run's absolute wall, carried under
    ``wall_fleet1_s`` so the shared fleet diff pass (lower-is-better,
    ``--require-all``) pairs every fleet row on one metric key; the
    rescale-vs-fixed ratio rides along informationally."""
    import contextlib
    import shutil

    from benchmarks._common import fleet_refusal
    from spatialflink_tpu.driver import main as driver_main
    from spatialflink_tpu.runtime import fleet as fleet_mod
    from spatialflink_tpu.streams.synthetic import clustered_lines

    refused = fleet_refusal("fleet_rescale")
    if refused:
        return refused
    n = 12_000  # pinned: spawn cost (two extra workers mid-run) is fixed,
    # routing cost is per-record — the ceiling needs a fixed workload
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    conf = os.path.join(root, "conf", "spatialflink-conf.yml")
    lines = clustered_lines(_grid(), n, 0.95, seed=7, fmt="geojson",
                            dt_ms=1)
    td = tempfile.mkdtemp(prefix="bench-rescale-")
    try:
        path1 = os.path.join(td, "in.geojson")
        with open(path1, "w") as f:
            f.write("\n".join(lines) + "\n")

        def fleet(tag, *extra):
            fdir = os.path.join(td, f"fleet-{tag}")
            t0 = time.perf_counter()
            with contextlib.redirect_stdout(sys.stderr):
                rc = driver_main([
                    "--config", conf, "--option", "1", "--input1", path1,
                    "--fleet", "2", "--fleet-dir", fdir,
                    "--fleet-epoch-records", str(10**9)] + list(extra))
            dt = time.perf_counter() - t0
            assert rc == 0
            res = fleet_mod.read_json(os.path.join(fdir,
                                                   fleet_mod.RESULT_FILE))
            return res, dt

        rescale_argv = ["--fleet-rescale", f"{n // 3}:4",
                        "--fleet-epoch-records", str(n // 6)]
        fleet("warm", *rescale_argv)  # fills the persistent compile
        # cache for BOTH worker-set shapes (N=2 and the post-rescale N=4)
        r_fix, dt_fix = fleet("n2")
        r_rs, dt_rs = fleet("rs", *rescale_argv)
        assert r_rs["digest"] == r_fix["digest"], \
            "fleet merged digest diverged across a live N=2->4 rescale"
        assert r_rs.get("workers_final") == 4, r_rs.get("workers_final")
        assert [(r["n_from"], r["n_to"])
                for r in r_rs.get("rescales", [])] == [(2, 4)]
        assert r_fix["merged_windows"] > 0
        return dict(path="fleet_rescale", records=n, workers=2,
                    workers_final=4,
                    merged_windows=r_rs["merged_windows"],
                    wall_fleet2_fixed_s=round(dt_fix, 3),
                    wall_fleet1_s=round(dt_rs, 3),
                    rescale_x=round(dt_rs / dt_fix, 2),
                    post_warmup_compiles=r_rs["post_warmup_compiles"])
    finally:
        shutil.rmtree(td, ignore_errors=True)


def bench_tenant_plane(n: int) -> dict:
    """Tenant-ledger overhead gate (ISSUE 20, lower-is-better): the same
    two-tenant Q=8 dynamic registry fleet over the same replay with the
    per-dispatch cost ledger OFF (no telemetry session — the gated hot
    path) vs ON (telemetry session: ``note_dispatch`` + the proportional
    ``resolve`` split, host-side arithmetic on already-materialized
    masks). The GATED metric is the on/off wall ratio
    (``overhead_vs_off_x``) at a PINNED record count against a generous
    ceiling — attribution must stay bookkeeping-priced. Window-table
    identity and the ledger's own conservation invariants (every
    dispatch resolved, zero residual from the exact-split fold) are
    asserted in-run, so a ledger that got cheap by dropping spans or
    changing results can never pass."""
    from spatialflink_tpu import driver
    from spatialflink_tpu.operators import (PointPointRangeQuery,
                                            QueryConfiguration, QueryType)
    from spatialflink_tpu.runtime.queryplane import QueryRegistry
    from spatialflink_tpu.utils import telemetry as _telemetry
    from spatialflink_tpu.utils.telemetry import telemetry_session

    n = 60_000  # pinned: the overhead ratio mixes per-dispatch ledger
    # cost into a fixed windowed workload
    lines = _lines(n)
    cfg, grid = _cfg(), _grid()
    conf = QueryConfiguration(QueryType.WindowBased, 10_000, 5_000)
    rng = np.random.default_rng(11)
    q = 8
    pts = [(115.5 + rng.random() * 2, 39.6 + rng.random() * 1.5)
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
    assert ledger["late_resolves"] == 0
    assert ledger["max_residual_ms"] < 1e-6, ledger["max_residual_ms"]
    assert set(ledger["tenants"]) == {"acme", "free"}
    return dict(path="tenant_plane", records=n, queries=q,
                overhead_vs_off_x=round(dt_on / dt_off, 2),
                dispatches_resolved=ledger["resolved"],
                max_residual_ms=ledger["max_residual_ms"])


def measure(n: int) -> list:
    return [bench_window_assign(n), bench_decode_columnar(n),
            bench_windowed_pipeline(n), bench_skew_adaptive(n),
            bench_query_plane(n), bench_controller_pareto(n),
            bench_realtime_vectorized(n), bench_latency_record_emit(n),
            bench_fleet_scaling(n), bench_fleet_rescale(n),
            bench_tenant_plane(n)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=120_000)
    ap.add_argument("--out", default=None)
    ap.add_argument("--check", action="store_true",
                    help="diff the fresh ratios against GUARD_baseline.json "
                         "(bench_diff, metric=speedup, threshold 0.25); "
                         "exit 1 on regression")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write measured/%.1f floors to GUARD_baseline.json"
                         % MARGIN)
    args = ap.parse_args()

    import jax

    backend = jax.default_backend()
    rows = measure(args.n)
    for r in rows:
        r["backend"] = backend
        print(json.dumps(r), flush=True)

    speed_rows = [r for r in rows if "speedup" in r]
    lat_rows = [r for r in rows if "p99_ms" in r]
    fleet_rows = [r for r in rows if "wall_fleet1_s" in r]
    tenant_rows = [r for r in rows if "overhead_vs_off_x" in r]

    if args.write_baseline:
        floors = [dict(path=r["path"],
                       speedup=round(max(
                           r["speedup"] / MARGIN_BY_PATH.get(r["path"],
                                                             MARGIN),
                           1.0), 2))
                  for r in speed_rows]
        ceilings = [dict(path=r["path"],
                         p99_ms=round(r["p99_ms"] * LATENCY_MARGIN, 1))
                    for r in lat_rows]
        fleet_ceilings = [dict(path=r["path"],
                               wall_fleet1_s=round(
                                   r["wall_fleet1_s"] * FLEET_MARGIN, 1))
                          for r in fleet_rows]
        tenant_ceilings = [dict(path=r["path"],
                                overhead_vs_off_x=round(
                                    max(r["overhead_vs_off_x"], 1.0)
                                    * TENANT_MARGIN, 2))
                           for r in tenant_rows]
        with open(BASELINE_PATH, "w") as f:
            json.dump({"metric": "speedup",
                       "note": "conservative floors = measured/%.1f "
                               "(skew_adaptive: /%.1f); bench_guard "
                               "--check trips >25%% below. latency_rows "
                               "are lower-is-better CEILINGS = measured x "
                               "%.1f (metric p99_ms); fleet_rows are "
                               "lower-is-better CEILINGS = measured x "
                               "%.1f (metric wall_fleet1_s: absolute "
                               "single-worker supervised-fleet wall at "
                               "the pinned record count); tenant_rows is "
                               "a lower-is-better CEILING = max(measured, "
                               "1.0) x %.1f (metric overhead_vs_off_x: "
                               "the tenant ledger's on/off wall ratio at "
                               "the pinned record count, identity + "
                               "conservation asserted in-run)"
                               % (MARGIN, MARGIN_BY_PATH["skew_adaptive"],
                                  LATENCY_MARGIN, FLEET_MARGIN,
                                  TENANT_MARGIN),
                       "rows": floors, "latency_rows": ceilings,
                       "fleet_rows": fleet_ceilings,
                       "tenant_rows": tenant_ceilings},
                      f, indent=1)
        print(f"# wrote {BASELINE_PATH}", file=sys.stderr)
        return 0

    if args.out:
        with open(args.out, "w") as f:
            json.dump({"backend": backend, "rows": rows}, f, indent=1)

    if args.check:
        from benchmarks.bench_diff import main as diff_main

        def run_diff(base_rows, fresh_rows, metric, extra):
            base_f = tempfile.NamedTemporaryFile("w", suffix=".json",
                                                 delete=False)
            fresh_f = tempfile.NamedTemporaryFile("w", suffix=".json",
                                                  delete=False)
            try:
                # identity = path only (the floors are scale/backend-
                # agnostic; keeping records/backend in the key would
                # unpair rows)
                json.dump({"rows": base_rows}, base_f)
                base_f.close()
                json.dump({"rows": [dict(path=r["path"],
                                         **{metric: r[metric]})
                                    for r in fresh_rows]}, fresh_f)
                fresh_f.close()
                return diff_main([base_f.name, fresh_f.name,
                                  "--metric", metric,
                                  "--threshold", "0.25",
                                  "--require-all"] + extra)
            finally:
                os.unlink(base_f.name)
                os.unlink(fresh_f.name)

        base = json.load(open(BASELINE_PATH))
        rc = run_diff(base.get("rows", []), speed_rows, "speedup", [])
        # second pass: the latency ceiling, lower-is-better (the worked
        # example in bench_diff's docs)
        rc_lat = run_diff(base.get("latency_rows", []), lat_rows,
                          "p99_ms", ["--lower-is-better"])
        # third pass: the fleet supervision-cost ceiling, also
        # lower-is-better (metric wall_fleet1_s)
        rc_fleet = run_diff(base.get("fleet_rows", []), fleet_rows,
                            "wall_fleet1_s", ["--lower-is-better"])
        # fourth pass: the tenant-ledger overhead ceiling (lower-is-
        # better ratio — the accounting plane must stay bookkeeping-
        # priced on the dispatch hot path)
        rc_tenant = run_diff(base.get("tenant_rows", []), tenant_rows,
                             "overhead_vs_off_x", ["--lower-is-better"])
        return rc or rc_lat or rc_fleet or rc_tenant
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
