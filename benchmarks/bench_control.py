"""Closed-loop controller Pareto bench — ISSUE 18's bench bar.

The chunk governor (``runtime/control.py``) claims it meets or beats
EVERY fixed decode-chunk size on the record→emit p99 vs throughput
frontier, per latency class, including under ``--chaos``. This harness
measures exactly that claim:

- ``frontier`` rows: for each (mode ∈ clean|chaos) × (latency class ∈
  batch|interactive), a fixed-chunk sweep of the windowed range pipeline
  plus ONE governed run (ChunkGovernor installed, ticking on the latency
  plane's bucket cadence like the production reporter thread does). Each
  governed row carries the Pareto composite

      score = min over fixed chunks c of max(gov_rps/rps_c, p99_c/gov_p99)

  — >= 1 means no fixed chunk dominates the governor on both axes. The
  harness asserts score >= 0.75 (the same 25% robustness margin the
  tier-1 ``bench_guard`` gate uses on its ``controller_pareto`` row).
- Window-table identity is asserted across every fixed chunk and the
  governed run of a sweep — and the chaos sweeps assert identity against
  the CLEAN reference table (the exactly-once resequencing contract:
  duplicates/reorder under ``FaultPlan`` must not change one window).
- The governed run of every sweep runs under the compile-registry
  recompile sentinel: live chunk resizes must cause 0 post-warmup XLA
  compiles (the recompile-surface rule's runtime half).
- ``realtime`` row: the rebuilt vectorized realtime mode vs the
  pre-rebuild scalar ``_micro_batches`` branch (fire-table identity
  asserted) — the ISSUE 18 realtime acceptance number.

The interactive class installs a QueryRegistry holding one ``interactive``
standing query, which engages the governor's fast lane (chunk capped at
``interactive_max_chunk``, drive-loop queue depth bounded) — the fixed
rows of that sweep run WITHOUT the cap, so the frontier shows what the
lane trades (throughput) for what it buys (tail latency).

Usage:
    python benchmarks/bench_control.py [--n N] [--chunks 512,...]
        [--out benchmarks/RESULTS_control.json] [--require-backend cpu]
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: governed score floor: the guard gate's 25% robustness margin
SCORE_FLOOR = 0.75
CHAOS_SPEC = "seed=11,duplicate=0.08,reorder=0.25"


def _lines(n: int):
    rng = np.random.default_rng(0)
    t0 = 1_700_000_000_000
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


@contextlib.contextmanager
def _ticker(tel, interval_s: float = 0.02):
    """A reporter-cadence stand-in: close latency-plane buckets (= feed
    the governor) from a side thread, like the production telemetry
    reporter does — the bench must not tick from the hot loop it times."""
    tel.latency.tick_interval_s = interval_s
    stop = threading.Event()

    def loop():
        while not stop.wait(interval_s):
            try:
                tel.latency.maybe_tick(tel)
            except Exception:
                pass

    t = threading.Thread(target=loop, name="bench-ctl-ticker", daemon=True)
    t.start()
    try:
        yield
    finally:
        stop.set()
        t.join(timeout=2.0)


@contextlib.contextmanager
def _latency_class(lclass: str):
    """Installed-registry context: ``interactive`` admits one interactive
    standing query (the governor's fast-lane signal); ``batch`` installs
    a batch-only fleet so the lane provably stays off."""
    from spatialflink_tpu.runtime.queryplane import QueryRegistry

    reg = QueryRegistry("range", radius=0.5)
    reg.admit({"id": "probe", "x": 116.5, "y": 40.3,
               "latency_class": lclass})
    reg.apply()
    reg.install()
    try:
        yield reg
    finally:
        reg.uninstall()


def _run_replay(lines, cfg, grid, chunk, gov=None, lclass="batch"):
    """(window_table, rps, p99_ms) for one clean-replay configuration."""
    from spatialflink_tpu import driver
    from spatialflink_tpu.models import Point
    from spatialflink_tpu.operators import (PointPointRangeQuery,
                                            QueryConfiguration, QueryType)
    from spatialflink_tpu.utils.telemetry import telemetry_session

    conf = QueryConfiguration(QueryType.WindowBased, 10_000, 5_000)
    qp = Point.create(116.5, 40.3, grid, obj_id="q")
    with telemetry_session() as tel, _latency_class(lclass), _ticker(tel):
        if gov is not None:
            gov.install()
        try:
            op = PointPointRangeQuery(conf, grid)
            s = driver.decode_stream(iter(lines), cfg, grid, chunk=chunk)
            t0 = time.perf_counter()
            table = [(r.window_start, len(r.records))
                     for r in op.run(s, qp, 0.5)]
            wall = time.perf_counter() - t0
            p99 = tel.latency.record_emit.percentile(99)
        finally:
            if gov is not None:
                gov.uninstall()
    return table, len(lines) / wall, p99


def _run_chaos(lines, cfg, grid, chunk, gov=None, lclass="batch", tag="0"):
    """Same measurement through the degraded transport: InMemoryBroker
    wrapped in a seeded ChaosBroker (duplicates + reordering), consumed
    via KafkaSource -> WindowCommitTap -> the chunked decode. The
    resequencing consumer must hand the SAME records downstream, so the
    window table is asserted (by the caller) against the clean run's."""
    from spatialflink_tpu import driver
    from spatialflink_tpu.models import Point
    from spatialflink_tpu.operators import (PointPointRangeQuery,
                                            QueryConfiguration, QueryType)
    from spatialflink_tpu.runtime.faults import ChaosBroker, FaultPlan
    from spatialflink_tpu.streams.kafka import (InMemoryBroker, KafkaSource,
                                                WindowCommitTap)
    from spatialflink_tpu.utils.telemetry import telemetry_session

    inner = InMemoryBroker()
    for ln in lines:
        inner.produce("t", ln)
    broker = ChaosBroker(inner, FaultPlan.from_spec(CHAOS_SPEC))
    conf = QueryConfiguration(QueryType.WindowBased, 10_000, 5_000)
    qp = Point.create(116.5, 40.3, grid, obj_id="q")
    with telemetry_session() as tel, _latency_class(lclass), _ticker(tel):
        if gov is not None:
            gov.install()
        try:
            src = KafkaSource(broker, "t", f"g-{tag}", poll_batch=500,
                              auto_commit=False, stop_at_end=True)
            tap = WindowCommitTap(
                src, 10_000, 5_000, parse=lambda r: None,
                bulk_decode=driver._kafka_bulk_decode(cfg, grid),
                bulk_chunk=chunk)
            op = PointPointRangeQuery(conf, grid)
            s = driver.decode_stream(tap, cfg, grid, chunk=chunk)
            t0 = time.perf_counter()
            table = [(r.window_start, len(r.records))
                     for r in op.run(s, qp, 0.5)]
            wall = time.perf_counter() - t0
            p99 = tel.latency.record_emit.percentile(99)
        finally:
            if gov is not None:
                gov.uninstall()
    return table, len(lines) / wall, p99


def frontier(lines, cfg, grid, chunks, mode: str, lclass: str,
             clean_ref=None, reps: int = 3):
    """One sweep: fixed chunks + the governed run, identity + sentinel
    asserted; returns (rows, governed_score, reference_table).

    Every configuration (each fixed chunk AND the governed run) is
    measured ``reps`` times and reported at its best p99 / best rps:
    single-shot p99 over ~20 windows through a chaos transport is
    scheduling-noise-dominated (the same fixed config varies up to 3x
    run to run), and best-of-R is the stable estimator of what a config
    can do — applied uniformly, so neither side of the comparison gets
    the optimism the other didn't."""
    from spatialflink_tpu.runtime.control import ChunkGovernor
    from spatialflink_tpu.utils import deviceplane

    runner = _run_chaos if mode == "chaos" else _run_replay
    rows = []
    ref = clean_ref
    fixed = {}
    for c in chunks:
        rps, p99 = 0.0, float("inf")
        for rep in range(reps):
            kw = (dict(tag=f"{mode}-{lclass}-{c}-{rep}")
                  if mode == "chaos" else {})
            table, r_, p_ = runner(lines, cfg, grid, c, lclass=lclass, **kw)
            if ref is None:
                ref = table
            assert table == ref, (
                f"{mode}/{lclass}: window table diverged at fixed "
                f"chunk {c}")
            rps, p99 = max(rps, r_), min(p99, p_)
        fixed[c] = (rps, p99)
        rows.append(dict(path="frontier", mode=mode, latency_class=lclass,
                         chunk=c, governed=False, records=len(lines),
                         reps=reps, records_per_sec=int(rps),
                         emit_p99_ms=round(p99, 3)))
        print(json.dumps(rows[-1]), flush=True)
    # the governed runs, under the recompile sentinel: a live resize must
    # never cost an XLA compile (shapes pre-warmed by the fixed sweep)
    dp = deviceplane.registry()
    dp.begin_run()
    dp.mark_warm("bench_control governed run (fixed sweep pre-warmed)")
    try:
        rps, p99 = 0.0, float("inf")
        for rep in range(reps):
            gov = ChunkGovernor()  # fresh trajectory per rep
            kw = (dict(tag=f"{mode}-{lclass}-gov-{rep}")
                  if mode == "chaos" else {})
            table, r_, p_ = runner(lines, cfg, grid, gov.chunk_callback(),
                                   gov=gov, lclass=lclass, **kw)
            assert table == ref, (
                f"{mode}/{lclass}: governed run changed results")
            rps, p99 = max(rps, r_), min(p99, p_)
        post_warm = dp.run_recompiles
    finally:
        dp.end_run()
    assert post_warm == 0, (
        f"{mode}/{lclass}: recompile sentinel fired {post_warm}x across "
        "governed chunk resizes — the decode chunk must only size host "
        "buffers")
    score = min(max(rps / frps, fp99 / p99)
                for frps, fp99 in fixed.values())
    st = gov.status()
    rows.append(dict(path="frontier", mode=mode, latency_class=lclass,
                     chunk="governed", governed=True, records=len(lines),
                     records_per_sec=int(rps), emit_p99_ms=round(p99, 3),
                     pareto_score=round(score, 2),
                     final_chunk=st["chunk"], fast_lane=st["fast_lane"],
                     ticks=st["ticks"],
                     steps=st["grows"] + st["shrinks"],
                     post_warmup_compiles=post_warm))
    print(json.dumps(rows[-1]), flush=True)
    assert score >= SCORE_FLOOR, (
        f"{mode}/{lclass}: governed run dominated by a fixed chunk "
        f"(score {score:.2f} < {SCORE_FLOOR}) — the governor must meet "
        "or beat every fixed size on the frontier")
    return rows, score, ref


def bench_realtime(lines, cfg, grid) -> dict:
    """Vectorized realtime vs the scalar oracle (same shape as the
    ``realtime_vectorized`` tier-1 guard row, kept here so the ISSUE 18
    results file is self-contained)."""
    from spatialflink_tpu import driver
    from spatialflink_tpu.models import Point
    from spatialflink_tpu.operators import (PointPointRangeQuery,
                                            QueryConfiguration, QueryType)

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

    run_new(), run_scalar()  # warm
    t0 = time.perf_counter()
    new = run_new()
    dt_new = time.perf_counter() - t0
    t0 = time.perf_counter()
    old = run_scalar()
    dt_old = time.perf_counter() - t0
    assert new == old, "vectorized realtime diverged from the scalar oracle"
    row = dict(path="realtime", records=len(lines), fires=len(new),
               wall_vectorized_s=round(dt_new, 3),
               wall_scalar_s=round(dt_old, 3),
               speedup=round(dt_old / dt_new, 2))
    print(json.dumps(row), flush=True)
    return row


def measure(n: int, chunks):
    cfg, grid = _cfg_grid()
    lines = _lines(n)
    rows = []
    _run_replay(lines, cfg, grid, 4096)  # jit warm
    clean_ref = None
    scores = {}
    for mode in ("clean", "chaos"):
        for lclass in ("batch", "interactive"):
            sweep, score, ref = frontier(
                lines, cfg, grid, chunks, mode, lclass,
                # chaos sweeps must reproduce the CLEAN table: the
                # exactly-once resequencing contract, asserted per row
                clean_ref=clean_ref if mode == "chaos" else None)
            if clean_ref is None:
                clean_ref = ref
            rows.extend(sweep)
            scores[f"{mode}/{lclass}"] = score
    rows.append(bench_realtime(lines, cfg, grid))
    return rows, scores


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=60_000)
    ap.add_argument("--chunks", default="512,1024,2048,4096,8192")
    ap.add_argument("--out", default=None)
    ap.add_argument("--require-backend", default=None)
    args = ap.parse_args()

    import jax

    backend = jax.default_backend()
    if args.require_backend and backend != args.require_backend:
        print(f"# backend {backend} != required {args.require_backend}",
              file=sys.stderr)
        return 2
    chunks = [int(c) for c in args.chunks.split(",") if c]
    rows, scores = measure(args.n, chunks)
    for r in rows:
        r["backend"] = backend
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"backend": backend, "chaos_spec": CHAOS_SPEC,
                       "score_floor": SCORE_FLOOR,
                       "pareto_scores": scores, "rows": rows}, f, indent=1)
        print(f"# wrote {args.out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
