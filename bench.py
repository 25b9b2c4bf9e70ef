"""Headline benchmark: windowed kNN (k=50) over a 1M-point sliding window.

North star (BASELINE.json): >= 10x per-window throughput vs CPU for kNN k=50
on 1M-point windows, single chip. Metric: points/sec/chip.

Prints exactly ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

The CPU baseline is a vectorized NumPy implementation of the same semantics
(masked distances -> per-object min dedup -> top-k), i.e. an *optimized* CPU
scan — a stronger baseline than the reference's per-tuple JVM loop.
"""

from __future__ import annotations

import json
import os
import sys
import time

N_POINTS = 1_000_000
K = 50
RADIUS = 0.5
# slope window: the high count must put MANY windows of device time between
# the two timings, so that the fixed dispatch->readback cost and its jitter
# cancel. The high count ESCALATES (x5) until the measured gap clears
# SLOPE_MIN_GAP_S.
SLOPE_LO = 2
SLOPE_HI = max(SLOPE_LO + 1,
               int(os.environ.get("SPATIALFLINK_BENCH_ITERS", "42")))
SLOPE_MIN_GAP_S = 0.2
SLOPE_MAX_HI = 40_000
# candidate strategies the bench times briefly and picks from when no
# explicit SPATIALFLINK_BENCH_STRATEGY is set: the bench tunes itself at run
# time instead of trusting constants derived on another backend
TPU_CANDIDATES = ("grouped", "prefilter", "approx_verified")


def build_inputs():
    import numpy as np

    from spatialflink_tpu.index import UniformGrid
    from spatialflink_tpu.models import PointBatch

    grid = UniformGrid(115.50, 117.60, 39.60, 41.10, num_grid_partitions=100)
    rng = np.random.default_rng(0)
    xs = rng.uniform(grid.min_x, grid.max_x, N_POINTS)
    ys = rng.uniform(grid.min_y, grid.max_y, N_POINTS)
    oid = rng.integers(0, N_POINTS // 4, N_POINTS).astype(np.int32)
    batch = PointBatch.from_arrays(xs, ys, grid=grid, obj_id=oid)
    return grid, batch, xs, ys, oid


def bench_device(grid, batch):
    """-> (points/sec/chip, p50_ms, strategy, pick_info) on the default device.

    Windows are processed in an on-device ``fori_loop`` whose body depends on
    the loop index (so XLA cannot hoist it); timing the loop at two iteration
    counts and taking the slope isolates per-window device time from the
    fixed per-dispatch overhead — the regime a streaming pipeline runs in,
    where window batches are queued back-to-back ahead of completion.

    Strategy selection: an explicit ``SPATIALFLINK_BENCH_STRATEGY`` wins;
    otherwise on TPU the bench briefly times each exact candidate and runs
    the full slope measurement on the winner (self-tuning — the constants in
    ops.knn's "auto" were derived on CPU and round 3 showed they don't
    transfer). CPU keeps "auto" (measured: prefilter).
    """
    from functools import partial

    import jax
    import jax.numpy as jnp

    from spatialflink_tpu.ops.knn import knn_point

    qx, qy = 116.5, 40.5
    q_cell, _ = grid.assign_cell(qx, qy)
    nb_layers = grid.candidate_layers(RADIUS)
    batch = jax.device_put(batch)
    qc = jnp.int32(q_cell)

    # iters is a DYNAMIC argument (fori_loop lowers to a while loop), so one
    # compile per strategy covers every loop count the escalation below needs
    @partial(jax.jit, static_argnames=("strategy",))
    def run_n(b, iters, *, strategy):
        def body(i, acc):
            r = knn_point(b, qx + i * 1e-7, qy, qc, RADIUS, nb_layers,
                          n=grid.n, k=K, strategy=strategy)
            return acc + r.dist[0]
        return jax.lax.fori_loop(0, iters, body, jnp.float32(0))

    warmed = set()

    def timed(strategy, iters, reps=3) -> float:
        it = jnp.int32(iters)
        if strategy not in warmed:  # one compile+warm covers every count
            jax.block_until_ready(run_n(batch, it, strategy=strategy))
            warmed.add(strategy)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(run_n(batch, it, strategy=strategy))
            best = min(best, time.perf_counter() - t0)
        return best

    env_strat = os.environ.get("SPATIALFLINK_BENCH_STRATEGY", "")
    pick_info = {}
    if env_strat and env_strat != "auto-pick":
        strategy = env_strat
    elif jax.default_backend() != "tpu":
        strategy = "auto"
    else:
        # probe by slope GAP (not one absolute loop time: the fixed
        # dispatch->readback cost would swamp the difference between
        # strategies), ESCALATING the count until the gap clears a 50ms
        # floor; a jitter-negative gap must rank the strategy as
        # unmeasured-worst, never as best
        def probe_per_window(s):
            p_lo, p_hi = 2, 102
            t_lo = timed(s, p_lo, reps=2)
            while True:
                gap = timed(s, p_hi, reps=2) - t_lo
                if gap >= 0.05 or p_hi >= 20_000:
                    break
                p_hi = min(p_hi * 5, 20_000)
            if gap < 0.05:
                # never cleared the noise floor, even at the cap — a tiny
                # positive jitter gap must rank as unmeasured-WORST, not as
                # the winner
                print(f"warning: strategy {s} probe gap {gap * 1e3:.1f}ms "
                      "below floor at cap; ranking it unmeasured",
                      file=sys.stderr)
                return float("inf")
            return gap / (p_hi - p_lo)

        for s in TPU_CANDIDATES:
            try:
                pick_info[s] = probe_per_window(s)
            except Exception as e:  # a strategy failing must not kill the run
                print(f"warning: strategy {s} failed quick probe: {e}",
                      file=sys.stderr)
        if pick_info and min(pick_info.values()) < float("inf"):
            strategy = min(pick_info, key=pick_info.get)
        else:  # every probe failed; don't let the pick kill the run
            strategy = "grouped"
            print("warning: all strategy probes failed; using 'grouped'",
                  file=sys.stderr)
        print("# strategy pick (probed s/window): "
              + ", ".join(f"{s}={t:.6f}" for s, t in pick_info.items())
              + f" -> {strategy}", file=sys.stderr)

    lo, hi = SLOPE_LO, SLOPE_HI
    t_lo = timed(strategy, lo)
    while True:
        t_hi = timed(strategy, hi)
        gap = t_hi - t_lo
        if gap >= SLOPE_MIN_GAP_S or hi >= SLOPE_MAX_HI:
            break
        hi = min(hi * 5, SLOPE_MAX_HI)
    per_window = gap / (hi - lo)
    if per_window <= 0:
        # timing noise swamped the slope even at SLOPE_MAX_HI; fall back to
        # the conservative whole-loop average (includes fixed dispatch
        # overhead) and say so.
        print("warning: non-positive slope; reporting whole-loop average",
              file=sys.stderr)
        per_window = t_hi / hi
    elif gap < SLOPE_MIN_GAP_S:
        # positive but sub-threshold at the cap: still jitter-sized — a
        # number this produces is NOT a clean measurement, say so loudly
        print(f"warning: slope gap {gap * 1e3:.1f}ms at the {hi}-window cap "
              f"is below the {SLOPE_MIN_GAP_S * 1e3:.0f}ms floor; headline "
              "may be noise-dominated", file=sys.stderr)
    else:
        print(f"# slope window: {lo}->{hi}, gap {gap * 1e3:.1f}ms "
              f"({per_window * 1e6:.1f}us/window)", file=sys.stderr)

    # measured single-window dispatch -> readback distributions (VERDICT
    # #6: a real per-window latency DISTRIBUTION, not slope arithmetic) at
    # pipeline depth 1 vs 2: depth 1 blocks on each window before
    # dispatching the next (what a realtime caller sees); depth 2 keeps one
    # window in flight while the next dispatches — the operator driver's
    # double-buffering — so its per-window latency includes queueing behind
    # the in-flight window, exactly what _drive_batched's readback pays
    win = jax.jit(lambda b, i: knn_point(b, qx + i * 1e-7, qy, qc, RADIUS,
                                         nb_layers, n=grid.n, k=K,
                                         strategy=strategy))
    jax.block_until_ready(win(batch, jnp.float32(0)))
    dist = window_latency_distribution(win, batch, depths=(1, 2))
    return (N_POINTS / per_window, dist["depth1"]["p50_ms"],
            strategy, pick_info, dist)


def window_latency_distribution(win, batch, depths=(1, 2), iters: int = 31):
    """Per-window dispatch->readback wall-clock distribution at each
    pipeline depth: dispatch window i, and block on the OLDEST in-flight
    window once ``depth`` are pending — the same drain rule as
    ``operators.base._drive_batched``. Returns {"depthN": {p50_ms, p99_ms,
    max_ms}} from the measured per-window latencies."""
    from collections import deque

    import jax
    import jax.numpy as jnp
    import numpy as _np

    out = {}
    for depth in depths:
        pending: deque = deque()
        lats = []

        def drain(n):
            while len(pending) > n:
                t0, res = pending.popleft()
                jax.block_until_ready(res)
                lats.append((time.perf_counter() - t0) * 1000)

        for i in range(iters):
            t0 = time.perf_counter()
            pending.append((t0, win(batch, jnp.float32(i))))
            drain(depth - 1)
        drain(0)
        out[f"depth{depth}"] = {
            "p50_ms": round(float(_np.percentile(lats, 50)), 3),
            "p99_ms": round(float(_np.percentile(lats, 99)), 3),
            "max_ms": round(float(_np.max(lats)), 3),
        }
    return out


def bench_cpu_numpy(grid, xs, ys, oid) -> float:
    """Vectorized NumPy baseline with identical semantics."""
    import numpy as np

    qx, qy = 116.5, 40.5
    q_cell, _ = grid.assign_cell(qx, qy)
    L = grid.candidate_layers(RADIUS)
    qcx, qcy = int(q_cell) // grid.n, int(q_cell) % grid.n

    cell, valid = grid.assign_cell(xs, ys)
    cx, cy = cell // grid.n, cell % grid.n

    def run():
        eligible = valid & (np.maximum(np.abs(cx - qcx), np.abs(cy - qcy)) <= L)
        d = np.hypot(xs - qx, ys - qy)
        d = np.where(eligible, d, np.inf)
        # per-object min dedup
        mins = np.full(int(oid.max()) + 1, np.inf)
        np.minimum.at(mins, oid, d)
        finite = np.isfinite(mins)
        idx = np.nonzero(finite)[0]
        if len(idx) > K:
            part = np.argpartition(mins[idx], K)[:K]
            idx = idx[part]
        order = np.argsort(mins[idx])
        return idx[order], mins[idx][order]

    run()  # warm caches
    t0 = time.perf_counter()
    iters = 3
    for _ in range(iters):
        run()
    dt = time.perf_counter() - t0
    return N_POINTS * iters / dt


def main(argv=None):
    import argparse

    argparse.ArgumentParser(
        description="headline kNN bench; prints exactly ONE JSON line. "
                    "Needs a TPU; JAX_PLATFORMS=cpu runs it on the CPU"
    ).parse_args(argv)
    import jax

    from spatialflink_tpu.utils.telemetry import telemetry_session

    backend = jax.default_backend()
    if backend != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        # a measurement path that finds no chip fails; it never falls back
        print(f"bench: JAX found no TPU (backend {backend!r}); set "
              "JAX_PLATFORMS=cpu to measure the CPU instead", file=sys.stderr)
        return 2
    # in-memory telemetry session (no reporter): per-stage spans + grid
    # occupancy ride the result row, so BENCH_* files carry a breakdown of
    # where the wall clock went, not just the headline number
    with telemetry_session() as tel:
        with tel.span("inputs", query="bench"):
            grid, batch, xs, ys, oid = build_inputs()
        with tel.span("device", query="bench"):
            (device_tput, p50_ms, strategy, _pick,
             win_lat) = bench_device(grid, batch)
        with tel.span("cpu-baseline", query="bench"):
            cpu_tput = bench_cpu_numpy(grid, xs, ys, oid)
        telemetry = tel.snapshot()

    row = {
        "metric": "knn_k50_1M_window_points_per_sec_per_chip",
        "value": round(device_tput),
        "unit": "points/s",
        "vs_baseline": round(device_tput / cpu_tput, 2),
        "backend": backend,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
        "valid_for_target": backend == "tpu",
        "p50_window_latency_ms": round(p50_ms, 3),
        # measured dispatch->readback distribution per pipeline depth:
        # depth1 = block-per-window, depth2 = one window in flight behind
        # the dispatch (the driver's double-buffering)
        "window_latency_ms": win_lat,
        "strategy": strategy,
        # final telemetry snapshot: bench.* stage spans, grid occupancy/skew
        "telemetry": telemetry,
    }
    print(json.dumps(row))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
