"""Pane-incremental window engine (--panes): equivalence + cache behavior.

Headline invariant: pane-incremental execution is an EXECUTION STRATEGY,
not a semantics change — for every supported family (range, kNN, join,
tRange, tStats, tAggregate) and every arrival pattern (in-order,
out-of-order, late-dropped, chaos-replayed), the pane window tables are
identical to full-recompute tables (exact for selections/ids, tolerance
for float aggregates whose summation order legitimately differs).

Fast tests (default marker set): the PaneBuffer unit contract against the
independent tests/oracles.py window oracle, per-family equivalence on
small streams, and the pane-cache smoke test asserting hit/miss counters +
pane-merge telemetry spans. The broad fuzz sweeps and the --chaos replay
identity are marked ``slow``.
"""

import json

import numpy as np
import pytest
import yaml

from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import Point, Polygon
from spatialflink_tpu.operators import (
    PointPointJoinQuery,
    PointPointKNNQuery,
    PointPointRangeQuery,
    QueryConfiguration,
    QueryType,
)
from spatialflink_tpu.operators.trajectory import (
    PointPolygonTRangeQuery,
    PointTAggregateQuery,
    PointTStatsQuery,
)
from spatialflink_tpu.runtime.windows import PaneBuffer, WindowAssembler, WindowSpec
from spatialflink_tpu.utils.metrics import scoped_registry
from spatialflink_tpu.utils.telemetry import telemetry_session
from tests import oracles as O

GRID = UniformGrid(115.50, 117.60, 39.60, 41.10, num_grid_partitions=100)
QUERY = Point.create(116.5, 40.5, GRID, obj_id="q")
POLY = Polygon.create(
    [[(116.0, 40.0), (117.0, 40.0), (117.0, 40.8), (116.0, 40.8)]], GRID)


def conf(panes, size_ms=20_000, slide_ms=5_000, **kw):
    return QueryConfiguration(query_type=QueryType.WindowBased,
                              window_size_ms=size_ms, slide_ms=slide_ms,
                              panes=panes, **kw)


def stream(n=400, seed=0, jitter_ms=0, span_ms=40_000, n_obj=30):
    """Synthetic point stream; ``jitter_ms`` > 0 makes arrivals
    out-of-order (and, with lateness 0, exercises late drops)."""
    r = np.random.default_rng(seed)
    ts = np.sort(r.integers(0, span_ms, n))
    if jitter_ms:
        ts = ts + r.integers(-jitter_ms, jitter_ms + 1, n)
    return [
        Point.create(float(x), float(y), GRID, obj_id=f"v{int(o)}",
                     timestamp=int(t))
        for x, y, o, t in zip(r.uniform(115.6, 117.5, n),
                              r.uniform(39.7, 41.0, n),
                              r.integers(0, n_obj, n), ts)
    ]


# --------------------------------------------------------------------- #
# PaneBuffer unit contract


class TestPaneBuffer:
    def test_rejects_non_decomposable_specs(self):
        with pytest.raises(ValueError):
            PaneBuffer(WindowSpec.sliding(10_000, 10_000))  # tumbling
        with pytest.raises(ValueError):
            PaneBuffer(WindowSpec.sliding(10_000, 4_000))  # slide !| size

    @pytest.mark.parametrize("jitter,lateness", [(0, 0), (1500, 0),
                                                 (1500, 2000)])
    def test_matches_assembler_and_oracle(self, jitter, lateness):
        spec = WindowSpec.sliding(15_000, 5_000)
        recs = stream(n=300, seed=4, jitter_ms=jitter)
        wa = WindowAssembler(spec, lateness)
        pb = PaneBuffer(spec, lateness)
        ref, pane = [], []
        for r in recs:
            ref += list(wa.add(r.timestamp, r))
            pane += list(pb.add(r.timestamp, r))
        ref += list(wa.flush())
        pane += list(pb.flush())
        flat = [(s, e, sorted(O.canon_point(p) for _, rs in panes
                              for p in rs)) for s, e, panes in pane]
        rf = [(s, e, sorted(O.canon_point(p) for p in rs))
              for s, e, rs in ref]
        assert flat == rf
        assert pb.late_dropped == wa.late_dropped
        # independent oracle: window starts + membership counts
        oracle = O.sliding_window_table([r.timestamp for r in recs],
                                        spec.size_ms, spec.slide_ms,
                                        lateness)
        assert sorted(s for s, _, _ in rf) == sorted(oracle)
        counts = {s: len(idx) for s, idx in oracle.items()}
        assert {s: len(r) for s, _, r in rf} == counts

    def test_each_record_buffered_once(self):
        spec = WindowSpec.sliding(20_000, 5_000)
        pb = PaneBuffer(spec)
        for r in stream(n=100, seed=1):
            list(pb.add(r.timestamp, r))
        assert sum(len(v) for v in pb._panes.values()) <= 100


# --------------------------------------------------------------------- #
# fast per-family equivalence (default marker set)


def canon_range(results):
    return O.canon_windows(results, O.canon_point)


def canon_knn(results):
    return O.canon_windows(results, O.canon_knn_pair)


def canon_join(results):
    return O.canon_windows(
        results, lambda ab: (O.canon_point(ab[0]), O.canon_point(ab[1])))


class TestFamilyEquivalence:
    def test_range(self):
        s = stream(jitter_ms=1200, seed=2)
        off = canon_range(PointPointRangeQuery(conf(False), GRID)
                          .run(iter(s), QUERY, 0.4))
        on = canon_range(PointPointRangeQuery(conf(True), GRID)
                         .run(iter(s), QUERY, 0.4))
        assert off == on and off

    def test_knn(self):
        s = stream(jitter_ms=800, seed=3)
        off = canon_knn(PointPointKNNQuery(conf(False), GRID)
                        .run(iter(s), QUERY, 0.5, 7))
        on = canon_knn(PointPointKNNQuery(conf(True), GRID)
                       .run(iter(s), QUERY, 0.5, 7))
        assert off == on and off

    def test_join(self):
        a, b = stream(n=250, seed=5, jitter_ms=600), stream(n=80, seed=6,
                                                            jitter_ms=600)
        off = canon_join(PointPointJoinQuery(conf(False), GRID, GRID)
                         .run(iter(a), iter(b), 0.2))
        on = canon_join(PointPointJoinQuery(conf(True), GRID, GRID)
                        .run(iter(a), iter(b), 0.2))
        assert off == on and any(r for _, _, r in off)

    def test_trange(self):
        s = stream(seed=7, jitter_ms=500)
        def canon(results):
            return [(r.window_start, sorted(r.extras["matched_ids"]),
                     sorted((getattr(g, "obj_id", ""), type(g).__name__)
                            for g in r.records)) for r in results]
        off = canon(PointPolygonTRangeQuery(conf(False), GRID)
                    .run(iter(s), [POLY]))
        on = canon(PointPolygonTRangeQuery(conf(True), GRID)
                   .run(iter(s), [POLY]))
        assert off == on and off

    def test_tstats(self):
        s = stream(seed=8, jitter_ms=500)
        off = list(PointTStatsQuery(conf(False), GRID).run(iter(s)))
        on = list(PointTStatsQuery(conf(True), GRID).run(iter(s)))
        _assert_tstats_equal(off, on)

    @pytest.mark.parametrize("agg", ["SUM", "AVG", "MIN", "MAX", "COUNT",
                                     "ALL"])
    def test_taggregate(self, agg):
        s = stream(seed=9, jitter_ms=400)
        off = list(PointTAggregateQuery(conf(False), GRID).run(iter(s), agg))
        on = list(PointTAggregateQuery(conf(True), GRID).run(iter(s), agg))
        _assert_taggregate_equal(off, on, agg)

    def test_run_multi_range_and_knn(self):
        s = stream(seed=10)
        qs = [QUERY, Point.create(116.0, 40.0, GRID, obj_id="q2")]
        for cls, args, canon in (
                (PointPointRangeQuery, (qs, 0.4), O.canon_point),
                (PointPointKNNQuery, (qs, 0.5, 5), O.canon_knn_pair)):
            def canon_multi(results):
                return [(r.window_start,
                         [sorted(canon(x) for x in per_q)
                          for per_q in r.records]) for r in results]
            off = canon_multi(cls(conf(False), GRID).run_multi(iter(s), *args))
            on = canon_multi(cls(conf(True), GRID).run_multi(iter(s), *args))
            assert off == on and off

    def test_bulk_range_and_knn(self):
        # the served columnar decode of CSV text, panes on and off, against
        # the oracle's window tables (kNN at radius 0: exact top-k)
        from spatialflink_tpu.config import StreamConfig
        from spatialflink_tpu.driver import decode_stream

        r = np.random.default_rng(11)
        n = 3000
        ts = 1_700_000_000_000 + np.sort(r.integers(0, 60_000, n))
        rows = [(f"v{int(o)}", int(t), round(float(x), 6), round(float(y), 6))
                for o, t, x, y in zip(r.integers(0, 50, n), ts,
                                      r.uniform(115.6, 117.5, n),
                                      r.uniform(39.7, 41.0, n))]
        lines = [f"{o},{t},{x:.6f},{y:.6f}" for o, t, x, y in rows]
        cfg = StreamConfig(format="CSV", date_format=None)

        def served(panes, run):
            return run(decode_stream(lines, cfg, GRID), panes)

        def range_run(src, panes):
            return {w.window_start: sorted((p.obj_id, p.timestamp)
                                           for p in w.records)
                    for w in PointPointRangeQuery(conf(panes), GRID).run(
                        src, QUERY, 0.4) if w.records}

        def knn_run(src, panes):
            return {w.window_start: sorted(map(_canon_any, w.records))
                    for w in PointPointKNNQuery(conf(panes), GRID).run(
                        src, QUERY, 0.0, 7)}

        want = O.range_window_table(rows, QUERY.x, QUERY.y, 0.4,
                                    20_000, 5_000)
        assert want
        assert served(False, range_run) == want
        assert served(True, range_run) == want
        knn_want = {
            start: sorted(zip(ids, (round(d, 6) for d in dists)))
            for start, (ids, dists) in O.knn_window_table(
                rows, QUERY.x, QUERY.y, 7, 20_000, 5_000).items()}
        for panes in (False, True):
            got = served(panes, knn_run)
            assert got.keys() == knn_want.keys()
            for start, recs in got.items():
                assert [o for o, _ in recs] == [o for o, _ in knn_want[start]]
                np.testing.assert_allclose([d for _, d in recs],
                                           [d for _, d in knn_want[start]],
                                           atol=1e-4)

    def test_tumbling_bypasses_cache(self):
        s = stream(seed=12)
        with scoped_registry() as reg:
            off = canon_range(
                PointPointRangeQuery(conf(False, 10_000, 10_000), GRID)
                .run(iter(s), QUERY, 0.4))
            on = canon_range(
                PointPointRangeQuery(conf(True, 10_000, 10_000), GRID)
                .run(iter(s), QUERY, 0.4))
            assert off == on
            assert reg.counter("pane-cache-hits").count == 0
            assert reg.counter("pane-cache-misses").count == 0


def _canon_any(rec):
    if isinstance(rec, tuple):
        return (rec[0], round(float(rec[1]), 6))
    return rec


def _assert_tstats_equal(off, on, tol_spatial=1e-3, tol_temporal=1):
    assert [(r.window_start, r.window_end) for r in off] == \
           [(r.window_start, r.window_end) for r in on]
    for a, b in zip(off, on):
        da = {t[0]: t[1:] for t in a.records}
        db = {t[0]: t[1:] for t in b.records}
        assert set(da) == set(db), a.window_start
        for oid in da:
            assert abs(da[oid][0] - db[oid][0]) < tol_spatial
            assert abs(da[oid][1] - db[oid][1]) <= tol_temporal


def _assert_taggregate_equal(off, on, agg):
    assert len(off) == len(on)
    for a, b in zip(off, on):
        assert (a.window_start, a.window_end) == (b.window_start,
                                                  b.window_end)
        if agg == "ALL":
            assert sorted(a.records) == sorted(b.records)
        else:
            np.testing.assert_allclose(a.extras["heatmap"],
                                       b.extras["heatmap"],
                                       rtol=1e-5, atol=1e-3)


# --------------------------------------------------------------------- #
# pane-cache smoke: counters + telemetry (default marker set)


class TestPaneCacheSmoke:
    def test_hit_miss_counters_and_merge_spans(self):
        """At overlap o over P panes, the kernel runs once per pane
        (misses == P) and every other pane slot is a cache hit
        (hits == total slots - P); the telemetry snapshot carries the
        pane-merge span and the counters."""
        s = stream(n=300, seed=13)  # in-order, spans [0, 40s)
        overlap, slide = 4, 5_000
        with scoped_registry() as reg, telemetry_session() as tel:
            results = list(PointPointRangeQuery(
                conf(True, overlap * slide, slide), GRID)
                .run(iter(s), QUERY, 0.4))
            snap = tel.snapshot()
        panes = {p.timestamp - p.timestamp % slide for p in s}
        misses = reg.counter("pane-cache-misses").count
        hits = reg.counter("pane-cache-hits").count
        assert misses == len(panes)
        total_slots = sum(
            1 for r in results
            for p in range(r.window_start,
                           r.window_start + overlap * slide, slide)
            if p in panes)
        assert hits + misses == total_slots
        assert hits > 0
        assert "range.pane-merge" in snap["spans"]
        assert snap["spans"]["range.pane-merge"]["count"] == len(results)
        assert snap["counters"]["pane-cache-hits"] == hits
        assert snap["counters"]["pane-cache-misses"] == misses

    def test_kernel_work_drops_with_overlap(self):
        """batches-evaluated counts kernel dispatches: panes-off runs one
        per window; panes-on one per window too (the merge Deferred), but
        records-evaluated stays the same while actual pane kernels =
        misses << windows * overlap panes."""
        s = stream(n=400, seed=14)
        with scoped_registry() as reg:
            list(PointPointRangeQuery(conf(True, 40_000, 5_000), GRID)
                 .run(iter(s), QUERY, 0.4))
            misses = reg.counter("pane-cache-misses").count
            hits = reg.counter("pane-cache-hits").count
        # overlap 8: >= 7/8 of pane evaluations served from cache at
        # steady state (edges lower the ratio slightly)
        assert hits >= 2 * misses


# --------------------------------------------------------------------- #
# broad fuzz + chaos replay (slow)


@pytest.mark.slow
class TestPaneFuzz:
    @pytest.mark.parametrize("seed", range(8))
    def test_fuzz_all_families(self, seed):
        r = np.random.default_rng(seed)
        overlap = int(r.choice([2, 3, 4, 8]))
        slide = int(r.choice([2_000, 5_000]))
        lateness = int(r.choice([0, 1_000, 3_000]))
        jitter = int(r.choice([0, 500, 2_500]))
        s = stream(n=int(r.integers(50, 500)), seed=seed + 100,
                   jitter_ms=jitter, span_ms=overlap * slide * 5)
        c_off = conf(False, overlap * slide, slide,
                     allowed_lateness_ms=lateness)
        c_on = conf(True, overlap * slide, slide,
                    allowed_lateness_ms=lateness)

        assert canon_range(PointPointRangeQuery(c_off, GRID)
                           .run(iter(s), QUERY, 0.4)) == \
            canon_range(PointPointRangeQuery(c_on, GRID)
                        .run(iter(s), QUERY, 0.4))
        assert canon_knn(PointPointKNNQuery(c_off, GRID)
                         .run(iter(s), QUERY, 0.5, 6)) == \
            canon_knn(PointPointKNNQuery(c_on, GRID)
                      .run(iter(s), QUERY, 0.5, 6))
        b = stream(n=60, seed=seed + 200, jitter_ms=jitter,
                   span_ms=overlap * slide * 5)
        assert canon_join(PointPointJoinQuery(c_off, GRID, GRID)
                          .run(iter(s), iter(b), 0.3)) == \
            canon_join(PointPointJoinQuery(c_on, GRID, GRID)
                       .run(iter(s), iter(b), 0.3))
        _assert_tstats_equal(
            list(PointTStatsQuery(c_off, GRID).run(iter(s))),
            list(PointTStatsQuery(c_on, GRID).run(iter(s))))


@pytest.mark.slow
class TestPaneChaosReplay:
    """--panes under --chaos: the recovered window table of a chaos-injected
    panes-on run is identical to the fault-free panes-off oracle (the PR 1
    invariant, now with the pane engine in the loop)."""

    def test_chaos_replay_identity(self, tmp_path):
        from spatialflink_tpu.driver import main
        from spatialflink_tpu.streams import (KafkaWindowSink,
                                              reset_memory_brokers,
                                              resolve_broker,
                                              serialize_spatial)
        from spatialflink_tpu.streams.sources import SyntheticPointSource

        reset_memory_brokers()
        try:
            with open("conf/spatialflink-conf.yml") as f:
                d = yaml.safe_load(f)
            d["window"].update(interval=20, step=5)
            lines = [serialize_spatial(p, "GeoJSON")
                     for p in SyntheticPointSource(
                         GRID, num_trajectories=8, steps=6, seed=3)]

            def run(name, extra):
                d["kafkaBootStrapServers"] = f"memory://{name}"
                cfg = tmp_path / f"{name}.yml"
                cfg.write_text(yaml.safe_dump(d))
                broker = resolve_broker(f"memory://{name}")
                for ln in lines:
                    broker.produce("points.geojson", ln)
                assert main(["--config", str(cfg), "--kafka",
                             "--option", "1"] + extra) == 0
                table = {}
                for r in broker.fetch("output", 0, 1_000_000):
                    if isinstance(r.key, str) and r.key.startswith(
                            KafkaWindowSink.MARKER):
                        table[r.key[len(KafkaWindowSink.MARKER):]] = \
                            int(r.value)
                return table

            oracle = run("pane-oracle", [])
            chaotic = run("pane-chaos", [
                "--panes",
                "--chaos", "seed=7,fetch_fail=0.2,duplicate=0.3,"
                           "reorder=0.5,latency=0.1,latency_ms=1",
                "--retry", "attempts=12,base_ms=1,max_ms=20"])
            assert oracle and chaotic == oracle
        finally:
            reset_memory_brokers()
