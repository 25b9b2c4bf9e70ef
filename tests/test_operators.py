"""End-to-end operator pipelines on synthetic streams (the minimum slice)."""

import numpy as np
import pytest

from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import Point
from spatialflink_tpu.operators import (
    PointPointJoinQuery,
    PointPointKNNQuery,
    PointPointRangeQuery,
    QueryConfiguration,
    QueryType,
)
from spatialflink_tpu.streams import SyntheticPointSource
from tests import oracles as O

GRID = UniformGrid(115.50, 117.60, 39.60, 41.10, num_grid_partitions=100)
QUERY = Point.create(116.5, 40.5, GRID, obj_id="q")


def window_conf(**kw):
    return QueryConfiguration(
        query_type=QueryType.WindowBased, window_size_ms=10_000, slide_ms=5_000, **kw
    )


def source(**kw):
    defaults = dict(num_trajectories=50, steps=30, dt_ms=1000, seed=3)
    defaults.update(kw)
    return SyntheticPointSource(GRID, **defaults)


class TestRangePipeline:
    def test_window_results_match_oracle(self):
        r = 0.3
        op = PointPointRangeQuery(window_conf(), GRID)
        results = list(op.run(source(), QUERY, r))
        assert results, "no windows sealed"
        # oracle per window: replay records through the same window assembler
        from spatialflink_tpu.runtime import WindowAssembler, WindowSpec

        wa = WindowAssembler(WindowSpec.sliding(10_000, 5_000))
        windows = {}
        for p in source():
            for s, e, recs in wa.add(p.timestamp, p):
                windows[s] = recs
        for res in results:
            if res.window_start not in windows:
                continue
            recs = windows[res.window_start]
            want = set()
            gn = GRID.guaranteed_cells_mask(r, QUERY.cell)
            cn = GRID.candidate_cells_mask(r, QUERY.cell, gn)
            for p in recs:
                if p.cell >= 0 and (
                    gn[p.cell]
                    or (cn[p.cell] and O.pp_dist(p.x, p.y, QUERY.x, QUERY.y) <= r)
                ):
                    want.add((p.obj_id, p.timestamp))
            got = {(p.obj_id, p.timestamp) for p in res.records}
            boundary = {
                t for t in got ^ want
            }
            for oid, ts in boundary:
                p = next(p for p in recs if (p.obj_id, p.timestamp) == (oid, ts))
                assert abs(O.pp_dist(p.x, p.y, QUERY.x, QUERY.y) - r) < 1e-3

    def test_realtime_mode_emits(self):
        op = PointPointRangeQuery(
            QueryConfiguration(query_type=QueryType.RealTime, realtime_batch_size=128),
            GRID,
        )
        results = list(op.run(source(), QUERY, 0.5))
        assert results
        assert all(len(r.records) > 0 for r in results)

    def test_count_windows_match_deque_oracle(self):
        """CountBased range (implemented here; the reference throws "Not
        yet support", QueryType.java:6): every `slide` arrivals, the last
        `size` records evaluate — oracle is a plain deque replay of the
        same stream through the single-window evaluator semantics."""
        from collections import deque

        size, slide, r = 40, 15, 0.3
        conf = QueryConfiguration(query_type=QueryType.CountBased,
                                  window_size_ms=size, slide_ms=slide)
        recs = list(source())
        got = list(PointPointRangeQuery(conf, GRID).run(iter(recs), QUERY, r))
        # oracle
        import math

        nb_mask = GRID.neighboring_cells_mask(r, QUERY.cell)

        def within(p):
            return bool(nb_mask[p.cell]) and \
                math.hypot(p.x - QUERY.x, p.y - QUERY.y) <= r

        buf, want = deque(maxlen=size), []
        for i, p in enumerate(recs, 1):
            buf.append(p)
            if i % slide == 0:
                want.append({q.obj_id for q in buf if q.cell >= 0
                             and within(q)})
        assert len(got) == len(want) > 0
        for g, w in zip(got, want):
            assert {p.obj_id for p in g.records} == w

    def test_count_based_still_raises_for_joins(self):
        """A count trigger over two independently-arriving streams is
        ambiguous; joins (incl. the trajectory join) keep the reference's
        construction-time rejection."""
        from spatialflink_tpu.operators import (
            PointPointJoinQuery,
            PointPointTJoinQuery,
        )

        for cls in (PointPointJoinQuery, PointPointTJoinQuery):
            with pytest.raises(NotImplementedError):
                cls(QueryConfiguration(query_type=QueryType.CountBased),
                    GRID)

    def test_count_based_bulk_paths_refuse(self):
        """A WindowSpec is EVENT-TIME; under count mode window_spec()
        raises rather than silently reinterpreting counts as
        milliseconds."""
        conf = QueryConfiguration(query_type=QueryType.CountBased,
                                  window_size_ms=40, slide_ms=15)
        with pytest.raises(NotImplementedError, match="record-path only"):
            conf.window_spec()

    def test_incremental_matches_full(self):
        r = 0.3
        op_full = PointPointRangeQuery(window_conf(), GRID)
        op_inc = PointPointRangeQuery(window_conf(), GRID)
        full = {
            res.window_start: {(p.obj_id, p.timestamp) for p in res.records}
            for res in op_full.run(source(), QUERY, r)
        }
        inc = {
            res.window_start: {(p.obj_id, p.timestamp) for p in res.records}
            for res in op_inc.run_incremental(source(), QUERY, r)
        }
        shared = set(full) & set(inc)
        assert shared
        for s in shared:
            assert full[s] == inc[s], f"window {s} differs"


class TestKnnPipeline:
    def test_window_knn_matches_oracle(self):
        k, r = 10, 0.0  # r=0: no pruning
        op = PointPointKNNQuery(window_conf(k=k), GRID)
        results = list(op.run(source(), QUERY, r))
        assert results
        from spatialflink_tpu.runtime import WindowAssembler, WindowSpec

        wa = WindowAssembler(WindowSpec.sliding(10_000, 5_000))
        windows = {}
        for p in source():
            for s, e, recs in wa.add(p.timestamp, p):
                windows[s] = recs
        checked = 0
        for res in results:
            recs = windows.get(res.window_start)
            if not recs:
                continue
            want_ids, want_d = O.knn(
                QUERY.x, QUERY.y,
                [p.x for p in recs], [p.y for p in recs],
                [p.obj_id for p in recs], k,
            )
            got_d = [d for _, d in res.records]
            np.testing.assert_allclose(got_d, want_d, atol=1e-4)
            checked += 1
        assert checked


class TestJoinPipeline:
    def test_join_pairs_match_oracle(self):
        r = 0.05
        conf = window_conf()
        op = PointPointJoinQuery(conf, GRID)
        ordinary = list(source(seed=10, num_trajectories=40, steps=20))
        queries = list(source(seed=11, num_trajectories=10, steps=20))
        results = list(op.run(iter(ordinary), iter(queries), r))
        assert results
        total_pairs = sum(len(res.records) for res in results)
        assert total_pairs > 0
        for res in results[:3]:
            for pa, pb in res.records:
                assert O.pp_dist(pa.x, pa.y, pb.x, pb.y) <= r + 1e-3


class TestJoinRegressions:
    def test_realtime_join_emits_microbatches(self):
        conf = QueryConfiguration(query_type=QueryType.RealTime, realtime_batch_size=64)
        op = PointPointJoinQuery(conf, GRID)
        ordinary = list(source(seed=20, num_trajectories=20, steps=10))
        queries = list(source(seed=21, num_trajectories=5, steps=10))
        results = list(op.run(iter(ordinary), iter(queries), 0.5))
        assert results, "realtime join must emit per micro-batch"

    def test_realtime_join_finds_cross_batch_pairs(self):
        """A pair whose two points straddle a micro-batch boundary must be
        found: both sides keep a rolling window_size_ms buffer across batches
        (reference realtime joins buffer a full small window per stream,
        tJoin/TJoinQuery.java:216-268)."""
        conf = QueryConfiguration(query_type=QueryType.RealTime,
                                  realtime_batch_size=4, window_size_ms=60_000)
        op = PointPointJoinQuery(conf, GRID)
        t0 = 1_700_000_000_000
        far = [Point.create(115.6 + 0.01 * i, 39.7, GRID, obj_id=f"f{i}",
                            timestamp=t0 + i * 100) for i in range(4)]
        # batch 1 = far[0:3] + a; batch 2 = far[3] + b: the (a, b) pair
        # straddles the boundary
        a = Point.create(116.5, 40.5, GRID, obj_id="a", timestamp=t0 + 150)
        b = Point.create(116.5001, 40.5001, GRID, obj_id="b", timestamp=t0 + 500)
        ordinary = [far[0], far[1], far[2], a, far[3]]
        queries = [b]
        results = list(op.run(iter(ordinary), iter(queries), 0.05))
        pairs = {(pa.obj_id, pb.obj_id) for r in results for pa, pb in r.records}
        assert ("a", "b") in pairs

    def test_realtime_join_eviction_spares_in_window_pairs(self):
        """A later filler in the same micro-batch must not evict a buffered
        point that is still within window_size_ms of a new arrival: eviction
        is horizon-ed on the earliest NEW record, and pair co-residence is
        |ta - tb| <= window_size_ms."""
        conf = QueryConfiguration(query_type=QueryType.RealTime,
                                  realtime_batch_size=2, window_size_ms=1_000)
        op = PointPointJoinQuery(conf, GRID)
        t0 = 1_700_000_000_000
        a = Point.create(116.5, 40.5, GRID, obj_id="a", timestamp=t0)
        f0 = Point.create(115.6, 39.7, GRID, obj_id="x", timestamp=t0 + 50)
        b = Point.create(116.5001, 40.5001, GRID, obj_id="b",
                         timestamp=t0 + 900)
        f1 = Point.create(115.7, 39.7, GRID, obj_id="y", timestamp=t0 + 1_100)
        results = list(op.run(iter([a, f0, f1]), iter([b]), 0.05))
        pairs = {(pa.obj_id, pb.obj_id) for r in results for pa, pb in r.records}
        assert ("a", "b") in pairs

    def test_realtime_join_no_duplicate_pairs(self):
        conf = QueryConfiguration(query_type=QueryType.RealTime,
                                  realtime_batch_size=8, window_size_ms=60_000)
        op = PointPointJoinQuery(conf, GRID)
        ordinary = list(source(seed=24, num_trajectories=10, steps=8))
        queries = list(source(seed=25, num_trajectories=4, steps=8))
        results = list(op.run(iter(ordinary), iter(queries), 0.5))
        emitted = [((pa.obj_id, pa.timestamp), (pb.obj_id, pb.timestamp))
                   for r in results for pa, pb in r.records]
        assert len(emitted) == len(set(emitted)), "pair emitted twice"

    def test_realtime_join_expires_old_buffer(self):
        """Points older than window_size_ms must not pair with new arrivals."""
        conf = QueryConfiguration(query_type=QueryType.RealTime,
                                  realtime_batch_size=2, window_size_ms=1_000)
        op = PointPointJoinQuery(conf, GRID)
        t0 = 1_700_000_000_000
        a_old = Point.create(116.5, 40.5, GRID, obj_id="a", timestamp=t0)
        filler = Point.create(115.6, 39.7, GRID, obj_id="x", timestamp=t0 + 100)
        b_new = Point.create(116.5, 40.5, GRID, obj_id="b", timestamp=t0 + 5_000)
        filler2 = Point.create(115.7, 39.7, GRID, obj_id="y", timestamp=t0 + 5_100)
        results = list(op.run(iter([a_old, filler, filler2]), iter([b_new]), 0.05))
        pairs = {(pa.obj_id, pb.obj_id) for r in results for pa, pb in r.records}
        assert ("a", "b") not in pairs

    def test_one_sided_windows_are_emitted_and_freed(self):
        conf = window_conf()
        op = PointPointJoinQuery(conf, GRID)
        # query side goes quiet after the first 10 seconds
        ordinary = list(source(seed=22, num_trajectories=10, steps=40))
        queries = [p for p in source(seed=23, num_trajectories=5, steps=40)
                   if p.timestamp < ordinary[0].timestamp + 10_000]
        results = list(op.run(iter(ordinary), iter(queries), 0.5))
        starts = [r.window_start for r in results]
        # windows long after the query side stopped must still be emitted
        assert max(starts) > min(starts) + 20_000


class TestPipelinedDispatch:
    """Deferred/pipelined window dispatch must not change results or order
    (operators keep pipeline_depth windows in flight on device)."""

    def _stream(self, n=400, seed=11):
        rng = np.random.default_rng(seed)
        t0 = 1_700_000_000_000
        return [
            Point.create(float(rng.uniform(115.6, 117.5)),
                         float(rng.uniform(39.7, 41.0)), GRID,
                         obj_id=str(i % 60), timestamp=t0 + i * 100)
            for i in range(n)
        ]

    def _run(self, mk_op, depth, pts, *args):
        conf = QueryConfiguration(QueryType.WindowBased, window_size_ms=10_000,
                                  slide_ms=5_000, pipeline_depth=depth)
        op = mk_op(conf)
        return list(op.run(iter(pts), *args))

    def test_range_depth_invariant(self):
        pts = self._stream()
        q = Point.create(116.5, 40.5, GRID)
        mk = lambda conf: PointPointRangeQuery(conf, GRID)
        r1 = self._run(mk, 1, pts, q, 0.4)
        r4 = self._run(mk, 4, pts, q, 0.4)
        assert [w.window_start for w in r1] == [w.window_start for w in r4]
        for a, b in zip(r1, r4):
            assert sorted(p.obj_id for p in a.records) == \
                   sorted(p.obj_id for p in b.records)

    def test_knn_depth_invariant(self):
        pts = self._stream()
        q = Point.create(116.5, 40.5, GRID)
        from spatialflink_tpu.operators.knn_query import PointPointKNNQuery
        mk = lambda conf: PointPointKNNQuery(conf, GRID)
        r1 = self._run(mk, 1, pts, q, 0.0, 7)
        r4 = self._run(mk, 4, pts, q, 0.0, 7)
        assert [(w.window_start, w.records) for w in r1] == \
               [(w.window_start, w.records) for w in r4]

    def test_join_depth_invariant(self):
        pts = self._stream(300, seed=1)
        qs = self._stream(80, seed=2)
        from spatialflink_tpu.operators.join_query import PointPointJoinQuery
        mk = lambda conf: PointPointJoinQuery(conf, GRID, GRID)
        r1 = self._run(mk, 1, pts, iter(qs), 0.25)
        r4 = self._run(mk, 4, pts, iter(qs), 0.25)
        assert [w.window_start for w in r1] == [w.window_start for w in r4]
        key = lambda w: sorted((a.obj_id, b.obj_id) for a, b in w.records)
        for a, b in zip(r1, r4):
            assert key(a) == key(b)
            assert isinstance(a.records, list)

    def test_geom_join_depth_invariant_exercises_deferred(self):
        # _GenericStreamJoin is the path that returns Deferred lattices
        from spatialflink_tpu.models import Polygon
        from spatialflink_tpu.operators.join_query import PointGeomJoinQuery

        pts = self._stream(300, seed=3)
        rng = np.random.default_rng(4)
        t0 = 1_700_000_000_000
        polys = []
        for i in range(40):
            cx = float(rng.uniform(115.8, 117.3))
            cy = float(rng.uniform(39.8, 40.9))
            polys.append(Polygon.create(
                [[(cx, cy), (cx + .05, cy), (cx + .05, cy + .05),
                  (cx, cy + .05), (cx, cy)]], GRID,
                obj_id=f"p{i}", timestamp=t0 + i * 500))
        mk = lambda conf: PointGeomJoinQuery(conf, GRID, GRID)
        r1 = self._run(mk, 1, pts, iter(polys), 0.2)
        r4 = self._run(mk, 4, pts, iter(polys), 0.2)
        assert [w.window_start for w in r1] == [w.window_start for w in r4]
        key = lambda w: sorted((a.obj_id, b.obj_id) for a, b in w.records)
        for a, b in zip(r1, r4):
            assert key(a) == key(b)
            assert isinstance(a.records, list)  # materialized before yield
