"""Multi-query batching (TPU-native extension, no reference analogue):
Q queries answered in one dispatch must agree bit-for-bit with Q
single-query dispatches for every selection strategy, including when a
query's exactness certificate fails and the scalar-cond rescue re-runs the
full sort."""

import numpy as np
import pytest

from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import Point, PointBatch
from spatialflink_tpu.operators import (
    PointPointKNNQuery,
    PointPointRangeQuery,
    QueryConfiguration,
    QueryType,
)
from spatialflink_tpu.ops.knn import knn_point, knn_point_multi
from spatialflink_tpu.ops.range import (
    range_filter_point_multi,
    range_filter_point_stats,
)
from tests import oracles as O

GRID = UniformGrid(115.50, 117.60, 39.60, 41.10, num_grid_partitions=100)
RADIUS = 0.5
K = 5


def _batch(n=4096, seed=0, oid_mod=None):
    rng = np.random.default_rng(seed)
    xs = rng.uniform(GRID.min_x, GRID.max_x, n)
    ys = rng.uniform(GRID.min_y, GRID.max_y, n)
    oid = rng.integers(0, oid_mod or n // 4, n).astype(np.int32)
    return PointBatch.from_arrays(xs, ys, grid=GRID, obj_id=oid)


def _queries(q=7, seed=1):
    rng = np.random.default_rng(seed)
    qx = rng.uniform(116.0, 117.0, q).astype(np.float32)
    qy = rng.uniform(40.0, 41.0, q).astype(np.float32)
    qc = np.asarray([GRID.assign_cell(float(x), float(y))[0]
                     for x, y in zip(qx, qy)], np.int32)
    return qx, qy, qc


STRATEGIES = ("sort", "grouped", "prefilter", "approx_verified")


class TestKnnMulti:
    @pytest.mark.parametrize("strategy", STRATEGIES)
    def test_matches_single_query_loop(self, strategy):
        b = _batch()
        qx, qy, qc = _queries()
        nb = GRID.candidate_layers(RADIUS)
        multi = knn_point_multi(b, qx, qy, qc, RADIUS, nb, n=GRID.n, k=K,
                                strategy=strategy)
        for q in range(len(qx)):
            single = knn_point(b, float(qx[q]), float(qy[q]), int(qc[q]),
                               RADIUS, nb, n=GRID.n, k=K, strategy=strategy)
            np.testing.assert_array_equal(np.asarray(multi.obj_id[q]),
                                          np.asarray(single.obj_id))
            np.testing.assert_allclose(np.asarray(multi.dist[q]),
                                       np.asarray(single.dist))

    @pytest.mark.parametrize("strategy,fast_fn,m", [
        ("prefilter", "_prefilter_fast", 256),
        ("approx_verified", "_approx_verified_fast", 512),
    ])
    def test_certificate_failure_rescue(self, strategy, fast_fn, m):
        """A mono-object cloud around query 0 starves its candidate set
        below k distinct objects — query 0's certificate fails while the
        other queries' pass, so the scalar-cond rescue must re-run the full
        sort and the per-query ``jnp.where`` merge must keep the passing
        queries' fast results AND replace the failing one. Asserts the
        mixed pass/fail precondition white-box so data drift can't silently
        turn this into an all-pass (merge-untested) run."""
        import jax

        from spatialflink_tpu.ops import knn as KN

        n = 2048
        rng = np.random.default_rng(3)
        qx = np.asarray([116.5, 117.3, 116.8], np.float32)
        qy = np.asarray([40.5, 41.0, 40.8], np.float32)
        qc = np.asarray([GRID.assign_cell(float(x), float(y))[0]
                         for x, y in zip(qx, qy)], np.int32)
        xs = rng.uniform(GRID.min_x, GRID.max_x, n)
        ys = rng.uniform(GRID.min_y, GRID.max_y, n)
        oid = rng.integers(0, n // 4, n).astype(np.int32)
        cloud = slice(0, 1024)  # mono-object ONLY near query 0
        xs[cloud] = float(qx[0]) + rng.normal(0, 1e-4, 1024)
        ys[cloud] = float(qy[0]) + rng.normal(0, 1e-4, 1024)
        oid[cloud] = 7
        b = PointBatch.from_arrays(xs, ys, grid=GRID, obj_id=oid)
        nb = GRID.n  # radius-0 semantics: no cell pruning

        def parts(qx_, qy_, qc_):
            d, e, _ = KN._knn_point_parts(b, qx_, qy_, qc_, 0.0, nb,
                                          GRID.n, False)
            return d, e

        d, e = jax.vmap(parts)(qx, qy, qc)
        fn = getattr(KN, fast_fn)
        _, exact = jax.vmap(lambda d_, e_: fn(b.obj_id, d_, e_, K, m))(d, e)
        exact = np.asarray(exact)
        assert not exact[0] and exact[1:].all(), exact

        multi = knn_point_multi(b, qx, qy, qc, 0.0, nb, n=GRID.n, k=K,
                                strategy=strategy)
        oracle = knn_point_multi(b, qx, qy, qc, 0.0, nb, n=GRID.n, k=K,
                                 strategy="sort")
        np.testing.assert_array_equal(np.asarray(multi.obj_id),
                                      np.asarray(oracle.obj_id))
        np.testing.assert_allclose(np.asarray(multi.dist),
                                   np.asarray(oracle.dist))

    def test_q1_matches_single(self):
        """A 1-query batch is the single kernel with an extra axis."""
        b = _batch(seed=5)
        qx, qy, qc = _queries(q=1, seed=6)
        nb = GRID.candidate_layers(RADIUS)
        multi = knn_point_multi(b, qx, qy, qc, RADIUS, nb, n=GRID.n, k=K)
        single = knn_point(b, float(qx[0]), float(qy[0]), int(qc[0]),
                           RADIUS, nb, n=GRID.n, k=K)
        np.testing.assert_array_equal(np.asarray(multi.obj_id[0]),
                                      np.asarray(single.obj_id))


class TestRangeMulti:
    @pytest.mark.parametrize("approximate", (False, True))
    def test_matches_single_query_loop(self, approximate):
        b = _batch(seed=7)
        qx, qy, qc = _queries(q=5, seed=8)
        gn = GRID.guaranteed_layers(RADIUS)
        cn = GRID.candidate_layers(RADIUS)
        masks, dists, gn_c, evals = range_filter_point_multi(
            b, qx, qy, qc, RADIUS, gn, cn, n=GRID.n, approximate=approximate)
        for q in range(len(qx)):
            m1, d1, g1, e1 = range_filter_point_stats(
                b, float(qx[q]), float(qy[q]), int(qc[q]), RADIUS, gn, cn,
                n=GRID.n, approximate=approximate)
            np.testing.assert_array_equal(np.asarray(masks[q]),
                                          np.asarray(m1))
            np.testing.assert_allclose(np.asarray(dists[q]), np.asarray(d1))
            assert int(gn_c[q]) == int(g1) and int(evals[q]) == int(e1)


class TestMultiEdgeCases:
    """Padding/degenerate boundaries: multi must agree with a single-query
    loop when the window is smaller than k, nothing is eligible, or sizes
    land on odd bucket boundaries."""

    @pytest.mark.parametrize("n,k,strategy", [
        (3, 5, "sort"),            # window smaller than k
        (7, 5, "prefilter"),       # m > n clamps
        (16, 5, "approx_verified"),
        (130, 7, "grouped"),       # non-power-of-two across groups
    ])
    def test_tiny_and_odd_sizes(self, n, k, strategy):
        b = _batch(n=n, seed=n, oid_mod=max(2, n // 2))
        qx, qy, qc = _queries(q=3, seed=n + 1)
        nb = GRID.n
        multi = knn_point_multi(b, qx, qy, qc, 0.0, nb, n=GRID.n, k=k,
                                strategy=strategy)
        for q in range(3):
            single = knn_point(b, float(qx[q]), float(qy[q]), int(qc[q]),
                               0.0, nb, n=GRID.n, k=k, strategy=strategy)
            np.testing.assert_array_equal(np.asarray(multi.obj_id[q]),
                                          np.asarray(single.obj_id))

    def test_nothing_eligible(self):
        """Radius pruning that excludes every point for every query: all
        rows come back invalid, no NaNs/garbage ids."""
        b = _batch(n=64, seed=2)
        # queries far outside every point's candidate layers
        qx = np.asarray([115.51, 115.52], np.float32)
        qy = np.asarray([39.61, 39.62], np.float32)
        qc = np.asarray([GRID.assign_cell(float(x), float(y))[0]
                         for x, y in zip(qx, qy)], np.int32)
        res = knn_point_multi(b, qx, qy, qc, 0.01, 0, n=GRID.n, k=K)
        assert not np.asarray(res.valid).any()

    def test_random_parity_sweep(self):
        """Randomized multi-vs-single parity across sizes/Q/strategies —
        padding boundaries are where vmapped reshapes break first."""
        rng = np.random.default_rng(99)
        for trial in range(6):
            n = int(rng.integers(8, 3000))
            q = int(rng.integers(1, 9))
            k = int(rng.integers(1, 12))
            strategy = ("sort", "grouped", "prefilter",
                        "approx_verified")[trial % 4]
            b = _batch(n=n, seed=1000 + trial, oid_mod=max(2, n // 3))
            qx, qy, qc = _queries(q=q, seed=2000 + trial)
            multi = knn_point_multi(b, qx, qy, qc, RADIUS,
                                    GRID.candidate_layers(RADIUS),
                                    n=GRID.n, k=k, strategy=strategy)
            for qi in range(q):
                single = knn_point(b, float(qx[qi]), float(qy[qi]),
                                   int(qc[qi]), RADIUS,
                                   GRID.candidate_layers(RADIUS),
                                   n=GRID.n, k=k, strategy=strategy)
                np.testing.assert_array_equal(
                    np.asarray(multi.obj_id[qi]), np.asarray(single.obj_id),
                    err_msg=f"trial={trial} n={n} q={q} k={k} {strategy}")


def _geom_stream(n=200, seed=31):
    from spatialflink_tpu.models import LineString, Polygon

    rng = np.random.default_rng(seed)
    t0 = 1_700_000_000_000
    out = []
    for i in range(n):
        cx = float(rng.uniform(116.0, 117.0))
        cy = float(rng.uniform(40.0, 41.0))
        w = float(rng.uniform(0.01, 0.05))
        if i % 3:
            out.append(Polygon.create(
                [[(cx - w, cy - w), (cx + w, cy - w), (cx + w, cy + w),
                  (cx - w, cy + w), (cx - w, cy - w)]], GRID,
                obj_id=f"g{i % 41}", timestamp=t0 + i * 60))
        else:
            out.append(LineString.create(
                [(cx - w, cy), (cx, cy + w), (cx + w, cy)], GRID,
                obj_id=f"g{i % 41}", timestamp=t0 + i * 60))
    return out


def _stream(n=600, seed=11):
    rng = np.random.default_rng(seed)
    t0 = 1_700_000_000_000
    return [Point.create(float(rng.uniform(116.0, 117.0)),
                         float(rng.uniform(40.0, 41.0)), GRID,
                         obj_id=f"v{i % 37}", timestamp=t0 + i * 40)
            for i in range(n)]


class TestOperatorMulti:
    def _conf(self):
        return QueryConfiguration(QueryType.WindowBased, 10_000, 5_000)

    def _qpoints(self, q=4):
        rng = np.random.default_rng(12)
        return [Point.create(float(rng.uniform(116.2, 116.8)),
                             float(rng.uniform(40.2, 40.8)), GRID)
                for _ in range(q)]

    def test_knn_run_multi_matches_run_loop(self):
        qs = self._qpoints()
        multi = list(PointPointKNNQuery(self._conf(), GRID).run_multi(
            _stream(), qs, RADIUS, K))
        singles = [list(PointPointKNNQuery(self._conf(), GRID).run(
            _stream(), q, RADIUS, K)) for q in qs]
        assert multi and multi[0].extras["queries"] == len(qs)
        for w, res in enumerate(multi):
            assert len(res.records) == len(qs)
            for qi in range(len(qs)):
                ref = singles[qi][w]
                assert res.window_start == ref.window_start
                assert res.records[qi] == ref.records

    def test_range_run_multi_matches_run_loop(self):
        qs = self._qpoints()
        multi = list(PointPointRangeQuery(self._conf(), GRID).run_multi(
            _stream(), qs, RADIUS))
        singles = [list(PointPointRangeQuery(self._conf(), GRID).run(
            _stream(), q, RADIUS)) for q in qs]
        for w, res in enumerate(multi):
            for qi in range(len(qs)):
                ref = singles[qi][w]
                assert res.window_start == ref.window_start
                assert ([r.obj_id for r in res.records[qi]]
                        == [r.obj_id for r in ref.records])

    def test_realtime_suppresses_all_empty_micro_batches(self):
        """The reference's fire-per-element trigger never emits empties;
        the multi path's list-of-Q-lists result is always truthy, so the
        suppression must look inside (operators/base.py _multi_results)."""
        conf = QueryConfiguration(QueryType.RealTime, 10_000, 5_000,
                                  realtime_batch_size=64)
        far = [Point.create(115.55, 39.65, GRID)]  # nothing within radius
        out = list(PointPointRangeQuery(conf, GRID).run_multi(
            _stream(), far, 0.01))
        assert out == []
        # a query batch where SOME query matches still emits (with empty
        # rows for the non-matching queries)
        mixed = far + [Point.create(116.5, 40.5, GRID)]
        out = list(PointPointRangeQuery(conf, GRID).run_multi(
            _stream(), mixed, 0.5))
        assert out and all(len(r.records) == 2 for r in out)
        assert any(r.records[1] for r in out)
        conf2 = QueryConfiguration(QueryType.RealTime, 10_000, 5_000,
                                   realtime_batch_size=64)
        assert list(PointPointKNNQuery(conf2, GRID).run_multi(
            _stream(), far, 0.0, K))  # kNN has no radius filter -> emits

    def test_knn_run_multi_feeds_distance_counter(self):
        from spatialflink_tpu.utils.metrics import REGISTRY

        before = REGISTRY.counter("distance-computations").count
        list(PointPointKNNQuery(self._conf(), GRID).run_multi(
            _stream(), self._qpoints(3), RADIUS, K))
        assert REGISTRY.counter("distance-computations").count > before

    def _qpolys(self, q=3):
        from spatialflink_tpu.models import Polygon

        rng = np.random.default_rng(21)
        out = []
        for _ in range(q):
            cx = float(rng.uniform(116.2, 116.8))
            cy = float(rng.uniform(40.2, 40.8))
            w = float(rng.uniform(0.05, 0.2))
            out.append(Polygon.create(
                [[(cx - w, cy - w), (cx + w, cy - w), (cx + w, cy + w),
                  (cx - w, cy + w), (cx - w, cy - w)]], GRID))
        return out

    @pytest.mark.parametrize("approximate", (False, True))
    def test_geom_query_run_multi_matches_run_loop(self, approximate):
        from spatialflink_tpu.operators import (
            PointPolygonKNNQuery as PointGeomKNNQuery,
        )

        def conf():
            return QueryConfiguration(QueryType.WindowBased, 10_000, 5_000,
                                      approximate=approximate)

        polys = self._qpolys()
        multi = list(PointGeomKNNQuery(conf(), GRID).run_multi(
            _stream(), polys, RADIUS, K))
        singles = [list(PointGeomKNNQuery(conf(), GRID).run(
            _stream(), p, RADIUS, K)) for p in polys]
        assert multi and multi[0].extras["queries"] == len(polys)
        for w, res in enumerate(multi):
            for qi in range(len(polys)):
                ref = singles[qi][w]
                assert res.window_start == ref.window_start
                assert res.records[qi] == ref.records

    def _geom_stream(self, n=200, seed=31):
        return _geom_stream(n, seed)

    @staticmethod
    def _assert_query_parity(multi_recs, single_recs, approximate):
        """Exact mode is bit-for-bit (both paths run the same jitted
        kernels); approximate mode allows 1-ulp distance drift — the
        single-query operator computes its bbox distances eagerly while the
        multi kernel fuses them inside one jit, and XLA fusion may round
        differently. Membership and order must still agree."""
        if not approximate:
            assert multi_recs == single_recs
            return
        assert [oid for oid, _ in multi_recs] == [o for o, _ in single_recs]
        np.testing.assert_allclose([d for _, d in multi_recs],
                                   [d for _, d in single_recs], rtol=1e-6)

    @pytest.mark.parametrize("approximate", (False, True))
    def test_geom_stream_point_query_run_multi(self, approximate):
        from spatialflink_tpu.operators import PolygonPointKNNQuery

        def conf():
            return QueryConfiguration(QueryType.WindowBased, 10_000, 5_000,
                                      approximate=approximate)

        qs = self._qpoints(3)
        multi = list(PolygonPointKNNQuery(conf(), GRID).run_multi(
            self._geom_stream(), qs, RADIUS, K))
        singles = [list(PolygonPointKNNQuery(conf(), GRID).run(
            self._geom_stream(), q, RADIUS, K)) for q in qs]
        assert multi
        for w, res in enumerate(multi):
            for qi in range(len(qs)):
                self._assert_query_parity(res.records[qi],
                                          singles[qi][w].records, approximate)

    @pytest.mark.parametrize("approximate", (False, True))
    def test_geom_stream_geom_query_run_multi(self, approximate):
        from spatialflink_tpu.operators import PolygonPolygonKNNQuery

        def conf():
            return QueryConfiguration(QueryType.WindowBased, 10_000, 5_000,
                                      approximate=approximate)

        qs = self._qpolys(3)
        multi = list(PolygonPolygonKNNQuery(conf(), GRID).run_multi(
            self._geom_stream(), qs, RADIUS, K))
        singles = [list(PolygonPolygonKNNQuery(conf(), GRID).run(
            self._geom_stream(), q, RADIUS, K)) for q in qs]
        assert multi
        for w, res in enumerate(multi):
            for qi in range(len(qs)):
                self._assert_query_parity(res.records[qi],
                                          singles[qi][w].records, approximate)

    @pytest.mark.parametrize("approximate", (False, True))
    def test_range_geom_query_run_multi(self, approximate):
        """Point stream x Q polygon queries (range)."""
        from spatialflink_tpu.operators import PointPolygonRangeQuery

        def conf():
            return QueryConfiguration(QueryType.WindowBased, 10_000, 5_000,
                                      approximate=approximate)

        qs = self._qpolys(3)
        multi = list(PointPolygonRangeQuery(conf(), GRID).run_multi(
            _stream(), qs, RADIUS))
        singles = [list(PointPolygonRangeQuery(conf(), GRID).run(
            _stream(), q, RADIUS)) for q in qs]
        assert multi and multi[0].extras["queries"] == 3
        for w, res in enumerate(multi):
            for qi in range(len(qs)):
                assert ([r.obj_id for r in res.records[qi]]
                        == [r.obj_id for r in singles[qi][w].records])

    @pytest.mark.parametrize("approximate", (False, True))
    def test_range_geom_stream_point_query_run_multi(self, approximate):
        """Polygon/linestring stream x Q point queries (range, GN-subset
        rule per query)."""
        from spatialflink_tpu.operators import PolygonPointRangeQuery

        def conf():
            return QueryConfiguration(QueryType.WindowBased, 10_000, 5_000,
                                      approximate=approximate)

        qs = self._qpoints(3)
        multi = list(PolygonPointRangeQuery(conf(), GRID).run_multi(
            self._geom_stream(), qs, RADIUS))
        singles = [list(PolygonPointRangeQuery(conf(), GRID).run(
            self._geom_stream(), q, RADIUS)) for q in qs]
        assert multi
        for w, res in enumerate(multi):
            for qi in range(len(qs)):
                assert ([r.obj_id for r in res.records[qi]]
                        == [r.obj_id for r in singles[qi][w].records])

    @pytest.mark.parametrize("approximate", (False, True))
    def test_range_geom_stream_geom_query_run_multi(self, approximate):
        """Polygon/linestring stream x Q polygon queries (range)."""
        from spatialflink_tpu.operators import PolygonPolygonRangeQuery

        def conf():
            return QueryConfiguration(QueryType.WindowBased, 10_000, 5_000,
                                      approximate=approximate)

        qs = self._qpolys(3)
        multi = list(PolygonPolygonRangeQuery(conf(), GRID).run_multi(
            self._geom_stream(), qs, RADIUS))
        singles = [list(PolygonPolygonRangeQuery(conf(), GRID).run(
            self._geom_stream(), q, RADIUS)) for q in qs]
        assert multi
        for w, res in enumerate(multi):
            for qi in range(len(qs)):
                assert ([r.obj_id for r in res.records[qi]]
                        == [r.obj_id for r in singles[qi][w].records])

    def _mixed_queries(self):
        """One polygon + one linestring query — exercises the TRACED
        per-query is_areal flag in the multi kernels (the single-query
        kernels take it statically)."""
        from spatialflink_tpu.models import LineString

        polys = self._qpolys(1)
        ls = LineString.create([(116.55, 40.35), (116.7, 40.5),
                                (116.85, 40.65)], GRID)
        return polys + [ls]

    def test_mixed_areal_query_batch_knn(self):
        from spatialflink_tpu.operators import PointPolygonKNNQuery

        def conf():
            return QueryConfiguration(QueryType.WindowBased, 10_000, 5_000)

        qs = self._mixed_queries()
        multi = list(PointPolygonKNNQuery(conf(), GRID).run_multi(
            _stream(), qs, RADIUS, K))
        singles = [list(PointPolygonKNNQuery(conf(), GRID).run(
            _stream(), q, RADIUS, K)) for q in qs]
        assert multi
        for w, res in enumerate(multi):
            for qi in range(len(qs)):
                assert res.records[qi] == singles[qi][w].records, (w, qi)

    def test_mixed_areal_query_batch_range(self):
        from spatialflink_tpu.operators import PolygonPolygonRangeQuery

        def conf():
            return QueryConfiguration(QueryType.WindowBased, 10_000, 5_000)

        qs = self._mixed_queries()
        multi = list(PolygonPolygonRangeQuery(conf(), GRID).run_multi(
            self._geom_stream(), qs, RADIUS))
        singles = [list(PolygonPolygonRangeQuery(conf(), GRID).run(
            self._geom_stream(), q, RADIUS)) for q in qs]
        assert multi
        for w, res in enumerate(multi):
            for qi in range(len(qs)):
                assert ([r.obj_id for r in res.records[qi]]
                        == [r.obj_id for r in singles[qi][w].records]), (w, qi)

    def test_driver_multi_query_range_geom_option(self):
        """queryOption 21 (Polygon-Polygon range) routes through run_multi
        under multiQuery."""
        from spatialflink_tpu.config import Params
        from spatialflink_tpu.driver import run_option
        from spatialflink_tpu.streams.formats import serialize_spatial

        lines = [serialize_spatial(g, "WKT") for g in self._geom_stream(120)]
        p = Params.from_yaml("conf/spatialflink-conf.yml")
        p.query.option = 21
        p.query.radius = RADIUS
        p.query.multi_query = True
        p.query.query_polygons = [
            [(116.2, 40.2), (116.5, 40.2), (116.5, 40.5), (116.2, 40.2)],
            [(116.6, 40.6), (116.9, 40.6), (116.9, 40.9), (116.6, 40.6)],
        ]
        import dataclasses
        p = dataclasses.replace(
            p, input1=dataclasses.replace(p.input1, format="WKT"))
        wins = list(run_option(p, lines))
        assert wins and wins[0].extras["queries"] == 2
        assert all(len(w.records) == 2 for w in wins)

    def test_driver_multi_query_geom_stream_option(self):
        """queryOption 66 (Polygon-Point kNN) routes through run_multi under
        multiQuery."""
        from spatialflink_tpu.config import Params
        from spatialflink_tpu.driver import run_option
        from spatialflink_tpu.streams.formats import serialize_spatial

        lines = [serialize_spatial(g, "WKT")
                 for g in self._geom_stream(120)]
        p = Params.from_yaml("conf/spatialflink-conf.yml")
        p.query.option = 66
        p.query.radius = RADIUS
        p.query.k = K
        p.query.multi_query = True
        p.query.query_points = [(116.3, 40.3), (116.7, 40.7)]
        import dataclasses
        p = dataclasses.replace(
            p, input1=dataclasses.replace(p.input1, format="WKT"))
        wins = list(run_option(p, lines))
        assert wins and wins[0].extras["queries"] == 2
        assert all(len(w.records) == 2 for w in wins)

    def test_driver_multi_query_dispatch(self):
        """query.multiQuery answers ALL configured queryPoints through
        run_option; without it the driver keeps reference parity (first
        query object only)."""
        from spatialflink_tpu.config import Params
        from spatialflink_tpu.driver import run_option
        from spatialflink_tpu.streams.formats import serialize_spatial

        lines = [serialize_spatial(p, "GeoJSON") for p in _stream()]
        p = Params.from_yaml("conf/spatialflink-conf.yml")
        p.query.option = 51
        p.query.radius = RADIUS
        p.query.k = K
        p.query.multi_query = True
        p.query.query_points = [(116.3, 40.3), (116.7, 40.7)]
        multi = list(run_option(p, lines))
        assert multi and multi[0].extras["queries"] == 2
        p.query.multi_query = False
        first_only = list(run_option(p, lines))
        assert [w.records[0] for w in multi] == [w.records for w in first_only]

    @pytest.mark.parametrize("option", (101,   # join
                                        208,   # trajectory (taggregate)
                                        504,   # deser
                                        2))    # realtime range is fine; 2 IS
    def test_driver_multi_query_ineligible_family_errors(self, option):
        """Every ineligible family errors under multiQuery — a silent
        first-query fallback would misreport coverage. (Option 2, realtime
        PP range, IS eligible and must not raise.)"""
        from spatialflink_tpu.config import Params
        from spatialflink_tpu.driver import run_option

        p = Params.from_yaml("conf/spatialflink-conf.yml")
        p.query.option = option
        p.query.multi_query = True
        if option == 2:
            assert list(run_option(p, [])) == []
            return
        with pytest.raises(ValueError, match="multiQuery is not supported"):
            next(iter(run_option(p, [], [])))

    def test_driver_multi_query_config_and_cli_flag(self, tmp_path):
        from spatialflink_tpu.config import Params
        from spatialflink_tpu import driver as drv

        # YAML opt-in parses
        p = Params.from_yaml("conf/spatialflink-conf.yml")
        assert p.query.multi_query is False
        # the flag set in the config turns a CSV replay into multi-query
        # windows on the served path
        p.query.multi_query = True
        p.query.option = 1
        src = tmp_path / "pts.csv"
        src.write_text("a,1700000000000,116.5,40.5\n")
        import dataclasses
        p = dataclasses.replace(
            p, input1=dataclasses.replace(p.input1, format="CSV"))
        p.input1.date_format = None
        with open(src) as f:
            res = list(drv.run_option(p, f))
        assert res and res[0].extras["queries"] >= 1

    def test_driver_multi_query_empty_list_errors(self):
        from spatialflink_tpu.config import Params
        from spatialflink_tpu.driver import run_option

        p = Params.from_yaml("conf/spatialflink-conf.yml")
        p.query.option = 56  # Point-Polygon kNN
        p.query.multi_query = True
        p.query.query_polygons = []
        with pytest.raises(ValueError, match="queryPolygons is empty"):
            next(iter(run_option(p, [])))

    def test_cli_multi_query_output_flattens_per_query(self, tmp_path):
        """--output keeps its one-record-per-line contract under
        --multi-query (per-query lists are flattened)."""
        from spatialflink_tpu.driver import main
        from spatialflink_tpu.streams.formats import parse_spatial, serialize_spatial

        inp = tmp_path / "in.jsonl"
        inp.write_text("\n".join(
            serialize_spatial(p, "GeoJSON") for p in _stream(300)) + "\n")
        out = tmp_path / "res.wkt"
        rc = main(["--config", "conf/spatialflink-conf.yml",
                   "--input1", str(inp), "--option", "1", "--multi-query",
                   "--output", str(out), "--output-format", "WKT"])
        assert rc == 0
        lines = [ln for ln in out.read_text().splitlines() if ln]
        assert lines and all(ln.startswith("POINT") or "," in ln
                             for ln in lines)
        # every line parses back as a single spatial record
        for ln in lines[:5]:
            assert parse_spatial(ln, "WKT").obj_id is not None

    def test_bulk_multi_query_matches_record_path(self, tmp_path):
        """--multi-query over a CSV replay (the columnar decode): every
        query's per-window answer equals the oracle's (range: the records
        within the radius; kNN at radius 0, no cell pruning: the exact
        top-k)."""
        from spatialflink_tpu.config import Params
        from spatialflink_tpu.driver import run_option

        rng = np.random.default_rng(17)
        t0 = 1_700_000_000_000
        rows = [(f"v{i % 37}", t0 + i * 40,
                 round(float(rng.uniform(116, 117)), 6),
                 round(float(rng.uniform(40, 41)), 6)) for i in range(800)]
        src = tmp_path / "pts.csv"
        src.write_text("\n".join(f"{o},{t},{x:.6f},{y:.6f}"
                                 for o, t, x, y in rows) + "\n")
        qpts = [(116.3, 40.3), (116.7, 40.7)]

        def params(option):
            p = Params.from_yaml("conf/spatialflink-conf.yml")
            p.query.option = option
            p.query.radius = RADIUS if option == 1 else 0.0
            p.query.k = K
            p.query.multi_query = True
            p.query.query_points = [(116.3, 40.3), (116.7, 40.7)]
            import dataclasses
            p = dataclasses.replace(
                p, input1=dataclasses.replace(p.input1, format="CSV"))
            p.input1.date_format = None
            return p

        for option in (1, 51):
            with open(src) as f:
                got = list(run_option(params(option), f))
            assert got, option
            for qi, (qx, qy) in enumerate(qpts):
                if option == 1:
                    want = O.range_window_table(rows, qx, qy, RADIUS,
                                                10_000, 5_000)
                    assert {w.window_start: sorted(
                                (p.obj_id, p.timestamp)
                                for p in w.records[qi])
                            for w in got if w.records[qi]} == want, qi
                    continue
                want = O.knn_window_table(rows, qx, qy, K, 10_000, 5_000)
                assert {w.window_start for w in got} == set(want)
                for w in got:
                    assert w.extras["queries"] == 2
                    ids, dists = want[w.window_start]
                    assert [o for o, _ in w.records[qi]] == ids, qi
                    np.testing.assert_allclose(
                        [d for _, d in w.records[qi]], dists, atol=1e-4)

    def test_tknn_run_multi_matches_run_loop(self):
        from spatialflink_tpu.operators import PointPointTKNNQuery

        qs = self._qpoints(3)
        multi = list(PointPointTKNNQuery(self._conf(), GRID).run_multi(
            _stream(), qs, RADIUS, K))
        singles = [list(PointPointTKNNQuery(self._conf(), GRID).run(
            _stream(), q, RADIUS, K)) for q in qs]
        assert multi and multi[0].extras["queries"] == 3
        hits = 0
        for w, res in enumerate(multi):
            for qi in range(len(qs)):
                ref = singles[qi][w].records
                got = res.records[qi]
                assert [(o, d) for o, d, _s in got] \
                    == [(o, d) for o, d, _s in ref], (w, qi)
                # sub-trajectories identical by value (assembled from the
                # union set in multi, per-query in single — same per-id
                # contents; fresh objects each run, so compare coords)
                def _coords(s):
                    if s is None:
                        return None
                    if hasattr(s, "coords_list"):
                        return [tuple(c) for c in s.coords_list]
                    return (s.x, s.y)

                for (_, _, s_got), (_, _, s_ref) in zip(got, ref):
                    assert _coords(s_got) == _coords(s_ref)
                hits += len(got)
        assert hits > 0  # the exact-radius rule left something to compare

    def test_driver_multi_query_tknn_options(self):
        from spatialflink_tpu.config import Params
        from spatialflink_tpu.driver import run_option
        from spatialflink_tpu.streams.formats import serialize_spatial

        lines = [serialize_spatial(p, "GeoJSON") for p in _stream(400)]
        for option in (211, 212):
            p = Params.from_yaml("conf/spatialflink-conf.yml")
            p.query.option = option
            p.query.radius = RADIUS
            p.query.k = K
            p.query.multi_query = True
            p.query.query_points = [(116.3, 40.3), (116.7, 40.7)]
            wins = list(run_option(p, lines))
            assert wins and wins[0].extras["queries"] == 2, option
        # the naive twin refuses the flag (it exists to oracle the single
        # pruned path)
        p.query.option = 2011
        with pytest.raises(ValueError, match="naive-twin"):
            next(iter(run_option(p, lines)))

    @pytest.mark.parametrize("option", (6,    # Point-Polygon range
                                        56,   # Point-Polygon kNN
                                        16,   # Polygon-Point range
                                        71,   # Polygon-Polygon kNN
                                        ))
    def test_bulk_multi_geometry_cases_match_record_path(self, option,
                                                         tmp_path):
        """The --multi-query matrix over geometry: geometry queries over
        point streams and geometry streams, served from the raw text
        (columnar decode for CSV points), agree with the same run over
        per-record parsed objects."""
        import dataclasses

        from spatialflink_tpu.config import Params
        from spatialflink_tpu.driver import CASES, run_option
        from spatialflink_tpu.streams.formats import (parse_spatial,
                                                      serialize_spatial)

        spec = CASES[option]
        src = tmp_path / "stream.txt"
        if spec.stream == "Point":
            rng = np.random.default_rng(41)
            t0 = 1_700_000_000_000
            line_ids = [f"v{i % 37}" for i in range(600)]
            src.write_text("\n".join(
                f"{line_ids[i]},{t0 + i * 40},{rng.uniform(116, 117):.6f},"
                f"{rng.uniform(40, 41):.6f}" for i in range(600)) + "\n")
            fmt = "CSV"
        else:
            geoms = self._geom_stream(200)
            line_ids = [g.obj_id for g in geoms]
            src.write_text("\n".join(
                serialize_spatial(g, "WKT") for g in geoms) + "\n")
            fmt = "WKT"

        def params():
            p = Params.from_yaml("conf/spatialflink-conf.yml")
            p.query.option = option
            p.query.radius = RADIUS
            p.query.k = K
            p.query.multi_query = True
            p.query.query_points = [(116.3, 40.3), (116.7, 40.7)]
            p.query.query_polygons = [
                [(116.2, 40.2), (116.6, 40.2), (116.6, 40.6), (116.2, 40.2)],
                [(116.5, 40.5), (116.9, 40.5), (116.9, 40.9), (116.5, 40.5)],
            ]
            p = dataclasses.replace(
                p, input1=dataclasses.replace(p.input1, format=fmt))
            p.input1.date_format = None
            return p

        with open(src) as f:
            served = list(run_option(params(), f))
        grid = params().grids()[0]
        with open(src) as f:
            objs = [parse_spatial(ln.rstrip("\n"), fmt, grid,
                                  date_format=None,
                                  geometry=spec.stream) for ln in f]
        rec = list(run_option(params(), iter(objs)))
        assert served and len(served) == len(rec), option
        assert len({o.obj_id for o in objs}) == len(set(line_ids))
        for b, r in zip(served, rec):
            assert b.window_start == r.window_start
            assert b.extras["queries"] == 2
            if spec.family == "knn":
                # geometry queries produce mass ties at distance 0 (points
                # INSIDE the polygon); top-k of ties has no canonical
                # member set, and the two batch layouts break ties
                # differently — distances must agree exactly, members only
                # where untied
                for bq, rq in zip(b.records, r.records):
                    assert [d for _, d in bq] == [d for _, d in rq], option
            else:
                # per-query obj_id MULTISETS (counts alone would pass a
                # transposed mask)
                for bq, rq in zip(b.records, r.records):
                    assert sorted(p.obj_id for p in bq) == \
                        sorted(p.obj_id for p in rq), option

    def test_cli_multi_query_flag(self, tmp_path, capsys):
        """--multi-query end-to-end through driver.main: the window summary
        carries per_query_counts for the configured queryPoints."""
        import ast

        from spatialflink_tpu.driver import main
        from spatialflink_tpu.streams.formats import serialize_spatial

        inp = tmp_path / "in.jsonl"
        inp.write_text("\n".join(
            serialize_spatial(p, "GeoJSON") for p in _stream(300)) + "\n")
        rc = main(["--config", "conf/spatialflink-conf.yml",
                   "--input1", str(inp), "--option", "1", "--multi-query"])
        assert rc == 0
        cap = capsys.readouterr()
        summaries = [ast.literal_eval(ln) for ln in cap.out.splitlines()
                     if ln.startswith("{")]
        assert summaries
        # conf/spatialflink-conf.yml configures one queryPoint; the summary
        # shape still proves the multi path ran end-to-end
        assert all("per_query_counts" in s and s["queries"] >= 1
                   for s in summaries)

    @pytest.mark.parametrize("op_kind,hosts", [
        ("range", None), ("knn", None), ("geom_knn", None),
        ("geom_range", None), ("tknn", None),
        # 2-D (hosts x chips) mesh drives the per-query merge's DCN level
        ("range", 2), ("knn", 2),
    ])
    def test_run_multi_mesh_matches_1dev(self, op_kind, hosts):
        """Multi-query composes with the mesh: 8-device (and 2-D
        hosts x chips) runs match single-device bit-for-bit across operator
        families (the same vmapped kernels run per shard; per-query
        partials merge with collectives)."""
        from spatialflink_tpu.operators import (
            PointPointTKNNQuery,
            PolygonPolygonRangeQuery,
            PointPolygonKNNQuery,
        )

        def conf(devices=None):
            return QueryConfiguration(QueryType.WindowBased, 10_000, 5_000,
                                      devices=devices,
                                      hosts=hosts if devices else None)

        def run(devices):
            if op_kind == "range":
                return [
                    [[r.obj_id for r in q] for q in w.records]
                    for w in PointPointRangeQuery(conf(devices), GRID)
                    .run_multi(_stream(), self._qpoints(3), RADIUS)]
            if op_kind == "knn":
                return [w.records for w in
                        PointPointKNNQuery(conf(devices), GRID).run_multi(
                            _stream(), self._qpoints(3), RADIUS, K)]
            if op_kind == "geom_knn":
                return [w.records for w in
                        PointPolygonKNNQuery(conf(devices), GRID).run_multi(
                            _stream(), self._qpolys(2), RADIUS, K)]
            if op_kind == "geom_range":
                return [
                    [[r.obj_id for r in q] for q in w.records]
                    for w in PolygonPolygonRangeQuery(conf(devices), GRID)
                    .run_multi(self._geom_stream(), self._qpolys(2), RADIUS)]
            return [
                [[(o, d) for o, d, _s in q] for q in w.records]
                for w in PointPointTKNNQuery(conf(devices), GRID).run_multi(
                    _stream(), self._qpoints(2), RADIUS, K)]

        from spatialflink_tpu.utils.metrics import REGISTRY

        single = run(None)
        degradations = REGISTRY.counter("mesh-degradations").count
        mesh = run(8)
        # a RuntimeError in the distributed path would silently degrade the
        # mesh to the single-device code and pass vacuously — assert the
        # mesh path actually ran
        assert REGISTRY.counter("mesh-degradations").count == degradations, \
            f"{op_kind}: mesh degraded — distributed multi path broken"
        assert single == mesh, op_kind



class TestCountModeComposition:
    def test_count_windows_compose_with_multi_query(self):
        """window.type COUNT + run_multi: Q queries per count window."""
        conf = QueryConfiguration(QueryType.CountBased, window_size_ms=60,
                                  slide_ms=30)
        qs = [Point.create(116.3, 40.3, GRID), Point.create(116.7, 40.7, GRID)]
        recs = _stream(300)
        out = list(PointPointKNNQuery(conf, GRID).run_multi(
            iter(recs), qs, RADIUS, K))
        assert len(out) == len(recs) // 30
        assert all(w.extras["queries"] == 2 for w in out)

    def test_geom_stream_realtime_multi(self):
        """Realtime micro-batch mode through a geometry-stream run_multi
        (the empty-suppression gate applies to the per-query lists)."""
        from spatialflink_tpu.operators import PolygonPointKNNQuery

        conf = QueryConfiguration(QueryType.RealTime, 10_000, 5_000,
                                  realtime_batch_size=64)
        qs = [Point.create(116.3, 40.3, GRID), Point.create(116.7, 40.7, GRID)]
        geoms = _geom_stream(150)
        out = list(PolygonPointKNNQuery(conf, GRID).run_multi(
            iter(geoms), qs, RADIUS, K))
        assert out and all(len(w.records) == 2 for w in out)

    def test_incremental_refuses_count_mode(self):
        conf = QueryConfiguration(QueryType.CountBased, 40, 15)
        with pytest.raises(NotImplementedError, match="temporal slide"):
            next(iter(PointPointRangeQuery(conf, GRID).run_incremental(
                iter(_stream(60)), Point.create(116.5, 40.5, GRID), 0.3)))

    def test_count_windows_compose_with_mesh(self):
        """window.type COUNT + conf.devices: count-window batches shard over
        the mesh like time windows — 8-dev ≡ 1-dev, no degradation."""
        from spatialflink_tpu.utils.metrics import REGISTRY

        def run(devices):
            conf = QueryConfiguration(QueryType.CountBased, window_size_ms=60,
                                      slide_ms=30, devices=devices)
            qs = [Point.create(116.3, 40.3, GRID),
                  Point.create(116.7, 40.7, GRID)]
            return [w.records for w in
                    PointPointKNNQuery(conf, GRID).run_multi(
                        iter(_stream(300)), qs, RADIUS, K)]

        single = run(None)
        degr = REGISTRY.counter("mesh-degradations").count
        mesh = run(8)
        assert REGISTRY.counter("mesh-degradations").count == degr
        assert single == mesh and len(single) == 10
