"""Distributed kernels on the 8-device virtual CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import PointBatch
from spatialflink_tpu.ops.knn import knn_point
from spatialflink_tpu.parallel import (
    distributed_join_counts,
    distributed_knn,
    distributed_range_count,
    make_mesh,
    shard_batch,
)
from spatialflink_tpu.parallel.mesh import cell_hash_order

GRID = UniformGrid(115.50, 117.60, 39.60, 41.10, num_grid_partitions=100)
QX, QY = 116.5, 40.5


def make_batch(n, seed=0):
    rng = np.random.default_rng(seed)
    return PointBatch.from_arrays(
        rng.uniform(115.5, 117.6, n),
        rng.uniform(39.6, 41.1, n),
        grid=GRID,
        obj_id=rng.integers(0, 200, n).astype(np.int32),
    )


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    return make_mesh(8)


class TestDistributedKnn:
    def test_matches_single_device(self, mesh):
        b = make_batch(2048)
        r = 0.3
        q_cell, _ = GRID.assign_cell(QX, QY)
        L = GRID.candidate_layers(r)
        single = knn_point(b, QX, QY, jnp.int32(q_cell), r, L, n=GRID.n, k=20)
        sharded = shard_batch(b, mesh)
        dist = distributed_knn(
            mesh, sharded, QX, QY, jnp.int32(int(q_cell)), r, L, n=GRID.n, k=20
        )
        np.testing.assert_allclose(
            np.asarray(dist.dist)[np.asarray(dist.valid)],
            np.asarray(single.dist)[np.asarray(single.valid)],
            atol=1e-5,
        )
        assert (np.asarray(dist.obj_id) == np.asarray(single.obj_id)).all()

    def test_strategy_threads_to_shards(self, mesh):
        """conf.approximate must behave the same at any parallelism: the
        per-shard strategy kwarg reaches knn_point (ADVICE round-2
        knn_query.py:58). On CPU approx_min_k is exact, so the distributed
        approx result must match the single-device approx result."""
        b = make_batch(2048)
        r = 0.3
        q_cell, _ = GRID.assign_cell(QX, QY)
        L = GRID.candidate_layers(r)
        single = knn_point(b, QX, QY, jnp.int32(q_cell), r, L,
                           n=GRID.n, k=20, strategy="approx")
        dist = distributed_knn(
            mesh, shard_batch(b, mesh), QX, QY, jnp.int32(int(q_cell)), r, L,
            n=GRID.n, k=20, strategy="approx",
        )
        assert np.asarray(dist.valid).sum() == np.asarray(single.valid).sum()
        np.testing.assert_allclose(
            np.sort(np.asarray(dist.dist)[np.asarray(dist.valid)]),
            np.sort(np.asarray(single.dist)[np.asarray(single.valid)]),
            atol=1e-5,
        )

    def test_cell_hash_order_preserves_results(self, mesh):
        b = make_batch(1024)
        idx = cell_hash_order(np.asarray(b.cell), 8)
        b_perm = jax.tree.map(lambda a: a[idx], b)
        q_cell, _ = GRID.assign_cell(QX, QY)
        r = 0.3
        L = GRID.candidate_layers(r)
        a1 = distributed_knn(mesh, shard_batch(b, mesh), QX, QY,
                             jnp.int32(int(q_cell)), r, L, n=GRID.n, k=10)
        a2 = distributed_knn(mesh, shard_batch(b_perm, mesh), QX, QY,
                             jnp.int32(int(q_cell)), r, L, n=GRID.n, k=10)
        np.testing.assert_allclose(np.asarray(a1.dist), np.asarray(a2.dist), atol=1e-5)


class TestDistributedRange:
    def test_count_matches_single_device(self, mesh):
        from spatialflink_tpu.ops.range import range_filter_point

        b = make_batch(2048, seed=5)
        r = 0.4
        q_cell, _ = GRID.assign_cell(QX, QY)
        mask, _ = range_filter_point(
            b, QX, QY, jnp.int32(q_cell), r,
            GRID.guaranteed_layers(r), GRID.candidate_layers(r), n=GRID.n,
        )
        count, dmask = distributed_range_count(
            mesh, shard_batch(b, mesh), QX, QY, jnp.int32(int(q_cell)), r,
            GRID.guaranteed_layers(r), GRID.candidate_layers(r), n=GRID.n,
        )
        assert int(count) == int(mask.sum())
        assert (np.asarray(dmask) == np.asarray(mask)).all()


class TestDistributedJoin:
    def test_total_matches_single_device(self, mesh):
        from spatialflink_tpu.ops.join import join_mask

        a = make_batch(1024, seed=7)
        b = make_batch(256, seed=8)
        r = 0.1
        L = GRID.candidate_layers(r)
        cx, cy = (GRID.min_x + GRID.max_x) / 2, (GRID.min_y + GRID.max_y) / 2
        m = np.asarray(join_mask(a, b, r, L, cx, cy, n=GRID.n))
        per_a, total = distributed_join_counts(
            mesh, shard_batch(a, mesh), b, r, L, cx, cy, n=GRID.n
        )
        assert int(total) == m.sum()
        assert (np.asarray(per_a) == m.sum(axis=1)).all()


class TestGraftEntry:
    def test_entry_compiles(self):
        import sys, os
        sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
        import __graft_entry__ as ge

        fn, args = ge.entry()
        out = jax.jit(fn)(*args)
        assert int(out.valid.sum()) > 0

    def test_dryrun_multichip(self, capsys):
        import __graft_entry__ as ge

        ge.dryrun_multichip(8)
        assert "ok" in capsys.readouterr().out


def test_hierarchical_knn_matches_single_device():
    """2-D (hosts, cells) mesh; two-level ICI->DCN merge must equal the
    single-device kernel."""
    from spatialflink_tpu.parallel import (
        distributed_knn_hierarchical,
        make_mesh_2d,
        shard_batch,
    )

    mesh = make_mesh_2d(2, 4)
    b = make_batch(512)
    sharded = shard_batch(b, mesh, axis=mesh.axis_names)
    qx, qy = 116.5, 40.5
    got = distributed_knn_hierarchical(
        mesh, sharded, qx, qy, jnp.int32(0), 0.0, GRID.n, n=GRID.n, k=10)
    want = knn_point(b, qx, qy, jnp.int32(0), 0.0, GRID.n, n=GRID.n, k=10)
    np.testing.assert_array_equal(np.asarray(got.valid), np.asarray(want.valid))
    np.testing.assert_allclose(
        np.asarray(got.dist)[np.asarray(got.valid)],
        np.asarray(want.dist)[np.asarray(want.valid)], atol=0)


def test_make_mesh_2d_shape_and_axes():
    from spatialflink_tpu.parallel import make_mesh_2d

    mesh = make_mesh_2d(4, 2)
    assert mesh.axis_names == ("hosts", "cells")
    assert mesh.devices.shape == (4, 2)


def test_make_mesh_2d_rejects_oversubscription():
    from spatialflink_tpu.parallel import make_mesh_2d

    with pytest.raises(ValueError):
        make_mesh_2d(16)  # 16 hosts on an 8-device pool -> inner axis would be 0
    with pytest.raises(ValueError):
        make_mesh_2d(4, 4)


def test_init_distributed_noop_single_process():
    from spatialflink_tpu.parallel import init_distributed

    init_distributed()  # no coordinator configured -> must be a silent no-op


class TestOperatorDistributedDispatch:
    """Mesh-aware operator mode (conf.devices): the driver-reachable path
    must match the single-device path bit-for-bit on the 8-device mesh."""

    def _points(self, n, seed):
        from spatialflink_tpu.models import Point

        rng = np.random.default_rng(seed)
        t0 = 1_700_000_000_000
        return [
            Point.create(float(rng.uniform(115.6, 117.5)),
                         float(rng.uniform(39.7, 41.0)), GRID,
                         obj_id=f"o{i % 97}", timestamp=t0 + i * 10)
            for i in range(n)
        ]

    def _conf(self, devices=None):
        from spatialflink_tpu.operators import QueryConfiguration, QueryType

        return QueryConfiguration(QueryType.WindowBased, window_size_ms=10_000,
                                  slide_ms=5_000, devices=devices)

    def test_range_matches_single_device(self):
        from spatialflink_tpu.models import Point
        from spatialflink_tpu.operators import PointPointRangeQuery

        pts = self._points(3000, 31)
        q = Point.create(QX, QY, GRID)
        r1 = list(PointPointRangeQuery(self._conf(), GRID).run(
            iter(pts), q, 0.4))
        r8 = list(PointPointRangeQuery(self._conf(8), GRID).run(
            iter(pts), q, 0.4))
        assert [w.window_start for w in r1] == [w.window_start for w in r8]
        for a, b in zip(r1, r8):
            assert [(p.obj_id, p.timestamp) for p in a.records] == \
                   [(p.obj_id, p.timestamp) for p in b.records]

    def test_knn_matches_single_device(self):
        from spatialflink_tpu.models import Point
        from spatialflink_tpu.operators import PointPointKNNQuery

        pts = self._points(3000, 32)
        q = Point.create(QX, QY, GRID)
        r1 = list(PointPointKNNQuery(self._conf(), GRID).run(
            iter(pts), q, 0.5, 15))
        r8 = list(PointPointKNNQuery(self._conf(8), GRID).run(
            iter(pts), q, 0.5, 15))
        assert len(r1) == len(r8)
        for a, b in zip(r1, r8):
            assert [o for o, _ in a.records] == [o for o, _ in b.records]
            np.testing.assert_array_equal(
                np.array([d for _, d in a.records]),
                np.array([d for _, d in b.records]))

    def test_join_matches_single_device(self):
        from spatialflink_tpu.operators import PointPointJoinQuery

        a = self._points(1500, 33)
        b = self._points(300, 34)
        r1 = list(PointPointJoinQuery(self._conf(), GRID).run(
            iter(a), iter(b), 0.2))
        r8 = list(PointPointJoinQuery(self._conf(8), GRID).run(
            iter(a), iter(b), 0.2))
        assert len(r1) == len(r8)
        for wa, wb in zip(r1, r8):
            pa = sorted((x.obj_id, x.timestamp, y.obj_id, y.timestamp)
                        for x, y in wa.records)
            pb = sorted((x.obj_id, x.timestamp, y.obj_id, y.timestamp)
                        for x, y in wb.records)
            assert pa == pb

    def test_driver_parallelism_dispatches_distributed(self, tmp_path):
        """End-to-end: query.parallelism in the YAML drives the mesh path
        through run_option and matches the single-device driver run."""
        import yaml

        from spatialflink_tpu.config import Params
        from spatialflink_tpu.driver import run_option

        with open("conf/spatialflink-conf.yml") as f:
            y = yaml.safe_load(f)
        y["query"]["option"] = 1
        y["query"]["radius"] = 0.4
        y["inputStream1"]["format"] = "CSV"
        y["inputStream1"]["csvTsvSchemaAttr"] = [0, 1, 2, 3]
        y["inputStream1"]["dateFormat"] = None
        pts = self._points(2000, 35)
        lines = [f"{p.obj_id},{p.timestamp},{p.x},{p.y}" for p in pts]
        single = list(run_option(Params.from_dict(y), iter(lines)))
        y["query"]["parallelism"] = 8
        dist = list(run_option(Params.from_dict(y), iter(lines)))
        assert [w.window_start for w in single] == [w.window_start for w in dist]
        for a, b in zip(single, dist):
            assert [(p.obj_id, p.timestamp) for p in a.records] == \
                   [(p.obj_id, p.timestamp) for p in b.records]

    def test_non_power_of_two_devices_rejected(self):
        from spatialflink_tpu.operators import PointPointRangeQuery

        with pytest.raises(ValueError):
            PointPointRangeQuery(self._conf(3), GRID)

    def test_config_rejects_bad_parallelism(self):
        from spatialflink_tpu.config import ConfigError, QueryConfig

        with pytest.raises(ConfigError):
            QueryConfig.from_dict({"option": 1, "parallelism": 3})


class TestGeomStreamDistributedDispatch:
    """VERDICT r3 #4: geometry-stream operators must dispatch through the
    mesh like PointPoint — 8-dev results must equal 1-dev bit-for-bit."""

    def _polys(self, n, seed):
        from spatialflink_tpu.models import Polygon

        rng = np.random.default_rng(seed)
        t0 = 1_700_000_000_000
        out = []
        for i in range(n):
            cx = float(rng.uniform(115.7, 117.4))
            cy = float(rng.uniform(39.8, 40.9))
            w = float(rng.uniform(0.01, 0.08))
            out.append(Polygon.create(
                [[(cx - w, cy - w), (cx + w, cy - w), (cx + w, cy + w),
                  (cx - w, cy + w)]], GRID, obj_id=f"g{i % 61}",
                timestamp=t0 + i * 10))
        return out

    def _pts(self, n, seed):
        from spatialflink_tpu.models import Point

        rng = np.random.default_rng(seed)
        t0 = 1_700_000_000_000
        return [
            Point.create(float(rng.uniform(115.6, 117.5)),
                         float(rng.uniform(39.7, 41.0)), GRID,
                         obj_id=f"o{i % 97}", timestamp=t0 + i * 10)
            for i in range(n)
        ]

    def _conf(self, devices=None):
        from spatialflink_tpu.operators import QueryConfiguration, QueryType

        return QueryConfiguration(QueryType.WindowBased, window_size_ms=10_000,
                                  slide_ms=5_000, devices=devices)

    def _qpoly(self):
        from spatialflink_tpu.models import Polygon

        return Polygon.create(
            [[(116.3, 40.3), (116.7, 40.3), (116.7, 40.7), (116.3, 40.7)]],
            GRID)

    def test_geomgeom_range_matches_single_device(self):
        from spatialflink_tpu.operators import PolygonPolygonRangeQuery

        polys = self._polys(700, 41)
        q = self._qpoly()
        r1 = list(PolygonPolygonRangeQuery(self._conf(), GRID).run(
            iter(polys), q, 0.3))
        r8 = list(PolygonPolygonRangeQuery(self._conf(8), GRID).run(
            iter(polys), q, 0.3))
        assert [w.window_start for w in r1] == [w.window_start for w in r8]
        assert any(w.records for w in r1)
        for a, b in zip(r1, r8):
            assert [(g.obj_id, g.timestamp) for g in a.records] == \
                   [(g.obj_id, g.timestamp) for g in b.records]

    def test_geompoint_range_matches_single_device(self):
        from spatialflink_tpu.models import Point
        from spatialflink_tpu.operators import PolygonPointRangeQuery

        polys = self._polys(500, 42)
        q = Point.create(QX, QY, GRID)
        r1 = list(PolygonPointRangeQuery(self._conf(), GRID).run(
            iter(polys), q, 0.4))
        r8 = list(PolygonPointRangeQuery(self._conf(8), GRID).run(
            iter(polys), q, 0.4))
        assert any(w.records for w in r1)
        for a, b in zip(r1, r8):
            assert [(g.obj_id, g.timestamp) for g in a.records] == \
                   [(g.obj_id, g.timestamp) for g in b.records]

    def test_pointgeom_knn_matches_single_device(self):
        from spatialflink_tpu.operators import PointPolygonKNNQuery

        pts = self._pts(3000, 43)
        q = self._qpoly()
        r1 = list(PointPolygonKNNQuery(self._conf(), GRID).run(
            iter(pts), q, 0.5, 12))
        r8 = list(PointPolygonKNNQuery(self._conf(8), GRID).run(
            iter(pts), q, 0.5, 12))
        assert any(w.records for w in r1)
        for a, b in zip(r1, r8):
            assert [o for o, _ in a.records] == [o for o, _ in b.records]
            np.testing.assert_array_equal(
                np.array([d for _, d in a.records]),
                np.array([d for _, d in b.records]))

    def test_geomgeom_knn_matches_single_device(self):
        from spatialflink_tpu.operators import PolygonPolygonKNNQuery

        polys = self._polys(400, 44)
        q = self._qpoly()
        r1 = list(PolygonPolygonKNNQuery(self._conf(), GRID).run(
            iter(polys), q, 0.8, 9))
        r8 = list(PolygonPolygonKNNQuery(self._conf(8), GRID).run(
            iter(polys), q, 0.8, 9))
        assert any(w.records for w in r1)
        for a, b in zip(r1, r8):
            assert [o for o, _ in a.records] == [o for o, _ in b.records]
            np.testing.assert_array_equal(
                np.array([d for _, d in a.records]),
                np.array([d for _, d in b.records]))

    def test_pointgeom_join_matches_single_device(self):
        from spatialflink_tpu.operators import PointPolygonJoinQuery

        pts = self._pts(1200, 45)
        polys = self._polys(150, 46)
        r1 = list(PointPolygonJoinQuery(self._conf(), GRID).run(
            iter(pts), iter(polys), 0.15))
        r8 = list(PointPolygonJoinQuery(self._conf(8), GRID).run(
            iter(pts), iter(polys), 0.15))
        assert len(r1) == len(r8)
        assert any(w.records for w in r1)
        for wa, wb in zip(r1, r8):
            pa = sorted((x.obj_id, x.timestamp, y.obj_id, y.timestamp)
                        for x, y in wa.records)
            pb = sorted((x.obj_id, x.timestamp, y.obj_id, y.timestamp)
                        for x, y in wb.records)
            assert pa == pb

    def test_geomgeom_join_matches_single_device(self):
        from spatialflink_tpu.operators import PolygonPolygonJoinQuery

        a = self._polys(250, 47)
        b = self._polys(60, 48)
        r1 = list(PolygonPolygonJoinQuery(self._conf(), GRID).run(
            iter(a), iter(b), 0.1))
        r8 = list(PolygonPolygonJoinQuery(self._conf(8), GRID).run(
            iter(a), iter(b), 0.1))
        assert any(w.records for w in r1)
        for wa, wb in zip(r1, r8):
            pa = sorted((x.obj_id, x.timestamp, y.obj_id, y.timestamp)
                        for x, y in wa.records)
            pb = sorted((x.obj_id, x.timestamp, y.obj_id, y.timestamp)
                        for x, y in wb.records)
            assert pa == pb

    def test_config5_reachable_via_run_option_21(self):
        """BASELINE config 5 (polygon-polygon range on a mesh) through the
        driver: run_option(option=21, parallelism=8) — not bespoke bench
        code (VERDICT r3 missing #3)."""
        import yaml

        from spatialflink_tpu.config import Params
        from spatialflink_tpu.driver import run_option
        from spatialflink_tpu.streams.formats import serialize_spatial

        with open("conf/spatialflink-conf.yml") as f:
            y = yaml.safe_load(f)
        y["query"]["option"] = 21
        y["query"]["radius"] = 0.3
        y["query"]["queryPolygons"] = [
            [[116.3, 40.3], [116.7, 40.3], [116.7, 40.7], [116.3, 40.7]]]
        y["inputStream1"]["format"] = "WKT"
        y["inputStream1"]["dateFormat"] = None
        polys = self._polys(400, 49)
        lines = [f"{p.obj_id}, {p.timestamp}, {serialize_spatial(p, 'WKT')}"
                 for p in polys]
        single = list(run_option(Params.from_dict(y), iter(lines)))
        y["query"]["parallelism"] = 8
        dist = list(run_option(Params.from_dict(y), iter(lines)))
        assert any(w.records for w in single)
        assert [w.window_start for w in single] == [w.window_start for w in dist]
        for a, b in zip(single, dist):
            assert [(g.obj_id, g.timestamp) for g in a.records] == \
                   [(g.obj_id, g.timestamp) for g in b.records]

    def test_knn_small_window_shards_smaller_than_k(self):
        """Shard capacity < k must clamp+pad, not crash at trace time:
        20 polygons over 8 devices (pad 32, shard 4) with k=10."""
        from spatialflink_tpu.operators import PolygonPolygonKNNQuery

        polys = self._polys(20, 51)
        q = self._qpoly()
        r1 = list(PolygonPolygonKNNQuery(self._conf(), GRID).run(
            iter(polys), q, 5.0, 10))
        r8 = list(PolygonPolygonKNNQuery(self._conf(8), GRID).run(
            iter(polys), q, 5.0, 10))
        assert any(w.records for w in r1)
        for a, b in zip(r1, r8):
            assert [o for o, _ in a.records] == [o for o, _ in b.records]
            np.testing.assert_array_equal(
                np.array([d for _, d in a.records]),
                np.array([d for _, d in b.records]))


class TestTrajectoryDistributedDispatch:
    """Kernel-backed trajectory ops ride the mesh too (tJoin already goes
    through the distributed join): tRange containment and tKnn top-k must
    match single-device bit-for-bit at parallelism 8."""

    def _traj_pts(self, n, seed):
        from spatialflink_tpu.models import Point

        rng = np.random.default_rng(seed)
        t0 = 1_700_000_000_000
        return [
            Point.create(float(rng.uniform(115.6, 117.5)),
                         float(rng.uniform(39.7, 41.0)), GRID,
                         obj_id=f"t{i % 37}", timestamp=t0 + i * 10)
            for i in range(n)
        ]

    def _conf(self, devices=None, realtime=False):
        from spatialflink_tpu.operators import QueryConfiguration, QueryType

        return QueryConfiguration(
            QueryType.RealTime if realtime else QueryType.WindowBased,
            window_size_ms=10_000, slide_ms=5_000, devices=devices)

    def test_trange_matches_single_device(self):
        from spatialflink_tpu.models import Polygon
        from spatialflink_tpu.operators import PointPolygonTRangeQuery

        pts = self._traj_pts(2000, 61)
        polys = [Polygon.create(
            [[(116.2, 40.2), (116.9, 40.2), (116.9, 40.8), (116.2, 40.8)]],
            GRID)]
        r1 = list(PointPolygonTRangeQuery(self._conf(), GRID).run(
            iter(pts), polys))
        r8 = list(PointPolygonTRangeQuery(self._conf(8), GRID).run(
            iter(pts), polys))
        assert any(w.records for w in r1)
        assert [w.extras.get("matched_ids") for w in r1] == \
               [w.extras.get("matched_ids") for w in r8]

    def test_trange_realtime_matches_single_device(self):
        from spatialflink_tpu.models import Polygon
        from spatialflink_tpu.operators import PointPolygonTRangeQuery

        pts = self._traj_pts(1500, 62)
        polys = [Polygon.create(
            [[(116.2, 40.2), (116.9, 40.2), (116.9, 40.8), (116.2, 40.8)]],
            GRID)]
        r1 = list(PointPolygonTRangeQuery(self._conf(realtime=True), GRID).run(
            iter(pts), polys))
        r8 = list(PointPolygonTRangeQuery(self._conf(8, realtime=True), GRID)
                  .run(iter(pts), polys))
        assert any(w.records for w in r1)
        assert [[(p.obj_id, p.timestamp) for p in w.records] for w in r1] == \
               [[(p.obj_id, p.timestamp) for p in w.records] for w in r8]

    def test_tknn_matches_single_device(self):
        from spatialflink_tpu.models import Point
        from spatialflink_tpu.operators import PointPointTKNNQuery

        pts = self._traj_pts(2000, 63)
        q = Point.create(QX, QY, GRID)
        r1 = list(PointPointTKNNQuery(self._conf(), GRID).run(
            iter(pts), q, 0.5, 8))
        r8 = list(PointPointTKNNQuery(self._conf(8), GRID).run(
            iter(pts), q, 0.5, 8))
        assert any(w.records for w in r1)
        assert len(r1) == len(r8)
        for a, b in zip(r1, r8):
            assert [(o, d) for o, d, _ in a.records] == \
                   [(o, d) for o, d, _ in b.records]

    def _assert_tstats_parity(self, r1, r8):
        assert any(w.records for w in r1)
        assert len(r1) == len(r8)
        for a, b in zip(r1, r8):
            assert (a.window_start, a.window_end) == \
                   (b.window_start, b.window_end)
            # trajectory ids + integer temporal lengths: exact; spatial
            # sums/speeds: f32 summation order differs between the sharded
            # stitch and the single-device cumsum — last-ulp tolerance
            assert [t[0] for t in a.records] == [t[0] for t in b.records]
            assert [t[2] for t in a.records] == [t[2] for t in b.records]
            # observed ~5e-6 relative over ~10^2 f32 pair additions
            np.testing.assert_allclose([t[1] for t in a.records],
                                       [t[1] for t in b.records], rtol=2e-5)
            np.testing.assert_allclose([t[3] for t in a.records],
                                       [t[3] for t in b.records], rtol=2e-5)

    def test_tstats_windowed_matches_single_device(self):
        from spatialflink_tpu.operators import PointTStatsQuery

        pts = self._traj_pts(2000, 64)
        r1 = list(PointTStatsQuery(self._conf(), GRID).run(iter(pts)))
        r8 = list(PointTStatsQuery(self._conf(8), GRID).run(iter(pts)))
        self._assert_tstats_parity(r1, r8)

    def test_tstats_windowed_out_of_order_and_duplicates(self):
        """Shuffled arrival and exact (objID, ts) duplicates — including
        same-ts different-coords pairs — must not break the sharded
        stitch's global-sort precondition (host pre-sort + dedup)."""
        from spatialflink_tpu.models import Point
        from spatialflink_tpu.operators import PointTStatsQuery

        pts = self._traj_pts(1200, 65)
        rng = np.random.default_rng(9)
        extra = []
        for i in range(0, len(pts), 10):
            p = pts[i]
            extra.append(Point.create(p.x, p.y, GRID, obj_id=p.obj_id,
                                      timestamp=p.timestamp))
            extra.append(Point.create(p.x + 0.01, p.y, GRID, obj_id=p.obj_id,
                                      timestamp=p.timestamp))
        pts = pts + extra
        # mild shuffle (bounded displacement keeps the window assembly
        # identical concern-free: both runs see the SAME stream)
        for i in range(0, len(pts) - 8, 8):
            j = i + int(rng.integers(0, 8))
            pts[i], pts[j] = pts[j], pts[i]
        from spatialflink_tpu.operators import PointTStatsQuery as Q

        r1 = list(Q(self._conf(), GRID).run(iter(pts)))
        r8 = list(Q(self._conf(8), GRID).run(iter(pts)))
        self._assert_tstats_parity(r1, r8)

    @pytest.mark.parametrize("agg", ["SUM", "COUNT", "MIN", "MAX", "AVG"])
    def test_taggregate_windowed_heatmap_matches_single_device(self, agg):
        from spatialflink_tpu.operators import PointTAggregateQuery

        pts = self._traj_pts(2000, 66)
        r1 = list(PointTAggregateQuery(self._conf(), GRID).run(
            iter(pts), agg))
        r8 = list(PointTAggregateQuery(self._conf(8), GRID).run(
            iter(pts), agg))
        assert len(r1) == len(r8) > 0
        assert any(w.extras["heatmap"].any() for w in r1)
        for a, b in zip(r1, r8):
            assert (a.window_start, a.window_end) == \
                   (b.window_start, b.window_end)
            # group lengths are exact ints; per-cell reductions of them in
            # f32 are exact at window scale -> bit-for-bit
            np.testing.assert_array_equal(a.extras["heatmap"],
                                          b.extras["heatmap"])

    def test_taggregate_windowed_all_matches_single_device(self):
        from spatialflink_tpu.operators import PointTAggregateQuery

        pts = self._traj_pts(1500, 67)
        r1 = list(PointTAggregateQuery(self._conf(), GRID).run(
            iter(pts), "ALL"))
        r8 = list(PointTAggregateQuery(self._conf(8), GRID).run(
            iter(pts), "ALL"))
        assert len(r1) == len(r8) > 0
        assert any(w.records for w in r1)
        for a, b in zip(r1, r8):
            assert a.records == b.records


class TestRealtimeDistributedDispatch:
    """Realtime (micro-batch) mode through the mesh: identical output to the
    single-device realtime run for range and kNN."""

    def _pts(self, n, seed):
        from spatialflink_tpu.models import Point

        rng = np.random.default_rng(seed)
        t0 = 1_700_000_000_000
        return [
            Point.create(float(rng.uniform(115.6, 117.5)),
                         float(rng.uniform(39.7, 41.0)), GRID,
                         obj_id=f"o{i % 53}", timestamp=t0 + i * 10)
            for i in range(n)
        ]

    def _conf(self, devices=None):
        from spatialflink_tpu.operators import QueryConfiguration, QueryType

        return QueryConfiguration(QueryType.RealTime, window_size_ms=10_000,
                                  slide_ms=5_000, realtime_batch_size=256,
                                  devices=devices)

    def test_realtime_range_matches_single_device(self):
        from spatialflink_tpu.models import Point
        from spatialflink_tpu.operators import PointPointRangeQuery

        pts = self._pts(1200, 71)
        q = Point.create(QX, QY, GRID)
        r1 = list(PointPointRangeQuery(self._conf(), GRID).run(
            iter(pts), q, 0.4))
        r8 = list(PointPointRangeQuery(self._conf(8), GRID).run(
            iter(pts), q, 0.4))
        assert any(w.records for w in r1)
        assert [[(p.obj_id, p.timestamp) for p in w.records] for w in r1] == \
               [[(p.obj_id, p.timestamp) for p in w.records] for w in r8]

    def test_realtime_knn_matches_single_device(self):
        from spatialflink_tpu.models import Point
        from spatialflink_tpu.operators import PointPointKNNQuery

        pts = self._pts(1200, 72)
        q = Point.create(QX, QY, GRID)
        r1 = list(PointPointKNNQuery(self._conf(), GRID).run(
            iter(pts), q, 0.5, 10))
        r8 = list(PointPointKNNQuery(self._conf(8), GRID).run(
            iter(pts), q, 0.5, 10))
        assert len(r1) == len(r8) and any(w.records for w in r1)
        for a, b in zip(r1, r8):
            assert a.records == b.records


class TestElasticDegradedMode:
    """SURVEY §7 phase 7's elastic/degraded-mode story: a device failure
    during a distributed window halves the mesh and re-dispatches; at one
    device the single-device path takes over. Output must stay identical to
    an undisturbed single-device run; host state is untouched."""

    def _points(self, n, seed):
        from spatialflink_tpu.models import Point

        rng = np.random.default_rng(seed)
        t0 = 1_700_000_000_000
        return [
            Point.create(float(rng.uniform(115.6, 117.5)),
                         float(rng.uniform(39.7, 41.0)), GRID,
                         obj_id=f"o{i % 53}", timestamp=t0 + i * 10)
            for i in range(n)
        ]

    def _conf(self, devices=None):
        from spatialflink_tpu.operators import QueryConfiguration, QueryType

        return QueryConfiguration(QueryType.WindowBased, window_size_ms=10_000,
                                  slide_ms=5_000, devices=devices)

    def test_range_degrades_and_matches(self, monkeypatch):
        from spatialflink_tpu.models import Point
        from spatialflink_tpu.operators import PointPointRangeQuery
        from spatialflink_tpu.parallel import ops as pops
        from spatialflink_tpu.utils.metrics import REGISTRY

        pts = self._points(2000, 61)
        q = Point.create(QX, QY, GRID)
        r1 = list(PointPointRangeQuery(self._conf(), GRID).run(
            iter(pts), q, 0.4))

        real = pops.distributed_stream_filter
        failures = {"left": 2}

        def flaky(mesh, batch, fn):
            if failures["left"] > 0:
                failures["left"] -= 1
                raise RuntimeError("injected device loss (test)")
            return real(mesh, batch, fn)

        monkeypatch.setattr(pops, "distributed_stream_filter", flaky)
        before = REGISTRY.counter("mesh-degradations").count
        op = PointPointRangeQuery(self._conf(8), GRID)
        r8 = list(op.run(iter(pts), q, 0.4))
        assert REGISTRY.counter("mesh-degradations").count == before + 2
        assert op.conf.devices == 2  # 8 -> 4 -> 2, success at 2
        assert [w.window_start for w in r1] == [w.window_start for w in r8]
        for a, b in zip(r1, r8):
            assert [(p.obj_id, p.timestamp) for p in a.records] == \
                   [(p.obj_id, p.timestamp) for p in b.records]

    def test_knn_persistent_failure_raises_after_bounded_degradations(
            self, monkeypatch):
        """A PERSISTENT distributed failure must trip a loud error after the
        elastic halvings run out (8 -> 4 -> 2, then refuse the final halving
        to 1) — never a permanent silent single-device run (the VERDICT r4
        tradeoff, now bounded)."""
        from spatialflink_tpu.models import Point
        from spatialflink_tpu.operators import PointPointKNNQuery
        from spatialflink_tpu.parallel import ops as pops

        pts = self._points(2000, 62)
        q = Point.create(QX, QY, GRID)

        def always_fail(*a, **kw):
            raise RuntimeError("injected device loss (test)")

        monkeypatch.setattr(pops, "knn_mesh_stats", always_fail)
        op = PointPointKNNQuery(self._conf(8), GRID)
        with pytest.raises(RuntimeError, match="refusing to silently"):
            list(op.run(iter(pts), q, 0.5, 15))
        assert op.conf.devices == 2 and op._degradations == 2
        # the loud error carries the underlying failure
        try:
            list(op.run(iter(pts), q, 0.5, 15))
        except RuntimeError as e:
            assert "injected device loss" in str(e.__cause__)

    def test_max_degradations_bound_is_configurable(self, monkeypatch):
        """conf.max_degradations=1 allows ONE elastic halving; the second
        failure raises instead of narrowing further."""
        from spatialflink_tpu.models import Point
        from spatialflink_tpu.operators import (PointPointRangeQuery,
                                                QueryConfiguration, QueryType)
        from spatialflink_tpu.parallel import ops as pops

        def always_fail(*a, **kw):
            raise RuntimeError("injected device loss (test)")

        monkeypatch.setattr(pops, "distributed_stream_filter", always_fail)
        pts = self._points(600, 64)
        q = Point.create(QX, QY, GRID)
        conf = QueryConfiguration(QueryType.WindowBased, 10_000, 5_000,
                                  devices=8, max_degradations=1)
        op = PointPointRangeQuery(conf, GRID)
        with pytest.raises(RuntimeError, match="refusing to silently"):
            list(op.run(iter(pts), q, 0.4))
        assert op.conf.devices == 4 and op._degradations == 1

    def test_two_device_mesh_failure_is_loud(self, monkeypatch):
        """At devices=2 there is no narrower multi-device width: the first
        failure raises (silent 2 -> 1 fallback would be the exact hidden
        state the bound exists to prevent)."""
        from spatialflink_tpu.models import Point
        from spatialflink_tpu.operators import PointPointRangeQuery
        from spatialflink_tpu.parallel import ops as pops

        def always_fail(*a, **kw):
            raise RuntimeError("injected device loss (test)")

        monkeypatch.setattr(pops, "distributed_stream_filter", always_fail)
        pts = self._points(600, 65)
        q = Point.create(QX, QY, GRID)
        op = PointPointRangeQuery(self._conf(2), GRID)
        with pytest.raises(RuntimeError, match="refusing to silently"):
            list(op.run(iter(pts), q, 0.4))
        assert op.conf.devices == 2 and op._degradations == 0

    def test_non_device_errors_propagate(self, monkeypatch):
        from spatialflink_tpu.models import Point
        from spatialflink_tpu.operators import PointPointRangeQuery
        from spatialflink_tpu.parallel import ops as pops

        def type_bug(*a, **kw):
            raise TypeError("shape bug (test)")

        monkeypatch.setattr(pops, "distributed_stream_filter", type_bug)
        pts = self._points(600, 63)
        q = Point.create(QX, QY, GRID)
        op = PointPointRangeQuery(self._conf(8), GRID)
        with pytest.raises(TypeError):
            list(op.run(iter(pts), q, 0.4))


class TestTwoDMeshOperators:
    """conf.hosts > 1 builds the 2-D (hosts x chips) mesh through the SAME
    operator paths: output must match single-device bit-for-bit, with kNN
    merged in two levels (ICI within a slice, then k-sized partials per
    slice over DCN)."""

    def _points(self, n, seed):
        from spatialflink_tpu.models import Point

        rng = np.random.default_rng(seed)
        t0 = 1_700_000_000_000
        return [
            Point.create(float(rng.uniform(115.6, 117.5)),
                         float(rng.uniform(39.7, 41.0)), GRID,
                         obj_id=f"o{i % 61}", timestamp=t0 + i * 10)
            for i in range(n)
        ]

    def _conf(self, devices=None, hosts=None):
        from spatialflink_tpu.operators import QueryConfiguration, QueryType

        return QueryConfiguration(QueryType.WindowBased, window_size_ms=10_000,
                                  slide_ms=5_000, devices=devices, hosts=hosts)

    def test_range_2d_matches_single(self):
        from spatialflink_tpu.models import Point
        from spatialflink_tpu.operators import PointPointRangeQuery

        pts = self._points(3000, 71)
        q = Point.create(QX, QY, GRID)
        r1 = list(PointPointRangeQuery(self._conf(), GRID).run(
            iter(pts), q, 0.4))
        r2d = list(PointPointRangeQuery(self._conf(8, hosts=2), GRID).run(
            iter(pts), q, 0.4))
        assert [w.window_start for w in r1] == [w.window_start for w in r2d]
        for a, b in zip(r1, r2d):
            assert [(p.obj_id, p.timestamp) for p in a.records] == \
                   [(p.obj_id, p.timestamp) for p in b.records]

    def test_knn_2d_matches_single(self):
        from spatialflink_tpu.models import Point
        from spatialflink_tpu.operators import PointPointKNNQuery

        pts = self._points(3000, 72)
        q = Point.create(QX, QY, GRID)
        r1 = list(PointPointKNNQuery(self._conf(), GRID).run(
            iter(pts), q, 0.5, 15))
        r2d = list(PointPointKNNQuery(self._conf(8, hosts=4), GRID).run(
            iter(pts), q, 0.5, 15))
        assert len(r1) == len(r2d) and any(w.records for w in r1)
        for a, b in zip(r1, r2d):
            assert a.records == b.records

    def test_join_2d_matches_single(self):
        from spatialflink_tpu.operators import PointPointJoinQuery

        a = self._points(1500, 73)
        b = self._points(400, 74)
        r1 = list(PointPointJoinQuery(self._conf(), GRID, GRID).run(
            iter(a), iter(b), 0.1))
        r2d = list(PointPointJoinQuery(self._conf(8, hosts=2), GRID, GRID).run(
            iter(a), iter(b), 0.1))
        assert len(r1) == len(r2d) and any(w.records for w in r1)
        for x, y in zip(r1, r2d):
            key = lambda prs: sorted((p.obj_id, p.timestamp, q.obj_id,
                                      q.timestamp) for p, q in prs)
            assert key(x.records) == key(y.records)

    def test_2d_degrades_to_flat_mesh(self, monkeypatch):
        from spatialflink_tpu.models import Point
        from spatialflink_tpu.operators import PointPointRangeQuery
        from spatialflink_tpu.parallel import ops as pops

        real = pops.distributed_stream_filter
        failures = {"left": 1}

        def flaky(mesh, batch, fn):
            if failures["left"] > 0:
                failures["left"] -= 1
                raise RuntimeError("injected device loss (test)")
            return real(mesh, batch, fn)

        monkeypatch.setattr(pops, "distributed_stream_filter", flaky)
        pts = self._points(1200, 75)
        q = Point.create(QX, QY, GRID)
        op = PointPointRangeQuery(self._conf(8, hosts=2), GRID)
        r = list(op.run(iter(pts), q, 0.4))
        assert op.conf.devices == 4 and op.conf.hosts is None
        r1 = list(PointPointRangeQuery(self._conf(), GRID).run(
            iter(pts), q, 0.4))
        for a, b in zip(r1, r):
            assert [(p.obj_id, p.timestamp) for p in a.records] == \
                   [(p.obj_id, p.timestamp) for p in b.records]

    def test_hosts_must_divide_devices(self):
        from spatialflink_tpu.operators import PointPointRangeQuery

        with pytest.raises(ValueError):  # power-of-two but > devices
            PointPointRangeQuery(self._conf(4, hosts=8), GRID)
        with pytest.raises(ValueError):  # not a power of two
            PointPointRangeQuery(self._conf(8, hosts=3), GRID)


class TestGeomStream2DMesh:
    """Geometry streams through the 2-D (hosts x chips) mesh: the generic
    stream funnels (filter / kNN / join lattice) must produce single-device
    output bit-for-bit on the hosts>1 shape too."""

    def _polys(self, n, seed):
        from spatialflink_tpu.models import Polygon

        rng = np.random.default_rng(seed)
        t0 = 1_700_000_000_000
        out = []
        for i in range(n):
            cx = float(rng.uniform(115.7, 117.4))
            cy = float(rng.uniform(39.8, 40.9))
            w = float(rng.uniform(0.01, 0.08))
            out.append(Polygon.create(
                [[(cx - w, cy - w), (cx + w, cy - w), (cx + w, cy + w),
                  (cx - w, cy + w)]], GRID, obj_id=f"g{i % 61}",
                timestamp=t0 + i * 10))
        return out

    def _conf(self, devices=None, hosts=None):
        from spatialflink_tpu.operators import QueryConfiguration, QueryType

        return QueryConfiguration(QueryType.WindowBased, window_size_ms=10_000,
                                  slide_ms=5_000, devices=devices, hosts=hosts)

    def _qpoly(self):
        from spatialflink_tpu.models import Polygon

        return Polygon.create([[(116.2, 40.2), (116.9, 40.2), (116.9, 40.8),
                                (116.2, 40.8)]], GRID)

    def test_polygon_range_2d_matches_single(self):
        from spatialflink_tpu.operators import PolygonPolygonRangeQuery

        polys = self._polys(600, 81)
        r1 = list(PolygonPolygonRangeQuery(self._conf(), GRID).run(
            iter(polys), self._qpoly(), 0.3))
        r2d = list(PolygonPolygonRangeQuery(self._conf(8, hosts=2), GRID).run(
            iter(polys), self._qpoly(), 0.3))
        assert any(w.records for w in r1)
        assert [(w.window_start,
                 sorted(g.obj_id for g in w.records)) for w in r1] == \
               [(w.window_start,
                 sorted(g.obj_id for g in w.records)) for w in r2d]

    def test_polygon_knn_2d_matches_single(self):
        from spatialflink_tpu.models import Point
        from spatialflink_tpu.operators import PolygonPointKNNQuery

        polys = self._polys(600, 82)
        q = Point.create(QX, QY, GRID)
        r1 = list(PolygonPointKNNQuery(self._conf(), GRID).run(
            iter(polys), q, 0.5, 9))
        r2d = list(PolygonPointKNNQuery(self._conf(8, hosts=2), GRID).run(
            iter(polys), q, 0.5, 9))
        assert len(r1) == len(r2d) and any(w.records for w in r1)
        for a, b in zip(r1, r2d):
            assert a.records == b.records
