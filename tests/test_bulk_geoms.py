"""Bulk WKT/GeoJSON geometry ingestion: native parse and SoA assembly
parity with the object path; geometry-stream windows on the served
decode and through the driver, against the per-record object path."""

import numpy as np
import pytest

from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models.batches import EdgeGeomBatch
from spatialflink_tpu.operators import QueryConfiguration
from spatialflink_tpu.streams.bulk import (
    ParsedGeoms,
    bulk_parse_wkt,
    geoms_to_edge_batch,
)
from spatialflink_tpu.streams.formats import parse_spatial
from spatialflink_tpu.utils import IdInterner

GRID = UniformGrid(0.0, 10.0, 0.0, 10.0, num_grid_partitions=10)
T0 = 1_700_000_000_000


def _lines(n=40, seed=1, t_step=1):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        cx, cy = rng.uniform(1, 9), rng.uniform(1, 9)
        w = float(rng.uniform(0.1, 1.5))
        t = T0 + i * t_step
        if i % 3 == 0:
            out.append(f"l{i}, {t}, LINESTRING ({cx} {cy}, {cx+w} {cy+w}, {cx+w} {cy})")
        elif i % 7 == 0:  # native-rejected: reparsed + flattened in Python
            out.append(f"m{i}, {t}, MULTIPOLYGON ((({cx} {cy}, {cx+w} {cy}, {cx+w} {cy+w}, {cx} {cy})))")
        else:
            out.append(f"p{i}, {t}, POLYGON (({cx} {cy}, {cx+w} {cy}, {cx+w} {cy+w}, {cx} {cy+w}), "
                       f"({cx+w/4} {cy+w/4}, {cx+w/2} {cy+w/4}, {cx+w/2} {cy+w/2}))")
    return out


class TestParsedGeomsParity:
    def _check_against_objects(self, lines):
        parsed = bulk_parse_wkt(("\n".join(lines)).encode())
        batch = geoms_to_edge_batch(parsed, GRID, ts_base=T0)
        i2 = IdInterner()
        objs = [parse_spatial(ln, "WKT", GRID) for ln in lines]
        want = EdgeGeomBatch.from_objects(objs, GRID, i2, ts_base=T0)
        n = len(lines)
        assert (batch.valid == want.valid).all()
        np.testing.assert_array_equal(batch.ts[:n], want.ts[:n])
        np.testing.assert_allclose(batch.bbox[:n], want.bbox[:n], atol=1e-6)
        np.testing.assert_array_equal(batch.is_areal[:n], want.is_areal[:n])
        np.testing.assert_array_equal(batch.cell[:n], want.cell[:n])
        for g in range(n):
            # cells and edge SETS equal (object path sorts polygon rings by
            # area; the edge set is identical and kernels are edge-order
            # invariant)
            assert set(batch.cells[g][batch.cells_mask[g]].tolist()) == \
                set(want.cells[g][want.cells_mask[g]].tolist()), g
            a = {tuple(e) for e in batch.edges[g][batch.edge_mask[g]].tolist()}
            b = {tuple(e) for e in want.edges[g][want.edge_mask[g]].tolist()}
            assert a == b, g
            assert parsed.interner.lookup(int(batch.obj_id[g])) == \
                i2.lookup(int(want.obj_id[g])), g

    def test_native_path_matches_object_path(self):
        self._check_against_objects(_lines(40))

    def test_python_fallback_matches_object_path(self, monkeypatch):
        monkeypatch.setenv("SPATIALFLINK_NATIVE", "0")
        self._check_against_objects(_lines(25, seed=2))

    def test_unclosed_rings_get_closure_edges(self):
        # raw ring not closed -> closure edge must appear (auto-close parity)
        parsed = bulk_parse_wkt(b"p, 1, POLYGON ((1 1, 3 1, 3 3, 1 3))")
        batch = geoms_to_edge_batch(parsed, GRID)
        edges = batch.edges[0][batch.edge_mask[0]]
        assert edges.shape[0] == 4  # 3 base + closure
        assert (edges[-1] == np.float32([1, 3, 1, 1])).all()

    def test_geometrycollection_line_raises(self):
        with pytest.raises(ValueError):
            bulk_parse_wkt(b"GEOMETRYCOLLECTION (POINT (1 2))")

    def test_subset_rebases_offsets(self):
        parsed = bulk_parse_wkt(("\n".join(_lines(30, seed=3))).encode())
        idx = np.array([4, 7, 20, 21])
        sub = parsed.subset(idx)
        full = geoms_to_edge_batch(parsed, GRID, ts_base=T0)
        part = geoms_to_edge_batch(sub, GRID, ts_base=T0)
        for k, g in enumerate(idx):
            a = {tuple(e) for e in part.edges[k][part.edge_mask[k]].tolist()}
            b = {tuple(e) for e in full.edges[g][full.edge_mask[g]].tolist()}
            assert a == b
            assert part.ts[k] == full.ts[g]


def _served_geoms(lines, fmt="WKT"):
    """The served decode of a geometry stream's text lines."""
    import dataclasses

    from spatialflink_tpu.config import StreamConfig
    from spatialflink_tpu.driver import decode_stream

    cfg = dataclasses.replace(StreamConfig(), format=fmt, date_format=None)
    return decode_stream(lines, cfg, GRID, "Polygon")


def _objects(lines, fmt="WKT", grid=GRID):
    return [parse_spatial(ln, fmt, grid, date_format=None) for ln in lines]


def _conf_file(tmp_path, option, fmt, **query):
    import yaml

    with open("conf/spatialflink-conf.yml") as fh:
        y = yaml.safe_load(fh)
    y["inputStream1"]["gridBBox"] = [0.0, 0.0, 10.0, 10.0]
    y["inputStream2"]["gridBBox"] = [0.0, 0.0, 10.0, 10.0]
    y["query"]["option"] = option
    y["query"].update(query)
    y["inputStream1"]["format"] = fmt
    y["inputStream1"]["dateFormat"] = None
    cfgf = tmp_path / "conf.yml"
    cfgf.write_text(yaml.safe_dump(y))
    return cfgf


def _assert_driver_matches_objects(tmp_path, capsys, option, fmt, lines,
                                   **query):
    """``main`` over the input file (the served decode) emits, window by
    window, the record counts ``run_option`` answers over per-record
    parsed objects."""
    import ast

    from spatialflink_tpu.config import Params
    from spatialflink_tpu.driver import main, run_option

    cfgf = _conf_file(tmp_path, option, fmt, **query)
    f = tmp_path / f"in.{fmt.lower()}"
    f.write_text("\n".join(lines))
    assert main(["--config", str(cfgf), "--input1", str(f)]) == 0
    got = {}
    for ln in capsys.readouterr().out.splitlines():
        if ln.startswith("{'window'"):
            row = ast.literal_eval(ln)
            got[row["window"][0]] = row["count"]
    params = Params.from_yaml(str(cfgf))
    want = {w.window_start: len(w.records) for w in run_option(
        params, iter(_objects(lines, fmt, params.grids()[0])))}
    assert got == want
    assert any(want.values())


class TestGeomBulkWindows:
    def test_served_decode_matches_record_path(self):
        from spatialflink_tpu.models import Polygon
        from spatialflink_tpu.operators import PolygonPolygonRangeQuery

        lines = _lines(60, seed=4, t_step=400)
        q = Polygon.create([[(3, 3), (7, 3), (7, 7), (3, 7)]], GRID)
        conf = QueryConfiguration(window_size_ms=10_000, slide_ms=5_000)
        rec = list(PolygonPolygonRangeQuery(conf, GRID).run(
            iter(_objects(lines)), q, 1.0))
        served = list(PolygonPolygonRangeQuery(conf, GRID).run(
            _served_geoms(lines), q, 1.0))
        assert any(w.records for w in rec)
        assert [(w.window_start, sorted(g.obj_id for g in w.records))
                for w in rec] == \
               [(w.window_start, sorted(g.obj_id for g in w.records))
                for w in served]

    def test_served_distributed_matches(self):
        from spatialflink_tpu.models import Point
        from spatialflink_tpu.operators import PolygonPointRangeQuery

        lines = _lines(60, seed=5, t_step=400)
        q = Point.create(5.0, 5.0, GRID)

        def ids(devices):
            conf = QueryConfiguration(window_size_ms=10_000, slide_ms=5_000,
                                      devices=devices)
            return [(w.window_start, sorted(g.obj_id for g in w.records))
                    for w in PolygonPointRangeQuery(conf, GRID).run(
                        _served_geoms(lines), q, 2.0)]

        r1 = ids(1)
        assert any(recs for _, recs in r1)
        assert r1 == ids(8)

    def test_window_assembly_groups_by_ts(self):
        from spatialflink_tpu.runtime import WindowAssembler, WindowSpec
        from tests.oracles import sliding_window_table

        lines = _lines(30, seed=6, t_step=1000)
        objs = _objects(lines)
        wa = WindowAssembler(WindowSpec.sliding(10_000, 5_000))
        wins = {start: sorted(g.obj_id for g in recs)
                for start, _end, recs in wa.assemble(_served_geoms(lines))}
        want = sliding_window_table([g.timestamp for g in objs],
                                    10_000, 5_000)
        assert wins == {start: sorted(objs[i].obj_id for i in idx)
                        for start, idx in want.items()}


class TestDriverGeomBulk:
    def test_driver_bulk_option21(self, tmp_path, capsys):
        _assert_driver_matches_objects(
            tmp_path, capsys, 21, "WKT", _lines(50, seed=7, t_step=400),
            radius=1.0, queryPolygons=[[[3, 3], [7, 3], [7, 7], [3, 7]]])


class TestGeomKnnBulk:
    def test_geom_knn_served_matches_record_path(self):
        from spatialflink_tpu.models import Point
        from spatialflink_tpu.operators import PolygonPointKNNQuery

        lines = _lines(60, seed=8, t_step=400)
        q = Point.create(5.0, 5.0, GRID)
        conf = QueryConfiguration(window_size_ms=10_000, slide_ms=5_000)
        rec = list(PolygonPointKNNQuery(conf, GRID).run(
            iter(_objects(lines)), q, 0.0, 7))
        served = list(PolygonPointKNNQuery(conf, GRID).run(
            _served_geoms(lines), q, 0.0, 7))
        assert any(w.records for w in rec)
        assert [(w.window_start, sorted(w.records)) for w in rec] == \
               [(w.window_start, sorted(w.records)) for w in served]

    def test_point_geom_knn_served_matches_record_path(self):
        from spatialflink_tpu.config import StreamConfig
        from spatialflink_tpu.driver import decode_stream
        from spatialflink_tpu.models import Point, Polygon
        from spatialflink_tpu.operators import PointPolygonKNNQuery

        rng = np.random.default_rng(9)
        rows = [f"o{i % 30},{T0 + i * 400},{rng.uniform(0.5, 9.5):.6f},"
                f"{rng.uniform(0.5, 9.5):.6f}" for i in range(400)]
        q = Polygon.create([[(4, 4), (6, 4), (6, 6), (4, 6)]], GRID)
        conf = QueryConfiguration(window_size_ms=10_000, slide_ms=5_000)
        pts = [Point.create(float(x), float(y), GRID, o, int(t))
               for o, t, x, y in (r.split(",") for r in rows)]
        rec = list(PointPolygonKNNQuery(conf, GRID).run(iter(pts), q, 0.0, 9))
        served = list(PointPolygonKNNQuery(conf, GRID).run(
            decode_stream(rows, StreamConfig(format="CSV", date_format=None),
                          GRID), q, 0.0, 9))
        assert any(w.records for w in rec)
        # equal-distance ties may order differently (interner id order
        # differs between the two paths); compare tie-insensitively
        assert [(w.window_start, sorted(w.records)) for w in rec] == \
               [(w.window_start, sorted(w.records)) for w in served]

    def test_driver_bulk_geom_knn_option(self, tmp_path, capsys):
        # option 71 = kNN, (Polygon, Point) stream/query pair
        from spatialflink_tpu.driver import CASES

        assert CASES[71].family == "knn" and CASES[71].stream == "Polygon"
        _assert_driver_matches_objects(
            tmp_path, capsys, 71, "WKT", _lines(50, seed=10, t_step=400),
            radius=0.0, k=5, queryPoints=[[5.0, 5.0]])


class TestPointGeomRangeBulkDriver:
    def test_driver_bulk_point_polygon_range_option6(self, tmp_path, capsys):
        from spatialflink_tpu.driver import CASES

        assert CASES[6].family == "range" and \
            (CASES[6].stream, CASES[6].query) == ("Point", "Polygon")
        rng = np.random.default_rng(11)
        rows = [f"o{i % 30},{T0 + i * 400},{rng.uniform(0.5, 9.5):.6f},"
                f"{rng.uniform(0.5, 9.5):.6f}" for i in range(300)]
        _assert_driver_matches_objects(
            tmp_path, capsys, 6, "CSV", rows,
            radius=1.0, queryPolygons=[[[4, 4], [6, 4], [6, 6], [4, 6]]])


def _geojson_lines(n=30, seed=1, t_step=1):
    import json as _json

    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        cx, cy = rng.uniform(1, 9), rng.uniform(1, 9)
        w = float(rng.uniform(0.1, 1.5))
        t = T0 + i * t_step
        props = {"oID": f"g{i}", "timestamp": t}
        if i % 3 == 0:
            geom = {"type": "LineString",
                    "coordinates": [[cx, cy], [cx + w, cy + w], [cx + w, cy]]}
        elif i % 7 == 0:  # native-rejected: reparsed + flattened in Python
            geom = {"type": "MultiPolygon", "coordinates": [
                [[[cx, cy], [cx + w, cy], [cx + w, cy + w], [cx, cy]]]]}
        else:  # polygon with a hole
            geom = {"type": "Polygon", "coordinates": [
                [[cx, cy], [cx + w, cy], [cx + w, cy + w], [cx, cy + w],
                 [cx, cy]],
                [[cx + w / 4, cy + w / 4], [cx + w / 2, cy + w / 4],
                 [cx + w / 2, cy + w / 2], [cx + w / 4, cy + w / 4]]]}
        rec = {"type": "Feature", "geometry": geom, "properties": props}
        if i % 5 == 0:  # Kafka envelope form
            rec = {"topic": "polys", "timestamp": 0, "value": rec}
        out.append(_json.dumps(rec))
    return out


class TestGeoJsonGeomsParity:
    """bulk_parse_geojson_geoms must equal the per-record GeoJSON object
    path — including native-rejected features (Multi*, envelope oddities)
    flattened through the Python reparser."""

    def _check_against_objects(self, lines):
        from spatialflink_tpu.streams.bulk import bulk_parse_geojson_geoms

        parsed = bulk_parse_geojson_geoms(("\n".join(lines)).encode())
        batch = geoms_to_edge_batch(parsed, GRID, ts_base=T0)
        i2 = IdInterner()
        objs = [parse_spatial(ln, "GeoJSON", GRID) for ln in lines]
        want = EdgeGeomBatch.from_objects(objs, GRID, i2, ts_base=T0)
        n = len(lines)
        assert (batch.valid == want.valid).all()
        np.testing.assert_array_equal(batch.ts[:n], want.ts[:n])
        np.testing.assert_allclose(batch.bbox[:n], want.bbox[:n], atol=1e-6)
        np.testing.assert_array_equal(batch.is_areal[:n], want.is_areal[:n])
        np.testing.assert_array_equal(batch.cell[:n], want.cell[:n])
        for g in range(n):
            assert set(batch.cells[g][batch.cells_mask[g]].tolist()) == \
                set(want.cells[g][want.cells_mask[g]].tolist()), g
            a = {tuple(e) for e in batch.edges[g][batch.edge_mask[g]].tolist()}
            b = {tuple(e) for e in want.edges[g][want.edge_mask[g]].tolist()}
            assert a == b, g
            assert parsed.interner.lookup(int(batch.obj_id[g])) == \
                i2.lookup(int(want.obj_id[g])), g

    def test_native_path_matches_object_path(self):
        self._check_against_objects(_geojson_lines(30))

    def test_python_fallback_matches_object_path(self, monkeypatch):
        monkeypatch.setenv("SPATIALFLINK_NATIVE", "0")
        self._check_against_objects(_geojson_lines(20, seed=4))

    def test_point_feature_raises(self):
        from spatialflink_tpu.streams.bulk import bulk_parse_geojson_geoms

        with pytest.raises(ValueError):
            bulk_parse_geojson_geoms(
                b'{"type": "Feature", "geometry": {"type": "Point", '
                b'"coordinates": [1, 2]}, "properties": {"oID": "p"}}')


class TestDriverGeoJsonGeomBulk:
    def test_driver_bulk_option21_geojson(self, tmp_path, capsys):
        _assert_driver_matches_objects(
            tmp_path, capsys, 21, "GeoJSON",
            _geojson_lines(40, seed=9, t_step=400),
            radius=1.0, queryPolygons=[[[3, 3], [7, 3], [7, 7], [3, 7]]])

    def test_bulk_output_matches_record_path(self, tmp_path, capsys):
        # the same file through the CLI twice: byte-identical output
        from spatialflink_tpu.driver import main

        lines = _geojson_lines(40, seed=9, t_step=400)
        f = tmp_path / "polys.geojson"
        f.write_text("\n".join(lines))
        cfgf = _conf_file(tmp_path, 21, "GeoJSON", radius=1.0,
                          queryPolygons=[[[3, 3], [7, 3], [7, 7], [3, 7]]])
        assert main(["--config", str(cfgf), "--input1", str(f)]) == 0
        first = capsys.readouterr().out
        assert main(["--config", str(cfgf), "--input1", str(f)]) == 0
        assert capsys.readouterr().out == first
        assert first.strip()


class TestMalformedConsistency:
    """Bulk ingest accepts exactly what the record path accepts — and FAILS
    exactly where it fails: a malformed line must raise the same exception
    type from both, never silently produce a record."""

    CASES = [
        ("GeoJSON", '{"type": "Feature", "geometry": {"type": "Polygon", '
                    '"coordinates": [[[1, 2], [3'),
        ("GeoJSON", '{"type": "Feature", "geometry": {"type": "Polygon"}, '
                    '"properties": {}}'),
        ("GeoJSON", "garbage line"),
        ("GeoJSON", '{"type": "Feature", "geometry": null, '
                    '"properties": {"oID": "x"}}'),
        ("WKT", "POLYGON ((1 1, 2 2"),
        ("WKT", "POLYGONE ((1 1, 2 2, 3 3))"),
    ]

    @pytest.mark.parametrize("fmt,line", CASES)
    def test_same_exception_type(self, fmt, line):
        from spatialflink_tpu.streams.bulk import (
            bulk_parse_geojson_geoms,
            bulk_parse_wkt,
        )

        bulk_fn = (bulk_parse_geojson_geoms if fmt == "GeoJSON"
                   else bulk_parse_wkt)
        with pytest.raises(Exception) as bulk_err:
            bulk_fn(line.encode())
        with pytest.raises(Exception) as rec_err:
            parse_spatial(line, fmt, GRID)
        assert type(bulk_err.value) is type(rec_err.value), \
            (bulk_err.value, rec_err.value)
