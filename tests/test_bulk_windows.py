"""Event-time windows on the served path — the columnar chunk decode
(``streams.bulk`` parsers under ``driver.decode_stream``) through the
operators and ``driver.run_option`` — against independent window-table
oracles (``tests/oracles.py``) and the per-record object path."""

import numpy as np
import pytest

from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import Point
from spatialflink_tpu.operators.base import QueryConfiguration, QueryType
from spatialflink_tpu.operators.knn_query import PointPointKNNQuery
from spatialflink_tpu.operators.range_query import PointPointRangeQuery
from spatialflink_tpu.runtime.windows import WindowSpec
from tests import oracles as O

GRID = UniformGrid(115.50, 117.60, 39.60, 41.10, num_grid_partitions=100)
T0 = 1_700_000_000_000
QX, QY = 116.5, 40.5  # the canonical config's query point


def point_rows(n=600, seed=0, ordered=True, ids=40, span_ms=60_000):
    """``(obj_id, ts, x, y)`` rows; coordinates carry the 6 decimals the
    CSV text does, so the oracle sees exactly what the parser reads."""
    rng = np.random.default_rng(seed)
    ts = T0 + rng.integers(0, span_ms, n)
    if ordered:
        ts = np.sort(ts)
    return [(f"o{i % ids}", int(t), round(float(rng.uniform(115.6, 117.5)), 6),
             round(float(rng.uniform(39.7, 41.0)), 6))
            for i, t in enumerate(ts)]


def csv_lines(rows, delim=","):
    return [delim.join((o, str(t), f"{x:.6f}", f"{y:.6f}"))
            for o, t, x, y in rows]


def served_stream(rows, fmt="CSV", chunk=4096):
    """The served decode of ``rows``' text: columnar chunks."""
    import dataclasses

    from spatialflink_tpu.config import StreamConfig
    from spatialflink_tpu.driver import decode_stream

    cfg = dataclasses.replace(StreamConfig(), format=fmt, date_format=None)
    delim = "\t" if fmt == "TSV" else ","
    return decode_stream(csv_lines(rows, delim), cfg, GRID, chunk=chunk)


def object_stream(rows):
    return [Point.create(x, y, GRID, o, t) for o, t, x, y in rows]


def range_table(results):
    return {w.window_start: sorted((p.obj_id, p.timestamp) for p in w.records)
            for w in results if w.records}


def assert_knn_matches(results, want):
    assert results
    for w in results:
        ids, dists = want[w.window_start]
        assert sorted(o for o, _ in w.records) == sorted(ids), w.window_start
        np.testing.assert_allclose(sorted(d for _, d in w.records),
                                   sorted(dists), atol=1e-4)
    assert {w.window_start for w in results} == set(want)


class TestAssignBulk:
    @pytest.mark.parametrize("size,slide", [(10_000, 5_000), (10_000, 3_000),
                                            (7_000, 7_000), (5_000, 1_000)])
    def test_matches_scalar_assign(self, size, slide):
        spec = WindowSpec(size, slide)
        rng = np.random.default_rng(size + slide)
        ts = T0 + rng.integers(0, 100_000, 500)
        win, rec = spec.assign_bulk(ts)
        got = {}
        for w, r in zip(win, rec):
            got.setdefault(int(w), []).append(int(r))
        want = {}
        for i, t in enumerate(ts):
            for w in spec.assign(int(t)):
                want.setdefault(w, []).append(i)
        assert set(got) == set(want)
        for w in want:
            assert sorted(want[w]) == got[w]  # grouped, record order preserved

    def test_empty(self):
        win, rec = WindowSpec(10_000, 5_000).assign_bulk(np.empty(0, np.int64))
        assert len(win) == 0 and len(rec) == 0


class TestRunBulkEquivalence:
    """The columnar (bulk-parsed) decode and the per-record object path
    both answer the oracle's window table."""

    def test_range_bulk_matches_record_path(self):
        rows = point_rows(500, seed=7)
        q = Point.create(QX, QY, GRID)
        conf = QueryConfiguration(window_size_ms=10_000, slide_ms=5_000)
        want = O.range_window_table(rows, QX, QY, 0.4, 10_000, 5_000)
        assert want
        served = range_table(PointPointRangeQuery(conf, GRID).run(
            served_stream(rows), q, 0.4))
        objects = range_table(PointPointRangeQuery(conf, GRID).run(
            iter(object_stream(rows)), q, 0.4))
        assert served == want
        assert objects == want

    def test_knn_bulk_matches_record_path(self):
        rows = point_rows(500, seed=8)
        q = Point.create(QX, QY, GRID)
        conf = QueryConfiguration(window_size_ms=10_000, slide_ms=5_000, k=5)
        want = O.knn_window_table(rows, QX, QY, 5, 10_000, 5_000)
        served = list(PointPointKNNQuery(conf, GRID).run(
            served_stream(rows), q, 0.0))
        objects = list(PointPointKNNQuery(conf, GRID).run(
            iter(object_stream(rows)), q, 0.0))
        assert_knn_matches(served, want)
        assert_knn_matches(objects, want)


class TestServedWindowSpecs:
    """Window shapes the served range path is held to: tumbling, a slide
    that does not divide the size, and a sampling spec (slide > size)
    whose gaps belong to no window."""

    @pytest.mark.parametrize("size,slide", [(10_000, 10_000), (10_000, 3_000),
                                            (1_000, 6_000)])
    def test_range_window_table_matches_oracle(self, size, slide):
        rows = point_rows(400, seed=size + slide)
        conf = QueryConfiguration(window_size_ms=size, slide_ms=slide)
        got = list(PointPointRangeQuery(conf, GRID).run(
            served_stream(rows), Point.create(QX, QY, GRID), 0.5))
        want = O.range_window_table(rows, QX, QY, 0.5, size, slide)
        assert want
        assert range_table(got) == want
        # every emitted window is one the oracle's assignment opens
        opened = O.sliding_window_table([r[1] for r in rows], size, slide)
        assert {w.window_start for w in got} <= set(opened)


class TestServedChunkingAndK:
    """Where decode chunks split the stream must not move a late-drop
    decision, and a k above a window's distinct objects returns each
    object once, at its nearest position."""

    @pytest.mark.parametrize("chunk", [1, 13, 4096])
    def test_lateness_independent_of_decode_chunk(self, chunk):
        rows = _shuffled_rows(seed=51, n=300)
        conf = QueryConfiguration(QueryType.WindowBased, 10_000, 5_000,
                                  allowed_lateness_ms=2_000)
        got = range_table(PointPointRangeQuery(conf, GRID).run(
            served_stream(rows, chunk=chunk), Point.create(QX, QY, GRID),
            0.5))
        assert got == O.range_window_table(rows, QX, QY, 0.5, 10_000, 5_000,
                                           lateness=2_000)
        assert got

    @pytest.mark.parametrize("k", [12, 50])
    def test_knn_k_at_or_above_distinct_objects(self, k):
        rows = point_rows(300, seed=61, ids=12)
        conf = QueryConfiguration(window_size_ms=10_000, slide_ms=5_000, k=k)
        got = list(PointPointKNNQuery(conf, GRID).run(
            served_stream(rows), Point.create(QX, QY, GRID), 0.0))
        assert_knn_matches(got, O.knn_window_table(rows, QX, QY, k,
                                                   10_000, 5_000))
        assert all(len({o for o, _ in w.records}) == len(w.records)
                   for w in got)


def stepped_rows(n=300, seed=12):
    """In-order rows 40 ms apart, over 30 object ids."""
    return [(f"o{i % 30}", T0 + i * 40, x, y)
            for i, (_o, _t, x, y) in enumerate(point_rows(n, seed))]


def _driver_params(option, lateness_s=0, radius=0.4, fmt="CSV"):
    import dataclasses
    from spatialflink_tpu.config import Params

    p = Params.from_yaml("conf/spatialflink-conf.yml")
    q = dataclasses.replace(p.query, option=option, radius=radius, k=5,
                            allowed_lateness_s=lateness_s)
    i1 = dataclasses.replace(p.input1, format=fmt, date_format=None)
    i2 = dataclasses.replace(p.input2, format=fmt, date_format=None)
    return dataclasses.replace(p, query=q, input1=i1, input2=i2)


def _shuffled_rows(seed=21, n=400):
    return point_rows(n, seed, ordered=False, ids=30, span_ms=30_000)


class TestDriverBulk:
    """``driver.run_option`` over raw CSV/TSV text against the oracle."""

    def test_bulk_matches_record_path_via_driver(self):
        from spatialflink_tpu.driver import run_option
        rows = stepped_rows()
        got = range_table(run_option(_driver_params(1),
                                     iter(csv_lines(rows))))
        assert got == O.range_window_table(rows, QX, QY, 0.4, 10_000, 5_000)
        assert got

    def test_bulk_matches_record_path_out_of_order_with_lateness(self):
        # shuffled timestamps: the watermark drops exactly the stragglers
        # the oracle's running-max rule drops
        from spatialflink_tpu.driver import run_option
        rows = _shuffled_rows()
        lines = csv_lines(rows)
        for lateness in (0, 2, 1000):
            got = range_table(run_option(_driver_params(1, lateness_s=lateness),
                                         iter(lines)))
            want = O.range_window_table(rows, QX, QY, 0.4, 10_000, 5_000,
                                        lateness=lateness * 1000)
            assert got == want and got, lateness

    def test_bulk_tsv_forces_tab_delimiter(self):
        from spatialflink_tpu.driver import run_option
        rows = point_rows(200, seed=13)
        got = range_table(run_option(_driver_params(1, fmt="TSV"),
                                     iter(csv_lines(rows, "\t"))))
        assert got == O.range_window_table(rows, QX, QY, 0.4, 10_000, 5_000)
        assert got


class TestServedKnnLateness:
    """Windowed kNN (option 51) over out-of-order text: late drops and
    top-k per window as the oracle derives them."""

    @pytest.mark.parametrize("lateness", [0, 2])
    def test_knn_out_of_order_matches_oracle(self, lateness):
        from spatialflink_tpu.driver import run_option
        rows = _shuffled_rows(seed=41)
        # radius 0: no cell pruning, so the exact top-k is the contract
        got = list(run_option(_driver_params(51, lateness_s=lateness,
                                             radius=0.0),
                              iter(csv_lines(rows))))
        assert_knn_matches(got, O.knn_window_table(
            rows, QX, QY, 5, 10_000, 5_000, lateness=lateness * 1000))


def _join_table(results):
    return {w.window_start:
            sorted(((a.obj_id, a.timestamp), (b.obj_id, b.timestamp))
                   for a, b in w.records)
            for w in results if w.records}


class TestJoinBulk:
    def test_join_bulk_matches_record_path(self):
        from spatialflink_tpu.operators.join_query import PointPointJoinQuery

        ra = point_rows(400, seed=31)
        rb = point_rows(120, seed=32, ids=12)
        conf = QueryConfiguration(window_size_ms=10_000, slide_ms=5_000)
        want = O.join_window_table(ra, rb, 0.25, 10_000, 5_000)
        assert want
        served = _join_table(PointPointJoinQuery(conf, GRID, GRID).run(
            served_stream(ra), served_stream(rb), 0.25))
        objects = _join_table(PointPointJoinQuery(conf, GRID, GRID).run(
            iter(object_stream(ra)), iter(object_stream(rb)), 0.25))
        assert served == want
        assert objects == want


class TestServedJoinWindowSpecs:
    """The windowed join's chunk-fed assembly under a tumbling spec and a
    slide that does not divide the size."""

    @pytest.mark.parametrize("size,slide", [(10_000, 10_000),
                                            (10_000, 3_000)])
    def test_join_window_specs_match_oracle(self, size, slide):
        from spatialflink_tpu.operators.join_query import PointPointJoinQuery

        ra = point_rows(300, seed=71)
        rb = point_rows(100, seed=72, ids=10)
        conf = QueryConfiguration(window_size_ms=size, slide_ms=slide)
        got = _join_table(PointPointJoinQuery(conf, GRID, GRID).run(
            served_stream(ra), served_stream(rb), 0.25))
        want = O.join_window_table(ra, rb, 0.25, size, slide)
        assert want
        assert got == want


class TestDriverBulkJoin:
    """``run_option`` on the windowed Point/Point join (option 101): the
    actual pairs per window match the oracle's."""

    def _params(self):
        return _driver_params(101, radius=0.2)

    def test_bulk_join_matches_record_path(self):
        from spatialflink_tpu.driver import run_option

        rows1, rows2 = stepped_rows(400, 31), stepped_rows(90, 32)
        got = _join_table(run_option(self._params(), iter(csv_lines(rows1)),
                                     iter(csv_lines(rows2))))
        assert got == O.join_window_table(rows1, rows2, 0.2, 10_000, 5_000)
        assert sum(len(p) for p in got.values()) > 0

    def test_bulk_join_requires_second_input(self):
        from spatialflink_tpu.driver import run_option

        lines = csv_lines(stepped_rows(50, 33))
        with pytest.raises(ValueError, match="needs stream2"):
            list(run_option(self._params(), iter(lines)))
