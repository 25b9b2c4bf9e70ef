"""The windowed two-stream joins read whole decoded chunks
(``operators/join_query.py`` ``PointPointJoinQuery._run_windowed``). Served
through the driver's chunked decode, they emit the window tables, late drops
and pairs that the same join emits when fed plain record lists, which it
reads one record at a time; ``join-columnar-windows`` counts the windows
whose side a was joined from columnar slices. A served windowed join killed
while one side's watermark runs a chunk ahead of the other's resumes to the
clean run's output."""

import contextlib
import dataclasses
import io
import os

import numpy as np
import pytest
import yaml

from spatialflink_tpu.config import Params
from spatialflink_tpu.driver import (CASES, _operator_class, _query_conf,
                                     decode_stream, main, run_option)
from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import Polygon
from spatialflink_tpu.operators import join_query
from spatialflink_tpu.runtime import WindowAssembler
from spatialflink_tpu.streams import (SyntheticPointSource,
                                      reset_memory_brokers, resolve_broker,
                                      serialize_spatial)
from spatialflink_tpu.streams.kafka import KafkaWindowSink
from spatialflink_tpu.utils.metrics import scoped_registry

CONF = "conf/spatialflink-conf.yml"
GRID = UniformGrid(115.5, 117.6, 39.6, 41.1, num_grid_partitions=100)
T0 = 1_700_000_000_000
COUNTER = "join-columnar-windows"


@pytest.fixture(autouse=True)
def _small_chunks(monkeypatch):
    # several decode chunks per side, so sides interleave chunk by chunk
    monkeypatch.setenv("SPATIALFLINK_DECODE_CHUNK", "48")
    reset_memory_brokers()
    yield
    reset_memory_brokers()


@pytest.fixture
def assemblers(monkeypatch):
    """Every window assembler the join builds, sides a then b."""
    made = []

    class Recording(WindowAssembler):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            made.append(self)

    monkeypatch.setattr(join_query, "WindowAssembler", Recording)
    return made


def _points(n_traj, steps, seed, keep=None, **kw):
    pts = SyntheticPointSource(GRID, num_trajectories=n_traj, steps=steps,
                               seed=seed, start_ts=T0, **kw)
    return [serialize_spatial(p, "GeoJSON") for p in pts
            if keep is None or keep(p.timestamp - T0)]


def _polygons(n, seed):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        cx = float(rng.uniform(115.8, 117.3))
        cy = float(rng.uniform(39.8, 40.9))
        poly = Polygon.create(
            [[(cx, cy), (cx + .2, cy), (cx + .2, cy + .2), (cx, cy + .2),
              (cx, cy)]], GRID, obj_id=f"p{i}", timestamp=T0 + i * 700)
        out.append(serialize_spatial(poly, "GeoJSON"))
    return out


def _params(option, radius, lateness_s=0, cells2=None) -> Params:
    p = Params.from_yaml(CONF)
    p.query.option = option
    p.query.radius = radius
    p.query.allowed_lateness_s = lateness_s
    if cells2 is not None:
        p.input2.num_grid_cells = cells2
    return p


def _row(obj):
    """A record as compared: everything but the decode's wall clock."""
    return dataclasses.replace(obj, ingestion_time=0)


def _table(results):
    return [(r.window_start, r.window_end,
             [(_row(a), _row(b)) for a, b in r.records]) for r in results]


def _both_paths(p, lines1, lines2, assemblers):
    """(chunk path, per-record path), each (window table, late drops of
    sides a and b, ``join-columnar-windows``). The chunk path is the
    driver's own (``run_option``); the per-record path is the same
    operator fed the decoded records as plain lists."""
    spec = CASES[p.query.option]
    with scoped_registry() as reg:
        chunked = _table(run_option(p, lines1, lines2))
        late = [wa.late_dropped for wa in assemblers]
        served = (chunked, late, reg.counter(COUNTER).count)
    del assemblers[:]
    u_grid, q_grid = p.grids()
    plain_a = list(decode_stream(lines1, p.input1, u_grid, spec.stream))
    plain_b = list(decode_stream(lines2, p.input2, q_grid, spec.query))
    op = _operator_class(spec)(_query_conf(p, spec), u_grid, q_grid)
    with scoped_registry() as reg:
        plain = _table(op.run(plain_a, plain_b, p.query.radius))
        late = [wa.late_dropped for wa in assemblers]
        per_record = (plain, late, reg.counter(COUNTER).count)
    return served, per_record


def _a_windows(p, lines1) -> int:
    """How many windows side a holds records of: side a alone through one
    assembler, with the join's window and lateness."""
    spec = CASES[p.query.option]
    grid = p.grids()[0]
    conf = _query_conf(p, spec)
    wa = WindowAssembler(conf.window_spec(), conf.allowed_lateness_ms)
    recs = list(decode_stream(lines1, p.input1, grid, spec.stream))
    return sum(1 for _ in wa.assemble(iter(recs)))


def test_in_order_streams_match_per_record(assemblers):
    p = _params(101, radius=0.3)
    served, per_record = _both_paths(
        p, _points(8, 60, seed=3), _points(6, 60, seed=8), assemblers)
    table = served[0]
    assert table == per_record[0]
    assert sum(len(pairs) for *_w, pairs in table) > 0
    assert served[1] == per_record[1] == [0, 0]
    # every window emitted was joined from side a's columnar slices
    assert served[2] == len(table) > 3
    assert per_record[2] == 0


def test_out_of_order_streams_match_per_record_late_drops(assemblers):
    p = _params(101, radius=0.3, lateness_s=2)
    ooo = dict(out_of_order_fraction=0.3, out_of_order_max_ms=6000)
    lines1 = _points(8, 60, seed=4, **ooo)
    served, per_record = _both_paths(
        p, lines1, _points(6, 60, seed=9, **ooo), assemblers)
    assert served[0] == per_record[0]
    assert served[1] == per_record[1]
    assert min(served[1]) > 0  # both sides dropped records as late
    assert served[2] == _a_windows(p, lines1) > 3
    assert per_record[2] == 0


def test_windows_with_one_side_only_match_per_record(assemblers):
    p = _params(101, radius=0.3)
    # side a holds event time [0, 20 s) and [40 s, 60 s), side b [15 s, 45 s)
    lines1 = _points(8, 60, seed=5,
                     keep=lambda t: t < 20_000 or t >= 40_000)
    lines2 = _points(6, 60, seed=10, keep=lambda t: 15_000 <= t < 45_000)
    served, per_record = _both_paths(p, lines1, lines2, assemblers)
    table = served[0]
    assert table == per_record[0]
    a_only = [w for w in table if w[0] >= T0 + 45_000]
    b_only = [w for w in table if T0 + 20_000 <= w[0] and w[1] <= T0 + 40_000]
    assert a_only and b_only
    assert all(not pairs for *_w, pairs in a_only + b_only)
    # counted: the windows side a holds records of (all but the b-only ones)
    assert served[2] == _a_windows(p, lines1) == len(table) - len(b_only)
    assert per_record[2] == 0


def test_query_grid_differs_pairs_in_operator_grid(assemblers):
    """The driver decodes side b in the query grid; the cell predicate
    compares cells in the operator's grid, so side b's batch takes its
    cells from there."""
    p = _params(101, radius=0.3, cells2=37)
    served, per_record = _both_paths(
        p, _points(8, 60, seed=6), _points(6, 60, seed=11), assemblers)
    assert served[0] == per_record[0]
    assert sum(len(pairs) for *_w, pairs in served[0]) > 0
    # the emitted b records keep the cells their own grid gave them
    q_grid = p.grids()[1]
    for *_w, pairs in served[0]:
        for _a, b in pairs:
            assert b.cell == int(q_grid.assign_cell(b.x, b.y)[0])
    assert served[2] == len(served[0])


def test_point_polygon_join_matches_per_record(assemblers):
    p = _params(106, radius=0.05)
    served, per_record = _both_paths(
        p, _points(8, 60, seed=7), _polygons(80, seed=12), assemblers)
    assert served[0] == per_record[0]
    assert sum(len(pairs) for *_w, pairs in served[0]) > 0
    assert served[2] == len(served[0])
    assert per_record[2] == 0


# ------------------------------------------------------------ recovery


def _window_table(broker):
    out = {}
    for r in broker.fetch("output", 0, 1_000_000):
        if isinstance(r.key, str) and r.key.startswith(KafkaWindowSink.MARKER):
            out.setdefault(r.key[len(KafkaWindowSink.MARKER):],
                           []).append(int(r.value))
    return out


def _broker_conf(tmp_path, name, lines1, lines2):
    with open(CONF) as f:
        d = yaml.safe_load(f)
    d["kafkaBootStrapServers"] = f"memory://{name}"
    cfg = tmp_path / f"{name}.yml"
    cfg.write_text(yaml.safe_dump(d))
    broker = resolve_broker(f"memory://{name}")
    for ln in lines1:
        broker.produce("points.geojson", ln)
    for ln in lines2:
        broker.produce("queries.geojson", ln)
    return str(cfg), broker


def test_resume_with_side_b_a_chunk_ahead(tmp_path, monkeypatch, assemblers):
    """Side b carries 1 record a second (a copy of one of side a's
    trajectories, so every window has pairs) and side a 6, so a 48-record
    chunk of b spans 48 s of event time and one of a 8 s: b's watermark
    runs up to a chunk ahead of a's. Killed at the 4th fresh window with
    b's watermark ahead, the resumed run's window table (pairs a window)
    is the clean run's, each window marked once."""
    lines1 = _points(6, 60, seed=3)
    lines2 = lines1[::6]
    argv = ["--kafka", "--option", "101"]
    cfg, broker = _broker_conf(tmp_path, "clean", lines1, lines2)
    with contextlib.redirect_stdout(io.StringIO()):
        assert main(["--config", cfg] + argv) == 0
    expected = {k: v[0] for k, v in _window_table(broker).items()}
    assert len(expected) > 6 and min(expected.values()) > 0

    cfg, broker = _broker_conf(tmp_path, "crash", lines1, lines2)
    cpd = str(tmp_path / "cp")
    argv = ["--config", cfg] + argv + ["--checkpoint-dir", cpd,
                                       "--checkpoint-every", "2"]
    del assemblers[:]
    emit = KafkaWindowSink.emit
    fresh = []
    ahead = []

    def boom(self, result):
        if self.window_key(result) not in self.delivered:
            fresh.append(result.window_start)
            if len(fresh) == 4:
                wa_a, wa_b = assemblers[-2:]
                ahead.append(wa_b.watermarker.watermark
                             - wa_a.watermarker.watermark)
                raise RuntimeError("injected crash")
        emit(self, result)

    with monkeypatch.context() as m:
        m.setattr(KafkaWindowSink, "emit", boom)
        with pytest.raises(RuntimeError, match="injected crash"), \
                contextlib.redirect_stdout(io.StringIO()):
            main(argv)
    # b's watermark more than one of a's chunks ahead of a's
    assert ahead and ahead[0] > 8_000
    assert any(f.endswith(".npz") for f in os.listdir(cpd))

    with contextlib.redirect_stdout(io.StringIO()):
        assert main(argv + ["--resume"]) == 0
    table = _window_table(broker)
    assert all(len(v) == 1 for v in table.values()), table
    assert {k: v[0] for k, v in table.items()} == expected
    assert broker.committed("points.geojson", "spatialflink") == len(lines1)
    assert broker.committed("queries.geojson", "spatialflink") == len(lines2)
