"""Stage spans of the served broker path (``--kafka`` over the in-process
``memory://`` broker), for the windowed kNN (option 51) and the windowed
join (option 101): every stage span appears, with one dispatch per window
and a decode span per chunk, and no decoded record is materialized to
feed window assembly; the ingest and decode spans nest inside the
window pulls; a ``jax.profiler`` capture holds them as host events on the
trace's own clock. The telemetry-off contract for these paths is in
``tests/test_telemetry.py``."""

import contextlib
import glob
import io
import os
import time

import pytest
import yaml

from spatialflink_tpu.driver import main
from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.ops import join as join_ops
from spatialflink_tpu.streams import (KafkaWindowSink, SyntheticPointSource,
                                      WindowCommitTap, reset_memory_brokers,
                                      resolve_broker, serialize_spatial)
from spatialflink_tpu.utils import telemetry as telemetry_mod

CONF = "conf/spatialflink-conf.yml"
INGEST = ("kafka.fetch", "kafka.poll", "kafka.decode", "decode.materialize")
EXPECTED = {
    51: {"kafka.fetch", "kafka.poll", "kafka.decode", "knn.window",
         "knn.dispatch", "knn.merge", "sink", "kafka.sink"},
    101: {"kafka.fetch", "kafka.poll", "kafka.decode",
          "join.window", "join.dispatch", "join.reduce", "join.compact",
          "join.lattice", "join.pairs", "sink", "kafka.sink"},
}
FAMILY = {51: "knn", 101: "join"}
#: spans that carry the window start as annotation metadata
PER_WINDOW = (".dispatch", ".merge", ".reduce", ".compact", ".lattice",
              ".pairs")


@pytest.fixture(autouse=True)
def _fresh_brokers():
    reset_memory_brokers()
    yield
    reset_memory_brokers()


@pytest.fixture
def broker_run(tmp_path, monkeypatch):
    """Run one option over 480 records per topic in 64-record decode
    chunks (several chunks per poll), with the join's pre-pass forced on;
    returns (the window starts emitted, chunks decoded)."""
    monkeypatch.setenv("SPATIALFLINK_DECODE_CHUNK", "64")
    monkeypatch.setattr(join_ops, "_LATTICE_BUDGET", 1)
    chunks = []
    track = WindowCommitTap._track_chunk

    def counted(self, chunk):
        chunks.append(len(chunk))
        return track(self, chunk)

    monkeypatch.setattr(WindowCommitTap, "_track_chunk", counted)

    def run(option: int) -> tuple:
        with open(CONF) as f:
            d = yaml.safe_load(f)
        url = f"memory://stage-spans-{option}"
        d["kafkaBootStrapServers"] = url
        cfg = tmp_path / "conf.yml"
        cfg.write_text(yaml.safe_dump(d))
        grid = UniformGrid(115.5, 117.6, 39.6, 41.1,
                           num_grid_partitions=100)
        broker = resolve_broker(url)
        for p in SyntheticPointSource(grid, num_trajectories=8, steps=60,
                                      seed=3):
            line = serialize_spatial(p, "GeoJSON")
            broker.produce("points.geojson", line)
            broker.produce("queries.geojson", line)
        with contextlib.redirect_stdout(io.StringIO()):
            assert main(["--config", str(cfg), "--kafka", "--option",
                         str(option)]) == 0
        pre = KafkaWindowSink.MARKER
        starts = [int(r.key[len(pre):].rsplit(":", 3)[1])
                  for r in broker.fetch("output", 0, 1 << 20)
                  if isinstance(r.key, str) and r.key.startswith(pre)]
        return starts, len(chunks)

    return run


@pytest.fixture
def recorded(monkeypatch):
    """Every span a session opens, as (name, start, end, metadata) on the
    perf counter, in the order they close."""
    spans = []

    @contextlib.contextmanager
    def trace(name, **meta):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            spans.append((name, t0, time.perf_counter(), meta))

    monkeypatch.setattr(telemetry_mod, "trace", trace)
    return spans


@pytest.mark.parametrize("option", [51, 101])
def test_stage_names_and_counts(broker_run, recorded, option):
    with telemetry_mod.telemetry_session():
        starts, chunks = broker_run(option)
    names = [n for n, *_ in recorded]
    assert EXPECTED[option] <= set(names)
    assert "ingest" not in names
    # both windowed operators buffer decoded chunks whole: no record is
    # materialized to feed window assembly
    assert "decode.materialize" not in names
    q = FAMILY[option]
    # one dispatch per window emitted, and a decode span per chunk
    assert starts and names.count(f"{q}.dispatch") == len(starts)
    assert chunks >= 2 and names.count("kafka.decode") >= chunks
    # one window's spans share its start
    for n, _s, _e, meta in recorded:
        if n.endswith(PER_WINDOW) or n == "sink":
            assert meta.get("window") in starts, (n, meta)
        elif n in INGEST or n.endswith(".window"):
            assert meta == {}, (n, meta)


@pytest.mark.parametrize("option", [51, 101])
def test_ingest_spans_nest_in_window_pulls(broker_run, recorded, option):
    with telemetry_mod.telemetry_session():
        broker_run(option)
    pulls = [(s, e) for n, s, e, _m in recorded
             if n == f"{FAMILY[option]}.window"]
    inner = [(n, s, e) for n, s, e, _m in recorded if n in INGEST]
    assert pulls and inner
    for n, s, e in inner:
        assert any(a <= s and e <= b for a, b in pulls), (n, s, e)
    # spans nest: one that starts inside another ends inside it too
    stack = []
    for n, s, e, _m in sorted(recorded, key=lambda r: (r[1], -r[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        if stack:
            assert e <= stack[-1][2], (n, stack[-1][0])
        stack.append((n, s, e))


def _host_events(log_dir: str, names: set) -> list:
    """(name, start ns, end ns, stats) of the host events named in
    ``names``: the planes ``perfbench/devtrace.py`` reads
    (``Trace.from_planes``)."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        out.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats)))
    return out


@pytest.mark.parametrize("option", [51, 101])
def test_profiler_capture_holds_stage_spans(broker_run, tmp_path, option):
    import jax

    log_dir = str(tmp_path / "trace")
    with telemetry_mod.telemetry_session():
        jax.profiler.start_trace(log_dir)
        try:
            starts, _chunks = broker_run(option)
        finally:
            jax.profiler.stop_trace()
    events = _host_events(log_dir, EXPECTED[option])
    assert EXPECTED[option] <= {n for n, *_ in events}
    q = FAMILY[option]
    dispatch = [st for n, _s, _e, st in events if n == f"{q}.dispatch"]
    assert sorted(st["window"] for st in dispatch) == sorted(starts)
    # on the trace's clock the decode spans lie inside the window pulls
    pulls = [(s, e) for n, s, e, _st in events if n == f"{q}.window"]
    for n, s, e, _st in events:
        if n == "kafka.decode":
            assert any(a <= s and e <= b for a, b in pulls)
