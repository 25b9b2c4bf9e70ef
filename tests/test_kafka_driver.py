"""Driver-level Kafka wiring (the CLI's ``--kafka`` mode): consume
``inputStream{1,2}.topicName``, produce marker-keyed windows to
``outputStream.topicName``, window-aligned offset commits, and crash/restart
recovery with no duplicate or missing windows (reference topology:
``StreamingJob.java:473`` consumers, ``:512`` EXACTLY_ONCE producer,
``HelperClass.java:455-529`` latency sinks)."""

import json

import pytest
import yaml

from spatialflink_tpu.driver import main
from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import Point
from spatialflink_tpu.streams import (
    InMemoryBroker,
    KafkaSource,
    KafkaWindowSink,
    SyntheticPointSource,
    WindowCommitTap,
    reset_memory_brokers,
    resolve_broker,
    serialize_spatial,
)

CONF = "conf/spatialflink-conf.yml"
IN1, IN2, OUT = "points.geojson", "queries.geojson", "output"


@pytest.fixture(autouse=True)
def _fresh_brokers():
    reset_memory_brokers()
    yield
    reset_memory_brokers()


def _conf(tmp_path, name, fname="conf.yml", **query_overrides):
    """A copy of the sample conf pointed at a process-shared memory broker."""
    with open(CONF) as f:
        d = yaml.safe_load(f)
    d["kafkaBootStrapServers"] = f"memory://{name}"
    d["query"].update(query_overrides)
    p = tmp_path / fname
    p.write_text(yaml.safe_dump(d))
    return str(p), f"memory://{name}"


def _lines(n_traj=8, steps=6, seed=3):
    grid = UniformGrid(115.5, 117.6, 39.6, 41.1, num_grid_partitions=100)
    pts = list(SyntheticPointSource(grid, num_trajectories=n_traj,
                                    steps=steps, seed=seed))
    return [serialize_spatial(p, "GeoJSON") for p in pts]


def _markers(broker, topic=OUT):
    pre = KafkaWindowSink.MARKER
    return [r.key[len(pre):] for r in broker.fetch(topic, 0, 1_000_000)
            if isinstance(r.key, str) and r.key.startswith(pre)]


def _strip_job(key):
    """Window key without the leading job fingerprint (the driver folds
    params.job_fingerprint(group) into every window key)."""
    return key.split(":", 1)[1]


# ------------------------------------------------------------ end-to-end


def test_kafka_range_end_to_end(tmp_path, capsys):
    """Option 1 through main(): topic in, marker-keyed windows out, full
    offsets committed on drain."""
    cfg, url = _conf(tmp_path, "range-e2e")
    broker = resolve_broker(url)
    lines = _lines()
    for ln in lines:
        broker.produce(IN1, ln)
    rc = main(["--config", cfg, "--kafka", "--option", "1"])
    assert rc == 0
    err = capsys.readouterr().err
    assert "# kafka:" in err
    marks = _markers(broker)
    assert marks and len(marks) == len(set(marks))
    # every produced window record carries its window's key
    recs = broker.fetch(OUT, 0, 1_000_000)
    data_keys = {r.key for r in recs
                 if isinstance(r.key, str)
                 and not r.key.startswith(KafkaWindowSink.MARKER)}
    assert data_keys <= set(marks)
    # bounded topic fully drained -> the group committed to the end
    assert broker.committed(IN1, "spatialflink") == len(lines)
    # marker value = the window's record count; data records under that key
    # agree (the marker-delimited window read contract)
    by_key = {}
    for r in recs:
        if isinstance(r.key, str) and not r.key.startswith(
                KafkaWindowSink.MARKER):
            by_key[r.key] = by_key.get(r.key, 0) + 1
    for r in recs:
        if isinstance(r.key, str) and r.key.startswith(KafkaWindowSink.MARKER):
            wk = r.key[len(KafkaWindowSink.MARKER):]
            assert int(r.value) == by_key.get(wk, 0)


def test_kafka_matches_file_replay(tmp_path, capsys):
    """The broker path answers exactly the windows the file path answers."""
    lines = _lines()
    inp = tmp_path / "in.geojson"
    inp.write_text("\n".join(lines) + "\n")
    cfg, url = _conf(tmp_path, "parity")
    rc = main(["--config", cfg, "--option", "1", "--input1", str(inp)])
    assert rc == 0
    import ast

    file_results = [ast.literal_eval(l) for l in
                    capsys.readouterr().out.strip().splitlines()
                    if l.startswith("{")]
    file_windows = [r["window"] for r in file_results]
    broker = resolve_broker(url)
    for ln in lines:
        broker.produce(IN1, ln)
    rc = main(["--config", cfg, "--kafka", "--option", "1"])
    assert rc == 0
    kafka_windows = sorted(_strip_job(m) for m in _markers(broker))
    assert kafka_windows == sorted(f"{w[0]}:{w[1]}:None"
                                   for w in file_windows)
    # per-window record COUNTS also match the file path (the broker path's
    # chunked native decode must select exactly the same records)
    marker_counts = {
        _strip_job(r.key[len(KafkaWindowSink.MARKER):]): int(r.value)
        for r in broker.fetch(OUT, 0, 1_000_000)
        if isinstance(r.key, str) and r.key.startswith(KafkaWindowSink.MARKER)
    }
    for r in file_results:
        w = r["window"]
        assert marker_counts[f"{w[0]}:{w[1]}:None"] == r["count"]


def test_kafka_bulk_decode_csv_and_fallbacks(tmp_path, capsys):
    """CSV records ride the chunked native decode; an embedded-newline
    record falls back to the exact per-record parse (never dropped or
    mis-attributed), and window counts match the file-path run."""
    import ast

    grid = UniformGrid(115.5, 117.6, 39.6, 41.1, num_grid_partitions=100)
    pts = list(SyntheticPointSource(grid, num_trajectories=6, steps=8,
                                    seed=4))
    rows = [serialize_spatial(p, "CSV") for p in pts]
    inp = tmp_path / "in.csv"
    inp.write_text("\n".join(rows) + "\n")
    cfg, url = _conf(tmp_path, "csvbulk")
    rc = main(["--config", cfg, "--option", "1", "--format", "CSV",
               "--input1", str(inp)])
    assert rc == 0
    file_windows = [ast.literal_eval(l) for l in
                    capsys.readouterr().out.strip().splitlines()
                    if l.startswith("{")]
    broker = resolve_broker(url)
    for r in rows:
        broker.produce(IN1, r)
    rc = main(["--config", cfg, "--kafka", "--option", "1",
               "--format", "CSV"])
    assert rc == 0
    counts = {
        _strip_job(r.key[len(KafkaWindowSink.MARKER):]): int(r.value)
        for r in broker.fetch(OUT, 0, 1_000_000)
        if isinstance(r.key, str) and r.key.startswith(KafkaWindowSink.MARKER)
    }
    assert counts == {f"{w['window'][0]}:{w['window'][1]}:None": w["count"]
                      for w in file_windows}

    # embedded newline: the whole chunk falls back to per-record parse
    broker2 = resolve_broker(url + "-nl")
    for r in rows[:10]:
        broker2.produce(IN1, r)
    broker2.produce(IN1, rows[10] + "\n")  # trailing newline, same record
    for r in rows[11:]:
        broker2.produce(IN1, r)
    cfg2, _ = _conf(tmp_path, "csvbulk-nl", "c2.yml")
    rc = main(["--config", cfg2, "--kafka", "--option", "1",
               "--format", "CSV", "--kafka-bootstrap", url + "-nl"])
    assert rc == 0
    assert broker2.committed(IN1, "spatialflink") == len(rows)
    counts2 = {
        _strip_job(r.key[len(KafkaWindowSink.MARKER):]): int(r.value)
        for r in broker2.fetch(OUT, 0, 1_000_000)
        if isinstance(r.key, str) and r.key.startswith(KafkaWindowSink.MARKER)
    }
    assert counts2 == counts, "newline-carrying record was dropped/shifted"


def test_kafka_preproduce_and_knn(tmp_path):
    """--input1 with --kafka pre-produces the file to the input topic;
    kNN (51) rides the same wiring."""
    lines = _lines()
    inp = tmp_path / "in.geojson"
    inp.write_text("\n".join(lines) + "\n")
    cfg, url = _conf(tmp_path, "knn", k=3)
    rc = main(["--config", cfg, "--kafka", "--option", "51",
               "--input1", str(inp)])
    assert rc == 0
    broker = resolve_broker(url)
    assert broker.end_offset(IN1) == len(lines)
    assert _markers(broker)
    assert broker.committed(IN1, "spatialflink") == len(lines)


def test_kafka_join_two_topics(tmp_path):
    """Join (101) consumes BOTH input topics; both groups commit."""
    cfg, url = _conf(tmp_path, "join")
    broker = resolve_broker(url)
    lines = _lines()
    for ln in lines:
        broker.produce(IN1, ln)
        broker.produce(IN2, ln)
    rc = main(["--config", cfg, "--kafka", "--option", "101"])
    assert rc == 0
    assert _markers(broker)
    assert broker.committed(IN1, "spatialflink") == len(lines)
    assert broker.committed(IN2, "spatialflink") == len(lines)


def test_kafka_latency_topic(tmp_path):
    """The latency variant (option 8) ships per-record now-ingestionTime
    millis to '<output>-latency' (HelperClass latency sinks)."""
    cfg, url = _conf(tmp_path, "latency")
    broker = resolve_broker(url)
    for ln in _lines():
        broker.produce(IN1, ln)
    rc = main(["--config", cfg, "--kafka", "--option", "8"])
    assert rc == 0
    lats = broker.topic_values(OUT + "-latency")
    assert lats and all(isinstance(v, (int, float)) for v in lats)


def test_kafka_control_tuple_stops(tmp_path, capsys):
    """A control tuple in the topic stops the pipeline gracefully without
    committing past the stop point (restart re-sees it)."""
    cfg, url = _conf(tmp_path, "control")
    broker = resolve_broker(url)
    lines = _lines()
    for ln in lines[:10]:
        broker.produce(IN1, ln)
    broker.produce(IN1, json.dumps(
        {"geometry": {"type": "control", "coordinates": []}}))
    for ln in lines[10:]:
        broker.produce(IN1, ln)
    rc = main(["--config", cfg, "--kafka", "--option", "1"])
    assert rc == 0
    assert "control-tuple stop" in capsys.readouterr().err
    assert broker.committed(IN1, "spatialflink") <= 11


def test_kafka_preproduce_skips_nonempty_topic(tmp_path, capsys):
    """Re-running the same --kafka --input1 command (the natural restart)
    must NOT append the file to the topic a second time — doubled records
    would corrupt every window still covered by uncommitted offsets."""
    lines = _lines()
    inp = tmp_path / "in.geojson"
    inp.write_text("\n".join(lines) + "\n")
    cfg, url = _conf(tmp_path, "repro")
    argv = ["--config", cfg, "--kafka", "--option", "1",
            "--input1", str(inp)]
    assert main(argv) == 0
    broker = resolve_broker(url)
    marks = sorted(_markers(broker))
    assert main(argv) == 0
    assert "NOT re-producing" in capsys.readouterr().err
    assert broker.end_offset(IN1) == len(lines)
    # second run re-reads nothing (offsets committed) and adds no windows
    assert sorted(_markers(broker)) == marks


def test_kafka_follow_requires_incremental_commits(tmp_path):
    """Unbounded (--kafka-follow) runs of cases with end-only commits would
    never advance the group offset; the CLI rejects them up front."""
    cfg, _ = _conf(tmp_path, "follow-gate")
    for opt in ("102", "2000"):  # realtime join; CheckIn app
        with pytest.raises(SystemExit):
            main(["--config", cfg, "--kafka", "--kafka-follow",
                  "--option", opt])


def test_kafka_realtime_lagged_commits(tmp_path):
    """Realtime range/kNN commit a bounded lag behind the read head, so a
    live-run restart reprocesses a tail, not the whole topic."""
    cfg, url = _conf(tmp_path, "rt-lag")
    broker = resolve_broker(url)
    grid = UniformGrid(115.5, 117.6, 39.6, 41.1, num_grid_partitions=100)
    pts = list(SyntheticPointSource(grid, num_trajectories=20, steps=150,
                                    seed=5))
    for p in pts:
        broker.produce(IN1, serialize_spatial(p, "GeoJSON"))
    broker.produce(IN1, json.dumps(
        {"geometry": {"type": "control", "coordinates": []}}))
    rc = main(["--config", cfg, "--kafka", "--kafka-follow", "--option", "2"])
    assert rc == 0
    committed = broker.committed(IN1, "spatialflink")
    # control stop skips finish(): only the lagged mid-stream commits stand
    assert 0 < committed < len(pts)
    cfg, _ = _conf(tmp_path, "reject")
    with pytest.raises(SystemExit):
        main(["--config", cfg, "--kafka", "--option", "99"])


def _file_table(capsys, cfg, argv, *inputs):
    """{window key: marker value} the broker path must produce for the
    same case, derived from the file replay's window summaries: the
    window's record count, plus the one JSON summary record the sink adds
    for a window that carries extras (kNN's k, multi-query metadata)."""
    import ast

    for flag, path in zip(("--input1", "--input2"), inputs):
        argv = argv + [flag, str(path)]
    assert main(["--config", cfg] + argv) == 0
    rows = [ast.literal_eval(ln) for ln in
            capsys.readouterr().out.splitlines() if ln.startswith("{")]
    summary = {"window", "count", "per_query_counts"}
    return {f"{r['window'][0]}:{r['window'][1]}:None":
            r["count"] + (1 if set(r) - summary else 0) for r in rows}


def _marker_table(broker):
    return {_strip_job(r.key[len(KafkaWindowSink.MARKER):]): int(r.value)
            for r in broker.fetch(OUT, 0, 1_000_000)
            if isinstance(r.key, str)
            and r.key.startswith(KafkaWindowSink.MARKER)}


def _write(tmp_path, name, lines):
    f = tmp_path / name
    f.write_text("\n".join(lines) + "\n")
    return f


def test_kafka_bulk_topic_replay(tmp_path, capsys):
    """A bounded topic replay: every window's record count equals the file
    replay's, the group commits the whole topic, and a re-run replays
    nothing and adds no window."""
    lines = _lines()
    cfg, url = _conf(tmp_path, "topic-replay")
    want = _file_table(capsys, cfg, ["--option", "1"],
                       _write(tmp_path, "in.geojson", lines))
    broker = resolve_broker(url)
    for ln in lines:
        broker.produce(IN1, ln)
    assert main(["--config", cfg, "--kafka", "--option", "1"]) == 0
    assert _marker_table(broker) == want != {}
    assert broker.committed(IN1, "spatialflink") == len(lines)
    marks = sorted(_markers(broker))
    assert main(["--config", cfg, "--kafka", "--option", "1"]) == 0
    assert sorted(_markers(broker)) == marks


def test_kafka_bulk_join_two_topics(tmp_path, capsys):
    """Join (101) over two topics: per-window pair counts equal the
    two-file replay's, and both groups commit their whole topic."""
    lines, lines2 = _lines(), _lines(seed=8)
    cfg, url = _conf(tmp_path, "join-topics")
    want = _file_table(capsys, cfg, ["--option", "101"],
                       _write(tmp_path, "a.geojson", lines),
                       _write(tmp_path, "b.geojson", lines2))
    broker = resolve_broker(url)
    for ln in lines:
        broker.produce(IN1, ln)
    for ln in lines2:
        broker.produce(IN2, ln)
    assert main(["--config", cfg, "--kafka", "--option", "101"]) == 0
    assert _marker_table(broker) == want != {}
    assert broker.committed(IN1, "spatialflink") == len(lines)
    assert broker.committed(IN2, "spatialflink") == len(lines2)


def test_kafka_mixed_geometry_record_resilience(tmp_path, capsys):
    """A stray polygon feature in a declared point topic must not crash
    the broker path: the chunked decode falls back to the per-record
    parse (which dead-letters the off-type record) and keeps producing
    windows."""
    poly = json.dumps({
        "geometry": {"type": "Polygon", "coordinates":
                     [[[116.2, 40.2], [116.4, 40.2], [116.4, 40.4],
                       [116.2, 40.2]]]},
        "properties": {"oID": "px", "timestamp": 1_700_000_003_000}})
    lines = _lines()
    records = lines[:15] + [poly] + lines[15:]
    cfg, url = _conf(tmp_path, "mixed-stream", "mixed-stream.yml")
    broker = resolve_broker(url)
    for r in records:
        broker.produce(IN1, r)
    rc = main(["--config", cfg, "--kafka", "--option", "1"])
    assert rc == 0
    assert _markers(broker)
    assert broker.committed(IN1, "spatialflink") == len(records)


def test_kafka_bulk_composes_with_multi_query(tmp_path, capsys):
    """--kafka --multi-query (kNN, two query points): per-window counts
    equal the file replay's under the same flag."""
    qp = {"queryPoints": [[116.3, 40.3], [116.7, 40.7]]}
    lines = _lines()
    cfg, url = _conf(tmp_path, "multi-topic", **qp)
    argv = ["--option", "51", "--multi-query"]
    want = _file_table(capsys, cfg, argv,
                       _write(tmp_path, "in.geojson", lines))
    broker = resolve_broker(url)
    for ln in lines:
        broker.produce(IN1, ln)
    assert main(["--config", cfg, "--kafka"] + argv) == 0
    assert _marker_table(broker) == want != {}
    assert broker.committed(IN1, "spatialflink") == len(lines)


def test_kafka_bulk_geometry_stream(tmp_path, capsys):
    """A WKT polygon STREAM (option 21, polygon-polygon range) over the
    broker: per-window counts equal the file replay's."""
    import numpy as np

    rng = np.random.default_rng(3)
    t0 = 1_700_000_000_000
    rows = []
    for i in range(120):
        cx, cy = rng.uniform(115.7, 117.4), rng.uniform(39.8, 40.9)
        w = rng.uniform(0.01, 0.05)
        rows.append(f"g{i % 16}, {t0 + i * 200}, POLYGON (("
                    f"{cx - w} {cy - w}, {cx + w} {cy - w}, "
                    f"{cx + w} {cy + w}, {cx - w} {cy + w}, "
                    f"{cx - w} {cy - w}))")
    cfg, url = _conf(tmp_path, "geo-topic")
    argv = ["--option", "21", "--format", "WKT"]
    want = _file_table(capsys, cfg, argv, _write(tmp_path, "in.wkt", rows))
    broker = resolve_broker(url)
    for r in rows:
        broker.produce(IN1, r)
    assert main(["--config", cfg, "--kafka"] + argv) == 0
    assert _marker_table(broker) == want != {}
    assert broker.committed(IN1, "spatialflink") == len(rows)


@pytest.mark.parametrize("opt,needs2", [
    (204, False),   # trange window (marker-keyed)
    (206, False),   # tstats window (marker-keyed)
    (208, False),   # taggregate window: heatmap rides the summary record
    (210, True),    # tjoin window: two topics
    (1010, False),  # StayTime app (plain sink)
    (2000, False),  # CheckIn app (DEIM CSV, plain sink)
    (504, False),   # WKT deser conformance (plain sink)
])
def test_kafka_family_matrix(tmp_path, opt, needs2):
    """Every family the driver serves runs through the broker topology end
    to end: windowed trajectory ops produce marker-keyed windows, apps and
    deser produce plain records, and all groups commit on drain."""
    cfg, url = _conf(tmp_path, f"matrix-{opt}")
    broker = resolve_broker(url)
    if opt == 504:
        records = ["GEOMETRYCOLLECTION (POINT (116.5 40.5), "
                   "LINESTRING (116 40, 117 41))"]
    elif opt == 2000:
        # DEIM check-in events: eventID,deviceID,userID,ts,x,y
        records = [f"e{i},room{i % 3}-{'in' if i % 2 == 0 else 'out'},"
                   f"u{i % 4},{1_700_000_000_000 + i * 1000},116.5,40.5"
                   for i in range(24)]
    else:
        records = _lines()
    for r in records:
        broker.produce(IN1, r)
    if needs2:
        for r in _lines(seed=5):
            broker.produce(IN2, r)
    argv = ["--config", cfg, "--kafka", "--option", str(opt)]
    if opt == 504:
        argv += ["--format", "WKT"]
    assert main(argv) == 0
    assert broker.end_offset(OUT) > 0, "nothing reached the output topic"
    assert broker.committed(IN1, "spatialflink") == len(records)
    if needs2:
        assert broker.committed(IN2, "spatialflink") == \
            broker.end_offset(IN2)
    if opt in (204, 206, 208, 210):
        assert _markers(broker), "windowed family should produce markers"


def test_kafka_composes_with_multi_query(tmp_path):
    """--kafka + --multi-query: one marker-keyed window per window (not per
    query), with the flattened per-query records under the window key and
    the multi-query metadata riding the JSON summary record."""
    cfg, url = _conf(tmp_path, "mq",
                     queryPoints=[[116.3, 40.3], [116.7, 40.7]])
    broker = resolve_broker(url)
    lines = _lines()
    for ln in lines:
        broker.produce(IN1, ln)
    assert main(["--config", cfg, "--kafka", "--option", "1",
                 "--multi-query"]) == 0
    marks = _markers(broker)
    assert marks and len(marks) == len(set(marks))
    assert broker.committed(IN1, "spatialflink") == len(lines)


def test_kafka_composes_with_mesh(tmp_path):
    """--kafka + --devices: broker-fed windows shard across the virtual
    mesh and produce the same marker set as the single-device broker run."""
    lines = _lines()
    cfg1, url1 = _conf(tmp_path, "mesh-1", "c1.yml")
    b1 = resolve_broker(url1)
    cfg8, url8 = _conf(tmp_path, "mesh-8", "c8.yml")
    b8 = resolve_broker(url8)
    for ln in lines:
        b1.produce(IN1, ln)
        b8.produce(IN1, ln)
    assert main(["--config", cfg1, "--kafka", "--option", "1"]) == 0
    assert main(["--config", cfg8, "--kafka", "--option", "1",
                 "--devices", "8"]) == 0
    assert _markers(b1), "baseline run produced no windows"
    assert sorted(_markers(b8)) == sorted(_markers(b1))
    assert b8.committed(IN1, "spatialflink") == len(lines)


# ------------------------------------------------------ crash / restart


@pytest.mark.parametrize("crash_point", ["before_produce", "after_produce"])
def test_kafka_crash_restart_no_dup_no_missing(tmp_path, monkeypatch,
                                               crash_point):
    """Kill mid-run, restart, assert no duplicate/missing windows via
    committed offsets + marker-seeded idempotency (VERDICT r4 item 1's
    done-criterion). Crashing BEFORE the 3rd window's production exercises
    re-delivery of uncommitted records; crashing AFTER production but
    before the offset commit exercises marker-seeded duplicate
    suppression across the restart."""
    # expected window set from an untouched clean run
    base_cfg, base_url = _conf(tmp_path, "crash-baseline", "base.yml")
    base_broker = resolve_broker(base_url)
    lines = _lines(6, 30)
    for ln in lines:
        base_broker.produce(IN1, ln)
    assert main(["--config", base_cfg, "--kafka", "--option", "1"]) == 0
    expected = sorted(_markers(base_broker))
    assert len(expected) >= 4, "need several windows for a mid-run crash"

    cfg, url = _conf(tmp_path, "crash")
    broker = resolve_broker(url)
    for ln in lines:
        broker.produce(IN1, ln)

    orig = KafkaWindowSink.emit
    state = {"fresh": 0}

    def boom(self, result):
        if self.window_key(result) not in self.delivered:
            state["fresh"] += 1
            if state["fresh"] == 3:
                if crash_point == "before_produce":
                    raise RuntimeError("injected crash (pre-production)")
                orig(self, result)
                raise RuntimeError("injected crash (post-production)")
        orig(self, result)

    with monkeypatch.context() as m:
        m.setattr(KafkaWindowSink, "emit", boom)
        with pytest.raises(RuntimeError, match="injected crash"):
            main(["--config", cfg, "--kafka", "--option", "1"])

    produced_before = 2 if crash_point == "before_produce" else 3
    assert len(_markers(broker)) == produced_before
    # conservative commits: never past what emitted windows fully cover
    assert broker.committed(IN1, "spatialflink") < len(lines)

    # restart: at-least-once re-delivery + idempotent suppression
    assert main(["--config", cfg, "--kafka", "--option", "1"]) == 0
    marks = sorted(_markers(broker))
    assert marks == expected, "windows missing or duplicated after restart"
    assert broker.committed(IN1, "spatialflink") == len(lines)


def test_kafka_realtime_crash_restart_no_missing_records(tmp_path,
                                                         monkeypatch):
    """Realtime range (option 2) with lagged commits: a crash mid-run and
    restart may duplicate output (at-least-once, plain sink) but must never
    MISS a matching record — the lag guarantees uncommitted records cover
    every batch not fully produced."""
    from spatialflink_tpu.streams.kafka import KafkaSink

    grid = UniformGrid(115.5, 117.6, 39.6, 41.1, num_grid_partitions=100)
    pts = list(SyntheticPointSource(grid, num_trajectories=20, steps=150,
                                    seed=12))
    lines = [serialize_spatial(p, "GeoJSON") for p in pts]

    cfg_o, url_o = _conf(tmp_path, "rt-oracle", "o.yml")
    bo = resolve_broker(url_o)
    for ln in lines:
        bo.produce(IN1, ln)
    assert main(["--config", cfg_o, "--kafka", "--option", "2"]) == 0
    oracle = set(bo.topic_values(OUT))
    assert oracle

    cfg, url = _conf(tmp_path, "rt-crash", "c.yml")
    broker = resolve_broker(url)
    for ln in lines:
        broker.produce(IN1, ln)
    orig = KafkaSink.emit
    state = {"n": 0}

    def boom(self, record):
        state["n"] += 1
        if state["n"] == len(oracle) // 2:
            raise RuntimeError("injected realtime crash")
        orig(self, record)

    with monkeypatch.context() as m:
        m.setattr(KafkaSink, "emit", boom)
        with pytest.raises(RuntimeError, match="injected realtime crash"):
            main(["--config", cfg, "--kafka", "--option", "2"])
    committed_mid = broker.committed(IN1, "spatialflink")
    assert committed_mid < len(lines)
    assert main(["--config", cfg, "--kafka", "--option", "2"]) == 0
    got = set(broker.topic_values(OUT))
    missing = oracle - got
    assert not missing, f"records lost across realtime restart: {missing}"
    assert broker.committed(IN1, "spatialflink") == len(lines)


def test_kafka_checkpoint_resume_no_double_counting(tmp_path, monkeypatch):
    """Stateful realtime tStats (205) through the broker with --checkpoint:
    a crash after some state was checkpointed resumes from the
    checkpoint's consumed offset (committed to the group at startup), so
    no record is double-applied — final per-trajectory stats match an
    uninterrupted oracle run."""
    from spatialflink_tpu.streams.kafka import KafkaSink

    grid = UniformGrid(115.5, 117.6, 39.6, 41.1, num_grid_partitions=100)
    pts = list(SyntheticPointSource(grid, num_trajectories=5, steps=400,
                                    seed=6))
    lines = [serialize_spatial(p, "GeoJSON") for p in pts]

    def last_per_traj(broker):
        out = {}
        for v in broker.topic_values(OUT):
            if isinstance(v, tuple) and len(v) == 4:
                out[v[0]] = v
        return out

    # oracle: one uninterrupted run
    cfg_o, url_o = _conf(tmp_path, "ckpt-oracle", "o.yml")
    bo = resolve_broker(url_o)
    for ln in lines:
        bo.produce(IN1, ln)
    assert main(["--config", cfg_o, "--kafka", "--option", "205",
                 "--checkpoint", str(tmp_path / "o.npz"),
                 "--checkpoint-every", "2"]) == 0
    oracle = last_per_traj(bo)
    assert oracle, "oracle run emitted nothing"

    # crashed run: KafkaSink dies mid-stream, restart resumes
    cfg, url = _conf(tmp_path, "ckpt-crash", "c.yml")
    broker = resolve_broker(url)
    for ln in lines:
        broker.produce(IN1, ln)
    ck = str(tmp_path / "c.npz")
    orig = KafkaSink.emit
    state = {"n": 0}

    def boom(self, record):
        state["n"] += 1
        # past the first checkpoint (checkpoint-every=2 micro-batches of
        # 512 records ≈ 1024 tuples): the restart must resume from the
        # checkpoint's consumed offset, not offset 0
        if state["n"] == 1200:
            raise RuntimeError("injected sink crash")
        orig(self, record)

    with monkeypatch.context() as m:
        m.setattr(KafkaSink, "emit", boom)
        with pytest.raises(RuntimeError, match="injected sink crash"):
            main(["--config", cfg, "--kafka", "--option", "205",
                  "--checkpoint", ck, "--checkpoint-every", "2"])
    from spatialflink_tpu.runtime.state import checkpoint_consumed

    consumed = checkpoint_consumed(ck)
    assert consumed > 0, "crash must land after the first checkpoint"
    assert main(["--config", cfg, "--kafka", "--option", "205",
                 "--checkpoint", ck, "--checkpoint-every", "2"]) == 0
    got = last_per_traj(broker)
    assert got.keys() == oracle.keys()
    for oid, t in oracle.items():
        g = got[oid]
        # cumulative state: identical final stats despite the different
        # batch split across the restart
        assert g[2] == t[2], (oid, g, t)          # temporal (int)
        assert abs(g[1] - t[1]) < 1e-4, (oid, g, t)  # spatial
    assert broker.committed(IN1, "spatialflink") == len(lines)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_kafka_crash_restart_out_of_order_fuzz(tmp_path, monkeypatch, seed):
    """Randomized soundness of the window-aligned commits: bounded
    OUT-OF-ORDER arrival (the prefix-conservative case the ordered tests
    never stress) + a crash at a random window production. Invariant after
    restart: the marker set equals the clean-run oracle with every window
    exactly once — nothing missing (commits never passed a record an
    unfired window needed) and nothing duplicated (marker-seeded
    suppression)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    grid = UniformGrid(115.5, 117.6, 39.6, 41.1, num_grid_partitions=100)
    t0 = 1_700_000_000_000
    n = 600
    # ~60 s of event time with ±1.5 s jitter (lateness is 1 s, so some
    # records are genuinely late-dropped too), shuffled locally
    ts = t0 + np.arange(n) * 100 + rng.integers(-1500, 1500, n)
    pts = [serialize_spatial(
        Point.create(float(rng.uniform(115.6, 117.5)),
                     float(rng.uniform(39.7, 41.0)), grid,
                     obj_id=f"o{i % 29}", timestamp=int(ts[i])), "GeoJSON")
        for i in range(n)]

    cfg_o, url_o = _conf(tmp_path, f"fuzz-oracle-{seed}", "o.yml")
    bo = resolve_broker(url_o)
    for ln in pts:
        bo.produce(IN1, ln)
    assert main(["--config", cfg_o, "--kafka", "--option", "1"]) == 0
    expected = sorted(_markers(bo))
    assert len(expected) >= 5

    cfg, url = _conf(tmp_path, f"fuzz-crash-{seed}", "c.yml")
    broker = resolve_broker(url)
    for ln in pts:
        broker.produce(IN1, ln)
    crash_at = int(rng.integers(2, len(expected)))
    orig = KafkaWindowSink.emit
    state = {"fresh": 0}

    def boom(self, result):
        if self.window_key(result) not in self.delivered:
            state["fresh"] += 1
            if state["fresh"] == crash_at:
                if int(rng.integers(0, 2)):
                    orig(self, result)  # crash between produce and commit
                raise RuntimeError("fuzz crash")
        orig(self, result)

    with monkeypatch.context() as m:
        m.setattr(KafkaWindowSink, "emit", boom)
        with pytest.raises(RuntimeError, match="fuzz crash"):
            main(["--config", cfg, "--kafka", "--option", "1"])
    assert main(["--config", cfg, "--kafka", "--option", "1"]) == 0
    assert sorted(_markers(broker)) == expected
    assert broker.committed(IN1, "spatialflink") == len(pts)


# ------------------------------------------------- robustness satellites


def test_output_topic_shared_across_different_queries(tmp_path):
    """Regression (ADVICE #1): a DIFFERENT query against the same output
    topic must not be suppressed by the first job's dedup markers — the job
    fingerprint in the window key isolates them. Same window bounds, two
    jobs, two marker sets."""
    lines = _lines()
    cfg_a, url = _conf(tmp_path, "fpshare", "a.yml")
    broker = resolve_broker(url)
    for ln in lines:
        broker.produce(IN1, ln)
    assert main(["--config", cfg_a, "--kafka", "--option", "1"]) == 0
    m1 = set(_markers(broker))
    assert m1

    # same broker/output topic, different query (radius changed) — the
    # group already committed, so feed the input again for the second job
    cfg_b, _ = _conf(tmp_path, "fpshare", "b.yml", radius=0.123)
    for ln in lines:
        broker.produce(IN1, ln)
    assert main(["--config", cfg_b, "--kafka", "--option", "1"]) == 0
    m2 = set(_markers(broker)) - m1
    assert m2, "second job's windows were suppressed by the first job's " \
               "markers (fingerprint regression)"
    # same event times -> same window bounds; only the job prefix differs
    assert {_strip_job(k) for k in m2} == {_strip_job(k) for k in m1}

    # and an identical re-run of job A (after re-feeding) IS suppressed
    for ln in lines:
        broker.produce(IN1, ln)
    assert main(["--config", cfg_a, "--kafka", "--option", "1"]) == 0
    assert set(_markers(broker)) == m1 | m2


def test_kafka_follow_sparse_stream_commits_on_consumption(tmp_path, capsys):
    """Regression (ADVICE #2): a realtime --kafka-follow stream whose
    query matches NOTHING (zero emissions, so the emit-time lagged commit
    never runs) must still advance the group offset from consumption
    progress, and a restart resumes from it instead of reprocessing the
    whole topic."""
    grid = UniformGrid(115.5, 117.6, 39.6, 41.1, num_grid_partitions=100)
    pts = list(SyntheticPointSource(grid, num_trajectories=20, steps=150,
                                    seed=5))
    # query pinned to a corner with a tiny radius: no point matches
    cfg, url = _conf(tmp_path, "sparse", "c.yml",
                     queryPoints=[[115.51, 39.61]], radius=1e-6)
    broker = resolve_broker(url)
    for p in pts:
        broker.produce(IN1, serialize_spatial(p, "GeoJSON"))
    broker.produce(IN1, json.dumps(
        {"geometry": {"type": "control", "coordinates": []}}))
    argv = ["--config", cfg, "--kafka", "--kafka-follow", "--option", "2"]
    assert main(argv) == 0
    err = capsys.readouterr().err
    assert "# emitted 0 results" in err
    c1 = broker.committed(IN1, "spatialflink")
    assert 0 < c1 < len(pts), \
        "sparse stream must commit consumption progress (lagged)"
    # restart: resumes from c1, re-reads only the tail, still commits
    assert main(argv) == 0
    assert "# emitted 0 results" in capsys.readouterr().err
    assert broker.committed(IN1, "spatialflink") >= c1


def test_window_sink_honors_pre_fingerprint_markers():
    """Upgrade continuity: markers written before job fingerprints existed
    (bare start:end:cell keys) still suppress re-delivery of the same
    window, so the first post-upgrade restart does not re-produce the
    topic's history."""
    from spatialflink_tpu.operators import WindowResult

    broker = InMemoryBroker()
    broker.produce(OUT, "1", key=f"{KafkaWindowSink.MARKER}1000:2000:None")
    sink = KafkaWindowSink(broker, OUT, job_id="deadbeef")
    sink.emit(WindowResult(1000, 2000, [Point.create(0.0, 0.0)]))
    assert sink.duplicates_suppressed == 1
    assert sink.windows_produced == 0
    # a genuinely new window still produces, prefixed
    sink.emit(WindowResult(2000, 3000, [Point.create(0.0, 0.0)]))
    assert sink.windows_produced == 1
    assert "deadbeef:2000:3000:None" in sink.delivered


def test_window_sink_seed_scan_warns_and_bounds(capsys):
    """Regression (ADVICE #4): the startup dedup-seed scan warns when it
    crosses the record threshold (uncompacted-topic risk), and
    seed_scan_limit bounds it to the topic tail with an explicit warning."""
    broker = InMemoryBroker()
    for i in range(60):
        broker.produce(OUT, "1", key=f"{KafkaWindowSink.MARKER}w{i}")
    sink = KafkaWindowSink(broker, OUT, seed_scan_warn=10)
    assert len(sink.delivered) == 60
    assert "uncompacted" in capsys.readouterr().err

    sink2 = KafkaWindowSink(broker, OUT, seed_scan_limit=10)
    assert sink2.delivered == {f"w{i}" for i in range(50, 60)}
    assert "last 10" in capsys.readouterr().err

    # quiet default: small topics scan silently
    KafkaWindowSink(broker, OUT)
    assert "warning" not in capsys.readouterr().err


# ------------------------------------------------------------- tap unit


def test_window_commit_tap_prefix_conservative():
    """An early-arriving record destined for a later window blocks commits
    behind it (prefix-only popping keeps at-least-once sound under
    out-of-order event time)."""
    broker = InMemoryBroker()
    for ts in (1_000, 22_000, 2_000):
        broker.produce("t", Point.create(0.0, 0.0, obj_id="a", timestamp=ts))
    src = KafkaSource(broker, "t", "g", auto_commit=False)
    tap = WindowCommitTap(src, size_ms=10_000, slide_ms=5_000)
    assert len(list(tap)) == 3
    # window [0, 10k) fired: record 1 (lwe 10k) commits; record 2
    # (lwe 30k) blocks record 3 (lwe 10k) despite its eligibility
    tap.on_window_emitted(10_000)
    assert broker.committed("t", "g") == 1
    tap.on_window_emitted(30_000)
    assert broker.committed("t", "g") == 3


def test_memory_broker_registry_is_process_shared():
    a = resolve_broker("memory://same")
    b = resolve_broker("memory://same")
    c = resolve_broker("memory://other")
    assert a is b and a is not c
