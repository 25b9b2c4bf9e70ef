"""Config system + driver dispatch tests (reference: Params.java /
StreamingJob.java switch)."""

import io
import json
import textwrap

import pytest

from spatialflink_tpu.config import ConfigError, Params
from spatialflink_tpu.driver import CASES, CaseSpec, main, run_option
from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import Point
from spatialflink_tpu.operators import (
    PointPointRangeQuery,
    QueryConfiguration,
    QueryType,
    WindowResult,
)
from spatialflink_tpu.streams.formats import serialize_spatial
from spatialflink_tpu.streams.sources import SyntheticPointSource

CONF = "conf/spatialflink-conf.yml"


# ------------------------------------------------------------------ config


def test_sample_conf_loads():
    p = Params.from_yaml(CONF)
    assert p.query.option == 1
    assert p.input1.format == "GeoJSON"
    assert p.input1.grid_bbox == (115.5, 39.6, 117.6, 41.1)
    assert p.window.interval_s == 10 and p.window.step_s == 5
    g1, g2 = p.grids()
    assert g1.n == 100 and g2.n == 100


def test_reference_conf_compat(tmp_path):
    """A reference-style file with the java type tag and TSV escapes loads."""
    y = tmp_path / "ref.yml"
    y.write_text(textwrap.dedent("""\
        !!GeoFlink.utils.ConfigType
        clusterMode: False
        kafkaBootStrapServers: "localhost:9092"
        inputStream1:
          topicName: "t"
          format: "CSV"
          dateFormat: "yyyy-MM-dd HH:mm:ss"
          csvTsvSchemaAttr: [1, 4, 5, 6]
          gridBBox: [115.5, 39.6, 117.6, 41.1]
          numGridCells: 50
          cellLength: 0
          delimiter: "\\\\t"
        outputStream: {topicName: "o"}
        query:
          option: 51
          radius: 0.05
          k: 3
          thresholds: {trajDeletion: 1000, outOfOrderTuples: 2}
        window: {type: "TIME", interval: 5, step: 5}
        """))
    p = Params.from_yaml(str(y))
    assert p.input1.delimiter == "\t"
    assert p.input1.csv_tsv_schema == [1, 4, 5, 6]
    assert p.input1.date_format == "%Y-%m-%d %H:%M:%S"
    assert p.query.allowed_lateness_s == 2
    # inputStream2 defaults to inputStream1
    assert p.input2.topic_name == "t"


@pytest.mark.parametrize("mutate,err_key", [
    (lambda d: d["inputStream1"].pop("topicName"), "topicName"),
    (lambda d: d["inputStream1"].update(format="SHP"), "format"),
    (lambda d: d["inputStream1"].update(numGridCells=0, cellLength=0),
     "numGridCells"),
    (lambda d: d["query"].pop("option"), "option"),
    (lambda d: d["window"].update(interval=0), "interval"),
    (lambda d: d["query"].update(aggregateFunction="MODE"), "aggregateFunction"),
])
def test_validation_errors(mutate, err_key):
    import yaml

    with open(CONF) as f:
        d = yaml.safe_load(f)
    mutate(d)
    with pytest.raises(ConfigError):
        Params.from_dict(d)


# ------------------------------------------------------------------ dispatch


def test_case_table_shape():
    # 9 pairs x {window, realtime} x {range, knn, join} = 54 core cases
    core = [s for s in CASES.values()
            if s.family in ("range", "knn", "join") and not s.latency]
    assert len(core) == 54
    assert CASES[1] == CaseSpec("range", "Point", "Point", "window")
    assert CASES[42].family == "range" and CASES[42].mode == "realtime"
    assert CASES[51].family == "knn" and CASES[91].query == "LineString"
    assert CASES[141].family == "join"
    assert CASES[8].latency and CASES[59].latency and CASES[108].latency
    assert CASES[2030].naive and CASES[2090].naive and CASES[2011].naive
    assert CASES[501].fmt == "WKT" and not CASES[501].timestamped
    assert CASES[906].fmt == "TSV" and CASES[906].timestamped


def _params(option: int, **qkw) -> Params:
    p = Params.from_yaml(CONF)
    p.query.option = option
    for k, v in qkw.items():
        setattr(p.query, k, v)
    return p


def _synth_lines(n_traj=8, steps=6):
    grid = UniformGrid(115.5, 117.6, 39.6, 41.1, num_grid_partitions=100)
    pts = list(SyntheticPointSource(grid, num_trajectories=n_traj,
                                    steps=steps, seed=3))
    return [serialize_spatial(p, "GeoJSON") for p in pts], pts, grid


def test_option1_matches_direct_operator():
    lines, pts, grid = _synth_lines()
    p = _params(1, radius=0.5)
    via_driver = list(run_option(p, lines))
    conf = QueryConfiguration(QueryType.WindowBased, 10_000, 5_000)
    direct = list(PointPointRangeQuery(conf, grid).run(
        iter(pts), Point.create(116.5, 40.5, grid), 0.5))
    assert len(via_driver) == len(direct) > 0
    for a, b in zip(via_driver, direct):
        assert a.window_start == b.window_start
        assert sorted(r.obj_id for r in a.records) == \
            sorted(r.obj_id for r in b.records)


def test_option2_realtime():
    lines, _, _ = _synth_lines()
    out = list(run_option(_params(2, radius=0.5), lines))
    assert out and all(isinstance(r, WindowResult) for r in out)


def test_option51_knn():
    lines, _, _ = _synth_lines()
    out = list(run_option(_params(51, radius=0.0, k=3), lines))
    assert out
    for r in out:
        assert r.extras["k"] == 3
        assert len(r.records) <= 3
        dists = [d for _, d in r.records]
        assert dists == sorted(dists)


def test_option101_join_needs_stream2():
    lines, _, _ = _synth_lines()
    with pytest.raises(ValueError):
        list(run_option(_params(101), lines))
    out = list(run_option(_params(101, radius=0.3), lines, lines[:12]))
    assert any(r.records for r in out)


def test_option8_latency_extras():
    lines, _, _ = _synth_lines()
    out = list(run_option(_params(8, radius=0.5), lines))
    assert out
    assert all("latency_ms" in r.extras for r in out)
    assert all(l >= 0 for r in out for l in r.extras["latency_ms"])


def test_trajectory_options():
    lines, _, _ = _synth_lines()
    # TStats realtime (205)
    out = list(run_option(_params(205), lines))
    assert out
    # TFilter windowed (202) with an explicit id set
    p = _params(202)
    p.query.traj_ids = ["traj-0", "traj-1"]
    out = list(run_option(p, lines))
    ids = {r.obj_id for w in out for r in w.records}
    assert ids and ids <= {"traj-0", "traj-1"}
    # TKNN naive twin (2011) agrees with pruned (211) on result object ids
    pruned = list(run_option(_params(211, radius=0.8, k=4), lines))
    naive = list(run_option(_params(2011, radius=0.8, k=4), lines))
    def flat(ws):
        return sorted({rec[0] if isinstance(rec, tuple) else rec.obj_id
                       for w in ws for rec in w.records})
    assert flat(pruned) == flat(naive)


def test_deser_roundtrip_options():
    lines, pts, _ = _synth_lines(n_traj=2, steps=2)
    # 701: GeoJSON trajectory round-trip
    out = list(run_option(_params(701), lines))
    assert len(out) == len(lines)
    for obj, ser in out:
        assert obj.obj_id.startswith("traj-")
        assert json.loads(ser)["geometry"]["type"] == "Point"
    # 501: WKT CSV point round-trip
    wkt_lines = [serialize_spatial(p, "WKT") for p in pts]
    out = list(run_option(_params(501), wkt_lines))
    assert all("POINT" in ser for _, ser in out)


def test_deser_geometrycollection_options():
    """Driver cases 504/604/804/904: WKT GeometryCollection round-trips
    (plain + trajectory, comma + TAB), Deserialization.java:836,854."""
    gc_wkt = ("GEOMETRYCOLLECTION (POINT (116.5 40.5), "
              "LINESTRING (116.0 40.0, 116.1 40.1))")
    for option, line in [
        (504, gc_wkt),
        (604, gc_wkt),
        (804, f"t9, 1700000000000, {gc_wkt}"),
        (904, f"t9\t1700000000000\t{gc_wkt}"),
    ]:
        (obj, ser), = run_option(_params(option), [line])
        assert type(obj).__name__ == "GeometryCollection", option
        assert len(obj.geometries) == 2, option
        if option in (804, 904):
            # trajectory variants carry oid/ts through serialization as
            # prefix fields (the reference's WKT output schemas include
            # both, Serialization.java:53-96; prefix-normalized here)
            assert obj.obj_id == "t9" and obj.timestamp == 1700000000000
            assert ser.startswith("t9"), option
            assert "GEOMETRYCOLLECTION (" in ser, option
        else:
            assert ser.startswith("GEOMETRYCOLLECTION ("), option


def test_tsv_wkt_deser_uses_tab():
    """Options 601-605/901-905 are the TAB-separated WKT families: prefix
    fields must split on TAB regardless of the configured delimiter."""
    line = "obj7\t1700000000000\tPOINT (116.5 40.5)"
    out = list(run_option(_params(901), [line]))
    (obj, ser), = out
    assert obj.obj_id == "obj7"
    assert obj.timestamp == 1700000000000
    assert CASES[601].delim == "\t" and CASES[501].delim is None


def test_count_window_type_drives_count_mode():
    """window.type COUNT runs sliding count windows through the driver
    (implemented here; the reference declares CountBased and throws "Not
    yet support", QueryType.java:6). Joins still raise — the count trigger
    is ambiguous over two streams."""
    p = _params(1, radius=0.5)
    p.window.type = "COUNT"
    p.window.interval_s = 8   # COUNTS in count mode, like tAggregate
    p.window.step_s = 4
    lines, pts, _ = _synth_lines(n_traj=4, steps=6)
    out = list(run_option(p, lines))
    assert len(out) == len(pts) // 4
    p.query.option = 101
    with pytest.raises(NotImplementedError):
        list(run_option(p, lines, lines))


def test_synthetic_harness_option99():
    """One smoke run exercises every trajectory family, like the reference
    harness sketch (StreamingJob.java:1571-1618)."""
    out = list(run_option(_params(99), []))
    assert out
    fams = {r.extras.get("family") for r in out if hasattr(r, "extras")}
    assert fams == {"tfilter", "trange", "tstats", "taggregate",
                    "tjoin", "tknn"}


def test_unknown_option():
    with pytest.raises(ValueError):
        list(run_option(_params(4999), []))


def test_query_geometry_bracket_string_forms():
    """queryPoints/queryPolygons accept the reference's CLI bracket-string
    form (HelperClass.java:145-179) as well as YAML lists."""
    from spatialflink_tpu.config import QueryConfig

    q = QueryConfig.from_dict({
        "option": 1,
        "queryPoints": "[116.5, 40.5], [117.0, 40.7]",
        "queryPolygons": "[[116.5, 40.5], [117.6, 40.5], [117.6, 41.4]], "
                         "[[117.5, 40.5], [118.6, 40.5], [118.6, 41.4]]",
    })
    assert q.query_points == [(116.5, 40.5), (117.0, 40.7)]
    assert len(q.query_polygons) == 2
    assert q.query_polygons[0][0] == (116.5, 40.5)


# ------------------------------------------------------------------ CLI


def test_cli_main(tmp_path, capsys):
    lines, _, _ = _synth_lines(n_traj=4, steps=4)
    inp = tmp_path / "in.jsonl"
    inp.write_text("\n".join(lines) + "\n")
    rc = main(["--config", CONF, "--input1", str(inp), "--option", "1"])
    assert rc == 0
    cap = capsys.readouterr()
    assert "emitted" in cap.err
    assert "window" in cap.out


def test_output_file_writes_serialized_records(tmp_path):
    """--output writes every result record serialized in --output-format —
    the reference's output Kafka topic (Serialization.java schemas), as a
    file."""
    lines, pts, grid = _synth_lines()
    inp = tmp_path / "pts.geojson"
    inp.write_text("\n".join(lines))
    import shutil

    cfg = tmp_path / "conf.yml"
    shutil.copy(CONF, cfg)
    out = tmp_path / "out.wkt"
    rc = main(["--config", str(cfg), "--input1", str(inp),
               "--output", str(out), "--output-format", "WKT"])
    assert rc == 0
    recs = out.read_text().strip().splitlines()
    # field-carrying WKT lines: "oid, ts, POINT (...)" (reference output
    # schemas include both fields, Serialization.java:53-96)
    assert recs and all("POINT" in r for r in recs)
    # round-trips through the WKT parser
    from spatialflink_tpu.streams.formats import parse_spatial

    assert parse_spatial(recs[0], "WKT", grid).obj_id is not None


def test_output_file_covers_deser_results(tmp_path):
    # deser results are (obj, serialized) pairs; --output must write the
    # object serialized in the OUTPUT format (the reference produces these
    # to the output topic, StreamingJob.java:1289-1545)
    import shutil

    line = "GEOMETRYCOLLECTION (POINT (1 2), LINESTRING (0 0, 1 1))"
    inp = tmp_path / "gc.wkt"
    inp.write_text(line)
    cfg = tmp_path / "conf.yml"
    shutil.copy(CONF, cfg)
    out = tmp_path / "out.wkt"
    rc = main(["--config", str(cfg), "--input1", str(inp), "--option", "504",
               "--output", str(out), "--output-format", "WKT"])
    assert rc == 0
    recs = out.read_text().strip().splitlines()
    assert len(recs) == 1 and recs[0].startswith("GEOMETRYCOLLECTION (")


def test_output_file_join_pairs_are_serialized(tmp_path):
    # join records are (a, b) pairs: written as a JSON array of the two
    # per-element serializations (never Python reprs)
    import json as _json
    import shutil

    lines, pts, grid = _synth_lines()
    inp = tmp_path / "pts.geojson"
    inp.write_text("\n".join(lines))
    cfg = tmp_path / "conf.yml"
    shutil.copy(CONF, cfg)
    out = tmp_path / "pairs.wkt"
    rc = main(["--config", str(cfg), "--input1", str(inp),
               "--input2", str(inp), "--option", "101",
               "--output", str(out), "--output-format", "WKT"])
    assert rc == 0
    recs = out.read_text().strip().splitlines()
    assert recs
    pair = _json.loads(recs[0])
    assert len(pair) == 2 and all("POINT" in s for s in pair)


def test_cli_profile_writes_trace_with_operator_annotations(tmp_path):
    """--profile DIR captures a jax.profiler trace of the run (SURVEY §5
    tracing ≙ the reference's Flink web UI, StreamingJob.java:70-72) with
    per-operator dispatch/merge spans, named as a telemetry session
    names them."""
    import glob
    import gzip

    lines, _, _ = _synth_lines(n_traj=4, steps=4)
    inp = tmp_path / "in.jsonl"
    inp.write_text("\n".join(lines) + "\n")
    prof = tmp_path / "trace"
    rc = main(["--config", CONF, "--input1", str(inp), "--option", "1",
               "--profile", str(prof)])
    assert rc == 0
    assert glob.glob(str(prof / "plugins" / "profile" / "*" / "*.xplane.pb"))
    js = glob.glob(str(prof / "plugins" / "profile" / "*" /
                       "*.trace.json.gz"))
    assert js
    body = gzip.open(js[0], "rt", errors="replace").read()
    assert "range.dispatch" in body
    assert "range.merge" in body


def test_cli_mesh_validation_after_overrides(tmp_path):
    import shutil

    lines, pts, grid = _synth_lines()
    inp = tmp_path / "pts.geojson"
    inp.write_text("\n".join(lines))
    cfg = tmp_path / "conf.yml"
    shutil.copy(CONF, cfg)
    # valid: hosts and devices both from the CLI
    rc = main(["--config", str(cfg), "--input1", str(inp),
               "--devices", "8", "--hosts", "2"])
    assert rc == 0
    # invalid combinations fail fast with an argparse error, not a traceback
    import pytest as _pytest

    with _pytest.raises(SystemExit):
        main(["--config", str(cfg), "--input1", str(inp), "--hosts", "3"])
    with _pytest.raises(SystemExit):
        main(["--config", str(cfg), "--input1", str(inp), "--hosts", "-2"])
    with _pytest.raises(SystemExit):
        main(["--config", str(cfg), "--input1", str(inp), "--hosts", "2"])


def test_cli_enables_compilation_cache(tmp_path, monkeypatch):
    """The CLI keeps XLA compilations where ``JAX_COMPILATION_CACHE_DIR``
    says, and otherwise at the fixed ``<checkout>/.jax_cache`` — never a
    per-user or per-run directory, which a fresh machine would miss."""
    import os

    import jax

    from spatialflink_tpu import driver

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(
        driver.__file__)))
    assert driver.CHECKOUT_CACHE_DIR == os.path.join(checkout, ".jax_cache")
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path / "xdg"))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path / "pre"))
        assert driver.enable_compilation_cache() == (
            driver.CHECKOUT_CACHE_DIR, None)
        assert jax.config.jax_compilation_cache_dir == \
            driver.CHECKOUT_CACHE_DIR
        assert os.path.isdir(driver.CHECKOUT_CACHE_DIR)
        assert not (tmp_path / "xdg").exists()

        # an explicit env var wins over the default
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "own"))
        assert driver.enable_compilation_cache() == (
            str(tmp_path / "own"), None)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "own")

        # a cache that cannot be made is reported, not fatal
        (tmp_path / "file").write_text("")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "file" / "sub"))
        cache, err = driver.enable_compilation_cache()
        assert cache is None and err
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
