"""Latency-decomposition plane tests: the stage-residency budget's
sums-to-total invariant per window, per-query record→emit demux at the
router (every route counts — the record-latency fix), backpressure-series
bounds and stall annotation, the /latency endpoint schema + 404/405, the
p99_emit_ms SLO keys (global /healthz flip + per-query transition counts),
the extended telemetry-off hot-path spy, and the --kafka-follow --chaos
acceptance run fetching /latency mid-run."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest
import yaml

from spatialflink_tpu import driver
from spatialflink_tpu.config import StreamConfig
from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import Point
from spatialflink_tpu.operators import (PointPointRangeQuery,
                                        QueryConfiguration, QueryType)
from spatialflink_tpu.runtime.health import HealthEvaluator
from spatialflink_tpu.runtime.opserver import OpServer, active_server
from spatialflink_tpu.runtime.queryplane import QueryRegistry, QueryRouter
from spatialflink_tpu.streams.formats import serialize_spatial
from spatialflink_tpu.utils.latencyplane import (CHAIN_STAGES,
                                                 DOWNSTREAM_STAGES,
                                                 LatencyPlane)
from spatialflink_tpu.utils.metrics import scoped_registry
from spatialflink_tpu.utils.telemetry import (active, status_snapshot,
                                              telemetry_session)

pytestmark = pytest.mark.latencyplane

GRID = UniformGrid(115.5, 117.6, 39.6, 41.1, num_grid_partitions=100)
CFG = StreamConfig(format="CSV", date_format=None, csv_tsv_schema=[0, 1, 2, 3])

#: the sum invariant's tolerance: the ingest stamp is an int-ms wall clock
#: while the chain timestamps are float seconds, so the budget may differ
#: from record→emit by sub-ms float association — never more
RESIDUAL_MS = 1.0


def _lines(n, span_ms=100_000):
    rng = np.random.default_rng(0)
    t0 = 1_700_000_000_000
    return [f"v{i % 97},{t0 + i * span_ms // n},"
            f"{115.5 + rng.random() * 2:.6f},"
            f"{39.6 + rng.random() * 1.5:.6f}" for i in range(n)]


def _run_range(lines, conf=None, radius=0.5):
    conf = conf or QueryConfiguration(QueryType.WindowBased, 10_000, 5_000)
    op = PointPointRangeQuery(conf, GRID)
    stream = driver.decode_stream(iter(lines), CFG, GRID)
    qp = Point.create(116.5, 40.3, GRID, obj_id="q")
    return [(r.window_start, len(r.records)) for r in op.run(stream, qp,
                                                             radius)]


def _chain_sum(row):
    return sum(v for k, v in row["stages"].items() if k in CHAIN_STAGES)


class TestStageBudget:
    def test_decomposition_sums_to_total_per_window(self):
        with scoped_registry(), telemetry_session() as tel:
            out = _run_range(_lines(20_000))
            plane = tel.latency
            rows = plane.recent_rows(64)
        assert len(out) == 21
        assert plane.windows == len(out)
        assert plane.record_emit.count == len(out)
        assert plane.max_residual_ms <= RESIDUAL_MS
        for row in rows:
            assert set(row["stages"]) == set(CHAIN_STAGES)
            assert all(v >= 0.0 for v in row["stages"].values())
            assert row["record_emit_ms"] is not None
            # the invariant: the consecutive-interval stages sum to the
            # measured record→emit latency within timer resolution
            assert abs(_chain_sum(row) - row["record_emit_ms"]) \
                <= RESIDUAL_MS, row
        # every chain stage histogram saw every window
        for stage in CHAIN_STAGES:
            assert plane.stages[stage].count == len(out), stage

    def test_pane_mode_budgets_identically(self):
        conf = QueryConfiguration(QueryType.WindowBased, 20_000, 5_000,
                                  panes=True)
        with scoped_registry(), telemetry_session() as tel:
            out = _run_range(_lines(20_000), conf=conf)
            plane = tel.latency
        assert plane.windows == len(out) > 0
        assert plane.max_residual_ms <= RESIDUAL_MS
        for row in plane.recent_rows(64):
            assert abs(_chain_sum(row) - row["record_emit_ms"]) \
                <= RESIDUAL_MS

    def test_true_seal_time_splits_buffer_from_queue(self):
        # windows sealed in one watermark sweep are stamped BEFORE the
        # first yields: later windows of the sweep must accumulate queue
        # time (their wait behind earlier windows' eval), and the chain
        # still sums
        with scoped_registry(), telemetry_session() as tel:
            _run_range(_lines(40_000))
            rows = tel.latency.recent_rows(64)
        assert sum(r["stages"]["queue"] for r in rows) > 0.0

    def test_bulk_payloads_skip_record_emit_but_feed_stages(self):
        # records without an ingest stamp (ingestion_time 0, e.g. objects
        # built outside any decode): the budget chain still feeds the
        # stage histograms, but record→emit (whose definition needs the
        # stamp) honestly records nothing
        import dataclasses

        recs = [dataclasses.replace(p, ingestion_time=0)
                for p in driver.decode_stream(iter(_lines(5_000)), CFG, GRID)]
        conf = QueryConfiguration(QueryType.WindowBased, 10_000, 5_000)
        with scoped_registry(), telemetry_session() as tel:
            op = PointPointRangeQuery(conf, GRID)
            qp = Point.create(116.5, 40.3, GRID, obj_id="q")
            out = list(op.run(iter(recs), qp, 0.5))
            plane = tel.latency
        assert plane.windows == len(out) > 0
        assert plane.record_emit.count == 0
        assert plane.stages["dispatch"].count == len(out)

    def test_downstream_sink_stage_appends_by_window_start(self):
        plane = LatencyPlane()
        t = time.time()
        plane.window_complete("range", 1000, 2000, int(t * 1000) - 5,
                              {"buffer": 1.0, "queue": 1.0, "dispatch": 1.0,
                               "inflight": 1.0, "merge": 0.5, "emit": 0.5},
                              t)
        plane.note_downstream("sink", 1000, t, t + 0.002)
        row = plane.recent_rows(1)[0]
        assert row["stages"]["sink"] == pytest.approx(2.0, abs=0.5)
        assert plane.stages["sink"].count == 1
        # downstream stages are OUTSIDE the sum invariant
        assert set(DOWNSTREAM_STAGES) & set(CHAIN_STAGES) == set()


class TestPerQueryDemux:
    def _registry(self, pts, routes=None, slo=None):
        reg = QueryRegistry("range", radius=0.5)
        for i, (x, y) in enumerate(pts):
            spec = {"id": f"q{i}", "x": x, "y": y}
            if routes:
                spec["route"] = routes[i]
            if slo:
                spec["slo"] = slo
            reg.admit(spec)
        reg.apply()
        return reg

    def test_router_demux_vs_dedicated_runs(self, tmp_path):
        lines = _lines(20_000)
        pts = [(116.5, 40.3), (116.0, 40.0)]
        conf = QueryConfiguration(QueryType.WindowBased, 10_000, 5_000)
        outs = [tmp_path / "q0.jsonl", tmp_path / "q1.jsonl"]
        with scoped_registry(), telemetry_session() as tel:
            reg = self._registry(pts, routes=[f"file:{o}" for o in outs])
            op = PointPointRangeQuery(conf, GRID)
            stream = driver.decode_stream(iter(lines), CFG, GRID)
            router = QueryRouter(reg)
            n_win = 0
            for w in op.run_dynamic(stream, reg, 0.5):
                router.route(w)
                n_win += 1
            router.close()
            plane = tel.latency
            # per-query record→emit histograms observed at the demux
            # point, one sample per routed window
            assert set(plane.queries) == {"q0", "q1"}
            for qid in ("q0", "q1"):
                assert plane.queries[qid].count == n_win
                assert plane.query_p99(qid) > 0
            # the record-latency fix: windows routed to file: feed the
            # shared record-latency-ms histogram (previously only the
            # driver's stdout loop observed it)
            assert tel.histograms["record-latency-ms"].count > 0
        # identity: each routed file carries exactly the dedicated run's
        # per-window record counts
        for i, (x, y) in enumerate(pts):
            op = PointPointRangeQuery(conf, GRID)
            stream = driver.decode_stream(iter(lines), CFG, GRID)
            dedicated = [(r.window_start, len(r.records)) for r in op.run(
                stream, Point.create(x, y, GRID), 0.5)]
            docs = [json.loads(ln) for ln in
                    outs[i].read_text().splitlines()]
            assert [(d["window"][0], d["count"]) for d in docs] == dedicated

    def test_per_query_p99_emit_slo_breach_transitions(self):
        lines = _lines(10_000)
        conf = QueryConfiguration(QueryType.WindowBased, 10_000, 5_000)
        with scoped_registry() as sreg, telemetry_session() as tel:
            # an impossible 1 microsecond SLO: every window breaches, but
            # transitions count ONCE until recovery
            reg = self._registry([(116.5, 40.3)],
                                 slo={"p99_emit_ms": 0.001})
            op = PointPointRangeQuery(conf, GRID)
            stream = driver.decode_stream(iter(lines), CFG, GRID)
            router = QueryRouter(reg)
            for w in op.run_dynamic(stream, reg, 0.5):
                router.route(w)
            entry = reg.active_entries()[0]
            assert entry.slo_ok is False
            assert entry.slo_breaches == 1  # transition, not per window
            assert sreg.counter("query-slo-breaches").count == 1
            kinds = [e["kind"] for e in tel.events.list()]
            assert "query-slo-breach" in kinds
            # the ledger row carries the verdict
            row = [q for q in reg.status()["queries"]
                   if q["id"] == "q0"][0]
            assert row["slo"] == {"ok": False, "breaches": 1}

    def test_p99_emit_ms_is_a_valid_query_spec_slo_key(self):
        from spatialflink_tpu.runtime.queryplane import (QuerySpec,
                                                         QuerySpecError)

        spec = QuerySpec.from_dict({"id": "a", "family": "range", "x": 1.0,
                                    "y": 2.0, "slo": {"p99_emit_ms": 10}})
        assert spec.slo == {"p99_emit_ms": 10.0}
        with pytest.raises(QuerySpecError):
            QuerySpec.from_dict({"id": "a", "family": "range", "x": 1.0,
                                 "y": 2.0, "slo": {"p42_emit_ms": 10}})


class TestBackpressureSeries:
    def test_series_bounded_with_schema(self):
        plane = LatencyPlane(series_capacity=4, tick_interval_s=0.01)
        with scoped_registry(), telemetry_session() as tel:
            for i in range(10):
                plane.window_complete(
                    "range", i * 1000, i * 1000 + 1000, None,
                    {"dispatch": 1.0}, time.time())
                plane.tick(tel)
        assert len(plane.series) == 4  # bounded
        bucket = plane.series[-1]
        assert {"ts_ms", "decode_buffer_depth", "window_backlog",
                "backlog_residency_ms", "control_queue_depth",
                "sink_queue_depth", "watermark_lag_ms", "event_time_ms",
                "wm_slope", "stall", "stage_delta_s"} <= set(bucket)
        assert bucket["event_time_ms"] == 10_000

    def test_stall_annotation_and_stage_budget_events(self):
        plane = LatencyPlane(tick_interval_s=0.01)
        with scoped_registry() as reg, telemetry_session() as tel:
            plane.window_complete("range", 0, 5_000, None,
                                  {"dispatch": 1.0}, time.time())
            reg.meter("ingest-throughput").mark(100)
            plane.tick(tel)
            assert plane.series[-1]["stall"] is False
            # records keep flowing but event time is frozen -> stall
            reg.meter("ingest-throughput").mark(100)
            time.sleep(0.02)
            plane.tick(tel)
            assert plane.series[-1]["stall"] is True
            kinds = [e["kind"] for e in tel.events.list()]
            assert "backpressure-stall" in kinds
            # one stage-budget event per closed bucket, with the deltas
            assert kinds.count("stage-budget") == 2
            ev = [e for e in tel.events.list()
                  if e["kind"] == "stage-budget"][-1]
            assert "dispatch_s" in ev and "windows" in ev

    def test_backlog_residency_tracks_oldest_inflight(self):
        plane = LatencyPlane()
        t = time.time()
        plane.note_dispatch(1000, t - 1.0)
        plane.note_dispatch(2000, t)
        assert plane.backlog_residency_ms(t) == pytest.approx(1000.0,
                                                              abs=50)
        plane.window_complete("range", 1000, 2000, None, {}, t)
        assert plane.backlog_residency_ms(t) == pytest.approx(0.0, abs=50)


class TestLatencyEndpoint:
    def _get(self, url, timeout=5):
        try:
            resp = urllib.request.urlopen(url, timeout=timeout)
            return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def test_latency_schema_live(self):
        with scoped_registry(), telemetry_session():
            _run_range(_lines(5_000))
            srv = OpServer(port=0).start()
            try:
                code, doc = self._get(srv.url + "/latency")
            finally:
                srv.close()
        assert code == 200
        assert {"ts_ms", "stages", "chain_stages", "downstream_stages",
                "record_emit", "queries", "recent", "sum_check",
                "backpressure"} <= set(doc)
        assert doc["record_emit"]["count"] > 0
        assert doc["sum_check"]["windows"] > 0
        assert doc["sum_check"]["max_residual_ms"] <= RESIDUAL_MS
        assert set(CHAIN_STAGES) <= set(doc["stages"])
        for row in doc["recent"]:
            assert {"query", "window_start", "window_end", "stages",
                    "record_emit_ms"} <= set(row)
        assert isinstance(doc["backpressure"]["series"], list)

    def test_latency_without_session_explains(self):
        assert active() is None
        srv = OpServer(port=0).start()
        try:
            code, doc = self._get(srv.url + "/latency")
        finally:
            srv.close()
        assert code == 200
        assert doc["stages"] == {} and "note" in doc

    def test_latency_405_and_404(self):
        srv = OpServer(port=0).start()
        try:
            req = urllib.request.Request(srv.url + "/latency",
                                         method="POST", data=b"{}")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=3)
            assert ei.value.code == 405
            assert ei.value.headers["Allow"] == "GET"
            code, doc = self._get(srv.url + "/latency/nope")
            assert code == 404
            # the endpoint index names the new route
            code, doc = self._get(srv.url + "/definitely-not")
            assert code == 404 and "/latency" in doc["endpoints"]
        finally:
            srv.close()


class TestEmitSLO:
    def test_global_p99_emit_ms_flips_healthz(self):
        with scoped_registry() as reg, telemetry_session() as tel:
            health = HealthEvaluator.from_spec("p99_emit_ms=50")
            srv = OpServer(port=0, health=health).start()
            try:
                # no windows budgeted yet: unknown counts healthy
                code, verdict = TestLatencyEndpoint()._get(
                    srv.url + "/healthz")
                assert code == 200 and verdict["healthy"]
                # feed a breaching record→emit distribution
                t = time.time()
                for i in range(5):
                    tel.latency.window_complete(
                        "range", i, i + 1, int(t * 1000) - 500,
                        {"buffer": 500.0}, t)
                code, verdict = TestLatencyEndpoint()._get(
                    srv.url + "/healthz")
                assert code == 503 and not verdict["healthy"]
                assert verdict["checks"]["p99_emit_ms"]["ok"] is False
                assert verdict["checks"]["p99_emit_ms"]["value"] > 50
                assert reg.counter("slo-breaches").count == 1
            finally:
                srv.close()

    def test_digest_carries_latency_block(self):
        with scoped_registry(), telemetry_session() as tel:
            _run_range(_lines(5_000))
            snap = status_snapshot(tel)
        lat = snap["status"]["latency"]
        assert lat["record_emit_ms"]["count"] > 0
        assert lat["dominant_stage"] in CHAIN_STAGES
        # snapshot block parity (reporter JSONL / /status / digest share it)
        assert snap["latency"]["windows"] > 0
        assert snap["latency"]["max_residual_ms"] <= RESIDUAL_MS


class _PlaneSpy:
    """Counts every LatencyPlane touch process-wide — the extended
    telemetry-off hot-path contract: the latency plane must cost a
    session-less run exactly zero calls (same rule as spans, cost
    profiles, trace book, flight recorder)."""

    METHODS = ("note_seal", "pop_seal", "note_dispatch", "window_complete",
               "note_downstream", "query_emit", "tick")

    def __init__(self, monkeypatch):
        self.calls = 0
        spy = self

        def wrap(name):
            orig = getattr(LatencyPlane, name)

            def spied(inner_self, *a, **k):
                spy.calls += 1
                return orig(inner_self, *a, **k)

            monkeypatch.setattr(LatencyPlane, name, spied)

        for name in self.METHODS:
            wrap(name)


class TestHotPathSpy:
    def _input(self, tmp_path):
        p = tmp_path / "pts.csv"
        p.write_text("\n".join(_lines(500)) + "\n")
        return str(p)

    def _conf(self, tmp_path):
        with open("conf/spatialflink-conf.yml") as f:
            d = yaml.safe_load(f)
        d["inputStream1"] = dict(d["inputStream1"])
        d["inputStream1"]["format"] = "CSV"
        d["inputStream1"]["csvTsvSchemaAttr"] = [0, 1, 2, 3]
        d["inputStream1"]["dateFormat"] = None
        p = tmp_path / "conf.yml"
        p.write_text(yaml.safe_dump(d))
        return str(p)

    def test_zero_plane_touches_without_session(self, tmp_path,
                                                monkeypatch):
        from spatialflink_tpu.driver import main

        spy = _PlaneSpy(monkeypatch)
        assert active() is None
        assert main(["--config", self._conf(tmp_path),
                     "--input1", self._input(tmp_path), "--option", "1"]) \
            == 0
        assert spy.calls == 0, \
            "a session-less run must never touch the latency plane"

    def test_zero_plane_touches_with_idle_status_port(self, tmp_path,
                                                      monkeypatch):
        from spatialflink_tpu.driver import main

        spy = _PlaneSpy(monkeypatch)
        assert active() is None
        assert main(["--config", self._conf(tmp_path),
                     "--input1", self._input(tmp_path), "--option", "1",
                     "--status-port", "0"]) == 0
        assert spy.calls == 0

    def test_session_run_touches_the_plane(self, tmp_path, monkeypatch):
        # the spy itself must be able to see calls (guards against the
        # zero assertions passing because the wiring is dead)
        with scoped_registry(), telemetry_session():
            spy = _PlaneSpy(monkeypatch)
            _run_range(_lines(2_000))
        assert spy.calls > 0


class TestPostmortemBundle:
    def test_bundle_carries_latency_and_doctor_prints_the_table(
            self, tmp_path, capsys):
        import io
        import os

        from spatialflink_tpu import doctor
        from spatialflink_tpu.utils import deviceplane

        with scoped_registry(), telemetry_session():
            rec = deviceplane.FlightRecorder(str(tmp_path),
                                             config={"test": True})
            try:
                _run_range(_lines(5_000))
                bundle = rec.dump("test")
            finally:
                rec.close()
        manifest = json.load(open(os.path.join(bundle, "manifest.json")))
        assert manifest["schema"] == deviceplane.BUNDLE_SCHEMA == 3
        assert "latency.json" in manifest["files"]
        lat = json.load(open(os.path.join(bundle, "latency.json")))
        assert set(CHAIN_STAGES) <= set(lat["stages"])
        assert lat["sum_check"]["windows"] == 21
        assert "series" in lat["backpressure"]
        # doctor summarize prints the stage-budget table offline
        out = io.StringIO()
        assert doctor.summarize(bundle, out=out) == 0
        text = out.getvalue()
        assert "latency    stage" in text
        for stage in CHAIN_STAGES:
            assert f"latency    {stage}" in text
        assert "sum check" in text
        # and the machine-readable digest carries the p99
        out = io.StringIO()
        doctor.summarize(bundle, as_json=True, out=out)
        d = json.loads(out.getvalue())
        assert d["record_emit_p99_ms"] > 0
        assert d["budgeted_windows"] == 21


CONTROL = json.dumps({"geometry": {"type": "control", "coordinates": []}})


def _follow_conf(tmp_path, name):
    with open("conf/spatialflink-conf.yml") as f:
        d = yaml.safe_load(f)
    d["kafkaBootStrapServers"] = f"memory://{name}"
    d["window"].update(interval=1, step=1)
    d["query"]["thresholds"]["outOfOrderTuples"] = 0
    p = tmp_path / "conf.yml"
    p.write_text(yaml.safe_dump(d))
    return str(p)


class _LatencyPoller(threading.Thread):
    """Waits for the driver's ephemeral server, then polls /latency until
    the decomposition matures (budgeted windows + populated stages)."""

    def __init__(self):
        super().__init__(daemon=True)
        self.result: dict = {}

    def run(self):
        deadline = time.monotonic() + 40.0
        srv = None
        while time.monotonic() < deadline and srv is None:
            srv = active_server()
            if srv is None or srv.port is None:
                srv = None
                time.sleep(0.01)
        if srv is None:
            self.result["error"] = "status server never came up"
            return
        while time.monotonic() < deadline:
            try:
                resp = urllib.request.urlopen(srv.url + "/latency",
                                              timeout=2)
                doc = json.loads(resp.read())
            except Exception:
                time.sleep(0.05)
                continue
            if (doc.get("sum_check", {}).get("windows", 0) >= 2
                    and doc.get("record_emit", {}).get("count", 0) >= 2):
                self.result["latency"] = doc
                break
            time.sleep(0.05)
        else:
            self.result["error"] = "/latency never matured mid-run"
            return
        try:
            resp = urllib.request.urlopen(srv.url + "/status", timeout=2)
            self.result["status"] = json.loads(resp.read())
        except Exception as e:  # pragma: no cover - diagnostic only
            self.result["error"] = repr(e)


class TestFollowAcceptance:
    """The ISSUE acceptance run: --kafka-follow --chaos --status-port 0
    serving the live decomposition mid-run under injected transport
    faults."""

    def test_follow_chaos_latency_live(self, tmp_path):
        from spatialflink_tpu.driver import main
        from spatialflink_tpu.streams.kafka import (reset_memory_brokers,
                                                    resolve_broker)

        reset_memory_brokers()
        try:
            cfg = _follow_conf(tmp_path, "latencyplane-follow")
            broker = resolve_broker("memory://latencyplane-follow")

            def produce():
                # span ≥4 wall-clock window boundaries REGARDLESS of the
                # phase the test starts at within the second: a deferred
                # window's budget only lands when the NEXT window seals,
                # so the poller needs three windows sealed while the
                # stream is still live — 2.6s of production crossed 2 or
                # 3 boundaries depending on start phase and the
                # acceptance flaked on the wall clock
                for i in range(420):
                    p = Point.create(116.5 + 0.001 * (i % 40), 40.5, GRID,
                                     obj_id=f"veh{i % 7}",
                                     timestamp=int(time.time() * 1000))
                    broker.produce("points.geojson",
                                   serialize_spatial(p, "GeoJSON"))
                    time.sleep(0.01)
                broker.produce("points.geojson", CONTROL)

            t = threading.Thread(target=produce, daemon=True)
            poller = _LatencyPoller()
            t.start()
            poller.start()
            rc = main(["--config", cfg, "--kafka", "--kafka-follow",
                       "--option", "1", "--status-port", "0",
                       "--chaos", "seed=3,fail_next_fetches=2",
                       "--retry", "attempts=8,base_ms=1",
                       "--live-stats", "--telemetry-interval", "0.1"])
            t.join(timeout=30)
            poller.join(timeout=30)
            assert rc == 0
            res = poller.result
            assert "error" not in res, res
            doc = res["latency"]
            # the live decomposition under chaos: chain stages populated,
            # sum invariant holding, sink-commit (the Kafka window sink)
            # appended downstream
            for stage in CHAIN_STAGES:
                assert doc["stages"][stage]["count"] >= 2, stage
            assert doc["sum_check"]["max_residual_ms"] <= RESIDUAL_MS
            for row in doc["recent"]:
                if row["record_emit_ms"] is None:
                    continue
                chain = sum(v for k, v in row["stages"].items()
                            if k in CHAIN_STAGES)
                assert abs(chain - row["record_emit_ms"]) <= RESIDUAL_MS
            assert doc["stages"].get("sink", {}).get("count", 0) >= 1
            assert doc["stages"].get("sink-commit", {}).get("count", 0) >= 1
            # the digest block rides /status too
            lat = res["status"]["status"]["latency"]
            assert lat["record_emit_ms"]["count"] >= 2
            # plane died with the run
            assert active_server() is None
        finally:
            reset_memory_brokers()
