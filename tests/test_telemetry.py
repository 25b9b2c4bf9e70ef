"""Telemetry layer tests: streaming histograms vs numpy, span nesting and
exception propagation, reporter snapshot schema, registry scoping, grid
occupancy, and the driver acceptance runs (file + live kafka-follow) —
including the telemetry-OFF contract: no span/histogram calls on the record
loop when no session is active."""

import json
import os
import threading
import time

import numpy as np
import pytest
import yaml

from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import Point
from spatialflink_tpu.streams.formats import serialize_spatial
from spatialflink_tpu.utils import telemetry as telemetry_mod
from spatialflink_tpu.utils.metrics import MetricsRegistry, scoped_registry
from spatialflink_tpu.utils.telemetry import (
    StreamingHistogram,
    Telemetry,
    TelemetryReporter,
    active,
    prometheus_text,
    telemetry_session,
)

GRID = UniformGrid(115.5, 117.6, 39.6, 41.1, num_grid_partitions=100)

SNAPSHOT_KEYS = {"ts_ms", "uptime_s", "spans", "histograms", "gauges",
                 "counters", "degradation", "grid"}


def _write_points(path, n=50, t0=1_700_000_000_000, step_ms=500):
    with open(path, "w") as f:
        for i in range(n):
            p = Point.create(116.5 + 0.001 * i, 40.5, GRID, obj_id=f"o{i}",
                             timestamp=t0 + i * step_ms)
            f.write(serialize_spatial(p, "GeoJSON") + "\n")
    return str(path)


def _snapshots(tdir):
    with open(os.path.join(str(tdir), "telemetry.jsonl")) as f:
        return [json.loads(line) for line in f]


class TestStreamingHistogram:
    def test_percentiles_match_numpy_on_random_samples(self):
        # log-bucket resolution bound: geometric-midpoint error <=
        # sqrt(growth) ~ 4.4% at the default growth; allow headroom for
        # rank-vs-interpolation differences at the tails
        rng = np.random.default_rng(7)
        for dist in (rng.lognormal(2.0, 1.5, 4000),
                     rng.uniform(0.5, 500.0, 4000),
                     rng.exponential(50.0, 4000) + 0.01):
            h = StreamingHistogram("t")
            for v in dist:
                h.record(float(v))
            for p in (50, 90, 95, 99):
                est = h.percentile(p)
                ref = float(np.percentile(dist, p))
                assert est == pytest.approx(ref, rel=0.08), (p, est, ref)
        assert h.count == 4000
        assert h.max == pytest.approx(float(dist.max()))

    def test_constant_memory(self):
        h = StreamingHistogram("t")
        buckets = len(h.counts)
        for i in range(100_000):
            h.record(0.001 * (i + 1))
        assert len(h.counts) == buckets  # O(1) per record, no growth
        assert h.count == 100_000

    def test_empty_and_edge_values(self):
        h = StreamingHistogram("t")
        assert h.percentile(50) == 0.0
        assert h.to_dict() == {"count": 0}
        h.record(0.0)      # at/below lo -> underflow bucket, not a crash
        h.record(-5.0)
        h.record(1e12)     # overflow clamps to the last bucket
        assert h.count == 3
        assert h.percentile(100) == pytest.approx(1e12)

    def test_single_value(self):
        h = StreamingHistogram("t")
        h.record(42.0)
        for p in (1, 50, 99):
            assert h.percentile(p) == pytest.approx(42.0, rel=0.05)


class TestSpans:
    def test_nesting_records_both_and_self_time(self):
        tel = Telemetry()
        with tel.span("outer", query="q"):
            with tel.span("inner", query="q"):
                time.sleep(0.02)
        outer, inner = tel.spans["q.outer"], tel.spans["q.inner"]
        assert outer.count == 1 and inner.count == 1
        assert outer.total_s >= inner.total_s
        # nesting-aware: the child's time is excluded from the parent's self
        assert outer.self_s <= outer.total_s - inner.total_s + 0.005

    def test_exception_propagates_and_is_counted(self):
        tel = Telemetry()
        with pytest.raises(ValueError):
            with tel.span("boom"):
                raise ValueError("x")
        st = tel.spans["boom"]
        assert st.count == 1 and st.errors == 1
        assert st.total_s >= 0.0

    def test_observe_accumulates(self):
        tel = Telemetry()
        tel.observe("decode", 0.01)
        tel.observe("decode", 0.03)
        st = tel.spans["decode"]
        assert st.count == 2
        assert st.total_s == pytest.approx(0.04)
        assert st.max_s == pytest.approx(0.03)

    def test_query_scoping_separates_families(self):
        tel = Telemetry()
        with tel.span("dispatch", query="knn"):
            pass
        with tel.span("dispatch", query="range"):
            pass
        assert {"knn.dispatch", "range.dispatch"} <= set(tel.spans)


class TestGaugesAndOccupancy:
    def test_gauge_set_and_callable(self):
        tel = Telemetry()
        tel.gauge("a").set(3.5)
        tel.gauge("b", fn=lambda: 7.0)
        snap = tel.snapshot()
        assert snap["gauges"]["a"] == 3.5
        assert snap["gauges"]["b"] == 7.0

    def test_cell_occupancy_topk_and_skew(self):
        tel = Telemetry()
        # 3 records in one cell, 1 in another -> skew = 3 / 2
        tel.record_cells(np.array([11, 11, 11, 55, -1], dtype=np.int32))
        g = tel.snapshot()["grid"]
        assert g["occupied_cells"] == 2
        assert g["top_cells"][0] == [11, 3] or g["top_cells"][0] == (11, 3)
        assert g["skew"] == pytest.approx(1.5)

    def test_cell_occupancy_scalar_fast_path(self):
        # per-record ingest assigns one cell at a time (0-d arrays from
        # assign_cell on scalars); the scalar path must count identically
        # to the vectorized one, including dropping invalid cells
        tel = Telemetry()
        for c in (np.int32(7), np.array(7, dtype=np.int32), 7, -1):
            tel.record_cells(c)
        g = tel.snapshot()["grid"]
        assert g["occupied_cells"] == 1
        assert list(g["top_cells"][0]) == [7, 3]

    def test_session_hooks_grid_assignment(self):
        with telemetry_session() as tel:
            GRID.assign_cell(np.array([116.5, 116.5]), np.array([40.5, 40.5]))
            assert tel.snapshot()["grid"]["occupied_cells"] >= 1
        # hook restored: assignments outside the session are not observed
        from spatialflink_tpu.index import uniform_grid
        assert uniform_grid._CELL_OBSERVER is None


class TestReporter:
    def test_snapshot_schema_and_min_two_snapshots(self, tmp_path):
        with telemetry_session(str(tmp_path), interval_s=0.05) as tel:
            with tel.span("stage", query="q"):
                time.sleep(0.12)
            tel.histogram("lat").record(5.0)
            tel.gauge("g").set(1.0)
        snaps = _snapshots(tmp_path)
        assert len(snaps) >= 2  # immediate + periodic(s) + final
        for s in snaps:
            assert SNAPSHOT_KEYS <= set(s)
        last = snaps[-1]
        assert last["spans"]["q.stage"]["count"] == 1
        for k in ("count", "total_ms", "max_ms", "self_ms", "errors"):
            assert k in last["spans"]["q.stage"]
        for k in ("count", "sum", "min", "max", "p50", "p95", "p99"):
            assert k in last["histograms"]["lat"]
        assert last["gauges"]["g"] == 1.0

    def test_snapshot_carries_status_digest_and_health(self, tmp_path):
        """Every JSONL line embeds the shared operator digest — incl. the
        PR 3 pane-cache counters and PR 4 checkpoint gauges an operator
        reads first — and, with an SLO evaluator attached, the health
        verdict."""
        from spatialflink_tpu.runtime.health import HealthEvaluator

        with scoped_registry() as reg, telemetry_session(
                str(tmp_path), interval_s=5.0,
                health=HealthEvaluator.from_spec("dlq_depth=100")) as tel:
            reg.counter("pane-cache-hits").inc(3)
            reg.counter("pane-cache-misses").inc(1)
            reg.counter("checkpoints-written").inc(1)
            tel.gauge("checkpoint.seq").set(1.0)
        snaps = _snapshots(tmp_path)
        for s in snaps:
            assert "status" in s and "health" in s
        st = snaps[-1]["status"]
        assert st["pane_cache"] == {"hits": 3, "misses": 1, "hit_rate": 0.75}
        assert st["checkpoint"]["written"] == 1
        assert st["checkpoint"]["seq"] == 1.0
        assert "watermark_lag_ms" in st and "breaker_state" in st
        assert snaps[-1]["health"]["status"] == "ok"

    def test_prometheus_dump(self, tmp_path):
        with telemetry_session(str(tmp_path), interval_s=5.0) as tel:
            with tel.span("s"):
                pass
            tel.histogram("h").record(2.0)
            tel.gauge("g").set(4.0)
        prom = open(os.path.join(str(tmp_path), "metrics.prom")).read()
        for family in ("spatialflink_span_count", "spatialflink_span_seconds_total",
                       "spatialflink_histogram_quantile", "spatialflink_gauge",
                       "spatialflink_counter"):
            assert family in prom
        assert 'stage="s"' in prom and 'name="h"' in prom

    def test_crash_still_writes_final_snapshot(self, tmp_path):
        with pytest.raises(RuntimeError):
            with telemetry_session(str(tmp_path), interval_s=5.0) as tel:
                with pytest.raises(RuntimeError):
                    with tel.span("dead"):
                        raise RuntimeError("boom")
                raise RuntimeError("run crashed")
        snaps = _snapshots(tmp_path)
        assert len(snaps) >= 2
        assert snaps[-1]["spans"]["dead"]["errors"] == 1


class TestScopedRegistry:
    def test_counters_do_not_bleed_through(self):
        from spatialflink_tpu.utils import metrics as m

        outer = m.REGISTRY
        outer_before = outer.counter("scoped-test").count
        with scoped_registry() as reg:
            assert m.REGISTRY is reg
            m.REGISTRY.counter("scoped-test").inc(5)
            assert reg.counter("scoped-test").count == 5
        assert m.REGISTRY is outer
        assert outer.counter("scoped-test").count == outer_before

    def test_registry_reset(self):
        r = MetricsRegistry()
        r.counter("a").inc(3)
        r.meter("b").mark()
        r.reset()
        assert r.snapshot() == {}

    def test_telemetry_snapshot_reads_scoped_registry(self):
        with scoped_registry() as reg:
            reg.counter("retry-attempts").inc(2)
            tel = Telemetry()
            snap = tel.snapshot()
        assert snap["counters"]["retry-attempts"] == 2
        assert snap["degradation"] == {"retry-attempts": 2}


class TestLatencySink:
    def test_histogram_backed_percentile_and_bounded_memory(self):
        from spatialflink_tpu.streams.sinks import LatencySink

        sink = LatencySink()
        for i in range(5000):
            p = Point.create(116.5, 40.5, GRID, obj_id="a",
                             timestamp=int(time.time() * 1000))
            # stamp RIGHT before emit so the latency is ~10ms regardless of
            # how long the loop itself takes
            p.ingestion_time = time.time() * 1000 - 10.0
            sink.emit(p)
        assert sink.count == 5000
        assert sink.percentile(50) == pytest.approx(10.0, rel=0.3)
        # no unbounded per-record sample list anywhere on the sink
        assert not hasattr(sink, "latencies_ms")
        assert len(sink.hist.counts) < 1000


class _CallCounter:
    """Counts every Telemetry.span/observe, StreamingHistogram.record,
    CostProfiles feed, WindowTraceBook note, FlightRecorder note, and
    device-memory probe process-wide — the telemetry-off hot-path
    assertion (the PR 6 cost/trace plane AND the ISSUE 12 device plane
    must obey the same contract as the PR 2 spans: zero calls without a
    session; memory probes happen per snapshot/request only, and no
    snapshot is built during an unqueried run)."""

    def __init__(self, monkeypatch):
        from spatialflink_tpu.utils import deviceplane as deviceplane_mod
        from spatialflink_tpu.utils.accounting import TenantLedger
        from spatialflink_tpu.utils.deviceplane import FlightRecorder
        from spatialflink_tpu.utils.latencyplane import LatencyPlane
        from spatialflink_tpu.utils.telemetry import (CostProfiles,
                                                      WindowTraceBook)

        self.calls = 0
        counter = self

        def wrap(cls, name):
            orig = getattr(cls, name)

            def spy(self, *a, **k):
                counter.calls += 1
                return orig(self, *a, **k)

            monkeypatch.setattr(cls, name, spy)

        for cls, name in ((Telemetry, "span"), (Telemetry, "observe"),
                          (StreamingHistogram, "record"),
                          (CostProfiles, "record_cells"),
                          (CostProfiles, "record_scalar"),
                          (CostProfiles, "record_counts"),
                          (CostProfiles, "attribute_kernel"),
                          (CostProfiles, "attribute_merge"),
                          (WindowTraceBook, "note"),
                          (WindowTraceBook, "note_any"),
                          (WindowTraceBook, "seal"),
                          (FlightRecorder, "note"),
                          # the latency-decomposition plane obeys the same
                          # contract: zero touches without a session
                          (LatencyPlane, "note_seal"),
                          (LatencyPlane, "note_dispatch"),
                          (LatencyPlane, "window_complete"),
                          (LatencyPlane, "note_downstream"),
                          (LatencyPlane, "query_emit"),
                          (LatencyPlane, "tick"),
                          # the tenant ledger rides the same gate: zero
                          # feeds without a session
                          (TenantLedger, "note_dispatch"),
                          (TenantLedger, "resolve"),
                          (TenantLedger, "note_window"),
                          (TenantLedger, "note_shed"),
                          (TenantLedger, "note_breach"),
                          (TenantLedger, "note_quota_rejection"),
                          (TenantLedger, "maybe_tick")):
            wrap(cls, name)

        orig_mem = deviceplane_mod.device_memory

        def mem_spy(*a, **k):
            counter.calls += 1
            return orig_mem(*a, **k)

        monkeypatch.setattr(deviceplane_mod, "device_memory", mem_spy)


class TestDriverTelemetry:
    def test_off_by_default_no_calls_on_record_loop(self, tmp_path,
                                                    monkeypatch, capsys):
        from spatialflink_tpu.driver import main

        spy = _CallCounter(monkeypatch)
        inp = _write_points(tmp_path / "pts.geojson")
        assert active() is None
        assert main(["--config", "conf/spatialflink-conf.yml",
                     "--input1", inp, "--option", "1"]) == 0
        assert spy.calls == 0, \
            "telemetry disabled must leave the record loop uninstrumented"

    @pytest.mark.parametrize("option", [51, 101])
    def test_off_by_default_no_calls_on_broker_paths(
            self, tmp_path, monkeypatch, capsys, option):
        """The served broker path's stage spans (fetch, poll, decode,
        materialize, window, dispatch, merge, the join's extraction, sink)
        make no session call without a session."""
        from spatialflink_tpu.driver import main
        from spatialflink_tpu.ops import join as join_ops
        from spatialflink_tpu.streams.kafka import (reset_memory_brokers,
                                                    resolve_broker)

        spy = _CallCounter(monkeypatch)
        monkeypatch.setattr(join_ops, "_LATTICE_BUDGET", 1)  # the pre-pass
        with open("conf/spatialflink-conf.yml") as f:
            d = yaml.safe_load(f)
        d["kafkaBootStrapServers"] = f"memory://tel-off-{option}"
        cfg = tmp_path / "conf.yml"
        cfg.write_text(yaml.safe_dump(d))
        reset_memory_brokers()
        try:
            broker = resolve_broker(f"memory://tel-off-{option}")
            with open(_write_points(tmp_path / "pts.geojson")) as f:
                for line in f:
                    broker.produce("points.geojson", line.strip())
                    broker.produce("queries.geojson", line.strip())
            assert active() is None
            assert main(["--config", str(cfg), "--kafka", "--option",
                         str(option)]) == 0
        finally:
            reset_memory_brokers()
        assert spy.calls == 0, \
            "telemetry disabled must leave the broker path uninstrumented"

    def test_status_server_idle_keeps_record_loop_identical(
            self, tmp_path, monkeypatch):
        """The live-plane hot-path guarantee: --status-port with no
        telemetry session leaves the record loop byte-identical to the
        uninstrumented run — zero span/observe/histogram calls — and with
        the server UNQUERIED, zero snapshot constructions (snapshots are
        built on request/interval only, never per record)."""
        from spatialflink_tpu.driver import main
        from spatialflink_tpu.runtime import opserver as opserver_mod

        spy = _CallCounter(monkeypatch)
        snap_calls = []
        orig_status = telemetry_mod.status_snapshot
        monkeypatch.setattr(
            telemetry_mod, "status_snapshot",
            lambda *a, **k: (snap_calls.append(1), orig_status(*a, **k))[1])
        inp = _write_points(tmp_path / "pts.geojson")
        assert active() is None
        assert main(["--config", "conf/spatialflink-conf.yml",
                     "--input1", inp, "--option", "1",
                     "--status-port", "0"]) == 0
        assert spy.calls == 0, \
            "an idle status server must not instrument the record loop"
        assert snap_calls == [], \
            "snapshot construction must happen on request only"
        # the plane died with the pipeline
        assert opserver_mod.active_server() is None

    def test_file_run_covers_ingest_to_sink(self, tmp_path, capsys):
        from spatialflink_tpu.driver import main

        inp = _write_points(tmp_path / "pts.geojson")
        tdir = tmp_path / "tel"
        assert main(["--config", "conf/spatialflink-conf.yml",
                     "--input1", inp, "--option", "1",
                     "--telemetry-dir", str(tdir),
                     "--telemetry-interval", "0.05", "--metrics"]) == 0
        snaps = _snapshots(tdir)
        assert len(snaps) >= 2
        last = snaps[-1]
        # the span taxonomy covers the pipeline end to end
        assert {"decode", "range.window", "range.dispatch", "range.merge",
                "sink"} <= set(last["spans"])
        assert last["histograms"]["window-latency-ms"]["count"] >= 1
        assert last["grid"]["occupied_cells"] >= 1
        assert os.path.exists(os.path.join(str(tdir), "metrics.prom"))
        # --metrics now emits sorted JSON with the degradation digest
        err = capsys.readouterr().err
        metrics_lines = [ln for ln in err.splitlines()
                         if ln.startswith("{")]
        assert metrics_lines, f"no JSON metrics line in stderr: {err!r}"
        payload = json.loads(metrics_lines[-1])
        assert "metrics" in payload and "degradation" in payload
        assert payload["metrics"]["batches-evaluated"] >= 1

    def test_session_leaves_no_active_telemetry(self, tmp_path, capsys):
        from spatialflink_tpu.driver import main

        inp = _write_points(tmp_path / "pts.geojson", n=10)
        assert main(["--config", "conf/spatialflink-conf.yml",
                     "--input1", inp, "--option", "1",
                     "--telemetry-dir", str(tmp_path / "t")]) == 0
        assert active() is None


class TestKafkaFollowAcceptance:
    """The ISSUE acceptance run: a live --kafka-follow driver run with
    --telemetry-dir emits >= 2 JSONL snapshots containing stage spans,
    latency-histogram percentiles, the watermark-lag gauge, and the PR 1
    degradation counters — correlated in one stream."""

    CONTROL = json.dumps({"geometry": {"type": "control", "coordinates": []}})

    def _conf(self, tmp_path, name):
        with open("conf/spatialflink-conf.yml") as f:
            d = yaml.safe_load(f)
        d["kafkaBootStrapServers"] = f"memory://{name}"
        d["window"].update(interval=1, step=1)
        # zero allowed lateness so 1s windows seal ~1s after they fill (the
        # default 1s out-of-orderness would need a 2s+ feed per window)
        d["query"]["thresholds"]["outOfOrderTuples"] = 0
        p = tmp_path / "conf.yml"
        p.write_text(yaml.safe_dump(d))
        return str(p), f"memory://{name}"

    def test_follow_run_snapshots(self, tmp_path, capsys):
        from spatialflink_tpu.driver import main
        from spatialflink_tpu.streams.kafka import (reset_memory_brokers,
                                                    resolve_broker)

        reset_memory_brokers()
        try:
            cfg, url = self._conf(tmp_path, "tel-follow")
            broker = resolve_broker(url)

            def produce():
                for i in range(250):
                    p = Point.create(116.5 + 0.001 * (i % 40), 40.5, GRID,
                                     obj_id=f"veh{i % 7}",
                                     timestamp=int(time.time() * 1000))
                    broker.produce("points.geojson",
                                   serialize_spatial(p, "GeoJSON"))
                    time.sleep(0.01)
                broker.produce("points.geojson", self.CONTROL)

            t = threading.Thread(target=produce, daemon=True)
            t.start()
            tdir = tmp_path / "tel"
            rc = main(["--config", cfg, "--kafka", "--kafka-follow",
                       "--option", "1",
                       # PR 1 machinery engaged so degradation counters are
                       # non-empty in the same snapshot stream
                       "--chaos", "seed=3,fail_next_fetches=2",
                       "--retry", "attempts=8,base_ms=1",
                       "--telemetry-dir", str(tdir),
                       "--telemetry-interval", "0.1"])
            t.join(timeout=30)
            assert rc == 0
            snaps = _snapshots(tdir)
            assert len(snaps) >= 2
            for s in snaps:
                assert SNAPSHOT_KEYS <= set(s)
            last = snaps[-1]
            # stage spans across the pipeline (+ transport)
            assert {"kafka.poll", "kafka.decode", "range.window",
                    "range.dispatch", "range.merge", "kafka.fetch",
                    "kafka.sink", "sink"} <= set(last["spans"])
            # latency histogram percentiles
            wl = last["histograms"]["window-latency-ms"]
            assert wl["count"] >= 1 and "p50" in wl and "p99" in wl
            # watermark-lag gauge (live run: small but present)
            assert "kafka.watermark-lag-ms" in last["gauges"]
            # PR 1 degradation counters in the SAME snapshot stream
            assert last["degradation"].get("chaos-fetch-fail", 0) >= 1
            assert last["degradation"].get("retry-attempts", 0) >= 1
        finally:
            reset_memory_brokers()
