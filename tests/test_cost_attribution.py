"""Cost-attribution plane tests: window trace lineage (ring, stable ids,
Chrome/Perfetto export), per-cell / per-family cost profiles under
clustered (Zipfian) streams — asserting the hot cell dominates COST, not
just count (groundwork for ROADMAP item 2) — the new /trace/<id>,
/trace/recent, /profile/cells endpoints and the /events?since= cursor, and
the driver acceptance run: a live --kafka-follow --chaos --panes run whose
exported trace.json carries ingest/pane-seal/kernel/merge/emit slices for
emitted windows while /trace and /profile answer schema-valid payloads
mid-run."""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest
import yaml

from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import Point
from spatialflink_tpu.operators import (PointPointRangeQuery,
                                        QueryConfiguration, QueryType)
from spatialflink_tpu.runtime.opserver import OpServer, active_server
from spatialflink_tpu.streams.formats import serialize_spatial
from spatialflink_tpu.utils.metrics import scoped_registry
from spatialflink_tpu.utils.telemetry import (CellOccupancy, CostProfiles,
                                              WindowTraceBook,
                                              status_snapshot,
                                              telemetry_session)

pytestmark = pytest.mark.costattr

GRID = UniformGrid(115.5, 117.6, 39.6, 41.1, num_grid_partitions=100)

TRACE_KEYS = {"trace_id", "query", "window_start", "window_end",
              "first_record_ms", "emitted_ms", "events"}


def _get(url, timeout=5):
    try:
        resp = urllib.request.urlopen(url, timeout=timeout)
        code, body = resp.status, resp.read()
        ctype = resp.headers.get("Content-Type", "")
    except urllib.error.HTTPError as e:
        code, body = e.code, e.read()
        ctype = e.headers.get("Content-Type", "")
    if "json" in ctype:
        return code, json.loads(body)
    return code, body.decode()


class TestWindowTraceBook:
    def test_lineage_roundtrip_and_stable_id(self):
        book = WindowTraceBook()
        assert book.trace_id("range", 5000) == "range:5000"
        t = time.time()
        book.first_record("range", 5000, 1_700_000_000_000)
        book.note("range", 5000, "window", t, t + 0.001)
        book.note("range", 5000, "pane-seal", t, t + 0.002, pane=4000)
        book.note("range", 5000, "kernel", t, t + 0.003)
        book.note("range", 5000, "merge", t, t + 0.001)
        book.seal("range", 5000, 10_000)
        book.note_any(5000, "sink-commit", t, t + 0.0005)
        tr = book.get("range:5000")
        assert TRACE_KEYS <= set(tr)
        assert tr["window_end"] == 10_000
        assert tr["first_record_ms"] == 1_700_000_000_000
        stages = [e["stage"] for e in tr["events"]]
        # ingest is inserted FIRST (it precedes everything it explains)
        assert stages == ["ingest", "window", "pane-seal", "kernel",
                          "merge", "emit", "sink-commit"]
        assert tr["events"][3]["dur_ms"] == pytest.approx(3.0, abs=0.5)
        assert tr["events"][2]["pane"] == 4000
        json.dumps(tr)  # JSON-safe as served
        # recent() newest-first summary
        rec = book.recent()
        assert rec[0]["trace_id"] == "range:5000"
        assert rec[0]["events"] == 7

    def test_ring_bounds_and_total(self):
        book = WindowTraceBook(capacity=4)
        for i in range(10):
            book.note("q", i, "kernel", time.time())
        assert book.total == 10
        assert len(book.recent(99)) == 4
        assert book.get("q:0") is None  # evicted
        assert book.get("q:9") is not None

    def test_note_any_matches_every_family(self):
        book = WindowTraceBook()
        t = time.time()
        book.note("range", 1000, "kernel", t)
        book.note("knn", 1000, "kernel", t)
        book.note("range", 2000, "kernel", t)
        book.note_any(1000, "sink", t, t + 0.001)
        assert [e["stage"] for e in book.get("range:1000")["events"]] == \
            ["kernel", "sink"]
        assert [e["stage"] for e in book.get("knn:1000")["events"]] == \
            ["kernel", "sink"]
        assert [e["stage"] for e in book.get("range:2000")["events"]] == \
            ["kernel"]

    def test_chrome_trace_perfetto_shape(self, tmp_path):
        book = WindowTraceBook()
        t = time.time()
        book.first_record("range", 0, int(t * 1000))
        book.note("range", 0, "kernel", t, t + 0.005)
        book.seal("range", 0, 5000)
        book.note("knn", 0, "kernel", t, t + 0.002)
        doc = book.chrome_trace()
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        evs = doc["traceEvents"]
        slices = [e for e in evs if e["ph"] == "X"]
        instants = [e for e in evs if e["ph"] == "i"]
        metas = [e for e in evs if e["ph"] == "M"]
        # every slice carries the Chrome trace-event required fields in
        # microseconds, pinned to a per-family track
        for e in slices:
            assert {"name", "ts", "dur", "pid", "tid", "args"} <= set(e)
            assert e["dur"] >= 1.0
            assert e["args"]["trace_id"]
        assert {e["name"] for e in instants} == {"ingest", "emit"}
        assert {m["args"]["name"] for m in metas} == {"range", "knn"}
        # distinct families get distinct tracks
        assert len({e["tid"] for e in slices}) == 2
        path = book.export_chrome(str(tmp_path / "trace.json"))
        assert json.load(open(path))["traceEvents"]


# the Zipf/clustered generator is SHARED with the adaptive-grid suites and
# benchmarks/bench_skew.py — one definition in streams.synthetic
from spatialflink_tpu.streams.synthetic import ZIPF_HOT, zipf_cells

_zipf_cells = zipf_cells


class TestZipfOccupancy:
    def test_topk_and_skew_on_clustered_stream(self):
        occ = CellOccupancy()
        cells = _zipf_cells()
        # half vectorized, half scalar (the per-record ingest path)
        occ.record(cells[: len(cells) // 2])
        for c in cells[len(cells) // 2:]:
            occ.record(int(c))
        top = occ.top_k(8)
        assert top[0][0] == ZIPF_HOT
        assert top[0][1] >= 0.55 * len(cells)
        # hottest cell dwarfs the runner-up and the skew factor says so
        assert top[0][1] > 3 * top[1][1]
        assert occ.skew() > 5.0
        # the skew-CONCENTRATION gauges (the --adaptive-grid trigger's
        # observable form): the hot cell holds ~60% of the records, and the
        # distribution is far from uniform on the Gini scale
        assert occ.top_share() == pytest.approx(
            top[0][1] / len(cells), abs=1e-9)
        assert occ.top_share() > 0.55
        assert occ.gini() > 0.5
        d = occ.to_dict()
        assert {"top_share", "gini"} <= set(d)
        # a perfectly uniform stream reads as unconcentrated
        flat = CellOccupancy()
        flat.record(np.arange(100, dtype=np.int64))
        assert flat.gini() == pytest.approx(0.0, abs=1e-9)
        assert flat.top_share() == pytest.approx(0.01, abs=1e-9)


class TestCostProfiles:
    def test_proportional_kernel_attribution(self):
        cp = CostProfiles()
        cp.record_cells(np.array([3, 3, 3, 9]))
        cp.attribute_kernel("range", 0.008, records=4, nbytes=64)
        top = cp.top_cost_cells(4)
        assert top[0] == [3, 6.0, 3]  # 3/4 of 8ms
        assert top[1] == [9, 2.0, 1]
        # pending drained: an all-cached window attributes nothing new
        cp.attribute_kernel("range", 0.050, records=0)
        assert cp.top_cost_cells(4)[0][1] == 6.0
        fam = cp.to_dict()["families"]["range"]
        assert fam["windows"] == 2 and fam["records_in"] == 4
        assert fam["kernel_ms"] == pytest.approx(58.0)
        assert fam["bytes_moved"] == 64

    def test_scalar_fast_path_counts_like_vectorized(self):
        a, b = CostProfiles(), CostProfiles()
        cells = _zipf_cells(n=500)
        a.record_cells(cells)
        for c in cells:
            b.record_cells(int(c))
        b.record_cells(-1)  # invalid cells drop
        a.attribute_kernel("q", 0.001)
        b.attribute_kernel("q", 0.001)
        assert a.top_cost_cells(16) == b.top_cost_cells(16)

    def test_hot_cell_dominates_cost_not_just_count(self):
        """The skew-COST signal: windows dominated by the hot cell run a
        LONGER kernel (more candidates in the cell), so the hot cell's
        attributed cost share must exceed even its (already dominant)
        record share — cost is the signal occupancy alone can't give."""
        cp = CostProfiles()
        rng = np.random.default_rng(3)
        hot_records = cold_records = 0
        for w in range(40):
            hot_window = w % 2 == 0
            if hot_window:  # 90% hot-cell records, slow kernel
                cells = np.where(rng.uniform(size=100) < 0.9, ZIPF_HOT,
                                 50 + rng.integers(0, 30, 100))
                hot_records += int((cells == ZIPF_HOT).sum())
                cold_records += int((cells != ZIPF_HOT).sum())
                cp.record_cells(cells)
                cp.attribute_kernel("range", 0.020, records=100)
            else:  # uniform cold window, fast kernel
                cells = 50 + rng.integers(0, 30, 100)
                cold_records += 100
                cp.record_cells(cells)
                cp.attribute_kernel("range", 0.002, records=100)
        top = cp.top_cost_cells(64)
        assert top[0][0] == ZIPF_HOT
        total_cost = sum(c for _, c, _ in top)
        cost_share = top[0][1] / total_cost
        record_share = hot_records / (hot_records + cold_records)
        assert cost_share > 0.5, "hot cell must dominate attributed cost"
        assert cost_share > record_share + 0.2, \
            "cost share must exceed record share (skew COST, not count)"

    def test_tick_series_buckets_deltas(self):
        cp = CostProfiles()
        cp.record_cells(np.array([1, 1]))
        cp.attribute_kernel("q", 0.004)
        b1 = cp.tick()
        assert b1["kernel_ms"] == pytest.approx(4.0)
        assert b1["top_cells"][0][0] == 1
        b2 = cp.tick()  # nothing new since the last bucket
        assert b2["kernel_ms"] == 0.0 and b2["top_cells"] == []
        assert list(cp.series) == [b1, b2]

    def test_scrape_driven_series_in_reporterless_session(self):
        """The /profile/cells read path itself buckets the series (at the
        tick interval), so a --trace-dir/--status-port run WITHOUT the
        JSONL reporter still serves a time series, while back-to-back
        scrapes inside one interval don't double-bucket."""
        cp = CostProfiles(tick_interval_s=3600.0)
        cp.record_cells(np.array([2, 2]))
        cp.attribute_kernel("q", 0.002)
        assert cp.cells_payload()["series"] == []  # interval not elapsed
        cp.tick_interval_s = 0.0
        assert len(cp.cells_payload()["series"]) == 1
        cp.tick_interval_s = 3600.0
        assert len(cp.cells_payload()["series"]) == 1  # no double-bucket

    def test_end_to_end_clustered_pipeline_profiles(self):
        """Full operator drive over a clustered point stream with a
        session: the hot cell tops the cost profile AND the status digest
        surfaces it (top_cost_cells), with the family profile fed from
        the real dispatch spans."""
        from spatialflink_tpu.streams.synthetic import clustered_points

        # shared generator (streams.synthetic): 70% of records in a tight
        # cluster spanning a third of one cell, anchored mid-cell so the
        # whole cluster shares ONE grid cell
        hot_x, hot_y = 116.4975, 40.5135
        stream = clustered_points(GRID, 600, 0.7, seed=11,
                                  hot_center=(hot_x, hot_y),
                                  cluster_span_cells=0.33)
        conf = QueryConfiguration(QueryType.WindowBased,
                                  window_size_ms=10_000, slide_ms=5_000)
        q = Point.create(hot_x, hot_y, GRID)
        with scoped_registry(), telemetry_session() as tel:
            n = sum(1 for _ in PointPointRangeQuery(conf, GRID).run(
                iter(stream), q, 0.5))
            assert n >= 2
            payload = tel.costs.cells_payload()
            snap = status_snapshot(tel)
        hot_cell = int(GRID.assign_cell(hot_x, hot_y)[0])
        assert payload["cells"], "pipeline produced no cost profile"
        assert payload["cells"][0]["cell"] == hot_cell
        # dominance, not an exact share: per-dispatch wall-clock weights
        # the attribution, and kernel timings shift with jit cache warmth
        # (cold first-window compiles overweight early arrivals)
        assert payload["cells"][0]["cost_share"] > 0.25
        assert payload["cells"][0]["cost_ms"] > \
            2 * payload["cells"][1]["cost_ms"]
        fam = payload["families"]["range"]
        assert fam["windows"] == n and fam["kernel_ms"] > 0
        assert fam["records_in"] > 600  # windows overlap: records recount
        assert snap["status"]["top_cost_cells"][0][0] == hot_cell


class TestEndpoints:
    def test_trace_profile_and_since_cursor(self):
        with scoped_registry(), telemetry_session(trace=True) as tel:
            t = time.time()
            tel.traces.note("range", 1000, "kernel", t, t + 0.004)
            tel.traces.seal("range", 1000, 2000)
            tel.costs.record_cells(np.array([5, 5, 8]))
            tel.costs.attribute_kernel("range", 0.004, records=3)
            for i in range(5):
                tel.event("e", i=i)
            srv = OpServer(port=0).start()
            try:
                code, recent = _get(srv.url + "/trace/recent")
                assert code == 200 and recent["total"] == 1
                tid = recent["traces"][0]["trace_id"]
                assert tid == "range:1000"
                code, tr = _get(srv.url + "/trace/" + tid)
                assert code == 200 and TRACE_KEYS <= set(tr)
                assert [e["stage"] for e in tr["events"]] == ["kernel",
                                                              "emit"]
                code, missing = _get(srv.url + "/trace/range:999")
                assert code == 404 and "unknown" in missing["error"]
                code, prof = _get(srv.url + "/profile/cells")
                assert code == 200
                assert prof["cells"][0]["cell"] == 5
                assert {"cell", "records", "cost_ms",
                        "cost_share"} <= set(prof["cells"][0])
                assert prof["families"]["range"]["kernel_ms"] > 0
                assert "series" in prof
                # the ?since cursor: resume from latest_seq, see only new
                code, evs = _get(srv.url + "/events")
                assert code == 200 and len(evs["events"]) == 5
                cursor = evs["latest_seq"]
                assert cursor == evs["events"][-1]["seq"], \
                    "latest_seq must not run ahead of the delivered list"
                code, evs2 = _get(srv.url + f"/events?since={cursor}")
                assert code == 200 and evs2["events"] == []
                assert evs2["latest_seq"] == cursor  # cursor never rewinds
                tel.event("fresh")
                code, evs3 = _get(srv.url + f"/events?since={cursor}")
                assert [e["kind"] for e in evs3["events"]] == ["fresh"]
                assert evs3["events"][0]["seq"] == cursor + 1
                assert "mono_ms" in evs3["events"][0]
                code, bad = _get(srv.url + "/events?since=nope")
                assert code == 400
            finally:
                srv.close()

    def test_endpoints_without_session_explain_themselves(self):
        from spatialflink_tpu.utils import telemetry as telemetry_mod

        assert telemetry_mod.active() is None
        srv = OpServer(port=0).start()
        try:
            code, recent = _get(srv.url + "/trace/recent")
            assert code == 200 and recent["traces"] == []
            assert "note" in recent
            code, tr = _get(srv.url + "/trace/range:1")
            assert code == 404
            code, prof = _get(srv.url + "/profile/cells")
            assert code == 200 and prof["cells"] == [] and "note" in prof
        finally:
            srv.close()

    def test_plain_session_has_no_trace_book(self):
        with telemetry_session() as tel:  # no trace=True / trace_dir
            assert tel.traces is None
            srv = OpServer(port=0).start()
            try:
                code, recent = _get(srv.url + "/trace/recent")
                assert code == 200 and "note" in recent
            finally:
                srv.close()


def _full_lineage_traces(trace_doc, required):
    """trace_ids whose event set covers ``required`` stage names."""
    per_trace = {}
    for e in trace_doc["traceEvents"]:
        tid = e.get("args", {}).get("trace_id")
        if tid:
            per_trace.setdefault(tid, set()).add(e["name"])
    return [t for t, s in per_trace.items() if required <= s]


class TestDriverTraceExport:
    def test_file_run_exports_perfetto_lineage(self, tmp_path):
        """--trace-dir on a plain file replay with --panes: trace.json is
        Chrome/Perfetto-loadable and ≥ 1 window's trace carries the full
        ingest → pane-seal → kernel → merge → emit → sink lineage."""
        from spatialflink_tpu.driver import main

        inp = tmp_path / "pts.geojson"
        with open(inp, "w") as f:
            for i in range(120):
                p = Point.create(116.5 + 0.001 * (i % 40), 40.5, GRID,
                                 obj_id=f"o{i}",
                                 timestamp=1_700_000_000_000 + i * 500)
                f.write(serialize_spatial(p, "GeoJSON") + "\n")
        tdir = tmp_path / "trace"
        assert main(["--config", "conf/spatialflink-conf.yml",
                     "--input1", str(inp), "--option", "1", "--panes",
                     "--trace-dir", str(tdir)]) == 0
        doc = json.load(open(tdir / "trace.json"))
        assert doc["traceEvents"], "empty trace export"
        full = _full_lineage_traces(
            doc, {"ingest", "pane-seal", "kernel", "merge", "emit", "sink"})
        assert full, "no window trace carries the full lineage"
        assert all(t.startswith("range:") for t in full)
        # slices are microsecond X events a viewer can actually render
        assert any(e["ph"] == "X" and e["dur"] >= 1 and e["name"] == "kernel"
                   for e in doc["traceEvents"])


CONTROL = json.dumps({"geometry": {"type": "control", "coordinates": []}})


class _TracePoller(threading.Thread):
    """Mid-run client for the acceptance test: waits for the driver's
    ephemeral server, then for a sealed window trace AND a non-empty cost
    profile, then grabs /trace/<id>, /profile/cells, and /events?since."""

    def __init__(self):
        super().__init__(daemon=True)
        self.result: dict = {}

    def run(self):
        deadline = time.monotonic() + 30.0
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
                _, recent = _get(srv.url + "/trace/recent", timeout=2)
                _, prof = _get(srv.url + "/profile/cells", timeout=2)
            except Exception:
                time.sleep(0.05)
                continue
            sealed = [t for t in recent.get("traces", [])
                      if t.get("emitted_ms")]
            if sealed and prof.get("cells") and \
                    prof.get("families", {}).get("range", {}).get(
                        "kernel_ms", 0) > 0:
                self.result["recent"] = recent
                self.result["profile"] = prof
                try:
                    self.result["trace"] = _get(
                        srv.url + "/trace/" + sealed[0]["trace_id"],
                        timeout=2)
                    _, evs = _get(srv.url + "/events", timeout=2)
                    self.result["events_since"] = _get(
                        srv.url + f"/events?since={evs['latest_seq']}",
                        timeout=2)
                except Exception as e:  # pragma: no cover - diagnostic
                    self.result["error"] = repr(e)
                return
            time.sleep(0.05)
        self.result["error"] = "no sealed trace + cost profile mid-run"


class TestLiveAcceptance:
    """The ISSUE acceptance run: --kafka-follow --chaos --panes with the
    trace plane on — mid-run /trace/<id> and /profile/cells return
    schema-valid payloads, and the exported trace.json is
    Perfetto-loadable with ingest/pane-seal/kernel/merge/emit slices for
    ≥ 1 window."""

    def test_follow_chaos_panes_trace_plane(self, tmp_path):
        from spatialflink_tpu.driver import main
        from spatialflink_tpu.streams.kafka import (reset_memory_brokers,
                                                    resolve_broker)

        reset_memory_brokers()
        try:
            with open("conf/spatialflink-conf.yml") as f:
                d = yaml.safe_load(f)
            d["kafkaBootStrapServers"] = "memory://costattr-follow"
            d["window"].update(interval=4, step=1)  # overlap 4: pane reuse
            d["query"]["thresholds"]["outOfOrderTuples"] = 0
            cfg = tmp_path / "conf.yml"
            cfg.write_text(yaml.safe_dump(d))
            broker = resolve_broker("memory://costattr-follow")

            def produce():
                # ~7s of wall-clock event time: 4s windows on 1s slides
                # seal from ~5s on, so the poller has a live span with
                # sealed traces and attributed kernel cost
                for i in range(700):
                    p = Point.create(116.5 + 0.001 * (i % 40), 40.5, GRID,
                                     obj_id=f"veh{i % 7}",
                                     timestamp=int(time.time() * 1000))
                    broker.produce("points.geojson",
                                   serialize_spatial(p, "GeoJSON"))
                    time.sleep(0.01)
                broker.produce("points.geojson", CONTROL)

            t = threading.Thread(target=produce, daemon=True)
            poller = _TracePoller()
            t.start()
            poller.start()
            tdir = tmp_path / "trace"
            rc = main(["--config", str(cfg), "--kafka", "--kafka-follow",
                       "--option", "1", "--panes",
                       "--chaos", "seed=3,fail_next_fetches=2",
                       "--retry", "attempts=8,base_ms=1",
                       "--status-port", "0",
                       "--trace-dir", str(tdir),
                       "--telemetry-dir", str(tmp_path / "tel"),
                       "--telemetry-interval", "0.1"])
            t.join(timeout=30)
            poller.join(timeout=30)
            assert rc == 0
            res = poller.result
            assert "error" not in res, res
            # --- /trace/<id> mid-run: schema-valid, real durations ---
            code, tr = res["trace"]
            assert code == 200 and TRACE_KEYS <= set(tr)
            assert tr["query"] == "range" and tr["emitted_ms"]
            stages = {e["stage"] for e in tr["events"]}
            assert {"kernel", "merge", "emit"} <= stages
            assert any("dur_ms" in e for e in tr["events"])
            # --- /profile/cells mid-run: schema-valid, cost attributed ---
            prof = res["profile"]
            assert {"cells", "families", "series",
                    "total_kernel_ms"} <= set(prof)
            assert prof["cells"][0]["cost_ms"] > 0
            assert prof["families"]["range"]["windows"] >= 1
            assert prof["families"]["range"]["pane_misses"] >= 1
            # --- /events?since= cursor drains mid-run ---
            code, evs = res["events_since"]
            assert code == 200 and isinstance(evs["events"], list)
            # --- the exported artifact: Perfetto-loadable full lineage ---
            doc = json.load(open(tdir / "trace.json"))
            full = _full_lineage_traces(
                doc, {"ingest", "pane-seal", "kernel", "merge", "emit"})
            assert full, "trace.json lacks a full-lineage window"
            # downstream sink stages ride the same traces (kafka commit)
            names = {e["name"] for e in doc["traceEvents"]}
            assert "sink-commit" in names
            # telemetry snapshots carry the cost digest alongside
            with open(tmp_path / "tel" / "telemetry.jsonl") as f:
                snaps = [json.loads(line) for line in f]
            assert snaps[-1]["status"]["top_cost_cells"]
            assert snaps[-1]["costs"]["families"]["range"]["kernel_ms"] > 0
            assert snaps[-1]["traces"]["enabled"] is True
        finally:
            reset_memory_brokers()
