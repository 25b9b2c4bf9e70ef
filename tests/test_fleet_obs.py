"""Fleet observability plane suite (runtime/fleetsup.py FleetMonitor +
lineage, runtime/fleet.py lat sidecar, opserver /fleet/* federation,
doctor fleet timeline).

Headline invariants: (1) the end-to-end record→merged-emit budget on
every merged window satisfies the same sums-to-total invariant as the
worker chain (the fleet stages are consecutive intervals — they
telescope); (2) the lineage sidecar is INVISIBLE to exactly-once
identity — the merged.jsonl bytes and digest are identical with the
plane on or off; (3) a chaos-killed worker's own events land in the
merged timeline BEFORE its restart (the kill path harvests the dying
worker's ring before noting the restart — from its mirrored event file
when its opserver has already closed); (4) ``/fleet/metrics``
federates every worker's Prometheus text under ``worker="wN"`` labels.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest
import yaml

from spatialflink_tpu.driver import main
from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.runtime import fleet as F
from spatialflink_tpu.runtime.fleetsup import (FLEET_STAGES, FleetMonitor,
                                               compute_merged_lineage,
                                               format_fleet_digest,
                                               format_relay)
from spatialflink_tpu.streams import SyntheticPointSource, serialize_spatial
from spatialflink_tpu.utils import metrics as _metrics
from spatialflink_tpu.utils.latencyplane import CHAIN_STAGES
from spatialflink_tpu.utils.telemetry import relabel_prometheus_lines

pytestmark = pytest.mark.fleet

CONF = "conf/spatialflink-conf.yml"


@pytest.fixture(autouse=True)
def _clear_shutdown_flag():
    _metrics.clear_shutdown()
    yield
    _metrics.clear_shutdown()


# ------------------------------------------------- prometheus relabeling


def test_relabel_prometheus_lines():
    text = ('# HELP spatialflink_gauge live gauges\n'
            '# TYPE spatialflink_gauge gauge\n'
            'spatialflink_gauge{name="window-backlog"} 3\n'
            'spatialflink_counter_total 42\n'
            'empty_braces{} 1\n'
            '\n')
    out = relabel_prometheus_lines(text, "worker", "w1")
    lines = out.splitlines()
    assert lines[0].startswith("# HELP")  # comments pass through
    assert lines[1].startswith("# TYPE")
    assert lines[2] == ('spatialflink_gauge{worker="w1",'
                        'name="window-backlog"} 3')
    assert lines[3] == 'spatialflink_counter_total{worker="w1"} 42'
    assert lines[4] == 'empty_braces{worker="w1"} 1'
    assert out.endswith("\n")  # exposition format keeps its newline


# -------------------------------------------------- lat sidecar + digest


def _win_result(records=("x",), cell=7):
    from spatialflink_tpu.operators import WindowResult

    return WindowResult(0, 5000, list(records), extras={"cell": cell})


def test_lat_sidecar_excluded_from_fingerprint_and_digest():
    r = _win_result()
    lat = {"first_ingest_ms": 100.0, "emitted_ms": 150.0,
           "record_emit_ms": 50.0, "stages": {"buffer": 50.0}}
    bare = F.canonical_window_doc(r, "range")
    carrying = F.canonical_window_doc(r, "range", lat=lat)
    assert carrying["lat"] == lat
    assert "lat" not in bare
    # identity is records-only: same fp with or without the sidecar
    assert carrying["fp"] == bare["fp"]
    # ...and the merged-table digest never sees it either
    m_bare = F.merge_outboxes({0: {bare["key"]: bare}}, "range")
    m_lat = F.merge_outboxes({0: {carrying["key"]: carrying}}, "range")
    assert F.merged_table_digest(m_bare) == F.merged_table_digest(m_lat)


def test_lat_sidecar_builder_filters_unusable_rows():
    assert F.lat_sidecar(None) is None
    assert F.lat_sidecar({}) is None
    # budget rows without an ingest stamp carry no lineage
    assert F.lat_sidecar({"first_ingest_ms": None,
                          "stages": {"emit": 1.0}}) is None
    row = {"first_ingest_ms": 10.0, "emitted_ms": 30.0,
           "record_emit_ms": 20.0, "last_ingest_ms": 12.0,
           "stages": {"buffer": 5.0, "emit": 15.0, "sink": 99.0}}
    sc = F.lat_sidecar(row)
    assert sc["first_ingest_ms"] == 10.0 and sc["emitted_ms"] == 30.0
    # downstream stages stay out of the sidecar: they are outside the
    # worker's sum invariant and would corrupt the extended chain's
    assert "sink" not in sc["stages"] and sc["stages"]["buffer"] == 5.0


def test_latencyplane_budget_row_accessor():
    from spatialflink_tpu.utils.latencyplane import LatencyPlane

    lp = LatencyPlane()
    lp.window_complete("q", 0, 5000, 100, {"buffer": 10.0, "emit": 5.0},
                       emit_s=0.2)
    row = lp.budget_row(0)
    assert row["emitted_ms"] == 200.0 and row["stages"]["buffer"] == 10.0
    row["stages"]["buffer"] = -1  # a COPY: the plane's ring is untouched
    assert lp.budget_row(0)["stages"]["buffer"] == 10.0
    assert lp.budget_row(999) is None


# ------------------------------------------------------- stderr relaying


def test_format_relay_prefixes_and_suppresses_digest():
    assert format_relay(2, "# emitted 9 results",
                        digest_active=False) == "[w2] # emitted 9 results"
    # a worker's own digest line is suppressed only while the fleet
    # digest owns the terminal
    assert format_relay(0, "# live: in 5 rec", digest_active=True) is None
    assert format_relay(0, "# live: in 5 rec",
                        digest_active=False) == "[w0] # live: in 5 rec"


def test_format_fleet_digest_aggregates_workers():
    view = {"alive": 1, "n_workers": 2, "routed": 100, "restarts_total": 1,
            "workers": [
                {"latency": {"sum_check": {"windows": 4},
                             "record_emit": {"count": 4, "p99": 120.0},
                             "stages": {"dispatch": {"sum": 300.0},
                                        "emit": {"sum": 10.0},
                                        "sink": {"sum": 999.0}}}},
                {"latency": {"sum_check": {"windows": 3},
                             "record_emit": {"count": 3, "p99": 80.0}}}]}
    line = format_fleet_digest(view)
    assert line.startswith("# fleet live: 1/2 up")
    assert "routed 100" in line and "win 7" in line
    # worst p99 across workers, dominant stage from CHAIN sums only
    assert "lat p99 120ms (dispatch)" in line and "restarts 1" in line


# ------------------------------------------------------ FleetMonitor


def test_fleet_monitor_harvest_cursor_and_reset(tmp_path):
    mon = FleetMonitor(str(tmp_path), 2)
    try:
        mon.note("worker-spawn", worker=0)
        added = mon.harvest(0, {"events": [
            {"seq": 1, "kind": "worker-online", "ts_ms": 111},
            {"seq": 2, "kind": "checkpoint-committed", "ts_ms": 222}]})
        assert added == 2 and mon.cursor(0) == 2
        # ?since= re-delivery: already-seen worker seqs never duplicate
        assert mon.harvest(0, {"events": [
            {"seq": 2, "kind": "checkpoint-committed", "ts_ms": 222}]}) == 0
        evs = mon.ring.list(None)
        assert [e["kind"] for e in evs] == ["worker-spawn", "worker-online",
                                           "checkpoint-committed"]
        got = evs[1]
        assert got["src"] == "worker" and got["worker"] == 0
        assert got["worker_seq"] == 1 and got["ts_ms"] == 111
        assert got["seq"] == 2  # the MERGED ring assigns fleet seqs
        # a respawned incarnation's ring restarts at 1: cursor follows
        mon.reset_cursor(0)
        assert mon.harvest(0, {"events": [
            {"seq": 1, "kind": "worker-online", "ts_ms": 333}]}) == 1
        # the durable mirror carries every merged event
        with open(os.path.join(str(tmp_path), F.EVENTS_FILE)) as f:
            assert sum(1 for ln in f if ln.strip()) == 4
    finally:
        mon.close()


def test_fleet_monitor_scan_outbox_torn_tail_and_first_visible(tmp_path):
    mon = FleetMonitor(str(tmp_path), 1)
    try:
        wd = F.worker_dir(str(tmp_path), 0)
        os.makedirs(wd, exist_ok=True)
        outbox = os.path.join(wd, F.OUTBOX_FILE)
        doc = {"key": "0:5:None", "records": ["r"], "fp": "aa",
               "lat": {"first_ingest_ms": time.time() * 1e3 - 50.0}}
        with open(outbox, "w") as f:
            f.write(json.dumps(doc) + "\n")
            f.write('{"torn')  # no newline: must be held back
        assert mon.scan_outbox(0) == 1
        first = mon.visible_ms(0, "0:5:None")
        assert first is not None
        with open(outbox, "a") as f:  # the tail completes + a replay dup
            f.write('-key": true}\n')
            f.write(json.dumps(doc) + "\n")
        assert mon.scan_outbox(0) == 2  # dup counted (chaos counts lines)
        # ...but the first-visible stamp is first-wins (crash replays
        # must not move a window's outbox-visible stage)
        assert mon.visible_ms(0, "0:5:None") == first
        assert mon.visible_hist()["count"] == 1
        assert mon.line_count(0) == 2
    finally:
        mon.close()


def test_fleet_monitor_ingest_poll_series(tmp_path):
    mon = FleetMonitor(str(tmp_path), 1, series_capacity=4)
    try:
        lat = {"record_emit": {"p99": 42.0},
               "stages": {"dispatch": {"sum": 100.0},
                          "buffer": {"sum": 1.0}},
               "backpressure": {"backlog_residency_ms": 7.0,
                                "series": [{"decode_buffer_depth": 3,
                                            "stall": False}]}}
        st = {"status": {"records_in": 10, "throughput_rps": 5.0,
                         "windows_evaluated": 2,
                         "device": {"recompiles": 0}}}
        for _ in range(6):  # bounded: capacity evicts, never grows
            mon.ingest_poll(0, st, lat, alive=True, incarnation=1)
        series = mon.series(0)
        assert len(series) == 4
        s = series[-1]
        assert s["record_emit_p99_ms"] == 42.0
        assert s["dominant_stage"] == "dispatch"
        assert s["backlog_residency_ms"] == 7.0
        assert s["decode_buffer_depth"] == 3 and s["recompiles"] == 0
        assert mon.last_samples()[0]["records_in"] == 10
        # the rebalance signal reads p99 + backlog residency
        assert mon.rebalance_load(0) == pytest.approx(49.0)
        assert mon.rebalance_load(99) is None  # never polled
    finally:
        mon.close()


# ---------------------------------------------------- merged lineage


def test_compute_merged_lineage_sums_to_total():
    t_merged, t_emit = 10_000.0, 10_040.0
    lat0 = {"first_ingest_ms": 1_000.0, "emitted_ms": 5_000.0,
            "stages": {"buffer": 3_000.0, "queue": 500.0,
                       "dispatch": 200.0, "inflight": 100.0,
                       "merge": 100.0, "emit": 100.0}}
    lat1 = {"first_ingest_ms": 2_000.0, "emitted_ms": 6_000.0,
            "stages": {"buffer": 3_000.0, "queue": 400.0,
                       "dispatch": 300.0, "inflight": 100.0,
                       "merge": 100.0, "emit": 100.0}}
    per_worker = {0: {"0:5:None": {"lat": lat0}},
                  1: {"0:5:None": {"lat": lat1}}}
    merged = [{"key": "0:5:None", "records": ["a"], "workers": [0, 1]},
              {"key": "5:10:None", "records": ["b"], "workers": [0]}]
    doc = compute_merged_lineage(merged, per_worker,
                                 lambda wid, key: 7_000.0,
                                 t_merged, t_emit)
    assert doc["schema"] == "fleet-latency-v1"
    # window 2 has no sidecar anywhere: counted, never guessed
    assert doc["sum_check"]["windows"] == 1 and doc["skipped_no_lat"] == 1
    row = doc["recent"][0]
    # worker 1 emitted last => it is the critical contributor; the global
    # first ingest is worker 0's
    assert row["worker"] == 1
    total = row["record_emit_ms"]
    assert total == pytest.approx(t_emit - 1_000.0)
    assert sum(row["stages"].values()) == pytest.approx(total)
    assert row["stages"]["spread"] == pytest.approx(1_000.0)
    assert row["stages"]["outbox-visible"] == pytest.approx(1_000.0)
    assert row["stages"]["fleet-merge"] == pytest.approx(3_000.0)
    assert row["stages"]["merged-emit"] == pytest.approx(40.0)
    assert doc["chain_stages"] == (["spread"] + list(CHAIN_STAGES)
                                   + list(FLEET_STAGES))
    # the fleet stages must never shadow a worker chain stage
    assert not set(FLEET_STAGES) & set(CHAIN_STAGES)


def test_compute_merged_lineage_clamps_visible_stamp():
    lat = {"first_ingest_ms": 0.0, "emitted_ms": 100.0,
           "stages": {"buffer": 100.0}}
    merged = [{"key": "k", "records": [], "workers": [0]}]
    per_worker = {0: {"k": {"lat": lat}}}
    # a visible stamp AFTER the merge wall clock (scan raced the merge)
    # clamps into [emit, merge]; the telescoping keeps sums-to-total
    doc = compute_merged_lineage(merged, per_worker,
                                 lambda w, k: 999_999.0, 200.0, 300.0)
    row = doc["recent"][0]
    assert row["stages"]["fleet-merge"] >= 0.0
    assert row["stages"]["outbox-visible"] >= 0.0
    assert sum(row["stages"].values()) == pytest.approx(
        row["record_emit_ms"])
    # and a missing stamp degrades to the emit wall clock: the window
    # was "visible" the moment it was emitted, so outbox-visible is 0
    # and the whole emit→merge interval lands in fleet-merge
    doc2 = compute_merged_lineage(merged, per_worker,
                                  lambda w, k: None, 200.0, 300.0)
    row2 = doc2["recent"][0]
    assert row2["stages"]["outbox-visible"] == pytest.approx(0.0)
    assert row2["stages"]["fleet-merge"] == pytest.approx(100.0)


# ------------------------------------------- federation without a fleet


def test_fleet_federation_endpoints_note_absence_without_supervisor():
    from spatialflink_tpu.runtime.fleetsup import active_fleet
    from spatialflink_tpu.runtime.opserver import OpServer

    assert active_fleet() is None
    srv = OpServer(port=0).start()
    try:
        for path in ("/fleet/latency", "/fleet/timeline", "/fleet/events"):
            with urllib.request.urlopen(f"{srv.url}{path}", timeout=5) as r:
                doc = json.loads(r.read().decode())
            assert "--fleet" in doc["note"], path
        with urllib.request.urlopen(f"{srv.url}/fleet/metrics",
                                    timeout=5) as r:
            assert "not a fleet supervisor" in r.read().decode()
        # /fleet/events keeps /events' since validation contract
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{srv.url}/fleet/events?since=bogus",
                                   timeout=5)
        assert ei.value.code == 400
    finally:
        srv.close()


# --------------------------------------------------- acceptance run


def _grid():
    return UniformGrid(115.5, 117.6, 39.6, 41.1, num_grid_partitions=100)


def _lines(n_traj=8, steps=80, seed=3):
    pts = list(SyntheticPointSource(_grid(), num_trajectories=n_traj,
                                    steps=steps, seed=seed))
    return [serialize_spatial(p, "GeoJSON") for p in pts]


def _conf_file(tmp_path):
    with open(CONF) as f:
        d = yaml.safe_load(f)
    p = tmp_path / "conf.yml"
    p.write_text(yaml.safe_dump(d))
    return str(p)


def _fleet_argv(cfg, path1, fleet_dir, n, *extra):
    return (["--config", cfg, "--option", "1", "--input1", path1,
             "--fleet", str(n), "--fleet-dir", str(fleet_dir),
             "--fleet-heartbeat", "0.25",
             "--fleet-epoch-records", "100"] + list(extra))


def test_worker_event_mirror_read_back_by_fence(tmp_path):
    """The worker's mirrored event file answers ``?since=`` for the
    incarnation the fence names: events already in the ring when the
    mirror is installed come first, a predecessor's lines and a torn tail
    line are skipped, and appends after close are dropped quietly."""
    from spatialflink_tpu.utils.telemetry import EventRing

    old = F.WorkerContext(str(tmp_path), 0, family="range", fence=0)
    ring0 = EventRing()
    old.mirror_events(ring0)
    ring0.append("worker-online", url="http://old")
    old.close()

    ctx = F.WorkerContext(str(tmp_path), 0, family="range", fence=1)
    ring = EventRing()
    ring.append("worker-online", url="http://new")
    ctx.mirror_events(ring)
    ring.append("sentinel-warm", reason="warm")
    ring.append("checkpoint-restored", seq=3)
    with open(os.path.join(ctx.dir, F.WORKER_EVENTS_FILE), "a") as f:
        f.write('{"fence": 1, "ev": {"kind": "tor')
    ctx.close()
    ring.append("after-close")  # the mirror swallows the closed file

    wd = F.worker_dir(str(tmp_path), 0)
    got = F.read_worker_events(wd, fence=1)
    assert [e["kind"] for e in got] == [
        "worker-online", "sentinel-warm", "checkpoint-restored"]
    assert [e["seq"] for e in got] == [1, 2, 3]
    assert got[0]["url"] == "http://new"
    assert [e["kind"] for e in F.read_worker_events(wd, fence=1, since=2)] \
        == ["checkpoint-restored"]
    assert [e["url"] for e in F.read_worker_events(wd, fence=0)] == \
        ["http://old"]
    assert F.read_worker_events(str(tmp_path / "absent"), fence=0) == []
    assert len(ring.list()) == 4


def _fetch_json(url, timeout=5):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read().decode())


def test_fleet_observability_acceptance_chaos_kill(tmp_path):
    """THE acceptance test: N=2 with a chaos kill, the federation
    endpoints fetched MID-RUN from the supervisor's opserver, then the
    persisted plane artifacts checked — restart ordered after the dead
    worker's own events, `worker=` labels on federated metrics, the
    end-to-end sums-to-total invariant, and merged.jsonl byte-identity
    with the plane off."""
    from spatialflink_tpu.runtime import opserver as op

    cfg = _conf_file(tmp_path)
    path1 = str(tmp_path / "in1.geojson")
    open(path1, "w").write("\n".join(_lines()) + "\n")
    fdir = tmp_path / "fleet_on"

    rc_box = {}

    def run():
        rc_box["rc"] = main(_fleet_argv(
            cfg, path1, fdir, 2, "--fleet-chaos-kill", "0:2",
            "--status-port", "0"))

    t = threading.Thread(target=run, name="fleet-acceptance")
    t.start()
    # ---- mid-run federation fetches (poll until the plane has data) ----
    saw_metrics = saw_events = False
    lat_doc = None
    deadline = time.monotonic() + 120
    try:
        while time.monotonic() < deadline and t.is_alive():
            srv = op.active_server()
            if srv is None or srv.port is None:
                time.sleep(0.05)
                continue
            try:
                if not saw_metrics:
                    with urllib.request.urlopen(f"{srv.url}/fleet/metrics",
                                                timeout=5) as r:
                        body = r.read().decode()
                    assert "spatialflink_fleet_workers_alive" in body
                    saw_metrics = 'worker="w' in body
                if not saw_events:
                    evd = _fetch_json(f"{srv.url}/fleet/events")
                    assert evd["latest_seq"] <= evd["total"]
                    saw_events = bool(evd["events"])
                if lat_doc is None or not lat_doc.get("workers"):
                    lat_doc = _fetch_json(f"{srv.url}/fleet/latency")
                    tld = _fetch_json(f"{srv.url}/fleet/timeline")
                    assert tld["total"] >= len([
                        e for e in tld["events"]])
            except (OSError, urllib.error.URLError):
                pass  # the run may finish between is_alive and the fetch
            if saw_metrics and saw_events and (lat_doc or {}).get(
                    "workers"):
                break
            time.sleep(0.05)
    finally:
        t.join(timeout=300)
    assert not t.is_alive(), "fleet run hung"
    assert rc_box["rc"] == 0
    assert saw_metrics, ("mid-run /fleet/metrics never federated a "
                         'worker="wN"-labeled body')
    assert saw_events, "mid-run /fleet/events stayed empty"
    assert lat_doc is not None and lat_doc.get("schema") == \
        "fleet-latency-v1"

    result = F.read_json(os.path.join(str(fdir), F.RESULT_FILE))
    assert sum(int(v) for v in result["restarts"].values()) >= 1, \
        "chaos kill never fired"
    # the result doc carries the lineage headline, outside the digest
    assert result["latency"]["sum_check"]["windows"] > 0

    # ---- timeline: the dead worker spoke BEFORE its restart ----
    events = []
    with open(os.path.join(str(fdir), F.EVENTS_FILE)) as f:
        for line in f:
            if line.strip():
                events.append(json.loads(line))
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs)
    restarts = [e for e in events if e["kind"] == "worker-restart"
                and e.get("worker") == 0]
    assert restarts, "restart never reached the merged timeline"
    own = [e for e in events if e.get("src") == "worker"
           and e.get("worker") == 0 and e["seq"] < restarts[0]["seq"]]
    assert own, ("the killed worker's own events were not harvested "
                 "before its restart was noted")
    kills = [e for e in events if e["kind"] == "worker-kill"
             and e.get("worker") == 0]
    assert kills and kills[0]["seq"] < restarts[0]["seq"]

    # ---- end-to-end budgets: sums-to-total on merged windows ----
    lat = F.read_json(os.path.join(str(fdir), F.LATENCY_FILE))
    assert lat["sum_check"]["windows"] > 0
    assert lat["sum_check"]["max_residual_ms"] < 50.0
    for row in lat["recent"]:
        assert abs(row["record_emit_ms"]
                   - sum(row["stages"].values())) < 5.0, row
        for s in FLEET_STAGES:
            assert s in row["stages"], row
    assert lat["record_visible"]["count"] > 0

    # every plane-on outbox line carries the sidecar
    with open(os.path.join(F.worker_dir(str(fdir), 1),
                           F.OUTBOX_FILE)) as f:
        docs = [json.loads(ln) for ln in f if ln.strip()]
    assert docs and all("lat" in d for d in docs)

    # ---- digest + merged.jsonl byte-identity with the plane off ----
    off_dir = tmp_path / "fleet_off"
    assert main(_fleet_argv(cfg, path1, off_dir, 2,
                            "--fleet-plane", "off")) == 0
    off = F.read_json(os.path.join(str(off_dir), F.RESULT_FILE))
    assert off["digest"] == result["digest"], \
        "the observability plane leaked into exactly-once identity"
    on_bytes = open(os.path.join(str(fdir), F.MERGED_FILE), "rb").read()
    off_bytes = open(os.path.join(str(off_dir), F.MERGED_FILE),
                     "rb").read()
    assert on_bytes == off_bytes
    # plane off: no retention artifacts, no sidecars
    assert not os.path.exists(os.path.join(str(off_dir), F.LATENCY_FILE))
    assert not os.path.exists(os.path.join(str(off_dir), F.EVENTS_FILE))
    with open(os.path.join(F.worker_dir(str(off_dir), 0),
                           F.OUTBOX_FILE)) as f:
        assert all("lat" not in json.loads(ln)
                   for ln in f if ln.strip())

    # ---- the fleet post-mortem snapshot landed next to the bundle ----
    view = F.read_json(os.path.join(F.worker_dir(str(fdir), 0),
                                    "postmortem", F.FLEET_VIEW_FILE))
    assert view is not None and view["death"]["worker"] == 0
    assert "chaos kill" in view["death"]["reason"]
    assert view.get("timeline_tail")

    # ---- doctor renders both dirs (timeline + e2e table; plane-off
    # dirs must not regress) ----
    from spatialflink_tpu import doctor

    assert doctor.main(["fleet", str(fdir)]) == 0
    assert doctor.main(["--json", "fleet", str(fdir)]) == 0
    assert doctor.main(["fleet", str(off_dir)]) == 0
