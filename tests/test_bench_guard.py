"""bench_guard's rows in tier-1: every hot path it measures still runs, in
the row contract bench_diff pairs on, with its in-run identity and
conservation checks. Its speed floors (``--check``) are not gated here: a
CPU wall-clock ratio on a shared machine is no evidence of speed."""

import json
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_guard_passes_thresholds():
    """bench_guard runs every row to its end (each row's in-run identity
    asserts hold: merged digests, window tables, attribution conservation)
    and pins the row contract bench_diff pairs on."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "benchmarks", "bench_guard.py"),
         "--n", "60000"],
        capture_output=True, text=True, timeout=480, env=env, cwd=_ROOT)
    rows = [json.loads(ln) for ln in r.stdout.splitlines()
            if ln.startswith("{")]
    assert [x["path"] for x in rows] == [
        "window_assign", "decode_columnar", "windowed_pipeline",
        "skew_adaptive", "query_plane", "controller_pareto",
        "realtime_vectorized", "latency_record_emit",
        "fleet_scaling", "fleet_rescale", "tenant_plane"], r.stdout
    assert all(x["speedup"] > 0 for x in rows if "speedup" in x)
    # the governor's Pareto composite row carries its convergence trace
    # (final chunk, tick/step counts) so a never-ticking controller is
    # visible even while the composite holds
    ctl = [x for x in rows if x["path"] == "controller_pareto"]
    assert len(ctl) == 1 and ctl[0]["gov_ticks"] > 0
    assert ctl[0]["gov_final_chunk"] > 0 and ctl[0]["gov_p99_ms"] > 0
    rt = [x for x in rows if x["path"] == "realtime_vectorized"]
    assert len(rt) == 1 and rt[0]["fires"] > 0
    # the lower-is-better latency row (record→emit p99 through the
    # latency-decomposition plane, gated against its baseline ceiling)
    lat = [x for x in rows if x["path"] == "latency_record_emit"]
    assert len(lat) == 1 and lat[0]["p99_ms"] > 0
    # the lower-is-better fleet row (absolute single-worker supervised-
    # fleet wall at the pinned record count, gated against its ceiling;
    # the bench asserts merged-digest identity across N=1/N=2 in-run)
    fl = [x for x in rows if x["path"] == "fleet_scaling"]
    assert len(fl) == 1 and fl[0]["wall_fleet1_s"] > 0
    assert fl[0]["scaling_n2"] > 0 and fl[0]["overhead_x"] > 0
    assert fl[0]["merged_windows"] > 0
    # the live-rescale row (N=2->4 mid-run at an epoch boundary, digest
    # asserted vs a fixed-N=2 oracle in-run; gated under the shared fleet
    # metric key)
    rs = [x for x in rows if x["path"] == "fleet_rescale"]
    assert len(rs) == 1 and rs[0]["wall_fleet1_s"] > 0
    assert rs[0]["workers_final"] == 4 and rs[0]["rescale_x"] > 0
    assert rs[0]["merged_windows"] > 0
    # the lower-is-better tenant-ledger row (session-on/off wall ratio
    # over the two-tenant dynamic fleet, gated against its ceiling; the
    # bench asserts window-table identity and attribution conservation
    # — every dispatch resolved, zero residual — in-run)
    tp = [x for x in rows if x["path"] == "tenant_plane"]
    assert len(tp) == 1 and tp[0]["overhead_vs_off_x"] > 0
    assert tp[0]["dispatches_resolved"] > 0
    assert tp[0]["max_residual_ms"] < 1e-6
    assert r.returncode == 0, (
        f"bench_guard failed:\n{r.stdout}\n{r.stderr[-1000:]}")


def test_guard_baseline_rows_exist():
    base = json.load(open(os.path.join(_ROOT, "benchmarks",
                                       "GUARD_baseline.json")))
    assert base["metric"] == "speedup"
    assert {r["path"] for r in base["rows"]} == {
        "window_assign", "decode_columnar", "windowed_pipeline",
        "skew_adaptive", "query_plane", "controller_pareto",
        "realtime_vectorized"}
    # the floors assert the batched path (and the skew-adaptive grid on
    # the clustered stream) is actually FASTER than its baseline
    assert all(r["speedup"] >= 1.0 for r in base["rows"])
    # the latency ceilings (lower-is-better second diff pass)
    assert {r["path"] for r in base["latency_rows"]} == {
        "latency_record_emit"}
    assert all(r["p99_ms"] > 0 for r in base["latency_rows"])
    # the fleet supervision-cost + live-rescale ceilings (lower-is-better
    # third pass, both paired on the shared wall_fleet1_s key)
    assert {r["path"] for r in base["fleet_rows"]} == {
        "fleet_scaling", "fleet_rescale"}
    assert all(r["wall_fleet1_s"] > 0 for r in base["fleet_rows"])
    # the tenant-ledger overhead ceiling (lower-is-better fourth pass):
    # a ratio ceiling >= 1 — the ledger may cost something, never 1.5x+
    assert {r["path"] for r in base["tenant_rows"]} == {"tenant_plane"}
    assert all(r["overhead_vs_off_x"] >= 1.0 for r in base["tenant_rows"])
