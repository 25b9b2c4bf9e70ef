"""Post-mortem / preflight doctor for the device-truth plane.

``python -m spatialflink_tpu.doctor`` reads what the flight recorder
(``--postmortem-dir``) writes and answers the questions an operator has
BEFORE and AFTER a run:

- ``--preflight [--require-backend tpu]`` — readiness check for the
  accelerator: backend provenance vs the required target (a run that
  would land on the CPU exits non-zero instead of being discovered in a
  ledger tail), device visibility, memory-stats availability, a tiny
  instrumented-jit probe compile (proves the compile path + registry), and
  the persistent compilation-cache configuration. Exit 0 = ready.
- ``summarize BUNDLE`` — one human digest of a post-mortem bundle: dump
  reason, error, backend, throughput/window counters, health verdict,
  compile/recompile counts with the hottest trigger signatures, last
  flight-recorder notes and lifecycle events.
- ``diff A B`` — compare two bundles (e.g. a crashed run against a healthy
  baseline): backend equality (cross-backend comparisons are flagged the
  way ``bench_diff`` refuses them), counter deltas, compile/recompile
  deltas, health verdicts side by side. Exit 0; structural problems
  (unreadable bundle, schema mismatch) exit 2.
- ``tenants BUNDLE`` — the per-tenant cost table from a bundle's
  ``tenants.json``: attributed kernel-ms (+ share), bytes, records,
  windows, SLO/shed/quota counters, the fairness line, and the worst
  attribution residual — "who was paying for the pipeline when it died".

All output is line-oriented text by default; ``--json`` emits one JSON
document instead (machine-readable — the same dict the text renders).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

from spatialflink_tpu.utils import deviceplane


# --------------------------------------------------------------------- #
# bundle IO


def load_bundle(path: str) -> dict:
    """Read one flight-recorder bundle directory into a dict keyed by file
    stem (manifest/status/compile/device/events/traces/flight/config).
    Raises ValueError on a missing/unreadable manifest or a schema this
    doctor does not speak."""
    manifest_path = os.path.join(path, "manifest.json")
    try:
        with open(manifest_path) as f:
            manifest = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise ValueError(f"{path}: not a post-mortem bundle "
                         f"(manifest.json unreadable: {e})")
    schema = manifest.get("schema")
    if schema != deviceplane.BUNDLE_SCHEMA:
        raise ValueError(f"{path}: bundle schema {schema!r} != "
                         f"{deviceplane.BUNDLE_SCHEMA} (this doctor is too "
                         "old or the bundle too new)")
    out = {"manifest": manifest, "path": path}
    for name in manifest.get("files", []):
        stem = name[:-5] if name.endswith(".json") else name
        try:
            with open(os.path.join(path, name)) as f:
                out[stem] = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            out[stem] = {"error": f"unreadable: {e}"}
    return out


def _bundle_digest(b: dict) -> dict:
    """The comparable core of one bundle (summarize renders it, diff
    subtracts it)."""
    manifest = b.get("manifest", {})
    status = b.get("status", {}) or {}
    st = status.get("status", {}) or {}
    device = b.get("device", {}) or {}
    compile_ = b.get("compile", {}) or {}
    health = status.get("health")
    return {
        "path": b.get("path"),
        "reason": manifest.get("reason"),
        "ts_ms": manifest.get("ts_ms"),
        "error": manifest.get("error"),
        "backend": (device.get("backend") or {}).get("platform"),
        "device_kind": (device.get("backend") or {}).get("device_kind"),
        "valid_for_target": (device.get("backend") or {}).get(
            "valid_for_target"),
        "records_in": st.get("records_in", 0),
        "windows": st.get("windows_evaluated", 0),
        "throughput_rps": st.get("throughput_rps", 0.0),
        "slo_breaches": st.get("slo_breaches", 0),
        "healthy": None if health is None else health.get("healthy"),
        "unhealthy_checks": ([] if health is None else
                             sorted(n for n, c in health["checks"].items()
                                    if not c["ok"])),
        "compiles": compile_.get("total_compiles", 0),
        "post_warmup_compiles": compile_.get("post_warmup_compiles", 0),
        "warm": compile_.get("warm"),
        "mem_bytes_in_use": (device.get("memory") or {}).get("bytes_in_use"),
        "d2h_bytes": (device.get("transfer") or {}).get("d2h_bytes", 0),
        "dispatch_overlap_p50": (device.get("dispatch_overlap") or {}).get(
            "p50"),
        "events": len((b.get("events") or {}).get("events", [])),
        "notes": (b.get("flight") or {}).get("total", 0),
        "record_emit_p99_ms": ((b.get("latency") or {}).get("record_emit")
                               or {}).get("p99"),
        "budgeted_windows": ((b.get("latency") or {}).get("sum_check")
                             or {}).get("windows", 0),
    }


def _latency_table(latency: dict) -> List[str]:
    """The stage-budget table of a bundle's latency decomposition — the
    offline answer to "which stage blew the budget": per-stage count /
    p50 / p99 / total, chain stages first (their totals decompose
    record→emit), downstream sink stages after."""
    stages = latency.get("stages") or {}
    if not stages:
        return []
    chain = list(latency.get("chain_stages")
                 or ("buffer", "queue", "dispatch", "inflight", "merge",
                     "emit"))
    order = [s for s in chain if s in stages] + sorted(
        s for s in stages if s not in chain)
    total_ms = sum((stages[s].get("sum") or 0.0) for s in order
                   if s in chain)
    lines = ["stage        windows      p50 ms      p99 ms    total ms  "
             "share"]
    for s in order:
        h = stages[s]
        share = ((h.get("sum") or 0.0) / total_ms * 100) if total_ms \
            and s in chain else None
        lines.append(
            f"{s:<12} {h.get('count', 0):>7} {h.get('p50', 0.0):>11.3f} "
            f"{h.get('p99', 0.0):>11.3f} {h.get('sum', 0.0):>11.1f}  "
            + (f"{share:>4.0f}%" if share is not None else "    -"))
    re_h = latency.get("record_emit") or {}
    if re_h.get("count"):
        lines.append(
            f"{'record→emit':<12} {re_h['count']:>7} {re_h['p50']:>11.3f} "
            f"{re_h['p99']:>11.3f} {re_h.get('sum', 0.0):>11.1f}   100%")
    check = latency.get("sum_check") or {}
    if check.get("windows"):
        lines.append(f"sum check    {check['windows']} window(s), max "
                     f"residual {check.get('max_residual_ms', 0.0)} ms")
    bp = (latency.get("backpressure") or {}).get("series") or []
    stalls = sum(1 for bkt in bp if bkt.get("stall"))
    if bp:
        lines.append(f"backpressure {len(bp)} bucket(s), {stalls} "
                     "stalled")
    return lines


# --------------------------------------------------------------------- #
# commands


def summarize(path: str, as_json: bool = False,
              out=None) -> int:
    # resolve at call time: a def-time sys.stdout default would pin
    # whatever stream was installed at first import (pytest capture)
    out = sys.stdout if out is None else out
    b = load_bundle(path)
    d = _bundle_digest(b)
    if as_json:
        print(json.dumps(d, sort_keys=True), file=out)
        return 0
    print(f"bundle     {path}", file=out)
    print(f"reason     {d['reason']}" + (f" — {d['error']}" if d["error"]
                                         else ""), file=out)
    print(f"backend    {d['backend']} ({d['device_kind']}), "
          f"valid_for_target={d['valid_for_target']}", file=out)
    print(f"pipeline   {d['records_in']} records in, {d['windows']} windows, "
          f"{d['throughput_rps']:.0f} rec/s", file=out)
    if d["healthy"] is not None:
        bad = ",".join(d["unhealthy_checks"]) or "-"
        print(f"health     {'ok' if d['healthy'] else 'BREACH'} "
              f"(failing: {bad}; {d['slo_breaches']} breach transition(s))",
              file=out)
    print(f"compiles   {d['compiles']} total, "
          f"{d['post_warmup_compiles']} post-warmup (warm={d['warm']})",
          file=out)
    for e in (b.get("compile") or {}).get("entries", [])[:5]:
        sig = e["signatures"][-1]["signature"] if e["signatures"] else "?"
        print(f"  {e['compiles']:3d}x {e['name']}  last {sig[:80]}",
              file=out)
    if d["dispatch_overlap_p50"] is not None:
        print(f"overlap    p50 {d['dispatch_overlap_p50']:.2f}", file=out)
    for line in _latency_table(b.get("latency") or {}):
        print(f"latency    {line}", file=out)
    print(f"transfer   d2h {d['d2h_bytes']} B; device mem in use "
          f"{d['mem_bytes_in_use']}", file=out)
    notes = (b.get("flight") or {}).get("notes", [])[-5:]
    for nte in notes:
        extra = {k: v for k, v in nte.items() if k not in ("ts_ms", "kind")}
        print(f"note       {nte.get('kind')} {extra}", file=out)
    evs = (b.get("events") or {}).get("events", [])[-5:]
    for ev in evs:
        print(f"event      #{ev.get('seq')} {ev.get('kind')}", file=out)
    return 0


def diff(path_a: str, path_b: str, as_json: bool = False,
         out=None) -> int:
    out = sys.stdout if out is None else out
    a, b = load_bundle(path_a), load_bundle(path_b)
    da, db = _bundle_digest(a), _bundle_digest(b)
    rows = []
    for key in ("reason", "error", "backend", "device_kind", "healthy",
                "unhealthy_checks", "records_in", "windows",
                "throughput_rps", "slo_breaches", "compiles",
                "post_warmup_compiles", "d2h_bytes",
                "dispatch_overlap_p50", "mem_bytes_in_use",
                "record_emit_p99_ms", "budgeted_windows"):
        va, vb = da.get(key), db.get(key)
        rows.append({"field": key, "a": va, "b": vb, "equal": va == vb})
    doc = {"a": path_a, "b": path_b,
           "cross_backend": da["backend"] != db["backend"],
           "rows": rows}
    if as_json:
        print(json.dumps(doc, sort_keys=True), file=out)
        return 0
    print(f"A: {path_a}  ({da['reason']})", file=out)
    print(f"B: {path_b}  ({db['reason']})", file=out)
    if doc["cross_backend"]:
        print(f"WARNING: cross-backend diff ({da['backend']} vs "
              f"{db['backend']}) — throughput/latency deltas are not "
              "comparable (the bench_diff pairing rule)", file=out)
    for r in rows:
        mark = " " if r["equal"] else "*"
        print(f"{mark} {r['field']:<22} {r['a']!r:>24} | {r['b']!r}",
              file=out)
    return 0


def preflight(require_backend: str = "tpu", as_json: bool = False,
              out=None) -> int:
    """Backend/memory/compile-cache readiness check; exit non-zero when the
    chip the operator asked for is not what the process would run on."""
    out = sys.stdout if out is None else out
    import time as _time

    checks: List[dict] = []

    def check(name: str, ok: Optional[bool], detail) -> None:
        checks.append({"check": name, "ok": ok, "detail": detail})

    prov = None
    try:
        prov = deviceplane.backend_provenance(target=require_backend)
        check("backend", prov["platform"] == require_backend,
              f"platform={prov['platform']} device_kind="
              f"{prov['device_kind']} x{prov['device_count']} "
              f"(required: {require_backend})")
    except Exception as e:
        check("backend", False, f"backend probe failed: {e}")
    mem = deviceplane.memory_gauges()
    check("memory_stats", None if not mem["available"] else True,
          ("memory_stats available, "
           f"in_use={mem['bytes_in_use']}" if mem["available"]
           else "no memory_stats on this backend (normal on CPU)"))
    # compile probe: a tiny instrumented jit through the registry — proves
    # the XLA compile path AND that the sentinel would see it
    try:
        import jax.numpy as jnp

        reg = deviceplane.registry()
        before = reg.total_compiles
        t0 = _time.perf_counter()
        fn = deviceplane.instrumented_jit(lambda x: (x * 2 + 1).sum())
        float(fn(jnp.arange(8.0)))
        dt_ms = (_time.perf_counter() - t0) * 1e3
        check("compile_probe", reg.total_compiles == before + 1,
              f"1 compile in {dt_ms:.0f}ms, registry saw it "
              f"({reg.total_compiles - before} recorded)")
    except Exception as e:
        check("compile_probe", False, f"probe compile failed: {e}")
    try:
        import jax

        cache_dir = jax.config.jax_compilation_cache_dir
        check("compilation_cache", None if not cache_dir else True,
              (f"persistent compilation cache at {cache_dir}" if cache_dir
               else "no persistent compilation cache configured "
                    "(jax_compilation_cache_dir unset — every process "
                    "pays cold compiles)"))
    except Exception as e:
        check("compilation_cache", None, f"unreadable: {e}")
    # static invariants: the same pass the tier-1 gate runs — a dirty
    # tree fails preflight exactly like a CPU fallback would
    analysis_summary = None
    try:
        from spatialflink_tpu.analysis import run_analysis

        rep = run_analysis()
        rep_doc = rep.to_dict()
        by_rule = rep_doc["findings_by_rule"]
        analysis_summary = {
            "ok": rep.ok,
            "findings": len(rep_doc["findings"]),
            "findings_by_rule": by_rule,
            "allowlisted": len(rep_doc["allowlisted"])
            + len(rep_doc["pragma_allowlisted"]),
            "stale_allowlist_entries": len(
                rep_doc["stale_allowlist_entries"]),
            "stale_pragmas": len(rep_doc["stale_pragmas"]),
            "files": rep_doc["files"],
            "rules": rep_doc["rules"],
        }
        stale = analysis_summary["stale_allowlist_entries"] \
            + analysis_summary["stale_pragmas"]
        dirty = ", ".join(f"{r}={n}" for r, n in sorted(by_rule.items())
                          if n) or "all rules clean"
        check("static_analysis", rep.ok,
              f"per-rule findings: {dirty}; "
              f"{analysis_summary['allowlisted']} allowlisted,"
              f" {stale} stale suppression"
              f"{'' if stale == 1 else 's'} across "
              f"{analysis_summary['files']} file(s)"
              + ("" if rep.ok else
                 " — run `python -m spatialflink_tpu.analysis --check`"))
    except Exception as e:
        check("static_analysis", False, f"analysis pass failed: {e}")
    failed = [c for c in checks if c["ok"] is False]
    doc = {"ready": not failed, "require_backend": require_backend,
           "provenance": prov, "checks": checks,
           "analysis": analysis_summary}
    if as_json:
        print(json.dumps(doc, sort_keys=True), file=out)
    else:
        for c in checks:
            mark = {True: "ok  ", False: "FAIL", None: "note"}[c["ok"]]
            print(f"{mark} {c['check']:<18} {c['detail']}", file=out)
        print(("ready" if not failed else
               f"NOT READY ({', '.join(c['check'] for c in failed)})"),
              file=out)
    return 0 if not failed else 1


def tenants(path: str, as_json: bool = False, out=None) -> int:
    """The per-tenant cost table of one bundle's ``tenants.json`` —
    the post-mortem answer to "who was paying when it died": attributed
    kernel-ms with shares, bytes moved, records in/out, windows, and the
    SLO/shed/quota counters, plus the fairness summary and the worst
    per-dispatch attribution residual (the conservation check)."""
    out = sys.stdout if out is None else out
    b = load_bundle(path)
    ten = b.get("tenants") or {}
    rows = ten.get("tenants") or {}
    doc = {"path": path, "tenants": rows,
           "fairness": ten.get("fairness"),
           "default_tenant": ten.get("default_tenant"),
           "pending": ten.get("pending"),
           "max_residual_ms": ten.get("max_residual_ms")}
    if as_json:
        print(json.dumps(doc, sort_keys=True), file=out)
        return 0
    print(f"bundle     {path}", file=out)
    if not rows:
        print("tenants    (no tenant ledger in this bundle — no telemetry "
              "session at dump time)", file=out)
        return 0
    total_ms = sum(float(r.get("kernel_ms") or 0.0) for r in rows.values())
    print(f"{'tenant':<16} {'kernel ms':>10} {'share':>6} {'bytes':>12} "
          f"{'rec in':>9} {'rec out':>8} {'windows':>8} {'slo':>4} "
          f"{'shed':>5} {'quota':>6}", file=out)
    for t, r in sorted(rows.items(),
                       key=lambda kv: -float(kv[1].get("kernel_ms") or 0.0)):
        kms = float(r.get("kernel_ms") or 0.0)
        share = f"{kms / total_ms * 100:.0f}%" if total_ms else "-"
        print(f"{t:<16} {kms:>10.1f} {share:>6} "
              f"{int(r.get('bytes_moved') or 0):>12} "
              f"{int(r.get('records_in') or 0):>9} "
              f"{int(r.get('records_out') or 0):>8} "
              f"{int(r.get('windows') or 0):>8} "
              f"{int(r.get('slo_breaches') or 0):>4} "
              f"{int(r.get('shed') or 0):>5} "
              f"{int(r.get('quota_rejections') or 0):>6}", file=out)
    fair = ten.get("fairness") or {}
    if fair.get("top") is not None:
        print(f"fairness   top {fair.get('top')} "
              f"({(fair.get('top_share') or 0.0) * 100:.0f}%), max/min "
              f"share {(fair.get('max_share') or 0.0) * 100:.0f}%/"
              f"{(fair.get('min_share') or 0.0) * 100:.0f}%, "
              f"gini {fair.get('gini') or 0.0:.2f}", file=out)
    resid = ten.get("max_residual_ms")
    if resid is not None:
        print(f"residual   max attribution residual {float(resid):.6f} ms "
              "(per-dispatch conservation: attributed sums to measured)",
              file=out)
    return 0


def fleet(path: str, as_json: bool = False, out=None) -> int:
    """One table over a whole fleet directory: per worker, every
    incarnation's run summary (``runs.jsonl``), the newest post-mortem
    bundle's verdict when one exists, restart reasons from the fleet
    result, recompile events, and record→emit p99 — "who died, why, and
    did the respawn stay warm" in one read. With the observability plane
    on the read widens: the end-to-end record→merged-emit stage-budget
    table from ``fleet_latency.json`` and the merged timeline tail from
    ``fleet_events.jsonl`` (both optional — plane-off and pre-plane
    fleet dirs still render). Elastic fleets add the fence history
    (which incarnations were superseded, how many stale zombie rows the
    merge dropped), the rescale log, and the quarantine log."""
    from spatialflink_tpu.runtime import fleet as fleet_mod

    out = sys.stdout if out is None else out
    if not os.path.isdir(path):
        raise ValueError(f"{path}: not a fleet directory")
    result = fleet_mod.read_json(
        os.path.join(path, fleet_mod.RESULT_FILE)) or {}
    manifest_state = fleet_mod.read_json(
        os.path.join(path, fleet_mod.MANIFEST_FILE)) or {}
    fence_log = manifest_state.get("fence_log") or []
    rescale_log = manifest_state.get("rescale_log") or []
    quarantine_log = manifest_state.get("quarantine_log") or []
    fences = {int(k): int(v) for k, v in
              (manifest_state.get("fences") or {}).items()}
    fleet_lat = fleet_mod.read_json(
        os.path.join(path, fleet_mod.LATENCY_FILE))
    timeline_tail: List[dict] = []
    try:
        with open(os.path.join(path, fleet_mod.EVENTS_FILE)) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    timeline_tail.append(json.loads(line))
                except json.JSONDecodeError:
                    continue
        timeline_tail = timeline_tail[-20:]
    except OSError:
        pass  # plane off / pre-plane fleet dir: no timeline to show
    worker_ids = sorted(
        int(name[len("worker"):]) for name in os.listdir(path)
        if name.startswith("worker")
        and name[len("worker"):].isdigit()
        and os.path.isdir(os.path.join(path, name)))
    if not worker_ids:
        raise ValueError(f"{path}: no worker directories (is this a "
                         "--fleet-dir?)")
    restart_reasons: dict = {}
    for r in result.get("restart_log", []):
        restart_reasons.setdefault(int(r.get("worker", -1)),
                                   []).append(r.get("reason"))
    rows = []
    for wid in worker_ids:
        wd = fleet_mod.worker_dir(path, wid)
        runs = fleet_mod.read_runs(wd)
        last = runs[-1] if runs else {}
        bundle_digest = None
        pm_dir = os.path.join(wd, "postmortem")
        if os.path.isdir(pm_dir):
            bundles = sorted(
                os.path.join(pm_dir, b) for b in os.listdir(pm_dir)
                if os.path.isdir(os.path.join(pm_dir, b)))
            for b in reversed(bundles):  # newest bundle that loads
                try:
                    bundle_digest = _bundle_digest(load_bundle(b))
                    break
                except ValueError:
                    continue
        # fence-aware read: apply the manifest's byte cutoffs so the
        # doctor's window counts match what the merge actually admitted,
        # and surface how many zombie rows were dropped per worker
        ob_stats: dict = {}
        cutoffs = {f: c["outbox"] for f, c in fleet_mod.fence_cutoffs_from(
            {"fence_log": fence_log}, wid).items()}
        windows = fleet_mod.read_outbox(
            os.path.join(wd, fleet_mod.OUTBOX_FILE),
            fence_cutoffs=cutoffs, stats=ob_stats)
        rows.append({
            "worker": wid,
            "incarnations": len(runs),
            "restarts": len(restart_reasons.get(wid, [])),
            "restart_reasons": restart_reasons.get(wid, []),
            "fence": fences.get(wid, 0),
            "stale_fence_rows": ob_stats.get("stale_fence_rows", 0),
            "fence_conflicts": ob_stats.get("fence_conflicts", 0),
            "windows": len(windows),
            "emitted": last.get("emitted"),
            "last_rc": last.get("rc"),
            "graceful": last.get("graceful"),
            "resumed": last.get("resumed"),
            "post_warmup_compiles": sum(
                int(r.get("post_warmup_compiles") or 0) for r in runs),
            "last_verdict": (None if bundle_digest is None
                             else bundle_digest.get("reason")),
            "bundle_healthy": (None if bundle_digest is None
                               else bundle_digest.get("healthy")),
            "record_emit_p99_ms": (
                last.get("record_emit_p99_ms")
                if last.get("record_emit_p99_ms") is not None
                else (bundle_digest or {}).get("record_emit_p99_ms")),
        })
    doc = {"path": path,
           "digest": result.get("digest"),
           "merged_windows": result.get("merged_windows"),
           "routed": result.get("routed"),
           "epochs": result.get("epochs"),
           "graceful": result.get("graceful"),
           "post_warmup_compiles": result.get("post_warmup_compiles"),
           "workers": rows,
           "fences": {str(k): v for k, v in sorted(fences.items())},
           "fence_log": fence_log,
           "rescale_log": rescale_log,
           "quarantine_log": quarantine_log,
           "stale_fence_rows": sum(r["stale_fence_rows"] for r in rows),
           "latency": fleet_lat,
           "timeline_tail": timeline_tail}
    if as_json:
        print(json.dumps(doc, sort_keys=True), file=out)
        return 0
    print(f"fleet      {path}", file=out)
    if result:
        digest = result.get("digest") or "?"
        print(f"result     {result.get('merged_windows')} merged windows "
              f"from {result.get('workers')} workers, "
              f"{result.get('routed')} routed, digest {digest[:16]}",
              file=out)
        print(f"compiles   {result.get('post_warmup_compiles')} "
              "post-warmup across all incarnations", file=out)
    else:
        print("result     (no fleet_result.json — run incomplete or "
              "killed)", file=out)
    hdr = (f"{'worker':>6} {'inc':>4} {'restarts':>8} {'fence':>5} "
           f"{'windows':>8} {'last rc':>7} {'compiles':>8} {'p99 ms':>8}"
           "  last verdict")
    print(hdr, file=out)
    for r in rows:
        p99 = r["record_emit_p99_ms"]
        verdict = r["last_verdict"] or (
            "graceful stop" if r.get("graceful") else "-")
        print(f"{r['worker']:>6} {r['incarnations']:>4} "
              f"{r['restarts']:>8} {r['fence']:>5} {r['windows']:>8} "
              f"{('-' if r['last_rc'] is None else r['last_rc']):>7} "
              f"{r['post_warmup_compiles']:>8} "
              f"{('-' if p99 is None else f'{p99:.1f}'):>8}  {verdict}",
              file=out)
        for reason in r["restart_reasons"]:
            print(f"{'':>6} restart: {reason}", file=out)
        if r["stale_fence_rows"] or r["fence_conflicts"]:
            print(f"{'':>6} fenced: {r['stale_fence_rows']} stale zombie "
                  f"row(s) dropped, {r['fence_conflicts']} cross-fence "
                  "conflict(s) resolved", file=out)
    for e in fence_log:
        print(f"fence      w{e.get('worker')} -> fence {e.get('fence')} "
              f"({e.get('reason')}; outbox cutoff "
              f"{e.get('outbox_bytes')}B, journal "
              f"{e.get('journal_bytes')}B)", file=out)
    for e in rescale_log:
        print(f"rescale    {e.get('n_from')} -> {e.get('n_to')} workers "
              f"at {e.get('at_records')} routed records "
              f"(epoch {e.get('epoch')})", file=out)
    for e in quarantine_log:
        extra = {k: v for k, v in e.items()
                 if k not in ("ts_ms", "worker", "action")}
        print(f"quarantine w{e.get('worker')} {e.get('action')}"
              + (f" {extra}" if extra else ""), file=out)
    if fleet_lat:
        # end-to-end record→merged-emit decomposition: the worker chain
        # plus spread/outbox-visible/merge/merged-emit — same renderer as
        # a bundle's table, so the two reads line up stage by stage
        for line in _latency_table(fleet_lat):
            print(f"e2e        {line}", file=out)
        skipped = fleet_lat.get("skipped_no_lat")
        if skipped:
            print(f"e2e        {skipped} merged window(s) without a "
                  "lineage sidecar (plane off for part of the run, or "
                  "budget rows evicted)", file=out)
        for wid, s in sorted((fleet_lat.get("workers") or {}).items()):
            dom = s.get("dominant_stage") or "-"
            p99 = s.get("record_emit_p99_ms")
            print(f"sample     w{wid} "
                  f"p99 {('-' if p99 is None else f'{p99:.1f}ms')} "
                  f"dom {dom} "
                  f"backlog {s.get('backlog_residency_ms') or 0:.0f}ms "
                  f"inc {s.get('incarnation')}", file=out)
    for ev in timeline_tail:
        who = (f"w{ev.get('worker')}" if ev.get("src") == "worker"
               else "sup")
        extra = {k: v for k, v in ev.items()
                 if k not in ("ts_ms", "mono_ms", "seq", "kind", "src",
                              "worker", "worker_seq")}
        print(f"timeline   #{ev.get('seq'):>4} {who:<4} {ev.get('kind')}"
              + (f" {extra}" if extra else ""), file=out)
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    # `doctor --preflight` and `doctor preflight` both work (the flag form
    # is what the flight-recorder banner and ISSUE spell)
    if "--preflight" in argv:
        argv[argv.index("--preflight")] = "preflight"
    ap = argparse.ArgumentParser(
        prog="python -m spatialflink_tpu.doctor",
        description="preflight the device plane; summarize/diff "
                    "post-mortem bundles")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON document instead of text")
    sub = ap.add_subparsers(dest="cmd", required=True)
    p = sub.add_parser("preflight", help="backend/memory/compile readiness")
    p.add_argument("--require-backend", default="tpu",
                   choices=("cpu", "tpu", "gpu"),
                   help="platform the run must land on (default tpu: the "
                        "CPU-fallback condition exits non-zero)")
    s = sub.add_parser("summarize", help="digest one bundle")
    s.add_argument("bundle")
    d = sub.add_parser("diff", help="compare two bundles")
    d.add_argument("bundle_a")
    d.add_argument("bundle_b")
    tn = sub.add_parser("tenants", help="per-tenant cost table from one "
                                        "bundle: attributed kernel-ms "
                                        "shares, quota/shed counters, "
                                        "fairness, attribution residual")
    tn.add_argument("bundle")
    fl = sub.add_parser("fleet", help="one table over a --fleet-dir: "
                                      "who died, restarts, recompiles, "
                                      "per-worker p99, the end-to-end "
                                      "stage-budget table, and the fleet "
                                      "timeline tail")
    fl.add_argument("fleet_dir")
    args = ap.parse_args(argv)
    try:
        if args.cmd == "preflight":
            return preflight(args.require_backend, as_json=args.json)
        if args.cmd == "summarize":
            return summarize(args.bundle, as_json=args.json)
        if args.cmd == "fleet":
            return fleet(args.fleet_dir, as_json=args.json)
        if args.cmd == "tenants":
            return tenants(args.bundle, as_json=args.json)
        return diff(args.bundle_a, args.bundle_b, as_json=args.json)
    except ValueError as e:
        print(f"doctor: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    raise SystemExit(main())
