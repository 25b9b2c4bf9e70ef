"""The benchmark harnesses are part of the deliverable (they produce the
BASELINE.md ledger) — smoke-run the end-to-end one as a real subprocess at
tiny scale so it can't rot, and pin the JSON-row contract the ledger and
driver rely on."""

import json
import os
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_window_assign_vectorized_guard():
    """Micro-benchmark guard for the chunked streaming assignment
    (``WindowAssembler.assemble`` riding ``WindowSpec.assign_bulk``): on a
    high-overlap stream it must produce IDENTICAL window tables to the
    per-record ``add`` loop and must not be slower (it removes the
    per-record Python assign loop and the per-record seal sweep, so the
    margin is generous — a regression to per-record cost trips this)."""
    import types

    import numpy as np

    from spatialflink_tpu.runtime.windows import WindowAssembler, WindowSpec

    n = 120_000
    rng = np.random.default_rng(0)
    ts = (1_700_000_000_000 + np.sort(rng.integers(0, 100_000, n))).tolist()
    recs = [types.SimpleNamespace(timestamp=t) for t in ts]
    spec = WindowSpec.sliding(40_000, 5_000)  # overlap 8

    def per_record():
        wa = WindowAssembler(spec)
        out = []
        for r in recs:
            out += [(s, e, len(rr)) for s, e, rr in wa.add(r.timestamp, r)]
        out += [(s, e, len(rr)) for s, e, rr in wa.flush()]
        return out

    def chunked():
        wa = WindowAssembler(spec)
        return [(s, e, len(rr)) for s, e, rr in wa.assemble(iter(recs))]

    per_record(), chunked()  # warm (allocator, numpy import paths)
    t0 = time.perf_counter()
    ref = per_record()
    dt_record = time.perf_counter() - t0
    t0 = time.perf_counter()
    fast = chunked()
    dt_chunk = time.perf_counter() - t0
    assert fast == ref
    # loose bound (CI noise tolerance); measured locally the chunked path
    # is several times faster
    assert dt_chunk < dt_record * 1.2, (dt_chunk, dt_record)


import pytest


@pytest.mark.slow
def test_sweep_panes_smoke(tmp_path):
    """Pane scaling-sweep harness (VERDICT #4) at tiny scale: row contract +
    the in-run window-table identity assertions. Slow: the sweep runs each
    (family, overlap) config in both modes."""
    out_path = tmp_path / "panes.json"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "benchmarks", "sweep_panes.py"),
         "--sizes", "4000", "--overlaps", "1,4", "--families", "knn,join",
         "--join-divisor", "4", "--out", str(out_path)],
        capture_output=True, text=True, timeout=420, env=env, cwd=_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    rows = [json.loads(ln) for ln in r.stdout.splitlines()
            if ln.startswith("{")]
    assert [(x["family"], x["overlap"], x["panes"]) for x in rows] == [
        ("knn", 1, "off"), ("knn", 1, "on"), ("knn", 4, "off"),
        ("knn", 4, "on"), ("join", 1, "off"), ("join", 1, "on"),
        ("join", 4, "off"), ("join", 4, "on")]
    assert all(x["identical"] and x["windows"] > 0 for x in rows)
    assert json.load(open(out_path))["rows"]


def test_bench_kafka_smoke(tmp_path):
    out_path = tmp_path / "kafka.json"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "benchmarks", "bench_kafka.py"),
         "--n", "3000", "--out", str(out_path)],
        capture_output=True, text=True, timeout=420, env=env, cwd=_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    rows = [json.loads(ln) for ln in r.stdout.splitlines()
            if ln.startswith("{")]
    assert [x["path"] for x in rows] == ["record", "chunked", "file"]
    assert all(x["windows"] == rows[0]["windows"] > 0 for x in rows)
    assert json.load(open(out_path))["rows"]


def test_bench_e2e_smoke(tmp_path):
    out_path = tmp_path / "e2e.json"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "benchmarks", "bench_e2e.py"),
         "--n", "2000", "--options", "1,101", "--multi", "2",
         "--out", str(out_path)],
        capture_output=True, text=True, timeout=420, env=env, cwd=_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    rows = [json.loads(ln) for ln in r.stdout.splitlines()
            if ln.startswith("{")]
    # one served row per option (range AND join); the multi rows cover
    # the --multi-query composition end-to-end
    assert [(x["option"], x["path"]) for x in rows] == [
        (1, "record"), (101, "record"),
        (1, "multi_query"), (1, "sequential_jobs")]
    for row in rows[:2]:
        assert row["records"] == 2000
        assert row["records_per_sec"] > 0
        assert row["windows"] > 0
    # the multi-query pipeline seals the single-query run's windows
    assert rows[2]["windows"] == rows[0]["windows"]
    assert rows[2]["queries"] == 2
    assert rows[2]["speedup_vs_sequential_jobs"] > 0
    table = json.loads(out_path.read_text())
    assert table["rows"] and table["backend"] == "cpu"


def test_fleet_rows_refuse_on_an_accelerator(monkeypatch, capsys):
    """Fleet rows spawn driver workers while the harness holds the chip: on
    an accelerator backend they refuse with a message instead of hanging;
    on the CPU they run."""
    import jax

    sys.path.insert(0, _ROOT)
    from benchmarks._common import fleet_refusal

    assert fleet_refusal("fleet") is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    row = fleet_refusal("fleet")
    assert row["path"] == "fleet" and "one process per chip" in row["refused"]
    assert "refused on tpu" in capsys.readouterr().err
