"""The four-chip kNN cell, ``tdrive-knn-mesh4-drain``, rehearsed on four
virtual CPU devices at the small scale through the whole harness, each run
in a process of its own (the device count is fixed when JAX starts). Then
the comparison is shown to fail for the control and for the mesh merge
left out. And the cell's two readers, on hand-built traces."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

import run as bench
from devtrace import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "tdrive-knn-mesh4-drain"
SEED = 2_147_483_777
E2E = {"events_per_s", "setup_s"}
LAYER = {"device_idle_share.drain", "fetch_share.drain",
         "readback_ms_per_window.drain", "decode_ms_per_window.drain",
         "assembly_ms_per_window.drain", "dispatch_ms_per_window.drain",
         "unattributed_share.drain", "collective_ms_per_window.drain",
         "place_ms_per_window.drain"}
#: the all-gather merge left out: each shard's own partial is the answer,
#: and the replicated output is shard 0's
NO_MERGE = ("from spatialflink_tpu.parallel import ops; "
            "ops._gather_topk = lambda partial, axis_name, k: partial; ")
MS = 1e-3


def run_cell(*extra, trace=0, prelude=""):
    """-> (rc, result line or None, standard error) of one rehearsal."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    argv = ["--workload", CELL, "--seed", str(SEED), "--seconds", "2",
            "--trace", str(trace), "--small", *extra]
    paths = [ROOT, os.path.join(ROOT, "perfbench")]
    code = (f"import sys; sys.path[:0] = {paths!r}; {prelude}"
            f"import run; raise SystemExit(run.main({argv!r}))")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    lines = p.stdout.strip().splitlines()
    return p.returncode, (json.loads(lines[-1]) if lines else None), p.stderr


def aux(err):
    return json.loads(next(ln for ln in err.splitlines()
                           if ln.startswith('{"setup_parts_s"')))


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(trace):
    rc, res, err = run_cell(trace=trace)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == (LAYER if trace else E2E)
    assert aux(err)["lowered_in_window"] == []
    if trace:
        ops = [n for n, _s in res["breakdown"]["device_ops"]]
        assert all(n.startswith("jit_knn_mesh_stats:") for n in ops), ops
        assert res["metrics"]["collective_ms_per_window.drain"]["value"] > 0
        assert res["metrics"]["place_ms_per_window.drain"]["value"] > 0


def test_control_fails():
    rc, res, err = run_cell("--control")
    assert rc == 0, err[-3000:]
    assert res["correct"] is True
    line = next(ln for ln in err.splitlines()
                if ln.startswith('{"control_correct"'))
    assert json.loads(line)["control_correct"] is False


def test_merge_left_out_is_not_correct():
    rc, res, err = run_cell(prelude=NO_MERGE)
    assert rc == 0, err[-3000:]
    assert res["correct"] is False
    assert err.strip().splitlines()[-1] == "correct False"


# ------------------------------------------------------------- readers


def ctx(ops=None, spans=(), windows=2, window=(0.0, 100 * MS)):
    """A reduced trace on a 100 ms window (times in ms): ``ops`` is
    {device: [(start, end, op name)]}."""
    trace = Trace({d: [(s * MS, e * MS, n, "jit_knn_mesh_stats")
                       for s, e, n in v] for d, v in (ops or {}).items()},
                  [(s * MS, e * MS, n) for s, e, n in spans], window)
    return NS(trace=trace, markers_in_window=lambda: [(0, 0, 0)] * windows)


def read(name, c):
    return bench._load("metrics", name).read(c)


def test_collective_time_is_a_per_device_union_averaged():
    c = ctx({"/device:TPU:0": [(-5, 5, "all-gather.2"),
                               (10, 20, "all-gather-start.1"),
                               (15, 25, "all-gather-done.1"),
                               (30, 40, "fusion.3")],
             "/device:TPU:1": [(50, 55, "all-reduce.2"), (60, 62, "psum.7"),
                               (0, 5, "sort.1"), (95, 110, "all_gather.3")]})
    # device 0: 0-5 and 10-25 = 20 ms; device 1: 5 + 2 + 5 = 12 ms
    assert read("collective_ms_per_window.drain", c) == pytest.approx(16 / 2)


def test_collective_time_without_collectives_reads_none():
    c = ctx({"/device:TPU:0": [(0, 10, "fusion"), (20, 30, "sort.1")]})
    assert read("collective_ms_per_window.drain", c) is None
    assert read("collective_ms_per_window.drain", ctx()) is None


def test_place_spans_per_window():
    spans = [(5, 30, "knn.dispatch"), (10, 12, "knn.place"),
             (40, 60, "knn.dispatch"), (41, 44, "knn.place"),
             (98, 104, "knn.place")]
    # the last span counts up to the window's close: 2 + 3 + 2
    assert read("place_ms_per_window.drain",
                ctx(spans=spans, windows=1)) == pytest.approx(7)
    assert read("place_ms_per_window.drain",
                ctx(spans=spans[:1])) is None
