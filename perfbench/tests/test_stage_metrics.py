"""The stage metrics (``stages.py`` and the readers of
``decode_ms_per_window``, ``assembly_ms_per_window``,
``dispatch_ms_per_window``, ``extract_ms_per_window`` and
``unattributed_share``) on hand-built traces with known answers."""

from types import SimpleNamespace as NS

import pytest

import run as bench
import stages
from devtrace import Trace

MS = 1e-3


def ctx(spans, ops=(), windows=2, window=(0.0, 100 * MS)):
    """A reduced trace on a 100 ms window (times in ms), with ``windows``
    windows emitted in it."""
    trace = Trace({"/device:TPU:0": [(s * MS, e * MS, "fusion", "jit_x")
                                     for s, e in ops]} if ops else {},
                  [(s * MS, e * MS, n) for s, e, n in spans], window)
    return NS(trace=trace, markers_in_window=lambda: [(0, 0, 0)] * windows)


def read(name, c):
    return bench._load("metrics", name).read(c)


#: one kNN window pull (10-60 ms) holding fetches, a poll and decodes that
#: overlap each other, then the dispatch, the readback and the sink
KNN = [(10, 60, "knn.window"),
       (12, 20, "kafka.fetch"), (20, 22, "kafka.poll"),
       (22, 30, "kafka.decode"), (28, 34, "kafka.decode"),
       (40, 44, "kafka.fetch"), (44, 45, "kafka.poll"),
       (61, 63, "knn.dispatch"), (63, 66, "knn.merge"),
       (66, 70, "sink"), (67, 69, "kafka.sink"),
       (40, 50, "PjitFunction(x)")]


def test_self_time_nested_and_overlapping_children():
    c = ctx(KNN)
    # 50 ms pull less the union of 12-34 and 40-45 = 50 - 27; a JAX event
    # inside it is no program span and is not taken off
    assert stages.self_s(c.trace) == pytest.approx(23 * MS)
    # per recorded pull, not per window emitted
    assert read("assembly_ms_per_window.drain", c) == pytest.approx(23)


def test_decode_is_the_union_of_poll_decode_materialize():
    c = ctx(KNN + [(30, 33, "decode.materialize")])
    # 20-34 and 44-45
    assert read("decode_ms_per_window.drain", c) == pytest.approx(15 / 2)


def test_dispatch_and_extract_per_window():
    spans = [(0, 90, "join.window"), (5, 15, "join.dispatch"),
             (15, 25, "join.reduce"), (25, 27, "join.compact"),
             (27, 30, "join.lattice"), (30, 40, "join.pairs"),
             (35, 41, "np.asarray(jax.Array)"),
             (50, 54, "join.dispatch"), (54, 56, "join.lattice"),
             (55, 60, "join.pairs")]
    c = ctx(spans, windows=2)
    assert read("dispatch_ms_per_window.drain", c) == pytest.approx(14 / 2)
    # 15-40 and 54-60
    assert read("extract_ms_per_window.drain", c) == pytest.approx(31 / 2)
    # one pull: 90 ms less 5-40 and 50-60
    assert read("assembly_ms_per_window.drain", c) == pytest.approx(45)


def test_spans_are_clipped_at_the_window_edges():
    spans = [(-20, 30, "knn.window"), (-10, 5, "kafka.decode"),
             (90, 130, "knn.window"), (95, 120, "kafka.poll"),
             (98, 105, "knn.dispatch")]
    c = ctx(spans, windows=1)
    assert read("decode_ms_per_window.drain", c) == pytest.approx(10)
    assert read("dispatch_ms_per_window.drain", c) == pytest.approx(2)
    # 0-30 less 0-5, and 90-100 less 95-100, over two pulls
    assert read("assembly_ms_per_window.drain", c) == pytest.approx(15)
    assert stages.union_s(ctx([(150, 160, "kafka.poll")]).trace,
                          stages.DECODE) is None


@pytest.mark.parametrize("name", [
    "decode_ms_per_window.drain", "assembly_ms_per_window.drain",
    "dispatch_ms_per_window.drain", "extract_ms_per_window.drain",
    "unattributed_share.drain"])
def test_none_without_the_spans(name):
    """A program without the stage spans reads None, and nothing raises; so
    does a per-window metric of a run that emitted no window."""
    old = [(0, 40, "kafka.fetch"), (40, 50, "PjitFunction(x)")]
    assert read(name, ctx(old, ops=[(1, 2)])) is None
    if name in ("decode_ms_per_window.drain", "dispatch_ms_per_window.drain",
                "extract_ms_per_window.drain"):
        every = KNN + [(1, 3, "join.pairs"), (70, 71, "decode")]
        assert read(name, ctx(every, windows=0)) is None


#: a second pull (75-95) after KNN's: the stretch is 10-95 ms
TWO = KNN + [(75, 95, "knn.window"), (80, 84, "kafka.decode")]


def test_unattributed_share_with_device_ops_and_overlapping_spans():
    # spans cover 10-60, 61-70 and 75-95; the device covers 44-62
    # (bridging two of them), 70-72 and 98-99 (outside the stretch); left
    # 72-75 = 3 of 85 ms
    c = ctx(TWO, ops=[(44, 50), (48, 62), (70, 72), (98, 99)])
    assert read("unattributed_share.drain", c) == pytest.approx(300 / 85)
    # no device operation at all: only the spans count, 60-61 and 70-75
    assert read("unattributed_share.drain", ctx(TWO)) == \
        pytest.approx(600 / 85)


def test_unattributed_share_skips_the_pulls_the_trace_cut():
    """Before the first recorded pull the trace holds the children of a
    pull already in progress when it opened, but not that pull: that
    stretch is left out, not read as unattributed."""
    c = ctx([(0, 4, "kafka.fetch"), (6, 8, "kafka.decode")] + TWO)
    assert read("unattributed_share.drain", c) == pytest.approx(600 / 85)


#: the stage metrics each cell's traced line must carry, beside the metrics
#: the cell had before them
STAGE = {"tdrive-knn-window-drain": {"decode_ms_per_window.drain",
                                     "assembly_ms_per_window.drain",
                                     "dispatch_ms_per_window.drain",
                                     "unattributed_share.drain"},
         "tdrive-join-window-drain": {"decode_ms_per_window.drain",
                                      "assembly_ms_per_window.drain",
                                      "dispatch_ms_per_window.drain",
                                      "extract_ms_per_window.drain",
                                      "unattributed_share.drain"}}


@pytest.mark.parametrize("cell", sorted(STAGE))
def test_traced_rehearsal_reports_the_stage_metrics(capsys, cell):
    """The CPU rehearsal of each cell, traced, reads every stage metric
    from the program's own spans, and nothing else changes in its line."""
    import json

    rc = bench.main(["--workload", cell, "--seed", "2147483999",
                     "--seconds", "2", "--trace", "1", "--small"])
    out, err = capsys.readouterr()
    assert rc == 0, err[-3000:]
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is True, res["compared"]
    metrics = set(res["metrics"])
    assert STAGE[cell] <= metrics
    assert {"device_idle_share.drain", "fetch_share.drain"} <= metrics
    for name in STAGE[cell]:
        assert res["metrics"][name]["value"] >= 0
    assert res["metrics"]["unattributed_share.drain"]["value"] <= 100
