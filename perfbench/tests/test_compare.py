"""The comparison's numbers on small hand-made streams: zero for the
reference's own answers, the planted error for a wrong one."""

import numpy as np
import pytest

import stream as gen
from reference import compare
from reference.oracle import Reference, round_bf16

CONF = {"grid_bbox": [115.5, 39.6, 117.6, 41.1], "num_grid_cells": 100}
Q = {"point": [116.5, 40.5], "radius": 0.5, "k": 5}


def stream(n=4000, seed=1, fleet=300, rate=400):
    s = gen.make_stream(CONF, n, seed, "taxis", "t", fleet, 0.2)
    s.ts = gen.T0 + np.arange(n) * 1000 // rate
    return s


def test_bf16_rounding():
    v = np.array([1.0, 1 + 2**-8, 1 + 1.5 * 2**-8, 0.3], np.float32)
    assert round_bf16(v).tolist() == [1.0, 1.0, 1.0078125, 0.30078125]


def knn_answers(ref, s, fleet=300):
    out = {}
    for start in (gen.T0, gen.T0 + 5000):
        lo, hi = np.searchsorted(s.ts, [start, start + 10_000])
        ids, d, _b = ref.knn(s.oid[lo:hi], s.x[lo:hi], s.y[lo:hi],
                             *Q["point"], Q["radius"], Q["k"], fleet)
        out[start] = list(zip(ids.tolist(), d.tolist()))
    return out


def test_knn_numbers():
    ref, s = Reference(CONF), stream()
    good = knn_answers(ref, s)
    assert compare.knn_numbers(ref, Q, s, good, 10_000, 5000, 300) == {
        "knn_dist_gap": 0.0, "knn_bad_windows": 0, "no_answers": 0}
    bad = {k: list(v) for k, v in good.items()}
    i, d = bad[gen.T0][2]
    bad[gen.T0][2] = (i, d + 1e-3)
    assert compare.knn_numbers(ref, Q, s, bad, 10_000, 5000, 300)[
        "knn_dist_gap"] == pytest.approx(1e-3)
    bad[gen.T0 + 5000] = bad[gen.T0 + 5000][:-1]
    assert compare.knn_numbers(ref, Q, s, bad, 10_000, 5000, 300)[
        "knn_bad_windows"] == 1
    assert compare.knn_numbers(ref, Q, s, {}, 10_000, 5000, 300)[
        "no_answers"] == 1


def test_knn_slides_make_the_window():
    """The per-slide reference gives each window what the reference over
    the whole window gives."""
    ref, s = Reference(CONF), stream(20_000)
    for start in (gen.T0, gen.T0 + 5000, gen.T0 + 10_000):
        lo, hi = np.searchsorted(s.ts, [start, start + 10_000])
        ids, d, _b = ref.knn(s.oid[lo:hi], s.x[lo:hi], s.y[lo:hi],
                             *Q["point"], Q["radius"], Q["k"], 300)
        good = {start: list(zip(ids.tolist(), d.tolist()))}
        assert compare.knn_numbers(ref, Q, s, good, 10_000, 5000, 300)[
            "knn_dist_gap"] == 0.0
        bad = {start: good[start][:-1] + [(int(ids[-1]), float(d[-1]) + 1e-3)]}
        assert compare.knn_numbers(ref, Q, s, bad, 10_000, 5000, 300)[
            "knn_dist_gap"] == pytest.approx(1e-3)


def test_control_is_caught():
    ref, ctrl, s = Reference(CONF), Reference(CONF, "bf16"), stream(20_000)
    n = compare.knn_numbers(ref, Q, s, knn_answers(ctrl, s), 10_000, 5000,
                            300)
    assert n["knn_dist_gap"] > 1e-4


def test_join_numbers():
    ref = Reference(CONF)
    a = stream(4000, seed=2)
    b = stream(400, seed=3, fleet=40, rate=40)
    r = {"radius": 0.05}
    windows = {}
    for start in (gen.T0, gen.T0 + 5000):
        a0, a1 = np.searchsorted(a.ts, [start, start + 10_000])
        b0, b1 = np.searchsorted(b.ts, [start, start + 10_000])
        ia, ib, _d = ref.join_pairs(a.x[a0:a1], a.y[a0:a1], b.x[b0:b1],
                                    b.y[b0:b1], 0.05)
        windows[start] = (ia + a0, ib + b0)
    assert compare.join_numbers(ref, r, a, b, windows, 10_000) == {
        "join_excess": 0.0, "join_missed": 0.0, "join_bad_pairs": 0,
        "no_answers": 0}
    ia, ib = windows[gen.T0]
    assert len(ia) > 2
    windows[gen.T0] = (ia[1:], ib[1:])
    n = compare.join_numbers(ref, r, a, b, windows, 10_000)
    d = np.hypot(a.x[ia[0]] - b.x[ib[0]], a.y[ia[0]] - b.y[ib[0]])
    assert n["join_missed"] == pytest.approx(0.05 - d)
    windows[gen.T0] = (np.r_[ia[1:], ia[1]], np.r_[ib[1:], ib[1]])
    assert compare.join_numbers(ref, r, a, b, windows, 10_000)[
        "join_bad_pairs"] == 1


def test_verdict():
    ok, shown = compare.verdict("knn", {"knn_dist_gap": 1e-6,
                                        "knn_bad_windows": 0,
                                        "no_answers": 0})
    assert ok and set(shown) == {"knn_dist_gap", "knn_bad_windows",
                                 "no_answers"}
    ok, _ = compare.verdict("knn", {"knn_dist_gap": 1.0,
                                    "knn_bad_windows": 0, "no_answers": 0})
    assert not ok
