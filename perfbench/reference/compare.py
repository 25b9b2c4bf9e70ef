"""The comparison that decides ``correct``: each query family's numbers,
taken between what the timed path emitted inside the window and the plain
reference (``oracle.py``), each held to its limit in ``limits.json``.

Every number reads "how far wrong", so a run is correct when each is at or
under its limit. Gaps are in degrees of the deployment's coordinates: how
far on the wrong side of the radius (join) an emitted or a missing
answer lies, or how far a reported kNN distance lies from the reference's.
Counts (``*_bad_*``, ``no_answers``) are exact, with the limit 0.
"""

from __future__ import annotations

import json
import os

import numpy as np

from reference.oracle import Reference, knn_top

LIMITS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "limits.json")


def limits(family: str) -> dict:
    with open(LIMITS) as f:
        return json.load(f)[family]


def verdict(family: str, numbers: dict):
    """-> (correct, {name: {"value", "limit"}})."""
    lim = limits(family)
    shown = {k: {"value": float(numbers[k]), "limit": lim[k]} for k in lim}
    return all(v["value"] <= v["limit"] for v in shown.values()), shown


def _gap(values) -> float:
    return float(np.max(values, initial=0.0))


def _xy(stream, idx):
    """Float64 coordinates of records ``idx`` (a slice or an index array),
    without converting the whole stream."""
    return stream.xi[idx] / 1e7, stream.yi[idx] / 1e7


def knn_numbers(ref: Reference, q: dict, stream, windows: dict,
                window_ms: int, slide_ms: int, fleet: int) -> dict:
    """Window kNN. ``windows``: {window start ms: [(id number, distance)]}
    for every window emitted in the measured window. A window's per-object
    least distances are the least of its slides', each slide's taken once
    (sliding windows share all their slides but one)."""
    if window_ms % slide_ms:
        raise ValueError("the window must be whole slides")
    gap, bad = 0.0, 0
    ts = stream.ts
    slides: dict = {}

    def slide_best(start):
        if start not in slides:
            lo, hi = np.searchsorted(ts, [start, start + slide_ms])
            sl = slice(int(lo), int(hi))
            slides[start] = ref.knn_best(stream.oid[sl], *_xy(stream, sl),
                                         q["point"][0], q["point"][1],
                                         q["radius"], fleet)
        return slides[start]

    for start, got in sorted(windows.items()):
        best = np.minimum.reduce([slide_best(s) for s in
                                  range(start, start + window_ms, slide_ms)])
        ids, dists, best = knn_top(best, q["k"])
        gid = np.array([g[0] for g in got], np.int64)
        gd = np.array([g[1] for g in got], np.float64)
        if (len(got) != len(ids) or len(np.unique(gid)) != len(gid)
                or np.any((gid < 0) | (gid >= fleet))):
            bad += 1
            continue
        gap = max(gap, _gap(np.abs(np.sort(gd) - dists)),
                  _gap(np.abs(gd - best[gid])))
    return {"knn_dist_gap": gap, "knn_bad_windows": bad,
            "no_answers": int(not windows)}


def join_numbers(ref: Reference, q: dict, s1, s2, windows: dict,
                 window_ms: int) -> dict:
    """Window join. ``windows``: {window start ms: (ia, ib)} with the
    indexes of the events each emitted pair reports (-1 where a record
    matches no event) for every window emitted in the measured window."""
    r = q["radius"]
    excess = missed = 0.0
    bad = 0
    for start, (ia, ib) in windows.items():
        a0, a1 = np.searchsorted(s1.ts, [start, start + window_ms])
        b0, b1 = np.searchsorted(s2.ts, [start, start + window_ms])
        known = ((ia >= a0) & (ia < a1) & (ib >= b0) & (ib < b1))
        bad += int(np.sum(~known))
        ia, ib = ia[known] - a0, ib[known] - b0
        got = np.unique(ia * (b1 - b0) + ib)
        bad += len(ia) - len(got)
        ax, ay = _xy(s1, slice(a0, a1))
        bx, by = _xy(s2, slice(b0, b1))
        wa, wb, _ = ref.join_pairs(ax, ay, bx, by, r)
        want = np.unique(wa * (b1 - b0) + wb)
        extra = np.setdiff1d(got, want, assume_unique=True)
        lack = np.setdiff1d(want, got, assume_unique=True)
        n = b1 - b0
        de = np.hypot(ax[extra // n] - bx[extra % n], ay[extra // n] - by[extra % n])
        dl = np.hypot(ax[lack // n] - bx[lack % n], ay[lack // n] - by[lack % n])
        excess = max(excess, _gap(de - r))
        missed = max(missed, _gap(r - dl))
    return {"join_excess": excess, "join_missed": missed,
            "join_bad_pairs": bad, "no_answers": int(not windows)}
