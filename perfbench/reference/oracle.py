"""Plain NumPy reference of the queries the cells run, written from
GeoFlink's semantics (copied in substance from ``chip_smoke.py`` and
``tests/oracles.py``, PR 21) and importing nothing of the program.

Coordinates are float64 from the exact decimals the generator wrote.
``Reference(conf, precision="bf16")`` is the control: the same reference
with every coordinate, centred on the grid box's middle, rounded to
bfloat16 before distances are taken (float32 arithmetic after that) -- the
step below the float32 coordinates the configuration states.
"""

from __future__ import annotations

import math

import numpy as np


def round_bf16(v) -> np.ndarray:
    """float32 -> nearest bfloat16 (ties to even), returned as float32."""
    u = np.asarray(v, np.float32).view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32)


class Reference:
    def __init__(self, conf: dict, precision: str = "f64"):
        if precision not in ("f64", "bf16"):
            raise ValueError(precision)
        self.precision = precision
        self.min_x, self.min_y, self.max_x, self.max_y = map(
            float, conf["grid_bbox"])
        self.n = int(conf["num_grid_cells"])
        self.cl = (self.max_x - self.min_x) / self.n
        self.cx0 = (self.min_x + self.max_x) / 2
        self.cy0 = (self.min_y + self.max_y) / 2

    # ------------------------------------------------------------ grid

    def cells(self, x, y):
        """(cx, cy, valid): the reference's floor-division cell of each
        point; points outside the box have no cell."""
        cx = np.floor((x - self.min_x) / self.cl).astype(np.int64)
        cy = np.floor((y - self.min_y) / self.cl).astype(np.int64)
        valid = (cx >= 0) & (cy >= 0) & (cx < self.n) & (cy < self.n)
        return cx, cy, valid

    def layers(self, radius: float):
        """(guaranteed, candidate) Chebyshev layer counts
        (``UniformGrid.java:427-444``)."""
        return (math.floor(radius / (self.cl * math.sqrt(2.0)) - 1),
                math.ceil(radius / self.cl))

    def cheb(self, x, y, qx: float, qy: float):
        """Chebyshev cell distance of each point's cell from the query
        point's cell, and the points' validity."""
        cx, cy, valid = self.cells(x, y)
        (qcx,), (qcy,), _ = self.cells(np.array([qx]), np.array([qy]))
        return np.maximum(np.abs(cx - qcx), np.abs(cy - qcy)), valid

    # -------------------------------------------------------- distance

    def dist(self, x, y, qx, qy) -> np.ndarray:
        if self.precision == "f64":
            return np.hypot(np.asarray(x) - qx, np.asarray(y) - qy)
        ax, ay = round_bf16(np.asarray(x) - self.cx0), round_bf16(
            np.asarray(y) - self.cy0)
        bx, by = round_bf16(np.asarray(qx) - self.cx0), round_bf16(
            np.asarray(qy) - self.cy0)
        return np.hypot(ax - bx, ay - by).astype(np.float64)

    # ---------------------------------------------------------- queries

    def knn_best(self, oid, x, y, qx, qy, radius, fleet) -> np.ndarray:
        """Each object's least distance over the points of the
        candidate-layer cells (inf where it has none)."""
        cheb, valid = self.cheb(x, y, qx, qy)
        elig = valid & (cheb <= self.layers(radius)[1])
        best = np.full(fleet, np.inf)
        np.minimum.at(best, oid[elig], self.dist(x[elig], y[elig], qx, qy))
        return best

    def knn(self, oid, x, y, qx, qy, radius, k, fleet):
        """Window kNN (option 51): the k objects with the least
        ``knn_best``, by distance. -> (ids, distances, per-object least
        distances)."""
        return knn_top(self.knn_best(oid, x, y, qx, qy, radius, fleet), k)

    def join_pairs(self, ax, ay, bx, by, radius):
        """Point-point window join (option 101): every (a, b) with both
        points inside the grid box and distance <= r. -> (ia, ib, d)."""
        va, vb = self.cells(ax, ay)[2], self.cells(bx, by)[2]
        ia_all, ib_all = _near_pairs(ax, ay, bx, by, radius + 1e-3)
        keep = va[ia_all] & vb[ib_all]
        ia_all, ib_all = ia_all[keep], ib_all[keep]
        d = self.dist(ax[ia_all], ay[ia_all], bx[ib_all], by[ib_all])
        m = d <= radius
        return ia_all[m], ib_all[m], d[m]


def knn_top(best: np.ndarray, k: int):
    """-> (ids, distances, best): the k objects with the least ``best``."""
    order = np.argsort(best, kind="stable")[:k]
    order = order[np.isfinite(best[order])]
    return order, best[order], best


def _near_pairs(ax, ay, bx, by, reach: float):
    """All (i, j) with |ax_i - bx_j| <= reach and |ay_i - by_j| <= reach,
    through a bucket grid of side ``reach`` over the a points."""
    kx = np.floor(ax / reach).astype(np.int64)
    ky = np.floor(ay / reach).astype(np.int64)
    key = kx * (1 << 32) + ky
    order = np.argsort(key, kind="stable")
    skey = key[order]
    bkx = np.floor(bx / reach).astype(np.int64)
    bky = np.floor(by / reach).astype(np.int64)
    out_i, out_j = [], []
    for ox in (-1, 0, 1):
        for oy in (-1, 0, 1):
            want = (bkx + ox) * (1 << 32) + (bky + oy)
            lo = np.searchsorted(skey, want, "left")
            hi = np.searchsorted(skey, want, "right")
            cnt = hi - lo
            j = np.repeat(np.arange(len(bx)), cnt)
            start = np.repeat(lo - np.cumsum(cnt) + cnt, cnt)
            i = order[start + np.arange(int(cnt.sum()))]
            out_i.append(i)
            out_j.append(j)
    i, j = np.concatenate(out_i), np.concatenate(out_j)
    close = (np.abs(ax[i] - bx[j]) <= reach) & (np.abs(ay[i] - by[j]) <= reach)
    return i[close], j[close]
