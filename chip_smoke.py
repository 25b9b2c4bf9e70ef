"""Chip smoke: the served driver path on one TPU chip, at the window size of
a GeoFlink T-Drive deployment, checked against an independent NumPy oracle.

The stream is shaped like the reference's own deployment: the Beijing grid
``gridBBox [115.5, 39.6, 117.6, 41.1]`` with ``numGridCells: 100``
(``conf/spatialflink-conf.yml``), 10,357 taxi object ids (T-Drive's fleet),
clustered positions (``streams.synthetic.clustered_xy``) and an event rate
that puts about 1,000,000 points in each 10 s / 5 s sliding window
(BASELINE config 2's window). Everything comes from ``--seed``.

Each phase writes the stream as CSV and runs it through the user's entry
point, ``spatialflink_tpu.driver.main([...])``, in this process:

- ``range``   option 1,   point-point range, r = 0.5;
- ``knn``     option 51,  point-point kNN, k = 50 (``approx_verified`` on
  the chip);
- ``polygon`` option 6,   point-polygon range against the config's polygon
  redrawn as one 600-vertex ring (the Pallas kernel's chunked-edge grid);
- ``join``    option 101, point-point join against a second stream of 1,024
  points per window (BASELINE config 3's shape).

Two whole windows of every phase are compared with a NumPy oracle that
re-derives the reference's semantics (tests/oracles.py): exact id sets for
range and join, kNN ids and distances within float32 tolerance. Points whose
oracle distance lies within ``BAND`` of the radius may go either way on the
device (float32 coordinates) and are left out of the exact comparison.

``--chips 4`` runs only the mesh path (``--devices 4``) for options 1 and 51
and compares every window with the same stream run on one device.

Exit status is non-zero, and the last line is not printed, when a phase
fails, an oracle disagrees, the Pallas mode is not ``tpu`` or JAX finds no
TPU. ``--small`` with an explicit ``JAX_PLATFORMS=cpu`` is the CPU rehearsal:
the same phases at a small scale, with the device line saying ``cpu``.

The compile cache follows the driver's rule: ``JAX_COMPILATION_CACHE_DIR``
when set, else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import re
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

BBOX = (115.5, 39.6, 117.6, 41.1)       # gridBBox: min x, min y, max x, max y
GRID_CELLS = 100
N_TAXIS = 10_357                        # T-Drive's fleet
HOT_SHARE = 0.2
T0 = 1_700_000_000_000                  # a multiple of the 5 s slide
WINDOW_MS, SLIDE_MS = 10_000, 5_000
QUERY_POINT = (116.5, 40.5)
CONFIG_POLYGON = (116.2, 40.2, 117.0, 40.9)   # the config's query rectangle
RING_VERTICES = 600
K = 50
# float32 coordinates (~7.6e-6 deg quantum at 116 deg) move a distance by up
# to ~1.6e-5; points this close to the radius are not compared exactly
BAND = 3e-5
COORD_SCALE = 10**7                     # coordinates are written in 1e-7 deg


@dataclasses.dataclass(frozen=True)
class Scale:
    rate_hz: int            # stream-1 events per second
    seconds: int            # stream length
    join_per_window: int    # stream-2 points per 10 s window


FULL = Scale(rate_hz=100_000, seconds=45, join_per_window=1024)
SMALL = Scale(rate_hz=4_000, seconds=45, join_per_window=1024)


@dataclasses.dataclass(frozen=True)
class Phase:
    name: str
    option: int
    radius: float
    join: bool = False


PHASES = (
    Phase("range", 1, 0.5),
    Phase("knn", 51, 0.5),
    Phase("polygon", 6, 0.01),
    Phase("join", 101, 0.005, join=True),
)
MESH_PHASES = (Phase("mesh_range", 1, 0.5), Phase("mesh_knn", 51, 0.5))


class SmokeFailure(Exception):
    """A phase ran but its result is wrong."""


# ------------------------------------------------------------------ input


@dataclasses.dataclass
class Stream:
    """One generated stream: integer coordinates in 1e-7 deg, event times in
    ms (sorted), object id numbers, and the CSV file holding them."""
    prefix: str
    xi: np.ndarray
    yi: np.ndarray
    ts: np.ndarray
    oid: np.ndarray
    path: str

    @property
    def x(self) -> np.ndarray:
        # the exact value strtod gives for the written decimal
        return self.xi / COORD_SCALE

    @property
    def y(self) -> np.ndarray:
        return self.yi / COORD_SCALE

    def window(self, start: int) -> slice:
        lo, hi = np.searchsorted(self.ts, [start, start + WINDOW_MS])
        return slice(int(lo), int(hi))


def _grid():
    from spatialflink_tpu.index import UniformGrid

    return UniformGrid(BBOX[0], BBOX[2], BBOX[1], BBOX[3],
                       num_grid_partitions=GRID_CELLS)


def _write_csv(path: str, prefix: str, oid, ts, xi, yi) -> None:
    def coord(v):
        return f"{v // COORD_SCALE}.{v % COORD_SCALE:07d}"

    with open(path, "w") as f:
        step = 200_000
        for lo in range(0, len(ts), step):
            sl = slice(lo, lo + step)
            f.write("\n".join(
                f"{prefix}{o},{t},{coord(a)},{coord(b)}"
                for o, t, a, b in zip(oid[sl].tolist(), ts[sl].tolist(),
                                      xi[sl].tolist(), yi[sl].tolist())))
            f.write("\n")


def make_streams(scale: Scale, seed: int, workdir: str):
    """-> (taxi stream, join query stream), both written as CSV."""
    from spatialflink_tpu.streams.synthetic import clustered_xy

    grid = _grid()
    n1 = scale.rate_hz * scale.seconds
    x, y = clustered_xy(grid, n1, HOT_SHARE, seed=seed)
    i = np.arange(n1, dtype=np.int64)
    # round-robin ids: (id, ms) is unique, since 10,357 > events per ms
    s1 = Stream("t", np.rint(x * COORD_SCALE).astype(np.int64),
                np.rint(y * COORD_SCALE).astype(np.int64),
                T0 + i * 1000 // scale.rate_hz, i % N_TAXIS,
                os.path.join(workdir, "taxis.csv"))
    n2 = scale.join_per_window * scale.seconds * 1000 // WINDOW_MS
    x2, y2 = clustered_xy(grid, n2, 0.0, seed=seed + 1)
    j = np.arange(n2, dtype=np.int64)
    s2 = Stream("q", np.rint(x2 * COORD_SCALE).astype(np.int64),
                np.rint(y2 * COORD_SCALE).astype(np.int64),
                T0 + j * WINDOW_MS // scale.join_per_window,
                j % scale.join_per_window,
                os.path.join(workdir, "queries.csv"))
    for s in (s1, s2):
        _write_csv(s.path, s.prefix, s.oid, s.ts, s.xi, s.yi)
    return s1, s2


def query_ring() -> list:
    """The config's query rectangle redrawn as one star-shaped ring of
    ``RING_VERTICES`` vertices (radius modulated by 5% around the
    rectangle's outline), closed. More than 512 edges, so the Pallas kernel
    streams the edges through SMEM in chunks."""
    x0, y0, x1, y1 = CONFIG_POLYGON
    cx, cy = (x0 + x1) / 2, (y0 + y1) / 2
    hw, hh = (x1 - x0) / 2, (y1 - y0) / 2
    ring = []
    for v in range(RING_VERTICES):
        th = 2 * math.pi * v / RING_VERTICES
        c, s = math.cos(th), math.sin(th)
        to_edge = min(hw / abs(c) if c else math.inf,
                      hh / abs(s) if s else math.inf)
        r = to_edge * (1 + 0.05 * math.sin(24 * th))
        ring.append([round(cx + r * c, 7), round(cy + r * s, 7)])
    return ring + [ring[0]]


def write_config(path: str, radius: float) -> None:
    import yaml

    with open(os.path.join(ROOT, "conf", "spatialflink-conf.yml")) as f:
        conf = yaml.safe_load(f)
    for key in ("inputStream1", "inputStream2"):
        conf[key].update(format="CSV", dateFormat=None,
                         csvTsvSchemaAttr=[0, 1, 2, 3],
                         gridBBox=list(BBOX), numGridCells=GRID_CELLS)
    conf["query"].update(radius=radius, k=K, queryPoints=[list(QUERY_POINT)],
                         queryPolygons=[query_ring()])
    conf["window"] = {"type": "TIME", "interval": WINDOW_MS // 1000,
                      "step": SLIDE_MS // 1000}
    with open(path, "w") as f:
        yaml.safe_dump(conf, f)


# ---------------------------------------------------------- driver runs


class CompileCacheEvents:
    """Counts JAX's persistent compile-cache hits and misses."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _compile_totals():
    """({kernel: compiles}, compile seconds) from the compile registry."""
    from spatialflink_tpu.utils import deviceplane

    entries = list(deviceplane.registry().entries.values())
    return ({e.name: e.compiles for e in entries},
            sum(e.trace_ms + e.backend_compile_ms for e in entries) / 1e3)


_WINDOW_LINE = re.compile(r"'window': \[(\d+), (\d+)\], 'count': (\d+)")


def run_driver(phase: Phase, streams, workdir: str, cache: CompileCacheEvents,
               devices: int = 0) -> dict:
    """One ``driver.main`` run. -> the phase record, with the emitted
    windows as [(start, end, count)] and the record file they were written
    to, in window order."""
    from spatialflink_tpu import driver
    from spatialflink_tpu.ops.pallas_kernels import pallas_mode

    tag = f"{phase.name}-d{devices}"
    conf = os.path.join(workdir, f"{tag}.yml")
    write_config(conf, phase.radius)
    out = os.path.join(workdir, f"{tag}.out")
    argv = ["--config", conf, "--option", str(phase.option),
            "--input1", streams[0].path, "--output", out,
            "--output-format", "CSV"]
    if phase.join:
        argv += ["--input2", streams[1].path]
    if devices:
        argv += ["--devices", str(devices)]
    c0, s0 = _compile_totals()
    h0, m0 = cache.hits, cache.misses
    summary = os.path.join(workdir, f"{tag}.windows")
    t0 = time.perf_counter()
    with open(summary, "w") as f, contextlib.redirect_stdout(f):
        rc = driver.main(argv)
    wall = time.perf_counter() - t0
    if rc != 0:
        raise SmokeFailure(f"{tag}: driver exited {rc}")
    with open(summary) as f:
        windows = [tuple(int(g) for g in m.groups())
                   for m in map(_WINDOW_LINE.search, f) if m]
    c1, s1 = _compile_totals()
    spans = [streams[0].window(s) for s, _e, _n in windows]
    return {
        "phase": tag, "option": phase.option, "radius": phase.radius,
        "devices": devices or 1, "wall_s": wall, "windows": len(windows),
        "window_points_max": max((w.stop - w.start for w in spans),
                                 default=0),
        "records": sum(n for _s, _e, n in windows),
        "compiles": sum(c1.values()) - sum(c0.values()),
        "compiled": sorted(k for k, v in c1.items() if v > c0.get(k, 0)),
        "compile_s": s1 - s0,
        "cache_hits": cache.hits - h0, "cache_misses": cache.misses - m0,
        "pallas_mode": pallas_mode(),
        "_windows": windows, "_out": out,
    }


def read_windows(rec: dict, starts) -> dict:
    """{window start: [record lines]} for the windows in ``starts``."""
    want, got = set(starts), {}
    with open(rec["_out"]) as f:
        for start, _end, n in rec["_windows"]:
            lines = [f.readline().rstrip("\n") for _ in range(n)]
            if start in want:
                got[start] = lines
    missing = want - set(got)
    if missing:
        raise SmokeFailure(f"{rec['phase']}: windows {sorted(missing)} "
                           "were not emitted")
    return got


# ---------------------------------------------------------------- oracle


def _point_key(line: str):
    """(id, x, y) in integer 1e-7 deg from a CSV point record."""
    oid, _ts, x, y = line.split(",")
    return (oid, round(float(x) * COORD_SCALE), round(float(y) * COORD_SCALE))


def _keys(s: Stream, idx) -> list:
    return [(f"{s.prefix}{o}", a, b)
            for o, a, b in zip(s.oid[idx].tolist(), s.xi[idx].tolist(),
                               s.yi[idx].tolist())]


def _cells(x, y):
    """(cx, cy, valid): the reference's floor-division cell assignment."""
    grid = _grid()
    cx = np.floor((x - grid.min_x) / grid.cell_length).astype(np.int64)
    cy = np.floor((y - grid.min_y) / grid.cell_length).astype(np.int64)
    valid = (cx >= 0) & (cy >= 0) & (cx < grid.n) & (cy < grid.n)
    return cx, cy, valid


def _layers(radius: float):
    """(guaranteed, candidate) Chebyshev layer counts (UniformGrid.java)."""
    cl = _grid().cell_length
    return (math.floor(radius / (cl * math.sqrt(2.0)) - 1),
            math.ceil(radius / cl))


def _compare_sets(tag: str, got, want, unsure) -> dict:
    got, want, unsure = set(got), set(want), set(unsure)
    missing = want - got - unsure
    extra = got - want - unsure
    if missing or extra:
        raise SmokeFailure(
            f"{tag}: {len(missing)} missing, {len(extra)} extra "
            f"(e.g. {sorted(missing)[:2]} / {sorted(extra)[:2]})")
    return {"records": len(got), "oracle": len(want), "band": len(unsure)}


def _range_mask(s: Stream, sl: slice, radius: float, dist_fn, q_cells):
    """Reference range semantics: guaranteed-cell points pass unchecked,
    candidate-cell points pass iff distance <= r. -> (pass, unsure)."""
    x, y = s.x[sl], s.y[sl]
    cx, cy, valid = _cells(x, y)
    (qx0, qy0), (qx1, qy1) = q_cells
    cheb = np.maximum(np.maximum(qx0 - cx, cx - qx1).clip(0),
                      np.maximum(qy0 - cy, cy - qy1).clip(0))
    gn_l, cn_l = _layers(radius)
    in_gn = valid & (cheb <= gn_l)
    in_cn = valid & (cheb <= cn_l) & ~in_gn
    d = np.full(len(x), np.inf)
    d[in_cn] = dist_fn(x[in_cn], y[in_cn])
    return in_gn | (in_cn & (d <= radius)), in_cn & (np.abs(d - radius) <= BAND)


def check_range(rec, s: Stream, starts, dist_fn, q_cells) -> dict:
    got = read_windows(rec, starts)
    out = {}
    for start in starts:
        sl = s.window(start)
        ok, unsure = _range_mask(s, sl, rec["radius"], dist_fn, q_cells)
        idx = np.arange(sl.start, sl.stop)
        out[start] = _compare_sets(
            f"{rec['phase']} window {start}", map(_point_key, got[start]),
            _keys(s, idx[ok & ~unsure]), _keys(s, idx[unsure]))
    return out


def _point_dist(x, y):
    return np.hypot(x - QUERY_POINT[0], y - QUERY_POINT[1])


def _ring_dist(ring):
    """Vectorized JTS Point.distance(Polygon) for one ring: 0 inside
    (even-odd ray cast), else the least point-segment distance."""
    r = np.asarray(ring, np.float64)
    x1, y1, x2, y2 = r[:-1, 0], r[:-1, 1], r[1:, 0], r[1:, 1]

    def dist(px, py):
        inside = np.zeros(len(px), bool)
        best = np.full(len(px), np.inf)
        for lo in range(0, len(x1), 64):
            e = slice(lo, lo + 64)
            ax, ay, bx, by = (v[e][None, :] for v in (x1, y1, x2, y2))
            qx, qy = px[:, None], py[:, None]
            straddle = (ay > qy) != (by > qy)
            with np.errstate(divide="ignore", invalid="ignore"):
                x_at = ax + (qy - ay) / (by - ay) * (bx - ax)
            inside ^= (np.sum(straddle & (qx < x_at), axis=1) % 2).astype(bool)
            dx, dy = bx - ax, by - ay
            t = np.clip(((qx - ax) * dx + (qy - ay) * dy)
                        / (dx * dx + dy * dy), 0.0, 1.0)
            best = np.minimum(best, np.hypot(qx - (ax + t * dx),
                                             qy - (ay + t * dy)).min(axis=1))
        return np.where(inside, 0.0, best)

    return dist


def check_knn(rec, s: Stream, starts) -> dict:
    got = read_windows(rec, starts)
    _gn, cn_l = _layers(rec["radius"])
    (qcx,), (qcy,), _ = _cells(np.array([QUERY_POINT[0]]),
                               np.array([QUERY_POINT[1]]))
    out = {}
    for start in starts:
        sl = s.window(start)
        x, y, oid = s.x[sl], s.y[sl], s.oid[sl]
        cx, cy, valid = _cells(x, y)
        elig = valid & (np.maximum(abs(cx - qcx), abs(cy - qcy)) <= cn_l)
        best = np.full(N_TAXIS, np.inf)
        np.minimum.at(best, oid[elig], _point_dist(x[elig], y[elig]))
        order = np.argsort(best, kind="stable")[:K]
        want_d = best[order]
        rows = [json.loads(line) for line in got[start]]
        got_ids = [int(r[0][1:]) for r in rows]
        got_d = np.array([r[1] for r in rows])
        tag = f"{rec['phase']} window {start}"
        if len(rows) != K or not np.allclose(got_d, want_d, atol=BAND,
                                             rtol=0):
            raise SmokeFailure(f"{tag}: distances differ from the oracle "
                               f"(max |diff| "
                               f"{np.max(np.abs(got_d - want_d[:len(rows)]))})")
        # ids may swap only where the k-th distance ties within BAND
        unsure = {int(o) for o in np.nonzero(
            np.abs(best - want_d[-1]) <= BAND)[0]}
        out[start] = _compare_sets(tag, got_ids, order.tolist(), unsure)
        out[start]["max_dist_err"] = float(np.max(np.abs(got_d - want_d)))
    return out


def check_join(rec, s1: Stream, s2: Stream, starts) -> dict:
    got = read_windows(rec, starts)
    r = rec["radius"]
    out = {}
    for start in starts:
        a, b = s1.window(start), s2.window(start)
        ax, ay, bx, by = s1.x[a], s1.y[a], s2.x[b], s2.y[b]
        va, vb = _cells(ax, ay)[2], _cells(bx, by)[2]
        order = np.argsort(ax, kind="stable")
        sx = ax[order]
        want, unsure = [], []
        ka, kb = _keys(s1, np.arange(a.start, a.stop)), _keys(
            s2, np.arange(b.start, b.stop))
        for j in np.nonzero(vb)[0]:
            lo, hi = np.searchsorted(sx, [bx[j] - r - BAND, bx[j] + r + BAND])
            cand = order[lo:hi]
            cand = cand[va[cand]]
            d = np.hypot(ax[cand] - bx[j], ay[cand] - by[j])
            for i in cand[(d <= r) & (np.abs(d - r) > BAND)]:
                want.append((ka[i], kb[j]))
            for i in cand[np.abs(d - r) <= BAND]:
                unsure.append((ka[i], kb[j]))
        pairs = []
        for line in got[start]:
            p, q = (_point_key(v) for v in json.loads(line))
            pairs.append((p, q) if p[0].startswith(s1.prefix) else (q, p))
        out[start] = _compare_sets(f"{rec['phase']} window {start}", pairs,
                                   want, unsure)
    return out


def full_windows(rec: dict, scale: Scale) -> list:
    """Window starts of the emitted windows the stream fills completely."""
    end = T0 + scale.seconds * 1000
    return [s for s, e, _n in rec["_windows"] if s >= T0 and e <= end]


def check_phase(rec: dict, streams, scale: Scale) -> dict:
    """Oracle verdict for the first and last full windows of a phase."""
    full = full_windows(rec, scale)
    if len(full) < 4:
        raise SmokeFailure(f"{rec['phase']}: {len(full)} full windows "
                           "emitted, want at least 4")
    starts = [full[0], full[-1]]
    s1, s2 = streams
    opt = rec["option"]
    if opt == 1:
        cx, cy, _ = _cells(np.array([QUERY_POINT[0]]),
                           np.array([QUERY_POINT[1]]))
        return check_range(rec, s1, starts, _point_dist,
                           ((cx[0], cy[0]), (cx[0], cy[0])))
    if opt == 6:
        # a polygon's cells are those its bounding box overlaps
        ring = np.asarray(query_ring())
        cx, cy, _ = _cells(np.array([ring[:, 0].min(), ring[:, 0].max()]),
                           np.array([ring[:, 1].min(), ring[:, 1].max()]))
        return check_range(rec, s1, starts, _ring_dist(ring),
                           ((cx[0], cy[0]), (cx[1], cy[1])))
    if opt == 51:
        return check_knn(rec, s1, starts)
    if opt == 101:
        return check_join(rec, s1, s2, starts)
    raise ValueError(f"no oracle for option {opt}")


def check_same_windows(mesh: dict, single: dict) -> dict:
    """The mesh run's windows equal the one-device run's, record for
    record (order within a window aside)."""
    if [w[:2] for w in mesh["_windows"]] != [w[:2] for w in single["_windows"]]:
        raise SmokeFailure(f"{mesh['phase']}: emitted windows differ from "
                           "the one-device run")
    starts = [w[0] for w in single["_windows"]]
    a, b = read_windows(mesh, starts), read_windows(single, starts)
    for s in starts:
        if sorted(a[s]) != sorted(b[s]):
            raise SmokeFailure(f"{mesh['phase']}: window {s} differs from "
                               "the one-device run")
    return {"windows_equal": len(starts),
            "records": sum(len(v) for v in a.values())}


# ------------------------------------------------------------------ main


def _public(rec: dict) -> dict:
    return {k: v for k, v in rec.items() if not k.startswith("_")}


def smoke(scale: Scale, seed: int, workdir: str, chips: int) -> list:
    """Run every phase; -> their records. Raises on the first failure."""
    import jax

    from spatialflink_tpu import native

    cache = CompileCacheEvents()
    t0 = time.perf_counter()
    streams = make_streams(scale, seed, workdir)
    print(json.dumps({"phase": "input", "events": len(streams[0].ts),
                      "join_events": len(streams[1].ts),
                      "seconds": time.perf_counter() - t0}), flush=True)
    records = []
    if chips > 1:
        from spatialflink_tpu.parallel.mesh import make_mesh
        from spatialflink_tpu.utils.metrics import REGISTRY

        mesh_devices = set(make_mesh(chips).devices.flat)
        if len(mesh_devices) != chips:
            raise SmokeFailure(f"mesh of {chips} holds "
                               f"{len(mesh_devices)} distinct devices")
        degr = REGISTRY.counter("mesh-degradations").count
        for ph in MESH_PHASES:
            single = run_driver(ph, streams, workdir, cache)
            mesh = run_driver(ph, streams, workdir, cache, devices=chips)
            if REGISTRY.counter("mesh-degradations").count != degr:
                raise SmokeFailure(f"{ph.name}: the mesh degraded")
            mesh["vs_one_device"] = check_same_windows(mesh, single)
            for rec in (single, mesh):
                print(json.dumps(_public(rec)), flush=True)
            records += [single, mesh]
        return records
    on_tpu = jax.devices()[0].platform == "tpu"
    for ph in PHASES:
        rec = run_driver(ph, streams, workdir, cache)
        if on_tpu and ph.option == 6 and "_pip_pallas" not in rec["compiled"]:
            raise SmokeFailure("polygon: the Pallas kernel did not run")
        rec["native_ingest"] = native.available()
        rec["oracle"] = check_phase(rec, streams, scale)
        print(json.dumps(_public(rec)), flush=True)
        records.append(rec)
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the mesh path (options 1 and 51 with "
                         "--devices 4) against one device")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--small", action="store_true",
                    help="CPU rehearsal scale; with JAX_PLATFORMS=cpu the "
                         "script runs without a TPU")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from spatialflink_tpu import driver

    cache_dir, cache_error = driver.enable_compilation_cache()
    print(json.dumps({"phase": "compile_cache", "dir": cache_dir,
                      "error": cache_error}), flush=True)
    import jax

    from spatialflink_tpu.ops.knn import _resolve_auto
    from spatialflink_tpu.ops.pallas_kernels import pallas_mode
    from spatialflink_tpu.utils import deviceplane

    devs = jax.devices()
    platform = devs[0].platform
    cpu_rehearsal = (args.small and platform == "cpu"
                     and os.environ.get("JAX_PLATFORMS") == "cpu")
    if platform != "tpu" and not cpu_rehearsal:
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); a CPU "
              "rehearsal needs JAX_PLATFORMS=cpu and --small",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} "
              "device(s)", file=sys.stderr)
        return 2
    print(json.dumps({"phase": "backend",
                      "provenance": deviceplane.backend_provenance(),
                      "pallas_mode": pallas_mode(),
                      "knn_auto_1M": _resolve_auto(1 << 20)}), flush=True)
    if platform == "tpu" and pallas_mode() != "tpu":
        print(f"chip_smoke: Pallas mode is {pallas_mode()!r} on a TPU",
              file=sys.stderr)
        return 1
    scale = SMALL if args.small else FULL
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke-") as workdir:
            smoke(scale, args.seed, workdir, args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
