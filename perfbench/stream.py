"""The benchmark's one traffic generator, driven by a traffic file.

The stream is T-Drive-shaped, as ``chip_smoke.py`` (PR 21) made it, and
copied here so that later PRs cannot move the yardstick: positions over the
deployment's grid box with ``hot_share`` of them inside a two-cell hot
cluster, object ids cycling round-robin through the fleet, CSV records
``<prefix><id>,<ts ms>,<x>,<y>`` with coordinates in whole 1e-7 degrees.
Everything comes from ``--seed``; the seed moves positions only, never the
number of records, their times or their order.

Records are rendered vectorised into fixed-width rows (44 bytes), so that
rendering millions of them is set-up of a second or two, not a Python loop:
ids are zero-padded to five digits and times to thirteen.

One arrival kind, the traffic file's ``arrivals``: ``drain``. The whole
backlog is produced before the window opens; event time advances at the
configuration's ``stream_rate_hz``, and the window measures how fast the
served path drains it.
"""

from __future__ import annotations

import dataclasses
import gc

import numpy as np

COORD_SCALE = 10**7          # coordinates are written in whole 1e-7 deg
T0 = 1_700_000_000_000       # drain streams start here (a multiple of 5 s)
ROW = 44                     # bytes per rendered row, newline included
# column of each field in a row: p ddddd , ts(13) , xxx.xxxxxxx , yy.yyyyyyy \n
_ID, _TS, _X, _Y = 1, 7, 21, 33


class Grid:
    """The deployment's uniform grid (``UniformGrid`` with
    ``num_grid_partitions``: square cells of width (max_x - min_x) / n)."""

    def __init__(self, bbox, cells: int):
        self.min_x, self.min_y, self.max_x, self.max_y = map(float, bbox)
        self.n = int(cells)
        self.cell_length = (self.max_x - self.min_x) / self.n


def clustered_xy(grid: Grid, n: int, hot_share: float, seed: int):
    """Copied from ``streams.synthetic.clustered_xy``: ``hot_share`` of the
    points uniform in a box two cells wide around the bbox middle (nudged a
    third of a cell off the cell boundaries), the rest uniform over the
    bbox."""
    rng = np.random.default_rng(seed)
    span = 2.0 * grid.cell_length
    hx, hy = hot_center(grid)
    hot = rng.uniform(size=n) < hot_share
    x = rng.uniform(grid.min_x, grid.max_x, n)
    y = rng.uniform(grid.min_y, grid.max_y, n)
    x[hot] = hx + rng.uniform(-span / 2, span / 2, int(hot.sum()))
    y[hot] = hy + rng.uniform(-span / 2, span / 2, int(hot.sum()))
    x = np.clip(x, grid.min_x, np.nextafter(grid.max_x, -np.inf))
    y = np.clip(y, grid.min_y, np.nextafter(grid.max_y, -np.inf))
    return x, y


def hot_center(grid: Grid):
    return ((grid.min_x + grid.max_x) / 2 + grid.cell_length / 3,
            (grid.min_y + grid.max_y) / 2 + grid.cell_length / 3)


@dataclasses.dataclass
class Stream:
    """One generated stream. ``xi``/``yi`` are coordinates in 1e-7 deg (the
    exact values the written decimals denote), ``oid`` the id numbers,
    ``ts`` the event times in ms."""
    topic: str
    prefix: str
    xi: np.ndarray
    yi: np.ndarray
    oid: np.ndarray
    ts: np.ndarray | None = None

    def __len__(self) -> int:
        return len(self.xi)

    @property
    def x(self) -> np.ndarray:
        return self.xi / COORD_SCALE

    @property
    def y(self) -> np.ndarray:
        return self.yi / COORD_SCALE

    def rows(self, lo: int, hi: int) -> np.ndarray:
        """Rows ``lo..hi`` rendered as a (hi - lo, ROW) uint8 array (built
        column by column, then transposed once)."""
        cols = np.empty((ROW, hi - lo), np.uint8)
        cols[0] = ord(self.prefix)
        cols[[6, 20, 32]] = ord(",")
        cols[[24, 35]] = ord(".")
        cols[ROW - 1] = ord("\n")
        _digits(cols, _ID, self.oid[lo:hi], 5)
        _digits(cols, _TS, self.ts[lo:hi], 13)
        xi, yi = self.xi[lo:hi], self.yi[lo:hi]
        _digits(cols, _X, xi // COORD_SCALE, 3)
        _digits(cols, _X + 4, xi % COORD_SCALE, 7)
        _digits(cols, _Y, yi // COORD_SCALE, 2)
        _digits(cols, _Y + 3, yi % COORD_SCALE, 7)
        return np.ascontiguousarray(cols.T)

    def strings(self, lo: int, hi: int) -> list:
        return self.rows(lo, hi).tobytes().decode("ascii").split("\n")[:-1]

    def keys(self, bbox) -> np.ndarray:
        """One int64 per record, unique per (id, x, y): how an emitted
        record is traced back to the event it reports."""
        return record_keys(self.oid, self.xi, self.yi, bbox)


def record_keys(oid, xi, yi, bbox) -> np.ndarray:
    """14 + 25 + 24 bits: the id, and x and y in 1e-7 deg from the grid
    box's corner (so the box may be 3.3 deg wide and 1.6 deg high)."""
    x0, y0 = round(bbox[0] * COORD_SCALE), round(bbox[1] * COORD_SCALE)
    dx = np.asarray(xi, np.int64) - x0
    dy = np.asarray(yi, np.int64) - y0
    return (np.asarray(oid, np.int64) << 49) | (dx << 24) | dy


def _digits(cols, row: int, values, width: int) -> None:
    """Write ``values`` as zero-padded decimals of ``width`` digits into
    rows ``row..row+width-1`` of the column-major buffer."""
    v = np.asarray(values, np.int64)
    for j in range(row + width - 1, row - 1, -1):
        cols[j] = 48 + v % 10
        v = v // 10


def make_stream(conf: dict, n: int, seed: int, topic: str, prefix: str,
                fleet: int, hot_share: float) -> Stream:
    grid = Grid(conf["grid_bbox"], conf["num_grid_cells"])
    if (grid.max_x - grid.min_x >= (1 << 25) / COORD_SCALE
            or grid.max_y - grid.min_y >= (1 << 24) / COORD_SCALE
            or fleet >= 1 << 14):
        raise ValueError("grid box or fleet too large for record_keys")
    x, y = clustered_xy(grid, n, hot_share, seed)
    return Stream(topic, prefix,
                  np.rint(x * COORD_SCALE).astype(np.int64),
                  np.rint(y * COORD_SCALE).astype(np.int64),
                  np.arange(n, dtype=np.int64) % fleet)


# ------------------------------------------------------------------ drain


def drain_streams(conf: dict, traffic: dict, seconds: float, seed: int):
    """The drain backlog: the event time the warm-up drains (the first
    window and ``warmup_windows`` - 1 slides, up to the event that fires
    the last of its windows) and one slide more, plus ``max_rate_hz`` x
    ``seconds`` for the window, so that the window never reaches the
    backlog's end. -> [Stream].

    ``warmup_hot_queries`` gives, for each slide of the warm-up, how many
    join-side points lie in the hot cluster (see ``_place_hot``)."""
    rate = conf["stream_rate_hz"]
    win_ms, slide_ms = conf["window_s"] * 1000, conf["slide_s"] * 1000
    warm = rate * (win_ms + traffic["warmup_windows"] * slide_ms) // 1000
    n1 = int(warm + traffic["max_rate_hz"] * seconds)
    span_ms = n1 * 1000 // rate + win_ms
    s1 = make_stream(conf, n1, seed, conf["topic1"], "t", conf["fleet_size"],
                     conf["hot_share"])
    s1.ts = T0 + np.arange(n1, dtype=np.int64) * 1000 // rate
    streams = [s1]
    side = conf.get("join_side")
    if side:
        per = side["points_per_window"]
        n2 = int(per * span_ms // win_ms)
        s2 = make_stream(conf, n2, seed + 1, conf["topic2"], "q", per,
                         side["hot_share"])
        s2.ts = T0 + np.arange(n2, dtype=np.int64) * win_ms // per
        spec = traffic.get("warmup_hot_queries")
        if spec:
            _place_hot(s2, conf, spec, slide_ms, seed)
        streams.append(s2)
    return streams


def _place_hot(s2: Stream, conf: dict, spec, slide_ms: int, seed: int):
    """The join compacts the taxi points that have partners into a padded
    power-of-two bucket; each query point in the hot cluster adds some
    12k of them, so the bucket a window needs depends on how many land
    there. In the warm-up's slides exactly ``spec[k]`` query points lie in
    or near the hot cluster (others there are moved 0.5 deg east), so the
    warm-up compiles every bucket the window can meet (windows of 0, 1
    and 4 hot points: 32k, 64k and 128k; with ``[0, 0, 1, 3]`` the three
    full windows of the warm-up hold 0, 1 and 4)."""
    grid = Grid(conf["grid_bbox"], conf["num_grid_cells"])
    hx, hy = hot_center(grid)
    half = grid.cell_length                 # the cluster box's half-span
    near = half + 0.012                     # and the join's reach beyond it
    rng = np.random.default_rng(seed + 2)
    for k, count in enumerate(spec):
        lo, hi = np.searchsorted(s2.ts, [T0 + k * slide_ms,
                                         T0 + (k + 1) * slide_ms])
        x, y = s2.xi[lo:hi] / COORD_SCALE, s2.yi[lo:hi] / COORD_SCALE
        hot = (np.abs(x - hx) <= near) & (np.abs(y - hy) <= near)
        s2.xi[lo:hi][hot] += COORD_SCALE // 2
        for v, c in ((s2.xi, hx), (s2.yi, hy)):
            v[lo:lo + count] = np.rint(
                (c + rng.uniform(-half, half, count)) * COORD_SCALE)


def produce_backlog(broker, streams, chunk: int = 1 << 20) -> None:
    """Produce every stream to its topic. The backlog is millions of
    objects that a deployment's broker would hold in another process: the
    collector is off while they are made, and they are frozen out of its
    reach afterwards, so that no collection in the window walks them."""
    gc.disable()
    try:
        for s in streams:
            for lo in range(0, len(s), chunk):
                broker.produce_many(s.topic,
                                    s.strings(lo, min(len(s), lo + chunk)))
    finally:
        gc.freeze()
        gc.enable()
