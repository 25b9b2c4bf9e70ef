"""Pure-NumPy float64 reference implementations used as correctness oracles.

These intentionally re-derive the reference's semantics independently of the
device kernels (no jax imports) — the rebuild's analogue of GeoFlink's naive
exhaustive-scan twins (SURVEY.md §4)."""

from __future__ import annotations

import numpy as np


def pp_dist(x1, y1, x2, y2):
    return np.hypot(np.asarray(x2) - x1, np.asarray(y2) - y1)


def point_segment_dist(px, py, x1, y1, x2, y2):
    px, py = float(px), float(py)
    cx, cy = x2 - x1, y2 - y1
    len_sq = cx * cx + cy * cy
    if len_sq == 0:
        return np.hypot(px - x1, py - y1)
    t = max(0.0, min(1.0, ((px - x1) * cx + (py - y1) * cy) / len_sq))
    return np.hypot(px - (x1 + t * cx), py - (y1 + t * cy))


def point_bbox_dist(px, py, bx1, by1, bx2, by2):
    dx = max(bx1 - px, px - bx2, 0.0)
    dy = max(by1 - py, py - by2, 0.0)
    return np.hypot(dx, dy)


def bbox_bbox_dist(a, b):
    dx = max(a[0] - b[2], b[0] - a[2], 0.0)
    dy = max(a[1] - b[3], b[1] - a[3], 0.0)
    return np.hypot(dx, dy)


def point_in_rings(px, py, rings) -> bool:
    """Even-odd rule over a list of rings (each a closed (k,2) array)."""
    inside = False
    for ring in rings:
        r = np.asarray(ring, np.float64)
        x1, y1 = r[:-1, 0], r[:-1, 1]
        x2, y2 = r[1:, 0], r[1:, 1]
        straddle = (y1 > py) != (y2 > py)
        with np.errstate(divide="ignore", invalid="ignore"):
            x_at = x1 + (py - y1) / (y2 - y1) * (x2 - x1)
        crossings = straddle & (px < x_at)
        inside ^= bool(np.sum(crossings) % 2)
    return inside


def point_rings_boundary_dist(px, py, rings) -> float:
    d = np.inf
    for ring in rings:
        r = np.asarray(ring, np.float64)
        for i in range(len(r) - 1):
            d = min(d, point_segment_dist(px, py, r[i, 0], r[i, 1], r[i + 1, 0], r[i + 1, 1]))
    return d


def point_polygon_dist(px, py, rings) -> float:
    """JTS Point.distance(Polygon): 0 inside the areal geometry."""
    if point_in_rings(px, py, rings):
        return 0.0
    return point_rings_boundary_dist(px, py, rings)


def _orient(ax, ay, bx, by, cx, cy):
    return (bx - ax) * (cy - ay) - (by - ay) * (cx - ax)


def segments_intersect(a, b) -> bool:
    d1 = _orient(b[0], b[1], b[2], b[3], a[0], a[1])
    d2 = _orient(b[0], b[1], b[2], b[3], a[2], a[3])
    d3 = _orient(a[0], a[1], a[2], a[3], b[0], b[1])
    d4 = _orient(a[0], a[1], a[2], a[3], b[2], b[3])
    return ((d1 > 0) != (d2 > 0)) and ((d3 > 0) != (d4 > 0))


def seg_seg_dist(a, b) -> float:
    if segments_intersect(a, b):
        return 0.0
    return min(
        point_segment_dist(a[0], a[1], b[0], b[1], b[2], b[3]),
        point_segment_dist(a[2], a[3], b[0], b[1], b[2], b[3]),
        point_segment_dist(b[0], b[1], a[0], a[1], a[2], a[3]),
        point_segment_dist(b[2], b[3], a[0], a[1], a[2], a[3]),
    )


def rings_to_segments(rings):
    segs = []
    for ring in rings:
        r = np.asarray(ring, np.float64)
        for i in range(len(r) - 1):
            segs.append((r[i, 0], r[i, 1], r[i + 1, 0], r[i + 1, 1]))
    return segs


def polygon_polygon_dist(rings_a, rings_b) -> float:
    """JTS Polygon.distance(Polygon): 0 if they intersect/contain."""
    a0 = np.asarray(rings_a[0], np.float64)[0]
    b0 = np.asarray(rings_b[0], np.float64)[0]
    if point_in_rings(a0[0], a0[1], rings_b) or point_in_rings(b0[0], b0[1], rings_a):
        return 0.0
    d = np.inf
    for sa in rings_to_segments(rings_a):
        for sb in rings_to_segments(rings_b):
            d = min(d, seg_seg_dist(sa, sb))
    return d


def scalar_decode_stream(records, cfg, grid, geometry="Point"):
    """THE SEED SCALAR DECODER, kept verbatim as a test-only oracle: raw
    lines/dicts -> spatial objects via one ``parse_spatial`` call per
    record, off-type records dropped — the per-record loop
    ``driver.decode_stream`` replaced with the chunk-vectorized
    ``decode_chunks`` seam. The batched path must emit byte-identical
    window contents when driven by either decoder."""
    from spatialflink_tpu.models import SpatialObject
    from spatialflink_tpu.streams.formats import parse_spatial

    needs_edges = geometry in ("Polygon", "LineString")
    for rec in records:
        obj = rec if isinstance(rec, SpatialObject) else parse_spatial(
            rec, cfg.format, grid, delimiter=cfg.delimiter,
            schema=cfg.csv_tsv_schema, geometry=geometry,
            **cfg.geojson_kwargs())
        if ((needs_edges and not hasattr(obj, "edge_array"))
                or (geometry == "Point" and not hasattr(obj, "x"))):
            continue  # off-type (the scalar path's drop rule)
        yield obj


def scalar_window_tables(records, cfg, grid, size_ms, slide_ms,
                         lateness_ms=0, geometry="Point"):
    """Seed scalar pipeline head: per-record decode + per-record
    ``WindowAssembler.add`` — yields ``(start, end, [records])`` with the
    emission ORDER the scalar loop produced (the timing oracle live tests
    compare consumption positions against)."""
    from spatialflink_tpu.runtime.windows import WindowAssembler, WindowSpec

    wa = WindowAssembler(WindowSpec.sliding(size_ms, slide_ms), lateness_ms)
    for obj in scalar_decode_stream(records, cfg, grid, geometry):
        yield from wa.add(obj.timestamp, obj)
    yield from wa.flush()


def sliding_window_table(ts_list, size, slide, lateness=0):
    """Independent re-derivation of the event-time sliding-window tables
    (Flink semantics + bounded out-of-orderness late drops): feeds the
    timestamps in order, drops records older than the running watermark
    (max seen - lateness), and assigns survivors to every aligned window
    containing them. Returns {window_start: [record_index, ...]} — the
    oracle the pane-incremental engine's window sets are checked against
    (it must match BOTH the per-record assembler and the pane buffer)."""
    out = {}
    max_ts = None
    for i, ts in enumerate(ts_list):
        ts = int(ts)
        if max_ts is not None and ts < max_ts - lateness:
            continue  # late
        if max_ts is None or ts > max_ts:
            max_ts = ts
        start = ts - (ts % slide)
        while start > ts - size:
            out.setdefault(start, []).append(i)
            start -= slide
    return out


def canon_windows(results, canon_record=None):
    """Canonical, order-insensitive window table from an iterator of
    WindowResults: [(start, end, sorted records)] — the shared shape every
    pane-equivalence assertion compares (pane merges may reorder records
    within a window; the SET per window is the contract)."""
    canon_record = canon_record or (lambda r: r)
    return [(r.window_start, r.window_end,
             sorted(canon_record(rec) for rec in r.records))
            for r in results]


def canon_point(p):
    """(obj_id, timestamp, rounded coords) — Point canonicalizer."""
    return (p.obj_id, p.timestamp, round(p.x, 9), round(p.y, 9))


def canon_knn_pair(t):
    """(obj_id, rounded distance) — kNN result-record canonicalizer."""
    return (t[0], round(float(t[1]), 6))


def knn(qx, qy, xs, ys, obj_ids, k, radius=None):
    """Top-k nearest objects with per-object dedup (keep min distance),
    mirroring KNNQuery's PQ + objID-dedup merge (knn/KNNQuery.java:204-300).
    Returns (obj_ids, dists) sorted ascending, at most k entries."""
    d = pp_dist(qx, qy, np.asarray(xs), np.asarray(ys))
    best = {}
    for oid, dist in zip(np.asarray(obj_ids), d):
        if radius is not None and dist > radius:
            continue
        if oid not in best or dist < best[oid]:
            best[oid] = dist
    items = sorted(best.items(), key=lambda kv: kv[1])[:k]
    return [o for o, _ in items], [float(v) for _, v in items]


def range_window_table(rows, qx, qy, radius, size, slide, lateness=0):
    """Per-window exact range answer over ``rows`` — ``(obj_id, ts, x, y)``
    tuples in arrival order: ``{window_start: sorted [(obj_id, ts)]}`` of
    the records within ``radius`` of (qx, qy), windows and late drops as
    :func:`sliding_window_table`. Windows with no match are left out."""
    table = sliding_window_table([r[1] for r in rows], size, slide, lateness)
    out = {}
    for start, idx in table.items():
        hits = sorted((rows[i][0], int(rows[i][1])) for i in idx
                      if pp_dist(qx, qy, rows[i][2], rows[i][3]) <= radius)
        if hits:
            out[start] = hits
    return out


def knn_window_table(rows, qx, qy, k, size, slide, lateness=0):
    """Per-window exact kNN answer over ``(obj_id, ts, x, y)`` rows:
    ``{window_start: (obj_ids, dists)}`` as :func:`knn` returns them."""
    table = sliding_window_table([r[1] for r in rows], size, slide, lateness)
    return {start: knn(qx, qy, [rows[i][2] for i in idx],
                       [rows[i][3] for i in idx],
                       [rows[i][0] for i in idx], k)
            for start, idx in table.items()}


def join_window_table(rows_a, rows_b, radius, size, slide):
    """Per-window exact point-point join over two in-order row streams:
    ``{window_start: sorted [((oid_a, ts_a), (oid_b, ts_b))]}`` of every
    same-window pair within ``radius``. Windows with no pair are left
    out."""
    ta = sliding_window_table([r[1] for r in rows_a], size, slide)
    tb = sliding_window_table([r[1] for r in rows_b], size, slide)
    out = {}
    for start in set(ta) & set(tb):
        pairs = sorted(
            ((rows_a[i][0], int(rows_a[i][1])),
             (rows_b[j][0], int(rows_b[j][1])))
            for i in ta[start] for j in tb[start]
            if pp_dist(rows_a[i][2], rows_a[i][3],
                       rows_b[j][2], rows_b[j][3]) <= radius)
        if pairs:
            out[start] = pairs
    return out
