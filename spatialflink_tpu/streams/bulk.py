"""Bulk (vectorized) point ingestion: text blocks -> structure-of-arrays.

The per-tuple path (``streams.formats.parse_spatial``) mirrors the
reference's per-record deserializer; this module is the high-throughput
twin that decodes a whole chunk of records at once — every file replay
chunk and every Kafka poll on the served path. The parse runs
in native C++ (:mod:`spatialflink_tpu.native`), known obj ids resolve
through the interner's hash index (strings only for unseen ids), and only
rejected lines (ISO dates, non-point GeoJSON, malformed rows) fall back
to the Python parser.

Output is a :class:`ParsedPoints` SoA — exactly what
:meth:`PointBatch.from_arrays` wants — plus the per-record Python
:class:`Point` view for code that needs objects.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from spatialflink_tpu import native
from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import Point, PointBatch
from spatialflink_tpu.streams import formats
from spatialflink_tpu.utils import IdInterner
from spatialflink_tpu.utils import metrics as _metrics

import ctypes


@dataclass
class ParsedPoints:
    """Structure-of-arrays result of a bulk parse (record order preserved)."""

    x: np.ndarray       # (N,) f64
    y: np.ndarray       # (N,) f64
    ts: np.ndarray      # (N,) i64 epoch millis
    obj_id: np.ndarray  # (N,) i32 interned ids
    interner: IdInterner

    def __len__(self) -> int:
        return self.x.shape[0]

    def to_batch(self, grid: Optional[UniformGrid] = None, *,
                 ts_base: Optional[int] = None,
                 pad: Optional[int] = None) -> PointBatch:
        base = int(self.ts[0]) if ts_base is None and len(self) else (ts_base or 0)
        return PointBatch.from_arrays(
            self.x, self.y, grid=grid, obj_id=self.obj_id, ts=self.ts,
            ts_base=base, pad=pad,
        )

    def to_points(self, grid: Optional[UniformGrid] = None) -> List[Point]:
        """Per-record Point objects (the ONE ParsedPoints->records
        conversion — the kafka chunked decode and tests share it); cell
        assignment is vectorized over the whole batch (Point.create's
        per-point assign would dominate the loop)."""
        if grid is not None:
            cells, _ = grid.assign_cell(self.x, self.y)
        else:
            cells = np.full(len(self), -1, np.int32)
        lk = self.interner.lookup
        return [
            Point(obj_id=lk(int(o)), timestamp=int(t), x=float(x),
                  y=float(y), cell=int(c))
            for o, t, x, y, c in zip(self.obj_id, self.ts, self.x, self.y,
                                     cells)
        ]


@dataclass
class PointChunk:
    """One decoded chunk riding the batched record path: the columnar parse
    result plus the vectorized per-record cell assignment, so nothing
    downstream re-derives either per record. ``positions`` (optional) carries
    the per-record source offsets a Kafka commit tap snapshotted at pull
    time; ``ingest_ms`` is the wall clock the chunk was decoded at — the
    stamp lazily-materialized Points inherit as ``ingestion_time`` (the
    scalar path stamped each record at parse; per-chunk is the batched
    equivalent)."""

    parsed: ParsedPoints
    cells: np.ndarray                       # (N,) i32, -1 = outside grid
    positions: Optional[np.ndarray] = None  # (N,) i64 source offsets
    ingest_ms: int = 0
    #: checkpoint-position callback (set by the Kafka commit tap): chunk
    #: consumers that dribble records out one at a time (the flatten path
    #: feeding realtime/pane joins and trajectory) re-note per record so a checkpoint barrier
    #: never covers records still sitting in a half-consumed chunk; the
    #: chunk-aware assemblers buffer whole chunks before any barrier can
    #: run, so the tap's chunk-level note is already safe there
    note: Optional[Callable[[int], None]] = None

    def __len__(self) -> int:
        return len(self.parsed)

    @staticmethod
    def build(parsed: ParsedPoints, grid: Optional[UniformGrid],
              positions=None) -> "PointChunk":
        import time as _time

        if grid is not None and len(parsed):
            cells, _ = grid.assign_cell(parsed.x, parsed.y)
            cells = np.asarray(cells, np.int32)
        else:
            cells = np.full(len(parsed), -1, np.int32)
        return PointChunk(parsed=parsed, cells=cells,
                          positions=None if positions is None
                          else np.asarray(positions, np.int64),
                          ingest_ms=int(_time.time() * 1000))

    def record(self, i: int) -> Point:
        """Materialize record ``i`` (the lazy per-record view)."""
        p = self.parsed
        return Point(obj_id=p.interner.lookup(int(p.obj_id[i])),
                     timestamp=int(p.ts[i]), x=float(p.x[i]),
                     y=float(p.y[i]), cell=int(self.cells[i]),
                     ingestion_time=self.ingest_ms)

    def records(self) -> List[Point]:
        """Materialize every record (the flatten path for consumers without
        a columnar window driver — realtime/pane joins, trajectory,
        realtime)."""
        lk = self.parsed.interner.lookup
        ing = self.ingest_ms
        return [
            Point(obj_id=lk(int(o)), timestamp=int(t), x=float(x),
                  y=float(y), cell=int(c), ingestion_time=ing)
            for o, t, x, y, c in zip(self.parsed.obj_id, self.parsed.ts,
                                     self.parsed.x, self.parsed.y,
                                     self.cells)
        ]


class LazyRecords:
    """A window's (or pane's) record list as columnar chunk slices,
    materializing per-record :class:`Point` objects only on demand.

    This is what the batched record path buffers instead of Python objects:
    segments are either ``(PointChunk, idx_array)`` columnar slices or plain
    record lists (mixed streams — a bulk-ineligible chunk falls back to
    objects). ``point_batch`` builds the window's device batch straight from
    the SoA slices (no per-record objects anywhere on the selected path);
    ``__getitem__`` materializes single records so sparse selections (range
    survivors, join pairs) only ever pay for what they emit. Object ids
    across every segment live in ONE id space — the stream's decode
    ``interner`` — which kNN result resolution and pane-merge tie-breaking
    read through."""

    __slots__ = ("_segs", "_offsets", "_len", "interner", "_cache")

    def __init__(self, segs):
        self._segs = segs
        self._offsets = []
        self._len = 0
        self.interner = None
        for seg in segs:
            self._offsets.append(self._len)
            if isinstance(seg, tuple):
                chunk, idx = seg
                self._len += int(idx.size)
                if self.interner is None:
                    self.interner = chunk.parsed.interner
            else:
                self._len += len(seg)
        self._cache: dict = {}

    def __len__(self) -> int:
        return self._len

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(self._len))]
        if i < 0:
            i += self._len
        if not 0 <= i < self._len:
            raise IndexError(i)
        hit = self._cache.get(i)
        if hit is not None:
            return hit
        # segment lookup (few segments per window; linear scan is fine)
        for seg, off in zip(reversed(self._segs), reversed(self._offsets)):
            if i >= off:
                rec = (seg[0].record(int(seg[1][i - off]))
                       if isinstance(seg, tuple) else seg[i - off])
                self._cache[i] = rec
                return rec
        raise IndexError(i)

    def __iter__(self):
        for seg in self._segs:
            if isinstance(seg, tuple):
                chunk, idx = seg
                for j in idx.tolist():
                    yield chunk.record(j)
            else:
                yield from seg

    def _flat(self):
        """Memoized concatenated per-record arrays (x, y, ts, oid, cell,
        ingest_ms) for vectorized selection; None when an object segment
        makes the columnar gather inapplicable (mixed streams take the
        per-item path)."""
        flat = self._cache.get("_flat_", False)
        if flat is not False:
            return flat
        xs, ys, tss, oids, cells, ings = [], [], [], [], [], []
        for seg in self._segs:
            if not isinstance(seg, tuple):
                self._cache["_flat_"] = None
                return None
            chunk, idx = seg
            p = chunk.parsed
            xs.append(p.x[idx])
            ys.append(p.y[idx])
            tss.append(p.ts[idx])
            oids.append(p.obj_id[idx])
            cells.append(chunk.cells[idx])
            ings.append(np.full(idx.size, chunk.ingest_ms, np.int64))
        flat = tuple(np.concatenate(a) for a in (xs, ys, tss, oids, cells,
                                                 ings))
        self._cache["_flat_"] = flat
        return flat

    def point_batch(self, grid, ts_base: int,
                    pad: Optional[int] = None) -> PointBatch:
        """The window's device batch from the columnar slices — cells were
        assigned once per chunk, obj ids stay in the decode interner's id
        space. Object segments (mixed streams) intern into the same space."""
        xs, ys, tss, oids, cells = [], [], [], [], []
        interner = self.interner if self.interner is not None else IdInterner()
        for seg in self._segs:
            if isinstance(seg, tuple):
                chunk, idx = seg
                p = chunk.parsed
                xs.append(p.x[idx])
                ys.append(p.y[idx])
                tss.append(p.ts[idx])
                oids.append(p.obj_id[idx])
                cells.append(chunk.cells[idx])
            elif seg:
                xs.append(np.array([r.x for r in seg], np.float64))
                ys.append(np.array([r.y for r in seg], np.float64))
                tss.append(np.array([r.timestamp for r in seg], np.int64))
                oids.append(np.array([interner.intern(r.obj_id)
                                      for r in seg], np.int32))
                cells.append(np.array([r.cell for r in seg], np.int32))
        if not xs:
            return PointBatch.from_arrays(np.empty(0), np.empty(0),
                                          grid=grid, ts_base=ts_base, pad=pad)
        return PointBatch.from_arrays(
            np.concatenate(xs), np.concatenate(ys), grid=grid,
            obj_id=np.concatenate(oids), ts=np.concatenate(tss),
            ts_base=ts_base, pad=pad, cell=np.concatenate(cells))

    def take(self, idx):
        """The records at ``idx`` as a :class:`PointRows` view — one
        vectorized gather instead of N ``__getitem__`` segment lookups, and
        Point objects materialize only if a consumer actually reads them
        (result sinks serialize straight from the arrays)."""
        flat = self._flat()
        if flat is None:
            return [self[int(i)] for i in idx]
        idx = np.asarray(idx, np.int64)
        return PointRows(tuple(a[idx] for a in flat), self.interner)


class PointRows:
    """A window's SELECTED records as columnar arrays — list-shaped (len /
    index / iterate / slice materialize real :class:`Point` objects,
    cached), but sinks that only need serialized output read
    :meth:`serialize_batch` and never build a Python object per record.
    This is what keeps the batched path's per-selected-record cost at
    string-format level instead of dataclass-construction level."""

    __slots__ = ("_cols", "interner", "_mat")

    def __init__(self, cols, interner):
        self._cols = cols  # (x, y, ts, oid, cell, ingest_ms) gathered
        self.interner = interner
        self._mat = None

    def __len__(self) -> int:
        return int(self._cols[0].shape[0])

    def _materialize(self) -> List[Point]:
        if self._mat is None:
            # Python scalars in one pass a column: converting numpy
            # scalars one field at a time costs more than the Point itself
            fx, fy, ft, fo, fc, fi = (c.tolist() for c in self._cols)
            lk = self.interner.lookup
            self._mat = [
                Point(obj_id=lk(o), timestamp=t, x=x, y=y, cell=c,
                      ingestion_time=g)
                for o, t, x, y, c, g in zip(fo, ft, fx, fy, fc, fi)
            ]
        return self._mat

    def __getitem__(self, i):
        return self._materialize()[i]

    def __iter__(self):
        return iter(self._materialize())

    def __eq__(self, other):
        if isinstance(other, PointRows):
            other = other._materialize()
        return self._materialize() == other

    def __repr__(self):
        return f"PointRows({len(self)} records)"

    def __add__(self, other):
        return self._materialize() + list(other)

    def __radd__(self, other):
        return list(other) + self._materialize()

    def serialize_batch(self, fmt, *, delimiter: str = ",",
                        date_format=None) -> Optional[List[str]]:
        """Serialized output records straight from the columnar arrays —
        GeoJSON rides the exact fast template ``formats.serialize_geojson``
        uses (byte-identical, equivalence-tested); other formats return
        None and the caller serializes materialized records."""
        if not fmt or fmt.lower() != "geojson":
            return None
        import json as _json

        from spatialflink_tpu.streams.formats import (_JSON_SAFE_RE,
                                                      format_timestamp)

        fx, fy, ft, fo, _fc, _fi = self._cols
        lk = self.interner.lookup
        tmpl = ('{"geometry": {"type": "Point", "coordinates": [%r, %r]}, '
                '"properties": {"oID": %s, "timestamp": %s}, '
                '"type": "Feature"}')
        safe = _JSON_SAFE_RE.match
        # ids: one quote/escape per DISTINCT object, gathered vectorized
        uniq, inv = np.unique(fo, return_inverse=True)
        qid = np.array(
            [('"%s"' % s if safe(s) else _json.dumps(s))
             for s in (lk(int(u)) for u in uniq)], dtype=object)[inv]
        if date_format and "%f" not in date_format:
            # timestamps quote-memoized per second (format_timestamp is
            # already second-memoized; this also amortizes the escape —
            # sound only without a sub-second token, like that memo)
            memo: dict = {}

            def jts(t):
                k = int(t) // 1000
                s = memo.get(k)
                if s is None:
                    raw = format_timestamp(int(t), date_format)
                    s = '"%s"' % raw if safe(raw) else _json.dumps(raw)
                    memo[k] = s
                return s
        elif date_format:
            def jts(t):
                raw = format_timestamp(int(t), date_format)
                return '"%s"' % raw if safe(raw) else _json.dumps(raw)
        else:
            jts = int
        return [tmpl % (float(x), float(y), o, jts(t))
                for x, y, o, t in zip(fx, fy, qid, ft)]

def _ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


def _intern_hashes(data: bytes, oid_hash, oid_start, oid_len,
                   interner: IdInterner, normalize) -> np.ndarray:
    """Vectorized obj-id interning. Hashes the interner's hash index
    already holds resolve in numpy; only the misses materialize a string,
    one per UNIQUE missing hash, interned in ascending hash order (the
    order that assigns new ids) and then indexed. ``normalize`` applies
    the same id normalization the native hash used (format-specific).

    The 64-bit FNV-1a hash of the normalized id is the id's identity, as
    it always was within a chunk; the index carries it across chunks.
    Expected collisions among n distinct ids are ~n²/2⁶⁵ (~3e-12 for a
    10k-taxi fleet). Counters ``intern-index-hits`` / ``-misses`` count
    records, once per chunk."""
    ids, miss = interner.lookup_hashes(oid_hash)
    reg = _metrics.REGISTRY
    reg.counter("intern-index-hits").inc(int(oid_hash.shape[0] - miss.shape[0]))
    reg.counter("intern-index-misses").inc(int(miss.shape[0]))
    if not miss.shape[0]:
        return ids
    uniq, first, inv = np.unique(oid_hash[miss], return_index=True,
                                 return_inverse=True)
    src = miss[first]
    # one decode for every missing id: spans never hold b"\n" (a record is
    # one line), so joining on it and splitting the text is exact, invalid
    # bytes included ("replace" ends each bad sequence at the separator)
    raw = b"\n".join([data[a: a + n] for a, n in
                      zip(oid_start[src].tolist(), oid_len[src].tolist())])
    new = np.array(interner.intern_many(
        map(normalize, raw.decode("utf-8", "replace").split("\n"))), np.int32)
    interner.index_hashes(uniq, new)
    ids[miss] = new[inv.reshape(-1)]
    return ids


# CSV ids: parse_csv removes every '"' then field-trims whitespace; GeoJSON
# ids: the native span is already the exact decoded value
_NORM_CSV = lambda s: s.replace('"', "").strip()  # noqa: E731
_NORM_RAW = lambda s: s  # noqa: E731


def _nonblank_lines(data: bytes):
    """The C parser's blank-line rule exactly: a line is blank iff it contains
    only ' ', '\t', '\r' — NOT the wider bytes.strip() whitespace set, so
    reject indices stay aligned."""
    return [ln for ln in data.split(b"\n") if ln.strip(b" \t\r")]


def _merge_rejects(n: int, accepted: dict, reparsed: List[Tuple[int, Point]],
                   interner: IdInterner) -> ParsedPoints:
    """Stitch native-accepted arrays and Python-reparsed records back into
    original line order."""
    if not reparsed:  # fast path: nothing rejected, arrays are already ordered
        return ParsedPoints(
            x=np.ascontiguousarray(accepted["x"]),
            y=np.ascontiguousarray(accepted["y"]),
            ts=np.ascontiguousarray(accepted["ts"]),
            obj_id=accepted["oid"], interner=interner,
        )
    total = n + len(reparsed)
    x = np.empty(total, np.float64)
    y = np.empty(total, np.float64)
    ts = np.empty(total, np.int64)
    oid = np.empty(total, np.int32)
    reject_lines = {line for line, _ in reparsed}
    # accepted records occupy the non-rejected line slots in order
    order = [i for i in range(total) if i not in reject_lines]
    x[order] = accepted["x"]
    y[order] = accepted["y"]
    ts[order] = accepted["ts"]
    oid[order] = accepted["oid"]
    for line, p in reparsed:
        x[line], y[line], ts[line] = p.x, p.y, p.timestamp
        oid[line] = interner.intern(p.obj_id)
    return ParsedPoints(x=x, y=y, ts=ts, obj_id=oid, interner=interner)


def _require_point(obj, line: str) -> Point:
    if not isinstance(obj, Point):
        raise ValueError(
            "bulk point ingestion got a non-Point record "
            f"({type(obj).__name__}); use streams.formats.parse_spatial for "
            f"mixed-geometry streams: {line[:120]!r}"
        )
    return obj


def _python_fallback(data: bytes, fmt: str, interner: IdInterner,
                     **kw) -> ParsedPoints:
    pts = []
    for ln in data.decode("utf-8", "replace").split("\n"):
        if not ln.strip():
            continue
        pts.append(_require_point(formats.parse_spatial(ln, fmt, None, **kw), ln))
    return ParsedPoints(
        x=np.array([p.x for p in pts], np.float64),
        y=np.array([p.y for p in pts], np.float64),
        ts=np.array([p.timestamp for p in pts], np.int64),
        obj_id=np.array([interner.intern(p.obj_id) for p in pts], np.int32),
        interner=interner,
    )


def bulk_parse_csv(
    data: bytes,
    *,
    delimiter: str = ",",
    schema: Sequence[Optional[int]] = (0, 1, 2, 3),
    date_format: Optional[str] = formats.DEFAULT_DATE_FORMAT,
    interner: Optional[IdInterner] = None,
) -> ParsedPoints:
    """Parse a newline-separated CSV/TSV block of points.

    ``schema`` = column indices of [oID, timestamp, x, y] (None = absent),
    matching :func:`formats.parse_csv` / ``Deserialization.java:288-330``.
    """
    interner = interner if interner is not None else IdInterner()
    nlib = native.lib()
    if nlib is None:
        return _python_fallback(data, "csv", interner, delimiter=delimiter,
                                schema=schema, date_format=date_format)
    cap = data.count(b"\n") + 1
    buf = data if data.endswith(b"\0") else data + b"\0"
    xs = np.empty(cap, np.float64)
    ys = np.empty(cap, np.float64)
    ts = np.empty(cap, np.int64)
    oh = np.empty(cap, np.uint64)
    os_ = np.empty(cap, np.int64)
    ol = np.empty(cap, np.int32)
    rej = np.empty(cap, np.int64)
    nrej = ctypes.c_long(0)
    nfloat = (ctypes.c_long * 2)()
    oi = -1 if schema[0] is None else int(schema[0])
    ti = -1 if schema[1] is None else int(schema[1])
    n = nlib.sf_parse_points_csv(
        buf, len(data), delimiter.encode()[:1] or b",",
        oi, ti, int(schema[2]), int(schema[3]),
        _ptr(xs, ctypes.c_double), _ptr(ys, ctypes.c_double),
        _ptr(ts, ctypes.c_int64),
        _ptr(oh, ctypes.c_uint64), _ptr(os_, ctypes.c_int64),
        _ptr(ol, ctypes.c_int32),
        _ptr(rej, ctypes.c_int64), ctypes.byref(nrej), nfloat,
    )
    # x and y fields (not records) by conversion: the exact short-decimal
    # path, or strtod for every other shape
    reg = _metrics.REGISTRY
    reg.counter("csv-float-fast").inc(nfloat[0])
    reg.counter("csv-float-slow").inc(nfloat[1])
    oid = _intern_hashes(data, oh[:n], os_[:n], ol[:n], interner, _NORM_CSV)
    accepted = {"x": xs[:n], "y": ys[:n], "ts": ts[:n], "oid": oid}
    reparsed = []
    if nrej.value:  # line-splitting is only paid when something was rejected
        lines = _nonblank_lines(data)
        for i in rej[: nrej.value]:
            ln = lines[int(i)].decode("utf-8", "replace")
            p = formats.parse_csv(ln, None, delimiter=delimiter, schema=schema,
                                  date_format=date_format)
            reparsed.append((int(i), _require_point(p, ln)))
    return _merge_rejects(n, accepted, reparsed, interner)


def bulk_parse_geojson(
    data: bytes,
    *,
    property_obj_id: str = "oID",
    property_timestamp: str = "timestamp",
    date_format: Optional[str] = None,
    interner: Optional[IdInterner] = None,
) -> ParsedPoints:
    """Parse a newline-separated block of GeoJSON Point features.

    Non-point features and date-formatted timestamps are re-parsed by the
    Python parser (full fidelity), so this accepts exactly what
    :func:`formats.parse_geojson` accepts.
    """
    interner = interner if interner is not None else IdInterner()
    nlib = native.lib()
    kw = dict(property_obj_id=property_obj_id,
              property_timestamp=property_timestamp,
              date_format=date_format)
    if nlib is None:
        return _python_fallback(data, "geojson", interner, **kw)
    cap = data.count(b"\n") + 1
    buf = data if data.endswith(b"\0") else data + b"\0"
    xs = np.empty(cap, np.float64)
    ys = np.empty(cap, np.float64)
    ts = np.empty(cap, np.int64)
    oh = np.empty(cap, np.uint64)
    os_ = np.empty(cap, np.int64)
    ol = np.empty(cap, np.int32)
    rej = np.empty(cap, np.int64)
    nrej = ctypes.c_long(0)
    n = nlib.sf_parse_points_geojson(
        buf, len(data),
        property_obj_id.encode(), property_timestamp.encode(),
        _ptr(xs, ctypes.c_double), _ptr(ys, ctypes.c_double),
        _ptr(ts, ctypes.c_int64),
        _ptr(oh, ctypes.c_uint64), _ptr(os_, ctypes.c_int64),
        _ptr(ol, ctypes.c_int32),
        _ptr(rej, ctypes.c_int64), ctypes.byref(nrej),
    )
    oid = _intern_hashes(data, oh[:n], os_[:n], ol[:n], interner, _NORM_RAW)
    accepted = {"x": xs[:n], "y": ys[:n], "ts": ts[:n], "oid": oid}
    reparsed = []
    if nrej.value:
        lines = _nonblank_lines(data)
        for i in rej[: nrej.value]:
            ln = lines[int(i)].decode("utf-8", "replace")
            p = formats.parse_geojson(ln, None, **kw)
            reparsed.append((int(i), _require_point(p, ln)))
    return _merge_rejects(n, accepted, reparsed, interner)


# --------------------------------------------------------------------------- #
# Bulk WKT geometry ingestion (polygon / linestring streams)

@dataclass
class ParsedGeoms:
    """Structure-of-arrays result of a bulk WKT geometry parse.

    Flattened ragged layout: geometry g owns rings
    ``ring_off[g] : ring_off[g] + ring_cnt[g]``; ring r owns raw vertices
    ``ring_voff[r] : ring_voff[r] + ring_size[r]`` in (``vx``, ``vy``).
    Lines the native parser rejected (MULTI* geometries, date-formatted
    timestamps, malformed WKT) are re-parsed in Python and flattened into
    the SAME arrays in original line order, so downstream assembly never
    sees two representations.
    """

    ts: np.ndarray        # (N,) i64 epoch millis
    obj_id: np.ndarray    # (N,) i32 interned
    is_areal: np.ndarray  # (N,) bool
    bbox: np.ndarray      # (N, 4) f64
    ring_off: np.ndarray  # (N,) i64
    ring_cnt: np.ndarray  # (N,) i32
    ring_voff: np.ndarray  # (R,) i64
    ring_size: np.ndarray  # (R,) i32
    vx: np.ndarray        # (V,) f64
    vy: np.ndarray        # (V,) f64
    interner: IdInterner

    def __len__(self) -> int:
        return self.ts.shape[0]

    def subset(self, idx: np.ndarray) -> "ParsedGeoms":
        """Geometry subset with re-based ring/vertex offsets (window
        assembly slices the stream dim; pure numpy)."""
        idx = np.asarray(idx)
        rcnt = self.ring_cnt[idx]
        # ring indices of the selected geometries, in selection order
        rrep = np.repeat(np.arange(idx.size), rcnt)
        cum = np.concatenate([[0], np.cumsum(rcnt)])
        rpos = np.arange(int(cum[-1])) - np.repeat(cum[:-1], rcnt)
        rings = self.ring_off[idx][rrep] + rpos
        sizes = self.ring_size[rings].astype(np.int64)
        # vertex gather per selected ring
        vrep = np.repeat(np.arange(rings.size), sizes)
        vcum = np.concatenate([[0], np.cumsum(sizes)])
        vpos = np.arange(int(vcum[-1])) - np.repeat(vcum[:-1], sizes)
        verts = self.ring_voff[rings][vrep] + vpos
        return ParsedGeoms(
            ts=self.ts[idx], obj_id=self.obj_id[idx],
            is_areal=self.is_areal[idx], bbox=self.bbox[idx],
            ring_off=cum[:-1].astype(np.int64),
            ring_cnt=rcnt,
            ring_voff=vcum[:-1].astype(np.int64),
            ring_size=sizes.astype(np.int32),
            vx=self.vx[verts], vy=self.vy[verts],
            interner=self.interner,
        )


def _object_rings(obj) -> Tuple[List[np.ndarray], bool]:
    """A parsed geometry object's rings as coordinate arrays + is_areal —
    how reject objects flatten into the ParsedGeoms layout. Multi-part
    geometries flatten to all their parts' rings (the edge/cells semantics
    EdgeGeomBatch.from_objects derives via obj.edge_array())."""
    from spatialflink_tpu.models import objects as sobj

    if isinstance(obj, sobj.MultiPolygon):
        return [np.asarray(r, np.float64) for p in obj.polygons
                for r in p.rings], True
    if isinstance(obj, sobj.Polygon):
        return [np.asarray(r, np.float64) for r in obj.rings], True
    if isinstance(obj, sobj.MultiLineString):
        return [np.asarray(l.coords_list, np.float64) for l in obj.lines], False
    if isinstance(obj, sobj.LineString):
        return [np.asarray(obj.coords_list, np.float64)], False
    raise ValueError(
        f"bulk WKT geometry ingestion got {type(obj).__name__}; use "
        "streams.formats.parse_spatial for mixed-geometry streams")


def bulk_parse_wkt(
    data: bytes,
    *,
    delimiter: str = ",",
    date_format: Optional[str] = formats.DEFAULT_DATE_FORMAT,
    interner: Optional[IdInterner] = None,
) -> ParsedGeoms:
    """Parse a newline-separated block of WKT polygon/linestring records
    with optional ``oid<delim>ts<delim>`` prefix fields — the bulk twin of
    ``parse_spatial(..., "WKT")`` for geometry streams
    (``Deserialization.java:516-628`` WKT polygon/linestring parsers).
    """
    interner = interner if interner is not None else IdInterner()

    def parse_line(ln):
        return formats.parse_spatial(ln, "WKT", None, delimiter=delimiter,
                                     date_format=date_format)

    nlib = native.lib()
    if nlib is None:
        return _geoms_python_fallback(data, parse_line, interner)
    capr = max(1, data.count(b"("))
    capv = data.count(b",") + capr + 2

    def invoke(buf, *arrs):
        return nlib.sf_parse_wkt_geoms(
            buf, len(data), delimiter.encode()[:1] or b",", *arrs)

    return _native_geoms_parse(data, invoke, parse_line, interner,
                               _NORM_CSV, capr, capv)


def bulk_parse_geojson_geoms(
    data: bytes,
    *,
    property_obj_id: str = "oID",
    property_timestamp: str = "timestamp",
    date_format: Optional[str] = None,
    interner: Optional[IdInterner] = None,
) -> ParsedGeoms:
    """Parse a newline-separated block of GeoJSON Polygon/LineString
    features — the bulk twin of ``parse_spatial(..., "GeoJSON")`` for
    geometry streams (``Deserialization.java:236-334``
    GeoJSONToSpatialPolygon/LineString). Point/Multi*/GeometryCollection
    features, escaped strings and date-formatted timestamps are re-parsed
    by the Python parser, so this accepts exactly what the record path
    accepts."""
    interner = interner if interner is not None else IdInterner()
    kw = dict(property_obj_id=property_obj_id,
              property_timestamp=property_timestamp,
              date_format=date_format)

    def parse_line(ln):
        return formats.parse_spatial(ln, "GeoJSON", None, **kw)

    nlib = native.lib()
    if nlib is None:
        return _geoms_python_fallback(data, parse_line, interner)
    # every point/ring/coords level opens one '[' -> safe upper bounds
    capr = max(1, data.count(b"["))
    capv = capr + 2

    def invoke(buf, *arrs):
        return nlib.sf_parse_geojson_geoms(
            buf, len(data), property_obj_id.encode(),
            property_timestamp.encode(), *arrs)

    return _native_geoms_parse(data, invoke, parse_line, interner,
                               _NORM_RAW, capr, capv)


def _native_geoms_parse(data: bytes, invoke, parse_line, interner, norm,
                        capr: int, capv: int) -> ParsedGeoms:
    """Shared buffers + assembly for the native geometry parsers
    (sf_parse_wkt_geoms / sf_parse_geojson_geoms — identical output
    contract). ``invoke(buf, *array_ptrs)`` calls the symbol with its
    format-specific leading arguments; rejects reparse via ``parse_line``."""
    cap = data.count(b"\n") + 1
    buf = data if data.endswith(b"\0") else data + b"\0"
    ts = np.empty(cap, np.int64)
    oh = np.empty(cap, np.uint64)
    os_ = np.empty(cap, np.int64)
    ol = np.empty(cap, np.int32)
    ispoly = np.empty(cap, np.int8)
    roff = np.empty(cap, np.int64)
    rcnt = np.empty(cap, np.int32)
    bbox = np.empty((cap, 4), np.float64)
    rvoff = np.empty(capr, np.int64)
    rsize = np.empty(capr, np.int32)
    vx = np.empty(capv, np.float64)
    vy = np.empty(capv, np.float64)
    rej = np.empty(cap, np.int64)
    nrej = ctypes.c_long(0)
    n = invoke(
        buf,
        _ptr(ts, ctypes.c_int64), _ptr(oh, ctypes.c_uint64),
        _ptr(os_, ctypes.c_int64), _ptr(ol, ctypes.c_int32),
        _ptr(ispoly, ctypes.c_int8),
        _ptr(roff, ctypes.c_int64), _ptr(rcnt, ctypes.c_int32),
        _ptr(bbox, ctypes.c_double),
        _ptr(rvoff, ctypes.c_int64), _ptr(rsize, ctypes.c_int32),
        _ptr(vx, ctypes.c_double), _ptr(vy, ctypes.c_double),
        _ptr(rej, ctypes.c_int64), ctypes.byref(nrej),
    )
    oid = _intern_hashes(data, oh[:n], os_[:n], ol[:n], interner, norm)
    n_rings = int(rcnt[:n].sum())
    n_verts = int(rsize[:n_rings].sum()) if n_rings else 0
    accepted = ParsedGeoms(
        ts=np.ascontiguousarray(ts[:n]), obj_id=oid,
        is_areal=ispoly[:n].astype(bool),
        bbox=np.ascontiguousarray(bbox[:n]),
        ring_off=np.ascontiguousarray(roff[:n]),
        ring_cnt=np.ascontiguousarray(rcnt[:n]),
        ring_voff=np.ascontiguousarray(rvoff[:n_rings]),
        ring_size=np.ascontiguousarray(rsize[:n_rings]),
        vx=np.ascontiguousarray(vx[:n_verts]),
        vy=np.ascontiguousarray(vy[:n_verts]),
        interner=interner,
    )
    if not nrej.value:
        return accepted
    lines = _nonblank_lines(data)
    reparsed = []
    for i in rej[: nrej.value]:
        ln = lines[int(i)].decode("utf-8", "replace")
        reparsed.append((int(i), parse_line(ln)))
    return _merge_geom_rejects(accepted, reparsed, interner)


def _geoms_python_fallback(data: bytes, parse_line, interner) -> ParsedGeoms:
    """No native library: parse every line in Python, same output layout."""
    reparsed = []
    i = 0
    for ln in data.decode("utf-8", "replace").split("\n"):
        if not ln.strip(" \t\r"):
            continue
        reparsed.append((i, parse_line(ln)))
        i += 1
    empty = ParsedGeoms(
        ts=np.empty(0, np.int64), obj_id=np.empty(0, np.int32),
        is_areal=np.empty(0, bool), bbox=np.empty((0, 4)),
        ring_off=np.empty(0, np.int64), ring_cnt=np.empty(0, np.int32),
        ring_voff=np.empty(0, np.int64), ring_size=np.empty(0, np.int32),
        vx=np.empty(0), vy=np.empty(0), interner=interner,
    )
    return _merge_geom_rejects(empty, reparsed, interner)


def _merge_geom_rejects(accepted: ParsedGeoms, reparsed, interner
                        ) -> ParsedGeoms:
    """Flatten Python-reparsed geometry objects into the SoA layout and
    stitch them back into original line order with the accepted records.

    Python loops touch only the REJECTED objects (their rings); the accepted
    block's flattened arrays are appended as-is and the line-order permute
    rides :meth:`ParsedGeoms.subset` (offset re-basing is exactly the
    subset gather)."""
    n_acc = len(accepted)
    # flatten reject objects -> a small SoA block (O(reject rings) Python)
    rej_rings: List[np.ndarray] = []
    rej_cnt = np.empty(len(reparsed), np.int32)
    rej_ts = np.empty(len(reparsed), np.int64)
    rej_oid = np.empty(len(reparsed), np.int32)
    rej_areal = np.empty(len(reparsed), bool)
    rej_bbox = np.empty((len(reparsed), 4), np.float64)
    for j, (_line, obj) in enumerate(reparsed):
        rl, is_areal = _object_rings(obj)
        rej_rings.extend(rl)
        rej_cnt[j] = len(rl)
        rej_ts[j] = obj.timestamp
        rej_oid[j] = interner.intern(obj.obj_id)
        rej_areal[j] = is_areal
        rej_bbox[j] = np.asarray(obj.bbox, np.float64)
    rej_size = np.array([r.shape[0] for r in rej_rings], np.int32)
    rej_coords = (np.concatenate(rej_rings, axis=0) if rej_rings
                  else np.empty((0, 2)))
    # combined = [accepted block | reject block], offsets shifted
    n_rings_acc = accepted.ring_size.shape[0]
    n_verts_acc = accepted.vx.shape[0]
    combined = ParsedGeoms(
        ts=np.concatenate([accepted.ts, rej_ts]),
        obj_id=np.concatenate([accepted.obj_id, rej_oid]),
        is_areal=np.concatenate([accepted.is_areal, rej_areal]),
        bbox=np.concatenate([accepted.bbox.reshape(n_acc, 4), rej_bbox]),
        ring_off=np.concatenate([
            accepted.ring_off,
            n_rings_acc + np.concatenate(
                [[0], np.cumsum(rej_cnt)])[:-1].astype(np.int64)]),
        ring_cnt=np.concatenate([accepted.ring_cnt, rej_cnt]),
        ring_voff=np.concatenate([
            accepted.ring_voff,
            n_verts_acc + np.concatenate(
                [[0], np.cumsum(rej_size)])[:-1].astype(np.int64)]),
        ring_size=np.concatenate([accepted.ring_size, rej_size]),
        vx=np.concatenate([accepted.vx, rej_coords[:, 0]]),
        vy=np.concatenate([accepted.vy, rej_coords[:, 1]]),
        interner=interner,
    )
    # permutation back to original line order: accepted rows occupy the
    # non-rejected line slots in order, rejects their recorded lines
    total = n_acc + len(reparsed)
    line_of = np.empty(total, np.int64)
    reject_lines = np.array([line for line, _ in reparsed], np.int64)
    is_rej = np.zeros(total, bool)
    is_rej[reject_lines] = True
    line_of[:n_acc] = np.nonzero(~is_rej)[0]
    line_of[n_acc:] = reject_lines
    perm = np.argsort(line_of, kind="stable")
    return combined.subset(perm)


def geoms_to_edge_batch(parsed: ParsedGeoms, grid=None, *,
                        ts_base: int = 0, pad: Optional[int] = None,
                        edge_pad: Optional[int] = None,
                        cell_pad: Optional[int] = None):
    """ParsedGeoms -> :class:`EdgeGeomBatch`, fully vectorized.

    Edge construction matches the object path (``Polygon.create`` +
    ``edge_array``): polygon rings are auto-closed (closure edge appended
    when the raw first and last vertices differ), linestrings are open
    chains; cells are the grid cells overlapped by the bbox with the
    centroid cell as representative (``_EdgeGeom._assign_cells`` rule).
    """
    from spatialflink_tpu.models.batches import EdgeGeomBatch
    from spatialflink_tpu.utils.padding import bucket_size, pad_to

    n = len(parsed)
    if n == 0:
        return EdgeGeomBatch.from_objects([], grid, parsed.interner,
                                          ts_base=ts_base, pad=pad)

    # --- per-ring edge construction --------------------------------------- #
    sizes = parsed.ring_size.astype(np.int64)
    voff = parsed.ring_voff
    R = sizes.shape[0]
    ring_geom = np.repeat(np.arange(n), parsed.ring_cnt)
    if R:
        closure = parsed.is_areal[ring_geom] & (
            (parsed.vx[voff] != parsed.vx[voff + sizes - 1])
            | (parsed.vy[voff] != parsed.vy[voff + sizes - 1]))
        e_r = sizes - 1 + closure
        eoff = np.concatenate([[0], np.cumsum(e_r)])
        total_e = int(eoff[-1])
        base_cnt = sizes - 1
        brep = np.repeat(np.arange(R), base_cnt)
        bcum = np.concatenate([[0], np.cumsum(base_cnt)])
        bpos = np.arange(int(bcum[-1])) - np.repeat(bcum[:-1], base_cnt)
        src = voff[brep] + bpos
        e_flat = np.empty((total_e, 4), np.float32)
        dest = eoff[brep] + bpos
        e_flat[dest, 0] = parsed.vx[src]
        e_flat[dest, 1] = parsed.vy[src]
        e_flat[dest, 2] = parsed.vx[src + 1]
        e_flat[dest, 3] = parsed.vy[src + 1]
        cr = np.nonzero(closure)[0]
        cdest = eoff[cr] + sizes[cr] - 1
        e_flat[cdest, 0] = parsed.vx[voff[cr] + sizes[cr] - 1]
        e_flat[cdest, 1] = parsed.vy[voff[cr] + sizes[cr] - 1]
        e_flat[cdest, 2] = parsed.vx[voff[cr]]
        e_flat[cdest, 3] = parsed.vy[voff[cr]]
        ge = np.bincount(ring_geom, weights=e_r, minlength=n).astype(np.int64)
    else:
        e_flat = np.empty((0, 4), np.float32)
        ge = np.zeros(n, np.int64)

    E = (bucket_size(max(int(ge.max()) if n else 1, 1), 8)
         if edge_pad is None else edge_pad)
    edges = np.zeros((n, E, 4), np.float32)
    emask = np.zeros((n, E), bool)
    if R:
        goff = np.concatenate([[0], np.cumsum(ge)])
        edge_geom = np.repeat(np.arange(n), ge)
        pos_in_geom = np.arange(int(goff[-1])) - np.repeat(goff[:-1], ge)
        edges[edge_geom, pos_in_geom] = e_flat
        emask[edge_geom, pos_in_geom] = True

    # --- cells from bbox --------------------------------------------------- #
    cell_rep = np.full(n, -1, np.int32)
    if grid is not None:
        ix1, iy1 = grid.cell_indices(parsed.bbox[:, 0], parsed.bbox[:, 1])
        ix2, iy2 = grid.cell_indices(parsed.bbox[:, 2], parsed.bbox[:, 3])
        ix1, iy1 = np.asarray(ix1, np.int64), np.asarray(iy1, np.int64)
        ix2, iy2 = np.asarray(ix2, np.int64), np.asarray(iy2, np.int64)
        inside = (ix2 >= 0) & (iy2 >= 0) & (ix1 < grid.n) & (iy1 < grid.n)
        ix1c = np.clip(ix1, 0, grid.n - 1)
        iy1c = np.clip(iy1, 0, grid.n - 1)
        ix2c = np.clip(ix2, 0, grid.n - 1)
        iy2c = np.clip(iy2, 0, grid.n - 1)
        nx = np.where(inside, ix2c - ix1c + 1, 0)
        ny = np.where(inside, iy2c - iy1c + 1, 0)
        counts = nx * ny
        C = (bucket_size(max(int(counts.max()), 1), 8)
             if cell_pad is None else cell_pad)
        cells = np.full((n, C), -1, np.int32)
        cmask = np.zeros((n, C), bool)
        total_c = int(counts.sum())
        if total_c:
            grep = np.repeat(np.arange(n), counts)
            gcum = np.concatenate([[0], np.cumsum(counts)])
            gpos = np.arange(total_c) - np.repeat(gcum[:-1], counts)
            ny_r = np.repeat(ny, counts)
            cxs = np.repeat(ix1c, counts) + gpos // np.maximum(ny_r, 1)
            cys = np.repeat(iy1c, counts) + gpos % np.maximum(ny_r, 1)
            cells[grep, gpos] = (cxs * grid.n + cys).astype(np.int32)
            cmask[grep, gpos] = True
        # representative: centroid cell when valid (always inside the bbox
        # range), else the minimum overlapped cell (= (ix1c, iy1c))
        cx = (parsed.bbox[:, 0] + parsed.bbox[:, 2]) / 2
        cy = (parsed.bbox[:, 1] + parsed.bbox[:, 3]) / 2
        c, valid = grid.assign_cell(cx, cy)
        rep = np.where(np.asarray(valid), np.asarray(c, np.int64),
                       ix1c * grid.n + iy1c)
        cell_rep = np.where(counts > 0, rep, -1).astype(np.int32)
    else:
        C = cell_pad or 8
        cells = np.full((n, C), -1, np.int32)
        cmask = np.zeros((n, C), bool)

    size = bucket_size(n, 8) if pad is None else pad
    ts32 = (parsed.ts - int(ts_base)).astype(np.int32)
    return EdgeGeomBatch(
        edges=pad_to(edges, size),
        edge_mask=pad_to(emask, size),
        bbox=pad_to(parsed.bbox.astype(np.float32), size),
        obj_id=pad_to(parsed.obj_id, size),
        ts=pad_to(ts32, size),
        cell=pad_to(cell_rep, size, fill=-1),
        cells=pad_to(cells, size, fill=-1),
        cells_mask=pad_to(cmask, size),
        is_areal=pad_to(parsed.is_areal, size),
        valid=pad_to(np.ones(n, bool), size),
    )
