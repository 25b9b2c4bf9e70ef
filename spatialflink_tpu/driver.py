"""Driver layer (reference: ``GeoFlink/StreamingJob.java:68-1704``).

The reference's ``main`` is a ~1700-line ``switch(queryOption)`` wiring Kafka
sources through deserializers into one of ~120 query pipelines. Here the same
option space is a declarative registry: ``CASES[option]`` describes the
family (range/knn/join/trajectory/deser), the stream/query geometry types,
window vs real-time mode, and the latency/naive variants; :func:`run_option`
builds the pipeline and returns the result iterator.

Option numbering parity (``StreamingJob.java:470-1704``):

- range:     1/2 + 5*i   (window/realtime) over the 9 ordered type pairs
- kNN:       51/52 + 5*i
- join:      101/102 + 5*i
- latency variants: 8/9 (range), 58/59 (kNN), 108/109 (join) — point-polygon
- trajectory: 201..212 (+ naive twins 2030/2090/2011)
- ser/de round-trips: 401..906
- shapefile: 1001..1003; synthetic harness: 99
- apps: 1010..1012 (StayTime), 2000 (CheckIn)
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from dataclasses import dataclass
from typing import Iterable, Iterator, List, Optional, Tuple

from spatialflink_tpu import operators as ops
from spatialflink_tpu.config import Params, StreamConfig
from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import SpatialObject
from spatialflink_tpu.operators import QueryConfiguration, QueryType, WindowResult
from spatialflink_tpu.streams.formats import parse_spatial, serialize_spatial

_PAIRS = [
    ("Point", "Point"), ("Point", "Polygon"), ("Point", "LineString"),
    ("Polygon", "Point"), ("Polygon", "Polygon"), ("Polygon", "LineString"),
    ("LineString", "Point"), ("LineString", "Polygon"),
    ("LineString", "LineString"),
]


@dataclass(frozen=True)
class CaseSpec:
    family: str                # range|knn|join|tfilter|trange|tstats|taggregate|tjoin|tknn|deser|shapefile|synthetic|staytime|checkin
    stream: str = "Point"      # geometry type of input stream 1
    query: str = "Point"       # geometry type of the query side
    mode: str = "window"       # window|realtime
    latency: bool = False
    naive: bool = False
    fmt: Optional[str] = None         # deser cases force a format
    timestamped: bool = False         # deser trajectory variants
    delim: Optional[str] = None       # deser cases force a delimiter (TSV)


def _build_cases() -> dict:
    c: dict = {}
    for i, (s, q) in enumerate(_PAIRS):
        c[1 + 5 * i] = CaseSpec("range", s, q, "window")
        c[2 + 5 * i] = CaseSpec("range", s, q, "realtime")
        c[51 + 5 * i] = CaseSpec("knn", s, q, "window")
        c[52 + 5 * i] = CaseSpec("knn", s, q, "realtime")
        c[101 + 5 * i] = CaseSpec("join", s, q, "window")
        c[102 + 5 * i] = CaseSpec("join", s, q, "realtime")
    # latency variants (StreamingJob.java:506-522, 685-700, 863-886)
    c[8] = CaseSpec("range", "Point", "Polygon", "window", latency=True)
    c[9] = CaseSpec("range", "Point", "Polygon", "realtime", latency=True)
    c[58] = CaseSpec("knn", "Point", "Polygon", "window", latency=True)
    c[59] = CaseSpec("knn", "Point", "Polygon", "realtime", latency=True)
    c[108] = CaseSpec("join", "Point", "Polygon", "window", latency=True)
    c[109] = CaseSpec("join", "Point", "Polygon", "realtime", latency=True)
    # trajectory queries (StreamingJob.java:1163-1287)
    c[201] = CaseSpec("tfilter", mode="realtime")
    c[202] = CaseSpec("tfilter", mode="window")
    c[203] = CaseSpec("trange", mode="realtime")
    c[2030] = CaseSpec("trange", mode="realtime", naive=True)
    c[204] = CaseSpec("trange", mode="window")
    c[205] = CaseSpec("tstats", mode="realtime")
    c[206] = CaseSpec("tstats", mode="window")
    c[207] = CaseSpec("taggregate", mode="realtime")
    c[208] = CaseSpec("taggregate", mode="window")
    c[209] = CaseSpec("tjoin", mode="realtime")
    c[2090] = CaseSpec("tjoin", mode="realtime", naive=True)
    c[210] = CaseSpec("tjoin", mode="window")
    c[211] = CaseSpec("tknn", mode="realtime")
    c[2011] = CaseSpec("tknn", mode="realtime", naive=True)
    c[212] = CaseSpec("tknn", mode="window")
    # ser/de conformance pipelines (StreamingJob.java:1289-1545)
    _types = ["Point", "Polygon", "LineString", "GeometryCollection",
              "MultiPoint"]
    for base, fmt, ts in ((400, "GeoJSON", False), (500, "WKT", False),
                          (600, "WKT", False), (700, "GeoJSON", True),
                          (800, "WKT", True), (900, "WKT", True)):
        # 600/900 families are the TAB-separated (TSV) variants
        delim = "\t" if base in (600, 900) else None
        for j, t in enumerate(_types, start=1):
            c[base + j] = CaseSpec("deser", t, fmt=fmt, timestamped=ts,
                                   delim=delim)
        # x06: plain (non-WKT) CSV/TSV point rows
        c[base + 6] = CaseSpec("deser", "Point",
                               fmt="TSV" if delim else "CSV",
                               timestamped=ts, delim=delim)
    # shapefile batch inputs (StreamingJob.java:1546-1569)
    c[1001] = CaseSpec("shapefile", "Point")
    c[1002] = CaseSpec("shapefile", "Polygon")
    c[1003] = CaseSpec("shapefile", "LineString")
    c[99] = CaseSpec("synthetic")
    # apps (StreamingJob.java:1619-1700): 1010 = CellStayTime over a point
    # stream, 1011 = CellSensorRangeIntersection over a polygon stream,
    # 1012 = normalizedCellStayTime over both
    c[1010] = CaseSpec("staytime", "Point")
    c[1011] = CaseSpec("staytime", "Polygon")
    c[1012] = CaseSpec("staytime", "Point", "Polygon")
    c[2000] = CaseSpec("checkin")
    return c


CASES = _build_cases()


# --------------------------------------------------------------------- #
# stream decoding


class ChunkedStream:
    """The decoded stream as the operators consume it: iterating yields
    spatial objects (the legacy record contract — the realtime and pane
    joins, trajectory, realtime and the apps flatten through here), while
    chunk-aware window drivers (``WindowAssembler.assemble`` /
    ``PaneBuffer.assemble``, and the windowed joins' two assemblers) pull
    :meth:`chunks` and never materialize per-record objects at all.
    ``interner`` is the stream's one obj-id space (kNN resolution and
    pane-merge tie order read through it)."""

    __slots__ = ("_chunks", "interner")

    def __init__(self, chunks: Iterator, interner):
        self._chunks = chunks
        self.interner = interner

    def chunks(self) -> Iterator:
        """Single-use chunk iterator (columnar PointChunk or record list)."""
        return self._chunks

    def __iter__(self):
        from spatialflink_tpu.utils import telemetry as _telemetry

        tel = _telemetry.active()
        for ch in self._chunks:
            if hasattr(ch, "parsed"):
                if tel is not None:
                    with tel.span("materialize", query="decode"):
                        recs = ch.records()
                else:
                    recs = ch.records()
                if ch.note is not None and ch.positions is not None:
                    # flatten consumers (realtime/pane joins, trajectory)
                    # pull one record at a time: re-note checkpoint
                    # positions per record so a barrier can never cover
                    # records still buffered in this loop
                    for rec, p in zip(recs, ch.positions.tolist()):
                        ch.note(int(p))
                        yield rec
                else:
                    yield from recs
            else:
                yield from ch


def _off_type_warner(geometry: str, dropped):
    """Counter-keyed off-type warning: warns when the ``off-type-dropped``
    counter first moves and again at each decade (1, 10, 100, ...), always
    printing the running count — the batched decoder's replacement for the
    old one-shot boolean (which went silent forever after one record)."""
    state = {"next": 1}

    def warn(typename: str) -> None:
        c = dropped.count
        if c >= state["next"]:
            print(f"warning: dropping off-type {typename} record(s) from "
                  f"declared {geometry} stream (off-type-dropped={c})",
                  file=sys.stderr)
            while state["next"] <= c:
                state["next"] *= 10
    return warn


def decode_chunks(records: Iterable, cfg: StreamConfig, grid: UniformGrid,
                  geometry: str = "Point", chunk: int = 4096,
                  interner=None, max_buffer_s: float = 0.2) -> Iterator:
    """Chunk-vectorized decode — THE ingest path for every mode (file
    replay, kafka chunked drain, ``--kafka-follow`` live). Raw lines buffer
    into chunks and parse through ``streams.bulk``'s columnar parsers (one
    native call per chunk for CSV/TSV/GeoJSON point streams, yielding a
    columnar :class:`~spatialflink_tpu.streams.bulk.PointChunk`); geometry
    streams and pre-parsed objects batch per chunk with the same amortized
    bookkeeping. Telemetry observes, the ingest meter, and the off-type
    filter all run ONCE PER CHUNK instead of once per record.

    Semantics preserved from the scalar decoder: the control-tuple stop
    hook fires at the record that carries it (buffered records before it
    still reach the pipeline), off-type rows — e.g. a stray polygon
    feature in a declared point stream — are dropped per-chunk with the
    same ``off-type-dropped`` counter (a chunk the columnar parser rejects
    falls back to the exact per-record parse rather than crashing), and
    live sources' starvation sentinel flushes the buffer so chunking adds
    at most one poll cycle of latency.

    ``chunk`` is an int OR a zero-arg size callback (the chunk governor's
    actuator, ``runtime/control.py``): a callback resolves ONCE at each
    buffer start, so a live resize lands between flushes — never inside
    one — and the flush threshold stays constant while a chunk fills."""
    from spatialflink_tpu.streams import bulk as B
    from spatialflink_tpu.streams.kafka import STARVED
    from spatialflink_tpu.utils import IdInterner
    from spatialflink_tpu.utils import metrics as _metrics
    from spatialflink_tpu.utils import telemetry as _telemetry
    from spatialflink_tpu.utils.metrics import (REGISTRY, ControlTupleExit,
                                                check_exit_control_tuple)

    meter = REGISTRY.meter("ingest-throughput")
    dropped = REGISTRY.counter("off-type-dropped")
    warn = _off_type_warner(geometry, dropped)
    needs_edges = geometry in ("Polygon", "LineString")
    is_point = geometry == "Point"
    fmt = cfg.format.lower()
    bulk_ok = is_point and fmt in ("csv", "tsv", "geojson")
    interner = interner if interner is not None else IdInterner()
    tel = _telemetry.active()
    # decode-chunk buffer depth (backpressure timeline): the fill level at
    # each flush — one gauge set per CHUNK, nothing per record
    depth_gauge = (tel.gauge("decode.buffer-depth")
                   if tel is not None else None)

    def off_type_filter(objs: List) -> List:
        kept = []
        for o in objs:
            if ((needs_edges and not hasattr(o, "edge_array"))
                    or (is_point and not hasattr(o, "x"))):
                dropped.inc()
                warn(type(o).__name__)
            else:
                kept.append(o)
        return kept

    def parse_one(rec):
        return parse_spatial(
            rec, cfg.format, grid,
            delimiter=cfg.delimiter,
            schema=cfg.csv_tsv_schema,
            # only CSV/TSV needs the hint (coordinate-string rows,
            # CSVTSVToSpatialPolygon); GeoJSON/WKT are self-describing
            geometry=geometry,
            **cfg.geojson_kwargs(),
        )

    def parse_raws(raws: List[str]):
        # the columnar parse rides only when the chunk maps 1:1 onto parser
        # lines (no INTERIOR newlines — a trailing newline from an
        # unstripped file iterator is normalized away) and every row is a
        # point the native/reject machinery accepts; anything else —
        # including off-type rows, which the point parsers reject with
        # ValueError — falls back to the exact per-record parse + the
        # off-type drop counter
        if bulk_ok:
            raws = [r[:-1] if r.endswith("\n") else r for r in raws]
        if bulk_ok and not any("\n" in r for r in raws):
            data = "\n".join(raws).encode()
            try:
                if fmt == "geojson":
                    parsed = B.bulk_parse_geojson(data, interner=interner,
                                                  **cfg.geojson_kwargs())
                else:
                    parsed = B.bulk_parse_csv(
                        data, delimiter="\t" if fmt == "tsv" else cfg.delimiter,
                        schema=_schema4(cfg), date_format=cfg.date_format,
                        interner=interner)
            except ValueError:
                parsed = None
            if parsed is not None and len(parsed) == len(raws):
                return B.PointChunk.build(parsed, grid)
        return off_type_filter([parse_one(r) for r in raws])

    src_chunks = getattr(records, "chunks", None)
    if src_chunks is not None:
        # an upstream chunked decoder (the Kafka commit tap) already parsed;
        # apply only the meter + off-type bookkeeping per chunk
        for ch in src_chunks():
            if hasattr(ch, "parsed"):
                meter.mark(len(ch))
                if len(ch):
                    yield ch
            else:
                meter.mark(len(ch))
                kept = off_type_filter(list(ch))
                if kept:
                    yield kept
        return

    buf: List = []
    kind = None  # "str" (columnar-parseable) | "obj" (parsed) | "raw"
    chunk_fn = chunk if callable(chunk) else None
    chunk_n = max(1, int(chunk_fn() if chunk_fn is not None else chunk))

    def parse_buf() -> List:
        if kind == "str":
            return parse_raws(buf)
        if kind == "obj":
            return off_type_filter(buf)
        return off_type_filter([parse_one(r) for r in buf])

    def flush():
        nonlocal buf, kind
        if not buf:
            return None
        if depth_gauge is not None:
            depth_gauge.set(len(buf))
        if tel is not None:
            # ONE decode span per chunk — the parse cost amortized over
            # the chunk (the scalar path observed per record)
            with tel.span("decode"):
                out = parse_buf()
        else:
            out = parse_buf()
        meter.mark(len(buf))
        buf = []
        kind = None
        return out if len(out) else None

    src = iter(records)
    shutdown_requested = _metrics.shutdown_requested  # hoisted: per-record
    while True:
        try:
            rec = next(src)
        except StopIteration:
            break
        except ControlTupleExit:
            # a source-raised stop (a tailing fleet source seeing the
            # shutdown flag while idle): drain the buffer downstream
            # first — every record already read must reach its window
            # before the stop propagates (positions were tap-counted)
            out = flush()
            if out is not None:
                yield out
            raise
        if rec is STARVED:
            # quiet live topic: hand everything buffered downstream so a
            # chunk never waits out dead air (latency bound = one poll)
            out = flush()
            if out is not None:
                yield out
            continue
        try:
            check_exit_control_tuple(rec)
        except ControlTupleExit:
            out = flush()
            if out is not None:
                yield out
            raise
        k = ("str" if isinstance(rec, str)
             else "obj" if isinstance(rec, SpatialObject) else "raw")
        if buf and k != kind:
            out = flush()
            if out is not None:
                yield out
        if not buf:
            t_first = time.perf_counter()
            if chunk_fn is not None:
                chunk_n = max(1, int(chunk_fn()))
        buf.append(rec)
        kind = k
        if shutdown_requested():
            # SIGTERM landed between records: the current record is
            # already buffered (tap-counted — dropping it would lose it
            # from the final checkpoint), so drain the chunk and stop
            out = flush()
            if out is not None:
                yield out
            raise _metrics.GracefulShutdown(
                "shutdown requested (SIGTERM): buffered records drained")
        # size OR age flush: a slow live source without a starvation
        # sentinel (direct KafkaSource feeds) must not hold records hostage
        # to a chunk fill — `max_buffer_s` bounds the added decode latency
        # (replay sources fill chunks in microseconds and never hit it)
        if (len(buf) >= chunk_n
                or time.perf_counter() - t_first >= max_buffer_s):
            out = flush()
            if out is not None:
                yield out
    out = flush()
    if out is not None:
        yield out


def decode_stream(records: Iterable, cfg: StreamConfig, grid: UniformGrid,
                  geometry: str = "Point",
                  chunk: int = 4096) -> "ChunkedStream":
    """Raw lines/dicts → spatial objects (the reference's per-case
    ``Deserialization.*Stream`` stage), rebuilt on the batched
    :func:`decode_chunks` seam: the scalar per-record parse loop is gone —
    every mode decodes chunk-vectorized, and the returned
    :class:`ChunkedStream` serves both per-record consumers (iteration)
    and the chunk-aware window assemblers (``.chunks``). The seed scalar
    decoder survives only as a test oracle (``tests/oracles.py``)."""
    from spatialflink_tpu.utils import IdInterner

    interner = getattr(records, "interner", None)
    if interner is None and geometry == "Point" \
            and cfg.format.lower() in ("csv", "tsv", "geojson"):
        interner = IdInterner()
    return ChunkedStream(
        decode_chunks(records, cfg, grid, geometry, chunk, interner=interner),
        interner)


#: (family, mode) combinations the coordinated checkpointer covers: their
#: drive loops register every piece of cross-record state with the
#: coordinator and barrier between processing units. Families with
#: unregistered cross-batch state (realtime join's rolling buffers, tJoin/
#: tKnn's bespoke loops, the apps) are refused — a checkpoint that misses
#: live state would LOSE records on resume, which is worse than no
#: checkpoint.
_CKPT_WINDOW_FAMILIES = ("range", "knn", "join", "tfilter", "trange",
                         "tstats", "taggregate")
_CKPT_REALTIME_FAMILIES = ("range", "knn", "tstats", "taggregate")


def _checkpoint_dir_unsupported(params: Params,
                                spec: CaseSpec) -> Optional[str]:
    """None when --checkpoint-dir covers this case; else the reason it
    doesn't (the driver warns and runs without the coordinator)."""
    if spec.naive:
        return "naive-twin oracles keep the plain path"
    if spec.mode == "window":
        if params.window.type == "COUNT":
            return ("count windows buffer by arrival order outside the "
                    "checkpointable assemblers")
        if spec.family not in _CKPT_WINDOW_FAMILIES:
            return (f"windowed {spec.family} has no registered "
                    "checkpoint state")
        return None
    if spec.family not in _CKPT_REALTIME_FAMILIES:
        return (f"realtime {spec.family} keeps cross-batch state outside "
                "the checkpointable participants")
    return None


def _query_conf(params: Params, spec: CaseSpec) -> QueryConfiguration:
    size_ms, step_ms = params.window_ms()
    if spec.mode == "realtime":
        qt = QueryType.RealTime
    elif params.window.type == "COUNT":
        # sliding count windows for every single-stream windowed operator
        # (the reference declares CountBased and throws "Not yet support"
        # everywhere except tAggregate's per-cell variant, QueryType.java:6;
        # here the mode is implemented — see operators/base.py
        # _count_windows); joins/apps with bespoke window logic still raise
        qt = QueryType.CountBased
        # count windows interpret interval/step as raw element COUNTS — the
        # reference hands the same config values to countWindow un-scaled
        size_ms, step_ms = int(params.window.interval_s), int(params.window.step_s)
    else:
        qt = QueryType.WindowBased
    return QueryConfiguration(
        query_type=qt,
        window_size_ms=size_ms,
        slide_ms=step_ms,
        allowed_lateness_ms=params.query.allowed_lateness_s * 1000,
        approximate=params.query.approximate,
        # pane-incremental sliding windows (--panes / query.panes): kernel
        # partials once per slide, merged across overlapping windows; only
        # engages for pane-decomposable event-time windows (operators gate)
        panes=params.query.panes,
        # --pane-merge device|host: where pane partials live and merge
        pane_device_merge=params.query.pane_device_merge,
        k=params.query.k,
        # query.parallelism ≙ env.setParallelism(30) (StreamingJob.java:221):
        # shard window batches across a device mesh; query.hosts > 1 makes
        # it the 2-D multi-host (DCN x ICI) shape
        devices=params.query.parallelism or None,
        hosts=params.query.hosts or None,
        # coordinated checkpointing (--checkpoint-dir): operators register
        # their window/pane/trajectory state and barrier through this
        checkpointer=getattr(params, "checkpointer", None),
        # skew-adaptive refinement layer (--adaptive-grid): the shared
        # AdaptiveGrid whose leaf masks drive the pre-kernel prefilter
        adaptive_grid=getattr(params, "adaptive_grid", None),
        # mesh shard placement (--shard-order)
        shard_order=getattr(params, "shard_order", "arrival"),
    )


def _operator_class(spec: CaseSpec):
    """The stream x query operator class for a range/kNN/join CaseSpec."""
    fam = {"range": "Range", "knn": "KNN", "join": "Join"}[spec.family]
    return getattr(ops, f"{spec.stream}{spec.query}{fam}Query")


def _query_object(params: Params, grid: UniformGrid, kind: str):
    if kind == "Point":
        pts = params.query_point_objects(grid)
        if not pts:
            raise ValueError("query.queryPoints is empty")
        return pts[0]
    if kind == "Polygon":
        polys = params.query_polygon_objects(grid)
        if not polys:
            raise ValueError("query.queryPolygons is empty")
        return polys[0]
    lss = params.query_linestring_objects(grid)
    if not lss:
        raise ValueError("query.queryLineStrings is empty")
    return lss[0]


def _run_multi_case(params: Params, spec: CaseSpec, op, s1,
                    u_grid: UniformGrid, radius: float) -> Iterator:
    """``query.multiQuery`` dispatch: answer ALL configured query objects in
    one dispatch per window via run_multi (TPU-native extension; without the
    flag the driver keeps reference parity and uses only the first query
    object). Supported: ALL NINE range and kNN pairs here, plus trajectory
    kNN (211/212) routed through its own branch in ``_run_trajectory`` —
    keep the three in sync: this dispatch, the tknn branch, and
    run_option's family gate. Other families error rather than silently
    falling back to first-query semantics (run_option rejects them before
    dispatch reaches here)."""
    if spec.latency:
        raise ValueError(
            "multiQuery does not combine with the latency variants "
            "(per-record latency assumes single-query record lists)")
    getter, name = {
        "Point": (params.query_point_objects, "queryPoints"),
        "Polygon": (params.query_polygon_objects, "queryPolygons"),
        "LineString": (params.query_linestring_objects, "queryLineStrings"),
    }[spec.query]
    qs = getter(u_grid)
    if not qs:
        raise ValueError(f"query.{name} is empty")
    if spec.family == "range":
        return op.run_multi(s1, qs, radius)
    return op.run_multi(s1, qs, radius, params.query.k)


def _with_latency(results: Iterator[WindowResult]) -> Iterator[WindowResult]:
    """Annotate each result with per-record latency millis (reference:
    ``now - ingestionTime`` shipped to a Kafka topic,
    ``utils/HelperClass.java:455-529``). With telemetry active the same
    values feed the session's ``record-latency-ms`` streaming histogram so
    the snapshots carry p50/p95/p99."""
    from spatialflink_tpu.utils import telemetry as _telemetry

    tel = _telemetry.active()
    hist = tel.histogram("record-latency-ms") if tel is not None else None
    for r in results:
        now = int(time.time() * 1000)
        lats = []
        for rec in r.records:
            obj = rec[0] if isinstance(rec, tuple) else rec
            base = getattr(obj, "ingestion_time", None)
            if isinstance(base, (int, float)) and base > 0:
                lats.append(now - int(base))
        if hist is not None:
            for v in lats:
                hist.record(v)
        r.extras["latency_ms"] = lats
        yield r


# --------------------------------------------------------------------- #


def run_option(params: Params, stream1: Iterable, stream2: Optional[Iterable]
               = None) -> Iterator:
    """Wire and run the pipeline for ``params.query.option``.

    ``stream1``/``stream2`` are iterables of raw records (str/dict) or parsed
    spatial objects — the host-side stand-ins for the reference's two Kafka
    consumers."""
    opt = params.query.option
    if opt not in CASES:
        raise ValueError(f"unknown queryOption {opt}")
    spec = CASES[opt]
    if params.query.multi_query and spec.family not in ("range", "knn",
                                                        "tknn"):
        # every ineligible family errors — silently answering only the
        # first query under the flag would be worse than failing
        raise ValueError(
            f"multiQuery is not supported for queryOption {opt} "
            f"({spec.family}); supported: all nine range and kNN "
            "pairs, plus trajectory kNN (211/212)")
    u_grid, q_grid = params.grids()
    conf = _query_conf(params, spec)
    radius = params.query.radius

    # decode chunk sizing: realtime chunks at the micro-batch size (chunk
    # fill and batch fire coincide — no added latency vs the scalar path);
    # count windows chunk at the slide COUNT (fires stay step-aligned);
    # windowed modes use the default throughput chunk (live sources bound
    # the buffering to one poll cycle via the starvation sentinel)
    if spec.mode == "realtime":
        # the vectorized micro-batcher cuts strictly every
        # realtime_batch_size records regardless of decode-chunk size, so
        # the governor may drive realtime chunks without moving a single
        # batch boundary (tests/test_control.py pins the identity)
        dchunk = _governed_chunk(max(1, conf.realtime_batch_size))
    elif params.window.type == "COUNT":
        dchunk = max(1, min(4096, int(params.window.step_s)))
    else:
        dchunk = _governed_chunk(_decode_chunk_env(4096))

    if spec.family in ("range", "knn", "join"):
        cls = _operator_class(spec)
        s1 = decode_stream(stream1, params.input1, u_grid, spec.stream,
                           chunk=dchunk)
        if spec.family == "join":
            op = cls(conf, u_grid, q_grid)
            if stream2 is None:
                raise ValueError(f"queryOption {opt} (join) needs stream2")
            s2 = decode_stream(stream2, params.input2, q_grid, spec.query,
                               chunk=dchunk)
            out = op.run(s1, s2, radius)
        else:
            op = cls(conf, u_grid)
            registry = getattr(params, "query_registry", None)
            if registry is not None:
                # dynamic standing-query plane: the live registry — not the
                # static config — says what runs (admissions/retirements
                # land at window boundaries, padded to Q-axis size buckets)
                if spec.family == "knn":
                    out = op.run_dynamic(s1, registry, radius,
                                         params.query.k)
                else:
                    out = op.run_dynamic(s1, registry, radius)
            elif params.query.multi_query:
                out = _run_multi_case(params, spec, op, s1, u_grid, radius)
            else:
                q = _query_object(params, u_grid, spec.query)
                if spec.family == "knn":
                    out = op.run(s1, q, radius, params.query.k)
                else:
                    out = op.run(s1, q, radius)
        return _with_latency(out) if spec.latency else out

    if spec.family in ("tfilter", "trange", "tstats", "taggregate", "tjoin",
                       "tknn"):
        return _run_trajectory(params, spec, conf, u_grid, q_grid,
                               stream1, stream2)

    if spec.family == "deser":
        return _run_deser(params, spec, u_grid, stream1)

    if spec.family == "shapefile":
        from spatialflink_tpu.streams.shapefile import read_shapefile

        # stream1 is a path (or iterable of paths) to .shp files
        paths = [stream1] if isinstance(stream1, (str, bytes)) else list(stream1)
        return iter([obj for p in paths for obj in read_shapefile(p, u_grid)])

    if spec.family == "synthetic":
        return _run_synthetic(params, conf, u_grid)

    if spec.family == "staytime":
        from spatialflink_tpu.apps.stay_time import StayTime

        app = StayTime(conf, u_grid)
        traj_ids = set(params.query.traj_ids) or None
        if spec.query == "Polygon":  # 1012: point stream + polygon stream
            if stream2 is None:
                raise ValueError("queryOption 1012 needs a polygon stream2")
            s1 = decode_stream(stream1, params.input1, u_grid)
            # both sides must live in the app's grid (the reference passes
            # ONE uGrid to normalizedCellStayTime, StreamingJob.java:1667)
            s2 = decode_stream(stream2, params.input2, u_grid, "Polygon")
            # query.trajIDs names moving-object trajectories; sensor polygon
            # IDs live in a different namespace, so the sensor side is never
            # filtered by it (StayTime.java keys sensors by poly id only)
            return app.normalized_cell_stay_time(
                s1, s2, traj_ids_points=traj_ids, traj_ids_sensors=None)
        s1 = decode_stream(stream1, params.input1, u_grid, spec.stream)
        if spec.stream == "Polygon":  # 1011: sensor-range intersection
            return app.cell_sensor_range_intersection(s1, traj_ids)
        return app.cell_stay_time(s1, traj_ids)

    if spec.family == "checkin":
        from spatialflink_tpu.apps.check_in import CheckIn

        # raw DEIM CSV lines (eventID,deviceID,userID,ts,x,y) are parsed by
        # the app itself; parsed Points pass through
        return CheckIn(conf).run(stream1)

    raise AssertionError(f"unhandled family {spec.family}")


def _run_trajectory(params, spec, conf, u_grid, q_grid, stream1, stream2):
    dchunk = _governed_chunk(
        max(1, conf.realtime_batch_size) if spec.mode == "realtime"
        else 4096)
    s1 = decode_stream(stream1, params.input1, u_grid, chunk=dchunk)
    q = params.query
    if spec.family == "tfilter":
        return ops.PointTFilterQuery(conf, u_grid).run(s1, set(q.traj_ids))
    if spec.family == "trange":
        polys = params.query_polygon_objects(u_grid)
        op = ops.PointPolygonTRangeQuery(conf, u_grid)
        return op.run_naive(s1, polys) if spec.naive else op.run(s1, polys)
    if spec.family == "tstats":
        return ops.PointTStatsQuery(conf, u_grid).run(
            s1, set(q.traj_ids) or None,
            checkpoint_path=params.checkpoint_path,
            checkpoint_every=params.checkpoint_every,
            checkpoint_job=params.checkpoint_job)
    if spec.family == "taggregate":
        return ops.PointTAggregateQuery(conf, u_grid).run(
            s1, q.aggregate_function,
            traj_deletion_threshold_ms=q.traj_deletion_threshold_s * 1000,
            checkpoint_path=params.checkpoint_path,
            checkpoint_every=params.checkpoint_every,
            checkpoint_job=params.checkpoint_job)
    if spec.family == "tjoin":
        if stream2 is None:
            raise ValueError("trajectory join needs stream2")
        s2 = decode_stream(stream2, params.input2, q_grid, chunk=dchunk)
        op = ops.PointPointTJoinQuery(conf, u_grid, q_grid)
        run = op.run_naive if spec.naive else op.run
        return run(s1, s2, params.query.radius)
    if spec.family == "tknn":
        op = ops.PointPointTKNNQuery(conf, u_grid)
        if params.query.multi_query:
            if spec.naive:
                raise ValueError(
                    "multiQuery does not combine with the naive-twin tKnn "
                    "(the oracle exists to check the pruned single path)")
            qps = params.query_point_objects(u_grid)
            if not qps:
                raise ValueError("query.queryPoints is empty")
            return op.run_multi(s1, qps, params.query.radius, q.k)
        qp = _query_object(params, u_grid, "Point")
        run = op.run_naive if spec.naive else op.run
        return run(s1, qp, params.query.radius, q.k)
    raise AssertionError(spec.family)


def _run_deser(params, spec, grid, stream1) -> Iterator:
    """Parse each record with the case's forced format and immediately
    re-serialize — the reference's parse→print→produce conformance path
    (``StreamingJob.java:1289-1545``)."""
    fmt = spec.fmt
    delim = spec.delim or ("\t" if fmt == "TSV" else params.input1.delimiter or ",")
    for rec in stream1:
        obj = rec if isinstance(rec, SpatialObject) else parse_spatial(
            rec, fmt, grid,
            delimiter=delim,
            schema=params.input1.csv_tsv_schema,
            date_format=params.input1.date_format,
        )
        yield obj, serialize_spatial(
            obj, fmt, delimiter=delim,
            date_format=params.input1.date_format if spec.timestamped else None)


def _run_synthetic(params: Params, conf, grid) -> Iterator[WindowResult]:
    """queryOption 99: run ALL SIX trajectory query families over
    deterministic synthetic trajectories — the reference harness sketched
    every one against ``env.fromCollection`` (``StreamingJob.java:1571-1618``).
    Results are tagged with the family via ``extras['family']`` so a smoke
    run can assert each family actually fired."""
    from spatialflink_tpu.models import Polygon
    from spatialflink_tpu.streams.sources import (SyntheticPointSource,
                                                  generate_query_polygons)

    def src():
        return SyntheticPointSource(grid, num_trajectories=16, steps=8, seed=7)

    def tagged(family, it):
        for r in it:
            if hasattr(r, "extras"):
                r.extras.setdefault("family", family)
            yield r

    first = list(src())
    traj_ids = {p.obj_id for p in first[:4]}
    qp = first[0]
    # a query polygon covering the middle of the grid (guarantees matches)
    # plus cell-sized tiles from the HelperClass.generateQueryPolygons
    # rebuild (streams.sources.generate_query_polygons) — the polygon-SET
    # shape the reference harness fed tRange
    cx = (grid.min_x + grid.max_x) / 2
    cy = (grid.min_y + grid.max_y) / 2
    dx = (grid.max_x - grid.min_x) / 4
    dy = (grid.max_y - grid.min_y) / 4
    qpoly = Polygon.create(
        [[(cx - dx, cy - dy), (cx + dx, cy - dy), (cx + dx, cy + dy),
          (cx - dx, cy + dy)]], grid)
    qpolys = [qpoly] + generate_query_polygons(8, grid)

    yield from tagged("tfilter",
                      ops.PointTFilterQuery(conf, grid).run(src(), traj_ids))
    yield from tagged("trange",
                      ops.PointPolygonTRangeQuery(conf, grid).run(src(), qpolys))
    yield from tagged("tstats", ops.PointTStatsQuery(conf, grid).run(src()))
    yield from tagged("taggregate", ops.PointTAggregateQuery(conf, grid).run(
        src(), params.query.aggregate_function))
    # query.radius defaults to 0.0 in the config schema (= unset); the
    # harness needs a working radius — tJoin's proximity test and tKnn's
    # enforced radius filter both emit nothing at 0 — so 0 falls back to a
    # half-degree probe. A deliberately tiny radius still passes through.
    radius = params.query.radius if params.query.radius > 0 else 0.5
    yield from tagged("tjoin", ops.PointPointTJoinQuery(conf, grid, grid).run(
        src(), src(), radius))
    yield from tagged("tknn", ops.PointPointTKNNQuery(conf, grid).run(
        src(), qp, radius, params.query.k))


# --------------------------------------------------------------------- #
# CLI


def _emit(result, sink) -> None:
    if isinstance(result, WindowResult):
        if "queries" in result.extras:
            # multi-query windows: records is a list of Q per-query lists
            counts = {"count": sum(len(r) for r in result.records),
                      "per_query_counts": [len(r) for r in result.records]}
        else:
            counts = {"count": len(result.records)}
        sink.emit({
            "window": [result.window_start, result.window_end],
            **counts,
            **{k: v for k, v in result.extras.items() if k != "latency_ms"},
        })
    else:
        sink.emit(result)


#: the checkout-local compile cache (listed in .gitignore): a fixed path, so
#: every process of a checkout — CLI runs, fleet workers, chip_smoke.py —
#: finds the others' compilations
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_compilation_cache() -> Tuple[Optional[str], Optional[str]]:
    """Persist XLA compilations across processes. -> (cache dir, error).

    A pipeline's kernels are identical run to run, but every fresh process
    pays the compiles again. ``JAX_COMPILATION_CACHE_DIR``, when set, is
    the cache and no other directory is set in code; otherwise the cache is
    :data:`CHECKOUT_CACHE_DIR`. Failure is non-fatal (the cache is an
    optimization, not a dependency) and is returned and printed.
    """
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CHECKOUT_CACHE_DIR
    try:
        os.makedirs(cache, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache)
        if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    except Exception as e:  # depends on fs/env
        print(f"note: compilation cache disabled ({e})", file=sys.stderr)
        return None, f"{type(e).__name__}: {e}"
    return cache, None


def _parse_fn(cfg: StreamConfig, grid: UniformGrid, geometry: str):
    """The per-record parse :func:`decode_stream` applies, as a plain
    callable (the Kafka commit tap parses BEFORE the pipeline so it can read
    event times; decode_stream then passes the parsed objects through)."""
    def parse(rec):
        if isinstance(rec, SpatialObject):
            return rec
        return parse_spatial(rec, cfg.format, grid, delimiter=cfg.delimiter,
                             schema=cfg.csv_tsv_schema, geometry=geometry,
                             **cfg.geojson_kwargs())
    return parse


def _decode_chunk_env(default: int) -> int:
    """Decode chunk size with the ``SPATIALFLINK_DECODE_CHUNK`` override —
    the knob tests/benches use to exercise chunk-boundary behavior (e.g.
    record-granular checkpoint positions on tiny topics)."""
    v = os.environ.get("SPATIALFLINK_DECODE_CHUNK")
    return max(1, int(v)) if v else default


def _governed_chunk(dchunk: int, pinned: bool = False):
    """The decode-chunk actuator seam: a per-flush size callback that
    reads the installed chunk governor (``--controller``) LATE — at each
    buffer start, not at wiring time — so stream construction order vs.
    governor install order does not matter, and a governor installed
    mid-run takes effect at the next flush. Without one the callback
    returns the fixed size (same values as the pre-governor int).
    ``pinned`` sizes — an explicit ``SPATIALFLINK_DECODE_CHUNK`` env
    override, or count-window step alignment — stay fixed ints: the
    operator asked for THAT chunk."""
    if pinned or os.environ.get("SPATIALFLINK_DECODE_CHUNK"):
        return dchunk
    from spatialflink_tpu.runtime.control import active_governor

    def _resolve() -> int:
        gov = active_governor()
        return gov.chunk() if gov is not None else dchunk
    return _resolve


def _schema4(cfg: StreamConfig) -> list:
    """csvTsvSchemaAttr padded to the 4 [oID, ts, x, y] slots (None =
    absent) — shared by :func:`decode_chunks` and the kafka chunked decode."""
    return (list(cfg.csv_tsv_schema) + [None] * 4)[:4]


def _kafka_bulk_decode(cfg: StreamConfig, grid: UniformGrid):
    """Chunked native decode for broker-fed POINT streams (CSV/TSV/GeoJSON):
    the columnar point parser applied to poll batches, returning a COLUMNAR
    :class:`~spatialflink_tpu.streams.bulk.PointChunk` (vectorized cell
    assignment; per-record Point objects materialize only if a non-columnar
    consumer flattens). None when the format cannot ride it (the tap then
    parses per record)."""
    from spatialflink_tpu.streams import bulk as B
    from spatialflink_tpu.utils import IdInterner

    fmt = cfg.format.lower()
    if fmt not in ("csv", "tsv", "geojson"):
        return None
    interner = IdInterner()
    schema = _schema4(cfg)

    def decode(raws: List[str]):
        data = "\n".join(raws).encode()
        if fmt == "geojson":
            parsed = B.bulk_parse_geojson(data, interner=interner,
                                          **cfg.geojson_kwargs())
        else:
            parsed = B.bulk_parse_csv(
                data, delimiter="\t" if fmt == "tsv" else cfg.delimiter,
                schema=schema, date_format=cfg.date_format,
                interner=interner)
        return B.PointChunk.build(parsed, grid)

    decode.interner = interner
    return decode


def _preproduce(broker, topic: str, path: str, limit: Optional[int]) -> None:
    """Produce the file to the topic EXACTLY ONCE across restarts: records
    already in the topic count as the file's prefix (this mode assumes the
    topic is fed only by this file), so a re-run of the same command after a
    crash — even a crash mid-preproduce — resumes producing at the first
    missing record instead of appending a duplicate copy (which would
    corrupt every window still covered by uncommitted offsets) or silently
    truncating the dataset."""
    from spatialflink_tpu.streams.sources import FileReplaySource

    have = broker.end_offset(topic)
    lim = None if limit is None else max(0, limit - have)
    n = 0
    for line in FileReplaySource(path, limit=lim, skip=have):
        broker.produce(topic, line)
        n += 1
    if have and n:
        print(f"# topic '{topic}' already held {have} records (interrupted "
              f"produce?); resumed {path} from record {have} (+{n})",
              file=sys.stderr)
    elif have:
        print(f"# topic '{topic}' already holds {have} records; NOT "
              f"re-producing {path} (restart detected — consumption resumes "
              "from the group's committed offset)", file=sys.stderr)
    else:
        print(f"# produced {n} records from {path} -> topic '{topic}'",
              file=sys.stderr)


# the operator families whose window-mode pipelines run records through the
# shared event-time WindowAssembler — eligible for window-aligned offset
# commits and the marker-keyed output sink (apps/deser have bespoke result
# shapes and commit only on full drain)
_KAFKA_WINDOWED_FAMILIES = ("range", "knn", "join", "tfilter", "trange",
                            "tstats", "taggregate", "tjoin", "tknn")


@dataclass
class _KafkaWiring:
    """The driver's broker-backed I/O: sources (+ commit taps), the
    marker-keyed window sink, the plain record sink, and the latency topic
    (reference topology: ``StreamingJob.java:473,512`` +
    ``HelperClass.java:455-529``)."""

    broker: object
    stream1: Iterable
    stream2: Optional[Iterable]
    sources: List
    taps: List
    win_sink: Optional[object]
    plain_sink: object
    latency_topic: str
    group: str
    #: for realtime single-stream cases: commit position minus this lag on
    #: every emitted result — any record more than pipeline_depth+1
    #: micro-batches behind the read head is in a long-emitted batch, so a
    #: restart reprocesses a bounded tail instead of the whole topic
    commit_lag: Optional[int] = None
    #: degradation counters at wiring time: the summary reports the DELTA,
    #: so a later in-process run doesn't inherit an earlier run's chaos/
    #: retry/dlq counts (the registry is process-global)
    deg_baseline: Optional[dict] = None

    def emit(self, result) -> None:
        """Produce one pipeline result, then advance window-aligned commits
        (produce-before-commit is the at-least-once ordering)."""
        suppressed = False
        if isinstance(result, WindowResult) and self.win_sink is not None:
            before = self.win_sink.duplicates_suppressed
            self.win_sink.emit(result)
            suppressed = self.win_sink.duplicates_suppressed > before
            for tap in self.taps:
                tap.on_window_emitted(result.window_end)
        elif isinstance(result, WindowResult):
            for rec in result.flat_records():
                self.plain_sink.emit(rec)
        elif (isinstance(result, tuple) and len(result) == 2
                and isinstance(result[0], SpatialObject)):
            # deser-family (obj, serialized) conformance pairs
            self.plain_sink.emit(result[0])
        else:
            self.plain_sink.emit(result)
        lats = (result.extras.get("latency_ms")
                if isinstance(result, WindowResult) else None)
        if lats and not suppressed:
            # a window the sink suppressed as a re-delivered duplicate must
            # not double its latency samples either (and restart-time
            # re-deliveries would skew the distribution upward)
            for v in lats:
                self.broker.produce(self.latency_topic, v)
        if self.commit_lag is not None:
            for src in self.sources:
                src.commit_to(max(0, src.position - self.commit_lag))

    def finish(self) -> None:
        """Bounded input fully drained + flushed: every consumed record is
        reflected in produced output, so the full positions commit. NOT
        called on a control-tuple stop or crash — the conservative
        window-aligned commits stand, and restart re-delivers."""
        tapped = {id(t.source) for t in self.taps}
        for tap in self.taps:
            tap.commit_all()
        for src in self.sources:
            if id(src) not in tapped:
                src.commit_to(src.position)

    def summary(self) -> str:
        from spatialflink_tpu.utils.metrics import degradation_snapshot

        parts = []
        if self.win_sink is not None:
            parts.append(f"{self.win_sink.windows_produced} windows produced"
                         f" (+{self.win_sink.duplicates_suppressed} "
                         "re-delivered suppressed)")
        parts.append("committed " + ", ".join(
            f"{s.topic}@{s.broker.committed(s.topic, s.group)}"
            for s in self.sources))
        base = self.deg_baseline or {}
        deg = {k: v - base.get(k, 0) for k, v in
               degradation_snapshot().items() if v > base.get(k, 0)}
        if deg:
            # injected faults + recovery activity (retries, breaker trips,
            # verified produces, dead-lettered records) THIS run — the
            # "how rough was the transport" digest
            parts.append("degraded: " + ", ".join(
                f"{k}={v}" for k, v in sorted(deg.items())))
        return "# kafka: " + "; ".join(parts)


def _wire_kafka(params: Params, spec: CaseSpec, args, skip1: int
                ) -> _KafkaWiring:
    from spatialflink_tpu.streams.kafka import (KafkaSink, KafkaSource,
                                                KafkaWindowSink,
                                                WindowCommitTap,
                                                resolve_broker)

    from spatialflink_tpu.utils.metrics import degradation_snapshot

    bootstrap = args.kafka_bootstrap or params.kafka_bootstrap_servers
    group = args.kafka_group
    chaos_spec = getattr(args, "chaos", None)
    retry_spec = getattr(args, "retry", None)
    use_dlq = bool(getattr(args, "dlq", False))
    deg_baseline = degradation_snapshot()
    t1, t2 = params.input1.topic_name, params.input2.topic_name
    windowed = (spec.mode == "window" and params.window.type != "COUNT"
                and spec.family in _KAFKA_WINDOWED_FAMILIES)
    commit_lag = None
    if spec.mode == "realtime" and spec.family in ("range", "knn"):
        # stateless single-stream micro-batches: a lagged commit bounds
        # restart reprocessing (join's rolling buffer and the stateful
        # trajectory/app cases keep end-only commits — their records stay
        # live past their own batch)
        qc = _query_conf(params, spec)
        commit_lag = (max(1, qc.pipeline_depth) + 1) * qc.realtime_batch_size
    # validate BEFORE any broker side effect (a rejected command must not
    # leave records on a shared cluster's input topic)
    if args.kafka_follow and not windowed and commit_lag is None and not (
            args.checkpoint and spec.family in ("tstats", "taggregate")):
        raise ValueError(
            "--kafka-follow needs a case with incremental commit support "
            "(event-time windowed families, realtime range/kNN, or "
            "checkpointed tStats/tAggregate with --checkpoint): an "
            "unbounded run of this case would never advance the group "
            "offset and a restart would reprocess the entire topic")

    broker = resolve_broker(bootstrap)
    if chaos_spec is not None:
        # fault injection UNDER the supervisor, so the recovery machinery
        # (not the pipeline) eats the injected faults — the layering a real
        # flaky cluster imposes
        from spatialflink_tpu.runtime.faults import ChaosBroker, FaultPlan

        broker = ChaosBroker(broker, FaultPlan.from_spec(chaos_spec))
    if retry_spec is not None:
        from spatialflink_tpu.runtime.supervisor import SupervisedBroker

        broker = SupervisedBroker.from_spec(broker, retry_spec)
    # bounded replay THROUGH the broker: file records become topic records
    if args.input1:
        _preproduce(broker, t1, args.input1, args.limit)
    if args.input2:
        _preproduce(broker, t2, args.input2, args.limit)
    # a checkpointed resume seeks the group past the records the saved state
    # already reflects — the file path's skip, as an offset commit (commit
    # is monotone, so an older checkpoint can never rewind the group)
    if skip1:
        broker.commit(t1, group, skip1)
    coord = getattr(params, "checkpointer", None)
    if coord is not None and retry_spec is not None:
        # carry the circuit breaker across restarts: a resume into a still-
        # degraded transport starts with the checkpointed failure history
        # instead of re-learning the outage from scratch
        coord.register("supervisor", lambda: ({}, broker.snapshot()),
                       lambda _arrays, meta: broker.restore(meta))
    if coord is not None and coord.restored:
        from spatialflink_tpu.utils import telemetry as _telemetry

        depth = 0
        for topic in dict.fromkeys([t1, t2]):
            pos = coord.position(f"kafka:{topic}", 0)
            if pos:
                broker.commit(topic, group, pos)
                depth += max(0, broker.end_offset(topic) - pos)
        print(f"# resume: consumer group sought to checkpointed offsets; "
              f"{depth} records past the checkpoint to (re)process",
              file=sys.stderr)
        tel = _telemetry.active()
        if tel is not None:
            tel.gauge("recovery.replay-depth").set(depth)
    follow = bool(args.kafka_follow)
    u_grid, q_grid = params.grids()
    size_ms, step_ms = params.window_ms()
    geom1 = spec.stream if spec.family in ("range", "knn", "join") \
        else "Point"
    geom2 = spec.query if spec.family == "join" else "Point"
    # point streams batch the decode through the native bulk parser; in
    # live (follow) mode the source's starvation sentinel bounds the chunk
    # buffering latency to one poll cycle, and a smaller chunk keeps the
    # per-flush work short
    two_stream = (spec.family in ("join", "tjoin")
                  or (spec.family == "staytime" and spec.query == "Polygon"))
    bulk1 = (_kafka_bulk_decode(params.input1, u_grid)
             if windowed and geom1 == "Point" else None)
    bulk2 = (_kafka_bulk_decode(params.input2, q_grid)
             if windowed and two_stream and geom2 == "Point" else None)
    # both modes seed at the measured 2048-4096 throughput/latency knee
    # (the old follow default of 512 sat on the wrong side of it — 20-50%
    # p99 on the table); the chunk governor, when installed, owns the
    # size from that starting point via its per-flush callback
    chunk = _governed_chunk(_decode_chunk_env(2048))
    # --limit bounds THIS run's consumption per stream (from the group's
    # resume point), mirroring the file path's record bound. Follow mode
    # ALWAYS sets the starvation sentinel on windowed sources: the commit
    # tap's chunk hand-off (native decode or record-mode batching) flushes
    # on it, so chunking never adds more than one poll cycle of latency.
    src1 = KafkaSource(broker, t1, group, auto_commit=False,
                       stop_at_end=not follow, limit=args.limit,
                       starvation_sentinel=follow and windowed,
                       commit_lag=commit_lag)
    sources = [src1]
    src2 = None
    if two_stream:
        src2 = KafkaSource(broker, t2, group, auto_commit=False,
                           stop_at_end=not follow, limit=args.limit,
                           starvation_sentinel=follow and windowed,
                           commit_lag=commit_lag)
        sources.append(src2)

    out = params.output.topic_name
    dlq = None
    if use_dlq and not windowed:
        # the quarantine hook lives in the windowed commit tap's parse
        # stage; realtime/app/deser cases parse inside their pipelines and
        # a poison record still raises — say so instead of silently
        # accepting a flag that protects nothing
        print("warning: --dlq applies to event-time windowed --kafka "
              "cases only; this case parses in-pipeline and poison "
              "records will still fail the run", file=sys.stderr)
    elif use_dlq:
        from spatialflink_tpu.runtime.supervisor import DeadLetterQueue

        dlq = DeadLetterQueue(broker, out + "-dlq")
    taps: List = []
    stream1: Iterable = src1
    stream2: Optional[Iterable] = src2
    if windowed:
        stream1 = WindowCommitTap(src1, size_ms, step_ms,
                                  parse=_parse_fn(params.input1, u_grid,
                                                  geom1),
                                  bulk_decode=bulk1, bulk_chunk=chunk,
                                  dlq=dlq, checkpointer=coord)
        taps.append(stream1)
        if src2 is not None:
            stream2 = WindowCommitTap(src2, size_ms, step_ms,
                                      parse=_parse_fn(params.input2, q_grid,
                                                      geom2),
                                      bulk_decode=bulk2, bulk_chunk=chunk,
                                      dlq=dlq, checkpointer=coord)
            taps.append(stream2)
    elif coord is not None:
        # non-windowed (realtime) supported cases: a pass-through tap
        # reports the live source position at each record hand-off, so
        # coordinated checkpoints can seek the group on resume
        from spatialflink_tpu.runtime.checkpoint import CheckpointTap

        stream1 = CheckpointTap(src1, coord, f"kafka:{t1}",
                                position_fn=lambda: src1.position)
        if src2 is not None:
            stream2 = CheckpointTap(src2, coord, f"kafka:{t2}",
                                    position_fn=lambda: src2.position)

    sink_kw = dict(fmt=args.output_format,
                   date_format=params.input1.date_format,
                   delimiter=params.output.delimiter)
    win_sink = KafkaWindowSink(broker, out,
                               job_id=params.job_fingerprint(group),
                               seed_scan_limit=getattr(
                                   args, "seed_scan_limit", None),
                               **sink_kw) if windowed else None
    return _KafkaWiring(
        broker=broker, stream1=stream1, stream2=stream2, sources=sources,
        taps=taps, win_sink=win_sink,
        plain_sink=KafkaSink(broker, out, **sink_kw),
        latency_topic=out + "-latency", group=group, commit_lag=commit_lag,
        deg_baseline=deg_baseline)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="spatialflink-tpu",
        description="TPU-native spatial stream query driver "
                    "(StreamingJob equivalent)")
    ap.add_argument("--config", required=True, help="YAML config path")
    ap.add_argument("--input1", help="newline-delimited input file for stream 1")
    ap.add_argument("--input2", help="newline-delimited input file for stream 2")
    ap.add_argument("--limit", type=int, default=None,
                    help="max records to read per stream")
    ap.add_argument("--option", type=int, default=None,
                    help="override query.option")
    ap.add_argument("--format", default=None,
                    help="override inputStream1.format (GeoJSON/WKT/CSV/TSV)")
    ap.add_argument("--format2", default=None,
                    help="override inputStream2.format (two-stream cases)")
    ap.add_argument("--checkpoint", default=None,
                    help="state checkpoint file for stateful realtime queries "
                         "(tStats): saved periodically, restored at startup")
    ap.add_argument("--checkpoint-every", type=int, default=16,
                    help="micro-batches between checkpoints (default 16); "
                         "with --checkpoint-dir, processing units (windows/"
                         "micro-batches) between coordinated checkpoints")
    ap.add_argument("--checkpoint-dir", metavar="DIR", default=None,
                    help="coordinated pipeline checkpointing: periodically "
                         "snapshot source positions, watermarks, open "
                         "window/pane buffers, pane-kernel partials, "
                         "trajectory state, and circuit-breaker state into "
                         "one atomic checksummed manifest under DIR "
                         "(retaining the last --checkpoint-retain, falling "
                         "back past corrupt ones). Resume with --resume: "
                         "sources seek to the checkpointed offsets and "
                         "re-emitted windows are suppressed (--kafka: the "
                         "marker-seeded window sink; stdout/--output: a "
                         "durable emitted-window journal in DIR) — bounded "
                         "replay, exactly-once windowed output. Realtime "
                         "results on the plain sink stay at-least-once "
                         "across a resume. Windowed + realtime range/kNN, "
                         "windowed join/trajectory, realtime tStats/"
                         "tAggregate")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest valid checkpoint from "
                         "--checkpoint-dir before running (refuses a "
                         "checkpoint written by a different query/window "
                         "config or consumer group)")
    ap.add_argument("--checkpoint-interval", type=float, default=None,
                    metavar="SECONDS",
                    help="also checkpoint when this much wall time passed "
                         "since the last one (default: batch cadence only)")
    ap.add_argument("--checkpoint-retain", type=int, default=3,
                    help="retained checkpoint manifests in --checkpoint-dir "
                         "(default 3); older ones are pruned, corrupt newest "
                         "falls back to the previous")
    ap.add_argument("--devices", type=int, default=None,
                    help="shard window batches across this many devices "
                         "(power of two; overrides query.parallelism)")
    ap.add_argument("--hosts", type=int, default=None,
                    help="outer DCN axis width: > 1 builds the 2-D "
                         "multi-host mesh (hosts x devices/hosts; overrides "
                         "query.hosts)")
    ap.add_argument("--output", default=None,
                    help="also write every result RECORD to this file, one "
                         "per line, serialized in --output-format — the "
                         "reference's output Kafka topic "
                         "(StreamingJob.java:512, Serialization.java output "
                         "schemas), as a file")
    ap.add_argument("--output-format", default="GeoJSON",
                    choices=["GeoJSON", "WKT", "CSV", "TSV"],
                    help="serialization for --output (spatial records; "
                         "non-spatial result tuples are written as JSON "
                         "lines)")
    ap.add_argument("--metrics", action="store_true",
                    help="print a sorted-JSON metrics snapshot (counters, "
                         "meters, degradation digest) to stderr at exit")
    ap.add_argument("--telemetry-dir", metavar="DIR", default=None,
                    help="enable structured telemetry: per-stage spans "
                         "(ingest/window/kernel/merge/sink), latency "
                         "histograms, watermark-lag/backlog/grid-skew "
                         "gauges, and the degradation counters, emitted as "
                         "JSONL snapshots to DIR/telemetry.jsonl (one "
                         "immediately, one per --telemetry-interval, one at "
                         "exit) plus a final Prometheus text dump "
                         "DIR/metrics.prom. Off by default — the record "
                         "loop runs uninstrumented")
    ap.add_argument("--telemetry-interval", type=float, default=5.0,
                    metavar="SECONDS",
                    help="seconds between periodic telemetry snapshots "
                         "(default 5.0); also the --live-stats digest "
                         "cadence")
    ap.add_argument("--trace-dir", metavar="DIR", default=None,
                    help="record per-window TRACE LINEAGE (first-record "
                         "ingest, assembly, pane seals, kernel dispatch, "
                         "merge/readback, emit, sink, Kafka sink commit — "
                         "stable trace ids derived from (query, "
                         "window_start), bounded ring of the last 256 "
                         "windows) and export it at exit as Chrome "
                         "trace-event JSON to DIR/trace.json — load it in "
                         "Perfetto (ui.perfetto.dev) or chrome://tracing "
                         "to scrub the run's timeline. Activates a "
                         "telemetry session; live access via the status "
                         "server's /trace/<id> and /trace/recent")
    ap.add_argument("--status-port", type=int, default=None, metavar="PORT",
                    help="serve a live in-run status plane on "
                         "127.0.0.1:PORT (0 = ephemeral, bound port "
                         "printed): GET /healthz (SLO verdict, 200/503), "
                         "/status (full JSON snapshot: throughput, latency "
                         "percentiles, watermark lag, backlogs, pane-cache "
                         "hit rate, checkpoint age/seq, breaker/DLQ state, "
                         "hottest cells), /metrics (live Prometheus text), "
                         "/events (lifecycle event ring). Snapshots are "
                         "built per request only; without a telemetry "
                         "session (--telemetry-dir/--live-stats) the "
                         "record loop stays byte-identical and the plane "
                         "serves the always-on registry counters")
    ap.add_argument("--live-stats", action="store_true",
                    help="print a one-line pipeline digest (throughput, "
                         "windows, latency p99, watermark lag, backlog, "
                         "checkpoint age, breaker/DLQ/degradation, health) "
                         "to stderr every --telemetry-interval seconds; "
                         "activates a telemetry session. Automatic in "
                         "--kafka-follow runs that already have one")
    ap.add_argument("--slo", metavar="SPEC", default=None,
                    help="health/SLO thresholds as comma-joined key=value "
                         "pairs, e.g. 'watermark_lag_ms=5000,"
                         "p99_window_ms=250,commit_backlog=10000,"
                         "checkpoint_age_s=60,recompiles=0,"
                         "device_mem_bytes=8e9'. Drives /healthz (503 on "
                         "breach), stamps a 'health' verdict into every "
                         "telemetry snapshot and digest line, counts "
                         "breach transitions in the slo-breaches counter, "
                         "and emits slo-breach/slo-recovered (and "
                         "watermark-stall) lifecycle events")
    ap.add_argument("--postmortem-dir", metavar="DIR", default=None,
                    help="arm the flight recorder: a bounded ring of run "
                         "lifecycle notes that dumps a post-mortem bundle "
                         "directory (status snapshot, event ring, compile "
                         "registry, recent window traces, device memory "
                         "profile, config fingerprint) to DIR on crash, "
                         "first SLO breach, strict-recompile abort, or "
                         "SIGUSR1 — read it with 'python -m "
                         "spatialflink_tpu.doctor summarize/diff'. "
                         "Activates a telemetry session")
    ap.add_argument("--strict-recompile", action="store_true",
                    help="abort the run (exit 3, post-mortem bundle if "
                         "--postmortem-dir) when any XLA kernel compiles "
                         "AFTER the declared warmup — the PR 8/9 "
                         "zero-recompile contracts as a hard production "
                         "invariant instead of a test-time assert. "
                         "Observational without this flag: post-warmup "
                         "compiles still count ('device-recompiles', "
                         "'recompile' events, GET /compile)")
    ap.add_argument("--sentinel-warmup", type=int, default=1,
                    metavar="WINDOWS",
                    help="recompile-sentinel warmup: compiles stop being "
                         "expected after this many emitted windows "
                         "(default 1). Streams whose batch sizes keep "
                         "growing into fresh padding buckets late in the "
                         "run may need a larger value before "
                         "--strict-recompile is safe")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="capture a jax.profiler trace of the run to DIR "
                         "(TensorBoard/XProf format) with per-operator "
                         "dispatch/readback annotations — the reference's "
                         "Flink web UI observability as a trace "
                         "(StreamingJob.java:70-72)")
    ap.add_argument("--pane-merge", choices=["auto", "device", "host"],
                    default=None,
                    help="where --panes partials live and merge: 'device' "
                         "keeps pane kernel partials resident in device "
                         "memory across slides and merges each sealed "
                         "window ON DEVICE (one merged readback per window "
                         "— kNN families; filter families keep their "
                         "already-optimal host union), 'host' resolves "
                         "each partial to host and merges there, 'auto' "
                         "(default) picks device on accelerator backends "
                         "(a per-pane host sync is a full dispatch RTT "
                         "there) and host on CPU (measured faster — the "
                         "pane-state bench rows are the A/B)")
    ap.add_argument("--panes", action="store_true",
                    help="pane-incremental sliding windows: buffer records "
                         "into non-overlapping slide-aligned panes, run the "
                         "device kernel once per sealed pane, and assemble "
                         "each window by merging its size/slide cached pane "
                         "partials — at overlap o the per-slide kernel work "
                         "drops ~o-fold. Results are identical to "
                         "full-window evaluation; tumbling windows and "
                         "specs whose slide does not divide the size bypass "
                         "the cache (pane-cache-hits/-misses counters show "
                         "the reuse rate)")
    ap.add_argument("--queries-file", metavar="PATH", default=None,
                    help="activate the DYNAMIC standing-query plane seeded "
                         "from a JSON file of query specs ([{'id', 'x', "
                         "'y', optional 'radius'/'k'/'route'/'slo'}, ...] "
                         "or {'queries': [...]}): the fleet batches onto "
                         "the device Q-axis (padded to power-of-two size "
                         "buckets so admissions repad instead of "
                         "recompiling) and queries are admitted/updated/"
                         "retired MID-RUN via POST/DELETE /queries on "
                         "--status-port and/or a --control-topic, with "
                         "per-query counters, routes (stdout/file:/"
                         "kafka:), SLO verdicts, GET /queries, and a "
                         "'queries' slot in coordinated checkpoints so "
                         "--resume restores the live fleet. Windowed "
                         "point-query range (all stream types) and "
                         "Point/Point kNN")
    ap.add_argument("--control-topic", metavar="TOPIC", default=None,
                    help="with --kafka: also consume JSON admit/update/"
                         "retire control records for the standing-query "
                         "plane from TOPIC ({'action': 'admit', 'query': "
                         "{...}} / {'action': 'retire', 'id': ...}), "
                         "applied at window boundaries (activates the "
                         "dynamic plane like --queries-file; both may be "
                         "used together)")
    ap.add_argument("--controller", metavar="SPEC", nargs="?", const="",
                    default=None,
                    help="closed-loop decode-chunk governor: tick on the "
                         "telemetry-reporter cadence, read the live stage "
                         "budget, and resize the decode chunk one "
                         "power-of-two bucket at a time between flushes — "
                         "shrink when queue/buffer wait dominates and the "
                         "record→emit p99 breaches, grow when dispatch-"
                         "bound or idle; never recompiles. SPEC tunes the "
                         "policy as comma key=value pairs over "
                         "target_p99_ms/min_chunk/max_chunk/"
                         "interactive_max_chunk/fast_lane_depth/"
                         "confirm_ticks/cooldown_ticks/shed_after_stalls/"
                         "unshed_after_clean/idle_headroom (bare "
                         "--controller = defaults). Needs a telemetry "
                         "session (--telemetry-dir/--live-stats/"
                         "--status-port/...) for the tick source; live "
                         "state in the controller block of GET /latency "
                         "and the stderr digest")
    ap.add_argument("--latency-class", choices=["interactive", "batch"],
                    default="batch", dest="latency_class",
                    help="latency class for this run's standing queries "
                         "(default batch; also the default class for "
                         "--queries-file/--control-topic admissions that "
                         "omit 'latency_class'). While any interactive "
                         "query serves, the --controller fast lane caps "
                         "the decode chunk at interactive_max_chunk and "
                         "bounds the pipeline queue depth to "
                         "fast_lane_depth so interactive emits never park "
                         "behind throughput amortization")
    ap.add_argument("--tenant-default", metavar="NAME", default="default",
                    dest="tenant_default",
                    help="tenant charged for queries that omit 'tenant' "
                         "(and for dispatch cost no standing query claims, "
                         "e.g. static single-query runs). Per-tenant "
                         "attributed kernel-ms/bytes, records, windows, "
                         "SLO/shed/quota counters and the fairness summary "
                         "serve at GET /tenants (+ /tenants/<id>, "
                         "tenant=\"T\" Prometheus labels, /fleet/tenants "
                         "on the supervisor); attribution splits each "
                         "measured dispatch across live fleet slots by "
                         "candidate work and sums to the measured span by "
                         "construction")
    ap.add_argument("--tenant-quota", metavar="SPEC", action="append",
                    default=None, dest="tenant_quota",
                    help="admission quota per tenant as "
                         "'T:max_active[,kernel_ms_s=X]' (repeatable, or "
                         "';'-separated). max_active caps the tenant's "
                         "held query slots (pending+active+draining+shed); "
                         "kernel_ms_s caps its recent attributed kernel-ms "
                         "per second. A breach answers POST /queries with "
                         "429 quota-exceeded and creates NO entry — unlike "
                         "backpressure shedding, which parks the spec and "
                         "auto-admits when pressure clears")
    ap.add_argument("--multi-query", action="store_true",
                    help="answer ALL configured query points/geometries in "
                         "one dispatch per window (run_multi; default keeps "
                         "reference parity: first query object only). "
                         "All nine range and kNN pairs, plus trajectory kNN")
    ap.add_argument("--adaptive-grid", nargs="?", const=4, type=int,
                    default=None, metavar="K", dest="adaptive_grid",
                    help="skew-adaptive grid: refine hot cells KxK (default "
                         "K=4) and coarsen cold neighborhoods, with "
                         "epoch-based split/merge decisions driven by the "
                         "live occupancy gauges (and per-cell attributed "
                         "cost when telemetry is on). Records keep their "
                         "base cells and device kernels are untouched; the "
                         "refined GN∪CN leaf masks gate window-batch "
                         "membership host-side before the kernel, so "
                         "exact-mode results are identical to the uniform "
                         "grid and the win is the smaller batch on skewed "
                         "streams (single-query range family; layout "
                         "served at /partition, carried in coordinated "
                         "checkpoints)")
    ap.add_argument("--repartition-interval", type=int, default=50_000,
                    metavar="N",
                    help="records per repartition epoch for "
                         "--adaptive-grid (default 50000): each epoch "
                         "re-evaluates split/merge thresholds with "
                         "hysteresis (split at 5%% epoch share, merge "
                         "back below 1.25%% for 2 consecutive epochs)")
    ap.add_argument("--shard-order", choices=["arrival", "cell"],
                    default="arrival",
                    help="mesh shard placement for distributed window "
                         "batches: 'arrival' (default) shards contiguously; "
                         "'cell' pre-permutes each batch so whole grid "
                         "cells co-locate per shard (keyBy(gridID) parity, "
                         "parallel.mesh.cell_hash_order) — results are "
                         "identical; BASELINE.md records the measured "
                         "verdict (the host permute usually costs more "
                         "than the kernel saving)")
    ap.add_argument("--kafka", action="store_true",
                    help="consume inputStream{1,2}.topicName and produce "
                         "results to outputStream.topicName through the "
                         "broker named by kafkaBootStrapServers "
                         "('memory://<name>' = the in-process shim; anything "
                         "else = a real cluster via kafka-python) — the "
                         "reference's FlinkKafkaConsumer/Producer topology "
                         "(StreamingJob.java:473,512). --input1/--input2 "
                         "files, when given, are pre-produced to the input "
                         "topics first (bounded replay through the broker)")
    ap.add_argument("--kafka-group", default="spatialflink",
                    help="consumer group id (restart resumes from the "
                         "group's committed offsets; default 'spatialflink')")
    ap.add_argument("--kafka-bootstrap", default=None,
                    help="override kafkaBootStrapServers from the config")
    ap.add_argument("--kafka-follow", action="store_true",
                    help="live mode: keep polling past the current end of "
                         "the input topic instead of stopping (a producer "
                         "feeds the topic concurrently; stop with the "
                         "control tuple)")
    ap.add_argument("--chaos", metavar="SPEC", default=None,
                    help="fault-inject the broker transport from a seeded "
                         "deterministic plan: comma-joined key=value pairs "
                         "over seed / produce_fail / ack_lost / fetch_fail "
                         "/ duplicate / reorder / torn / latency(+_ms) / "
                         "fail_next_produces / fail_next_fetches, e.g. "
                         "'seed=7,fetch_fail=0.2,torn=0.1'. Pair with "
                         "--retry (and --dlq for torn payloads) or the "
                         "injected faults will crash the run — that "
                         "contrast is the point")
    ap.add_argument("--retry", metavar="SPEC", nargs="?", const="",
                    default=None,
                    help="supervise broker produce/fetch with retry + "
                         "backoff + a circuit breaker (idempotent produce "
                         "retries: ambiguous failures re-check the log "
                         "before re-sending). Optional SPEC tunes it: "
                         "attempts / base_ms / max_ms / multiplier / "
                         "jitter / attempt_timeout_ms / deadline_ms / "
                         "seed / breaker_threshold / cooldown_ms")
    ap.add_argument("--dlq", action="store_true",
                    help="quarantine poison records (parse failures that "
                         "survive redelivery) to '<outputTopic>-dlq' with "
                         "failure metadata instead of crashing the "
                         "pipeline (windowed --kafka cases)")
    ap.add_argument("--seed-scan-limit", type=int, default=None,
                    metavar="N",
                    help="bound the output-topic dedup seed scan to the "
                         "last N records (default: full scan; the scan "
                         "warns when an uncompacted topic makes it large) "
                         "— accepts that windows committed before the "
                         "scanned tail can be re-produced on re-delivery")
    ap.add_argument("--fleet", type=int, default=None, metavar="N",
                    help="supervised multi-worker fleet: spawn N full "
                         "worker pipelines (each with its own checkpoint "
                         "manifest and opserver), partition --input1 by "
                         "grid leaf, restart dead workers from their "
                         "latest checkpoint, and merge the windowAll "
                         "results exactly-once (windowed range/kNN file "
                         "replays; aggregated view at GET /fleet)")
    ap.add_argument("--fleet-dir", metavar="DIR", default=None,
                    help="fleet working directory: per-worker partitions, "
                         "outboxes, logs, checkpoints, the fleet manifest, "
                         "and the merged result (required with --fleet; "
                         "inspect with python -m spatialflink_tpu.doctor "
                         "fleet DIR)")
    ap.add_argument("--fleet-role", choices=["supervisor", "worker"],
                    default=None,
                    help="process role under --fleet (workers are spawned "
                         "by the supervisor with this set; not for direct "
                         "use)")
    ap.add_argument("--fleet-worker-id", type=int, default=0,
                    metavar="ID", help="this worker's id (supervisor-set)")
    ap.add_argument("--fleet-heartbeat", type=float, default=1.0,
                    metavar="SECONDS",
                    help="worker heartbeat interval; the supervisor "
                         "declares a worker dead after ~5 missed beats "
                         "(default: 1.0)")
    ap.add_argument("--fleet-epoch-records", type=int, default=20000,
                    metavar="N",
                    help="repartition epoch length in routed records: at "
                         "each boundary the supervisor compares worker "
                         "backpressure and may move leaves off the hottest "
                         "worker (default: 20000)")
    ap.add_argument("--fleet-restart-cap", type=int, default=3,
                    metavar="N",
                    help="max restarts per worker before the fleet aborts "
                         "(default: 3)")
    ap.add_argument("--fleet-slo-p99-ms", type=float, default=None,
                    metavar="MS",
                    help="optional SLO supervision: restart a worker whose "
                         "record->emit p99 stays above MS for 3 "
                         "consecutive polls (default: off)")
    ap.add_argument("--fleet-chaos-kill", metavar="WID:N", default=None,
                    help="fault-injection hook: SIGKILL worker WID once "
                         "its outbox holds N windows (recovery tests and "
                         "the fault bench row)")
    ap.add_argument("--fleet-plane", choices=("on", "off"), default="on",
                    help="fleet observability plane: end-to-end "
                         "record->merged-emit lineage, the merged event "
                         "timeline, /fleet/latency|timeline|events|metrics "
                         "federation, and fleet post-mortem snapshots; "
                         "'off' disables retention and the outbox lineage "
                         "sidecar — the merged digest is identical either "
                         "way (default: on)")
    ap.add_argument("--fleet-rescale", metavar="AT:N[,AT:N...]",
                    default=None,
                    help="live rescale: once AT records have been routed, "
                         "scale the fleet to N workers at the next epoch "
                         "boundary (coordinated flush barrier, leaf "
                         "reassignment, fenced worker ids; e.g. "
                         "'10000:3,20000:2' runs 2->3->2); the merged "
                         "digest is identical to a fixed-N run")
    ap.add_argument("--fleet-chaos-stall", metavar="WID:SECONDS",
                    default=None,
                    help="fault-injection hook: worker WID's first "
                         "incarnation wedges heartbeat+checkpoints for "
                         "SECONDS after its first window while continuing "
                         "to write (gray-failure drill: the supervisor "
                         "fences+respawns it WITHOUT a kill; the zombie's "
                         "stale rows must be dropped at merge)")
    ap.add_argument("--fleet-quarantine-s", type=float, default=10.0,
                    metavar="S",
                    help="gray-failure quarantine deadline: a worker whose "
                         "suspicion score stays high is first drained of "
                         "new leaf routes (still merging its output), then "
                         "fenced+respawned after S seconds without "
                         "recovery (default: 10)")
    ap.add_argument("--fleet-fence", type=int, default=0,
                    metavar="TOKEN",
                    help=argparse.SUPPRESS)  # supervisor-issued fence
    ap.add_argument("--fleet-stall-s", type=float, default=0.0,
                    metavar="S",
                    help=argparse.SUPPRESS)  # chaos glue, supervisor-set
    args = ap.parse_args(argv)

    enable_compilation_cache()
    params = Params.from_yaml(args.config)
    if args.option is not None:
        params.query.option = args.option
    if args.multi_query:
        params.query.multi_query = True
    if args.panes:
        params.query.panes = True
    if args.pane_merge is not None and args.pane_merge != "auto":
        params.query.pane_device_merge = args.pane_merge == "device"
    if args.devices is not None:
        params.query.parallelism = args.devices
    if args.hosts is not None:
        params.query.hosts = args.hosts
    try:
        params.validate_mesh()
    except Exception as e:
        ap.error(str(e))
    if args.format is not None or args.format2 is not None:
        import dataclasses

        i1 = (dataclasses.replace(params.input1, format=args.format)
              if args.format is not None else params.input1)
        i2 = (dataclasses.replace(params.input2, format=args.format2)
              if args.format2 is not None else params.input2)
        params = dataclasses.replace(params, input1=i1, input2=i2)
    if args.checkpoint:
        params.checkpoint_path = args.checkpoint
        params.checkpoint_every = args.checkpoint_every
        # the job fingerprint rides the checkpoint meta so a resume under a
        # DIFFERENT query/window config is refused instead of silently
        # producing wrong state (the old silent-footgun UX)
        params.checkpoint_job = params.job_fingerprint(args.kafka_group)
        cp_spec = CASES.get(params.query.option)
        if cp_spec and not (cp_spec.family in ("tstats", "taggregate")
                            and cp_spec.mode == "realtime"):
            print("--checkpoint only applies to stateful realtime queries "
                  "(tStats 205 / tAggregate 207); ignored for this case",
                  file=sys.stderr)
        elif os.path.exists(args.checkpoint):
            # pre-flight: fail at arg-parse time with the SAME shared guard
            # the restore path enforces (fast, before any broker/source
            # side effect)
            from spatialflink_tpu.runtime.checkpoint import (
                CheckpointMismatch, check_job_fingerprint)
            from spatialflink_tpu.runtime.state import (CheckpointCorrupt,
                                                        checkpoint_meta)

            try:
                check_job_fingerprint(
                    checkpoint_meta(args.checkpoint).get("job"),
                    params.checkpoint_job, args.checkpoint)
            except CheckpointCorrupt as e:
                ap.error(f"--checkpoint: {e} (delete the file, or restore "
                         "a retained copy, to start over)")
            except CheckpointMismatch as e:
                ap.error(str(e))

    spec = CASES.get(params.query.option)
    if spec is None:
        print(f"unknown queryOption {params.query.option}", file=sys.stderr)
        return 2
    if args.fleet is not None and args.fleet_role != "worker":
        # supervised multi-worker fleet: validate here (argparse-grade
        # errors), then hand the whole run to the supervisor — workers
        # re-enter main() as plain single-process pipelines
        if args.fleet < 1:
            ap.error("--fleet needs N >= 1 workers")
        if not args.fleet_dir:
            ap.error("--fleet requires --fleet-dir (worker partitions, "
                     "outboxes, and the fleet manifest live there)")
        if args.kafka or not args.input1:
            ap.error("--fleet partitions a file replay and needs "
                     "--input1 (kafka transport stays single-process)")
        if spec.mode != "window" or spec.family not in ("range", "knn"):
            ap.error("--fleet supports windowed range/kNN cases (the "
                     "windowAll merge families); option "
                     f"{params.query.option} is {spec.family}/{spec.mode}")
        if params.query.multi_query:
            ap.error("--fleet does not compose with --multi-query")
        if args.queries_file or args.control_topic:
            ap.error("--fleet does not compose with the dynamic query "
                     "plane (each worker runs the static configured "
                     "query)")
        if args.adaptive_grid is not None:
            ap.error("--fleet owns the leaf placement layout; "
                     "--adaptive-grid inside workers does not compose")
        from spatialflink_tpu.runtime import fleetsup

        base_argv = list(sys.argv[1:] if argv is None else argv)
        return fleetsup.run_supervisor(args, params, spec, base_argv)
    if args.fleet_role == "worker" and not (
            args.fleet_dir and args.input1 and args.checkpoint_dir):
        ap.error("--fleet-role worker needs --fleet-dir, --input1 and "
                 "--checkpoint-dir (workers are spawned by the "
                 "supervisor, not launched directly)")
    # the dynamic standing-query plane (validated/constructed below, after
    # the checkpointer exists); the flag participates in the checkpoint
    # LAYOUT tag — a dynamic run's manifest carries a 'queries' component a
    # static run could never restore
    dynamic_queries = bool(args.queries_file or args.control_topic)
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint-dir")
    if args.checkpoint_dir:
        if args.checkpoint:
            ap.error("--checkpoint-dir and --checkpoint are mutually "
                     "exclusive (the directory coordinator subsumes the "
                     "single-file tStats/tAggregate checkpoint)")
        reason = _checkpoint_dir_unsupported(params, spec)
        if reason:
            print(f"--checkpoint-dir ignored: {reason}", file=sys.stderr)
        else:
            from spatialflink_tpu.runtime.checkpoint import (
                CheckpointCoordinator, CheckpointMismatch)

            # source identity: the sink-dedup job fingerprint deliberately
            # excludes transport/source (a sharded or re-encoded re-run must
            # dedup against the original's markers), but a CHECKPOINT is
            # bound to the exact source its positions index into — resuming
            # against a different file/topic/broker would seek into records
            # that were never processed
            if args.kafka:
                src_id = ("kafka:" + (args.kafka_bootstrap
                                      or params.kafka_bootstrap_servers)
                          + f"/{params.input1.topic_name}"
                          + f",{params.input2.topic_name}")
            else:
                src_id = f"file:{args.input1},{args.input2}"
            coord = CheckpointCoordinator(
                args.checkpoint_dir,
                every_batches=args.checkpoint_every,
                every_seconds=args.checkpoint_interval,
                retain=args.checkpoint_retain,
                job=params.job_fingerprint(args.kafka_group),
                # execution knobs the job fingerprint deliberately excludes
                # but the manifest's component layout + positions depend on
                layout=(f"{spec.family}:{spec.mode}"
                        f":panes={int(bool(params.query.panes))}"
                        f":multi={int(bool(params.query.multi_query))}"
                        f":dyn={int(dynamic_queries)}"
                        f":{src_id}"))
            if args.resume:
                try:
                    restored = coord.load()
                except CheckpointMismatch as e:
                    ap.error(str(e))
                if restored:
                    print(f"# resuming from checkpoint seq {coord.seq} "
                          f"(source positions: {coord.positions() or '{}'})",
                          file=sys.stderr)
                else:
                    print("# --resume: no valid checkpoint in "
                          f"{args.checkpoint_dir}; starting fresh",
                          file=sys.stderr)
            # dynamic attribute (not a dataclass field): the coordinator
            # must not leak into Params.to_dict()/fingerprints
            params.checkpointer = coord
    if args.shard_order != "arrival":
        params.shard_order = args.shard_order
    if args.adaptive_grid is not None:
        from spatialflink_tpu.index import AdaptiveGrid
        from spatialflink_tpu.runtime.repartition import (
            RepartitionController)

        try:
            agrid = AdaptiveGrid(params.grids()[0],
                                 refine=args.adaptive_grid)
        except ValueError as e:
            ap.error(f"--adaptive-grid: {e}")
        ctl = RepartitionController(
            agrid, interval_records=args.repartition_interval)
        coord = getattr(params, "checkpointer", None)
        if coord is not None:
            # grid layout rides the coordinated manifest: --resume
            # restores the adapted partitioning (auto-applied here if
            # the coordinator already loaded one)
            ctl.register_checkpoint(coord)
        # dynamic attributes (not dataclass fields), like checkpointer:
        # must not leak into Params.to_dict()/fingerprints
        params.adaptive_grid = agrid
        params.repartitioner = ctl
        print(f"# adaptive grid: hot cells split "
              f"{args.adaptive_grid}x{args.adaptive_grid}, repartition "
              f"epoch every {args.repartition_interval} records "
              "(layout at /partition)", file=sys.stderr)
    # tenant quotas parse up front: a malformed SPEC is a flag error, not a
    # mid-run surprise at first admission
    tenant_quotas = {}
    if getattr(args, "tenant_quota", None):
        from spatialflink_tpu.utils.accounting import parse_tenant_quotas

        try:
            tenant_quotas = parse_tenant_quotas(";".join(args.tenant_quota))
        except ValueError as e:
            ap.error(f"--tenant-quota: {e}")
    if dynamic_queries:
        from spatialflink_tpu.runtime.queryplane import (QueryRegistry,
                                                         QuerySpec,
                                                         QuerySpecError,
                                                         load_queries_file)

        if args.control_topic and not args.kafka:
            ap.error("--control-topic consumes admissions from the broker "
                     "and needs --kafka")
        if (spec.family not in ("range", "knn") or spec.query != "Point"
                or (spec.family == "knn" and spec.stream != "Point")):
            ap.error("--queries-file/--control-topic (the dynamic "
                     "standing-query plane) serve point-query fleets: "
                     "windowed range over any stream type, and Point/Point "
                     f"kNN — not queryOption {params.query.option} "
                     f"({spec.family}, {spec.stream}x{spec.query})")
        if spec.mode != "window" or params.window.type == "COUNT":
            ap.error("the dynamic standing-query plane runs event-time "
                     "windowed cases only (the fleet changes at window "
                     "boundaries)")
        if spec.latency:
            ap.error("the dynamic standing-query plane does not combine "
                     "with the latency variants (per-record latency "
                     "assumes single-query record lists)")
        if params.query.multi_query:
            ap.error("--multi-query is subsumed by the query registry "
                     "(the live fleet IS the multi-query set); drop the "
                     "flag")
        if params.query.panes:
            print("note: --panes is bypassed on the dynamic standing-query "
                  "path (pane partials are fleet-shaped; a fleet change "
                  "would serve stale partials) — full-window evaluation",
                  file=sys.stderr)
        registry = QueryRegistry(spec.family, radius=params.query.radius,
                                 k=params.query.k,
                                 default_latency_class=args.latency_class,
                                 default_tenant=args.tenant_default,
                                 tenant_quotas=tenant_quotas)
        coord = getattr(params, "checkpointer", None)
        restored = bool(coord is not None
                        and registry.register_checkpoint(coord))
        if restored:
            print(f"# resume: restored standing-query fleet "
                  f"(version {registry.fleet_version}, "
                  f"{len(registry.active_entries())} live)", file=sys.stderr)
        else:
            seeds = []
            try:
                if args.queries_file:
                    seeds = load_queries_file(
                        args.queries_file, spec.family,
                        default_latency_class=args.latency_class,
                        default_tenant=args.tenant_default)
            except (OSError, ValueError) as e:
                ap.error(f"--queries-file: {e}")
            if not seeds and params.query.query_points:
                # the config's queryPoints seed the fleet (the registry is
                # the source of truth for what runs; the static config is
                # just its time-zero admission batch)
                seeds = [QuerySpec(id=f"q{i}", family=spec.family, x=x, y=y,
                                   latency_class=args.latency_class)
                         for i, (x, y) in
                         enumerate(params.query.query_points)]
            try:
                for s in seeds:
                    registry.admit(s)
            except QuerySpecError as e:
                ap.error(f"--queries-file: {e}")
            # seeds serve from window one — dedicated-static-run parity
            registry.apply()
        # dynamic attribute, like checkpointer: must not leak into
        # Params.to_dict()/fingerprints
        params.query_registry = registry
        surfaces = ["POST/DELETE /queries (--status-port)"]
        if args.control_topic:
            surfaces.append(f"control topic '{args.control_topic}'")
        print(f"# query plane: dynamic {spec.family} fleet, "
              f"{len(registry.active_entries())} live "
              f"(admission via {' + '.join(surfaces)})", file=sys.stderr)
    if not args.kafka and (args.chaos is not None or args.retry is not None
                           or args.dlq or args.seed_scan_limit is not None):
        ap.error("--chaos/--retry/--dlq/--seed-scan-limit wrap the broker "
                 "transport and need --kafka")
    if args.kafka and spec.family in ("shapefile", "synthetic"):
        ap.error(f"--kafka does not apply to the {spec.family} cases "
                 "(no input topic)")
    if not args.input1 and not args.kafka and spec.family not in ("synthetic",):
        print("--input1 is required for this queryOption", file=sys.stderr)
        return 2
    # a resumed checkpointed run must not re-apply records the saved state
    # already reflects: the checkpoint records a consumed-record offset and
    # the file replay skips that many (a Kafka consumer group would seek)
    skip1 = 0
    if (args.checkpoint and spec.family in ("tstats", "taggregate")
            and spec.mode == "realtime"):
        from spatialflink_tpu.runtime.state import checkpoint_consumed

        skip1 = checkpoint_consumed(args.checkpoint)
        if skip1:
            print(f"# resuming from checkpoint: skipping {skip1} "
                  "already-consumed records", file=sys.stderr)

    # --limit bounds the *original* record range: a resumed run covers the
    # remainder of that range, not N additional records past the checkpoint
    limit1 = args.limit
    if skip1 and limit1 is not None:
        limit1 = max(0, limit1 - skip1)

    health = None
    if args.slo is not None:
        from spatialflink_tpu.runtime.health import HealthEvaluator

        try:
            health = HealthEvaluator.from_spec(args.slo)
        except ValueError as e:
            ap.error(str(e))
        if (args.status_port is None and not args.telemetry_dir
                and not args.live_stats):
            print("warning: --slo has no consumer without --status-port, "
                  "--telemetry-dir, or --live-stats (nothing evaluates "
                  "the thresholds)", file=sys.stderr)

    if args.controller is not None:
        from spatialflink_tpu.runtime.control import (ChunkGovernor,
                                                      GovernorPolicy)

        try:
            policy = GovernorPolicy.from_spec(args.controller)
        except ValueError as e:
            ap.error(str(e))
        # dynamic attribute, like checkpointer/query_registry: must not
        # leak into Params.to_dict()/fingerprints
        params.chunk_governor = ChunkGovernor(policy=policy)
        if not (args.telemetry_dir or args.live_stats or args.trace_dir
                or args.postmortem_dir):
            print("warning: --controller has no tick source without a "
                  "telemetry session (--telemetry-dir/--live-stats/"
                  "--trace-dir/--postmortem-dir): the latency plane's "
                  "bucket close drives the control law, so the chunk "
                  "stays at its seed", file=sys.stderr)

    if (args.telemetry_dir or args.live_stats or args.trace_dir
            or args.postmortem_dir):
        from spatialflink_tpu.utils.telemetry import telemetry_session

        # the session must wrap the KAFKA WIRING too (taps/sinks capture
        # their gauges at construction), not just the result loop.
        # --live-stats/--trace-dir/--postmortem-dir without
        # --telemetry-dir run a reporterless session (instrumentation on;
        # the digest / trace book / flight-recorder bundle are fed from
        # it)
        with telemetry_session(args.telemetry_dir or None,
                               args.telemetry_interval, health=health,
                               trace_dir=args.trace_dir):
            if args.telemetry_dir:
                print(f"# telemetry: JSONL snapshots every "
                      f"{args.telemetry_interval:g}s -> "
                      f"{os.path.join(args.telemetry_dir, 'telemetry.jsonl')}",
                      file=sys.stderr)
            if args.trace_dir:
                print("# tracing: per-window lineage -> "
                      f"{os.path.join(args.trace_dir, 'trace.json')} "
                      "(Chrome trace-event JSON; open in Perfetto)",
                      file=sys.stderr)
            return _run_cli(ap, args, params, spec, skip1, limit1, health)
    return _run_cli(ap, args, params, spec, skip1, limit1, health)


def _run_cli(ap, args, params: Params, spec: CaseSpec, skip1: int,
             limit1: Optional[int], health=None) -> int:
    """The post-validation half of :func:`main`: wire transport, run the
    pipeline, drain results into the sinks, print summaries. Split out so
    the telemetry session can scope the whole run."""
    from spatialflink_tpu.streams.sinks import StdoutSink
    from spatialflink_tpu.streams.sources import FileReplaySource
    from spatialflink_tpu.utils import telemetry as _telemetry

    coord = getattr(params, "checkpointer", None)
    tel = _telemetry.active()
    if tel is not None:
        # the ledger's catch-all tenant follows the flag; on resume the
        # 'tenants' checkpoint component restores cumulative attribution
        tel.tenants.default_tenant = getattr(args, "tenant_default",
                                             "default") or "default"
        if coord is not None:
            tel.tenants.register_checkpoint(coord)
    wctx = None
    if getattr(args, "fleet_role", None) == "worker":
        from spatialflink_tpu.runtime.fleet import WorkerContext

        # fleet worker glue: heartbeat + canonical outbox + tailing
        # partition source; everything else is the normal pipeline
        wctx = WorkerContext.from_args(args, spec).start()
    kafka = None
    if args.kafka:
        try:
            kafka = _wire_kafka(params, spec, args, skip1)
        except ValueError as e:
            ap.error(str(e))
        stream1, stream2 = kafka.stream1, kafka.stream2
    elif spec.family == "shapefile":
        stream1 = args.input1
    elif spec.family == "synthetic":
        stream1 = []
    elif coord is not None:
        # coordinated checkpointing over file replay: resume skips the
        # records the checkpoint already reflects (bounded replay, like a
        # consumer-group seek), and the tap reports the live position so
        # later checkpoints carry it. --limit keeps bounding the ORIGINAL
        # record range across the resume.
        from spatialflink_tpu.runtime.checkpoint import CheckpointTap

        skip_a = coord.position("file:1", 0)
        lim_a = (max(0, args.limit - skip_a)
                 if args.limit is not None else None)
        src_a = (wctx.tailing_source(limit=lim_a, skip=skip_a)
                 if wctx is not None else
                 FileReplaySource(args.input1, limit=lim_a, skip=skip_a))
        stream1 = CheckpointTap(src_a, coord, "file:1", base=skip_a)
        if skip_a:
            print(f"# resume: skipping {skip_a} already-reflected records "
                  "of --input1", file=sys.stderr)
    else:
        stream1 = (wctx.tailing_source(limit=limit1, skip=skip1)
                   if wctx is not None else
                   FileReplaySource(args.input1, limit=limit1, skip=skip1))
    if not args.kafka:
        stream2 = None
        if args.input2 and coord is not None:
            from spatialflink_tpu.runtime.checkpoint import CheckpointTap

            skip_b = coord.position("file:2", 0)
            lim_b = (max(0, args.limit - skip_b)
                     if args.limit is not None else None)
            stream2 = CheckpointTap(
                FileReplaySource(args.input2, limit=lim_b, skip=skip_b),
                coord, "file:2", base=skip_b)
        elif args.input2:
            stream2 = FileReplaySource(args.input2, limit=args.limit)

    from spatialflink_tpu.utils.metrics import ControlTupleExit

    results = run_option(params, stream1, stream2)

    sink = StdoutSink()
    out_sink = None
    if args.output:
        from spatialflink_tpu.streams.sinks import FileSink

        out_sink = FileSink(args.output, args.output_format,
                            delimiter=params.output.delimiter,
                            date_format=params.input1.date_format)
    import contextlib

    stack = contextlib.ExitStack()
    if wctx is not None:
        stack.callback(wctx.close)
    import signal as _signal
    import threading as _threading

    from spatialflink_tpu.utils import metrics as _metrics_mod

    if _threading.current_thread() is _threading.main_thread():
        # SIGTERM = graceful drain: the decode loop sees the flag at the
        # next record boundary, flushes its buffer into the pipeline, and
        # raises GracefulShutdown — which exits 0 below after a final
        # checkpoint. Cleared at run start so an earlier run's late signal
        # can't stop this one; handler restored by the stack.
        _metrics_mod.clear_shutdown()
        _prev_term = _signal.signal(
            _signal.SIGTERM,
            lambda signum, frame: _metrics_mod.request_shutdown())
        stack.callback(_signal.signal, _signal.SIGTERM, _prev_term)
    from spatialflink_tpu.utils import deviceplane

    # recompile sentinel: warmup re-opens for this run; after the declared
    # warmup (--sentinel-warmup emitted windows) every fresh XLA compile is
    # a 'recompile' event + counter, and an abort under --strict-recompile.
    # end_run on the stack so an in-process rerun (tests) starts cold.
    sentinel = deviceplane.registry()
    sentinel.begin_run(strict=args.strict_recompile)
    stack.callback(sentinel.end_run)
    recorder = None
    if args.postmortem_dir:
        recorder = deviceplane.FlightRecorder(
            args.postmortem_dir,
            config={
                "job_fingerprint": params.job_fingerprint(),
                "option": params.query.option,
                "family": spec.family,
                "mode": spec.mode,
                "backend": deviceplane.backend_provenance(),
                "flags": {
                    "kafka": bool(args.kafka),
                    "chaos": args.chaos is not None,
                    "panes": bool(getattr(args, "panes", False)),
                    "strict_recompile": args.strict_recompile,
                    "sentinel_warmup": args.sentinel_warmup,
                    "slo": args.slo,
                },
            })
        recorder.install_signal()
        if health is not None:
            recorder.attach_health(health)
        stack.callback(recorder.close)
        recorder.note("run-start", option=params.query.option,
                      family=spec.family)
        print(f"# flight recorder armed: post-mortem bundles -> "
              f"{args.postmortem_dir} (crash / SLO breach / SIGUSR1; "
              "read with python -m spatialflink_tpu.doctor)",
              file=sys.stderr)
    repartitioner = getattr(params, "repartitioner", None)
    if repartitioner is not None:
        # chain onto the grid-cell observer hook (decode-time base-cell
        # assignments feed the epoch counters) and become the /partition
        # endpoint's controller; restored on exit so repeated in-process
        # runs (tests) never leak the chain
        repartitioner.install()
        stack.callback(repartitioner.uninstall)
    governor = getattr(params, "chunk_governor", None)
    if governor is not None:
        # the decode streams resolve the governor per flush (late-bound
        # through _governed_chunk), so installing here — inside the stack,
        # uninstalled on every exit path — is safe regardless of wiring
        # order; checkpointed runs carry the control state as the
        # 'controller' manifest component
        governor.install()
        stack.callback(governor.uninstall)
        if coord is not None:
            governor.register_checkpoint(coord)
        pol = governor.policy
        print(f"# controller: decode-chunk governor on "
              f"(seed {governor.chunk()}, bounds "
              f"[{pol.min_chunk}, {pol.max_chunk}], target p99 "
              f"{pol.target_p99_ms:g}ms; live state at GET /latency)",
              file=sys.stderr)
    registry = getattr(params, "query_registry", None)
    router = None
    if registry is not None:
        from spatialflink_tpu.runtime.queryplane import (ControlTopicConsumer,
                                                         QueryRouter)

        # install BEFORE the opserver starts: POST/DELETE/GET /queries
        # discover the registry through queryplane.active_registry()
        registry.install()
        stack.callback(registry.uninstall)
        if getattr(args, "control_topic", None) and kafka is not None:
            registry.attach_control(ControlTopicConsumer(
                kafka.broker, args.control_topic, args.kafka_group))
        router = QueryRouter(registry, broker=kafka.broker
                             if kafka is not None else None)
        stack.callback(router.close)
    if args.profile:
        from spatialflink_tpu.utils.metrics import profile_to

        stack.enter_context(profile_to(args.profile))
        print(f"# profiling to {args.profile} (view with TensorBoard/xprof)",
              file=sys.stderr)
    from spatialflink_tpu.utils import telemetry as _telemetry

    tel = _telemetry.active()
    if args.status_port is not None:
        from spatialflink_tpu.runtime.opserver import OpServer

        # reads the active session (or the registry fallback) per request;
        # closed by the stack on pipeline exit — including a control-tuple
        # stop or a crash — so the port never outlives the run
        opserver = OpServer(port=args.status_port, health=health).start()
        stack.callback(opserver.close)
        if wctx is not None:
            # the supervisor discovers the ephemeral port through this
            # drop file and aggregates /status + /latency into /fleet
            wctx.write_url(opserver.url)
            if tel is not None and getattr(args, "fleet_plane",
                                           "on") != "off":
                # a durable copy of the ring for the harvest that comes
                # after this opserver has closed
                wctx.mirror_events(tel.events)
            # a harvestable first event per incarnation: the supervisor's
            # timeline shows each (re)spawn coming up before any window
            _telemetry.emit_event("worker-online", worker=wctx.worker_id,
                                  url=opserver.url)
        print(f"# status server: {opserver.url} "
              "(/healthz /status /metrics /events)", file=sys.stderr)
    if args.live_stats or (args.kafka_follow and tel is not None):
        from spatialflink_tpu.runtime.opserver import LiveStats

        # --kafka-follow runs with a telemetry session get the digest
        # automatically: a live run is exactly where a terminal operator
        # needs throughput/lag/health without the HTTP server
        live = LiveStats(interval_s=args.telemetry_interval,
                         health=health).start()
        stack.callback(live.close)
    # per-window pipeline latency: wall clock from asking the pipeline for
    # the next result to receiving it (assembly + kernel + readback for
    # that window — the end-to-end number per emitted window)
    win_hist = (tel.histogram("window-latency-ms")
                if tel is not None else None)

    def emit_result(result) -> None:
        _emit(result, sink)
        if kafka is not None:
            kafka.emit(result)
        if (router is not None and isinstance(result, WindowResult)
                and "query_ids" in result.extras):
            # per-query demux: counters/SLO verdicts always; non-stdout
            # routes (file:/kafka:) get one JSON doc per (window, query)
            router.route(result)
        if out_sink is not None:
            if isinstance(result, WindowResult):
                for rec in result.flat_records():
                    out_sink.emit(rec)
            elif (isinstance(result, tuple) and len(result) == 2
                    and isinstance(result[0], SpatialObject)):
                # deser-family results are (obj, serialized) pairs —
                # the reference produces exactly these to the output
                # topic (StreamingJob.java:1289-1545)
                out_sink.emit(result[0])
            else:
                out_sink.emit(result)

    journal = None
    if coord is not None and kafka is None and spec.mode == "window":
        # the Kafka window sink recovers its delivered-set from the topic's
        # commit markers; stdout/--output have no such log, so a durable
        # emitted-window journal in the checkpoint dir suppresses the
        # windows a resumed run would otherwise re-print — exactly-once on
        # the file path too
        from spatialflink_tpu.runtime.checkpoint import EmittedWindowJournal

        # a fresh run — including --resume that found no valid manifest —
        # must not inherit a previous run's emitted history; a fenced
        # fleet worker additionally drops journal lines its superseded
        # predecessor wrote past the fence cutoff (those windows were
        # never merged, so the successor must re-emit them)
        journal = EmittedWindowJournal(
            coord.dir,
            fresh=not (args.resume and coord.restored),
            fence=(wctx.fence if wctx is not None else 0),
            fence_cutoffs=(wctx.journal_fence_cutoffs()
                           if wctx is not None else None))

    n = 0
    stopped = False
    graceful_stop = False
    strict_abort = False
    it = iter(results)
    try:
        while True:
            t0 = time.perf_counter() if tel is not None else 0.0
            try:
                result = next(it)
            except StopIteration:
                break
            if win_hist is not None:
                win_hist.record((time.perf_counter() - t0) * 1e3)
            if (journal is not None and isinstance(result, WindowResult)
                    and journal.seen(result)):
                continue  # delivered by the pre-crash process
            if wctx is not None and isinstance(result, WindowResult):
                # canonical outbox line BEFORE the emit and the journal
                # record: a kill between outbox and journal re-appends an
                # identical line on resume, which the merge dedups — the
                # exactly-once ordering the fleet merge relies on. The
                # window's stage budget rides along as a lineage sidecar
                # OUTSIDE the fingerprint (--fleet-plane), so the merged
                # digest cannot depend on it
                budget = None
                if (tel is not None
                        and getattr(args, "fleet_plane", "on") != "off"):
                    budget = tel.latency.budget_row(result.window_start)
                wctx.note_window(result, budget=budget)
            if tel is not None:
                s0 = time.time()
                meta = ({"window": result.window_start}
                        if isinstance(result, WindowResult) else {})
                with tel.span("sink", **meta):
                    emit_result(result)
                s1 = time.time()
                if isinstance(result, WindowResult):
                    # the driver's emission stage, appended by
                    # window_start (the result no longer carries its
                    # family label): the latency plane's downstream
                    # "sink" budget, plus the trace-lineage note when
                    # tracing is on
                    tel.latency.note_downstream(
                        "sink", result.window_start, s0, s1)
                    if tel.traces is not None:
                        tel.traces.note_any(result.window_start, "sink",
                                            s0, s1)
            else:
                emit_result(result)
            if journal is not None and isinstance(result, WindowResult):
                journal.record(result)
            n += 1
            if (not sentinel.warm and isinstance(result, WindowResult)
                    and n >= args.sentinel_warmup):
                # declared warmup done: the run's steady-state shapes have
                # been seen; any later compile is a sentinel event
                sentinel.mark_warm(
                    f"{n} window(s) emitted (--sentinel-warmup "
                    f"{args.sentinel_warmup})")
            if recorder is not None and isinstance(result, WindowResult):
                recorder.note("window", start=result.window_start,
                              records=len(result.records))
    except ControlTupleExit as e:
        # the remote-stop hook (HelperClass.checkExitControlTuple:441-453) is
        # a graceful shutdown, not an error: finish the summary and exit 0.
        # A SIGTERM-raised stop additionally writes a final checkpoint
        # below — buffered records were drained into the pipeline first.
        stopped = True
        graceful_stop = isinstance(e, _metrics_mod.GracefulShutdown)
    except deviceplane.RecompileError as e:
        # --strict-recompile abort: the zero-recompile contract was
        # violated; capture the moment and exit distinctly (3)
        if recorder is not None:
            recorder.dump("strict-recompile", error=e)
        print(f"# STRICT-RECOMPILE ABORT: {e}", file=sys.stderr)
        strict_abort = True
    except BaseException as e:
        # any other crash: dump the post-mortem bundle (state at the
        # moment of death — the whole point of the recorder), then
        # propagate unchanged
        if recorder is not None:
            recorder.dump("crash", error=e)
        raise
    finally:
        stack.close()  # stop the profiler trace before the summary prints
        if out_sink is not None:
            out_sink.close()
        if journal is not None:
            journal.close()
    if graceful_stop and coord is not None:
        # a signal-driven stop writes one FINAL coordinated checkpoint:
        # the decode buffer drained into the pipeline before the stop
        # propagated, so operator state + source positions cover every
        # record read — a later --resume completes the stream with
        # nothing lost and nothing re-emitted
        final_path = coord.commit()
        print(f"# graceful shutdown: final checkpoint seq {coord.seq} "
              f"({final_path})", file=sys.stderr)
    if wctx is not None:
        wctx.write_run_summary(
            rc=3 if strict_abort else 0,
            stopped=stopped,
            graceful=graceful_stop,
            resumed=bool(coord is not None and coord.restored),
            emitted=n,
            suppressed=journal.suppressed if journal is not None else 0,
            post_warmup_compiles=sentinel.run_recompiles,
            checkpoint_seq=(coord.seq if coord is not None else None))
    if kafka is not None:
        if not stopped:
            # fully drained bounded topic: full positions are safe to commit.
            # A control-tuple stop keeps the conservative window-aligned
            # commits instead (buffered-but-unfired windows re-deliver).
            kafka.finish()
        print(kafka.summary(), file=sys.stderr)
    print(f"# emitted {n} results" + (" (control-tuple stop)" if stopped else ""),
          file=sys.stderr)
    if journal is not None and journal.suppressed:
        print(f"# resume: suppressed {journal.suppressed} window(s) the "
              "crashed run already emitted (journal "
              f"{journal.path})", file=sys.stderr)
    if out_sink is not None:
        print(f"# wrote {out_sink.records_written} records to {args.output} "
              f"({args.output_format})", file=sys.stderr)
    if args.metrics:
        import json

        from spatialflink_tpu.utils.metrics import (REGISTRY,
                                                    degradation_snapshot)

        # machine-readable: ONE sorted-JSON object on stderr (the old
        # Python-dict repr was neither parseable nor stable), with the
        # degradation digest alongside the raw counters
        print(json.dumps({"metrics": REGISTRY.snapshot(),
                          "degradation": degradation_snapshot()},
                         sort_keys=True), file=sys.stderr)
    return 3 if strict_abort else 0


if __name__ == "__main__":
    raise SystemExit(main())
