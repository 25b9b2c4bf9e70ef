"""Point-stream x point-stream join.

Reference: ``spatialOperators/join/PointPointJoinQuery.java`` — query-stream
replication to neighboring cells, gridID equi-join per window, exact-distance
filter (``:110-171``). Here both sides are windowed together and joined with
the MXU pairwise-distance kernel + Chebyshev cell predicate (ops.join); pairs
are extracted sparsely on the host.

Real-time mode micro-batches the *merged* arrival stream and joins each
micro-batch's two sides (the reference's fire-per-element trigger analogue,
``tJoin/TJoinQuery.java:216-268``).
"""

from __future__ import annotations

import heapq
from collections import deque
from typing import Dict, Iterable, Iterator, List, Tuple

import numpy as np

from spatialflink_tpu.models import Point, PointBatch
from spatialflink_tpu.operators.base import (
    Deferred,
    QueryType,
    SpatialOperator,
    WindowResult,
)
from spatialflink_tpu.ops.join import join_pairs_host
from spatialflink_tpu.runtime import WindowAssembler
from spatialflink_tpu.streams.bulk import LazyRecords
from spatialflink_tpu.utils import telemetry as _telemetry


def _merge_by_time(a: Iterable[Point], b: Iterable[Point]) -> Iterator[Tuple[int, int, Point]]:
    """Merge two event-time-ordered streams, tagging side 0/1."""
    return heapq.merge(
        ((p.timestamp, 0, p) for p in a),
        ((p.timestamp, 1, p) for p in b),
        key=lambda t: t[0],
    )


def _chunks(stream) -> Iterator:
    """A join side as decoded chunks: a chunked decode stream
    (``driver.decode_stream``) hands its chunks over whole; any other
    iterable is read one record at a time, as one-record chunks."""
    chunks_fn = getattr(stream, "chunks", None)
    if chunks_fn is not None:
        return iter(chunks_fn())
    return ([rec] for rec in stream)


def _assemble_chunk(wa: WindowAssembler, chunk) -> Iterator[Tuple[int, int, List]]:
    """Buffer one decoded chunk whole: a columnar :class:`PointChunk` as SoA
    slices, a record list (geometry or mixed streams) as records. A
    one-record chunk takes :meth:`WindowAssembler.add`, the same decisions
    as ``add_chunk`` without its per-call array set-up."""
    if hasattr(chunk, "parsed"):
        return wa.add_parsed_chunk(chunk)
    if len(chunk) == 1:
        return wa.add(chunk[0].timestamp, chunk[0])
    return wa.add_chunk([r.timestamp for r in chunk], chunk)


def _take(recs, idx) -> List:
    """The records at ``idx``. A columnar window gathers each distinct
    record once, in one vectorized :meth:`LazyRecords.take`, and pairs
    that share a member share its object, as a record list's do."""
    take = getattr(recs, "take", None)
    if take is None:
        return [recs[i] for i in idx.tolist()]
    uniq, inv = np.unique(idx, return_inverse=True)
    rows = list(take(uniq))
    return [rows[i] for i in inv.tolist()]


def _gather_pairs(recs_a, recs_b, ai, bi, max_dt: int = None
                  ) -> List[Tuple[object, object]]:
    """The ``(a, b)`` record pairs at survivor indices ``ai``/``bi``, in
    lattice order: padding rows dropped, and with ``max_dt`` (the realtime
    co-residence bound) only pairs whose event times lie within it."""
    keep = (ai < len(recs_a)) & (bi < len(recs_b))
    ai, bi = ai[keep], bi[keep]
    if not ai.size:
        return []
    pairs = zip(_take(recs_a, ai), _take(recs_b, bi))
    if max_dt is None:
        return list(pairs)
    return [(a, b) for a, b in pairs
            if abs(a.timestamp - b.timestamp) <= max_dt]


def _combine_windows(r1: WindowResult, r2: WindowResult) -> WindowResult:
    """One WindowResult whose records are r1's followed by r2's; deferred
    inputs stay deferred (both lattices remain in flight on device)."""
    rec1, rec2 = r1.records, r2.records
    if not isinstance(rec1, Deferred) and not isinstance(rec2, Deferred):
        return WindowResult(r1.window_start, r1.window_end, rec1 + rec2)

    def collect(_):
        out = rec1.finish() if isinstance(rec1, Deferred) else list(rec1)
        out += rec2.finish() if isinstance(rec2, Deferred) else list(rec2)
        return out

    return WindowResult(r1.window_start, r1.window_end, Deferred(None, collect))


def _spanned_windows(results: Iterator[WindowResult], tel, label: str
                     ) -> Iterator[WindowResult]:
    """Each pull of the next window timed as the ``<label>.window`` span,
    closed before the window is handed on: the pull of both streams'
    chunks, window assembly, and every stage nested in them (decode,
    dispatch, pair extraction)."""
    it = iter(results)
    while True:
        try:
            with tel.span("window", query=label):
                r = next(it)
        except StopIteration:
            return
        yield r


class PointPointJoinQuery(SpatialOperator):
    telemetry_label = "join"

    # a count trigger over TWO independently-arriving streams is ambiguous
    # (whose arrivals count?); joins keep the reference's rejection
    supports_count_windows = False

    prune_cells = True  # naive twins disable grid pruning (exact filter only)

    def run(self, ordinary: Iterable[Point], query_stream: Iterable[Point],
            radius: float) -> Iterator[WindowResult]:
        if self.conf.query_type is QueryType.RealTime:
            results = self._run_realtime(ordinary, query_stream, radius)
        elif self._panes_active():
            results = self._run_windowed_panes(ordinary, query_stream, radius)
        else:
            results = self._run_windowed(ordinary, query_stream, radius)
        tel = _telemetry.active()
        if tel is not None:
            results = _spanned_windows(results, tel, self.telemetry_label)
        return self._pipeline(results)

    def _pipeline(self, results: Iterator[WindowResult]
                  ) -> Iterator[WindowResult]:
        """Keep up to ``conf.pipeline_depth`` join lattices in flight on
        device (``records`` may arrive as a :class:`Deferred`), materializing
        in window order — the host seals and dispatches the next window while
        the device works on the previous one."""
        depth = max(1, self.conf.pipeline_depth)
        pending: deque = deque()
        coord = self.conf.checkpointer

        def force(r: WindowResult) -> WindowResult:
            if isinstance(r.records, Deferred):
                r.records = r.records.finish()
            return r

        # same knob semantics as base._drive: depth-1 windows stay in flight
        # behind the one being assembled; eager (non-Deferred) results pass
        # straight through once older deferred windows have drained
        for r in results:
            if isinstance(r.records, Deferred):
                pending.append(r)
                while len(pending) > depth - 1:
                    yield force(pending.popleft())
            else:
                while pending:
                    yield force(pending.popleft())
                yield r
            if coord is not None:
                # coordinated-checkpoint barrier (see base._drive_batched):
                # drain the in-flight lattices first — their windows' records
                # are no longer in the snapshotted assemblers/sealed maps,
                # so they must be fully emitted before the manifest writes
                coord.note_batch()
                if coord.due():
                    while pending:
                        yield force(pending.popleft())
                    coord.commit()
        while pending:
            yield force(pending.popleft())

    # ---------------------------------------------------------------- #

    def _register_ckpt_join(self, wa_a, wa_b, sealed_a, sealed_b,
                            panes: bool) -> None:
        """Coordinator participant for the two-stream windowed join: both
        sides' assemblers (or pane buffers) plus the sealed-on-one-side
        maps awaiting the other watermark. ``panes`` switches the sealed
        payload shape: record lists vs ``[(pane_start, records)]`` lists."""
        coord = self.conf.checkpointer
        if coord is None:
            return
        from spatialflink_tpu.runtime.checkpoint import record_codec

        # side b decodes against grid2 — the query-side grid the driver
        # parses stream2 into; decoding both sides with grid would mint
        # wrong cell ids whenever the two grids differ
        enc, dec_a = record_codec(self.grid)
        _, dec_b = record_codec(self.grid2)

        if panes:
            def enc_sealed(sealed):
                return {str(s): [[p, [enc(r) for r in recs]]
                                 for p, recs in pane_list]
                        for s, pane_list in sealed.items()}

            def dec_sealed(state, sealed, dec):
                sealed.update({int(s): [(int(p), [dec(r) for r in recs])
                                        for p, recs in pl]
                               for s, pl in state.items()})
        else:
            def enc_sealed(sealed):
                return {str(s): [enc(r) for r in recs]
                        for s, recs in sealed.items()}

            def dec_sealed(state, sealed, dec):
                sealed.update({int(s): [dec(r) for r in recs]
                               for s, recs in state.items()})

        def snap():
            return ({}, {"a": wa_a.snapshot(enc), "b": wa_b.snapshot(enc),
                         "sealed_a": enc_sealed(sealed_a),
                         "sealed_b": enc_sealed(sealed_b)})

        def restore(_arrays, meta):
            wa_a.restore(meta["a"], dec_a)
            wa_b.restore(meta["b"], dec_b)
            dec_sealed(meta["sealed_a"], sealed_a, dec_a)
            dec_sealed(meta["sealed_b"], sealed_b, dec_b)

        coord.register("join-windows", snap, restore)

    def _run_realtime(self, ordinary, query_stream, radius) -> Iterator[WindowResult]:
        """Micro-batched realtime join over a *rolling* window.

        The reference's realtime joins buffer a full small window per stream
        with fire-per-element triggers (``tJoin/TJoinQuery.java:216-268``), so
        any pair co-resident within the window is found regardless of arrival
        interleaving. Mirroring that: both sides keep a rolling buffer of the
        last ``window_size_ms`` of records across micro-batches; each batch
        joins (old ∪ new) × (old ∪ new) but suppresses old×old pairs (already
        emitted by an earlier fire), so a pair straddling a micro-batch
        boundary is emitted exactly once — when its later point arrives.
        """
        win = self.conf.window_size_ms
        buf_a: List[Point] = []
        buf_b: List[Point] = []
        new_a: List[Point] = []
        new_b: List[Point] = []
        seen = 0
        last_ts = 0

        def fire(end_ts):
            nonlocal buf_a, buf_b, new_a, new_b, seen
            # evict only points that cannot pair with ANY new arrival: the
            # earliest new record sets the horizon (evicting against end_ts
            # would drop a buffered point still within win of a new one);
            # the max_dt filter below enforces |ta - tb| <= win exactly
            first_new = min(p.timestamp for p in new_a + new_b)
            cutoff = first_new - win
            buf_a = [p for p in buf_a if p.timestamp >= cutoff]
            buf_b = [p for p in buf_b if p.timestamp >= cutoff]
            all_b = buf_b + new_b
            # two lattices instead of (old+new)^2: new_a x (old_b + new_b)
            # and old_a x new_b cover every pair with a new member exactly
            # once and never recompute the old x old block an earlier fire
            # already evaluated
            start = end_ts - win
            r1 = self._join_window(start, end_ts, new_a, all_b, radius,
                                   max_dt=win)
            r2 = self._join_window(start, end_ts, buf_a, new_b, radius,
                                   max_dt=win)
            res = _combine_windows(r1, r2)
            if not isinstance(res.records, Deferred) and not res.records:
                res = None  # realtime fires never emit known-empty results
            buf_a, buf_b = buf_a + new_a, all_b
            new_a, new_b, seen = [], [], 0
            return res

        for ts, side, rec in _merge_by_time(ordinary, query_stream):
            (new_a if side == 0 else new_b).append(rec)
            last_ts = ts
            seen += 1
            if seen >= self.conf.realtime_batch_size:
                res = fire(ts)
                if res is not None:
                    yield res
        if new_a or new_b:
            res = fire(last_ts)
            if res is not None:
                yield res

    # ---------------------------------------------------------------- #

    def _run_windowed(self, ordinary, query_stream, radius) -> Iterator[WindowResult]:
        """Each side buffers whole decoded chunks into its own assembler
        (a columnar :class:`PointChunk` as slices, a record list as
        records; a plain iterable arrives as one-record chunks), the next
        chunk always from the side whose watermark is lower. Every pulled
        chunk is in its assembler before any window yields, so a
        checkpoint barrier never covers a record outside the snapshot.
        Late drops read each side's own prefix watermark, so window
        contents, late drops and emission order match a per-record merge
        of the two streams; emission moves by at most one decode chunk."""
        from spatialflink_tpu.utils.metrics import REGISTRY

        spec = self.conf.window_spec()
        wa_a = WindowAssembler(spec, self.conf.allowed_lateness_ms)
        wa_b = WindowAssembler(spec, self.conf.allowed_lateness_ms)
        # windows sealed on one side, waiting for the other; bounded by the
        # watermark sweep below (a window is emitted -- possibly one-sided --
        # once BOTH sides' watermarks have passed its end)
        sealed_a: Dict[int, List[Point]] = {}
        sealed_b: Dict[int, List[Point]] = {}
        self._register_ckpt_join(wa_a, wa_b, sealed_a, sealed_b, panes=False)
        columnar = REGISTRY.counter("join-columnar-windows")

        def join(start: int) -> WindowResult:
            recs_a = sealed_a.pop(start, [])
            if isinstance(recs_a, LazyRecords):
                columnar.inc()
            return self._join_window(start, start + spec.size_ms, recs_a,
                                     sealed_b.pop(start, []), radius)

        def sweep() -> Iterator[WindowResult]:
            # Empty windows never appear in an assembler's buffers, so a
            # window sealed on one side may have no counterpart; once both
            # watermarks passed its end the missing side is final-empty.
            wm = min(wa_a.watermarker.watermark, wa_b.watermarker.watermark)
            for start in sorted(set(sealed_a) | set(sealed_b)):
                end = start + spec.size_ms
                both = start in sealed_a and start in sealed_b
                if both or end <= wm:
                    yield join(start)

        live = [(wa_a, sealed_a, _chunks(ordinary)),
                (wa_b, sealed_b, _chunks(query_stream))]
        while live:
            # the side whose watermark is lower (side a on a tie); an
            # exhausted side leaves the rotation
            i = min(range(len(live)),
                    key=lambda j: live[j][0].watermarker.watermark)
            wa, sealed, chunks = live[i]
            ch = next(chunks, None)
            if ch is None:
                del live[i]
                continue
            for start, _end, records in _assemble_chunk(wa, ch):
                sealed[start] = records
            yield from sweep()
        for start, _end, records in wa_a.flush():
            sealed_a[start] = records
        for start, _end, records in wa_b.flush():
            sealed_b[start] = records
        for start in sorted(set(sealed_a) | set(sealed_b)):
            yield join(start)

    def _run_windowed_panes(self, ordinary, query_stream, radius
                            ) -> Iterator[WindowResult]:
        """Pane-incremental windowed join (``--panes``): both sides buffer
        into slide-aligned panes, and each window's pair set is the union of
        its PANE-PAIR BLOCKS ``A_i x B_j`` — each block's lattice kernel
        runs once and is reused by every window containing both panes, so a
        slide adds only the O(overlap) new blocks touching the freshest
        pane instead of recomputing the O(overlap^2) full lattice. Window
        set/sealing/late-drops are identical to :meth:`_run_windowed`
        (same watermark sweep, pane-grouped); pair ORDER within a window is
        block order rather than full-lattice order — the pair SET is
        identical. Block results stay deferred until the window's readback,
        so pane mode composes with ``pipeline_depth``."""
        from spatialflink_tpu.operators.base import PaneCache, PanePartial
        from spatialflink_tpu.runtime.windows import PaneBuffer

        spec = self.conf.window_spec()
        slide = spec.slide_ms
        pb_a = PaneBuffer(spec, self.conf.allowed_lateness_ms)
        pb_b = PaneBuffer(spec, self.conf.allowed_lateness_ms)
        sealed_a: Dict[int, List] = {}  # start -> [(pane_start, records)]
        sealed_b: Dict[int, List] = {}
        self._register_ckpt_join(pb_a, pb_b, sealed_a, sealed_b, panes=True)
        # block cache keyed (pane_a, pane_b); a block is needed only while
        # BOTH its panes can appear in a future window, so eviction hinges
        # on the earlier pane
        cache = PaneCache(slide, key_floor=min)
        self._register_ckpt_pane_cache("pane-cache", cache)
        # per-side pane BATCH memo: a pane's device batch is built once and
        # shared by every block touching it — without this each new pane
        # would rebuild its batch O(overlap) times (once per block) and the
        # host batch-building cost would match full-window recompute
        bcache_a: Dict[int, object] = {}
        bcache_b: Dict[int, object] = {}

        def block(pa: int, ra: List, pb_s: int, rb: List) -> PanePartial:
            def evaluate():
                if pa not in bcache_a:
                    bcache_a[pa] = self._batch_a(ra, pa)
                if pb_s not in bcache_b:
                    bcache_b[pb_s] = self._batch_b(rb, pb_s)
                return PanePartial(self._join_block(
                    bcache_a[pa], ra, bcache_b[pb_s], rb, radius))

            return cache.get((pa, pb_s), evaluate)

        def evict(start: int) -> None:
            cache.evict_before(start)
            for bc in (bcache_a, bcache_b):
                for dead in [p for p in bc if p < start + slide]:
                    del bc[dead]

        def join_panes(start: int, panes_a: List, panes_b: List
                       ) -> WindowResult:
            if self._blocks_dispatch_bound(panes_a, panes_b):
                # ADAPTIVE GRANULARITY: the window's pane-pair blocks are
                # dispatch-bound (mean block lattice below the measured
                # per-dispatch break-even — the 0.56–0.95× dense regime in
                # BASELINE), so evaluate the window as ONE coalesced
                # lattice dispatch instead of overlap² tiny ones. No
                # cross-window reuse for such windows — matching the
                # full-recompute path they now cost — while big-block
                # (compute-bound) windows keep the cached-block path.
                from spatialflink_tpu.utils.metrics import REGISTRY

                REGISTRY.counter("join-blocks-coalesced").inc(
                    len(panes_a) * len(panes_b))
                evict(start)
                return self._join_window(
                    start, start + spec.size_ms,
                    [r for _, rs in panes_a for r in rs],
                    [r for _, rs in panes_b for r in rs], radius)
            blocks = [block(pa, ra, pb_s, rb)
                      for pa, ra in panes_a for pb_s, rb in panes_b]
            evict(start)

            def collect(_):
                return [pair for h in blocks for pair in h.resolve()]

            return WindowResult(start, start + spec.size_ms,
                                Deferred(None, collect))

        def sweep() -> Iterator[WindowResult]:
            wm = min(pb_a.watermarker.watermark, pb_b.watermarker.watermark)
            for start in sorted(set(sealed_a) | set(sealed_b)):
                end = start + spec.size_ms
                both = start in sealed_a and start in sealed_b
                if both or end <= wm:
                    yield join_panes(start, sealed_a.pop(start, []),
                                     sealed_b.pop(start, []))

        for ts, side, rec in _merge_by_time(ordinary, query_stream):
            pb = pb_a if side == 0 else pb_b
            sealed = sealed_a if side == 0 else sealed_b
            for start, _end, panes in pb.add(ts, rec):
                sealed[start] = panes
            yield from sweep()
        for start, _end, panes in pb_a.flush():
            sealed_a[start] = panes
        for start, _end, panes in pb_b.flush():
            sealed_b[start] = panes
        for start in sorted(set(sealed_a) | set(sealed_b)):
            yield join_panes(start, sealed_a.pop(start, []),
                             sealed_b.pop(start, []))

    @staticmethod
    def _blocks_dispatch_bound(panes_a: List, panes_b: List) -> bool:
        """True when this window's pane-pair blocks sit below the measured
        per-dispatch break-even (``ops.join.adaptive_block_min_cells``):
        mean block lattice cells at PADDED capacities — dispatch cost
        scales with the padded shape, not the live record count."""
        if not panes_a or not panes_b or len(panes_a) * len(panes_b) <= 1:
            return False
        from spatialflink_tpu.ops.join import adaptive_block_min_cells
        from spatialflink_tpu.utils.padding import bucket_size

        min_cells = adaptive_block_min_cells()
        if min_cells <= 0:
            return False
        mean_a = sum(bucket_size(max(len(rs), 1))
                     for _, rs in panes_a) / len(panes_a)
        mean_b = sum(bucket_size(max(len(rs), 1))
                     for _, rs in panes_b) / len(panes_b)
        return mean_a * mean_b < min_cells

    def _join_pairs(self, batch_a, batch_b, radius, window=None):
        """(a_index, b_index) survivor arrays for one window's pair lattice.

        Single-device: b-tiled host extraction (``ops.join.join_pairs_host``).
        With ``conf.devices``: the a side is sharded over the mesh and the
        query side replicated — the broadcast-join layout of SURVEY §2.5
        (``join/JoinQuery.java:72-90``'s replication without materialized
        copies) via ``parallel.ops.distributed_join_mask``.
        """
        nb_layers = None if self.prune_cells else self.grid.n
        if self.distributed:
            from spatialflink_tpu.parallel.ops import distributed_join_mask

            if nb_layers is None:
                nb_layers = (self.grid.n if radius == 0
                             else self.grid.candidate_layers(radius))
            cx = self.grid.min_x + self.grid.cell_length * self.grid.n / 2
            cy = self.grid.min_y + self.grid.cell_length * self.grid.n / 2
            m = self._eval_degradable(
                lambda: None,  # sentinel: single-device path yields below
                lambda mesh, sa: distributed_join_mask(
                    mesh, sa, batch_b, radius,
                    nb_layers, cx, cy, n=self.grid.n),
                batch_a)
            if m is not None:
                ai, bi = np.nonzero(np.asarray(m))
                if ai.size:
                    yield ai, bi
                return
        yield from join_pairs_host(batch_a, batch_b, radius, self.grid,
                                   nb_layers=nb_layers, window=window)

    def _batch_a(self, recs, ts_base):
        return self._join_batch(recs, ts_base, self.grid)

    def _batch_b(self, recs, ts_base):
        return self._join_batch(recs, ts_base, self.grid2)

    def _join_batch(self, recs, ts_base, decoded_in):
        """A side's point batch. A columnar window builds it from the
        window's memoized per-record arrays, which pair extraction then
        gathers from (``LazyRecords.take``) without concatenating the
        window again. Its cells are the decode's, assigned in
        ``decoded_in``; the join predicate compares cells in
        ``self.grid``, so they are assigned anew from x/y where the two
        differ, as a record list's always are (``from_points``)."""
        if not isinstance(recs, LazyRecords):
            return self._point_batch(recs, ts_base)
        x, y, ts, oid, cell = self._record_arrays(recs)
        if decoded_in is not self.grid:
            cell = None
        return PointBatch.from_arrays(x, y, grid=self.grid, obj_id=oid,
                                      ts=ts, ts_base=ts_base, cell=cell)

    def _join_block(self, batch_a, recs_a: List[Point], batch_b,
                    recs_b: List[Point], radius) -> List[Tuple[Point, Point]]:
        """One pane-pair block from PRE-BUILT pane batches — the pane
        path's :meth:`_join_window` twin (windowed semantics only: no
        realtime rolling-prefix/max_dt filters). Taking batches lets the
        pane driver build each pane's batch once per SIDE instead of once
        per block; the mixed ts bases are harmless (the join predicates
        read positions and cells, never the batch ts offsets)."""
        pairs: List[Tuple[Point, Point]] = []
        for ai, bi in self._join_pairs(batch_a, batch_b, radius):
            pairs.extend(_gather_pairs(recs_a, recs_b, ai, bi))
        return pairs

    def _join_window(self, start, end, recs_a: List[Point], recs_b: List[Point],
                     radius, *, max_dt: int = None) -> WindowResult:
        # max_dt: realtime co-residence bound — only pairs whose event times
        # lie within one realtime window of each other are emitted
        pairs: List[Tuple[Point, Point]] = []
        if recs_a and recs_b:
            label = self.telemetry_label
            with _telemetry.span("dispatch", label, window=start):
                batch_a = self._batch_a(recs_a, start)
                batch_b = self._batch_b(recs_b, start)
            for ai, bi in self._join_pairs(batch_a, batch_b, radius,
                                           window=start):
                with _telemetry.span("pairs", label, window=start):
                    pairs.extend(_gather_pairs(recs_a, recs_b, ai, bi,
                                               max_dt))
        return WindowResult(start, end, pairs)


class _GenericStreamJoin(PointPointJoinQuery):
    """Shared two-stream windowed/realtime join driver; subclasses override
    batch construction and the pair-lattice kernel."""

    def _join_window(self, start, end, recs_a, recs_b, radius, *,
                     max_dt: int = None) -> WindowResult:
        if not (recs_a and recs_b):
            return WindowResult(start, end, [])
        batch_a = self._batch_a(recs_a, start)
        batch_b = self._batch_b(recs_b, start)
        if self.distributed:
            # broadcast-join layout for the geometry pairs too: a sharded on
            # the mesh, query side replicated, same lattice kernel per shard
            from spatialflink_tpu.parallel.ops import (
                distributed_stream_join_lattice,
            )

            m_dev = self._eval_degradable(
                lambda: self._lattice(batch_a, batch_b, radius),
                lambda mesh, sa: distributed_stream_join_lattice(
                    mesh, sa, batch_b,
                    lambda a_s, b_r: self._lattice(a_s, b_r, radius)),
                batch_a)
        else:
            m_dev = self._lattice(batch_a, batch_b, radius)

        def collect(m):
            ai, bi = np.nonzero(np.asarray(m))
            return _gather_pairs(recs_a, recs_b, ai, bi, max_dt)

        return WindowResult(start, end, Deferred(m_dev, collect))

    def _nb_layers(self, radius):
        # radius 0 => all cells neighbors (UniformGrid.java:264-266)
        return self.grid.n if radius == 0 else self.grid.candidate_layers(radius)

    def _join_block(self, batch_a, recs_a, batch_b, recs_b, radius):
        """Pane-pair block for the geometry pairs: the same lattice kernel
        (single-device or broadcast-sharded) over pre-built pane batches,
        with the pair extraction DEFERRED — blocks stay in flight on device
        until the first covering window's readback."""
        if self.distributed:
            from spatialflink_tpu.parallel.ops import (
                distributed_stream_join_lattice,
            )

            m_dev = self._eval_degradable(
                lambda: self._lattice(batch_a, batch_b, radius),
                lambda mesh, sa: distributed_stream_join_lattice(
                    mesh, sa, batch_b,
                    lambda a_s, b_r: self._lattice(a_s, b_r, radius)),
                batch_a)
        else:
            m_dev = self._lattice(batch_a, batch_b, radius)

        def collect(m):
            ai, bi = np.nonzero(np.asarray(m))
            return _gather_pairs(recs_a, recs_b, ai, bi)

        return Deferred(m_dev, collect)


class PointGeomJoinQuery(_GenericStreamJoin):
    """Point stream x polygon/linestring query stream
    (``join/PointPolygonJoinQuery.java``, ``PointLineStringJoinQuery``)."""

    def _batch_b(self, recs, ts_base):
        return self._geom_batch(recs, ts_base)

    def _lattice(self, a, b, radius):
        from spatialflink_tpu.ops.join import join_point_geom_mask

        return join_point_geom_mask(a, b, radius, self._nb_layers(radius), n=self.grid.n)


class GeomPointJoinQuery(_GenericStreamJoin):
    """Polygon/linestring stream x point query stream
    (``join/PolygonPointJoinQuery.java``, ``LineStringPointJoinQuery``)."""

    def _batch_a(self, recs, ts_base):
        return self._geom_batch(recs, ts_base)

    def _lattice(self, a, b, radius):
        from spatialflink_tpu.ops.join import join_point_geom_mask

        # reuse the point x geom lattice with sides swapped
        return join_point_geom_mask(b, a, radius, self._nb_layers(radius),
                                    n=self.grid.n).T

    
class GeomGeomJoinQuery(_GenericStreamJoin):
    """Polygon/linestring stream x polygon/linestring query stream
    (``join/PolygonPolygonJoinQuery.java`` + 3 sibling pairs)."""

    def _batch_a(self, recs, ts_base):
        return self._geom_batch(recs, ts_base)

    _batch_b = _batch_a

    def _lattice(self, a, b, radius):
        from spatialflink_tpu.ops.join import join_geom_geom_mask

        return join_geom_geom_mask(a, b, radius, self._nb_layers(radius), n=self.grid.n)


# Reference-named aliases
PointPolygonJoinQuery = PointGeomJoinQuery
PointLineStringJoinQuery = PointGeomJoinQuery
PolygonPointJoinQuery = GeomPointJoinQuery
LineStringPointJoinQuery = GeomPointJoinQuery
PolygonPolygonJoinQuery = GeomGeomJoinQuery
PolygonLineStringJoinQuery = GeomGeomJoinQuery
LineStringPolygonJoinQuery = GeomGeomJoinQuery
LineStringLineStringJoinQuery = GeomGeomJoinQuery
