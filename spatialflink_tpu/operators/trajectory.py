"""Trajectory operators (reference: ``spatialOperators/t*``).

All six families of SURVEY §2.3, keyed by object id:

- :class:`PointTFilterQuery`   — trajectory-id filter + windowed LineString
  re-assembly (``tFilter/PointTFilterQuery.java``).
- :class:`PointPolygonTRangeQuery` — trajectories intersecting a polygon set
  (``tRange/PointPolygonTRangeQuery.java``), with the naive exhaustive twin
  (``tRange/TRangeQuery.java:33-63``) as :meth:`run_naive`.
- :class:`PointTStatsQuery`    — running spatial/temporal length + speed via
  the sorted-segment device kernel (ops.trajectory.tstats_update).
- :class:`PointTAggregateQuery`— per-cell heatmap of trajectory lengths with
  SUM/AVG/MIN/MAX/COUNT/ALL and stale-trajectory eviction
  (``tAggregate/TAggregateQuery.java``).
- :class:`PointPointTJoinQuery`— trajectory-trajectory proximity join deduped
  per (trajectory, partner) keeping the latest timestamp
  (``tJoin/PointPointTJoinQuery.java:133-177``), self-join variant
  :meth:`run_single`, naive all-pairs twin :meth:`run_naive`.
- :class:`PointPointTKNNQuery` — k nearest *trajectories* within radius
  (exact-radius filtered, ``tKnn/PointPointTKNNQuery.java:95-111``), naive
  twin :meth:`run_naive`.

Windowed modes re-assemble each selected trajectory's window points into
time-sorted sub-trajectory LineStrings, as the reference does.
"""

from __future__ import annotations

import os
from collections import defaultdict
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Set, Tuple

import numpy as np

from spatialflink_tpu.models import LineString, Point, Polygon
from spatialflink_tpu.operators.base import (
    GeomQueryMixin,
    QueryType,
    SpatialOperator,
    WindowResult,
)


def assemble_subtrajectories(records: List[Point]) -> Dict[str, object]:
    """objID -> time-sorted LineString of its window points (a single point
    stays a Point), mirroring the windowed re-assembly in
    ``tFilter/PointTFilterQuery.java:79-123``."""
    per_obj: Dict[str, List[Point]] = defaultdict(list)
    for p in records:
        per_obj[p.obj_id].append(p)
    out: Dict[str, object] = {}
    for oid, pts in per_obj.items():
        pts.sort(key=lambda p: p.timestamp)
        if len(pts) >= 2:
            out[oid] = LineString.create(
                [(p.x, p.y) for p in pts], None, oid, pts[-1].timestamp
            )
        else:
            out[oid] = pts[0]
    return out


class PointTFilterQuery(SpatialOperator):
    # interner-keyed cross-window state: windows must carry
    # materialized records in the OPERATOR's id space (the
    # chunked decode still batches the parse)
    columnar_windows = False
    telemetry_label = "tfilter"

    """Keep only trajectories whose objID is in ``traj_ids`` (empty => all)."""

    def run(self, stream: Iterable[Point], traj_ids: Set[str]
            ) -> Iterator[WindowResult]:
        allowed = set(traj_ids)

        def want(p: Point) -> bool:
            return not allowed or p.obj_id in allowed

        if self.conf.query_type is QueryType.RealTime:
            for records in self._micro_batches(stream):
                sel = [p for p in records if want(p)]
                if sel:
                    yield WindowResult(records[0].timestamp,
                                       records[-1].timestamp, sel)
        else:
            for start, end, records in self._windows(stream):
                sel = [p for p in records if want(p)]
                yield WindowResult(
                    start, end, list(assemble_subtrajectories(sel).values())
                )
                self._checkpoint_barrier()


class PointPolygonTRangeQuery(SpatialOperator, GeomQueryMixin):
    # interner-keyed cross-window state: windows must carry
    # materialized records in the OPERATOR's id space (the
    # chunked decode still batches the parse)
    columnar_windows = False
    telemetry_label = "trange"

    """Trajectories passing through any of a set of query polygons."""

    def _prepare(self, polygons):
        """Precompute the immutable query side once per run: the polygon
        edge batch and the union cell prefilter mask."""
        from spatialflink_tpu.models.batches import EdgeGeomBatch

        gb = EdgeGeomBatch.from_objects(list(polygons), self.grid, self.interner)
        cells = set()
        for poly in polygons:
            cells |= poly.cells
        cell_mask = np.zeros(self.grid.num_cells, bool)
        cell_mask[sorted(cells)] = True
        return gb, cell_mask

    def _cell_prefilter(self, records: List[Point], cell_mask) -> List[Point]:
        """Real pruning, BEFORE the kernel runs (the reference filters the
        stream by cell membership first, ``PointPolygonTRangeQuery.java:53-87``).
        Safe: a point inside a polygon lies in the polygon's bbox, so its cell
        is in the polygon's ``bbox_cells`` superset."""
        return [p for p in records if p.cell >= 0 and cell_mask[p.cell]]

    def _match_mask(self, records: List[Point], gb, ts_base: int) -> np.ndarray:
        """Per-record bool: inside any query polygon. ONE containment
        closure for both paths: ``_filter_stream`` runs it on the whole
        batch single-device or per shard over the mesh (the trajectory
        layer's spatial data parallelism, SURVEY §2.5) — the predicate
        cannot fork between parallelism levels."""
        import jax.numpy as jnp

        from spatialflink_tpu.ops.geom import points_in_geoms

        batch = self._point_batch(records, ts_base)
        g_valid = jnp.asarray(np.asarray(gb.valid))

        def mask_stats(b):
            inside = points_in_geoms(b.x, b.y, gb.edges, gb.edge_mask)
            m = jnp.any(inside & g_valid[None, :], axis=1) & b.valid
            return m, jnp.int32(0), jnp.int32(0)

        mask, _, _ = self._filter_stream(batch, mask_stats)
        return np.asarray(mask)

    def run(self, stream: Iterable[Point], polygons: Sequence[Polygon]
            ) -> Iterator[WindowResult]:
        gb, cell_mask = self._prepare(polygons)
        if self.conf.query_type is QueryType.RealTime:
            for records in self._micro_batches(stream):
                cand = self._cell_prefilter(records, cell_mask)
                if not cand:
                    continue
                m = self._match_mask(cand, gb, records[0].timestamp)
                sel = [cand[i] for i in np.nonzero(m)[0] if i < len(cand)]
                if sel:
                    yield WindowResult(records[0].timestamp,
                                       records[-1].timestamp, sel)
        elif self._panes_active():
            yield from self._run_windowed_panes(stream, gb, cell_mask)
        else:
            # windowed: find matched trajectory ids, then emit those
            # trajectories' FULL window points as sub-trajectories
            # (tRange/PointPolygonTRangeQuery.java:90-177)
            for start, end, records in self._windows(stream):
                cand = self._cell_prefilter(records, cell_mask)
                matched_ids = set()
                if cand:
                    m = self._match_mask(cand, gb, start)
                    matched_ids = {cand[i].obj_id
                                   for i in np.nonzero(m)[0] if i < len(cand)}
                sel = [p for p in records if p.obj_id in matched_ids]
                yield WindowResult(
                    start, end, list(assemble_subtrajectories(sel).values()),
                    extras={"matched_ids": matched_ids},
                )
                self._checkpoint_barrier()

    def _run_windowed_panes(self, stream, gb, cell_mask
                            ) -> Iterator[WindowResult]:
        """Pane-incremental windowed tRange (``--panes``): the containment
        kernel runs once per sealed PANE producing a matched trajectory-ID
        SET (``pane_partial``); a window's matched set is the UNION of its
        cached pane sets (``merge_partials`` = set union) and its
        sub-trajectories re-assemble from the pane record buffers —
        identical output to the full-window path (assembly time-sorts per
        object, so pane concatenation order is immaterial)."""
        from spatialflink_tpu.operators.base import PaneCache
        from spatialflink_tpu.runtime.windows import PaneBuffer

        cache = PaneCache(self.conf.slide_ms)
        self._register_ckpt_pane_cache("pane-cache", cache)

        def pane_partial(precs, pstart):
            cand = self._cell_prefilter(precs, cell_mask)
            if not cand:
                return set()
            m = self._match_mask(cand, gb, pstart)
            return {cand[i].obj_id
                    for i in np.nonzero(m)[0] if i < len(cand)}

        pb = PaneBuffer(self.conf.window_spec(),
                        self.conf.allowed_lateness_ms)
        self._register_ckpt_windows("panes", pb)

        def results(windows):
            for start, end, panes in windows:
                matched_ids: Set[str] = set()
                for pstart, precs in panes:
                    matched_ids |= cache.get(
                        pstart, lambda: pane_partial(precs, pstart))
                cache.evict_before(start)
                sel = [p for _, precs in panes for p in precs
                       if p.obj_id in matched_ids]
                yield WindowResult(
                    start, end, list(assemble_subtrajectories(sel).values()),
                    extras={"matched_ids": matched_ids},
                )
                self._checkpoint_barrier()

        for rec in stream:
            yield from results(pb.add(rec.timestamp, rec))
        yield from results(pb.flush())

    def run_naive(self, stream: Iterable[Point], polygons: Sequence[Polygon]
                  ) -> Iterator[WindowResult]:
        """Exhaustive twin: every polygon tested per point, no cell pruning
        (``tRange/TRangeQuery.java:33-63``)."""
        gb, _cell_mask = self._prepare(polygons)
        for records in self._micro_batches(stream):
            m = self._match_mask(records, gb, records[0].timestamp)
            sel = [records[i] for i in np.nonzero(m)[0] if i < len(records)]
            if sel:
                yield WindowResult(records[0].timestamp,
                                   records[-1].timestamp, sel)


class PointTStatsQuery(SpatialOperator):
    # interner-keyed cross-window state: windows must carry
    # materialized records in the OPERATOR's id space (the
    # chunked decode still batches the parse)
    columnar_windows = False
    telemetry_label = "tstats"

    """Per-trajectory spatial length / temporal length / average speed.

    Realtime mode carries device state across micro-batches (the reference's
    per-objID ValueStates); windowed mode recomputes per window from fresh
    state (``tStats/TStatsQuery.java:153-197``).
    """

    def run(self, stream: Iterable[Point], traj_ids: Optional[Set[str]] = None,
            *, checkpoint_path: Optional[str] = None,
            checkpoint_every: int = 16, resume: bool = True,
            checkpoint_job: Optional[str] = None
            ) -> Iterator[WindowResult]:
        """``checkpoint_path`` makes the realtime run durable: every
        ``checkpoint_every`` micro-batches the device state, the interner, and
        the timestamp base are snapshotted atomically; ``resume`` restores
        them at startup, so a restarted process continues accumulating where
        the previous one stopped (the source replays from its own offset —
        e.g. a Kafka consumer group — this restores the operator state the
        reference would have gotten from Flink checkpointing, were it
        configured; SURVEY §5). ``checkpoint_job`` (the driver's job
        fingerprint) is stored in the checkpoint meta; restoring under a
        DIFFERENT fingerprint refuses instead of producing wrong state."""
        from spatialflink_tpu.runtime.state import TrajStateStore

        allowed = set(traj_ids or ())

        if self.conf.query_type is QueryType.RealTime:
            # per-batch base, with carried last_ts offsets rebased between
            # batches — offsets stay comparable AND bounded (no int32 wrap
            # on unbounded runs). Batches spanning more event time than the
            # device's int32-offset horizon are split host-side first.
            # (mutable cell so the coordinator's snapshot/restore closures
            # see the loop's live store/ts_base/consumed)
            st = {"store": TrajStateStore(), "ts_base": None, "consumed": 0}
            if checkpoint_path and resume and os.path.exists(checkpoint_path):
                (st["store"], st["ts_base"],
                 st["consumed"]) = self._restore_checkpoint(
                     checkpoint_path, job=checkpoint_job)
            self._register_ckpt_tstats(st)
            n_batches = 0
            for records, tail_pending in self._split_by_span_flagged(
                    self._micro_batches(stream)):
                st["consumed"] += len(records)
                if allowed:
                    records = [p for p in records if p.obj_id in allowed]
                tuples = []
                if records:
                    if st["ts_base"] is None:
                        st["ts_base"] = records[0].timestamp
                    elif records[0].timestamp != st["ts_base"]:
                        st["store"].rebase_ts(
                            records[0].timestamp - st["ts_base"])
                        st["ts_base"] = records[0].timestamp
                    tuples = self._update(st["store"], records, st["ts_base"])
                    n_batches += 1
                    if checkpoint_path and \
                            n_batches % max(1, checkpoint_every) == 0:
                        self._save_checkpoint(st["store"], st["ts_base"],
                                              checkpoint_path, st["consumed"],
                                              job=checkpoint_job)
                if tuples:
                    yield WindowResult(records[0].timestamp,
                                       records[-1].timestamp, tuples)
                if not tail_pending:
                    # a span-split batch still holds unprocessed records in
                    # the splitter's frame — a coordinator checkpoint there
                    # would lose them; barrier only at true batch bounds
                    self._checkpoint_barrier()
            if checkpoint_path and n_batches:
                self._save_checkpoint(st["store"], st["ts_base"],
                                      checkpoint_path, st["consumed"],
                                      job=checkpoint_job)
        elif self._panes_active() and not self.distributed:
            # pane-incremental windowed stats; the distributed path keeps
            # its shard-stitch plan (pane partials would stitch the same
            # way, but per-pane sharding of already-small batches buys
            # nothing over the existing whole-window shards)
            yield from self._run_windowed_panes(stream, allowed)
        else:
            for start, end, records in self._windows(stream):
                if allowed:
                    records = [p for p in records if p.obj_id in allowed]
                if self.distributed and records:
                    tuples = self._window_tuples_distributed(records, start)
                else:
                    tuples = self._window_tuples_single(records, start)
                yield WindowResult(start, end, tuples)
                self._checkpoint_barrier()

    def _register_ckpt_tstats(self, st: dict) -> None:
        """Coordinator participant for the realtime device state: the
        TrajStatsState arrays plus capacity/ts_base/consumed/interner meta
        (the same payload the legacy single-file checkpoint carries)."""
        coord = self._ckpt
        if coord is None:
            return

        def snap():
            cp = st["store"].snapshot()
            meta = {"capacity": st["store"].capacity,
                    "ts_base": st["ts_base"], "consumed": st["consumed"],
                    "interner": self.interner.to_list()}
            return ({k: np.asarray(v) for k, v in cp.arrays.items()}, meta)

        def restore(arrays, meta):
            from spatialflink_tpu.runtime.state import (CheckpointableState,
                                                        TrajStateStore)
            from spatialflink_tpu.utils import IdInterner

            cp = CheckpointableState()
            cp.arrays.update(arrays)
            cp.meta["capacity"] = int(meta["capacity"])
            st["store"] = TrajStateStore.restore(cp)
            st["ts_base"] = (None if meta["ts_base"] is None
                             else int(meta["ts_base"]))
            st["consumed"] = int(meta.get("consumed", 0))
            self.interner = IdInterner.from_list(meta["interner"])

        coord.register("tstats", snap, restore)

    def _run_windowed_panes(self, stream, allowed
                            ) -> Iterator[WindowResult]:
        """Pane-incremental windowed tStats (``--panes``): one
        ``tstats_window_summary`` kernel per sealed PANE (``pane_partial`` —
        per-trajectory pair sums, counts, ts extents, boundary coords), and
        per-window stitching of the cached pane tables in time order
        (``merge_partials`` = ``ops.trajectory.tstats_stitch_host``) —
        exactly the contiguous-slice boundary merge the sharded window path
        already does, with panes in place of shards. Pane extents rebase to
        absolute ms at readback (per-pane batches have different int32
        offset bases). Emission: ascending interned id, count >= 2 — the
        same rule/order as the single and distributed paths."""
        from spatialflink_tpu.operators.base import PaneCache
        from spatialflink_tpu.ops.trajectory import (tstats_stitch_host,
                                                     tstats_window_summary)
        from spatialflink_tpu.runtime.windows import PaneBuffer
        from spatialflink_tpu.utils import bucket_size

        cache = PaneCache(self.conf.slide_ms)
        self._register_ckpt_pane_cache("pane-cache", cache)
        i64 = np.int64

        def pane_partial(precs, pstart) -> Optional[dict]:
            recs = ([p for p in precs if p.obj_id in allowed]
                    if allowed else precs)
            if not recs:
                return None
            batch = self._point_batch(recs, pstart)
            m = bucket_size(len(self.interner))
            s = tstats_window_summary(batch, m=m)
            cnt = np.asarray(s.count).astype(i64)
            present = cnt > 0
            return dict(
                spatial=np.asarray(s.spatial), count=cnt,
                min_ts=np.where(present,
                                np.asarray(s.min_ts).astype(i64) + pstart,
                                np.iinfo(i64).max),
                max_ts=np.where(present,
                                np.asarray(s.max_ts).astype(i64) + pstart,
                                np.iinfo(i64).min),
                first_x=np.asarray(s.first_x), first_y=np.asarray(s.first_y),
                last_x=np.asarray(s.last_x), last_y=np.asarray(s.last_y),
            )

        pb = PaneBuffer(self.conf.window_spec(),
                        self.conf.allowed_lateness_ms)
        self._register_ckpt_windows("panes", pb)

        def results(windows):
            for start, end, panes in windows:
                parts = []
                for pstart, precs in panes:
                    part = cache.get(pstart,
                                     lambda: pane_partial(precs, pstart))
                    if part is not None:
                        parts.append(part)
                cache.evict_before(start)
                tuples: List[Tuple] = []
                if parts:
                    sp, tm, cnt = tstats_stitch_host(parts)
                    for o in np.nonzero(cnt >= 2)[0]:
                        t, s = float(tm[o]), float(sp[o])
                        tuples.append((self.interner.lookup(int(o)), s,
                                       int(round(t)),
                                       s / t if t > 0 else 0.0))
                yield WindowResult(start, end, tuples)
                self._checkpoint_barrier()

        for rec in stream:
            yield from results(pb.add(rec.timestamp, rec))
        yield from results(pb.flush())

    def _window_tuples_single(self, records: List[Point], start: int
                              ) -> List[Tuple]:
        from spatialflink_tpu.runtime.state import TrajStateStore

        store = TrajStateStore()  # fresh per window
        tuples = self._update(store, records, start)
        # windowed mode reports one tuple per trajectory (final stats)
        final: Dict[str, Tuple] = {}
        for t in tuples:
            final[t[0]] = t
        return list(final.values())

    def _sorted_dedup(self, records: List[Point]) -> List[Point]:
        """Global (interned objID, ts) stable sort + exact-duplicate drop —
        the precondition of the sharded window summary (each shard must hold
        a contiguous slice of every trajectory's run, and the kernel's tie
        rule must have nothing left to drop ACROSS a shard boundary).
        Results are unchanged single-device: the kernel sorts and
        tie-drops internally anyway."""
        keyed = sorted((self.interner.intern(p.obj_id), p.timestamp, i)
                       for i, p in enumerate(records))
        out: List[Point] = []
        last = None
        for k_oid, k_ts, i in keyed:
            if (k_oid, k_ts) == last:
                continue
            last = (k_oid, k_ts)
            out.append(records[i])
        return out

    def _window_tuples_distributed(self, records: List[Point], start: int
                                   ) -> List[Tuple]:
        """Mesh-sharded windowed stats: per-shard summaries + boundary
        stitch (parallel.ops.distributed_tstats_window), with elastic
        degraded retry at halved widths (a failure surviving every
        multi-device width raises — see ``_degrade_mesh``). Emission order
        is ascending interned id — the same first-seen order the single
        path's dict preserves."""
        from spatialflink_tpu.parallel.ops import distributed_tstats_window
        from spatialflink_tpu.utils import bucket_size

        recs = self._sorted_dedup(records)
        batch = self._point_batch(recs, start)
        # bucketed capacity: the raw interner size grows with every new
        # trajectory, and m is a STATIC jit arg — unbucketed it would
        # recompile the whole shard_map program per churny window (padded
        # ids have count 0 and fail the cnt >= 2 emit rule)
        m = bucket_size(len(self.interner))

        def dist(mesh, sharded):
            sp, tp, cnt = distributed_tstats_window(mesh, sharded, m=m)
            sp, tp = np.asarray(sp), np.asarray(tp)
            out: List[Tuple] = []
            for o in np.nonzero(np.asarray(cnt) >= 2)[0]:
                t, s = float(tp[o]), float(sp[o])
                out.append((self.interner.lookup(int(o)), s,
                            int(round(t)), s / t if t > 0 else 0.0))
            return out

        return self._eval_degradable(
            lambda: self._window_tuples_single(records, start), dist, batch)

    def _save_checkpoint(self, store, ts_base: int, path: str,
                         consumed: int = 0,
                         job: Optional[str] = None) -> None:
        cp = store.snapshot()
        cp.meta["ts_base"] = int(ts_base)
        cp.meta["interner"] = self.interner.to_list()
        # number of source records the checkpointed state reflects; a
        # replaying source (file) must skip this many on resume or
        # already-applied records double-count (offset-managed sources such
        # as a Kafka consumer group seek instead and can ignore it)
        cp.meta["consumed"] = int(consumed)
        if job:
            # the job fingerprint guards resume-under-a-different-config:
            # restoring tStats state into a query it was not accumulated
            # for silently produces wrong numbers (see _check_job)
            cp.meta["job"] = job
        cp.save(path)

    @staticmethod
    def _check_job(meta: dict, path: str, job: Optional[str]) -> None:
        from spatialflink_tpu.runtime.checkpoint import check_job_fingerprint

        check_job_fingerprint(meta.get("job"), job, path)

    def _restore_checkpoint(self, path: str, job: Optional[str] = None):
        from spatialflink_tpu.runtime.state import CheckpointableState, TrajStateStore
        from spatialflink_tpu.utils import IdInterner

        cp = CheckpointableState.load(path)
        self._check_job(cp.meta, path, job)
        self.interner = IdInterner.from_list(cp.meta["interner"])
        return (TrajStateStore.restore(cp), int(cp.meta["ts_base"]),
                int(cp.meta.get("consumed", 0)))

    @staticmethod
    def checkpoint_consumed(path: str) -> int:
        """Resume offset recorded in a checkpoint (0 if none/absent)."""
        from spatialflink_tpu.runtime.state import checkpoint_consumed

        return checkpoint_consumed(path)

    _SPAN_HORIZON_MS = 2**30  # device ts offsets are int32; stay well inside

    def _split_by_span(self, batches) -> Iterator[List[Point]]:
        for records, _tail_pending in self._split_by_span_flagged(batches):
            yield records

    def _split_by_span_flagged(self, batches
                               ) -> Iterator[Tuple[List[Point], bool]]:
        """``(records, tail_pending)`` — ``tail_pending`` marks a span-split
        yield whose source batch still holds unprocessed records in this
        frame; a checkpoint barrier there would snapshot state missing
        records the source taps already reported (and lose them on
        resume)."""
        for records in batches:
            cur: List[Point] = []
            base = None
            for p in records:
                if base is None:
                    base = p.timestamp
                elif abs(p.timestamp - base) > self._SPAN_HORIZON_MS:
                    yield cur, True
                    cur, base = [], p.timestamp
                cur.append(p)
            if cur:
                yield cur, False

    def _update(self, store, records: List[Point], ts_base: int) -> List[Tuple]:
        from spatialflink_tpu.ops.trajectory import tstats_update

        batch = self._point_batch(records, ts_base)
        store.ensure(len(self.interner))
        store.state, out = tstats_update(store.state, batch)
        emit = np.asarray(out.emit)
        oids = np.asarray(out.obj_id)[emit]
        sp = np.asarray(out.spatial)[emit]
        tp = np.asarray(out.temporal)[emit]
        speed = np.asarray(out.speed)[emit]
        return [
            (self.interner.lookup(int(o)), float(s), int(round(float(t))), float(v))
            for o, s, t, v in zip(oids, sp, tp, speed)
        ]


class PointTAggregateQuery(SpatialOperator):
    # interner-keyed cross-window state: windows must carry
    # materialized records in the OPERATOR's id space (the
    # chunked decode still batches the parse)
    columnar_windows = False
    telemetry_label = "taggregate"

    """Per-cell heatmap of trajectory lengths.

    ``aggregate`` in {SUM, AVG, MIN, MAX, COUNT, ALL}. Realtime mode merges
    (cell, objID) group extents into host state with stale-trajectory
    eviction after ``traj_deletion_threshold_ms``
    (``tAggregate/TAggregateQuery.java:367-376``). CountBased mode runs
    per-cell count windows — the ONE operator family where the reference
    implements them (``TAggregateQuery.java:381-494``,
    ``countWindow(size, slide)`` over a ``GlobalWindow``): for each cell, a
    window of the last ``window_size_ms``-as-count points fires every
    ``slide_ms``-as-count arrivals.
    """


    def run(self, stream: Iterable[Point], aggregate: str = "SUM",
            traj_deletion_threshold_ms: int = 0, *,
            checkpoint_path: Optional[str] = None,
            checkpoint_every: int = 16, resume: bool = True,
            checkpoint_job: Optional[str] = None
            ) -> Iterator[WindowResult]:
        agg = aggregate.upper()
        if self.conf.query_type is QueryType.RealTime:
            yield from self._run_realtime(
                stream, agg, traj_deletion_threshold_ms,
                checkpoint_path=checkpoint_path,
                checkpoint_every=checkpoint_every, resume=resume,
                checkpoint_job=checkpoint_job)
            return
        if self.conf.query_type is QueryType.CountBased:
            yield from self._run_count_windows(stream, agg)
            return
        if self._panes_active() and not self.distributed:
            yield from self._run_windowed_panes(stream, agg)
            return
        for start, end, records in self._windows(stream):
            if not records:
                yield WindowResult(start, end, [])
                continue
            batch = self._point_batch(records, start)
            out = self._stream_dispatch(batch, self._window_local(agg),
                                        self._window_dist(agg))
            if agg == "ALL":
                groups = out
                first = np.asarray(groups.first)
                records_out = list(zip(
                    np.asarray(groups.cell)[first].tolist(),
                    [self.interner.lookup(int(o))
                     for o in np.asarray(groups.obj_id)[first]],
                    np.asarray(groups.length)[first].tolist(),
                ))
                yield WindowResult(start, end, records_out)
            else:
                yield WindowResult(start, end, [],
                                   extras={"heatmap": np.asarray(out)})
            self._checkpoint_barrier()

    def _run_windowed_panes(self, stream, agg: str) -> Iterator[WindowResult]:
        """Pane-incremental windowed tAggregate (``--panes``): one
        ``taggregate_group_extents`` kernel per sealed PANE, read back as
        (cell, objID, min_ts, max_ts) rows rebased to absolute ms
        (``pane_partial``); windows extent-merge the cached pane rows
        (``merge_partials`` = ``ops.trajectory.taggregate_merge_extents_host``
        — the pane twin of the distributed shard merge: a group split across
        panes must merge [min, max] BEFORE measuring its length) and derive
        the heatmap/ALL records from the merged groups."""
        from spatialflink_tpu.operators.base import PaneCache
        from spatialflink_tpu.ops.trajectory import (
            taggregate_group_extents, taggregate_merge_extents_host)
        from spatialflink_tpu.runtime.windows import PaneBuffer

        if agg not in ("ALL", "SUM", "AVG", "MIN", "MAX", "COUNT"):
            # fail fast like the device path's first window would
            raise ValueError(f"unknown aggregate {agg!r}")
        cache = PaneCache(self.conf.slide_ms)
        self._register_ckpt_pane_cache("pane-cache", cache)

        def pane_partial(precs, pstart):
            batch = self._point_batch(precs, pstart)
            e = taggregate_group_extents(batch,
                                         num_cells=self.grid.num_cells)
            first = np.asarray(e.first)
            return (np.asarray(e.cell)[first],
                    np.asarray(e.obj_id)[first],
                    np.asarray(e.min_ts)[first].astype(np.int64) + pstart,
                    np.asarray(e.max_ts)[first].astype(np.int64) + pstart)

        pb = PaneBuffer(self.conf.window_spec(),
                        self.conf.allowed_lateness_ms)
        self._register_ckpt_windows("panes", pb)

        def results(windows):
            for start, end, panes in windows:
                parts = [cache.get(pstart,
                                   lambda: pane_partial(precs, pstart))
                         for pstart, precs in panes]
                cache.evict_before(start)
                merged = taggregate_merge_extents_host(parts)
                if agg == "ALL":
                    records_out = [
                        (c, self.interner.lookup(int(o)), int(mx - mn))
                        for (c, o), (mn, mx) in sorted(merged.items())
                    ]
                    yield WindowResult(start, end, records_out)
                else:
                    yield WindowResult(
                        start, end, [],
                        extras={"heatmap": self._heatmap_from_groups(
                            merged, agg)})
                self._checkpoint_barrier()

        for rec in stream:
            yield from results(pb.add(rec.timestamp, rec))
        yield from results(pb.flush())

    def _heatmap_from_groups(self, merged: Dict, agg: str) -> np.ndarray:
        """Dense (num_cells,) float32 heatmap from merged (cell, objID) ->
        extent groups — the host mirror of ``ops.trajectory
        .taggregate_heatmap`` over pane-merged groups."""
        num_cells = self.grid.num_cells
        hm = np.zeros(num_cells, np.float32)
        if not merged:
            return hm
        cells = np.fromiter((k[0] for k in merged), np.int64, len(merged))
        lengths = np.fromiter((mx - mn for mn, mx in merged.values()),
                              np.float64, len(merged))
        if agg in ("AVG", "COUNT"):
            counts = np.zeros(num_cells, np.int64)
            np.add.at(counts, cells, 1)
        if agg in ("SUM", "AVG"):
            acc = np.zeros(num_cells, np.float64)
            np.add.at(acc, cells, lengths)
            if agg == "AVG":
                acc = np.where(counts > 0, acc / np.maximum(counts, 1), 0.0)
            hm = acc.astype(np.float32)
        elif agg == "COUNT":
            hm = counts.astype(np.float32)
        elif agg == "MIN":
            acc = np.full(num_cells, np.inf)
            np.minimum.at(acc, cells, lengths)
            hm = np.where(np.isfinite(acc), acc, 0.0).astype(np.float32)
        elif agg == "MAX":
            acc = np.full(num_cells, -np.inf)
            np.maximum.at(acc, cells, lengths)
            hm = np.where(np.isfinite(acc), acc, 0.0).astype(np.float32)
        else:
            # same error surface as the device twin (taggregate_heatmap):
            # --panes must not turn a typo'd aggregate into a silent SUM
            raise ValueError(f"unknown aggregate {agg!r}")
        return hm

    def _window_local(self, agg: str):
        """Single-device window evaluator: groups for ALL, heatmap
        otherwise."""
        from spatialflink_tpu.ops.trajectory import (taggregate_groups,
                                                     taggregate_heatmap)

        def local(batch):
            groups = taggregate_groups(batch, num_cells=self.grid.num_cells)
            if agg == "ALL":
                return groups
            return taggregate_heatmap(groups, num_cells=self.grid.num_cells,
                                      agg=agg)
        return local

    def _window_dist(self, agg: str):
        """Mesh twin: per-shard group extents, gathered + extent-merged
        (groups split at shard boundaries measure identically to the
        single-device sort — parallel.ops.distributed_taggregate)."""
        from spatialflink_tpu.parallel.ops import distributed_taggregate

        def dist(mesh, sharded):
            return distributed_taggregate(
                mesh, sharded, num_cells=self.grid.num_cells, agg=agg)
        return dist

    def _run_count_windows(self, stream, agg) -> Iterator[WindowResult]:
        """Per-cell sliding COUNT windows (Flink ``countWindow(size, slide)``
        semantics): keyed by cell, the trigger fires every ``slide`` arrivals
        in that cell and evaluates the last ``size`` points. Aggregation body
        matches the time-window process function: per-object trajLength =
        max - min timestamp within the window's points for that cell
        (``TAggregateQuery.java:381-494``).

        In count mode ``window_size_ms``/``slide_ms`` are COUNTS, mirroring
        the reference passing the same windowSize/windowSlideStep config
        values to ``countWindow``.
        """
        from collections import deque

        size = max(1, int(self.conf.window_size_ms))
        slide = max(1, int(self.conf.slide_ms))
        buffers: Dict[int, deque] = {}
        arrivals: Dict[int, int] = {}
        for p in stream:
            if p.cell < 0:
                continue  # reference filters null-gridID points first
            buf = buffers.setdefault(p.cell, deque(maxlen=size))
            buf.append(p)
            arrivals[p.cell] = arrivals.get(p.cell, 0) + 1
            if arrivals[p.cell] % slide == 0:
                result = self._count_window_result(p.cell, list(buf), agg)
                # SUM/AVG require sum > 0 and MIN/MAX a multi-point object;
                # the reference collects nothing otherwise (ALL/COUNT records
                # are never empty)
                if result.records:
                    yield result

    def _count_window_result(self, cell: int, pts: List[Point], agg: str
                             ) -> WindowResult:
        # MIN/MAX replicate CountWindowProcessFunction's per-point tracker
        # scan (TAggregateQuery.java:438-494): a length updates the trackers
        # only when an object is *re-sighted* (>= 2 points in the window), and
        # MIN is the minimum over intermediate lengths at each re-sighting —
        # an object's length at its 2nd point can undercut every final
        # length. No multi-point object => the reference emits nothing.
        extents: Dict[str, Tuple[int, int]] = {}
        min_len = min_oid = max_len = max_oid = None
        for p in pts:
            if p.obj_id in extents:
                mn, mx = extents[p.obj_id]
                mn, mx = min(mn, p.timestamp), max(mx, p.timestamp)
                extents[p.obj_id] = (mn, mx)
                length = mx - mn
                if max_len is None or length > max_len:
                    max_len, max_oid = length, p.obj_id
                if min_len is None or length < min_len:
                    min_len, min_oid = length, p.obj_id
            else:
                extents[p.obj_id] = (p.timestamp, p.timestamp)
        lengths = {oid: mx - mn for oid, (mn, mx) in extents.items()}
        n_objs = len(lengths)
        start = min(p.timestamp for p in pts)
        end = max(p.timestamp for p in pts)
        extras = {"cell": cell, "num_objects": n_objs, "aggregate": agg}
        if agg == "ALL":
            records = [(cell, lengths)]
        elif agg == "SUM":
            s = sum(lengths.values())
            records = [(cell, s)] if s > 0 else []
        elif agg == "AVG":
            s = sum(lengths.values())
            records = [(cell, round(s / n_objs))] if s > 0 else []
        elif agg == "MIN":
            records = [(cell, min_oid, min_len)] if min_len is not None else []
        elif agg == "MAX":
            records = [(cell, max_oid, max_len)] if max_len is not None else []
        elif agg == "COUNT":
            records = [(cell, n_objs)]
        else:
            records = [(cell, lengths)]
        return WindowResult(start, end, records, extras)

    def _run_realtime(self, stream, agg, eviction_ms, *,
                      checkpoint_path=None, checkpoint_every=16, resume=True,
                      checkpoint_job=None
                      ) -> Iterator[WindowResult]:
        # host state: (cell, objID) -> [min_ts, max_ts, last_seen], held in
        # the array-backed _ExtentStore. The reference's MapState does a full
        # per-output scan distributed over 30 subtasks
        # (TAggregateQuery.java:53-377); here ONE host thread owns the state,
        # so per-batch updates and the per-output heatmap must be O(state)
        # numpy, not O(state) Python (round-3 VERDICT weak #9). State grows
        # with distinct (cell, trajectory) pairs unless eviction_ms > 0
        # bounds it — production streams should set trajDeletionThreshold.
        # This is exactly the unbounded state most in need of checkpointing:
        # checkpoint_path snapshots the extent map (+ consumed offset)
        # every checkpoint_every micro-batches, like tStats.
        st = {"store": _ExtentStore(), "consumed": 0}
        if checkpoint_path and resume and os.path.exists(checkpoint_path):
            st["store"], st["consumed"] = self._restore_checkpoint(
                checkpoint_path, job=checkpoint_job)
        self._register_ckpt_taggregate(st)
        n_batches = 0
        for records in self._micro_batches(stream):
            st["consumed"] += len(records)
            n_batches += 1
            latest = st["store"].update_batch(records)
            if eviction_ms > 0:
                st["store"].evict(latest, eviction_ms)
            if checkpoint_path and n_batches % max(1, checkpoint_every) == 0:
                self._save_checkpoint(st["store"], checkpoint_path,
                                      st["consumed"], job=checkpoint_job)
            heatmap = st["store"].aggregate(agg, self.grid.num_cells)
            extras = {"heatmap": heatmap, "aggregate": agg}
            if agg == "ALL":
                # the realtime heatmap form has no per-(cell, objID) record
                # shape, so ALL is served as per-cell SUM — flag the
                # substitution instead of silently relabeling (windowed mode
                # returns true per-pair records for ALL)
                extras["heatmap_semantics"] = "SUM"
            yield WindowResult(
                records[0].timestamp, records[-1].timestamp, [],
                extras=extras,
            )
            self._checkpoint_barrier()
        if checkpoint_path and n_batches:
            self._save_checkpoint(st["store"], checkpoint_path,
                                  st["consumed"], job=checkpoint_job)

    def _register_ckpt_taggregate(self, st: dict) -> None:
        """Coordinator participant for the realtime extent map (the same
        rows the legacy single-file checkpoint persists)."""
        coord = self._ckpt
        if coord is None:
            return

        def snap():
            cells, oids, extents = st["store"].rows()
            return ({"cell": cells, "extent": extents},
                    {"obj_id": oids, "consumed": st["consumed"]})

        def restore(arrays, meta):
            st["store"] = _ExtentStore.from_rows(
                arrays.get("cell", np.empty(0, np.int64)),
                meta.get("obj_id", []),
                arrays.get("extent", np.empty((0, 3), np.int64)))
            st["consumed"] = int(meta.get("consumed", 0))

        coord.register("taggregate", snap, restore)

    @staticmethod
    def _save_checkpoint(store: "_ExtentStore", path: str,
                         consumed: int, job: Optional[str] = None) -> None:
        from spatialflink_tpu.runtime.state import CheckpointableState

        cells, oids, extents = store.rows()
        cp = CheckpointableState()
        cp.arrays["cell"] = cells
        cp.arrays["extent"] = extents
        cp.meta["obj_id"] = oids
        cp.meta["consumed"] = int(consumed)
        if job:
            cp.meta["job"] = job
        cp.save(path)

    @staticmethod
    def _restore_checkpoint(path: str, job: Optional[str] = None):
        from spatialflink_tpu.runtime.state import CheckpointableState

        cp = CheckpointableState.load(path)
        PointTStatsQuery._check_job(cp.meta, path, job)
        cells = cp.arrays.get("cell", np.empty(0, np.int64))
        extents = cp.arrays.get("extent", np.empty((0, 3), np.int64))
        oids = cp.meta.get("obj_id", [])
        store = _ExtentStore.from_rows(cells, oids, extents)
        return store, int(cp.meta.get("consumed", 0))

    @staticmethod
    def checkpoint_consumed(path: str) -> int:
        """Resume offset recorded in a checkpoint (0 if none/absent)."""
        from spatialflink_tpu.runtime.state import checkpoint_consumed

        return checkpoint_consumed(path)

class _ExtentStore:
    """Array-backed (cell, objID) -> [min_ts, max_ts, last_seen] extent map
    for the realtime tAggregate state.

    Per-batch updates touch the dict only for row allocation; min/max/seen
    merging, eviction, and the per-output heatmap are vectorized numpy over
    the row arrays (np.minimum.at / bincount-style scatters). Evicted rows
    are tombstoned (``alive`` mask) and the arrays compact once dead rows
    exceed half the store — so steady-state per-output cost is O(live rows)
    numpy, never O(rows) Python.
    """

    _I64_MAX = np.iinfo(np.int64).max
    _I64_MIN = np.iinfo(np.int64).min

    def __init__(self, capacity: int = 1024):
        self.index: Dict[Tuple[int, str], int] = {}
        self.keys: List[Tuple[int, str]] = []
        self.cells = np.zeros(capacity, np.int64)
        self.ext = np.zeros((capacity, 3), np.int64)
        self.alive = np.zeros(capacity, bool)
        self.n = 0

    def _ensure(self, need: int) -> None:
        cap = self.cells.shape[0]
        if need <= cap:
            return
        while cap < need:
            cap *= 2
        grow = cap - self.cells.shape[0]
        self.cells = np.concatenate([self.cells, np.zeros(grow, np.int64)])
        self.ext = np.concatenate([self.ext, np.zeros((grow, 3), np.int64)])
        self.alive = np.concatenate([self.alive, np.zeros(grow, bool)])

    def update_batch(self, records) -> int:
        """Merge one micro-batch; returns the batch's latest timestamp."""
        rows = np.empty(len(records), np.int64)
        ts = np.empty(len(records), np.int64)
        m = 0
        latest = 0
        for p in records:
            if p.cell < 0:
                continue
            if p.timestamp > latest:
                latest = p.timestamp
            key = (p.cell, p.obj_id)
            r = self.index.get(key)
            if r is None:
                r = self.n
                self._ensure(r + 1)
                self.index[key] = r
                self.keys.append(key)
                self.cells[r] = p.cell
                self.ext[r] = (self._I64_MAX, self._I64_MIN, self._I64_MIN)
                self.alive[r] = True
                self.n += 1
            rows[m] = r
            ts[m] = p.timestamp
            m += 1
        rows, ts = rows[:m], ts[:m]
        np.minimum.at(self.ext[:, 0], rows, ts)
        np.maximum.at(self.ext[:, 1], rows, ts)
        np.maximum.at(self.ext[:, 2], rows, ts)
        return latest

    def evict(self, latest: int, eviction_ms: int) -> None:
        """Tombstone rows unseen for eviction_ms (deleteHaltedTrajectories,
        ``TAggregateQuery.java:367-376``); compact when mostly dead."""
        live = self.alive[: self.n]
        stale = live & (latest - self.ext[: self.n, 2] > eviction_ms)
        if not stale.any():
            return
        for r in np.nonzero(stale)[0]:
            del self.index[self.keys[r]]
        self.alive[: self.n] &= ~stale
        if self.n and self.alive[: self.n].sum() < self.n // 2:
            self._compact()

    def _compact(self) -> None:
        keep = np.nonzero(self.alive[: self.n])[0]
        self.cells[: keep.size] = self.cells[keep]
        self.ext[: keep.size] = self.ext[keep]
        self.keys = [self.keys[r] for r in keep]
        self.alive[:] = False
        self.alive[: keep.size] = True
        self.n = keep.size
        self.index = {k: i for i, k in enumerate(self.keys)}

    def aggregate(self, agg: str, num_cells: int) -> np.ndarray:
        """Per-cell heatmap over live rows — all vectorized scatters."""
        live = np.nonzero(self.alive[: self.n])[0]
        cells = self.cells[live]
        lengths = (self.ext[live, 1] - self.ext[live, 0]).astype(np.float64)
        hm = np.zeros(num_cells, np.float64)
        if agg in ("AVG", "COUNT"):  # only they consume the counts scatter
            counts = np.zeros(num_cells, np.int64)
            np.add.at(counts, cells, 1)
        if agg in ("SUM", "AVG"):
            np.add.at(hm, cells, lengths)
            if agg == "AVG":
                hm = np.where(counts > 0, hm / np.maximum(counts, 1), 0.0)
        elif agg == "MIN":
            hm[:] = np.inf
            np.minimum.at(hm, cells, lengths)
        elif agg == "MAX":
            hm[:] = -np.inf
            np.maximum.at(hm, cells, lengths)
        elif agg == "COUNT":
            hm = counts.astype(np.float64)
        else:  # ALL behaves like SUM for the heatmap form
            np.add.at(hm, cells, lengths)
        hm[~np.isfinite(hm)] = 0.0
        return hm

    def rows(self):
        """(cells, obj_ids, extents) of live rows — the checkpoint payload
        (same format as the round-3 dict snapshot)."""
        live = np.nonzero(self.alive[: self.n])[0]
        return (self.cells[live].copy(),
                [self.keys[r][1] for r in live],
                self.ext[live].copy())

    @classmethod
    def from_rows(cls, cells, oids, extents) -> "_ExtentStore":
        store = cls(capacity=max(1024, len(oids)))
        for c, o, e in zip(cells, oids, extents):
            key = (int(c), str(o))
            r = store.n
            store.index[key] = r
            store.keys.append(key)
            store.cells[r] = int(c)
            store.ext[r] = (int(e[0]), int(e[1]), int(e[2]))
            store.alive[r] = True
            store.n += 1
        return store


class PointPointTJoinQuery(SpatialOperator):
    # interner-keyed cross-window state: windows must carry
    # materialized records in the OPERATOR's id space (the
    # chunked decode still batches the parse)
    columnar_windows = False
    telemetry_label = "tjoin"

    """Trajectory-trajectory proximity join: one output per
    (trajectory, partner) pair per window, keeping the LATEST co-located
    timestamp (``tJoin/PointPointTJoinQuery.java:133-177``).

    Windowed mode joins the deduped pairs back to both streams' windowed
    trajectories and emits *sub-trajectory LineString pairs* — a pair appears
    only when BOTH trajectories have >= 2 points in the window, exactly like
    the reference's joins against ``GenerateWindowedTrajectory`` output
    (``PointPointTJoinQuery.java:183-338``; the >=2-point rule is
    ``TJoinQuery.java:184``). Realtime mode emits point pairs.
    """

    # two-stream join: the count trigger is ambiguous across sides — keep
    # the construction-time rejection like the core joins
    supports_count_windows = False

    def _inner(self, prune_cells: bool = True):
        from spatialflink_tpu.operators.join_query import PointPointJoinQuery

        windowed = self.conf.query_type is not QueryType.RealTime
        outer = self

        class _CapturingJoin(PointPointJoinQuery):
            # windowed tJoin needs each window's full per-side record lists
            # to rebuild the trajectories the pairs join back to
            def _join_window(self, start, end, recs_a, recs_b, radius, **kw):
                res = super()._join_window(start, end, recs_a, recs_b,
                                           radius, **kw)
                if windowed:
                    res.extras["_recs_a"] = recs_a
                    res.extras["_recs_b"] = recs_b
                return res

        inner = _CapturingJoin(self.conf, self.grid)
        inner.interner = self.interner
        inner.prune_cells = prune_cells
        # windowed tJoin re-assembles each window's FULL per-side record
        # lists into trajectories; the pane-pair block path evaluates
        # _join_window per pane pair, so the captured extras would hold
        # pane fragments — keep the inner join on full windows (pane mode
        # has no mergeable partial for this family)
        inner.conf.panes = False
        return inner, windowed

    def run(self, ordinary: Iterable[Point], query_stream: Iterable[Point],
            radius: float) -> Iterator[WindowResult]:
        inner, windowed = self._inner()
        # flattened: columnar_windows is off for this family, and the inner
        # join would otherwise take a chunked decode stream's chunks whole
        for res in inner.run(iter(ordinary), iter(query_stream), radius):
            yield self._post(res, windowed)

    def run_single(self, stream: Iterable[Point], radius: float
                   ) -> Iterator[WindowResult]:
        """Self-join variant skipping identical objIDs
        (``tJoin/PointPointTJoinQuery.java:341-435``)."""
        records = list(stream)
        inner, windowed = self._inner()
        for res in inner.run(iter(records), iter(list(records)), radius):
            res.records = [(a, b) for a, b in res.records if a.obj_id != b.obj_id]
            yield self._post(res, windowed)

    def run_naive(self, ordinary: Iterable[Point], query_stream: Iterable[Point],
                  radius: float) -> Iterator[WindowResult]:
        """All-pairs twin without cell pruning
        (``tJoin/TJoinQuery.java:61-155``); the exact distance filter still
        applies."""
        inner, windowed = self._inner(prune_cells=False)
        for res in inner.run(iter(ordinary), iter(query_stream), radius):
            yield self._post(res, windowed)

    def _post(self, res: WindowResult, windowed: bool) -> WindowResult:
        res = self._dedup(res)
        if windowed:
            res = self._to_trajectory_pairs(res)
        return res

    @staticmethod
    def _dedup(res: WindowResult) -> WindowResult:
        best: Dict[Tuple[str, str], Tuple[Point, Point]] = {}
        for a, b in res.records:
            key = (a.obj_id, b.obj_id)
            cur = best.get(key)
            if cur is None or max(a.timestamp, b.timestamp) > max(
                cur[0].timestamp, cur[1].timestamp
            ):
                best[key] = (a, b)
        return WindowResult(res.window_start, res.window_end,
                            list(best.values()), res.extras)

    @staticmethod
    def _to_trajectory_pairs(res: WindowResult) -> WindowResult:
        """Deduped point pairs -> (LineString, LineString) sub-trajectory
        pairs over the window's full per-side records; pairs whose side has
        fewer than 2 window points are dropped (no LineString exists to join
        against, ``TJoinQuery.java:184``)."""
        recs_a = res.extras.pop("_recs_a", None) or []
        recs_b = res.extras.pop("_recs_b", None) or []
        a_ids = {a.obj_id for a, _ in res.records}
        b_ids = {b.obj_id for _, b in res.records}
        subs_a = assemble_subtrajectories(
            [p for p in recs_a if p.obj_id in a_ids])
        subs_b = assemble_subtrajectories(
            [p for p in recs_b if p.obj_id in b_ids])
        pairs = []
        for a, b in res.records:
            la = subs_a.get(a.obj_id)
            lb = subs_b.get(b.obj_id)
            if isinstance(la, LineString) and isinstance(lb, LineString):
                pairs.append((la, lb))
        return WindowResult(res.window_start, res.window_end, pairs,
                            res.extras)


class PointPointTKNNQuery(SpatialOperator):
    # interner-keyed cross-window state: windows must carry
    # materialized records in the OPERATOR's id space (the
    # chunked decode still batches the parse)
    columnar_windows = False
    telemetry_label = "tknn"

    """k nearest trajectories to a query point within ``radius`` (exact
    radius enforced, unlike plain kNN)."""

    def run(self, stream: Iterable[Point], query_point: Point, radius: float,
            k: Optional[int] = None) -> Iterator[WindowResult]:
        yield from self._run(stream, query_point, radius, k, prune=True)

    def run_naive(self, stream: Iterable[Point], query_point: Point,
                  radius: float, k: Optional[int] = None
                  ) -> Iterator[WindowResult]:
        """Exhaustive twin (``tKnn/PointPointTKNNQuery.java:59-78``)."""
        yield from self._run(stream, query_point, radius, k, prune=False)

    def _run(self, stream, query_point, radius, k, prune) -> Iterator[WindowResult]:
        import jax.numpy as jnp

        from spatialflink_tpu.ops.knn import knn_point

        k = k or self.conf.k
        nb_layers = (
            self.grid.candidate_layers(radius) if (prune and radius > 0) else self.grid.n
        )

        def eval_batch(records, ts_base):
            if not records:
                return []
            batch = self._point_batch(records, ts_base)

            def single():
                return knn_point(
                    batch, query_point.x, query_point.y,
                    jnp.int32(query_point.cell), radius, nb_layers,
                    n=self.grid.n, k=k, enforce_radius=radius > 0,
                )

            if self.distributed:
                # sharded per-device top-k + gather re-merge, same kernel
                # per shard (enforce_radius threads through)
                from spatialflink_tpu.parallel.ops import distributed_knn

                res = self._eval_degradable(
                    single,
                    lambda mesh, sb: distributed_knn(
                        mesh, sb, query_point.x, query_point.y,
                        jnp.int32(query_point.cell), radius, nb_layers,
                        n=self.grid.n, k=k, enforce_radius=radius > 0,
                    ),
                    batch)
            else:
                res = single()
            valid = np.asarray(res.valid)
            oids = [self.interner.lookup(int(o))
                    for o in np.asarray(res.obj_id)[valid]]
            dists = np.asarray(res.dist)[valid]
            selected_ids = set(oids)
            subs = assemble_subtrajectories(
                [p for p in records if p.obj_id in selected_ids]
            )
            return [(oid, float(d), subs.get(oid)) for oid, d in zip(oids, dists)]

        for result in self._drive(stream, eval_batch):
            result.extras["k"] = k
            yield result

    def run_multi(self, stream: Iterable[Point], query_points, radius: float,
                  k: Optional[int] = None) -> Iterator[WindowResult]:
        """Q query points, each answered with its k nearest TRAJECTORIES, in
        ONE dispatch per window (the trajectory layer's multi-query
        extension — ``ops.knn.knn_point_multi`` with the tKnn exact-radius
        rule threaded through). ``records[q]`` holds
        (objID, min_distance, sub_trajectory) triples for
        ``query_points[q]``; sub-trajectories are assembled once for the
        union of all queries' selected trajectories."""
        from spatialflink_tpu.ops.knn import knn_point_multi_stats

        k = k or self.conf.k
        qx, qy, qc = self._query_point_arrays(query_points)
        nb_layers = (
            self.grid.candidate_layers(radius) if radius > 0 else self.grid.n
        )

        def local(b):
            return knn_point_multi_stats(
                b, qx, qy, qc, radius, nb_layers, n=self.grid.n, k=k,
                enforce_radius=radius > 0)

        def eval_batch(records, ts_base):
            if not records:
                return [[] for _ in query_points]
            batch = self._point_batch(records, ts_base)
            res, _evals = self._knn_multi_result(batch, local, k)
            valid = np.asarray(res.valid)
            oid_rows = np.asarray(res.obj_id)
            dist_rows = np.asarray(res.dist)
            per_q = []
            union = set()
            for q in range(len(query_points)):
                oids = [self.interner.lookup(int(o))
                        for o in oid_rows[q][valid[q]]]
                per_q.append((oids, dist_rows[q][valid[q]]))
                union.update(oids)
            subs = assemble_subtrajectories(
                [p for p in records if p.obj_id in union])
            return [
                [(oid, float(d), subs.get(oid)) for oid, d in zip(oids, ds)]
                for oids, ds in per_q
            ]

        for result in self._multi_results(stream, eval_batch):
            result.extras["k"] = k
            result.extras["queries"] = len(query_points)
            yield result


# Reference base-class names
TFilterQuery = PointTFilterQuery
TRangeQuery = PointPolygonTRangeQuery
TStatsQuery = PointTStatsQuery
TAggregateQuery = PointTAggregateQuery
TJoinQuery = PointPointTJoinQuery
TKNNQuery = PointPointTKNNQuery
