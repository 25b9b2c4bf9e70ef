"""Point-stream x point-query continuous range query.

Reference: ``spatialOperators/range/PointPointRangeQuery.java`` — realtime
(:43-83), window (:85-141), incremental (:144-245). Semantics preserved:
guaranteed-cell points are emitted without distance computation; candidate
points pass iff exact distance <= r; approximate mode emits all GN∪CN points.
"""

from __future__ import annotations

from typing import Iterable, Iterator, List

import jax.numpy as jnp

from spatialflink_tpu.models import Point
from spatialflink_tpu.operators.base import (
    Deferred,
    GeomQueryMixin,
    QueryType,
    SpatialOperator,
    WindowResult,
)
from spatialflink_tpu.ops.range import range_filter_point_stats


class PointPointRangeQuery(SpatialOperator):
    telemetry_label = "range"

    #: pane-incremental hooks (``--panes``): the window evaluator IS the
    #: per-pane partial evaluator (the same mask kernel over a pane-sized
    #: batch), and disjoint panes union by concatenation — one definition
    #: for every filter-shaped range pair.
    merge_partials = staticmethod(SpatialOperator._pane_concat)

    def run(self, stream: Iterable[Point], query_point: Point, radius: float
            ) -> Iterator[WindowResult]:
        # --adaptive-grid: the query's GN∪CN leaf mask, version-cached so a
        # mid-run repartition invalidates it on the next window (the point
        # query tightens to its exact fine cell inside a split hot cell)
        mask_cache = self._leaf_mask_cache(
            lambda: self.conf.adaptive_grid.neighboring_leaf_mask(
                radius, query_point.cell,
                point=(query_point.x, query_point.y)))
        return self._drive(
            stream, lambda records, ts_base: self._eval(records, query_point,
                                                        radius, ts_base,
                                                        mask_cache),
            pane_merge=self.merge_partials,
        )

    # ---------------------------------------------------------------- #

    def _eval(self, records: List[Point], query_point: Point, radius: float,
              ts_base: int, mask_cache=None) -> List[Point]:
        if not records:
            return []
        pre = self._prefilter(records, mask_cache, ts_base)
        if pre is not None:
            idx, batch = pre
            if batch is None:  # no candidate leaves in this window
                return []
            mask, stats = self._range_mask(batch, query_point, radius)
            return self._defer_mask_select_at(mask, records, idx, stats)
        batch = self._point_batch(records, ts_base)
        mask, stats = self._range_mask(batch, query_point, radius)
        return self._defer_mask_select(mask, records, stats)

    def _mask_stats_fn(self, query_point: Point, radius: float):
        """Per-batch (mask, gn_bypassed, dist_evals) closure — the same
        shape every range operator exposes; _filter_stream runs it whole
        single-device or per shard on the mesh."""
        args = (
            query_point.x, query_point.y, jnp.int32(query_point.cell), radius,
            self.grid.guaranteed_layers(radius),
            self.grid.candidate_layers(radius),
        )

        def mask_stats(b):
            mask, _, gn_c, evals = range_filter_point_stats(
                b, *args, n=self.grid.n, approximate=self.conf.approximate,
            )
            return mask, gn_c, evals

        return mask_stats

    def _range_mask(self, batch, query_point: Point, radius: float):
        """(mask, (gn_bypassed, dist_evals)) for one window batch — the
        pruning-counter scalars are psum-merged on the distributed path like
        every other operator family."""
        mask, gn_bypassed, dist_evals = self._filter_stream(
            batch, self._mask_stats_fn(query_point, radius))
        return mask, (gn_bypassed, dist_evals)

    # ---------------------------------------------------------------- #

    def _multi_mask_stats(self, query_points, radius: float):
        """The per-batch multi-mask closure shared by run_multi and
        run_dynamic."""
        from spatialflink_tpu.ops.range import range_filter_point_multi_masks

        qx, qy, qc = self._query_point_arrays(query_points)
        args = (radius, self.grid.guaranteed_layers(radius),
                self.grid.candidate_layers(radius))

        def multi_mask_stats(b):
            return range_filter_point_multi_masks(
                b, qx, qy, qc, *args, n=self.grid.n,
                approximate=self.conf.approximate)

        return multi_mask_stats

    def run_multi(self, stream: Iterable[Point],
                  query_points: List[Point], radius: float
                  ) -> Iterator[WindowResult]:
        """Q continuous range queries over ONE stream in ONE dispatch per
        window (TPU-native extension; the reference runs one query per job,
        ``StreamingJob.java:470``). ``records[q]`` holds the records within
        ``radius`` of ``query_points[q]`` under the usual GN-bypass/CN
        semantics; ``extras["queries"] = Q``. Pruning counters aggregate
        across the Q queries of each dispatch; with ``conf.devices`` the
        stream batch shards over the mesh like every other operator."""
        def union_leaf_mask():
            # --adaptive-grid: a record outside EVERY query's GN∪CN leaf
            # set cannot appear in any per-query result — the Q×N kernel
            # shrinks to Q×kept (one leaf-space sweep for the whole fleet)
            return self.conf.adaptive_grid.union_neighboring_leaf_mask(
                radius, [(q.cell, (q.x, q.y)) for q in query_points])

        return self._run_multi_filter(
            stream, len(query_points),
            self._multi_mask_stats(query_points, radius),
            self._point_batch, leaf_mask_builder=union_leaf_mask)

    def run_dynamic(self, stream: Iterable[Point], registry, radius: float
                    ) -> Iterator[WindowResult]:
        """Standing-query serving: the Q-axis fleet comes from a live
        ``runtime.queryplane.QueryRegistry`` — queries admitted/updated/
        retired MID-RUN take effect at the next window, padded to size
        buckets so fleet changes within a bucket never recompile, with
        the adaptive-grid union leaf mask rebuilt on every fleet-version
        bump (exactly as it is on grid-version bumps)."""
        ag = self.conf.adaptive_grid
        leaf_union = None
        if ag is not None:
            def leaf_union(pts):
                return ag.union_neighboring_leaf_mask(
                    radius, [(p.cell, (p.x, p.y)) for p in pts])

        return self._run_dynamic_filter(
            stream, registry, radius, self._multi_mask_stats,
            self._point_batch, leaf_union_builder=leaf_union)

    def run_incremental(self, stream: Iterable[Point], query_point: Point,
                        radius: float) -> Iterator[WindowResult]:
        """Incremental sliding windows: carry the previous window's survivors
        and only evaluate records newer than the previous slide
        (``PointPointRangeQuery.queryIncremental``, ``:144-245``)."""
        if self.conf.query_type is QueryType.CountBased:
            raise NotImplementedError(
                "run_incremental carries survivors by TIME cutoff; count "
                "windows have no fixed temporal slide — use run()")
        prev: dict = {}  # id(record) -> record surviving from previous window
        prev_window_start = None
        for start, end, records in self._windows(stream):
            if prev_window_start is None:
                fresh = records
            else:
                cutoff = start + self.conf.window_size_ms - self.conf.slide_ms
                # records at/after the previous window's end are new
                fresh = [r for r in records if r.timestamp >= cutoff]
            sel = self._eval(fresh, query_point, radius, start)
            selected_new = sel.finish() if isinstance(sel, Deferred) else sel
            carried = [
                r for r in prev.values() if r.timestamp >= start
            ]
            out = {id(r): r for r in carried}
            out.update({id(r): r for r in selected_new})
            prev = out
            prev_window_start = start
            yield WindowResult(start, end, list(out.values()))


class PointGeomRangeQuery(SpatialOperator, GeomQueryMixin):
    telemetry_label = "range"

    merge_partials = staticmethod(SpatialOperator._pane_concat)

    """Point stream x polygon/linestring query
    (``range/PointPolygonRangeQuery.java``, ``PointLineStringRangeQuery``).

    Approximate mode filters on the bbox distance instead of the exact
    geometry distance (the reference's approximateQuery flag)."""

    def _mask_stats_fn(self, query_geom, radius: float):
        """Per-batch (mask, gn_bypassed, dist_evals) closure over the
        precomputed query-side arrays — the single source for both the
        single-device and mesh paths (and the bench harness)."""
        gn, cn, _nb = self._query_masks(query_geom, radius)
        q_edges, q_mask, q_areal = self._query_edges(query_geom)
        q_bbox = self._query_bbox(query_geom)

        def mask_stats(batch):
            from spatialflink_tpu.ops.distances import point_bbox_dist
            from spatialflink_tpu.ops.geom import points_to_single_geom_dist
            from spatialflink_tpu.ops.range import range_filter_masks_stats

            if self.conf.approximate:
                dists = point_bbox_dist(batch.x, batch.y,
                                        q_bbox[0], q_bbox[1], q_bbox[2], q_bbox[3])
            else:
                dists = points_to_single_geom_dist(batch, q_edges, q_mask, q_areal)
            return range_filter_masks_stats(batch, gn, cn, dists, radius)

        return mask_stats

    def run(self, stream: Iterable[Point], query_geom, radius: float
            ) -> Iterator[WindowResult]:
        mask_stats = self._mask_stats_fn(query_geom, radius)
        # --adaptive-grid: leaf mask unioned over the geometry's base cells
        # (UniformGrid.java:193-222 union semantics, refined per level)
        mask_cache = self._leaf_mask_cache(
            lambda: self.conf.adaptive_grid.neighboring_leaf_mask(
                radius, self._query_cells(query_geom)))

        def eval_batch(records, ts_base):
            if not records:
                return []
            pre = self._prefilter(records, mask_cache, ts_base)
            if pre is not None:
                idx, batch = pre
                if batch is None:
                    return []
                mask, gn_c, evals = self._filter_stream(batch, mask_stats)
                return self._defer_mask_select_at(mask, records, idx,
                                                 (gn_c, evals))
            batch = self._point_batch(records, ts_base)
            mask, gn_c, evals = self._filter_stream(batch, mask_stats)
            return self._defer_mask_select(mask, records, (gn_c, evals))

        return self._drive(stream, eval_batch, pane_merge=self.merge_partials)

    def _multi_mask_stats(self, query_geoms, radius: float):
        from spatialflink_tpu.ops.geom import range_points_to_geom_queries

        qgb = self._query_geom_batch(query_geoms)
        gn, cn = self._stack_query_masks(query_geoms, radius,
                                         which=("gn", "cn"))
        return lambda batch: range_points_to_geom_queries(
            batch, qgb, gn, cn, radius, approximate=self.conf.approximate)

    def run_multi(self, stream: Iterable[Point], query_geoms,
                  radius: float) -> Iterator[WindowResult]:
        """Q polygon/linestring QUERIES over one point stream in ONE
        dispatch per window (``ops.geom.range_points_to_geom_queries``);
        same contract as ``PointPointRangeQuery.run_multi``."""
        def union_leaf_mask():
            return self.conf.adaptive_grid.union_neighboring_leaf_mask(
                radius, [(self._query_cells(q), None) for q in query_geoms])

        return self._run_multi_filter(
            stream, len(query_geoms),
            self._multi_mask_stats(query_geoms, radius),
            self._point_batch, leaf_mask_builder=union_leaf_mask)


class GeomPointRangeQuery(SpatialOperator, GeomQueryMixin):
    telemetry_label = "range"

    merge_partials = staticmethod(SpatialOperator._pane_concat)

    """Polygon/linestring stream x point query
    (``range/PolygonPointRangeQuery.java``, ``LineStringPointRangeQuery``).
    GN-subset rule: a geometry passes without distance math only if ALL its
    cells are guaranteed neighbors (``:54-87``)."""

    def _mask_stats_fn(self, query_point: Point, radius: float):
        gn, _cn, nb = self._query_masks(query_point, radius)

        def mask_stats(geoms):
            from spatialflink_tpu.ops.distances import point_bbox_dist
            from spatialflink_tpu.ops.geom import (
                geom_cells_all_within,
                geom_cells_any_within,
                point_to_geoms_dist,
            )
            from spatialflink_tpu.ops.range import range_filter_geom_stream_stats

            all_gn = geom_cells_all_within(geoms.cells, geoms.cells_mask, gn)
            any_nb = geom_cells_any_within(geoms.cells, geoms.cells_mask, nb)
            if self.conf.approximate:
                dists = point_bbox_dist(query_point.x, query_point.y,
                                        geoms.bbox[:, 0], geoms.bbox[:, 1],
                                        geoms.bbox[:, 2], geoms.bbox[:, 3])
            else:
                dists = point_to_geoms_dist(query_point.x, query_point.y, geoms)
            return range_filter_geom_stream_stats(
                all_gn, any_nb, dists, radius, geoms.valid)

        return mask_stats

    def run(self, stream: Iterable, query_point: Point, radius: float
            ) -> Iterator[WindowResult]:
        mask_stats = self._mask_stats_fn(query_point, radius)

        def eval_batch(records, ts_base):
            if not records:
                return []
            geoms = self._geom_batch(records, ts_base)
            mask, gn_c, evals = self._filter_stream(geoms, mask_stats)
            return self._defer_mask_select(mask, records, (gn_c, evals))

        return self._drive(stream, eval_batch, pane_merge=self.merge_partials)

    def _multi_mask_stats(self, query_points, radius: float):
        from spatialflink_tpu.ops.geom import range_geoms_to_point_queries

        qx, qy, _qc = self._query_point_arrays(query_points)
        gn, nb = self._stack_query_masks(query_points, radius,
                                         which=("gn", "nb"))
        return lambda geoms: range_geoms_to_point_queries(
            geoms, qx, qy, gn, nb, radius,
            approximate=self.conf.approximate)

    def run_multi(self, stream: Iterable, query_points,
                  radius: float) -> Iterator[WindowResult]:
        """Q query POINTS over one polygon/linestring stream in ONE dispatch
        per window (``ops.geom.range_geoms_to_point_queries`` — GN-subset
        rule applied per query)."""
        return self._run_multi_filter(
            stream, len(query_points),
            self._multi_mask_stats(query_points, radius),
            self._geom_batch)

    def run_dynamic(self, stream: Iterable, registry, radius: float
                    ) -> Iterator[WindowResult]:
        """Standing point-query serving over a geometry STREAM — the same
        live-registry contract as ``PointPointRangeQuery.run_dynamic``
        (no leaf prefilter: geometry streams keep their full batch)."""
        return self._run_dynamic_filter(
            stream, registry, radius, self._multi_mask_stats,
            self._geom_batch)


class GeomGeomRangeQuery(SpatialOperator, GeomQueryMixin):
    telemetry_label = "range"

    merge_partials = staticmethod(SpatialOperator._pane_concat)

    """Polygon/linestring stream x polygon/linestring query
    (``range/PolygonPolygonRangeQuery.java`` and the 3 sibling pairs)."""

    def _mask_stats_fn(self, query_geom, radius: float):
        gn, _cn, nb = self._query_masks(query_geom, radius)
        q_edges, q_mask, q_areal = self._query_edges(query_geom)
        q_bbox = self._query_bbox(query_geom)

        def mask_stats(geoms):
            from spatialflink_tpu.ops.geom import (
                geom_cells_all_within,
                geom_cells_any_within,
                geoms_bbox_dist,
                geoms_to_single_geom_dist,
            )
            from spatialflink_tpu.ops.range import range_filter_geom_stream_stats

            all_gn = geom_cells_all_within(geoms.cells, geoms.cells_mask, gn)
            any_nb = geom_cells_any_within(geoms.cells, geoms.cells_mask, nb)
            if self.conf.approximate:
                dists = geoms_bbox_dist(geoms, q_bbox)
            else:
                dists = geoms_to_single_geom_dist(geoms, q_edges, q_mask, q_areal)
            return range_filter_geom_stream_stats(
                all_gn, any_nb, dists, radius, geoms.valid)

        return mask_stats

    def run(self, stream: Iterable, query_geom, radius: float
            ) -> Iterator[WindowResult]:
        mask_stats = self._mask_stats_fn(query_geom, radius)

        def eval_batch(records, ts_base):
            if not records:
                return []
            geoms = self._geom_batch(records, ts_base)
            mask, gn_c, evals = self._filter_stream(geoms, mask_stats)
            return self._defer_mask_select(mask, records, (gn_c, evals))

        return self._drive(stream, eval_batch, pane_merge=self.merge_partials)

    def _multi_mask_stats(self, query_geoms, radius: float):
        from spatialflink_tpu.ops.geom import range_geoms_to_geom_queries

        qgb = self._query_geom_batch(query_geoms)
        gn, nb = self._stack_query_masks(query_geoms, radius,
                                         which=("gn", "nb"))
        return lambda geoms: range_geoms_to_geom_queries(
            geoms, qgb, gn, nb, radius, approximate=self.conf.approximate)

    def run_multi(self, stream: Iterable, query_geoms,
                  radius: float) -> Iterator[WindowResult]:
        """Q query GEOMETRIES over one polygon/linestring stream in ONE
        dispatch per window (``ops.geom.range_geoms_to_geom_queries`` — the
        Q queries ride one exact-capacity padded edge batch)."""
        return self._run_multi_filter(
            stream, len(query_geoms),
            self._multi_mask_stats(query_geoms, radius),
            self._geom_batch)


# Reference-named aliases (stream type x query type), SURVEY §2.2
PointPolygonRangeQuery = PointGeomRangeQuery
PointLineStringRangeQuery = PointGeomRangeQuery
PolygonPointRangeQuery = GeomPointRangeQuery
LineStringPointRangeQuery = GeomPointRangeQuery
PolygonPolygonRangeQuery = GeomGeomRangeQuery
PolygonLineStringRangeQuery = GeomGeomRangeQuery
LineStringPolygonRangeQuery = GeomGeomRangeQuery
LineStringLineStringRangeQuery = GeomGeomRangeQuery
