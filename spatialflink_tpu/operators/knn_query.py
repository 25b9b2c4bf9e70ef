"""Point-stream x point-query continuous kNN.

Reference: ``spatialOperators/knn/PointPointKNNQuery.java`` (two-stage
per-cell top-k + global dedup merge). Here the whole window is one kernel:
masked distances -> objID dedup -> top-k (ops.knn), optionally sharded over a
mesh as one compiled program with an all-gather merge
(parallel.ops.knn_mesh_stats), which removes the reference's parallelism-1
``windowAll`` stage.

The radius argument prunes the candidate *cells* only — windowed kNN in the
reference does not radius-filter exact distances (``:152-183``); radius 0
disables pruning entirely.
"""

from __future__ import annotations

from typing import Iterable, Iterator, List, Optional, Tuple

import jax.numpy as jnp

from spatialflink_tpu.models import Point
from spatialflink_tpu.operators.base import (
    Deferred,
    GeomQueryMixin,
    SpatialOperator,
    WindowResult,
)
from spatialflink_tpu.ops.knn import knn_point_stats


def _knn_device_merge(op, k: int, interner, n_queries=None):
    """Device-resident pane merge factory for the kNN families: each sealed
    window's merge is ONE device gather+re-top-k over its panes' RESIDENT
    partial arrays (``ops.knn.merge_knn_device``); only the merged result
    crosses to host. Returns None — host-merge fallback, identical results
    — when any part is host-resident (checkpoint-restored partials, empty
    realtime evals). Pruning-counter scalars ride each pane's deferred
    payload and count exactly once (``PanePartial.stats_done``)."""
    def merge(parts):
        devs = []
        for p in parts:
            v = p.value
            d = getattr(v, "device_result", None)
            if (not isinstance(v, Deferred) or not isinstance(d, tuple)
                    or len(d) != 3
                    # every partial must share ONE id space (a restored
                    # host-layout pane or a plain-record pane resolves via
                    # a different interner — merging raw device ids across
                    # spaces would mint garbage; fall back to the host
                    # merge, which resolves each part through its own)
                    or getattr(v, "interner", None) is not interner):
                return None
            devs.append(d)
        from spatialflink_tpu.ops.knn import (merge_knn_device,
                                              merge_knn_device_multi)

        if n_queries is None:
            merged = merge_knn_device([d[0] for d in devs], k)
        else:
            merged = merge_knn_device_multi([d[0] for d in devs], k)

        def collect(r):
            import numpy as np

            for p, d in zip(parts, devs):
                if not p.stats_done:
                    op._record_pruning_stats(d[1], d[2])
                    p.stats_done = True
            valid = np.asarray(r.valid)
            oids = np.asarray(r.obj_id)
            dists = np.asarray(r.dist)
            if n_queries is None:
                return [(interner.lookup(int(o)), float(dd))
                        for o, dd in zip(oids[valid], dists[valid])]
            return [
                [(interner.lookup(int(o)), float(dd))
                 for o, dd in zip(oids[q][valid[q]], dists[q][valid[q]])]
                for q in range(n_queries)
            ]

        return Deferred(merged, collect)

    return merge


def merge_partials(parts, k: int, interner):
    """Pane-incremental merge for every kNN pair: per-pane top-k partial
    lists -> the window's exact top-k (``ops.knn.merge_topk_host`` — the
    host twin of the distributed gather+re-top-k merge). ``interner`` is
    the one the partials' ids were resolved through: its ``intern`` is the
    tie key that reproduces the device top-k's equal-distance order, so
    pane windows stay identical to full recompute even when two objects
    tie at the k-th place."""
    from spatialflink_tpu.ops.knn import merge_topk_host

    return merge_topk_host(parts, k, tie_key=interner.intern)


def _merge_partials_multi(n_queries: int, k: int, interner):
    """Per-query pane merge for the multi-query kNN paths."""
    def merge(parts):
        return [merge_partials([p[q] for p in parts], k, interner)
                for q in range(n_queries)]
    return merge


class PointPointKNNQuery(SpatialOperator):
    telemetry_label = "knn"

    def run(self, stream: Iterable[Point], query_point: Point, radius: float,
            k: Optional[int] = None) -> Iterator[WindowResult]:
        k = k or self.conf.k
        # a batched decode stream resolves ids through ITS interner (the
        # stream's one obj-id space); plain record streams keep the
        # operator's — the pane merges (host tie-break and device resolve)
        # must read the same space the partials were built in
        tie = getattr(stream, "interner", None)
        if tie is None:  # NOT `or`: a still-empty interner is falsy
            tie = self.interner
        for result in self._drive(
            stream, lambda records, ts_base: self._eval(records, query_point,
                                                        radius, k, ts_base),
            pane_merge=lambda parts: merge_partials(parts, k, tie),
            pane_device_merge=_knn_device_merge(self, k, tie),
        ):
            result.extras["k"] = k
            yield result

    def _eval(self, records: List[Point], query_point: Point, radius: float,
              k: int, ts_base: int) -> List[Tuple[str, float]]:
        if not records:
            return []
        batch = self._point_batch(records, ts_base)
        res, dist_evals = self._knn_result(batch, query_point, radius, k)
        ri = getattr(records, "interner", None)
        d = self._defer_knn(res, interner=ri, dist_evals=dist_evals)
        # the id space this partial's device ids live in (device pane merge
        # refuses to mix spaces)
        d.interner = ri if ri is not None else self.interner
        return d

    def _nb_layers(self, radius: float) -> int:
        """Candidate-cell layer count; radius 0 disables pruning (all cells
        neighbor, ``UniformGrid.java:264-266``) — ONE rule for run() and
        run_multi()."""
        return (self.grid.n if radius == 0
                else self.grid.candidate_layers(radius))

    def _knn_result(self, batch, query_point: Point, radius: float, k: int):
        """(KnnResult, dist_evals) over one window batch — the count rides
        the same dispatch and feeds the pruning counter. Single-device:
        ``ops.knn.knn_point_stats`` on the whole batch. With
        ``conf.devices`` the point dim is sharded and the one compiled mesh
        program ``parallel.ops.knn_mesh_stats`` runs the same kernel per
        shard, all-gathers the k-sized partials and re-merges them — the
        two-stage merge of SURVEY §2.5 without the reference's
        parallelism-1 windowAll stage."""
        nb_layers = self._nb_layers(radius)
        def local(b):
            return knn_point_stats(
                b, query_point.x, query_point.y,
                jnp.int32(query_point.cell), radius, nb_layers,
                n=self.grid.n, k=k, strategy=self._knn_strategy())

        def on_mesh(mesh, sb):
            from spatialflink_tpu.parallel.ops import knn_mesh_stats

            return knn_mesh_stats(
                sb, query_point.x, query_point.y,
                jnp.int32(query_point.cell), radius, mesh=mesh,
                nb_layers=nb_layers, n=self.grid.n, k=k,
                strategy=self._knn_strategy())

        return self._stream_dispatch(batch, local, on_mesh)

    def _multi_local(self, query_points, radius: float, k: int):
        """The per-batch multi-kernel closure shared by run_multi and
        run_dynamic."""
        from spatialflink_tpu.ops.knn import knn_point_multi_stats

        qx, qy, qc = self._query_point_arrays(query_points)
        nb_layers = self._nb_layers(radius)

        def local(b):
            return knn_point_multi_stats(
                b, qx, qy, qc, radius, nb_layers, n=self.grid.n, k=k,
                strategy=self._knn_strategy())

        return local

    def run_multi(self, stream: Iterable[Point],
                  query_points: "List[Point]", radius: float,
                  k: Optional[int] = None) -> Iterator[WindowResult]:
        """Q continuous kNN queries over ONE stream in ONE dispatch per
        window — a TPU-native extension with no reference analogue (GeoFlink
        wires exactly one query object per job, ``StreamingJob.java:470``,
        so Q queries cost Q jobs re-reading the stream). The vmapped kernel
        (``ops.knn.knn_point_multi``) answers all Q queries over the
        window's single device residency.

        Each WindowResult's ``records`` is a list of Q per-query result
        lists (``records[q]`` = the (objID, distance) pairs for
        ``query_points[q]``), with ``extras["queries"] = Q``. All queries
        share ``radius`` (one candidate-cell layer count). With
        ``conf.devices`` the STREAM batch shards over the mesh and per-shard
        (Q, k) partials merge per query
        (parallel.ops.distributed_stream_knn_multi) — 8-dev ≡ 1-dev."""
        k = k or self.conf.k
        local = self._multi_local(query_points, radius, k)
        tie = getattr(stream, "interner", None)
        if tie is None:  # NOT `or`: a still-empty interner is falsy
            tie = self.interner

        def eval_batch(records, ts_base):
            if not records:
                return [[] for _ in query_points]
            batch = self._point_batch(records, ts_base)
            res, evals = self._knn_multi_result(batch, local, k)
            ri = getattr(records, "interner", None)
            d = self._defer_knn_multi(res, jnp.sum(evals), interner=ri)
            d.interner = ri if ri is not None else self.interner
            return d

        for result in self._multi_results(
                stream, eval_batch,
                pane_merge=_merge_partials_multi(len(query_points), k, tie),
                pane_device_merge=_knn_device_merge(
                    self, k, tie, n_queries=len(query_points))):
            result.extras["k"] = k
            result.extras["queries"] = len(query_points)
            yield result

    def run_dynamic(self, stream: Iterable[Point], registry, radius: float,
                    k: Optional[int] = None) -> Iterator[WindowResult]:
        """Standing kNN serving from a live ``QueryRegistry``: the fleet's
        query points pad to size buckets on the vmapped (B, k) kernel —
        admissions within a bucket repad instead of recompiling — and
        only the LIVE slots demultiplex (``extras['query_ids']``), with
        the per-query distance-evaluation counters gated by the valid
        mask so padded slots count nothing. Full-window evaluation (no
        pane partials: they are fleet-shaped — see
        ``_run_dynamic_filter``'s rationale)."""
        import numpy as np

        from spatialflink_tpu.utils import telemetry as _telemetry

        k = k or self.conf.k
        label = self.telemetry_label or type(self).__name__
        state: dict = {"v": -1, "entries": [], "live": 0, "local": None,
                       "jvalid": None}

        def ensure() -> None:
            if state["v"] == registry.fleet_version:
                return
            entries, qpts, valid = registry.padded_fleet(self.grid)
            local = jvalid = None
            if entries:
                local = self._multi_local(qpts, radius, k)
                jvalid = jnp.asarray(valid)
            state.update(v=registry.fleet_version, entries=entries,
                         live=len(entries), local=local, jvalid=jvalid)

        window_ids: dict = {}

        def eval_batch(records, ts_base):
            registry.apply()
            ensure()
            live = state["live"]
            window_ids[ts_base] = [e.id for e in state["entries"]]
            if not live:
                return []
            if not records:
                return [[] for _ in range(live)]
            batch = self._point_batch(records, ts_base)
            res, evals = self._knn_multi_result(batch, state["local"], k)
            ri = getattr(records, "interner", None)
            interner = ri if ri is not None else self.interner
            tel = _telemetry.active()
            acct = tel.tenants if tel is not None else None
            # (id, tenant) per live slot, captured NOW: a later apply()
            # may repad before the deferred demux runs
            slots = ([(e.id, e.spec.tenant) for e in state["entries"]]
                     if acct is not None else None)

            def rows(r):
                valid = np.asarray(r.valid)
                oids = np.asarray(r.obj_id)
                dists = np.asarray(r.dist)
                if acct is not None:
                    # resolve the parked dispatch span across live slots
                    # proportional to each slot's valid-neighbor count —
                    # padded slots (rows >= live) never weigh in
                    weights = valid[:live].sum(axis=1)
                    acct.resolve(label, ts_base, [
                        (qid, tenant, int(c))
                        for (qid, tenant), c in zip(slots, weights)])
                return [
                    [(interner.lookup(int(o)), float(d))
                     for o, d in zip(oids[q][valid[q]], dists[q][valid[q]])]
                    for q in range(live)
                ]

            return self._defer_with_stats(
                res, (0, jnp.sum(evals * state["jvalid"])), rows)

        for result in self._drive(stream, eval_batch):
            ids = window_ids.pop(result.window_start, [])
            result.extras["query_ids"] = ids
            result.extras["queries"] = len(ids)
            result.extras["k"] = k
            yield result


class _GenericKnn(SpatialOperator, GeomQueryMixin):
    telemetry_label = "knn"

    """Shared kNN driver: subclasses provide the batch builder and the
    per-batch (eligible, dists) closure.

    Reference semantics for every pair (e.g.
    ``knn/PointPolygonKNNQuery.java:100-183``): radius prunes cells only;
    approximate mode substitutes bbox distance; global merge dedups objID
    keeping min distance (here: one dedup+top-k kernel). With
    ``conf.devices`` the stream batch is sharded and per-shard partials are
    all-gathered + re-merged (parallel.ops.distributed_stream_knn) — the same
    closure computes eligibility/distances per shard, so the two paths cannot
    fork semantically.
    """

    def _knn_eval(self, batch, elig_dists, k: int):
        """(KnnResult, dist_evals) over one batch — THE single kNN
        evaluation body of run(): distributed runs the same closure per
        shard, single-device goes through the module-jitted
        knn_eligible_stats."""
        def single(b):
            from spatialflink_tpu.ops.knn import knn_eligible_stats

            eligible, dists = elig_dists(b)
            return knn_eligible_stats(b.obj_id, dists, eligible, k=k,
                                      strategy=self._knn_strategy())

        from spatialflink_tpu.parallel.ops import distributed_stream_knn

        return self._stream_dispatch(
            batch, single,
            lambda mesh, sb: distributed_stream_knn(
                mesh, sb, elig_dists, k=k, strategy=self._knn_strategy()))

    def run(self, stream, query, radius: float, k: Optional[int] = None
            ) -> Iterator[WindowResult]:
        k = k or self.conf.k
        setup = self._setup(query, radius)
        tie = getattr(stream, "interner", None)
        if tie is None:  # NOT `or`: a still-empty interner is falsy
            tie = self.interner

        def elig_dists(batch):
            return self._elig_dists(batch, setup)

        def eval_batch(records, ts_base):
            if not records:
                return []
            res, dist_evals = self._knn_eval(
                self._batch(records, ts_base), elig_dists, k)
            ri = getattr(records, "interner", None)
            d = self._defer_knn(res, interner=ri, dist_evals=dist_evals)
            d.interner = ri if ri is not None else self.interner
            return d

        for result in self._drive(
                stream, eval_batch,
                pane_merge=lambda parts: merge_partials(parts, k, tie),
                pane_device_merge=_knn_device_merge(self, k, tie)):
            result.extras["k"] = k
            yield result

    def _drive_multi(self, stream, n_queries: int, local, k: int
                     ) -> Iterator[WindowResult]:
        """Shared run_multi loop: ``local(batch)`` is the class's
        multi-kernel closure (:meth:`_multi_local`) over the class's stream
        batch form (:meth:`_batch`)."""
        tie = getattr(stream, "interner", None)
        if tie is None:  # NOT `or`: a still-empty interner is falsy
            tie = self.interner

        def eval_batch(records, ts_base):
            if not records:
                return [[] for _ in range(n_queries)]
            batch = self._batch(records, ts_base)
            res, evals = self._knn_multi_result(batch, local, k)
            ri = getattr(records, "interner", None)
            d = self._defer_knn_multi(res, jnp.sum(evals), interner=ri)
            d.interner = ri if ri is not None else self.interner
            return d

        for result in self._multi_results(
                stream, eval_batch,
                pane_merge=_merge_partials_multi(n_queries, k, tie),
                pane_device_merge=_knn_device_merge(self, k, tie,
                                                    n_queries=n_queries)):
            result.extras["k"] = k
            result.extras["queries"] = n_queries
            yield result

    def run_multi(self, stream, queries, radius: float,
                  k: Optional[int] = None) -> Iterator[WindowResult]:
        """Q queries in ONE dispatch per window — contract as
        ``PointPointKNNQuery.run_multi`` (the class docstrings name the
        kernel each pair rides)."""
        k = k or self.conf.k
        return self._drive_multi(stream, len(queries),
                                 self._multi_local(queries, radius, k), k)


class _GeomStreamKnn(_GenericKnn):
    """Geometry-stream kNN base: EdgeGeomBatch construction (shared by
    GeomPoint and GeomGeom)."""

    def _batch(self, records, ts_base):
        return self._geom_batch(records, ts_base)


class PointGeomKNNQuery(_GenericKnn):
    """Point stream x polygon/linestring query (``PointPolygonKNNQuery``,
    ``PointLineStringKNNQuery``)."""

    def _multi_local(self, query_geoms, radius: float, k: int):
        """Q polygon/linestring QUERIES over a point stream: the Q query
        geometries ride one padded edge batch and the existing (N, G)
        lattice (``ops.geom.knn_points_to_geom_queries``); approximate mode
        substitutes bbox distances."""
        from spatialflink_tpu.ops.geom import knn_points_to_geom_queries

        gb = self._query_geom_batch(query_geoms)
        nb_masks = self._stack_query_nb(query_geoms, radius)

        def local(b):
            return knn_points_to_geom_queries(
                b, gb, nb_masks, k=k, strategy=self._knn_strategy(),
                approximate=self.conf.approximate)

        return local

    def _setup(self, query, radius):
        return dict(nb=self._query_nb(query, radius),
                    edges=self._query_edges(query), bbox=self._query_bbox(query))

    def _batch(self, records, ts_base):
        return self._point_batch(records, ts_base)

    def _elig_dists(self, batch, setup):
        from spatialflink_tpu.ops.distances import point_bbox_dist
        from spatialflink_tpu.ops.geom import points_to_single_geom_dist
        from spatialflink_tpu.ops.knn import point_stream_eligibility

        eligible = point_stream_eligibility(batch.cell, batch.valid, setup["nb"])
        q_edges, q_mask, q_areal = setup["edges"]
        if self.conf.approximate:
            b = setup["bbox"]
            dists = point_bbox_dist(batch.x, batch.y, b[0], b[1], b[2], b[3])
        else:
            dists = points_to_single_geom_dist(batch, q_edges, q_mask, q_areal)
        return eligible, dists


class GeomPointKNNQuery(_GeomStreamKnn):
    """Polygon/linestring stream x point query (``PolygonPointKNNQuery``,
    ``LineStringPointKNNQuery``)."""

    def _multi_local(self, query_points, radius: float, k: int):
        """Q query POINTS over a polygon/linestring stream
        (``ops.geom.knn_geoms_to_point_queries``)."""
        from spatialflink_tpu.ops.geom import knn_geoms_to_point_queries

        qx, qy, _qc = self._query_point_arrays(query_points)
        nb_masks = self._stack_query_nb(query_points, radius)
        return lambda geoms: knn_geoms_to_point_queries(
            geoms, qx, qy, nb_masks, k=k, strategy=self._knn_strategy(),
            approximate=self.conf.approximate)

    def _setup(self, query, radius):
        return dict(nb=self._query_nb(query, radius), query=query)

    def _elig_dists(self, geoms, setup):
        from spatialflink_tpu.ops.distances import point_bbox_dist
        from spatialflink_tpu.ops.geom import geom_cells_any_within, point_to_geoms_dist

        q = setup["query"]
        eligible = geoms.valid & geom_cells_any_within(geoms.cells, geoms.cells_mask,
                                                       setup["nb"])
        if self.conf.approximate:
            dists = point_bbox_dist(q.x, q.y, geoms.bbox[:, 0], geoms.bbox[:, 1],
                                    geoms.bbox[:, 2], geoms.bbox[:, 3])
        else:
            dists = point_to_geoms_dist(q.x, q.y, geoms)
        return eligible, dists


class GeomGeomKNNQuery(_GeomStreamKnn):
    """Polygon/linestring stream x polygon/linestring query (the remaining
    4 pairs of SURVEY §2.2)."""

    def _multi_local(self, query_geoms, radius: float, k: int):
        """Q query GEOMETRIES over a polygon/linestring stream — one
        exact-capacity padded query edge batch
        (``ops.geom.knn_geoms_to_geom_queries``)."""
        from spatialflink_tpu.ops.geom import knn_geoms_to_geom_queries

        qgb = self._query_geom_batch(query_geoms)
        nb_masks = self._stack_query_nb(query_geoms, radius)
        return lambda geoms: knn_geoms_to_geom_queries(
            geoms, qgb, nb_masks, k=k, strategy=self._knn_strategy(),
            approximate=self.conf.approximate)

    def _setup(self, query, radius):
        return dict(nb=self._query_nb(query, radius),
                    edges=self._query_edges(query), bbox=self._query_bbox(query))

    def _elig_dists(self, geoms, setup):
        from spatialflink_tpu.ops.geom import geoms_bbox_dist
        from spatialflink_tpu.ops.geom import (
            geom_cells_any_within,
            geoms_to_single_geom_dist,
        )

        eligible = geoms.valid & geom_cells_any_within(geoms.cells, geoms.cells_mask,
                                                       setup["nb"])
        q_edges, q_mask, q_areal = setup["edges"]
        if self.conf.approximate:
            dists = geoms_bbox_dist(geoms, setup["bbox"])
        else:
            dists = geoms_to_single_geom_dist(geoms, q_edges, q_mask, q_areal)
        return eligible, dists


# Reference-named aliases
PointPolygonKNNQuery = PointGeomKNNQuery
PointLineStringKNNQuery = PointGeomKNNQuery
PolygonPointKNNQuery = GeomPointKNNQuery
LineStringPointKNNQuery = GeomPointKNNQuery
PolygonPolygonKNNQuery = GeomGeomKNNQuery
PolygonLineStringKNNQuery = GeomGeomKNNQuery
LineStringPolygonKNNQuery = GeomGeomKNNQuery
LineStringLineStringKNNQuery = GeomGeomKNNQuery
