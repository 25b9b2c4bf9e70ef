"""Batch kernels over polygon / linestring edge-array geometries.

These replace the per-tuple JTS calls in the reference's polygon/linestring
operators (``range/PointPolygonRangeQuery.java``, ``PolygonPointRangeQuery``
etc.) with masked array math over :class:`EdgeGeomBatch`.

Distance semantics follow JTS ``Geometry.distance``:
- point -> polygon: 0 if the point is inside the areal geometry, else min
  boundary distance; point -> linestring: min boundary distance.
- polygon/linestring -> polygon/linestring: 0 if they intersect (boundary
  crossing or containment), else min boundary-boundary distance.

Shapes: a trailing broadcast convention — points (N,), geometries (G, E, 4)
— producing (N, G) results. The elementwise lattices ((N, G, E) etc.) are
reduction operands that XLA fuses; nothing of that size is materialized.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from spatialflink_tpu.models.batches import EdgeGeomBatch, PointBatch
from spatialflink_tpu.ops import distances as D
from spatialflink_tpu.utils.deviceplane import instrumented_jit

_BIG = np.float32(3.4e38)


@instrumented_jit
def points_in_geoms(px, py, edges, edge_mask):
    """(N, G) even-odd containment of each point in each geometry's rings."""
    return D.point_in_rings(
        px[:, None, None], py[:, None, None], edges[None], edge_mask[None]
    )


@instrumented_jit
def points_to_edges_dist(px, py, edges, edge_mask):
    """(N, G) min boundary distance from each point to each edge set."""
    d2 = D.point_segment_dist2(
        px[:, None, None],
        py[:, None, None],
        edges[None, ..., 0],
        edges[None, ..., 1],
        edges[None, ..., 2],
        edges[None, ..., 3],
    )
    return jnp.sqrt(jnp.min(jnp.where(edge_mask[None], d2, _BIG), axis=-1))


@instrumented_jit
def points_to_geoms_dist(points: PointBatch, geoms: EdgeGeomBatch):
    """(N, G) JTS-style distance from each point to each geometry."""
    bdist = points_to_edges_dist(points.x, points.y, geoms.edges, geoms.edge_mask)
    inside = points_in_geoms(points.x, points.y, geoms.edges, geoms.edge_mask)
    return jnp.where(inside & geoms.is_areal[None, :], 0.0, bdist)


@partial(instrumented_jit, static_argnames=("k", "strategy", "approximate"))
def knn_points_to_geom_queries(points: PointBatch, geoms: EdgeGeomBatch,
                               nb_masks, *, k: int, strategy: str = "auto",
                               approximate: bool = False):
    """kNN of each of Q geometry QUERIES over one point-window batch in ONE
    dispatch: -> (KnnResult with (Q, k) fields, dist_evals (Q,)).

    Multi-query companion of the ``PointPolygonKNNQuery`` path (reference
    runs one query polygon per job, ``StreamingJob.java:470``): ``geoms``
    holds the Q query polygons/linestrings as one padded edge batch,
    ``nb_masks`` is the (Q, n*n) dense neighboring-cells mask per query
    (``GeomQueryMixin._query_nb`` per geometry). Exact mode reuses the
    (N, G) point->geometry lattice; approximate mode substitutes bbox
    distances per the reference's approximate flag. Selection is the
    batched dedup+top-k (``ops.knn.topk_by_distance_multi`` — exactness
    rescue included).
    """
    from spatialflink_tpu.ops.knn import topk_by_distance_multi

    if approximate:
        b = geoms.bbox  # (Q, 4)
        d = D.point_bbox_dist(
            points.x[None, :], points.y[None, :],
            b[:, 0, None], b[:, 1, None], b[:, 2, None], b[:, 3, None])
    else:
        d = points_to_geoms_dist(points, geoms).T  # (Q, N)
    cell = jnp.maximum(points.cell, 0)
    in_grid = points.valid & (points.cell >= 0)
    elig = in_grid[None, :] & nb_masks[:, cell]
    res = topk_by_distance_multi(points.obj_id, d, elig, k, strategy)
    return res, jnp.sum(elig, axis=1, dtype=jnp.int32)


def points_to_single_geom_dist(points: PointBatch, edges, edge_mask, is_areal: bool):
    """(N,) distance from every point to ONE query geometry (the common
    point-stream x polygon-query case).

    Delegates to :func:`ops.pallas_kernels.pip_dist`, which self-dispatches:
    fused pallas kernel on TPU, the jnp twin everywhere else."""
    from spatialflink_tpu.ops import pallas_kernels as PK

    return PK.pip_dist(points.x, points.y, edges, edge_mask, bool(is_areal))


@instrumented_jit
def points_to_single_edges_raw(px, py, edges, edge_mask):
    """(inside, min_dist2) of each point vs ONE edge set — the shared jnp twin
    of the pallas pip kernel. Empty/fully-masked edge sets yield +inf dist2."""
    d2 = D.point_segment_dist2(
        px[:, None],
        py[:, None],
        edges[None, :, 0],
        edges[None, :, 1],
        edges[None, :, 2],
        edges[None, :, 3],
    )
    pad = jnp.full((d2.shape[0], 1), _BIG)  # keeps the reduction non-empty-safe
    mind2 = jnp.min(jnp.concatenate([jnp.where(edge_mask[None], d2, _BIG), pad], axis=-1), axis=-1)
    inside = D.point_in_rings(px[:, None], py[:, None], edges[None], edge_mask[None])
    return inside, mind2


@instrumented_jit
def geoms_to_single_geom_dist(geoms: EdgeGeomBatch, q_edges, q_mask, q_areal: bool):
    """(G,) JTS-style distance from each batch geometry to ONE query geometry.

    Intersection => 0 falls out of the segment-segment kernel (crossing
    boundaries have a zero-distance segment pair). Containment with disjoint
    boundaries is resolved by vertex tests — over ALL valid vertices on both
    sides, so multi-part geometries (one component far, another contained)
    are handled: with disjoint boundaries, any vertex inside <=> that whole
    component inside. Padded geometry slots (no valid edges) report +inf.
    """
    bdist2 = jax.vmap(
        lambda e, m: D.edges_edges_dist2(e, m, q_edges, q_mask)
    )(geoms.edges, geoms.edge_mask)

    # any valid vertex of the geometry inside the (areal) query: (G, E) -> (G,)
    g_in_q = D.point_in_rings(
        geoms.edges[..., 0:1], geoms.edges[..., 1:2], q_edges[None, None], q_mask[None, None]
    )
    g_in_q = jnp.any(g_in_q & geoms.edge_mask, axis=-1) & q_areal

    # any valid query vertex inside the (areal) geometry: (G, Eq) -> (G,)
    q_in_g = D.point_in_rings(
        q_edges[None, :, 0:1], q_edges[None, :, 1:2],
        geoms.edges[:, None], geoms.edge_mask[:, None],
    )
    q_in_g = jnp.any(q_in_g & q_mask[None, :], axis=-1) & geoms.is_areal

    has_edges = jnp.any(geoms.edge_mask, axis=-1)
    zero = (g_in_q | q_in_g) & has_edges
    return jnp.where(zero, 0.0, jnp.sqrt(bdist2))


@instrumented_jit
def geoms_bbox_dist(geoms: EdgeGeomBatch, q_bbox):
    """(G,) bbox-bbox distance to a query bbox — the approximate-mode
    prefilter (DistanceFunctions.java:298-421)."""
    return D.bbox_bbox_dist(geoms.bbox, q_bbox[None, :])


@instrumented_jit
def point_to_geoms_dist(px, py, geoms: EdgeGeomBatch):
    """(G,) distance from ONE query point to each batch geometry (the
    polygon-stream x point-query case, ``PolygonPointRangeQuery``)."""
    d2 = D.point_segment_dist2(
        px, py,
        geoms.edges[..., 0], geoms.edges[..., 1],
        geoms.edges[..., 2], geoms.edges[..., 3],
    )
    bdist = jnp.sqrt(jnp.min(jnp.where(geoms.edge_mask, d2, _BIG), axis=-1))
    inside = D.point_in_rings(px, py, geoms.edges, geoms.edge_mask)
    return jnp.where(inside & geoms.is_areal, 0.0, bdist)


def _geom_elig_multi(geoms: EdgeGeomBatch, nb_masks):
    """(Q, G) eligibility of each batch geometry for each query: valid and
    ANY overlapped cell inside that query's dense neighboring-cells mask
    (the multi-query form of :func:`geom_cells_any_within`)."""
    hit = nb_masks[:, jnp.maximum(geoms.cells, 0)]  # (Q, G, C)
    any_in = jnp.any(hit & geoms.cells_mask[None], axis=-1)
    return geoms.valid[None, :] & any_in


@partial(instrumented_jit, static_argnames=("k", "strategy", "approximate"))
def knn_geoms_to_point_queries(geoms: EdgeGeomBatch, qx, qy, nb_masks, *,
                               k: int, strategy: str = "auto",
                               approximate: bool = False):
    """kNN of Q query POINTS over one polygon/linestring window batch in ONE
    dispatch (multi-query ``PolygonPointKNNQuery``/``LineStringPoint...``):
    -> (KnnResult with (Q, k) fields, dist_evals (Q,)). Approximate mode
    substitutes point->bbox distances like the single-query path."""
    from spatialflink_tpu.ops.knn import topk_by_distance_multi

    if approximate:
        b = geoms.bbox
        # vmap of the single-query expression (not a 2-D broadcast): the
        # per-row computation graph then matches GeomPointKNNQuery._elig_dists
        # bit-for-bit, so run() and run_multi() results are identical
        d = jax.vmap(lambda x, y: D.point_bbox_dist(
            x, y, b[:, 0], b[:, 1], b[:, 2], b[:, 3]))(qx, qy)
    else:
        d = jax.vmap(lambda x, y: point_to_geoms_dist(x, y, geoms))(qx, qy)
    elig = _geom_elig_multi(geoms, nb_masks)
    res = topk_by_distance_multi(geoms.obj_id, d, elig, k, strategy)
    return res, jnp.sum(elig, axis=1, dtype=jnp.int32)


@partial(instrumented_jit, static_argnames=("k", "strategy", "approximate"))
def knn_geoms_to_geom_queries(geoms: EdgeGeomBatch, queries: EdgeGeomBatch,
                              nb_masks, *, k: int, strategy: str = "auto",
                              approximate: bool = False):
    """kNN of Q query GEOMETRIES over one polygon/linestring window batch in
    ONE dispatch (multi-query ``PolygonPolygonKNNQuery`` and the other
    geometry-geometry pairs): ``queries`` is the Q query geometries as one
    exact-capacity padded edge batch; distances are the vmapped
    geometry->geometry kernel (:func:`geoms_to_single_geom_dist`), bbox-bbox
    in approximate mode."""
    from spatialflink_tpu.ops.knn import topk_by_distance_multi

    if approximate:
        d = jax.vmap(lambda b: geoms_bbox_dist(geoms, b))(queries.bbox)
    else:
        d = jax.vmap(
            lambda e, m, a: geoms_to_single_geom_dist(geoms, e, m, a)
        )(queries.edges, queries.edge_mask, queries.is_areal)
    elig = _geom_elig_multi(geoms, nb_masks)
    res = topk_by_distance_multi(geoms.obj_id, d, elig, k, strategy)
    return res, jnp.sum(elig, axis=1, dtype=jnp.int32)


@partial(instrumented_jit, static_argnames=("approximate",))
def range_points_to_geom_queries(points: PointBatch, queries: EdgeGeomBatch,
                                 gn_masks, cn_masks, radius, *,
                                 approximate: bool = False):
    """Range filter of Q geometry QUERIES over one point window batch in ONE
    dispatch (multi-query ``PointPolygonRangeQuery``/``PointLineString...``):
    -> (masks (Q, N), gn_bypassed (Q,), dist_evals (Q,)). Per query, a vmap
    of the single-query expressions — dense GN/CN masks + exact geometry
    distance (bbox distance in approximate mode, which still passes through
    the radius check like the single path).

    Exact mode computes distances via the (N, G) lattice while the
    single-query path uses the static-``is_areal`` single-geom kernel, so
    ``run()`` and ``run_multi()`` may disagree on radius-BOUNDARY records
    in the last ulp on TPU (different reduction orders); CPU parity tests
    cannot observe this; the on-chip parity check is not measured."""
    from spatialflink_tpu.ops.range import range_filter_masks_stats

    if approximate:
        def one(bb, gn, cn):
            d = D.point_bbox_dist(points.x, points.y, bb[0], bb[1], bb[2],
                                  bb[3])
            return range_filter_masks_stats(points, gn, cn, d, radius)

        return jax.vmap(one)(queries.bbox, gn_masks, cn_masks)
    # exact mode rides the (N, G) lattice like the kNN multi path (the
    # single-geom kernel's pallas dispatch needs a STATIC is_areal, which a
    # vmapped per-query flag cannot provide)
    d_all = points_to_geoms_dist(points, queries).T  # (Q, N)
    return jax.vmap(
        lambda d, gn, cn: range_filter_masks_stats(points, gn, cn, d, radius)
    )(d_all, gn_masks, cn_masks)


@partial(instrumented_jit, static_argnames=("approximate",))
def range_geoms_to_point_queries(geoms: EdgeGeomBatch, qx, qy, gn_masks,
                                 nb_masks, radius, *,
                                 approximate: bool = False):
    """Range filter of Q query POINTS over one polygon/linestring window
    batch in ONE dispatch (multi-query ``PolygonPointRangeQuery``/
    ``LineStringPoint...``): -> (masks (Q, G), gn_bypassed (Q,),
    dist_evals (Q,)). Applies the GN-subset rule per query (ALL of a
    geometry's cells guaranteed -> no distance math,
    ``range/PolygonPointRangeQuery.java:54-87``)."""
    from spatialflink_tpu.ops.range import range_filter_geom_stream_stats

    def one(x, y, gn, nbm):
        all_gn = geom_cells_all_within(geoms.cells, geoms.cells_mask, gn)
        any_nb = geom_cells_any_within(geoms.cells, geoms.cells_mask, nbm)
        if approximate:
            b = geoms.bbox
            d = D.point_bbox_dist(x, y, b[:, 0], b[:, 1], b[:, 2], b[:, 3])
        else:
            d = point_to_geoms_dist(x, y, geoms)
        return range_filter_geom_stream_stats(all_gn, any_nb, d, radius,
                                              geoms.valid)

    return jax.vmap(one)(qx, qy, gn_masks, nb_masks)


@partial(instrumented_jit, static_argnames=("approximate",))
def range_geoms_to_geom_queries(geoms: EdgeGeomBatch, queries: EdgeGeomBatch,
                                gn_masks, nb_masks, radius, *,
                                approximate: bool = False):
    """Range filter of Q query GEOMETRIES over one polygon/linestring window
    batch in ONE dispatch (multi-query ``PolygonPolygonRangeQuery`` and
    siblings): -> (masks (Q, G), gn_bypassed (Q,), dist_evals (Q,))."""
    from spatialflink_tpu.ops.range import range_filter_geom_stream_stats

    def one(e, m, a, bb, gn, nbm):
        all_gn = geom_cells_all_within(geoms.cells, geoms.cells_mask, gn)
        any_nb = geom_cells_any_within(geoms.cells, geoms.cells_mask, nbm)
        if approximate:
            d = geoms_bbox_dist(geoms, bb)
        else:
            d = geoms_to_single_geom_dist(geoms, e, m, a)
        return range_filter_geom_stream_stats(all_gn, any_nb, d, radius,
                                              geoms.valid)

    return jax.vmap(one)(queries.edges, queries.edge_mask, queries.is_areal,
                         queries.bbox, gn_masks, nb_masks)


def geom_cells_all_within(cells, cells_mask, target_mask):
    """(G,) True iff ALL of a geometry's grid cells fall inside
    ``target_mask`` — the PolygonPointRangeQuery GN-subset rule: a polygon is
    a guaranteed result only if every cell it overlaps is guaranteed
    (``range/PolygonPointRangeQuery.java:54-87``)."""
    hit = target_mask[jnp.maximum(cells, 0)] | ~cells_mask
    return jnp.all(hit, axis=-1) & jnp.any(cells_mask, axis=-1)


def geom_cells_any_within(cells, cells_mask, target_mask):
    """(G,) True iff ANY of a geometry's cells falls inside ``target_mask``
    (the cell-filter rule for candidate membership of multi-cell geometries)."""
    hit = target_mask[jnp.maximum(cells, 0)] & cells_mask
    return jnp.any(hit, axis=-1)
