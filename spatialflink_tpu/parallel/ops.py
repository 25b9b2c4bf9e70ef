"""Distributed window kernels via shard_map + XLA collectives.

Each op shards the window batch's point dimension across the mesh, runs the
single-device kernel (spatialflink_tpu.ops) per shard, and merges partials
with collectives:

- kNN: per-shard dedup+top-k, then ``all_gather`` of the k-sized partials and
  a final re-top-k — a tree merge on ICI replacing the reference's
  parallelism-1 ``windowAll`` stage (``knn/PointPointKNNQuery.java:188-190``).
  Per-device traffic is O(k * n_devices), independent of window size.
- range: per-shard masked filter + ``psum`` count.
- join: the a-side is sharded, the (smaller) query side replicated — the
  broadcast-join layout, matching the reference's query-stream replication
  (``join/JoinQuery.java:72-90``) without materializing copies.

The shard bodies call the same kernels used single-device (jit-in-jit), so
eligibility/distance semantics cannot fork between the two paths.

All functions are jit-compatible and run under a ``jax.sharding.Mesh`` of any
size; they are exercised on an 8-device virtual CPU mesh in tests and
dry-run-compiled by ``__graft_entry__.dryrun_multichip``.

Device-truth coverage contract: this module deliberately has NO raw
``jax.jit`` sites (enforced by the ``TestJitCoverage`` AST meta-test in
tier-1). The point kNN (the served kNN operator, trajectory kNN and
:func:`distributed_knn`) is one module-level ``instrumented_jit``
program, :func:`knn_mesh_stats`: the mesh and the kernel's shape
parameters are static, the query point and radius are arguments, so a
window of a seen batch bucket dispatches the compiled program and lowers
nothing. Its per-shard body calls the single-device ``knn_point_stats``
(jit-in-jit), whose registry hook feeds the compile registry
(``utils.deviceplane``) like a single-device compile.
The other ops here build their ``shard_map`` per call around closures over
the same module-level kernels; they trace per call.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from spatialflink_tpu.models.batches import PointBatch
from spatialflink_tpu.ops.join import join_mask
from spatialflink_tpu.ops.knn import (KnnResult, knn_point, knn_point_stats,
                                      topk_by_distance)
from spatialflink_tpu.ops.range import range_filter_point
from spatialflink_tpu.parallel.mesh import CELL_AXIS, DCN_AXIS
from spatialflink_tpu.utils.deviceplane import instrumented_jit


def distributed_knn(
    mesh: Mesh,
    points: PointBatch,
    qx,
    qy,
    q_cell,
    radius,
    nb_layers: int,
    *,
    n: int,
    k: int,
    enforce_radius: bool = False,
    strategy: str = "auto",
) -> KnnResult:
    """kNN over a batch sharded on the point dim; result replicated — the
    KnnResult of :func:`knn_mesh_stats` (one compiled program for every
    point-kNN mesh caller). ``strategy`` is threaded to the per-shard
    ``knn_point_stats`` so approximate mode (``approx``) behaves the same at
    any parallelism; the re-merge is exact top-k over the k-sized partials
    either way."""
    res, _evals = knn_mesh_stats(
        points, qx, qy, q_cell, radius, mesh=mesh, nb_layers=nb_layers,
        n=n, k=k, enforce_radius=enforce_radius, strategy=strategy)
    return res


def distributed_knn_hierarchical(
    mesh: Mesh,
    points: PointBatch,
    qx,
    qy,
    q_cell,
    radius,
    nb_layers,
    *,
    n: int,
    k: int,
    enforce_radius: bool = False,
    strategy: str = "auto",
) -> KnnResult:
    """kNN over a 2-D (DCN_AXIS, CELL_AXIS) mesh with a two-level merge.

    The window's point dim is sharded over both axes. Each chip computes its
    local dedup+top-k; the first merge all-gathers k-sized partials *within*
    a slice (ICI — cheap), the second all-gathers one k-sized partial *per
    slice* across hosts (DCN — k * n_hosts elements total, independent of
    window size). This is the multi-host shape of the reference's two-stage
    local-top-k -> global-merge plan (SURVEY §2.5) without its parallelism-1
    global stage.
    """

    def per_shard(pts: PointBatch) -> KnnResult:
        local = knn_point(
            pts, qx, qy, q_cell, radius, nb_layers,
            n=n, k=k, enforce_radius=enforce_radius, strategy=strategy,
        )
        # level 1 across the slice (ICI), level 2 per-slice partials across
        # hosts (DCN) — ONE merge implementation (_gather_topk) shared with
        # the stream kNN ops' 2-D path
        return _gather_topk(_gather_topk(local, CELL_AXIS, k), DCN_AXIS, k)

    fn = jax.shard_map(
        per_shard,
        mesh=mesh,
        check_vma=False,
        in_specs=(P((DCN_AXIS, CELL_AXIS)),),
        out_specs=KnnResult(P(), P(), P()),
    )
    return fn(points)


def distributed_range_count(
    mesh: Mesh,
    points: PointBatch,
    qx,
    qy,
    q_cell,
    radius,
    gn_layers,
    cn_layers,
    *,
    n: int,
    approximate: bool = False,
):
    """Range-query match count with a psum merge; (count, mask_sharded)."""

    def per_shard(pts: PointBatch):
        mask, _dists = range_filter_point(
            pts, qx, qy, q_cell, radius, gn_layers, cn_layers,
            n=n, approximate=approximate,
        )
        count = jax.lax.psum(jnp.sum(mask, dtype=jnp.int32), CELL_AXIS)
        return count, mask

    fn = jax.shard_map(
        per_shard,
        mesh=mesh,
        check_vma=False,
        in_specs=(P(CELL_AXIS),),
        out_specs=(P(), P(CELL_AXIS)),
    )
    return fn(points)


def distributed_join_mask(
    mesh: Mesh,
    a: PointBatch,
    b: PointBatch,
    radius,
    nb_layers,
    center_x,
    center_y,
    *,
    n: int,
):
    """Broadcast join returning the full (Na, Nb) boolean pair lattice,
    sharded on the a (point) dim — the record-output form operators need
    (``distributed_join_counts`` is the count-only reduction). The a side is
    sharded, the (smaller) query side replicated; no collective is required
    for the lattice itself, so each device owns its row block."""

    return distributed_stream_join_lattice(
        mesh, a, b,
        lambda a_s, b_r: join_mask(a_s, b_r, radius, nb_layers,
                                   center_x, center_y, n=n))


def _point_axes(mesh: Mesh):
    """The point-dim sharding axes of ``mesh``: ``(CELL_AXIS,)`` for the 1-D
    mesh, ``(DCN_AXIS, CELL_AXIS)`` for the 2-D hybrid — single source for
    the stream ops' specs/collectives so every op accepts either shape."""
    return tuple(mesh.axis_names)


def distributed_stream_filter(mesh: Mesh, batch, mask_stats_fn):
    """Geometry/point STREAM filter over the mesh (the missing mesh dispatch
    for PointGeom/GeomPoint/GeomGeom range — every reference pipeline runs at
    parallelism 30, ``StreamingJob.java:221``).

    ``batch`` (any pytree whose leaves share the sharded leading dim) is
    sharded on that dim; ``mask_stats_fn(shard) -> (mask, gn_bypassed,
    dist_evals)`` runs the SAME single-device kernels per shard (closure over
    replicated query-side arrays), so semantics cannot fork between the two
    paths; the pruning stats are psum-merged. Returns (mask_sharded,
    gn_total, evals_total) — embarrassingly parallel on the mask, one scalar
    collective for the counters. Accepts 1-D and 2-D (hosts x chips) meshes.
    """
    return _stream_filter_impl(mesh, batch, mask_stats_fn,
                               lambda axes: P(axes))


def distributed_stream_filter_multi(mesh: Mesh, batch, multi_mask_stats):
    """Multi-query stream filter over the mesh: ``multi_mask_stats(shard) ->
    (masks (Q, n_shard), gn (Q,), evals (Q,))`` runs the SAME vmapped
    single-device kernels per shard (closures over replicated query-side
    stacks); per-query pruning counters psum-merge. Returns
    (masks (Q, N) sharded on the point dim, gn totals (Q,), evals totals
    (Q,)). Accepts 1-D and 2-D meshes."""
    return _stream_filter_impl(mesh, batch, multi_mask_stats,
                               lambda axes: P(None, axes))


def _stream_filter_impl(mesh: Mesh, batch, stats_fn, mask_spec):
    """Shared shard_map wiring for the single- and multi-query stream
    filters — they differ only in where the sharded point dim sits in the
    mask output (leading vs after the query axis)."""
    axes = _point_axes(mesh)

    def per_shard(b):
        mask, gn, evals = stats_fn(b)
        return (mask, jax.lax.psum(gn, axes), jax.lax.psum(evals, axes))

    fn = jax.shard_map(
        per_shard,
        mesh=mesh,
        check_vma=False,
        in_specs=(P(axes),),
        out_specs=(mask_spec(axes), P(), P()),
    )
    return fn(batch)


def distributed_stream_knn(mesh: Mesh, batch, elig_dist_fn, *, k: int,
                           strategy: str = "auto"):
    """Geometry STREAM kNN over the mesh: per-shard local dedup+top-k,
    all-gather of the k-sized partials, re-top-k — the generic-stream twin of
    :func:`knn_mesh_stats` (kills the reference's parallelism-1 ``windowAll``
    for the polygon/linestring pairs too). Returns (KnnResult replicated,
    dist_evals total) with the candidate count psum-merged for the pruning
    counter.

    Per-shard compute goes through the SAME module-level jitted kernels the
    single-device paths use — ``elig_dist_fn(shard) -> (eligible, dists)``
    fed into ``knn_eligible_stats`` — so XLA fuses the distance math
    identically in both paths and the 8-dev ≡ 1-dev parity is bit-for-bit,
    not just approximate. The re-merge is value-preserving (top-k selects,
    never recomputes), so merged distances are exact copies of per-shard
    results.
    """
    from spatialflink_tpu.ops.knn import knn_eligible_stats

    def local(b):
        eligible, dists = elig_dist_fn(b)
        return knn_eligible_stats(b.obj_id, dists, eligible,
                                  k=k, strategy=strategy)

    return _stream_knn_impl(mesh, batch, local, k, _gather_topk)


def distributed_stream_knn_multi(mesh: Mesh, batch, local_fn, *, k: int):
    """Multi-query stream kNN over the mesh: ``local_fn(shard) ->
    (KnnResult (Q, k), evals (Q,))`` is the vmapped single-device kernel
    closure; per-shard (Q, k) partials all-gather and re-top-k per query
    (two-level on a 2-D mesh — DCN traffic is Q * k * hosts, window-size
    independent). Returns (KnnResult (Q, k) replicated, evals totals (Q,))."""
    return _stream_knn_impl(mesh, batch, local_fn, k, _gather_topk_multi)


@partial(instrumented_jit,
         static_argnames=("mesh", "nb_layers", "n", "k", "enforce_radius",
                          "strategy"))
def knn_mesh_stats(points: PointBatch, qx, qy, q_cell, radius, *,
                   mesh: Mesh, nb_layers: int, n: int, k: int,
                   enforce_radius: bool = False, strategy: str = "auto"):
    """The point kNN over a mesh as ONE compiled program a (mesh, batch
    bucket, k, radius rule, strategy, layer count): ``knn_point_stats`` on
    each shard of the point dim, the all-gather + re-top-k merge of the k-sized partials
    (:func:`_gather_topk`) and the psum of the candidate count. The query
    point, its cell and the radius are traced arguments, so nothing is
    baked in per window. Returns (KnnResult replicated, dist_evals total),
    equal to single-device ``knn_point_stats`` on the whole batch."""

    def local(b, qx, qy, q_cell, radius):
        return knn_point_stats(b, qx, qy, q_cell, radius, nb_layers, n=n,
                               k=k, enforce_radius=enforce_radius,
                               strategy=strategy)

    return _stream_knn_impl(mesh, points, local, k, _gather_topk,
                            qx, qy, q_cell, radius)


def _stream_knn_impl(mesh: Mesh, batch, local_fn, k: int, gather, *rep):
    """Shared shard_map wiring for the stream kNN ops — they differ only in
    the partial shape ((k,) vs (Q, k)) and hence the gather-merge helper.
    ``rep`` are replicated operands passed to ``local_fn`` after the
    shard."""
    axes = _point_axes(mesh)

    def per_shard(b, *r):
        local, n_elig = local_fn(b, *r)
        # level 1: merge k-sized partials across the slice (ICI axis)
        merged = gather(local, CELL_AXIS, k)
        if DCN_AXIS in axes:
            # level 2 (2-D mesh): one k-sized partial per slice across
            # hosts — DCN traffic is k * n_hosts (* Q for multi),
            # window-size independent (the hierarchical merge of
            # distributed_knn_hierarchical, available to every stream type
            # through the operator path)
            merged = gather(merged, DCN_AXIS, k)
        return merged, jax.lax.psum(n_elig, axes)

    fn = jax.shard_map(
        per_shard,
        mesh=mesh,
        check_vma=False,
        in_specs=(P(axes),) + (P(),) * len(rep),
        out_specs=(KnnResult(P(), P(), P()), P()),
    )
    return fn(batch, *rep)


def _gather_topk(partial: KnnResult, axis_name: str, k: int) -> KnnResult:
    """all-gather k-sized per-shard partials over one mesh axis and re-top-k
    (value-preserving: selection only, distances are exact copies)."""
    return topk_by_distance(
        jax.lax.all_gather(partial.obj_id, axis_name).reshape(-1),
        jax.lax.all_gather(partial.dist, axis_name).reshape(-1),
        jax.lax.all_gather(partial.valid, axis_name).reshape(-1),
        k)


def _gather_topk_multi(partial: KnnResult, axis_name: str, k: int
                       ) -> KnnResult:
    """:func:`_gather_topk` for (Q, k) partials: all-gather over the mesh
    axis gives (D, Q, k); re-top-k per query over the D*k merged candidates.
    The merge operands are tiny (devices * k), so a vmapped full sort is the
    right selection — no cond, value-preserving."""
    def gather(x):
        g = jax.lax.all_gather(x, axis_name)         # (D, Q, k)
        return jnp.moveaxis(g, 0, 1).reshape(g.shape[1], -1)  # (Q, D*k)

    oid, dist, valid = (gather(partial.obj_id), gather(partial.dist),
                        gather(partial.valid))
    return jax.vmap(
        lambda o, d, v: topk_by_distance(o, d, v, k, strategy="sort")
    )(oid, dist, valid)


def distributed_stream_join_lattice(mesh: Mesh, a, b, lattice_fn):
    """Generic broadcast join for the geometry pairs: the a side (any batch
    pytree) sharded on its leading dim, the query side replicated;
    ``lattice_fn(a_shard, b) -> (rows, Nb) bool`` runs the same pair-lattice
    kernel as single-device (``join_point_geom_mask`` /
    ``join_geom_geom_mask``). No collective — each device owns its row
    block, mirroring :func:`distributed_join_mask` for PointPoint."""

    def per_shard(a_shard, b_rep):
        return lattice_fn(a_shard, b_rep)

    axes = _point_axes(mesh)
    fn = jax.shard_map(
        per_shard,
        mesh=mesh,
        check_vma=False,
        in_specs=(P(axes), P()),
        out_specs=P(axes),
    )
    return fn(a, b)


def distributed_join_counts(
    mesh: Mesh,
    a: PointBatch,
    b: PointBatch,
    radius,
    nb_layers,
    center_x,
    center_y,
    *,
    n: int,
):
    """Broadcast join: a sharded, b replicated; per-a counts + psum total."""

    def per_shard(a_shard: PointBatch, b_rep: PointBatch):
        m = join_mask(a_shard, b_rep, radius, nb_layers, center_x, center_y, n=n)
        per_a = jnp.sum(m, axis=1, dtype=jnp.int32)
        total = jax.lax.psum(jnp.sum(per_a), CELL_AXIS)
        return per_a, total

    fn = jax.shard_map(
        per_shard,
        mesh=mesh,
        check_vma=False,
        in_specs=(P(CELL_AXIS), P()),
        out_specs=(P(CELL_AXIS), P()),
    )
    return fn(a, b)


def _gather_shard_major(x, axes):
    """all_gather a per-shard array over the mesh's point axes into
    shard-major order matching the batch's contiguous sharding: outer (DCN)
    axis major, inner (ICI) axis minor — ``(D, *x.shape)``."""
    g = jax.lax.all_gather(x, CELL_AXIS)              # (n_cell, ...)
    if DCN_AXIS in axes:
        g = jax.lax.all_gather(g, DCN_AXIS)           # (n_dcn, n_cell, ...)
        g = g.reshape((-1,) + g.shape[2:])
    return g


def distributed_taggregate(mesh: Mesh, batch, *, num_cells: int, agg: str):
    """Windowed tAggregate over the mesh (``TAggregateQuery.java:53-377``):
    per-shard (cell, objID) group EXTENTS — the mergeable form; a length is
    not, since a group split at a shard boundary must merge [min_ts, max_ts]
    before measuring — then an all-gather of the shard representatives and
    a replicated extent-merge re-sort. ``agg='ALL'`` returns the merged
    :class:`TAggregateGroups` (size N, replicated — the same shape the
    single-device path extracts records from); other aggregates return the
    dense (num_cells,) heatmap, replicated."""
    from spatialflink_tpu.ops.trajectory import (_OID_SENTINEL, INT32_MIN,
                                                 taggregate_group_extents,
                                                 taggregate_heatmap,
                                                 taggregate_merge_extents)

    axes = _point_axes(mesh)
    int32_max = jnp.iinfo(jnp.int32).max

    def per_shard(b):
        e = taggregate_group_extents(b, num_cells=num_cells)
        # blank non-representatives so only one extent row per local group
        # survives the gather (sentinels sort last in the merge)
        cell = jnp.where(e.first, e.cell, num_cells)
        oid = jnp.where(e.first, e.obj_id, _OID_SENTINEL)
        mn = jnp.where(e.first, e.min_ts, int32_max)
        mx = jnp.where(e.first, e.max_ts, INT32_MIN)
        merged = taggregate_merge_extents(
            _gather_shard_major(cell, axes).reshape(-1),
            _gather_shard_major(oid, axes).reshape(-1),
            _gather_shard_major(mn, axes).reshape(-1),
            _gather_shard_major(mx, axes).reshape(-1),
            num_cells=num_cells)
        if agg == "ALL":
            return merged
        return taggregate_heatmap(merged, num_cells=num_cells, agg=agg)

    from spatialflink_tpu.ops.trajectory import TAggregateGroups

    out_spec = (TAggregateGroups(P(), P(), P(), P())
                if agg == "ALL" else P())
    fn = jax.shard_map(
        per_shard,
        mesh=mesh,
        check_vma=False,
        in_specs=(P(axes),),
        out_specs=out_spec,
    )
    return fn(batch)


def distributed_tstats_window(mesh: Mesh, batch, *, m: int):
    """Windowed tStats over the mesh (``TStatsQuery.java:153-197``): the
    window must be globally (objID, ts)-sorted and deduplicated BEFORE
    contiguous sharding (the operator does this host-side), so each shard
    summarizes a contiguous slice of every trajectory's run and the
    replicated stitch adds exactly the boundary pairs the single-device
    sorted cumsum would have linked. Returns (spatial (M,), temporal (M,)
    i32 ms, count (M,)), replicated; trajectories emit iff count >= 2."""
    from spatialflink_tpu.ops.trajectory import (tstats_stitch_summaries,
                                                 tstats_window_summary)

    axes = _point_axes(mesh)

    def per_shard(b):
        s = tstats_window_summary(b, m=m)
        # tree-map preserves the NamedTuple structure: (D, M) tables
        tabs = jax.tree.map(lambda x: _gather_shard_major(x, axes), s)
        return tstats_stitch_summaries(tabs)

    fn = jax.shard_map(
        per_shard,
        mesh=mesh,
        check_vma=False,
        in_specs=(P(axes),),
        out_specs=(P(), P(), P()),
    )
    return fn(batch)
