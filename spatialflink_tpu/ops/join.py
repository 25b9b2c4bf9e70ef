"""Grid-cell hash-join kernels.

Reference semantics (``join/JoinQuery.java:72-90`` +
``join/PointPointJoinQuery.java:110-171``): the query stream is replicated to
every neighboring cell of each query point, both sides are shuffled on
gridID, and each co-located pair is kept iff exact distance <= r.  The pair
condition is therefore::

    p.cell ∈ neighboringCells(q, r)   AND   dist(p, q) <= r

TPU re-design: no replication, no shuffle.  The cell-membership test is
Chebyshev index arithmetic evaluated directly on the (Na, Nb) pair lattice,
and the pairwise distances come from the MXU via the
|a|^2 + |b|^2 - 2 a.b^T expansion — a (Na,2)x(2,Nb) matmul.  Coordinates are
centered first: at degree magnitudes (~116) the f32 cancellation in the
expansion would swamp small distances; after centering the operands are O(1)
and the error is ~1e-6 degrees.

For windows too large to materialize (Na, Nb) the scan-tiled variants reduce
per-tile (counts / per-point flags) without ever holding the full lattice.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from spatialflink_tpu.models.batches import PointBatch
from spatialflink_tpu.utils import telemetry as _telemetry
from spatialflink_tpu.utils.deviceplane import instrumented_jit
from spatialflink_tpu.ops.range import cheb_layers

_BIG = np.float32(3.4e38)


def pairwise_dist2(ax, ay, bx, by, center_x=0.0, center_y=0.0):
    """(Na, Nb) squared Euclidean distances via the MXU.

    Callers should pass a center near the data (e.g. grid bbox midpoint) so
    the expansion runs on O(1)-magnitude operands.
    """
    a = jnp.stack([ax - center_x, ay - center_y], axis=1)  # (Na, 2)
    b = jnp.stack([bx - center_x, by - center_y], axis=1)  # (Nb, 2)
    a2 = jnp.sum(a * a, axis=1, keepdims=True)             # (Na, 1)
    b2 = jnp.sum(b * b, axis=1, keepdims=True).T           # (1, Nb)
    # HIGHEST keeps the MXU at full f32 (default TPU matmul precision is
    # bf16 inputs, ~1e-2 absolute error on O(1) operands — enough to flip
    # radius comparisons); K=2 makes the extra passes free
    cross = jnp.dot(a, b.T, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(a2 + b2 - 2.0 * cross, 0.0)


def bf16_distance_margin(ax, ay, bx, by, valid_a, valid_b,
                         center_x, center_y):
    """-> (margin, slack_sq): rigorous error bounds for the bf16 lattice.

    With centered coordinates bounded by X = max |coord| over valid slots:

    - ``margin`` (DISTANCE space) bounds the coordinate-rounding term: bf16
      rounding error per coordinate is <= X * 2^-8 (8 significand bits), so
      the bf16 pair offset differs from the true offset by at most
      sqrt(2) * 2 * X * 2^-8 in Euclidean norm.
    - ``slack_sq`` (SQUARED space) bounds the f32 accumulation of the
      a2 + b2 - 2ab expansion itself, whose rounding is ABSOLUTE at the
      operand magnitude (~X^2 * 2^-23 per op) and therefore must scale
      with X^2 — a fixed distance-space slack would be swamped for
      wide-extent grids (and gives only ~2*r*slack of squared-space
      headroom, vanishing at small radii). X^2 * 2^-16 over-covers the
      handful of f32 roundings by ~2 orders of magnitude while inflating
      the superset imperceptibly.

    Superset guarantee: any true pair (d <= r) satisfies
    ``d2_bf16 <= (r + margin)^2 + slack_sq``."""
    xa = jnp.max(jnp.where(valid_a, jnp.abs(ax - center_x), 0.0))
    ya = jnp.max(jnp.where(valid_a, jnp.abs(ay - center_y), 0.0))
    xb = jnp.max(jnp.where(valid_b, jnp.abs(bx - center_x), 0.0))
    yb = jnp.max(jnp.where(valid_b, jnp.abs(by - center_y), 0.0))
    x = jnp.maximum(jnp.maximum(xa, ya), jnp.maximum(xb, yb))
    margin = jnp.sqrt(2.0) * 2.0 * x * (2.0 ** -8)
    slack_sq = x * x * (2.0 ** -16) + 1e-12
    return margin, slack_sq


def pairwise_dist2_bf16(ax, ay, bx, by, center_x=0.0, center_y=0.0):
    """(Na, Nb) squared distances from a SINGLE-PASS bf16 MXU matmul.

    The f32 path (:func:`pairwise_dist2`) pins ``Precision.HIGHEST`` — three
    bf16 passes per matmul on TPU. Rounding the centered operands to bf16
    explicitly and accumulating in f32 runs one pass (~3x the MXU rate) at
    a bounded absolute distance error (:func:`bf16_distance_margin`);
    consumers use it as a conservative prefilter, never as the decision."""
    a = jnp.stack([ax - center_x, ay - center_y], axis=1).astype(jnp.bfloat16)
    b = jnp.stack([bx - center_x, by - center_y], axis=1).astype(jnp.bfloat16)
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    a2 = jnp.sum(af * af, axis=1, keepdims=True)
    b2 = jnp.sum(bf * bf, axis=1, keepdims=True).T
    cross = jnp.dot(a, b.T, preferred_element_type=jnp.float32)
    return jnp.maximum(a2 + b2 - 2.0 * cross, 0.0)


@partial(instrumented_jit, static_argnames=("n",))
def join_mask_bf16_superset(
    a: PointBatch,
    b: PointBatch,
    radius,
    nb_layers,
    center_x,
    center_y,
    *,
    n: int,
):
    """Conservative SUPERSET of :func:`join_mask` from the single-pass bf16
    lattice: every pair the f32 lattice keeps is kept (margin-inflated
    radius); extra near-boundary pairs are removed by the caller's exact
    f32 re-check on the (sparse) survivors. Cell pruning and validity are
    exact either way."""
    m, slack_sq = bf16_distance_margin(a.x, a.y, b.x, b.y, a.valid, b.valid,
                                       center_x, center_y)
    d2 = pairwise_dist2_bf16(a.x, a.y, b.x, b.y, center_x, center_y)
    r_sup = radius + m
    ok = _pair_cell_ok(a.cell, b.cell, nb_layers, n)
    return (ok & (d2 <= r_sup * r_sup + slack_sq)
            & a.valid[:, None] & b.valid[None, :])


def _pair_cell_ok(cell_a, cell_b, nb_layers, n):
    """(Na, Nb) cell-join predicate: a's cell within the neighboring layers
    of b's cell. ``nb_layers >= n`` disables pruning (radius-0 semantics)."""
    return cheb_layers(cell_a[:, None], cell_b[None, :], n) <= nb_layers


@partial(instrumented_jit, static_argnames=("n",))
def join_mask(
    a: PointBatch,
    b: PointBatch,
    radius,
    nb_layers,
    center_x,
    center_y,
    *,
    n: int,
):
    """Full (Na, Nb) boolean join lattice — for windows that fit in HBM."""
    d2 = pairwise_dist2(a.x, a.y, b.x, b.y, center_x, center_y)
    ok = _pair_cell_ok(a.cell, b.cell, nb_layers, n)
    return ok & (d2 <= radius * radius) & a.valid[:, None] & b.valid[None, :]


@partial(instrumented_jit, static_argnames=("n", "tile"))
def join_counts(
    a: PointBatch,
    b: PointBatch,
    radius,
    nb_layers,
    center_x,
    center_y,
    *,
    n: int,
    tile: int = 1024,
):
    """Scan-tiled join reduction: (per_a_count (Na,), total). Never holds the
    full lattice; tiles the b side in chunks of ``tile``, clamped to the b
    capacity (both are powers of two under batch bucketing, so the clamp
    guarantees divisibility)."""
    nb = b.x.shape[0]
    tile = min(tile, nb)
    assert nb % tile == 0, f"b capacity {nb} not a multiple of tile {tile}"
    bt = jax.tree.map(lambda v: v.reshape(nb // tile, tile, *v.shape[1:]), b)

    def step(carry, b_tile):
        m = join_mask(a, b_tile, radius, nb_layers, center_x, center_y, n=n)
        return carry + jnp.sum(m, axis=1, dtype=jnp.int32), None

    per_a, _ = jax.lax.scan(step, jnp.zeros(a.x.shape[0], jnp.int32), bt)
    return per_a, jnp.sum(per_a)


# Above this many lattice cells per window, join_pairs_host prefilters the
# a side with the tiled join_reduce reduction (O(Na) memory) before
# materializing any lattice tile — sparse joins then only pay for rows that
# actually have partners.
_LATTICE_BUDGET = 1 << 26


_BLOCK_MIN_CELLS = None


def adaptive_block_min_cells() -> int:
    """MEASURED dispatch-cost threshold for the adaptive pane-block
    coalescer: the lattice-cell count below which a standalone join block
    is dispatch-bound (its fixed dispatch+readback cost exceeds its math).

    Calibrated once per process on the live backend: time a minimal
    ``join_mask`` dispatch→readback (the per-dispatch floor) and a larger
    lattice (the marginal per-cell rate); ``min_cells = floor × rate`` is
    the break-even block size. BASELINE's dense pane-join rows lose
    (0.56–0.95×) exactly because their ``overlap²`` blocks sit below this
    point — the operator coalesces such windows into one lattice dispatch
    instead. ``SPATIALFLINK_JOIN_BLOCK_MIN_CELLS=<int>`` overrides (0
    disables coalescing — the A/B knob benches and tests use)."""
    global _BLOCK_MIN_CELLS
    if _BLOCK_MIN_CELLS is not None:
        return _BLOCK_MIN_CELLS
    import os
    import time

    env = os.environ.get("SPATIALFLINK_JOIN_BLOCK_MIN_CELLS")
    if env is not None:
        _BLOCK_MIN_CELLS = max(0, int(env))
        return _BLOCK_MIN_CELLS

    def batch(n):
        x = np.linspace(0.0, 1.0, n)
        return PointBatch.from_arrays(
            x, x, obj_id=np.arange(n, dtype=np.int32),
            cell=np.zeros(n, np.int32), pad=n)

    def run(a, b):
        np.asarray(join_mask(a, b, 0.1, 4, 0.5, 0.5, n=4))  # analysis: allow(host-sync): one-shot per-process calibration probe — the blocking readback IS the measurement (per-dispatch cost floor for the join block coalescer)

    sa, sb = batch(256), batch(128)
    ba, bb = batch(4096), batch(1024)
    run(sa, sb)
    run(ba, bb)  # compile both shapes outside the timed loops
    reps = 5
    t0 = time.perf_counter()
    for _ in range(reps):
        run(sa, sb)
    t_small = (time.perf_counter() - t0) / reps
    t0 = time.perf_counter()
    for _ in range(reps):
        run(ba, bb)
    t_big = (time.perf_counter() - t0) / reps
    cells_small, cells_big = 256 * 128, 4096 * 1024
    rate = (cells_big - cells_small) / max(t_big - t_small, 1e-9)
    # clamp: noise can make the floor look huge (or negative); a threshold
    # past ~16M cells would coalesce genuinely compute-bound blocks
    _BLOCK_MIN_CELLS = int(min(max(t_small * rate, 0.0), float(1 << 24)))
    return _BLOCK_MIN_CELLS


def _lattice_strategy() -> str:
    """'f32' (default) or 'bf16': which lattice _tiled_pairs_host runs. bf16 is
    the single-pass MXU superset + exact f32 re-check on survivors — the
    same pair sets up to f32 ties EXACTLY on the radius boundary (the
    re-check computes dx^2+dy^2 directly, which is slightly MORE accurate
    than the f32 lattice's a2+b2-2ab expansion; a pair whose true distance
    equals r to the last ulp can differ between strategies, measure-zero on
    real streams) at a hoped-for ~3x the lattice rate on TPU (not
    measured). Env-switched so the bench can A/B it
    without threading a parameter through every join operator."""
    import os

    v = os.environ.get("SPATIALFLINK_JOIN_LATTICE", "f32").strip().lower()
    if v not in ("f32", "bf16"):
        raise ValueError(
            f"SPATIALFLINK_JOIN_LATTICE={v!r}: expected 'f32' or 'bf16' "
            "(a typo here would silently measure f32 twice)")
    return v


def join_pairs_host(a: PointBatch, b: PointBatch, radius, grid, tile: int = 4096,
                    nb_layers=None, lattice_budget=None, window=None):
    """Host-side sparse pair extraction (the actual joined output stream).

    Iterates b tiles, pulls each tile's boolean lattice, and yields
    (a_index, b_index) integer arrays. Device does the O(Na*Nb) math; the
    host only touches the (sparse) survivors.

    When ``Na * Nb`` exceeds ``lattice_budget``, a :func:`ops.pallas_kernels.
    join_reduce` pre-pass computes per-a partner counts WITHOUT materializing
    the lattice (its docstring's whole argument), the a side is compacted to
    the rows with partners, and only the compacted lattice is extracted —
    for sparse joins this shrinks the materialized lattice by the selectivity
    factor.

    ``SPATIALFLINK_JOIN_LATTICE=bf16`` swaps the per-tile lattice for the
    single-pass bf16 superset + exact f32 re-check of the survivors (same
    pairs, less MXU time on TPU).

    With a telemetry session the stages are the ``join.reduce`` (pre-pass
    launch and count readback), ``join.compact`` and ``join.lattice`` (one
    per mask tile) spans, tagged ``window=<window>`` when it is given.
    """
    import numpy as np

    if nb_layers is None:
        # radius 0 => all cells are neighbors (UniformGrid.java:264-266)
        nb_layers = grid.n if radius == 0 else grid.candidate_layers(radius)
    cx = grid.min_x + grid.cell_length * grid.n / 2
    cy = grid.min_y + grid.cell_length * grid.n / 2
    na, nb = a.x.shape[0], b.x.shape[0]
    meta = {} if window is None else {"window": window}
    if lattice_budget is None:  # read at call time so tests can patch it
        lattice_budget = _LATTICE_BUDGET

    if na * nb > lattice_budget:
        from spatialflink_tpu.ops.pallas_kernels import join_reduce
        from spatialflink_tpu.utils.padding import bucket_size

        # conservative pre-radius: join_reduce computes exact squared
        # distances while join_mask uses the centered f32-precision MXU
        # expansion (pairwise_dist2 pins Precision.HIGHEST), whose error is
        # ABSOLUTE in d2 (~1e-6 on the O(1) centered operands, and it can
        # round tiny d2 all the way to 0) — so the slack must be absolute in
        # squared space, not relative in r (a relative bump vanishes for
        # small/zero radii). No row the lattice would keep is dropped; the
        # final pairs still come from join_mask.
        pre_r = float(np.sqrt(radius * radius + 1e-5))
        with _telemetry.span("reduce", "join", **meta):
            cnt, _, _ = join_reduce(a, b, pre_r, nb_layers, n=grid.n)
            rows = np.nonzero(np.asarray(cnt) > 0)[0]
        if rows.size == 0:
            return
        with _telemetry.span("compact", "join", **meta):
            size = bucket_size(rows.size)
            idx = np.concatenate(
                [rows, np.zeros(size - rows.size, rows.dtype)])
            sub = jax.tree.map(lambda v: np.asarray(v)[idx], a)
            # pad slots replay row 0 — mask them out via valid
            pad_valid = np.asarray(a.valid)[idx]
            pad_valid[rows.size:] = False
            sub = sub._replace(valid=pad_valid)
        for ai, bi in _tiled_pairs_host(sub, b, radius, nb_layers, cx, cy,
                                        grid.n, tile, meta):
            keep = ai < rows.size
            if keep.any():
                yield rows[ai[keep]], bi[keep]
        return

    yield from _tiled_pairs_host(a, b, radius, nb_layers, cx, cy, grid.n,
                                 tile, meta)


def _tiled_pairs_host(a: PointBatch, b: PointBatch, radius, nb_layers, cx, cy,
                      n: int, tile: int, meta: dict):
    """(a_index, b_index) survivors tile by tile over b; each tile's launch,
    readback and ``nonzero`` is one ``join.lattice`` span (``meta`` rides
    it), closed before the tile's pairs are handed on."""
    import numpy as np

    bf16 = _lattice_strategy() == "bf16"
    if bf16:
        # host copies once for the sparse re-check (centered f32, the same
        # arithmetic as the f32 lattice's expansion)
        axh, ayh = np.asarray(a.x) - cx, np.asarray(a.y) - cy
        bxh, byh = np.asarray(b.x) - cx, np.asarray(b.y) - cy
        r2 = np.float32(radius) * np.float32(radius)
    nb = b.x.shape[0]
    tile = min(tile, nb)
    for start in range(0, nb, tile):
        with _telemetry.span("lattice", "join", **meta):
            b_tile = jax.tree.map(lambda v: v[start : start + tile], b)
            if bf16:
                m = np.asarray(join_mask_bf16_superset(
                    a, b_tile, radius, nb_layers, cx, cy, n=n))
                ai, bj = np.nonzero(m)
                bj += start
                if ai.size:
                    # exact f32 re-check on the survivors only (sparse):
                    # the superset margin admits near-boundary extras,
                    # nothing else
                    dx = axh[ai] - bxh[bj]
                    dy = ayh[ai] - byh[bj]
                    keep = (dx * dx + dy * dy).astype(np.float32) <= r2
                    ai, bj = ai[keep], bj[keep]
            else:
                m = np.asarray(
                    join_mask(a, b_tile, radius, nb_layers, cx, cy, n=n)
                )
                ai, bj = np.nonzero(m)
                bj += start
        if ai.size:
            yield ai, bj


def pair_min_cheb(cells_a, mask_a, cells_b, mask_b, n):
    """(Ga, Gb) minimum Chebyshev layer distance between any valid cell pair
    of two multi-cell geometry batches.

    This is the arithmetic form of the reference's replication join for
    polygons/linestrings: object a (replicated to its own cells,
    ``HelperClass.java:299-376``) meets query b (replicated to the
    GN∪CN of its cells, ``join/JoinQuery.java:93-141``) iff some cell of a
    is within the candidate layers of some cell of b.
    """
    ch = cheb_layers(
        cells_a[:, None, :, None], cells_b[None, :, None, :], n
    )  # (Ga, Gb, Ca, Cb)
    valid = mask_a[:, None, :, None] & mask_b[None, :, None, :]
    return jnp.min(jnp.where(valid, ch, jnp.int32(2**30)), axis=(-2, -1))


@partial(instrumented_jit, static_argnames=("n",))
def join_point_geom_mask(points: PointBatch, geoms, radius, nb_layers, *, n: int):
    """(N, G) join lattice: point stream x polygon/linestring query stream
    (``join/PointPolygonJoinQuery.java``). Cell predicate: the point's cell
    within nb_layers of ANY geometry cell; exact distance <= r."""
    from spatialflink_tpu.ops.geom import points_to_geoms_dist

    d = points_to_geoms_dist(points, geoms)
    ch = cheb_layers(points.cell[:, None, None], geoms.cells[None], n)  # (N, G, C)
    cell_ok = jnp.any(
        (ch <= nb_layers) & geoms.cells_mask[None], axis=-1
    )
    return (
        cell_ok
        & (d <= radius)
        & points.valid[:, None]
        & geoms.valid[None, :]
    )


@partial(instrumented_jit, static_argnames=("n",))
def join_geom_geom_mask(a, b, radius, nb_layers, *, n: int):
    """(Ga, Gb) join lattice: polygon/linestring stream x polygon/linestring
    query stream (``join/PolygonPolygonJoinQuery.java`` etc.)."""
    from spatialflink_tpu.ops.geom import geoms_to_single_geom_dist

    d = jax.vmap(
        lambda eb, mb, areal: geoms_to_single_geom_dist(a, eb, mb, areal),
        out_axes=1,
    )(b.edges, b.edge_mask, b.is_areal)  # (Ga, Gb)
    cell_ok = pair_min_cheb(a.cells, a.cells_mask, b.cells, b.cells_mask, n) <= nb_layers
    return cell_ok & (d <= radius) & a.valid[:, None] & b.valid[None, :]
