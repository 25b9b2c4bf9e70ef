"""Distance math as branchless, vmap-friendly jnp functions.

Reference parity map (all in degree-space Euclidean unless stated — the
reference's hot paths call JTS ``geom.distance()`` on lon/lat degrees,
``utils/DistanceFunctions.java:14-54``):

- :func:`pp_dist`            <- getPointPointEuclideanDistance (:60-63)
- :func:`haversine`          <- HelperClass.computeHaverSine (HelperClass.java:379-385)
- :func:`point_segment_dist` <- getPointLineSegmentMinEuclideanDistance (:100-131)
- :func:`point_bbox_dist`    <- getPointPolygonBBoxMinEuclideanDistance (:150-200)
- :func:`bbox_bbox_dist`     <- getBBoxBBoxMinEuclideanDistance (:298-421)
- :func:`point_edges_dist`   <- getPointCoordinatesArrayMinEuclideanDistance (:74-85)
- :func:`point_in_rings`     <- JTS areal containment (even-odd ray cast)
- :func:`point_polygon_dist` <- JTS Point.distance(Polygon): 0 inside, else
                                min boundary distance
- :func:`seg_seg_dist` / :func:`edges_edges_dist` <- JTS boundary-boundary
                                distance (0 when boundaries cross)

Precision model: device coordinates are float32 absolute degrees. The f32
quantum at |x| ~ 116 deg is ~7.6e-6 deg (<1 m), which bounds every distance
below; the reference's canonical radii (0.005-0.5 deg) sit 3-5 orders of
magnitude above that floor. Kernels avoid *adding* error on top of storage
quantization (centered matmul expansion in ops.join, squared-distance
comparisons instead of sqrt).

Conventions: every "batch" geometry is a padded edge array
``edges: (..., E, 4)`` holding ``[x1, y1, x2, y2]`` per edge plus a boolean
``edge_mask: (..., E)``; padded edges must be excluded by the mask.  All
functions are elementwise over leading dims and safe under jit/vmap.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

EARTH_RADIUS_M = 6371008.7714  # HelperClass.java:50

_BIG = np.float32(3.4e38)  # sentinel "infinity" that survives f32 math


def pp_dist(x1, y1, x2, y2):
    """Euclidean point-point distance (degree space)."""
    return jnp.sqrt((x2 - x1) ** 2 + (y2 - y1) ** 2)


def pp_dist2(x1, y1, x2, y2):
    """Squared distance — prefer for comparisons; avoids the sqrt."""
    return (x2 - x1) ** 2 + (y2 - y1) ** 2


def haversine(lon1, lat1, lon2, lat2, radius=EARTH_RADIUS_M):
    """Great-circle distance in meters.

    Deliberate deviation: the reference's ``HelperClass.computeHaverSine``
    (HelperClass.java:379-385) is actually the spherical *law of cosines*
    (``acos(sin·sin + cos·cos·cos(dLon))·R``) despite its name, which loses
    all precision near acos(1) for close points. We use the true haversine
    formulation, which is numerically stable at small distances — that is
    where a radius predicate needs precision. For the law-of-cosines bitwise
    behavior use :func:`great_circle_law_of_cosines`.
    """
    lon1, lat1, lon2, lat2 = (jnp.deg2rad(v) for v in (lon1, lat1, lon2, lat2))
    dlat, dlon = lat2 - lat1, lon2 - lon1
    a = jnp.sin(dlat / 2) ** 2 + jnp.cos(lat1) * jnp.cos(lat2) * jnp.sin(dlon / 2) ** 2
    return 2 * radius * jnp.arcsin(jnp.sqrt(jnp.clip(a, 0.0, 1.0)))


def great_circle_law_of_cosines(lon1, lat1, lon2, lat2, radius=EARTH_RADIUS_M):
    """Exact formula of the reference's ``computeHaverSine`` (see above)."""
    lat1r, lat2r = jnp.deg2rad(lat1), jnp.deg2rad(lat2)
    dlon = jnp.deg2rad(lon2 - lon1)
    c = jnp.sin(lat1r) * jnp.sin(lat2r) + jnp.cos(lat1r) * jnp.cos(lat2r) * jnp.cos(dlon)
    return jnp.arccos(jnp.clip(c, -1.0, 1.0)) * radius


def point_segment_dist2(px, py, x1, y1, x2, y2):
    """Squared min distance from point to segment, branchless.

    Zero-length segments degrade to point distance (the reference sets
    param=-1 in that case, which clamps to the first endpoint — identical
    result since both endpoints coincide).
    """
    cx, cy = x2 - x1, y2 - y1
    len_sq = cx * cx + cy * cy
    # reciprocal BEFORE combining with the point operand: in the broadcast
    # lattices ((N, G, E) points x edges) this line has the edge shape only,
    # so the expensive divide runs O(G*E) times, not O(N*G*E) — the
    # per-point work below is multiply/add (measured +15% on config 4's CPU
    # bench; the divide is costlier still on the TPU VPU). A divide-free
    # cross-product form of the point_in_rings ray test was ALSO tried and
    # measured 25% SLOWER on CPU.
    inv_len = jnp.where(len_sq > 0, 1.0 / jnp.where(len_sq > 0, len_sq, 1.0),
                        0.0)
    dot = (px - x1) * cx + (py - y1) * cy
    t = jnp.clip(dot * inv_len, 0.0, 1.0)
    qx, qy = x1 + t * cx, y1 + t * cy
    return pp_dist2(px, py, qx, qy)


def point_segment_dist(px, py, x1, y1, x2, y2):
    return jnp.sqrt(point_segment_dist2(px, py, x1, y1, x2, y2))


def point_bbox_dist(px, py, bx1, by1, bx2, by2):
    """Min distance from a point to an axis-aligned box; 0 inside.

    Branchless equivalent of the 9-way case split in
    ``getPointPolygonBBoxMinEuclideanDistance`` (DistanceFunctions.java:150-200).
    """
    dx = jnp.maximum(jnp.maximum(bx1 - px, px - bx2), 0.0)
    dy = jnp.maximum(jnp.maximum(by1 - py, py - by2), 0.0)
    return jnp.sqrt(dx * dx + dy * dy)


def bbox_bbox_dist(a, b):
    """Min distance between two boxes given as (..., 4) [minx,miny,maxx,maxy];
    0 when they overlap (DistanceFunctions.java:298-421)."""
    ax1, ay1, ax2, ay2 = a[..., 0], a[..., 1], a[..., 2], a[..., 3]
    bx1, by1, bx2, by2 = b[..., 0], b[..., 1], b[..., 2], b[..., 3]
    dx = jnp.maximum(jnp.maximum(ax1 - bx2, bx1 - ax2), 0.0)
    dy = jnp.maximum(jnp.maximum(ay1 - by2, by1 - ay2), 0.0)
    return jnp.sqrt(dx * dx + dy * dy)


def point_edges_dist2(px, py, edges, edge_mask):
    """Squared min distance from point (px,py) to a masked edge array.

    edges: (E, 4), edge_mask: (E,). Invalid edges contribute +inf.
    """
    d2 = point_segment_dist2(px, py, edges[..., 0], edges[..., 1], edges[..., 2], edges[..., 3])
    return jnp.min(jnp.where(edge_mask, d2, _BIG), axis=-1)


def point_in_rings(px, py, edges, edge_mask):
    """Even-odd (ray cast) point-in-polygon over a masked edge array.

    Because every ring contributes its own closed edge loop to ``edges``,
    holes are handled naturally by crossing parity.  Horizontal edges and
    padded (masked / zero-length) edges contribute no crossings.
    """
    x1, y1 = edges[..., 0], edges[..., 1]
    x2, y2 = edges[..., 2], edges[..., 3]
    # half-open rule on y avoids double-counting shared vertices
    straddles = (y1 > py) != (y2 > py)
    # slope hoisted onto the edge shape: the divide runs O(G*E) times, the
    # (N, G, E) per-point lattice below is multiply/add/compare only (same
    # trick as point_segment_dist2's inv_len; straddles already excludes
    # horizontal edges, so the denom guard only protects padded slots)
    denom = jnp.where(y2 == y1, 1.0, y2 - y1)
    slope = (x2 - x1) / denom
    x_at_y = x1 + (py - y1) * slope
    crossing = straddles & edge_mask & (px < x_at_y)
    return jnp.sum(crossing.astype(jnp.int32), axis=-1) % 2 == 1


def point_polygon_dist(px, py, edges, edge_mask):
    """JTS ``Point.distance(Polygon)`` semantics: 0 if the point is inside the
    areal geometry (outer ring minus holes), else min boundary distance."""
    inside = point_in_rings(px, py, edges, edge_mask)
    bdist = jnp.sqrt(point_edges_dist2(px, py, edges, edge_mask))
    return jnp.where(inside, 0.0, bdist)


def _orient(ax, ay, bx, by, cx, cy):
    """Sign of the cross product (b-a) x (c-a)."""
    return (bx - ax) * (cy - ay) - (by - ay) * (cx - ax)


def segments_intersect(a, b):
    """Proper-or-touching intersection test for segments a=(x1,y1,x2,y2),
    b likewise; broadcasts over leading dims."""
    ax1, ay1, ax2, ay2 = a[..., 0], a[..., 1], a[..., 2], a[..., 3]
    bx1, by1, bx2, by2 = b[..., 0], b[..., 1], b[..., 2], b[..., 3]
    d1 = _orient(bx1, by1, bx2, by2, ax1, ay1)
    d2 = _orient(bx1, by1, bx2, by2, ax2, ay2)
    d3 = _orient(ax1, ay1, ax2, ay2, bx1, by1)
    d4 = _orient(ax1, ay1, ax2, ay2, bx2, by2)
    proper = ((d1 > 0) != (d2 > 0)) & ((d3 > 0) != (d4 > 0))
    # collinear/touching cases are covered by the endpoint-distance terms in
    # seg_seg_dist2 (distance 0 when an endpoint lies on the other segment)
    return proper


def seg_seg_dist2(a, b):
    """Squared min distance between two segments; 0 if they intersect."""
    d2 = jnp.minimum(
        jnp.minimum(
            point_segment_dist2(a[..., 0], a[..., 1], b[..., 0], b[..., 1], b[..., 2], b[..., 3]),
            point_segment_dist2(a[..., 2], a[..., 3], b[..., 0], b[..., 1], b[..., 2], b[..., 3]),
        ),
        jnp.minimum(
            point_segment_dist2(b[..., 0], b[..., 1], a[..., 0], a[..., 1], a[..., 2], a[..., 3]),
            point_segment_dist2(b[..., 2], b[..., 3], a[..., 0], a[..., 1], a[..., 2], a[..., 3]),
        ),
    )
    return jnp.where(segments_intersect(a, b), 0.0, d2)


def edges_edges_dist2(edges_a, mask_a, edges_b, mask_b):
    """Squared min distance between two masked edge sets (boundary-boundary).

    edges_a: (Ea, 4), edges_b: (Eb, 4). Cost is Ea*Eb — intended for
    per-candidate-pair evaluation after bbox/grid pruning, exactly where the
    reference runs JTS exact math.
    """
    d2 = seg_seg_dist2(edges_a[..., :, None, :], edges_b[..., None, :, :])
    valid = mask_a[..., :, None] & mask_b[..., None, :]
    return jnp.min(jnp.where(valid, d2, _BIG), axis=(-2, -1))
