"""Pallas TPU kernel for the point->query-geometry hot op, plus the tiled
join reduction.

- :func:`pip_dist` — point -> single-query-geometry distance: even-odd
  ray-cast containment fused with min point-segment boundary distance in one
  pass over the edge array. This is the hot loop of every point-stream x
  polygon/linestring-query operator (reference:
  ``range/PointPolygonRangeQuery.java:117-``, ``tRange/PointPolygonTRangeQuery
  .java:53-87`` — there a per-tuple JTS call; here one kernel per window).
  The pallas kernel is LANE-MAJOR: points tiled (128, 128) across the full
  VPU register file, edges broadcast one at a time from SMEM scalars
  ((TP, 1) column blocks would use 1 of 128 vector lanes). Its speed
  against the fused XLA twin is not measured on today's chip;
  ``chip_smoke.py`` runs it through the driver and checks its answers.
- :func:`join_reduce` — per-left-point reduction over the whole right batch:
  number of right partners within radius (after Chebyshev cell pruning,
  ``join/JoinQuery.java:148-162`` semantics) plus the nearest partner's
  distance and index, without materializing the (N, M) pair matrix in HBM
  (a lax.scan over right-side tiles; peak memory O(N * tile)). Reachable
  path: ``ops.join.join_pairs_host`` (every join operator's pair extraction)
  uses it to prefilter the a side when the window's lattice exceeds the
  budget, so sparse big-window joins only materialize rows that have
  partners. This one is deliberately NOT pallas: the compiler already
  emits good code for an elementwise broadcast reduction, so the hand
  kernel was deleted rather than carried as a showpiece.

:func:`pip_dist` dispatch is by backend — pallas on TPU, the jnp twin
(:func:`ops.geom.points_to_single_edges_raw`) elsewhere — overridable with
``SPATIALFLINK_PALLAS`` = ``off`` | ``interpret`` (CPU interpreter, used by
the test suite) | ``auto``. The edge array is staged in SMEM (a few KB of
scalar memory) in ``_EDGE_CHUNK``-edge blocks along a second grid
dimension, accumulating into the revisited point-tile output — so a
10k-vertex query polygon streams through the same kernel as a small
building footprint (the round-4 512-edge fallback cap is gone).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from spatialflink_tpu.utils.deviceplane import instrumented_jit

_BIG = np.float32(3.4e38)
_F_BIG = 3.4e38  # plain literal for in-kernel use (pallas kernels
#                  cannot capture traced constants)

# lane-major point tiling: (sublane rows, lanes) = (128, 128) => 16384
# points per grid step, every op on a full (8, 128) vreg
_TPS = 128
_LAN = 128
# scalar edge loop unroll (measured: 4 is ~35% over 1, 8 is flat)
_UNROLL = 4
# SMEM staging block: geometries up to this many edges load whole (8 KB of
# scalar memory); bigger ones STREAM chunk by chunk through a second grid
# dimension, with the point tile's partial cross-count/min-distance
# accumulated in the revisited VMEM output block — no edge-count cap
_EDGE_CHUNK = 512


def pallas_mode() -> str:
    """'tpu' | 'interpret' | 'off' — how/whether to run the pallas path."""
    env = os.environ.get("SPATIALFLINK_PALLAS", "auto").lower()
    if env in ("0", "off", "no"):
        return "off"
    if env == "interpret":
        return "interpret"
    return "tpu" if jax.default_backend() == "tpu" else "off"


def _pad_to(arr: jnp.ndarray, size: int, fill) -> jnp.ndarray:
    n = arr.shape[0]
    if n == size:
        return arr
    return jnp.concatenate(
        [arr, jnp.full((size - n,) + arr.shape[1:], fill, arr.dtype)]
    )


def _ceil_to(n: int, m: int) -> int:
    return max(((n + m - 1) // m) * m, m)


# --------------------------------------------------------------------------- #
# Fused point-in-rings + min boundary distance (lane-major pallas kernel)
# --------------------------------------------------------------------------- #


def _pip_kernel(e_ref, m_ref, px_ref, py_ref, cross_ref, mind2_ref):
    """One (TPS, LAN) point tile against one SMEM edge CHUNK.

    Edges live in SMEM as (4, EC) scalars; each loop step broadcasts one
    edge's parameters against the whole point tile, so the divide (slope,
    inv_len) is scalar work done once per edge — the vector units only see
    multiply/add/compare (the same hoisting as ops.distances, one level
    stronger: scalar instead of per-edge-lane). Grid dim 1 walks the edge
    chunks (innermost, so the output block stays VMEM-resident): chunk 0
    initializes the tile's accumulators, later chunks add crossings and
    take the running min — an even-odd count and a min compose exactly
    across any chunking of the edge list.
    """
    px = px_ref[:]  # (TPS, LAN)
    py = py_ref[:]
    ne = m_ref.shape[1]

    def one(t, cross, mind2):
        x1 = e_ref[0, t]
        y1 = e_ref[1, t]
        x2 = e_ref[2, t]
        y2 = e_ref[3, t]
        valid = m_ref[0, t] > 0

        # even-odd ray cast, half-open on y (ops.distances.point_in_rings)
        straddles = (y1 > py) != (y2 > py)
        denom = jnp.where(y2 == y1, 1.0, y2 - y1)
        x_at_y = x1 + (py - y1) * ((x2 - x1) / denom)
        crossing = straddles & (px < x_at_y) & valid
        # f32 accumulator: counts are <= E <= 512, exact in f32, and float
        # adds keep the whole loop on one vreg bank
        cross = cross + crossing.astype(jnp.float32)

        # point-segment squared distance (ops.distances.point_segment_dist2)
        cx, cy = x2 - x1, y2 - y1
        len_sq = cx * cx + cy * cy
        inv_len = jnp.where(len_sq > 0.0,
                            1.0 / jnp.where(len_sq > 0.0, len_sq, 1.0), 0.0)
        dot = (px - x1) * cx + (py - y1) * cy
        tt = jnp.clip(dot * inv_len, 0.0, 1.0)
        qx, qy = x1 + tt * cx, y1 + tt * cy
        d2 = (px - qx) ** 2 + (py - qy) ** 2
        mind2 = jnp.minimum(mind2, jnp.where(valid, d2, _F_BIG))
        return cross, mind2

    def body(t, carry):
        cross, mind2 = carry
        for u in range(_UNROLL):
            cross, mind2 = one(t * _UNROLL + u, cross, mind2)
        return cross, mind2

    cross, mind2 = jax.lax.fori_loop(
        0, ne // _UNROLL, body,
        (jnp.zeros((_TPS, _LAN), jnp.float32),
         jnp.full((_TPS, _LAN), _F_BIG, jnp.float32)),
    )
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        cross_ref[:] = cross
        mind2_ref[:] = mind2

    @pl.when(j > 0)
    def _accumulate():
        cross_ref[:] = cross_ref[:] + cross
        mind2_ref[:] = jnp.minimum(mind2_ref[:], mind2)


@functools.partial(instrumented_jit, static_argnames=("interpret",))
def _pip_pallas(px, py, edges, edge_mask, *, interpret: bool):
    n = px.shape[0]
    # edges arrive pre-bucketed by pip_dist OUTSIDE this jit boundary (to a
    # multiple of 64 up to _EDGE_CHUNK, then of _EDGE_CHUNK), so distinct
    # query geometries land on shared (ep, 4) avals and compilations
    ep = edges.shape[0]
    ec = min(ep, _EDGE_CHUNK)
    rows = -(-n // _LAN)
    rpad = _ceil_to(rows, _TPS)
    npad = rpad * _LAN

    pxp = _pad_to(px.astype(jnp.float32), npad, 0.0).reshape(rpad, _LAN)
    pyp = _pad_to(py.astype(jnp.float32), npad, 0.0).reshape(rpad, _LAN)
    e4 = edges.astype(jnp.float32).T  # (4, ep)
    em = edge_mask.astype(jnp.int32).reshape(1, ep)

    pt_spec = pl.BlockSpec((_TPS, _LAN), lambda i, j: (i, 0),
                           memory_space=pltpu.VMEM)
    out_spec = pl.BlockSpec((_TPS, _LAN), lambda i, j: (i, 0),
                            memory_space=pltpu.VMEM)
    e_spec = pl.BlockSpec((4, ec), lambda i, j: (0, j),
                          memory_space=pltpu.SMEM)
    m_spec = pl.BlockSpec((1, ec), lambda i, j: (0, j),
                          memory_space=pltpu.SMEM)

    cross, mind2 = pl.pallas_call(
        _pip_kernel,
        # edge chunks innermost: the point tile's output block is revisited
        # across j while resident, accumulating count/min
        grid=(rpad // _TPS, ep // ec),
        in_specs=[e_spec, m_spec, pt_spec, pt_spec],
        out_specs=(out_spec, out_spec),
        out_shape=(
            jax.ShapeDtypeStruct((rpad, _LAN), jnp.float32),
            jax.ShapeDtypeStruct((rpad, _LAN), jnp.float32),
        ),
        interpret=interpret,
    )(e4, em, pxp, pyp)
    inside = (cross.reshape(-1)[:n].astype(jnp.int32) % 2) == 1
    return inside, mind2.reshape(-1)[:n]


def pip_dist(px, py, edges, edge_mask, is_areal: bool):
    """(N,) JTS-style distance from each point to ONE query geometry.

    Drop-in twin of ``ops.geom.points_to_single_geom_dist`` (same semantics:
    0 inside areal geometries, else min boundary distance); fused lane-major
    pallas on TPU (any edge count — big geometries stream through SMEM in
    ``_EDGE_CHUNK``-edge chunks), jnp elsewhere.
    """
    mode = pallas_mode()
    if mode == "off":
        from spatialflink_tpu.ops.geom import points_to_single_edges_raw

        inside, mind2 = points_to_single_edges_raw(px, py, edges, edge_mask)
    else:
        # bucket the edge count BEFORE the jit boundary so a pipeline's
        # distinct query geometries share one compilation: multiples of 64
        # up to one SMEM chunk, whole chunks beyond (the chunked grid
        # streams any edge count — a 10k-vertex query polygon runs the
        # same kernel as a building footprint); padded slots are masked
        # out in-kernel
        ne = edges.shape[0]
        ep = (_ceil_to(ne, 64) if ne <= _EDGE_CHUNK
              else _ceil_to(ne, _EDGE_CHUNK))
        inside, mind2 = _pip_pallas(
            px, py, _pad_to(edges, ep, 0.0), _pad_to(edge_mask, ep, False),
            interpret=(mode == "interpret"))
    return jnp.where(inside & is_areal, 0.0, jnp.sqrt(mind2))


# --------------------------------------------------------------------------- #
# Per-left-point join reduction (tiled XLA scan — measured faster than the
# hand pallas kernel it replaced; see module docstring)
# --------------------------------------------------------------------------- #


@functools.partial(instrumented_jit, static_argnames=("n", "tile"))
def _join_reduce_impl(a, b, radius, nb_layers, *, n: int, tile: int):
    """a/b: PointBatch-like namedtuples with .x/.y/.cell/.valid.

    A lax.scan over right-side tiles so peak memory is (Na, tile) regardless
    of Nb (the whole point of this reduction; a single broadcast would
    materialize the (Na, Nb) lattice in HBM).
    """
    acx, acy = a.cell // n, a.cell % n
    bcx, bcy = b.cell // n, b.cell % n
    nb_ = b.x.shape[0]
    tile = min(tile, nb_)
    pad = (-nb_) % tile  # arbitrary capacities pad up, masked via valid
    n_tiles = (nb_ + pad) // tile

    def resh(v, fill=0):
        return _pad_to(v, nb_ + pad, fill).reshape(n_tiles, tile, *v.shape[1:])

    bx_t, by_t = resh(b.x), resh(b.y)
    bcx_t, bcy_t = resh(bcx), resh(bcy)
    bv_t = resh(b.valid, False)
    offsets = jnp.arange(n_tiles, dtype=jnp.int32) * tile

    def step(carry, xs):
        cnt, mind2, amin = carry
        bx, by, bcx_, bcy_, bv, off = xs
        cheb = jnp.maximum(jnp.abs(acx[:, None] - bcx_[None, :]),
                           jnp.abs(acy[:, None] - bcy_[None, :]))
        d2 = ((a.x[:, None] - bx[None, :]) ** 2
              + (a.y[:, None] - by[None, :]) ** 2)
        hit = (a.valid[:, None] & bv[None, :]
               & (cheb <= nb_layers) & (d2 <= radius * radius))
        cnt = cnt + jnp.sum(hit, axis=1, dtype=jnp.int32)
        d2m = jnp.where(hit, d2, _BIG)
        tmin = jnp.min(d2m, axis=1)
        targ = jnp.where(jnp.any(hit, axis=1),
                         jnp.argmin(d2m, axis=1).astype(jnp.int32) + off,
                         jnp.int32(-1))
        # strict < keeps the earliest tile's index on ties, matching a
        # one-pass argmin over the full lattice
        better = tmin < mind2
        return (cnt, jnp.where(better, tmin, mind2),
                jnp.where(better, targ, amin)), None

    na_ = a.x.shape[0]
    init = (jnp.zeros(na_, jnp.int32), jnp.full(na_, _BIG, jnp.float32),
            jnp.full(na_, -1, jnp.int32))
    (cnt, mind2, amin), _ = jax.lax.scan(
        step, init, (bx_t, by_t, bcx_t, bcy_t, bv_t, offsets))
    return cnt, mind2, amin


def join_reduce(a, b, radius, nb_layers, *, n: int, tile: int = 4096):
    """Per-left-point join reduction against the whole right batch.

    Returns ``(count, min_dist2, argmin)`` each (N,): how many valid right
    points lie within ``radius`` after Chebyshev cell pruning (the
    replicate-to-neighboring-cells rule, ``join/JoinQuery.java:72-90``), the
    squared distance to the nearest such partner (+inf if none) and its index
    in the right batch (-1 if none). ``tile`` bounds the per-scan-step
    lattice width (peak memory Na * tile).
    """
    return _join_reduce_impl(a, b, radius, nb_layers, n=n, tile=tile)
