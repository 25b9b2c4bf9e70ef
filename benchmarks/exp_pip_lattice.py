"""EXPERIMENT: lane-major pallas kernel for the multi-geometry PIP lattice.

Candidate replacement for ``ops.geom.points_to_geoms_dist`` (BASELINE
config 4: 65k points x 10.2k polygons). The XLA lattice measured 7.15G
pip-tests/s on the v5e-1 (~1.14T ops/s); the VPU ceiling is ~3-4T ops/s, so
there is headroom IF a hand kernel avoids XLA's lattice materialization
overheads without drowning in grid-step cost.

Layout: output tiles (PT points x GT geoms); points broadcast from a
(PT, 1) column against (1, GT) edge rows sliced from an edge array stored
(E, G) — each edge index yields contiguous geometry lanes. Accumulators
(crossings, min-d2) are full (PT, GT) tiles (unlike the deleted join
kernel's (TP, 1) columns, so lanes stay busy).

Run on the chip:  python benchmarks/exp_pip_lattice.py [--scale full]
Correctness (CPU): SPATIALFLINK_PALLAS=interpret python benchmarks/exp_pip_lattice.py --check
NOT wired into the library: promotion requires an on-chip win vs the XLA
twin.
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PT = 512   # point rows per output tile
GT = 512   # geometry lanes per output tile


def build_kernel(e_max: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    F_BIG = 3.4e38

    def kern(px_ref, py_ref, x1_ref, y1_ref, x2_ref, y2_ref, m_ref,
             cross_ref, mind2_ref):
        px = px_ref[:]  # (PT, 1)
        py = py_ref[:]

        def one(e, carry):
            cross, mind2 = carry
            x1 = x1_ref[e, :][None, :]  # (1, GT)
            y1 = y1_ref[e, :][None, :]
            x2 = x2_ref[e, :][None, :]
            y2 = y2_ref[e, :][None, :]
            valid = m_ref[e, :][None, :] > 0

            straddles = (y1 > py) != (y2 > py)
            denom = jnp.where(y2 == y1, 1.0, y2 - y1)
            slope = (x2 - x1) / denom          # (1, GT) — hoisted divide
            x_at_y = x1 + (py - y1) * slope    # (PT, GT)
            crossing = straddles & (px < x_at_y) & valid
            cross = cross + crossing.astype(jnp.float32)

            cx, cy = x2 - x1, y2 - y1
            len_sq = cx * cx + cy * cy
            inv_len = jnp.where(len_sq > 0.0,
                                1.0 / jnp.where(len_sq > 0.0, len_sq, 1.0),
                                0.0)             # (1, GT) — hoisted divide
            dot = (px - x1) * cx + (py - y1) * cy
            tt = jnp.clip(dot * inv_len, 0.0, 1.0)
            qx, qy = x1 + tt * cx, y1 + tt * cy
            d2 = (px - qx) ** 2 + (py - qy) ** 2
            mind2 = jnp.minimum(mind2, jnp.where(valid, d2, F_BIG))
            return cross, mind2

        cross, mind2 = jax.lax.fori_loop(
            0, e_max, one,
            (jnp.zeros((PT, GT), jnp.float32),
             jnp.full((PT, GT), F_BIG, jnp.float32)))
        cross_ref[:] = cross
        mind2_ref[:] = mind2

    @functools.partial(jax.jit, static_argnames=())
    def run(px, py, edges_t, mask_t, is_areal):
        # px/py (Np,), edges_t (E, G, 4) transposed to per-coord (E, G),
        # mask_t (E, G) int32, is_areal (G,) bool
        n, g = px.shape[0], edges_t.shape[1]
        npad = -(-n // PT) * PT
        gpad = -(-g // GT) * GT

        def padp(v):
            return jnp.pad(v.astype(jnp.float32), (0, npad - n)).reshape(npad, 1)

        def padg(v, fill=0.0):
            return jnp.pad(v, ((0, 0), (0, gpad - g)), constant_values=fill)

        pxp, pyp = padp(px), padp(py)
        x1 = padg(edges_t[..., 0].astype(jnp.float32))
        y1 = padg(edges_t[..., 1].astype(jnp.float32))
        x2 = padg(edges_t[..., 2].astype(jnp.float32))
        y2 = padg(edges_t[..., 3].astype(jnp.float32))
        em = padg(mask_t.astype(jnp.int32), 0)

        p_spec = pl.BlockSpec((PT, 1), lambda i, j: (i, 0),
                              memory_space=pltpu.VMEM)
        e_spec = pl.BlockSpec((e_max, GT), lambda i, j: (0, j),
                              memory_space=pltpu.VMEM)
        o_spec = pl.BlockSpec((PT, GT), lambda i, j: (i, j),
                              memory_space=pltpu.VMEM)

        cross, mind2 = pl.pallas_call(
            kern,
            grid=(npad // PT, gpad // GT),
            in_specs=[p_spec, p_spec] + [e_spec] * 5,
            out_specs=(o_spec, o_spec),
            out_shape=(jax.ShapeDtypeStruct((npad, gpad), jnp.float32),
                       jax.ShapeDtypeStruct((npad, gpad), jnp.float32)),
            interpret=interpret,
        )(pxp, pyp, x1, y1, x2, y2, em)
        inside = (cross[:n, :g].astype(jnp.int32) % 2) == 1
        d = jnp.sqrt(mind2[:n, :g])
        return jnp.where(inside & is_areal[None, :], 0.0, d)

    return run


def make_inputs(scale):
    import jax

    from spatialflink_tpu.models import Polygon
    from spatialflink_tpu.models.batches import EdgeGeomBatch
    from benchmarks.bench_configs import _grid, _points  # reuse config-4 gen

    grid = _grid()
    n = 65_536 if scale == "full" else 2_048
    g = 10_240 if scale == "full" else 256
    rng = np.random.default_rng(3)
    polys = []
    for _ in range(g):
        cx = rng.uniform(grid.min_x + 0.1, grid.max_x - 0.1)
        cy = rng.uniform(grid.min_y + 0.1, grid.max_y - 0.1)
        w, h = rng.uniform(0.01, 0.05, 2)
        polys.append(Polygon.create(
            [[(cx - w, cy - h), (cx + w, cy - h), (cx + w, cy + h),
              (cx - w, cy + h), (cx - w, cy - h)]], grid))
    gb = jax.device_put(EdgeGeomBatch.from_objects(polys, grid))
    pts = jax.device_put(_points(grid, n, seed=4))
    return grid, pts, gb


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="full", choices=["small", "full"])
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args()

    interpret = os.environ.get("SPATIALFLINK_PALLAS") == "interpret"
    import jax
    import jax.numpy as jnp

    from spatialflink_tpu.ops.geom import points_to_geoms_dist

    scale = "small" if args.check else args.scale
    grid, pts, gb = make_inputs(scale)
    e_max = gb.edges.shape[1]
    # (G, E, 4) -> (E, G, 4); (G, E) -> (E, G)
    edges_t = jnp.swapaxes(gb.edges, 0, 1)
    mask_t = jnp.swapaxes(gb.edge_mask, 0, 1)
    run = build_kernel(e_max, interpret)

    if args.check:
        got = np.asarray(run(pts.x, pts.y, edges_t, mask_t, gb.is_areal))
        want = np.asarray(points_to_geoms_dist(pts, gb))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        print(f"check ok: {got.shape} lattice matches XLA twin")
        return

    def slope(fn):
        @jax.jit
        def run_n(iters):
            def body(i, acc):
                return acc + fn(i)
            return jax.lax.fori_loop(0, iters, body, jnp.float32(0))
        jax.block_until_ready(run_n(jnp.int32(2)))

        def t(it, reps=3):
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(run_n(jnp.int32(it)))
                best = min(best, time.perf_counter() - t0)
            return best

        lo, hi = 2, 10
        tl = t(lo)
        while True:
            th = t(hi)
            gap = th - tl
            if gap >= 0.2 or hi >= 40_000:
                break
            hi = min(hi * 5, 40_000)
        return gap / (hi - lo)

    n, g = pts.x.shape[0], gb.edges.shape[0]

    def f_pallas(i):
        return jnp.sum(run(pts.x + i * 1e-9, pts.y, edges_t, mask_t,
                           gb.is_areal) <= 0.0).astype(jnp.float32)

    def f_xla(i):
        return jnp.sum(points_to_geoms_dist(
            pts._replace(x=pts.x + i * 1e-9), gb) <= 0.0).astype(jnp.float32)

    sp, sx = slope(f_pallas), slope(f_xla)
    print(f"pallas lattice: {sp * 1e3:.2f}ms/win ({n * g / sp / 1e9:.2f}G pip/s)")
    print(f"xla lattice:    {sx * 1e3:.2f}ms/win ({n * g / sx / 1e9:.2f}G pip/s)")
    print(f"ratio xla/pallas = {sx / sp:.2f}x")


if __name__ == "__main__":
    main()
