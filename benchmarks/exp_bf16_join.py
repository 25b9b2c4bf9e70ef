"""A/B the join lattice precisions on-chip:

- f32: `join_mask` — `Precision.HIGHEST`, three bf16 MXU passes;
- bf16: `join_mask_bf16_superset` — single pass + margin (the decision
  stays exact via the sparse f32 re-check in `join_pairs_host`, which this
  experiment does NOT time: the lattice is the MXU-bound term).

Usage: python benchmarks/exp_bf16_join.py [--na 262144] [--nb 1024]
Prints one JSON line per strategy with the slope-method per-window time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.bench_configs import _grid, _points, _slope_time  # noqa: E402

RADIUS = 0.5


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--na", type=int, default=262_144)
    ap.add_argument("--nb", type=int, default=1_024)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from spatialflink_tpu.ops import join as J

    backend = jax.default_backend()
    grid = _grid()
    a = jax.device_put(_points(grid, args.na, seed=0))
    b = jax.device_put(_points(grid, args.nb, seed=1))
    L = grid.candidate_layers(RADIUS)
    cx = (grid.min_x + grid.max_x) / 2
    cy = (grid.min_y + grid.max_y) / 2

    for name, fn in (("f32", J.join_mask),
                     ("bf16_superset", J.join_mask_bf16_superset)):
        @jax.jit  # one compile covers every count (_slope_time's contract)
        def run_n(iters, fn=fn):
            def body(i, acc):
                m = fn(a._replace(x=a.x + i * 1e-9), b, RADIUS, L, cx, cy,
                       n=grid.n)
                return acc + jnp.sum(m, dtype=jnp.int32)
            return jax.lax.fori_loop(0, iters, body, jnp.int32(0))

        per = _slope_time(run_n, lo=2, hi=6)
        print(json.dumps(dict(
            strategy=name, na=args.na, nb=args.nb,
            per_window_ms=round(per * 1e3, 3),
            pair_tests_per_sec=round(args.na * args.nb / per),
            backend=backend)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
