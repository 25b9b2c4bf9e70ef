"""Kill/resume a stateful trajectory query from its checkpoint.

Runs realtime per-trajectory stats (tStats) over the first part of a
stream, checkpointing as it goes; then "crashes", and a second operator
resumes from the snapshot and consumes only the remainder. The final state
equals an uninterrupted run — the reference inherits this from Flink
checkpointing; here the snapshot/restore is explicit (`runtime/state.py`).

Run: python examples/checkpoint_resume.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import Point
from spatialflink_tpu.operators import (
    PointTStatsQuery,
    QueryConfiguration,
    QueryType,
)


def stream(grid, lo, hi):
    rng = np.random.default_rng(3)
    t0 = 1_700_000_000_000
    xs = rng.uniform(116, 117, 400)
    ys = rng.uniform(40, 41, 400)
    pts = [Point.create(float(xs[i]), float(ys[i]), grid,
                        obj_id=f"traj{i % 7}", timestamp=t0 + i * 1000)
           for i in range(400)]
    return pts[lo:hi]


def main() -> int:
    grid = UniformGrid(115.50, 117.60, 39.60, 41.10, num_grid_partitions=100)
    conf = lambda: QueryConfiguration(QueryType.RealTime,
                                      realtime_batch_size=32)
    cp = os.path.join(tempfile.mkdtemp(), "tstats.npz")

    full = list(PointTStatsQuery(conf(), grid).run(iter(stream(grid, 0, 400))))

    # first run consumes 0..250, checkpointing every micro-batch, then "dies"
    list(PointTStatsQuery(conf(), grid).run(
        iter(stream(grid, 0, 250)), checkpoint_path=cp, checkpoint_every=1))
    consumed = PointTStatsQuery.checkpoint_consumed(cp)
    print(f"crashed after checkpoint; consumed offset = {consumed}")

    # resume: the operator restores STATE; the SOURCE must skip the already-
    # consumed prefix itself (slice a file replay by the recorded offset, as
    # here and in the driver's --resume; an offset-managed source like a
    # Kafka consumer group seeks instead). Feeding the full stream again
    # would double-count.
    resumed = list(PointTStatsQuery(conf(), grid).run(
        iter(stream(grid, consumed, 400)), checkpoint_path=cp))

    # realtime emissions cover the trajectories touched by each micro-batch,
    # and batch boundaries differ between the two runs — compare the LAST
    # reported stats per trajectory (the accumulated state), not one batch
    def final_stats(results):
        out = {}
        for w in results:
            for r in w.records:
                out[r[0]] = r[1:4]  # (spatial_len, temporal_len, speed)
        return out

    last_full = final_stats(full)
    last_res = final_stats(resumed)
    assert last_full.keys() == last_res.keys()
    for k in last_full:  # f32 length accumulation may differ in the last
        #                  bit across the checkpoint boundary — state parity,
        #                  not bitwise replay
        np.testing.assert_allclose(last_full[k], last_res[k], rtol=1e-5)
    print(f"resumed run matches uninterrupted run: "
          f"{len(last_full)} trajectories, e.g. "
          + ", ".join(f"{k}: len={v[0]:.3f}" for k, v in
                      sorted(last_full.items())[:3]))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
