"""Multi-query batching: Q standing queries over ONE stream, one device
dispatch per window.

A fleet-monitoring shape: 6 hotspot range queries and 6 hotspot kNN (k=5)
queries watch the same vehicle stream. The reference (GeoFlink) wires one
query object per Flink job (`StreamingJob.java:470`), so this workload
there is 12 jobs re-reading the stream 12 times; here it is TWO operators,
each answering its whole query batch per window via `run_multi` —
the query batch is one vmapped array axis over the window's single device
residency (exactness fallback included; see ARCHITECTURE.md "Multi-query
batching").

Run: python examples/multi_query_hotspots.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import Point
from spatialflink_tpu.operators import (
    PointPointKNNQuery,
    PointPointRangeQuery,
    QueryConfiguration,
    QueryType,
)


def main() -> int:
    grid = UniformGrid(115.50, 117.60, 39.60, 41.10, num_grid_partitions=100)
    t0 = 1_700_000_000_000

    def stream():
        # fresh generator per call: both operator passes replay the SAME
        # vehicle stream, as the docstring promises
        rng = np.random.default_rng(11)
        for i in range(6000):
            yield Point.create(float(rng.uniform(116, 117)),
                               float(rng.uniform(40, 41)), grid,
                               obj_id=f"veh{i % 113}",
                               timestamp=t0 + i * 10)

    hotspots = [Point.create(116.0 + 0.15 * q, 40.0 + 0.15 * q, grid)
                for q in range(6)]
    conf = QueryConfiguration(QueryType.WindowBased,
                              window_size_ms=10_000, slide_ms=5_000)

    windows = 0
    for res in PointPointRangeQuery(conf, grid).run_multi(
            stream(), hotspots, radius=0.25):
        windows += 1
        counts = [len(r) for r in res.records]
        print(f"range window [{res.window_start}, {res.window_end}) "
              f"per-hotspot matches: {counts}")

    knn_windows = 0
    for res in PointPointKNNQuery(conf, grid).run_multi(
            stream(), hotspots, radius=0.5, k=5):
        knn_windows += 1
        nearest = [r[0][0] if r else "-" for r in res.records]
        print(f"knn   window [{res.window_start}, {res.window_end}) "
              f"nearest per hotspot: {nearest}")

    print(f"answered {2 * len(hotspots)} standing queries x "
          f"{windows} windows in {windows + knn_windows} dispatches total "
          f"(one per operator per window; the reference: "
          f"{2 * len(hotspots)} Flink jobs)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
