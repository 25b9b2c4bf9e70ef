"""End-to-end streaming pipeline with the in-memory Kafka-style broker:
produce GeoJSON points to a topic, consume + parse them, run a windowed
point-point range query, and sink idempotent per-window results.

Mirrors the reference's `queryOption 1` pipeline (Kafka consumer ->
Deserialization -> PointPointRangeQuery -> Kafka producer) without needing
a broker process.

Run: python examples/streaming_range_query.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import Point
from spatialflink_tpu.operators import (
    PointPointRangeQuery,
    QueryConfiguration,
    QueryType,
)
from spatialflink_tpu.streams import InMemoryBroker, KafkaSource
from spatialflink_tpu.streams.formats import parse_spatial, serialize_spatial
from spatialflink_tpu.streams.kafka import IdempotentWindowSink


def main() -> int:
    grid = UniformGrid(115.50, 117.60, 39.60, 41.10, num_grid_partitions=100)
    broker = InMemoryBroker()

    # producer side: 2000 GeoJSON points over ~40s of event time
    rng = np.random.default_rng(7)
    t0 = 1_700_000_000_000
    for i in range(2000):
        p = Point.create(float(rng.uniform(116, 117)),
                         float(rng.uniform(40, 41)), grid,
                         obj_id=f"veh{i % 97}", timestamp=t0 + i * 20)
        broker.produce("points", serialize_spatial(p, "GeoJSON"))

    # consumer side: parse -> windowed range query -> idempotent sink
    stream = (parse_spatial(v, "GeoJSON", grid)
              for v in KafkaSource(broker, "points", group="range-demo"))
    conf = QueryConfiguration(QueryType.WindowBased,
                              window_size_ms=10_000, slide_ms=5_000)
    query = Point.create(116.5, 40.5, grid)
    sink = IdempotentWindowSink()
    for window in PointPointRangeQuery(conf, grid).run(stream, query, 0.5):
        sink.emit(window)
        print(f"window [{window.window_start}, {window.window_end}) "
              f"{len(window.records)} matches")
    print(f"delivered windows: {sink.delivered_count}; redelivered "
          f"duplicates suppressed: {sink.duplicates_suppressed}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
