"""LIVE streaming demo: a producer thread feeds wall-clock-stamped GeoJSON
points into the broker while a realtime range pipeline consumes them —
micro-batches evaluate mid-feed, per-record now-ingestionTime latencies ship
to a latency topic through :class:`KafkaLatencySink`, and the control tuple
stops the job remotely.

This is the reference's continuous operating mode (Kafka consumer feeding
``range/PointPointRangeQuery.java:43-83``, latency sinks at
``utils/HelperClass.java:455-529``) — replay answers "what were the
results", this answers "how far behind live is the pipeline".

Run: python examples/live_kafka_stream.py
"""

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from spatialflink_tpu.config import StreamConfig
from spatialflink_tpu.driver import decode_stream
from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import Point
from spatialflink_tpu.operators import (
    PointPointRangeQuery,
    QueryConfiguration,
    QueryType,
)
from spatialflink_tpu.streams import (
    InMemoryBroker,
    KafkaLatencySink,
    KafkaSource,
    serialize_spatial,
)
from spatialflink_tpu.utils.metrics import ControlTupleExit

N_RECORDS = 1500
RATE_HZ = 600


def main() -> int:
    grid = UniformGrid(115.50, 117.60, 39.60, 41.10, num_grid_partitions=100)
    broker = InMemoryBroker()
    done = {}

    def producer():
        rng = np.random.default_rng(7)
        for i in range(N_RECORDS):
            p = Point.create(float(rng.uniform(116.2, 117.0)),
                             float(rng.uniform(40.2, 40.9)), grid,
                             obj_id=f"veh{i % 61}",
                             timestamp=int(time.time() * 1000))
            broker.produce("points", serialize_spatial(p, "GeoJSON"))
            time.sleep(1.0 / RATE_HZ)
        done["at_ms"] = int(time.time() * 1000)
        broker.produce("points", json.dumps(
            {"geometry": {"type": "control", "coordinates": []}}))

    t = threading.Thread(target=producer, daemon=True)
    t.start()

    # consumer: follow the topic PAST its current end (live mode) until the
    # control tuple arrives; realtime micro-batches of 256 records
    source = KafkaSource(broker, "points", group="live-demo",
                         stop_at_end=False)
    stream = decode_stream(source, StreamConfig(format="GeoJSON"), grid)
    conf = QueryConfiguration(QueryType.RealTime, 10_000, 5_000,
                              realtime_batch_size=256)
    op = PointPointRangeQuery(conf, grid)
    lat_sink = KafkaLatencySink(broker, "latency")

    live_results = 0
    matched = 0
    try:
        for res in op.run(stream, Point.create(116.6, 40.55, grid), 0.25):
            matched += len(res.records)
            for rec in res.records:
                lat_sink.emit(rec)
            if "at_ms" not in done:
                live_results += 1
    except ControlTupleExit:
        pass
    t.join(timeout=30)

    lats = np.asarray(broker.topic_values("latency"), dtype=np.float64)
    assert lats.size > 0, "no latency records shipped"
    assert live_results >= 1, \
        "no result emitted while the producer was still feeding"
    p50, p95 = np.percentile(lats, [50, 95])
    print(f"{matched} matches in {live_results} live micro-batches "
          "(emitted while the producer was mid-feed)")
    print(f"live latency p50={p50:.0f}ms p95={p95:.0f}ms "
          f"over {lats.size} records")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
