"""Broker-path throughput: sustained records/s for the SAME bounded query
through the two ``--kafka`` decode paths the driver offers, plus the
file-replay reference point — quantifying what each decode/replay tier buys
(the reference's pipelines are all Kafka-fed, ``StreamingJob.java:473``):

- ``record``:  per-record ``parse_spatial`` in the commit tap (the
  fallback when a chunk cannot ride the native parser; live follow mode
  also uses chunked decode, with starvation-sentinel flushes bounding the
  buffering latency to one poll cycle)
- ``chunked``: the default bounded drain — raw records batch through the
  native bulk parser in ``WindowCommitTap`` chunks
- ``file``:    file replay of the same records (no broker)

All three produce identical windows (asserted). Usage:

    python benchmarks/bench_kafka.py [--n N] [--out PATH]

Emits one JSON line per path and writes the table to
``benchmarks/RESULTS_kafka_<backend>.json``.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))



def _rows(n: int):
    rng = np.random.default_rng(7)
    t0 = 1_700_000_000_000
    xs = rng.uniform(115.6, 117.5, n)
    ys = rng.uniform(39.7, 41.0, n)
    return [f"o{i % 512},{t0 + i * 5},{xs[i]:.6f},{ys[i]:.6f}"
            for i in range(n)]


def _conf_file(tmp: str, url: str) -> str:
    import yaml

    with open(os.path.join(os.path.dirname(__file__), "..", "conf",
                           "spatialflink-conf.yml")) as f:
        d = yaml.safe_load(f)
    d["kafkaBootStrapServers"] = url
    d["inputStream1"]["format"] = "CSV"
    path = os.path.join(tmp, url.rsplit("/", 1)[-1] + ".yml")
    with open(path, "w") as f:
        yaml.safe_dump(d, f)
    return path


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax

    from spatialflink_tpu import driver as drv
    from spatialflink_tpu.streams import resolve_broker

    backend = jax.default_backend()
    rows = _rows(args.n)
    results = []
    windows_by_path = {}

    with tempfile.TemporaryDirectory() as tmp:
        def run(name: str, extra, disable_chunked: bool = False,
                use_file: bool = False):
            url = f"memory://bench-kafka-{name}"
            cfg = _conf_file(tmp, url)
            argv = ["--config", cfg, "--option", "1"]
            if use_file:
                path = os.path.join(tmp, "rows.csv")
                with open(path, "w") as f:
                    f.write("\n".join(rows) + "\n")
                argv += ["--input1", path, "--format", "CSV"]
            else:
                broker = resolve_broker(url)
                for r in rows:
                    broker.produce("points.geojson", r)
                argv += ["--kafka"]
            argv += extra
            orig = drv._kafka_bulk_decode
            if disable_chunked:
                drv._kafka_bulk_decode = lambda *a, **k: None
            t = time.perf_counter()
            try:
                with contextlib.redirect_stdout(io.StringIO()) as out:
                    rc = drv.main(argv)
            finally:
                drv._kafka_bulk_decode = orig
            dt = time.perf_counter() - t
            assert rc == 0, name
            wins = [l for l in out.getvalue().splitlines()
                    if l.startswith("{")]
            windows_by_path[name] = wins
            row = {"path": name, "records": args.n,
                   "records_per_sec": round(args.n / dt),
                   "wall_s": round(dt, 3), "windows": len(wins),
                   "backend": backend}
            print(json.dumps(row))
            results.append(row)

        run("record", [], disable_chunked=True)
        run("chunked", [])
        run("file", [], use_file=True)

    base = windows_by_path["record"]
    for name, wins in windows_by_path.items():
        assert wins == base, f"{name} diverged from the record path windows"

    out = args.out or os.path.join(os.path.dirname(__file__),
                                   f"RESULTS_kafka_{backend}.json")
    with open(out, "w") as f:
        json.dump({"n": args.n, "backend": backend, "rows": results}, f,
                  indent=1)
    print(f"# wrote {out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
