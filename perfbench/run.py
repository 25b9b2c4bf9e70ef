"""The benchmark: one cell of BENCHMARK.json, one run.

    python3 perfbench/run.py --workload NAME --seed N --seconds S --trace 0|1

Everything a cell needs is found by name: the cell in BENCHMARK.json, its
configuration (``configs/<config>.json``), its traffic
(``traffic/<traffic>.json``), each metric's reader (``metrics/<name>.py``),
each kernel's operation and byte counts (``counts/<kernel>.py``) and the
chip's peaks (``peaks.json``, by ``device_kind``). The last line of standard
output is the run's JSON result; the last lines of standard error are the
numbers that decided ``correct``, each beside its limit.

It needs a TPU with as many chips as the cell asks for, and exits 2 without
a result otherwise. ``--small`` with ``JAX_PLATFORMS=cpu`` is the CPU
rehearsal: the same run at a small stream rate. ``--control`` also prints
the numbers of the control (the reference in bfloat16) on the same inputs.

A metric's reader is ``metrics/<name>.py``, or, where that file is missing,
``metrics/<base>.py`` for a name ``<base>.<suffix>``: one reader serves a
quantity split by the end-to-end metric it moves.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import stream as gen  # noqa: E402
from reference import compare  # noqa: E402
from reference.oracle import Reference  # noqa: E402

FAMILY = {51: "knn", 101: "join"}


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(HERE, kind, name.rsplit(".", 1)[0] + ".py")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str):
    """-> (benchmark, cell, configuration, traffic)."""
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    conf = _json(os.path.join(ROOT, cfg["file"]))
    traffic = _json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    return bench, cell, conf, traffic


def shrink(conf: dict, traffic: dict) -> None:
    """The CPU rehearsal's scale: 4,000 events/s of event time."""
    conf["stream_rate_hz"] = 4000
    traffic["max_rate_hz"] = 1_000_000


class Context:
    """What the metric readers read."""

    def __init__(self, run, conf, traffic, streams, setup_s, trace, peak,
                 devices):
        self.run, self.conf, self.traffic = run, conf, traffic
        self.streams = streams
        self.setup_s, self.trace, self.peak = setup_s, trace, peak
        self.devices = devices
        self.window = run.window
        self.window_points = conf["stream_rate_hz"] * conf["window_s"]
        side = conf.get("join_side")
        self.side_points = side["points_per_window"] if side else 0
        self.notes: dict = {}

    def markers_in_window(self) -> list:
        t0, t1 = self.window
        return sorted(m for m in self.run.markers
                      if t0 <= m[0] / 1e3 <= t1)

    def count(self, kernel: str, **shape):
        return _load("counts", kernel).count(**shape)

    def note(self, name: str, **kw) -> None:
        self.notes[name] = kw


# ------------------------------------------------------------- answers


def _index(keys_sorted, order, keys) -> np.ndarray:
    pos = np.searchsorted(keys_sorted, keys)
    pos = np.minimum(pos, len(keys_sorted) - 1)
    hit = keys_sorted[pos] == keys
    return np.where(hit, order[pos], -1)


class Lookup:
    """Traces emitted records back to the events they report."""

    def __init__(self, s: gen.Stream, bbox):
        self.bbox = bbox
        k = s.keys(bbox)
        self.order = np.argsort(k, kind="stable")
        self.sorted = k[self.order]

    def __call__(self, ids, xi, yi) -> np.ndarray:
        return _index(self.sorted, self.order,
                      gen.record_keys(ids, xi, yi, self.bbox))


def _point_fields(p) -> tuple:
    """(id number, xi, yi) of an emitted Point."""
    return (int(p.obj_id[1:]), round(float(p.x) * gen.COORD_SCALE),
            round(float(p.y) * gen.COORD_SCALE))


def collect(run, family: str, streams, conf) -> dict:
    """The answers on the output topic to the windows due in the measured
    window, by family."""
    marker = "__window_commit__:"
    due = set(due_windows(run))
    recs = [r for r in run.output()
            if not (isinstance(r.key, str) and r.key.startswith(marker))]
    by_window: dict = {}
    for r in recs:
        if isinstance(r.value, tuple):
            start = int(r.key.rsplit(":", 3)[1])
            if start in due:
                by_window.setdefault(start, []).append(r.value)
    if family == "knn":
        return {s: [(int(o[1:]), float(d)) for o, d in v]
                for s, v in by_window.items()}
    la = Lookup(streams[0], conf["grid_bbox"])
    lb = Lookup(streams[1], conf["grid_bbox"])
    out = {}
    for s, pairs in by_window.items():
        a, b = [], []
        for p, q in pairs:
            if not p.obj_id.startswith(streams[0].prefix):
                p, q = q, p
            a.append(_point_fields(p))
            b.append(_point_fields(q))
        a = np.array(a, np.int64).reshape(-1, 3)
        b = np.array(b, np.int64).reshape(-1, 3)
        out[s] = (la(a[:, 0], a[:, 1], a[:, 2]), lb(b[:, 0], b[:, 1], b[:, 2]))
    return out


def due_windows(run) -> list:
    """Starts of the windows emitted inside the measured window."""
    t0, t1 = run.window
    return sorted(s for t, s, _e in run.markers if t0 <= t / 1e3 <= t1)


def judge(family, ref, conf, q, streams, run, answers):
    """-> the family's numbers for the answers due in the window."""
    win_ms = conf["window_s"] * 1000
    due = due_windows(run)
    if family == "knn":
        return compare.knn_numbers(ref, q, streams[0],
                                   {s: answers.get(s, []) for s in due},
                                   win_ms, conf["slide_s"] * 1000,
                                   conf["fleet_size"])
    empty = (np.empty(0, np.int64), np.empty(0, np.int64))
    return compare.join_numbers(ref, q, streams[0], streams[1],
                                {s: answers.get(s, empty) for s in due},
                                win_ms)


def control_answers(family, ctrl, conf, q, streams, run, answers):
    """The control's answers to the same inputs, in the program's form."""
    win_ms = conf["window_s"] * 1000
    s1 = streams[0]
    out = {}
    for start in due_windows(run):
        lo, hi = np.searchsorted(s1.ts, [start, start + win_ms])
        sl = slice(lo, hi)
        if family == "knn":
            ids, d, _best = ctrl.knn(s1.oid[sl], *compare._xy(s1, sl),
                                     *q["point"], q["radius"], q["k"],
                                     conf["fleet_size"])
            out[start] = list(zip(ids.tolist(), d.tolist()))
        else:
            s2 = streams[1]
            b0, b1 = np.searchsorted(s2.ts, [start, start + win_ms])
            ia, ib, _d = ctrl.join_pairs(*compare._xy(s1, sl),
                                         *compare._xy(s2, slice(b0, b1)),
                                         q["radius"])
            out[start] = (ia + lo, ib + b0)
    return out


# ---------------------------------------------------------------- main


def _fail(msg: str) -> int:
    print(f"run.py: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--small", action="store_true",
                    help="CPU rehearsal scale (needs JAX_PLATFORMS=cpu)")
    ap.add_argument("--control", action="store_true",
                    help="also print the control's numbers (stderr)")
    args = ap.parse_args(argv)

    bench, cell, conf, traffic = load_cell(args.workload)
    if args.small:
        shrink(conf, traffic)
    # the compile cache: a fixed directory inside the checkout, whatever
    # the environment says, so that only a checkout's first run compiles
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, ROOT)
    try:
        import jax

        from spatialflink_tpu import driver
    except ImportError as e:
        return _fail(f"the system under test is missing: {e}")
    cache_dir, cache_err = driver.enable_compilation_cache()
    if cache_err:
        return _fail(f"compile cache: {cache_err}")
    devs = jax.devices()
    platform = devs[0].platform
    rehearsal = (args.small and platform == "cpu"
                 and os.environ.get("JAX_PLATFORMS") == "cpu")
    if platform != "tpu" and not rehearsal:
        return _fail(f"JAX found no TPU (platform {platform!r}); a CPU "
                     "rehearsal needs JAX_PLATFORMS=cpu and --small")
    if len(devs) < cell["chips"]:
        return _fail(f"the cell needs {cell['chips']} chips, JAX sees "
                     f"{len(devs)}")
    kind = devs[0].device_kind
    peaks = _json(os.path.join(HERE, "peaks.json"))
    if platform == "tpu" and kind not in peaks:
        return _fail(f"no peaks for device kind {kind!r} in peaks.json")
    peak = peaks.get(kind)
    print(json.dumps({"device": {"platform": platform, "kind": kind,
                                 "count": len(devs)},
                      "compile_cache": cache_dir}), file=sys.stderr)

    import drive
    from devtrace import Tracer

    t_jax = time.time()
    family = FAMILY[traffic["query_option"]]
    devices = int(conf.get("devices", 1))
    argv_d = ["--option", str(traffic["query_option"]), "--kafka",
              "--output-format", "CSV"]
    if devices > 1:
        argv_d += ["--devices", str(devices)]
    if traffic["arrivals"] != "drain":
        return _fail(f"unknown arrivals {traffic['arrivals']!r}")
    with tempfile.TemporaryDirectory(prefix="perfbench-trace-") as tdir:
        tracer = Tracer(tdir) if args.trace else None
        streams = gen.drain_streams(conf, traffic, args.seconds, args.seed)
        run = drive.Run(conf, traffic, streams, args.seconds,
                        drive.full_windows(traffic["warmup_windows"]), tracer)
        gen.produce_backlog(run.broker, streams)
        t_made = time.time()
        with _telemetry(args.trace):
            run.drive(argv_d)
        setup_s = run.window[0] - T_START
        trace = tracer.reduce() if tracer is not None else None

    peak_bytes = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak_bytes = max(peak_bytes, int(stats.get("peak_bytes_in_use", 0)))
    run.new_markers()
    answers = collect(run, family, streams, conf)
    run.answers = answers
    ctx = Context(run, conf, traffic, streams, setup_s, trace, peak, devices)
    metrics = {}
    names = bench["per_layer"] if args.trace else bench["end_to_end"]
    for m in names:
        if not _applies(m, cell["name"]):
            continue
        v = _load("metrics", m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # the reference runs after the window, the device read and the program
    q = {"point": conf["query_point"], "radius": conf["radius"],
         "k": conf["k"]}
    t_ref = time.time()
    numbers = judge(family, Reference(conf), conf, q, streams, run, answers)
    t_ref = time.time() - t_ref
    correct, shown = compare.verdict(family, numbers)
    attempted = len(ctx.markers_in_window())
    device = {"platform": platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    if trace is not None:
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
    aux = {"setup_parts_s": {"start_and_jax": t_jax - T_START,
                             "streams": t_made - t_jax,
                             "warmup": run.window[0] - t_made},
           "lowered_in_window": run.lowered_in_window(),
           "backlog_at_open": run.backlog_at_open,
           "backlog_at_close": run.backlog_at_close,
           "window_wall_s": run.window[1] - run.window[0],
           "closed_by": run.closed_by,
           "answers_checked": attempted, "reference_s": t_ref,
           "notes": ctx.notes}
    print(json.dumps(aux), file=sys.stderr)
    if args.control:
        ctrl = control_answers(family, Reference(conf, "bf16"), conf, q,
                               streams, run, answers)
        cn = judge(family, Reference(conf), conf, q, streams, run, ctrl)
        c_ok, c_shown = compare.verdict(family, cn)
        print(json.dumps({"control_correct": c_ok, "control": c_shown}),
              file=sys.stderr)
    result = {"correct": correct, "attempted": attempted,
              "failed": 0 if correct else attempted,
              "metrics": metrics, "device": device}
    if trace is not None:
        result["breakdown"] = {"device_ops": trace.top_ops(),
                               "idle_gaps": trace.idle_gaps()}
    result["compared"] = shown
    for k, v in shown.items():
        print(f"compared {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(f"correct {correct}", file=sys.stderr)
    print(json.dumps(result))
    return 0


def _telemetry(on: int):
    """The program's own spans, recorded into the profiler's trace, only
    in the traced run."""
    import contextlib

    if not on:
        return contextlib.nullcontext()
    from spatialflink_tpu.utils.telemetry import telemetry_session

    return telemetry_session(None)


if __name__ == "__main__":
    raise SystemExit(main())
