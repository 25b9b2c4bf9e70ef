"""The trace reduction (devtrace.py) on small traces: a hand-built one with
known answers, and one recorded from a real profiler session."""

import glob
import os
from types import SimpleNamespace as NS

import pytest

from devtrace import CLOSE, OPEN, Trace, Tracer, clip, union

MS = 1_000_000  # ns


def ev(name, start_ms, dur_ms, **stats):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS,
              stats=list(stats.items()))


def line(name, *events):
    return NS(name=name, events=list(events))


def planes():
    device = NS(name="/device:TPU:0", lines=[
        line("XLA Modules",
             ev("jit_knn_point_stats(7)", 10, 4),
             ev("jit_knn_point_stats(7)", 50, 4),
             ev("jit_other(3)", 70, 2)),
        line("XLA Ops",
             ev("fusion.1", 10, 3), ev("sort.2", 12, 2),   # overlap
             ev("%fusion.1 = f32[1048576]{0} fusion(f32[1048576]{0} %x)",
                50, 4),
             ev("copy.3", 70, 2),
             ev("fusion.9", 95, 20)),                      # runs past close
    ])
    host = NS(name="/host:CPU", lines=[
        line("python", ev(OPEN, 0, 0), ev(CLOSE, 100, 0),
             ev("kafka.fetch", 0, 10), ev("kafka.fetch", 20, 10),
             ev("knn.window", 14, 36), ev("knn.merge", 54, 3),
             ev("$profiler.py:1 start_trace", 0, 1)),
    ])
    return [NS(name="/host:metadata", lines=[]), device, host]


def test_union_and_clip():
    u = union(__import__("numpy").array([[3, 5], [0, 2], [1, 4], [7, 8]],
                                        float))
    assert u.tolist() == [[0, 5], [7, 8]]
    assert clip(u, 1, 7.5).tolist() == [[1, 5], [7, 7.5]]


def test_busy_window_and_idle():
    t = Trace.from_planes(planes())
    assert t.window == pytest.approx((0.0, 0.1))
    # ops union inside [0, 100 ms]: 10-14, 50-54, 70-72, 95-100 = 15 ms
    assert t.busy_s() == pytest.approx(0.015)


def test_program_time_and_calls():
    t = Trace.from_planes(planes())
    sec, calls = t.program_s("knn")
    assert sec == pytest.approx(0.008)
    assert calls == 2
    assert t.program_s("nothing") == (0.0, 0)


def test_host_spans():
    t = Trace.from_planes(planes())
    assert t.span_union_s({"kafka.fetch"}) == pytest.approx(0.020)
    assert t.span_sum_s(".merge") == (pytest.approx(0.003), 1)
    assert not any(n.startswith("$") for _s, _e, n in t.spans)


def test_top_ops_and_gaps():
    t = Trace.from_planes(planes())
    top = dict(t.top_ops())
    assert top["jit_knn_point_stats:fusion.1"] == pytest.approx(0.007)
    gaps = t.idle_gaps()
    # longest gap 14-50 ms lies inside knn.window (14-50), which covers it
    assert gaps[0][1] == pytest.approx(0.036)
    assert [round(g, 3) for _n, g in gaps] == [0.036, 0.023, 0.016, 0.01]
    # 72-95 ms lies under no span; knn.merge (54-57) covers 3 of 54-70
    assert [n for n, _g in gaps] == ["knn.window (100.0%)", "none",
                                     "knn.merge (18.8%)",
                                     "kafka.fetch (100.0%)"]


def test_no_marks_is_an_error():
    p = planes()
    p[2].lines[0].events = p[2].lines[0].events[2:]
    with pytest.raises(RuntimeError):
        Trace.from_planes(p)


def test_recorded_cpu_trace(tmp_path):
    """A real profiler session on the CPU backend: its XLA ops stand in for
    device operations, and the program spans are read back."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sort(x * 2.0))
    x = jnp.arange(50_000, dtype=jnp.float32)
    f(x).block_until_ready()
    tr = Tracer(str(tmp_path / "trace"))
    tr.start()
    for _ in range(3):
        with jax.profiler.TraceAnnotation("kafka.fetch"):
            f(x).block_until_ready()
    tr.stop()
    assert glob.glob(os.path.join(tmp_path, "trace", "**", "*.xplane.pb"),
                     recursive=True)
    t = tr.reduce()
    assert 0 < t.busy_s() <= t.window_s
    sec, calls = t.program_s("jit")
    assert sec > 0 and calls >= 1
    assert t.span_union_s({"kafka.fetch"}) > 0


def test_recorded_tpu_trace():
    """A trace recorded on one v5e chip (my chip run, PR 22): three calls
    of a jitted program, each inside a ``kafka.fetch`` span, between the
    window marks. The device plane's ``XLA Ops`` and ``XLA Modules`` lines
    are read; the ops lie on the host's clock, inside the window."""
    t = Trace.from_file(os.path.join(os.path.dirname(__file__), "data",
                                     "tpu_small.xplane.pb"))
    assert list(t.ops) == ["/device:TPU:0"]
    lo, hi = t.window
    assert all(lo <= s and e <= hi for s, e, _n, _m in t.ops["/device:TPU:0"])
    assert t.window_s == pytest.approx(0.003682, rel=1e-3)
    assert t.busy_s() == pytest.approx(0.0001248, rel=1e-3)
    sec, calls = t.program_s("knn_like")
    assert calls == 3 and sec == pytest.approx(t.busy_s())
    assert t.top_ops(1)[0][0] == "jit_knn_like:fusion.1"
    assert [n for n, _g in t.idle_gaps(3)] == [
        "kafka.fetch (82.1%)", "kafka.fetch (81.0%)", "kafka.fetch (98.8%)"]
