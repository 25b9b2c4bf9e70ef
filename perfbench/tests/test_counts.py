"""The kernels' operation and byte counts, and the roofline arithmetic the
readers build on them."""

import importlib.util
import json
import os
from types import SimpleNamespace as NS

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(kind, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("points", [1, 1_000_000, 250_000])
def test_knn_counts(points):
    flops, nbytes = load("counts", "knn").count(points=points)
    assert flops == 6 * points
    assert nbytes == 17 * points


def test_join_counts():
    flops, nbytes = load("counts", "join").count(points_a=1_000_000,
                                                 points_b=1024)
    assert flops == 6 * 1_000_000 * 1024
    assert nbytes == 13 * (1_000_000 + 1024)


def test_peaks_table_has_the_v5e():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9


class FakeTrace:
    def __init__(self, programs):
        self.programs = programs

    def program_s(self, match):
        hits = [v for k, v in self.programs.items() if match in k]
        return (sum(s for s, _n in hits), sum(n for _s, n in hits))


def ctx(trace, peak=True, devices=1):
    notes = {}
    c = NS(trace=trace, devices=devices, window_points=1_000_000,
           side_points=1024, notes=notes,
           peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
           if peak else None)
    c.count = lambda kernel, **kw: load("counts", kernel).count(**kw)
    c.note = lambda name, **kw: notes.__setitem__(name, kw)
    return c


def test_knn_roofline_is_bytes_bound():
    # two calls of 1M points: 2 x 17 MB at 819 GB/s = 41.5 us in 100 us
    c = ctx(FakeTrace({"jit_knn_point_stats": (100e-6, 2)}))
    v = load("metrics", "knn_roofline").read(c)
    assert v == pytest.approx(100 * 2 * 17e6 / 819e9 / 100e-6)
    assert c.notes["knn_roofline"]["bound"] == "bytes"


def test_knn_roofline_on_a_mesh_counts_shards():
    c = ctx(FakeTrace({"jit_knn_point_stats": (100e-6, 8)}), devices=4)
    v = load("metrics", "knn_roofline").read(c)
    assert v == pytest.approx(100 * 8 * 17 * 250_000 / 819e9 / 100e-6)


def test_join_roofline_is_flops_bound():
    c = ctx(FakeTrace({"jit__join_reduce_impl": (2e-3, 1),
                       "jit_join_mask": (1e-3, 1)}))
    v = load("metrics", "join_roofline").read(c)
    assert v == pytest.approx(100 * 6 * 1_000_000 * 1024 / 197e12 / 3e-3)
    assert c.notes["join_roofline"]["bound"] == "flops"


@pytest.mark.parametrize("name", ["knn_roofline", "join_roofline"])
def test_roofline_is_silent_without_calls_or_peaks(name):
    assert load("metrics", name).read(ctx(FakeTrace({}))) is None
    busy = FakeTrace({"jit_knn_point_stats": (1e-3, 1),
                      "jit__join_reduce_impl": (1e-3, 1)})
    assert load("metrics", name).read(ctx(busy, peak=False)) is None
