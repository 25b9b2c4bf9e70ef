"""The drain rate, from known append times of the windows' commit
markers."""

import importlib.util
import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

import stream as gen
from run import Context
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def metric(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def make_ctx(run, streams=()):
    conf = {"stream_rate_hz": 100, "window_s": 10}
    return Context(run, conf, {}, list(streams), 1.0, None, None, 1)


def test_drain_rate_counts_events_between_emissions():
    s = gen.Stream("taxis", "t", np.zeros(1), np.zeros(1), np.zeros(1))
    s.ts = gen.T0 + np.arange(200_000) * 10          # 100 events/s
    # windows ending at T0+10 s, +15 s, +20 s emitted at 1.0, 2.0, 3.0 s;
    # one before the window opens does not count
    markers = [(500.0, gen.T0 - 5000, gen.T0 + 5000),
               (1000.0, gen.T0, gen.T0 + 10_000),
               (2000.0, gen.T0 + 5000, gen.T0 + 15_000),
               (3000.0, gen.T0 + 10_000, gen.T0 + 20_000)]
    run = NS(window=(0.9, 3.5), markers=markers)
    c = make_ctx(run, streams=[s])
    # events with ts in [T0+10 s, T0+20 s) = 1,000 over 2 s
    assert metric("events_per_s")(c) == pytest.approx(500.0)
    run.window = (2.5, 3.5)
    assert metric("events_per_s")(make_ctx(run, streams=[s])) is None
