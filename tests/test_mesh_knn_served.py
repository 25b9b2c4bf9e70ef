"""The point kNN (option 51) on a four-device mesh through ``driver.main``,
the served broker path, against the oracle and against one device.

The stream is a seeded fleet of taxis with a hot cell cluster around the
query point; each object reports throughout the window, so its points fall
in every shard, and planted nearest points sit at the end of each slide,
which is never in shard 0. The mesh program is ``parallel.ops
.knn_mesh_stats``: one compiled program a batch bucket, so full windows
after the first lower nothing.
"""

import numpy as np
import pytest
import yaml

import jax.monitoring

from spatialflink_tpu.driver import main
from spatialflink_tpu.parallel import ops as pops
from spatialflink_tpu.streams import reset_memory_brokers, resolve_broker
from spatialflink_tpu.streams.kafka import KafkaWindowSink
from spatialflink_tpu.utils import telemetry
from spatialflink_tpu.utils.metrics import REGISTRY
from tests import oracles

CONF = "conf/spatialflink-conf.yml"
IN1, OUT = "taxis", "output"
QX, QY = 116.5, 40.5
K = 10
T0 = 1_700_000_000_000
RATE_HZ = 150            # event time: 1,500 points a 10 s window
SECONDS = 40
FLEET = 37
WINDOW_MS, SLIDE_MS = 10_000, 5_000
LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@pytest.fixture(autouse=True)
def _fresh_brokers():
    reset_memory_brokers()
    yield
    reset_memory_brokers()


def _stream(seed=11):
    """(ids, ts, x, y): round-robin ids over the fleet, 30% of points in a
    two-cell box around the query point, and one planted near point per
    slide in its last 5% (a later shard of any window holding it)."""
    rng = np.random.default_rng(seed)
    n = RATE_HZ * SECONDS
    ts = T0 + np.arange(n) * 1000 // RATE_HZ
    ids = np.arange(n) % FLEET
    x = rng.uniform(115.5, 117.6, n)
    y = rng.uniform(39.6, 41.1, n)
    hot = rng.uniform(size=n) < 0.3
    x[hot] = QX + rng.uniform(-0.021, 0.021, hot.sum())
    y[hot] = QY + rng.uniform(-0.021, 0.021, hot.sum())
    late = np.flatnonzero((ts - T0) % SLIDE_MS >= SLIDE_MS * 0.95)
    for slide in np.unique((ts[late] - T0) // SLIDE_MS):
        i = late[(ts[late] - T0) // SLIDE_MS == slide][0]
        x[i], y[i] = QX + 1e-4 * (slide + 1), QY
    return ids, ts, np.round(x, 7), np.round(y, 7)


def _conf(tmp_path, name):
    with open(CONF) as f:
        d = yaml.safe_load(f)
    d["kafkaBootStrapServers"] = f"memory://{name}"
    d["inputStream1"].update(topicName=IN1, format="CSV", dateFormat=None)
    d["query"].update(option=51, k=K, radius=0.5, queryPoints=[[QX, QY]])
    p = tmp_path / f"{name}.yml"
    p.write_text(yaml.safe_dump(d))
    return str(p), f"memory://{name}"


def _serve(tmp_path, name, devices, stream, on_emit=None):
    """Produce ``stream`` to the broker, run ``driver.main`` on it and
    return {window start: [(id, dist), ...]} from the output topic."""
    cfg, url = _conf(tmp_path, name)
    broker = resolve_broker(url)
    ids, ts, x, y = stream
    for i, t, xx, yy in zip(ids, ts, x, y):
        broker.produce(IN1, f"t{i},{t},{xx:.7f},{yy:.7f}")
    argv = ["--config", cfg, "--kafka", "--option", "51",
            "--output-format", "CSV"]
    if devices > 1:
        argv += ["--devices", str(devices)]
    emit = KafkaWindowSink.emit
    if on_emit is not None:
        def hooked(self, result):
            on_emit(result)
            return emit(self, result)
        KafkaWindowSink.emit = hooked
    try:
        assert main(argv) == 0
    finally:
        KafkaWindowSink.emit = emit
    out: dict = {}
    for r in broker.fetch(OUT, 0, broker.end_offset(OUT)):
        if isinstance(r.value, tuple):
            start = int(r.key.rsplit(":", 3)[1])
            out.setdefault(start, []).append(r.value)
    return out


def _oracle(stream):
    """The oracle's kNN of every full window: ([ids], [dists])."""
    ids, ts, x, y = stream
    out = {}
    for start in range(T0, T0 + SECONDS * 1000 - WINDOW_MS + 1, SLIDE_MS):
        m = (ts >= start) & (ts < start + WINDOW_MS)
        out[start] = oracles.knn(QX, QY, x[m], y[m],
                                 [f"t{i}" for i in ids[m]], K)
    return out


def _matches(got, want) -> bool:
    for start, (w_ids, w_d) in want.items():
        rows = got.get(start, [])
        if [o for o, _d in rows] != w_ids or not np.allclose(
                [d for _o, d in rows], w_d, rtol=0, atol=1e-5):
            return False
    return True


@pytest.fixture(scope="module")
def stream():
    return _stream()


def test_mesh_knn_equals_oracle_and_one_device(tmp_path, stream):
    degraded = REGISTRY.counter("mesh-degradations").count
    mesh = _serve(tmp_path, "mesh4", 4, stream)
    one = _serve(tmp_path, "mesh1", 1, stream)
    want = _oracle(stream)
    assert len(want) == 7
    assert _matches(mesh, want)
    # the planted near points (one a slide, never in shard 0) lead
    for start, (w_ids, _d) in want.items():
        assert mesh[start][0][0] == w_ids[0]
        assert mesh[start][0][1] < 1e-3
    assert mesh == one  # bit for bit
    assert REGISTRY.counter("mesh-degradations").count == degraded


def test_mesh_knn_full_windows_lower_nothing(tmp_path, stream):
    """After the first full window, the next three windows' dispatches
    lower no program: each is a hit in the compiled mesh program's
    cache."""
    lowered: list = []
    at_emit: list = []

    def listen(name, _secs, fun_name="?", **_kw):
        if name == LOWERED:
            lowered.append(fun_name)

    def on_emit(result):
        if result.window_start >= T0:
            at_emit.append(len(lowered))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        got = _serve(tmp_path, "lower", 4, stream, on_emit)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert _matches(got, _oracle(stream))
    assert len(at_emit) >= 5
    assert at_emit[4] == at_emit[1], lowered[at_emit[1]:at_emit[4]]


def test_mesh_knn_merge_left_out_is_caught(tmp_path, stream, monkeypatch):
    """A planted fault: the all-gather merge left out, so each shard's own
    partial is the answer and shard 0's is returned. The same comparison
    with the oracle fails."""
    monkeypatch.setattr(pops, "_gather_topk",
                        lambda partial, axis_name, k: partial)
    pops.knn_mesh_stats.clear_cache()
    try:
        got = _serve(tmp_path, "nomerge", 4, stream)
    finally:
        monkeypatch.undo()
        pops.knn_mesh_stats.clear_cache()
    assert got and not _matches(got, _oracle(stream))


def test_mesh_knn_place_spans(tmp_path, stream):
    with telemetry.telemetry_session(None) as tel:
        _serve(tmp_path, "spans", 4, stream)
    place = tel.spans.get("knn.place")
    assert place is not None and place.count >= 7
    assert tel.spans["knn.dispatch"].total_s >= place.total_s
