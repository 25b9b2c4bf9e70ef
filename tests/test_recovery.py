"""Coordinated-checkpoint crash/recovery suite (runtime/checkpoint.py,
driver --checkpoint-dir/--resume).

Headline invariant: for windowed range/kNN/join/tStats broker pipelines —
plain and pane-incremental, clean transport and under --chaos — a run
KILLED at an arbitrary point (including mid-checkpoint-write) and resumed
from the latest valid checkpoint produces a final marker-keyed window table
IDENTICAL to an uninterrupted run, with zero duplicate marker emissions and
bounded replay (only records past the checkpointed source position are
re-read). Plus: corrupt-manifest fallback, job-fingerprint refusal (new and
legacy checkpoint paths), and the unsupported-case gates.

Fast deterministic cases run in the tier-1 set (marker ``recovery``); the
randomized kill-point fuzz is additionally marked ``slow``.
"""

import json
import os
import random

import pytest
import yaml

from spatialflink_tpu.driver import main
from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.runtime.checkpoint import (CheckpointCoordinator,
                                                 CheckpointMismatch)
from spatialflink_tpu.streams import (
    SyntheticPointSource,
    reset_memory_brokers,
    resolve_broker,
    serialize_spatial,
)
from spatialflink_tpu.streams.kafka import KafkaWindowSink

pytestmark = pytest.mark.recovery

CONF = "conf/spatialflink-conf.yml"
IN1, IN2, OUT = "points.geojson", "queries.geojson", "output"
ALL_FAULTS = ("seed={seed},produce_fail=0.2,ack_lost=0.2,fetch_fail=0.2,"
              "duplicate=0.3,reorder=0.5,torn=0.15,latency=0.1,latency_ms=1")
RETRY = "attempts=12,base_ms=1,max_ms=20,breaker_threshold=4,cooldown_ms=5"


@pytest.fixture(autouse=True)
def _fresh_brokers():
    reset_memory_brokers()
    yield
    reset_memory_brokers()


def _conf(tmp_path, name, fname="conf.yml", **query_overrides):
    with open(CONF) as f:
        d = yaml.safe_load(f)
    d["kafkaBootStrapServers"] = f"memory://{name}"
    d["query"].update(query_overrides)
    p = tmp_path / fname
    p.write_text(yaml.safe_dump(d))
    return str(p), f"memory://{name}"


def _lines(n_traj=6, steps=40, seed=3):
    grid = UniformGrid(115.5, 117.6, 39.6, 41.1, num_grid_partitions=100)
    pts = list(SyntheticPointSource(grid, num_trajectories=n_traj,
                                    steps=steps, seed=seed))
    return [serialize_spatial(p, "GeoJSON") for p in pts]


def _window_table(broker, topic=OUT):
    """{window key: [marker values]} — duplicate-marker detection included
    (the zero-duplicate-sink-emissions criterion is 'every key marked
    exactly once')."""
    out = {}
    for r in broker.fetch(topic, 0, 1_000_000):
        if isinstance(r.key, str) and r.key.startswith(KafkaWindowSink.MARKER):
            out.setdefault(r.key[len(KafkaWindowSink.MARKER):],
                           []).append(int(r.value))
    return out


def _produce(tmp_path, name, lines, lines2=None, **overrides):
    cfg, url = _conf(tmp_path, name, f"{name}.yml", **overrides)
    broker = resolve_broker(url)
    for ln in lines:
        broker.produce(IN1, ln)
    for ln in lines2 or ():
        broker.produce(IN2, ln)
    return cfg, broker


def _oracle(tmp_path, option, lines, name, lines2=None, extra=()):
    cfg, broker = _produce(tmp_path, name, lines, lines2)
    assert main(["--config", cfg, "--kafka", "--option", str(option)]
                + list(extra)) == 0
    table = _window_table(broker)
    assert table, "oracle run produced no windows"
    assert all(len(v) == 1 for v in table.values())
    return {k: v[0] for k, v in table.items()}


def _crash_at_fresh_window(monkeypatch, nth):
    """Arm KafkaWindowSink.emit to raise on the nth NOT-yet-delivered
    window (re-deliveries the sink suppresses don't count)."""
    orig = KafkaWindowSink.emit
    state = {"fresh": 0}

    def boom(self, result):
        if self.window_key(result) not in self.delivered:
            state["fresh"] += 1
            if state["fresh"] == nth:
                raise RuntimeError("injected crash")
        orig(self, result)

    monkeypatch.setattr(KafkaWindowSink, "emit", boom)
    return state


# ------------------------------------------------ fast deterministic smoke


@pytest.mark.parametrize("opt,needs2,extra", [
    (1, False, []),            # windowed range
    (101, True, []),           # windowed join (two streams, two assemblers)
    (206, False, []),          # windowed tStats
    (51, False, ["--panes"]),  # pane-incremental kNN (PaneBuffer + cache)
])
def test_crash_resume_window_table_identical(tmp_path, monkeypatch, opt,
                                             needs2, extra):
    """Kill at the 4th fresh window, resume from the latest checkpoint:
    final window table identical to the uninterrupted run, every window
    marked exactly once, and the replay bounded to records past the
    checkpointed source position."""
    # the batched decode hands the tiny test topic over in one chunk at the
    # default size, which legitimately checkpoints position == end (every
    # record snapshotted in open buffers); pin a small chunk so the test
    # still proves the BOUNDED-replay property a real-sized topic exhibits
    monkeypatch.setenv("SPATIALFLINK_DECODE_CHUNK", "32")
    lines, lines2 = _lines(), (_lines(seed=8) if needs2 else None)
    expected = _oracle(tmp_path, opt, lines, f"oracle-{opt}{len(extra)}",
                       lines2, extra)

    cfg, broker = _produce(tmp_path, f"crash-{opt}{len(extra)}", lines,
                           lines2)
    cpd = str(tmp_path / f"cp-{opt}{len(extra)}")
    argv = ["--config", cfg, "--kafka", "--option", str(opt),
            "--checkpoint-dir", cpd, "--checkpoint-every", "2"] + extra
    with monkeypatch.context() as m:
        _crash_at_fresh_window(m, 4)
        with pytest.raises(RuntimeError, match="injected crash"):
            main(argv)
    manifests = [f for f in os.listdir(cpd) if f.endswith(".npz")]
    assert manifests, "crash run wrote no checkpoint"

    # bounded replay: the checkpointed position is strictly inside the topic
    coord = CheckpointCoordinator(cpd, job=None)
    assert coord.load()
    pos = coord.position(f"kafka:{IN1}")
    assert 0 < pos < len(lines)

    assert main(argv + ["--resume"]) == 0
    table = _window_table(broker)
    dups = {k: v for k, v in table.items() if len(v) > 1}
    assert not dups, f"duplicate sink emissions after resume: {dups}"
    assert {k: v[0] for k, v in table.items()} == expected
    assert broker.committed(IN1, "spatialflink") == len(lines)
    if needs2:
        assert broker.committed(IN2, "spatialflink") == len(lines)


def test_mid_checkpoint_write_crash_falls_back_and_recovers(tmp_path,
                                                            monkeypatch):
    """Kill DURING the second checkpoint's rename (a torn write leaves only
    the .tmp): resume must fall back to checkpoint 1 and still converge to
    the oracle table with no duplicate markers."""
    lines = _lines()
    expected = _oracle(tmp_path, 1, lines, "midwrite-oracle")
    cfg, broker = _produce(tmp_path, "midwrite", lines)
    cpd = str(tmp_path / "cp-midwrite")
    argv = ["--config", cfg, "--kafka", "--option", "1",
            "--checkpoint-dir", cpd, "--checkpoint-every", "2"]

    real_replace = os.replace

    def torn_replace(src, dst, *a, **kw):
        if "ckpt-00000002.npz" in str(dst):
            raise OSError("simulated crash mid-checkpoint-write")
        return real_replace(src, dst, *a, **kw)

    with monkeypatch.context() as m:
        m.setattr(os, "replace", torn_replace)
        with pytest.raises(OSError, match="mid-checkpoint-write"):
            main(argv)
    names = sorted(os.listdir(cpd))
    assert "ckpt-00000001.npz" in names
    assert "ckpt-00000002.npz" not in names  # the torn write never landed

    assert main(argv + ["--resume"]) == 0
    table = _window_table(broker)
    assert all(len(v) == 1 for v in table.values())
    assert {k: v[0] for k, v in table.items()} == expected


def test_corrupt_newest_manifest_falls_back_to_previous(tmp_path,
                                                        monkeypatch,
                                                        capsys):
    """Truncate the newest manifest after a crash: load() must warn, fall
    back to the previous retained one, and the resumed run still matches
    the oracle."""
    lines = _lines(steps=60)
    expected = _oracle(tmp_path, 1, lines, "corrupt-oracle")
    cfg, broker = _produce(tmp_path, "corrupt", lines)
    cpd = str(tmp_path / "cp-corrupt")
    argv = ["--config", cfg, "--kafka", "--option", "1",
            "--checkpoint-dir", cpd, "--checkpoint-every", "2"]
    with monkeypatch.context() as m:
        _crash_at_fresh_window(m, 8)
        with pytest.raises(RuntimeError, match="injected crash"):
            main(argv)
    manifests = sorted(f for f in os.listdir(cpd) if f.endswith(".npz"))
    assert len(manifests) >= 2, "need two checkpoints to test fallback"
    newest = os.path.join(cpd, manifests[-1])
    data = open(newest, "rb").read()
    open(newest, "wb").write(data[: len(data) // 3])

    assert main(argv + ["--resume"]) == 0
    err = capsys.readouterr().err
    assert "falling back to the previous retained checkpoint" in err
    table = _window_table(broker)
    assert all(len(v) == 1 for v in table.values())
    assert {k: v[0] for k, v in table.items()} == expected


def test_retention_prunes_old_manifests(tmp_path):
    lines = _lines(steps=80)
    cfg, _broker = _produce(tmp_path, "retain", lines)
    cpd = str(tmp_path / "cp-retain")
    assert main(["--config", cfg, "--kafka", "--option", "1",
                 "--checkpoint-dir", cpd, "--checkpoint-every", "1",
                 "--checkpoint-retain", "2"]) == 0
    manifests = [f for f in os.listdir(cpd) if f.endswith(".npz")]
    assert len(manifests) == 2, manifests


# ------------------------------------------------ fingerprint refusal


def test_resume_with_different_config_refused(tmp_path):
    """A checkpoint dir written by one query config must refuse a resume
    under a different one (the silent-footgun satellite, new path)."""
    lines = _lines()
    cfg, _broker = _produce(tmp_path, "fp-a", lines)
    cpd = str(tmp_path / "cp-fp")
    assert main(["--config", cfg, "--kafka", "--option", "1",
                 "--checkpoint-dir", cpd, "--checkpoint-every", "2"]) == 0
    assert [f for f in os.listdir(cpd) if f.endswith(".npz")]

    cfg2, _b2 = _produce(tmp_path, "fp-b", lines, radius=9.5)
    with pytest.raises(SystemExit):
        main(["--config", cfg2, "--kafka", "--option", "1",
              "--checkpoint-dir", cpd, "--resume"])
    # the coordinator-level error is also directly visible
    coord = CheckpointCoordinator(cpd, job="different-job")
    with pytest.raises(CheckpointMismatch, match="job fingerprint"):
        coord.load()


def test_resume_with_different_execution_layout_refused(tmp_path):
    """--panes is excluded from the job fingerprint (sink dedup must span
    both modes) but changes the checkpoint's component layout — resuming a
    panes-on checkpoint with panes off must refuse, not lose the pane
    buffers."""
    lines = _lines()
    cfg, _broker = _produce(tmp_path, "layout", lines)
    cpd = str(tmp_path / "cp-layout")
    assert main(["--config", cfg, "--kafka", "--option", "1", "--panes",
                 "--checkpoint-dir", cpd, "--checkpoint-every", "2"]) == 0
    with pytest.raises(SystemExit):
        main(["--config", cfg, "--kafka", "--option", "1",
              "--checkpoint-dir", cpd, "--resume"])


def test_legacy_checkpoint_job_mismatch_refused(tmp_path):
    """The single-file --checkpoint (tStats realtime) now stores the job
    fingerprint and refuses a resume under a different config instead of
    silently double-counting."""
    lines = _lines()
    path1 = str(tmp_path / "in1.geojson")
    open(path1, "w").write("\n".join(lines))
    cfg, _url = _conf(tmp_path, "legacy-a", "legacy-a.yml")
    ckpt = str(tmp_path / "tstats.npz")
    assert main(["--config", cfg, "--option", "205", "--input1", path1,
                 "--checkpoint", ckpt, "--checkpoint-every", "4"]) == 0
    assert os.path.exists(ckpt)

    cfg2, _url = _conf(tmp_path, "legacy-b", "legacy-b.yml",
                       trajIDs=["traj-0", "traj-1"])
    with pytest.raises(SystemExit):
        main(["--config", cfg2, "--option", "205", "--input1", path1,
              "--checkpoint", ckpt])


# ------------------------------------------------ realtime + file replay


def test_realtime_tstats_file_resume_matches_uninterrupted(tmp_path,
                                                           monkeypatch):
    """Realtime tStats over FILE replay with --checkpoint-dir: crash after
    a fixed number of emitted results, resume, and the final cumulative
    stats written to --output equal the uninterrupted run's."""
    lines = _lines(n_traj=4, steps=200)
    path1 = str(tmp_path / "in1.geojson")
    open(path1, "w").write("\n".join(lines))

    cfg, _url = _conf(tmp_path, "rt-oracle", "rt-oracle.yml")
    out_oracle = str(tmp_path / "oracle.out")
    assert main(["--config", cfg, "--option", "205", "--input1", path1,
                 "--output", out_oracle]) == 0
    oracle_tail = open(out_oracle).read().splitlines()[-4:]
    assert oracle_tail

    cpd = str(tmp_path / "cp-rt")
    out_a = str(tmp_path / "crashed.out")
    from spatialflink_tpu import driver as drv

    orig_emit = drv._emit
    state = {"n": 0}

    def boom(result, sink):
        state["n"] += 1
        if state["n"] == 2:
            raise RuntimeError("injected realtime crash")
        orig_emit(result, sink)

    with monkeypatch.context() as m:
        m.setattr(drv, "_emit", boom)
        with pytest.raises(RuntimeError, match="realtime crash"):
            main(["--config", cfg, "--option", "205", "--input1", path1,
                  "--output", out_a, "--checkpoint-dir", cpd,
                  "--checkpoint-every", "1"])
    assert [f for f in os.listdir(cpd) if f.endswith(".npz")]

    out_b = str(tmp_path / "resumed.out")
    assert main(["--config", cfg, "--option", "205", "--input1", path1,
                 "--output", out_b, "--checkpoint-dir", cpd,
                 "--resume"]) == 0
    resumed_tail = open(out_b).read().splitlines()[-4:]
    assert resumed_tail == oracle_tail, \
        "resumed cumulative stats diverged from the uninterrupted run"


def test_file_path_windowed_resume_exactly_once(tmp_path, monkeypatch,
                                                capsys):
    """Windowed range over FILE replay (stdout sink, no Kafka markers):
    the emitted-window journal must make crashed+resumed output exactly
    equal the uninterrupted run's — no window printed twice, none lost."""
    lines = _lines()
    path1 = str(tmp_path / "in1.geojson")
    open(path1, "w").write("\n".join(lines))
    cfg, _url = _conf(tmp_path, "fj")

    assert main(["--config", cfg, "--option", "1", "--input1", path1]) == 0
    oracle = capsys.readouterr().out.splitlines()
    assert len(oracle) == len(set(oracle)) and oracle

    cpd = str(tmp_path / "cp-fj")
    argv = ["--config", cfg, "--option", "1", "--input1", path1,
            "--checkpoint-dir", cpd, "--checkpoint-every", "2"]
    from spatialflink_tpu import driver as drv

    orig_emit = drv._emit
    state = {"n": 0}

    def boom(result, sink):
        state["n"] += 1
        if state["n"] == 5:
            raise RuntimeError("injected file-path crash")
        orig_emit(result, sink)

    with monkeypatch.context() as m:
        m.setattr(drv, "_emit", boom)
        with pytest.raises(RuntimeError, match="file-path crash"):
            main(argv)
    crashed = capsys.readouterr().out.splitlines()

    assert main(argv + ["--resume"]) == 0
    resumed = capsys.readouterr().out.splitlines()
    combined = crashed + resumed
    assert sorted(combined) == sorted(oracle), \
        "file-path resume lost or duplicated windows"


def test_resume_against_different_source_refused(tmp_path):
    """A checkpoint's positions index into one specific source; resuming
    with a different --input1 must refuse rather than seek into records
    that were never processed."""
    lines = _lines()
    path1 = str(tmp_path / "a.geojson")
    open(path1, "w").write("\n".join(lines))
    path_b = str(tmp_path / "b.geojson")
    open(path_b, "w").write("\n".join(lines))
    cfg, _url = _conf(tmp_path, "src")
    cpd = str(tmp_path / "cp-src")
    assert main(["--config", cfg, "--option", "1", "--input1", path1,
                 "--checkpoint-dir", cpd, "--checkpoint-every", "2"]) == 0
    with pytest.raises(SystemExit):
        main(["--config", cfg, "--option", "1", "--input1", path_b,
              "--checkpoint-dir", cpd, "--resume"])


def test_sigterm_graceful_drain_writes_final_checkpoint_and_resumes(
        tmp_path, monkeypatch, capsys):
    """Graceful shutdown on the single-process driver: a stop request
    mid-stream (what the SIGTERM handler raises) drains the records
    already decoded, writes a FINAL checkpoint past the regular cadence,
    and exits 0 — then ``--resume`` completes the stream and
    stopped+resumed output is exactly the uninterrupted run's."""
    lines = _lines(n_traj=8, steps=60)
    path1 = str(tmp_path / "in1.geojson")
    open(path1, "w").write("\n".join(lines))
    cfg, _url = _conf(tmp_path, "sig")
    # small decode chunks so windows emit interleaved with decoding —
    # otherwise the whole file buffers before the first _emit and the
    # stop request can never land mid-stream
    monkeypatch.setenv("SPATIALFLINK_DECODE_CHUNK", "16")

    assert main(["--config", cfg, "--option", "1", "--input1", path1]) == 0
    oracle = capsys.readouterr().out.splitlines()
    assert len(oracle) > 3

    cpd = str(tmp_path / "cp-sig")
    argv = ["--config", cfg, "--option", "1", "--input1", path1,
            "--checkpoint-dir", cpd, "--checkpoint-every", "2"]
    from spatialflink_tpu import driver as drv
    from spatialflink_tpu.utils import metrics as _metrics

    orig_emit = drv._emit
    state = {"n": 0}

    def stop_after_two(result, sink):
        orig_emit(result, sink)
        state["n"] += 1
        if state["n"] == 2:
            _metrics.request_shutdown()

    try:
        with monkeypatch.context() as m:
            m.setattr(drv, "_emit", stop_after_two)
            assert main(argv) == 0, "graceful stop must NOT be a crash exit"
        cap = capsys.readouterr()
        stopped = cap.out.splitlines()
        assert "graceful shutdown: final checkpoint" in cap.err
        assert 0 < len(stopped) < len(oracle), \
            "the stop request never landed mid-stream"

        assert main(argv + ["--resume"]) == 0
        resumed = capsys.readouterr().out.splitlines()
        assert sorted(stopped + resumed) == sorted(oracle), \
            "SIGTERM drain + resume lost or duplicated windows"
    finally:
        _metrics.clear_shutdown()


# ------------------------------------------------ gates


def test_checkpoint_dir_gates(tmp_path, capsys):
    lines = _lines(steps=6)
    path1 = str(tmp_path / "in1.geojson")
    open(path1, "w").write("\n".join(lines))
    cfg, _url = _conf(tmp_path, "gates")

    with pytest.raises(SystemExit):  # --resume without --checkpoint-dir
        main(["--config", cfg, "--option", "1", "--input1", path1,
              "--resume"])
    with pytest.raises(SystemExit):  # legacy flag does not compose
        main(["--config", cfg, "--option", "205", "--input1", path1,
              "--checkpoint", str(tmp_path / "x.npz"),
              "--checkpoint-dir", str(tmp_path / "cp2")])

    # unsupported case (realtime tFilter): warn + run WITHOUT the
    # coordinator (no manifests written)
    cpd = str(tmp_path / "cp3")
    assert main(["--config", cfg, "--option", "201", "--input1", path1,
                 "--checkpoint-dir", cpd]) == 0
    err = capsys.readouterr().err
    assert "--checkpoint-dir ignored" in err
    assert not os.path.exists(os.path.join(cpd, "ckpt-00000001.npz"))


def test_checkpoint_telemetry_surfaces(tmp_path):
    """checkpoint write duration/size histograms land in the telemetry
    snapshot of a checkpointed run."""
    lines = _lines()
    cfg, _broker = _produce(tmp_path, "tel", lines)
    cpd = str(tmp_path / "cp-tel")
    tdir = str(tmp_path / "tel-out")
    assert main(["--config", cfg, "--kafka", "--option", "1",
                 "--checkpoint-dir", cpd, "--checkpoint-every", "2",
                 "--telemetry-dir", tdir]) == 0
    snaps = [json.loads(ln) for ln in
             open(os.path.join(tdir, "telemetry.jsonl"))]
    final = snaps[-1]
    hists = final.get("histograms", {})
    assert "checkpoint-write-ms" in hists
    assert "checkpoint-size-bytes" in hists
    assert hists["checkpoint-write-ms"]["count"] >= 1
    assert "checkpoint.age-s" in final.get("gauges", {})


# ------------------------------------------------ randomized kill-point fuzz


@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("opt,needs2,extra", [
    (1, False, []),
    (51, False, []),
    (101, True, []),
    (206, False, []),
    (1, False, ["--panes"]),
])
def test_kill_point_fuzz_under_chaos(tmp_path, monkeypatch, seed, opt,
                                     needs2, extra):
    """Randomized kill point under full transport chaos, resume still under
    chaos (different fault seed): window-table identity, zero duplicate
    markers, full input committed."""
    rng = random.Random(1000 * opt + seed + len(extra))
    lines, lines2 = _lines(), (_lines(seed=8) if needs2 else None)
    tag = f"{opt}-{seed}-{len(extra)}"
    expected = _oracle(tmp_path, opt, lines, f"fz-oracle-{tag}", lines2,
                       extra)

    cfg, broker = _produce(tmp_path, f"fz-{tag}", lines, lines2)
    cpd = str(tmp_path / f"cp-fz-{tag}")
    argv = ["--config", cfg, "--kafka", "--option", str(opt),
            "--checkpoint-dir", cpd, "--checkpoint-every",
            str(rng.choice([1, 2, 3])), "--retry", RETRY, "--dlq"] + extra
    kill_at = rng.randint(1, len(expected))
    with monkeypatch.context() as m:
        _crash_at_fresh_window(m, kill_at)
        try:
            main(argv + ["--chaos", ALL_FAULTS.format(seed=100 + seed)])
            crashed = False
        except RuntimeError:
            crashed = True
    assert crashed or kill_at >= len(expected)

    assert main(argv + ["--resume",
                        "--chaos", ALL_FAULTS.format(seed=200 + seed)]) == 0
    table = _window_table(broker)
    dups = {k: v for k, v in table.items() if len(v) > 1}
    assert not dups, f"duplicate sink emissions: {dups}"
    assert {k: v[0] for k, v in table.items()} == expected
    assert broker.committed(IN1, "spatialflink") == len(lines)
    assert broker.end_offset(OUT + "-dlq") == 0
