"""Supervised multi-worker fleet suite (runtime/fleet.py +
runtime/fleetsup.py, driver --fleet).

Headline invariant: an N-worker fleet over a leaf-partitioned file replay
— including one forcibly SIGKILLed worker restarted from its checkpoint —
produces a merged global window table BYTE-IDENTICAL to a fault-free
single-worker run, with zero post-warmup recompiles across every
incarnation. Plus: the leaf packing / rebalance policy, the tailing
partition source, outbox dedup + fingerprint cross-check, the per-family
global merge seam, the fleet manifest's durability, worker argv
construction, the /fleet endpoint, and doctor fleet.

Fast deterministic cases run in tier-1; the randomized kill-point fuzz is
additionally marked ``slow``.
"""

import json
import os
import random
import subprocess
import sys
import threading
import time

import pytest
import yaml

from spatialflink_tpu.driver import main
from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.operators.base import merge_window_records
from spatialflink_tpu.runtime import fleet as F
from spatialflink_tpu.runtime.fleetsup import (_strip_flags, active_fleet,
                                               worker_argv)
from spatialflink_tpu.runtime.repartition import (balance_leaves,
                                                  pick_rebalance)
from spatialflink_tpu.streams import SyntheticPointSource, serialize_spatial
from spatialflink_tpu.utils import metrics as _metrics

pytestmark = pytest.mark.fleet

CONF = "conf/spatialflink-conf.yml"


@pytest.fixture(autouse=True)
def _clear_shutdown_flag():
    _metrics.clear_shutdown()
    yield
    _metrics.clear_shutdown()


def _grid():
    return UniformGrid(115.5, 117.6, 39.6, 41.1, num_grid_partitions=100)


def _lines(n_traj=6, steps=40, seed=3):
    pts = list(SyntheticPointSource(_grid(), num_trajectories=n_traj,
                                    steps=steps, seed=seed))
    return [serialize_spatial(p, "GeoJSON") for p in pts]


def _write_input(tmp_path, lines, name="in1.geojson"):
    p = tmp_path / name
    p.write_text("\n".join(lines) + "\n")
    return str(p)


def _fleet_argv(cfg, path1, fleet_dir, n, *extra, option="1"):
    return (["--config", cfg, "--option", option, "--input1", path1,
             "--fleet", str(n), "--fleet-dir", str(fleet_dir),
             "--fleet-heartbeat", "0.25",
             "--fleet-epoch-records", "100"] + list(extra))


def _result(fleet_dir):
    doc = F.read_json(os.path.join(str(fleet_dir), F.RESULT_FILE))
    assert doc is not None, "fleet run left no fleet_result.json"
    return doc


def _merged_table(fleet_dir):
    out = []
    with open(os.path.join(str(fleet_dir), F.MERGED_FILE)) as f:
        for line in f:
            if line.strip():
                out.append(json.loads(line))
    return out


# ---------------------------------------------------------------- policy


def test_balance_leaves_lpt_packing():
    occ = {1: 100, 2: 90, 3: 10, 4: 10, 5: 10}
    a = balance_leaves(occ, 2)
    # the two hot leaves must land on different workers (greedy LPT)
    assert a[1] != a[2]
    loads = {0: 0, 1: 0}
    for leaf, w in a.items():
        loads[w] += occ[leaf]
    assert abs(loads[0] - loads[1]) <= 30


def test_balance_leaves_single_worker_and_empty():
    assert balance_leaves({}, 3) == {}
    a = balance_leaves({7: 5, 9: 1}, 1)
    assert set(a.values()) == {0}


def test_pick_rebalance_hysteresis():
    # <25% spread: leave the fleet alone
    assert pick_rebalance({0: 100.0, 1: 80.0}) is None
    assert pick_rebalance({0: 0.0, 1: 0.0}) is None
    assert pick_rebalance({0: 5.0}) is None
    donor, receiver = pick_rebalance({0: 100.0, 1: 10.0, 2: 50.0})
    assert (donor, receiver) == (0, 1)


# ------------------------------------------------------- tailing source


def test_tailing_source_follows_until_done_marker(tmp_path):
    part = str(tmp_path / "p.ndjson")
    done = str(tmp_path / "p.done")
    src = F.TailingReplaySource(part, done, poll_s=0.01)
    got = []

    def consume():
        got.extend(src)

    t = threading.Thread(target=consume)
    t.start()
    with open(part, "w") as f:
        f.write("a\nb\n")
        f.flush()
        time.sleep(0.1)
        f.write("c")  # torn line: must be held back
        f.flush()
        time.sleep(0.1)
        assert got == ["a", "b"]
        f.write("\nd\n")
        f.flush()
    F.atomic_write_json(done, {"routed_total": 4})
    t.join(timeout=5)
    assert not t.is_alive()
    assert got == ["a", "b", "c", "d"]


def test_tailing_source_skip_limit_and_empty_partition(tmp_path):
    part = str(tmp_path / "p.ndjson")
    done = str(tmp_path / "p.done")
    open(part, "w").write("a\nb\nc\nd\n")
    open(done, "w").write("{}")
    assert list(F.TailingReplaySource(part, done, skip=1, limit=2)) == \
        ["b", "c"]
    # done marker with no partition file at all: clean empty stream
    os.unlink(part)
    assert list(F.TailingReplaySource(part, done)) == []


def test_tailing_source_graceful_shutdown_while_idle(tmp_path):
    part = str(tmp_path / "p.ndjson")
    done = str(tmp_path / "p.done")
    open(part, "w").write("a\n")
    src = F.TailingReplaySource(part, done, poll_s=0.01)
    it = iter(src)
    assert next(it) == "a"
    _metrics.request_shutdown()
    with pytest.raises(_metrics.GracefulShutdown):
        next(it)  # idle-tailing: the stop must not hang the worker


def test_tailing_source_stall_timeout(tmp_path):
    part = str(tmp_path / "p.ndjson")
    open(part, "w").write("a\n")
    src = F.TailingReplaySource(part, str(tmp_path / "p.done"),
                                poll_s=0.01, stall_timeout_s=0.05,
                                stall_deadline_s=0.2)
    with pytest.raises(RuntimeError, match="deadline"):
        list(src)
    # the bounded retry warned (partition-stall) before giving up
    assert src.stall_events >= 1


def test_tailing_source_stall_retry_survives_to_done(tmp_path):
    """A stall longer than the warn timeout but shorter than the deadline
    is a bounded retry (counted partition-stall events), not a crash —
    the pause a quarantine drain or rescale barrier produces."""
    part = str(tmp_path / "p.ndjson")
    done = str(tmp_path / "p.done")
    open(part, "w").write("a\n")
    src = F.TailingReplaySource(part, done, poll_s=0.01,
                                stall_timeout_s=0.05,
                                stall_deadline_s=30.0)
    got = []
    t = threading.Thread(target=lambda: got.extend(src))
    t.start()
    time.sleep(0.3)  # well past the warn timeout, far from the deadline
    assert t.is_alive(), "bounded retry gave up before the deadline"
    assert src.stall_events >= 1
    with open(part, "a") as f:
        f.write("b\n")
    F.atomic_write_json(done, {"routed_total": 2})
    t.join(timeout=5)
    assert not t.is_alive()
    assert got == ["a", "b"]


# -------------------------------------------------- outbox + global merge


def _doc(key, records, fp="x", cell=None):
    return {"key": key, "window": [0, 5], "cell": cell, "records": records,
            "count": len(records), "fp": fp}


def test_read_outbox_dedups_crash_replay_duplicates(tmp_path):
    p = str(tmp_path / "outbox.jsonl")
    with open(p, "w") as f:
        f.write(json.dumps(_doc("0:5:None", ["r1"], fp="aa")) + "\n")
        f.write(json.dumps(_doc("0:5:None", ["r1"], fp="aa")) + "\n")
        f.write(json.dumps(_doc("5:10:None", ["r2"], fp="bb")) + "\n")
        f.write('{"torn')  # kill mid-write: ignored, replayed later
    out = F.read_outbox(p)
    assert sorted(out) == ["0:5:None", "5:10:None"]


def test_read_outbox_raises_on_divergent_duplicate(tmp_path):
    p = str(tmp_path / "outbox.jsonl")
    with open(p, "w") as f:
        f.write(json.dumps(_doc("0:5:None", ["r1"], fp="aa")) + "\n")
        f.write(json.dumps(_doc("0:5:None", ["r2"], fp="cc")) + "\n")
    with pytest.raises(F.FleetMergeError, match="exactly-once"):
        F.read_outbox(p)


def test_merge_outboxes_union_family_is_assignment_independent():
    w0 = {"0:5:None": _doc("0:5:None", ["b", "a"])}
    w1 = {"0:5:None": _doc("0:5:None", ["c"]),
          "5:10:None": _doc("5:10:None", ["d"])}
    merged = F.merge_outboxes({0: w0, 1: w1}, "range")
    assert [m["key"] for m in merged] == ["0:5:None", "5:10:None"]
    assert merged[0]["records"] == ["a", "b", "c"]  # sorted union
    # flipping which worker held what must not change the table digest
    flipped = F.merge_outboxes({0: w1, 1: w0}, "range")
    assert F.merged_table_digest(merged) == F.merged_table_digest(flipped)


def test_merge_outboxes_knn_re_topk():
    w0 = {"0:5:None": _doc("0:5:None", [["a", 1.0], ["b", 2.0]])}
    w1 = {"0:5:None": _doc("0:5:None", [["c", 0.5], ["a", 1.0]])}
    merged = F.merge_outboxes({0: w0, 1: w1}, "knn", k=2)
    assert merged[0]["records"] == [["c", 0.5], ["a", 1.0]]


def test_merge_window_records_seam():
    assert merge_window_records("range", [["a"], ["b"]]) == ["a", "b"]
    top = merge_window_records("knn", [[("a", 2.0)], [("b", 1.0)]], k=1)
    assert top == [("b", 1.0)]
    with pytest.raises(ValueError, match="kNN merge needs k"):
        merge_window_records("knn", [[("a", 1.0)]])


# ------------------------------------------------------- fleet manifest


def test_fleet_manifest_roundtrip(tmp_path):
    p = str(tmp_path / "fleet.json")
    m = F.FleetManifest(p)
    m.assign_all({1: 0, 2: 1})
    m.assign(3, 0)
    assert m.advance_epoch() == 1
    assert m.note_restart(1) == 1
    assert m.note_restart(1) == 2
    m.save()
    m2 = F.FleetManifest(p)  # a crashed supervisor reloads everything
    assert m2.fleet_assignment == {1: 0, 2: 1, 3: 0}
    assert m2.fleet_epoch == 1
    assert m2.fleet_restarts == {1: 2}


# ------------------------------------------------------- fencing epochs


def _fdoc(key, records, fp, fence=0):
    d = _doc(key, records, fp=fp)
    if fence:
        d["fence"] = fence
    return d


def test_heartbeat_fence_stamping_and_age(tmp_path):
    hb = str(tmp_path / "heartbeat")
    w = F.HeartbeatWriter(hb, interval_s=0.05, fence=2)
    w.start()
    try:
        time.sleep(0.15)
        beat = json.load(open(hb))
        assert beat["fence"] == 2 and beat["pid"] == os.getpid()
        age = F.heartbeat_age_s(hb, fence=2)
        assert age is not None and age < 5.0
        # a successor expecting fence 3 must not read this beat as
        # liveness — it is the zombie predecessor's write
        assert F.heartbeat_age_s(hb, fence=3) is None
    finally:
        w.close()


def test_heartbeat_gate_suppresses_beats(tmp_path):
    hb = str(tmp_path / "heartbeat")
    w = F.HeartbeatWriter(hb, interval_s=0.02, fence=1,
                          gate=lambda: True)
    w.start()
    try:
        time.sleep(0.1)
        assert not os.path.exists(hb)  # wedged: silence, not beats
    finally:
        w.close()


def test_heartbeat_age_legacy_mtime_fallback(tmp_path):
    hb = tmp_path / "heartbeat"
    hb.write_text("")  # pre-fence format: an empty touch file
    age = F.heartbeat_age_s(str(hb), fence=1)
    assert age is not None and age < 5.0


def test_read_outbox_drops_zombie_rows_past_fence_cutoff(tmp_path):
    p = str(tmp_path / "outbox.jsonl")
    with open(p, "w") as f:
        f.write(json.dumps(_fdoc("0:5:None", ["r1"], "aa")) + "\n")
        cutoff = f.tell()
        # the zombie (still fence 0) keeps writing past its cutoff...
        f.write(json.dumps(_fdoc("5:10:None", ["zz"], "zz")) + "\n")
        # ...while the fenced successor re-emits the window correctly
        f.write(json.dumps(_fdoc("5:10:None", ["r2"], "bb", fence=1))
                + "\n")
    stats = {}
    out = F.read_outbox(p, fence_cutoffs={0: cutoff}, stats=stats)
    assert sorted(out) == ["0:5:None", "5:10:None"]
    assert out["0:5:None"]["records"] == ["r1"]  # pre-cutoff row survives
    assert out["5:10:None"]["records"] == ["r2"]
    assert stats == {"stale_fence_rows": 1, "fence_conflicts": 0}
    # stats accumulate across calls (one dict over a whole fleet)
    F.read_outbox(p, fence_cutoffs={0: cutoff}, stats=stats)
    assert stats["stale_fence_rows"] == 2


def test_read_outbox_cross_fence_conflict_keeps_newest_fence(tmp_path):
    p = str(tmp_path / "outbox.jsonl")
    with open(p, "w") as f:
        f.write(json.dumps(_fdoc("0:5:None", ["old"], "aa")) + "\n")
        f.write(json.dumps(_fdoc("0:5:None", ["new"], "bb", fence=1))
                + "\n")
    stats = {}
    out = F.read_outbox(p, stats=stats)
    # cross-fence divergence: the superseded writer is the less trusted
    # side — keep the newest fence, count a conflict, never abort
    assert out["0:5:None"]["records"] == ["new"]
    assert stats["fence_conflicts"] == 1
    with open(p, "a") as f:
        f.write(json.dumps(_fdoc("0:5:None", ["x"], "cc", fence=1))
                + "\n")
    # SAME-fence divergence stays the hard exactly-once error
    with pytest.raises(F.FleetMergeError, match="exactly-once"):
        F.read_outbox(p)


def test_fleet_manifest_fence_rescale_quarantine_roundtrip(tmp_path):
    p = str(tmp_path / "fleet.json")
    m = F.FleetManifest(p)
    assert m.fence_of(0) == 0
    assert m.bump_fence(0, outbox_bytes=100, journal_bytes=40,
                        reason="stall") == 1
    assert m.bump_fence(0, outbox_bytes=250, journal_bytes=90,
                        reason="crash") == 2
    m.note_rescale(n_from=2, n_to=3, at_records=150, epoch=2)
    m.note_quarantine(1, "quarantine", score=3.5)
    m.save()
    m2 = F.FleetManifest(p)  # durable across a supervisor crash
    assert m2.fence_of(0) == 2 and m2.fence_of(1) == 0
    assert m2.fence_cutoffs(0) == {0: {"outbox": 100, "journal": 40},
                                   1: {"outbox": 250, "journal": 90}}
    assert m2.fence_cutoffs(1) == {}
    assert m2.fleet_rescale_log[0]["n_to"] == 3
    assert m2.fleet_quarantine_log[0]["action"] == "quarantine"
    # the raw-state projection doctor uses agrees with the method
    assert F.fence_cutoffs_from(F.read_json(p), 0) == m2.fence_cutoffs(0)


def test_emitted_journal_fence_stamping_and_cutoffs(tmp_path):
    from spatialflink_tpu.operators import WindowResult
    from spatialflink_tpu.runtime.checkpoint import EmittedWindowJournal

    d = str(tmp_path)
    r1 = WindowResult(0, 5, ["a"], extras={"cell": 1})
    r2 = WindowResult(5, 10, ["b"], extras={"cell": 1})
    j0 = EmittedWindowJournal(d, fresh=True)  # fence-0 incarnation
    j0.record(r1)
    cutoff = os.path.getsize(j0.path)
    j0.record(r2)  # the zombie journals past its cutoff
    j0.close()
    # fence-0 lines stay bare keys: single-process byte-compat
    lines = open(j0.path).read().splitlines()
    assert lines == ["0:5:1", "5:10:1"]
    j1 = EmittedWindowJournal(d, fence=1, fence_cutoffs={0: cutoff})
    # r1 journaled pre-cutoff: suppressed; r2 post-cutoff: must re-emit
    assert j1.seen(r1) is True
    assert j1.seen(r2) is False
    j1.record(r2)
    j1.close()
    assert open(j1.path).read().splitlines()[-1] == "1\t5:10:1"
    # a third incarnation composes both fences' cutoffs
    j2 = EmittedWindowJournal(d, fence=2,
                              fence_cutoffs={0: cutoff,
                                             1: os.path.getsize(j1.path)})
    assert j2.seen(r1) is True and j2.seen(r2) is True
    j2.close()


def test_stall_fault_arms_wedges_and_expires():
    from spatialflink_tpu.runtime import faults

    f = faults.StallFault(0.2, superseded=lambda: True, emit_delay_s=0.0)
    assert not f.wedged()  # unarmed until the first emitted window
    f.on_window()
    assert f.wedged()
    time.sleep(0.25)
    assert not f.wedged()  # the gray failure heals after duration_s
    prev = faults.active_stall()
    try:
        assert faults.install_stall(f) is f
        assert faults.active_stall() is f
    finally:
        faults.install_stall(prev)


def test_stall_fault_gates_checkpoint_due(tmp_path):
    from spatialflink_tpu.runtime import faults
    from spatialflink_tpu.runtime.checkpoint import CheckpointCoordinator

    coord = CheckpointCoordinator(str(tmp_path / "ckpt"),
                                  every_batches=1)
    coord.note_batch()
    assert coord.due() is True
    f = faults.StallFault(30.0, superseded=lambda: True)
    f.on_window()  # armed + wedged
    prev = faults.active_stall()
    try:
        faults.install_stall(f)
        # a wedged zombie must not commit manifests its fenced
        # successor would resume from
        assert coord.due() is False
    finally:
        faults.install_stall(prev)
    assert coord.due() is True


def test_stall_fault_holds_wedged_writes_until_superseded(tmp_path):
    """A wedged worker's windows after the first wait for its successor's
    fence (read from the manifest), so the zombie writes past the fence
    however long the supervisor takes to issue it."""
    from types import SimpleNamespace

    from spatialflink_tpu.runtime import faults

    polls = []
    f = faults.StallFault(
        30.0, emit_delay_s=0.01,
        superseded=lambda: polls.append(1) or len(polls) >= 3)
    f.on_window()  # arms: the first window is written at once
    assert polls == []
    f.on_window()
    assert len(polls) == 3

    args = SimpleNamespace(fleet_role="worker", fleet_dir=str(tmp_path),
                           fleet_worker_id=0, fleet_heartbeat=1.0,
                           fleet_fence=0, fleet_stall_s=30.0)
    prev = faults.active_stall()
    ctx = F.WorkerContext.from_args(args, SimpleNamespace(family="range"))
    try:
        assert ctx.stall is faults.active_stall()
        assert ctx.stall.superseded() is False
        m = F.FleetManifest(os.path.join(str(tmp_path), F.MANIFEST_FILE))
        m.bump_fence(1)
        m.save()
        assert ctx.stall.superseded() is False  # another slot's fence
        m.bump_fence(0)
        m.save()
        assert ctx.stall.superseded() is True
        assert F.fence_superseded(str(tmp_path), 0, 1) is False
    finally:
        ctx.close()
        faults.install_stall(prev)


def test_parse_rescale_and_stall_chaos():
    from spatialflink_tpu.runtime.fleetsup import (_parse_rescale,
                                                   _parse_stall_chaos)

    assert _parse_rescale(None) == []
    assert _parse_rescale("300:2,150:3") == [(150, 3), (300, 2)]
    assert _parse_rescale("100:") == [(100, 1)]
    assert _parse_stall_chaos(None) is None
    assert _parse_stall_chaos("1:2.5") == (1, 2.5)
    assert _parse_stall_chaos("0:") == (0, 30.0)


def _bare_supervisor(tmp_path, **over):
    """A FleetSupervisor shell with just the state the quarantine
    machinery touches — the unit-test seam for the suspicion state
    machine (no processes, no routing)."""
    from spatialflink_tpu.runtime.fleetsup import FleetSupervisor

    sup = FleetSupervisor.__new__(FleetSupervisor)
    sup._lock = threading.RLock()
    sup.root = str(tmp_path)
    sup.heartbeat_s = 0.05
    sup.quarantine_s = over.get("quarantine_s", 10.0)
    sup.monitor = None
    sup.manifest = F.FleetManifest(str(tmp_path / F.MANIFEST_FILE))
    sup._active = over.get("active", [0, 1])
    sup._procs = {w: object() for w in sup._active}
    sup._quarantined = dict(over.get("quarantined", {}))
    sup._suspicion = {}
    sup._stall_chaos = None
    return sup


def _write_stale_heartbeat(tmp_path, wid, age_s):
    wd = F.worker_dir(str(tmp_path), wid)
    os.makedirs(wd, exist_ok=True)
    hb = os.path.join(wd, F.HEARTBEAT_FILE)
    open(hb, "w").write("")
    old = time.time() - age_s
    os.utime(hb, (old, old))
    return hb


def test_suspicion_quarantine_enter_and_hysteresis_exit(tmp_path):
    sup = _bare_supervisor(tmp_path)
    _write_stale_heartbeat(tmp_path, 0, age_s=60.0)  # slow, not dead
    _write_stale_heartbeat(tmp_path, 1, age_s=0.0)   # healthy
    for _ in range(3):
        sup._suspicion_tick()
    assert 0 in sup._quarantined, "stale heartbeat never quarantined"
    assert 1 not in sup._quarantined
    assert any(e["action"] == "quarantine" and e["worker"] == 0
               for e in sup.manifest.fleet_quarantine_log)
    # recovery: fresh beats decay the score; hysteresis exits at <= 1.0
    _write_stale_heartbeat(tmp_path, 0, age_s=0.0)
    for _ in range(12):
        sup._suspicion_tick()
    assert 0 not in sup._quarantined, "quarantine never lifted"
    assert any(e["action"] == "unquarantine"
               for e in sup.manifest.fleet_quarantine_log)


def test_suspicion_never_quarantines_last_routable_worker(tmp_path):
    sup = _bare_supervisor(tmp_path, active=[0, 1],
                           quarantined={1: time.monotonic()})
    # BOTH workers look sick — but with 1 already quarantined, 0 is the
    # last routable worker and must never be drained
    _write_stale_heartbeat(tmp_path, 0, age_s=60.0)
    _write_stale_heartbeat(tmp_path, 1, age_s=60.0)
    for _ in range(6):
        sup._suspicion_tick()
    assert 1 in sup._quarantined  # still sick, still quarantined
    assert 0 not in sup._quarantined, \
        "quarantined the only remaining routable worker"


def test_quarantine_tick_deadline(tmp_path):
    sup = _bare_supervisor(tmp_path, quarantine_s=0.05,
                           quarantined={0: time.monotonic()})
    assert sup._quarantine_tick() == []
    time.sleep(0.1)
    assert sup._quarantine_tick() == [0]  # deadline breach: escalate


# --------------------------------------------------------- worker argv


def test_worker_argv_strips_and_reissues():
    base = ["--config", "c.yml", "--option", "1",
            "--input1", "/orig/in.geojson", "--fleet", "4",
            "--fleet-dir", "/orig/fleet", "--limit", "100",
            "--checkpoint-dir", "/orig/ckpt", "--resume",
            "--strict-recompile", "--panes"]
    argv = worker_argv(base, fleet_dir="/f", worker_id=2,
                       heartbeat_s=0.5, resume=True)
    # fleet/placement flags replaced, pipeline flags inherited
    assert "--strict-recompile" in argv and "--panes" in argv
    assert "/orig/in.geojson" not in argv and "/orig/ckpt" not in argv
    assert "--limit" not in argv  # the supervisor already applied it
    assert argv[argv.index("--fleet-worker-id") + 1] == "2"
    assert argv[argv.index("--input1") + 1].endswith(
        os.path.join("worker2", F.PARTITION_FILE))
    assert argv.count("--resume") == 1
    # the fence token is always reissued (0 for a never-fenced slot)
    assert argv[argv.index("--fleet-fence") + 1] == "0"
    no_resume = worker_argv(base, fleet_dir="/f", worker_id=0,
                            heartbeat_s=0.5, resume=False)
    assert "--resume" not in no_resume
    fenced = worker_argv(base, fleet_dir="/f", worker_id=0,
                         heartbeat_s=0.5, resume=True, fence=3,
                         stall_s=2.5)
    assert fenced[fenced.index("--fleet-fence") + 1] == "3"
    assert fenced[fenced.index("--fleet-stall-s") + 1] == "2.5"
    assert "--fleet-stall-s" not in no_resume  # chaos glue is opt-in


def test_strip_flags_handles_equals_form():
    out = _strip_flags(["--fleet=2", "--option", "1", "--limit=5"],
                       {"--fleet": 1, "--limit": 1})
    assert out == ["--option", "1"]


# ------------------------------------------------------ canonical window


def test_canonical_window_doc_matches_journal_key():
    from spatialflink_tpu.operators import WindowResult

    r = WindowResult(0, 5000, ["x"], extras={"cell": 7})
    doc = F.canonical_window_doc(r, "range")
    assert doc["key"] == "0:5000:7"
    assert doc["window"] == [0, 5000]
    # identical content => identical fingerprint (the dedup cross-check)
    assert doc["fp"] == F.canonical_window_doc(r, "range")["fp"]


# ----------------------------------------------------- /fleet endpoint


def test_fleet_endpoint_without_supervisor_notes_absence():
    from spatialflink_tpu.runtime.opserver import OpServer

    assert active_fleet() is None
    srv = OpServer(port=0).start()
    try:
        import urllib.request

        with urllib.request.urlopen(f"{srv.url}/fleet", timeout=5) as r:
            doc = json.loads(r.read().decode())
        assert doc["fleet"] is False and "--fleet" in doc["note"]
    finally:
        srv.close()


def test_fleet_snapshot_schema():
    from spatialflink_tpu.utils.telemetry import fleet_snapshot

    snap = fleet_snapshot([{"worker": 0, "alive": True, "restarts": 2},
                           {"worker": 1, "alive": False, "restarts": 0}],
                          epoch=3, routed=100)
    assert snap["schema"] == "fleet-v1"
    assert snap["n_workers"] == 2 and snap["alive"] == 1
    assert snap["restarts_total"] == 2 and snap["epoch"] == 3


# --------------------------------------------------- integration smoke


def _conf_file(tmp_path):
    with open(CONF) as f:
        d = yaml.safe_load(f)
    p = tmp_path / "conf.yml"
    p.write_text(yaml.safe_dump(d))
    return str(p)


def test_fleet_kill_recovery_identity_vs_single_worker(tmp_path):
    """THE acceptance test: N=2 workers over a file replay, worker 0
    SIGKILLed mid-run by the chaos hook, restarted from its checkpoint by
    the supervisor — and the merged window table (and its digest) is
    byte-identical to a fault-free single-worker fleet run, with zero
    post-warmup recompiles across every incarnation."""
    cfg = _conf_file(tmp_path)
    path1 = _write_input(tmp_path, _lines())

    oracle_dir = tmp_path / "fleet1"
    assert main(_fleet_argv(cfg, path1, oracle_dir, 1)) == 0
    oracle = _result(oracle_dir)
    assert oracle["merged_windows"] > 0
    assert oracle["post_warmup_compiles"] == 0

    kill_dir = tmp_path / "fleet2k"
    assert main(_fleet_argv(cfg, path1, kill_dir, 2,
                            "--fleet-chaos-kill", "0:1")) == 0
    killed = _result(kill_dir)
    assert sum(int(v) for v in killed["restarts"].values()) >= 1, \
        "chaos kill never fired — the restart path went untested"
    assert killed["digest"] == oracle["digest"], \
        "merged fleet output diverged from the single-worker oracle"
    assert killed["post_warmup_compiles"] == 0, \
        "a worker respawn silently recompiled"
    # the tables themselves, not just the digest
    o_table = _merged_table(oracle_dir)
    k_table = _merged_table(kill_dir)
    assert [(m["key"], m["records"]) for m in k_table] == \
        [(m["key"], m["records"]) for m in o_table]
    # supervision left an audit trail
    log = killed["restart_log"]
    assert any("chaos kill" in (r.get("reason") or "") for r in log)
    # doctor fleet reads the same directory
    from spatialflink_tpu import doctor

    rc = doctor.main(["--json", "fleet", str(kill_dir)])
    assert rc == 0


def test_fleet_rescale_zombie_identity(tmp_path):
    """The elastic-fleet acceptance test: a live N=2→3→2 rescale with
    worker 0's first incarnation wedged into a writing zombie (stall
    chaos), fenced+respawned WITHOUT a kill — and the merged window
    table is byte-identical to a fault-free fixed-N oracle, with the
    zombie's stale-fence rows counted and dropped (never a merge error)
    and zero post-warmup recompiles on every incarnation."""
    cfg = _conf_file(tmp_path)
    path1 = _write_input(tmp_path, _lines(n_traj=8, steps=80))

    oracle_dir = tmp_path / "fleet1"
    assert main(_fleet_argv(cfg, path1, oracle_dir, 1)) == 0
    oracle = _result(oracle_dir)
    assert oracle["merged_windows"] > 0

    rdir = tmp_path / "rescale"
    assert main(_fleet_argv(cfg, path1, rdir, 2,
                            "--fleet-rescale", "150:3,300:2",
                            "--fleet-chaos-stall", "0:60",
                            "--fleet-quarantine-s", "1")) == 0
    got = _result(rdir)
    assert got["digest"] == oracle["digest"], \
        "rescale + zombie changed the merged output"
    o_table = _merged_table(oracle_dir)
    r_table = _merged_table(rdir)
    assert [(m["key"], m["records"]) for m in r_table] == \
        [(m["key"], m["records"]) for m in o_table]
    # both rescale points were consumed at epoch boundaries
    assert [(r["n_from"], r["n_to"]) for r in got["rescales"]] == \
        [(2, 3), (3, 2)]
    assert got["retired_workers"] == [2]
    assert got["workers_final"] == 2
    # the zombie was fenced (never merged) and kept writing past its
    # cutoff — containment proven by the dropped-row count
    assert int(got["fences"]["0"]) >= 1, "stall target was never fenced"
    assert got["stale_fence_rows"] >= 1, \
        "zombie wrote no stale rows — containment went unexercised"
    assert got["post_warmup_compiles"] == 0, \
        "a respawn or rescale silently recompiled"
    # doctor fleet renders the fence/rescale/quarantine history
    import io

    from spatialflink_tpu import doctor

    buf = io.StringIO()
    assert doctor.fleet(str(rdir), as_json=True, out=buf) == 0
    doc = json.loads(buf.getvalue())
    assert doc["stale_fence_rows"] >= 1
    assert len(doc["rescale_log"]) == 2
    assert any(e["worker"] == 0 for e in doc["fence_log"])
    buf = io.StringIO()
    assert doctor.fleet(str(rdir), as_json=False, out=buf) == 0
    text = buf.getvalue()
    assert "rescale    2 -> 3" in text and "fence      w0" in text


@pytest.mark.slow
def test_fleet_randomized_kill_fuzz(tmp_path):
    """Randomized kill points: whichever window count the kill lands on,
    the merged table must match the single-worker oracle. Half the
    trials additionally run a randomized live rescale plus a zombie
    writer (stall chaos on the OTHER worker) — the composed failure
    modes must still merge to the oracle."""
    cfg = _conf_file(tmp_path)
    path1 = _write_input(tmp_path, _lines(n_traj=8, steps=60))

    oracle_dir = tmp_path / "oracle"
    assert main(_fleet_argv(cfg, path1, oracle_dir, 1)) == 0
    oracle = _result(oracle_dir)

    rng = random.Random(11)
    for trial in range(4):
        wid = rng.randrange(2)
        nth = rng.randint(1, 6)
        extra = ["--fleet-chaos-kill", f"{wid}:{nth}"]
        if trial % 2:
            at1 = rng.randrange(100, 250)
            at2 = at1 + rng.randrange(100, 200)
            extra += ["--fleet-rescale", f"{at1}:3,{at2}:2",
                      "--fleet-chaos-stall", f"{1 - wid}:60",
                      "--fleet-quarantine-s", "1"]
        fdir = tmp_path / f"fuzz{trial}"
        assert main(_fleet_argv(cfg, path1, fdir, 2, *extra)) == 0
        got = _result(fdir)
        assert got["digest"] == oracle["digest"], \
            f"trial {trial}: {extra} changed the merged output"
        assert got["post_warmup_compiles"] == 0


@pytest.mark.slow
def test_fleet_supervisor_sigterm_drains_workers(tmp_path):
    """SIGTERM to the supervisor: routing stops, workers drain (final
    checkpoint each), the partial merge is written, exit 0."""
    cfg = _conf_file(tmp_path)
    path1 = _write_input(tmp_path, _lines(n_traj=10, steps=200))
    fdir = tmp_path / "drain"
    proc = subprocess.Popen(
        [sys.executable, "-m", "spatialflink_tpu.driver"]
        + _fleet_argv(cfg, path1, fdir, 2),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        deadline = time.monotonic() + 60
        started = False
        while time.monotonic() < deadline:
            if any(os.path.exists(os.path.join(F.worker_dir(str(fdir), w),
                                               F.OUTBOX_FILE))
                   for w in (0, 1)):
                started = True
                break
            time.sleep(0.2)
        assert started, "fleet never started emitting"
        proc.terminate()
        out, _ = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 0, out.decode()[-2000:]
    result = _result(fdir)
    assert result["graceful"] is True


def test_fleet_supervisor_initialises_no_backend(tmp_path):
    """One process per chip: on a TPU host every worker needs its own chip,
    so the ``--fleet`` supervisor must never take one — it routes, merges
    and supervises without initialising a JAX backend."""
    cfg = _conf_file(tmp_path)
    path1 = _write_input(tmp_path, _lines(n_traj=2, steps=20))
    code = ("import sys\n"
            "from jax._src import xla_bridge\n"
            "from spatialflink_tpu.driver import main\n"
            "rc = main(sys.argv[1:])\n"
            "assert not xla_bridge.backends_are_initialized(), "
            "'the fleet supervisor initialised a JAX backend'\n"
            "sys.exit(rc)\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", code]
        + _fleet_argv(cfg, path1, tmp_path / "fleet", 1),
        cwd=root, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert _result(tmp_path / "fleet")["merged_windows"] > 0
