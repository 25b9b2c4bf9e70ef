"""Chaos suite: fault injection + supervised recovery (runtime/faults.py,
runtime/supervisor.py, driver --chaos/--retry/--dlq).

Headline invariant, end to end: for windowed range/kNN/join broker
pipelines under EVERY injected fault class — transient produce/consume
errors, lost acks, latency spikes, duplicate deliveries, delivery
reordering, torn payloads, and crash/restart — the final per-window output
(marker-keyed window table: keys AND record counts) is identical to a
fault-free run, and the consumer group commits the full input. Poison
records (corrupt IN the log, not just in transport) quarantine to the
dead-letter topic with failure metadata while the pipeline keeps producing.

Everything is seeded (FaultPlan + RetryPolicy jitter), so the chaos runs
replay deterministically; the fast subset is marked ``chaos_smoke``.
"""

import json
import time

import pytest
import yaml

from spatialflink_tpu.driver import main
from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.runtime.faults import (ChaosBroker, FaultPlan,
                                             TransientBrokerError)
from spatialflink_tpu.runtime.supervisor import (CircuitBreaker,
                                                 CircuitOpenError,
                                                 DeadLetterQueue, RetryError,
                                                 RetryPolicy,
                                                 SupervisedBroker)
from spatialflink_tpu.streams import (
    InMemoryBroker,
    KafkaSource,
    KafkaWindowSink,
    SyntheticPointSource,
    reset_memory_brokers,
    resolve_broker,
    serialize_spatial,
)
from spatialflink_tpu.utils.metrics import REGISTRY

CONF = "conf/spatialflink-conf.yml"
IN1, IN2, OUT = "points.geojson", "queries.geojson", "output"

#: every fault class at a rate high enough to fire many times over a
#: ~50-record run, low enough that the seeded retry budget always wins
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


def _lines(n_traj=8, steps=6, seed=3):
    grid = UniformGrid(115.5, 117.6, 39.6, 41.1, num_grid_partitions=100)
    pts = list(SyntheticPointSource(grid, num_trajectories=n_traj,
                                    steps=steps, seed=seed))
    return [serialize_spatial(p, "GeoJSON") for p in pts]


def _window_table(broker, topic=OUT):
    """{window key: record count} from the marker records — the unit of
    output identity (keys cover window bounds + job; counts cover
    contents)."""
    out = {}
    for r in broker.fetch(topic, 0, 1_000_000):
        if isinstance(r.key, str) and r.key.startswith(KafkaWindowSink.MARKER):
            out[r.key[len(KafkaWindowSink.MARKER):]] = int(r.value)
    return out


def _oracle(tmp_path, option, lines, name, extra=()):
    """Fault-free run on its own broker: the expected window table."""
    cfg, url = _conf(tmp_path, name, f"{name}.yml")
    broker = resolve_broker(url)
    for ln in lines:
        broker.produce(IN1, ln)
    assert main(["--config", cfg, "--kafka", "--option", str(option)]
                + list(extra)) == 0
    table = _window_table(broker)
    assert table, "oracle run produced no windows"
    return table


# ------------------------------------------------------------- e2e identity


@pytest.mark.chaos_smoke
@pytest.mark.parametrize("fault", [
    "fetch_fail=0.35",
    "produce_fail=0.3",
    "ack_lost=0.3",
    "duplicate=0.5",
    "reorder=0.8",
    "torn=0.2",
    "latency=0.3,latency_ms=1",
])
def test_chaos_range_output_identical_per_fault_class(tmp_path, fault):
    """Option 1 (windowed range) under each single fault class: window
    table identical to the fault-free run, full input committed, nothing
    dead-lettered (transport faults all heal)."""
    lines = _lines()
    expected = _oracle(tmp_path, 1, lines, f"oracle-{fault[:6]}")
    cfg, url = _conf(tmp_path, f"chaos-{fault[:6]}", "c.yml")
    broker = resolve_broker(url)
    for ln in lines:
        broker.produce(IN1, ln)
    assert main(["--config", cfg, "--kafka", "--option", "1",
                 "--chaos", f"seed=11,{fault}",
                 "--retry", RETRY, "--dlq"]) == 0
    assert _window_table(broker) == expected
    assert broker.committed(IN1, "spatialflink") == len(lines)
    assert broker.end_offset(OUT + "-dlq") == 0, \
        "transport-only faults must not dead-letter records"


@pytest.mark.chaos_smoke
@pytest.mark.parametrize("opt,needs2", [(1, False), (51, False), (101, True)])
def test_chaos_all_faults_range_knn_join(tmp_path, opt, needs2):
    """The headline: range, kNN and join window pipelines under EVERY fault
    class at once produce bitwise-identical window tables."""
    lines = _lines()
    lines2 = _lines(seed=8)
    cfg_o, url_o = _conf(tmp_path, f"all-oracle-{opt}", "o.yml")
    bo = resolve_broker(url_o)
    for ln in lines:
        bo.produce(IN1, ln)
    if needs2:
        for ln in lines2:
            bo.produce(IN2, ln)
    assert main(["--config", cfg_o, "--kafka", "--option", str(opt)]) == 0
    expected = _window_table(bo)
    assert expected

    cfg, url = _conf(tmp_path, f"all-chaos-{opt}", "c.yml")
    broker = resolve_broker(url)
    for ln in lines:
        broker.produce(IN1, ln)
    if needs2:
        for ln in lines2:
            broker.produce(IN2, ln)
    assert main(["--config", cfg, "--kafka", "--option", str(opt),
                 "--chaos", ALL_FAULTS.format(seed=23),
                 "--retry", RETRY, "--dlq"]) == 0
    assert _window_table(broker) == expected
    assert broker.committed(IN1, "spatialflink") == len(lines)
    if needs2:
        assert broker.committed(IN2, "spatialflink") == len(lines2)
    assert broker.end_offset(OUT + "-dlq") == 0


def test_chaos_crash_restart_output_identical(tmp_path, monkeypatch):
    """Crash at the 3rd fresh window UNDER transport chaos, restart (still
    under chaos, different seed): the final window table equals the
    fault-free oracle — at-least-once redelivery + marker-seeded
    suppression survive a degraded transport too."""
    lines = _lines(6, 30)
    expected = _oracle(tmp_path, 1, lines, "crash-oracle")
    assert len(expected) >= 4

    cfg, url = _conf(tmp_path, "crash-chaos", "c.yml")
    broker = resolve_broker(url)
    for ln in lines:
        broker.produce(IN1, ln)
    argv = ["--config", cfg, "--kafka", "--option", "1",
            "--retry", RETRY, "--dlq"]
    orig = KafkaWindowSink.emit
    state = {"fresh": 0}

    def boom(self, result):
        if self.window_key(result) not in self.delivered:
            state["fresh"] += 1
            if state["fresh"] == 3:
                raise RuntimeError("injected crash under chaos")
        orig(self, result)

    with monkeypatch.context() as m:
        m.setattr(KafkaWindowSink, "emit", boom)
        with pytest.raises(RuntimeError, match="injected crash"):
            main(argv + ["--chaos", ALL_FAULTS.format(seed=31)])
    assert broker.committed(IN1, "spatialflink") < len(lines)

    assert main(argv + ["--chaos", ALL_FAULTS.format(seed=32)]) == 0
    assert _window_table(broker) == expected
    assert broker.committed(IN1, "spatialflink") == len(lines)


@pytest.mark.chaos_smoke
def test_poison_records_quarantined_pipeline_progresses(tmp_path):
    """Records corrupt IN the log (not transport-torn) fail every
    redelivery and land in the DLQ with failure metadata; the windows from
    the clean records match the oracle run on poison-free input, and the
    group commits past the poison (quarantine = reflected in output)."""
    lines = _lines()
    expected = _oracle(tmp_path, 1, lines, "poison-oracle")

    poison = ['{"definitely": "not a spatial feature"}',
              "%% torn beyond recognition \x00\x00",
              '{"geometry": {"type": "Poi']
    records = lines[:10] + poison[:2] + lines[10:-5] + [poison[2]] + lines[-5:]
    cfg, url = _conf(tmp_path, "poison", "c.yml")
    broker = resolve_broker(url)
    for r in records:
        broker.produce(IN1, r)
    assert main(["--config", cfg, "--kafka", "--option", "1",
                 "--retry", RETRY, "--dlq"]) == 0
    assert _window_table(broker) == expected
    assert broker.committed(IN1, "spatialflink") == len(records)

    dlq = DeadLetterQueue(broker, OUT + "-dlq")
    entries = dlq.entries()
    assert len(entries) == len(poison)
    for e in entries:
        assert e["topic"] == IN1
        assert e["error"] and e["error_type"]
        assert e["attempts"] > 1, "poison must be retried before quarantine"
        assert records[e["offset"]] == e["raw"], \
            "DLQ metadata must point at the quarantined record"


@pytest.mark.chaos_smoke
def test_circuit_breaker_trips_and_run_completes(tmp_path):
    """A scripted burst of consecutive produce failures trips the breaker
    (threshold 3 < burst 5); the supervisor waits out the cool-down,
    half-opens, recovers, and the run still produces the oracle table."""
    lines = _lines()
    expected = _oracle(tmp_path, 1, lines, "breaker-oracle")
    cfg, url = _conf(tmp_path, "breaker", "c.yml")
    broker = resolve_broker(url)
    for ln in lines:
        broker.produce(IN1, ln)
    trips0 = REGISTRY.counter("breaker-trips").count
    assert main(["--config", cfg, "--kafka", "--option", "1",
                 "--chaos", "seed=5,fail_next_produces=5",
                 "--retry", "attempts=10,base_ms=1,breaker_threshold=3,"
                            "cooldown_ms=5"]) == 0
    assert REGISTRY.counter("breaker-trips").count > trips0
    assert _window_table(broker) == expected
    assert broker.committed(IN1, "spatialflink") == len(lines)


def test_chaos_bulk_drain_falls_back_and_heals(tmp_path, capsys):
    """--kafka under torn/duplicate/reorder chaos and failing fetches: the
    chunked native (bulk) decode of each poll batch meets torn payloads,
    falls back to the per-record parse, redelivery heals them, and the
    window table still matches the fault-free oracle. At a 30% tear rate
    the redelivery budget can run out: a record torn on every re-fetch is
    quarantined, and the dead-letter topic then holds only such transport
    tears of records that are intact in the log."""
    lines = _lines()
    expected = _oracle(tmp_path, 1, lines, "bulkchaos-oracle")
    cfg, url = _conf(tmp_path, "bulkchaos", "c.yml")
    broker = resolve_broker(url)
    for ln in lines:
        broker.produce(IN1, ln)
    assert main(["--config", cfg, "--kafka", "--option", "1",
                 "--chaos", "seed=3,torn=0.3,fetch_fail=0.2,duplicate=0.3,"
                            "reorder=0.5",
                 "--retry", RETRY, "--dlq"]) == 0
    assert _window_table(broker) == expected
    assert broker.committed(IN1, "spatialflink") == len(lines)
    limit = DeadLetterQueue(broker, OUT + "-dlq").redelivery_limit
    for e in DeadLetterQueue(broker, OUT + "-dlq").entries():
        assert e["topic"] == IN1 and e["attempts"] == limit + 1
        assert e["raw"] != lines[e["offset"]], "only transport tears"


def test_chaos_without_retry_crashes_loudly(tmp_path):
    """--chaos without --retry: the injected transient error propagates —
    the contrast that shows the supervisor is doing the surviving."""
    cfg, url = _conf(tmp_path, "no-retry", "c.yml")
    broker = resolve_broker(url)
    for ln in _lines():
        broker.produce(IN1, ln)
    with pytest.raises(TransientBrokerError):
        main(["--config", cfg, "--kafka", "--option", "1",
              "--chaos", "seed=3,fail_next_fetches=1"])


def test_chaos_flags_require_kafka(tmp_path):
    cfg, _ = _conf(tmp_path, "gate", "c.yml")
    for extra in (["--chaos", "seed=1"], ["--retry"], ["--dlq"]):
        with pytest.raises(SystemExit):
            main(["--config", cfg, "--option", "1"] + extra)


# ------------------------------------------------------------------ units


def test_fault_plan_spec_parse_and_validation():
    p = FaultPlan.from_spec("seed=7,fetch_fail=0.25,torn=0.1,"
                            "fail_next_produces=3,latency_ms=4")
    assert (p.seed, p.fetch_fail, p.torn) == (7, 0.25, 0.1)
    assert p.fail_next_produces == 3 and p.latency_ms == 4.0
    with pytest.raises(ValueError, match="unknown field"):
        FaultPlan.from_spec("fetch_failz=0.2")
    with pytest.raises(ValueError, match="not in"):
        FaultPlan(duplicate=1.5)
    with pytest.raises(ValueError, match="malformed"):
        FaultPlan.from_spec("seed")


def test_chaos_broker_is_deterministic_and_log_preserving():
    """Same seed + same call sequence → the same fault schedule; torn
    payloads corrupt only the delivered COPY, never the log."""
    def run(seed):
        inner = InMemoryBroker()
        ch = ChaosBroker(inner, FaultPlan(seed=seed, fetch_fail=0.3,
                                          torn=0.5, reorder=0.5))
        for i in range(20):
            ch.produce("t", f"v{i}")
        seen = []
        for _ in range(30):
            try:
                seen.append([(r.offset, r.value) for r in ch.fetch("t", 0, 20)])
            except TransientBrokerError:
                seen.append("FAIL")
        return inner, seen

    inner_a, a = run(9)
    _, b = run(9)
    assert a == b, "same seed must replay the same fault schedule"
    assert [r.value for r in inner_a._topics["t"]] == \
        [f"v{i}" for i in range(20)], "chaos must never corrupt the log"
    assert any(s == "FAIL" for s in a)
    assert any(s != "FAIL" and any("TORN" in v for _, v in s) for s in a)


def test_kafka_source_resequences_duplicates_and_reordering():
    """The source delivers every record exactly once, in offset order, over
    a transport that duplicates and permutes every batch."""
    inner = InMemoryBroker()
    for i in range(200):
        inner.produce("t", i)
    chaos = ChaosBroker(inner, FaultPlan(seed=13, duplicate=1.0, reorder=1.0))
    src = KafkaSource(chaos, "t", "g", poll_batch=16, auto_commit=False)
    assert list(src) == list(range(200))
    assert src.position == 200


def test_retry_policy_backoff_schedule_and_give_up():
    sleeps = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        raise TransientBrokerError("nope")

    pol = RetryPolicy(max_attempts=4, base_delay_s=0.01, multiplier=2.0,
                      jitter=0.0, seed=0)
    with pytest.raises(RetryError) as ei:
        pol.call(flaky, sleep=sleeps.append)
    assert calls["n"] == 4
    assert sleeps == [0.01, 0.02, 0.04]
    assert isinstance(ei.value.__cause__, TransientBrokerError)

    # non-retryable errors propagate unchanged on the first attempt
    def boom():
        raise KeyError("not transient")

    with pytest.raises(KeyError):
        RetryPolicy(max_attempts=5).call(boom, sleep=sleeps.append)

    # seeded jitter is deterministic
    import itertools

    d1 = list(itertools.islice(RetryPolicy(seed=3).delays(), 5))
    d2 = list(itertools.islice(RetryPolicy(seed=3).delays(), 5))
    assert d1 == d2


def test_retry_policy_deadline_and_attempt_timeout():
    # deadline: no retry is scheduled past it (fake clock advances 1s/call)
    t = {"now": 0.0}

    def clock():
        t["now"] += 1.0
        return t["now"]

    def flaky():
        raise TransientBrokerError("nope")

    pol = RetryPolicy(max_attempts=10, base_delay_s=0.01, deadline_s=2.5)
    with pytest.raises(RetryError, match="deadline"):
        pol.call(flaky, clock=clock, sleep=lambda s: None)

    # per-attempt timeout: a stalled attempt counts as a retryable failure
    calls = {"n": 0}

    def stalls_once():
        calls["n"] += 1
        if calls["n"] == 1:
            time.sleep(0.2)
        return "done"

    pol = RetryPolicy(max_attempts=3, base_delay_s=0.001,
                      attempt_timeout_s=0.05)
    assert pol.call(stalls_once) == "done"
    assert calls["n"] == 2


def test_circuit_breaker_state_machine():
    t = {"now": 0.0}
    cb = CircuitBreaker(failure_threshold=3, cooldown_s=10.0,
                        clock=lambda: t["now"])
    assert cb.state == "closed" and cb.allow()
    cb.record_failure()
    cb.record_failure()
    assert cb.state == "closed", "below threshold"
    cb.record_failure()
    assert cb.state == "open" and cb.trips == 1
    assert not cb.allow()
    with pytest.raises(CircuitOpenError):
        cb.check()
    t["now"] = 5.0
    assert not cb.allow(), "cool-down not elapsed"
    t["now"] = 10.5
    assert cb.allow(), "cool-down elapsed: half-open probe"
    assert cb.state == "half-open"
    cb.record_failure()  # probe failed: re-open, cool-down restarts
    assert not cb.allow()
    t["now"] = 21.0
    assert cb.allow()
    cb.record_success()
    assert cb.state == "closed" and cb.allow()
    # success resets the consecutive count: 2 failures don't re-trip
    cb.record_failure()
    cb.record_failure()
    assert cb.state == "closed" and cb.trips == 1


def test_supervised_produce_verifies_lost_acks():
    """ack_lost on EVERY produce: each record lands exactly once (the
    verified retry finds the landed record instead of re-sending)."""
    inner = InMemoryBroker()
    chaos = ChaosBroker(inner, FaultPlan(seed=1, ack_lost=1.0))
    sup = SupervisedBroker(chaos, RetryPolicy(max_attempts=4,
                                              base_delay_s=0.0001),
                           CircuitBreaker(10, 0.001))
    offs = [sup.produce("t", f"v{i}", key=f"k{i}") for i in range(30)]
    assert offs == list(range(30))
    assert [r.value for r in inner.fetch("t", 0, 100)] == \
        [f"v{i}" for i in range(30)]


def test_breaker_cooldown_wait_not_charged_to_attempt_timeout():
    """Regression: the open-circuit cool-down wait runs OUTSIDE the
    per-attempt timeout. With the wait inside it, every attempt on an open
    circuit timed out, each timeout re-opened the breaker, and a recovered
    5-failure burst escalated into RetryError on a healthy transport."""
    inner = InMemoryBroker()
    inner.produce("t", "a")
    chaos = ChaosBroker(inner, FaultPlan(seed=2, fail_next_fetches=5))
    sup = SupervisedBroker(
        chaos,
        RetryPolicy(max_attempts=10, base_delay_s=0.001,
                    attempt_timeout_s=0.05),
        CircuitBreaker(failure_threshold=5, cooldown_s=0.2))
    recs = sup.fetch("t", 0, 10)  # must recover, not RetryError
    assert [r.value for r in recs] == ["a"]
    assert sup.breaker.trips == 1 and sup.breaker.state == "closed"


def test_supervised_fetch_waits_out_open_circuit():
    """A fetch burst longer than the breaker threshold trips the circuit;
    the supervisor sleeps out the cool-down and completes the call."""
    inner = InMemoryBroker()
    inner.produce("t", "a")
    chaos = ChaosBroker(inner, FaultPlan(seed=2, fail_next_fetches=4))
    slept = []
    sup = SupervisedBroker(
        chaos, RetryPolicy(max_attempts=10, base_delay_s=0.0001),
        CircuitBreaker(failure_threshold=3, cooldown_s=0.002),
        sleep=lambda s: slept.append(s) or time.sleep(min(s, 0.002)))
    recs = sup.fetch("t", 0, 10)
    assert [r.value for r in recs] == ["a"]
    assert sup.breaker.trips >= 1


def test_torn_control_tuple_heals_to_stop_not_dlq():
    """A remote-stop control tuple torn in transport must, once healed by
    the DLQ's redelivery, STOP the pipeline (ControlTupleExit) — not be
    quarantined as poison or passed through as data."""
    from dataclasses import replace as _replace

    from spatialflink_tpu.streams import WindowCommitTap
    from spatialflink_tpu.utils.metrics import ControlTupleExit

    inner = InMemoryBroker()
    inner.produce("t", json.dumps(
        {"geometry": {"type": "control", "coordinates": []}}))

    class TearFirstDelivery:
        """Corrupt the first delivery of each offset; redeliveries heal."""

        def __init__(self, b):
            self.b = b
            self.seen = set()

        def fetch(self, topic, offset, max_records=500):
            out = []
            for r in self.b.fetch(topic, offset, max_records):
                if r.offset not in self.seen:
                    self.seen.add(r.offset)
                    r = _replace(r, value=r.value[:5] + "\x00TORN")
                out.append(r)
            return out

        def __getattr__(self, name):
            return getattr(self.b, name)

    src = KafkaSource(TearFirstDelivery(inner), "t", "g", auto_commit=False)
    dlq = DeadLetterQueue(inner, "dead")
    tap = WindowCommitTap(src, 10_000, 5_000, parse=json.loads, dlq=dlq)
    with pytest.raises(ControlTupleExit):
        list(tap)
    assert len(dlq) == 0, "healed control tuple must not be quarantined"


def test_torn_control_tuple_in_chunk_flushes_parsed_prefix():
    """Bulk-decode path: when a torn STOP tuple heals mid-chunk, the
    records buffered BEFORE it must still reach the pipeline before the
    stop propagates (the intact-control path's contract)."""
    from dataclasses import replace as _replace

    from spatialflink_tpu.streams import WindowCommitTap
    from spatialflink_tpu.utils.metrics import ControlTupleExit

    inner = InMemoryBroker()
    for i in range(3):
        inner.produce("t", json.dumps({"v": i, "timestamp": 1000 + i}))
    inner.produce("t", json.dumps(
        {"geometry": {"type": "control", "coordinates": []}}))

    class TearFirstDelivery:
        def __init__(self, b):
            self.b = b
            self.seen = set()

        def fetch(self, topic, offset, max_records=500):
            out = []
            for r in self.b.fetch(topic, offset, max_records):
                if r.offset not in self.seen:
                    self.seen.add(r.offset)
                    r = _replace(r, value=r.value[:5] + "\x00TORN")
                out.append(r)
            return out

        def __getattr__(self, name):
            return getattr(self.b, name)

    def broken_bulk(raws):
        raise ValueError("chunk not bulk-decodable")

    src = KafkaSource(TearFirstDelivery(inner), "t", "g", auto_commit=False)
    tap = WindowCommitTap(src, 10_000, 5_000, parse=json.loads,
                          bulk_decode=broken_bulk,
                          dlq=DeadLetterQueue(inner, "dead"))
    got = []
    with pytest.raises(ControlTupleExit):
        for obj in tap:
            got.append(obj)
    assert [o["v"] for o in got] == [0, 1, 2], \
        "records before the stop tuple were dropped"
    assert inner.end_offset("dead") == 0


def test_dlq_quarantine_metadata_and_compactable_keys():
    broker = InMemoryBroker()
    dlq = DeadLetterQueue(broker, "dead", raw_limit=8)
    try:
        json.loads("{broken")
    except ValueError as e:
        dlq.quarantine(source_topic="in", offset=42,
                       raw="{broken-and-long-payload", error=e, attempts=5)
    assert len(dlq) == 1
    (e,) = dlq.entries()
    assert (e["topic"], e["offset"], e["attempts"]) == ("in", 42, 5)
    assert e["error_type"] == "JSONDecodeError"
    assert e["raw"] == "{broken-"  # truncated to raw_limit
    rec = broker.fetch("dead", 0, 10)[0]
    assert rec.key == f"{DeadLetterQueue.KEY_PREFIX}in:42"


def test_degradation_counters_surface_in_summary(tmp_path, capsys):
    """The driver's kafka summary line reports the degradation digest."""
    lines = _lines()
    cfg, url = _conf(tmp_path, "summary", "c.yml")
    broker = resolve_broker(url)
    for ln in lines:
        broker.produce(IN1, ln)
    assert main(["--config", cfg, "--kafka", "--option", "1",
                 "--chaos", "seed=9,fetch_fail=0.3",
                 "--retry", RETRY]) == 0
    err = capsys.readouterr().err
    assert "degraded:" in err
    assert "chaos-fetch-fail=" in err
    assert "retry-attempts=" in err
