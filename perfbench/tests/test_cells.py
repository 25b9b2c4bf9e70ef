"""Every cell rehearsed on the CPU at the small scale, through the whole
harness: the served path, the window, the metrics and the comparison. Then
the comparison is shown to fail: for the control (the reference in
bfloat16) and for faults planted under the timed path."""

import copy
import json

import pytest

import drive
import run as bench
from spatialflink_tpu.streams.kafka import BrokerRecord, InMemoryBroker

CELLS = ["tdrive-knn-window-drain", "tdrive-join-window-drain"]
E2E = {"tdrive-knn-window-drain": {"events_per_s", "setup_s"},
       "tdrive-join-window-drain": {"events_per_s", "setup_s"}}
STAGES = {"decode_ms_per_window.drain", "assembly_ms_per_window.drain",
          "dispatch_ms_per_window.drain", "unattributed_share.drain"}
LAYER = {"tdrive-knn-window-drain": {"device_idle_share.drain",
                                     "fetch_share.drain",
                                     "readback_ms_per_window.drain"} | STAGES,
         "tdrive-join-window-drain": {"device_idle_share.drain",
                                      "fetch_share.drain",
                                      "extract_ms_per_window.drain"} | STAGES}
SEED = 2_147_483_999


def run_cell(capsys, cell, *extra, trace=0, seconds=2):
    rc = bench.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                     str(seconds), "--trace", str(trace), "--small", *extra])
    out, err = capsys.readouterr()
    return rc, out, err


def result(out):
    return json.loads(out.strip().splitlines()[-1])


def aux(err):
    """The run's auxiliary line on standard error."""
    return json.loads(next(ln for ln in err.splitlines()
                           if ln.startswith('{"setup_parts_s"')))


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(capsys, cell, trace):
    rc, out, err = run_cell(capsys, cell, trace=trace)
    assert rc == 0, err[-3000:]
    res = result(out)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert set(res["metrics"]) == (LAYER if trace else E2E)[cell]
    assert list(res)[-1] == "compared"
    assert err.strip().splitlines()[-1] == "correct True"
    assert aux(err)["closed_by"] == "seconds"
    if trace:
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
        assert res["breakdown"]["device_ops"]


def cut_backlog(monkeypatch, rate_hz, max_rate_hz):
    """The rehearsal at ``rate_hz`` of event time with a backlog of
    ``max_rate_hz`` x seconds past the warm-up: small enough for the
    program to drain it inside the window, with one slide
    (``rate_hz`` x 5 s) many polls of the window's monitor long."""
    shrink = bench.shrink

    def cut(conf, traffic):
        shrink(conf, traffic)
        conf["stream_rate_hz"] = rate_hz
        traffic["max_rate_hz"] = max_rate_hz

    monkeypatch.setattr(bench, "shrink", cut)


def test_window_closes_at_the_backlog(capsys, monkeypatch):
    cut_backlog(monkeypatch, 20_000, 300_000)
    rc, out, err = run_cell(capsys, CELLS[0])
    assert rc == 0, err[-3000:]
    res = result(out)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] >= 2
    assert res["metrics"]["events_per_s"]["value"] > 0
    a = aux(err)
    assert a["closed_by"] == "backlog"
    assert a["window_wall_s"] < 2
    assert 0 < a["backlog_at_close"]["taxis"] < 20_000 * 5


def plant_lost_stream(monkeypatch, at=200_000):
    """The taxi stream ends at offset ``at``, far short of the backlog's
    end: the program's consumer gets the control tuple there."""
    fetch = InMemoryBroker.fetch

    def lost(self, topic, offset, max_records=500):
        if topic == "taxis" and offset >= at:
            return [BrokerRecord(offset=offset, key=None, value=drive.CONTROL)]
        return fetch(self, topic, offset, max_records)

    monkeypatch.setattr(InMemoryBroker, "fetch", lost)


def test_early_end_with_backlog_left_fails(capsys, monkeypatch):
    plant_lost_stream(monkeypatch)
    with pytest.raises(RuntimeError,
                       match="ended inside the window with [0-9]+ records"):
        run_cell(capsys, CELLS[0])
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails(capsys, cell):
    rc, out, err = run_cell(capsys, cell, "--control")
    assert rc == 0
    assert result(out)["correct"] is True
    line = next(ln for ln in err.splitlines()
                if ln.startswith('{"control_correct"'))
    assert json.loads(line)["control_correct"] is False


def alter(value):
    """An answer altered where it is produced: a point moved 0.01 deg, a
    kNN distance off by 0.01."""
    if isinstance(value, tuple) and len(value) == 2:
        a, b = value
        if isinstance(b, float):
            return (a, b + 0.01)
        a = copy.copy(a)
        a.x = a.x + 0.01
        return (a, b)
    return value


def plant_altered_answers(monkeypatch):
    produce, many = InMemoryBroker.produce, InMemoryBroker.produce_many

    def produce_altered(self, topic, value, key=None, timestamp_ms=None):
        if topic == "output":
            value = alter(value)
        return produce(self, topic, value, key, timestamp_ms)

    def many_altered(self, topic, values, key=None):
        if topic == "output":
            values = [alter(v) for v in values]
        return many(self, topic, values, key)

    monkeypatch.setattr(InMemoryBroker, "produce", produce_altered)
    monkeypatch.setattr(InMemoryBroker, "produce_many", many_altered)


def plant_half_the_batch(monkeypatch):
    """Half of every fetched batch of input records never reaches the
    operators."""
    fetch = InMemoryBroker.fetch

    def half(self, topic, offset, max_records=500):
        out = fetch(self, topic, offset, max_records)
        if topic == "output":
            return out
        return [r for r in out if r.offset % 2 == 0] or out[:1]

    monkeypatch.setattr(InMemoryBroker, "fetch", half)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [plant_altered_answers,
                                   plant_half_the_batch])
def test_fault_is_not_correct(capsys, monkeypatch, cell, fault):
    fault(monkeypatch)
    rc, out, err = run_cell(capsys, cell)
    assert rc == 0, err[-3000:]
    assert result(out)["correct"] is False
    assert err.strip().splitlines()[-1] == "correct False"


def test_no_tpu_no_result(capsys):
    rc = bench.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                     "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 2 and out == ""
    assert "no TPU" in err
