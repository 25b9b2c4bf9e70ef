"""chip_smoke.py's phases, in-process on the CPU at a small scale: every
served-path query through ``driver.main`` against the smoke's NumPy oracle,
the mesh path against one device, and the refusal without a chip."""

import json

import pytest

import chip_smoke as cs

# 25 s of stream: four full 10 s / 5 s windows
SCALE = cs.Scale(rate_hz=1_000, seconds=25, join_per_window=64)


@pytest.fixture(scope="module")
def smoke_env(tmp_path_factory):
    workdir = str(tmp_path_factory.mktemp("chip_smoke"))
    return (cs.make_streams(SCALE, seed=3, workdir=workdir), workdir,
            cs.CompileCacheEvents())


@pytest.mark.parametrize("phase", cs.PHASES, ids=lambda p: p.name)
def test_phase_matches_oracle(smoke_env, phase):
    streams, workdir, cache = smoke_env
    rec = cs.run_driver(phase, streams, workdir, cache)
    verdict = cs.check_phase(rec, streams, SCALE)
    assert len(verdict) == 2
    assert all(v["records"] >= v["oracle"] - v["band"] for v in
               verdict.values())
    assert rec["windows"] >= 4 and rec["records"] > 0


@pytest.mark.parametrize("phase", cs.MESH_PHASES, ids=lambda p: p.name)
def test_mesh_phase_matches_one_device(smoke_env, phase):
    streams, workdir, cache = smoke_env
    single = cs.run_driver(phase, streams, workdir, cache)
    mesh = cs.run_driver(phase, streams, workdir, cache, devices=4)
    assert cs.check_same_windows(mesh, single)["windows_equal"] >= 4


def test_oracle_rejects_a_wrong_answer(smoke_env):
    """The comparison is not vacuous: the range output checked against a
    smaller radius fails."""
    streams, workdir, cache = smoke_env
    rec = cs.run_driver(cs.PHASES[0], streams, workdir, cache)
    rec["radius"] = 0.45
    with pytest.raises(cs.SmokeFailure, match="extra"):
        cs.check_phase(rec, streams, SCALE)


def test_cpu_rehearsal_prints_the_device_line(monkeypatch, capsys):
    monkeypatch.setattr(cs, "SMALL", SCALE)
    assert cs.main(["--small"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is True and last["device"]["platform"] == "cpu"


@pytest.mark.parametrize("argv,platforms", [([], "cpu"), (["--small"], "")],
                         ids=["no-small-flag", "no-explicit-cpu"])
def test_refuses_without_a_chip(monkeypatch, capsys, argv, platforms):
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    assert cs.main(argv) == 2
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "found no TPU" in out.err
