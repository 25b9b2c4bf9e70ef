"""The examples/ scripts are part of the user-facing surface — run each as
a real subprocess (CPU platform, virtual mesh for the distributed demo) and
assert the banner output they promise."""

import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str) -> str:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "examples", script)],
        capture_output=True, text=True, timeout=480, env=env, cwd=_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout


@pytest.mark.parametrize("script,expect", [
    ("streaming_range_query.py", "delivered windows:"),
    ("distributed_knn.py", "matches single-device bit-for-bit"),
    ("checkpoint_resume.py", "matches uninterrupted run"),
    ("multi_query_hotspots.py", "standing queries x"),
    ("live_kafka_stream.py", "live latency p50="),
])
def test_example_runs(script, expect):
    out = _run(script)
    assert expect in out, out[-2000:]
