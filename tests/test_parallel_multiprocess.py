"""Execute the DCN branch: a real two-process jax.distributed run.

``make_mesh_2d``'s multi-process branch (``create_hybrid_device_mesh``) and
the ICI->DCN hierarchical kNN merge only mean anything across processes;
this test spawns two coordinator-connected CPU processes (2 virtual devices
each) and checks the merged result against the single-device oracle in both.
"""

import os
import socket
import subprocess
import sys


_WORKER = os.path.join(os.path.dirname(__file__), "dcn_worker.py")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def test_two_process_hierarchical_knn():
    port = _free_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO
    env.pop("XLA_FLAGS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, str(i), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            env=env, cwd=_REPO, text=True)
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=150)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {i} failed:\n{out[-3000:]}"
        assert f"DCN_OK {i}" in out, f"process {i} missing DCN_OK:\n{out[-3000:]}"
        assert f"DCN_MULTI_OK {i}" in out, \
            f"process {i} missing DCN_MULTI_OK:\n{out[-3000:]}"
