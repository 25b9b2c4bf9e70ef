"""Shared benchmark-harness plumbing.

Importers reach this as ``benchmarks._common``, which requires the repo root
on sys.path (each harness script inserts it before importing)."""

from __future__ import annotations

import sys
from typing import Optional


def bench_telemetry():
    """An in-memory telemetry session (no reporter thread) for harness
    scripts: ``with bench_telemetry() as tel: ...; attach_telemetry(row,
    tel)``. Spans recorded by the pipeline under test (ingest / window /
    kernel / merge / sink) land in the session automatically."""
    from spatialflink_tpu.utils.telemetry import telemetry_session

    return telemetry_session()


def attach_telemetry(row: dict, tel) -> dict:
    """Attach the final telemetry snapshot to a bench result row, so
    BENCH_*/RESULTS_* files carry per-stage breakdowns next to the
    end-to-end numbers."""
    row["telemetry"] = tel.snapshot()
    return row


def fleet_refusal(path: str) -> Optional[dict]:
    """None on the CPU; elsewhere a refusal row (and a message on stderr).

    Fleet rows start ``driver`` workers as child processes, while this
    harness process has already initialised JAX and holds the chip. A chip
    belongs to one process, so on an accelerator host each worker would
    need its own chip: the row refuses instead of hanging."""
    import jax

    backend = jax.default_backend()
    if backend == "cpu":
        return None
    msg = (f"{path}: refused on {backend}: fleet workers are separate "
           "processes and this harness already holds the chip (one process "
           "per chip); run the fleet rows with JAX_PLATFORMS=cpu")
    print(msg, file=sys.stderr)
    return {"path": path, "refused": msg}
