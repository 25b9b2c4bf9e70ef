"""The program's stage spans in a reduced trace (``devtrace.Trace``), read
by the stage metrics (``metrics/*_per_window.drain.py``,
``metrics/unattributed_share.drain.py``).

The served broker path's spans nest on the pipeline thread: ``<q>.window``
(one pull of the next window) holds the broker fetch, the poll, the decode
and the materialisation of that window's records, and on the join also the
window's dispatch and pair extraction. A window span's self time -- its
duration less the union of the program spans inside it -- is window
assembly. Only the part of a span inside the traced window counts.

The profiler records a span only if it opens and closes while the trace
runs: the pulls in progress when the window opens and when it closes are
missing (their nested spans, which open later and close sooner, are
there). So assembly is read per recorded pull, and the unattributed share
over the stretch from the first recorded pull's start to the last one's
end, in which every span is whole.
"""

from __future__ import annotations

import numpy as np

from devtrace import clip, union

DECODE = ("kafka.poll", "kafka.decode", "decode", "decode.materialize")
EXTRACT = ("join.reduce", "join.compact", "join.lattice", "join.pairs")
#: every span the program opens on the served path, by name or suffix
NAMES = ("kafka.fetch", "sink", "kafka.sink") + DECODE + EXTRACT
SUFFIXES = (".window", ".dispatch", ".merge")


def is_program(name: str) -> bool:
    return name in NAMES or name.endswith(SUFFIXES)


def intervals(trace, pick) -> np.ndarray:
    """(n, 2) intervals of the spans whose name ``pick`` accepts, clipped to
    the traced window."""
    iv = np.array([(s, e) for s, e, n in trace.spans if pick(n)],
                  np.float64).reshape(-1, 2)
    return clip(iv, *trace.window)


def covered(iv: np.ndarray) -> float:
    u = union(iv)
    return float((u[:, 1] - u[:, 0]).sum())


def union_s(trace, names) -> float | None:
    """Seconds of the window inside any span named in ``names``; None when
    there is no such span."""
    iv = intervals(trace, lambda n: n in names)
    return covered(iv) if len(iv) else None


def sum_s(trace, suffix: str) -> float | None:
    iv = intervals(trace, lambda n: n.endswith(suffix))
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else None


def pulls(trace) -> np.ndarray:
    """The recorded ``<q>.window`` spans: one per pull of the next window."""
    return intervals(trace, lambda n: n.endswith(".window"))


def self_s(trace) -> float | None:
    """Summed self time of the window pulls: each one's duration less the
    union of the other program spans inside it."""
    parents = pulls(trace)
    if not len(parents):
        return None
    kids = intervals(trace, lambda n: is_program(n)
                     and not n.endswith(".window"))
    total = 0.0
    for s, e in parents:
        total += (e - s) - covered(clip(kids, s, e))
    return total


def stretch(trace) -> tuple | None:
    """(first recorded pull's start, last recorded pull's end)."""
    p = pulls(trace)
    return (float(p[:, 0].min()), float(p[:, 1].max())) if len(p) else None


def unattributed_s(trace, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` in which the first device ran nothing and no
    program span was open."""
    busy = []
    if trace.ops:
        dev = sorted(trace.ops)[0]
        busy = [(s, e) for s, e, _n, _m in trace.ops[dev]]
    busy = clip(np.array(busy, np.float64).reshape(-1, 2), lo, hi)
    spans = clip(intervals(trace, is_program), lo, hi)
    return (hi - lo) - covered(np.concatenate([busy, spans]))


def per_window_ms(ctx, seconds: float | None) -> float | None:
    windows = len(ctx.markers_in_window())
    if seconds is None or not windows:
        return None
    return 1e3 * seconds / windows
