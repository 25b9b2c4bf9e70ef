"""The profiler trace of the window and its reduction to device busy time,
kernel time and host spans, all on the trace's own clock.

``Tracer`` starts JAX's profiler when the window opens and stops it when
the window closes; two host annotations (``perfbench.open`` and
``perfbench.close``) mark the window's edges inside the trace. ``reduce``
reads the ``.xplane.pb`` file with ``jax.profiler.ProfileData``:

- device operations are the events of each ``/device:*`` plane's
  ``XLA Ops`` line; on the CPU backend, which has no device plane, the
  host-thread events that carry an ``hlo_op`` stat stand in for them;
- each operation's program is its ``hlo_module`` stat, or else the
  ``XLA Modules`` event around it;
- host spans are every other host event, the program's telemetry spans
  (``kafka.fetch``, ``sink``, ``<query>.merge`` ...) among them.
"""

from __future__ import annotations

import glob
import os
import shutil

import numpy as np

OPEN, CLOSE = "perfbench.open", "perfbench.close"


class Tracer:
    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def start(self) -> None:
        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # a call per Python function: off
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(OPEN):
            pass

    def stop(self) -> None:
        import jax

        with jax.profiler.TraceAnnotation(CLOSE):
            pass
        jax.profiler.stop_trace()

    def reduce(self) -> "Trace":
        files = glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one xplane file, found {files}")
        return Trace.from_file(files[0])


def union(iv: np.ndarray) -> np.ndarray:
    """Merge (n, 2) [start, end) intervals into disjoint sorted ones."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


class Trace:
    """Events of one trace, in seconds on the trace's clock.

    ``ops``: {device name: [(start, end, op name, program name)]};
    ``spans``: [(start, end, name)] of the host; ``window``: (open, close).
    """

    def __init__(self, ops: dict, spans: list, window, mods=None):
        self.ops, self.spans, self.window = ops, spans, window
        #: {device: [(start, end, program)]} from ``XLA Modules`` lines
        self.mods = mods or {}

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        return cls.from_planes(ProfileData.from_file(path).planes)

    @classmethod
    def from_planes(cls, planes) -> "Trace":
        ops: dict = {}
        mods_by_dev: dict = {}
        spans: list = []
        marks: dict = {}
        host_ops: list = []
        for plane in planes:
            if plane.name.startswith("/device:"):
                lines = {ln.name: ln for ln in plane.lines}
                mods = sorted(
                    (e.start_ns, e.start_ns + e.duration_ns, _module(e.name))
                    for e in (lines["XLA Modules"].events
                              if "XLA Modules" in lines else ()))
                starts = np.array([m[0] for m in mods])
                out = []
                for e in (lines["XLA Ops"].events if "XLA Ops" in lines
                          else ()):
                    mod = _stat(e, "hlo_module")
                    if mod is None and len(mods):
                        i = int(np.searchsorted(starts, e.start_ns, "right")) - 1
                        if i >= 0 and e.start_ns < mods[i][1]:
                            mod = mods[i][2]
                    # TPU op events are named by their whole HLO text
                    out.append((e.start_ns / 1e9,
                                (e.start_ns + e.duration_ns) / 1e9,
                                e.name.split(" = ", 1)[0].lstrip("%"),
                                mod or "?"))
                if out:
                    ops[plane.name] = out
                    mods_by_dev[plane.name] = [(a / 1e9, b / 1e9, m)
                                               for a, b, m in mods]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        t0, t1 = e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9
                        if e.name in (OPEN, CLOSE):
                            marks[e.name] = t0
                            continue
                        op = _stat(e, "hlo_op")
                        if op is not None:
                            host_ops.append((t0, t1, op,
                                             _stat(e, "hlo_module") or "?"))
                        elif not e.name.startswith(("$", "ThreadpoolListener")):
                            spans.append((t0, t1, e.name))
        if not ops and host_ops:
            ops["/host:CPU"] = host_ops
        if OPEN not in marks or CLOSE not in marks:
            raise RuntimeError("the trace holds no window marks")
        return cls(ops, spans, (marks[OPEN], marks[CLOSE]), mods_by_dev)

    # ------------------------------------------------------------ reads

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        return float(np.mean([self._busy(d).sum() for d in self.ops]))

    def _busy(self, dev) -> np.ndarray:
        iv = np.array([(s, e) for s, e, _n, _m in self.ops[dev]], np.float64)
        u = clip(union(iv.reshape(-1, 2)), *self.window)
        return u[:, 1] - u[:, 0]

    def program_s(self, match: str) -> tuple:
        """(seconds, executions) of the device programs whose name holds
        ``match``, summed over devices, within the window."""
        lo, hi = self.window
        total, runs = 0.0, 0
        for dev, ops in self.ops.items():
            mods = [(s, e) for s, e, m in self.mods.get(dev, ()) if match in m]
            if not mods:   # no XLA Modules line: runs of the program's ops
                mods = _runs(ops, match)
            iv = [(s, e) for s, e, _n, m in ops if match in m]
            if iv:
                u = clip(union(np.array(iv, np.float64)), lo, hi)
                total += float((u[:, 1] - u[:, 0]).sum())
            runs += sum(lo <= s < hi for s, _e in mods)
        return total, runs

    def span_union_s(self, names) -> float:
        iv = [(s, e) for s, e, n in self.spans if n in names]
        if not iv:
            return 0.0
        u = clip(union(np.array(iv, np.float64)), *self.window)
        return float((u[:, 1] - u[:, 0]).sum())

    def span_sum_s(self, suffix: str) -> tuple:
        """(seconds, count) of host spans whose name ends with ``suffix``
        that lie in the window."""
        lo, hi = self.window
        d = [e - s for s, e, n in self.spans
             if n.endswith(suffix) and lo <= s and e <= hi]
        return float(sum(d)), len(d)

    def top_ops(self, k: int = 10) -> list:
        tot: dict = {}
        lo, hi = self.window
        for ops in self.ops.values():
            for s, e, n, m in ops:
                d = min(e, hi) - max(s, lo)
                if d > 0:
                    key = f"{m}:{n}"
                    tot[key] = tot.get(key, 0.0) + d / len(self.ops)
        return sorted(([n, v] for n, v in tot.items()),
                      key=lambda x: -x[1])[:k]

    def idle_gaps(self, k: int = 10) -> list:
        """The longest stretches of the window in which the first device ran
        nothing, each named by the host span that covered most of it and
        the share of the gap that span covers: ``knn.window (97.3%)``."""
        if not self.ops:
            return [["no device operations", self.window_s]]
        dev = sorted(self.ops)[0]
        iv = np.array([(s, e) for s, e, _n, _m in self.ops[dev]], np.float64)
        u = clip(union(iv.reshape(-1, 2)), *self.window)
        edges = np.concatenate([[self.window[0]], u.reshape(-1),
                                [self.window[1]]]).reshape(-1, 2)
        gaps = sorted(((b - a, a, b) for a, b in edges if b > a),
                      reverse=True)[:k]
        return [[self._cover(a, b), g] for g, a, b in gaps]

    def _cover(self, a: float, b: float) -> str:
        best, key = None, (0.0, 0.0)
        for s, e, n in self.spans:
            ov = min(e, b) - max(s, a)
            if ov > 0 and (ov, -(e - s)) > key:
                best, key = n, (ov, -(e - s))
        if best is None:
            return "none"
        return f"{best} ({100 * key[0] / (b - a):.1f}%)"


def _runs(ops, match: str):
    """One (start, end) per execution of a matching program: consecutive
    operations of the same program with no other program between."""
    out, cur = [], None
    for s, e, _n, m in sorted(ops):
        if match in m:
            if cur is None:
                cur = [s, e, m]
            else:
                cur[1] = e
        elif cur is not None:
            out.append((cur[0], cur[1]))
            cur = None
    if cur is not None:
        out.append((cur[0], cur[1]))
    return out


def _stat(event, name):
    for k, v in event.stats:
        if k == name:
            return v
    return None


def _module(name: str) -> str:
    """``jit_knn_point_stats(123)`` -> ``jit_knn_point_stats``."""
    return name.split("(", 1)[0]
