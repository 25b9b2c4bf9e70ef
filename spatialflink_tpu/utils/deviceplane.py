"""Device-truth observability plane: compile registry, recompile sentinel,
device-resource telemetry, and the post-mortem flight recorder.

The host side of the pipeline is well lit (spans, live opserver, cost
attribution — PRs 2/5/6) but the device/XLA layer was dark: the
zero-recompile contracts of the adaptive grid (PR 8) and the query plane
(PR 9) existed only as test-time asserts, and a silent CPU fallback
(a round-5 bench row) was discovered only by reading a ledger tail. This
module makes the device layer first-class:

- :func:`instrumented_jit` — a drop-in ``jax.jit`` replacement every kernel
  entry point in ``ops/*`` uses. It registers the function in the process's
  :class:`CompileRegistry` and hooks the TRACE: jax only executes the
  wrapped Python body on a cache miss (a fresh compile), so steady-state
  dispatch goes through the exact ``jax.jit`` fast path — zero per-call
  overhead, the instrumentation costs only when XLA is already spending
  hundreds of milliseconds compiling. Each trace records the trigger
  signature (abstract shapes/dtypes + static argument values), the trace
  wall-time, and (via a ``jax.monitoring`` listener) the backend compile
  wall-time; ``cost_analysis()`` FLOPs/bytes are computed lazily per entry
  on first request (an AOT lower+compile — one-time, never on a hot path).

- the **recompile sentinel** — ``registry().begin_run(strict)`` +
  ``mark_warm(reason)``: after the declared warmup, ANY fresh compile
  becomes a ``recompile`` lifecycle event (when a telemetry session is
  active), bumps the always-on ``device-recompiles`` counter, and — under
  ``--strict-recompile`` — raises :class:`RecompileError`, aborting the
  run. This promotes the PR 8/9 test-only zero-recompile contracts into an
  always-on production invariant, visible at ``GET /compile``.

- device-resource telemetry — :func:`backend_provenance` (platform, device
  kind, chip count, ``valid_for_target``), :func:`device_memory` (per-device
  live/peak HBM via ``Device.memory_stats()``; explicitly unavailable on
  CPU), and :func:`status_block`, the compact ``device`` stanza stamped
  into every status snapshot, stderr digest, and bench row. Host↔device
  transfer unifies with the existing accounting: the d2h side reads the
  always-on pane-readback byte counters, the h2d side the per-family
  ``CostProfiles.bytes_moved`` estimates.

- :class:`FlightRecorder` — a bounded always-on ring of run lifecycle notes
  that, on crash, SLO breach, strict-recompile abort, or SIGUSR1, dumps a
  post-mortem bundle directory (status snapshot, event ring, compile
  registry, recent window traces, device memory profile, config
  fingerprint) readable by ``python -m spatialflink_tpu.doctor``.

Gating contract: the registry's trace hook fires ONLY at compile time
(never on a cache-hit dispatch), memory probes run only on demand
(snapshot/request/dump — never per record), and the flight recorder exists
only under ``--postmortem-dir`` (which activates a telemetry session) — the
observability-off hot path stays byte-identical, extended-spy-tested in
``tests/test_deviceplane.py``.
"""

from __future__ import annotations

import functools
import json
import os
import signal
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from spatialflink_tpu.utils import metrics as _metrics

#: bundle layout version (doctor refuses bundles it cannot read).
#: 2: + latency.json — the stage-residency decomposition, record→emit
#: histograms (global + per query) and the backpressure time series, so a
#: breach bundle answers "which stage blew the budget" offline
#: 3: + tenants.json — the per-tenant cost ledger (attributed kernel-ms/
#: bytes, fairness summary, quota counters), so a breach bundle answers
#: "who was paying for the pipeline when it died"
BUNDLE_SCHEMA = 3


class RecompileError(Exception):
    """A post-warmup XLA compile under ``--strict-recompile``.

    Deliberately NOT a RuntimeError: the elastic mesh degradation path
    (``operators.base._eval_degradable``) absorbs RuntimeErrors as device
    failures, and a contract violation must abort, not degrade."""


# --------------------------------------------------------------------- #
# signature capture (at trace time the dynamic args are tracers — their
# avals are exactly the compile-cache trigger; statics are concrete)


def _sig_leaf(x) -> str:
    aval = getattr(x, "aval", None)
    if aval is not None and hasattr(aval, "shape"):
        return f"{aval.dtype}[{'x'.join(str(d) for d in aval.shape)}]"
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return f"{dtype}[{'x'.join(str(d) for d in shape)}]"
    r = repr(x)
    return r if len(r) <= 48 else r[:45] + "..."


def _lower_leaf(x):
    """Tracer -> ShapeDtypeStruct (the lazy cost-analysis lowering re-feeds
    these to ``jitted.lower``); everything else passes through concrete."""
    import jax

    aval = getattr(x, "aval", None)
    if aval is not None and hasattr(aval, "shape") and hasattr(aval, "dtype"):
        return jax.ShapeDtypeStruct(aval.shape, aval.dtype)
    return x


def _signature(args, kwargs) -> str:
    import jax

    parts = [_sig_leaf(leaf) for leaf in
             jax.tree_util.tree_leaves(args)]
    for k in sorted(kwargs):
        for leaf in jax.tree_util.tree_leaves(kwargs[k]):
            parts.append(f"{k}={_sig_leaf(leaf)}")
    return "(" + ", ".join(parts) + ")"


class CompileEntry:
    """One instrumented jit entry point's compile history."""

    __slots__ = ("name", "module", "jit_kwargs", "compiles", "recompiles",
                 "trace_ms", "backend_compile_ms", "signatures",
                 "first_compile_ms", "last_compile_ms", "_jitted",
                 "_lower_call", "_cost", "_cost_error")

    def __init__(self, name: str, module: str, jit_kwargs: dict):
        self.name = name
        self.module = module
        self.jit_kwargs = {k: repr(v) for k, v in sorted(jit_kwargs.items())}
        self.compiles = 0
        self.recompiles = 0          # post-warmup compiles, cumulative
        self.trace_ms = 0.0          # Python trace time (body execution)
        self.backend_compile_ms = 0.0  # attributed XLA backend compile time
        self.signatures: deque = deque(maxlen=8)
        self.first_compile_ms: Optional[int] = None
        self.last_compile_ms: Optional[int] = None
        self._jitted = None
        self._lower_call = None      # (args, kwargs) with ShapeDtypeStructs
        self._cost: Optional[dict] = None
        self._cost_error: Optional[str] = None

    @property
    def qualname(self) -> str:
        return f"{self.module}.{self.name}"

    def cache_size(self) -> Optional[int]:
        try:
            return int(self._jitted._cache_size())
        except Exception:
            return None

    def cost_analysis(self) -> Optional[dict]:
        """Lazy one-time ``cost_analysis()`` for the LAST-compiled
        signature: an AOT ``lower(...).compile()`` from the captured
        abstract shapes — a real (cached-per-entry) compile, so this runs
        only on explicit request (``/compile?cost=1``, doctor, bundle
        dump), never on a hot path."""
        if self._cost is not None or self._cost_error is not None:
            return self._cost
        if self._jitted is None or self._lower_call is None:
            self._cost_error = "never compiled"
            return None
        try:
            import warnings

            largs, lkwargs = self._lower_call
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                compiled = self._jitted.lower(*largs, **lkwargs).compile()
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            keep = {k: float(v) for k, v in (ca or {}).items()
                    if k in ("flops", "bytes accessed", "transcendentals",
                             "optimal_seconds")
                    and isinstance(v, (int, float))}
            self._cost = {"flops": keep.get("flops"),
                          "bytes_accessed": keep.get("bytes accessed"),
                          "transcendentals": keep.get("transcendentals")}
        except Exception as e:  # never let analysis kill an observer
            self._cost_error = f"{type(e).__name__}: {e}"
            return None
        return self._cost

    def to_dict(self, cost: bool = False) -> dict:
        out = {
            "name": self.name,
            "module": self.module,
            "jit_kwargs": dict(self.jit_kwargs),
            "compiles": self.compiles,
            "recompiles": self.recompiles,
            "trace_ms": round(self.trace_ms, 3),
            "backend_compile_ms": round(self.backend_compile_ms, 3),
            "cache_size": self.cache_size(),
            "first_compile_ms": self.first_compile_ms,
            "last_compile_ms": self.last_compile_ms,
            "signatures": list(self.signatures),
        }
        if cost:
            out["cost_analysis"] = self.cost_analysis()
            if self._cost_error is not None:
                out["cost_analysis_error"] = self._cost_error
        return out


class _TLS(threading.local):
    pending_entry: Optional[CompileEntry] = None


_tls = _TLS()
_MONITOR_INSTALLED = False


def _install_monitor() -> None:
    """One process-wide ``jax.monitoring`` duration listener attributing
    backend-compile wall time to the entry whose trace most recently ran on
    this thread (the events fire inside the same dispatch, after the traced
    body returns). Attribution, not measurement — an uninstrumented jit
    compiling between an instrumented trace and its backend compile would
    mis-attribute; in this codebase every kernel entry point is
    instrumented, so the window is negligible."""
    global _MONITOR_INSTALLED
    if _MONITOR_INSTALLED:
        return
    _MONITOR_INSTALLED = True
    try:
        import jax.monitoring as _mon

        def listener(name, dur, **_kw):
            if name.endswith("backend_compile_duration"):
                entry = _tls.pending_entry
                if entry is not None:
                    entry.backend_compile_ms += dur * 1e3
                    _tls.pending_entry = None

        _mon.register_event_duration_secs_listener(listener)
    except Exception:
        pass  # monitoring API absent: backend_compile_ms stays 0


class CompileRegistry:
    """Process-global ledger of every instrumented jit entry point plus the
    recompile-sentinel state. Hot-path contract: the only mutation path is
    :meth:`_on_traced`, which jax invokes exclusively at trace (= compile)
    time — a warmed pipeline never enters it."""

    def __init__(self):
        self.entries: Dict[str, CompileEntry] = {}
        self._lock = threading.Lock()
        #: sentinel state (driver begin_run/mark_warm/end_run; tests and
        #: bench harnesses drive the same API)
        self.warm = False
        self.warm_reason: Optional[str] = None
        self.warm_at_ms: Optional[int] = None
        self.strict = False
        #: compiles since begin_run() / since begin_run's mark_warm()
        self.run_compiles = 0
        self.run_recompiles = 0
        self.total_compiles = 0

    # ------------------------------ feeding --------------------------- #

    def register(self, fun, jit_kwargs: dict) -> CompileEntry:
        name = getattr(fun, "__qualname__", getattr(fun, "__name__", "?"))
        module = getattr(fun, "__module__", "?")
        entry = CompileEntry(name, module, jit_kwargs)
        with self._lock:
            self.entries[f"{module}.{name}"] = entry
        _install_monitor()
        return entry

    def _on_traced(self, entry: CompileEntry, args, kwargs,
                   dt_s: float) -> None:
        """One fresh trace (= one XLA compile) of ``entry``. Runs only at
        compile time; the sentinel turns it into a recompile event after
        warmup and aborts under strict mode."""
        now_ms = int(time.time() * 1000)
        sig = _signature(args, kwargs)
        import jax

        with self._lock:
            entry.compiles += 1
            entry.trace_ms += dt_s * 1e3
            entry.signatures.append({"ts_ms": now_ms, "signature": sig,
                                     "post_warmup": self.warm})
            if entry.first_compile_ms is None:
                entry.first_compile_ms = now_ms
            entry.last_compile_ms = now_ms
            entry._lower_call = jax.tree_util.tree_map(
                _lower_leaf, (args, kwargs))
            entry._cost = None  # fresh signature: re-analyze on demand
            entry._cost_error = None
            self.total_compiles += 1
            self.run_compiles += 1
            warm, strict = self.warm, self.strict
            if warm:
                entry.recompiles += 1
                self.run_recompiles += 1
        _metrics.REGISTRY.counter("device-compiles").inc()
        _tls.pending_entry = entry
        if warm:
            _metrics.REGISTRY.counter("device-recompiles").inc()
            from spatialflink_tpu.utils.telemetry import emit_event

            emit_event("recompile", fn=entry.qualname, signature=sig,
                       warm_reason=self.warm_reason, strict=strict)
            if strict:
                raise RecompileError(
                    f"fresh XLA compile of {entry.qualname}{sig} after "
                    f"declared warmup ({self.warm_reason!r}) under "
                    "--strict-recompile; the zero-recompile contract is "
                    "violated — see GET /compile for the trigger signature")

    # ------------------------------ sentinel -------------------------- #

    def begin_run(self, strict: bool = False) -> None:
        """Start a sentinel run: warmup re-opens, run counters reset."""
        with self._lock:
            self.warm = False
            self.warm_reason = None
            self.warm_at_ms = None
            self.strict = bool(strict)
            self.run_compiles = 0
            self.run_recompiles = 0

    def mark_warm(self, reason: str) -> None:
        """Declare warmup done: from here every fresh compile is a
        ``recompile`` event (and an abort under strict mode)."""
        with self._lock:
            if not self.warm:
                self.warm = True
                self.warm_reason = reason
                self.warm_at_ms = int(time.time() * 1000)
        from spatialflink_tpu.utils.telemetry import emit_event

        emit_event("sentinel-warm", reason=reason)

    def end_run(self) -> None:
        """Close the sentinel run (driver exit stack): warm/strict reset so
        a later in-process run (tests, notebooks) starts cold."""
        with self._lock:
            self.warm = False
            self.warm_reason = None
            self.strict = False

    # ------------------------------ reading --------------------------- #

    def snapshot(self, cost: bool = False) -> dict:
        """The full ``GET /compile`` document."""
        with self._lock:
            entries = list(self.entries.values())
            head = {
                "ts_ms": int(time.time() * 1000),
                "functions": len(entries),
                "total_compiles": self.total_compiles,
                "run_compiles": self.run_compiles,
                "post_warmup_compiles": self.run_recompiles,
                "warm": self.warm,
                "warm_reason": self.warm_reason,
                "warm_at_ms": self.warm_at_ms,
                "strict": self.strict,
            }
        head["entries"] = sorted((e.to_dict(cost=cost) for e in entries),
                                 key=lambda d: (-d["compiles"], d["name"]))
        return head


_REGISTRY = CompileRegistry()


def registry() -> CompileRegistry:
    """The process's compile registry (module-global, like
    ``metrics.REGISTRY``)."""
    return _REGISTRY


def instrumented_jit(fun=None, **jit_kwargs):
    """Drop-in ``jax.jit`` replacement that registers the function in the
    compile registry and meters every fresh compile.

    Usable exactly like ``jax.jit``: bare decorator, or with kwargs via
    ``partial(instrumented_jit, static_argnames=(...))`` /
    ``instrumented_jit(fn, donate_argnums=(0,))``. Returns the real
    ``jax.jit`` object (``.lower``/``._cache_size`` intact): on a cache hit
    the dispatch is the unmodified C++ fast path; the registry hook lives
    inside the traced body, which jax executes only when compiling."""
    if fun is None:
        return lambda f: instrumented_jit(f, **jit_kwargs)
    import jax

    entry = _REGISTRY.register(fun, jit_kwargs)

    @functools.wraps(fun)
    def traced(*args, **kwargs):
        t0 = time.perf_counter()
        out = fun(*args, **kwargs)
        # hook AFTER the body so a strict-mode abort cannot leave a
        # half-traced cache entry blamed on the wrong signature; dt covers
        # the Python trace (backend compile time arrives via monitoring)
        _REGISTRY._on_traced(entry, args, kwargs, time.perf_counter() - t0)
        return out

    jitted = jax.jit(traced, **jit_kwargs)
    entry._jitted = jitted
    return jitted


# --------------------------------------------------------------------- #
# device-resource telemetry


_PROVENANCE: Optional[dict] = None


def backend_provenance(target: str = "tpu") -> dict:
    """Backend identity stamped into snapshots, bench rows, and checkpoint
    manifests: platform, device kind, chip count, and the
    ``valid_for_target`` verdict (a run that landed on the CPU says so in
    a first-class field). Cached after the first call."""
    global _PROVENANCE
    if _PROVENANCE is None:
        import jax

        devs = jax.devices()
        _PROVENANCE = {
            "platform": jax.default_backend(),
            "device_kind": devs[0].device_kind if devs else None,
            "device_count": jax.device_count(),
            "local_device_count": jax.local_device_count(),
            "process_index": jax.process_index(),
            "jax_version": jax.__version__,
        }
    out = dict(_PROVENANCE)
    out["target"] = target
    out["valid_for_target"] = out["platform"] == target
    return out


def device_memory() -> List[dict]:
    """Per-device live/peak memory rows from ``Device.memory_stats()``.
    CPU devices report no stats — the row says so explicitly
    (``available: False``) instead of faking zeros."""
    import jax

    rows = []
    for d in jax.local_devices():
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            rows.append({"id": d.id, "kind": d.device_kind,
                         "available": False})
            continue
        rows.append({
            "id": d.id, "kind": d.device_kind, "available": True,
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
            "bytes_limit": int(stats.get("bytes_limit", 0)) or None,
        })
    return rows


def memory_gauges(rows: Optional[List[dict]] = None) -> dict:
    """Compact live/peak gauges over :func:`device_memory` rows: max
    bytes-in-use across devices (the health check's ``device_mem_bytes``
    value) and summed peak. None values when the backend exposes no
    stats."""
    rows = device_memory() if rows is None else rows
    avail = [r for r in rows if r.get("available")]
    if not avail:
        return {"available": False, "bytes_in_use": None,
                "peak_bytes_in_use": None}
    return {
        "available": True,
        "bytes_in_use": max(r["bytes_in_use"] for r in avail),
        "peak_bytes_in_use": sum(r["peak_bytes_in_use"] for r in avail),
    }


def _d2h_bytes(reg: Optional[_metrics.MetricsRegistry] = None) -> int:
    """Device→host bytes actually read back on the pane path — the
    always-on counters :class:`~spatialflink_tpu.operators.base.PanePartial`
    and the device pane merge maintain (the same numbers
    ``CostProfiles.bytes_moved`` folds in when a session is active)."""
    reg = reg if reg is not None else _metrics.REGISTRY
    return (reg.counter("pane-partial-readback-bytes").count
            + reg.counter("pane-merged-readback-bytes").count)


def status_block(tel=None, registry_=None) -> dict:
    """The compact ``device`` stanza every status snapshot carries (and the
    digest/bench rows read): backend provenance, sentinel counters, live
    memory gauges, and the d2h transfer bytes. Built on demand only —
    per snapshot/request, never per record."""
    reg = _REGISTRY
    mem = memory_gauges()
    return {
        "backend": backend_provenance(),
        "compiles": reg.total_compiles,
        "run_compiles": reg.run_compiles,
        "recompiles": reg.run_recompiles,
        "warm": reg.warm,
        "strict": reg.strict,
        "mem_available": mem["available"],
        "mem_bytes_in_use": mem["bytes_in_use"],
        "mem_peak_bytes": mem["peak_bytes_in_use"],
        "d2h_bytes": _d2h_bytes(registry_),
    }


def device_payload(tel=None) -> dict:
    """The full ``GET /device`` document: provenance, per-device memory,
    transfer accounting (d2h counters + per-family ``bytes_moved`` when a
    session is active), the dispatch-overlap distribution, the compile
    summary, and the flight-recorder state."""
    mem_rows = device_memory()
    reg = _REGISTRY
    out = {
        "ts_ms": int(time.time() * 1000),
        "backend": backend_provenance(),
        "memory": {"devices": mem_rows, **memory_gauges(mem_rows)},
        "transfer": {"d2h_bytes": _d2h_bytes()},
        "compile": {
            "functions": len(reg.entries),
            "total_compiles": reg.total_compiles,
            "post_warmup_compiles": reg.run_recompiles,
            "warm": reg.warm,
            "warm_reason": reg.warm_reason,
            "strict": reg.strict,
        },
    }
    if tel is not None:
        out["transfer"]["bytes_moved_by_family"] = {
            label: f.get("bytes_moved", 0)
            for label, f in tel.costs._families_dict().items()}
        h = tel.histograms.get("dispatch-overlap-ratio")
        out["dispatch_overlap"] = h.to_dict() if h is not None else {
            "count": 0}
    else:
        out["dispatch_overlap"] = {"count": 0}
    rec = active_recorder()
    out["recorder"] = ({"active": False} if rec is None else
                       {"active": True, "dir": rec.out_dir,
                        "dumps": rec.dumps, "notes": rec.total_notes})
    return out


# --------------------------------------------------------------------- #
# flight recorder


_ACTIVE_RECORDER: Optional["FlightRecorder"] = None


def active_recorder() -> Optional["FlightRecorder"]:
    return _ACTIVE_RECORDER


class FlightRecorder:
    """Bounded ring of run-lifecycle notes plus the post-mortem bundle
    dumper. The driver creates one under ``--postmortem-dir`` (which
    activates a telemetry session, so everything the bundle wants is being
    recorded); notes are appended at run/window/event granularity — never
    per record — and a dump renders one bundle directory:

    ========== ========================================================
    file        contents
    ========== ========================================================
    manifest    schema version, dump reason, timestamps, error, files
    status      the shared status snapshot (+ health verdict if --slo)
    compile     the full compile-registry snapshot (sentinel state)
    device      backend provenance + per-device memory + transfer
    events      the telemetry lifecycle event ring
    traces      recent window-trace summaries (+ full lineage, bounded)
    flight      this recorder's own note ring
    config      the run's config fingerprint (job id, argv, params)
    ========== ========================================================

    Triggers: pipeline crash (driver), SLO breach transition (health
    hook — one dump per run), strict-recompile abort, SIGUSR1, or an
    explicit :meth:`dump`. Bounded: at most ``max_dumps`` bundles per run
    so a crash loop cannot fill a disk."""

    def __init__(self, out_dir: str, config: Optional[dict] = None,
                 capacity: int = 512, max_dumps: int = 8):
        os.makedirs(out_dir, exist_ok=True)
        self.out_dir = out_dir
        self.config = config or {}
        self._ring: deque = deque(maxlen=max(1, int(capacity)))
        self._lock = threading.Lock()
        self.total_notes = 0
        self.dumps = 0
        self.max_dumps = int(max_dumps)
        self._dumped_reasons: set = set()
        self._old_handler = None
        self._signum = signal.SIGUSR1
        self._signal_installed = False
        global _ACTIVE_RECORDER
        _ACTIVE_RECORDER = self

    # ------------------------------ notes ----------------------------- #

    def note(self, kind: str, **fields) -> None:
        ev = {"ts_ms": int(time.time() * 1000), "kind": kind}
        ev.update(fields)
        with self._lock:
            self.total_notes += 1
            self._ring.append(ev)

    # ------------------------------ triggers -------------------------- #

    def install_signal(self, signum: int = signal.SIGUSR1) -> None:
        """SIGUSR1 → dump("signal") without exiting (kubectl-exec-able
        "what is it doing" capture). Main-thread only; silently skipped
        elsewhere (threaded test harnesses)."""
        try:
            old = signal.signal(signum, lambda s, f: self.dump("signal"))
            with self._lock:
                self._old_handler = old
                self._signum = signum
                self._signal_installed = True
        except ValueError:
            with self._lock:
                self._signal_installed = False

    def attach_health(self, health) -> None:
        """Hook the SLO evaluator's breach transitions: the FIRST breach of
        the run dumps a bundle (state at the moment the run went unhealthy
        — the timeline an operator wants after the fact)."""
        hooks = getattr(health, "hooks", None)
        if hooks is not None:
            hooks.append(self._on_breach)

    def _on_breach(self, check: str, value, threshold) -> None:
        self.note("slo-breach", check=check, value=value,
                  threshold=threshold)
        self.dump_once("slo-breach", "slo-breach",
                       detail={"check": check, "value": value,
                               "threshold": threshold})

    def dump_once(self, key: str, reason: str,
                  detail: Optional[dict] = None) -> Optional[str]:
        """:meth:`dump` at most once per ``key`` per run — the trigger
        discipline every breach-transition hook shares (global health,
        per-query SLO): an hour of flapping is one bundle, not a disk
        full. Returns the bundle directory on the first firing."""
        with self._lock:
            if key in self._dumped_reasons:
                return None
            self._dumped_reasons.add(key)
        return self.dump(reason, detail=detail)

    def close(self) -> None:
        global _ACTIVE_RECORDER
        with self._lock:
            restore = (self._old_handler
                       if self._signal_installed else None)
            signum = self._signum
            self._signal_installed = False
        if restore is not None:
            try:
                signal.signal(signum, restore)
            except ValueError:
                pass
        if _ACTIVE_RECORDER is self:
            _ACTIVE_RECORDER = None

    # ------------------------------ dumping --------------------------- #

    def dump(self, reason: str, error: Optional[BaseException] = None,
             detail: Optional[dict] = None) -> Optional[str]:
        """Write one post-mortem bundle; returns its directory (None when
        the per-run dump budget is exhausted). Best-effort per file — a
        torn telemetry read must not lose the rest of the bundle."""
        with self._lock:
            if self.dumps >= self.max_dumps:
                return None
            self.dumps += 1
            seq = self.dumps
        ts = time.strftime("%Y%m%dT%H%M%S")
        bundle = os.path.join(self.out_dir, f"bundle-{ts}-{seq:02d}-{reason}")
        os.makedirs(bundle, exist_ok=True)
        self.note("dump", reason=reason, bundle=bundle)

        from spatialflink_tpu.utils import telemetry as _telemetry

        tel = _telemetry.active()
        files: List[str] = []

        def write(name: str, build) -> None:
            try:
                payload = build()
            except Exception as e:
                payload = {"error": f"{type(e).__name__}: {e}"}
            path = os.path.join(bundle, name + ".json")
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f, sort_keys=True, default=repr)
            os.replace(tmp, path)
            files.append(name + ".json")

        write("status", lambda: _telemetry.status_snapshot())
        write("compile", lambda: _REGISTRY.snapshot())
        write("device", lambda: device_payload(tel))
        write("events", lambda: {
            "events": tel.events.list() if tel is not None else [],
            "total": tel.events.total if tel is not None else 0})
        write("traces", lambda: {
            "recent": (tel.traces.recent(32)
                       if tel is not None and tel.traces is not None else []),
            "enabled": tel is not None and tel.traces is not None})
        write("latency", lambda: (
            tel.latency.payload(tel=tel) if tel is not None
            else {"stages": {}, "recent": [],
                  "note": "no telemetry session at dump time"}))
        write("tenants", lambda: (
            tel.tenants.payload() if tel is not None
            else {"tenants": {}, "n": 0,
                  "note": "no telemetry session at dump time"}))
        with self._lock:
            ring = list(self._ring)
        write("flight", lambda: {"notes": ring, "total": self.total_notes})
        write("config", lambda: self.config)
        manifest = {
            "schema": BUNDLE_SCHEMA,
            "reason": reason,
            "ts_ms": int(time.time() * 1000),
            "error": (f"{type(error).__name__}: {error}"
                      if error is not None else None),
            "detail": detail,
            "files": sorted(files),
        }
        tmp = os.path.join(bundle, "manifest.json.tmp")
        with open(tmp, "w") as f:
            json.dump(manifest, f, sort_keys=True)
        os.replace(tmp, os.path.join(bundle, "manifest.json"))
        _telemetry.emit_event("postmortem-dump", reason=reason,
                              bundle=bundle)
        return bundle
