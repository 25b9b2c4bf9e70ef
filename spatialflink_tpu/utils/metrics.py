"""Metrics / observability (reference parity map):

- :class:`Counter` ≙ Flink metric ``Counter`` "Distance Computation Count"
  (``spatialObjects/Point.java:220-235``);
- :class:`Meter` ≙ Dropwizard "Throughput-Meter" (``Point.java:237-253``) —
  event rate over a sliding time window;
- :class:`MetricsRegistry` — named counters/meters, one place to scrape;
- :func:`check_exit_control_tuple` ≙ the remote-stop hook that kills the job
  when a tuple with ``geometry.type == "control"`` arrives
  (``utils/HelperClass.java:441-453``);
- :func:`trace` / :func:`profile_to` — named-stage visibility, the analogue
  of the reference's named Flink operators in the web UI (SURVEY §5):
  ``jax.profiler`` annotations when available, no-ops otherwise.

Per-record latency sinks live in :mod:`spatialflink_tpu.streams.sinks`
(:class:`LatencySink`).
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
from collections import deque
from typing import Dict, Iterable, Iterator, Optional


class ControlTupleExit(Exception):
    """Raised when a control tuple arrives (the reference throws IOException
    to crash the Flink job — a crude remote stop)."""


class GracefulShutdown(ControlTupleExit):
    """A SIGTERM-style stop request: drain buffered records into the
    pipeline, let sealed windows emit, write a final checkpoint, exit 0.
    Subclasses :class:`ControlTupleExit` so every existing stop path
    (decode buffer flush, driver summary, conservative Kafka commits)
    treats it as the graceful stop it is; the driver additionally writes
    a final coordinated checkpoint when the stop came from a signal."""


#: process-wide shutdown request flag (set from the driver's SIGTERM
#: handler; checked at record boundaries so no in-flight record is lost)
_SHUTDOWN = threading.Event()


def request_shutdown() -> None:
    """Ask the running pipeline to stop gracefully at the next record
    boundary (signal-handler safe: just sets an event)."""
    _SHUTDOWN.set()


def shutdown_requested() -> bool:
    return _SHUTDOWN.is_set()


def clear_shutdown() -> None:
    """Reset the flag (run start / test isolation)."""
    _SHUTDOWN.clear()


def check_exit_control_tuple(record) -> None:
    """Raise :class:`ControlTupleExit` if ``record`` is a control tuple.

    Accepts raw GeoJSON strings/dicts (pre-parse, like the reference's
    filter on the Kafka ObjectNode) — cheap substring guard first.
    """
    obj = record
    if isinstance(obj, str):
        if '"control"' not in obj:
            return
        try:
            obj = json.loads(obj)
        except ValueError:
            return
    if isinstance(obj, dict):
        env = obj.get("value")
        if isinstance(env, dict):  # Kafka envelope
            obj = env
        geom = obj.get("geometry", obj)
        if isinstance(geom, dict) and geom.get("type") == "control":
            raise ControlTupleExit("control tuple received")


class Counter:
    __slots__ = ("name", "count")

    def __init__(self, name: str):
        self.name = name
        self.count = 0

    def inc(self, n: int = 1) -> None:
        self.count += n


class Meter:
    """Events/sec over a sliding time window (default 60s).

    O(1) memory on the per-record hot path: marks aggregate into fixed
    one-second buckets (at most ``window_s`` of them), like Dropwizard's
    constant-space meters — NOT one entry per event."""

    def __init__(self, name: str, window_s: float = 60.0):
        self.name = name
        self.window_s = window_s
        self.count = 0
        self._buckets = deque()  # (whole_second, n), ascending

    def mark(self, n: int = 1, now: Optional[float] = None) -> None:
        now = time.monotonic() if now is None else now
        self.count += n
        sec = int(now)
        if self._buckets and self._buckets[-1][0] == sec:
            self._buckets[-1][1] += n
        else:
            self._buckets.append([sec, n])
            self._evict(now)

    def _evict(self, now: float) -> None:
        horizon = now - self.window_s - 1
        while self._buckets and self._buckets[0][0] < horizon:
            self._buckets.popleft()

    def rate(self, now: Optional[float] = None) -> float:
        now = time.monotonic() if now is None else now
        self._evict(now)
        if not self._buckets:
            return 0.0
        span = max(now - self._buckets[0][0], 1.0)
        return sum(n for _, n in self._buckets) / span


class MetricsRegistry:
    """Named counters and meters; ``snapshot()`` for scraping/logging.

    The registry is cross-thread (pipeline threads create handles while
    the reporter/opserver threads snapshot), so handle creation, reset,
    and snapshot iteration hold the instance lock. The handles themselves
    stay lock-free: ``Counter.inc``/``Meter.mark`` are the per-record hot
    path and rely on the GIL's atomic int bump."""

    def __init__(self):
        self.counters: Dict[str, Counter] = {}
        self.meters: Dict[str, Meter] = {}
        self._lock = threading.Lock()

    def reset(self) -> None:
        """Drop every counter and meter. Handles created before the reset
        stay usable but are no longer scraped — callers that cache a
        counter across a reset should re-fetch it."""
        with self._lock:
            self.counters.clear()
            self.meters.clear()

    def counter(self, name: str) -> Counter:
        with self._lock:
            if name not in self.counters:
                self.counters[name] = Counter(name)
            return self.counters[name]

    def meter(self, name: str, window_s: float = 60.0) -> Meter:
        with self._lock:
            if name not in self.meters:
                self.meters[name] = Meter(name, window_s)
            return self.meters[name]

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            counters = list(self.counters.items())
            meters = list(self.meters.items())
        out: Dict[str, float] = {}
        for n, c in counters:
            out[n] = c.count
        for n, m in meters:
            out[f"{n}.count"] = m.count
            out[f"{n}.rate"] = m.rate()
        return out


#: process-wide default registry (the reference's per-job metric group).
#: Pipelines read it through ``metrics.REGISTRY`` at CALL time (function-
#: level imports), so :func:`scoped_registry` can swap it for a run/test
#: without process-global counter bleed-through; the driver's kafka summary
#: keeps its baseline-delta logic only for true cross-run accumulation in
#: this default registry.
REGISTRY = MetricsRegistry()


def set_registry(registry: MetricsRegistry) -> MetricsRegistry:
    """Install ``registry`` as the ambient default; returns the previous
    one. Prefer :func:`scoped_registry` — it restores on exit."""
    global REGISTRY
    old = REGISTRY
    REGISTRY = registry
    return old


@contextlib.contextmanager
def scoped_registry(registry: Optional[MetricsRegistry] = None
                    ) -> Iterator[MetricsRegistry]:
    """Run the enclosed block against a fresh (or given) registry, restoring
    the previous one on exit — the test/driver isolation hook, so counters
    from one run cannot bleed into the next's snapshot."""
    reg = MetricsRegistry() if registry is None else registry
    old = set_registry(reg)
    try:
        yield reg
    finally:
        set_registry(old)

#: counter-name prefixes that mean "the transport or pipeline degraded and
#: recovery machinery engaged" — injected faults (runtime/faults.py), retry
#: and breaker activity, verified-produce recoveries, and dead-lettered
#: records (runtime/supervisor.py). One namespace so the driver's run
#: summary can surface every degradation event without naming each counter.
DEGRADATION_PREFIXES = ("chaos-", "retry-", "breaker-", "dlq-",
                        "produce-verified")


def degradation_snapshot(registry: Optional[MetricsRegistry] = None
                         ) -> Dict[str, int]:
    """Non-zero degradation counters (see :data:`DEGRADATION_PREFIXES`) —
    the summary line's "how rough was the transport" digest."""
    reg = REGISTRY if registry is None else registry
    return {n: c.count for n, c in sorted(reg.counters.items())
            if c.count and n.startswith(DEGRADATION_PREFIXES)}


def metered(stream: Iterable, meter: Meter,
            control_check: bool = False) -> Iterator:
    """Wrap a record stream: marks the meter per record and (optionally)
    raises on control tuples — the reference's map-stage metric wrappers."""
    for rec in stream:
        if control_check:
            check_exit_control_tuple(rec)
        meter.mark()
        yield rec


@contextlib.contextmanager
def trace(name: str, **meta):
    """Named trace annotation visible in a jax.profiler capture; no-op when
    profiling machinery is unavailable. ``meta`` rides the event as stats
    (``window=<start>`` ties one window's spans together). Only the
    annotation SETUP is guarded — an exception raised by the enclosed
    block must propagate unchanged (a try around the yield would swallow
    it and break the generator contract)."""
    try:
        import jax.profiler as _prof

        cm = _prof.TraceAnnotation(name, **meta)
    except Exception:
        cm = None
    if cm is None:
        yield
    else:
        with cm:
            yield


@contextlib.contextmanager
def profile_to(log_dir: str):
    """Capture a jax.profiler trace for the enclosed block (the rebuild's
    answer to the reference's Flink web UI, SURVEY §5)."""
    import jax.profiler as _prof

    _prof.start_trace(log_dir)
    try:
        yield
    finally:
        _prof.stop_trace()
