"""Fault injection: a seeded chaos layer over the broker interface.

The delivery-semantics redesign (``streams/kafka.py`` docstring) claims
at-least-once + idempotent window sinks are *exactly-once-equivalent* for
windowed results. Nothing in the happy-path tests proves that claim survives
a degraded transport — the classes of trouble a real cluster serves up:
transient produce/consume errors, lost acks, latency spikes, duplicate
deliveries, fetch-session reordering, and torn/corrupt payloads.

:class:`ChaosBroker` wraps any broker implementing the
:class:`~spatialflink_tpu.streams.kafka.InMemoryBroker` surface
(produce/fetch/commit/committed/end_offset) and injects exactly those faults
under a seeded, deterministic :class:`FaultPlan` — the same plan + the same
call sequence reproduces the same fault schedule, so a chaos run is as
replayable as a clean one. Recovery lives one layer up
(:mod:`spatialflink_tpu.runtime.supervisor`): retry/backoff + circuit
breaking for the transient errors, offset resequencing in
:class:`~spatialflink_tpu.streams.kafka.KafkaSource` for duplicates and
reordering, and redelivery-then-dead-letter for payload corruption.

Fault model boundaries (what each class means here):

- ``produce_fail`` — the produce raises BEFORE the record is appended (the
  record did not land; a blind retry is safe).
- ``ack_lost`` — the record IS appended, then the produce raises (the
  classic ambiguous failure; a blind retry would duplicate the record —
  the supervisor's verified produce re-checks the log instead).
- ``fetch_fail`` — the fetch raises; nothing about the log changed.
- ``duplicate`` — a fetched batch re-delivers a record it (or a previous
  fetch) already carried, possibly one from before the requested offset
  (fetch-session rewind).
- ``reorder`` — a fetched batch arrives permuted (NOT something a real
  single-partition consumer observes from Kafka itself, but exactly what a
  resequencing consumer must tolerate from retried fetch sessions — and the
  adversarial case for the window-aligned commit bookkeeping).
- ``torn`` — a delivered record's VALUE is corrupted in transport; the log
  itself stays intact, so a re-fetch of the same offset can heal it. A
  record that is corrupt IN the log (true poison) fails every redelivery
  and is the dead-letter queue's job.
- ``latency`` — a produce/fetch stalls for ``latency_ms`` before running.
- ``stall`` (:class:`StallFault`, process-level rather than broker-level) —
  the worker's LIVENESS surfaces wedge for a duration while the pipeline
  keeps running slowly: heartbeats stop, checkpoints stop committing, but
  windows keep trickling out. The gray failure / zombie case the fleet's
  fencing layer exists to contain, injectable via ``--fleet-chaos-stall``.

Every injection bumps a ``chaos-*`` counter in the process metrics registry
so a run summary can report how degraded the transport actually was.
"""

from __future__ import annotations

import random
import threading
import time
from dataclasses import dataclass, fields, replace
from typing import Callable, List, Optional


class TransientBrokerError(Exception):
    """A broker operation failed in a way a retry may fix (the injected
    stand-in for network timeouts, NotEnoughReplicas, fetch-session drops).
    The supervisor's :class:`~spatialflink_tpu.runtime.supervisor.RetryPolicy`
    treats this as retryable by default."""


def parse_spec(spec: str, known: dict, where: str) -> dict:
    """Parse a comma-joined ``key=value`` CLI spec (``--chaos``/``--retry``)
    against ``known`` (name -> value converter). Unknown keys fail loudly —
    a typoed field silently configuring nothing would defeat the point of
    both spec surfaces."""
    kw = {}
    for part in filter(None, (p.strip() for p in spec.split(","))):
        if "=" not in part:
            raise ValueError(f"{where}: malformed entry {part!r} "
                             "(want key=value)")
        k, v = part.split("=", 1)
        k = k.strip()
        if k not in known:
            raise ValueError(f"{where}: unknown field {k!r} "
                             f"(known: {', '.join(sorted(known))})")
        kw[k] = known[k](v)
    return kw


@dataclass
class FaultPlan:
    """Seeded, deterministic fault schedule for a :class:`ChaosBroker`.

    Rates are per-opportunity probabilities in ``[0, 1]`` drawn from one
    ``random.Random(seed)`` stream in broker-call order — single-threaded
    drivers replay identically. The ``fail_next_*`` fields are scripted
    BURSTS (consume-before-draw): the next N operations of that kind fail
    unconditionally — the deterministic way to drive a circuit breaker to
    its trip threshold in tests.
    """

    seed: int = 0
    produce_fail: float = 0.0     # raise before the record is appended
    ack_lost: float = 0.0         # append the record, then raise
    fetch_fail: float = 0.0       # raise instead of returning a batch
    duplicate: float = 0.0        # per-batch: re-deliver a record
    reorder: float = 0.0          # per-batch: permute delivery order
    torn: float = 0.0             # per-record: corrupt the delivered value
    latency: float = 0.0          # per-call: stall before the operation
    latency_ms: float = 2.0       # stall duration for latency injections
    fail_next_produces: int = 0   # scripted burst of produce failures
    fail_next_fetches: int = 0    # scripted burst of fetch failures

    _RATE_FIELDS = ("produce_fail", "ack_lost", "fetch_fail", "duplicate",
                    "reorder", "torn", "latency")

    def __post_init__(self):
        for name in self._RATE_FIELDS:
            v = getattr(self, name)
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"FaultPlan.{name}: rate {v} not in [0, 1]")

    @classmethod
    def from_spec(cls, spec: str) -> "FaultPlan":
        """Parse the CLI's ``--chaos`` spec: ``key=value`` pairs joined by
        commas, e.g. ``"seed=7,fetch_fail=0.2,torn=0.1,duplicate=0.3"``."""
        known = {f.name: (int if f.name.startswith("fail_next")
                          or f.name == "seed" else float)
                 for f in fields(cls)}
        return cls(**parse_spec(spec, known, "--chaos"))


class StallFault:
    """Injectable gray failure: the worker wedges for ``duration_s``
    WITHOUT exiting, and keeps writing.

    Arms on the first emitted window (``on_window``; a worker that never
    produced anything is indistinguishable from one still booting, which
    is the boot-timeout's job, not this fault's). While wedged:

    - the :class:`~spatialflink_tpu.runtime.fleet.HeartbeatWriter`'s gate
      (``wedged``) suppresses beats — the supervisor sees silence;
    - :meth:`~spatialflink_tpu.runtime.checkpoint.CheckpointCoordinator
      .due` returns False — a zombie must not commit manifests its fenced
      successor would resume from;
    - each subsequent ``on_window`` sleeps ``emit_delay_s`` — the worker
      is SLOW, not dead: it keeps appending outbox rows after the
      supervisor has presumed it dead, which is exactly the stale-fence
      traffic the containment tests need to observe being dropped. The
      wait lasts, in ``emit_delay_s`` steps, until ``superseded()`` says a
      successor's fence has been issued (or the stall ends), so the window
      is written past the fence whatever the timing.

    Installed process-globally (:func:`install_stall`) because the
    checkpoint coordinator has no handle on the worker context."""

    def __init__(self, duration_s: float, *,
                 superseded: Callable[[], bool],
                 emit_delay_s: float = 0.1):
        self.duration_s = float(duration_s)
        self.emit_delay_s = float(emit_delay_s)
        self._armed_at: Optional[float] = None
        #: has this incarnation been fenced out?
        self.superseded = superseded

    def on_window(self) -> None:
        if self._armed_at is None:
            self._armed_at = time.monotonic()
            from spatialflink_tpu.utils.metrics import REGISTRY
            REGISTRY.counter("chaos-stall").inc()
            return
        while self.wedged():
            time.sleep(self.emit_delay_s)
            if self.superseded():
                return

    def wedged(self) -> bool:
        return (self._armed_at is not None
                and time.monotonic() - self._armed_at < self.duration_s)


_STALL: Optional[StallFault] = None


def install_stall(fault: StallFault) -> StallFault:
    """Install the process-wide stall fault (one per worker process; the
    fleet chaos flag is the only writer)."""
    global _STALL
    _STALL = fault
    return fault


def active_stall() -> Optional[StallFault]:
    return _STALL


def _corrupt(value):
    """A torn payload: truncate and splice in bytes no spatial wire format
    accepts, so every parser fails loudly instead of mis-reading it."""
    if isinstance(value, str):
        return value[: max(1, len(value) // 2)] + "\x00TORN\x00"
    if isinstance(value, bytes):
        return value[: max(1, len(value) // 2)] + b"\x00TORN\x00"
    return "\x00TORN\x00"


class ChaosBroker:
    """Fault-injecting wrapper around any broker implementing the
    :class:`~spatialflink_tpu.streams.kafka.InMemoryBroker` surface.

    The wrapped log is never corrupted: torn payloads mutate COPIES of the
    fetched records, duplicates re-deliver existing records, and an
    ``ack_lost`` produce genuinely lands (that is the ambiguity being
    modeled). Offset bookkeeping (commit/committed/end_offset) passes
    through clean — chaos attacks the data path, not the control plane,
    matching where real deployments bleed first.
    """

    def __init__(self, inner, plan: Optional[FaultPlan] = None):
        from spatialflink_tpu.utils.metrics import REGISTRY

        self.inner = inner
        self.plan = plan or FaultPlan()
        self._rng = random.Random(self.plan.seed)
        # one mutable burst state so a shared plan object stays reusable
        self._burst_produce = int(self.plan.fail_next_produces)
        self._burst_fetch = int(self.plan.fail_next_fetches)
        self._lock = threading.Lock()
        self._c = {name: REGISTRY.counter(f"chaos-{name.replace('_', '-')}")
                   for name in ("produce_fail", "ack_lost", "fetch_fail",
                                "duplicate", "reorder", "torn", "latency")}

    # ------------------------------ helpers -------------------------- #

    def _hit(self, rate: float) -> bool:
        return rate > 0.0 and self._rng.random() < rate

    def _stall(self) -> None:
        self._c["latency"].inc()
        import time

        time.sleep(self.plan.latency_ms / 1000.0)

    # ------------------------------ broker surface ------------------- #
    # The lock guards only the RNG/burst draws (draw ORDER is what makes a
    # plan deterministic); injected sleeps and inner-broker I/O run outside
    # it so a latency spike on one call stalls THAT call, not every thread
    # sharing the broker — the per-call fault the model documents.

    def produce(self, topic: str, value, key: Optional[str] = None,
                timestamp_ms: Optional[int] = None) -> int:
        with self._lock:
            stall = self._hit(self.plan.latency)
            if self._burst_produce > 0:
                self._burst_produce -= 1
                fail = True
            else:
                fail = self._hit(self.plan.produce_fail)
            lose_ack = not fail and self._hit(self.plan.ack_lost)
        if stall:
            self._stall()
        if fail:
            self._c["produce_fail"].inc()
            raise TransientBrokerError(
                f"injected produce failure on {topic!r}")
        off = self.inner.produce(topic, value, key=key,
                                 timestamp_ms=timestamp_ms)
        if lose_ack:
            self._c["ack_lost"].inc()
            raise TransientBrokerError(
                f"injected lost ack on {topic!r} (record landed at "
                f"offset {off})")
        return off

    def fetch(self, topic: str, offset: int, max_records: int = 500
              ) -> List:
        with self._lock:
            stall = self._hit(self.plan.latency)
            if self._burst_fetch > 0:
                self._burst_fetch -= 1
                fail = True
            else:
                fail = self._hit(self.plan.fetch_fail)
        if stall:
            self._stall()
        if fail:
            self._c["fetch_fail"].inc()
            raise TransientBrokerError(
                f"injected fetch failure on {topic!r}@{offset}")
        batch = list(self.inner.fetch(topic, offset, max_records))
        if not batch:
            return batch
        with self._lock:
            dup = self._hit(self.plan.duplicate)
            rewind = dup and offset > 0 and self._rng.random() < 0.5
        prev = (self.inner.fetch(topic, offset - 1, 1) if rewind
                else None)  # rewind read is I/O: outside the lock
        with self._lock:
            if dup:
                self._c["duplicate"].inc()
                if rewind:
                    # fetch-session rewind: re-deliver a record from BEFORE
                    # the requested offset
                    if prev:
                        batch.insert(0, prev[0])
                else:
                    i = self._rng.randrange(len(batch))
                    batch.insert(self._rng.randrange(len(batch) + 1),
                                 batch[i])
            if len(batch) > 1 and self._hit(self.plan.reorder):
                self._c["reorder"].inc()
                self._rng.shuffle(batch)
            if self.plan.torn > 0.0:
                for i, rec in enumerate(batch):
                    if self._hit(self.plan.torn):
                        self._c["torn"].inc()
                        # corrupt a COPY; the log record stays intact so a
                        # redelivery of this offset can heal
                        batch[i] = replace(rec, value=_corrupt(rec.value))
        return batch

    def commit(self, topic: str, group: str, next_offset: int) -> None:
        self.inner.commit(topic, group, next_offset)

    def committed(self, topic: str, group: str) -> int:
        return self.inner.committed(topic, group)

    def end_offset(self, topic: str) -> int:
        return self.inner.end_offset(topic)

    def topic_values(self, topic: str) -> List:
        return self.inner.topic_values(topic)

    def close(self) -> None:
        if hasattr(self.inner, "close"):
            self.inner.close()
