"""Kafka transport: in-memory broker shim + source/sink + delivery semantics.

The reference's entire I/O backbone is Kafka: consumers feed every pipeline
(``StreamingJob.java:473``), producers ship results with EXACTLY_ONCE
semantics (``StreamingJob.java:512``), per-type output schemas serialize each
geometry family (``spatialStreams/Serialization.java:17-774``), and latency
values go to their own topic (``utils/HelperClass.java:455-529``).

This environment has no broker and no Kafka client library, so the transport
is built against a minimal broker *interface* with two implementations:

- :class:`InMemoryBroker` — a faithful shim (topics, partitions-as-one-log,
  offsets, consumer groups, commit) used by tests and local replays.
- a real client adapter via :func:`connect_kafka`, gated on kafka-python
  being installed (it is not, in this image).

Delivery semantics re-design (SURVEY §7): Flink's EXACTLY_ONCE producer rides
checkpoint-coordinated transactions; without Flink's checkpoint machinery the
rebuild ships **at-least-once + idempotent writes**: the consumer commits
offsets only AFTER results are produced (re-delivery on crash), and
:class:`IdempotentWindowSink` keys every result by (window_start, window_end,
key) so re-delivered duplicates overwrite instead of double-count — the
effective semantics match exactly-once for windowed results.
"""

from __future__ import annotations

import json
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

from spatialflink_tpu.streams.formats import serialize_spatial
from spatialflink_tpu.utils import telemetry as _telemetry


@dataclass(**({"slots": True} if __import__("sys").version_info >= (3, 10) else {}))
class BrokerRecord:
    """One record in a topic log."""

    offset: int
    key: Optional[str]
    value: Any
    timestamp_ms: int = 0


class InMemoryBroker:
    """Topics as append-only logs with consumer-group offset tracking.

    Threadsafe so a producer thread can feed a consuming pipeline, mirroring
    the reference's Kafka-decoupled source/sink topology.
    """

    def __init__(self):
        self._topics: Dict[str, List[BrokerRecord]] = {}
        self._commits: Dict[Tuple[str, str], int] = {}  # (group, topic) -> next offset
        self._lock = threading.Lock()

    # ------------------------------ producer ------------------------- #

    def produce(self, topic: str, value, key: Optional[str] = None,
                timestamp_ms: Optional[int] = None) -> int:
        """Append; returns the record's offset."""
        with self._lock:
            log = self._topics.setdefault(topic, [])
            rec = BrokerRecord(
                offset=len(log), key=key, value=value,
                timestamp_ms=timestamp_ms if timestamp_ms is not None
                else int(time.time() * 1000))
            log.append(rec)
            return rec.offset

    def produce_many(self, topic: str, values, key: Optional[str] = None
                     ) -> int:
        """Batched :meth:`produce` under ONE lock/timestamp — the window
        sink's per-record production amortized (only the plain in-memory
        broker offers this; fault-injecting/supervised wrappers keep the
        per-record path so chaos semantics cover every record). Returns the
        first offset."""
        with self._lock:
            log = self._topics.setdefault(topic, [])
            base = len(log)
            now = int(time.time() * 1000)
            log.extend(BrokerRecord(offset=base + i, key=key, value=v,
                                    timestamp_ms=now)
                       for i, v in enumerate(values))
            return base

    # ------------------------------ consumer ------------------------- #

    def fetch(self, topic: str, offset: int, max_records: int = 500
              ) -> List[BrokerRecord]:
        """Records from ``offset`` onward. Consumers track their own
        position (like a real Kafka consumer); the committed offset only
        decides where a RESTARTED group member resumes."""
        with self._lock:
            log = self._topics.get(topic, [])
            return log[offset:offset + max_records]

    def commit(self, topic: str, group: str, next_offset: int) -> None:
        with self._lock:
            cur = self._commits.get((group, topic), 0)
            self._commits[(group, topic)] = max(cur, next_offset)

    def committed(self, topic: str, group: str) -> int:
        with self._lock:
            return self._commits.get((group, topic), 0)

    def end_offset(self, topic: str) -> int:
        with self._lock:
            return len(self._topics.get(topic, []))

    def topic_values(self, topic: str) -> List[Any]:
        with self._lock:
            return [r.value for r in self._topics.get(topic, [])]


def resequence_batch(batch: List[BrokerRecord], next_offset: int
                     ) -> List[BrokerRecord]:
    """Restore single-log order over a degraded transport: sort a fetched
    batch by offset and drop records already delivered (offset below
    ``next_offset``) or re-delivered within the batch. What a real
    consumer's fetch-session dedup does; a no-op on clean transports.
    :class:`KafkaSource` assumes offset-ordered, exactly-once-per-position
    hand-off."""
    # fast path: a clean transport delivers the batch already contiguous
    # from next_offset — one scan, no sort, no copy (the common case on
    # every poll of an undegraded broker)
    if batch and batch[0].offset == next_offset:
        expected = next_offset
        for rec in batch:
            if rec.offset != expected:
                break
            expected += 1
        else:
            return batch
    cleaned: List[BrokerRecord] = []
    last = next_offset - 1
    for rec in sorted(batch, key=lambda r: r.offset):
        if rec.offset > last:
            cleaned.append(rec)
            last = rec.offset
    return cleaned


#: yielded by a KafkaSource constructed with ``starvation_sentinel=True``
#: whenever a live-mode poll comes up empty — a batching consumer (the
#: commit tap's chunked decode) flushes on it so buffered records never
#: wait out a quiet topic; it is NOT a record and never commits offsets
STARVED = object()


def _plain_records(vals: List) -> bool:
    """True when a poll batch can take the chunked decode whole: every
    value a raw string, none carrying the control marker."""
    for v in vals:
        if not isinstance(v, str) or '"control"' in v:
            return False
    return True


class KafkaSource:
    """Consumer-group iterator over a topic (reference:
    ``FlinkKafkaConsumer`` at ``StreamingJob.java:473``).

    Yields record values; offsets commit every ``commit_every`` records
    *after* the records were handed downstream, so a crash between hand-off
    and commit re-delivers (at-least-once — pair with
    :class:`IdempotentWindowSink` downstream).

    With ``auto_commit=False`` the source never commits on its own: the
    caller owns commit placement via :meth:`commit_to` and the live
    ``position`` attribute (next offset to read). The driver's Kafka mode
    uses this to align commits with WINDOW emission instead of record
    hand-off — a record handed to a window assembler is not yet reflected
    in any produced result (see :class:`WindowCommitTap`).
    """

    def __init__(self, broker: InMemoryBroker, topic: str, group: str,
                 poll_batch: int = 500, commit_every: int = 1,
                 stop_at_end: bool = True, auto_commit: bool = True,
                 limit: Optional[int] = None,
                 starvation_sentinel: bool = False,
                 commit_lag: Optional[int] = None):
        self.broker = broker
        self.topic = topic
        self.group = group
        self.poll_batch = poll_batch
        self.commit_every = max(1, commit_every)
        self.stop_at_end = stop_at_end
        self.auto_commit = auto_commit
        #: when set (and auto_commit is off), commit ``position - lag``
        #: after every consumed poll batch — progress-driven commits from
        #: the CONSUMPTION side, so an unbounded sparse-match stream (a
        #: --kafka-follow run whose micro-batches rarely emit) still bounds
        #: restart reprocessing. The lag must cover every record that can
        #: be in flight (batcher + device pipeline); the driver computes it
        #: as (pipeline_depth + 1) * realtime_batch_size.
        self.commit_lag = commit_lag
        #: live mode only: yield :data:`STARVED` before sleeping on an empty
        #: poll (opt-in — only consumers that understand the marker set it)
        self.starvation_sentinel = starvation_sentinel
        #: max records to hand out per iteration (None = unbounded) — the
        #: driver's --limit for broker-fed runs; counts THIS run's records,
        #: from the group's resume point
        self.limit = limit
        #: next offset to read; live while iterating (restart resume point)
        self.position = broker.committed(topic, group)

    def commit_to(self, next_offset: int) -> None:
        """Commit the group's resume point (monotone in the broker)."""
        self.broker.commit(self.topic, self.group, next_offset)

    def _clean(self, batch: List, pos: int, yielded: int) -> List:
        """A fetched batch resequenced past ``pos`` and cut to the limit:
        empty when every record in it was already delivered."""
        cleaned = resequence_batch(batch, pos)
        if cleaned and self.limit is not None:
            cleaned = cleaned[:self.limit - yielded]
        return cleaned

    def _take(self, batch: List, pos: int, yielded: int):
        """One poll's ``(values, next_positions)`` lists, with the position
        advanced and the lagged commit made; None when nothing is new."""
        cleaned = self._clean(batch, pos, yielded)
        if not cleaned:
            return None
        vals = [r.value for r in cleaned]
        poss = [r.offset + 1 for r in cleaned]
        self.position = poss[-1]
        if self.commit_lag is not None:
            self.broker.commit(self.topic, self.group,
                               max(0, poss[-1] - self.commit_lag))
        return vals, poss

    def iter_batches(self) -> Iterator:
        """Batched consumption for chunk-aware consumers (the commit tap's
        native decode): yields ``(values, next_positions)`` lists per poll —
        one Python-level iteration per POLL instead of per record, same
        resequencing/limit/lagged-commit semantics as :meth:`__iter__` (and
        :data:`STARVED` on empty live polls when the sentinel is on).
        Requires ``auto_commit=False`` (the tap owns commit placement);
        control tuples are NOT checked here — the consumer scans the batch
        (the tap does)."""
        if self.auto_commit:
            raise ValueError("iter_batches requires auto_commit=False "
                             "(the consumer owns commit placement)")
        pos = self.position = self.broker.committed(self.topic, self.group)
        yielded = 0
        tel = _telemetry.active()
        while True:
            if self.limit is not None and yielded >= self.limit:
                return
            if tel is not None:
                with tel.span("fetch", query="kafka"):
                    batch = self.broker.fetch(self.topic, pos,
                                              self.poll_batch)
            else:
                batch = self.broker.fetch(self.topic, pos, self.poll_batch)
            if not batch:
                if self.stop_at_end:
                    return
                if self.starvation_sentinel:
                    yield STARVED
                time.sleep(0.01)
                continue
            # the poll's own work (resequencing, the hand-off lists, the
            # lagged commit) is its own span, closed before the yield
            if tel is not None:
                with tel.span("poll", query="kafka"):
                    item = self._take(batch, pos, yielded)
            else:
                item = self._take(batch, pos, yielded)
            if item is None:
                continue  # all duplicates of already-delivered records
            pos = self.position
            yielded += len(item[0])
            yield item

    def __iter__(self) -> Iterator[Any]:
        # position starts at the group's committed offset (restart resume)
        # and advances in-memory as records are read, like a real consumer
        pos = self.position = self.broker.committed(self.topic, self.group)
        uncommitted = 0
        yielded = 0
        # telemetry is per-poll (never per record): one span around each
        # fetch when a session is active, the bare call otherwise
        tel = _telemetry.active()
        while True:
            if self.limit is not None and yielded >= self.limit:
                break
            if tel is not None:
                with tel.span("fetch", query="kafka"):
                    batch = self.broker.fetch(self.topic, pos,
                                              self.poll_batch)
            else:
                batch = self.broker.fetch(self.topic, pos, self.poll_batch)
            if not batch:
                if self.stop_at_end:
                    break
                if self.starvation_sentinel:
                    yield STARVED
                time.sleep(0.01)
                continue
            # a degraded transport (retried fetch sessions — see
            # runtime/faults.py) may deliver a batch permuted or with
            # records re-delivered, including from before ``pos``; the
            # window-aligned commit tap's prefix bookkeeping is unsound
            # under reordered positions, so disorder stops here
            if tel is not None:
                with tel.span("poll", query="kafka"):
                    cleaned = self._clean(batch, pos, yielded)
            else:
                cleaned = self._clean(batch, pos, yielded)
            if not cleaned:
                continue  # all duplicates of already-delivered records
            for rec in cleaned:
                # position advances BEFORE the hand-off so a tap reading it
                # right after receiving the record sees "offset past me"
                pos = self.position = rec.offset + 1
                yield rec.value
                yielded += 1
                uncommitted += 1
                if self.auto_commit and uncommitted >= self.commit_every:
                    self.broker.commit(self.topic, self.group, pos)
                    uncommitted = 0
            if self.commit_lag is not None and not self.auto_commit:
                # consumption-driven lagged commit, once per poll batch: a
                # stream that consumes without emitting (sparse realtime
                # matches) still advances the group offset (commit is
                # monotone, so the emit-time lagged commit composes)
                self.broker.commit(self.topic, self.group,
                                   max(0, pos - self.commit_lag))
        if self.auto_commit and uncommitted:
            self.broker.commit(self.topic, self.group, pos)


class KafkaSink:
    """Producer shipping spatial objects/results with a per-type output
    schema (reference: ``Serialization.java``'s ``*OutputSchema`` classes —
    here one serializer covers every geometry family × format via
    ``serialize_spatial``)."""

    def __init__(self, broker: InMemoryBroker, topic: str,
                 fmt: Optional[str] = None,
                 date_format: Optional[str] = None,
                 delimiter: str = ","):
        self.broker = broker
        self.topic = topic
        self.fmt = fmt
        self.date_format = date_format
        self.delimiter = delimiter

    def _encode(self, record):
        if self.fmt and hasattr(record, "obj_id"):
            return serialize_spatial(record, self.fmt,
                                     delimiter=self.delimiter,
                                     date_format=self.date_format)
        return record

    def emit(self, record) -> None:
        key = getattr(record, "obj_id", None)
        self.broker.produce(self.topic, self._encode(record), key=key)

    def close(self) -> None:
        pass


def _values_equal(a, b) -> bool:
    """Structural equality that tolerates ndarray-valued extras (a
    tAggregate heatmap WindowResult would make plain ``==`` raise
    "truth value of an array is ambiguous")."""
    import dataclasses

    import numpy as _np

    if isinstance(a, _np.ndarray) or isinstance(b, _np.ndarray):
        return _np.array_equal(a, b)
    if dataclasses.is_dataclass(a) and not isinstance(a, type):
        if type(a) is not type(b):
            return False
        return all(_values_equal(getattr(a, f.name), getattr(b, f.name))
                   for f in dataclasses.fields(a))
    if isinstance(a, dict) and isinstance(b, dict):
        return a.keys() == b.keys() and all(
            _values_equal(v, b[k]) for k, v in a.items())
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        return len(a) == len(b) and all(
            _values_equal(x, y) for x, y in zip(a, b))
    try:
        return bool(a == b)
    except Exception:
        return False


class IdempotentWindowSink:
    """At-least-once → effective exactly-once for windowed results.

    Results are keyed by (window_start, window_end, key); re-deliveries of a
    key are dropped entirely — first delivery wins in BOTH the snapshot
    table and the inner sink, so the two exposed outputs can never disagree.
    A re-delivery whose value differs from the recorded one (a recomputed
    window producing a different result — a determinism bug upstream, not
    normal retry noise) is counted separately in
    ``duplicates_value_differing`` so divergence is observable.
    ``key_fn`` extracts the idempotency key from a result (default: the
    window bounds plus a ``cell`` extra when present — SURVEY §7's
    "(window, cell)" plan).
    """

    def __init__(self, inner_sink=None,
                 key_fn: Optional[Callable[[Any], Tuple]] = None):
        self.inner = inner_sink
        self.key_fn = key_fn or self._default_key
        self._delivered: Dict[Tuple, Any] = {}
        self.duplicates_suppressed = 0
        self.duplicates_value_differing = 0

    @staticmethod
    def _default_key(result) -> Tuple:
        ws = getattr(result, "window_start", None)
        we = getattr(result, "window_end", None)
        cell = getattr(result, "extras", {}).get("cell") \
            if hasattr(result, "extras") else None
        return (ws, we, cell)

    def emit(self, result) -> None:
        key = self.key_fn(result)
        if key not in self._delivered:
            self._delivered[key] = result
            if self.inner is not None:
                self.inner.emit(result)
        else:
            self.duplicates_suppressed += 1
            if not _values_equal(self._delivered[key], result):
                self.duplicates_value_differing += 1

    @property
    def delivered_count(self) -> int:
        """Distinct (window, key) results delivered so far."""
        return len(self._delivered)

    def snapshot(self) -> Dict[Tuple, Any]:
        return dict(self._delivered)

    def close(self) -> None:
        if self.inner is not None:
            self.inner.close()


class WindowCommitTap:
    """Window-aligned offset commits for a :class:`KafkaSource` feeding an
    event-time windowed pipeline (the driver's ``--kafka`` mode).

    Sits between the source and the operator: parses each raw record,
    appends ``(source position after it, last-window-end)`` in arrival
    order, and hands the parsed object downstream. A record with event time
    ``ts`` is fully reflected in produced output once the window ending at
    ``lwe = ts - ts % slide + size`` has been EMITTED (windows fire in
    end order, and every window containing the record ends at or before
    ``lwe``). So on each emitted window ``[s, e)`` the longest PREFIX of
    pending records with ``lwe <= e`` commits — prefix-only, so an
    early-arriving record destined for a later window conservatively blocks
    commits behind it. Crash ⇒ re-delivery of exactly the records some
    unfired window still needed (at-least-once, never missing); the
    downstream :class:`KafkaWindowSink` suppresses the re-emitted windows.

    Control tuples are checked BEFORE parse (they are raw sentinel records,
    ``HelperClass.checkExitControlTuple``), so the remote-stop hook fires
    here rather than crashing the parser.

    ``bulk_decode`` (optional) batches the per-record parse through the
    native ingest: raw string records accumulate into chunks and decode in
    ONE native call (the columnar point parser, applied to broker
    records) — per-record positions are snapshotted at pull time, so the
    window-aligned commit bookkeeping is identical. In live mode the source
    must be constructed with ``starvation_sentinel=True``: the tap flushes
    its buffer on every :data:`STARVED` marker, bounding the added latency
    to one poll cycle instead of one chunk fill.
    """

    def __init__(self, source: KafkaSource, size_ms: int, slide_ms: int,
                 parse: Optional[Callable[[Any], Any]] = None,
                 bulk_decode: Optional[Callable[[List[str]], List[Any]]]
                 = None, bulk_chunk: int = 2048,
                 dlq=None, checkpointer=None):
        from collections import deque

        if bulk_decode is not None and parse is None:
            # the fallback branches (embedded newline, count mismatch)
            # reparse the chunk per record — without a parser they would
            # crash exactly when resilience is needed
            raise ValueError("bulk_decode requires a per-record parse "
                             "fallback")
        self.source = source
        self.size_ms = int(size_ms)
        self.slide_ms = max(1, int(slide_ms))
        self.parse = parse
        self.bulk_decode = bulk_decode
        #: int or a zero-arg size callback (the chunk governor's actuator)
        #: — read through the :attr:`bulk_chunk` property, which resolves
        #: a callback per take so a live resize lands between chunks
        self._bulk_chunk = bulk_chunk
        #: the chunked decoder's obj-id space (set by the driver when the
        #: decoder interns); downstream ChunkedStream consumers read it
        self.interner = getattr(bulk_decode, "interner", None)
        #: optional runtime.checkpoint.CheckpointCoordinator: the tap
        #: reports per-record source positions AT HAND-OFF time (not pull
        #: time — the chunked decode buffers raws past the source's read
        #: head, and a checkpoint must never record a position covering
        #: records still sitting in that buffer)
        self.checkpointer = checkpointer
        self._ckpt_key = f"kafka:{source.topic}"
        #: optional runtime.supervisor.DeadLetterQueue: parse failures are
        #: retried against FRESH fetches of the same offset (transport
        #: corruption heals on redelivery) and quarantined — with failure
        #: metadata, before any commit can pass them — when they persist.
        #: Without a DLQ a parse failure propagates, as it always did.
        self.dlq = dlq
        self._pending = deque()
        # telemetry gauges (watermark lag = wall clock minus newest event
        # time; commit backlog = records awaiting a covering window), set
        # per tracked record — cheap float stores, and only when a session
        # was active when the driver wired the tap
        tel = _telemetry.active()
        self._tel = tel
        self._lag_gauge = (tel.gauge("kafka.watermark-lag-ms")
                           if tel is not None else None)
        self._backlog_gauge = (tel.gauge("kafka.commit-backlog")
                               if tel is not None else None)

    @property
    def bulk_chunk(self) -> int:
        """The decode-chunk size RIGHT NOW (every read site resolves the
        governor callback afresh, so a resize applies at the next take)."""
        c = self._bulk_chunk
        return max(1, int(c() if callable(c) else c))

    def _parse_or_dlq(self, raw, position: int):
        """Parse one record; on failure, redeliver-and-retry, then
        quarantine to the DLQ and return None (caller skips the record).
        A quarantined record does not enter the commit bookkeeping: its
        dead-letter entry IS its reflection in produced output, so commits
        may pass it."""
        if self.parse is None:
            return raw
        try:
            return self.parse(raw)
        except Exception as e:
            if self.dlq is None:
                raise
            from spatialflink_tpu.utils.metrics import (
                REGISTRY, check_exit_control_tuple)

            offset = position - 1
            attempts = 1
            last: BaseException = e
            for _ in range(self.dlq.redelivery_limit):
                try:
                    fresh = self.source.broker.fetch(
                        self.source.topic, offset, 1)
                except Exception as fe:  # transport down past retry budget
                    last = fe
                    break
                rec = next((r for r in fresh if r.offset == offset), None)
                if rec is None:
                    break
                attempts += 1
                # a STOP tuple torn in transport parses as garbage; its
                # healed redelivery must honor the remote-stop contract,
                # not be quarantined as poison (ControlTupleExit
                # propagates — it is a control-flow signal, not a parse
                # failure)
                check_exit_control_tuple(rec.value)
                try:
                    obj = self.parse(rec.value)
                except Exception as e2:
                    last = e2
                    continue
                REGISTRY.counter("dlq-redelivery-healed").inc()
                _telemetry.emit_event("dlq-redelivery-healed",
                                      topic=self.source.topic, offset=offset,
                                      attempts=attempts)
                return obj
            self.dlq.quarantine(source_topic=self.source.topic,
                                offset=offset, raw=raw, error=last,
                                attempts=attempts)
            return None

    def _track(self, obj, position: int):
        if self.checkpointer is not None:
            self.checkpointer.note_position(self._ckpt_key, position)
        ts = getattr(obj, "timestamp", None)
        if isinstance(ts, (int, float)):
            lwe = int(ts) - int(ts) % self.slide_ms + self.size_ms
            if self._lag_gauge is not None:
                self._lag_gauge.set(time.time() * 1000 - ts)
        else:
            # unknown event time: block commits behind it until the
            # end-of-stream commit_all (conservative, never unsafe)
            lwe = float("inf")
        self._pending.append((position, lwe))
        if self._backlog_gauge is not None:
            self._backlog_gauge.set(len(self._pending))
        return obj

    def _track_chunk(self, chunk):
        """Vectorized :meth:`_track` for one columnar chunk: commit
        bookkeeping per record (the prefix-commit sweep needs per-record
        positions), checkpoint position + gauges once per chunk."""
        if self.checkpointer is not None:
            self.checkpointer.note_position(
                self._ckpt_key, int(chunk.positions[-1]))
            coord, key = self.checkpointer, self._ckpt_key
            # per-record re-note hook for flatten consumers (see
            # PointChunk.note); chunk-aware assemblers never need it
            chunk.note = lambda p: coord.note_position(key, p)
        ts = np.asarray(chunk.parsed.ts, np.int64)
        lwe = ts - ts % self.slide_ms + self.size_ms
        self._pending.extend(zip(chunk.positions.tolist(), lwe.tolist()))
        if self._lag_gauge is not None:
            self._lag_gauge.set(time.time() * 1000 - int(ts[-1]))
        if self._backlog_gauge is not None:
            self._backlog_gauge.set(len(self._pending))
        return chunk

    def chunks(self) -> Iterator[Any]:
        """Chunked hand-off for the batched decode path
        (``driver.decode_chunks``): yields columnar
        :class:`~spatialflink_tpu.streams.bulk.PointChunk` chunks (native
        decode, per-record positions snapshotted for the commit sweep) or
        plain record lists (per-record fallback / record-mode parse), one
        chunk per flush — at most one poll cycle of buffering in live mode
        (the starvation sentinel flushes)."""
        if self.bulk_decode is not None:
            yield from self._bulk_chunks()
            return
        yield from self._record_chunks()

    def __iter__(self) -> Iterator[Any]:
        from spatialflink_tpu.utils.metrics import check_exit_control_tuple

        if self.bulk_decode is not None:
            # flatten the chunked decode (same buffering the chunked
            # per-record hand-off always had); per-record position re-note
            # keeps checkpoint barriers sound while records dribble out
            for ch in self._bulk_chunks():
                if hasattr(ch, "records"):
                    recs = ch.records()
                    if ch.note is not None and ch.positions is not None:
                        for rec, p in zip(recs, ch.positions.tolist()):
                            ch.note(int(p))
                            yield rec
                    else:
                        yield from recs
                else:
                    yield from ch
            return
        for raw in self.source:
            if raw is STARVED:  # only batching consumers need the marker
                continue
            check_exit_control_tuple(raw)
            obj = self._parse_or_dlq(raw, self.source.position)
            if obj is None:  # quarantined poison record
                continue
            yield self._track(obj, self.source.position)

    def _record_chunks(self) -> Iterator[Any]:
        """Record-mode chunk hand-off (no native decoder — e.g. geometry
        streams): the per-record parse is unchanged, but records batch into
        chunks so downstream bookkeeping amortizes; STARVED flushes."""
        from spatialflink_tpu.utils.metrics import (ControlTupleExit,
                                                    check_exit_control_tuple)

        buf: List = []
        tel = self._tel
        for raw in self.source:
            if raw is STARVED:
                if buf:
                    yield buf
                    buf = []
                continue
            try:
                check_exit_control_tuple(raw)
            except ControlTupleExit:
                if buf:
                    yield buf
                raise
            t0 = time.perf_counter() if tel is not None else 0.0
            obj = self._parse_or_dlq(raw, self.source.position)
            if tel is not None:
                tel.observe("ingest", time.perf_counter() - t0)
            if obj is None:
                continue
            buf.append(self._track(obj, self.source.position))
            if len(buf) >= self.bulk_chunk:
                yield buf
                buf = []
        if buf:
            yield buf

    def _bulk_chunks(self) -> Iterator[Any]:
        from spatialflink_tpu.utils.metrics import (ControlTupleExit,
                                                    check_exit_control_tuple)

        raws: List[str] = []
        poss: List[int] = []
        tel = self._tel

        def decode():
            """The buffered records as one chunk (or record list), and the
            stop a torn control tuple defers past them."""
            # a record with an embedded newline would shift the native
            # parser's line<->record mapping; so would any count mismatch;
            # and a record the POINT bulk parser rejects outright (e.g. a
            # polygon feature in a point topic) raises ValueError — all
            # three fall back to the exact per-record parse, which handles
            # them the way the streaming path always did (never silently
            # drop, mis-attribute, or crash on a record)
            chunk = None
            if not any("\n" in r for r in raws):
                try:
                    chunk = self.bulk_decode(raws)
                except ValueError:
                    chunk = None
                if chunk is not None and len(chunk) != len(raws):
                    chunk = None
            stop = None
            if chunk is None:
                # a torn STOP tuple healing mid-fallback raises
                # ControlTupleExit; records parsed BEFORE it in the chunk
                # must still reach the pipeline (same contract as the
                # intact-control path below), so defer the stop until the
                # parsed prefix has been yielded
                objs = []
                for r, p in zip(raws, poss):
                    try:
                        obj = self._parse_or_dlq(r, p)
                    except ControlTupleExit as e:
                        stop = e
                        break
                    if obj is not None:  # None = quarantined poison record
                        objs.append(self._track(obj, p))
                out = objs if objs else None
            elif hasattr(chunk, "parsed"):
                # columnar chunk: attach the per-record source positions the
                # pull loop snapshotted and track in one vectorized pass
                if chunk.positions is None:
                    chunk.positions = np.asarray(poss, np.int64)
                out = self._track_chunk(chunk)
            else:
                # legacy decoder contract: a plain list of parsed records
                out = [self._track(obj, p)
                       for obj, p in zip(chunk, poss) if obj is not None]
            raws.clear()
            poss.clear()
            return out, stop

        def flush():
            if not raws:
                return
            # ONE decode span per chunk, closed before the hand-off
            if tel is not None:
                with tel.span("decode", query="kafka"):
                    out, stop = decode()
            else:
                out, stop = decode()
            if out is not None and len(out):
                yield out
            if stop is not None:
                raise stop

        # one Python-level iteration per POLL: the source hands whole
        # resequenced batches with per-record positions; only batches that
        # carry a control marker or non-string records drop to the
        # per-record slow path
        for item in self.source.iter_batches():
            if item is STARVED:
                # quiet topic: hand everything buffered downstream so a
                # chunk never waits out dead air (live-mode latency bound =
                # one poll cycle, not one chunk fill)
                yield from flush()
                continue
            vals, positions = item
            if tel is not None:
                with tel.span("decode", query="kafka"):
                    fast = _plain_records(vals)
            else:
                fast = _plain_records(vals)
            if fast:
                # append in chunk-sized slices so the decode-chunk bound
                # holds even when a poll batch exceeds it
                i = 0
                while i < len(vals):
                    take = max(self.bulk_chunk - len(raws), 1)
                    raws.extend(vals[i:i + take])
                    poss.extend(positions[i:i + take])
                    i += take
                    if len(raws) >= self.bulk_chunk:
                        yield from flush()
            else:
                for raw, position in zip(vals, positions):
                    if isinstance(raw, str) and '"control"' not in raw:
                        raws.append(raw)
                        poss.append(position)
                        continue
                    # control candidate or pre-parsed object: flush the
                    # buffered prefix FIRST (arrival order — the commit
                    # sweep's pending deque must stay position-sorted;
                    # records before a stop tuple must reach the pipeline)
                    yield from flush()
                    check_exit_control_tuple(raw)
                    if isinstance(raw, str):
                        # had the marker substring but is not an actual
                        # control tuple — a normal record
                        raws.append(raw)
                        poss.append(position)
                        continue
                    obj = self._parse_or_dlq(raw, position)
                    if obj is not None:
                        yield [self._track(obj, position)]
            if len(raws) >= self.bulk_chunk:
                yield from flush()
        yield from flush()

    def on_window_emitted(self, window_end: int) -> None:
        """Commit the prefix of records fully covered by windows ending at
        or before ``window_end`` (call AFTER the result was produced)."""
        pos = None
        while self._pending and self._pending[0][1] <= window_end:
            pos = self._pending.popleft()[0]
        if pos is not None:
            self.source.commit_to(pos)

    def commit_all(self) -> None:
        """Bounded stream fully drained and flushed: everything consumed is
        reflected in output; commit the source's full position."""
        self._pending.clear()
        self.source.commit_to(self.source.position)


def _jsonable(v):
    """Best-effort JSON projection for WindowResult extras (heatmap ndarrays,
    numpy scalars, query objects): arrays → nested lists, unknowns → str."""
    import numpy as _np

    if isinstance(v, _np.ndarray) or hasattr(v, "__array__"):
        return _np.asarray(v).tolist()
    if isinstance(v, _np.generic):
        return v.item()
    if isinstance(v, (set, frozenset)):
        return sorted(str(x) for x in v)
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


class KafkaWindowSink:
    """Windowed results → output topic with effective exactly-once ACROSS
    process restarts: the output log itself is the recovery state.

    Every record of a window is produced keyed by the window's idempotency
    key ``"start:end:cell"``, followed by ONE commit-marker record
    (key ``__window_commit__:<key>``, value = record count). At startup the
    sink replays the topic's existing MARKER keys to seed its delivered-set,
    so windows re-delivered by the at-least-once source after a crash are
    suppressed even in a fresh process — the in-memory
    :class:`IdempotentWindowSink` upgraded with log-based recovery
    (reference: Flink's checkpoint-coordinated EXACTLY_ONCE producer,
    ``StreamingJob.java:512``). A window interrupted mid-production leaves
    records without a marker and is re-produced in full on restart:
    record-level duplicates are possible for exactly that window, but
    marker-delimited window reads never see a duplicate or partial window.
    """

    MARKER = "__window_commit__:"

    def __init__(self, broker, topic: str, fmt: Optional[str] = None,
                 date_format: Optional[str] = None, delimiter: str = ",",
                 job_id: Optional[str] = None,
                 seed_scan_limit: Optional[int] = None,
                 seed_scan_warn: int = 100_000):
        self.broker = broker
        self.topic = topic
        #: job/query fingerprint folded into every window key: without it,
        #: re-running a DIFFERENT query/config against the same output
        #: topic would find the old run's markers and silently suppress
        #: every window of the new run (an output topic is otherwise bound
        #: to one job configuration forever). None keeps the legacy
        #: un-prefixed keys for single-job topics.
        self.job_id = job_id
        #: bound/flag the startup scan (see _seed_from_log): scan at most
        #: the last ``seed_scan_limit`` records (None = full scan), and
        #: warn once past ``seed_scan_warn`` scanned records — the
        #: uncompacted-topic signal.
        self.seed_scan_limit = seed_scan_limit
        self.seed_scan_warn = seed_scan_warn
        self._enc = KafkaSink(broker, topic, fmt, date_format, delimiter)
        self._tel = _telemetry.active()
        self.delivered = self._seed_from_log()
        self.duplicates_suppressed = 0
        self.windows_produced = 0

    def _seed_from_log(self) -> set:
        """Marker keys already in the topic. NOTE: a full-topic scan — O(1)
        against the shim, but on a real long-lived cluster topic this is a
        full read per driver start. The marker records are keyed, so running
        the output topic log-COMPACTED keeps the scan bounded by the live
        window count; that is the intended production configuration (the
        alternative — trusting only recent markers — could re-produce an
        old window after an unusually long outage). A scan past
        ``seed_scan_warn`` records warns about the compaction risk;
        ``seed_scan_limit`` hard-bounds the scan to the topic TAIL for
        operators who accept the old-window re-produce risk explicitly."""
        import sys as _sys

        seen: set = set()
        end = self.broker.end_offset(self.topic)
        off = 0
        if self.seed_scan_limit is not None and end > self.seed_scan_limit:
            off = end - self.seed_scan_limit
            print(f"warning: output topic '{self.topic}' holds {end} "
                  f"records; seeding the dedup set from the last "
                  f"{self.seed_scan_limit} only — windows committed before "
                  f"offset {off} can be re-produced on re-delivery",
                  file=_sys.stderr)
        scanned = 0
        warned = False
        while True:
            batch = self.broker.fetch(self.topic, off)
            if not batch:
                from spatialflink_tpu.utils.metrics import REGISTRY

                REGISTRY.counter("sink-seed-scan-records").inc(scanned)
                return seen
            for r in batch:
                if isinstance(r.key, str) and r.key.startswith(self.MARKER):
                    seen.add(r.key[len(self.MARKER):])
                # max(): a degraded transport can deliver the batch
                # permuted — never let the scan cursor move backward
                off = max(off, r.offset + 1)
                scanned += 1
            if scanned > self.seed_scan_warn and not warned:
                warned = True
                print(f"warning: dedup seed scan of output topic "
                      f"'{self.topic}' passed {self.seed_scan_warn} records "
                      "and is still going — the topic looks uncompacted; "
                      "run it log-compacted (marker records are keyed) or "
                      "bound the scan with seed_scan_limit",
                      file=_sys.stderr)

    def window_key(self, result) -> str:
        cell = result.extras.get("cell") if hasattr(result, "extras") else None
        base = (f"{getattr(result, 'window_start', None)}:"
                f"{getattr(result, 'window_end', None)}:{cell}")
        return f"{self.job_id}:{base}" if self.job_id else base

    def emit(self, result) -> None:
        if self._tel is not None:
            # per-window producing time under the sink stage (the span also
            # covers the dedup check — both are the sink's cost)
            t0 = time.time()
            with self._tel.span("sink", query="kafka"):
                self._emit(result)
            t1 = time.time()
            if hasattr(result, "window_start"):
                # the window's downstream sink-commit budget (latency
                # plane), plus — with tracing on — the lineage note that
                # closes the trace: records + marker are on the output
                # topic (suppressed duplicates included — their dedup
                # check IS the commit-path cost they paid)
                self._tel.latency.note_downstream(
                    "sink-commit", result.window_start, t0, t1)
                if self._tel.traces is not None:
                    self._tel.traces.note_any(result.window_start,
                                              "sink-commit", t0, t1)
        else:
            self._emit(result)

    def _emit(self, result) -> None:
        wk = self.window_key(result)
        if wk in self.delivered:
            self.duplicates_suppressed += 1
            return
        if self.job_id and wk.split(":", 1)[1] in self.delivered:
            # upgrade continuity: a PRE-fingerprint marker (bare
            # start:end:cell, written before job prefixes existed) still
            # covers this window — without this, the first restart after
            # an upgrade would re-produce every window already in the
            # topic. New markers are always written prefixed, so the
            # legacy cross-job ambiguity dies out with the old markers.
            self.duplicates_suppressed += 1
            return
        # flatten across the multi-query axis (one list per query)
        recs = (result.flat_records() if hasattr(result, "flat_records")
                else result.records)
        n = 0
        if recs and type(self.broker) is InMemoryBroker:
            # batched production (one lock/timestamp for the window's
            # records); wrapped brokers — chaos, supervised, real cluster —
            # keep the per-record path so their per-produce semantics
            # (fault injection, retries, acks) cover every record.
            # Columnar selections (PointRows) serialize straight from their
            # arrays — no per-record Python objects on the sink path.
            vals = None
            sb = getattr(recs, "serialize_batch", None)
            if sb is not None and self._enc.fmt:
                vals = sb(self._enc.fmt, delimiter=self._enc.delimiter,
                          date_format=self._enc.date_format)
            if vals is None:
                enc = self._enc._encode
                vals = [enc(r) for r in recs]
            n = len(vals)
            self.broker.produce_many(self.topic, vals, key=wk)
        else:
            for rec in recs:
                self.broker.produce(self.topic, self._enc._encode(rec),
                                    key=wk)
                n += 1
        extras = {k: v for k, v in getattr(result, "extras", {}).items()
                  if k != "latency_ms"}
        if extras:
            # aggregate-style windows carry their payload in extras
            # (tAggregate heatmaps, tStats rows, multi-query metadata); ship
            # it as ONE JSON summary record under the window key so the
            # topic — not just stdout — holds the full result
            self.broker.produce(self.topic, json.dumps({
                "window": [result.window_start, result.window_end],
                **{k: _jsonable(v) for k, v in extras.items()}}), key=wk)
            n += 1
        # marker value = how many records were produced under this key
        self.broker.produce(self.topic, str(n), key=self.MARKER + wk)
        self.delivered.add(wk)
        self.windows_produced += 1

    def close(self) -> None:
        pass


class KafkaLatencySink:
    """Per-record latency millis to a topic (reference:
    ``HelperClass.LatencySinkPoint``/``LatencySinkLong``,
    ``utils/HelperClass.java:455-529``): value = now - ingestion_time (or
    event time)."""

    def __init__(self, broker: InMemoryBroker, topic: str,
                 use_event_time: bool = False):
        self.broker = broker
        self.topic = topic
        self.use_event_time = use_event_time

    def emit(self, record) -> None:
        now = time.time() * 1000
        base = record.timestamp if self.use_event_time else getattr(
            record, "ingestion_time", record.timestamp)
        self.broker.produce(self.topic, now - base,
                            key=getattr(record, "obj_id", None))

    def close(self) -> None:
        pass


class RealKafkaBroker:
    """kafka-python-backed implementation of the broker surface
    (produce/fetch/commit/committed/end_offset) consumed by
    :class:`KafkaSource`/:class:`KafkaSink` — the adapter that swaps a real
    cluster in for :class:`InMemoryBroker` without touching the pipelines
    (reference consumers at ``StreamingJob.java:473``, producer at ``:512``).

    Topic-as-one-log mapping: the shim models a topic as a single ordered
    log, so the adapter pins every topic to **partition 0** (the reference's
    driver likewise treats each topic as one stream; scale-out happens in the
    operator mesh, not the partition count). Offsets commit through the
    consumer-group API, so a restarted group resumes where
    :class:`KafkaSource` committed — the same at-least-once contract the shim
    provides, with :class:`IdempotentWindowSink` upgrading it to effective
    exactly-once downstream.

    VERIFICATION BOUNDARY (permanent, environmental): this adapter is
    exercised against an injected fake of the kafka-python client API (see
    ``connect_kafka``'s ``kafka_module`` seam and tests/test_kafka.py) —
    the wire path has never run here, because the build environment has
    neither the kafka-python package nor any broker process to speak the
    Kafka protocol to (zero egress; vendoring a wire client would still
    leave nothing real on the other end of the socket). First use against
    a real cluster should smoke-test produce→fetch→commit→committed on a
    scratch topic before trusting a pipeline to it.
    """

    def __init__(self, kafka_module, bootstrap_servers: str, *,
                 produce_timeout_s: float = 30.0, poll_timeout_ms: int = 500,
                 fetch_retries: int = 20):
        self._kafka = kafka_module
        self.bootstrap = bootstrap_servers
        self.produce_timeout_s = produce_timeout_s
        self.poll_timeout_ms = poll_timeout_ms
        self.fetch_retries = fetch_retries
        self._producer = None
        self._fetch_c = None                      # group-less, for fetch/end
        self._group_c: Dict[str, Any] = {}        # group id -> consumer
        self._commit_hwm: Dict[Tuple[str, str], int] = {}  # (topic, group)

    # ------------------------------ helpers -------------------------- #

    @staticmethod
    def _to_bytes(v) -> Optional[bytes]:
        if v is None:
            return None
        if isinstance(v, bytes):
            return v
        return str(v).encode("utf-8")

    @staticmethod
    def _to_str(v):
        return v.decode("utf-8", errors="replace") if isinstance(v, bytes) else v

    def _tp(self, topic: str):
        return self._kafka.TopicPartition(topic, 0)

    def _oam(self, offset: int):
        cls = getattr(self._kafka, "OffsetAndMetadata", None)
        if cls is None:
            cls = self._kafka.structs.OffsetAndMetadata
        try:
            return cls(offset, "")
        except TypeError:  # newer kafka-python adds leader_epoch
            return cls(offset, "", -1)

    def _get_producer(self):
        if self._producer is None:
            self._producer = self._kafka.KafkaProducer(
                bootstrap_servers=self.bootstrap)
        return self._producer

    def _fetch_consumer(self):
        if self._fetch_c is None:
            self._fetch_c = self._kafka.KafkaConsumer(
                bootstrap_servers=self.bootstrap, enable_auto_commit=False)
        return self._fetch_c

    def _group_consumer(self, group: str):
        if group not in self._group_c:
            self._group_c[group] = self._kafka.KafkaConsumer(
                bootstrap_servers=self.bootstrap, group_id=group,
                enable_auto_commit=False)
        return self._group_c[group]

    # ------------------------------ broker surface ------------------- #

    def produce(self, topic: str, value, key: Optional[str] = None,
                timestamp_ms: Optional[int] = None) -> int:
        # partition=0 pins the producer to the same partition the consumer
        # side reads — without it a multi-partition topic would scatter
        # records where fetch()/end_offset() never look
        fut = self._get_producer().send(
            topic, value=self._to_bytes(value), key=self._to_bytes(key),
            partition=0, timestamp_ms=timestamp_ms)
        # blocking .get() = acknowledged write, the adapter's at-least-once
        # half (re-raise on broker error instead of dropping silently)
        return fut.get(timeout=self.produce_timeout_s).offset

    def fetch(self, topic: str, offset: int, max_records: int = 500
              ) -> List[BrokerRecord]:
        """An empty return means END OF TOPIC (``offset >= end_offset``),
        matching the shim contract KafkaSource relies on for ``stop_at_end``.
        A real consumer's poll() legitimately returns nothing while fetch
        sessions warm up or the broker hiccups, so empty polls are retried
        (up to ``fetch_retries``) as long as records exist past ``offset`` —
        otherwise a cold first poll would masquerade as stream end and the
        source would silently drop the topic's tail."""
        c = self._fetch_consumer()
        tp = self._tp(topic)
        c.assign([tp])
        c.seek(tp, offset)
        out: List[BrokerRecord] = []
        for _ in range(max(1, self.fetch_retries)):
            polled = c.poll(timeout_ms=self.poll_timeout_ms,
                            max_records=max_records)
            for recs in polled.values():
                for r in recs:
                    out.append(BrokerRecord(
                        offset=r.offset, key=self._to_str(r.key),
                        value=self._to_str(r.value),
                        timestamp_ms=getattr(r, "timestamp", 0) or 0))
            if out or offset >= self.end_offset(topic):
                return out
        raise TimeoutError(
            f"kafka fetch: {topic}@{offset} < end_offset but "
            f"{self.fetch_retries} polls returned no records")

    def commit(self, topic: str, group: str, next_offset: int) -> None:
        # monotonic like the shim: a slow replica must not rewind the group.
        # The high-water mark is cached locally (seeded from the broker on
        # first touch) — this adapter owns its group consumers, so one
        # committed() RPC per (topic, group) suffices instead of one per
        # commit on the hot path
        if next_offset <= self.committed(topic, group):
            return
        self._group_consumer(group).commit(
            {self._tp(topic): self._oam(next_offset)})
        self._commit_hwm[(topic, group)] = next_offset

    def committed(self, topic: str, group: str) -> int:
        hwm = self._commit_hwm.get((topic, group))
        if hwm is not None:
            return hwm
        off = self._group_consumer(group).committed(self._tp(topic))
        hwm = 0 if off is None else int(getattr(off, "offset", off))
        self._commit_hwm[(topic, group)] = hwm
        return hwm

    def end_offset(self, topic: str) -> int:
        c = self._fetch_consumer()
        tp = self._tp(topic)
        return int(c.end_offsets([tp])[tp])

    def close(self) -> None:
        if self._producer is not None:
            self._producer.flush()
            self._producer.close()
        for c in ([self._fetch_c] if self._fetch_c else []) + list(
                self._group_c.values()):
            c.close()


#: process-shared in-memory brokers, keyed by their ``memory://name`` URL —
#: a producer thread, a test, and a driver ``main()`` call in the same
#: process all reach the same log (and a re-run of ``main()`` after a
#: simulated crash finds its committed offsets again)
_MEMORY_BROKERS: Dict[str, InMemoryBroker] = {}
_MEMORY_BROKERS_LOCK = threading.Lock()


def resolve_broker(bootstrap_servers: str, kafka_module=None):
    """Broker by bootstrap string: ``memory://<name>`` → the process-shared
    :class:`InMemoryBroker` registered under that URL (created on first
    use); anything else → the real-cluster adapter via
    :func:`connect_kafka`. This is how the driver's ``--kafka`` mode picks
    its transport from ``kafkaBootStrapServers``."""
    if bootstrap_servers.startswith("memory://"):
        with _MEMORY_BROKERS_LOCK:
            return _MEMORY_BROKERS.setdefault(bootstrap_servers,
                                              InMemoryBroker())
    return connect_kafka(bootstrap_servers, kafka_module)


def reset_memory_brokers() -> None:
    """Drop every registered ``memory://`` broker (test isolation)."""
    with _MEMORY_BROKERS_LOCK:
        _MEMORY_BROKERS.clear()


def connect_kafka(bootstrap_servers: str, kafka_module=None) -> RealKafkaBroker:
    """Real-broker adapter against the kafka-python client API.

    ``kafka_module`` is the injection seam: tests pass a fake implementing
    the same surface (KafkaProducer/KafkaConsumer/TopicPartition/
    OffsetAndMetadata); production leaves it None to import kafka-python,
    raising RuntimeError when the package is absent (it is not installed in
    this image — use :class:`InMemoryBroker` for local pipelines).
    """
    if kafka_module is None:
        try:
            import kafka as kafka_module  # type: ignore
        except ImportError as e:
            raise RuntimeError(
                "connect_kafka requires the kafka-python package, which is "
                "not installed in this environment; use InMemoryBroker for "
                "local pipelines and tests.") from e
    return RealKafkaBroker(kafka_module, bootstrap_servers)
