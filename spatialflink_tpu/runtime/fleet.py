"""Fleet worker plumbing: the pieces a supervised multi-worker run adds
INSIDE each worker process, plus the global-merge machinery both sides
share.

The reference runs GeoFlink at Flink parallelism 30: a JobManager places
keyed subtasks on TaskManagers and restarts the ones that die. The
rebuild's equivalent (``runtime/fleetsup.py``) spawns N full pipelines —
each worker is the EXISTING single-process driver with its own PaneCache,
checkpoint manifest, and opserver on an ephemeral port — and partitions
the stream by grid leaf (PR 8's leaf layout as the placement unit). This
module is the worker half and the shared contracts:

- :class:`TailingReplaySource` — a file-replay source that FOLLOWS its
  partition file while the supervisor is still routing records into it,
  and treats the durable ``partition.done`` marker as EOF. Resume-aware
  exactly like ``FileReplaySource`` (``skip``/``limit`` compose with
  ``CheckpointTap``), and shutdown-aware: a SIGTERM that lands while the
  source is idle raises :class:`~spatialflink_tpu.utils.metrics
  .GracefulShutdown` so the drain path runs instead of a hang.
- :class:`HeartbeatWriter` — a daemon thread touching a heartbeat file
  every interval; the supervisor's liveness probe that works even when
  the worker's pipeline thread is busy inside a kernel dispatch.
- :class:`OutboxWriter` / :func:`read_outbox` — the worker's durable
  per-window emission log for the global merge stage: one canonical JSON
  line per emitted window (fingerprinted, flushed before the journal
  records the window), so the supervisor can merge windowAll results
  without re-parsing worker stdout. Appended only for windows the
  emitted-window journal has NOT seen — a crash between the outbox
  append and the journal record re-appends a canonically identical line
  on resume (identity = window key + records fingerprint; the
  observability plane's ``lat`` sidecar may differ across incarnations
  and is excluded from both), which the merge dedups by window key (and
  cross-checks by fingerprint): exactly-once output identity across a
  kill.
- :class:`FleetManifest` — the supervisor's durable state (leaf→worker
  assignment, repartition epoch, restart counts, fence tokens, rescale
  and quarantine history) with the ``snapshot``/``restore`` pair the
  checkpoint-coverage linter rule proves field-by-field.
- :class:`WorkerContext` — the driver's one handle on all of the above
  when it runs under ``--fleet-role worker``.

**Fencing epochs.** Heartbeat-kill-respawn alone cannot contain a
*zombie*: a stalled-but-alive worker that resumes writing after the
supervisor presumed it dead and spawned a successor. The fence layer
makes that impossible by construction: the manifest carries a monotonic
fence token per worker slot, every outbox line and heartbeat is stamped
with the writer's fence, and a respawn's FIRST act is bumping the token
while recording the predecessor's durable outbox/journal byte sizes
(``fleet_fence_log``). A row stamped with fence *f* is a zombie row iff
its byte offset is at-or-past the cutoff recorded when fence *f*+1 was
issued — everything the predecessor durably wrote BEFORE it was
superseded stays valid, everything after is dropped at merge (counted
and evented, never a run-aborting :class:`FleetMergeError`). The
journal applies the same per-fence cutoff rule at load, so a successor
re-emits exactly the windows whose journal lines were zombie-written —
the outbox-before-journal write order guarantees those re-emissions
dedup against the predecessor's (still valid) pre-bump rows.

Merging reuses the per-family pane/shard merge twins through
:func:`~spatialflink_tpu.operators.base.merge_window_records` — see
:func:`merge_outboxes`.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from typing import Dict, Iterator, List, Optional, Tuple

from spatialflink_tpu.runtime.checkpoint import (atomic_write_json,
                                                 read_json)
from spatialflink_tpu.utils import telemetry as _telemetry
from spatialflink_tpu.utils.metrics import (REGISTRY, GracefulShutdown,
                                            shutdown_requested)

#: files inside one worker's fleet directory (``<fleet-dir>/worker<i>/``)
PARTITION_FILE = "partition.ndjson"
DONE_MARKER = "partition.done"
OUTBOX_FILE = "outbox.jsonl"
HEARTBEAT_FILE = "heartbeat"
URL_FILE = "opserver.url"
RUNS_FILE = "runs.jsonl"
#: the worker's own event ring, one line per event tagged with the fence
#: of the incarnation that said it (read when its opserver is gone)
WORKER_EVENTS_FILE = "events.jsonl"
#: supervisor-owned files at the fleet root
MANIFEST_FILE = "fleet.json"
MERGED_FILE = "merged.jsonl"
RESULT_FILE = "fleet_result.json"
#: observability-plane files (absent under ``--fleet-plane off``)
EVENTS_FILE = "fleet_events.jsonl"
LATENCY_FILE = "fleet_latency.json"
#: the supervisor's fleet-view snapshot dropped next to a dead worker's
#: flight-recorder bundles (``worker<i>/postmortem/``)
FLEET_VIEW_FILE = "fleet_view.json"


def worker_dir(fleet_dir: str, worker_id: int) -> str:
    return os.path.join(fleet_dir, f"worker{int(worker_id)}")


class FleetMergeError(RuntimeError):
    """Two outbox lines for the SAME window key disagree on content — the
    exactly-once identity the journal + canonical outbox guarantee was
    violated (or two different jobs shared a fleet dir)."""


# --------------------------------------------------------------------- #
# tailing partition source


class TailingReplaySource:
    """Replay a partition file the supervisor is still appending to.

    Yields complete stripped lines; a partial tail line (the supervisor
    flushes whole lines, but the OS may expose a torn read mid-write) is
    held back until its newline arrives. EOF is the durable
    ``partition.done`` marker: once observed, one final read drains
    anything appended before the marker, then iteration ends — so a
    bounded fleet run terminates exactly like a file replay.

    ``skip``/``limit`` mirror :class:`~spatialflink_tpu.streams.sources
    .FileReplaySource` so ``CheckpointTap`` resume semantics carry over
    unchanged. A writer stall is handled in two stages so a temporarily
    paused supervisor route (a quarantine drain, a rescale barrier)
    doesn't kill an innocent worker: every ``stall_timeout_s`` of
    silence emits a ``partition-stall`` event (and bumps the
    ``partition-stall`` counter) but keeps polling; only
    ``stall_deadline_s`` (default 4× the timeout) with no new data and
    no marker raises — a dead supervisor must not leave orphan workers
    spinning forever."""

    def __init__(self, path: str, done_path: str, *,
                 limit: Optional[int] = None, skip: int = 0,
                 poll_s: float = 0.05, stall_timeout_s: float = 300.0,
                 stall_deadline_s: Optional[float] = None):
        self._path = path
        self._done_path = done_path
        self._limit = limit
        self._skip = max(0, int(skip))
        self._poll_s = poll_s
        self._stall_timeout_s = float(stall_timeout_s)
        self._stall_deadline_s = (float(stall_deadline_s)
                                  if stall_deadline_s is not None
                                  else 4.0 * float(stall_timeout_s))
        self._warn_at = 0.0
        self.stall_events = 0

    def __iter__(self) -> Iterator[str]:
        if self._limit is not None and self._limit <= 0:
            return
        f = None
        tail = ""
        skipped = 0
        yielded = 0
        last_data = time.monotonic()
        try:
            while True:
                if f is None:
                    if os.path.exists(self._path):
                        f = open(self._path)
                    elif os.path.exists(self._done_path):
                        return  # empty partition, already final
                    else:
                        self._wait(last_data)
                        continue
                chunk = f.read(1 << 16)
                if chunk:
                    last_data = time.monotonic()
                    tail += chunk
                    lines = tail.split("\n")
                    tail = lines.pop()
                    for line in lines:
                        if not line:
                            continue
                        if skipped < self._skip:
                            skipped += 1
                            continue
                        yield line
                        yielded += 1
                        if (self._limit is not None
                                and yielded >= self._limit):
                            return
                    continue
                # at EOF: the marker is written AFTER the final flush, so
                # observing it means one more read drains everything
                if os.path.exists(self._done_path):
                    chunk = f.read(1 << 16)
                    if chunk:
                        last_data = time.monotonic()
                        tail += chunk
                        continue
                    if tail.strip() and skipped >= self._skip:
                        yield tail.strip()  # defensively drain a torn tail
                    return
                self._wait(last_data)
        finally:
            if f is not None:
                f.close()

    def _wait(self, last_data: float) -> None:
        if shutdown_requested():
            raise GracefulShutdown(
                "shutdown requested while tailing the partition file")
        stalled = time.monotonic() - last_data
        if stalled > self._stall_deadline_s:
            raise RuntimeError(
                f"partition file {self._path} stalled for "
                f"{stalled:.1f}s (deadline {self._stall_deadline_s:g}s) "
                "with no done marker — supervisor dead?")
        if (stalled >= self._stall_timeout_s
                and time.monotonic() >= self._warn_at):
            # bounded retry: complain periodically, keep polling — the
            # route may merely be paused (quarantine drain, rescale
            # barrier); only the hard deadline above gives up
            self._warn_at = time.monotonic() + self._stall_timeout_s
            self.stall_events += 1
            REGISTRY.counter("partition-stall").inc()
            _telemetry.emit_event("partition-stall", path=self._path,
                                  stalled_s=round(stalled, 2),
                                  deadline_s=self._stall_deadline_s)
        time.sleep(self._poll_s)


# --------------------------------------------------------------------- #
# heartbeat


class HeartbeatWriter:
    """Write ``path`` every ``interval_s`` from a daemon thread. The
    supervisor reads the file's mtime age as the liveness signal — a
    worker wedged hard enough to stop a daemon thread (or SIGKILLed) goes
    stale within one interval.

    Each beat atomically replaces the file with a fence-stamped JSON doc
    (``{fence, pid, ts_ms}``): a zombie predecessor and its successor
    share the path, so the supervisor must be able to tell whose beat it
    is reading — a beat carrying a superseded fence is not liveness. The
    write goes through a pid-suffixed temp file so concurrent writers
    never clobber each other's temp, and ``os.replace`` keeps the read
    side tear-free. ``gate`` is the fault layer's wedge hook
    (:class:`~spatialflink_tpu.runtime.faults.StallFault`): while it
    returns True, beats are skipped — the injectable gray failure."""

    def __init__(self, path: str, interval_s: float = 1.0, *,
                 fence: int = 0, gate=None):
        self._path = path
        self._interval_s = max(0.05, float(interval_s))
        self._fence = int(fence)
        self._gate = gate
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "HeartbeatWriter":
        self._touch()
        self._thread = threading.Thread(target=self._loop,
                                        name="fleet-heartbeat", daemon=True)
        self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop.wait(self._interval_s):
            self._touch()

    def _touch(self) -> None:
        if self._gate is not None and self._gate():
            return  # injected gray failure: wedged, not dead
        tmp = f"{self._path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                f.write(json.dumps({"fence": self._fence,
                                    "pid": os.getpid(),
                                    "ts_ms": int(time.time() * 1000)},
                                   sort_keys=True))
            os.replace(tmp, self._path)
        except OSError:
            pass  # a missed beat is indistinguishable from a slow one

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None


def heartbeat_age_s(path: str, *,
                    fence: Optional[int] = None) -> Optional[float]:
    """Seconds since the worker last touched its heartbeat, or None when
    the file does not exist yet (worker still booting).

    With ``fence`` given, the beat's content is checked: a beat stamped
    with an OLDER fence than expected is a superseded incarnation's
    write, not liveness — it reads as None (successor still booting).
    Legacy/unparseable content falls back to plain mtime age."""
    try:
        age = max(0.0, time.time() - os.stat(path).st_mtime)
    except OSError:
        return None
    if fence is not None:
        try:
            with open(path) as f:
                beat = json.loads(f.read())
            if int(beat.get("fence", 0)) < int(fence):
                return None  # zombie beat: the expected fence never wrote
        except (OSError, ValueError, TypeError, AttributeError):
            pass  # legacy mtime-only heartbeat (or torn read): age stands
    return age


# --------------------------------------------------------------------- #
# canonical outbox


def _record_encoder():
    from spatialflink_tpu.streams.formats import serialize_spatial

    def encode(rec):
        try:
            return serialize_spatial(rec, "GeoJSON", date_format=None)
        except (AttributeError, TypeError, ValueError):
            return json.dumps(rec, sort_keys=True, default=str)

    return encode


def window_key(result) -> str:
    """The journal's idempotent window-sink key (``start:end:cell``) —
    the outbox keys windows identically so the two logs cross-check."""
    from spatialflink_tpu.runtime.checkpoint import EmittedWindowJournal

    return EmittedWindowJournal.key(result)


def canonical_window_doc(result, family: str,
                         lat: Optional[dict] = None,
                         fence: int = 0) -> dict:
    """One outbox line: the window's identity plus its records in a
    canonical, order-independent serialization (selection families sort
    encoded records; kNN keeps its (distance, id) top-k order, which IS
    canonical). The fingerprint seals the content so duplicate appends
    across a crash are provably identical.

    ``lat`` is the observability plane's lineage SIDECAR (the worker's
    stage budget + emit wall stamp, :func:`lat_sidecar`). It rides the
    line but is excluded from the fingerprint — the fp is computed over
    the records alone, BEFORE the sidecar is attached — and
    :func:`merged_table_digest` never reads it, so exactly-once identity
    and the merged digest are plane-independent: a resumed incarnation
    re-emitting a window with a different budget still dedups cleanly,
    and ``--fleet-plane off`` produces a byte-identical merged table.

    ``fence`` stamps the line with the writer incarnation's fence token
    (also outside the fingerprint — the same window re-emitted by a
    successor incarnation must still dedup against the predecessor's
    valid rows). Fence 0 (single-process runs, pre-fence outboxes) is
    not stamped, keeping those lines byte-identical to before."""
    if family == "knn":
        records = [[str(oid), float(d)] for oid, d in result.records]
    else:
        enc = _record_encoder()
        records = sorted(enc(r) for r in result.flat_records())
    payload = json.dumps(records, sort_keys=True)
    doc = {
        "key": window_key(result),
        "window": [int(result.window_start), int(result.window_end)],
        "cell": result.extras.get("cell"),
        "count": len(records),
        "records": records,
        "fp": hashlib.sha256(payload.encode()).hexdigest()[:16],
    }
    if lat is not None:
        doc["lat"] = lat
    if fence:
        doc["fence"] = int(fence)
    return doc


#: the sidecar's allowed stage keys: the worker's sum-to-total chain
#: (downstream sink stages run after emit and would break the fleet
#: chain's consecutive-interval construction)
_SIDECAR_STAGES = ("buffer", "queue", "dispatch", "inflight", "merge",
                   "emit")


def lat_sidecar(budget_row: Optional[dict]) -> Optional[dict]:
    """Filter one :meth:`~spatialflink_tpu.utils.latencyplane
    .LatencyPlane.budget_row` into the outbox lineage sidecar: the
    ingest/emit wall stamps plus the CHAIN stages only, so the
    supervisor can extend the chain with ``outbox-visible -> merge ->
    merged-emit`` and keep the sums-to-total invariant end to end.
    Returns None for windows without an ingest stamp — they cannot
    anchor a record→merged-emit measurement."""
    if not budget_row or budget_row.get("first_ingest_ms") is None:
        return None
    stages = budget_row.get("stages") or {}
    return {
        "first_ingest_ms": budget_row["first_ingest_ms"],
        "emitted_ms": budget_row.get("emitted_ms"),
        "record_emit_ms": budget_row.get("record_emit_ms"),
        "stages": {s: stages[s] for s in _SIDECAR_STAGES if s in stages},
    }


class OutboxWriter:
    """Append-only canonical window log, one flushed JSON line per emitted
    window. Flushed BEFORE the emitted-window journal records the window:
    a ``kill -9`` between the two re-appends a canonically identical line
    on resume (the journal did not suppress it; only the diagnostic
    ``lat`` sidecar — outside the fingerprint — may differ), and
    :func:`read_outbox` dedups by key — never a lost window, never a
    divergent one."""

    def __init__(self, path: str):
        self.path = path
        self._f = open(path, "a")
        self.appended = 0

    def append(self, doc: dict) -> None:
        self._f.write(json.dumps(doc, sort_keys=True) + "\n")
        self._f.flush()
        self.appended += 1

    def close(self) -> None:
        self._f.close()


def read_outbox(path: str, *,
                fence_cutoffs: Optional[Dict[int, int]] = None,
                stats: Optional[dict] = None) -> Dict[str, dict]:
    """Parse one worker's outbox into ``key -> doc``, deduplicating the
    crash-replay duplicates (first occurrence wins) and raising
    :class:`FleetMergeError` if a same-fence duplicate DISAGREES — that
    would mean a resumed worker emitted different window contents than
    its pre-crash incarnation, exactly the bug the exactly-once
    machinery exists to make impossible.

    ``fence_cutoffs`` maps a superseded fence token to the byte size the
    outbox had when that fence was bumped away (the manifest's
    ``fleet_fence_log``): a row stamped with fence *f* that STARTS
    at-or-past ``fence_cutoffs[f]`` was written by a zombie — an
    incarnation still running after the supervisor superseded it — and
    is dropped, never merged, never an error. Rows without a fence field
    are fence 0 (pre-fence outboxes stay readable). Cross-fence
    disagreement on a window's content keeps the NEWEST fence's doc and
    counts a conflict instead of aborting — the superseded side is by
    definition the less trusted writer. ``stats``, when given, receives
    ``stale_fence_rows`` / ``fence_conflicts`` counts (added to any
    existing values, so one dict can accumulate across workers)."""
    out: Dict[str, dict] = {}
    fences: Dict[str, int] = {}
    stale = 0
    conflicts = 0
    cutoffs = fence_cutoffs or {}
    if not os.path.exists(path):
        if stats is not None:
            stats["stale_fence_rows"] = stats.get("stale_fence_rows", 0)
            stats["fence_conflicts"] = stats.get("fence_conflicts", 0)
        return out
    with open(path, "rb") as f:
        pos = 0
        for raw in f:
            start = pos
            pos += len(raw)
            line = raw.decode("utf-8", "replace").strip()
            if not line:
                continue
            try:
                doc = json.loads(line)
            except ValueError:
                continue  # torn tail from a kill mid-write: replayed later
            key = doc.get("key")
            if key is None:
                continue
            fence = int(doc.get("fence") or 0)
            cut = cutoffs.get(fence)
            if cut is not None and start >= int(cut):
                stale += 1  # zombie write: fence superseded before this row
                continue
            prev = out.get(key)
            if prev is None:
                out[key] = doc
                fences[key] = fence
            elif prev.get("fp") != doc.get("fp"):
                kept = fences.get(key, 0)
                if kept == fence:
                    raise FleetMergeError(
                        f"outbox {path}: window {key} re-emitted with "
                        f"different content (fp {prev.get('fp')} vs "
                        f"{doc.get('fp')}) — exactly-once identity "
                        "violated")
                conflicts += 1
                if fence > kept:
                    out[key] = doc
                    fences[key] = fence
    if stats is not None:
        stats["stale_fence_rows"] = (
            stats.get("stale_fence_rows", 0) + stale)
        stats["fence_conflicts"] = (
            stats.get("fence_conflicts", 0) + conflicts)
    return out


# --------------------------------------------------------------------- #
# global merge


def merge_outboxes(per_worker: Dict[int, Dict[str, dict]], family: str,
                   *, k: Optional[int] = None) -> List[dict]:
    """The fleet's global merge stage: combine every worker's deduped
    outbox into the windowAll table a single unpartitioned run would have
    produced, through the per-family merge seam
    (:func:`~spatialflink_tpu.operators.base.merge_window_records`).
    Workers merge in worker-id order and selection-family unions re-sort,
    so the result is independent of BOTH the leaf assignment and emission
    timing — the property the identity tests pin."""
    from spatialflink_tpu.operators.base import merge_window_records

    by_key: Dict[str, List[Tuple[int, dict]]] = {}
    for wid in sorted(per_worker):
        for key, doc in per_worker[wid].items():
            by_key.setdefault(key, []).append((wid, doc))
    merged: List[dict] = []
    for key, docs in by_key.items():
        parts = [d["records"] for _, d in docs]
        if family == "knn":
            records = [[str(oid), float(d)] for oid, d in
                       merge_window_records(
                           family, [[(r[0], r[1]) for r in p]
                                    for p in parts], k=k, tie_key=str)]
        else:
            records = sorted(merge_window_records(family, parts))
        first = docs[0][1]
        merged.append({
            "key": key,
            "window": first["window"],
            "cell": first.get("cell"),
            "count": len(records),
            "records": records,
            "workers": [wid for wid, _ in docs],
        })
    merged.sort(key=lambda d: (d["window"][0], d["window"][1],
                               str(d.get("cell"))))
    return merged


def merged_table_digest(merged: List[dict]) -> str:
    """Stable content digest of the merged window table (identity column
    excludes which workers contributed — two fleets with different leaf
    assignments must digest identically)."""
    canon = [{"key": d["key"], "records": d["records"]} for d in merged]
    return hashlib.sha256(
        json.dumps(canon, sort_keys=True).encode()).hexdigest()


# --------------------------------------------------------------------- #
# fleet manifest (supervisor durable state)


def fence_cutoffs_from(state: Optional[dict], worker: int) -> Dict[int, dict]:
    """Project a manifest snapshot's ``fence_log`` into one worker's
    superseded-fence byte cutoffs: ``{old_fence: {"outbox": bytes,
    "journal": bytes}}``. An entry issuing fence *f* records the durable
    sizes at the instant fence *f*−1 was superseded — anything a fence
    *f*−1 writer appends past those offsets is a zombie write. Shared by
    the supervisor's merge, the worker's journal load, and the doctor
    (which reads the raw ``fleet.json``)."""
    out: Dict[int, dict] = {}
    for e in (state or {}).get("fence_log") or []:
        try:
            if int(e.get("worker", -1)) != int(worker):
                continue
            f = int(e.get("fence", 0))
        except (TypeError, ValueError):
            continue
        if f > 0:
            out[f - 1] = {"outbox": int(e.get("outbox_bytes", 0)),
                          "journal": int(e.get("journal_bytes", 0))}
    return out


class FleetManifest:
    """The supervisor's durable state: leaf→worker assignment, the
    repartition epoch, per-worker restart counts, per-slot fence tokens
    (with the byte-offset log that defines zombie-row validity), and the
    rescale/quarantine history, written atomically to
    ``<fleet-dir>/fleet.json`` after every mutation that must survive
    a supervisor crash. The ``snapshot``/``restore`` pair is the same
    contract the checkpoint coordinator registers — and the
    checkpoint-coverage linter rule proves every ``fleet_*`` field is
    carried by both, so a field added later cannot silently stop being
    durable."""

    def __init__(self, path: str):
        self.path = path
        self.fleet_assignment: Dict[int, int] = {}
        self.fleet_epoch = 0
        self.fleet_restarts: Dict[int, int] = {}
        self.fleet_fences: Dict[int, int] = {}
        self.fleet_fence_log: List[dict] = []
        self.fleet_rescale_log: List[dict] = []
        self.fleet_quarantine_log: List[dict] = []
        loaded = read_json(path)
        if loaded:
            self.restore(loaded)

    def assign(self, leaf: int, worker: int) -> None:
        self.fleet_assignment[int(leaf)] = int(worker)

    def assign_all(self, assignment: Dict[int, int]) -> None:
        for leaf, worker in assignment.items():
            self.fleet_assignment[int(leaf)] = int(worker)

    def advance_epoch(self) -> int:
        self.fleet_epoch += 1
        return self.fleet_epoch

    def note_restart(self, worker: int) -> int:
        w = int(worker)
        self.fleet_restarts[w] = self.fleet_restarts.get(w, 0) + 1
        return self.fleet_restarts[w]

    def fence_of(self, worker: int) -> int:
        return self.fleet_fences.get(int(worker), 0)

    def bump_fence(self, worker: int, *, outbox_bytes: int = 0,
                   journal_bytes: int = 0,
                   reason: str = "respawn") -> int:
        """Supersede worker ``worker``'s current incarnation: issue the
        next fence token and record the predecessor's durable outbox and
        journal byte sizes — the cutoffs past which any write stamped
        with the OLD fence is provably a zombie's. Called by the
        supervisor BEFORE spawning the successor, so the containment
        holds from the successor's first instant."""
        w = int(worker)
        nf = self.fleet_fences.get(w, 0) + 1
        self.fleet_fences[w] = nf
        self.fleet_fence_log.append({
            "ts_ms": int(time.time() * 1000), "worker": w, "fence": nf,
            "outbox_bytes": int(outbox_bytes),
            "journal_bytes": int(journal_bytes), "reason": reason})
        return nf

    def fence_cutoffs(self, worker: int) -> Dict[int, dict]:
        """This worker's superseded-fence byte cutoffs (see
        :func:`fence_cutoffs_from`)."""
        return fence_cutoffs_from({"fence_log": self.fleet_fence_log},
                                  worker)

    def note_rescale(self, *, n_from: int, n_to: int, at_records: int,
                     epoch: int) -> None:
        self.fleet_rescale_log.append({
            "ts_ms": int(time.time() * 1000), "n_from": int(n_from),
            "n_to": int(n_to), "at_records": int(at_records),
            "epoch": int(epoch)})

    def note_quarantine(self, worker: int, action: str,
                        **fields) -> None:
        doc = {"ts_ms": int(time.time() * 1000), "worker": int(worker),
               "action": action}
        doc.update(fields)
        self.fleet_quarantine_log.append(doc)

    def snapshot(self) -> dict:
        return {
            "assignment": {str(k): v
                           for k, v in self.fleet_assignment.items()},
            "epoch": self.fleet_epoch,
            "restarts": {str(k): v
                         for k, v in self.fleet_restarts.items()},
            "fences": {str(k): v
                       for k, v in self.fleet_fences.items()},
            "fence_log": list(self.fleet_fence_log),
            "rescale_log": list(self.fleet_rescale_log),
            "quarantine_log": list(self.fleet_quarantine_log),
        }

    def restore(self, state: dict) -> None:
        self.fleet_assignment = {int(k): int(v) for k, v in
                                 (state.get("assignment") or {}).items()}
        self.fleet_epoch = int(state.get("epoch", 0))
        self.fleet_restarts = {int(k): int(v) for k, v in
                               (state.get("restarts") or {}).items()}
        self.fleet_fences = {int(k): int(v) for k, v in
                             (state.get("fences") or {}).items()}
        self.fleet_fence_log = list(state.get("fence_log") or [])
        self.fleet_rescale_log = list(state.get("rescale_log") or [])
        self.fleet_quarantine_log = list(
            state.get("quarantine_log") or [])

    def save(self) -> None:
        atomic_write_json(self.path, self.snapshot())


# --------------------------------------------------------------------- #
# worker context (driver glue)


def fence_superseded(fleet_dir: str, worker_id: int, fence: int) -> bool:
    """Has the supervisor issued worker slot ``worker_id`` a newer fence
    than ``fence`` (read-only, from the manifest)?"""
    state = read_json(os.path.join(fleet_dir, MANIFEST_FILE)) or {}
    fences = state.get("fences") or {}
    return int(fences.get(str(int(worker_id)), 0)) > int(fence)


class WorkerContext:
    """Everything ``--fleet-role worker`` adds to a driver run: the
    worker's fleet directory layout, the heartbeat, the canonical outbox,
    the opserver-URL drop file, and the per-incarnation run summary the
    supervisor and ``doctor fleet`` read."""

    def __init__(self, fleet_dir: str, worker_id: int, *,
                 family: str, k: Optional[int] = None,
                 heartbeat_s: float = 1.0, fence: int = 0,
                 stall=None):
        self.worker_id = int(worker_id)
        self.fleet_dir = fleet_dir
        self.dir = worker_dir(fleet_dir, worker_id)
        os.makedirs(self.dir, exist_ok=True)
        self.family = family
        self.k = k
        self.fence = int(fence)
        self.stall = stall  # injected gray failure (faults.StallFault)
        self._t0 = time.time()
        self._heartbeat = HeartbeatWriter(
            os.path.join(self.dir, HEARTBEAT_FILE), heartbeat_s,
            fence=self.fence,
            gate=(stall.wedged if stall is not None else None))
        self.outbox = OutboxWriter(os.path.join(self.dir, OUTBOX_FILE))
        self._events_f = None

    @staticmethod
    def from_args(args, spec) -> Optional["WorkerContext"]:
        """The driver's constructor: a context iff this run is a fleet
        worker (validated in ``main``). The fence token is supervisor-
        assigned via ``--fleet-fence``; ``--fleet-stall-s`` arms the
        fault layer's injectable gray failure for chaos runs."""
        if getattr(args, "fleet_role", None) != "worker":
            return None
        fence = int(getattr(args, "fleet_fence", 0) or 0)
        stall = None
        stall_s = float(getattr(args, "fleet_stall_s", 0) or 0)
        if stall_s > 0:
            from spatialflink_tpu.runtime.faults import (StallFault,
                                                         install_stall)
            stall = install_stall(StallFault(
                stall_s, superseded=lambda: fence_superseded(
                    args.fleet_dir, args.fleet_worker_id, fence)))
        return WorkerContext(args.fleet_dir, args.fleet_worker_id,
                             family=spec.family,
                             heartbeat_s=args.fleet_heartbeat,
                             fence=fence, stall=stall)

    @property
    def partition_path(self) -> str:
        return os.path.join(self.dir, PARTITION_FILE)

    @property
    def done_path(self) -> str:
        return os.path.join(self.dir, DONE_MARKER)

    def start(self) -> "WorkerContext":
        self._heartbeat.start()
        return self

    def tailing_source(self, *, limit: Optional[int] = None,
                       skip: int = 0) -> TailingReplaySource:
        return TailingReplaySource(self.partition_path, self.done_path,
                                   limit=limit, skip=skip)

    def write_url(self, url: str) -> None:
        atomic_write_json(os.path.join(self.dir, URL_FILE), {"url": url})

    def mirror_events(self, ring) -> None:
        """Append every event of ``ring`` (a telemetry ``EventRing``) to
        ``events.jsonl``, flushed, as ``{"fence", "ev"}`` lines. A worker
        that finishes or dies between two supervisor polls takes its
        opserver with it; this copy is what the harvest reads then
        (:func:`read_worker_events`)."""
        f = open(os.path.join(self.dir, WORKER_EVENTS_FILE), "a")
        self._events_f = f
        fence = self.fence

        def write(ev: dict) -> None:
            try:
                f.write(json.dumps({"fence": fence, "ev": ev},
                                   sort_keys=True, default=str) + "\n")
                f.flush()
            except (OSError, ValueError):
                pass  # closed at shutdown: the ring still has the event

        ring.mirror(write)

    def note_window(self, result, budget: Optional[dict] = None) -> None:
        """Outbox-append one emitted window (called only for windows the
        journal has NOT suppressed; flushed before the journal records
        it — see :class:`OutboxWriter` for the crash ordering).
        ``budget`` is the latency plane's budget row for this window;
        when present it rides the line as the fingerprint-excluded
        lineage sidecar (:func:`lat_sidecar`)."""
        if self.stall is not None:
            # arms the injected gray failure on the first emitted window
            # (and throttles emission while wedged — slow, not dead)
            self.stall.on_window()
        self.outbox.append(canonical_window_doc(
            result, self.family, lat=lat_sidecar(budget),
            fence=self.fence))

    def journal_fence_cutoffs(self) -> Dict[int, int]:
        """This worker's superseded-fence JOURNAL byte cutoffs, read
        from the supervisor's manifest (read-only — the worker never
        writes ``fleet.json``). The emitted-window journal skips lines
        past these offsets at load: a zombie predecessor may have
        journaled windows whose emissions are fence-dropped at merge,
        and trusting those lines would suppress the re-emission that
        makes the merged table whole."""
        state = read_json(os.path.join(self.fleet_dir, MANIFEST_FILE))
        return {f: c["journal"] for f, c in
                fence_cutoffs_from(state, self.worker_id).items()}

    def write_run_summary(self, **fields) -> None:
        """Append this incarnation's exit record to ``runs.jsonl``."""
        doc = {"ts_ms": int(time.time() * 1000),
               "wall_s": round(time.time() - self._t0, 3),
               "worker": self.worker_id,
               "fence": self.fence,
               "windows_appended": self.outbox.appended}
        doc.update(fields)
        with open(os.path.join(self.dir, RUNS_FILE), "a") as f:
            f.write(json.dumps(doc, sort_keys=True) + "\n")
            f.flush()
            os.fsync(f.fileno())

    def close(self) -> None:
        self._heartbeat.close()
        self.outbox.close()
        if self._events_f is not None:
            self._events_f.close()


def read_worker_events(workdir: str, fence: int,
                       since: int = 0) -> List[dict]:
    """The events incarnation ``fence`` of a worker mirrored to its
    ``events.jsonl`` with seq past ``since``, oldest first — the
    ``/events?since=`` answer of an opserver that is no longer there.
    Lines of other fences (predecessors, a fenced zombie) are skipped, and
    so is a torn last line."""
    path = os.path.join(workdir, WORKER_EVENTS_FILE)
    out: List[dict] = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    doc = json.loads(line)
                    ev = doc["ev"]
                    if int(doc["fence"]) == fence and ev["seq"] > since:
                        out.append(ev)
                except (ValueError, KeyError, TypeError):
                    continue
    except OSError:
        pass
    return out


def read_runs(workdir: str) -> List[dict]:
    """All incarnation summaries for one worker dir, oldest first."""
    path = os.path.join(workdir, RUNS_FILE)
    out: List[dict] = []
    if not os.path.exists(path):
        return out
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue
    return out
