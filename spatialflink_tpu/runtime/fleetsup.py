"""Fleet supervisor: N supervised worker pipelines, leaf-partitioned
input, crash-recovering restarts, exactly-once global merge, and the
fleet observability plane.

The reference deploys GeoFlink at parallelism 30: Flink's JobManager
places keyed subtasks on TaskManagers, restarts dead ones from the last
checkpoint, and windowAll stages merge the keyed partials into one global
result — with the JobManager's web UI as the single pane of glass over
all of it. The rebuild's supervisor is that control plane shrunk to one
process:

- **Placement** — the stream partitions by grid LEAF (PR 8's adaptive
  layout as the placement unit; the default layout is one leaf per base
  cell). A seed scan of the input head feeds
  :func:`~spatialflink_tpu.runtime.repartition.balance_leaves` (greedy
  LPT) for the initial leaf→worker assignment; unseen leaves route by
  ``leaf % N``.
- **Workers** — each is the FULL existing single-process driver
  (``--fleet-role worker``): own PaneCache, own checkpoint manifest, own
  emitted-window journal, own opserver on an ephemeral port. The
  supervisor only routes lines into per-worker partition files and reads
  canonical outboxes back — no shared mutable state between pipelines.
- **Supervision** — a monitor thread watches exit codes, heartbeat-file
  age, and (optionally) record→emit p99 SLO breaches from the worker's
  ``/latency`` payload. Ops polls run CONCURRENTLY with a hard
  per-request deadline (one hung worker HTTP server cannot delay
  heartbeat-staleness detection of the others). A dead worker restarts
  from its latest checkpoint manifest with ``--resume``; the
  per-incarnation run summary carries the recompile sentinel's
  post-warmup count, so the respawn PROVES it never silently recompiled
  instead of asserting it by hope.
- **Observability** (:class:`FleetMonitor`, ``--fleet-plane``) — the
  polls feed a bounded per-worker time series (throughput, record→emit
  p99, dominant stage, backlog residency, buffer depth, compiles); every
  worker's ``/events`` ring is harvested via ``?since=`` cursors and
  merged with supervisor lifecycle events (spawn/kill/restart/rebalance/
  epoch/merge) into ONE causally-ordered timeline, mirrored to
  ``fleet_events.jsonl``. Outbox tails are scanned incrementally to
  stamp each window's first-visible wall clock — the ``outbox-visible``
  stage of the end-to-end record→merged-emit lineage
  (:func:`compute_merged_lineage`), persisted as ``fleet_latency.json``.
  The supervisor's opserver federates it all: ``/fleet/latency``,
  ``/fleet/timeline``, ``/fleet/events``, ``/fleet/metrics`` (every
  worker's Prometheus text relabeled with ``worker="wN"`` — one scrape
  point), and ``/fleet/tenants`` (every worker's tenant cost ledger
  merged, fleet-wide fairness recomputed). On worker death the fleet view is snapshotted next to the dead
  worker's flight-recorder bundle (``postmortem/fleet_view.json``).
- **Rebalance** — at repartition epochs the supervisor compares worker
  loads (the monitor's retained latency/backlog series when present,
  routed-record counts otherwise) and :func:`~spatialflink_tpu.runtime
  .repartition.pick_rebalance` moves leaves off the most loaded worker
  (with hysteresis) — the fleet analogue of PR 8's in-process
  repartitioner, now fed by the dominant-stage/backlog signal ROADMAP
  item 1 names instead of raw record counts.
- **Exactly-once merge** — workers append canonical fingerprinted window
  docs to their outboxes BEFORE journaling them; the supervisor dedups
  by window key, merges per-family through
  :func:`~spatialflink_tpu.operators.base.merge_window_records`, and the
  merged table's digest is byte-stable against a fault-free
  single-worker run — the property the tier-1 kill test pins. The
  lineage sidecar rides OUTSIDE the fingerprint, so the digest is
  byte-identical with the plane on or off.
- **Drain** — SIGTERM stops routing, forwards the signal to every
  worker (each drains open windows and writes a final checkpoint via the
  driver's graceful-shutdown path), then merges whatever was emitted and
  exits 0.

``GET /fleet`` on the supervisor's own opserver serves the aggregated
view (:meth:`FleetSupervisor.fleet_view` via :func:`active_fleet`, the
same module-global hook pattern as ``repartition.active_controller``).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Tuple

from spatialflink_tpu.runtime import fleet as F
from spatialflink_tpu.runtime.checkpoint import atomic_write_json
from spatialflink_tpu.runtime.repartition import (balance_leaves,
                                                  pick_rebalance)
from spatialflink_tpu.utils import metrics as _metrics
from spatialflink_tpu.utils import telemetry as _telemetry
from spatialflink_tpu.utils.latencyplane import CHAIN_STAGES

_ACTIVE_FLEET: Optional["FleetSupervisor"] = None

#: the fleet-level stages appended after the worker's chain — the same
#: consecutive-interval construction, so the extended chain still sums to
#: the record→merged-emit total by construction. The table-merge stage is
#: ``fleet-merge``, NOT ``merge``: the worker chain already owns ``merge``
#: (device readback) and the stage dict must stay collision-free for the
#: sum invariant to mean anything
FLEET_STAGES = ("outbox-visible", "fleet-merge", "merged-emit")


def active_fleet() -> Optional["FleetSupervisor"]:
    """The running supervisor, if any (the ``/fleet`` endpoint's data
    source — same pattern as ``repartition.active_controller``)."""
    return _ACTIVE_FLEET


def _set_active(sup: Optional["FleetSupervisor"]) -> None:
    global _ACTIVE_FLEET
    _ACTIVE_FLEET = sup


# --------------------------------------------------------------------- #
# worker argv


#: flags the supervisor OWNS per worker (stripped from the inherited argv
#: and re-issued with worker-specific values) or that must not recurse
#: into a worker process; value = number of value tokens the flag takes.
#: (``--fleet-plane`` is deliberately NOT stripped: workers inherit it and
#: gate the outbox lineage sidecar on it.)
_WORKER_STRIP = {
    "--fleet": 1, "--fleet-role": 1, "--fleet-dir": 1,
    "--fleet-worker-id": 1, "--fleet-heartbeat": 1,
    "--fleet-epoch-records": 1, "--fleet-restart-cap": 1,
    "--fleet-chaos-kill": 1, "--fleet-slo-p99-ms": 1,
    "--fleet-rescale": 1, "--fleet-chaos-stall": 1,
    "--fleet-quarantine-s": 1, "--fleet-fence": 1, "--fleet-stall-s": 1,
    "--input1": 1, "--checkpoint-dir": 1, "--status-port": 1,
    "--output": 1, "--postmortem-dir": 1, "--resume": 0,
    "--limit": 1, "--telemetry-dir": 1, "--trace-dir": 1, "--profile": 1,
}


def _strip_flags(argv: List[str], spec: Dict[str, int]) -> List[str]:
    out: List[str] = []
    i = 0
    while i < len(argv):
        tok = argv[i]
        name = tok.split("=", 1)[0]
        if name in spec:
            i += 1
            if spec[name] and "=" not in tok:
                i += spec[name]
            continue
        out.append(tok)
        i += 1
    return out


def worker_argv(base_argv: List[str], *, fleet_dir: str, worker_id: int,
                heartbeat_s: float, resume: bool, fence: int = 0,
                stall_s: float = 0.0) -> List[str]:
    """A worker's driver argv: the supervisor's own argv minus the
    fleet/placement flags, plus the worker-role glue. Everything else
    (config, query option, panes, strict-recompile, SLO, metrics…)
    inherits unchanged — a worker IS the single-process pipeline.
    ``fence`` is the incarnation's manifest-issued fence token;
    ``stall_s`` arms the injectable gray failure (chaos only)."""
    wd = F.worker_dir(fleet_dir, worker_id)
    argv = _strip_flags(list(base_argv), _WORKER_STRIP)
    argv += [
        "--fleet-role", "worker",
        "--fleet-dir", fleet_dir,
        "--fleet-worker-id", str(worker_id),
        "--fleet-heartbeat", f"{heartbeat_s:g}",
        "--fleet-fence", str(int(fence)),
        "--input1", os.path.join(wd, F.PARTITION_FILE),
        "--checkpoint-dir", os.path.join(wd, "ckpt"),
        "--postmortem-dir", os.path.join(wd, "postmortem"),
        "--status-port", "0",
    ]
    if stall_s > 0:
        argv += ["--fleet-stall-s", f"{stall_s:g}"]
    if resume:
        argv.append("--resume")
    return argv


def _parse_chaos(spec: Optional[str]) -> Optional[Tuple[int, int]]:
    """``WID:NWINDOWS`` — SIGKILL worker WID once its outbox holds
    NWINDOWS lines (the deterministic kill hook the recovery tests and
    the bench fault row use)."""
    if not spec:
        return None
    wid, _, n = str(spec).partition(":")
    return int(wid), max(1, int(n or 1))


def _parse_stall_chaos(spec: Optional[str]) -> Optional[Tuple[int, float]]:
    """``WID:SECONDS`` — worker WID's first incarnation wedges its
    heartbeat/checkpoint surfaces for SECONDS after its first emitted
    window while continuing to write (the zombie-containment hook: the
    supervisor fences+respawns it WITHOUT a kill and the stale rows must
    be dropped at merge)."""
    if not spec:
        return None
    wid, _, s = str(spec).partition(":")
    return int(wid), max(0.1, float(s or 30.0))


def _parse_rescale(spec: Optional[str]) -> List[Tuple[int, int]]:
    """``AT:N[,AT:N...]`` — once AT records have been routed, rescale the
    fleet to N workers at the next epoch boundary. Sorted by threshold;
    e.g. ``"150:3,300:2"`` scales 2→3→2 across a run."""
    out: List[Tuple[int, int]] = []
    for part in filter(None, (p.strip() for p in (spec or "").split(","))):
        at, _, n = part.partition(":")
        out.append((int(at), max(1, int(n or 1))))
    return sorted(out)


def _http_json(url: str, timeout: float = 1.0) -> Optional[dict]:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return json.loads(r.read().decode())
    except Exception:
        return None


def _http_text(url: str, timeout: float = 1.0) -> Optional[str]:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.read().decode()
    except Exception:
        return None


def _worker_load(poll: dict) -> Optional[float]:
    """A comparable load scalar from a worker's polled ops payloads:
    prefer the backpressure/latency plane (record→emit p99), fall back to
    None (caller then uses routed-record counts)."""
    lat = (poll or {}).get("latency") or {}
    re_h = lat.get("record_emit") or {}
    for key in ("p99_ms", "p99"):
        v = re_h.get(key)
        if isinstance(v, (int, float)):
            return float(v)
    return None


def format_relay(wid: int, line: str, *, digest_active: bool
                 ) -> Optional[str]:
    """The supervisor's terminal rendering of one relayed worker stderr
    line: prefixed ``[wN]`` so N workers stop interleaving anonymously;
    a worker's own ``# live:`` digest line is suppressed (None) while
    the fleet digest owns the terminal — the full unprefixed stream
    still lands in ``worker<i>/worker.log``."""
    if digest_active and line.startswith("# live:"):
        return None
    return f"[w{wid}] {line}"


def format_fleet_digest(view: dict) -> str:
    """One stderr line for the whole fleet — the N-worker analogue of
    ``opserver.format_digest`` (whose per-worker lines the relay
    suppresses while this digest is active): liveness, routed records,
    fleet-wide window count, worst record→emit p99 with the dominant
    chain stage, and the restart count."""
    workers = view.get("workers") or []
    parts = [f"{view.get('alive', 0)}/"
             f"{view.get('n_workers', len(workers))} up",
             f"routed {view.get('routed', 0)}"]
    wins = 0
    p99: Optional[float] = None
    totals: Dict[str, float] = {}
    for w in workers:
        lat = w.get("latency") or {}
        wins += int((lat.get("sum_check") or {}).get("windows") or 0)
        re_h = lat.get("record_emit") or {}
        if re_h.get("count"):
            p99 = max(p99 or 0.0, float(re_h.get("p99") or 0.0))
        for s, h in (lat.get("stages") or {}).items():
            if s in _telemetry.CHAIN_STAGES_SET:
                totals[s] = totals.get(s, 0.0) + float(h.get("sum") or 0.0)
    parts.append(f"win {wins}")
    if p99 is not None:
        dom = max(totals, key=totals.get) if any(totals.values()) else None
        parts.append(f"lat p99 {p99:.0f}ms" + (f" ({dom})" if dom else ""))
    if view.get("restarts_total"):
        parts.append(f"restarts {view['restarts_total']}")
    return "# fleet live: " + " | ".join(parts)


class FleetLiveStats:
    """Daemon thread printing :func:`format_fleet_digest` per interval —
    the fleet's ``--live-stats``: one line for N workers instead of N
    interleaved per-worker digests. Prints once at :meth:`start` and one
    final line at :meth:`close`, mirroring ``opserver.LiveStats``."""

    def __init__(self, sup: "FleetSupervisor", interval_s: float = 5.0):
        self.sup = sup
        self.interval_s = max(0.01, float(interval_s))
        self.emitted = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _tick(self) -> None:
        try:
            line = format_fleet_digest(self.sup.fleet_view())
        except Exception:
            return  # a digest failure must never take the fleet down
        print(line, file=sys.stderr, flush=True)
        self.emitted += 1

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            self._tick()

    def start(self) -> "FleetLiveStats":
        self._tick()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="fleet-live-stats")
        self._thread.start()
        return self

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=self.interval_s + 5.0)
            self._thread = None
        self._tick()


# --------------------------------------------------------------------- #
# the fleet observability monitor


class FleetMonitor:
    """The supervisor's retained observability state (``--fleet-plane``):

    - a bounded per-worker time SERIES distilled from the ``/status`` +
      ``/latency`` polls the supervisor already makes (throughput,
      record→emit p99, dominant stage, backlog residency, decode buffer
      depth, recompiles, incarnation) — the rebalance signal ROADMAP
      item 1 names, and the retained input item 3's controller needs;
    - the merged fleet EVENT timeline: supervisor lifecycle events plus
      every worker's own ``/events`` ring (harvested via ``?since=``
      cursors; the worker's wall stamp and seq are preserved as
      ``ts_ms``/``worker_seq`` while the fleet ring assigns the merged
      seq and the supervisor-arrival ``mono_ms``), mirrored append-only
      to ``<fleet-dir>/fleet_events.jsonl``;
    - incremental outbox TAILS stamping each window key's first-visible
      wall clock — the ``outbox-visible`` stage of the end-to-end
      lineage, and the line counts the chaos hook reads.

    Cross-thread discipline: the monitor loop, poll futures, the routing
    loop, and HTTP handler threads all touch this state, so EVERY
    instance-attribute write outside ``__init__`` holds ``self._lock``
    (the invariant linter's thread-shared-state rule proves it)."""

    def __init__(self, root: str, n_workers: int, *,
                 series_capacity: int = 256, ring_capacity: int = 1024):
        self._lock = threading.Lock()
        self.root = root
        self.n_workers = int(n_workers)
        #: the merged timeline ring (fleet seqs; EventRing's own lock)
        self.ring = _telemetry.EventRing(capacity=ring_capacity)
        self._series: Dict[int, deque] = {
            w: deque(maxlen=max(1, int(series_capacity)))
            for w in range(self.n_workers)}
        #: per-worker /events?since= cursors (worker seqs; reset per
        #: incarnation — a fresh ring restarts at 1)
        self._cursors: Dict[int, int] = {}
        #: per-worker outbox tail state: byte pos, torn-tail carry, count
        self._tails: Dict[int, dict] = {}
        #: (wid, window key) -> first-visible wall clock ms
        self._seen_ms: Dict[Tuple[int, str], float] = {}
        self._vis_hist = _telemetry.StreamingHistogram("record-visible-ms")
        self._last_lat: Dict[int, dict] = {}
        #: set when a harvested worker event escalates a sustained stall
        #: to a repartition request (the chunk governor's
        #: ``rebalance-request``); the routing loop pops it and forces an
        #: early epoch boundary
        self._rebalance_requested = False
        #: per-worker (run_id, snapshot_seq) high-water mark — polls racing
        #: across the thread pool can land out of order; a snapshot whose
        #: seq is <= the one already ingested for the same run is stale and
        #: must not append a time-travelling sample
        self._snap_seen: Dict[int, Tuple[str, int]] = {}
        self.stale_polls = 0
        self._ev_f = open(os.path.join(root, F.EVENTS_FILE), "a")

    # ------------------------- the timeline ------------------------- #

    def note(self, kind: str, **fields) -> dict:
        """One SUPERVISOR lifecycle event onto the merged timeline and
        its durable JSONL mirror (flushed — post-mortems read the file
        after a crash)."""
        with self._lock:
            ev = self.ring.append(kind, src="supervisor", **fields)
            self._write_event_locked(ev)
        return ev

    def _write_event_locked(self, ev: dict) -> None:
        """Mirror one timeline event to ``fleet_events.jsonl`` (caller
        holds the lock)."""
        try:
            self._ev_f.write(json.dumps(ev, sort_keys=True) + "\n")
            self._ev_f.flush()
        except (OSError, ValueError):
            pass  # closed during shutdown: the ring still has the event

    def harvest(self, wid: int, payload: Optional[dict]) -> int:
        """Fold one worker's ``/events?since=`` response into the merged
        timeline. The worker's own wall stamp overrides the ring default
        (EventRing honors a ``ts_ms`` field) and its seq is kept as
        ``worker_seq``; the fleet ring assigns the merged seq and the
        supervisor-arrival ``mono_ms`` — so a dying worker's last words,
        harvested before the restart is noted, always order before the
        restart in the merged timeline."""
        if not payload:
            return 0
        added = 0
        with self._lock:
            cur = self._cursors.get(wid, 0)
            for e in payload.get("events") or []:
                try:
                    wseq = int(e.get("seq") or 0)
                except (TypeError, ValueError):
                    continue
                if wseq <= cur:
                    continue  # ?since= can re-deliver, never lose
                cur = wseq
                fields = {k: v for k, v in e.items()
                          if k not in ("seq", "mono_ms", "kind")}
                fields["worker"] = wid
                fields["src"] = "worker"
                fields["worker_seq"] = wseq
                if str(e.get("kind")) == "rebalance-request":
                    # governor stall escalation — routing loop pops this
                    # and forces an early epoch boundary
                    self._rebalance_requested = True
                ev = self.ring.append(str(e.get("kind")), **fields)
                self._write_event_locked(ev)
                added += 1
            self._cursors[wid] = cur
        return added

    def pop_rebalance_request(self) -> bool:
        """True once per harvested ``rebalance-request`` burst; clears
        the flag so one stall escalation buys one early epoch."""
        with self._lock:
            req, self._rebalance_requested = self._rebalance_requested, False
            return req

    def cursor(self, wid: int) -> int:
        with self._lock:
            return self._cursors.get(wid, 0)

    def reset_cursor(self, wid: int) -> None:
        """A fresh incarnation's event ring restarts at seq 1 — the
        harvest cursor must follow it down."""
        with self._lock:
            self._cursors[wid] = 0

    # ------------------------- the time series ---------------------- #

    def ingest_poll(self, wid: int, status: Optional[dict],
                    latency: Optional[dict], *, alive: bool = True,
                    incarnation: int = 0) -> None:
        """Distill one ops poll into the worker's bounded time series —
        the retention the old supervisor threw away after each liveness
        check."""
        st = (status or {}).get("status") or {}
        lat = latency or {}
        re_h = lat.get("record_emit") or {}
        totals = {s: float(h.get("sum") or 0.0)
                  for s, h in (lat.get("stages") or {}).items()
                  if s in _telemetry.CHAIN_STAGES_SET}
        dominant = (max(totals, key=totals.get)
                    if any(totals.values()) else None)
        bp = lat.get("backpressure") or {}
        last_bucket = (bp.get("series") or [None])[-1] or {}
        sample = {
            "ts_ms": int(time.time() * 1000),
            "alive": bool(alive),
            "incarnation": int(incarnation),
            "records_in": st.get("records_in"),
            "throughput_rps": st.get("throughput_rps"),
            "windows": st.get("windows_evaluated"),
            "record_emit_p99_ms": re_h.get("p99"),
            "dominant_stage": dominant,
            "backlog_residency_ms": bp.get("backlog_residency_ms"),
            "decode_buffer_depth": last_bucket.get("decode_buffer_depth"),
            "stall": last_bucket.get("stall"),
            "recompiles": (st.get("device") or {}).get("recompiles"),
            "restarts": None,  # filled by the supervisor's view, not here
        }
        run_id = (status or {}).get("run_id")
        snap_seq = (status or {}).get("snapshot_seq")
        with self._lock:
            if isinstance(run_id, str) and isinstance(snap_seq, int):
                seen_run, seen_seq = self._snap_seen.get(wid, ("", 0))
                if run_id == seen_run and snap_seq <= seen_seq:
                    # an older snapshot of the same worker process arrived
                    # after a newer one — drop it rather than letting the
                    # series (and the rebalance policy reading its tail)
                    # step backwards
                    self.stale_polls += 1
                    return
                # a new run_id is a restarted worker: its seqs restart at
                # 1, so the high-water mark resets with it
                self._snap_seen[wid] = (run_id, snap_seq)
            dq = self._series.get(wid)
            if dq is None:
                dq = self._series.setdefault(wid, deque(maxlen=256))
            dq.append(sample)
            if latency is not None:
                self._last_lat[wid] = latency

    def rebalance_load(self, wid: int) -> Optional[float]:
        """The rebalance policy's load scalar for one worker: record→emit
        p99 PLUS backlog residency from the newest retained sample —
        latency/backlog truth instead of raw routed counts (ROADMAP
        item 1's signal). None before any poll landed."""
        with self._lock:
            dq = self._series.get(wid)
            s = dq[-1] if dq else None
        if not s:
            return None
        p99 = s.get("record_emit_p99_ms")
        res = s.get("backlog_residency_ms")
        if p99 is None and res is None:
            return None
        return float(p99 or 0.0) + float(res or 0.0)

    def series(self, wid: int) -> List[dict]:
        with self._lock:
            dq = self._series.get(wid)
            return [dict(s) for s in dq] if dq else []

    def last_samples(self) -> Dict[int, dict]:
        with self._lock:
            return {w: dict(dq[-1]) for w, dq in self._series.items()
                    if dq}

    def last_latency(self, wid: int) -> Optional[dict]:
        with self._lock:
            return self._last_lat.get(wid)

    # ------------------------- outbox tails ------------------------- #

    def scan_outbox(self, wid: int) -> int:
        """Incrementally tail one worker's outbox: stamp each NEW window
        key's first-visible wall clock (the ``outbox-visible`` lineage
        stage; crash-replay duplicates keep the first stamp), feed the
        record→visible histogram from the line's own sidecar, and return
        the total complete-line count (the chaos hook's trigger). A torn
        tail line is carried until its newline arrives — the same
        holdback the workers' tailing source applies."""
        path = os.path.join(F.worker_dir(self.root, wid), F.OUTBOX_FILE)
        now_ms = time.time() * 1e3
        with self._lock:
            t = self._tails.get(wid)
            if t is None:
                t = self._tails.setdefault(
                    wid, {"pos": 0, "carry": "", "count": 0})
            try:
                size = os.path.getsize(path)
            except OSError:
                return t["count"]
            if size < t["pos"]:  # replaced/truncated: rescan from zero
                t["pos"], t["carry"], t["count"] = 0, "", 0
            if size == t["pos"]:
                return t["count"]
            try:
                with open(path, "rb") as f:
                    f.seek(t["pos"])
                    chunk = f.read()
            except OSError:
                return t["count"]
            t["pos"] += len(chunk)
            lines = (t["carry"] + chunk.decode("utf-8", "replace")
                     ).split("\n")
            t["carry"] = lines.pop()
            for line in lines:
                line = line.strip()
                if not line:
                    continue
                try:
                    doc = json.loads(line)
                except ValueError:
                    continue
                key = doc.get("key")
                if key is None:
                    continue
                t["count"] += 1
                sk = (wid, str(key))
                if sk in self._seen_ms:
                    continue
                self._seen_ms[sk] = now_ms
                fi = (doc.get("lat") or {}).get("first_ingest_ms")
                if isinstance(fi, (int, float)):
                    self._vis_hist.record(max(0.0, now_ms - fi))
            if len(self._seen_ms) > 65536:  # runaway guard
                for sk in list(self._seen_ms)[:32768]:
                    del self._seen_ms[sk]
            return t["count"]

    def line_count(self, wid: int) -> int:
        with self._lock:
            t = self._tails.get(wid)
            return int(t["count"]) if t else 0

    def visible_ms(self, wid: int, key: str) -> Optional[float]:
        """When the supervisor first observed this window's outbox line
        (None for lines that never crossed a scan — shouldn't happen
        while the monitor loop runs, but the lineage falls back
        gracefully)."""
        with self._lock:
            return self._seen_ms.get((wid, str(key)))

    def visible_hist(self) -> dict:
        with self._lock:
            return self._vis_hist.to_dict()

    def close(self) -> None:
        with self._lock:
            try:
                self._ev_f.close()
            except OSError:
                pass


def compute_merged_lineage(merged: List[dict],
                           per_worker: Dict[int, Dict[str, dict]],
                           visible_of: Callable[[int, str],
                                                Optional[float]],
                           t_merged_ms: float, t_emit_ms: float) -> dict:
    """End-to-end record→merged-emit lineage over the merged window
    table. Per merged window the worker chain extends with the fleet
    stages — the same consecutive-interval construction the worker plane
    uses, so the stages sum to the total BY CONSTRUCTION:

    - ``spread``: the critical contributor's first ingest minus the
      GLOBAL first ingest across contributors (a partitioned window
      starts its clock at the earliest record on ANY worker);
    - the critical contributor's own chain stages (critical = the
      contributor whose emit completed last — it gates the merge);
    - ``outbox-visible``: worker emit → the supervisor first observed
      the outbox line (the monitor's tail stamp, clamped into
      [emit, merge-start] — clamping an INTERIOR chain stamp shifts
      time between adjacent stages and cannot break the sum);
    - ``fleet-merge``: observed → the global merge's table was built
      (named apart from the worker's device-readback ``merge`` stage —
      the stage dict must stay collision-free);
    - ``merged-emit``: table built → ``merged.jsonl`` durably replaced.

    The residual against the total is exactly the contributing worker's
    own chain residual — the fleet stages cancel telescopically.
    Returns the ``fleet-latency-v1`` document ``doctor fleet`` renders
    with the same stage-budget table bundles get; ``visible_of(wid,
    key) -> Optional[ms]``. Windows whose contributors carry no sidecar
    (plane off, evicted budget rows) are counted in ``skipped_no_lat``
    and excluded — never guessed."""
    total_h = _telemetry.StreamingHistogram("record-merged-emit-ms")
    stage_h: Dict[str, _telemetry.StreamingHistogram] = {}
    chain = ["spread"] + list(CHAIN_STAGES) + list(FLEET_STAGES)
    recent: List[dict] = []
    windows = 0
    max_residual = 0.0
    skipped = 0
    for doc in merged:
        key = doc["key"]
        contribs = []
        for wid in doc.get("workers", []):
            lat = ((per_worker.get(wid) or {}).get(key) or {}).get("lat")
            if (lat and lat.get("first_ingest_ms") is not None
                    and lat.get("emitted_ms") is not None):
                contribs.append((int(wid), lat))
        if not contribs:
            skipped += 1
            continue
        gfi = min(float(lat["first_ingest_ms"]) for _, lat in contribs)
        crit_wid, crit = max(contribs,
                             key=lambda c: float(c[1]["emitted_ms"]))
        emitted = float(crit["emitted_ms"])
        vis = visible_of(crit_wid, key)
        vis = min(max(float(vis) if vis is not None else emitted,
                      emitted), t_merged_ms)
        stages = {"spread": float(crit["first_ingest_ms"]) - gfi}
        for s, v in (crit.get("stages") or {}).items():
            stages[s] = float(v)
        stages["outbox-visible"] = vis - emitted
        stages["fleet-merge"] = t_merged_ms - vis
        stages["merged-emit"] = t_emit_ms - t_merged_ms
        total = t_emit_ms - gfi
        residual = abs(total - sum(stages.values()))
        windows += 1
        if residual > max_residual:
            max_residual = residual
        total_h.record(max(0.0, total))
        for s, v in stages.items():
            h = stage_h.get(s)
            if h is None:
                h = stage_h.setdefault(
                    s, _telemetry.StreamingHistogram(s))
            h.record(max(0.0, v))
        recent.append({
            "key": key, "worker": crit_wid,
            "first_ingest_ms": gfi,
            "record_emit_ms": round(total, 3),
            "stages": {s: round(v, 3) for s, v in stages.items()},
        })
    return {
        "schema": "fleet-latency-v1",
        "ts_ms": int(t_emit_ms),
        "chain_stages": chain,
        "stages": {s: h.to_dict() for s, h in stage_h.items()},
        "record_emit": total_h.to_dict(),
        "recent": recent[-64:],
        "sum_check": {"windows": windows,
                      "max_residual_ms": round(max_residual, 3)},
        "skipped_no_lat": skipped,
    }


# --------------------------------------------------------------------- #
# supervisor


class FleetSupervisor:
    """One supervisor process: spawns/monitors/restarts N worker drivers,
    routes the input stream into per-worker partition files by grid leaf,
    and merges the workers' canonical outboxes into the global window
    table.

    Cross-thread discipline: the monitor thread, poll futures, stderr
    relays, and the main routing loop share process/poll state, so EVERY
    instance-attribute write outside ``__init__`` holds ``self._lock``
    (the invariant linter's thread-shared-state rule proves this at the
    AST level). Durable state (assignment, epoch, restart counts) lives
    in :class:`~spatialflink_tpu.runtime.fleet.FleetManifest`, whose
    snapshot/restore pair the checkpoint-coverage rule proves
    field-by-field."""

    def __init__(self, args, params, spec, base_argv: List[str]):
        self._lock = threading.RLock()
        self.n_workers = int(args.fleet)
        self.root = args.fleet_dir
        self.args = args
        self.params = params
        self.case = spec
        self.base_argv = list(base_argv)
        self.heartbeat_s = float(getattr(args, "fleet_heartbeat", 1.0))
        self.hb_timeout_s = max(5.0, 5.0 * self.heartbeat_s)
        self.boot_timeout_s = 120.0
        self.epoch_records = max(1, int(getattr(args, "fleet_epoch_records",
                                                20000) or 20000))
        self.restart_cap = int(getattr(args, "fleet_restart_cap", 3))
        self.slo_p99_ms = getattr(args, "fleet_slo_p99_ms", None)
        os.makedirs(self.root, exist_ok=True)
        self.manifest = F.FleetManifest(
            os.path.join(self.root, F.MANIFEST_FILE))
        #: the observability plane (None under --fleet-plane off: no
        #: monitor, no sidecar harvesting, federation endpoints answer
        #: with notes — and the merged digest is provably unchanged)
        self.monitor: Optional[FleetMonitor] = None
        if getattr(args, "fleet_plane", "on") != "off":
            self.monitor = FleetMonitor(self.root, self.n_workers)
        self._chaos = _parse_chaos(getattr(args, "fleet_chaos_kill", None))
        self._chaos_fired = False
        self._stall_chaos = _parse_stall_chaos(
            getattr(args, "fleet_chaos_stall", None))
        self._stall_injected = False
        self._rescales = _parse_rescale(getattr(args, "fleet_rescale", None))
        self.quarantine_s = float(
            getattr(args, "fleet_quarantine_s", 10.0) or 10.0)
        self._digest_on = bool(getattr(args, "live_stats", False))
        self._poll_pool = ThreadPoolExecutor(
            max_workers=max(2, min(self.n_workers + 1, 16)),
            thread_name_prefix="fleet-poll")
        self._poll_busy: Dict[int, object] = {}
        self._relays: Dict[int, threading.Thread] = {}
        self._merged_lat: Optional[dict] = None
        self._procs: Dict[int, subprocess.Popen] = {}
        self._logs: Dict[int, object] = {}
        self._spawned_at: Dict[int, float] = {}
        self._incarnations: Dict[int, int] = {}
        self._urls: Dict[int, str] = {}
        self._polls: Dict[int, dict] = {}
        self._slo_strikes: Dict[int, int] = {}
        self._kill_reason: Dict[int, str] = {}
        self._rcs: Dict[int, int] = {}
        self._restart_log: List[dict] = []
        self._routed = 0
        self._routed_by_worker: Dict[int, int] = {}
        # elastic-fleet worker sets: routable actives vs the all-ever set
        # (merge/done-markers/metrics must cover retirees and scale-outs)
        self._active: List[int] = list(range(self.n_workers))
        self._all = set(range(self.n_workers))
        self._retired: set = set()
        #: fenced-but-unkilled predecessors (gray-failure containment:
        #: the zombie keeps running; its rows are dropped by fence)
        self._zombies: List[Tuple[int, subprocess.Popen]] = []
        #: wid -> monotonic time quarantine began (routing drained)
        self._quarantined: Dict[int, float] = {}
        #: wid -> accumulated gray-failure suspicion score
        self._suspicion: Dict[int, float] = {}
        #: wid -> read_outbox stats from the final merge (stale fences)
        self._outbox_stats: Dict[int, dict] = {}
        self._done_feeding = False
        self._draining = False
        self._stopping = False
        self._failed: Optional[Tuple[int, int]] = None
        self._monitor_thread: Optional[threading.Thread] = None

    # -------------------------------------------------------------- #
    # placement

    def _leaf_fn(self):
        """Vectorized line→leaf router over PR 8's leaf layout (default
        layout = one leaf per base cell of the configured uniform grid)."""
        from spatialflink_tpu.index.adaptive_grid import AdaptiveGrid
        from spatialflink_tpu.streams.formats import parse_spatial

        cfg = self.params.input1
        grid = self.params.grids()[0]
        refine = getattr(self.args, "adaptive_grid", None) or 4
        leaves = AdaptiveGrid(grid, refine=refine)
        geometry = self.case.stream
        kw = cfg.geojson_kwargs()

        def leaf_of(line: str) -> Optional[int]:
            try:
                obj = parse_spatial(line, cfg.format, grid,
                                    delimiter=cfg.delimiter,
                                    schema=cfg.csv_tsv_schema,
                                    geometry=geometry, **kw)
                if hasattr(obj, "x"):
                    xs, ys = obj.x, obj.y
                else:  # edge geometries place by bbox centroid
                    b = obj.bbox
                    xs, ys = (b[0] + b[2]) / 2, (b[1] + b[3]) / 2
                leaf = leaves.assign_leaf(xs, ys)
            except Exception:
                return None
            v = int(leaf if getattr(leaf, "ndim", 0) == 0 else leaf.flat[0])
            return v if v >= 0 else None

        return leaf_of

    def _seed_assignment(self, leaf_of) -> None:
        """Occupancy-seeded LPT packing from the input head (bounded by
        one epoch of records, capped — seeding is a sample-based estimate
        and must not re-parse a huge replay before routing starts); a
        resumed supervisor keeps its manifest's assignment so worker
        checkpoints stay aligned with their leaves."""
        if self.manifest.fleet_assignment:
            return
        occ: Dict[int, int] = {}
        scanned = 0
        head = min(self.epoch_records, 10_000)
        with open(self.args.input1) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                leaf = leaf_of(line)
                if leaf is not None:
                    occ[leaf] = occ.get(leaf, 0) + 1
                scanned += 1
                if scanned >= head:
                    break
        assignment = balance_leaves(occ, self.n_workers)
        self.manifest.assign_all(assignment)
        self.manifest.save()

    # -------------------------------------------------------------- #
    # worker lifecycle

    def _spawn_locked(self, wid: int, *, resume: bool, reason: str) -> None:
        wd = F.worker_dir(self.root, wid)
        os.makedirs(wd, exist_ok=True)
        inc = self._incarnations.get(wid, 0) + 1
        self._incarnations[wid] = inc
        fence = self.manifest.fence_of(wid)
        if resume:
            # Fence the predecessor BEFORE the successor boots. The byte
            # sizes recorded here become the validity cutoffs for the OLD
            # fence: anything a zombie predecessor appends after this
            # instant lands past the cutoff and is dropped at merge time
            # by construction — no signal delivery required.
            ob = os.path.join(wd, F.OUTBOX_FILE)
            jr = os.path.join(wd, "ckpt", "emitted.log")
            fence = self.manifest.bump_fence(
                wid,
                outbox_bytes=(os.path.getsize(ob)
                              if os.path.exists(ob) else 0),
                journal_bytes=(os.path.getsize(jr)
                               if os.path.exists(jr) else 0),
                reason=reason)
            self.manifest.save()
            if self.monitor is not None:
                self.monitor.note("fence-bump", worker=wid, fence=fence,
                                  reason=reason)
        stall_s = 0.0
        if (self._stall_chaos is not None and wid == self._stall_chaos[0]
                and inc == 1):
            # chaos: only the FIRST incarnation of the target wedges —
            # its fenced successor must run clean to prove containment
            stall_s = self._stall_chaos[1]
        argv = worker_argv(self.base_argv, fleet_dir=self.root,
                           worker_id=wid, heartbeat_s=self.heartbeat_s,
                           resume=resume, fence=fence, stall_s=stall_s)
        log = self._logs.get(wid)
        if log is None:
            log = open(os.path.join(wd, "worker.log"), "a")
            self._logs[wid] = log
        log.write(f"--- incarnation {inc} ({reason}) ---\n")
        log.flush()
        proc = subprocess.Popen(
            [sys.executable, "-m", "spatialflink_tpu.driver"] + argv,
            stdout=log, stderr=subprocess.PIPE, text=True,
            start_new_session=True)  # controlled drain: WE forward signals
        self._procs[wid] = proc
        # stderr relay: every line lands in worker.log AND echoes to the
        # supervisor's terminal prefixed [wN] (the fleet digest suppresses
        # the workers' own # live: lines) — see format_relay
        relay = threading.Thread(target=self._relay_stderr,
                                 args=(wid, proc, log),
                                 name=f"fleet-relay-w{wid}", daemon=True)
        self._relays[wid] = relay
        relay.start()
        self._spawned_at[wid] = time.monotonic()
        self._urls.pop(wid, None)
        self._slo_strikes[wid] = 0
        if self.monitor is not None:
            self.monitor.reset_cursor(wid)
            self.monitor.note("worker-spawn", worker=wid, incarnation=inc,
                              resume=bool(resume), reason=reason)

    def _relay_stderr(self, wid: int, proc: subprocess.Popen,
                      log) -> None:
        """Pump one incarnation's stderr pipe until EOF (daemon thread,
        one per spawn). Never writes supervisor state — reads only."""
        pipe = proc.stderr
        if pipe is None:
            return
        try:
            for line in pipe:
                line = line.rstrip("\n")
                try:
                    log.write(line + "\n")
                    log.flush()
                except (OSError, ValueError):
                    pass  # log closed during supervisor shutdown
                rendered = format_relay(wid, line,
                                        digest_active=self._digest_on)
                if rendered is not None:
                    print(rendered, file=sys.stderr, flush=True)
        except (OSError, ValueError):
            pass  # pipe torn down mid-read (SIGKILL)
        finally:
            try:
                pipe.close()
            except OSError:
                pass

    def _restart_locked(self, wid: int, rc: Optional[int],
                        reason: str) -> None:
        n = self.manifest.note_restart(wid)
        self.manifest.save()
        self._restart_log.append({"ts_ms": int(time.time() * 1000),
                                  "worker": wid, "rc": rc,
                                  "reason": reason, "restart": n})
        # fleet post-mortem: freeze the aggregated view next to the dead
        # worker's flight-recorder bundles BEFORE the respawn mutates it
        self._snapshot_fleet_view(wid, rc, reason)
        if self.monitor is not None:
            self.monitor.note("worker-restart", worker=wid, rc=rc,
                              reason=reason, restart=n)
        if n > self.restart_cap:
            self._failed = (wid, rc if rc is not None else -1)
            if self.monitor is not None:
                self.monitor.note("worker-failed", worker=wid, rc=rc,
                                  restarts=n, cap=self.restart_cap)
            return
        self._spawn_locked(wid, resume=True, reason=reason)

    def _snapshot_fleet_view(self, wid: int, rc: Optional[int],
                             reason: str) -> None:
        """Write ``postmortem/fleet_view.json`` for a dying worker: the
        supervisor's aggregated view plus the merged timeline tail at
        the moment of death — what the worker's own flight-recorder
        bundle cannot see. Diagnostics must never block the restart."""
        try:
            pm = os.path.join(F.worker_dir(self.root, wid), "postmortem")
            os.makedirs(pm, exist_ok=True)
            view = self.fleet_view()
            view["death"] = {"worker": wid, "rc": rc, "reason": reason,
                             "ts_ms": int(time.time() * 1000)}
            if self.monitor is not None:
                view["timeline_tail"] = self.monitor.ring.list(None)[-40:]
            atomic_write_json(os.path.join(pm, F.FLEET_VIEW_FILE), view)
        except Exception:
            pass

    def _monitor_loop(self) -> None:
        next_poll = 0.0
        while True:
            with self._lock:
                if self._stopping or self._failed:
                    return
                procs = dict(self._procs)
                wids = sorted(self._all)
            now = time.monotonic()
            poll_ops = now >= next_poll
            if poll_ops:
                next_poll = now + max(0.25, self.heartbeat_s)
            for wid, proc in procs.items():
                rc = proc.poll()
                if rc is not None:
                    self._on_exit(wid, proc, rc)
                    continue
                self._check_liveness(wid, proc)
                if poll_ops:
                    self._schedule_poll(wid)
            if self.monitor is not None:
                for wid in wids:
                    self.monitor.scan_outbox(wid)
            self._reap_zombies()
            self._suspicion_tick()
            for wid in self._quarantine_tick():
                with self._lock:
                    proc = self._procs.get(wid)
                if proc is not None:
                    self._fence_respawn(
                        wid, proc,
                        (f"gray failure: quarantined {self.quarantine_s:g}s"
                         " without recovery"),
                        kill=not self._is_stall_target(wid))
            self._check_chaos()
            time.sleep(0.2)

    def _on_exit(self, wid: int, proc: subprocess.Popen, rc: int) -> None:
        with self._lock:
            if self._procs.get(wid) is not proc:
                return
            del self._procs[wid]
            self._rcs[wid] = rc
            if self.monitor is not None:
                self.monitor.note("worker-exit", worker=wid, rc=rc)
            done = os.path.exists(
                os.path.join(F.worker_dir(self.root, wid), F.DONE_MARKER))
            if self._draining or self._stopping or (rc == 0 and done):
                return  # clean finish after EOF, or drain in progress
            reason = self._kill_reason.pop(wid, None) or (
                f"exit rc={rc}" if rc != 0
                else "exited before partition EOF")
            self._restart_locked(wid, rc, reason)

    def _check_liveness(self, wid: int, proc: subprocess.Popen) -> None:
        hb = os.path.join(F.worker_dir(self.root, wid), F.HEARTBEAT_FILE)
        # fence-aware: a beat left behind by the fenced predecessor must
        # not vouch for the successor (age None = "still booting")
        age = F.heartbeat_age_s(hb, fence=self.manifest.fence_of(wid))
        with self._lock:
            booted_s = time.monotonic() - self._spawned_at.get(wid, 0.0)
        if age is None:
            if booted_s > self.boot_timeout_s:
                self._contain(wid, proc, "no heartbeat after boot timeout")
        elif age > self.hb_timeout_s and booted_s > self.hb_timeout_s:
            self._contain(wid, proc, f"heartbeat stale {age:.1f}s")

    def _is_stall_target(self, wid: int) -> bool:
        return (self._stall_chaos is not None
                and wid == self._stall_chaos[0])

    def _contain(self, wid: int, proc: subprocess.Popen,
                 reason: str) -> None:
        """Route a hard liveness breach into containment. The stall-chaos
        target is fenced WITHOUT a kill — the predecessor lives on as a
        writing zombie, and the merge proving its rows were dropped is the
        whole point of the drill. Real failures keep the kill."""
        if self._is_stall_target(wid):
            self._fence_respawn(wid, proc, reason, kill=False)
        else:
            self._kill(wid, proc, reason)

    def _kill(self, wid: int, proc: subprocess.Popen, reason: str) -> None:
        if self.monitor is not None:
            # harvest the dying worker's own events BEFORE the SIGKILL
            # and the restart note: its last words must order before the
            # restart in the merged timeline (bounded — the worker may
            # already be unresponsive)
            self._harvest_events(wid, timeout=0.5)
            self.monitor.note("worker-kill", worker=wid, reason=reason)
        with self._lock:
            self._kill_reason[wid] = reason
        try:
            proc.kill()
        except OSError:
            pass

    # -------------------------------------------------------------- #
    # gray-failure containment: suspicion -> quarantine -> fence

    SUSPECT_ENTER = 3.0
    SUSPECT_EXIT = 1.0
    SUSPECT_CAP = 6.0

    def _fence_respawn(self, wid: int, proc: subprocess.Popen,
                       reason: str, *, kill: bool) -> None:
        """Fence + respawn a worker WITHOUT waiting for the predecessor
        to die. With ``kill=False`` the predecessor lives on as a writing
        zombie — provably contained, because the fence bump in
        ``_spawn_locked`` records its byte cutoffs before the successor
        boots, so everything it appends afterwards is stale by
        construction."""
        if self.monitor is not None:
            # bounded: the worker may already be unresponsive
            self._harvest_events(wid, timeout=0.5)
        with self._lock:
            if self._procs.get(wid) is not proc:
                return  # superseded while harvesting
            del self._procs[wid]
            self._zombies.append((wid, proc))
            self._quarantined.pop(wid, None)
            self._suspicion.pop(wid, None)
            if self.monitor is not None:
                self.monitor.note("worker-fence", worker=wid,
                                  reason=reason, kill=bool(kill))
            self._restart_locked(wid, None, reason)
        if kill:
            try:
                proc.kill()
            except OSError:
                pass

    def _reap_zombies(self) -> None:
        """Collect fenced predecessors that finally died. Their exit must
        NOT trip the restart path — zombies are out of ``_procs``, so
        ``_on_exit`` never sees them; this reap just records the death."""
        with self._lock:
            zombies = list(self._zombies)
        for wid, proc in zombies:
            rc = proc.poll()
            if rc is None:
                continue
            with self._lock:
                try:
                    self._zombies.remove((wid, proc))
                except ValueError:
                    continue
            if self.monitor is not None:
                self.monitor.note("zombie-exit", worker=wid, rc=rc)

    def _suspicion_tick(self) -> None:
        """Score gray failure per monitor cycle from soft signals: a
        slow-not-dead worker accrues suspicion (stale-ish heartbeat,
        backpressure stall flag, tail-latency skew vs the fleet median,
        backlog, throughput collapse) and decays it on healthy cycles.
        Crossing SUSPECT_ENTER quarantines the worker — new leaf routes
        drain away while its already-routed output keeps merging; falling
        back below SUSPECT_EXIT lifts the quarantine (hysteresis). The
        last routable worker is never quarantined."""
        samples = (self.monitor.last_samples()
                   if self.monitor is not None else {})
        with self._lock:
            candidates = [w for w in self._active if w in self._procs]
        p99s = [float(s["record_emit_p99_ms"]) for s in samples.values()
                if s.get("record_emit_p99_ms") is not None]
        med_p99 = sorted(p99s)[len(p99s) // 2] if p99s else None
        rpss = [float(s["throughput_rps"]) for s in samples.values()
                if s.get("throughput_rps")]
        med_rps = sorted(rpss)[len(rpss) // 2] if rpss else None
        now = time.monotonic()
        for wid in candidates:
            hb = os.path.join(F.worker_dir(self.root, wid),
                              F.HEARTBEAT_FILE)
            age = F.heartbeat_age_s(hb, fence=self.manifest.fence_of(wid))
            s = samples.get(wid) or {}
            pts = 0.0
            if age is not None and age > 2.0 * self.heartbeat_s:
                pts += 1.5
            if s.get("stall"):
                pts += 1.0
            p99 = s.get("record_emit_p99_ms")
            if (p99 is not None and med_p99 and len(p99s) >= 2
                    and float(p99) > 3.0 * med_p99):
                pts += 1.0
            res = s.get("backlog_residency_ms")
            if res is not None and float(res) > 1000.0:
                pts += 0.5
            rps = s.get("throughput_rps")
            if (rps is not None and med_rps and len(rpss) >= 2
                    and float(rps) < 0.2 * med_rps):
                pts += 0.5
            with self._lock:
                prev = self._suspicion.get(wid, 0.0)
                score = (min(self.SUSPECT_CAP, prev + pts) if pts > 0
                         else max(0.0, prev - 0.5))
                self._suspicion[wid] = score
                quarantined = wid in self._quarantined
                routable = [w for w in self._active
                            if w not in self._quarantined]
                if (not quarantined and score >= self.SUSPECT_ENTER
                        and len(routable) > 1):
                    self._quarantined[wid] = now
                    self.manifest.note_quarantine(
                        wid, "quarantine", score=round(score, 2))
                    self.manifest.save()
                    if self.monitor is not None:
                        self.monitor.note("worker-quarantine", worker=wid,
                                          score=round(score, 2))
                elif quarantined and score <= self.SUSPECT_EXIT:
                    self._quarantined.pop(wid, None)
                    self.manifest.note_quarantine(
                        wid, "unquarantine", score=round(score, 2))
                    self.manifest.save()
                    if self.monitor is not None:
                        self.monitor.note("worker-unquarantine",
                                          worker=wid,
                                          score=round(score, 2))

    def _quarantine_tick(self) -> List[int]:
        """Workers whose quarantine outlived the deadline — the caller
        escalates each to a fence+respawn (split out so unit tests can
        drive the state machine without a live fleet)."""
        now = time.monotonic()
        with self._lock:
            return [w for w, t0 in self._quarantined.items()
                    if now - t0 > self.quarantine_s]

    def _schedule_poll(self, wid: int) -> None:
        """Submit one worker's ops poll to the pool — the monitor loop
        never blocks on a worker's HTTP server (one hung worker used to
        serialize behind the others and delay THEIR heartbeat-staleness
        detection); a still-outstanding poll skips this round instead of
        stacking requests behind a wedged server."""
        with self._lock:
            fut = self._poll_busy.get(wid)
        if fut is not None and not fut.done():  # type: ignore[union-attr]
            return
        try:
            fut = self._poll_pool.submit(self._poll_ops, wid)
        except RuntimeError:
            return  # pool shut down: supervisor exiting
        with self._lock:
            self._poll_busy[wid] = fut

    def _poll_ops(self, wid: int) -> None:
        url = self._resolve_url(wid)
        if not url:
            return
        # hard per-request deadline, scaled to the heartbeat but bounded:
        # a wedged worker costs one pool slot for at most ~2s, never the
        # liveness loop
        deadline = max(0.5, min(2.0, self.heartbeat_s))
        status = _http_json(f"{url}/status", timeout=deadline)
        latency = _http_json(f"{url}/latency", timeout=deadline)
        if self.monitor is not None:
            self._harvest_events(wid, timeout=deadline)
        if status is None and latency is None:
            return
        with self._lock:
            self._polls[wid] = {"status": status, "latency": latency,
                                "ts_ms": int(time.time() * 1000)}
            alive = wid in self._procs
            inc = self._incarnations.get(wid, 0)
        if self.monitor is not None:
            self.monitor.ingest_poll(wid, status, latency, alive=alive,
                                     incarnation=inc)
        if self.slo_p99_ms:
            p99 = _worker_load({"latency": latency})
            with self._lock:
                if p99 is not None and p99 > float(self.slo_p99_ms):
                    self._slo_strikes[wid] = self._slo_strikes.get(wid,
                                                                   0) + 1
                    strikes = self._slo_strikes[wid]
                else:
                    self._slo_strikes[wid] = 0
                    strikes = 0
                proc = self._procs.get(wid)
            if strikes >= 3 and proc is not None:
                self._kill(wid, proc,
                           f"slo breach: record_emit p99 {p99:.1f}ms > "
                           f"{float(self.slo_p99_ms):g}ms x{strikes}")

    def _harvest_events(self, wid: int, timeout: float = 1.0) -> None:
        mon = self.monitor
        if mon is None:
            return
        url = self._resolve_url(wid)
        if not url:
            return
        payload = _http_json(f"{url}/events?since={mon.cursor(wid)}",
                             timeout=timeout)
        if payload is None:
            # the opserver is gone (the worker finished or died since the
            # last poll): what it said is still in its mirrored event file
            payload = {"events": F.read_worker_events(
                F.worker_dir(self.root, wid),
                fence=self.manifest.fence_of(wid), since=mon.cursor(wid))}
        mon.harvest(wid, payload)

    def _resolve_url(self, wid: int) -> Optional[str]:
        with self._lock:
            url = self._urls.get(wid)
        if url:
            return url
        doc = F.read_json(os.path.join(F.worker_dir(self.root, wid),
                                       F.URL_FILE))
        url = (doc or {}).get("url")
        if url:
            with self._lock:
                self._urls[wid] = url
        return url

    def _check_chaos(self) -> None:
        if self._chaos is None:
            return
        with self._lock:
            if self._chaos_fired:
                return
            wid, n = self._chaos
            proc = self._procs.get(wid)
        if proc is None:
            return
        if self.monitor is not None:
            # the monitor loop just tailed the outbox — reuse its count
            lines = self.monitor.line_count(wid)
        else:
            outbox = os.path.join(F.worker_dir(self.root, wid),
                                  F.OUTBOX_FILE)
            try:
                with open(outbox) as f:
                    lines = sum(1 for ln in f if ln.strip())
            except OSError:
                return
        if lines >= n:
            with self._lock:
                self._chaos_fired = True
            if self.monitor is not None:
                self.monitor.note("chaos-kill", worker=wid, windows=lines)
            self._kill(wid, proc, f"chaos kill at {lines} windows")

    # -------------------------------------------------------------- #
    # routing

    def _pick_worker(self, leaf: Optional[int], routed: int,
                     assignment: Dict[int, int],
                     outs: Dict[int, object]) -> int:
        """Quarantine-aware placement: the assigned worker wins while it
        is routable; a quarantined/retired assignee's NEW records deflect
        deterministically onto the routable set (its already-routed
        partition keeps draining — quarantine starves, never truncates)."""
        with self._lock:
            routable = [w for w in self._active
                        if w not in self._quarantined and w in outs]
        if not routable:
            routable = sorted(outs)
        if leaf is None:
            return routable[routed % len(routable)]
        wid = assignment.get(leaf)
        if wid is not None and wid in routable:
            return wid
        return routable[leaf % len(routable)]

    def _rescale_due(self, routed: int) -> Optional[int]:
        """Pop the next ``--fleet-rescale`` threshold once routed records
        cross it — consumed at an epoch boundary, never mid-epoch."""
        with self._lock:
            if self._rescales and routed >= self._rescales[0][0]:
                return self._rescales.pop(0)[1]
        return None

    def _route(self, leaf_of) -> int:
        """Feed the input file into per-worker partition files, one epoch
        at a time; at each epoch boundary, flush, rebalance if a worker
        is hot (or rescale if a ``--fleet-rescale`` threshold passed),
        and persist the manifest. A worker's ``rebalance-request`` event
        (the chunk governor's sustained-stall escalation) forces an early
        boundary at the next flush point. Returns routed-record count."""
        outs: Dict[int, object] = {}
        for wid in range(self.n_workers):
            wd = F.worker_dir(self.root, wid)
            os.makedirs(wd, exist_ok=True)
            outs[wid] = open(os.path.join(wd, F.PARTITION_FILE), "a")
        assignment = dict(self.manifest.fleet_assignment)
        occ: Dict[int, int] = {}
        routed = 0
        epoch_n = 0
        epoch_by_worker = {wid: 0 for wid in outs}
        try:
            with open(self.args.input1) as f:
                for line in f:
                    if _metrics.shutdown_requested():
                        break
                    with self._lock:
                        if self._failed:
                            break
                    line = line.rstrip("\n")
                    if not line.strip():
                        continue
                    if '"control"' in line:
                        # stop tuples fan out: every worker must see one
                        for w, out in outs.items():
                            out.write(line + "\n")
                            out.flush()
                        routed += 1
                        continue
                    leaf = leaf_of(line)
                    wid = self._pick_worker(leaf, routed, assignment, outs)
                    outs[wid].write(line + "\n")
                    routed += 1
                    epoch_n += 1
                    epoch_by_worker[wid] = epoch_by_worker.get(wid, 0) + 1
                    if leaf is not None:
                        occ[leaf] = occ.get(leaf, 0) + 1
                    force_epoch = False
                    if epoch_n % 512 == 0:
                        outs[wid].flush()
                        if (self.monitor is not None
                                and self.monitor.pop_rebalance_request()):
                            force_epoch = True
                    if epoch_n >= self.epoch_records or force_epoch:
                        for out in outs.values():
                            out.flush()
                        n_to = self._rescale_due(routed)
                        if n_to is not None:
                            assignment = self._apply_rescale(
                                assignment, occ, epoch_by_worker, outs,
                                n_to, routed)
                        else:
                            assignment = self._epoch_boundary(
                                assignment, occ, epoch_by_worker)
                        epoch_n = 0
                        epoch_by_worker = {w: 0 for w in outs}
                    if (self.args.limit is not None
                            and routed >= self.args.limit):
                        break
            for out in outs.values():
                out.flush()
                os.fsync(out.fileno())
        finally:
            for out in outs.values():
                out.close()
        with self._lock:
            self._routed = routed
            for w, n in epoch_by_worker.items():
                self._routed_by_worker[w] = (
                    self._routed_by_worker.get(w, 0) + n)
        return routed

    def _apply_rescale(self, assignment: Dict[int, int],
                       occ: Dict[int, int],
                       epoch_by_worker: Dict[int, int],
                       outs: Dict[int, object], n_to: int,
                       routed: int) -> Dict[int, int]:
        """Live rescale at an epoch boundary. The boundary IS the
        barrier: every partition is flushed and no record is in flight,
        so leaf moves need no state copy — the merge's per-family twin
        union reassembles a window split across old and new owners.
        Scale-out spawns FRESH worker ids (a retired id's done marker and
        fenced outbox must never be re-inhabited); scale-in retires the
        HIGHEST ids by writing their done markers now (done marker =
        drain-to-EOF: the retiree finishes its already-routed records,
        writes its final graceful checkpoint — the savepoint — and exits
        0). The assignment is recomputed by ``balance_leaves`` over the
        new width and remapped through the sorted active list."""
        with self._lock:
            for w, n in epoch_by_worker.items():
                self._routed_by_worker[w] = (
                    self._routed_by_worker.get(w, 0) + n)
            active = sorted(self._active)
        n_from = len(active)
        if n_to > n_from:
            for _ in range(n_to - n_from):
                with self._lock:
                    nw = max(self._all) + 1
                    self._all.add(nw)
                    self._active.append(nw)
                    self._spawn_locked(nw, resume=False,
                                       reason=f"scale-out at {routed}")
                active.append(nw)
                wd = F.worker_dir(self.root, nw)
                outs[nw] = open(os.path.join(wd, F.PARTITION_FILE), "a")
        elif n_to < n_from:
            retire = active[n_to:]
            active = active[:n_to]
            with self._lock:
                self._active = [w for w in self._active
                                if w not in retire]
                self._retired.update(retire)
            for w in retire:
                out = outs.pop(w, None)
                if out is not None:
                    out.flush()
                    out.close()
                atomic_write_json(
                    os.path.join(F.worker_dir(self.root, w),
                                 F.DONE_MARKER),
                    {"routed_total": routed,
                     "epoch": self.manifest.fleet_epoch,
                     "retired": True})
            self._await_retirement(retire)
        packed = balance_leaves(occ, len(active))
        order = sorted(active)
        new_assignment = {leaf: order[slot]
                          for leaf, slot in packed.items()}
        # leaves the occupancy sample never saw keep their owner if it
        # survived the rescale, else deflect deterministically
        for leaf, w in assignment.items():
            if leaf not in new_assignment:
                new_assignment[leaf] = (w if w in order
                                        else order[leaf % len(order)])
        with self._lock:
            self.manifest.note_rescale(
                n_from=n_from, n_to=len(order), at_records=routed,
                epoch=self.manifest.fleet_epoch + 1)
            self.manifest.assign_all(new_assignment)
            self.manifest.advance_epoch()
            self.manifest.save()
        if self.monitor is not None:
            self.monitor.note("rescale", n_from=n_from, n_to=len(order),
                              at_records=routed,
                              epoch=self.manifest.fleet_epoch)
        print(f"# fleet rescale at {routed} records: {n_from} -> "
              f"{len(order)} workers (epoch {self.manifest.fleet_epoch})",
              flush=True)
        return new_assignment

    def _await_retirement(self, wids: List[int],
                          timeout_s: float = 60.0) -> None:
        """Bounded wait for retirees to drain to their done markers and
        exit. A retiree that crashes mid-drain stays covered by the
        ordinary ``_on_exit`` restart machinery (it is still in
        ``_procs``), so this wait is a convergence aid, not a
        correctness gate — routing resumes either way."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._lock:
                if self._failed or self._stopping:
                    return
                live = [w for w in wids if w in self._procs]
            if not live:
                return
            time.sleep(0.1)

    def _epoch_boundary(self, assignment: Dict[int, int],
                        occ: Dict[int, int],
                        epoch_by_worker: Dict[int, int]) -> Dict[int, int]:
        """Rebalance decision at an epoch boundary: worker loads come
        from the monitor's retained series when the plane is on
        (record→emit p99 + backlog residency — latency/backlog truth),
        else the last raw poll, else this epoch's routed-record counts;
        leaves move smallest-first from donor to receiver until roughly
        half the spread is covered."""
        with self._lock:
            for w, n in epoch_by_worker.items():
                self._routed_by_worker[w] = (
                    self._routed_by_worker.get(w, 0) + n)
            polls = dict(self._polls)
            active = sorted(self._active)
        loads: Dict[int, float] = {}
        for wid in active:
            sig = (self.monitor.rebalance_load(wid)
                   if self.monitor is not None else None)
            if sig is None:
                sig = _worker_load(polls.get(wid, {}))
            loads[wid] = (sig if sig is not None
                          else float(epoch_by_worker.get(wid, 0)))
        pair = pick_rebalance(loads)
        if pair is not None:
            donor, receiver = pair
            donor_leaves = sorted(
                (leaf for leaf, w in assignment.items() if w == donor),
                key=lambda leaf: occ.get(leaf, 0))
            budget = sum(occ.get(l, 0) for l in donor_leaves) // 4
            moved = []
            for leaf in donor_leaves[:-1]:  # never strip the last leaf
                if budget <= 0:
                    break
                assignment[leaf] = receiver
                budget -= occ.get(leaf, 0)
                moved.append(leaf)
            if moved:
                self.manifest.assign_all({l: receiver for l in moved})
                if self.monitor is not None:
                    self.monitor.note("rebalance", donor=donor,
                                      receiver=receiver, moved=len(moved),
                                      loads={str(k): round(v, 3)
                                             for k, v in loads.items()})
                print(f"# fleet epoch {self.manifest.fleet_epoch + 1}: "
                      f"moved {len(moved)} leaves worker{donor} -> "
                      f"worker{receiver}", flush=True)
        self.manifest.advance_epoch()
        self.manifest.save()
        if self.monitor is not None:
            self.monitor.note("epoch", epoch=self.manifest.fleet_epoch)
        return assignment

    def _write_done_markers(self, routed: int) -> None:
        with self._lock:
            wids = sorted(self._all - self._retired)
        for wid in wids:  # retirees already hold their rescale markers
            atomic_write_json(
                os.path.join(F.worker_dir(self.root, wid), F.DONE_MARKER),
                {"routed_total": routed,
                 "epoch": self.manifest.fleet_epoch})
        if self.monitor is not None:
            self.monitor.note("partition-eof", routed=routed,
                              epoch=self.manifest.fleet_epoch)

    # -------------------------------------------------------------- #
    # fleet view + federation payloads

    def fleet_view(self) -> dict:
        """The ``/fleet`` payload: one aggregated snapshot of every
        worker's liveness, restarts, and polled ops-plane state."""
        from spatialflink_tpu.utils.telemetry import fleet_snapshot

        with self._lock:
            procs = dict(self._procs)
            rcs = dict(self._rcs)
            polls = dict(self._polls)
            urls = dict(self._urls)
            incs = dict(self._incarnations)
            routed = self._routed
            routed_by = dict(self._routed_by_worker)
            restart_log = list(self._restart_log)
            all_wids = sorted(self._all)
            active = sorted(self._active)
            retired = sorted(self._retired)
            quarantined = dict(self._quarantined)
            suspicion = dict(self._suspicion)
            zombies = len(self._zombies)
        per_leaf: Dict[int, int] = {}
        for leaf, wid in self.manifest.fleet_assignment.items():
            per_leaf[wid] = per_leaf.get(wid, 0) + 1
        workers = []
        for wid in all_wids:
            hb = os.path.join(F.worker_dir(self.root, wid),
                              F.HEARTBEAT_FILE)
            fence = self.manifest.fence_of(wid)
            workers.append({
                "worker": wid,
                "alive": wid in procs,
                "rc": rcs.get(wid),
                "incarnations": incs.get(wid, 0),
                "restarts": self.manifest.fleet_restarts.get(wid, 0),
                "heartbeat_age_s": F.heartbeat_age_s(hb, fence=fence),
                "url": urls.get(wid),
                "leaves": per_leaf.get(wid, 0),
                "routed": routed_by.get(wid, 0),
                "fence": fence,
                "quarantined": wid in quarantined,
                "suspicion": round(suspicion.get(wid, 0.0), 2),
                "retired": wid in retired,
                "status": (polls.get(wid) or {}).get("status"),
                "latency": (polls.get(wid) or {}).get("latency"),
            })
        view = fleet_snapshot(workers, epoch=self.manifest.fleet_epoch,
                              routed=routed, restart_log=restart_log)
        # elastic-fleet state the base snapshot schema predates
        view["active_workers"] = active
        view["retired_workers"] = retired
        view["zombies"] = zombies
        view["fences"] = {str(w): self.manifest.fence_of(w)
                          for w in all_wids}
        view["fence_log"] = list(self.manifest.fleet_fence_log)[-50:]
        view["rescale_log"] = list(self.manifest.fleet_rescale_log)[-50:]
        view["quarantine_log"] = list(
            self.manifest.fleet_quarantine_log)[-50:]
        return view

    _PLANE_NOTE = ("fleet observability plane is off "
                   "(--fleet-plane off)")

    def fleet_events_payload(self, since: Optional[int] = None) -> dict:
        """``GET /fleet/events``: the merged timeline ring with the same
        ``?since=`` cursor semantics as a worker's ``/events`` —
        ``latest_seq`` never runs ahead of the delivered list."""
        mon = self.monitor
        if mon is None:
            return {"events": [], "total": 0, "latest_seq": 0,
                    "note": self._PLANE_NOTE}
        latest = mon.ring.total
        evs = mon.ring.list(since)
        if evs:
            latest = evs[-1]["seq"]
        elif since is not None:
            latest = max(latest, since)
        return {"events": evs, "total": mon.ring.total,
                "latest_seq": latest}

    def fleet_timeline_payload(self) -> dict:
        """``GET /fleet/timeline``: the merged causally-ordered fleet
        timeline (supervisor lifecycle + harvested worker events) plus
        per-lane counts — the JobManager-web-UI event view, one
        document."""
        mon = self.monitor
        if mon is None:
            return {"events": [], "lanes": {}, "total": 0,
                    "note": self._PLANE_NOTE}
        evs = mon.ring.list(None)
        lanes: Dict[str, int] = {}
        for e in evs:
            lane = (f"w{e.get('worker')}" if e.get("src") == "worker"
                    else "supervisor")
            lanes[lane] = lanes.get(lane, 0) + 1
        return {"schema": "fleet-timeline-v1",
                "ts_ms": int(time.time() * 1000),
                "events": evs, "lanes": lanes, "total": mon.ring.total}

    def fleet_latency_payload(self) -> dict:
        """``GET /fleet/latency``: after the merge, the persisted
        record→merged-emit lineage document (stage table + sum check);
        mid-run, the record→outbox-visible histogram plus the monitor's
        newest per-worker samples — the fleet-wide percentile view."""
        mon = self.monitor
        if mon is None:
            return {"stages": {}, "recent": [], "note": self._PLANE_NOTE}
        with self._lock:
            merged = self._merged_lat
        if merged is not None:
            doc = dict(merged)
        else:
            doc = {
                "schema": "fleet-latency-v1",
                "ts_ms": int(time.time() * 1000),
                "chain_stages": (["spread"] + list(CHAIN_STAGES)
                                 + list(FLEET_STAGES)),
                "stages": {},
                "record_emit": {"count": 0},
                "recent": [],
                "sum_check": {"windows": 0, "max_residual_ms": 0.0},
                "note": "merged lineage lands at the global merge; "
                        "mid-run this carries record->outbox-visible "
                        "and the per-worker series",
            }
        doc["record_visible"] = mon.visible_hist()
        doc["workers"] = {str(w): s
                          for w, s in mon.last_samples().items()}
        return doc

    def fleet_metrics_text(self) -> str:
        """``GET /fleet/metrics``: one scrape point for the fleet —
        every live worker's ``/metrics`` body fetched concurrently under
        the poll deadline, relabeled with ``worker="wN"`` (the PR 6/9
        proper-label discipline), ``# TYPE`` headers deduped keeping the
        first, plus supervisor-level fleet gauges."""
        with self._lock:
            urls = dict(self._urls)
            routed = self._routed
            alive = len(self._procs)
            all_wids = sorted(self._all)
            active_n = len(self._active)
            quarantined_n = len(self._quarantined)
            zombies_n = len(self._zombies)
        for wid in all_wids:
            if wid not in urls:
                url = self._resolve_url(wid)
                if url:
                    urls[wid] = url
        deadline = max(0.5, min(2.0, self.heartbeat_s))
        bodies: Dict[int, str] = {}
        futs = []
        try:
            for wid, url in sorted(urls.items()):
                futs.append((wid, self._poll_pool.submit(
                    _http_text, f"{url}/metrics", deadline)))
        except RuntimeError:
            futs = []  # pool shut down: supervisor exiting
        for wid, fut in futs:
            try:
                body = fut.result(timeout=deadline + 1.0)
            except Exception:
                body = None
            if body:
                bodies[wid] = _telemetry.relabel_prometheus_lines(
                    body, "worker", f"w{wid}")
        lines: List[str] = []
        seen_types = set()
        for wid in sorted(bodies):
            for line in bodies[wid].splitlines():
                if line.startswith("# TYPE"):
                    if line in seen_types:
                        continue
                    seen_types.add(line)
                if line:
                    lines.append(line)
        restarts = sum(self.manifest.fleet_restarts.values())
        lines += [
            "# TYPE spatialflink_fleet_workers_alive gauge",
            f"spatialflink_fleet_workers_alive {alive}",
            "# TYPE spatialflink_fleet_routed_records counter",
            f"spatialflink_fleet_routed_records {routed}",
            "# TYPE spatialflink_fleet_restarts_total counter",
            f"spatialflink_fleet_restarts_total {restarts}",
            "# TYPE spatialflink_fleet_workers_active gauge",
            f"spatialflink_fleet_workers_active {active_n}",
            "# TYPE spatialflink_fleet_workers_quarantined gauge",
            f"spatialflink_fleet_workers_quarantined {quarantined_n}",
            "# TYPE spatialflink_fleet_zombies gauge",
            f"spatialflink_fleet_zombies {zombies_n}",
            "# TYPE spatialflink_fleet_fence_bumps_total counter",
            ("spatialflink_fleet_fence_bumps_total "
             f"{len(self.manifest.fleet_fence_log)}"),
            "# TYPE spatialflink_fleet_rescales_total counter",
            ("spatialflink_fleet_rescales_total "
             f"{len(self.manifest.fleet_rescale_log)}"),
        ]
        return "\n".join(lines) + "\n"

    def fleet_tenants_payload(self) -> dict:
        """``GET /fleet/tenants``: every live worker's ``/tenants`` ledger
        fetched concurrently within the poll deadline and merged — rows
        summed per tenant, fleet-wide fairness recomputed over the merged
        kernel-ms shares (``utils.accounting.merge_tenant_payloads``).
        Like ``/fleet/metrics``, needs only the worker URLs the supervisor
        already resolves — not the observability monitor."""
        from spatialflink_tpu.utils import accounting as _accounting

        with self._lock:
            urls = dict(self._urls)
            all_wids = sorted(self._all)
        for wid in all_wids:
            if wid not in urls:
                url = self._resolve_url(wid)
                if url:
                    urls[wid] = url
        deadline = max(0.5, min(2.0, self.heartbeat_s))
        futs = []
        try:
            for wid, url in sorted(urls.items()):
                futs.append((wid, self._poll_pool.submit(
                    _http_json, f"{url}/tenants", deadline)))
        except RuntimeError:
            futs = []  # pool shut down: supervisor exiting
        payloads = []
        polled = 0
        for wid, fut in futs:
            try:
                body = fut.result(timeout=deadline + 1.0)
            except Exception:
                body = None
            if isinstance(body, dict):
                polled += 1
                if body.get("tenants"):
                    payloads.append(body)
        merged = _accounting.merge_tenant_payloads(payloads)
        merged["workers_polled"] = polled
        return merged

    # -------------------------------------------------------------- #
    # run

    def run(self) -> int:
        os.makedirs(self.root, exist_ok=True)
        leaf_of = self._leaf_fn()
        self._seed_assignment(leaf_of)
        graceful = False
        with self._lock:
            for wid in range(self.n_workers):
                ckpt = os.path.join(F.worker_dir(self.root, wid), "ckpt")
                resume = bool(os.path.isdir(ckpt) and os.listdir(ckpt))
                self._spawn_locked(wid, resume=resume, reason="start")
            self._monitor_thread = threading.Thread(
                target=self._monitor_loop, name="fleet-monitor",
                daemon=True)
            self._monitor_thread.start()
        try:
            routed = self._route(leaf_of)
            graceful = _metrics.shutdown_requested()
            if graceful:
                self._forward_sigterm()
            else:
                self._write_done_markers(routed)
            with self._lock:
                self._done_feeding = True
            rc = self._await_workers()
            if rc != 0:
                return rc
            # a SIGTERM landing after EOF (while workers drain their
            # already-complete partitions) is still a graceful stop
            graceful = graceful or _metrics.shutdown_requested()
            return self._finish(routed, graceful)
        finally:
            with self._lock:
                self._stopping = True
                procs = dict(self._procs)
                zombies = list(self._zombies)
            for proc in procs.values():
                if proc.poll() is None:
                    proc.terminate()
            for _, proc in zombies:
                # fenced predecessors must not outlive the supervisor
                if proc.poll() is None:
                    try:
                        proc.kill()
                    except OSError:
                        pass
            mon = self._monitor_thread
            if mon is not None:
                mon.join(timeout=5.0)
            self._poll_pool.shutdown(wait=False)
            for relay in list(self._relays.values()):
                relay.join(timeout=1.0)
            if self.monitor is not None:
                self.monitor.close()
            for log in self._logs.values():
                try:
                    log.close()
                except OSError:
                    pass

    def _forward_sigterm(self) -> None:
        with self._lock:
            if self._draining:
                return
            self._draining = True
            procs = dict(self._procs)
        if self.monitor is not None:
            self.monitor.note("drain", workers=len(procs))
        print("# fleet: draining workers (SIGTERM)", flush=True)
        for proc in procs.values():
            if proc.poll() is None:
                try:
                    proc.terminate()
                except OSError:
                    pass

    def _await_workers(self) -> int:
        """Wait for every worker to reach a clean exit; the monitor keeps
        restarting crashed ones until the restart cap trips."""
        while True:
            if _metrics.shutdown_requested():
                self._forward_sigterm()  # SIGTERM after EOF: drain anyway
            with self._lock:
                failed = self._failed
                procs = dict(self._procs)
            if failed:
                wid, rc = failed
                print(f"# fleet: worker{wid} failed permanently "
                      f"(rc={rc}, restart cap {self.restart_cap})",
                      file=sys.stderr, flush=True)
                return 1
            if not procs:
                return 0
            time.sleep(0.1)

    def _finish(self, routed: int, graceful: bool) -> int:
        per_worker = {}
        runs = {}
        compiles = 0
        with self._lock:
            all_wids = sorted(self._all)
        if self.monitor is not None:
            # one final tail per worker: stamp any line that landed after
            # the monitor loop's last scan, so every merged window has an
            # outbox-visible stamp
            for wid in all_wids:
                self.monitor.scan_outbox(wid)
        for wid in all_wids:
            wd = F.worker_dir(self.root, wid)
            # fence-aware read: rows a superseded incarnation (a zombie)
            # appended past its cutoff are dropped and counted here, never
            # merged — containment by construction, not by kill latency
            stats: Dict[str, int] = {}
            cutoffs = {f: c["outbox"] for f, c in
                       self.manifest.fence_cutoffs(wid).items()}
            per_worker[wid] = F.read_outbox(
                os.path.join(wd, F.OUTBOX_FILE),
                fence_cutoffs=cutoffs, stats=stats)
            with self._lock:
                self._outbox_stats[wid] = stats
            runs[wid] = F.read_runs(wd)
            compiles += sum(int(r.get("post_warmup_compiles") or 0)
                            for r in runs[wid])
        with self._lock:
            outbox_stats = {w: dict(s)
                            for w, s in self._outbox_stats.items()}
        stale_rows = sum(s.get("stale_fence_rows", 0)
                         for s in outbox_stats.values())
        fence_conflicts = sum(s.get("fence_conflicts", 0)
                              for s in outbox_stats.values())
        if stale_rows and self.monitor is not None:
            self.monitor.note("stale-fence-drop", rows=stale_rows,
                              conflicts=fence_conflicts)
        merged = F.merge_outboxes(per_worker, self.case.family,
                                  k=self.params.query.k)
        t_merged_ms = time.time() * 1e3
        tmp = os.path.join(self.root, F.MERGED_FILE + ".tmp")
        with open(tmp, "w") as f:
            for doc in merged:
                f.write(json.dumps(doc, sort_keys=True) + "\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(self.root, F.MERGED_FILE))
        t_emit_ms = time.time() * 1e3
        digest = F.merged_table_digest(merged)
        lineage = None
        if self.monitor is not None:
            lineage = compute_merged_lineage(
                merged, per_worker, self.monitor.visible_ms,
                t_merged_ms, t_emit_ms)
            lineage["record_visible"] = self.monitor.visible_hist()
            lineage["workers"] = {str(w): s for w, s in
                                  self.monitor.last_samples().items()}
            atomic_write_json(os.path.join(self.root, F.LATENCY_FILE),
                              lineage)
            with self._lock:
                self._merged_lat = lineage
            self.monitor.note(
                "merge", windows=len(merged), digest=digest[:16],
                merged_p99_ms=(lineage.get("record_emit") or {}).get(
                    "p99"))
        with self._lock:
            restart_log = list(self._restart_log)
        with self._lock:
            active = sorted(self._active)
            retired = sorted(self._retired)
        result = {
            "digest": digest,
            "workers": self.n_workers,
            "workers_final": len(active),
            "workers_all": all_wids,
            "retired_workers": retired,
            "routed": routed,
            "merged_windows": len(merged),
            "epochs": self.manifest.fleet_epoch,
            "restarts": {str(k): v for k, v in
                         self.manifest.fleet_restarts.items()},
            "restart_log": restart_log,
            "post_warmup_compiles": compiles,
            "graceful": graceful,
            "fences": {str(w): self.manifest.fence_of(w)
                       for w in all_wids},
            "stale_fence_rows": stale_rows,
            "fence_conflicts": fence_conflicts,
            "rescales": list(self.manifest.fleet_rescale_log),
            "quarantines": list(self.manifest.fleet_quarantine_log),
            "runs": {str(k): v for k, v in runs.items()},
        }
        if lineage is not None:
            # headline lineage numbers ride the result doc (full table in
            # fleet_latency.json); the digest input is UNTOUCHED
            result["latency"] = {
                "record_emit": lineage["record_emit"],
                "sum_check": lineage["sum_check"],
                "skipped_no_lat": lineage.get("skipped_no_lat", 0),
            }
        atomic_write_json(os.path.join(self.root, F.RESULT_FILE), result)
        stale_note = (f", stale fence rows dropped {stale_rows}"
                      if stale_rows else "")
        print(f"# fleet merged {len(merged)} windows from "
              f"{len(all_wids)} workers (routed {routed}, "
              f"restarts {sum(self.manifest.fleet_restarts.values())}, "
              f"post-warmup compiles {compiles}{stale_note}, "
              f"digest {digest[:16]})",
              flush=True)
        return 0


# --------------------------------------------------------------------- #
# driver entry


def run_supervisor(args, params, spec, base_argv: List[str]) -> int:
    """``--fleet N``: run the supervisor role. Owns its own opserver
    (serving ``/fleet`` and the ``/fleet/latency|timeline|events|metrics``
    federation), the fleet stderr digest, and the SIGTERM drain handler;
    returns the process exit code."""
    from spatialflink_tpu.runtime.opserver import OpServer

    sup = FleetSupervisor(args, params, spec, base_argv)
    _set_active(sup)
    _metrics.clear_shutdown()
    prev_term = None
    on_main = threading.current_thread() is threading.main_thread()
    if on_main:
        prev_term = signal.signal(
            signal.SIGTERM, lambda s, f: _metrics.request_shutdown())
    server = None
    if args.status_port is not None:
        server = OpServer(port=args.status_port).start()
        print(f"# fleet opserver: {server.url}/fleet "
              "(+ /fleet/latency /fleet/timeline /fleet/events "
              "/fleet/metrics /fleet/tenants)", flush=True)
    live = None
    if getattr(args, "live_stats", False):
        live = FleetLiveStats(
            sup, interval_s=getattr(args, "telemetry_interval", 5.0)
        ).start()
    try:
        return sup.run()
    finally:
        if live is not None:
            live.close()
        if server is not None:
            server.close()
        if on_main and prev_term is not None:
            signal.signal(signal.SIGTERM, prev_term)
        _set_active(None)
