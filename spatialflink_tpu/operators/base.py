"""Operator plumbing: query configuration, window/micro-batch drivers.

Reference parity:
- :class:`QueryType` — ``spatialOperators/QueryType.java:3-7`` (RealTime,
  WindowBased, CountBased; the reference declares CountBased and throws
  "Not yet support" everywhere except tAggregate — here sliding count
  windows are IMPLEMENTED for every single-stream windowed operator).
- :class:`QueryConfiguration` — ``spatialOperators/QueryConfiguration.java``
  plus the window/approximate fields the reference passes via ``Params``.
- Real-time mode: the reference uses tiny tumbling windows with
  fire-per-element triggers (``tJoin/TJoinQuery.java:216-268``). The TPU
  equivalent is micro-batching: arrivals are grouped into batches of at most
  ``realtime_batch_size`` records and evaluated in one kernel launch, giving
  per-arrival-group latency without per-tuple kernel dispatch.
"""

from __future__ import annotations

import enum
import sys
import time
from collections import deque
import dataclasses
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Iterator, List, Optional, Tuple

import numpy as np

from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import Point, PointBatch
from spatialflink_tpu.runtime import WindowAssembler, WindowSpec
from spatialflink_tpu.utils import IdInterner


class QueryType(enum.Enum):
    RealTime = "realtime"
    WindowBased = "window"
    # the reference DECLARES CountBased and throws "Not yet support" in
    # every operator except tAggregate (QueryType.java:6); here it is
    # implemented: sliding count windows (every `slide` arrivals, the last
    # `size` records) for every single-stream windowed operator — see
    # SpatialOperator._windows. tAggregate keeps its per-cell counting
    # (reference parity); two-stream joins and the apps with bespoke window
    # logic still reject.
    CountBased = "count"


@dataclass
class QueryConfiguration:
    query_type: QueryType = QueryType.WindowBased
    window_size_ms: int = 10_000
    slide_ms: int = 5_000
    allowed_lateness_ms: int = 0
    # approximate mode: range queries skip the CN distance check (reference
    # parity); kNN uses lax.approx_min_k, trading RECALL (< 1, neighbors may
    # drop) where the reference traded ranking accuracy — see _knn_strategy
    approximate: bool = False
    realtime_batch_size: int = 512
    k: int = 10  # kNN only
    # max windows in flight on device before the driver blocks on the oldest;
    # >=2 overlaps host batch assembly with device compute (SURVEY §7's
    # host/device-overlap requirement — JAX dispatch is async until read)
    pipeline_depth: int = 2
    # device-mesh width: when > 1, EVERY operator family's window batches
    # are sharded (contiguously — see parallel.mesh on why not cell-bucketed)
    # across a 1-D mesh on the stream dim and merged with XLA collectives
    # (parallel.ops) — the keyBy(gridID) data parallelism of SURVEY §2.5,
    # minus the reference's parallelism-1 windowAll merge.
    # Must be a power of two (batch capacities are power-of-two buckets).
    devices: Optional[int] = None
    # outer (DCN) axis width: hosts > 1 builds a 2-D (hosts x devices/hosts)
    # mesh — kNN merges become two-level ICI->DCN (k * hosts DCN traffic,
    # window-size independent), filters/joins shard over both axes. Must be
    # a power of two dividing ``devices``.
    hosts: Optional[int] = None
    # pane-incremental execution (the --panes driver switch): sliding-window
    # batches are sliced into non-overlapping slide-aligned PANES, the
    # device kernel runs once per sealed pane, and each window merges its
    # size/slide cached pane partials instead of re-evaluating the full
    # window — at overlap o the per-slide kernel work drops ~o-fold. OFF by
    # default; bypassed (full recompute, identical results) for tumbling
    # windows (overlap 1: nothing to share), non-pane-decomposable specs
    # (slide must divide size), realtime/count modes, and operators without
    # a mergeable partial (run_incremental, tKnn's sub-trajectory windows).
    # Composes with pipeline_depth (pane kernels dispatch async and merge at
    # readback) and with the device mesh (each pane batch shards like a
    # window batch would).
    panes: bool = False
    # device-resident pane state (the --pane-merge driver switch): pane
    # kernel partials stay in HBM across slides and each window's merge is
    # a DEVICE op (kNN gather+re-top-k mirroring the shard merge), with only
    # the sealed window's merged result read back — instead of resolving
    # each partial to host (a blocking dispatch->readback sync per pane)
    # and merging there. None = AUTO: device on accelerator
    # backends, host on CPU (measured: the per-window merge dispatch costs
    # more than the host dict-merge of k-sized partials there, and
    # steady-state readback bytes are ~equal because PR 3's memoized
    # partials already cross at most once). Families without a device merge
    # (filter-shaped partials, whose host union is a plain concat of masks
    # each read exactly once) and host-resident partials
    # (checkpoint-restored) fall back to the host merge — results identical
    # either way.
    pane_device_merge: Optional[bool] = None
    # elastic-degradation bound: at most this many mesh halvings may absorb
    # dispatch failures before the operator raises instead of retrying
    # narrower. None = halvings down to TWO devices; the final halving to 1
    # ALWAYS raises — a failure surviving every multi-device width is a
    # distributed-path bug (or total hardware loss), and silently running
    # single-device forever hides it (the tradeoff VERDICT r4 flagged).
    # Deliberate single-device operation is devices=1/None, not degradation.
    max_degradations: Optional[int] = None
    # coordinated-checkpointing hook (the --checkpoint-dir driver switch):
    # a runtime.checkpoint.CheckpointCoordinator the operator registers its
    # window/pane state with and barriers against between processing units.
    # None (default) = no checkpointing — every hot path checks once.
    checkpointer: Optional[Any] = field(default=None, repr=False,
                                        compare=False)
    # skew-adaptive refinement layer (the --adaptive-grid driver switch):
    # an index.AdaptiveGrid whose leaf-space GN∪CN masks gate window-batch
    # membership HOST-SIDE before the kernel dispatch (the pre-kernel
    # candidate prefilter). Records keep their base cells, device kernels
    # and masks are untouched, and the leaf masks are a sound
    # over-approximation for every layout — exact-mode results are
    # identical to the uniform grid; the win is the smaller padded batch
    # on skewed streams. None (default) = uniform grid only.
    adaptive_grid: Optional[Any] = field(default=None, repr=False,
                                         compare=False)
    # mesh shard placement (--shard-order): "arrival" keeps the default
    # contiguous sharding; "cell" applies parallel.mesh.cell_hash_order so
    # whole grid cells co-locate per shard (keyBy(gridID) parity), with the
    # inverse permutation restoring mask alignment at readback. Results
    # are identical either way; see BASELINE.md for the measured verdict.
    shard_order: str = "arrival"

    def window_spec(self) -> WindowSpec:
        if self.query_type is QueryType.CountBased:
            # count windows trigger on ARRIVAL ORDER (operators/base.py
            # _count_windows); every caller of this method builds
            # event-time windows, which would silently reinterpret the
            # count values as milliseconds
            raise NotImplementedError(
                "count windows are record-path only; a WindowSpec is "
                "event-time — run() implements CountBased")
        return WindowSpec.sliding(self.window_size_ms, self.slide_ms)


@dataclass
class Deferred:
    """A window's result that has been *dispatched* to the device but not
    read back. ``device_result`` holds live jax arrays (computation already
    enqueued — JAX dispatch is asynchronous); ``collect`` turns them into the
    final host-side record list, forcing the device→host transfer.

    Operators return this from eval_batch so the window driver can keep
    ``pipeline_depth`` windows in flight: while the device works on window i,
    the host assembles and dispatches window i+1 (the double-buffering the
    reference gets for free from Flink's pipelined operator chains).
    """

    device_result: Any
    collect: Callable[[Any], List]

    def finish(self) -> List:
        return self.collect(self.device_result)


class PaneCache:
    """Shared pane-partial cache bookkeeping: get-or-evaluate with the
    ``pane-cache-hits``/``pane-cache-misses`` registry counters and
    ascending-window eviction — ONE implementation for the generic driver
    (:meth:`SpatialOperator._pane_eval`), the trajectory pane loops, and
    the join pane-pair blocks (whose keys are (pane_a, pane_b) tuples:
    ``key_floor`` maps a key to the pane start its eviction hinges on).

    Eviction contract: windows arrive in ascending start order, so once
    window ``s`` has looked up its panes, no later window can need a key
    whose floor is below ``s + slide``. ``None`` is a legitimate cached
    value (an empty-after-filter pane), hence the ``in`` check."""

    __slots__ = ("slide", "cache", "hits", "misses", "key_floor")

    def __init__(self, slide_ms: int, key_floor=None):
        from spatialflink_tpu.utils.metrics import REGISTRY

        self.slide = slide_ms
        self.cache: dict = {}
        self.hits = REGISTRY.counter("pane-cache-hits")
        self.misses = REGISTRY.counter("pane-cache-misses")
        self.key_floor = key_floor if key_floor is not None else (lambda k: k)

    def get(self, key, evaluate):
        if key in self.cache:
            self.hits.inc()
            return self.cache[key]
        self.misses.inc()
        value = self.cache[key] = evaluate()
        return value

    def evict_before(self, window_start: int) -> None:
        limit = window_start + self.slide
        for dead in [k for k in self.cache if self.key_floor(k) < limit]:
            del self.cache[dead]

    def snapshot(self, encode_value) -> dict:
        """JSON-able cache contents for the checkpoint coordinator, so a
        resumed run does not redo pane kernels. ``encode_value`` is the
        pane-partial codec (``runtime.checkpoint.value_codec``); entries it
        cannot encode are SKIPPED with a counter — on resume they are plain
        cache misses and recompute, never wrong. :class:`PanePartial`
        wrappers are resolved first (forcing the device readback — snapshot
        time is off the critical path) and re-wrapped on restore."""
        import json as _json

        from spatialflink_tpu.utils.metrics import REGISTRY

        entries = []
        skipped = REGISTRY.counter("pane-cache-snapshot-skipped")
        for key, value in self.cache.items():
            wrapped = isinstance(value, PanePartial)
            try:
                enc = encode_value(value.resolve() if wrapped else value)
            except TypeError:
                skipped.inc()
                continue
            entries.append([_json.dumps(key), wrapped, enc])
        return {"entries": entries}

    def restore(self, state: dict, decode_value) -> None:
        """Inverse of :meth:`snapshot` (keys round-trip through JSON:
        list-form keys — the join path's pane pairs — become tuples)."""
        import json as _json

        for raw_key, wrapped, enc in state.get("entries", []):
            key = _json.loads(raw_key)
            if isinstance(key, list):
                key = tuple(key)
            value = decode_value(enc)
            self.cache[key] = PanePartial(value) if wrapped else value


def _device_nbytes(x) -> int:
    """Summed ``nbytes`` over the array leaves of a deferred device payload
    (tuples/NamedTuples/lists of jax or numpy arrays) — the readback-bytes
    accounting the device-vs-host pane-state bench reads."""
    total = 0
    stack = [x]
    while stack:
        v = stack.pop()
        if v is None:
            continue
        nb = getattr(v, "nbytes", None)
        if nb is not None:
            total += int(nb)
        elif isinstance(v, (tuple, list)):
            stack.extend(v)
    return total


class PanePartial:
    """One pane's cached kernel partial. Holds the raw evaluator output —
    a :class:`Deferred` (device work in flight / resident in device memory)
    or an already-final host value — and memoizes the readback so every
    window sharing the pane pays the device→host transfer at most once.
    Under the device pane merge the Deferred is typically NEVER resolved:
    the merge kernel consumes the resident arrays and only the merged
    window result crosses to host (``resolve`` still works — the
    checkpoint snapshot uses it, which is the readback-on-snapshot
    contract). ``stats_done`` marks pruning-counter scalars already
    consumed by a device merge, so they count once per pane."""

    __slots__ = ("value", "stats_done")

    def __init__(self, value):
        self.value = value
        self.stats_done = False

    def resolve(self):
        if isinstance(self.value, Deferred):
            from spatialflink_tpu.utils.metrics import REGISTRY

            REGISTRY.counter("pane-partial-readbacks").inc()
            REGISTRY.counter("pane-partial-readback-bytes").inc(
                _device_nbytes(self.value.device_result))
            self.value = self.value.finish()
        return self.value


@dataclass
class WindowResult:
    """One emitted result event: the records selected in [start, end).

    Count-window mode is the one exception to the half-open contract:
    there the bounds are the buffered records' min/max event timestamps,
    so ``window_end`` is INCLUSIVE (count windows have no wall-clock
    extent — see ``SpatialOperator._count_windows``). Consumers that key
    on spans must not mix the two conventions."""

    window_start: int
    window_end: int
    records: List = field(default_factory=list)
    extras: dict = field(default_factory=dict)

    def flat_records(self) -> List:
        """Records flattened across the multi-query axis: ``records`` is one
        list per query when ``extras['queries']`` is set (run_multi
        windows); every record sink flattens through here so the
        one-record-per-line/message contract cannot drift per sink."""
        if "queries" in self.extras:
            return [r for per_query in self.records for r in per_query]
        return self.records


def merge_window_records(family: str, parts: List[List], *, k=None,
                         tie_key=None) -> List:
    """The per-family GLOBAL merge seam: combine one window's record lists
    from disjoint partitions of the stream into the windowAll result a
    single unpartitioned run would have produced.

    This is the fleet's merge stage, and it deliberately reuses the pane/
    shard merge twins rather than inventing a third semantics: filter
    families (range/tRange/join — any family whose window is a SELECTION
    of its input records) merge by union, exactly the host pane
    concatenation, because a record routed to exactly one partition
    appears in exactly one part; kNN merges through
    :func:`~spatialflink_tpu.ops.knn.merge_topk_host` (concatenate, dedup
    by id keeping the min distance, re-top-k) — exact by the same covering
    argument as the pane/shard merges, since every partition emits its
    local top-k over a superset-free subset of the candidates.

    ``tie_key`` for kNN must reproduce the single-run tie order at the
    k-th place (see ``merge_topk_host``); partitioned runs that cannot
    share an interner pass a content key (e.g. ``str``) and accept that
    exact-distance ties may order differently from a single-process run.
    """
    if family == "knn":
        if not k:
            raise ValueError("kNN merge needs k (the fleet's per-window "
                             "re-top-k bound)")
        from spatialflink_tpu.ops.knn import merge_topk_host

        return merge_topk_host(parts, int(k), tie_key=tie_key)
    # selection families: disjoint-partition union (the host pane merge)
    out: List = []
    for part in parts:
        out.extend(part)
    return out


class _LeafMaskCache:
    """One query's leaf-space mask under the adaptive grid, invalidated by
    the grid's monotonic version stamp: a repartition bumps ``version`` and
    the next window rebuilds the mask (counted on
    ``prefilter-mask-recomputes``). The cache is per run()-closure, so
    every standing query owns exactly one."""

    __slots__ = ("grid", "build", "version", "mask")

    def __init__(self, grid, build):
        self.grid = grid
        self.build = build
        self.version = -1
        self.mask = None

    def get(self):
        if self.mask is None or self.version != self.grid.version:
            from spatialflink_tpu.utils.metrics import REGISTRY

            if self.mask is not None:
                REGISTRY.counter("prefilter-mask-recomputes").inc()
            self.mask = self.build()
            self.version = self.grid.version
        return self.mask


class SpatialOperator:
    """Shared driver: turns a record stream into point-window batches."""

    # CountBased: implemented for every single-stream windowed operator
    # (the _windows assembler branches on it); the reference declares the
    # mode and throws "Not yet support" everywhere except tAggregate's
    # per-cell count windows (``TAggregateQuery.java:381-494``), which keep
    # their keyed semantics. Two-stream joins (whose count trigger is
    # ambiguous across sides) and apps with bespoke window logic opt OUT.
    supports_count_windows = True

    #: query-family label scoping telemetry span names (``knn.dispatch`` vs a
    #: flat namespace) so multi-family / --multi-query runs stay separable
    #: in one snapshot stream; subclasses set "range"/"knn"/"join"/"tknn"/…
    #: (None falls back to the class name)
    telemetry_label: Optional[str] = None

    #: window payloads may be columnar LazyRecords views over the batched
    #: decode's SoA chunks (device batches build straight from the slices;
    #: obj ids live in the STREAM's decode-interner space). Operators whose
    #: cross-window state or result resolution is keyed by the OPERATOR
    #: interner (the trajectory families' TrajStateStore, the apps) opt out
    #: — their windows materialize per-record objects as before (the decode
    #: itself stays chunk-vectorized either way).
    columnar_windows = True

    def __init__(self, conf: QueryConfiguration, grid: UniformGrid,
                 grid2: Optional[UniformGrid] = None):
        if (conf.query_type is QueryType.CountBased
                and not self.supports_count_windows):
            raise NotImplementedError("CountBased queries are not yet supported")
        if conf.devices and (conf.devices & (conf.devices - 1)):
            raise ValueError(
                f"conf.devices={conf.devices}: must be a power of two")
        if conf.hosts and conf.hosts > 1:
            if conf.hosts & (conf.hosts - 1):
                raise ValueError(
                    f"conf.hosts={conf.hosts}: must be a power of two")
            if not conf.devices or conf.devices % conf.hosts:
                raise ValueError(
                    f"conf.hosts={conf.hosts} must divide "
                    f"conf.devices={conf.devices}")
        # own copy: degraded mode mutates conf.devices, and a caller-shared
        # config must not silently degrade sibling operators (their cached
        # meshes would go stale against the mutated width)
        self.conf = dataclasses.replace(conf)
        self.grid = grid
        self.grid2 = grid2 or grid
        self.interner = IdInterner()
        self._mesh_obj = None
        self._degradations = 0  # elastic halvings absorbed so far

    @property
    def distributed(self) -> bool:
        return bool(self.conf.devices and self.conf.devices > 1)

    def _mesh(self):
        """Lazy device mesh for ``conf.devices`` (device access is deferred
        until the first window actually evaluates): 1-D, or 2-D
        (hosts x devices/hosts) when ``conf.hosts`` > 1 — the multi-host
        shape whose outer-axis collectives ride DCN."""
        if self._mesh_obj is None:
            from spatialflink_tpu.parallel.mesh import make_mesh, make_mesh_2d

            if self.conf.hosts and self.conf.hosts > 1:
                self._mesh_obj = make_mesh_2d(
                    self.conf.hosts, self.conf.devices // self.conf.hosts)
            else:
                self._mesh_obj = make_mesh(self.conf.devices)
        return self._mesh_obj

    def _shard(self, batch):
        """Place a window batch with its point dim sharded over the mesh
        (over BOTH axes of a 2-D mesh); the ``<q>.place`` span, inside the
        window's ``<q>.dispatch``."""
        from spatialflink_tpu.parallel.mesh import shard_batch
        from spatialflink_tpu.utils import telemetry as _telemetry

        mesh = self._mesh()
        with _telemetry.span("place", query=self.telemetry_label
                             or type(self).__name__):
            return shard_batch(batch, mesh, axis=tuple(mesh.axis_names))

    def _degrade_mesh(self, err: BaseException) -> None:
        """Elastic degraded mode (SURVEY §7 phase 7): a device failure during
        a distributed window halves the mesh (keeping the power-of-two
        invariant — any smaller power of two still divides the bucketed
        batch capacities) and the window is re-dispatched. Host-side state
        (window assembler, trajectory maps, checkpoints) is untouched, so
        degradation is purely a dispatch concern. The reference inherits its
        equivalent (restart from checkpoint on a task-manager loss) from
        Flink; here a recompile at the new shard count is the only cost.

        BOUNDED: degradation stops at two devices (or after
        ``conf.max_degradations`` halvings) and then raises loudly — a
        failure that survives every multi-device width is a deterministic
        distributed-path bug or total hardware loss, and absorbing it as a
        permanent silent single-device run would hide it (the counter-only
        tradeoff VERDICT r4 asked to bound)."""
        from spatialflink_tpu.utils.metrics import REGISTRY

        new = max(1, (self.conf.devices or 1) // 2)
        limit = self.conf.max_degradations
        if new < 2 or (limit is not None and self._degradations >= limit):
            raise RuntimeError(
                f"distributed dispatch failed after {self._degradations} "
                f"elastic degradation(s) (mesh width {self.conf.devices}); "
                "refusing to silently fall back to a permanent single-device "
                "run — a failure at every multi-device width is almost "
                "certainly a distributed-path bug (check the "
                "'mesh-degradations' counter and the chained error); run "
                "with devices=1 to bypass the mesh deliberately"
            ) from err
        print(f"warning: device failure during distributed window "
              f"({type(err).__name__}: {str(err)[:200]}); degrading mesh "
              f"{self.conf.devices} -> {new}", file=sys.stderr)
        REGISTRY.counter("mesh-degradations").inc()
        from spatialflink_tpu.utils.telemetry import emit_event

        emit_event("mesh-degradation", error_type=type(err).__name__,
                   from_devices=self.conf.devices, to_devices=new)
        self._degradations += 1
        self.conf.devices = new
        # a 2-D mesh drops to flat 1-D: after losing devices the hosts x
        # chips factorization no longer reflects the hardware, and results
        # are mesh-layout invariant anyway
        self.conf.hosts = None
        self._mesh_obj = None

    def _eval_degradable(self, single_fn, dist_fn, batch=None):
        """Run ``dist_fn(mesh)`` — or ``dist_fn(mesh, sharded_batch)`` when
        ``batch`` is given — with elastic retry at halved mesh widths;
        ``single_fn()`` serves callers invoking this on a non-distributed
        operator (degradation itself never reaches it: the final halving
        to one device raises instead — see ``_degrade_mesh``).

        Catches ``RuntimeError`` (``XlaRuntimeError``'s base — device loss,
        transfer failures) raised at DISPATCH time. Two documented
        tradeoffs: (1) with async dispatch (``pipeline_depth >= 2``) a
        failure can instead surface at the deferred readback, after this
        frame has returned — there it PROPAGATES to the caller (the
        window's inputs are gone); recovery is the framework's normal
        resume story (checkpoint ``--resume`` for stateful operators,
        source replay for stateless windows). (2) availability is BOUNDED:
        transient failures absorb as halvings down to two devices (or
        ``conf.max_degradations``), but a failure surviving every
        multi-device width — the signature of a deterministic
        distributed-path bug rather than hardware — raises loudly from
        ``_degrade_mesh`` instead of becoming a permanent silent
        single-device run. Bugs in the shared per-shard closure still
        re-raise from the single-device path; non-RuntimeError exceptions
        (shape/type bugs) propagate unchanged."""

        while self.distributed:
            try:
                mesh = self._mesh()
                if batch is not None:
                    return dist_fn(mesh, self._shard(batch))
                return dist_fn(mesh)
            except RuntimeError as e:
                self._degrade_mesh(e)
        return single_fn()

    # ------------------------- checkpointing -------------------------- #

    @property
    def _ckpt(self):
        """The run's CheckpointCoordinator (None = checkpointing off)."""
        return self.conf.checkpointer

    def _record_codec(self):
        from spatialflink_tpu.runtime.checkpoint import record_codec

        return record_codec(self.grid)

    def _register_ckpt_windows(self, name: str, wa) -> None:
        """Register a WindowAssembler/PaneBuffer (both expose the same
        ``snapshot(encode)``/``restore(state, decode)`` shape) with the
        coordinator; loaded state restores the moment it registers."""
        coord = self._ckpt
        if coord is None:
            return
        enc, dec = self._record_codec()
        coord.register(name, lambda: ({}, wa.snapshot(enc)),
                       lambda _arrays, meta: wa.restore(meta, dec))

    def _register_ckpt_pane_cache(self, name: str, cache: "PaneCache"
                                  ) -> None:
        coord = self._ckpt
        if coord is None:
            return
        from spatialflink_tpu.runtime.checkpoint import value_codec

        enc, dec = value_codec(self.grid)
        coord.register(name, lambda: ({}, cache.snapshot(enc)),
                       lambda _arrays, meta: cache.restore(meta, dec))
        # pane partials of the trajectory families index by INTERNED object
        # id across windows — restored partials are only meaningful against
        # the interner that minted those ids, so it checkpoints alongside
        # every pane cache (harmless for families whose partials carry
        # resolved string ids)
        self._register_ckpt_interner()

    def _register_ckpt_interner(self) -> None:
        coord = self._ckpt
        if coord is None:
            return

        def restore(_arrays, meta):
            from spatialflink_tpu.utils import IdInterner

            self.interner = IdInterner.from_list(meta["ids"])

        coord.register("interner",
                       lambda: ({}, {"ids": self.interner.to_list()}),
                       restore)

    def _checkpoint_barrier(self) -> None:
        """Barrier for the NON-pipelined drive loops (no deferred windows in
        flight): call at the end of a loop body, after any ``yield`` — at
        that point the yielded result has been fully consumed downstream,
        so every snapshotted structure is consistent with the noted source
        positions."""
        coord = self._ckpt
        if coord is not None:
            coord.barrier()

    # ---------------------------------------------------------------- #

    # --------------------- adaptive-grid prefilter -------------------- #

    def _leaf_mask_cache(self, build) -> Optional[_LeafMaskCache]:
        """A version-stamped cache of one query's GN∪CN leaf mask, or None
        when the adaptive refinement layer is off (``conf.adaptive_grid``
        unset) — the single gate every prefiltering operator checks."""
        ag = self.conf.adaptive_grid
        return _LeafMaskCache(ag, build) if ag is not None else None

    @staticmethod
    def _record_arrays(records):
        """(x, y, ts, obj_id, cell) numpy arrays for a window's records —
        zero-copy from a columnar LazyRecords window, one materializing
        pass for plain record lists (obj_id is None there: the prefiltered
        range batches never read it)."""
        from spatialflink_tpu.streams.bulk import LazyRecords

        if isinstance(records, LazyRecords):
            flat = records._flat()
            if flat is not None:
                return flat[0], flat[1], flat[2], flat[3], flat[4]
        xs = np.array([r.x for r in records], np.float64)
        ys = np.array([r.y for r in records], np.float64)
        ts = np.array([r.timestamp for r in records], np.int64)
        cells = np.array([r.cell for r in records], np.int32)
        return xs, ys, ts, None, cells

    @staticmethod
    def _chunk_leaves(chunk, ag) -> np.ndarray:
        """Per-CHUNK leaf assignment, cached on the chunk and stamped with
        the grid version: sliding windows revisit each chunk size/slide
        times, so the two-stage assignment runs once per chunk per layout
        (exactly how base cells are assigned once per chunk in
        ``PointChunk.build``), not once per window membership."""
        cache = getattr(chunk, "_leaf_cache", None)
        if cache is not None and cache[0] == ag.version:
            return cache[1]
        leaf = ag.assign_leaf(chunk.parsed.x, chunk.parsed.y)
        chunk._leaf_cache = (ag.version, leaf)
        return leaf

    def _prefilter(self, records, mask_cache: Optional[_LeafMaskCache],
                   ts_base: int):
        """Pre-kernel candidate prefilter over the refined leaf space: keep
        exactly the records whose leaf is in the query's GN∪CN leaf set and
        build the (smaller) device batch from the kept rows. Returns
        ``(keep_idx, PointBatch)`` — ``batch`` None when NO leaf survives
        (the window skips its kernel dispatch entirely) — or None when the
        layer is off.

        Identity: the leaf masks over-approximate the kernel's own
        GN/CN-and-distance selection for EVERY layout (every selected
        record lies within ``radius``, hence in a leaf the mask keeps), so
        the filtered dispatch emits the same records — the counters
        ``prefilter-records``/``prefilter-kept`` are the candidate-set
        selectivity the skew bench reports, and the only behavior change.
        Approximate mode is the one documented exception: the prefilter
        removes candidates that are provably outside ``radius``, making
        the approximate result set TIGHTER than the uniform grid's (never
        looser).

        Cost shape: leaf ids come from the per-chunk cache (amortized over
        the window overlap), the mask test is one boolean gather per
        window, and the kept batch builds from O(kept) per-segment
        gathers — the overhead stays far under the kernel/batch work it
        eliminates."""
        if mask_cache is None:
            return None
        from spatialflink_tpu.streams.bulk import LazyRecords
        from spatialflink_tpu.utils.metrics import REGISTRY

        ag = self.conf.adaptive_grid
        mask = mask_cache.get()
        segs = (records._segs if isinstance(records, LazyRecords)
                else None)
        if segs is not None and all(isinstance(s, tuple) for s in segs):
            # columnar window: per-seg leaf gathers + O(kept) batch build
            total = 0
            keep_pos: List[np.ndarray] = []
            xs, ys, tss, oids, cells = [], [], [], [], []
            for (chunk, idx), off in zip(segs, records._offsets):
                leaf = self._chunk_leaves(chunk, ag)[idx]
                # one gather + one AND (invalid leaves read slot 0, gated)
                k = mask[np.where(leaf >= 0, leaf, 0)] & (leaf >= 0)
                total += int(idx.size)
                kp = np.nonzero(k)[0]
                if kp.size:
                    keep_pos.append(off + kp)
                    sel = idx[kp]
                    p = chunk.parsed
                    xs.append(p.x[sel])
                    ys.append(p.y[sel])
                    tss.append(p.ts[sel])
                    oids.append(p.obj_id[sel])
                    cells.append(chunk.cells[sel])
            REGISTRY.counter("prefilter-records").inc(total)
            if not keep_pos:
                REGISTRY.counter("prefilter-windows-skipped").inc()
                return np.empty(0, np.int64), None
            idx = np.concatenate(keep_pos)
            REGISTRY.counter("prefilter-kept").inc(int(idx.size))
            batch = PointBatch.from_arrays(
                np.concatenate(xs), np.concatenate(ys),
                obj_id=np.concatenate(oids), ts=np.concatenate(tss),
                ts_base=ts_base, cell=np.concatenate(cells))
            return idx, batch
        # generic fallback (plain record lists / mixed streams)
        x, y, ts, oid, cell = self._record_arrays(records)
        leaf = ag.assign_leaf(x, y)
        keep = np.zeros(leaf.shape, bool)
        v = leaf >= 0
        keep[v] = mask[leaf[v]]
        idx = np.nonzero(keep)[0]
        REGISTRY.counter("prefilter-records").inc(int(leaf.size))
        REGISTRY.counter("prefilter-kept").inc(int(idx.size))
        if idx.size == 0:
            REGISTRY.counter("prefilter-windows-skipped").inc()
            return idx, None
        batch = PointBatch.from_arrays(
            x[idx], y[idx],
            obj_id=None if oid is None else oid[idx],
            ts=ts[idx], ts_base=ts_base, cell=cell[idx])
        return idx, batch

    def _defer_mask_select_at(self, mask, records: List, keep_idx,
                              stats=None) -> Deferred:
        """:meth:`_defer_mask_select` for a PREFILTERED batch: kernel mask
        positions map back to original records through ``keep_idx``."""
        take = getattr(records, "take", None)

        def rows(m):
            sel = np.nonzero(np.asarray(m))[0]
            sel = sel[sel < keep_idx.size]
            orig = keep_idx[sel]
            if take is not None:
                return take(orig)
            return [records[int(i)] for i in orig]

        return self._defer_with_stats(mask, stats, rows)

    # ------------------------------------------------------------------ #

    def _point_batch(self, records, ts_base: int) -> PointBatch:
        from spatialflink_tpu.streams.bulk import LazyRecords

        if isinstance(records, LazyRecords):
            # batched record path: the window's device batch builds straight
            # from the decoded SoA slices (cells assigned once per chunk, obj
            # ids in the stream's decode-interner space — kNN resolution and
            # pane tie-breaking read through `records.interner`)
            return records.point_batch(self.grid, ts_base)
        return PointBatch.from_points(records, self.grid, self.interner, ts_base=ts_base)

    def _windows(self, stream: Iterable[Point]) -> Iterator[Tuple[int, int, List[Point]]]:
        if self.conf.query_type is QueryType.CountBased:
            yield from self._count_windows(stream)
            return
        wa = WindowAssembler(self.conf.window_spec(), self.conf.allowed_lateness_ms)
        self._register_ckpt_windows("windows", wa)
        if not self.columnar_windows:
            stream = iter(stream)  # flatten any chunked decode stream
        # chunk-vectorized assignment (WindowSpec.assign_bulk under the
        # hood): identical window tables, late drops, and emission timing to
        # the per-record add loop, minus its per-record assign/seal cost
        yield from wa.assemble(stream)

    # ------------------------- pane-incremental ----------------------- #

    def _panes_active(self) -> bool:
        """Pane-incremental mode applies: the ``--panes`` switch is on, the
        query runs event-time windows, and the spec is pane-decomposable
        (slide divides size; tumbling bypasses — overlap 1 shares
        nothing)."""
        return (self.conf.panes
                and self.conf.query_type is QueryType.WindowBased
                and self.conf.window_spec().pane_decomposable())

    def _pane_windows(self, stream: Iterable[Point]
                      ) -> Iterator[Tuple[int, int, List]]:
        """Pane-sliced window source: same window set/sealing as
        :meth:`_windows`, but each window's payload is its list of
        ``(pane_start, records)`` panes and every record is buffered ONCE
        (not ``size/slide`` times)."""
        from spatialflink_tpu.runtime.windows import PaneBuffer

        pb = PaneBuffer(self.conf.window_spec(),
                        self.conf.allowed_lateness_ms)
        self._register_ckpt_windows("panes", pb)
        if not self.columnar_windows:
            stream = iter(stream)  # flatten any chunked decode stream
        # chunk-aware: a batched decode stream (driver.decode_stream) hands
        # columnar chunks straight into the pane buffer; plain record
        # streams keep the per-record add loop
        yield from pb.assemble(stream)

    def _pane_eval(self, pane_partial, merge_partials, device_merge=None):
        """The partial-cache evaluator for pane-window payloads: the window
        kernel (``pane_partial(payload, pane_start)`` — the same eval_batch
        the full-window path uses) runs ONCE per sealed pane; windows merge
        their cached partials via ``merge_partials(parts)`` at readback.
        Cache hits/misses ride the ``pane-cache-hits``/``pane-cache-misses``
        registry counters and the merge is a ``pane-merge`` telemetry span,
        so snapshots show both the reuse rate and where the merge time
        goes. Eviction: windows arrive in ascending start order, so once
        window ``s`` dispatches, no later window can need a pane below
        ``s + slide``.

        ``device_merge(parts)`` (optional, gated by
        ``conf.pane_device_merge``) is the family's DEVICE merge: it
        consumes the parts' resident device arrays and returns a
        :class:`Deferred` whose readback is the merged window result —
        partials never individually cross to host. It returns None when
        ineligible (e.g. a checkpoint-restored host-resident partial in the
        window), which falls back to the host merge with identical
        results."""
        from spatialflink_tpu.utils import telemetry as _telemetry
        from spatialflink_tpu.utils.metrics import REGISTRY

        cache = PaneCache(self.conf.slide_ms)
        self._register_ckpt_pane_cache("pane-cache", cache)
        tel = _telemetry.active()
        label = self.telemetry_label or type(self).__name__
        book = tel.traces if tel is not None else None
        costs = tel.costs if tel is not None else None
        want_device = self.conf.pane_device_merge
        if want_device is None:  # auto: device placement off the CPU backend
            import jax

            want_device = jax.default_backend() != "cpu"
        use_device = device_merge is not None and want_device
        rb_bytes = REGISTRY.counter("pane-partial-readback-bytes")

        def eval_batch(panes, ts_base):
            h0, m0 = ((cache.hits.count, cache.misses.count)
                      if costs is not None else (0, 0))

            def seal_pane(p_start, payload):
                # a cache MISS is a pane sealing: the kernel runs once,
                # here — trace it against the window that triggered it
                if book is None:
                    return PanePartial(pane_partial(payload, p_start))
                t0 = time.time()
                part = PanePartial(pane_partial(payload, p_start))
                book.note(label, ts_base, "pane-seal", t0, time.time(),
                          pane=int(p_start), records=len(payload))
                return part

            parts = [
                cache.get(p_start, lambda: seal_pane(p_start, payload))
                for p_start, payload in panes
            ]
            cache.evict_before(ts_base)
            if costs is not None:
                costs.note_pane(label, cache.hits.count - h0,
                                cache.misses.count - m0)

            merged = device_merge(parts) if use_device else None
            if merged is not None:
                # device-resident path: the partials stay in HBM; only the
                # merged window result crosses, counted as the window's
                # readback
                def collect_dev(_):
                    nb = _device_nbytes(merged.device_result)
                    REGISTRY.counter("pane-merged-readbacks").inc()
                    REGISTRY.counter("pane-merged-readback-bytes").inc(nb)
                    if tel is not None:
                        with tel.span("pane-merge", query=label):
                            out = merged.finish()
                        if costs is not None:
                            costs.note_readback(label, nb)
                        return out
                    return merged.finish()

                return Deferred(None, collect_dev)

            def collect(_):
                b0 = rb_bytes.count
                if tel is not None:
                    with tel.span("pane-merge", query=label):
                        out = merge_partials([h.resolve() for h in parts])
                    if costs is not None:
                        costs.note_readback(label, rb_bytes.count - b0)
                    return out
                return merge_partials([h.resolve() for h in parts])

            return Deferred(None, collect)

        return eval_batch

    @staticmethod
    def _pane_concat(parts: List[List]) -> List:
        """Default merge for filter-shaped partials: panes are disjoint, so
        the window's selection is the concatenation (pane-time order)."""
        return [r for part in parts for r in part]

    @staticmethod
    def _pane_count(panes) -> int:
        """records-evaluated metric for a pane-window payload: the window's
        record count, like the full-window paths report."""
        return sum(len(rs) for _, rs in panes)

    def _count_windows(self, stream: Iterable[Point]
                       ) -> Iterator[Tuple[int, int, List[Point]]]:
        """Sliding COUNT windows over the whole stream: every ``slide``
        arrivals, evaluate the last ``size`` records (Flink
        ``countWindow(size, slide)`` semantics on an un-keyed stream). In
        count mode ``window_size_ms``/``slide_ms`` are COUNTS — the
        reference hands the same config values to ``countWindow`` un-scaled
        (the convention tAggregate's per-cell count windows already use).
        Window bounds are the buffered records' min/max event times (count
        windows have no wall-clock extent) — note ``window_end`` is
        therefore INCLUSIVE here, unlike the half-open time windows; see
        :class:`WindowResult`."""
        from collections import deque

        size = max(1, int(self.conf.window_size_ms))
        slide = max(1, int(self.conf.slide_ms))
        buf: deque = deque(maxlen=size)
        n = 0
        for rec in stream:
            buf.append(rec)
            n += 1
            if n % slide == 0:
                records = list(buf)
                yield (min(r.timestamp for r in records),
                       max(r.timestamp for r in records), records)

    def _micro_batches(self, stream: Iterable[Point]) -> Iterator[List[Point]]:
        buf: List[Point] = []
        for rec in stream:
            buf.append(rec)
            if len(buf) >= self.conf.realtime_batch_size:
                yield buf
                buf = []
        if buf:
            yield buf

    def _geom_batch(self, records: List, ts_base: int):
        from spatialflink_tpu.models.batches import EdgeGeomBatch

        pad = None
        if self.distributed:
            # shard-ready capacity: the geometry dim must divide across the
            # mesh (point batches already bucket at >= 256)
            from spatialflink_tpu.utils.padding import bucket_size

            pad = bucket_size(len(records), max(8, self.conf.devices))
        return EdgeGeomBatch.from_objects(records, self.grid, self.interner,
                                          ts_base=ts_base, pad=pad)

    def _maybe_cell_order(self, batch):
        """``--shard-order cell``: pre-permute the batch so whole grid
        cells co-locate per shard (``parallel.mesh.cell_hash_order`` —
        keyBy(gridID) placement parity) and return the inverse permutation
        that restores per-record mask alignment at readback. Returns
        ``(batch, None)`` untouched in arrival order (the default), on
        single-device runs, and for batches without a 1-D cell column."""
        cell = getattr(batch, "cell", None)
        if (not self.distributed or self.conf.shard_order != "cell"
                or cell is None or getattr(cell, "ndim", 0) != 1):
            return batch, None
        from spatialflink_tpu.parallel.mesh import cell_hash_order

        perm = cell_hash_order(np.asarray(cell), self.conf.devices)
        inv = np.empty_like(perm)
        inv[perm] = np.arange(perm.size)
        batch = type(batch)(*(np.asarray(a)[perm] for a in batch))
        return batch, inv

    def _filter_stream(self, batch, mask_stats_fn):
        """(mask, gn_bypassed, dist_evals) for a stream batch: the
        single-device path calls ``mask_stats_fn(batch)`` directly; with
        ``conf.devices`` the batch is sharded and the SAME closure runs per
        shard with psum-merged stats (parallel.ops.distributed_stream_filter)
        — the mesh dispatch every reference pipeline gets from
        ``env.setParallelism(30)`` (``StreamingJob.java:221``). Under
        ``--shard-order cell`` the batch is cell-bucketed before sharding
        and the mask is un-permuted on device at the end."""
        from spatialflink_tpu.parallel.ops import distributed_stream_filter

        batch, inv = self._maybe_cell_order(batch)
        out = self._stream_dispatch(
            batch, mask_stats_fn,
            lambda mesh, sb: distributed_stream_filter(
                mesh, sb, mask_stats_fn))
        if inv is None:
            return out
        import jax.numpy as jnp

        mask, gn_c, evals = out
        return jnp.asarray(mask)[inv], gn_c, evals

    @staticmethod
    def _record_pruning_stats(gn_bypassed, dist_evals) -> None:
        """Pruning-effectiveness counters (the reference's "Distance
        Computation Count", ``spatialObjects/Point.java:220-235``, plus its
        complement): read device scalars and bump the registry."""
        from spatialflink_tpu.utils.metrics import REGISTRY

        REGISTRY.counter("gn-bypassed").inc(int(gn_bypassed))
        REGISTRY.counter("distance-computations").inc(int(dist_evals))

    def _defer_with_stats(self, dev, stats, rows) -> Deferred:
        """Single owner of the stats-payload protocol: ``stats`` is None or a
        (gn_bypassed, dist_evals) device-scalar pair; it rides the Deferred
        payload (no extra host sync — same readback as the main result) and
        bumps the pruning counters at collect time. ``rows(main_result)``
        turns the non-stats part into host rows."""
        def collect(payload):
            if stats is not None:
                main, gn, evals = payload
                self._record_pruning_stats(gn, evals)
            else:
                main = payload
            return rows(main)
        return Deferred((dev, *stats) if stats is not None else dev, collect)

    def _defer_mask_select(self, mask, records: List, stats=None) -> Deferred:
        """Deferred selection of ``records`` by a device boolean mask
        (columnar windows gather their selection in one vectorized
        ``LazyRecords.take``)."""
        take = getattr(records, "take", None)

        def rows(m):
            idx = np.nonzero(np.asarray(m))[0]
            idx = idx[idx < len(records)]
            if take is not None:
                return take(idx)
            return [records[i] for i in idx]
        return self._defer_with_stats(mask, stats, rows)

    def _defer_knn(self, res, interner=None, dist_evals=None) -> Deferred:
        """Deferred (objID, distance) list from a device KnnResult; ids
        resolve through ``interner`` (default: the operator's own — a
        columnar window passes its decode interner). ``dist_evals``
        (device scalar) feeds the distance-computation counter — kNN has
        no GN bypass
        (``knn/PointPointKNNQuery.java:152-183`` computes a distance for
        every candidate-cell point)."""
        interner = interner if interner is not None else self.interner

        def rows(r):
            valid = np.asarray(r.valid)
            oids = np.asarray(r.obj_id)[valid]
            dists = np.asarray(r.dist)[valid]
            return [(interner.lookup(int(o)), float(d))
                    for o, d in zip(oids, dists)]
        stats = None if dist_evals is None else (0, dist_evals)
        return self._defer_with_stats(res, stats, rows)

    @staticmethod
    def _query_point_arrays(query_points):
        """(qx, qy, qc) device-ready arrays from a query-point batch."""
        qx = np.asarray([q.x for q in query_points], np.float32)
        qy = np.asarray([q.y for q in query_points], np.float32)
        qc = np.asarray([q.cell for q in query_points], np.int32)
        return qx, qy, qc

    def _defer_knn_multi(self, res, dist_evals, interner=None) -> Deferred:
        """Deferred per-query (objID, distance) lists from a (Q, k)
        KnnResult; ``dist_evals`` (device scalar, summed over the Q
        queries) feeds the distance-computation counter like every other
        kNN path. A columnar window passes its decode ``interner``."""
        interner = interner if interner is not None else self.interner

        def rows(r):
            valid = np.asarray(r.valid)
            oids = np.asarray(r.obj_id)
            dists = np.asarray(r.dist)
            return [
                [(interner.lookup(int(o)), float(d))
                 for o, d in zip(oids[q][valid[q]], dists[q][valid[q]])]
                for q in range(valid.shape[0])
            ]

        return self._defer_with_stats(res, (0, dist_evals), rows)

    def _stream_dispatch(self, batch, local_fn, dist_entry):
        """SINGLE owner of the whole-batch-vs-mesh dispatch shape shared by
        every stream evaluation (filter/kNN, single- and multi-query):
        ``local_fn(batch)`` runs the single-device kernels; on a mesh,
        ``dist_entry(mesh, sharded_batch)`` runs the distributed twin with
        elastic degraded retry. One place to change the contract."""
        if self.distributed:
            return self._eval_degradable(
                lambda: local_fn(batch), dist_entry, batch)
        return local_fn(batch)

    def _multi_filter_stream(self, batch, multi_mask_stats):
        """(masks (Q, N), gn (Q,), evals (Q,)) for one batch — the same
        closure whole-batch or per shard with psum-merged per-query counters
        (parallel.ops.distributed_stream_filter_multi). ``--shard-order
        cell`` permutes/un-permutes around the dispatch like
        :meth:`_filter_stream` (the mask's record axis is the last)."""
        from spatialflink_tpu.parallel.ops import (
            distributed_stream_filter_multi,
        )

        batch, inv = self._maybe_cell_order(batch)
        out = self._stream_dispatch(
            batch, multi_mask_stats,
            lambda mesh, sb: distributed_stream_filter_multi(
                mesh, sb, multi_mask_stats))
        if inv is None:
            return out
        import jax.numpy as jnp

        masks, gn_c, evals = out
        return jnp.asarray(masks)[:, inv], gn_c, evals

    def _knn_multi_result(self, batch, local_fn, k: int):
        """(KnnResult (Q, k), evals (Q,)) for one batch — whole-batch, or
        per-shard partials merged per query
        (parallel.ops.distributed_stream_knn_multi)."""
        from spatialflink_tpu.parallel.ops import distributed_stream_knn_multi

        return self._stream_dispatch(
            batch, local_fn,
            lambda mesh, sb: distributed_stream_knn_multi(
                mesh, sb, local_fn, k=k))

    @staticmethod
    def _pane_concat_multi(n_queries: int):
        """Per-query concat merge for multi-query filter partials (each
        partial is a list of Q per-query lists)."""
        def merge(parts):
            return [[r for part in parts for r in part[q]]
                    for q in range(n_queries)]
        return merge

    def _run_multi_filter(self, stream: Iterable, n_queries: int,
                          multi_mask_stats, batch_builder,
                          leaf_mask_builder=None
                          ) -> Iterator["WindowResult"]:
        """Shared run_multi driver for FILTER-shaped operators (range):
        ``multi_mask_stats(batch) -> (masks (Q, N), gn_c (Q,), evals (Q,))``;
        records become Q per-query record lists, pruning counters aggregate
        across the query batch. With ``conf.devices`` the batch is sharded
        and the same closure runs per shard.

        ``leaf_mask_builder`` (adaptive grid only) builds the UNION of the
        Q queries' GN∪CN leaf masks: a record outside every query's
        candidate set cannot appear in any per-query result, so the
        prefilter shrinks the Q×N kernel to Q×kept — on a skewed stream
        this is where the adaptive win is largest, because the whole
        standing-query fleet shares one batch residency."""
        import jax.numpy as jnp

        mask_cache = (self._leaf_mask_cache(leaf_mask_builder)
                      if leaf_mask_builder is not None else None)
        empty = [[] for _ in range(n_queries)]

        def eval_batch(records, ts_base):
            if not records:
                return [list(e) for e in empty]
            pre = self._prefilter(records, mask_cache, ts_base)
            if pre is not None:
                keep, batch = pre
                if batch is None:
                    return [list(e) for e in empty]
            else:
                keep, batch = None, batch_builder(records, ts_base)
            masks, gn_c, evals = self._multi_filter_stream(
                batch, multi_mask_stats)
            take = getattr(records, "take", None)
            limit = keep.size if keep is not None else len(records)

            def rows(m):
                m = np.asarray(m)  # ONE (Q, N) device->host transfer
                out = []
                for q in range(n_queries):
                    idx = np.nonzero(m[q])[0]
                    idx = idx[idx < limit]
                    if keep is not None:
                        idx = keep[idx]
                    out.append(take(idx) if take is not None
                               else [records[int(i)] for i in idx])
                return out

            return self._defer_with_stats(
                masks, (jnp.sum(gn_c), jnp.sum(evals)), rows)

        for result in self._multi_results(
                stream, eval_batch,
                pane_merge=self._pane_concat_multi(n_queries)):
            result.extras["queries"] = n_queries
            yield result

    def _run_dynamic_filter(self, stream: Iterable, registry, radius: float,
                            multi_mask_builder, batch_builder,
                            leaf_union_builder=None
                            ) -> Iterator["WindowResult"]:
        """Dynamic standing-query driver for FILTER-shaped operators
        (range): the Q-axis fleet comes from a live
        :class:`~spatialflink_tpu.runtime.queryplane.QueryRegistry`
        instead of a frozen query list. Per window:

        1. ``registry.apply()`` lands any staged admissions/updates/
           retirements (and drains the control topic) — windows are the
           fleet-change granularity, so a window is never evaluated
           against a half-applied fleet and checkpoint barriers (also
           between windows) always snapshot a consistent one;
        2. on a ``fleet_version`` bump the padded query arrays, the gated
           multi-mask closure, and the union leaf-mask cache are rebuilt
           (the same invalidation contract grid-version bumps drive);
           within a size bucket the rebuild REPADS to identical shapes,
           so the jitted kernels are cache hits — zero XLA recompiles;
        3. the (B, N) kernel masks and per-query pruning counters are
           ANDed/scaled with the (B,) valid-slot gate, forcing padded
           slots empty, and only the LIVE slots demultiplex into the
           result — each window carries ``extras['query_ids']`` naming
           its fleet at dispatch time.

        Pane mode deliberately does not engage here: pane partials are
        fleet-shaped, and reusing a partial across a fleet change would
        serve stale queries — full-window evaluation keeps admissions
        exact."""
        import jax.numpy as jnp

        from spatialflink_tpu.utils import telemetry as _telemetry

        label = self.telemetry_label or type(self).__name__
        state: dict = {"v": -1, "entries": [], "live": 0, "fn": None,
                       "mask_cache": None}

        def ensure() -> None:
            if state["v"] == registry.fleet_version:
                return
            entries, qpts, valid = registry.padded_fleet(self.grid)
            fn = mask_cache = None
            if entries:
                base_fn = multi_mask_builder(qpts, radius)
                jvalid = jnp.asarray(valid)

                def fn(b, _base=base_fn, _v=jvalid):
                    masks, gn_c, evals = _base(b)
                    # padded slots forced empty: masks AND the valid gate,
                    # pruning counters scaled by it (a pad slot must not
                    # inflate gn-bypassed/distance-computations)
                    return masks & _v[:, None], gn_c * _v, evals * _v

                if leaf_union_builder is not None:
                    live_pts = qpts[:len(entries)]
                    mask_cache = self._leaf_mask_cache(
                        lambda: leaf_union_builder(live_pts))
            state.update(v=registry.fleet_version, entries=entries,
                         live=len(entries), fn=fn, mask_cache=mask_cache)

        window_ids: dict = {}

        def eval_batch(records, ts_base):
            registry.apply()
            ensure()
            live = state["live"]
            window_ids[ts_base] = [e.id for e in state["entries"]]
            if not live:
                return []
            if not records:
                return [[] for _ in range(live)]
            keep = None
            pre = self._prefilter(records, state["mask_cache"], ts_base)
            if pre is not None:
                keep, batch = pre
                if batch is None:
                    return [[] for _ in range(live)]
            else:
                batch = batch_builder(records, ts_base)
            masks, gn_c, evals = self._multi_filter_stream(batch, state["fn"])
            take = getattr(records, "take", None)
            limit = keep.size if keep is not None else len(records)
            tel = _telemetry.active()
            acct = tel.tenants if tel is not None else None
            # (id, tenant) per live slot, captured NOW: a later apply()
            # may repad before the deferred demux runs
            slots = ([(e.id, e.spec.tenant) for e in state["entries"]]
                     if acct is not None else None)

            def rows(m):
                m = np.asarray(m)  # ONE (B, N) device->host transfer
                if acct is not None:
                    # resolve the parked dispatch span across the live
                    # slots proportional to mask-true candidate work —
                    # padded slots (rows >= live) and padded record
                    # columns (>= limit) never weigh in; host-side sums
                    # on the already-transferred masks, no device ops
                    weights = m[:live, :limit].sum(axis=1)
                    acct.resolve(label, ts_base, [
                        (qid, tenant, int(c))
                        for (qid, tenant), c in zip(slots, weights)])
                out = []
                for q in range(live):
                    idx = np.nonzero(m[q])[0]
                    idx = idx[idx < limit]
                    if keep is not None:
                        idx = keep[idx]
                    out.append(take(idx) if take is not None
                               else [records[int(i)] for i in idx])
                return out

            return self._defer_with_stats(
                masks, (jnp.sum(gn_c), jnp.sum(evals)), rows)

        for result in self._drive(stream, eval_batch):
            ids = window_ids.pop(result.window_start, [])
            result.extras["query_ids"] = ids
            result.extras["queries"] = len(ids)
            yield result

    def _multi_results(self, stream: Iterable, eval_batch, *, pane_merge=None,
                       pane_device_merge=None) -> Iterator["WindowResult"]:
        """_drive for multi-query evaluators, whose per-window result is a
        list of Q per-query lists — always truthy, so _drive_batched's
        realtime no-empty-emission gate cannot see an all-empty micro-batch;
        re-apply it on the per-query contents (the reference's
        fire-per-element trigger never emits empties)."""
        realtime = self.conf.query_type is QueryType.RealTime
        for result in self._drive(stream, eval_batch, pane_merge=pane_merge,
                                  pane_device_merge=pane_device_merge):
            if realtime and not any(result.records):
                continue
            yield result

    def _knn_strategy(self) -> str:
        """Top-k selection strategy: approximate mode rides the TPU
        partial-reduce fast path (``lax.approx_min_k``), exact mode
        auto-selects.

        Documented deviation from the reference: its approximate kNN only
        substitutes cheaper bbox distances and still runs an *exact* top-k
        (``knn/PointPolygonKNNQuery.java:124-139``), so every true neighbor
        appears, just possibly mis-ranked. Here approximate mode trades
        *recall* instead (``approx_min_k`` recall < 1 — some true neighbors
        may be dropped entirely) because on TPU the distance computation is
        effectively free next to the selection; the selection itself is the
        cost worth approximating. Set ``approximate=False`` (default) for
        exact results.
        """
        return "approx" if self.conf.approximate else "auto"

    def _drive(self, stream: Iterable, eval_batch, *, pane_merge=None,
               pane_device_merge=None) -> Iterator["WindowResult"]:
        """Shared window/realtime driver.

        eval_batch(records, ts_base) returns either the final record list or
        a :class:`Deferred`; deferred results are pipelined — up to
        ``conf.pipeline_depth`` windows stay in flight on device while the
        host assembles the next batch — and emitted in window order.

        ``pane_merge(parts) -> records`` opts the operator into the
        pane-incremental mode (``conf.panes``): eval_batch then runs once
        per sealed PANE and each window's result is the merge of its cached
        pane partials. None = family has no mergeable partial; pane mode
        silently falls back to full-window evaluation (identical results).
        """
        realtime = self.conf.query_type is QueryType.RealTime
        if realtime:
            # realtime as a degenerate case of the batched path: tumbling
            # COUNT micro-windows cut by the vectorized MicroBatcher (SoA
            # slices straight off the decode chunks), driven through the
            # same pipelined loop as windowed queries — so realtime
            # inherits the checkpoint barrier, the latency plane, and the
            # chunk governor. Batch boundaries are count-strict in arrival
            # order, so results are identical to the old scalar
            # ``_micro_batches`` path (kept as the trajectory-family
            # helper and the identity oracle in tests/test_control.py).
            from spatialflink_tpu.runtime.windows import MicroBatcher

            mb = MicroBatcher(max(1, self.conf.realtime_batch_size))
            # the open micro-batch checkpoints like a window buffer:
            # records noted past the source position but not yet fired
            # restore from the manifest instead of being lost (the old
            # path relied on decode-chunk/batch-size alignment, which the
            # governor deliberately breaks)
            self._register_ckpt_windows("realtime-batcher", mb)
            if not self.columnar_windows:
                stream = iter(stream)  # flatten any chunked decode stream
            batched = mb.batches(stream)
        elif pane_merge is not None and self._panes_active():
            return self._drive_batched(
                self._pane_windows(stream),
                self._pane_eval(eval_batch, pane_merge,
                                device_merge=pane_device_merge),
                count=self._pane_count)
        else:
            batched = self._windows(stream)
        return self._drive_batched(batched, eval_batch, realtime=realtime)

    def _drive_batched(self, batched: Iterable, eval_batch, *,
                       realtime: bool = False, count=len
                       ) -> Iterator["WindowResult"]:
        """Pipelined evaluation over the (start, end, payload) triples
        _drive assembles (record lists, or pane lists in pane mode).
        ``count(payload)`` feeds the records-evaluated metric."""
        from spatialflink_tpu.utils import telemetry as _telemetry
        from spatialflink_tpu.utils.metrics import REGISTRY, trace

        batches = REGISTRY.counter("batches-evaluated")
        records_c = REGISTRY.counter("records-evaluated")
        depth = max(1, self.conf.pipeline_depth)
        # fast lane: while interactive queries are in the fleet, the chunk
        # governor caps how many deferred windows may queue here (depth is
        # throughput headroom; every queued window is emit latency for the
        # interactive class). Checked per batch — a plain bool read — so
        # the lane engages/disengages live with fleet changes.
        from spatialflink_tpu.runtime.control import active_governor
        gov = active_governor()
        pending: deque = deque()  # (start, end, Deferred)
        # named per-operator trace annotations (≙ the reference's named
        # operators in the Flink web UI, StreamingJob.java:70-72): visible
        # in a jax.profiler capture (--profile / utils.metrics.profile_to),
        # no-ops otherwise. With a telemetry session active they upgrade to
        # stage SPANS (window/dispatch/merge under the family label) which
        # still carry the trace annotation inside, under the same names;
        # checked ONCE here so a disabled run drives the exact
        # pre-telemetry loop.
        tel = _telemetry.active()
        label = self.telemetry_label or type(self).__name__
        book = tel.traces if tel is not None else None
        costs = tel.costs if tel is not None else None
        lat = tel.latency if tel is not None else None
        acct = tel.tenants if tel is not None else None
        if tel is not None:
            backlog = tel.gauge("window-backlog")
            # per-window dispatch→ready overlap: 1 − blocked/round-trip —
            # the fraction of the device round-trip hidden behind host
            # work (pipeline_depth's payoff; ~0 when the drain blocks the
            # whole time, →1 when readback returns instantly)
            overlap_hist = tel.histogram("dispatch-overlap-ratio")
            batched = self._spanned_batches(batched, tel, label)

        def emit(start, end, sel) -> Iterator[WindowResult]:
            # realtime mode only fires on non-empty selections (the
            # reference's fire-per-element trigger never emits empties);
            # windowed mode reports every window, selected-or-not
            if sel or not realtime:
                if book is not None:
                    book.seal(label, start, end)
                yield WindowResult(start, end, sel)

        def note_budget(start, end, meta, m0, m1) -> None:
            # the window's stage-residency budget: consecutive wall-clock
            # intervals from first-record ingest to emission, so the
            # stages SUM to record→emit by construction (the invariant
            # tests assert; ARCHITECTURE.md § Latency decomposition).
            # meta = (first_ingest_ms, t_seal, t_kernel0, t_kernel1);
            # m0/m1 bound the merge (equal for non-deferred results).
            if lat is None:  # every caller gates on tel, but the latency
                return       # contract must hold locally on every path
            fi, li, t_seal, k0, k1 = meta
            t_emit = time.time()
            if fi is not None and fi > t_seal * 1e3:
                # a seal note from a coarser clock (the int-ms ingest
                # stamp) must not yield a negative buffer stage
                fi = None
            lat.window_complete(label, start, end, fi, {
                "buffer": (t_seal * 1e3 - fi) if fi is not None else 0.0,
                "queue": (k0 - t_seal) * 1e3,
                "dispatch": (k1 - k0) * 1e3,
                "inflight": (m0 - k1) * 1e3,
                "merge": (m1 - m0) * 1e3,
                "emit": (t_emit - m1) * 1e3,
            }, t_emit, last_ingest_ms=li)

        def drain(n: int) -> Iterator[WindowResult]:
            while len(pending) > n:
                start, end, dfd, t_disp, meta = pending.popleft()
                if tel is not None:
                    w0 = time.time()
                    with tel.span("merge", query=label, window=start):
                        sel = dfd.finish()
                    w1 = time.time()
                    if book is not None:
                        book.note(label, start, "merge", w0, w1)
                    if costs is not None:
                        costs.attribute_merge(label, w1 - w0)
                    total = w1 - t_disp
                    if total > 0:
                        overlap_hist.record(
                            max(0.0, 1.0 - (w1 - w0) / total))
                    backlog.set(len(pending))
                    if not realtime or sel:
                        note_budget(start, end, meta, w0, w1)
                else:
                    with trace(f"{label}.merge", window=start):
                        sel = dfd.finish()
                yield from emit(start, end, sel)

        coord = self.conf.checkpointer
        for start, end, payload in batched:
            batches.inc()
            records_c.inc(count(payload))
            if tel is not None:
                w0 = time.time()
                # the chain's seal point: the assembler's sweep noted the
                # true seal wall clock for every ready window before the
                # first yielded, so windows pulled later carry their wait
                # behind earlier windows' eval/drain as "queue"; paths
                # without a sweeping assembler fall back to the pull time
                # (queue honestly 0)
                t_seal = lat.pop_seal(start, w0)
                fi = self._first_ingest_ms(payload)
                li = self._last_ingest_ms(payload) if fi is not None \
                    else None
                # host batch build, transfer and async launch: the kernel's
                # own time is on the device, read from the profiler trace
                with tel.span("dispatch", query=label, window=start):
                    sel = eval_batch(payload, start)
                w1 = time.time()
                if book is not None:
                    book.note(label, start, "kernel", w0, w1)
                if costs is not None:
                    nb = self._payload_nbytes(payload)
                    costs.attribute_kernel(
                        label, w1 - w0, records=count(payload), nbytes=nb)
                    # park the measured span on the tenant ledger; the
                    # dynamic demux (rows()) resolves it across the live
                    # slots, static paths age into the default tenant
                    acct.note_dispatch(label, start, w1 - w0,
                                       count(payload), nb)
                meta = (fi, li, min(t_seal, w0), w0, w1)
            else:
                meta = None
                with trace(f"{label}.dispatch", window=start):
                    sel = eval_batch(payload, start)
            if isinstance(sel, Deferred):
                if tel is not None:
                    pending.append((start, end, sel, w1, meta))
                    lat.note_dispatch(start, w1)
                    backlog.set(len(pending))
                else:
                    pending.append((start, end, sel, 0.0, None))
                eff = depth if gov is None else gov.drain_depth(depth)
                yield from drain(eff - 1)
            else:
                yield from drain(0)  # keep window order
                if tel is not None and (sel or not realtime):
                    note_budget(start, end, meta, w1, w1)
                yield from emit(start, end, sel)
            if coord is not None:
                # coordinated-checkpoint barrier: when a checkpoint is due,
                # drain every in-flight window first (each drained yield
                # returns only after the consumer sank it), so the manifest
                # never captures an assembler missing a sealed-but-unsunk
                # window's records. Off the critical path otherwise — one
                # int compare per batch.
                coord.note_batch()
                if coord.due():
                    yield from drain(0)
                    coord.commit()
        yield from drain(0)

    @classmethod
    def _spanned_batches(cls, batched: Iterable, tel, label: str) -> Iterator:
        """Wrap a (start, end, payload) source so each pull is timed as the
        ``window`` stage (assembly/buffering time — the host-side half the
        kernel spans don't see). The span is class-based, so the final
        StopIteration passes through it without being miscounted. With
        tracing on, each pull also opens the window's trace record: the
        assembly slice plus the first record's ingest wall clock."""
        it = iter(batched)
        book = tel.traces
        while True:
            try:
                t0 = time.time()
                with tel.span("window", query=label):
                    item = next(it)
            except StopIteration:
                return
            if book is not None:
                book.note(label, item[0], "window", t0, time.time())
                ing = cls._first_ingest_ms(item[2])
                if ing is not None:
                    book.first_record(label, item[0], ing)
            yield item

    @staticmethod
    def _first_ingest_ms(payload):
        """Best-effort first-record ingest wall clock for trace lineage:
        record lists carry Points with an ``ingestion_time`` stamped at
        parse; pane payloads hold ``(pane_start, records)`` pairs."""
        return SpatialOperator._ingest_ms(payload, -1)

    @staticmethod
    def _last_ingest_ms(payload):
        """The LAST record's ingest stamp — with the first-record stamp it
        bounds the window's buffer-residency spread (a window whose first
        record waited 9 s and whose last waited 10 ms is normal sliding-
        window fill; both old means the pipeline sat on a sealed-ready
        window)."""
        return SpatialOperator._ingest_ms(payload, +1)

    @staticmethod
    def _ingest_ms(payload, end: int):
        """Shared first/last ingest-stamp reader (``end`` = -1 first,
        +1 last); one record materializes per call, never the window."""
        from spatialflink_tpu.streams.bulk import LazyRecords

        try:
            recs = payload
            pos = 0 if end < 0 else -1
            if isinstance(recs, LazyRecords):
                # columnar window: materialize ONE record (its
                # ingestion_time is the chunk's decode stamp)
                return int(recs[pos].ingestion_time) if len(recs) else None
            if not isinstance(recs, list) or not recs:
                return None
            if (isinstance(recs[0], tuple) and len(recs[0]) == 2
                    and isinstance(recs[0][1], (list, LazyRecords))):
                recs = recs[pos][1]  # pane payload: first/last pane
                if not len(recs):
                    return None
            ing = getattr(recs[pos], "ingestion_time", None)
            if isinstance(ing, (int, float)) and ing > 0:
                return int(ing)
        except Exception:
            pass
        return None

    @staticmethod
    def _payload_nbytes(payload) -> int:
        """Approximate host->device bytes for one window payload: a flat
        32-bytes-per-record estimate (x/y/ts/id as packed fields) — a
        cost-profile ESTIMATE of data motion, not a transfer
        measurement."""
        from spatialflink_tpu.streams.bulk import LazyRecords

        try:
            if isinstance(payload, LazyRecords):
                return 32 * len(payload)
            if isinstance(payload, list):
                if (payload and isinstance(payload[0], tuple)
                        and len(payload[0]) == 2
                        and isinstance(payload[0][1], (list, LazyRecords))):
                    # pane payload
                    return 32 * sum(len(rs) for _, rs in payload)
                return 32 * len(payload)
        except Exception:
            pass
        return 0


class GeomQueryMixin:
    """Query-side precomputation shared by all operators: dense GN/CN/NB cell
    masks (union over the query geometry's cells — ``UniformGrid.java:193-222``)
    and padded query edge arrays."""

    def _query_cells(self, query) -> list:
        if isinstance(query, Point):
            return [query.cell] if query.cell >= 0 else []
        return sorted(query.cells)

    def _query_masks(self, query, radius: float):
        import jax.numpy as jnp

        cells = self._query_cells(query)
        gn = self.grid.guaranteed_cells_mask(radius, cells)
        cn = self.grid.candidate_cells_mask(radius, cells, gn)
        nb = self.grid.neighboring_cells_mask(radius, cells)
        return jnp.asarray(gn), jnp.asarray(cn), jnp.asarray(nb)

    def _query_nb(self, query, radius: float):
        """Dense neighboring-cells (GN ∪ CN) mask for a query geometry —
        radius 0 selects all cells (UniformGrid.java:264-266)."""
        import jax.numpy as jnp

        return jnp.asarray(
            self.grid.neighboring_cells_mask(radius, self._query_cells(query))
        )

    def _stack_query_nb(self, queries, radius: float):
        """(Q, n*n) dense neighboring-cells masks, one per query object —
        the multi-query form of :meth:`_query_nb`."""
        return self._stack_query_masks(queries, radius, which=("nb",))[0]

    def _stack_query_masks(self, queries, radius: float,
                           which=("gn", "cn", "nb")):
        """Selected dense-mask stacks, each (Q, n*n), in ``which`` order —
        the multi-query form of :meth:`_query_masks`. Builds straight from
        the grid's host-side masks (no per-query device round-trip) and
        only the masks the caller asked for (cn derives from gn, so
        requesting cn computes gn internally without stacking it)."""
        import jax.numpy as jnp

        rows = {k: [] for k in which}
        for q in queries:
            cells = self._query_cells(q)
            gn = (self.grid.guaranteed_cells_mask(radius, cells)
                  if ("gn" in which or "cn" in which) else None)
            if "gn" in which:
                rows["gn"].append(np.asarray(gn))
            if "cn" in which:
                rows["cn"].append(np.asarray(
                    self.grid.candidate_cells_mask(radius, cells, gn)))
            if "nb" in which:
                rows["nb"].append(np.asarray(
                    self.grid.neighboring_cells_mask(radius, cells)))
        return tuple(jnp.asarray(np.stack(rows[k])) for k in which)

    def _query_geom_batch(self, queries):
        """The Q query geometries as ONE exact-capacity padded edge batch
        (no bucket padding: built once per run_multi, and the G axis must
        match the (Q,) per-query mask stacks)."""
        from spatialflink_tpu.models.batches import EdgeGeomBatch

        return EdgeGeomBatch.from_objects(queries, self.grid,
                                          pad=len(queries))

    def _query_edges(self, query):
        from spatialflink_tpu.models.batches import single_query_edges
        import jax.numpy as jnp

        e, m = single_query_edges(query)
        from spatialflink_tpu.models.objects import Polygon as _P, MultiPolygon as _MP

        areal = isinstance(query, (_P, _MP))
        return jnp.asarray(e), jnp.asarray(m), areal

    def _query_bbox(self, query):
        import jax.numpy as jnp
        import numpy as np

        return jnp.asarray(np.asarray(query.bbox, np.float32))


