"""Physical operator model.

Execution contract (the ``doExecuteColumnar(): RDD[ColumnarBatch]`` analogue,
GpuExec.scala:58): every physical op exposes
``partitions(ctx) -> List[Iterator[batch]]`` — a list of lazily-evaluated
per-partition batch iterators.  TPU execs yield device
:class:`~spark_rapids_tpu.batch.ColumnBatch`; CPU (fallback) execs yield host
:class:`~spark_rapids_tpu.batch.HostBatch`.  The planner inserts
:class:`HostToDeviceExec` / :class:`DeviceToHostExec` transitions at every
CPU<->TPU boundary (GpuTransitionOverrides analogue).
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Callable, Dict, Iterator, List, Optional

import jax

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import (
    ColumnBatch, HostBatch, device_to_host, host_to_device,
)
from spark_rapids_tpu.config import RapidsConf


class Metric:
    """A named SQL-metric (GpuMetricNames analogue, GpuExec.scala:27-56)."""

    def __init__(self, name: str, unit: str = ""):
        self.name = name
        self.unit = unit
        self.value = 0

    def add(self, v):
        self.value += v

    def __repr__(self):
        return f"{self.name}={self.value}{self.unit}"


class ExecContext:
    """Per-query execution context: conf, metrics, device admission."""

    def __init__(self, conf: RapidsConf, semaphore=None, device=None,
                 mesh=None):
        self.conf = conf
        self.semaphore = semaphore
        self.device = device
        # multi-device jax.sharding.Mesh when the ICI collective shuffle is
        # active (spark.rapids.shuffle.ici.enabled + >1 device); exchanges
        # then run lax.all_to_all instead of the single-host split
        self.mesh = mesh
        self.metrics: Dict[str, Dict[str, Metric]] = {}
        # Net outstanding H2D admission acquires for this query.
        # HostToDeviceExec counts every semaphore acquire at acquire time;
        # each per-batch release site decrements; collect_host's finally
        # releases the residue.  Pairing releases to OUTPUT batches alone
        # leaks the difference whenever a plan is not 1:1 (a semi join
        # dropping an empty pair, an n->1 concat on the fallback path),
        # and a leaked permit silently shrinks device admission for the
        # rest of the process.
        self._pipeline_h2d = 0
        # spillable handles whose lifetime is the whole query (shuffle
        # outputs survive partition retries, like the reference's shuffle
        # files); collect_host closes them when the query ends
        self._deferred_handles: List = []
        # (op_id, mechanism) replan decisions the adaptive layer took for
        # this query (plan/adaptive.note_event), checked post-query by
        # analysis/plan_verify.check_adaptive_events: every event must
        # point at a live plan op and respect join-type legality
        self.adaptive_events: List = []
        # this execution's literal values for the shared plan's slots
        # (utils.params.BoundParams; the session executes under them) and
        # the plan facts it publishes as metrics (session.plan_bound)
        self.bound_params = None
        self.plan_facts: Dict[str, int] = {}

    def note_adaptive(self, op_id: str, mechanism: str) -> None:
        self.adaptive_events.append((op_id, mechanism))

    def defer_close(self, handle) -> None:
        self._deferred_handles.append(handle)

    def close_deferred(self) -> None:
        for h in self._deferred_handles:
            h.close()
        self._deferred_handles.clear()

    def metric(self, op_id: str, name: str) -> Metric:
        ops = self.metrics.setdefault(op_id, {})
        if name not in ops:
            ops[name] = Metric(name)
        return ops[name]

    def mesh_spmd_active(self) -> bool:
        """True when whole-stage SPMD fusion may run for this query: a
        multi-device mesh is installed AND mesh.spmd.enabled.  Both the
        stage builder (plan/pipeline) and the fusable ops (shuffle
        exchange, broadcast join) consult this single gate, so a plan
        segment can never half-fuse."""
        if self.mesh is None:
            return False
        from spark_rapids_tpu.config import MESH_SPMD_ENABLED
        return MESH_SPMD_ENABLED.get(self.conf)


def _release_admission(ctx: ExecContext, n: int = 1) -> None:
    """Release ``n`` H2D-paired admission permits and keep the query's
    outstanding-acquire count in step (``ExecContext._pipeline_h2d``)."""
    for _ in range(n):
        ctx.semaphore.release()
    ctx._pipeline_h2d = max(0, getattr(ctx, "_pipeline_h2d", 0) - n)


def prefetch_spillables(handles, depth: int = 1):
    """Drive a list of SpillableBatch handles with overlapped unspill:
    batch i+1's rehydration (disk read + decompress + async H2D enqueue)
    is already in flight while the consumer computes on batch i
    (catalog.prefetch).  The shared drive loop for cached-scan partitions
    and shuffle piece reads.  Admission is NOT acquired here: the calling
    task's semaphore permit is task-wide re-entrant and the catalog's
    reserve() bounds device bytes, so read-ahead adds no leakable depth."""
    handles = list(handles)
    if not handles:
        return iter(())
    return handles[0]._catalog.prefetch(handles, depth=depth)


_OP_SEQ = itertools.count(1)


class PhysicalOp:
    """Base physical operator."""

    is_tpu = False
    #: on a planned tree's root: constant subtrees TpuOverrides.apply folded
    folded_exprs = 0

    def __init__(self, children: List["PhysicalOp"], output_schema: T.Schema):
        self.children = children
        self.output_schema = output_schema
        # unique in the process and no memory address; a planned tree is
        # renumbered by :func:`assign_op_ids`, so that what is keyed on
        # an op_id reads the same in every process that plans the query
        self.op_id = f"{type(self).__name__}@{next(_OP_SEQ)}"

    @property
    def name(self) -> str:
        return type(self).__name__

    def describe(self) -> str:
        return self.name

    def tree_string(self, depth: int = 0) -> str:
        out = "  " * depth + ("*" if self.is_tpu else " ") + \
            self.describe() + "\n"
        for c in self.children:
            out += c.tree_string(depth + 1)
        return out

    def num_partitions(self, ctx: ExecContext) -> int:
        if self.children:
            return self.children[0].num_partitions(ctx)
        return 1

    def partitions(self, ctx: ExecContext) -> List[Iterator]:
        raise NotImplementedError(self.name)


class TpuExec(PhysicalOp):
    """Operator executing on device over ColumnBatch partitions."""

    is_tpu = True

    def pipeline_inline(self, ctx: "ExecContext", build):
        """Whole-pipeline hook (plan/pipeline.py): return
        f(args) -> List[ColumnBatch] composing this op into one jitted
        program (``build(child)`` composes a child), or None to act as a
        pipeline source fed through the iterator path."""
        return None


class CpuExec(PhysicalOp):
    """Host fallback operator over HostBatch partitions."""

    is_tpu = False


class _ReadAheadChannel:
    """Bounded staging channel for the read-ahead worker: put/get wait on a
    condition variable AND wake immediately on :meth:`stop` — the
    queue.Full poll loop this replaced re-armed a 0.25 s timeout on every
    back-pressure wait, so worker shutdown and a full queue both paid a
    polling tail latency.

    ``put`` returns False once stopped (the consumer has left: the item is
    dropped, never stranded).  ``get`` returns the sentinel ``None`` when
    stopped-and-drained.

    Waits are BOUNDED (re-armed in a loop): ``notify`` still wakes them
    immediately — the bound never adds latency — but it caps how long
    the blocked thread sits inside one C-level wait, so an async
    exception (the fault watchdog's PartitionTimeout, delivered only
    between Python bytecodes) reaches a consumer parked here within the
    bound instead of after the producer's entire stall.
    """

    _WAIT_SLICE = 0.25

    def __init__(self, depth: int):
        self._items = collections.deque()
        self._depth = max(1, depth)
        self._cond = threading.Condition()
        self._stopped = False

    @property
    def stopped(self) -> bool:
        return self._stopped

    def put(self, item) -> bool:
        with self._cond:
            while not self._stopped and len(self._items) >= self._depth:
                self._cond.wait(self._WAIT_SLICE)
            if self._stopped:
                return False
            self._items.append(item)
            self._cond.notify_all()
            return True

    def get(self):
        with self._cond:
            while not self._stopped and not self._items:
                self._cond.wait(self._WAIT_SLICE)
            if self._items:
                item = self._items.popleft()
                self._cond.notify_all()
                return item
            return None

    def stop(self) -> None:
        """Drain + wake everyone: blocked producers return False from
        ``put`` immediately instead of after a poll interval."""
        with self._cond:
            self._stopped = True
            self._items.clear()
            self._cond.notify_all()


class HostToDeviceExec(TpuExec):
    """Stage host batches into HBM (GpuRowToColumnarExec /
    HostColumnarToGpu analogue: acquire semaphore, bulk-copy to device)."""

    def __init__(self, child: PhysicalOp):
        super().__init__([child], child.output_schema)
        # Device-consumer handshake: a scan that can emit dictionary-encoded
        # string columns only does so when its batches are headed for H2D
        # staging (codes transfer instead of bytes); CPU-exec consumers
        # always get fully decoded host strings.
        probe = getattr(child, "set_device_consumer", None)
        if probe is not None:
            probe()

    def describe(self):
        return "HostToDevice"

    def partitions(self, ctx: ExecContext) -> List[Iterator]:
        from spark_rapids_tpu.config import STAGE_READAHEAD_BATCHES
        child_parts = self.children[0].partitions(ctx)
        t_metric = ctx.metric(self.op_id, "stageTime")
        depth = STAGE_READAHEAD_BATCHES.get(ctx.conf)

        def acquire_counted():
            # pipeline_collect counts H2D-side acquires via
            # ctx._pipeline_h2d and releases that many in its finally —
            # counting AT ACQUIRE TIME (not per materialized source)
            # keeps the books right when an abort (PartitionTimeout,
            # device loss) lands mid-source
            if ctx.semaphore is not None:
                ctx.semaphore.acquire()
                if hasattr(ctx, "_pipeline_h2d"):
                    ctx._pipeline_h2d += 1

        def stage(hb, catalog):
            from spark_rapids_tpu.mem.catalog import run_with_oom_retry
            t0 = time.monotonic()
            acquire_counted()
            db = run_with_oom_retry(
                catalog, lambda: host_to_device(hb, device=ctx.device))
            t_metric.add(time.monotonic() - t0)
            return db

        def gen(part):
            from spark_rapids_tpu.runtime.device import DeviceRuntime
            catalog = DeviceRuntime.get(ctx.conf).catalog
            for hb in part:
                yield stage(hb, catalog)

        def stage_nosem(hb, catalog):
            # worker-thread variant: NO semaphore acquire here.  Admission
            # is taken by the CONSUMER below before the batch is yielded
            # downstream, pairing with the release when results leave the
            # device (TpuSemaphore depth is task-wide, so the thread the
            # acquire/release lands on no longer matters); the read-ahead
            # transfer itself rides the catalog's OOM-retry.
            from spark_rapids_tpu.mem.catalog import run_with_oom_retry
            t0 = time.monotonic()
            db = run_with_oom_retry(
                catalog, lambda: host_to_device(hb, device=ctx.device))
            t_metric.add(time.monotonic() - t0)
            return db

        def gen_pipelined(part):
            # Read-ahead staging: a background thread pulls host batches
            # (driving the scan's decode) and stages them into HBM up to
            # ``depth`` ahead, so decode + H2D transfer overlap the
            # consumer's device compute — the reference's read-ahead pool
            # + semaphore shape (GpuParquetScan.scala:647-700) without a
            # dedicated stream: jax dispatch is async, the thread only
            # pays the host-side copy/transfer-enqueue cost.  Producer
            # back-pressure and shutdown ride the channel's condition
            # variable, so neither pays a poll interval.
            from spark_rapids_tpu.obs import events as obs_events
            from spark_rapids_tpu.runtime.device import DeviceRuntime
            catalog = DeviceRuntime.get(ctx.conf).catalog
            chan = _ReadAheadChannel(depth)
            DONE = object()
            # adopt the spawning query's scope on the worker so its
            # transfers/events attribute to THIS query even when several
            # queries are in flight (serve runtime)
            scope = obs_events.current_scope()
            from spark_rapids_tpu.utils import params
            bound = params.current()

            def worker():
                try:
                    with obs_events.adopt(scope), params.executing(bound):
                        for hb in part:
                            if chan.stopped:
                                return
                            if not chan.put(("b", stage_nosem(hb, catalog))):
                                return
                        chan.put((DONE, None))
                except BaseException as e:  # surfaced on the consumer side
                    chan.put(("e", e))

            t = threading.Thread(target=worker, daemon=True,
                                 name="stage-readahead")
            t.start()
            try:
                while True:
                    item = chan.get()
                    if item is None or item[0] is DONE:
                        return
                    kind, v = item
                    if kind == "e":
                        raise v
                    # device admission on the CONSUMER (main) thread —
                    # re-entrant there, and paired with DeviceToHostExec's
                    # release on the same thread
                    acquire_counted()
                    yield v
            finally:
                # Wake + reap the worker (bounded): stop() drains the
                # channel and releases any blocked put immediately; a
                # worker wedged inside a device transfer would otherwise
                # outlive the query and leak its generator state into the
                # next test/query — the cross-suite-state-leak shape.
                chan.stop()
                t.join(timeout=5.0)

        mk = gen_pipelined if depth > 0 else gen
        return [mk(p) for p in child_parts]


class DeviceToHostExec(CpuExec):
    """Copy device batches back to host (GpuColumnarToRowExec /
    GpuBringBackToHost analogue)."""

    def __init__(self, child: PhysicalOp):
        super().__init__([child], child.output_schema)

    def describe(self):
        return "DeviceToHost"

    def partitions(self, ctx: ExecContext) -> List[Iterator]:
        child_parts = self.children[0].partitions(ctx)

        def gen(part):
            from spark_rapids_tpu.ops.tpu_exec import shrink_to_fit
            for db in part:
                # Shrink to the live-row bucket first (one scalar round
                # trip + a device-side gather) so the bulk transfer moves
                # live rows, not padded capacity.
                hb = device_to_host(shrink_to_fit(db))
                if any(c.dictionary is not None for c in hb.columns):
                    # encoded-corridor invariant (analysis/plan_verify):
                    # collection D2H must materialize dictionary columns
                    ctx.encoded_d2h_leaks = \
                        getattr(ctx, "encoded_d2h_leaks", 0) + 1
                if ctx.semaphore is not None:
                    _release_admission(ctx)
                if hb.num_rows:
                    yield hb

        return [gen(p) for p in child_parts]


def run_partition_with_retry(root: PhysicalOp, ctx: ExecContext,
                             index: int, error=None) -> List:
    """Materialize one partition with retries (Spark task-retry analogue —
    SURVEY.md section 5: failure detection is delegated to task retry +
    lineage; partitions are pure recomputations of their lineage here too).

    Thin wrapper: the loop itself lives in fault.recovery, which
    classifies the failure (fault.errors), applies the unified
    RetryPolicy (spill on OOM, runtime reset + device-tier invalidation
    on device loss) and, once device attempts are exhausted, completes
    just this partition through the CPU operator path
    (``spark.rapids.sql.tpu.fallback.onDeviceError``).  ``error`` is the
    failure that already consumed attempt 1.
    """
    from spark_rapids_tpu.fault import recovery
    return recovery.run_partition_with_retry(root, ctx, index, error=error)


def _drive_partitions(root: PhysicalOp, ctx: ExecContext,
                      release_partial: bool) -> List:
    """Drive every partition of ``root`` (trace range, MemoryError
    pass-through, per-partition deadline + retry, collect/batches
    metric) into one flat batch list — shared by the bulk and iterator
    collect paths.

    ``release_partial=True`` (bulk path, where the semaphore release for
    a batch happens only after the final D2H): a partition attempt that
    fails after yielding k batches must release those k H2D-side acquires
    before the retry re-acquires for its own batches, or the depth leaks
    for the process lifetime.  The iterator path releases incrementally
    per converted batch (DeviceToHostExec), so it must NOT double-release
    here.
    """
    from spark_rapids_tpu.fault.watchdog import partition_deadline
    from spark_rapids_tpu.utils.tracing import trace_range
    with partition_deadline(ctx.conf, "plan-partitions"):
        # eager per-op work (e.g. the exchange split) happens here, under
        # its own deadline — a wedge before the first partition must
        # trip the watchdog too
        parts = root.partitions(ctx)
    flat: List = []
    for i, part in enumerate(parts):
        got: List = []
        try:
            with trace_range("partition", str(i)), \
                    partition_deadline(ctx.conf, f"partition:{i}"):
                for b in part:
                    got.append(b)
        except BaseException as e:
            if release_partial and ctx.semaphore is not None:
                _release_admission(ctx, len(got))
            if isinstance(e, MemoryError) or \
                    not isinstance(e, Exception):
                # MemoryError passes to the caller's handler;
                # KeyboardInterrupt/SystemExit must never be swallowed
                # by a successful retry
                raise
            got = run_partition_with_retry(root, ctx, i, error=e)
        flat.extend(got)
        ctx.metric("collect", "batches").add(len(got))
    return flat


def _collect_device_bulk(root: PhysicalOp, ctx: ExecContext
                         ) -> List[HostBatch]:
    """Async-overlapped collect of a TPU root: EVERY partition's device
    work is dispatched first (jax dispatch is async — the device pipelines
    across partitions instead of idling at each partition's D2H), then one
    batched sizes sync right-sizes all batches and ONE bulk transfer
    brings them home (the DeviceToHostExec iterator paid a sizes sync + a
    blocking copy per batch, serializing dispatch behind each round trip).
    """
    from spark_rapids_tpu.batch import device_to_host_many, host_sizes
    from spark_rapids_tpu.ops.tpu_exec import shrink_to_fit
    flat = _drive_partitions(root, ctx, release_partial=True)
    try:
        if not flat:
            return []
        # A partition completed via the CPU fallback path yields
        # HostBatch directly: pass those through in place and run the
        # sizes-sync + bulk D2H over the device batches only.
        out: List = list(flat)
        dev = [(j, b) for j, b in enumerate(flat)
               if isinstance(b, ColumnBatch)]
        if dev:
            dbs = [b for _, b in dev]
            sizes = host_sizes(dbs)
            shrunk = [shrink_to_fit(b, sizes=s)
                      for b, s in zip(dbs, sizes)]
            for (j, _), hb in zip(dev, device_to_host_many(shrunk)):
                out[j] = hb
        return [hb for hb in out if hb.num_rows]
    finally:
        # results left the device (or the sizes/D2H step failed — either
        # way this collect is done with them): release once per collected
        # DEVICE batch, pairing with the H2D-side acquires
        # (DeviceToHostExec's role in the iterator path); CPU-fallback
        # host batches never took device admission
        if ctx.semaphore is not None:
            _release_admission(
                ctx, sum(1 for b in flat if isinstance(b, ColumnBatch)))


def _history_cached_collect(op: PhysicalOp, ctx: ExecContext
                            ) -> Optional[HostBatch]:
    """Serve the whole collect from the cross-query fragment cache
    (history.fragcache) when the session armed a fragment key and the
    cache holds this (fingerprint, conf, input-identity): the cached
    device batches ARE a previous run's outputs, so D2H + concat here
    reproduces that run bit-identically with zero dispatches.  None on
    a miss (caller executes normally)."""
    key = getattr(ctx, "_history_frag_key", None)
    if key is None:
        return None
    from spark_rapids_tpu.history.fragcache import fragment_cache
    devs = fragment_cache().fetch(key, ctx)
    if devs is None:
        return None
    from spark_rapids_tpu.batch import device_to_host_many
    hbs = [hb for hb in device_to_host_many(devs) if hb.num_rows]
    if not hbs:
        return HostBatch(op.output_schema, [
            _empty_host_col(f) for f in op.output_schema.fields])
    return concat_result(hbs)


def assign_op_ids(root: PhysicalOp) -> PhysicalOp:
    """``<Class>#<k>``, ``k`` the operator's pre-order position in the
    planned tree: the same plan gets the same ids in every process (an
    operator made later, at run time, keeps its ``<Class>@<n>``)."""
    seen: set = set()
    stack = [root]
    while stack:
        op = stack.pop()
        if id(op) in seen:
            continue   # a subtree shared by two parents keeps its first id
        op.op_id = f"{type(op).__name__}#{len(seen)}"
        seen.add(id(op))
        stack.extend(reversed(op.children))
    return root


def concat_result(batches: List[HostBatch]) -> HostBatch:
    from spark_rapids_tpu.utils.tracing import span
    with span("result", "concat"):
        return HostBatch.concat(batches)


def collect_host(op: PhysicalOp, ctx: ExecContext) -> HostBatch:
    """Drive a plan to completion and concatenate all partitions on host."""
    from spark_rapids_tpu.utils.tracing import trace_range
    try:
        if op.is_tpu:
            hb = _history_cached_collect(op, ctx)
            if hb is not None:
                return hb
            from spark_rapids_tpu.fault.recovery import (
                run_pipeline_with_recovery,
            )
            with trace_range("collect", "pipeline",
                             ctx.metric("collect", "wallTimeNs")):
                hb = run_pipeline_with_recovery(op, ctx)
            if hb is not None:
                return hb
        t0 = time.monotonic()
        # a device root that inlines nothing (a join at the root) is
        # collected in bulk; a root on the host drives its partitions
        batches: List[HostBatch] = _collect_device_bulk(op, ctx) \
            if op.is_tpu else _drive_partitions(
                op, ctx, release_partial=False)
        ctx.metric("collect", "wallTimeNs").add(
            int((time.monotonic() - t0) * 1e9))
        if not batches:
            return HostBatch(op.output_schema, [
                _empty_host_col(f) for f in op.output_schema.fields
            ])
        return concat_result(batches)
    finally:
        ctx.close_deferred()
        # Give back any staging acquires whose batches never reached a
        # per-batch release (dropped-empty join pairs, n->1 concats):
        # the query is over, so the outstanding count must drain to zero
        # or the permit leaks for the process lifetime.  The plan
        # verifier (analysis/plan_verify.py) asserts the resulting
        # held_depth() == 0 after every suite query.
        if ctx.semaphore is not None:
            _release_admission(ctx, getattr(ctx, "_pipeline_h2d", 0))


def _empty_host_col(f: T.Field):
    import numpy as np
    from spark_rapids_tpu.batch import HostColumn
    vals = np.zeros(0, dtype=object if f.dtype.is_string else f.dtype.np_dtype)
    return HostColumn(f.dtype, vals, np.zeros(0, dtype=np.bool_))
