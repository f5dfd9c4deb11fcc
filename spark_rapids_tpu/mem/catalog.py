"""Spillable-buffer catalog with device -> host -> disk tiers.

Reference analogues: RapidsBufferCatalog.scala:36 (id->buffer registry wiring
the spill chain), RapidsBufferStore.scala:39 (priority-ordered spillable
tracking + synchronousSpill), RapidsDeviceMemoryStore / RapidsHostMemoryStore
/ RapidsDiskStore, SpillableColumnarBatch.scala:27 (operator-facing handle),
SpillPriorities.scala.

TPU adaptation: XLA owns HBM allocation and exposes no alloc-failure callback
(the RMM event-handler hook, DeviceMemoryEventHandler.scala:35).  Instead the
catalog enforces a *budget*: every operator that holds batches across
pipeline breaks registers them as SpillableBatch handles; when registered
device bytes exceed the budget the catalog spills lowest-priority handles to
host numpy, and past the host-store bound to disk — same three tiers, push
model instead of callback model.

Spill engine v2 (asynchronous tiered spill):

* ``reserve()`` picks victims and transitions them DEVICE -> SPILLING under
  the lock, but the D2H copy and any compress+disk write run on a bounded
  background writer pool (``spill.async.enabled`` / ``spill.writer.threads``)
  so the triggering register/get returns immediately.  A ``get()`` racing a
  spill that has not started yet cancels it cheaply (the device copy never
  moved); one racing a started spill joins just that handle's completion.
  ``spill.async.enabled=false`` restores the v1 synchronous semantics: the
  same state machine executed inline, errors surfacing from the triggering
  call.
* Accounting is incremental: per-tier running byte counters updated at every
  transition replace the O(n) re-scan per budget-loop iteration, and a
  handle's host bytes are computed once at spill time (string columns walk
  every value).  ``verify_accounting()`` (analysis/plan_verify.py) asserts
  counters == scan.
* ``prefetch()`` generalizes the shuffle drain's one-piece read-ahead: it
  yields handles' device batches with the next unspill (disk read +
  decompress + async H2D enqueue) already in flight.
* Disk frames are chunked (``spill.chunkBytes``, mem/codec.py) so
  compression overlaps the file write and unspill decompresses before the
  whole file is read.
* Fault interplay: ``spill:*`` injections fire on the writer thread and the
  classified error surfaces at the consumer's next ``get()`` (the handle
  reverts to the device tier, so the recovery ladder's replay succeeds);
  ``unspill:*`` fires on the rehydration path.  ``invalidate_device_tier``
  drains/aborts in-flight spills before rescuing.
"""

from __future__ import annotations

import os
import tempfile
import threading
import time
from collections import deque
from typing import Deque, Dict, Iterator, List, Optional, Sequence

import numpy as np

from spark_rapids_tpu.batch import (
    ColumnBatch, HostBatch, device_to_host, host_batch_bytes, host_to_device,
)
from spark_rapids_tpu.config import (
    RapidsConf, SPILL_ASYNC_ENABLED, SPILL_CHUNK_BYTES, SPILL_WRITER_THREADS,
    conf_bytes,
)
from spark_rapids_tpu.obs import events as obs_events
from spark_rapids_tpu.utils.tracing import span

DEVICE_SPILL_BUDGET = conf_bytes(
    "spark.rapids.memory.tpu.spillBudgetBytes", 8 << 30,
    "Device bytes the catalog lets spillable batches occupy before "
    "spilling lowest-priority ones to host.")

# Spill priority bands (SpillPriorities.scala:17-61).
PRIORITY_INPUT = 0
PRIORITY_SHUFFLE_OUTPUT = -1000
PRIORITY_ON_DECK = 1000
# Cross-query fragment-cache entries (history.fragcache): the MOST
# spillable band — a cached fragment is a speculative reuse bet and must
# yield HBM before any live query's inputs or shuffle outputs.
PRIORITY_FRAGMENT = -2000
# Front-door result-cache entries (serve.resultcache): below even
# fragments — a final result set was already delivered to its client, so
# keeping it resident is the purest reuse bet of all and yields first.
PRIORITY_RESULT = -3000

#: Bounded wait slice (seconds) for every blocking loop in this module:
#: notify still wakes immediately, the bound only caps the C-level block so
#: the fault watchdog's async PartitionTimeout can land (lint rule R3).
_WAIT_SLICE = 0.25


def device_batch_bytes(batch: ColumnBatch) -> int:
    total = 0
    for c in batch.columns:
        total += int(np.dtype(c.data.dtype).itemsize) * int(c.data.size)
        total += int(c.validity.size)
        if c.offsets is not None:
            total += 4 * int(c.offsets.size)
        if c.codes is not None:
            total += 4 * int(c.codes.size)
    return total


def device_batch_shard_bytes(batch: ColumnBatch) -> List[int]:
    """Per-device resident bytes of a MESH-SHARDED batch (every leaf a
    multi-device global array), ordered by device id.  Pure addressable-
    shard metadata — shapes and dtypes, never a transfer or sync — so the
    mesh-SPMD dispatcher can account a fused stage's HBM footprint per
    shard (and obs can report bytes_per_device) without touching the
    arrays.  Sums to :func:`device_batch_bytes` of the global batch for
    the standard int32-offsets/codes layout."""
    per: dict = {}

    def _add(arr) -> None:
        for s in arr.addressable_shards:
            per[s.device] = per.get(s.device, 0) + int(s.data.nbytes)

    for c in batch.columns:
        _add(c.data)
        _add(c.validity)
        if c.offsets is not None:
            _add(c.offsets)
        if c.codes is not None:
            _add(c.codes)
    return [per[d] for d in sorted(per, key=lambda d: d.id)]


class _SpillTask:
    """One in-flight tier move.  ``state`` transitions are guarded by the
    owning catalog's lock (queued -> running -> done, or queued ->
    cancelled); ``_done`` signals completion to joiners with bounded
    waits."""

    __slots__ = ("handle", "bytes", "state", "error", "_done", "scope")

    QUEUED, RUNNING, DONE, CANCELLED = "queued", "running", "done", \
        "cancelled"

    def __init__(self, handle: "SpillableBatch"):
        self.handle = handle
        self.bytes = handle.device_bytes
        self.state = self.QUEUED
        self.error: Optional[BaseException] = None
        self._done = threading.Event()
        # the query whose memory pressure queued this move: the writer
        # thread adopts it so spill events/transfer counters attribute
        # to the right query under concurrent serving
        self.scope = obs_events.current_scope()

    def mark_done(self) -> None:
        self._done.set()

    def wait_done(self) -> None:
        while not self._done.wait(_WAIT_SLICE):
            pass


class SpillableBatch:
    """Operator-facing handle for a batch that may move between tiers.

    Tier state machine (v2)::

        DEVICE --begin spill--> SPILLING --writer D2H--> HOST --> DISK
          ^                        |                      |        |
          |<---cancel (get race)---+      get() unspill --+--------+
          |
        LOST (device loss with no surviving copy; get() raises classified)

    SPILLING covers both directions of the middle hop: a device->host D2H
    on the writer (cancellable while queued) and a host->disk
    compress+write (runs to completion; get() joins it).
    """

    TIER_DEVICE, TIER_HOST, TIER_DISK, TIER_LOST, TIER_SPILLING = \
        0, 1, 2, 3, 4

    def __init__(self, catalog: "BufferCatalog", batch_id: int,
                 device_batch: ColumnBatch, priority: int):
        self._catalog = catalog
        self.batch_id = batch_id
        self.priority = priority
        self.tier = self.TIER_DEVICE
        self._device: Optional[ColumnBatch] = device_batch
        self._host: Optional[HostBatch] = None
        self._disk_path: Optional[str] = None
        self._schema = device_batch.schema
        self._capacity = device_batch.capacity
        self.device_bytes = device_batch_bytes(device_batch)
        #: host bytes, computed ONCE when the host copy materializes
        self._host_nbytes = 0
        #: in-flight tier move, None when settled (guarded by catalog lock)
        self._spill_task: Optional[_SpillTask] = None
        #: writer-thread failure awaiting the consumer's next get()
        self._pending_error: Optional[BaseException] = None
        self.closed = False

    # -- disk frames (catalog-internal) -------------------------------------

    def _write_disk(self, host: HostBatch, directory: str) -> int:
        """Serialize + chunk-compress ``host`` to the disk tier; returns
        encoded bytes written.  Pure IO — caller owns tier transitions."""
        from spark_rapids_tpu.mem.codec import get_codec, write_chunked
        from spark_rapids_tpu.native_rt import serialize_host_batch
        codec = get_codec(self._catalog.spill_codec)
        raw = serialize_host_batch(host)
        path = os.path.join(directory, f"spill-{self.batch_id}.tpub")
        with open(path, "wb") as f:
            enc = write_chunked(f, raw, codec, self._catalog.spill_chunk_bytes)
        self._disk_path = path
        return enc

    def _read_disk(self) -> HostBatch:
        from spark_rapids_tpu.mem.codec import get_codec, read_chunked
        from spark_rapids_tpu.native_rt import deserialize_host_batch
        codec = get_codec(self._catalog.spill_codec)
        with open(self._disk_path, "rb") as f:
            raw = read_chunked(f, codec)
        return deserialize_host_batch(raw, self._schema)

    def host_bytes(self) -> int:
        """Host bytes this handle's host-tier copy occupies (cached at
        spill time — never a per-call value walk)."""
        return self._host_nbytes if self._host is not None else 0

    # -- public -------------------------------------------------------------

    def get(self) -> ColumnBatch:
        """Materialize on device (joining an in-flight spill and/or
        unspilling as needed)."""
        assert not self.closed
        cat = self._catalog
        while True:
            with cat._lock:
                err = self._pending_error
                if err is not None:
                    # a writer-thread spill failed: surface the classified
                    # error ONCE (the handle already reverted to its prior
                    # tier, so the recovery ladder's replay will succeed)
                    self._pending_error = None
                    raise err
                tier = self.tier
                if tier == self.TIER_LOST:
                    from spark_rapids_tpu.fault.errors import DeviceLostError
                    raise DeviceLostError(
                        f"spillable batch {self.batch_id} was "
                        "device-resident when the device was lost and no "
                        "host/disk copy survived; its lineage must be "
                        "recomputed")
                if tier == self.TIER_DEVICE:
                    return self._device
                task = self._spill_task
                if tier == self.TIER_SPILLING and task is not None \
                        and task.state == _SpillTask.QUEUED:
                    # won the race against an unstarted spill: cancel
                    # cheaply — the device copy never moved
                    cat._cancel_spill_locked(self, task)
                    dev = self._device
                    cancelled = True
                else:
                    cancelled = False
                if tier in (self.TIER_HOST, self.TIER_DISK):
                    break
            if cancelled:
                # the budget pressure that picked this handle has not gone
                # away: re-run the loop (off the lock) so it lands on a
                # victim the consumer is NOT about to read
                cat.reserve(0, exclude=self.batch_id)
                return dev
            # spill in flight and already running: join THIS handle's
            # completion (not the writer queue), then re-examine
            if task is not None:
                task.wait_done()
        return self._unspill(tier)

    def _unspill(self, tier: int) -> ColumnBatch:
        """Rehydrate from host or disk.  IO runs off the lock; tier
        transitions and counters update under it."""
        from spark_rapids_tpu.fault import inject
        cat = self._catalog
        with span("unspill",
                  "disk" if tier == self.TIER_DISK else "host") as un_span:
            inject.maybe_fire("unspill")
            host = self._read_disk() if tier == self.TIER_DISK \
                else self._host
            with cat._lock:
                raced = self.tier != tier
                if not raced:
                    # Mark device-resident BEFORE reserving so the budget
                    # loop cannot pick this handle as its own spill victim
                    # mid-rehydration; keep the host copy until the upload
                    # lands so a failure can revert.
                    if tier == self.TIER_HOST:
                        cat._host_bytes -= self._host_nbytes
                    self.tier = self.TIER_DEVICE
                    cat._device_bytes += self.device_bytes
                    cat.metrics["unspilled"] += 1
            if not raced:
                return self._rehydrate(tier, host, un_span)
            un_span.set(raced=True)
        # lost to a concurrent get()/spill that moved the handle: retry
        # the state machine from the top, OUTSIDE the lock (the retry may
        # join a writer task that needs it)
        return self.get()

    def _rehydrate(self, tier: int, host, un_span) -> ColumnBatch:
        """The upload half of :meth:`_unspill`, with the tier already
        marked device-resident; a failure reverts that."""
        cat = self._catalog
        try:
            cat.reserve(self.device_bytes, exclude=self.batch_id)
            dev = host_to_device(host, capacity=self._capacity)
        except BaseException:
            with cat._lock:
                if self.tier == self.TIER_DEVICE and self._device is None:
                    self.tier = tier
                    cat._device_bytes -= self.device_bytes
                    cat.metrics["unspilled"] -= 1
                    if tier == self.TIER_HOST:
                        cat._host_bytes += self._host_nbytes
            raise
        with cat._lock:
            self._device = dev
            self._host = None
            self._host_nbytes = 0
        if tier == self.TIER_DISK and self._disk_path:
            if os.path.exists(self._disk_path):
                os.unlink(self._disk_path)
            self._disk_path = None
        un_span.set(bytes=self.device_bytes)
        return dev

    def close(self):
        cat = self._catalog
        with cat._lock:
            if self.closed:
                return
            self.closed = True
            task = self._spill_task
            if task is not None and task.state == _SpillTask.QUEUED:
                cat._cancel_spill_locked(self, task)
            # a RUNNING task finishes on the writer; its finalize sees
            # ``closed`` and drops the copy
            if self.tier == self.TIER_DEVICE:
                cat._device_bytes -= self.device_bytes
            elif self.tier == self.TIER_HOST:
                cat._host_bytes -= self._host_nbytes
            self._device = None
            self._host = None
            self._host_nbytes = 0
            path = self._disk_path
            self._disk_path = None
            cat._handles.pop(self.batch_id, None)
        if path and os.path.exists(path):
            os.unlink(path)


class BufferCatalog:
    """Process-wide registry of spillable batches with a device budget."""

    def __init__(self, conf: RapidsConf):
        self.conf = conf
        self.device_budget = DEVICE_SPILL_BUDGET.get(conf)
        self.host_budget = conf.host_spill_storage_size
        self.spill_codec = conf.get(
            "spark.rapids.shuffle.compression.codec", "copy") or "copy"
        self.async_spill = SPILL_ASYNC_ENABLED.get(conf)
        self.writer_threads = max(1, SPILL_WRITER_THREADS.get(conf))
        self.spill_chunk_bytes = SPILL_CHUNK_BYTES.get(conf)
        self._handles: Dict[int, SpillableBatch] = {}
        self._next_id = 0
        self._lock = threading.RLock()
        self._spill_dir: Optional[str] = None
        # -- incremental accounting: running per-tier byte counters updated
        # at every transition (verify_accounting asserts == scan)
        self._device_bytes = 0
        self._host_bytes = 0
        # -- async writer pool state (lazily started)
        self._queue: Deque[_SpillTask] = deque()
        self._queue_cond = threading.Condition(self._lock)
        self._writers: List[threading.Thread] = []
        self._writers_busy = 0
        self.metrics = {"spilled_to_host": 0, "spilled_to_disk": 0,
                        "unspilled": 0, "spill_cancelled": 0,
                        "spill_wall_ns": 0, "spill_queue_depth_max": 0,
                        "unspill_prefetch_hits": 0,
                        "spill_to_host_bytes": 0, "spill_to_disk_bytes": 0}

    def _dir(self) -> str:
        if self._spill_dir is None:
            self._spill_dir = tempfile.mkdtemp(prefix="rapids_tpu_spill_")
        return self._spill_dir

    # -- registry -----------------------------------------------------------

    def register(self, batch: ColumnBatch,
                 priority: int = PRIORITY_INPUT) -> SpillableBatch:
        with self._lock:
            h = SpillableBatch(self, self._next_id, batch, priority)
            self._next_id += 1
            self._handles[h.batch_id] = h
            self._device_bytes += h.device_bytes
        # budget enforcement OUTSIDE the registry mutation: a synchronous
        # spill's D2H/compress must not stall concurrent register/get
        self.reserve(0, exclude=h.batch_id)
        return h

    def register_sharded(self, batch: ColumnBatch,
                         priority: int = PRIORITY_ON_DECK) -> SpillableBatch:
        """Register a MESH-SHARDED batch (every leaf a multi-device global
        array) ONCE: one handle covers all shards, ``device_bytes`` is the
        global total and ``handle.shard_bytes`` carries the per-device
        split (:func:`device_batch_shard_bytes`).  Defaults to
        PRIORITY_ON_DECK — the least spillable band — because a victim
        pass spilling a sharded global would D2H-gather every shard and
        rehydrate to ONE device; the mesh-SPMD dispatcher holds such
        handles only across the unshard window and closes them before the
        per-device outputs flow downstream."""
        h = self.register(batch, priority)
        h.shard_bytes = device_batch_shard_bytes(batch)
        return h

    def _unregister(self, h: SpillableBatch):
        with self._lock:
            self._handles.pop(h.batch_id, None)

    # -- accounting ---------------------------------------------------------

    def device_bytes_in_use(self) -> int:
        """O(1): the running device-tier counter (v1 re-scanned every
        handle per budget-loop iteration)."""
        with self._lock:
            return self._device_bytes

    def host_bytes_in_use(self) -> int:
        with self._lock:
            return self._host_bytes

    # -- telemetry gauges (obs.timeseries; sampled at export time) ----------

    def writer_utilization(self) -> float:
        """Fraction of the spill-writer pool running a task right now."""
        with self._lock:
            return self._writers_busy / float(max(1, self.writer_threads))

    def writer_queue_depth(self) -> int:
        """Spill tasks queued but not yet picked up by a writer."""
        with self._lock:
            return len(self._queue)

    def tier_bytes(self) -> Dict[str, int]:
        """Bytes resident per tier right now: the device/host running
        counters plus a disk scan over spilled files (OSError-tolerant —
        a file mid-delete reads as absent)."""
        with self._lock:
            disk = 0
            for h in self._handles.values():
                path = h._disk_path
                if path:
                    try:
                        disk += os.path.getsize(path)
                    except OSError:
                        continue
            return {"device": self._device_bytes,
                    "host": self._host_bytes, "disk": disk}

    def verify_accounting(self) -> List[str]:
        """Debug invariant (analysis/plan_verify.py): the incremental
        counters must equal a full scan at any lock-quiesced instant —
        every transition updates both tier and counter under the lock."""
        with self._lock:
            dev = sum(h.device_bytes for h in self._handles.values()
                      if h.tier == SpillableBatch.TIER_DEVICE)
            host = sum(h._host_nbytes for h in self._handles.values()
                       if h.tier == SpillableBatch.TIER_HOST)
            problems = []
            if dev != self._device_bytes:
                problems.append(
                    f"catalog device-bytes counter {self._device_bytes} != "
                    f"scan {dev}")
            if host != self._host_bytes:
                problems.append(
                    f"catalog host-bytes counter {self._host_bytes} != "
                    f"scan {host}")
            return problems

    def verify_encoded_host_batches(self) -> List[str]:
        """Encoded-corridor invariant half (analysis/plan_verify.py): a
        host-tier handle holding dictionary-encoded columns must be
        structurally reconstructible — non-empty dictionary, integer
        codes inside it — or unspill would rebuild a different column."""
        with self._lock:
            hosts = [(hid, h._host) for hid, h in self._handles.items()
                     if h.tier == SpillableBatch.TIER_HOST and
                     h._host is not None]
        problems = []
        for hid, hb in hosts:
            for f, c in zip(hb.schema.fields, hb.columns):
                if c.dictionary is None:
                    continue
                codes = np.asarray(c.values)
                nd = len(c.dictionary)
                if codes.dtype.kind not in "iu":
                    problems.append(
                        f"catalog handle {hid}: encoded column {f.name!r} "
                        f"has non-integer codes dtype {codes.dtype}")
                elif nd == 0:
                    problems.append(
                        f"catalog handle {hid}: encoded column {f.name!r} "
                        "has an empty dictionary")
                elif len(codes) and (int(codes.min()) < 0 or
                                     int(codes.max()) >= nd):
                    problems.append(
                        f"catalog handle {hid}: encoded column {f.name!r} "
                        f"codes outside [0, {nd})")
        return problems

    # -- spill state machine ------------------------------------------------

    def _begin_spill_locked(self, victim: SpillableBatch) -> _SpillTask:
        """DEVICE -> SPILLING under the lock: the victim's bytes leave the
        device counter now (the copy is committed to go), the task carries
        the work."""
        task = _SpillTask(victim)
        victim._spill_task = task
        victim.tier = SpillableBatch.TIER_SPILLING
        self._device_bytes -= victim.device_bytes
        self.metrics["spilled_to_host"] += 1
        return task

    def _cancel_spill_locked(self, h: SpillableBatch,
                             task: _SpillTask) -> None:
        """SPILLING -> DEVICE for a still-queued task (get() won the race,
        or the handle closed): the device copy never moved."""
        task.state = _SpillTask.CANCELLED
        task.mark_done()
        h._spill_task = None
        h.tier = SpillableBatch.TIER_DEVICE
        self._device_bytes += h.device_bytes
        self.metrics["spilled_to_host"] -= 1
        self.metrics["spill_cancelled"] += 1
        obs_events.emit_instant("spill", "cancelled")

    def _submit(self, task: _SpillTask) -> None:
        with self._lock:
            self._ensure_writers_locked()
            self._queue.append(task)
            depth = len(self._queue)
            if depth > self.metrics["spill_queue_depth_max"]:
                self.metrics["spill_queue_depth_max"] = depth
            self._queue_cond.notify()

    def _ensure_writers_locked(self) -> None:
        self._writers = [t for t in self._writers if t.is_alive()]
        while len(self._writers) < self.writer_threads:
            t = threading.Thread(target=self._writer_loop, daemon=True,
                                 name=f"spill-writer-{len(self._writers)}")
            self._writers.append(t)
            t.start()

    def _writer_loop(self) -> None:
        while True:
            with self._queue_cond:
                while not self._queue:
                    self._queue_cond.wait(_WAIT_SLICE)
                task = self._queue.popleft()
                self._writers_busy += 1
            try:
                with obs_events.adopt(task.scope):
                    self._run_spill_task(task)
            finally:
                with self._lock:
                    self._writers_busy -= 1

    def _run_spill_task(self, task: _SpillTask,
                        raise_errors: bool = False) -> None:
        """Execute one device->host spill: D2H off the lock, finalize under
        it, then host-budget enforcement (compress+write, also off-lock).

        ``raise_errors`` is the synchronous mode (async disabled, or the
        eager OOM path): the failure reverts the handle and propagates to
        the triggering caller — exact v1 semantics.  Async mode stashes
        the error on the handle for the consumer's next ``get()``.
        """
        h = task.handle
        with self._lock:
            if task.state != _SpillTask.QUEUED:
                return  # cancelled while queued
            task.state = _SpillTask.RUNNING
            dev = h._device
        from spark_rapids_tpu.fault import inject
        sp = span("spill", "to_host")
        try:
            with sp:
                inject.maybe_fire("spill")
                host = device_to_host(dev, keep_dictionary=True)
                nbytes = host_batch_bytes(host)
                with self._lock:
                    live = h._spill_task is task and \
                        h.tier == SpillableBatch.TIER_SPILLING and not h.closed
                    if live:
                        h._host = host
                        h._host_nbytes = nbytes
                        h._device = None
                        h.tier = SpillableBatch.TIER_HOST
                        self._host_bytes += nbytes
                        self.metrics["spill_to_host_bytes"] += nbytes
                        # the copy is safe on host now: an earlier attempt's
                        # stashed failure is moot, don't fail a later get()
                        h._pending_error = None
                    # else: aborted (invalidate/close) mid-copy — drop it
                sp.set(bytes=nbytes if live else 0)
        except BaseException as e:
            with self._lock:
                if h._spill_task is task and \
                        h.tier == SpillableBatch.TIER_SPILLING:
                    # revert: the device copy is untouched, so a replay
                    # after the surfaced error succeeds bit-identically
                    h.tier = SpillableBatch.TIER_DEVICE
                    self._device_bytes += h.device_bytes
                    self.metrics["spilled_to_host"] -= 1
                    if not raise_errors:
                        h._pending_error = e
                task.error = e
            obs_events.emit_instant("spill", "error",
                                    error_type=type(e).__name__)
            if raise_errors or not isinstance(e, Exception):
                raise
            return
        finally:
            with self._lock:
                if h._spill_task is task:
                    h._spill_task = None
                task.state = _SpillTask.DONE
                self.metrics["spill_wall_ns"] += time.monotonic_ns() - sp.t0
            task.mark_done()
        self._enforce_host_budget(raise_errors=raise_errors)

    # -- budget enforcement -------------------------------------------------

    def reserve(self, incoming_bytes: int, exclude: int = -1):
        """Spill until (in_use + incoming) fits the budget (the
        synchronousSpill loop, RapidsBufferStore.scala:144).  Victim
        selection and the SPILLING transition happen under the lock; the
        copy itself runs on the writer pool (async) or inline off the lock
        (sync) — either way concurrent register/get never stall behind a
        multi-GB D2H."""
        while True:
            with self._lock:
                if self._device_bytes + incoming_bytes <= self.device_budget:
                    return
                victim = self._pick_victim(
                    SpillableBatch.TIER_DEVICE, exclude)
                if victim is None:
                    return
                task = self._begin_spill_locked(victim)
            if self.async_spill:
                obs_events.emit_instant("spill", "queued",
                                        bytes=victim.device_bytes)
                self._submit(task)
            else:
                self._run_spill_task(task, raise_errors=True)

    def _enforce_host_budget(self, raise_errors: bool = False):
        """Push host-tier handles to disk until the host store fits.  The
        victim transitions to SPILLING under the lock; serialize +
        chunk-compress + write run OUTSIDE it (v1 held the lock through
        the whole compress+write, stalling every register/get)."""
        while True:
            with self._lock:
                if self._host_bytes <= self.host_budget:
                    return
                victim = self._pick_victim(SpillableBatch.TIER_HOST, -1)
                if victim is None:
                    return
                task = _SpillTask(victim)
                task.state = _SpillTask.RUNNING
                victim._spill_task = task
                victim.tier = SpillableBatch.TIER_SPILLING
                self._host_bytes -= victim._host_nbytes
                host = victim._host
            sp = span("spill", "to_disk")
            try:
                with sp:
                    enc = victim._write_disk(host, self._dir())
                    with self._lock:
                        if victim.closed:
                            path = victim._disk_path
                            victim._disk_path = None
                        else:
                            path = None
                            victim._host = None
                            victim._host_nbytes = 0
                            victim.tier = SpillableBatch.TIER_DISK
                            victim._pending_error = None
                            self.metrics["spilled_to_disk"] += 1
                            self.metrics["spill_to_disk_bytes"] += enc
                    if path and os.path.exists(path):
                        os.unlink(path)
                    sp.set(bytes=enc)
            except BaseException as e:
                with self._lock:
                    if victim._spill_task is task and \
                            victim.tier == SpillableBatch.TIER_SPILLING:
                        victim.tier = SpillableBatch.TIER_HOST
                        self._host_bytes += victim._host_nbytes
                        if not raise_errors:
                            victim._pending_error = e
                    task.error = e
                    task.state = _SpillTask.DONE
                    if victim._spill_task is task:
                        victim._spill_task = None
                task.mark_done()
                if raise_errors or not isinstance(e, Exception):
                    raise
                return
            with self._lock:
                task.state = _SpillTask.DONE
                if victim._spill_task is task:
                    victim._spill_task = None
                self.metrics["spill_wall_ns"] += \
                    time.monotonic_ns() - sp.t0
            task.mark_done()

    def drain_spills(self) -> None:
        """Join every in-flight async spill (tests, bench, shutdown
        barriers).  Queued tasks run to completion; the wait is bounded
        per slice (watchdog-compatible).

        A writer thread clears its D2H task *before* it runs host-budget
        enforcement, so "no tasks visible" does not yet mean the host
        store fits: the host->disk push may not have started.  The host
        bytes ARE counted by then, so running enforcement here closes
        that window — it either does the push itself or loses the victim
        pick to the writer's concurrent loop, and the re-check below
        waits out whichever task that created."""
        while True:
            with self._lock:
                tasks = [h._spill_task for h in self._handles.values()
                         if h._spill_task is not None]
            if not tasks:
                self._enforce_host_budget()
                with self._lock:
                    tasks = [h._spill_task for h in self._handles.values()
                             if h._spill_task is not None]
                if not tasks:
                    return
            for t in tasks:
                t.wait_done()

    # -- OOM / device-loss entry points -------------------------------------

    def handle_alloc_failure(self, pinned=()) -> int:
        """Spill ALL device-tier spillables; bytes freed.

        The DeviceMemoryEventHandler role (DeviceMemoryEventHandler.scala:35):
        RMM invokes the reference's handler from inside a failed cudaMalloc;
        XLA exposes no such callback, so the engine instead catches the
        RESOURCE_EXHAUSTED runtime error at dispatch boundaries
        (:func:`run_with_oom_retry`) and calls this.  A real device OOM means
        the soft budget under-counted (unregistered transients, fragmentation),
        so everything spillable goes to host, not just down to the budget.

        Always EAGER — every spill completes (and every already-in-flight
        async spill is joined) before this returns, so the caller's retry
        runs against freed HBM — but the copies execute OFF the catalog
        lock: concurrent register/get don't stall behind them.

        ``pinned`` holds batches the retrying computation still references
        (its input args): spilling those would free nothing — the jax buffers
        stay alive through the caller's reference — while marking the handle
        host-tier, so a later ``get()`` would allocate a SECOND device copy.
        They are skipped and excluded from the freed count.
        """
        # Pin by LEAF array identity, not batch-wrapper identity: colocation
        # may rebuild wrappers around the same device arrays, and only a
        # handle whose underlying buffers are aliased by the retrying args
        # is futile to spill.
        import jax
        pinned_ids = {id(leaf) for b in pinned
                      for leaf in jax.tree_util.tree_leaves(b)}
        freed = 0
        mine: List[_SpillTask] = []
        inflight: List[_SpillTask] = []
        with self._lock:
            victims = sorted(
                (h for h in self._handles.values()
                 if h.tier == SpillableBatch.TIER_DEVICE and not h.closed
                 and h._device is not None
                 and not any(id(leaf) in pinned_ids for leaf in
                             jax.tree_util.tree_leaves(h._device))),
                key=lambda h: (h.priority, h.batch_id))
            for victim in victims:
                freed += victim.device_bytes
                mine.append(self._begin_spill_locked(victim))
            for h in self._handles.values():
                t = h._spill_task
                if t is not None and t not in mine:
                    inflight.append(t)
        for task in mine:
            self._run_spill_task(task, raise_errors=True)
        for task in inflight:
            # a spill the writer already owns frees HBM too once joined —
            # count it so the retry isn't abandoned as futile
            task.wait_done()
            if task.error is None and task.state == _SpillTask.DONE:
                freed += task.bytes
        if mine or inflight:
            self._enforce_host_budget(raise_errors=True)
        if freed:
            with self._lock:
                self.metrics["oom_spill_bytes"] = \
                    self.metrics.get("oom_spill_bytes", 0) + freed
        return freed

    def invalidate_device_tier(self, rescue: bool = True) -> int:
        """Device-lost recovery (fault.recovery): every device-tier
        handle is rescued to host when the buffers still answer (the
        simulated-fault case — and real losses where XLA kept the copy
        readable), else marked TIER_LOST so a later ``get()`` raises a
        classified DeviceLostError and the consumer's replay recomputes
        the batch from lineage.  ``rescue=False`` (timeout-classified
        recovery: the device is WEDGED, a rescue D2H against it would
        block the recovery path on the very hang being recovered from)
        marks device-tier handles lost without touching the device.
        Host- and disk-tier handles are untouched: they re-upload
        lazily on the next ``get()``.  Returns the number of handles
        that transitioned.

        In-flight spills are drained/aborted FIRST: queued writer tasks
        are cancelled (their device copies are handled here instead);
        running ones are abandoned when ``rescue=False`` (their D2H may
        be the very hang being recovered from — the late finalize sees
        the LOST tier and drops its copy) or joined briefly when
        rescuing.
        """
        running: List[_SpillTask] = []
        with self._lock:
            for h in list(self._handles.values()):
                t = h._spill_task
                if t is None or h.closed:
                    continue
                if t.state == _SpillTask.QUEUED:
                    self._cancel_spill_locked(h, t)
                elif t.state == _SpillTask.RUNNING:
                    running.append(t)
        if rescue:
            for t in running:
                t.wait_done()
        moved = 0
        with self._lock:
            for h in list(self._handles.values()):
                if h.closed or h.tier not in (SpillableBatch.TIER_DEVICE,
                                              SpillableBatch.TIER_SPILLING):
                    continue
                was_spilling = h.tier == SpillableBatch.TIER_SPILLING
                moved += 1
                if rescue and not was_spilling:
                    try:
                        host = device_to_host(h._device,
                                              keep_dictionary=True)
                        h._host = host
                        h._host_nbytes = host_batch_bytes(host)
                        h._device = None
                        h.tier = SpillableBatch.TIER_HOST
                        self._device_bytes -= h.device_bytes
                        self._host_bytes += h._host_nbytes
                        self.metrics["spilled_to_host"] += 1
                        continue
                    except Exception:  # noqa: BLE001 — buffers truly gone
                        pass
                if not was_spilling:
                    self._device_bytes -= h.device_bytes
                h._device = None
                h._host = None
                h._host_nbytes = 0
                h._spill_task = None
                h.tier = SpillableBatch.TIER_LOST
                self.metrics["lost_batches"] = \
                    self.metrics.get("lost_batches", 0) + 1
            if moved:
                self.metrics["device_invalidated"] = \
                    self.metrics.get("device_invalidated", 0) + moved
        if moved:
            self._enforce_host_budget()
        return moved

    # -- overlapped unspill --------------------------------------------------

    def prefetch(self, handles: Sequence[SpillableBatch],
                 depth: int = 1) -> Iterator[ColumnBatch]:
        """Yield each handle's device batch with up to ``depth`` unspills
        in flight ahead of the consumer: handle i+1's disk read +
        decompress + async H2D enqueue overlaps compute on batch i (the
        shuffle drain's one-piece read-ahead, generalized to any handle
        list).  Admission stays with the existing machinery — ``get()``'s
        reserve() bounds device bytes and the consumer task's semaphore
        permit is already held (re-entrant, task-wide) — so read-ahead
        cannot blow the budget or leak depth."""
        handles = list(handles)
        if not handles:
            return
        depth = max(1, depth)

        def _fetch(h: SpillableBatch) -> ColumnBatch:
            if h.tier != SpillableBatch.TIER_DEVICE:
                # the read-ahead actually hid an unspill (vs a device hit)
                with self._lock:
                    self.metrics["unspill_prefetch_hits"] += 1
                obs_events.emit_instant("unspill", "prefetch_hit")
            return h.get()

        window: Deque[ColumnBatch] = deque()
        nxt = 0
        while nxt < len(handles) and len(window) < depth:
            window.append(_fetch(handles[nxt]))
            nxt += 1
        while window:
            cur = window.popleft()
            if nxt < len(handles):
                window.append(_fetch(handles[nxt]))
                nxt += 1
            yield cur

    # -- victim selection ----------------------------------------------------

    def _pick_victim(self, tier: int, exclude: int
                     ) -> Optional[SpillableBatch]:
        best = None
        for h in self._handles.values():
            if h.tier != tier or h.batch_id == exclude or h.closed:
                continue
            if tier == SpillableBatch.TIER_DEVICE and h._device is None:
                continue  # mid-rehydration (get() marked early)
            if h._pending_error is not None:
                # a failed writer spill reverted this handle; re-picking
                # it before a get() consumed the error would livelock the
                # budget loop against a persistent fault
                continue
            if best is None or h.priority < best.priority or \
                    (h.priority == best.priority and
                     h.batch_id < best.batch_id):
                best = h
        return best


def is_device_oom(err: BaseException) -> bool:
    """True when ``err`` is an XLA out-of-device-memory failure.  JAX raises
    ``XlaRuntimeError``/``JaxRuntimeError`` whose message carries the ABSL
    status code name; allocation failures are RESOURCE_EXHAUSTED."""
    return "RESOURCE_EXHAUSTED" in str(err) \
        and type(err).__name__ in ("XlaRuntimeError", "JaxRuntimeError")


def run_with_oom_retry(catalog: "BufferCatalog", thunk,
                       retries: Optional[int] = None,
                       pinned=(), on_retry=None):
    """Run ``thunk`` and, on a device OOM, spill everything spillable and
    re-run — the engine-side analogue of the reference's alloc-failure →
    synchronous-spill → retry loop (DeviceMemoryEventHandler.scala:35,
    RmmRapidsRetryIterator.scala's withRetry).

    Thin wrapper over the unified fault machinery: the error must
    classify RETRYABLE_OOM (fault.errors — covers real XLA
    RESOURCE_EXHAUSTED and injected OOMs alike) and the attempt bound
    comes from the one RetryPolicy
    (``spark.rapids.sql.tpu.retry.maxAttempts``) unless ``retries``
    overrides it (``retries=0`` = fail fast, the donated-dispatch
    path).  No backoff sleep here: the corrective action (the spill)
    already completed synchronously, so there is no transient condition
    to wait out — backoff belongs to the device-lost replay ladder.
    Still gives up early when a retry frees nothing — spilling can no
    longer help.  ``pinned``: batches the thunk re-reads on retry (see
    :meth:`BufferCatalog.handle_alloc_failure`).
    """
    from spark_rapids_tpu.fault import metrics as fault_metrics
    from spark_rapids_tpu.fault.errors import ErrorClass, classify_error
    from spark_rapids_tpu.fault.retry import RetryPolicy
    max_attempts = RetryPolicy.from_conf(catalog.conf).max_attempts \
        if retries is None else retries + 1
    attempt = 0
    while True:
        attempt += 1
        try:
            return thunk()
        except Exception as e:  # noqa: BLE001 — filtered by classification
            if classify_error(e) is not ErrorClass.RETRYABLE_OOM or \
                    attempt >= max_attempts:
                raise
            freed = catalog.handle_alloc_failure(pinned=pinned)
            if freed == 0:
                raise
            if on_retry is not None:
                on_retry(freed)
            fault_metrics.record("retries")
