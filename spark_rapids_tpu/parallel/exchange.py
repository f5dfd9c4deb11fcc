"""Shuffle and broadcast exchanges (reference: GpuShuffleExchangeExec.scala,
GpuBroadcastExchangeExec.scala; SURVEY.md sections 2.5, 2.7).

Single-host model: an exchange materializes its child's partitions, splits
every batch by target-partition id (device-side compaction for TPU plans,
numpy for CPU fallback), and regroups — the "fallback path (a)" of the
reference.  The device-mesh all-to-all path (ICI analogue) lives in
``parallel.mesh_shuffle`` and is used by the distributed runner.
"""

from __future__ import annotations

from typing import Iterator, List

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import ColumnBatch, HostBatch, HostColumn
from spark_rapids_tpu.kernels.layout import gather_rows
from spark_rapids_tpu.parallel.partitioning import (
    HashPartitioning, Partitioning, RangePartitioning,
    RoundRobinPartitioning, SinglePartitioning,
)
from spark_rapids_tpu.plan.physical import (
    CpuExec, ExecContext, PhysicalOp, TpuExec,
)
from spark_rapids_tpu.obs import events as obs_events
from spark_rapids_tpu.utils.compile_registry import plan_jit
from spark_rapids_tpu.utils.tracing import device_read, span

def _range_sample_limit(ctx) -> int:
    from spark_rapids_tpu.config import CPU_RANGE_PARTITIONING_SAMPLE
    return max(1, CPU_RANGE_PARTITIONING_SAMPLE.get(ctx.conf))


def _collapse_local_conf(ctx) -> bool:
    """Single-process execution doesn't need a physical split: every
    downstream consumer sees all rows either way, and partitioning only
    constrains *placement* (trivially satisfied by one partition).
    Collapsing removes the per-batch count sync + one gather per target
    partition — pure overhead on one device.  The mesh (multi-device)
    path does its own all-to-all instead."""
    from spark_rapids_tpu.config import EXCHANGE_COLLAPSE_LOCAL
    return EXCHANGE_COLLAPSE_LOCAL.get(ctx.conf)


class CpuShuffleExchangeExec(CpuExec):
    def __init__(self, partitioning: Partitioning, child: PhysicalOp):
        super().__init__([child], child.output_schema)
        self.partitioning = partitioning

    def describe(self):
        p = self.partitioning
        return f"CpuShuffleExchange({type(p).__name__}, {p.num_partitions})"

    def num_partitions(self, ctx):
        if _collapse_local_conf(ctx):
            return 1
        return self.partitioning.num_partitions

    def partitions(self, ctx):
        n = self.partitioning.num_partitions
        in_parts = self.children[0].partitions(ctx)
        if _collapse_local_conf(ctx):
            # mirror the TPU exchange's local collapse so CPU and TPU
            # plans keep identical deterministic row orders (the compare
            # harness and mixed plans rely on it)
            def gen():
                for part in in_parts:
                    for hb in part:
                        yield hb

            return [gen()]
        all_batches: List[List[HostBatch]] = [list(p) for p in in_parts]
        if isinstance(self.partitioning, RangePartitioning):
            self.partitioning.prepare(_sample_host_keys(
                all_batches, self.partitioning.key_ordinals,
                _range_sample_limit(ctx)))
        out: List[List[HostBatch]] = [[] for _ in range(n)]
        for pi, batches in enumerate(all_batches):
            for hb in batches:
                ids = self.partitioning.host_partition_ids(hb, pi)
                # ONE stable argsort + split instead of N boolean-mask
                # scans: the stable sort keeps each target's rows in
                # original order (the deterministic order the compare
                # harness and mixed CPU/TPU plans rely on)
                order = np.argsort(ids, kind="stable")
                counts = np.bincount(ids, minlength=n)
                cuts = np.cumsum(counts)[:-1]
                split_cols = [(np.split(c.values[order], cuts),
                               np.split(c.validity[order], cuts))
                              for c in hb.columns]
                for p in range(n):
                    if counts[p] == 0:
                        continue
                    cols = [HostColumn(c.dtype, vs[p], vl[p])
                            for c, (vs, vl) in zip(hb.columns, split_cols)]
                    out[p].append(HostBatch(hb.schema, cols))
        return [iter(p) for p in out]


def _sample_host_keys(all_batches: List[List[HostBatch]],
                      key_ordinals: List[int],
                      limit: int) -> List[tuple]:
    rows: List[tuple] = []
    for batches in all_batches:
        for hb in batches:
            cols = [hb.columns[i].to_list() for i in key_ordinals]
            for r in range(hb.num_rows):
                rows.append(tuple(c[r] for c in cols))
                if len(rows) >= limit:
                    return rows
    return rows


class TpuShuffleExchangeExec(TpuExec):
    """Device-side partition split: pid per row (murmur3 pmod / range
    compare / round-robin), then one compaction per target partition —
    the single-host analogue of GPU partition + contiguousSplit
    (GpuPartitioning.scala:44-117)."""

    def __init__(self, partitioning: Partitioning, child: PhysicalOp):
        super().__init__([child], child.output_schema)
        self.partitioning = partitioning
        self._input_fns = []
        self._fused_map = None
        self._sort_by_pid = plan_jit(
            self._sort_by_pid_impl, label="TpuShuffleExchange:split",
            static_argnames=("n", "keep_encoded"))

    def absorb_input(self, fns):
        """Fuse upstream map-like stages into the partition-split program
        (one dispatch per batch for filter+project+hash+sort-by-pid)."""
        self._input_fns = list(fns)
        self._fused_map = None

    def _mesh_active(self, ctx) -> bool:
        return getattr(ctx, "mesh", None) is not None

    def _collapse_local(self, ctx) -> bool:
        return not self._mesh_active(ctx) and _collapse_local_conf(ctx)

    def describe(self):
        p = self.partitioning
        return f"TpuShuffleExchange({type(p).__name__}, {p.num_partitions})"

    def num_partitions(self, ctx):
        if self._mesh_active(ctx):
            from spark_rapids_tpu.parallel.mesh_shuffle import DATA_AXIS
            return ctx.mesh.shape[DATA_AXIS]
        if self._collapse_local(ctx):
            return 1
        return self.partitioning.num_partitions

    def _ensure_fused_map(self):
        """Compile any absorbed map stages (filter/project) into ONE
        program per batch; shared by the collapse-local, the adaptive
        bypass and the mesh paths, which all skip the split but must still
        apply the absorbed stages (a :class:`BatchProgram`: a filter among
        them counts its compactions here as in a stage program)."""
        if self._input_fns and self._fused_map is None:
            fns = list(self._input_fns)

            def composed(b):
                for f in fns:
                    b = f(b)
                return b

            from spark_rapids_tpu.plan.pipeline import BatchProgram
            self._fused_map = BatchProgram(composed,
                                           "TpuShuffleExchange:map")

    def has_materialized_split(self, ctx) -> bool:
        """True when this exchange's split already ran for ``ctx`` on the
        LIVE device generation, i.e. ``partitions`` would re-read the
        cached spillable pieces instead of re-splitting."""
        from spark_rapids_tpu.runtime.device import DeviceRuntime
        cached = getattr(self, "_split_cache", None)
        return cached is not None and cached[0]() is ctx and \
            cached[2] == DeviceRuntime.generation()

    def bypass_partitions(self, ctx):
        """Adaptive broadcast-switch probe elision (plan/adaptive): the
        consumer joins every partition against a broadcast build, so
        co-partitioning buys nothing — hand back the child's partitions
        with any absorbed map stages applied (one fused program per
        batch) and NO split: no pid programs, no piece gathers, no split
        host sync, no catalog registrations.  The exchange fault site
        still fires so injection specs aimed at exchanges cover elided
        ones, and the mesh path is never bypassed (its all_to_all IS the
        data movement)."""
        from spark_rapids_tpu.fault import inject
        inject.maybe_fire("exchange")
        if self._mesh_active(ctx):
            return self._mesh_partitions(ctx)
        ctx.metric(self.op_id, "shuffleElided").add(1)
        obs_events.emit_instant("exchange", "elided", self.op_id)
        self._ensure_fused_map()

        def gen(part):
            for db in part:
                yield self._fused_map(ctx, db) if self._fused_map else db

        return [gen(p) for p in self.children[0].partitions(ctx)]

    def pipeline_inline(self, ctx, build):
        if self._mesh_active(ctx):
            return self._mesh_spmd_inline(ctx, build)
        if not self._collapse_local(ctx):
            return None
        cf = build(self.children[0])
        fns = list(self._input_fns)

        def f(args):
            bs = cf(args)
            for fn in fns:
                bs = [fn(b) for b in bs]
            return bs

        return f

    def _mesh_spmd_inline(self, ctx, build):
        """Whole-stage SPMD fusion (mesh.spmd.enabled): instead of
        becoming a stage source that host-drives mesh_exchange_batches —
        one sync + restage per exchange — the exchange lowers INTO the
        surrounding stage program as an in-program all_to_all
        (mesh_shuffle.exchange_batch_collective).  Producer segment,
        shuffle and consumer segment then dispatch as ONE shard_map
        program with zero host syncs at the boundary.

        Returns None (exchange stays a host-driven stage source) when no
        mesh build scope is active, or when the partitioning matches no
        PartitionSpec rule (partitioning.MESH_PARTITION_RULES: single
        would leave each shard a private "partition 0", breaking global
        aggregates/limits) — unless mesh.spmd.autoFallback is off, which
        turns that silent fallback into an error for debugging fusion
        coverage.  Range partitioning fuses: its bounds are sampled,
        pooled (all_gather) and picked INSIDE the program
        (RangePartitioning.device_bounds_in_program), replacing the eager
        host prepare() pre-pass."""
        from spark_rapids_tpu.plan.pipeline import (
            concat_static, mesh_build_scope,
        )
        scope = mesh_build_scope()
        if scope is None:
            return None
        from spark_rapids_tpu.parallel.partitioning import (
            match_partition_rules,
        )
        if match_partition_rules(
                type(self.partitioning).__name__) is None:
            from spark_rapids_tpu.config import MESH_SPMD_AUTO_FALLBACK
            if not MESH_SPMD_AUTO_FALLBACK.get(ctx.conf):
                raise RuntimeError(
                    f"{self.describe()}: partitioning is not mesh-SPMD "
                    "compatible and spark.rapids.sql.tpu.mesh.spmd."
                    "autoFallback is disabled")
            obs_events.emit_instant(
                "exchange", "mesh_fallback", self.op_id,
                partitioning=type(self.partitioning).__name__)
            return None
        from spark_rapids_tpu.parallel.mesh_shuffle import (
            DATA_AXIS, exchange_batch_collective,
        )
        cf = build(self.children[0])
        fns = list(self._input_fns)
        n = ctx.mesh.shape[DATA_AXIS]
        part = _mesh_partitioning(self.partitioning, n)
        sample_per_shard = _range_sample_limit(ctx) if \
            isinstance(part, RangePartitioning) else 0
        scope.exchanges.append(self)

        def f(args):
            bs = cf(args)
            for fn in fns:
                bs = [fn(b) for b in bs]
            # one local concat per shard keeps pid assignment identical
            # to the host-driven path's merged batch (concat compacts
            # live rows at the front in input order, so row position —
            # all round-robin sees — matches _concat_all's)
            b = concat_static(bs, self.output_schema) if len(bs) != 1 \
                else bs[0]
            d = jax.lax.axis_index(DATA_AXIS)
            if isinstance(part, RangePartitioning):
                bounds = part.device_bounds_in_program(
                    b, DATA_AXIS, max(1, sample_per_shard // n))
                pid = part.device_partition_ids_from_words(b, bounds)
            else:
                pid = part.device_partition_ids(b, d)
            return [exchange_batch_collective(
                b, jnp.asarray(pid, jnp.int32), n)]

        return f

    def _sort_by_pid_impl(self, batch: ColumnBatch, part_index, n: int,
                          bound_words=None, keep_encoded: bool = False):
        """One pass: rows reordered so each target partition's rows are
        contiguous (the GPU `Table.partition` + contiguousSplit shape,
        GpuPartitioning.scala:44-117).  Returns (sorted batch, per-target
        row counts, per-target byte totals for each string column).

        ``bound_words`` (range partitioning only): pre-encoded range-bound
        word arrays passed as TRACED arguments, so range splits ride the
        same jitted program as hash/round-robin instead of the eager
        per-bound path.

        ``keep_encoded`` (dict-aware shuffle): the pid-sort permutes
        dictionary codes instead of materializing string bytes.  Byte
        totals always report MATERIALIZED per-target element totals for
        encoded columns (per-row entry lengths gathered through the
        codes) — they size the materialize-path byte caps and the
        encoded-path ``mat_byte_cap`` alike."""
        for f in self._input_fns:
            batch = f(batch)
        cap = batch.capacity
        if bound_words is not None:
            ids = self.partitioning.device_partition_ids_from_words(
                batch, bound_words)
        else:
            ids = self.partitioning.device_partition_ids(batch, part_index)
        live = jnp.arange(cap, dtype=jnp.int32) < batch.num_rows
        ids = jnp.where(live, ids, n)
        order = jnp.argsort(ids, stable=True).astype(jnp.int32)
        sorted_batch = gather_rows(batch, order, batch.num_rows,
                                   keep_encoded=keep_encoded)
        counts = jnp.zeros(n + 1, jnp.int32).at[ids].add(1)[:n]
        byte_totals = []
        for c in batch.columns:
            # ALL varlen columns (strings AND arrays), in column order —
            # the split's out_byte_caps align positionally with
            # gather_rows' varlen columns; totals are in element units
            # (bytes for strings, element count for arrays)
            if c.is_varlen:
                if c.codes is not None:
                    nd = int(c.offsets.shape[0]) - 1
                    ent_lens = (c.offsets[1:] - c.offsets[:-1]) \
                        .astype(jnp.int64)
                    codes_c = jnp.clip(c.codes, 0, max(nd - 1, 0))
                    lens = jnp.where(c.validity, ent_lens[codes_c], 0)
                else:
                    lens = (c.offsets[1:] - c.offsets[:-1]).astype(jnp.int64)
                byte_totals.append(jax.ops.segment_sum(
                    lens, ids, num_segments=n + 1)[:n])
        return sorted_batch, counts, byte_totals

    def _mesh_partitions(self, ctx):
        """ICI collective path: rows move between mesh devices with ONE
        lax.all_to_all per column payload (the reference's UCX transport
        role, RapidsShuffleTransport.scala:378-492, as a single compiled
        SPMD program)."""
        from spark_rapids_tpu.ops.tpu_exec import _concat_all
        from spark_rapids_tpu.parallel.mesh_shuffle import (
            DATA_AXIS, mesh_exchange_batches,
        )
        mesh = ctx.mesh
        n = mesh.shape[DATA_AXIS]
        batches: List[ColumnBatch] = []
        for part in self.children[0].partitions(ctx):
            batches.extend(part)
        self._ensure_fused_map()
        if self._fused_map:
            batches = [self._fused_map(ctx, b) for b in batches]
        if not batches:
            return [iter([]) for _ in range(n)]
        # re-key the partitioning onto the mesh: one output partition per
        # device (preserves range ordering / hash co-location)
        part = _mesh_partitioning(self.partitioning, n)
        if isinstance(part, RangePartitioning):
            part.prepare(_sample_device_keys([batches], part.key_ordinals,
                                             _range_sample_limit(ctx)))
        per_dev: List[List[ColumnBatch]] = [[] for _ in range(n)]
        for i, b in enumerate(batches):
            per_dev[i % n].append(b)
        local_batches, pids_list = [], []
        for d in range(n):
            merged = _concat_all(per_dev[d], self.output_schema)
            if merged is None:
                local_batches.append(None)
                pids_list.append(None)
                continue
            pid = part.device_partition_ids(merged, d)
            local_batches.append(merged)
            pids_list.append(jnp.asarray(pid, jnp.int32))
        stats: dict = {}
        with span("exchange", "mesh", self.op_id) as sp:
            out = mesh_exchange_batches(mesh, local_batches, pids_list,
                                        self.output_schema, stats=stats)
            # No host sync here: blocking on the all_to_all kills its
            # async overlap with downstream dispatch (the whole point of
            # the collective path), so this span is the enqueue's wall.
            sp.set(bytes=stats.get("payload_bytes", 0), devices=n,
                   bytes_per_device=stats.get("bytes_per_device"))
        ctx.metric(self.op_id, "meshExchanges").add(1)
        ctx.metric(self.op_id, "meshDevices").add(n)
        # shuffle throughput accounting (RapidsCachingReader.scala:125-133
        # role): bytes moved + wall time -> GB/s is derivable downstream
        ctx.metric(self.op_id, "shuffleBytes").add(
            stats.get("payload_bytes", 0))
        ctx.metric(self.op_id, "shuffleWireBytes").add(
            stats.get("wire_bytes", 0))
        if stats.get("encoded_materialized"):
            # the encoded-corridor gap at mesh boundaries, measured:
            # dict-encoded columns give up their codes here (the
            # collective wire format is materialized elements)
            ctx.metric(self.op_id, "meshEncodedMaterializedBytes").add(
                stats.get("materialized_bytes", 0))
            obs_events.emit_instant(
                "exchange", "mesh_materialize", self.op_id,
                batches=stats.get("encoded_materialized", 0),
                bytes=stats.get("materialized_bytes", 0))
        return [iter([b]) for b in out] if out else \
            [iter([]) for _ in range(n)]

    def partitions(self, ctx):
        from spark_rapids_tpu.fault import inject
        inject.maybe_fire("exchange")
        if self._mesh_active(ctx):
            return self._mesh_partitions(ctx)
        n = self.partitioning.num_partitions
        in_parts = self.children[0].partitions(ctx)
        if self._collapse_local(ctx):
            # one logical partition holding every input batch (with any
            # absorbed map stages applied as one fused program per batch);
            # no pid computation, no split, no sampling, no host syncs
            self._ensure_fused_map()

            def gen():
                for part in in_parts:
                    for db in part:
                        yield self._fused_map(ctx, db) \
                            if self._fused_map else db

            return [gen()]
        all_batches: List[List[ColumnBatch]] = [list(p) for p in in_parts]
        if isinstance(self.partitioning, RangePartitioning):
            self.partitioning.prepare(
                _sample_device_keys(all_batches,
                                    self.partitioning.key_ordinals,
                                    _range_sample_limit(ctx)))
        if isinstance(self.partitioning, SinglePartitioning):
            flat = [b for part in all_batches for b in part]
            return [iter(flat)]
        from spark_rapids_tpu.runtime.device import DeviceRuntime
        # Shuffle outputs accumulate across ALL partitions before any
        # consumer runs — exactly the working set the reference keeps in the
        # spillable shuffle catalog (RapidsShuffleInternalManager.scala:
        # 91-154, ShuffleBufferCatalog).  Register every piece so the budget
        # can push early partitions to host while later ones materialize.
        #
        # The split is memoized per query context: a task RETRY re-reads
        # the already-materialized (spillable) pieces instead of re-running
        # the whole upstream subtree — the role persisted shuffle files
        # play for Spark's task retry.  Handles stay open until the query
        # ends (ctx.close_deferred).  The cache holds the ctx via weakref:
        # exec nodes live as long as the session's plan cache, and a strong
        # ref would pin a finished query's whole object graph.
        # Generation-checked (fault.recovery): a device-lost reset bumps
        # the runtime generation, so a partition REPLAY recomputes the
        # split from lineage instead of draining pieces whose device
        # copies died with the old device.
        import weakref
        gen = DeviceRuntime.generation()
        cached = getattr(self, "_split_cache", None)
        if cached is not None and cached[0]() is ctx and cached[2] == gen:
            return [self._drain_cached(p) for p in cached[1]]
        catalog = DeviceRuntime.get(ctx.conf).catalog
        from spark_rapids_tpu.batch import (
            fixed_row_bytes, varlen_byte_scales,
        )
        from spark_rapids_tpu.config import SHUFFLE_SPLIT_V2
        frb = fixed_row_bytes(self.output_schema)
        vscales = varlen_byte_scales(self.output_schema)
        out: List[List] = [[] for _ in range(n)]
        with span("exchange", "split", self.op_id) as sp:
            if SHUFFLE_SPLIT_V2.get(ctx.conf):
                self._split_v2(ctx, all_batches, n, catalog, frb, vscales,
                               out)
            else:
                self._split_v1(ctx, all_batches, n, catalog, frb, vscales,
                               out)
            ctx.metric(self.op_id, "shufflePieces").add(
                sum(len(p) for p in out))
            # downstream AQE coalescing reads these instead of unspilling
            # batches just to count rows (GpuCustomShuffleReaderExec's use
            # of map-status sizes)
            self._last_part_rows = [sum(h.piece_rows for h in p)
                                    for p in out]
            self._last_part_bytes = [sum(h.piece_bytes for h in p)
                                     for p in out]
            # write-side shuffle metrics (single-host split path).  Wall
            # time covers pid-sort + the count sync(s); the final piece
            # gathers may still be in flight (async dispatch), so this is a
            # lower bound on split cost, not an upper
            ctx.metric(self.op_id, "shuffleBytes").add(
                sum(self._last_part_bytes))
            ctx.metric(self.op_id, "shuffleRows").add(
                sum(self._last_part_rows))
            sp.set(bytes=sum(self._last_part_bytes),
                   rows=sum(self._last_part_rows),
                   pieces=sum(len(p) for p in out), partitions=n)
        # planner-error accounting: the static size estimate the planner
        # used for this exchange's input (stashed by overrides) vs. the
        # actual materialized bytes just recorded — pure host arithmetic
        # on numbers the split's own sync fetched, no extra round trip
        est = getattr(self, "_aqe_est_bytes", None)
        if est is not None:
            actual = sum(self._last_part_bytes)
            pct = abs(est - actual) * 100.0 / max(actual, 1)
            ctx.metric(self.op_id, "aqeEstimateErrorPct").add(pct)
        self._split_cache = (weakref.ref(ctx), out, gen)
        return [self._drain_cached(p) for p in out]

    def _split_v2(self, ctx, all_batches, n, catalog, frb, vscales, out):
        """One-sync coalescing split: (1) dispatch the fused pid-sort
        program for EVERY input batch (nothing blocks, so B programs
        overlap on device); (2) fetch every batch's per-target counts and
        varlen byte totals in ONE bulk device_get (the host_sizes
        pattern); (3) assemble each target partition from ALL sorted
        batches with one k-way segment-gather dispatch — <=N pieces and
        ~B+N dispatches where the v1 path paid B syncs and B*(1+N)
        dispatches.  Spill-budget-aware: a partition whose coalesced size
        exceeds splitCoalesceMaxBytes falls back to per-batch pieces so
        the catalog can still spill early pieces independently."""
        from spark_rapids_tpu.batch import round_up_capacity
        from spark_rapids_tpu.config import (
            SHUFFLE_COALESCE_MAX_BYTES, SHUFFLE_DICT_AWARE,
        )
        from spark_rapids_tpu.kernels.layout import gather_segments_kway_run
        from spark_rapids_tpu.mem.catalog import PRIORITY_SHUFFLE_OUTPUT
        bound_words = None
        if isinstance(self.partitioning, RangePartitioning):
            # one batched H2D + one encode for ALL N-1 bounds; the word
            # arrays ride the jitted pid-sort as traced arguments
            bound_words = self.partitioning.encode_bounds_device()
        # dict-aware split (docs/shuffle.md): when any input column is
        # dictionary-encoded, the pid-sort permutes 4-byte codes and the
        # piece gather merges dictionaries instead of materializing string
        # bytes — decided BEFORE dispatch because it is a static arg of
        # the sort program (one cache key per mode, stable per query)
        keep_enc = SHUFFLE_DICT_AWARE.get(ctx.conf) and any(
            c.codes is not None
            for batches in all_batches for db in batches
            for c in db.columns)
        sorted_all = []
        for pi, batches in enumerate(all_batches):
            for db in batches:
                sorted_all.append(self._sort_by_pid(
                    db, pi, n, bound_words, keep_encoded=keep_enc))
                ctx.metric(self.op_id, "shuffleSplitDispatches").add(1)
        if not sorted_all:
            return
        host = device_read("split_counts",
                           [(c, bt) for _, c, bt in sorted_all], self.op_id)
        ctx.metric(self.op_id, "shuffleSyncs").add(1)
        counts_h = [np.asarray(c, dtype=np.int64) for c, _ in host]
        bytes_h = [[np.asarray(b, dtype=np.int64) for b in bt]
                   for _, bt in host]
        starts_h = [np.concatenate(([0], np.cumsum(c)))[:n]
                    for c in counts_h]
        cap_bytes = SHUFFLE_COALESCE_MAX_BYTES.get(ctx.conf)
        varlen_idx = [i for i, f in enumerate(self.output_schema.fields)
                      if f.dtype.is_string or f.dtype.is_array]

        def _col_encoded(ci, group):
            # encoded output requires EVERY contributing part encoded
            # (gather_segments_kway materializes mixed columns)
            return keep_enc and all(
                sorted_all[b][0].columns[varlen_idx[ci]].codes is not None
                for b in group)

        def _hbm_bytes(group, p, rows):
            # actual piece footprint: codes + dictionary buffers for
            # encoded columns, materialized elements otherwise — encoded
            # columns shrink the coalescing budget's view of a piece, so
            # more batches coalesce under the same cap
            t = rows * frb
            for ci, sc in enumerate(vscales):
                if _col_encoded(ci, group):
                    t += 4 * rows + sum(
                        int(sorted_all[b][0]
                            .columns[varlen_idx[ci]].data.shape[0])
                        for b in group)
                else:
                    t += sum(int(bytes_h[b][ci][p]) for b in group) * sc
            return t

        saved_total = 0
        for p in range(n):
            segs = [b for b in range(len(sorted_all))
                    if counts_h[b][p] > 0]
            if not segs:
                continue
            total_rows = sum(int(counts_h[b][p]) for b in segs)
            total_bytes = _hbm_bytes(segs, p, total_rows)
            if cap_bytes > 0 and total_bytes > cap_bytes and len(segs) > 1:
                groups = [[b] for b in segs]
            else:
                groups = [segs]
            for group in groups:
                rows = sum(int(counts_h[b][p]) for b in group)
                elems = [sum(int(bytes_h[b][ci][p]) for b in group)
                         for ci in range(len(vscales))]
                pcap = round_up_capacity(rows)
                # encoded columns: the slot is the OUTPUT mat_byte_cap —
                # same bucket of the same materialized total the plain
                # path would allocate, so downstream sizing is identical
                bcaps = [round_up_capacity(max(e, 16), minimum=16)
                         for e in elems]
                piece = gather_segments_kway_run(
                    [sorted_all[b][0] for b in group],
                    [int(starts_h[b][p]) for b in group],
                    [int(counts_h[b][p]) for b in group],
                    pcap, bcaps or None, keep_encoded=keep_enc)
                ctx.metric(self.op_id, "shuffleSplitDispatches").add(1)
                for ci, sc in enumerate(vscales):
                    if _col_encoded(ci, group):
                        wire = 4 * rows + sum(
                            int(sorted_all[b][0]
                                .columns[varlen_idx[ci]].data.shape[0])
                            for b in group)
                        saved_total += max(0, elems[ci] * sc - wire)
                h = catalog.register(piece, PRIORITY_SHUFFLE_OUTPUT)
                h.piece_rows = rows  # host-known: no sync for AQE sizing
                # piece_bytes stays the MATERIALIZED size either way, so
                # AQE coalescing decisions are bit-identical to encoded-off
                h.piece_bytes = rows * frb + sum(
                    e * sc for e, sc in zip(elems, vscales))
                ctx.defer_close(h)
                out[p].append(h)
        if keep_enc:
            ctx.metric(self.op_id, "shuffleEncodedBytesSaved").add(
                saved_total)

    def _split_v1(self, ctx, all_batches, n, catalog, frb, vscales, out):
        """Legacy per-batch split (one count sync per batch, one gather
        dispatch per (batch, target) pair) — kept behind
        splitV2.enabled=false as the bit-parity oracle for the coalescing
        engine."""
        from spark_rapids_tpu.batch import round_up_capacity
        from spark_rapids_tpu.mem.catalog import PRIORITY_SHUFFLE_OUTPUT
        for pi, batches in enumerate(all_batches):
            for db in batches:
                sorted_batch, counts, byte_totals = \
                    self._sort_by_pid(db, pi, n) \
                    if not isinstance(self.partitioning,
                                      RangePartitioning) \
                    else self._sort_by_pid_impl(db, pi, n)
                ctx.metric(self.op_id, "shuffleSplitDispatches").add(1)
                counts_h, bytes_h = device_read(
                    "split_counts", (counts, list(byte_totals)), self.op_id)
                counts_h = np.asarray(counts_h)
                bytes_h = [np.asarray(b) for b in bytes_h]
                ctx.metric(self.op_id, "shuffleSyncs").add(1)
                offset = 0
                for p in range(n):
                    cnt = int(counts_h[p])
                    if cnt == 0:
                        continue
                    pcap = round_up_capacity(cnt)
                    idx = offset + jnp.arange(pcap, dtype=jnp.int32)
                    bcaps = [round_up_capacity(max(int(bh[p]), 16),
                                               minimum=16)
                             for bh in bytes_h]
                    piece = gather_rows(sorted_batch, idx,
                                        jnp.asarray(cnt, jnp.int32),
                                        out_capacity=pcap,
                                        out_byte_caps=bcaps or None)
                    ctx.metric(self.op_id, "shuffleSplitDispatches").add(1)
                    h = catalog.register(piece, PRIORITY_SHUFFLE_OUTPUT)
                    h.piece_rows = cnt  # host-known: no sync for AQE sizing
                    h.piece_bytes = cnt * frb + \
                        sum(int(bh[p]) * sc
                            for bh, sc in zip(bytes_h, vscales))
                    ctx.defer_close(h)
                    out[p].append(h)
                    offset += cnt

    def _drain_cached(self, handles):
        # lazy, with ONE piece of read-ahead: when piece i is yielded,
        # piece i+1's unspill (an async H2D enqueue) is already in flight,
        # so the consumer's compute overlaps the next transfer.  Handles
        # stay registered (spillable + retry-reusable) until the query
        # closes them.  The overlap loop itself lives on the catalog
        # (prefetch) — shared with the cached-scan drive path.
        from spark_rapids_tpu.plan.physical import prefetch_spillables
        obs_events.emit_instant("exchange", "drain", self.op_id,
                                pieces=len(handles))
        return prefetch_spillables(handles)


def _mesh_partitioning(p: Partitioning, n: int) -> Partitioning:
    """Clone a partitioning with num_partitions = mesh device count, so one
    output partition maps to one device (range order and hash co-location
    are preserved by re-keying, not by folding pids mod n)."""
    if isinstance(p, HashPartitioning):
        return HashPartitioning(p.keys, n)
    if isinstance(p, RoundRobinPartitioning):
        return RoundRobinPartitioning(n)
    if isinstance(p, RangePartitioning):
        return RangePartitioning(p.orders, p.key_ordinals, n)
    return p  # SinglePartitioning


def _sample_device_keys(all_batches: List[List[ColumnBatch]],
                        key_ordinals: List[int],
                        limit: int) -> List[tuple]:
    """Sample <= ``limit`` key rows for range-bound computation.

    The keys are gathered down to the sample size ON DEVICE before any
    transfer: one bulk metadata get (num_rows + varlen offsets — bytes
    proportional to row count, not payload), then a right-sized head
    gather per contributing batch, then ONE bulk D2H for all gathered
    sub-batches.  The old path device_to_host'd every FULL batch (values
    included) just to read the first rows."""
    from spark_rapids_tpu.batch import device_to_host_many, round_up_capacity
    from spark_rapids_tpu.kernels.layout import dict_decode_column
    rows: List[tuple] = []
    # dict-encoded key columns (encoded corridor) materialize up front:
    # the offsets metadata below must describe ROW offsets, and bounds
    # need string content regardless
    subs = [ColumnBatch(
                T.Schema([db.schema.fields[i] for i in key_ordinals]),
                [dict_decode_column(c) if c.codes is not None else c
                 for c in (db.columns[i] for i in key_ordinals)],
                db.num_rows, db.capacity)
            for batches in all_batches for db in batches]
    if not subs:
        return rows
    meta = device_read("range_sample", [
        (b.num_rows, [c.offsets for c in b.columns if c.is_varlen])
        for b in subs])
    gathered = []
    remaining = limit
    for sub, (nr, off_arrays) in zip(subs, meta):
        if remaining <= 0:
            break
        take = min(int(nr), remaining)
        if take <= 0:
            continue
        pcap = round_up_capacity(take)
        bcaps = [round_up_capacity(max(int(offs[take]), 16), minimum=16)
                 for offs in off_arrays]
        gathered.append(gather_rows(
            sub, jnp.arange(pcap, dtype=jnp.int32),
            jnp.asarray(take, jnp.int32),
            out_capacity=pcap, out_byte_caps=bcaps or None))
        remaining -= take
    for hb in device_to_host_many(gathered):
        cols = [c.to_list() for c in hb.columns]
        for r in range(hb.num_rows):
            rows.append(tuple(c[r] for c in cols))
            if len(rows) >= limit:
                return rows
    return rows


class CpuBroadcastExchangeExec(CpuExec):
    """Materialize the whole child once; every consumer partition sees the
    same single host batch (driver-side broadcast analogue,
    GpuBroadcastExchangeExec.scala:53-135)."""

    def __init__(self, child: PhysicalOp):
        super().__init__([child], child.output_schema)
        self._cached = None

    def num_partitions(self, ctx):
        return 1

    def materialize(self, ctx) -> HostBatch:
        if self._cached is None:
            batches = []
            for p in self.children[0].partitions(ctx):
                batches.extend(p)
            if batches:
                self._cached = HostBatch.concat(batches)
            else:
                from spark_rapids_tpu.plan.physical import _empty_host_col
                self._cached = HostBatch(self.output_schema, [
                    _empty_host_col(f) for f in self.output_schema.fields
                ])
        return self._cached

    def partitions(self, ctx):
        return [iter([self.materialize(ctx)])]
