"""TPU physical operators: each one lowers its per-batch work to a jitted XLA
computation over the pytree :class:`ColumnBatch` (the analogue of the
reference's cudf-JNI calls inside ``doExecuteColumnar`` closures,
basicPhysicalOperators.scala:35-141, aggregate.scala:312, GpuSortExec.scala,
GpuHashJoin.scala).

jit granularity: one compiled program per (exec, schema, capacity-bucket).
Pipelines of Project/Filter ops fuse naturally because each exec's jit is
cheap to cache and XLA fuses elementwise chains into single kernels.
"""

from __future__ import annotations

import functools
from typing import Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import (
    ColumnBatch, DeviceColumn, HostBatch, empty_device_batch, host_to_device,
    round_up_capacity,
)
from spark_rapids_tpu.exprs.aggregates import AggregateExpression
from spark_rapids_tpu.exprs.base import (
    DevVal, Expression, SortOrder, TpuEvalCtx, column_as_it_is,
)
from spark_rapids_tpu.kernels.groupby import groupby_aggregate
from spark_rapids_tpu.kernels.join import (
    cross_join, hash_join, tally as join_tally,
)
from spark_rapids_tpu.kernels.layout import (
    compact, gather_rows, take_head,
)
from spark_rapids_tpu.kernels.sort import sort_batch
from spark_rapids_tpu.plan.physical import ExecContext, PhysicalOp, TpuExec
from spark_rapids_tpu.plan.pipeline import BatchProgram, _spec_of
from spark_rapids_tpu.utils.compile_registry import instrumented_jit, plan_jit
from spark_rapids_tpu.utils.tracing import device_read


def shrink_to_fit(batch: ColumnBatch,
                  sizes: Optional[tuple] = None) -> ColumnBatch:
    """Re-bucket a sparse batch down to its live-row count.

    The padded-capacity model means ops like filter/aggregate can leave
    batches with few live rows in huge buffers; every downstream kernel then
    pays O(capacity).  At pipeline breaks we pay one host sync + gather to
    move to the right power-of-two bucket — the CoalesceGoal/TargetSize
    analogue in reverse (GpuCoalesceBatches.scala).

    ``sizes`` is an optional pre-fetched (num_rows, [string byte totals])
    pair (see :func:`~spark_rapids_tpu.batch.host_sizes`) so callers
    shrinking many batches pay ONE round trip, not one per batch.
    """
    from spark_rapids_tpu.batch import host_sizes
    if sizes is None:
        sizes = host_sizes([batch])[0]
    n, str_totals = sizes
    cap = round_up_capacity(max(n, 1))
    if batch.capacity <= cap * 2:
        return batch
    byte_caps = [round_up_capacity(max(t, 16), minimum=16)
                 for t in str_totals]
    idx = jnp.arange(cap, dtype=jnp.int32)
    return gather_rows(batch, idx, jnp.asarray(n, jnp.int32),
                       out_capacity=cap, out_byte_caps=byte_caps or None)


def _reserve_for(ctx, batches: List[ColumnBatch], factor: int = 2) -> None:
    """Budget headroom before a large concat/gather: ask the catalog to
    evict lower-priority spillable batches so input + output fit
    (SpillableColumnarBatch.scala:27 callers' reserve pattern)."""
    if not batches:
        return
    from spark_rapids_tpu.mem.catalog import device_batch_bytes
    from spark_rapids_tpu.runtime.device import DeviceRuntime
    total = sum(device_batch_bytes(b) for b in batches)
    DeviceRuntime.get(ctx.conf).catalog.reserve(factor * total)


def _release_build_staging(ctx: ExecContext, depth0: int) -> None:
    """Give back the H2D admission permits taken while materializing a
    catalog-registered build side.  Build batches park in the spill
    catalog instead of flowing on to DeviceToHostExec, so the release
    that normally pairs each staging acquire never happens — without
    this give-back the task-wide hold depth leaks for the process
    lifetime, silently shrinking device admission for every later
    query.  The pipeline collect counts H2D acquires in
    ``ctx._pipeline_h2d`` and releases that many in its finally, so the
    count is walked back by the same amount."""
    sem = ctx.semaphore
    if sem is None:
        return
    extra = max(0, sem.task_depth() - depth0)
    for _ in range(extra):
        sem.release()
    if extra and hasattr(ctx, "_pipeline_h2d"):
        ctx._pipeline_h2d = max(0, ctx._pipeline_h2d - extra)


def _shrink_keeping_codes(batches, caps, bcapss):
    """Each batch re-bucketed to its own ``(row cap, varlen byte caps)``,
    a dictionary-encoded column keeping its codes: a prefix gather cannot
    grow what the column materializes to, and that bound is now the
    host-read total's bucket, not the source batch's (a decode costs its
    ``mat_byte_cap``, whatever is live)."""
    out = []
    for b, cap, bcaps in zip(batches, caps, bcapss):
        g = gather_rows(b, jnp.arange(cap, dtype=jnp.int32), b.num_rows,
                        out_capacity=cap, out_byte_caps=list(bcaps) or None,
                        keep_encoded=True)
        varlen = iter(bcaps)
        cols = []
        for c in g.columns:
            bcap = next(varlen) if c.is_varlen else 0
            cols.append(c if c.codes is None else DeviceColumn(
                c.dtype, c.data, c.validity, c.offsets, c.codes, bcap))
        out.append(ColumnBatch(g.schema, cols, g.num_rows, g.capacity))
    return tuple(out)


_shrink_keeping_codes_jit = instrumented_jit(
    _shrink_keeping_codes, label="kernels:shrinkSparse",
    static_argnames=("caps", "bcapss"))


def _concat_all(batches: List[ColumnBatch], schema: T.Schema,
                sizes: Optional[List[tuple]] = None
                ) -> Optional[ColumnBatch]:
    """Concatenate a partition's batches into one (RequireSingleBatch goal,
    GpuCoalesceBatches.scala:105-110).  Sizes the output by host-visible
    totals, fetched in ONE round trip for all batches (or passed in
    pre-fetched via ``sizes``); the k-way kernel then writes every input
    once into a single output allocation and the whole concat rides ONE
    compiled dispatch (the pairwise chain dispatched an eager op storm
    and materialized k-1 growing intermediates)."""
    if not batches:
        return None
    if len(batches) == 1:
        return batches[0]
    from spark_rapids_tpu.batch import colocate_batches, host_sizes
    from spark_rapids_tpu.kernels.layout import concat_kway_run
    batches = list(colocate_batches(batches))
    if sizes is None:
        sizes = host_sizes(batches)
    # a filter's output keeps its input's capacity: the concat (and the
    # decode of every encoded column inside it) would pay for a million
    # rows a batch to move the few thousand that passed.  The sizes are
    # on the host already, so the sparse inputs are re-bucketed first, in
    # one program, codes kept (TPC-H Q12: six lineitem batches of 5 k rows
    # in million-row buffers, 1.4 s a decode: PERF.md, PR 36)
    specs = _spec_of(sizes)
    sparse = [i for i, (b, (cap, _)) in enumerate(zip(batches, specs))
              if b.capacity > 2 * cap]
    if sparse:
        shrunk = _shrink_keeping_codes_jit(
            tuple(batches[i] for i in sparse),
            caps=tuple(specs[i][0] for i in sparse),
            bcapss=tuple(specs[i][1] for i in sparse))
        for i, b in zip(sparse, shrunk):
            batches[i] = b
    total_rows = sum(n for n, _ in sizes)
    cap = round_up_capacity(max(total_rows, 1))
    n_str = sum(1 for f in schema.fields
                if f.dtype.is_string or f.dtype.is_array)
    byte_caps = [
        round_up_capacity(max(sum(s[1][j] for s in sizes), 16), minimum=16)
        for j in range(n_str)
    ]
    return concat_kway_run(batches, cap, out_byte_caps=byte_caps or None)


def _concat_sized(batches: List[ColumnBatch], schema: T.Schema):
    """:func:`_concat_all` and the rows the host learnt on the way: the
    concatenation of two or more batches reads their sizes, a side that
    arrived as one batch (or none) is handed on unread — ``None``."""
    if len(batches) < 2:
        return _concat_all(batches, schema), None
    from spark_rapids_tpu.batch import colocate_batches, host_sizes
    batches = list(colocate_batches(batches))
    sizes = host_sizes(batches)
    return _concat_all(batches, schema, sizes), sum(n for n, _ in sizes)


def _counted_hash_join(ctx: ExecContext, op_id: str, lb, lkeys, rb, rkeys,
                       how: str, schema: T.Schema, condition,
                       probe_rows: Optional[int] = None,
                       build_rows: Optional[int] = None) -> ColumnBatch:
    """``hash_join`` (the right side is the build: sorted by key hash; the
    left probes it) with what it read back added to the operator's
    metrics: ``joinPairs`` and ``joinSizeReads`` from the kernel's own
    tally, ``joinProbeRows`` / ``joinBuildRows`` where the host already
    holds a side's row count (it concatenated the side); no read of its
    own."""
    with join_tally() as t:
        out = hash_join(lb, lkeys, rb, rkeys, how, schema,
                        condition=condition)
    ctx.metric(op_id, "joinPairs").add(t.pairs)
    ctx.metric(op_id, "joinSizeReads").add(t.reads)
    ctx.metric(op_id, "joinProbeRows").add(probe_rows or 0)
    ctx.metric(op_id, "joinBuildRows").add(build_rows or 0)
    return out


class TpuRangeExec(TpuExec):
    """GpuRangeExec analogue: generates ids directly in HBM."""

    def __init__(self, start, end, step, num_parts, schema: T.Schema):
        super().__init__([], schema)
        self.start, self.end, self.step = start, end, step
        self._n = max(1, num_parts)

    def num_partitions(self, ctx):
        return self._n

    def partitions(self, ctx):
        total = max(0, -(-(self.end - self.start) // self.step))
        per = -(-total // self._n)
        max_batch = 1 << 20

        def gen(p):
            lo_i = self.start + p * per * self.step
            count = max(0, min(per, total - p * per))
            done = 0
            while done < count:
                n = min(max_batch, count - done)
                cap = round_up_capacity(n)
                start = lo_i + done * self.step
                data = start + jnp.arange(cap, dtype=jnp.int64) * self.step
                col = DeviceColumn(T.LONG, data,
                                   jnp.arange(cap, dtype=jnp.int32) < n, None)
                yield ColumnBatch(self.output_schema, [col],
                                  jnp.asarray(n, jnp.int32), cap)
                done += n

        return [gen(p) for p in range(self._n)]


class TpuProjectExec(TpuExec):
    def __init__(self, exprs: List[Expression], child: PhysicalOp,
                 schema: T.Schema):
        super().__init__([child], schema)
        self.exprs = exprs

        def run(batch: ColumnBatch) -> ColumnBatch:
            ctx = TpuEvalCtx(batch)
            # a column that is only carried on stays as it came (encoded
            # where it was: what the projection hands on is what a scan
            # or a filter hands on, and consumers hold to the same rule)
            cols = [column_as_it_is(e, ctx) or e.tpu_eval(ctx).to_column()
                    for e in self.exprs]
            return ColumnBatch(schema, cols, batch.num_rows, batch.capacity)

        self.batch_fn = run
        self._run = plan_jit(run, label="TpuProject")

    def describe(self):
        return f"TpuProject({', '.join(f.name for f in self.output_schema)})"

    def pipeline_inline(self, ctx, build):
        cf = build(self.children[0])
        return lambda args: [self.batch_fn(b) for b in cf(args)]

    def partitions(self, ctx):
        return [map(self._run, p)
                for p in self.children[0].partitions(ctx)]


class TpuFilterExec(TpuExec):
    """Keeps the rows that pass ``condition`` by compacting the batch
    (kernels/layout.compact: a gather of every column).  ``batch_fn``
    goes wherever the planner fuses the filter (a consumer's absorbed
    input, a fused map), so it is what notes the compaction to the stage
    program that traces it (metric ``filterCompactedBatches``); a filter
    moved into a keyless aggregate's arguments never becomes this
    operator and compacts nothing.

    Encode-transparent: a dictionary-encoded string column leaves as it
    came, its 4-byte codes compacted and its dictionary buffers shared
    (a filter cannot grow the materialized total), so an encode-aware
    consumer above — a group key, a dict-key join, the dict-aware
    shuffle — still finds the codes.  What the filter hands on is what a
    scan hands on, and is held to the same rule: a consumer that is not
    encode-aware materializes at its own entry (``DevVal.from_column``
    for every expression, ``ensure_row_layout`` in the row-movement and
    join kernels, ``device_to_host`` at collection); the filter decodes
    nothing on anyone's behalf."""

    def __init__(self, condition: Expression, child: PhysicalOp):
        super().__init__([child], child.output_schema)
        self.condition = condition

        def run(batch: ColumnBatch) -> ColumnBatch:
            from spark_rapids_tpu.plan.pipeline import note_stage_batches
            ctx = TpuEvalCtx(batch)
            v = self.condition.tpu_eval(ctx)
            keep = v.validity & v.data.astype(jnp.bool_)
            note_stage_batches(self, 1)
            return compact(batch, keep, keep_encoded=True)

        self.batch_fn = run
        self._run = BatchProgram(run, "TpuFilter")

    def describe(self):
        return f"TpuFilter({self.condition!r})"

    def stage_ran(self, ctx, batches: int, speculated: bool) -> None:
        """A stage run that stands compacted ``batches`` batches here."""
        ctx.metric(self.op_id, "filterCompactedBatches").add(batches)

    def pipeline_inline(self, ctx, build):
        cf = build(self.children[0])
        return lambda args: [self.batch_fn(b) for b in cf(args)]

    def partitions(self, ctx):
        return [(self._run(ctx, b) for b in p)
                for p in self.children[0].partitions(ctx)]


class TpuUnionExec(TpuExec):
    def __init__(self, children: List[PhysicalOp], schema: T.Schema):
        super().__init__(children, schema)

    def num_partitions(self, ctx):
        return sum(c.num_partitions(ctx) for c in self.children)

    def pipeline_inline(self, ctx, build):
        cfs = [build(c) for c in self.children]

        def f(args):
            out = []
            for cf in cfs:
                for b in cf(args):
                    out.append(ColumnBatch(self.output_schema, b.columns,
                                           b.num_rows, b.capacity))
            return out

        return f

    def partitions(self, ctx):
        out = []
        for c in self.children:
            for p in c.partitions(ctx):
                out.append(self._rename(p))
        return out

    def _rename(self, part):
        for db in part:
            yield ColumnBatch(self.output_schema, db.columns, db.num_rows,
                              db.capacity)


class TpuCoalesceBatchesExec(TpuExec):
    """Concat small batches up to the target row goal
    (GpuCoalesceBatches.scala:115; the hot path for downstream op
    efficiency)."""

    def __init__(self, child: PhysicalOp, target_rows: int = 1 << 20):
        super().__init__([child], child.output_schema)
        self.target_rows = target_rows

    def pipeline_inline(self, ctx, build):
        # inside one compiled program batches are virtual — coalescing
        # is a no-op (consumers concat statically where they need to)
        return build(self.children[0])

    def partitions(self, ctx):
        def gen(part):
            pending: List[ColumnBatch] = []
            pending_rows = 0
            for db in part:
                n = db.host_num_rows()
                if n == 0:
                    continue
                if pending_rows + n > self.target_rows and pending:
                    out = _concat_all(pending, self.output_schema)
                    if out is not None:
                        yield out
                    pending, pending_rows = [], 0
                pending.append(db)
                pending_rows += n
            out = _concat_all(pending, self.output_schema)
            if out is not None:
                yield out

        return [gen(p) for p in self.children[0].partitions(ctx)]


# The adaptive planning logic itself (grouping rule, skew detection,
# stat accounting, legal broadcast sides) lives in plan/adaptive; these
# module-level aliases keep the historical import surface of this module
# stable (tests and tooling import the grouping rule from here).
from spark_rapids_tpu.plan import adaptive as _adaptive  # noqa: E402

_aqe_part_stats = _adaptive.part_stats
_aqe_target_rows = _adaptive.target_rows
_aqe_target_bytes = _adaptive.target_bytes
_aqe_target_for = _adaptive.target_for
_group_by_target = _adaptive.group_by_target
_coalesce_partition_lists = _adaptive.coalesce_partition_lists


def _aqe_enabled(ctx) -> bool:
    """Gate for the coalescing consumers (reader / agg merge / join pair
    grouping): the adaptive master switch AND the legacy coalesce conf."""
    return _adaptive.coalesce_enabled(ctx)


class TpuCoalescedShuffleReaderExec(TpuExec):
    """AQE-style post-shuffle partition coalescing as a general plan
    operator (GpuCustomShuffleReaderExec analogue): groups small
    post-exchange partitions so each downstream task covers a worthwhile
    row count.  The planner inserts it above exchanges feeding sort and
    window; the hash aggregate and shuffled join coalesce inline (they
    reuse the size fetch for output sizing)."""

    def __init__(self, child: PhysicalOp):
        super().__init__([child], child.output_schema)

    def describe(self):
        return "TpuCoalescedShuffleReader"

    def pipeline_inline(self, ctx, build):
        # inside one compiled program partitioning is virtual
        return build(self.children[0])

    def num_partitions(self, ctx):
        return self.children[0].num_partitions(ctx)

    def partitions(self, ctx):
        import itertools
        child = self.children[0]
        lazy_parts = child.partitions(ctx)
        if not _aqe_enabled(ctx) or len(lazy_parts) <= 1:
            return lazy_parts
        sizes, unit = _aqe_part_stats(child, len(lazy_parts))
        if sizes is not None:
            # spill-friendly path: sizes came with the shuffle (no unspill
            # just to count rows); chain the lazy generators per group.
            # Skewed partitions stay ALONE (their per-source pieces stream
            # through un-merged rather than dragging neighbors into one
            # giant downstream task).
            groups, _gflags = _adaptive.plan_groups(
                ctx, self.op_id, lazy_parts, sizes, unit)
            ctx.metric(self.op_id, "coalescedTo").add(len(groups))
            return [itertools.chain(*g) for g in groups]
        parts = [list(p) for p in lazy_parts]
        from spark_rapids_tpu.batch import host_sizes
        flat = [b for p in parts for b in p]
        if not flat:
            return [iter([])]
        flat_sizes = host_sizes(flat)
        by_id = {id(b): s[0] for b, s in zip(flat, flat_sizes)}
        sizes = [sum(by_id[id(b)] for b in p) for p in parts]
        groups = _coalesce_partition_lists(parts, sizes,
                                           _aqe_target_rows(ctx))
        ctx.metric(self.op_id, "coalescedTo").add(len(groups))
        return [iter(g) for g in groups]


class TpuFusedMapExec(TpuExec):
    """A chain of map-like stages (project/filter) compiled as ONE XLA
    program per batch.  Collapsing dispatch count matters doubly on TPU:
    host->device dispatch latency amortizes, and XLA fuses the whole chain
    into a single HBM pass (the role GpuCoalesceBatches + JIT fusion play
    for the reference's per-op cudf calls)."""

    def __init__(self, child: PhysicalOp, fns, schema: T.Schema,
                 labels: List[str]):
        super().__init__([child], schema)
        self.fns = list(fns)
        self.labels = labels

        def composed(batch: ColumnBatch) -> ColumnBatch:
            for f in self.fns:
                batch = f(batch)
            return batch

        self.batch_fn = composed
        self._run = BatchProgram(composed, "TpuFusedMap")

    def describe(self):
        return f"TpuFusedMap({' -> '.join(self.labels)})"

    def pipeline_inline(self, ctx, build):
        cf = build(self.children[0])
        return lambda args: [self.batch_fn(b) for b in cf(args)]

    def partitions(self, ctx):
        return [(self._run(ctx, b) for b in p)
                for p in self.children[0].partitions(ctx)]


class TpuLocalLimitExec(TpuExec):
    def __init__(self, n: int, child: PhysicalOp):
        super().__init__([child], child.output_schema)
        self.n = n

    def pipeline_inline(self, ctx, build):
        cf = build(self.children[0])

        def f(args):
            out = []
            left = jnp.asarray(self.n, jnp.int32)
            for b in cf(args):
                h = take_head(b, left)
                left = jnp.maximum(left - h.num_rows, 0)
                out.append(h)
            return out

        return f

    def partitions(self, ctx):
        def gen(part):
            left = self.n
            for db in part:
                if left <= 0:
                    break
                db = take_head(db, left)
                got = db.host_num_rows()
                left -= got
                if got:
                    yield db

        return [gen(p) for p in self.children[0].partitions(ctx)]


class TpuSortExec(TpuExec):
    """Whole-partition sort (cudf Table.orderBy analogue).  Requires a single
    batch, so it concats first — like the reference's RequireSingleBatch goal
    for global sorts (GpuSortExec.scala:50-98)."""

    def __init__(self, orders: List[SortOrder], key_exprs: List[Expression],
                 child: PhysicalOp, string_prefix_bytes: int = None):
        super().__init__([child], child.output_schema)
        self.orders = orders
        self.key_exprs = key_exprs
        self._input_fns = []
        if string_prefix_bytes is None:
            from spark_rapids_tpu.kernels.sort import \
                DEFAULT_STRING_PREFIX_BYTES
            string_prefix_bytes = DEFAULT_STRING_PREFIX_BYTES
        self.string_prefix_bytes = string_prefix_bytes

        def run(batch: ColumnBatch) -> ColumnBatch:
            for f in self._input_fns:
                batch = f(batch)
            ctx = TpuEvalCtx(batch)
            vals = [e.tpu_eval(ctx) for e in self.key_exprs]
            return sort_batch(batch, vals,
                              [o.ascending for o in self.orders],
                              [o.nulls_first for o in self.orders],
                              string_prefix_bytes=self.string_prefix_bytes)

        self._run = plan_jit(run, label="TpuSort")

    def absorb_input(self, fns):
        # project/filter commute with concat (row-wise / stable), so fused
        # stages run once on the merged batch
        self._input_fns = list(fns)

    def describe(self):
        return f"TpuSort({len(self.orders)} keys)"

    def pipeline_inline(self, ctx, build):
        from spark_rapids_tpu.plan.pipeline import concat_static
        cf = build(self.children[0])

        def f(args):
            batches = cf(args)
            if not batches:
                return []
            return [self._run(concat_static(batches, self.output_schema))]

        return f

    def partitions(self, ctx):
        def gen(part):
            batches = list(part)
            _reserve_for(ctx, batches)
            merged = _concat_all(batches, self.output_schema)
            if merged is not None:
                yield self._run(merged)

        return [gen(p) for p in self.children[0].partitions(ctx)]


def _buffer_schema(key_names: List[str], keys: List[Expression],
                   aggs: List[AggregateExpression]) -> T.Schema:
    fields = [T.Field(n, e.dtype, e.nullable)
              for n, e in zip(key_names, keys)]
    for i, a in enumerate(aggs):
        for j, spec in enumerate(a.fn.buffers()):
            fields.append(T.Field(f"__buf_{i}_{j}", spec.dtype, True))
    return T.Schema(fields)


class TpuHashAggregateExec(TpuExec):
    """Groupby aggregation, two-mode (update/merge) like the reference's
    Partial/Final plumbing (aggregate.scala:420-524).

    mode="update": raw rows -> per-partition partial batch
                   (group keys + agg buffers).
    mode="merge":  partial batches (post-exchange) -> merged groups ->
                   finalized output projection.

    Two forms of the grouping, one partial layout.  The sort form
    (kernels/groupby: argsort by key, segment, reduce) takes any key and
    is every merge.  A keyed update whose aggregates are inside
    ``hash_agg_capable`` and whose keys can each be a digit takes the slot
    contraction instead (kernels/hashagg.hash_group_aggregate; metric
    ``mxuAggBatches`` of the ``keyedUpdateBatches`` it saw): an
    integral/date/bool key by its value, a string key by its dictionary
    codes.  Whether a string key still carries codes is not a property of
    the plan (a format, a projection or a join may have delivered row
    layout), so it is asked of each batch as its program is traced
    (``hashagg.keys_are_digits``): a batch whose string key arrives plain
    takes the sort form, speculates nothing and raises no flag.

    No key expression (``sum(x)`` with no GROUP BY): one group, so neither
    mode groups anything.  An update batch is reduced
    (kernels/hashagg.keyless_aggregate; metric ``keylessAggBatches``, the
    slot path's ``mxuAggBatches`` stays 0) or, where the MXU gate is off or
    the aggregates are outside ``hash_agg_capable``, folded by the segment
    kernels over one segment without a sort (kernels/groupby); a merge is
    the latter.  Partials and the result are ONE row at ``MIN_CAPACITY``:
    there is nothing to re-bucket, so a keyless update is NOT a stage
    break — it is inlined into its consumer's stage program, and update,
    merge and the projection over them are one dispatch.

    An update on the fast path (slot contraction or reduction) speculates:
    a key range over the slot table or a NaN/Inf float input makes its
    result invalid.  In a stage program it says so through the pipeline's
    stage flags (plan/pipeline ``note_stage_batches``): the host reads the
    flag where the stage's outputs are handed on — beside the answer when
    the stage is the collected root — and answers through
    :meth:`stage_flagged` / :meth:`stage_ran`.
    """

    def __init__(self, mode: str, key_exprs: List[Expression],
                 key_names: List[str], aggs: List[AggregateExpression],
                 child: PhysicalOp, schema: T.Schema):
        assert mode in ("update", "merge")
        super().__init__([child], schema)
        self.mode = mode
        # keyed partial outputs have far fewer live rows than capacity: end
        # the compiled stage here so the driver re-buckets before
        # downstream concats/sorts pay O(padded capacity).  Keyless
        # partials are one row at MIN_CAPACITY: nothing to re-bucket
        self.pipeline_stage_break = (mode == "update" and bool(key_exprs))
        self.key_exprs = key_exprs
        self.key_names = key_names
        self.aggs = aggs
        self.key_schema = T.Schema([
            T.Field(n, e.dtype, e.nullable)
            for n, e in zip(key_names, key_exprs)
        ])
        self.buffer_schemas = [[s.dtype for s in a.fn.buffers()]
                               for a in aggs]
        from spark_rapids_tpu.exprs.base import may_stay_encoded
        from spark_rapids_tpu.kernels.hashagg import hash_agg_capable
        # a computed string key is never encoded: such a plan names no
        # fast variant at all (no speculation, sources stay donatable)
        self._hash_capable = hash_agg_capable(
            mode, [e.dtype for e in key_exprs], [a.fn for a in aggs]) and \
            all(may_stay_encoded(e) for e in key_exprs if e.dtype.is_string)
        self._hash_disabled = False  # sticky off after a collided batch
        from spark_rapids_tpu.kernels.hashagg import TABLE_SLOTS
        self._mxu_table = TABLE_SLOTS  # refreshed from conf in _hash_active

        @plan_jit(label="TpuHashAggregate")
        def run(batch: ColumnBatch) -> ColumnBatch:
            return self._aggregate_batch(batch)

        @plan_jit(label="TpuHashAggregate:hash")
        def run_hash(batch: ColumnBatch):
            return self._aggregate_batch_hash(batch)

        self._run = run
        self._run_hash = run_hash
        # the merge input is always a fresh >1-way concat this exec built
        # (never a cached/spill-held batch) and is consumed here: donate
        # its buffers so concat + merge don't hold two full copies (a
        # process that cannot donate gets the jit without the donation:
        # compile_registry.instrumented_jit)
        self._merge_run = plan_jit(
            self._merge_partials, label="TpuHashAggregate:merge",
            donate_argnums=(0,))
        self._input_fns = []

    def absorb_input(self, fns):
        """Fuse upstream map-like stages (project/filter) into this exec's
        per-batch compiled program — one XLA dispatch instead of N
        (critical when dispatch latency is high; also lets XLA fuse
        elementwise work into the aggregation's sort pass)."""
        self._input_fns = list(fns)

        def run(batch: ColumnBatch) -> ColumnBatch:
            for f in self._input_fns:
                batch = f(batch)
            return self._aggregate_batch(batch)

        def run_hash(batch: ColumnBatch):
            for f in self._input_fns:
                batch = f(batch)
            return self._aggregate_batch_hash(batch)

        self._run = plan_jit(run, label="TpuHashAggregate")
        self._run_hash = plan_jit(run_hash,
                                          label="TpuHashAggregate:hash")

    def _hash_active(self, ctx) -> bool:
        from spark_rapids_tpu.config import (
            HASH_AGG_MXU_ENABLED, HASH_AGG_MXU_SLOTS,
        )
        if not (self._hash_capable and not self._hash_disabled and
                HASH_AGG_MXU_ENABLED.get(ctx.conf)):
            return False
        self._mxu_table = HASH_AGG_MXU_SLOTS.get(ctx.conf)
        return True

    def describe(self):
        return f"TpuHashAggregate({self.mode}, keys={len(self.key_exprs)})"

    def stage_variant(self, ctx) -> str:
        """This operator's part of the pipeline's stage key: a stage that
        holds an update compiles a hash-path and a sort-path program (the
        latter built on demand when a flagged batch forces the exact
        fallback); a merge compiles one way and names none."""
        if self.mode != "update":
            return ""
        return "hash" if self._hash_active(ctx) else "sort"

    def stage_may_rerun(self, ctx) -> bool:
        """A set flag re-dispatches the stage in the exact sort variant on
        the SAME materialized inputs — the pipeline must not donate them
        (plan/pipeline._stage_may_rerun)."""
        return self.mode == "update" and self._hash_active(ctx)

    def stage_flagged(self, ctx) -> None:
        """The stage's flag came back set for this update (key range over
        the slot table, NaN/Inf float inputs): the stage's outputs are
        discarded and the fast path turns off for this exec —
        correctness never depends on data shape."""
        self._hash_disabled = True
        ctx.metric(self.op_id, "hashAggFallback").add(1)

    def stage_ran(self, ctx, batches: int, speculated: bool) -> None:
        """A stage run that stands handled ``batches`` update batches
        here, on the fast path where ``speculated``."""
        self._count_update_batches(ctx, batches, fast=speculated)

    def pipeline_inline(self, ctx, build):
        from spark_rapids_tpu.plan.pipeline import (
            concat_static, note_stage_batches,
        )
        cf = build(self.children[0])
        child_schema = self.children[0].output_schema
        use_hash = self.mode == "update" and self._hash_active(ctx)

        def f(args):
            batches = cf(args)
            for fn in self._input_fns:  # absorbed map stages
                batches = [fn(b) for b in batches]
            if self.mode == "update":
                # Emit per-batch partials.  Keyed, they are the stage's
                # outputs: the stage break re-buckets them to live size
                # (one sizes sync), so the downstream merge sorts a few
                # thousand rows — merging here would concat at FULL padded
                # capacity and sort O(sum of input caps) rows inside the
                # program (seconds at 16M).  Keyless, they are one row
                # each and the consumer's merge follows in this program.
                if use_hash:
                    outs, fast, ncoll = [], 0, jnp.asarray(0, jnp.int32)
                    for b in batches:
                        p, fl = self._aggregate_batch_hash(b)
                        outs.append(p)
                        if fl is not None:
                            fast += 1
                            ncoll = ncoll + fl.astype(jnp.int32)
                    # batches whose string key arrived plain took the
                    # sort form: counted, and nothing of theirs to flag
                    if fast:
                        note_stage_batches(self, fast, ncoll)
                    if fast < len(batches):
                        note_stage_batches(self, len(batches) - fast)
                    return outs
                note_stage_batches(self, len(batches))
                return [self._aggregate_batch(b) for b in batches]
            if not batches:
                if self.key_exprs:
                    return []
                merged = empty_device_batch(child_schema)
            else:
                merged = concat_static(batches, child_schema)
            return [self._aggregate_batch(merged)]

        return f

    def _count_update_batches(self, ctx, n: int, fast: bool):
        """Update batches by the form that aggregated them: the slot
        contraction (``mxuAggBatches``, with keys) or the reduction
        (``keylessAggBatches``, without), of the ``keyedUpdateBatches`` /
        ``keylessUpdateBatches`` an aggregate with / without keys saw in
        all; ``fast`` False is the sort variant."""
        ctx.metric(self.op_id, "keyedUpdateBatches" if self.key_exprs
                   else "keylessUpdateBatches").add(n)
        if fast:
            ctx.metric(self.op_id, "mxuAggBatches" if self.key_exprs
                       else "keylessAggBatches").add(n)

    # -- core ---------------------------------------------------------------

    def _eval_keys(self, batch) -> List[DevVal]:
        if self.mode == "update":
            # String group keys stay dictionary-encoded when they arrive
            # that way (from a scan, or through filters and the dict-aware
            # shuffle above one): the slot contraction groups by the codes
            # themselves, and the sort-based grouping only needs
            # lengths/hashes/prefixes, all of which gather through the
            # codes, so the dictionary is hashed once instead of per row.
            from spark_rapids_tpu.exprs.base import eval_maybe_encoded
            ctx = TpuEvalCtx(batch)
            return [eval_maybe_encoded(e, ctx) if e.dtype.is_string
                    else e.tpu_eval(ctx) for e in self.key_exprs]
        # merge mode: keys are the leading child columns by position
        return [DevVal.from_column(batch.columns[i])
                for i in range(len(self.key_exprs))]

    @staticmethod
    def _eval_agg_input(fn, ctx) -> DevVal:
        # Count consumes only validity, so a dictionary-encoded string
        # child stays encoded — no byte materialization just to count rows
        from spark_rapids_tpu.exprs.aggregates import Count
        from spark_rapids_tpu.exprs.base import eval_maybe_encoded
        if type(fn) is Count and fn.child.dtype.is_string:
            return eval_maybe_encoded(fn.child, ctx)
        return fn.child.tpu_eval(ctx)

    def _partial_buffers(self, batch: ColumnBatch) -> List[DevVal]:
        """The partial-buffer columns of a batch of update-mode outputs,
        flat, in aggregate order (they follow the key columns)."""
        i = len(self.key_exprs)
        n = sum(len(bufs) for bufs in self.buffer_schemas)
        return [DevVal.from_column(c) for c in batch.columns[i:i + n]]

    def _output_batch(self, group_keys: ColumnBatch, buffers,
                       finalize: bool = False) -> ColumnBatch:
        """Group keys + per-aggregate buffers (or, finalized, results) as
        one batch at the capacity the kernel gave the keys.  No key: the
        kernel gave one row at ``MIN_CAPACITY`` — a reduction always emits
        exactly one row, and an empty input left it the identity buffers
        -> SQL defaults (count=0, sum=NULL...)."""
        cols = list(group_keys.columns)
        for a, bufs in zip(self.aggs, buffers):
            for b in ([a.fn.finalize(bufs)] if finalize else bufs):
                cols.append(DeviceColumn(b.dtype, b.data, b.validity,
                                         b.offsets))
        return ColumnBatch(self.output_schema, cols, group_keys.num_rows,
                           group_keys.capacity)

    def _aggregate_batch(self, batch: ColumnBatch) -> ColumnBatch:
        if self.mode == "update":
            ctx = TpuEvalCtx(batch)
            agg_inputs = [self._eval_agg_input(a.fn, ctx)
                          for a in self.aggs]
        else:
            agg_inputs = self._partial_buffers(batch)
        merge = self.mode == "merge"
        group_keys, buffers = groupby_aggregate(
            batch, self._eval_keys(batch), agg_inputs,
            [a.fn for a in self.aggs], merge, self.key_schema,
            self.buffer_schemas, self.output_schema)
        # merge mode finalizes each agg into its output column
        return self._output_batch(group_keys, buffers, finalize=merge)

    def _aggregate_batch_hash(self, batch: ColumnBatch):
        """(partial batch, fallback flag) — same output layout as the
        sort-based update path.  With grouping keys: the MXU slot kernel.
        With none there is one group and nothing to slot: the kernel's
        limb rows are reduced, not contracted (``keyless_aggregate``).
        flag=True means the result is INVALID (key range exceeded the slot
        table, or a float sum saw NaN/Inf) and the caller must re-run the
        sort path.  flag None: a string key of this batch arrived without
        codes, so the partial IS the sort form's and stands as it is."""
        from spark_rapids_tpu.kernels.hashagg import (
            hash_group_aggregate, keyless_aggregate, keys_are_digits,
        )
        key_vals = self._eval_keys(batch)
        if not keys_are_digits(key_vals):
            return self._aggregate_batch(batch), None
        ctx = TpuEvalCtx(batch)
        agg_inputs = [self._eval_agg_input(a.fn, ctx) for a in self.aggs]
        fns = [a.fn for a in self.aggs]
        if self.key_exprs:
            group_keys, buffers, _, flagged = hash_group_aggregate(
                batch, key_vals, agg_inputs, fns,
                self.key_schema, self.output_schema, table=self._mxu_table)
        else:
            group_keys, buffers, flagged = keyless_aggregate(
                batch, agg_inputs, fns, self.key_schema)
        return self._output_batch(group_keys, buffers), flagged

    def partitions(self, ctx):
        child_schema = self.children[0].output_schema

        if self.mode == "merge":
            # Inputs are partial-buffer batches (post-exchange): concat the
            # whole partition FIRST, then merge+finalize once.  Re-merging
            # finalized outputs would be wrong (avg, first/last...).
            #
            # AQE-style partition coalescing (GpuCustomShuffleReaderExec
            # role): post-shuffle partitions are often tiny; group small
            # ones so one compiled merge covers a worthwhile row count and
            # downstream sees fewer partitions.
            import itertools

            from spark_rapids_tpu.batch import host_sizes
            child = self.children[0]
            lazy_parts = child.partitions(ctx)
            all_sizes: dict = {}
            if _aqe_enabled(ctx) and len(lazy_parts) > 1:
                sizes, unit = _aqe_part_stats(child, len(lazy_parts))
                if sizes is not None:
                    # spill-friendly: shuffle-known sizes, lazy chaining;
                    # skewed partitions stay un-merged (plan/adaptive)
                    groups, _gflags = _adaptive.plan_groups(
                        ctx, self.op_id, lazy_parts, sizes, unit)
                    parts = [itertools.chain(*g) for g in groups]
                else:
                    mats = [list(p) for p in lazy_parts]
                    # one round trip for every batch's sizes across ALL
                    # partitions (row counts + string byte totals), reused
                    # by the concat below
                    flat = [b for p in mats for b in p]
                    flat_sizes = host_sizes(flat) if flat else []
                    all_sizes = {id(b): s
                                 for b, s in zip(flat, flat_sizes)}
                    sizes = [sum(all_sizes[id(b)][0] for b in p)
                             for p in mats]
                    parts = _coalesce_partition_lists(
                        mats, sizes, _aqe_target_rows(ctx))
            else:
                parts = lazy_parts

            def gen(part):
                batches = list(part)
                pre = [all_sizes[id(b)] for b in batches] \
                    if batches and all(id(b) in all_sizes for b in batches) \
                    else None
                _reserve_for(ctx, batches)
                merged = _concat_all(batches, child_schema, sizes=pre)
                if merged is None:
                    if self.key_exprs:
                        return
                    # keyless reduction on empty input -> SQL default row
                    merged = empty_device_batch(child_schema)
                yield self._run(merged)

            return [gen(p) for p in parts]
        else:
            # update mode: aggregate each batch, then combine this
            # partition's partials: concat + buffer-merge (the reference's
            # concatenateBatches + merge-aggregate loop,
            # aggregate.scala:434-492).  Partials stay in their input-sized
            # buffers (no per-batch host sync); the downstream pipeline
            # break right-sizes them in one round trip.
            def gen(part):
                batches = list(part)
                partials = self._update_partials(ctx, batches)
                if not partials:
                    return
                if len(partials) == 1:
                    yield partials[0]
                    return
                merged = _concat_all(partials, self.output_schema)
                yield self._merge_run(merged)

        return [gen(p) for p in self.children[0].partitions(ctx)]

    def _update_partials(self, ctx, batches):
        """Per-batch partials, preferring the MXU slot path (keyless: the
        reduction); any flagged batch (key range over the slot table, or
        NaN/Inf float inputs — device-verified) re-runs on the exact sort
        path, and the fast path turns off for this exec."""
        if self._hash_active(ctx):
            pairs = [self._run_hash(db) for db in batches]
            # a batch whose string key arrived plain comes back in the
            # sort form with no flag (``_aggregate_batch_hash``)
            flags = [f for _, f in pairs if f is not None]
            if not flags or not any(
                    bool(f) for f in device_read("hashagg_flags", flags)):
                self._count_update_batches(ctx, len(flags), fast=True)
                self._count_update_batches(ctx, len(pairs) - len(flags),
                                           fast=False)
                return [p for p, _ in pairs]
            self._hash_disabled = True
            ctx.metric(self.op_id, "hashAggFallback").add(1)
        self._count_update_batches(ctx, len(batches), fast=False)
        return [self._run(db) for db in batches]

    def _merge_partials(self, merged: ColumnBatch) -> ColumnBatch:
        """Merge concatenated update-mode outputs back to one partial batch
        per partition (keys + buffers -> keys + buffers)."""
        key_vals = [DevVal.from_column(c)
                    for c in merged.columns[:len(self.key_exprs)]]
        group_keys, buffers = groupby_aggregate(
            merged, key_vals, self._partial_buffers(merged),
            [a.fn for a in self.aggs], True, self.key_schema,
            self.buffer_schemas, self.output_schema)
        return self._output_batch(group_keys, buffers)


def _eval_join_keys(exprs, batch, dict_keys: bool):
    """Evaluate equi-join key expressions against one side's batch.

    With ``dict_keys`` (spark.rapids.sql.tpu.join.dictKeys.enabled),
    string keys that arrived dictionary-encoded from the scan/shuffle
    corridor stay encoded — join_pairs then hashes/compares int32 codes
    when both sides align (rendezvous-translating divergent dictionaries)
    and falls back to content hashing THROUGH the codes otherwise, both
    bit-identical to materialized keys.  Off: keys materialize here, so
    the kernel never sees codes."""
    from spark_rapids_tpu.exprs.base import eval_maybe_encoded
    ctx = TpuEvalCtx(batch)
    if dict_keys:
        return [eval_maybe_encoded(e, ctx) if e.dtype.is_string
                else e.tpu_eval(ctx) for e in exprs]
    return [e.tpu_eval(ctx) for e in exprs]


class TpuShuffledHashJoinExec(TpuExec):
    """Equi-join per co-partitioned pair (GpuShuffledHashJoinExec analogue).
    Residual conditions are applied as a post-join filter for inner joins
    (GpuHashJoin.scala:265-271); outer+condition falls back at planning."""

    def __init__(self, left: PhysicalOp, right: PhysicalOp,
                 left_keys: List[Expression], right_keys: List[Expression],
                 how: str, condition: Optional[Expression],
                 schema: T.Schema):
        super().__init__([left, right], schema)
        self.left_keys = left_keys
        self.right_keys = right_keys
        self.how = how
        self.condition = condition

    def describe(self):
        return f"TpuShuffledHashJoin({self.how})"

    def num_partitions(self, ctx):
        return self.children[0].num_partitions(ctx)

    _FUSABLE_HOWS = ("inner", "left", "right", "full", "left_semi",
                     "left_anti")

    def pipeline_inline(self, ctx, build):
        """Mesh-SPMD fusion: lower the join INTO the surrounding
        shard_map program.  Both input shuffles fuse as in-program
        all_to_alls over the same key hash, so each shard holds a
        co-partitioned (left, right) pair — every join type is correct
        per shard — and the per-shard join runs with STATIC bucketed
        output sizing (kernels.join.hash_join_static), no host sync for
        the pair total.  A traced overflow flag rides the program's
        outputs (parallel.mesh_spmd.note_overflow_flag); when the true
        output exceeded its bucket the stage transparently reruns
        host-driven.  Returns None (host path: AQE coalescing, skew
        splits, broadcast switch, residual conditions) unless both
        children are rule-matched mesh exchanges."""
        from spark_rapids_tpu.parallel.exchange import (
            TpuShuffleExchangeExec,
        )
        from spark_rapids_tpu.parallel.partitioning import (
            match_partition_rules,
        )
        from spark_rapids_tpu.plan.pipeline import (
            concat_static, mesh_build_scope,
        )
        scope = mesh_build_scope()
        if scope is None or self.condition is not None or \
                self.how not in self._FUSABLE_HOWS:
            return None
        # static pre-check BEFORE building any child: a child that would
        # not fuse must leave this op (not its subtree) the stage source
        for ch in self.children:
            if not (isinstance(ch, TpuShuffleExchangeExec) and
                    ch._mesh_active(ctx) and
                    match_partition_rules(
                        type(ch.partitioning).__name__) is not None):
                return None
        from spark_rapids_tpu.config import (
            JOIN_DICT_KEYS_ENABLED, MESH_SPMD_JOIN_GROWTH,
        )
        from spark_rapids_tpu.kernels.join import hash_join_static
        from spark_rapids_tpu.parallel.mesh_spmd import note_overflow_flag
        growth = MESH_SPMD_JOIN_GROWTH.get(ctx.conf)
        dict_keys = JOIN_DICT_KEYS_ENABLED.get(ctx.conf)
        lf = build(self.children[0])
        rf = build(self.children[1])
        lsch = self.children[0].output_schema
        rsch = self.children[1].output_schema
        scope.joins.append(self)

        def f(args):
            lb = concat_static(lf(args), lsch)
            rb = concat_static(rf(args), rsch)
            lkeys = _eval_join_keys(self.left_keys, lb, dict_keys)
            rkeys = _eval_join_keys(self.right_keys, rb, dict_keys)
            out, ovf = hash_join_static(lb, lkeys, rb, rkeys, self.how,
                                        self.output_schema, growth=growth)
            note_overflow_flag(ovf)
            return [out]

        return f

    def partitions(self, ctx):
        import itertools
        from spark_rapids_tpu.config import JOIN_DICT_KEYS_ENABLED
        self._dict_keys = JOIN_DICT_KEYS_ENABLED.get(ctx.conf)
        lchild, rchild = self.children
        if self.num_partitions(ctx) > 1:
            switched = self._try_broadcast_switch(ctx)
            if switched is not None:
                return switched
        lparts = lchild.partitions(ctx)
        rparts = rchild.partitions(ctx)
        assert len(lparts) == len(rparts)
        skew_flags = [False] * len(lparts)

        if _aqe_enabled(ctx) and len(lparts) > 1:
            # Pair coalescing (GpuCustomShuffleReaderExec role for joins):
            # group co-partitioned (left, right) pairs by COMBINED size so
            # both sides stay aligned; plan_groups keeps a skewed pair
            # ALONE and flags it for the per-piece chunked join below.
            lsz, lunit = _aqe_part_stats(lchild, len(lparts))
            rsz, runit = _aqe_part_stats(rchild, len(rparts))
            if lsz is not None and rsz is not None and lunit == runit:
                # spill-friendly: shuffle-known sizes, lazy chaining (each
                # group's pieces unspill only when that pair is joined)
                sizes = [a + b for a, b in zip(lsz, rsz)]
                unit = lunit
                record = True
            else:
                lparts = [list(p) for p in lparts]
                rparts = [list(p) for p in rparts]
                from spark_rapids_tpu.batch import host_sizes
                flat = [b for p in lparts + rparts for b in p]
                by_id = {id(b): s[0]
                         for b, s in zip(flat, host_sizes(flat))} \
                    if flat else {}
                sizes = [sum(by_id[id(b)] for b in lp) +
                         sum(by_id[id(b)] for b in rp)
                         for lp, rp in zip(lparts, rparts)]
                unit = "rows"
                record = False  # these sizes cost a fetch, not free stats
            # history-seeded skew marks recorded on either exchange by a
            # previous run (history.seeding) isolate known-hot
            # partitions before this run's stats would
            seed = getattr(lchild, "_history_skew", None)
            if seed is None:
                seed = getattr(rchild, "_history_skew", None)
            groups, skew_flags = _adaptive.plan_groups(
                ctx, self.op_id, list(zip(lparts, rparts)), sizes, unit,
                record=record, detect_skew=self.how != "full",
                seed_flags=seed)
            lparts = [itertools.chain(*(lp for lp, _ in g))
                      for g in groups]
            rparts = [itertools.chain(*(rp for _, rp in g))
                      for g in groups]

        def gen(lp, rp, skewed):
            lbs, rbs = list(lp), list(rp)
            _reserve_for(ctx, lbs + rbs)
            if skewed and self.how != "full":
                yield from self._join_skewed(ctx, lbs, rbs)
                return
            lb, l_rows = _concat_sized(lbs, self.children[0].output_schema)
            rb, r_rows = _concat_sized(rbs, self.children[1].output_schema)
            out = self._join_pair(ctx, lb, rb, l_rows, r_rows)
            if out is not None:
                yield out

        return [gen(lp, rp, sk)
                for lp, rp, sk in zip(lparts, rparts, skew_flags)]

    def _try_broadcast_switch(self, ctx):
        """Dynamic broadcast switch (AQE OptimizeShuffledHashJoin +
        GpuCustomShuffleReaderExec role): try each legal build side in
        preference order; the FIRST whose exchange materializes under
        spark.sql.autoBroadcastJoinThreshold actual bytes wins.  The
        already-split shuffle pieces become the broadcast build (no
        recompute), and when the probe side's exchange has not split yet
        its shuffle is ELIDED entirely (bypass_partitions): no pid
        programs, no piece gathers, no split host sync on that side.
        Returns the broadcast-shaped partition list, or None to keep the
        shuffled shape."""
        from spark_rapids_tpu.parallel.exchange import (
            TpuShuffleExchangeExec,
        )
        if not _adaptive.replan_joins_enabled(ctx):
            return None
        thr = _adaptive.broadcast_threshold(ctx)
        if thr < 0:
            return None
        lchild, rchild = self.children
        sides = _adaptive.broadcast_build_sides(self.how)
        hint = getattr(self, "_history_bc_side", None)
        if hint in sides:
            # history-seeded build side (history.seeding): try the side
            # that won last run first, so the switch materializes the
            # right exchange without probing the other side
            sides = [hint] + [s for s in sides if s != hint]
        for side in sides:
            build = rchild if side == "right" else lchild
            probe = lchild if side == "right" else rchild
            bparts = build.partitions(ctx)
            bbytes = getattr(build, "_last_part_bytes", None)
            if bbytes is None or len(bbytes) != len(bparts) or \
                    sum(bbytes) > thr:
                continue
            _adaptive.record_stats(ctx, self.op_id, bbytes, "bytes")
            if isinstance(probe, TpuShuffleExchangeExec) and \
                    not probe.has_materialized_split(ctx):
                sparts = probe.bypass_partitions(ctx)
            else:
                # the probe already split (it was tried as a build
                # candidate, or a shared subtree ran it): read its
                # spillable pieces rather than re-running the upstream
                sparts = probe.partitions(ctx)
            return self._broadcast_partitions(ctx, side, bparts, sparts)
        return None

    def _broadcast_partitions(self, ctx, side, build_parts, stream_parts):
        """Execute as a broadcast join: materialize the small side once,
        join every stream partition against it.  The build handle is
        cached per (ctx, device generation) — a device-lost reset bumps
        the generation, so a partition REPLAY rebuilds the broadcast from
        lineage instead of reading a handle whose device copy died with
        the old device (fault.recovery contract, like the exchange's
        split cache)."""
        import weakref

        from spark_rapids_tpu.runtime.device import DeviceRuntime
        build_schema = self.children[1 if side == "right" else 0] \
            .output_schema
        stream_schema = self.children[0 if side == "right" else 1] \
            .output_schema
        gen_now = DeviceRuntime.generation()
        cached = getattr(self, "_switch_cache", None)
        if cached is not None and cached[0]() is ctx and \
                cached[1] == gen_now and cached[2] == side:
            bh = cached[3]
        else:
            bbs = [b for p in build_parts for b in p]
            _reserve_for(ctx, bbs)
            bc = _concat_all(bbs, build_schema)
            bh = None
            if bc is not None:
                bh = DeviceRuntime.get(ctx.conf).catalog.register(bc)
                ctx.defer_close(bh)
                del bc
            self._switch_cache = (weakref.ref(ctx), gen_now, side, bh)
        ctx.metric(self.op_id, "replannedBroadcast").add(1)
        ctx.metric(self.op_id, "aqeBroadcastSwitches").add(1)
        _adaptive.note_event(ctx, self.op_id, "broadcast_switch")

        def gen(part):
            sbs = list(part)
            if not sbs:
                return
            _reserve_for(ctx, sbs)
            sb = _concat_all(sbs, stream_schema)
            b = bh.get() if bh is not None else None
            lb, rb = (sb, b) if side == "right" else (b, sb)
            out = self._join_pair(ctx, lb, rb)
            if out is not None:
                yield out

        return [gen(p) for p in stream_parts]

    def _join_skewed(self, ctx, lbs, rbs):
        """Skewed-group handling (AQE OptimizeSkewedJoin role): instead
        of one giant stream-side concat+join, the stream side is joined
        PER SOURCE PIECE — the pieces the shuffle split already produced
        (its non-coalesced path) — and any single piece whose bytes
        exceed the target is further cut into row-granularity chunks, so
        the join's pair-space allocation is bounded per dispatch even
        when the whole skewed partition arrived as one piece.  Stream
        rows belong to exactly one chunk, so outer null-padding of the
        stream side per chunk stays correct; 'full' tracks unmatched
        rows on BOTH sides and is never chunked (caller guards).  ONE
        host round trip yields every piece's rows + varlen totals."""
        from spark_rapids_tpu.batch import (
            fixed_row_bytes, host_sizes, varlen_byte_scales,
        )
        split_left = self.how != "right"
        stream = lbs if split_left else rbs
        build = rbs if split_left else lbs
        stream_schema = self.children[0 if split_left else 1].output_schema
        build_schema = self.children[1 if split_left else 0].output_schema
        build_b = _concat_all(build, build_schema)
        from spark_rapids_tpu.kernels.layout import row_slices
        frb = fixed_row_bytes(stream_schema)
        vscales = varlen_byte_scales(stream_schema)
        target = max(_aqe_target_bytes(ctx), 1)
        plan = []
        chunks = 0
        for piece, (rows, vtotals) in zip(
                stream, host_sizes(stream) if stream else []):
            if rows == 0:
                continue
            pbytes = rows * frb + \
                sum(t * s for t, s in zip(vtotals, vscales))
            n_chunks = max(1, min(rows, -(-pbytes // target)))
            rows_per = -(-rows // n_chunks)
            plan.append((piece, rows, rows_per))
            chunks += -(-rows // rows_per)
        ctx.metric(self.op_id, "skewSplitChunks").add(chunks)
        if not plan:
            # no live stream rows: only a build-only shape can produce
            # output (it cannot for the non-'full' hows chunked here)
            out = self._join_pair(
                ctx, *((None, build_b) if split_left else (build_b, None)))
            if out is not None:
                yield out
            return
        for piece, rows, rows_per in plan:
            for sb in row_slices(piece, rows, rows_per):
                lb, rb = (sb, build_b) if split_left else (build_b, sb)
                out = self._join_pair(ctx, lb, rb)
                if out is not None:
                    yield out

    def _join_pair(self, ctx, lb, rb, l_rows: Optional[int] = None,
                   r_rows: Optional[int] = None) -> Optional[ColumnBatch]:
        lsch = self.children[0].output_schema
        rsch = self.children[1].output_schema
        if lb is None and self.how in ("inner", "left", "left_semi",
                                       "left_anti", "cross"):
            return None
        if lb is None:
            lb = empty_device_batch(lsch)
        if rb is None:
            if self.how in ("inner", "right", "cross", "left_semi"):
                if self.how in ("inner", "right", "cross"):
                    return None
                # left_semi with empty right = empty
                return None
            rb = empty_device_batch(rsch)
        dict_keys = getattr(self, "_dict_keys", False)
        lkeys = _eval_join_keys(self.left_keys, lb, dict_keys)
        rkeys = _eval_join_keys(self.right_keys, rb, dict_keys)
        # the residual condition runs INSIDE the join (it gates matches
        # before null-padding — GpuHashJoin.scala:265-271), so outer and
        # semi/anti joins with conditions are correct on device
        return _counted_hash_join(
            ctx, self.op_id, lb, lkeys, rb, rkeys, self.how,
            self.output_schema, self.condition, l_rows, r_rows)


class TpuNestedLoopJoinExec(TpuExec):
    """All-pairs join with optional condition, every join type; right side
    broadcast-materialized (GpuBroadcastNestedLoopJoinExec.scala:305 +
    GpuCartesianProductExec analogue)."""

    def __init__(self, left: PhysicalOp, right: PhysicalOp, how: str,
                 condition: Optional[Expression], schema: T.Schema):
        super().__init__([left, right], schema)
        self.how = how
        self.condition = condition

    def describe(self):
        return f"TpuNestedLoopJoin({self.how})"

    def num_partitions(self, ctx):
        if self.how in ("right", "full"):
            return 1
        return self.children[0].num_partitions(ctx)

    def partitions(self, ctx):
        from spark_rapids_tpu.config import NLJ_PAIR_CAPACITY
        from spark_rapids_tpu.kernels.join import (
            nested_loop_join, nested_loop_join_streamed,
        )
        from spark_rapids_tpu.kernels.layout import row_slices
        from spark_rapids_tpu.runtime.device import DeviceRuntime
        budget = max(NLJ_PAIR_CAPACITY.get(ctx.conf), 1)
        lsch = self.children[0].output_schema
        rsch = self.children[1].output_schema
        depth0 = ctx.semaphore.task_depth() if ctx.semaphore else 0
        rbatches = []
        for p in self.children[1].partitions(ctx):
            rbatches.extend(p)
        rb = _concat_all(rbatches, rsch)
        # The broadcast-materialized side lives in the spill catalog (the
        # reference registers broadcast tables with the buffer catalog) —
        # evictable under memory pressure, re-fetched per use.
        rh = None
        n_r = 0
        if rb is not None:
            n_r = rb.host_num_rows()
            catalog = DeviceRuntime.get(ctx.conf).catalog
            rh = catalog.register(rb)
            ctx.defer_close(rh)
            del rb
        _release_build_staging(ctx, depth0)

        def rb_local():
            return rh.get() if rh is not None else empty_device_batch(rsch)

        lparts = self.children[0].partitions(ctx)
        rows_per = max(1, budget // max(n_r, 1))

        if self.how in ("right", "full"):
            # right-unmatched rows are a property of the WHOLE left side:
            # stream left chunks against the full right, accumulating
            # right-matched flags; remainder emitted at the end
            def gen_all():
                lbatches = [b for p in lparts for b in p]
                _reserve_for(ctx, lbatches)
                lb = _concat_all(lbatches, lsch) or empty_device_batch(lsch)
                r = rb_local()
                n_l = lb.host_num_rows()
                if n_l * max(n_r, 1) <= budget:
                    yield nested_loop_join(lb, r, self.how, self.condition,
                                           self.output_schema)
                    return
                ctx.metric(self.op_id, "nljChunks").add(
                    -(-n_l // rows_per))
                yield from nested_loop_join_streamed(
                    row_slices(lb, n_l, rows_per),
                    empty_device_batch(lsch), r, self.how, self.condition,
                    self.output_schema)

            return [gen_all()]

        def gen(lp):
            for lb in lp:
                r = rb_local()
                n_l = lb.host_num_rows()
                if n_l * max(n_r, 1) <= budget:
                    yield nested_loop_join(lb, r, self.how, self.condition,
                                           self.output_schema)
                    continue
                # inner/left/semi/anti: each left row's outcome only needs
                # the FULL right side — chunking the left is exact
                ctx.metric(self.op_id, "nljChunks").add(
                    -(-n_l // rows_per))
                for chunk in row_slices(lb, n_l, rows_per):
                    yield nested_loop_join(chunk, r, self.how,
                                           self.condition,
                                           self.output_schema)

        return [gen(p) for p in lparts]


class TpuExpandExec(TpuExec):
    """Grouping-sets expansion via repeated projections
    (GpuExpandExec.scala)."""

    def __init__(self, projections: List[List[Expression]], child: PhysicalOp,
                 schema: T.Schema):
        super().__init__([child], schema)
        self.projections = projections
        self._runs = []
        for proj in projections:
            def make(proj=proj):
                @plan_jit(label="TpuExpand")
                def run(batch):
                    ctx = TpuEvalCtx(batch)
                    cols = [e.tpu_eval(ctx).to_column() for e in proj]
                    return ColumnBatch(schema, cols, batch.num_rows,
                                       batch.capacity)
                return run
            self._runs.append(make())

    def pipeline_inline(self, ctx, build):
        cf = build(self.children[0])
        return lambda args: [run(b) for b in cf(args)
                             for run in self._runs]

    def partitions(self, ctx):
        def gen(part):
            for db in part:
                for run in self._runs:
                    yield run(db)

        return [gen(p) for p in self.children[0].partitions(ctx)]


class TpuSampleExec(TpuExec):
    """Bernoulli sample.  Uses the same host RNG stream as the CPU exec so
    CPU-vs-TPU compare tests agree."""

    def __init__(self, fraction: float, seed: int, child: PhysicalOp):
        super().__init__([child], child.output_schema)
        self.fraction = fraction
        self.seed = seed

    def partitions(self, ctx):
        def gen(pi, part):
            rng = np.random.RandomState(self.seed + pi)
            for db in part:
                n = db.host_num_rows()
                keep_host = rng.rand(n) < self.fraction
                keep = jnp.zeros(db.capacity, dtype=jnp.bool_).at[:n].set(
                    jnp.asarray(keep_host))
                out = compact(db, keep)
                yield out

        return [gen(i, p)
                for i, p in enumerate(self.children[0].partitions(ctx))]


class TpuCachedScanExec(TpuExec):
    """Reads (and on first run populates) a CacheHolder of spillable device
    batches (df.cache() analogue — SURVEY.md section 5 checkpoint/resume:
    cached batches are evictable through the device->host->disk tiers)."""

    def __init__(self, holder, child: Optional[PhysicalOp],
                 schema: T.Schema):
        super().__init__([child] if child is not None else [], schema)
        self.holder = holder

    def describe(self):
        return "TpuCachedScan"

    def num_partitions(self, ctx):
        if self.holder.is_materialized:
            return len(self.holder.partitions)
        return self.children[0].num_partitions(ctx)

    def _materialize(self, ctx):
        from spark_rapids_tpu.runtime.device import DeviceRuntime
        from spark_rapids_tpu.utils.tracing import span
        catalog = DeviceRuntime.get(ctx.conf).catalog
        parts = []
        for p in self.children[0].partitions(ctx):
            handles = []
            for db in p:
                db = shrink_to_fit(db)
                # nothing consumes a batch while the table is staged, so
                # H2D can end in a sync here: host_to_device's own
                # h2d/transfer span times the enqueue, this one what is
                # left of the copies (and of the re-bucketing gather)
                with span("h2d", "cache_ready", self.op_id):
                    jax.block_until_ready(db)
                handles.append(catalog.register(db))
            parts.append(handles)
        self.holder.partitions = parts

    def partitions(self, ctx):
        if not self.holder.is_materialized:
            self._materialize(ctx)
        # overlapped unspill: under memory pressure the cached handles sit
        # on host/disk, and the drive loop keeps the next rehydration in
        # flight while the consumer computes on the current batch
        from spark_rapids_tpu.plan.physical import prefetch_spillables
        return [prefetch_spillables(p) for p in self.holder.partitions]


class TpuBroadcastHashJoinExec(TpuExec):
    """Hash join against a broadcast build side: the build side is
    materialized ONCE (all partitions concatenated on device) and every
    stream partition joins against it — no shuffle on either side
    (GpuBroadcastHashJoinExec analogue, shims/spark300).

    ``broadcast_side`` is "right" or "left".  Planner guarantees the join
    type is legal for the broadcast side (no broadcast of the outer side's
    opposite: right broadcast for inner/left/semi/anti, left broadcast for
    inner/right)."""

    def __init__(self, stream: PhysicalOp, broadcast: PhysicalOp,
                 left_keys: List[Expression], right_keys: List[Expression],
                 how: str, broadcast_side: str,
                 condition: Optional[Expression], schema: T.Schema):
        super().__init__([stream, broadcast], schema)
        self.left_keys = left_keys
        self.right_keys = right_keys
        self.how = how
        self.broadcast_side = broadcast_side
        self.condition = condition
        self._bc_cache = None  # (weakref(ctx), SpillableBatch | None)
        self._bc_rows = None   # the build's rows, where its concat read them

    def describe(self):
        return f"TpuBroadcastHashJoin({self.how}, bc={self.broadcast_side})"

    def num_partitions(self, ctx):
        return self.children[0].num_partitions(ctx)

    # planner-legal broadcast combinations (unmatched BUILD rows are never
    # emitted, so a replicated build joined per shard stays exact)
    _FUSABLE_HOWS = {
        "right": ("inner", "left", "left_semi", "left_anti"),
        "left": ("inner", "right"),
    }

    def pipeline_inline(self, ctx, build):
        """Mesh-SPMD fusion: join per shard inside the fused shard_map
        program with the build side REPLICATED — its stage sources are
        recorded in ``scope.replicated`` so parallel.mesh_spmd feeds them
        as PartitionSpec-() globals (every shard sees the full build,
        like the host path's broadcast handle).  The planner-guaranteed
        build-side legality (class docstring) means no unmatched build
        row is ever emitted, so replaying the build on every shard never
        duplicates output rows.  Output sizing is static-bucketed
        (hash_join_static) with the same traced overflow -> host-rerun
        contract as the shuffled join.  Returns None when the build
        subtree contains an exchange (it would fuse as a collective and
        SHARD the build) or shares nodes with the stream subtree (shared
        sources cannot be both replicated and distributed)."""
        from spark_rapids_tpu.parallel.exchange import (
            TpuShuffleExchangeExec,
        )
        from spark_rapids_tpu.plan.pipeline import (
            concat_static, mesh_build_scope,
        )
        scope = mesh_build_scope()
        if scope is None or self.condition is not None or \
                self.how not in self._FUSABLE_HOWS.get(
                    self.broadcast_side, ()):
            return None

        bc_nodes = list(self._walk(self.children[1]))
        if any(isinstance(o, TpuShuffleExchangeExec) for o in bc_nodes):
            return None
        if {id(o) for o in bc_nodes} & \
                {id(o) for o in self._walk(self.children[0])}:
            return None
        from spark_rapids_tpu.config import (
            JOIN_DICT_KEYS_ENABLED, MESH_SPMD_JOIN_GROWTH,
        )
        from spark_rapids_tpu.kernels.join import hash_join_static
        from spark_rapids_tpu.parallel.mesh_spmd import note_overflow_flag
        growth = MESH_SPMD_JOIN_GROWTH.get(ctx.conf)
        dict_keys = JOIN_DICT_KEYS_ENABLED.get(ctx.conf)
        before = len(scope.sources)
        bf = build(self.children[1])
        scope.replicated.update(range(before, len(scope.sources)))
        sf = build(self.children[0])
        bc_schema = self.children[1].output_schema
        stream_schema = self.children[0].output_schema
        scope.joins.append(self)

        def f(args):
            sb = concat_static(sf(args), stream_schema)
            bc = concat_static(bf(args), bc_schema)
            lb, rb = (sb, bc) if self.broadcast_side == "right" \
                else (bc, sb)
            lkeys = _eval_join_keys(self.left_keys, lb, dict_keys)
            rkeys = _eval_join_keys(self.right_keys, rb, dict_keys)
            out, ovf = hash_join_static(lb, lkeys, rb, rkeys, self.how,
                                        self.output_schema, growth=growth)
            note_overflow_flag(ovf)
            return [out]

        return f

    @staticmethod
    def _walk(op):
        yield op
        for c in op.children:
            yield from TpuBroadcastHashJoinExec._walk(c)

    def _broadcast_handle(self, ctx):
        """Materialize the build side ONCE per query and register it with
        the spill catalog (the reference keeps broadcast build batches in
        the buffer catalog, spillable like everything else — an
        unregistered cached build side would be un-evictable HBM).  The
        handle is ctx-scoped (weakref, like the exchange's split cache)
        and defer-closed, so a finished query's build side leaves the
        catalog instead of pinning device budget and spill files."""
        import weakref
        cached = self._bc_cache
        if cached is not None and cached[0]() is ctx:
            return cached[1]
        depth0 = ctx.semaphore.task_depth() if ctx.semaphore else 0
        batches = []
        for p in self.children[1].partitions(ctx):
            batches.extend(p)
        bc, self._bc_rows = _concat_sized(
            batches, self.children[1].output_schema)
        handle = None
        if bc is not None:
            from spark_rapids_tpu.runtime.device import DeviceRuntime
            catalog = DeviceRuntime.get(ctx.conf).catalog
            handle = catalog.register(bc)
            ctx.defer_close(handle)
        self._bc_cache = (weakref.ref(ctx), handle)
        _release_build_staging(ctx, depth0)
        return handle

    def partitions(self, ctx):
        from spark_rapids_tpu.config import JOIN_DICT_KEYS_ENABLED
        bh = self._broadcast_handle(ctx)
        bc_schema = self.children[1].output_schema
        stream_schema = self.children[0].output_schema
        dict_keys = JOIN_DICT_KEYS_ENABLED.get(ctx.conf)

        def gen(part):
            for sb in part:
                # re-fetch per stream batch: a spilled build side frees
                # real HBM between batches and unspills on demand
                bc_local = bh.get() if bh is not None else \
                    empty_device_batch(bc_schema)
                if self.broadcast_side == "right":
                    lb, rb = sb, bc_local
                else:
                    lb, rb = bc_local, sb
                lkeys = _eval_join_keys(self.left_keys, lb, dict_keys)
                rkeys = _eval_join_keys(self.right_keys, rb, dict_keys)
                # the broadcast side's rows, where its concatenation
                # read them, under the name of the role it plays
                yield _counted_hash_join(
                    ctx, self.op_id, lb, lkeys, rb, rkeys, self.how,
                    self.output_schema, self.condition,
                    *((None, self._bc_rows) if self.broadcast_side == "right"
                      else (self._bc_rows, None)))

        return [gen(p) for p in self.children[0].partitions(ctx)]


class TpuGenerateExec(TpuExec):
    """explode/posexplode of a fixed-width-element array column
    (GpuGenerateExec analogue, GpuGenerateExec.scala): one flat-position →
    parent-row mapping (searchsorted over the array offsets) drives a
    whole-row gather of the kept columns; the element buffer IS the new
    column.  Output capacity = the array column's element capacity
    (static); live rows = total elements (device scalar — no host sync)."""

    def __init__(self, column: str, alias: str, pos: bool,
                 child: PhysicalOp, schema: T.Schema):
        super().__init__([child], schema)
        self.column = column
        self.alias = alias
        self.pos = pos

    def describe(self):
        kind = "posexplode" if self.pos else "explode"
        return f"TpuGenerate({kind}({self.column}))"

    def _explode_batch(self, batch: ColumnBatch) -> ColumnBatch:
        from spark_rapids_tpu.exprs.strings import rows_of_positions
        child_schema = batch.schema
        ci = child_schema.index_of(self.column)
        arr = batch.columns[ci]
        elem_cap = int(arr.data.shape[0])
        total = arr.offsets[batch.num_rows].astype(jnp.int32)
        live = jnp.arange(elem_cap, dtype=jnp.int32) < total
        parent = jnp.clip(rows_of_positions(arr.offsets, elem_cap),
                          0, batch.capacity - 1)
        kept = [i for i in range(len(child_schema)) if i != ci]
        kept_schema = T.Schema([child_schema.fields[i] for i in kept])
        kept_batch = ColumnBatch(kept_schema,
                                 [batch.columns[i] for i in kept],
                                 batch.num_rows, batch.capacity)
        # string columns can EXPAND (parent rows repeat); size on host
        bcaps = []
        for i in kept:
            c = batch.columns[i]
            if c.is_varlen:
                lens = (c.offsets[1:] - c.offsets[:-1]).astype(jnp.int64)
                tot = jnp.sum(jnp.where(live, lens[parent], 0))
                bcaps.append(round_up_capacity(
                    max(int(device_read("generate_bytes", tot)), 16),
                    minimum=16))
        g = gather_rows(kept_batch, parent, total, out_capacity=elem_cap,
                        out_byte_caps=bcaps or None)
        cols = list(g.columns)
        if self.pos:
            pos_col = jnp.arange(elem_cap, dtype=jnp.int32) - \
                arr.offsets[parent]
            cols.append(DeviceColumn(
                T.INT, jnp.where(live, pos_col, 0), live, None))
        elem_valid = live & arr.validity[parent]
        cols.append(DeviceColumn(self.output_schema.fields[-1].dtype,
                                 jnp.where(live, arr.data, 0),
                                 elem_valid, None))
        return ColumnBatch(self.output_schema, cols, total, elem_cap)

    def partitions(self, ctx):
        def gen(part):
            for db in part:
                yield self._explode_batch(db)

        return [gen(p) for p in self.children[0].partitions(ctx)]
