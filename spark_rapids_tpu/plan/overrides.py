"""TPU overrides: tag every logical operator for TPU support, lower supported
ones to TPU execs and the rest to CPU execs, insert exchanges and
host<->device transitions, and produce the explain output.

Reference analogue: GpuOverrides.scala (rule registry + wrap/tag/convert,
:1884-1902), RapidsMeta.scala (tagging tree, willNotWorkOnGpu reasons :127),
GpuTransitionOverrides.scala (transition insertion :38-221).  Differences are
deliberate: the engine owns the frontend, so tagging happens on the *logical*
plan and the physical planner (exchange insertion, two-phase agg split) runs
fused with conversion — one pass instead of Catalyst's two.

Per-operator conf gates mirror the reference's generated keys
(GpuOverrides.scala:129-137): ``spark.rapids.sql.exec.<Name>`` and
``spark.rapids.sql.expression.<Name>``.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from spark_rapids_tpu import types as T
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.exprs.base import (
    ColumnRef, CpuEvalCtx, Expression, Literal, SortOrder, resolve,
)
from spark_rapids_tpu.exprs.aggregates import (
    AggregateExpression, AggregateFunction, Average, Count, Max, Min, Sum,
)
from spark_rapids_tpu.exprs.conditional import If
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.join_pushdown import (
    JoinPush, deterministic, narrow_join_inputs,
    push_filters_through_joins,
    split_conjuncts as _split_conjuncts,
)
from spark_rapids_tpu.ops import cpu_exec as C
from spark_rapids_tpu.ops import tpu_exec as X
from spark_rapids_tpu.parallel.exchange import (
    CpuBroadcastExchangeExec, CpuShuffleExchangeExec, TpuShuffleExchangeExec,
)
from spark_rapids_tpu.parallel.partitioning import (
    HashPartitioning, Partitioning, RangePartitioning, RoundRobinPartitioning,
    SinglePartitioning,
)
from spark_rapids_tpu.plan.physical import (
    DeviceToHostExec, HostToDeviceExec, PhysicalOp,
)
from spark_rapids_tpu.utils.tracing import span


class ExprMeta:
    """Tags one expression tree (BaseExprMeta analogue,
    RapidsMeta.scala:656)."""

    def __init__(self, expr: Expression, conf: RapidsConf):
        self.expr = expr
        self.conf = conf
        self.reasons: List[str] = []
        self._tag(expr)

    def _tag(self, e: Expression):
        cls = type(e)
        if cls.tpu_eval is Expression.tpu_eval and \
                not isinstance(e, AggregateFunction):
            self.reasons.append(
                f"expression {e.name} has no TPU implementation")
        else:
            reason = e.tpu_supported(self.conf)
            if reason:
                self.reasons.append(f"expression {e.name}: {reason}")
        key = f"spark.rapids.sql.expression.{e.name}"
        if self.conf.get(key, True) in (False, "false"):
            self.reasons.append(
                f"expression {e.name} disabled by {key}")
        for c in e.children:
            self._tag(c)

    @property
    def can_run_on_tpu(self) -> bool:
        return not self.reasons


class PlanMeta:
    """Tags one logical operator (SparkPlanMeta analogue,
    RapidsMeta.scala:418)."""

    def __init__(self, node: L.LogicalPlan, conf: RapidsConf):
        self.node = node
        self.conf = conf
        self.reasons: List[str] = []
        self.children = [PlanMeta(c, conf) for c in node.children]

    def will_not_work(self, reason: str):
        self.reasons.append(reason)

    def check_exprs(self, *exprs: Expression):
        for e in exprs:
            m = ExprMeta(e, self.conf)
            self.reasons.extend(m.reasons)

    @property
    def can_run_on_tpu(self) -> bool:
        return not self.reasons

    def explain_lines(self, depth: int = 0) -> List[str]:
        ind = "  " * depth
        name = self.node.name
        if self.can_run_on_tpu:
            lines = [f"{ind}*{name} will run on TPU"]
        else:
            why = "; ".join(self.reasons)
            lines = [f"{ind}!{name} cannot run on TPU because {why}"]
        for c in self.children:
            lines.extend(c.explain_lines(depth + 1))
        return lines


class RewriteNotes:
    """What :meth:`TpuOverrides.rewrite_logical` did to one plan object,
    in that query's own literal values: one description a constant fold,
    and one :class:`JoinPush` a join that was handed WHERE conjuncts or
    keys.  A query publishes the counts (``foldedExprs``,
    ``pushedJoinFilters``, ``joinKeysFromWhere``) and ``explain`` the
    lines, on a plan-cache hit as on a miss."""

    __slots__ = ("folded", "join_pushes")

    def __init__(self, folded: Sequence[str] = (),
                 join_pushes: Sequence[JoinPush] = ()):
        self.folded = list(folded)
        self.join_pushes = list(join_pushes)

    @property
    def pushed_join_filters(self) -> int:
        """Conjuncts that moved below a join; one that went on down
        through a second join is counted once."""
        return len({id(c) for p in self.join_pushes for c in p.pushed})

    @property
    def join_keys_from_where(self) -> int:
        return sum(len(p.keys) for p in self.join_pushes)


class PlanExplain:
    """The explain text of a planned shape.  The tagging lines are the
    shape's; what names literal values is rendered for one query: what
    moved below its joins, its own folds, and the absorbed filter
    conditions with that query's bound values in place of the lifted
    literals."""

    def __init__(self, lines: List[str], absorbed: List[Expression]):
        self.lines = lines
        self.absorbed = absorbed

    def render(self, notes: RewriteNotes, values: tuple = ()) -> str:
        from spark_rapids_tpu.utils import params
        lines = list(self.lines)
        lines.extend(p.describe() for p in notes.join_pushes)
        folded = notes.folded
        if folded:
            lines.append(f"folded {len(folded)}: " + ", ".join(folded))
        if self.absorbed:
            with params.showing(values):
                lines.append("filter applied inside the keyless aggregate "
                             "above it: " + ", ".join(
                                 repr(c) for c in self.absorbed))
        return "\n".join(lines)


class TpuOverrides:
    """The plan rewriter: logical plan -> physical plan with per-operator
    TPU/CPU placement, exchanges and transitions."""

    def __init__(self, conf: RapidsConf):
        self.conf = conf
        self.last_explain: str = ""
        self.explain: Optional[PlanExplain] = None

    # ------------------------------------------------------------------ tag

    def tag(self, meta: PlanMeta):
        for c in meta.children:
            self.tag(c)
        node = meta.node
        conf = self.conf
        if not conf.sql_enabled:
            meta.will_not_work("spark.rapids.sql.enabled is false")
            return
        key = f"spark.rapids.sql.exec.{node.name}"
        if conf.get(key, True) in (False, "false"):
            meta.will_not_work(f"disabled by {key}")

        # nested-type gating: array columns ride the varlen device layout
        # but only project/filter/explode consume them on TPU (the reference
        # gates nested types per-op the same way, GpuOverrides.scala:397-409)
        if not isinstance(node, (L.Project, L.Filter, L.Generate,
                                 L.InMemoryScan, L.FileScan, L.Union,
                                 L.Limit, L.CachedRelation)):
            schemas = [c.schema for c in node.children]
            if any(f.dtype.is_array for s in schemas for f in s.fields):
                meta.will_not_work(
                    "array columns: only project/filter/explode run on TPU")

        if isinstance(node, (L.InMemoryScan, L.FileScan)):
            # Scans decode on host by design (SURVEY.md section 7: host Arrow
            # decode staged into HBM); they are CPU execs + HostToDevice.
            meta.will_not_work("scans decode host-side (by design)")
        elif isinstance(node, L.CachedRelation):
            pass  # cached device batches are always TPU-resident
        elif isinstance(node, L.Project):
            meta.check_exprs(*node.exprs)
        elif isinstance(node, L.Filter):
            meta.check_exprs(node.condition)
        elif isinstance(node, L.Aggregate):
            meta.check_exprs(*node.keys)
            self._tag_string_keys(meta, node.keys, "group by")
            for a in node.aggs:
                meta.check_exprs(a.fn.child)
                reason = a.fn.tpu_supported(conf)
                if reason:
                    meta.will_not_work(f"aggregate {a.fn.name}: {reason}")
                if any(k.dtype.is_fractional for k in node.keys) and \
                        conf.has_nans:
                    meta.will_not_work(
                        "grouping by floating point when NaNs possible; set "
                        "spark.rapids.sql.hasNans=false to enable")
        elif isinstance(node, L.Sort):
            for o in node.orders:
                meta.check_exprs(o.child)
        elif isinstance(node, L.Join):
            meta.check_exprs(*node.left_keys, *node.right_keys)
            self._tag_string_keys(
                meta, list(node.left_keys) + list(node.right_keys), "join")
            if node.condition is not None:
                # conditions gate matches inside the join kernel for every
                # join type (GpuHashJoin.scala:265-271 parity)
                meta.check_exprs(node.condition)
        elif isinstance(node, L.Expand):
            for proj in node.projections:
                meta.check_exprs(*proj)
        elif isinstance(node, L.Window):
            for w in node.window_exprs:
                reason = w.tpu_supported(conf)
                if reason:
                    meta.will_not_work(reason)
        elif isinstance(node, L.Repartition):
            for k in node.keys:
                meta.check_exprs(k)
        elif isinstance(node, L.Generate):
            if node.outer:
                meta.will_not_work(
                    "explode_outer emits NULL-element rows (CPU path)")
            arr = node.children[0].schema.field(node.column)
            if not arr.dtype.is_array:
                meta.will_not_work(f"explode needs an array, got {arr.dtype}")
        elif isinstance(node, (L.MapInPandas, L.FlatMapGroupsInPandas,
                               L.FlatMapCoGroupsInPandas,
                               L.AggregateInPandas, L.WindowInPandas)):
            meta.will_not_work(
                "pandas exec runs python via the host Arrow path "
                "(GpuArrowEvalPythonExec data flow)")

    def _tag_string_keys(self, meta: PlanMeta, keys, what: str):
        """String keys group/join through 64-bit device hashes (documented
        collision incompat); ``stringHashGroupJoin.enabled=false`` opts the
        op out to the exact CPU path."""
        from spark_rapids_tpu.config import STRING_HASH_JOIN
        if any(k.dtype.is_string for k in keys) and \
                not STRING_HASH_JOIN.get(self.conf):
            meta.will_not_work(
                f"string {what} keys use device 64-bit hashes; disabled "
                "by spark.rapids.sql.stringHashGroupJoin.enabled")

    # -------------------------------------------------------------- convert

    def rewrite_logical(self, plan: L.LogicalPlan
                        ) -> Tuple[L.LogicalPlan, RewriteNotes]:
        """The logical rewrites that run on every new plan object, in
        planning order: UDF compilation, WHERE conjuncts and keys into the
        joins below them and the joins' inputs narrowed to what is read
        (``plan/join_pushdown.py``; a plan without a join comes back as it
        went in), scan pushdown (so a conjunct that moved below a join
        reaches the file scan under it) and constant folding.
        ``session.plan_bound`` runs them BEFORE it splits the plan into a
        shape and values: ``to_date('1994-01-01')`` becomes a DATE literal
        first and is lifted then.  Returns the plan and what was done to
        it.  Non-mutating (but for the UDF compiler's in-place edit)."""
        if self.conf.get("spark.rapids.sql.udfCompiler.enabled", False):
            plan = _compile_plan_udfs(plan)
        with span("plan", "pushdown") as sp:
            plan, join_pushes = push_filters_through_joins(plan)
            plan, narrowed = narrow_join_inputs(plan)
            sp.set(joins=len(join_pushes), narrowed=narrowed)
        if self.conf.get("spark.rapids.sql.scan.pushdown.enabled", True) \
                not in (False, "false"):
            plan = _pushdown_scan_filters(plan)
        # before tagging: a constant subtree with no device implementation
        # (cast('…' as date)) must not send its operator to the CPU
        with span("plan", "fold") as sp:
            plan, folded = _fold_constants(plan)
            sp.set(folded=len(folded))
        return plan, RewriteNotes(folded, join_pushes)

    def apply(self, plan: L.LogicalPlan) -> PhysicalOp:
        return self.lower(*self.rewrite_logical(plan))

    def lower(self, plan: L.LogicalPlan, notes: RewriteNotes) -> PhysicalOp:
        """Tag and lower a plan :meth:`rewrite_logical` has been over
        (lifted literals, where the caller shares the result among the
        queries of a shape, already slotted)."""
        plan, absorbed = _filters_into_keyless_aggregates(plan)
        meta = PlanMeta(plan, self.conf)
        self.tag(meta)
        self.explain = PlanExplain(meta.explain_lines(), absorbed)
        self.last_explain = self.explain.render(notes)
        if self.conf.explain_enabled:
            # routed through the obs sink (a logger by default) instead of
            # print(): library embedders and pytest capture aren't spammed,
            # and tools can install their own sink (obs.set_explain_sink)
            from spark_rapids_tpu.obs import explain_sink
            explain_sink(self.last_explain)
        phys = self._convert(meta)
        phys = _insert_transitions(phys)
        phys = _fuse_map_chains(phys)
        # last: every planner (session, ml, the recovery's CPU re-lowering)
        # hands out a tree whose op ids are its pre-order positions
        from spark_rapids_tpu.plan.physical import assign_op_ids
        phys = assign_op_ids(phys)
        # what the plan was FIRST built with; a query publishes its own
        # count as last_metrics["foldedExprs"]
        phys.folded_exprs = len(notes.folded)
        return phys

    def _shuffle_parts(self) -> int:
        return self.conf.shuffle_partitions

    def _convert(self, meta: PlanMeta) -> PhysicalOp:
        node = meta.node
        on_tpu = meta.can_run_on_tpu
        conv = [self._convert(c) for c in meta.children]

        if isinstance(node, L.InMemoryScan):
            return C.CpuInMemoryScanExec(node.batches, node.schema,
                                         node.num_partitions)
        if isinstance(node, L.FileScan):
            from spark_rapids_tpu.config import SCAN_V2_ENABLED
            if SCAN_V2_ENABLED.get(self.conf):
                from spark_rapids_tpu.io.scan_v2 import FileScanV2Exec
                return FileScanV2Exec(node, self.conf)
            from spark_rapids_tpu.io.scan import CpuFileScanExec
            return CpuFileScanExec(node, self.conf)
        if isinstance(node, L.BroadcastHint):
            return conv[0]
        if isinstance(node, L.CachedRelation):
            if not self.conf.sql_enabled:
                return conv[0]  # CPU engine: no device cache
            return X.TpuCachedScanExec(
                node.holder,
                None if node.holder.is_materialized else
                _to_device(conv[0]), node.schema)
        if isinstance(node, L.Range):
            if on_tpu:
                return X.TpuRangeExec(node.start, node.end, node.step,
                                      node.num_partitions, node.schema)
            return C.CpuRangeExec(node.start, node.end, node.step,
                                  node.num_partitions, node.schema)
        if isinstance(node, L.Project):
            if on_tpu:
                return X.TpuProjectExec(node.exprs, conv[0], node.schema)
            return C.CpuProjectExec(node.exprs, conv[0], node.schema)
        if isinstance(node, L.Filter):
            if on_tpu:
                return X.TpuFilterExec(node.condition, conv[0])
            return C.CpuFilterExec(node.condition, conv[0])
        if isinstance(node, L.Aggregate):
            return self._convert_aggregate(node, conv[0], on_tpu)
        if isinstance(node, L.Distinct):
            child = meta.node.children[0]
            keys = [ColumnRef(f.name, f.dtype, f.nullable)
                    for f in child.schema.fields]
            agg = L.Aggregate(keys, [f.name for f in child.schema.fields],
                              [], child)
            return self._convert_aggregate(agg, conv[0], on_tpu)
        if isinstance(node, L.Sort):
            return self._convert_sort(node, conv[0], on_tpu)
        if isinstance(node, L.Join):
            return self._convert_join(node, conv, on_tpu)
        if isinstance(node, L.Union):
            if on_tpu and all(c.is_tpu for c in conv):
                return X.TpuUnionExec(conv, node.schema)
            return C.CpuUnionExec(
                [_to_host(c) for c in conv], node.schema)
        if isinstance(node, L.Limit):
            return self._convert_limit(node, conv[0], on_tpu)
        if isinstance(node, L.Expand):
            flat_projs = node.projections
            if on_tpu:
                return X.TpuExpandExec(flat_projs, conv[0], node.schema)
            return C.CpuExpandExec(flat_projs, conv[0], node.schema)
        if isinstance(node, L.Sample):
            if on_tpu:
                return X.TpuSampleExec(node.fraction, node.seed, conv[0])
            return C.CpuSampleExec(node.fraction, node.seed, conv[0])
        if isinstance(node, L.Repartition):
            part = self._make_partitioning(node)
            if on_tpu:
                return TpuShuffleExchangeExec(part, conv[0])
            return CpuShuffleExchangeExec(part, conv[0])
        if isinstance(node, L.Generate):
            if on_tpu:
                return X.TpuGenerateExec(node.column, node.alias, node.pos,
                                         _to_device(conv[0]), node.schema)
            return C.CpuGenerateExec(node.column, node.alias, node.pos,
                                     node.outer, _to_host(conv[0]),
                                     node.schema)
        if isinstance(node, L.MapInPandas):
            from spark_rapids_tpu.ops.pandas_exec import CpuMapInPandasExec
            return CpuMapInPandasExec(node.fn, _to_host(conv[0]),
                                      node.schema)
        if isinstance(node, L.FlatMapGroupsInPandas):
            from spark_rapids_tpu.ops.pandas_exec import (
                CpuFlatMapGroupsInPandasExec,
            )
            part = HashPartitioning(node.keys, self._shuffle_parts())
            ex = CpuShuffleExchangeExec(part, _to_host(conv[0]))
            return CpuFlatMapGroupsInPandasExec(node.key_names, node.fn, ex,
                                                node.schema)
        if isinstance(node, L.FlatMapCoGroupsInPandas):
            from spark_rapids_tpu.ops.pandas_exec import (
                CpuFlatMapCoGroupsInPandasExec,
            )
            n_parts = self._shuffle_parts()
            lex = CpuShuffleExchangeExec(
                HashPartitioning(node.left_keys, n_parts),
                _to_host(conv[0]))
            rex = CpuShuffleExchangeExec(
                HashPartitioning(node.right_keys, n_parts),
                _to_host(conv[1]))
            return CpuFlatMapCoGroupsInPandasExec(
                node.left_names, node.right_names, node.fn, lex, rex,
                node.schema)
        if isinstance(node, L.AggregateInPandas):
            from spark_rapids_tpu.ops.pandas_exec import (
                CpuAggregateInPandasExec,
            )
            part = HashPartitioning(node.keys, self._shuffle_parts())
            ex = CpuShuffleExchangeExec(part, _to_host(conv[0]))
            return CpuAggregateInPandasExec(node.key_names, node.agg_specs,
                                            ex, node.schema)
        if isinstance(node, L.WindowInPandas):
            from spark_rapids_tpu.ops.pandas_exec import (
                CpuWindowInPandasExec,
            )
            part = HashPartitioning(node.keys, self._shuffle_parts())
            ex = CpuShuffleExchangeExec(part, _to_host(conv[0]))
            return CpuWindowInPandasExec(node.key_names, node.win_specs,
                                         ex, node.schema)
        if isinstance(node, L.Window):
            from spark_rapids_tpu.ops.window import (
                CpuWindowExec, TpuWindowExec,
            )
            w0 = node.window_exprs[0]
            part = HashPartitioning(w0.partition_by,
                                    self._shuffle_parts()) \
                if w0.partition_by else SinglePartitioning()
            if on_tpu:
                ex = X.TpuCoalescedShuffleReaderExec(
                    TpuShuffleExchangeExec(part, _to_device(conv[0])))
                return TpuWindowExec(node.window_exprs, node.output_names,
                                     ex, node.schema)
            ex = CpuShuffleExchangeExec(part, _to_host(conv[0]))
            return CpuWindowExec(node.window_exprs, node.output_names,
                                 ex, node.schema)
        raise NotImplementedError(f"cannot convert {node.name}")

    def _make_partitioning(self, node: L.Repartition) -> Partitioning:
        if node.mode == "hash":
            return HashPartitioning(node.keys, node.num_partitions)
        if node.mode == "roundrobin":
            return RoundRobinPartitioning(node.num_partitions)
        if node.mode == "single":
            return SinglePartitioning()
        if node.mode == "range":
            child = node.children[0]
            ordinals = [child.schema.index_of(o.child.column)
                        for o in node.orders]
            return RangePartitioning(node.orders, ordinals,
                                     node.num_partitions)
        raise ValueError(node.mode)

    def _convert_aggregate(self, node: L.Aggregate, child: PhysicalOp,
                           on_tpu: bool) -> PhysicalOp:
        n_parts = self._shuffle_parts()
        if on_tpu:
            child = _to_device(child)
            buf_schema = X._buffer_schema(node.key_names, node.keys,
                                          node.aggs)
            partial = X.TpuHashAggregateExec(
                "update", node.keys, node.key_names, node.aggs, child,
                buf_schema)
            if node.keys:
                keys = [ColumnRef(n, k.dtype, k.nullable)
                        for n, k in zip(node.key_names, node.keys)]
                part = HashPartitioning(keys, n_parts)
            else:
                part = SinglePartitioning()
            exchange = TpuShuffleExchangeExec(part, partial)
            return X.TpuHashAggregateExec(
                "merge", [ColumnRef(n, k.dtype, k.nullable)
                          for n, k in zip(node.key_names, node.keys)],
                node.key_names, node.aggs, exchange, node.schema)
        # CPU: exchange raw rows by key, then full groupby per partition.
        child = _to_host(child)
        if node.keys:
            part = HashPartitioning(node.keys, n_parts)
        else:
            part = SinglePartitioning()
        exchange = CpuShuffleExchangeExec(part, child)
        return C.CpuAggregateExec(node.keys, [], node.aggs, exchange,
                                  node.schema)

    def _convert_sort(self, node: L.Sort, child: PhysicalOp,
                      on_tpu: bool) -> PhysicalOp:
        # Sort keys that are not plain column refs get projected into hidden
        # columns first (Spark does the same materialization for sort exprs).
        orders = node.orders
        schema = node.schema
        hidden = [o for o in orders
                  if not isinstance(o.child, ColumnRef)]
        if hidden:
            base = [ColumnRef(f.name, f.dtype, f.nullable)
                    for f in schema.fields]
            names = [f.name for f in schema.fields]
            extra, new_orders = [], []
            for i, o in enumerate(orders):
                if isinstance(o.child, ColumnRef):
                    new_orders.append(o)
                else:
                    nm = f"__sortkey_{i}"
                    extra.append(o.child)
                    names.append(nm)
                    new_orders.append(SortOrder(
                        ColumnRef(nm, o.child.dtype, o.child.nullable),
                        o.ascending, o.nulls_first))
            proj_schema = T.Schema(
                list(schema.fields) +
                [T.Field(n, e.dtype, e.nullable)
                 for n, e in zip(names[len(schema.fields):], extra)])
            child = (X.TpuProjectExec(base + extra, _to_device(child),
                                      proj_schema) if on_tpu else
                     C.CpuProjectExec(base + extra, _to_host(child),
                                      proj_schema))
            inner = self._convert_sort(
                L.Sort(new_orders, node.is_global, _FakeNode(proj_schema)),
                child, on_tpu)
            final = [ColumnRef(f.name, f.dtype, f.nullable)
                     for f in schema.fields]
            if on_tpu:
                return X.TpuProjectExec(final, inner, schema)
            return C.CpuProjectExec(final, inner, schema)

        key_ordinals = [schema.index_of(o.child.column) for o in orders]
        if node.is_global:
            part = RangePartitioning(orders, key_ordinals,
                                     self._shuffle_parts())
            child = X.TpuCoalescedShuffleReaderExec(
                TpuShuffleExchangeExec(part, _to_device(child))) \
                if on_tpu else CpuShuffleExchangeExec(part, _to_host(child))
        if on_tpu:
            from spark_rapids_tpu.config import SORT_STRING_PREFIX_BYTES
            return X.TpuSortExec(
                orders, [o.child for o in orders], _to_device(child),
                string_prefix_bytes=SORT_STRING_PREFIX_BYTES.get(self.conf))
        return C.CpuSortExec(orders, key_ordinals, _to_host(child))

    # Heuristic average payload per varlen cell (string bytes / array
    # elements x element width) when actual values are not visible.
    _VARLEN_CELL_BYTES = 24

    def _field_width(self, f: T.Field) -> int:
        """Estimated bytes per row for one output column, mirroring the
        device layout the shuffle split accounts (batch.fixed_row_bytes):
        data itemsize + one validity byte, varlen columns a 4-byte offset
        entry + validity + the heuristic payload."""
        import numpy as np
        if f.dtype.is_string or f.dtype.is_array:
            return 5 + self._VARLEN_CELL_BYTES
        return int(np.dtype(f.dtype.np_dtype).itemsize) + 1

    def _estimate_rows(self, node: L.LogicalPlan):
        """Plan-output row estimate (None = unknown: aggregates, joins
        and other cardinality-changing ops make no guess)."""
        if isinstance(node, L.InMemoryScan):
            return sum(hb.num_rows for hb in node.batches)
        if isinstance(node, L.Range):
            return max(0, -(-(node.end - node.start) // node.step))
        if isinstance(node, L.Limit):
            rows = self._estimate_rows(node.children[0])
            return node.n if rows is None else min(node.n, rows)
        if isinstance(node, L.Sample):
            rows = self._estimate_rows(node.children[0])
            return None if rows is None else int(rows * node.fraction)
        if isinstance(node, (L.Project, L.Filter, L.Distinct, L.Sort,
                             L.CachedRelation, L.BroadcastHint)):
            return self._estimate_rows(node.children[0])
        return None

    def _estimate_size(self, node: L.LogicalPlan):
        """Per-column-aware plan-output byte estimate for broadcast
        decisions (the role Spark statistics play for
        GpuBroadcastHashJoinExec planning).  Scans with visible values
        are measured exactly — string/array payloads counted per cell —
        and every other estimable node multiplies its row estimate by
        ITS OWN output schema's per-column widths, so a narrowing
        projection over a wide scan estimates the projected width, not
        the scan's.  The runtime compares these against actual shuffle
        bytes (aqeEstimateErrorPct, parallel/exchange)."""
        if isinstance(node, L.BroadcastHint):
            return 0
        if isinstance(node, L.InMemoryScan):
            import numpy as np
            total = 0
            for hb in node.batches:
                for f, c in zip(hb.schema.fields, hb.columns):
                    if f.dtype.is_string:
                        total += sum(len(str(x)) for x in c.values
                                     if x is not None) + 5 * len(c.values)
                    elif f.dtype.is_array:
                        ew = int(np.dtype(
                            f.dtype.element.np_dtype).itemsize)
                        total += ew * sum(len(x) for x in c.values
                                          if x is not None) + \
                            5 * len(c.values)
                    else:
                        total += c.values.nbytes + len(c.values)
            return total
        if isinstance(node, L.FileScan):
            import os
            try:
                return sum(os.path.getsize(p) for p in node.paths)
            except OSError:
                return None
        rows = self._estimate_rows(node)
        if rows is None:
            return None
        fields = getattr(node.schema, "fields", None)
        if not fields:
            return None
        return rows * sum(self._field_width(f) for f in fields)

    def _convert_join(self, node: L.Join, conv: List[PhysicalOp],
                      on_tpu: bool) -> PhysicalOp:
        left, right = conv
        if node.how == "cross" or not node.left_keys:
            if on_tpu:
                return X.TpuNestedLoopJoinExec(
                    _to_device(left), _to_device(right), node.how,
                    node.condition, node.schema)
            return C.CpuNestedLoopJoinExec(
                _to_host(left), _to_host(right), node.how, node.condition,
                node.schema)
        if on_tpu:
            threshold = int(self.conf.get(
                "spark.sql.autoBroadcastJoinThreshold", 10 * 1024 * 1024))
            l_est = self._estimate_size(node.children[0])
            r_est = self._estimate_size(node.children[1])
            bc_side = None
            if node.how in ("inner", "left", "left_semi", "left_anti") and \
                    r_est is not None and r_est <= threshold:
                bc_side = "right"
            if node.how in ("inner", "right") and l_est is not None and \
                    l_est <= threshold and (
                        bc_side is None or (r_est is None or l_est < r_est)):
                bc_side = "left"
            if bc_side == "right":
                return X.TpuBroadcastHashJoinExec(
                    _to_device(left), _to_device(right), node.left_keys,
                    node.right_keys, node.how, "right", node.condition,
                    node.schema)
            if bc_side == "left":
                return X.TpuBroadcastHashJoinExec(
                    _to_device(right), _to_device(left), node.left_keys,
                    node.right_keys, node.how, "left", node.condition,
                    node.schema)
        n_parts = self._shuffle_parts()
        lpart = HashPartitioning(node.left_keys, n_parts)
        rpart = HashPartitioning(node.right_keys, n_parts)
        if on_tpu:
            lex = TpuShuffleExchangeExec(lpart, _to_device(left))
            rex = TpuShuffleExchangeExec(rpart, _to_device(right))
            # stash the static estimates: the exchange compares them
            # against actual materialized bytes (aqeEstimateErrorPct) so
            # bench runs quantify planner error
            if l_est is not None:
                lex._aqe_est_bytes = l_est
            if r_est is not None:
                rex._aqe_est_bytes = r_est
            return X.TpuShuffledHashJoinExec(
                lex, rex, node.left_keys, node.right_keys, node.how,
                node.condition, node.schema)
        lex = CpuShuffleExchangeExec(lpart, _to_host(left))
        rex = CpuShuffleExchangeExec(rpart, _to_host(right))
        return C.CpuHashJoinExec(lex, rex, node.left_keys, node.right_keys,
                                 node.how, node.condition, node.schema)

    def _convert_limit(self, node: L.Limit, child: PhysicalOp,
                       on_tpu: bool) -> PhysicalOp:
        if on_tpu:
            local = X.TpuLocalLimitExec(node.n, _to_device(child))
            single = TpuShuffleExchangeExec(SinglePartitioning(), local)
            return X.TpuLocalLimitExec(node.n, single)
        local = C.CpuLocalLimitExec(node.n, _to_host(child))
        single = CpuShuffleExchangeExec(SinglePartitioning(), local)
        return C.CpuLocalLimitExec(node.n, single)


#: aggregate functions that skip NULL arguments and do not depend on the
#: order of their rows (``first``/``last`` do): for these a dropped row
#: and a NULL argument are the same thing
_NULL_SKIPPING_AGGS = (Sum, Count, Min, Max, Average)


class _FakeNode:
    """Minimal logical-node stand-in for recursive planner helpers."""

    def __init__(self, schema: T.Schema):
        self._schema = schema
        self.children = ()

    @property
    def schema(self):
        return self._schema


def _pushdown_scan_filters(plan: L.LogicalPlan) -> L.LogicalPlan:
    """Push Filter conjuncts into a child FileScan so the parquet reader can
    skip row groups on statistics and prune partition directories
    (GpuParquetScan.scala:217-281 filterBlocks role).  Advisory: the Filter
    stays in place for exact row filtering.

    Non-mutating: untouched subtrees return the ORIGINAL nodes (the
    user-held plan object never changes, and the session's fingerprint
    cache — computed on the pre-rewrite tree — stays hittable)."""
    import copy

    from spark_rapids_tpu.io.scan import extract_pushdown_descriptors
    new_children = [_pushdown_scan_filters(c) for c in plan.children]
    changed = any(n is not o for n, o in zip(new_children, plan.children))
    if isinstance(plan, L.Filter) and \
            isinstance(new_children[0], L.FileScan):
        scan = new_children[0]
        conjuncts = _split_conjuncts(plan.condition)
        pushable = [c for c in conjuncts
                    if extract_pushdown_descriptors([c])]
        if pushable:
            new_scan = L.FileScan(scan.fmt, scan.paths, scan.schema,
                                  scan.options, pushed_filters=pushable,
                                  partitions=scan.partitions)
            return L.Filter(plan.condition, new_scan)
    if not changed:
        return plan
    clone = copy.copy(plan)
    clone.children = tuple(new_children)
    return clone


def _constant_literal(e: Expression) -> Optional[Literal]:
    """The :class:`Literal` a foldable ``e`` evaluates to on the CPU
    oracle — by definition the value the unfolded plan computes on every
    row — typed by the expression's resolved dtype (DATE stays DATE, held
    as days since the epoch); None when ``cpu_eval`` raises or answers
    in another type, and the subtree stays as written."""
    import numpy as np
    from spark_rapids_tpu.batch import HostBatch
    one_row = HostBatch.from_pydict({"__fold": (T.INT, [0])})
    try:
        v = e.cpu_eval(CpuEvalCtx(one_row))
    except Exception:
        return None
    if v.dtype != e.dtype:
        return None
    if not v.validity[0]:
        return Literal(None, e.dtype)
    value = v.values[0]
    return Literal(value.item() if isinstance(value, np.generic) else value,
                   e.dtype)


def _fold_constants(plan: L.LogicalPlan
                    ) -> Tuple[L.LogicalPlan, List[str]]:
    """Constant folding (Catalyst's ``ConstantFolding``; Spark never ships
    ``to_date('1994-01-01')`` to an executor, this engine plans for itself
    and has to do it): in every expression a logical node carries, replace
    each MAXIMAL foldable subtree that is not already a literal by the
    Literal it evaluates to.  Returns the plan and one
    ``<expr> -> lit(<value>:<type>)`` per fold.

    Non-mutating, like :func:`_pushdown_scan_filters`: untouched
    expressions, lists and subtrees come back as the ORIGINAL objects, so
    a plan with nothing to fold is returned itself.  Output names live on
    the nodes (``Project.names``, ``AggregateExpression.output_name``) or
    in an ``Alias``, which is never folded away, so no column is renamed."""
    import copy
    folded: List[str] = []

    def fold_expr(e: Expression) -> Expression:
        if isinstance(e, Literal):
            return e
        if e.foldable:
            lit = _constant_literal(e)
            if lit is not None:
                folded.append(f"{e!r} -> lit({lit.value!r}:{lit.dtype})")
                return lit
            return e
        kids = [fold_expr(c) for c in e.children]
        if all(n is o for n, o in zip(kids, e.children)):
            return e
        return e.with_children(kids)

    def fold_value(v):
        if isinstance(v, Expression):
            return fold_expr(v)
        if isinstance(v, L.LogicalPlan):
            return fold_node(v)
        if isinstance(v, SortOrder):
            child = fold_expr(v.child)
            return v if child is v.child else \
                SortOrder(child, v.ascending, v.nulls_first)
        if isinstance(v, AggregateExpression):
            fn = fold_expr(v.fn)
            return v if fn is v.fn else AggregateExpression(fn, v.output_name)
        if isinstance(v, (list, tuple)):
            new = [fold_value(x) for x in v]
            return v if all(n is o for n, o in zip(new, v)) else type(v)(new)
        return v

    def fold_node(node: L.LogicalPlan) -> L.LogicalPlan:
        new = {k: fold_value(v) for k, v in vars(node).items()}
        if all(new[k] is v for k, v in vars(node).items()):
            return node
        clone = copy.copy(node)
        vars(clone).update(new)
        return clone

    return fold_node(plan), folded


def _filters_into_keyless_aggregates(plan: L.LogicalPlan
                                     ) -> Tuple[L.LogicalPlan,
                                                List[Expression]]:
    """``SELECT sum(x) … WHERE p`` plans as ``sum(if(p, x, NULL))`` over
    the filter's input: a Filter directly under a keyless Aggregate whose
    functions all skip NULL arguments becomes a condition on each
    argument, and the Filter node goes.  The reduction emits its one row
    either way, a row the filter drops contributes a NULL, which those
    functions skip — and the device no longer compacts the surviving rows
    of every column the aggregate reads (one million-row gather a column
    and batch: 89 % of TPC-H Q6's device time, PERF.md, PR 27) only to sum
    them.  A keyed aggregate keeps its Filter: a group whose rows are all
    dropped must not appear.  Returns the plan and each absorbed condition.

    Only where the rewrite cannot show: every function is one of
    :data:`_NULL_SKIPPING_AGGS` over a non-string argument (``if`` has no
    device form for strings), and neither the condition nor an argument
    holds an expression that is not ``context_free`` (a ``rand()`` would
    be drawn once per function, and for other rows).  Non-mutating, like
    :func:`_pushdown_scan_filters`; it runs after the pushdown, so a file
    scan keeps the conjuncts it skips row groups by."""
    import copy
    absorbed: List[Expression] = []

    def absorbable(agg: L.Aggregate, cond: Expression) -> bool:
        return bool(agg.aggs) and deterministic(cond) and all(
            type(a.fn) in _NULL_SKIPPING_AGGS
            and not a.fn.child.dtype.is_string
            and deterministic(a.fn.child) for a in agg.aggs)

    def rewrite(node: L.LogicalPlan) -> L.LogicalPlan:
        children = tuple(rewrite(c) for c in node.children)
        if any(n is not o for n, o in zip(children, node.children)):
            node = copy.copy(node)
            node.children = children
        while isinstance(node, L.Aggregate) and not node.keys and \
                isinstance(node.children[0], L.Filter) and \
                absorbable(node, node.children[0].condition):
            below = node.children[0]
            cond = below.condition
            absorbed.append(cond)
            node = L.Aggregate([], [], [
                AggregateExpression(a.fn.with_children([If(
                    cond, a.fn.child, Literal(None, a.fn.child.dtype))]),
                    a.output_name)
                for a in node.aggs], below.children[0])
        return node

    return rewrite(plan), absorbed


def _compile_plan_udfs(plan: L.LogicalPlan) -> L.LogicalPlan:
    """udf-compiler analogue (udf-compiler/Plugin.scala:36-94): rewrite
    PythonUDF calls into engine expressions where bytecode compilation
    succeeds; silently keep the UDF (and its CPU fallback) otherwise."""
    from spark_rapids_tpu.exprs.python_udf import PythonUDF
    from spark_rapids_tpu.udf.compiler import CannotCompile, compile_udf

    def fix_expr(e):
        def fn(node):
            if isinstance(node, PythonUDF) and type(node) is PythonUDF:
                try:
                    return compile_udf(node.fn, list(node.children))
                except CannotCompile:
                    return node
            return node
        return e.transform_up(fn)

    new_children = [_compile_plan_udfs(c) for c in plan.children]
    if isinstance(plan, L.Project):
        return L.Project([fix_expr(e) for e in plan.exprs], plan.names,
                         new_children[0])
    if isinstance(plan, L.Filter):
        return L.Filter(fix_expr(plan.condition), new_children[0])
    # other nodes: rebuild children in place
    plan.children = tuple(new_children)
    return plan


def _is_map_like(op: PhysicalOp) -> bool:
    return isinstance(op, (X.TpuProjectExec, X.TpuFilterExec,
                           X.TpuFusedMapExec)) and len(op.children) == 1


def _map_fns(op: PhysicalOp):
    if isinstance(op, X.TpuFusedMapExec):
        return op.fns, op.labels
    return [op.batch_fn], [op.name]


def _fuse_map_chains(op: PhysicalOp) -> PhysicalOp:
    """Dispatch-count optimizer: collapse chains of per-batch map ops into
    one compiled program, and absorb map chains into the per-batch programs
    of aggregation/sort/exchange consumers.  One XLA dispatch then covers
    e.g. filter+project+partial-aggregate — XLA fuses the elementwise work
    into the aggregation's sort pass, and host->device dispatch latency is
    paid once per batch instead of once per operator."""
    from spark_rapids_tpu.parallel.partitioning import (
        HashPartitioning, RoundRobinPartitioning,
    )
    op.children = [_fuse_map_chains(c) for c in op.children]

    if _is_map_like(op) and op.children and _is_map_like(op.children[0]):
        child = op.children[0]
        cf, cl = _map_fns(child)
        of, ol = _map_fns(op)
        return X.TpuFusedMapExec(child.children[0], cf + of,
                                 op.output_schema, cl + ol)

    absorb_ok = (
        (isinstance(op, X.TpuHashAggregateExec) and op.mode == "update") or
        isinstance(op, X.TpuSortExec) or
        (isinstance(op, TpuShuffleExchangeExec) and
         isinstance(op.partitioning,
                    (HashPartitioning, RoundRobinPartitioning)))
    )
    if absorb_ok and op.children and _is_map_like(op.children[0]):
        child = op.children[0]
        fns, _ = _map_fns(child)
        op.absorb_input(fns)
        op.children = [child.children[0]]
    return op


def _to_device(op: PhysicalOp) -> PhysicalOp:
    return op if op.is_tpu else HostToDeviceExec(op)


def _to_host(op: PhysicalOp) -> PhysicalOp:
    return DeviceToHostExec(op) if op.is_tpu else op


def _insert_transitions(op: PhysicalOp) -> PhysicalOp:
    """Final pass: make every edge type-correct (device vs host batches) —
    the GpuTransitionOverrides analogue."""
    new_children = []
    for c in op.children:
        c = _insert_transitions(c)
        if op.is_tpu and not c.is_tpu and \
                not isinstance(op, HostToDeviceExec):
            c = HostToDeviceExec(c)
        elif not op.is_tpu and c.is_tpu and \
                not isinstance(op, DeviceToHostExec):
            c = DeviceToHostExec(c)
        new_children.append(c)
    op.children = new_children
    return op
