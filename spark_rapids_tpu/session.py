"""TpuSparkSession: the user entry point (the analogue of a Spark session
with the rapids plugin installed — SQLPlugin + RapidsExecutorPlugin,
Plugin.scala:106-146).

Construction initializes the device runtime once per process: device
discovery, the TpuSemaphore (device admission), and the spill-tier catalog —
mirroring RapidsExecutorPlugin.init (Plugin.scala:122-146).
"""

from __future__ import annotations

import os
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import HostBatch
from spark_rapids_tpu.config import RapidsConf, conf as global_conf


class _PlanNotes:
    """What the session keeps per logical-plan OBJECT (weakly): the wall
    of the ``sql()`` call that parsed it, and its shape — the rewritten,
    folded and slotted plan with its fingerprint and values — so a held
    DataFrame's next ``collect()`` neither folds nor fingerprints again
    (a logical plan is immutable once a DataFrame holds it)."""

    __slots__ = ("parse_ns", "shaped", "__weakref__")

    def __init__(self):
        self.parse_ns = 0
        self.shaped = None   # (rewrite flags, PlanShape, RewriteNotes)


_PLAN_NOTES: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_PLAN_NOTES_LOCK = threading.Lock()


def _plan_notes(plan) -> _PlanNotes:
    with _PLAN_NOTES_LOCK:
        notes = _PLAN_NOTES.get(plan)
        if notes is None:
            notes = _PLAN_NOTES[plan] = _PlanNotes()
        return notes


class _MetricsFrame:
    """Per-call holder for one query's metrics dict.

    ``execute_with_metrics`` fills a frame, then publishes it to
    ``session.last_metrics`` with a single reference assignment — the
    serving runtime runs N executes against one session, and filling
    ``self.last_metrics`` in place would let a concurrent reader observe
    a half-written mixture of two queries."""

    __slots__ = ("last_metrics",)

    def __init__(self, op_metrics: Dict[str, Any]):
        self.last_metrics: Dict[str, Any] = op_metrics


# process-wide session numbering: event-log headers stamp it so
# rapidsprof can group one shared log's queries by the session that ran
# them (query ids are already process-globally unique)
_SESSION_SEQ_LOCK = threading.Lock()
_SESSION_SEQ = 0


def _next_session_id() -> int:
    global _SESSION_SEQ
    with _SESSION_SEQ_LOCK:
        _SESSION_SEQ += 1
        return _SESSION_SEQ


class TpuSparkSession:
    _lock = threading.Lock()
    _active: Optional["TpuSparkSession"] = None

    def __init__(self, conf: Optional[RapidsConf] = None,
                 use_device: bool = True):
        self.conf = conf or global_conf.copy()
        self.session_id = _next_session_id()
        from spark_rapids_tpu.config import COMPILE_CACHE_DIR
        cache_dir = COMPILE_CACHE_DIR.get(self.conf)
        if cache_dir:
            from spark_rapids_tpu.utils.compile_registry import (
                enable_persistent_cache,
            )
            enable_persistent_cache(cache_dir)
        from spark_rapids_tpu.runtime.device import DeviceRuntime
        self.runtime = DeviceRuntime.get(self.conf) if use_device else None
        self._views: Dict[str, Any] = {}
        # bounded per-query observability profiles (obs.profile), newest
        # last; see query_history() / explain_last().  Guarded by
        # _history_lock: the serving runtime executes on N threads
        # against one session.
        self._query_history: List[Any] = []
        self._history_lock = threading.Lock()
        # last completed query's metrics; REPLACED wholesale per query
        # (never mutated in place) so concurrent readers see a
        # consistent dict
        self.last_metrics: Dict[str, Any] = {}
        # the logical-plan -> physical-plan memo is process-wide
        # (serve.excache.SharedPlanCache): N sessions serving the same
        # query shape share exec instances and therefore every compiled
        # executable.  Size it from this session's conf.
        from spark_rapids_tpu.config import SERVE_PLAN_CACHE_MAX
        from spark_rapids_tpu.serve.excache import shared_plan_cache
        shared_plan_cache().set_max_plans(SERVE_PLAN_CACHE_MAX.get(self.conf))
        with TpuSparkSession._lock:
            TpuSparkSession._active = self

    # -- catalog ------------------------------------------------------------

    def register_view(self, name: str, df) -> None:
        self._views[name.lower()] = df

    def table(self, name: str):
        df = self._views.get(name.lower())
        if df is None:
            raise KeyError(f"table or view not found: {name}")
        return df

    # -- builders -----------------------------------------------------------

    @classmethod
    def builder(cls) -> "SessionBuilder":
        return SessionBuilder()

    @classmethod
    def active(cls) -> "TpuSparkSession":
        with cls._lock:
            if cls._active is None:
                cls._active = TpuSparkSession()
            return cls._active

    # -- conf ---------------------------------------------------------------

    def set_conf(self, key: str, value: Any) -> "TpuSparkSession":
        self.conf.set(key, value)
        return self

    # -- data sources -------------------------------------------------------

    def create_dataframe(self, data, schema=None, num_partitions: int = 1):
        """Build a DataFrame from a pydict {name: (dtype, values)} /
        {name: values} / list of row tuples + schema."""
        from spark_rapids_tpu.dataframe import DataFrame
        from spark_rapids_tpu.plan.logical import InMemoryScan
        batch = _to_host_batch(data, schema)
        return DataFrame(InMemoryScan([batch], batch.schema, num_partitions),
                         self)

    def range(self, start: int, end: Optional[int] = None, step: int = 1,
              num_partitions: int = 1):
        from spark_rapids_tpu.dataframe import DataFrame
        from spark_rapids_tpu.plan.logical import Range
        if end is None:
            start, end = 0, start
        return DataFrame(Range(start, end, step, num_partitions), self)

    @property
    def read(self) -> "DataFrameReader":
        return DataFrameReader(self)

    def sql(self, query: str):
        from spark_rapids_tpu.sql.parser import parse_sql
        from spark_rapids_tpu.utils.tracing import span
        with span("plan", "parse") as sp:
            df = parse_sql(query, self)
        # outside queryWallNs: carried to the plan's queries as parseNs
        _plan_notes(df.plan).parse_ns = sp.elapsed_ns
        return df

    # -- execution ----------------------------------------------------------

    def plan_physical(self, plan):
        """The physical plan of :meth:`plan_bound`, for a caller that only
        inspects it.  Executing it takes the bound parameters too."""
        return self.plan_bound(plan)[0]

    def plan_bound(self, plan):
        """Lower a logical plan, memoized per (plan SHAPE fingerprint,
        conf state) — the canonicalized-plan-reuse role
        (GpuOverrides + Spark plan canonicalization): two structurally
        identical DataFrames (e.g. ``df.count()`` called twice, each
        building a fresh Aggregate node) share one physical plan and
        therefore every compiled XLA kernel — and so do two queries that
        differ only in the values of their liftable literals
        (``plan/logical.plan_shape``): the same SQL text with other
        parameters.  The memo is PROCESS-wide (serve.excache) and lives
        by LRU: every session serving the same shape shares one physical
        plan, so only the first execution anywhere in the process
        compiles.

        Returns ``(phys, bound, facts)``: the shared plan, THIS plan's
        literal values as :class:`~spark_rapids_tpu.utils.params.
        BoundParams` — whoever drives ``phys`` does so under
        ``params.executing(bound)`` — and the plan facts a query
        publishes (``planShapeHit``, ``boundParams``, ``bakedLiterals``,
        ``foldedExprs``, ``pushedJoinFilters``, ``joinKeysFromWhere``,
        ``parseNs``, ``planShapeNs``, ``planBindNs``)."""
        from spark_rapids_tpu.plan.logical import plan_shape
        from spark_rapids_tpu.plan.overrides import TpuOverrides
        from spark_rapids_tpu.serve.excache import (
            PlanEntry, shared_plan_cache,
        )
        from spark_rapids_tpu.utils.tracing import span
        notes = _plan_notes(plan)
        overrides = TpuOverrides(self.conf)
        # the logical rewrites read two conf keys; ICI-mesh programs take
        # sharded globals and no bound parameters, so under a mesh every
        # literal stays baked (the plan is keyed on its values, as ever)
        lift = self._shuffle_mesh() is None
        flags = (self.conf.get("spark.rapids.sql.udfCompiler.enabled", False),
                 self.conf.get("spark.rapids.sql.scan.pushdown.enabled", True),
                 lift)
        with span("plan", "shape") as shape_span:
            if notes.shaped is None or notes.shaped[0] != flags:
                rewritten, rewrites = overrides.rewrite_logical(plan)
                notes.shaped = (flags, plan_shape(rewritten, lift=lift),
                                rewrites)
            _flags, shape, rewrites = notes.shaped
        # obs knobs never change the plan: excluding them keeps the memo
        # (and therefore every compiled kernel) hittable when a
        # measurement run toggles the observability bus
        conf_state = tuple(sorted(
            (k, str(v)) for k, v in self.conf._settings.items()
            if not k.startswith("spark.rapids.sql.tpu.obs.")))

        def _build():
            phys = overrides.lower(shape.plan, rewrites)
            return PlanEntry(phys, overrides.explain, shape.dtypes,
                             shape.pinned)

        entry, hit = shared_plan_cache().get_or_build(
            shape.fingerprint, conf_state, _build)
        with span("plan", "bind") as bind_span:
            bound = entry.bind(shape.values)
        self._explained = (entry.explain, rewrites, shape.values)
        return entry.phys, bound, {
            "planShapeHit": int(hit), "boundParams": len(shape.values),
            "bakedLiterals": shape.baked,
            "foldedExprs": len(rewrites.folded),
            "pushedJoinFilters": rewrites.pushed_join_filters,
            "joinKeysFromWhere": rewrites.join_keys_from_where,
            "parseNs": notes.parse_ns,
            "planShapeNs": shape_span.elapsed_ns,
            "planBindNs": bind_span.elapsed_ns}

    @property
    def last_explain(self) -> str:
        """The explain output of the last plan this session lowered or
        found in the plan cache, with THAT query's literal values and
        folds (the tagging lines are its shape's)."""
        explained = getattr(self, "_explained", None)
        if explained is None:
            return ""
        explain, rewrites, values = explained
        return explain.render(rewrites, values)

    def _plan_and_context(self, plan):
        """Everything between entry and the first dispatch: conf-shaped
        process state, the physical plan (memo probe or lowering) and
        this query's :class:`ExecContext`."""
        from spark_rapids_tpu.config import (
            OBS_TELEMETRY_ENABLED, OBS_TELEMETRY_INTERVAL_MS,
            OBS_TELEMETRY_MAX_INTERVALS,
        )
        from spark_rapids_tpu import history as qhistory
        from spark_rapids_tpu.kernels import pallas_tier
        from spark_rapids_tpu.obs import timeseries as obs_ts
        from spark_rapids_tpu.plan.physical import ExecContext
        # (re)shape the process telemetry ring from this session's conf
        # and (re)register the engine gauges — a repeat execute with the
        # same shape keeps the live ring and its accumulated intervals
        obs_ts.configure(OBS_TELEMETRY_ENABLED.get(self.conf),
                         OBS_TELEMETRY_INTERVAL_MS.get(self.conf),
                         OBS_TELEMETRY_MAX_INTERVALS.get(self.conf))
        self._register_telemetry_gauges()
        # the Pallas kernel tier consults this session's conf for its
        # per-kernel gates at trace time (kernels.pallas_tier)
        pallas_tier.configure(self.conf)
        phys, bound, facts = self.plan_bound(plan)
        if self.conf.test_enforce_tpu:
            _assert_on_tpu(phys)
        if self.runtime is not None:
            # re-resolve: a device-lost recovery mid-query rebuilds the
            # process runtime (new semaphore/device, same catalog) — the
            # next query must ride the live instance, not the dead one
            from spark_rapids_tpu.runtime.device import DeviceRuntime
            self.runtime = DeviceRuntime.get(self.conf)
        ctx = ExecContext(
            self.conf,
            semaphore=self.runtime.semaphore if self.runtime else None,
            device=self.runtime.device if self.runtime else None,
            mesh=self._shuffle_mesh())
        # the fault-recovery CPU fallback re-lowers THIS logical plan
        # with sql.enabled=false to replay a failed partition on the CPU
        # operator path (fault.recovery)
        ctx.logical_plan = plan
        # this execution's literal values (the shared plan holds slots)
        # and the plan facts _query_metrics publishes
        ctx.bound_params = bound
        ctx.plan_facts = facts
        self.last_physical_plan = phys
        self.last_exec_ctx = ctx
        # query-intelligence hooks (history/): seed the plan from the
        # statistics store and arm the fragment-cache key on the context
        # — a single conf read when no history dir is configured
        qhistory.begin_query(self, plan, phys, ctx)
        return phys, ctx

    def _shuffle_mesh(self):
        """The >1-device mesh for the ICI collective shuffle, or None.

        Opt-in via spark.rapids.shuffle.ici.enabled (the reference's
        accelerated UCX shuffle is likewise explicitly configured:
        RapidsShuffleManager in docs/get-started).  On a single-chip
        process this is always None and exchanges use the host path.
        """
        from spark_rapids_tpu.config import ENABLE_ICI_SHUFFLE
        if not ENABLE_ICI_SHUFFLE.get(self.conf):
            return None
        if not hasattr(self, "_mesh"):
            import jax
            from spark_rapids_tpu.parallel.mesh_shuffle import make_mesh
            self._mesh = make_mesh() if len(jax.devices()) > 1 else None
        return self._mesh

    def execute(self, plan) -> HostBatch:
        out, _metrics = self.execute_with_metrics(plan)
        return out

    def execute_with_metrics(self, plan) -> Tuple[HostBatch, Dict[str, Any]]:
        """Execute and return ``(rows, this query's metrics dict)``.

        ``self.last_metrics`` is also published (one reference
        assignment, so concurrent executes on a shared session never
        expose a half-written dict), but under concurrency only the
        returned dict is guaranteed to describe THIS call — the serving
        scheduler uses it for per-tenant rollups."""
        from spark_rapids_tpu.config import (
            FAULTS_SPEC, OBS_ENABLED, OBS_RING_MAX_EVENTS,
        )
        from spark_rapids_tpu.fault import inject as fault_inject
        from spark_rapids_tpu.fault import metrics as FM
        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.kernels import pallas_tier
        from spark_rapids_tpu.plan.physical import collect_host
        from spark_rapids_tpu.utils import compile_registry as CR
        from spark_rapids_tpu.utils import params
        from spark_rapids_tpu.utils.tracing import span
        # the query wall is partitioned from HERE to the stamp taken just
        # before critpath.compute: the scope (event ring, this query's
        # counters and fault registry under concurrent serving) opens
        # first, so planning's span and everything after it land inside
        t_query0 = time.monotonic_ns()
        obs_token = obs_events.begin_query(
            enabled=OBS_ENABLED.get(self.conf),
            max_events=OBS_RING_MAX_EVENTS.get(self.conf))
        try:
            with span("plan", "physical"):
                phys, ctx = self._plan_and_context(plan)
        except BaseException:
            # a plan the test mode refuses must not leak its scope into
            # the next query's window
            obs_events.end_query(obs_token)
            raise
        # (re)install the deterministic fault registry per query (on the
        # scope just opened, so concurrent queries keep separate specs):
        # call counters reset so "the Nth dispatch" is query-relative;
        # an empty spec clears any previously installed registry, and
        # the finally clears an armed one so persistent @N+ rules cannot
        # outlive the query and fire at sites with no recovery around
        # them (e.g. ml.to_device_batches staging outside execute)
        spec = FAULTS_SPEC.get(self.conf)
        fault_inject.install(spec)
        before = CR.snapshot()
        fm_before = FM.snapshot()
        pt_before = pallas_tier.fallback_count()
        cat_before = dict(self.runtime.catalog.metrics) \
            if self.runtime is not None else {}
        try:
            with params.executing(ctx.bound_params):
                out = collect_host(phys, ctx)
        except BaseException:
            # close the scope so a failed query can't leak its bus into
            # the next query's window
            obs_events.end_query(obs_token)
            raise
        finally:
            if spec:
                fault_inject.uninstall()
        # the answer is on the host: what follows is the engine's own
        # bookkeeping (snapshot deltas, ~80 last_metrics keys, the
        # history record and its regression sentinel)
        with span("bookkeeping", "metrics") as bookkeeping:
            frame = self._query_metrics(
                plan, phys, ctx, out, obs_token,
                (before, fm_before, pt_before, cat_before),
                bookkeeping.t0 - t_query0)
        # drain the obs epoch and fold it into a bounded-history profile
        # (obs.profile); the event counts become metrics so tests and
        # bench can assert the bus's own economics
        obs_events_list, obs_dropped, obs_dropped_by_site = \
            obs_events.end_query(obs_token)
        frame.last_metrics["obsEventCount"] = len(obs_events_list)
        frame.last_metrics["obsEventsDropped"] = obs_dropped
        # exact wall decomposition (obs.critpath): the segments partition
        # [t_query0, t_query1) — entry to this stamp — so they sum to
        # queryWallNs EXACTLY
        from spark_rapids_tpu.obs import critpath as obs_critpath
        t_query1 = time.monotonic_ns()
        cp = obs_critpath.compute(obs_events_list, t_query0, t_query1)
        frame.last_metrics["critpathAttributedNs"] = cp.attributed_ns
        frame.last_metrics["critpath"] = dict(cp.segments)
        frame.last_metrics["queryWallNs"] = t_query1 - t_query0
        # publish by one reference assignment: a concurrent reader of
        # self.last_metrics sees the previous complete dict or this one,
        # never a half-filled frame
        self.last_metrics = frame.last_metrics
        if obs_token is not None and obs_token.bus is not None:
            self._record_profile(obs_token.query_id, obs_events_list,
                                 obs_dropped, t_query1 - t_query0,
                                 frame.last_metrics,
                                 dropped_by_site=obs_dropped_by_site,
                                 qt0_ns=t_query0, qt1_ns=t_query1)
        return out, frame.last_metrics

    def _query_metrics(self, plan, phys, ctx, out, obs_token, befores,
                       answer_wall_ns: int) -> "_MetricsFrame":
        """The ``last_metrics`` frame of one query: this query's counter
        deltas against the ``befores`` snapshots taken before it ran, the
        per-operator metrics summed, and the history record with its
        regression sentinel (the ``bookkeeping`` span's body)."""
        from spark_rapids_tpu.fault import metrics as FM
        from spark_rapids_tpu.obs import timeseries as obs_ts
        from spark_rapids_tpu import history as qhistory
        from spark_rapids_tpu.kernels import pallas_tier
        from spark_rapids_tpu.utils import compile_registry as CR
        before, fm_before, pt_before, cat_before = befores
        if obs_token is not None:
            # per-scope counters: exactly this query's activity, even
            # with N queries in flight (the global snapshot delta would
            # mix them)
            d = obs_token.counters_for(before)
            fm_d = obs_token.counters_for(fm_before)
        else:
            # nested execute (prewarm, recovery re-lowering) rides the
            # outer scope: fall back to the historical global deltas
            fm_d = FM.delta(fm_before, FM.snapshot())
            d = CR.delta(before, CR.snapshot())
        frame = _MetricsFrame({
            op: {name: m.value for name, m in ms.items()}
            for op, ms in ctx.metrics.items()})
        # compile/dispatch economics for THIS query (process-wide counters
        # snapshotted around the collect; compiledShapes is the cumulative
        # compiled-executable cardinality the bucket policy bounds)
        # kernel-tier economics: XLA fallbacks the Pallas tier took at
        # trace time during this query (backend/budget/lowering failure;
        # cached executables trace nothing and count nothing)
        frame.last_metrics["pallasFallbackCount"] = \
            pallas_tier.fallback_count() - pt_before
        # planning facts of THIS query (session.plan_bound): whether its
        # shape was in the plan cache, how many literal values it bound
        # and how many stayed baked, the constant subtrees folded for it,
        # the wall of the sql() call that parsed it (outside queryWallNs)
        # and of the shape and bind spans (inside critpath's ``plan``)
        facts = ctx.plan_facts
        frame.last_metrics["planShapeHit"] = facts["planShapeHit"]
        frame.last_metrics["boundParams"] = facts["boundParams"]
        frame.last_metrics["bakedLiterals"] = facts["bakedLiterals"]
        frame.last_metrics["foldedExprs"] = facts["foldedExprs"]
        frame.last_metrics["pushedJoinFilters"] = facts["pushedJoinFilters"]
        frame.last_metrics["joinKeysFromWhere"] = facts["joinKeysFromWhere"]
        frame.last_metrics["parseNs"] = facts["parseNs"]
        frame.last_metrics["planShapeNs"] = facts["planShapeNs"]
        frame.last_metrics["planBindNs"] = facts["planBindNs"]
        frame.last_metrics["compileCount"] = d["compiles"]
        frame.last_metrics["compileWallNs"] = d["compile_wall_ns"]
        frame.last_metrics["dispatchCount"] = d["dispatches"]
        # the compile wall by phase (jax.monitoring, routed by event
        # name): tracing, MLIR lowering, XLA compiling, persistent-cache
        # loads — disjoint, and together no more than compileWallNs
        frame.last_metrics["jaxTraceNs"] = d["trace_ns"]
        frame.last_metrics["lowerNs"] = d["lower_ns"]
        frame.last_metrics["backendCompileNs"] = d["backend_compile_ns"]
        frame.last_metrics["compileCacheLoadNs"] = d["cache_load_ns"]
        frame.last_metrics["compileCacheHits"] = d["cache_hits"]
        frame.last_metrics["compileCacheMisses"] = d["cache_misses"]
        frame.last_metrics["compiledShapes"] = CR.compiled_shapes()
        # data-plane economics: input bytes donated to dispatches (HBM
        # reused for outputs) and the host<->device staging volume/time
        frame.last_metrics["donatedBytes"] = d["donated_bytes"]
        frame.last_metrics["h2dBytes"] = d["h2d_bytes"]
        frame.last_metrics["h2dTimeNs"] = d["h2d_ns"]
        frame.last_metrics["d2hBytes"] = d["d2h_bytes"]
        frame.last_metrics["d2hTimeNs"] = d["d2h_ns"]
        # shuffle split economics, summed over every exchange op: split
        # programs dispatched, blocking host syncs paid, catalog pieces
        # registered, and the bytes the split moved
        frame.last_metrics["shuffleSplitDispatches"] = sum(
            ms["shuffleSplitDispatches"].value for ms in ctx.metrics.values()
            if "shuffleSplitDispatches" in ms)
        frame.last_metrics["shuffleSyncs"] = sum(
            ms["shuffleSyncs"].value for ms in ctx.metrics.values()
            if "shuffleSyncs" in ms)
        frame.last_metrics["shufflePieces"] = sum(
            ms["shufflePieces"].value for ms in ctx.metrics.values()
            if "shufflePieces" in ms)
        frame.last_metrics["shuffleBytes"] = sum(
            ms["shuffleBytes"].value for ms in ctx.metrics.values()
            if "shuffleBytes" in ms)
        # dict-aware shuffle economics: materialized string bytes the
        # split did NOT move because pieces stayed dictionary-encoded
        # (codes + merged dictionary instead of raw bytes); 0 when the
        # query shuffled no encoded columns or dictAware is off
        frame.last_metrics["shuffleEncodedBytesSaved"] = sum(
            ms["shuffleEncodedBytesSaved"].value
            for ms in ctx.metrics.values()
            if "shuffleEncodedBytesSaved" in ms)
        # mesh-SPMD economics (parallel.mesh_spmd): whole-stage programs
        # dispatched, exchange boundaries fused into them (each one is a
        # shuffle that ran as an in-program all_to_all with ZERO host
        # syncs), and which backend the shuffle mesh actually ran on —
        # bench consumers must not mislabel a CPU-virtual-device curve
        # as TPU ICI scaling
        frame.last_metrics["meshProgramDispatches"] = sum(
            ms["meshProgramDispatches"].value for ms in ctx.metrics.values()
            if "meshProgramDispatches" in ms)
        frame.last_metrics["meshBoundariesFused"] = sum(
            ms["meshBoundariesFused"].value for ms in ctx.metrics.values()
            if "meshBoundariesFused" in ms)
        # mesh-SPMD v2: joins compiled INTO fused stage programs (static
        # bucketed output sizing, no host sync), stages that overflowed a
        # bucket and transparently reran host-driven, and the string
        # bytes mesh exchanges materialized out of dictionary encoding
        # (the wire moves decoded rows — the give-up side of the scan's
        # dict corridor at mesh boundaries)
        frame.last_metrics["meshJoinsFused"] = sum(
            ms["meshJoinsFused"].value for ms in ctx.metrics.values()
            if "meshJoinsFused" in ms)
        frame.last_metrics["meshFallbacks"] = sum(
            ms["meshFallbacks"].value for ms in ctx.metrics.values()
            if "meshFallbacks" in ms)
        frame.last_metrics["meshEncodedMaterializedBytes"] = sum(
            ms["meshEncodedMaterializedBytes"].value
            for ms in ctx.metrics.values()
            if "meshEncodedMaterializedBytes" in ms)
        _mesh = self._shuffle_mesh()
        frame.last_metrics["meshBackend"] = (
            str(next(iter(_mesh.devices.flat)).platform)
            if _mesh is not None else "")
        # scan/ingest economics (io.scan_v2), summed over every scan op:
        # decode wall across pool workers, the part of it hidden behind
        # the consumer's H2D/compute, decoded volume, dictionary-encoded
        # column instances staged, and late-mat chunks skipped entirely
        def _scan_sum(key):
            return sum(ms[key].value for ms in ctx.metrics.values()
                       if key in ms)
        # aggregate economics (TpuHashAggregateExec): update batches that
        # took the slot contraction (keyed) or the reduction (keyless), of
        # the update batches keyed / keyless aggregates saw in all; and
        # the batches a TpuFilterExec compacted (kernels/layout.compact);
        # and what the equi-joins read back to size their outputs: the
        # candidate pairs, the number of such reads, and a side's rows
        # where the host held them already (it concatenated the side)
        for key in ("mxuAggBatches", "keyedUpdateBatches",
                    "keylessAggBatches", "keylessUpdateBatches",
                    "filterCompactedBatches", "joinPairs", "joinSizeReads",
                    "joinProbeRows", "joinBuildRows"):
            frame.last_metrics[key] = _scan_sum(key)
        frame.last_metrics["scanDecodeWallNs"] = _scan_sum("scanDecodeWallNs")
        frame.last_metrics["scanH2dOverlapNs"] = _scan_sum("scanH2dOverlapNs")
        frame.last_metrics["scanBytesDecoded"] = _scan_sum("scanBytesDecoded")
        frame.last_metrics["scanDictColumns"] = _scan_sum("scanDictColumns")
        frame.last_metrics["scanChunksSkipped"] = _scan_sum("scanChunksSkipped")
        # adaptive read-ahead: the deepest effective depth any scan op's
        # controller reached this query (equals the static conf when the
        # user pinned scan.readAhead.depth explicitly)
        _depths = [ms["readaheadDepthEffective"].value
                   for ms in ctx.metrics.values()
                   if "readaheadDepthEffective" in ms]
        frame.last_metrics["readaheadDepthEffective"] = \
            max(_depths) if _depths else 0
        # adaptive-execution economics (plan/adaptive), summed over every
        # op that replanned: partitions merged away by post-shuffle
        # coalescing, joins switched to the broadcast shape at runtime,
        # skewed partitions isolated/split, and the volume of host-known
        # statistics those decisions consumed (all recorded with zero
        # extra host syncs — the shuffle split already fetched them)
        frame.last_metrics["aqeCoalescedPartitions"] = sum(
            ms["aqeCoalescedPartitions"].value
            for ms in ctx.metrics.values()
            if "aqeCoalescedPartitions" in ms)
        frame.last_metrics["aqeBroadcastSwitches"] = sum(
            ms["aqeBroadcastSwitches"].value for ms in ctx.metrics.values()
            if "aqeBroadcastSwitches" in ms)
        frame.last_metrics["aqeSkewSplits"] = sum(
            ms["aqeSkewSplits"].value for ms in ctx.metrics.values()
            if "aqeSkewSplits" in ms)
        frame.last_metrics["aqeStatsRows"] = sum(
            ms["aqeStatsRows"].value for ms in ctx.metrics.values()
            if "aqeStatsRows" in ms)
        frame.last_metrics["aqeStatsBytes"] = sum(
            ms["aqeStatsBytes"].value for ms in ctx.metrics.values()
            if "aqeStatsBytes" in ms)
        # planner size-estimate error vs. actual shuffle bytes, averaged
        # over the exchanges that carried a static estimate (0.0 when the
        # query had none)
        _errs = [ms["aqeEstimateErrorPct"].value
                 for ms in ctx.metrics.values()
                 if "aqeEstimateErrorPct" in ms]
        frame.last_metrics["aqeEstimateErrorPct"] = \
            sum(_errs) / len(_errs) if _errs else 0.0
        # query-intelligence economics (history/): planning decisions the
        # store seeded up front, fragment-cache reuse (a hit re-executes
        # the whole subtree with ZERO dispatches), and how often the
        # persistent store was consulted
        frame.last_metrics["historySeededDecisions"] = _scan_sum(
            "historySeededDecisions")
        frame.last_metrics["fragmentCacheHits"] = _scan_sum(
            "fragmentCacheHits")
        frame.last_metrics["fragmentCacheBytes"] = _scan_sum(
            "fragmentCacheBytes")
        frame.last_metrics["statsStoreQueries"] = _scan_sum(
            "statsStoreQueries")
        # fault-tolerance economics (fault.metrics deltas): recovery
        # replays, deterministic-backoff wall, device losses handled,
        # partitions completed via the CPU path, and injected faults
        frame.last_metrics["retryCount"] = fm_d["retries"]
        frame.last_metrics["backoffWallNs"] = fm_d["backoff_wall_ns"]
        frame.last_metrics["deviceLostCount"] = fm_d["device_lost"]
        frame.last_metrics["partitionFallbackCount"] = \
            fm_d["partition_fallbacks"]
        frame.last_metrics["faultsInjected"] = fm_d["faults_injected"]
        # spill-engine economics for THIS query (catalog counters are
        # process-cumulative, so delta against the pre-query snapshot):
        # writer wall, peak writer-queue depth, read-aheads that hid an
        # unspill, and the bytes each tier hop moved
        cat_now = dict(self.runtime.catalog.metrics) \
            if self.runtime is not None else {}

        def cat_delta(key):
            return cat_now.get(key, 0) - cat_before.get(key, 0)

        frame.last_metrics["spillWallNs"] = cat_delta("spill_wall_ns")
        frame.last_metrics["spillQueueDepthMax"] = \
            cat_now.get("spill_queue_depth_max", 0)
        frame.last_metrics["unspillPrefetchHits"] = \
            cat_delta("unspill_prefetch_hits")
        frame.last_metrics["spillToHostBytes"] = cat_delta(
            "spill_to_host_bytes")
        frame.last_metrics["spillToDiskBytes"] = cat_delta(
            "spill_to_disk_bytes")
        if self.runtime is not None:
            frame.last_metrics["memory"] = dict(self.runtime.catalog.metrics)
        # telemetry economics: how many aggregation intervals the
        # process ring has completed so far (monotone across queries)
        frame.last_metrics["telemetryIntervals"] = obs_ts.completed_total()
        # persist this query's runtime facts for future plan seeding and
        # run the regression sentinel against the store's aggregate of
        # previous runs (history/; no-op without a history dir).  This
        # runs BEFORE the obs drain so each alert's ``regression``
        # instant lands inside this query's event window
        alerts = qhistory.end_query(self, plan, phys, ctx,
                                    frame.last_metrics,
                                    answer_wall_ns, out)
        frame.last_metrics["regressionAlerts"] = len(alerts)
        return frame

    def _register_telemetry_gauges(self) -> None:
        """(Re)register the engine gauges on the telemetry ring.  Gauges
        are sampled at export time only (never inside the emit path), so
        taking engine locks here is safe."""
        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.obs import timeseries as obs_ts
        if obs_ts.ring() is None:
            return
        obs_ts.register_gauge(
            "obs.ring_drops", lambda: float(obs_events.ring_drops_total()))
        from spark_rapids_tpu.history.fragcache import fragment_cache
        obs_ts.register_gauge(
            "fragcache.bytes",
            lambda: float(fragment_cache().stats().get(
                "fragment_cache_bytes", 0)))
        from spark_rapids_tpu.io.decode_pool import decode_pool_utilization
        obs_ts.register_gauge("io.decode_pool_utilization",
                              decode_pool_utilization)
        rt = self.runtime
        if rt is None:
            return
        cat = rt.catalog
        for tier in ("device", "host", "disk"):
            obs_ts.register_gauge(
                f"catalog.{tier}_bytes",
                lambda t=tier: float(cat.tier_bytes()[t]))
        obs_ts.register_gauge("spill.writer_utilization",
                              cat.writer_utilization)
        obs_ts.register_gauge(
            "spill.writer_queue_depth",
            lambda: float(cat.writer_queue_depth()))

    def _record_profile(self, query_id: int, events, dropped: int,
                        wall_ns: int, metrics: Dict[str, Any],
                        dropped_by_site: Optional[Dict[str, int]] = None,
                        qt0_ns: int = 0, qt1_ns: int = 0) -> None:
        """Fold one query's drained events into the bounded history and
        append to the JSONL event log when configured."""
        from spark_rapids_tpu.config import (
            OBS_EVENT_LOG_DIR, OBS_HISTORY_MAX,
        )
        from spark_rapids_tpu.obs.profile import QueryProfile
        scalars = {k: v for k, v in metrics.items()
                   if not isinstance(v, dict)}
        op_metrics = {k: v for k, v in metrics.items()
                      if isinstance(v, dict)
                      and k not in ("memory", "critpath")}
        prof = QueryProfile(query_id, events, dropped, wall_ns=wall_ns,
                            metrics=scalars, op_metrics=op_metrics,
                            dropped_by_site=dropped_by_site,
                            session_id=self.session_id,
                            qt0_ns=qt0_ns, qt1_ns=qt1_ns)
        keep = max(1, OBS_HISTORY_MAX.get(self.conf))
        with self._history_lock:
            self._query_history.append(prof)
            while len(self._query_history) > keep:
                self._query_history.pop(0)
        log_dir = OBS_EVENT_LOG_DIR.get(self.conf)
        if log_dir:
            from spark_rapids_tpu.obs import export as obs_export
            path = os.path.join(log_dir, f"events-{os.getpid()}.jsonl")
            obs_export.write_event_log(path, prof.query_record(), events)
            from spark_rapids_tpu.obs import timeseries as obs_ts
            r = obs_ts.ring()
            if r is not None:
                try:
                    r.flush_jsonl(os.path.join(
                        log_dir, f"telemetry-{os.getpid()}.jsonl"))
                except OSError:
                    pass

    def query_history(self) -> List[Any]:
        """The last ``spark.rapids.sql.tpu.obs.history.maxQueries``
        :class:`~spark_rapids_tpu.obs.profile.QueryProfile` objects,
        oldest first (empty when obs is disabled)."""
        with self._history_lock:
            return list(self._query_history)

    def explain_last(self, metrics: bool = False) -> str:
        """The last query's explain output; with ``metrics=True`` the
        physical tree follows, annotated per operator with the last
        profile's rollups (the SQL-UI exec-metrics analogue)."""
        base = self.last_explain
        if not metrics:
            return base
        phys = getattr(self, "last_physical_plan", None)
        if phys is None or not self._query_history:
            return base
        from spark_rapids_tpu.obs.profile import annotate_plan
        from spark_rapids_tpu.utils import params
        with params.showing(self._explained[2]):
            return base + "\n\n" + annotate_plan(
                phys, self._query_history[-1])

    def prewarm(self, *dataframes) -> Dict[str, int]:
        """Compile the hot bucket set once, ahead of the timed path.

        Executes each given DataFrame (default: every registered view) end
        to end, so every stage program compiles against the shared bucket
        policy's capacities — with ``spark.rapids.sql.tpu.compileCacheDir``
        set the executables also land in the persistent cache, making the
        next process's warmup near-free.  Returns the compile economics of
        the warmup: ``{"compileCount", "compileWallNs", "dispatchCount",
        "compiledShapes"}``.
        """
        from spark_rapids_tpu.utils import compile_registry as CR
        targets = list(dataframes) or list(self._views.values())
        before = CR.snapshot()
        for df in targets:
            self.execute(df.plan)
        d = CR.delta(before, CR.snapshot())
        return {
            "compileCount": d["compiles"],
            "compileWallNs": d["compile_wall_ns"],
            "dispatchCount": d["dispatches"],
            "compiledShapes": CR.compiled_shapes(),
        }

    def explain_plan(self, plan) -> str:
        from spark_rapids_tpu.plan.overrides import TpuOverrides
        overrides = TpuOverrides(self.conf)
        phys = overrides.apply(plan)
        return overrides.last_explain + "\n\n" + phys.tree_string()


class SessionBuilder:
    def __init__(self):
        self._conf = global_conf.copy()

    def config(self, key: str, value: Any) -> "SessionBuilder":
        self._conf.set(key, value)
        return self

    def get_or_create(self) -> TpuSparkSession:
        return TpuSparkSession(self._conf)


class DataFrameReader:
    """session.read.parquet(...) / .csv(...) / .orc(...) entry
    (GpuReadParquetFileFormat / GpuParquetScan analogues)."""

    def __init__(self, session: TpuSparkSession):
        self.session = session
        self._options: Dict[str, Any] = {}
        self._schema: Optional[T.Schema] = None

    def option(self, key: str, value: Any) -> "DataFrameReader":
        self._options[key] = value
        return self

    def schema(self, schema: T.Schema) -> "DataFrameReader":
        self._schema = schema
        return self

    def _scan(self, fmt: str, paths: Union[str, Sequence[str]]):
        from spark_rapids_tpu.dataframe import DataFrame
        from spark_rapids_tpu.io.discovery import (
            discover_partitions, expand_paths, infer_schema,
        )
        from spark_rapids_tpu.plan.logical import FileScan
        if isinstance(paths, str):
            paths = [paths]
        files = expand_paths(list(paths), fmt)
        schema = self._schema or infer_schema(fmt, files, self._options)
        partitions = discover_partitions(list(paths), files)
        if partitions is not None:
            part_schema, _vals = partitions
            new_fields = [f for f in part_schema.fields
                          if f.name not in set(schema.names)]
            if new_fields:
                schema = T.Schema(list(schema.fields) + new_fields)
            else:
                partitions = None
        return DataFrame(
            FileScan(fmt, files, schema, dict(self._options),
                     partitions=partitions), self.session)

    def parquet(self, *paths: str):
        return self._scan("parquet", list(paths))

    def csv(self, *paths: str):
        return self._scan("csv", list(paths))

    def orc(self, *paths: str):
        return self._scan("orc", list(paths))


def _to_host_batch(data, schema) -> HostBatch:
    import numpy as np
    if isinstance(data, HostBatch):
        return data
    if isinstance(data, dict):
        first = next(iter(data.values()), None)
        if isinstance(first, tuple) and len(first) == 2 and \
                isinstance(first[0], T.DataType):
            return HostBatch.from_pydict(data)
        # {name: values}: infer types
        out = {}
        for name, values in data.items():
            dt = _infer_dtype(values)
            out[name] = (dt, list(values))
        return HostBatch.from_pydict(out)
    if isinstance(data, (list, tuple)):
        assert schema is not None, "list-of-rows input requires a schema"
        if schema and not isinstance(schema, T.Schema):
            schema = T.Schema(schema)
        cols = {f.name: (f.dtype, [row[i] for row in data])
                for i, f in enumerate(schema.fields)}
        return HostBatch.from_pydict(cols)
    raise TypeError(f"cannot build DataFrame from {type(data)}")


def _infer_dtype(values) -> T.DataType:
    for v in values:
        if v is None:
            continue
        if isinstance(v, bool):
            return T.BOOLEAN
        if isinstance(v, int):
            return T.LONG
        if isinstance(v, float):
            return T.DOUBLE
        if isinstance(v, str):
            return T.STRING
        if isinstance(v, (list, tuple)):
            elems = [e for arr in values if arr is not None
                     for e in arr if e is not None]
            return T.ArrayType(_infer_dtype(elems) if elems else T.LONG)
    return T.STRING


def _assert_on_tpu(op, allow=("HostToDeviceExec", "CpuInMemoryScanExec",
                              "CpuFileScanExec", "FileScanV2Exec",
                              "DeviceToHostExec",
                              "CpuShuffleExchangeExec")):
    """spark.rapids.sql.test.enabled analogue
    (GpuTransitionOverrides.scala:277-322)."""
    name = type(op).__name__
    if not op.is_tpu and name not in allow:
        raise AssertionError(f"operator {name} fell back to CPU with "
                             "spark.rapids.sql.test.enabled=true")
    for c in op.children:
        _assert_on_tpu(c, allow)
