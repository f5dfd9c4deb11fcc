"""Whole-pipeline compilation: run an entire TPU query stage as ONE XLA
program (a few, at capacity-reduction boundaries).

The reference amortizes per-op JNI dispatch with batch-level cudf calls; on
TPU every dispatched program and every blocking host transfer costs a
host<->device round trip that can dwarf the compute, so the
engine's steady state must execute O(1) programs per query, not O(ops).
This module composes the per-batch functions of an all-TPU physical subtree
(map stages, collapsed exchanges, aggregate update/merge, sort, limit,
expand, union) into jitted stage functions over the source batches — the
TPU-native analogue of Spark whole-stage codegen, with XLA doing the
fusion.

Stage boundaries ("stage breaks") sit where live rows collapse far below
capacity (the partials of an aggregate WITH grouping keys): the driver
syncs the live sizes once (one round trip), re-buckets the shrunk batches
and feeds them to the next stage — otherwise padded capacities would
snowball through concats and every downstream sort would pay O(padded).
The re-bucketing gather is not a separate dispatched program: it compiles
INTO the consuming tail stage (cached per shrunk-bucket signature), so the
final merge-aggregate/order-by/limit tail costs one dispatch, not two.
Only a consumer that cannot compile it in (a mesh stage's shard_map
program, :func:`shrink_materialized`) and a directly collected root
(:func:`_shrink_outputs`) dispatch it alone.  A keyless aggregate has no
stage break: its partials are one row each at the minimum capacity, there
is nothing to re-bucket, and update, merge and whatever consumes them are
ONE program.

Stage flags.  An inlined operator may run a fast variant whose result is
only valid if a flag the program computes comes back clear (the hash
aggregate's slot table / NaN-Inf guard).  That is a notion of the stage,
not of its root: while the stage program is traced, every such operator
reports through :func:`note_stage_batches` how many batches it handled
and, when it speculated, the traced flag; the flags are extra scalar
outputs of the program, beside the batches (never a batch among them).
The stage's variant key is the variant of EVERY inlined operator that has
one (``stage_variant``), and a stage never donates its sources while any
of them may ask for a rerun (``stage_may_rerun``).  The host reads the
flags where the stage's outputs are handed on: for the collected root in
the same transfer as the answer (:func:`pipeline_collect`: one
``d2h_ready`` wait, no round trip of their own), for a stage that feeds
another in one ``device_read`` (:func:`_run_stage`) before the consumer
sees a batch.  A flag that comes back set discards the outputs, tells the
operator (``stage_flagged``: its fast variant goes off for good) and
re-dispatches the stage, in the variant the operators now name, on the
same materialised sources (``pipeline.flagReruns``); only the run that
stands is counted (``stage_ran``, ``pipeline.inlinedUpdates``).

Ops that cannot be inlined (host transitions, joins needing host-visible
output sizing, samples with host RNG) become pipeline *sources*: their
iterator path materializes batches that feed the program as arguments.

Data-plane economics (docs/dataplane.md): consumed source batches —
stage-break intermediates and fresh host->device stagings — are DONATED
to the stage program (``donate_argnums``) wherever the process can donate
(``compile_registry.donation_supported``), so XLA reuses their HBM for
outputs instead of holding two full copies; every source's program is
dispatched before any blocking sync and all stage-break size fetches ride
one batched round trip.

Every stage program dispatch is counted (utils/compile_registry: the
per-query ``dispatchCount`` / ``compileCount``) and runs inside a
``srt/stage/<root>`` span: the host's wall of the enqueue and of any
size read-back inside it, never device time.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import (
    BUCKETS, ColumnBatch, HostBatch, device_to_host_with, host_sizes,
    round_up_capacity,
)
from spark_rapids_tpu.plan.physical import ExecContext, PhysicalOp, TpuExec
from spark_rapids_tpu.utils.compile_registry import (
    donation_supported, instrumented_jit, plan_jit,
)
from spark_rapids_tpu.utils.tracing import device_read, span


def concat_static(batches: List[ColumnBatch], schema: T.Schema
                  ) -> ColumnBatch:
    """In-jit concatenation: output capacity = sum of input *capacities*
    (static — no host sync).  Stage breaks pay the padding back.  One
    single-allocation k-way kernel writes each input once at its offset;
    the pairwise chain this replaced materialized k-1 growing
    intermediates inside the program (O(k * out_capacity) HBM traffic)."""
    from spark_rapids_tpu.kernels.layout import concat_kway
    if len(batches) == 1:
        return batches[0]
    cap = round_up_capacity(sum(b.capacity for b in batches))

    def _col_elem_cap(c):
        # Dictionary-encoded inputs materialize inside concat_kway's
        # row-layout guard: size the output for the decoded bytes, not
        # the dictionary's.
        if c.codes is not None:
            return max(int(c.mat_byte_cap), 16)
        return int(c.data.shape[0])

    byte_caps = []
    for i, f in enumerate(schema.fields):
        if f.dtype.is_string or f.dtype.is_array:
            byte_caps.append(BUCKETS.elems(
                sum(_col_elem_cap(b.columns[i]) for b in batches)))
    return concat_kway(batches, cap, out_byte_caps=byte_caps or None)


def build_pipeline(op: PhysicalOp, ctx: ExecContext,
                   sources: List[PhysicalOp], memo: dict,
                   root: PhysicalOp) -> Callable:
    """Recursively compose ``op`` into f(args) -> List[ColumnBatch].

    ``args`` is a tuple aligned with ``sources``: args[i] is the tuple of
    batches materialized from sources[i].  Ops whose ``pipeline_inline``
    returns None — and stage-break ops below the stage root — become
    sources.
    """
    if id(op) in memo:
        return memo[id(op)]
    # the operator's pre-order position in its stage: with its class it
    # names the operator on the device timeline the same way in every
    # process (``op_id`` holds a memory address)
    k = memo[_ORDER] = memo.get(_ORDER, -1) + 1
    f = None
    if isinstance(op, TpuExec) and not (
            op is not root and getattr(op, "pipeline_stage_break", False)):
        f = op.pipeline_inline(
            ctx,
            lambda child: build_pipeline(child, ctx, sources, memo, root))
        if f is not None:
            f = _scoped(f, f"{type(op).__name__}.{k}")
            if hasattr(op, "stage_variant"):
                memo.setdefault(_VARIANTS, []).append(op)
    if f is None:
        idx = len(sources)
        sources.append(op)
        f = lambda args, _i=idx: list(args[_i])  # noqa: E731
    memo[id(op)] = f
    return f


#: ``memo`` keys (never collide with an ``id()``): the pre-order counter,
#: and the inlined operators that have a ``stage_variant``
_ORDER = "order"
_VARIANTS = "variants"


_STAGE_NOTES = threading.local()


def note_stage_batches(op: PhysicalOp, batches: int, flag=None) -> None:
    """Trace-time channel from an inlined operator to its stage program:
    ``op`` handled ``batches`` batches and, where it ran a fast variant
    that the data may invalidate, ``flag`` is the traced count of batches
    that did.  The program returns the flags beside its batches and the
    host reads them where the outputs are handed on (module docstring).
    Outside a collecting program body nobody would read the flag, and an
    unread flag is a wrong answer waiting: that is an error.  A note
    with no flag is a count only, and outside a stage program (an
    operator's own per-batch program on the eager route) a
    :class:`BatchProgram` collects it the same way."""
    sink = getattr(_STAGE_NOTES, "sink", None)
    if sink is not None:
        sink.append((op, batches, flag))
    elif flag is not None:
        raise RuntimeError(
            f"{op.name}: stage flag outside a stage program's trace")


@contextlib.contextmanager
def collect_stage_notes():
    """The notes of the operators traced inside the block, in trace
    order: ``[(op, batches, flag-or-None), ...]``."""
    prev = getattr(_STAGE_NOTES, "sink", None)
    sink = _STAGE_NOTES.sink = []
    try:
        yield sink
    finally:
        _STAGE_NOTES.sink = prev


class BatchProgram:
    """A per-batch program on the eager route (an operator's own
    ``partitions``, an exchange's absorbed map stages): ``plan_jit(fn)``
    that keeps what the operators inside it noted when it was traced and
    counts it again on every call, as every dispatch of a stage program
    does (``stage_ran``).  Nothing reads a flag out here, so an operator
    that speculates must not be traced into one."""

    def __init__(self, fn: Callable, label: str):
        self._noted: Tuple[Tuple[PhysicalOp, int], ...] = ()

        def body(batch):
            with collect_stage_notes() as notes:
                out = fn(batch)
            if any(flag is not None for _op, _n, flag in notes):
                raise RuntimeError(f"{label}: stage flag on the eager route")
            self._noted = tuple((op, n) for op, n, _flag in notes)
            return out

        self._jit = plan_jit(body, label=label)

    def __call__(self, ctx: ExecContext, batch):
        out = self._jit(batch)
        for op, n in self._noted:
            op.stage_ran(ctx, n, False)
        return out


def _scoped(f: Callable, scope: str) -> Callable:
    """``f`` traced under ``jax.named_scope(scope)``: every HLO operation
    the operator's inlined function emits carries ``…/<Class>.<k>/…`` in
    its ``op_name``, so a trace can charge device time to the operator."""
    def run(args):
        with jax.named_scope(scope):
            return f(args)
    return run


class MeshBuildScope:
    """Build-time channel between the stage builder and mesh-fusable ops,
    active only while ``ExecContext.mesh_spmd_active()``.

    ``TpuShuffleExchangeExec.pipeline_inline`` appends itself to
    ``exchanges`` when it fuses as an in-program all_to_all instead of
    becoming a host-driven stage source; join execs append themselves to
    ``joins`` when they lower per-shard with static bucketed output
    sizing, and ``TpuBroadcastHashJoinExec`` records in ``replicated``
    the source indices its build side added, so parallel.mesh_spmd feeds
    those sources as PartitionSpec-() replicated globals.  ``sources``
    aliases the stage's live source list, letting ops observe indices as
    ``build_pipeline`` appends."""

    def __init__(self, sources: List[PhysicalOp]):
        self.sources = sources
        self.exchanges: List[PhysicalOp] = []
        self.replicated: set = set()
        self.joins: List[PhysicalOp] = []


_MESH_BUILD = threading.local()


def mesh_build_scope() -> Optional[MeshBuildScope]:
    """The innermost active mesh-SPMD build scope; None outside a stage
    build or when SPMD fusion is off — ops treat None as 'do not
    mesh-fuse', which routes exchanges to the host-driven mesh path."""
    if getattr(_MESH_BUILD, "disabled", False):
        return None
    stack = getattr(_MESH_BUILD, "stack", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def mesh_fusion_disabled():
    """Scoped off-switch for mesh-SPMD fusion: while active,
    :func:`mesh_build_scope` reports no scope, so every exchange and
    join lowers host-driven.  The bucketed-join overflow fallback
    rebuilds an overflowed stage under this to get the classic
    host-synced plan (see :func:`run_stage_unfused`)."""
    prev = getattr(_MESH_BUILD, "disabled", False)
    _MESH_BUILD.disabled = True
    try:
        yield
    finally:
        _MESH_BUILD.disabled = prev


def _mesh_scoped_build(root: PhysicalOp, ctx: ExecContext,
                       sources: List[PhysicalOp]):
    """Run :func:`build_pipeline` under a :class:`MeshBuildScope` when
    SPMD fusion is active for ``ctx``; (fn, scope-or-None).  The first
    build of a root also records which inlined operators have a
    ``stage_variant`` (``root._stage_variant_ops``): what a stage inlines
    does not depend on the variant it is built in."""
    memo: dict = {}
    scope = None
    if not ctx.mesh_spmd_active():
        fn = build_pipeline(root, ctx, sources, memo, root)
    else:
        scope = MeshBuildScope(sources)
        stack = getattr(_MESH_BUILD, "stack", None)
        if stack is None:
            stack = _MESH_BUILD.stack = []
        stack.append(scope)
        try:
            fn = build_pipeline(root, ctx, sources, memo, root)
        finally:
            stack.pop()
    if getattr(root, "_stage_variant_ops", None) is None:
        root._stage_variant_ops = tuple(memo.get(_VARIANTS, ()))
    return fn, scope


def _variant_ops(root: PhysicalOp, ctx: ExecContext) -> tuple:
    """The operators inlined into ``root``'s stage (``root`` too) that
    compile in variants.  Learnt from one build that is thrown away:
    builds are cached by variant, and the variant is theirs to name."""
    ops = getattr(root, "_stage_variant_ops", None)
    if ops is None:
        _mesh_scoped_build(root, ctx, [])
        ops = root._stage_variant_ops
    return ops


def _stage_variant(root: PhysicalOp, ctx: ExecContext) -> str:
    """Key of the stage's build and programs: the variant of every
    inlined operator that has one.  An inlined operator closes over its
    variant at build time, so a stage keyed on its root alone would keep
    dispatching a build whose fast variant has since gone off."""
    return ",".join(filter(None, (
        op.stage_variant(ctx) for op in _variant_ops(root, ctx)))
    ) or "default"


def _shrink_threshold(ctx: ExecContext) -> int:
    """Padded outputs at or below this skip the sizes round-trip + shrink."""
    from spark_rapids_tpu.config import PIPELINE_SHRINK_BYTES
    return PIPELINE_SHRINK_BYTES.get(ctx.conf)


def _stage_may_rerun(root: PhysicalOp, ctx: ExecContext) -> bool:
    """True when a flag read may re-dispatch the stage on the SAME
    materialized inputs (hash-agg exact fallback, of the root or of any
    operator inlined under it): those inputs must then never be
    donated."""
    return any(op.stage_may_rerun(ctx) for op in _variant_ops(root, ctx))


def _batch_padded_bytes(b: ColumnBatch) -> int:
    total = 0
    for c in b.columns:
        total += c.data.size * c.data.dtype.itemsize
        total += c.validity.size * c.validity.dtype.itemsize
        if c.offsets is not None:
            total += c.offsets.size * c.offsets.dtype.itemsize
        if c.codes is not None:
            total += c.codes.size * c.codes.dtype.itemsize
    return total


def _shrink_gather(b: ColumnBatch, cap: int, bcaps: Tuple[int, ...]
                   ) -> ColumnBatch:
    """One compiled gather re-bucketing ``b`` to (cap, bcaps) — traceable,
    used both by the standalone shrink program and inlined in fused tail
    stage prologues."""
    from spark_rapids_tpu.kernels.layout import gather_rows
    idx = jnp.arange(cap, dtype=jnp.int32)
    return gather_rows(b, idx, b.num_rows, out_capacity=cap,
                       out_byte_caps=list(bcaps) or None)


def _shrink_many(bs: Tuple[ColumnBatch, ...], caps: Tuple[int, ...],
                 bcapss: Tuple[Tuple[int, ...], ...]):
    return tuple(_shrink_gather(b, cap, bcaps)
                 for b, cap, bcaps in zip(bs, caps, bcapss))


# Two compiled variants of the stage-break re-bucketing gather: the
# donating one consumes its inputs (raw stage outputs — nothing else ever
# references them, and an OOM retry recomputes them from the stage
# program), so XLA reuses their HBM for the shrunk outputs.
_shrink_jit = instrumented_jit(
    _shrink_many, label="pipeline:shrink",
    static_argnames=("caps", "bcapss"))
_shrink_jit_donate = instrumented_jit(
    _shrink_many, label="pipeline:shrink",
    static_argnames=("caps", "bcapss"), donate_argnums=(0,))


def _spec_of(sizes) -> tuple:
    """(row cap, varlen byte caps) re-bucketing spec from host-fetched
    (num_rows, [varlen totals]) pairs."""
    return tuple(
        (BUCKETS.rows(n), tuple(BUCKETS.elems(t) for t in totals))
        for n, totals in sizes)


def _worth_shrinking(outs: List[ColumnBatch], ctx: ExecContext) -> bool:
    return bool(outs) and sum(_batch_padded_bytes(b) for b in outs) > \
        _shrink_threshold(ctx)


def _record_break_stats(ctx: ExecContext, sizes) -> None:
    """Stage-break live sizes feed the adaptive statistics pool
    (aqeStatsRows): the sizes round trip was paid for the re-bucketing
    anyway, so accounting the rows it revealed keeps the pipelined path
    inside plan/adaptive's zero-extra-sync contract."""
    ctx.metric("pipeline", "aqeStatsRows").add(
        sum(int(n) for n, _ in sizes))


def _shrink_spec(outs: List[ColumnBatch], ctx: ExecContext):
    """Per-batch re-bucketing spec for a stage break's raw outputs — ONE
    sizes round trip for all batches — or None when the padded total is
    too small to be worth a shrink."""
    if not _worth_shrinking(outs, ctx):
        return None
    sizes = host_sizes(outs)
    _record_break_stats(ctx, sizes)
    return _spec_of(sizes)


def _apply_shrink(outs: List[ColumnBatch], spec: tuple, ctx: ExecContext,
                  guard: bool = False) -> List[ColumnBatch]:
    """One compiled gather re-bucketing every batch to ``spec`` (inputs
    donated where the process can donate — they are consumed).
    ``guard=True`` runs the dispatch under the OOM→spill→retry guard for
    call sites not already inside one (:func:`shrink_materialized`); a
    donating shrink still fails fast on OOM — its inputs are consumed at
    dispatch."""
    caps = tuple(c for c, _ in spec)
    bcapss = tuple(bc for _, bc in spec)
    devs = set()
    for b in outs:
        for leaf in jax.tree_util.tree_leaves(b):
            get_devs = getattr(leaf, "devices", None)
            if callable(get_devs):
                devs.update(get_devs())
    if len(devs) > 1:
        # mesh-stage outputs land one batch per mesh device: the gather
        # must dispatch per batch (one jit over the tuple would be an
        # illegal cross-device program, and colocating would drag every
        # shard onto one device).  No donation — per-batch signatures
        # would fragment the donate cache
        per_batch = lambda: [  # noqa: E731
            _shrink_jit((b,), (cap,), (bcaps,))[0]
            for b, cap, bcaps in zip(outs, caps, bcapss)]
        if guard:
            return _run_oom_guarded(ctx, per_batch, (outs,),
                                    retryable=True)
        return per_batch()
    # where the cache bypass could not install, instrumented_jit strips
    # donate_argnums: calling that jit "donating" would needlessly turn
    # the OOM spill-retry off (retryable=False)
    jit = _shrink_jit_donate if donation_supported() else _shrink_jit
    if jit is _shrink_jit_donate:
        leaves = jax.tree_util.tree_leaves(tuple(outs))
        if len({id(leaf) for leaf in leaves}) != len(leaves):
            # a duplicated leaf cannot be donated twice
            jit = _shrink_jit
    run = lambda: list(jit(tuple(outs), caps, bcapss))  # noqa: E731
    if guard:
        return _run_oom_guarded(ctx, run, (outs,),
                                retryable=jit is _shrink_jit)
    return run()


def _shrink_outputs(outs: List[ColumnBatch], ctx: ExecContext
                    ) -> List[ColumnBatch]:
    """Sizes round trip + one compiled gather re-bucketing every batch."""
    spec = _shrink_spec(outs, ctx)
    if spec is None:
        return outs
    ctx.metric("pipeline", "shrinks").add(1)
    return _apply_shrink(outs, spec, ctx)


def _shrink_outputs_sharded(outs: List[ColumnBatch], ctx: ExecContext
                            ) -> List[ColumnBatch]:
    """Mesh-stage variant of :func:`_shrink_outputs`: the unsharded
    outputs are committed one per mesh device, so the re-bucketing gather
    dispatches per batch (each on its own device — ONE jit over the whole
    tuple would be an illegal cross-device program).  Still exactly one
    sizes round trip for the lot.  No donation: per-batch signatures
    would fragment the donate cache, and mesh outputs are short-lived."""
    spec = _shrink_spec(outs, ctx)
    if spec is None:
        return outs
    ctx.metric("pipeline", "shrinks").add(1)
    return [
        _shrink_jit((b,), (cap,), (bcaps,))[0]
        for b, (cap, bcaps) in zip(outs, spec)]


def _materialize_sources(sources: List[PhysicalOp], ctx: ExecContext
                         ) -> List[list]:
    """Materialize every stage source -> [[batches, shrink_spec,
    donatable], ...].

    Dispatch-then-sync: every source's stage program (and iterator path)
    is driven FIRST; the stage-break sizes fetch — the only blocking host
    sync — is then taken for ALL sources in one batched ``host_sizes``
    round trip.  A stage-break source returns its RAW outputs plus the
    re-bucketing spec (None where the padded total is not worth a shrink)
    for the consumer to compile into its own program.

    ``donatable`` marks sources whose batches this stage consumes
    outright: stage-break intermediates and fresh host->device stagings.
    Everything else (cached scans, spill-catalog handles, broadcast
    builds) may be referenced again and must never be donated.
    """
    from spark_rapids_tpu.plan.physical import HostToDeviceExec
    mats: List[list] = []
    pending: List[Tuple[int, List[ColumnBatch]]] = []

    for src in sources:
        if getattr(src, "pipeline_stage_break", False):
            outs = _run_stage(src, ctx, shrink=False)
            mats.append([outs, None, True])
            if _worth_shrinking(outs, ctx):
                pending.append((len(mats) - 1, outs))
        else:
            batches = []
            for part in src.partitions(ctx):
                batches.extend(part)
            # H2D-side semaphore acquires are counted into
            # ctx._pipeline_h2d at acquire time (HostToDeviceExec), so
            # an abort mid-source releases exactly what was taken
            donatable = isinstance(src, HostToDeviceExec)
            mats.append([batches, None, donatable])
    if pending:
        # one sizes round trip across EVERY stage-break source, taken
        # only after all their programs are in flight
        flat = [b for _, outs in pending for b in outs]
        sizes = host_sizes(flat)
        _record_break_stats(ctx, sizes)
        pos = 0
        for i, outs in pending:
            mats[i][1] = _spec_of(sizes[pos:pos + len(outs)])
            pos += len(outs)
    return mats


def shrink_materialized(mats: List[list], ctx: ExecContext) -> None:
    """Dispatch the re-bucketing gather of every stage-break source in
    ``mats`` alone, in place — for a consumer that cannot compile it into
    its own program (a mesh stage: its shard_map program takes packed
    globals, parallel.mesh_spmd)."""
    for m in mats:
        if m[1] is not None:
            ctx.metric("pipeline", "shrinks").add(1)
            m[0] = _apply_shrink(m[0], m[1], ctx, guard=True)
            m[1] = None


def _stage_build(root: PhysicalOp, ctx: ExecContext, variant: str):
    """(sources, composed fn) for one variant of ``root``'s stage (ops like
    the hash aggregate compose a fast path and an exact-fallback path)."""
    cache = getattr(root, "_stage_builds", None)
    if not isinstance(cache, dict):
        cache = {}
        root._stage_builds = cache
    if variant not in cache:
        sources: List[PhysicalOp] = []
        fn, scope = _mesh_scoped_build(root, ctx, sources)
        if scope is not None and (scope.exchanges or scope.joins):
            minfo = getattr(root, "_mesh_stage_info", None)
            if not isinstance(minfo, dict):
                minfo = {}
                root._mesh_stage_info = minfo
            minfo[variant] = (list(scope.exchanges),
                              frozenset(scope.replicated),
                              list(scope.joins))
        cache[variant] = (sources, fn)
    return cache[variant]


def _stage_program(root: PhysicalOp, ctx: ExecContext, variant: str,
                   spec: tuple, dmask: Tuple[bool, ...]):
    """(sources, jitted) for (variant, tail-fusion shrink spec, donation
    mask).

    ``spec`` (one entry per source; None = feed raw) bakes the stage-break
    re-bucketing gathers into the stage program's prologue, so shrink +
    tail ride ONE dispatch.  Power-of-two bucketing keeps the number of
    distinct specs — and therefore compiled tail variants — small.

    ``dmask`` (one bool per source) selects which sources' batches are
    DONATED: the program takes (donated, kept) argument tuples and
    ``donate_argnums`` hands the donated buffers' HBM to XLA for reuse —
    a consumed input batch then never holds a second full copy across the
    dispatch.

    The program is a ``plan_jit``: besides the batches it takes the
    executing query's bound literal values (``utils/params``), so the
    executables that hang on this root op serve every query of the plan's
    shape, whatever its literals.
    """
    cache = getattr(root, "_stage_cache", None)
    if not isinstance(cache, dict):
        cache = {}
        root._stage_cache = cache
    key = (variant, spec, dmask)
    if key not in cache:
        sources, fn = _stage_build(root, ctx, variant)
        # what the inlined operators noted when the program was traced,
        # by the number of batches each source fed: static facts of a
        # trace that every later dispatch of it has to count again
        noted: dict = {}

        def assemble(dargs, kargs, _mask=dmask):
            di, ki, args = 0, 0, []
            for m in _mask:
                if m:
                    args.append(dargs[di])
                    di += 1
                else:
                    args.append(kargs[ki])
                    ki += 1
            return tuple(args)

        def run(dargs, kargs, _spec=spec):
            args = assemble(dargs, kargs)
            arity = tuple(len(bs) for bs in args)
            if any(sp is not None for sp in _spec):
                args = tuple(
                    tuple(bs) if sp is None else tuple(
                        _shrink_gather(b, cap, bcaps)
                        for b, (cap, bcaps) in zip(bs, sp))
                    for bs, sp in zip(args, _spec))
            with collect_stage_notes() as notes:
                outs = tuple(fn(args))
            noted[arity] = tuple((op, n, flag is not None)
                                 for op, n, flag in notes)
            return outs, tuple(flag for _, _, flag in notes
                               if flag is not None)
        jit_kw = {"donate_argnums": (0,)} if any(dmask) else {}
        cache[key] = (
            sources, plan_jit(run, label=f"stage:{root.name}", **jit_kw),
            noted)
    return cache[key]


def _run_oom_guarded(ctx: ExecContext, thunk, args=(), retryable=True):
    """Dispatch a stage program under the OOM→spill→retry guard
    (DeviceMemoryEventHandler.scala:35 role; see mem.catalog).  ``args`` —
    the stage's input batches, still referenced by the retry — are pinned
    so the spill pass doesn't waste a pass "freeing" live buffers.
    ``retryable=False`` (donated inputs: consumed at dispatch, a retry
    cannot re-present them) fails fast with the original OOM, TAGGED
    NON_RETRYABLE (fault.errors classification: donated-dispatch OOM) so no
    outer recovery level replays against consumed buffers either."""
    from spark_rapids_tpu.fault.errors import (
        ErrorClass, classify_error, mark_non_retryable,
    )
    from spark_rapids_tpu.mem.catalog import run_with_oom_retry
    from spark_rapids_tpu.runtime.device import DeviceRuntime
    pinned = [b for bs in args for b in bs]
    try:
        return run_with_oom_retry(
            DeviceRuntime.get(ctx.conf).catalog, thunk,
            retries=None if retryable else 0, pinned=pinned,
            on_retry=lambda _freed: ctx.metric("pipeline",
                                               "oom_retries").add(1))
    except Exception as e:
        # only raw XLA OOMs get the donated tag: they come from the
        # dispatch itself, after the inputs were consumed.  An error
        # already carrying an explicit class (an injected fault fires at
        # the call site, BEFORE any buffer is consumed) keeps it — the
        # stage replay is sound there.
        if not retryable and \
                getattr(e, "rapids_error_class", None) is None and \
                classify_error(e) is ErrorClass.RETRYABLE_OOM:
            raise mark_non_retryable(e)
        raise


class StageRun:
    """One dispatch of a stage: its output batches, still unvalidated
    while ``flags`` (device scalars, one a speculating operator; a vector
    a device from a mesh stage) have not been read clear.  ``notes`` is
    what the operators noted at the trace, ``[(op, batches,
    speculated)]`` with one flag per speculating entry in order, and
    ``redo()`` dispatches the stage again, in the variant its operators
    name by then, on the same materialised sources."""

    __slots__ = ("root", "outs", "flags", "notes", "redo")

    def __init__(self, root, outs, flags, notes, redo):
        self.root = root
        self.outs = outs
        self.flags = flags
        self.notes = notes
        self.redo = redo


def _stands(run: StageRun, flags, ctx: ExecContext) -> bool:
    """Judge ``run`` by its flags as read on the host.  Any set flag
    discards it: the operators that raised one are told
    (``stage_flagged``) and nothing is counted.  Else the run stands and
    every noting operator counts its batches (``stage_ran``)."""
    with span("bookkeeping", "stands", run.root.op_id):
        read = iter(flags)
        flagged = [op for op, _n, speculated in run.notes
                   if speculated and bool(next(read).any())]
        for op in flagged:
            op.stage_flagged(ctx)
        if flagged:
            ctx.metric("pipeline", "flagReruns").add(1)
            return False
        inlined = 0
        for op, n, speculated in run.notes:
            op.stage_ran(ctx, n, speculated)
            # an update below the root (a filter notes compactions only)
            inlined += op is not run.root and hasattr(op, "stage_variant")
        if inlined:
            ctx.metric("pipeline", "inlinedUpdates").add(inlined)
        return True


def _run_stage(root: PhysicalOp, ctx: ExecContext,
               shrink: bool = True) -> List[ColumnBatch]:
    """``root``'s stage as one program, for a consumer on the device:
    where the stage holds flags they are read here, in one
    ``device_read``, so no consumer sees an unvalidated batch.
    ``shrink=False`` hands raw outputs to a tail-fusing consumer."""
    run = _dispatch_stage(root, ctx, shrink)
    while not _stands(run, device_read("stage_flags", run.flags, root.op_id)
                      if run.flags else (), ctx):
        run = run.redo()
    return run.outs


def _dispatch_stage(root: PhysicalOp, ctx: ExecContext,
                    shrink: bool = True) -> StageRun:
    """Dispatch ``root``'s stage as one program.  ``shrink=True`` (the
    default, for directly-collected stages) re-buckets the outputs."""
    with span("stage_inputs", "variant", root.op_id):
        variant = _stage_variant(root, ctx)
        sources, _fn = _stage_build(root, ctx, variant)
        minfo = getattr(root, "_mesh_stage_info", None)
    if isinstance(minfo, dict) and variant in minfo:
        # the build fused at least one exchange as an in-program
        # all_to_all (or a join as a per-shard static kernel): this
        # stage MUST run as a mesh-sharded shard_map program — the
        # single-device path below would trace lax.axis_index with no
        # mesh axis bound
        from spark_rapids_tpu.parallel.mesh_spmd import run_mesh_stage
        return run_mesh_stage(root, ctx, variant, shrink=shrink)
    return _run_stage_host(root, ctx, variant, sources, shrink)


def run_stage_unfused(root: PhysicalOp, ctx: ExecContext, variant: str,
                      shrink: bool = True) -> StageRun:
    """Host-driven rerun of a fused mesh stage (the bucketed-join
    overflow fallback, parallel.mesh_spmd): rebuild the stage with mesh
    fusion disabled under a distinct ``nomesh:`` variant key — the
    unfused build/program caches never collide with the fused ones and
    the minfo lookup above misses — then dispatch through the normal
    host path (joins revert to the host-synced two-phase kernel)."""
    v = "nomesh:" + variant
    with mesh_fusion_disabled():
        sources, _fn = _stage_build(root, ctx, v)
    return _run_stage_host(root, ctx, v, sources, shrink, unfused=True)


def _run_stage_host(root: PhysicalOp, ctx: ExecContext, variant: str,
                    sources: List[PhysicalOp], shrink: bool,
                    unfused: bool = False) -> StageRun:
    from spark_rapids_tpu.batch import colocate_batches
    # the sources materialised, then handed to the program: colocated,
    # and the donation mask decided
    with span("stage_inputs", root.name):
        mats = _materialize_sources(sources, ctx)
        args = tuple(tuple(bs) for bs, _, _ in mats)
        spec = tuple(sp for _, sp, _ in mats)
        fused = sum(sp is not None for sp in spec)
        if fused:
            ctx.metric("pipeline", "fusedShrinks").add(fused)
        args = tuple(tuple(bs) for bs in colocate_batches(args))
        donate = donation_supported() and not _stage_may_rerun(root, ctx)
        dmask = tuple(bool(donate and d) for _, _, d in mats)
        if any(dmask):
            leaves = jax.tree_util.tree_leaves(
                tuple(a for a, m in zip(args, dmask) if m))
            if len({id(leaf) for leaf in leaves}) != len(leaves):
                # a duplicated leaf cannot be donated twice — keep all
                dmask = tuple(False for _ in dmask)
        arity = tuple(len(bs) for bs in args)

    def dispatch(v: str) -> StageRun:
        def call():
            outs, flags = jitted(dargs, kargs)
            outs = list(outs)
            return (_shrink_outputs(outs, ctx) if shrink else outs), flags

        # the program's lookup and its call; the jitted calls inside open
        # their own ``enqueue`` spans and inherit the stage root as their
        # operator
        with span("stage", root.name, root.op_id):
            s2, jitted, noted = _stage_program(root, ctx, v, spec, dmask)
            assert len(s2) == len(sources), "stage variants disagree"
            ctx.metric("pipeline", "programs").add(1)
            dargs = tuple(a for a, m in zip(args, dmask) if m)
            kargs = tuple(a for a, m in zip(args, dmask) if not m)
            outs, flags = _run_oom_guarded(ctx, call, args,
                                           retryable=not any(dmask))
        return StageRun(root, outs, flags, noted[arity], redo)

    def redo() -> StageRun:
        # an operator flipped its variant (hash -> exact sort):
        # re-execute on the SAME materialized source batches
        v2 = _stage_variant(root, ctx)
        if unfused:
            v2 = "nomesh:" + v2
            with mesh_fusion_disabled():
                _stage_build(root, ctx, v2)
        return dispatch(v2)

    return dispatch(variant)


def pipeline_collect(root: PhysicalOp, ctx: ExecContext
                     ) -> Optional[HostBatch]:
    """Try to run ``root`` as a whole-pipeline program; None if the plan
    doesn't inline anything or its root is not on the device (caller
    falls back to the iterator path)."""
    if not root.is_tpu:
        return None

    probe = getattr(root, "_pipeline_viable", None)
    if probe is None:
        sources: List[PhysicalOp] = []
        # probe under the mesh scope too: with SPMD fusion on, a plan
        # whose root consumes only a fused exchange (repartition/distinct
        # collected straight off the shuffle) is viable even though the
        # scope-less build would leave root as its own sole source
        _mesh_scoped_build(root, ctx, sources)
        probe = not (len(sources) == 1 and sources[0] is root)
        root._pipeline_viable = probe
    if not probe:
        return None

    ctx._pipeline_h2d = 0
    try:
        # the stage's flags come home beside the answer: one transfer,
        # one wait, and an answer whose flag is set is thrown away
        run = _dispatch_stage(root, ctx)
        while True:
            hbs, flags = device_to_host_with(run.outs, run.flags)
            if _stands(run, flags, ctx):
                break
            run = run.redo()
        outs = run.outs
        hbs = [hb for hb in hbs if hb.num_rows]
    finally:
        from spark_rapids_tpu.plan.physical import _release_admission
        if ctx.semaphore is not None:
            _release_admission(ctx, getattr(ctx, "_pipeline_h2d", 0))
        else:
            ctx._pipeline_h2d = 0
    with span("bookkeeping", "release", root.op_id):
        frag_key = getattr(ctx, "_history_frag_key", None)
        if frag_key is not None and \
                getattr(ctx, "logical_plan", None) is not None:
            # adopt the outputs into the cross-query fragment cache
            # (history.fragcache) AFTER the D2H landed: registering first
            # would let budget pressure spill a batch mid-transfer.  Only
            # this path inserts — its outs are always fresh jitted-program
            # outputs, never aliases of cached source batches.
            from spark_rapids_tpu.history.fragcache import fragment_cache
            fragment_cache().insert(frag_key, ctx.logical_plan, outs, ctx)
        # the answer is on the host: the stage's device outputs are let
        # go here, under the span, not as this frame unwinds
        del run, outs
    if not hbs:
        from spark_rapids_tpu.plan.physical import _empty_host_col
        return HostBatch(root.output_schema, [
            _empty_host_col(f) for f in root.output_schema.fields])
    from spark_rapids_tpu.plan.physical import concat_result
    return concat_result(hbs)
