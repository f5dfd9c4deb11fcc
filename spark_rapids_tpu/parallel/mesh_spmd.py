"""Whole-stage mesh-SPMD execution: one shard_map program per stage.

The host-driven mesh shuffle (parallel.mesh_shuffle, used when
``spark.rapids.shuffle.ici.enabled`` is on) is already collective on the
wire, but the PLAN around it is still host-driven: the producer stage
dispatches, the driver syncs live sizes, restages per-device batches into
mesh globals, dispatches the exchange program, unshards, and only then
dispatches the consumer stage — one host sync plus two extra dispatch
boundaries per exchange.

With ``spark.rapids.sql.tpu.mesh.spmd.enabled`` this module compiles the
contiguous plan segments on EITHER side of a shuffle into ONE shard_map
program: the producer segment runs per shard, the exchange is an
in-program ``lax.all_to_all`` (mesh_shuffle.exchange_batch_collective —
the same varlen re-bucketing collective the host-driven path dispatches,
so the two routes are bit-identical by construction), and the consumer
segment keeps going on the received rows without the program ever
returning to the host.  Zero host syncs at the boundary: wire capacities
come from the inputs' STATIC capacity buckets, trading bucket padding on
the wire for a sync-free dispatch (docs/mesh.md's fusion table).

How a stage gets here: plan/pipeline's builder runs under a
MeshBuildScope when ``ExecContext.mesh_spmd_active()``; a mesh-compatible
``TpuShuffleExchangeExec`` then inlines as the collective instead of
becoming a stage source and records itself on the scope, and
``_run_stage`` diverts the stage to :func:`run_mesh_stage`.  Exchanges
whose partitioning cannot lower in-program (partitioning.mesh_compatible:
range, single) stay host-driven sources — per-stage auto-fallback, under
``spark.rapids.sql.tpu.mesh.spmd.autoFallback``.

Input lowering (the PartitionSpec pytree threaded through the program):

* distributed sources — batch k of a source goes to device ``k % n``
  (exactly the host-driven path's ``per_dev[k % n]`` interleave, so pid
  assignment matches bit-for-bit), stacked per round-robin *slot* into
  ``[n, ...]`` globals via ``jax.make_array_from_single_device_arrays``
  after a per-device jitted pack to the slot's common static capacities;
  every leaf enters the program with spec ``P("data", None, ...)``.
* replicated sources (broadcast-join build sides) — each leaf is
  ``device_put`` with ``NamedSharding(mesh, P())``: one identical copy
  per device, spec all-``None`` — broadcast lowers to replication.

Outputs leave with spec ``P("data")``; each device's addressable shard is
that shard's result batch, squeezed to plain single-device arrays so
downstream programs stay strictly local.  The stacked output globals are
registered ONCE with the spill catalog across the unshard window
(catalog.register_sharded: one handle, per-shard byte accounting).

``mesh:*`` fault-injection fires before the program launches, so a
device-lost replays the full producer+exchange+consumer segment from
lineage (plan/recovery ladder); compiled programs are cached per
(variant, device generation, static input signature) on the stage root.
"""

from __future__ import annotations

import contextlib
import threading
from collections import OrderedDict
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from spark_rapids_tpu.batch import ColumnBatch, DeviceColumn, \
    round_up_capacity
from spark_rapids_tpu.obs import events as obs_events
from spark_rapids_tpu.parallel.mesh_shuffle import (
    DATA_AXIS, _fit_1d, _unshard,
)
from spark_rapids_tpu.utils.compile_registry import instrumented_jit
from spark_rapids_tpu.utils.tracing import device_read, span


def _is_varlen(f) -> bool:
    return f.dtype.is_string or getattr(f.dtype, "is_array", False)


def _payload_len(schema) -> int:
    """Flat payload arrays per batch of ``schema``: varlen columns ride as
    (elements, offsets, validity), fixed as (data, validity), plus one
    num_rows array."""
    return sum(3 if _is_varlen(f) else 2 for f in schema.fields) + 1


def _col_elem_cap(c) -> int:
    # dictionary-encoded columns materialize inside the pack's
    # ensure_row_layout guard: size the slot for the decoded bytes
    if c.codes is not None:
        return max(int(c.mat_byte_cap), 16)
    return int(c.data.shape[0])


def _pad_batch(schema, cap: int, ecaps: Tuple[int, ...]) -> ColumnBatch:
    """Zero-row batch at the slot's static capacities — the filler for
    mesh devices a source has no batch for (K not divisible by n)."""
    cols = []
    for ci, f in enumerate(schema.fields):
        if _is_varlen(f):
            edt = jnp.uint8 if f.dtype.is_string \
                else f.dtype.element.np_dtype
            cols.append(DeviceColumn(
                f.dtype, jnp.zeros(ecaps[ci], edt),
                jnp.zeros(cap, jnp.bool_), jnp.zeros(cap + 1, jnp.int32)))
        else:
            cols.append(DeviceColumn(
                f.dtype, jnp.zeros(cap, f.dtype.np_dtype),
                jnp.zeros(cap, jnp.bool_), None))
    return ColumnBatch(schema, cols, 0, cap)


# Per-device pack programs, keyed by (varlen signature, capacities) — the
# same LRU discipline as mesh_shuffle's exchange-program cache.
_PACK_CACHE_MAX = 64
_pack_cache: "OrderedDict" = OrderedDict()


def _pack_fn(schema, cap: int, ecaps: Tuple[int, ...]):
    """Jitted per-device pack of one ColumnBatch to the slot's common
    static capacities, each buffer gaining a leading shard axis of 1 —
    the per-shard half of a ``[n, ...]`` mesh global."""
    sig_key = tuple((f.dtype, _is_varlen(f)) for f in schema.fields)
    key = (sig_key, cap, ecaps)
    fn = _pack_cache.get(key)
    if fn is not None:
        _pack_cache.move_to_end(key)
        return fn

    def pack(b):
        from spark_rapids_tpu.kernels.layout import ensure_row_layout
        b = ensure_row_layout(b)
        out = []
        for ci, f in enumerate(b.schema.fields):
            c = b.columns[ci]
            if c.offsets is not None:
                offs = c.offsets
                if int(offs.shape[0]) > cap + 1:
                    offs = offs[:cap + 1]
                elif int(offs.shape[0]) < cap + 1:
                    tail = jnp.zeros((cap + 1 - int(offs.shape[0]),),
                                     offs.dtype) + offs[-1]
                    offs = jnp.concatenate([offs, tail])
                out += [_fit_1d(c.data, ecaps[ci])[None],
                        offs.astype(jnp.int32)[None],
                        _fit_1d(c.validity, cap)[None]]
            else:
                out += [_fit_1d(c.data, cap)[None],
                        _fit_1d(c.validity, cap)[None]]
        out.append(jnp.asarray(b.num_rows, jnp.int32).reshape(1))
        return out

    fn = instrumented_jit(pack, label="meshSpmd:pack")
    _pack_cache[key] = fn
    while len(_pack_cache) > _PACK_CACHE_MAX:
        _pack_cache.popitem(last=False)
    return fn


def _batch_from_payloads(schema, pls, cap: int,
                         squeeze: bool) -> ColumnBatch:
    """Rebuild a ColumnBatch from its flat payload list (``squeeze`` drops
    the leading shard axis — the in-program view of a slot's global)."""
    cols = []
    ai = 0
    for f in schema.fields:
        if _is_varlen(f):
            data, offs, valid = pls[ai], pls[ai + 1], pls[ai + 2]
            ai += 3
            if squeeze:
                data, offs, valid = data[0], offs[0], valid[0]
            cols.append(DeviceColumn(f.dtype, data, valid, offs))
        else:
            data, valid = pls[ai], pls[ai + 1]
            ai += 2
            if squeeze:
                data, valid = data[0], valid[0]
            cols.append(DeviceColumn(f.dtype, data, valid, None))
    nr = pls[ai]
    if squeeze:
        nr = nr[0]
    return ColumnBatch(schema, cols, nr, cap)


def _out_capacity(schema, pl) -> int:
    """Recover a flat output payload list's row capacity from its static
    shapes (trailing shard-axis layout: varlen offsets are [n, cap+1],
    fixed data is [n, cap])."""
    if schema.fields and _is_varlen(schema.fields[0]):
        return int(pl[1].shape[-1]) - 1
    return int(pl[0].shape[-1])


def _full_rank_spec(rank: int, sharded: bool):
    if not sharded:
        return P(*([None] * rank))
    return P(DATA_AXIS, *([None] * (rank - 1)))


def _global_batch(schema, pl, cap: int) -> ColumnBatch:
    """The STACKED view of one output: every leaf a mesh-sharded global.
    Used only for catalog accounting (register_sharded) — ``num_rows`` is
    the per-shard [n] count vector, not a scalar."""
    return _batch_from_payloads(schema, pl, cap, squeeze=False)


_OVERFLOW = threading.local()


def note_overflow_flag(flag) -> None:
    """Trace-time channel from a fused join to the mesh program: a join
    lowering with static bucketed output sizing calls this with its
    traced overflow bool; :func:`run_mesh_stage`'s program body collects
    every flag into one extra program output it checks post-dispatch
    (the only host read a fused stage pays, and only when a join fused).
    No-op outside a collecting mesh program body."""
    sink = getattr(_OVERFLOW, "sink", None)
    if sink is not None:
        sink.append(jnp.any(flag))


@contextlib.contextmanager
def _collect_overflow():
    prev = getattr(_OVERFLOW, "sink", None)
    sink = []
    _OVERFLOW.sink = sink
    try:
        yield sink
    finally:
        _OVERFLOW.sink = prev


def run_mesh_stage(root, ctx, variant: str, shrink: bool = True):
    """Dispatch a stage whose build fused >=1 exchange as ONE shard_map
    program over ``ctx.mesh`` — plan/pipeline._dispatch_stage's mesh
    divert; a ``StageRun`` like the host path's, its flags one vector a
    speculating operator with one entry a device."""
    from spark_rapids_tpu.fault import inject
    inject.maybe_fire("mesh")
    from spark_rapids_tpu.plan import pipeline as PL
    from spark_rapids_tpu.runtime.device import DeviceRuntime
    mesh = ctx.mesh
    n = mesh.shape[DATA_AXIS]
    devices = list(mesh.devices.flat)
    sources, fn = PL._stage_build(root, ctx, variant)
    exchanges, replicated, joins = root._mesh_stage_info[variant]
    mats = PL._materialize_sources(sources, ctx)
    PL.shrink_materialized(mats, ctx)

    sh_rep = NamedSharding(mesh, P())
    flat_globals: List = []
    in_specs: List = []
    src_plans: List = []
    sig_parts: List = []
    for i, src in enumerate(sources):
        batches = mats[i][0]
        schema = src.output_schema
        if i in replicated:
            # broadcast build side: one identical copy per device, spec
            # all-None — replication, not sharding
            tds = []
            for b in batches:
                leaves, td = jax.tree_util.tree_flatten(b)
                for leaf in leaves:
                    g = jax.device_put(leaf, sh_rep)
                    flat_globals.append(g)
                    in_specs.append(_full_rank_spec(g.ndim, sharded=False))
                tds.append(td)
            src_plans.append(("rep", tds))
            sig_parts.append(("rep", tuple(tds)))
        else:
            # batch k -> device k % n, slot k // n: the host-driven mesh
            # path's per_dev interleave, so round-robin pids see every
            # row at the same position on the same device
            nslots = max(1, -(-len(batches) // n))
            slot_caps = []
            for s in range(nslots):
                group = [batches[s * n + d] if s * n + d < len(batches)
                         else None for d in range(n)]
                have = [b for b in group if b is not None]
                cap = round_up_capacity(
                    max((b.capacity for b in have), default=8))
                ecaps = tuple(
                    round_up_capacity(
                        max((_col_elem_cap(b.columns[ci]) for b in have),
                            default=16), minimum=16)
                    if _is_varlen(f) else 0
                    for ci, f in enumerate(schema.fields))
                pack = _pack_fn(schema, cap, ecaps)
                shards_per_payload: Optional[List[list]] = None
                for d in range(n):
                    b = group[d]
                    if b is None:
                        b = _pad_batch(schema, cap, ecaps)
                    payloads = pack(jax.device_put(b, devices[d]))
                    if shards_per_payload is None:
                        shards_per_payload = [[] for _ in payloads]
                    for pi, p in enumerate(payloads):
                        shards_per_payload[pi].append(p)
                for shards in shards_per_payload:
                    tail = shards[0].shape[1:]
                    spec = _full_rank_spec(len(tail) + 1, sharded=True)
                    flat_globals.append(
                        jax.make_array_from_single_device_arrays(
                            (n,) + tail, NamedSharding(mesh, spec),
                            shards))
                    in_specs.append(spec)
                slot_caps.append((cap, ecaps))
            src_plans.append(("dist", slot_caps))
            sig_parts.append(("dist", tuple(slot_caps)))

    cache = getattr(root, "_mesh_programs", None)
    if not isinstance(cache, dict):
        cache = {}
        root._mesh_programs = cache
    key = (variant, n, DeviceRuntime.generation(), tuple(sig_parts))
    if key not in cache:
        # what the inlined operators noted at the trace (a shard handles
        # one batch a slot: n times that over the mesh), counted again by
        # every dispatch of the program
        noted: list = []

        def body(flat):
            from spark_rapids_tpu.kernels.layout import ensure_row_layout
            args = []
            pos = 0
            for plan, src2 in zip(src_plans, sources):
                schema2 = src2.output_schema
                if plan[0] == "rep":
                    bs = []
                    for td in plan[1]:
                        k = td.num_leaves
                        bs.append(jax.tree_util.tree_unflatten(
                            td, flat[pos:pos + k]))
                        pos += k
                    args.append(tuple(bs))
                else:
                    k = _payload_len(schema2)
                    bs = []
                    for cap, _ecaps in plan[1]:
                        bs.append(_batch_from_payloads(
                            schema2, flat[pos:pos + k], cap, squeeze=True))
                        pos += k
                    args.append(tuple(bs))
            with _collect_overflow() as ovf_flags, \
                    PL.collect_stage_notes() as notes:
                outs = fn(tuple(args))
            noted[:] = [(op, k * n, flag is not None)
                        for op, k, flag in notes]
            stage_flags = tuple(jnp.reshape(flag, (1,))
                                for _, _, flag in notes if flag is not None)
            ovf = jnp.zeros(1, jnp.bool_)
            for flag in ovf_flags:
                ovf = ovf | jnp.reshape(flag, (1,))
            flat_out = []
            for b in outs:
                b = ensure_row_layout(b)
                pl = []
                for c in b.columns:
                    if c.offsets is not None:
                        pl += [c.data[None],
                               c.offsets.astype(jnp.int32)[None],
                               c.validity[None]]
                    else:
                        pl += [c.data[None], c.validity[None]]
                pl.append(jnp.asarray(b.num_rows, jnp.int32).reshape(1))
                flat_out.append(pl)
            return flat_out, ovf, stage_flags

        from jax import shard_map
        # replication checker off unconditionally (not just for the
        # replicated-build fused join): pallas-tier kernels traced inside
        # the stage body have no replication rule — see mesh_shuffle
        program = instrumented_jit(
            shard_map(body, mesh=mesh, in_specs=(tuple(in_specs),),
                      out_specs=P(DATA_AXIS), check_vma=False),
            label=f"meshStage:{root.name}")
        cache[key] = (program, noted)
    program, noted = cache[key]

    with span("mesh", "program", root.op_id) as mesh_span:
        ctx.metric("pipeline", "programs").add(1)
        ctx.metric("pipeline", "meshProgramDispatches").add(1)
        for ex in exchanges:
            ctx.metric(ex.op_id, "meshBoundariesFused").add(1)
        for j in joins:
            ctx.metric(j.op_id, "meshJoinsFused").add(1)
        out_schema = root.output_schema
        overflowed = False
        results: List[ColumnBatch] = []
        with span("stage", root.name, root.op_id):
            out_lists, ovf_g, flags_g = PL._run_oom_guarded(
                ctx, lambda: program(tuple(flat_globals)), args=(),
                retryable=True)
            # the ONLY host read of a fused stage, paid only when a join
            # fused: did any shard's bucketed join output overflow its
            # static capacity?  (a [n]-bool fetch after the one dispatch,
            # not a per-boundary shuffleSync)
            if joins:
                overflowed = bool(device_read(
                    "join_overflow", ovf_g, root.op_id).any())
            if overflowed:
                out_lists = []
            # one catalog handle per stacked output global, closed right
            # after unsharding: per-shard HBM accounting without exposing a
            # long-lived spill victim that would gather every shard
            cat = DeviceRuntime.get(ctx.conf).catalog
            handles = [
                cat.register_sharded(
                    _global_batch(out_schema, pl,
                                  _out_capacity(out_schema, pl)))
                for pl in out_lists]
            bytes_per_device = [0] * n
            for h in handles:
                for d, v in enumerate(h.shard_bytes):
                    bytes_per_device[d] += v
            dev_pos = {d: i for i, d in enumerate(devices)}
            for pl in out_lists:
                cap = _out_capacity(out_schema, pl)
                per_dev: List[list] = [[] for _ in range(n)]
                for g in pl:
                    for shard in g.addressable_shards:
                        per_dev[dev_pos[shard.device]].append(shard.data)
                for d in range(n):
                    arrs = _unshard(per_dev[d])
                    results.append(_batch_from_payloads(
                        out_schema, arrs, cap, squeeze=False))
            for h in handles:
                h.close()
        mesh_span.set(devices=n, fused_boundaries=len(exchanges),
                      fused_joins=len(joins),
                      bytes_per_device=bytes_per_device)
    if overflowed:
        # a shard's true join output exceeded its static bucket: the
        # fused results are invalid — rerun the whole stage host-driven
        # (the classic host-synced join sizes exactly)
        ctx.metric("pipeline", "meshFallbacks").add(1)
        obs_events.emit_instant(
            "mesh", "join_overflow_fallback", root.op_id,
            joins=[j.op_id for j in joins])
        from spark_rapids_tpu.config import MESH_SPMD_AUTO_FALLBACK
        if not MESH_SPMD_AUTO_FALLBACK.get(ctx.conf):
            raise RuntimeError(
                f"{root.name}: fused join output overflowed its static "
                "capacity bucket and "
                "spark.rapids.sql.tpu.mesh.spmd.autoFallback is disabled "
                "(raise mesh.spmd.join.growthFactor or enable "
                "autoFallback)")
        return PL.run_stage_unfused(root, ctx, variant, shrink=shrink)
    # sharding invariants for analysis/plan_verify.check_mesh_sharding:
    # declared specs on every program input/output, boundary flips only
    # at the recorded reshard (exchange) ops — or, for a stage fused
    # around a broadcast join only, at no boundary at all — and no
    # donation under sharding.  ``replicated`` lists the input leaf
    # indices that entered with an all-None (replicated) spec.
    rep_leaves = [i for i, sp in enumerate(in_specs)
                  if all(ax is None for ax in tuple(sp))]
    root._mesh_partition_specs = {
        "in_specs": list(in_specs),
        "out_specs": [P(DATA_AXIS)] * sum(len(pl) for pl in out_lists),
        "reshards": [ex.op_id for ex in exchanges],
        "joins": [j.op_id for j in joins],
        "replicated": rep_leaves,
        "dmask": (False,) * len(sources),
    }
    if shrink:
        results = PL._shrink_outputs_sharded(results, ctx)
    return PL.StageRun(
        root, results, flags_g, tuple(noted),
        lambda: run_mesh_stage(root, ctx, PL._stage_variant(root, ctx),
                               shrink=shrink))
