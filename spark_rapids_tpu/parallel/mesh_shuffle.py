"""Device-mesh shuffle: XLA all-to-all over ICI (the accelerated-shuffle
analogue of the reference's UCX transport, SURVEY.md section 2.7b).

Where the reference moves map-side device batches between executors with UCX
tag-matched sends (UCX.scala:247-311), the TPU build keeps each partition's
batch sharded over a ``jax.sharding.Mesh`` and exchanges rows with a single
``lax.all_to_all`` collective inside ``shard_map`` — the transfer rides ICI
and is scheduled by XLA, no progress thread / bounce buffers needed.

Layout contract: a *mesh batch* is a pytree of arrays whose leading axis is
the mesh's ``data`` axis (one slice per device): data[N, cap], validity
[N, cap], num_rows[N].  Varlen columns (strings, arrays) ride the same
collective as fixed-width columns: each device's flat element buffer is
re-bucketed by destination inside the SPMD program and moves as one
``[N, ecap]`` stream with per-bucket element counts, the offsets layout
rebuilt on the receive side — the TPU answer to the reference's
bounce-buffer framing of varlen buffers
(RapidsShuffleServer.scala:343-612), with no host staging on either side.

:func:`mesh_exchange_batches` is the engine-facing entry: it is what
``TpuShuffleExchangeExec`` calls when a >1-device mesh is active
(``spark.rapids.shuffle.ici.enabled``), making the collective the query
plan's shuffle rather than a standalone demo.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import ColumnBatch, DeviceColumn
from spark_rapids_tpu.kernels.layout import (
    gather_stacked_elements, gather_stacked_rows,
    stacked_row_compaction_indices,
)

DATA_AXIS = "data"

# Every shard_map here and in mesh_spmd/distributed passes
# ``check_vma=False``: the static replication checker has no rule for
# ``pallas_call`` (kernel-tier kernels traced inside mesh programs raise
# NotImplementedError) and mis-tracks ``lax.scan`` carries mixing a
# replicated build side with sharded probe rows.  It is advisory only —
# output specs are verified structurally by plan_verify.


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    """n-device 1-D mesh on the ``data`` axis.

    The mesh is built from the DEFAULT platform's devices only, and
    raises when it has fewer than ``n_devices``: a process on a TPU never
    switches a mesh to CPU virtual devices (that is how a run mislabels
    CPU virtual-device scaling as ICI scaling).  Mesh logic is exercised
    without hardware by making the CPU the default platform
    (``JAX_PLATFORMS=cpu`` + ``--xla_force_host_platform_device_count``,
    as tests/conftest.py does).
    """
    devs = jax.devices()
    if n_devices is not None and len(devs) < n_devices:
        raise RuntimeError(
            f"need {n_devices} devices, default platform "
            f"{devs[0].platform!r} has {len(devs)}; for a CPU rehearsal "
            f"set JAX_PLATFORMS=cpu and "
            f"--xla_force_host_platform_device_count={n_devices}")
    n = n_devices or len(devs)
    return Mesh(np.array(devs[:n]), (DATA_AXIS,))


def _local_partition_buckets(data_cols, validity_cols, num_rows, pids,
                             n: int, cap: int):
    """Split local rows into n destination buckets of fixed capacity cap.

    Returns (bucketed columns [n, cap], bucketed validity [n, cap],
    counts [n]).  Gather-formulated: bucket d row j = j-th local row with
    pid == d.
    """
    live = jnp.arange(cap, dtype=jnp.int32) < num_rows
    pids = jnp.where(live, pids, n)  # padding rows to a dead bucket
    # stable order rows by pid -> rows of bucket d are contiguous
    order = jnp.argsort(pids, stable=True).astype(jnp.int32)
    sorted_pids = pids[order]
    counts = jnp.zeros(n + 1, dtype=jnp.int32).at[sorted_pids].add(
        1, mode="drop")[:n]
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(counts).astype(jnp.int32)[:-1]])
    # bucket[d, j] = sorted row at starts[d] + j (valid when j < counts[d])
    d_idx = jnp.arange(n, dtype=jnp.int32)[:, None]
    j_idx = jnp.arange(cap, dtype=jnp.int32)[None, :]
    src = jnp.clip(starts[:, None] + j_idx, 0, cap - 1)
    in_bucket = j_idx < counts[:, None]
    rows = order[src]
    out_data = [jnp.where(in_bucket, c[rows], 0) for c in data_cols]
    out_valid = [jnp.where(in_bucket, v[rows], False)
                 for v in validity_cols]
    return out_data, out_valid, counts


def _compact_received(data_cols, validity_cols, counts, n: int, cap: int):
    """Concatenate n received buckets ([n, cap] each) into one local batch
    of capacity n*cap."""
    total = jnp.sum(counts)
    out_cap = n * cap
    flat_pos = jnp.arange(out_cap, dtype=jnp.int32)
    cum = jnp.cumsum(counts)
    starts = cum - counts
    bucket = jnp.searchsorted(cum, flat_pos, side="right").astype(jnp.int32)
    bucket_c = jnp.clip(bucket, 0, n - 1)
    within = flat_pos - starts[bucket_c]
    live = flat_pos < total
    within = jnp.clip(within, 0, cap - 1)
    out_data = [jnp.where(live, c[bucket_c, within], 0) for c in data_cols]
    out_valid = [jnp.where(live, v[bucket_c, within], False)
                 for v in validity_cols]
    return out_data, out_valid, total.astype(jnp.int32)


def make_exchange_fn(mesh: Mesh, n_cols: int, cap: int):
    """Build a jittable SPMD function exchanging rows by partition id.

    fn(data_cols [N,cap]xk, validity_cols [N,cap]xk, num_rows [N],
       pids [N,cap]) -> (data [N, N*cap]xk, validity ..., num_rows [N])
    """
    n = mesh.shape[DATA_AXIS]

    def spmd(data_cols, validity_cols, num_rows, pids):
        # inside shard_map: leading axis is local (size 1); drop it
        data_cols = [c[0] for c in data_cols]
        validity_cols = [v[0] for v in validity_cols]
        nr = num_rows[0]
        p = pids[0]
        b_data, b_valid, counts = _local_partition_buckets(
            data_cols, validity_cols, nr, p, n, cap)
        # exchange bucket d -> device d; receive one bucket per device
        r_data = [jax.lax.all_to_all(c, DATA_AXIS, 0, 0, tiled=False)
                  for c in b_data]
        r_valid = [jax.lax.all_to_all(v, DATA_AXIS, 0, 0, tiled=False)
                   for v in b_valid]
        r_counts = jax.lax.all_to_all(counts, DATA_AXIS, 0, 0, tiled=False)
        o_data, o_valid, o_rows = _compact_received(
            r_data, r_valid, r_counts, n, cap)
        return ([c[None] for c in o_data], [v[None] for v in o_valid],
                o_rows[None])

    in_specs = (
        [P(DATA_AXIS, None)] * n_cols,
        [P(DATA_AXIS, None)] * n_cols,
        P(DATA_AXIS),
        P(DATA_AXIS, None),
    )
    out_specs = ([P(DATA_AXIS, None)] * n_cols,
                 [P(DATA_AXIS, None)] * n_cols,
                 P(DATA_AXIS))
    return jax.jit(shard_map(spmd, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))


# --------------------------------------------------------------------------
# Engine-facing batch exchange (strings/arrays included), device-resident
# --------------------------------------------------------------------------
#
# Shuffle payloads never visit the host.  The path is:
#
#   1. pack:    a per-device jitted pad-to-common-capacity of each local
#               batch's raw buffers (data, validity, offsets, pids), run on
#               the target mesh device after a device-to-device placement.
#   2. gather:  ``jax.make_array_from_single_device_arrays`` stitches the n
#               per-device shards into mesh-sharded globals — metadata only,
#               no copies.
#   3. exchange: ONE shard_map program buckets rows by destination device,
#               streams each varlen column's element buffer as a flat
#               per-bucket run (searchsorted over cumulative lengths — no
#               padded row matrix, so one long string no longer inflates
#               every row's slot), runs one lax.all_to_all per payload over
#               ICI, and compacts the n received buckets into a device-local
#               batch.
#   4. unshard: each output global's addressable shard *is* the per-device
#               result; one jitted squeeze per device yields plain
#               single-device arrays, so downstream per-partition programs
#               stay strictly local (no hidden collectives, no rendezvous
#               hazard between interleaved consumers).
#
# This is the TPU answer to the reference's device-resident shuffle: map
# output batches stay in the device store
# (RapidsShuffleInternalManager.scala:91-154) and receives land directly in
# device buffers (RapidsShuffleClient.scala:108-355); here both legs are a
# single XLA-scheduled collective.  tests/test_mesh_shuffle.py asserts that
# no payload-sized jax.device_get happens between map eval and consumption.


def _fit_1d(x, out_len: int):
    """Pad with zeros or truncate to ``out_len``.

    Truncation is safe because callers size out_len from live row / element
    counts (host_sizes): everything past them is padding."""
    in_len = int(x.shape[0])
    if in_len == out_len:
        return x
    if in_len > out_len:
        return x[:out_len]
    pad = jnp.zeros((out_len - in_len,), dtype=x.dtype)
    return jnp.concatenate([x, pad])


def _make_pack_fn(schema, cap: int, ecaps: dict):
    """Jitted per-device pack: fit every buffer of (columns, num_rows, pids)
    to the common capacities and add a leading shard axis of size 1."""

    def pack(columns, num_rows, pids):
        payloads = []
        for ci, f in enumerate(schema.fields):
            c = columns[ci]
            if c.offsets is not None:
                ecap = ecaps[ci]
                data = _fit_1d(c.data, ecap)
                # fit offsets: padded rows repeat the end offset
                # (zero-length); truncation keeps all live rows' offsets
                offs = c.offsets
                if int(offs.shape[0]) > cap + 1:
                    offs = offs[:cap + 1]
                elif int(offs.shape[0]) < cap + 1:
                    tail = jnp.full((cap + 1 - int(offs.shape[0]),),
                                    0, dtype=offs.dtype) + offs[-1]
                    offs = jnp.concatenate([offs, tail])
                payloads += [data[None], offs.astype(jnp.int32)[None],
                             _fit_1d(c.validity, cap)[None]]
            else:
                payloads += [_fit_1d(c.data, cap)[None],
                             _fit_1d(c.validity, cap)[None]]
        payloads.append(_fit_1d(pids.astype(jnp.int32), cap)[None])
        payloads.append(jnp.asarray(num_rows, jnp.int32).reshape(1))
        return payloads

    return jax.jit(pack)


@jax.jit
def _unshard(arrs):
    """Drop the leading shard axis of each per-device output shard — one
    dispatch per device, on that device."""
    return [a[0] for a in arrs]


def _exchange_shard(cols, nr, pid, sig, n: int, cap: int, ecaps,
                    out_cap: int, out_ecaps):
    """Per-shard body of the varlen re-bucketing all_to_all collective.

    Traceable and collective-bearing: must run inside ``shard_map`` over
    ``DATA_AXIS``.  Shared verbatim by the host-driven exchange
    (:func:`_make_mesh_payload_fn`) and the fused whole-stage SPMD path
    (:func:`exchange_batch_collective` via parallel.mesh_spmd), so the two
    routes are bit-identical by construction.

    ``cols`` is the flat single-device payload list in schema order
    (varlen -> elements[ecap], offsets[cap+1], validity[cap]; fixed ->
    data[cap], validity[cap]); ``ecaps``/``out_ecaps`` index by FIELD
    ordinal (0 for fixed columns).  Returns (outs, total): the received
    payload list in the same order (offsets rebuilt, zeros past the live
    region) and the received live-row count.
    """

    def a2a(x):
        return jax.lax.all_to_all(x, DATA_AXIS, 0, 0, tiled=False)

    live = jnp.arange(cap, dtype=jnp.int32) < nr
    pid = jnp.where(live, pid, n)  # padding rows -> dead bucket
    order = jnp.argsort(pid, stable=True).astype(jnp.int32)
    sorted_pid = pid[order]
    counts = jnp.zeros(n + 1, jnp.int32).at[sorted_pid].add(
        1, mode="drop")[:n]
    starts = jnp.cumsum(counts) - counts
    j_idx = jnp.arange(cap, dtype=jnp.int32)[None, :]
    src = jnp.clip(starts[:, None] + j_idx, 0, cap - 1)
    in_bucket = j_idx < counts[:, None]
    rows = order[src]  # [n, cap] source row per (dest bucket, slot)

    send = []          # bucketed payloads, one list entry per wire array
    recv_plan = []     # (kind, ...) mirror for the receive side
    slot = 0
    for vi, is_varlen in enumerate(sig):
        if is_varlen:
            data, offs, valid = cols[slot], cols[slot + 1], cols[slot + 2]
            ecap = ecaps[vi]
            lens = jnp.where(live, offs[1:] - offs[:-1], 0) \
                .astype(jnp.int32)
            slens = lens[order]
            scum = jnp.cumsum(slens).astype(jnp.int32)
            sexcl = scum - slens
            ecounts = jnp.zeros(n + 1, jnp.int32).at[sorted_pid].add(
                slens, mode="drop")[:n]
            estarts = jnp.cumsum(ecounts) - ecounts
            k = jnp.arange(ecap, dtype=jnp.int32)[None, :]
            pos = estarts[:, None] + k          # [n, ecap]
            r = jnp.clip(jnp.searchsorted(
                scum, pos, side="right").astype(jnp.int32), 0, cap - 1)
            src_e = offs[order[r]] + (pos - sexcl[r])
            elem = data[jnp.clip(src_e, 0, ecap - 1)]
            elem = jnp.where(k < ecounts[:, None], elem,
                             jnp.zeros((), data.dtype))
            blens = jnp.where(in_bucket, lens[rows], 0)
            bvalid = jnp.where(in_bucket, valid[rows], False)
            send += [elem, blens, bvalid, ecounts]
            recv_plan.append(("varlen", vi))
            slot += 3
        else:
            data, valid = cols[slot], cols[slot + 1]
            bdata = jnp.where(in_bucket, data[rows],
                              jnp.zeros((), data.dtype))
            bvalid = jnp.where(in_bucket, valid[rows], False)
            send += [bdata, bvalid]
            recv_plan.append(("fixed", vi))
            slot += 2

    wire = [a2a(x) for x in send] + [a2a(counts)]
    r_counts = wire[-1]

    # receive-side row compaction indices, shared by all columns
    # (kernels/layout.py sharded k-way gather primitives)
    bkt, within, live_o, total = stacked_row_compaction_indices(
        r_counts, n, cap, out_cap)

    outs = []
    wi = 0
    for kind, vi in recv_plan:
        if kind == "varlen":
            relem, rlens, rvalid, recounts = (
                wire[wi], wire[wi + 1], wire[wi + 2], wire[wi + 3])
            wi += 4
            lens_o = jnp.where(live_o, rlens[bkt, within], 0)
            offs_o = jnp.concatenate([
                jnp.zeros(1, jnp.int32),
                jnp.cumsum(lens_o).astype(jnp.int32)])
            elem_o = gather_stacked_elements(
                relem, recounts, n, ecaps[vi], out_ecaps[vi])
            valid_o = gather_stacked_rows(rvalid, bkt, within, live_o)
            outs += [elem_o, offs_o, valid_o]
        else:
            rdata, rvalid = wire[wi], wire[wi + 1]
            wi += 2
            data_o = gather_stacked_rows(rdata, bkt, within, live_o)
            valid_o = gather_stacked_rows(rvalid, bkt, within, live_o)
            outs += [data_o, valid_o]
    return outs, total


def exchange_batch_collective(batch: ColumnBatch, pid, n: int) -> ColumnBatch:
    """In-program mesh exchange of one per-shard batch by destination pid.

    The fused whole-stage SPMD entry (parallel.mesh_spmd): callable only
    inside ``shard_map`` over ``DATA_AXIS``, where ``batch`` is the
    shard-local producer output and ``pid`` int32[cap] names each row's
    destination device.  ZERO host syncs: wire capacities come from the
    batch's STATIC capacity buckets instead of the host-driven path's
    live-size metadata round trip — the fused boundary trades bucket
    padding on the wire for a sync-free dispatch.  Returns the shard's
    received batch (capacity round_up(n*cap), rows in sender order), bit
    identical to :func:`mesh_exchange_batches` output for the same rows.
    """
    from spark_rapids_tpu.batch import round_up_capacity
    from spark_rapids_tpu.kernels.layout import ensure_row_layout
    batch = ensure_row_layout(batch)
    schema = batch.schema
    cap = batch.capacity
    sig = tuple(f.dtype.is_string or getattr(f.dtype, "is_array", False)
                for f in schema.fields)
    ecaps = tuple(int(batch.columns[ci].data.shape[0]) if sig[ci] else 0
                  for ci in range(len(schema.fields)))
    out_cap = round_up_capacity(n * cap)
    out_ecaps = tuple(round_up_capacity(n * e, minimum=16) if e else 0
                      for e in ecaps)
    cols = []
    for ci, c in enumerate(batch.columns):
        if sig[ci]:
            cols += [c.data, c.offsets.astype(jnp.int32), c.validity]
        else:
            cols += [c.data, c.validity]
    outs, total = _exchange_shard(
        cols, batch.num_rows, jnp.asarray(pid, jnp.int32), sig, n, cap,
        ecaps, out_cap, out_ecaps)
    new_cols = []
    ai = 0
    for ci, f in enumerate(schema.fields):
        if sig[ci]:
            elem, offs, valid = outs[ai], outs[ai + 1], outs[ai + 2]
            ai += 3
            new_cols.append(DeviceColumn(f.dtype, elem, valid, offs))
        else:
            data, valid = outs[ai], outs[ai + 1]
            ai += 2
            new_cols.append(DeviceColumn(f.dtype, data, valid, None))
    return ColumnBatch(schema, new_cols, total, out_cap)


def _make_mesh_payload_fn(mesh: Mesh, sig, cap: int, ecaps: tuple,
                          out_cap: int, out_ecaps: tuple):
    """The SPMD exchange program over one batch schema shape.

    ``sig[i]`` is True for varlen columns.  Payload order per column:
    varlen -> (elements[ecap], offsets[cap+1], validity[cap]);
    fixed  -> (data[cap], validity[cap]); then pids[cap], num_rows[1].
    """
    n = mesh.shape[DATA_AXIS]

    def spmd(payloads):
        pls = [p[0] for p in payloads[:-1]]
        nr = payloads[-1][0]
        pid = pls[-1]
        cols = pls[:-1]
        outs, total = _exchange_shard(
            cols, nr, pid, sig, n, cap, ecaps, out_cap, out_ecaps)
        return [o[None] for o in outs] + [total[None]]

    in_specs = []
    for is_varlen in sig:
        k = 3 if is_varlen else 2
        in_specs += [P(DATA_AXIS, None)] * k
    in_specs += [P(DATA_AXIS, None), P(DATA_AXIS)]
    out_specs = []
    for is_varlen in sig:
        k = 3 if is_varlen else 2
        out_specs += [P(DATA_AXIS, None)] * k
    out_specs.append(P(DATA_AXIS))
    return jax.jit(shard_map(spmd, mesh=mesh, in_specs=(in_specs,),
                             out_specs=out_specs, check_vma=False))


# Compiled exchange programs, keyed by (mesh, schema signature, capacities).
# LRU-capped: every new capacity bucket x schema shape compiles and retains
# an SPMD program, the same pathology the plan-fingerprint cache caps.
_EXCHANGE_CACHE_MAX = 64
_exchange_fn_cache: "OrderedDict" = None  # type: ignore[assignment]


def _cached(key, builder):
    global _exchange_fn_cache
    if _exchange_fn_cache is None:
        from collections import OrderedDict
        _exchange_fn_cache = OrderedDict()
    fn = _exchange_fn_cache.get(key)
    if fn is None:
        fn = builder()
        _exchange_fn_cache[key] = fn
        while len(_exchange_fn_cache) > _EXCHANGE_CACHE_MAX:
            _exchange_fn_cache.popitem(last=False)
    else:
        _exchange_fn_cache.move_to_end(key)
    return fn


def mesh_exchange_batches(mesh: Mesh, local_batches, pids_list,
                          schema, stats: Optional[dict] = None
                          ) -> List[ColumnBatch]:
    """Exchange rows of per-device batches so every row lands on the device
    its pid names — the engine's accelerated shuffle.

    ``local_batches``: one ColumnBatch (or None) per mesh device.
    ``pids_list``: per-batch int32[cap] destination device ids in [0, n).
    Returns one ColumnBatch per device; every array in the outputs is a
    plain single-device array on its mesh device, and no payload buffer
    touches the host anywhere on this path.

    ``stats`` (optional dict) receives byte accounting for the exchange —
    the role of the reference's per-read shuffle metrics
    (RapidsCachingReader.scala:125-133):
      payload_bytes — LIVE rows x fixed row bytes + live varlen element
                      bytes (what "shuffle bytes written" means upstream);
      wire_bytes    — total size of the padded arrays the all_to_all
                      actually moves (upper bound incl. bucket padding).
    """
    from spark_rapids_tpu.batch import round_up_capacity
    n = mesh.shape[DATA_AXIS]
    devices = list(mesh.devices.flat)
    assert len(local_batches) == n and len(pids_list) == n
    present = [i for i, b in enumerate(local_batches) if b is not None]
    if not present:
        return []

    # Common static capacities, sized by LIVE rows/elements — one scalar
    # metadata round trip (the analogue of the reference's metadata
    # request/response before buffer transfer), so a sparse batch that kept
    # a huge input capacity doesn't inflate the wire shapes n-fold.
    from spark_rapids_tpu.batch import host_sizes
    sizes = host_sizes([local_batches[i] for i in present])
    cap = round_up_capacity(max(max(r for r, _ in sizes), 1))
    sig = tuple(f.dtype.is_string or getattr(f.dtype, "is_array", False)
                for f in schema.fields)
    ecaps = {}
    vi = 0
    for ci, f in enumerate(schema.fields):
        if sig[ci]:
            ecaps[ci] = round_up_capacity(
                max(max(totals[vi] for _, totals in sizes), 1), minimum=16)
            vi += 1
    out_cap = round_up_capacity(n * cap)
    out_ecaps = {ci: round_up_capacity(n * e) for ci, e in ecaps.items()}

    if stats is not None:
        from spark_rapids_tpu.batch import fixed_row_bytes, \
            varlen_byte_scales
        frb = fixed_row_bytes(schema)
        vscales = varlen_byte_scales(schema)
        by_dev = {d: rows * frb + sum(
            t * sc for t, sc in zip(totals, vscales))
            for d, (rows, totals) in zip(present, sizes)}
        stats["bytes_per_device"] = [by_dev.get(d, 0) for d in range(n)]
        stats["payload_bytes"] = sum(by_dev.values())
        # wire arrays: per column, bucketed [n, cap] (or [n, ecap]) on each
        # of n devices -> n x the packed global size, + counts
        wire = 0
        for ci, f in enumerate(schema.fields):
            if sig[ci]:
                edt = np.dtype(np.uint8) if f.dtype.is_string \
                    else np.dtype(f.dtype.element.np_dtype)
                wire += n * n * (ecaps[ci] * edt.itemsize  # elements
                                 + cap * 4                 # lens
                                 + cap * 1)                # validity
            else:
                itemsize = np.dtype(f.dtype.np_dtype).itemsize
                wire += n * n * cap * (itemsize + 1)
        stats["wire_bytes"] = wire

    sig_key = tuple((f.dtype, sig[ci]) for ci, f in enumerate(schema.fields))
    ecaps_t = tuple(ecaps.get(ci, 0) for ci in range(len(schema.fields)))
    oecaps_t = tuple(out_ecaps.get(ci, 0) for ci in range(len(schema.fields)))

    pack = _cached(("pack", mesh, sig_key, cap, ecaps_t),
                   lambda: _make_pack_fn(schema, cap, ecaps))
    fn = _cached(("spmd", mesh, sig_key, cap, ecaps_t, out_cap, oecaps_t),
                 lambda: _make_mesh_payload_fn(
                     mesh, sig, cap, ecaps_t, out_cap, oecaps_t))

    # Per-device pack on the mesh device (device-to-device placement only).
    shards_per_payload = None
    for d in range(n):
        b = local_batches[d]
        if b is None:
            cols, nr, pid = _empty_cols(schema, ecaps), 0, \
                jnp.zeros(cap, jnp.int32)
        else:
            if any(c.codes is not None for c in b.columns):
                # Dictionary-encoded columns materialize before packing:
                # the collective's wire format is (elements, lens,
                # validity) per varlen column, and host_sizes above
                # already sized ecaps at MATERIALIZED totals.  (The
                # single-host exchange keeps codes on the wire —
                # exchange.dictAware — but cross-device pieces would each
                # need the whole dictionary; see docs/shuffle.md.)
                if stats is not None:
                    # bytes the encoded corridor gives up at this
                    # boundary: the MATERIALIZED element bytes of the
                    # encoded columns (host_sizes already fetched them —
                    # no extra sync), surfaced as the exchange's
                    # mesh_materialize obs instant
                    from spark_rapids_tpu.batch import varlen_byte_scales
                    vs = varlen_byte_scales(schema)
                    _, totals = sizes[present.index(d)]
                    enc_flags = [c.codes is not None
                                 for c in b.columns if c.is_varlen]
                    stats["materialized_bytes"] = \
                        stats.get("materialized_bytes", 0) + sum(
                            int(t) * sc for t, sc, e
                            in zip(totals, vs, enc_flags) if e)
                    stats["encoded_materialized"] = \
                        stats.get("encoded_materialized", 0) + 1
                from spark_rapids_tpu.kernels.layout import ensure_row_layout
                b = ensure_row_layout(b)
            cols, nr, pid = list(b.columns), b.num_rows, pids_list[d]
        moved = jax.device_put((cols, nr, pid), devices[d])
        payloads = pack(*moved)
        if shards_per_payload is None:
            shards_per_payload = [[] for _ in payloads]
        for si, p in enumerate(payloads):
            shards_per_payload[si].append(p)

    sh2 = NamedSharding(mesh, P(DATA_AXIS, None))
    sh1 = NamedSharding(mesh, P(DATA_AXIS))
    globals_ = []
    for shards in shards_per_payload:
        tail = shards[0].shape[1:]
        sh = sh2 if tail else sh1
        globals_.append(jax.make_array_from_single_device_arrays(
            (n,) + tail, sh, shards))

    outs = fn(globals_)

    # Unshard: collect each device's shard of every output, squeeze the
    # shard axis in one per-device dispatch.
    per_dev_arrays = [[] for _ in range(n)]
    dev_pos = {d: i for i, d in enumerate(devices)}
    for g in outs:
        for shard in g.addressable_shards:
            per_dev_arrays[dev_pos[shard.device]].append(shard.data)
    results: List[ColumnBatch] = []
    for d in range(n):
        arrs = _unshard(per_dev_arrays[d])
        cols = []
        ai = 0
        for ci, f in enumerate(schema.fields):
            if sig[ci]:
                elem, offs, valid = arrs[ai], arrs[ai + 1], arrs[ai + 2]
                ai += 3
                cols.append(DeviceColumn(f.dtype, elem, valid, offs))
            else:
                data, valid = arrs[ai], arrs[ai + 1]
                ai += 2
                cols.append(DeviceColumn(f.dtype, data, valid, None))
        results.append(ColumnBatch(schema, cols, arrs[ai], out_cap))
    return results


def _empty_cols(schema, ecaps):
    cols = []
    for ci, f in enumerate(schema.fields):
        if f.dtype.is_string or getattr(f.dtype, "is_array", False):
            edt = jnp.uint8 if f.dtype.is_string \
                else f.dtype.element.np_dtype
            cols.append(DeviceColumn(
                f.dtype, jnp.zeros(ecaps[ci], edt),
                jnp.zeros(1, jnp.bool_), jnp.zeros(2, jnp.int32)))
        else:
            cols.append(DeviceColumn(
                f.dtype, jnp.zeros(1, f.dtype.np_dtype),
                jnp.zeros(1, jnp.bool_), None))
    return cols
