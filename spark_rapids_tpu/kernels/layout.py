"""Row-movement kernels: gather, filter compaction, concatenation, head.

Reference analogues: cudf ``Table.filter`` (basicPhysicalOperators.scala:121),
``Table.concatenate`` (GpuCoalesceBatches.scala), ``contiguousSplit`` /
gather-based slicing (GpuPartitioning.scala:44-117).

All kernels are pure functions over pytree :class:`ColumnBatch` values and are
safe to call inside ``jax.jit``.  Output capacities are static arguments.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import (
    ColumnBatch, DeviceColumn, round_up_capacity,
)
from spark_rapids_tpu.utils.tracing import device_read, kernel_scope


def _string_lengths(col: DeviceColumn):
    return (col.offsets[1:] - col.offsets[:-1]).astype(jnp.int32)


def _rows_of_positions(offsets, nbytes: int):
    pos = jnp.arange(nbytes, dtype=jnp.int32)
    return jnp.searchsorted(offsets[1:], pos, side="right").astype(jnp.int32)


def _gather_string_column(col: DeviceColumn, indices, live, out_cap: int,
                          out_byte_cap: int) -> DeviceColumn:
    """Gather whole varlen rows (strings, arrays): new row r = old row
    indices[r].

    Output elements are rebuilt with the flat position->row mapping (one
    searchsorted over the new offsets), so the whole thing is gathers +
    a cumsum — no per-row loops.
    """
    src_lens = _string_lengths(col)
    new_lens = jnp.where(live, src_lens[indices], 0)
    new_offsets = jnp.concatenate([
        jnp.zeros(1, dtype=jnp.int32),
        jnp.cumsum(new_lens).astype(jnp.int32),
    ])
    rows = _rows_of_positions(new_offsets, out_byte_cap)
    rows_c = jnp.clip(rows, 0, out_cap - 1)
    pos_in_row = jnp.arange(out_byte_cap, dtype=jnp.int32) - new_offsets[rows_c]
    src_row = indices[rows_c]
    src_pos = col.offsets[src_row] + pos_in_row
    in_range = jnp.arange(out_byte_cap, dtype=jnp.int32) < new_offsets[-1]
    src_pos = jnp.clip(src_pos, 0, int(col.data.shape[0]) - 1)
    data = jnp.where(in_range, col.data[src_pos], 0).astype(col.data.dtype)
    validity = jnp.where(live, col.validity[indices], False)
    return DeviceColumn(col.dtype, data, validity, new_offsets)


@kernel_scope
def gather_rows(batch: ColumnBatch, indices, num_rows,
                out_capacity: Optional[int] = None,
                out_byte_caps: Optional[Sequence[int]] = None,
                keep_encoded: bool = False) -> ColumnBatch:
    """New batch whose row r is ``batch`` row ``indices[r]`` for r < num_rows.

    ``indices`` must be int32[out_capacity] (entries past ``num_rows`` are
    ignored).  ``out_byte_caps`` optionally gives the static byte capacity per
    string column (defaults to the input column's byte capacity — valid
    whenever the gather cannot grow total bytes, e.g. permutations/filters).

    ``keep_encoded`` keeps dictionary-encoded columns encoded: the gather
    permutes the 4-byte codes and shares the input's dictionary buffers
    unchanged.  Only valid when the gather cannot grow the materialized
    total (permutations/filters — exactly when the default byte caps are
    valid), since ``mat_byte_cap`` is carried through as-is.
    """
    if not keep_encoded:
        batch = ensure_row_layout(batch)
    out_cap = out_capacity if out_capacity is not None else batch.capacity
    live = jnp.arange(out_cap, dtype=jnp.int32) < num_rows
    indices = jnp.clip(indices.astype(jnp.int32), 0, batch.capacity - 1)
    indices = jnp.where(live, indices, 0)
    cols = []
    str_i = 0
    for col in batch.columns:
        if col.codes is not None:
            if out_byte_caps is not None:
                str_i += 1  # slot reserved; encoded keeps its mat bucket
            codes = jnp.where(live, col.codes[indices], 0)
            validity = jnp.where(live, col.validity[indices], False)
            cols.append(DeviceColumn(col.dtype, col.data, validity,
                                     col.offsets, codes, col.mat_byte_cap))
        elif col.is_varlen:
            bcap = (out_byte_caps[str_i] if out_byte_caps is not None
                    else int(col.data.shape[0]))
            str_i += 1
            cols.append(_gather_string_column(col, indices, live, out_cap, bcap))
        else:
            data = jnp.where(live, col.data[indices], 0).astype(col.data.dtype)
            validity = jnp.where(live, col.validity[indices], False)
            cols.append(DeviceColumn(col.dtype, data, validity, None))
    return ColumnBatch(batch.schema, cols, jnp.asarray(num_rows, jnp.int32),
                       out_cap)


@kernel_scope
def dict_decode_column(col: DeviceColumn) -> DeviceColumn:
    """Materialize a dictionary-encoded string column to plain row layout.

    The column's data/offsets describe the dictionary ENTRIES; ``codes``
    maps rows to entries and ``mat_byte_cap`` is the static byte bucket
    the materialized bytes fit in (computed at staging from the live
    codes).  Output matches what staging the decoded values would have
    produced: invalid/dead rows contribute zero bytes, offsets constant
    past the live region.  Safe inside ``jax.jit``.
    """
    assert col.codes is not None
    cap = int(col.codes.shape[0])
    nd = int(col.offsets.shape[0]) - 1
    ent_lens = (col.offsets[1:] - col.offsets[:-1]).astype(jnp.int32)
    codes_c = jnp.clip(col.codes, 0, max(nd - 1, 0))
    lens = jnp.where(col.validity, ent_lens[codes_c], 0)
    new_offsets = jnp.concatenate([
        jnp.zeros(1, dtype=jnp.int32),
        jnp.cumsum(lens).astype(jnp.int32),
    ])
    bcap = col.mat_byte_cap if col.mat_byte_cap > 0 else int(col.data.shape[0])
    rows = _rows_of_positions(new_offsets, bcap)
    rows_c = jnp.clip(rows, 0, cap - 1)
    pos_in_row = jnp.arange(bcap, dtype=jnp.int32) - new_offsets[rows_c]
    src_pos = col.offsets[codes_c[rows_c]] + pos_in_row
    src_pos = jnp.clip(src_pos, 0, int(col.data.shape[0]) - 1)
    in_range = jnp.arange(bcap, dtype=jnp.int32) < new_offsets[-1]
    data = jnp.where(in_range, col.data[src_pos], 0).astype(col.data.dtype)
    return DeviceColumn(col.dtype, data, col.validity, new_offsets)


def ensure_row_layout(batch: ColumnBatch) -> ColumnBatch:
    """Materialize any dictionary-encoded columns of ``batch`` to plain
    row layout.  Python-level no-op (returns the same object) when none
    are encoded, so it is free at every exec entry; the decode itself is
    traceable and safe inside ``jax.jit``."""
    if not any(c.codes is not None for c in batch.columns):
        return batch
    cols = [dict_decode_column(c) if c.codes is not None else c
            for c in batch.columns]
    return ColumnBatch(batch.schema, cols, batch.num_rows, batch.capacity)


def row_slices(batch: ColumnBatch, total_rows: int, rows_per: int):
    """Yield right-sized row-range slices of ``batch``, ``rows_per`` rows
    each.  ONE host round trip sizes every slice's varlen buffers from the
    offsets; slices past ``total_rows`` are not produced."""
    bounds = list(range(0, total_rows, max(rows_per, 1))) + [total_rows]
    varlen = [c for c in batch.columns if c.is_varlen]
    marks = device_read(
        "slice_marks",
        [c.offsets[jnp.asarray(bounds, jnp.int32)] for c in varlen]) \
        if varlen else []
    for i in range(len(bounds) - 1):
        start, cnt = bounds[i], bounds[i + 1] - bounds[i]
        pcap = round_up_capacity(cnt)
        idx = start + jnp.arange(pcap, dtype=jnp.int32)
        bcaps = [round_up_capacity(max(int(m[i + 1] - m[i]), 16),
                                   minimum=16) for m in marks]
        yield gather_rows(batch, idx, jnp.asarray(cnt, jnp.int32),
                          out_capacity=pcap, out_byte_caps=bcaps or None)


@kernel_scope
def compaction_indices(mask, num_rows):
    """(indices, count): stable order of rows where mask is True and live.

    ``indices`` is int32[cap] — positions of kept rows first (stable),
    then arbitrary padding.  Sort-free AND search-free: a cumsum ranks the
    kept rows and one scatter inverts the ranking.  A boolean stable-argsort
    is an O(n log^2 n) bitonic sort on TPU (~300 ms at 2M rows), and a
    searchsorted inversion is ~22 dependent gathers per row (~350 ms at
    4M); cumsum + scatter is two HBM passes.
    """
    cap = int(mask.shape[0])
    live = jnp.arange(cap, dtype=jnp.int32) < num_rows
    keep = mask & live
    csum = jnp.cumsum(keep.astype(jnp.int32))
    count = csum[cap - 1] if cap else jnp.int32(0)
    iota = jnp.arange(cap, dtype=jnp.int32)
    # kept row i lands at slot csum[i]-1; dropped row i scatters to the
    # GENUINELY unique out-of-bounds slot cap+i (mode="drop" discards it)
    # so the unique_indices promise holds and XLA emits a plain scatter
    # instead of a sort-based one.
    target = jnp.where(keep, csum - 1, cap + iota)
    idx = jnp.zeros(cap, dtype=jnp.int32).at[target].set(
        iota, mode="drop", unique_indices=True)
    return idx, count.astype(jnp.int32)


@kernel_scope
def compact(batch: ColumnBatch, mask,
            keep_encoded: bool = False) -> ColumnBatch:
    """Filter: keep rows where mask (bool[cap]) is True.  Single-phase —
    output capacity = input capacity (a filter can only shrink), which is
    also why ``keep_encoded`` is always valid here: a dictionary-encoded
    column leaves with its codes compacted and its dictionary and
    ``mat_byte_cap`` as they came (``gather_rows``)."""
    indices, count = compaction_indices(mask, batch.num_rows)
    return gather_rows(batch, indices, count, keep_encoded=keep_encoded)


def take_head(batch: ColumnBatch, limit) -> ColumnBatch:
    """LocalLimit: clamp the live-row count (no data movement)."""
    n = jnp.minimum(batch.num_rows, jnp.asarray(limit, jnp.int32))
    return ColumnBatch(batch.schema, batch.columns, n, batch.capacity)


def _pack_kway(vals_list, los, his, out_cap: int):
    """K-way segment pack: input j's window ``[los[j], his[j])`` lands at
    the running output offset ``sum(his[:j] - los[:j])``; zeros elsewhere.

    This is THE scatter shape shared by every k-way assembly loop below
    (concat rows/bytes, split segments rows/bytes, dict code/byte
    merges): each value scatters once, rows outside the window target
    genuinely unique out-of-bounds slots (``out_cap + i``) so
    ``mode="drop"`` discards them while the ``unique_indices`` promise
    stays true and XLA emits a plain scatter.  The kernel tier's
    ``gatherScatter`` Pallas pack replaces the whole chain with one pass
    per output block when engaged (bit-identical; unsupported dtypes and
    degenerate shapes always take the XLA chain)."""
    los = [jnp.asarray(lo, jnp.int32) for lo in los]
    his = [jnp.asarray(hi, jnp.int32) for hi in his]

    def xla():
        out = jnp.zeros(out_cap, dtype=vals_list[0].dtype)
        off = jnp.asarray(0, jnp.int32)
        for vals, lo, hi in zip(vals_list, los, his):
            iota = jnp.arange(int(vals.shape[0]), dtype=jnp.int32)
            rel = iota - lo
            in_seg = (rel >= 0) & (iota < hi)
            tgt = jnp.where(in_seg, off + rel, out_cap + iota)
            out = out.at[tgt].set(vals, mode="drop", unique_indices=True)
            off = off + (hi - lo)
        return out

    from spark_rapids_tpu.kernels import pallas_tier as PT
    if out_cap < 1 or not PT.pack_supported(vals_list) or \
            any(int(v.shape[0]) < 1 for v in vals_list):
        return xla()
    resident = sum(int(v.shape[0]) * v.dtype.itemsize for v in vals_list)
    return PT.run(
        "gatherScatter",
        lambda interpret: PT.pack_segments(vals_list, los, his, out_cap,
                                           interpret=interpret),
        xla, resident_bytes=resident)


@kernel_scope
def concat_kway(batches: Sequence[ColumnBatch], out_capacity: int,
                out_byte_caps: Optional[Sequence[int]] = None) -> ColumnBatch:
    """Concatenate k batches (same schema) into ONE output allocation.

    The pairwise chain materializes k-1 growing intermediates, each a full
    read+write of everything concatenated so far — O(k * out_capacity) HBM
    traffic.  Here every input is written exactly ONCE at its row (and, for
    varlen columns, byte) offset: per input j, a scatter places its live
    rows at ``sum(num_rows[:j]) + i``; dead rows target genuinely unique
    out-of-bounds slots (``out_capacity + i``) so ``mode="drop"`` discards
    them while the ``unique_indices`` promise stays true and XLA emits a
    plain scatter (see :func:`compaction_indices`).

    Bit-identical to the :func:`concat_pair` chain: rows packed in input
    order, zeros past the live rows, varlen offsets rebuilt from one cumsum
    of the scattered live lengths (constant past the live total).  Safe
    inside ``jax.jit``; ``out_byte_caps`` defaults to the summed input byte
    capacities, matching the chain's accumulated default.
    """
    assert batches
    batches = [ensure_row_layout(b) for b in batches]
    if len(batches) == 1:
        return batches[0]
    schema = batches[0].schema
    for b in batches[1:]:
        assert b.schema == schema, f"{b.schema} != {schema}"
    ns = [b.num_rows for b in batches]
    acc = jnp.asarray(0, jnp.int32)
    for n in ns:
        acc = acc + n
    total = acc.astype(jnp.int32)
    zeros_lo = [jnp.asarray(0, jnp.int32)] * len(batches)

    def pack_rows(values_per_batch):
        return _pack_kway(values_per_batch, zeros_lo, ns, out_capacity)

    cols = []
    str_i = 0
    for ci, f in enumerate(schema.fields):
        parts = [b.columns[ci] for b in batches]
        validity = pack_rows([c.validity for c in parts])
        if parts[0].is_varlen:
            bcap = (out_byte_caps[str_i] if out_byte_caps is not None
                    else sum(int(c.data.shape[0]) for c in parts))
            str_i += 1
            lens = pack_rows([_string_lengths(c) for c in parts])
            new_offsets = jnp.concatenate([
                jnp.zeros(1, dtype=jnp.int32),
                jnp.cumsum(lens).astype(jnp.int32),
            ])
            # LIVE bytes only (offsets[num_rows], not offsets[-1]):
            # take_head truncates num_rows without repacking, so dead
            # rows keep growing offsets — their bytes must neither
            # advance the cursor nor overwrite the next input's region
            data = _pack_kway([c.data for c in parts], zeros_lo,
                              [c.offsets[n] for c, n in zip(parts, ns)],
                              bcap)
            cols.append(DeviceColumn(f.dtype, data, validity, new_offsets))
        else:
            data = pack_rows([c.data for c in parts])
            cols.append(DeviceColumn(f.dtype, data, validity, None))
    return ColumnBatch(schema, cols, total, out_capacity)


def _concat_kway_tuple(batches, out_capacity, out_byte_caps):
    return concat_kway(list(batches), out_capacity,
                       list(out_byte_caps) if out_byte_caps else None)


def concat_kway_run(batches: Sequence[ColumnBatch], out_capacity: int,
                    out_byte_caps: Optional[Sequence[int]] = None
                    ) -> ColumnBatch:
    """Eager-path entry: ONE compiled dispatch for the whole k-way concat
    (the pairwise chain ran as an eager op storm).  Cached per
    (input shape-bucket tuple, output caps) like every instrumented jit."""
    from spark_rapids_tpu.utils.compile_registry import instrumented_jit
    global _CONCAT_KWAY_JIT
    if _CONCAT_KWAY_JIT is None:
        _CONCAT_KWAY_JIT = instrumented_jit(
            _concat_kway_tuple, label="kernels:concatKway",
            static_argnames=("out_capacity", "out_byte_caps"))
    return _CONCAT_KWAY_JIT(
        tuple(batches), out_capacity,
        tuple(out_byte_caps) if out_byte_caps else None)


_CONCAT_KWAY_JIT = None


@kernel_scope
def gather_segments_kway(batches: Sequence[ColumnBatch], starts, counts,
                         out_capacity: int,
                         out_byte_caps: Optional[Sequence[int]] = None,
                         keep_encoded: bool = False) -> ColumnBatch:
    """Gather one contiguous row segment per input batch into ONE packed
    output batch: input j contributes rows ``[starts[j], starts[j]+counts[j])``
    at output row offset ``sum(counts[:j])``.

    This is the shuffle split's coalescing primitive: each input is a
    pid-sorted batch whose target-partition rows are contiguous, so one
    call assembles a whole target partition from every input batch — the
    write-combining replacement for one :func:`gather_rows` per
    (batch, partition) pair.  Same scatter shape as :func:`concat_kway`:
    every input is written exactly once at its row/byte offset, and rows
    outside the segment target genuinely unique out-of-bounds slots
    (``out_capacity + i``) so ``mode="drop"`` discards them while the
    ``unique_indices`` promise stays true.

    ``starts``/``counts`` are traced int32 scalars — different segment
    positions ride the same compiled program (the cache keys only on input
    capacity buckets and the static output caps).  Segments must lie
    within each input's live rows, so the varlen byte window
    ``offsets[start] .. offsets[start+count]`` covers exactly the
    segment's live bytes (offsets are constant past ``num_rows`` by
    construction; see concat_kway's live-bytes note).

    ``keep_encoded`` (dict-aware shuffle, docs/shuffle.md): when every
    input part of a string column is dictionary-encoded, the output stays
    encoded — codes are scattered with a per-input entry-base shift and
    the input dictionaries are packed back-to-back into one merged
    dictionary (entry bases are static: the cumsum of input dictionary
    capacities; byte bases are traced: the cumsum of live dictionary
    bytes, matching one dynamic scatter cursor per input exactly like
    concat_kway's byte packing).  The column's ``out_byte_caps`` slot
    then carries the OUTPUT ``mat_byte_cap`` (the materialized bucket a
    later :func:`dict_decode_column` needs), not a data-buffer capacity —
    the merged dictionary's capacity is the static sum of the input
    dictionary capacities.  Columns with any plain part fall back to
    materializing the encoded parts first.
    """
    assert batches
    if not keep_encoded:
        batches = [ensure_row_layout(b) for b in batches]
    schema = batches[0].schema
    for b in batches[1:]:
        assert b.schema == schema, f"{b.schema} != {schema}"
    starts = [jnp.asarray(s, jnp.int32) for s in starts]
    counts = [jnp.asarray(c, jnp.int32) for c in counts]
    seg_his = [s + c for s, c in zip(starts, counts)]
    acc = jnp.asarray(0, jnp.int32)
    for c in counts:
        acc = acc + c
    total = acc.astype(jnp.int32)

    def pack_segments(values_per_batch):
        return _pack_kway(values_per_batch, starts, seg_his, out_capacity)

    cols = []
    str_i = 0
    for ci, f in enumerate(schema.fields):
        parts = [b.columns[ci] for b in batches]
        if keep_encoded and any(c.codes is not None for c in parts) \
                and not all(c.codes is not None for c in parts):
            # mixed encoded/plain parts: no shared dictionary space exists,
            # so materialize the encoded ones and take the plain path
            parts = [dict_decode_column(c) if c.codes is not None else c
                     for c in parts]
        validity = pack_segments([c.validity for c in parts])
        if keep_encoded and all(c.codes is not None for c in parts):
            mat_cap = (out_byte_caps[str_i] if out_byte_caps is not None
                       else sum((c.mat_byte_cap or int(c.data.shape[0]))
                                for c in parts))
            str_i += 1
            shifted_codes = []
            ent_lens_parts = []
            entry_base = 0  # static: dictionary capacities are shapes
            for c in parts:
                shifted_codes.append(c.codes + entry_base)
                ent_lens_parts.append(
                    (c.offsets[1:] - c.offsets[:-1]).astype(jnp.int32))
                entry_base += int(c.offsets.shape[0]) - 1
            codes = pack_segments(shifted_codes)
            # merged dictionary: entry lens concatenate at static bases, so
            # one cumsum yields offsets whose per-input byte base equals the
            # dynamic packing cursor below (padded entries have zero lens)
            merged_offsets = jnp.concatenate([
                jnp.zeros(1, dtype=jnp.int32),
                jnp.cumsum(jnp.concatenate(ent_lens_parts)).astype(jnp.int32),
            ])
            dcap = sum(int(c.data.shape[0]) for c in parts)
            data = _pack_kway(
                [c.data for c in parts],
                [jnp.asarray(0, jnp.int32)] * len(parts),
                [c.offsets[int(c.offsets.shape[0]) - 1] for c in parts],
                dcap)
            cols.append(DeviceColumn(f.dtype, data, validity, merged_offsets,
                                     codes, mat_cap))
        elif parts[0].is_varlen:
            bcap = (out_byte_caps[str_i] if out_byte_caps is not None
                    else sum(int(c.data.shape[0]) for c in parts))
            str_i += 1
            lens = pack_segments([_string_lengths(c) for c in parts])
            new_offsets = jnp.concatenate([
                jnp.zeros(1, dtype=jnp.int32),
                jnp.cumsum(lens).astype(jnp.int32),
            ])
            data = _pack_kway(
                [c.data for c in parts],
                [c.offsets[s] for c, s in zip(parts, starts)],
                [c.offsets[s + n] for c, s, n in zip(parts, starts, counts)],
                bcap)
            cols.append(DeviceColumn(f.dtype, data, validity, new_offsets))
        else:
            data = pack_segments([c.data for c in parts])
            cols.append(DeviceColumn(f.dtype, data, validity, None))
    return ColumnBatch(schema, cols, total, out_capacity)


def _gather_segments_kway_tuple(batches, starts, counts, out_capacity,
                                out_byte_caps, keep_encoded=False):
    return gather_segments_kway(
        list(batches), list(starts), list(counts), out_capacity,
        list(out_byte_caps) if out_byte_caps else None,
        keep_encoded=keep_encoded)


def gather_segments_kway_run(batches: Sequence[ColumnBatch], starts, counts,
                             out_capacity: int,
                             out_byte_caps: Optional[Sequence[int]] = None,
                             keep_encoded: bool = False) -> ColumnBatch:
    """Eager-path entry: ONE compiled dispatch assembles a whole target
    partition from k pid-sorted batches.  Segment positions are traced, so
    every partition of a shuffle (and every repeat query) reuses the same
    executable per (input bucket tuple, output caps)."""
    from spark_rapids_tpu.utils.compile_registry import instrumented_jit
    global _GATHER_SEGMENTS_KWAY_JIT
    if _GATHER_SEGMENTS_KWAY_JIT is None:
        _GATHER_SEGMENTS_KWAY_JIT = instrumented_jit(
            _gather_segments_kway_tuple, label="kernels:gatherSegmentsKway",
            static_argnames=("out_capacity", "out_byte_caps", "keep_encoded"))
    return _GATHER_SEGMENTS_KWAY_JIT(
        tuple(batches),
        tuple(jnp.asarray(s, jnp.int32) for s in starts),
        tuple(jnp.asarray(c, jnp.int32) for c in counts),
        out_capacity,
        tuple(out_byte_caps) if out_byte_caps else None,
        keep_encoded)


_GATHER_SEGMENTS_KWAY_JIT = None


def stacked_row_compaction_indices(counts, n: int, cap: int, out_cap: int):
    """Row map compacting n stacked segments into one flat batch.

    The mesh exchange's receive side (and any [n, cap]-stacked layout)
    holds one segment per source with ``counts[d]`` live rows; output row
    r is segment ``bkt[r]`` row ``within[r]`` when ``live[r]``.  Returns
    ``(bkt, within, live, total)``, all over the static ``out_cap`` —
    searchsorted over the count cumsum, the sharded k-way sibling of
    :func:`gather_segments_kway`'s scatter (there the inputs are separate
    arrays; here one stacked axis, so a gather formulation wins).  Safe
    inside ``jax.jit`` and inside ``shard_map``.
    """
    total = jnp.sum(counts).astype(jnp.int32)
    cum = jnp.cumsum(counts)
    starts = cum - counts
    flat = jnp.arange(out_cap, dtype=jnp.int32)
    bkt = jnp.clip(jnp.searchsorted(
        cum, flat, side="right").astype(jnp.int32), 0, n - 1)
    within = jnp.clip(flat - starts[bkt], 0, cap - 1)
    live = flat < total
    return bkt, within, live, total


def gather_stacked_rows(stacked, bkt, within, live):
    """Apply a :func:`stacked_row_compaction_indices` map to one
    ``[n, cap]`` per-row payload (data or validity); dead output slots
    zero-fill (False for bool)."""
    return jnp.where(live, stacked[bkt, within],
                     jnp.zeros((), stacked.dtype))


def gather_stacked_elements(elems, ecounts, n: int, ecap: int,
                            out_ecap: int):
    """Compact n stacked varlen element streams (``elems[n, ecap]``,
    ``ecounts[d]`` live elements each) into one flat ``[out_ecap]``
    buffer — the element-axis counterpart of
    :func:`stacked_row_compaction_indices`, so a received varlen column's
    bytes land contiguous in segment order with zeros past the live
    total."""
    ecum = jnp.cumsum(ecounts)
    eexcl = ecum - ecounts
    p = jnp.arange(out_ecap, dtype=jnp.int32)
    eb = jnp.clip(jnp.searchsorted(
        ecum, p, side="right").astype(jnp.int32), 0, n - 1)
    ew = jnp.clip(p - eexcl[eb], 0, ecap - 1)
    return jnp.where(p < ecum[n - 1], elems[eb, ew],
                     jnp.zeros((), elems.dtype))


def concat_pair(a: ColumnBatch, b: ColumnBatch, out_capacity: int,
                out_byte_caps: Optional[Sequence[int]] = None) -> ColumnBatch:
    """Concatenate two batches (same schema) into one of static capacity.

    Gather-formulated: output row i reads a[i] when i < a.num_rows else
    b[i - a.num_rows].  ``out_capacity`` must be >= a.capacity + b.capacity
    is NOT required — only >= total live rows (host guarantees via sizing).
    """
    assert a.schema == b.schema, f"{a.schema} != {b.schema}"
    a, b = ensure_row_layout(a), ensure_row_layout(b)
    n_a = a.num_rows
    total = a.num_rows + b.num_rows
    live = jnp.arange(out_capacity, dtype=jnp.int32) < total
    i = jnp.arange(out_capacity, dtype=jnp.int32)
    from_a = i < n_a
    ia = jnp.clip(i, 0, a.capacity - 1)
    ib = jnp.clip(i - n_a, 0, b.capacity - 1)
    cols = []
    str_i = 0
    for f, ca, cb in zip(a.schema.fields, a.columns, b.columns):
        if ca.is_varlen:
            len_a = _string_lengths(ca)
            len_b = _string_lengths(cb)
            new_lens = jnp.where(
                live, jnp.where(from_a, len_a[ia], len_b[ib]), 0)
            new_offsets = jnp.concatenate([
                jnp.zeros(1, dtype=jnp.int32),
                jnp.cumsum(new_lens).astype(jnp.int32),
            ])
            bcap_a = int(ca.data.shape[0])
            bcap_b = int(cb.data.shape[0])
            bcap = (out_byte_caps[str_i] if out_byte_caps is not None
                    else bcap_a + bcap_b)
            str_i += 1
            rows = _rows_of_positions(new_offsets, bcap)
            rows_c = jnp.clip(rows, 0, out_capacity - 1)
            pos_in_row = jnp.arange(bcap, dtype=jnp.int32) - new_offsets[rows_c]
            row_from_a = from_a[rows_c]
            src_a = jnp.clip(ca.offsets[ia[rows_c]] + pos_in_row, 0, bcap_a - 1)
            src_b = jnp.clip(cb.offsets[ib[rows_c]] + pos_in_row, 0, bcap_b - 1)
            byte = jnp.where(row_from_a, ca.data[src_a], cb.data[src_b])
            in_range = jnp.arange(bcap, dtype=jnp.int32) < new_offsets[-1]
            data = jnp.where(in_range, byte, 0).astype(ca.data.dtype)
            validity = jnp.where(
                live, jnp.where(from_a, ca.validity[ia], cb.validity[ib]),
                False)
            cols.append(DeviceColumn(f.dtype, data, validity, new_offsets))
        else:
            data = jnp.where(from_a, ca.data[ia], cb.data[ib])
            data = jnp.where(live, data, 0).astype(ca.data.dtype)
            validity = jnp.where(
                live, jnp.where(from_a, ca.validity[ia], cb.validity[ib]),
                False)
            cols.append(DeviceColumn(f.dtype, data, validity, None))
    return ColumnBatch(a.schema, cols, total.astype(jnp.int32), out_capacity)
