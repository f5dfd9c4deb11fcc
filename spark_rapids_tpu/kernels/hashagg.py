"""MXU slot aggregation: groupby as a one-hot matmul contraction.

The sort-based groupby (kernels/groupby.py — cudf's sort-groupby analogue)
pays an argsort plus several full-size gathers and scatter reductions per
batch; on TPU every one of those is an HBM-bound pass (~100-300 ms at 4M
rows).  This path instead aggregates straight into a fixed table of slots
with ONE fused one-hot contraction — the systolic array does the
segmented reduction:

  slot = key - min(key)                       # elementwise, EXACT
  sums = stacked_value_rows @ one_hot(slot)   # ONE einsum on the MXU

Slotting by the key's own value range makes slot <-> key a bijection —
no hash, no collisions, no purity machinery, and the output key columns
are reconstructed from slot indices without touching the input again.
Multi-column keys pack into ONE slot index by mixed radix: each key
contributes a digit (its offset from the batch minimum, plus a NULL digit
when the column has NULLs) and the product of radices must fit the table.
An integral/date/bool key's digit comes from its value; a string key's
from its dictionary ``codes``, when the column arrives dictionary-encoded
(scan v2, docs/io.md) — a code is an integer that names the key within
ITS batch, which is all a per-batch slot needs.  The output key column is
then the slot's code looked up in the batch's own dictionary and
materialized, so partials leave in row layout as the sort form's do and
codes of two batches are never compared (a dictionary with duplicate
entries merely leaves two partial groups for the merge to combine).  With
every key encoded the dictionaries bound the packed key space statically
and the table is no wider than that bound.  A string key that arrives
plain has no digit: the caller takes the sort form for that batch
(``keys_are_digits``).  A batch whose packed key space exceeds the
table (or holds non-finite floats for a float sum) raises a
device-visible flag and the caller re-runs the exact sort path —
correctness never depends on data shape.

min/max/first/last ride the SAME slot index through the aggregate
classes' own segment kernels (one scatter-reduce pass, unsorted ids) —
bit-identical buffers and semantics to the sort path, minus the argsort.

Exactness of the reductions:
* Integer sums/counts ride 8-bit limb rows accumulated in f32 over
  bounded chunks (chunk sums stay < 2^24, exact in f32), recombined in
  int64 — bit-exact, including wrap-around.
* Float sums are 53-bit fixed-point limb rows against a per-chunk scale —
  error is at the final f64-rounding level (~1 ulp per chunk), tighter
  than a variable-order device reduction.

No grouping key (``keyless_aggregate``): one group, so there is no slot
and no table.  The same value rows — live count, per-aggregate counts,
8-bit limbs, fixed-point limbs, the NaN/Inf flag — are SUMMED along their
chunk axis instead of contracted against a one-hot; the chunk totals are
the same exact integers, so the buffers are bit-identical to slot 0 of the
contraction under a constant key, and the partial leaves as ONE row at
``MIN_CAPACITY``.  ``TpuHashAggregateExec`` takes it whenever its plan has
no key expression (same gate, same flag contract as the slot path); the
merge of such partials is kernels/groupby.py's keyless branch.

Reference role: the cudf hash aggregate (aggregate.scala:456) — re-imagined
for the MXU instead of a GPU hash table.
"""

from __future__ import annotations

import math
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import (
    BUCKETS, ColumnBatch, DeviceColumn, round_up_capacity,
)
from spark_rapids_tpu.exprs.base import DevVal
from spark_rapids_tpu.kernels.groupby import one_group_output
from spark_rapids_tpu.kernels.layout import (
    compaction_indices, dict_decode_column,
)
from spark_rapids_tpu.utils.tracing import kernel_scope

TABLE_SLOTS = 8192          # key-range capacity of the slot table
_CHUNK = 16384              # rows per exact-f32 accumulation chunk
_LANES = 128                # the one-hot's minor dimension tiles by this
_SIGN32 = np.uint32(0x80000000)


def _limb_rows_u32(w, use, bits: int) -> List[jnp.ndarray]:
    """f32 rows of ``bits``-wide limbs of a u32 word, zeroed where !use."""
    mask = jnp.uint32((1 << bits) - 1)
    rows = []
    for j in range(32 // bits):
        limb = ((w >> jnp.uint32(bits * j)) & mask).astype(jnp.float32)
        rows.append(jnp.where(use, limb, 0.0))
    return rows


def _int_value_words(x, use) -> List[Tuple[jnp.ndarray, bool]]:
    """(u32 word, biased) pairs whose limb sums recombine to sum(x) in
    int64.  The hi word is sign-biased by 2^31 so limbs stay unsigned."""
    x = x.astype(jnp.int64)
    lo = jax.lax.convert_element_type(x & jnp.int64(0xFFFFFFFF),
                                      jnp.uint32)
    hi = jax.lax.convert_element_type(
        (x >> jnp.int64(32)) & jnp.int64(0xFFFFFFFF), jnp.uint32)
    return [(jnp.where(use, lo, jnp.uint32(0)), False),
            (jnp.where(use, hi ^ _SIGN32, jnp.uint32(0)), True)]


_FIX_BITS = 53  # fixed-point precision of the float limb rows


def _float_limb_rows(x, use, nc: int, c: int):
    """(7 f32 limb rows, per-chunk f64 scales) for exact-ish float sums.

    Per chunk: scale = max|x| over the chunk; q = (x/scale + 1) * 2^53
    as int64; 8-bit limbs of q.  Rows accumulate exactly in f32 (ints
    < 2^24 per chunk); recombination is exact integer math until one
    final f64 rounding — per-row truncation error <= scale * 2^-53."""
    x = x.astype(jnp.float64)
    ax = jnp.abs(jnp.where(use, x, 0.0)).reshape(nc, c)
    cmax = jnp.max(ax, axis=1)
    scale = jnp.where(cmax > 0, cmax, 1.0)               # >= max|x|
    y = x.reshape(nc, c) / scale[:, None]                # in [-1, 1]
    z = jnp.where(use.reshape(nc, c), y + 1.0, 0.0)      # in [0, 2]
    qi = (z * float(2 ** _FIX_BITS)).astype(jnp.int64)   # <= 2^54
    rows = []
    for j in range(7):
        sh = jnp.int64(8 * (6 - j))
        limb = ((qi >> sh) & jnp.int64(0xFF)).astype(jnp.float32)
        rows.append(limb.reshape(nc * c))
    return rows, scale


def _value_rows(live, agg_inputs: List[DevVal], agg_fns: Sequence,
                nc: int, c: int, fallback):
    """(f32 rows [cap] each, recombination plan, fallback flag): what both
    forms of the contraction sum.  Row 0 is the live count; every sum,
    count and average adds its own count row and its limb rows, all zero
    on dead and NULL rows; min/max/first/last are only planned here
    (``_buffers`` runs their own segment kernels).  ``fallback`` is ORed
    with "a float sum saw NaN, Inf or a magnitude past 2^1000"."""
    from spark_rapids_tpu.exprs.aggregates import (
        Count, First, Last, Max, Min,
    )
    rows: List[jnp.ndarray] = [live.astype(jnp.float32)]  # per-slot count
    agg_plan = []                                         # recombination
    for fn, v in zip(agg_fns, agg_inputs):
        if type(fn) in (Min, Max, First, Last):
            # one scatter-reduce pass over the same slot ids, via the
            # aggregate's own segment kernel (sort-path parity)
            agg_plan.append(("segment", fn, v))
            continue
        use = v.validity & live
        use_at = len(rows)
        rows.append(use.astype(jnp.float32))              # per-agg count
        if type(fn) is Count:
            agg_plan.append(("count", use_at))
            continue
        if v.dtype.is_integral or v.dtype == T.BOOLEAN:
            spec = []
            for w, biased in _int_value_words(v.data, use):
                at = len(rows)
                rows.extend(_limb_rows_u32(w, use, 8))
                spec.append((at, biased))
            agg_plan.append(("int_sum", use_at, spec, type(fn)))
        else:
            # fixed-point rows require finite, sanely-scaled values —
            # NaN/Inf (or near-overflow) batches take the sort path,
            # which propagates them with float semantics
            x64 = v.data.astype(jnp.float64)
            fallback = fallback | jnp.any(
                use & (~jnp.isfinite(x64) |
                       (jnp.abs(x64) > float(2.0 ** 1000))))
            at = len(rows)
            fr, scale = _float_limb_rows(v.data, use, nc, c)
            rows.extend(fr)
            agg_plan.append(("float_sum", use_at, at, scale, type(fn)))
    return rows, agg_plan, fallback


def _buffers(per_chunk, agg_plan, agg_fns: Sequence, slot, live, ng: int):
    """(live count per slot, per-agg buffer lists over the first ``ng``
    slots) from the chunk totals ``per_chunk`` f32[nc, R, tt] — the same
    recombination whichever contraction produced them."""
    from spark_rapids_tpu.exprs.aggregates import Sum, unsorted_segment_ids
    nc, r_n, tt = per_chunk.shape
    # chunk partials are exact integers < 2^23: accumulate across chunks
    # in native i32 lanes up to 256 chunks (256 * 2^23 < 2^31), then in
    # i64 — a flat i32 sum would overflow past ~4M rows per batch
    pc_i = per_chunk.astype(jnp.int32)
    if nc > 256:
        pc_i = pc_i.reshape(nc // 256, 256, r_n, tt).sum(axis=1)
    totals_i = jnp.sum(pc_i.astype(jnp.int64), axis=0)    # [R, tt]

    def _int_total(spec, use_at):
        total = jnp.zeros(tt, jnp.int64)
        for base_at, biased in spec:
            word_sum = jnp.zeros(tt, jnp.int64)
            for k in range(4):
                word_sum = word_sum + (totals_i[base_at + k]
                                       << jnp.int64(8 * k))
            if biased:
                cnt = totals_i[use_at]
                word_sum = (word_sum - (cnt << jnp.int64(31))) \
                    << jnp.int64(32)
            total = total + word_sum
        return total

    ones_t = jnp.ones(ng, jnp.bool_)
    buffer_cols: List[List[DevVal]] = []
    for plan, fn in zip(agg_plan, agg_fns):
        kind = plan[0]
        if kind == "segment":
            _, sfn, sv = plan
            with unsorted_segment_ids():
                sb = sfn.segment_update(sv, slot, tt, live)
            bufs = [DevVal(b.dtype, b.data[:ng], b.validity[:ng])
                    for b in sb]
        elif kind == "count":
            cnt = totals_i[plan[1]][:ng]
            bufs = [DevVal(T.LONG, cnt, ones_t)]
        elif kind == "int_sum":
            _, use_at, spec, fcls = plan
            total = _int_total(spec, use_at)[:ng]
            cnt = totals_i[use_at][:ng]
            if fcls is Sum:
                bufs = [DevVal(fn.dtype, total.astype(fn.dtype.jnp_dtype),
                               ones_t),
                        DevVal(T.BOOLEAN, cnt > 0, ones_t)]
            else:  # Average over ints: exact f64 sum from the i64 total
                bufs = [DevVal(T.DOUBLE, total.astype(jnp.float64),
                               ones_t),
                        DevVal(T.LONG, cnt, ones_t)]
        else:  # float_sum
            _, use_at, base_at, scale, fcls = plan
            z = jnp.zeros((nc, tt), jnp.float64)
            for j in range(7):
                z = z + per_chunk[:, base_at + j, :].astype(jnp.float64) \
                    * float(2 ** (8 * (6 - j)))
            cnt_pc = per_chunk[:, use_at, :].astype(jnp.float64)
            y = z / float(2 ** _FIX_BITS) - cnt_pc
            total = jnp.sum(y * scale[:, None], axis=0)[:ng]
            cnt = totals_i[use_at][:ng]
            if fcls is Sum:
                bufs = [DevVal(T.DOUBLE, total, ones_t),
                        DevVal(T.BOOLEAN, cnt > 0, ones_t)]
            else:
                bufs = [DevVal(T.DOUBLE, total, ones_t),
                        DevVal(T.LONG, cnt, ones_t)]
        buffer_cols.append(bufs)
    return totals_i[0], buffer_cols


def keys_are_digits(key_vals: Sequence[DevVal]) -> bool:
    """Trace-time half of the capability check: every key the batch
    brought can be a digit of the slot index — a fixed-width value, or a
    string that still carries its dictionary ``codes``.  A plain string
    key (a computed key, a format or a join that delivered row layout)
    cannot, and its batch takes the sort form."""
    return all(kv.codes is not None or not kv.dtype.is_string
               for kv in key_vals)


def _key_entries(kv: DevVal) -> int:
    """Static count of an encoded key's dictionary entries (padding too)."""
    return int(kv.offsets.shape[0]) - 1


def _dictionary_table(key_vals: Sequence[DevVal], table: int) -> int:
    """``table``, or less where dictionaries bound the packed key space:
    with every key encoded no batch can pack past prod(entries + 1)
    (+1: the NULL digit), a static number, so slots beyond it would only
    widen the one-hot.  The narrowed table keeps ``table + 2`` a multiple
    of the lane width."""
    if not key_vals or any(kv.codes is None for kv in key_vals):
        return table
    space = math.prod(_key_entries(kv) + 1 for kv in key_vals)
    return min(table, -(-(space + 1) // _LANES) * _LANES - 2)


@kernel_scope
def hash_group_aggregate(batch: ColumnBatch, key_vals: List[DevVal],
                         agg_inputs: List[DevVal], agg_fns: Sequence,
                         key_schema: T.Schema,
                         out_schema: T.Schema,
                         table: int = TABLE_SLOTS):
    """(group-key batch, per-agg buffer lists, n_groups, fallback flag).

    Buffer layout matches the sort-based update path (consumed unchanged
    by the merge stage).  ``fallback`` True means the key range did not
    fit the slot table (or a float sum saw non-finite values) — the
    caller MUST discard the result and use the sort path.  Every key
    must be a digit (``keys_are_digits``): an encoded string key groups
    by its codes and leaves as a row-layout string column."""
    assert keys_are_digits(key_vals)
    table = _dictionary_table(key_vals, table)
    cap = batch.capacity
    c = min(_CHUNK, cap)
    nc = cap // c
    live = jnp.arange(cap, dtype=jnp.int32) < batch.num_rows

    # ---- mixed-radix slot packing over all key columns -------------------
    # digit_i = k_i - min_i (or range_i for NULL); radix_i = range_i +
    # has_null_i; slot = sum(digit_i * stride_i).  Bijective onto
    # [0, prod(radix)); fallback when the packed space exceeds table+1.
    i64max = jnp.int64(jnp.iinfo(jnp.int64).max)
    i64min = jnp.int64(jnp.iinfo(jnp.int64).min)
    fallback = jnp.asarray(False)
    slot64 = jnp.zeros(cap, jnp.int64)
    stride = jnp.int64(1)
    prod_f = jnp.float64(1.0)
    key_decode = []  # (kmin, rng, radix, stride) per key, for output
    for kv in key_vals:
        kx = (kv.data if kv.codes is None else kv.codes).astype(jnp.int64)
        usek = live & kv.validity
        any_key = jnp.any(usek)
        has_null = jnp.any(live & ~kv.validity)
        kmin = jnp.min(jnp.where(usek, kx, i64max))
        kmax = jnp.max(jnp.where(usek, kx, i64min))
        # wrap-around of (kmax - kmin) goes negative -> correctly rejected
        key_fits = (kmax - kmin >= 0) & (kmax - kmin < table + 1)
        fallback = fallback | (any_key & ~key_fits)
        kmin = jnp.where(any_key & key_fits, kmin, jnp.int64(0))
        rng = jnp.where(any_key & key_fits, kmax - kmin + 1, jnp.int64(0))
        radix = jnp.maximum(rng + has_null.astype(jnp.int64), jnp.int64(1))
        digit = jnp.where(usek, jnp.clip(kx - kmin, 0, table), rng)
        slot64 = slot64 + digit * stride
        key_decode.append((kmin, rng, radix, stride))
        stride = stride * radix
        prod_f = prod_f * radix.astype(jnp.float64)
    # capacity check in f64: an int64 stride product can wrap silently
    fallback = fallback | (prod_f > jnp.float64(table + 1))

    # slots: 0..table = packed key tuples, table+1 = dead rows
    tt = table + 2
    slot = jnp.where(live, jnp.clip(slot64, 0, table).astype(jnp.int32),
                     jnp.int32(table + 1))

    rows, agg_plan, fallback = _value_rows(live, agg_inputs, agg_fns, nc,
                                           c, fallback)
    r_n = len(rows)
    stacked = jnp.stack(rows, axis=0)                     # [R, cap] f32
    stacked = stacked.reshape(r_n, nc, c).transpose(1, 0, 2)
    oh = jax.nn.one_hot(slot.reshape(nc, c), tt, dtype=jnp.float32)
    per_chunk = jnp.einsum("crn,cnt->crt", stacked, oh,
                           preferred_element_type=jnp.float32)
    ng = table + 1
    live_cnt, buffer_cols = _buffers(per_chunk, agg_plan, agg_fns, slot,
                                     live, ng)
    used = live_cnt[:ng] > 0                              # incl NULL group

    # ---- compact used slots; keys reconstructed from slot indices -------
    # (mixed-radix decode: digit_i = (slot // stride_i) % radix_i; the
    # NULL digit rng_i decodes to validity False)
    idx, n_groups = compaction_indices(used, jnp.asarray(ng, jnp.int32))
    out_cap = round_up_capacity(ng)
    idx_p = jnp.pad(idx, (0, out_cap - idx.shape[0]))
    live_out = jnp.arange(out_cap, dtype=jnp.int32) < n_groups
    key_cols = []
    spaces = [ng if kv.codes is None else _key_entries(kv) + 1
              for kv in key_vals]           # static bound on each radix
    for i, (kf, kv, (kmin, rng, radix, stride)) in enumerate(
            zip(key_schema.fields, key_vals, key_decode)):
        d = (idx_p.astype(jnp.int64) // stride) % radix
        key_valid = (d < rng) & live_out
        if kv.codes is None:
            key_data = (kmin + d).astype(kf.dtype.jnp_dtype)
            key_cols.append(DeviceColumn(kf.dtype, key_data, key_valid,
                                         None))
            continue
        # the slot's code, looked up in THIS batch's dictionary.  Bytes:
        # an entry recurs once a combination of the other keys' digits
        # and in no more rows than there are slots; and a group's key is
        # some row's key, so the rows' own total bounds them too
        others = min(ng, math.prod(spaces[:i] + spaces[i + 1:]))
        nbytes = others * int(kv.data.shape[0])
        if kv.mat_byte_cap > 0:
            nbytes = min(nbytes, kv.mat_byte_cap)
        codes = jnp.where(key_valid, kmin + d, 0).astype(jnp.int32)
        key_cols.append(dict_decode_column(DeviceColumn(
            kf.dtype, kv.data, key_valid, kv.offsets, codes,
            BUCKETS.elems(nbytes))))
    group_keys = ColumnBatch(key_schema, key_cols, n_groups, out_cap)

    def _pad(a):
        return jnp.pad(a, [(0, out_cap - a.shape[0])] +
                       [(0, 0)] * (a.ndim - 1))

    compact_bufs = [[DevVal(b.dtype, _pad(b.data[idx]),
                            _pad(b.validity[idx])) for b in bufs]
                    for bufs in buffer_cols]
    return group_keys, compact_bufs, n_groups, fallback


@kernel_scope
def keyless_aggregate(batch: ColumnBatch, agg_inputs: List[DevVal],
                      agg_fns: Sequence, key_schema: T.Schema):
    """(column-less key batch, per-agg buffer lists, fallback flag) for an
    update batch of an aggregate with no grouping key: ONE row at
    ``MIN_CAPACITY``.

    One group, so nothing is slotted: the rows ``hash_group_aggregate``
    would contract against ``one_hot(slot)`` are summed along their chunk
    axis instead.  Chunk totals are exact integers below 2^24 in both
    forms, so the buffers equal, bit for bit, slot 0 of that kernel under
    a constant key; min/max/first/last run the aggregate's own segment
    kernel over one segment.  An empty batch yields the identity buffers.
    ``fallback`` True (a float sum saw NaN/Inf/over 2^1000): the caller
    MUST discard the result and use the sort path, as for the keyed
    kernel."""
    cap = batch.capacity
    c = min(_CHUNK, cap)
    nc = cap // c
    live = jnp.arange(cap, dtype=jnp.int32) < batch.num_rows
    rows, agg_plan, fallback = _value_rows(live, agg_inputs, agg_fns, nc,
                                           c, jnp.asarray(False))
    per_chunk = jnp.stack([r.reshape(nc, c).sum(axis=1) for r in rows],
                          axis=1)[:, :, None]             # [nc, R, 1] f32
    _, buffer_cols = _buffers(per_chunk, agg_plan, agg_fns,
                              jnp.zeros(cap, jnp.int32), live, 1)
    return one_group_output(key_schema, buffer_cols) + (fallback,)


def hash_agg_capable(mode: str, key_types: List[T.DataType],
                     agg_fns: Sequence) -> bool:
    """Static capability check, on types alone: the MXU path covers
    sum/count/avg (einsum limb rows) plus min/max/first/last (slot
    scatter-reduce) over fixed-width inputs, grouped by any number of
    integral/date/bool/string keys (mixed-radix slot packing) or no key
    (global reduction).  A string key is a digit only while its column
    carries dictionary codes, which no type says: that half is
    ``keys_are_digits``, asked of each batch when its program is
    traced."""
    from spark_rapids_tpu.exprs.aggregates import (
        Average, Count, First, Last, Max, Min, Sum,
    )
    if mode != "update":
        return False
    for kt in key_types:
        if not (kt.is_integral or kt.is_string or
                kt in (T.DATE, T.BOOLEAN)):
            return False
    for fn in agg_fns:
        if type(fn) in (Sum, Average, Min, Max, First, Last):
            if fn.child.dtype.is_string or fn.child.dtype.is_array:
                return False
        elif type(fn) is not Count:
            return False
    return True
