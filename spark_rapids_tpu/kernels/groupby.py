"""Sort-based groupby aggregation (cudf groupby analogue, aggregate.scala:456).

TPU-first: instead of a hash table (scatter-heavy, poor MXU/VPU fit), group
rows by *sorting* on the exact key columns, derive segment ids from adjacent
key equality, and run ``jax.ops.segment_*`` reductions with
``num_segments = capacity`` so shapes stay static.  The same machinery serves
partial (update) and final (merge) aggregation modes — mirroring the
reference's update/merge projections (aggregate.scala:420-431).

With no grouping key there is one group and nothing to sort: the same
segment kernels run over ONE segment in input order and the result is one
row at ``MIN_CAPACITY`` (``_keyless_aggregate``).
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import MIN_CAPACITY, ColumnBatch, DeviceColumn
from spark_rapids_tpu.exprs.base import DevVal
from spark_rapids_tpu.kernels.layout import (
    compaction_indices, ensure_row_layout, gather_rows,
)
from spark_rapids_tpu.kernels.sort import argsort_batch
from spark_rapids_tpu.kernels.sortkeys import keys_equal_prev
from spark_rapids_tpu.utils.tracing import kernel_scope


@dataclasses.dataclass
class GroupSegments:
    """Result of grouping: row order and segment structure."""

    perm: jnp.ndarray        # int32[cap] sort permutation
    seg_ids: jnp.ndarray     # int32[cap] group id per *sorted* row
    seg_start: jnp.ndarray   # bool[cap] first sorted row of each group
    num_groups: jnp.ndarray  # int32 scalar
    live: jnp.ndarray        # bool[cap] sorted-row liveness


@kernel_scope
def group_segments(key_vals: List[DevVal], num_rows) -> GroupSegments:
    """Sort rows by key and mark exact group boundaries."""
    cap = int(key_vals[0].validity.shape[0])
    perm = argsort_batch(key_vals, [True] * len(key_vals),
                         [True] * len(key_vals), num_rows,
                         groupings=[True] * len(key_vals))
    live = jnp.arange(cap, dtype=jnp.int32) < num_rows
    # Reorder key columns by the permutation; strings need real byte gathers
    # for the adjacent-equality check (cheap relative to the sort itself).
    # Dictionary-encoded strings just permute their codes — the entry
    # buffer is row-order independent, so no byte gather is needed.
    sorted_keys = []
    for v in key_vals:
        if v.codes is not None:
            sorted_keys.append(DevVal(v.dtype, v.data, v.validity[perm],
                                      v.offsets, v.codes[perm],
                                      v.mat_byte_cap))
        elif v.dtype.is_string:
            sorted_keys.append(_gather_str_val(v, perm, cap))
        else:
            sorted_keys.append(DevVal(v.dtype, v.data[perm],
                                      v.validity[perm]))
    eq_prev = keys_equal_prev(sorted_keys)
    seg_start = live & ~eq_prev
    seg_ids = jnp.clip(jnp.cumsum(seg_start.astype(jnp.int32)) - 1, 0, cap - 1)
    num_groups = jnp.sum(seg_start).astype(jnp.int32)
    return GroupSegments(perm, seg_ids, seg_start, num_groups, live)


@kernel_scope
def groupby_aggregate(batch: ColumnBatch, key_vals: List[DevVal],
                      agg_inputs: List[DevVal], agg_fns: Sequence,
                      merge: bool,
                      key_schema: T.Schema,
                      buffer_schemas: List[List[T.DataType]],
                      out_schema: T.Schema) -> Tuple[ColumnBatch, List[List[DevVal]]]:
    """One-batch groupby.

    Returns (group-key batch of num_groups rows, per-agg buffer lists aligned
    with group order).  In ``merge`` mode ``agg_inputs`` holds lists of
    partial buffers per aggregate (flattened by caller) and ``segment_merge``
    is used; otherwise raw inputs + ``segment_update``.
    """
    if not key_vals:
        return _keyless_aggregate(batch, agg_inputs, agg_fns, merge,
                                  key_schema, buffer_schemas)
    cap = batch.capacity
    segs = group_segments(key_vals, batch.num_rows)

    # Representative key rows: compact sorted rows where seg_start.
    # Encoded key columns materialize here — downstream (merge rounds,
    # concat, output) only ever sees the row layout.
    key_cols = [DeviceColumn(v.dtype, v.data, v.validity, v.offsets,
                             v.codes, v.mat_byte_cap)
                for v in key_vals]
    key_batch = ensure_row_layout(
        ColumnBatch(key_schema, key_cols, batch.num_rows, cap))
    sorted_keys = gather_rows(key_batch, segs.perm, batch.num_rows)
    idx, count = compaction_indices(segs.seg_start, jnp.asarray(cap, jnp.int32))
    group_keys = gather_rows(sorted_keys, idx, segs.num_groups)

    out_buffers: List[List[DevVal]] = []
    if merge:
        for fn, partials in zip(agg_fns,
                                _partials_of(agg_inputs, buffer_schemas)):
            partials = [DevVal(v.dtype, v.data[segs.perm],
                               v.validity[segs.perm]) for v in partials]
            out_buffers.append(fn.segment_merge(partials, segs.seg_ids, cap,
                                                segs.live))
    else:
        for fn, v in zip(agg_fns, agg_inputs):
            if v.codes is not None:
                # encoded input (Count over a dict string): permute codes,
                # entries are row-order independent
                sv = DevVal(v.dtype, v.data, v.validity[segs.perm],
                            v.offsets, v.codes[segs.perm], v.mat_byte_cap)
            elif v.dtype.is_string:
                sv = _gather_str_val(v, segs.perm, cap)
            else:
                sv = DevVal(v.dtype, v.data[segs.perm],
                            v.validity[segs.perm])
            out_buffers.append(fn.segment_update(sv, segs.seg_ids, cap,
                                                 segs.live))
    return group_keys, out_buffers


def _partials_of(agg_inputs: List[DevVal],
                 buffer_schemas: List[List[T.DataType]]):
    """The flat merge-mode inputs, regrouped one list an aggregate."""
    it = iter(agg_inputs)
    return [[next(it) for _ in bufs] for bufs in buffer_schemas]


def _keyless_aggregate(batch: ColumnBatch, agg_inputs: List[DevVal],
                       agg_fns: Sequence, merge: bool, key_schema: T.Schema,
                       buffer_schemas: List[List[T.DataType]]
                       ) -> Tuple[ColumnBatch, List[List[DevVal]]]:
    """No grouping key: one group, so nothing is sorted.  The rows stay in
    input order (the identity permutation: no gather, first/last keep
    their order), every row is segment 0 of ONE segment, and there is no
    key column to gather or compact.  Each aggregate's own
    ``segment_update`` / ``segment_merge`` runs unchanged over that one
    segment; its one-row buffers leave padded to ``MIN_CAPACITY``, which
    is the capacity of the (column-less) key batch returned.  An empty
    input yields the identity buffers (the SQL default row)."""
    cap = batch.capacity
    live = jnp.arange(cap, dtype=jnp.int32) < batch.num_rows
    seg_ids = jnp.zeros(cap, jnp.int32)
    if merge:
        bufs = [fn.segment_merge(partials, seg_ids, 1, live)
                for fn, partials in zip(
                    agg_fns, _partials_of(agg_inputs, buffer_schemas))]
    else:
        bufs = [fn.segment_update(v, seg_ids, 1, live)
                for fn, v in zip(agg_fns, agg_inputs)]
    return one_group_output(key_schema, bufs)


def one_group_output(key_schema: T.Schema, buffers: List[List[DevVal]]
                     ) -> Tuple[ColumnBatch, List[List[DevVal]]]:
    """(column-less key batch of ONE row at ``MIN_CAPACITY``, the one-row
    ``buffers`` padded to it): what a keyless aggregate hands on."""
    def _pad(a):
        return jnp.pad(a, (0, MIN_CAPACITY - 1))

    group_keys = ColumnBatch(key_schema, [], jnp.asarray(1, jnp.int32),
                             MIN_CAPACITY)
    return group_keys, [[DevVal(b.dtype, _pad(b.data), _pad(b.validity))
                         for b in bufs] for bufs in buffers]


def _gather_str_val(v: DevVal, perm, cap: int) -> DevVal:
    col = DeviceColumn(v.dtype, v.data, v.validity, v.offsets)
    b = ColumnBatch(T.Schema([("s", v.dtype)]), [col],
                    jnp.asarray(cap, jnp.int32), cap)
    g = gather_rows(b, perm, jnp.asarray(cap, jnp.int32)).columns[0]
    return DevVal(v.dtype, g.data, g.validity, g.offsets)
