"""Sort-based groupby aggregation (cudf groupby analogue, aggregate.scala:456).

TPU-first: instead of a hash table (scatter-heavy, poor MXU/VPU fit), group
rows by *sorting* on the exact key columns, derive segment ids from adjacent
key equality, and run ``jax.ops.segment_*`` reductions with
``num_segments = capacity`` so shapes stay static.  The same machinery serves
partial (update) and final (merge) aggregation modes — mirroring the
reference's update/merge projections (aggregate.scala:420-431).
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import ColumnBatch, DeviceColumn
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
        flat_i = 0
        for fn, bufs in zip(agg_fns, buffer_schemas):
            n = len(bufs)
            partials = []
            for k in range(n):
                v = agg_inputs[flat_i]
                flat_i += 1
                partials.append(DevVal(v.dtype, v.data[segs.perm],
                                       v.validity[segs.perm]))
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


def _gather_str_val(v: DevVal, perm, cap: int) -> DevVal:
    col = DeviceColumn(v.dtype, v.data, v.validity, v.offsets)
    b = ColumnBatch(T.Schema([("s", v.dtype)]), [col],
                    jnp.asarray(cap, jnp.int32), cap)
    g = gather_rows(b, perm, jnp.asarray(cap, jnp.int32)).columns[0]
    return DevVal(v.dtype, g.data, g.validity, g.offsets)
