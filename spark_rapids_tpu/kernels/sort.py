"""Multi-column sort over a ColumnBatch (cudf ``Table.orderBy`` analogue,
GpuSortExec.scala:241)."""

from __future__ import annotations

from typing import List

import jax.numpy as jnp

from spark_rapids_tpu.batch import ColumnBatch
from spark_rapids_tpu.exprs.base import DevVal
from spark_rapids_tpu.kernels.layout import gather_rows
from spark_rapids_tpu.kernels.sortkeys import (
    DEFAULT_STRING_PREFIX_BYTES,
    argsort_by_words,
    encode_sort_keys,
)
from spark_rapids_tpu.utils.tracing import kernel_scope


@kernel_scope
def argsort_batch(key_vals: List[DevVal], ascendings: List[bool],
                  nulls_firsts: List[bool], num_rows,
                  string_prefix_bytes: int = DEFAULT_STRING_PREFIX_BYTES,
                  groupings=None):
    """Permutation sorting rows by the given evaluated key columns.

    ``groupings`` marks columns that only need equal keys adjacent (see
    encode_sort_keys) — groupby/window partitioning pass it to keep string
    sorts at 3 key words instead of ~19."""
    cap = int(key_vals[0].validity.shape[0])
    words = encode_sort_keys(key_vals, ascendings, nulls_firsts, num_rows,
                             string_prefix_bytes, groupings=groupings)
    return argsort_by_words(words, cap)


@kernel_scope
def sort_batch(batch: ColumnBatch, key_vals: List[DevVal],
               ascendings: List[bool], nulls_firsts: List[bool],
               string_prefix_bytes: int = DEFAULT_STRING_PREFIX_BYTES
               ) -> ColumnBatch:
    perm = argsort_batch(key_vals, ascendings, nulls_firsts, batch.num_rows,
                         string_prefix_bytes)
    return gather_rows(batch, perm, batch.num_rows)
