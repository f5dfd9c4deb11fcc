"""Arrow <-> HostBatch conversion (the JCudfSerialization/host-buffer staging
analogue — Arrow is the interchange layer the TPU build standardizes on,
SURVEY.md section 7)."""

from __future__ import annotations

from typing import List, Optional

import numpy as np
import pyarrow as pa

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import HostBatch, HostColumn
from spark_rapids_tpu.utils.tracing import span

_ARROW_TO_TYPE = {
    pa.bool_(): T.BOOLEAN,
    pa.int8(): T.BYTE,
    pa.int16(): T.SHORT,
    pa.int32(): T.INT,
    pa.int64(): T.LONG,
    pa.float32(): T.FLOAT,
    pa.float64(): T.DOUBLE,
    pa.date32(): T.DATE,
    pa.string(): T.STRING,
    pa.large_string(): T.STRING,
}


def arrow_type_to_sql(at: pa.DataType) -> T.DataType:
    if at in _ARROW_TO_TYPE:
        return _ARROW_TO_TYPE[at]
    if pa.types.is_timestamp(at):
        return T.TIMESTAMP
    if pa.types.is_dictionary(at):
        return arrow_type_to_sql(at.value_type)
    raise TypeError(f"unsupported arrow type {at}")


def sql_type_to_arrow(dt: T.DataType) -> pa.DataType:
    for a, s in _ARROW_TO_TYPE.items():
        if s == dt and a != pa.large_string():
            return a
    if dt == T.TIMESTAMP:
        return pa.timestamp("us", tz="UTC")
    raise TypeError(f"unsupported sql type {dt}")


def schema_from_arrow(asch: pa.Schema) -> T.Schema:
    return T.Schema([
        T.Field(f.name, arrow_type_to_sql(f.type), f.nullable)
        for f in asch
    ])


def _dict_host_column(f: T.Field, arr: "pa.DictionaryArray") -> HostColumn:
    """Preserve an Arrow dictionary string array as (int64 codes, object
    dictionary): H2D then moves 4-byte indices per row instead of string
    bytes, and the dictionary's bytes move once."""
    validity = np.ones(len(arr), dtype=np.bool_) if arr.null_count == 0 \
        else np.asarray(arr.is_valid())
    codes = arr.indices.to_numpy(zero_copy_only=False)
    codes = np.where(validity, np.nan_to_num(codes), 0).astype(np.int64)
    entries = np.array(
        ["" if v is None else v for v in arr.dictionary.to_pylist()],
        dtype=object)
    if not len(entries):
        entries = np.array([""], dtype=object)
    return HostColumn(f.dtype, codes, validity, entries)


def arrow_to_host_batch(table_or_batch, schema: Optional[T.Schema] = None,
                        keep_dictionary: bool = False) -> HostBatch:
    with span("io", "arrow_convert") as sp:
        hb = _arrow_to_host_batch(table_or_batch, schema, keep_dictionary)
        sp.set(rows=hb.num_rows, columns=len(hb.columns))
    return hb


def _arrow_to_host_batch(table_or_batch, schema: Optional[T.Schema],
                         keep_dictionary: bool) -> HostBatch:
    tb = table_or_batch
    if isinstance(tb, pa.Table):
        tb = tb.combine_chunks()
    if schema is None:
        schema = schema_from_arrow(tb.schema)
    cols: List[HostColumn] = []
    for f, name in zip(schema.fields, tb.schema.names):
        arr = tb.column(name)
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks() if arr.num_chunks != 1 else \
                arr.chunk(0)
        if pa.types.is_dictionary(arr.type):
            if keep_dictionary and f.dtype.is_string:
                cols.append(_dict_host_column(f, arr))
                continue
            arr = arr.dictionary_decode()
        null_free = arr.null_count == 0
        # null-free columns skip the bit-unpacking is_valid() pass
        validity = np.ones(len(arr), dtype=np.bool_) if null_free \
            else np.asarray(arr.is_valid())
        if f.dtype.is_string:
            values = np.array(
                ["" if v is None else v for v in arr.to_pylist()],
                dtype=object)
        elif f.dtype == T.TIMESTAMP:
            arr2 = arr.cast(pa.timestamp("us"))
            values = np.nan_to_num(
                arr2.to_numpy(zero_copy_only=False)).astype(
                "datetime64[us]").astype(np.int64)
            values = np.where(validity, values, 0).astype(np.int64)
        else:
            values = None
            if null_free:
                # zero-copy view over the arrow buffer for contiguous
                # null-free numerics: the scan's read-ahead then feeds H2D
                # staging without an intermediate host copy (bit-packed
                # bools and anything non-contiguous raise and fall through)
                try:
                    values = arr.to_numpy(zero_copy_only=True)
                except (pa.ArrowInvalid, pa.ArrowNotImplementedError):
                    values = None
            if values is None:
                values = arr.to_numpy(zero_copy_only=False)
            if values.dtype.kind == "f" and not f.dtype.is_fractional:
                # arrow promotes nullable ints to float NaN; undo it
                values = np.where(validity, np.nan_to_num(values), 0)
            values = values.astype(f.dtype.np_dtype, copy=False)
        cols.append(HostColumn(f.dtype, values, validity))
    return HostBatch(schema, cols)


def host_batch_to_arrow(hb: HostBatch) -> pa.Table:
    arrays = []
    names = []
    for f, c in zip(hb.schema.fields, hb.columns):
        names.append(f.name)
        vals = c.to_list()
        at = sql_type_to_arrow(f.dtype)
        if f.dtype == T.TIMESTAMP:
            arrays.append(pa.array(
                [None if v is None else int(v) for v in vals],
                type=pa.int64()).cast(pa.timestamp("us", tz="UTC")))
        elif f.dtype == T.DATE:
            arrays.append(pa.array(
                [None if v is None else int(v) for v in vals],
                type=pa.int32()).cast(pa.date32()))
        else:
            arrays.append(pa.array(vals, type=at))
    return pa.table(dict(zip(names, arrays)))
