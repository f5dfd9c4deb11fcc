"""Columnar writers (GpuParquetFileFormat / GpuOrcFileFormat /
ColumnarOutputWriter analogues, SURVEY.md section 2.6): one output file per
partition, written host-side from staged batches via Arrow."""

from __future__ import annotations

import os
import shutil
from typing import List

from spark_rapids_tpu.batch import HostBatch
from spark_rapids_tpu.io.arrow_convert import host_batch_to_arrow


def _prepare_dir(path: str, mode: str):
    if os.path.exists(path):
        if mode == "overwrite":
            shutil.rmtree(path)
        elif mode in ("error", "errorifexists"):
            raise FileExistsError(path)
        elif mode == "ignore":
            return False
    os.makedirs(path, exist_ok=True)
    return True


def _write_table(table, fmt: str, fname: str):
    if fmt == "parquet":
        import pyarrow.parquet as pq
        pq.write_table(table, fname)
    elif fmt == "orc":
        import pyarrow.orc as paorc
        paorc.write_table(table, fname)
    elif fmt == "csv":
        import pyarrow.csv as pacsv
        pacsv.write_csv(table, fname)
    else:
        raise ValueError(fmt)


def write_dataframe(df, fmt: str, path: str, mode: str = "error",
                    partition_by=None):
    """Execute the plan and write one file per partition.

    ``partition_by``: column names for dynamic-partition output
    (key=value subdirectories — the GpuDynamicPartitionDataWriter role,
    GpuFileFormatDataWriter.scala).  Returns write stats
    (BasicColumnarWriteStatsTracker analogue): {num_files, num_rows,
    num_bytes, partitions}.
    """
    from spark_rapids_tpu.plan.physical import (
        DeviceToHostExec, ExecContext,
    )
    if not _prepare_dir(path, mode):
        return {"num_files": 0, "num_rows": 0, "num_bytes": 0,
                "partitions": 0}
    session = df.session
    from spark_rapids_tpu.utils import params
    phys, bound, _facts = session.plan_bound(df.plan)
    if phys.is_tpu:
        phys = DeviceToHostExec(phys)
    ctx = ExecContext(
        session.conf,
        semaphore=session.runtime.semaphore if session.runtime else None,
        device=session.runtime.device if session.runtime else None)
    stats = {"num_files": 0, "num_rows": 0, "num_bytes": 0, "partitions": 0}
    part_dirs = set()
    try:
        with params.executing(bound):
            for pi, part in enumerate(phys.partitions(ctx)):
                batches: List[HostBatch] = [hb for hb in part if hb.num_rows]
                if not batches:
                    continue
                hb = HostBatch.concat(batches)
                if partition_by:
                    _write_partitioned(hb, fmt, path, pi, partition_by, stats,
                                       part_dirs)
                    continue
                table = host_batch_to_arrow(hb)
                fname = os.path.join(path, f"part-{pi:05d}.{_ext(fmt)}")
                _write_table(table, fname=fname, fmt=fmt)
                stats["num_files"] += 1
                stats["num_rows"] += hb.num_rows
                stats["num_bytes"] += os.path.getsize(fname)
    finally:
        ctx.close_deferred()
    stats["partitions"] = len(part_dirs)
    if stats["num_files"] == 0 and fmt == "parquet" and not partition_by:
        # still write an empty file carrying the schema
        import pyarrow.parquet as pq
        empty = host_batch_to_arrow(HostBatch(df.plan.schema, [
            _empty_col(f) for f in df.plan.schema.fields]))
        fname = os.path.join(path, "part-00000.parquet")
        pq.write_table(empty, fname)
        stats["num_files"] = 1
    open(os.path.join(path, "_SUCCESS"), "w").close()
    return stats


def _write_partitioned(hb: HostBatch, fmt: str, path: str, pi: int,
                       partition_by, stats, part_dirs):
    """Dynamic-partition write: group rows by the partition-column values,
    one file per (task partition, value combination)."""
    import numpy as np

    from spark_rapids_tpu.batch import HostColumn
    key_idx = [hb.schema.index_of(c) for c in partition_by]
    data_fields = [f for f in hb.schema.fields
                   if f.name not in set(partition_by)]
    key_cols = [hb.columns[i].to_list() for i in key_idx]
    rows_by_key = {}
    for r in range(hb.num_rows):
        k = tuple(col[r] for col in key_cols)
        rows_by_key.setdefault(k, []).append(r)
    from spark_rapids_tpu import types as T
    for k, rows in rows_by_key.items():
        sub_dir = os.path.join(path, *[
            f"{name}={'__HIVE_DEFAULT_PARTITION__' if v is None else v}"
            for name, v in zip(partition_by, k)])
        os.makedirs(sub_dir, exist_ok=True)
        part_dirs.add(sub_dir)
        idx = np.asarray(rows)
        cols = []
        for f in data_fields:
            c = hb.columns[hb.schema.index_of(f.name)]
            cols.append(HostColumn(f.dtype, c.values[idx], c.validity[idx]))
        sub = HostBatch(T.Schema(data_fields), cols)
        fname = os.path.join(sub_dir, f"part-{pi:05d}.{_ext(fmt)}")
        _write_table(host_batch_to_arrow(sub), fmt, fname)
        stats["num_files"] += 1
        stats["num_rows"] += sub.num_rows
        stats["num_bytes"] += os.path.getsize(fname)


def _empty_col(f):
    import numpy as np
    from spark_rapids_tpu.batch import HostColumn
    vals = np.zeros(0, dtype=object if f.dtype.is_string else f.dtype.np_dtype)
    return HostColumn(f.dtype, vals, np.zeros(0, dtype=np.bool_))


def _ext(fmt: str) -> str:
    return {"parquet": "parquet", "orc": "orc", "csv": "csv"}[fmt]
