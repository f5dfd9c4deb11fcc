"""Scan v2: chunk-granular parallel decode with bounded read-ahead,
dictionary-preserving string decode and chunk-level late materialization
(docs/io.md; the MultiFileParquetPartitionReader shape,
GpuParquetScan.scala:647-700, rebuilt for the host-decode TPU pipeline).

v1 decodes whole files serially on one pool thread per file and
materializes every HostBatch before the first H2D transfer.  v2 splits the
decode at parquet row-group / ORC stripe granularity, runs chunks on the
process-shared decode pool (io.decode_pool) and yields them through an
ordered sliding window of ``scan.readAhead.depth`` in-flight futures — so
decode of chunks k+1..k+depth overlaps the consumer's H2D staging and
device compute of chunk k, while output order stays deterministic
(submission order, for bit parity with v1).

Late materialization (``scan.lateMaterialization.enabled``): when
conjuncts were pushed, each chunk first decodes ONLY the predicate
columns present in the file and evaluates the conjuncts exactly; chunks
with no surviving row skip the decode of every remaining projected
column.  The Filter above the scan re-applies the predicate, so the skip
is chunk-granular and bit-exact.

Dictionary encoding (``scan.dictEncoding.enabled``): when the consumer is
H2D staging (HostToDeviceExec's ``set_device_consumer`` handshake),
parquet string columns are decoded with Arrow dictionary preservation and
emitted as (codes, dictionary) HostColumns — the transfer moves integer
codes per row plus the dictionary's bytes once, and device kernels that
only need lengths/hashes/prefixes (string equality, group keys) never
touch the raw bytes (exprs.strings / kernels.sortkeys dict paths).
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Iterable, List, Optional

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import HostBatch, host_batch_bytes
from spark_rapids_tpu.config import (
    SCAN_DICT_ENCODING_ENABLED, SCAN_FILE_HANDLE_CACHE_SIZE,
    SCAN_LATE_MAT_ENABLED, SCAN_PAGE_CHUNK_MIN_BYTES,
    SCAN_READAHEAD_ADAPTIVE, SCAN_READAHEAD_DEPTH, SCAN_READAHEAD_MAX_DEPTH,
    RapidsConf,
)
from spark_rapids_tpu.fault import inject
from spark_rapids_tpu.io.arrow_convert import arrow_to_host_batch
from spark_rapids_tpu.io.decode_pool import (
    cached_reader, decode_pool_utilization, get_decode_pool,
)
from spark_rapids_tpu.io.discovery import csv_options
from spark_rapids_tpu.io.scan import CpuFileScanExec, _row_group_can_match
from spark_rapids_tpu.obs import timeseries as obs_ts
from spark_rapids_tpu.plan.physical import ExecContext
from spark_rapids_tpu.utils.tracing import annotated, record_span, span

#: Decoded-and-ready chunks held beyond the one being consumed — chunk k
#: on device, k+1 staged on host, k+2..k+1+depth decoding: the classic
#: triple buffer, with the decode window as the third stage.
_READY_BUF = 2

#: Drains between adaptive read-ahead adjustments (smooths the
#: blocked-fraction signal over a few chunks).
_ADAPT_EVERY = 4


@dataclasses.dataclass
class _ChunkResult:
    """One decoded (or skipped) chunk, in submission order."""

    batches: List[HostBatch]
    decode_ns: int = 0
    bytes_decoded: int = 0
    skipped: bool = False       # late-mat: no row can survive the conjuncts
    rg_total: int = 0
    rg_read: int = 0
    dict_columns: int = 0
    label: str = ""
    t0: int = 0                 # worker-side decode window (monotonic ns)
    t1: int = 0


def _chunk_survivors(descriptors, table) -> bool:
    """Exact chunk-level survival: does ANY row satisfy every pushed
    conjunct?  Evaluated with plain numpy comparisons — the same IEEE
    semantics the device Filter applies — so a skipped chunk can never
    contain a row the Filter would have kept."""
    import pyarrow as pa
    mask: Optional[np.ndarray] = None
    for name, op, value in descriptors:
        if name not in table.schema.names:
            continue
        arr = table.column(name)
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks() if arr.num_chunks != 1 else \
                arr.chunk(0)
        if pa.types.is_dictionary(arr.type):
            arr = arr.dictionary_decode()
        valid = np.ones(len(arr), dtype=np.bool_) if arr.null_count == 0 \
            else np.asarray(arr.is_valid())
        if op == "notnull":
            m = valid
        else:
            try:
                if pa.types.is_string(arr.type) or \
                        pa.types.is_large_string(arr.type):
                    vals = np.array(
                        ["" if v is None else v for v in arr.to_pylist()],
                        dtype=object)
                else:
                    vals = arr.to_numpy(zero_copy_only=False)
                cmp = {"eq": np.equal, "lt": np.less, "le": np.less_equal,
                       "gt": np.greater, "ge": np.greater_equal}[op]
                with np.errstate(invalid="ignore"):
                    m = valid & np.asarray(cmp(vals, value), dtype=np.bool_)
            except (TypeError, ValueError):
                continue  # incomparable: conservatively keep the chunk
        mask = m if mask is None else (mask & m)
    return bool(mask.any()) if mask is not None else True


def _dict_candidate(t) -> bool:
    """String columns the encoded corridor can carry: plain strings (the
    scan requests read_dictionary) and columns whose restored arrow
    schema is ALREADY dictionary<string> (pyarrow round-trips the arrow
    schema through parquet metadata, so a file written from encoded
    arrays reads back dictionary-typed with no read_dictionary ask)."""
    import pyarrow as pa
    if pa.types.is_string(t) or pa.types.is_large_string(t):
        return True
    return pa.types.is_dictionary(t) and (
        pa.types.is_string(t.value_type) or
        pa.types.is_large_string(t.value_type))


class FileScanV2Exec(CpuFileScanExec):
    """Chunk-parallel scan with read-ahead, dictionary strings and late
    materialization; bit-parity with :class:`CpuFileScanExec`."""

    def __init__(self, node, conf: RapidsConf):
        super().__init__(node, conf)
        self._depth = max(1, SCAN_READAHEAD_DEPTH.get(conf))
        # the adaptive controller owns the depth UNLESS the user pinned
        # scan.readAhead.depth explicitly — static wins when set
        self._adaptive = (SCAN_READAHEAD_ADAPTIVE.get(conf) and
                          not conf.explicitly_set(SCAN_READAHEAD_DEPTH.key))
        self._max_depth = max(self._depth, SCAN_READAHEAD_MAX_DEPTH.get(conf))
        self._page_min_bytes = SCAN_PAGE_CHUNK_MIN_BYTES.get(conf)
        self._handle_cache = max(0, SCAN_FILE_HANDLE_CACHE_SIZE.get(conf))
        self._dict_enabled = SCAN_DICT_ENCODING_ENABLED.get(conf)
        self._late_mat = SCAN_LATE_MAT_ENABLED.get(conf)
        self._device_consumer = False

    def set_device_consumer(self) -> None:
        """Called by HostToDeviceExec: batches feed device staging, so
        dictionary-encoded string columns may be emitted."""
        self._device_consumer = True

    def _use_dict(self) -> bool:
        return self._device_consumer and self._dict_enabled

    def describe(self):
        flags = []
        if self.descriptors:
            flags.append(f"pushed={len(self.descriptors)}")
        if self._use_dict():
            flags.append("dict")
        if self._late_mat:
            flags.append("latemat")
        extra = (", " + ",".join(flags)) if flags else ""
        return (f"FileScanV2({self.fmt}, {len(self.paths)} files, "
                f"depth={self._depth}{extra})")

    # -- chunk planning ------------------------------------------------------

    def _file_columns(self) -> List[str]:
        part_fields = []
        if self.partitions_info is not None:
            part_fields = self.partitions_info[0].fields
        part_names = {f.name for f in part_fields}
        return [n for n in self.output_schema.names if n not in part_names]

    def _parquet_file(self, path: str, read_dict: Optional[List[str]] = None):
        import pyarrow.parquet as pq
        kind = "pq" if not read_dict else "pq+dict:" + ",".join(read_dict)
        if read_dict:
            return cached_reader(
                kind, path,
                lambda: pq.ParquetFile(path, read_dictionary=read_dict),
                self._handle_cache)
        return cached_reader(kind, path, lambda: pq.ParquetFile(path),
                             self._handle_cache)

    def _orc_file(self, path: str):
        import pyarrow.orc as orc
        return cached_reader("orc", path, lambda: orc.ORCFile(path),
                             self._handle_cache)

    def _plan_column_slabs(self, meta, rg: int, columns: List[str]
                           ) -> Optional[List[List[str]]]:
        """Page-level chunk granularity: split an OVERSIZED parquet row
        group into contiguous column slabs of >= scan.pageChunk.minBytes
        compressed bytes each, decoded as parallel pool tasks and zipped
        back column-wise by the consumer — one writer's giant row group
        stops serializing the whole pipeline behind a single decode
        thread.  Returns None (no split) for small row groups, single- or
        zero-column projections, and pushed-predicate scans (slabs would
        re-run the survival probe per slab)."""
        if self._page_min_bytes <= 0 or self.descriptors or \
                len(columns) < 2:
            return None
        rgm = meta.row_group(rg)
        sizes = {}
        for i in range(rgm.num_columns):
            c = rgm.column(i)
            name = c.path_in_schema.split(".")[0]
            sizes[name] = sizes.get(name, 0) + c.total_compressed_size
        total = sum(sizes.get(n, 0) for n in columns)
        if total < 2 * self._page_min_bytes:
            return None
        n_slabs = min(len(columns), total // self._page_min_bytes)
        target = total / n_slabs
        slabs: List[List[str]] = []
        cur: List[str] = []
        acc = 0
        for name in columns:
            cur.append(name)
            acc += sizes.get(name, 0)
            if acc >= target and len(slabs) < n_slabs - 1:
                slabs.append(cur)
                cur, acc = [], 0
        if cur:
            slabs.append(cur)
        return slabs if len(slabs) > 1 else None

    def _chunk_tasks(self, files: List[str]):
        """Lazily yield one decode task GROUP per chunk as ``(path,
        [callables])``, in deterministic order (file order, then chunk
        index) — the sliding window preserves it.  A group has one task
        per column slab (len 1 for everything but oversized parquet row
        groups); the consumer zips multi-slab results column-wise."""
        columns = self._file_columns()
        batch_rows = self.conf.max_readers_batch_size_rows
        for path in files:
            if self.fmt == "parquet":
                meta = self._parquet_file(path).metadata
                for rg in range(meta.num_row_groups):
                    slabs = self._plan_column_slabs(meta, rg, columns) \
                        if columns else None
                    if slabs is None:
                        yield path, [
                            lambda p=path, i=rg:
                            self._decode_parquet_chunk(p, i, columns,
                                                       batch_rows)]
                    else:
                        yield path, [
                            lambda p=path, i=rg, s=slab:
                            self._decode_parquet_slab(p, i, s, batch_rows)
                            for slab in slabs]
            elif self.fmt == "orc":
                n_stripes = self._orc_file(path).nstripes
                for st in range(n_stripes):
                    yield path, [
                        lambda p=path, i=st:
                        self._decode_orc_chunk(p, i, columns, batch_rows)]
            elif self.fmt == "csv":
                yield path, [
                    lambda p=path:
                    self._decode_csv_chunk(p, columns, batch_rows)]
            else:
                raise ValueError(self.fmt)

    # -- per-chunk decode (runs on pool worker threads) ----------------------

    def _finish_chunk(self, path: str, batches: List[HostBatch],
                      res: _ChunkResult) -> _ChunkResult:
        use_dict = self._use_dict()
        batches = self._with_partition_columns(path, batches,
                                               use_dict=use_dict)
        res.batches = batches
        res.bytes_decoded = sum(host_batch_bytes(hb) for hb in batches)
        if use_dict:
            res.dict_columns = sum(
                1 for hb in batches[:1] for c in hb.columns
                if c.dictionary is not None)
        return res

    def _decode_parquet_chunk(self, path: str, rg: int, columns: List[str],
                              batch_rows: int) -> _ChunkResult:
        import pyarrow as pa
        import pyarrow.parquet as pq
        res = _ChunkResult([], rg_total=1, label=f"parquet:{rg}",
                           t0=time.monotonic_ns())
        # readers are per-THREAD (decode_pool.cached_reader): ParquetFile
        # is not safe for concurrent reads from multiple pool threads,
        # but one worker reusing its own handle across row groups is
        f = self._parquet_file(path)
        file_schema = f.schema_arrow
        read_dict: List[str] = []
        if self._use_dict():
            read_dict = [n for n in file_schema.names
                         if _dict_candidate(file_schema.field(n).type)]
            if read_dict:
                f = self._parquet_file(path, read_dict)
        meta = f.metadata
        col_index = {meta.schema.column(i).name: i
                     for i in range(meta.num_columns)}
        if self.descriptors and not _row_group_can_match(
                meta.row_group(rg), col_index, self.descriptors):
            res.t1 = time.monotonic_ns()
            res.decode_ns = res.t1 - res.t0
            return res  # statistics skip (v1 parity): nothing decoded
        res.rg_read = 1
        probe = None
        if self._late_mat and self.descriptors:
            pred_cols = sorted({name for name, _op, _v in self.descriptors
                                if name in file_schema.names})
            if pred_cols:
                probe = f.read_row_group(rg, columns=pred_cols)
                if not _chunk_survivors(self.descriptors, probe):
                    res.skipped = True
                    res.bytes_decoded = probe.nbytes
                    res.t1 = time.monotonic_ns()
                    res.decode_ns = res.t1 - res.t0
                    return res
        if not columns:
            tb = f.read_row_group(rg)  # v1 parity: empty projection -> all
        elif probe is None:
            tb = f.read_row_group(rg, columns=columns)
        else:
            # survivors exist: decode only the columns the probe didn't
            rest = [c for c in columns if c not in probe.schema.names]
            tb_rest = f.read_row_group(rg, columns=rest) if rest else None
            arrays = {}
            for src in (probe, tb_rest):
                if src is not None:
                    for name in src.schema.names:
                        arrays[name] = src.column(name)
            tb = pa.table({n: arrays[n] for n in columns})
        hb = arrow_to_host_batch(tb, keep_dictionary=bool(read_dict))
        batches = [hb.slice(j, min(batch_rows, hb.num_rows - j))
                   for j in range(0, hb.num_rows, batch_rows)]
        self._finish_chunk(path, batches, res)
        res.t1 = time.monotonic_ns()
        res.decode_ns = res.t1 - res.t0
        return res

    def _decode_parquet_slab(self, path: str, rg: int, slab: List[str],
                             batch_rows: int) -> _ChunkResult:
        """Decode ONE column slab of a row group (page-level granularity;
        no predicate pushdown here — _plan_column_slabs guards).  Raw
        result: no partition columns, no byte accounting — the consumer
        merges slabs and runs _finish_chunk once."""
        import pyarrow as pa
        res = _ChunkResult([], label=f"parquet:{rg}:{slab[0]}",
                           t0=time.monotonic_ns())
        f = self._parquet_file(path)
        file_schema = f.schema_arrow
        read_dict: List[str] = []
        if self._use_dict():
            read_dict = [n for n in slab
                         if n in file_schema.names and
                         _dict_candidate(file_schema.field(n).type)]
            if read_dict:
                f = self._parquet_file(path, read_dict)
        tb = f.read_row_group(rg, columns=slab)
        hb = arrow_to_host_batch(tb, keep_dictionary=bool(read_dict))
        res.batches = [hb.slice(j, min(batch_rows, hb.num_rows - j))
                       for j in range(0, hb.num_rows, batch_rows)]
        res.t1 = time.monotonic_ns()
        res.decode_ns = res.t1 - res.t0
        return res

    def _merge_slab_results(self, path: str,
                            results: List[_ChunkResult]) -> _ChunkResult:
        """Zip column-slab results back into one whole-row chunk.  Slabs
        cover disjoint contiguous column ranges of the SAME rows with the
        same batch_rows splits, so batch j of every slab aligns."""
        res = _ChunkResult([], rg_total=1, rg_read=1,
                           label=results[0].label.rsplit(":", 1)[0],
                           t0=min(r.t0 for r in results),
                           t1=max(r.t1 for r in results))
        res.decode_ns = sum(r.decode_ns for r in results)
        merged = []
        for parts in zip(*(r.batches for r in results)):
            fields = [f for hb in parts for f in hb.schema.fields]
            cols = [c for hb in parts for c in hb.columns]
            merged.append(HostBatch(T.Schema(fields), cols))
        self._finish_chunk(path, merged, res)
        return res

    def _dict_encode_table(self, tb):
        """Host-side dictionary encoding for formats without a native
        dictionary read path (ORC stripes, CSV): string columns re-encode
        to (codes, entries) before staging, so H2D still moves 4-byte
        codes plus the dictionary once.  Returns (table, encoded_any)."""
        import pyarrow as pa
        import pyarrow.compute as pc
        if not self._use_dict():
            return tb, False
        encoded = False
        for i, f in enumerate(tb.schema):
            if pa.types.is_string(f.type) or pa.types.is_large_string(f.type):
                tb = tb.set_column(i, f.name,
                                   pc.dictionary_encode(tb.column(i)))
                encoded = True
        return tb, encoded

    def _decode_orc_chunk(self, path: str, stripe: int, columns: List[str],
                          batch_rows: int) -> _ChunkResult:
        res = _ChunkResult([], rg_total=1, label=f"orc:{stripe}",
                           t0=time.monotonic_ns())
        f = self._orc_file(path)
        avail = set(f.schema.names)
        pred_cols = sorted({name for name, _op, _v in self.descriptors
                            if name in avail})
        if pred_cols:
            probe = f.read_stripe(stripe, columns=pred_cols)
            if self._late_mat:
                if not _chunk_survivors(self.descriptors, probe):
                    res.skipped = True
                    res.bytes_decoded = probe.nbytes
                    res.t1 = time.monotonic_ns()
                    res.decode_ns = res.t1 - res.t0
                    return res
            elif not self._stripe_can_match(probe):
                res.t1 = time.monotonic_ns()
                res.decode_ns = res.t1 - res.t0
                return res  # v1-style min/max stripe skip
        res.rg_read = 1
        tb, enc = self._dict_encode_table(
            f.read_stripe(stripe, columns=columns or None))
        hb = arrow_to_host_batch(tb, keep_dictionary=enc)
        batches = [hb.slice(j, min(batch_rows, hb.num_rows - j))
                   for j in range(0, hb.num_rows, batch_rows)]
        self._finish_chunk(path, batches, res)
        res.t1 = time.monotonic_ns()
        res.decode_ns = res.t1 - res.t0
        return res

    def _stripe_can_match(self, probe) -> bool:
        """v1 ORC min/max stripe test over probe columns (same NaN
        conservatism as io.scan._read_orc_file)."""
        from spark_rapids_tpu.io.scan import _range_can_match
        for name, op, value in self.descriptors:
            if name not in probe.schema.names:
                continue
            arr = probe.column(name)
            nulls = arr.null_count
            if op == "notnull":
                if nulls == len(arr):
                    return False
                continue
            if nulls == len(arr):
                return False  # all NULL: no comparison can hold
            vals = arr.drop_null().to_numpy(zero_copy_only=False)
            if vals.dtype.kind == "f" and np.isnan(vals).any():
                continue  # NaN poisons min/max; never skip such stripes
            if not _range_can_match(op, value, vals.min(), vals.max()):
                return False
        return True

    def _decode_csv_chunk(self, path: str, columns: List[str],
                          batch_rows: int) -> _ChunkResult:
        import pyarrow.csv as pacsv
        res = _ChunkResult([], rg_total=1, rg_read=1, label="csv",
                           t0=time.monotonic_ns())
        read_opts, parse_opts, conv_opts = csv_options(self.options)
        if columns:
            conv_opts.include_columns = columns
        tb = pacsv.read_csv(path, read_options=read_opts,
                            parse_options=parse_opts,
                            convert_options=conv_opts)
        tb, enc = self._dict_encode_table(tb)
        hb = arrow_to_host_batch(tb, keep_dictionary=enc)
        batches = [hb.slice(j, min(batch_rows, hb.num_rows - j))
                   for j in range(0, hb.num_rows, batch_rows)] \
            if hb.num_rows else []
        self._finish_chunk(path, batches, res)
        res.t1 = time.monotonic_ns()
        res.decode_ns = res.t1 - res.t0
        return res

    # -- partition driver ----------------------------------------------------

    def partitions(self, ctx: ExecContext):
        n = self.num_partitions(ctx)
        groups: List[List[str]] = [[] for _ in range(n)]
        for i, p in enumerate(self.paths):
            groups[i % n].append(p)
        pool = get_decode_pool(self._nthreads)
        m_decode = ctx.metric(self.op_id, "scanDecodeWallNs")
        m_overlap = ctx.metric(self.op_id, "scanH2dOverlapNs")
        m_bytes = ctx.metric(self.op_id, "scanBytesDecoded")
        m_dict = ctx.metric(self.op_id, "scanDictColumns")
        m_skipped = ctx.metric(self.op_id, "scanChunksSkipped")
        m_depth = ctx.metric(self.op_id, "readaheadDepthEffective")
        rg_read = ctx.metric(self.op_id, "rowGroupsRead")
        rg_total = ctx.metric(self.op_id, "rowGroupsTotal")
        adaptive = self._adaptive
        max_depth = self._max_depth

        def gen(files: List[str]):
            # pending: (path, [futures]) decode window, submission order.
            # ready: decoded chunks harvested off the window head but not
            # yet yielded — the host-side stage of the triple buffer.
            pending: collections.deque = collections.deque()
            ready: collections.deque = collections.deque()
            stats = {"decode": 0, "bytes": 0, "skipped": 0, "dict": 0,
                     "rg_read": 0, "rg_total": 0, "blocked": 0,
                     "drains": 0, "win_blocked": 0,
                     "win_t0": time.monotonic_ns(),
                     "depth": self._depth, "depth_max": self._depth}

            def finish_entry(entry, blocked_ns: int) -> _ChunkResult:
                _path, futs = entry  # every future completed by now
                rs = [fu.result() for fu in futs]
                res = rs[0] if len(rs) == 1 else \
                    self._merge_slab_results(_path, rs)
                stats["blocked"] += blocked_ns
                stats["win_blocked"] += blocked_ns
                stats["decode"] += res.decode_ns
                stats["bytes"] += res.bytes_decoded
                stats["skipped"] += 1 if res.skipped else 0
                stats["dict"] += res.dict_columns
                stats["rg_read"] += res.rg_read
                stats["rg_total"] += res.rg_total
                # the worker timed the decode (and opened its profiler
                # range, see ``annotated`` at the submit below); the
                # consumer owns the query scope, so the ring entry is here
                record_span(
                    "scan", "chunk", self.op_id, res.t0, res.t1,
                    label=res.label, bytes=res.bytes_decoded,
                    skipped=res.skipped)
                return res

            def adapt() -> None:
                # telemetry-driven read-ahead: raise the depth while the
                # consumer blocks on decode AND the pool has headroom;
                # shed it when chunks pile up decoded-but-unconsumed
                stats["drains"] += 1
                if not adaptive or stats["drains"] % _ADAPT_EVERY:
                    return
                now = time.monotonic_ns()
                wall = max(now - stats["win_t0"], 1)
                blocked_frac = stats["win_blocked"] / wall
                d = stats["depth"]
                if blocked_frac > 0.05 and decode_pool_utilization() < 1.0:
                    d = min(d + 1, max_depth)
                elif blocked_frac < 0.005 and len(ready) >= _READY_BUF:
                    d = max(d - 1, 1)
                if d != stats["depth"]:
                    stats["depth"] = d
                    stats["depth_max"] = max(stats["depth_max"], d)
                obs_ts.record_value("io.scan.readahead_depth", float(d))
                stats["win_blocked"] = 0
                stats["win_t0"] = now

            def drain_blocking() -> _ChunkResult:
                entry = pending.popleft()
                with span("scan", "wait_decode", self.op_id) as sp:
                    for fu in entry[1]:
                        fu.result()
                res = finish_entry(entry, sp.elapsed_ns)
                adapt()
                return res

            def harvest() -> None:
                # move COMPLETED head entries out of the decode window so
                # the submit loop starts the next decode immediately
                # instead of counting finished chunks against the depth
                while pending and len(ready) < _READY_BUF and \
                        all(fu.done() for fu in pending[0][1]):
                    ready.append(finish_entry(pending.popleft(), 0))

            def results():
                for path, tasks in self._chunk_tasks(files):
                    # fire on the consumer thread: deterministic per-query
                    # numbering AND the active query's scoped registry
                    # (pool workers carry no obs scope)
                    inject.maybe_fire("scan")
                    pending.append((path, [
                        pool.submit(annotated("scan", "chunk", t))
                        for t in tasks]))
                    harvest()
                    while len(pending) >= stats["depth"]:
                        if ready:
                            yield ready.popleft()
                        else:
                            yield drain_blocking()
                        harvest()
                while pending or ready:
                    if ready:
                        yield ready.popleft()
                    else:
                        yield drain_blocking()
                    harvest()

            try:
                for res in results():
                    for hb in res.batches:
                        if hb.num_rows:
                            yield hb
            finally:
                for _path, futs in pending:
                    for fu in futs:
                        fu.cancel()
                pending.clear()
                ready.clear()
                m_decode.add(stats["decode"])
                m_overlap.add(max(0, stats["decode"] - stats["blocked"]))
                m_bytes.add(stats["bytes"])
                m_dict.add(stats["dict"])
                m_skipped.add(stats["skipped"])
                # max, not sum: each partition generator reports the
                # deepest read-ahead it actually ran
                m_depth.value = max(m_depth.value, stats["depth_max"])
                rg_read.add(stats["rg_read"])
                rg_total.add(stats["rg_total"])

        return [gen(g) for g in groups]
