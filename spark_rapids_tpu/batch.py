"""Device-resident columnar batch model.

The TPU analogue of the reference's GpuColumnVector/ColumnarBatch layer
(GpuColumnVector.java:39, SURVEY.md section 2.3).  A cudf ``Table`` in GPU
memory becomes a :class:`ColumnBatch`: a struct of dense ``jax.Array`` buffers
staged in HBM.

TPU-first design decisions:

* **Static shapes.**  XLA compiles one executable per shape, so every batch is
  padded to a bucketed capacity (powers of two) and carries a dynamic
  ``num_rows`` scalar.  Kernels mask out rows >= num_rows.  This replaces the
  reference's dynamic cudf row counts and is the bucketed-padded-batch design
  called out in SURVEY.md section 7.
* **Pytree batches.**  ``ColumnBatch``/``DeviceColumn`` are registered pytrees
  with (schema, capacity) as static treedef aux data, so whole batches flow
  through ``jax.jit`` boundaries and fused pipeline stages without manual
  packing.
* **Validity masks, not sentinels.**  Every column has a bool validity array;
  NULL semantics live in the expression kernels.
* **Strings** use the cudf layout: ``offsets`` int32[cap+1] into a flat
  ``uint8`` byte buffer (itself bucketed), so most string ops become
  gather/scan ops which XLA handles well.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T

MIN_CAPACITY = 8
MIN_BYTE_CAPACITY = 16


class BucketPolicy:
    """THE shape-bucket policy: every capacity any exec ever bakes into a
    compiled program comes from this one object, so compiled-shape
    cardinality per schema is bounded by a single rule instead of drifting
    per call site (the recompilation-economics lever from SURVEY.md
    section 7; the ``compiledShapes`` metric proves the bound holds).

    Buckets are powers of two: row capacities >= ``min_rows``, varlen
    element/byte capacities >= ``min_bytes`` (strings ARE array<byte>, so
    both varlen kinds share the byte floor).
    """

    def __init__(self, min_rows: int = MIN_CAPACITY,
                 min_bytes: int = MIN_BYTE_CAPACITY):
        self.min_rows = min_rows
        self.min_bytes = min_bytes

    @staticmethod
    def quantize(n: int, minimum: int) -> int:
        cap = max(int(minimum), 1)
        n = max(int(n), 1)
        while cap < n:
            cap <<= 1
        return cap

    def rows(self, n: int) -> int:
        """Row-capacity bucket for ``n`` live rows."""
        return self.quantize(n, self.min_rows)

    def elems(self, n: int) -> int:
        """Varlen element/byte-capacity bucket for ``n`` elements."""
        return self.quantize(n, self.min_bytes)

    def hot_buckets(self, max_rows: int) -> List[int]:
        """The full row-bucket ladder up to ``max_rows`` — the shape set
        ``session.prewarm()`` compiles ahead of time."""
        out, cap = [], self.rows(1)
        while cap <= self.rows(max_rows):
            out.append(cap)
            cap <<= 1
        return out


#: Process-wide shared bucket policy (all exec inputs route through it).
BUCKETS = BucketPolicy()


def round_up_capacity(n: int, minimum: int = MIN_CAPACITY) -> int:
    """Bucketed capacity via the shared :data:`BUCKETS` policy: next power
    of two >= n (>= minimum)."""
    return BUCKETS.quantize(n, minimum)


# --------------------------------------------------------------------------
# Host-side column/batch: numpy representation used by IO, the CPU oracle and
# host<->HBM staging (the HostMemoryBuffer analogue).
# --------------------------------------------------------------------------


@dataclasses.dataclass
class HostColumn:
    dtype: T.DataType
    values: np.ndarray  # object ndarray of str|None for strings
    validity: np.ndarray  # bool, True = valid
    #: Dictionary-encoded strings (scan v2, docs/io.md): ``values`` hold
    #: int32 codes into this object array of entries, so staging moves
    #: indices instead of per-row bytes.  ``None`` = plain column.
    dictionary: Optional[np.ndarray] = None

    def __post_init__(self):
        self.values = np.asarray(self.values)
        self.validity = np.asarray(self.validity, dtype=np.bool_)
        assert len(self.values) == len(self.validity)
        if self.dictionary is not None:
            self.dictionary = np.asarray(self.dictionary, dtype=object)

    def __len__(self) -> int:
        return len(self.values)

    def decoded(self) -> "HostColumn":
        """Materialize a dictionary-encoded column to plain values (no-op
        for plain columns)."""
        if self.dictionary is None:
            return self
        n = len(self.values)
        values = np.empty(n, dtype=object)
        nd = len(self.dictionary)
        codes = np.asarray(self.values, dtype=np.int64)
        for i in range(n):
            c = codes[i]
            values[i] = (str(self.dictionary[c])
                         if self.validity[i] and 0 <= c < nd else "")
        return HostColumn(self.dtype, values, self.validity)

    @staticmethod
    def from_list(dtype: T.DataType, items: Sequence[Any]) -> "HostColumn":
        validity = np.array([x is not None for x in items], dtype=np.bool_)
        if dtype.is_string:
            values = np.array([x if x is not None else "" for x in items], dtype=object)
        elif dtype.is_array:
            values = np.empty(len(items), dtype=object)
            for i, x in enumerate(items):
                values[i] = list(x) if x is not None else []
        else:
            values = np.array(
                [x if x is not None else 0 for x in items], dtype=dtype.np_dtype
            )
        return HostColumn(dtype, values, validity)

    def to_list(self) -> List[Any]:
        if self.dictionary is not None:
            return self.decoded().to_list()
        out: List[Any] = []
        elem = self.dtype.element if self.dtype.is_array else None
        for v, ok in zip(self.values, self.validity):
            if not ok:
                out.append(None)
            elif self.dtype.is_string:
                out.append(str(v))
            elif self.dtype.is_array:
                out.append([_pyval(elem, e) for e in v])
            elif self.dtype == T.BOOLEAN:
                out.append(bool(v))
            elif self.dtype.is_fractional:
                out.append(float(v))
            else:
                out.append(int(v))
        return out


def _pyval(dtype: T.DataType, v):
    if v is None:
        return None  # element-level NULL (host representation only)
    if dtype.is_string:
        return str(v)
    if dtype == T.BOOLEAN:
        return bool(v)
    if dtype.is_fractional:
        return float(v)
    return int(v)


class HostBatch:
    """A host (numpy) table; the staging representation between IO and device."""

    def __init__(self, schema: T.Schema, columns: Sequence[HostColumn]):
        self.schema = schema
        self.columns = list(columns)
        nrows = {len(c) for c in self.columns}
        assert len(nrows) <= 1, f"ragged batch: {nrows}"
        self.num_rows = len(self.columns[0]) if self.columns else 0

    @staticmethod
    def from_pydict(data: Dict[str, Tuple[T.DataType, Sequence[Any]]]) -> "HostBatch":
        fields, cols = [], []
        for name, (dtype, items) in data.items():
            fields.append(T.Field(name, dtype))
            cols.append(HostColumn.from_list(dtype, items))
        return HostBatch(T.Schema(fields), cols)

    def to_pydict(self) -> Dict[str, List[Any]]:
        return {
            f.name: c.to_list() for f, c in zip(self.schema.fields, self.columns)
        }

    def column(self, name: str) -> HostColumn:
        return self.columns[self.schema.index_of(name)]

    def slice(self, start: int, length: int) -> "HostBatch":
        cols = [
            HostColumn(c.dtype, c.values[start : start + length],
                       c.validity[start : start + length], c.dictionary)
            for c in self.columns
        ]
        return HostBatch(self.schema, cols)

    @staticmethod
    def concat(batches: Sequence["HostBatch"]) -> "HostBatch":
        assert batches
        schema = batches[0].schema
        cols = []
        for i, f in enumerate(schema.fields):
            # dictionary-encoded parts decode first: dictionaries differ
            # per source chunk, so the concatenated column is plain
            parts = [b.columns[i].decoded() for b in batches]
            values = np.concatenate([p.values for p in parts])
            validity = np.concatenate([p.validity for p in parts])
            cols.append(HostColumn(f.dtype, values, validity))
        return HostBatch(schema, cols)

    def __repr__(self):
        return f"HostBatch({self.schema}, rows={self.num_rows})"


# --------------------------------------------------------------------------
# Device column
# --------------------------------------------------------------------------


class DeviceColumn:
    """One column staged in HBM: data buffer + validity mask (+ offsets).

    Dictionary-encoded strings (scan v2, docs/io.md) additionally carry
    ``codes`` — int32[cap] indices into the dictionary entries that
    data/offsets then describe — plus the static ``mat_byte_cap``: the
    byte-capacity bucket the column occupies once materialized
    (``kernels.layout.dict_decode_column``).  An encoded column stays
    encoded from scan staging through every operator that only moves or
    drops whole rows and says so — a filter's compaction, the dict-aware
    shuffle, the cache — until an operator consumes it: every exec
    materializes at entry (``DevVal.from_column``, ``ensure_row_layout``,
    ``device_to_host``) unless it is explicitly encode-aware (string
    equality and ``LIKE``, group keys, dict-key joins, ``Count``).  Codes
    name entries of THIS column's dictionary only; dictionaries differ
    batch to batch.
    """

    def __init__(self, dtype: T.DataType, data, validity, offsets=None,
                 codes=None, mat_byte_cap: int = 0):
        self.dtype = dtype
        self.data = data
        self.validity = validity
        self.offsets = offsets  # strings only: int32[cap+1]
        self.codes = codes  # dict-encoded strings only: int32[cap]
        self.mat_byte_cap = int(mat_byte_cap)

    @property
    def is_string(self) -> bool:
        return self.dtype.is_string

    @property
    def is_varlen(self) -> bool:
        """Strings and arrays: flat element buffer + offsets."""
        return self.offsets is not None

    @property
    def is_dict(self) -> bool:
        """Dictionary-encoded string column (codes + dictionary buffers)."""
        return self.codes is not None

    def tree_flatten(self):
        if self.codes is not None:
            return ((self.data, self.validity, self.offsets, self.codes),
                    (self.dtype, True, True, self.mat_byte_cap))
        if self.offsets is None:
            return (self.data, self.validity), (self.dtype, False, False, 0)
        return ((self.data, self.validity, self.offsets),
                (self.dtype, True, False, 0))

    @classmethod
    def tree_unflatten(cls, aux, children):
        dtype, has_offsets, has_codes, mat_byte_cap = aux
        if has_codes:
            data, validity, offsets, codes = children
            return cls(dtype, data, validity, offsets, codes, mat_byte_cap)
        if has_offsets:
            data, validity, offsets = children
            return cls(dtype, data, validity, offsets)
        data, validity = children
        return cls(dtype, data, validity, None)

    def __repr__(self):
        shape = getattr(self.data, "shape", None)
        enc = ", dict" if self.codes is not None else ""
        return f"DeviceColumn({self.dtype}, data={shape}{enc})"


jax.tree_util.register_pytree_node(
    DeviceColumn, DeviceColumn.tree_flatten, DeviceColumn.tree_unflatten
)


class ColumnBatch:
    """A device table: columns + dynamic valid-row count + static capacity."""

    def __init__(self, schema: T.Schema, columns: Sequence[DeviceColumn], num_rows,
                 capacity: int):
        self.schema = schema
        self.columns = tuple(columns)
        self.num_rows = num_rows  # int32 scalar (device array inside jit)
        self.capacity = int(capacity)

    def column(self, name: str) -> DeviceColumn:
        return self.columns[self.schema.index_of(name)]

    @property
    def row_mask(self):
        """bool[cap]: True for rows < num_rows (the live rows)."""
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.num_rows

    def with_columns(self, schema: T.Schema, columns: Sequence[DeviceColumn]
                     ) -> "ColumnBatch":
        return ColumnBatch(schema, columns, self.num_rows, self.capacity)

    def tree_flatten(self):
        return (tuple(self.columns), self.num_rows), (self.schema, self.capacity)

    @classmethod
    def tree_unflatten(cls, aux, children):
        schema, capacity = aux
        columns, num_rows = children
        return cls(schema, columns, num_rows, capacity)

    def __repr__(self):
        return f"ColumnBatch({self.schema}, cap={self.capacity})"

    def host_num_rows(self) -> int:
        from spark_rapids_tpu.utils.tracing import device_read
        return int(device_read("num_rows", self.num_rows))


jax.tree_util.register_pytree_node(
    ColumnBatch, ColumnBatch.tree_flatten, ColumnBatch.tree_unflatten
)


# --------------------------------------------------------------------------
# Host <-> device staging (the H2D/D2H copy layer; reference: GpuColumnVector
# host builders + copy, GpuColumnVector.java:41-130)
# --------------------------------------------------------------------------


def _string_host_to_buffers(values: np.ndarray, validity: np.ndarray,
                            byte_capacity: Optional[int] = None
                            ) -> Tuple[np.ndarray, np.ndarray]:
    """Encode an object array of strings to (offsets int32[n+1], bytes uint8)."""
    encoded = [
        (v if isinstance(v, bytes) else str(v).encode("utf-8")) if ok else b""
        for v, ok in zip(values, validity)
    ]
    lengths = np.fromiter((len(e) for e in encoded), dtype=np.int64,
                          count=len(encoded))
    offsets = np.zeros(len(encoded) + 1, dtype=np.int32)
    np.cumsum(lengths, out=offsets[1:])
    total = int(offsets[-1])
    cap = byte_capacity if byte_capacity is not None else round_up_capacity(
        max(total, 1), minimum=16)
    data = np.zeros(cap, dtype=np.uint8)
    if total:
        data[:total] = np.frombuffer(b"".join(encoded), dtype=np.uint8)
    return offsets, data


def _array_host_to_buffers(dtype: T.ArrayType, values: np.ndarray,
                           validity: np.ndarray
                           ) -> Tuple[np.ndarray, np.ndarray]:
    """Encode an object array of lists to (offsets int32[n+1], flat elems)
    — the same varlen layout strings use (strings ARE array<byte>)."""
    lists = [list(v) if ok else [] for v, ok in zip(values, validity)]
    if any(e is None for x in lists for e in x):
        raise NotImplementedError(
            "array element-level NULLs are host-only in the v1 nested "
            "envelope; keep such columns on the CPU path (see "
            "docs/compatibility.md)")
    lengths = np.fromiter((len(x) for x in lists), dtype=np.int64,
                          count=len(lists))
    offsets = np.zeros(len(lists) + 1, dtype=np.int32)
    np.cumsum(lengths, out=offsets[1:])
    total = int(offsets[-1])
    # shared varlen bucket floor (strings and arrays ride one policy so a
    # mixed suite compiles one ladder of element capacities, not two)
    cap = BUCKETS.elems(total)
    data = np.zeros(cap, dtype=dtype.element.np_dtype)
    if total:
        flat = [e for x in lists for e in x]
        data[:total] = np.asarray(flat, dtype=dtype.element.np_dtype)
    return offsets, data


def _pack_column(col: HostColumn, capacity: int) -> DeviceColumn:
    """The host's half of staging a column: its buffers packed at
    ``capacity`` as numpy arrays (validity, strings' offsets and bytes,
    dictionary codes), in the layout :func:`_put_column` copies."""
    n = len(col)
    assert capacity >= n
    validity = np.zeros(capacity, dtype=np.bool_)
    validity[:n] = col.validity
    if col.dictionary is not None and col.dtype.is_string:
        # dictionary-encoded staging: ship int32 codes plus the (small)
        # dictionary buffers instead of per-row string bytes
        entries = col.dictionary
        nd = max(len(entries), 1)
        ent_valid = np.ones(len(entries), dtype=np.bool_)
        d_offsets, d_data = _string_host_to_buffers(entries, ent_valid)
        dcap = round_up_capacity(nd)
        full_d_off = np.full(dcap + 1, d_offsets[-1], dtype=np.int32)
        full_d_off[: len(entries) + 1] = d_offsets
        raw = np.asarray(col.values, dtype=np.int64)
        safe = np.where(col.validity, np.clip(raw, 0, nd - 1), 0)
        codes = np.zeros(capacity, dtype=np.int32)
        codes[:n] = safe
        ent_lens = (d_offsets[1:] - d_offsets[:-1]).astype(np.int64)
        mat_total = int(ent_lens[safe[col.validity]].sum()) \
            if len(entries) and n else 0
        return DeviceColumn(col.dtype, d_data, validity, full_d_off, codes,
                            BUCKETS.elems(mat_total))
    if col.dtype.is_string or col.dtype.is_array:
        if col.dtype.is_string:
            offsets, data = _string_host_to_buffers(col.values, col.validity)
        else:
            offsets, data = _array_host_to_buffers(col.dtype, col.values,
                                                   col.validity)
        full_offsets = np.full(capacity + 1, offsets[-1], dtype=np.int32)
        full_offsets[: n + 1] = offsets
        return DeviceColumn(col.dtype, data, validity, full_offsets)
    data = np.zeros(capacity, dtype=col.dtype.np_dtype)
    data[:n] = col.values
    return DeviceColumn(col.dtype, data, validity, None)


def _put_column(packed: DeviceColumn, device=None) -> DeviceColumn:
    """The copy's half: every buffer of a :func:`_pack_column` result
    handed to ``jax.device_put``."""
    def put(x):
        return None if x is None else jax.device_put(x, device)
    return DeviceColumn(packed.dtype, put(packed.data),
                        put(packed.validity), put(packed.offsets),
                        put(packed.codes), packed.mat_byte_cap)


def host_to_device(batch: HostBatch, capacity: Optional[int] = None,
                   device=None) -> ColumnBatch:
    """Stage ``batch`` on the device, a column at a time: the host packs
    the column (``srt/pack/column``), then its buffers are copied
    (``srt/h2d/transfer``, the ``device_put`` calls), so the runtime's
    copy of one column runs while the host packs the next.
    ``device_put`` is asynchronous on a TPU, so a copy's span is its
    enqueue side; the df.cache() staging path, where nothing overlaps
    it, ends its own ``h2d`` span in a sync (ops.tpu_exec.
    TpuCachedScanExec)."""
    from spark_rapids_tpu.fault import inject
    from spark_rapids_tpu.utils.compile_registry import record_transfer
    from spark_rapids_tpu.utils.tracing import span
    inject.maybe_fire("h2d")
    cap = capacity if capacity is not None \
        else round_up_capacity(batch.num_rows)
    cols, nbytes = [], 0
    for c in batch.columns:
        with span("pack", "column"):
            packed = _pack_column(c, cap)
        with span("h2d", "transfer") as sp:
            cols.append(_put_column(packed, device))
            sp.set(bytes=sum(leaf.nbytes for leaf in
                             jax.tree_util.tree_leaves(cols[-1])))
        nbytes += sp.payload["bytes"]
    with span("h2d", "transfer") as sp:
        num_rows = jnp.asarray(batch.num_rows, dtype=jnp.int32)
        if device is not None:
            num_rows = jax.device_put(num_rows, device)
        sp.set(bytes=num_rows.nbytes)
    record_transfer("h2d", nbytes + num_rows.nbytes)
    return ColumnBatch(batch.schema, cols, num_rows, cap)


def device_to_host_many(batches: Sequence[ColumnBatch],
                        keep_dictionary: bool = False) -> List[HostBatch]:
    return device_to_host_with(batches, (), keep_dictionary)[0]


def device_to_host_with(batches: Sequence[ColumnBatch], riders,
                        keep_dictionary: bool = False):
    """(host batches, ``riders`` on the host): ``riders`` is a pytree of
    small device values (a stage's flags) that come home in the batches'
    own transfer — no wait and no round trip of their own."""
    # ONE bulk round trip for all batches' buffers AND num_rows scalars:
    # every leaf's copy is started with copy_to_host_async (as
    # jax.device_get does) before anything blocks, so the whole pytree
    # rides a single sync.  Per-column gets serialize one round trip
    # each, which dominated query wall time.
    # The wait is split by cause: ``device_wait`` ends when the programs
    # that produce the buffers have finished (block_until_ready — it
    # would block there anyway), ``d2h`` times what is left of the copy.
    from spark_rapids_tpu.fault import inject
    from spark_rapids_tpu.utils.compile_registry import (
        guard_check, record_transfer,
    )
    from spark_rapids_tpu.utils.tracing import device_wait, span
    inject.maybe_fire("d2h")
    with span("d2h", "start"):
        guard_check(list(batches), "device_to_host_many")
        tree = [
            (b.num_rows,
             [(c.data, c.validity, c.offsets, c.codes)
              if c.codes is not None
              else (c.data, c.validity, c.offsets) if c.offsets is not None
              else (c.data, c.validity) for c in b.columns])
            for b in batches]
        for leaf in jax.tree_util.tree_leaves((tree, riders)):
            start = getattr(leaf, "copy_to_host_async", None)
            if start is not None:
                start()
    device_wait("d2h_ready", (tree, riders))
    with span("d2h", "transfer") as sp:
        host, riders = jax.device_get((tree, riders))
        nbytes = sum(
            buf.nbytes
            for _num_rows, col_bufs in host
            for bufs in col_bufs for buf in bufs)
        sp.set(bytes=nbytes)
        record_transfer("d2h", nbytes)
    with span("result", "assemble"):
        return _host_batches(batches, host, keep_dictionary), riders


def _host_batches(batches: Sequence[ColumnBatch], host,
                  keep_dictionary: bool) -> List[HostBatch]:
    """HostBatches from the fetched buffers (padding trimmed, strings and
    arrays decoded into Python objects)."""
    out = []
    for batch, (num_rows, col_bufs) in zip(batches, host):
        n = int(num_rows)
        out_cols = []
        for f, bufs in zip(batch.schema.fields, col_bufs):
            validity = np.asarray(bufs[1])[:n]
            if f.dtype.is_string and len(bufs) == 4:
                # dictionary-encoded: decode the (small) dictionary once,
                # then fan the per-row codes out through it.  Collection
                # D2H always returns plain values; ``keep_dictionary``
                # (spill tier transitions) keeps (codes, entries) so an
                # encoded piece survives spill/unspill encoded.
                d_off = np.asarray(bufs[2])
                raw = np.asarray(bufs[0]).tobytes()
                codes = np.asarray(bufs[3])[:n]
                nd = int(codes.max()) + 1 if n else 0
                entries = [raw[d_off[i]:d_off[i + 1]].decode(
                    "utf-8", errors="replace") for i in range(nd)]
                if keep_dictionary:
                    ents = np.array(entries or [""], dtype=object)
                    out_cols.append(HostColumn(
                        f.dtype, codes.astype(np.int64), validity, ents))
                    continue
                values = np.empty(n, dtype=object)
                for i in range(n):
                    values[i] = entries[codes[i]] if validity[i] else ""
                out_cols.append(HostColumn(f.dtype, values, validity))
            elif f.dtype.is_string:
                # one bytes() copy + per-row slicing of it: slicing a bytes
                # object is a cheap memcpy, vs. the per-row ndarray slice +
                # bytes() pair this replaced (2 object allocs + dtype
                # machinery per row)
                offsets = np.asarray(bufs[2])
                raw = np.asarray(bufs[0]).tobytes()
                values = np.empty(n, dtype=object)
                for i in range(n):
                    values[i] = raw[offsets[i]:offsets[i + 1]].decode(
                        "utf-8", errors="replace")
                out_cols.append(HostColumn(f.dtype, values, validity))
            elif f.dtype.is_array:
                data = np.asarray(bufs[0])
                offsets = np.asarray(bufs[2])
                values = np.empty(n, dtype=object)
                if n:
                    # one vectorized split at the live offsets instead of
                    # n fancy-indexed copies
                    for i, seg in enumerate(np.split(
                            data[:offsets[n]], offsets[1:n])):
                        values[i] = list(seg)
                out_cols.append(HostColumn(f.dtype, values, validity))
            else:
                data = np.asarray(bufs[0])[:n]
                out_cols.append(HostColumn(f.dtype, data, validity))
        out.append(HostBatch(batch.schema, out_cols))
    return out


def device_to_host(batch: ColumnBatch,
                   keep_dictionary: bool = False) -> HostBatch:
    return device_to_host_many([batch], keep_dictionary=keep_dictionary)[0]


def host_batch_bytes(hb: HostBatch) -> int:
    """Host bytes a :class:`HostBatch` occupies (spill-catalog host-tier
    accounting).  Computed ONCE per tier transition and cached on the
    handle — string columns hold python objects, so sizing them walks
    every value and must never sit on a per-call budget path."""
    total = 0
    for c in hb.columns:
        if c.dictionary is not None:
            total += c.values.nbytes + len(c.dictionary) + sum(
                len(str(x)) for x in c.dictionary)
        elif c.dtype.is_string:
            total += sum(len(str(x)) for x in c.values) + len(c.values)
        else:
            total += c.values.nbytes
        total += c.validity.nbytes
    return total


def host_sizes(batches: Sequence[ColumnBatch]) -> List[Tuple[int, List[int]]]:
    """Fetch (num_rows, [string byte totals...]) for many batches in ONE
    blocking transfer (one round trip instead of one per scalar).

    String byte totals read ``offsets[-1]`` — valid because offsets are
    constant past num_rows by construction.
    """
    from spark_rapids_tpu.utils.compile_registry import guard_check
    from spark_rapids_tpu.utils.tracing import current_op, span
    guard_check(list(batches), "host_sizes")

    def _varlen_total(c):
        if c.codes is not None:
            # Dictionary-encoded: report the MATERIALIZED byte total (what
            # any gather/concat consumer will hold after its row-layout
            # guard decodes the column), not the dictionary's size.
            ent_lens = (c.offsets[1:] - c.offsets[:-1]).astype(jnp.int32)
            nd = int(c.offsets.shape[0]) - 1
            codes_c = jnp.clip(c.codes, 0, max(nd - 1, 0))
            return jnp.sum(jnp.where(c.validity, ent_lens[codes_c], 0))
        return c.offsets[-1]

    # the scalars come out of programs still in flight: slicing them out
    # and this read-back are where the host waits for the chip (TPC-H Q1
    # on a v5e sat 28.2 s in the slicing and 2 ms in the read-back)
    with span("device_wait", "host_sizes", current_op()):
        scalars = [(b.num_rows,
                    [_varlen_total(c) for c in b.columns if c.is_varlen])
                   for b in batches]
        host = jax.device_get(scalars)
    return [(int(n), [int(t) for t in totals]) for n, totals in host]


def fixed_row_bytes(schema: T.Schema) -> int:
    """Estimated fixed-width bytes per row: data itemsize plus one validity
    byte per column; varlen columns contribute their 4-byte offset entry
    (element bytes are accounted separately from offsets[-1]).  This is the
    size estimate AQE uses for byte-based targets (the reference's
    map-status byte sizes)."""
    total = 0
    for f in schema.fields:
        dt = f.dtype
        if dt.is_string or dt.is_array:
            total += 5
        else:
            total += int(np.dtype(dt.np_dtype).itemsize) + 1
    return total


def varlen_byte_scales(schema: T.Schema) -> List[int]:
    """Per-varlen-column multiplier converting offsets[-1] element totals
    to bytes: 1 for strings (elements ARE bytes), element itemsize for
    arrays.  Order matches the varlen-column order host_sizes and
    gather_rows use."""
    out = []
    for f in schema.fields:
        if f.dtype.is_string:
            out.append(1)
        elif f.dtype.is_array:
            out.append(int(np.dtype(f.dtype.element.np_dtype).itemsize))
    return out


def colocate_batches(batches: Sequence[ColumnBatch]
                     ) -> Sequence[ColumnBatch]:
    """Move batches onto one device when they span several.

    After a device-resident mesh shuffle, each partition's batch lives on
    its own mesh device; a stage that merges several partitions into one
    program (global sort, final collect, broadcast build) must first gather
    them — a device-to-device transfer, never through the host.  No-op in
    the common single-device case."""
    devs = set()
    for b in batches:
        for leaf in jax.tree_util.tree_leaves(b):
            get_devs = getattr(leaf, "devices", None)
            if callable(get_devs):
                devs.update(get_devs())
    if len(devs) <= 1:
        return batches
    target = sorted(devs, key=lambda d: d.id)[0]
    return jax.device_put(list(batches), target)


def empty_device_batch(schema: T.Schema, capacity: int = MIN_CAPACITY) -> ColumnBatch:
    cols = []
    for f in schema.fields:
        validity = jnp.zeros(capacity, dtype=jnp.bool_)
        if f.dtype.is_string:
            cols.append(DeviceColumn(
                f.dtype,
                jnp.zeros(16, dtype=jnp.uint8),
                validity,
                jnp.zeros(capacity + 1, dtype=jnp.int32),
            ))
        else:
            cols.append(DeviceColumn(
                f.dtype, jnp.zeros(capacity, dtype=f.dtype.jnp_dtype), validity, None
            ))
    return ColumnBatch(schema, cols, jnp.asarray(0, dtype=jnp.int32), capacity)
