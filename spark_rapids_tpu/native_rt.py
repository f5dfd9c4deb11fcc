"""ctypes bindings for the native host runtime (native/batch_runtime.cc).

Builds the shared library on first use (g++ -O3 -shared) and caches it next
to the source.  Every entry point has a pure-python fallback so the engine
works even where a toolchain is unavailable — but the native path is the
default, mirroring how the reference's host runtime is native
(SURVEY.md section 2.9).
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import List, Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_HERE, "native", "batch_runtime.cc")
_SO = os.path.join(_HERE, "native", "libbatch_runtime.so")

_log = logging.getLogger(__name__)
_lock = threading.Lock()
_lib = None
_tried = False


def _build() -> Optional[str]:
    """Path of an up-to-date library, building it from ``_SRC`` if
    needed; None (python path) when it cannot be built — logged once,
    with the compiler's own stderr.  The compiler writes to a
    per-process temporary name and ``os.replace`` publishes it, so
    concurrent builders (six test workers) never load a half-written
    file."""
    if os.path.exists(_SO) and \
            os.path.getmtime(_SO) >= os.path.getmtime(_SRC):
        return _SO
    tmp = f"{_SO}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-o", tmp,
             _SRC],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        return _SO
    except (OSError, subprocess.SubprocessError) as e:
        stderr = getattr(e, "stderr", b"") or b""
        _log.warning(
            "native host runtime not built (%s: %s); taking the pure-"
            "python path.  Compiler stderr:\n%s", type(e).__name__, e,
            stderr.decode(errors="replace")[-2000:])
        if os.path.exists(tmp):
            os.remove(tmp)
        return None


def get_lib():
    """The loaded native library, or None (python fallback)."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        so = _build()
        if so is None:
            return None
        lib = ctypes.CDLL(so)
        u64 = ctypes.c_uint64
        p8 = ctypes.POINTER(ctypes.c_uint8)
        lib.batch_serialized_size.restype = u64
        lib.batch_serialize.restype = u64
        lib.batch_read_header.restype = ctypes.c_int32
        lib.batch_deserialize_index.restype = ctypes.c_int32
        lib.arena_create.restype = ctypes.c_void_p
        lib.arena_alloc.restype = ctypes.c_void_p
        lib.arena_alloc.argtypes = [ctypes.c_void_p, u64]
        lib.arena_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p, u64]
        lib.arena_destroy.argtypes = [ctypes.c_void_p]
        lib.arena_stats.argtypes = [ctypes.c_void_p, ctypes.POINTER(u64),
                                    ctypes.POINTER(u64), ctypes.POINTER(u64)]
        lib.lz_compress_bound.restype = u64
        lib.lz_compress_bound.argtypes = [u64]
        lib.lz_compress.restype = u64
        lib.lz_compress.argtypes = [p8, u64, p8, u64]
        lib.lz_decompress.restype = ctypes.c_int32
        lib.lz_decompress.argtypes = [p8, u64, p8, u64]
        _lib = lib
        return _lib


# ---------------------------------------------------------------------------
# Batch (de)serialization — JCudfSerialization analogue
# ---------------------------------------------------------------------------

_TYPE_CODES = {}
_CODE_TYPES = {}


def _codes():
    if _TYPE_CODES:
        return
    from spark_rapids_tpu import types as T
    for i, t in enumerate(T.ALL_TYPES):
        _TYPE_CODES[t] = i
        _CODE_TYPES[i] = t


def _col_buffers(col) -> Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]:
    """HostColumn -> (data bytes, validity bytes, offsets bytes|None)."""
    from spark_rapids_tpu import types as T
    if col.dtype.is_string:
        encoded = [
            (str(v).encode("utf-8") if ok else b"")
            for v, ok in zip(col.values, col.validity)
        ]
        lens = np.fromiter((len(e) for e in encoded), dtype=np.int64,
                           count=len(encoded))
        offsets = np.zeros(len(encoded) + 1, dtype=np.int32)
        offsets[1:] = np.cumsum(lens)
        data = np.frombuffer(b"".join(encoded), dtype=np.uint8).copy()
        return data, col.validity.astype(np.uint8), offsets
    return (np.ascontiguousarray(col.values).view(np.uint8),
            col.validity.astype(np.uint8), None)


def serialize_host_batch(hb) -> bytes:
    """HostBatch -> one contiguous framed buffer (native when available)."""
    _codes()
    cols = [(f.dtype, *_col_buffers(c))
            for f, c in zip(hb.schema.fields, hb.columns)]
    lib = get_lib()
    n = len(cols)
    type_codes = np.array([_TYPE_CODES[c[0]] for c in cols], dtype=np.uint8)
    datas = [np.ascontiguousarray(c[1]).view(np.uint8) for c in cols]
    valids = [np.ascontiguousarray(c[2]) for c in cols]
    offs = [None if c[3] is None else
            np.ascontiguousarray(c[3]).view(np.uint8) for c in cols]
    data_lens = np.array([d.nbytes for d in datas], dtype=np.uint64)
    valid_lens = np.array([v.nbytes for v in valids], dtype=np.uint64)
    off_lens = np.array([0 if o is None else o.nbytes for o in offs],
                        dtype=np.uint64)
    if lib is None:
        return _py_serialize(hb.num_rows, type_codes, datas, valids, offs)
    u64a = data_lens.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))
    size = lib.batch_serialized_size(
        n, u64a,
        valid_lens.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        off_lens.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)))
    out = np.zeros(int(size), dtype=np.uint8)
    PP = ctypes.POINTER(ctypes.c_uint8) * n
    dp = PP(*[d.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
              for d in datas])
    vp = PP(*[v.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
              for v in valids])
    zero = np.zeros(1, dtype=np.uint8)
    op = PP(*[(o if o is not None else zero).ctypes.data_as(
        ctypes.POINTER(ctypes.c_uint8)) for o in offs])
    wrote = lib.batch_serialize(
        n, ctypes.c_uint64(hb.num_rows),
        type_codes.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        dp, data_lens.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        vp, valid_lens.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        op, off_lens.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.c_uint64(out.nbytes))
    assert wrote, "native serialization failed"
    return out[:int(wrote)].tobytes()


def deserialize_host_batch(buf: bytes, schema):
    """Framed buffer -> HostBatch (zero-copy views into the buffer)."""
    _codes()
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.batch import HostBatch, HostColumn
    lib = get_lib()
    arr = np.frombuffer(buf, dtype=np.uint8)
    if lib is None:
        return _py_deserialize(arr, schema)
    n_cols = ctypes.c_int32()
    n_rows = ctypes.c_uint64()
    ok = lib.batch_read_header(
        arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.c_uint64(arr.nbytes), ctypes.byref(n_cols),
        ctypes.byref(n_rows))
    assert ok, "bad batch frame"
    n = n_cols.value
    u64arr = lambda: np.zeros(n, dtype=np.uint64)  # noqa: E731
    tc = np.zeros(n, dtype=np.uint8)
    d_off, d_len = u64arr(), u64arr()
    v_off, v_len = u64arr(), u64arr()
    o_off, o_len = u64arr(), u64arr()
    P64 = ctypes.POINTER(ctypes.c_uint64)
    ok = lib.batch_deserialize_index(
        arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.c_uint64(arr.nbytes),
        tc.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        d_off.ctypes.data_as(P64), d_len.ctypes.data_as(P64),
        v_off.ctypes.data_as(P64), v_len.ctypes.data_as(P64),
        o_off.ctypes.data_as(P64), o_len.ctypes.data_as(P64))
    assert ok, "corrupt batch frame"
    rows = int(n_rows.value)
    cols = []
    for i, f in enumerate(schema.fields):
        validity = arr[int(v_off[i]):int(v_off[i]) + int(v_len[i])] \
            .astype(bool)
        if f.dtype.is_string:
            offsets = arr[int(o_off[i]):int(o_off[i]) + int(o_len[i])] \
                .view(np.int32)
            data = arr[int(d_off[i]):int(d_off[i]) + int(d_len[i])]
            values = np.empty(rows, dtype=object)
            raw = data.tobytes()
            for r in range(rows):
                values[r] = raw[offsets[r]:offsets[r + 1]].decode(
                    "utf-8", errors="replace")
            cols.append(HostColumn(f.dtype, values, validity))
        else:
            data = arr[int(d_off[i]):int(d_off[i]) + int(d_len[i])] \
                .view(f.dtype.np_dtype)
            cols.append(HostColumn(f.dtype, data.copy(), validity))
    return HostBatch(schema, cols)


def _py_serialize(n_rows, type_codes, datas, valids, offs) -> bytes:
    import struct
    out = [struct.pack("<IIIQ", 0x54505542, 1, len(datas), n_rows)]
    pos = 20

    def pad(b):
        nonlocal pos
        extra = (-pos) % 8
        out.append(b"\0" * extra)
        pos += extra

    for i in range(len(datas)):
        d = datas[i].tobytes()
        v = valids[i].tobytes()
        o = b"" if offs[i] is None else offs[i].tobytes()
        out.append(struct.pack("<BBQQQ", int(type_codes[i]),
                               1 if o else 0, len(d), len(v), len(o)))
        pos += 26
        pad(b"")
        for b in (d, v, o):
            if b or True:
                out.append(b)
                pos += len(b)
                pad(b"")
    return b"".join(out)


def _py_deserialize(arr, schema):
    # mirror of the native index walk
    import struct
    from spark_rapids_tpu.batch import HostBatch, HostColumn
    buf = arr.tobytes()
    magic, version, n, n_rows = struct.unpack_from("<IIIQ", buf, 0)
    assert magic == 0x54505542
    pos = 20
    cols = []
    for i, f in enumerate(schema.fields):
        t, has_o, dl, vl, ol = struct.unpack_from("<BBQQQ", buf, pos)
        pos += 26
        pos += (-pos) % 8
        d = buf[pos:pos + dl]
        pos += dl + ((-dl) % 8)
        v = np.frombuffer(buf[pos:pos + vl], dtype=np.uint8).astype(bool)
        pos += vl + ((-vl) % 8)
        if ol:
            o = np.frombuffer(buf[pos:pos + ol], dtype=np.int32)
            pos += ol + ((-ol) % 8)
            values = np.empty(n_rows, dtype=object)
            for r in range(n_rows):
                values[r] = d[o[r]:o[r + 1]].decode("utf-8",
                                                    errors="replace")
            cols.append(HostColumn(f.dtype, values, v))
        else:
            cols.append(HostColumn(
                f.dtype, np.frombuffer(d, dtype=f.dtype.np_dtype).copy(), v))
    return HostBatch(schema, cols)


# ---------------------------------------------------------------------------
# Host staging arena — PinnedMemoryPool analogue
# ---------------------------------------------------------------------------


class ArenaBuffer:
    """A host staging buffer leased from the arena."""

    __slots__ = ("array", "ptr", "size")

    def __init__(self, array: np.ndarray, ptr: int, size: int):
        self.array = array
        self.ptr = ptr
        self.size = size


class HostArena:
    """Aligned recycling host allocator (native; python fallback)."""

    def __init__(self, pool_limit_bytes: int = 1 << 30):
        self._lib = get_lib()
        if self._lib is not None:
            self._arena = self._lib.arena_create(
                ctypes.c_uint64(pool_limit_bytes))
        else:
            self._arena = None

    def alloc(self, size: int) -> ArenaBuffer:
        if self._arena:
            ptr = self._lib.arena_alloc(self._arena, ctypes.c_uint64(size))
            assert ptr, "arena OOM"
            buf = (ctypes.c_uint8 * size).from_address(ptr)
            return ArenaBuffer(np.frombuffer(buf, dtype=np.uint8), ptr, size)
        return ArenaBuffer(np.zeros(size, dtype=np.uint8), 0, size)

    def free(self, b: ArenaBuffer):
        if self._arena and b.ptr:
            self._lib.arena_free(self._arena, ctypes.c_void_p(b.ptr),
                                 ctypes.c_uint64(b.size))
            b.ptr = 0

    def stats(self):
        if not self._arena:
            return {"allocated": 0, "pooled": 0, "high_water": 0}
        a = ctypes.c_uint64()
        p = ctypes.c_uint64()
        h = ctypes.c_uint64()
        self._lib.arena_stats(self._arena, ctypes.byref(a), ctypes.byref(p),
                              ctypes.byref(h))
        return {"allocated": a.value, "pooled": p.value,
                "high_water": h.value}

    def close(self):
        if self._arena:
            self._lib.arena_destroy(self._arena)
            self._arena = None


def lz_compress(data: bytes) -> Optional[bytes]:
    """Native LZ4-style block compression; None when the library is
    unavailable or the emit bound is exceeded (caller stores raw)."""
    lib = get_lib()
    if lib is None:
        return None
    import ctypes
    n = len(data)
    bound = lib.lz_compress_bound(n)
    out = ctypes.create_string_buffer(bound)
    # zero-copy view of the immutable bytes (the C side only reads src)
    src = ctypes.cast(ctypes.c_char_p(data or b"\x00"),
                      ctypes.POINTER(ctypes.c_uint8))
    written = lib.lz_compress(
        src, n, ctypes.cast(out, ctypes.POINTER(ctypes.c_uint8)), bound)
    if written == 0 and n > 0:
        return None
    return out.raw[:written]


def lz_decompress(data: bytes, out_size: int) -> Optional[bytes]:
    lib = get_lib()
    if lib is None:
        return None
    import ctypes
    n = len(data)
    out = ctypes.create_string_buffer(max(out_size, 1))
    src = ctypes.cast(ctypes.c_char_p(data or b"\x00"),
                      ctypes.POINTER(ctypes.c_uint8))
    rc = lib.lz_decompress(
        src, n, ctypes.cast(out, ctypes.POINTER(ctypes.c_uint8)),
        out_size)
    if rc != 0:
        raise ValueError("corrupt nativelz stream")
    return out.raw[:out_size]
