"""String expressions on the TPU (reference: stringFunctions.scala, 862 LoC).

Device layout is cudf-style: ``offsets`` int32[cap+1] into a flat uint8 byte
buffer.  Every kernel below is built from three vectorizable primitives that
XLA lowers well:

* ``rows_of_positions`` — map each byte position to its owning row
  (one ``searchsorted`` over the offsets), turning per-row varlen work into
  flat elementwise work over the byte buffer;
* prefix sums (``cumsum``) to build output offsets from per-row lengths;
* gathers with clamped indices to materialize output bytes.

Row equality/grouping uses dual 64-bit polynomial hashes computed with a
weighted segment-sum over the byte buffer — O(byte_cap) work, no per-row
loops, no dynamic shapes.

Case mapping is ASCII-only (flagged incompat, like the reference's
string incompatibilities).  Patterns (needles) must be literals for device
execution; anything else falls back to CPU via the planner.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exprs.base import (
    CpuVal, DevVal, Expression, Literal, UnaryExpression,
)
from spark_rapids_tpu.utils.tracing import kernel_scope

# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------


def string_lengths(v: DevVal):
    if v.codes is not None:
        # Dictionary-encoded: offsets describe the ENTRIES; gather per-row
        # lengths through the codes (invalid rows are length-0, matching the
        # materialized layout).
        ent_lens = (v.offsets[1:] - v.offsets[:-1]).astype(jnp.int32)
        nd = int(v.offsets.shape[0]) - 1
        codes_c = jnp.clip(v.codes, 0, max(nd - 1, 0))
        return jnp.where(v.validity, ent_lens[codes_c], 0).astype(jnp.int32)
    return (v.offsets[1:] - v.offsets[:-1]).astype(jnp.int32)


def rows_of_positions(offsets, nbytes: int):
    """int32[nbytes]: owning row of each byte position (cap for padding)."""
    pos = jnp.arange(nbytes, dtype=jnp.int32)
    return jnp.searchsorted(offsets[1:], pos, side="right").astype(jnp.int32)


_HASH_BASES = (31, 131)


def _pow_table(base: int, n: int):
    """uint32 modular polynomial powers base^k (mod 2^32) for k in [0, n].

    Closed form via binary exponentiation: 32 elementwise multiplies
    selected by k's bits, with base^(2^j) precomputed in python.  A
    ``cumprod`` scan here compiles pathologically on TPU at byte-buffer
    sizes (the scan lowering, same family as the f64 cumsum blowup);
    the bit form is pure elementwise work.
    """
    k = jnp.arange(n + 1, dtype=jnp.uint32)
    out = jnp.ones(n + 1, dtype=jnp.uint32)
    sq = base % (1 << 32)
    for j in range(max(n, 1).bit_length()):
        bit = (k >> jnp.uint32(j)) & jnp.uint32(1)
        out = out * jnp.where(bit == 1, jnp.uint32(sq), jnp.uint32(1))
        sq = (sq * sq) % (1 << 32)
    return out


@kernel_scope
def string_hash2(v: DevVal) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dual 32-bit polynomial row hashes: h = sum byte[i] * base^(end-1-i)
    (mod 2^32).  Equality tests combine both hashes + length (+ the 64-byte
    sort prefix where exactness matters)."""
    if v.codes is not None:
        # Dictionary-encoded: hash each ENTRY once (O(dict bytes), not
        # O(row bytes)) and gather per-row hashes through the codes.
        # Invalid rows take the empty-string hash (0), exactly as the
        # materialized layout hashes its length-0 rows.
        nd_cap = int(v.offsets.shape[0]) - 1
        ent = DevVal(v.dtype, v.data,
                     jnp.ones(nd_cap, dtype=jnp.bool_), v.offsets)
        e1, e2 = string_hash2(ent)
        codes_c = jnp.clip(v.codes, 0, max(nd_cap - 1, 0))
        h1 = jnp.where(v.validity, e1[codes_c], jnp.uint32(0))
        h2 = jnp.where(v.validity, e2[codes_c], jnp.uint32(0))
        return h1, h2
    cap = v.capacity
    nbytes = int(v.data.shape[0])

    def xla():
        rows = rows_of_positions(v.offsets, nbytes)
        rows_c = jnp.clip(rows, 0, cap - 1)
        ends = v.offsets[rows_c + 1].astype(jnp.int32)
        pos = jnp.arange(nbytes, dtype=jnp.int32)
        in_data = pos < v.offsets[-1].astype(jnp.int32)
        exp = jnp.clip(ends - 1 - pos, 0, nbytes).astype(jnp.int32)
        byte = jnp.where(in_data, v.data, 0).astype(jnp.uint32)
        out = []
        for base in _HASH_BASES:
            pows = _pow_table(base, nbytes)
            contrib = byte * pows[exp]
            h = jax.ops.segment_sum(jnp.where(in_data, contrib, 0), rows_c,
                                    num_segments=cap,
                                    indices_are_sorted=True)
            # Mix in length so "" vs padding rows differ and lengths
            # disambiguate.
            h = h + string_lengths(v).astype(jnp.uint32) * \
                jnp.uint32(0x9E3779B9)
            out.append(h.astype(jnp.uint32))
        return out[0], out[1]

    if nbytes < 1 or cap < 1:
        return xla()
    # kernel tier: Horner over each row's byte window (bit-identical —
    # uint32 arithmetic is exact mod 2^32 in any association)
    from spark_rapids_tpu.kernels import pallas_tier as PT
    return PT.run(
        "stringHash",
        lambda interpret: PT.string_hash_rows(
            v.data, v.offsets, cap, _HASH_BASES, interpret=interpret),
        xla, resident_bytes=nbytes + 4 * (cap + 1))


def hash_literal2(s: str) -> Tuple[int, int]:
    raw = s.encode("utf-8")
    out = []
    for base in _HASH_BASES:
        h = 0
        for b in raw:
            h = (h * base + b) % (1 << 32)
        h = (h + len(raw) * 0x9E3779B9) % (1 << 32)
        out.append(h)
    return out[0], out[1]


class _DictLiteralCompares:
    """The stage-notes entry (``plan/pipeline.note_stage_batches``) of a
    test against literals that :func:`equals_any_literal` decided on a
    column's dictionary: every dispatch that stands counts it again
    (``dictLiteralCompares``)."""

    name = "dictLiteralCompares"

    def stage_ran(self, ctx, batches: int, speculated: bool) -> None:
        ctx.metric("exprs", self.name).add(batches)


DICT_LITERAL_COMPARES = _DictLiteralCompares()


@kernel_scope
def equals_any_literal(v: DevVal, lits: Tuple[str, ...]):
    """bool[cap]: the row of ``v`` equals one of the Python strings
    ``lits``, by its bytes; the row's validity is the caller's.  No literal
    is evaluated on the device.

    A dictionary-encoded ``v`` is decided on its ENTRIES: each is compared
    byte by byte with each literal's UTF-8 bytes (O(entries x literal
    bytes)), and the answer is gathered through the codes — no row's bytes
    are read and nothing is hashed.  A plain ``v`` is hashed once
    (:func:`string_hash2`) and its hashes and lengths are compared with
    :func:`hash_literal2`'s, computed on the host: the test a literal
    broadcast to the batch would get, without the broadcast."""
    lits = tuple(dict.fromkeys(lits))
    if v.codes is not None:
        from spark_rapids_tpu.plan.pipeline import note_stage_batches
        starts = v.offsets[:-1].astype(jnp.int32)
        ent_lens = (v.offsets[1:] - starts).astype(jnp.int32)
        nbytes = int(v.data.shape[0])
        hit = jnp.zeros(ent_lens.shape[0], dtype=jnp.bool_)
        for raw in (s.encode("utf-8") for s in lits):
            m = ent_lens == len(raw)
            if nbytes:      # else every entry is empty: the lengths decide
                for k, b in enumerate(raw):
                    idx = jnp.clip(starts + k, 0, nbytes - 1)
                    m = m & (v.data[idx] == jnp.uint8(b))
            hit = hit | m
        codes_c = jnp.clip(v.codes, 0, max(int(hit.shape[0]) - 1, 0))
        note_stage_batches(DICT_LITERAL_COMPARES, 1)
        return hit[codes_c]
    h1, h2 = string_hash2(v)
    lens = string_lengths(v)
    out = jnp.zeros(v.capacity, dtype=jnp.bool_)
    for s in lits:
        e1, e2 = hash_literal2(s)
        out = out | ((h1 == jnp.uint32(e1)) & (h2 == jnp.uint32(e2))
                     & (lens == len(s.encode("utf-8"))))
    return out


@kernel_scope
def build_string(dtype, new_lens, src_index_fn, out_byte_cap: int,
                 validity) -> DevVal:
    """Materialize a string column from per-row output lengths.

    ``src_index_fn(row, pos_in_row)`` returns the source byte index for each
    output byte (vectorized over flat arrays).
    """
    cap = int(new_lens.shape[0])
    new_lens = new_lens.astype(jnp.int32)
    offsets = jnp.concatenate([
        jnp.zeros(1, dtype=jnp.int32), jnp.cumsum(new_lens).astype(jnp.int32)
    ])
    rows = rows_of_positions(offsets, out_byte_cap)
    rows_c = jnp.clip(rows, 0, cap - 1)
    pos_in_row = jnp.arange(out_byte_cap, dtype=jnp.int32) - offsets[rows_c]
    live = jnp.arange(out_byte_cap, dtype=jnp.int32) < offsets[-1]
    data = src_index_fn(rows_c, pos_in_row)
    data = jnp.where(live, data, 0).astype(jnp.uint8)
    return DevVal(dtype, data, validity, offsets)


@kernel_scope
def _gather_substring(v: DevVal, starts, new_lens, out_byte_cap: int,
                      validity) -> DevVal:
    """Common shape: every output row is a contiguous slice of its input row."""
    src_base = v.offsets[:-1] + starts.astype(jnp.int32)
    nbytes = int(v.data.shape[0])

    def src(rows, pos):
        idx = jnp.clip(src_base[rows] + pos, 0, nbytes - 1)
        return v.data[idx]

    return build_string(T.STRING, new_lens, src, out_byte_cap, validity)


@kernel_scope
def _find_matches(v: DevVal, needle: bytes):
    """bool[nbytes]: needle match beginning at each byte position, fully
    inside the owning row."""
    nbytes = int(v.data.shape[0])
    L = len(needle)
    if L == 0:
        return jnp.ones(nbytes, dtype=jnp.bool_)
    cap = v.capacity
    rows = rows_of_positions(v.offsets, nbytes)
    rows_c = jnp.clip(rows, 0, cap - 1)
    ends = v.offsets[rows_c + 1]
    pos = jnp.arange(nbytes, dtype=jnp.int32)
    ok = (pos + L) <= ends
    match = ok
    for k, b in enumerate(needle):
        idx = jnp.clip(pos + k, 0, nbytes - 1)
        match = match & (v.data[idx] == np.uint8(b))
    return match


def _rows_with_match(v: DevVal, needle: bytes):
    cap = v.capacity

    def xla():
        match = _find_matches(v, needle)
        nbytes = int(v.data.shape[0])
        rows = jnp.clip(rows_of_positions(v.offsets, nbytes), 0, cap - 1)
        counts = jax.ops.segment_sum(match.astype(jnp.int32), rows,
                                     num_segments=cap,
                                     indices_are_sorted=True)
        return counts > 0

    if len(needle) == 0:
        return jnp.ones(cap, dtype=jnp.bool_)
    # Pallas one-pass scan through the kernel tier (the reference's
    # dedicated contains kernel role): conf-gated, TPU-or-interpret
    # backend predicate, XLA formulation as the automatic fallback.
    from spark_rapids_tpu.kernels import pallas_strings as PS
    from spark_rapids_tpu.kernels import pallas_tier as PT
    return PT.run(
        "strings",
        lambda interpret: PS.rows_with_match(
            v.data, v.offsets, v.validity, cap, needle,
            interpret=interpret),
        xla)


def _literal_needle(expr: Expression) -> Optional[str]:
    if isinstance(expr, Literal) and expr.value is not None:
        return str(expr.value)
    return None


# ---------------------------------------------------------------------------
# Expressions
# ---------------------------------------------------------------------------


class Length(UnaryExpression):
    def _resolve_type(self):
        self.dtype = T.INT
        self.nullable = self.child.nullable

    def tpu_eval(self, ctx) -> DevVal:
        v = self.child.tpu_eval(ctx)
        # NOTE: byte length == char length only for ASCII; Spark counts chars.
        return DevVal(T.INT, string_lengths(v), v.validity)

    def cpu_eval(self, ctx) -> CpuVal:
        v = self.child.cpu_eval(ctx)
        data = np.fromiter((len(str(s)) for s in v.values), dtype=np.int32,
                           count=len(v.values))
        return CpuVal(T.INT, data, v.validity)


class _CaseMap(UnaryExpression):
    _delta = 0

    def _resolve_type(self):
        self.dtype = T.STRING
        self.nullable = self.child.nullable

    def _map_dev(self, data):
        raise NotImplementedError

    def _map_cpu(self, s: str) -> str:
        raise NotImplementedError

    def tpu_eval(self, ctx) -> DevVal:
        v = self.child.tpu_eval(ctx)
        return DevVal(T.STRING, self._map_dev(v.data), v.validity, v.offsets)

    def cpu_eval(self, ctx) -> CpuVal:
        v = self.child.cpu_eval(ctx)
        out = np.array([self._map_cpu(str(s)) for s in v.values], dtype=object)
        return CpuVal(T.STRING, out, v.validity)


class Upper(_CaseMap):
    def _map_dev(self, data):
        is_lower = (data >= 97) & (data <= 122)
        return jnp.where(is_lower, data - 32, data).astype(jnp.uint8)

    def _map_cpu(self, s):
        return "".join(c.upper() if "a" <= c <= "z" else c for c in s)


class Lower(_CaseMap):
    def _map_dev(self, data):
        is_upper = (data >= 65) & (data <= 90)
        return jnp.where(is_upper, data + 32, data).astype(jnp.uint8)

    def _map_cpu(self, s):
        return "".join(c.lower() if "A" <= c <= "Z" else c for c in s)


def _substr_bounds(length, pos: int, sublen: Optional[int], xp):
    """Spark substring semantics (UTF8String.substringSQL): 1-based pos,
    negative counts from end; the length window is measured from the raw
    (possibly negative) start before clamping."""
    if pos > 0:
        start_raw = xp.full_like(length, pos - 1)
    elif pos == 0:
        start_raw = xp.zeros_like(length)
    else:
        start_raw = length + pos
    end_raw = length if sublen is None else start_raw + max(sublen, 0)
    start = xp.clip(start_raw, 0, length)
    end = xp.clip(end_raw, 0, length)
    n = xp.maximum(end - start, 0)
    return start.astype(xp.int32), n.astype(xp.int32)


class Substring(UnaryExpression):
    def __init__(self, child: Expression, pos: int, length: Optional[int] = None):
        self.pos = int(pos)
        self.sublen = None if length is None else int(length)
        super().__init__(child)

    def with_children(self, children):
        return Substring(children[0], self.pos, self.sublen)

    def _resolve_type(self):
        self.dtype = T.STRING
        self.nullable = self.child.nullable

    def tpu_eval(self, ctx) -> DevVal:
        v = self.child.tpu_eval(ctx)
        lens = string_lengths(v)
        start, n = _substr_bounds(lens, self.pos, self.sublen, jnp)
        n = jnp.where(v.validity & ctx.row_mask, n, 0)
        return _gather_substring(v, start, n, int(v.data.shape[0]), v.validity)

    def cpu_eval(self, ctx) -> CpuVal:
        v = self.child.cpu_eval(ctx)
        out = np.empty(len(v.values), dtype=object)
        for i, s in enumerate(v.values):
            s = str(s)
            L = len(s)
            if self.pos > 0:
                start_raw = self.pos - 1
            elif self.pos == 0:
                start_raw = 0
            else:
                start_raw = L + self.pos
            end_raw = L if self.sublen is None else start_raw + max(self.sublen, 0)
            start = min(max(start_raw, 0), L)
            end = min(max(end_raw, 0), L)
            out[i] = s[start:end]
        return CpuVal(T.STRING, out, v.validity)


class _Trim(UnaryExpression):
    _left = True
    _right = True

    def _resolve_type(self):
        self.dtype = T.STRING
        self.nullable = self.child.nullable

    def tpu_eval(self, ctx) -> DevVal:
        v = self.child.tpu_eval(ctx)
        cap = v.capacity
        nbytes = int(v.data.shape[0])
        lens = string_lengths(v)
        rows = jnp.clip(rows_of_positions(v.offsets, nbytes), 0, cap - 1)
        pos_in_row = jnp.arange(nbytes, dtype=jnp.int32) - v.offsets[rows]
        in_data = jnp.arange(nbytes, dtype=jnp.int32) < v.offsets[-1]
        is_space = (v.data == 32) & in_data
        big = jnp.int32(nbytes + 1)
        if self._left:
            first_ns = jax.ops.segment_min(
                jnp.where(~is_space & in_data, pos_in_row, big), rows,
                num_segments=cap, indices_are_sorted=True)
            lead = jnp.where(first_ns > lens, lens, first_ns.astype(jnp.int32))
        else:
            lead = jnp.zeros(cap, dtype=jnp.int32)
        if self._right:
            last_ns = jax.ops.segment_max(
                jnp.where(~is_space & in_data, pos_in_row, -1), rows,
                num_segments=cap, indices_are_sorted=True)
            trail = lens - 1 - last_ns.astype(jnp.int32)
            trail = jnp.clip(trail, 0, lens)
        else:
            trail = jnp.zeros(cap, dtype=jnp.int32)
        new_lens = jnp.maximum(lens - lead - trail, 0)
        new_lens = jnp.where(v.validity & ctx.row_mask, new_lens, 0)
        return _gather_substring(v, lead, new_lens, nbytes, v.validity)

    def cpu_eval(self, ctx) -> CpuVal:
        v = self.child.cpu_eval(ctx)
        out = np.empty(len(v.values), dtype=object)
        for i, s in enumerate(v.values):
            s = str(s)
            if self._left and self._right:
                out[i] = s.strip(" ")
            elif self._left:
                out[i] = s.lstrip(" ")
            else:
                out[i] = s.rstrip(" ")
        return CpuVal(T.STRING, out, v.validity)


class StringTrim(_Trim):
    _left = True
    _right = True


class StringTrimLeft(_Trim):
    _left = True
    _right = False


class StringTrimRight(_Trim):
    _left = False
    _right = True


class ConcatStrings(Expression):
    """concat(a, b, ...) over strings; NULL if any input is NULL (Spark)."""

    def __init__(self, *children: Expression):
        self.children = tuple(children)
        self.dtype = T.STRING
        self.nullable = any(c.nullable for c in children)

    def with_children(self, children):
        return ConcatStrings(*children)

    def tpu_eval(self, ctx) -> DevVal:
        vals = [c.tpu_eval(ctx) for c in self.children]
        acc = vals[0]
        for v in vals[1:]:
            acc = _concat2(acc, v, ctx)
        return acc

    def cpu_eval(self, ctx) -> CpuVal:
        vals = [c.cpu_eval(ctx) for c in self.children]
        n = ctx.num_rows
        out = np.empty(n, dtype=object)
        validity = np.ones(n, dtype=np.bool_)
        for v in vals:
            validity &= v.validity
        for i in range(n):
            out[i] = "".join(str(v.values[i]) for v in vals) if validity[i] else ""
        return CpuVal(T.STRING, out, validity)


def _concat2(a: DevVal, b: DevVal, ctx) -> DevVal:
    la, lb = string_lengths(a), string_lengths(b)
    validity = a.validity & b.validity
    new_lens = jnp.where(validity & ctx.row_mask, la + lb, 0)
    na, nb = int(a.data.shape[0]), int(b.data.shape[0])
    a_base, b_base = a.offsets[:-1], b.offsets[:-1]

    def src(rows, pos):
        from_a = pos < la[rows]
        ia = jnp.clip(a_base[rows] + pos, 0, na - 1)
        ib = jnp.clip(b_base[rows] + pos - la[rows], 0, nb - 1)
        return jnp.where(from_a, a.data[ia], b.data[ib])

    return build_string(T.STRING, new_lens, src, na + nb, validity)


class _NeedlePredicate(Expression):
    """startswith/endswith/contains with a literal needle."""

    def __init__(self, child: Expression, needle: Expression):
        if not isinstance(needle, Expression):
            needle = Literal(str(needle), T.STRING)
        self.children = (child, needle)
        self.dtype = T.BOOLEAN
        self.nullable = child.nullable or needle.nullable

    def with_children(self, children):
        return type(self)(children[0], children[1])

    @property
    def needle(self) -> Optional[str]:
        return _literal_needle(self.children[1])

    def tpu_supported(self, conf):
        if self.needle is None:
            return "search pattern must be a literal for TPU execution"
        return None

    def _match_dev(self, v: DevVal, needle: bytes):
        raise NotImplementedError

    def _match_cpu(self, s: str, needle: str) -> bool:
        raise NotImplementedError

    def tpu_eval(self, ctx) -> DevVal:
        v = self.children[0].tpu_eval(ctx)
        data = self._match_dev(v, self.needle.encode("utf-8"))
        return DevVal(T.BOOLEAN, data, v.validity)

    def cpu_eval(self, ctx) -> CpuVal:
        v = self.children[0].cpu_eval(ctx)
        nv = self.children[1].cpu_eval(ctx)
        data = np.fromiter(
            (self._match_cpu(str(s), str(n))
             for s, n in zip(v.values, nv.values)),
            dtype=np.bool_, count=len(v.values))
        return CpuVal(T.BOOLEAN, data, v.validity & nv.validity)


def _match_prefix(v: DevVal, needle: bytes):
    L = len(needle)
    if L == 0:
        return jnp.ones(v.capacity, dtype=jnp.bool_)
    nbytes = int(v.data.shape[0])
    ok = string_lengths(v) >= L
    starts = v.offsets[:-1]
    for k, bch in enumerate(needle):
        idx = jnp.clip(starts + k, 0, nbytes - 1)
        ok = ok & (v.data[idx] == np.uint8(bch))
    return ok


def _match_suffix(v: DevVal, needle: bytes):
    L = len(needle)
    if L == 0:
        return jnp.ones(v.capacity, dtype=jnp.bool_)
    nbytes = int(v.data.shape[0])
    ok = string_lengths(v) >= L
    ends = v.offsets[1:]
    for k, bch in enumerate(needle):
        idx = jnp.clip(ends - L + k, 0, nbytes - 1)
        ok = ok & (v.data[idx] == np.uint8(bch))
    return ok


class StringStartsWith(_NeedlePredicate):
    def _match_dev(self, v, needle):
        return _match_prefix(v, needle)

    def _match_cpu(self, s, needle):
        return s.startswith(needle)


class StringEndsWith(_NeedlePredicate):
    def _match_dev(self, v, needle):
        return _match_suffix(v, needle)

    def _match_cpu(self, s, needle):
        return s.endswith(needle)


class StringContains(_NeedlePredicate):
    def _match_dev(self, v, needle):
        return _rows_with_match(v, needle)

    def _match_cpu(self, s, needle):
        return needle in s


class Like(Expression):
    """SQL LIKE restricted to patterns translatable to prefix/suffix/contains
    tests: 'abc', 'abc%', '%abc', '%abc%', 'a%b'.  Other patterns (including
    '_' wildcards and escapes) fall back to CPU."""

    def __init__(self, child: Expression, pattern: str):
        self.children = (child,)
        self.pattern = pattern
        self.dtype = T.BOOLEAN
        self.nullable = child.nullable

    def with_children(self, children):
        return Like(children[0], self.pattern)

    def _plan(self):
        p = self.pattern
        if "_" in p or "\\" in p:
            return None
        parts = p.split("%")
        if len(parts) == 1:
            return ("exact", parts[0])
        if len(parts) == 2:
            if parts[0] == "" and parts[1] == "":
                return ("any",)
            if parts[1] == "":
                return ("prefix", parts[0])
            if parts[0] == "":
                return ("suffix", parts[1])
            return ("prefix_suffix", parts[0], parts[1])
        if len(parts) == 3 and parts[0] == "" and parts[2] == "":
            return ("contains", parts[1])
        return None

    def tpu_supported(self, conf):
        if self._plan() is None:
            return f"LIKE pattern {self.pattern!r} not supported on TPU"
        return None

    def tpu_eval(self, ctx) -> DevVal:
        plan = self._plan()
        kind = plan[0]
        if kind in ("any", "exact"):
            # Length and literal tests work on dictionary-encoded input.
            from spark_rapids_tpu.exprs.base import eval_maybe_encoded
            v = eval_maybe_encoded(self.children[0], ctx)
        else:
            v = self.children[0].tpu_eval(ctx)
        lens = string_lengths(v)
        if kind == "any":
            data = jnp.ones(v.capacity, dtype=jnp.bool_)
        elif kind == "exact":
            data = equals_any_literal(v, (plan[1],))
        elif kind == "prefix":
            data = _match_prefix(v, plan[1].encode())
        elif kind == "suffix":
            data = _match_suffix(v, plan[1].encode())
        elif kind == "contains":
            data = _rows_with_match(v, plan[1].encode())
        else:  # prefix_suffix
            pre, suf = plan[1], plan[2]
            data = (_match_prefix(v, pre.encode())
                    & _match_suffix(v, suf.encode())
                    & (lens >= len(pre) + len(suf)))
        return DevVal(T.BOOLEAN, data, v.validity)

    def cpu_eval(self, ctx) -> CpuVal:
        import re
        v = self.children[0].cpu_eval(ctx)
        regex = "^" + "".join(
            ".*" if c == "%" else "." if c == "_" else re.escape(c)
            for c in self.pattern) + "$"
        rx = re.compile(regex, re.DOTALL)
        data = np.fromiter((rx.match(str(s)) is not None for s in v.values),
                           dtype=np.bool_, count=len(v.values))
        return CpuVal(T.BOOLEAN, data, v.validity)


class StringLocate(Expression):
    """locate(needle, str): 1-based position of first match, 0 if absent."""

    def __init__(self, needle: Expression, child: Expression):
        if not isinstance(needle, Expression):
            needle = Literal(str(needle), T.STRING)
        self.children = (needle, child)
        self.dtype = T.INT
        self.nullable = child.nullable

    def with_children(self, children):
        return StringLocate(children[0], children[1])

    def tpu_supported(self, conf):
        if _literal_needle(self.children[0]) is None:
            return "locate needle must be a literal for TPU execution"
        return None

    def tpu_eval(self, ctx) -> DevVal:
        v = self.children[1].tpu_eval(ctx)
        needle = _literal_needle(self.children[0]).encode("utf-8")
        cap = v.capacity
        if len(needle) == 0:
            return DevVal(T.INT, jnp.ones(cap, dtype=jnp.int32), v.validity)
        nbytes = int(v.data.shape[0])
        match = _find_matches(v, needle)
        rows = jnp.clip(rows_of_positions(v.offsets, nbytes), 0, cap - 1)
        pos_in_row = jnp.arange(nbytes, dtype=jnp.int32) - v.offsets[rows]
        big = jnp.int32(nbytes + 1)
        first = jax.ops.segment_min(jnp.where(match, pos_in_row, big), rows,
                                    num_segments=cap, indices_are_sorted=True)
        data = jnp.where(first >= big, 0, first + 1).astype(jnp.int32)
        return DevVal(T.INT, data, v.validity)

    def cpu_eval(self, ctx) -> CpuVal:
        v = self.children[1].cpu_eval(ctx)
        needle = str(_literal_needle(self.children[0]) or "")
        data = np.fromiter((str(s).find(needle) + 1 for s in v.values),
                           dtype=np.int32, count=len(v.values))
        return CpuVal(T.INT, data, v.validity)


def _has_self_overlap(needle: bytes) -> bool:
    """True if the pattern can match at two positions closer than len(needle)."""
    L = len(needle)
    for k in range(1, L):
        if needle[k:] == needle[:-k]:
            return True
    return False


def _replace_match_starts(v: DevVal, match, Ls: int, repl: bytes,
                          ctx) -> DevVal:
    """Replace every Ls-byte run beginning at a True position of ``match``
    (bool[nbytes], match starts fully inside their row, non-overlapping)
    with ``repl``.  Scatter-formulated: copied bytes and replacement bytes
    land at positions shifted by (Lr-Ls) per preceding in-row match."""
    cap = v.capacity
    nbytes = int(v.data.shape[0])
    Lr = len(repl)
    rows = jnp.clip(rows_of_positions(v.offsets, nbytes), 0, cap - 1)
    n_matches = jax.ops.segment_sum(match.astype(jnp.int32), rows,
                                    num_segments=cap, indices_are_sorted=True)
    lens = string_lengths(v)
    new_lens = lens + n_matches * (Lr - Ls)
    new_lens = jnp.where(v.validity & ctx.row_mask, new_lens, 0)
    out_cap = nbytes if Lr <= Ls else nbytes + (nbytes // Ls) * (Lr - Ls)
    row_first_byte = v.offsets[rows]
    pos_in_row = jnp.arange(nbytes, dtype=jnp.int32) - row_first_byte
    starts_i = match.astype(jnp.int32)
    # covered[i] = any match start in (i-Ls, i] -> byte i is replaced.
    csum = jnp.concatenate([jnp.zeros(1, dtype=jnp.int32),
                            jnp.cumsum(starts_i)])
    lo = jnp.maximum(jnp.arange(nbytes) - Ls + 1, 0)
    covered = (csum[jnp.arange(nbytes) + 1] - csum[lo]) > 0
    # Matches before byte i in the same row:
    m_before = csum[jnp.arange(nbytes)]  # global matches strictly before i
    m_before_row_start = csum[jnp.clip(row_first_byte, 0, nbytes)]
    m_in_row_before = m_before - m_before_row_start
    # Output position of each *copied* byte and each *match start*:
    out_pos_copy = pos_in_row + m_in_row_before * (Lr - Ls)
    # Build output via scatter of copied bytes, then scatter replacement
    # bytes at match starts.
    out_offsets = jnp.concatenate([
        jnp.zeros(1, dtype=jnp.int32),
        jnp.cumsum(new_lens).astype(jnp.int32)])
    out_base = out_offsets[rows]
    out_idx_copy = out_base + out_pos_copy
    in_data_mask = jnp.arange(nbytes, dtype=jnp.int32) < v.offsets[-1]
    valid_copy = in_data_mask & ~covered
    out = jnp.zeros(out_cap, dtype=jnp.uint8)
    out = out.at[jnp.where(valid_copy, out_idx_copy, out_cap)].set(
        v.data, mode="drop")
    # match starts: the match at input pos i (m_in_row_before matches
    # before it) maps to output position pos_in_row + m_in_row_before*(Lr-Ls)
    out_idx_match = out_base + pos_in_row + m_in_row_before * (Lr - Ls)
    for k, bch in enumerate(repl):
        out = out.at[jnp.where(match & in_data_mask, out_idx_match + k,
                               out_cap)].set(
            jnp.full(nbytes, bch, dtype=jnp.uint8), mode="drop")
    return DevVal(T.STRING, out, v.validity, out_offsets)


class StringReplace(Expression):
    """replace(str, search, replacement) with literal search/replacement."""

    def __init__(self, child: Expression, search: Expression, replacement: Expression):
        if not isinstance(search, Expression):
            search = Literal(str(search), T.STRING)
        if not isinstance(replacement, Expression):
            replacement = Literal(str(replacement), T.STRING)
        self.children = (child, search, replacement)
        self.dtype = T.STRING
        self.nullable = child.nullable

    def with_children(self, children):
        return StringReplace(*children)

    def tpu_supported(self, conf):
        s = _literal_needle(self.children[1])
        if s is None or _literal_needle(self.children[2]) is None:
            return "replace search/replacement must be literals for TPU"
        if s == "":
            return "replace with empty search is a no-op handled on CPU"
        if _has_self_overlap(s.encode("utf-8")):
            return ("replace search pattern can self-overlap; sequential "
                    "matching required (CPU only)")
        return None

    def tpu_eval(self, ctx) -> DevVal:
        v = self.children[0].tpu_eval(ctx)
        search = _literal_needle(self.children[1]).encode("utf-8")
        repl = _literal_needle(self.children[2]).encode("utf-8")
        match = _find_matches(v, search)
        return _replace_match_starts(v, match, len(search), repl, ctx)

    def cpu_eval(self, ctx) -> CpuVal:
        v = self.children[0].cpu_eval(ctx)
        search = str(_literal_needle(self.children[1]) or "")
        repl = str(_literal_needle(self.children[2]) or "")
        if search == "":
            out = np.array([str(s) for s in v.values], dtype=object)
        else:
            out = np.array([str(s).replace(search, repl) for s in v.values],
                           dtype=object)
        return CpuVal(T.STRING, out, v.validity)


class _Pad(Expression):
    _left = True

    def __init__(self, child: Expression, length: int, pad: str = " "):
        self.children = (child,)
        self.target = int(length)
        self.pad = str(pad)
        self.dtype = T.STRING
        self.nullable = child.nullable

    def with_children(self, children):
        return type(self)(children[0], self.target, self.pad)

    def tpu_supported(self, conf):
        if len(self.pad) != 1:
            return "multi-char pad strings not supported on TPU yet"
        return None

    def tpu_eval(self, ctx) -> DevVal:
        v = self.children[0].tpu_eval(ctx)
        cap = v.capacity
        nbytes = int(v.data.shape[0])
        lens = string_lengths(v)
        tgt = jnp.int32(self.target)
        new_lens = jnp.where(v.validity & ctx.row_mask,
                             jnp.full(cap, tgt, dtype=jnp.int32), 0)
        pad_b = np.uint8(ord(self.pad))
        npad = jnp.maximum(tgt - lens, 0)
        base = v.offsets[:-1]

        def src_index(rows, pos):
            if self._left:
                is_pad = pos < npad[rows]
                src = base[rows] + pos - npad[rows]
            else:
                is_pad = pos >= lens[rows]
                src = base[rows] + pos
            byte = v.data[jnp.clip(src, 0, nbytes - 1)]
            return jnp.where(is_pad, pad_b, byte)

        out_cap = max(cap * max(self.target, 1), 16)
        return build_string(T.STRING, new_lens, src_index, out_cap, v.validity)

    def cpu_eval(self, ctx) -> CpuVal:
        v = self.children[0].cpu_eval(ctx)
        out = np.empty(len(v.values), dtype=object)
        for i, s in enumerate(v.values):
            s = str(s)
            if len(s) >= self.target:
                out[i] = s[: self.target]
            elif self._left:
                out[i] = (self.pad * self.target + s)[-self.target:] \
                    if self.pad else s
            else:
                out[i] = (s + self.pad * self.target)[: self.target] \
                    if self.pad else s
        return CpuVal(T.STRING, out, v.validity)


class StringLPad(_Pad):
    _left = True


class StringRPad(_Pad):
    _left = False


# ---------------------------------------------------------------------------
# regexp_replace / split_part / concat_ws
# (reference: stringFunctions.scala GpuRegExpReplace/GpuStringSplit/
#  GpuConcatWs; the reference likewise transpiles or rejects regex patterns —
#  RegexParser in RegexParser.scala)
# ---------------------------------------------------------------------------

_REGEX_META = set(".^$*+?()[]{}|\\")


def _regex_as_literal(pattern: str) -> Optional[str]:
    """The literal string a regex matches exactly, or None if it uses any
    unescaped metacharacter (conservative transpile, like the reference's
    RegexParser rejecting what cudf can't run)."""
    out = []
    i = 0
    while i < len(pattern):
        ch = pattern[i]
        if ch == "\\":
            if i + 1 >= len(pattern):
                return None
            nxt = pattern[i + 1]
            if nxt in _REGEX_META:
                out.append(nxt)
                i += 2
                continue
            return None  # \d, \s ... not a literal
        if ch in _REGEX_META:
            return None
        out.append(ch)
        i += 1
    return "".join(out)


def _regex_as_byte_class(pattern: str) -> Optional[bytes]:
    """The set of single bytes a regex char-class matches, or None.

    Supports ``[abc]`` and ``[a-z0-9]`` style classes over ASCII (no
    negation); escaped members and range ENDPOINTS (``[\\.-0]``) parse as
    one item each, so ranges with escaped endpoints are exact.
    """
    if len(pattern) < 3 or pattern[0] != "[" or pattern[-1] != "]":
        return None
    inner = pattern[1:-1]
    if inner.startswith("^") or not inner:
        return None

    def parse_item(i):
        """(char, next_i) for one literal-or-escaped class member."""
        ch = inner[i]
        if ch == "\\":
            if i + 1 >= len(inner):
                return None
            nxt = inner[i + 1]
            if nxt not in _REGEX_META and nxt != "-":
                return None  # \d, \s ... not a single char
            return nxt, i + 2
        return ch, i + 1

    members = set()
    i = 0
    while i < len(inner):
        item = parse_item(i)
        if item is None:
            return None
        lo_ch, i = item
        if i < len(inner) and inner[i] == "-" and i + 1 < len(inner):
            hi_item = parse_item(i + 1)
            if hi_item is None:
                return None
            hi_ch, i = hi_item
            lo, hi = ord(lo_ch), ord(hi_ch)
            if lo > hi or hi > 127:
                return None
            members.update(chr(c) for c in range(lo, hi + 1))
            continue
        if ord(lo_ch) > 127:
            return None
        members.add(lo_ch)
    if not members:
        return None
    return bytes(sorted(ord(c) for c in members))


class RegExpReplace(Expression):
    """regexp_replace(str, pattern, replacement).

    TPU path covers the subset the engine can transpile: patterns that are
    plain literals (after unescaping) reuse the StringReplace kernel, and
    single-char classes like ``[0-9]`` map each member byte.  Everything
    else (real regex) falls back to the CPU engine's ``re`` evaluation —
    the same accept/reject shape as the reference's RegexParser
    (stringFunctions.scala:862 + RegexParser).
    """

    def __init__(self, child: Expression, pattern: Expression,
                 replacement: Expression):
        if not isinstance(pattern, Expression):
            pattern = Literal(str(pattern), T.STRING)
        if not isinstance(replacement, Expression):
            replacement = Literal(str(replacement), T.STRING)
        self.children = (child, pattern, replacement)
        self.dtype = T.STRING
        self.nullable = child.nullable

    def with_children(self, children):
        return RegExpReplace(*children)

    def _plan(self):
        """("literal", s) | ("class", bytes) | None."""
        pat = _literal_needle(self.children[1])
        if pat is None or _literal_needle(self.children[2]) is None:
            return None
        lit = _regex_as_literal(pat)
        if lit is not None and lit != "":
            return ("literal", lit)
        cls = _regex_as_byte_class(pat)
        if cls is not None:
            return ("class", cls)
        return None

    def tpu_supported(self, conf):
        plan = self._plan()
        if plan is None:
            return ("regexp pattern is not in the transpilable subset "
                    "(literal or single-char class); CPU fallback")
        if plan[0] == "literal" and \
                _has_self_overlap(plan[1].encode("utf-8")):
            return ("regexp literal can self-overlap; sequential matching "
                    "required (CPU only)")
        return None

    def tpu_eval(self, ctx) -> DevVal:
        kind, what = self._plan()
        v = self.children[0].tpu_eval(ctx)
        repl = _literal_needle(self.children[2]).encode("utf-8")
        if kind == "literal":
            match = _find_matches(v, what.encode("utf-8"))
            return _replace_match_starts(v, match,
                                         len(what.encode("utf-8")),
                                         repl, ctx)
        # char class: every member byte is a length-1 match
        nbytes = int(v.data.shape[0])
        match = jnp.zeros(nbytes, dtype=jnp.bool_)
        for b in what:
            match = match | (v.data == np.uint8(b))
        in_data = jnp.arange(nbytes, dtype=jnp.int32) < v.offsets[-1]
        return _replace_match_starts(v, match & in_data, 1, repl, ctx)

    def cpu_eval(self, ctx) -> CpuVal:
        import re
        v = self.children[0].cpu_eval(ctx)
        pat = _literal_needle(self.children[1])
        repl = _literal_needle(self.children[2])
        if pat is None or repl is None:
            raise NotImplementedError(
                "regexp_replace pattern/replacement must be literals")
        rx = re.compile(pat)
        # LITERAL replacement (lambda sidesteps python's \\-template
        # expansion, which crashes on '\\U...' and renders '$1' literally
        # anyway) — matches the TPU path; Java $-group references are a
        # documented non-feature (docs/compatibility.md).
        out = np.array([rx.sub(lambda _m: repl, str(s)) for s in v.values],
                       dtype=object)
        return CpuVal(T.STRING, out, v.validity)


class SplitPart(Expression):
    """split_part(str, delimiter, partNum): 1-based field extraction on a
    literal delimiter; out-of-range -> empty string (Spark split_part /
    the getItem(i) shape of GpuStringSplit, stringFunctions.scala)."""

    def __init__(self, child: Expression, delimiter, part):
        if not isinstance(delimiter, Expression):
            delimiter = Literal(str(delimiter), T.STRING)
        self.children = (child, delimiter)
        self.part = int(part)
        if self.part == 0:
            # Spark raises for partNum 0 (ANSI and non-ANSI alike)
            raise ValueError("split_part: partNum must not be 0")
        self.dtype = T.STRING
        self.nullable = child.nullable

    def with_children(self, children):
        return SplitPart(children[0], children[1], self.part)

    def tpu_supported(self, conf):
        d = _literal_needle(self.children[1])
        if d is None or d == "":
            return "split delimiter must be a non-empty literal"
        if self.part < 0:
            return "negative part numbers run on CPU"
        if _has_self_overlap(d.encode("utf-8")):
            return "split delimiter can self-overlap (CPU only)"
        return None

    def tpu_eval(self, ctx) -> DevVal:
        v = self.children[0].tpu_eval(ctx)
        delim = _literal_needle(self.children[1]).encode("utf-8")
        Ld = len(delim)
        j = self.part - 1  # 0-based part index
        cap = v.capacity
        nbytes = int(v.data.shape[0])
        match = _find_matches(v, delim)
        rows = jnp.clip(rows_of_positions(v.offsets, nbytes), 0, cap - 1)
        pos = jnp.arange(nbytes, dtype=jnp.int32)
        starts_i = match.astype(jnp.int32)
        csum = jnp.concatenate([jnp.zeros(1, dtype=jnp.int32),
                                jnp.cumsum(starts_i)])
        rank = csum[pos] - csum[jnp.clip(v.offsets[rows], 0, nbytes)]
        big = jnp.int32(1 << 30)
        # in-row byte position of the (j-1)-th and j-th delimiter match
        def match_pos(k):
            sel = match & (rank == k)
            return jax.ops.segment_min(
                jnp.where(sel, pos, big), rows, num_segments=cap, indices_are_sorted=True)

        n_matches = jax.ops.segment_sum(starts_i, rows, num_segments=cap, indices_are_sorted=True)
        row_start = v.offsets[:-1]
        row_end = v.offsets[1:]
        start = row_start if j == 0 else \
            jnp.minimum(match_pos(j - 1) + Ld, row_end)
        end = jnp.where(n_matches > j, match_pos(j), row_end)
        exists = n_matches >= j  # part j exists when >= j delimiters... 
        # parts = n_matches + 1, so part index j valid iff j <= n_matches
        new_lens = jnp.where(exists, jnp.maximum(end - start, 0), 0)
        new_lens = jnp.where(v.validity & ctx.row_mask, new_lens, 0)
        rel_start = (start - row_start).astype(jnp.int32)
        return _gather_substring(v, rel_start, new_lens, nbytes, v.validity)

    def cpu_eval(self, ctx) -> CpuVal:
        v = self.children[0].cpu_eval(ctx)
        d = _literal_needle(self.children[1])
        if d is None:
            raise NotImplementedError(
                "split_part delimiter must be a literal")
        out = np.empty(len(v.values), dtype=object)
        for i, s in enumerate(v.values):
            parts = str(s).split(d) if d else [str(s)]
            k = self.part
            if k < 0:
                k = len(parts) + k + 1
            out[i] = parts[k - 1] if 1 <= k <= len(parts) else ""
        return CpuVal(T.STRING, out, v.validity)


class ConcatWs(Expression):
    """concat_ws(sep, cols...): join non-NULL values with a literal
    separator; NULL inputs are skipped (never nullify the result)."""

    def __init__(self, sep, *children: Expression):
        self.sep = str(sep)
        self.children = tuple(children)
        self.dtype = T.STRING
        self.nullable = False

    def with_children(self, children):
        return ConcatWs(self.sep, *children)

    def tpu_supported(self, conf):
        for c in self.children:
            if not c.dtype.is_string:
                return f"concat_ws child must be string, got {c.dtype}"
        return None

    def tpu_eval(self, ctx) -> DevVal:
        cap = ctx.capacity
        if not self.children:
            # Spark: concat_ws(sep) with no columns is '' per row
            return DevVal(T.STRING, jnp.zeros(16, dtype=jnp.uint8),
                          jnp.ones(cap, dtype=jnp.bool_),
                          jnp.zeros(cap + 1, dtype=jnp.int32))
        sep = self.sep.encode("utf-8")
        Lsep = len(sep)
        sep_arr = jnp.asarray(np.frombuffer(sep, dtype=np.uint8)) \
            if Lsep else jnp.zeros(1, dtype=jnp.uint8)
        vals = [c.tpu_eval(ctx) for c in self.children]
        # normalize the accumulator: NULL rows contribute zero bytes
        l0 = jnp.where(vals[0].validity, string_lengths(vals[0]), 0)
        acc = _gather_substring(
            vals[0],
            jnp.zeros(cap, dtype=jnp.int32),
            jnp.where(vals[0].validity & ctx.row_mask, l0, 0),
            int(vals[0].data.shape[0]),
            jnp.ones(cap, dtype=jnp.bool_))
        has_any = vals[0].validity
        for v in vals[1:]:
            la = string_lengths(acc)
            lv = jnp.where(v.validity, string_lengths(v), 0)
            add_sep = has_any & v.validity
            new_lens = la + jnp.where(v.validity,
                                      lv + jnp.where(add_sep, Lsep, 0), 0)
            new_lens = jnp.where(ctx.row_mask, new_lens, 0)
            na, nv = int(acc.data.shape[0]), int(v.data.shape[0])
            a_base, v_base = acc.offsets[:-1], v.offsets[:-1]
            sep_start = la  # in-row position where separator begins
            v_start = la + jnp.where(add_sep, Lsep, 0)

            def src(rows, pos, acc=acc, v=v, la=la, sep_start=sep_start,
                    v_start=v_start, na=na, nv=nv, a_base=a_base,
                    v_base=v_base):
                from_a = pos < la[rows]
                in_sep = (~from_a) & (pos < v_start[rows])
                ia = jnp.clip(a_base[rows] + pos, 0, na - 1)
                iv = jnp.clip(v_base[rows] + pos - v_start[rows], 0, nv - 1)
                isep = jnp.clip(pos - sep_start[rows], 0,
                                max(Lsep - 1, 0))
                return jnp.where(
                    from_a, acc.data[ia],
                    jnp.where(in_sep, sep_arr[isep], v.data[iv]))

            out_cap = na + nv + (cap * Lsep if Lsep else 0)
            acc = build_string(T.STRING, new_lens, src, out_cap,
                               jnp.ones(cap, dtype=jnp.bool_))
            has_any = has_any | v.validity
        return acc

    def cpu_eval(self, ctx) -> CpuVal:
        vals = [c.cpu_eval(ctx) for c in self.children]
        n = ctx.num_rows
        out = np.empty(n, dtype=object)
        for i in range(n):
            pieces = [str(v.values[i]) for v in vals if v.validity[i]]
            out[i] = self.sep.join(pieces)
        return CpuVal(T.STRING, out, np.ones(n, dtype=np.bool_))


class InitCap(_CaseMap):
    """initcap: lowercase everything, uppercase the first letter of each
    whitespace-separated word (Spark InitCap / GpuInitCap)."""

    def tpu_eval(self, ctx) -> DevVal:
        v = self.child.tpu_eval(ctx)
        data = v.data
        nbytes = int(data.shape[0])
        # word starts: first byte of each row (scatter of row offsets)
        # or a byte following a space
        starts = jnp.zeros(nbytes + 1, dtype=jnp.bool_) \
            .at[jnp.clip(v.offsets, 0, nbytes)].set(True)[:nbytes]
        after_space = jnp.concatenate(
            [jnp.ones(1, dtype=jnp.bool_), data[:-1] == 32])
        head = starts | after_space
        is_upper = (data >= 65) & (data <= 90)
        is_lower = (data >= 97) & (data <= 122)
        lowered = jnp.where(is_upper, data + 32, data)
        out = jnp.where(head & is_lower, data - 32,
                        jnp.where(~head & is_upper, lowered, data))
        return DevVal(T.STRING, out.astype(jnp.uint8), v.validity,
                      v.offsets)

    def _map_cpu(self, s):
        # ASCII-only, matching the device byte mapping (same convention
        # as Upper/Lower above)
        out = []
        head = True
        for ch in s:
            if head and "a" <= ch <= "z":
                out.append(chr(ord(ch) - 32))
            elif not head and "A" <= ch <= "Z":
                out.append(chr(ord(ch) + 32))
            else:
                out.append(ch)
            head = ch == " "
        return "".join(out)


class SubstringIndex(Expression):
    """substring_index(str, delim, count): prefix before the count-th
    delimiter (count > 0) or suffix after the |count|-th-from-the-right
    delimiter (count < 0); whole string when not enough delimiters
    (Spark SubstringIndex / GpuSubstringIndex)."""

    def __init__(self, child: Expression, delimiter, count: int):
        if not isinstance(delimiter, Expression):
            delimiter = Literal(str(delimiter), T.STRING)
        self.children = (child, delimiter)
        self.count = int(count)
        self.dtype = T.STRING
        self.nullable = child.nullable

    def with_children(self, children):
        return SubstringIndex(children[0], children[1], self.count)

    def tpu_supported(self, conf):
        d = _literal_needle(self.children[1])
        if d is None or d == "":
            return "substring_index delimiter must be a non-empty literal"
        if _has_self_overlap(d.encode("utf-8")):
            return "substring_index delimiter can self-overlap (CPU only)"
        return None

    def tpu_eval(self, ctx) -> DevVal:
        v = self.children[0].tpu_eval(ctx)
        delim = _literal_needle(self.children[1]).encode("utf-8")
        Ld = len(delim)
        cap = v.capacity
        nbytes = int(v.data.shape[0])
        row_start, row_end = v.offsets[:-1], v.offsets[1:]
        if self.count == 0:
            zero = jnp.zeros(cap, dtype=jnp.int32)
            return _gather_substring(v, zero, zero, nbytes, v.validity)
        match = _find_matches(v, delim)
        rows = jnp.clip(rows_of_positions(v.offsets, nbytes), 0, cap - 1)
        pos = jnp.arange(nbytes, dtype=jnp.int32)
        starts_i = match.astype(jnp.int32)
        csum = jnp.concatenate([jnp.zeros(1, dtype=jnp.int32),
                                jnp.cumsum(starts_i)])
        rank = csum[pos] - csum[jnp.clip(v.offsets[rows], 0, nbytes)]
        n_matches = jax.ops.segment_sum(starts_i, rows, num_segments=cap,
                                        indices_are_sorted=True)
        big = jnp.int32(1 << 30)
        if self.count > 0:
            # byte position of the (count-1)-th match per row
            sel = match & (rank == self.count - 1)
            kpos = jax.ops.segment_min(jnp.where(sel, pos, big), rows,
                                       num_segments=cap,
                                       indices_are_sorted=True)
            start = row_start
            end = jnp.where(n_matches >= self.count, kpos, row_end)
        else:
            # match index n_matches + count (0-based from the left)
            k = n_matches + self.count  # per-row target rank
            sel = match & (rank == k[rows])
            kpos = jax.ops.segment_min(jnp.where(sel, pos, big), rows,
                                       num_segments=cap,
                                       indices_are_sorted=True)
            start = jnp.where(n_matches >= -self.count, kpos + Ld,
                              row_start)
            end = row_end
        new_lens = jnp.maximum(end - start, 0)
        new_lens = jnp.where(v.validity & ctx.row_mask, new_lens, 0)
        rel_start = (start - row_start).astype(jnp.int32)
        return _gather_substring(v, rel_start, new_lens, nbytes,
                                 v.validity)

    def cpu_eval(self, ctx) -> CpuVal:
        v = self.children[0].cpu_eval(ctx)
        d = _literal_needle(self.children[1])
        if d is None:
            raise NotImplementedError(
                "substring_index delimiter must be a literal")
        out = np.empty(len(v.values), dtype=object)
        for i, s in enumerate(v.values):
            s = str(s)
            c = self.count
            if c == 0 or not d:
                out[i] = ""
            elif c > 0:
                parts = s.split(d)
                out[i] = d.join(parts[:c]) if len(parts) > c else s
            else:
                parts = s.split(d)
                out[i] = d.join(parts[c:]) if len(parts) > -c else s
        return CpuVal(T.STRING, out, v.validity)


class StringSplit(Expression):
    """split(str, delim) -> array<string> (Spark StringSplit).  The
    engine's array columns hold fixed-width elements, so an array of
    variable-length strings cannot live on the device — this expression
    always runs on the CPU engine (planner fallback), like any
    type-unsupported expression in the reference.  The delimiter is a
    regex, matching Spark's split()."""

    def __init__(self, child: Expression, delimiter):
        if not isinstance(delimiter, Expression):
            delimiter = Literal(str(delimiter), T.STRING)
        self.children = (child, delimiter)
        self.dtype = T.ArrayType(T.STRING)
        self.nullable = child.nullable

    def with_children(self, children):
        return StringSplit(children[0], children[1])

    def tpu_supported(self, conf):
        return ("split produces array<string>; variable-length array "
                "elements are CPU-only")

    def cpu_eval(self, ctx) -> CpuVal:
        v = self.children[0].cpu_eval(ctx)
        d = _literal_needle(self.children[1])
        if d is None:
            raise NotImplementedError("split delimiter must be a literal")
        import re
        pat = re.compile(d) if d else None
        out = np.empty(len(v.values), dtype=object)
        for i, s in enumerate(v.values):
            out[i] = pat.split(str(s)) if pat else [str(s)]
        return CpuVal(self.dtype, out, v.validity)


class Hex(Expression):
    """hex(integral) -> uppercase hex string (Spark Hex / GpuOverrides'
    hex; negative longs render as 16-digit two's complement).  Device
    path computes nibbles with arithmetic shifts — no 64-bit bitcast,
    which the chip's f64/i64 emulation cannot do."""

    def __init__(self, child: Expression):
        self.children = (child,)
        self.dtype = T.STRING
        self.nullable = child.nullable

    @property
    def child(self):
        return self.children[0]

    def with_children(self, children):
        return Hex(children[0])

    def tpu_supported(self, conf):
        if self.child.dtype is not T.NULL and \
                not self.child.dtype.is_integral:
            return "hex over non-integral inputs runs on CPU"
        return None

    def tpu_eval(self, ctx) -> DevVal:
        v = self.child.tpu_eval(ctx)
        cap = v.capacity
        x = v.data.astype(jnp.int64)
        # nibble k (0 = most significant); arithmetic >> keeps two's
        # complement bits, & 15 extracts the nibble
        nibbles = jnp.stack(
            [(x >> (4 * (15 - k))) & 15 for k in range(16)],
            axis=1).astype(jnp.int32)                       # [cap, 16]
        digits = jnp.where(nibbles < 10, nibbles + 48,
                           nibbles + 55).astype(jnp.uint8)
        # length = 16 - leading zero nibbles (min 1 so 0 -> "0")
        nz = nibbles != 0
        first_nz = jnp.argmax(nz, axis=1)                   # 0 if none
        any_nz = jnp.any(nz, axis=1)
        lens = jnp.where(any_nz, 16 - first_nz, 1).astype(jnp.int32)
        live = v.validity & ctx.row_mask
        lens = jnp.where(live, lens, 0)
        flat = digits.reshape(-1)
        offsets16 = (jnp.arange(cap + 1, dtype=jnp.int32) * 16)
        v16 = DevVal(T.STRING, flat, v.validity, offsets16)
        rel_start = jnp.where(any_nz, first_nz, 15).astype(jnp.int32)
        # cap is a power-of-two bucket, so cap*16 is too (stable compile
        # cache keys)
        return _gather_substring(v16, rel_start, lens, cap * 16,
                                 v.validity)

    def cpu_eval(self, ctx) -> CpuVal:
        v = self.child.cpu_eval(ctx)
        out = np.empty(len(v.values), dtype=object)
        is_str = self.child.dtype.is_string
        for i, x in enumerate(v.values):
            if is_str:
                # Spark hex(string) = hex of the UTF-8 bytes
                out[i] = str(x).encode("utf-8").hex().upper()
                continue
            if self.child.dtype.is_fractional:
                # Spark's implicit double->bigint cast: truncate toward
                # zero, NaN -> 0, +-inf/out-of-range saturate at the
                # long bounds (same rules as Cast.cpu_eval)
                xf = float(x)
                if xf != xf:
                    xi = 0
                elif xf >= 2.0 ** 63:
                    xi = (1 << 63) - 1
                elif xf < -(2.0 ** 63):
                    xi = -(1 << 63)
                else:
                    xi = int(xf)
            else:
                xi = int(x)  # int64-exact: no float round trip
            out[i] = format(xi if xi >= 0 else xi + (1 << 64), "X")
        return CpuVal(T.STRING, out, v.validity)

