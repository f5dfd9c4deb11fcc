"""Order-preserving uint32 sort-key encodings.

The TPU analogue of cudf ``Table.orderBy``'s comparators
(GpuSortExec.scala:241): every sort key column is encoded into one or more
``uint32`` words such that *lexicographic comparison of the word tuple*
equals the SQL ordering (ascending/descending, nulls first/last, padding
rows always last).  ``jax.lax.sort`` over the word list yields the
permutation.

Why 32-bit words: TPUs have no native 64-bit integer lanes — XLA *emulates*
u64 arithmetic/compares, which cripples the sort that every kernel here
(groupby, join, window, partition-split) is built on.  A 64-bit key split
into (hi, lo) u32 words compares identically under lexicographic multi-word
sort, and every op stays native.

Encodings:

* int8/16/32, date: one word — value ^ sign-bit (order-preserving bias)
* int64/timestamp: two words — biased hi 32 bits, raw lo 32 bits
* float/double: canonicalize NaN (Spark: NaN sorts greatest, -0.0 == 0.0),
  then the IEEE trick in (hi, lo) form: negative => flip all bits, else set
  the sign bit
* boolean: 0/1
* string: bytes padded with 0 and packed big-endian, 4 bytes per word, up
  to a configurable prefix (``spark.rapids.sql.tpu.sort.stringPrefixBytes``,
  default 64).  Byte-0 padding preserves "shorter prefix sorts first",
  matching Spark's unsigned-byte string comparison.  Strings equal in the
  prefix tie-break by full-length + dual 32-bit polynomial hash when
  exactness of *grouping* matters (groupby uses that); pure sort order
  beyond the prefix is documented approximate, like the reference flags
  incompat string cases.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import DeviceColumn
from spark_rapids_tpu.exprs.base import DevVal
from spark_rapids_tpu.utils.tracing import kernel_scope

DEFAULT_STRING_PREFIX_BYTES = 64

# numpy (not jnp) scalar: module import can happen lazily inside an active
# jit trace, where a jnp constant would be created as that trace's tracer
# and leak into every later program (UnexpectedTracerError)
_SIGN32 = np.uint32(1 << 31)

# f64 order words are backend-dependent:
#
# * CPU (tests, oracle, virtual mesh): real IEEE f64 — bitcast to a
#   (hi, lo) u32 pair, exact.
# * TPU: XLA emulates f64 as a float-float pair (two f32s: hi + lo), with
#   f32's exponent range — bitcasts of emulated f64 fail to compile, and
#   values outside ~[1e-38, 3.4e38] are already inf/0 on device.  The
#   emulation's own (hi, lo) split IS the encoding: s1 = f32(x),
#   s2 = f32(x - s1), compared lexicographically (standard double-float
#   comparison), using only native f32 bitcasts.  See
#   docs/compatibility.md "Double precision on TPU".


def _encode_fixed_words(v: DevVal) -> List[jnp.ndarray]:
    """Order-preserving u32 word list for a fixed-width column's values."""
    dt = v.dtype
    if dt == T.BOOLEAN:
        return [v.data.astype(jnp.uint32)]
    if dt in (T.BYTE, T.SHORT, T.INT, T.DATE):
        x = v.data.astype(jnp.int32)
        return [jax.lax.bitcast_convert_type(x, jnp.uint32) ^ _SIGN32]
    if dt in (T.LONG, T.TIMESTAMP):
        x = v.data.astype(jnp.int64)
        lo = jax.lax.convert_element_type(
            x & jnp.int64(0xFFFFFFFF), jnp.uint32)
        hi32 = jax.lax.convert_element_type(
            (x >> jnp.int64(32)) & jnp.int64(0xFFFFFFFF), jnp.uint32)
        return [hi32 ^ _SIGN32, lo]
    if dt == T.FLOAT:
        x = v.data.astype(jnp.float32)
        x = jnp.where(jnp.isnan(x), jnp.float32(jnp.nan), x)
        x = jnp.where(x == 0.0, jnp.float32(0.0), x)
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        neg = (bits & _SIGN32) != 0
        return [jnp.where(neg, ~bits, bits | _SIGN32)]
    if dt == T.DOUBLE:
        return _encode_double_words(v.data)
    raise TypeError(f"cannot encode sort key of type {dt}")


def _enc_f32_bits(f):
    """Order-preserving u32 encoding of a (native) f32 array."""
    bits = jax.lax.bitcast_convert_type(f.astype(jnp.float32), jnp.uint32)
    neg = (bits & _SIGN32) != 0
    return jnp.where(neg, ~bits, bits | _SIGN32)


def _encode_double_words(data) -> List[jnp.ndarray]:
    """u32 order words for f64 (Spark order: -inf..-0=0..+inf, NaN
    greatest), injective on device-representable canonicalized values."""
    if jax.default_backend() == "tpu":
        return _encode_double_words_ff(data)
    return _encode_double_words_bitcast(data)


def _encode_double_words_bitcast(data) -> List[jnp.ndarray]:
    """Exact (hi, lo) u32 pair via bitcast — real-f64 backends only."""
    x = data.astype(jnp.float64)
    x = jnp.where(jnp.isnan(x), jnp.float64(jnp.nan), x)
    x = jnp.where(x == 0.0, jnp.float64(0.0), x)
    pair = jax.lax.bitcast_convert_type(x, jnp.uint32)  # [..., 2] lo,hi
    lo, hi = pair[..., 0], pair[..., 1]
    neg = (hi & _SIGN32) != 0
    return [jnp.where(neg, ~hi, hi | _SIGN32),
            jnp.where(neg, ~lo, lo)]


def _encode_double_words_ff(data) -> List[jnp.ndarray]:
    """(nan-class, enc32(hi), enc32(lo)) for float-float-emulated f64.

    x < y  <=>  (f32(x), x - f32(x)) lexicographic (standard double-float
    comparison; both components signed, ordered by the f32 encoding).
    """
    x = data.astype(jnp.float64)
    isnan = jnp.isnan(x)
    x = jnp.where(isnan, jnp.float64(0.0), x)
    x = jnp.where(x == 0.0, jnp.float64(0.0), x)  # -0 -> +0
    s1 = x.astype(jnp.float32)
    r1 = x - s1.astype(jnp.float64)
    r1 = jnp.where(jnp.isinf(x), jnp.float64(0.0), r1)  # inf - inf = nan
    s2 = r1.astype(jnp.float32)
    cls = jnp.where(isnan, jnp.uint32(1), jnp.uint32(0))
    return [cls, _enc_f32_bits(s1), _enc_f32_bits(s2)]


# Backwards-compatible single-word view used by equality checks.
def _encode_fixed(v: DevVal) -> List[jnp.ndarray]:
    return _encode_fixed_words(v)


def string_prefix_words(col_or_val, prefix_bytes: int) -> List[jnp.ndarray]:
    """Big-endian packed u32 words of each row's first ``prefix_bytes``
    bytes."""
    v = col_or_val
    if getattr(v, "codes", None) is not None:
        # Dictionary-encoded: pack each ENTRY's prefix once, gather per row.
        nd = int(v.offsets.shape[0]) - 1
        ent = DevVal(v.dtype, v.data, jnp.ones(nd, dtype=jnp.bool_),
                     v.offsets)
        codes_c = jnp.clip(v.codes, 0, max(nd - 1, 0))
        return [jnp.where(v.validity, w[codes_c], jnp.uint32(0))
                for w in string_prefix_words(ent, prefix_bytes)]
    offsets, data = v.offsets, v.data
    cap = int(offsets.shape[0]) - 1
    nbytes = int(data.shape[0])
    lens = (offsets[1:] - offsets[:-1]).astype(jnp.int32)
    words: List[jnp.ndarray] = []
    n_words = (prefix_bytes + 3) // 4
    for w in range(n_words):
        word = jnp.zeros(cap, dtype=jnp.uint32)
        for b in range(4):
            j = w * 4 + b
            src = jnp.clip(offsets[:-1] + j, 0, nbytes - 1)
            byte = jnp.where(j < lens, data[src], 0).astype(jnp.uint32)
            word = (word << jnp.uint32(8)) | byte
        words.append(word)
    return words


@kernel_scope
def encode_sort_keys(vals: List[DevVal], ascendings: List[bool],
                     nulls_firsts: List[bool], num_rows,
                     string_prefix_bytes: int = DEFAULT_STRING_PREFIX_BYTES,
                     groupings: Optional[List[bool]] = None,
                     liveness: bool = True) -> List[jnp.ndarray]:
    """Full u32 key-word list for a multi-column sort.

    With ``liveness`` (the default), a leading word forces padding rows
    (row >= num_rows) to the end; each key column contributes a null-rank
    word then its value word(s).  The liveness bit is folded into the first
    null-rank word (both are un-negated 1-bit ranks) to save a sort pass.

    ``groupings[i]`` marks key i as *grouping-only*: the caller needs equal
    keys adjacent (groupby segmentation, window partitioning) but does not
    care about the order *between* distinct keys.  String columns then
    encode as (length, h1, h2) — 3 words instead of prefix_bytes/4 + 3 —
    which cuts the sort-operand count that drives TPU compile time.  Equal
    strings still always land adjacent; the only risk is a dual-32-bit-hash
    + length collision between *distinct* strings that interleave, the same
    collision class as the documented string join equality."""
    cap = int(vals[0].validity.shape[0]) if vals else 0
    words: List[jnp.ndarray] = []
    if liveness:
        live = jnp.arange(cap, dtype=jnp.int32) < num_rows
        words.append(jnp.where(live, 0, 1).astype(jnp.uint32))
    if groupings is None:
        groupings = [False] * len(vals)
    for v, asc, nf, grp in zip(vals, ascendings, nulls_firsts, groupings):
        null_rank = jnp.where(v.validity, 1, 0) if nf else \
            jnp.where(v.validity, 0, 1)
        words.append(null_rank.astype(jnp.uint32))
        if v.dtype.is_string:
            # Prefix words order the sort; the trailing (length, h1, h2)
            # tie-break words guarantee that *fully equal* strings always
            # sort adjacent even past the prefix, so group_segments /
            # window partitioning (which test full equality via
            # keys_equal_prev) never split one group across a run of
            # prefix-equal strings.  Beyond-prefix *order* between unequal
            # strings remains approximate (documented).
            from spark_rapids_tpu.exprs.strings import (
                string_hash2, string_lengths,
            )
            lens = string_lengths(v).astype(jnp.uint32)
            h1, h2 = string_hash2(v)
            tail = [lens, h1.astype(jnp.uint32), h2.astype(jnp.uint32)]
            if grp:
                vwords = tail
            else:
                vwords = string_prefix_words(v, string_prefix_bytes) + tail
        else:
            vwords = _encode_fixed_words(v)
        for w in vwords:
            w = jnp.where(v.validity, w, 0)  # nulls all compare equal
            words.append(w if asc else ~w)
    if liveness and len(words) >= 2:
        # Fold: (pad << 1) | null_rank_of_first_key.  Neither word is ever
        # negated for descending order, so the fold preserves the ordering.
        words = [(words[0] << jnp.uint32(1)) | words[1]] + words[2:]
    return words


# lax.sort compile time on this TPU toolchain grows ~2x per added operand
# (measured round 4: 8.6s / 17s / 67s / 171s cold for 1 / 2 / 3 / 5 key
# words at 64K-4M rows), so a 20-word string sort never finishes compiling.
# A least-significant-word-first chain of identical 2-operand stable sorts
# compiles once and stays flat (~20-35s for 20 passes) at <2x the direct
# sort's runtime — so on TPU any multi-word sort takes the LSD path.
_DIRECT_SORT_MAX_WORDS_TPU = 1


@kernel_scope
def argsort_by_words(words: List[jnp.ndarray], cap: int) -> jnp.ndarray:
    """Stable permutation (int32[cap]) ordering rows by the word tuple."""
    iota = jnp.arange(cap, dtype=jnp.int32)
    if not words:
        return iota
    if jax.default_backend() == "tpu" and \
            len(words) > _DIRECT_SORT_MAX_WORDS_TPU:
        return _argsort_lsd(words, iota)
    out = jax.lax.sort(tuple(words) + (iota,), num_keys=len(words),
                       is_stable=True)
    return out[-1]


def _argsort_lsd(words: List[jnp.ndarray], perm: jnp.ndarray) -> jnp.ndarray:
    """LSD radix argsort: stable-sort by each word, least significant first.

    After processing word i, rows are stably ordered by words[i:]; the final
    permutation therefore orders by the full lexicographic word tuple —
    identical to the direct multi-operand sort (cross-checked in
    tests/test_kernels_sort.py)."""
    for w in reversed(words):
        _, perm = jax.lax.sort((w[perm], perm), num_keys=1, is_stable=True)
    return perm


def keys_equal_prev(vals: List[DevVal]) -> jnp.ndarray:
    """bool[cap]: row i's key tuple exactly equals row i-1's (False at i=0).

    Used by sort-based groupby for exact segment boundaries.  Strings
    compare by (length, prefix words, dual 32-bit polynomial full hash) —
    an engineered-collision risk only, comparable to the reference
    partitioning on 32-bit murmur3."""
    cap = int(vals[0].validity.shape[0])
    eq = jnp.ones(cap, dtype=jnp.bool_)

    def shift_ne(x):
        prev = jnp.concatenate([x[:1], x[:-1]])
        return x != prev

    for v in vals:
        eq = eq & ~shift_ne(v.validity)
        if v.dtype.is_string:
            from spark_rapids_tpu.exprs.strings import (
                string_hash2, string_lengths,
            )
            lens = string_lengths(v)
            h1, h2 = string_hash2(v)
            cmp_words = [lens, h1, h2] + string_prefix_words(
                v, DEFAULT_STRING_PREFIX_BYTES)
            for x in cmp_words:
                same = ~shift_ne(x)
                eq = eq & jnp.where(v.validity, same, True)
        else:
            for w in _encode_fixed_words(v):
                same = ~shift_ne(w)
                eq = eq & jnp.where(v.validity, same, True)
    eq = eq.at[0].set(False)
    return eq
