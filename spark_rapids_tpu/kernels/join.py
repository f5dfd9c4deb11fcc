"""Equi-join kernels (cudf ``Table.onColumns(...).{inner,left,...}Join``
analogue, shims/spark300/GpuHashJoin.scala:282-308).

TPU-first design: no hash table.  The build side is *sorted by a 64-bit key
hash*; each probe row locates its candidate range with two ``searchsorted``
calls; candidates are verified by exact key comparison.  Output size is
data-dependent, so the join runs in two phases (SURVEY.md section 7's
bucketed-padded-batch recipe):

  phase 1 (jit, static shapes): per-probe candidate counts -> total pairs
           (+ unmatched-row counts for outer joins) -> host reads 3 scalars
  phase 2 (jit, static output capacity chosen by host): expand the pair list
           via searchsorted-on-cumsum, verify matches, compact, gather both
           sides' rows, stitch the output batch.

NULL equi-join keys never match (SQL semantics), including null==null.

Join types: inner, left, right, full, left_semi, left_anti, cross.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import ColumnBatch, round_up_capacity
from spark_rapids_tpu.utils.compile_registry import instrumented_jit
from spark_rapids_tpu.utils.tracing import device_read, kernel_scope
from spark_rapids_tpu.exprs.base import DevVal
from spark_rapids_tpu.kernels.layout import (
    compaction_indices, ensure_row_layout, gather_rows,
)

_TALLY = threading.local()


class JoinTally:
    """What the joins run inside a :func:`tally` block read back to the
    host: ``pairs``, the candidate pairs the probes counted (the totals
    the host needs to size phase 2 anyway), and ``reads``, the
    ``device_read``s named ``join_*``, each a round trip the host waits
    out.  The operator that runs the join adds them to its metrics
    (``joinPairs``, ``joinSizeReads``)."""

    __slots__ = ("pairs", "reads")

    def __init__(self):
        self.pairs = 0
        self.reads = 0


@contextlib.contextmanager
def tally():
    outer = getattr(_TALLY, "open", None)
    t = _TALLY.open = JoinTally()
    try:
        yield t
    finally:
        _TALLY.open = outer


def _size_read(name: str, tree):
    """``device_read`` of a size the join needs on the host, counted; the
    read named ``join_pairs`` is the candidate-pair total."""
    value = device_read(name, tree)
    t = getattr(_TALLY, "open", None)
    if t is not None:
        t.reads += 1
        if name == "join_pairs":
            t.pairs += int(value)
    return value


_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)


def _mix32(h, w):
    k = (w * _C1)
    k = (k << jnp.uint32(15)) | (k >> jnp.uint32(17))
    k = k * _C2
    h = h ^ k
    h = (h << jnp.uint32(13)) | (h >> jnp.uint32(19))
    return h * jnp.uint32(5) + jnp.uint32(0xE6546B64)


def _key_hash2(vals: List[DevVal], code_over: Optional[list] = None):
    """(h1 u32[cap], h2 u32[cap], all_valid bool[cap]) over the key columns.

    Two independent 32-bit hashes (native on TPU — no u64 emulation).  The
    build side sorts by (h1, h2); probes range-scan on h1 and verify
    exactly.  Rows with any NULL key get sentinel ~0 hashes (sort last,
    never matched — SQL null-key semantics).

    ``code_over`` (encoded corridor v2, docs/io.md): per-column aligned
    canonical code arrays from :func:`align_dict_codes`.  A column with an
    override hashes ONE int32 word per row instead of its string content —
    valid because aligned codes are equal exactly when contents are equal.
    Hash VALUES differ from content hashing, but the join's pair order
    does not depend on them (equal keys hash equal either way, and
    equal-hash build rows keep their stable original order), so results
    stay bit-identical."""
    cap = int(vals[0].validity.shape[0])
    h1 = jnp.full(cap, jnp.uint32(0x12345678))
    h2 = jnp.full(cap, jnp.uint32(0x9E3779B9))
    ok = jnp.ones(cap, dtype=jnp.bool_)
    for ki, v in enumerate(vals):
        ok = ok & v.validity
        over = code_over[ki] if code_over is not None else None
        if over is not None:
            words = [over.astype(jnp.uint32)]
        elif v.dtype.is_string:
            from spark_rapids_tpu.exprs.strings import (
                string_hash2, string_lengths,
            )
            s1, s2 = string_hash2(v)
            words = [s1, s2, string_lengths(v).astype(jnp.uint32)]
        else:
            from spark_rapids_tpu.kernels.sortkeys import \
                _encode_fixed_words
            words = _encode_fixed_words(v)
        for w in words:
            h1 = _mix32(h1, w)
            h2 = _mix32(h2, w ^ jnp.uint32(0xA5A5A5A5))
    sentinel = ~jnp.uint32(0)
    return (jnp.where(ok, h1, sentinel), jnp.where(ok, h2, sentinel), ok)


def _exact_eq(a_vals: List[DevVal], a_idx, b_vals: List[DevVal], b_idx,
              code_over: Optional[list] = None):
    """Exact key equality for gathered index pairs (both sides valid).

    ``code_over``: per-column (a_codes, b_codes) pairs of ALIGNED
    canonical codes — equality is then one int32 compare per pair, and it
    is EXACT (no residual hash-collision risk), since aligned codes are
    equal iff entry contents are equal."""
    eq = jnp.ones(a_idx.shape, dtype=jnp.bool_)
    for ki, (va, vb) in enumerate(zip(a_vals, b_vals)):
        eq = eq & va.validity[a_idx] & vb.validity[b_idx]
        over = code_over[ki] if code_over is not None else None
        if over is not None:
            oa, ob = over
            eq = eq & (oa[a_idx] == ob[b_idx])
        elif va.dtype.is_string:
            from spark_rapids_tpu.exprs.strings import (
                string_hash2, string_lengths,
            )
            from spark_rapids_tpu.kernels.sortkeys import (
                DEFAULT_STRING_PREFIX_BYTES, string_prefix_words,
            )
            la = string_lengths(va)[a_idx]
            lb = string_lengths(vb)[b_idx]
            a1, a2 = string_hash2(va)
            b1, b2 = string_hash2(vb)
            eq = eq & (la == lb) & (a1[a_idx] == b1[b_idx]) & \
                (a2[a_idx] == b2[b_idx])
            # Also compare the first 64 bytes exactly: a false match now
            # needs simultaneous collision of both 32-bit hashes AND an
            # identical 64-byte prefix + length — residual risk documented
            # in docs/compatibility.md.
            for wa, wb in zip(
                    string_prefix_words(va, DEFAULT_STRING_PREFIX_BYTES),
                    string_prefix_words(vb, DEFAULT_STRING_PREFIX_BYTES)):
                eq = eq & (wa[a_idx] == wb[b_idx])
        else:
            from spark_rapids_tpu.kernels.sortkeys import \
                _encode_fixed_words
            for wa, wb in zip(_encode_fixed_words(va),
                              _encode_fixed_words(vb)):
                eq = eq & (wa[a_idx] == wb[b_idx])
    return eq


def _exact_words(vals: List[DevVal], code_over: Optional[list] = None):
    """Pre-encoded u32 word matrix + combined validity for one side's key
    columns: ``(words u32[W, cap], valid bool[cap])``.

    Word-for-word the comparisons :func:`_exact_eq` performs — aligned
    codes (bit-preserving int32->u32 cast), string length + dual hashes +
    64-byte prefix words, :func:`_encode_fixed_words` for fixed types —
    so ``valid[a] & valid[b] & AND_w(words_a[w, a] == words_b[w, b])``
    equals ``_exact_eq`` at any index pair.  This is the layout the
    kernel tier's join-probe kernel keeps VMEM-resident."""
    cap = int(vals[0].validity.shape[0])
    valid = jnp.ones(cap, dtype=jnp.bool_)
    words: List[jnp.ndarray] = []
    for ki, v in enumerate(vals):
        valid = valid & v.validity
        over = code_over[ki] if code_over is not None else None
        if over is not None:
            words.append(over.astype(jnp.uint32))
        elif v.dtype.is_string:
            from spark_rapids_tpu.exprs.strings import (
                string_hash2, string_lengths,
            )
            from spark_rapids_tpu.kernels.sortkeys import (
                DEFAULT_STRING_PREFIX_BYTES, string_prefix_words,
            )
            s1, s2 = string_hash2(v)
            words += [string_lengths(v).astype(jnp.uint32), s1, s2]
            words += string_prefix_words(v, DEFAULT_STRING_PREFIX_BYTES)
        else:
            from spark_rapids_tpu.kernels.sortkeys import \
                _encode_fixed_words
            words += _encode_fixed_words(v)
    return jnp.stack(words), valid


def _exact_word_count(vals: List[DevVal],
                      code_over: Optional[list] = None) -> int:
    """Static W of :func:`_exact_words` (for VMEM budgeting before any
    array is built)."""
    from spark_rapids_tpu.kernels.sortkeys import (
        DEFAULT_STRING_PREFIX_BYTES,
    )
    n = 0
    for ki, v in enumerate(vals):
        over = code_over[ki] if code_over is not None else None
        if over is not None:
            n += 1
        elif v.dtype.is_string:
            n += 3 + (DEFAULT_STRING_PREFIX_BYTES + 3) // 4
        elif v.dtype in (T.LONG, T.TIMESTAMP):
            n += 2
        elif v.dtype == T.DOUBLE:
            # backend-dependent: 2 bitcast words on real-f64 hosts, 3
            # float-float words on TPU (_encode_double_words)
            n += 3 if jax.default_backend() == "tpu" else 2
        else:
            n += 1
    return n


#: Entry-pair table guard for :func:`align_dict_codes`: alignment builds
#: an [nd_a, nd_b] boolean content-equality grid; past this many cells
#: the memory/FLOP cost beats rehashing content through the codes, so
#: the caller falls back to content mode (still encoded, still exact
#: under the same residual-collision policy as plain string joins).
DICT_ALIGN_MAX_CELLS = 1 << 22


def _entry_eq_matrix(ent_a: DevVal, ent_b: DevVal):
    """[nd_a, nd_b] bool: dictionary entry contents equal.  Same equality
    policy as :func:`_exact_eq`'s string branch — dual 32-bit hashes +
    length + exact 64-byte prefix — applied entry-vs-entry."""
    from spark_rapids_tpu.exprs.strings import string_hash2
    from spark_rapids_tpu.kernels.sortkeys import (
        DEFAULT_STRING_PREFIX_BYTES, string_prefix_words,
    )
    a1, a2 = string_hash2(ent_a)
    b1, b2 = string_hash2(ent_b)
    la = (ent_a.offsets[1:] - ent_a.offsets[:-1]).astype(jnp.int32)
    lb = (ent_b.offsets[1:] - ent_b.offsets[:-1]).astype(jnp.int32)
    eq = (a1[:, None] == b1[None, :]) & (a2[:, None] == b2[None, :]) & \
        (la[:, None] == lb[None, :])
    for wa, wb in zip(
            string_prefix_words(ent_a, DEFAULT_STRING_PREFIX_BYTES),
            string_prefix_words(ent_b, DEFAULT_STRING_PREFIX_BYTES)):
        eq = eq & (wa[:, None] == wb[None, :])
    return eq


def _entries_of(v: DevVal) -> DevVal:
    nd = int(v.offsets.shape[0]) - 1
    return DevVal(v.dtype, v.data, jnp.ones(nd, dtype=jnp.bool_), v.offsets)


def align_dict_codes(lv: DevVal, rv: DevVal,
                     max_cells: int = DICT_ALIGN_MAX_CELLS):
    """Rendezvous alignment of two dictionary-encoded key columns into one
    canonical code space, so the join can hash/compare int32 codes.

    Returns ``(l_codes, r_codes)`` int32[cap] arrays where equal values
    mean equal string contents, or ``None`` when either side is not
    encoded or the entry-pair table would exceed ``max_cells``.

    Both sides canonicalize against the LARGER dictionary (the "dst"):
    every entry maps to the FIRST content-equal dst entry (argmax over the
    content-equality grid), which also collapses duplicate entries —
    shuffle-merged dictionaries legitimately repeat entries across their
    input pieces, so raw codes are NOT comparable even within one
    dictionary.  A src entry absent from dst maps to the distinct
    negative code ``-1 - entry`` (never equal to any canonical dst code,
    and rows sharing that src entry cannot match any dst row — its
    content does not exist on the other side).  Shared-dictionary sides
    (``data``/``offsets`` the same objects — the scan corridor's common
    case) skip the cross table and self-canonicalize once.  Invalid rows
    pass through masked by validity downstream, as everywhere else."""
    if lv.codes is None or rv.codes is None:
        return None
    nd_l = int(lv.offsets.shape[0]) - 1
    nd_r = int(rv.offsets.shape[0]) - 1
    if nd_l == 0 or nd_r == 0:
        return None

    def row_codes(v, mapping, nd):
        codes_c = jnp.clip(v.codes, 0, max(nd - 1, 0))
        return mapping[codes_c].astype(jnp.int32)

    shared = lv.data is rv.data and lv.offsets is rv.offsets
    if shared:
        if nd_l * nd_l > max_cells:
            return None
        ent = _entries_of(lv)
        canon = jnp.argmax(_entry_eq_matrix(ent, ent),
                           axis=1).astype(jnp.int32)
        return row_codes(lv, canon, nd_l), row_codes(rv, canon, nd_r)
    if nd_l * nd_r + max(nd_l, nd_r) ** 2 > max_cells:
        return None
    # translate the smaller dictionary into the larger's code space
    src, dst, src_is_left = (lv, rv, True) if nd_l <= nd_r else \
        (rv, lv, False)
    nd_src, nd_dst = (nd_l, nd_r) if src_is_left else (nd_r, nd_l)
    ent_src, ent_dst = _entries_of(src), _entries_of(dst)
    canon_dst = jnp.argmax(_entry_eq_matrix(ent_dst, ent_dst),
                           axis=1).astype(jnp.int32)
    cross = _entry_eq_matrix(ent_src, ent_dst)
    found = jnp.any(cross, axis=1)
    # argmax picks the FIRST content-equal dst entry — already canonical
    mapped = jnp.where(found, jnp.argmax(cross, axis=1).astype(jnp.int32),
                       -1 - jnp.arange(nd_src, dtype=jnp.int32))
    src_codes = row_codes(src, mapped, nd_src)
    dst_codes = row_codes(dst, canon_dst, nd_dst)
    return (src_codes, dst_codes) if src_is_left else \
        (dst_codes, src_codes)


@dataclasses.dataclass
class JoinSizing:
    """Host-visible scalars from phase 1 (+ device arrays reused by phase 2)."""

    total_pairs: int
    probe_cap: int
    build_cap: int


@kernel_scope
def _phase1(probe_h1, probe_ok, probe_live, build_sorted_h1, build_live_n):
    # candidate ranges on h1 only (h2 + exact keys verified in phase 2)
    lo = jnp.searchsorted(build_sorted_h1, probe_h1, side="left")
    hi = jnp.searchsorted(build_sorted_h1, probe_h1, side="right")
    counts = jnp.where(probe_ok & probe_live, hi - lo, 0).astype(jnp.int32)
    return lo.astype(jnp.int32), counts, jnp.sum(counts)


_phase1_jit = instrumented_jit(_phase1, label="join:phase1")


@kernel_scope
def _build_sort(h1, h2):
    cap = int(h1.shape[0])
    iota = jnp.arange(cap, dtype=jnp.int32)
    s1, _s2, perm = jax.lax.sort((h1, h2, iota), num_keys=2, is_stable=True)
    return perm, s1


_build_sort_jit = instrumented_jit(_build_sort, label="join:build_sort")


@kernel_scope
def _phase2(lo, counts, perm, l_keys, r_keys, code_pairs, total, pair_cap):
    """Expand the candidate ranges into ``pair_cap`` (static: the host's
    bucket of the phase-1 total) pairs, verify them, compact the matches
    to the front.  One program a shape, kept by the process like phase 1:
    a closure jitted inside :func:`join_pairs` was traced, lowered and
    compiled (or loaded) again by every join of every query."""
    l_cap = int(l_keys[0].validity.shape[0])
    r_cap = int(r_keys[0].validity.shape[0])
    cum = jnp.cumsum(counts)
    starts = cum - counts
    k = jnp.arange(pair_cap, dtype=jnp.int32)
    probe_row = jnp.searchsorted(cum, k, side="right").astype(jnp.int32)
    probe_row = jnp.clip(probe_row, 0, l_cap - 1)
    ordinal = (k - starts[probe_row]).astype(jnp.int32)
    build_pos = jnp.clip(lo[probe_row] + ordinal, 0, r_cap - 1)
    build_row = perm[build_pos]
    in_range = k < total
    match = in_range & _exact_eq(l_keys, probe_row, r_keys, build_row,
                                 code_pairs)
    # compact matches to the front
    order = jnp.argsort(jnp.where(match, 0, 1), stable=True)
    n_pairs = jnp.sum(match).astype(jnp.int32)
    l_idx = probe_row[order]
    r_idx = build_row[order]
    # per-left-row match counts + right matched flags (for outer joins)
    ones = match.astype(jnp.int32)
    l_counts = jax.ops.segment_sum(ones, probe_row, num_segments=l_cap,
                                   indices_are_sorted=True)
    r_matched = jax.ops.segment_max(
        ones, build_row, num_segments=r_cap) > 0
    return l_idx.astype(jnp.int32), r_idx.astype(jnp.int32), n_pairs, \
        l_counts, r_matched


_phase2_jit = instrumented_jit(_phase2, label="join:phase2",
                               static_argnames=("pair_cap",))


@kernel_scope
def join_pairs(left_keys: List[DevVal], left_num_rows,
               right_keys: List[DevVal], right_num_rows,
               pair_cap_hint: Optional[int] = None):
    """Compute matching (left_idx, right_idx) pair arrays.

    Returns (l_idx i32[pair_cap], r_idx i32[pair_cap], n_pairs i32 scalar,
    l_match_counts i64[l_cap], r_matched bool[r_cap]).  Pairs are compacted to
    the front.  Host sync: one scalar read for sizing.
    """
    l_cap = int(left_keys[0].validity.shape[0])
    r_cap = int(right_keys[0].validity.shape[0])
    l_live = jnp.arange(l_cap, dtype=jnp.int32) < left_num_rows
    r_live = jnp.arange(r_cap, dtype=jnp.int32) < right_num_rows

    # Encoded corridor v2: when both sides of a key column arrive
    # dictionary-encoded, align their codes once (eager — the decision
    # depends on host-known dictionary shapes) and hash/compare int32
    # codes instead of string content.  Per column: override on BOTH
    # sides or neither, so the hashes stay symmetric.
    l_over: List[Optional[jnp.ndarray]] = []
    r_over: List[Optional[jnp.ndarray]] = []
    for lv, rv in zip(left_keys, right_keys):
        pair = align_dict_codes(lv, rv)
        l_over.append(None if pair is None else pair[0])
        r_over.append(None if pair is None else pair[1])
    any_over = any(o is not None for o in l_over)

    l_h1, l_h2, l_ok = _key_hash2(left_keys, l_over if any_over else None)
    r_h1, r_h2, r_ok = _key_hash2(right_keys, r_over if any_over else None)
    sentinel = ~jnp.uint32(0)
    r_h1 = jnp.where(r_live & r_ok, r_h1, sentinel)
    perm, r_sorted = _build_sort_jit(r_h1, r_h2)
    # Sentinel rows (~0 hash) are never matched because probe rows with ok
    # hash ~0 are masked by probe_ok in phase 1.
    lo, counts, total = _phase1_jit(l_h1, l_ok, l_live, r_sorted,
                                    right_num_rows)

    total_pairs = int(_size_read("join_pairs", total))
    pair_cap = round_up_capacity(max(total_pairs, 1))
    if pair_cap_hint is not None:
        pair_cap = max(pair_cap, pair_cap_hint)

    # aligned codes ride into phase 2 as bare arrays (a None column is a
    # valid empty pytree) — NEVER wrapped in DevVals, where a stray
    # materialization would clip the -1-i sentinels into entry 0
    code_pairs = [None if a is None else (a, b)
                  for a, b in zip(l_over, r_over)] if any_over else None

    return _phase2_jit(lo, counts, perm, left_keys, right_keys, code_pairs,
                       total, pair_cap=pair_cap)


@kernel_scope
def join_pairs_static(left_keys: List[DevVal], left_num_rows,
                      right_keys: List[DevVal], right_num_rows,
                      pair_cap: int):
    """Fully-traced :func:`join_pairs`: the pair capacity is a STATIC
    argument chosen by the caller (mesh SPMD fuses the join into one
    ``shard_map`` program, so there is no host to read the phase-1 total).

    Returns ``(l_idx, r_idx, n_pairs, l_counts, r_matched, overflow)``
    where ``overflow`` is a traced bool: the true pair total exceeded
    ``pair_cap``.  On overflow the pair list is TRUNCATED (results are
    wrong) — the caller must check the flag and fall back to the
    host-driven two-phase path.  Safe inside ``jax.jit`` / ``shard_map``.
    """
    l_cap = int(left_keys[0].validity.shape[0])
    r_cap = int(right_keys[0].validity.shape[0])
    l_live = jnp.arange(l_cap, dtype=jnp.int32) < left_num_rows
    r_live = jnp.arange(r_cap, dtype=jnp.int32) < right_num_rows

    # encoded corridor: alignment decisions depend only on host-known
    # dictionary shapes / object identity, so they are trace-safe
    l_over: List[Optional[jnp.ndarray]] = []
    r_over: List[Optional[jnp.ndarray]] = []
    for lv, rv in zip(left_keys, right_keys):
        pair = align_dict_codes(lv, rv)
        l_over.append(None if pair is None else pair[0])
        r_over.append(None if pair is None else pair[1])
    any_over = any(o is not None for o in l_over)

    l_h1, _l_h2, l_ok = _key_hash2(left_keys, l_over if any_over else None)
    r_h1, r_h2, r_ok = _key_hash2(right_keys, r_over if any_over else None)
    sentinel = ~jnp.uint32(0)
    r_h1 = jnp.where(r_live & r_ok, r_h1, sentinel)
    perm, r_sorted = _build_sort(r_h1, r_h2)

    code_pairs = [None if a is None else (a, b)
                  for a, b in zip(l_over, r_over)] if any_over else None

    def xla_candidates():
        lo, counts, total = _phase1(l_h1, l_ok, l_live, r_sorted,
                                    right_num_rows)
        total_c = jnp.minimum(total, pair_cap)
        cum = jnp.cumsum(counts)
        starts = cum - counts
        k = jnp.arange(pair_cap, dtype=jnp.int32)
        probe_row = jnp.searchsorted(cum, k, side="right").astype(jnp.int32)
        probe_row = jnp.clip(probe_row, 0, l_cap - 1)
        ordinal = (k - starts[probe_row]).astype(jnp.int32)
        build_pos = jnp.clip(lo[probe_row] + ordinal, 0, r_cap - 1)
        build_row = perm[build_pos]
        in_range = k < total_c
        match = in_range & _exact_eq(left_keys, probe_row, right_keys,
                                     build_row, code_pairs)
        return probe_row, build_row, match, total

    def pallas_candidates(interpret):
        a_words, a_valid = _exact_words(left_keys,
                                        l_over if any_over else None)
        b_words, b_valid = _exact_words(right_keys,
                                        r_over if any_over else None)
        from spark_rapids_tpu.kernels import pallas_tier as PT
        return PT.probe_join(l_h1, l_ok & l_live, r_sorted, perm,
                             a_words, a_valid, b_words, b_valid,
                             pair_cap, interpret=interpret)

    # VMEM residency: the sorted build hashes, the permutation, the build
    # word matrix and validity must all stay resident for the fused probe
    from spark_rapids_tpu.kernels import pallas_tier as PT
    n_words = _exact_word_count(right_keys, r_over if any_over else None)
    resident = r_cap * (4 + 4 + 4 * n_words + 4)
    probe_row, build_row, match, total = PT.run(
        "joinProbe", pallas_candidates, xla_candidates,
        resident_bytes=resident)
    overflow = total > pair_cap
    order = jnp.argsort(jnp.where(match, 0, 1), stable=True)
    n_pairs = jnp.sum(match).astype(jnp.int32)
    l_idx = probe_row[order].astype(jnp.int32)
    r_idx = build_row[order].astype(jnp.int32)
    ones = match.astype(jnp.int32)
    l_counts = jax.ops.segment_sum(ones, probe_row, num_segments=l_cap,
                                   indices_are_sorted=True)
    r_matched = jax.ops.segment_max(ones, build_row,
                                    num_segments=r_cap) > 0
    return l_idx, r_idx, n_pairs, l_counts, r_matched, overflow


def _static_byte_caps(batch: ColumnBatch, growth: float,
                      out_cap: int = 0) -> List[int]:
    """Static growth-scaled output byte capacities per varlen column.

    A join gather can DUPLICATE one side's rows up to the pair count (a
    6-row build side probed by 200 rows emits its strings ~200 times),
    so input bytes alone under-size wildly: scale by the row expansion
    ``out_cap / capacity`` too — growth x expansion x input bytes holds
    as long as the duplicated rows' average length stays within growth of
    the input average; the in-program needed-bytes check catches the
    adversarial tail.  ``batch`` must already be in row layout."""
    expand = max(1.0, out_cap / batch.capacity) if out_cap else 1.0
    return [round_up_capacity(
        max(int(int(c.data.shape[0]) * growth * expand), 1), minimum=16)
        for c in batch.columns if c.is_varlen]


def _needed_bytes(batch: ColumnBatch, indices, live) -> List[jnp.ndarray]:
    """Traced per-varlen-column byte totals a gather at ``indices`` needs
    (the in-program sibling of :func:`_string_byte_caps` — no host sync).
    ``batch`` must already be in row layout."""
    needs = []
    for c in batch.columns:
        if c.is_varlen:
            lens = (c.offsets[1:] - c.offsets[:-1]).astype(jnp.int32)
            needs.append(jnp.sum(jnp.where(
                live, lens[jnp.clip(indices, 0, batch.capacity - 1)], 0)))
    return needs


def _caps_overflow(needs: List[jnp.ndarray], caps: List[int]):
    """Traced bool: any needed byte total exceeds its static capacity.
    Mandatory check — :func:`gather_rows` silently truncates varlen data
    past the byte cap (its ``in_range`` mask), so an undetected overflow
    would corrupt output instead of failing."""
    ovf = jnp.asarray(False)
    for need, cap in zip(needs, caps):
        ovf = ovf | (need > cap)
    return ovf


@kernel_scope
def stitch_join_output_static(left: ColumnBatch, right: ColumnBatch,
                              l_idx, r_idx, n_pairs, l_counts, r_matched,
                              join_type: str, out_schema: T.Schema,
                              growth: float):
    """Traced :func:`stitch_join_output` with STATIC output capacities.

    Row capacities: semi/anti at the left capacity (a filter — can never
    overflow); inner at the pair capacity; outer at
    ``round_up_capacity(pair_cap + l_cap + r_cap)`` (pairs plus every
    possibly-unmatched row — also exact, never overflows).  Varlen byte
    capacities are growth-scaled static buckets with an in-program
    needed-bytes check.  Returns ``(batch, overflow)``; on overflow the
    batch content is invalid and the caller must fall back."""
    left = ensure_row_layout(left)
    right = ensure_row_layout(right)
    l_cap, r_cap = left.capacity, right.capacity
    pair_cap = int(l_idx.shape[0])
    l_live = jnp.arange(l_cap, dtype=jnp.int32) < left.num_rows
    r_live = jnp.arange(r_cap, dtype=jnp.int32) < right.num_rows
    no_ovf = jnp.asarray(False)

    if join_type in ("left_semi", "left_anti"):
        if join_type == "left_semi":
            mask = l_live & (l_counts > 0)
        else:
            mask = l_live & (l_counts == 0)
        idx, count = compaction_indices(mask, left.num_rows)
        # pure row filter of the left side: default caps exact, no overflow
        return gather_rows(left, idx, count), no_ovf

    if join_type == "inner":
        live = jnp.arange(pair_cap, dtype=jnp.int32) < n_pairs
        lcaps = _static_byte_caps(left, growth, out_cap=pair_cap)
        rcaps = _static_byte_caps(right, growth, out_cap=pair_cap)
        ovf = _caps_overflow(_needed_bytes(left, l_idx, live), lcaps) | \
            _caps_overflow(_needed_bytes(right, r_idx, live), rcaps)
        lg = gather_rows(left, l_idx, n_pairs, out_capacity=pair_cap,
                         out_byte_caps=lcaps or None)
        rg = gather_rows(right, r_idx, n_pairs, out_capacity=pair_cap,
                         out_byte_caps=rcaps or None)
        return ColumnBatch(out_schema, list(lg.columns) + list(rg.columns),
                           n_pairs, pair_cap), ovf

    if join_type in ("left", "right", "full"):
        add_left = join_type in ("left", "full")
        add_right = join_type in ("right", "full")
        un_l_mask = l_live & (l_counts == 0) if add_left else \
            jnp.zeros(l_cap, dtype=jnp.bool_)
        un_r_mask = r_live & ~r_matched if add_right else \
            jnp.zeros(r_cap, dtype=jnp.bool_)
        n_un_l = jnp.sum(un_l_mask).astype(jnp.int32)
        n_un_r = jnp.sum(un_r_mask).astype(jnp.int32)
        total = n_pairs + n_un_l + n_un_r
        out_cap = round_up_capacity(pair_cap + l_cap + r_cap)

        un_l_idx, _ = compaction_indices(un_l_mask, left.num_rows)
        un_r_idx, _ = compaction_indices(un_r_mask, right.num_rows)

        i = jnp.arange(out_cap, dtype=jnp.int32)
        in_pairs = i < n_pairs
        in_un_l = (i >= n_pairs) & (i < n_pairs + n_un_l)
        li = jnp.where(in_pairs, l_idx[jnp.clip(i, 0, pair_cap - 1)],
                       un_l_idx[jnp.clip(i - n_pairs, 0, l_cap - 1)])
        li = jnp.where(in_un_l | in_pairs, li, 0)
        l_valid = in_pairs | in_un_l
        ri = jnp.where(in_pairs, r_idx[jnp.clip(i, 0, pair_cap - 1)],
                       un_r_idx[jnp.clip(i - n_pairs - n_un_l, 0,
                                         r_cap - 1)])
        in_un_r = (i >= n_pairs + n_un_l) & (i < n_pairs + n_un_l + n_un_r)
        ri = jnp.where(in_pairs | in_un_r, ri, 0)
        r_valid = in_pairs | in_un_r

        live = jnp.arange(out_cap, dtype=jnp.int32) < total
        # needed = matched pairs' bytes + unmatched rows' bytes; unmatched
        # rows alone can fill a whole input, so scale by growth + 1.
        # The needed-bytes mask is `live` alone (matching the gather,
        # which copies row 0's bytes for null-padded rows) — masking by
        # validity too would let a truncation slip past the overflow check
        lcaps = _static_byte_caps(left, growth + 1.0, out_cap=out_cap)
        rcaps = _static_byte_caps(right, growth + 1.0, out_cap=out_cap)
        ovf = _caps_overflow(
            _needed_bytes(left, jnp.where(l_valid, li, 0), live),
            lcaps) | _caps_overflow(
            _needed_bytes(right, jnp.where(r_valid, ri, 0), live),
            rcaps)
        lg = gather_rows(left, jnp.where(l_valid, li, 0), total,
                         out_capacity=out_cap, out_byte_caps=lcaps or None)
        rg = gather_rows(right, jnp.where(r_valid, ri, 0), total,
                         out_capacity=out_cap, out_byte_caps=rcaps or None)
        lcols = [type(c)(c.dtype, c.data, c.validity & l_valid, c.offsets)
                 for c in lg.columns]
        rcols = [type(c)(c.dtype, c.data, c.validity & r_valid, c.offsets)
                 for c in rg.columns]
        return ColumnBatch(out_schema, lcols + rcols, total, out_cap), ovf

    raise ValueError(f"unsupported join type: {join_type}")


def hash_join_static(left: ColumnBatch, left_keys: List[DevVal],
                     right: ColumnBatch, right_keys: List[DevVal],
                     join_type: str, out_schema: T.Schema,
                     growth: float = 2.0):
    """Fully-traced equi-join with capacity-bucketed output sizing (no
    host sync — the mesh-SPMD fused path).  The pair capacity is the
    BucketPolicy quantization of ``left.capacity * growth``; residual
    conditions are NOT supported (they host-sync for byte sizing — the
    lowering gates on ``condition is None``).  Returns
    ``(batch, overflow)``: on overflow the caller must discard the batch
    and rerun the stage host-driven."""
    pair_cap = round_up_capacity(max(int(left.capacity * growth), 1))
    l_idx, r_idx, n_pairs, l_counts, r_matched, ovf = join_pairs_static(
        left_keys, left.num_rows, right_keys, right.num_rows, pair_cap)
    out, ovf2 = stitch_join_output_static(
        left, right, l_idx, r_idx, n_pairs, l_counts, r_matched,
        join_type, out_schema, growth)
    return out, ovf | ovf2


def _string_byte_caps(batch: ColumnBatch, indices, live) -> List[int]:
    """Host-sync sizing of output byte capacities for string columns.

    Encoded columns size at their MATERIALIZED per-row lengths (entry
    lengths gathered through clipped codes, NULL rows zero) — the output
    gather materializes, and these caps must match encoded-off bit for
    bit."""
    caps = []
    for c in batch.columns:
        if c.is_string:
            if c.codes is not None:
                nd = int(c.offsets.shape[0]) - 1
                ent_lens = (c.offsets[1:] - c.offsets[:-1]).astype(jnp.int64)
                codes_c = jnp.clip(c.codes, 0, max(nd - 1, 0))
                lens = jnp.where(c.validity, ent_lens[codes_c], 0)
            else:
                lens = (c.offsets[1:] - c.offsets[:-1]).astype(jnp.int64)
            total = jnp.sum(jnp.where(live, lens[jnp.clip(
                indices, 0, batch.capacity - 1)], 0))
            caps.append(round_up_capacity(int(_size_read("join_bytes", total)),
                                          minimum=16))
    return caps


def _filter_pairs(left: ColumnBatch, right: ColumnBatch, l_idx, r_idx,
                  n_pairs, condition):
    """Apply a residual join condition to the matched pairs BEFORE any
    null-padding (GpuHashJoin.scala:265-271: the condition gates matches,
    so a row whose every match fails becomes an *unmatched* outer row).

    Only the columns the condition references are gathered.  Returns the
    filtered (l_idx, r_idx, n_pairs, l_counts, r_matched).
    """
    from spark_rapids_tpu.exprs.base import TpuEvalCtx
    pair_cap = int(l_idx.shape[0])
    l_cap, r_cap = left.capacity, right.capacity
    refs = set(condition.references)
    live = jnp.arange(pair_cap, dtype=jnp.int32) < n_pairs

    fields, cols = [], []
    for side, idx in ((left, l_idx), (right, r_idx)):
        for f, c in zip(side.schema.fields, side.columns):
            if f.name not in refs:
                continue
            sub = ColumnBatch(T.Schema([f]), [c], side.num_rows,
                              side.capacity)
            bcaps = _string_byte_caps(sub, idx, live)
            g = gather_rows(sub, idx, n_pairs, out_capacity=pair_cap,
                            out_byte_caps=bcaps or None)
            fields.append(f)
            cols.append(g.columns[0])
    paired = ColumnBatch(T.Schema(fields), cols, n_pairs, pair_cap)
    v = condition.tpu_eval(TpuEvalCtx(paired))
    keep = live & v.validity & v.data.astype(jnp.bool_)

    order = jnp.argsort(jnp.where(keep, 0, 1), stable=True).astype(jnp.int32)
    new_n = jnp.sum(keep).astype(jnp.int32)
    new_l = l_idx[order]
    new_r = r_idx[order]
    ones = keep.astype(jnp.int32)
    l_counts = jax.ops.segment_sum(
        ones, jnp.clip(l_idx, 0, l_cap - 1), num_segments=l_cap)
    r_matched = jax.ops.segment_max(
        ones, jnp.clip(r_idx, 0, r_cap - 1), num_segments=r_cap) > 0
    return new_l, new_r, new_n, l_counts, r_matched


def hash_join(left: ColumnBatch, left_keys: List[DevVal],
              right: ColumnBatch, right_keys: List[DevVal],
              join_type: str, out_schema: T.Schema,
              condition=None) -> ColumnBatch:
    """Full equi-join of two batches.  Output columns = left cols ++ right
    cols (semi/anti: left only), per ``out_schema``.  ``condition`` is an
    optional residual expression applied to matched pairs (before outer
    null-padding, so it changes which rows count as matched)."""
    l_idx, r_idx, n_pairs, l_counts, r_matched = join_pairs(
        left_keys, left.num_rows, right_keys, right.num_rows)
    if condition is not None:
        l_idx, r_idx, n_pairs, l_counts, r_matched = _filter_pairs(
            left, right, l_idx, r_idx, n_pairs, condition)
    return stitch_join_output(left, right, l_idx, r_idx, n_pairs, l_counts,
                              r_matched, join_type, out_schema)


@kernel_scope
def stitch_join_output(left: ColumnBatch, right: ColumnBatch, l_idx, r_idx,
                       n_pairs, l_counts, r_matched, join_type: str,
                       out_schema: T.Schema) -> ColumnBatch:
    """Materialize the joined batch from matched pair index arrays."""
    l_cap, r_cap = left.capacity, right.capacity
    pair_cap = int(l_idx.shape[0])
    l_live = jnp.arange(l_cap, dtype=jnp.int32) < left.num_rows
    r_live = jnp.arange(r_cap, dtype=jnp.int32) < right.num_rows

    if join_type in ("left_semi", "left_anti"):
        if join_type == "left_semi":
            mask = l_live & (l_counts > 0)
        else:
            mask = l_live & (l_counts == 0)
        idx, count = compaction_indices(mask, left.num_rows)
        return gather_rows(left, idx, count)

    if join_type == "inner":
        live = jnp.arange(pair_cap, dtype=jnp.int32) < n_pairs
        lcaps = _string_byte_caps(left, l_idx, live)
        rcaps = _string_byte_caps(right, r_idx, live)
        lg = gather_rows(left, l_idx, n_pairs, out_capacity=pair_cap,
                         out_byte_caps=lcaps or None)
        rg = gather_rows(right, r_idx, n_pairs, out_capacity=pair_cap,
                         out_byte_caps=rcaps or None)
        return ColumnBatch(out_schema, list(lg.columns) + list(rg.columns),
                           n_pairs, pair_cap)

    if join_type in ("left", "right", "full"):
        # Unmatched-left rows (left/full) and unmatched-right rows
        # (right/full) are appended after the matched pairs with the other
        # side NULL-padded.
        add_left = join_type in ("left", "full")
        add_right = join_type in ("right", "full")
        un_l_mask = l_live & (l_counts == 0) if add_left else \
            jnp.zeros(l_cap, dtype=jnp.bool_)
        un_r_mask = r_live & ~r_matched if add_right else \
            jnp.zeros(r_cap, dtype=jnp.bool_)
        n_un_l = jnp.sum(un_l_mask).astype(jnp.int32)
        n_un_r = jnp.sum(un_r_mask).astype(jnp.int32)
        total = n_pairs + n_un_l + n_un_r
        total_h = int(_size_read("join_rows", total))
        out_cap = round_up_capacity(max(total_h, 1))

        un_l_idx, _ = compaction_indices(un_l_mask, left.num_rows)
        un_r_idx, _ = compaction_indices(un_r_mask, right.num_rows)

        @jax.jit
        def stitch_indices(l_idx, r_idx, un_l_idx, un_r_idx, n_pairs, n_un_l,
                           n_un_r):
            i = jnp.arange(out_cap, dtype=jnp.int32)
            in_pairs = i < n_pairs
            in_un_l = (i >= n_pairs) & (i < n_pairs + n_un_l)
            li = jnp.where(in_pairs, l_idx[jnp.clip(i, 0, pair_cap - 1)],
                           un_l_idx[jnp.clip(i - n_pairs, 0, l_cap - 1)])
            li = jnp.where(in_un_l | in_pairs, li, 0)
            l_valid = in_pairs | in_un_l
            ri = jnp.where(in_pairs, r_idx[jnp.clip(i, 0, pair_cap - 1)],
                           un_r_idx[jnp.clip(i - n_pairs - n_un_l, 0,
                                             r_cap - 1)])
            in_un_r = (i >= n_pairs + n_un_l) & (i < n_pairs + n_un_l + n_un_r)
            ri = jnp.where(in_pairs | in_un_r, ri, 0)
            r_valid = in_pairs | in_un_r
            return li, l_valid, ri, r_valid

        li, l_valid, ri, r_valid = stitch_indices(
            l_idx, r_idx, un_l_idx, un_r_idx, n_pairs, n_un_l, n_un_r)
        live = jnp.arange(out_cap, dtype=jnp.int32) < total
        # caps must count what the gather COPIES, not what stays valid:
        # null-padded rows gather row 0's bytes (validity masked after),
        # so size over the zeroed indices with the live mask alone — a
        # `live & valid` mask undersizes and truncates the last real rows
        lcaps = _string_byte_caps(left, jnp.where(l_valid, li, 0), live)
        rcaps = _string_byte_caps(right, jnp.where(r_valid, ri, 0), live)
        # NULL-pad: gather with index 0 for padded side, then mask validity.
        lg = gather_rows(left, jnp.where(l_valid, li, 0), total,
                         out_capacity=out_cap, out_byte_caps=lcaps or None)
        rg = gather_rows(right, jnp.where(r_valid, ri, 0), total,
                         out_capacity=out_cap, out_byte_caps=rcaps or None)
        lcols = [type(c)(c.dtype, c.data, c.validity & l_valid, c.offsets)
                 for c in lg.columns]
        rcols = [type(c)(c.dtype, c.data, c.validity & r_valid, c.offsets)
                 for c in rg.columns]
        return ColumnBatch(out_schema, lcols + rcols, total, out_cap)

    raise ValueError(f"unsupported join type: {join_type}")


def cross_join(left: ColumnBatch, right: ColumnBatch,
               out_schema: T.Schema) -> ColumnBatch:
    """Cartesian product (GpuCartesianProductExec analogue)."""
    return nested_loop_join(left, right, "cross", None, out_schema)


def _cross_pairs(left: ColumnBatch, right: ColumnBatch, condition):
    """All-pairs index arrays (optionally condition-filtered):
    (l_idx, r_idx, n_pairs, l_counts, r_matched).  Pair capacity is
    n_l * n_r — callers bound it by chunking the left side."""
    l_cap, r_cap = left.capacity, right.capacity
    n_l, n_r = (int(n) for n in _size_read(
        "join_sides", (left.num_rows, right.num_rows)))
    total = n_l * n_r
    pair_cap = round_up_capacity(max(total, 1))
    i = jnp.arange(pair_cap, dtype=jnp.int32)
    li = jnp.where(n_r > 0, i // max(n_r, 1), 0).astype(jnp.int32)
    ri = jnp.where(n_r > 0, i % max(n_r, 1), 0).astype(jnp.int32)
    n_pairs = jnp.asarray(total, jnp.int32)
    l_live = jnp.arange(l_cap, dtype=jnp.int32) < left.num_rows
    r_live = jnp.arange(r_cap, dtype=jnp.int32) < right.num_rows
    if condition is not None:
        return _filter_pairs(left, right, li, ri, n_pairs, condition)
    l_counts = jnp.where(l_live, n_r, 0).astype(jnp.int32)
    r_matched = r_live & (n_l > 0)
    return li, ri, n_pairs, l_counts, r_matched


def nested_loop_join(left: ColumnBatch, right: ColumnBatch, join_type: str,
                     condition, out_schema: T.Schema) -> ColumnBatch:
    """All-pairs join with an optional condition — every join type
    (GpuBroadcastNestedLoopJoinExec.scala:305: the reference runs outer /
    semi NLJ on device too).  Matched pairs = cross pairs passing the
    condition; unmatched rows null-pad per the join type."""
    li, ri, n_pairs, l_counts, r_matched = _cross_pairs(
        left, right, condition)
    if join_type == "cross":
        join_type = "inner"
    return stitch_join_output(left, right, li, ri, n_pairs, l_counts,
                              r_matched, join_type, out_schema)


def nested_loop_join_streamed(left_chunks, left_empty: ColumnBatch,
                              right: ColumnBatch, join_type: str,
                              condition, out_schema: T.Schema):
    """right/full NLJ with the left side STREAMED in bounded chunks (the
    reference streams broadcast NLJ per stream batch,
    GpuBroadcastNestedLoopJoinExec.scala:305) — no n_l*n_r pair-space
    allocation.  Right-unmatched rows are a property of the WHOLE left
    side, so matched flags accumulate across chunks and the
    left-NULL-padded remainder is emitted once at the end.

    ``left_empty`` is an empty batch of the left schema used for the final
    right-unmatched emission (also correct when ``left_chunks`` is empty).
    Yields one batch per chunk plus the final remainder batch."""
    assert join_type in ("right", "full"), join_type
    r_cap = right.capacity
    acc = jnp.zeros(r_cap, dtype=jnp.bool_)
    # per-chunk: matched pairs (+ left-unmatched padding for 'full' —
    # left rows belong to exactly one chunk, right is fully present)
    per_chunk = "inner" if join_type == "right" else "left"
    for lb in left_chunks:
        li, ri, n_pairs, l_counts, r_matched = _cross_pairs(
            lb, right, condition)
        acc = acc | r_matched
        yield stitch_join_output(lb, right, li, ri, n_pairs, l_counts,
                                 r_matched, per_chunk, out_schema)
    pair1 = round_up_capacity(1)
    zero_idx = jnp.zeros(pair1, jnp.int32)
    yield stitch_join_output(
        left_empty, right, zero_idx, zero_idx,
        jnp.asarray(0, jnp.int32),
        jnp.zeros(left_empty.capacity, jnp.int32), acc, "right",
        out_schema)
