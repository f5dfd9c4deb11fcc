"""Pallas kernel tier: the registry every TPU kernel ships through.

Each kernel declares, in ONE place (:func:`register`):

* a name and its conf gate (``spark.rapids.sql.tpu.pallas.<kernel>.enabled``),
* a backend predicate — compiled on a real TPU backend only, interpret
  mode under ``spark.rapids.sql.tpu.pallas.interpret`` so CPU tests can
  pin bit-identity,
* the XLA formulation it replaces (the splitV2/donation conf-gate
  pattern: the XLA formulation IS the semantics, the kernel is only a
  faster lowering and must be bit-identical) — taken by POLICY only
  (gate off, non-TPU backend, residency over budget), never because an
  engaged kernel failed: an enabled kernel that cannot trace raises
  :class:`PallasKernelError` with the kernel's name, and one the chip's
  compiler refuses fails the enclosing stage's compile (Mosaic lowers
  when the ENCLOSING program is lowered, outside any ``try`` here),
* a per-kernel obs span (site ``pallas``) so ``rapidsprof --critpath``
  attributes each win, and
* a shared VMEM residency budget (``pallas.vmemBudgetBytes``): a kernel
  whose resident working set would not fit falls back.

Call sites route through :func:`run` with two closures — the Pallas
lowering (given the resolved interpret flag) and the XLA fallback.  The
decision is taken at TRACE time (plain Python), so cached executables
skip it entirely; ``fallback_count()`` feeds the session's
``pallasFallbackCount`` metric delta.

The tier is also where the kernel bodies live: rapidslint R9 rejects any
``pl.pallas_call`` outside this file and ``pallas_strings.py``, because a
bare call bypasses the fallback contract, the obs span and the metric.

Kernel families (docs/kernels.md has the layout/VMEM notes):

* ``gatherScatter`` — segmented k-way pack (:func:`pack_segments`), the
  fused replacement for the scatter chains in layout.concat_kway /
  gather_segments_kway;
* ``joinProbe`` — fused hash-join probe (:func:`probe_join`) with a
  VMEM-resident build side, replacing join._phase1 + pair expansion +
  word verify;
* ``stringHash`` — per-row polynomial hashing (:func:`string_hash_rows`)
  over the byte buffer, replacing exprs.strings.string_hash2's
  pow-table + segment-sum formulation;
* ``strings`` — the contains/LIKE scan (kernels.pallas_strings), now
  conf-gated through the tier.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from spark_rapids_tpu.config import (
    PALLAS_GATHER_SCATTER_ENABLED, PALLAS_INTERPRET,
    PALLAS_JOIN_PROBE_ENABLED, PALLAS_STRINGS_ENABLED,
    PALLAS_STRING_HASH_ENABLED, PALLAS_VMEM_BUDGET, RapidsConf,
)


class PallasKernelError(RuntimeError):
    """An ENABLED kernel failed while tracing.  Never retried and never
    replaced by the XLA formulation: a kernel whose gate is on must work
    (fault.errors classifies this NON_RETRYABLE)."""


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One registered kernel-tier entry."""

    name: str
    entry: object  # ConfEntry gating this kernel
    families: str  # what the kernel fuses
    fallback: str  # the XLA formulation it must stay bit-identical to


@dataclasses.dataclass(frozen=True)
class Decision:
    engaged: bool
    interpret: bool
    reason: str  # "" (engaged) | "off" | "backend" | "budget"


_KERNELS: Dict[str, KernelSpec] = {}


def register(name: str, entry, families: str, fallback: str) -> KernelSpec:
    spec = KernelSpec(name, entry, families, fallback)
    _KERNELS[name] = spec
    return spec


def registered() -> List[KernelSpec]:
    return [_KERNELS[k] for k in sorted(_KERNELS)]


_lock = threading.Lock()
_active_conf: Optional[RapidsConf] = None
_fallbacks = 0


def configure(conf: Optional[RapidsConf]) -> None:
    """Install the session conf the tier consults (session.execute does
    this per query, like obs_ts.configure); None reverts to the
    process-wide default conf."""
    global _active_conf
    _active_conf = conf


def _conf() -> RapidsConf:
    if _active_conf is not None:
        return _active_conf
    from spark_rapids_tpu.config import conf as process_conf
    return process_conf


def fallback_count() -> int:
    """Process-wide count of kernel-tier fallbacks taken at trace time
    (backend/budget; conf-off does NOT count — a disabled kernel is
    policy, not a fallback)."""
    return _fallbacks


def _note_fallback() -> None:
    global _fallbacks
    with _lock:
        _fallbacks += 1


def decide(name: str, resident_bytes: int = 0) -> Decision:
    """Pure trace-time gate for one kernel invocation (no counting —
    :func:`run` translates non-"off" reasons into fallback counts)."""
    spec = _KERNELS[name]
    conf = _conf()
    enabled = bool(spec.entry.get(conf))
    interp = bool(PALLAS_INTERPRET.get(conf))
    if not enabled:
        return Decision(False, False, "off")
    if resident_bytes and resident_bytes > PALLAS_VMEM_BUDGET.get(conf):
        # the budget applies in interpret mode too, so CPU tests exercise
        # the same decision the TPU takes
        return Decision(False, False, "budget")
    if interp:
        return Decision(True, True, "")
    if jax.default_backend() == "tpu":
        return Decision(True, False, "")
    return Decision(False, False, "backend")


def run(name: str, pallas_fn: Callable, fallback_fn: Callable,
        resident_bytes: int = 0):
    """Dispatch one kernel invocation through the tier.

    ``pallas_fn(interpret: bool)`` builds the Pallas lowering;
    ``fallback_fn()`` builds the XLA formulation.  Runs at trace time;
    an engaged kernel that fails to trace raises with its name."""
    d = decide(name, resident_bytes)
    if not d.engaged:
        if d.reason != "off":
            _note_fallback()
        return fallback_fn()
    from spark_rapids_tpu.utils.tracing import span
    with span("pallas", name, interpret=d.interpret,
              resident_bytes=resident_bytes):
        try:
            return pallas_fn(d.interpret)
        except Exception as e:
            raise PallasKernelError(
                f"Pallas kernel '{name}' is enabled "
                f"({_KERNELS[name].entry.key}) but "
                f"failed to trace: {type(e).__name__}: {e}") from e


# ---------------------------------------------------------------------------
# gatherScatter: segmented k-way pack
# ---------------------------------------------------------------------------

#: Output elements per program instance (128-aligned).
PACK_BLOCK = 8192

#: Element dtypes the pack kernel lowers; anything else (f64, i64 on x64
#: hosts) silently takes the XLA scatter chain — see docs/kernels.md.
_PACK_DTYPES = ("bool", "uint8", "int32", "uint32", "float32")


def pack_supported(arrays) -> bool:
    return bool(arrays) and all(a.dtype.name in _PACK_DTYPES
                                for a in arrays)


def _iota1d(n: int):
    # 1-D iota does not lower on compiled TPU; 2-D broadcasted_iota does
    return jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)


def _pack_kernel(tab_ref, *refs, k: int, block: int, sizes: tuple):
    out_ref = refs[-1]
    in_refs = refs[:-1]
    i = jnp.int32(0) + _program_id(0)
    p = i * block + _iota1d(block)  # (1, block) output positions
    acc = jnp.zeros((1, block), dtype=out_ref.dtype)
    # static walk of the segment table: position p belongs to input j iff
    # dst_start[j] <= p < dst_start[j+1]; its source index is then
    # lo[j] + (p - dst_start[j]).  Windows are disjoint by construction.
    for j in range(k):
        dst0 = tab_ref[0, j]
        dst1 = tab_ref[0, j + 1]
        src0 = tab_ref[1, j]
        data = in_refs[j][...]
        src = jnp.clip(src0 + (p - dst0), 0, sizes[j] - 1)
        sel = (p >= dst0) & (p < dst1)
        acc = jnp.where(sel, data[src], acc)
    out_ref[...] = acc.reshape((block,))


def _program_id(axis: int):
    from jax.experimental import pallas as pl
    return pl.program_id(axis)


def pack_segments(arrays, los, his, out_cap: int, *, interpret: bool):
    """Pallas k-way segment pack: ``out[dst_j + t] = arrays[j][los[j]+t]``
    for ``t in [0, his[j]-los[j])`` with ``dst_j`` the running total of
    earlier segment lengths; zeros elsewhere.  Bit-identical to
    layout._pack_kway's drop-mode scatter chain — the live window
    [lo, hi) is exactly what the scatters select, so take_head-truncated
    tail bytes can never leak."""
    from jax.experimental import pallas as pl

    k = len(arrays)
    out_dtype = arrays[0].dtype
    is_bool = out_dtype == jnp.bool_
    if is_bool:
        arrays = [a.astype(jnp.uint8) for a in arrays]
    los = [jnp.asarray(lo, jnp.int32) for lo in los]
    his = [jnp.asarray(hi, jnp.int32) for hi in his]
    dst = [jnp.zeros((), jnp.int32)]
    for lo, hi in zip(los, his):
        dst.append(dst[-1] + (hi - lo))
    # segment table (2, k+1) i32: row 0 cumulative dst starts (incl. the
    # total), row 1 source los (padded) — scalar-prefetch shaped, 2-D so
    # SMEM scalar loads stay legal on TPU
    tab = jnp.stack([jnp.stack(dst),
                     jnp.stack(los + [jnp.zeros((), jnp.int32)])])
    padded = -(-out_cap // PACK_BLOCK) * PACK_BLOCK
    nblocks = padded // PACK_BLOCK
    sizes = tuple(int(a.shape[0]) for a in arrays)
    kernel = functools.partial(_pack_kernel, k=k, block=PACK_BLOCK,
                               sizes=sizes)
    in_specs = [pl.BlockSpec(tab.shape, lambda i: (0, 0))]
    for a in arrays:
        in_specs.append(pl.BlockSpec(a.shape, lambda i: (0,)))
    out = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((PACK_BLOCK,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((padded,), arrays[0].dtype),
        interpret=interpret,
    )(tab, *arrays)
    out = out[:out_cap]
    return out != 0 if is_bool else out


# ---------------------------------------------------------------------------
# joinProbe: fused hash-join probe with a VMEM-resident build side
# ---------------------------------------------------------------------------


def _bsearch(sorted_vals, keys, n: int, side_right: bool):
    """Vectorized binary search == jnp.searchsorted(sorted_vals, keys,
    side): fixed-trip branchless bisection (the unique bound index is
    deterministic, so this is bit-identical to the XLA lowering)."""
    lo = jnp.zeros(keys.shape, jnp.int32)
    hi = jnp.full(keys.shape, n, jnp.int32)
    for _ in range(max(int(n).bit_length(), 1)):
        active = lo < hi
        mid = (lo + hi) >> 1
        v = sorted_vals[jnp.clip(mid, 0, n - 1)]
        pred = (v <= keys) if side_right else (v < keys)
        go = active & pred
        lo = jnp.where(go, mid + 1, lo)
        hi = jnp.where(active & ~pred, mid, hi)
    return lo


def _probe_kernel(lh1_ref, lmask_ref, rs_ref, perm_ref, av_ref, bv_ref,
                  aw_ref, bw_ref, pr_ref, br_ref, m_ref, tot_ref, *,
                  l_cap: int, r_cap: int, pair_cap: int, n_words: int):
    lh1 = lh1_ref[...]
    lmask = lmask_ref[...] != 0
    rs = rs_ref[...]
    # fused dual searchsorted (join._phase1) on the sorted build hashes
    lo_idx = _bsearch(rs, lh1, r_cap, side_right=False)
    hi_idx = _bsearch(rs, lh1, r_cap, side_right=True)
    counts = jnp.where(lmask, hi_idx - lo_idx, 0).astype(jnp.int32)
    total = jnp.sum(counts).astype(jnp.int32)
    # candidate expansion (searchsorted-on-cumsum), identical clips to
    # the XLA formulation in join_pairs_static
    cum = jnp.cumsum(counts).astype(jnp.int32)
    starts = cum - counts
    k = _iota1d(pair_cap).reshape((pair_cap,))
    probe_row = jnp.clip(_bsearch(cum, k, l_cap, side_right=True),
                         0, l_cap - 1)
    ordinal = (k - starts[probe_row]).astype(jnp.int32)
    build_pos = jnp.clip(lo_idx[probe_row] + ordinal, 0, r_cap - 1)
    build_row = perm_ref[...][build_pos]
    total_c = jnp.minimum(total, pair_cap)
    in_range = k < total_c
    # exact-match word verify (join._exact_eq, pre-encoded as u32 words)
    eq = (av_ref[...][probe_row] != 0) & (bv_ref[...][build_row] != 0)
    aw = aw_ref[...]
    bw = bw_ref[...]
    for w in range(n_words):
        eq = eq & (aw[w, probe_row] == bw[w, build_row])
    match = in_range & eq
    pr_ref[...] = probe_row.astype(jnp.int32)
    br_ref[...] = build_row.astype(jnp.int32)
    m_ref[...] = match.astype(jnp.int32)
    tot_ref[0, 0] = total


def probe_join(l_h1, l_mask, r_sorted, perm, a_words, a_valid,
               b_words, b_valid, pair_cap: int, *, interpret: bool):
    """Fused hash-join probe: both _phase1 searchsorted passes, the
    candidate expansion and the exact-match word verify in one kernel
    over the VMEM-resident build side.  Returns ``(probe_row i32,
    build_row i32, match bool, total i32)`` — exactly the candidate
    phase of join_pairs_static; probe_row stays sorted so the shared
    tail's ``indices_are_sorted`` promise holds."""
    from jax.experimental import pallas as pl

    l_cap = int(l_h1.shape[0])
    r_cap = int(r_sorted.shape[0])
    n_words = int(a_words.shape[0])
    kernel = functools.partial(_probe_kernel, l_cap=l_cap, r_cap=r_cap,
                               pair_cap=pair_cap, n_words=n_words)
    probe_row, build_row, match, tot = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((pair_cap,), jnp.int32),
                   jax.ShapeDtypeStruct((pair_cap,), jnp.int32),
                   jax.ShapeDtypeStruct((pair_cap,), jnp.int32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)),
        interpret=interpret,
    )(l_h1, l_mask.astype(jnp.int32), r_sorted, perm,
      a_valid.astype(jnp.int32), b_valid.astype(jnp.int32),
      a_words, b_words)
    return probe_row, build_row, match != 0, tot[0, 0]


# ---------------------------------------------------------------------------
# stringHash: per-row dual polynomial hashing over the byte buffer
# ---------------------------------------------------------------------------

#: Rows hashed per program instance.
HASH_ROW_BLOCK = 512


def _string_hash_kernel(data_ref, off_ref, h1_ref, h2_ref, *, cap: int,
                        nbytes: int, block: int, base1: int, base2: int,
                        golden: int):
    i = jnp.int32(0) + _program_id(0)
    r = jnp.clip(i * block + _iota1d(block).reshape((block,)), 0, cap - 1)
    offs = off_ref[...]
    data = data_ref[...]
    start = offs[r].astype(jnp.int32)
    length = (offs[r + 1] - offs[r]).astype(jnp.int32)
    maxlen = jnp.max(length)

    def body(t, carry):
        h1, h2 = carry
        idx = jnp.clip(start + t, 0, nbytes - 1)
        b = data[idx].astype(jnp.uint32)
        act = t < length
        h1 = jnp.where(act, h1 * jnp.uint32(base1) + b, h1)
        h2 = jnp.where(act, h2 * jnp.uint32(base2) + b, h2)
        return h1, h2

    z = jnp.zeros((block,), jnp.uint32)
    h1, h2 = jax.lax.fori_loop(0, maxlen, body, (z, z))
    lw = length.astype(jnp.uint32) * jnp.uint32(golden)
    h1_ref[...] = h1 + lw
    h2_ref[...] = h2 + lw


def string_hash_rows(data, offsets, cap: int, bases, *, interpret: bool):
    """Row-blocked Horner evaluation of the dual polynomial row hashes.

    Bit-identical to exprs.strings.string_hash2's weighted segment-sum:
    uint32 addition is exact mod 2^32, so Horner over [start, end) equals
    sum(byte * base^(end-1-pos)) in any association, and rows past
    num_rows hash their (live-offset-bounded) windows identically on both
    paths."""
    from jax.experimental import pallas as pl

    nbytes = int(data.shape[0])
    padded_rows = -(-cap // HASH_ROW_BLOCK) * HASH_ROW_BLOCK
    nblocks = padded_rows // HASH_ROW_BLOCK
    kernel = functools.partial(
        _string_hash_kernel, cap=cap, nbytes=nbytes, block=HASH_ROW_BLOCK,
        base1=int(bases[0]), base2=int(bases[1]), golden=0x9E3779B9)
    offsets = offsets.astype(jnp.int32)
    h1, h2 = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec(data.shape, lambda i: (0,)),
                  pl.BlockSpec(offsets.shape, lambda i: (0,))],
        out_specs=(pl.BlockSpec((HASH_ROW_BLOCK,), lambda i: (i,)),
                   pl.BlockSpec((HASH_ROW_BLOCK,), lambda i: (i,))),
        out_shape=(jax.ShapeDtypeStruct((padded_rows,), jnp.uint32),
                   jax.ShapeDtypeStruct((padded_rows,), jnp.uint32)),
        interpret=interpret,
    )(data, offsets)
    return h1[:cap], h2[:cap]


# ---------------------------------------------------------------------------
# Registry entries (docs/kernels.md documents the full fallback matrix)
# ---------------------------------------------------------------------------

STRINGS = register(
    "strings", PALLAS_STRINGS_ENABLED,
    "contains/LIKE '%needle%' scan in one pass over the byte buffer",
    "exprs.strings._find_matches + segment-sum")
GATHER_SCATTER = register(
    "gatherScatter", PALLAS_GATHER_SCATTER_ENABLED,
    "segmented k-way gather/scatter pack (concat/split rows and bytes)",
    "layout._pack_kway drop-mode scatter chain")
JOIN_PROBE = register(
    "joinProbe", PALLAS_JOIN_PROBE_ENABLED,
    "hash-join probe: dual searchsorted + expansion + exact word verify",
    "join._phase1 + join_pairs_static candidate phase")
STRING_HASH = register(
    "stringHash", PALLAS_STRING_HASH_ENABLED,
    "dual polynomial row hashes over the byte buffer (Horner, row blocks)",
    "exprs.strings.string_hash2 pow-table + segment-sum")
