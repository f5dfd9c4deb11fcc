"""Window exec: sort-once + segmented-scan window functions
(reference: GpuWindowExec.scala:99, GpuWindowExpression.scala:93-116; design
notes in exprs/windows.py)."""

from __future__ import annotations

import math
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import ColumnBatch, DeviceColumn, HostBatch
from spark_rapids_tpu.exprs.aggregates import (
    AggregateFunction, Average, Count, Max, Min, Sum,
)
from spark_rapids_tpu.exprs.base import (
    CpuEvalCtx, DevVal, Expression, SortOrder, TpuEvalCtx,
)
from spark_rapids_tpu.exprs.windows import (
    DenseRank, Lag, Lead, Rank, RowNumber, WindowExpression, WindowFrame,
)
from spark_rapids_tpu.kernels.groupby import _gather_str_val
from spark_rapids_tpu.kernels.layout import gather_rows
from spark_rapids_tpu.kernels.sort import argsort_batch
from spark_rapids_tpu.kernels.sortkeys import keys_equal_prev
from spark_rapids_tpu.ops.cpu_exec import _from_rows, _rows, sort_key_fn
from spark_rapids_tpu.ops.tpu_exec import _concat_all
from spark_rapids_tpu.plan.physical import CpuExec, PhysicalOp, TpuExec


# ---------------------------------------------------------------------------
# Device window math
# ---------------------------------------------------------------------------


def _prefix_incl(x):
    return jnp.cumsum(x)


def _range_sum(prefix, a, b):
    """sum x[a..b] inclusive from an inclusive prefix sum (0 when b < a)."""
    hi = prefix[jnp.clip(b, 0, prefix.shape[0] - 1)]
    lo = jnp.where(a > 0, prefix[jnp.clip(a - 1, 0, prefix.shape[0] - 1)], 0)
    return jnp.where(b >= a, hi - lo, 0)


def _range_minmax(x, a, b, is_min: bool):
    """Sliding min/max over [a,b] via a log-doubling sparse table."""
    cap = int(x.shape[0])
    levels = max(1, cap.bit_length())
    sp = [x]
    for j in range(1, levels):
        half = 1 << (j - 1)
        shifted = jnp.concatenate([sp[-1][half:],
                                   jnp.full(half, sp[-1][-1], x.dtype)])
        sp.append(jnp.minimum(sp[-1], shifted) if is_min
                  else jnp.maximum(sp[-1], shifted))
    table = jnp.stack(sp)  # [levels, cap]
    length = jnp.maximum(b - a + 1, 1)
    k = (jnp.ceil(jnp.log2(length.astype(jnp.float64) + 1e-9)) - 1)
    k = jnp.clip(k.astype(jnp.int32), 0, levels - 1)
    i1 = jnp.clip(a, 0, cap - 1)
    i2 = jnp.clip(b - (1 << k) + 1, 0, cap - 1)
    v1 = table[k, i1]
    v2 = table[k, i2]
    return jnp.minimum(v1, v2) if is_min else jnp.maximum(v1, v2)


class _Segments:
    """Row-position structure of the sorted batch."""

    def __init__(self, cap, live, seg_start, peers_change):
        pos = jnp.arange(cap, dtype=jnp.int32)
        self.pos = pos
        self.live = live
        self.seg_start_pos = jnp.maximum(
            jax.lax.cummax(jnp.where(seg_start, pos, -1)), 0)
        seg_ids = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
        self.seg_ids = jnp.clip(seg_ids, 0, cap - 1)
        n_live = jnp.sum(live.astype(jnp.int32))
        seg_len = jax.ops.segment_sum(live.astype(jnp.int32), self.seg_ids,
                                      num_segments=cap, indices_are_sorted=True)
        self.seg_end_pos = self.seg_start_pos + \
            jnp.maximum(seg_len[self.seg_ids] - 1, 0)
        # peers: change = seg_start | order-key change
        change = seg_start | peers_change
        self.peer_start_pos = jnp.maximum(
            jax.lax.cummax(jnp.where(change, pos, -1)), 0)
        nxt = jnp.where(change, pos, cap)
        rev_min = jnp.flip(jax.lax.cummin(jnp.flip(nxt)))
        nxt_change = jnp.concatenate(
            [rev_min[1:], jnp.full(1, cap, jnp.int32)])
        self.peer_end_pos = jnp.minimum(
            nxt_change.astype(jnp.int32) - 1, self.seg_end_pos)
        self.change = change


def _search_boundary(keys, target, lo0, hi0, strict: bool):
    """Vectorized binary search: first position p in [lo0, hi0+1) with
    keys[p] > target (strict) or >= target; hi0+1 when none.  keys must
    ascend within each row's [lo0, hi0] span."""
    cap = int(keys.shape[0])
    lo = lo0.astype(jnp.int32)
    hi = (hi0 + 1).astype(jnp.int32)
    for _ in range(cap.bit_length() + 1):
        active = lo < hi
        mid = (lo + hi) // 2
        kv = keys[jnp.clip(mid, 0, cap - 1)]
        pred = (kv > target) if strict else (kv >= target)
        hi = jnp.where(active & pred, mid, hi)
        lo = jnp.where(active & ~pred, mid + 1, lo)
    return lo


def _bounded_range_bounds(frame: WindowFrame, segs: _Segments,
                          okey, ascending: bool, nulls_first: bool):
    """Value-based RANGE frame bounds: rows whose single numeric order
    key lies in [k+start, k+end].  NULL and NaN keys frame over their
    peer block (Spark: each is only a peer of its own kind); the search
    span excludes those contiguous blocks so the keys stay monotone.
    UNBOUNDED bounds reach the partition edge (null blocks included),
    matching Spark's partition-boundary semantics."""
    kd = okey.data
    # widen so k + offset cannot wrap in a narrow key dtype
    kd = kd.astype(jnp.int64) if okey.dtype.is_integral or         okey.dtype in (T.DATE, T.TIMESTAMP) else kd.astype(jnp.float64)
    if not ascending and kd.dtype == jnp.int64:
        # -INT64_MIN wraps; saturate one ulp first.  INT64_MIN and
        # INT64_MIN+1 become frame-peers at that one extreme
        # (docs/compatibility.md).
        imin = jnp.int64(jnp.iinfo(jnp.int64).min)
        kd = jnp.where(kd == imin, imin + 1, kd)
    keys = kd if ascending else -kd
    is_nan = jnp.isnan(keys) if okey.dtype.is_fractional else         jnp.zeros_like(okey.validity)
    finite = okey.validity & ~is_nan
    cap = segs.pos.shape[0]

    def seg_count(mask):
        return jax.ops.segment_sum(
            (mask & segs.live).astype(jnp.int32), segs.seg_ids,
            num_segments=cap, indices_are_sorted=True)[segs.seg_ids]

    nulls_in_seg = seg_count(~okey.validity)
    nans_in_seg = seg_count(is_nan)
    # nulls sit at the span edge given by nulls_first; NaN sorts past
    # every finite value (Spark), i.e. last ascending / first descending
    lo0 = segs.seg_start_pos + jnp.where(nulls_first, nulls_in_seg, 0)
    hi0 = segs.seg_end_pos - jnp.where(nulls_first, 0, nulls_in_seg)
    if ascending:
        hi0 = hi0 - nans_in_seg
    else:
        lo0 = lo0 + nans_in_seg
    k = keys

    def _target(off):
        # k + off with SATURATING int64 arithmetic: near INT64_MAX /
        # INT64_MIN a wrapped target flips the binary-search ordering
        # and produces empty frames (round-5 review finding).
        if k.dtype != jnp.int64:
            return k + off
        info = jnp.iinfo(jnp.int64)
        off = int(off)
        if off >= 0:
            return jnp.where(k > info.max - off, jnp.int64(info.max),
                             k + jnp.int64(off))
        return jnp.where(k < info.min - off, jnp.int64(info.min),
                         k + jnp.int64(off))

    if frame.start is None:
        a = segs.seg_start_pos  # partition edge, null/NaN blocks included
    else:
        a = _search_boundary(keys, _target(frame.start), lo0, hi0,
                             strict=False)
    if frame.end is None:
        b = segs.seg_end_pos
    else:
        b = _search_boundary(keys, _target(frame.end), lo0, hi0,
                             strict=True) - 1
    a = jnp.where(finite, a, segs.peer_start_pos)
    b = jnp.where(finite, b, segs.peer_end_pos)
    return a, b


def _frame_bounds(frame: WindowFrame, segs: _Segments, okeys=None,
                  order_by=None):
    """(a, b) inclusive row-position bounds of the frame per row."""
    if frame.is_unbounded_whole:
        return segs.seg_start_pos, segs.seg_end_pos
    if frame.kind == "range":
        if frame.is_running:
            return segs.seg_start_pos, segs.peer_end_pos
        # bounded value range: exactly one numeric order key (validated
        # by WindowExpression.tpu_supported)
        assert okeys is not None and len(okeys) == 1, \
            "bounded RANGE frame needs exactly one order key"
        o = order_by[0]
        return _bounded_range_bounds(frame, segs, okeys[0],
                                     o.ascending, o.nulls_first)
    a = segs.seg_start_pos if frame.start is None else \
        jnp.maximum(segs.pos + frame.start, segs.seg_start_pos)
    b = segs.seg_end_pos if frame.end is None else \
        jnp.minimum(segs.pos + frame.end, segs.seg_end_pos)
    return a, b


def _eval_window_fn(w: WindowExpression, segs: _Segments,
                    sorted_batch: ColumnBatch, ctx: TpuEvalCtx,
                    sorted_okeys=None) -> DevVal:
    fn = w.function
    cap = sorted_batch.capacity
    one = jnp.int32(1)
    if isinstance(fn, RowNumber):
        out = segs.pos - segs.seg_start_pos + one
        return DevVal(T.INT, out.astype(jnp.int32), segs.live)
    if isinstance(fn, Rank):
        out = segs.peer_start_pos - segs.seg_start_pos + one
        return DevVal(T.INT, out.astype(jnp.int32), segs.live)
    if isinstance(fn, DenseRank):
        c = jnp.cumsum(segs.change.astype(jnp.int32))
        out = c - c[segs.seg_start_pos] + one
        return DevVal(T.INT, out.astype(jnp.int32), segs.live)
    if isinstance(fn, Lag):
        off = fn.offset
        direction = -1 if not isinstance(fn, Lead) else 1
        target = segs.pos + direction * off
        in_seg = (target >= segs.seg_start_pos) & \
            (target <= segs.seg_end_pos)
        v = fn.children[0].tpu_eval(ctx)
        tgt = jnp.clip(target, 0, cap - 1)
        if v.dtype.is_string:
            g = _gather_str_val(v, tgt, cap)
            data, offsets = g.data, g.offsets
            validity = jnp.where(in_seg, g.validity, False)
            if len(fn.children) > 1:
                # literal default fill not supported for strings yet
                pass
            return DevVal(v.dtype, data, validity & segs.live, offsets)
        data = v.data[tgt]
        validity = jnp.where(in_seg, v.validity[tgt], False)
        if len(fn.children) > 1:
            d = fn.children[1].tpu_eval(ctx)
            data = jnp.where(in_seg, data, d.data)
            validity = jnp.where(in_seg, validity, d.validity)
        return DevVal(v.dtype, data, validity & segs.live)
    if isinstance(fn, AggregateFunction):
        v = fn.child.tpu_eval(ctx)
        a, b = _frame_bounds(w.frame, segs, sorted_okeys, w.order_by)
        valid = v.validity & segs.live
        cnt_prefix = _prefix_incl(valid.astype(jnp.int64))
        frame_cnt = _range_sum(cnt_prefix, a, b)
        if isinstance(fn, Count):
            return DevVal(T.LONG, frame_cnt.astype(jnp.int64), segs.live)
        if isinstance(fn, (Sum, Average)):
            acc_dt = jnp.float64 if (v.dtype.is_fractional or
                                     isinstance(fn, Average)) else jnp.int64
            x = jnp.where(valid, v.data, 0).astype(acc_dt)
            prefix = _prefix_incl(x)
            total = _range_sum(prefix, a, b)
            if isinstance(fn, Average):
                out = total.astype(jnp.float64) / \
                    jnp.maximum(frame_cnt, 1).astype(jnp.float64)
                return DevVal(T.DOUBLE, out,
                              (frame_cnt > 0) & segs.live)
            out_dt = fn.dtype.jnp_dtype
            return DevVal(fn.dtype, total.astype(out_dt),
                          (frame_cnt > 0) & segs.live)
        if isinstance(fn, (Min, Max)):
            is_min = isinstance(fn, Min)
            jdt = fn.dtype.jnp_dtype
            if fn.dtype.is_fractional:
                ident = jnp.asarray(jnp.inf if is_min else -jnp.inf, jdt)
            elif fn.dtype == T.BOOLEAN:
                ident = jnp.asarray(True if is_min else False)
            else:
                info = jnp.iinfo(jdt)
                ident = jnp.asarray(info.max if is_min else info.min, jdt)
            x = jnp.where(valid, v.data.astype(jdt), ident)
            out = _range_minmax(x, a, b, is_min)
            return DevVal(fn.dtype, out, (frame_cnt > 0) & segs.live)
    raise NotImplementedError(f"window fn {fn.name}")


class TpuWindowExec(TpuExec):
    def __init__(self, window_exprs: List[WindowExpression],
                 output_names: List[str], child: PhysicalOp,
                 schema: T.Schema):
        super().__init__([child], schema)
        self.window_exprs = window_exprs
        self.output_names = output_names
        w0 = window_exprs[0]
        self.part_keys = w0.partition_by
        self.order_by = w0.order_by
        for w in window_exprs[1:]:
            assert repr(w.partition_by) == repr(self.part_keys) and \
                repr(w.order_by) == repr(self.order_by), \
                "one Window exec handles one (partition, order) spec"

        from spark_rapids_tpu.utils.compile_registry import (
            plan_jit,
        )

        @plan_jit(label="TpuWindow")
        def run(batch: ColumnBatch) -> ColumnBatch:
            return self._compute(batch)

        self._run = run

    def describe(self):
        return f"TpuWindow({len(self.window_exprs)} exprs)"

    def _compute(self, batch: ColumnBatch) -> ColumnBatch:
        cap = batch.capacity
        ctx0 = TpuEvalCtx(batch)
        pkeys = [e.tpu_eval(ctx0) for e in self.part_keys]
        okeys = [o.child.tpu_eval(ctx0) for o in self.order_by]
        all_vals = pkeys + okeys
        ascs = [True] * len(pkeys) + [o.ascending for o in self.order_by]
        nfs = [True] * len(pkeys) + [o.nulls_first for o in self.order_by]
        if all_vals:
            groupings = [True] * len(pkeys) + [False] * len(okeys)
            perm = argsort_batch(all_vals, ascs, nfs, batch.num_rows,
                                 groupings=groupings)
        else:
            perm = jnp.arange(cap, dtype=jnp.int32)
        sorted_batch = gather_rows(batch, perm, batch.num_rows)
        live = jnp.arange(cap, dtype=jnp.int32) < batch.num_rows

        ctx = TpuEvalCtx(sorted_batch)
        sorted_pkeys = [e.tpu_eval(ctx) for e in self.part_keys]
        sorted_okeys = [o.child.tpu_eval(ctx) for o in self.order_by]
        if sorted_pkeys:
            seg_start = live & ~keys_equal_prev(sorted_pkeys)
        else:
            seg_start = live & (jnp.arange(cap, dtype=jnp.int32) == 0)
        if sorted_okeys:
            peers_change = live & ~keys_equal_prev(sorted_okeys)
        else:
            peers_change = jnp.zeros(cap, dtype=jnp.bool_)
        segs = _Segments(cap, live, seg_start, peers_change)

        cols = list(sorted_batch.columns)
        for w in self.window_exprs:
            v = _eval_window_fn(w, segs, sorted_batch, ctx, sorted_okeys)
            cols.append(DeviceColumn(v.dtype, v.data, v.validity, v.offsets))
        return ColumnBatch(self.output_schema, cols, batch.num_rows, cap)

    def partitions(self, ctx):
        def gen(part):
            merged = _concat_all(list(part), self.children[0].output_schema)
            if merged is not None:
                yield self._run(merged)

        return [gen(p) for p in self.children[0].partitions(ctx)]


class CpuWindowExec(CpuExec):
    """Python oracle with exact Spark window semantics."""

    def __init__(self, window_exprs: List[WindowExpression],
                 output_names: List[str], child: PhysicalOp,
                 schema: T.Schema):
        super().__init__([child], schema)
        self.window_exprs = window_exprs
        self.output_names = output_names

    def partitions(self, ctx):
        def gen(part):
            batches = list(part)
            if not batches:
                return
            hb = HostBatch.concat(batches)
            yield self._compute(hb)

        return [gen(p) for p in self.children[0].partitions(ctx)]

    def _compute(self, hb: HostBatch) -> HostBatch:
        w0 = self.window_exprs[0]
        cctx = CpuEvalCtx(hb)
        pvals = [e.cpu_eval(cctx).to_column().to_list()
                 for e in w0.partition_by]
        ovals = [o.child.cpu_eval(cctx).to_column().to_list()
                 for o in w0.order_by]
        n = hb.num_rows
        rows = _rows(hb)
        pkey = [tuple(c[i] for c in pvals) for i in range(n)] if pvals \
            else [()] * n
        okey = [tuple(c[i] for c in ovals) for i in range(n)] if ovals \
            else [()] * n
        keyf = sort_key_fn(
            [SortOrder(o.child, o.ascending, o.nulls_first)
             for o in w0.order_by], list(range(len(w0.order_by))))
        idx = sorted(range(n), key=lambda i: (
            _pkey_sort(pkey[i]), keyf(okey[i])))
        out_rows = []
        # group by partition key
        groups = {}
        for i in idx:
            groups.setdefault(pkey[i], []).append(i)
        hb_cols = [c.to_list() for c in hb.columns]
        for w, _name in zip(self.window_exprs, self.output_names):
            pass
        extra_cols = [[None] * n for _ in self.window_exprs]
        order_pos = {i: p for p, i in enumerate(idx)}
        for g in groups.values():
            for wi, w in enumerate(self.window_exprs):
                vals = self._eval_group(w, g, okey, hb)
                for j, i in enumerate(g):
                    extra_cols[wi][i] = vals[j]
        out = []
        for i in idx:
            out.append(tuple(c[i] for c in hb_cols) +
                       tuple(extra_cols[wi][i]
                             for wi in range(len(self.window_exprs))))
        return _from_rows(self.output_schema, out)

    def _eval_group(self, w: WindowExpression, g: List[int], okey,
                    hb: HostBatch):
        fn = w.function
        m = len(g)
        if isinstance(fn, RowNumber):
            return [j + 1 for j in range(m)]
        if isinstance(fn, Rank):
            out, last, r = [], None, 0
            for j in range(m):
                if okey[g[j]] != last:
                    r = j + 1
                    last = okey[g[j]]
                out.append(r)
            return out
        if isinstance(fn, DenseRank):
            out, last, r = [], object(), 0
            for j in range(m):
                if okey[g[j]] != last:
                    r += 1
                    last = okey[g[j]]
                out.append(r)
            return out
        cctx = CpuEvalCtx(hb)
        if isinstance(fn, Lag):
            v = fn.children[0].cpu_eval(cctx).to_column().to_list()
            d = fn.children[1].cpu_eval(cctx).to_column().to_list() \
                if len(fn.children) > 1 else None
            direction = 1 if isinstance(fn, Lead) else -1
            out = []
            for j in range(m):
                t = j + direction * fn.offset
                if 0 <= t < m:
                    out.append(v[g[t]])
                else:
                    out.append(d[g[j]] if d is not None else None)
            return out
        if isinstance(fn, AggregateFunction):
            v = fn.child.cpu_eval(cctx)
            vals, valid = v.values, v.validity
            out = []
            for j in range(m):
                a, b = self._bounds(w, j, m, g, okey)
                sel = [g[k] for k in range(a, b + 1)] if b >= a else []
                import numpy as np
                gv = np.array([vals[i] for i in sel]) if sel else \
                    np.zeros(0)
                gm = np.array([bool(valid[i]) for i in sel], dtype=bool) \
                    if sel else np.zeros(0, dtype=bool)
                out.append(fn.cpu_reduce(gv, gm))
            return out
        raise NotImplementedError(fn.name)

    def _bounds(self, w: WindowExpression, j: int, m: int, g, okey):
        frame = w.frame
        if frame.is_unbounded_whole:
            return 0, m - 1
        if frame.kind == "range":
            if frame.is_running:
                b = j
                while b + 1 < m and okey[g[b + 1]] == okey[g[j]]:
                    b += 1
                return 0, b
            # bounded value range over the single numeric order key
            if len(w.order_by) != 1:
                raise ValueError(
                    "a bounded RANGE frame requires exactly one "
                    "ORDER BY expression")
            o = w.order_by[0]
            kd = o.child.dtype
            if not kd.is_numeric and kd not in (T.DATE, T.TIMESTAMP):
                raise ValueError(
                    f"bounded RANGE frames need a numeric order key, "
                    f"got {kd}")
            sgn = 1 if o.ascending else -1
            # okey entries are tuples over all order keys; bounded range
            # has exactly one
            kv = [okey[g[i]][0] for i in range(m)]
            k = kv[j]
            def _is_nan(v):
                return v is not None and v != v

            if _is_nan(k):
                # NaN keys frame over their peer (NaN) block
                a = j
                while a - 1 >= 0 and _is_nan(kv[a - 1]):
                    a -= 1
                b = j
                while b + 1 < m and _is_nan(kv[b + 1]):
                    b += 1
                return a, b
            if k is None:
                # NULL keys frame over their peer (null) block
                a = j
                while a - 1 >= 0 and kv[a - 1] is None:
                    a -= 1
                b = j
                while b + 1 < m and kv[b + 1] is None:
                    b += 1
                return a, b
            lo_v = None if frame.start is None else k + sgn * frame.start
            hi_v = None if frame.end is None else k + sgn * frame.end

            def inside(v):
                if lo_v is not None and sgn * v < sgn * lo_v:
                    return False
                if hi_v is not None and sgn * v > sgn * hi_v:
                    return False
                return True

            def finite(v):
                return v is not None and v == v  # excludes NULL and NaN

            hits = [i for i in range(m) if finite(kv[i]) and inside(kv[i])]
            # UNBOUNDED bounds reach the partition edge (incl. the
            # null/NaN blocks), matching Spark
            a = 0 if frame.start is None else (hits[0] if hits else m)
            b = m - 1 if frame.end is None else (hits[-1] if hits else -1)
            return a, b
        a = 0 if frame.start is None else max(0, j + frame.start)
        b = m - 1 if frame.end is None else min(m - 1, j + frame.end)
        return a, b


def _pkey_sort(k: tuple):
    return tuple((v is None, str(v)) for v in k)
