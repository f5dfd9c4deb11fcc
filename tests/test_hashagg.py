"""MXU slot-aggregation tests (kernels/hashagg.py): correctness vs the
CPU oracle, engagement on eligible plans, and the exact-fallback paths
(wide key range, NaN floats, unsupported aggs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import MIN_CAPACITY, ColumnBatch
from spark_rapids_tpu.dataframe import Column
from spark_rapids_tpu.exprs.aggregates import (
    Average, Count, First, Last, Max, Min, Sum,
)
from spark_rapids_tpu.exprs.base import Alias, ColumnRef, DevVal

from compare import assert_tpu_cpu_equal, cpu_session, tpu_session


def _mxu_engaged(session) -> bool:
    return any(isinstance(ms, dict) and ms.get("mxuAggBatches", 0) > 0
               for ms in session.last_metrics.values())


def _update_aggs(session):
    from spark_rapids_tpu.ops.tpu_exec import TpuHashAggregateExec
    found = []

    def walk(node):
        if isinstance(node, TpuHashAggregateExec) and node.mode == "update":
            found.append(node)
        for ch in getattr(node, "children", []):
            walk(ch)

    walk(session.last_physical_plan)
    return found


def _data(n=4000, key_range=97, with_nan=False):
    rng = np.random.RandomState(5)
    keys = [None if i % 13 == 0 else int(k)
            for i, k in enumerate(rng.randint(0, key_range, n))]
    vals = [None if i % 7 == 0 else int(v)
            for i, v in enumerate(rng.randint(-10**9, 10**9, n))]
    fl = [None if i % 5 == 0 else float(f)
          for i, f in enumerate((rng.rand(n) * 1e6 - 5e5).round(3))]
    if with_nan:
        fl[17] = float("nan")
    return {"k": (T.INT, keys), "v": (T.LONG, vals), "f": (T.DOUBLE, fl)}


def _q(s, data):
    df = s.create_dataframe(data, num_partitions=3)
    return df.group_by("k").agg(
        Column(Alias(Sum(ColumnRef("v")), "sv")),
        Column(Alias(Count(ColumnRef("v")), "cv")),
        Column(Alias(Sum(ColumnRef("f")), "sf")),
        Column(Alias(Average(ColumnRef("f")), "af")),
        Column(Alias(Average(ColumnRef("v")), "av")),
    )


def test_mxu_agg_matches_cpu_oracle():
    assert_tpu_cpu_equal(
        lambda s: _q(s, _data()), approx=True,
        confs={"spark.rapids.sql.variableFloatAgg.enabled": True})


def test_mxu_agg_engages_and_is_exact_for_ints():
    conf = {"spark.rapids.sql.variableFloatAgg.enabled": True}
    tpu = tpu_session(**conf)
    cpu = cpu_session(**conf)
    data = _data()
    t_rows = {r[0]: r[1:3] for r in _q(tpu, data).collect()}
    c_rows = {r[0]: r[1:3] for r in _q(cpu, data).collect()}
    # int sum + count EXACT (limb recombination is bit-exact)
    assert t_rows == c_rows
    # the update agg really took the hash variant (sticky flag untouched)
    aggs = _update_aggs(tpu)
    assert aggs and all(a._hash_capable and not a._hash_disabled
                        for a in aggs)


def test_mxu_agg_falls_back_on_wide_key_range():
    """Key range far above the slot table: results still correct (sort
    path), and the fallback metric fires."""
    conf = {"spark.rapids.sql.variableFloatAgg.enabled": True}
    rng = np.random.RandomState(9)
    data = {
        "k": (T.LONG, [int(x) for x in
                       rng.randint(-10**17, 10**17, 2000)]),
        "v": (T.LONG, [int(x) for x in rng.randint(0, 100, 2000)]),
    }

    def q(s):
        df = s.create_dataframe(data, num_partitions=2)
        return df.group_by("k").agg(
            Column(Alias(Sum(ColumnRef("v")), "sv")))

    tpu = tpu_session(**conf)
    cpu = cpu_session(**conf)
    t = sorted(q(tpu).collect())
    c = sorted(q(cpu).collect())
    assert t == c
    fell_back = any(isinstance(ms, dict) and "hashAggFallback" in ms
                    for ms in tpu.last_metrics.values())
    assert fell_back, tpu.last_metrics


def test_mxu_agg_falls_back_on_nan_floats():
    conf = {"spark.rapids.sql.variableFloatAgg.enabled": True}
    data = _data(n=1000, with_nan=True)

    def q(s):
        df = s.create_dataframe(data, num_partitions=2)
        return df.group_by("k").agg(
            Column(Alias(Sum(ColumnRef("f")), "sf")))

    tpu = tpu_session(**conf)
    cpu = cpu_session(**conf)
    t = {r[0]: r[1] for r in q(tpu).collect()}
    c = {r[0]: r[1] for r in q(cpu).collect()}
    assert set(t) == set(c)
    for k, v in c.items():
        tv = t[k]
        if v is None or (isinstance(v, float) and v != v):
            assert tv is None or (isinstance(tv, float) and tv != tv), \
                (k, v, tv)
        else:
            assert abs(tv - v) <= 1e-6 * max(1.0, abs(v)), (k, v, tv)


def test_mxu_agg_minmax_first_last():
    """Round 5: min/max/first/last ride the slot index through the
    aggregates' own segment kernels — the plan keeps hash capability and
    the MXU path engages (metric-asserted)."""
    from spark_rapids_tpu.kernels.hashagg import hash_agg_capable
    assert hash_agg_capable(
        "update", [T.INT], [Max(ColumnRef("v")), Min(ColumnRef("v"))])

    def q(s):
        df = s.create_dataframe(_data(), num_partitions=2)
        return df.group_by("k").agg(
            Column(Alias(Max(ColumnRef("v")), "mx")),
            Column(Alias(Min(ColumnRef("v")), "mn")),
            Column(Alias(Min(ColumnRef("f")), "mf")),
            Column(Alias(Sum(ColumnRef("v")), "sv")))

    conf = {"spark.rapids.sql.variableFloatAgg.enabled": True}
    assert_tpu_cpu_equal(q, approx=True, confs=conf)
    tpu = tpu_session(**conf)
    q(tpu).collect()
    assert _mxu_engaged(tpu), tpu.last_metrics


def test_mxu_agg_first_last_ordered_input():
    # first/last are order-sensitive: use a single partition so the CPU
    # oracle sees the same row order as the device batch
    n = 600
    data = {"k": (T.INT, [i % 37 for i in range(n)]),
            "v": (T.LONG, [None if i % 11 == 0 else i for i in range(n)])}

    def q(s):
        df = s.create_dataframe(data, num_partitions=1)
        return df.group_by("k").agg(
            Column(Alias(First(ColumnRef("v")), "fv")),
            Column(Alias(Last(ColumnRef("v")), "lv")),
            Column(Alias(Count(ColumnRef("v")), "cv")))

    assert_tpu_cpu_equal(q)
    tpu = tpu_session()
    q(tpu).collect()
    assert _mxu_engaged(tpu), tpu.last_metrics


def test_mxu_agg_multi_key():
    """Round 5: multiple small-range keys pack into one slot index
    (mixed radix, NULL digit per nullable column)."""
    rng = np.random.RandomState(11)
    n = 3000
    data = {
        "a": (T.INT, [None if i % 17 == 0 else int(x)
                      for i, x in enumerate(rng.randint(0, 50, n))]),
        "b": (T.INT, [int(x) for x in rng.randint(-3, 4, n)]),
        "c": (T.BOOLEAN, [None if i % 23 == 0 else bool(x)
                          for i, x in enumerate(rng.randint(0, 2, n))]),
        "v": (T.LONG, [int(x) for x in rng.randint(-10**9, 10**9, n)]),
        "f": (T.DOUBLE, [float(x) for x in
                         (rng.rand(n) * 1e4 - 5e3).round(3)]),
    }

    def q(s):
        df = s.create_dataframe(data, num_partitions=3)
        return df.group_by("a", "b", "c").agg(
            Column(Alias(Sum(ColumnRef("v")), "sv")),
            Column(Alias(Count(ColumnRef("v")), "cv")),
            Column(Alias(Average(ColumnRef("f")), "af")),
            Column(Alias(Max(ColumnRef("v")), "mv")))

    conf = {"spark.rapids.sql.variableFloatAgg.enabled": True}
    assert_tpu_cpu_equal(q, approx=True, confs=conf)
    tpu = tpu_session(**conf)
    q(tpu).collect()
    # 50-ish * 8 * 3 slots << 8192: the packed path must engage
    assert _mxu_engaged(tpu), tpu.last_metrics


def test_mxu_agg_multi_key_product_fallback():
    """Two keys whose RANGE PRODUCT exceeds the table (each alone fits):
    exact sort fallback, correct results, fallback metric fires."""
    rng = np.random.RandomState(13)
    n = 2000
    data = {
        "a": (T.INT, [int(x) for x in rng.randint(0, 200, n)]),
        "b": (T.INT, [int(x) for x in rng.randint(0, 200, n)]),
        "v": (T.LONG, [int(x) for x in rng.randint(0, 100, n)]),
    }

    def q(s):
        df = s.create_dataframe(data, num_partitions=2)
        return df.group_by("a", "b").agg(
            Column(Alias(Sum(ColumnRef("v")), "sv")))

    tpu = tpu_session()
    cpu = cpu_session()
    assert sorted(q(tpu).collect()) == sorted(q(cpu).collect())
    fell_back = any(isinstance(ms, dict) and "hashAggFallback" in ms
                    for ms in tpu.last_metrics.values())
    assert fell_back, tpu.last_metrics


def test_mxu_agg_widened_table_conf():
    """tableSlots conf admits a key space the default table rejects."""
    rng = np.random.RandomState(13)
    n = 2000
    data = {
        "a": (T.INT, [int(x) for x in rng.randint(0, 200, n)]),
        "b": (T.INT, [int(x) for x in rng.randint(0, 200, n)]),
        "v": (T.LONG, [int(x) for x in rng.randint(0, 100, n)]),
    }

    def q(s):
        df = s.create_dataframe(data, num_partitions=2)
        return df.group_by("a", "b").agg(
            Column(Alias(Sum(ColumnRef("v")), "sv")))

    conf = {"spark.rapids.sql.agg.mxuHash.tableSlots": 65536}
    tpu = tpu_session(**conf)
    cpu = cpu_session()
    assert sorted(q(tpu).collect()) == sorted(q(cpu).collect())
    assert _mxu_engaged(tpu), tpu.last_metrics


def test_mxu_agg_keyless_and_empty():
    from spark_rapids_tpu import functions as F

    def q(s):
        df = s.create_dataframe(_data(n=500), num_partitions=2)
        return df.filter(F.col("v") > 10**10).agg(  # empty after filter
            Column(Alias(Count(ColumnRef("v")), "c")),
            Column(Alias(Sum(ColumnRef("v")), "s")))

    assert_tpu_cpu_equal(q)

    def q2(s):
        df = s.create_dataframe(_data(n=500), num_partitions=2)
        return df.agg(Column(Alias(Count(ColumnRef("v")), "c")),
                      Column(Alias(Sum(ColumnRef("v")), "s")))

    assert_tpu_cpu_equal(q2)


def test_mxu_agg_negative_and_date_keys():
    rng = np.random.RandomState(4)
    # dates are epoch-day ints in this engine's host model
    dates = [None if i % 9 == 0 else 19723 + int(d)
             for i, d in enumerate(rng.randint(0, 300, 1500))]
    data = {
        "d": (T.DATE, dates),
        "k": (T.INT, [int(x) for x in rng.randint(-500, 500, 1500)]),
        "v": (T.LONG, [int(x) for x in rng.randint(-100, 100, 1500)]),
    }
    assert_tpu_cpu_equal(
        lambda s: s.create_dataframe(data, num_partitions=2)
        .group_by("d").agg(Column(Alias(Sum(ColumnRef("v")), "sv"))))
    assert_tpu_cpu_equal(
        lambda s: s.create_dataframe(data, num_partitions=2)
        .group_by("k").agg(Column(Alias(Sum(ColumnRef("v")), "sv")),
                           Column(Alias(Count(ColumnRef("v")), "cv"))))


# -- no grouping key: the rows are reduced, not contracted (PR 29) ------------

FLOAT_AGG = {"spark.rapids.sql.variableFloatAgg.enabled": True}


def _flat(session, key):
    return session.last_metrics.get(key, 0)


def _keyless_case(name):
    """(rows, capacity, {column: (dtype, values, validity)}, aggregates,
    slot table of the contraction it is compared with)."""
    from spark_rapids_tpu.kernels.hashagg import TABLE_SLOTS
    rng = np.random.RandomState(29)
    L, D = ColumnRef("l", T.LONG), ColumnRef("d", T.DOUBLE)

    def cols(n, l_valid=None, d_valid=None):
        lv = rng.randint(-2**62, 2**62, n).astype(np.int64)
        dv = (rng.rand(n) * 2e6 - 1e6) * 10.0 ** rng.randint(-8, 9, n)
        return {"l": (T.LONG, lv, np.arange(n) % 7 != 0
                      if l_valid is None else l_valid),
                "d": (T.DOUBLE, dv, np.arange(n) % 5 != 0
                      if d_valid is None else d_valid)}

    if name == "long_sum_count":
        return 5000, 8192, cols(5000), [Sum(L), Count(L), Average(L)], \
            TABLE_SLOTS
    if name == "double_sum_avg":
        return 5000, 8192, cols(5000), [Sum(D), Average(D), Count(D)], \
            TABLE_SLOTS
    if name == "min_max_first_last":
        return 3000, 4096, cols(3000), [
            Min(L), Max(L), Min(D), Max(D), First(L), Last(L),
            First(D, ignore_nulls=True), Last(D, ignore_nulls=True)], 126
    if name == "empty":
        return 0, 1024, cols(1024), [Sum(L), Count(L), Sum(D), Average(D),
                                     Min(D), First(L)], TABLE_SLOTS
    if name == "all_null":
        none = np.zeros(2000, np.bool_)
        return 2000, 2048, cols(2000, none, none), [
            Sum(L), Count(L), Sum(D), Average(D), Max(L), Last(D)], 126
    if name == "two_to_the_20_rows":
        # 64 chunks: the cross-chunk recombination.  A table of 6 slots
        # keeps the CPU's one-hot einsum short; slot 0 holds every live
        # row and the last slot every dead one whatever the table's size
        n = 1 << 20
        return n - 12345, n, cols(n), [Sum(L), Sum(D), Average(D),
                                       Count(L), Min(D)], 6
    raise AssertionError(name)


@pytest.mark.parametrize("name", [
    "long_sum_count", "double_sum_avg", "min_max_first_last", "empty",
    "all_null", "two_to_the_20_rows"])
def test_keyless_reduction_equals_slot_contraction_bit_for_bit(name):
    """With no key there is one group: summing each limb row along its
    chunk gives exactly what contracting it against one_hot(constant
    slot) gives, so every buffer has the parent kernel's bits."""
    from spark_rapids_tpu.kernels.hashagg import (
        hash_group_aggregate, keyless_aggregate,
    )
    n, cap, columns, fns, table = _keyless_case(name)

    def padded(a):
        return jnp.asarray(np.concatenate(
            [a[:min(len(a), cap)], np.zeros(cap - min(len(a), cap),
                                            a.dtype)]))

    vals = {c: DevVal(dt, padded(v), padded(ok))
            for c, (dt, v, ok) in columns.items()}
    inputs = [vals[fn.child.column] for fn in fns]
    batch = ColumnBatch(T.Schema([]), [], jnp.asarray(n, jnp.int32), cap)
    none, one_key = T.Schema([]), T.Schema([("__k", T.INT)])

    @jax.jit
    def contracted(batch, inputs):
        key = [DevVal(T.INT, jnp.zeros(cap, jnp.int32),
                      jnp.ones(cap, jnp.bool_))]
        _keys, bufs, _n, flag = hash_group_aggregate(
            batch, key, inputs, fns, one_key, none, table=table)
        return bufs, flag

    @jax.jit
    def reduced(batch, inputs):
        keys, bufs, flag = keyless_aggregate(batch, inputs, fns, none)
        return keys.num_rows, bufs, flag

    want, want_flag = contracted(batch, inputs)
    rows, got, got_flag = reduced(batch, inputs)
    assert int(rows) == 1 and not bool(want_flag) and not bool(got_flag)
    for fn, wb, gb in zip(fns, want, got):
        assert len(wb) == len(gb) == len(fn.buffers())
        for w, g in zip(wb, gb):
            assert g.data.shape == (MIN_CAPACITY,), (fn, g.data.shape)
            assert g.data.dtype == w.data.dtype, fn
            # row 0 is the one group; bytes, not values: -0.0 and NaN too
            assert np.asarray(g.data[:1]).tobytes() == \
                np.asarray(w.data[:1]).tobytes(), (name, fn, w.data[0],
                                                   g.data[0])
            assert bool(g.validity[0]) == bool(w.validity[0]), (name, fn)


def test_keyless_plan_counts_reductions_and_keyed_plan_contractions():
    data = _data(n=3000)

    def keyless(s):
        return s.create_dataframe(data, num_partitions=3).agg(
            Column(Alias(Sum(ColumnRef("f")), "sf")),
            Column(Alias(Count(ColumnRef("v")), "cv")),
            Column(Alias(Max(ColumnRef("v")), "mv")))

    assert_tpu_cpu_equal(keyless, approx=True, confs=FLOAT_AGG)
    tpu = tpu_session(**FLOAT_AGG)
    keyless(tpu).collect()
    assert _flat(tpu, "keylessAggBatches") > 0, tpu.last_metrics
    assert _flat(tpu, "keylessAggBatches") == \
        _flat(tpu, "keylessUpdateBatches")
    assert _flat(tpu, "mxuAggBatches") == 0 and not _mxu_engaged(tpu)
    assert _flat(tpu, "keyedUpdateBatches") == 0
    assert not any(a._hash_disabled for a in _update_aggs(tpu))

    _q(tpu, data).collect()     # the same columns, grouped by k
    assert _flat(tpu, "mxuAggBatches") > 0 and _mxu_engaged(tpu)
    assert _flat(tpu, "keylessAggBatches") == 0
    assert _flat(tpu, "keylessUpdateBatches") == 0


@pytest.mark.parametrize("mxu", [True, False])
def test_keyed_update_batches_count_the_sort_variant_too(mxu):
    """``keyedUpdateBatches`` is every update batch a keyed aggregate saw;
    ``mxuAggBatches`` only those the slot contraction took."""
    conf = dict(FLOAT_AGG, **{"spark.rapids.sql.agg.mxuHash.enabled": mxu})
    tpu = tpu_session(**conf)
    _q(tpu, _data(n=3000)).collect()
    assert _flat(tpu, "keyedUpdateBatches") > 0, tpu.last_metrics
    assert _flat(tpu, "mxuAggBatches") == \
        (_flat(tpu, "keyedUpdateBatches") if mxu else 0)
    assert _flat(tpu, "keylessUpdateBatches") == 0
    assert _flat(tpu, "filterCompactedBatches") == 0    # no filter


@pytest.mark.parametrize("mxu", [True, False])
def test_keyless_partials_and_result_leave_at_min_capacity(mxu):
    """One row at MIN_CAPACITY from the reduction and from the sort variant
    alike (keyless, the sort variant sorts nothing either), so the merge
    works on a few dozen rows and the answer's D2H is a few hundred bytes,
    not a padded megabyte."""
    conf = dict(FLOAT_AGG, **{"spark.rapids.sql.agg.mxuHash.enabled": mxu})
    data = _data(n=20000)

    def q(s):
        return s.create_dataframe(data, num_partitions=3).agg(
            Column(Alias(Sum(ColumnRef("f")), "sf")),
            Column(Alias(Average(ColumnRef("v")), "av")),
            Column(Alias(Count(ColumnRef("k")), "ck")))

    assert_tpu_cpu_equal(q, approx=True, confs=conf)
    s = tpu_session(**conf)
    q(s).collect()
    assert 0 < s.last_metrics["d2hBytes"] <= 1024, s.last_metrics["d2hBytes"]
    assert _flat(s, "keylessUpdateBatches") > 0
    assert _flat(s, "keylessAggBatches") == \
        (_flat(s, "keylessUpdateBatches") if mxu else 0)


def test_keyless_groupby_kernel_sorts_nothing_and_pads_to_min_capacity():
    from spark_rapids_tpu.kernels.groupby import groupby_aggregate
    n, cap = 900, 1024
    v = np.arange(cap, dtype=np.int64) - 400
    ok = np.arange(cap) % 3 != 0
    fns = [Sum(ColumnRef("l", T.LONG)), Last(ColumnRef("l", T.LONG))]
    batch = ColumnBatch(T.Schema([]), [], jnp.asarray(n, jnp.int32), cap)

    def run(batch, val):
        return groupby_aggregate(
            batch, [], [val, val], fns, False, T.Schema([]),
            [[b.dtype for b in fn.buffers()] for fn in fns], T.Schema([]))

    val = DevVal(T.LONG, jnp.asarray(v), jnp.asarray(ok))
    keys, bufs = jax.jit(run)(batch, val)
    assert keys.capacity == MIN_CAPACITY and int(keys.num_rows) == 1
    assert not keys.columns
    assert all(b.data.shape == (MIN_CAPACITY,) for bs in bufs for b in bs)
    assert int(bufs[0][0].data[0]) == int(v[:n][ok[:n]].sum())
    assert int(bufs[1][0].data[0]) == int(v[n - 1])      # input order kept
    text = jax.jit(run).lower(batch, val).as_text()
    assert "stablehlo.sort" not in text


def test_keyless_nan_batch_raises_the_flag_and_takes_the_exact_path():
    data = _data(n=1500, with_nan=True)

    def q(s):
        return s.create_dataframe(data, num_partitions=2).agg(
            Column(Alias(Sum(ColumnRef("f")), "sf")),
            Column(Alias(Count(ColumnRef("f")), "cf")),
            Column(Alias(Sum(ColumnRef("v")), "sv")))

    tpu, cpu = tpu_session(**FLOAT_AGG), cpu_session(**FLOAT_AGG)
    (t,), (c,) = q(tpu).collect(), q(cpu).collect()
    assert t[0] != t[0] and c[0] != c[0]          # NaN, as the CPU oracle
    assert t[1:] == c[1:]
    assert any(isinstance(ms, dict) and "hashAggFallback" in ms
               for ms in tpu.last_metrics.values()), tpu.last_metrics
    assert _flat(tpu, "keylessAggBatches") == 0
    assert _flat(tpu, "keylessUpdateBatches") > 0


# -- the flag's route: beside the answer, or where a stage hands on ----------


def _pipeline(s):
    return s.last_metrics.get("pipeline", {})


def _fallbacks(s):
    return sum(ms.get("hashAggFallback", 0)
               for ms in s.last_metrics.values() if isinstance(ms, dict))


def _keyless_sums(s, data, parts=2):
    return s.create_dataframe(data, num_partitions=parts).agg(
        Column(Alias(Sum(ColumnRef("f")), "sf")),
        Column(Alias(Count(ColumnRef("f")), "cf")),
        Column(Alias(Sum(ColumnRef("v")), "sv")))


def _same(t_rows, c_rows):
    """Rows equal: NaN equal to NaN, floats to the emulated f64's ulps."""
    def norm(rows):
        return [tuple("nan" if isinstance(x, float) and x != x else
                      pytest.approx(x, rel=1e-12) if isinstance(x, float)
                      else x for x in r) for r in rows]
    return norm(t_rows) == norm(c_rows)


def test_keyless_flag_rides_home_with_the_answer_and_the_rerun_is_sticky():
    """A NaN under a keyless sum: the flag is read beside the (discarded)
    answer, the stage is dispatched again in the exact variant on the same
    sources, and the NEXT collect of the DataFrame is one program in that
    variant — the stage's key follows the operator inlined into it."""
    data = _data(n=1500, with_nan=True)
    tpu, cpu = tpu_session(**FLOAT_AGG), cpu_session(**FLOAT_AGG)
    df = _keyless_sums(tpu, data)
    want = _keyless_sums(cpu, data).collect()
    assert want[0][0] != want[0][0]                     # the oracle's NaN

    assert _same(df.collect(), want)
    assert _fallbacks(tpu) == 1
    assert _pipeline(tpu)["flagReruns"] == 1, _pipeline(tpu)
    assert _pipeline(tpu)["programs"] == 2, _pipeline(tpu)
    assert tpu.last_metrics["dispatchCount"] == 2
    assert _flat(tpu, "keylessAggBatches") == 0         # only the run that
    assert _flat(tpu, "keylessUpdateBatches") > 0       # stands is counted
    waits = [e.name for e in tpu.query_history()[-1].events
             if e.kind == "span" and e.site == "device_wait"]
    assert waits == ["d2h_ready", "d2h_ready"], waits   # no read of its own

    assert _same(df.collect(), want)
    assert all(a._hash_disabled for a in _update_aggs(tpu))
    assert tpu.last_metrics["dispatchCount"] == 1
    assert _pipeline(tpu)["programs"] == 1, _pipeline(tpu)
    assert "flagReruns" not in _pipeline(tpu), _pipeline(tpu)
    assert _fallbacks(tpu) == 0
    assert _flat(tpu, "keylessAggBatches") == 0
    root = tpu.last_physical_plan
    assert sorted({k[0] for k in root._stage_cache}) == ["hash", "sort"]
    assert sorted(root._stage_builds) == ["hash", "sort"]


def test_keyless_aggregate_of_no_rows_yields_the_default_row():
    def q(s):
        df = s.create_dataframe(_data(n=300), num_partitions=2)
        return df.filter(df["v"] > 10**12).agg(
            Column(Alias(Sum(ColumnRef("f")), "sf")),
            Column(Alias(Count(ColumnRef("f")), "cf")))

    tpu = tpu_session(**FLOAT_AGG)
    assert q(tpu).collect() == q(cpu_session(**FLOAT_AGG)).collect() \
        == [(None, 0)]
    assert tpu.last_metrics["dispatchCount"] == 1
    assert "flagReruns" not in _pipeline(tpu)


def _clean_and_nan_sides(s):
    clean, bad = _data(n=700), _data(n=900, with_nan=True)
    return _keyless_sums(s, clean), _keyless_sums(s, bad)


def _union_of_keyless(s):
    a, b = _clean_and_nan_sides(s)
    return a.union(b)


def _keyed_over_keyless(s):
    # the keyless aggregate is fused into the KEYED update's stage, a
    # stage break: not the collected root
    _a, b = _clean_and_nan_sides(s)
    return b.group_by("cf").agg(Column(Alias(Sum(ColumnRef("sf")), "x")))


def _keyless_under_cross_join(s):
    _a, b = _clean_and_nan_sides(s)
    return s.create_dataframe({"w": (T.INT, [1, 2, 3])}).join(
        b, how="cross")


@pytest.mark.parametrize("build,reruns", [
    (_union_of_keyless, 1), (_keyed_over_keyless, 2),
    (_keyless_under_cross_join, None),
], ids=["two_under_a_union", "under_a_keyed_aggregate",
        "under_a_cross_join"])
def test_no_consumer_sees_an_unvalidated_keyless_partial(build, reruns):
    """Where the fused stage is not alone or not the collected root, every
    flag is read before the stage's outputs are handed on: under a union
    both flags ride with the answer and only the flagged side falls back;
    under a keyed aggregate (a stage break) they are read in one
    ``device_read`` before the consumer is dispatched; under a join the
    aggregate runs the iterator path, which reads its own."""
    tpu, cpu = tpu_session(**FLOAT_AGG), cpu_session(**FLOAT_AGG)
    want = build(cpu).collect()
    assert any(x != x for r in want for x in r if isinstance(x, float))
    assert _same(sorted(build(tpu).collect(), key=repr),
                 sorted(want, key=repr))
    assert _fallbacks(tpu) >= 1
    if reruns is not None:
        assert _pipeline(tpu)["flagReruns"] == reruns, _pipeline(tpu)
    flagged = [a._hash_disabled for a in _update_aggs(tpu)
               if not a.key_exprs]
    assert sorted(flagged) == ([False, True] if build is _union_of_keyless
                               else [True]), flagged
    waits = [e.name for e in tpu.query_history()[-1].events
             if e.kind == "span" and e.site == "device_wait"]
    if build is _keyed_over_keyless:
        # read where the break's stage hands on, before its consumer runs
        assert waits.count("stage_flags") == 2, waits
        assert waits.index("stage_flags") < waits.index("d2h_ready")
    elif build is _union_of_keyless:
        assert waits == ["d2h_ready", "d2h_ready"], waits


def test_a_stage_that_may_rerun_does_not_donate_its_sources():
    """The fused stage reads fresh host->device stagings, donatable to any
    other stage: while the inlined update speculates they are kept (the
    rerun needs them); once it runs the exact variant they are donated
    where the process can donate."""
    from spark_rapids_tpu.analysis.plan_verify import verify_plan
    from spark_rapids_tpu.plan.physical import HostToDeviceExec
    from spark_rapids_tpu.utils.compile_registry import donation_supported
    data = _data(n=1500, with_nan=True)
    tpu = tpu_session(**FLOAT_AGG)
    df = _keyless_sums(tpu, data)
    df.collect()
    df.collect()
    root = tpu.last_physical_plan
    sources = {v: b[0] for v, b in root._stage_builds.items()}
    assert all(isinstance(src, HostToDeviceExec)
               for srcs in sources.values() for src in srcs), sources
    masks = {v: dmask for v, _spec, dmask in root._stage_cache}
    assert not any(masks["hash"]), masks
    assert all(masks["sort"]) == donation_supported(), masks
    verify_plan(root)



# -- string keys that arrive dictionary-encoded are digits --------------------


def _encoded(columns):
    """Device batch from ``{name: values}``: a list of str/None becomes a
    dictionary-encoded string column (``(entries, codes)`` fixes the
    dictionary by hand), anything else a plain column."""
    import pyarrow as pa
    from spark_rapids_tpu.batch import host_to_device
    from spark_rapids_tpu.io.arrow_convert import arrow_to_host_batch
    arrays = {}
    for name, vals in columns.items():
        if isinstance(vals, tuple):
            entries, codes = vals
            arrays[name] = pa.DictionaryArray.from_arrays(
                pa.array(codes, type=pa.int32()),
                pa.array(entries, type=pa.string()))
        elif isinstance(vals, pa.Array):
            arrays[name] = vals
        else:
            arrays[name] = pa.array(vals, type=pa.string()) \
                .dictionary_encode()
    db = host_to_device(arrow_to_host_batch(pa.table(arrays),
                                            keep_dictionary=True))
    assert all(c.codes is not None for c in db.columns if c.is_string)
    return db


def _key_case(name):
    """(key names, [(batch columns, keep-mask or None), ...]): the update
    batches of one aggregate, each with the filter above it."""
    import pyarrow as pa
    rng = np.random.RandomState(41)

    def values(n):
        v = [None if i % 7 == 0 else int(x) for i, x in
             enumerate(rng.randint(-2**40, 2**40, n))]
        d = [None if i % 5 == 0 else float(x) for i, x in
             enumerate((rng.rand(n) * 2e5 - 1e5).round(2))]
        return {"v": pa.array(v, type=pa.int64()),
                "d": pa.array(d, type=pa.float64())}

    def batch(n, **keys):
        return dict(keys, **values(n))

    def pick(words, n):
        return [words[i] for i in rng.randint(0, len(words), n)]

    if name == "one_key":
        return ["a"], [(batch(900, a=pick(["A", "N", "R"], 900)), None)]
    if name == "two_keys":
        return ["a", "b"], [(batch(900, a=pick(["A", "N", "R"], 900),
                                   b=pick(["F", "O"], 900)), None)]
    if name == "null_keys":
        return ["a", "b"], [(batch(
            700, a=pick(["A", None, "R", ""], 700),
            b=pick([None, "O", "a longer status"], 700)), None)]
    if name == "unused_entry":
        # entries 0 and 3 of the dictionary name no row
        return ["a"], [(batch(500, a=(
            ["unused", "N", "R", "never"],
            [int(c) for c in rng.randint(1, 3, 500)])), None)]
    if name == "filter_drops_one_group":
        a = pick(["A", "N", "R"], 800)
        return ["a"], [(batch(800, a=a), np.array([x != "N" for x in a]))]
    if name == "filter_drops_every_row":
        return ["a", "b"], [(batch(300, a=pick(["A", "N"], 300),
                                   b=pick(["F", "O"], 300)),
                             np.zeros(300, np.bool_))]
    if name == "empty_batch":
        return ["a"], [(batch(0, a=[]), None),
                       (batch(200, a=pick(["A", "N"], 200)), None)]
    if name == "two_dictionaries":
        # the same strings under other codes, and strings of their own
        return ["a", "b"], [
            (batch(600, a=pick(["A", "N", "R"], 600),
                   b=pick(["F", "O"], 600)), None),
            (batch(400, a=pick(["R", "X", "A"], 400),
                   b=(["O", "Q", "F"],
                      [int(c) for c in rng.randint(0, 3, 400)])), None)]
    raise AssertionError(name)


def _rows_by_key(batch, n_keys):
    from spark_rapids_tpu.batch import device_to_host
    cols = list(device_to_host(batch).to_pydict().values())
    return sorted(zip(*cols), key=lambda r: tuple(
        (k is None, k or "") for k in r[:n_keys])) if cols else []


def _assert_same_groups(got, want, what):
    assert len(got) == len(want), (what, got, want)
    for g, w in zip(got, want):
        assert len(g) == len(w)
        for x, y in zip(g, w):
            if isinstance(y, float):
                assert x == pytest.approx(y, rel=1e-12, abs=0), (what, g, w)
            else:           # keys, counts, integer sums, NULLs: exact
                assert x == y and type(x) is type(y), (what, g, w)


@pytest.mark.parametrize("name", [
    "one_key", "two_keys", "null_keys", "unused_entry",
    "filter_drops_one_group", "filter_drops_every_row", "empty_batch",
    "two_dictionaries"])
def test_encoded_string_keys_group_by_their_codes_like_the_sort_form(name):
    """A string key that arrives dictionary-encoded is a digit of the slot
    index: every update batch answers through the contraction what the
    sort form answers on the SAME batch (string keys in row layout, one
    row a group), and the partials of batches with different dictionaries
    merge to the sort form's answer."""
    from spark_rapids_tpu.exprs.aggregates import (
        AggregateExpression, count_star,
    )
    from spark_rapids_tpu.kernels.layout import compact
    from spark_rapids_tpu.ops import tpu_exec as X
    from spark_rapids_tpu.plan.physical import PhysicalOp
    key_names, batches = _key_case(name)
    keys = [ColumnRef(k, T.STRING, True) for k in key_names]
    L, D = ColumnRef("v", T.LONG, True), ColumnRef("d", T.DOUBLE, True)
    aggs = [AggregateExpression(fn, f"o{i}") for i, fn in enumerate(
        [Sum(L), Count(L), Sum(D), Average(D), count_star(), Max(L)])]
    child = PhysicalOp([], T.Schema(
        [(k, T.STRING) for k in key_names] + [("v", T.LONG),
                                              ("d", T.DOUBLE)]))
    update = X.TpuHashAggregateExec(
        "update", keys, key_names, aggs, child,
        X._buffer_schema(key_names, keys, aggs))
    merge = X.TpuHashAggregateExec(
        "merge", keys, key_names, aggs, update, T.Schema(
            [(k, T.STRING) for k in key_names] +
            [(a.output_name, a.dtype) for a in aggs]))
    assert update._hash_capable and not merge._hash_capable

    fast, slow = [], []
    for columns, keep in batches:
        b = _encoded(columns)
        if keep is not None:
            mask = jnp.zeros(b.capacity, jnp.bool_).at[:len(keep)].set(
                jnp.asarray(keep))
            b = compact(b, mask, keep_encoded=True)
            assert all(c.codes is not None for c in b.columns[:len(keys)])
        partial, flag = jax.jit(update._aggregate_batch_hash)(b)
        assert flag is not None and not bool(flag), name
        # a partial leaves as the sort form's does: row-layout strings
        assert all(c.codes is None and c.offsets is not None
                   for c in partial.columns[:len(keys)])
        want = jax.jit(update._aggregate_batch)(b)
        _assert_same_groups(_rows_by_key(partial, len(keys)),
                            _rows_by_key(want, len(keys)), name)
        fast.append(partial)
        slow.append(want)

    def merged(partials):
        one = X._concat_all(partials, update.output_schema)
        return _rows_by_key(jax.jit(merge._aggregate_batch)(one), len(keys))

    answer = merged(fast)
    _assert_same_groups(answer, merged(slow), name)
    if name == "filter_drops_one_group":
        assert [r[0] for r in answer] == ["A", "R"]
    if name == "filter_drops_every_row":
        assert answer == []
    if name == "unused_entry":
        assert [r[0] for r in answer] == ["N", "R"]
    if name == "two_dictionaries":
        assert sorted({r[0] for r in answer}) == ["A", "N", "R", "X"]
        assert sorted({r[1] for r in answer}) == ["F", "O", "Q"]


@pytest.mark.parametrize("key", ["plain", "computed"])
def test_string_key_without_codes_takes_the_sort_form_and_speculates_nothing(
        tmp_path, key):
    """A string key that arrives in row layout (a source that never
    encoded it) or is computed (no column to carry codes) is no digit: the
    update answers by the sort form in its first and only dispatch — no
    contraction counted, no flag raised, no rerun."""
    from spark_rapids_tpu import functions as F
    data = {"s": (T.STRING, [None if i % 11 == 0 else "ANR"[i % 3]
                             for i in range(2000)]),
            "v": (T.LONG, list(range(2000))),
            "f": (T.DOUBLE, [i * 0.25 for i in range(2000)])}
    path = str(tmp_path / "sk.parquet")
    tpu_session().create_dataframe(data, num_partitions=2).write_parquet(path)

    def q(s):
        if key == "plain":      # host rows, staged in row layout
            df = s.create_dataframe(data, num_partitions=2)
            return df.group_by("s").agg(F.sum("v").alias("sv"),
                                        F.sum("f").alias("sf"))
        df = s.read.parquet(path)   # encoded, until the key is computed
        return df.group_by(F.concat(F.col("s"), F.lit("-x")).alias("k")) \
            .agg(F.sum("v").alias("sv"), F.sum("f").alias("sf"))

    assert_tpu_cpu_equal(q, approx=True, confs=FLOAT_AGG)
    tpu = tpu_session(**FLOAT_AGG)
    q(tpu).collect()
    assert _flat(tpu, "keyedUpdateBatches") > 0, tpu.last_metrics
    assert _flat(tpu, "mxuAggBatches") == 0 and not _mxu_engaged(tpu)
    assert _fallbacks(tpu) == 0
    assert "flagReruns" not in _pipeline(tpu), _pipeline(tpu)
    (update,) = _update_aggs(tpu)
    assert not update._hash_disabled
    # only a bare column can carry codes: a computed key names no fast
    # variant, a bare one that arrived plain was asked at the trace
    assert update._hash_capable == (key == "plain")
