"""Serving runtime tests (ISSUE PR 11 acceptance list): shared
executable cache across sessions, N-thread concurrent bit-parity with
per-query metric attribution, weighted fair queueing, micro-batch
coalescing + maxDelayMs semantics, per-query deadlines failing fast,
and clean semaphore/catalog accounting after a concurrent storm."""

import time

import pytest

from compare import tpu_session
from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import HostBatch
from spark_rapids_tpu.serve import (
    DeadlineExceeded, QueryTemplate, ServeScheduler, shared_plan_cache,
)


def _df(s, n=200, seed=0):
    return s.create_dataframe({
        "k": [(seed + i) % 5 for i in range(n)],
        "v": [(seed + 3 * i) % 97 for i in range(n)],
    })


def _rows(batch):
    cols = batch.to_pydict()
    return sorted(zip(*[cols[name] for name in batch.schema.names]))


# -- shared executable cache -------------------------------------------------


def test_second_session_compiles_zero_and_identical():
    """The plan/executable cache is process-wide: a second session
    executing the same plan reports compileCount == 0 with bit-identical
    rows."""
    s1 = tpu_session()
    df = _df(s1).group_by("k").sum("v")
    out1, m1 = s1.execute_with_metrics(df.plan)

    s2 = tpu_session()
    out2, m2 = s2.execute_with_metrics(df.plan)
    assert m2["compileCount"] == 0, m2
    assert _rows(out2) == _rows(out1)
    # and the cache recorded the cross-session hit
    assert shared_plan_cache().stats()["plan_cache_hits"] >= 1


def test_plan_cache_keyed_by_conf_state():
    """A plan-relevant conf change must NOT reuse the cached physical
    plan (only obs. knobs are excluded from the key)."""
    s1 = tpu_session()
    df = _df(s1).filter("v > 10")
    s1.execute(df.plan)
    phys1 = s1.last_physical_plan
    s2 = tpu_session(**{"spark.rapids.sql.enabled": False})
    s2.execute(df.plan)
    assert s2.last_physical_plan is not phys1
    # an observability knob does reuse it
    s3 = tpu_session(**{"spark.rapids.sql.tpu.obs.ring.maxEvents": 4096})
    s3.execute(df.plan)
    assert s3.last_physical_plan is phys1


# -- concurrent execution ----------------------------------------------------


def test_concurrent_parity_and_clean_accounting():
    """N threads x M distinct queries through one scheduler return the
    same rows as serial execution; afterwards nothing holds the device
    semaphore and the catalog accounting is clean."""
    s = tpu_session()
    dfs = [_df(s, n=150, seed=7 * i).group_by("k").sum("v")
           for i in range(6)]
    serial = [_rows(s.execute(df.plan)) for df in dfs]

    with ServeScheduler(s, max_concurrency=3) as sched:
        futs = [sched.submit(df) for df in dfs]
        got = [_rows(f.result(timeout=120)) for f in futs]
    assert got == serial

    if s.runtime is not None and s.runtime.semaphore is not None:
        assert s.runtime.semaphore.held_depth() == 0
    if s.runtime is not None:
        assert s.runtime.catalog.verify_accounting() == []


def test_concurrent_metric_attribution():
    """Each future's metrics dict describes ITS query: per-query
    dispatch counts under concurrency sum to what the same queries
    report serially, and every query saw at least one dispatch."""
    s = tpu_session()
    dfs = [_df(s, n=120, seed=11 * i).filter("v > 5") for i in range(4)]
    serial_total = 0
    for df in dfs:
        _out, m = s.execute_with_metrics(df.plan)
        serial_total += m["dispatchCount"]

    with ServeScheduler(s, max_concurrency=4) as sched:
        futs = [sched.submit(df) for df in dfs]
        for f in futs:
            f.result(timeout=120)
    per_query = [f.metrics["dispatchCount"] for f in futs]
    assert all(d >= 1 for d in per_query), per_query
    assert sum(per_query) == serial_total, (per_query, serial_total)


# -- weighted fair queueing --------------------------------------------------


def test_weighted_fairness_ratio():
    """With tenant a at weight 2 and b at weight 1 and the whole backlog
    queued before the (single) runner starts, a's queries complete ~2x
    as often in any completion-order prefix."""
    s = tpu_session(**{
        "spark.rapids.sql.tpu.serve.tenant.a.weight": "2",
        "spark.rapids.sql.tpu.serve.tenant.b.weight": "1",
    })
    df = _df(s).filter("v > 3")
    s.execute(df.plan)  # warm compile outside the scheduled phase
    sched = ServeScheduler(s, max_concurrency=1, autostart=False)
    done = []
    for i in range(18):
        tenant = "a" if i < 12 else "b"  # 12 a's, 6 b's, all pre-queued
        fut = sched.submit(df, tenant=tenant)
        done.append((tenant, fut))
    # record completion order via future resolution polling
    sched.start()
    for tenant, fut in done:
        fut.result(timeout=120)
    st = sched.stats()
    sched.close()
    assert st["tenants"]["a"]["completed"] == 12
    assert st["tenants"]["b"]["completed"] == 6
    # vtime law: while both queues are non-empty, a pops twice per b pop.
    # Verify via per-tenant latency: b's median wait is ~>= a's (a drains
    # faster under contention).
    assert st["tenants"]["a"]["p50_ms"] <= st["tenants"]["b"]["p50_ms"] * 2


def test_wfq_pop_order_two_to_one():
    """The scheduler's pop order itself honors the 2:1 weights (checked
    on the internal queues without running queries)."""
    s = tpu_session(**{
        "spark.rapids.sql.tpu.serve.tenant.a.weight": "2",
        "spark.rapids.sql.tpu.serve.tenant.b.weight": "1",
    })
    sched = ServeScheduler(s, max_concurrency=1, autostart=False)
    df = _df(s)
    for _ in range(8):
        sched.submit(df, tenant="a")
    for _ in range(8):
        sched.submit(df, tenant="b")
    pops = []
    with sched._lock:
        for _ in range(9):
            tenant, _item = sched._pop_locked()
            pops.append(tenant.name)
    # first 9 pops at weights 2:1 -> 6 a's, 3 b's
    assert pops.count("a") == 6, pops
    assert pops.count("b") == 3, pops
    sched.close()


# -- micro-query batching ----------------------------------------------------


def _mk_batch(lo, n=40):
    return HostBatch.from_pydict({
        "x": (T.LONG, [(lo + i) % 100 for i in range(n)]),
        "y": (T.DOUBLE, [float((lo + 2 * i) % 9) for i in range(n)]),
    })


def test_micro_batch_parity_and_coalescing():
    """Same-template queries queued together coalesce into fewer
    dispatches and every caller gets exactly its own rows (bit-parity
    with individual serial execution)."""
    s = tpu_session()
    tmpl = QueryTemplate("evens-t1", lambda d: d.filter("x % 2 = 0"))
    batches = [_mk_batch(13 * i) for i in range(8)]

    # serial reference: no coalescing
    ser = ServeScheduler(s, max_concurrency=1)
    ser._batch_enabled = False
    expected = [ser.submit_micro(tmpl, b).result(timeout=120).to_pydict()
                for b in batches]
    ser.close()

    sched = ServeScheduler(s, max_concurrency=1, autostart=False)
    futs = [sched.submit_micro(tmpl, b) for b in batches]
    sched.start()
    got = [f.result(timeout=120).to_pydict() for f in futs]
    st = sched.stats()
    sched.close()
    assert got == expected
    assert st["batched_queries"] >= 2, st
    assert st["micro_dispatches"] < len(batches), st


def test_micro_batch_respects_max_queries():
    """serve.batch.maxQueries caps how many queries one dispatch
    carries."""
    s = tpu_session(**{"spark.rapids.sql.tpu.serve.batch.maxQueries": 3})
    tmpl = QueryTemplate("evens-t2", lambda d: d.filter("x % 2 = 0"))
    batches = [_mk_batch(7 * i) for i in range(9)]
    sched = ServeScheduler(s, max_concurrency=1, autostart=False)
    futs = [sched.submit_micro(tmpl, b) for b in batches]
    sched.start()
    for f in futs:
        f.result(timeout=120)
    st = sched.stats()
    sched.close()
    assert st["micro_dispatches"] >= 3, st


def test_micro_batch_max_delay_window():
    """With batching eligible, a lone micro query lingers at most
    ~maxDelayMs for partners: a straggler submitted within the window
    rides the same dispatch."""
    s = tpu_session(**{
        "spark.rapids.sql.tpu.serve.batch.maxDelayMs": 300.0})
    tmpl = QueryTemplate("evens-t3", lambda d: d.filter("x % 2 = 0"))
    sched = ServeScheduler(s, max_concurrency=1)
    # warm the group binding so the timed window isn't compile-bound
    sched.submit_micro(tmpl, _mk_batch(0)).result(timeout=120)
    f1 = sched.submit_micro(tmpl, _mk_batch(5))
    time.sleep(0.05)  # inside the 300ms window
    f2 = sched.submit_micro(tmpl, _mk_batch(11))
    f1.result(timeout=120)
    f2.result(timeout=120)
    st = sched.stats()
    sched.close()
    # warm dispatch + ONE coalesced dispatch for the pair
    assert st["micro_dispatches"] == 2, st
    assert st["batched_queries"] == 2, st


def test_micro_batch_rejects_non_rowwise_templates():
    """A template containing an aggregation cannot be coalesced (rows
    from different callers would mix) and fails with a clear error."""
    s = tpu_session()
    tmpl = QueryTemplate("bad-agg", lambda d: d.group_by("x").sum("y"))
    sched = ServeScheduler(s, max_concurrency=1)
    fut = sched.submit_micro(tmpl, _mk_batch(0))
    with pytest.raises(ValueError, match="row-wise"):
        fut.result(timeout=120)
    sched.close()


# -- deadlines ---------------------------------------------------------------


def test_deadline_exceeded_fails_fast_neighbors_finish():
    """An already-expired deadline fails fast (never executes) with
    DeadlineExceeded while a neighboring query completes normally."""
    s = tpu_session()
    df = _df(s).group_by("k").sum("v")
    expected = _rows(s.execute(df.plan))
    sched = ServeScheduler(s, max_concurrency=1, autostart=False)
    doomed = sched.submit(df, tenant="a", deadline_sec=1e-9)
    ok = sched.submit(df, tenant="b")
    time.sleep(0.01)  # let the 1ns deadline lapse while queued
    sched.start()
    with pytest.raises(DeadlineExceeded):
        doomed.result(timeout=120)
    assert _rows(ok.result(timeout=120)) == expected
    st = sched.stats()
    sched.close()
    assert st["deadline_exceeded"] == 1, st
    assert st["tenants"]["a"]["deadline_exceeded"] == 1
    assert st["tenants"]["b"]["completed"] == 1
    # fail-fast: the doomed query is NON_RETRYABLE, no recovery replay
    assert doomed.exception().__class__ is DeadlineExceeded


def test_generous_deadline_completes():
    s = tpu_session()
    df = _df(s).filter("v > 1")
    expected = _rows(s.execute(df.plan))
    with ServeScheduler(s, max_concurrency=2) as sched:
        fut = sched.submit(df, deadline_sec=60.0)
        assert _rows(fut.result(timeout=120)) == expected
    assert fut.metrics is not None


# -- storm: concurrency + batching + sessions -------------------------------


def test_mixed_storm_clean_after():
    """Micro + plain queries from 3 tenants on 3 runners: everything
    completes with correct rows, and the process is clean afterwards
    (no held semaphore permits, catalog accounting passes)."""
    s = tpu_session()
    tmpl = QueryTemplate("storm", lambda d: d.filter("x % 3 = 0"))
    df = _df(s, n=100).filter("v > 2")
    plain_expected = _rows(s.execute(df.plan))
    with ServeScheduler(s, max_concurrency=3) as sched:
        micro = [sched.submit_micro(tmpl, _mk_batch(3 * i),
                                    tenant=f"t{i % 3}") for i in range(9)]
        plain = [sched.submit(df, tenant=f"t{i % 3}") for i in range(6)]
        for f in micro:
            out = f.result(timeout=120)
            got = out.to_pydict()
            assert all(v % 3 == 0 for v in got["x"])
        for f in plain:
            assert _rows(f.result(timeout=120)) == plain_expected
        st = sched.stats()
    assert st["completed"] == 15, st
    assert st["failed"] == 0, st
    if s.runtime is not None and s.runtime.semaphore is not None:
        assert s.runtime.semaphore.held_depth() == 0
    if s.runtime is not None:
        assert s.runtime.catalog.verify_accounting() == []
