"""Serving benchmark core: the workload behind ``tools/rapidsserve.py``.

The lane answers the serving runtime's three headline claims with one
deterministic template workload (filter+project over per-request row
batches, round-robined across tenants):

1. **Concurrent beats serial**: the same queries served through the
   scheduler (N runners, micro-batching on) finish in less wall time
   than strictly one-at-a-time submission (``serve_vs_serial > 1`` with
   ``serve_batched_queries > 0``) — while staying bit-identical
   (``serve_parity``).
2. **The executable cache is process-wide**: a second session executing
   the same plan reports ``compileCount == 0``
   (``serve_second_session_compiles``).
3. **Tenancy is observable**: per-tenant completed/failed/deadline
   counts and p50/p99 latencies roll up into the result
   (``serve_tenants``).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import HostBatch


def _request_batch(i: int, rows: int) -> HostBatch:
    """Deterministic per-request rows (seeded by the request index)."""
    base = i * 1315423911 % 1000003
    xs = [(base + 7 * j) % 1000 for j in range(rows)]
    ys = [float((base + 3 * j) % 97) for j in range(rows)]
    return HostBatch.from_pydict({"x": (T.LONG, xs),
                                  "y": (T.DOUBLE, ys)})


def _rows_sorted(batch: HostBatch) -> List[tuple]:
    """Row tuples in sorted order (aggregation output order is not
    deterministic across partition schedules)."""
    cols = batch.to_pydict()
    return sorted(zip(*[cols[name] for name in batch.schema.names]))


def _template():
    from spark_rapids_tpu.serve.batching import QueryTemplate
    return QueryTemplate(
        "bench-filter-project",
        lambda df: df.filter("x % 2 = 0").select("x", "y"))


def run_serve_bench(queries: int = 32, rows: int = 512,
                    tenants: Optional[Dict[str, float]] = None,
                    fault: str = "", deadline_sec: float = 0.0,
                    max_concurrency: int = 2,
                    conf=None) -> Dict[str, Any]:
    """Run the serving workload; returns the ``serve_*`` metric dict."""
    from spark_rapids_tpu.session import TpuSparkSession
    from spark_rapids_tpu.serve.scheduler import ServeScheduler
    tenants = tenants or {"a": 2.0, "b": 1.0}
    builder = TpuSparkSession.builder()
    if conf is not None:
        for k, v in conf._settings.items():
            builder.config(k, v)
    for name, weight in tenants.items():
        builder.config(
            f"spark.rapids.sql.tpu.serve.tenant.{name}.weight", str(weight))
    if fault:
        builder.config("spark.rapids.sql.tpu.faults.spec", fault)
    builder.config("spark.rapids.sql.tpu.serve.maxConcurrency",
                   str(max_concurrency))
    session = builder.get_or_create()
    tmpl = _template()
    tenant_names = sorted(tenants)
    batches = [_request_batch(i, rows) for i in range(queries)]

    # plain (non-micro) lane: a two-partition aggregation (multiple
    # dispatches per query), so a per-query fault spec like
    # dispatch:oom@2 actually fires mid-query and must be absorbed by
    # the recovery ladder without wrong rows
    from spark_rapids_tpu.dataframe import DataFrame
    from spark_rapids_tpu.plan.logical import InMemoryScan
    n = max(rows, 64)
    plain_parts = [HostBatch.from_pydict({
        "k": (T.LONG, [(p * n + j) % 5 for j in range(n)]),
        "v": (T.LONG, [(p * n + 3 * j) % 997 for j in range(n)]),
    }) for p in range(2)]
    plain_df = DataFrame(
        InMemoryScan(plain_parts, plain_parts[0].schema, num_partitions=2),
        session).group_by("k").sum("v")
    plain_expected, _pm = session.execute_with_metrics(plain_df.plan)
    plain_queries = max(2, queries // 4)

    # -- serial baseline: same template path, one at a time (no overlap,
    # no coalescing) --------------------------------------------------------
    serial_sched = ServeScheduler(session, max_concurrency=1)
    serial_sched._batch_enabled = False
    # warm the executables outside both timed phases so the comparison
    # measures serving, not first-compile
    serial_sched.submit_micro(tmpl, batches[0]).result(timeout=120)
    t0 = time.monotonic()
    serial_out: List[HostBatch] = []
    for i, b in enumerate(batches):
        fut = serial_sched.submit_micro(
            tmpl, b, tenant=tenant_names[i % len(tenant_names)],
            deadline_sec=deadline_sec)
        serial_out.append(fut.result(timeout=120))
    for i in range(plain_queries):
        serial_sched.submit(
            plain_df, tenant=tenant_names[i % len(tenant_names)],
            deadline_sec=deadline_sec).result(timeout=120)
    serial_wall = time.monotonic() - t0
    serial_sched.close()

    # -- concurrent served phase: one unmeasured pass compiles the
    # coalesced-bucket programs, the measured pass is steady-state
    # serving (the regime the scheduler exists for) ------------------------
    warm = ServeScheduler(session, max_concurrency=max_concurrency)
    for f in [warm.submit_micro(
            tmpl, b, tenant=tenant_names[i % len(tenant_names)])
            for i, b in enumerate(batches)]:
        f.result(timeout=120)
    warm.close()
    sched = ServeScheduler(session, max_concurrency=max_concurrency,
                           autostart=False)
    futs = [sched.submit_micro(
        tmpl, b, tenant=tenant_names[i % len(tenant_names)],
        deadline_sec=deadline_sec) for i, b in enumerate(batches)]
    plain_futs = [sched.submit(
        plain_df, tenant=tenant_names[i % len(tenant_names)],
        deadline_sec=deadline_sec) for i in range(plain_queries)]
    t0 = time.monotonic()
    sched.start()
    results = [f.result(timeout=120) for f in futs]
    plain_results = [f.result(timeout=120) for f in plain_futs]
    wall = time.monotonic() - t0
    stats = sched.stats()
    sched.close()

    parity = all(a.to_pydict() == b.to_pydict()
                 for a, b in zip(serial_out, results))
    expected_rows = _rows_sorted(plain_expected)
    parity = parity and all(_rows_sorted(r) == expected_rows
                            for r in plain_results)
    fault_metrics = [f.metrics for f in futs + plain_futs
                     if f.metrics is not None]
    faults_injected = sum(m.get("faultsInjected", 0)
                          for m in fault_metrics)
    retries = sum(m.get("retryCount", 0) for m in fault_metrics)

    # -- shared executable cache: a second session, same plan object ---
    probe = session.create_dataframe(
        {"x": (T.LONG, list(range(rows)))}).filter("x > 1").select("x")
    _out, _m = session.execute_with_metrics(probe.plan)
    second = TpuSparkSession(session.conf.copy())
    _out2, m2 = second.execute_with_metrics(probe.plan)

    total = queries + plain_queries
    return {
        "serve_queries": total,
        "serve_plain_queries": plain_queries,
        "serve_rows_per_query": rows,
        "serve_wall_s": round(wall, 4),
        "serve_serial_wall_s": round(serial_wall, 4),
        "serve_queries_per_sec": round(total / wall, 2) if wall else 0.0,
        "serve_vs_serial": round(serial_wall / wall, 3) if wall else 0.0,
        "serve_p50_ms": round(stats["p50_ms"], 3),
        "serve_p99_ms": round(stats["p99_ms"], 3),
        "serve_batched_queries": stats["batched_queries"],
        "serve_micro_dispatches": stats["micro_dispatches"],
        "serve_completed": stats["completed"],
        "serve_failed": stats["failed"],
        "serve_deadline_exceeded": stats["deadline_exceeded"],
        "serve_faults_injected": faults_injected,
        "serve_retries": retries,
        "serve_parity": bool(parity),
        "serve_second_session_compiles": m2["compileCount"],
        "serve_plan_cache_hits": stats["plan_cache_hits"],
        "serve_tenants": stats["tenants"],
    }


# -- network front-door lane -------------------------------------------------


FRONTEND_VIEW = "bench_events"
FRONTEND_SQLS = [
    f"SELECT k, SUM(v) AS s FROM {FRONTEND_VIEW} "
    f"WHERE v < {c} GROUP BY k"
    for c in (700, 800, 900, 997)
]


def frontend_demo_session(tenants: Optional[Dict[str, float]] = None,
                          history_dir: str = "", rows: int = 4096,
                          conf=None):
    """A session with the deterministic front-door demo view registered
    — shared by ``rapidsserve --server``, the CI smoke and
    ``tests/test_frontend.py`` so every client speaks the same schema."""
    from spark_rapids_tpu.dataframe import DataFrame
    from spark_rapids_tpu.plan.logical import InMemoryScan
    from spark_rapids_tpu.session import TpuSparkSession
    tenants = tenants or {"a": 2.0, "b": 1.0}
    builder = TpuSparkSession.builder()
    if conf is not None:
        for k, v in conf._settings.items():
            builder.config(k, v)
    for name, weight in tenants.items():
        builder.config(
            f"spark.rapids.sql.tpu.serve.tenant.{name}.weight", str(weight))
    if history_dir:
        builder.config("spark.rapids.sql.tpu.history.dir", history_dir)
    session = builder.get_or_create()
    n = max(64, rows // 2)
    parts = [HostBatch.from_pydict({
        "k": (T.LONG, [(p * n + j) % 5 for j in range(n)]),
        "v": (T.LONG, [(p * n + 3 * j) % 997 for j in range(n)]),
    }) for p in range(2)]
    session.register_view(FRONTEND_VIEW, DataFrame(
        InMemoryScan(parts, parts[0].schema, num_partitions=2), session))
    return session
