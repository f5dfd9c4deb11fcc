"""Serve front door tests (ISSUE PR 16 acceptance list): end-to-end
over a real socket with bit-identical rows, the shared plan cache
spanning client connections (second client compiles nothing), the
result cache answering warm repeats with zero compiles AND zero
dispatches, its three invalidation edges (input mtime, conf signature,
device generation), cost-weighted admission, sentinel-driven admission
control shedding predicted deadline misses before execution, clean
drain accounting, per-tenant telemetry gauges, and the adaptive
micro-batch window's clamping."""

import os
import threading
import time

import pytest

from compare import tpu_session
from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import HostBatch
from spark_rapids_tpu.obs import timeseries as obs_ts
from spark_rapids_tpu.serve import (
    DeadlineExceeded, FrontDoorClient, FrontDoorServer, ResultCache,
    ServeScheduler, result_cache,
)
from spark_rapids_tpu.serve import protocol

SQL = "SELECT k, SUM(v) AS s FROM events GROUP BY k"


@pytest.fixture(autouse=True)
def _clean_state():
    """The result cache and telemetry ring are process singletons —
    never let one test's entries serve another's queries."""
    saved_ring = obs_ts._RING
    result_cache().clear()
    yield
    result_cache().clear()
    obs_ts._RING = saved_ring


def _session(**confs):
    s = tpu_session(**confs)
    df = s.create_dataframe(
        {"k": [i % 5 for i in range(400)],
         "v": [(3 * i) % 97 for i in range(400)]}, num_partitions=2)
    s.register_view("events", df)
    return s


def _rows(batch):
    cols = batch.to_pydict()
    return sorted(zip(*[cols[name] for name in batch.schema.names]))


def _expected(s, sql=SQL):
    return _rows(s.execute(s.sql(sql).plan))


# -- wire protocol units ------------------------------------------------------


def test_wire_batch_roundtrip_json_and_arrow():
    """Both encodings must survive nulls, strings and doubles
    bit-identically."""
    hb = HostBatch.from_pydict({
        "s": (T.STRING, ["a", None, "", "δ"]),
        "i": (T.LONG, [1, None, -3, 2**40]),
        "d": (T.DOUBLE, [0.5, float("inf"), None, -0.0]),
    })
    for enc in ("json", "arrow"):
        wire = protocol.batch_to_wire(hb, enc)
        back = protocol.wire_to_batch(wire)
        assert back.to_pydict() == hb.to_pydict(), enc
        assert wire["encoding"] == enc


def test_wire_batch_rejects_malformed():
    with pytest.raises(protocol.ProtocolError):
        protocol.wire_to_batch({"names": ["a"], "types": []})


# -- end-to-end over a real socket -------------------------------------------


def test_socket_parity_and_second_client_compiles_zero():
    """Rows over the wire are bit-identical to in-process execution,
    and a second client CONNECTION compiles nothing — the plan cache
    (and the front door's statement cache pinning its entries) spans
    connections."""
    s = _session()
    # keep the in-process plan object alive for the whole test: the
    # shared plan cache's entries are weakly anchored to their logical
    # plan, so a throwaway plan would strand a dying entry on the
    # fingerprint and force one rebuild mid-sequence
    plan = s.sql(SQL).plan
    want = _rows(s.execute(plan))
    with FrontDoorServer(s) as srv:
        with FrontDoorClient("127.0.0.1", srv.port) as c1:
            out, m1 = c1.submit_sql(SQL, tenant="a", cache=False)
            assert _rows(out) == want
        with FrontDoorClient("127.0.0.1", srv.port) as c2:
            out2, m2 = c2.submit_sql(SQL, tenant="b", cache=False)
            assert _rows(out2) == want
            assert m2["compileCount"] == 0, m2
            assert m2["resultCacheHits"] == 0


def test_result_cache_warm_repeat_zero_compiles_zero_dispatches():
    """A repeat query answers from the result cache across
    connections: zero compiles, zero dispatches, same rows."""
    s = _session()
    want = _expected(s)
    with FrontDoorServer(s) as srv:
        with FrontDoorClient("127.0.0.1", srv.port) as c:
            _out, m1 = c.submit_sql(SQL)  # miss: executes + inserts
            assert m1["resultCacheHits"] == 0
        with FrontDoorClient("127.0.0.1", srv.port) as c2:
            out, m2 = c2.submit_sql(SQL)
            assert _rows(out) == want
            assert m2["resultCacheHits"] == 1, m2
            assert m2["compileCount"] == 0
            assert m2["dispatchCount"] == 0
            st = c2.stats()["frontend"]
            assert st["result_cache_hits"] == 1
            d = c2.drain()
            assert d["drained"] and d["held_depth"] == 0


def test_template_over_wire_matches_in_process():
    """The micro-query template path works over the socket and matches
    the in-process scheduler's rows."""
    from spark_rapids_tpu.serve.bench import _request_batch, _template
    s = tpu_session()
    tmpl = _template()
    batch = _request_batch(3, 64)
    sched = ServeScheduler(s)
    want = sched.submit_micro(tmpl, batch).result(timeout=120).to_pydict()
    with FrontDoorServer(s, scheduler=sched) as srv:
        srv.register_template(tmpl)
        with FrontDoorClient("127.0.0.1", srv.port) as c:
            out, _m = c.submit_template(tmpl.key, batch, tenant="a")
            assert out.to_pydict() == want


# -- result-cache invalidation edges -----------------------------------------


def test_result_cache_mtime_invalidation(tmp_path):
    """Touching an input file changes its (mtime_ns, size) identity:
    the repeat MUST re-execute (dispatches > 0), with the same rows."""
    s = tpu_session()
    df = s.create_dataframe(
        {"k": [i % 5 for i in range(256)],
         "v": [(3 * i) % 97 for i in range(256)]}, num_partitions=2)
    pq = str(tmp_path / "pq")
    df.write_parquet(pq)
    s.register_view("events", s.read.parquet(pq))
    want = _expected(s)
    with FrontDoorServer(s) as srv:
        with FrontDoorClient("127.0.0.1", srv.port) as c:
            c.submit_sql(SQL)
            _out, m_hit = c.submit_sql(SQL)
            assert m_hit["resultCacheHits"] == 1

            part = next(f for f in sorted(os.listdir(pq))
                        if f.endswith(".parquet"))
            path = os.path.join(pq, part)
            st = os.stat(path)
            os.utime(path, ns=(st.st_atime_ns, st.st_mtime_ns + 10**9))

            out, m = c.submit_sql(SQL)
            assert m["resultCacheHits"] == 0, m
            assert m["dispatchCount"] > 0
            assert _rows(out) == want


def test_result_cache_conf_signature_invalidation():
    """A plan-relevant conf change must MISS: the key carries the conf
    signature, so the repeat re-executes under the new conf."""
    s = _session()
    want = _expected(s)
    with FrontDoorServer(s) as srv:
        with FrontDoorClient("127.0.0.1", srv.port) as c:
            c.submit_sql(SQL)
            _out, m_hit = c.submit_sql(SQL)
            assert m_hit["resultCacheHits"] == 1

            s.conf.set("spark.sql.shuffle.partitions", 3)
            out, m = c.submit_sql(SQL)
            assert m["resultCacheHits"] == 0, m
            assert m["dispatchCount"] > 0
            assert _rows(out) == want


def test_result_cache_generation_invalidation():
    """A device-lost recovery bumps the runtime generation: entries
    built under the old device are dropped on fetch and the repeat
    re-executes on the recovered runtime."""
    from spark_rapids_tpu.runtime.device import DeviceRuntime
    DeviceRuntime.reset()
    try:
        s = _session()
        want = _expected(s)
        with FrontDoorServer(s) as srv:
            with FrontDoorClient("127.0.0.1", srv.port) as c:
                c.submit_sql(SQL)
                _out, m_hit = c.submit_sql(SQL)
                assert m_hit["resultCacheHits"] == 1

                DeviceRuntime.recover(s.conf)
                out, m = c.submit_sql(SQL)
                assert m["resultCacheHits"] == 0, m
                assert m["dispatchCount"] > 0
                assert _rows(out) == want
    finally:
        DeviceRuntime.reset()
        result_cache().clear()


def test_result_cache_cost_weighted_admission():
    """A cheap-compute / big-bytes result must be REJECTED: caching it
    would evict genuinely expensive results for no latency win."""
    cache = ResultCache(min_ns_per_byte=50.0)
    big = HostBatch.from_pydict(
        {"x": (T.LONG, list(range(4096)))})  # ~32 KiB
    # 1000 ns of recorded compute for ~32 KiB: way under 50 ns/byte
    assert cache.insert(("fp", "sig", "in"), None, big,
                        wall_ns=1000, conf=None) is False
    assert cache.stats()["result_cache_admission_rejects"] == 1
    assert len(cache) == 0


# -- sentinel-driven admission control ---------------------------------------


def test_admission_sheds_predicted_deadline_miss_before_executing(tmp_path):
    """With >= minRuns history records, a query whose predicted wall
    (median + K*MAD) already misses its deadline is shed at the front
    door: DeadlineExceeded classification, no execution, per-tenant rollup."""
    s = _session(**{
        "spark.rapids.sql.tpu.history.dir": str(tmp_path / "h"),
    })
    with FrontDoorServer(s) as srv:
        with FrontDoorClient("127.0.0.1", srv.port) as c:
            # cache=False: a result-cache hit would skip execution and
            # never append the history records the predictor needs
            for _ in range(3):
                c.submit_sql(SQL, tenant="a", cache=False)
            before = c.stats()
            completed_before = before["scheduler"]["completed"]

            with pytest.raises(DeadlineExceeded):
                c.submit_sql(SQL, tenant="a", cache=False,
                             deadline_sec=1e-6)

            st = c.stats()
            assert st["frontend"]["admission_shed"] == 1
            assert st["frontend"]["admission_shed_by_tenant"] == {"a": 1}
            # shed BEFORE executing: nothing new completed
            assert st["scheduler"]["completed"] == completed_before
            ten = st["scheduler"]["tenants"]["a"]
            assert ten["deadline_exceeded"] == 1
            assert ten["failed"] == 1

            # the same query WITHOUT a deadline still executes fine
            out, m = c.submit_sql(SQL, tenant="a", cache=False)
            assert m["admissionShed"] == 0
            assert _rows(out) == _expected(s)


def test_admission_inactive_without_history_baseline():
    """No history subsystem -> no prediction -> never shed (a tight
    deadline still applies at execution, but admission stays out)."""
    s = _session()
    with FrontDoorServer(s) as srv:
        with FrontDoorClient("127.0.0.1", srv.port) as c:
            out, m = c.submit_sql(SQL, tenant="a", cache=False,
                                  deadline_sec=60.0)
            assert m["admissionShed"] == 0
            assert c.stats()["frontend"]["admission_shed"] == 0


# -- telemetry ----------------------------------------------------------------


def test_per_tenant_gauges_in_prometheus_export():
    """Per-tenant queue/inflight/deadline-miss gauges register with the
    telemetry ring and render as Prometheus series."""
    s = _session()
    _expected(s)  # an execute configures the process telemetry ring
    with FrontDoorServer(s) as srv:
        with FrontDoorClient("127.0.0.1", srv.port) as c:
            c.submit_sql(SQL, tenant="a", cache=False)
            c.submit_sql(SQL, tenant="b", cache=False)
    ring = obs_ts.ring()
    assert ring is not None
    text = ring.prometheus_text()
    for name in ("rapids_serve_tenant_a_queue_depth",
                 "rapids_serve_tenant_a_inflight",
                 "rapids_serve_tenant_a_deadline_miss",
                 "rapids_serve_tenant_b_queue_depth",
                 "rapids_serve_frontend_connections",
                 "rapids_serve_frontend_requests"):
        assert name in text, (name, text)


# -- adaptive micro-batch window ---------------------------------------------


def test_adaptive_batch_window_clamped():
    """The adaptive linger is bounded to [0, maxDelayMs]: zero with no
    observed arrivals, clamped to maxDelayMs under a sparse trickle,
    near-zero under a flood, and the static linger while telemetry is
    off."""
    s = tpu_session(**{
        "spark.rapids.sql.tpu.serve.batch.adaptive.enabled": True,
        "spark.rapids.sql.tpu.serve.batch.maxDelayMs": 20,
    })
    sched = ServeScheduler(s, autostart=False)
    assert sched._batch_adaptive is True

    obs_ts._RING = None
    assert sched._adaptive_delay_s() == pytest.approx(0.020)

    obs_ts._RING = obs_ts.TelemetryRing(interval_ms=1000, max_intervals=2)
    assert sched._adaptive_delay_s() == 0.0  # quiet: don't linger

    obs_ts.record_value("serve.arrivals", 1.0)  # sparse: 2/rate > max
    assert sched._adaptive_delay_s() == pytest.approx(0.020)

    for _ in range(500):  # flood (near the per-interval sample cap):
        obs_ts.record_value("serve.arrivals", 1.0)  # 2/rate ~ 8ms
    d = sched._adaptive_delay_s()
    assert 0.0 < d < 0.020
    sched.close()


def test_adaptive_off_keeps_static_window():
    s = tpu_session(**{
        "spark.rapids.sql.tpu.serve.batch.maxDelayMs": 20,
    })
    sched = ServeScheduler(s, autostart=False)
    assert sched._batch_adaptive is False
    sched.close()


# -- concurrency + drain ------------------------------------------------------


def test_concurrent_socket_clients_parity_and_clean_drain():
    """Two weighted tenants hammering one front door from concurrent
    connections: every response bit-identical, then a clean drain with
    zero held semaphore depth."""
    s = _session(**{
        "spark.rapids.sql.tpu.serve.tenant.a.weight": "2",
        "spark.rapids.sql.tpu.serve.tenant.b.weight": "1",
    })
    want = _expected(s)
    errors = []
    with FrontDoorServer(s) as srv:
        def worker(tenant):
            try:
                with FrontDoorClient("127.0.0.1", srv.port) as c:
                    for _ in range(4):
                        out, _m = c.submit_sql(SQL, tenant=tenant,
                                               cache=False)
                        if _rows(out) != want:
                            errors.append(f"parity:{tenant}")
            except Exception as e:  # surfaced via the errors list
                errors.append(f"{tenant}: {type(e).__name__}: {e}")

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in ("a", "b")]
        for t in threads:
            t.start()
        for t in threads:
            while t.is_alive():
                t.join(0.25)
        assert errors == []
        with FrontDoorClient("127.0.0.1", srv.port) as c:
            d = c.drain()
            assert d["drained"] is True
            assert d["held_depth"] == 0
            sched_stats = c.stats()["scheduler"]
            tens = sched_stats["tenants"]
            assert tens["a"]["completed"] == 4
            assert tens["b"]["completed"] == 4
            assert sched_stats["failed"] == 0
