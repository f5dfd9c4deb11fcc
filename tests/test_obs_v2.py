"""Obs v2 tests (ISSUE PR 15 acceptance list): continuous time-series
telemetry (aggregation ring, JSONL flushes, Prometheus text, rapidstop),
exact critical-path attribution (serial and under serve concurrency,
with shuffle + spill + retry in the window), the cross-run regression
sentinel (fires on an injected slowdown, silent on clean runs, offline
via rapidshist --regressions), per-site ring-drop accounting with the
truncation banner, and session-stamped event-log round-trips."""

import json
import os
import subprocess
import sys
import time

import pytest

from compare import tpu_session
from spark_rapids_tpu import functions as F
from spark_rapids_tpu.fault import inject
from spark_rapids_tpu.history import store
from spark_rapids_tpu.history.fragcache import fragment_cache
from spark_rapids_tpu.obs import critpath as obs_critpath
from spark_rapids_tpu.obs import export as obs_export
from spark_rapids_tpu.obs import sentinel
from spark_rapids_tpu.obs import timeseries as obs_ts
from spark_rapids_tpu.obs.timeseries import TelemetryRing
from spark_rapids_tpu.serve import ServeScheduler

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_state():
    """Process-global state (fault registry, sentinel totals, store
    cache, fragment cache, telemetry ring) must never leak across
    tests."""
    saved_ring = obs_ts._RING
    sentinel.reset_alerts_total()
    store.invalidate_cache()
    fragment_cache().clear()
    yield
    inject.uninstall()
    sentinel.reset_alerts_total()
    store.invalidate_cache()
    fragment_cache().clear()
    obs_ts._RING = saved_ring


def _df(s, n=600, seed=0):
    return s.create_dataframe(
        {"k": [(seed + i) % 7 for i in range(n)],
         "v": [(seed + 3 * i) % 997 for i in range(n)]},
        num_partitions=2)


# -- telemetry ring units -----------------------------------------------------


def test_ring_rotation_and_drop_oldest():
    r = TelemetryRing(interval_ms=1, max_intervals=2)
    for _ in range(4):
        r.record_span("dispatch", 10_000, 64)
        time.sleep(0.003)  # force the next record into a newer bucket
    r.record_span("dispatch", 10_000, 64)
    done = r.snapshot()
    assert len(done) <= 2  # bounded
    assert r.completed_total >= 3
    assert r.dropped_intervals >= 1  # drop-OLDEST counted
    # the ring keeps the NEWEST intervals: indices strictly increase
    idxs = [iv.idx for iv in done]
    assert idxs == sorted(idxs)


def test_ring_value_samples_bounded_per_interval():
    r = TelemetryRing(interval_ms=60_000, max_intervals=4)
    for i in range(obs_ts.MAX_VALUES_PER_INTERVAL + 88):
        r.record_value("serve.latency_ms", float(i))
    vals = r.window_values("serve.latency_ms")
    assert len(vals) == obs_ts.MAX_VALUES_PER_INTERVAL
    assert vals[0] == 0.0  # first samples win (bounded append)


def test_failing_gauge_never_breaks_export():
    r = TelemetryRing(interval_ms=1000, max_intervals=4)

    def bad():
        raise RuntimeError("torn-down subsystem")

    r.register_gauge("bad", bad)
    r.register_gauge("good", lambda: 7.0)
    g = r.sample_gauges()
    assert g["good"] == 7.0
    assert "bad" not in g
    assert "telemetry.dropped_intervals" in g
    # and the Prometheus text still renders with the bad gauge armed
    assert "rapids_good 7" in r.prometheus_text()


def test_flush_jsonl_is_incremental(tmp_path):
    r = TelemetryRing(interval_ms=1, max_intervals=128)
    path = str(tmp_path / "telemetry.jsonl")
    r.record_span("dispatch", 5_000, 0)
    time.sleep(0.003)
    n1 = r.flush_jsonl(path)  # roll_now closes the stale interval
    assert n1 >= 1
    assert r.flush_jsonl(path) == 0  # nothing new -> nothing written
    r.record_span("h2d", 7_000, 1 << 20)
    time.sleep(0.003)
    n2 = r.flush_jsonl(path)
    assert n2 >= 1
    intervals = obs_ts.read_telemetry_log(path)
    assert len(intervals) == n1 + n2  # appended, never rewritten
    sites = {s for iv in intervals for s in (iv.get("sites") or {})}
    assert {"dispatch", "h2d"} <= sites
    # the newest flushed interval carries the gauge samples
    assert "telemetry.dropped_intervals" in (intervals[-1].get("gauges")
                                             or {})


def test_configure_keeps_ring_when_shape_unchanged():
    obs_ts.configure(True, 77, 9)
    r1 = obs_ts.ring()
    assert r1 is not None and r1.interval_ns == 77 * 1_000_000
    obs_ts.configure(True, 77, 9)
    assert obs_ts.ring() is r1  # repeat execute never resets the ring
    obs_ts.configure(True, 78, 9)
    assert obs_ts.ring() is not r1  # shape change replaces it
    obs_ts.configure(False, 78, 9)
    assert obs_ts.ring() is None
    obs_ts.record_span("dispatch", 1, 0)  # disabled fold is a no-op
    assert obs_ts.completed_total() == 0


def test_prometheus_text_parses():
    r = TelemetryRing(interval_ms=1, max_intervals=8)
    r.record_span("dispatch", 123_000, 4096)
    time.sleep(0.003)
    r.register_gauge("catalog.device_bytes", lambda: 1024.0)
    text = r.prometheus_text()
    assert text.endswith("\n")
    for line in text.strip().splitlines():
        if line.startswith("#"):
            parts = line.split()
            assert parts[:2] == ["#", "TYPE"] and parts[3] in (
                "counter", "gauge"), line
            continue
        name, val = line.rsplit(" ", 1)
        float(val)  # every sample value is numeric
        assert name.split("{")[0].startswith("rapids_")
    assert 'rapids_site_events_total{site="dispatch"} 1' in text
    assert "rapids_catalog_device_bytes 1024" in text


def test_render_intervals_empty_and_window():
    assert obs_ts.render_intervals([]) == "(no telemetry intervals)"
    ivs = [{"type": "interval", "idx": i, "t0_ns": i * 10, "dur_ns": 10,
            "sites": {"dispatch": [1, 5_000_000, 0]}} for i in range(3)]
    out = obs_ts.render_intervals(ivs, last=2)
    assert "2 interval(s)" in out
    assert "window (2 intervals)" in out
    assert "dispatch" in out


# -- critical path: unit ------------------------------------------------------


def test_critpath_exact_partition_with_overlap_and_priority():
    # window [1000, 1100): exchange covers [1005,1040) with a wait on
    # the device nested inside [1010,1030) — device_wait outranks
    # exchange, so the exchange is credited only its host-side remainder.
    evs = [
        {"kind": "span", "site": "exchange", "t0": 1005, "t1": 1040},
        {"kind": "span", "site": "device_wait", "t0": 1010, "t1": 1030},
        {"kind": "span", "site": "io", "t0": 1050, "t1": 1060},
        {"kind": "instant", "site": "fault", "t0": 1055, "t1": 1055},
        {"kind": "span", "site": "h2d", "t0": 1090, "t1": 1500},  # clipped
        {"kind": "span", "site": "spill", "t0": 0, "t1": 50},  # unstamped
    ]
    cp = obs_critpath.compute(evs, 1000, 1100)
    assert cp.total_ns == 100
    assert sum(cp.segments.values()) == cp.total_ns  # exact by construction
    assert cp.segments == {"exchange": 15, "device_wait": 20, "io": 10,
                           "h2d": 10, "wait": 45}
    assert cp.attributed_ns == 55
    # the chain is a merged, ordered partition of the window
    assert cp.chain[0] == ("wait", 1000, 1005)
    assert [c[0] for c in cp.chain] == ["wait", "exchange", "device_wait",
                                        "exchange", "wait", "io", "wait",
                                        "h2d"]
    assert all(a[2] == b[1] for a, b in zip(cp.chain, cp.chain[1:]))
    assert cp.top_site() == "wait"
    assert "critical path: " in cp.summary()


def test_critpath_empty_window_and_unknown_site():
    assert obs_critpath.compute([], 50, 50).segments == {}
    cp = obs_critpath.compute(
        [{"kind": "span", "site": "weird", "t0": 10, "t1": 20},
         {"kind": "span", "site": "enqueue", "t0": 12, "t1": 14}], 10, 20)
    assert cp.segments == {"weird": 8, "enqueue": 2}  # unknown = lowest rank


# -- critical path: end to end ------------------------------------------------


def _assert_exact(p):
    cp = obs_critpath.from_profile(p)
    assert cp is not None
    assert cp.total_ns == p.qt1_ns - p.qt0_ns
    assert sum(cp.segments.values()) == cp.total_ns, cp.segments
    return cp


def test_critpath_exact_on_shuffle_spill_retry_query():
    """The pinned exactness query: a shuffled hash join that spills
    (tiny device budget) and retries (dispatch:oom@2) — every
    nanosecond of the query window is attributed, metric included."""
    from spark_rapids_tpu.runtime.device import DeviceRuntime
    DeviceRuntime.reset()
    try:
        s = tpu_session(**{
            "spark.rapids.sql.tpu.faults.spec": "dispatch:oom@2",
            "spark.rapids.sql.tpu.exchange.collapseLocal": False,
            "spark.sql.autoBroadcastJoinThreshold": -1,
            "spark.rapids.memory.tpu.spillBudgetBytes": 64 * 1024,
            "spark.rapids.sql.tpu.spill.async.enabled": False,
        })
        n = 8192
        left = s.create_dataframe(
            {"k": [i % 500 for i in range(n)],
             "v": [(3 * i) % 997 for i in range(n)]}, num_partitions=3)
        right = s.create_dataframe(
            {"k": list(range(500)), "w": list(range(500))},
            num_partitions=2)
        s.execute(left.join(right, on="k", how="inner").plan)
        m = s.last_metrics
        p = s.query_history()[-1]
        cp = _assert_exact(p)
        assert 0 < cp.attributed_ns <= cp.total_ns
        # the call's wall outside the window: the profile's span and what
        # no span covers — the window's ``wait`` is in ``unspannedNs``
        tail = m["collectWallNs"] - m["unspannedNs"] - cp.attributed_ns
        assert 0 <= tail <= m["collectWallNs"] - m["queryWallNs"]
        assert m["unspannedNs"] >= cp.segments.get("wait", 0)
        # the decomposition saw the shuffle, the spill and the retry
        sites = {ev.site for ev in p.events}
        assert "retry" in sites or "fault" in sites
        assert "exchange" in sites
        assert "spill" in sites
        assert cp.top_site() != ""
    finally:
        DeviceRuntime.reset()


def test_critpath_exact_under_serve_concurrency():
    """3-thread serve: each query's window still decomposes exactly —
    spans from helper threads (decode pool, spill writer) land in the
    right query's profile and never break the partition."""
    s = tpu_session()
    before = len(s.query_history())
    dfs = [_df(s, seed=7 * i).group_by("k").sum("v") for i in range(6)]
    with ServeScheduler(s, max_concurrency=3) as sched:
        futs = [sched.submit(df) for df in dfs]
        for f in futs:
            f.result(timeout=120)
    hist = s.query_history()[before:]
    assert len(hist) == 6
    for p in hist:
        cp = _assert_exact(p)
        assert 0 <= cp.segments.get("wait", 0) <= cp.total_ns


# -- regression sentinel ------------------------------------------------------


def test_sentinel_check_band_math():
    agg = {"n": 5, "keys": {"wall_ns": {"median": 100e6, "mad": 1e6}}}
    # band = median + threshold * max(MAD, 25% median, 2ms floor)
    #      = 100e6 + 4 * 25e6 = 200e6
    assert sentinel.check({"wall_ns": 200e6}, agg, 4.0, 3) == []
    alerts = sentinel.check({"wall_ns": 200e6 + 1}, agg, 4.0, 3)
    assert [a["key"] for a in alerts] == ["wall_ns"]
    assert alerts[0]["band"] == 200e6
    assert alerts[0]["runs"] == 5
    assert sentinel.alerts_total() == 1
    # thin baseline: never alert below min_runs
    assert sentinel.check({"wall_ns": 1e12}, dict(agg, n=2), 4.0, 3) == []
    # downward excursions are not regressions
    assert sentinel.check({"wall_ns": 1.0}, agg, 4.0, 3) == []
    # unguarded keys are ignored
    agg2 = {"n": 5, "keys": {"out_rows": {"median": 1.0, "mad": 0.0}}}
    assert sentinel.check({"out_rows": 1e9}, agg2, 4.0, 3) == []


def _hist_session(hist_dir, **confs):
    # fragments off so warm repeats re-execute (0-dispatch fragment
    # serves would dodge the injected fault); seeding off so every run
    # keeps the identical unseeded plan fingerprint; faults.spec preset
    # empty so toggling it restores this exact conf state and a clean
    # repeat reuses the cached plan instead of recompiling
    return tpu_session(**{
        "spark.rapids.sql.tpu.history.dir": str(hist_dir),
        "spark.rapids.sql.tpu.history.fragments.enabled": False,
        "spark.rapids.sql.tpu.history.seed.enabled": False,
        "spark.rapids.sql.tpu.faults.spec": "",
        **confs})


def test_sentinel_fires_on_injected_slowdown(tmp_path):
    """4 clean runs build the baseline (the 4th, compared against the
    first 3, stays silent); a dispatch:slow run then alerts, emits the
    'regression' obs instant, and rapidshist --regressions finds the
    same alert offline with exit code 1."""
    hist = tmp_path / "h"
    s = _hist_session(hist)
    df = _df(s).filter(F.col("v") > 10)
    for _ in range(4):
        s.execute(df.plan)
        assert s.last_metrics["regressionAlerts"] == 0, s.last_metrics

    # same session, same plan fingerprint; the faults. conf namespace is
    # excluded from the conf signature, so the slow run is compared
    # against the clean baseline it just built
    s.conf.set("spark.rapids.sql.tpu.faults.spec",
               "dispatch:slow=500ms@1+")
    s.execute(df.plan)
    m = s.last_metrics
    assert m["regressionAlerts"] >= 1, m
    assert m["faultsInjected"] >= 1, m
    assert sentinel.alerts_total() >= 1
    p = s.query_history()[-1]
    regs = [ev for ev in p.events
            if ev.site == "history" and ev.name == "regression"]
    assert len(regs) == m["regressionAlerts"]
    assert any((ev.payload or {}).get("key") == "wall_ns" for ev in regs)

    # offline: the store's newest run (the slow one) vs the runs before
    # it — same alert, exit code 1
    tool = os.path.join(REPO_ROOT, "tools", "rapidshist.py")
    proc = subprocess.run(
        [sys.executable, tool, str(hist), "--regressions"],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "REGRESSION" in proc.stdout
    assert "wall_ns" in proc.stdout

    # a clean repeat against the now-5-run baseline stays silent (the
    # slow outlier cannot drag the median out of the clean band), and
    # restoring the preset conf state reuses the cached plan
    s.conf.set("spark.rapids.sql.tpu.faults.spec", "")
    s.execute(df.plan)
    assert s.last_metrics["regressionAlerts"] == 0, s.last_metrics
    assert s.last_metrics["compileCount"] == 0, s.last_metrics


def test_sentinel_silent_on_clean_runs_and_disable(tmp_path):
    hist = tmp_path / "h"
    s = _hist_session(hist)
    df = _df(s).filter(F.col("v") > 10)
    for _ in range(5):
        s.execute(df.plan)
        assert s.last_metrics["regressionAlerts"] == 0, s.last_metrics
    tool = os.path.join(REPO_ROOT, "tools", "rapidshist.py")
    proc = subprocess.run(
        [sys.executable, tool, str(hist), "--regressions"],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "no regressions" in proc.stdout
    # sentinel.enabled=false skips the comparison entirely, even with a
    # real slowdown injected against a mature baseline
    s.conf.set("spark.rapids.sql.tpu.sentinel.enabled", False)
    s.conf.set("spark.rapids.sql.tpu.faults.spec",
               "dispatch:slow=500ms@1+")
    s.execute(df.plan)
    assert s.last_metrics["faultsInjected"] >= 1
    assert s.last_metrics["regressionAlerts"] == 0


# -- ring drops: per-site accounting + truncation banner ----------------------


def test_truncated_profile_names_dropped_sites():
    s = tpu_session(**{"spark.rapids.sql.tpu.obs.ring.maxEvents": 4})
    _df(s).group_by("k").sum("v").collect()
    p = s.query_history()[-1]
    assert p.dropped > 0
    assert sum(p.dropped_by_site.values()) == p.dropped
    banner = p.summary()
    assert "TRUNCATED" in banner
    assert "obs.ring.maxEvents" in banner
    top_site = max(p.dropped_by_site.items(), key=lambda kv: kv[1])[0]
    assert top_site in banner
    # an untruncated profile shows no banner
    s2 = tpu_session()
    _df(s2).group_by("k").sum("v").collect()
    assert "TRUNCATED" not in s2.query_history()[-1].summary()


# -- serve sliding-window percentiles -----------------------------------------


def test_serve_stats_window_percentiles():
    s = tpu_session()
    dfs = [_df(s, seed=3 * i).group_by("k").sum("v") for i in range(5)]
    with ServeScheduler(s, max_concurrency=2) as sched:
        futs = [sched.submit(df, tenant="t") for df in dfs]
        for f in futs:
            f.result(timeout=120)
        st = sched.stats()
    assert st["completed"] == 5
    assert st["window_seconds"] > 0
    assert 0 < st["window_p50_ms"] <= st["window_p99_ms"]
    tn = st["tenants"]["t"]
    assert 0 < tn["window_p50_ms"] <= tn["window_p99_ms"]
    # all-time percentile fields are still reported alongside
    assert tn["p50_ms"] > 0


# -- event log: session stamps + rapidstop ------------------------------------


def test_event_log_roundtrips_session_and_window(tmp_path):
    log_dir = str(tmp_path / "obslog")
    s1 = tpu_session(**{"spark.rapids.sql.tpu.obs.eventLogDir": log_dir})
    _df(s1).group_by("k").sum("v").collect()
    s2 = tpu_session(**{"spark.rapids.sql.tpu.obs.eventLogDir": log_dir})
    _df(s2).filter(F.col("v") > 10).collect()
    log = os.path.join(log_dir, [f for f in os.listdir(log_dir)
                                 if f.startswith("events-")][0])
    queries = obs_export.read_event_log(log)
    assert len(queries) == 2
    sessions = {q["session"] for q in queries}
    assert len(sessions) == 2  # distinct session ids round-trip
    for q in queries:
        assert 0 < q["t0_ns"] < q["t1_ns"]
        assert isinstance(q["dropped_by_site"], dict)

    # rapidsprof groups by session and reconstructs the exact critpath
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "rapidsprof.py"),
         log, "--critpath"],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.count("critical path:") == 2
    assert "== session" in proc.stdout
    assert "| sess |" in proc.stdout


def test_rapidstop_renders_flushed_telemetry_without_jax(tmp_path):
    log_dir = str(tmp_path / "obslog")
    s = tpu_session(**{
        "spark.rapids.sql.tpu.obs.eventLogDir": log_dir,
        "spark.rapids.sql.tpu.obs.telemetry.intervalMs": 25,
    })
    df = _df(s, n=4096).group_by("k").sum("v")
    s.execute(df.plan)
    time.sleep(0.06)  # let the open interval's window pass
    s.execute(df.plan)  # second execute flushes the completed intervals
    assert s.last_metrics["telemetryIntervals"] >= 1
    tpath = os.path.join(log_dir, f"telemetry-{os.getpid()}.jsonl")
    assert os.path.exists(tpath)
    intervals = obs_ts.read_telemetry_log(tpath)
    assert intervals
    assert any("enqueue" in (iv.get("sites") or {}) for iv in intervals)

    # the CLI renders the table and the Prometheus view in a fresh
    # process that must never import jax (runtime-free discipline)
    tool = os.path.join(REPO_ROOT, "tools", "rapidstop.py")
    driver = (
        "import runpy, sys\n"
        "tool, path = sys.argv[1], sys.argv[2]\n"
        "sys.argv = [tool, path, '--once']\n"
        "try:\n"
        "    runpy.run_path(tool, run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    assert not e.code, e.code\n"
        "assert 'jax' not in sys.modules, 'rapidstop imported jax'\n")
    proc = subprocess.run([sys.executable, "-c", driver, tool, tpath],
                          capture_output=True, text=True, cwd=REPO_ROOT,
                          timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "telemetry:" in proc.stdout
    assert "enqueue" in proc.stdout
    prom = subprocess.run([sys.executable, tool, tpath, "--prom"],
                          capture_output=True, text=True, cwd=REPO_ROOT,
                          timeout=120)
    assert prom.returncode == 0, prom.stderr
    assert "rapids_telemetry_intervals_total" in prom.stdout
    assert 'rapids_site_events_total{site="enqueue"}' in prom.stdout
    missing = subprocess.run(
        [sys.executable, tool, str(tmp_path / "nope.jsonl"), "--once"],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
    assert missing.returncode == 2
    assert "(no telemetry intervals)" in missing.stdout


def test_telemetry_disabled_records_nothing():
    s = tpu_session(**{
        "spark.rapids.sql.tpu.obs.telemetry.enabled": False})
    _df(s).group_by("k").sum("v").collect()
    assert s.last_metrics["telemetryIntervals"] == 0
    assert obs_ts.ring() is None
