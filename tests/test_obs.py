"""Observability subsystem tests: event-bus epochs, ring bounds, profile
parity against last_metrics, JSONL export, the rapidsprof CLI, and
the zero-overhead disabled path (ISSUE PR 10 acceptance list)."""

import os
import subprocess
import sys

import numpy as np

from compare import tpu_session
from spark_rapids_tpu import functions as F
from spark_rapids_tpu.obs import export as obs_export
from spark_rapids_tpu.obs.events import EventBus
from spark_rapids_tpu.runtime.device import DeviceRuntime

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _simple_query(s, n=300):
    df = s.create_dataframe({"k": [i % 3 for i in range(n)],
                             "v": [float(i) for i in range(n)]})
    return df.group_by("k").agg(F.sum("v").alias("sv")).order_by("k")


def test_event_ordering_and_epoch_reset():
    """Each query drains into its own profile: per-query event counts match
    the obsEventCount metric, query ids are distinct/increasing, and the
    first profile is not mutated by the second query."""
    s = tpu_session()
    _simple_query(s).collect()
    count1 = s.last_metrics["obsEventCount"]
    p1 = s.query_history()[-1]
    assert count1 > 0
    assert p1.event_count == count1
    first_events = list(p1.events)

    df2 = s.create_dataframe({"a": list(range(100))})
    df2.filter(F.col("a") > 10).order_by("a").collect()
    count2 = s.last_metrics["obsEventCount"]
    hist = s.query_history()
    assert len(hist) == 2
    p2 = hist[-1]
    assert p2.event_count == count2
    assert p2.query_id > p1.query_id
    # epoch reset: the second query's events never leak into the first
    assert hist[0].events == first_events
    # spans carry a coherent clock: t1 >= t0 inside each event, and the
    # profile's window bounds cover every stamped span
    for p in hist:
        for ev in p.events:
            assert ev.t1 >= ev.t0
            if ev.t0:
                assert p.t_min <= ev.t0 <= p.t_max


def test_ring_overflow_increments_dropped():
    # direct bus semantics: drop-new, bounded length, counted drops
    bus = EventBus(max_events=4)
    for i in range(6):
        bus.append(object())
    events, dropped = bus.drain()
    assert len(events) == 4
    assert dropped == 2
    # drain resets
    events2, dropped2 = bus.drain()
    assert events2 == [] and dropped2 == 0

    # and through a real query with a tiny ring
    s = tpu_session(**{"spark.rapids.sql.tpu.obs.ring.maxEvents": 2})
    _simple_query(s).collect()
    assert s.last_metrics["obsEventCount"] == 2
    assert s.last_metrics["obsEventsDropped"] > 0
    assert s.query_history()[-1].dropped == \
        s.last_metrics["obsEventsDropped"]


def test_rollup_matches_last_metrics_on_shuffle_spill_query():
    """On a query that really shuffles and really spills, the profile's
    rollups reproduce the dispatch/device/shuffle/spill totals that the
    independent metric pipeline reports for the same window."""
    DeviceRuntime.reset()
    try:
        s = tpu_session(**{
            "spark.rapids.sql.tpu.exchange.collapseLocal": False,
            "spark.sql.autoBroadcastJoinThreshold": -1,
            # ~64KB device budget: far below the join working set
            "spark.rapids.memory.tpu.spillBudgetBytes": 64 * 1024,
            # synchronous spill so every spill span lands inside the
            # emitting query's epoch
            "spark.rapids.sql.tpu.spill.async.enabled": False,
        })
        n = 20_000
        rng = np.random.RandomState(7)
        left = s.create_dataframe(
            {"k": rng.randint(0, 500, n).tolist(),
             "v": rng.randint(0, 100, n).tolist()}, num_partitions=3)
        right = s.create_dataframe(
            {"k": list(range(500)), "w": list(range(500))},
            num_partitions=2)
        rows = left.join(right, on="k", how="inner").collect()
        assert len(rows) == n

        m = s.last_metrics
        p = s.query_history()[-1]
        # enqueue: ONE span per compiled-program dispatch (no second
        # `device`/`dispatch` span over the same call)
        assert p.site("enqueue")["count"] == m["dispatchCount"]
        assert p.site("dispatch")["count"] == p.site("device")["count"] == 0
        # stage: one span per stage program, and the site's wall is the
        # spans' own widths to the nanosecond
        stages = [e for e in p.events
                  if e.kind == "span" and e.site == "stage"]
        assert len(stages) == p.site("stage")["count"] == \
            m["pipeline"]["programs"] > 0
        assert p.site("stage")["wall_ns"] == \
            sum(e.t1 - e.t0 for e in stages) > 0
        # every enqueue nanosecond is tied to an operator or a program
        assert p.attributed_enqueue_ns == p.site("enqueue")["wall_ns"]
        # shuffle: exchange split/mesh spans carry the same bytes the
        # per-op shuffleBytes metric accumulated
        assert m["shuffleBytes"] > 0
        assert sum(r["shuffle_bytes"] for r in p.op_rollups.values()) == \
            m["shuffleBytes"]
        # spill: synchronous to_host/to_disk spans carry the same bytes
        # as the catalog's per-query byte deltas
        assert m["spillToHostBytes"] > 0
        assert p.site("spill")["bytes"] == \
            m["spillToHostBytes"] + m["spillToDiskBytes"]
        # named-operator attribution: rollup names are real exec names
        top = p.top_operators(3)
        assert top and any("Exec" in (r["name"] or "") for r in top)
    finally:
        DeviceRuntime.reset()


def test_jsonl_roundtrip_through_rapidsprof(tmp_path):
    log_dir = str(tmp_path / "obslog")
    s = tpu_session(**{"spark.rapids.sql.tpu.obs.eventLogDir": log_dir})
    _simple_query(s).collect()
    # the dir holds the per-pid event log plus the telemetry flush
    # (telemetry-<pid>.jsonl, rapidstop's input — covered in test_obs_v2)
    logs = [os.path.join(log_dir, f) for f in os.listdir(log_dir)
            if f.startswith("events-")]
    assert len(logs) == 1

    # the log parses back into the same profile shape
    queries = obs_export.read_event_log(logs[0])
    assert len(queries) == 1
    assert queries[0]["event_count"] == s.last_metrics["obsEventCount"]
    assert len(queries[0]["events"]) == queries[0]["event_count"]

    # and the runtime-free CLI renders a report
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "rapidsprof.py"),
         logs[0]],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "top operators by enqueue wall" in proc.stdout
    assert "Exec" in proc.stdout  # names at least one real operator


def test_obs_disabled_zero_events_bit_identical():
    on = tpu_session()
    off = tpu_session(**{"spark.rapids.sql.tpu.obs.enabled": False})
    rows_on = _simple_query(on).collect()
    rows_off = _simple_query(off).collect()
    assert rows_on == rows_off
    assert off.last_metrics["obsEventCount"] == 0
    assert off.last_metrics["obsEventsDropped"] == 0
    assert off.query_history() == []
    # the enabled session still profiled
    assert on.last_metrics["obsEventCount"] > 0
    assert len(on.query_history()) == 1


def test_held_depth_zero_after_profiled_query():
    """Profiling must not perturb semaphore accounting: after a profiled
    query completes, nothing still holds the device semaphore."""
    s = tpu_session()
    _simple_query(s).collect()
    assert s.query_history()
    if s.runtime is not None and s.runtime.semaphore is not None:
        assert s.runtime.semaphore.held_depth() == 0


def test_explain_last_metrics_annotates_operators():
    s = tpu_session()
    _simple_query(s).collect()
    text = s.explain_last(metrics=True)
    assert "dispatches=" in text
    assert "enqueue=" in text
