"""Query-intelligence tests (history/): persistent statistics store,
history-seeded planning, and the cross-query fragment cache — cold/warm
bit-parity, every invalidation edge (input mtime, conf state, eviction,
device-lost generation), clean semaphore/catalog accounting after warm
serves, the off-switch parity contract, and the rapidshist CLI."""

import os
import subprocess
import sys

import pytest

from compare import tpu_session
from spark_rapids_tpu.history import input_identity, runtime_stats, store
from spark_rapids_tpu.history.fragcache import fragment_cache

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_history_state():
    fragment_cache().clear()
    store.reset_stats()
    store.invalidate_cache()
    yield
    fragment_cache().clear()
    fragment_cache().configure(64, 256 << 20)
    store.reset_stats()
    store.invalidate_cache()


def _hist_session(hist_dir, **confs):
    return tpu_session(**{
        "spark.rapids.sql.tpu.history.dir": str(hist_dir), **confs})


def _df(s, n=2048, mod=7, seed=0):
    return s.create_dataframe(
        {"k": [(seed + i) % mod for i in range(n)],
         "v": [(seed + 3 * i) % 997 for i in range(n)]},
        num_partitions=2)


def _rows(batch):
    cols = batch.to_pydict()
    return sorted(zip(*[cols[name] for name in batch.schema.names]))


# -- fragment cache: cold/warm ------------------------------------------------


def test_warm_repeat_serves_fragment_bit_identical(tmp_path):
    """The second run of the same query serves the whole subtree from
    the fragment cache: zero compiles, zero dispatches, hits > 0, and
    bit-identical rows."""
    s = _hist_session(tmp_path / "h")
    q = _df(s).group_by("k").sum("v")
    cold, m1 = s.execute_with_metrics(q.plan)
    assert m1["fragmentCacheHits"] == 0, m1
    assert m1["statsStoreQueries"] == 1, m1
    warm, m2 = s.execute_with_metrics(q.plan)
    assert m2["fragmentCacheHits"] == 1, m2
    assert m2["fragmentCacheBytes"] > 0, m2
    assert m2["compileCount"] == 0, m2
    assert m2["dispatchCount"] == 0, m2
    assert _rows(warm) == _rows(cold)


def test_store_record_written_at_query_end(tmp_path):
    hist = tmp_path / "h"
    s = _hist_session(hist)
    s.execute(_df(s).group_by("k").sum("v").plan)
    records = store.load(str(hist))
    assert len(records) == 1
    (rec,) = records.values()
    assert rec["v"] == store.STORE_VERSION
    assert rec["conf_sig"] == store.conf_signature(s.conf._settings.items())
    assert rec["out_rows"] == 7
    assert rec["wall_ns"] > 0


def test_disabled_is_history_free_behavior(tmp_path):
    """history.enabled=false (even with a dir set) must be byte-for-byte
    today's engine: no store file, no metrics, no cache entries — and
    the same rows as a session with no history conf at all."""
    hist = tmp_path / "h"
    base = tpu_session()
    want = _rows(base.execute(_df(base).group_by("k").sum("v").plan))

    s = _hist_session(hist, **{
        "spark.rapids.sql.tpu.history.enabled": False})
    q = _df(s).group_by("k").sum("v")
    for _ in range(2):
        got = _rows(s.execute(q.plan))
        assert got == want
        m = s.last_metrics
        assert m["fragmentCacheHits"] == 0, m
        assert m["statsStoreQueries"] == 0, m
        assert m["historySeededDecisions"] == 0, m
    assert not os.path.exists(store.store_path(str(hist)))
    assert len(fragment_cache()) == 0


# -- invalidation edges -------------------------------------------------------


@pytest.fixture
def pq_dir(tmp_path):
    s = tpu_session()
    df = s.create_dataframe(
        {"k": [i % 5 for i in range(512)],
         "v": [(3 * i) % 97 for i in range(512)]}, num_partitions=2)
    out = str(tmp_path / "pq")
    df.write_parquet(out)
    return out


def _pq_query(s, pq_dir):
    return s.read.parquet(pq_dir).group_by("k").sum("v")


def test_input_mtime_change_invalidates_fragment(tmp_path, pq_dir):
    """Touching an input file changes its (mtime_ns, size) identity:
    the repeat run must MISS (recompute from the files), not serve the
    stale fragment."""
    s = _hist_session(tmp_path / "h")
    q = _pq_query(s, pq_dir)
    want = _rows(s.execute(q.plan))
    _, m2 = s.execute_with_metrics(q.plan)
    assert m2["fragmentCacheHits"] == 1, m2

    part = next(f for f in sorted(os.listdir(pq_dir))
                if f.endswith(".parquet"))
    path = os.path.join(pq_dir, part)
    st = os.stat(path)
    os.utime(path, ns=(st.st_atime_ns, st.st_mtime_ns + 10 ** 9))

    # the re-read plan sees the new identity -> different key -> miss
    q3 = _pq_query(s, pq_dir)
    got, m3 = s.execute_with_metrics(q3.plan)
    assert m3["fragmentCacheHits"] == 0, m3
    assert _rows(got) == want


def test_conf_state_change_invalidates_fragment(tmp_path, pq_dir):
    """A plan-relevant conf difference signs a different fragment key:
    a session under another configuration never serves the first
    session's fragment."""
    hist = tmp_path / "h"
    s1 = _hist_session(hist)
    q1 = _pq_query(s1, pq_dir)
    want = _rows(s1.execute(q1.plan))
    _, m = s1.execute_with_metrics(q1.plan)
    assert m["fragmentCacheHits"] == 1, m

    s2 = _hist_session(hist, **{"spark.sql.autoBroadcastJoinThreshold": -1})
    q2 = _pq_query(s2, pq_dir)
    got, m2 = s2.execute_with_metrics(q2.plan)
    assert m2["fragmentCacheHits"] == 0, m2
    assert _rows(got) == want


def test_conf_signature_excludes_inert_namespaces():
    base = [("spark.rapids.sql.enabled", True),
            ("spark.sql.shuffle.partitions", 4)]
    sig = store.conf_signature(base)
    # obs./history. knobs never change plans -> same signature
    assert store.conf_signature(base + [
        ("spark.rapids.sql.tpu.history.dir", "/x"),
        ("spark.rapids.sql.tpu.obs.eventLogDir", "/y"),
        ("spark.rapids.sql.tpu.obs.ring.maxEvents", 4096)]) == sig
    # anything else does
    assert store.conf_signature(base + [
        ("spark.sql.autoBroadcastJoinThreshold", -1)]) != sig


def test_eviction_under_tiny_budget_recomputes(tmp_path):
    """With a fragment budget too small to hold anything, the insert is
    immediately evicted: the repeat run recomputes from lineage with
    correct rows (never a crash, never stale data)."""
    s = _hist_session(tmp_path / "h", **{
        "spark.rapids.sql.tpu.history.fragments.maxBytes": 1})
    q = _df(s).group_by("k").sum("v")
    want = _rows(s.execute(q.plan))
    got, m2 = s.execute_with_metrics(q.plan)
    assert m2["fragmentCacheHits"] == 0, m2
    assert _rows(got) == want
    st = fragment_cache().stats()
    assert st["fragment_cache_evictions"] > 0, st
    assert st["fragment_cache_entries"] == 0, st


def test_device_lost_generation_invalidates(tmp_path):
    """A device-lost recovery bumps the runtime generation; fragments
    built under the old device must not serve — the repeat recomputes on
    the recovered runtime."""
    from spark_rapids_tpu.runtime.device import DeviceRuntime

    DeviceRuntime.reset()
    try:
        s = _hist_session(tmp_path / "h")
        q = _df(s).group_by("k").sum("v")
        want = _rows(s.execute(q.plan))
        assert len(fragment_cache()) == 1

        DeviceRuntime.recover(s.conf)
        got, m2 = s.execute_with_metrics(q.plan)
        assert m2["fragmentCacheHits"] == 0, m2
        assert _rows(got) == want
        # and the stale entry was dropped, replaced by a fresh insert
        assert len(fragment_cache()) == 1
    finally:
        DeviceRuntime.reset()
        fragment_cache().clear()


def test_clean_accounting_after_warm_serves(tmp_path):
    """Warm serves take no device admission and leak nothing: after a
    cold+warm+warm sequence the semaphore is free and the catalog
    accounting verifies clean (with the cached fragments still live)."""
    s = _hist_session(tmp_path / "h")
    q = _df(s).group_by("k").sum("v")
    s.execute(q.plan)
    s.execute(q.plan)
    s.execute(q.plan)
    assert s.last_metrics["fragmentCacheHits"] == 1
    assert s.runtime.semaphore.held_depth() == 0
    assert s.runtime.catalog.verify_accounting() == []


# -- history-seeded planning --------------------------------------------------


def test_seeding_applies_recorded_layout_with_parity(tmp_path):
    """With a warm store, a fresh physical plan of the same fingerprint
    applies the recorded exchange layout at PLAN time (decisions > 0)
    and still returns bit-identical rows."""
    from spark_rapids_tpu.serve import shared_plan_cache

    confs = {
        # collapsed local exchanges never split -> nothing to record/seed
        "spark.rapids.sql.tpu.exchange.collapseLocal": False,
        "spark.sql.shuffle.partitions": 16,
        # isolate seeding from the fragment path
        "spark.rapids.sql.tpu.history.fragments.enabled": False,
    }
    s = _hist_session(tmp_path / "h", **confs)
    q = _df(s, n=4096, mod=13).group_by("k").sum("v")
    want = _rows(s.execute(q.plan))
    assert s.last_metrics["historySeededDecisions"] == 0

    # a fresh phys of the same fingerprint seeds from the store
    shared_plan_cache().clear()
    got, m2 = s.execute_with_metrics(q.plan)
    assert m2["historySeededDecisions"] >= 1, m2
    assert m2["statsStoreQueries"] == 1, m2
    assert _rows(got) == want


def test_seed_disabled_consults_nothing(tmp_path):
    from spark_rapids_tpu.serve import shared_plan_cache

    s = _hist_session(tmp_path / "h", **{
        "spark.rapids.sql.tpu.history.seed.enabled": False,
        "spark.rapids.sql.tpu.exchange.collapseLocal": False,
        "spark.rapids.sql.tpu.history.fragments.enabled": False,
    })
    q = _df(s).group_by("k").sum("v")
    want = _rows(s.execute(q.plan))
    shared_plan_cache().clear()
    got, m2 = s.execute_with_metrics(q.plan)
    assert m2["statsStoreQueries"] == 0, m2
    assert m2["historySeededDecisions"] == 0, m2
    assert _rows(got) == want


# -- store unit behavior ------------------------------------------------------


def test_store_lookup_staleness_and_conf_mismatch(tmp_path):
    d = str(tmp_path / "h")
    store.append(d, {"fp": "aaaa", "conf_sig": "s1", "ts": 1000.0})
    # conf signature must match
    assert store.lookup(d, "aaaa", "s1") is not None
    assert store.lookup(d, "aaaa", "s2") is None
    # age horizon measured from `now`
    assert store.lookup(d, "aaaa", "s1", max_age_sec=50,
                        now=1030.0) is not None
    assert store.lookup(d, "aaaa", "s1", max_age_sec=50, now=1100.0) is None
    # absent fingerprint / absent dir are plain misses
    assert store.lookup(d, "bbbb", "s1") is None
    assert store.lookup(str(tmp_path / "nope"), "aaaa", "s1") is None


def test_store_newest_record_wins_and_tolerates_torn_tail(tmp_path):
    d = str(tmp_path / "h")
    store.append(d, {"fp": "aaaa", "conf_sig": "s1", "wall_ns": 1})
    store.append(d, {"fp": "aaaa", "conf_sig": "s1", "wall_ns": 2})
    with open(store.store_path(d), "a", encoding="utf-8") as f:
        f.write('{"fp": "cccc", "tor')  # torn tail write
    store.invalidate_cache(d)
    records = store.load(d)
    assert set(records) == {"aaaa"}
    assert records["aaaa"]["wall_ns"] == 2


def test_store_prune_bounds_and_keeps_newest(tmp_path):
    d = str(tmp_path / "h")
    for i in range(6):
        store.append(d, {"fp": f"fp{i % 3}", "conf_sig": "s", "n": i})
    before, after = store.prune(d, 2)
    assert before == 6 and after <= 2
    records = store.load(d)
    assert records["fp2"]["n"] == 5  # newest per fingerprint survived


def test_input_identity_kinds(tmp_path, pq_dir):
    s = tpu_session()
    mem = _df(s).plan
    sig = input_identity(mem)
    assert sig is not None and sig.startswith("mem:")
    file_plan = s.read.parquet(pq_dir).plan
    fsig = input_identity(file_plan)
    assert fsig is not None and "file:" in fsig and str(pq_dir) in fsig
    # a vanished input means "do not cache", not a crash
    part = next(f for f in os.listdir(pq_dir) if f.endswith(".parquet"))
    os.rename(os.path.join(pq_dir, part),
              os.path.join(pq_dir, part + ".gone"))
    try:
        assert input_identity(file_plan) is None
    finally:
        os.rename(os.path.join(pq_dir, part + ".gone"),
                  os.path.join(pq_dir, part))


# -- rollups and tooling ------------------------------------------------------


def test_serve_stats_roll_up_history_counters(tmp_path):
    from spark_rapids_tpu.serve import ServeScheduler

    s = _hist_session(tmp_path / "h")
    with ServeScheduler(s, max_concurrency=2) as sched:
        df = _df(s).group_by("k").sum("v")
        sched.submit(df).result(timeout=120)
        sched.submit(df).result(timeout=120)
        st = sched.stats()
    for key in ("history_store_queries", "history_store_appends",
                "fragment_cache_entries", "fragment_cache_hits",
                "fragment_cache_misses"):
        assert key in st, sorted(st)
    assert st["history_store_appends"] >= 2, st
    assert st["fragment_cache_hits"] >= 1, st
    assert runtime_stats()["history_store_appends"] >= 2


def test_rapidshist_cli_inspects_and_prunes(tmp_path):
    hist = str(tmp_path / "h")
    s = _hist_session(hist)
    q = _df(s).group_by("k").sum("v")
    s.execute(q.plan)
    s.execute(q.plan)

    tool = os.path.join(REPO_ROOT, "tools", "rapidshist.py")
    out = subprocess.run([sys.executable, tool, hist],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "fingerprint" in out.stdout
    assert "exchange" in out.stdout or "wall" in out.stdout

    out = subprocess.run([sys.executable, tool, hist, "--prune", "1"],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    store.invalidate_cache(hist)
    assert len(store.load(hist)) == 1

    # empty store exits 2, not 0 (scriptable "nothing here" signal)
    out = subprocess.run([sys.executable, tool, str(tmp_path / "none")],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 2, (out.stdout, out.stderr)
