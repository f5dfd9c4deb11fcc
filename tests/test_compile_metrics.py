"""Compile/dispatch economics tests: the per-query compile-miss /
dispatch-count accounting (utils/compile_registry), the shared
shape-bucket policy, the stage breaks' one order (sizes fetched once,
re-bucketing compiled into the consumer), and session.prewarm()."""

import pytest

from spark_rapids_tpu import functions as F

from compare import cpu_session, tpu_session


def _headline_query(s, rows=1000):
    """Mini clone of the bench headline shape: filter -> project ->
    two-key group-by aggregate -> order_by tail."""
    df = s.create_dataframe({
        "k": [i % 7 for i in range(rows)],
        "p": [i % 3 for i in range(rows)],
        "q": [i % 50 for i in range(rows)],
        "v": list(range(rows)),
    })
    return (df
            .filter(df["q"] < 40)
            .with_column("w", df["v"] * df["q"])
            .group_by("k", "p")
            .agg(F.sum("w").alias("sw"), F.count("w").alias("c"),
                 F.min("v").alias("mn"), F.max("v").alias("mx"))
            .order_by("k", "p"))


def test_metrics_present_for_jitted_query():
    s = tpu_session()
    q = _headline_query(s)
    rows = q.collect()
    assert rows
    m = s.last_metrics
    for key in ("compileCount", "compileWallNs", "dispatchCount",
                "compiledShapes"):
        assert key in m, f"last_metrics missing {key}: {sorted(m)}"
    assert m["compileCount"] > 0  # first run of fresh execs compiles
    assert m["compileWallNs"] > 0
    assert m["dispatchCount"] > 0
    assert m["compiledShapes"] >= m["compileCount"]


def test_repeated_query_reports_zero_new_compiles():
    s = tpu_session()
    q = _headline_query(s)
    first = q.collect()
    second = q.collect()
    assert first == second
    m = s.last_metrics
    assert m["compileCount"] == 0, \
        f"repeat of an identical query recompiled: {m['compileCount']}"
    assert m["compileWallNs"] == 0
    assert m["dispatchCount"] > 0  # still dispatches, just from cache


#: every stage break is worth shrinking at test scale, and so is the
#: collected root's own output
_SHRINK_ALL = {"spark.rapids.sql.tpu.pipeline.shrinkBytes": 0}


def test_tail_fusion_dispatches_no_shrink_for_a_break():
    """The headline query's one stage break is re-bucketed inside the
    tail program: the only ``pipeline:shrink`` program dispatched alone is
    the collected root's own, and the rows are the oracle's."""
    s = tpu_session(**_SHRINK_ALL)
    rows = _headline_query(s).collect()
    m = s.last_metrics["pipeline"]
    assert m["fusedShrinks"] == 1, m
    assert m["shrinks"] == 1, m      # the root's own output, no break's
    assert m["programs"] == 2, m
    enqueued = [e.name for e in s.query_history()[-1].events
                if e.kind == "span" and e.site == "enqueue"]
    assert enqueued.count("pipeline:shrink") == 1, enqueued
    assert enqueued.count("stage:TpuSortExec") == 1, enqueued
    assert rows == _headline_query(cpu_session()).collect()


def _keyless(s):
    df = s.create_dataframe({"q": [i % 50 for i in range(1000)],
                             "v": list(range(1000))}, num_partitions=3)
    return df.filter(df["q"] < 40).agg(
        F.sum(df["v"] * df["q"]).alias("sw"), F.count("v").alias("c"))


def _join_under_aggregate(s):
    fact = s.create_dataframe({"k": [i % 7 for i in range(500)],
                               "v": list(range(500))}, num_partitions=2)
    dim = s.create_dataframe({"k": list(range(7)),
                              "w": [i * 10 for i in range(7)]})
    return (fact.join(dim, on="k").group_by("w")
            .agg(F.sum("v").alias("sv")).order_by("w"))


def _union_of_aggregates(s):
    def side(mod, rows):
        df = s.create_dataframe({"k": [i % mod for i in range(rows)],
                                 "v": list(range(rows))}, num_partitions=2)
        return df.group_by("k").agg(F.sum("v").alias("sv"))
    return side(7, 500).union(side(5, 300)).order_by("k", "sv")


@pytest.mark.parametrize("build,breaks", [
    (_keyless, 0), (_headline_query, 1), (_join_under_aggregate, 1),
    (_union_of_aggregates, 2),
], ids=["keyless_aggregate", "keyed_aggregate_sort",
        "join_under_aggregate", "two_aggregates_under_a_union"])
def test_stage_breaks_take_one_sizes_round_trip(build, breaks, monkeypatch):
    """The one order of a stage's breaks: every break's program is
    dispatched, then ONE ``host_sizes`` round trip fetches the live sizes
    of all of them together (the other one a query is the collected
    root's own output), and each break's re-bucketing is compiled into
    the consumer — none dispatched alone.  A keyless aggregate has no
    break: one program, and only the root's own sizes are fetched.  Rows
    equal the oracle's."""
    from spark_rapids_tpu.plan import pipeline

    fetched = []
    real = pipeline.host_sizes

    def counted(batches):
        fetched.append(len(batches))
        return real(batches)

    monkeypatch.setattr(pipeline, "host_sizes", counted)
    s = tpu_session(**_SHRINK_ALL)
    rows = build(s).collect()
    m = s.last_metrics["pipeline"]
    assert m.get("fusedShrinks", 0) == breaks, m
    assert m["shrinks"] == 1, m
    assert m["programs"] == breaks + 1, m
    assert len(fetched) == (2 if breaks else 1), fetched
    assert fetched[0] >= breaks, fetched
    assert rows == build(cpu_session()).collect()


def _spans(s, *sites):
    return [(e.site, e.name) for e in s.query_history()[-1].events
            if e.kind == "span" and e.site in sites]


def test_keyless_aggregate_is_one_program_and_one_read_back():
    """A keyless filter-and-sum: the update is compiled into its
    consumer's stage, so a collect is ONE dispatch, and the fast path's
    flag comes home in the answer's transfer — the host waits for the
    chip once, at ``d2h_ready``.  The same columns grouped by a key keep
    their break: two programs and the one ``host_sizes`` round trip."""
    s = tpu_session()
    df = _keyless(s)
    for _ in range(2):      # the one that compiles, and a warm one
        rows = df.collect()
        m = s.last_metrics
        assert m["dispatchCount"] == 1, m["dispatchCount"]
        assert m["pipeline"]["programs"] == 1, m["pipeline"]
        assert m["pipeline"]["inlinedUpdates"] == 1, m["pipeline"]
        assert "flagReruns" not in m["pipeline"], m["pipeline"]
        assert _spans(s, "device_wait") == [("device_wait", "d2h_ready")]
        (enqueued,) = _spans(s, "enqueue")
        assert enqueued[1].startswith("stage:"), enqueued
    assert rows == _keyless(cpu_session()).collect()

    keyed = tpu_session(**_SHRINK_ALL)
    rows = _headline_query(keyed).collect()
    m = keyed.last_metrics
    assert m["pipeline"]["programs"] == 2, m["pipeline"]
    assert "inlinedUpdates" not in m["pipeline"], m["pipeline"]
    waits = [n for _, n in _spans(keyed, "device_wait")]
    assert waits.count("host_sizes") == 2, waits   # the break's, the root's
    assert waits[-1] == "d2h_ready", waits
    assert rows == _headline_query(cpu_session()).collect()


def test_prewarm_compiles_hot_set_once():
    s = tpu_session()
    q = _headline_query(s)
    warm = s.prewarm(q)
    assert warm["compileCount"] > 0
    q.collect()
    assert s.last_metrics["compileCount"] == 0, \
        "collect after prewarm() must hit every compiled program"
    # a second prewarm is a no-op compile-wise
    warm2 = s.prewarm(q)
    assert warm2["compileCount"] == 0


def test_shared_bucket_policy():
    from spark_rapids_tpu.batch import BUCKETS, round_up_capacity
    assert BUCKETS.rows(1) == 8
    assert BUCKETS.rows(9) == 16
    assert BUCKETS.elems(1) == 16
    assert BUCKETS.elems(17) == 32
    # round_up_capacity routes through the shared policy
    assert round_up_capacity(1000) == BUCKETS.rows(1000) == 1024
    ladder = BUCKETS.hot_buckets(1 << 20)
    assert ladder[0] == 8 and ladder[-1] == 1 << 20
    # pow2 ladder: compiled-shape cardinality is log2-bounded
    assert len(ladder) == 18


def test_pallas_strings_tpu_only(monkeypatch):
    """Pallas lowering is strictly backend == 'tpu' (plus the explicit
    interpret conf); any other accelerator backend takes the XLA
    formulation, and the conf gate turns it off on a TPU too."""
    import jax

    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.kernels import pallas_tier as PT

    def engaged(conf=None):
        PT.configure(RapidsConf(dict(conf or {})))
        try:
            return PT.decide("strings").engaged
        finally:
            PT.configure(None)

    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not engaged()
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert not engaged()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert engaged()
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert engaged({"spark.rapids.sql.tpu.pallas.interpret": True})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not engaged(
        {"spark.rapids.sql.tpu.pallas.strings.enabled": False})
