"""Compile/dispatch economics tests: the per-query compile-miss /
dispatch-count / device-time accounting (utils/compile_registry +
utils/tracing), the shared shape-bucket policy, tail-stage fusion, and
session.prewarm()."""

import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.session import TpuSparkSession

from compare import tpu_session


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
                "compiledShapes", "deviceTimeNs"):
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


def test_metrics_detail_toggle_keeps_plan_cache_warm():
    """The metrics-detail conf is excluded from the plan-cache fingerprint:
    flipping it must not recompile anything (bench relies on this for the
    accurate device-time capture run)."""
    s = tpu_session()
    q = _headline_query(s)
    q.collect()
    s.set_conf("spark.rapids.sql.tpu.metrics.detailEnabled", True)
    q.collect()
    m = s.last_metrics
    assert m["compileCount"] == 0
    assert m["deviceTimeNs"] > 0


def _dispatches(fuse: bool):
    conf = RapidsConf({
        "spark.rapids.sql.enabled": True,
        "spark.sql.shuffle.partitions": 4,
        # force the stage-break shrink so the fused-vs-separate dispatch
        # difference is observable at test scale
        "spark.rapids.sql.tpu.pipeline.shrinkBytes": 0,
        "spark.rapids.sql.tpu.pipeline.fuseTail.enabled": fuse,
    })
    s = TpuSparkSession(conf)
    q = _headline_query(s)
    rows = q.collect()
    assert rows
    return s.last_metrics["dispatchCount"], rows


def test_tail_fusion_reduces_dispatch_count():
    fused_d, fused_rows = _dispatches(fuse=True)
    plain_d, plain_rows = _dispatches(fuse=False)
    assert fused_rows == plain_rows  # fusion is a pure dispatch optimizer
    assert fused_d < plain_d, \
        f"tail fusion did not reduce dispatches: {fused_d} vs {plain_d}"


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
