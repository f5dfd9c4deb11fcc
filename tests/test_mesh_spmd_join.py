"""Mesh SPMD v2 fused-join tests: hash/broadcast joins compiled INTO the
fused shard_map program (static bucketed output sizing, zero host syncs),
bit-identical to the host-driven mesh path and the CPU oracle across
1/2/4/8 virtual devices; bucket-overflow fallback; dict-encoded keys and
the encoded-materialization boundary; plan_verify join-rule fixtures."""

import numpy as np
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu import types as T

from tests.compare import tpu_session
from tests.test_mesh_spmd import MESH_CONFS, SPMD_CONFS, SPMD_OFF_CONFS

# threshold 0 disables broadcast: the shuffled (hash) strategy runs
HASH_JOIN = {"spark.sql.autoBroadcastJoinThreshold": 0}
GROWTH_KEY = "spark.rapids.sql.tpu.mesh.spmd.join.growthFactor"


def _left_df(sess, n=200, parts=4):
    cats = ["red", "green", "blue", None, "a-very-long-color-name-x", ""]
    rng = np.random.RandomState(7)
    return sess.create_dataframe({
        "name": [cats[i] for i in rng.randint(0, len(cats), n)],
        "age": rng.randint(0, 90, n).tolist(),
    }, num_partitions=parts)


def _right_df(sess):
    return sess.create_dataframe({
        "name": ["red", "green", "blue", None, "missing", ""],
        "bonus": [1, 2, 3, 4, 5, 6],
    }, num_partitions=2)


def _join_query(s, how, strategy):
    left = _left_df(s)
    right = _right_df(s)
    return left.join(right, on="name", how=how)


def _rows(df):
    return sorted(df.collect(), key=repr)


def _cpu_rows(how, strategy):
    s = tpu_session(**{"spark.rapids.sql.enabled": False})
    return _rows(_join_query(s, how, strategy))


def _mesh_n_devices(monkeypatch, k):
    """Pin the session's shuffle mesh to the first ``k`` virtual devices
    (session._shuffle_mesh resolves make_mesh from the module at call
    time, so patching the module attribute sizes every new session)."""
    import spark_rapids_tpu.parallel.mesh_shuffle as MS
    real = MS.make_mesh

    def sized(n_devices=None):
        return real(k)

    monkeypatch.setattr(MS, "make_mesh", sized)


# -- fused-join parity matrix ------------------------------------------------


# The quick lane keeps one full-sweep combo per strategy plus the
# cheapest anti cases; the remaining hows ride the slow lane (the fused
# kernel is how-agnostic past the stitch masks, so one how per strategy
# exercises every compiled path — the slow sweep still proves the matrix)
_MATRIX = [
    pytest.param("inner", "hash"),
    pytest.param("left", "hash", marks=pytest.mark.slow),
    pytest.param("left_semi", "hash", marks=pytest.mark.slow),
    pytest.param("left_anti", "hash", marks=pytest.mark.slow),
    pytest.param("inner", "broadcast"),
    pytest.param("left", "broadcast", marks=pytest.mark.slow),
    pytest.param("left_semi", "broadcast", marks=pytest.mark.slow),
    pytest.param("left_anti", "broadcast"),
]


@pytest.mark.parametrize("how,strategy", _MATRIX)
def test_spmd_join_parity_matrix(monkeypatch, how, strategy):
    """inner/left/semi/anti x shuffled-hash/broadcast x 1/2/4/8 devices:
    the fused per-shard join (static bucketed sizing, build side
    replicated for broadcast) is bit-identical to spmd-off and the CPU
    oracle, with zero overflow fallbacks at the default growth factor."""
    confs = dict(SPMD_CONFS)
    if strategy == "hash":
        confs.update(HASH_JOIN)
    want = _cpu_rows(how, strategy)
    off = tpu_session(**{**confs,
                         "spark.rapids.sql.tpu.mesh.spmd.enabled": False})
    assert _rows(_join_query(off, how, strategy)) == want

    for k in (1, 2, 4, 8):
        _mesh_n_devices(monkeypatch, k)
        s = tpu_session(**confs)
        got = _rows(_join_query(s, how, strategy))
        assert got == want, (how, strategy, k, got[:4], want[:4])
        m = s.last_metrics
        assert m["meshJoinsFused"] >= 1, (how, strategy, k, m)
        assert m["meshFallbacks"] == 0, (how, strategy, k, m)


@pytest.mark.parametrize("how", ["right", "full"])
def test_spmd_join_outer_hash_parity(how):
    """right/full outer ride the shuffled path too (co-partitioned
    shards make every join type exact per shard).  A USING full join's
    key projection is a string Coalesce — not TPU-supported — so its
    plan root falls back to CPU and never enters the mesh pipeline:
    parity holds, but only 'right' asserts fusion."""
    confs = {**SPMD_CONFS, **HASH_JOIN}
    want = _cpu_rows(how, "hash")
    s = tpu_session(**confs)
    assert _rows(_join_query(s, how, "hash")) == want
    if how == "right":
        assert s.last_metrics["meshJoinsFused"] >= 1, s.last_metrics


def test_spmd_join_feeding_aggregation_parity():
    """join -> group_by: the fused-join stage's root is the MXU hash
    aggregate, whose stage flag is an output of the mesh program beside
    its batches — one entry a device, never a batch among the payload
    lists, which are all of root.output_schema."""
    def q(s):
        left = _left_df(s)
        right = _right_df(s)
        return left.join(right, on="name", how="inner").group_by(
            "name").agg(F.sum(F.col("bonus")).alias("sb"))

    cpu = tpu_session(**{"spark.rapids.sql.enabled": False})
    want = _rows(q(cpu))
    s = tpu_session(**{**SPMD_CONFS, **HASH_JOIN})
    assert _rows(q(s)) == want
    m = s.last_metrics
    assert m["meshJoinsFused"] >= 1, m
    assert m["meshFallbacks"] == 0, m


def test_spmd_stage_flag_set_on_one_shard_reruns_the_exact_variant():
    """A NaN under the float sum of the fused-join stage's hash aggregate:
    the mesh program's flag vector comes back set, the stage is
    dispatched again in the exact variant, and the rows are the
    oracle's."""
    confs = {"spark.rapids.sql.variableFloatAgg.enabled": True}

    def q(s):
        right = s.create_dataframe({
            "name": ["red", "green", "blue", None, "missing", ""],
            "w": [1.5, float("nan"), 3.0, 4.0, 5.0, 6.0],
        }, num_partitions=2)
        return _left_df(s).join(right, on="name", how="inner").group_by(
            "age").agg(F.sum(F.col("w")).alias("sw"),
                       F.count(F.col("name")).alias("c"))

    def rows(df):
        return sorted(
            (tuple("nan" if isinstance(x, float) and x != x else x
                   for x in r) for r in df.collect()), key=repr)

    want = rows(q(tpu_session(**{"spark.rapids.sql.enabled": False},
                              **confs)))
    assert any("nan" in r for r in want)
    s = tpu_session(**{**SPMD_CONFS, **HASH_JOIN, **confs})
    assert rows(q(s)) == want
    m = s.last_metrics
    assert m["meshJoinsFused"] >= 1 and m["meshFallbacks"] == 0, m
    assert m["pipeline"]["flagReruns"] == 1, m["pipeline"]
    assert sum(ms.get("hashAggFallback", 0) for ms in m.values()
               if isinstance(ms, dict)) == 1


def test_spmd_join_fused_economics():
    """The pinned acceptance shape: a hash join ACROSS a shuffle compiles
    into ONE fused program — zero blocking shuffle syncs, >=1 fused
    boundary, >=1 fused join, no fallback."""
    s = tpu_session(**SPMD_CONFS, **HASH_JOIN)
    out = _left_df(s).join(_right_df(s), on="name", how="inner") \
        .group_by("name").agg(F.sum(F.col("age")),
                              F.count(F.col("bonus")))
    rows = out.collect()
    assert rows
    m = s.last_metrics
    assert m["shuffleSyncs"] == 0, m
    assert m["meshBoundariesFused"] >= 1, m
    assert m["meshJoinsFused"] >= 1, m
    assert m["meshFallbacks"] == 0, m
    assert m["meshProgramDispatches"] >= 1, m


def test_spmd_join_with_pallas_probe_kernel_parity():
    """Mesh v2 fused join with the Pallas probe kernel engaged (interpret
    mode on the CPU mesh): bit-identical rows, still one fused program
    with zero shuffle syncs, and zero kernel fallbacks — the kernel tier
    is shard_map-compatible (docs/kernels.md)."""
    pallas_on = {
        "spark.rapids.sql.tpu.pallas.interpret": True,
    }
    pallas_off = {
        "spark.rapids.sql.tpu.pallas.strings.enabled": False,
        "spark.rapids.sql.tpu.pallas.gatherScatter.enabled": False,
        "spark.rapids.sql.tpu.pallas.joinProbe.enabled": False,
        "spark.rapids.sql.tpu.pallas.stringHash.enabled": False,
    }
    off = tpu_session(**SPMD_CONFS, **HASH_JOIN, **pallas_off)
    want = _rows(_join_query(off, "inner", "hash"))

    s = tpu_session(**SPMD_CONFS, **HASH_JOIN, **pallas_on)
    got = _rows(_join_query(s, "inner", "hash"))
    assert got == want, (got[:4], want[:4])
    m = s.last_metrics
    assert m["meshJoinsFused"] >= 1, m
    assert m["shuffleSyncs"] == 0, m
    assert m["meshFallbacks"] == 0, m
    assert m["pallasFallbackCount"] == 0, m


def test_spmd_join_empty_shards_parity():
    """2 distinct keys over 8 shards: most shards receive zero rows and
    the per-shard static join must stay exact through them."""
    def build(s):
        left = s.create_dataframe(
            {"k": ["a", "b"] * 30, "v": list(range(60))},
            num_partitions=4)
        right = s.create_dataframe(
            {"k": ["a", "z"], "w": [10, 20]}, num_partitions=2)
        return left.join(right, on="k", how="left")
    want = _rows(build(tpu_session(
        **{"spark.rapids.sql.enabled": False})))
    s = tpu_session(**SPMD_CONFS, **HASH_JOIN)
    assert _rows(build(s)) == want
    assert s.last_metrics["meshJoinsFused"] >= 1, s.last_metrics


# -- bucket overflow -> host-driven fallback ---------------------------------


def _dup_key_join(s):
    # heavily duplicated keys: the true pair count per shard far exceeds
    # a tiny growth factor's static bucket
    left = s.create_dataframe(
        {"k": ["x", "y"] * 100, "v": list(range(200))}, num_partitions=4)
    right = s.create_dataframe(
        {"k": ["x", "y"] * 10, "w": list(range(20))}, num_partitions=2)
    return left.join(right, on="k", how="inner")


def test_spmd_join_overflow_falls_back_with_parity():
    want = _rows(_dup_key_join(tpu_session(
        **{"spark.rapids.sql.enabled": False})))
    s = tpu_session(**SPMD_CONFS, **HASH_JOIN, **{GROWTH_KEY: 0.02})
    assert _rows(_dup_key_join(s)) == want
    m = s.last_metrics
    assert m["meshFallbacks"] >= 1, m
    assert m["meshProgramDispatches"] >= 1, m
    # the overflow is observable, not silent
    names = [e.name for e in s.query_history()[-1].events]
    assert "join_overflow_fallback" in names, names


def test_spmd_join_overflow_autofallback_disabled_raises():
    s = tpu_session(**SPMD_CONFS, **HASH_JOIN,
                    **{GROWTH_KEY: 0.02,
                       "spark.rapids.sql.tpu.mesh.spmd.autoFallback":
                       False})
    with pytest.raises(RuntimeError, match="growthFactor"):
        _dup_key_join(s).collect()


@pytest.mark.slow
def test_spmd_join_overflow_leaves_resources_clean():
    s = tpu_session(**SPMD_CONFS, **HASH_JOIN, **{GROWTH_KEY: 0.02})
    assert _dup_key_join(s).collect()
    assert s.runtime.semaphore.held_depth() == 0
    s.runtime.catalog.drain_spills()
    assert s.runtime.catalog.verify_accounting() == []


# -- fault injection through a FUSED join program ----------------------------


@pytest.mark.slow
def test_spmd_join_device_lost_replays_bit_identical():
    confs = {**SPMD_CONFS, **HASH_JOIN}
    want = _rows(_join_query(tpu_session(**confs), "inner", "hash"))
    s = tpu_session(**confs, **{
        "spark.rapids.sql.tpu.faults.spec": "mesh:device_lost@1"})
    got = _rows(_join_query(s, "inner", "hash"))
    assert got == want
    m = s.last_metrics
    assert m["faultsInjected"] >= 1, m
    assert m["deviceLostCount"] >= 1, m
    assert m["retryCount"] > 0, m
    assert m["meshJoinsFused"] >= 1, m
    assert s.runtime.semaphore.held_depth() == 0


# -- dict-encoded keys and the mesh materialization boundary -----------------


def _write_dict_parquet(tmp_path, sess):
    out = str(tmp_path / "pq")
    sess.create_dataframe({
        "name": (["red", "green", None, "blue", "red", ""] * 40),
        "age": list(range(240)),
    }, num_partitions=2).write_parquet(out)
    return out


def _scan_join(s, out):
    left = s.read.parquet(out)
    right = _right_df(s)
    return left.join(right, on="name", how="inner")


def test_mesh_exchange_materializes_encoded_with_parity(tmp_path):
    """Dict-encoded scan columns materialize before the host-driven mesh
    exchange (the wire moves decoded rows): parity with dict encoding
    off, plus the exchange/mesh_materialize instant and the
    meshEncodedMaterializedBytes metric account the bytes given up."""
    # threshold 0 forces the shuffled strategy: the encoded scan side must
    # actually cross a mesh exchange for the boundary to exist
    base = {**SPMD_OFF_CONFS, **HASH_JOIN,
            "spark.rapids.sql.tpu.scan.v2.enabled": True}
    out = _write_dict_parquet(tmp_path, tpu_session())
    s_on = tpu_session(**base)
    got = _rows(_scan_join(s_on, out))
    s_off = tpu_session(**base, **{
        "spark.rapids.sql.tpu.scan.dictEncoding.enabled": False})
    assert got == _rows(_scan_join(s_off, out))
    m = s_on.last_metrics
    assert m["meshEncodedMaterializedBytes"] > 0, m
    evs = [e for e in s_on.query_history()[-1].events
           if e.name == "mesh_materialize"]
    assert evs, [e.name for e in s_on.query_history()[-1].events][:40]
    assert sum(e.payload.get("bytes", 0) for e in evs) == \
        m["meshEncodedMaterializedBytes"], (evs, m)


def test_spmd_join_encoded_keys_parity(tmp_path):
    """Dict-encoded join keys through the FUSED mesh join: parity with
    dictKeys off and with spmd off."""
    out = _write_dict_parquet(
        tmp_path, tpu_session())
    base = {**SPMD_CONFS, **HASH_JOIN,
            "spark.rapids.sql.tpu.scan.v2.enabled": True}
    s = tpu_session(**base)
    got = _rows(_scan_join(s, out))
    s_nokeys = tpu_session(**base, **{
        "spark.rapids.sql.tpu.join.dictKeys.enabled": False})
    assert got == _rows(_scan_join(s_nokeys, out))
    s_off = tpu_session(**{
        **base, "spark.rapids.sql.tpu.mesh.spmd.enabled": False})
    assert got == _rows(_scan_join(s_off, out))


def test_spmd_join_encoded_keys_overflow_fallback_parity(tmp_path):
    """Encoded keys INTERACTING with the overflow fallback: a bucket
    overflow reruns the stage host-driven with the encoded corridor still
    on, bit-identical to the relaxed-growth fused run."""
    out = _write_dict_parquet(tmp_path, tpu_session())
    base = {**SPMD_CONFS, **HASH_JOIN,
            "spark.rapids.sql.tpu.scan.v2.enabled": True}
    want = _rows(_scan_join(tpu_session(**base), out))
    s = tpu_session(**base, **{GROWTH_KEY: 0.01})
    assert _rows(_scan_join(s, out)) == want
    assert s.last_metrics["meshFallbacks"] >= 1, s.last_metrics


# -- plan_verify join rules --------------------------------------------------


def test_plan_verify_fused_join_fixtures():
    """Verifier accept/reject over a REAL fused-join stage: undeclared
    leaf specs in the join subtree, out-of-subtree join ids, replicated
    leaves that are not P(), and replicated join outputs all reject;
    an exchange-free (broadcast-join-only) stage shape is legal."""
    from spark_rapids_tpu.analysis.plan_verify import (
        PlanInvariantError, verify_plan,
    )
    from tests.test_mesh_spmd import _mesh_spec_op
    s = tpu_session(**SPMD_CONFS, **HASH_JOIN)
    _join_query(s, "inner", "hash").collect()
    root = s.last_physical_plan
    op = _mesh_spec_op(root)
    assert op is not None, "no op recorded mesh partition specs"
    good = op._mesh_partition_specs
    assert good["joins"], good
    verify_plan(root)

    def reject(**overrides):
        op._mesh_partition_specs = {**good, **overrides}
        try:
            with pytest.raises(PlanInvariantError):
                verify_plan(root)
        finally:
            op._mesh_partition_specs = good

    missing = list(good["in_specs"])
    missing[0] = None  # undeclared-spec leaf in a fused join subtree
    reject(in_specs=missing)
    reject(joins=["no-such-op"])  # join outside the stage subtree
    # a data-sharded leaf claimed as a broadcast build side must reject
    sharded = [i for i, sp in enumerate(good["in_specs"])
               if not all(a is None for a in tuple(sp))]
    reject(replicated=[sharded[0]])
    from jax.sharding import PartitionSpec as P
    if good["out_specs"]:
        bad_out = list(good["out_specs"])
        bad_out[0] = P()  # a fused join's output must be data-sharded
        reject(out_specs=bad_out)
    # reshard-free is legal when a join fused (broadcast-only stages),
    # but only alongside its joins — both empty must still reject
    op._mesh_partition_specs = {**good, "reshards": []}
    try:
        verify_plan(root)
    finally:
        op._mesh_partition_specs = good
    reject(reshards=[], joins=[])
    verify_plan(root)
