"""Mesh all-to-all shuffle tests over the 8-device virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.parallel.distributed import run_distributed_agg_demo
from spark_rapids_tpu.parallel.mesh_shuffle import make_exchange_fn, make_mesh

from jax.sharding import NamedSharding, PartitionSpec as P


def test_exchange_roundtrip():
    mesh = make_mesh(4)
    n, cap = 4, 32
    rng = np.random.RandomState(0)
    data = rng.randint(0, 1000, size=(n, cap)).astype(np.int64)
    validity = rng.rand(n, cap) < 0.8
    num_rows = np.array([32, 20, 0, 7], dtype=np.int32)
    pids = rng.randint(0, n, size=(n, cap)).astype(np.int32)

    sh = NamedSharding(mesh, P("data", None))
    s1 = NamedSharding(mesh, P("data"))
    fn = make_exchange_fn(mesh, n_cols=1, cap=cap)
    (out_d,), (out_v,), out_n = fn(
        [jax.device_put(data, sh)], [jax.device_put(validity, sh)],
        jax.device_put(num_rows, s1), jax.device_put(pids, sh))
    out_d = np.asarray(out_d)
    out_v = np.asarray(out_v)
    out_n = np.asarray(out_n)

    # every (value, validity) row must land exactly once on the right device
    sent = {}
    for d in range(n):
        for r in range(num_rows[d]):
            key = (int(pids[d, r]),)
            sent.setdefault(key, []).append(
                (int(data[d, r]), bool(validity[d, r])))
    for dest in range(n):
        got = [(int(out_d[dest, i]), bool(out_v[dest, i]))
               for i in range(int(out_n[dest]))]
        exp = sent.get((dest,), [])
        assert sorted(got) == sorted(exp), f"dest {dest}"


def test_distributed_agg_demo_8dev():
    stats = run_distributed_agg_demo(8, rows_per_device=128)
    assert stats["devices"] == 8
    assert stats["groups"] == 17


# ---------------------------------------------------------------------------
# Engine-level mesh shuffle: planner-built queries whose exchanges run the
# ICI all-to-all collective (spark.rapids.shuffle.ici.enabled).
# ---------------------------------------------------------------------------

from tests.compare import assert_tpu_cpu_equal, tpu_session  # noqa: E402
from spark_rapids_tpu import functions as F  # noqa: E402

MESH_CONFS = {"spark.rapids.shuffle.ici.enabled": True,
              "spark.rapids.sql.variableFloatAgg.enabled": True}


def _people_df(sess, n=500, parts=5):
    cats = ["red", "green", "blue", None, "a-very-long-color-name-x", ""]
    rng = np.random.RandomState(3)
    return sess.create_dataframe({
        "name": [cats[i] for i in rng.randint(0, len(cats), n)],
        "age": rng.randint(0, 90, n).tolist(),
        "score": (rng.rand(n) * 10).round(4).tolist(),
    }, num_partitions=parts)


def _assert_mesh_used(sess):
    # host-driven exchanges count meshExchanges; with mesh SPMD (the
    # default) the exchange instead fuses into a shard_map program and
    # counts meshBoundariesFused — either proves rows moved over the mesh
    ops = [op for op, ms in sess.last_metrics.items()
           if isinstance(ms, dict) and (ms.get("meshExchanges") or
                                        ms.get("meshBoundariesFused"))]
    assert ops, f"no mesh exchange ran: {sess.last_metrics}"


def test_mesh_groupby_string_key():
    assert_tpu_cpu_equal(
        lambda s: _people_df(s).group_by("name").agg(
            F.sum(F.col("age")), F.count(F.col("age")),
            F.avg(F.col("score"))),
        approx=True, confs=MESH_CONFS)
    sess = tpu_session(**MESH_CONFS)
    _people_df(sess).group_by("name").agg(F.sum(F.col("age"))).collect()
    _assert_mesh_used(sess)


def test_mesh_shuffled_join():
    def build(s):
        left = _people_df(s, n=300, parts=4)
        right = s.create_dataframe({
            "name": ["red", "green", "blue", None, "missing"],
            "bonus": [1, 2, 3, 4, 5],
        }, num_partitions=2)
        # big threshold=0 disables broadcast so the shuffled path runs
        return left.join(right, on="name", how="inner")

    assert_tpu_cpu_equal(
        build, confs={**MESH_CONFS,
                      "spark.sql.autoBroadcastJoinThreshold": 0})
    sess = tpu_session(**MESH_CONFS,
                       **{"spark.sql.autoBroadcastJoinThreshold": 0})
    build(sess).collect()
    _assert_mesh_used(sess)


def test_mesh_global_sort_ordering():
    # range partitioning over the mesh must preserve total order across
    # device partitions (partition d's keys < partition d+1's)
    assert_tpu_cpu_equal(
        lambda s: _people_df(s, n=400).sort(
            F.col("age").asc(), F.col("name").asc()),
        approx=True, ignore_order=False, confs=MESH_CONFS)


def test_mesh_repartition_roundrobin():
    assert_tpu_cpu_equal(
        lambda s: _people_df(s, n=200).repartition(6).select("age"),
        confs=MESH_CONFS, ignore_order=True)


def test_mesh_distinct():
    assert_tpu_cpu_equal(
        lambda s: _people_df(s, n=300).select("name").distinct(),
        confs=MESH_CONFS)


def test_mesh_strings_survive_roundtrip():
    # empty strings, NULLs and long strings through the padded-matrix
    # all-to-all layout
    sess = tpu_session(**MESH_CONFS)
    vals = ["", None, "x" * 100, "short", "ünïcødé-ÿ", "tail"] * 20
    df = sess.create_dataframe(
        {"s": vals, "v": list(range(len(vals)))}, num_partitions=4)
    out = df.group_by("s").agg(F.count(F.col("v")))
    rows = sorted(out.collect(), key=lambda r: (r[0] is None, str(r[0])))
    expect = {}
    for s in vals:
        expect[s] = expect.get(s, 0) + 1
    exp = sorted(expect.items(), key=lambda r: (r[0] is None, str(r[0])))
    assert [(a, b) for a, b in rows] == exp
    _assert_mesh_used(sess)


def test_multihost_single_process_noop():
    """World size 1 (every dev/test environment): init is a no-op and the
    process-group info reflects a single process."""
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.parallel.multihost import init_multihost, world_info
    assert init_multihost(RapidsConf()) is False
    info = world_info()
    assert info["process_count"] == 1 and info["process_index"] == 0
    assert info["global_devices"] == info["local_devices"]


def test_mesh_shuffle_payloads_stay_on_device(monkeypatch):
    """The device-resident contract (VERDICT r3 #1): between map-side eval
    and reduce-side consumption, NO payload-sized buffer is device_get —
    only scalar/metadata fetches and the final result materialization
    touch the host."""
    import spark_rapids_tpu.batch as B
    import spark_rapids_tpu.plan.pipeline as PL

    in_materialize = []
    offending = []
    real_get = jax.device_get
    real_d2h_with = B.device_to_host_with

    def patched_d2h_with(batches, riders, keep_dictionary=False):
        in_materialize.append(True)
        try:
            return real_d2h_with(batches, riders, keep_dictionary)
        finally:
            in_materialize.pop()

    def patched_get(x):
        if not in_materialize:
            for leaf in jax.tree_util.tree_leaves(x):
                size = getattr(leaf, "size", None)
                if size is not None and size > 256:
                    offending.append(getattr(leaf, "shape", size))
        return real_get(x)

    monkeypatch.setattr(jax, "device_get", patched_get)
    monkeypatch.setattr(B, "device_to_host_with", patched_d2h_with)
    monkeypatch.setattr(PL, "device_to_host_with", patched_d2h_with)

    sess = tpu_session(**MESH_CONFS,
                       **{"spark.sql.autoBroadcastJoinThreshold": 0})
    left = _people_df(sess, n=600, parts=4)
    right = sess.create_dataframe({
        "name": ["red", "green", "blue", None, "missing"],
        "bonus": [1, 2, 3, 4, 5],
    }, num_partitions=2)
    out = left.join(right, on="name", how="inner") \
              .group_by("name").agg(F.sum(F.col("age")),
                                    F.count(F.col("bonus")))
    rows = out.collect()
    assert rows, "mesh query returned nothing"
    _assert_mesh_used(sess)
    assert not offending, \
        f"payload-sized device_get on the mesh path: {offending[:5]}"
