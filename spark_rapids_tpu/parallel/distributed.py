"""Distributed query step over a device mesh: the flagship SPMD pipeline
(partition -> ICI all-to-all -> local merge aggregation), demonstrating the
full multi-chip shuffle path that replaces the reference's
RapidsShuffleManager+UCX data plane (SURVEY.md section 2.7).

The same step structure the driver dry-runs: every device holds one shard of
rows, hashes its grouping keys, exchanges rows so equal keys co-locate, and
merge-aggregates locally — i.e. the Partial/Exchange/Final pipeline of
TpuHashAggregateExec, fused into one compiled SPMD program.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_tpu.parallel.mesh_shuffle import (
    DATA_AXIS, make_exchange_fn, make_mesh,
)


def _local_sum_by_key(keys, values, validity, num_rows, cap: int):
    """Per-device groupby-sum on int64 keys via sort + segment sums."""
    live = jnp.arange(cap, dtype=jnp.int32) < num_rows
    big = jnp.int64(jnp.iinfo(jnp.int64).max)
    k = jnp.where(live, keys, big)
    order = jnp.argsort(k, stable=True).astype(jnp.int32)
    ks = k[order]
    vs = jnp.where(validity[order] & live[order], values[order], 0)
    prev = jnp.concatenate([ks[:1] - 1, ks[:-1]])
    seg_start = live[order] & (ks != prev)
    seg_ids = jnp.clip(jnp.cumsum(seg_start.astype(jnp.int32)) - 1, 0,
                       cap - 1)
    sums = jax.ops.segment_sum(vs, seg_ids, num_segments=cap)
    n_groups = jnp.sum(seg_start).astype(jnp.int32)
    group_keys = jnp.where(seg_start, ks, big)
    gorder = jnp.argsort(jnp.where(seg_start, 0, 1), stable=True)
    out_keys = ks[gorder]
    return out_keys, sums, n_groups


def make_distributed_agg_step(mesh: Mesh, cap: int):
    """jitted SPMD fn: (keys [N,cap] i64, values [N,cap] i64,
    validity [N,cap] bool, num_rows [N]) ->
    (group_keys [N, N*cap], sums [N, N*cap], n_groups [N])."""
    n = mesh.shape[DATA_AXIS]
    exchange = make_exchange_fn(mesh, n_cols=2, cap=cap)

    from jax import shard_map

    def local_agg(keys, values, validity, num_rows):
        k, v, val, nr = keys[0], values[0], validity[0], num_rows[0]
        out_cap = int(k.shape[0])
        gk, gs, ng = _local_sum_by_key(k, v, val, nr, out_cap)
        return gk[None], gs[None], ng[None]

    local_agg_fn = jax.jit(shard_map(
        local_agg, mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None),
                  P(DATA_AXIS, None), P(DATA_AXIS)),
        out_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None), P(DATA_AXIS)),
        check_vma=False))

    def step(keys, values, validity, num_rows):
        pids = (jnp.abs(keys) % n).astype(jnp.int32)
        (d_cols, v_cols, new_rows) = exchange(
            [keys, values], [validity, validity], num_rows, pids)
        ex_keys, ex_vals = d_cols
        ex_kvalid, ex_vvalid = v_cols
        return local_agg_fn(ex_keys, ex_vals, ex_vvalid, new_rows)

    return jax.jit(step)


def run_distributed_agg_demo(n_devices: int, rows_per_device: int = 256,
                             n_keys: int = 17) -> dict:
    """Create an n-device mesh, run one full distributed aggregation step,
    verify against numpy, and return stats.  This is what
    ``__graft_entry__.dryrun_multichip`` calls."""
    mesh = make_mesh(n_devices)
    n = mesh.shape[DATA_AXIS]
    cap = rows_per_device
    rng = np.random.RandomState(7)
    keys = rng.randint(0, n_keys, size=(n, cap)).astype(np.int64)
    values = rng.randint(-100, 100, size=(n, cap)).astype(np.int64)
    validity = rng.rand(n, cap) < 0.9
    num_rows = np.full(n, cap, dtype=np.int32)
    num_rows[-1] = cap // 2  # ragged shard

    sharding = NamedSharding(mesh, P(DATA_AXIS, None))
    s1 = NamedSharding(mesh, P(DATA_AXIS))
    dk = jax.device_put(keys, sharding)
    dv = jax.device_put(values, sharding)
    dva = jax.device_put(validity, sharding)
    dn = jax.device_put(num_rows, s1)

    step = make_distributed_agg_step(mesh, cap)
    gk, gs, ng = jax.block_until_ready(step(dk, dv, dva, dn))

    # oracle
    expect = {}
    for d in range(n):
        for r in range(num_rows[d]):
            if validity[d, r]:
                expect[int(keys[d, r])] = expect.get(int(keys[d, r]), 0) + \
                    int(values[d, r])
            else:
                expect.setdefault(int(keys[d, r]), 0)
    got = {}
    gk_h = np.asarray(gk)
    gs_h = np.asarray(gs)
    ng_h = np.asarray(ng)
    for d in range(n):
        for i in range(int(ng_h[d])):
            got[int(gk_h[d, i])] = got.get(int(gk_h[d, i]), 0) + \
                int(gs_h[d, i])
    assert got == expect, f"distributed agg mismatch: {got} != {expect}"
    return {"devices": n, "groups": len(got), "rows": int(num_rows.sum())}


def run_distributed_query_demo(n_devices: int, n_rows: int = 4000) -> dict:
    """Execute a PLANNER-BUILT query (string group key included) with the
    mesh all-to-all as the engine's shuffle, and verify against a pure-CPU
    oracle session.

    This is the engine-level multi-chip path: TpuShuffleExchangeExec sees a
    >1-device mesh (spark.rapids.shuffle.ici.enabled) and routes the hash
    exchange through ``mesh_shuffle.mesh_exchange_batches`` — the analogue
    of running a real query through the reference's RapidsShuffleManager
    (RapidsShuffleInternalManager.scala:91-154) instead of Spark's fallback
    shuffle.  Requires the default platform to provide ``n_devices``
    devices (the dryrun subprocess forces CPU + device_count).
    """
    import jax
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.session import TpuSparkSession

    assert len(jax.devices()) >= n_devices, \
        f"need {n_devices} devices, have {len(jax.devices())}"

    cats = ["alpha", "beta", "gamma", "delta", None,
            "a-much-longer-category-name"]
    rng = np.random.RandomState(11)
    cat = [cats[i] for i in rng.randint(0, len(cats), n_rows)]
    qty = rng.randint(1, 100, n_rows).astype(np.int64)
    price = (rng.rand(n_rows) * 50).round(3)

    def build(sess):
        df = sess.create_dataframe(
            {"cat": list(cat), "qty": qty.tolist(),
             "price": price.tolist()},
            num_partitions=6)
        return (df.filter(F.col("qty") > 10)
                  .group_by("cat")
                  .agg(F.sum(F.col("qty")).alias("s"),
                       F.count(F.col("qty")).alias("c"),
                       F.avg(F.col("price")).alias("a")))

    tpu = (TpuSparkSession.builder()
           .config("spark.rapids.shuffle.ici.enabled", True)
           .config("spark.rapids.sql.variableFloatAgg.enabled", True)
           .config("spark.sql.shuffle.partitions", n_devices)
           .get_or_create())
    got_rows = build(tpu).collect()

    mesh_ops = [op for op, ms in tpu.last_metrics.items()
                if isinstance(ms, dict) and ms.get("meshExchanges")]
    assert mesh_ops, \
        f"no exchange took the mesh path; metrics={tpu.last_metrics}"

    # and a SHUFFLED JOIN through the same collective (both sides
    # all-to-all'd by key over the mesh, broadcast planning disabled)
    tpu.conf.set("spark.sql.autoBroadcastJoinThreshold", -1)
    dim = tpu.create_dataframe(
        {"cat": [c for c in cats if c is not None],
         "bonus": list(range(len(cats) - 1))}, num_partitions=2)
    fact = tpu.create_dataframe(
        {"cat": list(cat), "qty": qty.tolist()}, num_partitions=4)
    joined = fact.join(dim, on="cat", how="left")
    jrows = joined.collect()
    assert len(jrows) == n_rows, (len(jrows), n_rows)
    join_mesh_ops = [op for op, ms in tpu.last_metrics.items()
                     if isinstance(ms, dict)
                     if ms.get("meshExchanges")]
    assert len(join_mesh_ops) >= 2, tpu.last_metrics  # both join sides

    # oracle: plain python
    expect = {}
    for c, q, p in zip(cat, qty, price):
        if q <= 10:
            continue
        s, n_, a = expect.get(c, (0, 0, 0.0))
        expect[c] = (s + int(q), n_ + 1, a + float(p))
    exp_rows = sorted(
        ((k, s, n_, s_p / n_) for k, (s, n_, s_p) in expect.items()),
        key=lambda r: (r[0] is None, str(r[0])))
    got_sorted = sorted(got_rows, key=lambda r: (r[0] is None, str(r[0])))
    assert len(exp_rows) == len(got_sorted), \
        f"{len(exp_rows)} != {len(got_sorted)}"
    for e, g in zip(exp_rows, got_sorted):
        assert e[0] == g[0] and e[1] == g[1] and e[2] == g[2] and \
            abs(e[3] - g[3]) < 1e-6, f"mismatch: {e} vs {g}"
    return {"devices": n_devices, "groups": len(exp_rows),
            "mesh_exchanges": len(mesh_ops)}


def run_distributed_scale_demo(n_devices: int,
                               n_rows: int = 1_000_000) -> dict:
    """The dryrun's SCALE leg: >=1M rows through the planner-built mesh
    pipeline with a deliberately small spill budget, reporting shuffle
    bytes moved (the reference surfaces the same per-read shuffle
    accounting, RapidsCachingReader.scala:125-133; spill tiers are the
    "data > HBM" answer, SURVEY.md section 2.4).

    Asserts the mesh exchange carried >= the live payload of the rows and
    that the spill catalog actually fired.  Returns the stats dict the
    dryrun prints.
    """
    import jax
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.session import TpuSparkSession

    assert len(jax.devices()) >= n_devices, \
        f"need {n_devices} devices, have {len(jax.devices())}"

    rng = np.random.RandomState(23)
    keys = rng.randint(0, 100_000, n_rows).astype(np.int64)
    qty = rng.randint(1, 100, n_rows).astype(np.int64)
    price = (rng.rand(n_rows) * 50).round(3)

    tpu = (TpuSparkSession.builder()
           .config("spark.rapids.shuffle.ici.enabled", True)
           .config("spark.rapids.sql.variableFloatAgg.enabled", True)
           .config("spark.sql.shuffle.partitions", n_devices)
           .get_or_create())
    from spark_rapids_tpu import types as T
    df = tpu.create_dataframe(
        {"k": (T.LONG, keys), "qty": (T.LONG, qty),
         "price": (T.DOUBLE, price)},
        num_partitions=n_devices).cache()
    q = (df.group_by("k")
           .agg(F.sum(F.col("qty")).alias("s"),
                F.count(F.col("qty")).alias("c"),
                F.avg(F.col("price")).alias("a")))
    # Force the device budget BELOW the cached working set on the LIVE
    # catalog (DeviceRuntime is a process singleton — a session conf set
    # after first init would be ignored) and evict: the measured run must
    # unspill its inputs from host under a budget it cannot fit, the
    # "data > HBM" posture of the reference's spill tiers (SURVEY 2.4).
    catalog = tpu.runtime.catalog
    old_budget = catalog.device_budget
    mem0 = dict(catalog.metrics)
    try:
        q.collect()          # warmup: compiles + materializes the cache
        catalog.device_budget = max((n_rows * 24) // 3, 1 << 20)
        catalog.reserve(0)   # push the cached inputs to host
        rows = q.collect()   # measured run: unspills under budget
    finally:
        catalog.device_budget = old_budget
    assert len(rows) == len(np.unique(keys)), \
        (len(rows), len(np.unique(keys)))

    sh_bytes = wire = 0
    for op, ms in tpu.last_metrics.items():
        if op == "memory" or not isinstance(ms, dict):
            continue
        sh_bytes += ms.get("shuffleBytes", 0)
        wire += ms.get("shuffleWireBytes", 0)
    # the exchange carries PARTIAL-AGG output (100K distinct keys x agg
    # buffers), not raw rows — still megabytes at this scale
    assert sh_bytes >= 1 << 20, \
        f"mesh shuffle moved only {sh_bytes}B for {n_rows} rows"
    mem = tpu.last_metrics.get("memory", {})
    spilled = (mem.get("spilled_to_host", 0) - mem0["spilled_to_host"]) \
        + (mem.get("spilled_to_disk", 0) - mem0["spilled_to_disk"])
    unspilled = mem.get("unspilled", 0) - mem0["unspilled"]
    assert spilled > 0, f"spill never fired: {mem} (baseline {mem0})"
    assert unspilled > 0, \
        f"measured run never unspilled: {mem} (baseline {mem0})"
    return {"devices": n_devices, "rows": n_rows,
            "shuffle_bytes": int(sh_bytes), "wire_bytes": int(wire),
            "spilled_batches": int(spilled),
            "unspilled_batches": int(unspilled)}
