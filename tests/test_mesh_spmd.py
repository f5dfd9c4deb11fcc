"""Whole-stage mesh-SPMD execution tests (parallel.mesh_spmd) over the
8-device virtual CPU mesh: fused producer->all_to_all->consumer programs
must be bit-identical to the host-driven mesh path and the CPU oracle,
fall back per-exchange when the partitioning cannot lower in-program, and
leave the semaphore/catalog/plan invariants clean."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from spark_rapids_tpu import functions as F
from spark_rapids_tpu import types as T

from tests.compare import assert_tpu_cpu_equal, tpu_session
from tests.test_mesh_shuffle import MESH_CONFS

# spmd is the DEFAULT since mesh SPMD v2 — SPMD_CONFS keeps the explicit
# opt-in spelling, SPMD_OFF_CONFS pins the host-driven mesh path
SPMD_CONFS = {**MESH_CONFS,
              "spark.rapids.sql.tpu.mesh.spmd.enabled": True}
SPMD_OFF_CONFS = {**MESH_CONFS,
                  "spark.rapids.sql.tpu.mesh.spmd.enabled": False}


def _people_df(sess, n=400, parts=5):
    cats = ["red", "green", "blue", None, "a-very-long-color-name-x", ""]
    rng = np.random.RandomState(3)
    return sess.create_dataframe({
        "name": [cats[i] for i in rng.randint(0, len(cats), n)],
        "age": rng.randint(0, 90, n).tolist(),
        "score": (rng.rand(n) * 10).round(4).tolist(),
    }, num_partitions=parts)


def _groupby(s):
    return _people_df(s).group_by("name").agg(
        F.sum(F.col("age")), F.count(F.col("age")),
        F.avg(F.col("score")))


def _spmd_vs_hostdriven(build):
    """Collect ``build`` under spmd-on and spmd-off sessions; the fused
    program must be BIT-identical to the host-driven mesh path (same
    collective, same row placement — docs/mesh.md's parity contract)."""
    on = tpu_session(**SPMD_CONFS)
    off = tpu_session(**SPMD_OFF_CONFS)
    rows_on = sorted(build(on).collect(), key=repr)
    rows_off = sorted(build(off).collect(), key=repr)
    assert rows_on == rows_off, (rows_on[:5], rows_off[:5])
    return on


# -- parity: fused vs host-driven vs CPU oracle ------------------------------


def test_spmd_groupby_parity():
    assert_tpu_cpu_equal(_groupby, approx=True, confs=SPMD_CONFS)
    sess = _spmd_vs_hostdriven(_groupby)
    assert sess.last_metrics.get("meshProgramDispatches", 0) >= 1, \
        sess.last_metrics


def test_spmd_stage_break_source_is_shrunk_alone():
    """A mesh stage's shard_map program takes packed globals, so it cannot
    compile a stage break's re-bucketing in: the break's gather is
    dispatched alone (``shrinks``: the break's and the collected root's
    own), none is counted as fused, and the rows are the oracle's."""
    confs = {**SPMD_CONFS, "spark.rapids.sql.tpu.pipeline.shrinkBytes": 0}
    assert_tpu_cpu_equal(_groupby, approx=True, confs=confs)
    s = tpu_session(**confs)
    _groupby(s).collect()
    m = s.last_metrics
    assert m["meshProgramDispatches"] == 1, m
    assert m["pipeline"]["shrinks"] == 2, m["pipeline"]
    assert "fusedShrinks" not in m["pipeline"], m["pipeline"]


def test_spmd_repartition_roundrobin_parity():
    def build(s):
        return _people_df(s, n=200).repartition(6).select("age")
    assert_tpu_cpu_equal(build, confs=SPMD_CONFS, ignore_order=True)
    _spmd_vs_hostdriven(build)


def test_spmd_distinct_parity():
    def build(s):
        return _people_df(s, n=300).select("name").distinct()
    assert_tpu_cpu_equal(build, confs=SPMD_CONFS)
    _spmd_vs_hostdriven(build)


# -- fused-boundary economics ------------------------------------------------


def test_spmd_fused_metrics():
    """With spmd on and 8 virtual devices a two-stage shuffle query runs
    as ONE compiled program: >=1 fused boundary, ZERO blocking shuffle
    syncs, and the session reports which backend the mesh ran on."""
    s = tpu_session(**SPMD_CONFS)
    _groupby(s).collect()
    m = s.last_metrics
    assert m["meshProgramDispatches"] >= 1, m
    assert m["meshBoundariesFused"] >= 1, m
    assert m["shuffleSyncs"] == 0, m
    assert m["meshBackend"] == "cpu", m


def test_spmd_off_reports_zero_fusion():
    s = tpu_session(**SPMD_OFF_CONFS)
    _groupby(s).collect()
    m = s.last_metrics
    assert m["meshProgramDispatches"] == 0, m
    assert m["meshBoundariesFused"] == 0, m


def test_spmd_default_on():
    """Mesh SPMD v2 flips the default: a bare mesh session fuses without
    anyone setting mesh.spmd.enabled."""
    s = tpu_session(**MESH_CONFS)
    _groupby(s).collect()
    m = s.last_metrics
    assert m["meshProgramDispatches"] >= 1, m
    assert m["meshFallbacks"] == 0, m


# -- range partitioning fuses ------------------------------------------------


def test_spmd_range_sort_fuses_with_parity():
    """Mesh SPMD v2: range bounds are sampled, pooled (all_gather) and
    picked INSIDE the program (RangePartitioning.device_bounds_in_program)
    — the sort's exchange fuses instead of host-driving an eager
    prepare() sample, while the query keeps total order and CPU parity."""
    def build(s):
        return _people_df(s, n=300).sort(
            F.col("age").asc(), F.col("name").asc())
    assert_tpu_cpu_equal(build, approx=True, ignore_order=False,
                         confs=SPMD_CONFS)
    s = tpu_session(**SPMD_CONFS)
    build(s).collect()
    assert s.last_metrics["meshProgramDispatches"] >= 1, s.last_metrics
    _spmd_vs_hostdriven(build)


# -- fallback ----------------------------------------------------------------


def test_spmd_single_partition_falls_back_with_parity():
    """SinglePartitioning matches no PartitionSpec rule (each shard would
    see a private 'partition 0'): a keyless global aggregate's exchange
    stays host-driven with parity intact."""
    def build(s):
        return _people_df(s, n=200).agg(F.sum(F.col("age")),
                                        F.count(F.col("score")))
    assert_tpu_cpu_equal(build, approx=True, confs=SPMD_CONFS)


def test_spmd_autofallback_disabled_raises():
    s = tpu_session(**SPMD_CONFS, **{
        "spark.rapids.sql.tpu.mesh.spmd.autoFallback": False})
    q = _people_df(s, n=100).agg(F.sum(F.col("age")))
    with pytest.raises(RuntimeError, match="mesh-SPMD compatible"):
        q.collect()


# -- the in-program collective, unit-level -----------------------------------


def _decode_varlen(elems, offs, valid, total, string):
    out = []
    for r in range(total):
        if not valid[r]:
            out.append(None)
            continue
        seg = elems[int(offs[r]):int(offs[r + 1])]
        out.append(bytes(seg.tobytes()).decode("utf-8") if string
                   else tuple(int(x) for x in seg))
    return out


def test_exchange_batch_collective_unit():
    """exchange_batch_collective inside a hand-built shard_map: every
    (int, string, array) row lands exactly once on the device its pid
    names, across empty shards, NULLs, empty strings and empty arrays."""
    from spark_rapids_tpu.batch import HostBatch, host_to_device, \
        round_up_capacity
    from spark_rapids_tpu.parallel import mesh_spmd as MS
    from spark_rapids_tpu.parallel.mesh_shuffle import (
        exchange_batch_collective, make_mesh,
    )
    from jax import shard_map

    mesh = make_mesh(4)
    n = 4
    devices = list(mesh.devices.flat)
    cap = 16
    strs = ["", None, "x" * 40, "ünïcødé", "s"]
    arrs = [[1, 2, 3], [], None, [7], [9, 9]]
    per_dev_rows = [9, 5, 0, 7]
    hosts = []
    for d, rows in enumerate(per_dev_rows):
        hosts.append(HostBatch.from_pydict({
            "i": (T.INT, [(d * 31 + r * 7) % 97 for r in range(rows)]),
            "s": (T.STRING, [strs[(d + r) % len(strs)]
                             for r in range(rows)]),
            "a": (T.ArrayType(T.LONG), [arrs[(d + r) % len(arrs)]
                                        for r in range(rows)]),
        }))
    dbs = [host_to_device(hb, capacity=cap) for hb in hosts]
    schema = dbs[0].schema
    ecaps = tuple(
        round_up_capacity(
            max(int(db.columns[ci].data.shape[0]) for db in dbs),
            minimum=16)
        if MS._is_varlen(f) else 0
        for ci, f in enumerate(schema.fields))
    pack = MS._pack_fn(schema, cap, ecaps)
    shards_per_payload = None
    for d in range(n):
        payloads = pack(jax.device_put(dbs[d], devices[d]))
        if shards_per_payload is None:
            shards_per_payload = [[] for _ in payloads]
        for pi, p in enumerate(payloads):
            shards_per_payload[pi].append(p)
    in_specs, flat_globals = [], []
    for shards in shards_per_payload:
        tail = shards[0].shape[1:]
        spec = MS._full_rank_spec(len(tail) + 1, sharded=True)
        flat_globals.append(jax.make_array_from_single_device_arrays(
            (n,) + tail, NamedSharding(mesh, spec), shards))
        in_specs.append(spec)

    def body(flat):
        b = MS._batch_from_payloads(schema, list(flat), cap, squeeze=True)
        pid = (b.columns[0].data % n).astype(jnp.int32)
        out = exchange_batch_collective(b, pid, n)
        pl = []
        for c in out.columns:
            if c.offsets is not None:
                pl += [c.data[None], c.offsets.astype(jnp.int32)[None],
                       c.validity[None]]
            else:
                pl += [c.data[None], c.validity[None]]
        pl.append(jnp.asarray(out.num_rows, jnp.int32).reshape(1))
        return pl

    prog = shard_map(body, mesh=mesh, in_specs=(tuple(in_specs),),
                     out_specs=P("data"))
    outs = [np.asarray(g) for g in prog(tuple(flat_globals))]

    # host expectation: row (d, r) -> device i % n
    sent = {}
    for d, rows in enumerate(per_dev_rows):
        for r in range(rows):
            i = (d * 31 + r * 7) % 97
            sent.setdefault(i % n, []).append(
                (i, strs[(d + r) % len(strs)], arrs[(d + r) % len(arrs)]))
    totals = outs[-1]
    for dest in range(n):
        tot = int(totals[dest])
        ivals = [int(v) for v in outs[0][dest][:tot]]
        ivalid = outs[1][dest]
        svals = _decode_varlen(outs[2][dest], outs[3][dest],
                               outs[4][dest], tot, string=True)
        avals = _decode_varlen(outs[5][dest], outs[6][dest],
                               outs[7][dest], tot, string=False)
        assert all(bool(v) for v in ivalid[:tot])
        got = sorted(zip(ivals, [s if s is not None else "\0N" for s
                                 in svals],
                         [a if a is not None else ("\0N",) for a
                          in avals]))
        exp = sorted((i, s if s is not None else "\0N",
                      tuple(a) if a is not None else ("\0N",))
                     for i, s, a in sent.get(dest, []))
        assert got == exp, f"dest {dest}: {got[:4]} vs {exp[:4]}"


# -- fault injection / recovery ----------------------------------------------


def test_spmd_device_lost_replays_from_lineage():
    want = sorted(_groupby(tpu_session(**SPMD_CONFS)).collect(), key=repr)
    s = tpu_session(**SPMD_CONFS, **{
        "spark.rapids.sql.tpu.faults.spec": "mesh:device_lost@1"})
    got = sorted(_groupby(s).collect(), key=repr)
    assert got == want
    m = s.last_metrics
    assert m["faultsInjected"] >= 1, m
    assert m["deviceLostCount"] >= 1, m
    assert m["meshProgramDispatches"] >= 1, m


# -- resource hygiene --------------------------------------------------------


def test_spmd_leaves_semaphore_and_catalog_clean():
    s = tpu_session(**SPMD_CONFS)
    rows = _groupby(s).collect()
    assert rows
    assert s.runtime.semaphore.held_depth() == 0
    s.runtime.catalog.drain_spills()
    assert s.runtime.catalog.verify_accounting() == []


# -- plan_verify sharding invariants -----------------------------------------


def _mesh_spec_op(root):
    stack, seen = [root], set()
    while stack:
        op = stack.pop()
        if id(op) in seen:
            continue
        seen.add(id(op))
        if isinstance(getattr(op, "_mesh_partition_specs", None), dict):
            return op
        stack.extend(getattr(op, "children", ()) or ())
    return None


def test_plan_verify_mesh_fixtures():
    from spark_rapids_tpu.analysis.plan_verify import (
        PlanInvariantError, verify_plan,
    )
    s = tpu_session(**SPMD_CONFS)
    _groupby(s).collect()
    root = s.last_physical_plan
    op = _mesh_spec_op(root)
    assert op is not None, "no op recorded mesh partition specs"
    good = op._mesh_partition_specs
    verify_plan(root)  # accept fixture: the executed fused plan

    def reject(**overrides):
        op._mesh_partition_specs = {**good, **overrides}
        try:
            with pytest.raises(PlanInvariantError):
                verify_plan(root)
        finally:
            op._mesh_partition_specs = good

    bad_specs = list(good["in_specs"])
    bad_specs[0] = P(None, "data")  # neither replicated nor data-leading
    reject(in_specs=bad_specs)
    missing = list(good["in_specs"])
    missing[0] = None  # undeclared spec
    reject(in_specs=missing)
    reject(reshards=["no-such-op"])  # reshard outside the stage subtree
    reject(reshards=[])  # fused stage must record its boundary
    reject(dmask=(True,))  # donation under sharding
    verify_plan(root)  # restored


# -- backend honesty ---------------------------------------------------------


def test_make_mesh_never_switches_a_tpu_process_to_cpu(monkeypatch):
    """A default platform too small for the requested mesh silently
    switching to CPU virtual devices is how a bench mislabels CPU scaling
    as TPU scaling — on a tpu default platform make_mesh raises, even
    though enough CPU virtual devices exist."""
    import spark_rapids_tpu.parallel.mesh_shuffle as MS
    cpu = jax.devices("cpu")
    assert len(cpu) >= 4

    class FakeDev:
        platform = "tpu"

    def fake_devices(platform=None):
        if platform == "cpu":
            return cpu
        return [FakeDev()]

    monkeypatch.setattr(MS.jax, "devices", fake_devices)
    with pytest.raises(RuntimeError, match="'tpu' has 1"):
        MS.make_mesh(4)
