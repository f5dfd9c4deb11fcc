"""Chip smoke: TPC-H SF1 through the normal SQL path on the accelerator.

    python chip_smoke.py                 # one chip: q6, q1, q12 + LIKE probe
    python chip_smoke.py --queries q6,q1,q3,q12   # with q3 (see DROPPED)
    python chip_smoke.py --chips 4       # ONLY the cross-chip path (ICI shuffle)
    JAX_PLATFORMS=cpu python chip_smoke.py --sf 0.002   # rehearsal: ends non-zero

One process, the entry points a user calls: ``TpuSparkSession`` ->
``session.read.parquet`` -> ``create_or_replace_temp_view`` ->
``session.sql`` -> ``collect()``.  Data (TPC-H SF1 row counts, lineitem =
6,000,000) is generated from ``--seed``; every query's rows are compared
with a pandas reference over the same parquet files that never touches
the device.  The session plans with ``spark.rapids.sql.test.enabled``
(no supported operator may go to the CPU) and with the device-error CPU
fallback OFF, and after each query the script asserts that nothing was
retried, lost, completed on the host or diverted from an enabled Pallas
kernel, and that the second execution compiled nothing.

Every line printed is one JSON object; the LAST line is the verdict
``{"ok": ..., "device": {"platform", "kind", "count"}}``.  Any failed
phase raises (exit code 1, no verdict line).  ``ok`` is true only on a
``tpu`` platform: a CPU rehearsal runs every phase and exits 1 with
``"ok": false`` — a number from the CPU is never a speed.
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys
import tempfile
import time

#: selectable with --queries ("groupby" is the four-chip phase's shuffle
#: query; naming it here runs it on one chip, to find faults there first)
QUERY_NAMES = ("q6", "q1", "q3", "q12", "groupby")
DEFAULT_QUERIES = ("q6", "q1", "q12")

#: Kept at SF1 but out of the default run, with the reason (never shrink
#: the rows to make a query fit).  q3 compiles, runs and agrees with the
#: reference on the chip; what drops it is the clock.
DROPPED = {
    "q3": "cold 764.0 s + warm 349.4 s on one v5e chip (PR 23, builder's "
          "run, results equal to the reference): the query alone takes "
          "1113 s of the 1200 s this script may run; run it with "
          "--queries q6,q1,q3,q12",
}

#: exercises the one Pallas kernel that is on by default (strings: the
#: contains/LIKE scan) at SF1; not a TPC-H query, so it rides after them
LIKE_SQL = ("SELECT l_shipmode, count(*) AS n FROM lineitem "
            "WHERE l_shipmode LIKE '%AI%' "
            "GROUP BY l_shipmode ORDER BY l_shipmode")

#: the two-stage (partial agg -> hash exchange -> merge agg) shuffle query
#: of the four-chip phase
GROUPBY_SQL = ("SELECT l_orderkey, sum(l_quantity) AS qty, count(*) AS n "
               "FROM lineitem GROUP BY l_orderkey")

#: The script must end within 1200 s (compilation included).  The LIKE
#: probe takes ~195 s cold on one v5e chip (PR 23, builder's run), so it
#: runs only if it can start by this many seconds; on a slow host it is
#: skipped, on a printed line, rather than risking the TPC-H verdict.
BUDGET_S = 1200.0
LIKE_START_BY_S = BUDGET_S - 300.0

MUST_BE_ZERO = ("retryCount", "deviceLostCount", "partitionFallbackCount",
                "pallasFallbackCount")

#: the tables the reference needs (the session registers all eight)
TABLES = ("lineitem", "orders", "customer")


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ---------------------------------------------------------------------------
# host-side reference: pandas over the same parquet, no jax
# ---------------------------------------------------------------------------


def load_reference_tables(root: str) -> dict:
    import pyarrow as pa
    import pyarrow.parquet as pq
    out = {}
    for name in TABLES:
        t = pq.read_table(os.path.join(root, name))
        for i, f in enumerate(t.schema):
            if pa.types.is_date32(f.type):  # days since epoch, as the SQL
                t = t.set_column(i, f.name, t.column(i).cast(pa.int32()))
        out[name] = t.to_pandas()
    return out


def _rows(df) -> list:
    return [tuple(r) for r in df.itertuples(index=False, name=None)]


def ref_q6(t):
    li = t["lineitem"]
    m = ((li.l_shipdate >= 8766) & (li.l_shipdate < 9131)
         & (li.l_discount >= 0.05) & (li.l_discount <= 0.07)
         & (li.l_quantity < 24))
    return [(float(li.l_extendedprice[m].sum()),)]


def ref_q1(t):
    li = t["lineitem"]
    li = li[li.l_shipdate <= 10471]
    g = li.groupby(["l_returnflag", "l_linestatus"], sort=True)
    out = g.agg(sum_qty=("l_quantity", "sum"),
                sum_base_price=("l_extendedprice", "sum"),
                avg_qty=("l_quantity", "mean"),
                avg_price=("l_extendedprice", "mean"),
                avg_disc=("l_discount", "mean"),
                count_order=("l_quantity", "size")).reset_index()
    return _rows(out)


def ref_q3(t):
    c = t["customer"]
    c = c[c.c_mktsegment == "BUILDING"][["c_custkey"]]
    o = t["orders"]
    o = o[o.o_orderdate < 9204][
        ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"]]
    li = t["lineitem"]
    li = li[li.l_shipdate > 9204][["l_orderkey", "l_extendedprice"]]
    j = c.merge(o, left_on="c_custkey", right_on="o_custkey") \
         .merge(li, left_on="o_orderkey", right_on="l_orderkey")
    g = j.groupby(["o_orderkey", "o_orderdate", "o_shippriority"],
                  sort=False)["l_extendedprice"].sum().reset_index()
    g = g.rename(columns={"l_extendedprice": "revenue"})
    g = g.sort_values(["revenue", "o_orderdate"],
                      ascending=[False, True], kind="stable").head(10)
    return _rows(g)


def ref_q12(t):
    li = t["lineitem"]
    li = li[li.l_shipmode.isin(["MAIL", "SHIP"])
            & (li.l_commitdate < li.l_receiptdate)
            & (li.l_shipdate < li.l_commitdate)
            & (li.l_receiptdate >= 8766) & (li.l_receiptdate < 9131)]
    j = t["orders"][["o_orderkey", "o_orderpriority"]].merge(
        li[["l_orderkey", "l_shipmode"]],
        left_on="o_orderkey", right_on="l_orderkey")
    high = j.o_orderpriority.isin(["1-URGENT", "2-HIGH"]).astype("int64")
    j = j.assign(high_line_count=high, low_line_count=1 - high)
    g = j.groupby("l_shipmode", sort=True)[
        ["high_line_count", "low_line_count"]].sum().reset_index()
    return _rows(g)


def ref_like(t):
    li = t["lineitem"]
    li = li[li.l_shipmode.str.contains("AI", regex=False)]
    g = li.groupby("l_shipmode", sort=True).size().reset_index(name="n")
    return _rows(g)


def ref_groupby(t):
    g = t["lineitem"].groupby("l_orderkey", sort=True).agg(
        qty=("l_quantity", "sum"), n=("l_quantity", "size")).reset_index()
    return _rows(g)


REFERENCES = {"q6": ref_q6, "q1": ref_q1, "q3": ref_q3, "q12": ref_q12,
              "like": ref_like, "groupby": ref_groupby}


# ---------------------------------------------------------------------------
# comparison (sf1_run's checksum + tolerance; row by row when small)
# ---------------------------------------------------------------------------


def _plain(v):
    if isinstance(v, datetime.date):
        return (v - datetime.date(1970, 1, 1)).days
    if hasattr(v, "item"):
        return v.item()
    return v


def compare(name: str, got_rows, want_rows, ordered: bool) -> dict:
    """Raises AssertionError on disagreement; returns the checksums."""
    from spark_rapids_tpu.benchmarks.sf1_run import (
        _checksum, checksums_agree, values_agree,
    )
    got = [tuple(_plain(v) for v in r) for r in got_rows]
    want = [tuple(_plain(v) for v in r) for r in want_rows]
    gc, wc = _checksum(got), _checksum(want)
    assert checksums_agree(gc, wc), \
        f"{name}: device checksum {gc} != host reference {wc}"
    if len(want) <= 1000:
        if not ordered:
            got, want = sorted(got), sorted(want)
        for i, (g, w) in enumerate(zip(got, want)):
            assert len(g) == len(w) and all(
                values_agree(a, b) for a, b in zip(g, w)), \
                f"{name} row {i}: device {g} != host reference {w}"
    return {"rows": gc[0], "checksum": list(gc[1])}


# ---------------------------------------------------------------------------
# the device side
# ---------------------------------------------------------------------------


def session_conf(chips: int, platform: str) -> dict:
    conf = {
        # planning fails if a supported operator would go to the CPU
        "spark.rapids.sql.test.enabled": True,
        # a device error surfaces; it is never finished on the host
        "spark.rapids.sql.tpu.fallback.onDeviceError": False,
        # armed deadline: a wedged dispatch fails instead of hanging
        "spark.rapids.sql.tpu.partition.timeoutSec": 900.0,
    }
    if chips > 1:
        conf["spark.rapids.shuffle.ici.enabled"] = True
    if platform != "tpu":
        # rehearsal off the chip: enabled kernels run under the Pallas
        # interpreter, so their logic (not the XLA formulation) is what
        # the reference checks and pallasFallbackCount stays 0
        conf["spark.rapids.sql.tpu.pallas.interpret"] = True
    return conf


def run_query(session, name: str, sql: dict, refs,
              check_metrics=None) -> None:
    """Cold run, warm run, reference compare and the no-detour asserts."""
    walls, metrics, rows = [], [], None
    for _ in range(2):
        t0 = time.monotonic()
        rows = session.sql(sql[name]).collect()
        walls.append(time.monotonic() - t0)
        metrics.append(dict(session.last_metrics))
    cold, warm = metrics
    for m in metrics:
        for key in MUST_BE_ZERO:
            assert m[key] == 0, f"{name}: {key} = {m[key]} (must be 0)"
    assert warm["compileCount"] == 0, \
        f"{name}: second execution compiled {warm['compileCount']} programs"
    if check_metrics is not None:
        check_metrics(name, warm)
    # every query but the bare group-by has an ORDER BY
    agreed = compare(name, rows, REFERENCES[name](refs),
                     ordered=name != "groupby")
    hist = session.query_history()
    pallas = sorted({ev.name for prof in hist[-2:] for ev in prof.events
                     if ev.site == "pallas"})
    emit("query", query=name, cold_s=walls[0], warm_s=walls[1],
         compileCount=cold["compileCount"],
         compileWallNs=cold["compileWallNs"],
         backendCompileNs=cold["backendCompileNs"],
         dispatchCount=warm["dispatchCount"],
         compiledShapes=warm["compiledShapes"],
         warmCompileCount=warm["compileCount"],
         **{k: warm[k] for k in MUST_BE_ZERO},
         pallasKernels=pallas, equal=True, **agreed)


def check_residency(session, runtime) -> dict:
    """Every cached input batch sits in the device tier with every array
    on the runtime's device (the cache is what the queries read)."""
    import jax
    from spark_rapids_tpu.benchmarks.sf1_run import TABLE_NAMES
    assert session.table("lineitem").plan.holder.is_materialized
    n_batches = n_arrays = nbytes = 0
    for name in TABLE_NAMES:
        holder = session.table(name).plan.holder
        if not holder.is_materialized:  # no query read this table
            continue
        for part in holder.partitions:
            for h in part:
                assert h.tier == h.TIER_DEVICE, f"{name}: tier {h.tier}"
                n_batches += 1
                for leaf in jax.tree_util.tree_leaves(h.get()):
                    assert leaf.devices() == {runtime.device}, \
                        f"{name}: array on {leaf.devices()}, " \
                        f"runtime device {runtime.device}"
                    n_arrays += 1
                    nbytes += leaf.nbytes
    return {"batches": n_batches, "arrays": n_arrays, "bytes": nbytes}


def one_chip(session, refs, queries, sql, t_start: float) -> str:
    """Runs the one-chip phases; returns the device runtime's platform."""
    from spark_rapids_tpu.runtime.device import DeviceRuntime
    for q in queries:
        run_query(session, q, sql, refs)
    elapsed = time.monotonic() - t_start
    if elapsed <= LIKE_START_BY_S:
        run_query(session, "like", sql, refs)
    else:
        emit("skipped", query="like", elapsed_s=elapsed,
             reason=f"not started after {LIKE_START_BY_S:g} s of the "
                    f"{BUDGET_S:g} s this script may run")
    runtime = DeviceRuntime.get(session.conf)
    resident = check_residency(session, runtime)
    stats = runtime.device.memory_stats() or {}
    emit("device", runtimePlatform=runtime.platform,
         runtimeDevice=str(runtime.device), cachedInputs=resident,
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_limit=stats.get("bytes_limit"))
    return runtime.platform


class _MeshProgramRecorder:
    """Captures each fused mesh-stage program with its first arguments,
    so the script can read the COMPILED text for the collective (the
    lower/compile below re-reads the just-written persistent cache)."""

    def __init__(self, mesh_spmd_module):
        self.programs = []
        real = mesh_spmd_module.instrumented_jit

        def recording(fn, **kw):
            program = real(fn, **kw)
            if not program.label.startswith("meshStage:"):
                return program  # a helper jit, not a fused stage

            def call(*args):
                if not any(p is program for p, _ in self.programs):
                    self.programs.append((program, args))
                return program(*args)
            call.jitted = program.jitted
            call.label = program.label
            return call

        mesh_spmd_module.instrumented_jit = recording

    def check(self) -> dict:
        import jax
        assert self.programs, "no fused mesh-stage program was built"
        with_a2a, shard_devices = 0, set()
        for program, args in self.programs:
            text = program.jitted.lower(*args).compile().as_text()
            with_a2a += "all-to-all" in text
            for leaf in jax.tree_util.tree_leaves(args):
                shard_devices |= {s.device for s in leaf.addressable_shards}
        assert with_a2a >= 1, "no all-to-all in any compiled mesh program"
        assert len(shard_devices) == 4, \
            f"input shards on {len(shard_devices)} device(s), need 4"
        return {"meshPrograms": len(self.programs),
                "programsWithAllToAll": with_a2a,
                "inputShardDevices": sorted(str(d) for d in shard_devices)}


def four_chips(session, refs, sql) -> str:
    """Runs ONLY the cross-chip path; returns the mesh's platform."""
    import jax
    from spark_rapids_tpu.parallel import mesh_spmd
    devices = jax.devices()
    assert len(devices) == 4 and len(set(devices)) == 4, \
        f"--chips 4 needs four devices, jax sees {len(devices)}"
    mesh = session._shuffle_mesh()
    assert mesh is not None and len(set(mesh.devices.flat)) == 4
    platforms = {d.platform for d in mesh.devices.flat}
    assert platforms == {devices[0].platform}, platforms
    recorder = _MeshProgramRecorder(mesh_spmd)

    fused = 0

    def mesh_metrics(name, m):
        nonlocal fused
        # exchanges that ran as their own all_to_all program (the joins'
        # shuffles) vs. fused INTO a whole-stage program (the group-by's)
        collective = sum(ms.get("meshExchanges", 0) for ms in m.values()
                         if isinstance(ms, dict))
        assert m["meshBackend"] == devices[0].platform, m["meshBackend"]
        assert m["meshFallbacks"] == 0, f"{name}: meshFallbacks"
        assert m["meshBoundariesFused"] + collective >= 1, \
            f"{name}: no exchange went over the mesh"
        fused += m["meshBoundariesFused"]
        emit("mesh", query=name, meshBackend=m["meshBackend"],
             meshFallbacks=m["meshFallbacks"],
             meshBoundariesFused=m["meshBoundariesFused"],
             meshJoinsFused=m["meshJoinsFused"],
             meshProgramDispatches=m["meshProgramDispatches"],
             meshExchanges=collective, shuffleSyncs=m["shuffleSyncs"])

    # the group-by first: it is the query whose exchange fuses into ONE
    # shard_map program, and the cheaper of the two
    for q in ("groupby", "q3"):
        run_query(session, q, sql, refs, check_metrics=mesh_metrics)
    assert fused >= 1, "no exchange fused into a whole-stage mesh program"
    emit("mesh_programs", meshDevices=[str(d) for d in mesh.devices.flat],
         **recorder.check())
    return platforms.pop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (1.0 = 6,000,000 lineitem rows)")
    ap.add_argument("--seed", type=int, default=20260928)
    ap.add_argument("--queries", default=",".join(DEFAULT_QUERIES),
                    help="comma-separated subset of " + ",".join(QUERY_NAMES))
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run ONLY the cross-chip (ICI shuffle) phase")
    args = ap.parse_args(argv)
    queries = [q for q in args.queries.split(",") if q]
    assert set(queries) <= set(QUERY_NAMES), queries

    t_start = time.monotonic()
    import jax

    import spark_rapids_tpu
    from spark_rapids_tpu import native_rt
    from spark_rapids_tpu.benchmarks import sf1_run
    from spark_rapids_tpu.benchmarks.tpch_like import QUERIES
    from spark_rapids_tpu.utils import compile_registry as CR

    cache_dir = CR.enable_persistent_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    emit("env", jax=jax.__version__, engine=spark_rapids_tpu.__version__,
         device=device, chips=args.chips, sf=args.sf, seed=args.seed,
         compileCacheDir=cache_dir,
         nativeRuntimeLoaded=native_rt.get_lib() is not None)

    if args.chips == 1:
        for q, why in DROPPED.items():
            if q not in queries:
                emit("dropped", query=q, reason=why)

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        t0 = time.monotonic()
        # one file (= one scan partition) per chip
        sf1_run.generate_dataset(args.sf, num_partitions=args.chips,
                                 seed=args.seed, root=root)
        refs = load_reference_tables(root)
        emit("datagen", seconds=time.monotonic() - t0,
             rows={n: len(df) for n, df in refs.items()})
        session = sf1_run._session(
            True, root,
            extra_conf=session_conf(args.chips, device["platform"]))
        sql = {**QUERIES, "like": LIKE_SQL, "groupby": GROUPBY_SQL}
        if args.chips == 4:
            ran_on = four_chips(session, refs, sql)
        else:
            ran_on = one_chip(session, refs, queries, sql, t_start)

    emit("cache", **CR.persistent_cache_stats())
    # success only if jax's default device AND the engine's runtime (or
    # shuffle mesh) are the tpu, with as many chips as asked for
    ok = (device["platform"] == "tpu" and ran_on == "tpu"
          and device["count"] == args.chips)
    emit("total", seconds=time.monotonic() - t_start)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
