"""File-backed TPC-H SF1 run: generate parquet tables once (lineitem = 6M
rows — true TPC-H SF1 row counts), run every TPC-H-like query on the TPU
engine AND the CPU engine from the files, verify agreement, and emit a
timing table (the BenchUtils.runBench role,
integration_tests/.../common/BenchUtils.scala:109-240).

    python -m spark_rapids_tpu.benchmarks.sf1_run [--sf 1.0] [--out sf1_report.md]

Correctness: row counts must match exactly; numeric columns are
checksummed (sums rounded to 2dp) and compared within float-agg
tolerance.  The parquet dataset is cached under /tmp keyed by scale.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional

TABLE_NAMES = ("lineitem", "orders", "customer", "supplier", "nation",
               "part", "partsupp", "region")

# TPC-H SF1 row counts; the synthetic generator's own `sf` knob is
# rows = sf * 60_000 for lineitem, so generator_sf = 100 * true_sf
_GEN_PER_TRUE_SF = 100


def _dataset_dir(true_sf: float) -> str:
    import tempfile
    return os.path.join(tempfile.gettempdir(),
                        f"rapids_tpu_tpch_sf{true_sf:g}")


def generate_dataset(true_sf: float, num_partitions: int = 1,
                     seed: Optional[int] = None,
                     root: Optional[str] = None) -> str:
    """Write the TPC-H-like tables as parquet once; returns the dir.
    The completion marker records a schema fingerprint, so a schema,
    scale or seed change regenerates instead of reusing stale files (a
    pure value-distribution change with the same columns still needs a
    manual directory wipe).  ``seed`` offsets every table generator's
    RandomState (None keeps the generators' own defaults); ``root``
    places the dataset (default: a per-scale dir under the tempdir).
    Each table is written as ``num_partitions`` files of contiguous rows
    (tiny tables: one file per row at most), and a scan yields one
    partition per file."""
    from spark_rapids_tpu.batch import HostBatch
    from spark_rapids_tpu.benchmarks import datagen
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.dataframe import DataFrame
    from spark_rapids_tpu.plan.logical import InMemoryScan
    from spark_rapids_tpu.session import TpuSparkSession

    root = root or _dataset_dir(true_sf)
    marker = os.path.join(root, "_COMPLETE")
    gen_sf = true_sf * _GEN_PER_TRUE_SF

    def seeded(gen, i):
        if seed is None:
            return gen
        return lambda sf: gen(sf, seed=seed + i)

    tables = [
        ("lineitem", seeded(datagen.gen_lineitem, 0)),
        ("orders", seeded(datagen.gen_orders, 1)),
        ("customer", seeded(datagen.gen_customer, 2)),
        ("supplier", seeded(datagen.gen_supplier, 3)),
        ("nation", lambda _sf: datagen.gen_nation()),
        ("part", seeded(datagen.gen_part, 4)),
        ("partsupp", seeded(datagen.gen_partsupp, 5)),
        ("region", lambda _sf: datagen.gen_region()),
    ]
    # cheap fingerprint: every table's column names + dtypes (from a
    # tiny-scale probe of the same generators) + the scale
    cols = {n: sorted((k, str(dt)) for k, (dt, _) in g(0.001).items())
            for n, g in tables}
    fingerprint = json.dumps({"cols": cols, "gen_sf": gen_sf, "seed": seed,
                              "files": num_partitions}, sort_keys=True)
    if os.path.exists(marker) and open(marker).read() == fingerprint:
        return root
    s = TpuSparkSession(RapidsConf({"spark.rapids.sql.enabled": False}))
    for name, gen in tables:
        # contiguous row ranges, one host batch (= one written file) each
        whole = HostBatch.from_pydict(gen(gen_sf))
        step = -(-whole.num_rows // num_partitions)
        chunks = [whole.slice(lo, step)
                  for lo in range(0, whole.num_rows, step)]
        df = DataFrame(InMemoryScan(chunks, whole.schema, len(chunks)), s)
        df.write_parquet(os.path.join(root, name), mode="overwrite")
        print(f"wrote {name}", file=sys.stderr, flush=True)
    open(marker, "w").write(fingerprint)
    return root


def _session(tpu: bool, root: str, extra_conf: Optional[dict] = None):
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.session import TpuSparkSession
    s = TpuSparkSession(RapidsConf({
        "spark.rapids.sql.enabled": tpu,
        "spark.sql.shuffle.partitions": 4,
        "spark.rapids.sql.variableFloatAgg.enabled": True,
        **(extra_conf or {}),
    }))
    for name in TABLE_NAMES:
        df = s.read.parquet(os.path.join(root, name))
        # BOTH engines cache inputs after the first read so the timing
        # table compares engine steady-state, not cache-vs-reread
        df = df.cache()
        df.create_or_replace_temp_view(name)
    return s


def _checksum(rows):
    """(row count, rounded numeric sums) — agreement proxy for large
    results where a full row-by-row compare would dominate the run."""
    if not rows:
        return (0, ())
    sums = []
    for j in range(len(rows[0])):
        v = [r[j] for r in rows if r[j] is not None]
        if v and isinstance(v[0], (int, float)) and \
                not isinstance(v[0], bool):
            sums.append(round(float(sum(v)), 2))
    return (len(rows), tuple(sums))


def values_agree(a, b) -> bool:
    """Float-agg tolerance for one value pair (non-floats: equality)."""
    if isinstance(a, float) or isinstance(b, float):
        if a is None or b is None:
            return a is b
        return abs(a - b) <= 1e-4 * max(1.0, abs(a), abs(b))
    return a == b


def checksums_agree(tc, cc) -> bool:
    """Two :func:`_checksum` results agree: same row count, and every
    numeric column sum within the float-agg tolerance."""
    return tc[0] == cc[0] and len(tc[1]) == len(cc[1]) and all(
        values_agree(a, b) for a, b in zip(tc[1], cc[1]))


def run(true_sf: float, out_path: str) -> dict:
    from spark_rapids_tpu.benchmarks.bench_utils import run_bench
    from spark_rapids_tpu.benchmarks.tpch_like import QUERIES

    root = generate_dataset(true_sf)
    results = {}
    sessions = {"tpu": _session(True, root), "cpu": _session(False, root)}
    # Query-outer so the report can be (re)written after every query: a
    # timeout partway through a long run still leaves a usable table.
    # Cost of the interleave: both sessions' input caches stay live for
    # the whole run (TPU's on device — spillable, budget-enforced — and
    # CPU's in host memory) instead of one engine at a time.
    for qname in sorted(QUERIES):
        sql = QUERIES[qname]
        for label, s in sessions.items():
            rep = run_bench(s, qname, lambda: s.sql(sql),
                            iterations=1, warmups=1, keep_rows=True)
            r = results.setdefault(qname, {})
            r[f"{label}_s"] = round(rep["best_s"], 3)
            r[f"{label}_check"] = _checksum(rep["rows"])
            print(f"{label} {qname}: {r[f'{label}_s']}s "
                  f"rows={r[f'{label}_check'][0]}", flush=True)
        _write_report(true_sf, results, out_path)

    rep = _write_report(true_sf, results, out_path)
    print(f"\nwrote {out_path}; all_agree={rep['all_agree']}", flush=True)
    return rep


def _write_report(true_sf: float, results: dict, out_path: str) -> dict:
    lines = [
        f"# TPC-H-like SF{true_sf:g} file-backed timings",
        "",
        "Parquet-backed run (lineitem = "
        f"{int(true_sf * 6_000_000):,} rows); TPU inputs device-cached "
        "after the first read (spillable).  Checksums = (row count, "
        "rounded numeric column sums); both engines must agree.",
        "",
        "| query | tpu s | cpu s | speedup | rows | agree |",
        "|---|---|---|---|---|---|",
    ]
    all_ok = True
    for qname in sorted(results):
        r = results[qname]
        if "tpu_check" not in r or "cpu_check" not in r:
            continue  # mid-query interruption
        tc, cc = r["tpu_check"], r["cpu_check"]
        ok = checksums_agree(tc, cc)
        all_ok = all_ok and ok
        sp = r["cpu_s"] / r["tpu_s"] if r["tpu_s"] else float("inf")
        lines.append(f"| {qname} | {r['tpu_s']} | {r['cpu_s']} | "
                     f"{sp:.2f}x | {tc[0]} | {'yes' if ok else 'NO'} |")
        r["speedup"] = round(sp, 3)
        r["agree"] = ok
    done = [r for r in results.values() if "agree" in r]
    tot_t = sum(r["tpu_s"] for r in done)
    tot_c = sum(r["cpu_s"] for r in done)
    ratio = f"{tot_c / tot_t:.2f}x" if tot_t > 0 else "n/a"
    lines += ["",
              f"Total steady-state over {len(done)} queries: "
              f"tpu {tot_t:.2f}s, cpu {tot_c:.2f}s ({ratio})", ""]
    with open(out_path, "w") as f:
        f.write("\n".join(lines))
    return {"all_agree": all_ok, "queries": results,
            "total_tpu_s": round(tot_t, 3), "total_cpu_s": round(tot_c, 3)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=1.0)
    ap.add_argument("--out", default="sf1_report.md")
    args = ap.parse_args(argv)
    rep = run(args.sf, args.out)
    print(json.dumps({"sf": args.sf, "all_agree": rep["all_agree"],
                      "total_tpu_s": rep["total_tpu_s"],
                      "total_cpu_s": rep["total_cpu_s"]}))
    return 0 if rep["all_agree"] else 1


if __name__ == "__main__":
    import sys
    sys.exit(main())
