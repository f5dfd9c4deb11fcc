#!/usr/bin/env bash
# CI pipeline (SURVEY.md section 4.6 analogue of the reference's
# jenkins/ + github-actions workflows): unit + integration tests on the
# virtual 8-device CPU mesh, entry-point compile checks, multichip dryrun.
#
# Usage: ci/run_ci.sh [quick|full]
#   quick: kernel + expression + e2e suites only
#   full (default): whole suite + graft entry + 8-device dryrun
set -euo pipefail
cd "$(dirname "$0")/.."

MODE="${1:-full}"

echo "== python/jax versions"
python - << 'PY'
import sys, jax
print(sys.version.split()[0], "jax", jax.__version__)
PY

# Per-test wall clock bound (tests/conftest.py SIGALRM hook): a wedged
# test (e.g. a leaked read-ahead worker blocking the next suite) FAILS
# with a TimeoutError + traceback instead of hanging the whole run.
export PYTEST_PER_TEST_TIMEOUT="${PYTEST_PER_TEST_TIMEOUT:-120}"

echo "== docs/configs.md freshness"
python ci/gen_configs_doc.py --check

# Static analysis gate BEFORE any test runs: rapidslint is runtime-free
# (plain ast, no jax import) so the whole tree checks in ~2s — a lint
# regression fails the build without paying for a suite run first.
# Budget: must stay under 15s.  See docs/static_analysis.md.
echo "== rapidslint gate"
python tools/rapidslint.py --check

# Structural plan verification for every query the suite executes:
# schema/transition consistency, donation-mask provenance, semaphore
# balance (spark_rapids_tpu/analysis/plan_verify.py via tests/conftest.py).
export RAPIDS_PLAN_VERIFY=1

if [ "$MODE" = "quick" ]; then
  python -m pytest tests/test_kernels_layout.py tests/test_kernels_join.py \
      tests/test_exprs.py tests/test_e2e_basic.py -q
  exit 0
fi

echo "== full test suite"
python -m pytest tests/ -q

echo "== serve smoke: rapidsserve with 2 weighted tenants and a per-query"
echo "   dispatch:oom@2 fault — every served query must recover with"
echo "   correct rows, latencies parseable, per-tenant counts consistent"
python - << 'PY'
import json
import subprocess
import sys

out = subprocess.run(
    [sys.executable, "tools/rapidsserve.py", "--tenants", "a:2,b:1",
     "--queries", "12", "--rows", "256", "--concurrency", "2",
     "--fault", "dispatch:oom@2"],
    capture_output=True, text=True, timeout=600)
assert out.returncode == 0, f"rapidsserve failed:\n{out.stderr[-3000:]}"
lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
assert lines, f"no JSON line in rapidsserve output:\n{out.stdout[-2000:]}"
j = json.loads(lines[-1])
assert j["serve_parity"] is True, j
assert j["serve_failed"] == 0, j
assert j["serve_faults_injected"] >= 1, j
assert float(j["serve_p99_ms"]) > 0, j
assert float(j["serve_p50_ms"]) <= float(j["serve_p99_ms"]), j
tenants = j["serve_tenants"]
assert set(tenants) == {"a", "b"}, tenants
assert tenants["a"]["weight"] == 2.0 and tenants["b"]["weight"] == 1.0, tenants
for name, t in tenants.items():
    assert t["submitted"] == t["completed"] + t["failed"], (name, t)
assert sum(t["completed"] for t in tenants.values()) == j["serve_completed"], j
print("serve smoke ok:", {k: j[k] for k in (
    "serve_queries_per_sec", "serve_p50_ms", "serve_p99_ms",
    "serve_batched_queries", "serve_faults_injected", "serve_retries",
    "serve_second_session_compiles")})
PY

echo "== front-door smoke: rapidsserve --server subprocess, 2 weighted"
echo "   tenants x concurrent socket clients — row parity vs in-process,"
echo "   second-client compileCount == 0, warm repeat served from the"
echo "   result cache, doomed deadline shed without executing, clean"
echo "   drain with held_depth == 0"
python - << 'PY'
import json
import select
import shutil
import signal
import subprocess
import sys
import tempfile
import threading

from spark_rapids_tpu.serve.bench import frontend_demo_session
from spark_rapids_tpu.serve.scheduler import DeadlineExceeded
from spark_rapids_tpu.serve.protocol import FrontDoorClient

hist_dir = tempfile.mkdtemp(prefix="rapids_frontdoor_smoke_")
proc = subprocess.Popen(
    [sys.executable, "tools/rapidsserve.py", "--server", "--port", "0",
     "--tenants", "a:2,b:1", "--concurrency", "2", "--rows", "512",
     "--history-dir", hist_dir],
    stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
try:
    # the banner is the FIRST stdout line; session build takes a while
    ready, _, _ = select.select([proc.stdout], [], [], 300)
    assert ready, "server printed no banner within 300s"
    banner = json.loads(proc.stdout.readline())
    host, port, sqls = banner["host"], banner["port"], banner["sqls"]

    def rows_of(batch):
        cols = batch.to_pydict()
        return sorted(zip(*[cols[n] for n in batch.schema.names]))

    # in-process oracle: same deterministic demo view, same SQL texts
    oracle = frontend_demo_session({"a": 2.0, "b": 1.0}, rows=512)
    want = {sql: rows_of(oracle.execute(oracle.sql(sql).plan))
            for sql in sqls}

    # warm passes bypassing the result cache: compile once AND seed the
    # admission predictor's history baseline (minRuns real executions)
    with FrontDoorClient(host, port) as c:
        for _ in range(3):
            for sql in sqls:
                batch, _m = c.submit_sql(sql, tenant="a", cache=False)
                assert rows_of(batch) == want[sql], sql

    # concurrent storm: one socket client per weighted tenant
    errs = []
    def storm(tenant):
        try:
            with FrontDoorClient(host, port) as c:
                for sql in sqls:
                    batch, _m = c.submit_sql(sql, tenant=tenant)
                    assert rows_of(batch) == want[sql], (tenant, sql)
        except Exception as e:  # surfaced below; threads must not die silently
            errs.append((tenant, repr(e)))
    threads = [threading.Thread(target=storm, args=(t,)) for t in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errs, errs

    with FrontDoorClient(host, port) as c:
        # a brand-new connection is a "second client": the prepared-
        # statement + shared plan caches must hand it warm executables
        batch, m = c.submit_sql(sqls[0], tenant="b", cache=False)
        assert rows_of(batch) == want[sqls[0]]
        assert m.get("compileCount", 0) == 0, m
        # warm repeat: served from the result cache, no dispatch at all
        batch, m = c.submit_sql(sqls[0], tenant="b")
        assert rows_of(batch) == want[sqls[0]]
        assert m.get("resultCacheHits", 0) > 0, m
        assert m.get("dispatchCount", 0) == 0, m
        # doomed deadline: the admission predictor sheds it fail-fast
        try:
            c.submit_sql(sqls[1], tenant="a", deadline_sec=1e-6,
                         cache=False)
            raise AssertionError("doomed deadline was not shed")
        except DeadlineExceeded:
            pass
        st = c.stats()
        assert st["frontend"]["admission_shed"] >= 1, st["frontend"]
        assert st["frontend"]["result_cache_hits"] >= 1, st["frontend"]
        assert st["scheduler"]["tenants"]["a"]["completed"] >= 1, st
        assert st["scheduler"]["tenants"]["b"]["completed"] >= 1, st
        d = c.drain()
        assert d["drained"] is True, d
        assert d["held_depth"] == 0, d
        print("front-door smoke ok:", {
            "port": port, "queries": 3 * len(sqls) + 2 * len(sqls) + 2,
            "admission_shed": st["frontend"]["admission_shed"],
            "result_cache_hits": st["frontend"]["result_cache_hits"]})
finally:
    proc.send_signal(signal.SIGTERM)
    try:
        proc.wait(30)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait(30)
    shutil.rmtree(hist_dir, ignore_errors=True)
assert proc.returncode == 0, (proc.returncode, proc.stderr.read()[-3000:])
PY

echo "== obs smoke: event log -> rapidsprof report"
python - << 'PY'
import os
import subprocess
import sys
import tempfile

from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.session import TpuSparkSession

log_dir = tempfile.mkdtemp(prefix="rapids_obs_smoke_")
s = TpuSparkSession(RapidsConf({
    "spark.rapids.sql.enabled": True,
    "spark.rapids.sql.tpu.obs.eventLogDir": log_dir,
}))
df = s.create_dataframe(
    {"k": [i % 7 for i in range(4096)], "v": list(range(4096))},
    num_partitions=2)
df.group_by("k").sum("v").order_by("k").collect()
assert s.last_metrics["obsEventCount"] > 0, s.last_metrics
assert s.query_history(), "no profile recorded"
logs = [os.path.join(log_dir, f) for f in os.listdir(log_dir)]
assert len(logs) == 1, logs

out = subprocess.run(
    [sys.executable, "tools/rapidsprof.py", logs[0]],
    capture_output=True, text=True, timeout=300)
assert out.returncode == 0, f"rapidsprof failed:\n{out.stderr[-2000:]}"
assert "Exec" in out.stdout, f"report names no operator:\n{out.stdout}"
print("obs smoke ok:", {
    "events": s.last_metrics["obsEventCount"],
    "dropped": s.last_metrics["obsEventsDropped"]})
PY

echo "== telemetry smoke: flushed JSONL -> rapidstop --once renders >=1"
echo "   interval with nonzero dispatch wall, Prometheus export parses"
python - << 'PY'
import os
import subprocess
import sys
import tempfile
import time

from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.session import TpuSparkSession

log_dir = tempfile.mkdtemp(prefix="rapids_telemetry_smoke_")
s = TpuSparkSession(RapidsConf({
    "spark.rapids.sql.enabled": True,
    "spark.rapids.sql.tpu.obs.eventLogDir": log_dir,
    "spark.rapids.sql.tpu.obs.telemetry.intervalMs": 25,
}))
df = s.create_dataframe(
    {"k": [i % 7 for i in range(8192)], "v": list(range(8192))},
    num_partitions=2)
q = df.group_by("k").sum("v")
q.collect()
time.sleep(0.06)  # let the open interval's window pass
q.collect()       # the flush at query end writes the completed intervals
assert s.last_metrics["telemetryIntervals"] >= 1, s.last_metrics
tpath = os.path.join(log_dir, f"telemetry-{os.getpid()}.jsonl")
assert os.path.exists(tpath), os.listdir(log_dir)

out = subprocess.run(
    [sys.executable, "tools/rapidstop.py", tpath, "--once"],
    capture_output=True, text=True, timeout=300)
assert out.returncode == 0, f"rapidstop failed:\n{out.stdout}{out.stderr}"
assert "telemetry:" in out.stdout, out.stdout
assert "enqueue" in out.stdout, out.stdout

prom = subprocess.run(
    [sys.executable, "tools/rapidstop.py", tpath, "--prom"],
    capture_output=True, text=True, timeout=300)
assert prom.returncode == 0, prom.stderr
wall = 0
for line in prom.stdout.strip().splitlines():
    if line.startswith("#"):
        assert line.split()[1] == "TYPE", line
        continue
    name, val = line.rsplit(" ", 1)
    float(val)  # every sample parses
    if name == 'rapids_site_wall_ns_total{site="enqueue"}':
        wall = float(val)
assert wall > 0, f"no enqueue wall in Prometheus export:\n{prom.stdout}"
print("telemetry smoke ok:", {
    "intervals": s.last_metrics["telemetryIntervals"],
    "enqueue_wall_ms": round(wall / 1e6, 2)})
PY

echo "== sentinel smoke: injected dispatch:slow regression must flag"
echo "   regressionAlerts > 0 against a clean baseline; a clean repeat"
echo "   must flag none; aggregates visible via rapidshist --json"
python - << 'PY'
import json
import shutil
import subprocess
import sys
import tempfile

from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.session import TpuSparkSession

hist_dir = tempfile.mkdtemp(prefix="rapids_sentinel_smoke_")
try:
    s = TpuSparkSession(RapidsConf({
        "spark.rapids.sql.enabled": True,
        "spark.rapids.sql.tpu.history.dir": hist_dir,
        # re-execute warm repeats so the injected fault actually fires,
        # and keep the plan fingerprint identical run over run
        "spark.rapids.sql.tpu.history.fragments.enabled": False,
        "spark.rapids.sql.tpu.history.seed.enabled": False,
        # preset so toggling the spec off restores this exact conf
        # state and the clean repeat reuses the cached plan (an absent->
        # empty transition would replan and recompile, inflating wall)
        "spark.rapids.sql.tpu.faults.spec": "",
    }))
    df = s.create_dataframe(
        {"k": [i % 7 for i in range(4096)], "v": list(range(4096))},
        num_partitions=2)
    q = df.group_by("k").sum("v")
    for _ in range(4):
        q.collect()
        assert s.last_metrics["regressionAlerts"] == 0, s.last_metrics
    # faults. confs are excluded from the conf signature: the slow run
    # is judged against the clean baseline it just built
    s.conf.set("spark.rapids.sql.tpu.faults.spec",
               "dispatch:slow=500ms@1+")
    q.collect()
    m = dict(s.last_metrics)
    assert m["faultsInjected"] >= 1, m
    assert m["regressionAlerts"] > 0, m
    s.conf.set("spark.rapids.sql.tpu.faults.spec", "")
    q.collect()
    assert s.last_metrics["regressionAlerts"] == 0, s.last_metrics

    out = subprocess.run(
        [sys.executable, "tools/rapidshist.py", hist_dir, "--json"],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    recs = json.loads(out.stdout)
    aggs = [r["agg"] for r in recs.values() if r.get("agg")]
    assert aggs and aggs[0]["n"] >= 4, recs
    assert "median" in aggs[0]["keys"]["wall_ns"], aggs[0]
    print("sentinel smoke ok:", {
        "alerts": m["regressionAlerts"],
        "baseline_runs": aggs[0]["n"],
        "wall_median_ms": round(
            aggs[0]["keys"]["wall_ns"]["median"] / 1e6, 2)})
finally:
    shutil.rmtree(hist_dir, ignore_errors=True)
PY

echo "== history smoke: same aggregation twice against a fresh history"
echo "   dir — the repeat must serve from the fragment cache (hits > 0,"
echo "   zero compiles, zero dispatches) with bit-identical rows, and the"
echo "   statistics store must be inspectable with rapidshist"
python - << 'PY'
import os
import shutil
import subprocess
import sys
import tempfile

from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.history.fragcache import fragment_cache
from spark_rapids_tpu.session import TpuSparkSession

hist_dir = tempfile.mkdtemp(prefix="rapids_hist_smoke_")
try:
    fragment_cache().clear()
    s = TpuSparkSession(RapidsConf({
        "spark.rapids.sql.enabled": True,
        "spark.rapids.sql.tpu.history.dir": hist_dir,
    }))
    df = s.create_dataframe(
        {"k": [i % 7 for i in range(4096)], "v": list(range(4096))},
        num_partitions=2)
    q = df.group_by("k").sum("v")
    want = sorted(q.collect())
    m1 = dict(s.last_metrics)
    got = sorted(q.collect())
    m2 = dict(s.last_metrics)
    assert got == want, f"warm run diverged:\n{got[:5]}\n{want[:5]}"
    assert m2["fragmentCacheHits"] > 0, m2
    assert m2["compileCount"] == 0, m2
    assert m2["dispatchCount"] == 0, m2
    assert os.path.exists(os.path.join(hist_dir, "stats.jsonl")), \
        os.listdir(hist_dir)
    out = subprocess.run(
        [sys.executable, "tools/rapidshist.py", hist_dir],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, f"rapidshist failed:\n{out.stderr[-2000:]}"
    assert "fingerprint" in out.stdout, out.stdout
    print("history smoke ok:", {
        "cold_compiles": m1["compileCount"],
        "warm_hits": m2["fragmentCacheHits"],
        "warm_compiles": m2["compileCount"],
        "warm_dispatches": m2["dispatchCount"],
        "store_queries": m1["statsStoreQueries"]})
finally:
    fragment_cache().clear()
    shutil.rmtree(hist_dir, ignore_errors=True)
PY

echo "== fault-injection smoke: dispatch:oom@2 must spill-retry and still"
echo "   produce correct results with retryCount > 0"
python - << 'PY'
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.session import TpuSparkSession

def make(s):
    df = s.create_dataframe(
        {"k": [i % 7 for i in range(4096)],
         "v": list(range(4096))}, num_partitions=2)
    return df.group_by("k").sum("v")

clean = TpuSparkSession(RapidsConf({"spark.rapids.sql.enabled": True}))
want = sorted(make(clean).collect())

s = TpuSparkSession(RapidsConf({
    "spark.rapids.sql.enabled": True,
    "spark.rapids.sql.tpu.faults.spec": "dispatch:oom@2",
}))
got = sorted(make(s).collect())
assert got == want, f"faulted run diverged:\n{got[:5]}\n{want[:5]}"
m = s.last_metrics
assert m["retryCount"] > 0, m
assert m["faultsInjected"] >= 1, m
print("fault smoke ok:", {k: m[k] for k in (
    "retryCount", "faultsInjected", "deviceLostCount",
    "partitionFallbackCount", "backoffWallNs")})
PY

echo "== fault-injection smoke: exchange:oom@2 must replay the coalesced"
echo "   shuffle split through the retry ladder (split v2 path)"
python - << 'PY'
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.session import TpuSparkSession

def make(s):
    df = s.create_dataframe(
        {"k": [i % 7 for i in range(4096)],
         "v": list(range(4096))}, num_partitions=2)
    # two non-collapsed exchanges (hash groupby + range order_by): the
    # @2 rule fires on the SECOND exchange-site call of the query
    return df.group_by("k").sum("v").order_by("k")

clean = TpuSparkSession(RapidsConf({
    "spark.rapids.sql.enabled": True,
    "spark.rapids.sql.tpu.exchange.collapseLocal": False,
}))
want = make(clean).collect()

s = TpuSparkSession(RapidsConf({
    "spark.rapids.sql.enabled": True,
    "spark.rapids.sql.tpu.exchange.collapseLocal": False,
    "spark.rapids.sql.tpu.faults.spec": "exchange:oom@2",
}))
got = make(s).collect()
assert got == want, f"faulted run diverged:\n{got[:5]}\n{want[:5]}"
m = s.last_metrics
assert m["retryCount"] > 0, m
assert m["faultsInjected"] >= 1, m
assert m["shuffleSyncs"] >= 1, m
print("exchange fault smoke ok:", {k: m[k] for k in (
    "retryCount", "faultsInjected", "shuffleSyncs",
    "shuffleSplitDispatches", "shufflePieces")})
PY

echo "== fault-injection smoke: mesh:device_lost@1 through a FUSED mesh"
echo "   join program — the lost device replays the whole fused stage"
echo "   bit-identically with retryCount > 0 and held_depth == 0"
python - << 'PY'
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.session import TpuSparkSession

def make(s):
    left = s.create_dataframe(
        {"k": [i % 13 for i in range(4096)],
         "v": list(range(4096))}, num_partitions=4)
    right = s.create_dataframe(
        {"k": list(range(13)), "w": [i * 7 for i in range(13)]},
        num_partitions=2)
    return left.join(right, on="k", how="inner")

BASE = {
    "spark.rapids.sql.enabled": True,
    "spark.rapids.shuffle.ici.enabled": True,
    # threshold 0 keeps the shuffled-hash strategy: the join fuses INTO
    # the mesh shard_map program (mesh.spmd.enabled is default-on)
    "spark.sql.autoBroadcastJoinThreshold": 0,
}
clean = TpuSparkSession(RapidsConf(BASE))
want = sorted(map(str, make(clean).collect()))
assert clean.last_metrics["meshJoinsFused"] >= 1, clean.last_metrics

s = TpuSparkSession(RapidsConf({
    **BASE, "spark.rapids.sql.tpu.faults.spec": "mesh:device_lost@1"}))
got = sorted(map(str, make(s).collect()))
assert got == want, f"faulted fused join diverged:\n{got[:5]}\n{want[:5]}"
m = s.last_metrics
assert m["faultsInjected"] >= 1, m
assert m["deviceLostCount"] >= 1, m
assert m["retryCount"] > 0, m
assert m["meshJoinsFused"] >= 1, m
assert s.runtime.semaphore.held_depth() == 0
print("mesh fused-join fault smoke ok:", {k: m[k] for k in (
    "retryCount", "faultsInjected", "deviceLostCount",
    "meshJoinsFused", "meshProgramDispatches")})
PY

echo "== pallas kernel-tier smoke (interpret mode): one query per kernel"
echo "   family with the kernel forced on, bit-identical rows vs the"
echo "   kernel-off XLA run, zero fallbacks and held_depth == 0; plus the"
echo "   mesh fused join with the probe kernel on keeps shuffleSyncs == 0"
python - << 'PY'
import os

# same virtual-device trick as tests/conftest.py: the mesh leg below
# needs a multi-device mesh even on a single-CPU host
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.session import TpuSparkSession

PALLAS_ON = {
    "spark.rapids.sql.tpu.pallas.strings.enabled": True,
    "spark.rapids.sql.tpu.pallas.gatherScatter.enabled": True,
    "spark.rapids.sql.tpu.pallas.joinProbe.enabled": True,
    "spark.rapids.sql.tpu.pallas.stringHash.enabled": True,
    "spark.rapids.sql.tpu.pallas.interpret": True,
}
PALLAS_OFF = {k: False for k in PALLAS_ON}
BASE = {
    "spark.rapids.sql.enabled": True,
    "spark.sql.autoBroadcastJoinThreshold": 0,
}
NAMES = ["ace", "bog", "cab", "dim", "", "abacus", "zebra", "cabal"]
LEFT = {"name": [NAMES[i % len(NAMES)] for i in range(4096)],
        "v": list(range(4096))}
RIGHT = {"name": list(dict.fromkeys(NAMES)),
         "w": [i * 7 for i in range(len(dict.fromkeys(NAMES)))]}

def run(s):
    # string-key join (joinProbe + stringHash), contains filter
    # (strings), multi-partition concat on collect (gatherScatter)
    left = s.create_dataframe(LEFT, num_partitions=4)
    right = s.create_dataframe(RIGHT, num_partitions=2)
    df = left.join(right, on="name", how="inner")
    return sorted(map(str, df.filter(df["name"].contains("ab")).collect()))

off = TpuSparkSession(RapidsConf({**BASE, **PALLAS_OFF}))
want = run(off)
assert want, "smoke query returned no rows"

on = TpuSparkSession(RapidsConf({**BASE, **PALLAS_ON}))
got = run(on)
assert got == want, f"pallas parity diverged:\n{got[:5]}\n{want[:5]}"
m = on.last_metrics
# interpret mode engages every kernel: nothing may have fallen back
assert m["pallasFallbackCount"] == 0, m
assert on.runtime.semaphore.held_depth() == 0

# mesh fused join with the probe kernel on: the join still compiles
# INTO the fused shard_map program — no host-driven shuffle syncs
mesh = TpuSparkSession(RapidsConf({
    **BASE, **PALLAS_ON, "spark.rapids.shuffle.ici.enabled": True}))
got_mesh = run(mesh)
assert got_mesh == want, \
    f"mesh+pallas parity diverged:\n{got_mesh[:5]}\n{want[:5]}"
mm = mesh.last_metrics
assert mm["meshJoinsFused"] >= 1, mm
assert mm["shuffleSyncs"] == 0, mm
assert mm["pallasFallbackCount"] == 0, mm
assert mesh.runtime.semaphore.held_depth() == 0
print("pallas kernel-tier smoke ok:", {
    "rows": len(got), "pallasFallbackCount": m["pallasFallbackCount"],
    "meshJoinsFused": mm["meshJoinsFused"],
    "shuffleSyncs": mm["shuffleSyncs"]})
PY

echo "== adaptive smoke: skewed join coalesces with bit-identical rows"
echo "   adaptive on/off, and exchange:oom@2 replays through a"
echo "   coalesced-then-switched plan"
python - << 'PY'
import numpy as np
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.session import TpuSparkSession

rng = np.random.RandomState(11)
n = 20000
FACT = {"k": np.where(rng.rand(n) < 0.9, 0,
                      rng.randint(1, 50, n)).tolist(),
        "v": list(range(n))}
DIM = {"k": list(range(50)), "w": [i * 3 for i in range(50)]}
BASE = {
    "spark.rapids.sql.enabled": True,
    "spark.sql.shuffle.partitions": 8,
    "spark.rapids.sql.tpu.exchange.collapseLocal": False,
    "spark.sql.autoBroadcastJoinThreshold": -1,
}

def skew_join(s):
    big = s.create_dataframe(FACT, num_partitions=3)
    dim = s.create_dataframe(DIM, num_partitions=2)
    return sorted(map(str, big.join(dim, on="k").collect()))

on = TpuSparkSession(RapidsConf(BASE))
got_on = skew_join(on)
off = TpuSparkSession(RapidsConf({
    **BASE, "spark.rapids.sql.tpu.adaptive.enabled": False}))
got_off = skew_join(off)
assert got_on == got_off, "adaptive on/off rows diverged"
m = on.last_metrics
assert m["aqeCoalescedPartitions"] > 0, m
assert off.last_metrics["aqeCoalescedPartitions"] == 0, off.last_metrics
print("adaptive skew smoke ok:", {k: m[k] for k in (
    "aqeCoalescedPartitions", "aqeSkewSplits", "aqeStatsBytes")})

# coalesced-then-switched plan under an exchange OOM: aggregate join
# inputs (sizes unknown at plan time) with a live broadcast threshold;
# the @2 rule fires on the second exchange-site call mid-replan
def replan_join(s):
    big = s.create_dataframe(FACT, num_partitions=3) \
        .group_by("k").sum("v")
    dim = s.create_dataframe(DIM, num_partitions=2) \
        .group_by("k").sum("w")
    return sorted(map(str, big.join(dim, on="k").collect()))

REPLAN = {k: v for k, v in BASE.items()
          if k != "spark.sql.autoBroadcastJoinThreshold"}
clean = TpuSparkSession(RapidsConf(REPLAN))
want = replan_join(clean)
assert clean.last_metrics["aqeBroadcastSwitches"] >= 1, clean.last_metrics

s = TpuSparkSession(RapidsConf({
    **REPLAN, "spark.rapids.sql.tpu.faults.spec": "exchange:oom@2"}))
got = replan_join(s)
assert got == want, f"faulted replan diverged:\n{got[:3]}\n{want[:3]}"
m = s.last_metrics
assert m["retryCount"] > 0, m
assert m["faultsInjected"] >= 1, m
assert m["aqeBroadcastSwitches"] >= 1, m
print("adaptive fault smoke ok:", {k: m[k] for k in (
    "retryCount", "faultsInjected", "aqeBroadcastSwitches",
    "aqeCoalescedPartitions")})
PY

echo "== fault-injection smoke: scan:oom@2 through the adaptive read-ahead"
echo "   path — the faulted chunk replays through the retry ladder with"
echo "   bit-identical rows, dict columns intact, held_depth == 0"
python - << 'PY'
import os
import tempfile

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.session import TpuSparkSession

out = tempfile.mkdtemp(prefix="rapids_scan_fault_smoke_")
rng = np.random.RandomState(3)
n = 8192
cats = np.array([f"c{i:03d}" for i in range(64)], dtype=object)
pq.write_table(pa.table({
    "k": pa.array(rng.randint(0, 64, n).astype(np.int64)),
    "s": pa.array(cats[rng.randint(0, 64, n)]),
    "v": pa.array((rng.rand(n) * 10).round(3)),
}), os.path.join(out, "part-00000.parquet"), row_group_size=n // 8)

BASE = {
    "spark.rapids.sql.enabled": True,
    "spark.rapids.sql.tpu.scan.v2.enabled": True,
    "spark.rapids.sql.variableFloatAgg.enabled": True,
    # adaptive controller live (no explicit depth -> adaptive governs)
    "spark.rapids.sql.tpu.scan.readAhead.adaptive.enabled": True,
}

def q(s):
    from spark_rapids_tpu import functions as F
    df = s.read.parquet(out)
    return sorted(map(str, df.filter(df["k"] < 48).group_by("s")
                      .agg(F.sum("v").alias("sv"),
                           F.count("k").alias("c")).collect()))

clean = TpuSparkSession(RapidsConf(BASE))
want = q(clean)

s = TpuSparkSession(RapidsConf({
    **BASE, "spark.rapids.sql.tpu.faults.spec": "scan:oom@2"}))
got = q(s)
assert got == want, f"faulted scan diverged:\n{got[:3]}\n{want[:3]}"
m = s.last_metrics
assert m["retryCount"] > 0, m
assert m["faultsInjected"] >= 1, m
assert s.runtime.semaphore.held_depth() == 0
print("scan fault smoke ok:", {k: m[k] for k in (
    "retryCount", "faultsInjected", "scanBytesDecoded",
    "scanDictColumns")})
PY

echo "== fault-injection smoke: unspill:oom@1 under a tiny budget must"
echo "   hit the rehydration path, retry, and still produce exact results"
python - << 'PY'
import numpy as np
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.runtime.device import DeviceRuntime
from spark_rapids_tpu.session import TpuSparkSession

def make(s):
    n = 20000
    rng = np.random.RandomState(5)
    left = s.create_dataframe(
        {"k": rng.randint(0, 500, n).tolist(),
         "v": rng.randint(0, 100, n).tolist()}, num_partitions=3)
    right = s.create_dataframe(
        {"k": list(range(500)), "w": list(range(500))}, num_partitions=2)
    return left.join(right, on="k", how="inner")

BASE = {
    "spark.rapids.sql.enabled": True,
    "spark.sql.shuffle.partitions": 4,
    "spark.rapids.sql.tpu.exchange.collapseLocal": False,
    "spark.sql.autoBroadcastJoinThreshold": -1,
}
DeviceRuntime.reset()
try:
    clean = TpuSparkSession(RapidsConf(BASE))
    want = sorted(map(str, make(clean).collect()))
    DeviceRuntime.reset()
    s = TpuSparkSession(RapidsConf({
        **BASE,
        # ~64KB budget: shuffle pieces spill, so their reads must unspill
        "spark.rapids.memory.tpu.spillBudgetBytes": 65536,
        "spark.rapids.sql.tpu.faults.spec": "unspill:oom@1",
    }))
    got = sorted(map(str, make(s).collect()))
    assert got == want, f"faulted run diverged:\n{got[:5]}\n{want[:5]}"
    m = s.last_metrics
    assert m["faultsInjected"] >= 1, m
    assert m["retryCount"] > 0, m
    mem = m.get("memory", {})
    assert mem.get("unspilled", 0) > 0, mem
    print("unspill fault smoke ok:", {k: m[k] for k in (
        "retryCount", "faultsInjected", "unspillPrefetchHits")},
        {k: mem.get(k, 0) for k in ("spilled_to_host", "unspilled")})
finally:
    DeviceRuntime.reset()
PY

echo "== oocore smoke: q1 under a 2MB budget, async writer on AND off,"
echo "   both bit-correct with spills recorded"
python - << 'PY'
import tempfile, os
from spark_rapids_tpu.benchmarks import oocore_run

for async_on in (True, False):
    out = os.path.join(tempfile.mkdtemp(), "oocore.md")
    res = oocore_run.run(
        sf=0.2, budget_mb=2, queries=["q1"], out_path=out,
        extra_conf={"spark.rapids.sql.tpu.spill.async.enabled": async_on})
    r = res["q1"]
    assert r["agree"], (async_on, r)
    assert r["spilled_to_host"] + r["spilled_to_disk"] > 0, (async_on, r)
    print(f"oocore q1 async={async_on}: tpu {r['tpu_s']}s "
          f"spills {r['spilled_to_host']}/{r['spilled_to_disk']} "
          f"unspilled {r['unspilled']}")
PY

echo "== single-chip entry compile check"
python - << 'PY'
import jax
import __graft_entry__ as g
fn, args = g.entry()
out = jax.jit(fn)(*args)
print("entry ok:", [getattr(o, "shape", o) for o in out[:2]])
PY

echo "== 8-device multichip dryrun (virtual CPU mesh)"
python - << 'PY'
import __graft_entry__ as g
g.dryrun_multichip(8)
print("dryrun ok")
PY

echo "== two-process multi-host dryrun (2 x 4 virtual CPU devices)"
python -m pytest tests/test_multihost.py -q

echo "CI PASSED"
