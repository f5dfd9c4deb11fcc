"""The one span API (utils/tracing.span), names on the device timeline, the
exact partition of the query wall, and the compile wall by phase (PR 26).

Every test here runs on the CPU backend: it pins names, counts and sums —
never a time.
"""

import importlib.util
import json
import os
import re
import subprocess
import sys
import threading

import pytest

from compare import lowered_stage_texts, tpu_session
from spark_rapids_tpu import functions as F
from spark_rapids_tpu.obs import xplane as obs_xplane
from spark_rapids_tpu.utils import compile_registry as CR
from spark_rapids_tpu.utils import tracing

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO_ROOT, "tests", "data", "q6_scopes_v5e.xplane.pb")


#: float sums run on the device only where the session allows their
#: run-to-run variability (as the benchmark's configuration does)
FLOAT_AGG = {"spark.rapids.sql.variableFloatAgg.enabled": True}


def _q6_shaped(s, n=512, tag="q6"):
    """sum(price * discount) under a conjunctive filter, no keys: Q6."""
    df = s.create_dataframe({
        f"{tag}_price": [float(100 + i % 50) for i in range(n)],
        f"{tag}_discount": [0.01 * (i % 10) for i in range(n)],
        f"{tag}_quantity": [float(i % 50) for i in range(n)]})
    return (df.filter((F.col(f"{tag}_discount") >= 0.05)
                      & (F.col(f"{tag}_discount") <= 0.07)
                      & (F.col(f"{tag}_quantity") < 24.0))
            .agg(F.sum(F.col(f"{tag}_price") * F.col(f"{tag}_discount"))
                 .alias("revenue")))


# -- names on the device timeline ---------------------------------------------


def test_stage_program_module_name_and_scopes(monkeypatch):
    """A q6-shaped stage lowers to a module named after its label, and
    its operations carry the operator's and the kernels' scopes."""
    s, texts = lowered_stage_texts(
        monkeypatch, lambda s: _q6_shaped(s, tag="names"), **FLOAT_AGG)
    stage = [n for n in texts if n.startswith("stage_")]
    assert stage, texts.keys()
    for name in stage:
        assert re.search(rf"module @jit_{name}\b", texts[name])
    assert not any(n == "run" for n in texts)   # no more jit_run(<hash>)
    # no grouping key: the update runs the reduction, under its own scope
    assert not any("k.hashagg.hash_group_aggregate" in t
                   for t in texts.values())
    update = next(t for t in texts.values()
                  if "k.hashagg.keyless_aggregate" in t)
    # the planner applies Q6's filter inside the update aggregate: its
    # scope is the aggregate's, <Class>.<pre-order position>
    assert re.search(r"TpuHashAggregateExec\.\d+/", update)
    assert "e.Multiply" in update or "e.And" in update   # expression scopes
    assert not re.search(r"@[0-9a-f]{6,}|0x[0-9a-f]{6,}", " ".join(
        re.findall(r'loc\("([^"]*)"', update)))


def _widest_dimension(text):
    """The largest dimension of any tensor type in a lowered module."""
    return max((int(d) for dims in re.findall(r"tensor<((?:\d+x)+)", text)
                for d in dims.split("x") if d), default=0)


def test_keyless_aggregate_lowers_without_contraction_or_sort(monkeypatch,
                                                              tmp_path):
    """A q6-shaped plan is ONE program: its update reduces (no one-hot
    ``dot_general``), its merge sorts nothing, and it holds nothing wider
    than an input batch — no 8,194-slot table, no 131,072-row concat.
    With a grouping key the update program keeps its contraction against
    ``table + 2`` slots."""
    from spark_rapids_tpu.kernels.hashagg import TABLE_SLOTS
    n_batches = 6

    path = str(tmp_path / "kl.parquet")
    tpu_session().create_dataframe({
        "kl_price": [float(100 + i % 50) for i in range(3000)],
        "kl_discount": [0.01 * (i % 10) for i in range(3000)]}
    ).write_parquet(path)

    def keyless(s):     # six batches of 500 rows, as SF1's six of a million
        return (s.read.parquet(path).filter(F.col("kl_discount") >= 0.05)
                .agg(F.sum(F.col("kl_price") * F.col("kl_discount"))
                     .alias("kl_sum"))
                .select((F.col("kl_sum") * 1.0).alias("revenue")))

    s, texts = lowered_stage_texts(
        monkeypatch, keyless, **FLOAT_AGG,
        **{"spark.rapids.sql.reader.batchSizeRows": 3000 // n_batches})
    assert s.last_metrics["keylessAggBatches"] == n_batches
    assert s.last_metrics["mxuAggBatches"] == 0
    assert s.last_metrics["keylessUpdateBatches"] == n_batches
    assert s.last_metrics["dispatchCount"] == 1
    (fused,) = [t for n, t in texts.items() if n.startswith("stage_")]
    assert "k.hashagg.keyless_aggregate" in fused       # the update
    assert "k.groupby.groupby_aggregate" in fused       # and its merge
    assert "dot_general" not in fused and "stablehlo.sort" not in fused
    # an input batch (500 rows at capacity 512) is the widest thing in it
    assert 64 < _widest_dimension(fused) <= 512, _widest_dimension(fused)

    with monkeypatch.context() as m:
        s2, keyed = lowered_stage_texts(
            m, lambda s: s.create_dataframe({
                "kd_k": [i % 7 for i in range(3000)],
                "kd_v": [float(i) for i in range(3000)]})
            .group_by("kd_k").agg(F.sum(F.col("kd_v")).alias("sv")),
            **FLOAT_AGG)
    assert s2.last_metrics["mxuAggBatches"] > 0
    assert s2.last_metrics["keylessAggBatches"] == 0
    update = next(t for t in keyed.values()
                  if "k.hashagg.hash_group_aggregate" in t)
    assert "k.hashagg.keyless_aggregate" not in update
    contraction = re.search(r"stablehlo\.dot_general.*", update)
    assert contraction and f"x{TABLE_SLOTS + 2}xf32>" in contraction.group(0)


def test_keyless_batches_are_counted_by_every_dispatch(tmp_path):
    """How many batches the inlined update handled is known when the
    stage program is traced; a collect that traces nothing counts them
    all the same, so ``keyless_reduce_pct`` reads 100 on every query."""
    n_batches = 6
    path = str(tmp_path / "kc.parquet")
    s = tpu_session(**FLOAT_AGG, **{
        "spark.rapids.sql.reader.batchSizeRows": 3000 // n_batches})
    s.create_dataframe({
        "kc_price": [float(100 + i % 50) for i in range(3000)],
        "kc_discount": [0.01 * (i % 10) for i in range(3000)]}
    ).write_parquet(path)
    df = (s.read.parquet(path).filter(F.col("kc_discount") >= 0.05)
          .agg(F.sum(F.col("kc_price") * F.col("kc_discount"))
               .alias("revenue")))
    first = df.collect()
    for _ in range(2):      # the second and the third: nothing traced
        assert df.collect() == first
        m = s.last_metrics
        assert m["compileCount"] == 0
        assert m["keylessAggBatches"] == n_batches, m["keylessAggBatches"]
        assert m["keylessUpdateBatches"] == n_batches
        assert m["mxuAggBatches"] == 0
        assert m["dispatchCount"] == 1
        assert m["pipeline"]["inlinedUpdates"] == 1, m["pipeline"]


@pytest.mark.parametrize("key,mxu", [("kd_code", True), ("kd_code", False),
                                     ("kd_flag", True), (None, True)])
def test_keyed_and_compacted_batches_are_counted_by_every_dispatch(
        tmp_path, key, mxu):
    """A keyed aggregate's update batches (``keyedUpdateBatches``; of them
    ``mxuAggBatches`` through the slot contraction: all with an integer
    key or a string key the scan left dictionary-encoded, none in the
    sort variant) and
    the batches its filter compacted (``filterCompactedBatches``) are
    noted when the update stage is traced and counted by every collect.
    A keyless plan's filter sits inside the aggregate's arguments: it
    compacts nothing and both counters stay 0."""
    n_batches = 6
    path = str(tmp_path / "kd.parquet")
    s = tpu_session(**FLOAT_AGG, **{
        "spark.rapids.sql.agg.mxuHash.enabled": mxu,
        "spark.rapids.sql.reader.batchSizeRows": 3000 // n_batches})
    s.create_dataframe({
        "kd_flag": ["ANR"[i % 3] for i in range(3000)],
        "kd_code": [i % 3 for i in range(3000)],
        "kd_price": [float(100 + i % 50) for i in range(3000)],
        "kd_discount": [0.01 * (i % 10) for i in range(3000)]}
    ).write_parquet(path)
    df = s.read.parquet(path).filter(F.col("kd_discount") >= 0.05)
    total = F.sum(F.col("kd_price") * F.col("kd_discount")).alias("revenue")
    df = df.group_by(key).agg(total).order_by(key) if key else df.agg(total)
    first = df.collect()
    keyed = n_batches if key else 0
    for _ in range(2):      # the second and the third: nothing traced
        assert df.collect() == first
        m = s.last_metrics
        assert m["compileCount"] == 0
        assert m["keyedUpdateBatches"] == keyed, m["keyedUpdateBatches"]
        assert m["mxuAggBatches"] == (keyed if mxu else 0)
        assert m["filterCompactedBatches"] == keyed
        assert m["keylessUpdateBatches"] == n_batches - keyed
        # the keyed update is its stage's root; the filter notes no update
        assert ("inlinedUpdates" in m["pipeline"]) == (key is None)


def _scatters_over(text, rows):
    """The operand types of every ``stablehlo.scatter`` of a lowered
    module that scatters into an operand as long as an input batch (the
    type signature follows the operation's region)."""
    sigs = re.findall(r'"stablehlo\.scatter".*?\}\) : \((.*?)\) ->', text,
                      flags=re.S)
    return [sig for sig in sigs if sig.startswith(f"tensor<{rows}x")]


def _q1_shaped(s, path, where=True):
    """Eight aggregates by two string keys under a filter, sorted: Q1,
    over a CACHED parquet table, whose batches hold the keys encoded."""
    df = s.read.parquet(path).cache()
    if where:
        df = df.filter(F.col("q1_ship") <= 70)
    return (df.group_by("q1_flag", "q1_status").agg(
        F.sum("q1_qty").alias("sum_qty"),
        F.sum(F.col("q1_price") * (1 - F.col("q1_disc"))).alias("sum_disc"),
        F.avg("q1_qty").alias("avg_qty"), F.avg("q1_disc").alias("avg_disc"),
        F.count("*").alias("n")).order_by("q1_flag", "q1_status"))


@pytest.mark.parametrize("where", [True, False])
def test_q1_shaped_update_groups_by_codes_on_the_contraction(
        monkeypatch, tmp_path, where):
    """Q1's two string keys reach the keyed update dictionary-encoded —
    through the filter's compaction, which moves codes — and every update
    batch goes through the slot contraction, in every query; still two
    programs and the break's ``host_sizes`` beside the root's.  The
    update program sorts nothing and scatters over no batch-sized operand
    but the compaction's own rank inversion, one a batch; its table is as
    narrow as the two dictionaries allow."""
    from spark_rapids_tpu.kernels.hashagg import TABLE_SLOTS
    n_batches, rows = 4, 2048
    path = str(tmp_path / "q1.parquet")
    tpu_session().create_dataframe({
        "q1_flag": ["ANR"[(i * 7) % 3] for i in range(rows)],
        "q1_status": ["OF"[(i // 5) % 2] for i in range(rows)],
        "q1_qty": [float(i % 50) for i in range(rows)],
        "q1_price": [float(900 + i % 1000) for i in range(rows)],
        "q1_disc": [0.01 * (i % 10) for i in range(rows)],
        "q1_ship": [i % 100 for i in range(rows)]}).write_parquet(path)
    confs = dict(FLOAT_AGG, **{
        "spark.rapids.sql.tpu.pipeline.shrinkBytes": 0,
        "spark.rapids.sql.reader.batchSizeRows": rows // n_batches})
    held = {}

    def build(s):
        held["df"] = _q1_shaped(s, path, where)
        return held["df"]

    s, texts = lowered_stage_texts(monkeypatch, build, **confs)
    want = _q1_shaped(tpu_session(**{"spark.rapids.sql.enabled": False}),
                      path, where).collect()
    for _ in range(2):      # warm queries: the cache read, nothing traced
        got = held["df"].collect()
        assert len(got) == len(want) == 6
        for g, w in zip(got, want):
            assert g[:2] == w[:2] and g[-1] == w[-1]
            assert g[2:-1] == pytest.approx(w[2:-1], rel=1e-12)
        m = s.last_metrics
        assert m["compileCount"] == 0
        assert m["keyedUpdateBatches"] == n_batches
        assert m["mxuAggBatches"] == n_batches        # what the pct reads
        assert m["filterCompactedBatches"] == (n_batches if where else 0)
        assert m["pipeline"]["programs"] == 2, m["pipeline"]
        assert "flagReruns" not in m["pipeline"], m["pipeline"]
        assert not any(ms.get("hashAggFallback") for ms in m.values()
                       if isinstance(ms, dict))
        waits = [e.name for e in s.query_history()[-1].events
                 if e.kind == "span" and e.site == "device_wait"]
        assert waits.count("host_sizes") == 2, waits
    update = next(t for t in texts.values()
                  if "k.hashagg.hash_group_aggregate" in t)
    assert "k.groupby.group_segments" not in update
    assert "k.strings.string_hash2" not in update
    assert "k.layout.dict_decode_column" in update  # the key column it emits
    assert "stablehlo.sort" not in update
    cap = rows // n_batches
    scatters = _scatters_over(update, cap)
    assert len(scatters) == (n_batches if where else 0), scatters
    contraction = re.search(r"stablehlo\.dot_general.*", update)
    # (3 + 1) * (2 + 1) key tuples at most, at the dictionaries' padded
    # sizes: one lane tile of slots where the integer-key table has 8,194
    assert contraction and "x128xf32>" in contraction.group(0)
    assert f"x{TABLE_SLOTS + 2}xf32>" not in update


def test_filter_operator_has_its_own_scope(monkeypatch):
    """Where a filter stays an operator of its own, ``TpuFilterExec.<k>``
    is on its operations, beside the compaction kernel's scope."""
    def build(s):
        df = s.create_dataframe({"fa": list(range(300)),
                                 "fb": [float(i) for i in range(300)]})
        return df.filter(F.col("fa") > 10).order_by("fb")

    _s, texts = lowered_stage_texts(monkeypatch, build)
    joined = "\n".join(texts.values())
    assert re.search(r"TpuFilterExec\.\d+", joined), texts.keys()
    assert re.search(r"k\.layout\.(compact|gather_rows|compaction_indices)",
                     joined)


def test_program_name_is_an_identifier():
    assert CR.program_name("stage:TpuHashAggregateExec") == \
        "stage_TpuHashAggregateExec"
    assert CR.program_name("join:phase1") == "join_phase1"
    assert CR.program_name("TpuFilter") == "TpuFilter"
    assert CR.program_name("serve-batch:q 1").isidentifier()
    f = CR.instrumented_jit(lambda x: x + 1, label="unit:name check")
    assert f.label == "unit:name check"
    assert f.program == "unit_name_check"
    import jax.numpy as jnp
    assert "module @jit_unit_name_check" in \
        f.jitted.lower(jnp.arange(3)).as_text()


_NAMES_SCRIPT = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "tests")]
import jax
from test_tracing import _q6_shaped
from compare import lowered_stage_texts, tpu_session
from spark_rapids_tpu.utils import compile_registry as CR
junk = [object() for _ in range(int(sys.argv[2]))]   # move the heap
s = tpu_session(**{"spark.rapids.sql.variableFloatAgg.enabled": True})
_q6_shaped(s, tag="proc").collect()
p = s.query_history()[-1]
def walk(op):
    yield op.op_id
    for c in op.children:
        yield from walk(c)
print(json.dumps({
    "programs": sorted(k for k in CR.per_label_compiles()
                       if k.startswith("stage_")),
    "op_ids": list(walk(s.last_physical_plan)),
    "spans": sorted({f"{e.site}/{e.name}/{e.op_id}" for e in p.events
                     if e.kind == "span"}),
}))
"""


def test_names_are_the_same_in_two_processes():
    outs = []
    for junk in ("10", "100000"):
        proc = subprocess.run(
            [sys.executable, "-c", _NAMES_SCRIPT, REPO_ROOT, junk],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        outs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    assert outs[0] == outs[1]
    assert outs[0]["programs"] and outs[0]["op_ids"] and outs[0]["spans"]
    flat = json.dumps(outs[0])
    assert not re.search(r"@[0-9a-f]{6,}|0x[0-9a-f]+", flat), flat
    assert all(re.fullmatch(r"[A-Za-z]\w*#\d+", i) for i in outs[0]["op_ids"])


def test_assign_op_ids_preorder_and_shared_subtree():
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.plan.physical import PhysicalOp, assign_op_ids
    schema = T.Schema([])
    leaf = PhysicalOp([], schema)
    assert re.fullmatch(r"PhysicalOp@\d+", leaf.op_id)   # a counter, no id()
    mid = PhysicalOp([leaf], schema)
    root = PhysicalOp([mid, leaf], schema)                # leaf shared
    assign_op_ids(root)
    assert [root.op_id, mid.op_id, leaf.op_id] == \
        ["PhysicalOp#0", "PhysicalOp#1", "PhysicalOp#2"]


# -- one emitter, on both timelines -------------------------------------------


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``."""

    names = []
    exited = []
    lock = threading.Lock()

    def __init__(self, name, **kw):
        self.name = name

    def __enter__(self):
        with _Recorder.lock:
            _Recorder.names.append(self.name)
        return self

    def __exit__(self, *exc):
        with _Recorder.lock:
            _Recorder.exited.append(self.name)
        return False


def _shuffle_spill_retry_session():
    return tpu_session(**{
        "spark.rapids.sql.tpu.faults.spec": "dispatch:oom@2",
        "spark.rapids.sql.tpu.exchange.collapseLocal": False,
        "spark.sql.autoBroadcastJoinThreshold": -1,
        "spark.rapids.memory.tpu.spillBudgetBytes": 64 * 1024,
        "spark.rapids.sql.tpu.spill.async.enabled": False,
    })


def _shuffle_spill_retry_plan(s, n=8192):
    left = s.create_dataframe(
        {"k": [i % 500 for i in range(n)],
         "v": [(3 * i) % 997 for i in range(n)]}, num_partitions=3)
    right = s.create_dataframe(
        {"k": list(range(500)), "w": list(range(500))}, num_partitions=2)
    return left.join(right, on="k", how="inner").plan


def test_every_span_is_a_trace_annotation_under_one_prefix(monkeypatch):
    from spark_rapids_tpu.runtime.device import DeviceRuntime
    _Recorder.names = []
    monkeypatch.setattr(tracing.jax.profiler, "TraceAnnotation", _Recorder)
    DeviceRuntime.reset()
    try:
        s = _shuffle_spill_retry_session()
        s.execute(_shuffle_spill_retry_plan(s))
        p = s.query_history()[-1]
    finally:
        DeviceRuntime.reset()
    assert _Recorder.names
    assert all(n.startswith("srt/") for n in _Recorder.names)
    recorded = set(_Recorder.names)
    spans = [e for e in p.events if e.kind == "span"]
    sites = {e.site for e in spans}
    assert {"plan", "stage_inputs", "enqueue", "device_wait", "d2h", "h2d",
            "exchange", "spill", "result", "bookkeeping"} <= sites, sites
    for e in spans:
        assert f"srt/{e.site}/{e.name}" in recorded, (e.site, e.name)


def test_span_that_raises_closes_its_range_and_restores_the_operator(
        monkeypatch):
    from spark_rapids_tpu.obs import events as obs_events
    _Recorder.names, _Recorder.exited = [], []
    monkeypatch.setattr(tracing.jax.profiler, "TraceAnnotation", _Recorder)
    scope = obs_events.begin_query(enabled=True, max_events=100)
    try:
        with tracing.span("stage", "outer", "OuterExec#1"):
            with pytest.raises(RuntimeError):
                with tracing.span("exchange", "unit", "UnitExec#3") as sp:
                    sp.set(bytes=7)
                    assert tracing.current_op() == "UnitExec#3"
                    raise RuntimeError("boom")
            assert tracing.current_op() == "OuterExec#1"
    finally:
        events, _d, _s = obs_events.end_query(scope)
    assert tracing.current_op() == ""
    assert sorted(_Recorder.exited) == sorted(_Recorder.names) == \
        ["srt/exchange/unit", "srt/stage/outer"]
    failed = [e for e in events if e.site == "exchange"]
    assert len(failed) == 1 and failed[0].payload == \
        {"bytes": 7, "error": True}


def test_split_that_raises_leaves_no_operator_on_the_thread(monkeypatch):
    """An exchange's split that fails (an injected fault, an OOM, a lost
    device) must not leave its op id as the thread's current operator:
    every later op-less ``enqueue``/``device_wait`` span would be charged
    to it."""
    from spark_rapids_tpu.parallel.exchange import TpuShuffleExchangeExec
    _Recorder.names, _Recorder.exited = [], []
    monkeypatch.setattr(tracing.jax.profiler, "TraceAnnotation", _Recorder)
    after = []

    def boom(self, *a, **k):
        raise RuntimeError("split refused")

    real = TpuShuffleExchangeExec.partitions

    def partitions(self, ctx):
        try:
            return real(self, ctx)
        except RuntimeError:
            after.append(tracing.current_op())     # on the split's thread
            raise

    monkeypatch.setattr(TpuShuffleExchangeExec, "_split_v2", boom)
    monkeypatch.setattr(TpuShuffleExchangeExec, "_split_v1", boom)
    monkeypatch.setattr(TpuShuffleExchangeExec, "partitions", partitions)
    s = tpu_session(**{
        "spark.rapids.sql.tpu.exchange.collapseLocal": False,
        "spark.sql.autoBroadcastJoinThreshold": -1})
    with pytest.raises(Exception, match="split refused"):
        s.execute(_shuffle_spill_retry_plan(s, n=512))
    assert after and set(after) == {""}
    assert "srt/exchange/split" in _Recorder.names
    assert sorted(_Recorder.exited) == sorted(_Recorder.names)
    assert tracing.current_op() == ""


def test_emit_span_has_one_caller():
    """Only utils/tracing.py (and the definition) may call emit_span."""
    hits = []
    pkg = os.path.join(REPO_ROOT, "spark_rapids_tpu")
    for d, _dirs, files in os.walk(pkg):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(d, f)
                with open(path) as fh:
                    if "emit_span(" in fh.read():
                        hits.append(os.path.relpath(path, pkg))
    assert sorted(hits) == ["obs/events.py", "utils/tracing.py"]


def test_one_jitted_call_one_enqueue_span():
    """No second `dispatch`/`device` span over the same call, and a nested
    instrumented call made while tracing is neither a span nor a dispatch."""
    import jax.numpy as jnp
    from spark_rapids_tpu.obs import events as obs_events
    inner = CR.instrumented_jit(lambda x: x * 2, label="unit:inner")
    outer = CR.instrumented_jit(lambda x: inner(x) + 1, label="unit:outer")
    scope = obs_events.begin_query(enabled=True, max_events=100)
    try:
        before = CR.snapshot()["dispatches"]
        with tracing.span("stage", "unit", "UnitExec#7"):
            outer(jnp.arange(4))
            outer(jnp.arange(4))
        dispatched = CR.snapshot()["dispatches"] - before
    finally:
        events, _dropped, _by_site = obs_events.end_query(scope)
    enq = [e for e in events if e.site == "enqueue"]
    assert dispatched == 2 and len(enq) == 2
    assert {e.name for e in enq} == {"unit:outer"}
    assert all(e.op_id == "UnitExec#7" for e in enq)     # the operator
    assert [bool((e.payload or {}).get("compiled")) for e in enq] == \
        [True, False]
    assert not [e for e in events if e.site in ("dispatch", "device")]


def test_device_to_host_splits_the_wait_from_the_copy():
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.batch import (
        HostBatch, device_to_host_many, host_to_device,
    )
    from spark_rapids_tpu.obs import events as obs_events
    dev = host_to_device(HostBatch.from_pydict({"a": (T.INT, [1, None, 3])}))
    scope = obs_events.begin_query(enabled=True, max_events=100)
    try:
        out = device_to_host_many([dev])
    finally:
        events, _d, _s = obs_events.end_query(scope)
    assert out[0].to_pydict() == {"a": [1, None, 3]}
    order = [(e.site, e.name) for e in events]
    assert order == [("device_wait", "d2h_ready"), ("d2h", "transfer"),
                     ("result", "assemble")]
    assert events[0].t1 <= events[1].t0   # the copy is timed after the wait


# -- the query wall, partitioned ----------------------------------------------


def _assert_partition(m):
    cp = m["critpath"]
    assert sum(cp.values()) == m["queryWallNs"], cp
    assert m["critpathAttributedNs"] == m["queryWallNs"] - cp.get("wait", 0)
    for site in ("plan", "device_wait", "d2h", "bookkeeping"):
        assert cp.get(site, 0) > 0, (site, cp)


def test_critpath_partitions_the_query_wall_shuffle_spill_retry():
    from spark_rapids_tpu.runtime.device import DeviceRuntime
    DeviceRuntime.reset()
    try:
        s = _shuffle_spill_retry_session()
        s.execute(_shuffle_spill_retry_plan(s))
        m = s.last_metrics
        _assert_partition(m)
        assert m["retryCount"] >= 1
        p = s.query_history()[-1]
        assert p.qt1_ns - p.qt0_ns == m["queryWallNs"] == p.wall_ns
    finally:
        DeviceRuntime.reset()


def test_critpath_partitions_the_query_wall_under_serve_concurrency():
    from spark_rapids_tpu.serve import ServeScheduler
    s = tpu_session()
    dfs = [s.create_dataframe(
        {"k": [(7 * i + j) % 7 for j in range(600)],
         "v": [(i + 3 * j) % 997 for j in range(600)]},
        num_partitions=2).group_by("k").sum("v") for i in range(6)]
    got = []
    real = s.execute_with_metrics

    def keep(plan):
        out, m = real(plan)
        got.append(m)
        return out, m

    s.execute_with_metrics = keep
    with ServeScheduler(s, max_concurrency=3) as sched:
        for f in [sched.submit(df) for df in dfs]:
            f.result(timeout=120)
    assert len(got) == 6
    for m in got:
        _assert_partition(m)


def test_failed_plan_closes_its_scope():
    """The scope now opens before planning: a plan that raises there must
    not leave it open for the next query."""
    from spark_rapids_tpu.obs import events as obs_events
    s = tpu_session(**FLOAT_AGG)
    with pytest.raises(Exception):
        s.execute_with_metrics(object())       # not a logical plan
    assert obs_events.current_scope() is None
    _q6_shaped(s, tag="after").collect()
    assert s.last_metrics["queryWallNs"] > 0


# -- the compile wall by phase ------------------------------------------------

_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_ns",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_ns",
    "/jax/core/compile/backend_compile_duration": "backend_compile_ns",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_ns",
    "/jax/compilation_cache/compile_time_saved_sec": None,   # not spent
}
_WALL_COUNTERS = ("trace_ns", "lower_ns", "backend_compile_ns",
                  "cache_load_ns")


@pytest.mark.parametrize("event", sorted(_EVENTS))
def test_event_duration_moves_only_its_counter(event, monkeypatch):
    monkeypatch.setattr(CR._COMPILE_PHASES, "program", "unit_routing",
                        raising=False)
    monkeypatch.setattr(CR._COMPILE_PHASES, "retrieved", 0, raising=False)
    before = CR.snapshot()
    if "/compile/" in event:   # jax brackets a phase: start scalar first
        CR._on_compile_phase_start(event, 0.0, fun_name="jit_unit_routing")
    CR._on_event_duration(event, 0.25, fun_name="jit_unit_routing")
    moved = {k: v for k, v in CR.delta(before, CR.snapshot()).items() if v}
    counter = _EVENTS[event]
    if counter is None:    # time saved is time NOT spent: no counter moves
        assert moved == {}
        return
    assert moved == {counter: 250_000_000}
    assert CR.per_label_compiles()["unit_routing"][counter] >= 250_000_000


def test_backend_phase_excludes_the_cache_load_inside_it(monkeypatch):
    monkeypatch.setattr(CR._COMPILE_PHASES, "program", "unit_hit",
                        raising=False)
    monkeypatch.setattr(CR._COMPILE_PHASES, "retrieved", 0, raising=False)
    backend = "/jax/core/compile/backend_compile_duration"
    before = CR.snapshot()
    CR._on_compile_phase_start(backend, 0.0, fun_name="jit_unit_hit")
    CR._on_event("/jax/compilation_cache/cache_hits")
    CR._on_event_duration(
        "/jax/compilation_cache/compile_time_saved_sec", 9.0)
    CR._on_event_duration(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.75)
    CR._on_event_duration(backend, 1.0, fun_name="jit_unit_hit")
    d = CR.delta(before, CR.snapshot())
    assert d["cache_load_ns"] == 750_000_000
    assert d["backend_compile_ns"] == 250_000_000    # 1.0 - 0.75
    assert d["cache_hits"] == 1 and "cache_saved_ns" not in d
    assert sum(d[k] for k in _WALL_COUNTERS) == 1_000_000_000


def test_nested_trace_phase_counts_once(monkeypatch):
    monkeypatch.setattr(CR._COMPILE_PHASES, "program", "unit_nested",
                        raising=False)
    ev = "/jax/core/compile/jaxpr_trace_duration"
    before = CR.snapshot()
    CR._on_compile_phase_start(ev, 0.0, fun_name="unit_nested")
    CR._on_compile_phase_start(ev, 0.0, fun_name="_where")   # a jnp helper
    CR._on_event_duration(ev, 0.1, fun_name="_where")
    CR._on_event_duration(ev, 0.5, fun_name="unit_nested")
    assert CR.delta(before, CR.snapshot())["trace_ns"] == 500_000_000


def test_compile_phases_first_query_and_warm_query():
    s = tpu_session(**FLOAT_AGG)
    df = _q6_shaped(s, n=640, tag="phases")
    df.collect()
    first = dict(s.last_metrics)
    assert first["compileCount"] >= 1
    phases = [first[k] for k in ("jaxTraceNs", "lowerNs", "backendCompileNs",
                                 "compileCacheLoadNs")]
    assert all(v >= 0 for v in phases) and first["jaxTraceNs"] > 0
    assert 0 < sum(phases) <= first["compileWallNs"], (phases, first)
    df.collect()
    warm = s.last_metrics
    assert warm["compileCount"] == 0
    assert [warm[k] for k in ("jaxTraceNs", "lowerNs", "backendCompileNs",
                              "compileCacheLoadNs", "compileCacheHits",
                              "compileCacheMisses")] == [0] * 6
    per_label = CR.per_label_compiles()
    stage = [k for k in per_label if k.startswith("stage_")]
    assert stage and all("trace_ns" in per_label[k] for k in stage)


# -- rapidsprof --xplane ------------------------------------------------------


def test_scopes_of_op_name_path():
    assert obs_xplane.scopes_of(
        "jit(stage_TpuHashAggregateExec)/TpuHashAggregateExec.0/"
        "TpuFilterExec.2/k.layout.compact/k.layout.gather_rows/gather:") == \
        ("TpuFilterExec.2", "k.layout.gather_rows")
    assert obs_xplane.scopes_of(
        "jit(stage_X)/TpuProjectExec.1/e.And/e.ToDate/gather:") == \
        ("TpuProjectExec.1", "e.ToDate")
    assert obs_xplane.scopes_of("") == (obs_xplane.NO_SCOPE,) * 2
    assert obs_xplane.scopes_of("dargs[0][0][0]:") == \
        (obs_xplane.NO_SCOPE,) * 2


def test_rapidsprof_xplane_on_a_recorded_chip_trace():
    """One query of ``tpch_sf1_cached.q6`` recorded on a v5e with this PR's
    names (cut to the first ``bench:query`` window, names to 64 characters,
    metadata stats to tf_op/program_id/hlo_category): every device operation
    falls under a named stage program and an operator scope, every long
    idle gap under an ``srt/`` span; read without jax."""
    r = obs_xplane.reduce_xplane(FIXTURE)
    assert r is not None and r["queries"] >= 1 and r["device_events"] > 100
    assert 0 < r["busy_s"] <= r["window_s"]
    modules = {m for m, _o, _k, _s in r["by_scope"]}
    assert modules and all(m.startswith("jit_stage_") for m in modules)
    assert r["named_module_share"] >= 0.95
    assert r["operator_scope_share"] >= 0.95
    assert r["srt_gap_share"] >= 0.90
    inner = {k: s for _m, _o, k, s in r["by_scope"]}
    assert inner["k.layout.gather_rows"] > inner["e.ToDate"] > \
        inner["k.hashagg.hash_group_aggregate"] > 0   # PERF.md section 5
    assert abs(sum(s for *_x, s in r["by_scope"]) - r["busy_s"]) < 1e-6
    spans = {name for name, _c, _s in r["host_spans"]}
    assert {"srt/plan/physical", "srt/device_wait/d2h_ready",
            "srt/d2h/transfer", "srt/bookkeeping/metrics"} <= spans

    driver = (
        "import runpy, sys\n"
        "sys.argv = [sys.argv[1], '--xplane', sys.argv[2]]\n"
        "try:\n"
        "    runpy.run_path(sys.argv[0], run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    assert not e.code, e.code\n"
        "assert 'jax' not in sys.modules, 'rapidsprof --xplane imported jax'\n")
    proc = subprocess.run(
        [sys.executable, "-c", driver,
         os.path.join(REPO_ROOT, "tools", "rapidsprof.py"), FIXTURE],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "jit_stage_TpuHashAggregateExec" in proc.stdout
    assert "idle gaps by innermost srt/ span" in proc.stdout
    assert "jit_run" not in proc.stdout


def test_xplane_reader_finds_no_device_plane_in_a_cpu_trace(tmp_path):
    """Nothing to read is None, not a reading of 0."""
    import jax.numpy as jnp
    d = str(tmp_path / "prof")
    tracing.start_profile(d)
    with tracing.span("plan", "unit"):
        jnp.arange(8).sum().block_until_ready()
    tracing.stop_profile()
    files = [os.path.join(r, f) for r, _d, fs in os.walk(d) for f in fs
             if f.endswith(".xplane.pb")]
    assert files
    planes = obs_xplane.read_xspace(files[0]).planes
    host = [p for p in planes if p.name.startswith("/host:")]
    assert any(md.name == "srt/plan/unit" for p in host
               for md in p.event_metadata.values())
    assert obs_xplane.reduce_xplane(files[0]) is None


# -- the benchmark's new readers ----------------------------------------------


def _reader(name):
    path = os.path.join(REPO_ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_dict(window_counters, setup_counters):
    return {"records": [{"answered": True, "counters": c}
                        for c in window_counters]
            + [{"answered": False, "counters": {}}],
            "setup": {"executions": [{"counters": c}
                                     for c in setup_counters]}}


_FULL = _run_dict(
    [{"queryWallNs": 616_000_000,
      "critpath": {"plan": 400_000, "device_wait": 604_000_000,
                   "d2h": 2_000_000, "wait": 9_600_000}},
     {"queryWallNs": 618_000_000,
      "critpath": {"plan": 600_000, "device_wait": 606_000_000,
                   "d2h": 2_000_000, "wait": 9_400_000}}],
    [{"jaxTraceNs": 3_000_000_000, "lowerNs": 1_000_000_000,
      "backendCompileNs": 20_000_000_000, "compileCacheLoadNs": 500_000_000},
     {"jaxTraceNs": 1_000_000_000, "lowerNs": 500_000_000,
      "backendCompileNs": 5_000_000_000, "compileCacheLoadNs": 1_500_000_000},
     {"jaxTraceNs": 0, "lowerNs": 0, "backendCompileNs": 0,
      "compileCacheLoadNs": 0}])
# the parent's program: no critpath, no phase keys, and a backendCompileNs
# that meant something else
_PARENT = _run_dict([{"dispatchCount": 2}],
                    [{"backendCompileNs": 47_000_000_000}])


@pytest.mark.parametrize("name,expected", [
    ("plan_ms", 0.5), ("device_wait_ms", 605.0), ("host_ms_per_query", 12.0),
    ("jax_trace_s", 4.0), ("lower_s", 1.5), ("backend_compile_s", 25.0),
    ("cache_load_s", 2.0)])
def test_new_reader_reads_its_key_and_nothing_where_absent(name, expected):
    read = _reader(name).read
    assert read(_FULL) == pytest.approx(expected)
    assert read(_PARENT) is None
    assert read(_run_dict([], [])) is None


def test_benchmark_json_gained_only_the_seven_entries():
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    # PR 26's seven, in place; PR 28 appended four behind them, each listing
    # the one cell whose counters it reads; PR 29 one more, in every cell
    # that reports ``rows_per_s``
    first = names.index("plan_ms")
    assert names[first:first + 7] == [
        "plan_ms", "device_wait_ms", "host_ms_per_query", "jax_trace_s",
        "lower_s", "backend_compile_s", "cache_load_s"]
    for m in bench["per_layer"][first:first + 7]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}
        assert m["source"] == "program_counter"
    assert names[first + 7:] == ["shape_hit_pct", "parse_ms", "bind_ms",
                                 "setup_variant_compiles",
                                 "keyless_reduce_pct",
                                 # PR 34's five, each listing its cells
                                 "scan_decode_ms", "scan_bytes_per_row",
                                 "scan_overlap_pct", "keyed_contraction_pct",
                                 "compacted_batches_per_query",
                                 # PR 36's three, of the join cell alone
                                 "join_filters_pushed",
                                 "join_pairs_per_query",
                                 "join_size_reads_per_query"]
    q6_cells = ["tpch_sf1_cached.q6", "tpch_sf1_qgen.q6_text",
                "tpch_sf1_parquet.q6"]       # those with a keyless aggregate
    for m in bench["per_layer"][first:first + 12]:
        assert m.get("workloads", ["tpch_sf1_qgen.q6_text"]) == (
            q6_cells if m["name"] == "keyless_reduce_pct"
            else ["tpch_sf1_qgen.q6_text"])
    for m in bench["per_layer"][first + 12:first + 17]:
        assert set(m["workloads"]) <= {"tpch_sf1_cached.q1",
                                       "tpch_sf1_parquet.q6",
                                       "tpch_sf1_join.q12"}
    for m in bench["per_layer"][first + 17:]:
        assert m["workloads"] == ["tpch_sf1_join.q12"]
        assert m["moves"] == "rows_per_s"
    for m in bench["per_layer"][first:]:
        assert os.path.exists(os.path.join(
            REPO_ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
