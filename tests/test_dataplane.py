"""Data-plane throughput tests: buffer donation (safety + accounting),
the single-allocation k-way concat kernel's bit-parity with the pairwise
chain, the stop-aware read-ahead channel, async partition overlap, and
the bulk D2H metrics."""

import threading
import time

import jax
import numpy as np
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import (
    device_to_host, host_to_device, HostBatch, round_up_capacity,
)
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.kernels.layout import (
    concat_kway, concat_kway_run, concat_pair,
)
from spark_rapids_tpu.session import TpuSparkSession
from spark_rapids_tpu.utils import compile_registry as CR

from compare import assert_tpu_cpu_equal, tpu_session
from conftest import assert_batches_equal


def make_batch(data):
    return host_to_device(HostBatch.from_pydict(data))


# ---------------------------------------------------------------------------
# k-way concat: bit-parity with the pairwise chain
# ---------------------------------------------------------------------------


def _rand_data(rng, n, with_arrays=True):
    words = ["", "a", "hello world", "xyzzy", "long string value é"]
    data = {
        "i": (T.INT, [None if rng.rand() < 0.2 else int(rng.randint(-5, 99))
                      for _ in range(n)]),
        "d": (T.DOUBLE, [None if rng.rand() < 0.2 else float(rng.randn())
                         for _ in range(n)]),
        "s": (T.STRING, [None if rng.rand() < 0.2
                         else words[rng.randint(len(words))]
                         for _ in range(n)]),
    }
    if with_arrays:
        data["a"] = (T.ArrayType(T.LONG),
                     [None if rng.rand() < 0.2
                      else [int(x) for x in
                            rng.randint(0, 9, rng.randint(0, 4))]
                      for _ in range(n)])
    return data


def _pair_chain(batches, cap, byte_caps):
    acc = batches[0]
    for nxt in batches[1:]:
        acc = concat_pair(acc, nxt, cap, out_byte_caps=byte_caps or None)
    return acc


@pytest.mark.parametrize("k", [2, 3, 5])
def test_concat_kway_matches_pair_chain(rng, k):
    sizes = [int(rng.randint(1, 9)) for _ in range(k)]
    batches = [make_batch(_rand_data(rng, n)) for n in sizes]
    total = sum(sizes)
    cap = round_up_capacity(total)
    # byte caps bucketed from summed input byte capacities — the same
    # sizing concat_static uses for string AND array columns
    byte_caps = []
    for ci, f in enumerate(batches[0].schema.fields):
        if f.dtype.is_string or f.dtype.is_array:
            byte_caps.append(round_up_capacity(
                sum(int(b.columns[ci].data.shape[0]) for b in batches),
                minimum=16))
    got = concat_kway(batches, cap, out_byte_caps=byte_caps)
    exp = _pair_chain(batches, cap, byte_caps)
    assert got.capacity == exp.capacity == cap
    assert int(jax.device_get(got.num_rows)) == total
    for cg, ce in zip(got.columns, exp.columns):
        # bit-parity of every buffer, padding included
        assert cg.data.shape == ce.data.shape
        np.testing.assert_array_equal(np.asarray(jax.device_get(cg.data)),
                                      np.asarray(jax.device_get(ce.data)))
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(cg.validity)),
            np.asarray(jax.device_get(ce.validity)))
        if cg.offsets is not None:
            np.testing.assert_array_equal(
                np.asarray(jax.device_get(cg.offsets)),
                np.asarray(jax.device_get(ce.offsets)))
    assert_batches_equal(device_to_host(exp).to_pydict(),
                         device_to_host(got).to_pydict())


def test_concat_kway_run_single_dispatch(rng):
    batches = [make_batch(_rand_data(rng, 4, with_arrays=False))
               for _ in range(3)]
    cap = round_up_capacity(12)
    before = CR.snapshot()
    out = concat_kway_run(batches, cap, out_byte_caps=[64])
    d = CR.delta(before, CR.snapshot())
    assert d["dispatches"] == 1  # the chain was an eager op storm
    assert int(jax.device_get(out.num_rows)) == 12


def test_concat_kway_after_take_head(rng):
    """take_head truncates num_rows WITHOUT repacking offsets, so a
    truncated input's offsets keep growing past its live rows — the k-way
    byte cursor must advance by offsets[num_rows] (live bytes), not
    offsets[-1], or every later input's bytes land past where the rebuilt
    offsets point (tpcds q49 regression: union of sorted+limited arms)."""
    from spark_rapids_tpu.kernels.layout import take_head
    full = [make_batch(_rand_data(rng, 8)) for _ in range(3)]
    heads = [take_head(b, 3) for b in full]
    total = 9
    cap = round_up_capacity(total)
    byte_caps = []
    for ci, f in enumerate(heads[0].schema.fields):
        if f.dtype.is_string or f.dtype.is_array:
            byte_caps.append(round_up_capacity(
                sum(int(b.columns[ci].data.shape[0]) for b in heads),
                minimum=16))
    got = concat_kway(heads, cap, out_byte_caps=byte_caps)
    exp = _pair_chain(heads, cap, byte_caps)
    assert_batches_equal(device_to_host(exp).to_pydict(),
                         device_to_host(got).to_pydict())


def test_concat_kway_default_byte_caps(rng):
    """Default byte capacity = summed input byte capacities, matching the
    chain's accumulated default."""
    a = make_batch({"s": (T.STRING, ["aa", "b"])})
    b = make_batch({"s": (T.STRING, ["cccc"])})
    cap = round_up_capacity(3)
    got = concat_kway([a, b], cap)
    exp = concat_pair(a, b, cap)
    assert got.columns[0].data.shape == exp.columns[0].data.shape
    assert_batches_equal(device_to_host(exp).to_pydict(),
                         device_to_host(got).to_pydict())


# ---------------------------------------------------------------------------
# donation: accounting + use-after-donate safety across pipeline paths
# ---------------------------------------------------------------------------


def _pipeline_queries(s):
    """One DataFrame per pipeline_inline path family: map (project/filter),
    aggregate update+merge (stage break), sort tail, limit, union,
    expand (grouping semantics via distinct)."""
    df = s.create_dataframe({
        "k": [i % 5 for i in range(400)],
        "v": [float(i) for i in range(400)],
        "s": [f"row{i % 7}" for i in range(400)],
    })
    agg = (df.filter(df["k"] > 0)
             .with_column("w", df["v"] * 2.0)
             .group_by("k")
             .agg(F.sum("w").alias("sw"), F.count("w").alias("c"),
                  F.min("v").alias("mn"))
             .order_by("k"))
    sorted_q = df.order_by(df["v"].desc()).limit(10)
    union_q = df.filter(df["k"] == 1).union(df.filter(df["k"] == 2))
    distinct_q = df.select("k").distinct().order_by("k")
    return [agg, sorted_q, union_q, distinct_q]


def test_donation_accounting_and_guard():
    """Every pipeline path runs under the armed use-after-donate guard —
    a donated buffer presented to any later dispatch or sync site raises —
    and the headline-shaped aggregate reports donatedBytes > 0."""
    s = tpu_session()
    with CR.donation_guard():
        results = [q.collect() for q in _pipeline_queries(s)]
        assert all(r is not None for r in results)
    m = s.last_metrics
    assert "donatedBytes" in m
    # re-run the aggregate alone for its own metrics delta
    with CR.donation_guard():
        agg = _pipeline_queries(s)[0]
        agg.collect()
    assert s.last_metrics["donatedBytes"] > 0


def test_donation_safe_with_cached_input_repeat():
    """A cached (spill-catalog) scan must never be donated: on backends
    that implement donation the second collect would hit deleted buffers.
    jax implements donation on CPU, so this test is load-bearing."""
    s = tpu_session()
    df = s.create_dataframe({"k": [i % 3 for i in range(100)],
                             "v": list(range(100))}).cache()
    q = df.group_by("k").agg(F.sum("v").alias("sv")).order_by("k")
    first = q.collect()
    second = q.collect()
    assert first == second


def test_donating_paths_match_the_oracle():
    """Every pipeline path family, donating where the process can, returns
    the CPU oracle's rows (in order, where the query fixes one)."""
    for i, ordered in enumerate((True, True, False, True)):
        assert_tpu_cpu_equal(lambda s, i=i: _pipeline_queries(s)[i],
                             ignore_order=not ordered)


def test_donating_programs_bypass_persistent_cache():
    """XLA:CPU mishandles donation aliasing in executables DESERIALIZED
    from the persistent compilation cache (use-after-free; jax 0.4.37).
    Donating programs must therefore never be written to it: their
    compiles run inside the no-persist scope with the cache hooks
    patched."""
    assert CR.donation_supported()
    from jax._src import compilation_cache as cc
    # hooks installed (wrapped functions carry the originals' names)
    assert cc.get_executable_and_time.__wrapped__ is not None
    assert cc.put_executable_and_time.__wrapped__ is not None
    with CR._no_persist_scope():
        assert cc.get_executable_and_time("k", None, None) == (None, None)
        assert cc.put_executable_and_time("k", "m", None, None, 0) is None


def test_donation_guard_catches_use_after_donate():
    """The guard itself must detect a genuine use-after-donate."""
    import jax.numpy as jnp
    donating = CR.instrumented_jit(lambda x: x + 1, label="guardtest",
                                   donate_argnums=(0,))
    plain = CR.instrumented_jit(lambda x: x * 2, label="guardtest2")
    with CR.donation_guard():
        x = jnp.arange(8, dtype=jnp.float32)
        donating(x)
        with pytest.raises(AssertionError, match="use-after-donate"):
            plain(x)


# ---------------------------------------------------------------------------
# async partition overlap + bulk D2H
# ---------------------------------------------------------------------------


def _multi_part_query(s):
    df = s.create_dataframe({
        "k": [i % 11 for i in range(600)],
        "v": [float(i) for i in range(600)],
    }, num_partitions=4)
    return (df.filter(df["v"] < 500.0)
              .group_by("k").agg(F.sum("v").alias("sv"),
                                 F.count("v").alias("c"))
              .order_by("k"))


def test_multi_partition_dispatch_matches_the_oracle():
    """Four partitions' programs dispatched before the one sizes sync:
    the rows are the CPU oracle's, in order."""
    assert_tpu_cpu_equal(_multi_part_query, ignore_order=False)


def test_bulk_collect_join_root(monkeypatch):
    """A nested-loop join as the plan root inlines nothing, so the plan is
    not pipeline-viable: it takes the bulk collect (all partitions
    dispatched, one sizes sync, one bulk D2H) and returns the CPU
    oracle's rows."""
    from spark_rapids_tpu.plan import physical

    roots = []
    bulk = physical._collect_device_bulk

    def counted(root, ctx):
        roots.append(type(root).__name__)
        return bulk(root, ctx)

    monkeypatch.setattr(physical, "_collect_device_bulk", counted)

    def q(s):
        left = s.create_dataframe({"k": [1, 2, 3, 4], "l": [10, 20, 30, 40]},
                                  num_partitions=2)
        right = s.create_dataframe({"j": [2, 3, 5], "r": [200, 300, 500]})
        return left.cross_join(right)

    assert_tpu_cpu_equal(q)
    assert roots == ["TpuNestedLoopJoinExec"], roots


def test_transfer_metrics_reported():
    s = tpu_session()
    q = _multi_part_query(s)
    q.collect()
    m = s.last_metrics
    for key in ("h2dBytes", "h2dTimeNs", "d2hBytes", "d2hTimeNs",
                "donatedBytes"):
        assert key in m, f"last_metrics missing {key}"
    assert m["h2dBytes"] > 0  # fresh (uncached) input staged this query
    assert m["d2hBytes"] > 0  # results came home


# ---------------------------------------------------------------------------
# stop-aware read-ahead channel
# ---------------------------------------------------------------------------


def test_readahead_channel_backpressure_and_stop():
    from spark_rapids_tpu.plan.physical import _ReadAheadChannel
    chan = _ReadAheadChannel(2)
    assert chan.put(1) and chan.put(2)
    blocked_result = []

    def producer():
        blocked_result.append(chan.put(3))  # blocks: channel full

    t = threading.Thread(target=producer)
    t.start()
    time.sleep(0.05)
    assert t.is_alive()  # back-pressured, not dropped
    t0 = time.monotonic()
    chan.stop()
    t.join(timeout=2.0)
    assert not t.is_alive()
    # condition-variable wake, not a poll-interval tail
    assert time.monotonic() - t0 < 0.2
    assert blocked_result == [False]
    assert chan.get() is None  # stopped + drained


def test_readahead_channel_fifo_and_drain():
    from spark_rapids_tpu.plan.physical import _ReadAheadChannel
    chan = _ReadAheadChannel(4)
    for i in range(3):
        assert chan.put(i)
    assert [chan.get() for _ in range(3)] == [0, 1, 2]
    got = []

    def consumer():
        got.append(chan.get())  # blocks: channel empty

    t = threading.Thread(target=consumer)
    t.start()
    time.sleep(0.05)
    chan.put("x")
    t.join(timeout=2.0)
    assert got == ["x"]


def test_readahead_scan_pipeline_still_works(tmp_path):
    """End-to-end through the read-ahead staging thread (depth > 0) with
    the new channel: a file-backed scan query."""
    s = tpu_session(**{"spark.rapids.sql.tpu.stage.readAheadBatches": 2,
                       "spark.rapids.sql.reader.batchSizeRows": 16})
    cpu = TpuSparkSession(RapidsConf({"spark.rapids.sql.enabled": False}))
    df = cpu.create_dataframe({"k": [i % 4 for i in range(100)],
                               "v": list(range(100))})
    path = str(tmp_path / "pq")
    df.write_parquet(path, mode="overwrite")
    out = (s.read.parquet(path).group_by("k")
           .agg(F.sum("v").alias("sv")).order_by("k").collect())
    exp = {0: 1200, 1: 1225, 2: 1250, 3: 1275}
    got = {r[0]: r[1] for r in out}
    assert got == exp
