"""Encoded corridor v2 tests: dictionary codes crossing the shuffle and
join layers (dict-aware shuffle matrix, shared/divergent/duplicate-entry
dictionary joins), gather_segments_kway's encoded merge, the adaptive
read-ahead controller, per-format dict decode (CSV/ORC), the page-level
chunk slabs, the per-thread reader handle cache, and the D2H invariant
that collected results never carry unmaterialized codes."""

import os
import threading

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import (
    HostBatch, device_to_host, device_to_host_many, host_to_device,
)
from spark_rapids_tpu.io.arrow_convert import arrow_to_host_batch

from compare import tpu_session

DICT_AWARE_OFF = {"spark.rapids.sql.tpu.exchange.dictAware.enabled": False}
JOIN_KEYS_OFF = {"spark.rapids.sql.tpu.join.dictKeys.enabled": False}
NO_COLLAPSE = {"spark.rapids.sql.tpu.exchange.collapseLocal": False}

DATA = {
    "i": (T.INT, [1, 2, None, 4, 5, 6, 7, None] * 30),
    "l": (T.LONG, [10, None, 30, 40, 50, 60, 70, 80] * 30),
    # low-cardinality strings with nulls and empties: the dictionary case
    "s": (T.STRING, ["aa", "bb", None, "bb", "", "cc", "aa", "cc"] * 30),
}


def _v2_session(**confs):
    return tpu_session(**{"spark.rapids.sql.tpu.scan.v2.enabled": True,
                          **NO_COLLAPSE, **confs})


def _cpu_session():
    return tpu_session(**{"spark.rapids.sql.enabled": False})


def _write_dict_parquet(tmp_path, name="pq", data=None, rows_per_group=60):
    import pyarrow.parquet as pq
    s = tpu_session()
    out = str(tmp_path / name)
    s.create_dataframe(data or DATA, num_partitions=2).write_parquet(out)
    files = [f for f in os.listdir(out) if f.endswith(".parquet")]
    big = pa.concat_tables(
        [pq.read_table(os.path.join(out, f)) for f in files])
    for f in files:
        os.remove(os.path.join(out, f))
    pq.write_table(big, os.path.join(out, "part-00000.parquet"),
                   row_group_size=rows_per_group)
    return out


def _rows(session, build):
    return sorted(build(session).collect(),
                  key=lambda r: tuple((v is None, str(v)) for v in r))


# -- dict-aware shuffle matrix ------------------------------------------------


def _shuffle_query(kind):
    def q(s, out):
        df = s.read.parquet(out)
        if kind == "hash":
            return df.group_by("s").agg(F.count("i").alias("c"),
                                        F.sum("l").alias("sl"))
        if kind == "range":
            return df.order_by("s", "i")
        return df.repartition(4)
    return q


@pytest.mark.parametrize("kind", ["hash", "range", "roundrobin"])
def test_dict_shuffle_parity_matrix(tmp_path, kind):
    """Encoded pieces (codes + merged dictionary on the wire) are
    bit-identical to the materialized split and the CPU oracle across all
    three partitionings, over a string column with NULLs and empties —
    with the same sync count either way."""
    out = _write_dict_parquet(tmp_path)
    q = _shuffle_query(kind)
    s_on = _v2_session()
    got_on = _rows(s_on, lambda s: q(s, out))
    m_on = dict(s_on.last_metrics)
    s_off = _v2_session(**DICT_AWARE_OFF)
    got_off = _rows(s_off, lambda s: q(s, out))
    m_off = dict(s_off.last_metrics)
    want = _rows(_cpu_session(), lambda s: q(s, out))
    assert got_on == got_off, (got_on[:5], got_off[:5])
    assert got_on == want, (got_on[:5], want[:5])
    # the encoded wire format must not change the split's sync economics
    assert m_on.get("shuffleSyncs") == m_off.get("shuffleSyncs"), \
        (m_on.get("shuffleSyncs"), m_off.get("shuffleSyncs"))


def test_dict_shuffle_warm_repeat_compiles_nothing(tmp_path):
    """The encoded split's programs are shape-stable: a warm repeat of
    the same shuffle recompiles nothing."""
    out = _write_dict_parquet(tmp_path)
    s = _v2_session()
    q = _shuffle_query("hash")
    first = _rows(s, lambda s2: q(s2, out))
    again = _rows(s, lambda s2: q(s2, out))
    assert first == again
    assert s.last_metrics.get("compileCount", 0) == 0, s.last_metrics


def test_dict_shuffle_empty_pieces_parity(tmp_path):
    """More targets than distinct keys: empty target partitions flow
    through the encoded split identically to the materialized one."""
    data = {
        "i": (T.INT, list(range(40))),
        "s": (T.STRING, (["x", "y", None, "x"] * 10)),
    }
    out = _write_dict_parquet(tmp_path, data=data, rows_per_group=10)

    def q(s):
        df = s.read.parquet(out)
        return df.group_by("s").agg(F.count("i").alias("c"))
    confs = {"spark.sql.shuffle.partitions": 8}
    got_on = _rows(_v2_session(**confs), q)
    got_off = _rows(_v2_session(**confs, **DICT_AWARE_OFF), q)
    assert got_on == got_off
    assert len(got_on) == 3


def test_dict_shuffle_saved_metric_nonnegative(tmp_path):
    out = _write_dict_parquet(tmp_path)
    s = _v2_session()
    _rows(s, lambda s2: _shuffle_query("roundrobin")(s2, out))
    m = s.last_metrics
    assert m.get("shuffleEncodedBytesSaved", 0) >= 0, m


# -- gather_segments_kway encoded merge --------------------------------------


def _encoded_batch(strings, extra=None):
    """Device batch whose string column keeps its arrow dictionary."""
    cols = {"s": pa.array(strings, type=pa.string()).dictionary_encode()}
    if extra:
        cols.update(extra)
    hb = arrow_to_host_batch(pa.table(cols), keep_dictionary=True)
    db = host_to_device(hb)
    assert db.columns[0].codes is not None
    return db


def test_gather_segments_kway_encoded_merges_dictionaries():
    """Two inputs with DIFFERENT dictionaries: the encoded k-way gather
    shifts codes by static entry bases and packs both dictionaries; the
    materialized rows equal the plain path's."""
    from spark_rapids_tpu.kernels.layout import gather_segments_kway_run
    a = _encoded_batch(["aa", "bb", "aa", "cc"],
                       {"v": pa.array([1, 2, 3, 4], type=pa.int64())})
    b = _encoded_batch(["dd", "aa"],
                       {"v": pa.array([5, 6], type=pa.int64())})
    enc = gather_segments_kway_run([a, b], [1, 0], [3, 2],
                                   out_capacity=8, out_byte_caps=[64],
                                   keep_encoded=True)
    assert enc.columns[0].codes is not None  # stayed encoded
    plain = gather_segments_kway_run([a, b], [1, 0], [3, 2],
                                     out_capacity=8, out_byte_caps=[64])
    assert plain.columns[0].codes is None
    got = device_to_host_many([enc])[0].to_pydict()
    want = device_to_host_many([plain])[0].to_pydict()
    assert got == want
    assert got["s"] == ["bb", "aa", "cc", "dd", "aa"]
    assert got["v"] == [2, 3, 4, 5, 6]


def test_gather_segments_kway_mixed_parts_materialize():
    """One encoded + one plain input for the same column: no shared
    dictionary space exists, so the output is materialized — with the
    same rows."""
    from spark_rapids_tpu.kernels.layout import gather_segments_kway_run
    enc = _encoded_batch(["aa", "bb", "aa"])
    plain = host_to_device(HostBatch.from_pydict(
        {"s": (T.STRING, ["zz", "bb"])}))
    out = gather_segments_kway_run([enc, plain], [0, 0], [3, 2],
                                   out_capacity=8, out_byte_caps=[64],
                                   keep_encoded=True)
    assert out.columns[0].codes is None
    got = device_to_host_many([out])[0].to_pydict()
    assert got["s"] == ["aa", "bb", "aa", "zz", "bb"]


# -- encoded join keys --------------------------------------------------------


def _canon_eq(l_codes, r_codes, l_strs, r_strs):
    """Aligned codes must agree with content equality row-by-row."""
    l_codes = np.asarray(l_codes)[: len(l_strs)]
    r_codes = np.asarray(r_codes)[: len(r_strs)]
    for i, a in enumerate(l_strs):
        for j, b in enumerate(r_strs):
            assert (l_codes[i] == r_codes[j]) == (a == b), \
                (i, j, a, b, int(l_codes[i]), int(r_codes[j]))


def test_align_dict_codes_shared_dictionary_with_duplicates():
    """A shuffle-merged dictionary can hold DUPLICATE entries; raw code
    equality would miss matches, canonical alignment must not."""
    from spark_rapids_tpu.exprs.base import DevVal
    from spark_rapids_tpu.kernels.join import align_dict_codes
    idx = pa.array([0, 1, 2, 3], type=pa.int32())
    # entries 0 and 2 are both "aa"; 1 and 3 differ
    arr = pa.DictionaryArray.from_arrays(
        idx, pa.array(["aa", "bb", "aa", "cc"]))
    hb = arrow_to_host_batch(pa.table({"s": arr}), keep_dictionary=True)
    col = host_to_device(hb).columns[0]
    v = DevVal.from_column_encoded(col)
    pair = align_dict_codes(v, v)
    assert pair is not None
    strs = ["aa", "bb", "aa", "cc"]
    _canon_eq(pair[0], pair[1], strs, strs)


def test_align_dict_codes_divergent_dictionaries():
    """Different dictionaries: the smaller side translates into the
    larger; unmatched entries get sentinel codes that equal nothing."""
    from spark_rapids_tpu.exprs.base import DevVal
    from spark_rapids_tpu.kernels.join import align_dict_codes
    l_strs = ["aa", "bb", "zz", "aa"]
    r_strs = ["bb", "qq", "aa", "bb", "aa"]
    lv = DevVal.from_column_encoded(_encoded_batch(l_strs).columns[0])
    rv = DevVal.from_column_encoded(_encoded_batch(r_strs).columns[0])
    pair = align_dict_codes(lv, rv)
    assert pair is not None
    _canon_eq(pair[0], pair[1], l_strs, r_strs)


def test_align_dict_codes_falls_back_when_oversized(monkeypatch):
    from spark_rapids_tpu.exprs.base import DevVal
    from spark_rapids_tpu.kernels.join import align_dict_codes
    lv = DevVal.from_column_encoded(_encoded_batch(["aa", "bb"]).columns[0])
    rv = DevVal.from_column_encoded(_encoded_batch(["bb", "cc"]).columns[0])
    assert align_dict_codes(lv, rv, max_cells=1) is None


def _join_data(tmp_path):
    left = {
        "s": (T.STRING, ["aa", "bb", None, "cc", "", "aa", "dd"] * 20),
        "v": (T.LONG, list(range(140))),
    }
    right = {
        "s": (T.STRING, ["bb", "aa", "", None, "ee"] * 8),
        "w": (T.LONG, [i * 3 for i in range(40)]),
    }
    return (_write_dict_parquet(tmp_path, "left", left),
            _write_dict_parquet(tmp_path, "right", right, rows_per_group=10))


def test_encoded_join_parity_divergent_dictionaries(tmp_path):
    """Scanned-in string join keys ride as codes: each side carries its
    own file's dictionary (divergent), and the encoded hash join must be
    bit-identical to dictKeys-off and the CPU oracle."""
    lp, rp = _join_data(tmp_path)

    def q(s):
        left = s.read.parquet(lp)
        right = s.read.parquet(rp)
        return left.join(right, on="s", how="inner")
    confs = {"spark.sql.autoBroadcastJoinThreshold": -1,
             "spark.sql.shuffle.partitions": 4}
    got_on = _rows(_v2_session(**confs), q)
    got_off = _rows(_v2_session(**confs, **JOIN_KEYS_OFF), q)
    want = _rows(_cpu_session(), q)
    assert got_on == got_off
    assert got_on == want


def test_encoded_join_parity_shared_dictionary(tmp_path):
    """Self-join over the SAME scanned file: both sides' dictionaries
    hold the same entries (the shared/duplicate alignment path at the
    session level)."""
    lp, _ = _join_data(tmp_path)

    def q(s):
        a = s.read.parquet(lp)
        b = s.read.parquet(lp).group_by("s").agg(
            F.count("v").alias("c"))
        return a.join(b, on="s", how="inner")
    confs = {"spark.sql.autoBroadcastJoinThreshold": -1,
             "spark.sql.shuffle.partitions": 4}
    got_on = _rows(_v2_session(**confs), q)
    got_off = _rows(_v2_session(**confs, **JOIN_KEYS_OFF), q)
    want = _rows(_cpu_session(), q)
    assert got_on == got_off
    assert got_on == want


def test_encoded_broadcast_join_parity(tmp_path):
    lp, rp = _join_data(tmp_path)

    def q(s):
        left = s.read.parquet(lp)
        right = s.read.parquet(rp)
        return left.join(right, on="s", how="left")
    got_on = _rows(_v2_session(), q)
    got_off = _rows(_v2_session(**JOIN_KEYS_OFF), q)
    want = _rows(_cpu_session(), q)
    assert got_on == got_off
    assert got_on == want


def test_encoded_join_warm_repeat_compiles_nothing(tmp_path):
    lp, rp = _join_data(tmp_path)
    s = _v2_session(**{"spark.sql.autoBroadcastJoinThreshold": -1,
                       "spark.sql.shuffle.partitions": 4})

    def q(s2):
        return s2.read.parquet(lp).join(s2.read.parquet(rp), on="s")
    first = _rows(s, q)
    again = _rows(s, q)
    assert first == again
    assert s.last_metrics.get("compileCount", 0) == 0, s.last_metrics


# -- a filter hands on what a scan hands on: codes ----------------------------


def _after_filter(consumer):
    """A query whose filter sits over encoded columns, under ``consumer``."""
    def q(s, out, dim):
        df = s.read.parquet(out).filter(F.col("l") >= 30)
        if consumer == "collect":
            return df
        if consumer == "project":       # the bytes of every kept row
            return df.select(F.concat(F.col("s"), F.lit("|")).alias("t"),
                             F.length(F.col("s")).alias("n"), F.col("i"))
        if consumer == "sort":
            return df.order_by("s", "l")
        if consumer == "limit":
            return df.order_by("l", "s").limit(50)
        if consumer == "union":
            return df.union(s.read.parquet(out).filter(F.col("i") == 1))
        if consumer == "string_predicate":  # a second filter, on the string
            return df.filter(F.col("s") != "bb")
        assert consumer.startswith("join")
        return df.join(s.read.parquet(dim).filter(F.col("w") < 40), on="s")
    return q


@pytest.mark.parametrize("consumer", [
    "collect", "project", "sort", "limit", "union", "string_predicate",
    "join", "join_dict_keys_off", "join_dict_shuffle_off"])
def test_filter_keeps_codes_and_every_consumer_sees_its_rows(tmp_path,
                                                             consumer):
    """``TpuFilterExec`` compacts an encoded column's codes and decodes
    nothing; ``DeviceColumn``'s invariant holds all the same — a consumer
    that is not encode-aware materializes at its own entry, as it does
    over a scan — so every consumer returns the oracle's rows."""
    out = _write_dict_parquet(tmp_path, "fact")
    dim = _write_dict_parquet(tmp_path, "dim", {
        "s": (T.STRING, ["bb", "cc", "zz", None, ""] * 4),
        "w": (T.INT, list(range(20)) * 1)}, rows_per_group=7)
    q = _after_filter(consumer)
    confs = {"spark.sql.autoBroadcastJoinThreshold": -1,
             "spark.sql.shuffle.partitions": 3}
    if consumer == "join_dict_keys_off":
        confs.update(JOIN_KEYS_OFF)
    if consumer == "join_dict_shuffle_off":
        confs.update(DICT_AWARE_OFF)
    s = _v2_session(**confs)
    got = _rows(s, lambda s2: q(s2, out, dim))
    want = _rows(_cpu_session(), lambda s2: q(s2, out, dim))
    assert got == want and len(want) > 0
    m = s.last_metrics
    assert m.get("scanDictColumns", 0) > 0, m   # the filter saw codes

    # and the filter itself: codes in, codes out, the dictionary shared
    from spark_rapids_tpu.ops.tpu_exec import TpuFilterExec
    from spark_rapids_tpu.plan.physical import PhysicalOp
    from spark_rapids_tpu.exprs.base import ColumnRef, Literal
    from spark_rapids_tpu.exprs.predicates import GreaterThanOrEqual
    db = _encoded_batch(["aa", None, "bb", "aa", "cc"], {
        "l": pa.array([10, 30, 40, None, 50], type=pa.int64())})
    flt = TpuFilterExec(
        GreaterThanOrEqual(ColumnRef("l", T.LONG, True),
                           Literal(30, T.LONG)),
        PhysicalOp([], db.schema))
    kept = flt.batch_fn(db)
    col = kept.columns[0]
    assert col.codes is not None and col.data is db.columns[0].data
    assert col.mat_byte_cap == db.columns[0].mat_byte_cap
    assert device_to_host(kept).to_pydict() == {
        "s": [None, "bb", "cc"], "l": [30, 40, 50]}


# -- D2H invariant: codes never leak into collected results ------------------


def test_collected_host_batches_are_materialized():
    """device_to_host without keep_dictionary always materializes; only
    the spill path may keep dictionaries (and must keep codes sane)."""
    db = _encoded_batch(["aa", None, "bb", "aa"],
                        {"v": pa.array([1, 2, 3, 4], type=pa.int64())})
    hb = device_to_host(db)
    assert all(c.dictionary is None for c in hb.columns)
    assert hb.to_pydict()["s"] == ["aa", None, "bb", "aa"]
    kept = device_to_host(db, keep_dictionary=True)
    dc = kept.columns[0]
    assert dc.dictionary is not None
    codes = np.asarray(dc.values, dtype=np.int64)
    assert codes.min() >= 0 and codes.max() < len(dc.dictionary)
    # round-trip: a spilled encoded batch rehydrates to the same rows
    back = device_to_host(host_to_device(kept))
    assert back.to_pydict() == hb.to_pydict()


def test_plan_verify_reports_encoded_d2h_leak():
    from spark_rapids_tpu.analysis.plan_verify import check_encoded_corridor

    class Ctx:
        encoded_d2h_leaks = 2
    problems = check_encoded_corridor(None, Ctx())
    assert problems and "2" in problems[0]
    assert check_encoded_corridor(None, None) == []


# -- adaptive read-ahead ------------------------------------------------------


def test_explicit_depth_disables_adaptive(tmp_path):
    """scan.readAhead.depth set explicitly pins the window: the adaptive
    controller must never move it."""
    out = _write_dict_parquet(tmp_path, rows_per_group=20)
    s = _v2_session(**{"spark.rapids.sql.tpu.scan.readAhead.depth": 3})
    assert len(s.read.parquet(out).collect()) == 240
    assert s.last_metrics.get("readaheadDepthEffective") == 3, \
        s.last_metrics


def test_adaptive_depth_stays_clamped_and_recorded(tmp_path):
    out = _write_dict_parquet(tmp_path, rows_per_group=20)
    s = _v2_session(**{
        "spark.rapids.sql.tpu.scan.readAhead.adaptive.enabled": True,
        "spark.rapids.sql.tpu.scan.readAhead.maxDepth": 6})
    assert len(s.read.parquet(out).collect()) == 240
    d = s.last_metrics.get("readaheadDepthEffective", 0)
    assert 1 <= d <= 6, s.last_metrics
    assert s.runtime.semaphore.held_depth() == 0


def test_adaptive_off_keeps_static_depth(tmp_path):
    out = _write_dict_parquet(tmp_path, rows_per_group=20)
    s = _v2_session(**{
        "spark.rapids.sql.tpu.scan.readAhead.adaptive.enabled": False})
    assert len(s.read.parquet(out).collect()) == 240
    # static default depth reported unchanged
    assert s.last_metrics.get("readaheadDepthEffective") == 4, \
        s.last_metrics


# -- per-format dict decode (CSV / ORC) --------------------------------------


def test_orc_dict_encoding_v1_v2_parity(tmp_path):
    s = tpu_session()
    out = str(tmp_path / "orc")
    s.create_dataframe(DATA, num_partitions=2).write_orc(out)

    def q(s2):
        df = s2.read.orc(out)
        return df.group_by("s").agg(F.count("i").alias("c"),
                                    F.sum("l").alias("sl"))
    want = _rows(tpu_session(
        **{"spark.rapids.sql.tpu.scan.v2.enabled": False}), q)
    s_on = _v2_session()
    got_on = _rows(s_on, q)
    got_off = _rows(_v2_session(
        **{"spark.rapids.sql.tpu.scan.dictEncoding.enabled": False}), q)
    assert got_on == want
    assert got_off == want
    assert s_on.last_metrics.get("scanDictColumns", 0) > 0, \
        s_on.last_metrics


def test_csv_dict_encoding_v1_v2_parity(tmp_path):
    s = tpu_session()
    data = {
        "i": (T.INT, list(range(80))),
        # no nulls/empties: CSV cannot round-trip '' vs NULL
        "s": (T.STRING, ["red", "green", "blue", "red"] * 20),
    }
    out = str(tmp_path / "csv")
    s.create_dataframe(data, num_partitions=2).write_csv(out)

    def q(s2):
        df = s2.read.csv(out)
        return df.group_by("s").agg(F.count("i").alias("c"))
    want = _rows(tpu_session(
        **{"spark.rapids.sql.tpu.scan.v2.enabled": False}), q)
    s_on = _v2_session()
    got_on = _rows(s_on, q)
    got_off = _rows(_v2_session(
        **{"spark.rapids.sql.tpu.scan.dictEncoding.enabled": False}), q)
    assert got_on == want
    assert got_off == want
    assert s_on.last_metrics.get("scanDictColumns", 0) > 0, \
        s_on.last_metrics


def test_parquet_dictionary_typed_schema_enters_corridor(tmp_path):
    """A parquet file written from dictionary-encoded arrow arrays reads
    back with a dictionary<string> arrow schema (pyarrow round-trips the
    arrow schema through file metadata, so read_dictionary is never
    asked).  The scan must still feed the encoded corridor — and decode
    correctly when the corridor is off."""
    import pyarrow.parquet as pq
    cats = ["aa", "bb", None, "", "cc"]
    tb = pa.table({
        "i": pa.array(list(range(200)), pa.int64()),
        "s": pa.array([cats[i % len(cats)] for i in range(200)])
             .dictionary_encode(),
    })
    out = str(tmp_path / "dictschema")
    os.makedirs(out)
    pq.write_table(tb, os.path.join(out, "part-00000.parquet"),
                   row_group_size=50)

    def q(s2):
        df = s2.read.parquet(out)
        return df.group_by("s").agg(F.count("i").alias("c"))
    want = _rows(tpu_session(
        **{"spark.rapids.sql.tpu.scan.v2.enabled": False}), q)
    s_on = _v2_session()
    got_on = _rows(s_on, q)
    got_off = _rows(_v2_session(
        **{"spark.rapids.sql.tpu.scan.dictEncoding.enabled": False}), q)
    assert got_on == want
    assert got_off == want
    assert s_on.last_metrics.get("scanDictColumns", 0) > 0, \
        s_on.last_metrics


# -- page-level chunk slabs ---------------------------------------------------


def test_page_chunk_slabs_parity_one_big_row_group(tmp_path):
    """A single huge row group split into column slabs decodes to the
    same rows as the whole-row-group path (consumer-side zip merge)."""
    import pyarrow.parquet as pq
    rng = np.random.RandomState(9)
    n = 5000
    out = str(tmp_path / "big_rg")
    os.makedirs(out)
    pq.write_table(pa.table({
        "k": pa.array(rng.randint(0, 100, n).astype(np.int64)),
        "v": pa.array(rng.rand(n).round(4)),
        "s": pa.array(np.array([f"t{i % 13}" for i in range(n)],
                               dtype=object)),
    }), os.path.join(out, "part-00000.parquet"), row_group_size=n)

    def q(s):
        df = s.read.parquet(out)
        return df.group_by("s").agg(F.count("k").alias("c"),
                                    F.sum("v").alias("sv"))
    want = _rows(_v2_session(), q)
    s = _v2_session(
        **{"spark.rapids.sql.tpu.scan.pageChunk.minBytes": 1024})
    got = _rows(s, q)
    assert got == want
    assert s.runtime.semaphore.held_depth() == 0


def test_page_chunk_disabled_by_zero(tmp_path):
    out = _write_dict_parquet(tmp_path)

    def q(s):
        return s.read.parquet(out)
    want = _rows(_v2_session(), q)
    got = _rows(_v2_session(
        **{"spark.rapids.sql.tpu.scan.pageChunk.minBytes": 0}), q)
    assert got == want


# -- per-thread reader handle cache ------------------------------------------


class _Handle:
    def __init__(self):
        self.closed = False

    def close(self):
        self.closed = True


def test_cached_reader_hits_and_staleness(tmp_path):
    from spark_rapids_tpu.io.decode_pool import (
        cached_reader, clear_reader_cache, reader_cache_stats,
    )
    clear_reader_cache()
    p = str(tmp_path / "f.bin")
    with open(p, "wb") as f:
        f.write(b"x" * 64)
    made = []

    def factory():
        h = _Handle()
        made.append(h)
        return h

    a = cached_reader("t", p, factory, 4)
    b = cached_reader("t", p, factory, 4)
    assert a is b and len(made) == 1
    hits, misses = reader_cache_stats()
    assert hits >= 1 and misses >= 1
    # rewritten file (different size -> different key): never stale
    with open(p, "wb") as f:
        f.write(b"y" * 128)
    c = cached_reader("t", p, factory, 4)
    assert c is not a and len(made) == 2
    # a different kind on the same path is a distinct handle
    d = cached_reader("t2", p, factory, 4)
    assert d is not c and len(made) == 3
    clear_reader_cache()


def test_cached_reader_lru_closes_evicted(tmp_path):
    from spark_rapids_tpu.io.decode_pool import (
        cached_reader, clear_reader_cache,
    )
    clear_reader_cache()
    paths = []
    for i in range(3):
        p = str(tmp_path / f"f{i}.bin")
        with open(p, "wb") as f:
            f.write(b"z" * (32 + i))
        paths.append(p)
    made = {}

    def factory_for(p):
        def factory():
            h = _Handle()
            made[p] = h
            return h
        return factory

    for p in paths:
        cached_reader("t", p, factory_for(p), 2)
    assert made[paths[0]].closed      # evicted past cache_size=2
    assert not made[paths[1]].closed
    assert not made[paths[2]].closed
    clear_reader_cache()
    assert made[paths[1]].closed and made[paths[2]].closed


def test_cached_reader_disabled_and_missing_file(tmp_path):
    from spark_rapids_tpu.io.decode_pool import cached_reader
    made = []

    def factory():
        h = _Handle()
        made.append(h)
        return h
    p = str(tmp_path / "g.bin")
    with open(p, "wb") as f:
        f.write(b"q" * 16)
    a = cached_reader("t", p, factory, 0)
    b = cached_reader("t", p, factory, 0)
    assert a is not b and len(made) == 2  # size<=0: cache bypassed
    missing = str(tmp_path / "nope.bin")
    c = cached_reader("t", missing, factory, 4)
    assert c is made[-1]  # stat failure: factory, uncached


def test_scan_reader_cache_hits_in_session(tmp_path):
    """Many row groups in one file: pool threads reopen the same path and
    must hit their thread-local handle cache."""
    from spark_rapids_tpu.io.decode_pool import reader_cache_stats
    out = _write_dict_parquet(tmp_path, rows_per_group=15)
    h0, _ = reader_cache_stats()
    s = _v2_session()
    assert len(s.read.parquet(out).collect()) == 240
    h1, _ = reader_cache_stats()
    assert h1 > h0, (h0, h1)


def test_scan_reader_cache_disabled_still_works(tmp_path):
    out = _write_dict_parquet(tmp_path, rows_per_group=15)
    s = _v2_session(
        **{"spark.rapids.sql.tpu.scan.fileHandleCache.size": 0})
    assert len(s.read.parquet(out).collect()) == 240
