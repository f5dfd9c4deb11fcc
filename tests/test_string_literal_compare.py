"""A string tested for equality with literals (``=``, ``<>``, ``<=>``,
``IN``, an exact ``LIKE``) never evaluates the literal on the device:
``exprs/strings.equals_any_literal`` decides it on a dictionary-encoded
column's entries, and on a plain column compares the column's hashes with the
literal's, computed on the host.  Every answer equals the CPU oracle on both
layouts; the encoded form's program holds nothing of the batch's byte size;
the plain form's literal hash is the hash the device gives a broadcast copy
of the literal; and the queries that take the dictionary form count it
(``dictLiteralCompares``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import (
    ColumnBatch, DeviceColumn, HostBatch, host_to_device,
)
from spark_rapids_tpu.exprs import (
    ColumnRef, EqualNullSafe, Equals, In, Like, Literal, NotEquals,
)
from spark_rapids_tpu.exprs.base import CpuEvalCtx, TpuEvalCtx, resolve
from spark_rapids_tpu.exprs.predicates import _string_eq_dev
from spark_rapids_tpu.exprs.strings import (
    equals_any_literal, hash_literal2, string_hash2,
)
from spark_rapids_tpu.io.arrow_convert import arrow_to_host_batch

from compare import cpu_session, tpu_session

# NULLs, the empty string, multi-byte UTF-8, and near misses of the
# literals ('MAI', 'MAILX': a prefix and an extension of 'MAIL')
STRINGS = ["MAIL", "SHIP", None, "AIR", "", "é", "MAIL", "TRUCK", "日本",
           None, "MAI", "MAILX", "SHIP", "", "é"]
S = ColumnRef("s")


def _plain(strings):
    batch = HostBatch.from_pydict({"s": (T.STRING, strings)})
    return host_to_device(batch)


def _dictionary(strings):
    hb = arrow_to_host_batch(
        pa.table({"s": pa.array(strings, type=pa.string()).dictionary_encode()}),
        keep_dictionary=True)
    db = host_to_device(hb)
    assert db.columns[0].codes is not None
    return db


def _padded_dictionary(strings):
    """An encoded column whose dictionary holds more entries than its rows
    use — a live entry no row names, and empty padding entries — and
    whose invalid rows (the NULLs and the batch's padding) carry codes
    outside the dictionary."""
    live = list(dict.fromkeys(s for s in strings if s is not None))
    entries = live + ["UNUSED"] + [""] * 5
    raw = [e.encode("utf-8") for e in entries]
    offsets = np.concatenate([[0], np.cumsum([len(r) for r in raw])])
    data = np.zeros(-(-max(int(offsets[-1]), 1) // 16) * 16, dtype=np.uint8)
    data[:offsets[-1]] = np.frombuffer(b"".join(raw), dtype=np.uint8)
    cap = 16
    codes = np.full(cap, 1 << 20, dtype=np.int32)
    codes[-1] = -7
    valid = np.zeros(cap, dtype=np.bool_)
    for i, s in enumerate(strings):
        if s is not None:
            codes[i], valid[i] = live.index(s), True
    mat = sum(len(s.encode("utf-8")) for s in strings if s is not None)
    col = DeviceColumn(T.STRING, jnp.asarray(data), jnp.asarray(valid),
                       jnp.asarray(offsets, dtype=jnp.int32),
                       jnp.asarray(codes), -(-max(mat, 1) // 16) * 16)
    schema = T.Schema([T.Field("s", T.STRING)])
    return ColumnBatch(schema, [col], jnp.asarray(len(strings), jnp.int32), cap)


LAYOUTS = {"plain": _plain, "dictionary": _dictionary,
           "padded_dictionary": _padded_dictionary}

CASES = {
    "eq": lambda: Equals(S, Literal("MAIL")),
    "eq_literal_left": lambda: Equals(Literal("MAIL"), S),
    "ne": lambda: NotEquals(S, Literal("SHIP")),
    "ne_literal_left": lambda: NotEquals(Literal("SHIP"), S),
    "null_safe": lambda: EqualNullSafe(S, Literal("AIR")),
    "null_safe_literal_left": lambda: EqualNullSafe(Literal("AIR"), S),
    "in": lambda: In(S, ["MAIL", "SHIP"]),
    "in_null_option": lambda: In(S, ["MAIL", None]),
    "in_only_null": lambda: In(S, [None]),
    "eq_empty": lambda: Equals(S, Literal("")),
    "ne_empty": lambda: NotEquals(S, Literal("")),
    "in_empty": lambda: In(S, ["", "AIR"]),
    "eq_absent": lambda: Equals(S, Literal("ABSENT")),
    "in_absent": lambda: In(S, ["ABSENT", "NOPE"]),
    "eq_prefix_of_entries": lambda: Equals(S, Literal("MAI")),
    "eq_multibyte": lambda: Equals(S, Literal("é")),
    "in_multibyte": lambda: In(S, ["日本", "MAIL", "MAIL"]),
    "like_exact": lambda: Like(S, "MAIL"),
    "like_exact_multibyte": lambda: Like(S, "日本"),
    "like_exact_empty": lambda: Like(S, ""),
}


def _device_answer(expr, batch, n):
    def run(b):
        v = expr.tpu_eval(TpuEvalCtx(b))
        return v.data, v.validity
    data, valid = jax.jit(run)(batch)
    return [bool(x) if ok else None
            for x, ok in zip(np.asarray(data)[:n], np.asarray(valid)[:n])]


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_answers_equal_the_cpu_oracle(case, layout):
    host = HostBatch.from_pydict({"s": (T.STRING, STRINGS)})
    expr = resolve(CASES[case](), host.schema)
    cpu = expr.cpu_eval(CpuEvalCtx(host))
    want = [bool(x) if ok else None for x, ok in zip(cpu.values, cpu.validity)]
    got = _device_answer(expr, LAYOUTS[layout](STRINGS), len(STRINGS))
    assert got == want, (case, layout, list(zip(STRINGS, got, want)))


def _avals(jaxpr):
    """Every value's abstract shape in ``jaxpr`` and the jaxprs it holds."""
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            if hasattr(v, "aval"):
                yield eqn.primitive.name, v.aval
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _avals(inner)


def _largest(fn, batch):
    """(the largest element count of any value in ``fn``'s program, the
    names of the primitives that make values of the byte buffer's size)."""
    jaxpr = jax.make_jaxpr(fn)(batch).jaxpr
    cap = batch.capacity
    sizes = [int(np.prod(a.shape)) for _, a in _avals(jaxpr)
             if hasattr(a, "shape")]
    byte_sized = {name for name, a in _avals(jaxpr)
                  if hasattr(a, "shape") and int(np.prod(a.shape)) >= 4 * cap}
    return max(sizes), byte_sized


def test_an_encoded_filter_holds_nothing_of_the_batchs_byte_size():
    """``s IN ('MAIL', 'SHIP')`` over an encoded batch of 2^16 rows, and
    its filter's compaction: no value in the program is as large as the
    literal's broadcast (capacity x 4 bytes), so neither the copy nor a
    ``searchsorted`` over its byte positions is there.  The broadcast
    literal itself, traced alone, is that large: the test sees it."""
    from spark_rapids_tpu.kernels.layout import compact
    cap = 1 << 16
    modes = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
    batch = _dictionary([modes[i % 7] for i in range(cap)])
    assert batch.capacity == cap
    expr = resolve(In(S, ["MAIL", "SHIP"]), batch.schema)

    def filt(b):
        v = expr.tpu_eval(TpuEvalCtx(b))
        return compact(b, v.validity & v.data, keep_encoded=True)

    largest, byte_sized = _largest(filt, batch)
    assert largest < 4 * cap and not byte_sized, byte_sized
    _, broadcast = _largest(
        lambda b: Literal("MAIL").tpu_eval(TpuEvalCtx(b)).data, batch)
    assert broadcast


@pytest.mark.parametrize("s", ["MAIL", "SHIP", "", "é", "日本語",
                               "x" * 70, "\x00a"])
def test_the_literal_hash_is_the_hash_of_its_broadcast(s):
    """Path 2's inputs: ``hash_literal2`` on the host equals
    ``string_hash2`` of the literal broadcast to a batch, in every row, for
    ASCII, non-ASCII, empty and long strings."""
    batch = _plain(["pad"] * 5)
    h1, h2 = jax.jit(lambda b: string_hash2(
        Literal(s).tpu_eval(TpuEvalCtx(b))))(batch)
    e1, e2 = hash_literal2(s)
    assert set(np.asarray(h1).tolist()) == {e1}
    assert set(np.asarray(h2).tolist()) == {e2}


@pytest.mark.parametrize("s", ["MAIL", "", "é", "ABSENT", "MAI"])
def test_a_plain_column_gets_the_broadcast_comparisons_answer_bit_for_bit(s):
    """On plain bytes the set test is the parent's test of the column with
    a broadcast literal, every row, padding rows included."""
    batch = _plain(STRINGS)

    def both(b):
        v = resolve(S, b.schema).tpu_eval(TpuEvalCtx(b))
        lit = Literal(s).tpu_eval(TpuEvalCtx(b))
        return equals_any_literal(v, (s,)), _string_eq_dev(v, lit)

    new, old = jax.jit(both)(batch)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


# -- the counter ---------------------------------------------------------------

ORDERS = 100
LINES = 600
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
MODES = ["MAIL", "SHIP", "AIR", "TRUCK", "RAIL", "FOB", "REG AIR"]


def _tables(tmp_path, rows_per_group):
    import pyarrow.parquet as pq
    lines = pa.table({
        "l_orderkey": pa.array([i % ORDERS for i in range(LINES)], pa.int64()),
        "l_shipmode": pa.array([MODES[i % 7] if i % 11 else None
                                for i in range(LINES)]),
        "l_quantity": pa.array([float(i % 50) for i in range(LINES)])})
    orders = pa.table({
        "o_orderkey": pa.array(list(range(ORDERS)), pa.int64()),
        "o_orderpriority": pa.array([PRIORITIES[i % 5 if i % 3 else 2]
                                     for i in range(ORDERS)])})
    dirs = {}
    for name, table in (("lineitem", lines), ("orders", orders)):
        d = tmp_path / name
        d.mkdir()
        pq.write_table(table, str(d / "part-00000.parquet"),
                       row_group_size=rows_per_group)
        dirs[name] = str(d)
    return dirs


Q12_SHAPED = """
SELECT l_shipmode,
       sum(CASE WHEN o_orderpriority = '1-URGENT' OR o_orderpriority = '2-HIGH'
           THEN 1 ELSE 0 END) AS high_line_count,
       sum(CASE WHEN o_orderpriority <> '1-URGENT'
                 AND o_orderpriority <> '2-HIGH' THEN 1 ELSE 0 END)
           AS low_line_count
FROM orders, lineitem
WHERE o_orderkey = l_orderkey AND l_shipmode IN ('MAIL', 'SHIP')
  AND l_quantity < 40.0
GROUP BY l_shipmode ORDER BY l_shipmode"""


def _run(session, dirs, build):
    for name, d in dirs.items():
        session.read.parquet(d).cache().create_or_replace_temp_view(name)
    build(session).collect()                 # the first run traces
    return build(session).collect(), dict(session.last_metrics)


def test_a_q12_shaped_query_counts_one_test_a_lineitem_batch(tmp_path):
    """The ``IN`` on the cached, dictionary-encoded ``l_shipmode`` below
    the join is decided on each lineitem batch's dictionary, once a batch
    (its stage notes it when traced, and every run counts it again); the
    ``o_orderpriority`` tests after the join see plain bytes and do not
    count.  The answer is the CPU session's."""
    dirs = _tables(tmp_path, rows_per_group=200)
    rows, m = _run(tpu_session(), dirs, lambda s: s.sql(Q12_SHAPED))
    want, _ = _run(cpu_session(), dirs, lambda s: s.sql(Q12_SHAPED))
    assert rows == want and len(rows) == 2
    assert m["filterCompactedBatches"] == 3      # 600 lines, 200 a batch
    assert m["dictLiteralCompares"] == 3


def test_a_q6_shaped_query_counts_none(tmp_path):
    dirs = _tables(tmp_path, rows_per_group=200)

    def q6(s):
        return s.table("lineitem").filter(
            (F.col("l_quantity") < 24.0) & (F.col("l_quantity") >= 2.0)
        ).agg(F.sum("l_quantity").alias("revenue"))

    rows, m = _run(tpu_session(), dirs, q6)
    want, _ = _run(cpu_session(), dirs, q6)
    assert rows == want
    assert m["dictLiteralCompares"] == 0


def test_only_the_dictionary_form_is_noted():
    """Traced inside a stage program, the dictionary form notes itself and
    the plain form notes nothing."""
    from spark_rapids_tpu.exprs.strings import DICT_LITERAL_COMPARES
    from spark_rapids_tpu.plan.pipeline import collect_stage_notes
    for make, want in ((_dictionary, [(DICT_LITERAL_COMPARES, 1, None)]),
                       (_plain, [])):
        batch = make(STRINGS)
        expr = resolve(In(S, ["MAIL", "SHIP"]), batch.schema)
        with collect_stage_notes() as notes:
            jax.make_jaxpr(lambda b: expr.tpu_eval(TpuEvalCtx(b)).data)(batch)
        assert notes == want

