"""WHERE conjuncts and keys into the joins below them
(plan/join_pushdown.py, PR 36): the parser's FROM list, which conjunct of a
Filter above a join goes where on every join type, that the answers are
those of the plan as written (the CPU operators on the un-rewritten plan,
and a pandas merge), that a plan without a join comes back as the object it
was, and TPC-H Q12 as the source writes it against the benchmark's own
reference.  CPU backend: plans, counters and answers, never a time.
"""

import os
import sys

import pandas as pd
import pytest

from compare import cpu_session, tpu_session
from spark_rapids_tpu import functions as F
from spark_rapids_tpu import types as T
from spark_rapids_tpu.plan import join_pushdown as JP
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan import overrides as O
from spark_rapids_tpu.serve.excache import shared_plan_cache

NL, NR = 41, 29
LEFT = {
    "lid": (T.INT, list(range(NL))),
    # NULL keys, keys with several matches, keys with none
    "lk": (T.INT, [None if i % 9 == 4 else i % 13 for i in range(NL)]),
    "lv": (T.INT, [None if i % 7 == 3 else (i * 5) % 23 for i in range(NL)]),
    "ls": (T.STRING, [None if i % 10 == 6 else "ab"[i % 2] + str(i % 3)
                      for i in range(NL)]),
}
RIGHT = {
    "rid": (T.INT, list(range(NR))),
    "rk": (T.INT, [None if j % 8 == 5 else (j * 2) % 17 for j in range(NR)]),
    "rv": (T.INT, [None if j % 6 == 2 else (j * 3) % 19 for j in range(NR)]),
    "rs": (T.STRING, ["xy"[j % 2] for j in range(NR)]),
}


def _frames(s):
    return (s.create_dataframe(LEFT, num_partitions=3),
            s.create_dataframe(RIGHT, num_partitions=2))


def _nodes(plan):
    yield plan
    for c in plan.children:
        yield from _nodes(c)


def _as_written(monkeypatch):
    """The planner without the rules of this module: the plan as written."""
    monkeypatch.setattr(O, "push_filters_through_joins",
                        lambda plan: (plan, []))
    monkeypatch.setattr(O, "narrow_join_inputs", lambda plan: (plan, 0))
    shared_plan_cache().clear()


def _canon(rows):
    return sorted(tuple((v is None, v) for v in r) for r in rows)


# -- the oracle: a pandas merge, NULL keys never matching ----------------------

def _pandas_join(how):
    left = pd.DataFrame({k: pd.array(v[1], dtype=object)
                         for k, v in LEFT.items()})
    right = pd.DataFrame({k: pd.array(v[1], dtype=object)
                          for k, v in RIGHT.items()})
    if how == "cross":
        return left.merge(right, how="cross")
    inner = left[left.lk.notna()].merge(right[right.rk.notna()],
                                        left_on="lk", right_on="rk")
    if how == "inner":
        return inner
    if how == "left_semi":
        return left[left.lid.isin(inner.lid)]
    if how == "left_anti":
        return left[~left.lid.isin(inner.lid)]
    parts = [inner]
    if how in ("left", "full"):
        parts.append(left[~left.lid.isin(inner.lid)])
    if how in ("right", "full"):
        parts.append(right[~right.rid.isin(inner.rid)])
    out = pd.concat(parts, ignore_index=True)
    return out[list(inner.columns)]


def _pandas_rows(how, keep):
    """Rows of the join that ``keep`` (a row dict -> True / False / None)
    passes, NULLs as None."""
    frame = _pandas_join(how)
    rows = [tuple(None if pd.isna(v) else v for v in r)
            for r in frame.itertuples(index=False, name=None)]
    names = list(frame.columns)
    return [r for r in rows if keep(dict(zip(names, r))) is True]


def _lt(a, b):
    return None if a is None or b is None else a < b


def _gt(a, b):
    return None if a is None or b is None else a > b


# -- which conjunct goes where, on every join type ------------------------------

#: conjunct kind -> (the Column, the oracle's predicate over a row dict)
CONJUNCTS = {
    "left_only": (lambda: F.col("lv") > 5, lambda r: _gt(r["lv"], 5)),
    "right_only": (lambda: F.col("rv") < 12, lambda r: _lt(r["rv"], 12)),
    "both_sides": (lambda: F.col("lv") < F.col("rv"),
                   lambda r: _lt(r["lv"], r["rv"])),
    # the anti-join idiom: true only on the rows a left join pads
    "right_is_null": (lambda: F.col("rid").is_null(),
                      lambda r: r["rid"] is None),
    "left_is_null": (lambda: F.col("lid").is_null(),
                     lambda r: r["lid"] is None),
}

#: (join type, conjunct kind) -> conjuncts that move below the join.  What
#: is not listed stays in the Filter: moving it would change the answer (a
#: padded row must still meet the Filter) or it reads both sides.
MOVES = {
    ("inner", "left_only"): 1, ("inner", "right_only"): 1,
    ("inner", "right_is_null"): 1, ("inner", "left_is_null"): 1,
    ("cross", "left_only"): 1, ("cross", "right_only"): 1,
    ("left", "left_only"): 1, ("left", "left_is_null"): 1,
    ("right", "right_only"): 1, ("right", "right_is_null"): 1,
    ("left_semi", "left_only"): 1, ("left_anti", "left_only"): 1,
    ("left_semi", "left_is_null"): 1, ("left_anti", "left_is_null"): 1,
}

CASES = [(how, kind)
         for how in ("inner", "left", "right", "full", "left_semi",
                     "left_anti", "cross")
         for kind in CONJUNCTS
         if not (how in ("left_semi", "left_anti")
                 and kind in ("right_only", "both_sides", "right_is_null"))
         and not (how == "cross" and kind.endswith("is_null"))]


def _joined(s, how, kind):
    left, right = _frames(s)
    if how == "cross":
        df = left.cross_join(right)
    else:
        df = left.join(right, on=(F.col("lk") == F.col("rk")), how=how)
    return df.filter(CONJUNCTS[kind][0]())


@pytest.mark.parametrize("how,kind", CASES)
def test_conjunct_placement_and_answers(how, kind, monkeypatch):
    shared_plan_cache().clear()
    tpu = tpu_session(**{"spark.rapids.sql.test.enabled": True})
    df = _joined(tpu, how, kind)
    plan, notes = O.TpuOverrides(tpu.conf).rewrite_logical(df.plan)
    want_moved = MOVES.get((how, kind), 0)
    assert notes.pushed_join_filters == want_moved
    assert notes.join_keys_from_where == 0
    filters_above = [n for n in _nodes(plan) if isinstance(n, L.Filter)
                     and isinstance(n.children[0], L.Join)]
    assert len(filters_above) == 1 - want_moved
    if not want_moved:
        assert plan is df.plan       # nothing to do: the object it was
    got = df.collect()
    assert tpu.last_metrics["pushedJoinFilters"] == want_moved
    assert _canon(got) == _canon(_pandas_rows(how, CONJUNCTS[kind][1]))
    # the CPU operators, with the rule and on the plan as written
    assert _canon(_joined(cpu_session(), how, kind).collect()) == _canon(got)
    _as_written(monkeypatch)
    assert _canon(_joined(cpu_session(), how, kind).collect()) == _canon(got)


# -- WHERE equalities as join keys ----------------------------------------------

def _views(s):
    left, right = _frames(s)
    left.create_or_replace_temp_view("lt")
    right.create_or_replace_temp_view("rt")


KEY_TEXTS = {
    "from_list": "SELECT lid, rid FROM lt, rt WHERE lk = rk AND lv > 5",
    "from_list_reversed_equality":
        "SELECT lid, rid FROM lt, rt WHERE rk = lk AND lv > 5",
    "cross_join": "SELECT lid, rid FROM lt CROSS JOIN rt "
                  "WHERE lk = rk AND lv > 5",
    "second_key_on_an_inner_join":
        "SELECT lid, rid FROM lt JOIN rt ON lv = rv WHERE lk = rk AND lv > 5",
}


@pytest.mark.parametrize("name", sorted(KEY_TEXTS))
def test_where_equality_becomes_a_join_key(name, monkeypatch):
    shared_plan_cache().clear()
    tpu = tpu_session(**{"spark.rapids.sql.test.enabled": True})
    _views(tpu)
    df = tpu.sql(KEY_TEXTS[name])
    plan, notes = O.TpuOverrides(tpu.conf).rewrite_logical(df.plan)
    join = next(n for n in _nodes(plan) if isinstance(n, L.Join))
    assert join.how == "inner"
    assert [k.column for k in join.left_keys][-1] == "lk"
    assert [k.column for k in join.right_keys][-1] == "rk"
    assert (notes.join_keys_from_where, notes.pushed_join_filters) == (1, 1)
    assert not any(isinstance(n, L.Filter) and isinstance(n.children[0],
                                                          L.Join)
                   for n in _nodes(plan))
    got = df.collect()
    assert tpu.last_metrics["joinKeysFromWhere"] == 1
    assert "TpuNestedLoopJoin" not in tpu.plan_physical(df.plan).tree_string()
    # NULL keys never match, as NULL = NULL never passes a WHERE
    extra = (lambda r: r["lv"] == r["rv"]) \
        if name == "second_key_on_an_inner_join" else (lambda r: True)
    want = [(r[0], r[4]) for r in _pandas_rows(
        "inner", lambda r: _gt(r["lv"], 5) and extra(r))]
    assert _canon(got) == _canon(want)
    _as_written(monkeypatch)
    cpu = cpu_session()
    _views(cpu)
    assert _canon(cpu.sql(KEY_TEXTS[name]).collect()) == _canon(got)


def test_equality_of_two_types_stays_a_condition():
    s = tpu_session()
    left, _ = _frames(s)
    right = s.create_dataframe(
        {"rk": (T.LONG, [0, 1, 2, None]), "rv": (T.INT, [1, 2, 3, 4])})
    df = left.cross_join(right).filter(F.col("lk") == F.col("rk"))
    plan, notes = O.TpuOverrides(s.conf).rewrite_logical(df.plan)
    assert plan is df.plan and notes.join_keys_from_where == 0


# -- what must not move -----------------------------------------------------------

def test_nondeterministic_conjunct_stays_above_the_join(monkeypatch):
    shared_plan_cache().clear()
    s = tpu_session()

    def build(s):
        left, right = _frames(s)
        return left.join(right, on=(F.col("lk") == F.col("rk")), how="inner") \
            .filter((F.rand(7) < 2.0) & (F.col("lv") > 5))

    df = build(s)
    plan, notes = O.TpuOverrides(s.conf).rewrite_logical(df.plan)
    assert notes.pushed_join_filters == 1
    above = next(n for n in _nodes(plan) if isinstance(n, L.Filter)
                 and isinstance(n.children[0], L.Join))
    assert "Rand" in repr(above.condition) and \
        "lv" not in above.condition.references
    assert _canon(df.collect()) == _canon(
        _pandas_rows("inner", lambda r: _gt(r["lv"], 5)))


def test_a_name_on_both_sides_moves_nothing():
    from spark_rapids_tpu.exprs.base import ColumnRef, Literal
    from spark_rapids_tpu.exprs.predicates import GreaterThan
    s = tpu_session()
    left, _ = _frames(s)
    cross = left.cross_join(s.create_dataframe({"lv": (T.INT, [1, 2, 3])}))
    # lid is the left side's alone; lv says nothing about its side
    for column, moved in (("lid", 1), ("lv", 0)):
        plan = L.Filter(GreaterThan(ColumnRef(column, T.INT, True),
                                    Literal(3)), cross.plan)
        out, pushes = JP.push_filters_through_joins(plan)
        assert len(pushes) == moved and (out is plan) == (not moved)


def test_a_chain_of_filters_and_three_tables(monkeypatch):
    shared_plan_cache().clear()
    s = tpu_session(**{"spark.rapids.sql.test.enabled": True})
    _views(s)
    s.create_dataframe({"tk": (T.INT, [0, 2, 4, 6, None]),
                        "tv": (T.INT, [10, 20, 30, 40, 50])}) \
        .create_or_replace_temp_view("tt")
    text = ("SELECT lid, rid, tv FROM lt, rt, tt "
            "WHERE lk = rk AND rk = tk AND lv > 5 AND tv < 40")
    df = s.sql(text).filter(F.col("rid") > 1)
    plan, notes = O.TpuOverrides(s.conf).rewrite_logical(df.plan)
    joins = [n for n in _nodes(plan) if isinstance(n, L.Join)]
    assert [j.how for j in joins] == ["inner", "inner"]
    assert notes.join_keys_from_where == 2
    got = df.collect()
    assert "TpuNestedLoopJoin" not in s.plan_physical(df.plan).tree_string()
    _as_written(monkeypatch)
    cpu = cpu_session()
    _views(cpu)
    cpu.create_dataframe({"tk": (T.INT, [0, 2, 4, 6, None]),
                          "tv": (T.INT, [10, 20, 30, 40, 50])}) \
        .create_or_replace_temp_view("tt")
    assert _canon(cpu.sql(text).filter(F.col("rid") > 1).collect()) \
        == _canon(got) and got


def test_a_broadcast_hint_stays_on_the_joins_side():
    """What moves onto a hinted side goes UNDER the hint: the planner reads
    the hint on the join's child itself."""
    shared_plan_cache().clear()
    # a threshold only a hinted side (estimated at 0 bytes) comes under
    s = tpu_session(**{"spark.sql.autoBroadcastJoinThreshold": 0})
    left, right = _frames(s)
    df = left.join(F.broadcast(right), on=(F.col("lk") == F.col("rk")),
                   how="inner") \
        .filter(F.col("rv") < 12).group_by("ls").agg(F.count(F.lit(1)))
    plan, notes = O.TpuOverrides(s.conf).rewrite_logical(df.plan)
    join = next(n for n in _nodes(plan) if isinstance(n, L.Join))
    assert notes.pushed_join_filters == 1
    assert isinstance(join.children[1], L.BroadcastHint)
    assert join.children[1].schema.names == ["rk"]     # narrowed under it
    assert "TpuBroadcastHashJoin" in s.plan_physical(df.plan).tree_string()


# -- the parser's FROM list ---------------------------------------------------------

FROM_LISTS = {
    "two": ("SELECT lid, rid FROM lt, rt", ["cross"]),
    "aliases": ("SELECT lid, rid FROM lt AS a, rt b", ["cross"]),
    "three": ("SELECT lid FROM lt, rt, lt2", ["cross", "cross"]),
    # a comma binds looser than JOIN: lt x (rt JOIN lt2)
    "comma_then_join": ("SELECT lid FROM lt, rt JOIN lt2 ON rk = lk2",
                        ["cross", "inner"]),
    "subquery": ("SELECT lid FROM lt, (SELECT rk FROM rt WHERE rv > 3) q",
                 ["cross"]),
}


@pytest.mark.parametrize("name", sorted(FROM_LISTS))
def test_parser_from_list(name):
    s = tpu_session()
    _views(s)
    s.create_dataframe({"lk2": (T.INT, [1, 2]), "lid2": (T.INT, [7, 8])}) \
        .create_or_replace_temp_view("lt2")
    text, hows = FROM_LISTS[name]
    joins = [n for n in _nodes(s.sql(text).plan) if isinstance(n, L.Join)]
    assert [j.how for j in joins] == hows
    if name == "comma_then_join":
        assert isinstance(joins[0].children[1], L.Join)


# -- plans without a join ---------------------------------------------------------

def test_a_plan_without_a_join_comes_back_itself():
    s = tpu_session()
    left, _ = _frames(s)
    df = left.filter(F.col("lv") > 5).group_by("ls").agg(F.sum("lv"))
    assert JP.push_filters_through_joins(df.plan) == (df.plan, [])
    assert JP.narrow_join_inputs(df.plan) == (df.plan, 0)
    plan, notes = O.TpuOverrides(s.conf).rewrite_logical(df.plan)
    assert plan is df.plan
    assert (notes.pushed_join_filters, notes.join_keys_from_where) == (0, 0)
    df.collect()
    assert s.last_metrics["pushedJoinFilters"] == 0
    assert s.last_metrics["joinKeysFromWhere"] == 0
    assert "pushed" not in s.last_explain


# -- required columns ---------------------------------------------------------------

def test_required_columns_and_the_narrowed_join():
    s = tpu_session()
    left, right = _frames(s)
    df = left.join(right, on=(F.col("lk") == F.col("rk")), how="inner") \
        .filter(F.col("lv") > 5).group_by("rs").agg(F.count(F.lit(1)))
    plan, _ = JP.push_filters_through_joins(df.plan)
    need = JP.required_columns(plan)
    join = next(n for n in _nodes(plan) if isinstance(n, L.Join))
    assert need[id(plan)] is None                     # the root: all of it
    assert need[id(join)] == {"rs"}
    assert need[id(join.children[0])] == {"lk"}       # a Filter(lv > 5) ...
    assert need[id(join.children[0].children[0])] == {"lk", "lv"}
    assert need[id(join.children[1])] == {"rk", "rs"}
    narrow, dropped = JP.narrow_join_inputs(plan)
    join = next(n for n in _nodes(narrow) if isinstance(n, L.Join))
    assert [c.schema.names for c in join.children] == [["lk"], ["rk", "rs"]]
    assert dropped == 3 + 2
    assert all(isinstance(c, L.Project) for c in join.children)
    # an outer join reads its keys and what is read above it, no more
    df = left.join(right, on=(F.col("lk") == F.col("rk")), how="full") \
        .select("lid", "rv")
    narrow, _ = JP.narrow_join_inputs(df.plan)
    join = next(n for n in _nodes(narrow) if isinstance(n, L.Join))
    assert [c.schema.names for c in join.children] == [["lid", "lk"],
                                                       ["rk", "rv"]]
    # SELECT * above a join reads everything: nothing is inserted
    df = left.join(right, on=(F.col("lk") == F.col("rk")), how="inner")
    assert JP.narrow_join_inputs(df.plan) == (df.plan, 0)


# -- counters of the join and of the eager route -------------------------------------

def _in_batches(s, data, n):
    """``data`` as a scan of ``n`` host batches (``create_dataframe`` makes
    one)."""
    from spark_rapids_tpu.batch import HostBatch
    from spark_rapids_tpu.dataframe import DataFrame
    rows = len(next(iter(data.values()))[1])
    step = -(-rows // n)
    batches = [HostBatch.from_pydict(
        {k: (t, v[i:i + step]) for k, (t, v) in data.items()})
        for i in range(0, rows, step)]
    return DataFrame(L.InMemoryScan(batches, batches[0].schema, n), s)


def test_join_counters_and_compactions_on_the_eager_route():
    shared_plan_cache().clear()
    s = tpu_session(**{"spark.rapids.sql.test.enabled": True,
                       "spark.sql.autoBroadcastJoinThreshold": -1})
    _in_batches(s, LEFT, 3).create_or_replace_temp_view("lt")
    _in_batches(s, RIGHT, 2).create_or_replace_temp_view("rt")
    df = s.sql("SELECT ls, count(*) AS n FROM lt, rt "
               "WHERE lk = rk AND lv > 5 AND rv < 12 GROUP BY ls")
    want = _pandas_rows(
        "inner", lambda r: _gt(r["lv"], 5) and _lt(r["rv"], 12))
    for _ in range(2):   # the second run traces nothing and counts the same
        got = df.collect()
        m = s.last_metrics
        assert sum(r[1] for r in got) == len(want)
        assert m["joinPairs"] == len(want)
        # three batches of lt and two of rt, each compacted under the join
        assert m["filterCompactedBatches"] == 3 + 2
        # both sides were concatenated, so the host held their rows
        assert m["joinProbeRows"] == sum(
            1 for v in LEFT["lv"][1] if v is not None and v > 5)
        assert m["joinBuildRows"] == sum(
            1 for v in RIGHT["rv"][1] if v is not None and v < 12)
        # join_pairs and one join_bytes for the string column stitched
        assert m["joinSizeReads"] == 2
        assert m["pushedJoinFilters"] == 2 and m["joinKeysFromWhere"] == 1
    explain = s.last_explain
    assert "pushed 2 below Join(inner): " in explain
    assert "; keys 1 from WHERE: Equals(`lk`, `rk`)" in explain


# -- TPC-H Q12 as the source writes it, against the benchmark's own reference --------

@pytest.fixture(scope="module")
def bench():
    here = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, here)
    import harness
    yield harness
    sys.path.remove(here)


@pytest.mark.parametrize("seed", [7, 3600000123])
def test_tpch_q12_source_text_end_to_end(bench, seed, tmp_path, monkeypatch):
    """The rehearsal's row counts (12,002 lines, 3,000 orders), seeded data,
    the cell's session conf: CPU fallback forbidden."""
    shared_plan_cache().clear()
    monkeypatch.setattr(bench, "DATA_DIR", str(tmp_path))
    spec = bench.load_cell("tpch_sf1_join.q12")
    config = spec["config"]
    q = bench.load_query("q12")
    rows = bench.table_rows(config, 0.002)
    dirs = bench.ensure_dataset(config, q["module"].TABLES, rows, seed)
    s = bench.open_session(config, dirs, "cpu")
    df = s.sql(q["text"])
    got = df.collect()
    frames = bench.reference_frames(config, q["module"].TABLES, rows, seed)
    assert [tuple(r) for r in got] == q["module"].reference(frames)
    m = s.last_metrics
    assert m["pushedJoinFilters"] == 5 and m["joinKeysFromWhere"] == 1
    assert m["joinPairs"] == len(q["module"].joined(frames)) > 0
    assert m["foldedExprs"] == 2
    explain = s.last_explain
    assert "pushed 5 below Join(inner): In(`l_shipmode`" in explain
    assert "keys 1 from WHERE: Equals(`o_orderkey`, `l_orderkey`)" in explain
    assert "cannot run on TPU because expression" not in explain
    tree = s.plan_physical(df.plan).tree_string()
    assert "TpuShuffledHashJoin(inner)" in tree and "NestedLoop" not in tree
    # the join's inputs carry what is read: 2 columns a side
    plan, _ = O.TpuOverrides(s.conf).rewrite_logical(df.plan)
    join = next(n for n in _nodes(plan) if isinstance(n, L.Join))
    assert [c.schema.names for c in join.children] == [
        ["o_orderkey", "o_orderpriority"], ["l_orderkey", "l_shipmode"]]
    # a held statement asked again: the plan is found, the counters stand
    assert [tuple(r) for r in df.collect()] == [tuple(r) for r in got]
    m = s.last_metrics
    assert (m["planShapeHit"], m["pushedJoinFilters"],
            m["joinKeysFromWhere"], m["compileCount"]) == (1, 5, 1, 0)
