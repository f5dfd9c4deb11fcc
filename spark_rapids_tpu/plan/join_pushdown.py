"""What a Filter above joins hands the joins, and which columns a plan reads.

Spark's Catalyst does this before the plugin sees a plan
(``PushPredicateThroughJoin``, the key extraction of ``ReorderJoin`` /
``ExtractEquiJoinKeys``, ``ColumnPruning``); this engine plans by itself, so
``TpuOverrides.rewrite_logical`` runs it on every new plan object:

* :func:`push_filters_through_joins` — over a ``Filter`` directly above a
  ``Join``, (a) a conjunct ``left_col = right_col`` across the two sides of a
  cross or inner join becomes a join key (cross -> inner equi-join), (b) a
  deterministic conjunct that reads one side only moves below the join on
  that side, for the join types where that is sound, (c) anything else stays
  in the Filter.  TPC-H writes all 22 queries as ``FROM a, b WHERE a.k = b.k
  AND ...``; without the rule Q12 joins all of lineitem to all of orders and
  only then drops 99.4 % of the rows.
* :func:`required_columns` — for every node, the columns of its output that
  something above reads: one analysis over the logical plan, for whoever can
  use it (today :func:`narrow_join_inputs`; the scans' read lists are
  ROADMAP S2a).
* :func:`narrow_join_inputs` — a ``Project`` under a join side that carries
  columns nothing above reads.  The planner fuses it with the filter below
  it, so the compaction gathers, the exchange carries and the join stitches
  2 columns of lineitem where the table has 16.

All three are non-mutating, like their neighbours in ``plan/overrides.py``:
untouched subtrees come back as the ORIGINAL objects, and a plan without a
join comes back itself, so the session's per-object notes and the shape
fingerprint of every join-less plan are what they were.
"""

from __future__ import annotations

import copy
from typing import Dict, List, Optional, Sequence, Set, Tuple

from spark_rapids_tpu.exprs.base import ColumnRef, Expression
from spark_rapids_tpu.plan import logical as L

#: join types below whose LEFT side a left-only conjunct of a Filter above
#: may move (every output row carries one left row, unchanged); likewise
#: the right side.  ``full`` pads both sides with NULL rows: nothing moves.
_LEFT_PUSHABLE = ("inner", "cross", "left", "left_semi", "left_anti")
_RIGHT_PUSHABLE = ("inner", "cross", "right")


class JoinPush:
    """What one join was given: the conjuncts that moved below it and the
    WHERE equalities that became its keys.  ``how`` is the join's type
    after the rewrite."""

    __slots__ = ("how", "pushed", "keys")

    def __init__(self, how: str, pushed: List[Expression],
                 keys: List[Expression]):
        self.how = how
        self.pushed = pushed
        self.keys = keys

    def describe(self) -> str:
        line = f"pushed {len(self.pushed)} below Join({self.how})"
        if self.pushed:
            line += ": " + ", ".join(repr(c) for c in self.pushed)
        line += f"; keys {len(self.keys)} from WHERE"
        if self.keys:
            line += ": " + ", ".join(repr(c) for c in self.keys)
        return line


def split_conjuncts(e: Expression) -> List[Expression]:
    from spark_rapids_tpu.exprs.predicates import And
    if isinstance(e, And):
        return split_conjuncts(e.children[0]) + \
            split_conjuncts(e.children[1])
    return [e]


def _conjunction(conjuncts: Sequence[Expression]) -> Expression:
    from spark_rapids_tpu.exprs.predicates import And
    out = conjuncts[0]
    for c in conjuncts[1:]:
        out = And(out, c)
    return out


def deterministic(e: Expression) -> bool:
    """No class in ``e`` draws on anything but its children's values."""
    return not e.collect(lambda x: not x.context_free)


def _has_join(plan: L.LogicalPlan) -> bool:
    return isinstance(plan, L.Join) or any(_has_join(c) for c in plan.children)


def _with_children(node: L.LogicalPlan, children) -> L.LogicalPlan:
    if all(n is o for n, o in zip(children, node.children)):
        return node
    clone = copy.copy(node)
    clone.children = tuple(children)
    return clone


def _on_side(side: L.LogicalPlan, wrap) -> L.LogicalPlan:
    """``wrap(side)``, but UNDER a broadcast hint: the hint marks the
    join's side itself (``TpuOverrides._estimate_size`` reads it there)."""
    if isinstance(side, L.BroadcastHint):
        return _with_children(side, [_on_side(side.children[0], wrap)])
    return wrap(side)


def push_filters_through_joins(plan: L.LogicalPlan
                               ) -> Tuple[L.LogicalPlan, List[JoinPush]]:
    """The plan with every Filter directly above a join (or above a chain
    of Filters that ends on one) taken apart as the module docstring says,
    and one :class:`JoinPush` per join that was given something.  A
    conjunct that lands on a side that is itself a join goes on down
    (``FROM a, b, c``: the cross join of three becomes two equi-joins)."""
    pushes: List[JoinPush] = []
    if not _has_join(plan):
        return plan, pushes

    def rewrite(node: L.LogicalPlan) -> L.LogicalPlan:
        if isinstance(node, L.Filter):
            conjuncts, base = [], node
            while isinstance(base, L.Filter):
                conjuncts += split_conjuncts(base.condition)
                base = base.children[0]
            if isinstance(base, L.Join):
                join, rest = _push(conjuncts, base)
                if join is not base:
                    # what moved may go further down; what stayed stays
                    join = _with_children(
                        join, [rewrite(c) for c in join.children])
                    return L.Filter(_conjunction(rest), join) if rest \
                        else join
        return _with_children(node, [rewrite(c) for c in node.children])

    def _push(conjuncts: List[Expression], join: L.Join):
        left, right = join.children
        lnames = set(left.schema.names)
        rnames = set(right.schema.names)
        if join.how in ("left_semi", "left_anti"):
            rnames = set()   # the Filter above sees the left side only
        both = lnames & rnames   # a name on both sides says nothing
        to_left, to_right, keys, rest = [], [], [], []
        for c in conjuncts:
            refs = set(c.references)
            if not refs or refs & both or not deterministic(c):
                rest.append(c)
            elif refs <= lnames and join.how in _LEFT_PUSHABLE:
                to_left.append(c)
            elif refs <= rnames and join.how in _RIGHT_PUSHABLE:
                to_right.append(c)
            elif join.how in ("inner", "cross") and \
                    _key_pair(c, lnames, rnames) is not None:
                keys.append(c)
            else:
                rest.append(c)
        if not (to_left or to_right or keys):
            return join, conjuncts
        if to_left:
            left = _on_side(
                left, lambda x: L.Filter(_conjunction(to_left), x))
        if to_right:
            right = _on_side(
                right, lambda x: L.Filter(_conjunction(to_right), x))
        pairs = [_key_pair(c, lnames, rnames) for c in keys]
        how = "inner" if keys else join.how
        pushes.append(JoinPush(how, to_left + to_right, keys))
        return L.Join(left, right,
                      list(join.left_keys) + [p[0] for p in pairs],
                      list(join.right_keys) + [p[1] for p in pairs],
                      how, join.condition), rest

    return rewrite(plan), pushes


def _key_pair(c: Expression, lnames: Set[str], rnames: Set[str]
              ) -> Optional[Tuple[ColumnRef, ColumnRef]]:
    """``(left key, right key)`` where ``c`` is ``column = column`` across
    the two sides with one type on both (the join hashes its keys' bits:
    an INT against a BIGINT stays a condition of the Filter)."""
    from spark_rapids_tpu.exprs.predicates import Equals
    if not isinstance(c, Equals):
        return None
    a, b = c.children
    if not (isinstance(a, ColumnRef) and isinstance(b, ColumnRef)) or \
            a.dtype != b.dtype:
        return None
    if a.column in lnames and b.column in rnames:
        return a, b
    if b.column in lnames and a.column in rnames:
        return b, a
    return None


def _refs(exprs) -> Set[str]:
    return {name for e in exprs if e is not None for name in e.references}


def required_columns(plan: L.LogicalPlan
                     ) -> Dict[int, Optional[Set[str]]]:
    """``id(node)`` -> the names of the node's output columns that the
    plan above it reads, or None where it reads them all (the root, a
    node under an operator this analysis does not look into).  A node
    seen under two parents takes the union."""
    need: Dict[int, Optional[Set[str]]] = {}

    def ask(node: L.LogicalPlan, cols: Optional[Set[str]]) -> None:
        if cols is not None:
            cols = cols & set(node.schema.names)
        seen = need.get(id(node), set())
        need[id(node)] = None if cols is None or seen is None \
            else seen | cols
        wanted = need[id(node)]
        for child, c in zip(node.children, _of_children(node, wanted)):
            ask(child, c)

    ask(plan, None)
    return need


def _of_children(node: L.LogicalPlan, wanted: Optional[Set[str]]
                 ) -> List[Optional[Set[str]]]:
    """What ``node`` reads of each child when ``wanted`` of its own
    output is read."""
    if isinstance(node, L.Project):
        # every expression is evaluated, read above or not (nothing here
        # prunes the Project itself)
        return [_refs(node.exprs)]
    if isinstance(node, L.Aggregate):
        return [_refs(node.keys) | _refs(a.fn for a in node.aggs)]
    if wanted is None and not isinstance(node, L.Join):
        return [None] * len(node.children)
    if isinstance(node, L.Filter):
        return [wanted | _refs([node.condition])]
    if isinstance(node, L.Sort):
        return [wanted | _refs(o.child for o in node.orders)]
    if isinstance(node, (L.Limit, L.Sample, L.BroadcastHint)):
        return [wanted]
    if isinstance(node, L.Join):
        left, right = node.children
        lnames, rnames = set(left.schema.names), set(right.schema.names)
        if lnames & rnames:
            return [None, None]
        cond = _refs([node.condition])
        out = set(node.schema.names) if wanted is None else wanted
        return [(out | cond) & lnames | _refs(node.left_keys),
                (out | cond) & rnames | _refs(node.right_keys)]
    # a cached relation is materialised whole; every other operator
    # (union, distinct, window, expand, generate, the pandas execs) is
    # taken to read all of its input
    return [None] * len(node.children)


def narrow_join_inputs(plan: L.LogicalPlan
                       ) -> Tuple[L.LogicalPlan, int]:
    """``plan`` with a Project of the columns :func:`required_columns`
    names on every join side that carries others, and how many columns
    were dropped that way."""
    if not _has_join(plan):
        return plan, 0
    need = required_columns(plan)
    dropped = 0

    def narrowed(old: L.LogicalPlan, new: L.LogicalPlan) -> L.LogicalPlan:
        nonlocal dropped
        cols = need.get(id(old))
        fields = old.schema.fields
        if not cols or len(cols) == len(fields):
            return new   # reads all of it, or nothing of it (count(*))
        keep = [f for f in fields if f.name in cols]
        dropped += len(fields) - len(keep)
        return _on_side(new, lambda x: L.Project(
            [ColumnRef(f.name, f.dtype, f.nullable) for f in keep],
            [f.name for f in keep], x))

    def rewrite(node: L.LogicalPlan) -> L.LogicalPlan:
        children = [rewrite(c) for c in node.children]
        if isinstance(node, L.Join):
            children = [narrowed(o, n)
                        for o, n in zip(node.children, children)]
        return _with_children(node, children)

    return rewrite(plan), dropped
