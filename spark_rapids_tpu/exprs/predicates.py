"""Comparison and boolean predicates (reference: predicates.scala, 631 LoC).

And/Or implement Kleene three-valued logic exactly as Spark does.  String
equality is evaluated on device via dual 64-bit polynomial hashes plus length
(config spark.rapids.sql.stringHashGroupJoin.enabled); ordering comparisons on
strings fall back to CPU.  A string tested for equality with literals (``=``,
``<>``, ``<=>``, ``IN``) never evaluates a literal on the device: the test is
``strings.equals_any_literal`` on the other side, decided on its dictionary
where it arrives encoded.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exprs.base import (
    BinaryExpression, CpuVal, DevVal, Expression, Literal, UnaryExpression,
    promote_cpu, promote_dev,
)


def _string_eq_dev(a: DevVal, b: DevVal):
    from spark_rapids_tpu.exprs.strings import string_hash2, string_lengths
    ha1, ha2 = string_hash2(a)
    hb1, hb2 = string_hash2(b)
    la, lb = string_lengths(a), string_lengths(b)
    return (ha1 == hb1) & (ha2 == hb2) & (la == lb)


def _baked_literal(e: Expression) -> bool:
    """``e`` is a literal compiled into the program as a constant (no
    ``slot``) that is NULL or a string: its value is known when the
    program is traced."""
    return isinstance(e, Literal) and e.slot is None and (
        e.value is None or e.dtype.is_string)


def _equals_string_literal(left: Expression, right: Expression, ctx):
    """``(value, eq)`` where one side is a non-NULL baked string literal:
    the other side evaluated, kept encoded where it is a bare column, and
    ``eq`` its rows' equality with the literal
    (``strings.equals_any_literal``).  None where neither side is one."""
    from spark_rapids_tpu.exprs.base import eval_maybe_encoded
    from spark_rapids_tpu.exprs.strings import equals_any_literal
    for value, lit in ((left, right), (right, left)):
        if _baked_literal(lit) and lit.value is not None:
            v = eval_maybe_encoded(value, ctx)
            return v, equals_any_literal(v, (str(lit.value),))
    return None


class _Comparison(BinaryExpression):
    def _resolve_type(self):
        self.dtype = T.BOOLEAN
        self.nullable = self.left.nullable or self.right.nullable

    def _compute(self, x, y):
        raise NotImplementedError

    def _supports_string(self) -> bool:
        return False

    def tpu_supported(self, conf):
        if self.left.dtype.is_string or self.right.dtype.is_string:
            if not self._supports_string():
                return "string ordering comparisons not supported on TPU"
        return None

    def tpu_eval(self, ctx) -> DevVal:
        if self.left.dtype.is_string and self._supports_string():
            tested = _equals_string_literal(self.left, self.right, ctx)
            if tested is not None:
                v, eq = tested
                return DevVal(T.BOOLEAN, self._from_string_eq(eq), v.validity)
            # Hash-based equality works directly on dictionary-encoded
            # columns — keep the encoding so the dictionary is hashed once.
            from spark_rapids_tpu.exprs.base import eval_maybe_encoded
            lv = eval_maybe_encoded(self.left, ctx)
            rv = eval_maybe_encoded(self.right, ctx)
        else:
            lv, rv = self.left.tpu_eval(ctx), self.right.tpu_eval(ctx)
        if lv.dtype.is_string:
            data = self._from_string_eq(_string_eq_dev(lv, rv))
            return DevVal(T.BOOLEAN, data, lv.validity & rv.validity)
        a, b, _ = promote_dev(lv, rv)
        return DevVal(T.BOOLEAN, self._compute(a.data, b.data),
                      a.validity & b.validity)

    def cpu_eval(self, ctx) -> CpuVal:
        lv, rv = self.left.cpu_eval(ctx), self.right.cpu_eval(ctx)
        if lv.dtype.is_string:
            x = np.array([str(v) for v in lv.values], dtype=object)
            y = np.array([str(v) for v in rv.values], dtype=object)
            data = self._compute(x, y)
            return CpuVal(T.BOOLEAN, np.asarray(data, dtype=np.bool_),
                          lv.validity & rv.validity)
        a, b, _ = promote_cpu(lv, rv)
        return CpuVal(T.BOOLEAN, np.asarray(self._compute(a.values, b.values),
                                            dtype=np.bool_),
                      a.validity & b.validity)

    def _from_string_eq(self, eq):
        """The comparison's answer from the rows' string equality."""
        raise NotImplementedError


class Equals(_Comparison):
    def _supports_string(self):
        return True

    def _compute(self, x, y):
        return x == y

    def _from_string_eq(self, eq):
        return eq


class NotEquals(_Comparison):
    def _supports_string(self):
        return True

    def _compute(self, x, y):
        return x != y

    def _from_string_eq(self, eq):
        return ~eq


class LessThan(_Comparison):
    def _compute(self, x, y):
        return x < y


class LessThanOrEqual(_Comparison):
    def _compute(self, x, y):
        return x <= y


class GreaterThan(_Comparison):
    def _compute(self, x, y):
        return x > y


class GreaterThanOrEqual(_Comparison):
    def _compute(self, x, y):
        return x >= y


class EqualNullSafe(BinaryExpression):
    """<=> : never NULL; NULL <=> NULL is true."""

    def _resolve_type(self):
        self.dtype = T.BOOLEAN
        self.nullable = False

    def tpu_supported(self, conf):
        return None

    def tpu_eval(self, ctx) -> DevVal:
        if self.left.dtype.is_string:
            tested = _equals_string_literal(self.left, self.right, ctx)
            if tested is not None:
                # the literal is never NULL: a NULL row reads false
                v, eq = tested
                return DevVal(T.BOOLEAN, eq & v.validity,
                              jnp.ones_like(v.validity, dtype=jnp.bool_))
            from spark_rapids_tpu.exprs.base import eval_maybe_encoded
            lv = eval_maybe_encoded(self.left, ctx)
            rv = eval_maybe_encoded(self.right, ctx)
        else:
            lv, rv = self.left.tpu_eval(ctx), self.right.tpu_eval(ctx)
        if lv.dtype.is_string:
            eq = _string_eq_dev(lv, rv)
        else:
            a, b, _ = promote_dev(lv, rv)
            eq = a.data == b.data
            lv, rv = a, b
        both_null = ~lv.validity & ~rv.validity
        data = jnp.where(both_null, True, eq & lv.validity & rv.validity)
        return DevVal(T.BOOLEAN, data, jnp.ones_like(data, dtype=jnp.bool_))

    def cpu_eval(self, ctx) -> CpuVal:
        lv, rv = self.left.cpu_eval(ctx), self.right.cpu_eval(ctx)
        if lv.dtype.is_string:
            eq = np.array([str(a) == str(b) for a, b in zip(lv.values, rv.values)],
                          dtype=np.bool_)
        else:
            a, b, _ = promote_cpu(lv, rv)
            eq = a.values == b.values
        both_null = ~lv.validity & ~rv.validity
        data = np.where(both_null, True, eq & lv.validity & rv.validity)
        return CpuVal(T.BOOLEAN, data.astype(np.bool_),
                      np.ones(len(data), dtype=np.bool_))


class And(BinaryExpression):
    def _resolve_type(self):
        self.dtype = T.BOOLEAN
        self.nullable = self.left.nullable or self.right.nullable

    def tpu_eval(self, ctx) -> DevVal:
        a, b = self.left.tpu_eval(ctx), self.right.tpu_eval(ctx)
        x = a.data & a.validity  # treat NULL as "not definitely true"
        y = b.data & b.validity
        false_a = a.validity & ~a.data
        false_b = b.validity & ~b.data
        validity = (a.validity & b.validity) | false_a | false_b
        return DevVal(T.BOOLEAN, x & y, validity)

    def cpu_eval(self, ctx) -> CpuVal:
        a, b = self.left.cpu_eval(ctx), self.right.cpu_eval(ctx)
        x = a.values.astype(np.bool_) & a.validity
        y = b.values.astype(np.bool_) & b.validity
        false_a = a.validity & ~a.values.astype(np.bool_)
        false_b = b.validity & ~b.values.astype(np.bool_)
        validity = (a.validity & b.validity) | false_a | false_b
        return CpuVal(T.BOOLEAN, x & y, validity)


class Or(BinaryExpression):
    def _resolve_type(self):
        self.dtype = T.BOOLEAN
        self.nullable = self.left.nullable or self.right.nullable

    def tpu_eval(self, ctx) -> DevVal:
        a, b = self.left.tpu_eval(ctx), self.right.tpu_eval(ctx)
        true_a = a.validity & a.data
        true_b = b.validity & b.data
        validity = (a.validity & b.validity) | true_a | true_b
        return DevVal(T.BOOLEAN, true_a | true_b, validity)

    def cpu_eval(self, ctx) -> CpuVal:
        a, b = self.left.cpu_eval(ctx), self.right.cpu_eval(ctx)
        true_a = a.validity & a.values.astype(np.bool_)
        true_b = b.validity & b.values.astype(np.bool_)
        validity = (a.validity & b.validity) | true_a | true_b
        return CpuVal(T.BOOLEAN, true_a | true_b, validity)


class Not(UnaryExpression):
    def _resolve_type(self):
        self.dtype = T.BOOLEAN
        self.nullable = self.child.nullable

    def tpu_eval(self, ctx) -> DevVal:
        v = self.child.tpu_eval(ctx)
        return DevVal(T.BOOLEAN, ~v.data.astype(jnp.bool_), v.validity)

    def cpu_eval(self, ctx) -> CpuVal:
        v = self.child.cpu_eval(ctx)
        return CpuVal(T.BOOLEAN, ~v.values.astype(np.bool_), v.validity)


class In(Expression):
    """value IN (literals...) — OR of equality tests (GpuInSet analogue).

    A string value whose options are all baked literals is ONE set test
    (``strings.equals_any_literal``) on the value evaluated once; a NULL
    option leaves a row that matches nothing NULL, as Spark's answer is."""

    def __init__(self, value: Expression, options):
        opts = tuple(o if isinstance(o, Expression) else Literal(o) for o in options)
        self.children = (value,) + opts
        self.dtype = T.BOOLEAN
        self.nullable = value.nullable

    def with_children(self, children):
        return In(children[0], children[1:])

    def _as_or(self) -> Expression:
        value = self.children[0]
        expr: Expression = Equals(value, self.children[1])
        for opt in self.children[2:]:
            expr = Or(expr, Equals(value, opt))
        return expr

    def tpu_eval(self, ctx):
        value, opts = self.children[0], self.children[1:]
        if value.dtype.is_string and all(_baked_literal(o) for o in opts):
            from spark_rapids_tpu.exprs.base import eval_maybe_encoded
            from spark_rapids_tpu.exprs.strings import equals_any_literal
            lits = tuple(str(o.value) for o in opts if o.value is not None)
            v = eval_maybe_encoded(value, ctx)
            hit = equals_any_literal(v, lits)
            if len(lits) < len(opts):
                return DevVal(T.BOOLEAN, hit, v.validity & hit)
            return DevVal(T.BOOLEAN, hit, v.validity)
        return self._as_or().tpu_eval(ctx)

    def cpu_eval(self, ctx):
        return self._as_or().cpu_eval(ctx)
