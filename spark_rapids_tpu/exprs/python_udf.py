"""Python UDF expressions.

``PythonUDF`` (row-at-a-time) and ``PandasUDF`` (vectorized over numpy/
pandas) evaluate host-side only; on a TPU plan the projection containing one
falls back to CPU, which — given the automatic device<->host transitions —
reproduces the reference's GpuArrowEvalPythonExec data flow
(GpuArrowEvalPythonExec.scala:484): device batch -> host columnar -> python
-> staged back to the device, with the semaphore released while python runs.

When ``spark.rapids.sql.udfCompiler.enabled`` is set, the planner first
tries :func:`spark_rapids_tpu.udf.compiler.compile_udf` to decompile the
function's bytecode into engine expressions so the whole projection stays on
the TPU (udf-compiler analogue).
"""

from __future__ import annotations

from typing import Callable, List, Optional

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exprs.base import CpuVal, Expression


class PythonUDF(Expression):
    # opaque python: it may read a clock, a file, its own call count
    context_free = False

    def __init__(self, fn: Callable, return_type: T.DataType,
                 *children: Expression, name: Optional[str] = None):
        self.fn = fn
        self.children = tuple(children)
        self.dtype = return_type
        self.nullable = True
        self.udf_name = name or getattr(fn, "__name__", "udf")

    def with_children(self, children):
        return type(self)(self.fn, self.dtype, *children,
                          name=self.udf_name)

    @property
    def name(self):
        return f"PythonUDF({self.udf_name})"

    def tpu_supported(self, conf):
        return ("python row UDF runs via the host Arrow path; enable "
                "spark.rapids.sql.udfCompiler.enabled to attempt columnar "
                "compilation")

    def cpu_eval(self, ctx) -> CpuVal:
        args = [c.cpu_eval(ctx) for c in self.children]
        n = ctx.num_rows
        out = np.empty(n, dtype=object)
        validity = np.zeros(n, dtype=np.bool_)
        arg_lists = [a.to_column().to_list() for a in args]
        for i in range(n):
            r = self.fn(*[al[i] for al in arg_lists])
            if r is not None:
                out[i] = r
                validity[i] = True
        if self.dtype.is_string:
            values = np.array(["" if not v else str(o)
                               for o, v in zip(out, validity)], dtype=object)
        else:
            values = np.array([o if v else 0
                               for o, v in zip(out, validity)],
                              dtype=self.dtype.np_dtype)
        return CpuVal(self.dtype, values, validity)


class PandasUDF(PythonUDF):
    """Vectorized UDF: fn(pandas.Series...) -> pandas.Series."""

    def cpu_eval(self, ctx) -> CpuVal:
        import pandas as pd
        args = [c.cpu_eval(ctx) for c in self.children]
        series = [pd.Series(a.to_column().to_list()) for a in args]
        res = self.fn(*series)
        if not isinstance(res, pd.Series):
            res = pd.Series(res)
        validity = ~res.isna().to_numpy()
        if self.dtype.is_string:
            values = np.array([
                "" if not v else str(x)
                for x, v in zip(res.tolist(), validity)], dtype=object)
        else:
            filled = res.fillna(0)
            values = filled.to_numpy().astype(self.dtype.np_dtype)
        return CpuVal(self.dtype, values, validity)
