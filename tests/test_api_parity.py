"""API-parity validation (api_validation/ApiValidation.scala analogue):
checks that every exec/expression family in the reference's component
inventory (SURVEY.md section 2.5) has a counterpart in this framework, so
parity gaps show up as test failures instead of silent omissions."""

import importlib

import pytest

# reference exec (SURVEY.md 2.5) -> implementing class here (TPU + CPU)
EXEC_PARITY = {
    "GpuProjectExec": ("spark_rapids_tpu.ops.tpu_exec", "TpuProjectExec"),
    "GpuFilterExec": ("spark_rapids_tpu.ops.tpu_exec", "TpuFilterExec"),
    "GpuUnionExec": ("spark_rapids_tpu.ops.tpu_exec", "TpuUnionExec"),
    "GpuRangeExec": ("spark_rapids_tpu.ops.tpu_exec", "TpuRangeExec"),
    "GpuHashAggregateExec": ("spark_rapids_tpu.ops.tpu_exec",
                             "TpuHashAggregateExec"),
    "GpuSortExec": ("spark_rapids_tpu.ops.tpu_exec", "TpuSortExec"),
    "GpuShuffledHashJoinExec": ("spark_rapids_tpu.ops.tpu_exec",
                                "TpuShuffledHashJoinExec"),
    "GpuBroadcastHashJoinExec": ("spark_rapids_tpu.ops.tpu_exec",
                                 "TpuBroadcastHashJoinExec"),
    "GpuBroadcastNestedLoopJoinExec": ("spark_rapids_tpu.ops.tpu_exec",
                                       "TpuNestedLoopJoinExec"),
    "GpuCartesianProductExec": ("spark_rapids_tpu.kernels.join",
                                "cross_join"),
    "GpuBroadcastExchangeExec": ("spark_rapids_tpu.parallel.exchange",
                                 "CpuBroadcastExchangeExec"),
    "GpuShuffleExchangeExec": ("spark_rapids_tpu.parallel.exchange",
                               "TpuShuffleExchangeExec"),
    "GpuHashPartitioning": ("spark_rapids_tpu.parallel.partitioning",
                            "HashPartitioning"),
    "GpuRangePartitioning": ("spark_rapids_tpu.parallel.partitioning",
                             "RangePartitioning"),
    "GpuRoundRobinPartitioning": ("spark_rapids_tpu.parallel.partitioning",
                                  "RoundRobinPartitioning"),
    "GpuSinglePartitioning": ("spark_rapids_tpu.parallel.partitioning",
                              "SinglePartitioning"),
    "GpuWindowExec": ("spark_rapids_tpu.ops.window", "TpuWindowExec"),
    "GpuExpandExec": ("spark_rapids_tpu.ops.tpu_exec", "TpuExpandExec"),
    "GpuLocalLimitExec": ("spark_rapids_tpu.ops.tpu_exec",
                          "TpuLocalLimitExec"),
    "GpuCoalesceBatches": ("spark_rapids_tpu.ops.tpu_exec",
                           "TpuCoalesceBatchesExec"),
    "GpuRowToColumnarExec": ("spark_rapids_tpu.plan.physical",
                             "HostToDeviceExec"),
    "GpuColumnarToRowExec": ("spark_rapids_tpu.plan.physical",
                             "DeviceToHostExec"),
    "GpuArrowEvalPythonExec": ("spark_rapids_tpu.exprs.python_udf",
                               "PandasUDF"),
    "GpuParquetScan": ("spark_rapids_tpu.io.scan", "CpuFileScanExec"),
    "GpuOverrides": ("spark_rapids_tpu.plan.overrides", "TpuOverrides"),
    "RapidsMeta": ("spark_rapids_tpu.plan.overrides", "PlanMeta"),
    "RapidsBufferCatalog": ("spark_rapids_tpu.mem.catalog", "BufferCatalog"),
    "SpillableColumnarBatch": ("spark_rapids_tpu.mem.catalog",
                               "SpillableBatch"),
    "GpuSemaphore": ("spark_rapids_tpu.runtime.device", "TpuSemaphore"),
    "GpuDeviceManager": ("spark_rapids_tpu.runtime.device", "DeviceRuntime"),
    "RapidsConf": ("spark_rapids_tpu.config", "RapidsConf"),
    "TableCompressionCodec": ("spark_rapids_tpu.mem.codec", "Codec"),
    "JCudfSerialization": ("spark_rapids_tpu.native_rt",
                           "serialize_host_batch"),
    "udf-compiler": ("spark_rapids_tpu.udf.compiler", "compile_udf"),
    "ColumnarRdd": ("spark_rapids_tpu.ml", "to_device_batches"),
    "UCXShuffleTransport": ("spark_rapids_tpu.parallel.mesh_shuffle",
                            "make_exchange_fn"),
    # fault tolerance: the reference's retry/OOM machinery
    # (RmmRapidsRetryIterator's withRetry + RetryOOM classification) and the
    # task-retry delegation (SURVEY.md section 5) map to the unified
    # fault subsystem
    "RmmRapidsRetryIterator": ("spark_rapids_tpu.fault.retry",
                               "RetryPolicy"),
    "DeviceMemoryEventHandler": ("spark_rapids_tpu.mem.catalog",
                                 "run_with_oom_retry"),
    "TaskRetryLineage": ("spark_rapids_tpu.fault.recovery",
                         "run_partition_with_retry"),
}

# reference expression file (SURVEY.md 2.5 expression library) -> our module
EXPR_MODULE_PARITY = {
    "arithmetic.scala": "spark_rapids_tpu.exprs.arithmetic",
    "predicates.scala": "spark_rapids_tpu.exprs.predicates",
    "stringFunctions.scala": "spark_rapids_tpu.exprs.strings",
    "datetimeExpressions.scala": "spark_rapids_tpu.exprs.datetime",
    "AggregateFunctions.scala": "spark_rapids_tpu.exprs.aggregates",
    "mathExpressions.scala": "spark_rapids_tpu.exprs.mathexprs",
    "nullExpressions.scala": "spark_rapids_tpu.exprs.nullexprs",
    "conditionalExpressions.scala": "spark_rapids_tpu.exprs.conditional",
    "GpuCast": "spark_rapids_tpu.exprs.cast",
    "GpuWindowExpression": "spark_rapids_tpu.exprs.windows",
    "GpuRandomExpressions": "spark_rapids_tpu.exprs.misc",
    "GpuHashPartitioning-hash": "spark_rapids_tpu.exprs.hashing",
}


@pytest.mark.parametrize("ref", sorted(EXEC_PARITY.keys()))
def test_exec_parity(ref):
    mod_name, attr = EXEC_PARITY[ref]
    mod = importlib.import_module(mod_name)
    assert hasattr(mod, attr), f"{ref} has no counterpart {mod_name}.{attr}"


@pytest.mark.parametrize("ref", sorted(EXPR_MODULE_PARITY.keys()))
def test_expr_module_parity(ref):
    importlib.import_module(EXPR_MODULE_PARITY[ref])


def test_configs_docs_cover_full_registry():
    """docs/configs.md must include every registered conf — including ones
    defined in lazily-imported modules (catalog, multihost, python worker);
    a partial-registry regeneration silently drops rows."""
    import os

    import spark_rapids_tpu.config as C
    import spark_rapids_tpu.mem.catalog  # noqa: F401
    import spark_rapids_tpu.parallel.multihost  # noqa: F401
    import spark_rapids_tpu.runtime.python_worker  # noqa: F401
    import spark_rapids_tpu.session  # noqa: F401

    doc = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "configs.md")).read()
    missing = [e.key for e in C.registry()
               if not e.internal and e.key not in doc]
    assert not missing, f"configs.md missing: {missing}"


def test_pyspark_dataframe_api_surface():
    """pyspark-API surface the frontend commits to (grows per round)."""
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.dataframe import DataFrame, GroupedData

    df_methods = [
        "select", "filter", "with_column", "with_column_renamed", "drop",
        "join", "cross_join", "union", "distinct", "drop_duplicates",
        "order_by", "limit", "sample", "repartition", "coalesce",
        "group_by", "rollup", "cube", "grouping_sets", "agg", "explode",
        "dropna", "fillna", "describe", "intersect", "subtract",
        "cache", "unpersist", "collect", "show", "head", "take",
        "to_pandas", "write_parquet", "write_csv", "write_orc",
        "create_or_replace_temp_view",
    ]
    for m in df_methods:
        assert hasattr(DataFrame, m), f"DataFrame.{m} missing"
    gd_methods = ["agg", "count", "sum", "avg", "min", "max", "pivot",
                  "apply_in_pandas", "agg_in_pandas", "cogroup"]
    for m in gd_methods:
        assert hasattr(GroupedData, m), f"GroupedData.{m} missing"
    fns = ["col", "lit", "sum", "count", "avg", "min", "max", "first",
           "last", "count_distinct", "percentile", "stddev",
           "stddev_pop", "variance", "var_pop", "corr", "covar_pop",
           "covar_samp", "hex", "grouping_id", "when",
           "coalesce", "concat", "substring", "substring_index", "split",
           "initcap", "upper", "lower", "regexp_replace", "broadcast",
           "row_number", "rank", "dense_rank", "lag", "lead", "hash",
           "year", "month", "dayofmonth", "weekday", "unix_timestamp",
           "udf", "pandas_udf"]
    for fn in fns:
        assert hasattr(F, fn), f"functions.{fn} missing"
