"""stage pipeline: ``jaxTraceNs`` summed over set-up's executions, in s: the
part of the compile wall spent tracing the programs in Python (jax.monitoring
``/jax/core/compile/jaxpr_trace_duration``).  Nothing to read where the program
has no such counter."""

KEY = "jaxTraceNs"


def read(run):
    ns = [e["counters"][KEY] for e in run["setup"]["executions"]
          if KEY in e.get("counters", {})]
    return sum(ns) / 1e9 if ns else None
