"""stage pipeline: ``backendCompileNs`` summed over set-up's executions, in s:
XLA compiling and nothing else (the cache retrieval jax times inside the backend
phase is ``cache_load_s``).  Read only where the program also publishes
``compileCacheLoadNs``: before that the counter of this name summed every
jax.monitoring duration whose name held ``compil``, time saved included."""

def read(run):
    ns = [e["counters"]["backendCompileNs"]
          for e in run["setup"]["executions"]
          if "compileCacheLoadNs" in e.get("counters", {})]
    return sum(ns) / 1e9 if ns else None
