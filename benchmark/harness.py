"""One run of one cell: set-up, warm-up, the measured window, the comparison
that decides ``correct``, and the result line.

The harness holds no list of cells, queries or metrics.  It finds, by the
names in ``BENCHMARK.json``:

* the configuration  ``<file named in configs[]>``  (sizes, session conf),
* the traffic mix    ``benchmark/traffic/<traffic>.json``  (traffic.py; its
  ``statement`` says what a query of the window is: ``held``, the client
  prepares ``df = session.sql(text)`` once in set-up and every query is
  ``df.collect()``; ``text``, every query is ``session.sql(text).collect()``
  with nothing held),
* each query         ``benchmark/queries/<query>/{query.sql, reference.py,
  limits.json}``,
* the data generator ``benchmark/datagen/<schema>.py``,
* each metric        ``benchmark/end_to_end/<name>.py`` or
  ``benchmark/layer_metrics/<name>.py``: a module with ``read(run)`` that
  returns the number, or None where it finds nothing to read.  A metric
  named ``<quantity>.<class>`` (the same quantity under a bound of its own,
  for cells of another noise class: ``rows_per_s.scan``) is read by
  ``<quantity>.py`` unless it has a file of its own.

A cell reports the end-to-end metrics that list it (or list no cells), and the
per-layer metrics that list it or, listing no cells, move an end-to-end
metric the cell reports.

``run`` (what a reader is given) is a dict:

``cell``, ``config``, ``mix``      the three definitions, as loaded
``rows``                           table -> row count of this run
``queries``                        query name -> its ``reference.py`` module
``records``                        one dict per query of the window: ``query``,
                                   ``start``/``end`` (monotonic s),
                                   ``latency_s`` (from when it was due),
                                   ``answered`` (bool), ``counters`` (all of
                                   ``session.last_metrics`` after that query),
                                   ``traced`` (profiler was on)
``window_s``                       real length of the window
``setup``                          ``setup_s``, ``first_query_s``,
                                   ``executions`` (set-up's queries, as
                                   ``records``), ``compile_wall_ns``, ``cache``
                                   (the compile registry's
                                   ``persistent_cache_stats()``)
``trace``                          the reduced trace (trace_reduce.py) or None
``peaks``                          ``peaks.json`` entry of this device kind
``memory_peak_bytes``, ``device``  as the result line reports them
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time
import traceback
from typing import Dict, List, Optional

import numpy as np

import checks

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DATA_DIR = os.path.join(HERE, "data")
TRACE_DIR = os.path.join(HERE, "trace_tmp")
#: datasets kept per table before the oldest is deleted: a check runs two sets
#: of six seeds (and a few traced ones) in one checkout, and the second set
#: and the other cells of the same configuration find the first's files
KEEP_DATASETS = 8
#: the window's latency percentiles the result line's ``info`` carries beside
#: min, median and max (``tools/spread.py`` reads them; no metric reads ``info``)
LATENCY_QUANTILES = (1, 5, 10, 25, 75, 95, 99)


def _module(path: str, name: str):
    """The module at ``path``, loaded once under ``name``."""
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def _json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell, its configuration, its mix and the metrics it reports."""
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    conf_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])

    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    reported = {m["name"] for m in end_to_end}
    return {
        "cell": cell,
        "config": _json(os.path.join(ROOT, conf_entry["file"])),
        "mix": _json(os.path.join(HERE, "traffic", cell["traffic"] + ".json")),
        "end_to_end": end_to_end,
        "per_layer": [m for m in bench["per_layer"]
                      if (name in m["workloads"] if "workloads" in m
                          else m["moves"] in reported)],
    }


def load_reader(group: str, metric: str):
    """The reader of ``metric``: its own file, or its quantity's."""
    for stem in (metric, metric.split(".")[0]):
        path = os.path.join(HERE, group, stem + ".py")
        if os.path.exists(path):
            return _module(path, f"benchmark_metric_{stem}")
    raise SystemExit(f"no reader for {metric!r} under benchmark/{group}/")


def load_query(name: str) -> dict:
    d = os.path.join(HERE, "queries", name)
    with open(os.path.join(d, "query.sql")) as f:
        text = f.read()
    return {"text": text, "module": _module(os.path.join(d, "reference.py"),
                                            f"benchmark_query_{name}"),
            "limits": _json(os.path.join(d, "limits.json"))}


def table_rows(config: dict, scale_factor: Optional[float]) -> Dict[str, int]:
    """Row counts of this run: the configuration's, or for a CPU rehearsal
    the same tables at ``scale_factor``."""
    k = 1.0 if scale_factor is None else scale_factor / config["scale_factor"]
    return {t: max(1, int(spec["rows"] * k))
            for t, spec in config["tables"].items()}


# -- data ---------------------------------------------------------------------


def _datagen(config: dict):
    return _module(os.path.join(HERE, "datagen", config["schema"] + ".py"),
                   "benchmark_datagen_" + config["schema"])


def dataset_dir(config: dict, table: str, rows: Dict[str, int],
                seed: int) -> str:
    files = config["tables"][table]["files"]
    return os.path.join(DATA_DIR, f"{config['schema']}_{table}_"
                        f"{rows[table]}rows_{files}files_seed{seed}")


def ensure_dataset(config: dict, tables, rows: Dict[str, int],
                   seed: int) -> Dict[str, str]:
    """Writes each table's parquet files unless a run of the same seed, size
    and schema left them; returns table -> directory."""
    import pyarrow.parquet as pq
    gen = _datagen(config)
    out = {}
    for table in tables:
        d = dataset_dir(config, table, rows, seed)
        out[table] = d
        marker = os.path.join(d, "_COMPLETE")
        if os.path.exists(marker):
            continue
        _prune(f"{config['schema']}_{table}_")
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
        whole = gen.to_arrow(gen.generate(table, rows, seed))
        files = config["tables"][table]["files"]
        step = -(-whole.num_rows // files)
        for i in range(files):
            pq.write_table(whole.slice(i * step, step),
                           os.path.join(d, f"part-{i:05d}.parquet"))
        with open(marker, "w") as f:
            f.write(str(whole.schema))
    return out


def _prune(prefix: str) -> None:
    if not os.path.isdir(DATA_DIR):
        return
    old = sorted((os.path.join(DATA_DIR, n) for n in os.listdir(DATA_DIR)
                  if n.startswith(prefix)), key=os.path.getmtime)
    for d in old[:max(0, len(old) - (KEEP_DATASETS - 1))]:
        shutil.rmtree(d, ignore_errors=True)


def reference_frames(config: dict, tables, rows: Dict[str, int], seed: int):
    """The generated columns as pandas frames, made again from the seed: the
    reference takes nothing the program has read, decoded or written."""
    gen = _datagen(config)
    return {t: gen.to_pandas(gen.generate(t, rows, seed)) for t in tables}


# -- the system under test -----------------------------------------------------


def open_session(config: dict, dirs: Dict[str, str], platform: str):
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.session import TpuSparkSession
    from spark_rapids_tpu.utils import compile_registry as CR
    CR.enable_persistent_cache()
    conf = dict(config["session_conf"])
    if platform != "tpu":
        # rehearsal off the chip: an enabled Pallas kernel runs interpreted
        conf["spark.rapids.sql.tpu.pallas.interpret"] = True
    session = TpuSparkSession(RapidsConf(conf))
    for table, d in dirs.items():
        df = session.read.parquet(d)
        if config["cache_tables"]:
            df = df.cache()
        df.create_or_replace_temp_view(table)
    return session


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "used": devs[:chips]}


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# -- one run ------------------------------------------------------------------


def _finite(x: float) -> float:
    """JSON has no Infinity: a gap that is not a number reads 1e300."""
    return x if x == x and abs(x) != float("inf") else 1e300


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             t_start: float, scale_factor: Optional[float] = None,
             keep_trace: Optional[str] = None) -> dict:
    """Runs the cell and returns the result line as a dict (plus
    ``exit_code``).  ``scale_factor`` is the CPU rehearsal: the chip is not
    looked for, the tables shrink, and every metric's name gets the prefix
    ``rehearsal.`` so that no CPU number stands under a device metric."""
    import jax
    from jax.profiler import ProfileOptions, TraceAnnotation

    import spark_rapids_tpu  # noqa: F401  (the system under test; fails here
    import traffic           # where the checkout holds only the benchmark)

    spec = load_cell(cell_name)
    cell, config, mix = spec["cell"], spec["config"], spec["mix"]
    rehearsal = scale_factor is not None
    dev = device_info(cell["chips"])
    if not rehearsal and (dev["platform"] != "tpu"
                          or dev["count"] < cell["chips"]):
        raise SystemExit(
            f"{cell_name} needs {cell['chips']} tpu chip(s); jax found "
            f"{dev['count']} x {dev['platform']} ({dev['kind']})")
    peaks = _json(os.path.join(HERE, "peaks.json"))
    if not rehearsal and dev["kind"] not in peaks:
        raise SystemExit(f"no peaks for device kind {dev['kind']!r} in "
                         f"benchmark/peaks.json")

    queries = {q: load_query(q) for q in mix["queries"]}
    tables = sorted({t for q in queries.values() for t in q["module"].TABLES})
    rows = table_rows(config, scale_factor)
    t_data = time.monotonic()
    dirs = ensure_dataset(config, tables, rows, seed)
    t_session = time.monotonic()
    session = open_session(config, dirs, dev["platform"])

    records: List[dict] = []
    state = {"tracing": False}
    held = {}   # query -> the DataFrame a "held" mix prepares once, in set-up

    def issue(qname: str, due_at: Optional[float]) -> None:
        rec = {"query": qname, "answered": False, "traced": state["tracing"]}
        rec["start"] = time.monotonic()
        try:
            with TraceAnnotation("bench:query"):
                df = (held[qname] if qname in held
                      else session.sql(queries[qname]["text"]))
                rec["rows"] = df.collect()
                del df
            rec["counters"] = dict(session.last_metrics)
            rec["answered"] = True
        except Exception:   # a query that raises is counted, not fatal
            traceback.print_exc(file=sys.stderr)
        rec["end"] = time.monotonic()
        rec["latency_s"] = rec["end"] - (due_at or rec["start"])
        records.append(rec)

    # warm-up, as the mix says: the text submitted with nothing held
    # (``text_submissions_in_setup`` times), the held statement prepared, then
    # two executions of what the window sends.  The process's first execution
    # reads, stages and compiles (or loads the compile cache); the last takes
    # the steady-state path and must compile nothing.
    from spark_rapids_tpu.utils import compile_registry as CR
    for q in mix["queries"]:
        for _ in range(mix.get("text_submissions_in_setup", 0)):
            issue(q, None)
        if mix.get("statement", "text") == "held":
            held[q] = session.sql(queries[q]["text"])
        issue(q, None)
        issue(q, None)
        last = records[-1].get("counters", {}).get("compileCount", 0)
        if last:
            raise SystemExit(f"{q}: the last warm-up execution compiled {last} "
                             f"program(s): the window would compile too")
    executions, records[:] = list(records), []
    if not all(r["answered"] for r in executions):
        raise SystemExit("a warm-up query raised (see the traceback above)")
    setup = {
        "first_query_s": executions[0]["latency_s"],
        "executions": executions,
        "compile_wall_ns": CR.snapshot()["compile_wall_ns"],
        "cache": CR.persistent_cache_stats(),
    }

    def stop_trace() -> None:
        jax.profiler.stop_trace()
        state["tracing"] = False

    def between() -> None:
        t = mix.get("trace", {})
        if state["tracing"] and (
                len(records) >= t.get("queries", 1)
                or time.monotonic() - window_t0 >= t.get("max_seconds", 20.0)):
            stop_trace()

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = ProfileOptions()
        opts.python_tracer_level = 0   # host spans come from TraceAnnotation
        opts.enable_hlo_proto = False  # keeps the file small
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        state["tracing"] = True
    setup["setup_s"] = time.monotonic() - t_start
    window_t0 = time.monotonic()
    try:
        window_s = traffic.run_window(mix, seed, seconds, issue, between)
    finally:
        if state["tracing"]:
            stop_trace()

    peak = memory_peak_bytes(dev["used"])
    attempted = len(records)
    answered = [r for r in records if r["answered"]]
    detoured = [r for r in answered
                if any(r["counters"].get(k, 0) for k in checks.MUST_BE_ZERO)]
    failed = attempted - len(answered) + len(detoured)

    reduced, trace_info = None, None
    if trace:
        import glob

        import trace_reduce
        files = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                          recursive=True)
        if files:
            t_reduce = time.monotonic()
            reduced = trace_reduce.reduce_trace(files[0])
            trace_info = {"file_bytes": os.path.getsize(files[0]),
                          "reduce_s": time.monotonic() - t_reduce}
        if keep_trace and files:
            os.makedirs(os.path.dirname(keep_trace) or ".", exist_ok=True)
            shutil.copy(files[0], keep_trace)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": peak}
    run = {"cell": cell, "config": config, "mix": mix, "rows": rows,
           "queries": {q: v["module"] for q, v in queries.items()},
           "records": records, "window_s": window_s, "setup": setup,
           "trace": reduced, "peaks": peaks.get(dev["kind"]),
           "memory_peak_bytes": peak, "device": device}
    metrics = {}
    group = "layer_metrics" if trace else "end_to_end"
    for m in spec["per_layer" if trace else "end_to_end"]:
        value = load_reader(group, m["name"]).read(run)
        if value is not None:
            name = ("rehearsal." if rehearsal else "") + m["name"]
            metrics[name] = {"value": float(value), "unit": m["unit"]}

    # the comparison, once the window has closed: every answer that came
    t_reference = time.monotonic()
    frames = reference_frames(config, tables, rows, seed)
    verdict = checks.judge(
        [(r["query"], r["rows"]) for r in answered],
        {q: v["module"].reference(frames) for q, v in queries.items()},
        {q: v["module"].ORDERED for q, v in queries.items()},
        {q: v["limits"]["max_rel_gap"] for q, v in queries.items()},
        failed)
    for n in verdict["numbers"].values():
        n.update({k: _finite(v) for k, v in n.items()})
    reference_s = time.monotonic() - t_reference

    result = {"correct": verdict["correct"], "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace and reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    lat = sorted(r["latency_s"] for r in records)
    result["info"] = {
        "workload": cell_name, "seed": seed, "rehearsal": rehearsal,
        "rows": rows, "window_s": window_s, "queries": attempted,
        "latency_s": dict(
            {"min": lat[0], "median": lat[len(lat) // 2], "max": lat[-1],
             "count": len(lat)},
            **{f"p{q}": float(v) for q, v in zip(
                LATENCY_QUANTILES, np.percentile(lat, LATENCY_QUANTILES))})
        if lat else None,
        "setup_s": setup["setup_s"], "first_query_s": setup["first_query_s"],
        "setup_parts_s": {"to_data": t_data - t_start,
                          "data": t_session - t_data,
                          "executions": [e["latency_s"] for e in executions]},
        "reference_s": reference_s, "trace": dict(trace_info, device_events=reduced["device_events"],
                      queries=reduced["queries"])
        if reduced and trace_info else trace_info,
    }
    result["compared"] = verdict["numbers"]   # comes last in the line
    result["exit_code"] = 3 if rehearsal else 0
    return result
