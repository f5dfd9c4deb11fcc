#!/usr/bin/env python
"""rapidsprof — offline analysis of obs event logs.

Usage:
    python tools/rapidsprof.py <events.jsonl> [more.jsonl ...]
        [--top N] [--query ID] [--critpath]
    python tools/rapidsprof.py --xplane <file.xplane.pb> [--top N]

Reads the JSONL event log(s) a session wrote under
``spark.rapids.sql.tpu.obs.eventLogDir`` and prints, per query and in
aggregate: top operators by enqueue wall, transfer/spill pressure, the
retry/fault summary, and a per-query comparison table.

``--xplane`` reads a ``jax.profiler`` trace instead (made with
``benchmark/run.py --trace 1 --keep-trace PATH`` or
``utils.tracing.start_profile``) and prints which operator ate the
DEVICE's time: self-time by ``XLA Modules`` name (the stage program) ->
operator scope -> kernel scope, and the idle gaps by the innermost
``srt/<site>/<name>`` program span the host was in (``obs/xplane.py``).

Runtime-free by construction (the RAPIDS profiling-tool role, and the
same loading discipline as ``rapidslint``): the ``obs`` package is
loaded standalone without executing the engine's root ``__init__``, so
no jax import and no device runtime — a log from a TPU host analyzes on
any laptop.  Only ``--xplane`` imports more than the stdlib: tsl's
generated ``xplane_pb2`` (``google.protobuf``), on first use.
"""

from __future__ import annotations

import argparse
import importlib.util
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_obs():
    """Load spark_rapids_tpu.obs WITHOUT executing the engine's package
    __init__ (which imports jax) — obs is stdlib-only and relative-
    imported precisely so this tool stays runtime-free."""
    pkg_dir = os.path.join(REPO_ROOT, "spark_rapids_tpu", "obs")
    spec = importlib.util.spec_from_file_location(
        "rapidsprof_obs", os.path.join(pkg_dir, "__init__.py"),
        submodule_search_locations=[pkg_dir])
    mod = importlib.util.module_from_spec(spec)
    sys.modules["rapidsprof_obs"] = mod
    spec.loader.exec_module(mod)
    return mod


_obs = _load_obs()
from rapidsprof_obs import critpath as obs_critpath  # noqa: E402
from rapidsprof_obs import export as obs_export  # noqa: E402
from rapidsprof_obs import xplane as obs_xplane  # noqa: E402
from rapidsprof_obs.profile import QueryProfile  # noqa: E402


def load_profiles(paths):
    profiles = []
    for path in paths:
        for i, q in enumerate(obs_export.read_event_log(path)):
            profiles.append(QueryProfile(
                q.get("id", i + 1), q.get("events", []),
                dropped=q.get("dropped", 0), wall_ns=q.get("wall_ns", 0),
                metrics=q.get("metrics") or {},
                dropped_by_site=q.get("dropped_by_site") or {},
                session_id=q.get("session", 0),
                qt0_ns=q.get("t0_ns", 0), qt1_ns=q.get("t1_ns", 0)))
    return profiles


def _gbps(nbytes: int, ns: int) -> str:
    if not ns:
        return "-"
    return f"{nbytes / max(ns, 1):.3f} GB/s"


def _mb(nbytes: int) -> str:
    return f"{nbytes / (1 << 20):.2f} MB"


def report(profiles, top_n: int = 10, critpath: bool = False) -> str:
    lines = []
    # group per-query blocks by the session that ran them (one shared
    # log accumulates every session in the process)
    sessions = sorted({p.session_id for p in profiles})
    grouped = len(sessions) > 1
    for sid in sessions:
        if grouped:
            lines.append(f"== session {sid} ==")
        for p in profiles:
            if p.session_id != sid:
                continue
            lines.append(p.summary())
            if critpath:
                cp = obs_critpath.from_profile(p)
                lines.append(cp.summary() if cp is not None
                             else "critical path: (no query window "
                                  "recorded)")
            lines.append("")

    # aggregate top operators by enqueue wall
    merged = {}
    for p in profiles:
        for r in p.top_operators(10 ** 9):
            m = merged.setdefault(
                r["op_id"] or r["name"],
                {"name": r["name"], "enqueue_ns": 0, "dispatches": 0,
                 "errors": 0, "shuffle_bytes": 0})
            m["name"] = m["name"] or r["name"]
            m["enqueue_ns"] += r["enqueue_ns"]
            m["dispatches"] += r["dispatches"]
            m["errors"] += r["errors"]
            m["shuffle_bytes"] += r["shuffle_bytes"]
    lines.append("== top operators by enqueue wall ==")
    ops = sorted(merged.values(), key=lambda m: m["enqueue_ns"],
                 reverse=True)[:top_n]
    if not ops:
        lines.append("  (no operator events)")
    for m in ops:
        extra = f", {m['errors']} errored" if m["errors"] else ""
        sh = f", shuffle {_mb(m['shuffle_bytes'])}" \
            if m["shuffle_bytes"] else ""
        lines.append(f"  {m['name'] or '?'}: {m['enqueue_ns'] / 1e6:.2f} ms "
                     f"across {m['dispatches']} dispatches{extra}{sh}")

    # transfer/spill pressure
    lines.append("")
    lines.append("== transfer/spill pressure ==")
    for site, label in (("h2d", "host->device"), ("d2h", "device->host"),
                        ("spill", "spill"), ("unspill", "unspill"),
                        ("io", "arrow decode")):
        tot = {"count": 0, "wall_ns": 0, "bytes": 0}
        for p in profiles:
            s = p.site(site)
            for k in tot:
                tot[k] += s[k]
        if not tot["count"]:
            continue
        lines.append(f"  {label}: {tot['count']} events, "
                     f"{_mb(tot['bytes'])}, {tot['wall_ns'] / 1e6:.2f} ms "
                     f"({_gbps(tot['bytes'], tot['wall_ns'])})")

    # retry/fault summary
    lines.append("")
    lines.append("== retry/fault summary ==")
    retry = sum(p.site("retry")["count"] for p in profiles)
    fault = sum(p.site("fault")["count"] for p in profiles)
    adaptive = sum(p.site("adaptive")["count"] for p in profiles)
    rmetrics = {"retryCount": 0, "faultsInjected": 0, "deviceLostCount": 0,
                "partitionFallbackCount": 0}
    for p in profiles:
        for k in rmetrics:
            rmetrics[k] += int(p.metrics.get(k, 0) or 0)
    lines.append(f"  retry events {retry}, fault events {fault}, "
                 f"adaptive decisions {adaptive}")
    lines.append("  metrics: " + ", ".join(
        f"{k}={v}" for k, v in rmetrics.items()))

    # query-intelligence summary (history/): seeded decisions and
    # fragment-cache reuse recorded by the sessions that wrote these logs
    hist_events = sum(p.site("history")["count"] for p in profiles)
    hmetrics = {"historySeededDecisions": 0, "fragmentCacheHits": 0,
                "fragmentCacheBytes": 0, "statsStoreQueries": 0}
    for p in profiles:
        for k in hmetrics:
            hmetrics[k] += int(p.metrics.get(k, 0) or 0)
    if hist_events or any(hmetrics.values()):
        lines.append("")
        lines.append("== query intelligence (history) ==")
        lines.append(f"  history events {hist_events}")
        lines.append("  metrics: " + ", ".join(
            f"{k}={v}" for k, v in hmetrics.items()))

    # per-query comparison
    if len(profiles) > 1:
        lines.append("")
        lines.append("== per-query comparison ==")
        lines.append("  query | sess | wall ms | enqueue ms | events | "
                     "dropped | dispatches | shuffle MB")
        for p in profiles:
            sh = sum(r["shuffle_bytes"] for r in p.op_rollups.values())
            lines.append(
                f"  {p.query_id:>5} | {p.session_id:>4} | "
                f"{p.wall_ns / 1e6:>7.1f} | "
                f"{p.attributed_enqueue_ns / 1e6:>9.2f} | "
                f"{p.event_count:>6} | {p.dropped:>7} | "
                f"{p.site('enqueue')['count']:>10} | "
                f"{sh / (1 << 20):>10.2f}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="analyze spark_rapids_tpu obs event logs")
    ap.add_argument("logs", nargs="*", help="JSONL event log path(s)")
    ap.add_argument("--xplane", default=None, metavar="FILE",
                    help="read a jax.profiler .xplane.pb instead: device "
                         "time by stage program/operator/kernel scope, "
                         "idle gaps by srt/ span")
    ap.add_argument("--top", type=int, default=10,
                    help="operators to list (default 10)")
    ap.add_argument("--query", type=int, default=None,
                    help="restrict to one query id")
    ap.add_argument("--critpath", action="store_true",
                    help="print each query's exact critical-path "
                         "decomposition")
    args = ap.parse_args(argv)

    if args.xplane:
        reduced = obs_xplane.reduce_xplane(args.xplane)
        if reduced is None:
            print("no TPU device plane in", args.xplane)
            return 2
        print(obs_xplane.format_report(reduced, max(args.top, 25)))
        return 0
    if not args.logs:
        ap.error("give event log path(s) or --xplane FILE")
    profiles = load_profiles(args.logs)
    if args.query is not None:
        profiles = [p for p in profiles if p.query_id == args.query]
    if not profiles:
        print("no queries found in", ", ".join(args.logs))
        return 2
    print(report(profiles, args.top, critpath=args.critpath))
    return 0


if __name__ == "__main__":
    sys.exit(main())
