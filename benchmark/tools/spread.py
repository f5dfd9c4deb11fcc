"""Measures how far a cell's runs spread, the way the check does, and what the
bounds of ``BENCHMARK.json`` can therefore tell.

    python3 benchmark/tools/spread.py --workload <cell> --runs N --seed <base>
        [--seconds 51] [--out DIR] [--split] [--bound NAME=SHARE ...]
    python3 benchmark/tools/spread.py --workload <cell> --from DIR [DIR ...]
        [--split] [--bound NAME=SHARE ...]
    python3 benchmark/tools/spread.py --workload <cell> --runs N --seed <base>
        --handicap-us 150

The first form runs the cell N times through the ``run.py`` the driver calls,
one process after the other (this parent never imports jax: a chip belongs to
one process), seeds ``base, base+1, ...``, and keeps every result line under
``--out`` (default ``chiprun_out/spread/<cell>/``).  The second reads kept
lines again, so that the runs of several machines are pooled.  Both print, for
every end-to-end metric of the cell (a ``<quantity>.<class>`` entry beside its
quantity: the same values, another bound) and for every low latency quantile
of ``info.latency_s`` (``query_min_ms``, ``query_p1_ms`` ... ``query_p25_ms``:
candidates for a judged statistic, none of which repeated better than the rate
in PR 31), the values, the median, and the spread as the check reckons it:
(max - min) / median with the one run farthest from the median left out where
that narrows it; beside it the distance between the quartiles
(``statistics.quantiles``) as a share of the median, and whether the spread is
under HALF the metric's bound ("admitted"; a candidate, which has no entry, is
held to 0.01).

``--split`` cuts the runs into two interleaved halves and judges them as the
parent and the change of a PR that claims no gain: ``unresolved`` where either
half spreads by more than bound x the parent's median, else ``worse`` where the
change's median is worse by more than that, else ``unchanged`` (the A/A test).

``--handicap-us U`` is the sensitivity test: every seed runs twice, plain and
with a busy-wait of U microseconds added inside each timed query, alternating
which goes first; a metric has "seen" a pair where the handicapped run reads
worse than the plain one by more than its bound.  The handicap lives in this
tool's own child entry (``--child``): it wraps ``DataFrame.collect`` in that
process only and then calls ``run.main``; ``run.py`` and ``harness.py`` have no
such option.

``--rehearsal-sf`` is handed to ``run.py`` (the CPU rehearsal: exit code 3,
metric names prefixed ``rehearsal.``; a number from it is not a speed).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import subprocess
import sys
import time
from typing import Dict, List, Sequence

TOOLS = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(TOOLS)
ROOT = os.path.dirname(BENCH_DIR)
RUN_PY = os.path.join(BENCH_DIR, "run.py")
#: keys of ``info.latency_s`` that are candidates for the judged quantile
CANDIDATES = ("min", "p1", "p5", "p10", "p25")
CANDIDATE_BOUND = 0.01


# -- arithmetic (no chip, no jax) -----------------------------------------------


def check_spread(values: Sequence[float]) -> float:
    """max - min, with the one value farthest from the median left out where
    that narrows it (it always does, or leaves it as it is): absolute."""
    v = sorted(values)
    if len(v) < 3:
        return v[-1] - v[0]
    med = statistics.median(v)
    far = max(v, key=lambda x: abs(x - med))
    v.remove(far)
    return v[-1] - v[0]


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile: absolute."""
    q = statistics.quantiles(values, n=4)
    return q[2] - q[0]


def summarize(values: Sequence[float], bound: float) -> dict:
    med = statistics.median(values)
    spread = check_spread(values) / med
    return {"n": len(values), "median": med, "spread": spread,
            "quartile_spread": quartile_spread(values) / med,
            "bound": bound, "admitted": spread < bound / 2}


def judge_no_gain(parent: Sequence[float], change: Sequence[float],
                  bound: float, better: str) -> dict:
    """The rule a PR that claims no gain is held to, on one metric."""
    p_med, c_med = statistics.median(parent), statistics.median(change)
    room = bound * p_med
    spreads = check_spread(parent), check_spread(change)
    worse_by = (p_med - c_med) if better == "higher" else (c_med - p_med)
    verdict = ("unresolved" if max(spreads) > room
               else "worse" if worse_by > room else "unchanged")
    return {"verdict": verdict, "parent_median": p_med, "change_median": c_med,
            "parent_spread": spreads[0], "change_spread": spreads[1],
            "room": room, "worse_by": worse_by}


def seen(plain: float, handicapped: float, bound: float, better: str) -> dict:
    """Whether the handicapped run reads worse than the plain one by more
    than the bound (a share of the plain reading)."""
    worse_by = ((plain - handicapped) if better == "higher"
                else (handicapped - plain)) / plain
    return {"worse_by": worse_by, "seen": worse_by > bound}


# -- the cell's metrics and the kept lines --------------------------------------


def judged_metrics(workload: str, overrides: Dict[str, float]) -> Dict[str, dict]:
    """name -> {bound, better}: the cell's end-to-end metrics, then the
    candidates that have no entry yet."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    out = {m["name"]: {"bound": m["bound"], "better": m["better"]}
           for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])}
    for k in CANDIDATES:
        out.setdefault(f"query_{k}_ms",
                       {"bound": CANDIDATE_BOUND, "better": "lower"})
    for name, bound in overrides.items():
        out[name]["bound"] = bound
    return out


def readings(line: dict) -> Dict[str, float]:
    """Every number of one result line this tool looks at, by metric name."""
    out = {name.split("rehearsal.")[-1]: m["value"]
           for name, m in line["metrics"].items()}
    lat = line["info"]["latency_s"] or {}
    for k in CANDIDATES:
        if k in lat:
            out.setdefault(f"query_{k}_ms", lat[k] * 1e3)
    return out


def load_lines(dirs: Sequence[str], workload: str) -> List[dict]:
    lines = []
    for d in dirs:
        for path in sorted(glob.glob(os.path.join(d, "*.json"))):
            with open(path) as f:
                line = json.load(f)
            if line.get("info", {}).get("workload") == workload:
                lines.append(line)
    return lines


# -- running --------------------------------------------------------------------


def run_once(args, seed: int, handicap_us: int, order: int, out: str) -> dict:
    """One run in a process of its own; its result line, kept under ``out``."""
    tail = ["--workload", args.workload, "--seed", str(seed),
            "--seconds", str(args.seconds), "--trace", "0"]
    if args.rehearsal_sf is not None:
        tail += ["--rehearsal-sf", str(args.rehearsal_sf)]
    cmd = [sys.executable, RUN_PY] + tail if not handicap_us else \
        [sys.executable, os.path.abspath(__file__), "--child",
         "--handicap-us", str(handicap_us)] + tail
    stem = os.path.join(out, f"{order:02d}_seed{seed}_h{handicap_us}")
    t0 = time.monotonic()
    with open(stem + ".log", "w") as log:
        done = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                              stderr=log, text=True)
    last = (done.stdout.strip().splitlines() or [""])[-1]
    if done.returncode not in ((0, 3) if args.rehearsal_sf is not None else (0,)):
        raise SystemExit(f"run {order} (seed {seed}) exited with "
                         f"{done.returncode}; see {stem}.log")
    line = json.loads(last)
    line["spread_tool"] = {"order": order, "handicap_us": handicap_us,
                           "wall_s": time.monotonic() - t0}
    with open(stem + ".json", "w") as f:
        json.dump(line, f)
    m = readings(line)
    print(f"run {order:2d} seed {seed} handicap_us {handicap_us} correct "
          f"{line['correct']} failed {line['failed']} "
          + " ".join(f"{k} {v!r}" for k, v in m.items()), flush=True)
    return line


def child(argv: List[str], handicap_us: int) -> int:
    """The handicapped run: ``run.main`` with U microseconds of busy-wait
    added inside every ``collect()`` of this process."""
    sys.path.insert(0, BENCH_DIR)
    import run   # starts set-up's clock, as ``python3 benchmark/run.py`` does
    from spark_rapids_tpu.dataframe import DataFrame
    real, wait_ns = DataFrame.collect, handicap_us * 1000

    def collect(self):
        rows = real(self)
        until = time.perf_counter_ns() + wait_ns
        while time.perf_counter_ns() < until:
            pass
        return rows
    DataFrame.collect = collect
    return run.main(argv)


# -- reports --------------------------------------------------------------------


def column(rows: List[Dict[str, float]], name: str) -> List[float]:
    return [r[name] for r in rows if name in r]


def report_spread(rows: List[Dict[str, float]], metrics: Dict[str, dict]) -> None:
    for name, m in metrics.items():
        values = column(rows, name)
        if len(values) < 2:
            continue
        s = summarize(values, m["bound"])
        print(f"{name}: n {s['n']} median {s['median']!r} spread "
              f"{100 * s['spread']:.3f} % (quartiles "
              f"{100 * s['quartile_spread']:.3f} %) bound {m['bound']} "
              f"{'admitted' if s['admitted'] else 'NOT admitted'}\n    values "
              + " ".join(repr(v) for v in values))


def report_split(rows: List[Dict[str, float]], metrics: Dict[str, dict]) -> None:
    for name, m in metrics.items():
        values = column(rows, name)
        if len(values) < 4 or name == "setup_s":   # judged by its median only
            continue
        j = judge_no_gain(values[0::2], values[1::2], m["bound"], m["better"])
        print(f"A/A {name}: {j['verdict']} (parent median "
              f"{j['parent_median']!r} spread {j['parent_spread']!r}; change "
              f"median {j['change_median']!r} spread {j['change_spread']!r}; "
              f"bound {m['bound']} = {j['room']!r})")


def report_pairs(pairs, metrics: Dict[str, dict]) -> None:
    """``pairs``: (plain readings, handicapped readings) of one seed each."""
    for name, m in metrics.items():
        found = [seen(p[name], h[name], m["bound"], m["better"])
                 for p, h in pairs if name in p and name in h]
        if not found or name == "setup_s":
            continue
        print(f"handicap {name}: seen in {sum(r['seen'] for r in found)} of "
              f"{len(found)} pairs at bound {m['bound']}; worse by "
              + " ".join(f"{100 * r['worse_by']:.3f}%" for r in found))


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv[:1] == ["--child"]:
        return child(argv[3:], int(argv[2]))
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--out", default=None)
    ap.add_argument("--from", dest="dirs", nargs="+", default=None)
    ap.add_argument("--split", action="store_true")
    ap.add_argument("--handicap-us", type=int, default=0)
    ap.add_argument("--bound", action="append", default=[],
                    metavar="NAME=SHARE")
    ap.add_argument("--rehearsal-sf", type=float, default=None)
    args = ap.parse_args(argv)
    metrics = judged_metrics(args.workload, {
        k: float(v) for k, v in (b.split("=") for b in args.bound)})

    if args.dirs:
        lines = load_lines(args.dirs, args.workload)
    else:
        if args.seed is None:
            ap.error("--seed is needed to run")
        out = args.out or os.path.join(ROOT, "chiprun_out", "spread",
                                       args.workload)
        os.makedirs(out, exist_ok=True)
        lines, order = [], 0
        for i in range(args.runs):
            sides = [0, args.handicap_us] if args.handicap_us else [0]
            for h in (sides if i % 2 == 0 else sides[::-1]):
                lines.append(run_once(args, args.seed + i, h, order, out))
                order += 1
    if not lines:
        raise SystemExit("no result line of this workload found")
    bad = [line["info"]["seed"] for line in lines
           if not line["correct"] or line["failed"]]
    print(f"{len(lines)} runs, not correct or with failures: {bad or 'none'}")

    plain = {l["info"]["seed"]: readings(l) for l in lines
             if not l["spread_tool"]["handicap_us"]}
    rows = [readings(l) for l in lines if not l["spread_tool"]["handicap_us"]]
    report_spread(rows, metrics)
    if args.split:
        report_split(rows, metrics)
    report_pairs([(plain[l["info"]["seed"]], readings(l)) for l in lines
                  if l["spread_tool"]["handicap_us"]
                  and l["info"]["seed"] in plain], metrics)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
