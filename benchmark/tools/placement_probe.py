"""Does where buffers lie in HBM decide a query's device time?  One process,
one configuration's set-up, then one query's held statement executed again and again
while the probe moves what it can of the placement:

* ``steady``    executions with nothing changed (the process's own level);
* ``pad``       a dummy device buffer of some size is allocated and kept, so
                every later intermediate lands elsewhere; the cached tables
                stay where they are;
* ``unpad``     the dummies are freed;
* ``restage``   the cached tables are unpersisted, so the next execution reads
                and stages them again (they land elsewhere); the execution
                after that is the one to read.

    python benchmark/tools/placement_probe.py --config <configuration> \
        --query <query> --seed <n> [--conf key=json ...] [--busy N]

``--conf`` overrides a key of the configuration's ``session_conf`` for this
process; ``--busy N`` burns N host cores (plain python loops, no jax) from
before the session opens until the first execution has answered, which is
when the tables are read and staged: a stand-in for a shared host.

It prints one JSON line a step: the step, the wall of ``df.collect()`` and
``compileCount``.  A diagnostic for PERF.md's open question on
``tpch_sf1_cached.q1`` (whose processes read one of two levels, 1 % apart, in
one kind of device op); no benchmark run calls it.  Needs the chip.
"""

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True,
                    help="a file's name under benchmark/configs/")
    ap.add_argument("--query", required=True,
                    help="a directory's name under benchmark/queries/")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--pads", default="1048832,16777216,104861696",
                    help="dummy buffer sizes in bytes, one step each")
    ap.add_argument("--restages", type=int, default=2)
    ap.add_argument("--steadies", type=int, default=2)
    ap.add_argument("--budget-s", type=float, default=600.0,
                    help="no new step starts after this many seconds")
    ap.add_argument("--conf", action="append", default=[],
                    help="session_conf override, key=<json value>")
    ap.add_argument("--busy", type=int, default=0,
                    help="host cores burnt while the tables are staged")
    ap.add_argument("--rehearsal-sf", type=float, default=None,
                    help="CPU rehearsal of the probe itself, at this scale")
    args = ap.parse_args(argv)
    t_start = time.monotonic()

    import harness

    with open(os.path.join(HERE, "configs", args.config + ".json")) as f:
        config = json.load(f)
    for kv in args.conf:
        k, v = kv.split("=", 1)
        config["session_conf"][k] = json.loads(v)
    query = harness.load_query(args.query)
    rows = harness.table_rows(config, args.rehearsal_sf)
    dirs = harness.ensure_dataset(config, query["module"].TABLES, rows,
                                  args.seed)
    burners = [subprocess.Popen([sys.executable, "-c", "while True: pass"])
               for _ in range(args.busy)]

    def stop_burners() -> None:
        for b in burners:
            b.kill()
        for b in burners:
            b.wait()
        del burners[:]

    try:
        return _probe(args, config, query, dirs, t_start, stop_burners)
    finally:
        stop_burners()


def _probe(args, config, query, dirs, t_start, stop_burners) -> int:
    import jax
    import numpy as np

    import harness
    from spark_rapids_tpu.utils import compile_registry as CR

    session = harness.open_session(config, dirs,
                                   jax.devices()[0].platform)
    df = session.sql(query["text"])

    def step(name: str, **more) -> None:
        t0 = time.monotonic()
        n = len(df.collect())
        wall = time.monotonic() - t0
        print(json.dumps(dict(
            step=name, wall_s=wall, rows=n,
            compile_count=session.last_metrics.get("compileCount"),
            bytes_in_use=(jax.devices()[0].memory_stats() or {}).get(
                "bytes_in_use"),
            at_s=time.monotonic() - t_start, **more)), flush=True)

    def in_budget() -> bool:
        return time.monotonic() - t_start < args.budget_s

    step("first", busy=args.busy, conf=args.conf)
    stop_burners()
    for _ in range(args.steadies):
        step("steady")
    pads = []
    for size in (int(s) for s in args.pads.split(",") if s):
        if not in_budget():
            break
        pads.append(jax.device_put(np.zeros(size, np.uint8)))
        pads[-1].block_until_ready()
        step("pad", pad_bytes=size)
    if pads and in_budget():
        del pads[:]
        step("unpad")
    views = [session.table(t) for t in dirs] if hasattr(session, "table") else []
    for i in range(args.restages):
        if not in_budget():
            break
        try:
            for v in views:
                v.unpersist()
            step("restage", nth=i)
            step("restaged", nth=i)
        except Exception as e:   # the probe reports, it does not judge
            print(json.dumps({"step": "restage", "error": repr(e)}),
                  flush=True)
            break
    print(json.dumps({"step": "done", "snapshot": {
        k: v for k, v in CR.snapshot().items()
        if isinstance(v, (int, float))}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
