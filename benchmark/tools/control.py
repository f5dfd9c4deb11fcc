"""The control of ``correct``: the plain reference computed in float32 (the
nearest precision below the float64 the configurations state), put in the
program's place and judged by the same comparison as a run.  It has to come
out NOT correct on every seed; its ``max_rel_gap`` readings are the upper
readings the limits in ``queries/<q>/limits.json`` were set under (PERF.md §2).

    python benchmark/tools/control.py --workload <cell> --seeds 1,2,3

Host arithmetic only (numpy/pandas): it needs no chip, and runs no query.
Exit code 0 when every seed came out not correct.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]


def control_verdict(cell_name: str, seed: int, scale_factor=None) -> dict:
    import numpy as np

    import checks
    import harness
    spec = harness.load_cell(cell_name)
    config, mix = spec["config"], spec["mix"]
    queries = {q: harness.load_query(q) for q in mix["queries"]}
    tables = sorted({t for q in queries.values() for t in q["module"].TABLES})
    rows = harness.table_rows(config, scale_factor)
    frames = harness.reference_frames(config, tables, rows, seed)
    return checks.judge(
        [(q, v["module"].reference(frames, np.float32))
         for q, v in queries.items()],
        {q: v["module"].reference(frames) for q, v in queries.items()},
        {q: v["module"].ORDERED for q, v in queries.items()},
        {q: v["limits"]["max_rel_gap"] for q, v in queries.items()}, 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearsal-sf", type=float, default=None)
    args = ap.parse_args(argv)
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        v = control_verdict(args.workload, seed, args.rehearsal_sf)
        passed += v["correct"]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": v["correct"],
                          "compared": v["numbers"]}), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
