"""The benchmark's entry point.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json`` on the machine it is started on;
the last line of standard output is the result (see harness.py).  Without a
``tpu`` platform holding the chips the cell asks for it exits non-zero and
prints no result.

    JAX_PLATFORMS=cpu python benchmark/run.py ... --rehearsal-sf 0.002

is the CPU rehearsal: the same run at a tiny scale factor, every metric's
name prefixed ``rehearsal.``, exit code 3.  A number from it is not a speed.
``--keep-trace PATH`` (with ``--trace 1``) keeps the profiler's
``.xplane.pb`` at PATH, to look at by hand or to record a test's fixture.
"""

import time

T_START = time.monotonic()   # process start, for setup_s

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal-sf", type=float, default=None)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)

    import harness
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START,
                              scale_factor=args.rehearsal_sf,
                              keep_trace=args.keep_trace)
    code = result.pop("exit_code")
    result["info"]["total_s"] = time.monotonic() - T_START
    for name, n in result["compared"].items():
        print(f"compared {name}: {json.dumps(n)}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
