#!/usr/bin/env python3
"""The benchmark's command: one cell, once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs only on a TPU (exit 2 and no result line anywhere else). The last line
of standard output is the contract's JSON object. Everything about a cell is
found by name from ``BENCHMARK.json`` (``README.md`` beside this file).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="with --trace 1: also copy the .xplane.pb and its "
                         "reduction there (how fixtures/ was recorded)")
    ap.add_argument("--trace-seconds", type=float, default=None,
                    help="with --trace 1: seconds of the window to profile")
    args = ap.parse_args(argv)

    import harness

    try:
        line = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            T_START, keep_trace=args.keep_trace,
            trace_seconds=args.trace_seconds or harness.TRACE_SECONDS)
    except harness.Refused as e:
        print(f"benchmark: refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0 if line["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
