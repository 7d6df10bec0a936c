"""Chip benchmark of the ring-mesh NoC simulator, one cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for (``BENCHMARK.json``).  The last line of standard output is
the result as one JSON object; the numbers compared for ``correct`` are
the last lines of standard error.  Without a TPU, or without the program
(``src/repro``) in the checkout, it prints no result and exits 3.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from ringbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    src = os.path.join(harness.ROOT, "src")
    try:
        if not os.path.isdir(os.path.join(src, "repro")):
            raise harness.Refused(
                f"the checkout has no program to measure ({src}/repro)")
        sys.path.insert(0, src)
        spec = harness.load_cell(args.workload)
        out = harness.run(spec, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START)
    except harness.Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
