"""Readings of the control (``ringbench.check``) at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]

Prints one JSON line per seed with the number compared (the count of
counters that differ from the reference) and its limit.  The control runs
on the host CPU, as the reference does; the benchmark's runs never run it.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from ringbench import check, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = harness.load_cell(args.workload)
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "mismatches": check.control_reading(spec, seed),
                          "limit": 0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
