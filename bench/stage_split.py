"""A traced run of one cell, with the step's device time split by stage.

    python3 bench/stage_split.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does and keeps the profiler's
events.  Prints one JSON line: the run's result line; the self time of
the ops of the sweep's batched executable in the window, by the step
stage each op's metadata names (``ringbench.stages``), in seconds and in
microseconds per point-cycle; the window's device idle time by program
span; and the program's span and counter registry (``repro.obs``).
Exit 3 as ``bench/run.py``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from ringbench import harness, registry, stages, trace_reduce  # noqa: E402


def run(spec: dict, seed: int, seconds: float, *, t_start: float,
        platform: str = "tpu") -> dict:
    kept = {}
    split_fn = stages.stage_self_s

    def keep(events, **kw):
        kept["events"] = events
        kept["split"] = split_fn(events, **kw)
        return kept["split"]

    stages.stage_self_s = keep
    try:
        out = harness.run(spec, seed, seconds, True, t_start=t_start,
                          platform=platform)
    finally:
        stages.stage_self_s = split_fn
    events, split = kept["events"], kept["split"]
    c = registry.snapshot() or {}
    pc = c.get("sweep.point_cycles", 0)
    op = next((e for e in events if trace_reduce._is_device(e.plane)
               and e.line == trace_reduce.OPS_LINE), None)
    return {
        "result": out,
        "stage_s": split,
        "stage_us_per_point_cycle": ({s: 1e6 * t / pc
                                      for s, t in split.items()}
                                     if pc else {}),
        "idle_by_span_s": stages.idle_by_span(events,
                                              window=harness.WINDOW_SPAN),
        "op_stat_keys": sorted(op.stats) if op else [],
        "registry": c,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    try:
        out = run(harness.load_cell(args.workload), args.seed, args.seconds,
                  t_start=T_START)
    except harness.Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
