"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--terse]
                                            [--only NAME] [--no-baseline]

Prints ``name,us_per_call,derived`` CSV lines (harness contract) followed
by the full table rows.  Each simulation table is run twice: the first
(cold) call pays XLA compilation, the second measures the steady state;
``us_per_call`` is the steady-state time and the cold/steady/compile split
is written — together with the frozen-seed serial-baseline comparison for
``figs15_17`` and the sweep engine's compile counters — to
``BENCH_noc.json`` so the perf trajectory is tracked across PRs.  The
persistent compilation cache is always on (``repro.compile_cache``: where
``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``), and
its hit/miss counts are recorded too.

Roofline terms for the dry-run cells live in EXPERIMENTS.md (they come
from launch/dryrun.py, not wall-clock).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from benchmarks import analysis_bench, fault_sweep, kernel_micro, \
    noc_tables, serial_baseline
from benchmarks import trace_replay as trace_replay_mod
from repro import compile_cache
from repro.core import sweep

RESULTS: dict = {"tables": {}}


def _with_fresh_cache(fn):
    def wrapped(**kw):
        noc_tables.clear_sweep_cache()
        return fn(**kw)
    return wrapped


def _run_table(name, fn, verbose=True, rerun=True, **kw):
    t0 = time.perf_counter()
    rows, derived = fn(**kw)
    cold_s = time.perf_counter() - t0
    steady_s = None
    if rerun:
        t0 = time.perf_counter()
        rows, derived = fn(**kw)
        steady_s = time.perf_counter() - t0
    us = (steady_s if steady_s is not None else cold_s) * 1e6
    print(f"{name},{us:.0f},{derived}")
    if verbose and rows:
        cols = list(rows[0].keys())
        print("  # " + " | ".join(str(c) for c in cols))
        for r in rows:
            print("  # " + " | ".join(str(r[c]) for c in cols))
    sys.stdout.flush()
    RESULTS["tables"][name] = {
        "cold_s": round(cold_s, 3),
        "steady_s": round(steady_s, 3) if steady_s is not None else None,
        # cold - steady ~= XLA compilation + one-time topology builds
        "compile_est_s": round(cold_s - steady_s, 3)
        if steady_s is not None else None,
        "derived": derived,
        "rows": rows,
    }
    return rows


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true",
                   help="smaller sim grid (CI)")
    p.add_argument("--terse", action="store_true", help="CSV lines only")
    p.add_argument("--only", default=None, metavar="NAME",
                   help="run a single table (substring match)")
    p.add_argument("--no-baseline", action="store_true",
                   help="skip the frozen-seed serial baseline comparison")
    args, _ = p.parse_known_args()
    v = not args.terse
    compile_cache.enable()
    pcache_before = compile_cache.stats()

    sizes = (16, 64) if args.quick else (16, 64, 256)
    scal_sizes = (16, 32, 64, 128) if args.quick \
        else (16, 32, 64, 128, 256, 512, 1024)
    RESULTS["quick"] = args.quick

    # (name, fn, kwargs, fresh): fresh tables drop the memoized sweep
    # results before each timed call so cold/steady measure real dispatch;
    # figs12_14 deliberately reads figs9_11's grid (same simulations).
    # The headline scalability table (and its frozen-baseline comparison)
    # runs before the big rate x pattern grids so its cold timing is not
    # polluted by their accumulated device state.
    tables = [
        ("table2_router_area_power", noc_tables.table2_router_area_power,
         {}, False),
        ("table3_relative_area", noc_tables.table3_relative_area, {}, False),
        ("fig7_power_breakdown", noc_tables.fig7_power_breakdown, {}, False),
        ("fig8_power_scaling", noc_tables.fig8_power_scaling, {}, False),
        ("figs15_17_scalability", noc_tables.figs15_17_scalability,
         {"sizes": scal_sizes}, True),
        ("figs9_11_latency", noc_tables.figs9_11_latency,
         {"sizes": sizes}, True),
        ("figs12_14_throughput", noc_tables.figs12_14_throughput,
         {"sizes": sizes}, False),
        ("figs_extended_patterns", noc_tables.figs_extended_patterns,
         {"sizes": (16, 64)}, True),
        ("experiment_grid_smoke", noc_tables.experiment_grid_smoke,
         {}, False),
        ("trace_replay", trace_replay_mod.trace_replay,
         {"quick": args.quick}, True),
        ("fault_tolerance", fault_sweep.fault_tolerance,
         {"quick": args.quick}, False),
        ("fault_trace_watchdog", fault_sweep.watchdog_demo, {}, False),
        ("analysis_certify", analysis_bench.analysis_certify,
         {"quick": args.quick}, False),
        ("paper_validation_c1_c8", noc_tables.paper_validation, {}, False),
    ]

    print("name,us_per_call,derived")
    stats_before = sweep.compile_stats()
    matched = False
    for name, fn, kw, fresh in tables:
        if args.only and args.only not in name:
            continue
        matched = True
        if fresh:
            fn = _with_fresh_cache(fn)
        _run_table(name, fn, v, **kw)
        if name == "figs15_17_scalability":
            stats = sweep.compile_stats()
            tbl = RESULTS["tables"][name]
            # One executable per (topology geometry, cycle budget): the
            # whole run may compile at most one batch program per
            # (size, topology) geometry per distinct cycle budget.
            tbl["compile_cache"] = stats
            if not args.no_baseline:
                t0 = time.perf_counter()
                serial_baseline.figs15_17_serial(
                    sizes=scal_sizes, cycles=900)
                base_s = time.perf_counter() - t0
                speedup_cold = base_s / tbl["cold_s"]
                speedup_steady = base_s / tbl["steady_s"]
                tbl["serial_baseline_s"] = round(base_s, 3)
                tbl["speedup_vs_serial_cold"] = round(speedup_cold, 2)
                tbl["speedup_vs_serial_steady"] = round(speedup_steady, 2)
                print(f"figs15_17_serial_baseline,{base_s * 1e6:.0f},"
                      f"sweep speedup: {speedup_cold:.1f}x cold / "
                      f"{speedup_steady:.1f}x steady (seed per-point path)")
                sys.stdout.flush()

    RESULTS["compile_cache"] = {"before": stats_before,
                                "after": sweep.compile_stats()}
    if not args.only or args.only in "kernel_micro":
        matched = True
        km_rows = []
        for name, us, derived in kernel_micro.run(quick=args.quick):
            print(f"{name},{us:.0f},{derived}")
            km_rows.append({"name": name, "us_per_call": round(us, 1),
                            "derived": derived})
        RESULTS["tables"]["kernel_micro"] = {"rows": km_rows}
    if not matched:
        print(f"# no table matches --only {args.only!r}", file=sys.stderr)

    pcache = compile_cache.stats()
    pcache["entries_before"] = pcache_before["entries"]
    RESULTS["compile_cache"]["persistent"] = pcache
    print(f"# persistent compile cache: {pcache['hits']} hits / "
          f"{pcache['misses']} misses ({pcache['entries_before']} -> "
          f"{pcache['entries']} entries in {pcache['dir']})")

    # Quick / partial runs must not clobber the committed full-run record.
    out = "BENCH_noc.json" if not (args.quick or args.only) \
        else "BENCH_noc_quick.json"
    _write_results(out)
    print(f"# wrote {out}")


def _write_results(out: str) -> None:
    """Write RESULTS to ``out``.  A truncated/corrupt prior record (a
    killed run, a bad merge) is moved aside to ``<out>.corrupt`` — with a
    warning, so the loss is visible — rather than crashing or being
    silently destroyed; a valid prior record is simply replaced."""
    if os.path.exists(out):
        try:
            with open(out) as f:
                json.load(f)
        except (OSError, UnicodeDecodeError, json.JSONDecodeError) as e:
            backup = out + ".corrupt"
            os.replace(out, backup)
            print(f"# prior {out} was corrupt ({e}); moved to {backup}",
                  file=sys.stderr)
    with open(out, "w") as f:
        json.dump(RESULTS, f, indent=1, default=str)


if __name__ == "__main__":
    main()
