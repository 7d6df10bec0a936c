"""One run of one cell: set-up, the measured window, the check, the result.

The cell names a configuration (``bench/configs/<name>.json``) and a traffic
mix (``bench/traffic/<name>.json``).  Set-up builds the points, the cell's
fabric, its device geometry and the reachability of its fault sets, and
loads (or, in a fresh checkout, compiles) the executables the calls will
use.  The window then calls the user's entry point, ``run_experiments``, on the cell's
whole point list, back to back, until ``seconds`` have passed; the last
call runs to its end.  Every call's counters are compared with the plain
reference (``ringbench.reference``) after the window.

Every metric is read by its own reader, ``bench/metrics/<name>.py``, from
the run's context: end-to-end metrics in a ``--trace 0`` run, per-layer
metrics in a ``--trace 1`` run, which also records a profiler trace of the
window.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

from ringbench import (check, load_named, reference, stages, trace_reduce,
                       workload)

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
BENCHMARK = os.path.join(ROOT, "BENCHMARK.json")
TRAFFIC_DIR = os.path.join(BENCH_DIR, "traffic")
WINDOW_SPAN = "bench.window"
STEP_MODULE = "_run_batch"   # the sweep's batched executable


class Refused(Exception):
    """No run: the machine or the checkout cannot run the cell."""


def load_cell(name: str) -> dict:
    with open(BENCHMARK) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(TRAFFIC_DIR, cell["traffic"] + ".json")) as f:
        mix = json.load(f)

    def mine(m):
        return name in m.get("workloads", [name])
    return {"cell": cell, "config": config, "mix": mix,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def read_metric(name: str, ctx: dict):
    return load_named(os.path.join(BENCH_DIR, "metrics"), name).read(ctx)


def devices(chips: int, platform: str):
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise Refused(f"needs {platform} devices; JAX's default device is "
                      f"{devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise Refused(f"needs {chips} devices, found {len(devs)}")
    return devs[:chips], len(devs)


def run(spec: dict, seed: int, seconds: float, trace: bool, *,
        t_start: float, platform: str = "tpu") -> dict:
    """The result line of one run (``spec`` from ``load_cell``)."""
    devs, n_devs = devices(spec["cell"]["chips"], platform)
    devices_s = time.perf_counter() - t_start
    import jax
    from repro import compile_cache
    from repro.core import sim, sweep
    from repro.core.experiment import run_experiments
    from ringbench import program

    config, mix = spec["config"], spec["mix"]
    compile_cache.enable()
    t0 = time.perf_counter()
    pts = workload.points(config, mix, seed)
    try:
        exps = [program.experiment(config, p) for p in pts]
        points_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        topo = exps[0].topology.build()
        sim.build_geometry(topo)
        # The program walks a fault set's reachability on the host the
        # first time it reports on it, and caches the answer: walk it here.
        for e in exps:
            sim._fault_reachability(topo, e.sim_config().faults)
        geometry_s = time.perf_counter() - t0
        c0 = compile_cache.stats()["compile_s"]
        t0 = time.perf_counter()
        sweep.precompile([(topo, [e.sim_config() for e in exps])])
        precompile_s = time.perf_counter() - t0
        setup_compile_s = compile_cache.stats()["compile_s"] - c0
    except Exception:
        # The program refused the points or could not set up: no window,
        # every point failed.
        print(traceback.format_exc(), file=sys.stderr)
        return _result(devs, n_devs, correct=False, attempted=0,
                       failed=len(pts), metrics={}, memory=_peak(devs),
                       checks={"mismatches": {"value": 0, "limit": 0},
                               "failed_points": {"value": len(pts),
                                                 "limit": 0}})
    setup_s = time.perf_counter() - t_start

    prof_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(prof_dir)
    compiles0 = sweep.compile_stats()["batch_xla_compiles"]
    cc0 = compile_cache.stats()
    calls, call_s, crashed = [], [], None
    w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        while time.perf_counter() - w0 < seconds:
            c_start = time.perf_counter()
            try:
                calls.append(run_experiments(exps))
            except Exception:
                crashed = traceback.format_exc()
                break
            call_s.append(time.perf_counter() - c_start)
    wall = time.perf_counter() - w0
    compiles = sweep.compile_stats()["batch_xla_compiles"] - compiles0
    cc1 = compile_cache.stats()
    window_compile = {k: cc1[k] - cc0[k] for k in ("hits", "misses",
                                                   "compile_s")}
    if trace:
        jax.profiler.stop_trace()
    memory = _peak(devs)

    red, stage_s = None, {}
    if trace:
        files = glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"),
                          recursive=True)
        events = trace_reduce.events_from_file(files[0])
        red = trace_reduce.reduce(events, window=WINDOW_SPAN,
                                  module_key=STEP_MODULE)
        stage_s = stages.stage_self_s(events, window=WINDOW_SPAN,
                                      module_key=STEP_MODULE,
                                      hlo_text=_step_hlo())
        del events   # free the trace before the reference's check
        shutil.rmtree(prof_dir, ignore_errors=True)

    # The check, after the window: every call's counters against the
    # reference's.
    t_check = time.perf_counter()
    want = [reference.counters(config, p) for p in pts]
    mismatches = 0
    failed = len(pts) if crashed else 0
    for reports in calls:
        for r, w in zip(reports, want):
            mismatches += check.mismatches(program.counters(r), w)
        failed += sum(bool(program.broken_invariants(
            r, mix.get("invariants", []))) for r in reports)
    check_s = time.perf_counter() - t_check
    if crashed:
        print(crashed, file=sys.stderr)

    dev = devs[0]
    ctx = {
        "setup_s": setup_s,
        "window": {"seconds": wall, "calls": len(calls),
                   "point_cycles": len(calls) * sum(p["cycles"]
                                                    for p in pts)},
        "counters": {"compiles_in_window": compiles,
                     "setup_compile_s": setup_compile_s},
        "spans": {"geometry_s": geometry_s},
        "trace": red,
        "stages": stage_s,
        "fabric": reference.fabric_of(config),
        "device_kind": dev.device_kind,
    }
    metrics = {}
    for m in spec["per_layer"] if trace else spec["end_to_end"]:
        v = read_metric(m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = {"mismatches": {"value": mismatches, "limit": 0},
              "failed_points": {"value": failed, "limit": 0}}
    correct = not crashed and bool(calls) and all(
        c["value"] <= c["limit"] for c in checks.values())
    busy = breakdown = None
    if red is not None:
        busy = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
        breakdown = {"device_ops": trace_reduce.top(red["op_self_s"]),
                     "idle_gaps": trace_reduce.top(red["idle_gaps_s"])}
    print(json.dumps({"call_s": call_s, "check_s": check_s,
                      "setup": {"to_devices_s": devices_s,
                                "points_s": points_s,
                                "geometry_s": geometry_s,
                                "precompile_s": precompile_s,
                                "compile_s": setup_compile_s},
                      "window_compile": window_compile}), file=sys.stderr)
    return _result(devs, n_devs, correct=correct,
                   attempted=len(calls) * len(pts), failed=failed,
                   metrics=metrics, checks=checks, memory=memory,
                   busy=busy, breakdown=breakdown)


def _step_hlo() -> str:
    """The compiled text of the sweep's batched executables, which joins a
    trace's op to its stage where the op's event names none; empty on a
    program that keeps no such executables."""
    from repro.core import sweep
    return "\n".join(exe.as_text() or ""
                     for exe in getattr(sweep, "_AOT", {}).values())


def _peak(devs) -> int:
    """Peak device memory in use on the fullest chip."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)


def _result(devs, n_devs: int, *, correct: bool, attempted: int,
            failed: int, metrics: dict, checks: dict, memory: int,
            busy: dict | None = None, breakdown: dict | None = None) -> dict:
    """The result line; the numbers compared, each beside its limit, come
    last there and as the last lines of standard error."""
    dev = devs[0]
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": n_devs, "memory_peak_bytes": memory,
                      **(busy or {})}}
    if breakdown is not None:
        out["breakdown"] = breakdown
    for k, c in checks.items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    out["checks"] = checks
    return out
