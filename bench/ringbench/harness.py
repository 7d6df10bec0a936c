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
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

from ringbench import check, reference, trace_reduce, workload

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
WINDOW_SPAN = "bench.window"
STEP_MODULE = "_run_batch"   # the sweep's batched executable


class Refused(Exception):
    """No run: the machine or the checkout cannot run the cell."""


def load_cell(name: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)

    def mine(m):
        return name in m.get("workloads", [name])
    return {"cell": cell, "config": config, "mix": mix,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def read_metric(name: str, ctx: dict):
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


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
    exps = [program.experiment(config, p) for p in pts]
    points_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.build"):
        topo = exps[0].topology.build()
        sim.build_geometry(topo)
        # The program walks a fault set's reachability on the host the
        # first time it reports on it, and caches the answer: walk it here.
        for e in exps:
            sim._fault_reachability(topo, e.sim_config().faults)
    geometry_s = time.perf_counter() - t0
    c0 = compile_cache.stats()["compile_s"]
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.precompile"):
        sweep.precompile([(topo, [e.sim_config() for e in exps])])
    precompile_s = time.perf_counter() - t0
    setup_compile_s = compile_cache.stats()["compile_s"] - c0
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
                with jax.profiler.TraceAnnotation("bench.call"):
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
    memory = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devs)

    red = None
    if trace:
        files = glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"),
                          recursive=True)
        red = trace_reduce.reduce(trace_reduce.events_from_file(files[0]),
                                  window=WINDOW_SPAN, module_key=STEP_MODULE)
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
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_devs, "memory_peak_bytes": memory}
    out = {"correct": correct, "attempted": len(calls) * len(pts),
           "failed": failed, "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = {
            "device_ops": trace_reduce.top(red["op_self_s"]),
            "idle_gaps": trace_reduce.top(red["idle_gaps_s"])}
    print(json.dumps({"call_s": call_s, "check_s": check_s,
                      "setup": {"to_devices_s": devices_s,
                                "points_s": points_s,
                                "geometry_s": geometry_s,
                                "precompile_s": precompile_s,
                                "compile_s": setup_compile_s},
                      "window_compile": window_compile}), file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    out["checks"] = checks
    return out
