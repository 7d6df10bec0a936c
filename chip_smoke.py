"""Smoke run of the simulator's main path on one TPU chip.

    python chip_smoke.py

Drives ``Experiment.run_grid`` / ``run_experiments`` on the default
XLA-scan backend at the paper's largest fabric, 1024 PEs:

A. the scalability grid of the paper's Figs. 15-17 on ring_mesh and
   flat_mesh: inj_rate 0.25 / 0.625 / 1.0 x uniform / bit_reversal /
   transpose under the paper's locality, 900 cycles of which 300 are
   warmup, one batched dispatch per family;
B. replay of the three mined gradient-reduction schedules on both
   families: every phase must complete and no flit may be lost;
C. a faulted ring_mesh grid, healthy plus 8 dead links injected
   unrepaired: flit conservation must hold exactly.

Each phase runs twice, cold (compiling) and warm, and the two must agree.
One point of each phase and family runs again on the host CPU in the same
process, and its integer metrics must equal the chip's.  The script exits
non-zero, printing no result, when JAX's default device is not a TPU.
Earlier lines of standard output are per-phase JSON records (timings from
single smoke runs, not benchmark metrics); the last line is the result.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

from repro import compile_cache, trace  # noqa: E402
from repro.core import sim  # noqa: E402
from repro.core import traffic as tf  # noqa: E402
from repro.core.experiment import Budget, Experiment, run_experiments  # noqa: E402
from repro.core.spec import TopologySpec  # noqa: E402
from repro.faults import sample_faults  # noqa: E402

N_PES = 1024
FAMILIES = ("ring_mesh", "flat_mesh")
INJ_RATES = (0.25, 0.625, 1.0)
PATTERNS = ("uniform", "bit_reversal", "transpose")
GRID_BUDGET = Budget(cycles=900, warmup=300)
TRACE_BUDGET = Budget(cycles=4000, warmup=0)
FAULT_BUDGET = Budget(cycles=1200, warmup=0)
FAULT_INJ = 0.02       # below saturation, as in the fault_tolerance table
N_DEAD_LINKS = 8


def _spec(family: str, n_pes: int) -> TopologySpec:
    return TopologySpec(family=family, n_pes=n_pes, src_queue_depth=8)


def _ints(r: sim.SimResult) -> dict:
    """The run's integer accumulators.  ``lat_sum`` and ``moved`` are
    recovered from their quotients, exactly: both are below 2^31, far
    inside float64's exact range."""
    return {
        "delivered": r.delivered, "offered": r.offered,
        "accepted": r.accepted, "dropped": r.dropped, "lost": r.lost,
        "lat_sum": round(r.avg_latency * max(r.delivered, 1)),
        "moved": round(r.flit_hops_per_cycle * r.measured_cycles),
        "in_flight": r.in_flight, "phase_done": list(r.phase_done),
    }


# Each phase builder returns its experiments and the indexes of the points
# that are run again on the CPU: one per family.
def _phase_a(n_pes):
    exps = [Experiment(topology=_spec(fam, n_pes),
                       traffic=tf.spec(p, **sim.PAPER_LOCALITY),
                       budget=GRID_BUDGET, inj_rate=ir, seed=1)
            for fam in FAMILIES for ir in INJ_RATES for p in PATTERNS]
    per_family = len(INJ_RATES) * len(PATTERNS)
    # The saturated transpose point: the most contention and drops.
    return exps, [f * per_family + per_family - 1
                  for f in range(len(FAMILIES))]


def _phase_b(n_pes):
    traces = trace.traces_for_schedules(
        n_pes, pod_size=16, algorithm="halving_doubling", normalize_flits=8)
    exps = [Experiment(topology=_spec(fam, n_pes), traffic=t,
                       budget=TRACE_BUDGET, inj_rate=1.0, seed=1)
            for fam in FAMILIES for t in traces.values()]
    # The "flat" schedule, the slowest to complete.
    return exps, [f * len(traces) for f in range(len(FAMILIES))]


def _phase_c(n_pes):
    spec = _spec("ring_mesh", n_pes)
    faults = sample_faults(spec.build(), n_dead_links=N_DEAD_LINKS, seed=0)
    exp = Experiment(topology=spec, budget=FAULT_BUDGET, inj_rate=FAULT_INJ)
    return [dataclasses.replace(exp, faults=f) for f in (None, faults)], [1]


def _require(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: {msg}")


def _check(phase: str, reports) -> None:
    """Phases B and C count from cycle 0, so every flit is accounted for."""
    if phase == "A":
        return
    for rep in reports:
        r, tag = rep.sim, f"{phase} {rep.experiment.topology.family}"
        if phase == "B":
            _require(r.trace_completed, f"{tag}: trace did not complete in "
                     f"{r.cfg.cycles} cycles: {r.phase_done}")
        _require(r.lost == 0, f"{tag}: lost={r.lost}")
        _require(r.offered == r.delivered + r.dropped + r.in_flight,
                 f"{tag}: flits unaccounted for: {r.row()}")


def _run_phase(phase, exps, ref, device, cpu):
    c0 = compile_cache.stats()["compile_s"]
    t0 = time.perf_counter()
    cold = run_experiments(exps)    # returns host ints: the device is done
    cold_s = time.perf_counter() - t0
    compile_s = compile_cache.stats()["compile_s"] - c0
    t0 = time.perf_counter()
    warm = run_experiments(exps)
    warm_s = time.perf_counter() - t0
    _require([r.sim for r in warm] == [r.sim for r in cold],
             f"{phase}: warm run differs from cold run")
    _check(phase, cold)

    t0 = time.perf_counter()
    for i in ref:
        e = exps[i]
        want = _ints(cold[i].sim)
        got = _ints(sim.simulate(e.topology.build(), e.sim_config(),
                                 device=cpu))
        _require(got == want, f"{phase} {e.topology.family}: chip {want} "
                 f"!= cpu reference {got}")
    ref_s = time.perf_counter() - t0

    point_cycles = sum(e.budget.cycles for e in exps)
    counts = {}
    for rep in cold:
        c = counts.setdefault(rep.experiment.topology.family,
                              dict.fromkeys(("offered", "delivered",
                                             "dropped", "in_flight",
                                             "lost"), 0))
        for k in c:
            c[k] += getattr(rep.sim, k)
    print(json.dumps({
        "phase": phase, "device_kind": device.device_kind,
        "n_pes": exps[0].topology.n_pes, "points": len(exps),
        "compile_s": compile_s, "cold_s": cold_s, "warm_s": warm_s,
        "point_cycles_per_s": point_cycles / warm_s,
        "cpu_reference": {"points": len(ref), "equal": True, "s": ref_s},
        "counts": counts}), flush=True)


def main(n_pes: int = N_PES, platform: str = "tpu") -> dict:
    """Run phases A-C at ``n_pes`` on JAX's default device, which must be
    a ``platform`` device; returns the result printed as the last line."""
    device = jax.devices()[0]
    _require(device.platform == platform,
             f"needs a {platform} device; JAX's default device is "
             f"{device.platform} ({device.device_kind})")
    cpu = jax.devices("cpu")[0]
    compile_cache.enable()
    for phase, build in (("A", _phase_a), ("B", _phase_b), ("C", _phase_c)):
        _run_phase(phase, *build(n_pes), device, cpu)
    print(json.dumps({"compile_cache": compile_cache.stats()}), flush=True)
    return {"ok": True, "device": {"platform": device.platform,
                                   "kind": device.device_kind,
                                   "count": len(jax.devices())}}


if __name__ == "__main__":
    print(json.dumps(main()), flush=True)
