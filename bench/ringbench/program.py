"""The benchmark's points as the program's ``Experiment``s, and the integer
counters of the program's ``Report``s."""
from __future__ import annotations

import numpy as np

from repro.core import traffic as tf
from repro.core.experiment import Budget, Experiment
from repro.core.spec import TopologySpec
from repro.faults import FaultSpec
from repro.trace import Trace, TraceSpec


def topology(config: dict) -> TopologySpec:
    return TopologySpec(family=config["family"], n_pes=config["n_pes"],
                        queue_depth=config["queue_depth"],
                        src_queue_depth=config["src_queue_depth"])


def experiment(config: dict, pt: dict) -> Experiment:
    if pt["phases"] is not None:
        dst, flits = pt["phases"]
        phases = tuple(tuple((int(s), int(dst[i, s]), int(flits[i, s]))
                             for s in np.nonzero(flits[i])[0])
                       for i in range(dst.shape[0]))
        traffic = Trace(trace=TraceSpec(
            n_pes=config["n_pes"], phases=phases,
            flit_bytes=pt["flit_bytes"], scale=pt["scale"],
            label=pt["schedule"]))
    else:
        traffic = tf.spec(pt["pattern"], locality_ringlet=pt["loc_ringlet"],
                          locality_block=pt["loc_block"])
    return Experiment(
        topology=topology(config), traffic=traffic,
        budget=Budget(cycles=pt["cycles"], warmup=pt["warmup"],
                      starvation_limit=config["starvation_limit"]),
        inj_rate=pt["inj_rate"], seed=pt["seed"],
        faults=(FaultSpec(dead_links=tuple(pt["dead_links"]))
                if pt["dead_links"] else None))


def counters(report) -> dict:
    """The run's integer accumulators.  ``lat_sum`` and ``moved`` come back
    from their quotients exactly: both are below 2^31, far inside
    float64's exact range."""
    r = report.sim
    return {"delivered": r.delivered, "offered": r.offered,
            "accepted": r.accepted, "dropped": r.dropped, "lost": r.lost,
            "lat_sum": round(r.avg_latency * max(r.delivered, 1)),
            "moved": round(r.flit_hops_per_cycle * r.measured_cycles),
            "in_flight": r.in_flight, "phase_done": list(r.phase_done)}


def broken_invariants(report, invariants: list[str]) -> list[str]:
    r = report.sim
    tests = {"lost_zero": lambda: r.lost == 0,
             "conserved": lambda: (r.offered == r.delivered + r.dropped
                                   + r.in_flight),
             "trace_completed": lambda: r.trace_completed}
    return [name for name in invariants if not tests[name]()]
