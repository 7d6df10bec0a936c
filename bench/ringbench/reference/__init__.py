"""Plain reference of the simulator's semantics.

It imports nothing of the program under test and takes nothing it has
made: fabrics, routes, traffic and faults are built here from the
configuration and the point.  ``counters(config, point)`` gives the integer
counters the program's ``Report`` carries for that point.
"""
from __future__ import annotations

import functools
import json

import numpy as np

from ringbench.reference import fabric, sim, traffic


@functools.lru_cache(maxsize=4)
def _fabric(config_json: str) -> fabric.Fabric:
    return fabric.build(json.loads(config_json))


def fabric_of(config: dict) -> fabric.Fabric:
    """The configuration's fabric, built once per process."""
    return _fabric(json.dumps(config, sort_keys=True))


def dead_queues(f: fabric.Fabric, dead_links) -> np.ndarray:
    """Queues of the dead physical channels (every VC of the wire)."""
    return np.nonzero(np.isin(f.phys, list(dead_links))
                      & np.isin(f.kind, fabric.FABRIC_KINDS))[0]


def counters(config: dict, point: dict,
             arbitration_passes: int | None = None) -> dict:
    """Counters of ``point`` on the configuration's fabric.
    ``arbitration_passes`` overrides the configuration's (the control)."""
    f = fabric_of(config)
    p = f.n_pes
    dead = list(point["dead_links"])
    d = traffic.draws(point["seed"], point["cycles"], p, point["inj_rate"],
                      faulted=bool(dead))
    phases = point.get("phases")
    dst = None
    if phases is None:
        dst = traffic.destinations(d, p, point["pattern"],
                                   point["loc_ringlet"], point["loc_block"])
    return sim.simulate(
        f, cycles=point["cycles"], warmup=point["warmup"],
        starvation_limit=config["starvation_limit"],
        arbitration_passes=(arbitration_passes if arbitration_passes
                            is not None else config["arbitration_passes"]),
        inj=d["inj"], dst=dst, phases=phases,
        dead=dead_queues(f, dead) if dead else None)
