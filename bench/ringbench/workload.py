"""The one traffic generator: a traffic mix's parameters and a seed -> points.

A traffic file (``bench/traffic/<name>.json``) holds:

- ``cycles``, ``warmup``: every point's budget;
- ``inj_rates``: injection rates (flits per PE per cycle);
- one of: ``patterns`` with ``locality`` (``ringlet``, ``block`` shares)
  for statistical traffic; ``collectives`` (``algorithm``,
  ``normalize_flits``, ``flit_bytes``, ``schedules``: a list of ``{name,
  pod_size, bytes_by_kind}``) for phase-gated trace replay; or
  ``generator``, the name of a module ``bench/generators/<name>.py`` whose
  ``traffics(config, mix, rng) -> list[dict]`` makes trace traffics from
  the rest of the file.  Each dict is shaped as a collective traffic:
  ``schedule`` (its label), ``flit_bytes``, ``scale`` and ``phases``.
  ``rng`` is a NumPy ``Generator`` seeded from the run's seed and the
  generator's name, apart from the experiment seeds' draw.  A generator
  derives every size from ``config`` (``small.shrink`` cuts its cell by
  the PE count);
- ``dead_links``: fault scenarios, each a list of dead physical channels
  (``[]``: healthy); default ``[[]]``.  The file fixes them, so every
  seed runs the same fabric;
- ``invariants``: what every point must show (``lost_zero``, ``conserved``:
  offered = delivered + dropped + in flight, ``trace_completed``).

A trace traffic's ``phases`` is a list with one int array ``[R, 3]`` of
send-ordered records per phase: each row is ``(src, dst, flits)``, and a
source may own several rows, which it sends in their order
(``reference.sim`` states the replay).

Points are the product rate x traffic x fault scenario, in that order.
The run's ``--seed`` draws each point's experiment seed, which seeds its
random streams (injection, destinations, faults): the same ``--seed`` gives
the same points.
"""
from __future__ import annotations

import os

import numpy as np

from ringbench import collectives, load_named

GENERATOR_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "generators")


def generator_traffics(config: dict, mix: dict, seed: int) -> list[dict]:
    """The traffics of ``bench/generators/<mix["generator"]>.py``."""
    name = mix["generator"]
    rng = np.random.default_rng([seed % (1 << 64), *name.encode()])
    return load_named(GENERATOR_DIR, name).traffics(config, mix, rng)


def points(config: dict, mix: dict, seed: int) -> list[dict]:
    n_pes = config["n_pes"]
    traffics = []
    if "generator" in mix:
        traffics = generator_traffics(config, mix, seed)
    elif "collectives" in mix:
        col = mix["collectives"]
        for s in col["schedules"]:
            phases, scale = collectives.schedule_phases(
                s, n_pes, algorithm=col["algorithm"],
                pod_size=s["pod_size"],
                normalize_flits=col["normalize_flits"],
                flit_bytes=col["flit_bytes"])
            traffics.append({
                "schedule": s["name"], "flit_bytes": col["flit_bytes"],
                "scale": scale, "phases": phases})
    else:
        loc = mix.get("locality", {})
        traffics = [{"pattern": p,
                     "loc_ringlet": loc.get("ringlet", 0.0),
                     "loc_block": loc.get("block", 0.0)}
                    for p in mix["patterns"]]
    pts = [{"cycles": mix["cycles"], "warmup": mix["warmup"],
            "inj_rate": ir, "dead_links": dead,
            "pattern": None, "phases": None, **t}
           for ir in mix["inj_rates"] for t in traffics
           for dead in mix.get("dead_links", [[]])]
    # The program takes an int32 experiment seed.
    seeds = np.random.default_rng(seed % (1 << 64)).integers(
        0, 2**31 - 1, size=len(pts))
    return [{**p, "seed": int(s)} for p, s in zip(pts, seeds)]
