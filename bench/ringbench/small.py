"""A cell cut to a size the CPU test suite can run: 16 or 256 PEs, short
budgets.

Every traffic parameter keeps its meaning; only sizes shrink (PE count,
cycles, pod size, the number of dead links, drawn again for the small
fabric), so a small run drives the same code paths as the cell."""
from __future__ import annotations

import copy

import numpy as np

from ringbench import reference

GRIDS = {("ring_mesh", 16): (1, 1), ("ring_mesh", 256): (4, 4),
         ("flat_mesh", 16): (4, 4), ("flat_mesh", 256): (16, 16)}


def shrink(spec: dict, n_pes: int = 16, cycles: int = 160) -> dict:
    spec = copy.deepcopy(spec)
    cfg, mix = spec["config"], spec["mix"]
    cfg["blocks_x"], cfg["blocks_y"] = GRIDS[cfg["family"], n_pes]
    cfg["n_pes"] = n_pes
    if "collectives" in mix:
        for s in mix["collectives"]["schedules"]:
            if s["pod_size"]:
                s["pod_size"] = 4
        cycles *= 3   # every phase of every schedule must still complete
    mix["warmup"] = mix["warmup"] * cycles // mix["cycles"]
    mix["cycles"] = cycles
    chans = reference.fabric_of(cfg).fabric_channels()
    mix["dead_links"] = [
        sorted(int(c) for c in np.random.default_rng(0).choice(
            chans, size=min(len(dead), 3), replace=False))
        for dead in mix.get("dead_links", [[]])]
    return spec
