"""The paper's baseline, a flat 2D mesh: one 5-port router per PE.

Node ``r`` is the router of PE ``r``.  Queue order: each PE's inject buffer
and eject sink, then the mesh channels router by router, east, west, north,
south (2 VCs).  Routing, per flit: eject at the destination's router,
otherwise XY dimension order on the VC given by the destination's parity.
"""
from __future__ import annotations

import numpy as np

from ringbench.reference import fabric as fb


def build(config: dict) -> fb.Fabric:
    p, nx, ny = config["n_pes"], config["blocks_x"], config["blocks_y"]
    if nx * ny != p:
        raise ValueError(f"a {nx}x{ny} mesh is not {p} PEs")
    b = fb.Builder()
    pe_src, pe_eject = [], []
    for pe in range(p):
        pe_src += b.add(fb.PE_SRC, -1, pe, config["src_queue_depth"])
        pe_eject += b.add(fb.EJECT, pe, -1, fb.UNBOUNDED)
    mesh = fb.add_mesh(b, nx, ny, 0, config["queue_depth"])
    pe_eject = np.asarray(pe_eject)

    def next_queue(q: np.ndarray, d: np.ndarray) -> np.ndarray:
        r = np.clip(f.dst[q], 0, p - 1)
        go = fb.mesh_direction(r % nx, r // nx, d % nx, d // nx)
        return np.where(r == d, pe_eject[r], mesh[r, go, d % 2])

    f = b.fabric(p, pe_src, pe_eject, next_queue)
    return f
