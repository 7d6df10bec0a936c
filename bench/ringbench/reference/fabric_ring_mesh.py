"""The paper's ring-mesh (Fig. 1): blocks of four 4-PE bidirectional
ringlets, each block's ringlets joined at their master ring switch
(position 0) to the block's router, routers in a 2D mesh.

Nodes: ring switch of PE ``p`` is node ``p``; router of block ``b`` is node
``n_pes + b``.  Queue order: each PE's inject buffer and eject sink; each
PE's clockwise and counter-clockwise ring channel (2 VCs); each ringlet's
master-to-router and router-to-master channel (1 VC); the mesh channels,
router by router, east, west, north, south (2 VCs).

Routing, per flit:
- at a ring switch, a flit for its own ringlet takes the shorter ring
  direction (clockwise on a tie) and ejects at its PE; a flit for another
  ringlet travels to the master and up to the router;
- VC phases keep the channel dependencies acyclic: fresh flits ride VC 0,
  flits coming down from the router or passing the master ride VC 1;
- at a router, a flit for its own block goes down to its ringlet's master;
  otherwise XY dimension order, on the mesh VC given by the parity of the
  destination's ringlet.
"""
from __future__ import annotations

import numpy as np

from ringbench.reference import fabric as fb

RP = fb.PES_PER_RINGLET


def build(config: dict) -> fb.Fabric:
    p, bx, by = config["n_pes"], config["blocks_x"], config["blocks_y"]
    n_blocks = bx * by
    n_ringlets = p // RP
    if n_blocks * fb.PES_PER_BLOCK != p:
        raise ValueError(f"{bx}x{by} blocks of {fb.PES_PER_BLOCK} PEs "
                         f"are not {p} PEs")
    qd, sqd = config["queue_depth"], config["src_queue_depth"]
    b = fb.Builder()
    pe_src, pe_eject = [], []
    for pe in range(p):
        pe_src += b.add(fb.PE_SRC, -1, pe, sqd)
        pe_eject += b.add(fb.EJECT, pe, -1, fb.UNBOUNDED)
    cw = np.zeros((p, 2), np.int64)
    ccw = np.zeros((p, 2), np.int64)
    for pe in range(p):
        base = pe - pe % RP
        cw[pe] = b.add(fb.RING, pe, base + (pe + 1) % RP, qd, 2)
        ccw[pe] = b.add(fb.RING, pe, base + (pe - 1) % RP, qd, 2)
    up = np.zeros(n_ringlets, np.int64)
    down = np.zeros(n_ringlets, np.int64)
    for r in range(n_ringlets):
        router = p + r // (fb.PES_PER_BLOCK // RP)
        up[r] = b.add(fb.RS2R, r * RP, router, qd)[0]
        down[r] = b.add(fb.R2RS, router, r * RP, qd)[0]
    mesh = fb.add_mesh(b, bx, by, p, qd)
    pe_eject = np.asarray(pe_eject)

    def next_queue(q: np.ndarray, d: np.ndarray) -> np.ndarray:
        kind, node, vc = f.kind[q], f.dst[q], f.vc[q]
        d_ringlet = d // RP
        # Flits at a ring switch.
        pe = np.clip(node, 0, p - 1)
        pos, ringlet = pe % RP, pe // RP
        dpos = d % RP
        vc_out = np.where(kind == fb.R2RS, 1,
                          np.where((pos == 0) & (kind == fb.RING), 1,
                                   np.where(kind == fb.PE_SRC, 0, vc)))
        shorter_cw = (dpos - pos) % RP <= (pos - dpos) % RP
        along = np.where(shorter_cw, cw[pe, vc_out], ccw[pe, vc_out])
        own = np.where(dpos == pos, pe_eject[pe], along)
        toward_master = np.where((-pos) % RP <= pos, cw[pe, 0], ccw[pe, 0])
        other = np.where(pos == 0, up[ringlet], toward_master)
        at_switch = np.where(d_ringlet == ringlet, own, other)
        # Flits at a router.
        blk = np.clip(node - p, 0, n_blocks - 1)
        d_blk = d // fb.PES_PER_BLOCK
        go = fb.mesh_direction(blk % bx, blk // bx, d_blk % bx, d_blk // bx)
        across = mesh[blk, go, d_ringlet % 2]
        descend = down[blk * (fb.PES_PER_BLOCK // RP)
                       + d_ringlet % (fb.PES_PER_BLOCK // RP)]
        at_router = np.where(d_blk == blk, descend, across)
        return np.where(node < p, at_switch, at_router)

    f = b.fabric(p, pe_src, pe_eject, next_queue)
    return f
