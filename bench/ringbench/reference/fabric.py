"""Queue-level description of a fabric, as the plain reference simulates it.

Every virtual-channel buffer of every directed channel is one FIFO queue.
Queues are numbered in the order a family's builder adds them; the
numbering is part of the model, because arbitration breaks ties by a
rotating order over queue ids.  Two VCs of one directed channel share one
physical channel (``phys``): one flit may cross a wire per cycle.

A family module (``fabric_<family>.py`` beside this file) builds a
``Fabric`` from a configuration dict and supplies ``next_queue``: for a
flit at the head of queue ``q`` bound for PE ``d``, the queue it moves to
next, computed from the family's routing rules for each flit (no table).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Callable

import numpy as np

# Queue kinds.
PE_SRC, EJECT, RING, RS2R, R2RS, MESH = range(6)
FABRIC_KINDS = (RING, RS2R, R2RS, MESH)
# Static arbitration priority per kind: in-ring traffic first, then the
# router's ports, PE injection last (the paper, section 4.2).
PRIORITY = {PE_SRC: 1, EJECT: 0, RING: 3, RS2R: 3, R2RS: 2, MESH: 2}
UNBOUNDED = 1 << 30   # capacity of an eject sink

PES_PER_RINGLET = 4
PES_PER_BLOCK = 16


@dataclasses.dataclass
class Fabric:
    n_pes: int
    kind: np.ndarray      # [L] int
    vc: np.ndarray        # [L] int
    phys: np.ndarray      # [L] int physical channel
    src: np.ndarray       # [L] source node (-1: a PE's inject buffer)
    dst: np.ndarray       # [L] destination node (-1: an eject sink)
    cap: np.ndarray       # [L] capacity
    n_phys: int
    pe_src: np.ndarray    # [P] inject queue of each PE
    pe_eject: np.ndarray  # [P] eject sink of each PE
    next_queue: Callable[[np.ndarray, np.ndarray], np.ndarray] = None

    @property
    def n_queues(self) -> int:
        return int(self.kind.shape[0])

    @property
    def prio(self) -> np.ndarray:
        return np.array([PRIORITY[int(k)] for k in self.kind], np.int64)

    def fabric_channels(self) -> np.ndarray:
        """Sorted physical channel ids of the fabric's own channels (not
        the PE inject and eject buffers): what a dead link can be."""
        return np.unique(self.phys[np.isin(self.kind, FABRIC_KINDS)])


class Builder:
    """Adds queues in order; the VCs of one call share a physical id."""

    def __init__(self):
        self.rows: list[tuple[int, int, int, int, int, int]] = []
        self.n_phys = 0

    def add(self, kind: int, src: int, dst: int, cap: int,
            n_vcs: int = 1) -> list[int]:
        ids = []
        for vc in range(n_vcs):
            ids.append(len(self.rows))
            self.rows.append((kind, vc, self.n_phys, src, dst, cap))
        self.n_phys += 1
        return ids

    def fabric(self, n_pes: int, pe_src, pe_eject, next_queue) -> Fabric:
        a = np.array(self.rows, np.int64)
        return Fabric(n_pes=n_pes, kind=a[:, 0], vc=a[:, 1], phys=a[:, 2],
                      src=a[:, 3], dst=a[:, 4], cap=a[:, 5],
                      n_phys=self.n_phys,
                      pe_src=np.asarray(pe_src, np.int64),
                      pe_eject=np.asarray(pe_eject, np.int64),
                      next_queue=next_queue)


def mesh_direction(x, y, tx, ty):
    """XY dimension order: 0 east, 1 west (x first), 2 north (+y),
    3 south (-y)."""
    return np.where(x != tx, np.where(tx > x, 0, 1),
                    np.where(ty > y, 2, 3))


def add_mesh(b: Builder, nx: int, ny: int, node0: int, cap: int):
    """Two-VC channels between neighbouring routers of an nx-by-ny grid,
    router ``r`` at node ``node0 + r``; returns ``[routers, 4, 2]`` queue
    ids by direction and VC (-1 where the grid ends)."""
    out = np.full((nx * ny, 4, 2), -1, np.int64)
    for y in range(ny):
        for x in range(nx):
            a = y * nx + x
            for d, (dx, dy) in enumerate(((1, 0), (-1, 0), (0, 1), (0, -1))):
                if 0 <= x + dx < nx and 0 <= y + dy < ny:
                    c = (y + dy) * nx + x + dx
                    out[a, d] = b.add(MESH, node0 + a, node0 + c, cap, 2)
    return out


def build(config: dict) -> Fabric:
    """The fabric of a configuration, by its ``family`` key."""
    mod = importlib.import_module(
        f"ringbench.reference.fabric_{config['family']}")
    return mod.build(config)
