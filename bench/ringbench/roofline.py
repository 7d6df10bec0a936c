"""The least bytes one simulated cycle of one point has to move.

Whatever implements the step, each cycle reads and writes the fabric's
live state once: one 4-byte word per buffer slot of the fabric's queues
(the sum of their capacities; eject sinks hold nothing), and per queue its
length and its waiting count.  It also reads the cycle's injection row: a
1-byte flag and a 2-byte destination per PE.  The step does almost no
arithmetic per byte, so its roofline is the HBM bandwidth: least time =
these bytes / the chip's HBM bytes per second.
"""
from __future__ import annotations

from ringbench.reference import fabric as fb

WORD = 4


def bytes_per_point_cycle(f: fb.Fabric) -> int:
    slots = int(f.cap[f.cap < fb.UNBOUNDED].sum())
    state = WORD * (slots + 2 * f.n_queues)
    return 2 * state + 3 * f.n_pes
