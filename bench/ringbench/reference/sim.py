"""Plain cycle-by-cycle simulation of one point, in NumPy.

Each cycle, in order:

1. every non-empty queue's head flit finds its next queue by the family's
   routing rules; a flit with no route is dropped;
2. heads bound for the same physical channel contend.  Score = (2 x the
   queue kind's priority + its waiting cycles, capped at the starvation
   limit), ties broken by a rotating order over queue ids: ``(q + cycle)
   mod 2^k``, 2^k the least power of two above the queue count.  The best
   score wins the channel.  A winner whose target queue is full, and whose
   target's own head did not also win, cannot move: it leaves the
   contest, and the channel is arbitrated again among those left, for up
   to ``arbitration_passes`` passes in all.  Winners still blocked after
   the last pass stay put and are counted as ``lost``;
3. winners leave their queues; a winner crossing a faulty wire is dropped
   on it; the others enter the tail of their target, or are delivered at
   an eject sink (latency = cycle - injection cycle).  A target that is
   full after all of this (a blocked head that was counted above) loses
   the flit, counted in ``lost`` and ``dropped``;
4. PEs inject into their inject buffer where it has room;
5. counters: from ``warmup`` on, delivered, offered, accepted, dropped,
   latency sum and hops moved; ``lost`` from cycle 0.

Trace replay: each PE injects the current phase's flits to the phase's
destination; the phase ends in the cycle in which the last of its flits
is delivered or dropped, and the next phase's flits start the cycle after.
A blocked injection waits, so trace traffic offers what it injects.
"""
from __future__ import annotations

import numpy as np

from ringbench.reference import fabric as fb


def simulate(f: fb.Fabric, *, cycles: int, warmup: int,
             starvation_limit: int, arbitration_passes: int,
             inj: np.ndarray, dst: np.ndarray | None = None,
             phases: tuple[np.ndarray, np.ndarray] | None = None,
             dead: np.ndarray | None = None) -> dict:
    """Counters of one point.  ``inj`` [cycles, P] bool, ``dst`` [cycles, P]
    (statistical); ``phases`` = (dst, flits) [n_phases, P] (trace replay);
    ``dead`` the ids of queues whose wire is dead: a flit granted onto it
    leaves its queue and is dropped on the wire."""
    n_q, p = f.n_queues, f.n_pes
    finite = f.cap < fb.UNBOUNDED
    depth = int(f.cap[finite].max())
    born = np.zeros((n_q, depth), np.int64)
    dest = np.zeros((n_q, depth), np.int64)
    qlen = np.zeros(n_q, np.int64)
    wait = np.zeros(n_q, np.int64)
    prio, cap, phys, kind = f.prio, f.cap, f.phys, f.kind
    # Least power of two above the queue count, plus one spare id (an
    # empty queue row kept past the last).
    rr = 1 << n_q.bit_length()
    c = dict.fromkeys(("delivered", "offered", "accepted", "dropped", "lost",
                       "lat_sum", "moved"), 0)
    if phases is not None:
        ph_dst, ph_flits = phases
        ph_total = ph_flits.sum(axis=1)
        n_ph = ph_dst.shape[0]
        ph_idx, sent, credit = 0, np.zeros(p, np.int64), 0
        ph_done = [-1] * n_ph

    for cycle in range(cycles):
        g = cycle >= warmup
        inj_row = inj[cycle]
        if phases is not None:
            cur = min(ph_idx, n_ph - 1)
            inj_row = inj_row & (ph_idx < n_ph) & (ph_flits[cur] - sent > 0)
            dst_row = ph_dst[cur]
        else:
            dst_row = dst[cycle]

        # 1. routing
        held = np.nonzero(qlen > 0)[0]
        nxt = f.next_queue(held, dest[held, 0])
        no_route = held[nxt < 0]
        q, tgt = held[nxt >= 0], nxt[nxt >= 0]

        # 2. arbitration
        score = ((prio[q] * 2 + np.minimum(wait[q], starvation_limit)) * rr
                 + ((q + cycle) & (rr - 1)))
        chan = phys[tgt]
        active = np.ones(q.size, bool)
        for i in range(arbitration_passes):
            best = np.full(f.n_phys, -1, np.int64)
            np.maximum.at(best, chan[active], score[active])
            win = active & (score == best[chan])
            won = np.zeros(n_q, np.int64)
            won[q[win]] = 1
            blocked = win & (qlen[tgt] - won[tgt] >= cap[tgt])
            if not blocked.any():
                break
            if i + 1 < arbitration_passes:
                active &= ~blocked
        win &= ~blocked
        n_residue = int(blocked.sum())

        # 3. moves
        sink = kind[tgt] == fb.EJECT
        wire_drop = win & np.isin(tgt, dead if dead is not None else [])
        send = win & ~sink & ~wire_drop
        deliver = win & sink & ~wire_drop
        delivered = int(deliver.sum())
        lat = int((cycle - born[q[deliver], 0]).sum())
        moved = int(win.sum())
        m_tgt = tgt[send]
        m_born, m_dest = born[q[send], 0], dest[q[send], 0]

        leave = np.concatenate([q[win], no_route])
        stay = np.ones(n_q, bool)
        stay[leave] = False
        wait = np.where((qlen > 0) & stay, wait + 1, 0)
        born[leave, :-1] = born[leave, 1:]
        dest[leave, :-1] = dest[leave, 1:]
        qlen[leave] -= 1

        full = qlen[m_tgt] >= cap[m_tgt]
        n_lost = int(full.sum())
        t = m_tgt[~full]
        born[t, qlen[t]] = m_born[~full]
        dest[t, qlen[t]] = m_dest[~full]
        qlen[t] += 1

        # 4. injection
        src_q = f.pe_src
        room = qlen[src_q] < cap[src_q]
        acc = inj_row & room
        t = src_q[acc]
        born[t, qlen[t]] = cycle
        dest[t, qlen[t]] = dst_row[acc]
        qlen[t] += 1

        # 5. counters
        hard = no_route.size + n_lost + int(wire_drop.sum())
        n_acc = int(acc.sum())
        if phases is None:
            offered = int(inj_row.sum())
            dropped = int((inj_row & ~room).sum()) + hard
        else:
            offered, dropped = n_acc, hard
        if g:
            c["delivered"] += delivered
            c["offered"] += offered
            c["accepted"] += n_acc
            c["dropped"] += dropped
            c["lat_sum"] += lat
            c["moved"] += moved
        c["lost"] += n_lost + n_residue

        if phases is not None:
            sent += acc
            credit += delivered + hard
            if ph_idx < n_ph and credit >= ph_total[cur]:
                ph_done[cur] = cycle
                ph_idx += 1
                sent[:] = 0
                credit = 0

    c["in_flight"] = int(qlen.sum())
    c["phase_done"] = ph_done if phases is not None else []
    return c
