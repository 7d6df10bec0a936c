"""Fused Pallas kernel for the NoC simulator's per-cycle hot path.

The scatter-free cycle step (DESIGN.md §4/§11) is three fused stages —
route + arbitrate (gather + row-max over the structural fan-in candidate
tables, then the grant/re-arbitrate feasibility fixpoint), queue
dequeue/enqueue on the packed int32 queue words, and integer Metrics
accumulation.  Under the XLA backend each stage's intermediates round-trip
through HBM between ``lax.scan`` iterations; here the whole cycle loop runs
as ONE ``pl.pallas_call`` with ``grid=(cycles,)``: TPU grids execute
sequentially, so the queue state (packed words, lengths, aging counters)
and the metric accumulators are carried across cycles in VMEM scratch, and
the arbitration fixpoint iterates over VMEM-resident candidate scores
instead of re-materializing the tables per pass (same state-in-scratch
trick as ``kernels/flash_attention.py`` / ``kernels/ssd_scan.py``).  Only
the per-cycle traffic rows (pregenerated Bernoulli injections and
destination draws) stream in, one row per grid step.

``cycle_step`` is the single source of truth for the step *math*: the XLA
backend scans it and the Pallas kernel calls it on values read from its
refs, so the two backends are bit-identical by construction — every
accumulator is an int32, leaving no reduction-order slack
(tests/test_noc_kernel.py asserts equality across the full matrix).  Off
TPU the kernel runs in interpret mode (``interpret=None`` auto-detects).
On TPU, Mosaic does not compile it yet: its gather lowering accepts only
same-shape 2-D ``take_along_axis``, and ``cycle_step`` is built from
arbitrary integer gathers (tests/test_tpu_compile.py pins the refusal).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Flat metric-accumulator layout shared by both simulator backends
# (``core.sim`` reassembles its Metrics pytree from these): slot names of
# the [N_SCALARS] int32 vector, then the rows of the [N_KIND_ROWS, 8]
# per-queue-kind table.  The vector is padded to 8 lanes; KIND_QLEN is
# written from the final queue lengths after the run, not per cycle.
# STALL_CREDIT records the credits the stall watchdog found unretired
# when it terminated a trace phase (0 otherwise).
(DELIVERED, OFFERED, ACCEPTED, DROPPED, LOST, LAT_SUM, MOVED,
 STALL_CREDIT) = range(8)
N_SCALARS = 8
KIND_WINS, KIND_STALLS, KIND_QLEN = range(3)
N_KIND_ROWS = 3


class GeomArrays(NamedTuple):
    """Duck-typed view of ``sim.Geometry``'s device arrays.  The XLA scan
    backend passes the Geometry itself; the kernel rebuilds this view from
    values read out of its VMEM refs — ``cycle_step`` only touches these
    attributes and ``.shape``, so either works."""

    route: jax.Array        # [L+1, P] int16
    kind: jax.Array         # [L+1] int32
    prio: jax.Array         # [L+1] int32
    cap: jax.Array          # [L+1] int32
    phys: jax.Array         # [L+1] int32
    is_sink: jax.Array      # [L+1] bool
    pe_src_link: jax.Array  # [P] int32
    inj_pe: jax.Array       # [L+1] int32
    cand: jax.Array         # [n_phys+1, Fc] int32
    intab: jax.Array        # [L+1, Fi] int32


def initial_state(n_links: int, depth: int, *, n_pes: int = 0,
                  n_phases: int = 0):
    """Zeroed carry for ``cycle_step``: (packed queue words, queue
    lengths, aging counters, scalar metrics, per-kind metrics).  With
    ``n_phases > 0`` (trace replay, DESIGN.md §12) the carry extends to
    the 10-tuple: + (phase cursor [1], per-PE flits sent [P], retired-flit
    credit [1], per-phase completion cycles [n_phases] initialized -1,
    stall-watchdog counter [1]).
    """
    base = (jnp.zeros((n_links + 1, depth), jnp.int32),
            jnp.zeros((n_links + 1,), jnp.int32),
            jnp.zeros((n_links + 1,), jnp.int32),
            jnp.zeros((N_SCALARS,), jnp.int32),
            jnp.zeros((N_KIND_ROWS, 8), jnp.int32))
    if n_phases <= 0:
        return base
    return base + (jnp.zeros((1,), jnp.int32),
                   jnp.zeros((n_pes,), jnp.int32),
                   jnp.zeros((1,), jnp.int32),
                   jnp.full((n_phases,), -1, jnp.int32),
                   jnp.zeros((1,), jnp.int32))


def cycle_step(geom, state, cycle, inj, dst, fault_u=None, *, warmup: int,
               starvation_limit: int, arb_iters: int, trace=None,
               faults=None, strict_barrier: bool = False, watchdog: int = 0,
               diagnostics: bool = False):
    """One simulator cycle (route -> arbitrate -> move -> inject -> count).

    Pure function of VMEM-sized values; see ``core.sim``'s module docstring
    for the model and the scatter-free layout.  ``inj`` is the [P] bool
    injection row and ``dst`` the [P] int16 destination row for this cycle
    (pregenerated by ``sim._run_core``).  Returns the updated state tuple.

    ``trace`` switches on phase-gated replay (DESIGN.md §12): a static
    triple of ``(ph_dst [n_phases, P], ph_flits [n_phases, P],
    ph_total [n_phases])`` int32 arrays.  ``state`` is then the 10-tuple of
    ``initial_state(..., n_phases=...)``; the injection row is masked to
    PEs with flits left in the *current* phase, destinations come from the
    phase's map, and the phase cursor advances — at the END of the cycle,
    recording the completion cycle — once every one of the phase's flits
    has retired (delivered, route-dropped, or lost; drops count so a lossy
    route cannot deadlock the barrier).  Because the phase advance is this
    same int32 math in both backends, XLA scan and the fused Pallas kernel
    stay bit-identical in trace mode too.

    ``faults`` switches on fault injection (repro.faults, DESIGN.md §13):
    a traced triple ``(links [F] int32 queue ids, drop_p [F] f32,
    onset [F] int32)`` with ``fault_u`` the [F] pregenerated uniform row
    for this cycle.  A flit granted a move into a faulty queue is dropped
    crossing the wire with probability ``drop_p`` once ``cycle >= onset``
    (dead components lower to permanent ``drop_p=1`` entries); the drop is
    the same int32 bookkeeping on both backends.  Pad entries point at the
    dummy queue row and carry ``drop_p=0``, so they can never fire.

    ``strict_barrier`` (trace mode) makes phase barriers retire *delivered*
    flits only — faithful collective semantics, where a dropped flit means
    the barrier never closes.  ``watchdog > 0`` (trace mode) then detects a
    phase making no progress (no credit retired, no injection accepted, no
    flit moved) for ``watchdog`` consecutive cycles, records the stall as
    ``ph_done[phase] = -2 - cycle`` plus the unretired credit in the
    ``STALL_CREDIT`` metric slot, and aborts the replay — a per-phase
    diagnostic instead of spinning to budget exhaustion.
    """
    if trace is None:
        q_pack, q_len, wait, m_scal, m_kind = state
    else:
        (q_pack, q_len, wait, m_scal, m_kind,
         ph_idx, sent, credit, ph_done, stall) = state
        ph_dst, ph_flits, ph_total = trace
        n_phases = ph_dst.shape[0]
        cur = jnp.clip(ph_idx[0], 0, n_phases - 1)
        active = ph_idx[0] < n_phases
        cur_dst = jax.lax.dynamic_slice_in_dim(ph_dst, cur, 1, 0)[0]
        cur_flits = jax.lax.dynamic_slice_in_dim(ph_flits, cur, 1, 0)[0]
        # The Bernoulli row throttles bandwidth (inj_rate=1.0 -> inject as
        # fast as back-pressure allows); the phase gate does the rest.
        inj = inj & active & (cur_flits - sent > 0)
        dst = cur_dst
    lp1, p_pes = geom.route.shape
    n_links = lp1 - 1
    depth = q_pack.shape[1]
    np1 = geom.cand.shape[0]
    link_ids = jnp.arange(lp1, dtype=jnp.int32)
    pow2 = 1 << int(np.ceil(np.log2(lp1)))
    row_ids = link_ids[:, None]                        # [L+1, 1]
    p_ids = jnp.arange(np1, dtype=jnp.int32)[:, None]  # [NP1, 1]
    col_k = jnp.arange(depth, dtype=jnp.int32)[None, :]
    measure = cycle >= warmup

    # --- 1. routing: next link for every queue head ----------------------
    head_pack = q_pack[:, 0]
    head_dst = (head_pack & 2047) - 1
    head_born = head_pack >> 11
    valid = q_len > 0
    nxt = jnp.take_along_axis(
        geom.route, jnp.clip(head_dst, 0, p_pes - 1)[:, None],
        axis=1)[:, 0].astype(jnp.int32)
    nxt = jnp.where(valid, nxt, -1)
    nxt_c = jnp.clip(nxt, 0, n_links)
    nxt_phys = geom.phys[nxt_c]

    # Switched-off routes (INVALID) drop the flit — paper §5.1.
    drop_route = valid & (nxt < 0)

    # --- 2. arbitration over each output physical channel ----------------
    # One grant per physical channel per cycle; weighted round-robin
    # (§4.2): in-ring traffic leads by a small static margin; waiting
    # inputs age upward so no port starves.
    contend = valid & (nxt >= 0)
    eff_prio = geom.prio * 2 + jnp.minimum(wait, starvation_limit)
    rot = (link_ids + cycle) & (pow2 - 1)     # unique RR tiebreak
    score = eff_prio * pow2 + rot             # globally unique

    # Fixpoint-invariant gathers: candidate scores, candidate->channel
    # match, and target occupancy/capacity change per cycle, not per
    # re-arbitration pass — in the fused kernel they stay in VMEM for the
    # whole while_loop.
    cand_score = jnp.where(nxt_phys[geom.cand] == p_ids,
                           score[geom.cand], -1)   # [NP1, Fc]
    ql_t = q_len[nxt_c]
    cap_t = geom.cap[nxt_c]

    def select(active):
        # Scatter-free argmax per output channel: mask each channel's
        # structural candidates to the active ones, row-max, then winners
        # are the queues matching their channel's best (unique) score.
        best = jnp.max(jnp.where(active[geom.cand], cand_score, -1),
                       axis=1)
        return active & (score == best[nxt_phys])

    def feasible(w):
        # A grant into a full queue is only feasible if that queue's own
        # head departs this cycle (lockstep / slotted-ring semantics).
        return (ql_t - w[nxt_c].astype(jnp.int32)) < cap_t

    # Grant-and-re-arbitrate fixpoint with early exit; residue past the
    # iteration cap is counted (not moved) so conservation stays exact.
    w0 = select(contend)
    feas0 = feasible(w0)

    def arb_cond(s):
        return s[3] & (s[4] < arb_iters)

    def arb_body(s):
        active, w, feas_w, _, i = s
        active = active & (~w | feas_w)
        w = select(active)
        feas_w = feasible(w)
        return (active, w, feas_w, jnp.any(w & ~feas_w), i + 1)

    _, winner, feas_w, _, _ = jax.lax.while_loop(
        arb_cond, arb_body,
        (contend, w0, feas0, jnp.any(w0 & ~feas0), jnp.int32(1)))
    residue = winner & ~feas_w
    winner = winner & ~residue

    deq = winner | drop_route
    sink = geom.is_sink[nxt_c]
    # Fault injection: a granted flit crossing a faulty wire is dropped on
    # the wire (it leaves its source queue but never arrives).  faulty_now
    # is a scatter-free [F] x [L+1] compare collapsed over entries.
    if faults is not None:
        f_links, f_drop_p, f_onset = faults
        f_act = (fault_u < f_drop_p) & (cycle >= f_onset)        # [F]
        faulty_now = jnp.any((f_links[:, None] == link_ids[None, :])
                             & f_act[:, None], axis=0)           # [L+1]
        fault_drop = winner & faulty_now[nxt_c]
        send = winner & ~sink & ~fault_drop
    else:
        fault_drop = None
        send = winner & ~sink

    # --- 3. apply moves ---------------------------------------------------
    q_pack = jnp.where(
        deq[:, None],
        jnp.concatenate([q_pack[:, 1:],
                         jnp.zeros((lp1, 1), jnp.int32)], 1), q_pack)
    q_len = q_len - deq.astype(jnp.int32)

    # Scatter-free enqueue: invert the move map through the structural
    # fan-in table — each queue row finds the (unique) sender targeting
    # it, then writes its tail slot with a one-hot column mask.
    inc = send[geom.intab] & (nxt_c[geom.intab] == row_ids)
    src_q = jnp.max(jnp.where(inc, geom.intab, -1), axis=1)
    has_in = src_q >= 0
    src_qc = jnp.clip(src_q, 0, n_links)
    # Exactness guard: a residue removal can leave a grant whose target is
    # still full; such moves become counted drops rather than corrupting
    # queue state (kept 0 by the fixpoint in practice).
    lost_enq_row = has_in & (q_len >= geom.cap)
    enq_row = has_in & ~lost_enq_row

    deliver = (winner & sink if fault_drop is None
               else winner & sink & ~fault_drop)
    delivered_c = jnp.sum(deliver.astype(jnp.int32))
    lat_c = jnp.sum(jnp.where(deliver, cycle - head_born, 0))
    moved_c = jnp.sum(winner.astype(jnp.int32))
    wait = jnp.where(valid & ~deq, wait + 1, 0)

    # --- 4. injection -----------------------------------------------------
    # Nothing ever routes *into* a PE_SRC queue, so enqueue and injection
    # touch disjoint rows and share one tail-write pass.
    room = q_len[geom.pe_src_link] < geom.cap[geom.pe_src_link]
    acc = inj & room
    pe_of_row = geom.inj_pe
    pec = jnp.clip(pe_of_row, 0, p_pes - 1)
    acc_row = (pe_of_row >= 0) & acc[pec]

    put = enq_row | acc_row
    tail = put[:, None] & (col_k == jnp.clip(q_len, 0, depth - 1)[:, None])
    inj_pack = (cycle << 11) | (dst[pec].astype(jnp.int32) + 1)
    val = jnp.where(enq_row, head_pack[src_qc], inj_pack)
    q_pack = jnp.where(tail, val[:, None], q_pack)
    q_len = q_len + put.astype(jnp.int32)

    # --- 5. metric accumulation (int32, warmup-gated; `lost` ungated) ----
    g = measure.astype(jnp.int32)
    lost_c = jnp.sum(lost_enq_row.astype(jnp.int32))
    acc_c = jnp.sum(acc.astype(jnp.int32))
    fault_drop_c = (jnp.sum(fault_drop.astype(jnp.int32))
                    if fault_drop is not None else 0)
    hard_drop_c = (jnp.sum(drop_route.astype(jnp.int32)) + lost_c
                   + fault_drop_c)
    if trace is None:
        offered_c = jnp.sum(inj.astype(jnp.int32))
        dropped_c = jnp.sum((inj & ~room).astype(jnp.int32)) + hard_drop_c
    else:
        # Trace semantics: a blocked injection retries next cycle (the
        # flit is workload, not a Bernoulli draw that evaporates), so
        # offered := accepted and back-pressure is not a drop.  This
        # keeps conservation exact: offered == delivered + dropped +
        # in_flight at every cycle.
        offered_c = acc_c
        dropped_c = hard_drop_c
    m_scal = m_scal + jnp.stack([
        g * delivered_c,
        g * offered_c,
        g * acc_c,
        g * dropped_c,
        lost_c + jnp.sum(residue.astype(jnp.int32)),
        g * lat_c,
        g * moved_c,
        jnp.int32(0),
    ])
    if diagnostics:
        kinds8 = jnp.arange(8, dtype=jnp.int32)[:, None]
        kind_oh = geom.kind[None, :] == kinds8
        stalled = contend & ~winner
        stall_kind = geom.kind[nxt_c]
        wins = g * jnp.sum(kind_oh & winner[None, :], axis=1,
                           dtype=jnp.int32)
        stalls = g * jnp.sum((stall_kind[None, :] == kinds8)
                             & stalled[None, :], axis=1, dtype=jnp.int32)
        m_kind = m_kind + jnp.stack(
            [wins, stalls, jnp.zeros((8,), jnp.int32)])
    if trace is None:
        return q_pack, q_len, wait, m_scal, m_kind

    # --- 6. phase barrier (trace mode) -----------------------------------
    # A flit retires when it delivers, is dropped by a switched-off route,
    # or is lost to the exactness guard; the phase completes once all of
    # its flits have retired.  The cursor advances at the END of the
    # cycle, so phase i+1's first injection happens at cycle+1 — strictly
    # after phase i's last delivery cycle (the recorded ph_done[i]).
    # Under strict_barrier only *deliveries* retire credit (faithful
    # collective semantics: a flit dropped on a dead link leaves the
    # barrier waiting forever — the stall watchdog's job to report).
    sent = sent + acc.astype(jnp.int32)
    retired_c = delivered_c if strict_barrier else delivered_c + hard_drop_c
    credit = credit + retired_c
    cur_total = jax.lax.dynamic_slice_in_dim(ph_total, cur, 1, 0)[0]
    done_now = active & (credit[0] >= cur_total)
    ph_arange = jnp.arange(n_phases, dtype=jnp.int32)
    ph_done = jnp.where(done_now & (ph_arange == cur), cycle, ph_done)
    ph_idx = ph_idx + done_now.astype(jnp.int32)
    sent = jnp.where(done_now, 0, sent)
    credit = jnp.where(done_now, 0, credit)
    if watchdog:
        # Progress = the active phase retired credit, accepted an
        # injection, or moved a flit (congestion is not a stall; a phase
        # with nothing in flight and nothing left to retire is).
        progress = (retired_c > 0) | (acc_c > 0) | (moved_c > 0)
        stall = jnp.where(active & ~done_now & ~progress, stall + 1,
                          jnp.zeros_like(stall))
        fire = active & ~done_now & (stall[0] >= watchdog)
        # Per-phase diagnostic: the stalled phase records -2 - cycle, the
        # unretired credit lands in the STALL_CREDIT slot, and the cursor
        # jumps past the end, aborting the replay.
        ph_done = jnp.where(fire & (ph_arange == cur), -2 - cycle, ph_done)
        m_scal = m_scal + jnp.where(
            jnp.arange(N_SCALARS, dtype=jnp.int32) == STALL_CREDIT,
            fire.astype(jnp.int32) * (cur_total - credit[0]), 0)
        ph_idx = jnp.where(fire, n_phases, ph_idx)
    return (q_pack, q_len, wait, m_scal, m_kind,
            ph_idx, sent, credit, ph_done, stall)


# ---------------------------------------------------------------------------
# The fused kernel: the whole cycle loop as one pallas_call.
#
# Mosaic tiles the last two dimensions of every ref (8 sublanes x 128
# lanes), so the kernel's refs are all at least 2-D: each 1-D vector rides
# as a [1, N] row (read back with ``_vec``), the per-cycle streams are
# [cycles, 1, N] with the cycle dimension squeezed out of the block, and
# bool/int16 rows travel as int32.  The route table keeps int16 to halve
# its VMEM footprint.
# ---------------------------------------------------------------------------
def _vec(ref):
    """[N] value of a [1, N] row ref."""
    return ref[0]


def _noc_step_kernel(*refs, cycles: int, warmup: int, starvation_limit: int,
                     arb_iters: int, diagnostics: bool, trace_mode: bool,
                     fault_mode: bool, strict_barrier: bool, watchdog: int):
    inj_ref, dst_ref = refs[:2]
    k = 2
    fu_ref = None
    if fault_mode:
        fu_ref = refs[k]
        k += 1
    (route_ref, kind_ref, prio_ref, cap_ref, phys_ref,
     sink_ref, pe_src_ref, inj_pe_ref, cand_ref, intab_ref) = refs[k:k + 10]
    k += 10
    faults = None
    if fault_mode:
        faults = tuple(_vec(r) for r in refs[k:k + 3])
        k += 3
    if trace_mode:
        ph_dst_ref, ph_flits_ref, ph_total_ref = refs[k:k + 3]
        k += 3
        qlen_out_ref, mscal_out_ref, mkind_out_ref, phdone_out_ref = \
            refs[k:k + 4]
        (qpack_ref, qlen_ref, wait_ref, mscal_ref, mkind_ref,
         phidx_ref, sent_ref, credit_ref, phdone_ref, stall_ref) = \
            refs[k + 4:]
    else:
        qlen_out_ref, mscal_out_ref, mkind_out_ref = refs[k:k + 3]
        (qpack_ref, qlen_ref, wait_ref, mscal_ref, mkind_ref) = refs[k + 3:]
    cycle = pl.program_id(0)

    @pl.when(cycle == 0)
    def _init():
        qpack_ref[...] = jnp.zeros_like(qpack_ref)
        qlen_ref[...] = jnp.zeros_like(qlen_ref)
        wait_ref[...] = jnp.zeros_like(wait_ref)
        mscal_ref[...] = jnp.zeros_like(mscal_ref)
        mkind_ref[...] = jnp.zeros_like(mkind_ref)
        if trace_mode:
            phidx_ref[...] = jnp.zeros_like(phidx_ref)
            sent_ref[...] = jnp.zeros_like(sent_ref)
            credit_ref[...] = jnp.zeros_like(credit_ref)
            phdone_ref[...] = jnp.full_like(phdone_ref, -1)
            stall_ref[...] = jnp.zeros_like(stall_ref)

    geom = GeomArrays(
        route=route_ref[...], kind=_vec(kind_ref), prio=_vec(prio_ref),
        cap=_vec(cap_ref), phys=_vec(phys_ref), is_sink=_vec(sink_ref) != 0,
        pe_src_link=_vec(pe_src_ref), inj_pe=_vec(inj_pe_ref),
        cand=cand_ref[...], intab=intab_ref[...])
    state = (qpack_ref[...], _vec(qlen_ref), _vec(wait_ref),
             _vec(mscal_ref), mkind_ref[...])
    trace = None
    if trace_mode:
        state = state + tuple(_vec(r) for r in (phidx_ref, sent_ref,
                                                credit_ref, phdone_ref,
                                                stall_ref))
        trace = (ph_dst_ref[...], ph_flits_ref[...], _vec(ph_total_ref))
    out = cycle_step(
        geom, state, cycle, _vec(inj_ref) != 0, _vec(dst_ref),
        _vec(fu_ref) if fault_mode else None, warmup=warmup,
        starvation_limit=starvation_limit, arb_iters=arb_iters,
        trace=trace, faults=faults, strict_barrier=strict_barrier,
        watchdog=watchdog, diagnostics=diagnostics)
    q_pack, q_len, wait, m_scal, m_kind = out[:5]
    qpack_ref[...] = q_pack
    qlen_ref[...] = q_len[None]
    wait_ref[...] = wait[None]
    mscal_ref[...] = m_scal[None]
    mkind_ref[...] = m_kind
    if trace_mode:
        ph_idx, sent, credit, ph_done, stall = out[5:]
        phidx_ref[...] = ph_idx[None]
        sent_ref[...] = sent[None]
        credit_ref[...] = credit[None]
        phdone_ref[...] = ph_done[None]
        stall_ref[...] = stall[None]

    @pl.when(cycle == cycles - 1)
    def _fin():
        qlen_out_ref[...] = q_len[None]
        mscal_out_ref[...] = m_scal[None]
        mkind_out_ref[...] = m_kind
        if trace_mode:
            phdone_out_ref[...] = ph_done[None]


def default_interpret() -> bool:
    """``interpret=None`` resolves to True off-TPU, so the fused kernel
    runs (and is oracle-tested) on CPU CI; on TPU it compiles via Mosaic."""
    return jax.default_backend() != "tpu"


def run_fused(geom, inj_s: jax.Array, dst_s: jax.Array, *, cycles: int,
              warmup: int, starvation_limit: int, arb_iters: int,
              trace=None, faults=None, fault_u=None,
              strict_barrier: bool = False, watchdog: int = 0,
              diagnostics: bool = False,
              interpret: bool | None = None):
    """Run ``cycles`` simulator steps as one fused Pallas invocation.

    ``geom`` is a ``sim.Geometry`` (or anything exposing the same device
    arrays + ``depth``); ``inj_s``/``dst_s`` are the [cycles, P]
    pregenerated traffic streams.  Returns the final ``(q_len [L+1],
    m_scal [N_SCALARS], m_kind [N_KIND_ROWS, 8])`` int32 accumulators —
    ``core.sim`` turns them into its Metrics pytree.  vmap-compatible:
    batched traffic streams against a broadcast geometry is exactly how
    ``core.sweep`` runs whole grids through one compilation.

    ``trace`` (the ``cycle_step`` triple) switches on phase-gated replay:
    the phase tables join the resident VMEM set, the barrier state rides
    in five extra scratch buffers, and a fourth output returns the
    per-phase completion cycles ``ph_done [n_phases]``.

    ``faults`` (the ``cycle_step`` triple) switches on fault injection:
    the [F] entry arrays join the resident VMEM set and ``fault_u``
    ([cycles, F] pregenerated uniforms) streams in one row per grid step,
    exactly like the traffic rows.  ``strict_barrier``/``watchdog`` are
    forwarded to the step (DESIGN.md §13).
    """
    if interpret is None:
        interpret = default_interpret()
    lp1, p_pes = geom.route.shape
    depth = geom.depth

    def _block(shape):
        nd = len(shape)
        return pl.BlockSpec(shape, lambda c, _nd=nd: (0,) * _nd)

    def _const(shape):
        # Grid-invariant inputs are fetched once: one VMEM buffer each.
        nd = len(shape)
        return pl.BlockSpec(shape, lambda c, _nd=nd: (0,) * _nd,
                            pipeline_mode=pl.Buffered(1))

    def _stream(n):
        # Row c of a [cycles, 1, n] stream at grid step c.
        return pl.BlockSpec((None, 1, n), lambda c: (c, 0, 0))

    def _row(x):
        return x.reshape(1, -1)

    kernel = functools.partial(
        _noc_step_kernel, cycles=cycles, warmup=warmup,
        starvation_limit=starvation_limit, arb_iters=arb_iters,
        diagnostics=diagnostics, trace_mode=trace is not None,
        fault_mode=faults is not None, strict_barrier=strict_barrier,
        watchdog=watchdog)
    in_specs = [_stream(p_pes), _stream(p_pes)]
    operands = [inj_s.astype(jnp.int32)[:, None],
                dst_s.astype(jnp.int32)[:, None]]
    if faults is not None:
        n_faults = int(faults[0].shape[0])
        in_specs.append(_stream(n_faults))
        operands.append(fault_u[:, None])
    in_specs += [
        _const((lp1, p_pes)),                  # route
        _const((1, lp1)), _const((1, lp1)),    # kind, prio
        _const((1, lp1)), _const((1, lp1)),    # cap, phys
        _const((1, lp1)),                      # is_sink
        _const((1, p_pes)),                    # pe_src_link
        _const((1, lp1)),                      # inj_pe
        _const(tuple(geom.cand.shape)),
        _const(tuple(geom.intab.shape)),
    ]
    operands += [geom.route, _row(geom.kind), _row(geom.prio),
                 _row(geom.cap), _row(geom.phys),
                 _row(geom.is_sink.astype(jnp.int32)),
                 _row(geom.pe_src_link), _row(geom.inj_pe),
                 geom.cand, geom.intab]
    if faults is not None:
        in_specs += [_const((1, n_faults))] * 3
        operands += [_row(f) for f in faults]
    out_specs = [_block((1, lp1)), _block((1, N_SCALARS)),
                 _block((N_KIND_ROWS, 8))]
    out_shape = [jax.ShapeDtypeStruct((1, lp1), jnp.int32),
                 jax.ShapeDtypeStruct((1, N_SCALARS), jnp.int32),
                 jax.ShapeDtypeStruct((N_KIND_ROWS, 8), jnp.int32)]
    scratch_shapes = [
        pltpu.VMEM((lp1, depth), jnp.int32),      # packed queue words
        pltpu.VMEM((1, lp1), jnp.int32),          # queue lengths
        pltpu.VMEM((1, lp1), jnp.int32),          # aging counters
        pltpu.VMEM((1, N_SCALARS), jnp.int32),    # scalar metrics
        pltpu.VMEM((N_KIND_ROWS, 8), jnp.int32),  # per-kind metrics
    ]
    if trace is not None:
        ph_dst, ph_flits, ph_total = trace
        n_phases = ph_dst.shape[0]
        in_specs += [_const((n_phases, p_pes)), _const((n_phases, p_pes)),
                     _const((1, n_phases))]
        operands += [ph_dst, ph_flits, _row(ph_total)]
        out_specs.append(_block((1, n_phases)))
        out_shape.append(jax.ShapeDtypeStruct((1, n_phases), jnp.int32))
        scratch_shapes += [
            pltpu.VMEM((1, 1), jnp.int32),          # phase cursor
            pltpu.VMEM((1, p_pes), jnp.int32),      # per-PE flits sent
            pltpu.VMEM((1, 1), jnp.int32),          # retired-flit credit
            pltpu.VMEM((1, n_phases), jnp.int32),   # completion cycles
            pltpu.VMEM((1, 1), jnp.int32),          # stall-watchdog counter
        ]
    out = pl.pallas_call(
        kernel,
        grid=(cycles,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        scratch_shapes=scratch_shapes,
        interpret=interpret,
    )(*operands)
    return (out[0][0], out[1][0], out[2]) + tuple(o[0] for o in out[3:])
