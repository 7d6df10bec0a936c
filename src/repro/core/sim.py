"""Vectorized cycle-level NoC simulator (pure JAX, ``lax.scan`` over cycles).

Model (see DESIGN.md §4): every buffered channel is a directed link with a
small FIFO queue (depth 2 = the paper's two VCs per input port; the PE
inject buffer is deeper, Fig. 4's Buf-3).  Each cycle:

1. every queue head looks up its next link in the static route table
   (XY-DoR + shortest-ring-direction, precomputed by ``core.topology``);
2. contenders for the same output link arbitrate: static priority
   (in-ring > router > PE-inject, §4.2) with a rotating round-robin
   tiebreak and anti-starvation aging (the paper's weighted round-robin);
3. winners move one hop if the target queue has space (store-and-forward
   with back-pressure, the req/ack protocol of §4.3); moves into EJECT
   sinks are deliveries;
4. traffic generators inject new single-flit packets Bernoulli(Ir) per PE
   (§7.2), with optional ringlet/block locality (§3's operating regime).

Hot-path layout (DESIGN.md §4/§11): the per-cycle update is scatter-free.
Arbitration and enqueue both run over *static fan-in candidate tables*
(every queue can only receive traffic from the queues entering its source
node, a property of the topology, not of the current route table), so the
whole step is gathers, compares, row-reductions and masked writes — no
``segment_max``/scatter ops, which dominate CPU wall-clock.  The
arbitration fixpoint is a single early-exiting ``lax.while_loop`` with a
residue check instead of two fixed 12-iteration scans.  All per-point
parameters (injection rate, locality, seed, destination map) are *traced*,
so one XLA compilation covers a whole sweep grid; ``core.sweep`` vmaps the
same step over batches of points.

The step *math* lives in ``kernels.noc_step.cycle_step`` and runs behind
``SimConfig(backend=...)``: ``"xla"`` scans it with ``lax.scan`` (the
bit-exact correctness oracle), ``"pallas"`` runs the whole cycle loop as
one fused Pallas kernel that keeps queue state, candidate scores and the
metric accumulators in VMEM scratch across cycles and fixpoint passes
(interpret mode off-TPU).  Both backends share every accumulator as an
int32, so they are bit-identical — asserted by tests/test_noc_kernel.py.

Accumulators are integers (latency is in whole cycles), so batched and
single-point executions produce bit-identical metrics regardless of XLA
reduction order; ``lat_sum``'s int32 envelope (cycles x total buffer
capacity < 2^31 — every in-flight flit accrues one latency cycle per
cycle) is asserted at trace time.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import packet as pk
from repro.core import topology as topo_mod
from repro.core import traffic
from repro.faults.spec import FaultSpec
from repro.kernels import noc_step

BACKENDS = ("xla", "pallas")

# Legacy string patterns — deprecation shims over the ``core.traffic``
# registry (new code passes TrafficSpec instances; these strings resolve
# to the default-constructed spec of the same kind, bit-identically).
UNIFORM = "uniform"
BIT_REVERSAL = "bit_reversal"
TRANSPOSE = "transpose"
SHUFFLE = "shuffle"
TORNADO = "tornado"
HOTSPOT = "hotspot"
PATTERNS = (UNIFORM, BIT_REVERSAL, TRANSPOSE, SHUFFLE, TORNADO, HOTSPOT)

# Arbitration fixpoint iteration cap.  The grant/prune cascade peels at
# most one queue per iteration along a blocked chain, so the cap bounds the
# chain depth handled exactly; beyond it the residue counter (`lost`)
# flags the approximation.  24 matches the seed's 12 re-arb + 12 prune
# passes; the while_loop exits as soon as the winner set is feasible, which
# under normal load happens in 1-3 iterations.
ARB_ITERS = 24


@dataclasses.dataclass(frozen=True)
class SimConfig:
    cycles: int = 2000
    warmup: int = 500
    inj_rate: float = 0.25
    pattern: Union[str, traffic.TrafficSpec] = UNIFORM
    locality_ringlet: float = 0.0
    locality_block: float = 0.0
    seed: int = 0
    starvation_limit: int = 8
    backend: str = "xla"  # "xla" (lax.scan oracle) | "pallas" (fused kernel)
    # Fault injection (repro.faults): faults are lowered to a per-link
    # drop mask inside the shared cycle step — routing is untouched, so
    # whole resilience grids vmap on the healthy geometry.
    faults: Optional[FaultSpec] = None
    # Trace replay semantics under faults: with strict_barrier a phase
    # barrier retires *delivered* flits only (dropped flits leave the
    # barrier waiting forever on a dead link); the watchdog then detects
    # a phase making no progress for `watchdog` consecutive cycles and
    # terminates with a per-phase diagnostic instead of spinning to
    # budget exhaustion.  0 disables the watchdog (compiled away).
    strict_barrier: bool = False
    watchdog: int = 0

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(
                f"backend must be one of {BACKENDS}, got {self.backend!r}")
        if not 0.0 <= self.inj_rate <= 1.0:
            raise ValueError(
                f"inj_rate must be in [0, 1], got {self.inj_rate}")
        if self.cycles <= 0:
            raise ValueError(f"cycles must be > 0, got {self.cycles}")
        if not 0 <= self.warmup < self.cycles:
            raise ValueError(
                f"warmup must satisfy 0 <= warmup < cycles, got "
                f"warmup={self.warmup} cycles={self.cycles}")
        spec = traffic.resolve(self.pattern)  # raises on unknown patterns
        if spec.is_trace and self.warmup != 0:
            raise ValueError(
                "trace replay needs warmup=0: per-phase completion cycles "
                "count from cycle 0 and every injected flit is workload")
        if self.faults is not None and not isinstance(self.faults,
                                                      FaultSpec):
            raise TypeError(
                f"faults must be a repro.faults.FaultSpec, got "
                f"{type(self.faults).__name__}")
        if self.watchdog < 0:
            raise ValueError(
                f"watchdog must be >= 0 cycles, got {self.watchdog}")
        if (self.strict_barrier or self.watchdog) and not spec.is_trace:
            raise ValueError(
                "strict_barrier/watchdog are trace-replay semantics "
                "(phase barriers); statistical traffic has no barrier "
                "to watch")
        if not 0 <= self.locality_ringlet + self.locality_block <= 1:
            raise ValueError("locality fractions must sum to <= 1")
        if isinstance(self.pattern, traffic.TrafficSpec) and (
                self.locality_ringlet or self.locality_block):
            raise ValueError(
                "locality is declared on the TrafficSpec when one is "
                "passed as `pattern`; leave SimConfig's locality at 0")

    def effective_locality(self) -> tuple[float, float]:
        """(ringlet, block) fractions that drive traffic generation: the
        spec's when ``pattern`` is a TrafficSpec, else this config's."""
        if isinstance(self.pattern, traffic.TrafficSpec):
            return (self.pattern.locality_ringlet,
                    self.pattern.locality_block)
        return self.locality_ringlet, self.locality_block


@dataclasses.dataclass(frozen=True)
class SimResult:
    topology: str
    n_pes: int
    cfg: SimConfig
    delivered: int
    offered: int
    accepted: int
    dropped: int
    lost: int        # exactness-guard counter; 0 in all validated runs
    in_flight: int   # flits still queued at the end (conservation checks)
    measured_cycles: int
    avg_latency: float          # generation -> ejection, cycles
    throughput: float           # delivered packets / cycle
    flit_hops_per_cycle: float  # link traversals / cycle (activity factor)
    per_pe_throughput: float
    # Trace replay only (DESIGN.md §12): the cycle each phase's last flit
    # retired, -1 for phases the cycle budget did not complete, and
    # ``-2 - cycle`` for a phase the stall watchdog terminated at
    # ``cycle`` (DESIGN.md §13).  Empty for statistical traffic.
    phase_done: tuple = ()
    # Graceful degradation (repro.faults): fraction of (src, dst) pairs
    # with a live route (1.0 for healthy fabrics), and — when the stall
    # watchdog fired — the credits the stalled phase never retired.
    reachability: float = 1.0
    stall_unretired: int = 0
    # Arbitration passes this point needed, summed over every cycle,
    # warmup included: 1 to ARB_ITERS a cycle.  The same under simulate,
    # sweep and both backends (a batch's passes run are the sweep's
    # counter, DESIGN.md §15).
    arb_passes: int = 0

    @property
    def n_phases(self) -> int:
        return len(self.phase_done)

    @property
    def trace_completed(self) -> bool:
        """True when every phase of a trace replay finished in budget."""
        return bool(self.phase_done) and self.phase_done[-1] >= 0

    @property
    def delivered_fraction(self) -> float:
        """Delivered / offered — the resilience headline (1.0 healthy)."""
        return self.delivered / max(self.offered, 1)

    @property
    def stalled_phase(self) -> int:
        """Index of the trace phase the stall watchdog terminated, or -1
        (phases encode the stall as ``phase_done = -2 - cycle``)."""
        for i, d in enumerate(self.phase_done):
            if d <= -2:
                return i
        return -1

    @property
    def stall_cycle(self) -> int:
        """Cycle at which the watchdog fired, or -1 if it never did."""
        i = self.stalled_phase
        return -2 - self.phase_done[i] if i >= 0 else -1

    @property
    def completion_cycles(self) -> int:
        """Cycles to drain the whole trace (last phase's completion cycle
        + 1, since cycles are 0-based); -1 if the budget ran out."""
        if not self.trace_completed:
            return -1
        return self.phase_done[-1] + 1

    def phase_latencies(self) -> tuple[int, ...]:
        """Per-phase cycle cost: completion-cycle deltas between
        consecutive phase barriers (phase 0 counts from cycle 0).
        Incomplete phases report -1."""
        out, prev = [], -1
        for d in self.phase_done:
            out.append(d - prev if d >= 0 else -1)
            prev = d
        return tuple(out)

    def row(self) -> dict:
        r = {
            "topology": self.topology, "n_pes": self.n_pes,
            "pattern": traffic.name_of(self.cfg.pattern),
            "inj_rate": self.cfg.inj_rate,
            "avg_latency": round(self.avg_latency, 2),
            "throughput": round(self.throughput, 3),
            "per_pe_throughput": round(self.per_pe_throughput, 4),
            "flit_hops_per_cycle": round(self.flit_hops_per_cycle, 3),
            "delivered": self.delivered, "offered": self.offered,
            "dropped": self.dropped, "lost": self.lost,
            "in_flight": self.in_flight,
            "arb_passes": self.arb_passes,
        }
        if self.phase_done:
            r["n_phases"] = self.n_phases
            r["completion_cycles"] = self.completion_cycles
            r["phase_latencies"] = list(self.phase_latencies())
            if self.stalled_phase >= 0:
                r["stalled_phase"] = self.stalled_phase
                r["stall_cycle"] = self.stall_cycle
                r["stall_unretired"] = self.stall_unretired
        if self.reachability != 1.0 or (self.cfg is not None
                                        and self.cfg.faults):
            r["reachability"] = round(self.reachability, 4)
            r["delivered_fraction"] = round(self.delivered_fraction, 4)
        return r


def pattern_destinations(pattern: Union[str, traffic.TrafficSpec],
                         n_pes: int) -> Optional[np.ndarray]:
    """Deprecation shim: fixed destination map (None = uniform-random).
    Destination-map generation lives in the ``core.traffic`` registry."""
    return traffic.resolve(pattern).destinations(n_pes)


# ---------------------------------------------------------------------------
# Per-point traced parameters and metric accumulators (both are pytrees so
# `core.sweep` can vmap whole grids of them through one compilation).
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SweepPoint:
    """One sweep-grid coordinate.  Every field is traced (never a compile
    key): rates/localities are f32 scalars, the destination map is always
    passed (``use_perm`` selects it against uniform-random draws)."""
    inj_rate: jax.Array
    loc_ring: jax.Array
    loc_block: jax.Array
    seed: jax.Array
    use_perm: jax.Array
    perm_dst: jax.Array  # [n_pes] int32
    # Trace replay tables (DESIGN.md §12): [n_phases, n_pes] int32 per-phase
    # destination map and flit counts.  Statistical points carry the empty
    # [0, n_pes] shape, which is static, so trace-ness (and the phase
    # count) is part of the compile key while the tables stay traced data —
    # grids of different traces on one topology share one executable
    # (``core.sweep`` pads shorter tables with zero-flit phases).
    ph_dst: jax.Array
    ph_flits: jax.Array
    # Fault injection (repro.faults): lowered per-queue drop-mask entries
    # (queue id, drop probability, onset cycle).  Healthy points carry the
    # empty [0] shape; faulted points are padded to a small static bucket,
    # so the fault *shape* joins the compile key while fault identity
    # (which links, what rates, what seeds) stays traced data — whole
    # resilience grids vmap through one executable (``core.sweep`` pads
    # every point of a batch to its largest bucket).
    fault_links: jax.Array   # [F] int32 queue ids (pad -> n_links)
    fault_drop_p: jax.Array  # [F] f32 (pad -> 0.0)
    fault_onset: jax.Array   # [F] int32


jax.tree_util.register_dataclass(
    SweepPoint,
    data_fields=["inj_rate", "loc_ring", "loc_block", "seed", "use_perm",
                 "perm_dst", "ph_dst", "ph_flits", "fault_links",
                 "fault_drop_p", "fault_onset"],
    meta_fields=[])


@dataclasses.dataclass(frozen=True)
class Metrics:
    """Integer metric accumulators carried through the cycle scan."""
    delivered: jax.Array
    offered: jax.Array
    accepted: jax.Array
    dropped: jax.Array
    lost: jax.Array
    lat_sum: jax.Array   # int32: whole-cycle latencies, order-independent
    moved: jax.Array
    in_flight: jax.Array
    wins_by_kind: jax.Array       # [8]
    stall_next_kind: jax.Array    # [8]
    q_len_by_kind: jax.Array      # [8]
    phase_done: jax.Array         # [n_phases] int32 ([0] when statistical)
    stall_unretired: jax.Array    # credits unretired at watchdog fire
    arb_passes: jax.Array         # arbitration passes the point needed
    arb_passes_run: jax.Array     # passes its program ran (batch max)


jax.tree_util.register_dataclass(
    Metrics,
    data_fields=["delivered", "offered", "accepted", "dropped", "lost",
                 "lat_sum", "moved", "in_flight", "wins_by_kind",
                 "stall_next_kind", "q_len_by_kind", "phase_done",
                 "stall_unretired", "arb_passes", "arb_passes_run"],
    meta_fields=[])


def make_point(cfg: SimConfig, n_pes: int,
               topo: Optional[topo_mod.Topology] = None) -> SweepPoint:
    """Host-side SweepPoint for one SimConfig (pattern strings and
    TrafficSpec instances both resolve through the traffic registry).
    ``topo`` is required only when ``cfg.faults`` is set — fault ids
    lower to queue-level drop entries against the concrete topology."""
    spec = traffic.resolve(cfg.pattern)
    perm = spec.destinations(n_pes)
    use_perm = perm is not None
    if perm is None:
        perm = np.zeros((n_pes,), np.int32)
    else:
        perm = np.asarray(perm)
        if (perm.shape != (n_pes,)
                or not np.issubdtype(perm.dtype, np.integer)
                or perm.min() < 0 or perm.max() >= n_pes):
            raise ValueError(
                f"traffic spec {traffic.name_of(spec)!r} produced an invalid "
                f"destination map for {n_pes} PEs "
                f"(shape {perm.shape}, dtype {perm.dtype}); expected int "
                f"[{n_pes}] with entries in [0, {n_pes})")
        perm = perm.astype(np.int32)
    loc_ring, loc_block = cfg.effective_locality()
    if spec.is_trace:
        ph_dst, ph_flits = spec.trace_arrays(n_pes)
        ph_dst = np.asarray(ph_dst, np.int32)
        ph_flits = np.asarray(ph_flits, np.int32)
    else:
        ph_dst = np.zeros((0, n_pes), np.int32)
        ph_flits = np.zeros((0, n_pes), np.int32)
    if cfg.faults:
        if topo is None:
            raise ValueError(
                "SimConfig.faults lowers against the concrete topology; "
                "call make_point(cfg, n_pes, topo)")
        cfg.faults.validate_against(topo)
        f_links, f_drop_p, f_onset = cfg.faults.lower(topo)
    else:
        f_links = np.zeros((0,), np.int32)
        f_drop_p = np.zeros((0,), np.float32)
        f_onset = np.zeros((0,), np.int32)
    return SweepPoint(
        inj_rate=np.float32(cfg.inj_rate),
        loc_ring=np.float32(loc_ring),
        loc_block=np.float32(loc_block),
        seed=np.int32(cfg.seed),
        use_perm=np.bool_(use_perm),
        perm_dst=np.asarray(perm, np.int32),
        ph_dst=ph_dst,
        ph_flits=ph_flits,
        fault_links=f_links,
        fault_drop_p=f_drop_p,
        fault_onset=f_onset,
    )


# ---------------------------------------------------------------------------
# Geometry: topology arrays preprocessed for the scatter-free step.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Geometry:
    """Device-ready topology view.  Shapes (not values) are the compile
    key: one XLA program serves every sweep point on this geometry.

    ``cand``/``intab`` are *structural* fan-in tables: queue q can only
    ever receive a flit from a queue whose destination node is q's source
    node (routes are node-local, an invariant morphing preserves), so they
    are supersets of any route table's live edges and stay valid across
    morphs.  Runtime masks (`nxt == target`) select the live subset.

    ``outtab`` is the structural fan-out table, the same invariant seen
    from the sender: q's next queue always leaves q's destination node, so
    row q lists every queue leaving ``dst[q]`` (dead queues included: a
    route may still name them).  ``outphys``/``outcap`` are ``phys`` and
    ``cap`` read through it.  A batched step looks up "the value at q's
    target" as a static-index gather over these rows plus a one-hot
    reduction, instead of a gather whose index differs per point
    (``kernels.noc_step.cycle_step``, ``arb_fanout``).
    """
    route: jax.Array      # [L+1, P] int16 (refreshed per call: morph-aware)
    kind: jax.Array       # [L+1] int32
    prio: jax.Array       # [L+1] int32
    cap: jax.Array        # [L+1] int32
    phys: jax.Array       # [L+1] int32 (dummy row -> n_phys)
    is_sink: jax.Array    # [L+1] bool
    pe_src_link: jax.Array  # [P] int32
    inj_pe: jax.Array     # [L+1] int32: PE injecting into this row, or -1
    cand: jax.Array       # [n_phys+1, Fc] int32 queue ids (pad = L)
    intab: jax.Array      # [L+1, Fi] int32 queue ids (pad = L)
    outtab: jax.Array     # [L+1, Fo] int32 queue ids (pad = L)
    outphys: jax.Array    # [L+1, Fo] int32: phys[outtab]
    outcap: jax.Array     # [L+1, Fo] int32: cap[outtab]
    n_links: int
    n_phys: int
    n_pes: int
    depth: int
    cap_total: int        # sum of finite queue capacities (lat_sum bound)


jax.tree_util.register_dataclass(
    Geometry,
    data_fields=["route", "kind", "prio", "cap", "phys", "is_sink",
                 "pe_src_link", "inj_pe", "cand", "intab", "outtab",
                 "outphys", "outcap"],
    meta_fields=["n_links", "n_phys", "n_pes", "depth", "cap_total"])


def _structural_cache(topo: topo_mod.Topology) -> dict:
    """Route-independent device arrays, cached on the topology object."""
    cache = topo.__dict__.get("_sim_geometry_cache")
    if cache is not None:
        return cache
    L, P = topo.n_links, topo.n_pes
    assert L + 1 < (1 << 15), "int16 queue ids require < 32767 links"
    src = topo.link_src_node
    dst = topo.link_dst_node
    # Structural invariant behind the fan-in tables: every route hop is
    # node-local (next queue leaves the current queue's destination node).
    nxt = topo.route_table
    live = nxt >= 0
    src_of_nxt = src[np.clip(nxt, 0, L - 1)]
    assert np.all(src_of_nxt[live] == np.broadcast_to(dst[:, None],
                                                      nxt.shape)[live]), \
        "route table contains a non-node-local hop"

    n_nodes = int(max(src.max(), dst.max())) + 1
    dead = (topo.dead_queues if topo.dead_queues is not None
            else np.zeros(L, bool))
    buckets: list[list[int]] = [[] for _ in range(n_nodes)]
    for q in range(L):
        # Dead queues (faulted fabrics) leave the candidate tables: they
        # can never hold a flit, so they must never win arbitration.
        if dst[q] >= 0 and not dead[q]:
            buckets[dst[q]].append(q)
    fi = max((len(b) for b in buckets), default=1) or 1

    intab = np.full((L + 1, fi), L, np.int32)
    for q in range(L):
        if src[q] >= 0:
            b = buckets[src[q]]
            intab[q, :len(b)] = b
    cand = np.full((topo.n_phys + 1, fi), L, np.int32)
    phys = topo.link_phys
    for q in range(L):
        if src[q] >= 0:
            b = buckets[src[q]]
            cand[phys[q], :len(b)] = b

    # Fan-out table: the queues leaving q's destination node.  Unlike the
    # candidate tables it keeps dead queues, since a route may name one.
    leaving: list[list[int]] = [[] for _ in range(n_nodes)]
    for q in range(L):
        if src[q] >= 0:
            leaving[src[q]].append(q)
    fo = max((len(b) for b in leaving), default=1) or 1
    outtab = np.full((L + 1, fo), L, np.int32)
    for q in range(L):
        if dst[q] >= 0:
            b = leaving[dst[q]]
            outtab[q, :len(b)] = b

    inj_pe = np.full(L + 1, -1, np.int32)
    inj_pe[topo.pe_src_link] = np.arange(P, dtype=np.int32)

    phys_d = np.concatenate([phys.astype(np.int32), [topo.n_phys]])
    cap_d = np.concatenate([topo.link_cap.astype(np.int32), [1 << 30]])
    finite = topo.link_cap < (1 << 29)
    cache = dict(
        kind=jnp.asarray(np.concatenate([topo.link_kind.astype(np.int32),
                                         [0]])),
        prio=jnp.asarray(np.concatenate([topo.link_prio.astype(np.int32),
                                         [0]])),
        cap=jnp.asarray(cap_d),
        phys=jnp.asarray(phys_d),
        is_sink=jnp.asarray(np.concatenate([topo.is_sink,
                                            [False]])),
        pe_src_link=jnp.asarray(topo.pe_src_link.astype(np.int32)),
        inj_pe=jnp.asarray(inj_pe),
        cand=jnp.asarray(cand),
        intab=jnp.asarray(intab),
        outtab=jnp.asarray(outtab),
        outphys=jnp.asarray(phys_d[outtab]),
        outcap=jnp.asarray(cap_d[outtab]),
        depth=int(topo.link_cap[finite].max()),
        cap_total=int(topo.link_cap[finite].sum()),
    )
    topo.__dict__["_sim_geometry_cache"] = cache
    return cache


def build_geometry(topo: topo_mod.Topology) -> Geometry:
    """Device-ready geometry; the route table is re-read every call so
    in-place morphs (``core.morph``) take effect immediately."""
    with obs.span("repro.sim.geometry"):
        c = _structural_cache(topo)
        route = jnp.asarray(np.concatenate(
            [topo.route_table.astype(np.int16),
             np.full((1, topo.n_pes), -1, np.int16)], axis=0))
    return Geometry(
        route=route,
        kind=c["kind"], prio=c["prio"], cap=c["cap"], phys=c["phys"],
        is_sink=c["is_sink"], pe_src_link=c["pe_src_link"],
        inj_pe=c["inj_pe"], cand=c["cand"], intab=c["intab"],
        outtab=c["outtab"], outphys=c["outphys"], outcap=c["outcap"],
        n_links=topo.n_links, n_phys=topo.n_phys, n_pes=topo.n_pes,
        depth=c["depth"], cap_total=c["cap_total"])


# ---------------------------------------------------------------------------
# The hot path.
# ---------------------------------------------------------------------------
def _run_core(geom: Geometry, point: SweepPoint, *, cycles: int, warmup: int,
              starvation_limit: int, arb_iters: int = ARB_ITERS,
              diagnostics: bool = False, backend: str = "xla",
              strict_barrier: bool = False, watchdog: int = 0,
              batch_axis: str | None = None,
              arb_fanout: bool = False) -> Metrics:
    """The whole run of one point: traffic drawn under the named scope
    ``point.traffic``, then ``cycles`` steps of ``noc_step.cycle_step``.
    ``batch_axis`` names the vmap axis the run is batched under (XLA
    backend; ``cycle_step`` counts the passes the batch ran);
    ``arb_fanout`` picks the step's fan-out lookups (XLA backend, chosen
    by ``core.sweep`` from the batch size; the results are identical)."""
    L, P = geom.n_links, geom.n_pes
    kinds8 = jnp.arange(8, dtype=jnp.int32)[:, None]  # [8, 1]
    kind_oh = geom.kind[None, :] == kinds8           # [8, L+1] static mask

    # --- traffic pregeneration (cycle-invariant work hoisted out of the
    # scan: peer indices are static, all randomness is drawn in five large
    # vectorized calls instead of per-cycle splits) ----------------------
    # Fault entries ride the point as traced data; their [F] shape is the
    # static "fault shape".  A point with fault entries splits its key 6
    # ways and a healthy one 5 ways; the first five keys of a 6-way split
    # are the 5-way split's (threefry, partitionable), which is what lets
    # healthy and faulted points share a dispatch: a healthy point padded
    # with entries that never fire draws its own streams.  Entry j's drop
    # chances are row j of an [F, cycles] draw, the same for every F, so a
    # point padded to a larger fault bucket keeps them too.
    n_faults = int(point.fault_links.shape[0])
    with jax.named_scope("point.traffic"):
        pes = jnp.arange(P, dtype=jnp.int32)
        ring_base = pes - pes % pk.PES_PER_RINGLET
        pos_ring = pes % pk.PES_PER_RINGLET
        blk_base = pes - pes % pk.PES_PER_BLOCK
        pos_blk = pes % pk.PES_PER_BLOCK
        key = jax.random.PRNGKey(point.seed)
        if n_faults:
            k_inj, k_dst, k_loc, k_ring, k_blk, k_flt = jax.random.split(
                key, 6)
            fu_s = jax.random.uniform(k_flt, (n_faults, cycles)).T
            faults = (point.fault_links, point.fault_drop_p,
                      point.fault_onset)
        else:
            k_inj, k_dst, k_loc, k_ring, k_blk = jax.random.split(key, 5)
            fu_s, faults = None, None
        inj_s = jax.random.bernoulli(k_inj, point.inj_rate, (cycles, P))
        off_s = jax.random.randint(k_dst, (cycles, P), 1, P,
                                   dtype=jnp.int32)
        u_s = jax.random.uniform(k_loc, (cycles, P))
        ring_s = jax.random.randint(k_ring, (cycles, P), 1,
                                    pk.PES_PER_RINGLET, dtype=jnp.int32)
        blk_s = jax.random.randint(k_blk, (cycles, P), 1, pk.PES_PER_BLOCK,
                                   dtype=jnp.int32)
        base_s = (pes[None, :] + off_s) % P  # uniform over everyone else
        base_s = jnp.where(point.use_perm,
                           jnp.broadcast_to(point.perm_dst, (cycles, P)),
                           base_s)
        ring_peer = (ring_base
                     + (pos_ring[None, :] + ring_s) % pk.PES_PER_RINGLET)
        blk_peer = blk_base + (pos_blk[None, :] + blk_s) % pk.PES_PER_BLOCK
        dst_s = jnp.where(
            u_s < point.loc_ring, ring_peer,
            jnp.where(u_s < point.loc_ring + point.loc_block, blk_peer,
                      base_s)).astype(jnp.int16)

    # Queue payload: one packed int32 word per slot, ``born << 11 | dst+1``
    # (n_pes <= 1024 so dst+1 < 2048; empty slot = 0 -> dst -1).  One array
    # instead of separate dst/born halves the queue shift/write traffic,
    # and a whole flit moves as a single gathered word.
    assert cycles < (1 << 20), "packed born field supports < 2^20 cycles"
    # lat_sum <= cycles * (flits simultaneously in flight) <= cycles *
    # total finite buffer capacity: every in-flight flit accrues one cycle
    # of eventual latency per cycle.  Enforce the int32 envelope exactly.
    assert cycles * geom.cap_total < (1 << 31), \
        "int32 lat_sum could overflow for this (cycles, topology) budget"

    # Trace replay (DESIGN.md §12): the phase tables ride the point as
    # traced data, but their [n_phases, P] *shape* is static, so this
    # branch specializes the executable without adding a dynamic check.
    n_phases = int(point.ph_dst.shape[0])
    trace = None
    if n_phases:
        trace = (point.ph_dst, point.ph_flits,
                 jnp.sum(point.ph_flits, axis=1, dtype=jnp.int32))

    # The step math is shared with the fused kernel (kernels.noc_step):
    # "xla" scans it (the bit-exact oracle), "pallas" runs the whole loop
    # as one kernel with the carry in VMEM scratch.
    if backend == "pallas":
        out = noc_step.run_fused(
            geom, inj_s, dst_s, cycles=cycles, warmup=warmup,
            starvation_limit=starvation_limit, arb_iters=arb_iters,
            trace=trace, faults=faults, fault_u=fu_s,
            strict_barrier=strict_barrier, watchdog=watchdog,
            diagnostics=diagnostics)
        ql, m_scal, m_kind = out[:3]
        ph_done = out[3] if n_phases else jnp.zeros((0,), jnp.int32)
    elif backend == "xla":
        def step(carry, xs):
            cycle, inj, dst = xs[:3]
            fu = xs[3] if n_faults else None
            return noc_step.cycle_step(
                geom, carry, cycle, inj, dst, fault_u=fu, warmup=warmup,
                starvation_limit=starvation_limit, arb_iters=arb_iters,
                trace=trace, faults=faults, strict_barrier=strict_barrier,
                watchdog=watchdog, diagnostics=diagnostics,
                batch_axis=batch_axis, arb_fanout=arb_fanout), None

        carry0 = noc_step.initial_state(L, geom.depth, n_pes=P,
                                        n_phases=n_phases)
        xs = (jnp.arange(cycles, dtype=jnp.int32), inj_s, dst_s)
        if n_faults:
            xs = xs + (fu_s,)
        final, _ = jax.lax.scan(step, carry0, xs)
        ql, m_scal, m_kind = final[1], final[3], final[4]
        ph_done = final[8] if n_phases else jnp.zeros((0,), jnp.int32)
    else:  # pragma: no cover - SimConfig validates before tracing
        raise ValueError(f"unknown simulator backend {backend!r}")

    return Metrics(
        delivered=m_scal[noc_step.DELIVERED],
        offered=m_scal[noc_step.OFFERED],
        accepted=m_scal[noc_step.ACCEPTED],
        dropped=m_scal[noc_step.DROPPED],
        lost=m_scal[noc_step.LOST],
        lat_sum=m_scal[noc_step.LAT_SUM],
        moved=m_scal[noc_step.MOVED],
        in_flight=jnp.sum(ql),
        wins_by_kind=m_kind[noc_step.KIND_WINS],
        stall_next_kind=m_kind[noc_step.KIND_STALLS],
        q_len_by_kind=jnp.sum(jnp.where(kind_oh, ql[None, :], 0), axis=1,
                              dtype=jnp.int32),
        phase_done=ph_done,
        stall_unretired=m_scal[noc_step.STALL_CREDIT],
        arb_passes=m_scal[noc_step.ARB_PASSES],
        arb_passes_run=m_scal[noc_step.ARB_PASSES_RUN])


_run_single = jax.jit(
    _run_core,
    static_argnames=("cycles", "warmup", "starvation_limit", "arb_iters",
                     "diagnostics", "backend", "strict_barrier",
                     "watchdog"))


def compile_cache_size() -> int:
    """Number of compiled single-point executables held by ``simulate``.
    Public counterpart of the private jit internals, used by
    ``sweep.compile_stats()`` and by tests asserting compile reuse."""
    return int(_run_single._cache_size())


def clear_compile_cache() -> None:
    """Drop the compiled single-point executables (tests use this to reset
    compile counters between cases; the next ``simulate`` recompiles)."""
    _run_single.clear_cache()


# Host-side reachability cache: FaultSpec is frozen/hashable and the
# route walk is pure, so one walk serves every point sharing (topology,
# fault set) in a sweep grid.
_REACH_CACHE: dict = {}


def _fault_reachability(topo: topo_mod.Topology,
                        faults: Optional[FaultSpec]) -> float:
    if not faults:
        return topo.reachable_frac  # 1.0 healthy; baked value if repaired
    key = (id(topo), topo.name, faults)
    hit = _REACH_CACHE.get(key)
    if hit is None:
        with obs.span("repro.sim.reachability"):
            dead = faults.dead_queue_mask(topo)
            hit = (topo.reachable_frac if not dead.any()
                   else topo_mod.reachable_fraction(topo, dead))
        if len(_REACH_CACHE) > 512:
            _REACH_CACHE.clear()
        _REACH_CACHE[key] = hit
    return hit


def _to_result(topo: topo_mod.Topology, cfg: SimConfig,
               m: Metrics) -> SimResult:
    """Shared host-side conversion (identical for single and batched runs,
    which keeps the sweep/simulate equivalence exact)."""
    mc = cfg.cycles - cfg.warmup
    delivered = int(m.delivered)
    return SimResult(
        topology=topo.name, n_pes=topo.n_pes, cfg=cfg,
        delivered=delivered,
        offered=int(m.offered),
        accepted=int(m.accepted),
        dropped=int(m.dropped),
        lost=int(m.lost),
        in_flight=int(m.in_flight),
        measured_cycles=mc,
        avg_latency=int(m.lat_sum) / max(delivered, 1),
        throughput=delivered / mc,
        flit_hops_per_cycle=int(m.moved) / mc,
        per_pe_throughput=delivered / mc / topo.n_pes,
        # A sweep pads a point's phase table to its group's longest; the
        # padded phases are not the point's.
        phase_done=tuple(int(d) for d in np.asarray(m.phase_done)[
            :traffic.resolve(cfg.pattern).n_trace_phases]),
        reachability=_fault_reachability(topo, cfg.faults),
        stall_unretired=int(m.stall_unretired),
        arb_passes=int(m.arb_passes),
    )


def simulate(topo: topo_mod.Topology, cfg: SimConfig,
             device: Optional[jax.Device] = None) -> SimResult:
    """Run one simulation; returns steady-state metrics.  ``device``
    commits the inputs there, so the run compiles for and executes on that
    device (default: JAX's default device)."""
    geom = build_geometry(topo)
    point = make_point(cfg, topo.n_pes, topo)
    if device is not None:
        geom, point = jax.device_put((geom, point), device)
    metrics = _run_single(geom, point, cycles=cfg.cycles, warmup=cfg.warmup,
                          starvation_limit=cfg.starvation_limit,
                          backend=cfg.backend,
                          strict_barrier=cfg.strict_barrier,
                          watchdog=cfg.watchdog)
    metrics = jax.tree.map(np.asarray, metrics)
    return _to_result(topo, cfg, metrics)


def kind_diagnostics(topo: topo_mod.Topology, cfg: SimConfig) -> dict:
    """Per-queue-kind instrumentation: arbitration wins, stalls-by-blocking
    -kind, and final occupancy.  Compiled separately with
    ``diagnostics=True`` — the benchmark/sweep hot path skips these
    counters entirely."""
    geom = build_geometry(topo)
    point = make_point(cfg, topo.n_pes, topo)
    m = _run_single(geom, point, cycles=cfg.cycles, warmup=cfg.warmup,
                    starvation_limit=cfg.starvation_limit, diagnostics=True,
                    backend=cfg.backend,
                    strict_barrier=cfg.strict_barrier,
                    watchdog=cfg.watchdog)
    names = topo_mod.KIND_NAMES
    return {
        field: {names[k]: int(np.asarray(getattr(m, field))[k])
                for k in names}
        for field in ("wins_by_kind", "stall_next_kind", "q_len_by_kind")
    }


# Paper operating regime (§1/§3): "the majority of the traffic remains
# restricted to the rings". Used by the figure-reproduction benchmarks.
PAPER_LOCALITY = dict(locality_ringlet=0.75, locality_block=0.20)
