"""Fault-injection tests (DESIGN.md §13): spec validation and JSON,
fault-aware routing and reachability, flit conservation under every fault
style on both backends, cross-backend bit-identity on faulted fabrics,
repair morphs, the trace stall watchdog, and batched resilience sweeps."""
import dataclasses

import numpy as np
import pytest

from repro import trace as tr
from repro.core import morph as morph_mod
from repro.core import packet as pk
from repro.core import sim, sweep, topology
from repro.core.experiment import Budget, Experiment, Report
from repro.core.spec import TopologySpec
from repro.faults import (FaultSpec, LinkFault, merge_faults, sample_faults,
                          split_faults, suggest_repair_morph)

_SPEC = TopologySpec("ring_mesh", 16)


def _faults(n_dead=2, n_transient=0, seed=0, **kw):
    return sample_faults(_SPEC.build(), n_dead_links=n_dead,
                         n_transient=n_transient, seed=seed, **kw)


# ---------------------------------------------------------------------------
# Spec: validation + serialization
# ---------------------------------------------------------------------------
def test_fault_spec_json_roundtrip():
    f = FaultSpec(dead_links=(3, 7), dead_routers=(1,),
                  transient=(LinkFault(link=5, drop_p=0.25, onset=100),))
    assert FaultSpec.from_json(f.to_json()) == f
    assert FaultSpec.from_dict(f.to_dict()) == f
    assert bool(f) and not bool(FaultSpec())


def test_fault_spec_rejects_bad_values():
    with pytest.raises(ValueError):
        FaultSpec(dead_links=(-1,))
    with pytest.raises(ValueError):
        FaultSpec(dead_links=(3, 3))
    with pytest.raises(ValueError):
        LinkFault(link=0, drop_p=0.0)
    with pytest.raises(ValueError):
        LinkFault(link=0, drop_p=1.5)
    with pytest.raises(ValueError):
        LinkFault(link=0, onset=-1)


def test_validate_against_names_the_offender():
    topo = _SPEC.build()
    with pytest.raises(ValueError, match="out of range"):
        FaultSpec(dead_links=(10 ** 6,)).validate_against(topo)
    with pytest.raises(ValueError, match="router"):
        FaultSpec(dead_routers=(10 ** 6,)).validate_against(topo)
    # PE inject/eject channels are not fabric faults.
    pe_phys = int(topo.link_phys[topo.link_kind == topology.PE_SRC][0])
    with pytest.raises(ValueError, match="PE"):
        FaultSpec(dead_links=(pe_phys,)).validate_against(topo)


def test_merge_and_split():
    a = FaultSpec(dead_links=(1, 2), transient=(LinkFault(link=9),))
    b = FaultSpec(dead_links=(2, 3), dead_routers=(0,))
    m = merge_faults(a, b)
    assert m.dead_links == (1, 2, 3) and m.dead_routers == (0,)
    dead, trans = split_faults(m)
    assert dead.transient == () and trans.dead_links == ()
    assert merge_faults(None, a) == a and merge_faults(a, None) == a


# ---------------------------------------------------------------------------
# Construction-time validation (Experiment / TopologySpec / Morph)
# ---------------------------------------------------------------------------
def test_experiment_rejects_out_of_range_fault_ids():
    with pytest.raises(ValueError, match="out of range"):
        Experiment(topology=_SPEC, faults=FaultSpec(dead_links=(10 ** 6,)))
    with pytest.raises(ValueError, match="router"):
        Experiment(topology=_SPEC, faults=FaultSpec(dead_routers=(99,)))


def test_topology_spec_rejects_out_of_range_morph_target():
    ls = (pk.LINK_BYPASS,) * 8
    with pytest.raises(ValueError, match="router 99"):
        TopologySpec("ring_mesh", 16,
                     morphs=(dict(hl=1, target=99, link_states=ls),))
    with pytest.raises(ValueError, match="ring switch 16"):
        TopologySpec("ring_mesh", 16,
                     morphs=(dict(hl=0, target=16, link_states=ls),))


def test_morph_controller_rejects_out_of_range_target():
    ctl = morph_mod.MorphController(_SPEC.build_fresh())
    m = pk.MorphPacket(hl=1, ers=0, link_states=(pk.LINK_ACTIVE,) * 8)
    with pytest.raises(ValueError, match="router 99"):
        ctl.apply(m, target=99)


def test_budget_trace_semantics_rejected_for_statistical_traffic():
    with pytest.raises(ValueError, match="trace-replay"):
        Experiment(topology=_SPEC, budget=Budget(watchdog=64))
    with pytest.raises(ValueError, match="trace-replay"):
        Experiment(topology=_SPEC, budget=Budget(strict_barrier=True))


# ---------------------------------------------------------------------------
# Conservation: injected == delivered + dropped + lost + in-flight
# ---------------------------------------------------------------------------
_STYLES = {
    "dead_links": lambda t: sample_faults(t, n_dead_links=3, seed=1),
    "dead_router": lambda t: sample_faults(t, n_dead_routers=1, seed=1),
    "transient": lambda t: sample_faults(t, n_transient=3, drop_p=0.3,
                                         seed=1),
    "onset_mix": lambda t: sample_faults(t, n_dead_links=1, n_transient=2,
                                         drop_p=0.2, onset=150, seed=1),
}


@pytest.mark.parametrize("family", ("ring_mesh", "flat_mesh"))
@pytest.mark.parametrize("style", sorted(_STYLES))
@pytest.mark.parametrize("backend", ("xla", "pallas"))
def test_conservation_under_faults(family, style, backend):
    """Every offered flit must be delivered, dropped, or still queued —
    faults may destroy flits only through the *dropped* counter.  Metrics
    are warmup-gated, so the identity is asserted at warmup=0."""
    spec = TopologySpec(family, 16)
    topo = spec.build()
    cfg = sim.SimConfig(cycles=400, warmup=0, inj_rate=0.3, seed=2,
                        backend=backend, faults=_STYLES[style](topo))
    r = sim.simulate(topo, cfg)
    assert r.lost == 0
    assert r.offered == r.delivered + r.dropped + r.in_flight, r.row()
    assert r.delivered > 0


def test_conservation_on_repaired_fabric():
    spec = dataclasses.replace(_SPEC, faults=_faults(n_dead=3, seed=5))
    topo = spec.build()
    for backend in ("xla", "pallas"):
        r = sim.simulate(topo, sim.SimConfig(cycles=400, warmup=0,
                                             inj_rate=0.3, seed=2,
                                             backend=backend))
        assert r.lost == 0
        assert r.offered == r.delivered + r.dropped + r.in_flight


# ---------------------------------------------------------------------------
# Cross-backend identity on faulted fabrics
# ---------------------------------------------------------------------------
def test_backends_identical_under_runtime_faults():
    topo = _SPEC.build()
    f = sample_faults(topo, n_dead_links=2, n_transient=2, drop_p=0.3,
                      seed=4)
    rows = {}
    for backend in ("xla", "pallas"):
        cfg = sim.SimConfig(cycles=400, warmup=100, inj_rate=0.4, seed=3,
                            backend=backend, faults=f)
        rows[backend] = sim.simulate(topo, cfg).row()
    assert rows["xla"] == rows["pallas"]


def test_backends_identical_on_repaired_fabric():
    spec = dataclasses.replace(_SPEC, faults=_faults(n_dead=3, seed=5))
    topo = spec.build()
    rows = {b: sim.simulate(topo, sim.SimConfig(cycles=400, warmup=100,
                                                inj_rate=0.4, seed=3,
                                                backend=b)).row()
            for b in ("xla", "pallas")}
    assert rows["xla"] == rows["pallas"]


def test_onset_gates_fault_activation():
    """Transient faults with onset beyond the horizon never fire: the
    run is bit-identical to the same fault shape with a different drop
    probability (same RNG stream), and strictly better than onset=0."""
    topo = _SPEC.build()
    from repro.faults import fabric_channels
    chans = fabric_channels(topo)[:3]
    mk = lambda p, onset: FaultSpec(transient=tuple(
        LinkFault(link=int(l), drop_p=p, onset=onset) for l in chans))
    run = lambda f: sim.simulate(topo, sim.SimConfig(
        cycles=300, warmup=0, inj_rate=0.3, seed=1, faults=f))
    late_a, late_b = run(mk(0.5, 10 ** 6)), run(mk(0.9, 10 ** 6))
    assert late_a.row() == late_b.row()
    # Active faults add their drops on top of congestion drops.
    assert run(mk(0.5, 0)).dropped > late_a.dropped


# ---------------------------------------------------------------------------
# Degradation, reachability, repair
# ---------------------------------------------------------------------------
def test_faults_degrade_and_report_reachability():
    topo = _SPEC.build()
    cfg = sim.SimConfig(cycles=500, warmup=0, inj_rate=0.1, seed=2)
    healthy = sim.simulate(topo, cfg)
    faulted = sim.simulate(topo, dataclasses.replace(
        cfg, faults=_faults(n_dead=3, seed=5)))
    assert healthy.reachability == 1.0
    assert faulted.reachability < 1.0
    assert faulted.delivered_fraction < healthy.delivered_fraction
    assert "reachability" in faulted.row()
    assert "reachability" not in healthy.row()


def test_repair_morph_restores_delivery():
    """§5.1: re-morphing around dead links wins delivered fraction back.
    Dead ring links are fully bypassable, so the repaired fabric must
    beat the unrepaired one and restore full reachability."""
    from repro.faults import FABRIC_KINDS  # noqa: F401 (doc import)

    spec = TopologySpec("flat_mesh", 16)
    f = sample_faults(spec.build(), n_dead_links=3, seed=0)
    cfg = sim.SimConfig(cycles=500, warmup=0, inj_rate=0.1, seed=2)
    faulted = sim.simulate(spec.build(), dataclasses.replace(cfg, faults=f))
    repaired_spec = suggest_repair_morph(spec, f)
    repaired = sim.simulate(repaired_spec.build(), cfg)
    assert repaired_spec.build().reachable_frac == 1.0
    assert repaired.delivered_fraction > faulted.delivered_fraction


def test_partitioned_fabric_reports_unreachable_not_hangs():
    """Killing every router (ring_mesh_16 has one block, hence one)
    severs all cross-ringlet routes: the build must classify the severed
    pairs (not loop in the route walk) and a simulation must still
    complete, delivering the ring-local share."""
    spec = dataclasses.replace(_SPEC, faults=FaultSpec(dead_routers=(0,)))
    topo = spec.build()
    # Each PE reaches only the 3 others on its ringlet: 48/240 pairs.
    assert topo.reachable_frac == pytest.approx(48 / 240)
    pairs = topo.unreachable_pairs(limit=8)
    assert len(pairs) == 8 and all(s // 4 != d // 4 for s, d in pairs)
    r = sim.simulate(topo, sim.SimConfig(cycles=300, warmup=0,
                                         inj_rate=0.2, seed=1))
    assert r.delivered > 0
    assert r.offered == r.delivered + r.dropped + r.in_flight
    assert r.reachability == pytest.approx(48 / 240)


# ---------------------------------------------------------------------------
# Trace watchdog
# ---------------------------------------------------------------------------
def _stall_exp(strict, watchdog):
    trace = tr.from_records(16, [[(0, 1, 4)], [(0, 8, 4)]])
    return Experiment(topology=_SPEC, traffic=trace,
                      budget=Budget(cycles=600, warmup=0,
                                    strict_barrier=strict,
                                    watchdog=watchdog),
                      inj_rate=1.0, faults=FaultSpec(dead_routers=(0,)))


def test_watchdog_terminates_severed_trace_with_diagnostic():
    r = _stall_exp(strict=True, watchdog=48).run().sim
    assert not r.trace_completed
    assert r.stalled_phase == 1          # phase 0 (ring-local) completed
    assert r.phase_done[0] > 0
    assert r.stall_cycle > 0
    assert r.stall_unretired == 4        # the 4 flits that can never land
    assert "stalled_phase" in r.row()


def test_lenient_barrier_completes_by_retiring_drops():
    r = _stall_exp(strict=False, watchdog=0).run().sim
    assert r.trace_completed
    assert r.dropped == 4 and r.stalled_phase == -1


def test_watchdog_does_not_fire_on_healthy_trace():
    trace = tr.from_records(16, [[(0, 1, 4)], [(0, 8, 4)]])
    r = Experiment(topology=_SPEC, traffic=trace,
                   budget=Budget(cycles=600, warmup=0, strict_barrier=True,
                                 watchdog=48),
                   inj_rate=1.0).run().sim
    assert r.trace_completed and r.stalled_phase == -1


# ---------------------------------------------------------------------------
# Batched resilience sweeps
# ---------------------------------------------------------------------------
def test_fault_sweep_batches_and_matches_simulate():
    """A fault grid must vmap: the scenarios and the healthy points, padded
    to the one fault bucket, share one executable, and every batched row
    must equal its per-point oracle bit for bit."""
    topo = _SPEC.build()
    sweep.reset_caches()
    cfgs = sweep.grid(inj_rates=(0.2, 0.4), seeds=(0,), cycles=300,
                      warmup=0,
                      faults=(None, _faults(n_dead=2, seed=0),
                              _faults(n_dead=4, seed=1),
                              _faults(n_transient=2, seed=2)))
    rs = sweep.sweep(topo, cfgs)
    assert sweep.compile_stats()["batch_xla_compiles"] == 1
    for cfg, rb in zip(cfgs, rs):
        assert rb == sim.simulate(topo, cfg)


def test_experiment_grid_fault_axis_and_report_roundtrip():
    f = _faults(n_dead=2, seed=0)
    exp = Experiment(topology=_SPEC, budget=Budget(cycles=300, warmup=0),
                     inj_rate=0.2)
    reports = exp.run_grid(faults=(None, f))
    assert reports[0].reachability == 1.0
    assert reports[1].reachability < 1.0
    for rep in reports:
        assert Report.from_json(rep.to_json()) == rep
    assert reports[1].latency_inflation(reports[0]) > 0
