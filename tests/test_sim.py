"""Simulator behaviour tests: conservation, saturation sanity, paper trends."""
import numpy as np
import pytest

from repro.core import sim, topology


def run(name, n, **kw):
    defaults = dict(cycles=800, warmup=300, inj_rate=0.25, pattern="uniform",
                    seed=0)
    defaults.update(kw)
    t = topology.build(name, n)
    return sim.simulate(t, sim.SimConfig(**defaults))


@pytest.mark.parametrize("name", ["ring_mesh", "flat_mesh"])
@pytest.mark.parametrize("pattern", sim.PATTERNS)
def test_no_lost_flits(name, pattern):
    r = run(name, 64, pattern=pattern, inj_rate=1.0,
            locality_ringlet=0.5, locality_block=0.3)
    assert r.lost == 0


@pytest.mark.parametrize("name", ["ring_mesh", "flat_mesh"])
def test_low_load_throughput_equals_offered(name):
    # At 5% injection nothing saturates: delivery rate == offered rate.
    r = run(name, 64, inj_rate=0.05, cycles=1500, warmup=500)
    offered_rate = r.offered / r.measured_cycles
    assert r.dropped == 0
    assert r.throughput == pytest.approx(offered_rate, rel=0.05)


def test_latency_at_least_path_length():
    r = run("ring_mesh", 16, inj_rate=0.05)
    # min possible: inject + >=1 hop + eject
    assert r.avg_latency >= 2.0


@pytest.mark.parametrize("name", ["ring_mesh", "flat_mesh"])
def test_latency_monotone_in_load(name):
    lats = [run(name, 64, inj_rate=ir, seed=3,
                locality_ringlet=0.5, locality_block=0.3).avg_latency
            for ir in (0.1, 0.5, 1.0)]
    assert lats[0] <= lats[1] * 1.1  # allow small noise
    assert lats[0] < lats[2]


def test_saturation_does_not_collapse():
    """Post-deadlock-fix regression: at full load with locality the
    ring-mesh must sustain >0.3 packets/PE/cycle (it used to gridlock)."""
    for n in (64, 256):
        r = run("ring_mesh", n, inj_rate=1.0, cycles=1200, warmup=400,
                **sim.PAPER_LOCALITY)
        assert r.per_pe_throughput > 0.3, (n, r.row())


def test_paper_claim_c6_throughput_doubles():
    """C6: throughput grows ~2x when the PE count doubles (locality mode)."""
    thr = {}
    for n in (64, 128, 256):
        thr[n] = run("ring_mesh", n, inj_rate=0.625, cycles=1200, warmup=400,
                     seed=1, **sim.PAPER_LOCALITY).throughput
    assert 1.6 < thr[128] / thr[64] < 2.4
    assert 1.6 < thr[256] / thr[128] < 2.4


def test_paper_claim_c5_latency_advantage_at_scale():
    """C5: ring-mesh latency <= flat-mesh latency at 256 PEs under the
    paper's locality-heavy operating regime."""
    rm = run("ring_mesh", 256, inj_rate=0.625, cycles=1200, warmup=400,
             seed=1, **sim.PAPER_LOCALITY)
    fm = run("flat_mesh", 256, inj_rate=0.625, cycles=1200, warmup=400,
             seed=1, **sim.PAPER_LOCALITY)
    assert rm.avg_latency < fm.avg_latency
    assert rm.throughput > fm.throughput


def test_deterministic_given_seed():
    a = run("ring_mesh", 16, seed=7)
    b = run("ring_mesh", 16, seed=7)
    assert a.row() == b.row()


def test_single_packet_block_transaction_latency():
    """§4.2 / C8: one cross-ringlet transfer in an idle block is fast.
    With Ir=1/16 on 16 PEs the network is essentially idle; mean latency
    should be <= 8 cycles one-way (12-cycle transaction bound)."""
    r = run("ring_mesh", 16, inj_rate=1.0 / 16, cycles=2000, warmup=200)
    assert r.avg_latency <= 8.0


def test_kind_diagnostics_consistent():
    """Optional per-kind instrumentation agrees with the main counters:
    wins sum to measured link traversals, final occupancy to in_flight."""
    t = topology.build_ring_mesh(16)
    cfg = sim.SimConfig(cycles=500, warmup=0, inj_rate=0.5, seed=4)
    d = sim.kind_diagnostics(t, cfg)
    r = sim.simulate(t, cfg)
    moved = r.flit_hops_per_cycle * r.measured_cycles
    assert sum(d["wins_by_kind"].values()) == round(moved)
    assert sum(d["q_len_by_kind"].values()) == r.in_flight
    # wins are keyed by the *winning* queue's kind; eject queues are pure
    # sinks and never contend
    assert d["wins_by_kind"]["eject"] == 0
    assert all(v >= 0 for sub in d.values() for v in sub.values())


def test_simconfig_rejects_bad_inj_rate():
    with pytest.raises(ValueError, match="inj_rate"):
        sim.SimConfig(inj_rate=1.5)
    with pytest.raises(ValueError, match="inj_rate"):
        sim.SimConfig(inj_rate=-0.1)


def test_simconfig_rejects_bad_cycles():
    with pytest.raises(ValueError, match="cycles"):
        sim.SimConfig(cycles=0, warmup=0)
    with pytest.raises(ValueError, match="cycles"):
        sim.SimConfig(cycles=-10, warmup=0)


def test_simconfig_rejects_bad_warmup():
    with pytest.raises(ValueError, match="warmup"):
        sim.SimConfig(cycles=100, warmup=100)
    with pytest.raises(ValueError, match="warmup"):
        sim.SimConfig(cycles=100, warmup=250)
    with pytest.raises(ValueError, match="warmup"):
        sim.SimConfig(cycles=100, warmup=-1)
    sim.SimConfig(cycles=100, warmup=0)  # boundary: measure from cycle 0


def test_simconfig_rejects_unknown_pattern():
    with pytest.raises(ValueError, match="unknown pattern"):
        sim.SimConfig(pattern="zipf")


def test_simconfig_rejects_bad_locality():
    with pytest.raises(ValueError, match="locality"):
        sim.SimConfig(locality_ringlet=0.8, locality_block=0.3)


def test_patterns_are_fixed_permutations():
    perm = sim.pattern_destinations("transpose", 64)
    assert sorted(perm.tolist()) == list(range(64))
    perm = sim.pattern_destinations("bit_reversal", 256)
    assert sorted(perm.tolist()) == list(range(256))
    assert sim.pattern_destinations("uniform", 64) is None


def _fanout_topology(case):
    import dataclasses

    from repro.core import morph
    from repro.core import packet as pk
    from repro.core.spec import TopologySpec
    from repro.faults import sample_faults
    if case == "morphed_ring_mesh":
        # Morphed in place after the tables were built: they must stay
        # valid for the new routes (a bypass and a switched-off ringlet).
        t = topology.build_ring_mesh(64)
        sim.build_geometry(t)
        ctl = morph.MorphController(t)
        ctl.apply(pk.MorphPacket(hl=1, ers=0,
                                 link_states=(0, 0, 0, 1, 0, 0, 0, 0)),
                  target=1)
        ctl.apply(pk.MorphPacket(hl=1, ers=0,
                                 link_states=(0, 0, 0, 0, 2, 0, 0, 0)),
                  target=0)
        return t
    if case.startswith("repaired_"):
        spec = TopologySpec(case.removeprefix("repaired_"), 64)
        t = dataclasses.replace(spec, faults=sample_faults(
            spec.build(), n_dead_links=3, seed=6)).build()
        assert t.dead_queues is not None and t.dead_queues.any()
        return t
    name, n = case.rsplit("_", 1)
    return topology.build(name, int(n))


@pytest.mark.parametrize("case", [
    f"{name}_{n}" for name in ("ring_mesh", "flat_mesh")
    for n in (16, 64, 256, 1024)] + [
    "morphed_ring_mesh", "repaired_ring_mesh", "repaired_flat_mesh"])
def test_fanout_table_covers_every_route(case):
    """Row q of ``Geometry.outtab`` lists every queue leaving q's
    destination node, dead ones included, then pads with the dummy row:
    so every live next hop of the route table is in its queue's row."""
    t = _fanout_topology(case)
    g = sim.build_geometry(t)
    L = t.n_links
    ot = np.asarray(g.outtab)
    src, dst = t.link_src_node, t.link_dst_node
    assert ot.shape[0] == L + 1 and np.all(ot[L] == L)
    n_nodes = int(max(src.max(), dst.max())) + 1
    leaving = np.bincount(src[src >= 0], minlength=n_nodes)
    want = np.where(dst >= 0, leaving[np.clip(dst, 0, None)], 0)
    real = ot[:L] != L
    assert np.array_equal(real, np.arange(ot.shape[1]) < want[:, None])
    assert np.all(src[ot[:L][real]]
                  == np.broadcast_to(dst[:, None], real.shape)[real])
    rt = t.route_table
    live = rt >= 0
    rows = np.arange(L, dtype=np.int64)[:, None] * (L + 1)
    assert np.all(np.isin((rows + rt)[live], (rows + ot[:L])[real]))
    assert np.array_equal(np.asarray(g.outphys), np.asarray(g.phys)[ot])
    assert np.array_equal(np.asarray(g.outcap), np.asarray(g.cap)[ot])
