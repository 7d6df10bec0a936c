"""Sweep-engine tests: bit-exact equivalence with single-run simulate(),
conservation over the extended traffic patterns, grouping, compile reuse."""
import dataclasses

import numpy as np
import pytest

from repro.core import sim, sweep, topology

GRID = [(16, "ring_mesh"), (16, "flat_mesh"), (64, "ring_mesh"),
        (64, "flat_mesh")]


def _topo(name, n):
    return topology.build(name, n)


@pytest.mark.parametrize("n,name", GRID)
def test_sweep_matches_simulate_bitforbit(n, name):
    """The vmapped batch must reproduce per-point simulate() *exactly*:
    every metric is an integer accumulator, so there is no reduction-order
    slack to hide behind — all patterns, two rates/seeds per pattern."""
    t = _topo(name, n)
    cfgs = [sim.SimConfig(cycles=400, warmup=100, inj_rate=ir, pattern=p,
                          seed=s, locality_ringlet=lr, locality_block=lb)
            for p in sim.PATTERNS
            for (ir, s, lr, lb) in ((0.25, 0, 0.0, 0.0),
                                    (0.9, 3, 0.5, 0.3))]
    batched = sweep.sweep(t, cfgs)
    for cfg, rb in zip(cfgs, batched):
        rs = sim.simulate(t, cfg)
        assert rs == rb, (cfg.pattern, cfg.inj_rate, rs.row(), rb.row())


def test_sweep_mixed_budgets_group_and_preserve_order():
    t = _topo("ring_mesh", 16)
    cfgs = [sim.SimConfig(cycles=300, warmup=100, inj_rate=0.3, seed=1),
            sim.SimConfig(cycles=200, warmup=50, inj_rate=0.4, seed=2),
            sim.SimConfig(cycles=300, warmup=100, inj_rate=0.6, seed=3)]
    rs = sweep.sweep(t, cfgs)
    assert [r.cfg for r in rs] == cfgs
    for cfg, r in zip(cfgs, rs):
        assert r == sim.simulate(t, cfg)


def test_sweep_empty():
    assert sweep.sweep(_topo("ring_mesh", 16), []) == []


def test_sweep_compile_reuse_across_points():
    """Rates / seeds / patterns / localities are traced: re-sweeping a
    different grid of the same shape must not add executables."""
    t = _topo("flat_mesh", 16)
    g1 = sweep.grid(inj_rates=(0.2, 0.8), patterns=("uniform", "tornado"),
                    seeds=(0,), cycles=250, warmup=50)
    sweep.sweep(t, g1)
    before = sweep.compile_stats()["batch_xla_compiles"]
    g2 = sweep.grid(inj_rates=(0.3, 0.9), patterns=("hotspot", "shuffle"),
                    seeds=(7,), cycles=250, warmup=50,
                    locality_ringlet=0.4)
    sweep.sweep(t, g2)
    assert sweep.compile_stats()["batch_xla_compiles"] == before


@pytest.mark.parametrize("pattern", ["shuffle", "tornado", "hotspot"])
@pytest.mark.parametrize("name", ["ring_mesh", "flat_mesh"])
def test_conservation_new_patterns(name, pattern):
    """Flit conservation with warmup=0: every offered packet is delivered,
    dropped, or still queued; the exactness guard stays silent."""
    t = _topo(name, 64)
    r = sim.simulate(t, sim.SimConfig(cycles=600, warmup=0, inj_rate=0.9,
                                      pattern=pattern, seed=2))
    assert r.lost == 0
    assert r.offered == r.delivered + r.dropped + r.in_flight


def test_new_patterns_are_valid_maps():
    for pat in ("shuffle", "tornado"):
        perm = sim.pattern_destinations(pat, 64)
        assert sorted(perm.tolist()) == list(range(64))  # permutations
    # tornado's constant offset never maps a node to itself; shuffle keeps
    # the classic fixed points (0 and all-ones rotate onto themselves)
    tor = sim.pattern_destinations("tornado", 64)
    assert not np.any(tor == np.arange(64))
    hot = sim.pattern_destinations("hotspot", 64)
    assert np.all(hot[np.arange(64) != 32] == 32)
    assert hot[32] != 32


def test_sweep_many_pipelines_match():
    tasks = [(_topo("ring_mesh", 16),
              sweep.grid(inj_rates=(0.25, 0.75), cycles=250, warmup=50)),
             (_topo("flat_mesh", 16),
              sweep.grid(inj_rates=(0.5,), patterns=("transpose",),
                         cycles=250, warmup=50))]
    many = sweep.sweep_many(tasks)
    for (topo, cfgs), res in zip(tasks, many):
        assert res == sweep.sweep(topo, cfgs)


def test_geometry_morph_aware():
    """build_geometry must re-read the route table so in-place morphs
    (switched-off links) take effect without rebuilding the topology."""
    from repro.core import morph, packet
    t = topology.build_ring_mesh(16)
    cfg = sim.SimConfig(cycles=300, warmup=100, inj_rate=0.2, seed=0)
    before = sim.simulate(t, cfg)
    ctl = morph.MorphController(t)
    ctl.apply(packet.MorphPacket(hl=1, ers=0,
                                 link_states=(0, 0, 0, 0, 2, 0, 0, 0)),
              target=0)  # switch ringlet 0 of block 0 off
    after = sim.simulate(t, cfg)
    assert after.dropped > before.dropped
    ctl.reset()
    restored = sim.simulate(t, cfg)
    assert restored == before


def _fanout_batch(name, mode):
    """Four points of one sweep group on a 16-PE fabric: statistical,
    with faults injected unrepaired, or trace replays."""
    from repro.faults import sample_faults
    from repro.trace import Trace, TraceSpec
    t = _topo(name, 16)
    rates = ((0.3, 1), (0.7, 2), (1.0, 3), (1.0, 4))
    if mode == "healthy":
        return t, [sim.SimConfig(cycles=250, warmup=50, inj_rate=ir,
                                 pattern=p, seed=s)
                   for (ir, s), p in zip(rates, ("uniform", "transpose",
                                                 "hotspot", "tornado"))]
    if mode == "faults":
        f = sample_faults(t, n_dead_links=2, n_transient=2, drop_p=0.3,
                          onset=60, seed=6)
        return t, [sim.SimConfig(cycles=250, warmup=50, inj_rate=ir,
                                 seed=s, faults=f) for ir, s in rates]
    return t, [sim.SimConfig(cycles=250, warmup=0, inj_rate=ir, seed=s,
                             pattern=Trace(trace=TraceSpec(n_pes=16, phases=(
                                 tuple((i, (i + 5) % 16, k) for i in range(16)),
                                 tuple((i, (3 * i + 1) % 16, k)
                                       for i in range(16))))))
               for k, (ir, s) in enumerate(rates, start=2)]


@pytest.mark.parametrize("mode", ["healthy", "faults", "trace"])
@pytest.mark.parametrize("name", ["ring_mesh", "flat_mesh"])
def test_fanout_batch_matches_direct_and_points(name, mode):
    """A batch the sweep runs with the fan-out lookups gives every Metrics
    field of the same batch run with the direct gathers, ``arb_passes_run``
    included, and every per-point field of ``simulate``'s program and of
    the Pallas interpret kernel (which run no batch, so their passes run
    are their passes needed, at most the batch's)."""
    import functools

    import jax
    t, cfgs = _fanout_batch(name, mode)
    geom, ((key, idxs, points),) = sweep._grouped(t, cfgs)
    assert sweep.arb_fanout(len(idxs), "xla")
    cycles, warmup, starv, _, strict, watchdog = key
    fan = sweep._run_batch(geom, points, cycles=cycles, warmup=warmup,
                           starvation_limit=starv, strict_barrier=strict,
                           watchdog=watchdog)
    direct = jax.jit(jax.vmap(functools.partial(
        sim._run_core, cycles=cycles, warmup=warmup, starvation_limit=starv,
        strict_barrier=strict, watchdog=watchdog, batch_axis=sweep._AXIS,
        arb_fanout=False), in_axes=(None, 0), axis_name=sweep._AXIS))(
            geom, points)
    fields = [f.name for f in dataclasses.fields(sim.Metrics)]
    for f in fields:
        np.testing.assert_array_equal(getattr(fan, f), getattr(direct, f), f)
    assert np.all(fan.arb_passes_run >= fan.arb_passes)
    for b in range(len(cfgs)):
        point = jax.tree.map(lambda x, b=b: x[b], points)
        for backend in ("xla", "pallas"):
            one = sim._run_single(geom, point, cycles=cycles, warmup=warmup,
                                  starvation_limit=starv, backend=backend,
                                  strict_barrier=strict, watchdog=watchdog)
            assert one.arb_passes_run == one.arb_passes
            for f in fields:
                if f != "arb_passes_run":
                    np.testing.assert_array_equal(
                        getattr(one, f), getattr(fan, f)[b], (backend, f))
    if mode == "trace":
        assert np.all(fan.phase_done[:, -1] >= 0)   # every replay completes
    if mode == "faults":
        assert np.all(fan.dropped > 0)


def _trace(*phases):
    from repro import trace as tr
    return tr.from_records(16, [list(p) for p in phases])


def _padded_calls():
    """Calls whose points differ only in trace phase count or in fault
    bucket, on a 16-PE ring-mesh, with the points each call pads."""
    from repro.faults import FaultSpec, LinkFault, fabric_channels
    t = _topo("ring_mesh", 16)
    two = _trace([(i, (i + 5) % 16, 2) for i in range(16)],
                 [(i, (3 * i + 1) % 16, 2) for i in range(16)])
    three = _trace([(i, (i + 7) % 16, 1) for i in range(16)],
                   [(i, (i + 1) % 16, 3) for i in range(16)],
                   [(i, (5 * i + 2) % 16, 2) for i in range(16)])
    lengths = [sim.SimConfig(cycles=300, warmup=0, inj_rate=0.8, seed=s,
                             pattern=p) for s, p in ((1, two), (2, three))]
    # PE 0 -> 8 crosses router 0, which is dead: the watchdog ends the
    # 2-phase replay in its first phase; the 3-phase replay is healthy.
    stall = [sim.SimConfig(cycles=400, warmup=0, inj_rate=1.0, seed=s,
                           pattern=p, strict_barrier=True, watchdog=48,
                           faults=f)
             for s, p, f in ((3, _trace([(0, 8, 4)], [(0, 1, 4)]),
                              FaultSpec(dead_routers=(0,))),
                             (4, _trace([(0, 1, 4)], [(2, 9, 3)],
                                        [(5, 12, 2)]), None))]
    # Transient faults in bucket 16 padded to 32: their drop chances must
    # not move with the bucket.
    chans = [int(c) for c in fabric_channels(t)]
    buckets = [sim.SimConfig(cycles=300, warmup=0, inj_rate=0.4, seed=s,
                             faults=f)
               for s, f in ((5, None),
                            (6, FaultSpec(transient=tuple(
                                LinkFault(link=c, drop_p=0.4, onset=20)
                                for c in chans[:3]))),
                            (7, FaultSpec(dead_links=tuple(chans[4:14]))))]
    return t, {"trace_lengths": (lengths, 1), "strict_watchdog": (stall, 2),
               "fault_buckets": (buckets, 2)}


@pytest.mark.parametrize("case", ["trace_lengths", "strict_watchdog",
                                  "fault_buckets"])
def test_unequal_shapes_stack_into_one_dispatch(case):
    """Points of one call with unequal trace phase counts or fault buckets
    are padded to one shape: one compile, one dispatch, and every row
    equal to ``simulate``'s, with the point's own phases."""
    from repro import obs
    t, calls = _padded_calls()
    cfgs, n_padded = calls[case]
    shapes = {sweep._own_shape(c, t) for c in cfgs}
    assert len(shapes) == len(cfgs)     # no two points share a shape
    sweep.reset_caches()
    rs = sweep.sweep(t, cfgs)
    counters = obs.snapshot()
    assert sweep.compile_stats()["batch_xla_compiles"] == 1
    assert counters["sweep.dispatches"] == 1
    assert counters["sweep.padded_points"] == n_padded
    for cfg, r in zip(cfgs, rs):
        assert r == sim.simulate(t, cfg), cfg
        assert len(r.phase_done) == sweep._own_shape(cfg, t)[0]
    if case == "strict_watchdog":
        assert rs[0].stalled_phase == 0 and rs[0].phase_done[1] == -1
        assert rs[1].trace_completed
    if case == "fault_buckets":
        assert [sweep._own_shape(c, t)[1] for c in cfgs] == [0, 16, 32]
        assert rs[1].dropped > 0 and rs[2].dropped > 0


def test_six_way_split_starts_with_the_five_way_split():
    """A healthy point batched with faulted ones splits its key 6 ways,
    and depends on the first five keys being the 5-way split's for its
    random streams to be its own."""
    import jax
    for seed in (0, 1, 12345, 2 ** 31 - 1, -7):
        key = jax.random.PRNGKey(seed)
        np.testing.assert_array_equal(
            np.asarray(jax.random.split(key, 6))[:5],
            np.asarray(jax.random.split(key, 5)))
