"""The plain reference against the program on the CPU at small sizes:
the same fabrics, the same routes, the same counters."""
import numpy as np
import pytest

from ringbench import harness, program, reference, small, workload
from ringbench.reference import fabric

CELLS = ["ring_mesh_1024.fig15_grid", "flat_mesh_1024.fig15_grid",
         "ring_mesh_1024.trace_hd", "ring_mesh_1024.faults_low"]


def config(family, n_pes):
    from repro.core import topology as T
    grids = T.RING_MESH_GRIDS if family == "ring_mesh" else T.FLAT_MESH_GRIDS
    bx, by = grids[n_pes]
    return {"family": family, "n_pes": n_pes, "blocks_x": bx,
            "blocks_y": by, "queue_depth": 2, "src_queue_depth": 8}


@pytest.mark.parametrize("family", ["ring_mesh", "flat_mesh"])
@pytest.mark.parametrize("n_pes", [16, 64, 256])
def test_fabric_and_routes_match_the_program(family, n_pes):
    from repro.core import topology as T
    t = T.build(family, n_pes, src_queue_depth=8)
    f = fabric.build(config(family, n_pes))
    for mine, theirs in ((f.kind, t.link_kind), (f.vc, t.link_vc),
                         (f.phys, t.link_phys), (f.src, t.link_src_node),
                         (f.dst, t.link_dst_node), (f.cap, t.link_cap),
                         (f.prio, t.link_prio), (f.pe_src, t.pe_src_link),
                         (f.pe_eject, t.pe_eject_link)):
        np.testing.assert_array_equal(mine, theirs)
    rows = np.nonzero(t.link_dst_node >= 0)[0]
    q = np.repeat(rows, n_pes)
    d = np.tile(np.arange(n_pes), rows.size)
    np.testing.assert_array_equal(f.next_queue(q, d),
                                  t.route_table[rows].reshape(-1))


@pytest.mark.parametrize("cell", CELLS)
def test_counters_match_the_program(cell):
    from repro.core.experiment import run_experiments
    spec = small.shrink(harness.load_cell(cell))
    pts = workload.points(spec["config"], spec["mix"], 2**31 + 7)
    reports = run_experiments([program.experiment(spec["config"], p)
                               for p in pts])
    for p, r in zip(pts, reports):
        assert program.counters(r) == reference.counters(spec["config"], p)


@pytest.mark.parametrize("passes", [1, 2])
def test_blocked_winners_match_the_program(passes):
    """Few arbitration passes at saturation leave blocked winners, the
    path that counts ``lost``: both sides count them alike."""
    from repro.core import sim
    spec = small.shrink(harness.load_cell(CELLS[0]))
    cfg = spec["config"]
    pts = [p for p in workload.points(cfg, spec["mix"], 3)
           if p["inj_rate"] == 1.0]
    for p in pts:
        exp = program.experiment(cfg, p)
        topo = exp.topology.build()
        m = sim._run_single(sim.build_geometry(topo),
                            sim.make_point(exp.sim_config(), 16, topo),
                            cycles=p["cycles"], warmup=p["warmup"],
                            starvation_limit=cfg["starvation_limit"],
                            arb_iters=passes)
        want = reference.counters(cfg, p, arbitration_passes=passes)
        assert int(m.lost) == want["lost"] > 0
        assert int(m.delivered) == want["delivered"]
        assert int(m.lat_sum) == want["lat_sum"]


def test_the_seed_draws_each_points_experiment_seed():
    spec = harness.load_cell(CELLS[0])

    def pts(seed):
        return workload.points(spec["config"], spec["mix"], seed)
    key, again, other = pts(2**31 + 1), pts(2**31 + 1), pts(2**31 + 2)
    assert key == again
    assert [(p["inj_rate"], p["pattern"]) for p in key] == \
        [(p["inj_rate"], p["pattern"]) for p in other]
    seeds = [p["seed"] for p in key]
    assert len(set(seeds)) == len(seeds) == 9
    assert not set(seeds) & {p["seed"] for p in other}
    assert all(0 <= s < 2**31 for s in seeds)
    faults = harness.load_cell(CELLS[3])
    for seed in (5, 2**33 + 7):
        got = workload.points(faults["config"], faults["mix"], seed)
        assert [p["dead_links"] for p in got] == faults["mix"]["dead_links"]


@pytest.mark.parametrize("n_pes", [64, 1024])
def test_collective_phases_match_the_program_extractor(n_pes):
    """``trace_hd``'s phases are the program's mined schedules."""
    from repro import trace
    mix = harness.load_cell(CELLS[2])["mix"]
    cfg = {"n_pes": n_pes}
    pts = workload.points(cfg, mix, 1)
    col = mix["collectives"]
    want = trace.traces_for_schedules(
        n_pes, pod_size=16, algorithm=col["algorithm"],
        normalize_flits=col["normalize_flits"], flit_bytes=col["flit_bytes"])
    assert [p["schedule"] for p in pts] == list(want)
    for p in pts:
        dst, flits = want[p["schedule"]].trace.arrays()
        assert len(p["phases"]) == len(dst)
        for r, d, f in zip(p["phases"], dst, flits):
            # One row per active source, in source order.
            np.testing.assert_array_equal(r[:, 0], np.nonzero(f)[0])
            np.testing.assert_array_equal(r[:, 1], d[r[:, 0]])
            np.testing.assert_array_equal(r[:, 2], f[r[:, 0]])
        assert p["scale"] == want[p["schedule"]].trace.scale
