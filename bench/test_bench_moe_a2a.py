"""The moe_a2a traffic (``bench/generators/moe_a2a.py``): DeepSeek-V3's
router at 1024 PEs against a plain per-token loop of its rule, the
records' shape, and the reference's replay of the cut cells."""
import json
import os

import numpy as np
import pytest

from ringbench import harness, load_named, reference, small, workload

GEN = load_named(workload.GENERATOR_DIR, "moe_a2a")
SEED = 2**31 + 57


def cell():
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "ring_mesh_1024.json")) as f:
        config = json.load(f)
    with open(os.path.join(harness.TRAFFIC_DIR, "moe_a2a.json")) as f:
        mix = json.load(f)
    return config, mix


def routed(s: np.ndarray, n_group: int, topk_group: int,
           top_k: int) -> list[int]:
    """One token's experts by the published rule, as a plain loop."""
    per = len(s) // n_group
    score = [sum(sorted(s[g * per:(g + 1) * per])[-2:])
             for g in range(n_group)]
    groups = sorted(range(n_group), key=lambda g: (-score[g], g))[:topk_group]
    cands = [e for g in groups for e in range(g * per, (g + 1) * per)]
    return sorted(sorted(cands, key=lambda e: (-s[e], e))[:top_k])


def test_the_published_router_at_1024_pes():
    config, mix = cell()
    assert GEN.router(config, mix) == (256, 8, 4, 8)
    traffics = GEN.traffics(config, mix, np.random.default_rng(SEED))
    assert [t["schedule"] for t in traffics] == ["draw0", "draw1", "draw2",
                                                 "draw3"]
    for t in traffics:
        assert t["flit_bytes"] == 32 and t["scale"] == 224
        assert len(t["phases"]) == 2


def test_records_follow_the_rule_at_1024_pes():
    """Each draw's records against a per-token loop over the same normal
    draws: 8 distinct experts in at most 4 router rows, one per ringlet;
    dispatch 16 rows a source less its local pairs; combine twice the
    dispatch's flits, each source's rows in (owner, token, expert) order."""
    config, mix = cell()
    traffics = GEN.traffics(config, mix, np.random.default_rng(SEED))
    draws = np.random.default_rng(SEED)
    n_pes, bx = config["n_pes"], config["blocks_x"]
    for t in traffics:
        u = draws.standard_normal((n_pes * 2, 256))
        s = 1.0 / (1.0 + np.exp(-u))
        go, local = [], np.zeros(n_pes, int)
        for tok in range(n_pes * 2):
            p = tok // 2
            experts = routed(s[tok], 8, 4, 8)
            assert len(set(experts)) == 8
            # Ringlet e hangs off block e // 4, in router row e // 4 // bx.
            assert len({e // 4 // bx for e in experts}) <= 4
            for e in experts:
                if 4 * e + p % 4 == p:
                    local[p] += 1
                else:
                    go.append((p, 4 * e + p % 4, 1))
        dispatch, combine = t["phases"]
        assert dispatch.tolist() == [list(r) for r in go]
        assert (np.bincount(dispatch[:, 0], minlength=n_pes)
                == 16 - local).all()
        assert 0 < local.sum() < 200
        assert set(dispatch[:, 1] // 4) == set(range(256))
        back = sorted(((d, p, 2) for p, d, _ in go), key=lambda r: r[0])
        assert combine.tolist() == [list(r) for r in back]
        assert combine[:, 2].sum() == 2 * dispatch[:, 2].sum()


def test_the_seed_fixes_the_records():
    config, mix = cell()

    def records(seed):
        return [r.tolist() for t in workload.generator_traffics(
            config, mix, seed) for r in t["phases"]]
    assert records(SEED) == records(SEED)
    assert records(SEED) != records(SEED + 1)


@pytest.mark.parametrize("config", [
    {"family": "flat_mesh", "n_pes": 1024, "blocks_x": 32, "blocks_y": 32},
    {"family": "ring_mesh", "n_pes": 1024, "blocks_x": 16, "blocks_y": 4},
], ids=["no_ringlets", "four_rows"])
def test_a_fabric_that_does_not_map_the_router_raises(config):
    _, mix = cell()
    with pytest.raises(ValueError):
        GEN.router(config, mix)


@pytest.mark.parametrize("n_pes,cycles,router", [
    (16, 160, (4, 1, 1, 2)), (256, 600, (64, 4, 2, 8))])
def test_the_reference_completes_the_cut_cells(n_pes, cycles, router):
    config, mix = cell()
    spec = small.shrink({"config": config, "mix": mix}, n_pes=n_pes,
                        cycles=cycles)
    cfg = spec["config"]
    assert GEN.router(cfg, spec["mix"]) == router
    pts = workload.points(cfg, spec["mix"], SEED)
    assert len(pts) == 4
    for p in pts:
        c = reference.counters(cfg, p)
        assert c["lost"] == 0 and c["in_flight"] == 0
        assert c["offered"] == c["delivered"] + c["dropped"]
        assert c["delivered"] == sum(int(r[:, 2].sum()) for r in p["phases"])
        assert len(c["phase_done"]) == 2
        assert 0 <= c["phase_done"][0] < c["phase_done"][1] < p["cycles"]
        # Room in the budget, as the 1024-PE cell keeps 15%.
        assert c["phase_done"][1] * 1.15 < p["cycles"]
