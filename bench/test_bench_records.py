"""Trace traffic as send-ordered records, and traffic kinds added by a
generator file: the existing cells' points and the reference's counters
are as they were before records, a source with several records replays
as the reference's docstring states, and a new generator plus a new
traffic file run end to end, and traffic with several records per
source runs as the reference does or fails in set-up."""
import hashlib
import json
import time

import numpy as np
import pytest

from ringbench import harness, reference, small, workload

CELLS = ["ring_mesh_1024.fig15_grid", "flat_mesh_1024.fig15_grid",
         "ring_mesh_1024.trace_hd", "ring_mesh_1024.faults_low"]
SEEDS = [1, 2**31 + 3, 2**33 + 12345]

# Digests of every cell's points, computed by the generator that read
# phases as [n_phases, P] (dst, flits) arrays: seeds, budgets, rates,
# patterns, faults, schedules, scales, and each phase's dst / flits.
POINTS = {
    CELLS[0]: ["c86e9ff995ef7c1f", "1dfbe462c1aad050", "13d7dc6ea11cb862"],
    CELLS[1]: ["c86e9ff995ef7c1f", "1dfbe462c1aad050", "13d7dc6ea11cb862"],
    CELLS[2]: ["d0816d90a5570795", "7979496587a5e21c", "3518c3414a26105e"],
    CELLS[3]: ["678bdf69bcbca73f", "20888be6dc11a9c5", "20143f4f30dbe2b1"],
}


def dense(records: list, n_pes: int) -> tuple[np.ndarray, np.ndarray]:
    """Records with one row per source as ``[n_phases, P]`` (dst, flits)."""
    dst = np.zeros((len(records), n_pes), np.int64)
    flits = np.zeros((len(records), n_pes), np.int64)
    for i, r in enumerate(records):
        r = np.asarray(r)
        assert len(set(r[:, 0])) == len(r), "one row per source"
        dst[i, r[:, 0]], flits[i, r[:, 0]] = r[:, 1], r[:, 2]
    return dst, flits


def digest(pts: list[dict], n_pes: int) -> str:
    out = []
    for p in pts:
        q = {k: v for k, v in p.items() if k != "phases"}
        q["dst"] = q["flits"] = None
        if p["phases"] is not None:
            d, f = dense(p["phases"], n_pes)
            q["dst"], q["flits"] = d.tolist(), f.tolist()
        out.append(q)
    return hashlib.sha256(
        json.dumps(out, sort_keys=True).encode()).hexdigest()[:16]


@pytest.mark.parametrize("cell", CELLS)
def test_points_are_unchanged(cell):
    spec = harness.load_cell(cell)
    got = [digest(workload.points(spec["config"], spec["mix"], s),
                  spec["config"]["n_pes"]) for s in SEEDS]
    assert got == POINTS[cell]


def _trace_hd_points(n_pes, cycles):
    spec = small.shrink(harness.load_cell(CELLS[2]), n_pes=n_pes,
                        cycles=cycles)
    return spec["config"], workload.points(spec["config"], spec["mix"],
                                           2**31 + 3)


def _c(delivered, lat_sum, moved, phase_done):
    return {"delivered": delivered, "offered": delivered,
            "accepted": delivered, "dropped": 0, "lost": 0,
            "lat_sum": lat_sum, "moved": moved, "in_flight": 0,
            "phase_done": phase_done}


# The reference's counters for trace_hd's three schedules (flat, hier,
# hier_int8), as it gave them when it replayed [n_phases, P] tables.
TRACE_HD = {
    (16, 160): [
        _c(480, 5664, 2176, [34, 53, 59, 62, 65, 71, 90, 125]),
        _c(288, 1392, 912, [17, 23, 32, 41, 50, 59, 62, 66]),
        _c(464, 5632, 2128, [34, 53, 72, 107, 111, 119]),
    ],
    (256, 600): [
        _c(9728, 654080, 58880, [260, 328, 397, 419, 428, 437, 441, 444,
                                 447, 451, 460, 469, 491, 560, 629, 890]),
        _c(6656, 56576, 27904, [17, 23, 61, 83, 121, 143, 152, 161, 170,
                                179, 201, 239, 261, 299, 302, 306]),
        _c(9984, 655616, 59648, [260, 328, 397, 419, 428, 437, 446, 455,
                                 477, 546, 615, 876, 880, 888]),
    ],
}


@pytest.mark.parametrize("size", list(TRACE_HD))
def test_trace_hd_reference_counters_are_unchanged(size):
    cfg, pts = _trace_hd_points(*size)
    assert [reference.counters(cfg, p) for p in pts] == TRACE_HD[size]


def test_splitting_a_record_changes_no_counter():
    """A record of f flits and the two records (1, f - 1) to the same
    destination, one after the other, are the same traffic."""
    cfg, pts = _trace_hd_points(16, 160)
    for p in pts:
        split = []
        for r in p["phases"]:
            rows = []
            for s, d, f in r.tolist():
                rows += [[s, d, 1], [s, d, f - 1]] if f > 1 else [[s, d, f]]
            split.append(np.array(rows))
        assert sum(len(r) for r in split) > sum(len(r) for r in p["phases"])
        assert reference.counters(cfg, {**p, "phases": split}) \
            == reference.counters(cfg, p)


def test_hand_traced_phase():
    """16-PE ring-mesh, one phase: PE 0 sends 2 flits to PE 1, then 1 flit
    to PE 2; every other PE is idle.  PE 0 injects one flit a cycle.

    - cycle 0: A (to 1) enters PE 0's inject buffer;
    - cycle 1: A moves onto the clockwise ring channel 0 -> 1; B (to 1)
      injected;
    - cycle 2: A ejects at PE 1 (latency 2); B moves onto 0 -> 1; the
      first record is sent, so C goes to PE 2, injected;
    - cycle 3: B ejects at PE 1 (latency 2); C moves onto 0 -> 1
      (clockwise on the tie at distance 2);
    - cycle 4: C moves onto 1 -> 2;
    - cycle 5: C ejects at PE 2 (latency 3): the phase's last flit, so the
      phase is done in cycle 5.

    delivered 3, latency sum 7, hops moved 1 + 2 + 2 + 1 + 1 = 7.  In the
    other order (C first) C ejects in cycle 3, and A and B in cycles 3
    and 4: done in cycle 4, with the same sums.
    """
    cfg = {**harness.load_cell(CELLS[2])["config"], "n_pes": 16,
           "blocks_x": 1, "blocks_y": 1}
    pt = {"seed": 7, "cycles": 8, "warmup": 0, "inj_rate": 1.0,
          "dead_links": [], "phases": [np.array([[0, 1, 2], [0, 2, 1]])]}
    want = _c(3, 7, 7, [5])
    assert reference.counters(cfg, pt) == want
    pt["phases"] = [np.array([[0, 2, 1], [0, 1, 2]])]
    assert reference.counters(cfg, pt) == {**want, "phase_done": [4]}


def test_conservation_with_four_records_per_source():
    cfg = {**harness.load_cell(CELLS[0])["config"], "n_pes": 64,
           "blocks_x": 2, "blocks_y": 2}
    rng = np.random.default_rng(2**31 + 9)
    src = np.repeat(np.arange(64), 4)
    dst = (src + rng.integers(1, 64, src.size)) % 64
    rec = np.stack([src, dst, rng.integers(1, 4, src.size)], axis=1)
    rec = rec[rng.permutation(src.size)]   # sources interleaved
    total = int(rec[:, 2].sum())
    for cycles in (60, 2000):   # mid-phase, and the phase complete
        c = reference.counters(cfg, {
            "seed": 11, "cycles": cycles, "warmup": 0, "inj_rate": 1.0,
            "dead_links": [], "phases": [rec]})
        assert c["offered"] == c["delivered"] + c["dropped"] + c["in_flight"]
        assert c["lost"] == 0
    assert c["delivered"] == total and c["phase_done"][0] >= 0


GENERATOR = '''
import numpy as np


def traffics(config, mix, rng):
    """Each phase, every PE sends to ``rows`` PEs drawn from ``rng``."""
    n = config["n_pes"]
    out = []
    for name in ("a", "b"):
        phases = []
        for _ in range(mix["phases"]):
            src = np.repeat(np.arange(n), mix["rows"])
            dst = (src + rng.integers(1, n, src.size)) % n
            phases.append(np.stack(
                [src, dst, rng.integers(1, 4, src.size)], axis=1))
        out.append({"schedule": name, "flit_bytes": 32, "scale": 1.0,
                    "phases": phases})
    return out
'''


@pytest.fixture
def list_cell(tmp_path, monkeypatch):
    """A copy of ``BENCHMARK.json`` that lists the cell
    ``ring_mesh_1024.<traffic>``, as the cell's own entry would, in
    ``point_cycles_per_s``'s cells too; the cell cut to 16 PEs."""
    def make(traffic):
        with open(harness.BENCHMARK) as f:
            bench = json.load(f)
        name = f"ring_mesh_1024.{traffic}"
        bench["workloads"].append({
            "name": name, "config": "ring_mesh_1024", "traffic": traffic,
            "chips": 1, "why": "a test's cell"})
        for m in bench["end_to_end"]:
            if "workloads" in m and m["name"] == "point_cycles_per_s":
                m["workloads"].append(name)
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
        monkeypatch.setattr(harness, "BENCHMARK",
                            str(tmp_path / "BENCHMARK.json"))
        return small.shrink(harness.load_cell(name))
    return make


@pytest.fixture
def probe_cell(tmp_path, monkeypatch, list_cell):
    """A cell whose traffic comes from a new generator file and a new
    traffic file, in a directory of their own; ``rows`` records per
    source per phase."""
    def make(rows):
        gen, traffic = tmp_path / "generators", tmp_path / "traffic"
        gen.mkdir(exist_ok=True)
        traffic.mkdir(exist_ok=True)
        (gen / "probe.py").write_text(GENERATOR)
        (traffic / "probe.json").write_text(json.dumps({
            "generator": "probe", "phases": 3, "rows": rows,
            "cycles": 400, "warmup": 0, "inj_rates": [1.0],
            "invariants": ["lost_zero", "conserved", "trace_completed"]}))
        monkeypatch.setattr(harness, "TRAFFIC_DIR", str(traffic))
        monkeypatch.setattr(workload, "GENERATOR_DIR", str(gen))
        return list_cell("probe")
    return make


def test_a_generator_file_adds_a_cell(probe_cell):
    spec = probe_cell(rows=1)
    assert spec["config"]["n_pes"] == 16 and spec["mix"]["cycles"] == 480
    seed = 2**31 + 21
    pts = workload.points(spec["config"], spec["mix"], seed)
    assert [p["schedule"] for p in pts] == ["a", "b"]
    assert all(len(r) == 16 for p in pts for r in p["phases"])
    # The same seed gives the same records; another seed, other records.
    again = workload.points(spec["config"], spec["mix"], seed)
    other = workload.points(spec["config"], spec["mix"], seed + 1)
    assert digest(pts, 16) == digest(again, 16) != digest(other, 16)
    out = harness.run(spec, seed, 0.05, False, t_start=time.perf_counter(),
                      platform="cpu")
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["attempted"] % 2 == 0
    assert {"point_cycles_per_s", "setup_s"} <= set(out["metrics"])


def assert_refused(out: dict, err: str, n_points: int, seconds: float):
    """The result of a run whose set-up raised: a result line, at once,
    with every point failed, no window run and the traceback on standard
    error."""
    assert seconds < 60
    assert out["correct"] is False
    assert out["attempted"] == 0 and out["failed"] == n_points
    assert out["metrics"] == {}
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert out["checks"]["failed_points"] == {"value": n_points, "limit": 0}
    assert "Traceback" in err
    assert err.rstrip().splitlines()[-1] == \
        f"check failed_points {n_points} limit 0"
    json.dumps(out)


def test_a_setup_refusal_fails_every_point(probe_cell, capsys, monkeypatch):
    """A program that refuses the points in set-up fails every point; the
    refusal is planted here, so the test rests on no rule of the
    program's."""
    from ringbench import program

    def refuse(config, pt):
        raise ValueError("the program refuses this point")

    spec = probe_cell(rows=1)
    monkeypatch.setattr(program, "experiment", refuse)
    t0 = time.perf_counter()
    out = harness.run(spec, 2**31 + 23, 0.05, False, t_start=t0,
                      platform="cpu")
    err = capsys.readouterr().err
    assert_refused(out, err, 2, time.perf_counter() - t0)
    assert "the program refuses this point" in err


@pytest.mark.parametrize("traffic", ["probe", "moe_a2a"])
def test_several_records_per_source_run_or_fail_in_setup(
        traffic, probe_cell, list_cell, capsys):
    """Traffic with several records per source in a phase (the probe with
    two rows, and moe_a2a at 16 PEs) passes in one of two ways: the
    program replays it as the reference does, or it refuses it in set-up.
    A program that accepts the records and replays them otherwise fails
    the comparison, and this test with it."""
    spec = probe_cell(rows=2) if traffic == "probe" else list_cell(traffic)
    seed = 2**31 + 23
    pts = workload.points(spec["config"], spec["mix"], seed)
    assert any(len(np.unique(r[:, 0])) < len(r)
               for p in pts for r in p["phases"])
    t0 = time.perf_counter()
    out = harness.run(spec, seed, 0.05, False, t_start=t0, platform="cpu")
    seconds = time.perf_counter() - t0
    err = capsys.readouterr().err
    if out["attempted"] == 0:
        assert_refused(out, err, len(pts), seconds)
    else:
        assert out["checks"] == {"mismatches": {"value": 0, "limit": 0},
                                 "failed_points": {"value": 0, "limit": 0}}
        assert out["correct"] is True and out["failed"] == 0
