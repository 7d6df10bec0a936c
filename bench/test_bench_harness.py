"""A whole run at a small size on the CPU: the last line's contract, the
comparison that decides ``correct`` failing when the timed path is broken
underneath, the control failing it, and the refusals."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from ringbench import check, harness, small

CELLS = ["ring_mesh_1024.fig15_grid", "flat_mesh_1024.fig15_grid",
         "ring_mesh_1024.trace_hd", "ring_mesh_1024.faults_low"]
RUN = os.path.join(harness.BENCH_DIR, "run.py")


def run_small(cell, trace=False, seed=2**31 + 11):
    spec = small.shrink(harness.load_cell(cell))
    return harness.run(spec, seed, 0.05, trace, t_start=time.perf_counter(),
                       platform="cpu")


def _last_line(cell, trace, n_points):
    out = run_small(cell, trace)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["attempted"] % n_points == 0
    assert out["checks"] == {"mismatches": {"value": 0, "limit": 0},
                             "failed_points": {"value": 0, "limit": 0}}
    dev = out["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    spec = harness.load_cell(cell)
    metrics = spec["per_layer"] if trace else spec["end_to_end"]
    units = {m["name"]: m["unit"] for m in metrics}
    for name, m in out["metrics"].items():
        assert m["unit"] == units[name] and isinstance(m["value"], (int, float))
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        # The CPU trace has no device plane: device metrics stay silent.
        assert not any(n.startswith("device.") for n in out["metrics"])
        assert [m["value"] for n, m in out["metrics"].items()
                if n.startswith("sweep.compiles_in_window")] == [0]
    else:
        assert set(out["metrics"]) == set(units)
    json.dumps(out)


@pytest.mark.parametrize("trace", [False, True])
def test_last_line_contract(trace):
    _last_line(CELLS[0], trace, 9)


@pytest.mark.parametrize("trace", [False, True])
def test_last_line_contract_of_the_fault_cell(trace):
    """Its rate and device readings go under their ``.faults`` names."""
    _last_line(CELLS[3], trace, 2)


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    # Light load blocks a winner rarely: 256 PEs over 600 cycles has some.
    spec = small.shrink(harness.load_cell(cell), n_pes=256, cycles=600)
    assert check.control_reading(spec, 2**31 + 5) > 0


def _bench(cwd, *args, **env):
    return subprocess.run(
        [sys.executable, RUN, "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", *args], cwd=cwd, capture_output=True, text=True,
        env={**os.environ, **env}, timeout=120)


def test_refuses_without_a_tpu():
    p = _bench(harness.ROOT, JAX_PLATFORMS="cpu")
    assert p.returncode == 3 and p.stdout == ""
    assert "needs tpu devices" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1"], cwd=tmp_path, capture_output=True,
        text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)
    assert p.returncode == 3 and p.stdout == ""
    assert "no program" in p.stderr
