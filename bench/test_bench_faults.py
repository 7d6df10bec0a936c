"""A run at a small size on the CPU with the timed path broken underneath:
the comparison that decides ``correct`` has to fail it, once for each
fault the cells can have: a step that returns its state unchanged, half
of a batch left out (its points given the other half's results), an
answer altered where it is produced.  (No cell spans chips, so no
exchange between chips can be left out.)"""
import dataclasses
import time

import jax
import pytest

from ringbench import harness, small

CELLS = ["ring_mesh_1024.fig15_grid", "flat_mesh_1024.fig15_grid",
         "ring_mesh_1024.trace_hd", "ring_mesh_1024.faults_low"]


def _unchanged_step(geom, state, *args, **kwargs):
    return state


def _half_batch(dispatch):
    def run(topo, cfgs, geom, idxs, points, exe, out):
        dispatch(topo, cfgs, geom, idxs, points, exe, out)
        for i in idxs[(len(idxs) + 1) // 2:]:
            out[i] = dataclasses.replace(out[idxs[0]], cfg=cfgs[i])
    return run


def _altered(to_result):
    def run(topo, cfg, m):
        r = to_result(topo, cfg, m)
        return dataclasses.replace(r, delivered=r.delivered + 1)
    return run


# Every dispatch of the faults cell holds one point: no half to leave out.
CASES = [(c, f) for c in CELLS
         for f in ("unchanged_step", "half_batch", "altered_answer")
         if not (f == "half_batch" and c == CELLS[3])]


@pytest.mark.parametrize("cell,fault", CASES)
def test_broken_timed_path_is_not_correct(monkeypatch, cell, fault):
    from repro.core import sim, sweep
    from repro.kernels import noc_step
    if fault == "unchanged_step":
        monkeypatch.setattr(noc_step, "cycle_step", _unchanged_step)
    elif fault == "half_batch":
        monkeypatch.setattr(sweep, "_dispatch", _half_batch(sweep._dispatch))
    else:
        monkeypatch.setattr(sim, "_to_result", _altered(sim._to_result))
    # Traced and compiled programs of the sound path must not serve the
    # broken one, nor the broken one's outlive the test.
    sweep.reset_caches()
    jax.clear_caches()
    try:
        spec = small.shrink(harness.load_cell(cell))
        out = harness.run(spec, 2**31 + 11, 0.05, False,
                          t_start=time.perf_counter(), platform="cpu")
    finally:
        sweep.reset_caches()
        jax.clear_caches()
    assert out["correct"] is False
    assert out["checks"]["mismatches"]["value"] > 0
