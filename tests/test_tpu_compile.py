"""Compiles for a described TPU v5e: the chip's compiler, no chip attached.

The main path's batch program (``sweep._run_batch``) is compiled at the
1024-PE sizes ``chip_smoke.py`` runs, with its exact grids, so a program
the chip's compiler refuses, or one that outgrows the chip's 16 GiB of
HBM, fails here at no chip time.  The fused Pallas kernel is compiled
through Mosaic at 64 PEs: Mosaic refuses its gathers today, which the
strict xfail pins.

The topology is described only inside the ``one_chip`` fixture: only one
process at a time may load the TPU library, so describing it at import
would break the other test workers.
"""
import dataclasses
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.core import sweep
from repro.kernels import noc_step

HBM_BYTES = 16 * 2**30
_STATICS = ("cycles", "warmup", "starvation_limit", "backend",
            "strict_barrier", "watchdog")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A TPU executable written to the persistent cache cannot be read back
    # without a chip; keep these compiles out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(one_chip, exps):
    """Compile the batch program of ``exps``' first sweep group (the first
    experiment's topology) for the described chip."""
    spec = exps[0].topology
    cfgs = [e.sim_config() for e in exps if e.topology == spec]
    geom, groups = sweep._grouped(spec.build(), cfgs)
    key, _, points = groups[0]
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (geom, points))
    return sweep._run_batch.lower(
        *args, **dict(zip(_STATICS, key))).compile()


def _fits(compiled):
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.temp_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("family", ["ring_mesh", "flat_mesh"])
def test_statistical_grid_compiles_at_1024(one_chip, family):
    exps, _ = chip_smoke._phase_a(1024)
    exps = [e for e in exps if e.topology.family == family]
    assert len(exps) == 9
    assert _fits(_compile(one_chip, exps))


def test_trace_replay_compiles_at_1024(one_chip):
    exps, _ = chip_smoke._phase_b(1024)
    assert exps[0].sim_config().pattern.n_trace_phases > 0
    assert _fits(_compile(one_chip, exps))


def test_faulted_grid_compiles_at_1024(one_chip):
    exps, _ = chip_smoke._phase_c(1024)
    faulted = exps[1:]
    assert faulted[0].faults
    assert _fits(_compile(one_chip, faulted))


@pytest.mark.xfail(
    strict=True, raises=AssertionError,
    reason="Mosaic refuses cycle_step's integer gathers: the route "
           "take_along_axis trips `assert indices_aval.shape == "
           "in_aval.shape + (1,)` in _gather_lowering_rule "
           "(jax/_src/pallas/mosaic/lowering.py); Mosaic lowers only "
           "same-shape 2-D take_along_axis")
def test_fused_kernel_compiles_at_64(one_chip, monkeypatch):
    # Off the chip, run_fused would pick interpret mode; compile the
    # kernel itself through Mosaic.
    monkeypatch.setattr(noc_step, "default_interpret", lambda: False)
    exp = chip_smoke._phase_a(64)[0][0]
    exp = dataclasses.replace(
        exp, budget=dataclasses.replace(exp.budget, backend="pallas"))
    assert "tpu_custom_call" in _compile(one_chip, [exp]).as_text()
