"""The readers of ``sweep.dispatches_per_call`` and its fault-cell twin:
the registry's dispatches over its ``run_experiments`` calls, silent on a
program without the counter."""
import pytest

from ringbench import harness, registry


@pytest.mark.parametrize("name", ["sweep.dispatches_per_call",
                                  "sweep.dispatches_per_call.faults"])
@pytest.mark.parametrize("counters,want", [
    (None, None),                                     # no registry
    ({}, None),                                       # nothing called
    ({"repro.run_experiments.n": 2}, None),           # no such counter
    ({"sweep.dispatches": 3}, None),                  # no call counted
    ({"repro.run_experiments.n": 2, "sweep.dispatches": 4}, 2.0),
    ({"repro.run_experiments.n": 3, "sweep.dispatches": 3}, 1.0),
])
def test_reader(monkeypatch, name, counters, want):
    monkeypatch.setattr(registry, "snapshot", lambda: counters)
    assert harness.read_metric(name, {}) == want
