"""The reader of ``step.arb_fanout_share``: the registry's fan-out
point-cycles over all point-cycles, silent on a program without them."""
import pytest

from ringbench import harness, registry

NAME = "step.arb_fanout_share"


@pytest.mark.parametrize("counters,want", [
    (None, None),                                     # no registry
    ({}, None),                                       # nothing dispatched
    ({"sweep.point_cycles": 900}, None),              # no such counter
    ({"sweep.point_cycles": 900, "sweep.arb_fanout_point_cycles": 0}, 0.0),
    ({"sweep.point_cycles": 800, "sweep.arb_fanout_point_cycles": 600},
     75.0),
    ({"sweep.point_cycles": 900, "sweep.arb_fanout_point_cycles": 900},
     100.0),
])
def test_reader(monkeypatch, counters, want):
    monkeypatch.setattr(registry, "snapshot", lambda: counters)
    assert harness.read_metric(NAME, {}) == want
