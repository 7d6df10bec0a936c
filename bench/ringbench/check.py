"""The comparison that decides ``correct``, and its control.

A point's counters (``delivered``, ``offered``, ``accepted``, ``dropped``,
``lost``, ``lat_sum``, ``moved``, ``in_flight``, ``phase_done``) are whole
numbers the configuration's semantics fix exactly, so the program's must
equal the plain reference's: the number compared is how many of them
differ, and its limit is 0.

The control breaks one guarantee the configuration states: it puts the
reference in the program's place with a single arbitration pass per
cycle (a blocked winner is not re-arbitrated), the shortcut a faster step
would be tempted by.  Its counters have to fail the comparison.
"""
from __future__ import annotations

from ringbench import reference, workload

CONTROL_PASSES = 1


def mismatches(got: dict, want: dict) -> int:
    return sum(got[k] != want[k] for k in want)


def control_reading(spec: dict, seed: int) -> int:
    """The number compared when the control stands in for the program on
    the points of a run with ``seed``."""
    config = spec["config"]
    return sum(mismatches(
        reference.counters(config, p, arbitration_passes=CONTROL_PASSES),
        reference.counters(config, p))
        for p in workload.points(config, spec["mix"], seed))
