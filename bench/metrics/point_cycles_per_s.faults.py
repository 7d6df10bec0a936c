"""``point_cycles_per_s`` in the fault cells: the same reading, beside
``point_cycles_per_s.faults``, which has its own bound."""
from ringbench import harness


def read(ctx):
    return harness.read_metric("point_cycles_per_s", ctx)
