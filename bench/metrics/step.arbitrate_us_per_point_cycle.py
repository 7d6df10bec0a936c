"""Device self time of the step's ``cycle.arbitrate`` ops per simulated
point-cycle, in microseconds, over the traced window (``ringbench.stages``:
the ops of the sweep's batched executable, each by the stage its
metadata names)."""
from ringbench import stages


def read(ctx):
    return stages.us_per_point_cycle(ctx, "cycle.arbitrate")
