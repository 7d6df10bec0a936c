"""Arbitration passes the simulated points needed per point-cycle
(``sweep.arb_passes_needed`` over ``sweep.point_cycles`` in
``repro.obs``): each point's own passes, 1 to 24 a cycle.  Over
``step.arb_passes_run`` it is the share of the executed arbitration work
that some point needed."""
from ringbench import registry


def read(ctx):
    c = registry.snapshot()
    if not c or not c.get("sweep.point_cycles"):
        return None
    return c.get("sweep.arb_passes_needed", 0) / c["sweep.point_cycles"]
