"""Arbitration passes the sweep's dispatches ran per simulated point-cycle
(``sweep.arb_passes_run`` over ``sweep.point_cycles`` in ``repro.obs``).
A vmapped arbitration loop runs until the batch's slowest point
converges, so every point of a dispatch pays the batch's most passes."""
from ringbench import registry


def read(ctx):
    c = registry.snapshot()
    if not c or not c.get("sweep.point_cycles"):
        return None
    return c.get("sweep.arb_passes_run", 0) / c["sweep.point_cycles"]
