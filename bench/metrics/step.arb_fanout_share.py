"""Share of the simulated point-cycles whose dispatch read each queue's
arbitration target through the static fan-out table, in percent
(``sweep.arb_fanout_point_cycles`` over ``sweep.point_cycles`` in
``repro.obs``).  Nothing where the program has no such counter."""
from ringbench import registry


def read(ctx):
    c = registry.snapshot()
    if (not c or not c.get("sweep.point_cycles")
            or "sweep.arb_fanout_point_cycles" not in c):
        return None
    return 100.0 * c["sweep.arb_fanout_point_cycles"] / c["sweep.point_cycles"]
