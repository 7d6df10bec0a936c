"""Seconds the sweep spent tracing and lowering its batch programs
(``repro.sweep.lower`` spans in ``repro.obs``): set-up's precompile, and
nothing in the window when ``sweep.compiles_in_window`` reads 0."""
from ringbench import registry


def read(ctx):
    c = registry.snapshot()
    if c is None:
        return None
    return c.get("repro.sweep.lower.s", 0.0)
