"""Host seconds of a ``run_experiments`` call outside its waits on the
device: the ``repro.run_experiments`` span's seconds less the
``repro.sweep.wait`` spans', over the calls (``repro.obs``).  Grouping,
points, geometry upload, result conversion and reports."""
from ringbench import registry


def read(ctx):
    c = registry.snapshot()
    if not c or not c.get("repro.run_experiments.n"):
        return None
    return ((c["repro.run_experiments.s"] - c.get("repro.sweep.wait.s", 0.0))
            / c["repro.run_experiments.n"])
