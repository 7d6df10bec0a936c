"""Device dispatches the sweep made per ``run_experiments`` call:
``sweep.dispatches`` over ``repro.run_experiments.n`` (``repro.obs``).  One
where every point of a call stacks into one batch.  Nothing where the
program has no such counter."""
from ringbench import registry


def read(ctx):
    c = registry.snapshot()
    if (not c or not c.get("repro.run_experiments.n")
            or "sweep.dispatches" not in c):
        return None
    return c["sweep.dispatches"] / c["repro.run_experiments.n"]
