"""Device time of the sweep's batched step executable per simulated
point-cycle, in microseconds, over the traced window."""


def read(ctx):
    t, pc = ctx["trace"], ctx["window"]["point_cycles"]
    if not t or t["module_s"] <= 0 or not pc:
        return None
    return 1e6 * t["module_s"] / pc
