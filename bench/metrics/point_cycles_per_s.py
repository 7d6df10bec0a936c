"""Simulated point-cycles per second of wall time over the whole window:
every point-cycle of every call, over the time from the first call's
start to the last call's end."""


def read(ctx):
    w = ctx["window"]
    return w["point_cycles"] / w["seconds"] if w["calls"] else None
