"""Seconds from process start to the first measured call: JAX and the
TPU coming up, the points, the fabric and its geometry, and the
executables loaded from the persistent cache (or compiled)."""


def read(ctx):
    return ctx["setup_s"]
