"""Seconds the program spent compiling, or loading executables from the
persistent cache, during set-up (``compile_cache.stats()["compile_s"]``,
from JAX's ``backend_compile_duration``)."""


def read(ctx):
    return ctx["counters"]["setup_compile_s"]
