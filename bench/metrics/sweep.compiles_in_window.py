"""Batch executables the sweep compiled during the window (its exact
counter, ``sweep.compile_stats()["batch_xla_compiles"]``); 0 when set-up
loaded every shape the window uses."""


def read(ctx):
    return ctx["counters"]["compiles_in_window"]
