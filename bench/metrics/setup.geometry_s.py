"""Seconds of set-up spent building the cell's topology and its device
geometry (``TopologySpec.build``, ``sim.build_geometry``), by the host
clock around the benchmark's own calls."""


def read(ctx):
    return ctx["spans"]["geometry_s"]
