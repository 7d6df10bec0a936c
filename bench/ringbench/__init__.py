"""The chip benchmark's harness: traffic generation, the plain reference,
the comparison that decides ``correct``, and the reduction from traces,
spans and counters to metrics (``bench/run.py`` drives it)."""

import importlib.util
import os


def load_named(directory: str, name: str):
    """The module ``<directory>/<name>.py``: a metric's reader or a traffic
    generator, found by the name ``BENCHMARK.json`` or a traffic file
    gives."""
    spec = importlib.util.spec_from_file_location(
        f"{os.path.basename(directory)}_{name.replace('.', '_')}",
        os.path.join(directory, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
