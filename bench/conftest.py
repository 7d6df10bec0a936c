"""Test bootstrap for the benchmark's own tests: puts ``bench/`` (the
``ringbench`` package) and the program's ``src/`` on the import path."""
import os
import sys

_BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(_BENCH), "src"))
sys.path.insert(0, _BENCH)
