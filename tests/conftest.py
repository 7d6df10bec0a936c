"""Test-suite bootstrap: puts ``src/`` and the checkout root (for
``chip_smoke.py``) on the import path."""
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)
