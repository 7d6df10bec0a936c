"""The program's counter and span registry (``repro.obs``), for the metric
readers.  The benchmark's process calls the program only in its window
(set-up builds, lowers and compiles, and dispatches nothing), so the
registry's totals of dispatch counters and call spans are the window's.
None where the program has no registry."""
from __future__ import annotations


def snapshot() -> dict | None:
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.snapshot()
