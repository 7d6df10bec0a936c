"""The chip benchmark's harness: traffic generation, the plain reference,
the comparison that decides ``correct``, and the reduction from traces,
spans and counters to metrics (``bench/run.py`` drives it)."""
