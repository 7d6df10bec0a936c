"""Host spans and counters of the program, in one in-process registry.

    from repro import obs
    with obs.span("repro.sweep.wait", group=0):   # a host span
        ...
    obs.add("sweep.arb_passes_run", n)            # a counter
    obs.snapshot()   # {"repro.sweep.wait.s": 1.25, "repro.sweep.wait.n": 3,
                     #  "sweep.arb_passes_run": 8127, ...}
    obs.reset()      # everything; obs.reset("sweep.") one prefix

A span is a ``jax.profiler.TraceAnnotation``: under ``jax.profiler.trace``
it lands on the profiler's host plane, on the clock the device planes
share, with its ids as event stats.  It also adds its host seconds to
``<name>.s`` and one to ``<name>.n`` in the registry, traced or not.
Ids are inherited: a span, or an ``obs.tag`` block, passes its ids to
every span opened inside it on the same thread, and to work handed to
another thread through ``contextvars.copy_context().run``.

DESIGN.md §15 lists the spans and counters and what reads each.
"""
from __future__ import annotations

import contextlib
import contextvars
import threading
import time

import jax

_LOCK = threading.Lock()
_VALUES: dict[str, float] = {}
_IDS: contextvars.ContextVar[dict] = contextvars.ContextVar("obs_ids",
                                                            default={})


def add(name: str, n: float = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    with _LOCK:
        _VALUES[name] = _VALUES.get(name, 0) + n


@contextlib.contextmanager
def tag(**ids):
    """Give every span opened inside this block the ids ``ids``."""
    token = _IDS.set({**_IDS.get(), **ids})
    try:
        yield
    finally:
        _IDS.reset(token)


@contextlib.contextmanager
def span(name: str, **ids):
    """Time the block as the host span ``name``, with ``ids`` and the ids
    of the spans and tags around it."""
    with tag(**ids):
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name, **_IDS.get()):
                yield
        finally:
            dt = time.perf_counter() - t0
            with _LOCK:
                _VALUES[name + ".s"] = _VALUES.get(name + ".s", 0.0) + dt
                _VALUES[name + ".n"] = _VALUES.get(name + ".n", 0) + 1


def snapshot() -> dict[str, float]:
    """A copy of every counter and span total."""
    with _LOCK:
        return dict(_VALUES)


def reset(*prefixes: str) -> None:
    """Zero the entries whose names start with one of ``prefixes`` (every
    entry when none is given)."""
    with _LOCK:
        for k in list(_VALUES):
            if not prefixes or k.startswith(prefixes):
                del _VALUES[k]
