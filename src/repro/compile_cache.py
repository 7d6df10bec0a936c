"""JAX's persistent compilation cache, placed from outside or at one fixed path.

    from repro import compile_cache
    compile_cache.enable()        # before the first compile
    ...
    compile_cache.stats()         # {"dir", "entries", "hits", "misses", ...}

Where ``JAX_COMPILATION_CACHE_DIR`` is set (JAX reads it into
``jax_compilation_cache_dir`` when it is imported), the cache lives there
and no other path is set here.  Otherwise it lives at ``<checkout>/.jax_cache``
(gitignored): a fixed path, never one built from a temporary name, a pid or
a time, so that a later run in the same checkout finds what this one
compiled.  A directory that cannot be created or written raises: a cache
that turns itself off in silence costs every later run its compile time.
"""
from __future__ import annotations

import os
import pathlib

import jax
from jax.experimental.compilation_cache import compilation_cache

from repro import obs

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"

_listening = False


def _on_event(event: str, **kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        obs.add("compile_cache.hits")
    elif event == "/jax/compilation_cache/cache_misses":
        obs.add("compile_cache.misses")


def _on_duration(event: str, secs: float, **kw) -> None:
    # XLA compilation, or loading the executable on a persistent-cache hit.
    if event == "/jax/core/compile/backend_compile_duration":
        obs.add("compile_cache.compile_s", secs)


def enable() -> str:
    """Turn the persistent cache on and return its directory."""
    global _listening
    outside = jax.config.jax_compilation_cache_dir
    d = outside or str(DEFAULT_DIR)
    os.makedirs(d, exist_ok=True)
    probe = os.path.join(d, ".write_probe")
    with open(probe, "w"):
        pass
    os.remove(probe)
    if not outside:
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_enable_compilation_cache", True)
    # Cache every executable, so the hit counters count every compile.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # JAX decides once per process whether the cache is in use; a process
    # that compiled before this call has already decided "no".
    compilation_cache.reset_cache()
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    return d


def stats() -> dict:
    """Cache directory, its entry count, and this process's persistent-cache
    hits and misses plus the seconds spent in XLA compilation (or cache
    loads) since it started: a view of ``obs``' ``compile_cache.*``
    counters."""
    d = jax.config.jax_compilation_cache_dir
    entries = len(os.listdir(d)) if d and os.path.isdir(d) else 0
    c = obs.snapshot()
    return {"dir": d, "entries": entries,
            "hits": int(c.get("compile_cache.hits", 0)),
            "misses": int(c.get("compile_cache.misses", 0)),
            "compile_s": float(c.get("compile_cache.compile_s", 0.0))}
