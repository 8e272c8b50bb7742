"""JAX's persistent compilation cache, and a meter for compile time.

Every entry point (``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``, ``chip_smoke.py``) calls ``enable_compile_cache``
once, before it compiles anything.  Tests never do.
"""
from __future__ import annotations

import contextlib
import os
import pathlib
from typing import Dict, Iterator

import jax

# <checkout>/.jax_cache (git-ignored).  A fixed path: it is part of each
# entry's key, so a directory named after a temp dir, a PID or the time
# would never hit.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

_TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration")
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set here; otherwise the cache is ``CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


@contextlib.contextmanager
def count_compiles() -> Iterator[Dict[str, float]]:
    """Count, while the block runs: ``compile_s``, seconds in the backend
    compiler (a persistent-cache hit counts its retrieval time instead),
    over ``compiles`` programs; ``trace_s``, seconds tracing and lowering
    to MLIR, which the cache does not save; and the persistent cache's
    hits and writes.  JAX neither reads nor writes the cache for a compile
    shorter than ``jax_persistent_cache_min_compile_time_secs``."""
    out = {"compile_s": 0.0, "compiles": 0, "trace_s": 0.0,
           "cache_hits": 0, "cache_writes": 0}

    def on_duration(event, secs, **_):
        if event == _BACKEND_EVENT:
            out["compile_s"] += secs
            out["compiles"] += 1
        elif event in _TRACE_EVENTS:
            out["trace_s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            out["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            out["cache_writes"] += 1     # recorded as an entry is written

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield out
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)
