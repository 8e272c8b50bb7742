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

from repro import obs as OBS

# <checkout>/.jax_cache (git-ignored).  A fixed path: it is part of each
# entry's key, so a directory named after a temp dir, a PID or the time
# would never hit.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


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
    shorter than ``jax_persistent_cache_min_compile_time_secs``.  The
    counts come from the process's one listener (``obs.listen_compiles``)
    and fill the yielded dict when the block ends."""
    stats = OBS.listen_compiles()
    before = dict(stats)
    out = {k: 0 for k in stats}
    try:
        yield out
    finally:
        out.update({k: stats[k] - before[k] for k in stats})
