"""The time plane: host spans on the profiler's clock, named scopes in the
megastep, and the process's compile counter.

* ``span(name)`` is ``jax.profiler.TraceAnnotation("repro." + name)``.  With
  no trace active it costs about a microsecond; inside a
  ``jax.profiler`` trace it lands in the host plane on the same clock as
  the device's operations, so an idle gap on the device can be put down to
  the span the host was in.
* ``SCOPES`` are the ``jax.named_scope`` names the serving megastep carries
  (``serving/engine.py``).  They are metadata: the compiled program computes
  the same thing.  ``scope_map`` reads them back from a compiled program's
  HLO text, keyed by the instruction names a device trace gives its
  operations (``fusion.370``).
* ``COMPILE_STATS`` counts every backend compile in the process, from one
  ``jax.monitoring`` listener installed by ``listen_compiles``.

See ``obs/README.md`` ("The time plane") for the span and scope names.
"""
from __future__ import annotations

import functools
import re
import weakref
from collections import Counter
from typing import Callable, Dict, Optional

import jax

SPAN_PREFIX = "repro."

# the megastep's named scopes, one per layer of a decode step
SCOPES = ("embed", "allocator", "attn_proj", "kv_write", "attend", "mlp",
          "ssm", "state_freeze", "lm_head", "sampling")


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span ``repro.<name>`` on the profiler's clock."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


# -- named scopes -------------------------------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")


def scope_of(op_name: str) -> Optional[str]:
    """The innermost of ``SCOPES`` in an operation's ``op_name`` path
    (``jit(megastep)/while/body/attend/dot_general`` -> ``attend``)."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return None


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> scope, for every instruction of a compiled
    module's HLO text (``compiled.as_text()``) that has one.  A fusion whose
    own metadata names no scope takes the scope most of the instructions in
    its fused computation carry."""
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    inside: Dict[str, Counter] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        scope = scope_of(op.group(1)) if op else None
        own[name] = scope
        if scope is not None and comp is not None:
            inside.setdefault(comp, Counter())[scope] += 1
        c = _CALLS.search(rest)
        if c is not None:
            calls[name] = c.group(1)
    out = {n: s for n, s in own.items() if s is not None}
    for name, comp in calls.items():
        if name not in out and inside.get(comp):
            out[name] = inside[comp].most_common(1)[0][0]
    return out


def fresh_hlo_text(fun: Callable, args, **jit_kwargs) -> str:
    """The optimized HLO text of ``jax.jit(fun, **jit_kwargs)`` for
    ``args``, compiled afresh.  JAX's persistent cache keys a program
    without its metadata, so a cached entry written before the program had
    its named scopes would be found, and its text would carry none; and a
    new function object misses JAX's in-memory caches.  XLA names the
    instructions of one program alike in every compile, so the names are
    those a device trace of the running executable shows."""
    from jax.experimental.compilation_cache import compilation_cache
    lowered = jax.jit(functools.partial(fun), **jit_kwargs).lower(*args)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


# programs whose scope maps this process can give, by the name a device
# trace gives their module (``jit_<name>``); held weakly, so a batcher that
# is gone publishes nothing
_PROGRAMS: Dict[str, Callable[[], Callable[[], Dict[str, str]]]] = {}


def publish_scopes(program: str, method: Callable[[], Dict[str, str]]
                   ) -> None:
    """Let in-process trace readers find ``program``'s scope map through
    ``program_scopes``: ``method`` is a bound method that builds it."""
    _PROGRAMS[program] = weakref.WeakMethod(method)


def program_scopes(program: str) -> Optional[Dict[str, str]]:
    """The scope map of the live program last published as ``program``, or
    None where there is none."""
    ref = _PROGRAMS.get(program)
    method = ref() if ref is not None else None
    return None if method is None else method()


# -- compiles ---------------------------------------------------------------

COMPILE_STATS: Dict[str, float] = {
    "compiles": 0, "compile_s": 0.0, "trace_s": 0.0, "cache_hits": 0,
    "cache_writes": 0}

_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration")
_listening = False


def _on_duration(event, secs, **_):
    if event == _BACKEND_EVENT:
        COMPILE_STATS["compile_s"] += secs
        COMPILE_STATS["compiles"] += 1
    elif event in _TRACE_EVENTS:
        COMPILE_STATS["trace_s"] += secs


def _on_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        COMPILE_STATS["cache_hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        COMPILE_STATS["cache_writes"] += 1    # recorded as an entry is written


def listen_compiles() -> Dict[str, float]:
    """Start counting into ``COMPILE_STATS`` (once per process) and return
    it: ``compiles`` programs, ``compile_s`` seconds in the backend compiler
    (a persistent-cache hit counts its retrieval time instead), ``trace_s``
    seconds tracing and lowering to MLIR, and the persistent cache's hits
    and writes.  The counts are cumulative; difference two reads."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return COMPILE_STATS


def compile_stats() -> Dict[str, float]:
    """``compiles`` and ``compile_s`` so far: the registry's ``jit`` source
    (``jit_compiles``, ``jit_compile_s``)."""
    return {"compiles": COMPILE_STATS["compiles"],
            "compile_s": COMPILE_STATS["compile_s"]}
