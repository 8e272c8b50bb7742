"""Distributed-execution layer above ``repro.core``.

The paper's process model is "n processes on shared memory"; on a TPU mesh
the analogue is "n chips on a sharded address space".  This package maps the
logical-axis annotations every model/layer carries (see ``models/nn.py``)
onto concrete mesh axes, and supplies the fault-tolerance scaffolding a
production deployment needs when chips stall or drop:

- ``ctx``             — active sharding-rule context + ``shard_act``
- ``sharding``        — ``ShardingRules`` and the train/serve/dp rule tables
- ``tp``              — tensor-parallel block application (gspmd | manual)
- ``compression``     — int8 gradient compression with error feedback
- ``fault_tolerance`` — watchdog, straggler monitor, elastic remeshing
- ``pipeline``        — GPipe-style pipeline parallelism over the pod axis
- ``compat``          — the shard_map facade (binds ``ctx.manual_axes``)

Submodules are imported lazily so that ``from repro.dist import ctx`` never
drags the model stack (``tp`` imports ``models.layers``) into lightweight
consumers like the checkpoint tooling.
"""
from __future__ import annotations

import importlib

_SUBMODULES = ("compat", "compression", "ctx", "fault_tolerance", "pipeline",
               "sharding", "tp")


def __getattr__(name):
    if name in _SUBMODULES:
        return importlib.import_module(f"{__name__}.{name}")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_SUBMODULES))
