"""On-device counter plane (tentpole a of the unified telemetry layer).

``Counters`` is a tiny pytree of scalar int32 leaves that rides INSIDE the
decode state dict (``state["counters"]``), so it flows through the megastep
``lax.scan`` like any other recurrent leaf and crosses the device boundary
exactly when the batcher already fetches ``state["pos"]`` — the once-per-K
host sync.  Telemetry therefore adds **zero extra device syncs**: the
counters are accumulated in-graph (a handful of scalar adds per token) and
read out for free in the post-dispatch host section.

The plane is guarded by ``cfg.telemetry`` with an identity fast path: when
the knob is off, ``make_decode_state`` never creates the leaf and every
update site keys on ``"counters" in state`` — the traced program is
*bitwise identical* to the un-instrumented one (pinned by
``tests/test_obs.py::test_telemetry_off_parity``).

Two planes share this schema:

* **device plane** — jnp scalars inside the engine state, updated by
  ``serving/engine`` (token body / serve step) and eagerly by the batcher
  between rounds (frees, rebuild events); and
* **host plane** — plain-int module counters (``HOST_COUNTERS``) for the
  eager paths that never enter a trace: ``dist/table_shard`` migration
  sweeps and the sharded simulator.  Same field names, so
  ``snapshot``/``delta`` work on either.
"""
from __future__ import annotations

import contextlib
from typing import Dict, NamedTuple

import jax.numpy as jnp


class Counters(NamedTuple):
    """Monotone event counts since state creation (scalar int32 each).

    ``snapshot`` them cumulatively and difference on the host; per-round
    rates are then exact even though the device only ever accumulates.
    """

    probe_steps: jnp.ndarray          # 2 per page crossing, not chain steps
    pages_allocated: jnp.ndarray      # page-boundary inserts that landed
    pages_freed: jnp.ndarray          # pages deleted on sequence free
    tombstones_created: jnp.ndarray   # deletes that left a TOMBSTONE
    tombstones_reclaimed: jnp.ndarray  # inserts that re-claimed a TOMBSTONE
    abort_events: jnp.ndarray         # lanes newly latched ABORT
    tokens_accepted: jnp.ndarray      # decode tokens committed (act lanes)
    migration_moved: jnp.ndarray      # entries moved by lazy-resize sweeps

    @classmethod
    def zeros(cls) -> "Counters":
        # one buffer per leaf: the serving megastep donates the state, and
        # a buffer shared by two leaves cannot be donated twice
        return cls(*(jnp.zeros((), jnp.int32) for _ in cls._fields))

    @classmethod
    def axes(cls) -> "Counters":
        """Per-leaf sharding axes, all replicated scalars — the
        ``make_decode_state`` axes-dict entry (HashTable ``num_keys``
        pattern)."""
        return cls(*([()] * len(cls._fields)))


def snapshot(c) -> Dict[str, int]:
    """Materialize a Counters (device or host plane) as a plain-int dict.
    On the device plane this is the ONLY transfer, done at the per-K sync."""
    return {f: int(v) for f, v in zip(Counters._fields, c)}


def delta(cur: Dict[str, int], prev: Dict[str, int]) -> Dict[str, int]:
    """Per-round rates from two cumulative snapshots."""
    return {k: cur[k] - prev.get(k, 0) for k in cur}


def update_token_counters(counters: Counters, *, act, aborts, positions,
                          page_size: int, table_before=None,
                          table_after=None) -> Counters:
    """One decode token's worth of in-graph accumulation.

    Called at the end of the serve step / token body with the pre- and
    post-alloc table (when the family is paged).  Derivations, not taps:
    ``need_new`` is recomputed from positions (a lane allocates exactly at
    page boundaries); ``probe_steps`` adds 2 per crossing, mirroring
    ``alloc_step_incremental``'s 2*need_new host-side note, so it counts
    crossings, not the steps of the probe chains; tombstone reclamation is
    the ``num_tombs`` drop across the insert (inserts only ever reclaim;
    deletes only ever create — so the sign splits the two counts).
    """
    act_i = act.astype(jnp.int32)
    ab_i = aborts.astype(jnp.int32)
    upd = {
        "abort_events": counters.abort_events + jnp.sum(ab_i),
        "tokens_accepted": counters.tokens_accepted
        + jnp.sum(act_i * (1 - ab_i)),
    }
    if table_before is not None and table_after is not None:
        need_new = ((positions % page_size) == 0).astype(jnp.int32) * act_i
        dk = (table_after.num_keys - table_before.num_keys).astype(jnp.int32)
        dt = (table_before.num_tombs - table_after.num_tombs).astype(
            jnp.int32)
        upd["probe_steps"] = counters.probe_steps + 2 * jnp.sum(need_new)
        upd["pages_allocated"] = counters.pages_allocated + dk
        upd["tombstones_reclaimed"] = (counters.tombstones_reclaimed
                                       + jnp.maximum(dt, 0))
    return counters._replace(**upd)


def note_free(counters: Counters, *, table_before, table_after) -> Counters:
    """Eager (between-round) accounting for ``free_sequences``: the key
    drop is pages freed, the tombstone rise is tombstones created."""
    dk = (table_before.num_keys - table_after.num_keys).astype(jnp.int32)
    dt = (table_after.num_tombs - table_before.num_tombs).astype(jnp.int32)
    return counters._replace(
        pages_freed=counters.pages_freed + jnp.maximum(dk, 0),
        tombstones_created=counters.tombstones_created + jnp.maximum(dt, 0))


# -- host plane -------------------------------------------------------------
#
# Module counters for eager code that has no device state to ride: the
# TableShard migration sweeps, simulator allocs, etc.  Mirrors the
# PROBE_STATS scope idiom so tests/benches can bracket a region.

HOST_COUNTERS: Dict[str, int] = {f: 0 for f in Counters._fields}


def note_host(field: str, n: int) -> None:
    HOST_COUNTERS[field] = HOST_COUNTERS.get(field, 0) + int(n)


@contextlib.contextmanager
def host_counters_scope():
    """Zero the host plane for the ``with`` body; restore (outer + body)
    afterwards so nesting composes additively."""
    outer = dict(HOST_COUNTERS)
    for k in HOST_COUNTERS:
        HOST_COUNTERS[k] = 0
    try:
        yield HOST_COUNTERS
    finally:
        body = dict(HOST_COUNTERS)
        for k in HOST_COUNTERS:
            HOST_COUNTERS[k] = outer.get(k, 0) + body.get(k, 0)
