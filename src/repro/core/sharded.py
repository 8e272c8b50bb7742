"""Mesh-sharded distributed hash table (DHT).

The table is hash-partitioned across one mesh axis (usually ``model``):
shard-of-key is a hash of the key, independent of the within-shard probe
hash.  Operations are routed to the owning shard with the MoE-dispatch
pattern — capacity-bounded bucketing + ``jax.lax.all_to_all`` — applied
locally with the batched engine (scatter-min arbitration, tombstone reuse),
and results are routed back.  This is the paper's "shared memory accessed by
n processes" reshaped for a TPU mesh: chips are the processes, the ICI
all-to-all is the interconnect, and per-shard batch application provides the
same linearizable per-key semantics because every key has a single owner
shard (single-owner ⇒ per-key operations serialize at the owner — the
distributed analog of the paper's per-cell atomicity).

All functions here are designed to be called INSIDE ``shard_map`` (they use
``axis_name`` collectives); ``make_sharded_table`` builds the jitted
outer functions for a given mesh.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import batched as BT
from repro.core import encoding as E
from repro.core import hashing as H
from repro.core.spec import OP_LOOKUP
from repro.dist.compat import shard_map

SHARD_SEED = 0x5EED


class ShardedTable(NamedTuple):
    """Global view: leaves sharded over the table axis."""
    table: jnp.ndarray      # uint32[S, m_local]
    num_keys: jnp.ndarray   # int32[S]
    num_tombs: jnp.ndarray  # int32[S]
    seed: jnp.ndarray       # int32[S]


def create_sharded(num_shards: int, m_local: int, seed: int = 0) -> ShardedTable:
    return ShardedTable(
        table=jnp.full((num_shards, m_local), E.EMPTY, dtype=jnp.uint32),
        num_keys=jnp.zeros((num_shards,), jnp.int32),
        num_tombs=jnp.zeros((num_shards,), jnp.int32),
        seed=jnp.full((num_shards,), seed, jnp.int32),
    )


def shard_of(keys, num_shards: int):
    """Owner shard of each key (independent hash from the probe hash)."""
    return H.hash_keys(jnp.asarray(keys, jnp.uint32), num_shards, SHARD_SEED)


def _local_view(st: ShardedTable) -> BT.HashTable:
    """Per-device view inside shard_map: leading shard dim of size 1."""
    return BT.HashTable(table=st.table[0], num_keys=st.num_keys[0],
                        num_tombs=st.num_tombs[0], seed=st.seed[0],
                        meta=jnp.zeros((0,), jnp.uint32))


def _pack_local(ht: BT.HashTable) -> ShardedTable:
    return ShardedTable(table=ht.table[None], num_keys=ht.num_keys[None],
                        num_tombs=ht.num_tombs[None], seed=ht.seed[None])


def routed_apply(st_local: ShardedTable, ops, keys, *, axis_name: str,
                 capacity: int):
    """INSIDE shard_map: apply (ops, keys) of this device's local request
    batch to the distributed table.

    Returns (st_local', ret int32[B], overflowed bool[B]).  Overflowed
    requests (more than ``capacity`` requests from this device to one shard)
    are not applied and return -1; callers retry them in the next batch
    (production note: capacity is sized so overflow is statistically rare,
    like MoE expert capacity).
    """
    ops = jnp.asarray(ops, jnp.int32)
    keys = jnp.asarray(keys, jnp.uint32)
    B = keys.shape[0]
    S = jax.lax.axis_size(axis_name)

    dest = shard_of(keys, S)                              # [B]
    # position of each request within its destination bucket
    onehot = jax.nn.one_hot(dest, S, dtype=jnp.int32)     # [B, S]
    pos_in_bucket = (jnp.cumsum(onehot, axis=0) - 1)      # [B, S]
    pos = jnp.take_along_axis(pos_in_bucket, dest[:, None], axis=1)[:, 0]
    ok = pos < capacity
    flat = dest * capacity + pos                          # [B]
    flat = jnp.where(ok, flat, S * capacity)              # OOB -> drop

    send_keys = jnp.full((S * capacity,), E.MAX_KEY, jnp.uint32)
    send_keys = send_keys.at[flat].set(keys, mode="drop")
    send_ops = jnp.full((S * capacity,), OP_LOOKUP, jnp.int32)
    send_ops = send_ops.at[flat].set(ops, mode="drop")
    send_act = jnp.zeros((S * capacity,), bool).at[flat].set(ok, mode="drop")

    # exchange: chunk s of my buffer goes to shard s (tiled all_to_all over
    # the flat [S*capacity] layout — the MoE dispatch idiom)
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name,
                            split_axis=0, concat_axis=0, tiled=True)
    rk = a2a(send_keys)
    rop = a2a(send_ops)
    ract = a2a(send_act.astype(jnp.int32)) > 0

    ht = _local_view(st_local)
    from repro.core.spec import OP_DELETE, OP_INSERT
    ht, del_ret = BT.delete_batch(ht, rk, active=ract & (rop == OP_DELETE))
    ht, ins_ret = BT.insert_batch(ht, rk, active=ract & (rop == OP_INSERT))
    look_ret = BT.lookup_batch(ht, rk).astype(jnp.int32)
    rret = jnp.where(rop == OP_DELETE, del_ret,
                     jnp.where(rop == OP_INSERT, ins_ret, look_ret))
    rret = jnp.where(ract, rret, -1)

    # route results back
    back = a2a(rret)
    safe_flat = jnp.where(ok, flat, 0)
    ret = jnp.where(ok, back[safe_flat], -1)
    return _pack_local(ht), ret, ~ok


def make_sharded_table(mesh: Mesh, axis: str, m_global: int,
                       capacity: int, seed: int = 0):
    """Build (state, apply_fn) for a DHT sharded over ``mesh[axis]``.

    ``apply_fn(state, ops, keys)``: ops/keys are [S*B_local] arrays sharded
    over ``axis``; returns (state', ret, overflow).
    """
    S = mesh.shape[axis]
    assert m_global % S == 0
    m_local = m_global // S
    st = create_sharded(S, m_local, seed)

    table_spec = ShardedTable(P(axis, None), P(axis), P(axis), P(axis))
    st = jax.device_put(st, jax.tree.map(
        lambda s: NamedSharding(mesh, s), table_spec,
        is_leaf=lambda x: isinstance(x, P)))

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(table_spec, P(axis), P(axis)),
        out_specs=(table_spec, P(axis), P(axis)),
        check_vma=False)
    def _apply(st_local, ops, keys):
        st2, ret, ovf = routed_apply(st_local, ops, keys, axis_name=axis,
                                     capacity=capacity)
        return st2, ret, ovf

    def apply_fn(state, ops, keys):
        return jax.jit(_apply)(state, ops, keys)

    return st, apply_fn
