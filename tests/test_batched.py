"""Tests for the TPU-native batched hash table (core/batched.py) and the
sharded DHT (core/sharded.py)."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import batched as BT
from repro.core import encoding as E
from repro.core.baselines import gao_noreuse as GN
from repro.core.spec import (OP_DELETE, OP_INSERT, OP_LOOKUP, RET_ABORT,
                             RET_FALSE, RET_TRUE, step_spec)


def spec_apply_grouped(state, ops, keys, m):
    """Reference: the documented linearization (deletes < inserts < lookups,
    each by batch index), with ABORT when the table genuinely has no space."""
    rets = [None] * len(ops)
    for grp in (OP_DELETE, OP_INSERT, OP_LOOKUP):
        for b, (o, k) in enumerate(zip(ops, keys)):
            if o != grp:
                continue
            if o == OP_INSERT and k not in state and len(state) >= m:
                rets[b] = RET_ABORT
                continue
            state, r = step_spec(state, o, k)
            rets[b] = r
    return state, rets


def table_keys(ht):
    tab = np.asarray(ht.table)
    keys = tab >> 2
    return set(int(k) for k in keys[keys != E.RESERVED_KEY])


@pytest.mark.parametrize("strategy", ["linear", "robinhood", "hopscotch"])
@pytest.mark.parametrize("claim_tombstones", [True, False])
def test_insert_lookup_delete_roundtrip(claim_tombstones, strategy):
    # strategy-parameterized: the ProbeStrategy refactor keeps one
    # observable contract (deeper conformance in test_probe_strategies.py)
    ht = BT.create(64, seed=1, strategy=strategy)
    keys = jnp.arange(10, dtype=jnp.uint32)
    ht, ret = BT.insert_batch(ht, keys, claim_tombstones=claim_tombstones,
                              strategy=strategy)
    assert np.all(np.asarray(ret) == RET_TRUE)
    assert np.all(np.asarray(BT.lookup_batch(ht, keys, strategy=strategy)))
    assert not np.any(np.asarray(BT.lookup_batch(
        ht, jnp.arange(100, 110, dtype=jnp.uint32), strategy=strategy)))
    ht, ret = BT.delete_batch(ht, keys[:5], strategy=strategy)
    assert np.all(np.asarray(ret) == 1)
    present = np.asarray(BT.lookup_batch(ht, keys, strategy=strategy))
    assert not np.any(present[:5]) and np.all(present[5:])
    assert int(ht.num_keys) == 5
    assert int(ht.num_tombs) == (5 if strategy != "hopscotch" else 0)


def test_duplicate_inserts_one_winner():
    """Batch-internal duplicate inserts: exactly one returns true — the
    batched analog of Lemma 4 / 'exactly one copy survives'."""
    ht = BT.create(16)
    keys = jnp.array([7, 7, 7, 7], dtype=jnp.uint32)
    ht, ret = BT.insert_batch(ht, keys)
    ret = np.asarray(ret)
    assert (ret == RET_TRUE).sum() == 1
    assert ret[0] == RET_TRUE  # lowest batch index wins (priority order)
    assert int(ht.num_keys) == 1
    tab = np.asarray(ht.table)
    assert ((tab >> 2) == 7).sum() == 1


def test_tombstone_reuse_vs_noreuse():
    """Churn in a small table: the paper's table reuses tombstones and never
    aborts; the no-reuse baseline fills with tombstones and aborts."""
    m = 8
    ht = BT.create(m)
    gn = GN.create(m)
    gn_aborted = False
    for t in range(m + 1):
        k = jnp.array([1000 + t], dtype=jnp.uint32)
        ht, r1 = BT.insert_batch(ht, k)
        assert int(r1[0]) == RET_TRUE, f"reuse table aborted at churn {t}"
        ht, r2 = BT.delete_batch(ht, k)
        assert int(r2[0]) == 1
        if not gn_aborted:
            gn, g1 = GN.insert_batch(gn, k)
            gn_aborted = int(g1[0]) == RET_ABORT
            if not gn_aborted:
                gn, _ = GN.delete_batch(gn, k)
    assert gn_aborted, "no-reuse baseline should abort under churn"
    assert bool(GN.needs_rebuild(gn, slack=0.9))


def test_abort_when_full_and_rebuild():
    m = 8
    ht = BT.create(m)
    ht, r = BT.insert_batch(ht, jnp.arange(m, dtype=jnp.uint32))
    assert np.all(np.asarray(r) == RET_TRUE)
    ht, r = BT.insert_batch(ht, jnp.array([99], dtype=jnp.uint32))
    assert int(r[0]) == RET_ABORT
    ht2 = BT.rebuild(ht, 32)
    assert table_keys(ht2) == set(range(m))
    ht2, r = BT.insert_batch(ht2, jnp.array([99], dtype=jnp.uint32))
    assert int(r[0]) == RET_TRUE


@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 2), st.integers(0, 9)),
                min_size=1, max_size=24),
       st.integers(0, 5))
def test_apply_batch_matches_spec(ops_keys, seed):
    """Property: apply_batch == the documented sequential serialization."""
    m = 16
    ht = BT.create(m, seed=seed)
    state = set()
    # split into a few batches
    rng = np.random.default_rng(seed)
    arr = np.array(ops_keys, dtype=np.int64)
    n_batches = rng.integers(1, 4)
    for chunk in np.array_split(arr, n_batches):
        if len(chunk) == 0:
            continue
        ops = jnp.asarray(chunk[:, 0], jnp.int32)
        keys = jnp.asarray(chunk[:, 1], jnp.uint32)
        ht, ret = BT.apply_batch(ht, ops, keys)
        state, expect = spec_apply_grouped(state, list(chunk[:, 0]),
                                           list(chunk[:, 1]), m)
        assert list(np.asarray(ret)) == expect, (chunk, state)
    assert table_keys(ht) == state


def test_no_holes_invariant():
    """Prop 3 analog: every stored key is reachable by probing from h(v)
    without crossing EMPTY (checked via lookup after heavy churn)."""
    rng = np.random.default_rng(0)
    m = 64
    ht = BT.create(m, seed=3)
    live = set()
    for _ in range(30):
        ks = rng.integers(0, 40, size=16).astype(np.uint32)
        ops = rng.integers(1, 3, size=16).astype(np.int32)
        ht, _ = BT.apply_batch(ht, jnp.asarray(ops), jnp.asarray(ks))
        for o, k in zip(ops, ks):
            state_set = live
            if o == OP_INSERT:
                state_set.add(int(k))
            elif o == OP_DELETE:
                state_set.discard(int(k))
    # NOTE: apply_batch order is deletes<inserts, so replay with same order:
    # instead of tracking exactly, just verify lookup self-consistency:
    assert table_keys(ht) == {int(k) for k in
                              np.asarray(jnp.arange(40, dtype=jnp.uint32))
                              [np.asarray(BT.lookup_batch(
                                  ht, jnp.arange(40, dtype=jnp.uint32)))]}


def test_counts_track_state():
    rng = np.random.default_rng(5)
    ht = BT.create(128, seed=2)
    for _ in range(10):
        ks = jnp.asarray(rng.integers(0, 60, size=32), jnp.uint32)
        ops = jnp.asarray(rng.integers(0, 3, size=32), jnp.int32)
        ht, _ = BT.apply_batch(ht, ops, ks)
    assert int(ht.num_keys) == len(table_keys(ht))
    tab = np.asarray(ht.table)
    assert int(ht.num_tombs) == int((tab == E.TOMBSTONE).sum())


SHARD_TEST = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, jax.numpy as jnp
from repro.core import sharded as SH
from repro.core.spec import OP_INSERT, OP_DELETE, OP_LOOKUP, step_spec
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("model",))
st, apply_fn = SH.make_sharded_table(mesh, "model", m_global=8 * 64,
                                     capacity=32, seed=0)
rng = np.random.default_rng(0)
state = set()
for it in range(6):
    B = 8 * 16
    ops = rng.integers(0, 3, size=B).astype(np.int32)
    keys = rng.integers(0, 200, size=B).astype(np.uint32)
    st, ret, ovf = apply_fn(st, jnp.asarray(ops), jnp.asarray(keys))
    ret = np.asarray(ret); ovf = np.asarray(ovf)
    assert not ovf.any(), "unexpected overflow"
    # reference: group by (shard, op-kind) — within one batch the DHT applies
    # deletes<inserts<lookups per shard; keys are single-owner so the global
    # order across shards is a valid interleaving. Verify per-key end state.
    for grp in (OP_DELETE, OP_INSERT, OP_LOOKUP):
        for b in range(B):
            if ops[b] != grp: continue
            state, r = step_spec(state, int(ops[b]), int(keys[b]))
            assert int(ret[b]) == r, (it, b, ops[b], keys[b], int(ret[b]), r)
print("SHARDED-OK")
"""


def test_sharded_dht_8dev():
    """Run the DHT on 8 forced host devices in a subprocess (keeps this
    process at 1 device)."""
    r = subprocess.run([sys.executable, "-c", SHARD_TEST],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"})
    assert "SHARDED-OK" in r.stdout, r.stdout + r.stderr
