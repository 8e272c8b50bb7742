"""Serving tests: paged decode == full forward for every family; page-table
allocator invariants (tombstone reuse under eviction churn); engine state
plumbing; the fused manual-TP decode region on a 1-wide mesh."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.core import batched as BT
from repro.dist.sharding import serve_manual_rules
from repro.launch.mesh import make_mesh
from repro.models.registry import get_model
from repro.serving import engine as EG
from repro.serving import page_table as PT

LPT = PT.for_strategy("linear")  # the strategy-bound facade

DECODE_ARCHS = ["qwen2.5-32b", "qwen1.5-32b", "codeqwen1.5-7b",
                "granite-moe-1b-a400m", "qwen3-moe-235b-a22b",
                "gemma3-12b", "mamba2-2.7b", "zamba2-1.2b", "qwen2-vl-7b",
                "seamless-m4t-large-v2"]


def _fill_cross_kv(cfg, params, state, memory):
    def one_layer(lp):
        cp = lp["cross"]
        k = jnp.einsum("bsd,dhk->bshk", memory, cp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", memory, cp["wv"])
        if "bk" in cp:
            k, v = k + cp["bk"], v + cp["bv"]
        return k, v
    ck, cv = jax.vmap(one_layer)(params["decoder"])
    state["cross_k"], state["cross_v"] = ck, cv
    return state


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_decode_matches_forward(arch):
    cfg = get_smoke_config(arch)
    model = get_model(cfg)
    key = jax.random.PRNGKey(0)
    params, _ = model.init(cfg, key)
    B, T = 2, 12
    tokens = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
    kw = {}
    state, _ = EG.make_decode_state(cfg, B, S_max=64, page_size=8)
    if cfg.family == "vlm":
        kw["mrope_positions"] = jnp.broadcast_to(
            jnp.arange(T)[None, None], (3, B, T)).astype(jnp.int32)
    if cfg.family == "encdec":
        src = jax.random.normal(key, (B, 8, cfg.d_model),
                                cfg.activation_dtype())
        kw["src_embeds"] = src
        memory = model.encode(cfg, params, src)
        state = _fill_cross_kv(cfg, params, state, memory)
    ref, _ = model.forward(cfg, params, tokens, **kw)
    step = jax.jit(EG.make_serve_step(cfg, S_max=64, page_size=8))
    errs = []
    for t in range(T):
        pos = jnp.full((B,), t, jnp.int32)
        args = (params, state, tokens[:, t:t + 1], pos)
        if cfg.family == "vlm":
            args += (jnp.full((3, B, 1), t, jnp.int32),)
        logits, state = step(*args)
        errs.append(float(jnp.max(jnp.abs(
            logits - ref[:, t].astype(jnp.float32)))))
    assert max(errs) < 6e-2, (arch, errs)   # bf16 accumulation tolerance


def _mesh_1x1():
    return make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])


@pytest.mark.parametrize("arch", ["qwen2.5-32b", "granite-moe-1b-a400m",
                                  "qwen2-vl-7b", "gemma3-12b",
                                  "zamba2-1.2b"])
def test_manual_decode_single_device_matches_reference(arch):
    """``tp_impl="manual"`` on a 1-wide model axis routes through the fused
    manual shard_map region (decode_manual_tp deliberately allows tp == 1)
    and must match the no-rules single-device decode numerically."""
    cfg = dataclasses.replace(get_smoke_config(arch), tp_impl="manual")
    rules = serve_manual_rules(_mesh_1x1())
    assert EG._manual_decode_ok(cfg, rules)
    model = get_model(cfg)
    params, _ = model.init(cfg, jax.random.PRNGKey(0))
    B, T = 2, 6
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                              cfg.vocab_size)

    def run(r):
        state, _ = EG.make_decode_state(cfg, B, S_max=32, page_size=4,
                                        rules=r)
        step = jax.jit(EG.make_serve_step(cfg, S_max=32, page_size=4,
                                          rules=r))
        outs = []
        for t in range(T):
            pos = jnp.full((B,), t, jnp.int32)
            args = (params, state, toks[:, t:t + 1], pos)
            if cfg.family == "vlm":
                args += (jnp.full((3, B, 1), t, jnp.int32),)
            lg, state = step(*args)
            outs.append(np.asarray(lg))
        return np.stack(outs)

    np.testing.assert_allclose(run(rules), run(None), atol=5e-2, rtol=1e-2)


def test_manual_decode_gate_and_fallback_reasons():
    """After the universal fused decode, only genuinely unsupported shapes
    fall back (ssm: attention-free; encdec: cross-attn state) — and every
    fallback carries a loggable reason, never a silent swallow.  gemma3
    (local-window) and zamba2 (hybrid) now PASS the gate."""
    rules = serve_manual_rules(_mesh_1x1())
    gemma = dataclasses.replace(get_smoke_config("gemma3-12b"),
                                tp_impl="manual")
    assert gemma.pattern_local and EG._manual_decode_ok(gemma, rules)
    hybrid = dataclasses.replace(get_smoke_config("zamba2-1.2b"),
                                 tp_impl="manual")
    assert EG._manual_decode_ok(hybrid, rules)
    ssm = dataclasses.replace(get_smoke_config("mamba2-2.7b"),
                              tp_impl="manual")
    assert not EG._manual_decode_ok(ssm, rules)
    assert "SSM" in EG._manual_decode_reason(ssm, rules)
    encdec = dataclasses.replace(get_smoke_config("seamless-m4t-large-v2"),
                                 tp_impl="manual")
    assert not EG._manual_decode_ok(encdec, rules)
    assert "cross-attention" in EG._manual_decode_reason(encdec, rules)
    # gspmd impl never takes the fused path
    dense = get_smoke_config("qwen2.5-32b")
    assert not EG._manual_decode_ok(dense, rules)
    assert "manual" in EG._manual_decode_reason(dense, rules)


MEGA_CASES = [("qwen2.5-32b", {}), ("granite-moe-1b-a400m", {}),
              ("qwen2.5-32b", {"kv_cache_dtype": "int8"}),
              ("gemma3-12b", {}), ("zamba2-1.2b", {})]


def _drive_single(cfg, params, state, tok, step, K):
    """Reference driver: K jitted single steps + host-side greedy sampling
    (exactly what the megastep fuses in-graph)."""
    B = tok.shape[0]
    toks = []
    for _ in range(K):
        pos = state["pos"]
        args = (params, state, tok, pos)
        if cfg.family == "vlm":
            args += (jnp.broadcast_to(pos[None, :, None],
                                      (3, B, 1)).astype(jnp.int32),)
        logits, state = step(*args)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        tok = jnp.where(state["aborted"][:, None], tok, nxt)
        toks.append(np.asarray(tok[:, 0]))
    return np.stack(toks, axis=1), state


def _assert_state_bitwise(a, b):
    mism = [k for k in a
            if not all(jax.tree.leaves(jax.tree.map(
                lambda x, y: bool(np.array_equal(np.asarray(x),
                                                 np.asarray(y))),
                a[k], b[k])))]
    assert not mism, f"state leaves diverged: {mism}"


@pytest.mark.parametrize("arch,over", MEGA_CASES)
def test_megastep_matches_single_steps(arch, over):
    """K=8 megastep == 8 single steps, BITWISE: same greedy tokens, same
    final state (pools included) — the scan dispatch may not change a single
    bit of the decode."""
    cfg = dataclasses.replace(get_smoke_config(arch), **over)
    model = get_model(cfg)
    params, _ = model.init(cfg, jax.random.PRNGKey(0))
    B, K = 2, 8
    tok0 = jax.random.randint(jax.random.PRNGKey(1), (B, 1), 0,
                              cfg.vocab_size)
    state, _ = EG.make_decode_state(cfg, B, S_max=32, page_size=4)
    step = jax.jit(EG.make_serve_step(cfg, S_max=32, page_size=4))
    ref_toks, ref_state = _drive_single(cfg, params, dict(state), tok0,
                                        step, K)

    state2, _ = EG.make_decode_state(cfg, B, S_max=32, page_size=4)
    mega = jax.jit(EG.make_serve_megastep(cfg, S_max=32, K=K, page_size=4))
    mtoks, mstate = mega(params, state2, tok0)
    np.testing.assert_array_equal(np.asarray(mtoks), ref_toks)
    _assert_state_bitwise(ref_state, mstate)
    if "table" in mstate:
        assert int(LPT.verify_block_table(
            mstate["table"], mstate["seq_ids"], mstate["pos"],
            mstate["block_table"], page_size=4)) == 0


def test_megastep_abort_latch_and_resume():
    """Abort mid-megastep: the lane latches at the right token (pos frozen,
    pending token = the refused one, trailing outputs frozen), and after the
    §4.3 rebuild the next megastep re-issues the refused suffix — the full
    8-token stream matches a single-step driver that rebuilds and retries
    the moment the abort surfaces.  Also exercises the in-graph done latch
    (``stop_len``)."""
    cfg = get_smoke_config("qwen2.5-32b")
    model = get_model(cfg)
    params, _ = model.init(cfg, jax.random.PRNGKey(0))
    B, page_size, K = 2, 4, 8                          # S_max=8 -> maxP=2
    step = jax.jit(EG.make_serve_step(cfg, S_max=8, page_size=page_size))
    mega = jax.jit(EG.make_serve_megastep(cfg, S_max=8, K=K,
                                          page_size=page_size))
    state, _ = EG.make_decode_state(cfg, B, S_max=8, page_size=page_size)
    n_pages = state["pools"].k.shape[1]                # 6

    # shared prefix: fill 4 of 6 pages, then re-admit WITHOUT evicting
    # (stale pages stay live — the scenario slack cannot absorb)
    tok = jnp.zeros((B, 1), jnp.int32)
    for _ in range(8):
        logits, state = step(params, state, tok, state["pos"])
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    state = dict(state)
    state["seq_ids"] = state["seq_ids"] + B
    state["pos"] = jnp.zeros((B,), jnp.int32)
    tok0 = jnp.zeros((B, 1), jnp.int32)

    # PATH A: single steps, rebuild immediately when the abort surfaces
    stA, tokA, streamA, rebuildsA = dict(state), tok0, [], 0
    while len(streamA) < 8:
        logits, st2 = step(params, stA, tokA, stA["pos"])
        if bool(np.asarray(st2["aborted"]).any()):
            assert rebuildsA == 0
            stA = EG.rebuild_page_table(st2, n_pages=n_pages * 2)
            rebuildsA += 1
            continue                                   # re-issue, same pos
        tokA = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        streamA.append(np.asarray(tokA[:, 0]))
        stA = st2
    streamA = np.stack(streamA, axis=1)
    assert rebuildsA == 1

    # PATH B: one megastep aborts at token index 4 and latches
    toksB1, stB = mega(params, dict(state), tok0)
    assert np.asarray(stB["aborted"]).all(), "abort not latched"
    assert (np.asarray(stB["pos"]) == 4).all(), "latched at wrong token"
    t1 = np.asarray(toksB1)
    np.testing.assert_array_equal(                      # suffix frozen at
        t1[:, 4:], np.broadcast_to(t1[:, 3:4], (B, 4)))  # the refused token
    stB = EG.rebuild_page_table(stB, n_pages=n_pages * 2)
    assert not np.asarray(stB["aborted"]).any()
    # refused suffix re-issued: feed the pending token; stop_len latches the
    # lanes done in-graph at pos 8 (S_max) instead of overshooting
    toksB2, stB = mega(params, stB, toksB1[:, -1:],
                       jnp.full((B,), 8, jnp.int32))
    assert (np.asarray(stB["pos"]) == 8).all()
    assert not np.asarray(stB["active"]).any(), "done not latched in-graph"
    streamB = np.concatenate([t1[:, :4], np.asarray(toksB2)[:, :4]], axis=1)
    np.testing.assert_array_equal(streamB, streamA)


def test_block_table_evict_readmit_invalidation():
    """Evict -> re-admit must invalidate the cached block-table row: without
    invalidation the re-admitted slot would read a reclaimed physical page
    (stale slot); with it the cache stays coherent with the wait-free
    lookup at every step."""
    n_pages, B, page_size, maxP = 16, 2, 2, 4
    table = LPT.create_table(n_pages)
    seq = jnp.arange(B, dtype=jnp.int32)
    bt = jnp.full((B, maxP), -1, jnp.int32)
    for pos in range(6):
        (table, ws, ab), bt = LPT.alloc_step_incremental(
            table, seq, jnp.full((B,), pos, jnp.int32), bt,
            page_size=page_size)
        assert (np.asarray(ws) >= 0).all() and not np.asarray(ab).any()
    stale_row = np.asarray(bt[0]).copy()
    assert (stale_row[:3] >= 0).all()
    # evict lane 0; its pages become tombstones, immediately reclaimable
    table = LPT.free_sequences(table, seq, jnp.full((B,), 6, jnp.int32),
                              page_size=page_size, max_pages=maxP,
                              active=jnp.asarray([True, False]))
    bt = LPT.invalidate_block_rows(bt, jnp.asarray([True, False]))
    assert (np.asarray(bt[0]) == -1).all()
    assert (np.asarray(bt[1]) == np.asarray(
        LPT.rebuild_block_table(table, seq, maxP))[1]).all()
    # re-admit lane 0 with a fresh sequence id; had the stale row survived,
    # verify_block_table would flag it as soon as its pages went live
    seq = seq.at[0].set(B)
    stale_bt = bt.at[0].set(jnp.asarray(stale_row))
    for pos in range(6):
        p = jnp.full((B,), pos, jnp.int32)
        (table, ws, ab), bt = LPT.alloc_step_incremental(
            table, seq, p, bt, page_size=page_size)
        assert (np.asarray(ws) >= 0).all() and not np.asarray(ab).any()
        assert int(LPT.verify_block_table(table, seq, p, bt,
                                         page_size=page_size)) == 0
    # the hazard is real: the un-invalidated row disagrees with the lookup
    assert int(LPT.verify_block_table(
        table, seq, jnp.full((B,), 0, jnp.int32), stale_bt,
        page_size=page_size)) > 0


def test_block_table_matches_wait_free_lookup_under_churn():
    """CI verification mode under allocator churn (admit / decode / evict /
    reclaim): the incremental cache equals the authoritative wait-free
    lookup after every step, while probing ~page_size x fewer keys."""
    n_pages, B, page_size, maxP = 64, 4, 4, 8
    rng = np.random.default_rng(0)
    table = LPT.create_table(n_pages)
    seq = np.arange(B, dtype=np.int32)
    pos = np.zeros(B, np.int32)
    next_id = B
    bt = jnp.full((B, maxP), -1, jnp.int32)
    PT.probe_stats_reset()
    for round_ in range(40):
        (table, ws, ab), bt = LPT.alloc_step_incremental(
            table, jnp.asarray(seq), jnp.asarray(pos), bt,
            page_size=page_size)
        assert not np.asarray(ab).any()
        pos += 1
        assert int(LPT.verify_block_table(
            table, jnp.asarray(seq), jnp.asarray(pos - 1), bt,
            page_size=page_size)) == 0
        if round_ % 7 == 6:                 # evict a random lane, re-admit
            v = int(rng.integers(B))
            mask = np.zeros(B, bool)
            mask[v] = True
            table = LPT.free_sequences(
                table, jnp.asarray(seq), jnp.asarray(pos),
                page_size=page_size, max_pages=maxP,
                active=jnp.asarray(mask))
            bt = LPT.invalidate_block_rows(bt, jnp.asarray(mask))
            seq[v] = next_id
            next_id += 1
            pos[v] = 0
            bt = jnp.where(jnp.asarray(mask)[:, None],
                           LPT.rebuild_block_table(table, jnp.asarray(seq),
                                                  maxP), bt)
            assert int(LPT.verify_block_table(
                table, jnp.asarray(seq), jnp.asarray(pos), bt,
                page_size=page_size)) == 0


def test_batcher_megastep_churn():
    """End-to-end continuous batching on megasteps with the CI block-table
    verification enabled: evictions + re-admissions over several rounds,
    cache never diverges, one host sync per K tokens."""
    from repro.launch.serve import ContinuousBatcher
    cfg = get_smoke_config("qwen2.5-32b")
    model = get_model(cfg)
    params, _ = model.init(cfg, jax.random.PRNGKey(0))
    srv = ContinuousBatcher(cfg, params, batch=4, max_len=24, page_size=4,
                            megastep_k=4, verify_block_table=True)
    for _ in range(8):
        srv.decode_round(8)
    assert srv.evictions > 0
    st = srv.table_stats()
    assert int(st.live_pages) + int(st.tombstones) <= \
        srv.state["pools"].k.shape[1]
    # the proactive scheduler must keep the default (non-overcommitted)
    # pool out of ABORT entirely, and its per-round stats must carry the
    # scoped probe counter (PROBE_STATS lifecycle satellite)
    assert srv.sched.stats.aborts == 0
    assert len(srv.sched.rounds) == 16
    assert any(rs.keys_probed > 0 for rs in srv.sched.rounds)


def test_page_allocator_tombstone_reuse():
    """Evicted sequences' page slots are re-claimed in place: after heavy
    churn, live+tombstone occupancy stays bounded and allocation never
    aborts — the paper's Prop. 2 as a memory allocator."""
    n_pages = 64
    table = LPT.create_table(n_pages)
    page_size = 4
    maxP = 8
    rng = np.random.default_rng(0)
    active = {}   # seq_id -> position
    next_id = 0
    for round_ in range(30):
        # admit until ~75% pool
        while len(active) < 6:
            active[next_id] = 0
            next_id += 1
        seq = jnp.asarray(sorted(active), jnp.int32)
        pos = jnp.asarray([active[int(s)] for s in seq], jnp.int32)
        table, slots, aborted = LPT.alloc_step(table, seq, pos,
                                              page_size=page_size)
        assert (np.asarray(slots) >= 0).all(), "allocator aborted"
        assert not np.asarray(aborted).any()
        for s in np.asarray(seq):
            active[int(s)] += 1
        # evict sequences that got long
        done = [s for s, p in active.items() if p >= rng.integers(8, 24)]
        if done:
            dseq = jnp.asarray(done, jnp.int32)
            dpos = jnp.asarray([active[s] for s in done], jnp.int32)
            table = LPT.free_sequences(table, dseq, dpos,
                                      page_size=page_size, max_pages=maxP)
            for s in done:
                del active[s]
        assert int(table.num_keys) + int(table.num_tombs) <= n_pages
    # table survived 30 rounds of churn without rebuild
    # pages for a sequence at next-write position p: ceil(p / page_size)
    live = sum(-(-p // page_size) for p in active.values())
    assert int(table.num_keys) == live


def test_lookup_pages_consistency():
    table = LPT.create_table(32)
    seq = jnp.arange(3, dtype=jnp.int32)
    for pos in range(10):
        table, ws, _ = LPT.alloc_step(table, seq,
                                     jnp.full((3,), pos, jnp.int32),
                                     page_size=4)
    slots = LPT.lookup_pages(table, seq, jnp.full((3,), 9, jnp.int32),
                            page_size=4, max_pages=8)
    s = np.asarray(slots)
    assert (s[:, :3] >= 0).all()        # pages 0..2 live (pos 9 -> page 2)
    assert (s[:, 3:] == -1).all()       # beyond current position
    flat = s[s >= 0]
    assert len(set(flat.tolist())) == len(flat), "duplicate physical pages"


@settings(max_examples=20, deadline=None)
@given(psize=st.sampled_from([2, 4, 8]),
       steps=st.integers(1, 30),
       B=st.integers(1, 4))
def test_alloc_monotone_pages(psize, steps, B):
    """Each sequence owns exactly ceil(pos/psize) pages, all distinct."""
    n_pages = 256
    table = LPT.create_table(n_pages)
    seq = jnp.arange(B, dtype=jnp.int32)
    for pos in range(steps):
        table, _, _ = LPT.alloc_step(table, seq,
                                    jnp.full((B,), pos, jnp.int32),
                                    page_size=psize)
    expect = -(-steps // psize)
    assert int(table.num_keys) == B * expect
    slots = LPT.lookup_pages(table, seq, jnp.full((B,), steps - 1, jnp.int32),
                            page_size=psize, max_pages=64)
    s = np.asarray(slots)
    live = s[s >= 0]
    assert len(live) == B * expect
    assert len(set(live.tolist())) == len(live)


def test_page_pool_exhaustion_lifecycle():
    """Adversarial allocator lifecycle, under jit: fill the pool to
    exhaustion — the ABORT must be *surfaced* (aborted flag, write_slot
    refused as -1, never wrapped into a valid page) — then evict half the
    sequences and verify the very next alloc_steps re-claim the tombstoned
    slots (Proposition 2 operating as the allocator), with write_slot >= 0
    throughout the reclaim."""
    import functools
    n_pages, B, page_size = 16, 4, 2
    step = jax.jit(functools.partial(LPT.alloc_step, page_size=page_size))
    table = LPT.create_table(n_pages)
    seq = jnp.arange(B, dtype=jnp.int32)
    steps_to_fill = (n_pages // B) * page_size          # 8 -> pool full
    for pos in range(steps_to_fill):
        table, ws, ab = step(table, seq, jnp.full((B,), pos, jnp.int32))
        assert (np.asarray(ws) >= 0).all() and not np.asarray(ab).any()
    assert int(table.num_keys) == n_pages               # every cell live
    # the next boundary must ABORT on every lane — reported, not wrapped
    table, ws, ab = step(table, seq,
                         jnp.full((B,), steps_to_fill, jnp.int32))
    assert np.asarray(ab).all(), "abort not surfaced"
    assert (np.asarray(ws) == -1).all(), "wrapped write_slot"
    # evict half -> tombstones; freed slots are re-claimable IMMEDIATELY
    freed = np.asarray(LPT.lookup_pages(
        table, seq[:2], jnp.full((2,), steps_to_fill - 1, jnp.int32),
        page_size=page_size, max_pages=n_pages))
    table = LPT.free_sequences(table, seq[:2],
                              jnp.full((2,), steps_to_fill, jnp.int32),
                              page_size=page_size, max_pages=n_pages)
    assert int(table.num_tombs) == n_pages // 2
    fresh = jnp.arange(B, B + 2, dtype=jnp.int32)
    for pos in range(steps_to_fill):
        table, ws, ab = step(table, fresh, jnp.full((2,), pos, jnp.int32))
        assert (np.asarray(ws) >= 0).all(), "reclaim failed"
        assert not np.asarray(ab).any()
        if pos % page_size == 0:
            assert set(np.asarray(ws).tolist()) <= set(
                freed[freed >= 0].tolist()), "did not reuse tombstones"
    assert int(table.num_tombs) == 0                    # all reclaimed


def test_engine_abort_refusal_and_rebuild():
    """End-to-end §4.3: exhaust the pool (sequences re-admitted without
    eviction — the scenario page slack cannot absorb), verify the engine
    latches ``aborted`` and refuses the token (pos frozen, no silent
    wrap/drop), then ``rebuild_page_table`` into a larger pool (table
    re-hashed AND physical pages moved to the keys' new slots) and the
    retried step must match a big-pool reference run bit-for-nearly."""
    cfg = get_smoke_config("qwen2.5-32b")
    model = get_model(cfg)
    params, _ = model.init(cfg, jax.random.PRNGKey(0))
    B, page_size = 2, 4                                  # maxP = 2
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, 16), 0,
                                cfg.vocab_size)
    step = jax.jit(EG.make_serve_step(cfg, S_max=8, page_size=page_size))
    state, _ = EG.make_decode_state(cfg, B, S_max=8, page_size=page_size)
    n_pages = state["pools"].k.shape[1]                  # 6
    # big-pool reference with IDENTICAL maxP: rebuild (on a healthy state)
    # into 4x the pages — also covers rebuild without any abort
    ref_state = EG.rebuild_page_table(dict(state), n_pages=n_pages * 4)

    def both(t):
        nonlocal state, ref_state
        pos = jnp.full((B,), t, jnp.int32)
        lg, state = step(params, state, tokens[:, t:t + 1], pos)
        rlg, ref_state = step(params, ref_state, tokens[:, t:t + 1], pos)
        return np.asarray(lg), np.asarray(rlg)

    for t in range(8):                                   # 4 of 6 pages
        lg, rlg = both(t)
        np.testing.assert_allclose(lg, rlg, atol=2e-4, rtol=1e-4)
    assert not np.asarray(state["aborted"]).any()
    # re-admit both slots WITHOUT evicting (stale pages stay live)
    for s in (state, ref_state):
        s["seq_ids"] = s["seq_ids"] + B
        s["pos"] = jnp.zeros((B,), jnp.int32)
    lg, rlg = both(0)                                    # 6 of 6 pages
    np.testing.assert_allclose(lg, rlg, atol=2e-4, rtol=1e-4)
    for t in range(1, 4):
        lg, rlg = both(t)
    # t=4 page boundary: the small pool is full -> ABORT, token refused
    lg, rlg = both(4)
    assert np.asarray(state["aborted"]).all(), "abort not surfaced"
    assert (np.asarray(state["pos"]) == 4).all(), "token not refused"
    assert (np.asarray(ref_state["pos"]) == 5).all()
    # §4.3 rebuild: 2x pool, pages follow their keys; flags cleared
    state = EG.rebuild_page_table(state, n_pages=n_pages * 2)
    assert not np.asarray(state["aborted"]).any()
    assert state["pools"].k.shape[1] == n_pages * 2
    # retry the refused token against the reference's stored step, then
    # decode on in lockstep
    pos = jnp.full((B,), 4, jnp.int32)
    lg2, state = step(params, state, tokens[:, 4:5], pos)
    np.testing.assert_allclose(np.asarray(lg2), rlg, atol=2e-4, rtol=1e-4)
    assert (np.asarray(state["pos"]) == 5).all()
    for t in range(5, 7):
        lg, rlg = both(t)
        np.testing.assert_allclose(lg, rlg, atol=2e-4, rtol=1e-4)


def test_inactive_lanes_leak_no_pages():
    """Phantom-page fix: a finished (inactive) lane must stop allocating
    pages and its pos must freeze, while live lanes decode on."""
    cfg = get_smoke_config("qwen2.5-32b")
    model = get_model(cfg)
    params, _ = model.init(cfg, jax.random.PRNGKey(0))
    B, page_size = 4, 2
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, 12), 0,
                                cfg.vocab_size)
    step = jax.jit(EG.make_serve_step(cfg, S_max=32, page_size=page_size))
    state, _ = EG.make_decode_state(cfg, B, S_max=32, page_size=page_size)
    state["active"] = jnp.asarray([True, True, False, False])
    for t in range(8):
        pos = state["pos"]
        _, state = step(params, state, tokens[:, t:t + 1], pos)
    assert (np.asarray(state["pos"]) == [8, 8, 0, 0]).all()
    # only the two live lanes own pages: 8 steps @ page_size 2 -> 4 each
    assert int(state["table"].num_keys) == 2 * 4


def test_decode_state_after_eviction_reuse():
    """End-to-end: decode, evict, re-admit — logits of the new sequence are
    unaffected by the stale pages it reclaimed."""
    cfg = get_smoke_config("qwen2.5-32b")
    model = get_model(cfg)
    params, _ = model.init(cfg, jax.random.PRNGKey(0))
    B, T = 2, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                                cfg.vocab_size)
    step = jax.jit(EG.make_serve_step(cfg, S_max=32, page_size=4))

    # run seq ids (0,1) for T steps, evict, re-admit as (2,3), rerun
    state, _ = EG.make_decode_state(cfg, B, S_max=32, page_size=4)
    ref_logits = None
    for t in range(T):
        pos = jnp.full((B,), t, jnp.int32)
        logits, state = step(params, state, tokens[:, t:t + 1], pos)
        if ref_logits is None:
            ref_logits = logits
    state["table"] = LPT.free_sequences(
        state["table"], state["seq_ids"], jnp.full((B,), T, jnp.int32),
        page_size=4, max_pages=8)
    state["seq_ids"] = state["seq_ids"] + B
    logits2, _ = step(params, state, tokens[:, 0:1],
                      jnp.zeros((B,), jnp.int32))
    np.testing.assert_allclose(np.asarray(logits2), np.asarray(ref_logits),
                               atol=1e-4)
