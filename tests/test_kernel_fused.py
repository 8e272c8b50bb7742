"""Fused probe+paged-attention decode kernel (kernels/fused_decode).

Kernel level: the fused one-dispatch kernel must be BITWISE identical to
the two-dispatch baseline (materialized slots view -> paged-attention
kernel) it replaces — dense MHA / GQA / MQA, f32 / bf16, int8+scales,
and the unnormalized (o, m, l) partials contract.

Engine level: a serve step with ``cfg.fused_kernel=True`` must match the
two-dispatch step — gspmd AND the fully-manual shard_map region — and the
adversarial probe-run construction must exercise the probe kernel's
in-graph oracle fallback through ``rebuild_block_table(use_kernel=True)``
with bitwise-identical rows.

The whole file runs in interpret mode and under EITHER 1 or 8 fake
devices (CI kernels-interpret matrix): mesh-dependent tests size their
mesh from ``jax.device_count()``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import batched as BT
from repro.dist.sharding import serve_manual_rules
from repro.kernels import stats as KS
from repro.kernels.fused_decode import (block_table_slots_ref,
                                        fused_decode_ref,
                                        fused_paged_attention,
                                        merge_fused_partials)
from repro.kernels.probe import probe_lookup, resolved_fraction
from repro.launch.mesh import make_mesh
from repro.models.registry import get_model
from repro.serving import engine as EG
from repro.serving import page_table as PT


# ---------------------------------------------------------------------------
# Kernel-level bitwise parity.

def make_inputs(B, QH, KH, D, NP, PS, MP, dtype, seed=0, holes=False):
    """Random pools + a raw incremental-style block table: each sequence at
    position pos[b] owns distinct physical pages for logicals 0..pos//PS
    (optionally with stale entries past the horizon, as a real incremental
    cache can briefly hold — the kernel must mask them by position)."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, QH, D)).astype(np.float32)
    k = rng.standard_normal((NP, PS, KH, D)).astype(np.float32)
    v = rng.standard_normal((NP, PS, KH, D)).astype(np.float32)
    pos = rng.integers(0, MP * PS, size=B).astype(np.int32)
    perm = rng.permutation(NP)
    bt = np.full((B, MP), -1, np.int32)
    nxt = 0
    for b in range(B):
        last = pos[b] // PS
        for p in range(MP):
            if p <= last or (holes and rng.random() < 0.5):
                bt[b, p] = perm[nxt % NP]
                nxt += 1
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype), jnp.asarray(bt), jnp.asarray(pos))


SHAPES = [
    (2, 4, 4, 32, 16, 8, 4),     # dense MHA
    (2, 8, 2, 32, 16, 8, 4),     # GQA G=4
    (3, 4, 1, 16, 32, 4, 8),     # MQA, small pages
    (1, 4, 2, 64, 8, 16, 2),     # single lane, wide head
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_bitwise_vs_two_dispatch(shape, dtype):
    B, QH, KH, D, NP, PS, MP = shape
    q, k, v, bt, pos = make_inputs(B, QH, KH, D, NP, PS, MP, dtype,
                                   seed=sum(shape))
    out = fused_paged_attention(q, k, v, bt, pos, interpret=True)
    ref = fused_decode_ref(q, k, v, bt, pos, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_fused_bitwise_with_stale_rows():
    """Raw-table entries past the live horizon (and -1 holes) must be
    position-masked in-kernel exactly like the slots view masks them."""
    q, k, v, bt, pos = make_inputs(4, 4, 4, 32, 64, 8, 6, jnp.bfloat16,
                                   seed=3, holes=True)
    out = fused_paged_attention(q, k, v, bt, pos, interpret=True)
    ref = fused_decode_ref(q, k, v, bt, pos, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_fused_int8_scales_bitwise():
    B, QH, KH, D, NP, PS, MP = 2, 8, 2, 32, 16, 8, 4
    q, k, v, bt, pos = make_inputs(B, QH, KH, D, NP, PS, MP, jnp.float32,
                                   seed=11)
    rng = np.random.default_rng(7)
    k8 = jnp.asarray(rng.integers(-127, 128, k.shape), jnp.int8)
    v8 = jnp.asarray(rng.integers(-127, 128, v.shape), jnp.int8)
    scales = (jnp.asarray(rng.uniform(0.01, 0.2, (NP, PS, KH)),
                          jnp.bfloat16),
              jnp.asarray(rng.uniform(0.01, 0.2, (NP, PS, KH)),
                          jnp.bfloat16))
    out = fused_paged_attention(q.astype(jnp.bfloat16), k8, v8, bt, pos,
                                scales=scales, interpret=True)
    ref = fused_decode_ref(q.astype(jnp.bfloat16), k8, v8, bt, pos,
                           scales=scales, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_fused_partials_contract():
    """partials=True returns the unnormalized per-chip (o, m, l) triple:
    merging it must reproduce the normalized single-chip output."""
    B, QH, KH, D, NP, PS, MP = 2, 4, 2, 32, 16, 8, 4
    q, k, v, bt, pos = make_inputs(B, QH, KH, D, NP, PS, MP, jnp.float32,
                                   seed=21)
    o, m, l = fused_paged_attention(q, k, v, bt, pos, partials=True,
                                    interpret=True)
    assert o.shape == (B, KH, QH // KH, D) and o.dtype == jnp.float32
    assert m.shape == l.shape == (B, KH, QH // KH)
    merged = merge_fused_partials(o, m, l).reshape(B, QH, D)
    full = fused_paged_attention(q, k, v, bt, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full),
                               rtol=1e-6, atol=1e-6)


def test_slots_ref_matches_serving_view():
    """The kernel package's local duplicate of the slots math must equal
    serving/page_table.block_table_slots (drift here silently changes what
    'two-dispatch baseline' means)."""
    rng = np.random.default_rng(5)
    bt = jnp.asarray(rng.integers(-1, 64, (8, 16)), jnp.int32)
    pos = jnp.asarray(rng.integers(0, 16 * 8, 8), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(block_table_slots_ref(bt, pos, page_size=8)),
        np.asarray(PT.PageTable.block_table_slots(bt, pos,
                                                  page_size=8)))


def test_fused_byte_accounting():
    """Eager fused dispatch accounts bytes structurally: the raw table read
    (B·MP·4, no slot round trip) + only the LIVE fetched pages."""
    B, QH, KH, D, NP, PS, MP = 2, 4, 4, 32, 16, 8, 4
    q, k, v, bt, pos = make_inputs(B, QH, KH, D, NP, PS, MP, jnp.bfloat16,
                                   seed=2)
    live = np.arange(MP)[None, :] * PS <= np.asarray(pos)[:, None]
    fetched = int(np.sum(live & (np.asarray(bt) >= 0)))
    with KS.kernel_stats_scope() as st:
        fused_paged_attention(q, k, v, bt, pos, interpret=True)
        got = dict(st)           # read BEFORE exit: the scope restores
    assert got["probe_bytes"] == B * MP * 4
    assert got["attn_bytes"] == fetched * KH * PS * D * 4   # bf16 k+v


# ---------------------------------------------------------------------------
# Engine-level parity (gspmd + manual), 1 or 8 fake devices.

def _decode_parity(cfg0, rules, T=8, atol=1e-4):
    model = get_model(cfg0)
    params, _ = model.init(cfg0, jax.random.PRNGKey(0))
    B = 2
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                              cfg0.vocab_size)

    def run(cfg):
        state, _ = EG.make_decode_state(cfg, B, S_max=32, page_size=4,
                                        rules=rules)
        step = jax.jit(EG.make_serve_step(cfg, S_max=32, page_size=4,
                                          rules=rules))
        outs = []
        for t in range(T):
            pos = jnp.full((B,), t, jnp.int32)
            args = (params, state, toks[:, t:t + 1], pos)
            if cfg.family == "vlm":
                args += (jnp.full((3, B, 1), t, jnp.int32),)
            lg, state = step(*args)
            outs.append(np.asarray(lg))
        return np.stack(outs)

    fused_cfg = dataclasses.replace(cfg0, fused_kernel=True)
    assert EG._fused_kernel_ok(fused_cfg, rules), \
        EG._fused_kernel_reason(fused_cfg, rules)
    np.testing.assert_allclose(run(fused_cfg), run(cfg0), atol=atol,
                               rtol=1e-5)


@pytest.mark.parametrize("arch,over", [
    ("qwen2.5-32b", {}),                            # dense GQA
    ("granite-moe-1b-a400m", {}),                   # MoE
    ("gemma3-12b", {}),                             # local:global pattern
    ("qwen2.5-32b", {"kv_cache_dtype": "int8"}),    # quantized KV pool
    ("qwen2-vl-7b", {}),                            # vlm (mrope)
])
def test_engine_fused_matches_two_dispatch_gspmd(arch, over):
    cfg = dataclasses.replace(get_smoke_config(arch), **over)
    _decode_parity(cfg, rules=None)


def _manual_mesh():
    n = jax.device_count()
    shape = (2, n // 2) if n >= 2 else (1, 1)
    return make_mesh(shape, ("data", "model"),
                         devices=jax.devices()[:shape[0] * shape[1]])


@pytest.mark.parametrize("arch", ["qwen1.5-32b", "zamba2-1.2b"])
def test_engine_fused_matches_two_dispatch_manual(arch):
    """The fused kernel inside the fully-manual shard_map region (per-chip
    raw-block-table walk + lse merge over the page axes) vs the
    compact+attend two-dispatch region — whatever mesh the CI leg's device
    count allows (1x1 or 2x4)."""
    cfg = dataclasses.replace(get_smoke_config(arch), tp_impl="manual")
    rules = serve_manual_rules(_manual_mesh())
    assert EG._manual_decode_ok(cfg, rules)
    _decode_parity(cfg, rules=rules)


def test_fused_gate_reasons_never_silent():
    """Every non-fused outcome has a reason string; the families that
    cannot take the kernel are named, not dropped."""
    dense = get_smoke_config("qwen2.5-32b")
    assert "off" in EG._fused_kernel_reason(dense, None)
    on = dataclasses.replace(dense, fused_kernel=True)
    assert EG._fused_kernel_reason(on, None) is None
    ssm = dataclasses.replace(get_smoke_config("mamba2-2.7b"),
                              fused_kernel=True)
    assert "SSM" in EG._fused_kernel_reason(ssm, None)
    encdec = dataclasses.replace(get_smoke_config("seamless-m4t-large-v2"),
                                 fused_kernel=True)
    assert "cross-attention" in EG._fused_kernel_reason(encdec, None)


def test_fused_kernel_follows_the_platform(monkeypatch):
    """Unset, ``fused_kernel`` follows the one "on TPU" predicate that also
    picks the kernels' interpret mode: on a TPU the cells' qwen2.5-32b takes
    the kernel and mamba2-2.7b gives the SSM reason; elsewhere the jnp
    gather attends.  Forcing either way still works."""
    from repro import kernels as KN
    from repro.configs import get_config
    from repro.launch.serve import serve_rules_for
    qwen = dataclasses.replace(get_config("qwen2.5-32b"), num_layers=4,
                               pad_heads_to=0)
    mamba = get_config("mamba2-2.7b")
    assert qwen.fused_kernel is None and mamba.fused_kernel is None
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    rules = serve_rules_for(qwen, mesh)

    monkeypatch.setattr(KN, "on_tpu", lambda: True)
    assert EG.fallback_report(qwen, rules)["fused_kernel"] == "ok"
    assert EG.attention_path(qwen, rules) == "fused_decode_kernel"
    assert not EG._kernel_interpret()
    assert "SSM" in EG.fallback_report(mamba, rules)["fused_kernel"]
    assert EG.attention_path(mamba, rules) is None
    off = dataclasses.replace(qwen, fused_kernel=False)
    assert EG.fallback_report(off, rules)["fused_kernel"] == \
        "off (cfg.fused_kernel=False)"

    monkeypatch.setattr(KN, "on_tpu", lambda: False)
    reason = EG.fallback_report(qwen, rules)["fused_kernel"]
    assert "jnp gather" in reason, reason
    assert EG.attention_path(qwen, rules) == "jnp_gather"
    assert EG._kernel_interpret()
    on = dataclasses.replace(qwen, fused_kernel=True)
    assert EG.fallback_report(on, rules)["fused_kernel"] == "ok"


PS_CELL = 16


def _cell_heads_cfg():
    """qwen2.5-32b's attention (GQA 40/8, heads of 128) at a smoke width and
    depth; the cells' 16-token pages are passed by the caller."""
    return dataclasses.replace(get_smoke_config("qwen2.5-32b"),
                               num_heads=40, num_kv_heads=8, head_dim=128,
                               num_layers=2)


def _lane_story(cfg, params, *, K, on_megastep=None, S_max=64):
    """Drive one megastep program through the lane states the kernel must
    get right, and return every megastep's tokens and a final step's
    logits.  Lane 0 waits unseated (position 0, no pages), then is seated
    at position 0; lane 1 stops at 14, then resumes across the page
    boundary at 16 inside a megastep; lane 2 runs to 16 and is evicted (its
    block-table rows -1); lane 3 stops at 9 and stays, inactive, holding
    its pages.  ``on_megastep(p0, p1, seated, state)`` sees each one."""
    B, PS = 4, PS_CELL
    pt = PT.for_strategy("linear")
    mega = jax.jit(EG.make_serve_megastep(cfg, S_max=S_max, K=K,
                                          page_size=PS))
    state, _ = EG.make_decode_state(cfg, B, S_max=S_max, page_size=PS)
    tok = jax.random.randint(jax.random.PRNGKey(3), (B, 1), 0,
                             cfg.vocab_size)
    toks = []

    def run(steps, active, stop, seated):
        nonlocal state, tok
        state["active"] = jnp.asarray(active)
        for _ in range(steps // K):
            p0 = np.asarray(state["pos"])
            out, state = mega(params, state, tok,
                              jnp.asarray(stop, jnp.int32))
            tok = out[:, -1:]
            toks.append(np.asarray(out))
            if on_megastep is not None:
                on_megastep(p0, np.asarray(state["pos"]),
                            np.asarray(seated), state)

    run(16, [False, True, True, True], [S_max, 14, S_max, 9],
        [False, True, True, True])
    assert list(np.asarray(state["pos"])) == [0, 14, 16, 9]
    evict = jnp.asarray([False, False, True, False])
    state["table"] = pt.free_sequences(
        state["table"], state["seq_ids"], state["pos"], page_size=PS,
        max_pages=S_max // PS, active=evict)
    state["block_table"] = pt.invalidate_block_rows(state["block_table"],
                                                    evict)
    run(8, [True, True, False, False], [S_max, S_max, S_max, 9],
        [True, True, False, True])
    assert list(np.asarray(state["pos"])) == [8, 22, 16, 9]
    step = jax.jit(EG.make_serve_step(cfg, S_max=S_max, page_size=PS))
    logits, _ = step(params, state, tok, state["pos"])
    return np.concatenate(toks, axis=1), np.asarray(logits)


def test_engine_fused_matches_jnp_at_cell_heads():
    """The engine's fused path against the jnp gather at the cells' head
    shapes and pages, through a megastep: a lane at position 0, one that
    crosses a page boundary inside the megastep, an evicted lane with -1
    rows and an inactive lane holding pages."""
    cfg = _cell_heads_cfg()
    params, _ = get_model(cfg).init(cfg, jax.random.PRNGKey(0))
    toks_k, lg_k = _lane_story(dataclasses.replace(cfg, fused_kernel=True),
                               params, K=4)
    toks_j, lg_j = _lane_story(dataclasses.replace(cfg, fused_kernel=False),
                               params, K=4)
    np.testing.assert_array_equal(toks_k, toks_j)
    np.testing.assert_allclose(lg_k, lg_j, atol=1e-4, rtol=1e-5)


def test_live_pages_read_counts_the_kernels_walk():
    """``engine.live_pages_read`` over four-token megasteps equals the
    kernel's own page walk (``p·PS <= pos`` and a present block-table
    entry, after the step's allocation) counted one token at a time."""
    cfg = dataclasses.replace(_cell_heads_cfg(), fused_kernel=False)
    params, _ = get_model(cfg).init(cfg, jax.random.PRNGKey(0))
    walked, rounds = [], []

    def tally(p0, p1, seated, state):
        bt = np.asarray(state["block_table"])
        need = ((np.arange(bt.shape[1])[None, :] * PS_CELL <= p0[:, None])
                & (bt >= 0))
        walked.append(int(need.sum()))
        rounds.append((p0, p1, seated))

    _lane_story(cfg, params, K=1, on_megastep=tally)
    assert len(walked) == 24
    for r in range(0, 24, 4):
        p0, _, seated = rounds[r]
        p1 = rounds[r + 3][1]
        assert EG.live_pages_read(p0, p1, seated, 4, PS_CELL) == \
            sum(walked[r:r + 4]), r


# ---------------------------------------------------------------------------
# Satellite: adversarial probe-run fallback through the rebuild path.

def test_adversarial_rebuild_falls_back_bitwise():
    """A single giant probe run (filler keys clustered into one narrow hash
    band) extends past the probe kernel's resident window, so page keys
    deep in the run are UNRESOLVED by the fast path and must be served by
    the in-graph oracle — ``rebuild_block_table(use_kernel=True)`` must be
    bitwise-identical to the oracle rebuild, and a decode step from either
    rebuilt state must produce identical logits (gspmd and manual)."""
    m, TB, MP = 512, 256, 8
    table = BT.create(m, seed=5)
    rng = np.random.default_rng(12)

    # filler run: arbitrary uint32 keys whose hash lands in cells < 64
    cand = rng.choice(1 << 27, size=1 << 17, replace=False).astype(np.uint32)
    hv = np.asarray(BT._hash(table, jnp.asarray(cand)))
    filler = cand[hv < 64][:280]
    table, ret = BT.insert_batch(table, jnp.asarray(filler))
    assert not np.any(np.asarray(ret) == 2)

    # sequences with at least one page key hashing INTO the band — that
    # key's probe starts inside the ~280-cell run and must walk past the
    # kernel's resident window to its (late-inserted) cell
    seqs = []
    for s in range(4096):
        keys = PT.page_key(jnp.uint32(s), jnp.arange(MP, dtype=jnp.uint32))
        kh = np.asarray(BT._hash(table, keys))
        if (kh < 64).any():
            seqs.append(s)
        if len(seqs) == 8:
            break
    assert len(seqs) == 8, "rejection sampling found too few band seqs"
    seq_ids = jnp.asarray(seqs, jnp.uint32)
    page_keys = PT.page_key(seq_ids[:, None],
                            jnp.arange(MP, dtype=jnp.uint32)[None, :])
    table, ret = BT.insert_batch(table, page_keys.reshape(-1))
    assert not np.any(np.asarray(ret) == 2)

    # the construction is genuinely adversarial: the kernel fast path must
    # resolve SOME of the probed keys but not all of them
    frac = float(resolved_fraction(table, page_keys.reshape(-1), TB=TB,
                                   interpret=True))
    assert 0.0 < frac < 1.0, frac

    f_k, s_k = probe_lookup(table, page_keys.reshape(-1), TB=TB,
                            interpret=True)
    f_o, s_o = BT.find_batch(table, page_keys.reshape(-1))
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_o))
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_o))

    pt = PT.for_strategy("linear")
    bt_k = pt.rebuild_block_table(table, seq_ids, MP, use_kernel=True)
    bt_o = pt.rebuild_block_table(table, seq_ids, MP, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(bt_k), np.asarray(bt_o))


@pytest.mark.parametrize("mode", ["gspmd", "manual"])
def test_rebuild_use_kernel_identical_decode(mode):
    """Engine rebuild with the probe kernel vs the oracle: the rebuilt
    states are bitwise-identical, so the next decode step is too — checked
    end-to-end on both serve paths."""
    cfg = get_smoke_config("qwen1.5-32b")
    rules = None
    if mode == "manual":
        cfg = dataclasses.replace(cfg, tp_impl="manual")
        rules = serve_manual_rules(_manual_mesh())
        assert EG._manual_decode_ok(cfg, rules)
    model = get_model(cfg)
    params, _ = model.init(cfg, jax.random.PRNGKey(0))
    B = 2
    state, _ = EG.make_decode_state(cfg, B, S_max=32, page_size=4,
                                    rules=rules)
    step = jax.jit(EG.make_serve_step(cfg, S_max=32, page_size=4,
                                      rules=rules))
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, 6), 0,
                              cfg.vocab_size)
    for t in range(6):
        pos = jnp.full((B,), t, jnp.int32)
        _, state = step(params, state, toks[:, t:t + 1], pos)

    st_k = EG.rebuild_page_table(dict(state), use_kernel=True)
    st_o = EG.rebuild_page_table(dict(state), use_kernel=False)
    np.testing.assert_array_equal(np.asarray(st_k["block_table"]),
                                  np.asarray(st_o["block_table"]))
    pos = jnp.full((B,), 6, jnp.int32)
    lg_k, _ = step(params, st_k, toks[:, :1], pos)
    lg_o, _ = step(params, st_o, toks[:, :1], pos)
    np.testing.assert_array_equal(np.asarray(lg_k), np.asarray(lg_o))
