"""Tests for the ``repro.dist`` subsystem: rules context nesting/restore,
``shard_act`` as identity outside a mesh, spec resolution (divisibility,
no mesh-axis reuse), TP block application matching the plain
``models.layers`` path numerically on CPU, and the compression/pipeline
helpers that do not need a multi-device mesh (those run in
``test_mesh.py``)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_smoke_config
from repro.dist import compression as COMP
from repro.dist import ctx
from repro.dist import pipeline as PL
from repro.dist import tp as TP
from repro.dist.sharding import ShardingRules, dp_rules, serve_manual_rules, \
    serve_rules, train_rules
from repro.launch.mesh import make_mesh
from repro.models import layers as L


def _mesh_1x1():
    return make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])


# ---------------------------------------------------------------------------
# ctx: nesting / restore / identity.

def test_use_rules_nesting_and_restore():
    assert ctx.current_rules() is None
    r1 = train_rules(_mesh_1x1())
    r2 = serve_rules(_mesh_1x1())
    with ctx.use_rules(r1):
        assert ctx.current_rules() is r1
        with ctx.use_rules(r2):
            assert ctx.current_rules() is r2
            # None explicitly clears (single-device code paths key on it)
            with ctx.use_rules(None):
                assert ctx.current_rules() is None
            assert ctx.current_rules() is r2
        assert ctx.current_rules() is r1
    assert ctx.current_rules() is None


def test_use_rules_restores_on_exception():
    r1 = train_rules(_mesh_1x1())
    with pytest.raises(RuntimeError):
        with ctx.use_rules(r1):
            raise RuntimeError("boom")
    assert ctx.current_rules() is None


def test_shard_act_identity_outside_mesh():
    x = jnp.ones((4, 8, 16))
    y = ctx.shard_act(x, ("batch", "seq", None))
    assert y is x            # no rules active -> exact identity, no op added


def test_shard_act_identity_when_spec_replicated():
    # 1x1 mesh: every mapping fails divisibility-or-size>1 -> replicated
    with ctx.use_rules(train_rules(_mesh_1x1())):
        x = jnp.ones((3, 5, 7))
        y = ctx.shard_act(x, ("batch", "seq", None))
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


# ---------------------------------------------------------------------------
# ShardingRules.spec resolution.

def _fake_mesh_rules():
    """Rules over an abstract 2x4 mesh (no devices needed for spec logic)."""
    mesh = jax.sharding.AbstractMesh((2, 4), ("data", "model"))
    return ShardingRules(mesh=mesh, rules={
        "batch": ("pod", "data"), "heads": ("model",), "kv": ("model",),
        "embed": ("data",), "vocab": ("model",),
    })


def test_spec_divisibility_gates_mapping():
    r = _fake_mesh_rules()
    # batch 6 % data 2 == 0 -> sharded; heads 6 % model 4 != 0 -> replicated
    assert r.spec(("batch", "heads"), (6, 6)) == P("data")
    assert r.spec(("batch", "heads"), (6, 8)) == P("data", "model")
    # absent mesh axis ("pod") is skipped silently
    assert r.spec(("batch",), (8,)) == P("data")


def test_spec_never_reuses_a_mesh_axis():
    r = _fake_mesh_rules()
    # heads and kv both want "model": first dim wins, second replicated
    assert r.spec(("heads", "kv"), (8, 8)) == P("model")


def test_spec_exclude_manual_axes():
    r = _fake_mesh_rules()
    assert r.spec(("batch", "heads"), (6, 8),
                  exclude=frozenset({"data"})) == P(None, "model")
    assert r.drop("model").spec(("heads",), (8,)) == P()


def test_axis_for_experts_contract():
    """models/moe.py keys expert parallelism off axis_for("experts", E)."""
    mesh = jax.sharding.AbstractMesh((2, 4), ("data", "model"))
    r = train_rules(mesh)
    assert r.axis_for("experts", 8) == "model"
    assert r.axis_for("experts", 6) is None        # 6 % 4 != 0
    assert dp_rules(mesh).axis_for("experts", 8) is None


def test_tree_shardings_handles_scalars_and_tuples():
    r = train_rules(_mesh_1x1())
    axes = {"w": ("embed", "heads"), "step": (), "nested": {"b": None}}
    sds = {"w": jax.ShapeDtypeStruct((4, 4), jnp.float32),
           "step": jax.ShapeDtypeStruct((), jnp.int32),
           "nested": {"b": jax.ShapeDtypeStruct((3,), jnp.float32)}}
    out = r.tree_shardings(axes, sds)
    assert out["step"].spec == P()
    assert out["nested"]["b"].spec == P()


# ---------------------------------------------------------------------------
# TP block application == plain layers path.

@pytest.mark.parametrize("tp_impl", ["gspmd", "manual"])
def test_block_apply_tp_matches_layers(tp_impl):
    cfg = dataclasses.replace(get_smoke_config("qwen2.5-32b"),
                              dtype="float32", tp_impl=tp_impl)
    key = jax.random.PRNGKey(0)
    p, _ = L.block_init(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, cfg.d_model))
    positions = jnp.arange(16)[None, :]
    ref = L.block_apply(p, x, positions, cfg)

    # outside any mesh: both impls must be the identical baseline path
    got = TP.block_apply_tp(cfg, p, x, positions)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    # under a 1-wide model axis the manual shard_map path is exercised but
    # must still match the un-TP'd reference numerically
    with ctx.use_rules(train_rules(_mesh_1x1())):
        got = jax.jit(lambda p, x: TP.block_apply_tp(cfg, p, x, positions))(
            p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_attn_apply_tp_matches_layers():
    from repro.models import nn
    cfg = dataclasses.replace(get_smoke_config("qwen2.5-32b"),
                              dtype="float32")
    key = jax.random.PRNGKey(0)
    p, _ = L.block_init(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, cfg.d_model))
    positions = jnp.arange(16)[None, :]
    ref = x + L.self_attention(p["attn"], nn.rmsnorm(p["ln1"], x),
                               positions, cfg)
    got = TP.attn_apply_tp(cfg, p, x, positions)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# Decode-side manual TP: gate + layout rules.

def test_decode_manual_tp_gate():
    """decode_manual_tp: tp_impl/mesh/divisibility gating, tp==1 allowed,
    refusal inside an enclosing manual region (serving/engine keys the fused
    decode region off this)."""
    mesh42 = jax.sharding.AbstractMesh((4, 2), ("data", "model"))
    mesh24 = jax.sharding.AbstractMesh((2, 4), ("data", "model"))
    dense = get_smoke_config("qwen2.5-32b")          # n_q=8, n_kv=2
    man = dataclasses.replace(dense, tp_impl="manual")
    assert TP.decode_manual_tp(dense, serve_manual_rules(mesh42)) == 0
    assert TP.decode_manual_tp(man, None) == 0
    assert TP.decode_manual_tp(man, serve_manual_rules(mesh42)) == 2
    # kv=2 on a 4-wide model axis: REPLICATED (rep=2), no longer a fallback
    assert TP.decode_manual_tp(man, serve_manual_rules(mesh24)) == 4
    assert TP.decode_kv_rep(man, 4) == 2
    assert TP.decode_kv_rep(man, 2) == 1
    # n_q must still divide, and kv must divide or be divided by tp
    assert TP.decode_manual_tp(
        dataclasses.replace(man, pad_heads_to=9),
        serve_manual_rules(mesh24)) == 0
    assert TP.decode_kv_rep(dataclasses.replace(man, pad_kv_to=3), 4) == 0
    assert TP.decode_manual_tp(
        dataclasses.replace(man, pad_kv_to=3), serve_manual_rules(mesh24)) == 0
    assert TP.decode_manual_tp(
        dataclasses.replace(man, d_ff=191), serve_manual_rules(mesh42)) == 0
    # every refusal has a loggable reason; applicability has none
    assert TP.decode_manual_unsupported(man, serve_manual_rules(mesh42)) is None
    assert "d_ff" in TP.decode_manual_unsupported(
        dataclasses.replace(man, d_ff=191), serve_manual_rules(mesh42))
    # tp == 1 still takes the fused path (single-device CPU coverage)
    assert TP.decode_manual_tp(man, serve_manual_rules(_mesh_1x1())) == 1
    # MoE gates on expert divisibility instead of d_ff
    moe = dataclasses.replace(get_smoke_config("granite-moe-1b-a400m"),
                              tp_impl="manual")
    assert TP.decode_manual_tp(moe, serve_manual_rules(mesh42)) == 2
    assert TP.decode_manual_tp(
        dataclasses.replace(moe, num_experts=3),
        serve_manual_rules(mesh42)) == 0
    # inside a region that already owns the model axis: refuse
    with ctx.manual_axes({"model"}):
        assert TP.decode_manual_tp(man, serve_manual_rules(mesh42)) == 0


def test_decode_ssm_tp_gate():
    """decode_ssm_tp: the hybrid mamba backbone shards its per-head dims
    over model iff B/C streams are shared (ssm_groups == 1) and the head
    count divides; tp == 1 passes for CPU coverage of the sharded path."""
    hyb = get_smoke_config("zamba2-1.2b")            # Hg=8, G=1, di=128
    assert TP.decode_ssm_tp(hyb, 1)
    assert TP.decode_ssm_tp(hyb, 2)
    assert TP.decode_ssm_tp(hyb, 4)
    assert TP.decode_ssm_tp(hyb, 8)
    assert not TP.decode_ssm_tp(hyb, 3)              # Hg % tp != 0
    assert not TP.decode_ssm_tp(hyb, 16)             # wider than Hg
    # grouped B/C (ssm_groups > 1): the head shard would split groups
    assert not TP.decode_ssm_tp(
        dataclasses.replace(hyb, ssm_groups=2), 2)
    # the full config shards on the 16-wide production model axis
    from repro.configs import get_config
    assert TP.decode_ssm_tp(get_config("zamba2-1.2b"), 16)
    # attention archs without SSM dims never pass
    assert not TP.decode_ssm_tp(get_smoke_config("qwen2.5-32b"), 2)
    # the sharded param specs cover exactly the mamba param set
    from repro.models import ssm as SSM
    p, _ = SSM.mamba_init(jax.random.PRNGKey(0), hyb, jnp.float32)
    assert set(TP._mamba_param_specs()) == set(p)


def test_serve_manual_rules_pool_layout():
    """The fused-decode layout: pages over (pod, data) only, KV heads over
    model — serve_manual_rules + POOL_AXES_TP must resolve to exactly that."""
    from repro.serving import paged
    mesh = jax.sharding.AbstractMesh((2, 4), ("data", "model"))
    r = serve_manual_rules(mesh)
    spec = r.spec(paged.POOL_AXES_TP, (2, 8, 4, 8, 16))
    assert spec == P(None, "data", None, "model")
    # baseline serve rules keep pages over every axis and heads unsharded
    spec0 = serve_rules(mesh).spec(paged.POOL_AXES, (2, 8, 4, 8, 16))
    assert spec0 == P(None, ("data", "model"))


# ---------------------------------------------------------------------------
# compression (single-process pieces; the psum path runs in test_mesh).

def test_compress_leaf_error_feedback_identity():
    g = jnp.asarray(np.random.default_rng(0).normal(size=(257,)),
                    jnp.float32)
    err = jnp.zeros_like(g)
    sent, err2 = COMP.compress_leaf(g, err)
    np.testing.assert_allclose(np.asarray(sent + err2), np.asarray(g),
                               atol=1e-6)


def test_compressed_bytes_counts_int8_payload():
    tree = {"a": jnp.zeros((10,)), "b": jnp.zeros((3, 4))}
    assert COMP.compressed_bytes(tree) == 10 + 4 + 12 + 4


# ---------------------------------------------------------------------------
# pipeline (single stage degenerates to sequential; S>1 runs in test_mesh).

def test_pipeline_single_stage_matches_sequential():
    mesh = make_mesh((1,), ("pod",), devices=jax.devices()[:1])

    class Cfg:
        num_layers = 4

    ws = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 8)) * 0.1

    def apply_range(w_stack, x):
        def body(x, w):
            return jnp.tanh(x @ w), None
        x, _ = jax.lax.scan(body, x, w_stack)
        return x

    fwd = PL.make_pipelined_forward(Cfg, mesh, apply_range, microbatches=2)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 3, 8))
    np.testing.assert_allclose(np.asarray(jax.jit(fwd)(ws, x)),
                               np.asarray(apply_range(ws, x)),
                               atol=1e-6, rtol=1e-6)
    assert PL.bubble_fraction(4, 4) == pytest.approx(3 / 7)


def test_pipeline_rejects_bad_partition():
    mesh = make_mesh((1,), ("pod",), devices=jax.devices()[:1])

    class Cfg:
        num_layers = 4

    fwd = PL.make_pipelined_forward(Cfg, mesh, lambda w, x: x,
                                    microbatches=3)
    with pytest.raises(ValueError):
        fwd(jnp.zeros((4, 2, 2)), jnp.zeros((4, 2)))   # 4 % 3 != 0


def test_elastic_host_loss_readmission():
    """dist/fault_tolerance.elastic_plan end-to-end: a host-group loss
    remeshes the survivors, the manifest reassigns the dead prefix ranges,
    and the per-shard schedulers re-admit every lost lane via recompute
    preemption — zero lost requests, table counters consistent."""
    import _multihost as MH
    from repro.dist import fault_tolerance as FT
    from repro.dist.table_shard import ShardManifest
    from repro.serving.sched import synthetic_workload

    cluster = MH.SimCluster(hosts=3, pages_per_shard=24, slots_per_shard=2,
                            page_size=4, max_len=16, megastep_k=4,
                            fail_on_abort=True)
    wl = synthetic_workload(9, vocab_size=64, max_len=16, seed=1,
                            prompt_len=(2, 4), max_new=(6, 10))
    cluster.router.submit_many(wl)
    for _ in range(3):
        cluster.run_round()
    lost_sid = cluster.spt.live_shards()[-1]
    victims_running = sum(
        1 for r in cluster.router.scheds[lost_sid].running())
    n_rehomed = cluster.lose_host(lost_sid)
    assert n_rehomed >= victims_running

    # (a) the surviving mesh and the reassigned manifest agree on the fleet
    new_man, shape, names = FT.elastic_table_plan(
        ShardManifest.balanced(3), lost_shard=lost_sid, model_parallel=16)
    assert len(new_man.live_shards()) == len(cluster.spt.live_shards()) == 2
    assert names == ("pod", "data", "model") and shape[0] == 2

    # (b) victims took the recompute-preemption transition
    rehomed = [r for sc in cluster.router.scheds.values()
               for r in list(sc.queue) + list(sc.running())
               if r.preemptions > 0]
    assert victims_running == 0 or rehomed

    # (c) the storm still drains with zero lost requests / zero aborts
    while not cluster.router.drained:
        assert cluster.rounds_run < 200
        cluster.run_round()
    cluster.verify()   # counters consistent (shadow census + per-shard)
    s = cluster.router.summary()
    assert int(s["completed"]) == int(s["submitted"]) == 9
    assert cluster.aborts == 0
