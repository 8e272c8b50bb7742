"""Multi-device tests (8 fake CPU devices via subprocess — the main pytest
process stays single-device per the dry-run isolation rule).

Covers the real shard_map paths: MoE dispatch, paged attention, the
compressed manual-pod train step, GPipe pipeline, and elastic restore onto a
different mesh."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(script: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n}")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


COMMON = """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
assert len(jax.devices()) == 8, jax.devices()
"""


def test_moe_sharded_matches_single():
    run_with_devices(COMMON + """
from repro.configs import get_smoke_config
from repro.dist import ctx
from repro.dist.sharding import train_rules
from repro.models import moe as MOE
cfg = get_smoke_config("granite-moe-1b-a400m")   # 4 experts
mesh = make_mesh((2, 4), ("data", "model"))
key = jax.random.PRNGKey(0)
p, a = MOE.moe_init(key, cfg, jnp.float32)
x = jax.random.normal(key, (4, 8, cfg.d_model), jnp.float32)
y0, aux0 = MOE.moe_apply(p, x, cfg)                       # single-shard path
with ctx.use_rules(train_rules(mesh)):
    y1, aux1 = jax.jit(lambda p, x: MOE.moe_apply(p, x, cfg))(p, x)
np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=2e-5,
                           rtol=1e-4)
print("moe sharded == single OK")
""")


def test_paged_decode_sharded_matches_single():
    run_with_devices(COMMON + """
from repro.configs import get_smoke_config
from repro.dist.sharding import serve_rules
from repro.models.registry import get_model
from repro.serving import engine as EG
cfg = get_smoke_config("qwen2.5-32b")   # 8 q heads, kv 2
mesh = make_mesh((2, 4), ("data", "model"))
rules = serve_rules(mesh)
model = get_model(cfg)
params, _ = model.init(cfg, jax.random.PRNGKey(0))
B, T = 2, 10
toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)

def run(rules):
    state, _ = EG.make_decode_state(cfg, B, S_max=32, page_size=4,
                                    rules=rules)
    step = jax.jit(EG.make_serve_step(cfg, S_max=32, page_size=4,
                                      rules=rules))
    outs = []
    for t in range(T):
        pos = jnp.full((B,), t, jnp.int32)
        lg, state = step(params, state, toks[:, t:t+1], pos)
        outs.append(np.asarray(lg))
    return np.stack(outs)

ref = run(None)
shd = run(rules)
np.testing.assert_allclose(shd, ref, atol=5e-2, rtol=1e-2)
print("paged decode sharded == single OK, maxerr",
      float(np.abs(shd - ref).max()))
""")


def test_manual_pod_compressed_step():
    run_with_devices(COMMON + """
from repro.configs import get_smoke_config
from repro.dist.sharding import train_rules
from repro.training import train_step as TS
from repro.training import data as D
cfg = get_smoke_config("codeqwen1.5-7b")
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
rules = train_rules(mesh)
state, axes = TS.init_state(cfg, jax.random.PRNGKey(0))
err = TS.init_pod_error_buffers(state.params, 2)
step = TS.make_train_step_manual_pod(cfg, mesh, rules=rules)
b = D.synth_batch(cfg, batch=4, seq_len=16, step=0)
state2, err2, metrics = jax.jit(step)(state, err, b)
assert np.isfinite(float(metrics["loss"])), metrics
# compare against the plain GSPMD step on the same batch: compressed-DP
# loss must match exactly (loss is computed before any compression)
plain = TS.make_train_step(cfg, rules=None)
_, m2 = jax.jit(plain)(state, b)
# bf16 graphs differ (pod-sharded batch order, compressed grads touch the
# metrics only post-loss): loss agrees to bf16 noise
np.testing.assert_allclose(float(metrics["loss"]), float(m2["loss"]),
                           rtol=2e-3)
print("manual-pod compressed step OK, loss", float(metrics["loss"]))
""")


def test_pipeline_matches_sequential():
    run_with_devices(COMMON + """
from repro.dist import pipeline as PL
mesh = make_mesh((4, 2), ("pod", "data"))
L, d = 8, 16
key = jax.random.PRNGKey(0)
ws = jax.random.normal(key, (L, d, d)) * 0.1

class Cfg: num_layers = L
def apply_range(w_stack, x):
    def body(x, w):
        return jnp.tanh(x @ w), None
    x, _ = jax.lax.scan(body, x, w_stack)
    return x

fwd = PL.make_pipelined_forward(Cfg, mesh, apply_range, microbatches=4)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, d))
y_pipe = jax.jit(fwd)(ws, x)
y_seq = apply_range(ws, x)
np.testing.assert_allclose(np.asarray(y_pipe), np.asarray(y_seq),
                           atol=1e-5, rtol=1e-5)
print("gpipe == sequential OK; bubble",
      PL.bubble_fraction(4, 4))
""")


def test_elastic_restore_new_mesh(tmp_path):
    run_with_devices(COMMON + f"""
import os
from repro.configs import get_smoke_config
from repro.dist.sharding import train_rules
from repro.training import checkpoint as CKPT
from repro.training import train_step as TS
cfg = get_smoke_config("qwen2.5-32b")
state, axes = TS.init_state(cfg, jax.random.PRNGKey(0))
CKPT.save({str(tmp_path)!r}, 5, state, axes)
# restore onto a DIFFERENT mesh shape (elastic resize 8 -> 4+4)
mesh = make_mesh((4, 2), ("data", "model"))
rules = train_rules(mesh)
restored, step = CKPT.restore({str(tmp_path)!r}, state, rules=rules)
assert step == 5
leaf = jax.tree.leaves(restored)[0]
assert len(leaf.sharding.device_set) >= 1
for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))
print("elastic restore OK")
""")


def test_manual_tp_matches_baseline():
    run_with_devices(COMMON + """
import dataclasses
from repro.configs import get_smoke_config
from repro.dist import ctx
from repro.dist import tp as TP
from repro.dist.sharding import train_rules
from repro.models import layers as L
cfg = dataclasses.replace(get_smoke_config("qwen2.5-32b"),
                          dtype="float32", tp_impl="manual")
mesh = make_mesh((4, 2), ("data", "model"))   # tp=2 divides q=8, kv=2
key = jax.random.PRNGKey(0)
p, _ = L.block_init(key, cfg, jnp.float32)
x = jax.random.normal(jax.random.fold_in(key, 1), (4, 16, cfg.d_model))
positions = jnp.arange(16)[None, :]
ref = L.block_apply(p, x, positions, cfg)
with ctx.use_rules(train_rules(mesh)):
    got = jax.jit(lambda p, x: TP.block_apply_tp(cfg, p, x, positions))(p, x)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5,
                           rtol=1e-4)
print("manual TP == baseline OK")
""")


def test_manual_decode_matches_gspmd():
    """The fused manual-TP decode step (one shard_map over all axes,
    head-sharded KV pools) matches the GSPMD decode path on an 8-device
    mesh — dense (pod/data/model), MoE (expert-parallel), int8-KV,
    non-divisible GQA (kv=2 on a 4-wide model axis -> KV replication),
    gemma3 local-window ring layers, and the zamba2 hybrid family.

    The MoE router carries a deterministic snap+index tie-break
    (moe._router_top_k), so impls on the same mesh can no longer flip
    experts on bf16 near-ties — the old top-2-gap-aware token allowance
    (0.12-wide, sized for whole-expert flips) is gone; parity is the plain
    allclose at fp-noise tolerance for every family."""
    run_with_devices(COMMON + """
import dataclasses
from repro.configs import get_smoke_config
from repro.dist import tp as TP
from repro.dist.sharding import serve_rules, serve_manual_rules
from repro.models.registry import get_model
from repro.serving import engine as EG

CASES = [
    ("qwen2.5-32b", (2, 2, 2), ("pod", "data", "model"), {}),
    ("granite-moe-1b-a400m", (4, 2), ("data", "model"), {}),
    ("qwen2.5-32b", (4, 2), ("data", "model"), {"kv_cache_dtype": "int8"}),
    # kv=2 on tp=4: the KV-replication path (rep=2), previously a fallback
    ("qwen2.5-32b", (2, 4), ("data", "model"), {}),
    # local-window ring layers inside the fused region
    ("gemma3-12b", (2, 2, 2), ("pod", "data", "model"), {}),
    # hybrid: mamba backbone HEAD-SHARDED over model (decode_ssm_tp) +
    # Megatron-sharded shared attn block
    ("zamba2-1.2b", (4, 2), ("data", "model"), {}),
]
for arch, shape, axes, over in CASES:
    cfg = dataclasses.replace(get_smoke_config(arch), **over)
    mesh = make_mesh(shape, axes)
    model = get_model(cfg)
    params, _ = model.init(cfg, jax.random.PRNGKey(0))
    B, T = 2, 10
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                              cfg.vocab_size)

    def run(c, r):
        state, _ = EG.make_decode_state(c, B, S_max=32, page_size=4, rules=r)
        step = jax.jit(EG.make_serve_step(c, S_max=32, page_size=4, rules=r))
        outs = []
        for t in range(T):
            pos = jnp.full((B,), t, jnp.int32)
            lg, state = step(params, state, toks[:, t:t+1], pos)
            outs.append(np.asarray(lg))
        return np.stack(outs)

    man_cfg = dataclasses.replace(cfg, tp_impl="manual")
    man_rules = serve_manual_rules(mesh)
    assert EG._manual_decode_ok(man_cfg, man_rules), (arch, "gate refused")
    if cfg.family == "hybrid":
        # the mamba math must take the SHARDED path on this mesh (tp=2),
        # so the parity below covers it against the gspmd/replicated impls
        assert TP.decode_ssm_tp(man_cfg, mesh.shape["model"])
    gspmd = run(cfg, serve_rules(mesh))
    manual = run(man_cfg, man_rules)
    np.testing.assert_allclose(manual, gspmd, atol=5e-2, rtol=1e-2,
                               err_msg=arch)
    if cfg.family == "dense" and not over:
        ref = run(cfg, None)
        np.testing.assert_allclose(manual, ref, atol=5e-2, rtol=1e-2)
    print(arch, shape, over, "manual == gspmd OK, maxerr",
          float(np.abs(manual - gspmd).max()))
print("fused manual decode == gspmd OK")
""")


def test_megastep_matches_single_steps_multidevice():
    """The K=8 decode megastep is BITWISE-identical (greedy tokens + final
    state) to 8 single steps on an 8-device mesh, for BOTH decode families:
    the gspmd step and the fused manual-TP region (where the whole scan
    lives inside the one fully-manual shard_map).  Covers dense, MoE,
    int8-KV, gemma3 local-window rings and the zamba2 hybrid."""
    run_with_devices(COMMON + """
import dataclasses
from repro.configs import get_smoke_config
from repro.dist.sharding import serve_rules, serve_manual_rules
from repro.models.registry import get_model
from repro.serving import engine as EG
from repro.serving import page_table as PT

CASES = [
    ("qwen2.5-32b", (2, 2, 2), ("pod", "data", "model"), {}),
    ("granite-moe-1b-a400m", (4, 2), ("data", "model"), {}),
    ("qwen2.5-32b", (4, 2), ("data", "model"), {"kv_cache_dtype": "int8"}),
    ("gemma3-12b", (2, 2, 2), ("pod", "data", "model"), {}),
    ("zamba2-1.2b", (4, 2), ("data", "model"), {}),
]
B, K = 2, 8
for arch, shape, axes, over in CASES:
    base = dataclasses.replace(get_smoke_config(arch), **over)
    mesh = make_mesh(shape, axes)
    model = get_model(base)
    params, _ = model.init(base, jax.random.PRNGKey(0))
    tok0 = jax.random.randint(jax.random.PRNGKey(1), (B, 1), 0,
                              base.vocab_size)
    for impl, mk_rules in (("gspmd", serve_rules),
                           ("manual", serve_manual_rules)):
        cfg = (dataclasses.replace(base, tp_impl="manual")
               if impl == "manual" else base)
        rules = mk_rules(mesh)
        if impl == "manual":
            assert EG._manual_decode_ok(cfg, rules), (arch, "gate refused")
        state, _ = EG.make_decode_state(cfg, B, S_max=32, page_size=4,
                                        rules=rules)
        step = jax.jit(EG.make_serve_step(cfg, S_max=32, page_size=4,
                                          rules=rules))
        st, tok, ref = dict(state), tok0, []
        for _ in range(K):
            lg, st = step(params, st, tok, st["pos"])
            nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)[:, None]
            tok = jnp.where(st["aborted"][:, None], tok, nxt)
            ref.append(np.asarray(tok[:, 0]))
        ref = np.stack(ref, axis=1)
        state2, _ = EG.make_decode_state(cfg, B, S_max=32, page_size=4,
                                         rules=rules)
        mega = jax.jit(EG.make_serve_megastep(cfg, S_max=32, K=K,
                                              page_size=4, rules=rules))
        mtoks, mst = mega(params, state2, tok0)
        np.testing.assert_array_equal(np.asarray(mtoks), ref,
                                      err_msg=f"{arch}/{impl}")
        for k in st:
            ok = all(jax.tree.leaves(jax.tree.map(
                lambda x, y: bool(np.array_equal(np.asarray(x),
                                                 np.asarray(y))),
                st[k], mst[k])))
            assert ok, (arch, impl, k, "state leaf diverged")
        assert int(PT.for_strategy("linear").verify_block_table(
            mst["table"], mst["seq_ids"], mst["pos"], mst["block_table"],
            page_size=4)) == 0, (arch, impl)
    print(arch, shape, over, "megastep == single steps OK (gspmd+manual)")
print("megastep parity multidevice OK")
""")


def test_sharded_dht_roundtrip():
    run_with_devices(COMMON + """
from repro.core import sharded as SHT
from repro.core.spec import OP_INSERT, OP_LOOKUP, OP_DELETE
mesh = make_mesh((8,), ("model",))
st, apply_fn = SHT.make_sharded_table(mesh, "model", m_global=1024,
                                      capacity=64)
B = 128
keys = jnp.arange(B, dtype=jnp.uint32) * 7
ops = jnp.full((B,), OP_INSERT, jnp.int32)
st, ret, ovf = apply_fn(st, ops, keys)
assert int(ret.sum()) == B, ret
st, ret, _ = apply_fn(st, jnp.full((B,), OP_LOOKUP, jnp.int32), keys)
assert int(ret.sum()) == B
st, ret, _ = apply_fn(st, jnp.full((B,), OP_DELETE, jnp.int32), keys)
assert int(ret.sum()) == B
st, ret, _ = apply_fn(st, jnp.full((B,), OP_LOOKUP, jnp.int32), keys)
assert int(ret.sum()) == 0
print("sharded DHT OK")
""")
