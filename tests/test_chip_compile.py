"""Compile for a described TPU v5e (no chip attached): the Pallas kernels
and the serving megastep at the widths ``chip_smoke.py`` runs.

Interpret mode cannot see what Mosaic refuses (block shapes off the (8, 128)
tiling, unaligned slices) or whether a program fits the chip's 16 GiB; the
TPU compiler installed here can.  The topology is described inside a
fixture, never at import: only one process may load the TPU library, and
the test workers each import this file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro import kernels as KN
from repro import obs as OBS
from repro.configs import get_config
from repro.dist.sharding import serve_manual_rules
from repro.kernels.fused_decode.fused import fused_decode_kernel
from repro.kernels.paged_attention.paged_attention import \
    paged_attention_kernel
from repro.kernels.probe.probe import probe_lookup_kernel
from repro.launch.mesh import make_mesh
from repro.models.registry import get_model
from repro.serving import engine as EG

HBM_BYTES = 16 * 2 ** 30                      # one v5e chip

# chip_smoke.py's serving shapes: 32 lanes of qwen2.5-32b, 8 KV heads of
# 128 (G = 40 / 8 query heads each), 16-token pages, 4096-token lanes
B, KH, G, D, PS, S_MAX, K = 32, 8, 5, 128, 16, 4096, 8
MP = S_MAX // PS
NP = 10240
LAYERS = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip can be written to the persistent
    # cache but not read back without one
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler: nothing to check here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(one_chip, name):
    s = lambda shape, dt: _sds(one_chip, shape, dt)
    q = s((B, KH * G, D), jnp.bfloat16)
    bt, pos = s((B, MP), jnp.int32), s((B,), jnp.int32)
    if name == "probe":
        nt, kt = B * MP // 128, 128           # (64, 128) key tiles
        return (lambda *a: probe_lookup_kernel(*a),
                (s((NP,), jnp.uint32), s((nt * kt,), jnp.uint32),
                 s((nt * kt,), jnp.int32), s((nt,), jnp.int32)))
    if name == "paged_attention":
        pool = s((NP, PS, KH, D), jnp.bfloat16)
        return (lambda *a: paged_attention_kernel(*a), (q, pool, pool, bt,
                                                        pos))
    # the engine's layout: pools stacked over layers, one layer attended
    pool = s((LAYERS, NP, PS, KH, D), jnp.bfloat16)
    partials = name == "fused_partials"
    return (lambda q, k, v, bt, pos, layer: fused_decode_kernel(
                q, k, v, bt, pos, layer=layer, partials=partials),
            (q, pool, pool, bt, pos, s((), jnp.int32)))


@pytest.mark.parametrize("name", ["probe", "paged_attention",
                                  "fused_partials", "fused_full"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_args(one_chip, name)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def _smoke_cfg(layers, **over):
    return dataclasses.replace(get_config("qwen2.5-32b"), num_layers=layers,
                               pad_heads_to=0, **over)


def _megastep_memory(cfg, params, state, sharding, *, rules=None):
    i32 = lambda *shape: _sds(sharding, shape, jnp.int32)
    mega = EG.make_serve_megastep(cfg, S_max=S_MAX, K=K, rules=rules,
                                  page_size=PS)
    compiled = jax.jit(mega, donate_argnums=(1,)).lower(
        params, state, i32(B, 1), i32(B), i32(B, K),
        _sds(sharding, (B, K), bool)).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    return compiled, m, total


def _one_chip_shapes(cfg, one_chip):
    put = lambda tree: jax.tree.map(
        lambda x: _sds(one_chip, x.shape, x.dtype), tree)
    params = put(jax.eval_shape(lambda k: get_model(cfg).init(cfg, k)[0],
                                jax.random.PRNGKey(0)))
    _, n_pages = EG.plan_pages(cfg, B, S_MAX, PS, 1)
    state, _ = EG.make_decode_state(cfg, B, S_max=S_MAX, page_size=PS,
                                    n_pages=n_pages, abstract=True)
    return params, put(state)


def test_megastep_fits_one_chip(one_chip):
    """The donated default megastep: its pools are updated in place (the
    output aliases the state) and no temporary copies the KV pool."""
    cfg = _smoke_cfg(LAYERS)
    params, state = _one_chip_shapes(cfg, one_chip)
    _, m, total = _megastep_memory(cfg, params, state, one_chip)
    pool = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(state["pools"]))
    assert m.alias_size_in_bytes >= pool
    assert m.temp_size_in_bytes < pool
    assert total <= HBM_BYTES, total / 2 ** 30


def test_fused_megastep_compiles_kernel(one_chip, monkeypatch):
    """With the fused kernel on, the megastep compiles the Pallas kernel
    (not its interpreter) at real widths and still fits."""
    monkeypatch.setattr(EG, "_kernel_interpret", lambda: False)
    cfg = _smoke_cfg(LAYERS, fused_kernel=True)
    params, state = _one_chip_shapes(cfg, one_chip)
    compiled, _, total = _megastep_memory(cfg, params, state, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    assert total <= HBM_BYTES, total / 2 ** 30


def test_default_megastep_takes_kernel_on_tpu(one_chip, monkeypatch):
    """On a TPU the default megastep, with no ``fused_kernel`` override,
    attends through the compiled kernel, and the kernel's instruction sits
    in the ``attend`` scope that ``attend_ms_per_megastep`` reads."""
    monkeypatch.setattr(KN, "on_tpu", lambda: True)
    cfg = _smoke_cfg(LAYERS)
    assert cfg.fused_kernel is None
    params, state = _one_chip_shapes(cfg, one_chip)
    compiled, _, total = _megastep_memory(cfg, params, state, one_chip)
    text = compiled.as_text()
    kernels = [m.group(1) for m in map(OBS.timeplane._INSTR.match,
                                       text.splitlines())
               if m is not None and "tpu_custom_call" in m.group(2)]
    assert kernels
    scopes = OBS.scope_map(text)
    assert {scopes.get(k) for k in kernels} == {"attend"}, kernels
    assert total <= HBM_BYTES, total / 2 ** 30


def test_manual_tp_megastep_fits_four_chips(topo):
    """``chip_smoke.py --chips 4``: 16 layers (more than one chip holds)
    on a (data 1, model 4) mesh, manual tensor-parallel decode."""
    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices[:4])
    rules = serve_manual_rules(mesh)
    cfg = _smoke_cfg(16, tp_impl="manual")
    assert EG._manual_decode_ok(cfg, rules)
    box = {}

    def init(k):
        p, box["axes"] = get_model(cfg).init(cfg, k)
        return p

    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda x, s: _sds(s, x.shape, x.dtype), shapes,
                          rules.tree_shardings(box["axes"], shapes))
    _, n_pages = EG.plan_pages(cfg, B, S_MAX, PS, 1)
    st, axes = EG.make_decode_state(cfg, B, S_max=S_MAX, page_size=PS,
                                    n_pages=n_pages, rules=rules,
                                    abstract=True)
    state = jax.tree.map(lambda x, s: _sds(s, x.shape, x.dtype), st,
                         rules.tree_shardings(axes, st))
    _, _, total = _megastep_memory(cfg, params, state,
                                   NamedSharding(mesh, jax.sharding
                                                 .PartitionSpec()),
                                   rules=rules)
    assert total <= HBM_BYTES, total / 2 ** 30
