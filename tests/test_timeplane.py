"""The time plane (obs/timeplane.py): the serving loop's host spans on the
profiler's clock, the megastep's named scopes read back from its compiled
HLO, and the process's compile counter in the metrics registry."""
from __future__ import annotations

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs as OBS
from repro.configs import get_smoke_config
from repro.launch import compile_cache as CC
from repro.launch.serve import ContinuousBatcher
from repro.models.registry import get_model
from repro.serving.sched import Request

# the scopes each family's megastep carries at smoke shape
FAMILY_SCOPES = {
    "qwen2.5-32b": {"embed", "allocator", "attn_proj", "kv_write", "attend",
                    "mlp", "lm_head", "sampling"},
    "mamba2-2.7b": {"embed", "ssm", "state_freeze", "lm_head", "sampling"},
}


def _batcher(arch, **kw):
    cfg = get_smoke_config(arch)
    params, _ = get_model(cfg).init(cfg, jax.random.PRNGKey(0))
    args = dict(batch=2, max_len=32, page_size=4, megastep_k=4)
    args.update(kw)
    return ContinuousBatcher(cfg, params, **args)


def _submit(srv, req_id, prompt_len, max_new):
    rng = np.random.default_rng(req_id)
    srv.sched.submit(Request(
        req_id=req_id, max_new_tokens=max_new, arrival=srv.sched.clock,
        prompt=rng.integers(0, srv.cfg.vocab_size, prompt_len,
                            dtype=np.int32)))


def test_scope_of_and_scope_map_parse():
    assert OBS.scope_of("jit(megastep)/while/body/attend/dot_general") == \
        "attend"
    assert OBS.scope_of("jit(megastep)/mlp/attend/add") == "attend"
    assert OBS.scope_of("jit(megastep)/while/body/add") is None
    text = "\n".join([
        "%fused_computation.3 (p.0: f32[8]) -> f32[8] {",
        '  %m.1 = f32[8]{0} multiply(%p.0, %p.0), '
        'metadata={op_name="jit(megastep)/mlp/mul"}',
        '  ROOT %s.1 = f32[8]{0} sine(%m.1), '
        'metadata={op_name="jit(megastep)/mlp/sin"}',
        "}",
        "ENTRY %main.4 (x.1: f32[8]) -> f32[8] {",
        "  %x.1 = f32[8]{0} parameter(0)",
        "  %fusion.7 = f32[8]{0} fusion(%x.1), kind=kLoop, "
        "calls=%fused_computation.3",
        '  ROOT %add.2 = f32[8]{0} add(%fusion.7, %x.1), '
        'metadata={op_name="jit(megastep)/while/body/add"}',
        "}"])
    m = OBS.scope_map(text)
    assert m["fusion.7"] == "mlp"        # from its fused computation
    assert m["m.1"] == "mlp" and "add.2" not in m and "x.1" not in m


@pytest.mark.parametrize("arch", sorted(FAMILY_SCOPES))
def test_megastep_hlo_carries_the_scopes(arch):
    srv = _batcher(arch)
    srv.step_round()
    scopes = srv.megastep_scopes()
    assert set(scopes.values()) == FAMILY_SCOPES[arch]
    assert OBS.program_scopes("megastep") is scopes
    # the map names the compiled megastep's own instructions: most of its
    # fusions carry a scope, the rest are norms, residual adds and loop
    # bookkeeping
    text = srv.mega_fn.lower(
        srv.params, srv.state, srv.tokens, jnp.asarray(srv.lane_stop),
        jnp.zeros((2, 4), jnp.int32), jnp.zeros((2, 4), bool)
    ).compile().as_text()
    fusions = [ln.split("=")[0].split()[-1].lstrip("%")
               for ln in text.splitlines() if " fusion(" in ln]
    assert fusions
    assert sum(f in scopes for f in fusions) >= len(fusions) // 3
    assert set(FAMILY_SCOPES[arch]) <= set(OBS.SCOPES)


def test_fresh_hlo_text_sees_scopes_a_stale_cache_entry_lacks(tmp_path):
    """The persistent cache keys a program without its metadata: an entry
    written by the program before it had a scope is found for the scoped
    program, whose text then names no scope.  ``fresh_hlo_text`` compiles
    past it, and leaves the cache as it found it."""
    from jax.experimental.compilation_cache import compilation_cache as PCC

    def plain(x):
        return jnp.sin(x) @ x + 1

    def scoped(x):
        with jax.named_scope("attend"):
            y = jnp.sin(x) @ x
        return y + 1
    plain.__name__ = scoped.__name__ = "step"
    x = jnp.ones((8, 8))
    before = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    PCC.reset_cache()
    try:
        jax.jit(plain).lower(x).compile()
        jax.clear_caches()
        stale = jax.jit(scoped).lower(x).compile().as_text()
        assert "attend" not in stale
        fresh = OBS.fresh_hlo_text(scoped, (x,))
        assert "attend" in set(OBS.scope_map(fresh).values())
        assert jax.config.jax_enable_compilation_cache
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        PCC.reset_cache()


def test_program_scopes_is_weak():
    class Holder:
        def scopes(self):
            return {"fusion.1": "attend"}
    h = Holder()
    OBS.publish_scopes("probe-program", h.scopes)
    assert OBS.program_scopes("probe-program") == {"fusion.1": "attend"}
    del h
    assert OBS.program_scopes("probe-program") is None
    assert OBS.program_scopes("never-published") is None


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events
                        if e.name.startswith(OBS.timeplane.SPAN_PREFIX)]
    return out


def test_step_round_span_tree(tmp_path):
    """A traced round with a completion, a free and an admission gives
    each phase its span, nested as the loop nests them."""
    srv = _batcher("qwen2.5-32b", auto_refill=False)
    _submit(srv, 1, prompt_len=3, max_new=2)
    _submit(srv, 2, prompt_len=3, max_new=20)
    _submit(srv, 3, prompt_len=3, max_new=4)
    srv.step_round()                      # admits 1 and 2
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):                    # 1 finishes and 3 takes its lane
        srv.step_round()
    jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    names = {n for n, _, _ in spans}
    pre = OBS.timeplane.SPAN_PREFIX
    phases = ["serve.forcing", "serve.dispatch", "serve.wait",
              "serve.absorb", "serve.headroom", "sched.plan_round",
              "serve.apply_plan", "serve.telemetry"]
    for n in ["serve.round", "serve.free", "serve.admit", "pt.delete",
              "pt.count"] + phases:
        assert pre + n in names, n

    def parents(name):
        a = [(s, e) for n, s, e in spans if n == pre + name]
        return a

    def inside(child, parent):
        outer = parents(parent)
        for s, e in parents(child):
            assert any(ps <= s and e <= pe for ps, pe in outer), \
                (child, parent)

    for ph in phases:
        inside(ph, "serve.round")
    for ph in ("serve.free", "serve.admit"):
        inside(ph, "serve.apply_plan")
    for ph in ("pt.delete", "pt.count"):
        inside(ph, "serve.free")
    # the round's phases follow one another without overlap
    rounds = parents("serve.round")
    assert len(rounds) == 2
    for rs, re_ in rounds:
        seq = sorted((s, e) for n, s, e in spans
                     if n[len(pre):] in phases and rs <= s < re_)
        assert len(seq) == len(phases)
        assert all(a[1] <= b[0] for a, b in zip(seq, seq[1:]))


def test_fresh_jit_in_a_round_counts_one_compile():
    srv = _batcher("qwen2.5-32b", auto_refill=False)
    _submit(srv, 1, prompt_len=3, max_new=24)
    for _ in range(3):                    # every program of a decode round
        srv.step_round()
    x = jnp.ones(3)

    def compiles():
        return srv.metrics.snapshot()["gauges"]["jit_compiles"]
    before = compiles()
    srv.step_round()
    assert compiles() == before           # a steady round compiles nothing
    forcing = srv._forcing

    def forcing_and_a_fresh_jit():
        jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
        return forcing()
    srv._forcing = forcing_and_a_fresh_jit
    srv.step_round()
    assert compiles() == before + 1
    assert "jit_compile_s" in srv.metrics.snapshot()["gauges"]


def test_count_compiles_reads_the_one_listener():
    x = jnp.ones(5)
    with CC.count_compiles() as c:
        jax.jit(lambda x: x - 7)(x).block_until_ready()
    assert c["compiles"] == 1 and c["compile_s"] > 0
    assert set(c) == {"compiles", "compile_s", "trace_s", "cache_hits",
                      "cache_writes"}
    n = OBS.COMPILE_STATS["compiles"]
    with CC.count_compiles() as c2:
        pass
    assert c2["compiles"] == 0 and OBS.COMPILE_STATS["compiles"] == n
