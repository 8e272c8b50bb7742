"""The program's own host spans and registry counts in the readers'
context: ``reduce.load`` keeps the program's spans beside the harness's,
``harness.traced_drive`` differences the registry's counts over the window,
and the readers ``host_critical_ms_per_round``, ``free_ms_per_round`` and
``compiles_per_round`` read them.  Every reader the benchmark had before
reads the same value with the program's spans there."""
import glob
import os
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, reduce, spec
from chipbench.reduce import Trace
from chipbench_smoke import record_cpu_trace
from repro import obs as OBS

SLEEP_S = 0.02
ROUNDS = 3
NEW_READERS = ("host_critical_ms_per_round", "free_ms_per_round",
               "compiles_per_round")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Three rounds as ``step_round`` writes them: the dispatch, a wait of
    SLEEP_S after the megastep, and an apply_plan of 2 SLEEP_S (one in the
    free, inside the harness's own apply_plan span)."""
    def megastep(x):
        return jnp.tanh(x @ x).sum()

    f = jax.jit(megastep)
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()

    def body():
        with jax.profiler.TraceAnnotation(reduce.WINDOW_SPAN):
            for _ in range(ROUNDS):
                with jax.profiler.TraceAnnotation(reduce.ROUND_SPAN), \
                        OBS.span("serve.round"):
                    with OBS.span("serve.dispatch"):
                        out = f(x)
                    with OBS.span("serve.wait"):
                        out.block_until_ready()
                        time.sleep(SLEEP_S)
                    with OBS.span("serve.apply_plan"), \
                            jax.profiler.TraceAnnotation(
                                reduce.SPAN + "apply_plan"):
                        with OBS.span("serve.free"):
                            time.sleep(SLEEP_S)
                        time.sleep(SLEEP_S)
    d = str(tmp_path_factory.mktemp("trace"))
    return (d,) + record_cpu_trace(d, megastep, body)


def _ctx(tr, **kw):
    win = reduce.window(tr)
    names = sorted({e.name for ev in tr.ops.values() for e in ev})
    scopes = {n: ("attend", "allocator", "state_freeze")[i % 3]
              for i, n in enumerate(names)}
    base = dict(trace=tr, win=win, rounds=ROUNDS, window_flops=3e9,
                chips=1, peaks={"bf16_flops_per_s": 1e12},
                counters={"probe_p99": 1.0, "jit_compiles": 6},
                host_spans=harness.HOST_SPANS, scopes=scopes)
    return SimpleNamespace(**dict(base, **kw))


def test_load_keeps_the_program_spans_apart(recorded):
    """``spans`` holds what the load kept before, the harness's spans alone;
    the program's are in ``program_spans``."""
    d, loaded, _ = recorded
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                     recursive=True)[0]
    harness_only = [e for plane in ProfileData.from_file(path).planes
                    if plane.name.startswith("/host:")
                    for line in plane.lines for e in reduce._events(line)
                    if e.name.startswith(reduce.SPAN)]
    assert loaded.spans == harness_only
    names = [s.name for s in loaded.program_spans]
    for span in ("round", "dispatch", "wait", "apply_plan", "free"):
        assert names.count("repro.serve." + span) == ROUNDS
    assert all(n.startswith(reduce.PROGRAM_SPAN) for n in names)


def test_readers_of_the_program_spans(recorded):
    _, _, tr = recorded
    ctx = _ctx(tr)
    read = {m: spec.metric_reader(m).read(ctx) for m in NEW_READERS}
    ms = lambda name: 1e3 * reduce.span_s(  # noqa: E731
        tr, ctx.win, ("repro.serve." + name,)) / ROUNDS
    assert read["host_critical_ms_per_round"] == pytest.approx(
        ms("round") - ms("wait"))
    assert read["host_critical_ms_per_round"] >= 2 * SLEEP_S * 1e3 * 0.9
    assert read["free_ms_per_round"] == pytest.approx(ms("free"))
    assert SLEEP_S * 1e3 * 0.9 <= read["free_ms_per_round"] < \
        read["host_critical_ms_per_round"]
    assert read["compiles_per_round"] == 2.0
    # a program that writes no spans, or a registry with no compile count
    silent = _ctx(Trace(tr.ops, tr.modules, tr.spans),
                  counters={"probe_p99": 1.0})
    assert all(spec.metric_reader(m).read(silent) is None
               for m in NEW_READERS)


def test_existing_readers_read_the_same_with_program_spans(recorded):
    _, _, tr = recorded
    bench = spec.load_benchmark()
    before = [m["name"] for m in bench["per_layer"]
              if m["name"] not in NEW_READERS]
    with_program = _ctx(tr)
    harness_only = _ctx(Trace(tr.ops, tr.modules, tr.spans))
    got = {m: spec.metric_reader(m).read(with_program) for m in before}
    assert got == {m: spec.metric_reader(m).read(harness_only)
                   for m in before}
    for m in ("host_ms_per_round", "device_idle_share", "megastep_ms",
              "megastep_mfu", "probe_len_p99"):
        assert got[m] is not None and got[m] > 0, m
    assert got["attend_ms_per_megastep"] is not None


def test_registry_counts_are_differenced_over_the_window(smoke_root,
                                                         on_cpu):
    """The counts in the readers' context are those of the window's rounds:
    the batcher counts K attention token steps a round, and a program
    compiled inside the window is one compile."""
    s = harness.setup(smoke_root, "qwen-smoke.open")
    server = harness.make_server(s, harness.draw_weights(s, 4), 4)
    server.warm_up(np.random.default_rng([4, 2]))
    step, planted = server.step, []

    def step_and_compile():
        if not planted:
            planted.append(jax.jit(lambda v: v * 3 + 1)(jnp.ones(5)))
        step()
    server.step = step_and_compile
    rep, tr, counts = harness.traced_drive(server, s.mix, 4, 1.0,
                                           drain_cap=0.0)
    assert rep["rounds"] > 0
    assert counts["attn_token_steps"] == server.K * rep["rounds"]
    assert counts["jit_compiles"] >= 1
    ctx = harness.reader_context(server, tr, rep, counts, 1,
                                 {"bf16_flops_per_s": 1e12})
    assert ctx.counters["probe_p99"] == server.probe_p99()
    assert ctx.scopes is None              # no device plane on the CPU
    assert spec.metric_reader("compiles_per_round").read(ctx) == \
        counts["jit_compiles"] / rep["rounds"]
