"""The trace reduction, on a trace this test records on the CPU with
``jax.profiler`` (``chipbench_smoke.record_cpu_trace``: the CPU client
thread's XLA operations stand for the device's), and on synthetic ones."""
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import reduce
from chipbench.reduce import Event, Trace
from chipbench_smoke import record_cpu_trace

SLEEP_S = 0.05


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    def megastep(x):
        return jnp.tanh(x @ x).sum()

    f = jax.jit(megastep)
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()

    def body():
        with jax.profiler.TraceAnnotation(reduce.WINDOW_SPAN):
            for _ in range(3):
                with jax.profiler.TraceAnnotation(reduce.ROUND_SPAN):
                    f(x).block_until_ready()
                    with jax.profiler.TraceAnnotation(
                            reduce.SPAN + "plan_round"):
                        time.sleep(SLEEP_S)
    return record_cpu_trace(str(tmp_path_factory.mktemp("trace")),
                            megastep, body)


def test_load_finds_the_harness_spans(recorded):
    loaded, _ = recorded
    names = [s.name for s in loaded.spans]
    assert names.count(reduce.WINDOW_SPAN) == 1
    assert names.count(reduce.ROUND_SPAN) == 3
    assert names.count(reduce.SPAN + "plan_round") == 3
    assert loaded.ops == {} and loaded.modules == {}   # no device plane


def test_busy_program_and_idle(recorded):
    _, tr = recorded
    win = reduce.window(tr)
    span = (win[1] - win[0]) / 1e9
    assert span >= 3 * SLEEP_S
    busy = reduce.device_busy_s(tr, win)
    assert 0 < busy < span - 3 * SLEEP_S * 0.9
    secs, execs = reduce.program_time(tr, win, "megastep")
    assert execs == 3 and 0 < secs < span
    gaps = reduce.idle_gaps(tr, win, n=3)
    # the three longest idle gaps are the host sleeping in plan_round
    assert [g[0] for g in gaps] == [reduce.SPAN + "plan_round"] * 3
    assert all(g[1] >= SLEEP_S * 0.9 for g in gaps)
    assert reduce.collective_s(tr, win) == 0.0
    top = reduce.top_ops(tr, win, n=2)
    assert len(top) == 2 and top[0][1] >= top[1][1] > 0
    assert reduce.span_count(tr, win, reduce.ROUND_SPAN) == 3
    assert reduce.span_s(tr, win, (reduce.SPAN + "plan_round",)) >= \
        3 * SLEEP_S * 0.9


def test_union_and_clipping():
    ev = [Event("a", 0, 10), Event("b", 5, 20), Event("c", 30, 40),
          Event("d", 95, 120)]
    assert reduce.union([(0, 10), (5, 20), (30, 40)]) == [(0, 20), (30, 40)]
    assert reduce.busy_ns(ev, (0, 100)) == 20 + 10 + 5


def test_operation_names_and_containers():
    assert reduce._short("%fusion.12 = bf16[8]{0} fusion(%p.1)") == \
        "fusion.12"
    assert reduce._short("jit_megastep(5)") == "jit_megastep(5)"
    tr = Trace(ops={"/device:TPU:0": [Event("while.3", 0, 100),
                                      Event("fusion.1", 10, 40),
                                      Event("fusion.2", 50, 60)]},
               modules={}, spans=[Event(reduce.WINDOW_SPAN, 0, 100)])
    win = reduce.window(tr)
    assert [n for n, _ in reduce.top_ops(tr, win)] == ["fusion.1",
                                                       "fusion.2"]
    assert reduce.device_busy_s(tr, win) == pytest.approx(100e-9)


def test_collectives_average_over_devices():
    dev = lambda off: [Event("fusion.1", 0, 10),
                       Event("all-reduce.3", 10, 14 + off),
                       Event("all-gather-start.2", 20, 22)]
    tr = Trace(ops={"/device:TPU:0": dev(0), "/device:TPU:1": dev(2)},
               modules={"/device:TPU:0": [Event("jit_megastep(1)", 0, 30)],
                        "/device:TPU:1": [Event("jit_megastep(1)", 0, 30)]},
               spans=[Event(reduce.WINDOW_SPAN, 0, 50)])
    win = reduce.window(tr)
    assert reduce.collective_s(tr, win) == pytest.approx(7e-9)
    assert reduce.program_time(tr, win, "megastep") == (30e-9, 1.0)
    assert reduce.program_name("jit_megastep(1234)") == "megastep"


def test_idle_gaps_are_named_by_the_program_span_first():
    """A gap is named after the innermost program span open at its middle,
    even inside a shorter harness span; where none is open, after the
    innermost harness span; where neither is, ``host``."""
    tr = Trace(ops={"/device:TPU:0": [Event("fusion.1", 0, 10),
                                      Event("fusion.2", 38, 50),
                                      Event("fusion.3", 80, 100)]},
               modules={},
               spans=[Event(reduce.WINDOW_SPAN, 0, 120),
                      Event(reduce.ROUND_SPAN, 0, 100),
                      Event(reduce.SPAN + "apply_plan", 15, 35)],
               program_spans=[Event("repro.serve.round", 1, 60),
                              Event("repro.serve.apply_plan", 12, 38)])
    assert reduce.idle_gaps(tr, reduce.window(tr)) == [
        (reduce.ROUND_SPAN, 30e-9), ("repro.serve.apply_plan", 28e-9),
        ("host", 20e-9)]
