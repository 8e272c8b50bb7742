"""Device time by the megastep's named scopes, idle time by the program's
host spans (``chipbench.attribution``, ``reduce.idle_by_span``), and the
three per-layer readers of scoped device time, on synthetic traces and one
recorded on the CPU."""
import time
from types import SimpleNamespace

import jax
import pytest

from chipbench import attribution, reduce, spec
from chipbench.reduce import Event, Trace
from repro import obs as OBS

READERS = {"attend_ms_per_megastep": "attend",
           "allocator_ms_per_megastep": "allocator",
           "state_freeze_ms_per_megastep": "state_freeze"}


class _Program:
    """Stands in for a live batcher that publishes its megastep's map."""

    def __init__(self, scopes):
        self.scopes = scopes

    def megastep_scopes(self):
        return self.scopes


def _two_programs():
    """Two executions of the megastep and one of an eager program whose
    operations reuse the megastep's instruction names."""
    mega = lambda t: [Event("while.2", t, t + 100),          # noqa: E731
                      Event("fusion.1", t, t + 30),
                      Event("fusion.2", t + 30, t + 50),
                      Event("fusion.3", t + 50, t + 56),
                      Event("copy.4", t + 56, t + 100)]
    ops = mega(0) + mega(200) + [Event("fusion.1", 120, 190),
                                 Event("fusion.2", 300, 310)]
    mods = [Event("jit_megastep(11)", 0, 100),
            Event("jit_delete_batch(12)", 110, 195),
            Event("jit_megastep(11)", 200, 300)]
    return Trace(ops={"/device:TPU:0": ops},
                 modules={"/device:TPU:0": mods},
                 spans=[Event(reduce.WINDOW_SPAN, 0, 400)])


SCOPES = {"fusion.1": "attend", "fusion.2": "allocator",
          "fusion.3": "state_freeze", "while.2": "attend"}


def _ctx(tr, win):
    """The readers' context as the harness builds it: the trace, its
    window, and the scope map the live program publishes."""
    return SimpleNamespace(trace=tr, win=win,
                           scopes=attribution.program_scopes())


@pytest.fixture
def published():
    prog = _Program(dict(SCOPES))
    OBS.publish_scopes("megastep", prog.megastep_scopes)
    yield prog
    del prog


def test_scoped_time_keeps_to_the_program(published):
    tr = _two_programs()
    win = reduce.window(tr)
    secs, execs = attribution.scoped_s(tr, win, "megastep", SCOPES)
    assert execs == 2.0
    # the loop is left out; the eager program's fusion.1 and the fusion.2
    # that ran after the second execution ended are not the megastep's
    assert secs == pytest.approx({"attend": 60e-9, "allocator": 40e-9,
                                  "state_freeze": 12e-9, None: 88e-9})
    ctx = _ctx(tr, win)
    got = {m: spec.metric_reader(m).read(ctx) for m in READERS}
    assert got == pytest.approx({"attend_ms_per_megastep": 30e-6,
                                 "allocator_ms_per_megastep": 20e-6,
                                 "state_freeze_ms_per_megastep": 6e-6})


def test_readers_average_over_chips(published):
    one = _two_programs()
    ops = one.ops["/device:TPU:0"]
    slow = [Event(e.name, e.start, e.start + 2 * (e.end - e.start))
            if e.name == "fusion.1" else e for e in ops]
    tr = Trace(ops={"/device:TPU:0": ops, "/device:TPU:1": slow},
               modules={"/device:TPU:0": one.modules["/device:TPU:0"],
                        "/device:TPU:1": one.modules["/device:TPU:0"]},
               spans=one.spans)
    ctx = _ctx(tr, reduce.window(tr))
    assert spec.metric_reader("attend_ms_per_megastep").read(ctx) == \
        pytest.approx(45e-6)


def test_readers_find_nothing_to_read(published):
    tr = _two_programs()
    win = reduce.window(tr)
    empty = _ctx(Trace({}, {}, tr.spans), win)
    assert all(spec.metric_reader(m).read(empty) is None for m in READERS)
    # a program with no such scope (a model with no allocator)
    published.scopes = {"fusion.1": "attend"}
    ctx = _ctx(tr, win)
    assert spec.metric_reader("allocator_ms_per_megastep").read(ctx) is None
    # no live program has published a map: the parent commit's program
    OBS.publish_scopes("megastep", _Program({}).megastep_scopes)
    ctx = _ctx(tr, win)
    assert all(spec.metric_reader(m).read(ctx) is None for m in READERS)


def test_idle_split_by_innermost_span():
    spans = [Event(reduce.WINDOW_SPAN, 0, 100),
             Event("chipbench.round", 0, 60),
             Event("repro.serve.round", 2, 58),
             Event("repro.serve.apply_plan", 30, 50),
             Event("repro.serve.free", 32, 45)]
    gaps = [(20, 40), (55, 70)]
    got = reduce.idle_by_span(gaps, spans)
    assert got == pytest.approx({
        "repro.serve.round": (10 + 3) / 1e9,
        "repro.serve.apply_plan": 2 / 1e9,
        "repro.serve.free": 8 / 1e9,
        "chipbench.round": 2 / 1e9,
        "host": 10 / 1e9})


def test_load_keeps_program_spans_beside_the_harness(tmp_path):
    d = str(tmp_path)
    f = jax.jit(lambda x: x * 2)
    x = jax.numpy.ones(8)
    f(x).block_until_ready()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation(reduce.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation(reduce.ROUND_SPAN):
            with OBS.span("serve.round"):
                with OBS.span("serve.free"):
                    time.sleep(0.01)
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = reduce.load(d)
    # the harness's spans stay as they were; the program's are beside them
    assert sorted(s.name for s in tr.spans) == \
        [reduce.ROUND_SPAN, reduce.WINDOW_SPAN]
    assert sorted(s.name for s in tr.program_spans) == \
        ["repro.serve.free", "repro.serve.round"]
    ctx = SimpleNamespace(trace=tr, win=reduce.window(tr), rounds=1,
                          counters={"jit_compiles": 0}, scopes=None)
    rep = attribution.report(ctx)
    assert rep["span_ms_per_round"]["repro.serve.free"] >= 9.0
    assert rep["free_ms_per_round"] == pytest.approx(
        rep["span_ms_per_round"]["repro.serve.free"])
    assert rep["host_critical_ms_per_round"] > 0
    assert rep["compiles_per_round"] == 0
