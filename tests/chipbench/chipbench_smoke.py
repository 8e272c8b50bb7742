"""Small cells for the benchmark's CPU tests: tiny models of both
families, tiny traffic, a checkout-shaped temporary directory that holds
them (``write_smoke_root``), and a trace recorded on the CPU in the form
the chip's takes (``record_cpu_trace``)."""
import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

SMOKE_SERVING = {"batch": 4, "max_len": 128, "page_size": 8, "megastep": 4,
                 "policy": "fcfs", "proactive": True}

# tiny models of the two families: the configuration files' form, with
# every width cut so that a CPU runs them in seconds
SMOKE_CONFIGS = {
    "qwen-smoke": {
        "source": "test", "family": "qwen2", "arch": "qwen2.5-32b",
        "model": {"hidden_size": 64, "intermediate_size": 128,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "num_hidden_layers": 2, "vocab_size": 512,
                  "rope_theta": 10000.0, "rms_norm_eps": 1e-06},
        "overrides": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                      "num_kv_heads": 2, "d_ff": 128, "vocab_size": 512,
                      "head_dim": 16, "pad_heads_to": 0,
                      "rope_theta": 10000.0},
        "serving": SMOKE_SERVING,
        "weight_draw": {"embedding_std": 1.0, "bias_std": 0.5},
        "limits": {"logit_gap": 0.02}},
    "mamba-smoke": {
        "source": "test", "family": "mamba2", "arch": "mamba2-2.7b",
        "model": {"d_model": 64, "n_layer": 2, "vocab_size": 512,
                  "d_state": 16, "headdim": 16, "norm_epsilon": 1e-06},
        "overrides": {"num_layers": 2, "d_model": 64, "vocab_size": 512,
                      "ssm_state": 16, "ssm_head_dim": 16, "ssm_chunk": 32},
        "serving": SMOKE_SERVING,
        "weight_draw": {"embedding_std": 0.1, "bias_std": 0.1},
        "limits": {"logit_gap": 0.08}},
}

# the qwen smoke model on the manual tensor-parallel path over four chips
SMOKE_CONFIGS["qwen-smoke-tp"] = dict(
    SMOKE_CONFIGS["qwen-smoke"],
    overrides=dict(SMOKE_CONFIGS["qwen-smoke"]["overrides"],
                   tp_impl="manual"))

SMOKE_TRAFFIC = {
    "open": {"loop": "open", "rate_per_s": 20.0, "gap_cv": 2.0,
             "prompt_len": {"dist": "uniform", "min": 4, "max": 12},
             "output_len": {"dist": "uniform", "min": 4, "max": 10},
             "block": 8},
    "backlog": {"loop": "backlog", "backlog_per_lane": 2,
                "prompt_len": {"dist": "uniform", "min": 4, "max": 12},
                "output_len": {"dist": "lognormal", "median": 12,
                               "sigma": 0.5, "min": 6, "max": 24},
                "block": 8},
}

SMOKE_CELLS = [("qwen-smoke.open", "qwen-smoke", "open"),
               ("mamba-smoke.backlog", "mamba-smoke", "backlog")]
TP_CELL = ("qwen-smoke-tp.backlog", "qwen-smoke-tp", "backlog")


def write_smoke_root(root: pathlib.Path) -> pathlib.Path:
    """A checkout-shaped directory whose configurations, traffic and
    BENCHMARK.json exist only there; the metric readers and references are
    copied from the package."""
    pkg = root / "chipbench"
    (pkg / "configs").mkdir(parents=True)
    (pkg / "traffic").mkdir()
    for sub in ("metrics", "reference"):
        shutil.copytree(ROOT / "chipbench" / sub, pkg / sub)
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name, conf in SMOKE_CONFIGS.items():
        (pkg / "configs" / f"{name}.json").write_text(json.dumps(conf))
    for name, mix in SMOKE_TRAFFIC.items():
        (pkg / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    (pkg / "peaks.json").write_text(json.dumps(
        {"cpu": {"bf16_flops_per_s": 1e12, "source": "test"}}))
    bench = {
        "command": real["command"], "paths": real["paths"],
        "run_seconds": 2,
        "configs": [{"name": n, "source": "test",
                     "file": f"chipbench/configs/{n}.json", "reduced": [],
                     "why": "test"} for n in SMOKE_CONFIGS],
        "workloads": [{"name": w, "config": c, "traffic": t,
                       "chips": 4 if w == TP_CELL[0] else 1, "why": "test"}
                      for w, c, t in SMOKE_CELLS + [TP_CELL]],
        "end_to_end": real["end_to_end"],
        "per_layer": real["per_layer"],
    }
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "ttft_p90_ms":
            m["workloads"] = ["qwen-smoke.open"]
        elif "workloads" in m:
            m["workloads"] = [w for w, _, _ in SMOKE_CELLS + [TP_CELL]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def record_cpu_trace(d: str, megastep, body):
    """Trace ``body()`` into ``d`` with ``jax.profiler``; returns the trace
    as ``reduce.load`` reads it and the same trace with the CPU's device
    work in it.  The CPU has no device plane, so the CPU client thread's XLA
    operations stand for the device's and the executions of the jitted
    ``megastep`` (its function's name must be ``megastep``) for its program
    events; everything else is what the chip's trace goes through."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    from chipbench import reduce
    from chipbench.reduce import Event, Trace
    jax.profiler.start_trace(d)
    body()
    jax.profiler.stop_trace()
    loaded = reduce.load(d)
    data = ProfileData.from_file(glob.glob(
        os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0])
    ops, mods = [], []
    skip = ("ThreadpoolListener", "SlinkyThreadPool", "ThunkExecutor",
            "end: ")
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                ev = Event(e.name, int(e.start_ns),
                           int(e.start_ns + e.duration_ns))
                if line.name.startswith("tf_XLA") and not \
                        e.name.startswith(skip):
                    ops.append(ev)
                elif e.name == f"PjitFunction({megastep.__name__})":
                    mods.append(Event("jit_megastep(7)", ev.start, ev.end))
    # a call can show as nested events: keep the outermost of each
    mods.sort(key=lambda e: (e.start, -e.end))
    mods = [e for i, e in enumerate(mods)
            if not any(o.start <= e.start and e.end <= o.end
                       for o in mods[:i])]
    cpu = Trace(ops={"/device:CPU:0": ops},
                modules={"/device:CPU:0": mods}, spans=loaded.spans,
                program_spans=loaded.program_spans)
    return loaded, cpu
