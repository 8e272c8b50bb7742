"""Find a cell's configuration, traffic, metric readers and reference by
the names ``BENCHMARK.json`` gives them.

Every loader takes the checkout's root directory, so a cell whose files
exist only somewhere else (a test's temporary directory) loads the same way.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from types import ModuleType

ROOT = pathlib.Path(__file__).resolve().parents[1]
PACKAGE = "chipbench"


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(pathlib.Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def cell(bench: dict, workload: str) -> dict:
    for c in bench["workloads"]:
        if c["name"] == workload:
            return c
    raise KeyError(f"no cell {workload!r} in BENCHMARK.json")


def config(bench: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            with open(pathlib.Path(root) / c["file"]) as f:
                conf = json.load(f)
            conf["name"] = name
            return conf
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, root: pathlib.Path = ROOT) -> dict:
    with open(pathlib.Path(root) / PACKAGE / "traffic" / f"{name}.json") as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def metrics_for(bench: dict, workload: str, *, trace: bool) -> list:
    """The metric entries a run of ``workload`` reports: the end-to-end
    ones without the trace, the per-layer ones with it."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def _module(path: pathlib.Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: pathlib.Path = ROOT) -> ModuleType:
    """``metrics/<name>.py``: a module with ``read(ctx) -> float | None``."""
    path = pathlib.Path(root) / PACKAGE / "metrics" / f"{name}.py"
    return _module(path, f"{PACKAGE}_metric_{name.replace('.', '_')}")


def reference(family: str, root: pathlib.Path = ROOT) -> ModuleType:
    """``reference/<family>.py``: the plain float32 model of a family."""
    path = pathlib.Path(root) / PACKAGE / "reference" / f"{family}.py"
    return _module(path, f"{PACKAGE}_reference_{family}")


def peaks(kind: str, root: pathlib.Path = ROOT) -> dict:
    """The published peaks of one chip of ``kind``; an unknown kind is an
    error, never a default."""
    with open(pathlib.Path(root) / PACKAGE / "peaks.json") as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]
