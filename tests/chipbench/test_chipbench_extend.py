"""A configuration, a traffic mix and a per-layer metric are added as new
files plus new entries in ``BENCHMARK.json``, with no edit to a file the
package already has: the loaders find them by name under the checkout's
root, here a temporary directory that holds the only copies."""
import json
import textwrap
from types import SimpleNamespace

from chipbench import spec
from chipbench_smoke import ROOT


def test_config_and_mix_that_exist_only_in_the_temp_dir(smoke_root):
    assert not (ROOT / "chipbench" / "configs" / "qwen-smoke.json").exists()
    assert not (ROOT / "chipbench" / "traffic" / "open.json").exists()
    bench = spec.load_benchmark(smoke_root)
    cell = spec.cell(bench, "qwen-smoke.open")
    conf = spec.config(bench, cell["config"], smoke_root)
    mix = spec.traffic(cell["traffic"], smoke_root)
    assert conf["model"]["hidden_size"] == 64
    assert mix["loop"] == "open" and mix["name"] == "open"


def test_new_metric_reader_is_found_by_name(tmp_path):
    (tmp_path / "chipbench" / "metrics").mkdir(parents=True)
    (tmp_path / "chipbench" / "metrics" / "rounds_seen.py").write_text(
        textwrap.dedent("""
            def read(ctx):
                return float(ctx.rounds) if ctx.rounds else None
        """))
    reader = spec.metric_reader("rounds_seen", tmp_path)
    assert reader.read(SimpleNamespace(rounds=7)) == 7.0
    assert reader.read(SimpleNamespace(rounds=0)) is None


def test_metrics_for_follow_the_workloads_lists():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = lambda w, t: [m["name"] for m in
                          spec.metrics_for(bench, w, trace=t)]
    assert "ttft_p90_ms" in names("qwen2.5-32b.chat", False)
    assert "ttft_p90_ms" not in names("mamba2-2.7b.decode", False)
    assert "probe_len_p99" not in names("mamba2-2.7b.decode", True)
    for cell in bench["workloads"]:
        assert "setup_s" in names(cell["name"], False)
        assert names(cell["name"], True)


def test_every_named_file_of_the_benchmark_exists():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        conf = spec.config(bench, c["name"])
        assert (ROOT / "chipbench" / "reference" /
                f"{conf['family']}.py").exists()
        ref = spec.reference(conf["family"])
        assert callable(ref.logits) and callable(ref.lane_flops)
    for cell in bench["workloads"]:
        spec.traffic(cell["traffic"])
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]).read)
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
