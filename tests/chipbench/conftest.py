"""Fixtures of the benchmark's CPU tests; the cells they build are in
``chipbench_smoke``."""
import pytest

from chipbench_smoke import write_smoke_root


@pytest.fixture(scope="module")
def smoke_root(tmp_path_factory):
    return write_smoke_root(tmp_path_factory.mktemp("smoke"))


@pytest.fixture
def on_cpu(monkeypatch):
    """Let a run proceed on the CPU: the test, not the program, steers the
    harness's look for a chip."""
    from chipbench import harness
    monkeypatch.setattr(harness, "device_check", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    # tests never turn the persistent compilation cache on
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
