"""Chip benchmark of the serving path.

One run is one cell of ``BENCHMARK.json`` (a model configuration under a
traffic mix) in one process that holds the chip:

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, per-layer metric
or reference model lives in a file of its own, found by name:
``configs/<config>.json``, ``traffic/<traffic>.json``,
``metrics/<metric>.py`` and ``reference/<family>.py``.
"""
