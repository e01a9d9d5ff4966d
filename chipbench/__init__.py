"""chipbench — the benchmark of paddle_tpu on the chip.

One command runs one cell once::

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
metric is a file of its own, found by the name in ``BENCHMARK.json``
(see ``PERF.md`` section 3 for the list of files a new cell adds).
"""
