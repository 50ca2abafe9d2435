"""Benchmark of the closed-loop consolidation engine on one accelerator.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``; see ``run.py``.
"""
