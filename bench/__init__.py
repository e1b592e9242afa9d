"""Chip benchmark of the repository: harness, traffic, configurations,
per-layer metric readers, trace reduction and a plain float32 reference.

Run one cell of ``BENCHMARK.json`` from the root of a checkout::

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
"""
