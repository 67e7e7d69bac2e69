"""Chip benchmark of the dehazing service: one cell per run, driven by data.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
serves one cell of ``BENCHMARK.json`` through ``ElasticServer.serve_many``
on the chip and prints one JSON result line. Cells, configurations,
traffic mixes and per-layer metrics are files under this directory that
the harness finds by name (``workloads/``, ``configs/``, ``traffic/``,
``metrics/``).
"""
