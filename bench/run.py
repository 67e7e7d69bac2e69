#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Serves the cell through ``ElasticServer.serve_many`` in this one process,
which holds the chip, and prints one JSON line last on stdout (see
``bench/harness.py``). Exits nonzero, printing no result, without an
accelerator, with fewer chips than the cell asks for, or without the
program (``src/repro``) beside ``bench/``.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
