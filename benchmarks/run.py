"""Benchmark harness: one module per paper table/figure + roofline view.

Prints ``name,us_per_call,derived`` CSV rows (one per measurement).
Select subsets with ``python -m benchmarks.run table1 fig8``.
"""
import sys

from benchmarks import (fig6_flicker, fig8_atmolight, kernels_bench,
                        roofline_report, table1_throughput)
from repro.core import env

SUITES = {
    "table1": table1_throughput.rows,
    "fig6": fig6_flicker.rows,
    "fig8": fig8_atmolight.rows,
    "kernels": kernels_bench.rows,
    "roofline": roofline_report.rows,
    # Ramping-load subset of table1 (elastic lane ladder vs fixed-max
    # fleet + switch latency) — cheap enough for the CI smoke job.
    "autoscale": table1_throughput.autoscale_rows,
    # Fleet subset of table1 (1 vs 2 simulated hosts; asserts the >= 1.8x
    # aggregate-fps scaling bar + zero EMA migrations).
    "fleet": table1_throughput.fleet_rows,
    # Zero-copy tick I/O subset of table1 (overlapped vs blocking serve at
    # sparse occupancy; asserts fps(on) >= fps(off) + D2H byte reduction).
    "overlap": table1_throughput.overlap_rows,
}


def main() -> None:
    env.enable_compile_cache()
    wanted = [a for a in sys.argv[1:] if a in SUITES] or list(SUITES)
    print("name,us_per_call,derived")
    for key in wanted:
        for name, us, derived in SUITES[key]():
            print(f"{name},{us:.1f},{derived}", flush=True)


if __name__ == "__main__":
    main()
