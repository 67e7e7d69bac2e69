#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, at a cell's size.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3
    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 --sound

Runs the cell in this one process once per seed, on the chip, and prints
each run's compared numbers. By default the program runs its own
bfloat16 path (``io_dtype="bfloat16"``) in place of the float32 one the
configuration states: the control, which has to come out not correct.
``--sound`` runs the program as configured, for the lower readings. The
benchmark's own runs (``bench/run.py``) never run this.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sound", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from repro.core import env
    try:
        harness.check_chip(int(harness.cell_spec(args.workload)["chips"]))
    except harness.NoChip as e:
        print(f"bench/control.py: {e}", file=sys.stderr)
        return 1
    env.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    overrides = {} if args.sound else {"dehaze": {"io_dtype": "bfloat16"}}
    mode = "sound" if args.sound else "control"
    for seed in args.seeds:
        r = harness.run(args.workload, seed, args.seconds, False,
                        overrides=overrides, log=lambda msg: None)
        print(json.dumps({"mode": mode, "workload": args.workload,
                          "seed": seed, "correct": r["correct"],
                          "checks": r["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
