"""Shared helpers of the benchmark's tests: a cell run at a tiny size."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SEED = 2 ** 31 + 17                      # a seed above 32 signed bits
SMALL = {"config": {"height": 48, "width": 64, "pool_frames": 4}}
SMALL_MIX = {"dcp-1080p-backlog": {"streams": 2, "lanes": 2, "batch": 2},
             "cap-1080p-nav-b1": {}}


def run_small(cell, seconds=1.0, trace=False, dehaze=None, seed=SEED):
    from bench import harness
    overrides = {**SMALL, "mix": SMALL_MIX.get(cell, {}),
                 "dehaze": dehaze or {}}
    return harness.run(cell, seed, seconds, trace, require_chip=False,
                       overrides=overrides, log=lambda msg: None)
