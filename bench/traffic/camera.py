"""Open-loop cameras on their own clock.

Frame ``k`` of camera ``c`` is due at ``phase_c + k / fps`` seconds after
the window opens, whether or not earlier frames are done: the schedule is
a function of the mix and the seed alone, never of service time. Every
frame due inside the window is sent; the camera then stops.

Mix keys: ``streams``, ``fps``, ``phase`` (``"zero"``, or ``"uniform"``:
each camera's phase drawn uniformly within one frame period).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def schedule(mix: Dict, rng: np.random.Generator, seconds: float
             ) -> List[np.ndarray]:
    """Per camera, the due times (seconds from window open) of its frames."""
    fps, n = float(mix["fps"]), int(mix["streams"])
    period = 1.0 / fps
    if mix.get("phase", "zero") == "uniform":
        phases = rng.uniform(0.0, period, n)
    else:
        phases = np.zeros(n)
    out = []
    for phase in phases:
        due = phase + np.arange(int(np.ceil(seconds * fps)) + 1) * period
        out.append(due[due < seconds])
    return out
