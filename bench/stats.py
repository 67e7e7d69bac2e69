"""Order statistics over every sample of a run (never over chunk medians)."""
from __future__ import annotations

from typing import Iterable, Optional

import numpy as np


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (linear interpolation) of all ``values``;
    ``None`` when there are none."""
    arr = np.asarray(list(values), np.float64)
    if arr.size == 0:
        return None
    return float(np.percentile(arr, q))
