"""A backlog: recorded footage replayed as fast as it is served.

Every frame of every stream is available when the window opens; each
stream sends frames until the window closes, stopping on a batch
boundary so that no partial batch is served. ``None`` as a schedule means
"no due times: send while the window is open".

Mix keys: ``streams``.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


def schedule(mix: Dict, rng: np.random.Generator, seconds: float
             ) -> List[Optional[np.ndarray]]:
    del rng, seconds
    return [None] * int(mix["streams"])
