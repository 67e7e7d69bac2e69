"""Serve-loop spans: one timing system for ``ServeReport.phases`` and the
profiler's host timeline.

A span named ``<name>`` wraps one boundary of a serve loop. It opens
``jax.profiler.TraceAnnotation("repro.<name>")``, so under a profiler
trace the span sits on the host timeline, which shares its clock with
the device's ``XLA Ops`` line (an idle gap on the device can then be put
down to a program phase), and it adds its ``time.perf_counter()``
duration to the phase's key. Durations never use the scheduler's
injectable deadline clock: a test that fakes deadlines still gets real
durations. Both serve loops (``MultiStreamScheduler`` and
``StreamDispatcher``) use the same names where the phase exists:

============== ================= ==========================================
span           ``phases`` key    what it wraps
============== ================= ==========================================
spout          spout_s           ``Spout._emit``: padding, ``np.stack``, ids
                                 (not the source iterator's own wait)
stage          host_stage_s      lane ``device_put``s and splices (or the
                                 blocking path's host ``np.stack``), ids
inflight_wait  inflight_wait_s   the ``max_in_flight`` semaphore acquire
dispatch       dispatch_s        the step call alone (an enqueue: JAX
                                 returns before the device finishes)
device_wait    device_wait_s     completion thread: ``block_until_ready``
                                 on the lane's on-device slice
fetch          fetch_s           completion thread: ``np.asarray`` of the
                                 ready slice (D2H DMA + host delinearize)
============== ================= ==========================================

``monitor_queue_s`` is a counter, not a span (a frame's wait spans two
threads): each ``Monitor`` sums write time minus ``put`` time per frame
(``MonitorStats.queue_s``), and the serve loop adds a stream's total when
it finalizes the stream. Completion-thread keys are summed over threads,
so ``device_wait_s`` and ``fetch_s`` may exceed the serve's wall time.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Iterator, Optional

from jax.profiler import TraceAnnotation

# span name -> ServeReport.phases key ("stage" keeps its established key).
SPAN_KEYS: Dict[str, str] = {
    "spout": "spout_s",
    "stage": "host_stage_s",
    "inflight_wait": "inflight_wait_s",
    "dispatch": "dispatch_s",
    "device_wait": "device_wait_s",
    "fetch": "fetch_s",
}
MONITOR_QUEUE_KEY = "monitor_queue_s"
PHASE_KEYS = tuple(SPAN_KEYS.values()) + (MONITOR_QUEUE_KEY,)
TRACE_PREFIX = "repro."
_TRACE_NAMES = {name: TRACE_PREFIX + name for name in SPAN_KEYS}


class Phases:
    """Seconds by phase for one serve, written by its serve thread and its
    completion threads. ``lock`` lets the owner share the lock that already
    guards its other report counters."""

    def __init__(self, lock: Optional[threading.Lock] = None):
        self._lock = lock if lock is not None else threading.Lock()
        self._totals: Dict[str, float] = dict.fromkeys(PHASE_KEYS, 0.0)

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        key, trace_name = SPAN_KEYS[name], _TRACE_NAMES[name]
        t0 = time.perf_counter()
        try:
            with TraceAnnotation(trace_name):
                yield
        finally:
            self.add(key, time.perf_counter() - t0)

    def add(self, key: str, seconds: float) -> None:
        with self._lock:
            self._totals[key] += seconds

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._totals)


__all__ = ["MONITOR_QUEUE_KEY", "PHASE_KEYS", "Phases", "SPAN_KEYS",
           "TRACE_PREFIX"]
