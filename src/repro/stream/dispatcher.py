"""The dispatcher: paper §3.2 layers 2-4 collapsed onto SPMD workers.

Drives the jitted dehaze step over a stream of frame batches with:
  - a bounded in-flight window (backpressure, overlaps host I/O with device
    compute — JAX dispatch is async, so enqueueing batch k+1 while batch k
    executes gives the compute/transfer overlap the paper gets from
    component pipelining);
  - per-batch completion threads that block on device results and feed the
    Monitor out of order (exactly the paper's layer-4 → layer-5 hand-off)
    through the shared valid-only deferred-fetch helper
    (``stream.iobuf.fetch_valid`` — padding frames never cross the wire);
  - sequential state threading: the EMA state of batch k feeds batch k+1 on
    the *device* (no host round-trip), which preserves the paper's §3.3
    coherence chain across batches;
  - an optional zero-copy mode (``overlap=True``, README §Tick I/O &
    overlap): each batch is ``jax.device_put`` ahead of the call (async
    H2D, overlapping the in-flight batch's compute) and the step is built
    with full donation (``make_step(..., donate=True)``), so for aliasable
    wire dtypes (f32→f32, bf16→bf16) ``out.frames`` reuses the input
    buffer and the state chain allocates nothing per batch;
  - elastic worker simulation: N logical workers round-robin batches, a
    worker can be paused/killed to exercise straggler and failure paths.
"""
from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Iterable, Optional

import jax
import numpy as np

from repro.core.normalize import AtmoState
from repro.stream.iobuf import fetch_valid
from repro.stream.monitor import Monitor
from repro.stream.spans import Phases
from repro.stream.spout import FrameBatch


@dataclass
class DispatchStats:
    batches: int = 0
    frames: int = 0
    wall_s: float = 0.0
    # Batches dispatched through the zero-copy path (explicit async H2D +
    # donated step). 0 when the dispatcher runs the blocking oracle.
    overlap_batches: int = 0
    # Bytes fetched device->host by completions (valid-only always).
    d2h_bytes: int = 0

    @property
    def fps(self) -> float:
        return self.frames / self.wall_s if self.wall_s > 0 else 0.0


class StreamDispatcher:
    """Runs ``step(frames, frame_ids, state) -> DehazeOutput`` over a stream."""

    def __init__(self, step: Callable, monitor: Monitor,
                 max_in_flight: int = 4,
                 n_workers: int = 1,
                 worker_delay_s: Optional[Callable[[int], float]] = None,
                 overlap: bool = False):
        self._step = step
        self._monitor = monitor
        self._sem = threading.Semaphore(max_in_flight)
        self._n_workers = max(1, n_workers)
        self._worker_delay = worker_delay_s
        self._overlap = overlap
        self._completions: "queue.Queue" = queue.Queue()
        self._stats_lock = threading.Lock()
        # Serve-loop seconds by phase (the ``ServeReport.phases`` keys);
        # hand it to the Spout that feeds ``run``.
        self.phases = Phases(self._stats_lock)
        self.stats = DispatchStats()

    def run(self, batches: Iterable[FrameBatch], state: AtmoState) -> AtmoState:
        t0 = time.perf_counter()
        threads = []
        batch_idx = 0
        for fb in batches:
            with self.phases.span("stage"):
                if self._overlap:
                    # Async H2D ahead of the dispatch: the transfer of
                    # batch k+1 overlaps batch k's compute. With a donated
                    # step the device buffer is consumed by the call
                    # (out.frames aliases it when the dtype contract
                    # allows), so it is never reused across batches.
                    frames = jax.device_put(fb.frames)
                else:
                    frames = fb.frames
            with self.phases.span("inflight_wait"):
                self._sem.acquire()
            # State threading is sequential by construction: the step for
            # batch k is dispatched with the (device-resident, possibly
            # not-yet-computed) state output of batch k-1. JAX's async
            # dispatch pipelines them without blocking the host. With a
            # donated step the old state is consumed by this call — it is
            # dead here anyway (rebound to out.state below).
            with self.phases.span("dispatch"):
                out = self._step(frames, fb.frame_ids, state)
            state = out.state
            worker = batch_idx % self._n_workers
            th = threading.Thread(
                target=self._complete, args=(fb, out, worker), daemon=True)
            th.start()
            threads.append(th)
            batch_idx += 1
            self.stats.batches += 1
            self.stats.frames += fb.n_valid
            if self._overlap:
                self.stats.overlap_batches += 1
        for th in threads:
            th.join()
        self.stats.wall_s = time.perf_counter() - t0
        return jax.device_get(state)

    def _complete(self, fb: FrameBatch, out: Any, worker: int) -> None:
        try:
            # One completion mechanism for both serve paths: valid-only
            # deferred fetch (the old whole-batch np.asarray stalled on —
            # and shipped — the padding tail too).
            frames = fetch_valid(out.frames, fb.n_valid, phases=self.phases)
            if self._worker_delay is not None:
                time.sleep(self._worker_delay(worker))
            for i in range(fb.n_valid):
                self._monitor.put(int(fb.frame_ids[i]), frames[i])
            with self._stats_lock:
                self.stats.d2h_bytes += frames.nbytes
        finally:
            self._sem.release()
