"""Multi-tenant lane scheduler: continuous batching across video streams.

The paper's five-layer network (§3.2, Fig. 2) serves ONE video: spout →
transmission estimator → atmospheric-light estimator → haze-free generator
→ monitor. Its §5 future work — "coordinating atmospheric light across
multiple videos" and a cluster that "scales with the actual workload" —
is this module: N live videos multiplexed onto L *lanes* of one
fixed-shape ``(L, B, H, W, 3)`` device batch, stepped by the vmapped
component chain (``core.pipeline.make_multi_stream_step``), so the fleet
scales with users instead of serializing them.

Layer mapping, per lane:

  layer 1 (spout)        — one ``Spout`` per admitted stream assigns ids
                           from that stream's restart-safe cursor;
  layers 2-4 (components)— all lanes share ONE compiled program per tick;
                           each lane's §3.3 EMA state is one row of the
                           lane-batched ``AtmoState`` (its own coherent A
                           trajectory, bit-identical to a solo serve);
  layer 5 (monitor)      — one ``Monitor`` per stream restores that
                           stream's order and applies the paper's 20 ms
                           reader-skip rule independently of its peers.

Scheduling is *continuous batching* in the serving-system sense: a stream
is admitted into the first free lane the moment one is available, an
exhausted stream is evicted at the tick it ends (state + cursor written
back to the ``StreamStateStore``), and the freed lane is reused by the
next pending stream in the same tick. Unoccupied lanes are padded with
``frame_id = -1`` batches, which the masked EMA scans treat as identity —
a dead lane's state rides through every step unchanged and emits nothing.

**Requests.** A stream to serve is a :class:`StreamRequest` — stream id,
frame iterable, optional ``deadline`` and optional ``priority``. Legacy
positional tuples (``(sid, frames)`` / ``(sid, frames, deadline)``) are
coerced through :func:`_coerce_request` with a ``DeprecationWarning`` and
keep working this release.

**Admission policy.** The pending queue is ordered by
``(priority, deadline, arrival)``: lower priority values admit first
(default 0; negative jumps the whole default class), then earliest
deadline first within a priority class (deadline-less streams rank after
every deadlined one), and ties break by arrival order — so plain FIFO
callers see the exact pre-deadline behavior and a real-time stream never
queues behind a batch backfill.

**Deadline-aware eviction** (``evict_tardy_after``): a stream that is
*past its deadline* (``clock() >= deadline``) and has held a lane for
that many ticks while other streams queue is preempted — its cursor and
EMA state are checkpointed (the same restart-safe snapshot a crash would
use) and it requeues as deadline-less (it already missed its deadline, so
it loses EDF privilege and falls behind the waiting streams; FIFO among
its peers). Re-admission is gated on the old monitor draining, so the
sink still sees every frame exactly once, in order, and the resumed lane
continues the identical EMA trajectory.

**Elastic lane autoscaling** (``autoscaler``): the lane count walks a
precompiled ladder (``stream.autoscale``) from pending-queue depth and
occupancy. A ladder switch repacks the live lane state row-for-row
(``unpack_atmo_states`` → compact → ``pack``-style ``set_lane_state``),
so no stream loses its EMA trajectory or emits a frame twice, and the
target rung's step is always pre-warmed on a background thread — the
switch itself is a dictionary lookup, never a trace on the serve thread.
"""
from __future__ import annotations

import dataclasses
import heapq
import math
import threading
import time
import warnings
from typing import (Callable, Dict, Iterable, List, Optional, Tuple, Union)

import jax
import numpy as np

from repro.core.normalize import (AtmoState, get_lane_state,
                                  init_atmo_state_lanes, set_lane_state,
                                  unpack_atmo_states)
from repro.stream.iobuf import fetch_ready, fetch_valid, is_overlap_step
from repro.stream.monitor import DEADLINE_CLOCK, Monitor
from repro.stream.spans import MONITOR_QUEUE_KEY, Phases
from repro.stream.spout import FrameBatch, Spout
from repro.stream.state import StreamStateStore


@dataclasses.dataclass
class StreamRequest:
    """One stream to serve.

    ``frames`` is an iterable of ``(H, W, 3)`` float frames. ``deadline``
    is a value on the scheduler's ``clock`` timebase — by default
    :data:`repro.stream.monitor.DEADLINE_CLOCK` (``time.monotonic``
    seconds, NOT epoch seconds: produce deadlines as
    ``DEADLINE_CLOCK() + budget_s``, and note monotonic values are only
    comparable within one process). It requests earliest-deadline-first
    lane admission and, when eviction is enabled, marks when the stream
    counts as tardy. ``priority`` (lower = earlier, default 0) orders
    ahead of the deadline: a negative priority admits before the whole
    default class regardless of deadlines.
    """
    stream_id: str
    frames: Iterable[np.ndarray]
    deadline: Optional[float] = None
    priority: Optional[int] = None

    def admission_key(self, arrival: int) -> Tuple[float, float, int]:
        prio = 0 if self.priority is None else self.priority
        deadline = math.inf if self.deadline is None else self.deadline
        return (prio, deadline, arrival)


# Legacy request forms still accepted by ``serve_many`` / ``run``:
# (stream_id, frames) or (stream_id, frames, deadline). Coerced through
# ``_coerce_request`` with a DeprecationWarning.
StreamEntry = Union[StreamRequest,
                    Tuple[str, Iterable[np.ndarray]],
                    Tuple[str, Iterable[np.ndarray], Optional[float]]]
# sink(stream_id, frame_id, frame) — called in per-stream ascending order.
MultiSink = Callable[[str, int, np.ndarray], None]


def _coerce_request(entry: StreamEntry) -> StreamRequest:
    """Normalize a caller-supplied stream entry to a ``StreamRequest``.

    Positional tuples were the whole API before the request dataclass;
    they keep working this release but warn — the tuple union had already
    grown a third overload and the autoscaler needs named fields to grow
    more (priority, per-stream knobs) without another positional slot.
    """
    if isinstance(entry, StreamRequest):
        return entry
    if isinstance(entry, (tuple, list)) and len(entry) in (2, 3):
        warnings.warn(
            "positional (stream_id, frames[, deadline]) stream entries are "
            "deprecated and will be removed in v0.3; pass "
            "stream.StreamRequest(stream_id, frames, deadline=..., "
            "priority=...) instead",
            DeprecationWarning, stacklevel=3)
        return StreamRequest(entry[0], entry[1],
                             entry[2] if len(entry) > 2 else None)
    raise TypeError(
        f"expected StreamRequest or (stream_id, frames[, deadline]) tuple, "
        f"got {type(entry).__name__}")


@dataclasses.dataclass
class StreamReport:
    """Per-stream serving outcome (one row of ``ServeReport.per_stream``)."""
    stream_id: str
    frames: int
    skipped: int
    wall_s: float

    @property
    def fps(self) -> float:
        return self.frames / self.wall_s if self.wall_s > 0 else 0.0


@dataclasses.dataclass
class ServeReport:
    """Unified serving outcome: ``serve`` is the single-lane view of
    ``serve_many`` — one report type, ``per_stream`` populated by both, so
    callers never branch on which server method produced it.

    ``ladder_switches`` counts committed autoscale rung changes and
    ``evictions`` counts deadline preemptions (both 0 outside autoscale /
    eviction serving). The fleet tier (``stream.fleet``) aggregates
    per-host reports into one: ``n_hosts`` > 1 then, ``n_lanes`` sums the
    hosts' lanes, ``spillovers`` counts admissions that landed off the
    stream's preferred host because its lanes were full, and
    ``migrations`` counts sticky-placement violations — by construction
    always 0 (a live stream's EMA state never moves hosts); it is
    reported so serving code can *assert* that.
    """
    per_stream: Dict[str, StreamReport]
    frames: int          # total real frames stepped, all streams
    skipped: int         # total monitor skips, all streams
    wall_s: float
    n_lanes: int         # lanes at the end of the call (1 worker = 1 lane)
    ticks: int           # device steps issued
    admissions: int = 0  # lane admissions (>= streams when eviction requeues)
    ladder_switches: int = 0
    switch_wall_s: float = 0.0   # serve-thread seconds spent in rung switches
    evictions: int = 0
    n_hosts: int = 1
    spillovers: int = 0
    migrations: int = 0
    # Ladder rungs whose warm-up exhausted its attempts (autoscale serving
    # only; summed across hosts by the fleet tier). Non-zero means part of
    # the ladder is unreachable — serving that *expects* switches treats
    # it as a hard error (see launch/serve.py --expect-switches).
    warm_failures: int = 0
    # Ticks that took the zero-copy overlapped path (device-resident lane
    # buffer + donated state, README §Tick I/O & overlap). 0 on the
    # blocking path; a serve that *expected* overlap treats
    # overlap_ticks < ticks as a hard error (launch/serve.py
    # --expect-overlap — the silent-fallback gate).
    overlap_ticks: int = 0
    # Completion/finalizer threads still alive when the shutdown join
    # timed out. Always 0 in a healthy serve; non-zero means a monitor or
    # device fetch wedged and the report was returned without it.
    stragglers: int = 0
    # Bytes actually fetched device->host by completions (valid-only
    # slices on the overlapped path; whole batches, padding included, on
    # the blocking path — the bench rows report the ratio).
    d2h_bytes: int = 0
    # Serve-loop seconds by phase (``repro.stream.spans``: one key per
    # ``repro.<name>`` profiler span, on ``time.perf_counter``): serve
    # thread "spout_s", "host_stage_s", "inflight_wait_s", "dispatch_s";
    # completion threads "device_wait_s", "fetch_s" (summed across
    # threads); and "monitor_queue_s", the monitors' per-frame wait.
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def fps(self) -> float:
        """Throughput: total frames across streams per wall second."""
        return self.frames / self.wall_s if self.wall_s > 0 else 0.0

    # Fleet-level alias; identical to fps, kept for serving-code idiom.
    aggregate_fps = fps

    @property
    def n_workers(self) -> int:
        """Back-compat alias from the pre-unification single-stream report."""
        return self.n_lanes


# Back-compat alias: the multi-stream report is the report.
MultiServeReport = ServeReport


@dataclasses.dataclass
class _Resume:
    """Checkpoint a preempted stream carries back through the pending heap.

    Admission reads state + cursor from here (not the store — the store
    write happens on the background finalizer, and racing it would resume
    from a stale cursor). ``barrier`` is set when the old monitor has
    drained: re-admission waits on it so the sink's per-stream ordering
    survives the preemption."""
    state: AtmoState
    cursor: int
    barrier: threading.Event


class _Lane:
    """Host-side bookkeeping for one occupied lane."""
    __slots__ = ("request", "raw_it", "it", "monitor", "mon_thread", "start",
                 "frames_done", "ticks", "admitted_at")

    def __init__(self, request: StreamRequest, raw_it, it, monitor: Monitor,
                 mon_thread: threading.Thread, start: int,
                 admitted_at: float):
        self.request = request
        self.raw_it = raw_it          # the underlying frame iterator (requeue)
        self.it = it                  # the Spout batch iterator
        self.monitor = monitor
        self.mon_thread = mon_thread
        self.start = start
        self.frames_done = 0
        self.ticks = 0
        self.admitted_at = admitted_at

    @property
    def stream_id(self) -> str:
        return self.request.stream_id


class MultiStreamScheduler:
    """Drives ``step(frames (L,B,H,W,3), ids (L,B), state) -> DehazeOutput``
    over many live streams with lane admission/eviction/reuse.

    ``step`` is typically ``jax.jit(make_multi_stream_step(cfg))``; the
    scheduler itself is model-agnostic — it only assumes the lane axis and
    the padding-id contract (``frame_id < 0`` slots touch nothing).

    ``autoscaler`` (a ``stream.autoscale.LaneAutoscaler``) makes the lane
    count elastic: ``n_lanes`` then gives the *starting* rung and the
    scheduler walks the precompiled ladder. ``evict_tardy_after`` enables
    deadline-aware preemption (see the module docstring); ``clock`` is
    what deadlines are compared against — default
    :data:`repro.stream.monitor.DEADLINE_CLOCK` (``time.monotonic``), the
    same timebase the Monitor uses, so EDF ordering and tardy eviction
    cannot misfire across an NTP wall-clock step.
    """

    def __init__(self, step: Callable, store: StreamStateStore,
                 n_lanes: int, batch: int = 8, timeout_s: float = 0.020,
                 max_in_flight: int = 4, max_skipped_ids: int = 64,
                 autoscaler=None, evict_tardy_after: Optional[int] = None,
                 clock: Callable[[], float] = DEADLINE_CLOCK,
                 tick_delay_s: float = 0.0,
                 shutdown_timeout_s: float = 30.0):
        if n_lanes < 1:
            raise ValueError(f"n_lanes must be >= 1, got {n_lanes}")
        self._step = step
        self.store = store
        self.n_lanes = n_lanes
        self.batch = batch
        self.timeout_s = timeout_s
        self.max_skipped_ids = max_skipped_ids
        self._sem = threading.Semaphore(max_in_flight)
        self._autoscaler = autoscaler
        self._evict_tardy_after = evict_tardy_after
        self._clock = clock
        # Bound on the shutdown join over completion/finalizer threads: a
        # wedged monitor or device fetch must not hang run() forever — the
        # report returns with the straggler counted instead.
        self._shutdown_timeout_s = shutdown_timeout_s
        # Simulated per-tick device service time (seconds) on the serve
        # thread. 0 disables. The fleet benchmarks use this to model
        # device-bound hosts on the CPU container: with a fixed per-tick
        # cost, aggregate fps scales with how many hosts tick in parallel.
        self._tick_delay_s = tick_delay_s

    # -- lane lifecycle ----------------------------------------------------

    def _admit(self, lane_idx: int, req: StreamRequest,
               resume: Optional[_Resume], packed: AtmoState,
               sink: Optional[MultiSink]) -> AtmoState:
        sid = req.stream_id
        if resume is not None:
            start, state = resume.cursor, resume.state
        else:
            start, state = self.store.cursor(sid), self.store.get(sid)

        def write(fid: int, payload: np.ndarray) -> None:
            if sink is not None:
                sink(sid, fid, payload)

        monitor = Monitor(write, timeout_s=self.timeout_s, start_frame=start,
                          max_skipped_ids=self.max_skipped_ids)
        mon_thread = threading.Thread(target=monitor.run, daemon=True)
        mon_thread.start()
        raw_it = iter(req.frames)
        spout = Spout(raw_it, batch=self.batch, start_frame=start,
                      stream_id=sid, phases=self._phases)
        self._lanes[lane_idx] = _Lane(req, raw_it, iter(spout), monitor,
                                      mon_thread, start, time.perf_counter())
        self._admissions += 1
        return set_lane_state(packed, lane_idx, state)

    def _evict(self, lane_idx: int, packed: AtmoState,
               requeue: bool = False) -> None:
        """Free the lane NOW, finalize in the background.

        The lane's final EMA state is a functional snapshot of the packed
        state (safe to read later even after the lane is reassigned), so
        the expensive parts — waiting for in-flight completions that may
        still hold frames for this stream's monitor, draining it, and the
        blocking ``device_get`` — run in a finalizer thread while the main
        loop keeps ticking with the lane already reused. This is what
        keeps high-churn workloads (many short clips) pipelined instead of
        stalling every tick on an eviction barrier.

        ``requeue=True`` is the deadline-preemption path: the stream goes
        back onto the pending heap as deadline-less, carrying a ``_Resume``
        checkpoint (this same snapshot + cursor) whose barrier the
        finalizer sets once the old monitor has drained."""
        lane = self._lanes[lane_idx]
        self._lanes[lane_idx] = None
        final_state = get_lane_state(packed, lane_idx)
        cursor = lane.start + lane.frames_done
        waits = list(self._inflight)
        # Stamp the stream's wall NOW: the finalizer below also waits on
        # other lanes' in-flight ticks, which is scheduler bookkeeping, not
        # this stream's service time.
        wall_s = time.perf_counter() - lane.admitted_at
        barrier = threading.Event() if requeue else None

        def finalize() -> None:
            for th in waits:
                th.join()
            lane.monitor.close()
            lane.mon_thread.join(timeout=5.0)
            lane.monitor.drain()
            self._phases.add(MONITOR_QUEUE_KEY, lane.monitor.stats.queue_s)
            self.store.update(lane.stream_id, jax.device_get(final_state),
                              cursor)
            with self._report_lock:
                # A preempted stream serves in several segments: the
                # report accumulates frames/skips/wall across them.
                prev = self._reports.get(lane.stream_id)
                frames = lane.frames_done + (prev.frames if prev else 0)
                skipped = lane.monitor.stats.skipped \
                    + (prev.skipped if prev else 0)
                self._reports[lane.stream_id] = StreamReport(
                    stream_id=lane.stream_id, frames=frames, skipped=skipped,
                    wall_s=wall_s + (prev.wall_s if prev else 0.0))
            if barrier is not None:
                barrier.set()

        th = threading.Thread(target=finalize, daemon=True)
        th.start()
        self._finalizers.append(th)

        if requeue:
            self._evictions += 1
            # Past-deadline streams lose EDF privilege: requeue as
            # deadline-less (priority preserved), FIFO behind the class.
            req = StreamRequest(lane.stream_id, lane.raw_it, deadline=None,
                                priority=lane.request.priority)
            arrival = self._arrival
            self._arrival += 1
            self._push_requeue(req.admission_key(arrival), req,
                               _Resume(final_state, cursor, barrier))

    # -- pending-queue access (the fleet tier overrides these four to talk
    # -- to a shared cross-host queue instead of the local heap) -----------

    def _queue_depth(self) -> int:
        """Streams waiting for a lane (this scheduler's view)."""
        return len(self._pending)

    def _push_requeue(self, key, req: StreamRequest,
                      resume: "_Resume") -> None:
        """Return a preempted stream to the pending queue."""
        heapq.heappush(self._pending, (key, req, resume))

    def _wait_pending(self) -> bool:
        """No live lanes: ``True`` = pending work may still arrive, wait
        briefly and retry the admission loop; ``False`` = drained, exit.

        Single-host: every pending entry is a preempted stream still
        draining its previous segment's monitor — wait for the earliest
        barrier."""
        if self._pending:
            self._pending[0][2].barrier.wait(timeout=0.1)
            return True
        return False

    def _pop_ready(self):
        """Pop the best pending entry whose resume barrier (if any) is set;
        entries still draining their previous segment stay queued."""
        deferred, entry = [], None
        while self._pending:
            cand = heapq.heappop(self._pending)
            if cand[2] is None or cand[2].barrier.is_set():
                entry = cand
                break
            deferred.append(cand)
        for d in deferred:
            heapq.heappush(self._pending, d)
        return entry

    def _fill_lane(self, lane_idx: int, packed: AtmoState,
                   sink: Optional[MultiSink]
                   ) -> Tuple[Optional[FrameBatch], AtmoState]:
        """Next batch for a lane, chaining evictions and admissions: an
        exhausted stream is evicted and the lane immediately reused by the
        next pending stream (continuous batching)."""
        while True:
            if self._lanes[lane_idx] is None:
                entry = self._pop_ready()
                if entry is None:
                    return None, packed
                _, req, resume = entry
                packed = self._admit(lane_idx, req, resume, packed, sink)
                # Keep the shared view current immediately: if the new
                # stream's iterator raises below, the error-path eviction
                # in run() must see THIS stream's state in the lane, not
                # the previous tenant's.
                self._packed = packed
            fb = next(self._lanes[lane_idx].it, None)
            if fb is not None:
                return fb, packed
            self._evict(lane_idx, packed)

    # -- elastic lane count ------------------------------------------------

    def _switch_lanes(self, new_n: int, packed: AtmoState) -> AtmoState:
        """Repack live lane state onto a ``new_n``-lane batch.

        Occupied lanes compact to the low indices; each survivor's EMA
        state row moves with it (a functional gather/scatter — bit-exact,
        so per-stream A trajectories are indistinguishable from a serve
        that never switched). Host bookkeeping (_Lane objects, monitors,
        spouts) moves by reference. In-flight ticks are untouched: they
        hold the *old* packed arrays and their metas carry monitor
        references, not lane indices into the new layout."""
        occ = [i for i, ln in enumerate(self._lanes) if ln is not None]
        if len(occ) > new_n:
            raise ValueError(
                f"cannot shrink to {new_n} lanes with {len(occ)} occupied")
        states = unpack_atmo_states(packed)
        new_packed = init_atmo_state_lanes(new_n)
        for j, i in enumerate(occ):
            new_packed = set_lane_state(new_packed, j, states[i])
        self._lanes = [self._lanes[i] for i in occ] \
            + [None] * (new_n - len(occ))
        self.n_lanes = new_n
        return new_packed

    def _maybe_autoscale(self, packed: AtmoState) -> AtmoState:
        occupied = sum(1 for ln in self._lanes if ln is not None)
        target = self._autoscaler.observe(self._queue_depth(), occupied)
        if target is None or target == self.n_lanes or occupied > target:
            return packed
        t0 = time.perf_counter()
        # Dictionary lookup by contract: observe() only offers warm rungs.
        self._step = self._autoscaler.step_for(target)
        packed = self._switch_lanes(target, packed)
        self._autoscaler.commit(target, time.perf_counter() - t0)
        return packed

    def _evict_tardy(self, packed: AtmoState) -> None:
        """Deadline-aware preemption: a past-deadline stream that has held
        a lane for ``evict_tardy_after`` ticks while others queue is
        checkpointed and requeued (see ``_evict(requeue=True)``)."""
        for i, lane in enumerate(self._lanes):
            if self._queue_depth() == 0:
                return
            if (lane is not None and lane.request.deadline is not None
                    and lane.ticks >= self._evict_tardy_after
                    and self._clock() >= lane.request.deadline):
                self._evict(i, packed, requeue=True)

    # -- the serve loop ----------------------------------------------------

    def run(self, streams: Iterable[StreamEntry],
            sink: Optional[MultiSink] = None) -> ServeReport:
        requests = [_coerce_request(e) for e in streams]
        sids = [r.stream_id for r in requests]
        if len(set(sids)) != len(sids):
            # A duplicate id would race its predecessor's background
            # finalizer for the store cursor and the report slot. Resume a
            # stream with a second serve_many call instead — run() joins
            # all finalizers before returning, so the cursor is settled.
            dupes = sorted({s for s in sids if sids.count(s) > 1})
            raise ValueError(f"duplicate stream ids in one serve_many call: "
                             f"{dupes}")
        # Pending heap keyed (priority, deadline, arrival): lower priority
        # first, then earliest-deadline-first within the class,
        # deadline-less streams (deadline inf) after every deadlined one
        # and FIFO among themselves — with no deadlines or priorities this
        # is exactly the old FIFO deque.
        self._pending: List[tuple] = []
        for arrival, req in enumerate(requests):
            heapq.heappush(self._pending,
                           (req.admission_key(arrival), req, None))
        self._arrival = len(requests)
        self._lanes: List[Optional[_Lane]] = [None] * self.n_lanes
        self._inflight: List[threading.Thread] = []
        self._finalizers: List[threading.Thread] = []
        self._reports: Dict[str, StreamReport] = {}
        self._report_lock = threading.Lock()
        self._admissions = 0
        self._evictions = 0
        self._overlap_ticks = 0
        self._stragglers = 0
        self._d2h_bytes = 0
        self._phases = Phases(self._report_lock)

        packed = init_atmo_state_lanes(self.n_lanes)
        pad_frames: Optional[np.ndarray] = None       # (B, H, W, 3) zeros
        pad_ids = np.full((self.batch,), -1, np.int32)
        ticks = 0
        t0 = time.perf_counter()

        try:
            ticks = self._tick_loop(packed, pad_frames, pad_ids, sink)
        finally:
            # Normal exit or mid-serve error (e.g. a mismatched-resolution
            # stream): evict every live lane so already-served streams
            # flush their monitors and persist state + cursor, then wait
            # out all completion/finalizer threads.
            for i in range(len(self._lanes)):
                if self._lanes[i] is not None:
                    self._evict(i, self._packed)
            # Bounded join: the old code joined without a timeout, so a
            # wedged completion/finalizer daemon hung run() forever (and a
            # fast exit silently leaked them). One deadline covers the
            # whole set; survivors are counted, not waited out.
            deadline = time.perf_counter() + self._shutdown_timeout_s
            for th in self._inflight + self._finalizers:
                th.join(timeout=max(0.0, deadline - time.perf_counter()))
                if th.is_alive():
                    self._stragglers += 1
        wall = time.perf_counter() - t0
        reports = self._reports
        return ServeReport(
            per_stream=reports,
            frames=sum(r.frames for r in reports.values()),
            skipped=sum(r.skipped for r in reports.values()),
            wall_s=wall, n_lanes=self.n_lanes, ticks=ticks,
            admissions=self._admissions,
            ladder_switches=len(self._autoscaler.switches)
            if self._autoscaler is not None else 0,
            switch_wall_s=sum(s["wall_s"]
                              for s in self._autoscaler.switches)
            if self._autoscaler is not None else 0.0,
            evictions=self._evictions,
            warm_failures=self._autoscaler.warm_failures
            if self._autoscaler is not None else 0,
            overlap_ticks=self._overlap_ticks,
            stragglers=self._stragglers,
            d2h_bytes=self._d2h_bytes,
            phases=self._phases.snapshot())

    def _tick_loop(self, packed: AtmoState, pad_frames: Optional[np.ndarray],
                   pad_ids: np.ndarray, sink: Optional[MultiSink]) -> int:
        ticks = 0
        self._packed = packed

        while True:
            if self._evict_tardy_after is not None:
                self._evict_tardy(packed)
            fbs: List[Optional[FrameBatch]] = []
            for i in range(len(self._lanes)):
                fb, packed = self._fill_lane(i, packed, sink)
                self._packed = packed
                fbs.append(fb)
            live = [fb for fb in fbs if fb is not None]
            if not live:
                if self._wait_pending():
                    continue
                break

            if pad_frames is None:
                pad_frames = np.zeros_like(live[0].frames)
            if self._autoscaler is not None:
                self._autoscaler.ensure_warming(pad_frames.shape,
                                                pad_frames.dtype)
            for fb in live:
                if fb.frames.shape != pad_frames.shape:
                    raise ValueError(
                        f"stream {fb.stream_id!r} batch shape "
                        f"{fb.frames.shape} != lane shape {pad_frames.shape};"
                        " all multiplexed streams must share (H, W) and the"
                        " scheduler's frame batch")

            overlap = is_overlap_step(self._step)
            with self._phases.span("stage"):
                if overlap:
                    # Zero-copy path: upload only the live lanes into the
                    # persistent device buffer (padding lanes keep stale
                    # rows — id-masked from the EMA, never fetched).
                    # device_put + the donated splice dispatch
                    # asynchronously, so this H2D overlaps the in-flight
                    # tick's compute — which is why it runs BEFORE the
                    # in-flight window acquire below.
                    for i, fb in enumerate(fbs):
                        if fb is not None:
                            self._step.stage(i, fb.frames)
                    frames = None
                else:
                    frames = np.stack([fb.frames if fb is not None else
                                       pad_frames for fb in fbs])
                ids = np.stack([fb.frame_ids if fb is not None else pad_ids
                                for fb in fbs])
            metas = [(i, self._lanes[i].monitor, fb.frame_ids, fb.n_valid)
                     for i, fb in enumerate(fbs) if fb is not None]
            for i, fb in enumerate(fbs):
                if fb is not None:
                    self._lanes[i].frames_done += fb.n_valid
                    self._lanes[i].ticks += 1

            with self._phases.span("inflight_wait"):
                self._sem.acquire()
            with self._phases.span("dispatch"):
                if overlap:
                    # The state input is donated into this call: every
                    # read of `packed` (eviction snapshots, rung repacks)
                    # was dispatched before it, and nothing touches it
                    # after.
                    out = self._step.tick(ids, packed)
                else:
                    out = self._step(frames, ids, packed)
            if overlap:
                self._overlap_ticks += 1
            packed = out.state          # device-resident, possibly in flight
            self._packed = packed
            if self._tick_delay_s > 0.0:
                time.sleep(self._tick_delay_s)
            th = threading.Thread(target=self._complete,
                                  args=(metas, out, overlap), daemon=True)
            th.start()
            self._inflight.append(th)
            self._inflight = [t for t in self._inflight if t.is_alive()]
            ticks += 1

            if self._autoscaler is not None:
                packed = self._maybe_autoscale(packed)
                self._packed = packed

        return ticks

    def _complete(self, metas, out, overlap: bool = False) -> None:
        try:
            d2h = 0
            if overlap:
                # Valid-only D2H: per live lane, slice on device and fetch
                # just its real frames — padding lanes (and the padded
                # tail of live ones) never cross the wire. Lane k's slice
                # is dispatched only after lane k-1's fetch returns.
                for lane_idx, monitor, frame_ids, n_valid in metas:
                    lane_frames = fetch_valid(out.frames, n_valid,
                                              lane=lane_idx,
                                              phases=self._phases)
                    d2h += lane_frames.nbytes
                    for b in range(n_valid):
                        monitor.put(int(frame_ids[b]), lane_frames[b])
            else:
                frames = fetch_ready(out.frames, self._phases)
                d2h += frames.nbytes
                for lane_idx, monitor, frame_ids, n_valid in metas:
                    for b in range(n_valid):
                        monitor.put(int(frame_ids[b]), frames[lane_idx, b])
            with self._report_lock:
                self._d2h_bytes += d2h
        finally:
            self._sem.release()
