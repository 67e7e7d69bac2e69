"""Elastic scaling + fault tolerance for the serving runtime.

The paper's cluster "automatically scales up and down based on the actual
workload" (§5). On a TPU fleet the analogous operations are:

  * ``ElasticServer.resize(n)``    — rebuild the device mesh over the
    surviving/new workers and re-shard the stream state (cheap: the state
    is a few bytes; model-based pipelines also re-shard params via
    ``jax.device_put`` with the new sharding).
  * checkpoint/restart             — the stream state store + frame cursor
    are snapshotted through ``repro.checkpoint``; a restarted server
    resumes mid-stream with the SAME coherent A trajectory, and the
    monitor cursor guarantees no frame is emitted twice.
  * straggler mitigation           — inherited from the Monitor timeout
    (paper's 20 ms rule) plus the dispatcher's bounded in-flight window.

On this CPU container "workers" are logical (host threads over one XLA
device); on a real fleet the resize hook swaps the jitted executable for
one compiled against the new mesh — the dry-run in launch/dryrun.py proves
those executables compile for every mesh we claim to support.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Callable, Iterable, List, Optional, Sequence

import jax
import numpy as np

from repro.core import (DehazeConfig, PlacementSpec, make_dehaze_step,
                        make_step, resolve_lane_native)
from repro.core import env as _env
from repro.stream import iobuf
from repro.stream.autoscale import LaneAutoscaler, ScalePolicy, ladder_rungs
from repro.stream.dispatcher import StreamDispatcher
from repro.stream.fleet import FleetScheduler, PlacementPolicy
from repro.stream.monitor import DEADLINE_CLOCK, Monitor
from repro.stream.scheduler import (MultiServeReport, MultiStreamScheduler,
                                    ServeReport, StreamEntry, StreamReport,
                                    _coerce_request)
from repro.stream.spans import MONITOR_QUEUE_KEY
from repro.stream.spout import Spout
from repro.stream.state import StreamStateStore


class _LRUStepCache:
    """Bounded jitted-step cache. The old module-global dict grew without
    bound across config sweeps (every ``DehazeConfig`` variant pins its
    executable forever); this keeps the ``maxsize`` most recently used.
    Shared by the single-stream and the multi-stream (lane-vmapped) step
    builders — the kind of step is part of the key.

    ``hits``/``misses`` and ``built_by`` (key -> ident of the thread that
    built the entry) exist so serving code can *assert* its compile
    discipline: the autoscale tests check every ladder rung beyond the
    starting one was built by the background warm thread, never the serve
    thread."""

    def __init__(self, maxsize: int):
        self.maxsize = maxsize
        self._d: "collections.OrderedDict" = collections.OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.built_by: dict = {}

    def get(self, key, build: Callable):
        with self._lock:
            if key in self._d:
                self._d.move_to_end(key)
                self.hits += 1
                return self._d[key]
            self.misses += 1
        step = build()                       # build outside the lock (slow)
        with self._lock:
            if key not in self._d:
                self.built_by[key] = threading.get_ident()
            self._d[key] = step
            self._d.move_to_end(key)
            while len(self._d) > self.maxsize:
                self._d.popitem(last=False)
        return step

    def __len__(self) -> int:
        return len(self._d)


_STEP_CACHE = _LRUStepCache(maxsize=_env.step_cache_size())


def _cached_step(cfg: DehazeConfig, donate=False):
    """One jitted executable per (config, donation contract) — servers
    with the same config (e.g. benchmark sweeps over worker counts) share
    compilations. ``donate`` is the ``make_step`` donation contract; a
    donating executable must never be handed to a caller that reuses its
    input buffers, hence the key."""
    def build():
        if donate is not False:
            return make_step(cfg, PlacementSpec.single(), donate=donate)
        return jax.jit(make_dehaze_step(cfg))
    return _STEP_CACHE.get(("single", cfg, donate), build)


def _cached_multi_step(cfg: DehazeConfig, n_lanes: int, lane_native: bool,
                       placement: Optional[PlacementSpec] = None,
                       donate=False):
    """Multi-stream step (lane-native megakernel or lane-vmapped chain),
    same bounded cache.

    The key is ``(cfg, n_lanes, lane_native, placement, donate)``: a
    ``serve_many`` resize, a ``REPRO_LANE_NATIVE`` toggle, a different
    axis placement, or a different donation contract between calls must
    never reuse a stale compiled step — the old ``("multi", cfg)`` key
    did exactly that, handing a 4-lane fleet the executable (and, for
    lane-native, the grid/tuning resolution) built for a different lane
    count or the other dispatch path. ``jax.jit`` still specializes per
    input shape underneath; changing the lane count mid-fleet costs a
    recompile (see the ROADMAP lane-autoscaling follow-on).

    ``n_hosts`` is normalized out of the key: the device step is
    host-count agnostic (the fleet tier schedules hosts above it), so a
    2-host fleet reuses the executable its 1-host twin compiled.
    ``donate`` is NOT normalized out: ``"state"`` builds the tick-step
    contract the overlapped serve path donates its EMA chain through
    (``make_step`` docs)."""
    if placement is None:
        placement = PlacementSpec.lane_batched()
    if placement.n_hosts != 1:
        placement = dataclasses.replace(placement, n_hosts=1)

    def build():
        if donate is not False:
            return make_step(cfg, placement, lane_native=lane_native,
                             donate=donate)
        return jax.jit(make_step(cfg, placement, lane_native=lane_native))
    return _STEP_CACHE.get(
        ("multi", cfg, n_lanes, lane_native, placement, donate), build)


def _resolve_overlap(tick_overlap: Optional[bool]) -> bool:
    """Should this serve call take the zero-copy overlapped tick path?

    Explicit argument wins; ``None`` defers to ``REPRO_TICK_OVERLAP``
    (off when unset — the blocking path is the long-standing default and
    the parity oracle). Either way the request is honored only when the
    backend supports buffer donation; a forced-but-unsupported overlap
    falls back to blocking, which ``ServeReport.overlap_ticks`` exposes
    and ``launch/serve.py --expect-overlap`` turns into a hard failure.
    """
    req = tick_overlap if tick_overlap is not None else _env.tick_overlap()
    return bool(req) and iobuf.donation_supported()


class ElasticServer:
    """Serves dehazing streams with an elastically sized worker pool."""

    def __init__(self, cfg: DehazeConfig, n_workers: int = 1,
                 batch: int = 8, timeout_s: float = 0.020,
                 max_in_flight: int = 4,
                 worker_delay_s: Optional[Callable[[int], float]] = None):
        self.cfg = cfg
        self.batch = batch
        self.timeout_s = timeout_s
        self.max_in_flight = max_in_flight
        self.store = StreamStateStore()
        self._worker_delay = worker_delay_s
        self._step = _cached_step(cfg)
        self.n_workers = n_workers
        # Last FleetScheduler used by a multi-host serve_many — exposes the
        # sticky-placement ledger and admission log for callers/tests.
        self.last_fleet: Optional[FleetScheduler] = None

    def resize(self, n_workers: int) -> None:
        """Elastic scale up/down. State survives; executables are reused
        (single-host) or recompiled against the new mesh (fleet)."""
        self.n_workers = max(1, n_workers)

    def serve(self, frames: Iterable[np.ndarray], stream_id: str = "default",
              sink: Optional[Callable[[int, np.ndarray], None]] = None,
              tick_overlap: Optional[bool] = None) -> ServeReport:
        """Serve one stream through the dispatcher.

        ``tick_overlap`` opts this call into the zero-copy path: explicit
        async H2D per batch plus a fully donated step (state always;
        frames too when ``cfg.io_dtype`` aliases the resolved output
        dtype), with valid-only D2H on completion. ``None`` defers to
        ``REPRO_TICK_OVERLAP`` (default off). Outputs are bit-identical
        either way — donation changes buffer reuse, not values.
        """
        out_frames: List[int] = []

        def write(fid: int, payload: np.ndarray) -> None:
            out_frames.append(fid)
            if sink is not None:
                sink(fid, payload)

        overlap = _resolve_overlap(tick_overlap)
        step = _cached_step(self.cfg, donate=True) if overlap else self._step
        start = self.store.cursor(stream_id)
        monitor = Monitor(write, timeout_s=self.timeout_s, start_frame=start)
        dispatcher = StreamDispatcher(
            step, monitor, max_in_flight=self.max_in_flight,
            n_workers=self.n_workers, worker_delay_s=self._worker_delay,
            overlap=overlap)
        spout = Spout(frames, batch=self.batch, start_frame=start,
                      stream_id=stream_id, phases=dispatcher.phases)

        import threading
        mon_thread = threading.Thread(target=monitor.run, daemon=True)
        mon_thread.start()
        t0 = time.perf_counter()
        state = dispatcher.run(iter(spout), self.store.get(stream_id))
        monitor.close()
        mon_thread.join(timeout=5.0)
        monitor.drain()
        wall = time.perf_counter() - t0
        dispatcher.phases.add(MONITOR_QUEUE_KEY, monitor.stats.queue_s)

        cursor = start + dispatcher.stats.frames
        self.store.update(stream_id, state, cursor)
        rep = StreamReport(stream_id=stream_id,
                           frames=dispatcher.stats.frames,
                           skipped=monitor.stats.skipped, wall_s=wall)
        return ServeReport(
            per_stream={stream_id: rep},
            frames=rep.frames, skipped=rep.skipped, wall_s=wall,
            n_lanes=self.n_workers, ticks=dispatcher.stats.batches,
            overlap_ticks=dispatcher.stats.overlap_batches,
            d2h_bytes=dispatcher.stats.d2h_bytes,
            phases=dispatcher.phases.snapshot())

    def serve_many(self, streams: Sequence[StreamEntry],
                   n_lanes: Optional[int] = None,
                   sink: Optional[Callable[[str, int, np.ndarray], None]]
                   = None, autoscale: bool = False,
                   policy: Optional[ScalePolicy] = None,
                   clock: Callable[[], float] = DEADLINE_CLOCK,
                   n_hosts: int = 1,
                   placement: Optional[PlacementSpec] = None,
                   placement_policy: PlacementPolicy = "first-fit",
                   host_delay_s: float = 0.0,
                   tick_overlap: Optional[bool] = None) -> MultiServeReport:
        """Serve N videos concurrently via lane-batched continuous batching.

        ``streams`` is a sequence of :class:`~repro.stream.StreamRequest`
        (stream id, frames, optional ``deadline`` for
        earliest-deadline-first admission when lanes are scarce, optional
        ``priority``); legacy ``(stream_id, frames[, deadline])`` tuples
        are coerced with a ``DeprecationWarning``. All streams must share
        the same (H, W) resolution (the lane batch has one fixed device
        shape). ``n_lanes`` defaults to one lane per stream; with fewer
        lanes than streams the scheduler queues the surplus and admits
        them as lanes free up (eviction + reuse).

        ``autoscale=True`` makes the lane count elastic: ``n_lanes``
        becomes the *cap*, the serve starts at the smallest rung of
        ``policy.rungs`` (capped ladder — see ``autoscale.ladder_rungs``)
        and walks up/down with queue depth under hysteresis, with the
        other rungs precompiled on a background thread so a switch never
        traces on the serve thread. Passing a ``policy`` without
        ``autoscale`` still applies its ``evict_tardy_after``
        deadline-aware eviction at a fixed lane count.

        With a fused-covered config the device step is the *lane-native*
        megakernel — all L lanes fold into one ``pallas_call`` grid, so a
        tick costs one kernel launch instead of L (env
        ``REPRO_LANE_NATIVE=0`` forces the vmapped path back).

        Per-stream semantics match N sequential :meth:`serve` calls to
        float32 round-off (exactly, on the fused path; the vmapped staged
        XLA program may fuse FMAs differently, <= ~2 ULP) — same EMA
        trajectories (each lane scans its own causal chain), same monitor
        ordering + timeout-skip rules, same restart-safe cursors in
        ``self.store``. Stream ids must be unique per call (resume a
        stream with a follow-up call). The device sees ONE
        ``(L, B, H, W, 3)`` program per tick instead of N serialized
        streams, which is where the aggregate-fps win comes from.

        Frames travel at their wire dtype end-to-end: a uint8 stream stays
        uint8 through the spout, the scheduler's lane batches and the
        ladder warm-ups, and is only upcast in-VMEM by the kernels
        (``cfg.io_dtype`` declares the contract; ``cfg.out_dtype`` the
        output side). Both fields are part of the frozen config and hence
        of every step-cache key — toggling the ingest dtype can never
        reuse a step compiled for another dtype.

        ``n_hosts > 1`` (or a ``placement`` with ``n_hosts > 1``) serves
        the same streams through a :class:`~repro.stream.FleetScheduler`:
        ``n_hosts`` host-level schedulers behind one global-EDF front door,
        with sticky stream→host placement (EMA state never migrates) and
        spillover admission once a host's lanes fill. ``n_lanes`` is then
        the *per-host* lane count; ``placement_policy`` picks each fresh
        stream's preferred host; ``host_delay_s`` simulates per-tick device
        service time on each host (fleet benchmarks). Per-stream outputs,
        EMA trajectories and cursors stay bit-identical to the single-host
        serve — only which host runs a stream changes.

        ``tick_overlap`` opts into the zero-copy overlapped tick path
        (README §Tick I/O & overlap): the lane batch lives on device in a
        per-serve (per-host, for fleets) buffer, live lanes are staged by
        async per-lane ``device_put`` + a donated splice, the EMA state
        chain is donated tick-to-tick, and completions fetch valid frames
        only. ``None`` defers to ``REPRO_TICK_OVERLAP`` (default off —
        the blocking path stays the parity oracle). Per-stream outputs
        are bit-identical on both paths; ``ServeReport.overlap_ticks``
        records which one actually ran.
        """
        # Coerce HERE (not in the scheduler) and with a plain loop (not a
        # comprehension, which owns its own frame on CPython < 3.12): the
        # deprecation warning's stacklevel then lands on the caller that
        # actually passed the legacy tuple.
        coerced = []
        for s in streams:
            coerced.append(_coerce_request(s))
        streams = coerced
        if not streams:
            return MultiServeReport(per_stream={}, frames=0, skipped=0,
                                    wall_s=0.0, n_lanes=0, ticks=0,
                                    admissions=0)
        if placement is None:
            placement = PlacementSpec.lane_batched(n_hosts=n_hosts)
        else:
            placement.validate()
            n_hosts = placement.n_hosts
        if placement.sharded:
            raise ValueError(
                "serve_many drives local lane batches; mesh-sharded "
                "placements go through core.make_step(cfg, placement, mesh) "
                "with the launch tooling")
        if not placement.lanes:
            raise ValueError("serve_many needs a lane placement; use "
                             "PlacementSpec.lane_batched(...)")
        lanes = n_lanes if n_lanes is not None \
            else max(1, -(-len(streams) // n_hosts))
        lane_native = resolve_lane_native(self.cfg)
        scaler = None
        evict_after = policy.evict_tardy_after if policy is not None else None
        pol = policy if policy is not None else ScalePolicy()
        overlap = _resolve_overlap(tick_overlap)

        def base_step_for(n: int):
            return _cached_multi_step(self.cfg, n, lane_native, placement,
                                      donate="state" if overlap else False)

        def mk_step_for(_host: int = 0):
            """Per-host step factory. On the overlapped path each host
            gets its OWN TickBufferPool — the device frame buffer belongs
            to one serve loop — while the donated jitted steps underneath
            still share the bounded cache fleet-wide."""
            if not overlap:
                return base_step_for
            return iobuf.TickBufferPool(base_step_for).adapter

        def mk_scaler(host: int = 0) -> LaneAutoscaler:
            return LaneAutoscaler(mk_step_for(host),
                                  ladder_rungs(pol.rungs, lanes),
                                  policy=pol)

        if autoscale:
            evict_after = pol.evict_tardy_after

        if n_hosts > 1:
            factory = mk_scaler if autoscale else None
            fleet = FleetScheduler(
                base_step_for(lanes), self.store, n_hosts=n_hosts,
                n_lanes=lanes,
                batch=self.batch, timeout_s=self.timeout_s,
                max_in_flight=self.max_in_flight,
                autoscaler_factory=factory, evict_tardy_after=evict_after,
                clock=clock, placement_policy=placement_policy,
                tick_delay_s=host_delay_s,
                step_factory=((lambda h: mk_step_for(h)(lanes))
                              if overlap else None))
            self.last_fleet = fleet          # placements/log for callers
            return fleet.run(streams, sink=sink)

        if autoscale:
            scaler = mk_scaler()
            step = scaler.acquire_initial()
            lanes = scaler.rung
        else:
            step = mk_step_for()(lanes)
        scheduler = MultiStreamScheduler(
            step, self.store, n_lanes=lanes,
            batch=self.batch, timeout_s=self.timeout_s,
            max_in_flight=self.max_in_flight, autoscaler=scaler,
            evict_tardy_after=evict_after, clock=clock,
            tick_delay_s=host_delay_s)
        return scheduler.run(streams, sink=sink)
