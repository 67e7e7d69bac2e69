"""Serve-loop spans (``repro.stream.spans``): the keys of
``ServeReport.phases``, their ``repro.*`` events on the profiler's host
timeline, and the monitor's queue counter."""
import glob
import itertools
import os
import sys
import threading
import time

import numpy as np
import pytest

import jax

from repro.core import DehazeConfig
from repro.stream import ElasticServer, Monitor, StreamRequest
from repro.stream.iobuf import donation_supported
from repro.stream.spans import PHASE_KEYS, SPAN_KEYS, TRACE_PREFIX, Phases

SERVE_THREAD_KEYS = ("spout_s", "host_stage_s", "inflight_wait_s",
                     "dispatch_s")


def _cfg():
    return DehazeConfig(kernel_mode="ref", gf_radius=2, update_period=2)


def _videos(n, frames=7, seed=3):
    rng = np.random.default_rng(seed)
    return [[rng.random((12, 16, 3)).astype(np.float32)
             for _ in range(frames)] for _ in range(n)]


def _serve(entry, overlap, clock=None):
    """One small serve through ``serve`` or ``serve_many`` (2 lanes)."""
    srv = ElasticServer(_cfg(), batch=2, timeout_s=5.0)
    if entry == "serve":
        return srv.serve(iter(_videos(1)[0]), tick_overlap=overlap)
    kw = {} if clock is None else {"clock": clock}
    return srv.serve_many([StreamRequest(f"s{i}", iter(v))
                           for i, v in enumerate(_videos(2))],
                          n_lanes=2, tick_overlap=overlap, **kw)


def test_the_documented_keys():
    assert PHASE_KEYS == ("spout_s", "host_stage_s", "inflight_wait_s",
                          "dispatch_s", "device_wait_s", "fetch_s",
                          "monitor_queue_s")
    assert set(SERVE_THREAD_KEYS) < set(SPAN_KEYS.values())


@pytest.mark.parametrize("entry,overlap",
                         itertools.product(["serve", "serve_many"],
                                           [True, False]))
def test_every_serve_reports_every_phase(entry, overlap):
    if overlap and not donation_supported():
        pytest.skip("backend does not honor donate_argnums")
    rep = _serve(entry, overlap)
    assert rep.overlap_ticks == (rep.ticks if overlap else 0)
    assert set(rep.phases) == set(PHASE_KEYS)
    assert all(v >= 0.0 for v in rep.phases.values())
    assert rep.phases["fetch_s"] > 0.0
    assert rep.phases["dispatch_s"] > 0.0
    assert sum(rep.phases[k] for k in SERVE_THREAD_KEYS) <= rep.wall_s


def test_phases_ignore_a_faked_deadline_clock():
    """Deadlines run on the injectable clock; durations never do."""
    jumps = itertools.count(step=1000.0)
    rep = _serve("serve_many", None, clock=lambda: next(jumps))
    assert rep.frames == 14
    assert sum(rep.phases[k] for k in SERVE_THREAD_KEYS) <= rep.wall_s


def _trace_sums(log_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    sums = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(TRACE_PREFIX):
                    sums[e.name] = sums.get(e.name, 0.0) \
                        + e.duration_ns * 1e-9
    return sums


def test_spans_land_on_the_profiler_timeline(tmp_path):
    """Each span leaves ``repro.<name>`` host events whose summed duration
    is the report's phase, within 20 % or 2 ms."""
    if not donation_supported():
        pytest.skip("backend does not honor donate_argnums")
    with jax.profiler.trace(str(tmp_path)):
        rep = _serve("serve_many", True)
    sums = _trace_sums(str(tmp_path))
    assert set(sums) == {TRACE_PREFIX + name for name in SPAN_KEYS}
    for name, key in SPAN_KEYS.items():
        got, want = sums[TRACE_PREFIX + name], rep.phases[key]
        assert abs(got - want) <= max(0.2 * want, 2e-3), (name, got, want)


@pytest.mark.parametrize("order,waited", [((0, 1, 2), ()),
                                          ((1, 0, 2), (1,)),
                                          ((2, 1, 0), (2, 1))])
def test_monitor_queue_counts_only_frames_that_waited(order, waited):
    """A frame put ahead of a missing predecessor accrues the wait; a
    frame written as soon as it is put accrues (almost) nothing."""
    got = []
    mon = Monitor(lambda fid, _: got.append(fid), timeout_s=60.0)
    pause = 0.05
    for fid in order:
        mon.put(fid, None)
        mon.poll()
        time.sleep(pause)
    assert got == [0, 1, 2]
    # Frame f waited from its put until frame 0's put, which came
    # order.index(0) - order.index(f) pauses later.
    expect = sum(order.index(0) - order.index(f) for f in waited) * pause
    assert expect <= mon.stats.queue_s <= 1.5 * expect + 0.02


def test_phases_lose_no_update_across_threads():
    """Completion threads and the serve thread add into one ``Phases``."""
    phases, n_threads, n_adds = Phases(), 16, 2000

    def work():
        for _ in range(n_adds):
            phases.add("fetch_s", 1.0)
            with phases.span("device_wait"):
                pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    got = phases.snapshot()
    assert got["fetch_s"] == n_threads * n_adds
    assert got["device_wait_s"] > 0.0
