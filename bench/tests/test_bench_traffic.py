"""Traffic generators: open-loop camera schedules and the backlog feed."""
import _small  # noqa: F401
import numpy as np
import pytest

from bench import harness


def _schedule(mix, seed=5, seconds=2.0):
    kind = harness.traffic_kind(mix["kind"])
    return kind.schedule(mix, np.random.default_rng(seed), seconds)


def test_camera_due_times_are_the_frame_clock():
    mix = harness.mix_spec("camera-30fps-b1")
    (due,) = _schedule(mix, seconds=20.0)
    assert len(due) == 600
    assert np.allclose(np.diff(due), 1 / 30)
    assert due[-1] < 20.0


def test_camera_phases_are_seeded_and_within_a_period():
    mix = {"kind": "camera", "streams": 6, "fps": 25, "phase": "uniform"}
    a, b = _schedule(mix, seed=9), _schedule(mix, seed=9)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert all(0 <= d[0] < 1 / 25 for d in a)
    assert len({d[0] for d in a}) == 6


def test_camera_due_times_do_not_depend_on_service_time():
    """A server that asks late gets the frame at once; one that asks early
    waits for the due time. Either way the due times are the schedule's."""
    now = [0.0]
    clock = lambda: now[0]                           # noqa: E731

    def sleep(dt):
        now[0] += dt

    due = np.arange(5) / 30
    rec = harness.StreamRecord("cam", due)
    it = harness.feed(rec, lambda k: k, t0=0.0, t_end=1.0, batch=1,
                      clock=clock, sleep=sleep)
    for k, service in enumerate([0.0, 0.2, 0.0, 0.0, 0.0]):
        assert next(it) == k
        now[0] += service                            # the server's tick
    assert list(rec.due) == list(due)
    late = 1 / 30 + 0.2                              # after the slow tick
    assert rec.t_call[1] == 0.0 and rec.t_pull[1] == due[1]  # early: waits
    assert rec.t_call[2] == rec.t_pull[2] == late    # late: gets it at once
    ctx = harness.RunContext(seconds=1.0, t0=0.0, t_end=1.0, t_done=1.0,
                             batch=1, streams=[rec], report=None, setup_s=0.0,
                             service_bytes=0, device_kind="")
    waits = ctx.admission_waits_ms()
    assert waits[:2] == [0.0, 0.0]
    assert waits[2:] == pytest.approx([(late - d) * 1e3 for d in due[2:]])


def test_frames_over_the_latency_limit_count_as_late():
    due = np.arange(6) / 30
    rec = harness.StreamRecord("cam", due)
    for k, lat in enumerate([0.010, 0.050, 0.070, 0.200, 0.066]):
        rec.sink[k] = due[k] + lat                   # frame 5 never comes
    ctx = harness.RunContext(seconds=1.0, t0=0.0, t_end=1.0, t_done=1.0,
                             batch=1, streams=[rec], report=None, setup_s=0.0,
                             service_bytes=0, device_kind="")
    assert ctx.late(66.7, drain_cap=10.0) == 2
    assert ctx.late(66.7, drain_cap=0.2) == 1        # frame 3 came after it
    assert ctx.delivered_per(0.25) == [4, 1, 0, 0]
    ctx.latency_limit_ms = 66.7
    late_pct = harness.metric_reader("late_frames_pct").read(ctx)
    assert late_pct == pytest.approx(100.0 * 3 / 6)  # 2 late, 1 never came
    ctx.latency_limit_ms = None
    assert harness.metric_reader("late_frames_pct").read(ctx) is None


def test_backlog_feed_stops_on_a_batch_boundary_after_the_window():
    now = [0.0]
    rec = harness.StreamRecord("clip", None)
    it = harness.feed(rec, lambda k: k, t0=0.0, t_end=1.0, batch=4,
                      clock=lambda: now[0], sleep=lambda dt: None)
    got = []
    for k in it:
        got.append(k)
        now[0] += 0.15
    assert len(got) % 4 == 0 and len(got) == 8
