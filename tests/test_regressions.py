"""Regression tests for targeted bugfixes (no hypothesis dependency).

Covers: empty-batch EMA state round-trips (spout tail / elastic drain),
``resolve_mode`` rejecting unknown ``REPRO_KERNEL_MODE`` values instead of
silently taking the compiled-Pallas branch, the fused megakernel's
``frames_per_block`` degrading to the largest dividing tile instead of 1,
spout tail padding being tagged ``frame_id = -1`` and masked out of
the EMA recurrence (it used to carry *future real* ids, double-advancing
the coherence state when the real frames with those ids arrived),
``tuning.autotune`` refusing to persist the built-in DEFAULTS as a
measured winner when every candidate raises, the serving stack defaulting
every deadline comparison to one monotonic clock (scheduler/fleet/
``serve_many`` used wall-clock ``time.time`` while the Monitor used
``time.monotonic`` — an NTP step could evict lanes or reorder EDF
admission spuriously), and ``LaneAutoscaler`` warm-up failures being
surfaced (logged, retried once, reported) instead of silently never
offering the rung.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ema_scan, ema_scan_associative, init_atmo_state
from repro.core.normalize import AtmoState
from repro.kernels import ops
from repro.kernels.fused import _resolve_frames_per_block
from repro.stream import Spout


# --- empty-batch EMA state round-trip ----------------------------------------

@pytest.mark.parametrize("scan", [ema_scan, ema_scan_associative],
                         ids=["scan", "associative"])
def test_empty_batch_preserves_uninitialized_state(scan):
    """A zero-length batch must NOT flip ``initialized``: the next real
    first frame has to *replace* the white-light bootstrap placeholder, not
    EMA-blend with it."""
    state = init_atmo_state()
    empty = jnp.zeros((0, 3), jnp.float32)
    ids = jnp.zeros((0,), jnp.int32)
    a_seq, out = scan(empty, ids, state, period=4, lam=0.3)
    assert a_seq.shape == (0, 3)
    assert not bool(out.initialized)
    np.testing.assert_array_equal(np.asarray(out.A), np.asarray(state.A))
    assert int(out.last_update) == int(state.last_update)

    # The frame after the drain still bootstraps: A == candidate exactly.
    cand = jnp.asarray([[0.5, 0.6, 0.7]], jnp.float32)
    a_seq, out2 = scan(cand, jnp.asarray([12], jnp.int32), out,
                       period=4, lam=0.3)
    np.testing.assert_array_equal(np.asarray(a_seq[0]), np.asarray(cand[0]))
    assert bool(out2.initialized) and int(out2.last_update) == 12


@pytest.mark.parametrize("scan", [ema_scan, ema_scan_associative],
                         ids=["scan", "associative"])
def test_empty_batch_preserves_warm_state(scan):
    state = AtmoState(A=jnp.asarray([0.8, 0.85, 0.9], jnp.float32),
                      last_update=jnp.asarray(7, jnp.int32),
                      initialized=jnp.asarray(True))
    a_seq, out = scan(jnp.zeros((0, 3), jnp.float32),
                      jnp.zeros((0,), jnp.int32), state, period=4, lam=0.3)
    assert a_seq.shape == (0, 3)
    assert bool(out.initialized)
    np.testing.assert_array_equal(np.asarray(out.A), np.asarray(state.A))
    assert int(out.last_update) == 7


# --- resolve_mode env validation ---------------------------------------------

@pytest.mark.parametrize("bad", ["Pallas", "refs", "INTERPRET", "xla"])
def test_resolve_mode_rejects_unknown_env(monkeypatch, bad):
    """Unknown REPRO_KERNEL_MODE values used to fall through every dispatch
    wrapper's ``m == "ref"`` check into the compiled-Pallas branch."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", bad)
    with pytest.raises(ValueError, match="REPRO_KERNEL_MODE"):
        ops.resolve_mode("auto")
    with pytest.raises(ValueError, match="REPRO_KERNEL_MODE"):
        ops.dark_channel(jnp.zeros((1, 8, 8, 3)), 1)


def test_resolve_mode_rejects_unknown_argument():
    with pytest.raises(ValueError, match="unknown kernel mode"):
        ops.resolve_mode("fastest")


@pytest.mark.parametrize("env,expected", [
    ("ref", "ref"), ("pallas", "pallas"), ("interpret", "interpret"),
    ("fused", "ref"),       # pipeline-level mode -> default substrate (CPU)
    ("auto", "ref"),        # explicit "auto" == unset
])
def test_resolve_mode_accepts_known_env(monkeypatch, env, expected):
    monkeypatch.setenv("REPRO_KERNEL_MODE", env)
    assert ops.resolve_mode("auto") == expected
    assert ops.resolve_mode("fused") in ("ref", "pallas", "interpret")


def test_resolve_mode_explicit_arg_still_resolves(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
    assert ops.resolve_mode("ref") == "ref"
    assert ops.resolve_mode("interpret") == "interpret"
    assert ops.resolve_mode("fused") in ("ref", "pallas")


def test_resolve_mode_auto_is_xla_on_tpu(monkeypatch):
    """On a TPU ``auto`` resolves to the XLA substrate (the Pallas kernels
    do not lower for v5e yet), while the explicit opt-ins stay Pallas so
    they fail loudly at lowering instead of being swapped in silence."""
    monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops.resolve_mode("auto") == "ref"
    assert ops.resolve_mode("pallas") == "pallas"
    assert ops.resolve_mode("fused") == "pallas"


# --- spout padding must not advance coherence state --------------------------

def test_spout_padding_tagged_minus_one():
    frames = [np.full((4, 4, 3), i, np.float32) for i in range(6)]
    batches = list(Spout(iter(frames), batch=4))
    np.testing.assert_array_equal(batches[0].frame_ids, [0, 1, 2, 3])
    # Tail padding: ids are -1, NOT the future real ids 2..5.
    np.testing.assert_array_equal(batches[1].frame_ids, [4, 5, -1, -1])
    assert batches[1].n_valid == 2


@pytest.mark.parametrize("scan", [ema_scan, ema_scan_associative],
                         ids=["scan", "associative"])
def test_padding_ids_do_not_advance_ema(scan):
    """State after a padded batch [k, -1, -1, -1] must equal the state
    after just [k]; previously the padded tail got ids k+1..k+3 and the
    EMA advanced on duplicate frames whose ids were later reused."""
    rng = np.random.default_rng(0)
    cand = jnp.asarray(rng.random((4, 3)), jnp.float32)
    state = init_atmo_state()
    a_pad, s_pad = scan(cand, jnp.asarray([4, -1, -1, -1], jnp.int32),
                        state, period=2, lam=0.3)
    a_one, s_one = scan(cand[:1], jnp.asarray([4], jnp.int32),
                        state, period=2, lam=0.3)
    np.testing.assert_array_equal(np.asarray(s_pad.A), np.asarray(s_one.A))
    assert int(s_pad.last_update) == 4 and bool(s_pad.initialized)
    # Padding output slots carry the running A through unchanged.
    np.testing.assert_array_equal(np.asarray(a_pad[1:]),
                                  np.broadcast_to(np.asarray(a_one[0]), (3, 3)))


@pytest.mark.parametrize("scan", [ema_scan, ema_scan_associative],
                         ids=["scan", "associative"])
def test_all_padding_batch_is_identity(scan):
    """A batch of only padding (an unoccupied scheduler lane) behaves like
    the empty batch: no update, no ``initialized`` flip."""
    state = init_atmo_state()
    cand = jnp.ones((4, 3), jnp.float32) * 0.5
    ids = jnp.full((4,), -1, jnp.int32)
    _, out = scan(cand, ids, state, period=4, lam=0.3)
    assert not bool(out.initialized)
    np.testing.assert_array_equal(np.asarray(out.A), np.asarray(state.A))
    assert int(out.last_update) == int(state.last_update)

    warm = AtmoState(A=jnp.asarray([0.8, 0.85, 0.9], jnp.float32),
                     last_update=jnp.asarray(7, jnp.int32),
                     initialized=jnp.asarray(True))
    _, out = scan(cand, ids, warm, period=4, lam=0.3)
    assert bool(out.initialized) and int(out.last_update) == 7
    np.testing.assert_array_equal(np.asarray(out.A), np.asarray(warm.A))


@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_fused_dehaze_masks_padding_ids(mode):
    """The megakernel's in-grid EMA carry must honor the same padding
    contract as the host-side scans."""
    r = np.random.default_rng(5)
    img = jnp.asarray(r.random((4, 12, 16, 3), np.float32))
    ids = jnp.asarray([8, 9, -1, -1], jnp.int32)
    s = init_atmo_state()
    kw = dict(radius=2, omega=0.95, refine=False, gf_radius=2, gf_eps=1e-3,
              t0=0.1, gamma=1.0, period=3, lam=0.2)
    got = ops.fused_dehaze(img, ids, s.A, s.last_update, s.initialized,
                           mode=mode, **kw)
    want = ops.fused_dehaze(img[:2], ids[:2], s.A, s.last_update,
                            s.initialized, mode=mode, **kw)
    np.testing.assert_allclose(np.asarray(got[3]), np.asarray(want[3]),
                               atol=1e-6)                  # A_fin
    assert int(got[4]) == int(want[4]) == 8                # k_fin: bootstrap@8


def test_serve_chunked_with_padded_tails_matches_unchunked():
    """End-to-end: serving a stream in two chunks whose tails are padded
    must leave the same EMA state as one uninterrupted serve — the
    original bug EMA-advanced on padded duplicates of frames 4..5, then
    again on the real frames 4..5 of chunk 2."""
    from repro.core import DehazeConfig
    from repro.stream import ElasticServer
    rng = np.random.default_rng(6)
    frames = [rng.random((16, 20, 3)).astype(np.float32) for _ in range(12)]
    cfg = DehazeConfig(kernel_mode="ref", gf_radius=2, update_period=2)

    srv_ref = ElasticServer(cfg, n_workers=1, batch=4, timeout_s=5.0)
    srv_ref.serve(iter(frames))
    srv = ElasticServer(cfg, n_workers=1, batch=4, timeout_s=5.0)
    srv.serve(iter(frames[:6]))      # tail batch: [4, 5, pad, pad]
    srv.serve(iter(frames[6:]))      # resumes at cursor 6
    np.testing.assert_allclose(
        np.asarray(srv.store.get("default").A),
        np.asarray(srv_ref.store.get("default").A), atol=1e-6)
    assert srv.store.cursor("default") == 12


# --- supports_fused must cover the full production config grid ---------------

def test_supports_fused_production_grid():
    """Regression gate for the fused-coverage contract: ``supports_fused``
    used to gate on ``topk == 1`` (and the halo kernel on height-only
    sharding), silently bouncing the production configs — robust top-k A
    estimation, W-sharded high-res frames — to the seven-launch per-stage
    chain. It must now return True for every serving config; if a future
    kernel change reintroduces a gate, this fails loudly instead of
    production quietly losing the megakernel.
    """
    import itertools

    from repro.core import DehazeConfig
    from repro.core import algorithms as alg

    grid = itertools.product(
        ("dcp", "cap"),                    # algorithm
        (1, 4, 32),                        # topk: Eq. 6 and robust top-k
        ("float32", "bfloat16"),           # serving dtypes
        (False, True),                     # halo_packed (sharded perf lever)
        ("float32", "bfloat16"),           # halo_dtype
        (1, 8),                            # update_period
    )
    for algorithm, topk, dtype, packed, hdt, period in grid:
        cfg = DehazeConfig(algorithm=algorithm, topk=topk, dtype=dtype,
                           halo_packed=packed, halo_dtype=hdt,
                           update_period=period, kernel_mode="fused")
        assert alg.supports_fused(cfg), (algorithm, topk, dtype, packed,
                                         hdt, period)
    # The one documented fallback: DCP's recompute-with-final-A second
    # transmission pass is inherently two-stage.
    assert not alg.supports_fused(
        DehazeConfig(algorithm="dcp", recompute_t_with_final_a=True))


def test_supports_fused_docs_match_behavior():
    """The docstring/config comment used to still describe the retired
    ``topk == 1`` gate; keep the prose in sync with the predicate."""
    import inspect

    from repro.core import algorithms as alg
    from repro.core import config as cfg_mod

    doc = inspect.getdoc(alg.supports_fused)
    assert "topk == 1" not in doc and "k=1) estimator" not in doc
    assert "top-k" in doc                 # coverage is called out explicitly
    src = inspect.getsource(cfg_mod)
    assert "top-k / recompute configs fall" not in src
    assert "any topk" in src


# --- frames_per_block largest-divisor degradation ----------------------------

@pytest.mark.parametrize("batch,requested,expected", [
    (4, 3, 2),    # non-divisor rounds DOWN to the largest divisor, not to 1
    (6, 4, 3),
    (12, 5, 4),
    (5, 4, 1),    # prime batch: only 1 divides
    (4, 9, 4),    # over-request clamps to the batch
    (4, 0, 1),    # unset/registry-default
    (4, -1, 1),
])
def test_frames_per_block_largest_divisor(batch, requested, expected):
    assert _resolve_frames_per_block(batch, requested) == expected


def test_non_divisor_tile_stays_exact():
    """Requested tile 3 over a batch of 8 runs 2-frame blocks; the EMA grid
    carry must stay exact across the resulting block boundaries."""
    r = np.random.default_rng(3)
    img = jnp.asarray(r.random((8, 12, 16, 3), np.float32))
    ids = jnp.arange(8, dtype=jnp.int32)
    s = init_atmo_state()
    kw = dict(radius=2, omega=0.95, refine=False, gf_radius=2, gf_eps=1e-3,
              t0=0.1, gamma=1.0, period=3, lam=0.2)
    got = ops.fused_dehaze(img, ids, s.A, s.last_update, s.initialized,
                           frames_per_block=3, mode="interpret", **kw)
    want = ops.fused_dehaze(img, ids, s.A, s.last_update, s.initialized,
                            mode="ref", **kw)
    for g, w in zip(got[:3], want[:3]):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), atol=1e-5)
    assert int(got[4]) == int(want[4])


# --- autotune all-candidates-fail must not persist DEFAULTS ------------------

def test_autotune_all_fail_does_not_persist_defaults(tmp_path, monkeypatch):
    """Pre-fix, ``autotune`` initialized the winner to ``DEFAULTS[op]`` and
    silently ``continue``d on every exception — a sweep whose every
    candidate raised (wrong shapes, VMEM overflow) persisted the built-in
    defaults into the table with full measured authority."""
    from repro.kernels import tuning

    table = tmp_path / "tuning.json"
    monkeypatch.setenv("REPRO_KERNEL_TUNING", str(table))

    def build(params):
        raise RuntimeError("candidate cannot compile")

    stats = tuning.TuneStats()
    with pytest.raises(tuning.AutotuneError, match="refusing to persist"):
        tuning.autotune("fused_dcp", (2, 8, 8),
                        [{"frames_per_block": f} for f in (1, 2, 4)],
                        build, stats=stats)
    assert not table.exists()                  # nothing persisted
    assert stats.skipped == {"RuntimeError": 3}
    # ...and the search core enforces the same contract.
    with pytest.raises(tuning.AutotuneError):
        tuning.measured_search("fused_dcp", (2, 8, 8),
                               [{"frames_per_block": 1}], build)
    assert not table.exists()


# --- one monotonic deadline clock across the serving stack -------------------

def test_deadline_clock_unified_monotonic(monkeypatch):
    """``MultiStreamScheduler``/``FleetScheduler``/``serve_many`` defaulted
    ``clock=time.time`` while the Monitor used ``time.monotonic``: a
    deadline produced against one timebase was compared against the other,
    and an NTP wall-clock step could instantly mark every deadlined lane
    tardy. All defaults must be the one shared monotonic DEADLINE_CLOCK."""
    import inspect
    import time

    from repro.stream import elastic, fleet, monitor, scheduler
    from repro.stream.state import StreamStateStore

    assert monitor.DEADLINE_CLOCK is time.monotonic
    for fn in (scheduler.MultiStreamScheduler.__init__,
               fleet.FleetScheduler.__init__,
               elastic.ElasticServer.serve_many,
               monitor.Monitor.__init__):
        default = inspect.signature(fn).parameters["clock"].default
        assert default is monitor.DEADLINE_CLOCK, fn.__qualname__

    # Behavioral: a deadline an hour out stays an hour out across a
    # simulated NTP step. With the old wall-clock default, clock() jumps
    # to epoch scale and the fresh deadline is instantly "past due".
    deadline = monitor.DEADLINE_CLOCK() + 3600.0
    monkeypatch.setattr(time, "time", lambda: 4.0e9)   # the NTP step
    sched = scheduler.MultiStreamScheduler(
        step=lambda *a: None, store=StreamStateStore(), n_lanes=1)
    assert sched._clock() < deadline           # not tardy: monotonic clock
    assert time.time() >= deadline             # the old default would be


# --- LaneAutoscaler warm failures surfaced, retried once, reported -----------

class _FlakyRungFactory:
    """Step factory whose rung-8 build fails ``fail_times`` times before
    succeeding (or forever, for the permanent-failure case)."""

    def __init__(self, fail_times):
        self.fail_times = fail_times
        self.attempts = {}

    def __call__(self, rung):
        self.attempts[rung] = self.attempts.get(rung, 0) + 1
        if rung == 8 and self.attempts[rung] <= self.fail_times:
            raise RuntimeError(f"rung {rung} compile blew VMEM")

        def step(frames, ids, state):
            import types
            return types.SimpleNamespace(state=state)
        return step


def _spin_until(cond, timeout=5.0):
    import time as _t
    t0 = _t.monotonic()
    while not cond():
        if _t.monotonic() - t0 > timeout:
            raise AssertionError("condition not reached in time")
        _t.sleep(0.005)


def test_warm_failure_surfaced_and_retried_once():
    """Pre-fix, a rung whose background warm-up raised was recorded in
    ``_warm_errors`` and then *nothing* referenced that dict: the rung was
    silently never offered. Now the failure is logged, retried once when
    the ladder actually wants the rung, and a successful retry makes the
    rung offerable."""
    from repro.stream.autoscale import LaneAutoscaler, ScalePolicy

    factory = _FlakyRungFactory(fail_times=1)      # transient: retry wins
    scaler = LaneAutoscaler(factory, rungs=(4, 8),
                            policy=ScalePolicy(rungs=(4, 8), dwell_up=2))
    scaler.acquire_initial()
    scaler.ensure_warming((1, 8, 8, 3))
    scaler.wait_warm(timeout=5.0)
    assert 8 in scaler.warm_errors                 # surfaced, not buried
    assert scaler.warm_failures == 1

    # Load wants the bigger rung: dwell reached -> the retry is kicked.
    assert scaler.observe(pending=2, occupied=4) is None
    assert scaler.observe(pending=2, occupied=4) is None
    _spin_until(lambda: scaler.is_ready(8))
    assert scaler.warm_errors == {}                # retry cleared it
    assert scaler.warm_failures == 0
    assert scaler.observe(pending=2, occupied=4) == 8
    assert factory.attempts[8] == 2


def test_warm_failure_permanent_raises_on_request():
    from repro.stream.autoscale import (WARM_MAX_ATTEMPTS, LaneAutoscaler,
                                        ScalePolicy)

    factory = _FlakyRungFactory(fail_times=10**9)  # permanent
    scaler = LaneAutoscaler(factory, rungs=(4, 8),
                            policy=ScalePolicy(rungs=(4, 8), dwell_up=2))
    scaler.acquire_initial()
    scaler.ensure_warming((1, 8, 8, 3))
    scaler.wait_warm(timeout=5.0)
    for _ in range(4):                             # retry budget exhausts
        scaler.observe(pending=2, occupied=4)
        scaler.wait_warm(timeout=5.0)
    assert factory.attempts[8] == WARM_MAX_ATTEMPTS   # exactly one retry
    assert scaler.warm_failures == 1
    with pytest.raises(RuntimeError, match="rung"):
        scaler.wait_warm(timeout=5.0, raise_on_error=True)


def test_warm_failures_ride_the_serve_report():
    """`ServeReport.warm_failures` carries the count (the serve launcher
    exits nonzero on it)."""
    import dataclasses

    from repro.stream.scheduler import ServeReport

    assert any(f.name == "warm_failures"
               for f in dataclasses.fields(ServeReport))
    rep = ServeReport(per_stream={}, frames=0, skipped=0, wall_s=0.0,
                      n_lanes=4, ticks=0, warm_failures=2)
    assert rep.warm_failures == 2


def test_serve_launcher_fails_on_warm_failures(monkeypatch):
    """A ladder rung that failed to warm (e.g. a lane batch too large for
    device memory) makes the serve launcher exit nonzero even when no
    switches were expected: it is not a warning after which the run
    exits 0."""
    import argparse

    from repro.core import DehazeConfig
    from repro.launch import serve
    from repro.stream.scheduler import ServeReport

    def fake_serve_many(self, streams, **kw):
        return ServeReport(per_stream={}, frames=0, skipped=0, wall_s=0.0,
                           n_lanes=2, ticks=0, warm_failures=1)

    monkeypatch.setattr(serve.ElasticServer, "serve_many", fake_serve_many)
    args = argparse.Namespace(
        ramp=False, streams=2, frames=2, io_dtype="float32", lanes=2,
        batch=2, timeout_ms=20.0, autoscale=False, hosts=1,
        algorithm="dcp", resolution="tiny", expect_switches=0,
        expect_spillover=0, expect_overlap=False)
    cfg = DehazeConfig(algorithm="dcp", kernel_mode="ref")
    with pytest.raises(SystemExit) as exc:
        serve._serve_many(args, cfg, 8, 8)
    assert exc.value.code == 1
