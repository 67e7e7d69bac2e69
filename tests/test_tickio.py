"""Zero-copy tick I/O: donation contract, lane buffer adapter, overlap parity.

The overlapped serve path (``stream.iobuf``) must be bit-identical to the
blocking oracle it replaces on every cell of the dispatch-path x occupancy
matrix, the donated-state step must actually alias (zero new HBM for the
state output), and use-after-donate must be confined to the documented
ownership contract: reads dispatched before the donating tick are safe,
reads after it are the bug the contract exists to prevent.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (DehazeConfig, PlacementSpec, init_atmo_state_lanes,
                        make_step)
from repro.core.pipeline import donation_spec
from repro.stream import (ElasticServer, LaneTickStep, StreamRequest,
                          TickBufferPool, donation_supported, fetch_valid)
from repro.stream.elastic import _cached_multi_step

needs_donation = pytest.mark.skipif(
    not donation_supported(),
    reason="backend does not honor donate_argnums")


def _frames(lanes, batch, h=12, w=16, seed=0):
    rng = np.random.default_rng(seed)
    return rng.random((lanes, batch, h, w, 3)).astype(np.float32)


def _cfg(**kw):
    kw.setdefault("kernel_mode", "ref")
    kw.setdefault("gf_radius", 2)
    kw.setdefault("update_period", 2)
    return DehazeConfig(**kw)


# --- donation probe -----------------------------------------------------------

@pytest.mark.parametrize("fault", ["raises", "not_donated"])
def test_donation_probe_raises_on_tpu(monkeypatch, fault):
    """On a TPU a failing donation probe is a device-path fault: it raises
    instead of quietly turning the overlapped tick off (off the TPU the
    same failure still means "no donation" and the blocking path)."""
    from repro.stream import iobuf

    def fake_jit(fn, donate_argnums=()):
        if fault == "raises":
            raise RuntimeError("probe compile failed")
        return lambda x: x + 1                 # runs, donates nothing

    monkeypatch.setattr(iobuf, "_donation_supported", None)
    monkeypatch.setattr(iobuf.jax, "jit", fake_jit)
    monkeypatch.setattr(iobuf.jax, "default_backend", lambda: "cpu")
    assert iobuf.donation_supported() is False
    monkeypatch.setattr(iobuf, "_donation_supported", None)
    monkeypatch.setattr(iobuf.jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError):
        iobuf.donation_supported()


# --- fetch_valid --------------------------------------------------------------

def test_fetch_valid_slices_and_lane_select():
    frames = jnp.asarray(_frames(3, 4))
    got = fetch_valid(frames, 2, lane=1)
    np.testing.assert_array_equal(got, np.asarray(frames)[1, :2])
    assert got.nbytes == frames[1, :2].nbytes
    whole = fetch_valid(frames, 2)            # lane=None: batch-axis slice
    np.testing.assert_array_equal(whole, np.asarray(frames)[:2])


# --- donation contract (core.pipeline.make_step) ------------------------------

def test_donation_spec_follows_dtype_contract():
    # f32 in / f32 out: frames buffer can alias the output -> donated.
    assert donation_spec(_cfg()) == (0, 2)
    # uint8 wire dtype, f32 out: shapes/dtypes differ -> state only.
    assert donation_spec(_cfg(io_dtype="uint8")) == (2,)
    # bf16 in / bf16 out aliases again.
    assert donation_spec(_cfg(io_dtype="bfloat16",
                              out_dtype="bfloat16")) == (0, 2)


@needs_donation
def test_state_donated_step_aliases_input_state():
    """donate="state": the packed EMA state passed in is consumed by the
    call — deleted on exit, proving the output state aliased its buffer
    (zero new HBM allocated for the state each steady tick)."""
    cfg = _cfg()
    step = make_step(cfg, PlacementSpec.lane_batched(), donate="state")
    frames = jnp.asarray(_frames(2, 4))
    ids = jnp.stack([jnp.arange(4, dtype=jnp.int32)] * 2)
    packed = init_atmo_state_lanes(2)
    out = step(frames, ids, packed)
    jax.block_until_ready(out.state)
    assert packed.A.is_deleted(), "input state survived a donating step"
    assert not frames.is_deleted(), 'donate="state" must not touch frames'


@needs_donation
def test_full_donation_takes_frames_when_dtypes_alias():
    cfg = _cfg()
    step = make_step(cfg, PlacementSpec.lane_batched(), donate=True)
    frames = jnp.asarray(_frames(2, 4, seed=1))
    ids = jnp.stack([jnp.arange(4, dtype=jnp.int32)] * 2)
    packed = init_atmo_state_lanes(2)
    out = step(frames, ids, packed)
    jax.block_until_ready(out.frames)
    assert frames.is_deleted() and packed.A.is_deleted()


def test_donation_rejected_for_sharded_placement():
    with pytest.raises(ValueError, match="donat"):
        make_step(_cfg(), PlacementSpec.lane_sharded(), donate="state")


# --- use-after-donate: the ownership contract, both directions ----------------

@needs_donation
def test_use_after_donate_regression():
    """The serve loop's pattern: a host read of ``out.state`` dispatched
    BEFORE the next (donating) tick sees the pre-donation value; touching
    the same buffer AFTER it was donated raises instead of silently
    returning garbage. This is the eviction-snapshot/rung-repack ordering
    rule from the iobuf ownership contract."""
    cfg = _cfg()
    step = make_step(cfg, PlacementSpec.lane_batched(), donate="state")
    frames = jnp.asarray(_frames(2, 4, seed=2))
    ids = jnp.stack([jnp.arange(4, dtype=jnp.int32)] * 2)
    out1 = step(frames, ids, init_atmo_state_lanes(2))
    # Snapshot BEFORE tick 2, with an explicit copy: np.asarray on CPU
    # returns a zero-copy view whose external reference pins the buffer
    # (the runtime then declines to donate that leaf — correct, but it
    # would mask the deletion this test asserts).
    snapshot = np.array(out1.state.A)
    out2 = step(frames, ids + 4, out1.state)  # donates out1.state
    jax.block_until_ready(out2.state)
    assert out1.state.A.is_deleted()
    with pytest.raises(RuntimeError):
        np.asarray(out1.state.A)              # after donation: loud failure
    assert snapshot.shape == (2, 3)           # the early read stayed valid


# --- LaneTickStep adapter -----------------------------------------------------

def test_lane_tick_step_matches_blocking_step():
    """stage()-per-lane + tick() on the device-resident buffer produces
    the same frames and state as the blocking full-batch call of the
    plain (non-donating) step."""
    cfg = _cfg()
    lanes, batch = 3, 4
    frames = _frames(lanes, batch, seed=3)
    ids = np.stack([np.arange(batch, dtype=np.int32) + 10 * i
                    for i in range(lanes)])
    ref = _cached_multi_step(cfg, lanes, False)(
        jnp.asarray(frames), jnp.asarray(ids), init_atmo_state_lanes(lanes))

    adapter = LaneTickStep(
        _cached_multi_step(cfg, lanes, False, donate="state"), lanes)
    for i in range(lanes):
        adapter.stage(i, frames[i])
    out = adapter.tick(ids, init_atmo_state_lanes(lanes))
    np.testing.assert_array_equal(np.asarray(out.frames),
                                  np.asarray(ref.frames))
    np.testing.assert_array_equal(np.asarray(out.state.A),
                                  np.asarray(ref.state.A))
    assert adapter.staged_lanes == lanes
    assert adapter.staged_bytes == frames.nbytes


def test_lane_tick_step_stale_padding_rows_are_inert():
    """Sparse occupancy: restaging only lane 0 leaves lane 1's row stale
    on device — the frame_id=-1 mask must keep lane 1's state bit-frozen
    and lane 0's output equal to a fresh full-batch run."""
    cfg = _cfg()
    lanes, batch = 2, 4
    f0, f1 = _frames(lanes, batch, seed=4)
    adapter = LaneTickStep(
        _cached_multi_step(cfg, lanes, False, donate="state"), lanes)
    adapter.stage(0, f0)
    adapter.stage(1, f1)
    ids = np.stack([np.arange(batch, dtype=np.int32)] * lanes)
    out1 = adapter.tick(ids, init_atmo_state_lanes(lanes))
    # Host snapshot BEFORE the next tick donates out1.state (the contract).
    state1_host = jax.tree.map(np.asarray, out1.state)

    f0b = _frames(1, batch, seed=5)[0]
    adapter.stage(0, f0b)                     # lane 1 left stale
    ids2 = np.stack([np.arange(batch, dtype=np.int32) + batch,
                     np.full((batch,), -1, np.int32)])
    out2 = adapter.tick(ids2, out1.state)

    ref_frames = np.stack([f0b, f1])          # what the buffer now holds
    ref = _cached_multi_step(cfg, lanes, False)(
        jnp.asarray(ref_frames), jnp.asarray(ids2),
        jax.tree.map(jnp.asarray, state1_host))
    np.testing.assert_array_equal(np.asarray(out2.frames[0]),
                                  np.asarray(ref.frames[0]))
    # Padding lane's state rode through bit-unchanged despite stale frames.
    np.testing.assert_array_equal(np.asarray(out2.state.A[1]),
                                  state1_host.A[1])


def test_all_padding_tick_keeps_state_bit_unchanged():
    """A tick where every lane is padding (all frame ids -1, nothing ever
    staged beyond buffer init) must return the packed state bit-for-bit."""
    cfg = _cfg()
    lanes, batch = 2, 3
    adapter = LaneTickStep(
        _cached_multi_step(cfg, lanes, False, donate="state"), lanes)
    adapter.ensure_buf((batch, 12, 16, 3), np.float32)
    ids = np.full((lanes, batch), -1, np.int32)
    packed = init_atmo_state_lanes(lanes)
    before = jax.tree.map(np.asarray, packed)
    out = adapter.tick(ids, packed)
    after = jax.tree.map(np.asarray, out.state)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(a, b)


def test_tick_buffer_pool_one_adapter_per_rung():
    pool = TickBufferPool(lambda n: _cached_multi_step(_cfg(), n, False,
                                                       donate="state"))
    a2, a4 = pool.adapter(2), pool.adapter(4)
    assert a2 is pool.adapter(2) and a4 is pool.adapter(4)
    assert a2 is not a4 and a2.n_lanes == 2 and a4.n_lanes == 4


# --- overlap vs blocking serve parity matrix ----------------------------------

@pytest.mark.parametrize("mode,lane_native", [
    ("ref", False),           # staged XLA chain
    ("fused", False),         # fused kernels, lane-vmapped
    ("fused", True),          # lane-native megakernel
])
@pytest.mark.parametrize("occupancy", ["full", "sparse"])
def test_overlap_serve_parity(monkeypatch, mode, lane_native, occupancy):
    """Every dispatch path x occupancy cell: the overlapped serve's
    delivered frames and final EMA states are bit-identical to the
    blocking oracle's (same executable, same values — donation and
    device-resident staging change where buffers live, never the math)."""
    if not donation_supported():
        pytest.skip("backend does not honor donate_argnums")
    monkeypatch.setenv("REPRO_LANE_NATIVE", "1" if lane_native else "0")
    cfg = _cfg(kernel_mode=mode)
    n_streams, lanes = (4, 4) if occupancy == "full" else (2, 4)
    lengths = [10, 7, 13, 5][:n_streams]
    rng = np.random.default_rng(42)
    vids = [[rng.random((12, 16, 3)).astype(np.float32) for _ in range(k)]
            for k in lengths]

    def serve(tick_overlap):
        srv = ElasticServer(cfg, batch=4, timeout_s=5.0)
        outs = {}
        rep = srv.serve_many(
            [StreamRequest(f"s{i}", iter(v)) for i, v in enumerate(vids)],
            n_lanes=lanes, tick_overlap=tick_overlap,
            sink=lambda sid, fid, f: outs.setdefault((sid, fid), f))
        finals = {f"s{i}": np.asarray(srv.store.get(f"s{i}").A)
                  for i in range(n_streams)}
        return rep, outs, finals

    rep_b, outs_b, fin_b = serve(False)
    rep_o, outs_o, fin_o = serve(True)
    assert rep_b.overlap_ticks == 0
    assert rep_o.overlap_ticks == rep_o.ticks > 0
    assert rep_o.frames == rep_b.frames == sum(lengths)
    assert outs_o.keys() == outs_b.keys()
    for k in outs_b:
        np.testing.assert_array_equal(outs_o[k], outs_b[k])
    for sid in fin_b:
        np.testing.assert_array_equal(fin_o[sid], fin_b[sid])
    if occupancy == "sparse":
        # Valid-only D2H: the blocking path fetched the padding lanes too.
        assert rep_o.d2h_bytes < rep_b.d2h_bytes


def test_env_knob_forces_overlap(monkeypatch):
    if not donation_supported():
        pytest.skip("backend does not honor donate_argnums")
    monkeypatch.setenv("REPRO_TICK_OVERLAP", "1")
    cfg = _cfg()
    rng = np.random.default_rng(7)
    vids = [[rng.random((12, 16, 3)).astype(np.float32) for _ in range(6)]
            for _ in range(2)]
    srv = ElasticServer(cfg, batch=3, timeout_s=5.0)
    rep = srv.serve_many([StreamRequest(f"s{i}", iter(v))
                          for i, v in enumerate(vids)], n_lanes=2)
    assert rep.overlap_ticks == rep.ticks > 0
    monkeypatch.setenv("REPRO_TICK_OVERLAP", "0")
    rep2 = srv.serve_many([StreamRequest(f"t{i}", iter(v))
                           for i, v in enumerate(vids)], n_lanes=2)
    assert rep2.overlap_ticks == 0


def test_serve_report_phases_and_stragglers():
    """Healthy serve: every span key of ``repro.stream.spans`` and the
    monitor-queue counter are on the report, the step's dispatch and the
    fetch took time, and no shutdown stragglers are counted."""
    cfg = _cfg()
    rng = np.random.default_rng(11)
    vids = [[rng.random((12, 16, 3)).astype(np.float32) for _ in range(5)]]
    srv = ElasticServer(cfg, batch=4, timeout_s=5.0)
    rep = srv.serve_many([StreamRequest("s0", iter(vids[0]))], n_lanes=1)
    assert set(rep.phases) == {"spout_s", "host_stage_s", "inflight_wait_s",
                               "dispatch_s", "device_wait_s", "fetch_s",
                               "monitor_queue_s"}
    assert all(v >= 0.0 for v in rep.phases.values())
    assert rep.phases["dispatch_s"] > 0.0
    assert rep.phases["fetch_s"] > 0.0
    assert rep.stragglers == 0
