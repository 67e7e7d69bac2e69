"""Cross-path differential-test matrix for the production dehazing configs.

Sweeps {dcp, cap} x {topk 1, 4} x {staged, fused, lane_native} x
{n_h 1, 2} x {n_w 1, 2} x {single-stream, lanes 1, 4} and asserts
J / t / A / AtmoState agreement against the per-stage ref-oracle chain —
including all-padding lanes and mesh-edge shards. Every serving config is
fused-covered now (``supports_fused`` has no topk / sharding gates), so
this matrix is the contract that future kernel work cannot silently fork
the fused and staged semantics. The lane-native cells additionally pin
the multi-stream refactor's parity bar: per lane, the megakernel with the
lane axis folded into its grid must equal the ``jax.vmap``-of-fused path
(bit-for-bit on the XLA-oracle substrate; to 2 ulp across the separately
compiled interpret-mode programs).

Single-device and multi-stream cells run in-process (under
``REPRO_KERNEL_MODE=interpret`` they exercise the actual Pallas kernel
bodies — the CI kernel-parity job does exactly that); the sharded cells
spawn subprocesses with 8 forced host devices, one per mesh shape, and
sweep the algorithm/topk/path axes inside the child.

No hypothesis dependency on purpose: this file is minimal-install
tier-1 coverage for the whole fused surface.
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DehazeConfig, init_atmo_state, make_dehaze_step,
                        make_multi_stream_step)
from repro.core.normalize import pack_atmo_states

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

ALGORITHMS = ["dcp", "cap"]
TOPKS = [1, 4]
PATHS = ["staged", "fused"]

# Frames/transmission: the fused substrate composes the same jnp ops in a
# different order than the per-stage chain, so XLA re-association costs a
# few float32 ulps through the guided filter. A is compared tighter (the
# candidate selection is bit-identical by construction).
TOL_IMG = 2e-4
TOL_A = 1e-4


def _cfg(algorithm: str, topk: int, path: str) -> DehazeConfig:
    return DehazeConfig(algorithm=algorithm, topk=topk,
                        kernel_mode="fused" if path == "fused" else "ref",
                        patch_radius=3, gf_radius=4, update_period=2)


def _oracle_cfg(algorithm: str, topk: int) -> DehazeConfig:
    return DehazeConfig(algorithm=algorithm, topk=topk, kernel_mode="ref",
                        patch_radius=3, gf_radius=4, update_period=2)


def _frames(seed=17, b=4, h=32, w=32):
    """Tie-stable parity frames — ``conftest.ramp_frames``, THE shared
    recipe for differential-testing discontinuous top-k selections across
    separately compiled programs (see its docstring for why uniform random
    frames are unusable here: observed a 0.03 A fork from one 1-ulp
    boundary tie flipping a pick)."""
    from conftest import ramp_frames
    return ramp_frames(seed, b, h=h, w=w)


def _assert_output_close(got, want, tag=""):
    np.testing.assert_allclose(np.asarray(got.frames),
                               np.asarray(want.frames), atol=TOL_IMG,
                               err_msg=f"J {tag}")
    np.testing.assert_allclose(np.asarray(got.transmission),
                               np.asarray(want.transmission), atol=TOL_IMG,
                               err_msg=f"t {tag}")
    np.testing.assert_allclose(np.asarray(got.atmo_light),
                               np.asarray(want.atmo_light), atol=TOL_A,
                               err_msg=f"a_seq {tag}")
    np.testing.assert_allclose(np.asarray(got.state.A),
                               np.asarray(want.state.A), atol=TOL_A,
                               err_msg=f"state.A {tag}")
    assert int(got.state.last_update) == int(want.state.last_update), tag
    assert bool(got.state.initialized) == bool(want.state.initialized), tag


# ---------------------------------------------------------------------------
# Single-device cells (n_h = n_w = 1, single stream)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("topk", TOPKS)
@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_single_device_parity(algorithm, topk, path):
    frames = _frames()
    ids = jnp.arange(4, dtype=jnp.int32)
    got = make_dehaze_step(_cfg(algorithm, topk, path))(
        frames, ids, init_atmo_state())
    want = make_dehaze_step(_oracle_cfg(algorithm, topk))(
        frames, ids, init_atmo_state())
    _assert_output_close(got, want, f"{algorithm}/topk{topk}/{path}")


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_single_device_parity_warm_state_chain(algorithm, path):
    """Two chained batches: the EMA state handed from batch 1 to batch 2
    must keep the paths in lockstep (a state fork would compound)."""
    ids1 = jnp.arange(4, dtype=jnp.int32)
    ids2 = jnp.arange(4, 8, dtype=jnp.int32)
    f1, f2 = _frames(seed=3), _frames(seed=5)
    step_g = make_dehaze_step(_cfg(algorithm, 4, path))
    step_w = make_dehaze_step(_oracle_cfg(algorithm, 4))
    out_g = step_g(f1, ids1, init_atmo_state())
    out_w = step_w(f1, ids1, init_atmo_state())
    got = step_g(f2, ids2, out_g.state)
    want = step_w(f2, ids2, out_w.state)
    _assert_output_close(got, want, f"{algorithm}/{path}/chained")


# ---------------------------------------------------------------------------
# Multi-stream cells (4 lanes, incl. an all-padding lane)
# ---------------------------------------------------------------------------

# The lane axis has two device realizations: the single-stream chain under
# jax.vmap ("staged"/"fused"), and the lane-native megakernel with the
# lane axis folded into the pallas grid ("lane_native").
MULTI_PATHS = PATHS + ["lane_native"]


def _multi_step(algorithm, topk, path):
    if path == "lane_native":
        return make_multi_stream_step(_cfg(algorithm, topk, "fused"),
                                      lane_native=True)
    return make_multi_stream_step(_cfg(algorithm, topk, path),
                                  lane_native=False)


@pytest.mark.parametrize("path", MULTI_PATHS)
@pytest.mark.parametrize("topk", TOPKS)
@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_multistream_parity(algorithm, topk, path):
    """4-lane lane-batched step vs per-lane single-stream oracle runs.

    Lane 3 is all padding (an unoccupied scheduler lane): its outputs are
    discarded by the scheduler, but its state must ride through
    bit-unchanged and must not perturb the live lanes.
    """
    n_lanes, b = 4, 4
    frames = jnp.stack([_frames(seed=20 + lane, b=b) for lane in range(n_lanes)])
    ids = jnp.stack([jnp.arange(lane * 10, lane * 10 + b, dtype=jnp.int32)
                     for lane in range(n_lanes - 1)]
                    + [jnp.full((b,), -1, jnp.int32)])
    states = [init_atmo_state() for _ in range(n_lanes)]
    packed = pack_atmo_states(states)

    multi = _multi_step(algorithm, topk, path)
    out = multi(frames, ids, packed)

    oracle = make_dehaze_step(_oracle_cfg(algorithm, topk))
    for lane in range(n_lanes - 1):
        want = oracle(frames[lane], ids[lane], states[lane])
        tag = f"{algorithm}/topk{topk}/{path}/lane{lane}"
        np.testing.assert_allclose(np.asarray(out.frames[lane]),
                                   np.asarray(want.frames), atol=TOL_IMG,
                                   err_msg=tag)
        np.testing.assert_allclose(np.asarray(out.transmission[lane]),
                                   np.asarray(want.transmission),
                                   atol=TOL_IMG, err_msg=tag)
        np.testing.assert_allclose(np.asarray(out.atmo_light[lane]),
                                   np.asarray(want.atmo_light), atol=TOL_A,
                                   err_msg=tag)
        np.testing.assert_allclose(np.asarray(out.state.A[lane]),
                                   np.asarray(want.state.A), atol=TOL_A,
                                   err_msg=tag)
        assert int(out.state.last_update[lane]) == int(want.state.last_update)
    # The all-padding lane: state unchanged, bit-for-bit.
    pad = n_lanes - 1
    np.testing.assert_array_equal(np.asarray(out.state.A[pad]),
                                  np.asarray(packed.A[pad]))
    assert int(out.state.last_update[pad]) == int(packed.last_update[pad])
    assert not bool(out.state.initialized[pad])


@pytest.mark.parametrize("n_lanes", [1, 4])
@pytest.mark.parametrize("topk", TOPKS)
@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_multistream_lane_native_matches_vmapped_fused(algorithm, topk,
                                                       n_lanes):
    """The lane-axis refactor's parity bar: the lane-native megakernel
    equals ``jax.vmap`` of the fused single-stream step per lane — for
    lane counts 1 and 4, including an all-padding lane (and, at
    ``n_lanes == 1``, a batch that is *entirely* padding in a second
    step). On the XLA-oracle substrate the two paths are bit-identical;
    on the interpret substrate (the CI kernel-parity job) the separately
    compiled programs may differ by FMA reassociation, bounded at 2 ulp.
    Integer state is exact everywhere.
    """
    from repro.kernels.ops import resolve_mode
    float_tol = 0.0 if resolve_mode("fused") == "ref" else 1.2e-7
    b = 4
    frames = jnp.stack([_frames(seed=60 + lane, b=b)
                        for lane in range(n_lanes)])
    if n_lanes == 1:
        ids = jnp.arange(b, dtype=jnp.int32)[None]
    else:
        ids = jnp.stack(
            [jnp.arange(lane * 7, lane * 7 + b, dtype=jnp.int32)
             for lane in range(n_lanes - 1)]
            + [jnp.full((b,), -1, jnp.int32)])
    packed = pack_atmo_states([init_atmo_state() for _ in range(n_lanes)])

    lane_native = _multi_step(algorithm, topk, "lane_native")
    vmapped = _multi_step(algorithm, topk, "fused")

    def check(got, want, tag):
        for field in ("frames", "transmission", "atmo_light"):
            np.testing.assert_allclose(
                np.asarray(getattr(got, field)),
                np.asarray(getattr(want, field)), atol=float_tol, rtol=0,
                err_msg=f"{field} {tag}")
        np.testing.assert_allclose(np.asarray(got.state.A),
                                   np.asarray(want.state.A), atol=float_tol,
                                   rtol=0, err_msg=f"state.A {tag}")
        np.testing.assert_array_equal(np.asarray(got.state.last_update),
                                      np.asarray(want.state.last_update),
                                      err_msg=tag)
        np.testing.assert_array_equal(np.asarray(got.state.initialized),
                                      np.asarray(want.state.initialized),
                                      err_msg=tag)

    tag = f"{algorithm}/topk{topk}/L{n_lanes}"
    got = lane_native(frames, ids, packed)
    want = vmapped(frames, ids, packed)
    check(got, want, tag)

    # Chain a second batch through the returned states: a state fork
    # between the two realizations would compound here. At n_lanes == 1
    # the second batch is all padding — the whole program must be a state
    # no-op on both paths.
    ids2 = jnp.full_like(ids, -1) if n_lanes == 1 else ids + b
    got2 = lane_native(frames, ids2, got.state)
    want2 = vmapped(frames, ids2, want.state)
    check(got2, want2, tag + "/chained")
    if n_lanes == 1:
        np.testing.assert_array_equal(np.asarray(got2.state.A),
                                      np.asarray(got.state.A))
        np.testing.assert_array_equal(np.asarray(got2.state.last_update),
                                      np.asarray(got.state.last_update))


# ---------------------------------------------------------------------------
# Sharded cells (subprocess with 8 forced host devices per mesh shape)
# ---------------------------------------------------------------------------

def _run_child(body: str, devices: int = 8) -> None:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
    """) + textwrap.dedent(body)
    # CPU only: a child must never reach for a chip its parent may hold.
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.abspath(REPO_SRC))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, f"child failed:\n{proc.stdout}\n{proc.stderr}"


@pytest.mark.parametrize("n_h,n_w", [(2, 1), (1, 2), (2, 2)],
                         ids=["nh2", "nw2", "nh2xnw2"])
def test_sharded_parity_matrix(n_h, n_w):
    """{{dcp, cap}} x {{topk 1, 4}} x {{staged, fused}} on a (2, n_h, n_w)
    mesh vs the single-device ref-oracle chain. Every shard of a 2-shard
    spatial axis touches a mesh edge, so the row/column validity masking
    (and the lexicographic cross-shard top-k merge) is exercised in every
    cell; the (2, 2) mesh adds the corner shards."""
    _run_child(f"""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import (DehazeConfig, make_dehaze_step,
                                make_sharded_dehaze_step, init_atmo_state)
        mesh = jax.make_mesh((2, {n_h}, {n_w}), ("data", "model", "width"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        # Tie-stable ramp frames — see _frames() in the parent module.
        rng = np.random.default_rng(2)
        g = (rng.permutation(4 * 32 * 32).reshape(4, 32, 32) + 1.0) / (4096 + 1.0)
        I = jnp.asarray(np.stack([g, 0.9 * g, 0.8 * g], -1).astype(np.float32))
        ids = jnp.arange(4, dtype=jnp.int32)
        for algo in ("dcp", "cap"):
            for topk in (1, 4):
                base = DehazeConfig(algorithm=algo, kernel_mode="ref",
                                    patch_radius=3, gf_radius=4,
                                    update_period=2, topk=topk)
                want = jax.jit(make_dehaze_step(base))(I, ids,
                                                       init_atmo_state())
                for km in ("ref", "fused"):
                    cfg = DehazeConfig(algorithm=algo, kernel_mode=km,
                                       patch_radius=3, gf_radius=4,
                                       update_period=2, topk=topk)
                    step, _, _ = make_sharded_dehaze_step(
                        cfg, mesh, ("data",), "model", "width")
                    with mesh:
                        out = jax.jit(step)(I, ids, init_atmo_state())
                    tag = f"{{algo}}/topk{{topk}}/{{km}}"
                    np.testing.assert_allclose(
                        np.asarray(out.frames), np.asarray(want.frames),
                        atol=2e-5, err_msg=tag)
                    np.testing.assert_allclose(
                        np.asarray(out.transmission),
                        np.asarray(want.transmission), atol=2e-5,
                        err_msg=tag)
                    np.testing.assert_allclose(
                        np.asarray(out.atmo_light),
                        np.asarray(want.atmo_light), atol=1e-5, err_msg=tag)
                    np.testing.assert_allclose(
                        np.asarray(out.state.A), np.asarray(want.state.A),
                        atol=1e-5, err_msg=tag)
                    assert int(out.state.last_update) == \\
                        int(want.state.last_update), tag
        print("ok")
    """)


def test_sharded_parity_tie_plateau():
    """Adversarial tie cell: a transmission plateau spanning the shard
    boundaries (constant image regions -> piecewise-constant min-filter
    output). The cross-shard merge must still pick the same top-k pixels
    as the single device — this is exactly what the explicit global-index
    sort key exists for."""
    _run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import (DehazeConfig, make_dehaze_step,
                                make_sharded_dehaze_step, init_atmo_state)
        mesh = jax.make_mesh((1, 2, 2), ("data", "model", "width"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        rng = np.random.default_rng(7)
        # Quantized frames: large equal-t plateaus across shard boundaries,
        # but per-pixel RGB still varies inside a plateau (the channel mins
        # tie, the picked colors do not) — wrong tie-breaking shows up in A.
        I = jnp.asarray(np.round(rng.random((2, 32, 32, 3)) * 4) / 4
                        ).astype(jnp.float32)
        I = I * 0.8 + 0.1
        ids = jnp.arange(2, dtype=jnp.int32)
        for km in ("ref", "fused"):
            cfg = DehazeConfig(algorithm="dcp", kernel_mode=km,
                               patch_radius=3, gf_radius=4, topk=4,
                               update_period=1)
            want = jax.jit(make_dehaze_step(
                DehazeConfig(algorithm="dcp", kernel_mode="ref",
                             patch_radius=3, gf_radius=4, topk=4,
                             update_period=1)))(I, ids, init_atmo_state())
            step, _, _ = make_sharded_dehaze_step(cfg, mesh, ("data",),
                                                  "model", "width")
            with mesh:
                out = jax.jit(step)(I, ids, init_atmo_state())
            np.testing.assert_allclose(np.asarray(out.atmo_light),
                                       np.asarray(want.atmo_light),
                                       atol=1e-6, err_msg=km)
            np.testing.assert_allclose(np.asarray(out.state.A),
                                       np.asarray(want.state.A), atol=1e-6,
                                       err_msg=km)
        print("ok")
    """)


# ---------------------------------------------------------------------------
# Fleet cells ({1 host, 2 hosts} x {staged, lane_native}, serving tier).
# The n_h 2 dimension of the fleet bar — lanes sharded over the data axis
# composed with height-halo sharding — runs in
# test_distributed.test_lane_sharded_step_matches_per_lane_single_device.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path", ["staged", "lane_native"])
@pytest.mark.parametrize("n_hosts", [1, 2])
def test_fleet_parity_cells(n_hosts, path):
    """Fleet serve == single-host serve, bit-for-bit per stream: emitted
    frames (the EMA trajectory is baked into every recovered frame via
    a_seq), final EMA state, cursors. Sticky placement asserted: zero EMA
    migrations; at 2 hosts the first-fit waterfall must spill."""
    from repro.stream import ElasticServer, StreamRequest

    cfg = _cfg("dcp", 4, "fused" if path == "lane_native" else "staged")

    def stream_frames():
        return [[np.asarray(f) for f in _frames(seed=40 + i, b=6, h=24, w=24)]
                for i in range(4)]

    def run(server, n):
        sunk = {}
        rep = server.serve_many(
            [StreamRequest(f"v{i}", iter(v))
             for i, v in enumerate(stream_frames())],
            n_lanes=2, n_hosts=n,
            sink=lambda s, f, p: sunk.setdefault(s, []).append((f, p.copy())))
        return rep, sunk

    base = ElasticServer(cfg, batch=3, timeout_s=5.0)
    rep_w, want = run(base, 1)
    srv = ElasticServer(cfg, batch=3, timeout_s=5.0)
    rep_g, got = run(srv, n_hosts)

    tag = f"fleet/{n_hosts}host/{path}"
    assert rep_g.frames == rep_w.frames == 24, tag
    assert rep_g.skipped == 0 and rep_g.migrations == 0, tag
    if n_hosts > 1:
        assert rep_g.spillovers >= 1, tag
        placements = srv.last_fleet.queue.placements
        assert all(e["host"] == placements[e["stream_id"]]
                   for e in srv.last_fleet.queue.admission_log), tag
    for sid in want:
        assert [f for f, _ in got[sid]] == [f for f, _ in want[sid]], tag
        for (_, a), (_, b) in zip(got[sid], want[sid]):
            np.testing.assert_array_equal(a, b, err_msg=f"{tag}/{sid}")
        np.testing.assert_array_equal(
            np.asarray(srv.store.get(sid).A),
            np.asarray(base.store.get(sid).A), err_msg=f"{tag}/{sid}")
        assert srv.store.cursor(sid) == base.store.cursor(sid), tag
