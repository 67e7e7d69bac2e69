"""Multi-device semantics, validated in subprocesses with 8 host devices.

conftest must NOT set --xla_force_host_platform_device_count globally (the
smoke tests need the real single device), so every test here launches a
fresh python with the flag and asserts inside the child.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_child(body: str, devices: int = 8) -> None:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
    """) + textwrap.dedent(body)
    # CPU only: a child must never reach for a chip its parent may hold.
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.abspath(REPO_SRC))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"child failed:\n{proc.stdout}\n{proc.stderr}"


def test_sharded_dehaze_matches_single_device():
    run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import (DehazeConfig, make_dehaze_step,
                                make_sharded_dehaze_step, init_atmo_state)
        from repro.core.physics import synthesize_haze, transmission_from_depth
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rng = np.random.default_rng(2)
        B, H, W = 4, 64, 48
        J = jnp.asarray(rng.random((B, H, W, 3), np.float32)) * 0.8
        t = transmission_from_depth(
            jnp.asarray(rng.random((B, H, W), np.float32)) * 2 + 0.2, 1.0)
        I = synthesize_haze(J, t, jnp.asarray([0.9, 0.85, 0.95]))
        ids = jnp.arange(B, dtype=jnp.int32)
        for algo in ("dcp", "cap"):
            cfg = DehazeConfig(algorithm=algo, kernel_mode="ref", gf_radius=8)
            ref = jax.jit(make_dehaze_step(cfg))(I, ids, init_atmo_state())
            step, _, _ = make_sharded_dehaze_step(cfg, mesh)
            with mesh:
                out = jax.jit(step)(I, ids, init_atmo_state())
            np.testing.assert_allclose(np.asarray(out.frames),
                                       np.asarray(ref.frames), atol=2e-5)
            np.testing.assert_allclose(np.asarray(out.transmission),
                                       np.asarray(ref.transmission), atol=2e-5)
            np.testing.assert_allclose(np.asarray(out.atmo_light),
                                       np.asarray(ref.atmo_light), atol=1e-5)
            np.testing.assert_allclose(np.asarray(out.state.A),
                                       np.asarray(ref.state.A), atol=1e-5)
        print("ok")
    """)


def test_sharded_dehaze_multihop_halo():
    """Halo larger than the per-shard height -> multi-hop ppermute path."""
    run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import (DehazeConfig, make_dehaze_step,
                                make_sharded_dehaze_step, init_atmo_state)
        mesh = jax.make_mesh((1, 8), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rng = np.random.default_rng(3)
        B, H, W = 2, 64, 32          # 8 rows/shard
        I = jnp.asarray(rng.random((B, H, W, 3), np.float32))
        ids = jnp.arange(B, dtype=jnp.int32)
        # patch 7 + 2*gf 12 = halo 31 -> 4 hops over 8-row shards
        cfg = DehazeConfig(algorithm="dcp", kernel_mode="ref",
                           patch_radius=7, gf_radius=12)
        ref = jax.jit(make_dehaze_step(cfg))(I, ids, init_atmo_state())
        step, _, _ = make_sharded_dehaze_step(cfg, mesh)
        with mesh:
            out = jax.jit(step)(I, ids, init_atmo_state())
        np.testing.assert_allclose(np.asarray(out.frames),
                                   np.asarray(ref.frames), atol=2e-5)
        print("ok")
    """)


def test_packed_halo_matches_rgb_halo():
    """Perf lever (EXPERIMENTS §Perf): exchanging the packed 2-channel
    (pre-map, guide) halo — optionally in bf16 — must match the full-RGB
    halo path within dtype tolerance."""
    run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import (DehazeConfig, make_dehaze_step,
                                make_sharded_dehaze_step, init_atmo_state)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rng = np.random.default_rng(2)
        I = jnp.asarray(rng.random((4, 64, 48, 3), np.float32))
        ids = jnp.arange(4, dtype=jnp.int32)
        for algo in ("dcp", "cap"):
            base = DehazeConfig(algorithm=algo, kernel_mode="ref", gf_radius=8)
            ref = jax.jit(make_dehaze_step(base))(I, ids, init_atmo_state())
            for hdt, tol in (("float32", 3e-5), ("bfloat16", 2e-2)):
                cfg = DehazeConfig(algorithm=algo, kernel_mode="ref",
                                   gf_radius=8, halo_packed=True,
                                   halo_dtype=hdt)
                step, _, _ = make_sharded_dehaze_step(cfg, mesh)
                with mesh:
                    out = jax.jit(step)(I, ids, init_atmo_state())
                np.testing.assert_allclose(np.asarray(out.frames),
                                           np.asarray(ref.frames), atol=tol)
        print("ok")
    """)


def test_sharded_fused_halo_matches_staged_chain():
    """Height sharding (n_h > 1) keeps ``use_fused``: the halo-aware fused
    kernel (fed by the packed (pre-map, guide) exchange + row-validity
    masking) must match the single-device per-stage chain — including the
    mesh-edge shards — on both the XLA oracle and the interpreted kernel
    body. A spy asserts the fused halo op is actually what ran."""
    run_child("""
        import os
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import (DehazeConfig, make_dehaze_step,
                                make_sharded_dehaze_step, init_atmo_state)
        import repro.kernels.ops as kops

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rng = np.random.default_rng(2)
        I = jnp.asarray(rng.random((4, 64, 48, 3), np.float32))
        ids = jnp.arange(4, dtype=jnp.int32)

        calls = []
        orig = kops.fused_transmission_halo
        def spy(*a, **k):
            calls.append(1)
            return orig(*a, **k)
        kops.fused_transmission_halo = spy

        for algo in ("dcp", "cap"):
            base = DehazeConfig(algorithm=algo, kernel_mode="ref",
                                gf_radius=8, update_period=2)
            want = jax.jit(make_dehaze_step(base))(I, ids, init_atmo_state())
            for env, packed in (("", False), ("", True), ("interpret", False)):
                if env:
                    os.environ["REPRO_KERNEL_MODE"] = env
                else:
                    os.environ.pop("REPRO_KERNEL_MODE", None)
                cfg = DehazeConfig(algorithm=algo, kernel_mode="fused",
                                   gf_radius=8, update_period=2,
                                   halo_packed=packed)
                n0 = len(calls)
                step, _, _ = make_sharded_dehaze_step(cfg, mesh)
                with mesh:
                    out = jax.jit(step)(I, ids, init_atmo_state())
                assert len(calls) > n0, "fused halo path was not taken"
                np.testing.assert_allclose(np.asarray(out.frames),
                                           np.asarray(want.frames), atol=1e-5)
                np.testing.assert_allclose(
                    np.asarray(out.transmission),
                    np.asarray(want.transmission), atol=1e-5)
                np.testing.assert_allclose(np.asarray(out.atmo_light),
                                           np.asarray(want.atmo_light),
                                           atol=1e-5)
                np.testing.assert_allclose(np.asarray(out.state.A),
                                           np.asarray(want.state.A),
                                           atol=1e-5)
        print("ok")
    """)


def test_sharded_fused_halo_multihop():
    """Fused halo path when the halo spans multiple shards (multi-hop
    ppermute) — the extended block is mostly neighbor rows."""
    run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import (DehazeConfig, make_dehaze_step,
                                make_sharded_dehaze_step, init_atmo_state)
        mesh = jax.make_mesh((1, 8), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rng = np.random.default_rng(3)
        I = jnp.asarray(rng.random((2, 64, 32, 3), np.float32))
        ids = jnp.arange(2, dtype=jnp.int32)
        # patch 7 + 2*gf 12 = halo 31 -> 4 hops over 8-row shards
        base = DehazeConfig(algorithm="dcp", kernel_mode="ref",
                            patch_radius=7, gf_radius=12)
        want = jax.jit(make_dehaze_step(base))(I, ids, init_atmo_state())
        cfg = DehazeConfig(algorithm="dcp", kernel_mode="fused",
                           patch_radius=7, gf_radius=12)
        step, _, _ = make_sharded_dehaze_step(cfg, mesh)
        with mesh:
            out = jax.jit(step)(I, ids, init_atmo_state())
        np.testing.assert_allclose(np.asarray(out.frames),
                                   np.asarray(want.frames), atol=1e-5)
        print("ok")
    """)


def test_moe_ep_matches_single_device():
    """Expert-parallel all-to-all MoE == single-device execution."""
    run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.models import transformer as T
        from repro.models import common as cm
        cfg = T.LMConfig(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                         head_dim=8, d_ff=64, vocab=64, moe_experts=8,
                         moe_topk=2, moe_capacity_factor=8.0,
                         dtype="float32", kv_block=16, remat=False)
        params = cm.init_params(jax.random.key(0), T.lm_param_table(cfg))
        toks = jax.random.randint(jax.random.key(1), (4, 8), 0, 64)
        ref_logits, _ = jax.jit(T.make_forward(cfg))(params, toks)

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        fwd = T.make_forward(cfg, mesh, ("data",))
        from jax.sharding import NamedSharding, PartitionSpec as P
        pspecs = cm.param_pspecs(T.lm_param_table(cfg), mesh=mesh)
        shard = jax.tree.map(lambda p: NamedSharding(mesh, p), pspecs,
                             is_leaf=lambda x: isinstance(x, P))
        with mesh:
            jf = jax.jit(fwd, in_shardings=(shard,
                         NamedSharding(mesh, P("data", None))))
            logits, _ = jf(params, toks)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(ref_logits), atol=3e-4)
        print("ok")
    """)


def test_ema_state_sync_across_batches_sharded():
    """The EMA chain must continue across batches when frames are sharded
    over the data axis (collective state synchronization)."""
    run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import (DehazeConfig, make_dehaze_step,
                                make_sharded_dehaze_step, init_atmo_state)
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rng = np.random.default_rng(5)
        cfg = DehazeConfig(kernel_mode="ref", gf_radius=4, update_period=3)
        step_ref = jax.jit(make_dehaze_step(cfg))
        step_sh, _, _ = make_sharded_dehaze_step(cfg, mesh)
        state_r = state_s = init_atmo_state()
        for chunk in range(3):
            I = jnp.asarray(rng.random((8, 32, 32, 3), np.float32))
            ids = jnp.arange(chunk * 8, chunk * 8 + 8, dtype=jnp.int32)
            out_r = step_ref(I, ids, state_r); state_r = out_r.state
            with mesh:
                out_s = jax.jit(step_sh)(I, ids, state_s); state_s = out_s.state
            np.testing.assert_allclose(np.asarray(out_s.atmo_light),
                                       np.asarray(out_r.atmo_light), atol=1e-5)
        assert int(state_s.last_update) == int(state_r.last_update)
        print("ok")
    """)


def test_seqpar_flash_decode_matches_standard():
    """Distributed flash-decoding (KV cache sequence-sharded over the
    model axis, pmax/psum softmax combine) == standard decode, for both
    full and chunked attention (EXPERIMENTS §Perf / long_500k)."""
    run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models import transformer as T, common as cm
        for chunk in (0, 8):
            cfg = T.LMConfig(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                             head_dim=8, d_ff=64, vocab=64, dtype="float32",
                             kv_block=16, remat=False, chunk_attn=chunk,
                             global_every=2)
            params = cm.init_params(jax.random.key(0), T.lm_param_table(cfg))
            toks = jax.random.randint(jax.random.key(1), (2, 17), 0, 64)
            pre = jax.jit(T.make_prefill(cfg, max_len=32))
            dec = jax.jit(T.make_decode_step(cfg))
            last, cache = pre(params, toks[:, :16])
            ref_lg, ref_cache = dec(params, cache, toks[:, 16:17])
            mesh = jax.make_mesh((2, 4), ("data", "model"),
                                 axis_types=(jax.sharding.AxisType.Auto,) * 2)
            cfg2 = T.LMConfig(**{**cfg.__dict__, "decode_seq_shard": True})
            dec2 = T.make_decode_step(cfg2, mesh, ("data",))
            spec = {"k": P(None, "data", "model", None, None),
                    "v": P(None, "data", "model", None, None), "pos": P()}
            sc = jax.tree.map(lambda x, sp: jax.device_put(
                x, NamedSharding(mesh, sp)), cache, spec)
            with mesh:
                lg2, c2 = jax.jit(dec2)(params, sc, toks[:, 16:17])
            np.testing.assert_allclose(np.asarray(lg2), np.asarray(ref_lg),
                                       atol=3e-4)
            np.testing.assert_allclose(np.asarray(c2["k"]),
                                       np.asarray(ref_cache["k"]), atol=1e-5)
        print("ok")
    """)


def test_seq_sharded_lm_forward_matches():
    """LM forward with batch+TP sharding == single device (numerics)."""
    run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.models import transformer as T
        from repro.models import common as cm
        from jax.sharding import NamedSharding, PartitionSpec as P
        cfg = T.LMConfig(n_layers=2, d_model=32, n_heads=4, n_kv_heads=4,
                         head_dim=8, d_ff=64, vocab=64, dtype="float32",
                         kv_block=16, remat=False)
        params = cm.init_params(jax.random.key(0), T.lm_param_table(cfg))
        toks = jax.random.randint(jax.random.key(1), (4, 16), 0, 64)
        ref, _ = jax.jit(T.make_forward(cfg))(params, toks)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        pspecs = cm.param_pspecs(T.lm_param_table(cfg), mesh=mesh)
        shard = jax.tree.map(lambda p: NamedSharding(mesh, p), pspecs,
                             is_leaf=lambda x: isinstance(x, P))
        with mesh:
            jf = jax.jit(T.make_forward(cfg, mesh, ("data",)),
                         in_shardings=(shard, NamedSharding(mesh, P("data", None))))
            got, _ = jf(params, toks)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=3e-4)
        print("ok")
    """)


def test_batch_axes_for_pod_prefix():
    """Regression: the prefix walk must try ("pod", "data") THEN ("pod",) —
    the old loop built prefixes back-to-front and could never return the
    pod-only prefix, silently replicating pod-divisible batches."""
    from repro.launch.mesh import batch_axes_for

    class PodMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 3, "model": 4}

    class DataMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 2}

    assert batch_axes_for(PodMesh(), 6) == ("pod", "data")
    assert batch_axes_for(PodMesh(), 12) == ("pod", "data")
    assert batch_axes_for(PodMesh(), 4) == ("pod",)     # the fixed case
    assert batch_axes_for(PodMesh(), 2) == ("pod",)
    assert batch_axes_for(PodMesh(), 9) is None         # divides neither
    assert batch_axes_for(PodMesh(), 3) is None         # data alone: no prefix
    assert batch_axes_for(DataMesh(), 8) == ("data",)
    assert batch_axes_for(DataMesh(), 3) is None


def test_lane_sharded_step_matches_per_lane_single_device():
    """The tentpole composition: lanes sharded over the data axis (each
    lane's causal chain shard-local, EMA rows co-placed via P(lane_axis)),
    with and without simultaneous height-halo sharding (n_h 2) — both the
    lane-native/fused and the staged ref substrate must match L independent
    single-device ``make_dehaze_step`` chains."""
    run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import (DehazeConfig, PlacementSpec, make_step,
                                make_dehaze_step, init_atmo_state,
                                init_atmo_state_lanes, get_lane_state)
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rng = np.random.default_rng(7)
        L, B, H, W = 4, 3, 32, 32
        frames = jnp.asarray(rng.random((L, B, H, W, 3), np.float32))
        ids = jnp.tile(jnp.arange(B, dtype=jnp.int32), (L, 1))
        placements = [
            PlacementSpec.lane_sharded(lane_axis="data"),
            PlacementSpec.lane_sharded(lane_axis="data",
                                       height_axis="model"),   # n_h 2
        ]
        for mode, tol in (("ref", 3e-5), ("fused", 2e-4)):
            cfg = DehazeConfig(kernel_mode=mode, patch_radius=3,
                               gf_radius=4, update_period=2, topk=4)
            ref_step = jax.jit(make_dehaze_step(
                DehazeConfig(kernel_mode="ref", patch_radius=3,
                             gf_radius=4, update_period=2, topk=4)))
            refs = [ref_step(frames[l], ids[l], init_atmo_state())
                    for l in range(L)]
            for place in placements:
                step = make_step(cfg, place, mesh)
                with mesh:
                    out = jax.jit(step)(frames, ids,
                                        init_atmo_state_lanes(L))
                for l in range(L):
                    np.testing.assert_allclose(
                        np.asarray(out.frames[l]),
                        np.asarray(refs[l].frames), atol=tol)
                    np.testing.assert_allclose(
                        np.asarray(out.atmo_light[l]),
                        np.asarray(refs[l].atmo_light), atol=tol)
                    st = get_lane_state(out.state, l)
                    np.testing.assert_allclose(
                        np.asarray(st.A), np.asarray(refs[l].state.A),
                        atol=tol)
                    assert int(st.last_update) \\
                        == int(refs[l].state.last_update)
        print("ok")
    """, devices=4)
