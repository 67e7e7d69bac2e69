"""Ahead-of-time compiles of the serving step for a described TPU v5e.

The TPU compiler is installed without a chip: it compiles for a topology
that is described, not attached, and refuses what the chip would refuse
(block layouts, VMEM and HBM overruns). These tests compile the device
path that ``kernel_mode="auto"`` resolves to, the XLA component chain, at
a camera deployment's real size (8 x 1080 x 1920 uint8 frames), single
stream and lane-batched, and check that it fits one chip's 16 GB and
contains no Pallas kernel.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and under several
pytest workers only the worker that runs this file may take it.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import (DehazeConfig, PlacementSpec, init_atmo_state,
                        init_atmo_state_lanes, make_step)

HBM_BYTES = 16 * 10 ** 9          # one v5e chip
B, H, W = 8, 1080, 1920


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                        # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(x, sharding):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _compile(step, frames_shape, ids_shape, state, sharding):
    state_spec = jax.tree_util.tree_map(
        lambda x: _spec(x, sharding), jax.eval_shape(lambda: state))
    return step.lower(
        jax.ShapeDtypeStruct(frames_shape, jnp.uint8, sharding=sharding),
        jax.ShapeDtypeStruct(ids_shape, jnp.int32, sharding=sharding),
        state_spec).compile()


def _serving_step(algorithm, batch, lanes, sharding):
    """The auto-mode uint8 serving step at 1080p, compiled for one chip."""
    cfg = DehazeConfig(algorithm=algorithm, io_dtype="uint8")  # mode "auto"
    if lanes:
        step = make_step(cfg, PlacementSpec.lane_batched(), donate="state")
        return _compile(step, (lanes, batch, H, W, 3), (lanes, batch),
                        init_atmo_state_lanes(lanes), sharding)
    step = make_step(cfg, PlacementSpec.single(), donate=True)
    return _compile(step, (batch, H, W, 3), (batch,), init_atmo_state(),
                    sharding)


@pytest.mark.parametrize("lanes", [0, 2], ids=["single", "lanes2"])
def test_serving_step_compiles_for_v5e(one_chip, lanes):
    compiled = _serving_step("dcp", B, lanes, one_chip)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 1e9:.2f} GB does not fit one chip"
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("lanes", [0, 2], ids=["single", "lanes2"])
@pytest.mark.parametrize("algorithm,batch", [("dcp", B), ("cap", 1)],
                         ids=["dcp-b8", "cap-b1"])
def test_serving_step_picks_light_without_sort(one_chip, algorithm, batch,
                                               lanes):
    """At ``topk=1`` Eq. 6's pixel is an argmin reduction: the step holds
    no ``TopK`` custom call and no sort of the 2,073,600-pixel plane."""
    text = _serving_step(algorithm, batch, lanes, one_chip).as_text()
    assert "TopK" not in text
    assert "sort(" not in text
