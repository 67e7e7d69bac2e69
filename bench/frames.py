"""Seeded synthetic hazy camera frames, made on the device in one call.

A port of ``repro.data.generate_haze_video`` (the physics of paper Eq. 1-2:
``I = J t + A (1 - t)``, ``t = exp(-beta d)``) to ``jax.numpy``, so that a
pool of 1080p frames for many streams costs one jitted call instead of
seconds of host numpy. One panning scene (albedo with dark speckle, smooth
depth) is shared; each stream gets its own atmospheric light: a base drawn
from the seed, a slow drift and per-frame noise, as in the original.

Frames leave as the uint8 wire quantization ``round(clip(I, 0, 1) * 255)``
with shape ``(streams, pool, H, W, 3)``. The same seed gives the same
bytes on a given backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

A_BASE = (0.90, 0.92, 0.95)
A_BASE_JITTER = 0.05       # per-stream base A drawn in +-this box
A_DRIFT = 0.04
A_NOISE = 0.02
MOTION = 2.0               # scene pan, pixels per frame
DARK_SPECKLE = 0.03
BETA = 1.0
OCTAVES = 4


def _lerp_matrix(n: int, g: int) -> jnp.ndarray:
    """``(n, g)`` weights of the original's linear interpolation of a
    ``g``-point grid at ``n`` evenly spaced positions: the hat function
    ``max(0, 1 - |pos - j|)``, two nonzeros per row."""
    pos = jnp.linspace(0.0, g - 1.0, n, dtype=jnp.float32)[:, None]
    return jnp.maximum(0.0, 1.0 - jnp.abs(pos - jnp.arange(g)[None, :]))


def _smooth_noise(key, h: int, w: int) -> jnp.ndarray:
    """Multi-octave bilinear value noise in [0, 1] (the original's
    ``_smooth_noise``: same grid sizes and interpolation, written as two
    interpolation matrices per octave so that it runs as matmuls on the
    device rather than as gathers)."""
    out = jnp.zeros((h, w), jnp.float32)
    amp, total = 1.0, 0.0
    for o in range(OCTAVES):
        key, sub = jax.random.split(key)
        gh, gw = max(2, h >> (OCTAVES - o)), max(2, w >> (OCTAVES - o))
        grid = jax.random.uniform(sub, (gh, gw), jnp.float32)
        v = jnp.matmul(jnp.matmul(_lerp_matrix(h, gh), grid,
                                  precision="highest"),
                       _lerp_matrix(w, gw).T, precision="highest")
        out = out + amp * v
        total += amp
        amp *= 0.5
    return out / total


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _make_pool(key, streams: int, pool: int, h: int, w: int) -> jnp.ndarray:
    pad = int(MOTION * pool) + 8
    k_alb, k_shadow, k_depth, k_base, k_noise = jax.random.split(key, 5)
    alb_keys = jax.random.split(k_alb, 3)
    albedo = jnp.stack([_smooth_noise(k, h + pad, w + pad) for k in alb_keys],
                       axis=-1)
    albedo = 0.15 + 0.7 * albedo
    shadow = jax.random.uniform(k_shadow, (h + pad, w + pad)) < DARK_SPECKLE
    albedo = jnp.where(shadow[..., None], albedo * 0.05, albedo)
    depth_world = 0.3 + 2.2 * _smooth_noise(k_depth, h + pad, w + pad)

    base = jnp.asarray(A_BASE, jnp.float32) + jax.random.uniform(
        k_base, (streams, 3), jnp.float32, -A_BASE_JITTER, A_BASE_JITTER)
    i = jnp.arange(pool, dtype=jnp.float32)
    drift = A_DRIFT * jnp.sin(2 * jnp.pi * i / pool)              # (P,)
    noise = A_NOISE * jax.random.normal(k_noise, (streams, pool, 3))
    A = jnp.clip(base[:, None, :] + drift[None, :, None] + noise, 0.6, 1.0)

    def frame(j):
        off = (MOTION * j).astype(jnp.int32)
        J = jax.lax.dynamic_slice(albedo, (off, off, 0), (h, w, 3))
        d = jax.lax.dynamic_slice(depth_world, (off, off), (h, w))
        return J, jnp.exp(-BETA * d)

    def per_frame(j):
        J, t = frame(j.astype(jnp.float32))
        a = A[:, j][:, None, None, :]                               # (S,1,1,3)
        hazy = J[None] * t[None, ..., None] + a * (1.0 - t[None, ..., None])
        return jnp.round(jnp.clip(hazy, 0.0, 1.0) * 255.0).astype(jnp.uint8)

    out = jax.lax.map(per_frame, jnp.arange(pool))                  # (P,S,...)
    return jnp.swapaxes(out, 0, 1)


def jax_key(seed: int):
    """A JAX key from any non-negative whole seed (also above 2**32)."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def make_pool(seed: int, streams: int, pool: int, h: int, w: int,
              timings: dict = None) -> np.ndarray:
    """``(streams, pool, h, w, 3)`` uint8 hazy frames on the host.
    ``timings``, when given, gets the seconds spent tracing and compiling
    (or loading from the compile cache) the generator, running it, and
    fetching the frames."""
    import time
    t0 = time.perf_counter()
    key = jax_key(seed)
    compiled = _make_pool.lower(key, streams, pool, h, w).compile()
    t1 = time.perf_counter()
    dev = jax.block_until_ready(compiled(key))
    t2 = time.perf_counter()
    host = np.asarray(jax.device_get(dev))
    dev.delete()
    if timings is not None:
        timings.update(compile_s=t1 - t0, run_s=t2 - t1,
                       fetch_s=time.perf_counter() - t2)
    return host
