"""Spatial (within-frame) parallelism primitives: halo exchange + masked filters.

The paper parallelizes only *across* frames (its unit of work is one frame
on one thread). On a TPU mesh we additionally shard the image height over
the ``model`` axis so a single high-resolution frame is processed by many
chips — the windowed min/box filters then need ``halo`` rows of context
from neighboring shards, fetched with ``lax.ppermute``.

Halo composition rule for the full DCP/CAP chain:
  halo = patch_radius (+ 2 * gf_radius when guided refinement is on),
because the guided filter consumes t_raw within 2r_gf of the core and
t_raw itself consumes the image within patch_radius of that.

Both spatial axes shard: image height over one mesh axis and image width
over another (``halo_exchange_height`` then ``halo_exchange_width`` — the
W exchange moves H-extended blocks, so diagonal corner halos need no extra
collective). Shards at the mesh edge receive no neighbor rows/columns; a
*separable* validity mask (per-axis row and column vectors, combined as an
outer product) restores the exact global border semantics (clipped
windows): min filters treat invalid rows/cols as +inf, box filters exclude
them from both sum and count, so the sharded pipeline is bit-comparable to
the single-device one (verified in tests/test_distributed.py and
tests/test_parity_matrix.py).

In-kernel masking contract (the fused halo path): with
``kernel_mode="fused"`` the masked filters below are *not* launched as a
per-stage XLA chain — the halo-exchange outputs (the packed (pre-map,
guide) planes plus the row/column validity vectors) feed
``kernels.fused.fused_transmission_halo_pallas`` directly, and the kernel
applies the identical masking rules in VMEM: pixels whose row *or* column
is invalid become +inf before the separable min passes, and the box-filter
divisor is (windowed sum of the row mask) x (windowed sum of the column
mask), never counting masked pixels. Any change to the masking semantics
here must be mirrored there (and in ``kernels.ref.fused_transmission_halo``
and ``kernels.boxfilter._masked_box_mean``); parity across them is
asserted to 1e-5 in tests/test_fused.py, tests/test_distributed.py and
tests/test_parity_matrix.py, including mesh-edge shards.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax


# ---------------------------------------------------------------------------
# Masked separable filters (reduce_window based — XLA path used under
# shard_map; the unmasked Pallas kernels remain the single-shard fast path).
# ---------------------------------------------------------------------------

def _mask_2d(valid: jnp.ndarray, valid_w) -> jnp.ndarray:
    """(H,) row validity [x (W,) column validity] -> broadcastable 2-D mask.

    The halo masks are separable (outer products of per-axis validity), so
    every masked filter takes the two 1-D masks and combines them here.
    """
    mask = valid[:, None]
    if valid_w is not None:
        mask = jnp.logical_and(mask, valid_w[None, :])
    return mask


def masked_min_filter_2d(x: jnp.ndarray, valid: jnp.ndarray, radius: int,
                         valid_w: jnp.ndarray = None) -> jnp.ndarray:
    """Windowed min ignoring rows/columns where validity is False.

    x: (..., H, W); valid: (H,) row validity; valid_w: optional (W,)
    column validity (the W-sharded halo path).
    """
    big = jnp.asarray(jnp.inf, jnp.float32)
    xm = jnp.where(_mask_2d(valid, valid_w), x.astype(jnp.float32), big)
    from repro.kernels import ref
    return ref.min_filter_2d(xm, radius).astype(x.dtype)


def masked_box_filter_2d(x: jnp.ndarray, valid: jnp.ndarray, radius: int,
                         valid_w: jnp.ndarray = None) -> jnp.ndarray:
    """Windowed mean over valid rows/columns only (count excludes invalid)."""
    mask = _mask_2d(valid, valid_w)
    # `where`, not multiply: invalid rows may hold ±inf from an upstream
    # masked min filter and inf * 0 would poison the sums with NaN.
    xm = jnp.where(mask, x.astype(jnp.float32), 0.0)
    k = 2 * radius + 1
    ndim = x.ndim
    dims_r = (1,) * (ndim - 2) + (k, 1)
    pads_r = ((0, 0),) * (ndim - 2) + ((radius, radius), (0, 0))
    dims_c = (1,) * (ndim - 2) + (1, k)
    pads_c = ((0, 0),) * (ndim - 2) + ((0, 0), (radius, radius))

    def wsum(a):
        s = lax.reduce_window(a, 0.0, lax.add, dims_r, (1,) * ndim, pads_r)
        return lax.reduce_window(s, 0.0, lax.add, dims_c, (1,) * ndim, pads_c)

    def count(v, n):
        # In-window valid count along one axis. The mask is separable, so
        # the 2-D count is the outer product of the two 1-D counts: a
        # full-plane reduce_window over the mask would be constant-folded
        # by XLA whenever the mask is constant (no spatial sharding),
        # which takes minutes at 1080p.
        v = jnp.ones((n,)) if v is None else v
        return lax.reduce_window(v.astype(jnp.float32), 0.0, lax.add, (k,),
                                 (1,), ((radius, radius),))

    acc = wsum(xm)
    cnt = (count(valid, x.shape[-2])[:, None]
           * count(valid_w, x.shape[-1])[None, :])
    return (acc / jnp.maximum(cnt, 1.0)).astype(x.dtype)


def masked_guided_filter(guide: jnp.ndarray, src: jnp.ndarray,
                         valid: jnp.ndarray, radius: int, eps: float,
                         valid_w: jnp.ndarray = None) -> jnp.ndarray:
    """Guided filter with all five means computed over valid rows/cols only."""
    g = guide.astype(jnp.float32)
    p = src.astype(jnp.float32)
    bf = lambda a: masked_box_filter_2d(a, valid, radius, valid_w)
    mean_g = bf(g)
    mean_p = bf(p)
    corr_gp = bf(g * p)
    corr_gg = bf(g * g)
    var_g = corr_gg - mean_g * mean_g
    cov_gp = corr_gp - mean_g * mean_p
    a = cov_gp / (var_g + eps)
    b = mean_p - a * mean_g
    return (bf(a) * g + bf(b)).astype(src.dtype)


# ---------------------------------------------------------------------------
# Halo exchange along a mesh axis sharding image height
# ---------------------------------------------------------------------------

def halo_exchange_along(x: jnp.ndarray, halo: int, axis_name: str,
                        n_shards: int,
                        axis: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Extend local blocks with ``halo`` slices of context from each side
    along array ``axis`` (1 = image height, 2 = image width).

    x: local block whose ``axis`` dimension is globally sharded over mesh
    axis ``axis_name`` (shard 0 holds the leading slices). Returns
    ``(x_ext, valid)`` where ``x_ext`` grows ``axis`` by ``2*halo`` and
    ``valid`` is a (size + 2*halo,) mask marking slices that exist in the
    global image.

    Slices that live ``s`` shards away arrive via a single distance-s
    ``ppermute`` (any fixed permutation is one collective on TPU), so a
    halo spanning multiple shards costs ceil(halo/size) permutes per side,
    each moving only the slices actually needed.
    """
    size = x.shape[axis]
    if halo == 0:
        return x, jnp.ones((size,), bool)
    hops = math.ceil(halo / size)
    idx = lax.axis_index(axis_name)

    lead_parts = []   # ordered first -> last, total `halo` slices
    trail_parts = []
    for s in range(hops, 0, -1):
        # Slices contributed by the shard `s` before us: its last c_s ones.
        c_s = min(size, halo - (s - 1) * size)
        if c_s <= 0:
            continue
        down_perm = [(j, j + s) for j in range(n_shards - s)]
        up_perm = [(j + s, j) for j in range(n_shards - s)]
        from_before = lax.ppermute(
            lax.slice_in_dim(x, size - c_s, size, axis=axis),
            axis_name, down_perm)
        from_after = lax.ppermute(
            lax.slice_in_dim(x, 0, c_s, axis=axis), axis_name, up_perm)
        lead_parts.append((from_before, s, c_s))
        trail_parts.append((from_after, s, c_s))

    x_ext = jnp.concatenate([p for p, _, _ in lead_parts] + [x] +
                            [p for p, _, _ in reversed(trail_parts)],
                            axis=axis)

    # Validity: a leading part from distance s exists iff idx >= s; a
    # trailing one iff idx < n_shards - s.
    parts = []
    for _, s, c_s in lead_parts:
        parts.append(jnp.broadcast_to(idx >= s, (c_s,)))
    parts.append(jnp.ones((size,), bool))
    for _, s, c_s in reversed(trail_parts):
        parts.append(jnp.broadcast_to(idx < n_shards - s, (c_s,)))
    return x_ext, jnp.concatenate(parts)


def halo_exchange_height(x: jnp.ndarray, halo: int, axis_name: str,
                         n_shards: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(B, H_loc, W, C) block, H sharded over ``axis_name`` -> H-extended
    block + (H_loc + 2*halo,) row validity. See ``halo_exchange_along``."""
    return halo_exchange_along(x, halo, axis_name, n_shards, axis=1)


def halo_exchange_width(x: jnp.ndarray, halo: int, axis_name: str,
                        n_shards: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(B, H, W_loc, C) block, W sharded over ``axis_name`` -> W-extended
    block + (W_loc + 2*halo,) column validity.

    Runs *after* the height exchange when both axes are sharded: the
    H-extended block (every shard holds one) is what rides the W-axis
    ppermute, so the diagonal corner halos arrive for free — the W-neighbor
    already concatenated its own H-neighbors' rows, and its row validity is
    identical to ours (same height-axis coordinate).
    """
    return halo_exchange_along(x, halo, axis_name, n_shards, axis=2)
