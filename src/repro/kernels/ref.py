"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground-truth implementations: numerically exact, shape-
polymorphic, differentiable where meaningful. The Pallas kernels in the
sibling modules must ``allclose`` against these across the shape/dtype
sweeps in ``tests/test_kernels.py``.

Conventions: images are ``(..., H, W, C)`` float in [0, 1]; scalar maps are
``(..., H, W)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


# ---------------------------------------------------------------------------
# Frame I/O dtype contract (README §Dtype contract)
# ---------------------------------------------------------------------------

U8_SCALE = 255.0


def upcast_frames(x: jnp.ndarray) -> jnp.ndarray:
    """Wire/ingest dtype -> the f32 compute domain.

    THE canonical ingest upcast: the megakernels run it in-VMEM after the
    HBM copy and the oracles/staged chain run it in XLA, so every path sees
    bit-identical f32 frames and uint8-ingest parity stays exact. uint8
    frames are the wire quantization ``round(v * 255)`` of the [0,1] image
    (so the upcast is ``x / 255``); bf16 -> f32 is an exact widening cast;
    f32 is the identity.
    """
    if x.dtype == jnp.uint8:
        return x.astype(jnp.float32) * jnp.float32(1.0 / U8_SCALE)
    return x.astype(jnp.float32)


def resolve_out_dtype(in_dtype, out_dtype=None) -> jnp.dtype:
    """Resolve the J/t output dtype. ``None``/"auto" follows the ingest
    dtype for float ingest (f32 -> f32, bf16 -> bf16 — the pre-contract
    behavior) and resolves to float32 for uint8 ingest (dehazed frames are
    continuous; re-quantizing is the caller's choice, not the kernel's).
    """
    if out_dtype is not None and out_dtype != "auto":
        return jnp.dtype(out_dtype)
    d = jnp.dtype(in_dtype)
    return d if jnp.issubdtype(d, jnp.floating) else jnp.dtype(jnp.float32)


def quantize_frames(x, io_dtype):
    """Host-side [0,1] float frames -> the wire dtype (numpy in, numpy out).

    The inverse of :func:`upcast_frames` up to quantization: uint8 is
    ``round(clip(v, 0, 1) * 255)``, floats are a plain cast. Used by the
    serve driver and the parity tests to synthesize wire-dtype streams.
    """
    import numpy as np
    dt = jnp.dtype(io_dtype)
    if dt == jnp.uint8:
        arr = np.asarray(x, np.float32)
        return np.clip(np.round(arr * U8_SCALE), 0.0, U8_SCALE).astype(np.uint8)
    return np.asarray(x).astype(dt)


# ---------------------------------------------------------------------------
# Windowed min filter (dark channel prior, paper Eq. 3)
# ---------------------------------------------------------------------------

def min_filter_2d(x: jnp.ndarray, radius: int) -> jnp.ndarray:
    """Windowed minimum over a (2r+1)x(2r+1) box, clipped at borders.

    Border semantics match DCP's patch definition: the window is the
    intersection of the box with the image (equivalent to +inf padding).
    ``x``: (..., H, W).
    """
    if radius == 0:
        return x
    k = 2 * radius + 1
    ndim = x.ndim
    dims = (1,) * (ndim - 2) + (k, 1)
    pads = ((0, 0),) * (ndim - 2) + ((radius, radius), (0, 0))
    # Separable: rows then cols.
    rows = lax.reduce_window(x, jnp.inf, lax.min, dims, (1,) * ndim, pads)
    dims_c = (1,) * (ndim - 2) + (1, k)
    pads_c = ((0, 0),) * (ndim - 2) + ((0, 0), (radius, radius))
    out = lax.reduce_window(rows, jnp.inf, lax.min, dims_c, (1,) * ndim, pads_c)
    return out.astype(x.dtype)


def dark_channel(img: jnp.ndarray, radius: int) -> jnp.ndarray:
    """min over channels then windowed min (He et al. DCP). (...,H,W,3)->(...,H,W)."""
    return min_filter_2d(jnp.min(img, axis=-1), radius)


# ---------------------------------------------------------------------------
# Box filter / guided filter (He et al. [28], transmission refinement)
# ---------------------------------------------------------------------------

def box_filter_2d(x: jnp.ndarray, radius: int) -> jnp.ndarray:
    """Windowed mean over a (2r+1)^2 box normalized by the per-pixel count
    of in-bounds window elements (matches the reference guided-filter code).
    """
    if radius == 0:
        return x
    k = 2 * radius + 1
    ndim = x.ndim
    dims_r = (1,) * (ndim - 2) + (k, 1)
    pads_r = ((0, 0),) * (ndim - 2) + ((radius, radius), (0, 0))
    dims_c = (1,) * (ndim - 2) + (1, k)
    pads_c = ((0, 0),) * (ndim - 2) + ((0, 0), (radius, radius))

    def windowed_sum(v):
        s = lax.reduce_window(v, 0.0, lax.add, dims_r, (1,) * ndim, pads_r)
        return lax.reduce_window(s, 0.0, lax.add, dims_c, (1,) * ndim, pads_c)

    acc = windowed_sum(x.astype(jnp.float32))
    # Closed-form per-pixel in-bounds window counts (avoids a second
    # reduce_window over a constant ones-image, which XLA would try to
    # constant-fold at compile time).
    h, w = x.shape[-2], x.shape[-1]

    def axis_counts(n):
        i = jnp.arange(n, dtype=jnp.float32)
        return (jnp.minimum(i + radius, n - 1.0)
                - jnp.maximum(i - radius, 0.0) + 1.0)

    cnt = axis_counts(h)[:, None] * axis_counts(w)[None, :]
    return (acc / cnt).astype(x.dtype)


def guided_filter(guide: jnp.ndarray, src: jnp.ndarray, radius: int,
                  eps: float) -> jnp.ndarray:
    """Gray-guide guided filter. guide/src: (..., H, W)."""
    g = guide.astype(jnp.float32)
    p = src.astype(jnp.float32)
    mean_g = box_filter_2d(g, radius)
    mean_p = box_filter_2d(p, radius)
    corr_gp = box_filter_2d(g * p, radius)
    corr_gg = box_filter_2d(g * g, radius)
    var_g = corr_gg - mean_g * mean_g
    cov_gp = corr_gp - mean_g * mean_p
    a = cov_gp / (var_g + eps)
    b = mean_p - a * mean_g
    mean_a = box_filter_2d(a, radius)
    mean_b = box_filter_2d(b, radius)
    return (mean_a * g + mean_b).astype(src.dtype)


# ---------------------------------------------------------------------------
# Atmospheric light estimation (paper Eq. 5/6, robust top-k form)
# ---------------------------------------------------------------------------

def first_min_index(t: jnp.ndarray) -> jnp.ndarray:
    """(..., H, W) -> (...,) int32: the row-major flat index of each
    plane's first minimum, Eq. 6's pixel.

    One argmin reduction, ties at the lowest flat index: the pixel
    ``lax.top_k(-t, 1)`` picks, without sorting the plane.
    """
    flat = t.reshape(*t.shape[:-2], -1)
    return jnp.argmin(flat, axis=-1).astype(jnp.int32)


def atmospheric_light(img: jnp.ndarray, t_raw: jnp.ndarray, k: int = 1) -> jnp.ndarray:
    """A = mean of I over the k pixels with smallest raw transmission.

    k=1 reproduces paper Eq. 6 exactly (the argmin-t pixel) as a
    first-index argmin reduction (:func:`first_min_index`). Larger k is
    the standard robustification (top 0.1 %), a ``lax.top_k`` selection;
    both break ties at the lowest flat index. ``img``: (..., H, W, 3),
    ``t_raw``: (..., H, W) -> (..., 3).
    """
    flat_i = img.reshape(*img.shape[:-3], -1, 3)
    if k == 1:
        j = first_min_index(t_raw)
        return jnp.take_along_axis(flat_i, j[..., None, None], axis=-2)[..., 0, :]
    flat_t = t_raw.reshape(*t_raw.shape[:-2], -1)
    _, idx = lax.top_k(-flat_t, k)                      # smallest t
    picked = jnp.take_along_axis(flat_i, idx[..., None], axis=-2)
    return picked.mean(axis=-2)


def smallest_t_candidates(t_raw: jnp.ndarray, x: jnp.ndarray, k: int):
    """Per-frame k smallest-t candidates of a (B, H, W) block: ``(tk_t
    (B, k), tk_rgb (B, k, 3), tk_idx (B, k) int32)`` in ascending (t, flat
    index) order, the selection :func:`atmospheric_light` makes. ``k == 1``
    is :func:`first_min_index`; ``k > 1`` is ``lax.top_k``."""
    b = t_raw.shape[0]
    flat_t = t_raw.reshape(b, -1)
    if k == 1:
        idx = first_min_index(t_raw)[:, None]
    else:
        _, idx = lax.top_k(-flat_t, k)             # k smallest, ties by idx
    tk_t = jnp.take_along_axis(flat_t, idx, axis=-1)
    tk_rgb = jnp.take_along_axis(x.reshape(b, -1, 3), idx[..., None], axis=1)
    return tk_t, tk_rgb, idx.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Fused haze-free recovery (paper Eq. 8)
# ---------------------------------------------------------------------------

def recover(hazy: jnp.ndarray, t: jnp.ndarray, A: jnp.ndarray,
            t0: float = 0.1) -> jnp.ndarray:
    """J = clip((I - A)/max(t, t0) + A, 0, 1). A: (..., 3)."""
    tt = jnp.maximum(t, t0)[..., None]
    A = jnp.broadcast_to(A[..., None, None, :], hazy.shape)
    return jnp.clip((hazy - A) / tt + A, 0.0, 1.0).astype(hazy.dtype)


# ---------------------------------------------------------------------------
# CAP depth map (Zhu et al. [23], paper Eq. 4)
# ---------------------------------------------------------------------------

# Published CAP linear-model coefficients (w0, w1, w2) — the single source
# shared by the fused kernels; ``DehazeConfig`` defaults mirror these.
CAP_COEFFS = (0.121779, 0.959710, -0.780245)


def cap_depth(img: jnp.ndarray, w0: float, w1: float, w2: float) -> jnp.ndarray:
    """d(x) = w0 + w1 * value(x) + w2 * saturation(x) from RGB in [0,1]."""
    v = jnp.max(img, axis=-1)
    mn = jnp.min(img, axis=-1)
    s = jnp.where(v > 0, (v - mn) / jnp.maximum(v, 1e-12), 0.0)
    return (w0 + w1 * v + w2 * s).astype(img.dtype)


# ---------------------------------------------------------------------------
# Fused megakernel oracles (paper Eq. 3/4 + 6 + 9 + 8 in one logical op)
# ---------------------------------------------------------------------------

# Rec.601 luma — THE guided-filter guide definition. The fused kernel, the
# per-stage chain (core.algorithms.luminance) and the benchmarks all share
# these weights; parity between them is asserted to 1e-5 in CI.
LUMA_WEIGHTS = (0.299, 0.587, 0.114)


def luminance(img: jnp.ndarray) -> jnp.ndarray:
    """Rec.601 luma in float32 — the guided-filter guide of the fused op."""
    w = jnp.asarray(LUMA_WEIGHTS, jnp.float32)
    return img.astype(jnp.float32) @ w


def premap(x: jnp.ndarray, a0: jnp.ndarray, algorithm: str,
           cap_w=CAP_COEFFS) -> jnp.ndarray:
    """Per-pixel stage-1 map: DCP min_c I/A (Eq. 3) or CAP depth (Eq. 4).

    THE canonical pre-map: the fused kernels, the oracles, and the sharded
    pipeline (which computes it before the halo exchange) all route here,
    so the in-kernel and out-of-kernel forms stay bit-identical.
    """
    if algorithm == "dcp":
        return jnp.min(x / a0, axis=-1)
    return cap_depth(x, *cap_w)


def tmap_from_dark(dark: jnp.ndarray, algorithm: str, omega: float,
                   beta: float) -> jnp.ndarray:
    """Min-filtered pre-map -> raw transmission: DCP ``1 - omega*dark``
    (Eq. 3 outer map) or CAP ``exp(-beta*dark)`` (Eq. 4).

    Like ``premap``, this is THE canonical form — the fused kernels, the
    oracles, and the sharded staged chain all route here.
    """
    if algorithm == "dcp":
        return 1.0 - omega * dark
    return jnp.exp(-beta * dark)


def fused_transmission(img: jnp.ndarray, A_saved: jnp.ndarray, *,
                       algorithm: str = "dcp", radius: int,
                       omega: float = 0.95, beta: float = 1.0,
                       cap_w=CAP_COEFFS, refine: bool, gf_radius: int,
                       gf_eps: float, topk: int = 1, out_dtype=None):
    """Oracle for ``fused.fused_transmission_pallas``.

    (B,H,W,3) -> (t, t_min (B,), cand_rgb (B,3)): Eq. 3 (DCP) / Eq. 4 (CAP)
    transmission, guided-filter refinement, per-frame atmospheric-light
    candidate — the argmin-t pixel (Eq. 6) for ``topk == 1``, the mean of
    the ``topk`` smallest-t pixels (the robust Eq. 5/6 generalization,
    identical to :func:`atmospheric_light`) otherwise. ``img`` may be any
    wire dtype (f32/bf16/uint8 — see :func:`upcast_frames`); outputs are
    cast to :func:`resolve_out_dtype`.
    """
    odt = resolve_out_dtype(img.dtype, out_dtype)
    x = upcast_frames(img)
    a0 = jnp.maximum(A_saved.astype(jnp.float32), 1e-3)
    pre = premap(x, a0, algorithm, cap_w)
    dark = min_filter_2d(pre, radius)
    t_raw = tmap_from_dark(dark, algorithm, omega, beta)
    tk_t, tk_rgb, _ = smallest_t_candidates(t_raw, x, 1)
    t_min = tk_t[:, 0]
    cand = tk_rgb[:, 0] if topk == 1 else atmospheric_light(x, t_raw, topk)
    if refine:
        t = jnp.clip(guided_filter(luminance(x), t_raw, gf_radius, gf_eps),
                     0.0, 1.0)
    else:
        t = t_raw
    return t.astype(odt), t_min, cand.astype(odt)


def fused_transmission_dcp(img: jnp.ndarray, A_saved: jnp.ndarray, *,
                           radius: int, omega: float, refine: bool,
                           gf_radius: int, gf_eps: float):
    """Back-compat DCP-only entry point (PR 1 name)."""
    return fused_transmission(img, A_saved, algorithm="dcp", radius=radius,
                              omega=omega, refine=refine, gf_radius=gf_radius,
                              gf_eps=gf_eps)


def fused_transmission_halo(img: jnp.ndarray, pre_ext: jnp.ndarray,
                            guide_ext: jnp.ndarray, valid: jnp.ndarray,
                            valid_w: jnp.ndarray = None, *,
                            algorithm: str = "dcp", radius: int,
                            omega: float = 0.95, beta: float = 1.0,
                            refine: bool, gf_radius: int, gf_eps: float,
                            topk: int = 1, out_dtype=None):
    """Oracle for ``fused.fused_transmission_halo_pallas``.

    Composes the masked XLA filters from ``core.spatial`` on the
    halo-extended (pre-map, guide) planes — exactly the per-stage chain the
    spatially-sharded pipeline ran before the fused halo kernel existed.
    ``valid``/``valid_w`` are the row/column validity vectors from the halo
    exchange (``valid_w=None`` means all columns valid, i.e. no W
    sharding).

    Returns ``(t (B, H_loc, W_loc), tk_t (B, k), tk_rgb (B, k, 3),
    tk_idx (B, k) int32)``: the refined transmission plus the shard-local
    top-k smallest-t candidates over the core block, ascending in
    (t, local flat index) — ready for the cross-shard lexicographic merge
    in ``core.pipeline``. ``topk == 1`` is the Eq. 6 argmin candidate.
    ``img`` may be any wire dtype; ``pre_ext``/``guide_ext`` are the
    already-upcast halo planes (f32 or bf16 per ``halo_dtype``).
    """
    from repro.core import spatial                 # lazy: spatial imports ref
    odt = resolve_out_dtype(img.dtype, out_dtype)
    h_loc, w_loc = img.shape[1], img.shape[2]
    halo_h = (pre_ext.shape[1] - h_loc) // 2
    halo_w = (pre_ext.shape[2] - w_loc) // 2
    dark = spatial.masked_min_filter_2d(pre_ext.astype(jnp.float32), valid,
                                        radius, valid_w)
    t_raw_ext = tmap_from_dark(dark, algorithm, omega, beta)
    core_h = slice(halo_h, halo_h + h_loc)
    core_w = slice(halo_w, halo_w + w_loc)
    t_raw = t_raw_ext[:, core_h, core_w]
    if refine:
        t_ext = spatial.masked_guided_filter(guide_ext.astype(jnp.float32),
                                             t_raw_ext, valid, gf_radius,
                                             gf_eps, valid_w)
        t = jnp.clip(t_ext[:, core_h, core_w], 0.0, 1.0)
    else:
        t = t_raw
    tk_t, tk_rgb, idx = smallest_t_candidates(t_raw, upcast_frames(img),
                                              topk)
    return t.astype(odt), tk_t, tk_rgb.astype(odt), idx


def fused_dehaze(img: jnp.ndarray, frame_ids: jnp.ndarray,
                 A_saved: jnp.ndarray, last_update: jnp.ndarray,
                 initialized: jnp.ndarray, *, algorithm: str = "dcp",
                 radius: int, omega: float = 0.95, beta: float = 1.0,
                 cap_w=CAP_COEFFS, refine: bool, gf_radius: int,
                 gf_eps: float, t0: float, gamma: float, period: int,
                 lam: float, topk: int = 1, out_dtype=None):
    """Oracle for ``fused.fused_dehaze_pallas``: (J, t, a_seq, A_fin, k_fin).

    Composes the per-stage oracles plus the Eq. 9 EMA recurrence (lax.scan)
    — the sequential scan the megakernel realizes via its grid carry.
    ``topk > 1`` feeds the EMA the robust mean-of-top-k candidate. ``img``
    may be any wire dtype (the canonical :func:`upcast_frames` ingest);
    J/t are cast to :func:`resolve_out_dtype`, a_seq stays f32.
    """
    odt = resolve_out_dtype(img.dtype, out_dtype)
    x = upcast_frames(img)
    t, _, cand = fused_transmission(
        x, A_saved, algorithm=algorithm, radius=radius, omega=omega,
        beta=beta, cap_w=cap_w, refine=refine, gf_radius=gf_radius,
        gf_eps=gf_eps, topk=topk)

    def step(carry, inp):
        A_prev, k, inited = carry
        c, fid = inp
        valid = fid >= 0                  # ids < 0 are padding: no update
        bootstrap = jnp.logical_and(valid, jnp.logical_not(inited))
        do = jnp.logical_and(valid, jnp.logical_or(
            bootstrap, (fid - k) >= period))
        target = jnp.where(bootstrap, c, lam * c + (1.0 - lam) * A_prev)
        A = jnp.where(do, target, A_prev)
        k_next = jnp.where(do, fid, k)
        return (A, k_next, jnp.logical_or(inited, valid)), A

    (A_fin, k_fin, _), a_seq = lax.scan(
        step,
        (A_saved.astype(jnp.float32), last_update.astype(jnp.int32),
         initialized.astype(bool)),
        (cand.astype(jnp.float32), frame_ids.astype(jnp.int32)))
    tt = jnp.maximum(t.astype(jnp.float32), t0)[..., None]
    A_b = a_seq[:, None, None, :]
    J = jnp.clip((x - A_b) / tt + A_b, 0.0, 1.0)
    if gamma != 1.0:
        J = J ** gamma
    return (J.astype(odt), t.astype(odt), a_seq,
            A_fin, k_fin.astype(jnp.int32))


def fused_dehaze_dcp(img: jnp.ndarray, frame_ids: jnp.ndarray,
                     A_saved: jnp.ndarray, last_update: jnp.ndarray,
                     initialized: jnp.ndarray, *, radius: int, omega: float,
                     refine: bool, gf_radius: int, gf_eps: float, t0: float,
                     gamma: float, period: int, lam: float):
    """Back-compat DCP-only entry point (PR 1 name)."""
    return fused_dehaze(img, frame_ids, A_saved, last_update, initialized,
                        algorithm="dcp", radius=radius, omega=omega,
                        refine=refine, gf_radius=gf_radius, gf_eps=gf_eps,
                        t0=t0, gamma=gamma, period=period, lam=lam)
