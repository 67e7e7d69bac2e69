"""Jitted dispatch wrappers for the dehazing kernels.

Every op has three execution paths selected by ``mode``:
  - ``"ref"``      : pure-jnp oracle (XLA; the default on every backend)
  - ``"pallas"``   : compiled Pallas TPU kernel (opt-in; does not lower
                     for v5e yet, see ``resolve_mode``)
  - ``"interpret"``: Pallas kernel body interpreted on CPU (tests)

``"fused"`` is a fourth, *pipeline-level* mode: instead of one launch per
stage, the whole DCP/CAP chain runs as the single-pass megakernel in
``kernels.fused`` (see ``fused_dehaze`` below). Its execution substrate
is still resolved to ref/pallas/interpret per backend/env, so the fused
path also runs on the CPU CI container.

Core code calls these and never touches pallas_call directly, so the same
pipeline runs on the CPU CI container and on a real pod unchanged.
"""
from __future__ import annotations

import functools
from typing import Literal, Tuple

import jax
import jax.numpy as jnp

from repro.core import env as _env
from repro.kernels import ref as _ref
from repro.kernels import tuning
from repro.kernels.dark_channel import dark_channel_pallas, min_filter_2d_pallas
from repro.kernels.boxfilter import box_filter_2d_pallas
from repro.kernels.recover import recover_pallas
from repro.kernels.atmolight import (atmolight_pallas, atmolight_topk_pallas,
                                     merge_topk_pallas)
from repro.kernels.fused import (fused_dehaze_lanes_pallas,
                                 fused_dehaze_pallas,
                                 fused_transmission_halo_pallas,
                                 fused_transmission_lanes_pallas,
                                 fused_transmission_pallas)
from repro.kernels.ref import CAP_COEFFS

Mode = Literal["auto", "ref", "pallas", "interpret", "fused"]

SUBSTRATES = _env.SUBSTRATES
MODES = _env.KERNEL_MODES


def resolve_mode(mode: Mode = "auto") -> str:
    """Resolve to an execution substrate: ref | pallas | interpret.

    ``"auto"`` resolves to env ``REPRO_KERNEL_MODE`` if set, else to the
    XLA substrate (``"ref"``) on every backend, TPU included. The Pallas
    kernels do not lower for v5e yet: an ahead-of-time compile for a
    described ``v5e:2x2`` at 8 x 1080 x 1920 uint8 refuses the
    ``_atmolight_kernel`` output block and the frame-id block of
    ``_fused_dehaze_dbuf_kernel`` (both break the (8, 128) block rule),
    while the XLA step compiles. ROADMAP Speed item 2 lists every refusal
    and the layout work that would fix them.

    ``"fused"`` is a pipeline-level mode (it selects *which* ops run, not
    *how*); its substrate is env ``REPRO_KERNEL_MODE`` if it names one,
    else the compiled Pallas megakernel on TPU and the XLA oracle
    elsewhere. ``"pallas"`` and ``"fused"`` are explicit opt-ins: on a TPU
    they run the Pallas kernels and fail at lowering, never swapped for
    the XLA path in silence.

    Unknown values — in the argument or in ``REPRO_KERNEL_MODE`` — raise
    ``ValueError`` (validation lives in ``core.env.kernel_mode``). They
    used to fall straight through every dispatch wrapper's ``m == "ref"``
    check into the compiled-Pallas branch, so a typo like
    ``REPRO_KERNEL_MODE=Pallas`` silently ran compiled kernels.
    """
    if mode not in MODES:
        raise ValueError(
            f"unknown kernel mode {mode!r}; expected one of {sorted(MODES)}")
    env = _env.kernel_mode()
    if env == "auto":                    # explicit "auto" == unset
        env = ""
    m = mode
    if m == "auto":
        m = env or "ref"
    if m == "fused":
        m = env if env in SUBSTRATES else (
            "pallas" if jax.default_backend() == "tpu" else "ref")
    return m


# Alias used by the fused ops, where the distinction matters for readers.
resolve_substrate = resolve_mode


def _batched(x: jnp.ndarray, rank: int):
    """Collapse leading dims so kernels always see (B, ...)."""
    lead = x.shape[: x.ndim - rank]
    flat = x.reshape((-1,) + x.shape[x.ndim - rank:])
    return flat, lead


def dark_channel(img: jnp.ndarray, radius: int, mode: Mode = "auto") -> jnp.ndarray:
    """(..., H, W, 3) -> (..., H, W)."""
    m = resolve_mode(mode)
    if m == "ref":
        return _ref.dark_channel(img, radius)
    flat, lead = _batched(img, 3)
    out = dark_channel_pallas(flat, radius, interpret=(m == "interpret"))
    return out.reshape(lead + out.shape[1:])


def min_filter_2d(x: jnp.ndarray, radius: int, mode: Mode = "auto") -> jnp.ndarray:
    """(..., H, W) -> (..., H, W)."""
    m = resolve_mode(mode)
    if m == "ref":
        return _ref.min_filter_2d(x, radius)
    flat, lead = _batched(x, 2)
    out = min_filter_2d_pallas(flat, radius, interpret=(m == "interpret"))
    return out.reshape(lead + out.shape[1:])


def box_filter_2d(x: jnp.ndarray, radius: int, mode: Mode = "auto") -> jnp.ndarray:
    """(..., H, W) -> (..., H, W)."""
    m = resolve_mode(mode)
    if m == "ref":
        return _ref.box_filter_2d(x, radius)
    flat, lead = _batched(x, 2)
    out = box_filter_2d_pallas(flat, radius, interpret=(m == "interpret"))
    return out.reshape(lead + out.shape[1:])


def masked_min_filter_2d(x: jnp.ndarray, valid: jnp.ndarray, radius: int,
                         valid_w: jnp.ndarray = None,
                         mode: Mode = "auto") -> jnp.ndarray:
    """(..., H, W) with (H,) row-validity (and optional (W,) column
    validity, the W-sharded halo path) — the halo-exchange filter."""
    m = resolve_mode(mode)
    if m == "ref":
        from repro.core import spatial
        return spatial.masked_min_filter_2d(x, valid, radius, valid_w)
    from repro.kernels.dark_channel import masked_min_filter_2d_pallas
    flat, lead = _batched(x, 2)
    out = masked_min_filter_2d_pallas(flat, valid, radius, valid_w,
                                      interpret=(m == "interpret"))
    return out.reshape(lead + out.shape[1:])


def masked_box_filter_2d(x: jnp.ndarray, valid: jnp.ndarray, radius: int,
                         valid_w: jnp.ndarray = None,
                         mode: Mode = "auto") -> jnp.ndarray:
    m = resolve_mode(mode)
    if m == "ref":
        from repro.core import spatial
        return spatial.masked_box_filter_2d(x, valid, radius, valid_w)
    from repro.kernels.boxfilter import masked_box_filter_2d_pallas
    flat, lead = _batched(x, 2)
    out = masked_box_filter_2d_pallas(flat, valid, radius, valid_w,
                                      interpret=(m == "interpret"))
    return out.reshape(lead + out.shape[1:])


def guided_filter(guide: jnp.ndarray, src: jnp.ndarray, radius: int, eps: float,
                  mode: Mode = "auto") -> jnp.ndarray:
    """Guided filter composed from the box-filter op (5 box passes)."""
    m = resolve_mode(mode)
    if m == "ref":
        return _ref.guided_filter(guide, src, radius, eps)
    g = guide.astype(jnp.float32)
    p = src.astype(jnp.float32)
    bf = functools.partial(box_filter_2d, radius=radius, mode=m)
    mean_g = bf(g)
    mean_p = bf(p)
    corr_gp = bf(g * p)
    corr_gg = bf(g * g)
    var_g = corr_gg - mean_g * mean_g
    cov_gp = corr_gp - mean_g * mean_p
    a = cov_gp / (var_g + eps)
    b = mean_p - a * mean_g
    return (bf(a) * g + bf(b)).astype(src.dtype)


def atmospheric_light(img: jnp.ndarray, t_raw: jnp.ndarray, k: int = 1,
                      mode: Mode = "auto") -> jnp.ndarray:
    """(..., H, W, 3), (..., H, W) -> (..., 3).

    k=1 is the Eq. 6 argmin-t pixel; k>1 the robust mean-of-top-k. On the
    XLA substrate (``ref``, what ``auto`` resolves to) k=1 is a first-index
    argmin reduction and k>1 a ``lax.top_k``; the Pallas kernels are
    ``atmolight_pallas`` and ``atmolight_topk_pallas`` (an in-VMEM k-row
    running selection). All break ties at the lowest flat index, as
    ``kernels.ref.atmospheric_light`` does.
    """
    m = resolve_mode(mode)
    if m == "ref":
        return _ref.atmospheric_light(img, t_raw, k)
    flat_i, lead = _batched(img, 3)
    flat_t, _ = _batched(t_raw, 2)
    if k > 1:
        tile_h = int(tuning.get_params(
            "atmolight_topk", flat_t.shape).get("tile_h", 0))
        out = atmolight_topk_pallas(flat_i, flat_t, k, tile_h=tile_h,
                                    interpret=(m == "interpret"))
    else:
        tile_h = int(tuning.get_params(
            "atmolight", flat_t.shape).get("tile_h", 0))
        out = atmolight_pallas(flat_i, flat_t, tile_h=tile_h,
                               interpret=(m == "interpret"))
    return out.reshape(lead + (3,))


def merge_topk_candidates(tk_t: jnp.ndarray, tk_idx: jnp.ndarray,
                          tk_rgb: jnp.ndarray, k: int,
                          mode: Mode = "auto") -> jnp.ndarray:
    """(B, M) t + global-index lists, (B, M, 3) rgb -> (B, 3) mean of the
    k lexicographically smallest (t, index) rows.

    The sharded pipeline's cross-shard candidate merge: after the
    all-gather, M = n_shards * k rows per frame. ``ref`` is the two-key
    ``lax.sort`` (t, then global flat index — reproducing ``lax.top_k``'s
    lowest-index tie-break across shard boundaries); pallas/interpret fold
    the list through a sequential grid carry (``merge_topk_pallas``) in
    k-row segments, bit-identical by the shared tie-break rule.
    """
    tk_t = tk_t.astype(jnp.float32)
    tk_rgb = tk_rgb.astype(jnp.float32)
    m = resolve_mode(mode)
    if m == "ref":
        _, _, r_s, g_s, b_s = jax.lax.sort(
            (tk_t, tk_idx, tk_rgb[..., 0], tk_rgb[..., 1], tk_rgb[..., 2]),
            dimension=1, num_keys=2)
        top = jnp.stack([r_s[:, :k], g_s[:, :k], b_s[:, :k]], axis=-1)
        return top.mean(axis=1)
    return merge_topk_pallas(tk_t, tk_idx, tk_rgb, k,
                             interpret=(m == "interpret"))


def recover(img: jnp.ndarray, t: jnp.ndarray, A: jnp.ndarray, t0: float = 0.1,
            gamma: float = 1.0, mode: Mode = "auto") -> jnp.ndarray:
    """(..., H, W, 3), (..., H, W), (..., 3) -> (..., H, W, 3)."""
    m = resolve_mode(mode)
    if m == "ref":
        out = _ref.recover(img, t, A, t0)
        return out ** gamma if gamma != 1.0 else out
    flat_i, lead = _batched(img, 3)
    flat_t, _ = _batched(t, 2)
    flat_a = A.reshape(-1, 3)
    out = recover_pallas(flat_i, flat_t, flat_a, t0=t0, gamma=gamma,
                         interpret=(m == "interpret"))
    return out.reshape(lead + out.shape[1:])


def cap_depth(img: jnp.ndarray, w0: float, w1: float, w2: float) -> jnp.ndarray:
    """CAP linear depth model — pure elementwise, XLA fuses it optimally."""
    return _ref.cap_depth(img, w0, w1, w2)


# ---------------------------------------------------------------------------
# Fused single-pass megakernels (kernels.fused) — algorithm-parametric
# ---------------------------------------------------------------------------

def fused_dehaze(img: jnp.ndarray, frame_ids: jnp.ndarray,
                 A_saved: jnp.ndarray, last_update: jnp.ndarray,
                 initialized: jnp.ndarray, *, algorithm: str = "dcp",
                 radius: int, omega: float = 0.95, beta: float = 1.0,
                 cap_w: Tuple[float, float, float] = CAP_COEFFS,
                 refine: bool, gf_radius: int, gf_eps: float, t0: float,
                 gamma: float, period: int, lam: float, topk: int = 1,
                 frames_per_block: int = 0, out_dtype: str = "auto",
                 buffer_depth: int = 0,
                 mode: Mode = "auto") -> Tuple[jnp.ndarray, ...]:
    """Whole DCP/CAP chain in one launch: (..., H, W, 3) -> (J, t, a_seq, A, k).

    ``topk`` selects the atmospheric-light candidate estimator: 1 is the
    Eq. 6 argmin-t pixel, >1 the robust in-VMEM mean-of-top-k.
    ``frames_per_block <= 0`` resolves the tile from the tuning registry's
    per-algorithm bucket (env ``REPRO_TUNE_FUSED_DCP`` /
    ``REPRO_TUNE_FUSED_CAP`` > the *current device kind's* measured entry
    in ``results/kernel_tuning.json`` > legacy device-untagged entry > 1 —
    see ``kernels.tuning.get_params``); the top-k selection changes the
    kernel's VMEM/compute profile, so ``topk > 1`` resolves from its own
    ``fused_<algorithm>_topk`` bucket.

    ``img`` may be any wire dtype (f32/bf16/uint8 — the canonical
    ``ref.upcast_frames`` ingest; non-f32 streams resolve dtype-tagged
    tuning buckets). ``out_dtype`` picks the J/t output dtype ("auto":
    follow float ingest, f32 for uint8). ``buffer_depth <= 0`` resolves
    the double-buffered DMA ring depth from the bucket; the interpret
    substrate falls back to the classic single-buffered body (depth 1)
    unless an explicit depth is requested — that is the interpret-safe
    fallback, while tests pass ``buffer_depth >= 2`` to execute the
    manual-DMA body itself under interpret.
    """
    m = resolve_substrate(mode)
    flat, lead = _batched(img, 3)
    flat_ids = frame_ids.reshape(-1)
    if m == "ref":
        j, t, a_seq, a_fin, k_fin = _ref.fused_dehaze(
            flat, flat_ids, A_saved, last_update, initialized,
            algorithm=algorithm, radius=radius, omega=omega, beta=beta,
            cap_w=cap_w, refine=refine, gf_radius=gf_radius, gf_eps=gf_eps,
            t0=t0, gamma=gamma, period=period, lam=lam, topk=topk,
            out_dtype=out_dtype)
    else:
        op = f"fused_{algorithm}" + ("_topk" if topk > 1 else "")
        params = tuning.get_params(op, flat.shape[:3], dtype=flat.dtype)
        if frames_per_block <= 0:
            frames_per_block = int(params.get("frames_per_block", 1))
        if buffer_depth <= 0:
            buffer_depth = 1 if m == "interpret" \
                else int(params.get("buffer_depth", 1))
        j, t, a_seq, a_fin, k_fin = fused_dehaze_pallas(
            flat, flat_ids, A_saved, last_update, initialized,
            algorithm=algorithm, radius=radius, omega=omega, beta=beta,
            cap_w=tuple(cap_w), refine=refine, gf_radius=gf_radius,
            gf_eps=gf_eps, t0=t0, gamma=gamma, period=period, lam=lam,
            topk=topk, frames_per_block=frames_per_block,
            out_dtype=out_dtype, buffer_depth=buffer_depth,
            interpret=(m == "interpret"))
    return (j.reshape(lead + j.shape[1:]), t.reshape(lead + t.shape[1:]),
            a_seq.reshape(lead + (3,)), a_fin, k_fin)


def fused_dehaze_lanes(img: jnp.ndarray, frame_ids: jnp.ndarray,
                       carry_f: jnp.ndarray, carry_i: jnp.ndarray, *,
                       algorithm: str = "dcp", radius: int,
                       omega: float = 0.95, beta: float = 1.0,
                       cap_w: Tuple[float, float, float] = CAP_COEFFS,
                       refine: bool, gf_radius: int, gf_eps: float, t0: float,
                       gamma: float, period: int, lam: float, topk: int = 1,
                       frames_per_block: int = 0, lane_major=None,
                       out_dtype: str = "auto", buffer_depth: int = 0,
                       mode: Mode = "auto") -> Tuple[jnp.ndarray, ...]:
    """Lane-native fused dehaze: L streams, one launch.

    img: (L, B, H, W, 3); frame_ids: (L, B); carry_f (L, 3) f32 /
    carry_i (L, 2) int32 are the lane-packed EMA carry rows
    (``core.normalize.lane_carry``). Returns ``(J, t, a_seq (L, B, 3),
    carry_f', carry_i')`` — per lane identical to :func:`fused_dehaze` on
    that lane alone, padding lanes (all ids < 0) untouched.

    ``frames_per_block <= 0`` and ``lane_major=None`` resolve from the
    ``fused_lanes`` tuning bucket (env ``REPRO_TUNE_FUSED_LANES`` >
    device-kind-keyed measured table > lane-major, 1 frame per block —
    run ``python -m repro.kernels.tuning --search`` on the serving pod to
    bake real measurements); the bucket's shape
    key includes the lane count, so the lane-major-vs-frame-major grid
    order and the ``frames_per_block`` x L tile sweep are tuned per
    serving shape. ``out_dtype``/``buffer_depth`` follow the
    :func:`fused_dehaze` dtype/DMA contract (non-f32 wire dtypes resolve
    dtype-tagged buckets; interpret falls back to depth 1 unless an
    explicit depth is passed).
    """
    assert img.ndim == 5, img.shape
    n_lanes, b = img.shape[0], img.shape[1]
    assert frame_ids.shape == (n_lanes, b), frame_ids.shape
    m = resolve_substrate(mode)
    if m == "ref":
        def one_lane(im, ids, cf, ci):
            j, t, a_seq, a_fin, k_fin = _ref.fused_dehaze(
                im, ids, cf, ci[0], ci[1].astype(bool), algorithm=algorithm,
                radius=radius, omega=omega, beta=beta, cap_w=cap_w,
                refine=refine, gf_radius=gf_radius, gf_eps=gf_eps, t0=t0,
                gamma=gamma, period=period, lam=lam, topk=topk,
                out_dtype=out_dtype)
            inited = jnp.maximum(ci[1], jnp.any(ids >= 0).astype(ci.dtype))
            return j, t, a_seq, a_fin, jnp.stack([k_fin, inited])
        return jax.vmap(one_lane)(img, frame_ids, carry_f, carry_i)
    params = tuning.get_params("fused_lanes", img.shape[:4], dtype=img.dtype)
    if frames_per_block <= 0:
        frames_per_block = int(params.get("frames_per_block", 1))
    if lane_major is None:
        lane_major = str(params.get("grid_order", "lane_major")) \
            != "frame_major"
    if buffer_depth <= 0:
        buffer_depth = 1 if m == "interpret" \
            else int(params.get("buffer_depth", 1))
    return fused_dehaze_lanes_pallas(
        img, frame_ids, carry_f, carry_i, algorithm=algorithm, radius=radius,
        omega=omega, beta=beta, cap_w=tuple(cap_w), refine=refine,
        gf_radius=gf_radius, gf_eps=gf_eps, t0=t0, gamma=gamma, period=period,
        lam=lam, topk=topk, frames_per_block=frames_per_block,
        lane_major=bool(lane_major), out_dtype=out_dtype,
        buffer_depth=buffer_depth, interpret=(m == "interpret"))


def fused_transmission(img: jnp.ndarray, A_saved: jnp.ndarray, *,
                       algorithm: str = "dcp", radius: int,
                       omega: float = 0.95, beta: float = 1.0,
                       cap_w: Tuple[float, float, float] = CAP_COEFFS,
                       refine: bool, gf_radius: int, gf_eps: float,
                       topk: int = 1, out_dtype: str = "auto",
                       mode: Mode = "auto") -> Tuple[jnp.ndarray, ...]:
    """Fused t-map + A candidates (the batch-sharded-step stage):
    (..., H, W, 3) -> (t, t_min (...,), cand_rgb (..., 3)). The candidate
    is the argmin-t pixel for ``topk == 1``, the mean of the ``topk``
    smallest-t pixels otherwise (each frame is whole on its shard, so the
    mean needs no cross-shard merge). ``img`` may be any wire dtype; t and
    the candidate RGB are cast per ``out_dtype`` (see
    :func:`fused_dehaze`)."""
    m = resolve_substrate(mode)
    flat, lead = _batched(img, 3)
    if m == "ref":
        t, t_min, cand = _ref.fused_transmission(
            flat, A_saved, algorithm=algorithm, radius=radius, omega=omega,
            beta=beta, cap_w=cap_w, refine=refine, gf_radius=gf_radius,
            gf_eps=gf_eps, topk=topk, out_dtype=out_dtype)
    else:
        t, t_min, cand = fused_transmission_pallas(
            flat, A_saved, algorithm=algorithm, radius=radius, omega=omega,
            beta=beta, cap_w=tuple(cap_w), refine=refine, gf_radius=gf_radius,
            gf_eps=gf_eps, topk=topk, out_dtype=out_dtype,
            interpret=(m == "interpret"))
    return (t.reshape(lead + t.shape[1:]), t_min.reshape(lead),
            cand.reshape(lead + (3,)))


def fused_transmission_lanes(img: jnp.ndarray, A_saved: jnp.ndarray, *,
                             algorithm: str = "dcp", radius: int,
                             omega: float = 0.95, beta: float = 1.0,
                             cap_w: Tuple[float, float, float] = CAP_COEFFS,
                             refine: bool, gf_radius: int, gf_eps: float,
                             topk: int = 1, out_dtype: str = "auto",
                             mode: Mode = "auto") -> Tuple[jnp.ndarray, ...]:
    """Lane-native fused t-map stage: (L, B, H, W, 3) + per-lane saved A
    (L, 3) -> (t (L, B, H, W), t_min (L, B), cand_rgb (L, B, 3)).

    The lane-batched form of :func:`fused_transmission` — each lane's DCP
    pre-map divides by its own coherent A, and all L lanes ride one
    launch. The stage is stateless across frames, so there is no carry to
    fold; the per-lane A input is what distinguishes this from reshaping
    the lane axis into the batch."""
    assert img.ndim == 5, img.shape
    n_lanes = img.shape[0]
    assert A_saved.shape == (n_lanes, 3), A_saved.shape
    m = resolve_substrate(mode)
    if m == "ref":
        def one_lane(im, a):
            return _ref.fused_transmission(
                im, a, algorithm=algorithm, radius=radius, omega=omega,
                beta=beta, cap_w=cap_w, refine=refine, gf_radius=gf_radius,
                gf_eps=gf_eps, topk=topk, out_dtype=out_dtype)
        return jax.vmap(one_lane)(img, A_saved)
    return fused_transmission_lanes_pallas(
        img, A_saved, algorithm=algorithm, radius=radius, omega=omega,
        beta=beta, cap_w=tuple(cap_w), refine=refine, gf_radius=gf_radius,
        gf_eps=gf_eps, topk=topk, out_dtype=out_dtype,
        interpret=(m == "interpret"))


def fused_transmission_halo(img: jnp.ndarray, pre_ext: jnp.ndarray,
                            guide_ext: jnp.ndarray, valid: jnp.ndarray,
                            valid_w: jnp.ndarray = None, *,
                            algorithm: str = "dcp", radius: int,
                            omega: float = 0.95, beta: float = 1.0,
                            refine: bool, gf_radius: int, gf_eps: float,
                            topk: int = 1, frames_per_block: int = 0,
                            out_dtype: str = "auto", buffer_depth: int = 0,
                            mode: Mode = "auto") -> Tuple[jnp.ndarray, ...]:
    """Halo-aware fused t-map stage for the spatially-sharded pipeline.

    img: (..., H_loc, W_loc, 3) core block; pre_ext/guide_ext:
    (..., H_ext, W_ext) halo-extended planes from the ``core.spatial`` halo
    exchanges; valid: (H_ext,) row-validity mask; valid_w: optional (W_ext,)
    column-validity mask (None = no W sharding). Returns ``(t, tk_t
    (..., k), tk_rgb (..., k, 3), tk_idx (..., k))`` — the shard-local
    top-k smallest-t candidates ascending in (t, local flat index), ready
    for the cross-shard lexicographic merge in ``core.pipeline``. The
    masked min/box filters run in-VMEM on the Pallas substrates and through
    ``core.spatial`` on the XLA oracle. ``frames_per_block <= 0`` and
    ``buffer_depth <= 0`` resolve from the ``fused_halo_2d`` tuning bucket
    (Pallas substrates only; the resolved buffer depth is clamped to 1 on
    the interpret substrate, where manual DMA brings no overlap — pass an
    explicit ``buffer_depth >= 2`` to force the double-buffered body).
    ``img`` may be uint8/bfloat16 wire frames (upcast in-VMEM); t/tk_rgb
    are cast per ``out_dtype``.
    """
    m = resolve_substrate(mode)
    flat, lead = _batched(img, 3)
    flat_pre, _ = _batched(pre_ext, 2)
    flat_guide, _ = _batched(guide_ext, 2)
    if m == "ref":
        t, tk_t, tk_rgb, tk_idx = _ref.fused_transmission_halo(
            flat, flat_pre, flat_guide, valid, valid_w, algorithm=algorithm,
            radius=radius, omega=omega, beta=beta, refine=refine,
            gf_radius=gf_radius, gf_eps=gf_eps, topk=topk,
            out_dtype=out_dtype)
    else:
        params = tuning.get_params("fused_halo_2d", flat.shape[:3],
                                   dtype=flat.dtype)
        if frames_per_block <= 0:
            frames_per_block = int(params.get("frames_per_block", 1))
        if buffer_depth <= 0:
            buffer_depth = 1 if m == "interpret" \
                else int(params.get("buffer_depth", 1))
        t, tk_t, tk_rgb, tk_idx = fused_transmission_halo_pallas(
            flat, flat_pre, flat_guide, valid, valid_w, algorithm=algorithm,
            radius=radius, omega=omega, beta=beta, refine=refine,
            gf_radius=gf_radius, gf_eps=gf_eps, topk=topk,
            frames_per_block=frames_per_block, out_dtype=out_dtype,
            buffer_depth=buffer_depth, interpret=(m == "interpret"))
    return (t.reshape(lead + t.shape[1:]), tk_t.reshape(lead + (topk,)),
            tk_rgb.reshape(lead + (topk, 3)), tk_idx.reshape(lead + (topk,)))


def fused_dehaze_dcp(img: jnp.ndarray, frame_ids: jnp.ndarray,
                     A_saved: jnp.ndarray, last_update: jnp.ndarray,
                     initialized: jnp.ndarray, *, radius: int, omega: float,
                     refine: bool, gf_radius: int, gf_eps: float, t0: float,
                     gamma: float, period: int, lam: float,
                     frames_per_block: int = 0,
                     mode: Mode = "auto") -> Tuple[jnp.ndarray, ...]:
    """Back-compat DCP-only entry point (PR 1 name) -> ``fused_dehaze``."""
    return fused_dehaze(img, frame_ids, A_saved, last_update, initialized,
                        algorithm="dcp", radius=radius, omega=omega,
                        refine=refine, gf_radius=gf_radius, gf_eps=gf_eps,
                        t0=t0, gamma=gamma, period=period, lam=lam,
                        frames_per_block=frames_per_block, mode=mode)


def fused_transmission_dcp(img: jnp.ndarray, A_saved: jnp.ndarray, *,
                           radius: int, omega: float, refine: bool,
                           gf_radius: int, gf_eps: float,
                           mode: Mode = "auto") -> Tuple[jnp.ndarray, ...]:
    """Back-compat DCP-only entry point (PR 1 name) -> ``fused_transmission``."""
    return fused_transmission(img, A_saved, algorithm="dcp", radius=radius,
                              omega=omega, refine=refine,
                              gf_radius=gf_radius, gf_eps=gf_eps, mode=mode)


# ---------------------------------------------------------------------------
# Introspection: pallas_call launches in a traced program
# ---------------------------------------------------------------------------

def _iter_jaxprs(val):
    from jax import core
    if isinstance(val, core.Jaxpr):
        yield val
    elif isinstance(val, core.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _iter_jaxprs(v)


def _count_pallas(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            for sub in _iter_jaxprs(v):
                n += _count_pallas(sub)
    return n


def pallas_launch_count(fn, *args, **kwargs) -> int:
    """Number of ``pallas_call`` equations in ``fn``'s traced jaxpr
    (recursing into nested call/scan/cond jaxprs).

    This is the per-tick launch count the lane-native refactor optimizes:
    dispatching L streams through per-lane kernel calls traces L
    ``pallas_call``s, the lane-native kernel exactly one. Used by the
    ``kernels/fused_lanes_*`` bench rows and the launch-count regression
    test."""
    return _count_pallas(jax.make_jaxpr(fn)(*args, **kwargs).jaxpr)


def _count_prim(jaxpr, name: str) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            n += 1
        for v in eqn.params.values():
            for sub in _iter_jaxprs(v):
                n += _count_prim(sub, name)
    return n


def dma_copy_count(fn, *args, **kwargs) -> dict:
    """Count manual-DMA equations in ``fn``'s traced program, recursing
    into every nested jaxpr (including pallas_call kernel bodies).

    Returns ``{"starts": n, "waits": m}``. The double-buffered megakernel
    bodies trace two ``dma_start``s (warm-up + prefetch) and one
    ``dma_wait`` per input plane; the classic single-buffered bodies trace
    zero of each. Used by the ``kernels/fused_dbuf`` bench row and the
    overlap-structure regression test to assert the copy/compute overlap
    is actually in the lowered program, independent of wall-clock."""
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs).jaxpr
    return {"starts": _count_prim(jaxpr, "dma_start"),
            "waits": _count_prim(jaxpr, "dma_wait")}
