"""Dehazing step builders: the paper's component chain as jitted SPMD steps.

``make_step(cfg, placement)`` is THE step-construction path: a
:class:`~repro.core.placement.PlacementSpec` declares once how every axis
of the serving batch maps onto mesh axes, and the builder realizes it —
the plain batched step, the lane-batched multi-stream step, the
frame/spatially sharded production step, and (new) the *lane-sharded*
pod-scale step where the lane axis shards over the ``data`` mesh axis and
composes with H/W halo sharding. The three legacy builders
(``make_dehaze_step``, ``make_multi_stream_step``,
``make_sharded_dehaze_step``) are thin views of ``make_step`` and keep
their exact signatures and semantics.

The three paper components run back-to-back inside one compiled program:
on TPU the win from the paper's operator parallelism is realized across
*frames* (data axis), *rows* (model axis) and now *streams* (lane axis),
while component handoff is a register/VMEM boundary instead of an
Ethernet hop (DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import algorithms as alg
from repro.core import env as _env
from repro.core import spatial
from repro.core.config import DehazeConfig
from repro.core.normalize import (AtmoState, ema_scan, ema_scan_associative,
                                  ema_scan_lanes, init_atmo_state,
                                  init_atmo_state_lanes, pack_atmo_states,
                                  unpack_atmo_states)
from repro.core.placement import PlacementSpec
from repro.kernels import ref as kref


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DehazeOutput:
    frames: jnp.ndarray      # (B, H, W, 3) haze-free J
    transmission: jnp.ndarray  # (B, H, W) refined t
    atmo_light: jnp.ndarray    # (B, 3) per-frame normalized A
    state: AtmoState


def _ingest(frames: jnp.ndarray, cfg: DehazeConfig):
    """Resolve the frame I/O dtype contract for one step invocation.

    Returns ``(x, odt)``: ``x`` is the compute-dtype view of ``frames``
    (float ingest passes through untouched — bit-identical to the
    pre-contract pipeline; uint8 ingest upcasts via the canonical
    ``kernels.ref.upcast_frames`` quantization map) and ``odt`` is the
    resolved output dtype for J / t / A per ``cfg.out_dtype``. The fused
    megakernels never see ``x`` — they take the raw wire-dtype frames and
    upcast in-VMEM (that is the 4x input-HBM-traffic win); ``x`` feeds the
    staged XLA chain and the host-side epilogue stages.
    """
    odt = kref.resolve_out_dtype(frames.dtype, cfg.out_dtype)
    x = frames if jnp.issubdtype(frames.dtype, jnp.floating) \
        else kref.upcast_frames(frames)
    return x, odt


# ---------------------------------------------------------------------------
# Buffer donation contract
# ---------------------------------------------------------------------------

# Step argument positions (frames, frame_ids, state) — the donation
# argnums below index into this signature.
_ARG_FRAMES, _ARG_IDS, _ARG_STATE = 0, 1, 2


def donation_spec(cfg: DehazeConfig) -> Tuple[int, ...]:
    """The step arguments eligible for ``jax.jit`` buffer donation.

    The EMA state (argnum 2) is always donatable: ``out.state`` has the
    input state's exact shape/dtype, the serve loops thread it
    sequentially, and nothing else holds the old value once the next tick
    is dispatched — donating it makes steady-state serving allocate zero
    new HBM for the state chain.

    The frame batch (argnum 0) is donatable only when the wire dtype
    equals the resolved output dtype (f32-in/f32-out, bf16-in/bf16-out):
    XLA then aliases ``out.frames`` onto the input buffer. A uint8 stream
    can never alias (J is float), and ``out_dtype`` overrides that differ
    from ``io_dtype`` break the aliasing too — donating a buffer XLA
    cannot alias is legal but wasteful (the input is freed, a fresh output
    allocated), so we only offer arguments that actually alias.
    """
    cfg = cfg.validate()
    argnums = [_ARG_STATE]
    if kref.resolve_out_dtype(jnp.dtype(cfg.io_dtype), cfg.out_dtype) \
            == jnp.dtype(cfg.io_dtype):
        argnums.insert(0, _ARG_FRAMES)
    return tuple(argnums)


# ---------------------------------------------------------------------------
# The placement-driven entry point
# ---------------------------------------------------------------------------

def make_step(cfg: DehazeConfig, placement: Optional[PlacementSpec] = None,
              mesh: Optional[jax.sharding.Mesh] = None, *,
              associative: bool = True, lane_native: Optional[bool] = None,
              donate=False):
    """Build the dehaze step a :class:`PlacementSpec` declares.

    - no mesh axes, no lanes  -> ``step(frames (B,H,W,3), ids (B,), state)``
    - ``lanes`` (no mesh axes)-> lane-batched ``(L, B, H, W, 3)`` step
      (lane-native megakernel when the config is fused-covered);
    - ``batch_axes``/spatial  -> the shard_map production step (frames over
      the data axes, H/W halo-sharded, state synchronized by collectives);
    - ``lane_axis``           -> the pod-scale lane-sharded step: the lane
      axis shards over the mesh (each shard owns whole lanes, so per-lane
      EMA rows are co-placed and scan shard-locally), optionally composed
      with H/W halo sharding inside each shard.

    ``mesh`` is required iff the placement names mesh axes. ``lane_native``
    follows :func:`resolve_lane_native` when ``None``.

    ``donate`` is the buffer-donation contract (README §Tick I/O &
    overlap). ``False`` (default) returns the un-jitted step exactly as
    before (callers jit, typically through the serving step cache which
    keys on ``(cfg, placement)``). Donation is a property of the *jitted*
    call, so a non-``False`` value returns ``jax.jit(step,
    donate_argnums=...)``:

    - ``"state"`` — donate only the EMA state (argnum 2). This is the
      tick-step contract: the serve loop owns a long-lived device frame
      buffer that must survive the call, while the state chain is
      strictly sequential and its input is dead after dispatch.
    - ``True`` — donate everything :func:`donation_spec` allows (state
      always, frames when the wire dtype aliases the output dtype). This
      is the dispatcher contract: each batch's input buffer is
      single-use, so ``out.frames`` can alias it.

    Donation with a mesh-sharded placement is not offered (the serving
    tiers drive local lane batches; a sharded step's buffers belong to
    the launch tooling) and raises.
    """
    placement = (placement if placement is not None
                 else PlacementSpec()).validate()
    cfg = cfg.validate()
    if donate is not False and placement.sharded:
        raise ValueError(
            "donate= is a serving-tier contract for local batches; "
            f"mesh-sharded placement {placement} manages its own buffers")
    if placement.sharded:
        if mesh is None:
            raise ValueError(
                f"placement {placement} names mesh axes "
                f"{placement.mesh_axes}; make_step needs the mesh")
        return _make_sharded_step(cfg, mesh, placement,
                                  associative=associative,
                                  lane_native=lane_native)
    if placement.lanes:
        step = _make_lane_step(cfg, associative=associative,
                               lane_native=lane_native)
    else:
        step = _make_single_step(cfg, associative=associative)
    if donate is False:
        return step
    if donate == "state":
        argnums: Tuple[int, ...] = (_ARG_STATE,)
    elif donate is True:
        argnums = donation_spec(cfg)
    else:
        raise ValueError(
            f"donate must be False, True or 'state', got {donate!r}")
    return jax.jit(step, donate_argnums=argnums)


# ---------------------------------------------------------------------------
# Single-shard batched step
# ---------------------------------------------------------------------------

def _make_single_step(cfg: DehazeConfig, associative: bool = True):
    if cfg.kernel_mode == "fused" and alg.supports_fused(cfg):
        def fused_step(frames: jnp.ndarray, frame_ids: jnp.ndarray,
                       state: AtmoState) -> DehazeOutput:
            # Raw wire-dtype frames go straight into the megakernel (in-VMEM
            # upcast); the kernel's J dtype IS the resolved out dtype.
            out, t, a_seq, new_state = alg.fused_dehaze(
                frames, frame_ids, state, cfg)
            return DehazeOutput(out, t, a_seq.astype(out.dtype), new_state)
        return fused_step

    t_est = alg.get_transmission_estimator(cfg.algorithm)
    scan = ema_scan_associative if associative else ema_scan

    # Each component and stage runs under a ``jax.named_scope``: the scope
    # prefixes the HLO ``op_name`` metadata (and nothing else), so a
    # profiler trace can attribute device time per component.
    def step(frames: jnp.ndarray, frame_ids: jnp.ndarray,
             state: AtmoState) -> DehazeOutput:
        x, odt = _ingest(frames, cfg)
        # Component 1: transmission from the *saved* shared A (paper §3.3).
        with jax.named_scope("transmission"):
            t_raw = t_est(x, state.A, cfg)
        # Component 2: per-frame candidates, then cross-frame normalization.
        with jax.named_scope("atmospheric_light"):
            a_new = alg.estimate_atmospheric_light(x, t_raw, cfg)
        with jax.named_scope("normalize"):
            a_seq, new_state = scan(a_new, frame_ids, state,
                                    cfg.update_period, cfg.lam)
            a_seq = a_seq.astype(x.dtype)
        if cfg.recompute_t_with_final_a and cfg.algorithm == "dcp":
            with jax.named_scope("transmission"):
                t_raw = t_est(x, a_seq, cfg)
        with jax.named_scope("refine"):
            t = alg.refine_transmission(x, t_raw, cfg)
        # Component 3: haze-free generation.
        with jax.named_scope("recover"):
            out = alg.generate_haze_free(x, t, a_seq, cfg)
        return DehazeOutput(out.astype(odt), t.astype(odt),
                            a_seq.astype(odt), new_state)

    return step


def make_dehaze_step(cfg: DehazeConfig, associative: bool = True):
    """Returns step(frames (B,H,W,3), frame_ids (B,), state) -> DehazeOutput.

    Thin view of :func:`make_step` with the empty placement. With
    ``cfg.kernel_mode == "fused"`` (and a config the megakernel covers,
    see ``algorithms.supports_fused``) the whole component chain runs as
    one single-pass launch; otherwise the per-stage chain.
    """
    return make_step(cfg, PlacementSpec.single(), associative=associative)


# ---------------------------------------------------------------------------
# Multi-stream (lane-batched) step — N videos in one compiled program
# ---------------------------------------------------------------------------

def resolve_lane_native(cfg: DehazeConfig) -> bool:
    """Should the multi-stream step use the lane-native megakernel?

    Default: yes whenever the fused megakernel covers the config
    (``kernel_mode == "fused"`` and ``algorithms.supports_fused``) — the
    lane axis then folds into the pallas grid and L streams cost one
    launch. Env ``REPRO_LANE_NATIVE`` overrides: ``0`` forces the vmapped
    path (A/B benchmarking, bisection), ``1`` forces lane-native and
    *raises* if the config cannot take it — CI uses this to guarantee the
    smoke run exercised the lane-native path rather than silently falling
    back.
    """
    cfg = cfg.validate()
    fused_ok = cfg.kernel_mode == "fused" and alg.supports_fused(cfg)
    forced = _env.lane_native()             # validated; raises on junk
    if forced:
        if not fused_ok:
            raise ValueError(
                "REPRO_LANE_NATIVE=1 requires kernel_mode='fused' and a "
                "config the megakernel covers (algorithms.supports_fused); "
                f"got kernel_mode={cfg.kernel_mode!r}, "
                f"algorithm={cfg.algorithm!r}")
        return True
    if forced is not None:
        return False
    return fused_ok


def _make_lane_step(cfg: DehazeConfig, associative: bool = True,
                    lane_native: Optional[bool] = None):
    if lane_native is None:
        lane_native = resolve_lane_native(cfg)
    if lane_native:
        if not (cfg.kernel_mode == "fused" and alg.supports_fused(cfg)):
            raise ValueError(
                "lane_native=True requires kernel_mode='fused' and a config "
                "the megakernel covers (algorithms.supports_fused)")

        def lane_step(frames: jnp.ndarray, frame_ids: jnp.ndarray,
                      state: AtmoState) -> DehazeOutput:
            out, t, a_seq, new_state = alg.fused_dehaze_lanes(
                frames, frame_ids, state, cfg)
            return DehazeOutput(out, t, a_seq.astype(out.dtype), new_state)
        return lane_step
    return jax.vmap(_make_single_step(cfg, associative=associative))


def make_multi_stream_step(cfg: DehazeConfig, associative: bool = True,
                           lane_native: Optional[bool] = None):
    """Returns step(frames (L, B, H, W, 3), frame_ids (L, B), state) ->
    DehazeOutput with a leading lane axis on every field. Thin view of
    :func:`make_step` with the lane-batched placement.

    The paper's §5 future work — coordinating atmospheric light "across
    multiple videos" — realized as *continuous batching*: L independent
    streams ride one fixed-shape device batch, each lane carrying its own
    causal A trajectory (the state is a lane-batched ``AtmoState``, see
    ``normalize.pack_atmo_states``).

    Two realizations, selected by ``lane_native`` (None =
    :func:`resolve_lane_native`: lane-native whenever the megakernel
    covers the config, env ``REPRO_LANE_NATIVE`` to force):

    - *lane-native* (fused configs): the lane axis is folded into the
      megakernel's own grid (``ops.fused_dehaze_lanes``) — one
      ``pallas_call`` launch and one VMEM carry setup for all L lanes,
      instead of L kernel launches under vmap;
    - *vmapped* (staged configs, or forced): the single-stream component
      chain under ``jax.vmap`` over the lane axis.

    Lane semantics are identical in both: per-lane outputs match running
    ``make_dehaze_step`` on that lane's frames alone (neither the vmap nor
    the in-kernel lane grid reorders any within-frame reduction).
    Unoccupied (padding) lanes carry ``frame_ids == -1`` everywhere; the
    masked EMA paths pass their state through untouched and their frame
    outputs are discarded by the scheduler.
    """
    return make_step(cfg, PlacementSpec.lane_batched(),
                     associative=associative, lane_native=lane_native)


# ---------------------------------------------------------------------------
# Sharded step (production mesh)
# ---------------------------------------------------------------------------

def _merge_topk_over_spatial(tk_t: jnp.ndarray, tk_rgb: jnp.ndarray,
                             tk_gidx: jnp.ndarray, axis_names, cfg):
    """Merge per-shard top-k candidate lists into the per-frame global A
    candidate (B, 3): all-gather the (t, rgb, global flat index) lists over
    the spatial mesh axes, select the k lexicographically best (t, index)
    rows, mean their rgb. The explicit global-index key reproduces
    ``lax.top_k``'s lowest-flat-index tie-breaking even when a t plateau
    spans shard boundaries — common, since the min-filter output is
    piecewise constant — so the sharded candidate equals the single-device
    one bit-for-bit, not just in value. The selection itself dispatches
    through ``ops.merge_topk_candidates``: a two-key ``lax.sort`` on the
    ref substrate, an in-kernel grid-carry fold on the pallas ones."""
    tk_rgb = tk_rgb.astype(jnp.float32)
    for ax in axis_names:
        tk_t = lax.all_gather(tk_t, ax, axis=1, tiled=True)
        tk_rgb = lax.all_gather(tk_rgb, ax, axis=1, tiled=True)
        tk_gidx = lax.all_gather(tk_gidx, ax, axis=1, tiled=True)
    return alg.merge_topk_candidates(tk_t, tk_gidx, tk_rgb, cfg)


def _make_sharded_step(cfg: DehazeConfig, mesh: jax.sharding.Mesh,
                       placement: PlacementSpec, associative: bool = True,
                       lane_native: Optional[bool] = None):
    """Realize a mesh-sharded placement as a shard_map step.

    Non-lane placements reproduce the classic production step: frames over
    ``batch_axes``, H/W halo-sharded, AtmoState replicated and synchronized
    by an all-gather + causal EMA scan over the frame axis. Lane placements
    are the pod-scale composition: whole lanes shard over ``lane_axis``
    (state rows co-placed, per-lane EMA scans shard-locally with NO
    cross-shard sync), while H/W sharding inside each shard reuses the
    halo machinery on the lane-flattened frame axis with *per-frame saved
    A* rows — the per-lane saved-A input of
    ``fused_transmission_lanes_pallas`` generalized to the halo kernel.
    """
    lanes = placement.lanes
    lane_axis = placement.lane_axis
    batch_axes = placement.batch_axes
    height_axis, width_axis = placement.height_axis, placement.width_axis
    if not lanes and not batch_axes:
        raise ValueError(
            "a sharded non-lane placement needs batch_axes (the state sync "
            f"gathers candidates over them); got {placement}")
    n_h = mesh.shape[height_axis] if height_axis else 1
    n_w = mesh.shape[width_axis] if width_axis else 1
    shard_h = height_axis is not None and n_h > 1
    shard_w = width_axis is not None and n_w > 1
    # Mesh axes that actually split a spatial dimension — the candidate
    # merge and the halo machinery only engage for these.
    spatial_axes = tuple(ax for ax, on in ((height_axis, shard_h),
                                           (width_axis, shard_w)) if on)
    halo = cfg.patch_radius + (2 * cfg.gf_radius if cfg.refine else 0)
    # With spatial sharding the fused path switches to the halo-aware
    # megakernel: the exchanged (pre-map, guide) planes plus the
    # row/column-validity masks feed the kernel directly and the min/box
    # filters run masked in-VMEM (kernels.fused.fused_transmission_halo_pallas).
    use_fused = cfg.kernel_mode == "fused" and alg.supports_fused(cfg)
    if lanes and lane_native is None:
        # The lane-native megakernel has no halo variant: spatial sharding
        # composes through the halo kernel + shard-local lane EMA instead.
        lane_native = resolve_lane_native(cfg) and not spatial_axes

    fspec = placement.frame_spec()
    ispec = placement.ids_spec()
    state_spec = placement.state_spec()

    def halo_premap_and_guide(frames, a_saved, keep_halo_dtype=False):
        """Halo-extended (pre-map, guide) planes + row/column validity,
        honoring ``cfg.halo_packed``: either exchange the packed 2-channel
        stack (what the stencils consume — 1/3 less wire than RGB) or
        exchange RGB and compute the maps on the extended block. Both the
        staged chain and the fused halo kernel consume this, so the two
        paths see identical inputs (including bf16 halo rounding
        placement). ``a_saved`` is the saved atmospheric light, already
        broadcast-shaped against ``frames`` (replicated (3,) for the
        classic step, per-frame (B, 1, 1, 3) lane rows for the
        lane-sharded one).

        ``keep_halo_dtype`` (fused packed path): hand the exchanged planes
        onward in the wire dtype instead of re-casting at the boundary —
        the halo megakernel accepts bf16 inputs and upcasts in-VMEM, so
        ``halo_dtype="bfloat16"`` halves the exchange bytes end-to-end
        with no extra cast pass. Values are unchanged (bf16 -> f32 is
        exact; the rounding already happened before the exchange). The
        unpacked path always upcasts: its maps are *computed* from the
        exchanged RGB and must use the same f32 arithmetic as the staged
        chain."""
        hdt = jnp.dtype(cfg.halo_dtype)

        def exchange(p):
            p = p.astype(hdt)
            valid_w = None
            if shard_h:
                p, valid_h = spatial.halo_exchange_height(
                    p, halo, height_axis, n_h)
            else:
                valid_h = jnp.ones((p.shape[1],), bool)
            if shard_w:
                p, valid_w = spatial.halo_exchange_width(
                    p, halo, width_axis, n_w)
            return p, valid_h, valid_w

        if cfg.halo_packed:
            packed = jnp.stack([alg.premap(frames, a_saved, cfg),
                                alg.luminance(frames)], axis=-1)
            p_ext, valid_h, valid_w = exchange(packed)
            if not keep_halo_dtype:
                p_ext = p_ext.astype(frames.dtype)
            return p_ext[..., 0], p_ext[..., 1], valid_h, valid_w
        x_ext, valid_h, valid_w = exchange(frames)
        x_ext = x_ext.astype(frames.dtype)
        return (alg.premap(x_ext, a_saved, cfg), alg.luminance(x_ext),
                valid_h, valid_w)

    def global_flat_idx(lidx, h_loc, w_loc):
        """Shard-local flat core index -> global flat (row-major) index —
        the cross-shard tie-break key of the candidate merge."""
        row = lidx // w_loc
        col = lidx % w_loc
        if shard_h:
            row = row + lax.axis_index(height_axis) * h_loc
        if shard_w:
            col = col + lax.axis_index(width_axis) * w_loc
        return row * (w_loc * n_w) + col

    def candidates_from_local_topk(tk_t, tk_rgb, tk_idx, frames):
        """Per-frame A candidate (B, 3) from shard-local top-k lists."""
        if spatial_axes:
            gidx = global_flat_idx(tk_idx, frames.shape[1], frames.shape[2])
            return _merge_topk_over_spatial(tk_t, tk_rgb, gidx,
                                            spatial_axes, cfg)
        return tk_rgb.astype(jnp.float32).mean(axis=1)

    def staged_t_and_candidates(frames, a_saved):
        """Per-stage chain: masked filters over halo-extended blocks ->
        (refined t, per-frame A candidates)."""
        if spatial_axes:
            pre_ext, guide_ext, valid_h, valid_w = halo_premap_and_guide(
                frames, a_saved)
        else:
            valid_h = jnp.ones((frames.shape[1],), bool)
            valid_w = None
            pre_ext = alg.premap(frames, a_saved, cfg)
            guide_ext = alg.luminance(frames)

        # --- Component 1 on the halo-extended block (masked filters). ---
        t_raw_ext = kref.tmap_from_dark(
            spatial.masked_min_filter_2d(pre_ext, valid_h, cfg.patch_radius,
                                         valid_w),
            cfg.algorithm, cfg.omega, cfg.beta)
        t_raw_ext = t_raw_ext.astype(frames.dtype)

        core_h = slice(halo, halo + frames.shape[1]) if shard_h \
            else slice(None)
        core_w = slice(halo, halo + frames.shape[2]) if shard_w \
            else slice(None)
        t_raw = t_raw_ext[:, core_h, core_w]

        # --- Component 2: per-frame candidates (paper Eq. 5/6). ---
        tk_t, tk_rgb, tk_idx = kref.smallest_t_candidates(
            t_raw.astype(jnp.float32), frames.astype(jnp.float32), cfg.topk)
        rgb = candidates_from_local_topk(tk_t, tk_rgb, tk_idx, frames)

        # --- Refinement on the halo-extended block. ---
        if cfg.refine:
            t_ext = spatial.masked_guided_filter(
                guide_ext, t_raw_ext, valid_h, cfg.gf_radius, cfg.gf_eps,
                valid_w)
            t = jnp.clip(t_ext[:, core_h, core_w], 0.0, 1.0)
        else:
            t = t_raw
        return t, rgb

    def fused_t_and_candidates(frames, x, a_saved):
        """Fused megakernel form of ``staged_t_and_candidates``: one launch
        per block instead of the masked per-stage XLA chain. ``frames`` is
        the raw wire-dtype block (the kernels upcast in-VMEM); ``x`` its
        compute-dtype view for the XLA-side premap/guide stages."""
        if spatial_axes:
            # Halo-aware fused kernel: the exchange output is the kernel
            # input; masking (and any bf16/uint8 -> f32 upcast of wire
            # frames or packed halo planes) happens in-VMEM.
            pre_ext, guide_ext, valid_h, valid_w = halo_premap_and_guide(
                x, a_saved, keep_halo_dtype=cfg.halo_packed)
            t, tk_t, tk_rgb, tk_idx = alg.fused_transmission_halo(
                frames, pre_ext, guide_ext, valid_h, valid_w, cfg)
            rgb = candidates_from_local_topk(tk_t, tk_rgb, tk_idx, frames)
        else:
            t, _t_min, rgb = alg.fused_transmission(frames, a_saved, cfg)
        return t, rgb

    def local_step(frames, frame_ids, state):
        b_loc = frames.shape[0]
        x, odt = _ingest(frames, cfg)
        if use_fused:
            # Components 1 + 2 candidates + refinement in ONE launch.
            t, rgb = fused_t_and_candidates(frames, x, state.A)
        else:
            t, rgb = staged_t_and_candidates(x, state.A)

        # State sync: all-gather candidates over the frame axes, scan,
        # slice the local part (the paper's A broadcast, minus the race).
        a_all = lax.all_gather(rgb, batch_axes, axis=0, tiled=True)
        ids_all = lax.all_gather(frame_ids, batch_axes, axis=0, tiled=True)
        a_seq_all, new_state = ema_scan_associative(
            a_all, ids_all, state, cfg.update_period, cfg.lam)
        didx = lax.axis_index(batch_axes)
        a_seq = lax.dynamic_slice_in_dim(a_seq_all, didx * b_loc, b_loc)
        a_seq = a_seq.astype(x.dtype)

        # --- Component 3 on the core block. ---
        out = alg.generate_haze_free(x, t, a_seq,
                                     dataclasses.replace(cfg, kernel_mode="ref"))
        return DehazeOutput(out.astype(odt), t.astype(odt),
                            a_seq.astype(odt), new_state)

    def lane_local_step(frames, frame_ids, state):
        # frames (L_loc, B, h, w, 3); state rows (L_loc,) — whole lanes
        # live on this shard, so the EMA scans are shard-local and causal.
        l_loc, b = frames.shape[:2]
        if use_fused and lane_native and not spatial_axes:
            # Whole chain in one lane-native launch per shard.
            out, t, a_seq, new_state = alg.fused_dehaze_lanes(
                frames, frame_ids, state, cfg)
            return DehazeOutput(out, t, a_seq.astype(out.dtype), new_state)
        x, odt = _ingest(frames, cfg)
        if use_fused and not spatial_axes:
            # Per-lane saved-A fused t + candidates
            # (fused_transmission_lanes_pallas's building-block input).
            t, _t_min, rgb = alg.fused_transmission_lanes(frames, state.A,
                                                          cfg)
        else:
            # H/W halo sharding composes on the lane-flattened frame axis:
            # every component is frame-generic, so per-frame saved-A rows
            # (each lane's A repeated over its batch) stand in for the
            # replicated A of the classic step.
            flat = frames.reshape((l_loc * b,) + frames.shape[2:])
            flat_x = x.reshape((l_loc * b,) + x.shape[2:])
            a_pf = jnp.repeat(state.A.astype(jnp.float32), b,
                              axis=0)[:, None, None, :]
            if use_fused:
                t, rgb = fused_t_and_candidates(flat, flat_x, a_pf)
            else:
                t, rgb = staged_t_and_candidates(flat_x, a_pf)
            t = t.reshape((l_loc, b) + t.shape[1:])
            rgb = rgb.reshape(l_loc, b, 3)
        a_seq, new_state = ema_scan_lanes(rgb, frame_ids, state,
                                          cfg.update_period, cfg.lam,
                                          associative=associative)
        a_seq = a_seq.astype(x.dtype)
        out = alg.generate_haze_free(x, t, a_seq,
                                     dataclasses.replace(cfg, kernel_mode="ref"))
        return DehazeOutput(out.astype(odt), t.astype(odt),
                            a_seq.astype(odt), new_state)

    step = jax.shard_map(
        lane_local_step if lanes else local_step, mesh=mesh,
        in_specs=(fspec, ispec, state_spec),
        out_specs=DehazeOutput(frames=fspec, transmission=fspec,
                               atmo_light=ispec, state=state_spec),
        check_vma=False,
    )
    return step


def make_sharded_dehaze_step(cfg: DehazeConfig, mesh: jax.sharding.Mesh,
                             batch_axes: Tuple[str, ...] = ("data",),
                             height_axis: Optional[str] = "model",
                             width_axis: Optional[str] = None):
    """Build a shard_map dehaze step for ``mesh``. Thin view of
    :func:`make_step` with the frame-sharded placement; returns
    ``(step, frame_spec, ids_spec)`` as before.

    Sharding: frames (B, H, W, 3) with B over ``batch_axes``, H over
    ``height_axis`` and W over ``width_axis`` (None disables that spatial
    axis). frame_ids (B,) over ``batch_axes``. The AtmoState is replicated.
    With both spatial axes a 2-D (n_h x n_w) tile of shards covers each
    frame; the halo exchange runs height-then-width (corner halos ride the
    W hop for free) and every windowed filter is masked by the separable
    row x column validity mask.
    """
    placement = PlacementSpec.frame_sharded(batch_axes=tuple(batch_axes),
                                            height_axis=height_axis,
                                            width_axis=width_axis)
    step = make_step(cfg, placement, mesh)
    return step, placement.frame_spec(), placement.ids_spec()


__all__ = ["DehazeOutput", "PlacementSpec", "make_step", "donation_spec",
           "make_dehaze_step",
           "make_multi_stream_step", "make_sharded_dehaze_step",
           "resolve_lane_native", "init_atmo_state", "init_atmo_state_lanes",
           "pack_atmo_states", "unpack_atmo_states", "AtmoState", "ema_scan",
           "ema_scan_associative", "DehazeConfig"]
