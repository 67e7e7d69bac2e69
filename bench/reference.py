"""Plain reference of the dehazing service, for the benchmark's ``correct``.

What a served frame must be, written from the published equations and the
configuration's numbers alone, in straightforward ``jax.numpy`` float32:

- ingest: uint8 wire value ``u`` is the image value ``u / 255``;
- transmission (component 1), from the atmospheric light ``A_saved`` that
  the stream's state holds when the frame's batch starts (paper §3.3):
  DCP (He et al., Eq. 3) ``t = 1 - omega * min_patch min_c I_c / A_c``,
  CAP (Zhu et al., Eq. 4) ``t = exp(-beta * min_patch (w0 + w1 v + w2 s))``;
  the patch is the (2r+1)^2 box clipped at the image border;
- atmospheric light (component 2, Eq. 6): the colour of the first pixel,
  in row-major order, of least raw transmission, folded into the stream's
  light every ``update_period`` frames by ``A = lam * A_new + (1 - lam) A``
  (Eq. 9); the first frame sets it, and the stream starts from white;
- refinement: the grey-guide guided filter (He et al. 2010) with Rec.601
  luma as the guide, clipped to [0, 1];
- recovery (component 3, Eq. 8): ``J = clip((I - A)/max(t, t0) + A, 0, 1)``.

It imports nothing of the program under test and takes nothing it made:
frames come from the benchmark's own pool, parameters from the
configuration file. Nothing here runs inside a measured window.

Two precisions, on purpose. The choice of each frame's light pixel is made
in float32 on the device (``_candidates``), as the configuration states
its arithmetic: an exact tie goes to the first pixel, and distinct
transmissions of 8-bit frames lie far apart in float32, so the choice is
the configuration's own. Everything continuous after it (transmission,
guided filter, recovery) is float64 on the host (``recover64``), with
box means by running sums, so a gap is the program's round-off and not
the reference's.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LUMA = (0.299, 0.587, 0.114)        # Rec.601, the guided filter's guide


def upcast(u8: jnp.ndarray) -> jnp.ndarray:
    return u8.astype(jnp.float32) / 255.0


def min_filter(x: jnp.ndarray, r: int) -> jnp.ndarray:
    """(2r+1)^2 window minimum over the last two axes, border-clipped."""
    if not r:
        return x
    nd = x.ndim
    return lax.reduce_window(x, jnp.inf, lax.min,
                             (1,) * (nd - 2) + (2 * r + 1, 2 * r + 1),
                             (1,) * nd, ((0, 0),) * (nd - 2) + ((r, r),) * 2)


def raw_transmission(x: jnp.ndarray, a_saved: jnp.ndarray, p: Dict):
    """(..., H, W, 3) frames in [0, 1], (..., 3) saved light -> (..., H, W)."""
    if p["algorithm"] == "dcp":
        a = jnp.maximum(a_saved, 1e-3)[..., None, None, :]
        dark = min_filter(jnp.min(x / a, axis=-1), p["patch_radius"])
        return 1.0 - p["omega"] * dark
    v, mn = jnp.max(x, axis=-1), jnp.min(x, axis=-1)
    s = jnp.where(v > 0, (v - mn) / jnp.where(v > 0, v, 1.0), 0.0)
    depth = p["cap_w0"] + p["cap_w1"] * v + p["cap_w2"] * s
    return jnp.exp(-p["beta"] * min_filter(depth, p["patch_radius"]))


@functools.partial(jax.jit, static_argnums=(2,))
def _candidates(u8, a_saved, pkey):
    """Eq. 6 for a batch of frames: the colour at the first argmin of t."""
    p = dict(pkey)
    x = upcast(u8)
    t = raw_transmission(x, a_saved, p)
    flat = t.reshape(t.shape[0], -1)
    j = jnp.argmin(flat, axis=-1)
    return jnp.take_along_axis(x.reshape(x.shape[0], -1, 3),
                               j[:, None, None], axis=1)[:, 0]


def _box64(v: np.ndarray, r: int) -> np.ndarray:
    """Border-clipped (2r+1)^2 box mean in float64 by running sums."""
    if not r:
        return v

    def axis_sum(v, ax):
        pad = [(0, 0)] * v.ndim
        pad[ax] = (r + 1, r)
        c = np.cumsum(np.pad(v, pad), axis=ax)
        n = v.shape[ax]
        return (np.take(c, np.arange(2 * r + 1, 2 * r + 1 + n), axis=ax)
                - np.take(c, np.arange(n), axis=ax))

    def count(n):
        i = np.arange(n)
        return np.minimum(i + r, n - 1) - np.maximum(i - r, 0) + 1

    s = axis_sum(axis_sum(v, 0), 1)
    return s / (count(v.shape[0])[:, None] * count(v.shape[1])[None, :])


def recover64(u8: np.ndarray, a_saved: np.ndarray, a_frame: np.ndarray,
              p: Dict) -> np.ndarray:
    """J of one ``(H, W, 3)`` uint8 frame, computed in float64 on the host:
    the continuous part of the reference, as exact as the host can make
    it, so that the gap it shows is the program's own round-off."""
    from scipy.ndimage import minimum_filter1d
    x = u8.astype(np.float64) / 255.0
    r = int(p["patch_radius"])

    def min_filter64(v):
        if not r:
            return v
        v = minimum_filter1d(v, 2 * r + 1, axis=0, mode="nearest")
        return minimum_filter1d(v, 2 * r + 1, axis=1, mode="nearest")

    if p["algorithm"] == "dcp":
        a = np.maximum(a_saved.astype(np.float64), 1e-3)
        t_raw = 1.0 - p["omega"] * min_filter64(np.min(x / a, axis=-1))
    else:
        v, mn = x.max(axis=-1), x.min(axis=-1)
        s = np.where(v > 0, (v - mn) / np.where(v > 0, v, 1.0), 0.0)
        depth = p["cap_w0"] + p["cap_w1"] * v + p["cap_w2"] * s
        t_raw = np.exp(-p["beta"] * min_filter64(depth))
    t = t_raw
    if p["refine"]:
        g = LUMA[0] * x[..., 0] + LUMA[1] * x[..., 1] + LUMA[2] * x[..., 2]
        rg, eps = int(p["gf_radius"]), float(p["gf_eps"])
        mg, mp = _box64(g, rg), _box64(t_raw, rg)
        a_ = (_box64(g * t_raw, rg) - mg * mp) / (_box64(g * g, rg) - mg * mg
                                                  + eps)
        b_ = mp - a_ * mg
        t = np.clip(_box64(a_, rg) * g + _box64(b_, rg), 0.0, 1.0)
    a = a_frame.astype(np.float64)
    j = np.clip((x - a) / np.maximum(t, p["t0"])[..., None] + a, 0.0, 1.0)
    if p["gamma"] != 1.0:
        j = j ** p["gamma"]
    return j


def params_key(p: Dict) -> Tuple:
    keys = ("algorithm", "patch_radius", "omega", "beta", "cap_w0", "cap_w1",
            "cap_w2", "refine", "gf_radius", "gf_eps", "t0", "gamma")
    return tuple((k, p[k]) for k in keys)


def light_trajectory(frame_of, n_frames: Sequence[int], batch: int,
                     p: Dict) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """Per stream, the saved light each frame's batch starts from and the
    light each frame is recovered with: ``(a_saved[s], a_frame[s])``, each
    ``(n_frames[s], 3)`` float32.

    ``frame_of(streams, frame_ids) -> (n, H, W, 3)`` uint8 gives the
    frames; stream ``s`` serves frames ``0 .. n_frames[s]-1`` in batches
    of ``batch`` from a fresh (white) state. The streams step in lockstep,
    so one device call picks the candidates of every stream that
    refreshes at that frame.
    """
    pkey = params_key(p)
    period, lam = int(p["update_period"]), np.float32(p["lam"])
    n_streams = len(n_frames)
    light = np.ones((n_streams, 3), np.float32)
    last = np.full(n_streams, -1, np.int64)          # -1: not yet set
    saved = light.copy()
    a_saved = [np.empty((n, 3), np.float32) for n in n_frames]
    a_frame = [np.empty((n, 3), np.float32) for n in n_frames]
    for f in range(max(n_frames, default=0)):
        live = [s for s in range(n_streams) if f < n_frames[s]]
        if f % batch == 0:
            saved = light.copy()
        due = [s for s in live if last[s] < 0 or f - last[s] >= period]
        if due:
            cand = np.asarray(_candidates(
                frame_of(due, [f] * len(due)), saved[due], pkey), np.float32)
            for c, s in zip(cand, due):
                light[s] = c if last[s] < 0 else lam * c + (1 - lam) * light[s]
                last[s] = f
        for s in live:
            a_saved[s][f] = saved[s]
            a_frame[s][f] = light[s]
    return a_saved, a_frame


def max_gap(frames_u8: Sequence[np.ndarray], a_saved: Sequence[np.ndarray],
            a_frame: Sequence[np.ndarray], served: Sequence[np.ndarray],
            p: Dict, threads: int = 8) -> float:
    """Widest |served J - reference J| over a set of frames (host threads;
    numpy releases the interpreter lock in the filters)."""
    from concurrent.futures import ThreadPoolExecutor

    def one(i):
        ref = recover64(frames_u8[i], a_saved[i], a_frame[i], p)
        return float(np.max(np.abs(np.asarray(served[i], np.float64) - ref)))

    with ThreadPoolExecutor(max(1, min(threads, len(frames_u8)))) as ex:
        return max(ex.map(one, range(len(frames_u8))), default=0.0)
