"""Zero-copy tick I/O: device-resident lane buffers, donation, deferred D2H.

The serve loop's host⇄device boundary used to serialize three ways every
tick: a host-side ``np.stack`` over all L lanes, a blocking full-batch
H2D inside the jitted call, and a whole-batch ``np.asarray(out.frames)``
readback that fetched padding lanes nobody would ever look at. This
module is the overlapped replacement (README §Tick I/O & overlap):

  * :class:`LaneTickStep` keeps the ``(L, B, H, W, 3)`` wire-dtype frame
    batch *living on device*. ``stage(lane, frames)`` uploads one lane's
    batch (``jax.device_put`` — async, overlapping whatever tick is in
    flight) and splices it in with a *donated* ``dynamic_update_slice``
    (in-place on the persistent buffer: no copy of the other L-1 lanes).
    ``tick(ids, state)`` then runs the state-donated step on the buffer.
    Padding lanes are simply never staged — their rows hold stale frames
    that the ``frame_id = -1`` masking makes inert and valid-only D2H
    makes invisible.
  * :func:`fetch_valid` is the one deferred-fetch helper both serve paths
    complete through: it slices ``out.frames[lane, :n_valid]`` on device
    and fetches only those bytes.
  * :func:`donation_supported` probes (once) whether the backend honors
    ``donate_argnums`` — the serving tiers only take the overlapped path
    when it does, and ``launch/serve.py --expect-overlap`` turns the
    fallback into a hard failure.

Buffer ownership contract (who may touch what, until when):

  * the adapter owns ``self._buf`` — callers never read it, and the step
    does NOT donate it (only the state argnum), so ``out.frames`` is a
    distinct buffer the completion thread may hold for as long as it
    likes;
  * ``out.state`` belongs to the serve loop and is *donated into the next
    tick*: every read of it (eviction snapshots, rung-switch repacks)
    must be dispatched before the next ``tick()`` call — device execution
    order equals dispatch order, so anything enqueued earlier reads the
    pre-donation value;
  * a staged lane upload belongs to the adapter the moment ``stage``
    returns; the caller may free/reuse its host array immediately.
"""
from __future__ import annotations

import threading
import warnings
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.stream.spans import Phases

_probe_lock = threading.Lock()
_donation_supported: Optional[bool] = None


def donation_supported() -> bool:
    """Does this backend honor ``jax.jit(..., donate_argnums=...)``?

    Probed once per process with a trivial donated add: on a supporting
    backend the donated input is deleted after the call
    (``x.is_deleted()``); a backend that cannot implement donation warns
    and leaves the input alive. CPU jaxlibs historically fell in the
    second bucket; current ones alias. The serving tiers gate the
    overlapped tick path on this, keeping the blocking path as both the
    fallback and the parity oracle.

    On a TPU there is no fallback: the backend donates, so a probe that
    raises, or a donated input that stays alive, is a fault of the device
    path and raises instead of quietly turning the overlapped tick off.
    """
    global _donation_supported
    if _donation_supported is not None:
        return _donation_supported
    with _probe_lock:
        if _donation_supported is not None:
            return _donation_supported
        on_tpu = jax.default_backend() == "tpu"
        try:
            f = jax.jit(lambda x: x + 1, donate_argnums=(0,))
            x = jnp.zeros((8,), jnp.float32)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                jax.block_until_ready(f(x))
            supported = bool(x.is_deleted())
        except Exception:
            if on_tpu:
                raise
            supported = False
        if on_tpu and not supported:
            raise RuntimeError(
                "buffer donation probe: the TPU backend left a donated "
                "input alive; the overlapped serve tick cannot run")
        _donation_supported = supported
    return supported


def fetch_ready(view, phases: Phases) -> np.ndarray:
    """Fetch a device array to the host in two spans of ``phases``:
    ``device_wait`` until the device has computed it, then ``fetch``, the
    D2H copy and host delinearize of the ready array."""
    with phases.span("device_wait"):
        jax.block_until_ready(view)
    with phases.span("fetch"):
        return np.asarray(view)


def fetch_valid(frames, n_valid: int, lane: Optional[int] = None,
                phases: Optional[Phases] = None) -> np.ndarray:
    """Valid-only D2H: fetch ``frames[lane, :n_valid]`` (or
    ``frames[:n_valid]`` when ``lane`` is None) as a host array, through
    :func:`fetch_ready` into the serve's ``phases``.

    The slice is dispatched on device *before* the blocking fetch, so
    only the requested bytes cross the wire — padding frames (and, per
    lane, the other lanes) never leave HBM. This is the single completion
    mechanism shared by the lane scheduler and the single-stream
    dispatcher.
    """
    view = frames if lane is None else frames[lane]
    return fetch_ready(view[:n_valid],
                       phases if phases is not None else Phases())


@partial(jax.jit, donate_argnums=(0,))
def _lane_update(buf, lane, idx):
    """In-place (donated) write of one lane's batch into the persistent
    device buffer. ``idx`` is a traced scalar — one executable per buffer
    shape/dtype, not one per lane index."""
    zeros = (0,) * (buf.ndim - 1)
    return lax.dynamic_update_slice(buf, lane[None], (idx,) + zeros)


class LaneTickStep:
    """Device-resident lane buffer + state-donated step, one lane count.

    ``step`` is the jitted lane-batched step built with
    ``make_step(..., donate="state")``. The adapter is *call-compatible*
    with the raw step (``adapter(frames, ids, state)`` uploads the full
    batch and ticks), which is exactly what the autoscaler's rung warm-up
    invokes — so warming a rung through the adapter pre-binds its donated
    buffer AND populates both executables (step + lane splice) for the
    serving avals, with zero autoscaler changes.

    ``stage``/``tick`` belong to one serve thread (the completion threads
    only ever hold ``out.frames``, never the buffer). ``__call__`` is
    additionally serialized by a lock: concurrent full-batch calls on one
    adapter (the autoscaler's warm + retry threads can overlap) would
    interleave the buffer rebind with the donated splice and hand one
    thread the other's already-donated buffer.
    """

    def __init__(self, step: Callable, n_lanes: int):
        self._step = step
        self.n_lanes = n_lanes
        self._buf = None
        self._call_lock = threading.Lock()
        self.staged_lanes = 0       # stage() calls (live-lane uploads)
        self.staged_bytes = 0       # H2D bytes actually shipped

    def ensure_buf(self, lane_shape: Tuple[int, ...], dtype) -> None:
        """(Re)allocate the persistent ``(L,) + lane_shape`` device buffer
        when the lane batch shape or wire dtype changes."""
        shape = (self.n_lanes,) + tuple(lane_shape)
        if (self._buf is None or self._buf.shape != shape
                or self._buf.dtype != np.dtype(dtype)):
            self._buf = jnp.zeros(shape, dtype)

    def stage(self, lane_idx: int, frames) -> None:
        """Upload one lane's ``(B, H, W, 3)`` batch into its buffer row.

        ``device_put`` starts the H2D transfer without blocking on
        in-flight compute; the donated splice executes in dispatch order,
        after any tick already reading the buffer.
        """
        arr = np.asarray(frames)
        self.ensure_buf(arr.shape, arr.dtype)
        dev = jax.device_put(arr)
        self._buf = _lane_update(self._buf, dev, np.int32(lane_idx))
        self.staged_lanes += 1
        self.staged_bytes += arr.nbytes

    def tick(self, frame_ids, state):
        """Run the step on the device-resident buffer. ``state`` is
        donated — the caller must not touch it after this call (reads it
        dispatched *before* the call are safe)."""
        return self._step(self._buf, np.asarray(frame_ids), state)

    def __call__(self, frames, frame_ids, state):
        """Full-batch compatibility path (rung warm-up, direct callers):
        upload the whole batch, prime the lane-splice executable, tick."""
        with self._call_lock:
            arr = np.asarray(frames)
            self._buf = jax.device_put(arr)
            if arr.shape[0] > 0:
                self.stage(0, arr[0])
            return self.tick(frame_ids, state)


class TickBufferPool:
    """Per-serve (or per-fleet-host) pool of :class:`LaneTickStep`
    adapters, one per lane count.

    ``step_factory(n_lanes)`` returns the state-donated jitted step for a
    rung (typically ``stream.elastic._cached_multi_step(...,
    donate="state")``). ``pool.adapter`` has the exact
    ``step_factory(n)`` signature the autoscaler and ``serve_many``
    already use, so the overlapped path slots in wherever a step factory
    went before. Pools are intentionally NOT shared across fleet hosts:
    each host owns its device frame buffer (the jitted steps underneath
    still share the bounded step cache).
    """

    def __init__(self, step_factory: Callable[[int], Callable]):
        self._factory = step_factory
        self._adapters: Dict[int, LaneTickStep] = {}
        self._lock = threading.Lock()

    def adapter(self, n_lanes: int) -> LaneTickStep:
        with self._lock:
            a = self._adapters.get(n_lanes)
            if a is None:
                a = LaneTickStep(self._factory(n_lanes), n_lanes)
                self._adapters[n_lanes] = a
            return a


def is_overlap_step(step) -> bool:
    """Duck-typed detection of the overlapped tick contract: anything
    with ``stage``/``tick`` (a :class:`LaneTickStep`) takes the
    zero-copy path; a plain callable takes the blocking oracle path."""
    return callable(getattr(step, "stage", None)) \
        and callable(getattr(step, "tick", None))


__all__ = ["LaneTickStep", "TickBufferPool", "donation_supported",
           "fetch_ready", "fetch_valid", "is_overlap_step"]
