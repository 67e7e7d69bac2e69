"""The spout (paper §3.2 layer 1): frame source, id assignment, batching."""
from __future__ import annotations

import dataclasses
from typing import Iterator, Optional

import numpy as np

from repro.stream.spans import Phases


def _wire_frame(f) -> np.ndarray:
    """Keep native wire dtypes (README §Dtype contract) — uint8 is the
    round(v*255) quantized [0,1] image (4x less wire + HBM traffic than
    f32, upcast in-VMEM by the kernels), bfloat16/float32 pass through —
    and coerce everything else to float32."""
    arr = np.asarray(f)
    if arr.dtype == np.uint8 or arr.dtype == np.float32 \
            or arr.dtype.name == "bfloat16":
        return arr
    return arr.astype(np.float32)


@dataclasses.dataclass
class FrameBatch:
    frames: np.ndarray      # (B, H, W, 3) wire dtype: uint8 | bf16 | f32
    frame_ids: np.ndarray   # (B,) int32: consecutive ids, then -1 padding
    n_valid: int            # trailing frames may be padding on the last batch
    stream_id: str = "default"


class Spout:
    """Wraps an iterator of frames, assigns consecutive ids, emits batches.

    Frames keep their wire dtype end-to-end: uint8 / bfloat16 / float32
    pass through untouched (the device kernels upcast in-VMEM — a uint8
    camera feed stays 1 byte/channel from source to HBM), any other dtype
    is coerced to float32 here. The final partial batch is padded by
    repeating the last frame (dtype-matched by construction) so the jitted
    step always sees a static shape; ``n_valid`` tells the sink how many
    outputs are real. Padding slots carry ``frame_id = -1`` so the EMA
    scans mask them out — they must NOT get the future real ids the spout
    will later assign to real frames (that double-advanced the coherence
    state on duplicate frames).

    Batch assembly is the ``spout`` span of ``phases`` (the serve's
    :class:`~repro.stream.spans.Phases`); waiting on the source iterator
    is not part of it.
    """

    def __init__(self, frames: Iterator[np.ndarray], batch: int,
                 start_frame: int = 0, stream_id: str = "default",
                 phases: Optional[Phases] = None):
        self._it = iter(frames)
        self._batch = batch
        self._next_id = start_frame
        self._stream_id = stream_id
        self._phases = phases if phases is not None else Phases()

    def __iter__(self) -> Iterator[FrameBatch]:
        buf = []
        for f in self._it:
            buf.append(_wire_frame(f))
            if len(buf) == self._batch:
                yield self._emit(buf)
                buf = []
        if buf:
            yield self._emit(buf)

    def _emit(self, buf) -> FrameBatch:
        with self._phases.span("spout"):
            n_valid = len(buf)
            while len(buf) < self._batch:
                buf.append(buf[-1])
            ids = np.full((self._batch,), -1, np.int32)
            ids[:n_valid] = np.arange(self._next_id, self._next_id + n_valid,
                                      dtype=np.int32)
            self._next_id += n_valid
            return FrameBatch(frames=np.stack(buf), frame_ids=ids,
                              n_valid=n_valid, stream_id=self._stream_id)
