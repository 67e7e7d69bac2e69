"""What the service must move per frame, from shapes alone.

The least HBM traffic of any implementation of one served frame: the
wire frame read once and the delivered J written once. The transmission
map is never delivered, so it is not counted. The chain is elementwise
and windowed work on the vector units, so the bound is bandwidth alone: a
FLOP term against the matrix unit's peak would mean nothing here.
"""
from __future__ import annotations

import json
from pathlib import Path

import ml_dtypes  # noqa: F401  (registers "bfloat16" with numpy)
import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def frame_dtypes(dehaze: dict) -> tuple:
    """The wire and delivered dtypes of a configuration's ``dehaze`` block.

    ``out_dtype`` "auto" delivers a float wire dtype as itself and an
    integer one (uint8) as float32: dehazed frames are continuous.
    """
    wire = np.dtype(dehaze["io_dtype"])
    out = dehaze.get("out_dtype", "auto")
    if out != "auto":
        return wire, np.dtype(out)
    return wire, (wire if np.issubdtype(wire, np.floating)
                  or wire == np.dtype("bfloat16") else np.dtype(np.float32))


def service_bytes(h: int, w: int, wire_dtype, out_dtype) -> int:
    """Bytes in (wire frame) plus bytes out (J) for one ``h x w`` RGB frame."""
    px = h * w * 3
    return px * np.dtype(wire_dtype).itemsize + px * np.dtype(out_dtype).itemsize


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"device_kind {device_kind!r} is not in {PEAKS.name}")
    return table[device_kind]
