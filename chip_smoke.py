#!/usr/bin/env python3
"""Serve 1080p uint8 camera streams once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --chips 4    # four chips: the sharded phases only

Phase A serves one 1080 x 1920 uint8 stream of ``dehaze-dcp`` (32 frames,
batch 8) through ``ElasticServer.serve`` on the overlapped tick path. It
checks that no frame was skipped, that every tick was overlapped, that the
output is closer to the clean frames than the hazy input is, and that the
first batch's J, t and A agree with the same step run on the host CPU.

Phase B serves four 1080p uint8 ``dehaze-cap`` streams on four lanes
through ``ElasticServer.serve_many`` and checks each stream against a
single-stream serve of the same frames (the lane parity contract).

With ``--chips 4`` only the paths across chips run: the lane-sharded step
(4 lanes x 8 x 1080p over a 4-way ``data`` mesh) and the height-sharded
step (8 x 2160 x 3840 over a 4-way ``model`` mesh), each against the
one-chip step on the same frames, with outputs that must span 4 devices.

Frames are seeded synthetic haze (``repro.data.generate_haze_video``)
quantized to the uint8 wire. Everything runs in this one process, which
holds the chip. Without a TPU the script exits nonzero before serving.
The last line of stdout is ``{"ok": true, "device": {...}}``; a failed
check exits nonzero without it. Times printed here are smoke output, not
benchmark results.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
try:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import dehaze_cap, dehaze_dcp
    from repro.core import (PlacementSpec, env, init_atmo_state,
                            init_atmo_state_lanes, make_dehaze_step,
                            make_sharded_dehaze_step, make_step)
    from repro.data import HazeVideoSpec, generate_haze_video
    from repro.kernels.ref import quantize_frames
    from repro.stream import ElasticServer, StreamRequest
except ImportError as e:
    sys.exit(f"chip_smoke.py needs the repository next to it (src/repro): "
             f"{e}")

H, W, BATCH = 1080, 1920, 8
# The monitor skips a frame only after waiting this long for it; a 1080p
# uint8 batch fetches 8 x 25 MB of f32 output, far inside a minute.
TIMEOUT_S = 60.0
# Chip vs host CPU on the first batch. Reordering the f32 luma sum moves J
# and t by ~2e-6 at 1080p; computing the guided-filter guide in bf16 moves
# them by ~1e-3 (both measured on the CPU). A is a picked pixel and the
# EMA, exact up to f32 round-off.
TOL_CPU = {"J": 1e-4, "t": 1e-4, "A": 1e-5}
# Lanes vs single-stream serve, the serving parity contract
# (launch/serve.py gates the same drift).
TOL_LANES = 1e-5
# Sharded vs one-chip step (tests/test_distributed.py holds the same).
TOL_SHARDED = {"J": 2e-5, "t": 2e-5, "A": 1e-5}


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def max_diff(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float32)
                               - np.asarray(b, np.float32))))


def wire_video(h: int, w: int, n: int, seed: int):
    vid = generate_haze_video(HazeVideoSpec(height=h, width=w, n_frames=n,
                                            seed=seed))
    return vid, quantize_frames(vid.hazy, "uint8")


def peak_hbm(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} B"


def compare(name: str, got, want, tol) -> None:
    diffs = {"J": max_diff(got.frames, want.frames),
             "t": max_diff(got.transmission, want.transmission),
             "A": max_diff(got.atmo_light, want.atmo_light)}
    print(f"{name}: max_abs_diff " + " ".join(
        f"{k}={v:.3e} (tol {tol[k]:.0e})" for k, v in diffs.items()))
    for k, v in diffs.items():
        check(v <= tol[k], f"{name}: {k} differs by {v:.3e} > {tol[k]:.0e}")


def phase_a(h: int, w: int, n_frames: int, batch: int, cpu) -> None:
    """One dehaze-dcp stream through ElasticServer.serve."""
    cfg = dehaze_dcp.config(io_dtype="uint8")
    vid, wire = wire_video(h, w, n_frames, seed=11)
    srv = ElasticServer(cfg, batch=batch, timeout_s=TIMEOUT_S)
    t0 = time.perf_counter()
    srv.serve(iter(wire[:batch]), stream_id="warm", tick_overlap=True)
    print(f"phase A setup: warm serve (compile + 1 batch) "
          f"{time.perf_counter() - t0:.3f} s")

    outs = {}
    rep = srv.serve(iter(wire), stream_id="cam0", tick_overlap=True,
                    sink=lambda fid, f: outs.setdefault(fid, np.asarray(f)))
    print(f"phase A serve: {h}x{w} uint8 frames={rep.frames} "
          f"ticks={rep.ticks} overlap_ticks={rep.overlap_ticks} "
          f"skipped={rep.skipped} wall={rep.wall_s:.3f} s (smoke output)")
    check(rep.frames == n_frames and sorted(outs) == list(range(n_frames)),
          f"phase A delivered {sorted(outs)[:4]}... of {n_frames} frames")
    check(rep.skipped == 0, f"phase A skipped {rep.skipped} frame(s)")
    check(rep.overlap_ticks == rep.ticks,
          f"phase A: {rep.overlap_ticks}/{rep.ticks} ticks overlapped")
    got = np.stack([outs[i] for i in range(n_frames)])
    l1_hazy = float(np.abs(vid.hazy - vid.clear).mean())
    l1_out = float(np.abs(got - vid.clear).mean())
    print(f"phase A L1 vs clean: hazy={l1_hazy:.5f} dehazed={l1_out:.5f}")
    check(l1_out < l1_hazy, "phase A: dehazing did not reduce L1")

    # The first batch: the served frames, the step on the chip, and the
    # same step on the host CPU of this process.
    step = jax.jit(make_dehaze_step(cfg))
    args = (wire[:batch], np.arange(batch, dtype=np.int32),
            init_atmo_state())
    chip = step(*args)
    host = step(*jax.device_put(args, cpu))
    served = max_diff(got[:batch], chip.frames)
    print(f"phase A served vs step: J max_abs_diff={served:.3e} "
          f"(tol {TOL_LANES:.0e})")
    check(served <= TOL_LANES, "phase A: served frames differ from the step")
    compare("phase A chip vs cpu", chip, host, TOL_CPU)


def phase_b(h: int, w: int, n_frames: int, batch: int,
            n_streams: int = 4) -> None:
    """Four dehaze-cap streams on four lanes through serve_many, each
    against a single-stream serve of the same frames."""
    cfg = dehaze_cap.config(io_dtype="uint8")
    wires = [wire_video(h, w, n_frames, seed=21 + i)[1]
             for i in range(n_streams)]
    srv = ElasticServer(cfg, batch=batch, timeout_s=TIMEOUT_S)
    t0 = time.perf_counter()
    srv.serve_many([StreamRequest(f"warm{i}", iter(x[:batch]))
                    for i, x in enumerate(wires)],
                   n_lanes=n_streams, tick_overlap=True)
    print(f"phase B setup: warm serve_many (compile + 1 tick) "
          f"{time.perf_counter() - t0:.3f} s")

    lanes = {}
    rep = srv.serve_many(
        [StreamRequest(f"cam{i}", iter(x)) for i, x in enumerate(wires)],
        n_lanes=n_streams, tick_overlap=True,
        sink=lambda sid, fid, f: lanes.setdefault((sid, fid), np.asarray(f)))
    print(f"phase B serve_many: {n_streams} streams x {h}x{w} uint8 "
          f"lanes={rep.n_lanes} frames={rep.frames} ticks={rep.ticks} "
          f"overlap_ticks={rep.overlap_ticks} skipped={rep.skipped} "
          f"warm_failures={rep.warm_failures} stragglers={rep.stragglers} "
          f"wall={rep.wall_s:.3f} s (smoke output)")
    check(rep.frames == n_streams * n_frames
          and len(lanes) == n_streams * n_frames,
          f"phase B delivered {len(lanes)} of {n_streams * n_frames} frames")
    check(rep.skipped == 0, f"phase B skipped {rep.skipped} frame(s)")
    check(rep.warm_failures == 0,
          f"phase B: {rep.warm_failures} lane rung(s) failed to warm")
    check(rep.overlap_ticks == rep.ticks,
          f"phase B: {rep.overlap_ticks}/{rep.ticks} ticks overlapped")
    check(rep.stragglers == 0, f"phase B: {rep.stragglers} straggler(s)")

    single = {}
    ref = ElasticServer(cfg, batch=batch, timeout_s=TIMEOUT_S)
    for i, x in enumerate(wires):
        r = ref.serve(iter(x), stream_id=f"cam{i}", tick_overlap=True,
                      sink=lambda fid, f, i=i: single.setdefault(
                          (f"cam{i}", fid), np.asarray(f)))
        check(r.skipped == 0, f"phase B single cam{i} skipped {r.skipped}")
    check(sorted(single) == sorted(lanes),
          "phase B: lane and single-stream serves delivered other frames")
    drift = max(max_diff(lanes[k], single[k]) for k in lanes)
    print(f"phase B lanes vs single-stream: J max_abs_diff={drift:.3e} "
          f"(tol {TOL_LANES:.0e}) over {len(lanes)} frames")
    check(drift <= TOL_LANES, "phase B: lanes differ from single streams")


def _put(tree, mesh, specs):
    return jax.device_put(tree, jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P)))


def _spans(name: str, out, n: int) -> None:
    for field in ("frames", "transmission"):
        got = len(getattr(out, field).sharding.device_set)
        check(got == n, f"{name}: {field} spans {got} device(s), not {n}")
    print(f"{name}: frames and transmission span {n} devices")


def phase_lane_sharded(h: int, w: int, batch: int, devices) -> None:
    """Lane-sharded step: one lane per chip over a ``data`` mesh."""
    cfg = dehaze_dcp.config(io_dtype="uint8")
    n = len(devices)
    frames = np.stack([wire_video(h, w, batch, seed=31 + i)[1]
                       for i in range(n)])
    args = (frames, np.tile(np.arange(batch, dtype=np.int32), (n, 1)),
            init_atmo_state_lanes(n))
    mesh = jax.make_mesh((n,), ("data",), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,))
    placement = PlacementSpec.lane_sharded(lane_axis="data")
    step = jax.jit(make_step(cfg, placement, mesh))
    specs = (placement.frame_spec(), placement.ids_spec(),
             placement.state_spec())
    t0 = time.perf_counter()
    out = jax.block_until_ready(step(*_put(args, mesh, specs)))
    print(f"lane-sharded setup: compile + 1 step "
          f"{time.perf_counter() - t0:.3f} s ({n} lanes x {batch} x "
          f"{h}x{w} uint8)")
    _spans("lane-sharded", out, n)
    one = jax.jit(make_step(cfg, PlacementSpec.lane_batched()))(
        *jax.device_put(args, devices[0]))
    compare("lane-sharded vs one chip", out, one, TOL_SHARDED)


def phase_height_sharded(h: int, w: int, batch: int, devices) -> None:
    """Height-sharded halo step: image rows split over a ``model`` mesh."""
    cfg = dehaze_dcp.config(io_dtype="uint8")
    n = len(devices)
    _, frames = wire_video(h, w, batch, seed=41)
    args = (frames, np.arange(batch, dtype=np.int32), init_atmo_state())
    mesh = jax.make_mesh((1, n), ("data", "model"), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    step, fspec, ispec = make_sharded_dehaze_step(cfg, mesh)
    specs = (fspec, ispec, jax.tree.map(lambda _: P(), args[2]))
    t0 = time.perf_counter()
    out = jax.block_until_ready(jax.jit(step)(*_put(args, mesh, specs)))
    print(f"height-sharded setup: compile + 1 step "
          f"{time.perf_counter() - t0:.3f} s ({batch} x {h}x{w} uint8 over "
          f"{n} row shards)")
    _spans("height-sharded", out, n)
    one = jax.jit(make_dehaze_step(cfg))(*jax.device_put(args, devices[0]))
    compare("height-sharded vs one chip", out, one, TOL_SHARDED)


def main() -> int:
    ap = argparse.ArgumentParser(
        description="Serve 1080p uint8 camera streams once on a TPU.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the paths across four chips")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    if dev.platform != "tpu" or len(devices) < args.chips:
        print(f"FAIL: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform!r} device(s)", file=sys.stderr)
        return 1
    print(f"setup: compile cache {env.enable_compile_cache()}")

    if args.chips == 4:
        four = devices[:4]
        phases = [("lane-sharded", lambda: phase_lane_sharded(
                      H, W, BATCH, four)),
                  ("height-sharded", lambda: phase_height_sharded(
                      2 * H, 2 * W, BATCH, four))]
    else:
        phases = [("A", lambda: phase_a(H, W, 32, BATCH,
                                         jax.devices("cpu")[0])),
                  ("B", lambda: phase_b(H, W, 16, BATCH))]
    failed = []
    for name, run in phases:
        # A failed phase does not stop the next one, so one run on the
        # chip reports every phase; any failure still fails the script.
        try:
            run()
        except Exception as e:
            traceback.print_exc()
            print(f"FAIL: phase {name}: {e}", file=sys.stderr)
            failed.append(name)
        print(f"phase {name} peak HBM so far: {peak_hbm(dev)}")
    if failed:
        print(f"FAIL: phase(s) {', '.join(failed)} failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
