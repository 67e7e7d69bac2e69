"""Serving driver: the paper's full pipeline over synthetic hazy streams.

Spout -> dehaze workers (jitted component chain) -> monitor (reorder +
timeout skip) -> sink, with per-stream EMA state, elastic resize and
stream-state checkpointing.

Single stream:
  PYTHONPATH=src python -m repro.launch.serve --algorithm dcp \
      --resolution 480p --frames 96 --workers 3 --batch 8

A camera at 1080p on the uint8 wire (the shapes chip_smoke.py serves; a
1080p batch takes longer than the paper's 20 ms reader timeout to fetch):
  PYTHONPATH=src python -m repro.launch.serve --resolution 1080p \
      --io-dtype uint8 --frames 32 --timeout-ms 60000 --fail-on-skipped

Multi-tenant (N videos continuously batched over L device lanes):
  PYTHONPATH=src python -m repro.launch.serve --streams 4 --lanes 4 \
      --resolution 120p --frames 32

Elastic autoscaling (lane count walks a precompiled ladder under load;
--ramp staggers stream lengths so the burst forces a grow and the long
tail a shrink — the CI smoke leg asserts the switches happened):
  PYTHONPATH=src python -m repro.launch.serve --streams 6 --lanes 4 \
      --autoscale --ladder 2,4 --ramp --expect-switches 2

Fleet serving (2 simulated hosts x 4 lanes behind one global-EDF front
door; sticky placement keeps every stream's EMA on one host, overflow
spills to the other — the CI smoke leg asserts >= 1 spillover):
  PYTHONPATH=src python -m repro.launch.serve --streams 8 --hosts 2 \
      --lanes 4 --resolution 120p --frames 32 --expect-spillover 1
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from repro.core import DehazeConfig, env
from repro.data import HazeVideoSpec, generate_haze_video
from repro.kernels import ref as kref
from repro.stream import (ElasticServer, ScalePolicy, StreamRequest,
                          ladder_rungs)

RESOLUTIONS = {"120p": (120, 160), "240p": (240, 320), "480p": (480, 640),
               "576p": (576, 1024), "1080p": (1080, 1920),
               "2160p": (2160, 3840)}


def _make_videos(n: int, h: int, w: int, frames, seed0: int = 100):
    """N synthetic videos with distinct scenes + base atmospheric lights,
    so each lane exercises its own coherence trajectory. ``frames`` is an
    int or a per-stream list (the --ramp workload)."""
    lengths = frames if isinstance(frames, (list, tuple)) else [frames] * n
    vids = []
    for i in range(n):
        base = 0.75 + 0.05 * (i % 4)
        vids.append(generate_haze_video(HazeVideoSpec(
            height=h, width=w, n_frames=lengths[i], seed=seed0 + i,
            a_noise=0.0, a_base=(base, base, min(1.0, base + 0.02)))))
    return vids


def _wire_hazy(vid, io_dtype: str) -> np.ndarray:
    """The stream actually put on the wire: the synthetic f32 hazy video
    quantized/cast to the serving ingest dtype (no-op for float32)."""
    if io_dtype == "float32":
        return vid.hazy
    return kref.quantize_frames(vid.hazy, io_dtype)


def _print_tick_io(rep) -> None:
    """One line of tick-I/O accounting (README §Tick I/O & overlap):
    how many ticks took the zero-copy path, the valid-only D2H volume,
    and every ``ServeReport.phases`` key (``repro.stream.spans``)."""
    ph = rep.phases or {}
    phase_txt = " ".join(f"{k}={ph[k] * 1e3:.1f}ms" for k in sorted(ph))
    print(f"tick_io: overlap_ticks={rep.overlap_ticks}/{rep.ticks} "
          f"d2h_bytes={rep.d2h_bytes} stragglers={rep.stragglers}"
          + (f" {phase_txt}" if phase_txt else ""))


def _gate_overlap(args, rep) -> None:
    """--expect-overlap: a serve that expects the zero-copy tick path
    cannot tolerate a silent fallback to the blocking oracle (donation
    probe failing, env knob ignored) — that is exactly the regression
    the CI overlap leg exists to catch."""
    if args.expect_overlap and rep.overlap_ticks < rep.ticks:
        print(f"FAIL: expected every tick on the overlapped path, got "
              f"{rep.overlap_ticks}/{rep.ticks} (silent fallback to the "
              f"blocking path)", file=sys.stderr)
        sys.exit(1)


def _serve_single(args, cfg, h: int, w: int) -> int:
    vid = _make_videos(1, h, w, args.frames)[0]
    hazy = _wire_hazy(vid, args.io_dtype)
    srv = ElasticServer(cfg, n_workers=args.workers, batch=args.batch,
                        timeout_s=args.timeout_ms / 1e3)
    outs = {}
    t0 = time.perf_counter()
    rep = srv.serve(iter(hazy), sink=lambda fid, f: outs.setdefault(fid, f))
    wall = time.perf_counter() - t0

    got = np.stack([np.asarray(outs[k], np.float32) for k in sorted(outs)])
    err_hazy = np.abs(vid.hazy[:len(got)] - vid.clear[:len(got)]).mean()
    err_out = np.abs(got - vid.clear[sorted(outs)]).mean()
    print(f"algorithm={args.algorithm} resolution={args.resolution} "
          f"workers={rep.n_workers}")
    print(f"frames={rep.frames} skipped={rep.skipped} "
          f"fps={rep.fps:.2f} wall={wall:.2f}s")
    _print_tick_io(rep)
    print(f"L1 vs ground truth: hazy={err_hazy:.4f} dehazed={err_out:.4f}")
    a = srv.store.get("default").A
    print(f"final shared A = {np.asarray(a)}")
    _gate_overlap(args, rep)
    return rep.skipped


def _serve_many(args, cfg, h: int, w: int) -> int:
    if args.ramp:
        # Burst of short clips, then long tails: queue depth forces a
        # ladder grow, the drained tail forces a shrink.
        n_long = min(2, args.streams)
        lengths = [max(args.batch, args.frames // 4)] \
            * (args.streams - n_long) + [args.frames] * n_long
    else:
        lengths = [args.frames] * args.streams
    vids = _make_videos(args.streams, h, w, lengths)
    wires = [_wire_hazy(v, args.io_dtype) for v in vids]
    lanes = args.lanes if args.lanes > 0 else args.streams
    srv = ElasticServer(cfg, batch=args.batch,
                        timeout_s=args.timeout_ms / 1e3)
    counts: dict = {}
    cam0_out: dict = {}

    def sink(sid: str, fid: int, f) -> None:
        counts[sid] = counts.get(sid, 0) + 1
        if sid == "cam0":
            cam0_out[fid] = np.asarray(f, np.float32)

    policy = None
    if args.autoscale:
        rungs = tuple(int(r) for r in args.ladder.split(","))
        policy = ScalePolicy(rungs=rungs, dwell_up=1, dwell_down=2)
        # Prime every rung's executable so the smoke run's switches gate
        # on load, not on compile latency racing short streams.
        warm = _wire_hazy(_make_videos(1, h, w, args.batch, seed0=90)[0],
                          args.io_dtype)
        for r in ladder_rungs(rungs, lanes):
            srv.serve_many([StreamRequest(f"_warm{r}", iter(warm))],
                           n_lanes=r)

    rep = srv.serve_many(
        [StreamRequest(f"cam{i}", iter(wire))
         for i, wire in enumerate(wires)],
        n_lanes=lanes, sink=sink, autoscale=args.autoscale, policy=policy,
        n_hosts=args.hosts)
    print(f"algorithm={args.algorithm} resolution={args.resolution} "
          f"streams={args.streams} lanes={rep.n_lanes} batch={args.batch} "
          f"hosts={rep.n_hosts}")
    print(f"frames={rep.frames} skipped={rep.skipped} ticks={rep.ticks} "
          f"aggregate_fps={rep.aggregate_fps:.2f} wall={rep.wall_s:.2f}s")
    _print_tick_io(rep)
    if args.hosts > 1:
        print(f"spillovers={rep.spillovers} migrations={rep.migrations}")
        if rep.migrations != 0:
            print(f"FAIL: sticky placement violated — {rep.migrations} EMA "
                  f"migration(s)", file=sys.stderr)
            sys.exit(1)
    if args.autoscale:
        print(f"ladder_switches={rep.ladder_switches} "
              f"switch_wall={rep.switch_wall_s * 1e3:.1f}ms "
              f"evictions={rep.evictions} final_lanes={rep.n_lanes} "
              f"warm_failures={rep.warm_failures}")
    for sid in sorted(rep.per_stream):
        if sid.startswith("_warm"):
            continue
        r = rep.per_stream[sid]
        a = np.asarray(srv.store.get(sid).A).round(3)
        print(f"  {sid}: frames={r.frames} emitted={counts.get(sid, 0)} "
              f"skipped={r.skipped} fps={r.fps:.2f} A={a}")
    if rep.warm_failures:
        # Part of the ladder failed to warm (e.g. a rung whose lane batch
        # does not fit device memory): the fleet could never scale onto
        # it, so the serve fails instead of exiting 0 after a warning.
        print(f"FAIL: {rep.warm_failures} ladder rung(s) failed to warm "
              f"(retried once)", file=sys.stderr)
        sys.exit(1)
    if rep.ladder_switches < args.expect_switches:
        print(f"FAIL: expected >= {args.expect_switches} ladder switches, "
              f"got {rep.ladder_switches}", file=sys.stderr)
        sys.exit(1)
    if rep.spillovers < args.expect_spillover:
        print(f"FAIL: expected >= {args.expect_spillover} spillover "
              f"admission(s), got {rep.spillovers}", file=sys.stderr)
        sys.exit(1)
    _gate_overlap(args, rep)
    if args.io_dtype != "float32" and cam0_out:
        # Non-f32 wire dtype: replay cam0 alone through a fresh server
        # (same config, same quantized stream) and gate on parity — the
        # multi-tenant lane path must dehaze a uint8/bf16 stream exactly
        # as the single-stream path does.
        ref_srv = ElasticServer(cfg, batch=args.batch,
                                timeout_s=args.timeout_ms / 1e3)
        ref_out: dict = {}
        ref_srv.serve(iter(wires[0]), stream_id="cam0",
                      sink=lambda fid, f: ref_out.setdefault(
                          fid, np.asarray(f, np.float32)))
        common = sorted(set(cam0_out) & set(ref_out))
        drift = max((np.abs(cam0_out[k] - ref_out[k]).max()
                     for k in common), default=0.0)
        print(f"io_dtype={args.io_dtype} parity(cam0): "
              f"frames={len(common)} maxerr={drift:.2e}")
        if not common or drift > 1e-5:
            print(f"FAIL: cam0 parity drift {drift:.2e} > 1e-5 between the "
                  f"lane-batched and single-stream serves at "
                  f"io_dtype={args.io_dtype}", file=sys.stderr)
            sys.exit(1)
    return rep.skipped


def _tune_for_serve(args, h: int, w: int) -> None:
    """--tune: measured-search the tile space for *this serve's* shapes
    before serving, so the run resolves freshly measured winners for the
    current device kind instead of defaults (or a stale table)."""
    from repro.kernels import tuning

    stats = tuning.TuneStats()
    kw = dict(method="search", persist=True, stats=stats)
    tuning.autotune_fused(shapes=((args.batch, h, w),),
                          algorithms=(args.algorithm,), topks=(1,),
                          io_dtypes=(args.io_dtype,), **kw)
    if args.streams > 1:
        lanes = args.lanes if args.lanes > 0 else args.streams
        tuning.autotune_fused_lanes(
            shapes=((lanes, args.batch, h, w),), **kw)
    print(f"tune: device_kind={tuning.device_kind()} "
          f"table={tuning.table_path()} timed_runs={stats.timed_runs} "
          f"(exhaustive would be {stats.exhaustive_runs}) "
          f"skipped={stats.skipped}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--algorithm", default="dcp", choices=["dcp", "cap"])
    ap.add_argument("--resolution", default="240p",
                    choices=sorted(RESOLUTIONS))
    ap.add_argument("--frames", type=int, default=64,
                    help="frames per stream")
    ap.add_argument("--streams", type=int, default=1,
                    help="number of concurrent videos (>1 uses the "
                         "lane-batched multi-tenant scheduler)")
    ap.add_argument("--lanes", type=int, default=0,
                    help="device lanes for --streams > 1 "
                         "(default 0 = one lane per stream; per-host count "
                         "when --hosts > 1)")
    ap.add_argument("--hosts", type=int, default=1,
                    help="simulated fleet hosts: >1 serves through the "
                         "FleetScheduler (global EDF, sticky placement, "
                         "spillover admission)")
    ap.add_argument("--expect-spillover", type=int, default=0,
                    help="exit nonzero unless at least this many spillover "
                         "admissions happened (CI fleet gating)")
    ap.add_argument("--workers", type=int, default=3)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--autoscale", action="store_true",
                    help="elastic lane count: --lanes becomes the cap and "
                         "the fleet walks the --ladder under load")
    ap.add_argument("--ladder", default="4,8,16,32",
                    help="comma-separated lane-count rungs (capped by "
                         "--lanes)")
    ap.add_argument("--ramp", action="store_true",
                    help="stagger stream lengths (short burst + long "
                         "tails) to force a grow and a shrink")
    ap.add_argument("--expect-switches", type=int, default=0,
                    help="exit nonzero unless at least this many ladder "
                         "switches were committed (CI autoscale gating)")
    ap.add_argument("--timeout-ms", type=float, default=20.0,
                    help="monitor reader timeout (paper: 20 ms)")
    ap.add_argument("--update-period", type=int, default=8)
    ap.add_argument("--lam", type=float, default=0.05)
    ap.add_argument("--kernel-mode", default="auto")
    ap.add_argument("--io-dtype", default="float32",
                    choices=["float32", "bfloat16", "uint8"],
                    help="wire dtype of the frame streams: the synthetic "
                         "videos are quantized host-side and stay at this "
                         "dtype through spout/scheduler to the kernels "
                         "(uint8 = 4x less ingest traffic). With "
                         "--streams > 1 a non-f32 run also replays cam0 "
                         "single-stream and fails on parity drift")
    ap.add_argument("--tune", action="store_true",
                    help="run the successive-halving measured search for "
                         "this serve's exact shapes/dtype first (winners "
                         "persist under the current device kind in the "
                         "tuning table), then serve with them")
    ap.add_argument("--expect-overlap", action="store_true",
                    help="exit nonzero unless every tick took the "
                         "zero-copy overlapped path (pair with "
                         "REPRO_TICK_OVERLAP=1; CI gating against a "
                         "silent fallback to the blocking path)")
    ap.add_argument("--fail-on-skipped", action="store_true",
                    help="exit nonzero if any frame was timeout-skipped "
                         "(CI smoke gating)")
    args = ap.parse_args()
    env.enable_compile_cache()

    h, w = RESOLUTIONS[args.resolution]
    cfg = DehazeConfig(algorithm=args.algorithm,
                       update_period=args.update_period, lam=args.lam,
                       kernel_mode=args.kernel_mode,
                       io_dtype=args.io_dtype)
    if args.tune:
        _tune_for_serve(args, h, w)
    if args.streams > 1:
        if args.workers != ap.get_default("workers"):
            print("note: --workers applies to single-stream serving only; "
                  "the multi-stream scheduler parallelizes over --lanes "
                  "instead", file=sys.stderr)
        skipped = _serve_many(args, cfg, h, w)
    else:
        skipped = _serve_single(args, cfg, h, w)
    if args.fail_on_skipped and skipped > 0:
        print(f"FAIL: {skipped} frame(s) timeout-skipped", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
