"""Run one benchmark cell once, in this process, and print its result line.

A cell (``workloads/<cell>.json``) names a configuration
(``configs/<config>.json``), a traffic mix (``traffic/<mix>.json``, whose
``kind`` names the generator ``traffic/<kind>.py``) and its chips. The
metrics a run prints are those of ``BENCHMARK.json`` that apply to the
cell: the end-to-end ones with ``--trace 0``, the per-layer ones with
``--trace 1``; each is read by ``metrics/<name>.py`` (a suffix after the
first ``.`` names a split and shares the reader). So a new cell, mix,
configuration or metric is a new file here and an entry there, never an
edit.

A run: check the chip; make the frame pool on the device from the seed;
warm the cell's own program with one tick through ``serve_many``; open the
window and serve the mix through ``ElasticServer.serve_many(...,
tick_overlap=True)``; then, with the window closed and the peak memory
read, compare a sample of the delivered frames, drawn from the seed, with
``bench.reference``. ``failed`` counts the frames due in the window that
the sink did not get within the mix's ``drain_cap_s`` (the monitor's
skips among them). A frame that came later than the mix's
``latency_limit_ms`` after its due time is late, not failed: stderr
counts it, and so does the per-layer ``late_frames_pct``. The last stdout
line is the JSON result; the numbers compared, each with its limit, are the last
lines of stderr and the last key of that line.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CLOCK = time.perf_counter
WARMUP_ID = "warm"
HOST_TRACER_LEVEL = 2          # host TraceMe events, for labelling idle gaps


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# Finding the pieces by name
# ---------------------------------------------------------------------------

def _json(path: Path) -> Dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path.relative_to(ROOT)} does not exist")
    return json.loads(path.read_text())


def cell_spec(name: str) -> Dict:
    return _json(BENCH / "workloads" / f"{name}.json")


def config_spec(name: str) -> Dict:
    return _json(BENCH / "configs" / f"{name}.json")


def mix_spec(name: str) -> Dict:
    return _json(BENCH / "traffic" / f"{name}.json")


def list_cells() -> List[str]:
    return sorted(p.stem for p in (BENCH / "workloads").glob("*.json"))


def _module(sub: str, name: str):
    path = BENCH / sub / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{path.relative_to(ROOT)} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"bench.{sub}.{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def traffic_kind(kind: str):
    return _module("traffic", kind)


def metric_reader(name: str):
    return _module("metrics", name.split(".", 1)[0])


def benchmark() -> Dict:
    return _json(ROOT / "BENCHMARK.json")


def metrics_for(bench: Dict, cell: str, trace: bool) -> List[Dict]:
    """The metrics of ``BENCHMARK.json`` this cell reports in this mode."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in moved
                             else [])]


# ---------------------------------------------------------------------------
# The camera side: per-stream feeds and what they record
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StreamRecord:
    """One stream's clock readings. ``due`` is None for a backlog."""
    sid: str
    due: Optional[np.ndarray]                 # seconds after window open
    t_call: List[float] = dataclasses.field(default_factory=list)
    t_pull: List[float] = dataclasses.field(default_factory=list)
    sink: Dict[int, float] = dataclasses.field(default_factory=dict)
    order: List[int] = dataclasses.field(default_factory=list)


def feed(rec: StreamRecord, frame_of: Callable[[int], np.ndarray], t0: float,
         t_end: float, batch: int, clock=CLOCK, sleep=time.sleep):
    """The camera: yields frame ``k`` no earlier than its due time (a
    backlog: at once, while the window is open, in whole batches) and
    records when the server asked for it and when it got it."""
    from jax.profiler import TraceAnnotation
    k = 0
    while True:
        if rec.due is None:
            if k % batch == 0 and clock() >= t_end:
                return
            target = None
        elif k >= len(rec.due):
            return
        else:
            target = t0 + rec.due[k]
        with TraceAnnotation("bench.camera_pull"):
            t_call = clock()
            if target is not None and target > t_call:
                sleep(target - t_call)
            t_pull = clock()
        rec.t_call.append(t_call)
        rec.t_pull.append(t_pull)
        yield frame_of(k)
        k += 1


class Sampler:
    """Keeps, per stream, the frames the check compares: a reservoir of
    ``m`` frames with id >= ``min_id`` (uniform over what was delivered,
    drawn from the seed) and the last frame delivered. It keeps the
    delivered arrays themselves, never a copy, so that sampling adds no
    work to the sink inside the window."""

    def __init__(self, m: int, min_id: int, seed: int, index: int):
        self.m, self.min_id = m, min_id
        self.rng = np.random.default_rng([seed, index, 7])
        self.kept: Dict[int, np.ndarray] = {}
        self.slots: List[int] = []
        self.seen = 0
        self.last: Optional[tuple] = None

    def offer(self, fid: int, payload: np.ndarray) -> None:
        self.last = (fid, payload)
        if fid < self.min_id:
            return
        if self.seen < self.m:
            self.slots.append(fid)
            self.kept[fid] = payload
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.m:
                del self.kept[self.slots[j]]
                self.slots[j] = fid
                self.kept[fid] = payload
        self.seen += 1

    def frames(self) -> Dict[int, np.ndarray]:
        out = dict(self.kept)
        if self.last is not None:
            out[self.last[0]] = self.last[1]
        return out


# ---------------------------------------------------------------------------
# What the metric readers see
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunContext:
    seconds: float
    t0: float
    t_end: float
    t_done: float
    batch: int
    streams: List[StreamRecord]
    report: object                      # the program's ServeReport
    setup_s: float
    service_bytes: int
    device_kind: str
    trace: object = None                # trace_reduce.Summary or None
    latency_limit_ms: Optional[float] = None

    def delivered_in_window(self) -> int:
        return sum(1 for r in self.streams for t in r.sink.values()
                   if self.t0 <= t <= self.t_end)

    def _due_abs(self, r: StreamRecord) -> np.ndarray:
        n = len(r.t_pull)
        return (self.t0 + r.due[:n] if r.due is not None
                else np.full(n, self.t0))

    def latencies_ms(self) -> List[float]:
        out = []
        for r in self.streams:
            due = (self.t0 + r.due if r.due is not None
                   else np.full(len(r.t_pull), self.t0))
            for k, d in enumerate(due):
                out.append((r.sink.get(k, self.t_done) - d) * 1e3)
        return out

    def late(self, limit_ms: float, drain_cap: float) -> int:
        """Frames delivered by ``drain_cap`` but more than ``limit_ms``
        after their due time."""
        return sum(1 for r in self.streams if r.due is not None
                   for k, t in r.sink.items()
                   if t <= drain_cap and (t - self.t0 - r.due[k]) * 1e3
                   > limit_ms)

    def delivered_per(self, step: float) -> List[int]:
        """Frames the sink got in each ``step`` seconds of the window."""
        edges = np.minimum(self.t0 + step * np.arange(
            int(np.ceil(self.seconds / step)) + 1), self.t_end)
        return np.histogram([t for r in self.streams for t in r.sink.values()],
                            edges)[0].tolist()

    def admission_waits_ms(self) -> List[float]:
        return [(p - d) * 1e3 for r in self.streams
                for p, d in zip(r.t_pull, self._due_abs(r))]

    def tick_service_ms(self) -> List[float]:
        out = []
        for r in self.streams:
            for k in range(self.batch - 1, len(r.t_pull), self.batch):
                if k in r.sink:
                    out.append((r.sink[k] - r.t_pull[k]) * 1e3)
        return out


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def check_chip(chips: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < chips:
        raise NoChip(f"needs {chips} accelerator chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")


def device_info() -> Dict:
    import jax
    devs = jax.local_devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": jax.device_count(), "memory_peak_bytes": peak}


class CompileLog:
    """XLA backend compiles in this process: (end time, seconds)."""

    def __init__(self, clock=CLOCK):
        import jax
        self.events: List[tuple] = []
        self._clock = clock

        def on_event(name, secs, **kw):
            if name == "/jax/core/compile/backend_compile_duration":
                self.events.append((self._clock(), secs))
        self._cb = on_event
        jax.monitoring.register_event_duration_secs_listener(on_event)

    def between(self, a: float, b: float) -> int:
        return sum(1 for t, _ in self.events if a <= t <= b)

    def seconds(self, a: float, b: float) -> float:
        return sum(d for t, d in self.events if a <= t <= b)

    def close(self) -> None:
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._cb)


def _wire(pool: np.ndarray, io_dtype: str) -> np.ndarray:
    """The pool as the configuration's wire dtype (uint8 passes through)."""
    if io_dtype == "uint8":
        return pool
    import jax.numpy as jnp
    return (pool.astype(np.float32) / 255.0).astype(jnp.dtype(io_dtype))


def run(cell: str, seed: int, seconds: float, trace: bool, *,
        t_start: Optional[float] = None, require_chip: bool = True,
        overrides: Optional[Dict] = None, log=None) -> Dict:
    """Run ``cell`` once and return its result dict (the printed line).

    ``overrides`` replaces fields of the cell's mix or configuration
    (keys ``"mix"``, ``"config"``, ``"dehaze"``): the tests shrink a cell
    with it, and the control runs the program's bfloat16 path with it.
    Runs from the command line never pass it.
    """
    t_start = CLOCK() if t_start is None else t_start
    t_entry = CLOCK()
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    overrides = overrides or {}
    spec = cell_spec(cell)
    conf = {**config_spec(spec["config"]), **overrides.get("config", {})}
    conf["dehaze"] = {**conf["dehaze"], **overrides.get("dehaze", {})}
    mix = {**mix_spec(spec["traffic"]), **overrides.get("mix", {})}

    if require_chip:
        check_chip(int(spec["chips"]))
    import jax
    from jax.profiler import TraceAnnotation

    from bench import costs, frames, reference, trace_reduce
    from repro.core import DehazeConfig
    from repro.stream import ElasticServer, StreamRequest

    kind = traffic_kind(mix["kind"])
    rng = np.random.default_rng([seed, 1])
    plans = kind.schedule(mix, rng, seconds)
    n_streams, pool_n = len(plans), int(conf["pool_frames"])
    h, w = int(conf["height"]), int(conf["width"])
    offsets = (rng.permutation(pool_n)[:n_streams] if n_streams <= pool_n
               else rng.integers(0, pool_n, n_streams))
    dcfg = DehazeConfig(**conf["dehaze"]).validate()
    batch, lanes = int(mix["batch"]), int(mix["lanes"])
    compiles = CompileLog()

    t_pool, pool_times = CLOCK(), {}
    pool_u8 = frames.make_pool(seed, n_streams, pool_n, h, w, pool_times)
    pool = _wire(pool_u8, dcfg.io_dtype)
    t_pool = CLOCK() - t_pool

    def frame_of(s: int) -> Callable[[int], np.ndarray]:
        return lambda k: pool[s, (offsets[s] + k) % pool_n]

    srv = ElasticServer(dcfg, batch=batch,
                        timeout_s=float(mix["monitor_timeout_s"]))
    t_warm = CLOCK()
    warm = srv.serve_many(
        [StreamRequest(f"{WARMUP_ID}{i}",
                       [frame_of(i % n_streams)(k) for k in range(batch)])
         for i in range(lanes)],
        n_lanes=lanes, tick_overlap=True)
    t_warm = CLOCK() - t_warm
    if warm.overlap_ticks != warm.ticks or warm.ticks != 1:
        raise RuntimeError(f"warm-up took {warm.ticks} ticks, "
                           f"{warm.overlap_ticks} overlapped; expected 1, 1")

    records = [StreamRecord(f"s{i}", plan) for i, plan in enumerate(plans)]
    samplers = [Sampler(int(mix["check_frames_per_stream"]),
                        dcfg.update_period, seed, i)
                for i in range(n_streams)]
    by_sid = {r.sid: (r, smp) for r, smp in zip(records, samplers)}

    def sink(sid: str, fid: int, payload: np.ndarray) -> None:
        t = CLOCK()
        with TraceAnnotation("bench.sink"):
            rec, smp = by_sid[sid]
            rec.sink[fid] = t
            rec.order.append(fid)
            smp.offer(fid, payload)

    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = HOST_TRACER_LEVEL
        jax.profiler.start_trace(tmp, profiler_options=opts)
    t0 = CLOCK()
    t_end = t0 + seconds
    setup_s = t0 - t_start

    def window_span() -> None:
        with TraceAnnotation(trace_reduce.WINDOW_SPAN):
            time.sleep(max(0.0, t_end - CLOCK()))
    marker = threading.Thread(target=window_span, daemon=True)
    marker.start()
    requests = [StreamRequest(r.sid, feed(r, frame_of(i), t0, t_end, batch))
                for i, r in enumerate(records)]
    rep = srv.serve_many(requests, n_lanes=lanes, sink=sink, tick_overlap=True)
    t_done = CLOCK()
    marker.join()
    summary = None
    if trace:
        jax.profiler.stop_trace()
        summary = trace_reduce.summarize(
            trace_reduce.load(trace_reduce.find_xplane(tmp)))
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)
        if summary is None and require_chip:
            raise RuntimeError("the trace holds no device op in the window")
    dev = device_info()
    in_window = compiles.between(t0, t_end)
    compiles.close()

    ctx = RunContext(
        seconds=seconds, t0=t0, t_end=t_end, t_done=t_done, batch=batch,
        streams=records, report=rep, setup_s=setup_s,
        service_bytes=costs.service_bytes(
            h, w, *costs.frame_dtypes(conf["dehaze"])),
        device_kind=dev["kind"], trace=summary,
        latency_limit_ms=(float(mix["latency_limit_ms"])
                          if "latency_limit_ms" in mix else None))

    # -- what was asked for and what came back ---------------------------
    drain_cap = t_end + float(mix["drain_cap_s"])
    attempted = sum(len(r.due) if r.due is not None else len(r.t_pull)
                    for r in records)
    delivered = sum(1 for r in records for t in r.sink.values()
                    if t <= drain_cap)
    pulled = sum(len(r.t_pull) for r in records)
    lost = pulled - len([1 for r in records for _ in r.sink]) - rep.skipped
    order_bad = sum(1 for r in records
                    for a, b in zip(r.order, r.order[1:]) if b <= a)
    woke = [((p - max(c, t0 + d)) * 1e3, k, p - t0)
            for r in records if r.due is not None
            for k, (c, p, d) in enumerate(zip(r.t_call, r.t_pull, r.due))]
    log(f"compiles in set-up: {compiles.between(t_entry, t0)} taking "
        f"{compiles.seconds(t_entry, t0):.3f} s")
    log(f"setup: start to run() {t_entry - t_start:.3f} s, pool {t_pool:.3f} s "
        f"(compile {pool_times['compile_s']:.3f} s, run "
        f"{pool_times['run_s']:.3f} s, fetch {pool_times['fetch_s']:.3f} s), "
        f"warm serve_many (one tick) "
        f"{t_warm:.3f} s, total {setup_s:.3f} s")
    log(f"window: {seconds} s, compiles inside it: {in_window}")
    log(f"delivered per 5 s of the window: {ctx.delivered_per(5.0)}")
    log(f"frames: attempted {attempted} pulled {pulled} delivered "
        f"{delivered} (in window {ctx.delivered_in_window()}) skipped "
        f"{rep.skipped} ticks {rep.ticks} overlapped {rep.overlap_ticks} "
        f"stragglers {rep.stragglers}")
    limit_ms = ctx.latency_limit_ms
    if limit_ms is not None:
        late_n = ctx.late(limit_ms, drain_cap)
        log(f"latency limit {limit_ms:.4f} ms: {late_n} of {attempted} "
            f"frames delivered later ({100.0 * late_n / max(attempted, 1):.2f}"
            f" %): late, not failed")
    if woke:
        ms = [x[0] for x in woke]
        worst = sorted(woke, reverse=True)[:3]
        log("camera wake-up lateness ms: p50 {:.4f} p99 {:.4f} max {:.4f}; "
            "latest: {}".format(
                np.percentile(ms, 50), np.percentile(ms, 99), max(ms),
                ", ".join(f"frame {k} at +{t:.3f} s {m:.1f} ms"
                          for m, k, t in worst)))

    metrics = {}
    for m in metrics_for(benchmark(), cell, trace):
        value = metric_reader(m["name"]).read(
            ctx, m["name"].split(".", 1)[1] if "." in m["name"] else None)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # -- correctness: after the window, state freed, peak memory read -----
    del rep, warm, srv
    checks = compare(conf, spec, records, samplers, pool_u8, offsets, batch,
                     lost, order_bad, reference, log)
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(attempted - delivered),
              "metrics": metrics,
              "device": dev}
    if trace and summary is not None:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary.device_ops],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps]}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def compare(conf: Dict, spec: Dict, records: Sequence[StreamRecord],
            samplers: Sequence[Sampler], pool_u8: np.ndarray,
            offsets: np.ndarray, batch: int, lost: int, order_bad: int,
            reference, log) -> Dict[str, Dict]:
    """The numbers ``correct`` rests on, each with its limit."""
    p = {**conf["dehaze"]}
    pool_n = pool_u8.shape[1]

    def frames_at(streams, fids):
        return np.stack([pool_u8[s, (offsets[s] + f) % pool_n]
                         for s, f in zip(streams, fids)])

    picked = [smp.frames() for smp in samplers]
    need = [max(pk, default=-1) + 1 for pk in picked]
    a_saved, a_frame = reference.light_trajectory(frames_at, need, batch, p)
    keys = [(s, f) for s, pk in enumerate(picked) for f in sorted(pk)]
    gap = 0.0
    if keys:
        gap = reference.max_gap(
            [pool_u8[s, (offsets[s] + f) % pool_n] for s, f in keys],
            [a_saved[s][f] for s, f in keys],
            [a_frame[s][f] for s, f in keys],
            [picked[s][f] for s, f in keys], p)
    unchecked = sum(1 for pk in picked
                    if not any(f >= p["update_period"] for f in pk))
    log(f"frames compared with the reference: {len(keys)} over "
        f"{len(records)} streams")
    return {
        "streams_unchecked": {"value": unchecked, "limit": 0},
        "order_violations": {"value": order_bad, "limit": 0},
        "frames_lost": {"value": lost, "limit": 0},
        "J_max_abs_diff": {"value": gap,
                           "limit": spec["limits"]["J_max_abs_diff"]},
    }


# ---------------------------------------------------------------------------
# Command line
# ---------------------------------------------------------------------------

def parse(argv: Sequence[str]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        prog="bench/run.py", description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv: Sequence[str], t_start: float) -> int:
    args = parse(argv)
    if args.seed < 0:
        print("--seed must be a non-negative whole number", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro.core.env as env
        import repro.stream  # noqa: F401
    except ImportError as e:
        print(f"bench/run.py needs the program next to it (src/repro): {e}",
              file=sys.stderr)
        return 2
    try:
        spec = cell_spec(args.workload)
        check_chip(int(spec["chips"]))
    except (FileNotFoundError, NoChip) as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 1
    import jax
    env.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                 t_start=t_start)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
