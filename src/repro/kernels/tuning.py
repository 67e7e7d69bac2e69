"""Measured-search autotuner + device-kind-keyed tile-parameter tables.

Tile parameters (frames-per-block for the fused megakernel, DMA ring
depth, the lane-native grid order, row-tile height for the atmolight
reduction) are resolved per (op, shape-bucket) through a layered lookup,
highest priority first:

  1. env override    ``REPRO_TUNE_<OP>`` — a JSON object, e.g.
                     ``REPRO_TUNE_FUSED_DCP='{"frames_per_block": 4}'``
  2. measured table  the entry for the *current device kind*
                     (``jax.devices()[0].device_kind``, override with
                     ``REPRO_TUNE_DEVICE_KIND``) in the persisted JSON
                     table, default ``results/kernel_tuning.json``
                     (path override ``REPRO_KERNEL_TUNING``); within a
                     device kind the dtype-tagged bucket (``…xu8``)
                     layers over the untagged f32 bucket
  3. legacy table    pre-schema-2 tables had no device-kind key; their
                     entries still load, *below* any device-kind entry —
                     a table tuned on a TPU pod can no longer be silently
                     resolved as-if-measured by CPU CI (or vice versa)
  4. built-in default

``REPRO_TUNE_REQUIRE_TABLE=1`` turns a resolution that found neither a
table entry nor an env override into an error — serving fleets use it to
insist on real measurements instead of the built-in defaults.

The autotuner is a **measured search**: :func:`measured_search` runs
successive halving (eta = 3) over the joint candidate space — the whole
population is timed at ``start_iters`` timing iterations, only the
fastest third survives each rung at a tripled iteration count (capped at
``iters``) — so the total timed runs are provably below the exhaustive
``len(candidates) × iters`` product for every ``iters >= 2`` (each rung
costs at most ``N × start_iters`` runs and there are strictly fewer than
``iters`` rungs), while the winner matches the exhaustive sweep whenever
the candidate ranking is fidelity-stable (the best candidate ranks first
at every rung, and ``keep >= 1`` never prunes rank 1). Winners persist
under ``{device_kinds: {kind: {op: {bucket: {params, provenance}}}}}``
with per-entry provenance (time measured, iters, candidates
considered/skipped, method). A one-off
``python -m repro.kernels.tuning --search`` on the target hardware bakes
real measurements into the table every later run picks up; ``--validate``
checks a committed table's schema/provenance in CI.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from pathlib import Path
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax

from repro.core import env as _env

DEFAULTS: Dict[str, Dict[str, Any]] = {
    # Per-algorithm buckets: CAP's pre-map (HSV depth, no divide-by-A) has a
    # different VMEM/FLOP profile, so its sweet spot is tuned separately.
    # ``buffer_depth`` is the manual-DMA input ring depth of the
    # double-buffered megakernel body (1 = classic automatic BlockSpec
    # pipeline; 2 = copy of block n+1 overlaps compute on block n). The
    # dispatch layer clamps it to 1 on the interpret substrate.
    "fused_dcp": {"frames_per_block": 1, "buffer_depth": 2},
    "fused_cap": {"frames_per_block": 1, "buffer_depth": 2},
    # Robust top-k A estimator (k > 1): the in-VMEM k-step running
    # selection adds compute per frame, so its tile is tuned apart from
    # the argmin (k=1) kernels.
    "fused_dcp_topk": {"frames_per_block": 1, "buffer_depth": 2},
    "fused_cap_topk": {"frames_per_block": 1, "buffer_depth": 2},
    # Spatially-sharded (H and/or W) halo megakernel: per-shard blocks are
    # smaller than full frames, so more of them fit one grid step.
    "fused_halo_2d": {"frames_per_block": 1, "buffer_depth": 2},
    # Lane-native multi-stream megakernel: the (lane, batch-block) grid
    # order trades carry-row locality (lane-major streams one lane's
    # whole batch) against output-tile locality (frame-major interleaves
    # lanes per block); the shape key includes the lane count, so the
    # frames_per_block x L product is swept per serving shape.
    "fused_lanes": {"frames_per_block": 1, "grid_order": "lane_major",
                    "buffer_depth": 2},
    "atmolight": {"tile_h": 0},          # 0 = whole frame per grid step
    "atmolight_topk": {"tile_h": 0},     # k-row grid-carry fold tile
}

# Persisted-table schema version. Version 2 keys entries by device kind
# and wraps each winner as {"params", "provenance"}; version-1 tables
# (bare {op: {bucket: params}}) still load through the legacy layer.
SCHEMA_VERSION = 2


class AutotuneError(RuntimeError):
    """Every candidate in an autotune sweep failed to build/run.

    Raised instead of persisting the built-in DEFAULTS as a "measured
    winner" (the pre-schema-2 behavior: ``best_t`` never left ``inf``, so
    a sweep whose every candidate raised — wrong shapes, VMEM overflow —
    silently wrote the defaults into the table with full measured
    authority)."""


def table_path() -> Path:
    return _env.tuning_table_path()


_HW_DEVICE_KIND: Optional[str] = None


def device_kind() -> str:
    """The device-kind key measured winners persist (and resolve) under.

    ``REPRO_TUNE_DEVICE_KIND`` overrides (checked per call — CI validates
    foreign tables this way); the hardware answer
    (``jax.devices()[0].device_kind``, e.g. ``"cpu"``, ``"TPU v5 lite"``) is
    cached for the process, since ``get_params`` sits on the eager
    per-batch dispatch path."""
    env = _env.tune_device_kind()
    if env:
        return env
    global _HW_DEVICE_KIND
    if _HW_DEVICE_KIND is None:
        _HW_DEVICE_KIND = str(jax.devices()[0].device_kind)
    return _HW_DEVICE_KIND


# Wire-dtype tags for non-f32 frame streams. The f32 bucket key stays the
# bare shape (back-compat with every committed/persisted table); uint8 and
# bf16 streams get their own buckets because the HBM-traffic profile — and
# therefore the optimal tile/buffer depth — changes with bytes/frame.
_DTYPE_TAGS = {"uint8": "u8", "bfloat16": "bf16"}


def shape_bucket(shape: Iterable[int], dtype=None) -> str:
    key = "x".join(str(int(s)) for s in shape)
    tag = _DTYPE_TAGS.get(jax.numpy.dtype(dtype).name) \
        if dtype is not None else None
    return f"{key}x{tag}" if tag else key


# (path, mtime) -> parsed table. get_params sits on the per-batch dispatch
# path, so eager (non-jitted) streaming must not pay a disk read per frame.
_TABLE_CACHE: Dict[str, tuple] = {}


def load_table(path: Optional[Path] = None) -> Dict[str, Any]:
    p = path or table_path()
    key = str(p)
    try:
        mtime = os.stat(p).st_mtime_ns
    except OSError:
        _TABLE_CACHE[key] = (None, {})
        return {}
    cached = _TABLE_CACHE.get(key)
    if cached and cached[0] == mtime:
        return cached[1]
    try:
        with open(p) as f:
            table = json.load(f)
    except (OSError, ValueError):
        table = {}
    _TABLE_CACHE[key] = (mtime, table)
    return table


def save_table(table: Dict[str, Any], path: Optional[Path] = None) -> Path:
    p = path or table_path()
    p.parent.mkdir(parents=True, exist_ok=True)
    with open(p, "w") as f:
        json.dump(table, f, indent=2, sort_keys=True)
    # Update the cache directly: mtime granularity can be coarser than a
    # save-then-load round trip within one process.
    _TABLE_CACHE[str(p)] = (os.stat(p).st_mtime_ns, table)
    return p


# ---------------------------------------------------------------------------
# Schema-2 table layout + legacy migration
# ---------------------------------------------------------------------------

_RESERVED_KEYS = ("schema", "device_kinds", "legacy")


def migrate_table(table: Dict[str, Any]) -> Dict[str, Any]:
    """Normalize any loaded table to the schema-2 layout.

    A version-1 table is a bare ``{op: {bucket: params}}`` mapping with no
    record of what hardware measured it; migration moves those ops under
    the ``"legacy"`` section (NOT under the current device kind — claiming
    a foreign table as locally measured is exactly the bug the device-kind
    key fixes) and leaves ``device_kinds`` for real measurements."""
    if table.get("schema") == SCHEMA_VERSION:
        return table
    legacy_ops = {k: v for k, v in table.items() if k not in _RESERVED_KEYS}
    return {"schema": SCHEMA_VERSION,
            "device_kinds": dict(table.get("device_kinds", {})),
            "legacy": {**table.get("legacy", {}), **legacy_ops}}


def _entry_params(entry: Dict[str, Any]) -> Dict[str, Any]:
    """A table entry's params: schema-2 entries wrap them as
    ``{"params": ..., "provenance": ...}``, legacy entries are bare."""
    if "params" in entry and isinstance(entry["params"], dict):
        return entry["params"]
    return entry


def _table_layers(table: Dict[str, Any], kind: str
                  ) -> List[Dict[str, Dict[str, Any]]]:
    """``{op: {bucket: entry}}`` mappings lowest-priority first: the
    legacy (untagged-by-device) section, then the current device kind's."""
    if table.get("schema") == SCHEMA_VERSION or "device_kinds" in table:
        legacy = table.get("legacy", {})
        kinds = table.get("device_kinds", {})
    else:                                   # version-1 file, unmigrated
        legacy = {k: v for k, v in table.items() if k not in _RESERVED_KEYS}
        kinds = {}
    return [legacy, kinds.get(kind, {})]


def get_params(op: str, shape: Iterable[int], dtype=None) -> Dict[str, Any]:
    """Resolved tile params for ``op`` at ``shape``.

    Layering (see module docstring): env override > the current device
    kind's table entry > legacy (device-untagged) table entry > built-in
    default; within each table layer the wire-dtype-tagged bucket
    (``…xu8`` / ``…xbf16``) overrides the untagged f32 bucket for the
    keys it sets, so a uint8 toggle can never silently reuse an f32-tuned
    tile, and a CPU process can never silently treat a TPU pod's
    measurements as its own (or vice versa).

    With ``REPRO_TUNE_REQUIRE_TABLE=1`` a lookup that found neither a
    table entry nor an env override raises — production serving opts in
    to "real measurements only" instead of silently running defaults."""
    params = dict(DEFAULTS.get(op, {}))
    table = load_table()
    buckets = [shape_bucket(shape)]
    tagged = shape_bucket(shape, dtype)
    if tagged != buckets[0]:
        buckets.append(tagged)
    found = False
    for layer in _table_layers(table, device_kind()):
        entries = layer.get(op, {})
        for bucket in buckets:
            entry = entries.get(bucket)
            if entry:
                params.update(_entry_params(entry))
                found = True
    override = _env.tune_override(op)       # malformed override -> ignored
    params.update(override)
    if not found and not override and _env.tune_require_table():
        raise AutotuneError(
            f"REPRO_TUNE_REQUIRE_TABLE is set but no measured table entry "
            f"(device kind {device_kind()!r}, buckets {buckets}) or env "
            f"override exists for op {op!r} — run "
            f"`python -m repro.kernels.tuning --search` on this hardware")
    return params


def validate_table(table: Optional[Dict[str, Any]] = None,
                   path: Optional[Path] = None) -> List[str]:
    """Schema/provenance lint for a persisted table; returns error strings.

    Checks: schema version, known op names, bucket-key grammar, wrapped
    ``{params, provenance}`` entries under ``device_kinds`` with the
    required provenance fields, bare param dicts under ``legacy``."""
    import re
    if table is None:
        table = load_table(path)
    errors: List[str] = []
    if not table:
        return ["table is empty or unreadable"]
    if table.get("schema") != SCHEMA_VERSION:
        return [f"schema={table.get('schema')!r}, expected {SCHEMA_VERSION} "
                "(legacy tables load at runtime but do not validate — "
                "regenerate with `python -m repro.kernels.tuning --search`)"]
    bucket_re = re.compile(r"^\d+(x\d+)*(xu8|xbf16)?$")
    required_prov = ("time_us", "iters", "considered", "skipped", "method")
    kinds = table.get("device_kinds")
    if not isinstance(kinds, dict) or not kinds:
        errors.append("device_kinds section missing or empty")
        kinds = {}
    for kind, ops_map in kinds.items():
        for op, entries in ops_map.items():
            if op not in DEFAULTS:
                errors.append(f"{kind}/{op}: unknown op")
            for bucket, entry in entries.items():
                where = f"{kind}/{op}/{bucket}"
                if not bucket_re.match(bucket):
                    errors.append(f"{where}: malformed bucket key")
                if not isinstance(entry, dict) \
                        or not isinstance(entry.get("params"), dict):
                    errors.append(f"{where}: entry must wrap a params dict")
                    continue
                prov = entry.get("provenance")
                if not isinstance(prov, dict):
                    errors.append(f"{where}: missing provenance")
                    continue
                for field in required_prov:
                    if field not in prov:
                        errors.append(f"{where}: provenance lacks {field!r}")
    for op, entries in table.get("legacy", {}).items():
        if op not in DEFAULTS:
            errors.append(f"legacy/{op}: unknown op")
        for bucket, entry in entries.items():
            if not bucket_re.match(bucket):
                errors.append(f"legacy/{op}/{bucket}: malformed bucket key")
            if not isinstance(entry, dict):
                errors.append(f"legacy/{op}/{bucket}: not a param dict")
    return errors


# ---------------------------------------------------------------------------
# Measurement + search core
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TuneStats:
    """Cost ledger for one or more autotune calls (accumulates).

    ``timed_runs`` counts executions inside timing loops (the search's
    cost unit); ``builds`` counts candidate build+warm compiles;
    ``exhaustive_runs`` is the ``len(candidates) × iters`` product the
    exhaustive sweep would have timed over the same calls — the measured
    search's headline claim is ``timed_runs < exhaustive_runs``."""
    builds: int = 0
    timed_runs: int = 0
    rounds: int = 0
    considered: int = 0
    exhaustive_runs: int = 0
    skipped: Dict[str, int] = dataclasses.field(default_factory=dict)

    def record_skip(self, exc: BaseException) -> None:
        name = type(exc).__name__
        self.skipped[name] = self.skipped.get(name, 0) + 1


def _time_callable(fn: Callable[[], Any], iters: int = 3,
                   timer: Callable[[], float] = time.perf_counter,
                   warm: bool = True,
                   stats: Optional[TuneStats] = None) -> float:
    if warm:
        jax.block_until_ready(fn())          # compile + warm
    t0 = timer()
    out = None
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    if stats is not None:
        stats.timed_runs += iters
    return (timer() - t0) / iters


def _provenance(best_t: float, iters: int, considered: int,
                skipped: Dict[str, int], method: str) -> Dict[str, Any]:
    return {"time_us": round(best_t * 1e6, 3), "iters": iters,
            "considered": considered, "skipped": skipped,
            "method": method, "device_kind": device_kind(),
            "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}


def _stats_delta(stats: TuneStats, c0: int, skip0: Dict[str, int]
                 ) -> Tuple[int, Dict[str, int]]:
    """This call's own considered/skipped counts — callers share one
    accumulating :class:`TuneStats` across ops, but each persisted entry's
    provenance must describe only its own sweep."""
    skipped = {k: v - skip0.get(k, 0) for k, v in stats.skipped.items()
               if v - skip0.get(k, 0)}
    return stats.considered - c0, skipped


def _persist_winner(op: str, shape: Iterable[int], dtype,
                    params: Dict[str, Any],
                    provenance: Dict[str, Any]) -> None:
    table = migrate_table(load_table())
    table["device_kinds"].setdefault(device_kind(), {}).setdefault(op, {})[
        shape_bucket(shape, dtype)] = {"params": params,
                                       "provenance": provenance}
    save_table(table)


def _build_pool(op: str, shape, dtype, candidates, build,
                stats: TuneStats) -> List[Tuple[Dict[str, Any], Callable]]:
    """Build + warm every candidate once; callables are reused across
    measurement rungs. All-fail raises instead of letting a caller
    persist DEFAULTS as a measured winner."""
    pool: List[Tuple[Dict[str, Any], Callable]] = []
    for params in candidates:
        stats.considered += 1
        try:
            fn = build(params)
            jax.block_until_ready(fn())      # compile + warm
        except Exception as e:               # non-dividing tile, VMEM OOM...
            stats.record_skip(e)
            continue
        stats.builds += 1
        pool.append((dict(params), fn))
    if not pool:
        raise AutotuneError(
            f"autotune({op!r}, bucket {shape_bucket(shape, dtype)!r}): all "
            f"{stats.considered} candidates failed to build/run "
            f"(skipped by exception type: {stats.skipped}) — refusing to "
            "persist the built-in defaults as a measured winner")
    return pool


def autotune(op: str, shape: Iterable[int],
             candidates: Iterable[Dict[str, Any]],
             build: Callable[[Dict[str, Any]], Callable[[], Any]],
             iters: int = 3, persist: bool = True, dtype=None,
             timer: Callable[[], float] = time.perf_counter,
             stats: Optional[TuneStats] = None) -> Dict[str, Any]:
    """Exhaustive sweep: every candidate timed at full ``iters``.

    Kept as the measured search's baseline (the cost-comparison bench row
    and the same-winner differential test run both); candidates whose
    build or execution raises are skipped *and recorded* in
    ``stats.skipped`` by exception type. If every candidate raises, the
    sweep raises :class:`AutotuneError` — it never persists the built-in
    DEFAULTS as a measured winner. ``dtype`` routes the persisted winner
    into the wire-dtype-tagged bucket (see :func:`shape_bucket`)."""
    stats = stats if stats is not None else TuneStats()
    c0, skip0 = stats.considered, dict(stats.skipped)
    pool = _build_pool(op, shape, dtype, candidates, build, stats)
    stats.exhaustive_runs += len(pool) * iters
    best, best_t = None, float("inf")
    for params, fn in pool:
        try:
            t = _time_callable(fn, iters=iters, timer=timer, warm=False,
                               stats=stats)
        except Exception as e:
            stats.record_skip(e)
            continue
        if t < best_t:
            best, best_t = params, t
    stats.rounds += 1
    if best is None:
        raise AutotuneError(
            f"autotune({op!r}): every candidate raised during timing "
            f"(skipped: {stats.skipped}); not persisting")
    if persist:
        considered, skipped = _stats_delta(stats, c0, skip0)
        _persist_winner(op, shape, dtype, best,
                        _provenance(best_t, iters, considered, skipped,
                                    "exhaustive"))
    return best


def measured_search(op: str, shape: Iterable[int],
                    candidates: Iterable[Dict[str, Any]],
                    build: Callable[[Dict[str, Any]], Callable[[], Any]],
                    iters: int = 3, start_iters: int = 1, eta: int = 3,
                    persist: bool = True, dtype=None,
                    timer: Callable[[], float] = time.perf_counter,
                    stats: Optional[TuneStats] = None) -> Dict[str, Any]:
    """Successive-halving measured search over ``candidates``.

    Rung 0 times the whole population at ``start_iters`` timing
    iterations; each later rung keeps the fastest ``1/eta`` of the
    survivors (never fewer than one) and multiplies the iteration count
    by ``eta``, capped at ``iters``. The search stops at the first rung
    measured at the cap — or as soon as one survivor remains — so its
    total timed runs stay strictly below the exhaustive
    ``len(candidates) × iters`` product whenever ``iters >= 2``: rung r
    costs at most ``N / eta^r × start_iters·eta^r = N·start_iters`` runs
    and there are strictly fewer than ``iters`` rungs.

    On a deterministic timer whose candidate ranking is independent of
    the iteration count, the winner equals the exhaustive sweep's: the
    fastest candidate ranks first at every rung, survives every cut, and
    ties break toward the earlier candidate in both (stable sort here,
    strict ``<`` there). Failures during timing are recorded per
    exception type; an all-fail population raises :class:`AutotuneError`
    and persists nothing."""
    if iters < 1 or start_iters < 1 or eta < 2:
        raise ValueError(f"need iters/start_iters >= 1 and eta >= 2, got "
                         f"iters={iters} start_iters={start_iters} eta={eta}")
    stats = stats if stats is not None else TuneStats()
    c0, skip0 = stats.considered, dict(stats.skipped)
    pool = _build_pool(op, shape, dtype, candidates, build, stats)
    stats.exhaustive_runs += len(pool) * iters
    it = min(start_iters, iters)
    best, best_t = None, float("inf")
    while True:
        scored: List[Tuple[float, Dict[str, Any], Callable]] = []
        for params, fn in pool:
            try:
                t = _time_callable(fn, iters=it, timer=timer, warm=False,
                                   stats=stats)
            except Exception as e:
                stats.record_skip(e)
                continue
            scored.append((t, params, fn))
        stats.rounds += 1
        if not scored:
            raise AutotuneError(
                f"measured_search({op!r}): every surviving candidate raised "
                f"during timing (skipped: {stats.skipped}); not persisting")
        scored.sort(key=lambda s: s[0])      # stable: ties keep seed order
        best_t, best = scored[0][0], scored[0][1]
        if it >= iters or len(scored) == 1:
            break
        keep = max(1, len(scored) // eta)
        pool = [(p, f) for _, p, f in scored[:keep]]
        if len(pool) == 1:                   # decided — skip the re-measure
            break
        it = min(iters, it * eta)
    if persist:
        considered, skipped = _stats_delta(stats, c0, skip0)
        _persist_winner(op, shape, dtype, best,
                        _provenance(best_t, it, considered, skipped,
                                    "successive_halving"))
    return best


def _tune(method: str):
    """Driver dispatch: ``"search"`` (the default measured search) or
    ``"exhaustive"`` (the legacy full sweep, kept as baseline)."""
    if method == "search":
        return measured_search
    if method == "exhaustive":
        return autotune
    raise ValueError(f"method must be 'search' or 'exhaustive', "
                     f"got {method!r}")


def autotune_fused(shapes=((4, 48, 64), (2, 120, 160)),
                   candidates=(1, 2, 4), iters: int = 3, persist: bool = True,
                   algorithms=("dcp", "cap"), topks=(1, 4),
                   depths=(1, 2, 3), io_dtypes=("float32", "uint8"),
                   method: str = "search",
                   stats: Optional[TuneStats] = None) -> Dict[str, Any]:
    """Search ``frames_per_block`` x ``buffer_depth`` for the fused
    megakernels, per algorithm, per A-estimator (argmin vs robust top-k),
    and per frame wire dtype (f32 vs uint8 ingest — different bytes/frame,
    different overlap sweet spot; winners persist into dtype-tagged
    buckets under the current device kind).

    Uses the dispatch layer, so it times whatever substrate the current
    backend resolves to (Pallas on TPU, the XLA oracle on CPU). Each
    (algorithm, estimator) pair persists into its own bucket:
    ``fused_<algorithm>`` for topk=1, ``fused_<algorithm>_topk`` for k>1.
    ``method="search"`` runs :func:`measured_search` per bucket (cost
    strictly below the exhaustive candidates x depths x iters product);
    pass a shared :class:`TuneStats` to read the totals back.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    tune = _tune(method)
    table: Dict[str, Any] = {}
    for algorithm in algorithms:
        for topk in topks:
            op = f"fused_{algorithm}" + ("_topk" if topk > 1 else "")
            table.setdefault(op, {})
            for io_dtype in io_dtypes:
                for b, h, w in shapes:
                    r = np.random.default_rng(0)
                    frames = r.random((b, h, w, 3), np.float32)
                    img = jnp.asarray(ref.quantize_frames(frames, io_dtype))
                    ids = jnp.arange(b, dtype=jnp.int32)
                    A = jnp.ones((3,), jnp.float32)
                    k0 = jnp.asarray(-(2 ** 30), jnp.int32)
                    init = jnp.asarray(False)

                    def build(params):
                        def run():
                            return ops.fused_dehaze(
                                img, ids, A, k0, init, algorithm=algorithm,
                                radius=7, omega=0.95, refine=True,
                                gf_radius=8, gf_eps=1e-3, t0=0.1, gamma=1.0,
                                period=8, lam=0.05, topk=topk,
                                frames_per_block=params["frames_per_block"],
                                buffer_depth=params["buffer_depth"])
                        return run

                    table[op][shape_bucket((b, h, w), img.dtype)] = tune(
                        op, (b, h, w),
                        [{"frames_per_block": f, "buffer_depth": d}
                         for f in candidates for d in depths],
                        build, iters=iters, persist=persist, dtype=img.dtype,
                        stats=stats)
    return table


def autotune_fused_lanes(shapes=((4, 4, 48, 64), (16, 2, 48, 64)),
                         fpb_candidates=(1, 2, 4),
                         orders=("lane_major", "frame_major"),
                         depths=(1, 2, 3),
                         iters: int = 3, persist: bool = True,
                         method: str = "search",
                         stats: Optional[TuneStats] = None) -> Dict[str, Any]:
    """Search the lane-native megakernel's joint grid space:
    ``frames_per_block`` x grid order (lane-major vs frame-major) x DMA
    ``buffer_depth``, per ``(L, B, H, W)`` serving shape, into the
    ``fused_lanes`` bucket of the current device kind's table.

    Uses the dispatch layer, so it times whatever substrate the backend
    resolves to — run on the serving pod to bake in real measurements.
    One lane is all-padding (ids -1), matching a typical partially
    occupied fleet tick.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    tune = _tune(method)
    table: Dict[str, Any] = {"fused_lanes": {}}
    for n_lanes, b, h, w in shapes:
        r = np.random.default_rng(0)
        img = jnp.asarray(r.random((n_lanes, b, h, w, 3), np.float32))
        ids = jnp.stack(
            [jnp.arange(b, dtype=jnp.int32)] * (n_lanes - 1)
            + [jnp.full((b,), -1, jnp.int32)])
        carry_f = jnp.ones((n_lanes, 3), jnp.float32)
        carry_i = jnp.stack([jnp.full((n_lanes,), -(2 ** 30), jnp.int32),
                             jnp.zeros((n_lanes,), jnp.int32)], axis=-1)

        def build(params):
            def run():
                return ops.fused_dehaze_lanes(
                    img, ids, carry_f, carry_i, algorithm="dcp", radius=7,
                    omega=0.95, refine=True, gf_radius=8, gf_eps=1e-3,
                    t0=0.1, gamma=1.0, period=8, lam=0.05,
                    frames_per_block=params["frames_per_block"],
                    lane_major=(params["grid_order"] == "lane_major"),
                    buffer_depth=params["buffer_depth"])
            return run

        table["fused_lanes"][shape_bucket((n_lanes, b, h, w))] = tune(
            "fused_lanes", (n_lanes, b, h, w),
            [{"frames_per_block": f, "grid_order": o, "buffer_depth": d}
             for f in fpb_candidates for o in orders for d in depths],
            build, iters=iters, persist=persist, stats=stats)
    return table


def autotune_fused_halo(shapes=((4, 24, 64), (2, 60, 160)), halo=23,
                        candidates=(1, 2, 4), depths=(1, 2, 3),
                        iters: int = 3, persist: bool = True,
                        method: str = "search",
                        stats: Optional[TuneStats] = None) -> Dict[str, Any]:
    """Search ``frames_per_block`` x ``buffer_depth`` for the
    spatially-sharded halo megakernel (``fused_halo_2d`` bucket) on
    representative per-shard block shapes."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    tune = _tune(method)
    table: Dict[str, Any] = {"fused_halo_2d": {}}
    for b, h_loc, w in shapes:
        r = np.random.default_rng(0)
        img = jnp.asarray(r.random((b, h_loc, w, 3), np.float32))
        pre = jnp.asarray(r.random((b, h_loc + 2 * halo, w), np.float32))
        guide = jnp.asarray(r.random((b, h_loc + 2 * halo, w), np.float32))
        valid = jnp.arange(h_loc + 2 * halo) >= halo      # top-edge shard

        def build(params):
            def run():
                return ops.fused_transmission_halo(
                    img, pre, guide, valid, algorithm="dcp", radius=7,
                    omega=0.95, refine=True, gf_radius=8, gf_eps=1e-3,
                    frames_per_block=params["frames_per_block"],
                    buffer_depth=params["buffer_depth"])
            return run

        table["fused_halo_2d"][shape_bucket((b, h_loc, w))] = tune(
            "fused_halo_2d", (b, h_loc, w),
            [{"frames_per_block": f, "buffer_depth": d}
             for f in candidates for d in depths],
            build, iters=iters, persist=persist, stats=stats)
    return table


# ---------------------------------------------------------------------------
# CLI: generate / validate per-hardware tables
# ---------------------------------------------------------------------------

_SMOKE = dict(shapes=((2, 8, 8),), lanes_shapes=((2, 2, 8, 8),),
              halo_shapes=((2, 8, 16),), halo=3, io_dtypes=("float32",),
              algorithms=("dcp",), topks=(1,), iters=2)


def run_search(smoke: bool = False, iters: Optional[int] = None,
               persist: bool = True, method: str = "search"
               ) -> Tuple[Dict[str, Any], TuneStats]:
    """Run all three drivers; returns (merged winner table, cost stats)."""
    stats = TuneStats()
    kw: Dict[str, Any] = dict(method=method, persist=persist, stats=stats)
    if iters is not None:
        kw["iters"] = iters
    if smoke:
        kw.setdefault("iters", _SMOKE["iters"])
        out = autotune_fused(shapes=_SMOKE["shapes"],
                             algorithms=_SMOKE["algorithms"],
                             topks=_SMOKE["topks"],
                             io_dtypes=_SMOKE["io_dtypes"], **kw)
        out.update(autotune_fused_lanes(shapes=_SMOKE["lanes_shapes"], **kw))
        out.update(autotune_fused_halo(shapes=_SMOKE["halo_shapes"],
                                       halo=_SMOKE["halo"], **kw))
    else:
        out = autotune_fused(**kw)
        out.update(autotune_fused_lanes(**kw))
        out.update(autotune_fused_halo(**kw))
    return out, stats


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Measured-search kernel autotuner: persists winners "
                    "into the device-kind-keyed tuning table")
    ap.add_argument("--search", action="store_true",
                    help="run the successive-halving measured search "
                         "(the default action)")
    ap.add_argument("--exhaustive", action="store_true",
                    help="run the legacy exhaustive sweep instead")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + low iters (CI): also exits nonzero "
                         "unless the search timed strictly fewer runs than "
                         "the exhaustive candidates x iters product")
    ap.add_argument("--iters", type=int, default=None,
                    help="full-fidelity timing iterations (default 3; "
                         "smoke default 2)")
    ap.add_argument("--no-persist", action="store_true",
                    help="measure only; do not write the table")
    ap.add_argument("--validate", action="store_true",
                    help="validate the persisted table's schema/provenance "
                         "and exit")
    ap.add_argument("--require-kind", default=None,
                    help="with --validate: fail unless this device kind "
                         "has measured entries in the table")
    args = ap.parse_args(argv)

    if args.validate:
        table = load_table()
        errors = validate_table(table)
        kinds = sorted(table.get("device_kinds", {}))
        if args.require_kind and args.require_kind not in kinds:
            errors.append(f"required device kind {args.require_kind!r} has "
                          f"no measured entries (kinds present: {kinds})")
        print(json.dumps({"path": str(table_path()), "device_kinds": kinds,
                          "errors": errors}, indent=2))
        return 1 if errors else 0

    method = "exhaustive" if args.exhaustive else "search"
    out, stats = run_search(smoke=args.smoke, iters=args.iters,
                            persist=not args.no_persist, method=method)
    summary = {**out, "path": str(table_path()),
               "device_kind": device_kind(), "method": method,
               "stats": dataclasses.asdict(stats)}
    print(json.dumps(summary, indent=2))
    if args.smoke and method == "search" \
            and stats.timed_runs >= stats.exhaustive_runs:
        print(f"FAIL: measured search timed {stats.timed_runs} runs, not "
              f"fewer than the exhaustive product {stats.exhaustive_runs}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
