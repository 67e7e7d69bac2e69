"""Consolidated ``REPRO_*`` environment resolution.

Every runtime knob the repo reads from the environment goes through one
typed, validated accessor here — call sites (`kernels.ops`,
`kernels.tuning`, `core.pipeline`, `stream.elastic`, the benchmark
drivers) never touch ``os.environ`` directly. Unknown or malformed values
raise ``ValueError`` (the ``resolve_mode`` precedent: a typo like
``REPRO_KERNEL_MODE=Pallas`` must not silently select a different code
path), with one documented exception: ``REPRO_TUNE_<OP>`` overrides are
best-effort performance hints, so malformed JSON there is ignored rather
than taking a serving fleet down over a tuning experiment.

Knobs:

  REPRO_KERNEL_MODE      execution substrate / pipeline mode override
  REPRO_LANE_NATIVE      force the lane-native megakernel on (1) or off (0)
  REPRO_TICK_OVERLAP     force the zero-copy overlapped serve tick path on
                         (1) or off (0; the blocking parity oracle)
  REPRO_STEP_CACHE_SIZE  bounded LRU size of the jitted-step cache
  REPRO_KERNEL_TUNING    path of the persisted kernel-tuning table
  REPRO_TUNE_<OP>        per-op JSON tile-parameter override
  REPRO_TUNE_DEVICE_KIND override the device-kind key tuned winners
                         persist/resolve under (CI validates foreign tables)
  REPRO_TUNE_REQUIRE_TABLE
                         when truthy, get_params raises if neither a table
                         entry nor an env override exists (no silent defaults)
  REPRO_BENCH_SMOKE      benchmark drivers use tiny CI shapes when truthy

JAX's own ``JAX_COMPILATION_CACHE_DIR`` is read here too
(:func:`compile_cache_dir`): the entry points turn the persistent compile
cache on through :func:`enable_compile_cache`, never at import.

``snapshot()`` / ``restore()`` capture and reinstate the full ``REPRO_*``
environment for test isolation (monkeypatch-free setup/teardown of
multi-knob scenarios).
"""
from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Any, Dict, Optional

# Execution substrates and pipeline-level modes (see ``kernels.ops``):
# "fused" selects the megakernel path, "auto" defers to the backend.
SUBSTRATES = ("ref", "pallas", "interpret")
KERNEL_MODES = SUBSTRATES + ("fused", "auto")

_TUNING_DEFAULT_PATH = Path("results") / "kernel_tuning.json"
# The checkout root (src/repro/core/env.py -> three levels up).
_CHECKOUT = Path(__file__).resolve().parents[3]


def kernel_mode() -> str:
    """``REPRO_KERNEL_MODE``: a mode from :data:`KERNEL_MODES`, or ``""``
    when unset. Unknown values raise."""
    env = os.environ.get("REPRO_KERNEL_MODE", "")
    if env and env not in KERNEL_MODES:
        raise ValueError(
            f"REPRO_KERNEL_MODE={env!r} is not a valid kernel mode; "
            f"expected one of {sorted(KERNEL_MODES)}, or unset it")
    return env


def lane_native() -> Optional[bool]:
    """``REPRO_LANE_NATIVE``: ``True`` (force lane-native), ``False``
    (force the vmapped path) or ``None`` when unset. Unknown values raise;
    the fused-coverage check the force implies lives with the config, in
    ``core.pipeline.resolve_lane_native``."""
    env = os.environ.get("REPRO_LANE_NATIVE", "")
    if env not in ("", "0", "1"):
        raise ValueError(
            f"REPRO_LANE_NATIVE={env!r} is not a valid override; expected "
            "'0' (force vmap), '1' (force lane-native) or unset")
    return None if env == "" else env == "1"


def tick_overlap() -> Optional[bool]:
    """``REPRO_TICK_OVERLAP``: ``True`` (force the zero-copy overlapped
    serve tick path), ``False`` (force the blocking path — the parity
    oracle) or ``None`` when unset. Unknown values raise. Whether forcing
    overlap on can actually be honored (device-resident staging needs
    ``jax.device_put`` + donation on the backend) is decided by
    ``stream.iobuf.donation_supported``; ``launch/serve.py`` turns a
    silent fallback into a hard failure under ``--expect-overlap``."""
    env = os.environ.get("REPRO_TICK_OVERLAP", "")
    if env not in ("", "0", "1"):
        raise ValueError(
            f"REPRO_TICK_OVERLAP={env!r} is not a valid override; expected "
            "'0' (force blocking), '1' (force overlap) or unset")
    return None if env == "" else env == "1"


def step_cache_size(default: int = 8) -> int:
    """``REPRO_STEP_CACHE_SIZE``: max entries in the bounded LRU jitted-step
    cache. Must parse as a positive integer."""
    env = os.environ.get("REPRO_STEP_CACHE_SIZE", "")
    if not env:
        return default
    try:
        size = int(env)
    except ValueError:
        raise ValueError(
            f"REPRO_STEP_CACHE_SIZE={env!r} is not an integer") from None
    if size < 1:
        raise ValueError(
            f"REPRO_STEP_CACHE_SIZE must be >= 1, got {size}")
    return size


def tuning_table_path() -> Path:
    """``REPRO_KERNEL_TUNING``: path of the persisted tuning table."""
    return Path(os.environ.get("REPRO_KERNEL_TUNING",
                               str(_TUNING_DEFAULT_PATH)))


def tune_override(op: str) -> Dict[str, Any]:
    """``REPRO_TUNE_<OP>``: JSON object of tile-parameter overrides for
    ``op``, ``{}`` when unset. Malformed JSON (or a non-object) is
    *ignored* — tuning overrides are performance hints, never allowed to
    turn a typo into a serving outage (unlike the mode knobs above)."""
    env = os.environ.get(f"REPRO_TUNE_{op.upper()}")
    if not env:
        return {}
    try:
        params = json.loads(env)
    except ValueError:
        return {}
    return params if isinstance(params, dict) else {}


def tune_device_kind() -> str:
    """``REPRO_TUNE_DEVICE_KIND``: overrides the device-kind key measured
    tuning winners persist (and resolve) under, ``""`` when unset — the
    hardware answer ``jax.devices()[0].device_kind`` then applies. Used by
    CI to validate a table tuned for foreign hardware without owning it."""
    return os.environ.get("REPRO_TUNE_DEVICE_KIND", "")


def tune_require_table() -> bool:
    """``REPRO_TUNE_REQUIRE_TABLE``: when set, ``tuning.get_params`` raises
    for lookups that found neither a measured table entry nor an env
    override — serving fleets opt in to "real measurements only" instead
    of silently running the built-in defaults. '0'/'1' or unset."""
    env = os.environ.get("REPRO_TUNE_REQUIRE_TABLE", "")
    if env not in ("", "0", "1"):
        raise ValueError(
            f"REPRO_TUNE_REQUIRE_TABLE={env!r} is not a valid value; "
            "expected '0', '1' or unset")
    return env == "1"


def bench_smoke() -> bool:
    """``REPRO_BENCH_SMOKE``: benchmark drivers shrink to CI smoke shapes
    when set to anything non-empty."""
    return bool(os.environ.get("REPRO_BENCH_SMOKE"))


def compile_cache_dir() -> Path:
    """Directory of JAX's persistent compilation cache:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` in the
    checkout. The path is part of the cache key, so it is fixed: never a
    temporary name, a pid or the time."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    return Path(env) if env else _CHECKOUT / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn the persistent compilation cache on for an entry point
    (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``) and
    return its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX
    already uses it and nothing is changed; otherwise the cache goes to
    :func:`compile_cache_dir`. Tests never call this."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path


# ---------------------------------------------------------------------------
# Test isolation
# ---------------------------------------------------------------------------

def snapshot() -> Dict[str, str]:
    """Current values of every ``REPRO_*`` variable (for :func:`restore`)."""
    return {k: v for k, v in os.environ.items() if k.startswith("REPRO_")}


def restore(snap: Dict[str, str]) -> None:
    """Reinstate a :func:`snapshot`: variables added since are removed,
    changed ones reset — the inverse of any ``REPRO_*`` mutation batch."""
    for k in [k for k in os.environ if k.startswith("REPRO_")]:
        if k not in snap:
            del os.environ[k]
    os.environ.update(snap)


__all__ = ["SUBSTRATES", "KERNEL_MODES", "kernel_mode", "lane_native",
           "tick_overlap",
           "step_cache_size", "tuning_table_path", "tune_override",
           "tune_device_kind", "tune_require_table", "bench_smoke",
           "compile_cache_dir", "enable_compile_cache", "snapshot", "restore"]
