"""The single env-knob surface (repro.core.env): typed accessors that
reject junk values loudly, plus snapshot/restore for test isolation.

Every ``REPRO_*`` read in the codebase goes through this module — a
regression test greps the source tree to keep it that way.
"""
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core import env

SRC = Path(__file__).resolve().parent.parent / "src"


def test_kernel_mode(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
    assert env.kernel_mode() == ""
    for v in env.KERNEL_MODES:
        monkeypatch.setenv("REPRO_KERNEL_MODE", v)
        assert env.kernel_mode() == v
    monkeypatch.setenv("REPRO_KERNEL_MODE", "tpu_magic")
    with pytest.raises(ValueError, match="REPRO_KERNEL_MODE"):
        env.kernel_mode()


def test_lane_native(monkeypatch):
    monkeypatch.delenv("REPRO_LANE_NATIVE", raising=False)
    assert env.lane_native() is None
    monkeypatch.setenv("REPRO_LANE_NATIVE", "1")
    assert env.lane_native() is True
    monkeypatch.setenv("REPRO_LANE_NATIVE", "0")
    assert env.lane_native() is False
    monkeypatch.setenv("REPRO_LANE_NATIVE", "yes")
    with pytest.raises(ValueError, match="REPRO_LANE_NATIVE"):
        env.lane_native()


def test_tick_overlap(monkeypatch):
    monkeypatch.delenv("REPRO_TICK_OVERLAP", raising=False)
    assert env.tick_overlap() is None
    monkeypatch.setenv("REPRO_TICK_OVERLAP", "1")
    assert env.tick_overlap() is True
    monkeypatch.setenv("REPRO_TICK_OVERLAP", "0")
    assert env.tick_overlap() is False
    monkeypatch.setenv("REPRO_TICK_OVERLAP", "on")
    with pytest.raises(ValueError, match="REPRO_TICK_OVERLAP"):
        env.tick_overlap()


def test_step_cache_size(monkeypatch):
    monkeypatch.delenv("REPRO_STEP_CACHE_SIZE", raising=False)
    assert env.step_cache_size() == 8
    assert env.step_cache_size(default=3) == 3
    monkeypatch.setenv("REPRO_STEP_CACHE_SIZE", "16")
    assert env.step_cache_size() == 16
    for bad in ("zero", "0", "-2"):
        monkeypatch.setenv("REPRO_STEP_CACHE_SIZE", bad)
        with pytest.raises(ValueError, match="REPRO_STEP_CACHE_SIZE"):
            env.step_cache_size()


def test_tuning_table_path(monkeypatch, tmp_path):
    monkeypatch.delenv("REPRO_KERNEL_TUNING", raising=False)
    assert env.tuning_table_path().name == "kernel_tuning.json"
    monkeypatch.setenv("REPRO_KERNEL_TUNING", str(tmp_path / "t.json"))
    assert env.tuning_table_path() == tmp_path / "t.json"


def test_tune_override_ignores_malformed_json(monkeypatch):
    """The one deliberate exception to raise-on-junk: a tuning override is
    a performance hint, and a typo in it must never take serving down."""
    monkeypatch.setenv("REPRO_TUNE_FUSED_DCP", '{"frames_per_block": 4}')
    assert env.tune_override("fused_dcp") == {"frames_per_block": 4}
    monkeypatch.setenv("REPRO_TUNE_FUSED_DCP", "not json")
    assert env.tune_override("fused_dcp") == {}
    monkeypatch.setenv("REPRO_TUNE_FUSED_DCP", '["a", "list"]')
    assert env.tune_override("fused_dcp") == {}
    monkeypatch.delenv("REPRO_TUNE_FUSED_DCP")
    assert env.tune_override("fused_dcp") == {}


def test_bench_smoke(monkeypatch):
    monkeypatch.delenv("REPRO_BENCH_SMOKE", raising=False)
    assert env.bench_smoke() is False
    monkeypatch.setenv("REPRO_BENCH_SMOKE", "1")
    assert env.bench_smoke() is True
    monkeypatch.setenv("REPRO_BENCH_SMOKE", "")
    assert env.bench_smoke() is False


def test_snapshot_restore(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")
    monkeypatch.setenv("REPRO_STEP_CACHE_SIZE", "4")
    snap = env.snapshot()
    assert snap["REPRO_KERNEL_MODE"] == "ref"
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")
    monkeypatch.delenv("REPRO_STEP_CACHE_SIZE")
    monkeypatch.setenv("REPRO_LANE_NATIVE", "1")       # not in the snapshot
    env.restore(snap)
    assert env.kernel_mode() == "ref"
    assert env.step_cache_size() == 4
    assert env.lane_native() is None                   # stray var removed


def test_no_environ_reads_outside_env_module():
    """Satellite guarantee: ``os.environ`` access for REPRO_* knobs lives
    only in repro/core/env.py (non-knob uses like the dry-run's XLA_FLAGS
    export are fine)."""
    hits = subprocess.run(
        ["grep", "-rn", "environ", str(SRC / "repro")],
        capture_output=True, text=True).stdout.splitlines()
    offenders = [h for h in hits
                 if "core/env.py" not in h.split(":", 1)[0]
                 and "REPRO_" in h]
    assert offenders == [], f"REPRO_* environ reads outside env.py: {offenders}"


def test_benchmarks_use_env_module():
    for bench in ("kernels_bench.py", "table1_throughput.py"):
        text = (SRC.parent / "benchmarks" / bench).read_text()
        assert "environ" not in text, f"{bench} bypasses repro.core.env"


def test_compile_cache_dir(monkeypatch):
    """The persistent compile cache lives where JAX_COMPILATION_CACHE_DIR
    says, else at one fixed, git-ignored directory of the checkout."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/srv/jax-cache")
    assert env.compile_cache_dir() == Path("/srv/jax-cache")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = env.compile_cache_dir()
    assert path == SRC.parent / ".jax_cache"
    assert path == env.compile_cache_dir()          # fixed, not per call
    ignored = (SRC.parent / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
