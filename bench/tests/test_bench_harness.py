"""The harness finds cells, configurations, mixes and metrics by name."""
import json
import os
import shutil
import subprocess
import sys

import _small
import pytest

from bench import harness

CELLS = ["cap-1080p-nav-b1", "dcp-1080p-backlog"]


def test_benchmark_json_and_the_cell_files_agree():
    bench = harness.benchmark()
    assert sorted(w["name"] for w in bench["workloads"]) == CELLS
    assert harness.list_cells() == CELLS
    for w in bench["workloads"]:
        spec = harness.cell_spec(w["name"])
        assert {k: spec[k] for k in ("config", "traffic", "chips", "why")} \
            == {k: w[k] for k in ("config", "traffic", "chips", "why")}
        harness.config_spec(w["config"])
        harness.traffic_kind(harness.mix_spec(w["traffic"])["kind"])
    for c in bench["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"]).read)


def test_every_cell_reports_setup_another_metric_and_a_layer():
    bench = harness.benchmark()
    for cell in CELLS:
        e2e = [m["name"] for m in harness.metrics_for(bench, cell, False)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_for(bench, cell, True)


def test_a_new_cell_is_a_data_file(tmp_path, monkeypatch):
    """A cell dropped in as a file (and an entry in BENCHMARK.json) is
    listed and run with no code changed."""
    bench_dir = tmp_path / "bench"
    for sub in ("workloads", "configs", "traffic", "metrics"):
        shutil.copytree(harness.BENCH / sub, bench_dir / sub)
    new = dict(harness.cell_spec("dcp-1080p-backlog"),
               config="dehaze-cap-1080p",
               why="the control for a DCP-only change: CAP under the backlog")
    (bench_dir / "workloads" / "cap-1080p-backlog.json").write_text(
        json.dumps(new))
    doc = harness.benchmark()
    doc["workloads"].append({"name": "cap-1080p-backlog", **{
        k: new[k] for k in ("config", "traffic", "chips", "why")}})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "dcp-1080p-backlog" in m.get("workloads", []):
            m["workloads"].append("cap-1080p-backlog")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    monkeypatch.setattr(harness, "BENCH", bench_dir)
    monkeypatch.setattr(harness, "ROOT", tmp_path)

    assert "cap-1080p-backlog" in harness.list_cells()
    r = _small.run_small("cap-1080p-backlog")
    assert r["correct"] and set(r["metrics"]) == {"frames_per_s", "setup_s"}
    r = _small.run_small("cap-1080p-backlog", trace=True)
    assert {"d2h_bytes_per_frame", "stage_host_ms_per_frame"} <= set(r["metrics"])


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cap-1080p-nav-b1",
         "--seed", "2147483700", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_exits_nonzero_without_a_tpu():
    p = _run_cli(_small.ROOT)
    assert p.returncode != 0
    assert "accelerator" in p.stderr
    assert "{" not in p.stdout


def test_command_exits_nonzero_without_the_program(tmp_path):
    shutil.copytree(_small.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(_small.ROOT / "BENCHMARK.json", tmp_path)
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout


@pytest.mark.parametrize("cell", CELLS)
def test_a_small_run_prints_what_the_contract_asks(cell):
    r = _small.run_small(cell)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "checks"
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())
    assert set(r["device"]) >= {"platform", "kind", "count",
                                "memory_peak_bytes"}
