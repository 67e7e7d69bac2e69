"""The per-layer metrics that read the program's serve-loop spans."""
from types import SimpleNamespace

import _small
import pytest

from bench import harness

READERS = [("spout_ms_per_frame.backlog", "spout_s"),
           ("spout_ms_per_frame.live", "spout_s"),
           ("inflight_wait_ms_per_frame", "inflight_wait_s"),
           ("d2h_ms_per_frame.backlog", "fetch_s"),
           ("d2h_ms_per_frame.live", "fetch_s"),
           ("monitor_queue_ms_per_frame.live", "monitor_queue_s")]
SPAN_METRICS = {
    "dcp-1080p-backlog": {"spout_ms_per_frame.backlog",
                          "inflight_wait_ms_per_frame",
                          "d2h_ms_per_frame.backlog"},
    "cap-1080p-nav-b1": {"spout_ms_per_frame.live", "d2h_ms_per_frame.live",
                         "monitor_queue_ms_per_frame.live"},
}


def _read(name, phases, frames):
    ctx = SimpleNamespace(report=SimpleNamespace(frames=frames,
                                                 phases=phases))
    split = name.split(".", 1)[1] if "." in name else None
    return harness.metric_reader(name).read(ctx, split)


@pytest.mark.parametrize("name,key", READERS)
def test_span_reader_is_per_frame_and_silent_without_its_key(name, key):
    # A parent program reports only the keys it had: the reader is silent.
    assert _read(name, {"host_stage_s": 1.0}, 40) is None
    assert _read(name, {key: 0.5}, 0) is None
    assert _read(name, {key: 0.5, "host_stage_s": 9.0}, 40) \
        == pytest.approx(12.5)


@pytest.mark.parametrize("cell", sorted(SPAN_METRICS))
def test_a_small_traced_run_prints_the_span_metrics(cell):
    listed = {m["name"] for m in harness.metrics_for(harness.benchmark(),
                                                     cell, True)}
    assert SPAN_METRICS[cell] <= listed
    r = _small.run_small(cell, trace=True)
    assert r["correct"]
    for name in SPAN_METRICS[cell]:
        assert r["metrics"][name]["unit"] == "ms"
        assert r["metrics"][name]["value"] >= 0.0
