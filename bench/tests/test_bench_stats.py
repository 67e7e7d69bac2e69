"""Percentiles over every frame, the service-bytes count, the peaks table."""
import _small  # noqa: F401
import numpy as np
import pytest

from bench import costs, stats


def test_percentile_is_over_all_frames_not_chunk_medians():
    fast = [1.0] * 90                  # one chunk of quick frames
    slow = [100.0] * 10                # one chunk of slow ones
    assert stats.percentile(fast + slow, 50) == 1.0
    assert stats.percentile(fast + slow, 95) == 100.0
    chunk_medians = [np.median(fast), np.median(slow)]
    assert np.percentile(chunk_medians, 50) == pytest.approx(50.5)
    assert stats.percentile([], 95) is None


def test_service_bytes_at_1080p():
    from bench import harness
    dehaze = harness.config_spec("dehaze-dcp-1080p")["dehaze"]
    n = costs.service_bytes(1080, 1920, *costs.frame_dtypes(dehaze))
    assert n == 1080 * 1920 * 3 * (1 + 4) == 31_104_000
    least_us = n / costs.peaks("TPU v5 lite")["hbm_bytes_per_s"] * 1e6
    assert least_us == pytest.approx(37.98, abs=0.01)


@pytest.mark.parametrize("io, out, wire_b, out_b", [
    ("uint8", "auto", 1, 4), ("bfloat16", "auto", 2, 2),
    ("float32", "auto", 4, 4), ("uint8", "bfloat16", 1, 2)])
def test_frame_dtypes_follow_the_dehaze_block(io, out, wire_b, out_b):
    wire, got = costs.frame_dtypes({"io_dtype": io, "out_dtype": out})
    assert (wire.itemsize, got.itemsize) == (wire_b, out_b)


def test_peaks_table_refuses_an_unknown_device_kind():
    assert costs.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        costs.peaks("TPU v4")
    with pytest.raises(KeyError):
        costs.peaks("source")
