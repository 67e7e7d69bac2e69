"""The reduction from a profiler trace to busy time, idle gaps and top ops."""
import _small  # noqa: F401  (puts the checkout on sys.path)
import pytest

from bench import trace_reduce as tr


def test_union_merges_overlaps_and_clips_to_window():
    got = tr.union([(5, 8), (0, 3), (2, 4), (7, 12), (20, 30)], (1, 10))
    assert got == [(1, 4), (5, 10)]


def test_gaps_are_the_complement_of_busy():
    busy = [(1, 4), (5, 10)]
    assert tr.gaps(busy, (0, 12)) == [(0, 1), (4, 5), (10, 12)]
    assert tr.gaps([], (0, 12)) == [(0, 12)]


def test_gap_label_prefers_the_benchmark_span_that_covers_half():
    host = [tr.Span("PjitFunction(step)", 0, 100, "python3"),
            tr.Span("Transpose::ExecuteChunk", 0, 100, "futex-default"),
            tr.Span("bench.camera_pull", 10, 30, "python3"),
            tr.Span("bench.sink", 70, 72, "python3"),
            tr.Span(tr.WINDOW_SPAN, 0, 100, "python3")]
    assert tr.label((5, 40), host) == "bench.camera_pull"
    assert tr.label((50, 80), host) == "PjitFunction(step)"   # sink: 2 of 30
    assert tr.label((200, 300), host) == "none"


def test_op_names_drop_the_hlo_text_and_suffix():
    assert tr.op_name("%reduce_window_sum.90 = f32[1,1,1080,1920]{3,2,1,0} "
                      "reduce-window(...)") == "reduce_window_sum"
    assert tr.op_name("%sort = (f32[8], s32[8]) sort(...)") == "sort"


def test_summary_of_a_synthetic_trace():
    t = tr.Trace(device_ops={"/device:TPU:0": [
        tr.Span("fusion.1", 10, 40), tr.Span("fusion.2", 30, 50),
        tr.Span("copy.3", 80, 90), tr.Span("fusion.1", 95, 130)]},
        host=[tr.Span(tr.WINDOW_SPAN, 0, 100), tr.Span("bench.sink", 50, 80)])
    s = tr.summarize(t)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx((40 + 10 + 5) * 1e-9)   # union, clipped
    assert s.device_ops == [("fusion", pytest.approx(55e-9)),
                            ("copy", pytest.approx(10e-9))]
    assert s.idle_gaps[0] == ("bench.sink", pytest.approx(30e-9))
    assert [g for g, _ in s.idle_gaps] == ["bench.sink", "none", "none"]


def test_no_window_or_no_device_reads_nothing():
    assert tr.summarize(tr.Trace({}, [tr.Span(tr.WINDOW_SPAN, 0, 9)])) is None
    assert tr.summarize(tr.Trace({"/device:TPU:0": [tr.Span("a", 0, 1)]},
                                 [])) is None


TRACE = _small.ROOT / "bench" / "testdata" / "nav-1s.xplane.pb.gz"


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    """A 1 s window of ``cap-1080p-nav-b1`` traced on one TPU v5e chip."""
    import gzip
    path = tmp_path_factory.mktemp("trace") / "nav.xplane.pb"
    path.write_bytes(gzip.decompress(TRACE.read_bytes()))
    return tr.load(str(path))


def _naive_busy(spans, window):
    """Busy time by marking each nanosecond-microsecond bucket (slow, plain)."""
    lo, hi = window
    marks = set()
    for s in spans:
        a, b = max(s.start, lo), min(s.end, hi)
        marks.update(range(int(a // 1000), int(-(-b // 1000))) if b > a
                     else ())
    return len(marks) * 1000


def test_recorded_trace_busy_idle_and_top_ops(chip_trace):
    (plane, ops), = chip_trace.device_ops.items()
    assert plane == "/device:TPU:0" and len(ops) > 1000
    window = tr.window_of(chip_trace)
    s = tr.summarize(chip_trace)
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(1.0, abs=0.01)
    busy = sum(b - a for a, b in tr.union(((o.start, o.end) for o in ops),
                                          window))
    assert s.busy_s == pytest.approx(busy * 1e-9)
    # Microsecond buckets over-count each interval by at most 2 us.
    assert busy <= _naive_busy(ops, window) <= busy + 2000 * len(ops)
    assert 0.5 < s.busy_s / s.window_s < 1.0          # idle share 0..0.5
    idle = sum(b - a for a, b in tr.gaps(tr.union(
        ((o.start, o.end) for o in ops), window), window))
    assert (busy + idle) * 1e-9 == pytest.approx(s.window_s)
    names = [n for n, _ in s.device_ops]
    secs = [v for _, v in s.device_ops]
    assert names[0] == "sort" and secs == sorted(secs, reverse=True)
    assert sum(secs) <= s.busy_s * (1 + 1e-9) + 1e-6
    assert len(s.idle_gaps) == 10
    assert {g for g, _ in s.idle_gaps} <= {"bench.camera_pull",
                                           "np.asarray(jax.Array)"}
