"""Fleet-tier serving: global EDF over N hosts, sticky placement,
spillover admission — and bit-identical per-stream semantics vs the
single-host serve (the paper's §4 claim, distribution changes *where* a
stream runs, never *what* it computes)."""
import threading

import numpy as np
import pytest

from repro.core import DehazeConfig
from repro.stream import ElasticServer, StreamRequest
from repro.stream.fleet import _FleetQueue
from repro.stream.scheduler import _Resume


def _videos(n, length, h=16, w=20, seed=5):
    rng = np.random.default_rng(seed)
    return [[rng.random((h, w, 3)).astype(np.float32)
             for _ in range(length)] for _ in range(n)]


def _serve(srv, vids, sink_store, **kw):
    def sink(sid, fid, payload):
        sink_store.setdefault(sid, []).append((fid, payload.copy()))
    return srv.serve_many(
        [StreamRequest(f"s{i}", iter(v)) for i, v in enumerate(vids)],
        sink=sink, **kw)


# --- parity matrix: fleet cells ----------------------------------------------

@pytest.mark.parametrize("path", ["staged", "lane_native"])
@pytest.mark.parametrize("n_hosts", [1, 2])
def test_fleet_serve_matches_single_host(path, n_hosts):
    """{1, 2 hosts} x {staged, lane-native}: per-stream emitted frames
    (the EMA trajectory is baked into every recovered frame), emission
    order, final EMA state and cursors are bit-identical to the one-host
    one-scheduler serve of the same streams; sticky placement holds
    (zero migrations)."""
    cfg = DehazeConfig(kernel_mode="fused" if path == "lane_native"
                       else "ref", patch_radius=3, gf_radius=4,
                       update_period=2)
    vids = _videos(6, 8)

    base = ElasticServer(cfg, batch=4, timeout_s=5.0)
    want = {}
    rep_w = _serve(base, _videos(6, 8), want, n_lanes=2)
    assert rep_w.frames == 48 and rep_w.skipped == 0

    srv = ElasticServer(cfg, batch=4, timeout_s=5.0)
    got = {}
    rep = _serve(srv, vids, got, n_lanes=2, n_hosts=n_hosts)
    assert rep.frames == 48 and rep.skipped == 0
    assert rep.n_hosts == n_hosts
    assert rep.migrations == 0
    if n_hosts > 1:
        # first-fit waterfall over 2 lanes/host MUST have spilled
        assert rep.spillovers >= 1
        placements = srv.last_fleet.queue.placements
        assert sorted(placements) == [f"s{i}" for i in range(6)]
        for entry in srv.last_fleet.queue.admission_log:
            assert entry["host"] == placements[entry["stream_id"]]

    for sid in want:
        fids_w = [f for f, _ in want[sid]]
        fids_g = [f for f, _ in got[sid]]
        assert fids_g == fids_w == sorted(fids_w)        # order + exactly-once
        for (_, a), (_, b) in zip(got[sid], want[sid]):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(
            np.asarray(srv.store.get(sid).A), np.asarray(base.store.get(sid).A))
        assert srv.store.cursor(sid) == base.store.cursor(sid)


def test_fleet_duplicate_stream_ids_rejected():
    cfg = DehazeConfig(kernel_mode="ref", gf_radius=2)
    srv = ElasticServer(cfg, batch=4, timeout_s=5.0)
    v = _videos(2, 3)
    with pytest.raises(ValueError, match="duplicate"):
        srv.serve_many([StreamRequest("dup", iter(v[0])),
                        StreamRequest("dup", iter(v[1]))],
                       n_lanes=1, n_hosts=2)


def test_fleet_hash_policy_spreads_and_stays_sticky():
    cfg = DehazeConfig(kernel_mode="ref", gf_radius=2)
    srv = ElasticServer(cfg, batch=4, timeout_s=5.0)
    rep = _serve(srv, _videos(8, 4), {}, n_lanes=2, n_hosts=2,
                 placement_policy="hash")
    assert rep.frames == 32 and rep.migrations == 0
    hosts_used = {e["host"] for e in srv.last_fleet.queue.admission_log}
    assert hosts_used == {0, 1}


# --- the sticky/spillover no-migration property ------------------------------

def _drive_queue(n_streams, n_hosts, lanes, prefs, choices):
    """Replay a random schedule against the shared queue: hosts pop in an
    arbitrary interleaving, admitted streams either finish or get
    preempted-and-requeued (pinned), until the queue drains. Returns the
    queue for invariant checks.

    Preemption stops after ``16 * n_streams`` steps, so every schedule
    drains: a choice sequence that always says "preempt" (hypothesis's
    first example is all zeros) would otherwise requeue forever."""
    q = _FleetQueue(n_hosts, lanes, lambda sid: prefs[sid])
    for i in range(n_streams):
        q.seed(StreamRequest(f"s{i}", iter(())))
    live = []                         # (host, req) admitted, lane occupied
    occupied = [0] * n_hosts
    step = 0
    while True:
        acted = False
        for h in range(n_hosts):
            if occupied[h] < lanes:
                got = q.pop_for(h)
                if got is not None:
                    _, req, _resume = got
                    occupied[h] += 1
                    live.append((h, req))
                    acted = True
        if live:
            step += 1
            h, req = live.pop(choices(step) % len(live))
            occupied[h] -= 1
            if (choices(step + 1) % 3 == 0       # preempt: requeue pinned
                    and step < 16 * n_streams):
                resume = _Resume(None, 0, threading.Event())
                resume.barrier.set()
                q.push_requeue(req, resume, pin=h)
            else:                                # stream done
                q.note_freed(h)
            acted = True
        if not acted:
            break
    return q


@pytest.mark.parametrize("seed", range(6))
def test_sticky_spillover_never_migrates(seed):
    """Deterministic slice of the property: under arbitrary pop/finish/
    preempt interleavings, every admission of a stream after its first
    lands on the same host — spillover picks the FIRST host, it never
    moves a live stream's EMA."""
    rng = np.random.default_rng(seed)
    n_streams, n_hosts, lanes = 7, 3, 2
    prefs = {f"s{i}": int(rng.integers(n_hosts)) for i in range(n_streams)}
    seq = rng.integers(0, 1_000_000, size=4096)
    q = _drive_queue(n_streams, n_hosts, lanes, prefs,
                     lambda step: int(seq[step % len(seq)]))
    assert q.migrations == 0
    assert not q._entries
    hosts_per_sid = {}
    for e in q.admission_log:
        hosts_per_sid.setdefault(e["stream_id"], set()).add(e["host"])
    assert all(len(hs) == 1 for hs in hosts_per_sid.values()), hosts_per_sid
    # re-admissions are never counted as fresh spillovers
    for e in q.admission_log:
        if e["resumed"]:
            assert not e["spillover"]


def test_sticky_spillover_never_migrates_property():
    """The hypothesis version: random host counts, lane widths, policies
    and interleavings."""
    pytest.importorskip(
        "hypothesis",
        reason="property tests need hypothesis (pip install -e .[dev])")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.integers(1, 4), st.integers(1, 3), st.integers(1, 10),
           st.data())
    def prop(n_hosts, lanes, n_streams, data):
        prefs = {f"s{i}": data.draw(st.integers(0, n_hosts - 1))
                 for i in range(n_streams)}
        seq = data.draw(st.lists(st.integers(0, 10**6), min_size=64,
                                 max_size=64))
        q = _drive_queue(n_streams, n_hosts, lanes, prefs,
                         lambda step: seq[step % len(seq)])
        assert q.migrations == 0 and not q._entries
        hosts_per_sid = {}
        for e in q.admission_log:
            hosts_per_sid.setdefault(e["stream_id"], set()).add(e["host"])
        assert all(len(h) == 1 for h in hosts_per_sid.values())

    prop()


# --- exactly-once / frame order through real (subprocess) devices ------------

def test_fleet_exactly_once_frame_order_subprocess():
    """Reuses the distributed harness: a child with 2 forced host devices
    serves 5 streams over a 2-host fleet and asserts every frame id is
    emitted exactly once, in order, matching a sequential single-stream
    reference serve."""
    from test_distributed import run_child
    run_child("""
        import numpy as np
        from repro.core import DehazeConfig
        from repro.stream import ElasticServer, StreamRequest
        cfg = DehazeConfig(kernel_mode="ref", patch_radius=2, gf_radius=3,
                           update_period=2)
        rng = np.random.default_rng(3)
        vids = [[rng.random((16, 20, 3)).astype(np.float32)
                 for _ in range(7)] for _ in range(5)]
        ref = ElasticServer(cfg, batch=4, timeout_s=5.0)
        want = {}
        # sequential reference: same 2-lane executable, one host
        ref.serve_many(
            [StreamRequest(f"s{i}", iter(v)) for i, v in enumerate(vids)],
            n_lanes=2,
            sink=lambda s, f, p: want.setdefault(s, []).append((f, p.copy())))
        srv = ElasticServer(cfg, batch=4, timeout_s=5.0)
        got = {}
        rep = srv.serve_many(
            [StreamRequest(f"s{i}", iter(v)) for i, v in enumerate(vids)],
            n_lanes=2, n_hosts=2,
            sink=lambda s, f, p: got.setdefault(s, []).append((f, p.copy())))
        assert rep.frames == 35 and rep.skipped == 0
        assert rep.migrations == 0
        for sid, pairs in want.items():
            fids = [f for f, _ in got[sid]]
            assert fids == list(range(7)), (sid, fids)       # exactly once
            for (fw, pw), (fg, pg) in zip(pairs, got[sid]):
                assert fw == fg
                np.testing.assert_array_equal(pw, pg)
        print("ok")
    """, devices=2)
