"""OpTracker tests: stage-latency attribution, terminal-event
lifecycle, slow-op ring admission, leak sanitizer, dump surfaces
(reference TrackedOp.h / OpRequest.h + the `ceph daemon <osd>
dump_ops_in_flight` family)."""

import os
import time

import pytest

from ceph_tpu.core import optracker
from ceph_tpu.core.optracker import LEAKS, OpTracker, declare_op_hists
from ceph_tpu.core.perf import (PerfCounters, hist_delta, hist_merge,
                                hist_quantile)
from ceph_tpu.core.tracing import STAGES


def _tracker(threshold=1.0, **kw):
    pc = PerfCounters("osd.t.op")
    declare_op_hists(pc)
    return OpTracker(slow_op_threshold=threshold, perf=pc, **kw), pc


# -- stage histograms ---------------------------------------------------------

def test_stage_events_feed_per_stage_histograms():
    trk, pc = _tracker()
    op = trk.create_op("osd_op(x)")
    op.mark_event("queued_for_pg")
    op.mark_event("reached_pg")
    op.mark_event("admitted")
    op.mark_event("submitted")
    op.mark_event("commit")
    op.finish(stage="commit_sent")
    d = pc.dump()
    for hist in ("lat_recv_us", "lat_queue_us", "lat_admission_us",
                 "lat_encode_fanout_us", "lat_commit_wait_us",
                 "lat_reply_us", "lat_op_us"):
        assert d[hist]["count"] == 1, (hist, d[hist])
    # stage deltas sum to roughly the op total (same timeline)
    stage_sum = sum(d[h]["sum"] for h in (
        "lat_recv_us", "lat_queue_us", "lat_admission_us",
        "lat_encode_fanout_us", "lat_commit_wait_us", "lat_reply_us"))
    assert abs(stage_sum - d["lat_op_us"]["sum"]) < 100  # us


def test_stage_delta_is_since_previous_event():
    trk, pc = _tracker()
    op = trk.create_op("x")
    op.mark_event("queued_for_pg")
    time.sleep(0.05)
    op.mark_event("reached_pg")  # ~50ms queue wait
    op.finish(stage="commit_sent")
    q = pc.dump()["lat_queue_us"]
    assert q["count"] == 1
    assert q["sum"] >= 45_000  # the sleep landed in THIS stage
    assert pc.dump()["lat_recv_us"]["sum"] < 45_000


def test_timeline_and_registry_agree():
    """Every hist-feeding stage used by the pipeline is declared."""
    for stage, hist in STAGES.items():
        assert isinstance(stage, str) and stage
        if hist:
            assert hist.startswith("lat_") and hist.endswith("_us")


# -- lifecycle ---------------------------------------------------------------

def test_finish_is_idempotent_one_history_entry():
    trk, _ = _tracker()
    op = trk.create_op("x")
    op.finish(stage="commit_sent")
    op.finish()          # double finish: no-op
    with op:             # context-manager sugar after explicit finish
        pass
    assert trk.dump_historic()["num_ops"] == 1
    assert trk.num_in_flight == 0


def test_terminal_event_recorded_for_eagain_and_abort():
    trk, _ = _tracker()
    op = trk.create_op("x")
    op.finish(stage="eagain")
    op2 = trk.create_op("y")
    with pytest.raises(RuntimeError):
        with op2:
            raise RuntimeError("boom")
    events = [o["events"][-1]["event"]
              for o in trk.dump_historic()["ops"]]
    assert events[0] == "eagain"
    assert events[1].startswith("aborted")
    assert trk.num_in_flight == 0


def test_drain_shutdown_vs_leak():
    trk, _ = _tracker()
    healthy = trk.create_op("in-flight-at-kill")   # never replied
    leaky = trk.create_op("replied-but-never-finished")
    leaky.mark_event("commit_sent")                # reply went out...
    before = len(LEAKS)
    try:
        trk.drain()
        assert trk.num_in_flight == 0
        evs = {o["description"]: o["events"][-1]["event"]
               for o in trk.dump_historic()["ops"]}
        # a kill mid-write is NOT a leak; a concluded op still in the
        # table IS
        assert evs["in-flight-at-kill"] == "daemon_shutdown"
        assert evs["replied-but-never-finished"] == "leaked"
        assert len(LEAKS) == before + 1
        assert "replied-but-never-finished" in LEAKS[-1]
        assert trk.ops_leaked == 1
        assert healthy.done_at is not None
    finally:
        # consume the deliberately-injected leak so the conftest
        # sanitizer (which asserts LEAKS empty) sees a clean test
        del LEAKS[before:]


def test_mark_event_thread_safety_ordered_timeline():
    """Stages arrive from different threads (fan-out lane, store-commit
    callbacks, the deadline sweep): concurrent marks must keep the
    timeline ordered — no interleaved garble, no lost events, and the
    since-previous deltas the histograms eat stay non-negative."""
    import threading

    trk, pc = _tracker()
    op = trk.create_op("racy")
    n_threads, n_marks = 8, 200
    barrier = threading.Barrier(n_threads)

    def w():
        barrier.wait()
        for _ in range(n_marks):
            op.mark_event("reached_pg")

    ts = [threading.Thread(target=w) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    stamps = [t for t, _, _ in op.events]
    assert stamps == sorted(stamps)
    assert len(op.events) == 1 + n_threads * n_marks
    op.finish(stage="commit_sent")
    d = pc.dump()["lat_queue_us"]
    assert d["count"] == n_threads * n_marks
    assert d["sum"] >= 0


def test_mark_event_overhead_is_microseconds():
    """The tracked-op hot path (mark_event + histogram feed) must stay
    negligible next to a ~1ms write — the instrumentation-overhead
    analog of the PR-7 disarmed-failpoint bound, generous for the
    box's documented drift."""
    trk, _ = _tracker()
    op = trk.create_op("bench")
    n = 2000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _i in range(n):
            op.mark_event("commit")
        best = min(best, (time.perf_counter() - t0) / n)
    op.finish()
    assert best < 50e-6, f"mark_event cost {best * 1e6:.1f}us"


# -- histogram math ----------------------------------------------------------

def test_hist_quantile_bucket_math():
    pc = PerfCounters("t")
    pc.add_histogram("h")
    # 90 small values (bucket [64,128)) + 10 large ([65536,131072))
    for _ in range(90):
        pc.hinc("h", 100.0)
    for _ in range(10):
        pc.hinc("h", 100_000.0)
    d = pc.dump()["h"]
    p50 = hist_quantile(d, 0.50)
    p99 = hist_quantile(d, 0.99)
    assert 64 <= p50 < 128, p50
    assert 65536 <= p99 <= 131072, p99
    assert hist_quantile({"count": 0, "buckets": []}, 0.5) == 0.0


def test_hist_merge_and_delta():
    pc = PerfCounters("t")
    pc.add_histogram("h")
    pc.hinc("h", 10.0)
    snap1 = pc.dump()["h"]
    pc.hinc("h", 1000.0)
    snap2 = pc.dump()["h"]
    dd = hist_delta(snap2, snap1)
    assert dd["count"] == 1 and 512 <= hist_quantile(dd, 0.5) <= 1024
    acc = {}
    hist_merge(acc, snap1)
    hist_merge(acc, dd)
    assert acc["count"] == snap2["count"]
    assert acc["buckets"] == snap2["buckets"]


# -- cluster integration ------------------------------------------------------

def test_slow_ring_and_dump_commands_on_minicluster(tmp_path):
    """The acceptance shape: a write artificially slowed through an
    existing failpoint lands in dump_historic_slow_ops with its full
    stage timeline, retrieved over the REAL admin socket; the
    complaint time is conf-driven at runtime."""
    from ceph_tpu.core import failpoint as fp
    from ceph_tpu.core.admin_socket import admin_command
    from ceph_tpu.osd import types as t_

    from tests.test_osd_cluster import EC_POOL, LibClient, MiniCluster

    sock = str(tmp_path / "admin.sock")
    c = MiniCluster(overrides={"admin_socket": sock})
    cl = LibClient(c)
    try:
        io = cl.rc.ioctx(EC_POOL)
        io.write_full("warm", b"w" * 1024)  # pools active, obc warm
        # runtime conf drives the ring: every op now counts as slow
        c.ctx.conf.set_val("osd_op_complaint_time", 0.01)
        for o in c.osds.values():
            assert o.op_tracker.slow_op_threshold == 0.01
        # artificially slow the sub-write fan-out (existing failpoint,
        # fires on the fan-out executor — never the messenger loop);
        # sleep returns None, so nothing is dropped, just delayed
        fp.arm("backend.subwrite.fanout", fp.sleep_ms(25))
        try:
            io.write_full("slowme", b"s" * 2048)
        finally:
            fp.disarm("backend.subwrite.fanout")
        pgid, _acting, primary = c.primary_of(EC_POOL, "slowme")
        # over the admin socket, per-daemon prefixed like `ceph daemon`
        # the primary sends the reply, then concludes the op and files
        # it into the ring (`daemon.py` `reply`: `conn.send` before
        # `top.finish`); under load the client's dump came between the
        # two (D15 ii), so ask until the op is there
        deadline = time.monotonic() + 10.0
        while True:
            d = admin_command(
                sock, f"osd.{primary} dump_historic_slow_ops")
            ops = [o for o in d["ops"] if "slowme" in o["description"]]
            if ops or time.monotonic() > deadline:
                break
            time.sleep(0.05)
        assert ops, d
        events = [e["event"] for e in ops[-1]["events"]]
        for stage in ("initiated", "queued_for_pg", "reached_pg",
                      "admitted", "submitted", "commit", "commit_sent"):
            assert any(ev.split(" ")[0] == stage for ev in events), (
                stage, events)
        # ordering follows the pipeline
        idx = {ev.split(" ")[0]: i for i, ev in enumerate(events)}
        assert (idx["initiated"] < idx["queued_for_pg"]
                < idx["reached_pg"] < idx["admitted"]
                < idx["submitted"] < idx["commit"] < idx["commit_sent"])
        # in-flight dump answers too (likely empty now, shape check)
        infl = admin_command(sock, f"osd.{primary} dump_ops_in_flight")
        assert "num_ops" in infl and "ops" in infl
        # per-stage histograms appear in perf dump
        perf = admin_command(sock, "perf dump")
        opset = perf[f"osd.{primary}.op"]
        assert opset["lat_commit_wait_us"]["count"] >= 1
        assert opset["lat_reply_us"]["count"] >= 1
        # the injected per-peer sleeps (2 peers x 25ms, sequential in
        # the fan-out loop) land in the encode/fan-out stage
        assert hist_quantile(opset["lat_encode_fanout_us"],
                             0.99) >= 40_000
        # reads conclude with their OWN terminal stage: read_sent ->
        # lat_read_us; whole read service times must never inflate
        # lat_reply_us (which for writes is reply-send only)
        assert io.read("slowme") == b"s" * 2048
        hist = admin_command(sock, f"osd.{primary} dump_historic_ops")
        reads = [o for o in hist["ops"]
                 if "slowme" in o["description"]
                 and any(e["event"].split(" ")[0] == "read_sent"
                         for e in o["events"])]
        assert reads, hist
        perf2 = admin_command(sock, "perf dump")
        assert perf2[f"osd.{primary}.op"]["lat_read_us"]["count"] >= 1
    finally:
        cl.shutdown()
        c.shutdown()


def test_mgr_ops_module_merges_cluster_wide(tmp_path):
    """mgr cluster poll: slow ops and stage histograms merge across
    registered daemons (the DaemonServer/MMgrReport role)."""
    from ceph_tpu.mgr.manager import MgrDaemon

    from tests.test_osd_cluster import EC_POOL, LibClient, MiniCluster

    c = MiniCluster()
    cl = LibClient(c)
    try:
        c.ctx.conf.set_val("osd_op_complaint_time", 0.0)
        io = cl.rc.ioctx(EC_POOL)
        io.write_full("mobj", b"m" * 4096)
        mgr = MgrDaemon(c.ctx)
        for i, svc in c.osds.items():
            mgr.register_daemon(f"osd.{i}", c.ctx, service=svc)
        rc, slow = mgr.handle_command({"prefix": "ops dump_slow"})
        assert rc == 0 and slow["num_ops"] >= 1
        assert any("mobj" in o["description"] for o in slow["ops"])
        assert all("daemon" in o for o in slow["ops"])
        rc, lat = mgr.handle_command({"prefix": "ops latency"})
        assert rc == 0
        assert lat["lat_reply_us"]["count"] >= 1
        assert lat["lat_op_us"]["p99_us"] > 0
        rc, infl = mgr.handle_command({"prefix": "ops dump_in_flight"})
        assert rc == 0 and "ops" in infl
    finally:
        cl.shutdown()
        c.shutdown()


def test_cephtop_renders_breakdown(tmp_path):
    """tools/cephtop.py end-to-end over a real admin socket."""
    import contextlib
    import io as _io
    import sys

    sys.path.insert(0, os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "tools")))
    import cephtop

    from tests.test_osd_cluster import REP_POOL, LibClient, MiniCluster

    def _run(argv):
        buf = _io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = cephtop.main(argv)
        return rc, buf.getvalue()

    sock = str(tmp_path / "a.sock")
    c = MiniCluster(overrides={"admin_socket": sock})
    cl = LibClient(c)
    try:
        c.ctx.conf.set_val("osd_op_complaint_time", 0.0)
        io = cl.rc.ioctx(REP_POOL)
        io.write_full("topobj", b"t" * 512)
        rc, out = _run(["--socket", sock])
        assert rc == 0
        assert "lat_reply_us" in out and "p99_us" in out
        rc, out = _run(["--socket", sock, "--slow"])
        assert rc == 0
        assert "topobj" in out
    finally:
        cl.shutdown()
        c.shutdown()
