"""Device-runtime observability (PR 10): XLA compile attribution,
recompile-storm detection, steady-state guard, op-level compile blame,
crash flight-recorder integration, and the dump/export surfaces.

Reference tier: the `dout` gather ring + fatal-signal crash dump
(src/log/Log.cc, src/global/signal_handler.cc) applied to the device
runtime — every compile and batch dispatch is an attributed, recorded
event.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from ceph_tpu.tpu import devwatch
from ceph_tpu.tpu.devwatch import (
    GUARD_VIOLATIONS, _churn_dim, instrumented_jit, sig_str, signature,
    watch,
)


@pytest.fixture
def dw():
    """The process-wide watcher with config/wiring save-restored so
    tests can shrink storm thresholds and attach stub logs/queues."""
    w = watch()
    saved = (w.storm_window_s, w.storm_min_sigs, w.storm_min_rogue_sigs,
             w._log, w._queue)
    yield w
    (w.storm_window_s, w.storm_min_sigs, w.storm_min_rogue_sigs,
     w._log, w._queue) = saved
    GUARD_VIOLATIONS.clear()


class StubLog:
    def __init__(self):
        self.lines = []
        self.cluster_msgs = []

    def log(self, subsys, level, msg):
        self.lines.append((subsys, level, msg))

    def cluster(self, level, msg):
        self.cluster_msgs.append((level, msg))


def _codec():
    from ceph_tpu.ec import codec_from_profile

    return codec_from_profile("plugin=isa k=2 m=1 "
                              "technique=reed_sol_van")


# -- signature machinery ------------------------------------------------------

def test_signature_dedup_same_family_same_shape_is_one_compile(dw):
    fam = "t_dedup"
    f = instrumented_jit(lambda x: x + 1, family=fam)
    a = np.arange(64, dtype=np.int32)
    f(a)
    f(a)
    f(np.arange(64, dtype=np.int32))  # same signature, fresh buffer
    st = dw.family_stats(fam)
    assert st["compiles"] == 1
    assert st["cache_hits"] == 2
    assert st["distinct_signatures"] == 1
    f(np.arange(128, dtype=np.int32))  # novel shape = trace re-entry
    st = dw.family_stats(fam)
    assert st["compiles"] == 2 and st["distinct_signatures"] == 2
    # cache hits feed the family's execute histogram
    hist = dw.perf.dump()[f"exec_{fam}_us"]
    assert hist["count"] == 2


def test_signature_covers_dtype_and_mirrors_jax_static_semantics():
    a32 = np.arange(8, dtype=np.int32)
    a64 = np.arange(8, dtype=np.int64)
    assert signature((a32,), {}) != signature((a64,), {})
    # dynamic Python scalars key by TYPE, like jax (value-keying
    # would inflate compile counts and raise false storms on a
    # healthy kernel taking a varying offset — review finding)
    assert signature((a32, 3), {}) == signature((a32, 4), {})
    assert signature((a32, 3), {}) != signature((a32, 3.0), {})
    # DECLARED-static args key by value: each value IS a compile
    assert signature((a32, 3), {}, static_argnums=(1,)) \
        != signature((a32, 4), {}, static_argnums=(1,))
    assert signature((a32,), {"tile_n": 256},
                     static_argnames=("tile_n",)) \
        != signature((a32,), {"tile_n": 512},
                     static_argnames=("tile_n",))
    assert "int32[8]" in sig_str(signature((a32,), {}))


def test_instrumented_jit_static_argnames_key_by_value(dw):
    fam = "t_static"
    f = instrumented_jit(lambda x, n: x[:n], family=fam,
                        static_argnames=("n",))
    a = np.arange(16, dtype=np.int32)
    f(a, n=4)
    f(a, n=4)   # same static value: cache hit
    f(a, n=8)   # new static value: a real jax recompile
    st = dw.family_stats(fam)
    assert st["compiles"] == 2 and st["cache_hits"] == 1


def test_churn_dim_names_the_varying_axis():
    sigs = [signature((np.zeros((2, n), np.uint8),), {})
            for n in (128, 256, 512)]
    assert _churn_dim(sigs) == "arg0.shape[1]"
    sigs = [signature((np.zeros((2, 64), np.uint8), k), {},
                      static_argnums=(1,))
            for k in (1, 2, 3)]
    assert _churn_dim(sigs) == "arg1"


# -- recompile-storm detection ------------------------------------------------

def test_storm_detector_fires_and_names_family_and_dimension(dw):
    fam = "t_storm"
    log = StubLog()
    dw.attach_log(log)
    dw.configure(window_s=30.0, min_sigs=3)
    g = instrumented_jit(lambda x: x * 2, family=fam)
    for n in (16, 24, 40):  # deliberate shape churn
        g(np.arange(n, dtype=np.int32))
    warns = [m for _l, m in log.cluster_msgs if "RECOMPILE_STORM" in m]
    assert warns, log.cluster_msgs
    assert fam in warns[0]
    assert "arg0.shape[0]" in warns[0]
    storm = dw.dump()["storms"][-1]
    assert storm["family"] == fam
    assert storm["distinct_signatures"] == 3
    assert storm["churning"] == "arg0.shape[0]"
    # cooldown: more churn inside the same window is one WARN, not N
    g(np.arange(56, dtype=np.int32))
    assert len([m for _l, m in log.cluster_msgs
                if "RECOMPILE_STORM" in m and fam in m]) == 1


def test_no_storm_below_threshold(dw):
    fam = "t_quiet"
    log = StubLog()
    dw.attach_log(log)
    dw.configure(window_s=30.0, min_sigs=4)
    g = instrumented_jit(lambda x: x - 1, family=fam)
    for n in (8, 12):
        g(np.arange(n, dtype=np.int32))
    assert not [m for _l, m in log.cluster_msgs if fam in m]


# -- steady-state guard -------------------------------------------------------

def test_steady_state_guard_catches_in_section_compile(dw):
    fam = "t_guard"
    f = instrumented_jit(lambda x: x ^ 1, family=fam)
    f(np.arange(32, dtype=np.int32))  # warmup: outside the section
    with dw.steady_state():
        f(np.arange(32, dtype=np.int32))  # cache hit: fine
    assert not GUARD_VIOLATIONS
    with dw.steady_state():
        f(np.arange(48, dtype=np.int32))  # novel shape: violation
    assert len(GUARD_VIOLATIONS) == 1
    assert fam in GUARD_VIOLATIONS[0]
    GUARD_VIOLATIONS.clear()  # consumed here, not by the conftest


# -- op-level compile blame ---------------------------------------------------

def test_compile_wait_annotation_on_op_racing_a_live_compile(dw):
    """An op whose encode batch window overlaps a live XLA compile
    gets the compile_wait annotation + lat_compile_wait_us evidence —
    slow-op forensics can now tell compile stalls from queue depth."""
    from ceph_tpu.core.optracker import OpTracker, declare_op_hists
    from ceph_tpu.core.perf import PerfCounters
    from ceph_tpu.tpu.queue import StripeBatchQueue

    pc = PerfCounters("osd.t.op")
    declare_op_hists(pc)
    trk = OpTracker(perf=pc)
    op = trk.create_op("osd_op(client.1:1 w)")
    q = StripeBatchQueue()
    try:
        tok = dw.compile_begin("t_race")  # a cold kernel is compiling
        fut = q.encode_async(
            _codec(), np.arange(256, dtype=np.uint8).reshape(2, 128),
            trop=op)
        fut.result(10.0)
        dw.compile_end(tok, signature((np.zeros(1),), {}))
        events = [e["event"] for e in op.dump()["events"]]
        assert any(e.startswith("compile_wait") for e in events), events
        assert pc.dump()["lat_compile_wait_us"]["count"] >= 1
    finally:
        op.finish(stage="commit_sent")
        q.stop()


def test_compile_wait_annotation_does_not_shift_stage_baseline(dw):
    """compile_wait is an ANNOTATION: it lands on the timeline but
    must not advance the since-previous-event baseline, or the next
    stage's histogram (lat_commit_wait_us) reads from the blame stamp
    instead of its real predecessor (review finding)."""
    from ceph_tpu.core.optracker import OpTracker, declare_op_hists
    from ceph_tpu.core.perf import PerfCounters

    pc = PerfCounters("osd.tb.op")
    declare_op_hists(pc)
    trk = OpTracker(perf=pc)
    op = trk.create_op("osd_op(client.1:9 w)")
    op.mark_event("submitted")
    time.sleep(0.3)
    op.mark_event("compile_wait", "5.0ms", annotation=True)
    time.sleep(0.01)
    op.mark_event("commit")
    events = [e["event"] for e in op.dump()["events"]]
    assert any(e.startswith("compile_wait") for e in events)
    h = pc.dump()["lat_commit_wait_us"]
    # measured since 'submitted' (~310ms+), not since the annotation
    # (~10ms+scheduling)
    assert h["sum"] / h["count"] > 150e3, h
    op.finish(stage="commit_sent")


def test_no_compile_wait_when_no_compile_is_live(dw):
    from ceph_tpu.core.optracker import OpTracker, declare_op_hists
    from ceph_tpu.core.perf import PerfCounters
    from ceph_tpu.tpu.queue import StripeBatchQueue

    pc = PerfCounters("osd.t2.op")
    declare_op_hists(pc)
    trk = OpTracker(perf=pc)
    op = trk.create_op("osd_op(client.1:2 w)")
    codec = _codec()
    q = StripeBatchQueue()
    try:
        # warm the engine so nothing compiles during the watched job,
        # then push the compile-span ring past the retention horizon?
        # No — spans are bounded but long-lived; instead assert on the
        # op's own window: with no overlap there is no annotation.
        q.encode(codec, np.arange(256, dtype=np.uint8).reshape(2, 128))
        time.sleep(0.01)  # the op's window opens after any prior span
        op2 = trk.create_op("osd_op(client.1:3 w)")
        fut = q.encode_async(
            codec, np.arange(256, dtype=np.uint8).reshape(2, 128),
            trop=op2)
        fut.result(10.0)
        events = [e["event"] for e in op2.dump()["events"]]
        assert not any(e.startswith("compile_wait") for e in events), \
            events
        op2.finish(stage="commit_sent")
    finally:
        op.finish(stage="commit_sent")
        q.stop()


# -- crash flight recorder ----------------------------------------------------

def test_crash_report_device_section_roundtrips(dw, tmp_path):
    """An induced device-worker stall (failpoint on
    queue.batch.dispatch) produces a crash report whose device section
    shows the in-flight batch and the last compiles — the wedged
    worker leaves a diagnosable corpse (acceptance criterion)."""
    from ceph_tpu.core import failpoint as fp
    from ceph_tpu.core.crash import CrashArchive
    from ceph_tpu.tpu.queue import StripeBatchQueue

    codec = _codec()
    # seed at least one compile event so last_compiles is non-empty
    instrumented_jit(lambda x: x + 7, family="t_crash")(
        np.arange(16, dtype=np.int32))
    q = StripeBatchQueue()
    dw.attach_queue(q)
    fp.arm("queue.batch.dispatch", fp.barrier("devwatch-stall"))
    try:
        fut = q.encode_async(
            codec, np.arange(512, dtype=np.uint8).reshape(2, 256))
        assert fp.wait_hit("devwatch-stall", timeout=10.0)
        arch = CrashArchive(str(tmp_path / "crash"), entity="osd.7")
        try:
            raise RuntimeError("device worker wedged")
        except RuntimeError as e:
            cid = arch.record(e)
        # round-trip through the on-disk JSON (the mgr crash-info path)
        info = arch.info(cid)
        dev = info["device"]
        assert dev["in_flight_batch"]["jobs"] == 1
        assert dev["in_flight_batch"]["kind"] == "enc"
        assert dev["in_flight_batch"]["shapes"] == [[2, 256]]
        assert any(ev["family"] == "t_crash"
                   for ev in dev["last_compiles"])
        assert "staging" in dev and "queue_depth" in dev
        json.dumps(info)  # fully serializable
    finally:
        fp.release("devwatch-stall")
        fut.result(10.0)
        fp.disarm_all()
        q.stop()


def test_gather_ring_records_compile_and_batch_events(dw):
    """Compile and dispatch events land in the core log gather ring
    under the tpu subsys (the dout gather-level discipline: recorded
    always, emitted never at default levels)."""
    from ceph_tpu.core.log import Log

    log = Log(default_level=1, name="t.gather")
    dw.attach_log(log)
    instrumented_jit(lambda x: x + 3, family="t_gather")(
        np.arange(8, dtype=np.int32))
    from ceph_tpu.tpu.queue import StripeBatchQueue

    q = StripeBatchQueue()
    try:
        q.encode(_codec(),
                 np.arange(256, dtype=np.uint8).reshape(2, 128))
    finally:
        q.stop()
    recent = log.dump_recent()
    assert any("devwatch compile t_gather" in ln for ln in recent)
    assert any("devwatch batch queue" in ln for ln in recent)


# -- surfaces: perf set, admin socket, mgr, prometheus, cephtop ---------------

def test_osd_xla_perf_set_registered():
    """Every OSDService registers the process watcher as osd.N.xla
    (the osd.N.tpuq shape: process-wide set, per-daemon label)."""
    from tests.test_osd_cluster import MiniCluster

    c = MiniCluster()
    try:
        whoami = next(iter(c.osds))
        dump = c.ctx.perf.dump()
        assert f"osd.{whoami}.xla" in dump
        assert "compile_total" in dump[f"osd.{whoami}.xla"]
    finally:
        c.shutdown()


def test_device_compile_dump_admin_socket_and_cephtop(dw, tmp_path):
    import contextlib
    import io as _io

    sys.path.insert(0, os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "tools")))
    import cephtop

    from ceph_tpu.core.admin_socket import admin_command
    from ceph_tpu.core.context import Context

    instrumented_jit(lambda x: x + 9, family="t_sock")(
        np.arange(8, dtype=np.int32))
    sock = str(tmp_path / "dw.sock")
    ctx = Context("osd.5", {"admin_socket": sock})
    try:
        d = admin_command(sock, "device compile dump")
        assert "t_sock" in d["families"]
        assert d["families"]["t_sock"]["compiles"] >= 1
        assert d["totals"]["compiles"] >= 1
        # cephtop --device renders the same table
        buf = _io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = cephtop.main(["--socket", sock, "--device"])
        assert rc == 0
        out = buf.getvalue()
        assert "t_sock" in out and "compiles" in out
    finally:
        ctx.shutdown()


def test_mgr_device_module_and_cli_parse(dw):
    from ceph_tpu.core.context import Context
    from ceph_tpu.mgr.manager import MgrDaemon

    instrumented_jit(lambda x: x + 11, family="t_mgr")(
        np.arange(8, dtype=np.int32))
    mgr = MgrDaemon(Context("mgr.t", {}))
    rc, out = mgr.handle_command({"prefix": "device compile dump"})
    assert rc == 0 and "t_mgr" in out["families"]
    # the CLI reaches every new prefix from argv (satellite: crash
    # ls/info and device compile dump were mgr-served but unreachable)
    sys.path.insert(0, os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "tools")))
    import ceph as ceph_cli

    assert ceph_cli._parse(["crash", "ls"]) == {"prefix": "crash ls"}
    assert ceph_cli._parse(["crash", "info", "x.1"]) == {
        "prefix": "crash info", "id": "x.1"}
    assert ceph_cli._parse(["device", "compile", "dump"]) == {
        "prefix": "device compile dump"}


def test_prometheus_export_includes_xla_and_reparses(dw):
    from ceph_tpu.core.context import Context
    from ceph_tpu.mgr.manager import MgrDaemon

    from tests.test_pgmap import parse_exposition

    fam = "t_prom"
    f = instrumented_jit(lambda x: x * 3, family=fam)
    f(np.arange(8, dtype=np.int32))
    f(np.arange(8, dtype=np.int32))  # one hit -> exec histogram fed
    mgr = MgrDaemon(Context("mgr.p", {}))
    body = mgr.modules["prometheus"].export()
    types, samples = parse_exposition(body)  # every line must parse
    assert types["ceph_xla_compile_total"] == "counter"
    assert types["ceph_xla_exec_us"] == "histogram"
    by_name = {}
    for name, labels, val in samples:
        by_name.setdefault(name, []).append((labels, val))
    comp = {lab["family"]: float(v)
            for lab, v in by_name["ceph_xla_compile_total"]}
    assert comp[fam] >= 1
    shapes = {lab["family"]: float(v)
              for lab, v in by_name["ceph_xla_distinct_shapes"]}
    assert shapes[fam] >= 1
    # the family's exec histogram carries the mandatory terminal +Inf
    # bucket equal to _count (the PR 9 exposition rule)
    buckets = [(lab, float(v))
               for lab, v in by_name["ceph_xla_exec_us_bucket"]
               if lab["family"] == fam]
    assert buckets and buckets[-1][0]["le"] == "+Inf"
    count = next(float(v) for lab, v in by_name["ceph_xla_exec_us_count"]
                 if lab["family"] == fam)
    assert buckets[-1][1] == count >= 1
    finite = [(float(lab["le"]), v) for lab, v in buckets
              if lab["le"] != "+Inf"]
    assert finite == sorted(finite)  # monotone cumulative


def test_ceph_cli_serves_device_and_crash_prefixes(dw, tmp_path):
    """End-to-end through tools/ceph.py argv: `device compile dump`
    and `crash ls` both reach the mgr (satellite: the CrashModule
    served them but no prefix was parseable)."""
    import contextlib
    import io as _io

    sys.path.insert(0, os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "tools")))
    import ceph as ceph_cli

    instrumented_jit(lambda x: x + 13, family="t_cli")(
        np.arange(8, dtype=np.int32))
    buf = _io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = ceph_cli.main(
            ["--vstart", "1x1", "--data-dir", str(tmp_path / "d"),
             "--script", "device compile dump; crash ls"])
    assert rc == 0
    out = buf.getvalue()
    assert "t_cli" in out
    assert "crashes" in out


def test_vstart_durable_cluster_archives_crashes(dw, tmp_path):
    """A durable vstart wires a crash spool into the mgr CrashModule;
    a recorded crash is listable and its report carries the device
    section."""
    from ceph_tpu.vstart import VStartCluster

    with VStartCluster(n_mons=1, n_osds=1,
                       data_dir=str(tmp_path / "dd")) as c:
        mgr = c.start_mgr()
        arch = c._crash_archive
        try:
            raise RuntimeError("vstart-crash")
        except RuntimeError as e:
            cid = arch.record(e)
        rc, out = mgr.handle_command({"prefix": "crash ls"})
        assert rc == 0
        assert cid in [x["crash_id"] for x in out["crashes"]]
        rc, info = mgr.handle_command(
            {"prefix": "crash info", "id": cid})
        assert rc == 0 and "device" in info


# -- the CRUSH churn acceptance (compile-heavy: slow tier) --------------------

@pytest.mark.slow
def test_crush_churn_storm_and_pow2_padding_steady(dw):
    """Acceptance: a deliberately shape-churning CRUSH sweep raises
    the recompile-storm WARN (family + distinct-signature count in the
    dump), and re-running through sweep()'s pow2 high-water padding
    (the PR 3 fix) shows zero storm and zero steady-state compiles."""
    from ceph_tpu.crush import map as cmap
    from ceph_tpu.crush import mapper

    log = StubLog()
    dw.attach_log(log)
    dw.configure(window_s=120.0, min_sigs=3)
    m, root = cmap.build_flat_cluster(8, hosts=4)
    steps = [(cmap.OP_TAKE, root, 0),
             (cmap.OP_CHOOSELEAF_FIRSTN, 2, 1),
             (cmap.OP_EMIT, 0, 0)]
    flat = m.flatten()
    w = np.full(8, 0x10000, dtype=np.uint32)
    fast = mapper.compile_rule(flat, steps, 2, None, one_shot=True)
    base = dw.family_stats("crush_mapper")["compiles"]
    # churn: every distinct batch length is a fresh XLA program
    for n in (17, 33, 65):
        fast(np.arange(n, dtype=np.int32), w)
    st = dw.family_stats("crush_mapper")
    assert st["compiles"] - base >= 3
    warns = [msg for _l, msg in log.cluster_msgs
             if "RECOMPILE_STORM" in msg and "crush_mapper" in msg]
    assert warns, log.cluster_msgs
    storm = next(s for s in reversed(dw.dump()["storms"])
                 if s["family"] == "crush_mapper")
    assert storm["distinct_signatures"] >= 3
    # pow2 high-water padding: warm once, then the same sweep shapes
    # re-run compile-free — asserted by the steady-state guard itself
    xs = np.arange(300, dtype=np.int32)
    mapper.sweep(flat, steps, 2, xs, w, chunk=256)  # warmup
    storms_before = len(dw.dump()["storms"])
    with dw.steady_state():
        got = mapper.sweep(flat, steps, 2, xs, w, chunk=256)
    assert not GUARD_VIOLATIONS, GUARD_VIOLATIONS
    assert len(dw.dump()["storms"]) == storms_before  # zero new storms
    assert got.shape == (300, 2)


def test_heartbeat_holds_its_verdict_while_this_process_compiles(dw):
    """A compile in this process starves the process's OWN handling of
    ping replies (PR 22, first chip run: every OSD of a 12-OSD
    in-process cluster marked down during the post-pool warmup), so a
    grace overrun seen while one is live — or ended less than a fuse
    ago — is not reported; it is counted (heartbeat_compile_holds) and
    judged once the compile is a full fuse in the past."""
    from ceph_tpu.vstart import VStartCluster

    with VStartCluster(n_mons=1, n_osds=3, conf={
            "osd_heartbeat_interval": 0.2,
            "osd_heartbeat_grace": 1.0}) as c:
        tok = dw.compile_begin("crush_mapper")  # a compile is live
        try:
            c.kill_osd(2)
            # a fuse is one grace, three before a first reply, and a
            # loaded box stretches it up to 3x (_load_stretch): wait for
            # the overrun itself, not for a time by which it should be
            c.wait_for(lambda: sum(
                o.perf.value("heartbeat_compile_holds")
                for o in c.osds.values() if o.up) > 0, timeout=60.0,
                what="a grace overrun held while the compile is live")
            time.sleep(1.0)  # one more grace of silence, still held
            assert c.leader().osdmap.is_up(2), "judged during a compile"
        finally:
            dw.compile_end(tok, ())
        c.wait_for(lambda: not c.leader().osdmap.is_up(2), timeout=60.0,
                   what="osd.2 marked down once the compile had ended")
