"""Trace-span tests (reference src/blkin/ + src/tracing/ tracepoints)."""

import pytest

from ceph_tpu.core.tracing import Tracer, trace_id_of


def test_span_parentage_and_dump():
    tr = Tracer("t")
    root = tr.start_span("client.op")
    root.annotate("sent")
    child = tr.start_span("osd.op", parent=root.context())
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    child.finish()
    root.finish()
    spans = tr.dump(root.trace_id)
    assert [s["name"] for s in spans] == ["client.op", "osd.op"]
    assert spans[0]["parent_id"] is None
    assert spans[1]["parent_id"] == spans[0]["span_id"]
    assert spans[0]["annotations"][0]["what"] == "sent"


def test_disabled_tracer_archives_nothing():
    tr = Tracer("t", enabled=False)
    with tr.start_span("x") as s:
        s.annotate("y")
    assert tr.recent() == []


def test_trace_id_of_is_deterministic_correlator():
    assert trace_id_of("client.1:42") == trace_id_of("client.1:42")
    assert trace_id_of("client.1:42") != trace_id_of("client.1:43")
    assert trace_id_of("x") & 1  # never zero


def test_blkin_spans_share_the_recorders_ring_and_clock():
    """A finished blkin span is one record of the process's one ring,
    on the recorder's clock, with ids from the recorder (no random
    pair a span); each Tracer reads back only its own."""
    from ceph_tpu.core import tracing

    a, b = Tracer("a"), Tracer("b")
    t0 = tracing.clock()
    with a.start_span("a.op") as sa:
        sa.annotate("sent")
    with b.start_span("b.op", parent=sa.context()):
        pass
    t1 = tracing.clock()
    assert [s["name"] for s in a.recent()] == ["a.op"]
    assert [s["name"] for s in b.recent()] == ["b.op"]
    assert b.dump(sa.trace_id)[0]["parent_id"] == f"{sa.span_id:016x}"
    held, _lost = tracing.recorder().held()
    mine = [r for r in held if r[tracing.ID] == sa.span_id]
    assert len(mine) == 1 and t0 <= mine[0][tracing.T0] <= t1
    assert sa.span_id and sa.trace_id and sa.span_id != sa.trace_id


def test_stage_registry_sane():
    from ceph_tpu.core.tracing import STAGES

    # the write pipeline's histogram-fed stages, in order
    for s in ("queued_for_pg", "reached_pg", "admitted", "submitted",
              "commit", "ack_gated", "commit_sent"):
        assert s in STAGES
    # peer-side span stages the cross-daemon tree uses
    for s in ("store_commit", "sub_read_served", "note_persisted"):
        assert s in STAGES and STAGES[s] == ""


def test_wire_trace_context_roundtrip_and_byte_stability():
    """The optional trace tail: carried when set, absent (and
    byte-identical to the pre-PR encoding) when not."""
    from ceph_tpu.msg.message import Message
    from ceph_tpu.osd import messages as om

    vec = om.MECSubWriteVec((1, 2), 3, "o", b"t", [])
    plain = vec.to_bytes()
    vec.set_trace((0x1234, 0x5678))
    traced = vec.to_bytes()
    assert traced != plain
    back = Message.from_bytes(traced)
    assert back.trace_ctx() == (0x1234, 0x5678)
    back.set_trace(None)  # None = keep as-is
    assert back.trace_ctx() == (0x1234, 0x5678)
    # untraced re-encode of an untraced blob is byte-stable
    again = Message.from_bytes(plain)
    assert again.trace_ctx() is None
    assert again.to_bytes() == plain


def test_cross_daemon_trace_tree_over_admin_socket(tmp_path):
    """Acceptance: one client EC write on a MiniCluster (3 acting
    OSDs) yields a dumpable cross-daemon causal tree — client root ->
    primary do_op (pipeline stage annotations) -> >=2 peer sub_write
    children with store_commit annotations — retrievable by trace_id
    via the admin socket."""
    import time as _time

    from ceph_tpu.core.admin_socket import admin_command
    from ceph_tpu.osd import types as t_
    from tests.test_osd_cluster import EC_POOL, LibClient, MiniCluster

    sock = str(tmp_path / "admin.sock")
    c = MiniCluster(overrides={"admin_socket": sock})
    c.ctx.trace.enabled = True
    cl = LibClient(c)
    try:
        io = cl.rc.ioctx(EC_POOL)
        op = io.aio_operate(
            "traced_ec",
            [t_.OSDOp(t_.OP_WRITEFULL, data=b"t" * 8192)])
        rep = op.result(15.0)
        assert rep.result == 0
        assert op.span is not None
        trace_id = op.span.trace_id
        # peer sub_write spans finish on their store-commit threads:
        # they may trail the client reply by a beat
        deadline = _time.time() + 10.0
        spans = []
        while _time.time() < deadline:
            spans = admin_command(sock, "dump_trace",
                                  trace_id=f"{trace_id:x}")
            if sum(1 for s in spans if ".sub_write" in s["name"]) >= 2:
                break
            _time.sleep(0.1)
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"].split(".", 1)[-1], []).append(s)
        assert len(by_name.get("op", [])) == 1, spans  # client.op
        client = by_name["op"][0]
        do_ops = [s for s in spans if ".do_op" in s["name"]]
        assert len(do_ops) == 1, spans
        do_op = do_ops[0]
        # parentage: client -> do_op -> each peer's sub_write
        assert do_op["trace_id"] == client["trace_id"]
        assert do_op["parent_id"] == client["span_id"]
        subs = [s for s in spans if ".sub_write" in s["name"]]
        assert len(subs) >= 2, spans
        for s in subs:
            assert s["parent_id"] == do_op["span_id"]
            whats = [a["what"] for a in s["annotations"]]
            assert any(w == "store_commit" for w in whats), whats
        # the primary's pipeline stages annotate its span
        whats = [a["what"].split(" ")[0] for a in do_op["annotations"]]
        for stage in ("admitted", "submitted", "commit"):
            assert stage in whats, do_op["annotations"]
    finally:
        cl.shutdown()
        c.shutdown()


def test_recovery_round_spans_and_peer_children():
    """Recovery rounds open spans; peers serving the window's vec
    sub-reads hang children off them (sub_read_served)."""
    import time as _time

    from tests.test_osd_cluster import EC_POOL, LibClient, MiniCluster

    c = MiniCluster()
    c.ctx.trace.enabled = True
    cl = LibClient(c)
    try:
        io = cl.rc.ioctx(EC_POOL)
        io.write_full("rec_traced", b"r" * 16384)
        pgid, acting, primary = c.primary_of(EC_POOL, "rec_traced")
        # kill the PRIMARY: on revive it re-takes the pg and pulls its
        # missing shards through the windowed engine (the bench shape)
        c.kill(primary)
        io.write_full("rec_traced", b"R" * 16384)  # degraded write
        c.revive(primary)
        deadline = _time.time() + 15.0
        rounds, serves = [], []
        while _time.time() < deadline:
            recent = c.ctx.trace.recent(500)
            rounds = [s for s in recent
                      if s["name"].endswith("recovery.round")]
            serves = [s for s in recent if ".sub_read" in s["name"]]
            if rounds and serves:
                break
            _time.sleep(0.2)
        assert rounds, "no recovery-round span archived"
        round_ids = {s["span_id"] for s in rounds}
        assert any(s["parent_id"] in round_ids for s in serves), (
            rounds, serves)
    finally:
        cl.shutdown()
        c.shutdown()


def test_pg_op_spans_cross_daemon_correlation():
    """The PG op path emits spans correlated by reqid when tracing is
    on (covers the do_op wiring + admin dump shape)."""
    import sys, os

    sys.path.insert(0, os.path.dirname(__file__))
    from test_osd_cluster import MiniCluster, LibClient, REP_POOL

    c = MiniCluster()
    c.ctx.trace.enabled = True
    cl = LibClient(c)
    try:
        io = cl.rc.ioctx(REP_POOL)
        io.write_full("traced", b"x")
        io.read("traced")
        spans = c.ctx.trace.recent(50)
        names = [s["name"] for s in spans]
        assert any(".do_op" in n for n in names)
        # the write and its read correlate to DIFFERENT traces
        tids = {s["trace_id"] for s in spans if ".do_op" in s["name"]}
        assert len(tids) >= 2
    finally:
        cl.shutdown()
        c.shutdown()
