"""A served write's whole life on the span ring's one clock: the client's
timeline (objecter), the primary's three stages around the encode queue,
the join of the two by reqid, the CPU clock of every span, and the two
benchmark readers that take them apart (benchmarks/readers)."""

import collections
import math
import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "benchmarks") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from readers import op_joined_mean, span_offcpu_per_batch  # noqa: E402

from ceph_tpu.core import optracker, tracing  # noqa: E402
from ceph_tpu.core.optracker import OpTracker, declare_op_hists  # noqa: E402
from ceph_tpu.core.perf import PerfCounters  # noqa: E402
from ceph_tpu.core.tracing import (CLIENT_RECORD, COUNTS, CPU, ID,  # noqa: E402
                                   NAME, OP_RECORD, T0, T1, Recorder)

# the nine stretches of a served write, first mark to first mark
CHAIN = ["created", "initiated", "admitted", "encode_queued", "encoded",
         "fanout_begun", "submitted", "commit", "commit_sent", "returned"]
MS = 1_000_000


def first_marks(line):
    at = {}
    for t, stage in line:
        at.setdefault(stage, t)
    return at


def test_new_stages_and_record_are_registered():
    for s in ("created", "sent", "reply_recv", "returned", "encode_queued",
              "encoded", "fanout_begun"):
        assert tracing.STAGES[s] == "", s   # no histogram of their own
    assert CLIENT_RECORD not in (OP_RECORD, *tracing.SPANS)


# -- the served path, end to end ---------------------------------------------

def test_every_acknowledged_write_has_one_joined_line():
    """A small EC cluster with blkin tracing on: each acknowledged
    write_full's client record joins its primary's op record by reqid;
    the stages are in order and the nine stretches add up to created ->
    returned to the nanosecond; the client.op span's instants are the
    timeline's and the primary's span carries the three new stages."""
    from ceph_tpu.client.rados import RadosClient
    from ceph_tpu.core.context import Context
    from ceph_tpu.osd import types as t_
    from ceph_tpu.osd.types import OSDOp
    from ceph_tpu.vstart import VStartCluster

    with VStartCluster(n_mons=1, n_osds=3, conf={"tracing": True}) as c:
        pool = c.create_pool("tl_ec", size=3, pool_type="erasure",
                             ec_profile="k=2 m=1")
        for svc in c.osds.values():
            assert svc.wait_pgs_settled(15.0)
        rc = RadosClient(Context("client.tl", {"tracing": True}))
        rc.connect(c.monmap)
        c._clients.append(rc)   # shut down with the cluster
        io = rc.ioctx(pool)
        io.write_full("tl_warm", b"w" * 4096)
        ops = [io.aio_operate(f"tl_{i}", [OSDOp(t_.OP_WRITEFULL,
                                                data=bytes([i]) * 8192)])
               for i in range(8)]
        assert all(op.result(30.0).result == 0 for op in ops)
        mine = {op.reqid for op in ops}
        # the primary files its op record just after it sends the reply
        deadline = time.monotonic() + 10.0
        while True:
            recs, _ = tracing.recorder().held()
            clients = [r for r in recs if r[NAME] == CLIENT_RECORD
                       and r[COUNTS]["reqid"] in mine]
            lines = tracing.joined(clients, recs)
            if len(lines) == len(ops) or time.monotonic() > deadline:
                break
            time.sleep(0.02)
        assert len(clients) == len(ops) and len(lines) == len(ops)
        for line in lines:
            at = first_marks(line)
            assert [at[s] for s in CHAIN] == sorted(at[s] for s in CHAIN), \
                line
            assert at["created"] <= at["sent"] <= at["initiated"]
            assert at["reply_recv"] <= at["returned"]
            assert sum(at[b] - at[a] for a, b in zip(CHAIN, CHAIN[1:])) \
                == at["returned"] - at["created"]
        for op in ops:
            stages = [s for _t, s in op.events]
            assert stages == ["created", "sent", "reply_recv", "returned"]
            t = dict((s, t) for t, s in op.events)
            sp = op.span
            assert sp.start == t["created"] and sp.end == t["reply_recv"]
            assert [a for a, _w in sp.annotations] == [t["sent"],
                                                       t["reply_recv"]]
        # a second result() files nothing more
        ops[0].result(1.0)
        assert len([r for r in tracing.recorder().held()[0]
                    if r[NAME] == CLIENT_RECORD
                    and r[COUNTS]["reqid"] == ops[0].reqid]) == 1
        do_ops = [r for r in tracing.recorder().held()[0]
                  if r[NAME].endswith(".do_op")
                  and r[tracing.CAUSES][0] == ops[0].span.trace_id]
        assert do_ops
        said = [w.split(" ")[0] for _a, w in do_ops[-1][COUNTS]["annotations"]]
        assert said.index("admitted") < said.index("encode_queued") \
            < said.index("encoded") < said.index("fanout_begun") \
            < said.index("submitted")


def test_annotations_leave_every_stage_histogram_as_it_was(monkeypatch):
    """The three stages around the queue go through `_op_stage` as
    annotations: every lat_*_us histogram reads, count and sum, what it
    read without them, on a clock the test sets."""
    from ceph_tpu.osd.backend import _op_stage

    now = [0.0]
    monkeypatch.setattr(optracker, "time",
                        types.SimpleNamespace(monotonic=lambda: now[0]))
    marks = [(100.001, "queued_for_pg", False), (100.002, "reached_pg", False),
             (100.004, "admitted", False), (100.006, "encode_queued", True),
             (100.9, "encoded", True), (100.91, "fanout_begun", True),
             (100.93, "submitted", False), (101.0, "commit", False)]

    def run(annotate):
        pc = PerfCounters("osd.t.op")
        declare_op_hists(pc)
        now[0] = 100.0
        op = OpTracker(perf=pc).create_op("osd_op(x)", reqid="client.1:1")
        msg = types.SimpleNamespace(trop=op)
        for t, stage, ann in marks:
            now[0] = t
            if not ann:
                _op_stage(msg, stage)
            elif annotate:
                _op_stage(msg, stage, annotation=True)
        now[0] = 101.001
        op.finish(stage="commit_sent")
        return ({k: v for k, v in pc.dump().items() if k.startswith("lat_")},
                [s for _t, s, _d in op.events])

    hists, stages = run(True)
    assert (hists, [s for s in stages if s not in (
        "encode_queued", "encoded", "fanout_begun")]) == run(False)
    assert stages.index("encode_queued") < stages.index("submitted")
    assert hists["lat_encode_fanout_us"]["count"] == 1
    assert hists["lat_encode_fanout_us"]["sum"] == pytest.approx(
        (100.93 - 100.004) * 1e6)


# -- the CPU clock ------------------------------------------------------------

def test_a_sleeping_span_reads_off_the_cpu_and_a_spinning_one_does_not():
    rec = Recorder(capacity=16)
    with rec.span("batch.stack") as slept:
        time.sleep(0.02)
    spun = []
    for _ in range(3):   # a busy machine may take the CPU away once
        with rec.span("batch.crc_layout") as sp:
            end = time.perf_counter() + 0.02
            while time.perf_counter() < end:
                pass
        spun.append(sp)
    recs = {r[ID]: r for r in rec.held()[0]}

    def off(sp):
        r = recs[sp.id]
        return (r[T1] - r[T0] - r[CPU]) / 1e6

    assert 19.0 <= off(slept) <= 40.0
    assert min(off(sp) for sp in spun) < 1.0
    assert all(0 <= recs[sp.id][CPU] <= recs[sp.id][T1] - recs[sp.id][T0]
               for sp in spun + [slept])


def test_self_cpu_subtracts_direct_children_as_self_ns_does():
    # (id, name, t0, t1, thread, parent, causes, counts, seq, cpu)
    recs = [(1, "queue.batch", 0, 100, 7, 0, (), {}, 3, 60),
            (2, "batch.encode", 10, 50, 7, 1, (), {}, 1, 30),
            (3, "dev.dispatch", 12, 20, 7, 2, (), {}, 0, 8),
            (4, "batch.fanout", 60, 70, 7, 1, (), {}, 2, 10),
            (5, "op", 0, 90, 9, 0, (), {}, 4, None)]
    own_cpu = tracing.self_cpu_ns(recs)
    assert own_cpu == {1: 60 - 30 - 10, 2: 30 - 8, 3: 8, 4: 10}
    own = tracing.self_ns(recs)
    assert own[1] == 100 - 40 - 10 and own[2] == 40 - 8
    # the tree's self CPU adds up to the root's CPU, as wall does
    assert sum(own_cpu.values()) == 60
    assert sum(own[i] for i in (1, 2, 3, 4)) == 100


# -- the join -----------------------------------------------------------------

def _op_record(rec, reqid, t0_ms, stages, terminal="commit_sent"):
    events = [(0.0, "initiated", "")] + [
        (ms / 1e3, s, "") for s, ms in stages] + [
        (stages[-1][1] / 1e3 + 0.002 if stages else 0.001, terminal, "")]
    rec._file(rec.next_id(), OP_RECORD, t0_ms * MS,
              t0_ms * MS + round(events[-1][0] * 1e9), 3, 0, (),
              {"desc": "osd_op(w)", "reqid": reqid, "events": tuple(events)})


def _client_record(rec, reqid, t0_ms, sent_ms, back_ms, ret_ms):
    rec.client(reqid, ((t0_ms * MS, "created"), (sent_ms * MS, "sent"),
                       (back_ms * MS, "reply_recv"), (ret_ms * MS, "returned")))


PRIMARY = [("queued_for_pg", 1), ("reached_pg", 2), ("admitted", 4),
           ("encode_queued", 6), ("encoded", 90), ("fanout_begun", 91),
           ("submitted", 95), ("commit", 135)]


def test_a_resend_joins_the_primary_record_that_answered():
    """EAGAIN, then the resend commits: the line is the commit's, the
    last record.  A resend of a slow op answered from the log after the
    original committed: the original's, the first that committed.
    Where none committed the last one joins; a reqid no primary saw
    joins nothing."""
    rec = Recorder(capacity=64)
    _op_record(rec, "c.1:1", 1000, [("admitted", 4)], terminal="eagain")
    _op_record(rec, "c.1:1", 1200, PRIMARY)
    _op_record(rec, "c.1:2", 1300, [("admitted", 1)], terminal="eagain")
    _op_record(rec, "c.1:2", 1400, [("admitted", 2)], terminal="aborted")
    _client_record(rec, "c.1:1", 999, 999.5, 1340, 1341)
    _client_record(rec, "c.1:2", 1299, 1299.5, 1403, 1404)
    _client_record(rec, "c.1:3", 1299, 1299.5, 1403, 1404)
    recs, _ = rec.held()
    clients = [r for r in recs if r[NAME] == CLIENT_RECORD]
    lines = tracing.joined(clients, recs)
    assert len(lines) == 2
    one = first_marks(lines[0])
    assert one["initiated"] == 1200 * MS and one["admitted"] == 1204 * MS
    assert one["created"] == 999 * MS and one["returned"] == 1341 * MS
    assert [t for t, _s in lines[0]] == sorted(t for t, _s in lines[0])
    assert first_marks(lines[1])["aborted"] == 1400 * MS + 4 * MS
    # the original committed; a later resend of the same reqid was
    # answered EAGAIN and another from the log: the original's line
    _op_record(rec, "c.1:1", 1250, [("reached_pg", 1)], terminal="eagain")
    _op_record(rec, "c.1:1", 1330, [("reached_pg", 1)])
    recs, _ = rec.held()
    assert first_marks(tracing.joined(clients[:1], recs)[0])["initiated"] \
        == 1200 * MS


# -- the two readers on a synthetic ring --------------------------------------

def _ring(monkeypatch, clients=True, cpu=True):
    """Two batches (seq 6 and 7) of queue 1 on thread 7 with their CPU
    times, and two served writes that concluded inside them: one 141 ms
    at the client, one 191 ms."""
    rec = Recorder(capacity=256)

    def span(name, t0, t1, cpu_ms, parent=0, **counts):
        i = rec.next_id()
        rec._file(i, name, t0 * MS, t1 * MS, 7, parent, (), counts,
                  cpu_ms * MS if cpu else None)
        return i

    def batch(seq, t):
        span("queue.idle", t - 3, t - 2, 0, q=1, seq=seq)
        span("queue.coalesce", t - 2, t, 1, q=1, seq=seq)
        b = rec.next_id()
        span("batch.stack", t + 1, t + 4, 2, parent=b)          # off 1
        c = rec.next_id()
        span("dev.dispatch", t + 5, t + 9, 3, parent=c)         # off 1
        span("dev.wait", t + 9, t + 80, 1, parent=c)            # not read
        rec._file(c, "batch.crc", (t + 4) * MS, (t + 82) * MS, 7, b, (),
                  {}, (1 + 3 + 1) * MS if cpu else None)        # off 2
        rec._file(b, "queue.batch", t * MS, (t + 90) * MS, 7, 0, (),
                  {"q": 1, "seq": seq}, (6 + 2 + 5) * MS if cpu else None)
        # queue.batch self: wall 90 - 3 - 78 = 9, cpu 6: off 3

    batch(5, 800)
    batch(6, 1100)
    batch(7, 1200)
    _op_record(rec, "c.1:1", 1000, PRIMARY)
    _op_record(rec, "c.1:2", 1100, [(s, ms + 50 if s in ("encoded",
                                                         "fanout_begun",
                                                         "submitted",
                                                         "commit") else ms)
                                    for s, ms in PRIMARY])
    if clients:
        _client_record(rec, "c.1:1", 999, 999.5, 1139, 1140)    # 141 ms
        _client_record(rec, "c.1:2", 1099, 1099.5, 1289, 1290)  # 191 ms
        _client_record(rec, "c.1:9", 1150, 1150.5, 1180, 1181)  # no join
    monkeypatch.setattr(tracing, "_recorder", rec)
    return rec


def ctx_of(lo, hi):
    return {"before": {"queue.batches": lo}, "after": {"queue.batches": hi}}


def test_op_joined_mean_on_a_synthetic_ring(monkeypatch):
    _ring(monkeypatch)
    ctx = ctx_of(5, 7)
    read = op_joined_mean.read
    assert read({"from": "created", "to": "initiated"}, ctx) == 1.0
    assert read({"from": "admitted", "to": "encode_queued"}, ctx) == 2.0
    # 84 and 134 ms
    assert read({"from": "encode_queued", "to": "encoded"}, ctx) == 109.0
    assert read({"from": "commit_sent", "to": "returned"}, ctx) == 3.0
    # the slowest twentieth of two ops is one: c.1:2, 191 ms
    assert read({"from": "encode_queued", "to": "encoded",
                 "slowest": 0.05}, ctx) == 134.0
    assert read({"from": "created", "to": "returned", "slowest": 0.05,
                 "minus": [["encode_queued", "encoded"],
                           ["submitted", "commit"]]}, ctx) \
        == 191.0 - 134.0 - 40.0
    # the nine stretches add up to the whole op
    whole = read({"from": "created", "to": "returned"}, ctx)
    assert whole == 166.0
    assert math.isclose(sum(read({"from": a, "to": b}, ctx)
                            for a, b in zip(CHAIN, CHAIN[1:])), whole)
    assert len(ctx["ring_window"].joined) == 2


@pytest.mark.parametrize("case", ["no batch", "no client records",
                                  "no join", "a stage no op has",
                                  "a parent's window"])
def test_op_joined_mean_reads_nothing(monkeypatch, case):
    rec = _ring(monkeypatch, clients=case != "no client records")
    args, ctx = {"from": "created", "to": "initiated"}, ctx_of(5, 7)
    if case == "no batch":
        ctx = ctx_of(7, 7)
    elif case == "no join":
        recs, _ = rec.held()
        rec2 = Recorder(capacity=256)
        for r in recs:
            if r[NAME] != OP_RECORD:
                rec2._file(*r[:8], r[CPU])
        monkeypatch.setattr(tracing, "_recorder", rec2)
    elif case == "a stage no op has":
        args = {"from": "created", "to": "ack_gated"}
    elif case == "a parent's window":
        old = collections.namedtuple("BatchWindow", "batches self_ns ops")
        monkeypatch.setattr(tracing, "batch_window",
                            lambda lo, hi: old(2, {"queue.batch": 1}, []))
    assert op_joined_mean.read(args, ctx) is None


SPANS = ["queue.batch", "batch.stack", "batch.encode", "batch.crc_layout",
         "batch.crc", "batch.fanout", "dev.dispatch", "clay.uncouple",
         "clay.mds", "clay.couple"]


def test_span_offcpu_per_batch_on_a_synthetic_ring(monkeypatch):
    _ring(monkeypatch)
    ctx = ctx_of(5, 7)
    # off the CPU a batch: stack 1, dispatch 1, crc 2, batch 3
    assert span_offcpu_per_batch.read({"spans": SPANS}, ctx) == 7.0
    assert span_offcpu_per_batch.read({"spans": ["dev.wait"]}, ctx) == 70.0


@pytest.mark.parametrize("case", ["no batch", "no CPU field",
                                  "no such span", "a parent's window"])
def test_span_offcpu_per_batch_reads_nothing(monkeypatch, case):
    _ring(monkeypatch, cpu=case != "no CPU field")
    spans, ctx = SPANS, ctx_of(5, 7)
    if case == "no batch":
        ctx = ctx_of(7, 9)
    elif case == "no such span":
        spans = ["clay.mds"]
    elif case == "a parent's window":
        old = collections.namedtuple("BatchWindow", "batches self_ns ops")
        monkeypatch.setattr(tracing, "batch_window",
                            lambda lo, hi: old(2, {"queue.batch": 1}, []))
    assert span_offcpu_per_batch.read({"spans": spans}, ctx) is None
