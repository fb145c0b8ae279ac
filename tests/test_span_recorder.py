"""The one span recorder (core/tracing.py): nesting and self time, the
ring's bound, the batch stages of a real StripeBatchQueue, op records,
and the named device scopes leaving results bit-identical."""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from ceph_tpu.core import tracing
from ceph_tpu.core.tracing import (COUNTS, ID, NAME, PARENT, T0, T1, THREAD,
                                   Recorder)


def _by_name(recs):
    out = {}
    for r in recs:
        out.setdefault(r[NAME], []).append(r)
    return out


def test_nesting_parents_and_self_time():
    """A span's parent is the span open on ITS thread when it started;
    self time subtracts direct children only (a grandchild is inside
    its parent's cover already), and a sibling on another thread, open
    over the same time, subtracts nothing."""
    rec = Recorder(capacity=64)
    other_started, release = threading.Event(), threading.Event()

    def sibling():
        with rec.span("dev.wait"):
            other_started.set()
            release.wait(5.0)

    th = threading.Thread(target=sibling)
    with rec.span("queue.batch", jobs=2) as root:
        th.start()
        assert other_started.wait(5.0)
        with rec.span("batch.encode"):
            with rec.span("dev.dispatch", family="t"):
                time.sleep(0.01)
            time.sleep(0.005)
        with rec.span("batch.fanout"):
            time.sleep(0.005)
        time.sleep(0.005)
    release.set()
    th.join(5.0)
    assert not th.is_alive()
    recs, lost_until = rec.held()
    assert lost_until == 0 and rec.overwritten == 0
    by = _by_name(recs)
    batch, = by["queue.batch"]
    enc, = by["batch.encode"]
    disp, = by["dev.dispatch"]
    fan, = by["batch.fanout"]
    wait, = by["dev.wait"]
    assert batch[ID] == root.id and batch[PARENT] == 0
    assert enc[PARENT] == batch[ID] and fan[PARENT] == batch[ID]
    assert disp[PARENT] == enc[ID]
    assert wait[PARENT] == 0 and wait[THREAD] != batch[THREAD]
    assert batch[COUNTS] == {"jobs": 2}
    own = tracing.self_ns(recs)
    dur = {r[ID]: r[T1] - r[T0] for r in recs}
    assert own[disp[ID]] == dur[disp[ID]]
    assert own[enc[ID]] == dur[enc[ID]] - dur[disp[ID]]
    # children once, the grandchild not again, the other thread never
    assert own[batch[ID]] == dur[batch[ID]] - dur[enc[ID]] - dur[fan[ID]]
    assert own[batch[ID]] >= 4_000_000
    # the tree's self times add up to the root's length
    tree = [r for r in recs if r[THREAD] == batch[THREAD]]
    assert sum(own[r[ID]] for r in tree) == dur[batch[ID]]


def test_self_time_counts_overlapping_cover_once():
    """Children that overlap, or reach past their parent, are clipped:
    each stretch of the parent is subtracted once."""
    parent = (1, "queue.batch", 100, 200, 7, 0, (), {}, 3)
    kids = [(2, "batch.stack", 110, 150, 7, 1, (), {}, 0),
            (3, "batch.encode", 140, 170, 7, 1, (), {}, 1),
            (4, "batch.fanout", 190, 230, 7, 1, (), {}, 2)]
    own = tracing.self_ns([parent] + kids)
    assert own[1] == 100 - (170 - 110) - (200 - 190)


def test_exception_inside_a_span_closes_it():
    rec = Recorder(capacity=8)
    with pytest.raises(ValueError):
        with rec.span("queue.batch"):
            with rec.span("batch.stack"):
                raise ValueError("boom")
    recs, _ = rec.held()
    assert [r[NAME] for r in recs] == ["batch.stack", "queue.batch"]
    assert all(r[T1] >= r[T0] > 0 for r in recs)
    # the thread's stack is clean: the next span is a root again
    with rec.span("queue.idle"):
        pass
    assert rec.held()[0][-1][PARENT] == 0


def _record_batches(rec, first_seq, n):
    for seq in range(first_seq, first_seq + n):
        with rec.span("queue.coalesce", q=1, seq=seq):
            pass
        with rec.span("queue.batch", q=1, seq=seq, jobs=1):
            with rec.span("batch.stack"):
                pass


def test_wrapped_ring_makes_the_reader_give_nothing():
    """The ring is bounded and honest: it counts what it overwrote, and
    a window whose range reaches back to an overwritten record (or
    whose batches are no longer all there) reads None, never a number
    from half the records."""
    rec = Recorder(capacity=30)          # ten batches of three records
    _record_batches(rec, 1, 8)
    whole = tracing.batch_window(0, 8, rec)
    assert whole is not None and whole.batches == 8
    assert rec.overwritten == 0
    _record_batches(rec, 9, 6)           # 42 records: 12 overwritten
    assert rec.overwritten == 12 and rec.held()[1] > 0
    assert tracing.batch_window(0, 8, rec) is None    # batches gone
    assert tracing.batch_window(3, 10, rec) is None   # starts in the lost
    # the oldest record held is batch 5's coalesce: a range that
    # starts after it reads again
    late = tracing.batch_window(5, 14, rec)
    assert late is not None and late.batches == 9
    assert set(late.self_ns) == {"queue.coalesce", "queue.batch",
                                 "batch.stack"}
    assert tracing.batch_window(14, 20, rec) is None  # never recorded
    dump = rec.dump(count=5)
    assert dump["overwritten"] == 12 and dump["held"] == 30
    assert len(dump["spans"]) == 5 and dump["ops"] == []


def _window_when_closed(before, n=1, timeout=10.0):
    """A future resolves in the fan-out, before the worker has counted
    the batch and closed its span: wait for the span."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        w = tracing.batch_window(before, before + n)
        if w is not None:
            return w
        time.sleep(0.005)
    raise AssertionError(f"no whole window ({before}, {before + n}]")


def _codec():
    from ceph_tpu.ec import instance

    return instance().factory(
        "isa", {"technique": "reed_sol_van", "k": "4", "m": "2"})


STAGES_OF = {
    "encp": {"batch.stack", "batch.encode", "batch.crc_layout", "batch.crc",
             "batch.fanout"},
    "dec": {"batch.stack", "batch.encode", "batch.fanout"},
}


@pytest.mark.parametrize("kind", ["encp", "dec"])
def test_batch_stages_add_up_to_the_batch_span(kind):
    """Through a real StripeBatchQueue: every dispatch leaves one
    `queue.batch` span whose `seq` is the value `batches` took with it,
    with the stage spans as children; stack + encode + crc_layout + crc
    + fanout + the batch's self time IS the batch span, and with idle +
    coalesce the worker's whole cycle."""
    from ceph_tpu.core.optracker import OpTracker
    from ceph_tpu.tpu.queue import StripeBatchQueue

    codec = _codec()
    trk = OpTracker()
    rng = np.random.default_rng(7)
    q = StripeBatchQueue(window_s=0.001)
    try:
        for n in (1, 2, 3):
            before = q.batches
            op = trk.create_op(f"osd_op(client.9:{n} w)")
            planes = rng.integers(0, 256, (4, 1024), dtype=np.uint8)
            if kind == "encp":
                coding, crcs = q.encode_crc_async(
                    codec, planes, trop=op).result(30.0)
                assert coding.shape == (2, 1024) and len(crcs) == 6
            else:
                full = np.concatenate(
                    [planes, codec.encode_array(planes)])
                got = q.decode_data_async(
                    codec, {i: full[i] for i in (0, 2, 3, 5)},
                    trop=op).result(30.0)
                np.testing.assert_array_equal(got, planes)
            op.finish(stage="commit_sent")
            w = _window_when_closed(before)
            assert q.batches == before + 1 and w.batches == 1
            recs, _ = tracing.recorder().held()
            batch = [r for r in recs if r[NAME] == "queue.batch"
                     and r[COUNTS]["q"] == q._span_q][-1]
            assert batch[COUNTS]["seq"] == q.batches
            assert batch[COUNTS]["kind"] == kind
            assert batch[COUNTS]["jobs"] == 1
            assert batch[COUNTS]["cols"] == 1024
            assert batch[COUNTS]["padded"] >= 1024
            assert batch[tracing.CAUSES] == (op.id,)
            kids = [r for r in recs if r[PARENT] == batch[ID]]
            assert {r[NAME] for r in kids} == STAGES_OF[kind]
            own = tracing.self_ns(recs)
            assert (sum(r[T1] - r[T0] for r in kids) + own[batch[ID]]
                    == batch[T1] - batch[T0])
            # the window's self times by name: the same identity, taken
            # the way the benchmark's readers take it
            under = {n_: v for n_, v in w.self_ns.items()
                     if not n_.startswith("queue.") or n_ == "queue.batch"}
            assert sum(under.values()) == batch[T1] - batch[T0]
            assert "queue.coalesce" in w.self_ns
            # the op concluded inside nothing the window covers unless
            # it finished before the batch's end: its record is in the
            # ring either way, under the id the batch names
            ops = [r for r in recs if r[NAME] == tracing.OP_RECORD
                   and r[ID] == op.id]
            assert len(ops) == 1
            assert [e[1] for e in ops[0][COUNTS]["events"]][-1] \
                == "commit_sent"
    finally:
        q.stop()


def test_coalesced_batch_counts_its_jobs_and_ops():
    """Jobs that wait while a batch runs ride the next one together:
    that batch's span says how many, how wide, and for which ops."""
    from ceph_tpu.core.optracker import OpTracker
    from ceph_tpu.tpu.queue import StripeBatchQueue

    codec, trk = _codec(), OpTracker()
    q = StripeBatchQueue(window_s=0.25)
    try:
        before = q.batches
        ops = [trk.create_op(f"osd_op(client.8:{i} w)") for i in range(4)]
        futs = [q.encode_crc_async(
            codec, np.full((4, 512), i, dtype=np.uint8), trop=op)
            for i, op in enumerate(ops)]
        for f in futs:
            f.result(30.0)
        for op in ops:
            op.finish(stage="commit_sent")
        while sum(n * c for n, c in q.batch_jobs.items()) < 4:
            time.sleep(0.005)
        w = _window_when_closed(before, q.batches - before)
        assert w.batches == q.batches - before
        recs, _ = tracing.recorder().held()
        mine = [r for r in recs if r[NAME] == "queue.batch"
                and r[COUNTS]["q"] == q._span_q]
        assert [r[COUNTS]["seq"] for r in mine] == list(
            range(before + 1, q.batches + 1))
        assert sum(r[COUNTS]["jobs"] for r in mine) == 4
        assert sorted(i for r in mine for i in r[tracing.CAUSES]) \
            == sorted(op.id for op in ops)
        assert max(r[COUNTS]["jobs"] for r in mine) >= 2
    finally:
        q.stop()


def test_op_record_carries_the_timeline_on_the_recorders_clock():
    from ceph_tpu.core.optracker import OpTracker

    trk = OpTracker()
    t0 = tracing.clock()
    op = trk.create_op("osd_op(client.7:1 w)", reqid="client.7:1")
    for stage in ("queued_for_pg", "reached_pg", "admitted", "submitted",
                  "commit"):
        op.mark_event(stage)
    op.finish(stage="commit_sent")
    t1 = tracing.clock()
    recs, _ = tracing.recorder().held()
    rec, = [r for r in recs if r[ID] == op.id]
    assert rec[NAME] == tracing.OP_RECORD
    assert t0 <= rec[T0] <= rec[T1] <= t1
    c = rec[COUNTS]
    assert c["reqid"] == "client.7:1" and "client.7:1" in c["desc"]
    stages = [e[1] for e in c["events"]]
    assert stages == ["initiated", "queued_for_pg", "reached_pg",
                      "admitted", "submitted", "commit", "commit_sent"]
    deltas = [e[0] for e in c["events"]]
    assert deltas == sorted(deltas) and deltas[0] == 0.0
    # a second finish (context-manager sugar) files nothing more
    op.finish()
    assert len([r for r in tracing.recorder().held()[0]
                if r[ID] == op.id]) == 1


def test_dump_trace_writes_the_ring_out(tmp_path):
    """The operator's use: `dump_trace` on any daemon's admin socket
    gives the process's one ring (spans, op records, the overwritten
    count); with trace_id= it still gives one blkin trace."""
    from ceph_tpu.core.admin_socket import admin_command
    from ceph_tpu.core.context import Context
    from ceph_tpu.core.optracker import OpTracker

    sock = str(tmp_path / "a.sock")
    ctx = Context("osd.0", {"admin_socket": sock, "tracing": True})
    try:
        with tracing.span("queue.batch", jobs=3, kind="encp"):
            pass
        OpTracker().create_op("osd_op(client.5:1 w)").finish(
            stage="commit_sent")
        with ctx.trace.start_span("client.op") as sp:
            sp.annotate("sent")
        out = admin_command(sock, "dump_trace", count=50)
        assert out["clock"] == "monotonic_ns"
        assert out["overwritten"] == tracing.recorder().overwritten
        last = [s for s in out["spans"] if s["name"] == "queue.batch"][-1]
        assert last["jobs"] == 3 and last["kind"] == "encp"
        assert last["duration_ns"] >= 0 and last["parent"] == 0
        assert out["ops"][-1]["desc"] == "osd_op(client.5:1 w)"
        assert out["ops"][-1]["events"][-1][1] == "commit_sent"
        one = admin_command(sock, "dump_trace",
                            trace_id=f"{sp.trace_id:x}")
        assert [s["name"] for s in one] == ["client.op"]
    finally:
        ctx.shutdown()


def test_every_span_site_uses_a_registered_name():
    """The registry names the metric each span is for; the sites in the
    tree are held to it by cephlint, and so is the queue at run time."""
    assert set(tracing.SPANS) >= {
        "queue.idle", "queue.coalesce", "queue.batch", "batch.stack",
        "batch.encode", "batch.crc_layout", "batch.crc", "batch.fanout",
        "dev.dispatch", "dev.wait", "crush.sweep"}
    assert not set(tracing.SPANS) & set(tracing.STAGES)
    assert tracing.OP_RECORD not in tracing.SPANS


@pytest.mark.parametrize("kind,engine", [
    ("encp", "native"), ("encp", "xla"), ("crep", "native"),
    ("cdec", "native")])
def test_clay_steps_are_registered_and_nest_under_batch_encode(
        kind, engine, monkeypatch):
    """The array codec's steps (ec/clay.py) through the queue's array
    branch.  On the native engine `clay.uncouple`, `clay.mds`,
    `clay.couple` are the children of an encode batch's `batch.encode`
    in that order, a call of the engine each; on a device engine (the
    XLA network here, as on a chip) the batch is ONE call under
    `clay.mds` alone, with its `dev.dispatch` and `dev.wait` below.
    Their self times are in the window the readers take; a repair's are
    one `clay.repair` with its `clay.solve` below, a layered decode's
    the `clay.solve` of each level; every step counts its bytes."""
    from ceph_tpu.ec import clay
    from ceph_tpu.ops import gf256_swar
    from ceph_tpu.tpu.queue import StripeBatchQueue

    if engine != "native":
        monkeypatch.setattr(gf256_swar, "_engine", lambda n: engine)

    names = {"clay.uncouple", "clay.mds", "clay.couple", "clay.repair",
             "clay.solve", "clay.dev_calls"}
    assert names <= set(tracing.SPANS)
    assert {tracing.SPANS[n] for n in (
        "clay.uncouple", "clay.mds", "clay.couple")} == {"clay_host_ms"}
    assert tracing.SPANS["clay.dev_calls"] == "clay_dev_calls_per_batch"
    codec = clay.ClayCodec(4, 2)
    Z, s = codec.get_sub_chunk_count(), 16
    data = np.random.default_rng(3).integers(
        0, 256, (4, Z * s), dtype=np.uint8)
    full = np.concatenate([data, codec.encode_array(data)])
    q = StripeBatchQueue(window_s=0.001)
    try:
        before, calls = q.batches, clay.dev_calls()
        if kind == "encp":
            coding, _crcs = q.encode_crc_async(codec, data).result(30.0)
            np.testing.assert_array_equal(coding, full[4:])
        elif kind == "crep":
            layers = codec.repair_layers(1)
            helpers = [0, 2, 3, 4, 5]
            got = q.clay_repair(codec, 1, helpers, np.stack(
                [full[h].reshape(Z, s)[layers] for h in helpers]))
            np.testing.assert_array_equal(got, full[1])
        else:
            got = q.clay_decode_async(
                codec, {i: full[i] for i in (1, 2, 4, 5)}).result(30.0)
            np.testing.assert_array_equal(got, data)
        w = _window_when_closed(before)
        recs, _ = tracing.recorder().held()
        batch = [r for r in recs if r[NAME] == "queue.batch"
                 and r[COUNTS].get("q") == q._span_q][-1]
        assert batch[COUNTS]["kind"] == kind
        enc, = [r for r in recs if r[NAME] == "batch.encode"
                and r[PARENT] == batch[ID]]
        kids = [r for r in recs if r[PARENT] == enc[ID]]
        below = {r[ID] for r in kids}
        steps = [r for r in recs
                 if r[NAME] in names and (r[ID] in below
                                          or r[PARENT] in below)]
        assert all(r[COUNTS]["bytes"] > 0 for r in steps)
        if kind == "encp" and engine != "native":
            assert [r[NAME] for r in kids] == ["clay.mds"]
            assert kids[0][COUNTS]["layers"] == Z
            assert [r[NAME] for r in recs if r[PARENT] == kids[0][ID]] == [
                "dev.dispatch", "dev.wait"]
            assert clay.dev_calls() - calls == 1
            # what `clay_host_ms` reads: the host work around the call
            assert 0 < w.self_ns["clay.mds"] < kids[0][T1] - kids[0][T0]
            assert not {"clay.uncouple", "clay.couple"} & set(w.self_ns)
        elif kind == "encp":
            assert [r[NAME] for r in kids] == [
                "clay.uncouple", "clay.mds", "clay.couple"]
            assert [r[COUNTS].get("pairs") for r in kids] == [
                int((~codec.dot[:4]).sum()), None,
                int((~codec.dot[4:]).sum())]
            assert kids[1][COUNTS]["layers"] == Z
            assert clay.dev_calls() - calls == 3
        elif kind == "crep":
            assert [r[NAME] for r in kids] == ["clay.repair"]
            assert [r[NAME] for r in steps] == ["clay.solve", "clay.repair"]
            assert kids[0][COUNTS]["layers"] == len(layers)
        else:
            assert kids and {r[NAME] for r in kids} <= {
                "clay.solve", "dev.dispatch", "dev.wait"}
            assert "clay.solve" in {r[NAME] for r in kids}
        # the window the benchmark's readers take holds the steps' self
        # times beside the stages', and still adds up to the batch
        assert {r[NAME] for r in steps} <= set(w.self_ns)
        under = {n_: v for n_, v in w.self_ns.items()
                 if not n_.startswith("queue.") or n_ == "queue.batch"}
        assert sum(under.values()) == batch[T1] - batch[T0]
    finally:
        q.stop()


# -- named scopes change no result ------------------------------------------

@pytest.mark.parametrize("engine", ["pallas", "xla"])
def test_named_scopes_leave_encode_array_bit_identical(engine, monkeypatch):
    """`ec.encode` and the family scope are metadata: the Pallas route
    (the interpreter here) and the XLA network give the C oracle's
    bytes, and the call left a dev.dispatch and a dev.wait span."""
    from ceph_tpu import _native
    from ceph_tpu.ops import gf256_swar

    monkeypatch.setattr(gf256_swar, "_engine", lambda n: engine)
    codec = _codec()
    data = np.random.default_rng(3).integers(
        0, 256, (4, 2048), dtype=np.uint8)
    want = _native.rs_encode(np.asarray(codec.coding, dtype=np.uint8), data)
    n0 = len(tracing.recorder().held()[0])
    np.testing.assert_array_equal(codec.encode_array(data), want)
    new = tracing.recorder().held()[0][n0:]
    assert [r[NAME] for r in new] == ["dev.dispatch", "dev.wait"]
    assert new[0][COUNTS]["family"] in ("gf256_pallas", "gf256_swar")


def test_named_scopes_leave_crc32c_rows_bit_identical():
    from ceph_tpu.core.crc import crc32c
    from ceph_tpu.ops.crc32c_device import crc32c_rows

    full = np.random.default_rng(5).integers(
        0, 256, (6, 1536), dtype=np.uint8)
    offs, lens = [0, 512, 1024], [512, 509, 300]
    got = crc32c_rows(full, offs, lens)
    assert got.shape == (3, 6)
    for j, (o, ln) in enumerate(zip(offs, lens)):
        for s in range(6):
            assert int(got[j, s]) == crc32c(full[s, o:o + ln].tobytes())


def test_named_scopes_leave_sweep_device_bit_identical():
    """`crush.fast` / `crush.mid` / `crush.slow` wrap the three stage
    programs inside the one dispatch: placements equal the host
    sweep's, and the call is one `crush.sweep` span with its
    `dev.dispatch` below it."""
    from ceph_tpu.crush import map as cmap
    from ceph_tpu.crush import mapper

    m, root = cmap.build_flat_cluster(64, hosts=8)
    steps = [(cmap.OP_TAKE, root, 0),
             (cmap.OP_CHOOSELEAF_FIRSTN, 3, 1), (cmap.OP_EMIT, 0, 0)]
    flat = m.flatten()
    dev_w = np.full(64, 0x10000, dtype=np.uint32)
    dev_w[5] = 0
    xs = np.arange(1024, dtype=np.int32)
    want = mapper.sweep(flat, steps, 3, xs, dev_w, chunk=1024)
    n0 = len(tracing.recorder().held()[0])
    got, overflow = mapper.sweep_device(flat, steps, 3, xs, dev_w,
                                        chunk=1024, bad_div=1, bad2_div=1)
    assert not bool(overflow)
    np.testing.assert_array_equal(np.asarray(got), want)
    new = tracing.recorder().held()[0][n0:]
    sweep, = [r for r in new if r[NAME] == "crush.sweep"]
    # three stage programs, each with the root's level (constants) and
    # the hosts' (read by their place in the frontier); the one-shot and
    # the budgeted program draw by fastcmp, the exact one by the tables
    assert sweep[COUNTS] == {"ids": 1024, "chunk": 1024, "numrep": 3,
                             "mode": "firstn", "cap": 1024, "cap2": 1024,
                             "budget": 3, "const": 3, "onehot": 3,
                             "gather": 0, "draw_fast": 4, "draw_class": 0,
                             "draw_table": 2, "draw_limb": 0}
    assert any(r[NAME] == "dev.dispatch" and r[PARENT] == sweep[ID]
               and r[COUNTS]["family"] == "crush_mapper" for r in new)
    # the three stage programs sit under their scopes inside the one
    # family scope: what a trace's op metadata will say
    digest = mapper._rule_digest(flat, steps, 3, None)
    run, = [v for k, v in mapper._compiled_rules.items()
            if k[:6] == (digest, "sweep_device", 1024, 1024, 1024, 1024)]
    text = run.jitted.lower(
        jnp.asarray(xs), jnp.asarray(dev_w)).as_text(debug_info=True)
    assert "jit(run)/crush_mapper/" in text
    for scope in ("crush.fast", "crush.mid", "crush.slow"):
        assert f"{scope}/jit(" in text, scope
