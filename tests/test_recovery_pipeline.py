"""Windowed EC recovery engine + recover-on-read (osd/recovery.py).

The read-side twin of the PR-4 write-pipeline tests: W-object windowed
pulls land every object with correct _av stamps and an incrementally
draining pg.missing; sub-reads aggregate into ONE MECSubReadVec per
peer per round (not per object); a peer that only speaks legacy
MECSubRead still completes the window (mixed-version fallback); a peer
killed mid-window degrades to the survivors without losing window
slots; and a read of a missing object promotes it to the front of the
window and is served within one recovery round (recover-on-read)
instead of EAGAINing until the whole pull finishes.
"""

import sys, os
import threading
import time
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(__file__))
from test_osd_cluster import EC_POOL, LibClient, MiniCluster, N_OSDS

from ceph_tpu.core.context import Context
from ceph_tpu.ec import codec_from_profile
from ceph_tpu.msg.message import EntityName
from ceph_tpu.osd import messages as m
from ceph_tpu.osd import types as t_
from ceph_tpu.osd.backend import _av_stamp, _hinfo
from ceph_tpu.osd.daemon import OSDService
from ceph_tpu.osd.pg import PG, STATE_DEGRADED, STATE_PEERING
from ceph_tpu.osd.types import EVersion, LogEntry
from ceph_tpu.store.memstore import MemStore
from ceph_tpu.store.objectstore import GHObject, Transaction

EAGAIN = -11


# ---------------------------------------------------------------------------
# stub harness: a real PG + ECBackend over a MemStore with a scripted
# "cluster" around it, so vec aggregation / fallback / peer-death paths
# are exercised deterministically without sockets
# ---------------------------------------------------------------------------


class _Perf:
    def __init__(self):
        self.vals = {}

    def inc(self, name, by=1):
        self.vals[name] = self.vals.get(name, 0) + by

    def set(self, name, v):
        self.vals[name] = v

    def value(self, name, default=0):
        return self.vals.get(name, default)


class _StubMap:
    def __init__(self, down=()):
        self.down = set(down)

    def is_up(self, o):
        return o not in self.down


class _StubOSD:
    """Duck-typed OSDService host: records sends, lets the test answer
    them (optionally through an auto-responder)."""

    def __init__(self, whoami, peers, conf=None):
        self.whoami = whoami
        self.ctx = Context(f"stub.osd{whoami}", conf or {})
        self.store = MemStore()
        self.store.mkfs()
        self.store.mount()
        self.addr_book = {p: ("stub", p) for p in peers}
        self.osdmap = _StubMap()
        self.sent = []
        self.responder = None  # fn(osd_id, msg) -> None
        self._read_cbs = {}
        self._tid = 0
        self._tid_lock = threading.Lock()
        self.perf = _Perf()
        self.pg_perf = _Perf()

    def epoch(self):
        return 7

    def _log(self, lvl, msg):
        pass

    def new_tid(self):
        with self._tid_lock:
            self._tid += 1
            return self._tid

    def track_reads(self, pgid, cb, count=None):
        tid = self.new_tid()
        self._read_cbs[tid] = cb
        return tid

    def untrack_reads(self, tid):
        self._read_cbs.pop(tid, None)

    def send_to_osd(self, osd_id, msg):
        self.sent.append((osd_id, msg))
        if self.responder is not None:
            self.responder(osd_id, msg)

    def reply(self, tid, rep):
        cb = self._read_cbs.get(tid)
        if cb is not None:
            cb(rep)

    def note_recovery_active(self, n):
        if n > self.pg_perf.vals.get("recovery_active", 0):
            self.pg_perf.set("recovery_active", n)


def _stub_pg(profile, acting, whoami=0, peers=(1, 2), conf=None):
    osd = _StubOSD(whoami, peers, conf=conf)
    codec = codec_from_profile(profile)
    pool = SimpleNamespace(size=len(acting), hit_set_count=0)
    pg = PG((3, 0), pool, osd, codec)
    t = Transaction()
    t.create_collection(pg.coll)
    osd.store.queue_transaction(t)
    with pg.lock:
        pg.acting = list(acting)
        pg.primary = whoami
        pg.state = STATE_DEGRADED
    return pg, osd


def _seed_missing(pg, oids, payload=b"r" * 4096):
    """Log entries + missing marks for `oids`; returns the per-oid
    chunk set a peer serves from (encoded with the pg's own codec)."""
    chunks = {}
    base = pg.log.head.version
    for i, oid in enumerate(sorted(oids)):
        v = EVersion(7, base + i + 1)
        data = oid.encode() + payload
        with pg.lock:
            pg.log.append(LogEntry(op=t_.LOG_MODIFY, oid=oid, version=v,
                                   prior_version=EVersion(0, 0)))
            pg.missing[oid] = v
        cs, _ = pg.backend._encode_object(data)
        chunks[oid] = (cs, v, data)
    return chunks


def _peer_row(chunks, oid, shard):
    cs, v, data = chunks[oid]
    attrs = {"hinfo": _hinfo(cs[shard], len(data)), "_av": _av_stamp(v)}
    return (shard, oid, cs[shard], 0, attrs, {})


def _vec_responder(osd, chunks, answer_peers=None, src_epoch=7):
    """Auto-answer vec (and legacy) sub-reads with the right chunks."""

    def respond(osd_id, msg):
        if answer_peers is not None and osd_id not in answer_peers:
            return
        if isinstance(msg, m.MECSubReadVec):
            rows = [_peer_row(chunks, oid, shard)
                    for shard, oid, _o, _l in msg.reads]
            rep = m.MECSubReadVecReply((3, 0), src_epoch, rows)
        elif isinstance(msg, m.MECSubRead):
            row = _peer_row(chunks, msg.oid, msg.shard)
            rep = m.MECSubReadReply((3, 0), src_epoch, msg.shard,
                                    msg.oid, row[2], 0, row[4], row[5])
        else:
            return
        rep.tid = msg.tid
        rep.src = EntityName("osd", osd_id)
        osd.reply(msg.tid, rep)

    return respond


def test_vec_subread_aggregation_one_msg_per_peer_per_round():
    """k=4,m=2 over 3 OSDs (each holds two shards): a 5-object window
    costs one MECSubReadVec per PEER per round — 2 rounds x 2 peers =
    4 messages, not 5 objects x 2 peers (let alone per shard) — and
    every object lands with the right chunk bytes and _av stamp."""
    pg, osd = _stub_pg("plugin=isa k=4 m=2 technique=reed_sol_van",
                       acting=[0, 1, 2, 0, 1, 2], peers=(1, 2))
    oids = [f"agg{i}" for i in range(5)]
    chunks = _seed_missing(pg, oids)
    osd.responder = _vec_responder(osd, chunks)
    pg.recovery_engine().recover(
        {oid: pg.log.latest_for(oid) for oid in oids})
    with pg.lock:
        assert not pg.missing, f"window left objects: {pg.missing}"
    vecs = [(o, v) for o, v in osd.sent if isinstance(v, m.MECSubReadVec)]
    assert vecs, "no vec sub-reads sent"
    assert len(vecs) == 4, (  # ceil(5/3)=2 rounds x 2 peers
        f"{len(vecs)} vec messages for 5 objects over 2 peers — "
        f"expected 4 (one per peer per round)")
    # first-round vecs carry all 3 objects' rows for both peer shards
    first = [v for _o, v in vecs[:2]]
    assert all(len(v.reads) == 6 for v in first), \
        [len(v.reads) for v in first]
    assert osd.pg_perf.vals.get("subread_msgs") == 4
    assert osd.pg_perf.vals.get("subread_ops") == 5
    assert osd.pg_perf.vals.get("recovery_active", 0) >= 3
    # decode really rode the batch queue (shards 0,3 were missing)
    assert osd.pg_perf.vals.get("decode_batch_jobs", 0) >= 1
    for oid in oids:
        cs, v, data = chunks[oid]
        for shard in (0, 3):
            g = GHObject(oid, shard=shard)
            assert osd.store.read(pg.coll, g) == cs[shard], \
                f"{oid} shard {shard}: wrong recovered bytes"
            assert osd.store.getattr(pg.coll, g, "_av") == _av_stamp(v)


def test_mixed_version_peer_falls_back_to_legacy_subreads():
    """One peer never answers the vec (an old build would not even
    decode it): after the read window it gets ONE legacy per-shard
    retry, the window still completes, and the peer is remembered as
    legacy-only — the next window skips the vec for it entirely."""
    pg, osd = _stub_pg(
        "plugin=isa k=4 m=2 technique=reed_sol_van",
        acting=[0, 1, 2, 0, 1, 2], peers=(1, 2),
        conf={"osd_recovery_read_timeout": 0.5})
    oids = ["mv0", "mv1"]
    chunks = _seed_missing(pg, oids)

    base = _vec_responder(osd, chunks)

    def legacy_peer1(osd_id, msg):
        if osd_id == 1 and isinstance(msg, m.MECSubReadVec):
            return  # peer 1 "cannot decode" the vec: silence
        base(osd_id, msg)

    osd.responder = legacy_peer1
    t0 = time.monotonic()
    pg.recovery_engine().recover(
        {oid: pg.log.latest_for(oid) for oid in oids})
    with pg.lock:
        assert not pg.missing, f"fallback never completed: {pg.missing}"
    assert time.monotonic() - t0 < 5.0
    legacy = [(o, v) for o, v in osd.sent
              if isinstance(v, m.MECSubRead) and o == 1]
    assert len(legacy) == 4, (  # 2 oids x peer 1's two shards
        f"expected 4 legacy sub-reads to the vec-less peer, "
        f"got {len(legacy)}")
    assert 1 in pg.recovery_engine()._no_vec
    # second window: peer 1 goes straight to legacy, peer 2 keeps vec
    osd.sent.clear()
    more = ["mv2", "mv3"]
    chunks2 = _seed_missing(pg, more, payload=b"s" * 4096)
    chunks.update(chunks2)
    pg.recovery_engine().recover(
        {oid: pg.log.latest_for(oid) for oid in more})
    with pg.lock:
        assert not pg.missing
    p1_msgs = [v for o, v in osd.sent if o == 1]
    assert p1_msgs and all(isinstance(v, m.MECSubRead) for v in p1_msgs)
    p2_msgs = [v for o, v in osd.sent if o == 2]
    assert p2_msgs and all(isinstance(v, m.MECSubReadVec)
                           for v in p2_msgs)


def test_kill_peer_mid_window_degrades_to_survivors():
    """k=2,m=2 over four holders: a peer that dies after the window's
    vec sub-reads went out must not burn the read timeout per object —
    peer_down fails its outstanding rows, and every object still
    recovers from the surviving k holders (no lost window slots)."""
    pg, osd = _stub_pg(
        "plugin=isa k=2 m=2 technique=reed_sol_van",
        acting=[0, 1, 2, 3], peers=(1, 2, 3),
        conf={"osd_recovery_read_timeout": 5.0})
    oids = [f"kp{i}" for i in range(4)]
    chunks = _seed_missing(pg, oids)
    held = []  # peer 1's vecs, answered only after the death below

    base = _vec_responder(osd, chunks)

    def respond(osd_id, msg):
        if osd_id == 3:
            return  # peer 3 dies before answering
        if osd_id == 1 and isinstance(msg, m.MECSubReadVec):
            held.append(msg)
            return
        base(osd_id, msg)

    osd.responder = respond
    done = []
    th = threading.Thread(
        target=lambda: (pg.recovery_engine().recover(
            {oid: pg.log.latest_for(oid) for oid in oids}),
            done.append(1)),
        daemon=True)
    t0 = time.monotonic()
    th.start()
    deadline = time.monotonic() + 5.0
    while not held and time.monotonic() < deadline:
        time.sleep(0.02)
    assert held, "peer 1 never got its vec"
    # the map marks peer 3 down mid-window
    osd.osdmap = _StubMap(down={3})
    pg.note_peers_down({3})
    for msg in held:  # peer 1 answers late
        base(1, msg)
    held.clear()
    osd.responder = lambda o, v: (None if o == 3 else base(o, v))
    th.join(timeout=10.0)
    assert done, "window wedged after mid-window peer death"
    # fail-fast: nothing waited out the 5s read timeout on peer 3
    assert time.monotonic() - t0 < 4.5
    with pg.lock:
        assert not pg.missing, f"lost window slots: {pg.missing}"


def test_park_read_serves_after_recovery_and_times_out_honestly():
    pg, osd = _stub_pg(
        "plugin=isa k=4 m=2 technique=reed_sol_van",
        acting=[0, 1, 2, 0, 1, 2], peers=(1, 2),
        conf={"osd_recovery_read_timeout": 0.4})
    chunks = _seed_missing(pg, ["pk0"])
    osd.responder = _vec_responder(osd, chunks)
    got = []
    ev = threading.Event()
    assert pg.recovery_engine().park_read(
        "pk0", lambda ok: (got.append(ok), ev.set()))
    assert ev.wait(10.0), "parked read never woken"
    assert got == [True]
    with pg.lock:
        assert "pk0" not in pg.missing
    # an object nobody can serve: the parked read answers False
    # (EAGAIN) within the bounded wait, not never
    _seed_missing(pg, ["pk1"], payload=b"t" * 4096)
    osd.responder = None  # every peer silent now
    got2, ev2 = [], threading.Event()
    assert pg.recovery_engine().park_read(
        "pk1", lambda ok: (got2.append(ok), ev2.set()))
    assert ev2.wait(10.0), "bounded wait never fired"
    assert got2 == [False]
    # already-recovered object: park refuses, caller re-checks
    assert not pg.recovery_engine().park_read("pk0", lambda ok: None)


# ---------------------------------------------------------------------------
# cluster integration: the real pull path over sockets
# ---------------------------------------------------------------------------


def _same_pg_oids(c, n, prefix):
    """n object names all landing in one EC pg; returns (pgid, oids)."""
    target = c.osdmap.object_to_pg(EC_POOL, f"{prefix}0")
    oids = []
    i = 0
    while len(oids) < n:
        oid = f"{prefix}{i}"
        if c.osdmap.object_to_pg(EC_POOL, oid) == target:
            oids.append(oid)
        i += 1
        assert i < 2000, "could not find same-pg names"
    return target, oids


def _revive_hooked(c, osd_id, pre_activate=None):
    """MiniCluster.revive with a hook between daemon construction and
    activation (to wrap send_to_osd etc.), optionally without the
    settle wait."""
    from tests.test_osd_cluster import MiniCluster as _MC  # noqa: F401

    old = c.osds[osd_id]
    svc = OSDService(c.ctx, osd_id, old.store, c.osdmap,
                     codec_from_profile)
    svc.init()
    c.osds[osd_id] = svc
    if pre_activate is not None:
        pre_activate(svc)
    c.osdmap.set_osd_up(osd_id)
    c.refresh()
    for o in c.osds.values():
        if o.up:
            o.activate_pgs()
    return svc


def test_windowed_pull_end_to_end():
    """Kill an EC pg's primary, write 8 objects degraded, revive it:
    the revived primary recovers every object through the windowed
    engine — aggregated vec sub-reads (< 1 message per object per
    peer), correct post-recovery bytes and _av stamps, drained
    missing set, and a recovery_active high-water > 1."""
    c = MiniCluster()
    cl = LibClient(c)
    try:
        pgid, oids = _same_pg_oids(c, 8, "wp")
        _pg, acting, primary = c.primary_of(EC_POOL, oids[0])
        for oid in oids:
            assert cl.put(EC_POOL, oid,
                          f"{oid}-v1".encode() * 100).result == 0
        c.kill(primary)
        for oid in oids:
            assert cl.put(EC_POOL, oid,
                          f"{oid}-v2".encode() * 100).result == 0

        vec_msgs = []

        def hook(svc):
            orig = svc.send_to_osd

            def spy(osd_id, msg):
                if isinstance(msg, m.MECSubReadVec) \
                        and msg.pgid == pgid:
                    vec_msgs.append((osd_id, msg))
                orig(osd_id, msg)

            svc.send_to_osd = spy

        svc = _revive_hooked(c, primary, pre_activate=hook)
        for o in c.osds.values():
            if o.up:
                o.wait_pgs_settled(20.0)
        pg = svc.pgs[pgid]
        with pg.lock:
            assert not pg.missing, f"pull left missing: {pg.missing}"
        for oid in oids:
            assert cl.get(EC_POOL, oid) == f"{oid}-v2".encode() * 100
        assert vec_msgs, "pull never used vec sub-reads"
        # aggregation: 8 objects over 2 peers at W=3 is <= 6 vecs;
        # the old shape was one message per (object, peer) = 16
        assert len(vec_msgs) <= 8, (
            f"{len(vec_msgs)} vec messages for 8 objects — "
            "window aggregation is not happening")
        perf = svc.pg_perf.dump()
        assert perf.get("recovery_active", 0) >= 2, perf
        assert perf.get("subread_ops", 0) >= 8, perf
        # recovered shards carry the newest entry's _av stamp
        n = pg.backend.k + pg.backend.m
        my_shards = pg.backend.local_shards(pg.acting[:n])
        for oid in oids:
            en = pg.log.latest_for(oid)
            for shard in my_shards:
                got = svc.store.getattr(pg.coll,
                                        GHObject(oid, shard=shard),
                                        "_av")
                assert got == _av_stamp(en.version), \
                    f"{oid} shard {shard}: stale recovery stamp"
    finally:
        cl.shutdown()
        c.shutdown()


def test_recover_on_read_serves_before_full_pull():
    """With a slow 32-object pull at window W=1, a read of an object
    deep in the queue promotes it and is served by its own recovery
    round — while most of the pull is still outstanding — instead of
    EAGAINing until the end (recover_on_read_hits proves the parked
    read was woken by recovery, not by luck)."""
    c = MiniCluster()
    cl = LibClient(c)
    c.ctx.conf.set_val("osd_recovery_max_active", 1, force=True)
    try:
        pgid, oids = _same_pg_oids(c, 32, "rr")
        _pg, acting, primary = c.primary_of(EC_POOL, oids[0])
        for oid in oids:
            assert cl.put(EC_POOL, oid,
                          f"{oid}|A".encode() * 64).result == 0
        c.kill(primary)
        for oid in oids:
            assert cl.put(EC_POOL, oid,
                          f"{oid}|B".encode() * 64).result == 0
        # slow every surviving peer's vec answer: 0.25s per window
        # round makes the 32-round pull take 8 s, of which more than
        # 8 objects are missing for 5.75 s: room for a loaded rig to
        # get from the revive to the read
        round_s = [0.25]
        for o in c.osds.values():
            if not o.up or pgid not in o.pgs:
                continue
            opg = o.pgs[pgid]
            orig = opg.handle_sub_read_vec

            def slow(msg, conn, _orig=orig):
                time.sleep(round_s[0])
                _orig(msg, conn)

            opg.handle_sub_read_vec = slow
        svc = _revive_hooked(c, primary)  # no settle wait
        pg = svc.pgs[pgid]
        target = sorted(oids)[-1]  # recovered LAST in queue order
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            with pg.lock:
                started = (pg.state != STATE_PEERING
                           and target in pg.missing
                           and len(pg.missing) > 8)
            if started:
                break
            time.sleep(0.05)
        assert started, "pull drained before the read could race it"
        rep = cl.op(EC_POOL, target, [t_.OSDOp(t_.OP_READ)],
                    timeout=15.0)
        assert rep.result == 0, f"promoted read failed: {rep.result}"
        assert rep.ops[0].out_data == f"{target}|B".encode() * 64
        with pg.lock:
            left = len(pg.missing)
        assert left > 0, (
            "read only completed after the full pull — promotion "
            "did not shortcut the window")
        hits = svc.pg_perf.dump().get("recover_on_read_hits", 0)
        assert hits >= 1, "no parked read was woken by recovery"
        round_s[0] = 0.0   # the race is over: let the pull drain
        for o in c.osds.values():
            if o.up:
                o.wait_pgs_settled(30.0)
        for oid in oids:
            assert cl.get(EC_POOL, oid) == f"{oid}|B".encode() * 64
    finally:
        c.ctx.conf.set_val("osd_recovery_max_active", 3, force=True)
        cl.shutdown()
        c.shutdown()
