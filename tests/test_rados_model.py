"""Model-based randomized op testing — the RadosModel/ceph_test_rados
role (reference src/test/osd/RadosModel.h + TestRados.cc, driven by
qa/tasks/rados.py): a randomized op sequence runs against the REAL
cluster through the real client while a trivial in-memory model mirrors
every op; any divergence between cluster state and model is a
consistency bug.  Replicated and EC pools both run the same sequence
shape."""

import random

import pytest

from ceph_tpu.client.rados import RadosError
from ceph_tpu.osd import types as t_

from tests.test_osd_cluster import (EC_POOL, REP_POOL, LibClient,
                                    MiniCluster)


@pytest.fixture(scope="module")
def cluster():
    c = MiniCluster()
    yield c
    c.shutdown()


@pytest.fixture(scope="module")
def client(cluster):
    cl = LibClient(cluster)
    yield cl
    cl.shutdown()


class Model:
    """The in-memory truth: {oid: {data, xattrs, omap}} — plus the
    ACKED-MUTATION LOG that powers the durability oracle.  The model
    only updates after an op returns success, so model state IS acked
    state; `acked` remembers, per granule (data, one xattr key, one
    omap key, existence), WHICH op acked it — on divergence the report
    names the acking op instead of just the symptom."""

    def __init__(self) -> None:
        self.objs = {}
        self.acked = {}   # (oid, kind, name) -> {step, op}
        self.step = -1

    def ensure(self, oid):
        return self.objs.setdefault(
            oid, {"data": b"", "xattrs": {}, "omap": {}})

    def note_ack(self, op: str, oid: str, kind: str,
                 name: str = "") -> None:
        self.acked[(oid, kind, name)] = {"step": self.step, "op": op}

    def note_removed(self, oid: str) -> None:
        for key in [k for k in self.acked if k[0] == oid]:
            del self.acked[key]
        self.acked[(oid, "removed", "")] = {"step": self.step,
                                            "op": "remove"}


def _rollback_events_for(oid):
    """Divergent-rollback events touching `oid` (forensic channel in
    osd/pg.py): the oracle joins a lost granule to the rewind that
    destroyed it."""
    from ceph_tpu.osd.pg import ROLLBACK_EVENTS

    return [e for e in list(ROLLBACK_EVENTS)
            if any(o == oid for o, _v, _op in e["entries"])]


def _oracle_detail(model, oid, kind, name=""):
    """Acked-durability context for one lost granule: the acking op
    and any rollback events that touched the object."""
    rec = model.acked.get((oid, kind, name))
    parts = []
    if rec is not None:
        parts.append(f"ACKED at step {rec['step']} by {rec['op']}")
    else:
        parts.append("no ack recorded for this granule")
    try:
        for e in _rollback_events_for(oid):
            ents = [f"{o}@{v}" for o, v, _op in e["entries"] if o == oid]
            parts.append(f"rolled back on osd.{e['osd']} pg {e['pg']} "
                         f"to {e['target']}: {ents}")
    except Exception:
        pass
    return " [acked-durability oracle: " + "; ".join(parts) + "]"


def _run_model_sequence(io, rng, rounds, oid_space, model_box=None):
    from ceph_tpu.osd.pg import ROLLBACK_EVENTS

    # the rollback ring is process-global and oid namespaces repeat
    # across runs: stale events from an earlier (clean) run must not
    # be attributed to this run's failure provenance
    ROLLBACK_EVENTS.clear()
    model = Model()
    if model_box is not None:
        model_box.append(model)  # caller forensics see the acked log
    ops_run = {k: 0 for k in ("write_full", "write", "append",
                              "truncate", "remove", "setxattr",
                              "omap_set", "omap_rm")}
    for step in range(rounds):
        model.step = step
        oid = f"m{rng.randrange(oid_space)}"
        op = rng.choice(list(ops_run))
        try:
            if op == "write_full":
                data = rng.randbytes(rng.randrange(1, 8192))
                io.write_full(oid, data)
                model.ensure(oid)["data"] = data
                model.note_ack(op, oid, "data")
            elif op == "write":
                ent = model.ensure(oid)
                off = rng.randrange(0, 4096)
                data = rng.randbytes(rng.randrange(1, 2048))
                io.write(oid, data, off=off)
                cur = bytearray(ent["data"])
                if len(cur) < off:
                    cur.extend(b"\0" * (off - len(cur)))
                cur[off:off + len(data)] = data
                ent["data"] = bytes(cur)
                model.note_ack(op, oid, "data")
            elif op == "append":
                ent = model.ensure(oid)
                data = rng.randbytes(rng.randrange(1, 1024))
                io.append(oid, data)
                ent["data"] += data
                model.note_ack(op, oid, "data")
            elif op == "truncate":
                ent = model.ensure(oid)
                size = rng.randrange(0, 4096)
                io.truncate(oid, size)
                cur = ent["data"]
                ent["data"] = (cur[:size] if len(cur) >= size
                               else cur + b"\0" * (size - len(cur)))
                model.note_ack(op, oid, "data")
            elif op == "remove":
                if oid in model.objs:
                    io.remove(oid)
                    del model.objs[oid]
                    model.note_removed(oid)
                else:
                    with pytest.raises(RadosError):
                        io.remove(oid)
            elif op == "setxattr":
                ent = model.ensure(oid)
                k = f"x{rng.randrange(4)}"
                v = rng.randbytes(16)
                io.setxattr(oid, k, v)
                ent["xattrs"][k] = v
                model.note_ack(op, oid, "xattr", k)
            elif op == "omap_set":
                ent = model.ensure(oid)
                kv = {f"k{rng.randrange(8)}": rng.randbytes(12)
                      for _ in range(rng.randrange(1, 4))}
                io.omap_set(oid, kv)
                ent["omap"].update(kv)
                for k in kv:
                    model.note_ack(op, oid, "omap", k)
            elif op == "omap_rm":
                ent = model.objs.get(oid)
                if ent and ent["omap"]:
                    k = rng.choice(sorted(ent["omap"]))
                    io.operate(oid, [t_.OSDOp(t_.OP_OMAP_RM, keys=[k])])
                    del ent["omap"][k]
                    model.acked.pop((oid, "omap", k), None)
                else:
                    continue
            ops_run[op] += 1
        except RadosError as e:  # pragma: no cover - surface with context
            raise AssertionError(
                f"step {step}: {op} on {oid} failed rc={e.rc}") from e

        if step % 50 == 49:
            _verify(io, model)
    _verify(io, model)
    assert sum(ops_run.values()) >= rounds * 0.8  # the mix actually ran
    return ops_run


def _verify(io, model):
    """The acked-durability oracle: cluster state must equal the model
    exactly — and the model holds ONLY client-acked state, so any
    divergence is an acked mutation that was rewound.  Every failure
    message leads with "{oid}: ..." (the forensics hook keys on it)
    and carries the acking op + any rollback events for the object."""
    listed = set(io.list_objects())
    if listed != set(model.objs):
        missing = set(model.objs) - listed
        extra = listed - set(model.objs)
        detail = ""
        if missing:
            oid = sorted(missing)[0]
            detail = _oracle_detail(model, oid, "data")
        elif extra:
            detail = _oracle_detail(model, sorted(extra)[0], "removed")
        raise AssertionError(
            f"object set diverged: extra={extra} missing={missing}"
            f"{detail}")
    for oid, ent in model.objs.items():
        # ALWAYS read: an object the model says is empty must read
        # empty — skipping the read would hide a lost truncate
        try:
            got = io.read(oid)
        except RadosError as e:
            raise AssertionError(f"{oid}: read failed rc={e.rc}")
        want = ent["data"]
        # trailing zeros are representation-equivalent (sparse tails)
        assert got.rstrip(b"\0") == want.rstrip(b"\0"), (
            f"{oid}: data diverged ({len(got)}B vs {len(want)}B)"
            + _oracle_detail(model, oid, "data"))
        # ghost checks run even when the model holds NOTHING: an acked
        # removal of the last xattr/omap key followed by a rollback
        # resurrecting it is exactly the loss class the oracle exists
        # for (the model's x0..x3/k0..k7 namespaces keep internal
        # attrs like snapset out of the comparison)
        stored = {k: v for k, v in io.getxattrs(oid).items()
                  if k.startswith("x")}
        for k, v in ent["xattrs"].items():
            assert stored.get(k) == v, (
                f"{oid}: xattr {k}"
                + _oracle_detail(model, oid, "xattr", k))
        ghost = set(stored) - set(ent["xattrs"])
        assert not ghost, (
            f"{oid}: unacked xattrs resurrected: {sorted(ghost)}"
            + _oracle_detail(model, oid, "xattr", sorted(ghost)[0]))
        stored = io.omap_get(oid)
        for k, v in ent["omap"].items():
            assert stored.get(k) == v, (
                f"{oid}: omap {k}"
                + _oracle_detail(model, oid, "omap", k))
        ghost = set(stored) - set(ent["omap"])
        assert not ghost, (
            f"{oid}: unacked omap keys resurrected: "
            f"{sorted(ghost)}"
            + _oracle_detail(model, oid, "omap", sorted(ghost)[0]))


def test_rados_model_replicated(cluster, client):
    rng = random.Random(0xC3F)
    ops = _run_model_sequence(client.rc.ioctx(REP_POOL), rng,
                              rounds=300, oid_space=24)
    assert ops["remove"] > 0 and ops["write"] > 0


def test_rados_model_ec(cluster, client):
    """The same randomized consistency sweep over the EC pool: every
    op lands through the RMW/striped-shard write pipeline."""
    rng = random.Random(0xEC)
    ops = _run_model_sequence(client.rc.ioctx(EC_POOL), rng,
                              rounds=200, oid_space=16)
    assert ops["truncate"] > 0 and ops["append"] > 0


def test_rados_model_under_thrash():
    """The model sequence with an OSD thrasher bouncing daemons the
    whole time (qa/tasks/thrashosds.py + rados.py combined): every op
    either completes or retries to completion, and the full-state
    verification still holds at every checkpoint.  This hunt caught
    two real bugs when first run: PGLS omitting known-but-unrecovered
    objects, and a freshly-remapped primary serving ops BEFORE peering
    converged on the authoritative log (now gated with EAGAIN)."""
    import threading
    import time

    from tests.test_osd_cluster import N_OSDS

    c = MiniCluster()
    cl = LibClient(c)
    stop = threading.Event()

    def thrasher():
        rng = random.Random(99)
        while not stop.is_set():
            victim = rng.randrange(N_OSDS)
            try:
                c.kill(victim)
                time.sleep(rng.uniform(0.3, 0.8))
                c.revive(victim)
                time.sleep(rng.uniform(0.5, 1.0))
            except Exception:
                pass

    th = threading.Thread(target=thrasher, daemon=True)
    th.start()
    try:
        ops = _run_model_sequence(cl.rc.ioctx(REP_POOL),
                                  random.Random(0xBEEF),
                                  rounds=250, oid_space=20)
        assert sum(ops.values()) >= 200
    finally:
        stop.set()
        th.join(timeout=10)
        cl.shutdown()
        c.shutdown()


def _dump_thrash_forensics(c, err, seed, model=None):
    """PR-4 caveat follow-up: the EC thrash model flaked ONCE at seed
    0x1EC with a byte mismatch and left nothing to analyze.  On any
    model divergence, capture the failing seed plus a full shard dump
    (per-osd chunk lengths/crcs/_av stamps, pg state/missing/log
    heads) into scratch/ BEFORE the cluster is torn down, so the next
    occurrence is a root-cause session instead of a shrug."""
    import json
    import os
    import time as _time

    from ceph_tpu.core.crc import crc32c
    from ceph_tpu.osd import types as ot
    from ceph_tpu.store.objectstore import Collection, GHObject

    from ceph_tpu.tpu.queue import default_queue

    # staging-pool state rides every forensics dump (PR 6): a
    # divergence with slots still held or host touches recorded
    # implicates the device-resident path's buffer lifecycle, one
    # without them exonerates it
    _dq = default_queue()
    report = {"seed": hex(seed), "time": _time.time(), "error": str(err),
              "osds_up": {i: o.up for i, o in c.osds.items()},
              "staging_pool": {
                  "occupancy": _dq.pool.occupancy,
                  "slots": _dq.pool.nslots,
                  "slot_bytes": _dq.pool.slot_bytes,
                  **_dq.stats.snapshot()},
              "pgs": {}, "object": {}}
    # the _verify assertions lead with "{oid}: ..."
    oid = str(err).split(":", 1)[0].strip() or None
    # the acked-mutation log (oracle): which op acked each granule of
    # the diverged object, plus every divergent-rollback event — the
    # PR-7 schema addition that turns a symptom into a provenance
    from ceph_tpu.osd.pg import ROLLBACK_EVENTS

    report["rollback_events"] = list(ROLLBACK_EVENTS)
    # op-observability evidence (PR 8): every OSD's slow-op ring and
    # in-flight op timelines ride the dump — a divergence now shows
    # WHERE the implicated ops spent their time (stage events), not
    # just what state they left behind.  Down OSDs included: a killed
    # daemon's drained history is exactly the kill-window testimony.
    report["slow_ops"] = {}
    report["ops_in_flight"] = {}
    for i, o in c.osds.items():
        trk = getattr(o, "op_tracker", None)
        if trk is None:
            continue
        try:
            report["slow_ops"][f"osd{i}"] = trk.dump_slow()
            report["ops_in_flight"][f"osd{i}"] = trk.dump_in_flight()
        except Exception as e:  # best-effort forensics
            report["slow_ops"][f"osd{i}"] = {"error": repr(e)}
    if model is not None and oid:
        report["acked_mutations"] = {
            f"{kind}:{name}" if name else kind: rec
            for (o, kind, name), rec in sorted(model.acked.items())
            if o == oid}
    for i, o in c.osds.items():
        if not o.up:
            continue
        for pgid, pg in o.pgs.items():
            if pgid[0] != EC_POOL:
                continue
            key = f"osd{i}.pg{pgid[0]}.{pgid[1]:x}"
            try:
                with pg.lock:
                    report["pgs"][key] = {
                        "state": pg.state, "acting": list(pg.acting),
                        "primary": pg.primary,
                        "log_head": str(pg.log.head),
                        "missing": {k: str(v)
                                    for k, v in pg.missing.items()},
                        "stale_peers": sorted(pg.stale_peers),
                    }
            except Exception as e:  # best-effort forensics
                report["pgs"][key] = {"error": repr(e)}
            if not oid:
                continue
            coll = Collection(ot.pgid_str(pgid) + "_head")
            shards = {}
            for s in range(pg.backend.k + pg.backend.m):
                g = GHObject(oid, shard=s)
                try:
                    if not o.store.exists(coll, g):
                        continue
                    data = o.store.read(coll, g)
                    attrs = o.store.getattrs(coll, g)
                    shards[s] = {
                        "len": len(data), "crc": hex(crc32c(data)),
                        "_av": attrs.get("_av", b"").hex(),
                        "hinfo": attrs.get("hinfo", b"").hex(),
                    }
                except Exception as e:
                    shards[s] = {"error": repr(e)}
            if shards:
                en = pg.log.latest_for(oid)
                report["object"][key] = {
                    "shards": shards,
                    "latest_entry": (None if en is None else
                                     f"op={en.op} v={en.version}"),
                }
    out = os.path.abspath(os.path.join(
        os.path.dirname(__file__), "..", "scratch",
        f"thrash_ec_forensics_{seed:#x}.json"))
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True, default=str)


def test_rados_model_ec_under_thrash():
    """The EC-pool model sequence under OSD thrashing: the hunt that
    drove the round's EC consistency fixes (deletion-push guard,
    backfill authority incl. peer missing sets, source-ranked reads
    with _av attr-version metas, retryable watchdog reads, interval-
    token activations).  Seed 0x1EC was a deterministic xattr-loss
    repro before those fixes."""
    import threading
    import time

    from tests.test_osd_cluster import N_OSDS

    c = MiniCluster()
    cl = LibClient(c)
    stop = threading.Event()

    def thrasher():
        rng = random.Random(0x1EC ^ 3)
        while not stop.is_set():
            victim = rng.randrange(N_OSDS)
            try:
                c.kill(victim)
                time.sleep(rng.uniform(0.4, 0.9))
                c.revive(victim)
                time.sleep(rng.uniform(0.6, 1.2))
            except Exception:
                pass

    th = threading.Thread(target=thrasher, daemon=True)
    th.start()
    model_box = []
    try:
        try:
            ops = _run_model_sequence(cl.rc.ioctx(EC_POOL),
                                      random.Random(0x1EC),
                                      rounds=150, oid_space=16,
                                      model_box=model_box)
        except AssertionError as e:
            # capture the shard-level evidence while the cluster is
            # still alive (PR-4's seed byte-mismatch flake left none)
            stop.set()
            th.join(timeout=10)
            _dump_thrash_forensics(
                c, e, seed=0x1EC,
                model=model_box[0] if model_box else None)
            raise
        assert sum(ops.values()) >= 120
    finally:
        stop.set()
        th.join(timeout=10)
        cl.shutdown()
        c.shutdown()
