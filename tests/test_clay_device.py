"""Clay repair on the device queue (PR 19): the batched coupled-layer
kernels ("crep"/"cdec" StripeBatchQueue kinds) are bit-exact against
the host codec API across (k,m,d) configs — ragged tails and every
lost-shard index included — and a degraded clay pool recovers through
the SUB-CHUNK read plan end to end: one MECSubReadVec runs tail per
helper, layers-only wire payloads, the repair_read_frac gauge landing
at ~d/(k*q), and the recovered shard carrying the recovery _av stamp.
"""

import sys, os
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from test_recovery_pipeline import _seed_missing, _stub_pg

from ceph_tpu.ec.clay import ClayCodec
from ceph_tpu.msg.message import EntityName
from ceph_tpu.osd import messages as m
from ceph_tpu.osd.backend import _av_stamp, _hinfo
from ceph_tpu.store.objectstore import GHObject
from ceph_tpu.tpu.queue import StripeBatchQueue


def _chunks(codec, s, seed=0):
    """Random data planes [k, Z*s] + parity via the codec: returns the
    full chunk list (row i = chunk i, flat uint8 [Z*s])."""
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, size=(codec.k, codec.sub_count * s),
                        dtype=np.uint8)
    parity = np.asarray(codec.encode_array(data), dtype=np.uint8)
    return [np.ascontiguousarray(r) for r in np.vstack([data, parity])]


def _repair_planes(codec, chunks, lost, s):
    """Layers-only helper planes [d, L, s] for a single-shard repair —
    exactly what the sub-chunk read plan pulls over the wire."""
    layers = codec.repair_layers(lost)
    helpers = [i for i in range(codec.k + codec.m) if i != lost][:codec.d]
    planes = np.stack([
        chunks[h].reshape(codec.sub_count, s)[layers] for h in helpers])
    return helpers, planes


def _sweep_crep(k, m, s, seed):
    """Every lost-shard index through the queue's crep kind: the device
    result must match BOTH the original chunk and the host repair API."""
    codec = ClayCodec(k=k, m=m)
    chunks = _chunks(codec, s, seed=seed)
    q = StripeBatchQueue(window_s=0.001)
    try:
        for lost in range(k + m):
            helpers, planes = _repair_planes(codec, chunks, lost, s)
            got = np.asarray(q.clay_repair(codec, lost, helpers, planes))
            np.testing.assert_array_equal(
                got, chunks[lost].ravel(),
                err_msg=f"k{k}m{m} s={s}: device repair of shard {lost}")
            host = codec.repair_chunk(
                [lost], {h: chunks[h] for h in helpers})[lost]
            np.testing.assert_array_equal(
                got, np.asarray(host).ravel(),
                err_msg=f"k{k}m{m} s={s}: device vs host, shard {lost}")
    finally:
        q.stop()


def _sweep_cdec(k, m, s, seed):
    """Erasure patterns through the queue's cdec kind vs the host
    decode: data planes must come back bit-exact."""
    codec = ClayCodec(k=k, m=m)
    chunks = _chunks(codec, s, seed=seed)
    want = np.stack(chunks[:k])
    q = StripeBatchQueue(window_s=0.001)
    rng = np.random.default_rng(seed + 1)
    try:
        for _ in range(4):
            n_erase = int(rng.integers(1, m + 1))
            erased = set(rng.choice(k + m, size=n_erase,
                                    replace=False).tolist())
            avail = {i: chunks[i] for i in range(k + m) if i not in erased}
            got = np.asarray(q.clay_decode_async(codec, avail).result())
            np.testing.assert_array_equal(
                got, want, err_msg=f"k{k}m{m} s={s}: erased={erased}")
    finally:
        q.stop()


def test_crep_device_bit_exact_every_lost_shard_k4m2():
    # s=40: a ragged (non-pow2) per-layer width — the covering pad in
    # _dispatch_array must never leak into real bytes
    _sweep_crep(4, 2, s=40, seed=3)


def test_cdec_device_bit_exact_k4m2():
    _sweep_cdec(4, 2, s=40, seed=7)


def test_crep_ragged_tail_widths():
    """Odd per-layer widths (1, 5, 7 bytes) through the bucketed
    dispatch: the smallest shapes stress the pad-then-slice path."""
    codec = ClayCodec(k=4, m=2)
    q = StripeBatchQueue(window_s=0.001)
    try:
        for s in (1, 5, 7):
            chunks = _chunks(codec, s, seed=s)
            lost = 3
            helpers, planes = _repair_planes(codec, chunks, lost, s)
            got = np.asarray(q.clay_repair(codec, lost, helpers, planes))
            np.testing.assert_array_equal(
                got, chunks[lost].ravel(), err_msg=f"s={s}")
    finally:
        q.stop()


@pytest.mark.parametrize("k,m,s", [(8, 4, 33), (5, 3, 17)])
def test_crep_device_bit_exact_full_matrix(k, m, s):
    """Bigger geometries (k8m4 = the paper's headline config, k5m3 =
    shortened construction with a virtual node) across every lost
    shard, ragged widths — small widths keep this tier-1 fast."""
    _sweep_crep(k, m, s=s, seed=k * 31 + m)
    _sweep_cdec(k, m, s=s, seed=k * 37 + m)


def test_crep_jobs_coalesce_into_one_batch():
    """Concurrent repairs of the SAME lost shard (a recovery window
    draining one dead OSD) must coalesce along the S axis — and every
    job in the batch still comes back bit-exact."""
    codec = ClayCodec(k=4, m=2)
    q = StripeBatchQueue(window_s=0.25)
    try:
        jobs = []
        for seed in range(6):
            chunks = _chunks(codec, 24, seed=seed)
            helpers, planes = _repair_planes(codec, chunks, 2, 24)
            jobs.append((chunks, q.clay_repair_async(
                codec, 2, helpers, planes)))
        for chunks, fut in jobs:
            np.testing.assert_array_equal(
                np.asarray(fut.result()), chunks[2].ravel())
        # 6 jobs enqueued within one coalescing window: at most the
        # first dispatches alone before the rest pile up
        assert q.batches <= 3, f"{q.batches} batches for 6 same-sig jobs"
        assert max(q.dec_batch_jobs) >= 2, q.dec_batch_jobs
    finally:
        q.stop()


# ---------------------------------------------------------------------------
# degraded clay pool, end to end: sub-chunk plan -> layers-only wire ->
# crep kernel -> _store_repaired, with the counter evidence
# ---------------------------------------------------------------------------

CLAY_PROFILE = "plugin=clay k=8 m=4 d=11"


def _clay_vec_responder(osd, chunks, Z, src_epoch=7, mute=(), unit=4096):
    """Answer MECSubReadVec honoring the v2 runs tail: a row with runs
    gets ONLY those sub-chunk extents of every stripe back (served=1;
    a stripe's `unit` bytes are a codeword's chunk of Z sub-chunks), an
    empty-runs row gets the whole chunk (served=0) — a peer in `mute`
    never answers rows that carry runs (plan-failure injection)."""

    def respond(osd_id, msg):
        if not isinstance(msg, m.MECSubReadVec):
            return
        run_plans = (msg.runs if len(msg.runs) == len(msg.reads)
                     else [[] for _ in msg.reads])
        if osd_id in mute and any(run_plans):
            return
        rows, served = [], []
        for (shard, oid, _o, _l), rr in zip(msg.reads, run_plans):
            cs, v, data = chunks[oid]
            chunk = bytes(cs[shard])
            attrs = {"hinfo": _hinfo(cs[shard], len(data)),
                     "_av": _av_stamp(v)}
            if rr:
                sub = unit // Z
                blob = b"".join(
                    chunk[base + so * sub:base + (so + cnt) * sub]
                    for base in range(0, len(chunk), unit)
                    for so, cnt in rr)
                rows.append((shard, oid, blob, 0, attrs, {}))
                served.append(1)
            else:
                rows.append((shard, oid, chunk, 0, attrs, {}))
                served.append(0)
        rep = m.MECSubReadVecReply((3, 0), src_epoch, rows, served=served)
        rep.tid = msg.tid
        rep.src = EntityName("osd", osd_id)
        osd.reply(msg.tid, rep)

    return respond


def test_clay_degraded_recovery_uses_subchunk_plan_e2e():
    """k=8,m=4,d=11 clay pool, primary missing its single local shard
    for a window of objects: recovery sends per-helper RUN tails, the
    wire carries only repair layers, every object lands with correct
    chunk bytes + recovery _av stamp, and repair_read_frac measures
    ~d/(k*q) = 344 permille — the ISSUE's <= 0.4 acceptance."""
    pg, osd = _stub_pg(CLAY_PROFILE, acting=list(range(12)),
                       whoami=0, peers=tuple(range(1, 12)))
    Z = pg.backend.codec.get_sub_chunk_count()
    oids = [f"clay{i}" for i in range(3)]
    chunks = _seed_missing(pg, oids)
    osd.responder = _clay_vec_responder(osd, chunks, Z)
    pg.recovery_engine().recover(
        {oid: pg.log.latest_for(oid) for oid in oids})
    with pg.lock:
        assert not pg.missing, f"window left objects: {pg.missing}"
    # the plan actually engaged: every helper's vec row carried runs
    vecs = [v for _o, v in osd.sent if isinstance(v, m.MECSubReadVec)]
    assert vecs and all(
        all(rr for rr in v.runs) for v in vecs), \
        [v.runs for v in vecs]
    # layers-only wire: the ratio gauge sits at the MSR point
    frac = osd.pg_perf.value("repair_read_frac")
    assert 0 < frac <= 400, f"repair_read_frac={frac} permille"
    assert osd.pg_perf.value("subread_bytes") > 0
    # the repair rode the device queue, not a host bypass
    assert osd.pg_perf.value("decode_batch_jobs") >= 1
    for oid in oids:
        cs, v, _data = chunks[oid]
        g = GHObject(oid, shard=0)
        assert osd.store.read(pg.coll, g) == bytes(cs[0]), \
            f"{oid}: wrong repaired bytes"
        assert osd.store.getattr(pg.coll, g, "_av") == _av_stamp(v)


def test_clay_plan_helper_failure_falls_back_whole_chunk():
    """A helper that never answers the sub-chunk round: attempt 1 times
    out retryable, attempt 2 re-gathers WHOLE chunks (no runs) and the
    object still lands — the plan can only save bytes, never lose an
    object."""
    pg, osd = _stub_pg(CLAY_PROFILE, acting=list(range(12)),
                       whoami=0, peers=tuple(range(1, 12)),
                       conf={"osd_recovery_read_timeout": 0.5})
    Z = pg.backend.codec.get_sub_chunk_count()
    chunks = _seed_missing(pg, ["cfb0"])
    osd.responder = _clay_vec_responder(osd, chunks, Z, mute={11})
    t0 = time.monotonic()
    pg.recovery_engine().recover({"cfb0": pg.log.latest_for("cfb0")})
    assert time.monotonic() - t0 < 8.0
    with pg.lock:
        assert not pg.missing, "fallback never landed the object"
    cs, v, _data = chunks["cfb0"]
    g = GHObject("cfb0", shard=0)
    assert osd.store.read(pg.coll, g) == bytes(cs[0])
    assert osd.store.getattr(pg.coll, g, "_av") == _av_stamp(v)
    # both rounds visible: a runs round, then a whole-chunk round
    vecs = [v_ for _o, v_ in osd.sent if isinstance(v_, m.MECSubReadVec)]
    assert any(any(rr for rr in v_.runs) for v_ in vecs)
    assert any(not any(rr for rr in v_.runs) for v_ in vecs)
    # the whole-chunk retry pushes the running ratio past the plan's
    # 344 permille — honest accounting, not a vanity gauge
    assert osd.pg_perf.value("repair_read_frac") > 344


# ---------------------------------------------------------------------------
# clay pool under OSD thrashing: the acked-durability oracle
# (test_rados_model's model sequence) + thrash_hunt's forensics hooks,
# the same bar the RS pools clear
# ---------------------------------------------------------------------------

TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")
sys.path.insert(0, os.path.abspath(TOOLS))


def test_thrash_clay_model_oracle():
    """One seeded kill/revive thrash on the clay pool while the rados
    model sequence runs: every acked op must be durable and readable
    (failures dump shard-level forensics via thrash_hunt)."""
    import thrash_hunt

    assert thrash_hunt.run_one(0xC1A9, "clay", rounds=60)


@pytest.mark.slow
@pytest.mark.parametrize("seed", range(10))
def test_thrash_clay_matrix(seed):
    """The acceptance grid: ten seeds of model-under-thrash on the
    clay pool, all green — sub-chunk repair plans, their whole-chunk
    fallbacks, and plain degraded ops interleave freely here."""
    import thrash_hunt

    assert thrash_hunt.run_one(0xC1A0 + seed, "clay", rounds=80)
