"""The clay pool of `ec-clay-k8m4d11-12osd` held to the plain numpy
clay code (benchmarks/reference_clay.py, nothing of ceph_tpu in it): the
codec's encode, the queue's array branch with its crcs (rows of one
codeword and rows of many: a shard's stripes, each coded by itself), the
single-shard repair, and a pool on a mini cluster.  CPU, small sizes,
seeded.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "benchmarks") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import reference  # noqa: E402
import reference_clay  # noqa: E402

from ceph_tpu.core import tracing  # noqa: E402
from ceph_tpu.ec import clay, codec_from_profile  # noqa: E402
from ceph_tpu.ec.clay import ClayCodec  # noqa: E402
from ceph_tpu.ops import gf256_swar  # noqa: E402
from ceph_tpu.tpu.queue import StripeBatchQueue  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "configs",
                       "ec-clay-k8m4d11-12osd.json")) as _f:
    CFG = json.load(_f)
K, M, N, Z = 8, 4, 12, 64


def _data(s: int, seed: int) -> np.ndarray:
    return np.random.default_rng([36, s, seed]).integers(
        0, 256, size=(K, Z * s), dtype=np.uint8)


def _ref_parity(data: np.ndarray) -> np.ndarray:
    return reference_clay.Clay(CFG).encode(
        data.reshape(K, Z, -1)).reshape(M, -1)


def test_the_configuration_is_the_codecs_geometry():
    codec = codec_from_profile(CFG["ec_profile"])
    ref = reference_clay.Clay(CFG)
    assert (codec.k, codec.m, codec.d, codec.q, codec.t, codec.nu,
            codec.get_sub_chunk_count(), codec.gamma) == (
        CFG["k"], CFG["m"], CFG["d"], CFG["q"], CFG["t"], CFG["nu"],
        CFG["sub_chunks"], CFG["gamma"]) == (8, 4, 11, 4, 3, 0, 64, 2)
    assert (ref.q, ref.t, ref.Z) == (4, 3, 64)
    # the scalar MDS code the configuration assumes is the codec's
    assert np.array_equal(ref.G[K:], np.asarray(codec.coding, np.uint8))


@pytest.mark.parametrize("s", [4, 64, 2048])
def test_encode_array_is_the_references_code(s):
    """s bytes a sub-chunk; 2,048 is the cell's (a 1 MiB object)."""
    data = _data(s, 0)
    before = clay.dev_calls()
    got = np.asarray(ClayCodec(8, 4, 11).encode_array(data))
    assert clay.dev_calls() - before == 3
    assert np.array_equal(got, _ref_parity(data))
    # the coupling is in it: the scalar MDS code alone gives other bytes
    assert not np.array_equal(got, reference.gf_matmul(
        reference_clay.Clay(CFG).G[K:], data))


@pytest.fixture
def device_engine(monkeypatch):
    """The engine choice a chip makes, on the CPU backend: every GF call
    takes the jitted XLA network, and an encode its one program."""
    monkeypatch.setattr(gf256_swar, "_engine", lambda n: "xla")


def _cfg(k: int, m: int) -> dict:
    return {"k": k, "m": m, "d": k + m - 1, "gamma": 2}


# (k, m): the cell's q = 4; q = 3 and q = 2; and two shortened codes whose
# grid holds nu = 1 virtual zero node (q = 3, and q = 4 with t = 4: 256
# layers), which the reference does not run: held to the host composition
@pytest.mark.parametrize("k,m", [(8, 4), (6, 3), (4, 2), (5, 3), (11, 4)])
@pytest.mark.parametrize("s", [1, 6, 64])
def test_the_one_program_is_the_host_composition_and_the_reference(
        k, m, s, device_engine):
    """`s` bytes a sub-chunk: under a word (padded on the host), no whole
    number of words, and the cell's 64."""
    codec = ClayCodec(k, m)
    Z = codec.get_sub_chunk_count()
    assert (codec.q != 4, codec.nu) == {
        (8, 4): (False, 0), (6, 3): (True, 0), (4, 2): (True, 0),
        (5, 3): (True, 1), (11, 4): (False, 1)}[k, m]
    data = np.random.default_rng([38, k, m, s]).integers(
        0, 256, size=(k, Z * s), dtype=np.uint8)
    before = clay.dev_calls()
    got = codec.encode_array(data)
    assert clay.dev_calls() - before == 1
    assert got.shape == (m, Z * s) and got.dtype == np.uint8
    before = clay.dev_calls()
    assert np.array_equal(got, codec._encode_host(data))
    assert clay.dev_calls() - before == 3
    if not codec.nu:
        assert np.array_equal(got, reference_clay.Clay(_cfg(k, m)).encode(
            data.reshape(k, Z, s)).reshape(m, -1))


@pytest.mark.parametrize("jobs", [1, 2, 4, 8])
def test_the_one_program_codes_the_cells_batches(jobs, device_engine):
    """`jobs` 1 MiB objects of the cell (32 stripes of 64 sub-chunks of
    64 B a shard) coalesced by the queue's array branch, which lays
    stripes and jobs side by side along the intra-sub-chunk axis: ONE
    call of the engine a batch, every job's coding planes the
    reference's of each stripe by itself, and the program's output at
    that width the host composition's byte for byte."""
    codec = ClayCodec(8, 4, 11)
    ref = reference_clay.Clay(CFG)
    S, s = 32, 64
    planes = [_data(S * s, 100 + j) for j in range(jobs)]
    q = StripeBatchQueue()
    q._started = True     # hold the worker back until all are queued
    futs = [q.encode_async(codec, p, chunk=Z * s) for p in planes]
    q._started = False
    before = clay.dev_calls()
    q.start()
    try:
        got = [np.asarray(f.result(timeout=120)) for f in futs]
    finally:
        q.stop()
    assert q.batch_jobs == {jobs: 1} and clay.dev_calls() - before == 1
    for p, coding in zip(planes, got):
        assert np.array_equal(coding, reference_clay.shards_of(
            ref.encode(_stripes(p, S))))
    # the batch as the codec saw it: [8, 64, 2048 * jobs], no padding
    wide = np.concatenate([_stripes(p, S).reshape(K, Z, S * s)
                           for p in planes], axis=2).reshape(K, -1)
    assert wide.shape[1] == Z * 2048 * jobs
    dev = codec.encode_array(wide)
    assert np.array_equal(dev, codec._encode_host(wide))
    assert np.array_equal(dev, _ref_parity(wide))


@pytest.mark.parametrize("k,m", [(8, 4), (5, 3), (6, 3)])
def test_what_the_one_program_stores_is_repaired_and_decoded(
        k, m, device_engine):
    """Encode on the device path, then every single lost shard through
    `repair_planes` and m lost shards through `decode_array`: the data
    and the stored parity come back."""
    codec = ClayCodec(k, m)
    Z, s, n = codec.get_sub_chunk_count(), 8, k + m
    data = np.random.default_rng([38, k, m]).integers(
        0, 256, size=(k, Z * s), dtype=np.uint8)
    full = np.concatenate([data, codec.encode_array(data)])
    for lost in range(n):
        layers = codec.repair_layers(lost)
        helpers = [i for i in range(n) if i != lost]
        got = codec.repair_planes(lost, helpers, np.stack(
            [full[h].reshape(Z, s)[layers] for h in helpers]))
        assert np.array_equal(np.asarray(got).reshape(-1), full[lost]), lost
    erased = list(range(1, 2 * m, 2))     # data and parity among them
    out = codec.decode_array(
        {i: full[i] for i in range(n) if i not in erased},
        list(range(n)), Z * s)
    for i in range(n):
        assert np.array_equal(np.asarray(out[i]).reshape(-1), full[i]), i


def test_a_strided_fetch_is_laid_out_before_it_is_viewed_as_bytes(
        device_engine, monkeypatch):
    """At the narrowest widths the chip hands the parity words back as
    an array whose last axis is not contiguous (PR 38's first chip call:
    the pool's warm-up of 4,096 columns, W = 16); a u8 view of that
    raises.  The same shape of array here, from a stand-in fetch."""
    def strided(out):
        host = np.asarray(out)
        wide = np.zeros(host.shape[:-1] + (2 * host.shape[-1],), host.dtype)
        wide[..., ::2] = host
        return wide[..., ::2]

    monkeypatch.setattr(clay, "fetch", strided)
    codec = ClayCodec(8, 4, 11)
    data = _data(64, 7)
    assert np.array_equal(codec.encode_array(data), _ref_parity(data))


def test_codecs_of_one_profile_share_the_one_program(device_engine):
    """Each PG has a codec of its own: the second one's encode at a
    width the first has run traces and compiles nothing."""
    from ceph_tpu.tpu import devwatch

    a, b = ClayCodec(4, 2), ClayCodec(4, 2)
    assert clay._encode_program(*a._program_key) is clay._encode_program(
        *b._program_key)
    assert clay._encode_program(*a._program_key) is not (
        clay._encode_program(*ClayCodec(4, 2, gamma=3)._program_key))
    data = np.random.default_rng(38).integers(
        0, 256, size=(4, 8 * 16), dtype=np.uint8)
    want = a.encode_array(data)
    compiles = devwatch.watch().family_stats("gf256_clay")["compiles"]
    assert np.array_equal(b.encode_array(data), want)
    assert devwatch.watch().family_stats(
        "gf256_clay")["compiles"] == compiles


def _stripes(planes: np.ndarray, S: int) -> np.ndarray:
    """Rows of S codewords one after another -> [r, Z, S, s]: the nodes
    of the S codewords, for the reference's loops."""
    return planes.reshape(len(planes), S, Z, -1).transpose(0, 2, 1, 3)


# a job is (codewords a row, bytes a sub-chunk); the first three are rows
# of ONE codeword (the bare plugin's call), the others a shard's stripes
# (32 x 64 B: a 1 MiB object of the cell at its 4 KiB stripe_unit)
@pytest.mark.parametrize("jobs", [
    [(1, 64)], [(1, 4), (1, 36)], [(1, 8), (1, 2), (1, 21)],
    [(32, 64)], [(3, 4), (9, 4)], [(2, 8), (1, 8), (5, 8)]],
    ids=["1job", "2jobs", "3jobs",
         "1job-32stripes", "2jobs-striped", "3jobs-striped"])
def test_the_queues_array_branch_codes_and_crcs_each_job(jobs):
    """Jobs of unequal widths coalesced along the intra-sub-chunk byte
    axis and padded there: every job's coding planes are the reference's
    of each of its codewords by itself, in the job's own layout, and its
    twelve crcs those of its own shards."""
    codec = ClayCodec(8, 4, 11)
    ref = reference_clay.Clay(CFG)
    q = StripeBatchQueue()
    q._started = True     # hold the worker back until all are queued
    planes = [_data(S * s, j) for j, (S, s) in enumerate(jobs)]
    futs = [q.encode_crc_async(codec, p, chunk=Z * s if S > 1 else 0)
            for p, (S, s) in zip(planes, jobs)]
    q._started = False
    before = clay.dev_calls()
    q.start()
    try:
        for p, f, (S, s) in zip(planes, futs, jobs):
            coding, crcs = f.result(timeout=60)
            want = ref.encode(_stripes(p, S)).transpose(
                0, 2, 1, 3).reshape(M, -1)
            if S > 1:   # the first stripe's, coded with nothing else
                assert np.array_equal(want[:, :Z * s],
                                      _ref_parity(p[:, :Z * s]))
            assert np.array_equal(np.asarray(coding), want)
            assert [int(c) for c in crcs] == [
                int(c) for c in reference.crc32c_rows(
                    np.concatenate([p, want]))]
    finally:
        q.stop()
    assert q.batch_jobs == {len(jobs): 1} and q.batches == 1
    assert clay.dev_calls() - before == 3
    # the batch's span carries the kind and the clay steps below encode
    recs, _ = tracing.recorder().held()
    batch = [r for r in recs if r[tracing.NAME] == "queue.batch"
             and r[tracing.COUNTS].get("q") == q._span_q][-1]
    assert batch[tracing.COUNTS]["kind"] == "encp"
    assert batch[tracing.COUNTS]["padded"] == Z * (
        1 << (sum(S * s for S, s in jobs) - 1).bit_length())
    enc = [r for r in recs if r[tracing.NAME] == "batch.encode"
           and r[tracing.PARENT] == batch[tracing.ID]]
    assert len(enc) == 1
    kids = [r[tracing.NAME] for r in recs
            if r[tracing.PARENT] == enc[0][tracing.ID]]
    assert kids == ["clay.uncouple", "clay.mds", "clay.couple"]


@pytest.mark.parametrize("lost", [0, 5, 10])
def test_the_queue_repairs_a_striped_shard_from_each_stripes_layers(lost):
    """What a helper reads of its shard: of every stripe, the lost
    node's repair sub-chunks, stripe after stripe.  The rebuilt shard is
    the stored one, stripe for stripe, and the reference's."""
    S, s = 5, 8
    data = _data(S * s, lost)
    ref = reference_clay.Clay(CFG)
    nodes = np.concatenate([_stripes(data, S),
                            ref.encode(_stripes(data, S))])   # [N, Z, S, s]
    shards = nodes.transpose(0, 2, 1, 3).reshape(N, -1)
    codec = ClayCodec(8, 4, 11)
    layers = ref.repair_layers(lost)
    helpers = [i for i in range(N) if i != lost]
    q = StripeBatchQueue()
    try:
        got = np.asarray(q.clay_repair(
            codec, lost, helpers,
            np.stack([shards[h].reshape(S, Z, s)[:, layers].reshape(-1)
                      for h in helpers]), chunk=Z * s))
        # a shard of three survivors lost as well: the layered decode
        avail = {i: shards[i] for i in range(N) if i not in (1, lost, 11)}
        dec = np.asarray(q.clay_decode_async(
            codec, avail, chunk=Z * s).result(timeout=60))
    finally:
        q.stop()
    assert np.array_equal(got, shards[lost])
    assert np.array_equal(got.reshape(S, Z, s).transpose(1, 0, 2), ref.repair(
        lost, {h: nodes[h][layers] for h in helpers}))
    assert np.array_equal(dec, data)


@pytest.mark.parametrize("lost", range(N))
def test_repair_planes_rebuilds_every_shard_as_the_reference_does(lost):
    s = 8
    data = _data(s, lost)
    full = np.concatenate([data, _ref_parity(data)]).reshape(N, Z, s)
    codec, ref = ClayCodec(8, 4, 11), reference_clay.Clay(CFG)
    layers = [int(z) for z in codec.repair_layers(lost)]
    assert layers == ref.repair_layers(lost) and len(layers) == 16
    helpers = [i for i in range(N) if i != lost]
    got = np.asarray(codec.repair_planes(
        lost, helpers, np.stack([full[h][layers] for h in helpers])))
    assert np.array_equal(got, full[lost])
    assert np.array_equal(got, ref.repair(
        lost, {h: full[h][layers] for h in helpers}))
    # 11 x 16 of 8 x 64 sub-chunks: 11/32 of what Reed-Solomon reads
    assert len(helpers) * len(layers) * 32 == 11 * K * Z


@pytest.mark.parametrize("size", [1 << 20, 300_000],
                         ids=["1MiB", "ragged"])
def test_a_clay_pool_stores_the_references_shards(size):
    """The configuration's profile on a mini cluster: written, read back,
    and the shards the OSDs hold are the reference's code of the payload
    under upstream's 4 KiB striping, with their recorded crcs."""
    from drivers import rados_closed_loop

    system = rados_closed_loop.Cluster({**CFG, "pg_num": 2})
    try:
        payload = np.random.default_rng([36, size]).bytes(size)
        system.write_full("obj", payload)
        assert system.read("obj") == payload
        held = system.stored("obj")
    finally:
        system.close()
    # the pool pads an object to whole stripes with zeros, and every
    # stripe is a codeword of its own: 64 sub-chunks of 64 B a chunk
    stripe = K * reference.UNIT
    want = reference_clay.clay_shards(
        payload + bytes(-len(payload) % stripe), CFG)
    assert want.shape == (N, -(-size // stripe) * reference.UNIT)
    first = want[:, :reference.UNIT]
    assert np.array_equal(first[K:], _ref_parity(first[:K]))
    assert CFG["sub_chunk_bytes"] * Z == CFG["stripe_unit"] == reference.UNIT
    assert sorted(held) == list(range(N))
    crcs = reference.crc32c_rows(want)
    for s_, (stored, crc) in held.items():
        assert stored == want[s_].tobytes(), s_
        assert crc == int(crcs[s_]), s_


def test_dev_calls_loses_no_count_between_threads():
    """The counter is one for every codec of the process (each PG has a
    codec of its own; OSD threads warm theirs while the queue's worker
    encodes): more threads than cores, a short switch interval, and
    every call counted."""
    import threading

    codec = ClayCodec(4, 2)
    data = np.zeros((4, codec.get_sub_chunk_count()), dtype=np.uint8)
    threads, each = 24, 40
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        before = clay.dev_calls()
        ts = [threading.Thread(
            target=lambda: [codec.encode_array(data) for _ in range(each)])
            for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in ts)
        assert clay.dev_calls() - before == 3 * threads * each
    finally:
        sys.setswitchinterval(was)
