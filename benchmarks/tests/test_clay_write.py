"""The fifth configuration and its cell (PR 37): `ec-clay-k8m4d11-12osd`
under `write-1MiB-t16-clay`.  CPU only, small counts; the shapes (1 MiB
objects, k=8 m=4 d=11, every 32 KiB stripe a codeword of 64 sub-chunks
of 64 B a chunk) stay the configuration's.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_clay_write.py -q

Written to prefixes where the manifest's lists are concerned, so that a
later append does not fail it.
"""

from __future__ import annotations

import itertools
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import reference  # noqa: E402
import reference_clay  # noqa: E402
import run  # noqa: E402
import work  # noqa: E402
from reference_clay import MUL  # noqa: E402

CELL, CONFIG = "clay-k8m4d11-write-1MiB", "ec-clay-k8m4d11-12osd"
ISA_CELL = "ec-k8m4-write-1MiB"
TRAFFIC = "write-1MiB-t16-clay"
SEED = 2_500_000_011
SMALL = {"warm_batch_widths": [2], "check_shards_of": 6,
         "check_repairs_of": 3}
NEW = ["clay_host_ms.write", "clay_dev_calls_per_batch.write"]
COMPARED = ["ops_failed", "no_op_compared", "readback_wrong",
            "shards_missing", "shards_wrong", "crcs_wrong", "repair_wrong"]


def cfg() -> dict:
    return run.load_cell(CELL)["cfg"]


def rehearse(**kw) -> dict:
    return run.run_cell(CELL, SEED, 2.0, kw.pop("trace", False),
                        require_chip=False, traffic_over=SMALL, **kw)


# -- the manifest's fifth configuration and cell ----------------------------------
def test_the_manifest_gained_one_configuration_one_cell_two_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    conf, = [c for c in m["configs"] if c["name"] == CONFIG]
    assert conf["reduced"] == ["osd_hosts", "pg_num", "objects"]
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert len(conf["source"]) <= 200 and len(conf["why"]) <= 200
    assert "erasure-code-clay" in conf["source"]
    cell, = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    e2e = {x["name"]: x for x in m["end_to_end"]}
    for name in ("write_MBps", "op_p95_ms"):
        assert e2e[name]["workloads"][:2] == [ISA_CELL, CELL]
    assert CELL not in e2e["placements_per_s"]["workloads"]
    per = {x["name"]: x for x in m["per_layer"]}
    for name in NEW:
        assert per[name]["workloads"][:1] == [CELL]
        assert (per[name]["layer"], per[name]["moves"]) == (
            "kernels", "write_MBps")
    assert run.metric_how(NEW[0])["kind"] == "span_self_per_batch"
    assert run.metric_how(NEW[0])["args"] == {
        "spans": ["clay.uncouple", "clay.mds", "clay.couple"]}
    assert run.metric_how(NEW[1])["kind"] == "counter_delta"
    assert run.metric_how(NEW[1])["args"] == {
        "counter": "clay.dev_calls", "per": "queue.batches"}
    spec = run.load_cell(CELL)
    assert [x["name"] for x in spec["end_to_end"]] == [
        "write_MBps", "op_p95_ms", "setup_s"]
    # every per-layer metric the isa cell reads, and the two new ones,
    # which the isa cell does not read
    isa = [x["name"] for x in run.load_cell(ISA_CELL)["per_layer"]]
    mine = [x["name"] for x in spec["per_layer"]]
    assert len(isa) >= 15 and not set(NEW) & set(isa)
    assert [n for n in mine if n not in NEW] == isa
    assert set(NEW) <= set(mine) and "ec_write_roofline" in mine


def test_the_configuration_states_the_deployment_the_issue_names():
    c = cfg()
    isa = run.load_cell(ISA_CELL)["cfg"]
    assert c["name"] == CONFIG and c["architecture"] is None
    assert c["ec_profile"] == "plugin=clay k=8 m=4 d=11"
    assert (c["k"], c["m"], c["d"], c["q"], c["t"], c["sub_chunks"],
            c["gamma"], c["stripe_unit"]) == (8, 4, 11, 4, 3, 64, 2, 4096)
    assert c["q"] == c["d"] - c["k"] + 1 and c["q"] ** c["t"] == 64
    # the record width of the stored format is upstream's: a stripe's
    # 4 KiB chunk is a codeword's node, 64 B a sub-chunk
    assert c["sub_chunk_bytes"] * c["sub_chunks"] == c["stripe_unit"]
    for key in ("mons", "osds", "pool_size", "pg_num", "osd_hosts",
                "heartbeat", "stripe_unit"):
        assert c[key] == isa[key], key
    assert c["reduced"] == isa["reduced"]      # word for word
    assert set(c["reduced"]) == {"osd_hosts", "pg_num", "objects"}
    assert all(k in c for k in c["reduced"])
    assert set(c["assumed"]) == {"store", "in_flight", "scalar_mds",
                                 "gamma"}
    assert c["guarantees"][:3] == isa["guarantees"][:3]
    assert len(c["guarantees"]) == 4 and "11/32" in c["guarantees"][3]
    t = run.load_cell(CELL)["traffic"]
    assert t == {**run.load_cell(ISA_CELL)["traffic"], "why": t["why"],
                 "driver": "rados_closed_loop_clay", "check_repairs_of": 16,
                 "trace_for_s": 0.27}  # a whole crc loop in every slice
    # the kernel's share of its roofline counts the same bytes a write
    # whatever codes them
    one = {"objects": 1, "object_bytes": t["object_bytes"]}
    assert work.ec_write_bytes(c, one) == work.ec_write_bytes(isa, one)


# -- the reference's two witnesses, blind to the code's structure ------------------
INV = np.array([0] + [reference.gf_inv(a) for a in range(1, 256)],
               dtype=np.uint8)


def gf_rank(a: np.ndarray) -> int:
    """Rank over GF(2^8) by Gaussian elimination."""
    a, r = a.copy(), 0
    for c in range(a.shape[1]):
        piv = np.flatnonzero(a[r:, c])
        if not piv.size:
            continue
        p = r + piv[0]
        if p != r:
            a[[r, p]] = a[[p, r]]
        a[r] = MUL[INV[a[r, c]]][a[r]]
        below = r + 1 + np.flatnonzero(a[r + 1:, c])
        if below.size:
            a[below, c:] ^= MUL[a[below, c][:, None], a[r, c:][None, :]]
        r += 1
        if r == a.shape[0]:
            break
    return r


def generator(coupled: bool = True) -> np.ndarray:
    """The code's generator over sub-chunk symbols, [768, 512], from
    nothing but its encoder: column j is the code of the unit vector
    whose one byte is sub-chunk symbol j of the data (one byte a
    sub-chunk: a 'shard' of 64 bytes)."""
    c = cfg()
    code = reference_clay.Clay(c)
    kz = code.k * code.Z
    unit = np.eye(kz, dtype=np.uint8).reshape(code.k, code.Z, kz)
    if coupled:
        parity = code.encode(unit)
    else:   # the scalar MDS code alone, as `mds_shards` codes
        parity = np.stack([reference.gf_matmul(code.G[code.k:], unit[:, z])
                           for z in range(code.Z)], axis=1)
    return np.concatenate([unit, parity]).reshape(code.n * code.Z, kz)


def node_rows(g: np.ndarray, nodes, layers=range(64)) -> np.ndarray:
    return np.concatenate([g[i * 64 + np.asarray(layers)] for i in nodes])


def test_any_eight_nodes_determine_the_data():
    """MDS: the 512 rows of any 8 of the 12 nodes are of full rank, for
    every one of the 495 subsets; 7 nodes are not enough."""
    g = generator()
    assert g.shape == (768, 512)
    for nodes in itertools.combinations(range(12), 8):
        assert gf_rank(node_rows(g, nodes)) == 512, nodes
    assert gf_rank(node_rows(g, range(5, 12))) == 448


def test_sixteen_sub_chunks_of_each_of_eleven_rebuild_the_twelfth():
    """MSR: for every lost node the 11 x 16 helper symbols of its repair
    layers determine its 64 (its rows lie in the span of the helpers'
    176); ten helpers do not, nor do the same layers of the scalar MDS
    code alone, which is what the control stores."""
    c = cfg()
    code = reference_clay.Clay(c)
    g = generator()
    plain = generator(coupled=False)
    for lost in range(12):
        layers = code.repair_layers(lost)
        assert len(layers) == 16
        others = [i for i in range(12) if i != lost]
        helpers = node_rows(g, others, layers)
        mine = node_rows(g, [lost])
        assert helpers.shape == (176, 512) and gf_rank(helpers) == 176
        assert gf_rank(np.concatenate([helpers, mine])) == 176, lost
        assert gf_rank(np.concatenate(
            [node_rows(g, others[1:], layers), mine])) > 160
        # without the coupling the same reads give 16 of the 64 symbols
        assert gf_rank(np.concatenate(
            [node_rows(plain, others, layers),
             node_rows(plain, [lost])])) - gf_rank(
                 node_rows(plain, others, layers)) == 48


def test_the_reference_agrees_with_the_programs_codec():
    """A second witness of the bytes: the program's ClayCodec (tier-1
    holds it to the reference at three widths, tests/test_clay_reference)."""
    from ceph_tpu.ec import codec_from_profile

    c = cfg()
    codec = codec_from_profile(c["ec_profile"])
    payload = np.random.default_rng(8).bytes(1 << 20)
    sh = reference_clay.clay_shards(payload, c)
    assert sh.shape == (12, 131072)
    # every stripe by itself: the codec's one-codeword call on its 4 KiB
    # chunks, and the reference's loops on that stripe alone
    code = reference_clay.Clay(c)
    for st in (0, 13, 31):
        one = sh[:, st * 4096:(st + 1) * 4096]
        assert np.array_equal(one[8:], np.asarray(
            codec.encode_array(one[:8])))
        assert np.array_equal(one[8:], code.encode(
            one[:8].reshape(8, 64, 64)).reshape(4, 4096))
    # not the shard coded whole (this repo's layout before PR 37)
    assert not np.array_equal(sh[8:], np.asarray(codec.encode_array(sh[:8])))
    assert np.array_equal(reference_clay.mds_shards(payload, c)[:8], sh[:8])
    store = reference_clay.RefStore(c)
    store.write_full("o", payload)
    assert store.read("o") == payload
    for lost in (0, 7, 10):
        assert store.repair("o", lost) == sh[lost].tobytes()
    with pytest.raises(ValueError, match="not a code this reference runs"):
        reference_clay.Clay({**c, "d": 10})


# -- a run, rehearsed ------------------------------------------------------------------
def test_the_driver_against_the_reference_store():
    """The driver's whole course with the reference store in the
    program's place: every comparison 0, the repair through the
    reference's own."""
    from drivers import rados_closed_loop_clay as drv

    c = cfg()
    t = {**run.load_cell(CELL)["traffic"], **SMALL}
    d = drv.Driver(c, t, SEED, system=reference_clay.RefStore(c))
    d.setup()
    assert d.counters() == {}
    got = d.window(1.0, None)
    compared = d.check()
    d.close()
    assert got["attempted"] > 0 and got["failed"] == 0
    assert list(compared) == COMPARED
    assert all(v == 0 and lim == 0 for v, lim in compared.values())


@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal_ends_in_the_contracts_line(trace):
    r = rehearse(trace=trace)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu"       # never calls itself a TPU
    assert r["compared"] == {k: {"value": 0, "limit": 0} for k in COMPARED}
    if trace:
        # no device plane on the CPU: the roofline share is left out
        want = {x["name"] for x in run.load_cell(CELL)["per_layer"]}
        assert set(r["metrics"]) == want - {"ec_write_roofline"}
        assert r["metrics"][NEW[1]]["value"] == 3.0
        assert r["metrics"][NEW[0]]["value"] > 0
        assert r["metrics"]["queue_jobs_per_batch.write"]["value"] >= 1
    else:
        assert set(r["metrics"]) == {"write_MBps", "op_p95_ms", "setup_s"}
    json.dumps(r)


# -- `correct` can fail: the control, and a fault planted in the timed path ------------
def test_the_control_is_refused():
    """The scalar MDS code alone: every read-back succeeds, the four
    coding shards of every sampled object are other bytes (and their
    recorded crcs those of other bytes), and no shard is rebuilt from
    the repair sub-chunks."""
    r = rehearse(control=True)
    assert r["correct"] is False
    v = {k: c["value"] for k, c in r["compared"].items()}
    assert v == {**dict.fromkeys(COMPARED, 0),
                 "shards_wrong": 4 * SMALL["check_shards_of"],
                 "crcs_wrong": 4 * SMALL["check_shards_of"],
                 "repair_wrong": SMALL["check_repairs_of"]}


def test_a_program_that_codes_a_shard_whole_ends_at_once(monkeypatch):
    """The parent's queue takes no `chunk` and codes a shard as one
    codeword: another stored format.  The driver's set-up raises before
    anything boots (run.py then exits 1), so such a program fails the
    cell cleanly and is not compared in it."""
    from ceph_tpu.tpu.queue import StripeBatchQueue
    from drivers import rados_closed_loop_clay as drv

    def clay_repair(self, codec, lost, helpers, planes):
        raise AssertionError("never reached")

    monkeypatch.setattr(StripeBatchQueue, "clay_repair", clay_repair)
    spec = run.load_cell(CELL)
    d = drv.Driver(spec["cfg"], spec["traffic"], SEED)
    with pytest.raises(RuntimeError, match="codes a shard as one codeword"):
        d.setup()
    assert d.sys is None
    d.close()      # run.py's `finally`: nothing was booted, nothing raises


def test_an_altered_coding_byte_is_caught(monkeypatch):
    """One byte of a coding plane flipped where `_dispatch_array`
    produces it, in a sub-chunk that the first repair reads."""
    from ceph_tpu.tpu.queue import StripeBatchQueue

    code = reference_clay.Clay(cfg())
    lost = int(np.random.default_rng([SEED, 5]).integers(12))
    z = code.repair_layers(lost)[0]      # shard 8 gives it as a helper,
    real = StripeBatchQueue._dispatch_array   # or is itself the lost one

    def altered(self, codec, batch, widths):
        outs, crcs, padded = real(self, codec, batch, widths)
        if batch[0].kind == "encp":
            for o in outs:
                o[0, z * 64] ^= 1      # sub-chunk z of the first stripe
        return outs, crcs, padded

    monkeypatch.setattr(StripeBatchQueue, "_dispatch_array", altered)
    r = rehearse()
    assert r["correct"] is False, r["compared"]
    assert r["compared"]["shards_wrong"]["value"] >= SMALL["check_shards_of"]
    assert r["compared"]["repair_wrong"]["value"] > 0
    assert r["compared"]["readback_wrong"]["value"] == 0
