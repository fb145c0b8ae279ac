"""The benchmark's own tests: CPU only, small sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

Nothing here describes a TPU topology or needs a chip; a number a
rehearsal prints is never a device number (the device block says cpu).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
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
import run  # noqa: E402
import trace_reduce  # noqa: E402
import work  # noqa: E402

WRITE, CRUSH = "ec-k8m4-write-1MiB", "crush-1024osd-sweep-10M"
# a size a test run can hold; shapes (1 MiB objects, k=8 m=4, the map)
# stay the configuration's
SMALL = {
    WRITE: {"warm_batch_widths": [2], "check_shards_of": 6},
    CRUSH: {"ids": 8192, "check_ids": 2048},
}
KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearse(cell: str, **kw) -> dict:
    return run.run_cell(cell, 2_500_000_011, 2.0, kw.pop("trace", False),
                        require_chip=False, traffic_over=SMALL[cell], **kw)


# -- trace reduction ---------------------------------------------------------
def test_trace_reduce_on_the_recorded_trace():
    """fixtures/trace_degraded_read.json: a trimmed copy of a trace from
    the chip (PR 26, a degraded-read run); `by_hand` was worked out from
    its events without `reduce` (fixtures/README.md shows the sums)."""
    with open(os.path.join(HERE, "fixtures", "trace_degraded_read.json")) as f:
        fx = json.load(f)
    got = trace_reduce.reduce(fx["trace"])
    want = fx["by_hand"]
    assert got["chips"] == 1
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["device_ops"][0][0] == want["top_op"]
    assert got["device_ops"][0][1] == pytest.approx(want["top_op_s"], rel=1e-9)
    assert got["idle_gaps"][0][0] == want["longest_gap_label"]
    assert dict(map(tuple, got["idle_gaps"]))["unattributed"] == \
        pytest.approx(want["unattributed_gap_s"], rel=1e-9)
    assert got["buffers_dropped"] is False and got["events"] == 10
    from readers import trace_idle_share

    assert trace_idle_share.read({}, {"trace": got}) == \
        pytest.approx(want["idle_pct"], rel=1e-9)


def test_trace_reduce_needs_its_slice_and_reports_no_zero_share():
    from readers import trace_idle_share, trace_roofline_share

    with pytest.raises(ValueError):
        trace_reduce.reduce({"planes": []})
    idle_only = {"busy_s": 0.0, "window_s": 1.0}
    assert trace_roofline_share.read(
        {"work": "crush_bytes"}, {"trace": idle_only}) is None
    # a trace whose buffer overflowed is known to be incomplete
    dropped = {"busy_s": 0.5, "window_s": 1.0, "buffers_dropped": True}
    ctx = {"trace": dropped, "cfg": {"num_rep": 3}, "slice": {"ids": 8},
           "device_kind": "TPU v5 lite"}
    assert trace_idle_share.read({}, ctx) is None
    assert trace_roofline_share.read({"work": "crush_bytes"}, ctx) is None
    assert trace_roofline_share.read(
        {"work": "crush_bytes"}, {**ctx, "trace": {**dropped,
                                                   "buffers_dropped": False}})


# -- work and peaks -------------------------------------------------------------
def test_work_bytes_and_peaks():
    ec = {"k": 8, "m": 4}
    one = {"objects": 1, "object_bytes": 1 << 20}
    assert work.ec_write_bytes(ec, one) == 1_572_864
    assert work.crush_bytes({"num_rep": 3}, {"ids": 10_485_760}) == 167_772_160
    assert work.peak("TPU v5 lite") == 819e9
    # 1.5 MiB at 819 GB/s is 1.92 us: against 57 ms of busy time
    assert work.roofline_pct(1_572_864, "TPU v5 lite", 0.057) == \
        pytest.approx(0.003369, rel=1e-3)
    with pytest.raises(KeyError):
        work.peak("cpu")


# -- the manifest and the files it names -------------------------------------------
def test_manifest_names_things_that_exist():
    m = manifest()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    cells = {w["name"]: w for w in m["workloads"]}
    configs = {c["name"]: c for c in m["configs"]}
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for c in configs.values():
        assert name.match(c["name"]) and len(c["source"]) <= 200
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert all(k in held for k in c["reduced"])
    for w in cells.values():
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert w["config"] in configs and len(w["why"]) <= 200
        traffic = run._json("traffic", w["traffic"] + ".json")
        assert os.path.exists(os.path.join(
            BENCH, "drivers", traffic["driver"] + ".py"))
        reports = [x["name"] for x in m["end_to_end"]
                   if w["name"] in x.get("workloads", [w["name"]])]
        assert len(reports) >= 2, w["name"]
    for x in m["per_layer"]:
        assert name.match(x["name"]) and x["moves"] in e2e
        how = run.metric_how(x["name"])
        assert os.path.exists(os.path.join(
            BENCH, "readers", how["kind"] + ".py"))
        if "work" in how.get("args", {}):
            assert callable(getattr(work, how["args"]["work"]))
        for cell in x.get("workloads", []):
            assert cell in cells
            assert cell in e2e[x["moves"]].get("workloads", [cell])
    # every cell reads at least one per-layer metric, each moving an
    # end-to-end metric that the cell reports
    for w in cells:
        spec = run.load_cell(w)
        mine = {x["name"] for x in spec["end_to_end"]}
        assert spec["per_layer"]
        assert all(x["moves"] in mine for x in spec["per_layer"])


# -- the references against a second witness ------------------------------------------
def test_references_agree_with_the_c_oracles():
    from ceph_tpu import _native
    from ceph_tpu.crush import map as cmap

    cfg = run.load_cell(CRUSH)["cfg"]
    m, root = cmap.build_flat_cluster(cfg["num_osds"], hosts=cfg["hosts"])
    steps = np.asarray([(cmap.OP_TAKE, root, 0),
                        (cmap.OP_CHOOSELEAF_FIRSTN, 3, 1),
                        (cmap.OP_EMIT, 0, 0)], dtype=np.int32).ravel()
    w = np.full(cfg["num_osds"], 0x10000, dtype=np.uint32)
    xs = np.random.default_rng(7).integers(0, cfg["ids"], 3000)
    flat = m.flatten()
    want = np.array([_native.do_rule(flat, steps, int(x), 3, w) for x in xs])
    assert np.array_equal(reference.CrushRef(cfg).do_rule(xs), want)
    assert (reference.CrushRef(cfg, retry=False).do_rule(xs) != want).any()

    data = np.random.default_rng(8).bytes(1 << 16)
    sh = reference.rs_shards(data, 8, 4)
    coding = np.ascontiguousarray(reference.isa_rs_matrix(8, 4)[8:])
    assert np.array_equal(sh[8:], _native.rs_encode(
        coding, np.ascontiguousarray(sh[:8])))
    assert reference.rs_decode({i: sh[i] for i in range(12)
                                if i not in (1, 9, 10)}, 8, 4) == data
    assert int(reference.crc32c_rows(
        np.frombuffer(b"123456789", np.uint8)[None])[0]) == 0xE3069283


# -- a run, rehearsed ----------------------------------------------------------------
@pytest.mark.parametrize("cell,trace", [(CRUSH, True), (WRITE, False),
                                        (WRITE, True)])
def test_rehearsal_ends_in_the_contracts_line(cell, trace):
    r = rehearse(cell, trace=trace)
    assert set(r) == KEYS | ({"breakdown"} if trace else set())
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu"       # never calls itself a TPU
    declared = {x["name"] for x in manifest()[
        "per_layer" if trace else "end_to_end"]}
    assert set(r["metrics"]) <= declared and r["metrics"]
    if trace:
        # no device plane on the CPU: the trace's shares are left out
        assert not any("roofline" in k for k in r["metrics"])
        assert {"busy_s", "window_s"} <= set(r["device"])
    else:
        assert "setup_s" in r["metrics"] and len(r["metrics"]) >= 2
    json.dumps(r)


def test_a_run_without_a_tpu_prints_no_result():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CRUSH,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 2 and p.stdout.strip() == ""


# -- `correct` can fail: the control, and a fault planted in the timed path ------------
@pytest.mark.parametrize("cell", [WRITE, CRUSH])
def test_the_control_is_refused(cell):
    r = rehearse(cell, control=True)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["compared"].values())


def test_an_altered_shard_is_caught(monkeypatch):
    """One byte of what the device matmul returns flipped where it is
    produced."""
    from ceph_tpu.tpu.queue import StripeBatchQueue

    real = StripeBatchQueue._apply_matrix

    def altered(self, codec, batch, stacked):
        out = np.array(real(self, codec, batch, stacked))
        out[0, 0] ^= 1
        return out

    monkeypatch.setattr(StripeBatchQueue, "_apply_matrix", altered)
    r = rehearse(WRITE)
    assert r["correct"] is False, r["compared"]


def test_an_altered_placement_is_caught(monkeypatch):
    from ceph_tpu.crush import mapper

    real = mapper.sweep_device

    def altered(*a, **kw):
        res, ovf = real(*a, **kw)
        return res.at[::5, 1].add(1), ovf

    monkeypatch.setattr(mapper, "sweep_device", altered)
    r = rehearse(CRUSH)
    assert r["correct"] is False
    assert r["compared"]["placements_wrong"]["value"] > 0
