"""The third configuration and its cell (PR 30): `crush-ec-k8m4-rack-1024osd`
under `sweep-ec-host-out`.  CPU only, small sizes; the map, the rule and
the device weights stay the configuration's.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_crush_rule_sweep.py -q
"""

from __future__ import annotations

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

import reference_crush_tree  # noqa: E402
import run  # noqa: E402
import work  # noqa: E402

CELL, CONFIG = "crush-ec-k8m4-host-out-remap", "crush-ec-k8m4-rack-1024osd"
SMALL = {"ids": 8192, "check_ids": 2048}
NEW_METRICS = ["crush_mid_lanes_per_id", "crush_slow_lanes_per_id"]


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearse(**kw) -> dict:
    return run.run_cell(CELL, 2_500_000_011, 2.0, kw.pop("trace", False),
                        require_chip=False, traffic_over=SMALL, **kw)


# -- the manifest's third configuration and cell ------------------------------------
def test_the_manifest_gained_one_configuration_one_cell_two_metrics():
    m = manifest()
    assert [c["name"] for c in m["configs"]][:2] == [
        "ec-isa-k8m4-12osd", "crush-straw2-1024osd"]
    conf, = [c for c in m["configs"] if c["name"] == CONFIG]
    assert conf["reduced"] == ["ids"] and len(conf["source"]) <= 200
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"
    cell, = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "sweep-ec-host-out", 1)
    assert len(cell["why"]) <= 200
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert e2e["placements_per_s"]["workloads"] == [
        "crush-1024osd-sweep-10M", CELL]
    assert e2e["placements_per_s"]["bound"] == 0.01
    assert all(CELL not in x.get("workloads", [])
               for n, x in e2e.items() if n != "placements_per_s")
    new = [x for x in m["per_layer"] if x["name"] in NEW_METRICS]
    assert [x["name"] for x in new] == NEW_METRICS
    assert m["per_layer"][-2:] == new
    for x in new:
        assert x == {"name": x["name"], "unit": "lanes/id",
                     "better": "lower", "source": "program_counter",
                     "layer": "kernels", "moves": "placements_per_s",
                     "workloads": [CELL]}
        how = run.metric_how(x["name"])
        assert how["kind"] == "counter_delta"
        assert how["args"]["per"] == "crush.ids"
    # the cell reads the three that follow placements_per_s, and its two
    spec = run.load_cell(CELL)
    assert [x["name"] for x in spec["end_to_end"]] == [
        "placements_per_s", "setup_s"]
    assert [x["name"] for x in spec["per_layer"]] == [
        "inline_compiles.crush", "crush_roofline",
        "device_idle_pct.crush"] + NEW_METRICS
    # and the flat cell does not read the new two
    assert not set(NEW_METRICS) & {
        x["name"] for x in run.load_cell("crush-1024osd-sweep-10M")[
            "per_layer"]}


def test_the_configuration_states_the_deployment_the_issue_names():
    cfg = run.load_cell(CELL)["cfg"]
    assert cfg["name"] == CONFIG and set(cfg["reduced"]) == {"ids"}
    assert (cfg["num_osds"], cfg["osd_weight"], cfg["num_rep"]) == (
        1024, 0x10000, 12)
    # the one cut ISSUE 30 allows: 8 chunks where the source has 20
    assert (cfg["min_x"], cfg["ids"], cfg["chunk"]) == (0, 4_194_304, 1 << 19)
    assert [(la["type_name"], la["type_id"], la["alg"], la["size"],
             len(la["bucket_ids"])) for la in cfg["layers"]] == [
        ("host", 1, "straw2", 16, 64), ("rack", 2, "straw2", 8, 8),
        ("root", 3, "straw2", 0, 1)]
    assert cfg["layers"][0]["bucket_ids"] == list(range(-1, -65, -1))
    assert cfg["layers"][1]["bucket_ids"] == list(range(-65, -73, -1))
    assert cfg["layers"][2]["bucket_ids"] == [-73]
    assert cfg["rule_steps"] == [
        ["set_chooseleaf_tries", 5], ["set_choose_tries", 100],
        ["take", "root"], ["chooseleaf_indep", 0, "host"], ["emit"]]
    w = reference_crush_tree.device_weights(cfg)
    assert (w[:16] == 0).all() and int((w == 0).sum()) == 16
    assert sorted(np.nonzero(w == 0xC000)[0]) == [
        16 * h + 5 for h in range(1, 33)]
    assert int((w == 0x10000).sum()) == 1024 - 48
    assert cfg["tunables"] == run.load_cell(
        "crush-1024osd-sweep-10M")["cfg"]["tunables"]
    assert {"device_weights", "chunk"} <= set(cfg["assumed"])
    assert len(cfg["guarantees"]) == 2
    # all that crush_bytes takes: the slice's ids and num_rep
    assert work.crush_bytes(cfg, {"ids": cfg["ids"]}) == 4_194_304 * 4 * 13


# -- the reference against a second witness -----------------------------------------
def test_the_tree_reference_agrees_with_the_c_oracle():
    from ceph_tpu import _native
    from ceph_tpu.crush import map as cmap

    cfg = run.load_cell(CELL)["cfg"]
    m, ids = cmap.build_layered_cluster(
        1024, [(la["type_id"], la["size"]) for la in cfg["layers"]])
    assert ids == [la["bucket_ids"] for la in cfg["layers"]]
    m.add_simple_rule("ec", ids[-1][0], 1, mode="indep")
    steps = np.asarray(m.rules[0].steps, dtype=np.int32).ravel()
    w = reference_crush_tree.device_weights(cfg)
    xs = np.random.default_rng(7).integers(0, cfg["ids"], 3000)
    flat = m.flatten()
    want = np.array([_native.do_rule(flat, steps, int(x), 12, w) for x in xs])
    got = reference_crush_tree.CrushTreeRef(cfg).do_rule(xs)
    assert np.array_equal(got, want)
    # nothing lands on the host that is out; rows are 12 distinct hosts
    assert (want >= 16).all() and (want != reference_crush_tree.NONE).all()
    assert all(len(set(row // 16)) == 12 for row in want[:200])
    # a plain choose down to devices: is_out on the pick itself, no
    # leaf recursion, the total tries the tunables give
    flat_cfg = {**cfg, "rule_steps": [["take", "root"],
                                      ["choose_indep", 4, "osd"], ["emit"]],
                "num_rep": 4}
    steps2 = np.asarray([(cmap.OP_TAKE, -73, 0), (cmap.OP_CHOOSE_INDEP, 4, 0),
                         (cmap.OP_EMIT, 0, 0)], dtype=np.int32).ravel()
    want2 = np.array([_native.do_rule(flat, steps2, int(x), 4, w)
                      for x in xs[:500]])
    assert np.array_equal(
        reference_crush_tree.CrushTreeRef(flat_cfg).do_rule(xs[:500]), want2)


# -- a run, rehearsed ------------------------------------------------------------------
@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal_ends_in_the_contracts_line(trace):
    r = rehearse(trace=trace)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu"
    assert r["compared"] == {k: {"value": 0, "limit": 0} for k in (
        "placements_wrong", "sweeps_overflowed", "no_sweep_compared")}
    if trace:
        # no device plane on the CPU: the roofline share is left out
        assert set(r["metrics"]) == {"inline_compiles.crush",
                                     "device_idle_pct.crush"} | set(NEW_METRICS)
        assert r["metrics"]["inline_compiles.crush"]["value"] == 0
        mid = r["metrics"]["crush_mid_lanes_per_id"]["value"]
        slow = r["metrics"]["crush_slow_lanes_per_id"]["value"]
        assert 0 < slow <= 0.03 and slow < mid <= 1.0
    else:
        assert set(r["metrics"]) == {"placements_per_s", "setup_s"}
    json.dumps(r)


def test_the_control_is_refused():
    r = rehearse(control=True)
    assert r["correct"] is False
    assert r["compared"]["sweeps_overflowed"]["value"] == r["attempted"]
    # most rows needed a retry somewhere
    assert r["compared"]["placements_wrong"]["value"] > \
        SMALL["check_ids"] * r["attempted"] // 2


def test_an_altered_placement_is_caught(monkeypatch):
    from ceph_tpu.crush import mapper

    real = mapper.sweep_device

    def altered(*a, **kw):
        res, ovf = real(*a, **kw)
        return res.at[7, 11].add(1), ovf    # one shard of one row

    monkeypatch.setattr(mapper, "sweep_device", altered)
    # every position compared, so the one row is among them
    r = run.run_cell(CELL, 2_500_000_011, 1.0, False, require_chip=False,
                     traffic_over={"ids": 8192, "check_ids": 8192})
    assert r["correct"] is False
    assert r["compared"]["placements_wrong"]["value"] == r["attempted"]


def test_the_driver_refuses_another_map(monkeypatch):
    from drivers import crush_rule_sweep

    cfg = json.loads(json.dumps(run.load_cell(CELL)["cfg"]))
    cfg["layers"][1]["bucket_ids"][0] = -99
    d = crush_rule_sweep.Driver(cfg, {"check_ids": 16, "ids": 1024}, 1)
    with pytest.raises(RuntimeError, match="not the configuration's"):
        d.setup()
