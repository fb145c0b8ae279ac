"""The fourth configuration and its cell (PR 34):
`crush-rep3-hetero-rack-1024osd` under `sweep-rep3-hetero`.  CPU only,
small sizes; the map, its weights and the rule stay the configuration's.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_crush_weighted_sweep.py -q
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

import reference_crush_firstn_tree  # noqa: E402
import run  # noqa: E402
import work  # noqa: E402

CELL, CONFIG = "crush-rep3-hetero-remap", "crush-rep3-hetero-rack-1024osd"
EC_CELL = "crush-ec-k8m4-host-out-remap"
SMALL = {"ids": 8192, "check_ids": 2048}
LANES = ["crush_mid_lanes_per_id", "crush_slow_lanes_per_id"]
DRAWS = "crush_full_draws_per_id"
TB = {4: 0x3A352, 8: 0x746A5, 16: 0xE8D4A}


def rehearse(**kw) -> dict:
    return run.run_cell(CELL, 2_500_000_011, 2.0, kw.pop("trace", False),
                        require_chip=False, traffic_over=SMALL, **kw)


# -- the manifest's fourth configuration and cell -----------------------------------
def test_the_manifest_gained_one_configuration_one_cell_one_metric():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    conf, = [c for c in m["configs"] if c["name"] == CONFIG]
    assert conf["reduced"] == ["ids"] and len(conf["source"]) <= 200
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"
    cell, = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "sweep-rep3-hetero", 1)
    assert len(cell["why"]) <= 200 and len(conf["why"]) <= 200
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert e2e["placements_per_s"]["workloads"][:3] == [
        "crush-1024osd-sweep-10M", EC_CELL, CELL]
    assert all(CELL not in x.get("workloads", [])
               for n, x in e2e.items() if n != "placements_per_s")
    per = {x["name"]: x for x in m["per_layer"]}
    for name in LANES:
        assert per[name]["workloads"][:2] == [EC_CELL, CELL]
    assert per[DRAWS] == {
        "name": DRAWS, "unit": "draws/id", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "placements_per_s", "workloads": [EC_CELL, CELL]}
    assert run.metric_how(DRAWS) == {
        "kind": "counter_delta", "what": run.metric_how(DRAWS)["what"],
        "args": {"counter": "crush.full_draws", "per": "crush.ids"}}
    spec = run.load_cell(CELL)
    assert [x["name"] for x in spec["end_to_end"]] == [
        "placements_per_s", "setup_s"]
    assert [x["name"] for x in spec["per_layer"]] == [
        "inline_compiles.crush", "crush_roofline",
        "device_idle_pct.crush"] + LANES + [DRAWS]
    # the EC cell reads the new one too, the flat cell does not
    assert DRAWS in [x["name"] for x in run.load_cell(EC_CELL)["per_layer"]]
    assert DRAWS not in [x["name"] for x in run.load_cell(
        "crush-1024osd-sweep-10M")["per_layer"]]


def test_the_configuration_states_the_deployment_the_issue_names():
    cfg = run.load_cell(CELL)["cfg"]
    assert cfg["name"] == CONFIG and set(cfg["reduced"]) == {"ids"}
    assert (cfg["num_osds"], cfg["num_rep"]) == (1024, 3)
    # the one cut ISSUE 34 allows: 4 chunks where the source has 20
    assert (cfg["min_x"], cfg["ids"], cfg["chunk"]) == (
        0, 2_097_152, 1 << 19)
    ec = run.load_cell(EC_CELL)["cfg"]
    assert cfg["layers"] == ec["layers"]
    assert cfg["tunables"] == ec["tunables"]
    assert cfg["rule_steps"] == [
        ["take", "root"], ["chooseleaf_firstn", 0, "host"], ["emit"]]
    # capacity in TiB as a 16.16 number, and the recipe by h mod 4
    assert all(w == (tb * 10**12 << 16) >> 40 for tb, w in TB.items())
    want = []
    for h in range(64):
        want += {0: [4] * 16, 1: [8] * 16, 2: [4, 8] * 8,
                 3: [8 if h < 32 else 16] * 16}[h % 4]
    assert cfg["osd_weights"] == [TB[tb] for tb in want]
    hosts = [sum(cfg["osd_weights"][16 * h: 16 * h + 16]) for h in range(64)]
    racks = [sum(hosts[8 * r: 8 * r + 8]) for r in range(8)]
    assert cfg["bucket_weights"] == {
        "host": hosts, "rack": racks, "root": [sum(racks)]}
    # unlike weights inside the root, inside every rack, in 16 hosts
    assert len(set(hosts)) == 4 and len(set(racks)) == 2
    assert all(len(set(hosts[8 * r: 8 * r + 8])) > 1 for r in range(8))
    assert sum(len(set(cfg["osd_weights"][16 * h: 16 * h + 16])) > 1
               for h in range(64)) == 16
    w = reference_crush_firstn_tree.device_weights(cfg)
    assert (w == 0x10000).all() and len(w) == 1024
    assert "drive mix" in cfg["assumed"] and len(cfg["guarantees"]) == 2
    assert work.crush_bytes(cfg, {"ids": cfg["ids"]}) == 2_097_152 * 4 * 4


# -- the reference against a second witness -----------------------------------------
def _oracle(m, steps, xs, nrep, w):
    from ceph_tpu import _native

    flat = m.flatten()
    steps = np.asarray(steps, dtype=np.int32).ravel()
    return np.array([_native.do_rule(flat, steps, int(x), nrep, w)
                     for x in xs])


def test_the_firstn_reference_agrees_with_the_c_oracle():
    from ceph_tpu.crush import map as cmap

    cfg = run.load_cell(CELL)["cfg"]
    m, ids = cmap.build_layered_cluster(
        1024, [(la["type_id"], la["size"]) for la in cfg["layers"]],
        cfg["osd_weights"])
    xs = np.random.default_rng(7).integers(0, cfg["ids"], 4096)
    w = reference_crush_firstn_tree.device_weights(cfg)
    rule = [(cmap.OP_TAKE, -73, 0), (cmap.OP_CHOOSELEAF_FIRSTN, 0, 1),
            (cmap.OP_EMIT, 0, 0)]
    want = _oracle(m, rule, xs, 3, w)
    got = reference_crush_firstn_tree.CrushFirstnTreeRef(cfg).do_rule(xs)
    assert np.array_equal(got, want)
    assert all(len(set(row // 16)) == 3 for row in want[:200])
    # a 16 TB drive draws about four times a 4 TB drive's share
    share = np.bincount(want.ravel(), minlength=1024)
    big = share[np.asarray(cfg["osd_weights"]) == TB[16]].mean()
    small = share[np.asarray(cfg["osd_weights"]) == TB[4]].mean()
    assert 3.0 < big / small < 5.0

    # a host out and reweighted OSDs: is_out on the leaf, the retry of
    # the whole descent; then the leaf recursion with all its tries
    out = {"default": 0x10000, "marks": [
        {"weight": 0, "osds": list(range(16))},
        {"weight": 0x4000, "osds": [16 * h + 5 for h in range(1, 33)]}]}
    cfg2 = {**cfg, "device_weights": out}
    w2 = reference_crush_firstn_tree.device_weights(cfg2)
    xs = xs[:1024]
    assert np.array_equal(
        reference_crush_firstn_tree.CrushFirstnTreeRef(cfg2).do_rule(xs),
        _oracle(m, rule, xs, 3, w2))
    m.tunables.chooseleaf_descend_once = 0
    cfg3 = {**cfg2, "tunables": {**cfg["tunables"],
                                 "chooseleaf_descend_once": 0}}
    assert np.array_equal(
        reference_crush_firstn_tree.CrushFirstnTreeRef(cfg3).do_rule(xs),
        _oracle(m, rule, xs, 3, w2))
    m.tunables.chooseleaf_descend_once = 1
    # two chooses, the second from two sources; a choose down to devices
    cfg4 = {**cfg2, "num_rep": 4, "rule_steps": [
        ["take", "root"], ["choose_firstn", 2, "rack"],
        ["chooseleaf_firstn", 2, "host"], ["emit"]]}
    assert np.array_equal(
        reference_crush_firstn_tree.CrushFirstnTreeRef(cfg4).do_rule(xs),
        _oracle(m, [(cmap.OP_TAKE, -73, 0), (cmap.OP_CHOOSE_FIRSTN, 2, 2),
                    (cmap.OP_CHOOSELEAF_FIRSTN, 2, 1), (cmap.OP_EMIT, 0, 0)],
                xs, 4, w2))
    cfg5 = {**cfg2, "rule_steps": [
        ["take", "root"], ["choose_firstn", 0, "osd"], ["emit"]]}
    assert np.array_equal(
        reference_crush_firstn_tree.CrushFirstnTreeRef(cfg5).do_rule(xs),
        _oracle(m, [(cmap.OP_TAKE, -73, 0), (cmap.OP_CHOOSE_FIRSTN, 0, 0),
                    (cmap.OP_EMIT, 0, 0)], xs, 3, w2))


def test_the_reference_refuses_what_it_does_not_run():
    cfg = run.load_cell(CELL)["cfg"]
    with pytest.raises(ValueError, match="jewel"):
        reference_crush_firstn_tree.CrushFirstnTreeRef(
            {**cfg, "tunables": {**cfg["tunables"], "chooseleaf_stable": 0}})
    ref = reference_crush_firstn_tree.CrushFirstnTreeRef(
        {**cfg, "rule_steps": [["take", "root"],
                               ["chooseleaf_indep", 0, "host"], ["emit"]]})
    with pytest.raises(ValueError, match="not one this reference runs"):
        ref.do_rule(np.arange(4))


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
        assert set(r["metrics"]) == {
            "inline_compiles.crush", "device_idle_pct.crush", DRAWS} | set(
                LANES)
        assert r["metrics"]["inline_compiles.crush"]["value"] == 0
        mid = r["metrics"]["crush_mid_lanes_per_id"]["value"]
        slow = r["metrics"]["crush_slow_lanes_per_id"]["value"]
        assert 0 <= slow < 0.001 and 0.04 < mid < 0.07
        # the one-shot pass draws 3 x (8 + 8 + 16) items an id in full,
        # the budgeted stage 3 tries of that a lane, the exact one once
        assert r["metrics"][DRAWS]["value"] == pytest.approx(
            96 + 288 * mid + 96 * slow)
    else:
        assert set(r["metrics"]) == {"placements_per_s", "setup_s"}
    json.dumps(r)


def test_the_control_is_refused():
    r = rehearse(control=True)
    assert r["correct"] is False
    assert r["compared"]["sweeps_overflowed"]["value"] == 0
    # the mean-weight map places most rows elsewhere
    assert r["compared"]["placements_wrong"]["value"] > \
        SMALL["check_ids"] * r["attempted"] // 2


def test_one_placement_in_five_altered_is_caught(monkeypatch):
    from ceph_tpu.crush import mapper

    real = mapper.sweep_device

    def altered(*a, **kw):
        res, ovf = real(*a, **kw)
        return res.at[::5, 2].add(1), ovf   # the third replica, a row in 5

    monkeypatch.setattr(mapper, "sweep_device", altered)
    r = run.run_cell(CELL, 2_500_000_011, 1.0, False, require_chip=False,
                     traffic_over={"ids": 8192, "check_ids": 8192})
    assert r["correct"] is False
    assert r["compared"]["placements_wrong"]["value"] == \
        r["attempted"] * -(-8192 // 5)


@pytest.mark.parametrize("what", ["bucket_ids", "bucket_weights"])
def test_the_driver_refuses_another_map(what):
    from drivers import crush_weighted_sweep

    cfg = json.loads(json.dumps(run.load_cell(CELL)["cfg"]))
    if what == "bucket_ids":
        cfg["layers"][1]["bucket_ids"][0] = -99
    else:
        cfg["bucket_weights"]["rack"][3] += 1
    d = crush_weighted_sweep.Driver(cfg, {"check_ids": 16, "ids": 1024}, 1)
    with pytest.raises(RuntimeError, match="not the configuration's"):
        d.setup()
