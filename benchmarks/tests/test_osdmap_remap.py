"""The per-epoch remap cell: `crush-1024osd-pgremap` on
`osdmap-rep3-32768pg-1024osd` under `remap-epochs-32768pg`.  CPU only,
at a small size (64 OSDs in 4 hosts, a pool of 256 PGs, 16 upmapped);
the map's layout, its rule, tunables and the epoch cycle stay the
configuration's.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_osdmap_remap.py -q
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

import reference_osdmap  # noqa: E402
import run  # noqa: E402
import work  # noqa: E402

CELL, CONFIG = "crush-1024osd-pgremap", "osdmap-rep3-32768pg-1024osd"
REMAP = ["remap_apply_ms", "remap_host_ms", "remap_rule_ms"]
SEED = 4_100_000_017


def small(cfg: dict) -> dict:
    """The configuration at 64 OSDs in 4 hosts and 256 PGs."""
    cfg = json.loads(json.dumps(cfg))
    cfg["num_osds"], cfg["hosts"] = 64, 4
    cfg["osd_weights"] = [0x10000] * 64
    cfg["layers"][0]["bucket_ids"] = [-1, -2, -3, -4]
    cfg["layers"][1]["bucket_ids"] = [-5]
    cfg["pool"].update(pg_num=256, pgp_num=256)
    cfg["upmap_items"] = 16
    return cfg


@pytest.fixture
def small_cell(monkeypatch):
    real = run.load_cell

    def load(name):
        spec = real(name)
        if name == CELL:
            spec["cfg"] = small(spec["cfg"])
        return spec

    monkeypatch.setattr(run, "load_cell", load)


def rehearse(seconds=2.0, **kw) -> dict:
    return run.run_cell(CELL, SEED, seconds, kw.pop("trace", False),
                        require_chip=False, **kw)


# -- the manifest -------------------------------------------------------------------
def test_the_manifest_gained_one_configuration_one_cell_three_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    conf, = [c for c in m["configs"] if c["name"] == CONFIG]
    assert conf["reduced"] == [] and len(conf["source"]) <= 200
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"
    cell, = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "remap-epochs-32768pg", 1)
    assert len(cell["why"]) <= 200 and len(conf["why"]) <= 200
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert e2e["placements_per_s"]["workloads"][-1] == CELL
    assert m["per_layer"][-3:] == [
        {"name": n, "unit": "ms", "better": "lower",
         "source": "program_counter", "layer": layer,
         "moves": "placements_per_s", "workloads": [CELL]}
        for n, layer in zip(REMAP, ["OSDMap epoch", "OSDMap epoch",
                                    "kernels"])]
    spec = run.load_cell(CELL)
    assert [x["name"] for x in spec["end_to_end"]] == [
        "placements_per_s", "setup_s"]
    assert [x["name"] for x in spec["per_layer"]] == [
        "inline_compiles.crush", "crush_roofline",
        "device_idle_pct.crush"] + REMAP


def test_the_configuration_states_the_deployment():
    cfg = run.load_cell(CELL)["cfg"]
    flat = run.load_cell("crush-1024osd-sweep-10M")["cfg"]
    assert cfg["name"] == CONFIG and cfg["reduced"] == {}
    assert (cfg["num_osds"], cfg["hosts"], cfg["num_rep"]) == (1024, 64, 3)
    assert cfg["layers"][0]["bucket_ids"] == flat["host_bucket_ids"]
    assert cfg["layers"][1]["bucket_ids"] == [flat["root_bucket_id"]]
    assert cfg["osd_weights"] == [flat["osd_weight"]] * 1024
    assert cfg["tunables"] == flat["tunables"]
    assert cfg["rule_steps"] == [
        ["take", "root"], ["chooseleaf_firstn", 0, "host"], ["emit"]]
    assert cfg["pool"] == {
        "pool_id": 1, "type": "replicated", "pg_num": 32768,
        "pgp_num": 32768, "size": 3, "min_size": 2,
        "flags": ["hashpspool"]}
    assert (cfg["upmap_items"], cfg["affinity"]) == (2048, 0x8000)
    assert set(cfg["assumed"]) == {
        "upmap_items", "primary_affinity", "epoch_cycle", "pg_temp_priming"}
    assert work.crush_bytes(cfg, {"ids": 32768}) == 32768 * 4 * 4


# -- the reference against the scalar path -------------------------------------------
def test_the_reference_agrees_with_the_scalar_path(small_cell):
    """`OSDMap.pg_to_up_acting` (the native `do_rule`, one PG at a time)
    is the second witness: through a whole cycle with upmap items,
    primary affinity, an out OSD, a down one and pg_temp, every PG."""
    from drivers import osdmap_remap

    d = osdmap_remap.Driver(small(run.load_cell(CELL)["cfg"]),
                            {"check_pgs": 32, "check_upmapped": 8}, SEED)
    maps = []
    real = d._apply

    def keep(delta):
        maps.append(real(delta))
        return maps[-1]

    d._apply = keep
    d.setup()
    ref = reference_osdmap.OSDMapRef(d.cfg)
    ref.apply(d.tables)
    assert ref.primary_affinity is not None
    for delta, m in zip([e[2] for e in d.epochs], maps[1:]):
        ref.apply(delta)
        want = ref.pg_to_up_acting(range(d.pg_num))
        for ps in range(d.pg_num):
            up, upp, act, actp = m.pg_to_up_acting((d.pool_id, ps))
            assert [int(o) for o in want[0][ps] if o != 0x7FFFFFFF] == up
            assert [int(o) for o in want[2][ps] if o != 0x7FFFFFFF] == act
            assert (want[1][ps], want[3][ps]) == (upp, actp)
    assert d.epochs[0][2]["new_pg_temp"] and d.epochs[1][2]["new_down"]
    d.close()


# -- a run, rehearsed ------------------------------------------------------------------
@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal_ends_in_the_contracts_line(trace, small_cell):
    r = rehearse(trace=trace)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu"
    assert r["compared"] == {k: {"value": 0, "limit": 0} for k in (
        "placements_wrong", "epochs_compared_none")}
    if trace:
        # no device plane on the CPU: the roofline share is left out
        assert set(r["metrics"]) == {
            "inline_compiles.crush", "device_idle_pct.crush"} | set(REMAP)
        assert r["metrics"]["inline_compiles.crush"]["value"] == 0
        assert all(r["metrics"][n]["value"] > 0 for n in REMAP)
    else:
        assert set(r["metrics"]) == {"placements_per_s", "setup_s"}
    json.dumps(r)


def test_the_control_is_refused(small_cell):
    r = rehearse(1.0, control=True)
    assert r["correct"] is False
    assert r["compared"]["placements_wrong"]["value"] > 0
    assert r["compared"]["epochs_compared_none"]["value"] == 0


def test_one_acting_row_altered_is_caught(small_cell, monkeypatch):
    from ceph_tpu.osd import osdmap

    real = osdmap.OSDMap.map_pgs

    def altered(self, pool_id):
        out = real(self, pool_id)
        out["acting"][7, 1] = (out["acting"][7, 1] + 1) % self.max_osd
        return out

    monkeypatch.setattr(osdmap.OSDMap, "map_pgs", altered)
    r = rehearse(1.0)
    assert r["correct"] is False
    # the last epoch is compared at every PG, PG 7 among them
    assert r["compared"]["placements_wrong"]["value"] >= 1


def test_a_first_raw_row_altered_is_caught(small_cell, monkeypatch):
    """The upmap pairs are drawn against the program's first raw mapping:
    the check holds that mapping to the reference's at every upmapped
    PG."""
    from drivers import osdmap_remap

    real = osdmap_remap.Driver._draw_upmaps

    def altered(self, raw, per):
        out = real(self, raw, per)
        (_pool, ps), = list(out)[:1]
        self.first_raw = self.first_raw.copy()
        self.first_raw[ps, 0] = (self.first_raw[ps, 0] + 1) % 64
        return out

    monkeypatch.setattr(osdmap_remap.Driver, "_draw_upmaps", altered)
    r = rehearse(1.0)
    assert r["correct"] is False
    assert r["compared"]["placements_wrong"]["value"] >= 1


def test_the_driver_refuses_another_map(small_cell):
    from drivers import osdmap_remap

    cfg = small(run.load_cell(CELL)["cfg"])
    cfg["layers"][1]["bucket_ids"] = [-9]
    d = osdmap_remap.Driver(cfg, {"check_pgs": 8, "check_upmapped": 2}, 1)
    with pytest.raises(RuntimeError, match="not the configuration's"):
        d.setup()


def test_a_parent_without_the_totals_reads_nothing(small_cell):
    """The remap readers give nothing, and do not raise, where the
    driver's snapshots lack `osdmap.mappings` (a program without it)."""
    from readers import span_per_epoch

    for n in REMAP:
        args = run.metric_how(n)["args"]
        assert span_per_epoch.read(args, {"before": {}, "after": {}}) is None
