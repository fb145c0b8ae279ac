"""The per-epoch remap: `Incremental.apply`, then `OSDMap.map_pgs`, held
to `benchmarks/reference_osdmap.py` (numpy, written from OSDMap.cc) and to
the scalar `pg_to_up_acting` (the native `do_rule`) over one whole epoch
cycle of the benchmark's driver, at 64 OSDs in 4 hosts and 256 PGs: upmap
items, an out OSD that is an upmap target, a down OSD, primary affinity
and pg_temp all present.  Also the spans and counters `map_pgs` and
`apply` file, and the control's mapping.
"""

import json
import os
import sys

import numpy as np
import pytest

from ceph_tpu.core import tracing
from ceph_tpu.crush import map as cmap
from ceph_tpu.crush import mapper as cmapper
from ceph_tpu.osd import map_inc
from ceph_tpu.osd import osdmap as om
from ceph_tpu.osd.map_inc import Incremental

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "benchmarks") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import reference_osdmap  # noqa: E402
from drivers import osdmap_remap  # noqa: E402

NONE = om.CRUSH_ITEM_NONE
SEED = 4_100_000_041
TRAFFIC = {"check_pgs": 32, "check_upmapped": 8}


def _cfg(hosts: int = 4, pg_num: int = 256) -> dict:
    """The benchmark's configuration at 64 OSDs in `hosts` hosts, with
    `pg_num` PGs and an upmap item for one PG in 16."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "osdmap-rep3-32768pg-1024osd.json")) as f:
        cfg = json.load(f)
    cfg["num_osds"], cfg["hosts"] = 64, hosts
    cfg["osd_weights"] = [0x10000] * 64
    cfg["layers"][0].update(size=64 // hosts,
                            bucket_ids=list(range(-1, -hosts - 1, -1)))
    cfg["layers"][1]["bucket_ids"] = [-hosts - 1]
    cfg["pool"].update(pg_num=pg_num, pgp_num=pg_num)
    cfg["upmap_items"] = pg_num // 16
    return cfg


def _driver(control: bool = False, cfg=None):
    """The driver's set-up (the tables and one whole cycle), with A the
    target of an upmap item and B one of another item's PG's OSDs, and
    the map of each epoch kept."""
    make = osdmap_remap.control if control else osdmap_remap.Driver
    d = make(cfg or _cfg(), TRAFFIC, SEED)
    maps = []
    apply = d._apply

    def keep(delta):
        maps.append(apply(delta))
        return maps[-1]

    def ab(cycle):
        items = list(d.tables["new_pg_upmap_items"].values())
        return items[0][0][1], items[1][0][0]

    d._apply, d._draw_ab = keep, ab
    before = om.mapping_totals()
    d.setup()
    # what map_pgs' totals moved by in the set-up
    d.moved = {k: v - before[k] for k, v in om.mapping_totals().items()}
    return d, maps[1:]


@pytest.fixture(scope="module")
def cycle():
    """(driver, the four epochs' maps, the reference's four results)."""
    d, maps = _driver()
    ref = reference_osdmap.OSDMapRef(d.cfg)
    first = ref.raw(range(d.pg_num))
    assert all([o for o in d.first_raw[ps] if o != NONE] == first[ps]
               for ps in range(d.pg_num))
    ref.apply(d.tables)
    want = []
    for _t0, _t1, delta, _got in d.epochs:
        ref.apply(delta)
        want.append(ref.pg_to_up_acting(range(d.pg_num)))
    return d, maps, want


@pytest.mark.parametrize("epoch", osdmap_remap.KINDS)
def test_map_pgs_equals_the_reference_and_the_scalar_path(epoch, cycle):
    d, maps, want = cycle
    i = osdmap_remap.KINDS.index(epoch)
    m, got = maps[i], d.epochs[i][3]
    # what the epoch holds
    assert m.osd_primary_affinity is not None and m.pg_upmap_items
    assert (m.osd_weight[d.a] == 0) == (epoch in ("out", "down"))
    assert bool(m.osd_state_up[d.b]) == (epoch in ("out", "up"))
    assert bool(m.pg_temp) == (epoch in ("out", "down"))
    if epoch == "out":   # an upmap item whose target is out is skipped
        assert any(to == d.a for (_f, to), in m.pg_upmap_items.values())
    for k, w in zip(("up", "up_primary", "acting", "acting_primary"),
                    want[i]):
        np.testing.assert_array_equal(got[k], w, err_msg=k)
    for ps in range(d.pg_num):
        up, upp, act, actp = m.pg_to_up_acting((d.pool_id, ps))
        assert [o for o in got["up"][ps] if o != NONE] == up
        assert [o for o in got["acting"][ps] if o != NONE] == act
        assert (got["up_primary"][ps], got["acting_primary"][ps]) == (
            upp, actp)


def test_the_controls_mapping_differs(cycle):
    """The control maps without pg_upmap_items and pg_temp: the upmapped
    and the primed PGs differ from the reference's."""
    d, _maps, want = cycle
    c, _ = _driver(control=True)
    got = c.epochs[0][3]
    wrong = (got["acting"] != want[0][2]).any(axis=1)
    assert wrong.sum() > len(c.primed) // 2
    assert wrong[[ps for _p, ps in d.tables["new_pg_upmap_items"]]].any()


@pytest.fixture(scope="module")
def staged_cycle():
    """(driver, the four epochs' maps) on 64 OSDs in 8 hosts with 4,096
    PGs: more than the 2,048 lanes the staged sweep's exact stage takes,
    so map_pgs places them through the staged plan."""
    return _driver(cfg=_cfg(hosts=8, pg_num=4096))


def _pps(m, d) -> np.ndarray:
    return m.pools[d.pool_id].pps_vector(
        np.arange(d.pg_num, dtype=np.int64)).astype(np.int32)


def _equals_the_scalar_path(m, d, got) -> None:
    for ps in range(d.pg_num):
        up, upp, act, actp = m.pg_to_up_acting((d.pool_id, ps))
        assert [o for o in got["up"][ps] if o != NONE] == up
        assert [o for o in got["acting"][ps] if o != NONE] == act
        assert (got["up_primary"][ps], got["acting_primary"][ps]) == (
            upp, actp)


@pytest.mark.parametrize("pool,staged", [("cycle", False),
                                         ("staged_cycle", True)])
def test_map_pgs_stages_a_pool_only_above_the_exact_stages_capacity(
        pool, staged, request):
    """Every mapping of the set-up (the plain map, the tables, the four
    epochs) took the staged plan for 4,096 PGs and none for the 256-PG
    rehearsal pool; none overflowed."""
    d, maps = request.getfixturevalue(pool)[:2]
    n = 2 + len(osdmap_remap.KINDS)
    assert d.moved["osdmap.mappings"] == n
    assert d.moved["osdmap.staged_mappings"] == (n if staged else 0)
    assert d.moved["osdmap.rule_overflows"] == 0
    assert all(m._staged(m.pools[d.pool_id]) == staged for m in maps)


@pytest.mark.parametrize("epoch", osdmap_remap.KINDS)
def test_staged_map_pgs_equals_the_exact_program_and_the_scalar_path(
        epoch, staged_cycle):
    """The staged sweep's raw rows are the exact program's on every lane,
    and the epoch's four results (upmap items, affinity, pg_temp on top)
    the scalar path's on every PG."""
    d, maps = staged_cycle
    i = osdmap_remap.KINDS.index(epoch)
    m, got = maps[i], d.epochs[i][3]
    assert (m.osd_weight[d.a] == 0) == (epoch in ("out", "down"))
    assert bool(m.pg_temp) == (epoch in ("out", "down"))
    pool, pps = m.pools[d.pool_id], _pps(m, d)
    fn = m._rule_fn(pool)
    np.testing.assert_array_equal(m._rule_raw(pool, fn, pps, True),
                                  np.asarray(fn(pps, m.osd_weight)))
    _equals_the_scalar_path(m, d, got)


def test_an_overflowing_staged_sweep_is_mapped_again_by_the_exact_program(
        staged_cycle, monkeypatch):
    """With five hosts of eight out the plan gives the exact stage every
    lane, so the pool is not staged.  A plan that misjudges the map,
    pinned here to the healthy map's, sends it through the staged sweep,
    which overflows: map_pgs places the pool again by the exact program
    and counts it."""
    d, maps = staged_cycle
    m = map_inc.clone_map(maps[-1])
    pool = m.pools[d.pool_id]
    healthy = cmapper.sweep_plan(m._flatten(), m.crush.rules[
        pool.crush_rule].steps, pool.size, m.osd_weight)
    m.osd_weight[24:] = 0
    assert not m._staged(pool)
    monkeypatch.setattr(cmapper, "sweep_plan", lambda *a, **k: healthy)
    assert m._staged(pool)
    before = om.mapping_totals()
    got = m.map_pgs(d.pool_id)
    moved = {k: v - before[k] for k, v in om.mapping_totals().items()}
    assert (moved["osdmap.mappings"], moved["osdmap.staged_mappings"],
            moved["osdmap.rule_overflows"]) == (1, 1, 1)
    pps = _pps(m, d)
    raw = np.asarray(m._rule_fn(pool)(pps, m.osd_weight))
    assert (raw[:, 1:] == NONE).any()   # some PGs lack a replica
    np.testing.assert_array_equal(
        m._rule_raw(pool, m._rule_fn(pool), pps, True), raw)
    assert om.mapping_totals()["osdmap.rule_overflows"] == (
        before["osdmap.rule_overflows"] + 2)
    _equals_the_scalar_path(m, d, got)


def _small_map():
    m, root = cmap.build_flat_cluster(32, hosts=8)
    rule = m.add_simple_rule("replicated_rule", root, 1, "firstn")
    osdmap = om.OSDMap(m)
    osdmap.add_pool(om.PGPool(1, size=3, pg_num=32, pgp_num=32,
                              crush_rule=rule))
    return osdmap


def _small_ref():
    """The reference on `_small_map`'s map and pool."""
    cfg = _cfg()
    cfg.update(num_osds=32, hosts=8, osd_weights=[0x10000] * 32)
    cfg["layers"][0].update(size=4, bucket_ids=list(range(-1, -9, -1)))
    cfg["layers"][1]["bucket_ids"] = [-9]
    cfg["pool"].update(pg_num=32, pgp_num=32)
    return reference_osdmap.OSDMapRef(cfg)


def test_an_osd_that_does_not_exist_leaves_raw_before_the_upmap_items():
    """OSDMap.cc `_pg_to_raw_osds` drops OSDs that do not exist before
    `_apply_upmap`: an item whose `from` is such an OSD finds nothing to
    replace, on the vector and the scalar path alike."""
    osdmap = _small_map()
    raw = osdmap.map_pgs(1)["raw"]
    row = [int(o) for o in raw[5]]
    gone = row[1]
    tgt = next(o for o in range(32) if o // 4 not in {r // 4 for r in row})
    osdmap.pg_upmap_items[(1, 5)] = [(gone, tgt)]
    osdmap.osd_state_exists[gone] = False   # still weighted in CRUSH
    ref = _small_ref()
    ref.apply({"new_pg_upmap_items": {(1, 5): [(gone, tgt)]}})
    ref.exists[gone] = False
    got = osdmap.map_pgs(1)
    assert [int(o) for o in got["raw"][5]] == [row[0], row[2], NONE]
    want = ref.pg_to_up_acting(range(32))
    for k, w in zip(("up", "up_primary", "acting", "acting_primary"), want):
        np.testing.assert_array_equal(got[k], w, err_msg=k)
    up, _upp, act, _actp = osdmap.pg_to_up_acting((1, 5))
    assert up == act == [row[0], row[2]]


def test_map_pgs_files_its_spans_and_counters_with_seq():
    osdmap = _small_map()
    raw = osdmap.map_pgs(1)["raw"]
    osdmap.pg_temp[(1, 3)] = [int(o) for o in raw[4]]
    osdmap.pg_upmap_items[(1, 5)] = [(int(raw[5][0]), int(raw[6][0]))]
    before = om.mapping_totals()
    osdmap.map_pgs(1)   # the cache is warm: no flatten
    after = om.mapping_totals()
    # 32 PGs: fewer than the staged sweep's exact stage takes
    assert after == {"osdmap.mappings": before["osdmap.mappings"] + 1,
                     "osdmap.pgs_mapped": before["osdmap.pgs_mapped"] + 32,
                     "osdmap.temp_rows": before["osdmap.temp_rows"] + 1,
                     "osdmap.staged_mappings":
                         before["osdmap.staged_mappings"],
                     "osdmap.rule_overflows": before["osdmap.rule_overflows"]}
    applies = map_inc.apply_totals()["osdmap.applies"]
    nxt = Incremental(epoch=osdmap.epoch + 1,
                      prev_epoch=osdmap.epoch).apply(osdmap)
    assert map_inc.apply_totals() == {"osdmap.applies": applies + 1}
    nxt.map_pgs(1)      # a new map: its cache is empty
    recs, _ = tracing.recorder().held()

    def named(name, **counts):
        return [r for r in recs if r[tracing.NAME] == name and all(
            r[tracing.COUNTS].get(k) == v for k, v in counts.items())]

    n = after["osdmap.mappings"]
    warm, = named("osdmap.map_pgs", mapping=n)
    cold, = named("osdmap.map_pgs", mapping=n + 1)
    assert warm[tracing.COUNTS] == {"mapping": n, "pool": 1, "pgs": 32,
                                    "upmap_rows": 1, "temp_rows": 1}
    # the apply between the two calls counts the value its total took
    applied, = named("osdmap.apply", apply=applies + 1)
    assert applied[tracing.COUNTS] == {"epoch": nxt.epoch,
                                       "apply": applies + 1}
    assert warm[tracing.T1] <= applied[tracing.T0]
    assert applied[tracing.T1] <= cold[tracing.T0]

    def kids(r):
        return [k[tracing.NAME] for k in recs
                if k[tracing.PARENT] == r[tracing.ID]]

    steps = ["osdmap.pps", "osdmap.rule", "osdmap.exceptions",
             "osdmap.up", "osdmap.temp"]
    assert kids(warm) == steps
    assert kids(cold) == steps[:1] + ["osdmap.flatten"] + steps[1:]
    rule, = [k for k in recs if k[tracing.PARENT] == warm[tracing.ID]
             and k[tracing.NAME] == "osdmap.rule"]
    assert kids(rule) == ["dev.dispatch", "dev.wait"]
    assert rule[tracing.COUNTS] == {"staged": 0}
    assert all(tracing.SPANS[n] for n in steps + ["osdmap.map_pgs",
                                                  "osdmap.apply"])
    assert {"osdmap.applies", "osdmap.mappings", "osdmap.pgs_mapped",
            "osdmap.temp_rows", "osdmap.staged_mappings",
            "osdmap.rule_overflows"} <= set(tracing.SPANS)


def test_a_pg_upmap_onto_an_out_osd_drops_the_pgs_items_too():
    """OSDMap.cc `_apply_upmap` returns where a pg_upmap names an out
    OSD: neither the mapping nor the PG's pg_upmap_items apply."""
    osdmap = _small_map()
    raw = osdmap.map_pgs(1)["raw"]
    row = [int(o) for o in raw[5]]
    other = next(o for o in range(32) if o // 4 not in {r // 4 for r in row})
    osdmap.pg_upmap[(1, 5)] = [other, row[1], row[2]]
    tgt = next(o for o in range(32) if o not in row + [other])
    osdmap.pg_upmap_items[(1, 5)] = [(row[1], tgt)]
    osdmap.set_osd_out(other)
    up, *_ = osdmap.pg_to_up_acting((1, 5))
    want = [int(o) for o in osdmap.map_pgs(1)["raw"][5]]
    assert up == want and row[1] in want
    assert [int(o) for o in osdmap.map_pgs(1)["up"][5]] == up
    # and the reference says the same, with the same tables
    ref = _small_ref()
    ref.apply({"new_weights": {other: 0},
               "new_pg_upmap": {(1, 5): osdmap.pg_upmap[(1, 5)]},
               "new_pg_upmap_items": {(1, 5): osdmap.pg_upmap_items[(1, 5)]}})
    assert [int(o) for o in ref.pg_to_up_acting([5])[0][0]] == up


def test_batch_window_selects_the_queues_spans_by_seq():
    """A mapping counts no `seq`, the stripe queue's join: a map_pgs
    span beside a batch of the range is not a batch of the window."""
    _small_map().map_pgs(1)
    recs, _ = tracing.recorder().held()
    counts = [r[tracing.COUNTS] for r in recs
              if r[tracing.NAME] == "osdmap.map_pgs"][-1]
    assert "seq" not in counts
    rec = tracing.Recorder(capacity=64)
    with rec.span("queue.batch", q=1, seq=3):
        pass
    with rec.span("osdmap.map_pgs", **dict(counts, mapping=3)):
        pass
    w = tracing.batch_window(2, 3, rec)
    assert w is not None and w.batches == 1
    assert set(w.self_ns) == {"queue.batch"}
