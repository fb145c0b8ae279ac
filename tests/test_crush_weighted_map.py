"""A map whose CRUSH weights are its drives' capacities (PR 34): three
drive generations over root/rack/host, so that no straw2 level has
uniform weights and every item of every level is drawn in full (the
table path), in the one-shot pass too.

Small size, CPU: 128 OSDs, `host straw2 4 rack straw2 4 root straw2 0`,
the benchmark configuration's three weights laid out by its h mod 4
recipe.  Placements are held to two witnesses: the C oracle
`_native.do_rule` and the benchmark's numpy reference
(benchmarks/reference_crush_firstn_tree.py, nothing of ceph_tpu in it).
Also here: `CrushMap.adjust_item_weight` and `crushtool
--reweight-item`, by which such a map is made.
"""

import contextlib
import io
import json
import os
import sys

import numpy as np
import pytest

from ceph_tpu import _native
from ceph_tpu.core import tracing
from ceph_tpu.core.tracing import COUNTS, NAME
from ceph_tpu.crush import map as cmap
from ceph_tpu.crush import mapper

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "benchmarks"), os.path.join(ROOT, "tools")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import crushtool  # noqa: E402
import reference_crush_firstn_tree  # noqa: E402

TB4, TB8, TB16 = 0x3A352, 0x746A5, 0xE8D4A
N_OSDS, PER_HOST, HOSTS = 128, 4, 32
LAYERS = [{"type_name": "host", "type_id": 1, "alg": "straw2", "size": 4,
           "bucket_ids": list(range(-1, -33, -1))},
          {"type_name": "rack", "type_id": 2, "alg": "straw2", "size": 4,
           "bucket_ids": list(range(-33, -41, -1))},
          {"type_name": "root", "type_id": 3, "alg": "straw2", "size": 0,
           "bucket_ids": [-41]}]
ROOT_ID = -41
STEPS = [(cmap.OP_TAKE, ROOT_ID, 0), (cmap.OP_CHOOSELEAF_FIRSTN, 0, 1),
         (cmap.OP_EMIT, 0, 0)]
XS = np.arange(4096, dtype=np.int32)
# device (override) weights: every OSD in; host 3 out and eight OSDs at
# 0.75
DEV_W = {"all_in": [],
         "host_out": [{"weight": 0, "osds": [12, 13, 14, 15]},
                      {"weight": 0xC000,
                       "osds": [4 * h + 1 for h in range(8, 16)]}]}


def osd_weights():
    w = []
    for h in range(HOSTS):
        w += {0: [TB4] * 4, 1: [TB8] * 4, 2: [TB4, TB8] * 2,
              3: [TB8 if h < HOSTS // 2 else TB16] * 4}[h % 4]
    return w


def cluster(mean=False):
    m, ids = cmap.build_layered_cluster(
        N_OSDS, [(la["type_id"], la["size"]) for la in LAYERS], osd_weights())
    assert ids == [la["bucket_ids"] for la in LAYERS]
    if mean:
        for b in m.buckets.values():
            b.weights = [b.weight // len(b.items)] * len(b.items)
    return m


def config(case):
    return {"num_osds": N_OSDS, "osd_weights": osd_weights(),
            "layers": LAYERS, "num_rep": 3,
            "rule_steps": [["take", "root"],
                           ["chooseleaf_firstn", 0, "host"], ["emit"]],
            "device_weights": {"default": 0x10000, "marks": DEV_W[case]},
            "tunables": {"choose_total_tries": 50, "choose_local_tries": 0,
                         "choose_local_fallback_tries": 0,
                         "chooseleaf_descend_once": 1,
                         "chooseleaf_vary_r": 1, "chooseleaf_stable": 1}}


def oracle(flat, dev_w, xs=XS):
    steps = np.asarray(STEPS, dtype=np.int32).ravel()
    return np.array([_native.do_rule(flat, steps, int(x), 3, dev_w)
                     for x in xs])


def sweep_device(flat, dev_w, xs=XS):
    got, overflow = mapper.sweep_device(flat, STEPS, 3, xs, dev_w,
                                        chunk=1024)
    assert not bool(overflow)
    return np.asarray(got)


PATHS = {
    "compile_rule": lambda flat, w, cfg: np.asarray(
        mapper.compile_rule(flat, STEPS, 3)(XS, w)),
    "sweep": lambda flat, w, cfg: mapper.sweep(flat, STEPS, 3, XS, w,
                                               chunk=1024),
    "sweep_device": lambda flat, w, cfg: sweep_device(flat, w),
    "reference": lambda flat, w, cfg:
        reference_crush_firstn_tree.CrushFirstnTreeRef(cfg).do_rule(XS),
}


# -- the builder takes a weight a device; sums go up -------------------------
def test_the_builder_takes_a_weight_for_each_device():
    m = cluster()
    w = osd_weights()
    assert m.buckets[-1].weights == [TB4] * 4
    assert m.buckets[-3].weights == [TB4, TB8, TB4, TB8]
    assert m.buckets[-33].weights == [4 * TB4, 4 * TB8, 2 * (TB4 + TB8),
                                      4 * TB8]
    assert m.buckets[-40].weights[3] == 4 * TB16
    assert m.buckets[ROOT_ID].weights == [
        sum(w[16 * r: 16 * r + 16]) for r in range(8)]
    # three device weights, four host weights, two rack weights: unlike
    # weights inside the root, every rack and the mixed hosts
    assert len(set(m.buckets[ROOT_ID].weights)) == 2
    assert all(len(set(m.buckets[b].weights)) >= 3 for b in range(-40, -32))
    # an int is what it was; a wrong count is refused
    flat, _ = cmap.build_layered_cluster(8, [(1, 0)], 0x20000)
    assert flat.buckets[-1].weights == [0x20000] * 8
    with pytest.raises(ValueError, match="device weights"):
        cmap.build_layered_cluster(8, [(1, 0)], [1, 2, 3])


# -- placements: every path against both witnesses ---------------------------
@pytest.mark.parametrize("case", list(DEV_W))
@pytest.mark.parametrize("path", list(PATHS))
def test_placements_equal_crush_do_rule(path, case):
    flat = cluster().flatten()
    cfg = config(case)
    dev_w = reference_crush_firstn_tree.device_weights(cfg)
    want = oracle(flat, dev_w)
    np.testing.assert_array_equal(PATHS[path](flat, dev_w, cfg), want)
    # three hosts a row, none of them the one that is out
    assert all(len(set(row // PER_HOST)) == 3 for row in want[:256])
    if case == "host_out":
        assert not np.isin(want, [12, 13, 14, 15]).any()


def test_weight_follows_capacity():
    """A 16 TB drive gets about four times a 4 TB drive's placements."""
    flat = cluster().flatten()
    got = sweep_device(flat, np.full(N_OSDS, 0x10000, np.uint32),
                       np.arange(1 << 15, dtype=np.int32))
    share = np.bincount(got.ravel(), minlength=N_OSDS)
    w = np.asarray(osd_weights())
    assert 3.2 < share[w == TB16].mean() / share[w == TB4].mean() < 4.8


# -- no level is fastcmp: plans, span, counter ----------------------------------
@pytest.mark.parametrize("budget", [1, 3, 0])
def test_every_level_of_every_stage_draws_in_full(budget):
    hm = mapper._HostMap(cluster().flatten())
    assert hm.table_mode and len(hm._distinct) == 9
    ch, = mapper._choose_plans(hm, STEPS, 3, budget,
                               cmap.Tunables())
    assert [(lv.width, lv.delta, lv.read) for lv in ch.plan] == [
        (8, 0, "const"), (4, 0, "onehot")]
    assert [(lv.width, lv.delta, lv.read) for lv in ch.leaf_plan] == [
        (4, 0, "onehot")]
    # descents a lane: one a replica in the one-shot pass, `budget` a
    # replica unrolled, one pass of the retry loop in the exact program
    tries = {1: 1, 3: 3, 0: 1}[budget]
    assert ch.descents() == (3 * tries, 3 * tries)
    assert ch.full_draws() == 3 * tries * (8 + 4 + 4)
    # the same tree with mean weights is the one the fast path was made
    # for: every level has its window
    mean, = mapper._choose_plans(mapper._HostMap(cluster(True).flatten()),
                                 STEPS, 3, 3, cmap.Tunables())
    assert all(lv.delta > 0 for lv in mean.plan + mean.leaf_plan)
    assert mean.full_draws() == 0


def test_the_span_counts_how_levels_draw_and_the_total_grows():
    flat = cluster().flatten()
    dev_w = np.full(N_OSDS, 0x10000, np.uint32)
    before = mapper.sweep_totals()
    n0 = len(tracing.recorder().held()[0])
    sweep_device(flat, dev_w)
    span, = [r for r in tracing.recorder().held()[0][n0:]
             if r[NAME] == "crush.sweep"]
    # three stage programs of three levels each, all through the tables
    assert {k: span[COUNTS][k] for k in (
        "draw_fast", "draw_table", "draw_limb", "const", "onehot",
        "gather")} == {"draw_fast": 0, "draw_table": 9, "draw_limb": 0,
                       "const": 3, "onehot": 6, "gather": 0}
    after = mapper.sweep_totals()
    grew = {k: after[k] - before[k] for k in after}
    assert grew["crush.ids"] == len(XS)
    assert 0.02 * len(XS) < grew["crush.mid_lanes"] < 0.2 * len(XS)
    # 48 items an id in the one-shot pass, three tries of that a lane of
    # the budgeted stage, one pass of it a lane of the exact stage
    assert grew["crush.full_draws"] == 48 * (
        len(XS) + 3 * grew["crush.mid_lanes"] + grew["crush.slow_lanes"])
    # the host sweep files the same
    mapper.sweep(flat, STEPS, 3, XS, dev_w, chunk=1024)
    host = {k: v - after[k] for k, v in mapper.sweep_totals().items()}
    assert host == grew
    assert tracing.SPANS["crush.full_draws"] == "crush_full_draws_per_id"


def test_uniform_weights_draw_in_full_in_the_exact_stage_only():
    m, root = cmap.build_flat_cluster(64, hosts=8)
    steps = [(cmap.OP_TAKE, root, 0), (cmap.OP_CHOOSELEAF_FIRSTN, 3, 1),
             (cmap.OP_EMIT, 0, 0)]
    flat = m.flatten()
    fast, mid, slow = mapper._stage_programs(
        flat, steps, 3, None, mapper.DEFAULT_PLAN, True)
    assert (fast.full_draws, mid.full_draws, slow.full_draws) == (
        0, 0, 3 * (8 + 8))
    assert fast.draws["draw_fast"] == mid.draws["draw_fast"] == 2
    assert slow.draws == {"draw_fast": 0, "draw_table": 2, "draw_limb": 0}


def test_the_mean_weight_map_places_elsewhere():
    """The control's premise: the tree with every bucket's weights
    replaced by their mean is another map."""
    dev_w = np.full(N_OSDS, 0x10000, np.uint32)
    got = sweep_device(cluster(True).flatten(), dev_w)
    want = oracle(cluster().flatten(), dev_w)
    assert (got != want).any(axis=1).mean() > 0.1


# -- adjust_item_weight ---------------------------------------------------------
def sums_hold(m):
    """Every bucket's weight as an item is the sum of its own items'."""
    return all(m.buckets[it].weight == w
               for b in m.buckets.values()
               for it, w in zip(b.items, b.weights) if it < 0)


@pytest.mark.parametrize("weight", [TB16, 0])
def test_adjust_item_weight_carries_a_device_up(weight):
    m = cluster()
    root_was = m.buckets[ROOT_ID].weight
    assert m.adjust_item_weight(5, weight) == 1      # host 1: 4 x 8 TB
    assert m.buckets[-2].weights == [TB8, weight, TB8, TB8]
    assert m.buckets[-33].weights[1] == 3 * TB8 + weight
    assert m.buckets[ROOT_ID].weights[0] == m.buckets[-33].weight
    assert m.buckets[ROOT_ID].weight == root_was + weight - TB8
    assert sums_hold(m)
    # reweight_item stays the one-bucket step: the sums above go stale
    m.reweight_item(-2, 5, TB4)
    assert not sums_hold(m)


def test_adjust_item_weight_in_every_bucket_that_holds_the_item():
    m = cluster()
    # a second root over racks 0 and 1, as a rule for one room has
    room = m.add_bucket(cmap.ALG_STRAW2, 3, [-33, -34],
                        [m.buckets[-33].weight, m.buckets[-34].weight])
    assert m.adjust_item_weight(-1, 7) == 1          # host 0, in rack 0
    assert m.buckets[-33].weights[0] == 7
    assert m.adjust_item_weight(-33, 1000) == 2      # both roots hold it
    assert m.buckets[ROOT_ID].weights[0] == 1000
    assert m.buckets[room].weights == [1000, m.buckets[-34].weight]
    # a device two hosts hold
    m.add_item(-2, 0, TB4)
    assert m.adjust_item_weight(0, TB8) == 2
    assert m.buckets[-1].weights[0] == m.buckets[-2].weights[-1] == TB8
    assert m.buckets[-33].weights[:2] == [m.buckets[-1].weight,
                                         m.buckets[-2].weight]
    assert m.adjust_item_weight(999, 1) == 0


def test_reweighting_a_uniform_build_gives_the_weighted_build():
    m, _ = cmap.build_layered_cluster(
        N_OSDS, [(la["type_id"], la["size"]) for la in LAYERS])
    for osd, w in enumerate(osd_weights()):
        m.adjust_item_weight(osd, w)
    want = cluster()
    assert {b: (v.items, v.weights) for b, v in m.buckets.items()} == \
        {b: (v.items, v.weights) for b, v in want.buckets.items()}


# -- crushtool --reweight-item --------------------------------------------------
def crushtool_main(argv):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = crushtool.main(argv)
    return rc, buf.getvalue()


def test_crushtool_reweight_item_then_test(tmp_path):
    build = ["--build", "--num_osds", "32", "host", "straw2", "4",
             "root", "straw2", "0"]
    rc, text = crushtool_main(build + [
        "--reweight-item", "osd.5", "3.638", "--reweight-item", "osd.20",
        "14.552", "--test", "--num-rep", "3", "--max-x", "1023",
        "--show-mappings", "--max-show", "1024", "-o",
        str(tmp_path / "m.bin")])
    assert rc == 0
    got = json.loads(text[text.index("{"):])["mappings"]
    m, ids = cmap.build_layered_cluster(32, [(1, 4), (2, 0)])
    m.adjust_item_weight(5, int(3.638 * 0x10000))
    m.adjust_item_weight(20, int(14.552 * 0x10000))
    assert m.buckets[-2].weights[1] == int(3.638 * 0x10000)
    assert m.buckets[ids[-1][0]].weights[5] == m.buckets[-6].weight
    steps = np.asarray([(cmap.OP_TAKE, ids[-1][0], 0),
                        (cmap.OP_CHOOSELEAF_FIRSTN, 3, 1),
                        (cmap.OP_EMIT, 0, 0)], dtype=np.int32).ravel()
    flat = m.flatten()
    dev_w = np.full(32, 0x10000, np.uint32)
    for x in range(1024):
        assert got[str(x)] == list(_native.do_rule(flat, steps, x, 3, dev_w))
    # the heavier drive draws more than its neighbours
    share = np.bincount(np.asarray(list(got.values())).ravel(), minlength=32)
    assert share[20] > 2 * np.delete(share, [5, 20]).mean()
    # the written map carries the weights; a bucket goes by -d's name
    rc, text = crushtool_main(["-i", str(tmp_path / "m.bin"),
                               "--reweight-item", "bucket2", "8", "-d"])
    assert rc == 0 and "item bucket2 weight 8.000" in text
    assert "item osd.20 weight 14.55" in text
    with pytest.raises(SystemExit, match="no item"):
        crushtool_main(build + ["--reweight-item", "rack9", "1"])
