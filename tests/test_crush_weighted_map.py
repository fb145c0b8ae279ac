"""A map whose CRUSH weights are its drives' capacities (PR 34): three
drive generations over root/rack/host, so that no straw2 level has
uniform weights.  Since PR 35 a level whose buckets hold fewer distinct
weights than items draws one candidate a weight class (the class
draw); the exact program still draws every item in full (the table
path).

Small size, CPU: 128 OSDs, `host straw2 4 rack straw2 4 root straw2 0`,
the benchmark configuration's three weights laid out by its h mod 4
recipe.  Placements are held to two witnesses: the C oracle
`_native.do_rule` and the benchmark's numpy reference
(benchmarks/reference_crush_firstn_tree.py, nothing of ceph_tpu in it).
Also here: `CrushMap.adjust_item_weight` and `crushtool
--reweight-item`, by which such a map is made; and the benchmark
cell's own map (1,024 OSDs, hosts of 16), on which the plans, the span
and the sweeps of a firstn and an indep rule are held to the oracle.
"""

import contextlib
import io
import json
import os
import sys

import functools

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


# -- the benchmark cell's own map: plans, span, counter, sweeps ------------------
CELL_IDS = 16384


@functools.lru_cache(maxsize=None)
def cell():
    """(flat map, root id, configuration) of
    `crush-rep3-hetero-rack-1024osd`."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "crush-rep3-hetero-rack-1024osd.json")) as f:
        cfg = json.load(f)
    m, ids = cmap.build_layered_cluster(
        cfg["num_osds"], [(la["type_id"], la["size"]) for la in cfg["layers"]],
        cfg["osd_weights"])
    return m.flatten(), ids[-1][0], cfg


def cell_steps(op=cmap.OP_CHOOSELEAF_FIRSTN):
    return [(cmap.OP_TAKE, cell()[1], 0), (op, 0, 1), (cmap.OP_EMIT, 0, 0)]


@pytest.mark.parametrize("budget", [1, 3, 0])
def test_every_level_of_every_stage_draws_in_full(budget):
    """What it said until PR 35, and still says of the exact program
    (budget 0).  The budgeted traces draw a candidate a weight class:
    2 rack weights in the root (the greater one over bound[2]: delta
    3), 4 host weights in a rack, 3 device weights over the 64 hosts."""
    flat, _, _ = cell()
    hm = mapper._HostMap(flat)
    assert hm.table_mode and len(hm._distinct) == 9
    ch, = mapper._choose_plans(hm, cell_steps(), 3, budget, cmap.Tunables())
    levels = [(lv.width, lv.delta, len(lv.classes), lv.read)
              for lv in ch.plan + ch.leaf_plan]
    # descents a lane: one a replica in the one-shot pass, `budget` a
    # replica unrolled, one pass of the retry loop in the exact program
    tries = {1: 1, 3: 3, 0: 1}[budget]
    assert ch.descents() == (3 * tries, 3 * tries)
    if budget == 0:
        assert levels == [(8, 0, 0, "const"), (8, 0, 0, "onehot"),
                          (16, 0, 0, "onehot")]
        assert ch.full_draws() == 3 * (8 + 8 + 16)
    else:
        assert levels == [(8, 3, 2, "const"), (8, 2, 4, "onehot"),
                          (16, 2, 3, "onehot")]
        # the classes are the weights' rows of the draw tables
        w = np.asarray(flat.weights)
        for lv in ch.plan + ch.leaf_plan:
            rows = w[list(lv.frontier), :lv.width]
            assert [int(hm._distinct[c]) for c in lv.classes] == sorted(
                set(rows[rows > 0].tolist()))
            assert lv.resolve == (budget == 3)
        # a candidate a class where a contested draw is flagged, its
        # runner-up too where it is settled
        assert ch.full_draws() == 3 * tries * (2 + 4 + 3) * (
            2 if budget == 3 else 1)
    # the same tree with mean weights is the one the fast path was made
    # for: every level has its window and one class a bucket
    mean, = mapper._choose_plans(mapper._HostMap(cluster(True).flatten()),
                                 STEPS, 3, 3, cmap.Tunables())
    assert all(lv.delta > 0 and not lv.classes
               for lv in mean.plan + mean.leaf_plan)
    assert mean.full_draws() == 0
    # the small map above: two classes in the root, as many host weights
    # as hosts in a rack (drawn in full), three classes over the hosts
    small, = mapper._choose_plans(mapper._HostMap(cluster().flatten()),
                                  STEPS, 3, budget, cmap.Tunables())
    assert [(lv.width, lv.delta, len(lv.classes))
            for lv in small.plan + small.leaf_plan] == (
        [(8, 0, 0), (4, 0, 0), (4, 0, 0)] if budget == 0 else
        [(8, 2, 2), (4, 0, 0), (4, 2, 3)])


@functools.lru_cache(maxsize=None)
def cell_sweep(op):
    """sweep_device over the cell's map, CELL_IDS ids: (placements, what
    the totals grew by, the span's counts, device weights)."""
    flat, _, cfg = cell()
    dev_w = np.full(cfg["num_osds"], 0x10000, np.uint32)
    nrep = 3
    if op == cmap.OP_CHOOSELEAF_INDEP:
        # an EC pool's 4+2 with a host out and eight OSDs at 0.75
        nrep = 6
        dev_w[:16] = 0
        dev_w[[16 * h + 5 for h in range(1, 9)]] = 0xC000
    before = mapper.sweep_totals()
    n0 = len(tracing.recorder().held()[0])
    got, overflow = mapper.sweep_device(
        flat, cell_steps(op), nrep, np.arange(CELL_IDS, dtype=np.int32),
        dev_w, chunk=4096)
    assert not bool(overflow)
    span, = [r for r in tracing.recorder().held()[0][n0:]
             if r[NAME] == "crush.sweep"]
    after = mapper.sweep_totals()
    return (np.asarray(got), {k: after[k] - before[k] for k in after},
            span[COUNTS], dev_w)


def test_the_span_counts_how_levels_draw_and_the_total_grows():
    flat, _, _ = cell()
    _, grew, counts, dev_w = cell_sweep(cmap.OP_CHOOSELEAF_FIRSTN)
    # three stage programs of three levels each: the one-shot and the
    # budgeted one draw by classes, the exact one through the tables
    assert {k: counts[k] for k in (
        "draw_fast", "draw_class", "draw_table", "draw_limb", "const",
        "onehot", "gather")} == {
            "draw_fast": 0, "draw_class": 6, "draw_table": 3, "draw_limb": 0,
            "const": 3, "onehot": 6, "gather": 0}
    assert grew["crush.ids"] == CELL_IDS
    assert 0.02 * CELL_IDS < grew["crush.mid_lanes"] < 0.2 * CELL_IDS
    # 9 candidates a replica in the one-shot pass; candidate and
    # runner-up, three tries a replica, a lane of the budgeted stage;
    # every item, one pass, a lane of the exact stage
    assert grew["crush.full_draws"] == (
        27 * CELL_IDS + 162 * grew["crush.mid_lanes"]
        + 96 * grew["crush.slow_lanes"])
    # the host sweep files the same
    before = mapper.sweep_totals()
    mapper.sweep(flat, cell_steps(), 3, np.arange(CELL_IDS, dtype=np.int32),
                 dev_w, chunk=4096)
    host = {k: v - before[k] for k, v in mapper.sweep_totals().items()}
    assert host == grew
    assert tracing.SPANS["crush.full_draws"] == "crush_full_draws_per_id"


def contested_lanes(flat, root, xs):
    """Lanes of the firstn one-shot pass (replica r descends with r at
    every level: vary_r 1, stable) in which a weight class of a bucket
    on a replica's way is contested, reckoned on the host with the true
    winners."""
    from ceph_tpu.crush import hashes, ln

    items, weights = np.asarray(flat.items), np.asarray(flat.weights)
    sizes = np.asarray(flat.sizes)
    bounds = ln.fastcmp_bounds()
    flagged = np.zeros(len(xs), bool)
    for r in range(3):
        bno = np.full(len(xs), -1 - root)
        for _ in range(3):   # root, rack, host
            width = int(sizes[bno].max())
            its, ws = items[bno, :width], weights[bno, :width]
            u = (hashes.hash32_3(xs.astype(np.uint32)[:, None],
                                 its.astype(np.uint32), np.uint32(r), xp=np)
                 & 0xFFFF).astype(np.int64)
            delta = next(d for d, b in bounds.items() if ws.max() <= b)
            for w in np.unique(ws[ws > 0]):
                uc = np.where(ws == w, u, -1)
                u1 = uc.max(axis=1)
                u2 = np.where(uc == u1[:, None], -1, uc).max(axis=1)
                flagged |= (u2 >= 0) & (u1 - u2 <= delta)
            win = ln.straw2_draw(u.astype(np.uint32), ws).argmax(axis=1)
            bno = -1 - its[np.arange(len(xs)), win]
    return flagged


def test_the_cells_firstn_sweeps_equal_crush_do_rule():
    """sweep_device == sweep() == _native.do_rule on the cell's own map,
    and the lanes whose class draw is contested are in crush.mid_lanes:
    the one-shot pass flags them for the budgeted stage."""
    flat, root, _ = cell()
    xs = np.arange(CELL_IDS, dtype=np.int32)
    got, grew, _, dev_w = cell_sweep(cmap.OP_CHOOSELEAF_FIRSTN)
    steps = np.asarray(cell_steps(), dtype=np.int32).ravel()
    want = np.array([_native.do_rule(flat, steps, int(x), 3, dev_w)
                     for x in xs])
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        mapper.sweep(flat, cell_steps(), 3, xs, dev_w, chunk=4096), want)
    fast = mapper.compile_rule(flat, cell_steps(), 3, one_shot=True)
    clean = np.concatenate([np.asarray(fast(xs[o:o + 4096], dev_w)[1])
                            for o in range(0, CELL_IDS, 4096)])
    assert grew["crush.mid_lanes"] == int((~clean).sum())
    flagged = contested_lanes(flat, root, xs)
    # three replicas x about (8 x 3 + 8 x 2 + 16 x 2) / 65536 at most
    assert 10 < flagged.sum() < 0.0033 * 1.5 * CELL_IDS
    assert not clean[flagged].any()
    assert all(len(set(row // 16)) == 3 for row in want[:256])


def test_the_cells_indep_sweeps_equal_crush_do_rule():
    """`chooseleaf indep` over the same map (a vector of slots through
    _straw2_choose_slots, class levels resolving): sweep_device ==
    sweep() == _native.do_rule with a host out and OSDs reweighted."""
    flat, _, _ = cell()
    op = cmap.OP_CHOOSELEAF_INDEP
    xs = np.arange(CELL_IDS, dtype=np.int32)
    got, grew, counts, dev_w = cell_sweep(op)
    assert counts["mode"] == "indep" and counts["draw_class"] >= 3
    steps = np.asarray(cell_steps(op), dtype=np.int32).ravel()
    want = np.array([_native.do_rule(flat, steps, int(x), 6, dev_w)
                     for x in xs])
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        mapper.sweep(flat, cell_steps(op), 6, xs, dev_w, chunk=4096), want)
    assert not np.isin(want, np.arange(16)).any()
    assert grew["crush.mid_lanes"] > 0


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
    assert slow.draws == {"draw_fast": 0, "draw_class": 0, "draw_table": 2,
                          "draw_limb": 0}


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
