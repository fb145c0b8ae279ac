"""The staged sweeps' own plan (mapper.sweep_plan) and the deployment it
was made for: an erasure-coded pool's `chooseleaf indep` rule over a
layered map (`crushtool --build` with several layers) with a host out.

Small sizes, CPU: 64 OSDs in 32 hosts in 4 racks.  Placements are held
to three witnesses: the host-staged sweep(), the C oracle
`_native.do_rule`, and the benchmark's numpy reference
(benchmarks/reference_crush_tree.py), which imports nothing of ceph_tpu.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

from ceph_tpu import _native
from ceph_tpu.crush import map as cmap
from ceph_tpu.crush import mapper

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference_crush_tree  # noqa: E402

LAYERS = [{"type_name": "host", "type_id": 1, "alg": "straw2", "size": 2,
           "bucket_ids": list(range(-1, -33, -1))},
          {"type_name": "rack", "type_id": 2, "alg": "straw2", "size": 8,
           "bucket_ids": list(range(-33, -37, -1))},
          {"type_name": "root", "type_id": 3, "alg": "straw2", "size": 0,
           "bucket_ids": [-37]}]


def _ec_cluster(numrep):
    """(flat, steps, device weights, the same deployment as the
    reference's configuration): host 0 out, three OSDs at 0.75."""
    m, ids = cmap.build_layered_cluster(
        64, [(la["type_id"], la["size"]) for la in LAYERS])
    assert ids == [la["bucket_ids"] for la in LAYERS]
    m.add_simple_rule("ec", ids[-1][0], 1, mode="indep")
    cfg = {"num_osds": 64, "osd_weight": 0x10000, "layers": LAYERS,
           "num_rep": numrep,
           "rule_steps": [["set_chooseleaf_tries", 5],
                          ["set_choose_tries", 100], ["take", "root"],
                          ["chooseleaf_indep", 0, "host"], ["emit"]],
           "device_weights": {"default": 0x10000, "marks": [
               {"weight": 0, "osds": [0, 1]},
               {"weight": 0xC000, "osds": [9, 22, 41]}]},
           "tunables": {"choose_total_tries": 50}}
    return (m.flatten(), m.rules[0].steps,
            reference_crush_tree.device_weights(cfg), cfg)


# -- the layered builder -----------------------------------------------------
@pytest.mark.parametrize("hosts", [0, 8])
def test_layered_builder_is_build_flat_clusters_general_case(hosts):
    """One and two layers: the ids, types and weights build_flat_cluster
    has always handed out (root at type 10)."""
    m, root = cmap.build_flat_cluster(64, hosts=hosts)
    layers = ([(1, 8)] if hosts else []) + [(10, 0)]
    m2, ids = cmap.build_layered_cluster(64, layers)
    assert root == ids[-1][0] == (-9 if hosts else -1)
    assert {b: (v.alg, v.type, v.items, v.weights)
            for b, v in m.buckets.items()} == \
        {b: (v.alg, v.type, v.items, v.weights)
         for b, v in m2.buckets.items()}
    if hosts:
        assert ids[0] == list(range(-1, -9, -1))
        assert m.buckets[-3].items == list(range(16, 24))
        assert m.buckets[root].weights == [8 * 0x10000] * 8
        assert (m.buckets[-3].type, m.buckets[root].type) == (1, 10)


def test_layered_builder_numbers_buckets_as_crushtool_build():
    """`--build --num_osds 70 host straw2 4 rack straw2 4 root straw2 0`:
    ids in order of creation from the lowest layer, the last bucket of a
    layer short, a bucket's weight the sum of its items'."""
    m, ids = cmap.build_layered_cluster(70, [(1, 4), (2, 4), (3, 0)])
    assert ids == [list(range(-1, -19, -1)), list(range(-19, -24, -1)),
                   [-24]]
    assert m.buckets[-18].items == [68, 69]
    assert m.buckets[-23].items == [-17, -18]
    assert m.buckets[-23].weights == [4 * 0x10000, 2 * 0x10000]
    assert m.buckets[-24].weights == [16 * 0x10000] * 4 + [6 * 0x10000]
    assert [m.buckets[i].type for i in (-1, -19, -24)] == [1, 2, 3]
    assert all(b.alg == cmap.ALG_STRAW2 for b in m.buckets.values())


def test_simple_indep_rule_sets_upstreams_tries():
    m, root = cmap.build_flat_cluster(16, hosts=4)
    m.add_simple_rule("ec", root, 1, mode="indep")
    m.add_simple_rule("rep", root, 1)
    assert m.rules[0].steps == [
        (cmap.OP_SET_CHOOSELEAF_TRIES, 5, 0),
        (cmap.OP_SET_CHOOSE_TRIES, 100, 0), (cmap.OP_TAKE, root, 0),
        (cmap.OP_CHOOSELEAF_INDEP, 0, 1), (cmap.OP_EMIT, 0, 0)]
    assert m.rules[1].steps == [
        (cmap.OP_TAKE, root, 0), (cmap.OP_CHOOSELEAF_FIRSTN, 0, 1),
        (cmap.OP_EMIT, 0, 0)]


# -- the plan ---------------------------------------------------------------------
@pytest.mark.parametrize("osds,hosts", [(1024, 64), (64, 8)])
def test_a_healthy_replicated_maps_plan_is_todays(osds, hosts):
    """firstn 3 over a flat map with every OSD in: one-shot pass, 1/8 of
    a chunk for the budgeted stage, 1/2048 of the sweep for the exact
    one, budget 3 (what sweep_device's defaults were)."""
    m, root = cmap.build_flat_cluster(osds, hosts=hosts)
    steps = [(cmap.OP_TAKE, root, 0), (cmap.OP_CHOOSELEAF_FIRSTN, 3, 1),
             (cmap.OP_EMIT, 0, 0)]
    w = np.full(osds, 0x10000, dtype=np.uint32)
    plan = mapper.sweep_plan(m.flatten(), steps, 3, w)
    if osds == 1024:
        assert plan == (8, 2048, 3, None) == mapper.DEFAULT_PLAN
        assert plan.fast
    else:       # 8 hosts collide eight times as often: more room
        assert plan.budget == 3 and plan.rounds is None
        assert plan.bad_div <= 2 and plan.bad2_div < 2048


@pytest.mark.parametrize("hosts", [64, 0])
def test_the_plan_counts_the_one_shot_passes_contested_draws(hosts):
    """A firstn one-shot pass flags every contested fastcmp draw for
    the budgeted stage, numrep * sum(width * delta) / 65536 of the
    lanes by the descent plans.  The benchmark's map (64 hosts of 16):
    0.7 % beside 4.7 % of collisions, twice which is still under 1/8,
    so the plan is the default.  1024 OSDs straight under the root:
    9.4 % beside 0.3 %, so stage 2 gets a quarter of the chunk, which
    collisions alone would not ask for.  A level of fewer weights than
    items (the class draw) flags as many; one of as many weights as
    items draws in full and flags nothing."""
    m, root = cmap.build_flat_cluster(1024, hosts=hosts)
    steps = [(cmap.OP_TAKE, root, 0),
             (cmap.OP_CHOOSELEAF_FIRSTN, 3, 1 if hosts else 0),
             (cmap.OP_EMIT, 0, 0)]
    flat = m.flatten()
    w = np.full(1024, 0x10000, dtype=np.uint32)
    indep, numrep, s2, q, _a = mapper._retry_model(flat, steps, 3, w)
    assert (indep, numrep, q) == (False, 3, 0.0)
    collisions = 3 * s2
    share = mapper._contested_share(flat, steps, 3)
    plan = mapper.sweep_plan(flat, steps, 3, w)
    if hosts:
        assert share == pytest.approx(3 * (64 * 2 + 16 * 2) / 65536)
        assert 0.05 < collisions + share < 1 / 16
        assert plan == mapper.DEFAULT_PLAN
    else:
        assert share == pytest.approx(3 * 1024 * 2 / 65536)
        assert 2 * collisions < 1 / 8 < 2 * (collisions + share) < 1 / 4
        assert plan == (4, 2048, 3, None)
    # two item weights in every bucket: every level draws a candidate a
    # weight class (_level_fast_delta) and flags a contested class, so
    # the same room is made (the classes' sizes sum to the width)
    wts = np.asarray(flat.weights).copy()
    wts[:, 1::2] *= 3   # every other item of every bucket
    mixed = dataclasses.replace(flat, weights=wts)
    assert mapper._contested_share(mixed, steps, 3) == share
    s2_mixed = mapper._retry_model(mixed, steps, 3, w)[2]
    assert mapper.sweep_plan(mixed, steps, 3, w).bad_div == mapper._pow2_div(
        2 * (3 * s2_mixed + share), mapper.DEFAULT_PLAN.bad_div)
    # as many item weights as items make every level ineligible
    # (_level_fast_delta): nothing is contested, no room is made for it
    wts = np.asarray(flat.weights).copy()
    wts *= 1 + np.arange(wts.shape[1], dtype=wts.dtype) % 50
    every = dataclasses.replace(flat, weights=wts)
    assert mapper._contested_share(every, steps, 3) == (
        0.0 if hosts else share)


def test_the_ec_pools_plan_on_the_benchmarks_map():
    """chooseleaf indep 12 over 64 hosts in 8 racks, host 0 out, 32
    OSDs at 0.75: three lanes in four are unclean after one attempt, so
    no one-shot pass; the budgeted rounds narrow; the exact stage gets
    a few per cent of the lanes at most."""
    m, ids = cmap.build_layered_cluster(1024, [(1, 16), (2, 8), (3, 0)])
    assert ids[1] == list(range(-65, -73, -1)) and ids[2] == [-73]
    m.add_simple_rule("ec", -73, 1, mode="indep")
    w = np.full(1024, 0x10000, dtype=np.uint32)
    w[:16] = 0
    w[[16 * h + 5 for h in range(1, 33)]] = 0xC000
    flat, steps = m.flatten(), m.rules[0].steps
    indep, numrep, s2, q, a = mapper._retry_model(flat, steps, 12, w)
    assert (indep, numrep) == (True, 12)
    assert s2 == pytest.approx(1 / 64) and q == pytest.approx(1 / 64, rel=1e-3)
    assert a == pytest.approx(1 / 64 + 32 / 64 * 0.25 / 16)
    plan = mapper.sweep_plan(flat, steps, 12, w)
    assert not plan.fast and plan.bad_div == 1
    assert 16 <= plan.bad2_div <= 256
    assert mapper.MID_BUDGET <= plan.budget == len(plan.rounds) \
        <= mapper.MAX_BUDGET
    widths = [s for s, _ in plan.rounds]
    assert widths[0] == 12 and widths == sorted(widths, reverse=True)
    assert widths[-1] <= 2 and sum(widths) <= 30
    # every OSD in: the same rule needs no leaf retries at all
    healthy = mapper.sweep_plan(flat, steps, 12,
                                np.full(1024, 0x10000, dtype=np.uint32))
    assert not healthy.fast and all(r == 0 for _, r in healthy.rounds)
    # a rule the model does not cover gets the default
    two = [(cmap.OP_TAKE, -73, 0), (cmap.OP_CHOOSE_INDEP, 4, 2),
           (cmap.OP_CHOOSELEAF_INDEP, 3, 1), (cmap.OP_EMIT, 0, 0)]
    assert mapper.sweep_plan(flat, two, 12, w) == mapper.DEFAULT_PLAN


# -- the sweeps under their own plan -----------------------------------------------
@pytest.mark.parametrize("numrep", [12, 6])
def test_sweep_device_places_the_ec_rule_exactly_by_itself(numrep):
    """Default arguments: no overflow, rows equal to sweep()'s, the
    reference's and the C oracle's, holes kept in place."""
    flat, steps, w, cfg = _ec_cluster(numrep)
    xs = np.arange(5000, 5000 + 2048, dtype=np.int32)
    sa = np.asarray(steps, dtype=np.int32).ravel()
    oracle = np.array([_native.do_rule(flat, sa, int(x), numrep, w)
                       for x in xs])
    ref = reference_crush_tree.CrushTreeRef(cfg).do_rule(xs)
    np.testing.assert_array_equal(ref, oracle)
    before = mapper.sweep_totals()
    got, overflow = mapper.sweep_device(flat, steps, numrep, xs, w,
                                        chunk=1024)
    assert not bool(overflow)
    np.testing.assert_array_equal(np.asarray(got), oracle)
    mid = mapper.sweep_totals()
    again, overflow = mapper.sweep_device(flat, steps, numrep, xs, w,
                                          chunk=1024)
    # reading the totals between two sweeps changes no placement
    np.testing.assert_array_equal(np.asarray(again), oracle)
    after = mapper.sweep_totals()
    d1 = {k: mid[k] - before[k] for k in mid}
    assert d1 == {k: after[k] - mid[k] for k in mid}
    assert d1["crush.ids"] == 2048
    assert 0 < d1["crush.slow_lanes"] <= d1["crush.mid_lanes"] <= 2048
    plan = mapper.sweep_plan(flat, steps, numrep, w)
    assert d1["crush.slow_lanes"] <= 2048 // plan.bad2_div
    np.testing.assert_array_equal(
        mapper.sweep(flat, steps, numrep, xs, w, chunk=1024), oracle)
    host = mapper.sweep_totals()
    assert host["crush.ids"] - after["crush.ids"] == 2048
    assert (host["crush.slow_lanes"] - after["crush.slow_lanes"]
            == d1["crush.slow_lanes"])


def test_a_starved_plan_still_says_so():
    """The capacities stay overrides: with both starved the EC rule's
    sweep raises its flag and most rows keep one attempt's holes."""
    flat, steps, w, cfg = _ec_cluster(6)
    xs = np.arange(1024, dtype=np.int32)
    got, overflow = mapper.sweep_device(flat, steps, 6, xs, w, chunk=1024,
                                        bad_div=1 << 30, bad2_div=1 << 30)
    assert bool(overflow)
    want = reference_crush_tree.CrushTreeRef(cfg).do_rule(xs)
    assert (np.asarray(got) != want).any(axis=1).mean() > 0.2


def test_the_sweep_span_says_what_ran():
    from ceph_tpu.core import tracing
    from ceph_tpu.core.tracing import COUNTS, NAME

    flat, steps, w, _cfg = _ec_cluster(6)
    plan = mapper.sweep_plan(flat, steps, 6, w)
    n0 = len(tracing.recorder().held()[0])
    mapper.sweep_device(flat, steps, 6, np.arange(2048, dtype=np.int32), w,
                        chunk=1024)
    span, = [r for r in tracing.recorder().held()[0][n0:]
             if r[NAME] == "crush.sweep"]
    # no one-shot pass: the budgeted and the exact program, each with
    # the root (one bucket: constants) and the racks and the hosts
    # (read by their place in the level's frontier); nothing gathers.
    # Uniform weights: the budgeted program's three levels draw by
    # fastcmp, the exact program's three through the draw tables.  The
    # exact stage's floor is 512 lanes for a sweep of 2048 ids
    assert span[COUNTS] == {
        "ids": 2048, "chunk": 1024, "numrep": 6, "mode": "indep",
        "cap": 1024, "cap2": 512, "budget": plan.budget,
        "const": 2, "onehot": 4, "gather": 0,
        "draw_fast": 3, "draw_class": 0, "draw_table": 3, "draw_limb": 0}
    assert {"crush.sweep", "crush.ids", "crush.mid_lanes",
            "crush.slow_lanes", "crush.full_draws"} <= set(tracing.SPANS)
