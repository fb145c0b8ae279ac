"""A straw2 level reads its bucket rows from the level's static frontier
(mapper._Rows): as constants where the frontier is one bucket, by a
one-hot contraction with the frontier's own table where it is a few,
and by a gather with the bucket index where the plan does not know the
frontier, finds it too wide, or finds a legacy alg in it.  However a
level reads, the placements are the C oracle's.

Small maps, CPU.  Every map x stage program x rule is one case; the
cases of the firstn rule are collected by test_crush_frontier_reads.py
and ..._reads_b.py, those of the indep rule by ..._reads_indep.py and
..._reads_indep_b.py, half of the maps each (four files, so that four
workers share the compiles).
"""

import functools

import numpy as np

from ceph_tpu import _native
from ceph_tpu.crush import map as cmap
from ceph_tpu.crush import mapper

W = 0x10000
N_IDS = 2048


def _cluster(host_items, racks=0, host_alg=None, root_weights=None):
    """root [-> racks of `racks` hosts] -> hosts; host_items: a list of
    [(osd weight), ...] a host.  Returns (map, root id)."""
    m = cmap.CrushMap()
    hosts, host_w, osd = [], [], 0
    for h, ws in enumerate(host_items):
        alg = (host_alg or {}).get(h, cmap.ALG_STRAW2)
        hosts.append(m.add_bucket(
            alg, 1, list(range(osd, osd + len(ws))), list(ws)))
        host_w.append(sum(ws))
        osd += len(ws)
    if root_weights:
        for h, w in root_weights.items():
            host_w[h] = w
    lower, lower_w = hosts, host_w
    if racks:
        made, made_w = [], []
        for lo in range(0, len(hosts), racks):
            made.append(m.add_bucket(cmap.ALG_STRAW2, 2,
                                     lower[lo: lo + racks],
                                     lower_w[lo: lo + racks]))
            made_w.append(sum(lower_w[lo: lo + racks]))
        lower, lower_w = made, made_w
    return m, m.add_bucket(cmap.ALG_STRAW2, 3, lower, lower_w)


def _marks(n, out=(), part=()):
    w = np.full(n, W, dtype=np.uint32)
    w[list(out)] = 0
    w[list(part)] = 0xC000
    return w


def _flat():
    m, root = _cluster([[W] * 2] * 64)
    return m, root, _marks(128, out=[7], part=[12, 50])


def _ec_rack():
    """The benchmark's shape: root/rack/host, a host out, OSDs at 0.75."""
    m, root = _cluster([[W] * 2] * 64, racks=8)
    return m, root, _marks(128, out=range(2), part=[2 * h + 1
                                                    for h in range(1, 33)])


def _unlike_hosts():
    m, root = _cluster([[W] * (1 + h % 3) for h in range(64)])
    return m, root, _marks(127, out=[3], part=[10, 31])


def _zero_weight_item():
    hosts = [[W] * 2 for _ in range(64)]
    hosts[3][1] = 0
    hosts[9][0] = 0
    m, root = _cluster(hosts)
    return m, root, _marks(128, out=[20], part=[41])


def _empty_bucket():
    """Host 5 holds nothing and the root still draws it: REJECT."""
    hosts = [[W] * 2 for _ in range(64)]
    hosts[5] = []
    m, root = _cluster(hosts, root_weights={5: 2 * W})
    return m, root, _marks(126, out=[8], part=[30])


def _mixed_weights():
    """No level is eligible for fastcmp: every stage draws every item
    through the draw tables, and which table is the item's own."""
    m, root = _cluster([[W, 3 * W // 2], [W // 2, 2 * W]] * 32)
    return m, root, _marks(128, out=[2], part=[17, 60])


def _wide_frontier():
    """More hosts than a level reads from its frontier."""
    n = mapper._MAX_ONEHOT_FRONTIER + 8
    m, root = _cluster([[W] * 2] * n)
    return m, root, _marks(2 * n, out=[5], part=[9, 77])


def _legacy_alg():
    """A uniform bucket among the hosts (the legacy choose functions
    unroll to the map's widest bucket, so the map is small: four hosts,
    no room for twelve shards)."""
    m, root = _cluster([[W] * 4] * 4, host_alg={2: cmap.ALG_UNIFORM})
    return m, root, _marks(16, out=[13], part=[5])


MAPS = {"flat": _flat, "ec_rack": _ec_rack, "wide_frontier": _wide_frontier,
        "unlike_hosts": _unlike_hosts,
        "zero_weight_item": _zero_weight_item, "empty_bucket": _empty_bucket,
        "mixed_weights": _mixed_weights, "legacy_alg": _legacy_alg}
# the maps in two halves, a test file each (the workers share files)
HALVES = {"a": list(MAPS)[:4], "b": list(MAPS)[4:]}
# the reads of (outer plan, leaf plan) that each map's exact program
# makes, root downward
READS = {"flat": ["const", "onehot"],
         "ec_rack": ["const", "onehot", "onehot"],
         "unlike_hosts": ["const", "onehot"],
         "zero_weight_item": ["const", "onehot"],
         "empty_bucket": ["const", "onehot"],
         "mixed_weights": ["const", "onehot"],
         "wide_frontier": ["const", "gather"],
         "legacy_alg": ["const", "gather"]}
RULES = {"firstn3": ("firstn", 3), "indep12": ("indep", 12)}


@functools.lru_cache(maxsize=None)
def case(name, rule):
    m, root, w = MAPS[name]()
    mode, numrep = RULES[rule]
    m.add_simple_rule(rule, root, 1, mode=mode)
    flat, steps = m.flatten(), m.rules[0].steps
    xs = np.arange(N_IDS, dtype=np.int32) * 5 + 11
    sa = np.asarray(steps, dtype=np.int32).ravel()
    oracle = np.array([_native.do_rule(flat, sa, int(x), numrep, w)
                       for x in xs])
    return flat, steps, numrep, w, xs, oracle


STAGES = ["exact", "budgeted", "one_shot", "sweep_device"]


def check_places_as_the_oracle(name, rule, stage):
    """Every clean lane of each stage program equals _native.do_rule,
    every lane of the exact program and of sweep_device does."""
    flat, steps, numrep, w, xs, oracle = case(name, rule)
    plan = mapper.sweep_plan(flat, steps, numrep, w)
    if stage == "sweep_device":
        # the retry model does not cover a legacy alg: with the default
        # plan's capacities four hosts overflow, so every stage gets
        # room for every lane there
        room = dict(bad_div=1, bad2_div=1) if name == "legacy_alg" else {}
        got, overflow = mapper.sweep_device(flat, steps, numrep, xs, w,
                                            chunk=N_IDS // 2, **room)
        assert not bool(overflow)
        np.testing.assert_array_equal(np.asarray(got), oracle)
        return
    fast, mid, slow = mapper._stage_programs(
        flat, steps, numrep, None, plan, stage == "one_shot")
    prog = {"exact": slow, "budgeted": mid, "one_shot": fast}[stage]
    reads = [r for r, n in prog.levels.items() for _ in range(n)]
    assert sorted(reads) == sorted(READS[name])
    if stage == "exact":
        np.testing.assert_array_equal(np.asarray(prog(xs, w)), oracle)
        return
    got, clean = (np.asarray(v) for v in prog(xs, w))
    np.testing.assert_array_equal(got[clean], oracle[clean])
    # the budgeted program leaves little (three replicas over the four
    # hosts of the legacy map collide more often), one attempt a choose
    # leaves more
    few = 0.7 if name == "legacy_alg" else 0.9
    assert clean.mean() > (few if stage == "budgeted" else 0.1)
