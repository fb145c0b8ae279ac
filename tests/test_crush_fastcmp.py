"""The fastcmp straw2 draw: hash+argmax with exact top-2 resolution.

The staged sweep's budgeted traces replace the per-item draw-table
gathers with a max-hash pick (ln.fastcmp_bounds proves any runner-up
more than delta below the max loses outright).  Inside the window the
budgeted stage makes an exact two-lookup compare; the firstn one-shot
pass only flags the lane for that stage and reads no table.  These
tests pin:

- the bounds derivation (suffix-max over the real ln table);
- draw-for-draw equivalence of the fastcmp choose vs the table choose
  whenever the ambiguity flag is False: the resolving form (the flag
  only fires for >= 3 distinct hashes inside the window) and the
  flag-only form (the flag fires for exactly the contested draws);
- that the one-shot firstn program holds no draw table at all;
- the class draw (PR 35): a level whose buckets hold unlike weights
  draws the max-hash item of each weight class, in both forms of the
  choose, against ln.straw2_draw on real and on crafted hashes (ties in
  and across classes, an empty class, contested classes), and the
  window by which a level is eligible;
- end-to-end: staged sweep() and sweep_device() == the exact full
  program on maps that exercise the fast path, including a weights
  profile that DISABLES it.

Reference: bucket_straw2_choose, src/crush/mapper.c:361-384.
"""

import numpy as np
import pytest

from ceph_tpu.crush import ln
from ceph_tpu.crush import map as cmap
from ceph_tpu.crush import mapper


def test_fastcmp_bounds_derivation():
    n = (-ln.ln16_table()).astype(np.int64)
    sm = np.maximum.accumulate(n[::-1])[::-1]
    bounds = ln.fastcmp_bounds()
    assert set(bounds) == {2, 3, 4}
    for d, b in bounds.items():
        assert b == int((n[:-d] - sm[d:]).min())
        assert b > 0
    # delta=2 must cover ordinary 16.16 weights (1.0 = 0x10000) with
    # huge headroom; delta=1 must NOT be safe (the ln table inverts)
    assert bounds[2] > 1 << 24
    assert (n[:-1] - sm[1:]).min() < 0
    assert bounds[2] < bounds[3] < bounds[4]


def _uniform_cluster(n_osds=64, hosts=8):
    m, root = cmap.build_flat_cluster(n_osds, hosts=hosts)
    steps = [(cmap.OP_TAKE, root, 0),
             (cmap.OP_CHOOSELEAF_FIRSTN, 3, 1),
             (cmap.OP_EMIT, 0, 0)]
    return m.flatten(), steps


def _with_weights(flat, bno, weights):
    """`flat` with the item weights of bucket `bno` replaced."""
    import dataclasses

    w = np.asarray(flat.weights).copy()
    w[bno, : len(weights)] = weights
    return dataclasses.replace(flat, weights=w)


def _a_host(dm):
    return next(b for b in range(dm.n_buckets)
                if dm._np_sizes[b] > 0 and dm._np_items[b, 0] >= 0)


def test_level_delta_eligibility():
    flat, steps = _uniform_cluster()
    dm = mapper._DeviceMap(flat)
    # uniform weights -> eligible at delta 2, one class a bucket
    frontier = [b for b in range(dm.n_buckets)]
    assert mapper._level_fast_delta(dm, frontier) == (2, ())
    host0 = _a_host(dm)
    width = int(dm._np_sizes[host0])
    # an item of another weight in a bucket: two classes, each drawn one
    # candidate; the classes are the weights' rows of the draw tables
    w = np.asarray(flat.weights)[host0, :width].copy()
    w[0] *= 2
    dm2 = mapper._DeviceMap(_with_weights(flat, host0, w))
    assert list(dm2._distinct[:2]) == [0x10000, 0x20000]
    assert mapper._level_fast_delta(dm2, [host0]) == (2, (0, 1))
    # buckets each uniform inside but unlike one another stay one class
    # a bucket: no table is read there
    hosts = [b for b in frontier if dm._np_items[b, 0] >= 0]
    dm2b = mapper._DeviceMap(_with_weights(
        flat, hosts[1], np.full(width, 0x30000)))
    assert mapper._level_fast_delta(dm2b, hosts) == (2, ())
    # as many classes as items: nothing is saved, the level draws in full
    dm2c = mapper._DeviceMap(_with_weights(
        flat, host0, 0x10000 * (1 + np.arange(width))))
    assert mapper._level_fast_delta(dm2c, [host0]) == (0, ())
    assert mapper._level_fast_delta(dm2c, hosts) == (0, ())
    # one fewer: a class level
    dm2d = mapper._DeviceMap(_with_weights(
        flat, host0, 0x10000 * np.maximum(1, np.arange(width))))
    assert mapper._level_fast_delta(dm2d, [host0]) == (
        2, tuple(range(width - 1)))
    # gigantic uniform weight above every bound -> ineligible
    dm3 = mapper._DeviceMap(_with_weights(
        flat, host0, np.full(width, 1 << 31)))
    assert mapper._level_fast_delta(dm3, [host0]) == (0, ())


@pytest.mark.parametrize("d", [2, 3, 4])
def test_the_window_follows_the_greatest_weight(d):
    """The greatest weight just under bound[d] gives delta d, just over
    it the next window, over bound[4] none: with one weight and with a
    second class beside it."""
    flat, _ = _uniform_cluster()
    host0 = _a_host(mapper._HostMap(flat))
    bound = ln.fastcmp_bounds()[d]
    after = d + 1 if d < 4 else 0
    for small, classes in ((None, 1), (0x10000, 2)):
        for wmax, want in ((bound, d), (bound + 1, after)):
            w = np.full(8, wmax, dtype=np.uint32)
            if small:
                w[1::2] = small
            hm = mapper._HostMap(_with_weights(flat, host0, w))
            delta, cls = mapper._level_fast_delta(hm, [host0])
            assert delta == want
            assert len(cls) == (classes if classes > 1 and want else 0)
            # the plan carries it, in a budgeted trace only
            lvl, = mapper._descent_plan(hm, [host0], 0, fastcmp=True)
            assert (lvl.delta, lvl.classes) == (delta, cls)
            lvl, = mapper._descent_plan(hm, [host0], 0)
            assert (lvl.delta, lvl.classes) == (0, ())


def test_a_limb_mode_map_is_untouched():
    """Over _MAX_DRAW_TABS distinct weights the map has no draw tables:
    a bucket of unlike weights draws every item by the limb division,
    as it did; buckets uniform inside keep their window."""
    m, root = cmap.build_flat_cluster(1024, hosts=64)
    flat = m.flatten()
    hm = mapper._HostMap(flat)
    hosts = [b for b in range(hm.n_buckets) if hm._np_items[b, 0] >= 0]
    w = np.asarray(flat.weights).copy()
    for k, b in enumerate(hosts):
        w[b, :16] = 0x10000 + 64 * k + np.arange(16) % 2
    import dataclasses
    hm = mapper._HostMap(dataclasses.replace(flat, weights=w))
    assert not hm.table_mode and len(hm._distinct) > mapper._MAX_DRAW_TABS
    assert mapper._level_fast_delta(hm, hosts) == (0, ())
    assert mapper._level_fast_delta(hm, [-1 - root]) == (2, ())
    steps = [(cmap.OP_TAKE, root, 0), (cmap.OP_CHOOSELEAF_FIRSTN, 3, 1),
             (cmap.OP_EMIT, 0, 0)]
    ch, = mapper._choose_plans(hm, steps, 3, 1, cmap.Tunables())
    assert [(lv.delta, lv.classes) for lv in ch.plan + ch.leaf_plan] == [
        (2, ()), (0, ())]
    assert mapper._level_counts(hm, [ch])[1] == {
        "draw_fast": 1, "draw_class": 0, "draw_table": 0, "draw_limb": 1}
    assert ch.full_draws() == 3 * 16


def test_fastcmp_choose_matches_table_choose():
    """Per-draw: fastcmp winner == table winner whenever ambig=False,
    across enough (x, r) pairs to hit the contested window repeatedly
    (a 16-osd bucket hits u1-u2 <= 2 every ~1600 draws)."""
    import jax
    import jax.numpy as jnp

    flat, steps = _uniform_cluster(n_osds=64, hosts=4)  # 16-wide buckets
    dm = mapper._DeviceMap(flat)
    host0 = next(b for b in range(dm.n_buckets)
                 if dm._np_sizes[b] > 0 and dm._np_items[b, 0] >= 0)
    width = int(dm._np_sizes[host0])

    @jax.jit
    def both(xs):
        def one(x):
            fast_it, amb, _ = mapper._straw2_choose(
                dm, x, jnp.int32(0), mapper._Rows(
                    dm, jnp.int32(host0), mapper._Level(width, 2, True)))
            tab_it, _, _ = mapper._straw2_choose(
                dm, x, jnp.int32(0), mapper._Rows(
                    dm, jnp.int32(host0), mapper._Level(width, 0, True)))
            return fast_it, tab_it, amb
        return jax.vmap(one)(xs)

    n_draws = 200_000
    xs = jnp.arange(n_draws, dtype=jnp.int32)
    fast_it, tab_it, amb = (np.asarray(v) for v in both(xs))
    # the exact top-2 resolution makes contested draws exact too, so
    # disagreement is impossible outside the (rare) ambig flag
    assert (fast_it[~amb] == tab_it[~amb]).all()
    # the flag = THREE distinct hashes inside the window; P ~ 1e-5
    assert amb.sum() < n_draws // 1000
    # prove the contested two-candidate window was genuinely exercised
    # (otherwise the equality above proves nothing about the exact
    # top-2 resolution): recompute the draws host-side
    from ceph_tpu.crush import hashes as h

    items = dm._np_items[host0, :width].astype(np.uint32)
    contested = 0
    for x in range(0, n_draws, 5):  # ~40k samples, P(contested)~5e-4
        u = np.sort(h.hash32_3(np.uint32(x), items, np.uint32(0),
                               xp=np) & 0xFFFF)
        if 0 < u[-1] - u[-2] <= 2:
            contested += 1
    assert contested > 5


def _contested(u, delta):
    """Host-side, over the last axis of 16-bit hashes: (index of the
    max hash, whether the nearest DISTINCT runner-up is within delta)."""
    u = u.astype(np.int64)
    u1 = u.max(axis=-1)
    u2 = np.where(u == u1[..., None], -1, u).max(axis=-1)
    return u.argmax(axis=-1), (u2 >= 0) & (u1 - u2 <= delta)


@pytest.mark.parametrize("width", [16, 64])
def test_flag_only_choose_matches_table_choose(width):
    """The one-shot firstn form (resolve=False): the max-hash item is
    the table winner wherever the draw is not flagged, and the flag is
    exactly "nearest distinct runner-up within delta", about
    width * delta / 65536 of the draws."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.crush import hashes as h

    if width == 16:
        flat, _ = _uniform_cluster(n_osds=64, hosts=4)
    else:
        flat = cmap.build_flat_cluster(64)[0].flatten()  # OSDs under root
    dm = mapper._DeviceMap(flat)
    bno = next(b for b in range(dm.n_buckets)
               if dm._np_sizes[b] == width and dm._np_items[b, 0] >= 0)
    delta, classes = mapper._level_fast_delta(dm, [bno])
    assert delta == 2 and classes == () and dm.table_mode

    @jax.jit
    def both(xs, rs):
        def one(x, r):
            flag_it, amb, _ = mapper._straw2_choose(
                dm, x, r, mapper._Rows(
                    dm, jnp.int32(bno), mapper._Level(width, delta, False)))
            tab_it, _, _ = mapper._straw2_choose(
                dm, x, r, mapper._Rows(
                    dm, jnp.int32(bno), mapper._Level(width, 0, True)))
            return flag_it, tab_it, amb
        return jax.vmap(one)(xs, rs)

    n_draws = 200_000
    xs = np.arange(n_draws, dtype=np.int32) * 7 + 3
    rs = (np.arange(n_draws, dtype=np.int32) // 11) % 5
    flag_it, tab_it, amb = (np.asarray(v) for v in both(
        jnp.asarray(xs), jnp.asarray(rs)))
    assert (flag_it[~amb] == tab_it[~amb]).all()
    share = width * delta / 65536
    assert share / 1.5 < amb.mean() < share * 1.5
    # the flag is the contested window and nothing else: every fourth
    # draw again on the host
    items = dm._np_items[bno, :width].astype(np.uint32)
    some = slice(0, n_draws, 4)
    u = h.hash32_3(xs[some].astype(np.uint32)[:, None], items[None, :],
                   rs[some].astype(np.uint32)[:, None], xp=np) & 0xFFFF
    i1, contested = _contested(u, delta)
    np.testing.assert_array_equal(amb[some], contested)
    assert contested.sum() > 5
    assert (flag_it[some] == items[i1].astype(np.int32)).all()
    # the flag is needed: among the contested draws the runner-up wins
    # some (the ln table is not monotonic inside the window)
    assert (flag_it[amb] != tab_it[amb]).any()


# -- the class draw: a level whose buckets hold unlike weights (PR 35) ----------
TB4, TB8, TB16 = 0x3A352, 0x746A5, 0xE8D4A
# bucket A: three classes, a zero-weight item, ten items of the level's
# twelve (two padded); B: no TB16 (an empty class), twelve items; C:
# nothing that can win
CLASS_W = {"A": [TB4, TB8, TB4, TB8, 0, TB16, TB4, TB8, TB16, TB4],
           "B": [TB8, TB4] * 6,
           "C": [0, 0, 0]}


def _class_level(resolve):
    """(dm, bucket numbers of A, B, C, their one level as the plan of a
    budgeted trace has it)."""
    m = cmap.CrushMap()
    bnos, dev = [], 0
    for ws in CLASS_W.values():
        bid = m.add_bucket(cmap.ALG_STRAW2, 1,
                           list(range(dev, dev + len(ws))), ws)
        bnos.append(-1 - bid)
        dev += len(ws)
    m.add_bucket(cmap.ALG_STRAW2, 2, [-1 - b for b in bnos],
                 [sum(ws) or 1 for ws in CLASS_W.values()])
    dm = mapper._DeviceMap(m.flatten())
    lvl, = mapper._descent_plan(dm, bnos, 0, fastcmp=True, resolve=resolve)
    assert (lvl.width, lvl.delta, lvl.read) == (12, 2, "onehot")
    assert [int(dm._distinct[c]) for c in lvl.classes] == [TB4, TB8, TB16]
    return dm, bnos, lvl


def _class_oracle(u, weights, delta):
    """bucket_straw2_choose on the host for hashes u [..., size]: (the
    winner's place, whether a class is contested, whether a class has a
    third distinct hash inside the window)."""
    weights = np.asarray(weights, dtype=np.uint32)
    draws = ln.straw2_draw(u.astype(np.uint32), weights)
    contested = np.zeros(u.shape[:-1], bool)
    third = np.zeros(u.shape[:-1], bool)
    for w in set(weights.tolist()) - {0}:
        uc = np.where(weights == w, u.astype(np.int64), -1)
        contested |= _contested(uc, delta)[1]
        # the same with the runner-up's hash taken out: a third one
        u1 = uc.max(axis=-1)
        rest = np.where(uc == u1[..., None], -1, uc)
        u3 = np.where(rest == rest.max(axis=-1)[..., None], -1,
                      rest).max(axis=-1)
        third |= (u3 >= 0) & (u1 - u3 <= delta)
    return draws.argmax(axis=-1), contested, third


def _both_forms(dm, bnos, lvl):
    """jitted: xs [N] -> (place, ambig) [N, 3] of the buckets A, B, C
    by _straw2_choose (r = 0, 1, 2) and by _straw2_choose_slots (the
    three as one vector of slots)."""
    import jax
    import jax.numpy as jnp

    b, r = jnp.asarray(bnos, jnp.int32), jnp.arange(3, dtype=jnp.int32)

    @jax.jit
    def run(xs):
        def one(x):
            scalar = [mapper._straw2_choose(
                dm, x, r[k], mapper._Rows(dm, b[k], lvl)) for k in range(3)]
            slots = mapper._straw2_choose_slots(
                dm, x, r, mapper._Rows(dm, b, lvl))
            return tuple(jnp.stack([s[k] for s in scalar])
                         for k in range(3)), slots
        return jax.vmap(one)(xs)
    return run


@pytest.mark.parametrize("resolve", [False, True])
def test_class_choose_is_bucket_straw2_choose(resolve):
    """Both forms of the choose on real hashes, 100,000 draws a bucket:
    wherever the draw is not flagged the winner is the C's, the flag is
    exactly "a class is contested" (resolve=False) or "a class has a
    third hash in the window" (resolve=True), and both forms agree."""
    from ceph_tpu.crush import hashes as h

    dm, bnos, lvl = _class_level(resolve)
    n = 100_000
    xs = np.arange(n, dtype=np.int32) * 5 + 11
    (item, amb, idx), slots = _both_forms(dm, bnos, lvl)(xs)
    for a, b in zip((item, amb, idx), slots):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    item, amb, idx = (np.asarray(v) for v in (item, amb, idx))
    runner_up_won = 0
    for k, (bno, ws) in enumerate(zip(bnos, CLASS_W.values())):
        items = dm._np_items[bno, :len(ws)]
        u = h.hash32_3(xs.astype(np.uint32)[:, None],
                       items.astype(np.uint32)[None, :], np.uint32(k),
                       xp=np) & 0xFFFF
        want, contested, third = _class_oracle(u, ws, lvl.delta)
        np.testing.assert_array_equal(
            amb[:, k], third if resolve else contested)
        ok = ~amb[:, k]
        np.testing.assert_array_equal(idx[ok, k], want[ok])
        np.testing.assert_array_equal(item[ok, k], items[want[ok]])
        if any(ws):
            # a class's size * delta / 65536 of the draws each, so the
            # level's width * delta / 65536 bounds what to expect
            share = len(ws) * lvl.delta / 65536
            assert 5 < contested.sum() < 1.5 * share * n
            # the comparison is needed: a class's runner-up wins some
            uc = np.where(np.asarray(ws) > 0, u, -1)
            runner_up_won += int((contested & (
                u[np.arange(n), want] != uc.max(axis=-1))).sum())
        else:
            assert not contested.any() and (idx[:, k] == 0).all()
    assert runner_up_won > 2, runner_up_won


def _equal_quotients():
    """(u of a TB4 item, u of a TB8 item) with equal straw2 quotients,
    both hashes under 65,000."""
    n = (-ln.ln16_table()).astype(np.int64)[:65000]
    q4, q8 = n // TB4, n // TB8
    q, i4, i8 = np.intersect1d(q4, q8, return_indices=True)
    assert q.size
    return int(i4[0]), int(i8[0])


def _inverted_pair():
    """(u whose TB4 draw loses to that of u - 1, u whose draw equals
    that of u - 1): the ln table is not monotone at its top, and flat
    in ten thousand places."""
    q = (-ln.ln16_table()).astype(np.int64) // TB4
    return (int(np.nonzero(q[:-1] < q[1:])[0][-1]) + 1,
            int(np.nonzero(q[:-1] == q[1:])[0][0]) + 1)


def _crafted_cases():
    """name -> (hashes of bucket A's twelve places, the winner's place,
    contested, a third hash in the window)."""
    u4, u8 = _equal_quotients()
    inv, flat = _inverted_pair()
    low = np.arange(100, 112)

    def row(**at):
        u = low.copy()
        for k, v in at.items():
            u[int(k[1:])] = v
        return u
    return {
        "plain": (row(p1=60000), 1, False, False),
        "tie inside a class": (row(p2=60000, p6=60000, p9=60000), 2,
                               False, False),
        "one hash in every class: the heaviest wins": (
            np.full(12, 65535), 5, False, False),
        "zero weight and padded places hash highest": (
            row(p4=65535, p10=65535, p11=65535, p3=64000), 3, False, False),
        "equal quotients in two classes, TB8 first": (
            row(p1=u8, p2=u4), 1, False, False),
        "equal quotients in two classes, TB4 first": (
            row(p0=u4, p1=u8), 0, False, False),
        "contested, the runner-up wins": (
            row(p0=inv, p6=inv - 1), 6, True, False),
        "contested, equal draws, the lower hash first in the row": (
            row(p2=flat - 1, p9=flat), 2, True, False),
        "contested, the max hash wins": (
            row(p2=50000, p9=49998), None, True, False),
        "contested in a class that loses": (
            row(p5=65000, p0=30000, p2=29999), 5, True, False),
        "a third hash in the window": (
            row(p0=inv, p6=inv - 1, p9=inv - 2), None, True, True),
        "a hash tie and a runner-up in the window": (
            row(p1=40000, p3=40000, p7=39999), None, True, False),
    }


@pytest.mark.parametrize("resolve", [False, True])
def test_class_choose_on_crafted_hashes(resolve, monkeypatch):
    """The cases real hashes meet once in millions of draws, made by
    hand: the hash is replaced by a table of (case, bucket, place)."""
    import jax.numpy as jnp

    dm, bnos, lvl = _class_level(resolve)
    cases = _crafted_cases()
    table = np.zeros((len(cases), 3, 12), dtype=np.uint32)
    for c, (u, *_) in enumerate(cases.values()):
        table[c, 0] = u
        table[c, 1] = u[::-1]      # bucket B: no TB16 to draw
        table[c, 2] = u
    tab = jnp.asarray(table)
    monkeypatch.setattr(
        mapper.hashes, "hash32_3",
        lambda x, items, r, xp=None: tab[
            x.astype(jnp.int32),
            jnp.broadcast_to(r.astype(jnp.int32), items.shape),
            jnp.arange(items.shape[-1]) % 12])
    (item, amb, idx), slots = _both_forms(dm, bnos, lvl)(
        np.arange(len(cases), dtype=np.int32))
    for a, b in zip((item, amb, idx), slots):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    item, amb, idx = (np.asarray(v) for v in (item, amb, idx))
    for c, (name, (u, place, contested, third)) in enumerate(cases.items()):
        for k, (bno, ws) in enumerate(zip(bnos, CLASS_W.values())):
            want, cont, thrd = _class_oracle(
                table[c, k, :len(ws)].astype(np.int64), ws, lvl.delta)
            if k == 0:   # the case is what its name says
                assert (cont, thrd) == (contested, third), name
                assert place in (None, want), name
            assert amb[c, k] == (thrd if resolve else cont), (name, k)
            if not amb[c, k]:
                assert idx[c, k] == want, (name, k)
                assert item[c, k] == dm._np_items[bno, want], (name, k)
    # the crafted runner-up does win, so resolving is what settles it
    u = cases["contested, the runner-up wins"][0]
    assert u[6] < u[0]


def _program_consts(fn, *args):
    """Every array a jitted program closes over, nested jaxprs
    included."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    out = list(closed.consts)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    if hasattr(sub, "consts"):
                        out.extend(sub.consts)
                        walk(sub.jaxpr)
                    elif hasattr(sub, "eqns"):
                        walk(sub)

    walk(closed.jaxpr)
    return out


def test_one_shot_firstn_program_holds_no_draw_table():
    """The flat benchmark map's one-shot program reads neither draw_hi,
    draw_lo nor w_idx (they are not among the arrays it closes over);
    the budgeted and the exact programs hold the two draw tables, and
    not w_idx either: the map has one distinct weight, so which table
    an item draws from is a constant of each level."""
    m, root = cmap.build_flat_cluster(1024, hosts=64)
    steps = [(cmap.OP_TAKE, root, 0), (cmap.OP_CHOOSELEAF_FIRSTN, 3, 1),
             (cmap.OP_EMIT, 0, 0)]
    flat = m.flatten()
    dm = mapper._DeviceMap(flat)
    tables = {name: np.asarray(getattr(dm, name))
              for name in ("draw_hi", "draw_lo", "w_idx")}
    xs = np.arange(64, dtype=np.int32)
    w = np.full(1024, 0x10000, dtype=np.uint32)

    def held(**kw):
        consts = _program_consts(
            mapper.compile_rule(flat, steps, 3, **kw), xs, w)
        return {name for name, tab in tables.items() if any(
            np.shape(c) == tab.shape and np.asarray(c).dtype == tab.dtype
            and np.array_equal(np.asarray(c), tab) for c in consts)}

    assert held(one_shot=True) == set()
    assert held(one_shot=True, budget=mapper.MID_BUDGET) == {
        "draw_hi", "draw_lo"}
    assert held() == {"draw_hi", "draw_lo"}
    # what the plans say: the one-shot pass flags, the budgeted stage
    # settles, the exact program has no fastcmp level
    for kw, want in ((dict(fastcmp=True, resolve=False), (2, False)),
                     (dict(fastcmp=True), (2, True)), ({}, (0, True))):
        plan = mapper._descent_plan(dm, [-1 - root], 1, **kw)
        assert [lv[1:3] for lv in plan] == [want]


def test_staged_sweeps_exact_with_contested_draws():
    """sweep() and sweep_device() against the full program with a
    device out and one at half weight, over enough ids that the
    one-shot pass flags contested draws at the root and in a host: row
    for row equal, nothing overflowed.  32 hosts of 8: on the 8-host
    map of the slow tests below the plan drops the one-shot pass (three
    replicas collide in three lanes of eight), and with it the code
    under test."""
    from ceph_tpu.crush import hashes as h

    flat, steps = _uniform_cluster(n_osds=256, hosts=32)
    dev_w = np.full(256, 0x10000, dtype=np.uint32)
    dev_w[7] = 0          # out device
    dev_w[12] = 0x8000    # half-weight: is_out rejections
    assert mapper.sweep_plan(flat, steps, 3, dev_w).fast
    n, chunk = 16384, 8192
    xs = np.arange(n, dtype=np.int32) + 1000
    # contested draws of the one-shot pass, counted on the host: the
    # root's draw of replica r, then the leaf draw (r again: vary_r 1,
    # stable) in the host whose hash is greatest there
    dm = mapper._DeviceMap(flat)
    root = next(b for b in range(dm.n_buckets) if dm._np_items[b, 0] < 0)
    delta, _ = mapper._level_fast_delta(dm, range(dm.n_buckets))
    assert delta == 2
    xu = xs.astype(np.uint32)[:, None, None]
    ru = np.arange(3, dtype=np.uint32)[None, :, None]
    hosts = dm._np_items[root, :32]
    i1, close = _contested(h.hash32_3(
        xu, hosts.astype(np.uint32)[None, None, :], ru, xp=np) & 0xFFFF,
        delta)
    at_root = close.any(axis=1)
    osds = dm._np_items[-1 - hosts[i1]][..., :8]
    _, close = _contested(h.hash32_3(
        xu, osds.astype(np.uint32), ru, xp=np) & 0xFFFF, delta)
    in_host = close.any(axis=1) & ~at_root
    assert at_root.sum() > 3 and in_host.sum() > 3

    full = mapper.compile_rule(flat, steps, 3)
    fast = mapper.compile_rule(flat, steps, 3, one_shot=True)
    want = np.concatenate([np.asarray(full(xs[o:o + chunk], dev_w))
                           for o in range(0, n, chunk)])
    clean = np.concatenate([np.asarray(fast(xs[o:o + chunk], dev_w)[1])
                            for o in range(0, n, chunk)])
    assert not clean[at_root | in_host].any()
    before = mapper.sweep_totals()
    got = mapper.sweep(flat, steps, 3, xs, dev_w, chunk=chunk)
    np.testing.assert_array_equal(got, want)
    mid = mapper.sweep_totals()
    dev, overflow = mapper.sweep_device(flat, steps, 3, xs, dev_w,
                                        chunk=chunk)
    assert not bool(overflow)
    np.testing.assert_array_equal(np.asarray(dev), want)
    after = mapper.sweep_totals()
    for a, b in ((before, mid), (mid, after)):
        assert b["crush.ids"] - a["crush.ids"] == n
        assert (b["crush.mid_lanes"] - a["crush.mid_lanes"]
                == int((~clean).sum()))


@pytest.mark.slow  # tier-2: ~1 min compile-heavy sweep (see README test tiers)
def test_staged_sweep_exact_vs_full_program():
    flat, steps = _uniform_cluster()
    dev_w = np.full(64, 0x10000, dtype=np.uint32)
    dev_w[7] = 0          # out device
    dev_w[12] = 0x8000    # half-weight: is_out rejections
    xs = np.arange(50_000, dtype=np.int32)
    full = mapper.compile_rule(flat, steps, 3)
    want = np.asarray(full(xs, dev_w))
    got = mapper.sweep(flat, steps, 3, xs, dev_w, chunk=16384)
    np.testing.assert_array_equal(got, want)


@pytest.mark.slow  # tier-2: ~1 min compile-heavy sweep (see README test tiers)
def test_staged_sweep_exact_when_fastcmp_disabled():
    """Mixed weights, as many as a bucket has items, knock out
    eligibility; the staged sweep must stay exact through its
    table-path stages."""
    import dataclasses

    flat, steps = _uniform_cluster()
    w = np.asarray(flat.weights).copy()
    rng = np.random.default_rng(7)
    for b in range(w.shape[0]):
        sz = int(np.asarray(flat.sizes)[b])
        if sz:
            w[b, :sz] = (w[b, :sz].astype(np.uint64)
                         * (rng.permutation(sz) + 1)).astype(w.dtype)
    flat2 = dataclasses.replace(flat, weights=w)
    dm = mapper._DeviceMap(flat2)
    assert mapper._level_fast_delta(
        dm, list(range(dm.n_buckets))) == (0, ())
    dev_w = np.full(64, 0x10000, dtype=np.uint32)
    xs = np.arange(20_000, dtype=np.int32)
    full = mapper.compile_rule(flat2, steps, 3)
    want = np.asarray(full(xs, dev_w))
    got = mapper.sweep(flat2, steps, 3, xs, dev_w, chunk=8192)
    np.testing.assert_array_equal(got, want)
