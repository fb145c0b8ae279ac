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


def test_level_delta_eligibility():
    flat, steps = _uniform_cluster()
    dm = mapper._DeviceMap(flat)
    # uniform weights -> eligible at delta 2
    frontier = [b for b in range(dm.n_buckets)]
    assert mapper._level_fast_delta(dm, frontier) == 2
    # non-uniform weights anywhere in the frontier -> ineligible
    w = np.asarray(flat.weights).copy()
    host0 = next(b for b in range(dm.n_buckets)
                 if dm._np_sizes[b] > 0 and dm._np_items[b, 0] >= 0)
    w[host0, 0] *= 2
    import dataclasses
    flat2 = dataclasses.replace(flat, weights=w)
    dm2 = mapper._DeviceMap(flat2)
    assert mapper._level_fast_delta(dm2, [host0]) == 0
    # gigantic uniform weight above every bound -> ineligible
    w3 = np.asarray(flat.weights).copy()
    w3[host0, : int(dm._np_sizes[host0])] = 1 << 31
    flat3 = dataclasses.replace(flat, weights=w3)
    dm3 = mapper._DeviceMap(flat3)
    assert mapper._level_fast_delta(dm3, [host0]) == 0


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
    delta = mapper._level_fast_delta(dm, [bno])
    assert delta == 2 and dm.table_mode

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
    delta = mapper._level_fast_delta(dm, range(dm.n_buckets))
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
    """Mixed weights knock out eligibility; the staged sweep must stay
    exact through its table-path stages."""
    import dataclasses

    flat, steps = _uniform_cluster()
    w = np.asarray(flat.weights).copy()
    rng = np.random.default_rng(7)
    for b in range(w.shape[0]):
        sz = int(np.asarray(flat.sizes)[b])
        if sz:
            w[b, :sz] = (w[b, :sz].astype(np.uint64)
                         * rng.integers(1, 5, sz)).astype(w.dtype)
    flat2 = dataclasses.replace(flat, weights=w)
    dm = mapper._DeviceMap(flat2)
    assert mapper._level_fast_delta(
        dm, list(range(dm.n_buckets))) == 0
    dev_w = np.full(64, 0x10000, dtype=np.uint32)
    xs = np.arange(20_000, dtype=np.int32)
    full = mapper.compile_rule(flat2, steps, 3)
    want = np.asarray(full(xs, dev_w))
    got = mapper.sweep(flat2, steps, 3, xs, dev_w, chunk=8192)
    np.testing.assert_array_equal(got, want)
