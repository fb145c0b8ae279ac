"""Vmapped CRUSH rule interpreter — full-cluster placement in one jit.

The reference walks buckets scalar-style per object
(crush_do_rule / crush_choose_firstn / crush_choose_indep, reference:
src/crush/mapper.c:900,460,655).  Here a rule is *compiled*: its steps
are unrolled at trace time into a jit-friendly function of the hash
input x, every straw2 choice is a vectorized draw+argmax over the padded
bucket arrays, and ``jax.vmap`` maps the whole walk over millions of
object ids at once — the north-star replacement for the thread-pooled
ParallelPGMapper (reference: src/osd/OSDMapMapping.h:17).

Throughput formulation (round-3 rework; the round-2 nested-while_loop
version serialized catastrophically under vmap):
- the bucket descent is UNROLLED to the map's actual tree depth
  (computed host-side from the flattened hierarchy, typically 2-3
  levels) with masked carry — there is no data-dependent while_loop
  inside the descent, so each level is one wide [batch, bucket_width]
  hash+draw+argmax block that XLA fuses and tiles;
- only the retry state machine (rare collisions/rejections) remains a
  ``lax.while_loop``, whose body is now the cheap unrolled descent; in
  the common case it runs 1-2 rounds for the whole batch;
- callers chunk very large id batches host-side (bench.py) so live HBM
  temps stay bounded.

Semantics notes (kept bit-exact vs the real reference C,
tests/test_crush_vs_reference.py):
- straw2 draw: crush_hash32_3(x, id, r) & 0xffff -> fixed-point ln table
  -> truncating s64 divide by the 16.16 weight; ties keep the first item
  (argmax == the C "strictly greater" update rule).
- firstn: per-rep retry with r' = rep + ftotal, collision against chosen
  prefix, reweight rejection via is_out, chooseleaf recursion with
  vary_r / stable.
- indep: breadth-first rounds r' = rep + n*ftotal, positionally stable,
  CRUSH_ITEM_NONE holes.
- Supported bucket algs in the jit path: straw2 (the modern default).
  uniform/list/tree/straw maps fall back to the native oracle.

64-bit note: straw2 draws are exact signed-64-bit fixed-point math in
the reference (crush_ln values scaled 2^48 divided by 16.16 weights,
div64_s64 at mapper.c:358).  TPUs have no 64-bit integer datapath, so
this interpreter computes the EXACT quotient entirely in uint32:
n = -(ln) < 2^48 is split into 16-bit limbs, multiplied by a
per-weight magic reciprocal floor((2^64-1)/w) (weights are map
constants) via limb products that never overflow u32, and corrected by
one (q+1)*w comparison; the winning item is the lexicographic argmin
of (q_hi, q_lo) with first-index tie-break — identical to the C's
strictly-greater draw update.  No jax_enable_x64 anywhere (the round-2
global flip advisory), and no 64-bit ops for XLA to emulate.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.core import tracing
from ceph_tpu.crush import hashes, ln
from ceph_tpu.tpu import shapebucket
from ceph_tpu.tpu.devwatch import instrumented_jit
from ceph_tpu.crush.map import (
    ALG_LIST,
    ALG_STRAW,
    ALG_STRAW2,
    ALG_TREE,
    ALG_UNIFORM,
    ITEM_NONE,
    ITEM_UNDEF,
    OP_CHOOSE_FIRSTN,
    OP_CHOOSE_INDEP,
    OP_CHOOSELEAF_FIRSTN,
    OP_CHOOSELEAF_INDEP,
    OP_EMIT,
    OP_SET_CHOOSE_TRIES,
    OP_SET_CHOOSELEAF_TRIES,
    OP_TAKE,
    FlatMap,
)

def dataclasses_replace_weights(flat: FlatMap, weights: np.ndarray):
    import dataclasses

    return dataclasses.replace(flat, weights=weights)


# descend status codes
_OK = 0
_REJECT = 1  # empty bucket mid-descent: retry with higher ftotal
_SKIP = 2  # bad item / bad type: give up on this replica slot

# draw-table fast path: one 256 KiB table pair per distinct weight value
# (real maps quantize weights to a handful of device sizes)
_MAX_DRAW_TABS = 64

# mid-stage retry budget for the staged sweeps: real retry semantics
# statically unrolled this many attempts (resolves ~97% of stage-1
# unclean lanes; the rest hit the exact full program)
MID_BUDGET = 3


class _DeviceMap:
    """FlatMap lowered to device arrays (captured by the compiled rule).

    Everything is int32/uint32: the 2^48-scale ln magnitudes and the
    64-bit magic reciprocals live as 16-bit limb planes (see
    _straw2_choose).
    """

    def __init__(self, flat: FlatMap, choose_args=None):
        # choose_args ({bucket_id: [weights]}, reference
        # CrushWrapper.h:72 / crush_choose_arg) substitute the straw2
        # draw weights — balancer weight-set overrides
        base_w = np.asarray(flat.weights).copy()
        if choose_args:
            algs_np = np.asarray(flat.algs)
            for bid, ws in choose_args.items():
                bno = -1 - bid
                # the reference consults the weight set in straw2
                # buckets only (bucket_straw2_choose's arg)
                if (0 <= bno < base_w.shape[0]
                        and algs_np[bno] == ALG_STRAW2):
                    base_w[bno, : len(ws)] = ws
        flat = dataclasses_replace_weights(flat, base_w)
        # magic reciprocals for the straw2 divide: weights are map
        # constants, so the exact truncating s64 division ln/w becomes
        # a 16-bit-limb mulhi + one correction, all in uint32 (TPU has
        # no native 64-bit integer datapath at all)
        w_safe = np.maximum(np.asarray(flat.weights, dtype=np.uint64), 1)
        magic = (np.uint64(0xFFFFFFFFFFFFFFFF) // w_safe).astype(object)
        # magic split into 4x16-bit limbs
        self.magic_l = [
            jnp.asarray(
                ((magic >> (16 * i)) & 0xFFFF).astype(np.uint32))
            for i in range(4)
        ]
        self.items = jnp.asarray(flat.items, dtype=jnp.int32)
        self.weights = jnp.asarray(flat.weights, dtype=jnp.uint32)
        self.sizes = jnp.asarray(flat.sizes, dtype=jnp.int32)
        self.algs = jnp.asarray(flat.algs, dtype=jnp.int32)
        self.types = jnp.asarray(flat.types, dtype=jnp.int32)
        # ---- straw2 DRAW TABLES (the fast path) -----------------------
        # weights are map constants, so the exact truncating draw
        # q = floor(n/w) is PRECOMPUTED per distinct weight as two u32
        # planes (q < 2^49): the per-item choose collapses to one hash
        # + two table gathers + a lexicographic argmin — no limb
        # arithmetic at all.  Maps with pathological weight diversity
        # (> _MAX_DRAW_TABS distinct values) fall back to the exact
        # u32-limb magic-reciprocal path below.
        w_all = np.asarray(flat.weights, dtype=np.uint64)
        distinct = np.unique(w_all[w_all > 0])
        self.table_mode = 0 < len(distinct) <= _MAX_DRAW_TABS
        if self.table_mode:
            n64 = (-ln.ln16_table()).astype(np.uint64)
            thi = np.empty((len(distinct), 65536), dtype=np.uint32)
            tlo = np.empty((len(distinct), 65536), dtype=np.uint32)
            for i, w in enumerate(distinct):
                q = n64 // w
                thi[i] = (q >> 32).astype(np.uint32)
                tlo[i] = (q & 0xFFFFFFFF).astype(np.uint32)
            self.draw_hi = jnp.asarray(thi)
            self.draw_lo = jnp.asarray(tlo)
            # per-(bucket, item) index into the tables (0 for w==0
            # slots; those are masked invalid in the choose)
            self.w_idx = jnp.asarray(
                np.searchsorted(distinct, np.maximum(w_all, 1)
                                ).astype(np.int32))
        # n = -(crush_ln(u) - 2^48) in [1, 2^48] — note u=0 hits 2^48
        # EXACTLY, so limbs must cover 49 bits: 4x16-bit tables
        n = (-ln.ln16_table()).astype(np.uint64)
        self.ln_l = [
            jnp.asarray(((n >> (16 * i)) & 0xFFFF).astype(np.uint32))
            for i in range(4)
        ]
        self.n_buckets = int(flat.items.shape[0])
        self.max_size = int(flat.items.shape[1])
        self.max_devices = int(flat.max_devices)
        self.depth = _tree_depth(flat)
        # host-side copies for static descent planning
        self._np_items = np.asarray(flat.items)
        self._np_sizes = np.asarray(flat.sizes)
        self._np_types = np.asarray(flat.types)
        self._np_algs = np.asarray(flat.algs)
        self._np_weights = np.asarray(flat.weights)  # post-choose_args
        # legacy bucket algorithm support: aux planes are materialized
        # only for algs the map actually uses (straw2-only maps — the
        # modern default — pay nothing)
        present = set(int(a) for a, s in
                      zip(np.asarray(flat.algs), np.asarray(flat.sizes))
                      if s > 0)
        self.algs_present = present
        self.only_straw2 = present <= {ALG_STRAW2}
        if flat.straws is not None:
            self.straws = jnp.asarray(flat.straws, dtype=jnp.uint32)
        if flat.sum_weights is not None:
            self.sum_weights = jnp.asarray(flat.sum_weights,
                                           dtype=jnp.uint32)
        if flat.tree_weights is not None:
            self.tree_weights = jnp.asarray(flat.tree_weights,
                                            dtype=jnp.uint32)
            self.tree_nodes = jnp.asarray(flat.tree_nodes,
                                          dtype=jnp.int32)
            self.tree_depth_max = max(
                1, int(np.asarray(flat.tree_weights).shape[1]
                       ).bit_length() - 1)


def _level_fast_delta(dm: "_DeviceMap", frontier) -> int:
    """Hash-ambiguity window for the fastcmp straw2 draw at one descent
    level, or 0 when the level is ineligible.

    Eligible when every frontier bucket is straw2 with uniform positive
    item weights, all under ln.fastcmp_bounds()[delta]: then the draw
    winner is exactly the max-hash item unless the runner-up hash is
    within delta (those lanes are flagged unclean and re-run through
    the exact table path — see ln.fastcmp_bounds).
    CEPH_TPU_CRUSH_NO_FASTCMP=1 disables (A/B + safety)."""
    import os

    from ceph_tpu.crush import ln as _ln

    if os.environ.get("CEPH_TPU_CRUSH_NO_FASTCMP") == "1":
        return 0

    wmax = 0
    for b in frontier:
        if int(dm._np_algs[b]) != ALG_STRAW2:
            return 0
        sz = int(dm._np_sizes[b])
        if sz == 0:
            continue
        ws = dm._np_weights[b, :sz]
        pos = ws[ws > 0]
        if pos.size == 0:
            continue
        if (pos != pos[0]).any():
            return 0
        wmax = max(wmax, int(pos[0]))
    if wmax == 0:
        return 0
    for d, bound in _ln.fastcmp_bounds().items():
        if wmax <= bound:
            return d
    return 0


def _descent_plan(dm: "_DeviceMap", frontier, want_type: int,
                  fastcmp: bool = False):
    """Static unroll plan for a descent whose possible start buckets
    are known at trace time: per level, (max bucket width actually
    reachable, fastcmp delta).  A take->chooseleaf walk on a
    root(64 hosts) -> host(16 osds) map plans [64, 16] instead of
    paying the global max_size at every level AND the global tree
    depth — for typical 2-level maps this halves the straw2 work per
    choose.  fastcmp=True (one-shot traces only) additionally marks
    levels whose frontier buckets have uniform weights: those levels
    draw by pure hash+argmax with an unclean flag instead of table
    gathers (_level_fast_delta).

    frontier: iterable of bucket indices possibly holding the walk at
    level 0.  Returns a list of per-level (width, delta) tuples;
    falls back to the conservative global plan when the frontier is
    unknown."""
    frontier = {b for b in frontier if 0 <= b < dm.n_buckets}
    if not frontier:
        return [(dm.max_size, 0)] * dm.depth
    plan = []
    for _ in range(dm.depth):
        width = max(int(dm._np_sizes[b]) for b in frontier)
        delta = _level_fast_delta(dm, frontier) if fastcmp else 0
        plan.append((max(width, 1), delta))
        nxt = set()
        for b in frontier:
            for j in range(int(dm._np_sizes[b])):
                it = int(dm._np_items[b, j])
                if it >= 0:
                    continue  # device: walk ends here
                sub = -1 - it
                if 0 <= sub < dm.n_buckets and \
                        int(dm._np_types[sub]) != want_type:
                    nxt.add(sub)
        if not nxt:
            break
        frontier = nxt
    return plan


def _tree_depth(flat: FlatMap) -> int:
    """Longest bucket chain (number of straw2 choices from any bucket to
    a device) — the static unroll bound for the descent."""
    items = np.asarray(flat.items)
    sizes = np.asarray(flat.sizes)
    n = items.shape[0]
    memo = [0] * n

    def depth(bno, seen):
        if memo[bno]:
            return memo[bno]
        if bno in seen:  # defensive: cyclic map
            return 1
        d = 1
        for j in range(int(sizes[bno])):
            it = int(items[bno, j])
            if it < 0:
                sub = -1 - it
                if 0 <= sub < n:
                    d = max(d, 1 + depth(sub, seen | {bno}))
        memo[bno] = d
        return d

    best = 1
    for b in range(n):
        if sizes[b] > 0:
            best = max(best, depth(b, frozenset()))
    return best


_U16 = jnp.uint32(0xFFFF)
_UMAX = jnp.uint32(0xFFFFFFFF)


def _straw2_choose(dm: _DeviceMap, bno, x, r, width=None, delta: int = 0):
    """Vectorized bucket_straw2_choose (reference: mapper.c:361-384),
    exact and 64-bit-free.  Returns (item, ambig).

    The C computes draw = div64_s64(ln, w) per item and keeps the
    strictly-greatest draw (first index on ties).  ln is negative with
    |ln| = n < 2^48, so argmax(draw) == lexicographic argmin of the
    positive quotient q = floor(n / w).

    fastcmp path (delta > 0, one-shot traces on uniform-weight
    buckets): the winner is the max-hash item directly — NO table
    access at all (TPU gathers measured ~8x slower than the hash
    itself).  Exact except when the runner-up hash is within `delta`
    of the winner (ln.fastcmp_bounds derivation); those lanes return
    ambig=True and the two-stage sweep re-runs them through the exact
    program, so end-to-end results stay bit-identical.

    Table path (table_mode): weights are map constants, so q is
    precomputed per distinct weight as (hi, lo) u32 planes over all
    2^16 hash values — the choose is one hash + two gathers + a
    lexicographic argmin.  Fallback: q computed exactly in uint32 limb
    arithmetic: q_est = floor(n * floor((2^64-1)/w) / 2^64) via 16-bit
    limb products (never overflowing u32), then one upward correction
    (q_est is provably in {q-1, q} for n < 2^48).
    """
    width = width or dm.max_size
    items = dm.items[:, :width][bno]
    wts = dm.weights[:, :width][bno]
    size = dm.sizes[bno]
    u = hashes.hash32_3(
        x.astype(jnp.uint32), items.astype(jnp.uint32), r.astype(jnp.uint32),
        xp=jnp,
    ) & _U16
    if delta:
        valid = (jnp.arange(width) < size) & (wts > 0)
        uv = jnp.where(valid, u.astype(jnp.int32), jnp.int32(-1))
        u1 = jnp.max(uv)
        sel1 = uv == u1  # valid implied: invalid slots are -1 < u1
        i1 = jnp.argmax(sel1).astype(jnp.int32)
        # nearest DISTINCT runner-up; hash ties (same u -> same draw)
        # resolve first-index exactly like the table path
        sel2 = (~sel1) & (uv >= 0)
        u2 = jnp.max(jnp.where(sel2, uv, jnp.int32(-1)))
        close2 = (u2 >= 0) & (u1 - u2 <= delta)
        if dm.table_mode:
            # EXACT runner-up resolution: the only contested case is
            # u1 - u2 <= delta (ln.fastcmp_bounds), so compare the two
            # candidates' true draws via two precomputed q-table
            # lookups — 4 scattered gathers instead of 2*width.  Only
            # a THIRD distinct hash inside the window (P ~ 1e-5 per
            # draw) stays ambiguous.
            i2 = jnp.argmax(sel2 & (uv == u2)).astype(jnp.int32)
            wi = dm.w_idx[bno, jnp.minimum(i1, width - 1)]
            u2c = jnp.clip(u2, 0, 0xFFFF)
            q1h, q1l = dm.draw_hi[wi, u1], dm.draw_lo[wi, u1]
            q2h, q2l = dm.draw_hi[wi, u2c], dm.draw_lo[wi, u2c]
            two_wins = (q2h < q1h) | ((q2h == q1h) & (q2l < q1l))
            q_tie = (q2h == q1h) & (q2l == q1l)
            resolved = jnp.where(
                q_tie, jnp.minimum(i1, i2), jnp.where(two_wins, i2, i1))
            idx = jnp.where(close2, resolved, i1)
            u3 = jnp.max(jnp.where(sel2 & (uv != u2), uv, jnp.int32(-1)))
            ambig = (u3 >= 0) & (u1 - u3 <= delta)
            return items[idx], ambig
        # no q tables on this map: flag the contested case instead
        # all-invalid: u1 == -1, argmax(all False) == 0 -> items[0],
        # identical to the table path's all-masked argmin
        return items[i1], close2
    no_ambig = jnp.asarray(False)
    if dm.table_mode:
        ui = u.astype(jnp.int32)
        wi = dm.w_idx[:, :width][bno]
        q_hi = dm.draw_hi[wi, ui]
        q_lo = dm.draw_lo[wi, ui]
        valid = (jnp.arange(width) < size) & (wts > 0)
        q_hi = jnp.where(valid, q_hi, _UMAX)
        q_lo = jnp.where(valid, q_lo, _UMAX)
        min_hi = jnp.min(q_hi)
        cand = q_hi == min_hi
        min_lo = jnp.min(jnp.where(cand, q_lo, _UMAX))
        sel = cand & (q_lo == min_lo)
        return items[jnp.argmax(sel)], no_ambig
    ui = u.astype(jnp.int32)
    nl = [dm.ln_l[i][ui] for i in range(4)]  # n in 4x16-bit limbs
    ml = [mlj[:, :width][bno] for mlj in dm.magic_l]  # magic, 16-bit limbs

    # P = n * magic: 16-bit-limb column accumulation; per-column sums
    # stay < 2^19 (<= 4 lo + 4 hi terms of < 2^16 each)
    prods = {(i, j): nl[i] * ml[j] for i in range(4) for j in range(4)}
    carry = jnp.zeros_like(u)
    digits = []
    for k in range(7):
        s = carry
        for (i, j), v in prods.items():
            if i + j == k:
                s = s + (v & _U16)
            if i + j == k - 1:
                s = s + (v >> 16)
        digits.append(s & _U16)
        carry = s >> 16
    q_top = carry + (prods[(3, 3)] >> 16)  # digit 7 (tiny, no split)
    q_lo = digits[4] | (digits[5] << 16)
    q_hi = digits[6] | (q_top << 16)

    # correction: rdr = n - q*w in 16-bit borrow arithmetic; q += (rdr>=w)
    w0, w1 = wts & _U16, wts >> 16
    ql = (digits[4], digits[5], digits[6], q_top)
    uprods = {(i, j): ql[i] * (w0 if j == 0 else w1)
              for i in range(4) for j in range(2)}
    ucar = jnp.zeros_like(u)
    udig = []
    for k in range(4):
        s = ucar
        for (i, j), v in uprods.items():
            if i + j == k:
                s = s + (v & _U16)
            if i + j == k - 1:
                s = s + (v >> 16)
        udig.append(s & _U16)
        ucar = s >> 16
    # rdr = n - q*w (borrow chain; q*w <= n so the final borrow is 0)
    borrow = jnp.zeros_like(u)
    rd = []
    for k in range(4):
        t = nl[k] + jnp.uint32(0x10000) - udig[k] - borrow
        rd.append(t & _U16)
        borrow = jnp.uint32(1) - (t >> 16)
    # rdr >= w  (rdr < 2w < 2^33: limbs 2+3 are tiny)
    ge = ((rd[3] > 0) | (rd[2] > 0) | (rd[1] > w1)
          | ((rd[1] == w1) & (rd[0] >= w0)))
    bump = ge.astype(jnp.uint32)
    q_lo2 = q_lo + bump
    q_hi = q_hi + (bump & (q_lo2 == 0).astype(jnp.uint32))
    q_lo = q_lo2

    # winner = first index of the minimal (q_hi, q_lo) among valid items
    valid = (jnp.arange(width) < size) & (wts > 0)
    q_hi = jnp.where(valid, q_hi, _UMAX)
    q_lo = jnp.where(valid, q_lo, _UMAX)
    min_hi = jnp.min(q_hi)
    cand = q_hi == min_hi
    min_lo = jnp.min(jnp.where(cand, q_lo, _UMAX))
    sel = cand & (q_lo == min_lo)
    return items[jnp.argmax(sel)], no_ambig


def _umulhi32(a, b):
    """(u32 * u32) >> 32 exactly, via 16-bit limbs (no 64-bit ops)."""
    mask = _U16
    a0, a1 = a & mask, a >> 16
    b0, b1 = b & mask, b >> 16
    mid = a1 * b0 + ((a0 * b0) >> 16)
    mid2 = a0 * b1 + (mid & mask)
    return a1 * b1 + (mid >> 16) + (mid2 >> 16)


def _bucket_id_u32(bno):
    """The bucket's signed id (-1-bno) as the u32 the C hashes use."""
    return (jnp.int32(-1) - bno).astype(jnp.uint32)


def _straw_choose(dm: _DeviceMap, bno, x, r):
    """Original straw (reference mapper.c:227 bucket_straw_choose):
    draw = (hash16) * precomputed straw scale; strictly-greater keeps
    the first maximum.  Draws are 48-bit: compared as (hi, lo16)."""
    items = dm.items[bno]
    strw = dm.straws[bno]
    size = dm.sizes[bno]
    h = hashes.hash32_3(
        x.astype(jnp.uint32), items.astype(jnp.uint32),
        r.astype(jnp.uint32), xp=jnp) & _U16
    hi = h * (strw >> 16)
    lo = h * (strw & _U16)
    c_hi = hi + (lo >> 16)
    c_lo = lo & _U16
    valid = jnp.arange(dm.max_size) < size
    c_hi = jnp.where(valid, c_hi, 0)
    c_lo = jnp.where(valid, c_lo, 0)
    max_hi = jnp.max(c_hi)
    cand = c_hi == max_hi
    max_lo = jnp.max(jnp.where(cand, c_lo, 0))
    sel = cand & (c_lo == max_lo)
    return items[jnp.argmax(sel)]


def _list_choose(dm: _DeviceMap, bno, x, r):
    """List bucket (reference mapper.c:141 bucket_list_choose): walk
    from the tail; item i wins when hash16 * sum_weights[i] >> 16 <
    item_weights[i]; fall back to items[0]."""
    items = dm.items[bno]
    sumw = dm.sum_weights[bno]
    iw = dm.weights[bno]
    size = dm.sizes[bno]
    h = hashes.hash32_4(
        x.astype(jnp.uint32), items.astype(jnp.uint32),
        r.astype(jnp.uint32), _bucket_id_u32(bno), xp=jnp) & _U16
    scaled = h * (sumw >> 16) + ((h * (sumw & _U16)) >> 16)
    cond = (jnp.arange(dm.max_size) < size) & (scaled < iw)
    # the C loop runs size-1 down to 0 and returns the first hit =
    # the LARGEST satisfying index
    rev_first = jnp.argmax(cond[::-1])
    idx = jnp.where(jnp.any(cond),
                    jnp.int32(dm.max_size - 1) - rev_first.astype(jnp.int32),
                    jnp.int32(0))
    return items[idx]


def _tree_choose(dm: _DeviceMap, bno, x, r):
    """Tree bucket (reference mapper.c:195 bucket_tree_choose): descend
    the weight tree from the root, hashing (x, node, r, id) at each
    level; leaves live at odd nodes, item = node >> 1."""
    nw = dm.tree_weights[bno]
    n = (dm.tree_nodes[bno] >> 1).astype(jnp.int32)
    bid = _bucket_id_u32(bno)
    for _ in range(dm.tree_depth_max):
        term = (n & 1) == 1
        w = nw[n]
        t = _umulhi32(
            hashes.hash32_4(x.astype(jnp.uint32), n.astype(jnp.uint32),
                            r.astype(jnp.uint32), bid, xp=jnp), w)
        lowbit = (n & (-n)).astype(jnp.int32)
        half = lowbit >> 1
        left = n - half
        nxt = jnp.where(t < nw[jnp.clip(left, 0, nw.shape[0] - 1)],
                        left, n + half)
        n = jnp.where(term, n, nxt)
    return dm.items[bno][jnp.clip(n >> 1, 0, dm.max_size - 1)]


def _uniform_choose(dm: _DeviceMap, bno, x, r):
    """Uniform bucket (reference mapper.c:73 bucket_perm_choose): the
    lazily-built pseudo-random permutation, computed functionally —
    the C's incremental workspace state is path-independent (each step
    p's swap depends only on (x, id, p)), so running the swaps
    0..pr reproduces perm[pr] exactly."""
    size = dm.sizes[bno]
    bid = _bucket_id_u32(bno)
    pr = (r % jnp.maximum(size, 1)).astype(jnp.int32)
    perm = jnp.arange(dm.max_size, dtype=jnp.int32)
    for p in range(dm.max_size - 1):
        active = (jnp.int32(p) <= pr) & (jnp.int32(p) < size - 1)
        i = (hashes.hash32_3(
            x.astype(jnp.uint32), bid, jnp.uint32(p), xp=jnp)
            % jnp.maximum(size - p, 1).astype(jnp.uint32)).astype(jnp.int32)
        pi = jnp.clip(p + i, 0, dm.max_size - 1)
        vp, vpi = perm[p], perm[pi]
        swapped = perm.at[p].set(vpi).at[pi].set(vp)
        perm = jnp.where(active, swapped, perm)
    return dm.items[bno][perm[pr]]


def _bucket_choose(dm: _DeviceMap, bno, x, r, width=None, delta: int = 0):
    """Per-alg dispatch; straw2-only maps trace straight through the
    straw2 path with zero overhead.  `width` / `delta` are the static
    per-level bounds from the descent plan (straw2 only; the legacy
    algs are rare enough to always run at full width).  Returns
    (item, ambig); delta > 0 implies the plan proved every reachable
    bucket at this level is straw2, so the legacy overrides below are
    per-lane no-ops then."""
    if dm.only_straw2:
        return _straw2_choose(dm, bno, x, r, width, delta)
    out, ambig = _straw2_choose(dm, bno, x, r, width, delta)
    alg = dm.algs[bno]
    if ALG_STRAW in dm.algs_present:
        out = jnp.where(alg == ALG_STRAW, _straw_choose(dm, bno, x, r),
                        out)
    if ALG_LIST in dm.algs_present:
        out = jnp.where(alg == ALG_LIST, _list_choose(dm, bno, x, r),
                        out)
    if ALG_TREE in dm.algs_present:
        out = jnp.where(alg == ALG_TREE, _tree_choose(dm, bno, x, r),
                        out)
    if ALG_UNIFORM in dm.algs_present:
        out = jnp.where(alg == ALG_UNIFORM,
                        _uniform_choose(dm, bno, x, r), out)
    return out, ambig


def _is_out(dev_weights, max_devices, item, x):
    """Reweight rejection (reference: mapper.c:424-438)."""
    wmax = dev_weights.shape[0]
    idx = jnp.clip(item, 0, wmax - 1)
    w = dev_weights[idx].astype(jnp.uint32)
    h = hashes.hash32_2(
        x.astype(jnp.uint32), item.astype(jnp.uint32), xp=jnp
    ) & jnp.uint32(0xFFFF)
    out = jnp.where(
        w >= 0x10000, False, jnp.where(w == 0, True, h >= w)
    )
    return jnp.where(item >= wmax, True, out)


def _descend(
    dm: _DeviceMap,
    start_bno,
    x,
    r_base,
    want_type: int,
    *,
    indep_numrep: Optional[object] = None,
    ftotal=None,
    plan=None,
):
    """Walk intervening buckets until an item of want_type is chosen.

    STATICALLY UNROLLED to the map's tree depth with masked carry — no
    while_loop, so under vmap every level is one wide batch of straw2
    draws.  For indep, r is recomputed per level from the current
    bucket's alg (reference: mapper.c:719-728); for firstn r_base is
    final.  Returns (item, status).
    """

    def r_for(bno):
        if indep_numrep is None:
            return r_base
        numrep = indep_numrep
        uniform = (dm.algs[bno] == ALG_UNIFORM) & (
            dm.sizes[bno] % jnp.maximum(numrep, 1) == 0
        )
        mult = jnp.where(uniform, numrep + 1, numrep)
        return r_base + mult * ftotal

    bno = jnp.asarray(start_bno, dtype=jnp.int32)
    item = jnp.int32(0)
    done = jnp.asarray(False)
    status = jnp.int32(_OK)
    ambig = jnp.asarray(False)

    levels = plan if plan is not None else [(dm.max_size, 0)] * dm.depth
    for width, fast_delta in levels:
        empty = dm.sizes[bno] == 0
        it, amb = _bucket_choose(dm, bno, x, r_for(bno), width, fast_delta)
        bad_item = it >= dm.max_devices
        sub_bno = -1 - it
        valid_sub = (it < 0) & (sub_bno < dm.n_buckets)
        itemtype = jnp.where(
            valid_sub, dm.types[jnp.clip(sub_bno, 0, dm.n_buckets - 1)], 0
        )
        is_target = itemtype == want_type
        # resolution order mirrors the C walk
        new_status = jnp.where(
            empty,
            jnp.int32(_REJECT),
            jnp.where(
                bad_item,
                jnp.int32(_SKIP),
                jnp.where(
                    is_target,
                    jnp.int32(_OK),
                    jnp.where(valid_sub, jnp.int32(_OK), jnp.int32(_SKIP)),
                ),
            ),
        )
        keep_going = (~empty) & (~bad_item) & (~is_target) & valid_sub
        new_item = jnp.where(empty, item, it)
        # masked carry: lanes already done pass through unchanged
        status = jnp.where(done, status, new_status)
        item = jnp.where(done, item, new_item)
        ambig = ambig | ((~done) & amb)
        bno = jnp.where((~done) & keep_going, sub_bno, bno)
        done = done | ~keep_going

    status = jnp.where(done, status, jnp.int32(_SKIP))  # depth exhausted
    return item, status, ambig


def _leaf_attempt(dm, dev_weights, bno, x, r, outpos, out2, plan=None):
    """One recursive chooseleaf descent attempt (type-0 target)."""
    nslots = out2.shape[0]
    item, status, ambig = _descend(dm, bno, x, r, 0, plan=plan)
    collide = jnp.any((jnp.arange(nslots) < outpos) & (out2 == item))
    reject = (status == _REJECT) | _is_out(
        dev_weights, dm.max_devices, item, x
    )
    skip = status == _SKIP
    fail = reject | collide
    return item, (~fail) & (~skip), skip, fail, ambig


def _leaf_firstn(
    dm: _DeviceMap,
    dev_weights,
    bucket_item,
    x,
    outpos,
    out2,
    sub_r,
    recurse_tries: int,
    stable: int,
    plan=None,
    unroll: int = 0,
):
    """The chooseleaf recursion: pick ONE device under bucket_item.

    Mirrors the recursive crush_choose_firstn call at mapper.c:573-588:
    numrep = 1 (stable) / outpos+1 (legacy), collision checked against
    the leaves chosen so far (out2[:outpos]).
    Returns (leaf_item, ok).

    With the modern chooseleaf_descend_once profile recurse_tries == 1,
    so the retry loop is statically elided to a single attempt.
    """
    bno = -1 - bucket_item
    rep = jnp.where(jnp.bool_(stable), 0, outpos)

    if recurse_tries == 1:
        item, placed, _, _, ambig = _leaf_attempt(
            dm, dev_weights, bno, x, rep + sub_r, outpos, out2, plan
        )
        return item, placed, ambig

    def cond(c):
        ftotal, _, placed, give_up, _ = c
        return (~placed) & (~give_up)

    def body(c):
        ftotal, _, placed, give_up, amb0 = c
        item, ok, skip, fail, amb = _leaf_attempt(
            dm, dev_weights, bno, x, rep + sub_r + ftotal, outpos, out2,
            plan,
        )
        nf = ftotal + 1
        return (nf, item, ok, skip | (fail & (nf >= recurse_tries)),
                amb0 | amb)

    init = (jnp.int32(0), jnp.int32(0), jnp.asarray(False),
            jnp.asarray(False), jnp.asarray(False))
    if unroll:
        c = init
        for _ in range(min(unroll, recurse_tries)):
            active = cond(c)
            cn = body(c)
            c = jax.tree.map(
                lambda new, old: jnp.where(active, new, old), cn, c)
        _, item, placed, _, ambig = c
        # ran out of unroll budget while the exact program would keep
        # trying: reporting failure here would let the OUTER retry
        # diverge from the exact walk — poison the lane instead
        ambig = ambig | cond(c)
        return item, placed, ambig
    _, item, placed, _, ambig = jax.lax.while_loop(cond, body, init)
    return item, placed, ambig


def _choose_firstn_oneshot(
    dm: _DeviceMap,
    dev_weights,
    bucket_bno,
    x,
    numrep: int,
    want_type: int,
    recurse_to_leaf: bool,
    vary_r: int,
    plan,
    leaf_plan,
):
    """One-attempt-per-rep firstn (the two-stage sweep's fast pass,
    stable-chooseleaf profile): every rep's descent is INDEPENDENT at
    ftotal=0, so all numrep descents run as one vmapped [numrep, width]
    block (XLA fuses the hashes/gathers wide) and only the cheap
    accept/collision logic stays sequential.  Bit-identical to the
    tries=1 sequential body: retries only change results on failure,
    and failures here mean the lane is re-run by the full program."""
    reps = jnp.arange(numrep, dtype=jnp.int32)
    items, statuses, ambigs = jax.vmap(
        lambda r: _descend(dm, bucket_bno, x, r, want_type, plan=plan)
    )(reps)
    ambig_any = jnp.any(ambigs)
    if recurse_to_leaf:
        sub_rs = (reps >> (vary_r - 1)) if vary_r else jnp.zeros_like(reps)
        # stable profile: leaf rep is 0 for every slot
        leaf_items, leaf_statuses, leaf_ambigs = jax.vmap(
            lambda it, sr: _descend(
                dm, -1 - jnp.minimum(it, -1), x, sr, 0, plan=leaf_plan)
        )(items, sub_rs)
        # dummy descents (item not a bucket) carry no real ambiguity
        ambig_any = ambig_any | jnp.any(leaf_ambigs & (items < 0))

    out = jnp.full((numrep,), ITEM_NONE, dtype=jnp.int32)
    out2 = jnp.full((numrep,), ITEM_NONE, dtype=jnp.int32)
    outpos = jnp.int32(0)
    for rep in range(numrep):
        item, status = items[rep], statuses[rep]
        collide = jnp.any((jnp.arange(numrep) < outpos) & (out == item))
        reject = status == _REJECT
        skip = status == _SKIP
        leaf = item
        if recurse_to_leaf:
            is_bucket = item < 0
            l_item, l_status = leaf_items[rep], leaf_statuses[rep]
            l_collide = jnp.any((jnp.arange(numrep) < outpos)
                                & (out2 == l_item))
            l_ok = ((l_status == _OK) & (~l_collide)
                    & ~_is_out(dev_weights, dm.max_devices, l_item, x))
            leaf = jnp.where(is_bucket, l_item, item)
            leaf_fail = is_bucket & (~l_ok) & (~collide) & (status == _OK)
            reject = reject | leaf_fail
        if want_type == 0:
            reject = reject | (
                (status == _OK) & (~collide)
                & _is_out(dev_weights, dm.max_devices, item, x))
        placed = (status == _OK) & (~reject) & (~collide) & (~skip)
        out = jnp.where(placed, out.at[outpos].set(item), out)
        out2 = jnp.where(placed, out2.at[outpos].set(leaf), out2)
        outpos = outpos + placed.astype(jnp.int32)
    values = out2 if recurse_to_leaf else out
    return values, outpos, ambig_any


def _choose_firstn(
    dm: _DeviceMap,
    dev_weights,
    bucket_bno,
    x,
    numrep: int,
    want_type: int,
    tries: int,
    recurse_tries: int,
    recurse_to_leaf: bool,
    vary_r: int,
    stable: int,
    plan=None,
    leaf_plan=None,
    unroll: int = 0,
):
    """crush_choose_firstn for one source bucket (outpos starts at 0).

    Returns (values[numrep], count, ambig): values are leaves when
    recurse_to_leaf else items; only the first `count` are valid.

    unroll > 0 (bounded-budget traces, the sweep's mid stage): the
    retry while_loops are statically unrolled to `unroll` attempts.  A
    lane whose every rep places within the budget follows the exact
    program's attempt sequence verbatim (retries are deterministic), so
    its result is bit-identical; a rep that exhausts the budget leaves
    count < numrep (or sets ambig via the bounded leaf recursion) and
    the caller re-runs the lane through the full program.
    """
    out = jnp.full((numrep,), ITEM_NONE, dtype=jnp.int32)
    out2 = jnp.full((numrep,), ITEM_NONE, dtype=jnp.int32)
    outpos = jnp.int32(0)
    ambig_all = jnp.asarray(False)

    for rep in range(numrep):
        def cond(c):
            ftotal, _, _, placed, give_up, _ = c
            return (~placed) & (~give_up)

        def body(c, rep=rep):
            ftotal, item_prev, leaf_prev, placed, give_up, amb0 = c
            r = rep + ftotal
            item, status, amb = _descend(dm, bucket_bno, x, r, want_type,
                                         plan=plan)
            collide = jnp.any((jnp.arange(numrep) < outpos) & (out == item))
            reject = status == _REJECT
            skip = status == _SKIP
            leaf = item
            if recurse_to_leaf:
                sub_r = (r >> (vary_r - 1)) if vary_r else jnp.int32(0)
                is_bucket = item < 0
                leaf_item, leaf_ok, leaf_amb = _leaf_firstn(
                    dm, dev_weights, jnp.minimum(item, -1), x, outpos,
                    out2, sub_r, recurse_tries, stable, leaf_plan,
                    unroll,
                )
                leaf = jnp.where(is_bucket, leaf_item, item)
                leaf_fail = is_bucket & (~leaf_ok) & (~collide) & (status == _OK)
                reject = reject | leaf_fail
                amb = amb | (leaf_amb & is_bucket)
            if want_type == 0:
                reject = reject | (
                    (status == _OK)
                    & (~collide)
                    & _is_out(dev_weights, dm.max_devices, item, x)
                )
            fail = reject | collide
            nf = ftotal + 1
            return (
                nf,
                item,
                leaf,
                (status == _OK) & (~fail) & (~skip),
                skip | (fail & (nf >= tries)),
                amb0 | amb,
            )

        init = (
            jnp.int32(0),
            jnp.int32(0),
            jnp.int32(0),
            jnp.asarray(False),
            jnp.asarray(False),
            jnp.asarray(False),
        )
        if tries == 1:
            # one-shot trace (the two-stage sweep's fast pass): a single
            # inline attempt, no while_loop round-trips
            _, item, leaf, placed, _, amb = body(init)
        elif unroll:
            c = init
            for _ in range(min(unroll, tries)):
                active = cond(c)
                cn = body(c)
                c = jax.tree.map(
                    lambda new, old: jnp.where(active, new, old), cn, c)
            _, item, leaf, placed, _, amb = c
            # budget exhausted mid-retry: not placed -> count stays
            # short -> the lane is re-run by the full program
        else:
            _, item, leaf, placed, _, amb = jax.lax.while_loop(
                cond, body, init)
        out = jnp.where(placed, out.at[outpos].set(item), out)
        out2 = jnp.where(placed, out2.at[outpos].set(leaf), out2)
        outpos = outpos + placed.astype(jnp.int32)
        ambig_all = ambig_all | amb

    values = out2 if recurse_to_leaf else out
    return values, outpos, ambig_all


def _leaf_indep(dm, dev_weights, bucket_item, x, numrep, parent_r,
                recurse_tries: int, plan=None, unroll: int = 0):
    """Recursive indep leaf choice: one slot, r' = parent_r + n*ftotal."""
    bno = -1 - bucket_item

    def attempt(ftotal):
        item, status, amb = _descend(
            dm, bno, x, parent_r, 0,
            indep_numrep=jnp.int32(numrep), ftotal=ftotal, plan=plan,
        )
        bad = status != _OK
        outed = _is_out(dev_weights, dm.max_devices, item, x)
        return jnp.where(bad | outed, ITEM_UNDEF, item), amb

    def body(ftotal, c):
        got, amb0 = c
        nxt, amb = attempt(jnp.int32(ftotal))
        return (jnp.where(got == ITEM_UNDEF, nxt, got),
                amb0 | (amb & (got == ITEM_UNDEF)))

    init = (jnp.int32(ITEM_UNDEF), jnp.asarray(False))
    if recurse_tries == 1:
        got, ambig = attempt(jnp.int32(0))
    elif unroll:
        c = init
        for f in range(min(unroll, recurse_tries)):
            c = body(f, c)
        got, ambig = c
        # budget < the exact program's tries and still unresolved:
        # the exact result could differ — poison the lane
        ambig = ambig | ((got == ITEM_UNDEF) & (unroll < recurse_tries))
    else:
        got, ambig = jax.lax.fori_loop(0, recurse_tries, body, init)
    return jnp.where(got == ITEM_UNDEF, ITEM_NONE, got), ambig


def _choose_indep(
    dm: _DeviceMap,
    dev_weights,
    bucket_bno,
    x,
    left0: int,
    numrep: int,
    want_type: int,
    tries: int,
    recurse_tries: int,
    recurse_to_leaf: bool,
    plan=None,
    leaf_plan=None,
    unroll: int = 0,
):
    """crush_choose_indep for one source bucket (positional, out_size
    slots).  Returns (values[left0], nslots, ambig) with
    CRUSH_ITEM_NONE holes.  unroll bounds the retry rounds statically
    (see _choose_firstn): unfilled slots after the budget leave NONE
    holes, which the bounded-budget caller treats as unclean."""
    nslots = left0
    out = jnp.full((nslots,), ITEM_UNDEF, dtype=jnp.int32)
    out2 = jnp.full((nslots,), ITEM_UNDEF, dtype=jnp.int32)

    def round_body(c):
        ftotal, out, out2, left, ambig = c
        for rep in range(nslots):
            # compute the slot unconditionally (under vmap a cond is a
            # select anyway) and mask the update on slot-vacancy
            vacant = out[rep] == ITEM_UNDEF
            item, status, amb = _descend(
                dm, bucket_bno, x, jnp.int32(rep), want_type,
                indep_numrep=jnp.int32(numrep), ftotal=ftotal, plan=plan,
            )
            collide = jnp.any(out == item)
            hard_fail = status == _SKIP
            soft_fail = (status == _REJECT) | collide
            leaf = item
            if recurse_to_leaf:
                is_bucket = item < 0
                # the recursion's slot r is rep + parent_r where
                # parent_r is the r at which this bucket was chosen
                # (straw2-only => the per-level multiplier is always
                # numrep, so r_parent is the top-level r')
                r_parent = jnp.int32(rep) + jnp.int32(numrep) * ftotal
                leaf_val, leaf_amb = _leaf_indep(
                    dm, dev_weights, jnp.minimum(item, -1), x,
                    numrep, jnp.int32(rep) + r_parent, recurse_tries,
                    leaf_plan, unroll,
                )
                leaf = jnp.where(is_bucket, leaf_val, item)
                amb = amb | (leaf_amb & is_bucket)
                soft_fail = soft_fail | (
                    is_bucket & (leaf == ITEM_NONE) & (status == _OK)
                )
            outed = jnp.where(
                want_type == 0,
                (status == _OK)
                & _is_out(dev_weights, dm.max_devices, item, x),
                False,
            )
            soft_fail = soft_fail | outed
            ok = (status == _OK) & (~soft_fail) & (~hard_fail)
            new_item = jnp.where(
                hard_fail, ITEM_NONE, jnp.where(ok, item, ITEM_UNDEF)
            )
            new_leaf = jnp.where(
                hard_fail, ITEM_NONE, jnp.where(ok, leaf, ITEM_UNDEF)
            )
            placed = (ok | hard_fail) & vacant
            out = jnp.where(placed, out.at[rep].set(new_item), out)
            out2 = jnp.where(placed, out2.at[rep].set(new_leaf), out2)
            left = left - placed.astype(jnp.int32)
            ambig = ambig | (amb & vacant)
        return ftotal + 1, out, out2, left, ambig

    def round_cond(c):
        ftotal, _, _, left, _ = c
        return (left > 0) & (ftotal < tries)

    init = (jnp.int32(0), out, out2, jnp.int32(nslots), jnp.asarray(False))
    if unroll:
        c = init
        for _ in range(min(unroll, tries)):
            active = round_cond(c)
            cn = round_body(c)
            c = jax.tree.map(
                lambda new, old: jnp.where(active, new, old), cn, c)
        _, out, out2, _, ambig = c
    else:
        _, out, out2, _, ambig = jax.lax.while_loop(
            round_cond, round_body, init)
    out = jnp.where(out == ITEM_UNDEF, ITEM_NONE, out)
    out2 = jnp.where(out2 == ITEM_UNDEF, ITEM_NONE, out2)
    return (out2 if recurse_to_leaf else out), jnp.int32(nslots), ambig


def _rule_digest(flat: FlatMap, steps, result_max: int,
                 choose_args) -> str:
    """Content key for the global compile cache: two maps with identical
    structure share one compiled program (the map arrays are baked into
    the trace as constants, so identical content => identical program)."""
    import hashlib

    h = hashlib.sha1()
    for arr in (flat.items, flat.weights, flat.sizes, flat.algs,
                flat.types, flat.straws, flat.sum_weights,
                flat.tree_weights, flat.tree_nodes):
        if arr is not None:
            a = np.ascontiguousarray(arr)
            h.update(str(a.dtype).encode())
            h.update(str(a.shape).encode())
            h.update(a.tobytes())
    h.update(repr(flat.tunables).encode())
    h.update(repr((flat.max_devices, result_max, list(steps))).encode())
    if choose_args:
        for bid in sorted(choose_args):
            h.update(repr((bid, list(choose_args[bid]))).encode())
    return h.hexdigest()


_compiled_rules: dict = {}  # digest -> compiled fn (process lifetime)


def compile_rule(
    flat: FlatMap,
    steps: Sequence[Tuple[int, int, int]],
    result_max: int,
    choose_args=None,
    one_shot: bool = False,
    budget: Optional[int] = None,
):
    """Build fn(xs[int32 N], device_weights[uint32 D]) -> int32 [N, result_max].

    Steps are unrolled at trace time (rules are tiny and static); holes
    are CRUSH_ITEM_NONE.  The returned callable is jitted and vmapped;
    the whole program is uint32/int32 (see module docstring), so no x64
    configuration is involved anywhere.  `choose_args`
    ({bucket_id: [weights]}) bakes straw2 weight-set overrides into the
    compiled rule (reference crush_do_rule's choose_args parameter).

    one_shot=True builds the staged sweep's FAST pass: every choose
    gets exactly one attempt (tries=1, no retry while_loops) and the
    function returns (result, clean[bool N]).  clean lanes are exactly
    the lanes whose every placement succeeded at first attempt with no
    fastcmp draw ambiguity (_straw2_choose) — for those the full
    algorithm provably produces the identical result (retries only
    trigger on failure).  Unclean lanes must be re-run through a
    higher-budget program (see sweep()); under vmap this removes the
    dominant cost of the full program, where every lane pays the
    batch's WORST-CASE retry rounds.

    budget=N (with one_shot=True) builds the MID stage: real retry
    semantics statically unrolled to N attempts per choose; lanes fully
    placed within the budget are bit-identical to the full program
    (deterministic attempt sequences), the rest stay unclean for the
    exact full program.

    Compiled programs are cached process-wide by map content: rebuilding
    an identical map (common in tests and in OSDMap churn that leaves
    the crush tree untouched) costs a digest, not a ~10s XLA compile.
    """
    import os

    budget_val = (1 if one_shot else 0) if budget is None else int(budget)
    # the kill-switch is read at TRACE time (_level_fast_delta), so it
    # must key the compile cache or toggling it mid-process is inert
    no_fc = os.environ.get("CEPH_TPU_CRUSH_NO_FASTCMP") == "1"
    digest = _rule_digest(flat, steps, result_max, choose_args) + (
        f":budget{budget_val}{':nofc' if no_fc else ''}"
        if budget_val else "")
    cached = _compiled_rules.get(digest)
    if cached is not None:
        return cached
    dm = _DeviceMap(flat, choose_args)
    tun = flat.tunables
    steps = [tuple(int(v) for v in s) for s in steps]

    def one_x(x, dev_weights):
        x = x.astype(jnp.int32)
        w_buf = jnp.full((result_max,), ITEM_NONE, dtype=jnp.int32)
        wsize = jnp.int32(0)
        result = jnp.full((result_max,), ITEM_NONE, dtype=jnp.int32)
        result_len = jnp.int32(0)
        clean = jnp.asarray(True)  # every choose succeeded first try

        choose_tries = tun.choose_total_tries + 1
        choose_leaf_tries = 0
        vary_r = tun.chooseleaf_vary_r
        stable = tun.chooseleaf_stable
        wsize_bound = 0  # static upper bound on wsize, tracked at trace time
        # static frontier: the set of buckets the NEXT choose could
        # start from, known at trace time (take args are static; after
        # a typed choose, every bucket of that type).  Drives the
        # per-level width/depth descent plans.
        static_frontier = None

        for op, arg1, arg2 in steps:
            if op == OP_TAKE:
                w_buf = w_buf.at[0].set(arg1)
                wsize = jnp.int32(1)
                wsize_bound = 1
                static_frontier = [-1 - arg1]
            elif op == OP_SET_CHOOSE_TRIES:
                if arg1 > 0:
                    choose_tries = arg1
            elif op == OP_SET_CHOOSELEAF_TRIES:
                if arg1 > 0:
                    choose_leaf_tries = arg1
            elif op in (
                OP_CHOOSE_FIRSTN,
                OP_CHOOSELEAF_FIRSTN,
                OP_CHOOSE_INDEP,
                OP_CHOOSELEAF_INDEP,
            ):
                firstn = op in (OP_CHOOSE_FIRSTN, OP_CHOOSELEAF_FIRSTN)
                recurse = op in (OP_CHOOSELEAF_FIRSTN, OP_CHOOSELEAF_INDEP)
                numrep = arg1 if arg1 > 0 else result_max + arg1
                if numrep <= 0:
                    continue
                numrep = min(numrep, result_max)
                if firstn:
                    recurse_tries = (
                        choose_leaf_tries
                        or (1 if tun.chooseleaf_descend_once else choose_tries)
                    )
                else:
                    recurse_tries = choose_leaf_tries or 1
                if budget_val == 1:
                    # legacy one-shot shape: single inline attempt
                    use_tries, use_recurse, use_unroll = 1, 1, 0
                elif budget_val > 1:
                    # bounded-budget mid stage: real retry semantics,
                    # statically unrolled to budget attempts
                    use_tries, use_recurse, use_unroll = (
                        choose_tries, recurse_tries, budget_val)
                else:
                    use_tries, use_recurse, use_unroll = (
                        choose_tries, recurse_tries, 0)
                # fastcmp deltas only in budgeted traces; the full
                # program must stay exact standalone (it is the final
                # stage unclean lanes re-run through).  With the
                # table_mode top-2 exact resolution the fastcmp draw is
                # exact except for 3-candidates-in-window (~1e-5), so
                # the mid stage keeps it too.
                fc = budget_val > 0
                plan = (_descent_plan(dm, static_frontier, arg2,
                                      fastcmp=fc)
                        if static_frontier is not None else None)
                leaf_plan = None
                if recurse and arg2 > 0:
                    # the leaf recursion starts from a bucket of type
                    # arg2 (whichever one the outer choose picked)
                    leaf_starts = [b for b in range(dm.n_buckets)
                                   if int(dm._np_types[b]) == arg2]
                    leaf_plan = _descent_plan(dm, leaf_starts, 0,
                                              fastcmp=fc)
                # after this choose the walk holds items of type arg2
                static_frontier = (
                    [b for b in range(dm.n_buckets)
                     if int(dm._np_types[b]) == arg2]
                    if arg2 > 0 else None)

                o_buf = jnp.full((result_max,), ITEM_NONE, dtype=jnp.int32)
                osize = jnp.int32(0)
                # sources are w_buf[:wsize]; wsize_bound keeps the unroll
                # tight for the common take->choose->emit shape (1 source)
                for i in range(min(wsize_bound, result_max)):
                    src_active = jnp.int32(i) < wsize
                    bno = -1 - w_buf[i]
                    bno_ok = (bno >= 0) & (bno < dm.n_buckets)
                    active = src_active & bno_ok
                    bno_safe = jnp.clip(bno, 0, dm.n_buckets - 1)
                    if firstn:
                        if budget_val == 1 and (stable or not recurse):
                            # rep-vectorized fast pass (see helper)
                            vals, cnt, amb = _choose_firstn_oneshot(
                                dm, dev_weights, bno_safe, x, numrep,
                                arg2, recurse, vary_r, plan, leaf_plan,
                            )
                        else:
                            vals, cnt, amb = _choose_firstn(
                                dm, dev_weights, bno_safe, x, numrep,
                                arg2, use_tries, use_recurse, recurse,
                                vary_r, stable, plan, leaf_plan,
                                use_unroll,
                            )
                        step_clean = (cnt == numrep) & (~amb)
                    else:
                        vals, cnt, amb = _choose_indep(
                            dm, dev_weights, bno_safe, x, numrep, numrep,
                            arg2, use_tries, use_recurse, recurse,
                            plan, leaf_plan, use_unroll,
                        )
                        step_clean = jnp.all(vals != ITEM_NONE) & (~amb)
                    clean = clean & ((~active) | step_clean)
                    cnt = jnp.where(active, cnt, 0)
                    # append vals[:cnt] at o_buf[osize:]
                    for jj in range(vals.shape[0]):
                        valid = (jnp.int32(jj) < cnt) & (osize < result_max)
                        o_buf = jnp.where(
                            valid,
                            o_buf.at[jnp.clip(osize, 0, result_max - 1)].set(
                                vals[jj]
                            ),
                            o_buf,
                        )
                        osize = osize + valid.astype(jnp.int32)
                w_buf = o_buf
                wsize = osize
                wsize_bound = min(result_max, wsize_bound * numrep)
            elif op == OP_EMIT:
                for i in range(min(wsize_bound, result_max)):
                    valid = (jnp.int32(i) < wsize) & (result_len < result_max)
                    result = jnp.where(
                        valid,
                        result.at[
                            jnp.clip(result_len, 0, result_max - 1)
                        ].set(w_buf[i]),
                        result,
                    )
                    result_len = result_len + valid.astype(jnp.int32)
                wsize = jnp.int32(0)
        if budget_val:
            return result, clean
        return result

    mapped = instrumented_jit(jax.vmap(one_x, in_axes=(0, None)),
                              family="crush_mapper")

    def run(xs, dev_weights):
        return mapped(
            jnp.asarray(xs, dtype=jnp.int32),
            jnp.asarray(dev_weights, dtype=jnp.uint32),
        )

    _compiled_rules[digest] = run
    if len(_compiled_rules) > 256:  # bound trace/executable retention
        _compiled_rules.pop(next(iter(_compiled_rules)))
    return run


def sweep(
    flat: FlatMap,
    steps: Sequence[Tuple[int, int, int]],
    result_max: int,
    xs: np.ndarray,
    dev_weights: np.ndarray,
    choose_args=None,
    chunk: int = 1 << 19,
) -> np.ndarray:
    """Full-cluster placement sweep (the ParallelPGMapper workload,
    reference src/osd/OSDMapMapping.h:17) as a THREE-STAGE program:

    1. the one-shot trace maps every id with exactly one attempt per
       choose (fastcmp draws) — the overwhelmingly common case on
       healthy maps — and reports which lanes were clean;
    2. the unclean lanes (collisions/rejections/draw ambiguity,
       typically <6%) re-run through the bounded-budget trace (real
       retry semantics unrolled to a few attempts — resolves nearly
       all collisions at a fraction of the full program's cost);
    3. the residue (typically <0.2%) re-runs through the exact
       full-retry program, padded to a power-of-two batch so the slow
       program compiles for O(log) distinct shapes.

    Chunked so live device temps stay bounded at 10M+ ids.  Bit-exact
    with running the full program on everything: a clean lane's result
    is identical by construction (retries only fire on failure, and
    budgeted lanes follow the exact attempt sequence — see
    compile_rule).
    """
    xs = np.asarray(xs, dtype=np.int32)
    n = len(xs)
    if n == 0:
        return np.empty((0, result_max), dtype=np.int32)
    fast = compile_rule(flat, steps, result_max, choose_args,
                        one_shot=True)
    mid = compile_rule(flat, steps, result_max, choose_args,
                       one_shot=True, budget=MID_BUDGET)
    slow = compile_rule(flat, steps, result_max, choose_args)
    chunk = min(chunk, n)
    outs = []
    # power-of-two padding bounds fixup shapes to O(log chunk); the
    # high-water marks additionally make them MONOTONIC within one
    # sweep: a later chunk with a smaller bad set reuses the largest
    # already-compiled shape instead of compiling a fresh smaller one
    # (pad lanes are free; a second ~5s XLA compile of the same
    # program at 4096 lanes right after the 8192-lane one is not)
    hw_mid = hw_slow = 0
    for off in range(0, n, chunk):
        sub = xs[off: off + chunk]
        if len(sub) < chunk:  # uniform shape: ONE compiled fast program
            sub = np.concatenate(
                [sub, np.full(chunk - len(sub), sub[-1], np.int32)])
        res, clean = fast(sub, dev_weights)
        res = np.array(res)  # writable host copy
        bad = np.nonzero(~np.asarray(clean))[0]
        if bad.size:
            n_pad = shapebucket.covering(int(bad.size))
            n_pad = hw_mid = max(n_pad, hw_mid)
            padded = np.full(n_pad, sub[bad[0]], dtype=np.int32)
            padded[: bad.size] = sub[bad]
            res2, clean2 = mid(padded, dev_weights)
            res[bad] = np.asarray(res2)[: bad.size]
            bad2 = np.nonzero(~np.asarray(clean2)[: bad.size])[0]
            if bad2.size:
                n_pad2 = shapebucket.covering(int(bad2.size))
                n_pad2 = hw_slow = max(n_pad2, hw_slow)
                padded2 = np.full(n_pad2, padded[bad2[0]], dtype=np.int32)
                padded2[: bad2.size] = padded[bad2]
                fixed = np.asarray(slow(padded2, dev_weights))
                res[bad[bad2]] = fixed[: bad2.size]
        outs.append(res[: len(xs) - off])
    return np.concatenate(outs) if len(outs) > 1 else outs[0]


def sweep_device(
    flat: FlatMap,
    steps: Sequence[Tuple[int, int, int]],
    result_max: int,
    xs,
    dev_weights,
    choose_args=None,
    chunk: int = 1 << 19,
    bad_div: int = 8,
    bad2_div: int = 2048,
):
    """Device-resident staged sweep: the whole multi-million-id program
    is ONE jit dispatch, placements stay in HBM, and nothing
    round-trips to the host (sweep()'s per-chunk host fixup pays a
    fetch and an upload per chunk).

    Same three-stage semantics as sweep() but with static shapes:

    1. fast one-shot pass (fastcmp draws) over each chunk;
    2. the unclean lane INDICES are extracted with a fixed capacity of
       chunk/bad_div (jnp.nonzero(size=...)), re-run through the
       bounded-budget program, and scattered back (out-of-capacity
       padding indices are dropped);
    3. lanes still unclean after the budget re-run through the exact
       full-retry program in ONE global batch after the scan (capacity
       max(n/bad2_div, 2048)) — the full program's while_loop overhead
       is paid once per sweep, not once per chunk.

    Healthy maps run ~6% unclean after stage 1 and ~0.006% after stage
    2, far under the 12.5% / 0.05%+floor default capacities; if the
    sweep overflows either capacity, the returned flag is True and the
    caller must fall back to sweep() (results would be incomplete, not
    wrong: overflowed lanes keep their earlier-stage placement, which
    may differ from full retry).  bad_div=1, bad2_div=1 gives full
    capacity at every stage (exact on any map, at full-program cost
    for the fixup batches).

    xs length must be a multiple of `chunk` (callers pad; the bench
    repeats ids).  Returns (placements i32 [N, result_max] ON DEVICE,
    overflow bool ON DEVICE).
    """
    xs = jnp.asarray(xs, dtype=jnp.int32)
    n = int(xs.shape[0])
    chunk = min(chunk, n)
    assert n % chunk == 0, (n, chunk)
    cap = max(1, chunk // bad_div)
    # global stage-3 capacity: residue is ~0.006% on healthy maps; the
    # floor keeps small sweeps from starving the exact stage
    cap2 = min(n, max(n // bad2_div, 2048))

    # the jitted runner is cached process-wide (like compile_rule):
    # a fresh jax.jit wrapper per call would re-trace + re-compile on
    # EVERY call, so repeated sweeps would time XLA, not the sweep
    import os

    key = (_rule_digest(flat, steps, result_max, choose_args),
           "sweep_device", n, chunk, cap, cap2,
           os.environ.get("CEPH_TPU_CRUSH_NO_FASTCMP") == "1")
    run = _compiled_rules.get(key)
    if run is None:
        fast = compile_rule(flat, steps, result_max, choose_args,
                            one_shot=True)
        mid = compile_rule(flat, steps, result_max, choose_args,
                           one_shot=True, budget=MID_BUDGET)
        slow = compile_rule(flat, steps, result_max, choose_args)

        @functools.partial(instrumented_jit, family="crush_mapper")
        def run(xs2, w):
            def body(overflow, sub):
                with jax.named_scope("crush.fast"):
                    res, clean = fast(sub, w)
                bad = jnp.nonzero(~clean, size=cap, fill_value=chunk)[0]
                n_bad = jnp.sum(~clean)
                # padding lanes (index==chunk) clamp to chunk-1 and
                # recompute sub[chunk-1]; their scatter is dropped
                bad_xs = sub[jnp.minimum(bad, chunk - 1)]
                with jax.named_scope("crush.mid"):
                    res2, clean2 = mid(bad_xs, w)
                res = res.at[bad].set(res2, mode="drop")
                # residual mask back in chunk shape (padding dropped);
                # the exact full-program fixup runs ONCE over the whole
                # sweep after the scan — its while_loop overhead is per
                # batch, not per chunk
                resid = jnp.zeros((chunk,), jnp.bool_).at[bad].set(
                    ~clean2, mode="drop")
                return overflow | (n_bad > cap), (res, resid)

            overflow, (out, resids) = jax.lax.scan(
                body, jnp.asarray(False), xs2.reshape(-1, chunk))
            out = out.reshape(n, result_max)
            resid_all = resids.reshape(n)
            n3 = jnp.sum(resid_all)
            b3 = jnp.nonzero(resid_all, size=cap2, fill_value=n)[0]
            xs3 = xs2[jnp.minimum(b3, n - 1)]
            with jax.named_scope("crush.slow"):
                fixed = slow(xs3, w)
            out = out.at[b3].set(fixed, mode="drop")
            return out, overflow | (n3 > cap2)

        _compiled_rules[key] = run
        if len(_compiled_rules) > 256:
            _compiled_rules.pop(next(iter(_compiled_rules)))

    with tracing.span("crush.sweep", ids=n, chunk=chunk):
        return run(xs, jnp.asarray(dev_weights, dtype=jnp.uint32))
