"""Fixed-point crush_ln and the straw2 draw — bit-exact, vectorized.

crush_ln computes 2^44 * log2(x + 1) with the interpolation tables in
ln_table.py (reference: src/crush/mapper.c:248-290).  The straw2 draw is
  ln(hash3(x, id, r) & 0xffff) - 2^48, divided (signed, truncating) by the
16.16 item weight (reference: src/crush/mapper.c:334-359).

Because the hash is masked to 16 bits, crush_ln over the straw2 domain has
exactly 65536 distinct outputs; ``LN16`` tabulates them once so device
code replaces the bit-twiddling with a single gather.
"""

from __future__ import annotations

import functools

import numpy as np

from ceph_tpu.crush.ln_table import LL_TBL, RH_LH_TBL

_RH_LH = np.asarray(RH_LH_TBL, dtype=np.uint64)
_LL = np.asarray(LL_TBL, dtype=np.uint64)


def crush_ln(xin, xp=np, rh_lh=None, ll=None):
    """Vectorized bit-exact crush_ln over uint32 inputs in [0, 0x10000)."""
    if rh_lh is None:
        rh_lh = _RH_LH if xp is np else xp.asarray(_RH_LH)
    if ll is None:
        ll = _LL if xp is np else xp.asarray(_LL)
    x = xp.asarray(xin).astype(xp.uint32) + xp.uint32(1)

    # normalize: shift x so its highest set bit lands at position >= 15;
    # mirrors the clz branch at mapper.c:261-265 (x <= 0x10000 here).
    hb = xp.zeros(x.shape, dtype=xp.int32)
    xs = x.astype(xp.int64)
    for b in (16, 8, 4, 2, 1):
        over = (xs >> b) > 0
        hb = hb + xp.where(over, xp.int32(b), xp.int32(0))
        xs = xp.where(over, xs >> b, xs)
    bits = xp.maximum(xp.int32(15) - hb, xp.int32(0))
    x = (x.astype(xp.int64) << bits.astype(xp.int64)).astype(xp.uint32)
    iexpon = (xp.int32(15) - bits).astype(xp.int64)

    index1 = (x >> 8).astype(xp.int64) * 2
    RH = rh_lh[index1 - 256]
    LH = rh_lh[index1 + 1 - 256]

    xl64 = (x.astype(xp.uint64) * RH) >> xp.uint64(48)
    result = iexpon.astype(xp.uint64) << xp.uint64(12 + 32)

    index2 = (xl64 & xp.uint64(0xFF)).astype(xp.int64)
    LL = ll[index2]
    LH = (LH + LL) >> xp.uint64(48 - 12 - 32)
    return (result + LH).astype(xp.int64)


@functools.lru_cache(maxsize=None)
def ln16_table() -> np.ndarray:
    """int64[65536]: crush_ln(u) - 2^48 for every 16-bit hash value.

    These are the (negative) log values straw2 divides by the item weight;
    tabulating collapses crush_ln to one gather on device.
    """
    u = np.arange(0x10000, dtype=np.uint32)
    return (crush_ln(u) - np.int64(0x1000000000000)).astype(np.int64)


@functools.lru_cache(maxsize=None)
def fastcmp_bounds() -> dict:
    """{delta: bound}: for every pair of 16-bit hash values u_i < u_j
    with u_j - u_i >= delta, the straw2 magnitudes satisfy
    n(u_i) - n(u_j) >= bound, where n(u) = 2^48 - crush_ln(u).

    crush_ln's fixed-point interpolation is NOT monotone (adjacent
    values can invert by up to ~2^27.7), but the inversion is local:
    at distance >= 2 the magnitudes separate by > 2^25.  Consequence:
    among ANY set of items that share one (positive) weight
    w <= bound[d] — a whole bucket of uniform weights, or one weight
    class of a bucket that holds several — the least quotient
    floor(n/w) is EXACTLY that of the item with the maximum hash
    (first index on hash ties) whenever the set's runner-up hash is
    more than d below its maximum — floor(a/w) > floor(b/w) for
    a - b >= w.  The vmapped one-shot sweep uses this to replace
    the draw-table gathers with a pure hash+argmax in a bucket of one
    weight, and to draw one candidate a weight class in a bucket of
    several, flagging lanes whose top-2 hashes (of a class) are within
    d as unclean for the exact re-run (mapper._straw2_choose fastcmp
    path, mapper._class_choose).

    Computed exactly from the table via suffix-max (not hardcoded so
    the derivation is checkable): bound[d] = min_u [n(u) -
    max_{v >= u+d} n(v)].
    """
    n = (-ln16_table()).astype(np.int64)
    sm = np.maximum.accumulate(n[::-1])[::-1]
    return {d: int((n[:-d] - sm[d:]).min()) for d in (2, 3, 4)}


def div64_trunc(num, den, xp=np):
    """C-style truncating signed 64-bit division (div64_s64 semantics).

    numpy/jax integer ``//`` floors; C truncates toward zero.  num is the
    (negative) ln value, den the positive 16.16 weight.
    """
    num = xp.asarray(num).astype(xp.int64)
    den = xp.asarray(den).astype(xp.int64)
    q = xp.abs(num) // den
    return xp.where(num < 0, -q, q)


def straw2_draw(hash16, weight, xp=np, ln16=None):
    """draw = div64_s64(crush_ln(u) - 2^48, weight); S64_MIN if weight==0.

    hash16: uint32 array of (hash & 0xffff); weight: uint32 16.16 weights.
    reference: src/crush/mapper.c:334-375.
    """
    if ln16 is None:
        ln16 = ln16_table() if xp is np else xp.asarray(ln16_table())
    ln = ln16[xp.asarray(hash16).astype(xp.int64)]
    weight = xp.asarray(weight).astype(xp.int64)
    draw = div64_trunc(ln, xp.maximum(weight, xp.int64(1)), xp)
    s64_min = xp.int64(-0x8000000000000000)
    return xp.where(weight == 0, s64_min, draw)
