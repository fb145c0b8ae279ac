"""rjenkins1 32-bit hash family, vectorized.

Bit-exact port of the reference's crush_hash32* functions
(reference: src/crush/hash.c:12-90).  Written against an array-namespace
parameter ``xp`` so the identical code serves as the numpy oracle and the
jax.numpy device kernel (uint32 wraparound semantics match in both); the
mix round takes its five operations from ``_ops(xp)``.
"""

from __future__ import annotations

import contextlib
import functools
import operator

import numpy as np

CRUSH_HASH_SEED = 1315423911  # reference: src/crush/hash.c:24
CRUSH_HASH_RJENKINS1 = 0


def _quiet(xp):
    """uint32 wraparound is intended; silence numpy scalar warnings."""
    if xp is np:
        return np.errstate(over="ignore")
    return contextlib.nullcontext()


@functools.lru_cache(maxsize=None)
def _ops(xp):
    """(uint32 cast, subtract, xor, shift right, shift left) over the
    namespace: numpy's operators, or jax.lax's primitives, which trace
    to the same operations as jax.numpy's operators without their
    per-call dispatch (a CRUSH rule program holds thousands of them);
    lax takes operands of one shape, or a scalar beside an array."""
    if xp is np:
        return (lambda v: v.astype(np.uint32) if hasattr(v, "astype")
                else np.uint32(v),
                operator.sub, operator.xor, operator.rshift, operator.lshift)
    from jax import lax

    def u32(v):
        if not hasattr(v, "astype"):
            return xp.uint32(v)
        return v if v.dtype == np.uint32 else v.astype(np.uint32)

    return (u32, lax.sub, lax.bitwise_xor,
            lambda v, s: lax.shift_right_logical(v, np.uint32(s)),
            lambda v, s: lax.shift_left(v, np.uint32(s)))


def _mix(a, b, c, xp):
    """One crush_hashmix round (reference: src/crush/hash.c:12-22)."""
    u32, sub, xor, shr, shl = _ops(xp)
    a, b, c = u32(a), u32(b), u32(c)
    a = xor(sub(sub(a, b), c), shr(c, 13))
    b = xor(sub(sub(b, c), a), shl(a, 8))
    c = xor(sub(sub(c, a), b), shr(b, 13))
    a = xor(sub(sub(a, b), c), shr(c, 12))
    b = xor(sub(sub(b, c), a), shl(a, 16))
    c = xor(sub(sub(c, a), b), shr(b, 5))
    a = xor(sub(sub(a, b), c), shr(c, 3))
    b = xor(sub(sub(b, c), a), shl(a, 10))
    c = xor(sub(sub(c, a), b), shr(b, 15))
    return a, b, c


def hash32(a, xp=np):
    with _quiet(xp):
        a = xp.asarray(a).astype(xp.uint32)
        h = xp.uint32(CRUSH_HASH_SEED) ^ a
        b = a
        x = xp.uint32(231232)
        y = xp.uint32(1232)
        b, x, h = _mix(b, x, h, xp)
        y, a, h = _mix(y, a, h, xp)
        return h


def hash32_2(a, b, xp=np):
    with _quiet(xp):
        a = xp.asarray(a).astype(xp.uint32)
        b = xp.asarray(b).astype(xp.uint32)
        h = xp.uint32(CRUSH_HASH_SEED) ^ a ^ b
        x = xp.uint32(231232)
        y = xp.uint32(1232)
        a, b, h = _mix(a, b, h, xp)
        x, a, h = _mix(x, a, h, xp)
        b, y, h = _mix(b, y, h, xp)
        return h


def hash32_3(a, b, c, xp=np):
    with _quiet(xp):
        a = xp.asarray(a).astype(xp.uint32)
        b = xp.asarray(b).astype(xp.uint32)
        c = xp.asarray(c).astype(xp.uint32)
        h = xp.uint32(CRUSH_HASH_SEED) ^ a ^ b ^ c
        x = xp.uint32(231232)
        y = xp.uint32(1232)
        a, b, h = _mix(a, b, h, xp)
        c, x, h = _mix(c, x, h, xp)
        y, a, h = _mix(y, a, h, xp)
        b, x, h = _mix(b, x, h, xp)
        y, c, h = _mix(y, c, h, xp)
        return h


def hash32_4(a, b, c, d, xp=np):
    with _quiet(xp):
        a = xp.asarray(a).astype(xp.uint32)
        b = xp.asarray(b).astype(xp.uint32)
        c = xp.asarray(c).astype(xp.uint32)
        d = xp.asarray(d).astype(xp.uint32)
        h = xp.uint32(CRUSH_HASH_SEED) ^ a ^ b ^ c ^ d
        x = xp.uint32(231232)
        y = xp.uint32(1232)
        a, b, h = _mix(a, b, h, xp)
        c, d, h = _mix(c, d, h, xp)
        a, x, h = _mix(a, x, h, xp)
        y, b, h = _mix(y, b, h, xp)
        c, x, h = _mix(c, x, h, xp)
        y, d, h = _mix(y, d, h, xp)
        return h


def hash32_5(a, b, c, d, e, xp=np):
    with _quiet(xp):
        arrs = [xp.asarray(v).astype(xp.uint32) for v in (a, b, c, d, e)]
        a, b, c, d, e = arrs
        h = xp.uint32(CRUSH_HASH_SEED) ^ a ^ b ^ c ^ d ^ e
        x = xp.uint32(231232)
        y = xp.uint32(1232)
        a, b, h = _mix(a, b, h, xp)
        c, d, h = _mix(c, d, h, xp)
        e, x, h = _mix(e, x, h, xp)
        y, a, h = _mix(y, a, h, xp)
        b, x, h = _mix(b, x, h, xp)
        y, c, h = _mix(y, c, h, xp)
        d, x, h = _mix(d, x, h, xp)
        y, e, h = _mix(y, e, h, xp)
        return h


def str_hash_rjenkins(name: bytes) -> int:
    """ceph_str_hash_rjenkins — the object-name hash feeding pg selection.

    Bit-exact port of the reference's string rjenkins
    (reference: src/common/ceph_hash.cc: ceph_str_hash_rjenkins), used by
    pg_pool_t::hash_key (reference: src/osd/osd_types.cc:1468).
    """
    if isinstance(name, str):
        name = name.encode()
    length = len(name)
    a = np.uint32(0x9E3779B9)
    b = np.uint32(0x9E3779B9)
    c = np.uint32(0)
    pos = 0
    ln = length
    with _quiet(np):
        while ln >= 12:
            k = name[pos : pos + 12]
            a = a + np.uint32(k[0] + (k[1] << 8) + (k[2] << 16) + (k[3] << 24))
            b = b + np.uint32(k[4] + (k[5] << 8) + (k[6] << 16) + (k[7] << 24))
            c = c + np.uint32(k[8] + (k[9] << 8) + (k[10] << 16) + (k[11] << 24))
            a, b, c = _mix(a, b, c, np)
            pos += 12
            ln -= 12
        # last <= 11 bytes; fall-through switch, first byte of c reserved
        # for the length
        c = c + np.uint32(length)
        k = name[pos:]
        if ln >= 11:
            c = c + np.uint32(k[10] << 24)
        if ln >= 10:
            c = c + np.uint32(k[9] << 16)
        if ln >= 9:
            c = c + np.uint32(k[8] << 8)
        if ln >= 8:
            b = b + np.uint32(k[7] << 24)
        if ln >= 7:
            b = b + np.uint32(k[6] << 16)
        if ln >= 6:
            b = b + np.uint32(k[5] << 8)
        if ln >= 5:
            b = b + np.uint32(k[4])
        if ln >= 4:
            a = a + np.uint32(k[3] << 24)
        if ln >= 3:
            a = a + np.uint32(k[2] << 16)
        if ln >= 2:
            a = a + np.uint32(k[1] << 8)
        if ln >= 1:
            a = a + np.uint32(k[0])
        a, b, c = _mix(a, b, c, np)
    return int(c)
