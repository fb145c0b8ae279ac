"""Plain references for the benchmark's `correct`: numpy only, nothing of
`ceph_tpu` is imported and nothing the program made is taken.

- GF(2^8) Reed-Solomon of the upstream `isa` plugin (`reed_sol_van`:
  ISA-L `gf_gen_rs_matrix`, polynomial 0x11d), encode and decode;
- crc32c (Castagnoli, reflected, init and final xor 0xffffffff) over
  many rows at once;
- `crush_do_rule` for `take root; chooseleaf firstn N type host; emit` on
  a two-level straw2 map under the jewel tunables (src/crush/mapper.c);
- `RefStore`: the object store's guarantees in a dict, one of which the
  control (control.py) breaks.
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# -- GF(2^8), polynomial x^8+x^4+x^3+x^2+1 (0x11d), generator 2 -------------
_EXP = np.zeros(512, dtype=np.uint8)
_LOG = np.zeros(256, dtype=np.int32)
_x = 1
for _i in range(255):
    _EXP[_i] = _x
    _LOG[_x] = _i
    _x <<= 1
    if _x & 0x100:
        _x ^= 0x11D
_EXP[255:510] = _EXP[:255]


def gf_mul(a: int, b: int) -> int:
    return 0 if a == 0 or b == 0 else int(_EXP[_LOG[a] + _LOG[b]])


def gf_inv(a: int) -> int:
    return int(_EXP[255 - _LOG[a]])


def isa_rs_matrix(k: int, m: int) -> np.ndarray:
    """ISA-L gf_gen_rs_matrix: identity over the coding rows
    [gen^0, gen^1, ...] with gen = 1, 2, 4, ... -> uint8 [k+m, k]."""
    a = np.zeros((k + m, k), dtype=np.uint8)
    a[:k] = np.eye(k, dtype=np.uint8)
    gen = 1
    for i in range(k, k + m):
        p = 1
        for j in range(k):
            a[i, j] = p
            p = gf_mul(p, gen)
        gen = gf_mul(gen, 2)
    return a


def gf_matmul(mat: np.ndarray, planes: np.ndarray) -> np.ndarray:
    """uint8 [r, k] x uint8 [k, w] -> uint8 [r, w] over GF(2^8)."""
    out = np.zeros((mat.shape[0], planes.shape[1]), dtype=np.uint8)
    logp = _LOG[planes]
    nz = planes != 0
    for r in range(mat.shape[0]):
        for j in range(mat.shape[1]):
            c = int(mat[r, j])
            if c:
                out[r] ^= np.where(nz[j], _EXP[logp[j] + _LOG[c]], 0
                                   ).astype(np.uint8)
    return out


def gf_invert(mat: np.ndarray) -> np.ndarray:
    """Gauss-Jordan inverse of a square uint8 matrix over GF(2^8)."""
    n = mat.shape[0]
    a = [[int(v) for v in row] + [int(i == r) for i in range(n)]
         for r, row in enumerate(mat)]
    for c in range(n):
        p = next(r for r in range(c, n) if a[r][c])
        a[c], a[p] = a[p], a[c]
        inv = gf_inv(a[c][c])
        a[c] = [gf_mul(v, inv) for v in a[c]]
        for r in range(n):
            if r != c and a[r][c]:
                f = a[r][c]
                a[r] = [v ^ gf_mul(f, w) for v, w in zip(a[r], a[c])]
    return np.array([row[n:] for row in a], dtype=np.uint8)


UNIT = 4096   # osd_pool_erasure_code_stripe_unit, upstream's default


def split(payload: bytes, k: int) -> np.ndarray:
    """The pool's striping (ECUtil.h stripe_info_t): logical bytes
    [s*k*UNIT + i*UNIT, +UNIT) live at offset s*UNIT of shard i
    -> uint8 [k, S/k].  S is a whole number of stripes here."""
    return np.frombuffer(payload, dtype=np.uint8).reshape(
        -1, k, UNIT).transpose(1, 0, 2).reshape(k, -1)


def join(planes: np.ndarray) -> bytes:
    """split's inverse: data planes uint8 [k, S/k] -> the object."""
    k = planes.shape[0]
    return planes.reshape(k, -1, UNIT).transpose(1, 0, 2).tobytes()


def rs_shards(payload: bytes, k: int, m: int) -> np.ndarray:
    """All k+m shards of one object -> uint8 [k+m, S/k]."""
    data = split(payload, k)
    return np.concatenate([data, gf_matmul(isa_rs_matrix(k, m)[k:], data)])


def rs_decode(shards: dict, k: int, m: int) -> bytes:
    """{shard id: uint8 [w]} with >= k entries -> the object's bytes."""
    ids = sorted(shards)[:k]
    if ids == list(range(k)):   # every data shard is there: no decode
        return join(np.stack([shards[i] for i in ids]))
    rec = gf_invert(isa_rs_matrix(k, m)[ids])
    return join(gf_matmul(rec, np.stack([shards[i] for i in ids])))


# -- crc32c -------------------------------------------------------------------
_CRC = np.zeros(256, dtype=np.uint32)
for _i in range(256):
    _c = _i
    for _ in range(8):
        _c = (_c >> 1) ^ (0x82F63B78 if _c & 1 else 0)
    _CRC[_i] = _c


def crc32c_rows(rows: np.ndarray) -> np.ndarray:
    """crc32c of every row of uint8 [n, w] -> uint32 [n] (all rows step
    through the byte-wise table together)."""
    c = np.full(rows.shape[0], 0xFFFFFFFF, dtype=np.uint32)
    for col in np.ascontiguousarray(rows.T):
        c = _CRC[(c ^ col) & 0xFF] ^ (c >> 8)
    return c ^ np.uint32(0xFFFFFFFF)


# -- CRUSH ------------------------------------------------------------------
NONE = 0x7FFFFFFF
_M32 = np.uint64(0xFFFFFFFF)


def _mix(a, b, c):
    a = (a - b - c) & _M32; a ^= c >> np.uint64(13)            # noqa: E702
    b = (b - c - a) & _M32; b ^= (a << np.uint64(8)) & _M32    # noqa: E702
    c = (c - a - b) & _M32; c ^= b >> np.uint64(13)            # noqa: E702
    a = (a - b - c) & _M32; a ^= c >> np.uint64(12)            # noqa: E702
    b = (b - c - a) & _M32; b ^= (a << np.uint64(16)) & _M32   # noqa: E702
    c = (c - a - b) & _M32; c ^= b >> np.uint64(5)             # noqa: E702
    a = (a - b - c) & _M32; a ^= c >> np.uint64(3)             # noqa: E702
    b = (b - c - a) & _M32; b ^= (a << np.uint64(10)) & _M32   # noqa: E702
    c = (c - a - b) & _M32; c ^= b >> np.uint64(15)            # noqa: E702
    return a, b, c


def hash32_3(a, b, c):
    """crush_hash32_rjenkins1_3 over uint64 arrays holding u32 values."""
    a, b, c = (np.asarray(v, dtype=np.uint64) & _M32 for v in (a, b, c))
    h = np.uint64(1315423911) ^ a ^ b ^ c
    x = np.full_like(h, 231232)
    y = np.full_like(h, 1232)
    a, b, h = _mix(a, b, h)
    c, x, h = _mix(c, x, h)
    y, a, h = _mix(y, a, h)
    b, x, h = _mix(b, x, h)
    y, c, h = _mix(y, c, h)
    return h


def _ln16() -> np.ndarray:
    """crush_ln(u) - 2^48 for every 16-bit u -> int64 [65536]
    (src/crush/mapper.c crush_ln, tables from crush_ln_table.h)."""
    with open(os.path.join(HERE, "crush_ln_table.json")) as f:
        t = json.load(f)
    rh_lh = np.array(t["RH_LH"], dtype=np.uint64)
    ll = np.array(t["LL"], dtype=np.uint64)
    x = np.arange(0x10000, dtype=np.uint64) + np.uint64(1)
    iexpon = np.full(x.shape, 15, dtype=np.uint64)
    for _ in range(16):  # shift left until bit 15 or 16 is set
        low = (x & np.uint64(0x18000)) == 0
        x = np.where(low, x << np.uint64(1), x)
        iexpon = np.where(low, iexpon - np.uint64(1), iexpon)
    i1 = ((x >> np.uint64(8)) << np.uint64(1)).astype(np.int64)
    rh, lh = rh_lh[i1 - 256], rh_lh[i1 + 1 - 256]
    xl64 = (x * rh) >> np.uint64(48)
    lh = (lh + ll[(xl64 & np.uint64(0xFF)).astype(np.int64)]) >> np.uint64(4)
    return ((iexpon << np.uint64(44)) + lh).astype(np.int64) - (1 << 48)


class CrushRef:
    """`take root; chooseleaf firstn nrep type host; emit` on a root of
    straw2 hosts of straw2 osds, every osd in (weight 0x10000), jewel
    tunables: total tries 50, local tries 0, descend once, vary_r 1,
    stable 1.  With every device in, the leaf pick under a host cannot
    fail, so a placement is: per replica, the first r = rep + ftotal
    whose host is not taken yet; the leaf is drawn with the same r."""

    def __init__(self, cfg: dict, retry: bool = True) -> None:
        self.hosts = np.array(cfg["host_bucket_ids"], dtype=np.int64)
        self.per = cfg["num_osds"] // cfg["hosts"]
        self.w_osd = cfg["osd_weight"]
        self.nrep = cfg["num_rep"]
        self.tries = cfg["tunables"]["choose_total_tries"]
        self.retry = retry  # False: the control (collision retry left out)
        self.ln = _ln16()

    def _straw2(self, x, ids, weight, r):
        """x [n], ids [n, items] (u32 view of the item ids) -> index of
        the largest draw, first on ties."""
        u = hash32_3(x[:, None], ids.astype(np.uint64) & _M32, r[:, None])
        ln = self.ln[(u & np.uint64(0xFFFF)).astype(np.int64)]
        draw = -((-ln) // np.int64(weight))  # div64_s64 truncates
        return np.argmax(draw, axis=1)

    def do_rule(self, xs: np.ndarray) -> np.ndarray:
        xs = np.asarray(xs, dtype=np.int64)
        n = len(xs)
        out = np.full((n, self.nrep), NONE, dtype=np.int32)
        took = np.full((n, self.nrep), -1, dtype=np.int64)
        x = xs.astype(np.uint64) & _M32
        w_host = self.w_osd * self.per
        for rep in range(self.nrep):
            todo = np.arange(n)
            for ftotal in range(self.tries):
                if not len(todo):
                    break
                r = np.full(len(todo), rep + ftotal, dtype=np.uint64)
                hids = np.broadcast_to(self.hosts, (len(todo), len(self.hosts)))
                h = self._straw2(x[todo], hids, w_host, r)
                clash = (took[todo, :rep] == h[:, None]).any(axis=1)
                if not self.retry:
                    clash[:] = False
                ok = todo[~clash]
                osds = h[~clash, None] * self.per + np.arange(self.per)
                leaf = self._straw2(x[ok], osds, self.w_osd, r[~clash])
                took[ok, rep] = h[~clash]
                out[ok, rep] = osds[np.arange(len(ok)), leaf]
                todo = todo[clash]
        # firstn packs the replicas that were placed to the front
        for row in np.nonzero((out == NONE).any(axis=1))[0]:
            got = out[row][out[row] != NONE]
            out[row] = NONE
            out[row, :len(got)] = got
        return out


# -- the object store's guarantees, in a dict ----------------------------------
class RefStore:
    """write_full / read / stored shards of an EC pool, as the
    configuration states them.  `ack_after` < k+m acknowledges a write
    with the last shards uncommitted: the guarantee the control breaks."""

    def __init__(self, k: int, m: int, ack_after: int = 0) -> None:
        self.k, self.m = k, m
        self.ack_after = ack_after or k + m
        self.shards: dict = {}

    def write_full(self, oid: str, data: bytes) -> None:
        sh = rs_shards(data, self.k, self.m)
        self.shards[oid] = {s: sh[s] for s in range(self.ack_after)}

    def read(self, oid: str) -> bytes:
        return rs_decode(self.shards[oid], self.k, self.m)

    def stored(self, oid: str) -> dict:
        """{shard: (bytes, recorded crc32c)} as the OSDs hold them."""
        ids = sorted(self.shards[oid])
        crcs = crc32c_rows(np.stack([self.shards[oid][s] for s in ids]))
        return {s: (self.shards[oid][s].tobytes(), int(c))
                for s, c in zip(ids, crcs)}

    def pg_of(self, oid: str) -> int:
        return 0    # one placement group holds everything

    def close(self) -> None:
        self.shards.clear()
