"""crc32c on device, batched over stripe planes — fused with encode.

The reference computes ECUtil::HashInfo per-shard crcs on the CPU from
host bufferlists (src/osd/ECUtil.h:101-122).  With payloads device-
resident, a host crc would force a d2h fetch of every chunk — the
exact transfer the staging pipeline removes — so the crc runs ON the
device, in the same coalesced batch as the GF matmul, and only the
4-byte digests cross back (metadata, not payload).

Formulation: CRC-32C is a GF(2) polynomial remainder; the classic
table method is a per-byte affine update ``c' = T[(c ^ b) & 0xff] ^
(c >> 8)``.  Batched the TPU way: every (job, shard) chunk of the
coalesced batch becomes one ROW of a [rows, cols] lane matrix, and
slicing-by-8 tables (T0..T7, 256-entry u32 gathers) consume 8 bytes of
EVERY row per ``fori_loop`` step — a whole [jobs x (k+m)] batch crcs
in ``cols/8`` vectorized steps.  Per-row length masking handles the
pow2 padding and non-aligned tails; per-row init values chain running
crcs.  (No per-row offsets inside the kernel: a vmapped
``dynamic_slice`` at per-lane offsets lowers to an O(batch) gather per
step on CPU XLA — measured quadratic; the row layout keeps each step
O(rows).)

Bit-exactness against ``core.crc.crc32c`` (the native slicing-by-8
kernel) is asserted in tier-1 (tests/test_device_datapath.py) across
lengths 0..4KiB including ragged tails and chained calls.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ceph_tpu.tpu.devwatch import fetch, instrumented_jit

_POLY = np.uint32(0x82F63B78)


def _make_tables(n: int = 8) -> np.ndarray:
    """Slicing-by-N tables: T[0] is the classic byte table; T[k+1][i]
    advances T[k][i] one more zero byte."""
    t0 = np.arange(256, dtype=np.uint32)
    for _ in range(8):
        t0 = np.where(t0 & 1, (t0 >> 1) ^ _POLY, t0 >> 1)
    out = np.empty((n, 256), dtype=np.uint32)
    out[0] = t0
    for k in range(1, n):
        prev = out[k - 1]
        out[k] = t0[prev & 0xFF] ^ (prev >> np.uint32(8))
    return out


_TABLES = _make_tables()


def _round_up_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


@functools.lru_cache(maxsize=64)
def _rows_kernel(R: int, C: int):
    """Compiled crc pass over a [R, C] row batch with per-row
    (length, init).  Cached per shape: callers pad both axes to
    pow2, so the compile set stays small (same discipline as the
    encode matmul shapes)."""
    # h2d upload of the constant slicing tables (8 KiB), once per
    # compiled shape  # cephlint: disable=no-d2h-on-hot-path
    tables = jnp.asarray(_TABLES)
    W = C // 8

    @jax.named_scope("ec.crc32c")
    def kernel(rows, lens, inits):
        c0 = inits ^ jnp.uint32(0xFFFFFFFF)
        nwords = lens // 8

        def word_step(w, c):
            blk = lax.dynamic_slice_in_dim(
                rows, 8 * w, 8, axis=1).astype(jnp.uint32)
            x = (c ^ (blk[:, 0] | (blk[:, 1] << 8)
                      | (blk[:, 2] << 16) | (blk[:, 3] << 24)))
            nc = (tables[7][x & 0xFF]
                  ^ tables[6][(x >> 8) & 0xFF]
                  ^ tables[5][(x >> 16) & 0xFF]
                  ^ tables[4][(x >> 24) & 0xFF]
                  ^ tables[3][blk[:, 4]]
                  ^ tables[2][blk[:, 5]]
                  ^ tables[1][blk[:, 6]]
                  ^ tables[0][blk[:, 7]])
            return jnp.where(w < nwords, nc, c)

        c = lax.fori_loop(0, W, word_step, c0)

        def tail_step(t, c):
            pos = jnp.minimum(8 * nwords + t, C - 1)
            b = jnp.take_along_axis(
                rows, pos[:, None], axis=1)[:, 0].astype(jnp.uint32)
            nc = tables[0][(c ^ b) & 0xFF] ^ (c >> 8)
            return jnp.where(8 * nwords + t < lens, nc, c)

        c = lax.fori_loop(0, 8, tail_step, c)
        return c ^ jnp.uint32(0xFFFFFFFF)

    return instrumented_jit(kernel, family="crc32c_device")


def crc32c_lanes(rows: np.ndarray, lens, inits=None) -> np.ndarray:
    """crc32c of ``rows[i, :lens[i]]`` for every row, in one batched
    device pass.  ``rows`` uint8 [R, C]; returns u32 [R]."""
    R, C = int(rows.shape[0]), int(rows.shape[1])
    # cephlint: disable=no-d2h-on-hot-path — per-lane lengths/inits:
    # u32 metadata arrays, not payload
    lens = np.asarray(lens, dtype=np.int32)
    inits = (np.zeros(R, dtype=np.uint32) if inits is None
             else np.asarray(inits, dtype=np.uint32))  # cephlint: disable=no-d2h-on-hot-path — metadata
    if R == 0:
        return np.empty(0, dtype=np.uint32)
    if C % 8:
        rows = np.concatenate(
            [rows, np.zeros((R, 8 - C % 8), dtype=np.uint8)], axis=1)
        C = int(rows.shape[1])
    # the digest fetch: 4 bytes per lane of METADATA crossing back, the
    # point of the fused crc
    return fetch(_rows_kernel(R, C)(rows, lens, inits))


def crc32c_rows(full: np.ndarray, offs, lens, inits=None) -> np.ndarray:
    """Per-(job, shard) running crc32c over a coalesced plane batch.

    ``full``: uint8 [S, P] (data planes stacked over coding planes, P
    the padded batch width).  ``offs``/``lens``: J per-job column
    extents within the batch.  Returns u32 [J, S]: the crc of shard
    ``s`` of job ``j`` — exactly what each shard's HashInfo wants,
    fetched as metadata (4 bytes/shard) instead of payload.

    Two steps, which the stripe batch queue takes one by one so that
    each is a span of its own: the host relayout (`rows_layout`) and
    the device pass over it (`crc32c_lanes`)."""
    J, S = len(offs), int(full.shape[0])
    if J == 0:
        return np.empty((0, S), dtype=np.uint32)
    rows, rlens, rinits = rows_layout(full, offs, lens, inits)
    return crc32c_lanes(rows, rlens, rinits).reshape(-1, S)[:J]


def rows_layout(full: np.ndarray, offs, lens, inits=None):
    """The row relayout of `crc32c_rows`: -> (rows u8 [Jp*S, C], lens
    i32, inits u32), job-major, both axes padded to pow2 so the compile
    set stays bounded.  A host move (on CPU rigs inside the already-
    counted upload — no extra crossing)."""
    # cephlint: disable=no-d2h-on-hot-path — column extents: metadata
    offs = np.asarray(offs, dtype=np.int64)
    lens = np.asarray(lens, dtype=np.int64)  # cephlint: disable=no-d2h-on-hot-path — metadata
    J, S = len(offs), int(full.shape[0])
    if inits is None:
        inits = np.zeros(J, dtype=np.uint32)
    else:
        inits = np.asarray(inits, dtype=np.uint32)  # cephlint: disable=no-d2h-on-hot-path — metadata
    Jp = _round_up_pow2(J)
    C = max(64, _round_up_pow2(int(lens.max(initial=1))))
    rows = np.zeros((Jp * S, C), dtype=np.uint8)
    rlens = np.zeros(Jp * S, dtype=np.int32)
    rinits = np.zeros(Jp * S, dtype=np.uint32)
    for j in range(J):
        o, ln = int(offs[j]), int(lens[j])
        rows[j * S:(j + 1) * S, :ln] = full[:, o:o + ln]
        rlens[j * S:(j + 1) * S] = ln
        rinits[j * S:(j + 1) * S] = inits[j]
    return rows, rlens, rinits


# pow2-bucketed single-buffer entry (tests, tools, ad-hoc checksums)
_PAD_MIN = 64


def crc32c_dev(data, crc: int = 0) -> int:
    """Device crc32c of one buffer; chain by passing the prior value.
    Pads to a pow2 length bucket so ad-hoc lengths reuse compiles."""
    if isinstance(data, np.ndarray):
        arr = data.reshape(-1).view(np.uint8)
    else:
        arr = np.frombuffer(data, dtype=np.uint8)
    n = arr.size
    C = max(_PAD_MIN, _round_up_pow2(n))
    rows = np.zeros((1, C), dtype=np.uint8)
    rows[0, :n] = arr
    return int(crc32c_lanes(rows, [n], [crc])[0])
