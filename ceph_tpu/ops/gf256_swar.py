"""GF(2^8) coefficient-matrix multiply as a SWAR xor network — the fast
erasure-code engine.

The round-1 engine lowered RS codes to an int8 bit-plane matmul on the
MXU.  Profiling showed the kernel was VPU-bound on the bit
extraction/packing around the matmul (each byte occupies a whole 32-bit
lane during extraction), capping throughput far below HBM.  This engine
keeps the bytes PACKED — four per 32-bit lane — and evaluates the code
as a fixed xor/shift network (SWAR: SIMD-within-a-register):

- doubling a packed word (multiply every byte by x in GF(2^8), poly
  0x11d): ``((v << 1) & 0xfefefefe) ^ (((v >> 7) & 0x01010101) * 0x1d)``
- multiply by a constant c: xor of the doubled powers selected by c's
  set bits (the powers are shared across all m output rows)
- the whole (m x k) coefficient matrix unrolls, at trace time, into
  ~`7k` doublings + `popcount(matrix)` xors per word — ~14 VPU ops per
  input byte, an order of magnitude less VPU work than bit-plane
  extraction, and no MXU dependency at all.

This mirrors what the reference's SIMD backends do per-architecture
(gf-complete's CLMUL/SSSE3 regions, src/erasure-code/jerasure/
CMakeLists.txt:12-38; ISA-L's asm kernels behind ec_encode_data,
src/erasure-code/isa/ErasureCodeIsa.cc:128) — but expressed once in
jnp, fused by XLA, and identical on TPU and CPU.

Scope: any code expressed as a GF(2^8) COEFFICIENT matrix (reed_sol,
isa vandermonde/cauchy, lrc, shec, clay).  Bit-matrix techniques
(liberation family) keep the general GF(2) engine in ops.gf2_matmul.
"""

from __future__ import annotations

import functools
import os
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.tpu.devwatch import fetch, instrumented_jit

_native_rs = None  # None = unresolved, False = unavailable


def _native_rs_encode():
    """Resolve the native SIMD encode once per process (the resolver
    may shell out to make when the lib is unbuilt — never per call)."""
    global _native_rs
    if _native_rs is None:
        try:
            from ceph_tpu import _native

            _native.lib()  # force build/load now, not per call
            _native_rs = _native.rs_encode_simd
        except Exception:  # pragma: no cover — no native lib built
            _native_rs = False
    return _native_rs or None

_LOW7 = np.uint32(0x7F7F7F7F)
_HI = np.uint32(0x80808080)
_ONES = np.uint32(0x01010101)
_RED = np.uint32(0x1D)  # poly 0x11d reduction byte


def _double(v: jax.Array) -> jax.Array:
    """Multiply every packed byte by x (i.e. 2) in GF(2^8)."""
    carry = (v >> 7) & _ONES
    return ((v & _LOW7) << 1) ^ (carry * _RED)


def _build_network(matrix: np.ndarray) -> Callable[[jax.Array], jax.Array]:
    """Unroll (R x k) GF(2^8) coefficients into a packed-word function.

    Returns f(words: u32 [k, W]) -> u32 [R, W].
    """
    R, k = matrix.shape
    mat = [[int(c) for c in row] for row in matrix]
    # which powers of two each column actually needs (skip dead doublings)
    need_bits = [0] * k
    for row in mat:
        for j, c in enumerate(row):
            need_bits[j] |= c
    max_bit = [nb.bit_length() for nb in need_bits]

    @jax.named_scope("ec.encode")
    def apply(words: jax.Array) -> jax.Array:
        acc = [None] * R
        for j in range(k):
            p = words[j]
            for b in range(max(max_bit[j], 1)):
                if b > 0:
                    p = _double(p)
                for i in range(R):
                    if (mat[i][j] >> b) & 1:
                        acc[i] = p if acc[i] is None else acc[i] ^ p
        zero = jnp.zeros_like(words[0])
        return jnp.stack([a if a is not None else zero for a in acc])

    return apply


_cache: Dict[Tuple, Callable] = {}


def _compiled_words(matrix: np.ndarray,
                    family: str = "gf256_swar") -> Callable:
    """jit of the network over PRE-PACKED u32 words [k, W] -> [R, W]
    (packing is a free numpy view on the host; no device-side
    bitcasts — see gf_matmul_bytes)."""
    # cephlint: disable=no-d2h-on-hot-path — coefficient-matrix cache
    # key: `matrix` is metadata-scale host numpy, not a device buffer
    key = (matrix.tobytes(), matrix.shape, family)
    fn = _cache.get(key)
    if fn is None:
        # the caller's devwatch family (default "gf256_swar") tags the
        # compile so shape-bucket discipline and the steady guard
        # attribute it to the right kernel class (clay's coupled-layer
        # matmuls run under "gf256_clay")
        fn = _cache[key] = instrumented_jit(
            _build_network(matrix), family=family)
    return fn


def _compiled_words_operand(R: int, k: int, family: str) -> Callable:
    """The same network with the matrix as DATA (select masks, see
    gf256_pallas.masked_network): f(masks u32[R*k*8], words u32[k, W])
    -> u32 [R, W], one program per width for every matrix."""
    key = ("operand", R, k, family)
    fn = _cache.get(key)
    if fn is None:
        from ceph_tpu.ops.gf256_pallas import masked_network

        @jax.named_scope("ec.decode")
        def run(masks: jax.Array, words: jax.Array) -> jax.Array:
            return jnp.stack(masked_network(
                lambda idx: masks[idx], [words[j] for j in range(k)],
                R, k))

        fn = _cache[key] = instrumented_jit(run, family=family)
    return fn


# sublane rows per Pallas grid step.  Measured against the v5e's
# compiler: tile 1024 is refused (RESOURCE_EXHAUSTED, scoped VMEM) for
# the k=8 planes, 512 compiles for every (R, k) up to 16 x 16
_PALLAS_MAX_TILE = 512


def pallas_tile(T: int) -> Tuple[int, int]:
    """Tile choice for a (k, T, 128) Pallas encode: ``(tile, T_pad)``.

    Mosaic accepts a block whose sublane dim is a multiple of 8 or the
    whole axis, nothing else.  So: the largest multiple of 8 (at most
    _PALLAS_MAX_TILE) dividing T; else the whole axis when it fits in
    one tile; else T rounds up to a multiple of 8 (``T_pad > T``: the
    caller zero-pads the planes and slices the result)."""
    if T > _PALLAS_MAX_TILE:
        T += -T % 8
    for t in range(min(_PALLAS_MAX_TILE, T) // 8 * 8, 7, -8):
        if T % t == 0:
            return t, T
    return T, T


def _engine(n: int) -> str:
    """Which engine serves a width-n call — a choice by backend and
    shape, never by a failure: "pallas" on a TPU when the width is a
    whole number of 128-lane word rows (the VMEM-tiled kernel; the XLA
    graph materializes the network's intermediates to HBM), "native"
    on the CPU backend (AVX2 split-nibble kernel, csrc/gf256_simd.cc:
    beats the jit'd network at every size there, and a ctypes call is
    ~2 us against ~25 us of jax dispatch), else "xla".
    CEPH_TPU_FORCE_PALLAS=1 selects the Pallas route off-TPU
    (interpreter), so the CPU suite runs the wrapper a chip runs."""
    backend = jax.default_backend()
    if ((backend == "tpu"
         or os.environ.get("CEPH_TPU_FORCE_PALLAS") == "1")
            and n % 512 == 0):
        return "pallas"
    if backend == "cpu" and _native_rs_encode() is not None:
        return "native"
    return "xla"


def gf_matmul_bytes(matrix: np.ndarray, x, donate: bool = False,
                    family: str = "gf256_swar",
                    operand: bool = False) -> np.ndarray:
    """Apply a GF(2^8) coefficient matrix (R x k) to byte rows [k, n]:
    host uint8 in, host uint8 [R, n] out (what the queue, the codecs
    and clay all send and consume).

    The bytes are packed four-per-u32 word ONCE, on the host, ahead of
    the engine choice: a numpy view reinterprets for free, while the
    device-side u8 relayout is what XLA-CPU lowers slower than the
    whole xor network and what the v5e's compiler took 76-160 s to
    compile in line at 256Ki-512Ki columns (PR 22, first chip runs).
    Every device program here therefore sees u32 words only.

    `operand=True` passes the matrix to the device as data instead of
    baking it into the program: for matrices that vary per call (a
    decode's recovery matrix, one per survivor signature) so that one
    compiled program per width serves them all.  The fixed coding
    matrix of an encode stays baked (half the VPU work).
    `donate` lets a square Pallas call alias its freshly uploaded
    input planes (live HBM stays ~one batch deep); the caller's host
    buffer is never consumed.
    No fallback engine: a kernel the chip's compiler refuses raises
    here, naming the shape."""
    # cephlint: disable=no-d2h-on-hot-path — coefficient matrix:
    # metadata-scale, host-built; no payload crosses here
    matrix = np.asarray(matrix, dtype=np.uint8)
    # host bytes by contract (see above)
    # cephlint: disable=no-d2h-on-hot-path
    x = np.ascontiguousarray(x, dtype=np.uint8)
    R, k = matrix.shape
    n = x.shape[1]
    engine = _engine(n)
    if engine == "native":
        return _native_rs_encode()(matrix, x)
    pad = (-n) % 4
    if pad:
        x = np.pad(x, ((0, 0), (0, pad)))
    words = x.view(np.uint32)
    if engine == "pallas":
        # same bytes as the other engines, pinned equal by
        # tests/test_gf256_pallas.py through this wrapper
        from ceph_tpu.ops import gf256_pallas

        words3 = words.reshape(k, -1, gf256_pallas.LANES)
        T = words3.shape[1]
        tile, T_pad = pallas_tile(T)
        if T_pad != T:
            words3 = np.pad(words3, ((0, 0), (0, T_pad - T), (0, 0)))
        # interpret=None: real lowering on TPU, interpreter elsewhere
        if operand:
            out3 = gf256_pallas.apply_planes(
                matrix, words3, tile=tile, donate=donate)
        else:
            out3 = gf256_pallas.encode_planes(
                matrix, words3, tile=tile, donate=donate)
        # the fetch every consumer makes anyway
        out32 = fetch(out3)[:, :T].reshape(R, -1)
    elif operand:
        from ceph_tpu.ops import gf256_pallas

        out32 = fetch(_compiled_words_operand(R, k, family)(
            gf256_pallas.matrix_masks(matrix), words))
    else:
        out32 = fetch(_compiled_words(matrix, family)(words))
    out = np.ascontiguousarray(out32).view(np.uint8)
    return out[:, :n] if pad else out
