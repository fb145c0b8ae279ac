"""Pallas TPU kernel for GF(2^8) coefficient-matrix multiply.

The SWAR xor network (ops/gf256_swar.py) is the right computation —
~14 VPU ops per input byte, no MXU dependency — but when XLA lowers it
as a graph of full-size jnp ops it materializes the doubled-power
intermediates to HBM.  This module runs the SAME network inside a
single Pallas kernel: each grid step DMAs one (k, S, 128) tile of the
packed u32 planes into VMEM, evaluates the whole network on-register,
and writes the (R, S, 128) output tile — HBM traffic is exactly
read-k + write-R planes, the roofline the engine is supposed to hit.

Layout: bytes are packed four-per-u32 word (the SWAR invariant), and
words are shaped (T, 128) per plane so every VPU op sees native
(sublane, lane) tiles — a 1-D (W,) layout measured ~2x slower.

The kernel takes a u32 seed scalar XOR'd into every loaded word.  The
product path passes 0 (a no-op on the data); benchmarks pass the
iteration index so consecutive in-jit iterations cannot be hoisted as
loop-invariant (benches loop inside one jit so a per-dispatch host
round trip is not what they time).

Reference role: the per-arch SIMD encode kernels behind
``ec_encode_data`` (src/erasure-code/isa/ErasureCodeIsa.cc:128) and
gf-complete's SSSE3/AVX regions (src/erasure-code/jerasure/
CMakeLists.txt:12-38).
"""

from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.tpu.devwatch import (instrumented_jit,
                                   instrumented_pallas_call)

LANES = 128
DEFAULT_TILE = 512  # sublane rows per grid step: (k, 512, 128) u32 = 2 MiB for k=8


def _net_matrix_meta(matrix: np.ndarray):
    mat = [[int(c) for c in row] for row in matrix]
    R, k = matrix.shape
    need_bits = [0] * k
    for row in mat:
        for j, c in enumerate(row):
            need_bits[j] |= c
    max_bit = [nb.bit_length() for nb in need_bits]
    return mat, R, k, max_bit


def _double_word(p, mul_shift: bool):
    """Multiply every packed byte by x in GF(2^8) (poly 0x11d).

    mul_shift=True replaces the u32 multiply `carry * 0x1D` with the
    equivalent shift/xor chain (0x1D = bits 0,2,3,4) — on some VPU
    generations integer multiply is multi-cycle, so both forms are
    autotune candidates.
    """
    low7 = jnp.uint32(0x7F7F7F7F)
    ones = jnp.uint32(0x01010101)
    carry = (p >> 7) & ones
    if mul_shift:
        red = carry ^ (carry << 2) ^ (carry << 3) ^ (carry << 4)
    else:
        red = carry * jnp.uint32(0x1D)
    return ((p & low7) << 1) ^ red


def _make_kernel(matrix: np.ndarray, mul_shift: bool = False) -> Callable:
    """Kernel over refs: (seed u32[1] SMEM, x u32[k,S,128], o u32[R,S,128])."""
    mat, R, k, max_bit = _net_matrix_meta(matrix)

    def kernel(seed_ref, x_ref, o_ref):
        seed = seed_ref[0]
        acc = [None] * R
        for j in range(k):
            p = x_ref[j] ^ seed
            for b in range(max(max_bit[j], 1)):
                if b > 0:
                    p = _double_word(p, mul_shift)
                for i in range(R):
                    if (mat[i][j] >> b) & 1:
                        acc[i] = p if acc[i] is None else acc[i] ^ p
        zero = jnp.zeros_like(x_ref[0])
        for i in range(R):
            o_ref[i] = acc[i] if acc[i] is not None else zero

    return kernel


@functools.lru_cache(maxsize=64)
def _compiled(matrix_bytes: bytes, shape: Tuple[int, int], tile: int,
              interpret: bool, mul_shift: bool = False,
              donate: bool = False, dimsem: str = "arbitrary") -> Callable:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    matrix = np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(shape)
    R, k = shape
    kernel = _make_kernel(matrix, mul_shift)
    # donation: only a square code (R == k, e.g. a decode recovery
    # matrix) has an output the same shape as the input, so only then
    # can the input buffer be aliased (the StripeBatchQueue decode path
    # that keeps live HBM ~one batch deep)
    alias = {1: 0} if (donate and R == k and not interpret) else {}

    @jax.named_scope("ec.encode")
    def run(words3: jax.Array, seed: jax.Array) -> jax.Array:
        kk, T, L = words3.shape
        assert kk == k and L == LANES and T % tile == 0, (kk, T, L)
        return instrumented_pallas_call(
            kernel, family="gf256_pallas", name="ec_encode",
            out_shape=jax.ShapeDtypeStruct((R, T, LANES), jnp.uint32),
            grid=(T // tile,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((k, tile, LANES), lambda i: (0, i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((R, tile, LANES), lambda i: (0, i, 0),
                                   memory_space=pltpu.VMEM),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=(dimsem,)),
            input_output_aliases=alias,
            interpret=interpret,
        )(seed, words3)

    return (instrumented_jit(run, family="gf256_pallas",
                             donate_argnums=(0,)) if alias
            else instrumented_jit(run, family="gf256_pallas"))


def _words_operand(words3):
    """Host words go into the jitted call as they are, so that their
    upload is part of the call's `dev.dispatch` span (a `jnp.asarray`
    ahead of the call is a transfer of its own, outside it); anything
    else becomes a u32 device array as before."""
    if isinstance(words3, np.ndarray):
        return words3.astype(np.uint32, copy=False)
    # an h2d upload or a no-op, not a fetch back to host
    # cephlint: disable=no-d2h-on-hot-path
    return jnp.asarray(words3, dtype=jnp.uint32)


def encode_planes(matrix: np.ndarray, words3, seed=None, *,
                  tile: int = DEFAULT_TILE, interpret: bool | None = None,
                  mul_shift: bool = False, donate: bool = False,
                  dimsem: str = "arbitrary"):
    """Apply GF(2^8) matrix (R x k) to packed planes u32 [k, T, 128].

    T must be a multiple of `tile` (callers control the batch shape; the
    StripeBatchQueue and the bench both produce power-of-two tiles).
    Returns u32 [R, T, 128].  `interpret` defaults to True off-TPU so
    the same code path is testable on the CPU backend.  donate=True
    hands the input buffer to XLA when the code is square (R == k);
    the caller must not reuse it afterwards.
    """
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if seed is None:
        seed = jnp.zeros((1,), jnp.uint32)
    # cephlint: disable=no-d2h-on-hot-path — `matrix` is the k x m
    # COEFFICIENT matrix (metadata-scale, host numpy by construction
    # two lines up); tobytes() keys the jit cache, no device buffer
    # is touched
    fn = _compiled(matrix.tobytes(), matrix.shape, tile, interpret,
                   mul_shift, donate, dimsem)
    return fn(_words_operand(words3), seed)


# ---------------------------------------------------------------------------
# The same network with the matrix as an OPERAND: one program per
# (R, k, T) serves every coefficient matrix.  A decode's recovery matrix
# depends on which shards survived, so a baked-in matrix is one compile
# per survivor signature x width, in line on the queue's one worker
# (~1 s each on the v5e: 16 degraded reads in flight compiled 69 kernels
# and ran the client into its 30 s op timeout, PR 22).  Here bit b of
# coefficient (i, j) arrives as an all-ones / all-zeros u32 mask in
# SMEM and selects the doubled power with an AND: ~2x the VPU work of
# the baked network, zero compiles after the width is warm.
# ---------------------------------------------------------------------------

def matrix_masks(matrix: np.ndarray) -> np.ndarray:
    """(R x k) GF(2^8) coefficients -> u32 [R*k*8] select masks,
    index (i*k + j)*8 + b: 0xFFFFFFFF where bit b of matrix[i, j] is
    set, else 0."""
    m = np.ascontiguousarray(matrix, dtype=np.uint8)
    bits = (m[:, :, None] >> np.arange(8, dtype=np.uint8)) & 1
    return (bits.astype(np.uint32) * np.uint32(0xFFFFFFFF)).reshape(-1)


def masked_network(mask, planes, R: int, k: int):
    """out[i] = XOR_{j,b} (planes[j] * x^b) & mask((i*k + j)*8 + b).

    `mask(idx)` yields one u32 scalar (an SMEM ref read inside the
    Pallas kernel, an array element under plain jit), `planes` is a
    list of k packed-word arrays.  Returns the R output planes."""
    acc = [None] * R
    for j in range(k):
        p = planes[j]
        for b in range(8):
            if b > 0:
                p = _double_word(p, False)
            for i in range(R):
                t = p & mask((i * k + j) * 8 + b)
                acc[i] = t if acc[i] is None else acc[i] ^ t
    return acc


@functools.lru_cache(maxsize=16)
def _compiled_operand(R: int, k: int, tile: int, interpret: bool,
                      donate: bool = False) -> Callable:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(m_ref, x_ref, o_ref):
        acc = masked_network(lambda idx: m_ref[idx],
                             [x_ref[j] for j in range(k)], R, k)
        for i in range(R):
            o_ref[i] = acc[i]

    # a square code's output aliases its input (see _compiled)
    alias = {1: 0} if (donate and R == k and not interpret) else {}

    @jax.named_scope("ec.decode")
    def run(masks: jax.Array, words3: jax.Array) -> jax.Array:
        kk, T, L = words3.shape
        assert kk == k and L == LANES and T % tile == 0, (kk, T, L)
        return instrumented_pallas_call(
            kernel, family="gf256_pallas", name="ec_decode",
            out_shape=jax.ShapeDtypeStruct((R, T, LANES), jnp.uint32),
            grid=(T // tile,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((k, tile, LANES), lambda i: (0, i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((R, tile, LANES), lambda i: (0, i, 0),
                                   memory_space=pltpu.VMEM),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            input_output_aliases=alias,
            interpret=interpret,
        )(masks, words3)

    return (instrumented_jit(run, family="gf256_pallas",
                             donate_argnums=(1,)) if alias
            else instrumented_jit(run, family="gf256_pallas"))


def apply_planes(matrix: np.ndarray, words3, *,
                 tile: int = DEFAULT_TILE, interpret: bool | None = None,
                 donate: bool = False):
    """encode_planes with the matrix passed as data: u32 [k, T, 128]
    -> u32 [R, T, 128], bit-identical to encode_planes(matrix, ...),
    one compiled program per (R, k, T) whatever the coefficients."""
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    R, k = matrix.shape
    fn = _compiled_operand(R, k, tile, interpret, donate)
    return fn(matrix_masks(matrix), _words_operand(words3))


def pack_planes(x: np.ndarray) -> np.ndarray:
    """Host helper: uint8 [k, n] -> u32 [k, T, 128] (n % 512 == 0)."""
    k, n = x.shape
    assert n % (4 * LANES) == 0, n
    return np.ascontiguousarray(x).view("<u4").reshape(k, -1, LANES)


def unpack_planes(words3: np.ndarray) -> np.ndarray:
    """Host helper: u32 [R, T, 128] -> uint8 [R, n]."""
    # benches and tests hand this planes they fetched to compare
    # cephlint: disable=no-d2h-on-hot-path
    w = np.ascontiguousarray(np.asarray(words3), dtype=np.uint32)
    return w.view(np.uint8).reshape(w.shape[0], -1)


# ---------------------------------------------------------------------------
# Interleaved layout: planes stored (T, k, 128) so each grid step's
# input block is ONE contiguous DMA (the (k, T, 128) layout issues k
# strided slab reads per step).  Same network, same bytes.
# ---------------------------------------------------------------------------

def _make_kernel_interleaved(matrix: np.ndarray,
                             mul_shift: bool = False) -> Callable:
    """Kernel over refs: (seed u32[1], x u32[S,k,128], o u32[S,R,128])."""
    mat, R, k, max_bit = _net_matrix_meta(matrix)

    def kernel(seed_ref, x_ref, o_ref):
        seed = seed_ref[0]
        acc = [None] * R
        for j in range(k):
            p = x_ref[:, j, :] ^ seed
            for b in range(max(max_bit[j], 1)):
                if b > 0:
                    p = _double_word(p, mul_shift)
                for i in range(R):
                    if (mat[i][j] >> b) & 1:
                        acc[i] = p if acc[i] is None else acc[i] ^ p
        zero = jnp.zeros_like(x_ref[:, 0, :])
        for i in range(R):
            o_ref[:, i, :] = acc[i] if acc[i] is not None else zero

    return kernel


@functools.lru_cache(maxsize=64)
def _compiled_interleaved(matrix_bytes: bytes, shape: Tuple[int, int],
                          tile: int, interpret: bool,
                          mul_shift: bool = False) -> Callable:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    matrix = np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(shape)
    R, k = shape
    kernel = _make_kernel_interleaved(matrix, mul_shift)

    @functools.partial(instrumented_jit, family="gf256_pallas")
    @jax.named_scope("ec.encode")
    def run(words3: jax.Array, seed: jax.Array) -> jax.Array:
        T, kk, L = words3.shape
        assert kk == k and L == LANES and T % tile == 0, (T, kk, L)
        return instrumented_pallas_call(
            kernel, family="gf256_pallas", name="ec_encode_interleaved",
            out_shape=jax.ShapeDtypeStruct((T, R, LANES), jnp.uint32),
            grid=(T // tile,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((tile, k, LANES), lambda i: (i, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((tile, R, LANES), lambda i: (i, 0, 0),
                                   memory_space=pltpu.VMEM),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(seed, words3)

    return run


def encode_planes_interleaved(matrix: np.ndarray, words3, seed=None, *,
                              tile: int = DEFAULT_TILE,
                              interpret: bool | None = None,
                              mul_shift: bool = False):
    """Apply GF(2^8) matrix (R x k) to interleaved planes u32
    [T, k, 128] -> u32 [T, R, 128].  T must be a multiple of `tile`."""
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if seed is None:
        seed = jnp.zeros((1,), jnp.uint32)
    fn = _compiled_interleaved(matrix.tobytes(), matrix.shape, tile,
                               interpret, mul_shift)
    return fn(_words_operand(words3), seed)
