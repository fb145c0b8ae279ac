"""GF(2) bit-sliced matmul over byte streams — the erasure-code engine.

Every technique in the reference's codec family is linear over GF(2):

- RS over GF(2^8) (jerasure reed_sol_*, isa): each generator coefficient
  c is an 8x8 GF(2) companion block (gf.const_to_bitmatrix), so encode is
  one (8m x 8k) @ (8k x N) binary matmul over bit-planes of the chunk
  bytes (reference semantics: jerasure_matrix_encode,
  src/erasure-code/jerasure/ErasureCodeJerasure.cc:155; ISA-L
  ec_encode_data, src/erasure-code/isa/ErasureCodeIsa.cc:128).
- Bit-matrix codes (cauchy_*, liberation family) are *already* GF(2)
  matrices applied to w packets per chunk — same engine, different
  plane layout.
- Parity/XOR (RAID4-style, the isa single-erasure fast path
  src/erasure-code/isa/ErasureCodeIsa.cc:198) is the all-ones row.

On TPU the binary matmul rides the MXU as int8 x int8 -> int32 with a
mod-2 epilogue.  The Pallas kernel fuses bitplane expansion, matmul,
mod-2 and bit-packing in VMEM so HBM traffic is exactly k bytes read +
m bytes written per stripe column (the bandwidth-optimal schedule).
The jnp path expresses the same computation for CPU tests and as an XLA
fallback.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ceph_tpu.tpu.devwatch import (instrumented_jit,
                                   instrumented_pallas_call)


# ---------------------------------------------------------------------------
# jnp reference path
# ---------------------------------------------------------------------------


def bytes_to_bitplanes(x: jax.Array) -> jax.Array:
    """uint8 [k, n] -> int8 bitplanes [8k, n]; row j*8+b = bit b of row j."""
    k, n = x.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (x[:, None, :] >> shifts[None, :, None]) & jnp.uint8(1)
    return bits.reshape(k * 8, n).astype(jnp.int8)


def bitplanes_to_bytes(planes: jax.Array) -> jax.Array:
    """int32/int8 bitplanes [8m, n] -> uint8 [m, n]."""
    m8, n = planes.shape
    m = m8 // 8
    grouped = planes.reshape(m, 8, n).astype(jnp.int32)
    weights = (jnp.int32(1) << jnp.arange(8, dtype=jnp.int32))[None, :, None]
    # int32 accumulation: Mosaic/Pallas doesn't lower unsigned reductions
    return (grouped * weights).sum(axis=1, dtype=jnp.int32).astype(jnp.uint8)


def gf2_matmul_bytes_ref(mbits: jax.Array, x: jax.Array) -> jax.Array:
    """Apply a GF(2) bit-matrix to byte rows: [R8, K8] x uint8 [k, n].

    mbits: int8 (R8 x K8) binary matrix with R8 = 8*rows_out, K8 = 8*k.
    Returns uint8 [rows_out, n].
    """
    planes = bytes_to_bitplanes(x)
    acc = jax.lax.dot_general(
        mbits,
        planes,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return bitplanes_to_bytes(acc & 1)


# ---------------------------------------------------------------------------
# Pallas fused kernel
# ---------------------------------------------------------------------------


def _gf2_kernel(mbits_ref, x_ref, out_ref):
    """One (k, TN) tile: expand -> int8 matmul -> mod 2 -> pack."""
    x = x_ref[:]  # uint8 [k, TN]
    k, tn = x.shape
    # Mosaic only legalizes 32-bit iota/shifts: extract bits in int32
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 8, 1), 1)
    xi = x.astype(jnp.int32)
    bits = ((xi[:, None, :] >> shifts) & 1).astype(jnp.int8)
    planes = bits.reshape(k * 8, tn)
    acc = jax.lax.dot_general(
        mbits_ref[:],
        planes,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    acc = acc & 1
    m8 = acc.shape[0]
    weights = jnp.int32(1) << jax.lax.broadcasted_iota(jnp.int32, (1, 8, 1), 1)
    packed = (acc.reshape(m8 // 8, 8, tn) * weights).sum(
        axis=1, dtype=jnp.int32
    )
    out_ref[:] = packed.astype(jnp.uint8)


@functools.partial(instrumented_jit, family="gf2_matmul",
                   static_argnames=("tile_n",))
def gf2_matmul_bytes_pallas(
    mbits: jax.Array, x: jax.Array, tile_n: int = 2048
) -> jax.Array:
    """Fused TPU kernel: uint8 in / uint8 out, bitplanes never touch HBM."""
    r8, k8 = mbits.shape
    k, n = x.shape
    assert k8 == 8 * k and r8 % 8 == 0
    assert n % tile_n == 0, "pad n to a tile_n multiple"
    grid = (n // tile_n,)
    return instrumented_pallas_call(
        _gf2_kernel, family="gf2_matmul",
        out_shape=jax.ShapeDtypeStruct((r8 // 8, n), jnp.uint8),
        grid=grid,
        in_specs=[
            pl.BlockSpec((r8, k8), lambda i: (0, 0)),
            pl.BlockSpec((k, tile_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((r8 // 8, tile_n), lambda i: (0, i)),
    )(mbits, x)


def gf2_matmul_bytes(mbits: jax.Array, x: jax.Array, *, tile_n: int = 2048):
    """Dispatch by backend and shape: the fused Pallas kernel on a TPU
    when n is a whole number of tiles, the XLA graph otherwise."""
    n = x.shape[1]
    if jax.default_backend() == "tpu" and n % tile_n == 0:
        return gf2_matmul_bytes_pallas(mbits, x, tile_n=tile_n)
    return _ref_jit(mbits, x)


_ref_jit = instrumented_jit(gf2_matmul_bytes_ref, family="gf2_matmul")


def prepare_bitmatrix(matrix: np.ndarray, w: int = 8) -> np.ndarray:
    """Host-side: GF(2^w) coding matrix -> int8 GF(2) bit-matrix operand."""
    from ceph_tpu.ec import gf

    return gf.matrix_to_bitmatrix(matrix, w).astype(np.int8)
