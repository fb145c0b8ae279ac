"""Pallas GF(2^8) engine pinned against the SWAR network + native oracle.

Runs in Pallas interpret mode on the CPU backend (the kernel body is the
same python; only the TPU lowering differs), mirroring how the reference
pins its SIMD encode regions against the scalar gf-complete path
(src/test/erasure-code/TestErasureCodeIsa.cc)."""

import numpy as np
import pytest

from ceph_tpu import _native
from ceph_tpu.ec import matrices
from ceph_tpu.ops import gf256_pallas, gf256_swar


@pytest.mark.parametrize("k,m", [(8, 4), (4, 2), (3, 3)])
def test_pallas_matches_network_and_oracle(k, m):
    coding = matrices.isa_cauchy(k, m)
    rng = np.random.default_rng(7)
    n = 4 * gf256_pallas.LANES * 8  # T = 8 sublane rows
    x = rng.integers(0, 256, size=(k, n), dtype=np.uint8)

    words = gf256_pallas.pack_planes(x)
    out = gf256_pallas.encode_planes(coding, words, tile=4)
    got = gf256_pallas.unpack_planes(out)

    want = np.asarray(gf256_swar.gf_matmul_bytes(coding, x))
    assert np.array_equal(got, want)

    oracle = _native.rs_encode(coding.astype(np.uint8), x)
    assert np.array_equal(got, oracle)


def test_pallas_seed_xor_is_encode_of_xored_input():
    """The bench's anti-hoisting seed must equal encoding (x ^ seed)."""
    coding = matrices.isa_cauchy(4, 2)
    rng = np.random.default_rng(8)
    x = rng.integers(0, 256, size=(4, 4 * gf256_pallas.LANES * 4),
                     dtype=np.uint8)
    words = gf256_pallas.pack_planes(x)
    import jax.numpy as jnp
    seed = jnp.full((1,), 0xA5A5A5A5, jnp.uint32)
    out = gf256_pallas.encode_planes(coding, words, seed, tile=4)

    x2 = (gf256_pallas.pack_planes(x) ^ np.uint32(0xA5A5A5A5))
    want = gf256_pallas.encode_planes(coding, x2, tile=4)
    assert np.array_equal(np.asarray(out), np.asarray(want))


def test_pallas_recovery_matrix_decode():
    """Decode via recovery matrix through the same kernel."""
    from ceph_tpu.ec.codec import RSMatrixCodec

    k, m = 8, 4
    coding = matrices.isa_cauchy(k, m)
    codec = RSMatrixCodec(k, m, coding)
    rng = np.random.default_rng(9)
    n = 4 * gf256_pallas.LANES * 8
    x = rng.integers(0, 256, size=(k, n), dtype=np.uint8)
    words = gf256_pallas.pack_planes(x)
    coded = gf256_pallas.unpack_planes(
        gf256_pallas.encode_planes(coding, words, tile=4))

    survivors = [0, 2, 3, 5, 6, 7, 8, 11]  # lose 1, 4 + coding 9, 10
    rec, _ = codec.recovery_matrix(survivors)
    surv = np.stack([x[s] if s < k else coded[s - k] for s in survivors])
    out = gf256_pallas.encode_planes(
        rec, gf256_pallas.pack_planes(surv), tile=4)
    assert np.array_equal(gf256_pallas.unpack_planes(out), x)


def test_pallas_interleaved_matches_planar():
    coding = matrices.isa_cauchy(8, 4)
    rng = np.random.default_rng(11)
    x = rng.integers(0, 256, size=(8, 4 * gf256_pallas.LANES * 8),
                     dtype=np.uint8)
    words = gf256_pallas.pack_planes(x)
    want = np.asarray(gf256_pallas.encode_planes(coding, words, tile=4))
    got = np.asarray(gf256_pallas.encode_planes_interleaved(
        coding, np.transpose(words, (1, 0, 2)), tile=4))
    assert np.array_equal(np.transpose(got, (1, 0, 2)), want)


def test_product_routing_wrapper_roundtrip(monkeypatch):
    """The gf_matmul_bytes TPU routing branch (host-packed u32 planes,
    pallas encode, host unpack) — forced on via env so the CPU suite
    exercises the exact wrapper a real TPU runs (a reshape bug here
    shipped blind once; never again)."""
    from ceph_tpu.ops import gf256_swar

    monkeypatch.setenv("CEPH_TPU_FORCE_PALLAS", "1")
    coding = matrices.isa_cauchy(8, 4)
    rng = np.random.default_rng(12)
    for n in (512, 4096):
        x = rng.integers(0, 256, size=(8, n), dtype=np.uint8)
        got = gf256_swar.gf_matmul_bytes(coding, x)
        want = _native.rs_encode(coding.astype(np.uint8), x)
        assert np.array_equal(got, want), n
    # square decode with donate=True (the queue path) aliases buffers
    from ceph_tpu.ec.codec import RSMatrixCodec

    codec = RSMatrixCodec(8, 4, coding)
    survivors = [0, 1, 2, 3, 4, 5, 8, 9]
    rec, _ = codec.recovery_matrix(survivors)
    x = rng.integers(0, 256, size=(8, 512), dtype=np.uint8)
    coded = _native.rs_encode(coding.astype(np.uint8), x)
    surv = np.stack([x[s] if s < 8 else coded[s - 8] for s in survivors])
    got = gf256_swar.gf_matmul_bytes(rec, surv, donate=True)
    assert np.array_equal(got, x)


# -- the tile ladder (PR 22): Mosaic takes a sublane block that is a
# multiple of 8 or the whole axis; interpret mode checks neither, so
# the rule itself is pinned here and the real lowering in
# tests/test_chip_compile.py

@pytest.mark.parametrize("T", [1, 2, 3, 4, 7, 8, 12, 24, 27, 48, 100,
                               256, 344, 511, 512, 513, 520, 1027,
                               1032, 2048, 4099, 8192])
def test_pallas_tile_is_legal_for_mosaic(T):
    tile, T_pad = gf256_swar.pallas_tile(T)
    assert T_pad >= T and T_pad - T < 8
    assert T_pad % tile == 0
    assert tile <= gf256_swar._PALLAS_MAX_TILE
    # a multiple of 8, or the whole (unpadded) axis
    assert tile % 8 == 0 or (tile == T_pad == T)
    if T % 8 == 0 or T <= gf256_swar._PALLAS_MAX_TILE:
        assert T_pad == T  # no pad where a legal tile exists


@pytest.mark.parametrize("matrix_as", ["baked", "operand"])
@pytest.mark.parametrize("n", [1536, 3072, 13824, 513 * 512])
def test_product_wrapper_odd_widths_roundtrip(monkeypatch, n, matrix_as):
    """Widths whose T = n/512 has no power-of-two divisor >= 8 (the old
    ladder picked tile 4 / 1 for them, which the chip refuses), and
    one T > 512 that pads: bit-exact against the C oracle through the
    exact wrapper a TPU runs (host bytes packed into words on the
    host), with the matrix compiled in and with it passed as data."""
    monkeypatch.setenv("CEPH_TPU_FORCE_PALLAS", "1")
    coding = matrices.isa_cauchy(8, 4)
    rng = np.random.default_rng(n)
    x = rng.integers(0, 256, size=(8, n), dtype=np.uint8)
    got = gf256_swar.gf_matmul_bytes(
        coding, x, operand=matrix_as == "operand")
    want = _native.rs_encode(coding.astype(np.uint8), x)
    assert isinstance(got, np.ndarray) and got.shape == want.shape
    assert np.array_equal(got, want)


@pytest.mark.parametrize("n", [512, 4096, 131072])
def test_product_wrapper_host_input_decode_donated(monkeypatch, n):
    """The queue's decode dispatch: host numpy survivors, square
    recovery matrix as an operand, donate=True — through the Pallas
    route."""
    from ceph_tpu.ec.codec import RSMatrixCodec

    monkeypatch.setenv("CEPH_TPU_FORCE_PALLAS", "1")
    coding = matrices.isa_cauchy(8, 4)
    survivors = [0, 1, 2, 3, 4, 5, 8, 9]
    rec, _ = RSMatrixCodec(8, 4, coding).recovery_matrix(survivors)
    rng = np.random.default_rng(n)
    x = rng.integers(0, 256, size=(8, n), dtype=np.uint8)
    coded = _native.rs_encode(coding.astype(np.uint8), x)
    surv = np.stack([x[s] if s < 8 else coded[s - 8] for s in survivors])
    keep = surv.copy()
    got = gf256_swar.gf_matmul_bytes(rec, surv, donate=True, operand=True)
    assert isinstance(got, np.ndarray) and np.array_equal(got, x)
    assert np.array_equal(surv, keep)  # the caller's host buffer is intact


def test_operand_decode_is_one_program_for_every_signature(monkeypatch):
    """The repair of PR 22's degraded-read failure: with the recovery
    matrix passed as data, every survivor signature of a k=8 m=4 code
    at one width runs the SAME compiled program (the baked kernel
    compiled one per signature, in line on the queue's worker)."""
    import itertools

    from ceph_tpu.ec.codec import RSMatrixCodec
    from ceph_tpu.tpu import devwatch

    monkeypatch.setenv("CEPH_TPU_FORCE_PALLAS", "1")
    coding = matrices.isa_cauchy(8, 4)
    codec = RSMatrixCodec(8, 4, coding)
    rng = np.random.default_rng(5)
    x = rng.integers(0, 256, size=(8, 2048), dtype=np.uint8)
    shards = np.concatenate([x, _native.rs_encode(
        coding.astype(np.uint8), x)])
    sigs = [s for s in itertools.combinations(range(12), 8)][::29]
    assert len(sigs) >= 16
    compiles = []
    for sig in sigs:
        rec, _ = codec.recovery_matrix(list(sig))
        got = gf256_swar.gf_matmul_bytes(
            rec, shards[list(sig)], donate=True, operand=True)
        assert np.array_equal(got, x), sig
        compiles.append(
            devwatch.watch().family_stats("gf256_pallas")["compiles"])
    # whatever the first call compiled, no later signature added one
    assert len(set(compiles)) == 1, compiles


@pytest.mark.parametrize("shape,n", [((8, 8), 64), ((4, 8), 100),
                                     ((3, 5), 1000), ((2, 2), 4097)])
def test_operand_matrix_on_the_xla_words_route(monkeypatch, shape, n):
    """Widths that are not whole 128-lane rows take the XLA network on
    a TPU; its operand twin (matrix as select masks) equals the GF
    reference."""
    from ceph_tpu.ec import gf

    monkeypatch.setattr(gf256_swar, "_engine", lambda n: "xla")
    rng = np.random.default_rng(n)
    mat = rng.integers(0, 256, size=shape, dtype=np.uint8)
    x = rng.integers(0, 256, size=(shape[1], n), dtype=np.uint8)
    want = gf.matmul(mat, x)
    assert np.array_equal(
        gf256_swar.gf_matmul_bytes(mat, x, operand=True), want)
    assert np.array_equal(gf256_swar.gf_matmul_bytes(mat, x), want)
