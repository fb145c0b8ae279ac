"""Device codecs: RS-over-GF(2^8) and GF(2) bit-matrix codes.

Both lower to the single GF(2) matmul engine (ceph_tpu.ops.gf2_matmul).
Decode matrices are built host-side per erasure signature and cached,
mirroring the isa plugin's table cache (reference:
src/erasure-code/isa/ErasureCodeIsaTableCache.cc; signature construction
at src/erasure-code/isa/ErasureCodeIsa.cc:226-302).
"""

from __future__ import annotations

from typing import Dict, List, Mapping, Sequence, Tuple

import numpy as np

from ceph_tpu.ec import gf, matrices
from ceph_tpu.ec.interface import ErasureCode, ErasureCodeError
from ceph_tpu.ops import gf2_matmul, gf256_swar

try:  # CPU small-op hot path (csrc/fastec.c); optional by design
    from ceph_tpu import _fastec
except Exception:  # pragma: no cover — extension not built
    _fastec = None

_backend_is_cpu = None


def _on_cpu_backend() -> bool:
    """jax.default_backend(), cached: the backend never changes within
    a process and the lookup is measurable on the 4 KiB hot path."""
    global _backend_is_cpu
    if _backend_is_cpu is None:
        import jax

        _backend_is_cpu = jax.default_backend() == "cpu"
    return _backend_is_cpu


class RSMatrixCodec(ErasureCode):
    """Systematic Reed-Solomon over GF(2^8) given an (m x k) coding block.

    encode: the packed-word SWAR xor network (ops.gf256_swar) — bytes
    stay four-per-lane end to end.  decode: invert the survivors' k x k
    generator rows over GF(2^8) on host (signature-cached), then the
    same engine applies the recovery matrix; missing coding chunks are
    re-encoded from the recovered data (matching jerasure_matrix_decode
    semantics, reference:
    src/erasure-code/jerasure/ErasureCodeJerasure.cc:163).
    """

    def __init__(self, k: int, m: int, coding: np.ndarray | None = None):
        super().__init__()
        self._k = int(k)
        self._m = int(m)
        if coding is not None:
            self.set_coding_matrix(coding)
        self._decode_cache: Dict[Tuple[int, ...], np.ndarray] = {}

    @property
    def k(self) -> int:
        return self._k

    @property
    def m(self) -> int:
        return self._m

    def set_coding_matrix(self, coding: np.ndarray) -> None:
        self.coding = np.asarray(coding, dtype=np.uint32)
        assert self.coding.shape == (self._m, self._k)
        self.full_generator = matrices.full_generator(self.coding)
        self._encode_bits = gf2_matmul.prepare_bitmatrix(self.coding)
        self._coding_u8 = np.ascontiguousarray(self.coding,
                                               dtype=np.uint8)
        self._decode_cache = {}
        self._bs_cache = {}  # object len -> chunk size (hot-path memo)

    def encode(self, want_to_encode, data):
        """Byte-object encode with a one-C-call fast path on the CPU
        backend: at the 4 KiB BASELINE row the interpreter overhead of
        split/pad/dispatch WAS the benchmark (~15 us vs ~1 us of GF
        math); _fastec.encode_obj collapses it (reference comparator:
        jerasure_matrix_encode,
        src/erasure-code/jerasure/ErasureCodeJerasure.cc:155)."""
        if (_fastec is not None and _on_cpu_backend() and len(data)
                and isinstance(data, (bytes, bytearray, memoryview))):
            n = len(data)
            blocksize = self._bs_cache.get(n)
            if blocksize is None:
                if len(self._bs_cache) > 4096:
                    self._bs_cache.clear()
                blocksize = self._bs_cache[n] = self.get_chunk_size(n)
            allchunks = _fastec.encode_obj(self._coding_u8, data,
                                           blocksize)
            return {i: allchunks[i] for i in want_to_encode}
        return super().encode(want_to_encode, data)

    # -- device entry points ----------------------------------------------
    def encode_array(self, data: np.ndarray) -> np.ndarray:
        data = np.asarray(data, dtype=np.uint8)
        return np.asarray(gf256_swar.gf_matmul_bytes(self.coding, data))

    def recovery_matrix(self, survivors: Sequence[int]) -> Tuple[np.ndarray, np.ndarray]:
        """Per-signature cached (k x k GF(2^8) matrix, prepared bit-matrix)
        mapping k surviving chunks -> k data chunks."""
        key = tuple(survivors)
        got = self._decode_cache.get(key)
        if got is None:
            rec = matrices.decode_matrix(self.full_generator, list(key))
            got = (rec, gf2_matmul.prepare_bitmatrix(rec))
            self._decode_cache[key] = got
        return got

    def decode_array(
        self, available: Mapping[int, np.ndarray], want: Sequence[int], n: int
    ) -> Dict[int, np.ndarray]:
        avail_ids = sorted(available.keys())
        if len(avail_ids) < self._k:
            raise ErasureCodeError(
                f"need {self._k} chunks, have {len(avail_ids)}"
            )
        survivors = avail_ids[: self._k]
        out: Dict[int, np.ndarray] = {}
        want_data = [i for i in want if i < self._k]
        want_coding = [i for i in want if i >= self._k]
        data = None
        if want_data or want_coding:
            rec, _ = self.recovery_matrix(survivors)
            stacked = np.stack(
                [np.asarray(available[i], dtype=np.uint8) for i in survivors]
            )
            # per-signature matrix: passed as data, not compiled in
            data = gf256_swar.gf_matmul_bytes(rec, stacked, operand=True)
        for i in want_data:
            out[i] = available[i] if i in available else data[i]
        if want_coding:
            coding = self.encode_array(data)
            for i in want_coding:
                out[i] = (
                    available[i] if i in available else coding[i - self._k]
                )
        return out


def _gf2_mat_inv(A: np.ndarray) -> np.ndarray:
    """Invert a square 0/1 matrix over GF(2) (host, Gauss-Jordan)."""
    A = np.array(A, dtype=np.uint8) & 1
    n = A.shape[0]
    aug = np.concatenate([A, np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        pivot = col + int(np.argmax(aug[col:, col]))
        if aug[pivot, col] == 0:
            raise ErasureCodeError("singular GF(2) matrix")
        if pivot != col:
            aug[[col, pivot]] = aug[[pivot, col]]
        rows = np.nonzero(aug[:, col])[0]
        rows = rows[rows != col]
        aug[rows] ^= aug[col]
    return aug[:, n:].copy()


class BitmatrixCodec(ErasureCode):
    """GF(2) bit-matrix code applied at packet granularity.

    The technique family jerasure calls "schedule" codes (cauchy_orig,
    cauchy_good, liberation, blaum_roth, liber8tion; reference:
    src/erasure-code/jerasure/ErasureCodeJerasure.h:118-247): each chunk
    holds w packets of ``packetsize`` bytes and the (w*m x w*k) 0/1
    matrix XORs packets together.  On device this is the same int8
    matmul-mod-2, with bits extracted along the byte lanes.
    """

    def __init__(self, k: int, m: int, w: int, bitmatrix: np.ndarray):
        super().__init__()
        self._k = int(k)
        self._m = int(m)
        self.w = int(w)
        # full generator over GF(2): identity (wk) stacked on coding rows
        coding = np.asarray(bitmatrix, dtype=np.uint8).reshape(m * w, k * w)
        self.coding_bits = coding
        self.full_bits = np.concatenate(
            [np.eye(k * w, dtype=np.uint8), coding]
        )
        self._decode_cache: Dict[Tuple[int, ...], np.ndarray] = {}
        self._apply_cache: Dict[bytes, np.ndarray] = {}

    @property
    def k(self) -> int:
        return self._k

    @property
    def m(self) -> int:
        return self._m

    def get_alignment(self) -> int:
        # the object pads to a multiple of this, so fold k in to make
        # every chunk a whole number of w-packet groups (the reference
        # jerasure alignment is likewise k*w*sizeof(int),
        # ErasureCodeJerasure.cc get_alignment)
        return self._k * self.w * 16

    def _to_packets(self, chunk_planes: np.ndarray) -> np.ndarray:
        """uint8 [c, n] -> packet rows [c*w, n/w] (w packets per chunk)."""
        c, n = chunk_planes.shape
        assert n % self.w == 0
        return chunk_planes.reshape(c * self.w, n // self.w)

    def _from_packets(self, packets: np.ndarray, c: int) -> np.ndarray:
        cw, ps = packets.shape
        return packets.reshape(c, cw // c * ps)

    def _apply(self, M: np.ndarray, planes: np.ndarray) -> np.ndarray:
        """XOR-matmul of byte rows: out[i] = XOR_j M[i,j]&planes[j].

        A 0/1 matrix acting on byte packets IS a GF(2^8) matrix with 0/1
        coefficients, so this reuses the one device engine (0/1 entries
        expand to zero/identity 8x8 blocks in prepare_bitmatrix).
        """
        key = M.tobytes()
        bits = self._apply_cache.get(key)
        if bits is None:
            bits = gf2_matmul.prepare_bitmatrix(M.astype(np.uint32))
            self._apply_cache[key] = bits
        return np.asarray(gf2_matmul.gf2_matmul_bytes(bits, planes))

    def encode_array(self, data: np.ndarray) -> np.ndarray:
        data = np.asarray(data, dtype=np.uint8)
        packets = self._to_packets(data)
        out = self._apply(self.coding_bits, packets)
        return self._from_packets(out, self._m)

    def decode_array(
        self, available: Mapping[int, np.ndarray], want: Sequence[int], n: int
    ) -> Dict[int, np.ndarray]:
        avail_ids = sorted(available.keys())
        if len(avail_ids) < self._k:
            raise ErasureCodeError("not enough chunks")
        survivors = avail_ids[: self._k]
        key = tuple(survivors)
        rec = self._decode_cache.get(key)
        if rec is None:
            rows = []
            for cid in survivors:
                rows.append(
                    self.full_bits[cid * self.w : (cid + 1) * self.w]
                )
            sub = np.concatenate(rows)  # (k*w, k*w)
            rec = _gf2_mat_inv(sub)
            self._decode_cache[key] = rec
        stacked = np.stack(
            [np.asarray(available[i], dtype=np.uint8) for i in survivors]
        )
        packets = self._to_packets(stacked)
        data_packets = self._apply(rec, packets)
        data = self._from_packets(data_packets, self._k)
        out: Dict[int, np.ndarray] = {}
        coding = None
        for i in want:
            if i in available:
                out[i] = np.asarray(available[i])
            elif i < self._k:
                out[i] = data[i]
            else:
                if coding is None:
                    coding = self.encode_array(data)
                out[i] = coding[i - self._k]
        return out
