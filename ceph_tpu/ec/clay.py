"""Clay (coupled-layer) MSR codes — sub-chunk array codes with optimal
single-node repair bandwidth.

The reference tree (v13.1.0) predates the clay plugin, but its interface
already anticipates array codes via sub-chunks
(reference: src/erasure-code/ErasureCodeInterface.h:259
get_sub_chunk_count, :297-340 sub-chunk minimum_to_decode) and
BASELINE.md metric 3 names clay k=8 m=4 d=11 as the repair-decode
benchmark.  This implements the coupled-layer construction (Clay codes,
FAST'18) natively against that sub-chunk API.

Construction (k data + m coding, d = k+m-1 helpers):
- q = d-k+1 (= m), t = (k+nu+m)/q with nu virtual all-zero data chunks
  padding (k+m) to a multiple of q.  Nodes live on a q x t grid,
  node i -> (x=i%q, y=i//q); each chunk holds q^t sub-chunks indexed by
  z = (z_0..z_{t-1}), a base-q t-digit number (y=0 most significant).
- The *uncoupled* symbols U form an MDS codeword per layer z; the
  *stored* symbols C couple intra-column pairs: for (x,y,z) with
  z_y != x the pair partner is node (z_y, y) at layer z(y->x), through
  the invertible transform (char-2 GF(256), gamma not in {0,1}):
      C1 = U1 + g*U2          U1 = (C1 + g*C2) / (1+g^2)
      C2 = g*U1 + U2          U2 = (g*C1 + C2) / (1+g^2)
  Symbols with z_y == x ("dots") are uncoupled: C = U.
- Single-node repair of (x0,y0) reads ONLY the q^{t-1} layers with
  z_{y0} = x0 from each of the d survivors — a d/(k*q) fraction of the
  RS repair bytes (11/32 for k=8,m=4,d=11).

TPU mapping: because parity nodes fill exactly the last grid column
(k+nu = q*(t-1)), encode needs no layer ordering.  On a device engine
(`gf256_swar._engine`: "pallas" / "xla") an encode is ONE jitted program
of the `gf256_clay` family (`_encode_program`): the data planes go up as
packed u32 words [k, Z, W], the m stored parity planes come back, and
uncoupling, the per-layer MDS code and re-coupling are steps inside it.
The partner map is an axis swap there, not a gather: with the layer axis
viewed as its t base-q digits and a column's nodes as x, the partner of
(x, y) at layer z is column y's block with the axes x and z_y exchanged,
and the dots are the diagonal x == z_y; the GF(2^8) arithmetic is the
SWAR xor network (ceph_tpu.ops.gf256_swar) over the words, elementwise
over W, which stays the minor axis throughout.  On the CPU backend's
native engine (a 2 us ctypes call, where a jit dispatch costs more than
the gathers) the same three steps run as wide [[a,b]] 1x2 GF(2^8)
matmuls over (chunk, partner) row pairs gathered by numpy, and ONE
coding-matrix matmul over all layers.  Both give the same bytes.
Repair and the general multi-erasure decode keep the host composition:
the intersection-score layer ordering runs host-side with a cached
device matmul per IS level.

A call codes one codeword: a chunk of n bytes is q^t sub-chunks of
n/q^t.  Every step is elementwise over the bytes within a sub-chunk, so
that axis also carries MANY codewords side by side.  An EC pool codes
each stripe by itself (stripe_unit bytes a chunk: 64 sub-chunks of 64 B
for k=8 m=4 d=11 at 4 KiB, as upstream's ECUtil::encode hands the
plugin stripe_width bytes a call); StripeBatchQueue._dispatch_array
lays the stripes of a shard, and the jobs of a batch, side by side
along that axis, so the device programs see one wide codeword.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Iterable, List, Mapping, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ceph_tpu.core import tracing
from ceph_tpu.core.lockdep import make_lock
from ceph_tpu.ec import gf, matrices
from ceph_tpu.ec.interface import (
    SIMD_ALIGN,
    ErasureCode,
    ErasureCodeError,
    ErasureCodeProfile,
    to_int,
)
from ceph_tpu.ops import gf256_swar
from ceph_tpu.tpu.devwatch import fetch, instrumented_jit


def _gf_pair(a: int, b: int) -> np.ndarray:
    return np.array([[a, b]], dtype=np.uint32)


# calls of the GF(2^8) engine made by every clay codec of the process (a
# device call each on the chip): the program counter `clay.dev_calls`
_dev_calls = 0
_dev_calls_lock = make_lock("clay.dev_calls")


def dev_calls() -> int:
    return _dev_calls


def _count_call() -> None:
    global _dev_calls
    with _dev_calls_lock:
        _dev_calls += 1


def _gf_call(M: np.ndarray, x: np.ndarray, **kw) -> np.ndarray:
    """`gf_matmul_bytes` in the clay family, counted."""
    _count_call()
    return gf256_swar.gf_matmul_bytes(M, x, family="gf256_clay", **kw)


@functools.lru_cache(maxsize=16)
def _encode_program(k: int, q: int, t: int, coding: bytes,
                    uncouple: bytes, couple: bytes) -> Callable:
    """The whole encode of one profile as one jitted program:
    f(words u32[k, Z, W]) -> u32[q, Z, W], the data planes in and the
    stored parity planes out, four bytes a word (`coding` is the
    [q, q*(t-1)] MDS matrix, `uncouple` and `couple` the [[a, b]] pair
    rows, as bytes: a program is a function of its constants alone, and
    every codec of a profile, one a PG, shares it).

    A column's nodes are a block [x, z_0 .. z_{t-1}, W]; the partner of
    x at layer z is the block with the axes x and z_y exchanged, the
    dots its diagonal.  Every swap is on major axes and every GF step
    elementwise, so W is the minor axis of every array in the program.
    """
    kk, Z = q * (t - 1), q ** t
    nets = [gf256_swar._build_network(
        np.frombuffer(b, dtype=np.uint8).reshape(shape))
        for b, shape in ((uncouple, (1, 2)), (coding, (q, kk)),
                         (couple, (1, 2)))]
    grid = np.arange(q)

    def pair(net, col, y: int):
        """where dot: col, else a*col + b*partner, for the column at
        grid position y given as [x, z_0 .. z_{t-1}, W]."""
        dot = grid.reshape(-1, *[1] * (t + 1)) == grid.reshape(
            *[1] * (1 + y), -1, *[1] * (t - y))
        mixed = net(jnp.stack([col, jnp.swapaxes(col, 0, 1 + y)]))[0]
        return jnp.where(dot, col, mixed)

    def run(words):
        W = words.shape[2]
        if kk > k:   # the virtual nodes: rows of zeros
            words = jnp.concatenate(
                [words, jnp.zeros((kk - k, Z, W), words.dtype)])
        cols = words.reshape(t - 1, q, *[q] * t, W)
        U = jnp.stack([pair(nets[0], cols[y], y) for y in range(t - 1)])
        U_par = nets[1](U.reshape(kk, Z, W))
        return pair(nets[2], U_par.reshape(q, *[q] * t, W),
                    t - 1).reshape(q, Z, W)

    return instrumented_jit(run, family="gf256_clay")


class ClayCodec(ErasureCode):
    """Coupled-layer MSR codec over the SWAR GF(2^8) engine."""

    def __init__(self, k: int = 0, m: int = 0, d: int | None = None,
                 gamma: int = 2):
        super().__init__()
        self._k = int(k)
        self._m = int(m)
        self._d = int(d) if d is not None else 0
        self.gamma = int(gamma)
        if k and m:
            self._setup()

    # -- profile plumbing (plugin registry path) ---------------------------
    def parse(self, profile: ErasureCodeProfile) -> None:
        super().parse(profile)
        self._k = to_int(profile, "k", 4)
        self._m = to_int(profile, "m", 2)
        self._d = to_int(profile, "d", self._k + self._m - 1)
        self._setup()

    def _setup(self) -> None:
        k, m = self._k, self._m
        if not self._d:
            self._d = k + m - 1
        d = self._d
        if d != k + m - 1:
            raise ErasureCodeError(
                f"clay: only d = k+m-1 supported (got d={d}, k={k}, m={m})"
            )
        if k < 2:
            raise ErasureCodeError("k must be >= 2")
        if m < 2:
            raise ErasureCodeError("clay needs m >= 2")
        if self.gamma in (0, 1):
            raise ErasureCodeError("clay: gamma must not be 0 or 1")
        self.q = d - k + 1  # == m
        self.nu = (self.q - (k + m) % self.q) % self.q
        self.t = (k + m + self.nu) // self.q
        self.sub_count = self.q ** self.t
        kk = k + self.nu  # internal data width incl. virtual zero chunks
        self.kk = kk
        assert kk == self.q * (self.t - 1), "parity column must be whole"
        # the MDS code applied per uncoupled layer
        self.coding = matrices.isa_cauchy(kk, m)
        self.full_generator = matrices.full_generator(self.coding)
        g = self.gamma
        det = 1 ^ int(gf.mul(g, g))  # 1 + g^2 (char 2)
        inv_det = int(gf.inv(det))
        inv_g = int(gf.inv(g))
        self._det = det
        # [[a, b]] row transforms (see module docstring):
        #   uncouple: U1 = inv_det*C1 + inv_det*g*C2
        #   couple:   C1 = U1 + g*U2
        #   repair:   C(A) = (det*U(B) + C(B)) / g
        self._uncouple_M = _gf_pair(inv_det, int(gf.mul(inv_det, g)))
        self._couple_M = _gf_pair(1, g)
        self._repair_M = _gf_pair(int(gf.mul(det, inv_g)), inv_g)
        # recover stored C from own U + KNOWN partner C:
        #   C1 = det*U1 + g*C2  (derived in the module docstring)
        self._c_from_U_M = _gf_pair(det, g)
        # the constants of the encode's one device program
        self._program_key = (k, self.q, self.t) + tuple(
            np.asarray(M, dtype=np.uint8).tobytes()
            for M in (self.coding, self._uncouple_M, self._couple_M))
        self._pair_tables()
        self._solve_cache: Dict[Tuple[Tuple[int, ...], Tuple[int, ...]],
                                np.ndarray] = {}

    def _pair_tables(self) -> None:
        """Precompute per-(node, layer) partner indices and dot masks."""
        q, t = self.q, self.t
        n = self.kk + self._m
        zs = np.arange(self.sub_count)
        # digit y of layer z (y=0 most significant)
        self.digits = np.stack(
            [(zs // q ** (t - 1 - y)) % q for y in range(t)]
        )  # [t, Z]
        x = np.arange(n) % q
        y = np.arange(n) // q
        dig_y = self.digits[y]  # [n, Z]: z_y per node
        self.dot = dig_y == x[:, None]  # [n, Z]
        self.pnode = y[:, None] * q + dig_y  # partner node (z_y, y)
        # partner layer: digit y replaced by x
        pw = np.array([q ** (t - 1 - yy) for yy in range(t)])
        self.pz = zs[None, :] + (x[:, None] - dig_y) * pw[y][:, None]

    # -- shape queries ----------------------------------------------------
    @property
    def k(self) -> int:
        return self._k

    @property
    def m(self) -> int:
        return self._m

    @property
    def d(self) -> int:
        return self._d

    def get_sub_chunk_count(self) -> int:
        return self.sub_count

    def get_alignment(self) -> int:
        # chunk_size must split into q^t sub-chunks and stay SIMD-aligned
        import math

        return SIMD_ALIGN * self.sub_count // math.gcd(
            SIMD_ALIGN, self.sub_count
        )

    # -- pairwise transforms (each ONE 1x2 GF matmul on device) ------------
    def _apply_pair(self, M: np.ndarray, a: np.ndarray,
                    b: np.ndarray) -> np.ndarray:
        """out = M[0,0]*a + M[0,1]*b elementwise over byte arrays."""
        stacked = np.stack(
            [np.ascontiguousarray(a).ravel(),
             np.ascontiguousarray(b).ravel()]
        ).astype(np.uint8)
        out = np.asarray(_gf_call(M, stacked))
        return out.reshape(np.shape(a))

    def _uncouple_nodes(self, C: np.ndarray,
                        nodes: np.ndarray) -> np.ndarray:
        """U[i] = C[i] where dot else (C[i] + g*C[partner])/det."""
        own = C[nodes]
        nd = ~self.dot[nodes]  # pair transform only off the diagonal
        out = own.copy()
        if nd.any():
            out[nd] = self._apply_pair(
                self._uncouple_M, own[nd],
                C[self.pnode[nodes][nd], self.pz[nodes][nd]])
        return out

    def _couple_nodes(self, U: np.ndarray, nodes: np.ndarray) -> np.ndarray:
        """C[i] = U[i] where dot else U[i] + g*U[partner]."""
        own = U[nodes]
        nd = ~self.dot[nodes]
        out = own.copy()
        if nd.any():
            out[nd] = self._apply_pair(
                self._couple_M, own[nd],
                U[self.pnode[nodes][nd], self.pz[nodes][nd]])
        return out

    # -- encode ------------------------------------------------------------
    def encode_array(self, data: np.ndarray) -> np.ndarray:
        data = np.ascontiguousarray(data, dtype=np.uint8)
        k, n = data.shape
        if k != self._k or n % self.sub_count:
            raise ErasureCodeError(
                f"clay encode: bad planes {data.shape} (k={self._k}, "
                f"n must be a multiple of {self.sub_count})"
            )
        # by backend and width, as every GF call of the tree chooses
        if gf256_swar._engine(n) == "native":
            return self._encode_host(data)
        return self._encode_device(data)

    def _encode_device(self, data: np.ndarray) -> np.ndarray:
        """One call of `_encode_program`: one upload (the data words),
        one fetch (the parity planes); under `clay.mds`, whose self time
        is the host work left around the call."""
        Z, m = self.sub_count, self._m
        k, n = data.shape
        s = n // Z
        with tracing.span("clay.mds", layers=Z, bytes=self.kk * n):
            planes = data.reshape(k, Z, s)
            if s % 4:   # no whole number of words: no served width
                planes = np.pad(planes, ((0, 0), (0, 0), (0, -s % 4)))
            _count_call()
            # contiguous as gf_matmul_bytes makes its fetch: the chip
            # hands back a strided array at the narrowest widths
            par = np.ascontiguousarray(fetch(_encode_program(
                *self._program_key)(planes.view(np.uint32)))).view(np.uint8)
            return par[:, :, :s].reshape(m, n)

    def _encode_host(self, data: np.ndarray) -> np.ndarray:
        """The same three steps around three calls of the native engine,
        the partners gathered by numpy."""
        n = data.shape[1]
        Z = self.sub_count
        s = n // Z
        dnodes = np.arange(self.kk)
        # coupled symbols (node x layer) of the data nodes and of the
        # parity column: what the two pair transforms count
        pairs_d = int((~self.dot[: self.kk]).sum())
        pairs_p = int((~self.dot[self.kk:]).sum())
        with tracing.span("clay.uncouple", pairs=pairs_d,
                          bytes=2 * pairs_d * s):
            C = np.zeros((self.kk + self._m, Z, s), dtype=np.uint8)
            C[: self._k] = data.reshape(self._k, Z, s)
            U_data = self._uncouple_nodes(C, dnodes)
        # per-layer MDS: U_parity = coding @ U_data, all layers at once
        with tracing.span("clay.mds", layers=Z, bytes=self.kk * n):
            U_flat = U_data.reshape(self.kk, Z * s)
            U_par = np.asarray(_gf_call(self.coding, U_flat)).reshape(
                self._m, Z, s)
        # couple the parity column back to stored symbols
        with tracing.span("clay.couple", pairs=pairs_p,
                          bytes=2 * pairs_p * s):
            U_all = np.concatenate([U_data, U_par])
            pnodes = np.arange(self.kk, self.kk + self._m)
            C_par = self._couple_nodes(U_all, pnodes)
        return C_par.reshape(self._m, n)

    # -- repair (single erasure, the MSR bandwidth win) --------------------
    def _node(self, ext: int) -> int:
        """External chunk id -> internal grid node id (virtual zero
        chunks occupy internal slots [k, k+nu))."""
        return ext if ext < self._k else ext + self.nu

    def repair_layers(self, lost: int) -> np.ndarray:
        """The q^{t-1} layer indices z with z_{y0} == x0 (lost is an
        external chunk id)."""
        n = self._node(lost)
        x0, y0 = n % self.q, n // self.q
        return np.nonzero(self.digits[y0] == x0)[0]

    def minimum_to_decode(
        self, want_to_read: Iterable[int], available: Iterable[int]
    ) -> Dict[int, List[Tuple[int, int]]]:
        """Sub-chunk-aware helper selection: a single lost chunk reads
        only the repair layers of every survivor (reference semantics:
        ErasureCodeInterface.h:297-325)."""
        want = sorted(set(want_to_read))
        avail = sorted(set(available))
        missing = [w for w in want if w not in avail]
        if len(missing) == 1 and len(avail) >= self.d:
            layers = self.repair_layers(missing[0])
            runs = _as_runs(layers)
            helpers = [a for a in avail if a != missing[0]][: self.d]
            return {h: runs for h in helpers}
        return super().minimum_to_decode(want_to_read, available)

    def repair_read_bytes(self, lost: Sequence[int], helpers: Iterable[int],
                          chunk_size: int | None = None) -> int:
        """Total bytes read for a repair plan (for assertions/bench)."""
        plan = self.minimum_to_decode(lost, helpers)
        cs = chunk_size if chunk_size is not None else self.sub_count
        s = cs // self.sub_count
        return sum(sum(c for _, c in runs) * s for runs in plan.values())

    def repair_chunk(
        self, lost: Sequence[int], chunks: Mapping[int, np.ndarray],
        *, layers_only: bool = False,
    ) -> Dict[int, np.ndarray]:
        """Recover ONE lost chunk reading only repair-layer sub-chunks.

        ``chunks`` values are full chunks (sliced internally), or — with
        ``layers_only=True`` — just the repair-layer sub-chunks
        concatenated in layer order.
        """
        (l0,) = lost
        Z = self.sub_count
        layers = self.repair_layers(l0)
        L = len(layers)
        helpers = sorted(h for h in chunks.keys() if h != l0)
        if len(helpers) < self.d:
            raise ErasureCodeError(
                f"clay repair needs d={self.d} helpers, have {len(helpers)}"
            )
        helpers = helpers[: self.d]
        sizes = {np.asarray(chunks[h]).size for h in helpers}
        if len(sizes) != 1:
            raise ErasureCodeError("clay repair: helper sizes differ")
        size = sizes.pop()
        full = not layers_only
        s = size // Z if full else size // L
        planes = np.empty((self.d, L, s), dtype=np.uint8)
        for hi, h in enumerate(helpers):
            arr = np.asarray(chunks[h], dtype=np.uint8).ravel()
            planes[hi] = (
                arr.reshape(Z, s)[layers] if full else arr.reshape(L, s)
            )
        out = self.repair_planes(l0, helpers, planes)
        return {l0: out.reshape(-1)}

    def repair_planes(self, lost: int, helpers: Sequence[int],
                      planes: np.ndarray) -> np.ndarray:
        """Batched single-erasure repair kernel: ``planes`` [d, L, S]
        holds each helper's repair-layer sub-chunks (row order =
        ``helpers``, layer order = ``repair_layers(lost)``); returns the
        rebuilt chunk as [Z, S].

        Every transform here is elementwise over the S axis — the
        coupled-pair index j never mixes byte positions within a
        sub-chunk — so the StripeBatchQueue concatenates many objects'
        repairs along S and runs the whole batch as ONE set of device
        matmuls (the repair twin of the write path's encode batching).
        """
        l0n = self._node(lost)
        x0, y0 = l0n % self.q, l0n // self.q
        q, Z = self.q, self.sub_count
        layers = self.repair_layers(lost)
        L = len(layers)
        planes = np.asarray(planes, dtype=np.uint8)
        if planes.ndim != 3 or planes.shape[:2] != (len(helpers), L):
            raise ErasureCodeError(
                f"clay repair_planes: bad planes {planes.shape} "
                f"(want ({len(helpers)}, {L}, S))"
            )
        with tracing.span("clay.repair", layers=L, bytes=planes.nbytes):
            s = planes.shape[2]
            n_total = self.kk + self._m
            # read planes [n_total, L, s], indexed by INTERNAL node id;
            # virtual nodes stay zero (their reads are free)
            Cr = np.zeros((n_total, L, s), dtype=np.uint8)
            for hi, h in enumerate(helpers):
                Cr[self._node(h)] = planes[hi]
            # map a global layer index to its position in `layers`
            lpos = np.full(Z, -1)
            lpos[layers] = np.arange(L)

            # 1. U of nodes outside column y0: their partners are also in the
            #    repair layer set (partner layer only changes digit y != y0)
            nodes_other = np.array([i for i in range(n_total) if i // q != y0])
            own = Cr[nodes_other]
            pn = self.pnode[nodes_other][:, layers]
            pzl = lpos[self.pz[nodes_other][:, layers]]
            dot = self.dot[nodes_other][:, layers]
            # dot positions pass C through untouched — gather partners and
            # run the pair transform ONLY where coupling happens (1/q of
            # the grid is dot, so this trims the matmul width by ~25% for
            # q=4 and skips the partner gather at those positions)
            nd = ~dot
            U_known = own.copy()
            if nd.any():
                U_known[nd] = self._apply_pair(
                    self._uncouple_M, own[nd], Cr[pn[nd], pzl[nd]])

            # 2. MDS-solve the q column-y0 U rows in every repair layer at
            #    once (q == m unknowns per layer, one cached matrix)
            col = list(range(y0 * q, y0 * q + q))
            U_col = self._solve_unknowns(
                col, nodes_other.tolist(),
                U_known.reshape(len(nodes_other), -1),
            ).reshape(q, L, s)

            # 3a. dot layers of the lost node: C = U
            out = np.zeros((Z, s), dtype=np.uint8)
            out[layers] = U_col[x0]

            # 3b. other layers: C(A) = (det*U(B) + C(B)) / g where B is the
            #     partner (surviving column-y0 node, repair layer)
            pw_y0 = q ** (self.t - 1 - y0)
            # one _repair_M transform serves every partner column: batch
            # the q-1 per-column slices into a single wide matmul instead
            # of q-1 narrow dispatches
            zs_cat, ub_cat, cb_cat = [], [], []
            for xb in range(q):
                if xb == x0:
                    continue
                zs_a = np.nonzero(self.digits[y0] == xb)[0]  # lost-node layers
                zb = lpos[zs_a + (x0 - xb) * pw_y0]
                assert (zb >= 0).all()
                zs_cat.append(zs_a)
                ub_cat.append(U_col[xb, zb])
                cb_cat.append(Cr[y0 * q + xb, zb])
            out[np.concatenate(zs_cat)] = self._apply_pair(
                self._repair_M, np.concatenate(ub_cat),
                np.concatenate(cb_cat))
            return out

    def _solve_unknowns(self, unknown: List[int], known: List[int],
                        U_known: np.ndarray) -> np.ndarray:
        """U rows of `unknown` node ids from >= kk known U rows: one
        cached [len(unknown) x kk] matrix applied as a single wide device
        matmul (signature cache mirroring ErasureCodeIsaTableCache,
        reference: src/erasure-code/isa/ErasureCodeIsa.cc:226-302)."""
        key = (tuple(unknown), tuple(known))
        M = self._solve_cache.get(key)
        if M is None:
            basis = known[: self.kk]
            R = matrices.decode_matrix(self.full_generator, basis)
            rows = self.full_generator[np.asarray(unknown)]
            M = gf.matmul(rows, R)
            self._solve_cache[key] = M
        # M depends on the erasure signature: passed as data, so one
        # program per width serves every signature
        known = U_known[: self.kk]
        with tracing.span("clay.solve", rows=len(unknown),
                          bytes=known.nbytes):
            return _gf_call(M, known, operand=True)

    # -- general decode (multi-erasure, layered IS ordering) ---------------
    def decode_array(
        self, available: Mapping[int, np.ndarray], want: Sequence[int], n: int
    ) -> Dict[int, np.ndarray]:
        avail = sorted(available.keys())
        erased = sorted(set(range(self._k + self._m)) - set(avail))
        if len(erased) > self._m:
            raise ErasureCodeError("too many erasures for clay")
        want_missing = [w for w in want if w not in avail]
        if not want_missing:
            return {w: np.asarray(available[w]) for w in want}
        if len(erased) == 1 and len(avail) >= self.d:
            got = self.repair_chunk(erased, dict(available))
            out = {w: np.asarray(available[w]) for w in want if w in avail}
            out.update({w: got[w] for w in want_missing})
            return out

        q, Z = self.q, self.sub_count
        s = n // Z
        n_total = self.kk + self._m
        C = np.zeros((n_total, Z, s), dtype=np.uint8)
        known_mask = np.zeros(n_total, dtype=bool)
        for i in range(n_total):
            src = i if i < self._k else (
                i - self.nu if i >= self.kk else None
            )
            if src is not None and src in available:
                C[i] = np.asarray(
                    available[src], dtype=np.uint8).reshape(Z, s)
                known_mask[i] = True
            elif self._k <= i < self.kk:  # virtual zero chunk
                known_mask[i] = True
        erased_n = [i for i in range(n_total) if not known_mask[i]]
        known_n = [i for i in range(n_total) if known_mask[i]]

        # intersection score per layer = number of erased "dot" coords
        IS = np.zeros(Z, dtype=np.int64)
        for e in erased_n:
            IS += self.dot[e].astype(np.int64)
        U = np.zeros_like(C)
        have_U = np.zeros((n_total, Z), dtype=bool)
        ka = np.asarray(known_n)
        for level in range(int(IS.max()) + 1):
            zs = np.nonzero(IS == level)[0]
            if len(zs) == 0:
                continue
            # batched U of every known node at this level's layers —
            # three cases masked together, each ONE wide pair matmul
            # over the full (known x layers x s) volume:
            #   dot:            U = C
            #   partner known:  U = uncouple(C_own, C_partner)
            #   partner erased: U = C_own + g*U_partner (its U solved
            #                   at IS level-1; same [[1,g]] as couple)
            own = C[ka][:, zs]
            pn = self.pnode[ka][:, zs]
            pzz = self.pz[ka][:, zs]
            assert have_U[pn, pzz][~known_mask[pn]].all(), \
                "IS ordering violated"
            unc = self._apply_pair(self._uncouple_M, own, C[pn, pzz])
            via_U = self._apply_pair(self._couple_M, own, U[pn, pzz])
            dotm = self.dot[ka][:, zs][..., None]
            pk = known_mask[pn][..., None]
            U[ka[:, None], zs[None, :]] = np.where(
                dotm, own, np.where(pk, unc, via_U))
            have_U[ka[:, None], zs[None, :]] = True
            U_known = U[ka][:, zs].reshape(len(known_n), -1)
            solved = self._solve_unknowns(erased_n, known_n, U_known)
            solved = solved.reshape(len(erased_n), len(zs), s)
            for ei, e in enumerate(erased_n):
                U[e, zs] = solved[ei]
                have_U[e, zs] = True
        # recover the stored C of erased nodes — all layers at once
        # (partner known: C1 = det*U1 + g*C2; partner erased: couple)
        er = np.asarray(erased_n)
        own_U = U[er]
        pn = self.pnode[er]
        pzz = self.pz[er]
        from_C = self._apply_pair(self._c_from_U_M, own_U, C[pn, pzz])
        from_U = self._apply_pair(self._couple_M, own_U, U[pn, pzz])
        pk = known_mask[pn][..., None]
        C[er] = np.where(self.dot[er][..., None], own_U,
                         np.where(pk, from_C, from_U))
        out: Dict[int, np.ndarray] = {}
        for w in want:
            if w in avail:
                out[w] = np.asarray(available[w])
            else:
                i = w if w < self._k else w + self.nu
                out[w] = C[i].reshape(-1)
        return out

    def decode_planes(self, avail_ids: Sequence[int],
                      planes: np.ndarray) -> np.ndarray:
        """Batched data decode kernel for the StripeBatchQueue: ``planes``
        [A, n] stacks the surviving chunks (row order = ``avail_ids``,
        n a multiple of sub_count); returns the k data chunks [k, n].
        Like repair_planes, every step is elementwise over the intra-
        sub-chunk byte axis, so multi-object batches concatenated along
        that axis decode in one pass."""
        planes = np.asarray(planes, dtype=np.uint8)
        available = {a: planes[i] for i, a in enumerate(avail_ids)}
        out = self.decode_array(
            available, list(range(self._k)), planes.shape[1])
        return np.stack([np.asarray(out[i]) for i in range(self._k)])

    def supports_partial_writes(self) -> bool:
        """False: clay couples layers across a codeword's whole chunk.
        A byte at sub-chunk z of any data chunk feeds, via the pairwise
        coupling, the uncoupled symbol at the PARTNER layer z(y->x) of
        another node — so the only write sets closed under the coupling
        are whole codewords (a pool's whole stripes), and extent-local
        parity deltas cannot exist (the reference likewise refuses
        ec_overwrites on clay pools)."""
        return False


class ErasureCodeClay:
    """Registry factory (plugin name "clay")."""

    @staticmethod
    def create(profile: dict) -> ClayCodec:
        codec = ClayCodec()
        codec.init(profile)
        return codec


def _gfc(c: int, arr: np.ndarray) -> np.ndarray:
    return np.asarray(gf.mul(int(c), arr), dtype=np.uint8)


def _pair_scalar(M: np.ndarray, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Host-side tiny-pair transform (general-decode path)."""
    return _gfc(int(M[0, 0]), a) ^ _gfc(int(M[0, 1]), b)


def _as_runs(idx: np.ndarray) -> List[Tuple[int, int]]:
    """Sorted indices -> [(sub_chunk_offset, count)] runs."""
    runs: List[Tuple[int, int]] = []
    for i in np.sort(np.asarray(idx)):
        i = int(i)
        if runs and runs[-1][0] + runs[-1][1] == i:
            runs[-1] = (runs[-1][0], runs[-1][1] + 1)
        else:
            runs.append((i, 1))
    return runs
