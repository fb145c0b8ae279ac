"""Plain reference for the clay (coupled-layer MSR) pool's `correct`:
numpy only, nothing of `ceph_tpu` is imported and nothing the program
made is taken.  GF(2^8) tables (polynomial 0x11d), the 4 KiB striping and
crc32c are `reference.py`'s.

The construction, from Vajha et al., "Clay Codes: Moulding MDS Codes to
Yield an MSR Code" (FAST'18), section 4, as docs.ceph.com
`rados/operations/erasure-code-clay` parametrises it (k, m, d):

- q = d - k + 1, t = (k + m) / q, a codeword's chunk holds q^t
  sub-chunks;
- a pool codes each stripe by itself (ECUtil::encode hands the plugin
  `stripe_width` = k * 4096 bytes a call): a stripe's 4 KiB chunk on
  shard i, bytes [st*4096, (st+1)*4096) of the shard, is one codeword's
  node i, q^t sub-chunks of s = 4096 / q^t bytes, and a 1 MiB object is
  32 codewords;
- node i sits at (x, y) = (i mod q, i div q) of a q x t grid; a layer z
  is the t base-q digits (z_0 .. z_{t-1}), z_0 the most significant
  (upstream's `get_plane_vector`), and sub-chunk z of a chunk is bytes
  [z*s, (z+1)*s) of it;
- the symbol of node (x, y) in layer z is UNCOUPLED when z_y = x: C = U.
  Otherwise its partner is node (z_y, y) in layer z(y -> x) (digit y
  replaced by x), and the two are coupled:
      C1 = U1 + g U2        C2 = g U1 + U2
  (symmetric in the two, so neither is "first"); hence
      U1 = (C1 + g C2) / (1 + g^2);
- in every layer the n uncoupled symbols U are a codeword of one scalar
  [n, k] MDS code.

Encode = uncouple the k data nodes (their partners are data nodes: the
parity nodes fill the grid's last column), MDS-encode each of the q^t
layers by a loop, couple the parity column.  Repair of one node (x0, y0)
reads only the q^(t-1) layers with z_y0 = x0 of the other d = n - 1.

Departures from the paper and from upstream, each the deployment's
(`configs/ec-clay-k8m4d11-12osd.json` `assumed`):
- the pairwise transform is the paper's gamma form with g = `gamma`
  (upstream derives it from a 2+2 code of the scalar plugin);
- the scalar MDS code is ISA-L's Cauchy matrix `gf_gen_cauchy1_matrix`:
  coding row i (k <= i < n), column j: 1 / (i xor j)  (upstream's
  default scalar code is jerasure `reed_sol_van`);
- only (k + m) mod q = 0 (no shortening, nu = 0) and d = k + m - 1.

Nothing here is a closed form over all layers or an index table: every
step is the loop the equations spell.  A symbol is an array whose axes
after the layer's are the bytes it is applied to alike: the stripes of
an object and the bytes of a sub-chunk, so the 32 codewords of an object
are coded by one pass of the loops, each from its own stripe alone.
"""

from __future__ import annotations

import numpy as np

import reference

# MUL[c][a] = c * a over GF(2^8), from reference.py's log/exp tables
MUL = np.zeros((256, 256), dtype=np.uint8)
for _c in range(1, 256):
    MUL[_c, 1:] = reference._EXP[reference._LOG[_c] + reference._LOG[1:]]


def matmul(mat: np.ndarray, syms: np.ndarray) -> np.ndarray:
    """`reference.gf_matmul` over symbols of any shape:
    uint8 [r, k] x uint8 [k, ...] -> uint8 [r, ...]."""
    return reference.gf_matmul(mat, syms.reshape(len(syms), -1)).reshape(
        (len(mat),) + syms.shape[1:])


class Clay:
    """The code of one configuration (`k`, `m`, `d`, `gamma`)."""

    def __init__(self, cfg: dict) -> None:
        self.k, self.m, self.d = cfg["k"], cfg["m"], cfg["d"]
        self.n = self.k + self.m
        self.g = cfg["gamma"]
        self.q = self.d - self.k + 1
        if self.d != self.n - 1 or self.n % self.q or self.g in (0, 1):
            raise ValueError("not a code this reference runs: it needs "
                             "d = k+m-1, (k+m) mod q = 0, gamma not 0 or 1")
        self.t = self.n // self.q
        self.Z = self.q ** self.t
        # 1 / (1 + g^2): 1 + g^2 is not 0 because g is not 1
        self.inv_det = reference.gf_inv(1 ^ reference.gf_mul(self.g, self.g))
        # the scalar MDS code's generator [n, k]: identity over Cauchy
        self.G = np.zeros((self.n, self.k), dtype=np.uint8)
        self.G[:self.k] = np.eye(self.k, dtype=np.uint8)
        for i in range(self.k, self.n):
            for j in range(self.k):
                self.G[i, j] = reference.gf_inv(i ^ j)

    # -- the grid -------------------------------------------------------------
    def digit(self, z: int, y: int) -> int:
        return z // self.q ** (self.t - 1 - y) % self.q

    def partner(self, i: int, z: int):
        """(node, layer) coupled with symbol (i, z); None where z_y = x."""
        x, y = i % self.q, i // self.q
        zy = self.digit(z, y)
        if zy == x:
            return None
        return y * self.q + zy, z + (x - zy) * self.q ** (self.t - 1 - y)

    def repair_layers(self, lost: int) -> list:
        x0, y0 = lost % self.q, lost // self.q
        return [z for z in range(self.Z) if self.digit(z, y0) == x0]

    # -- the pairwise transform -------------------------------------------------
    def uncoupled(self, c_own: np.ndarray, c_partner: np.ndarray):
        """U1 = (C1 + g C2) / (1 + g^2)."""
        return MUL[self.inv_det][c_own ^ MUL[self.g][c_partner]]

    def coupled(self, u_own: np.ndarray, u_partner: np.ndarray):
        """C1 = U1 + g U2."""
        return u_own ^ MUL[self.g][u_partner]

    # -- encode ----------------------------------------------------------------
    def encode(self, data: np.ndarray) -> np.ndarray:
        """Data nodes uint8 [k, Z, ...] -> parity nodes uint8 [m, Z, ...]."""
        k, n, Z = self.k, self.n, self.Z
        U = np.zeros((n, Z) + data.shape[2:], dtype=np.uint8)
        for z in range(Z):
            for i in range(k):
                p = self.partner(i, z)
                U[i, z] = data[i, z] if p is None else self.uncoupled(
                    data[i, z], data[p])
        for z in range(Z):
            U[k:, z] = matmul(self.G[k:], U[:k, z])
        C = np.zeros((self.m,) + U.shape[1:], dtype=np.uint8)
        for z in range(Z):
            for i in range(k, n):
                p = self.partner(i, z)
                C[i - k, z] = U[i, z] if p is None else self.coupled(
                    U[i, z], U[p])
        return C

    # -- repair of one node from the repair layers of the others ---------------
    def repair(self, lost: int, helpers: dict) -> np.ndarray:
        """{node: uint8 [q^(t-1), ...], its sub-chunks of
        `repair_layers(lost)` in that order} for the n-1 others -> the
        lost node uint8 [Z, ...]."""
        q, k, n = self.q, self.k, self.n
        layers = self.repair_layers(lost)
        at = {z: j for j, z in enumerate(layers)}
        y0 = lost // q
        column = [y0 * q + x for x in range(q)]
        others = [i for i in range(n) if i not in column]
        s = next(iter(helpers.values())).shape[1:]
        out = np.zeros((self.Z,) + s, dtype=np.uint8)
        # the column's U from the others' U, which is a codeword's k symbols
        solve = reference.gf_matmul(
            self.G[column], reference.gf_invert(self.G[others]))
        for z in layers:
            known = []
            for i in others:
                p = self.partner(i, z)   # in another column: a repair layer
                c = helpers[i][at[z]]
                known.append(c if p is None else self.uncoupled(
                    c, helpers[p[0]][at[p[1]]]))
            u_col = matmul(solve, np.stack(known))
            out[z] = u_col[lost % q]      # z_y0 = x0: uncoupled
            for x in range(q):
                b = y0 * q + x
                if b == lost:
                    continue
                # the lost node's symbol coupled with (b, z): C_b = g U_a
                # + U_b gives U_a, and C_a = U_a + g U_b
                _a_node, za = self.partner(b, z)
                u_a = MUL[reference.gf_inv(self.g)][
                    helpers[b][at[z]] ^ u_col[x]]
                out[za] = self.coupled(u_a, u_col[x])
        return out


def codewords(shards: np.ndarray, code: Clay) -> np.ndarray:
    """Shards uint8 [r, S*4096] -> their stripes' nodes uint8
    [r, Z, S, s]: axis 2 is the stripe, a codeword of its own."""
    r = shards.shape[0]
    return shards.reshape(r, -1, code.Z, reference.UNIT // code.Z
                          ).transpose(0, 2, 1, 3)


def shards_of(nodes: np.ndarray) -> np.ndarray:
    """codewords' inverse: uint8 [r, Z, S, s] -> uint8 [r, S*4096]."""
    return nodes.transpose(0, 2, 1, 3).reshape(nodes.shape[0], -1)


def clay_shards(payload: bytes, cfg: dict) -> np.ndarray:
    """All k+m shards of one object under upstream's 4 KiB striping,
    every stripe a clay codeword -> uint8 [k+m, S/k]."""
    code = Clay(cfg)
    data = reference.split(payload, code.k)
    parity = code.encode(codewords(data, code))
    return np.concatenate([data, shards_of(parity)])


def mds_shards(payload: bytes, cfg: dict) -> np.ndarray:
    """The same with the coupling left out (U = C everywhere): the scalar
    MDS code alone, which decodes as well and repairs from whole shards
    only.  What the control stores."""
    code = Clay(cfg)
    data = reference.split(payload, code.k)
    return np.concatenate([data, reference.gf_matmul(code.G[code.k:], data)])


class RefStore:
    """write_full / read / stored shards / repair of a clay pool, as the
    configuration states them.  `coupled=False` stores the scalar MDS
    code's shards: the guarantee the control breaks."""

    def __init__(self, cfg: dict, coupled: bool = True) -> None:
        self.cfg, self.code = cfg, Clay(cfg)
        self.shards_of = clay_shards if coupled else mds_shards
        self.shards: dict = {}

    def write_full(self, oid: str, data: bytes) -> None:
        self.shards[oid] = self.shards_of(data, self.cfg)

    def read(self, oid: str) -> bytes:
        return reference.join(self.shards[oid][:self.code.k])

    def stored(self, oid: str) -> dict:
        sh = self.shards[oid]
        return {s: (sh[s].tobytes(), int(c))
                for s, c in enumerate(reference.crc32c_rows(sh))}

    def repair(self, oid: str, lost: int) -> bytes:
        """From the repair sub-chunks of every stripe of the others."""
        code = self.code
        nodes = codewords(self.shards[oid], code)
        layers = code.repair_layers(lost)
        return shards_of(code.repair(lost, {
            i: nodes[i][layers]
            for i in range(code.n) if i != lost})[None]).tobytes()

    def pg_of(self, oid: str) -> int:
        return 0

    def close(self) -> None:
        self.shards.clear()
