"""EC kernel bake-off: race the candidate GF engines across stripe sizes.

VERDICT round-1 asked for exactly this: (a) the bit-plane MXU matmul,
(b) the packed SWAR xor network, (c) a log/antilog VMEM-LUT gather, each
measured across a 4 KiB - 4 MiB stripe sweep (mirroring the reference's
ceph_erasure_code_benchmark, src/test/erasure-code/
ceph_erasure_code_benchmark.cc:151-190 and qa/workunits/erasure-code/
bench.sh:103-145), with a roofline read-out (bytes moved vs HBM peak).

Run on the chip (one process holds it):  python tools/bench_kernels.py
The roofline read-out needs the device's HBM peak: a device_kind that
is not in HBM_PEAK is an error, not a default (so a CPU run fails).
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

K, M = 8, 4
# published peak HBM bytes/s by jax device_kind (Google Cloud
# documentation, "TPU v5e": 819 GB/s)
HBM_PEAK = {"TPU v5 lite": 819e9}


def _bench(fn, warmup=2, iters=10):
    out = None
    for _ in range(warmup):
        out = fn()
    if hasattr(out, "block_until_ready"):
        out.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    if hasattr(out, "block_until_ready"):
        out.block_until_ready()
    return (time.perf_counter() - t0) / iters


def variant_bitplane_xla(md, xd):
    from ceph_tpu.ops import gf2_matmul

    return lambda: gf2_matmul.gf2_matmul_bytes_ref(md, xd)


def variant_bitplane_pallas(md, xd, tile_n):
    from ceph_tpu.ops import gf2_matmul

    return lambda: gf2_matmul.gf2_matmul_bytes_pallas(md, xd, tile_n=tile_n)


def variant_swar_xla(coding, x):
    """The SWAR xor network as an XLA graph over pre-packed u32 words
    resident on the device (n is a multiple of 4 here)."""
    import jax

    from ceph_tpu.ops import gf256_swar

    fn = gf256_swar._compiled_words(np.asarray(coding, dtype=np.uint8))
    wd = jax.device_put(np.ascontiguousarray(x).view(np.uint32))
    return lambda: fn(wd)


def variant_lut_gather(coding, xd):
    """Log/antilog VMEM gather: y += antilog[(log[c] + log[x]) % 255].

    Included for completeness of the bake-off; gathers serialize on the
    VPU so this is expected to lose badly.
    """
    import jax
    import jax.numpy as jnp

    from ceph_tpu.ec import gf

    logt, antit = gf.tables(8)
    log_d = jnp.asarray(np.concatenate([[0], logt[1:]]).astype(np.int32))
    anti_d = jnp.asarray(
        np.concatenate([antit[:255], antit[:255]]).astype(np.uint8))
    cmat = np.asarray(coding, dtype=np.uint32)

    @jax.jit
    def run(x):
        lx = log_d[x.astype(jnp.int32)]  # [k, n]
        nz = x != 0
        out = []
        for i in range(cmat.shape[0]):
            acc = jnp.zeros(x.shape[1], dtype=jnp.uint8)
            for j in range(cmat.shape[1]):
                c = int(cmat[i, j])
                if c == 0:
                    continue
                lc = int(gf.tables(8)[0][c])
                term = anti_d[lx[j] + lc]
                acc = acc ^ jnp.where(nz[j], term, 0)
            out.append(acc)
        return jnp.stack(out)

    return lambda: run(xd)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=int, nargs="*",
                    default=[4096, 65536, 1 << 20, 4 << 20])
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    import jax

    from ceph_tpu.ec import matrices
    from ceph_tpu.ops import gf2_matmul

    backend = jax.default_backend()
    kind = jax.devices()[0].device_kind
    if kind not in HBM_PEAK:
        raise SystemExit(
            f"bench_kernels: no HBM peak known for device_kind "
            f"{kind!r} (known: {sorted(HBM_PEAK)}); add it with its "
            "source rather than reporting a roofline share of 0")
    peak = HBM_PEAK[kind]
    coding = matrices.isa_cauchy(K, M)
    mbits = gf2_matmul.prepare_bitmatrix(coding)
    md = jax.device_put(mbits)
    rng = np.random.default_rng(0)

    print(f"# backend={backend} device_kind={kind} k={K} m={M} "
          f"(sizes are TOTAL object bytes; chunk = size/k)")
    results = []
    for size in args.sizes:
        n = max(256, size // K)  # chunk bytes
        x = rng.integers(0, 256, size=(K, n), dtype=np.uint8)
        xd = jax.device_put(x)
        row = {"object_bytes": K * n}
        variants = {
            "bitplane_xla": variant_bitplane_xla(md, xd),
            "swar_xla": variant_swar_xla(coding, x),
        }
        if backend != "cpu":
            for tile in (2048, 8192, 32768):
                if n % tile == 0:
                    variants[f"bitplane_pallas_t{tile}"] = (
                        variant_bitplane_pallas(md, xd, tile))
        if size <= (1 << 20):
            variants["lut_gather"] = variant_lut_gather(coding, xd)
        for name, fn in variants.items():
            try:
                dt = _bench(fn, iters=args.iters)
            except Exception as e:  # noqa: BLE001
                row[name] = f"error: {type(e).__name__}"
                continue
            gbps = K * n / dt / 1e9
            row[name] = round(gbps, 2)
            # roofline: encode moves (k+m)/k x input bytes over HBM
            moved = (K + M) * n
            row[name + "_hbm_frac"] = round((moved / dt) / peak, 3)
        results.append(row)
        print(json.dumps(row))


if __name__ == "__main__":
    main()
