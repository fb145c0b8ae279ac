"""Shared in-jit loop measurement harness for engine benchmarks.

A per-dispatch timing measures the host round trip, not the kernel, so
every EC engine benchmark measures the same way: iterations loop
INSIDE one jit, each iteration XORs an
anti-hoisting seed into the input (so XLA cannot hoist the encode as
loop-invariant), each iteration's output reduces to a SCALAR digest
accumulated across the loop (sum_digest_runner; the xor-fold variant
seeded_loop_runner survives for comparisons but adds a full-size
accumulator pass a pallas_call cannot fuse away), and only that digest
is fetched.  bench.py measures through THIS module — the protocol
lives in one place (review finding: four hand copies drift).

At FIXED small iteration counts every engine "measures"
(iters x size)/RTT — wall time is one dispatch round trip no matter
the work.  `calibrated_rate` is the fix: grow the in-jit iteration
count until one dispatch's wall clock dwarfs the round trip, capped
at `cap_s`.  The envelope this was calibrated against (a link with a
tens-of-ms round trip) has not been re-measured on the current chip
tool; figures taken there are not repeated here.
"""

from __future__ import annotations

import functools
import time

from ceph_tpu.tpu.devwatch import instrumented_jit


LANES = 128


def gen_planes(k: int, T: int, interleaved: bool = False):
    """Device-resident deterministic batch: u32 planes (k,T,128) (or
    (T,k,128) interleaved) from iota -> splitmix mix32.  The numpy twin
    for oracle pins is mix32.mix_np over the same iota — keeping the
    generator HERE (one copy) is what makes bench/minibench/tune
    numbers and their pins comparable."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ceph_tpu.ops.mix32 import mix_jnp

    shape = (T, k, LANES) if interleaved else (k, T, LANES)

    @functools.partial(instrumented_jit, family="benchloop")
    def g():
        return mix_jnp(lax.iota(jnp.uint32, k * T * LANES).reshape(shape))

    return g()


def xla_swar_engine(net, R: int):
    """enc(words3, seed) for the XLA-graph SWAR network `net` over
    planar (k, T, 128) batches -> (R, T, 128)."""
    def enc(w3, seed):
        k, T, _ = w3.shape
        return net((w3 ^ seed[0]).reshape(k, -1)).reshape(R, T, LANES)

    return enc


def seeded_loop_runner(enc, out_shape, iters: int):
    """jit'd runner: enc(words, seed_u32[1]) -> u32[out_shape] folded
    over `iters` seeded iterations; returns a scalar digest."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @functools.partial(instrumented_jit, family="benchloop")
    def run(w3):
        def body(i, acc):
            s = jnp.full((1,), i, jnp.uint32)
            return acc ^ enc(w3, s)
        o = lax.fori_loop(0, iters, body, jnp.zeros(out_shape, jnp.uint32))
        return jnp.sum(o & 0xFF)

    return run


def timed_best(run, w3, reps: int = 2) -> float:
    """Compile+warm once (digest fetch = the only true sync on this
    rig), then best-of-`reps` wall seconds."""
    int(run(w3))
    best = 1e18
    for _ in range(reps):
        t0 = time.perf_counter()
        int(run(w3))
        best = min(best, time.perf_counter() - t0)
    return best


def loop_rate_gbps(enc, w3, out_shape, iters: int, object_bytes: int,
                   reps: int = 2) -> float:
    """GB/s of `enc` over `iters` in-jit iterations on batch `w3`."""
    dt = timed_best(seeded_loop_runner(enc, out_shape, iters), w3, reps)
    return iters * object_bytes / dt / 1e9


def sum_digest_runner(enc, iters: int):
    """jit'd runner: per-iteration scalar digest (sum of out & 0xff)
    accumulated as a scalar.  Cheaper than the xor-fold runner for
    pallas engines: the fold's full-size accumulator pass cannot be
    fused into a pallas_call the way XLA fuses it into its own graph."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @functools.partial(instrumented_jit, family="benchloop")
    def run(w3):
        def body(i, acc):
            s = jnp.full((1,), i, jnp.uint32)
            return acc + jnp.sum(enc(w3, s) & 0xFF, dtype=jnp.uint32)
        return lax.fori_loop(0, iters, body, jnp.uint32(0))

    return run


def calibrate_loop(make_run, *, start_iters: int = 16,
                   target_s: float = 1.5, cap_s: float = 25.0,
                   max_iters: int = 1 << 20):
    """(iters, wall_s): grow an in-jit iteration count until one
    dispatch's wall clock reaches `target_s` — the only honest timing
    where the dispatch round trip swallows fixed-iteration runs whole
    (see module docstring).  `make_run(iters)` returns a zero-arg callable
    whose invocation runs + truly syncs (fetches) one dispatch.
    The projected next dispatch is clamped to `cap_s` (no single
    dispatch runs for minutes) and `max_iters`."""
    target_s = min(target_s, cap_s)  # a target past the cap can't halt
    iters = int(start_iters)
    while True:
        run = make_run(iters)
        run()  # compile + warm (fetch = the only true sync)
        t0 = time.perf_counter()
        run()
        dt = time.perf_counter() - t0
        if dt >= target_s or iters >= max_iters:
            return iters, dt
        ips = iters / max(dt, 1e-4)  # iters/s, floor-biased by the RTT
        want_s = min(target_s * 1.3, cap_s)
        nxt = max(iters * 2, int(ips * want_s))
        # real dispatch-wall clamp on BOTH growth arms (the doubling
        # arm can outrun the projection when target_s approaches cap_s)
        iters = min(max_iters, nxt, max(iters, int(ips * cap_s)))


def calibrated_rate(enc, w3, object_bytes: int, *, start_iters: int = 16,
                    target_s: float = 1.5, cap_s: float = 25.0,
                    max_iters: int = 1 << 20, runner=sum_digest_runner):
    """(gbps, iters, wall_s) for an engine over batch `w3` under the
    calibrated protocol (see calibrate_loop)."""
    def make_run(iters):
        run = runner(enc, iters)
        return lambda: int(run(w3))

    iters, dt = calibrate_loop(make_run, start_iters=start_iters,
                               target_s=target_s, cap_s=cap_s,
                               max_iters=max_iters)
    return object_bytes * iters / dt / 1e9, iters, dt
