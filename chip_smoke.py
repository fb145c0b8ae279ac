#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on
the chip: the served EC path and the device-resident CRUSH sweep, once,
through the entry points a user calls, on ONE TPU.

    python chip_smoke.py            # needs a TPU; anything else exits 1

One process, which holds the chip; the cluster (VStartCluster) runs
inside it as threads.  Sets no JAX_PLATFORMS, no XLA_FLAGS, no x64.
Every phase fails the run on its own (an exception ends the process
non-zero); one JSON object per phase is printed as it completes, and
the LAST line of stdout is the contract's

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Phases:

  device  jax.devices(): refuses anything but a TPU before any work
  build   make -C csrc clean && make: the native library and the
          CPython extension are built on THIS machine from tracked
          sources (a copied tree carries ignored -march=native objects)
  ec      BASELINE config 2 at a deployment's width: 12 OSDs, pool
          plugin=isa technique=reed_sol_van k=8 m=4, >=256 distinct
          1 MiB objects from --seed written with 16 in flight through
          ioctx.write_full, all read back byte for byte; kill one
          acting OSD, degraded read of a sample with the same 16 in
          flight (decode through the queue, no kernel compiled in
          line); a handful of 4 KiB / 64 KiB objects (small-width
          route + fused device crc)
  oracle  the same stripes through the queue's device encode vs the C
          oracle _native.rs_encode, for every chunk width the run used;
          one write batch's device work timed apart (encode / crc)
  crush   BASELINE config 6: sweep_device over the 1024-OSD straw2 map
          for 10,485,760 ids, overflow false, first 100k placements
          equal to _native.do_rule looped on the host

--rehearse allows a non-TPU backend so the control flow can be checked
in a sandbox at a tiny size (--objects 4 --crush-ids 8192); it is a
rehearsal, never a chip result, and every line it prints says so via
the device block.
"""

from __future__ import annotations

import argparse
import concurrent.futures as cf
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OBJ_SIZE = 1 << 20          # never cut: the profile's stripe
SMALL_SIZES = (4096, 65536)
IN_FLIGHT = 16             # writes, read-back and degraded reads alike
FULL_OBJECTS = 256
FULL_CRUSH_IDS = 20 << 19   # 10,485,760
CRUSH_HEAD = 100_000
EC_PROFILE = "plugin=isa technique=reed_sol_van k=8 m=4"
FAMILIES = ("gf256_pallas", "gf256_swar", "crc32c_device", "gf2_matmul",
            "gf256_clay", "crush_mapper")


def require(ok: bool, what: str) -> None:
    """A failed check fails the run (not an assert: -O must not skip it)."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def emit(phase: str, **kw) -> None:
    print(json.dumps({"phase": phase, **kw}), flush=True)


def payload(seed: int, idx: int, size: int) -> bytes:
    import numpy as np

    return np.random.default_rng([seed, idx, size]).bytes(size)


def fam_table(dw) -> dict:
    out = {}
    for f in FAMILIES:
        st = dw.family_stats(f)
        if st["compiles"] or st["dispatches"] or st["cache_hits"]:
            out[f] = {k: st[k] for k in (
                "compiles", "compile_s", "persist_hits", "cache_hits",
                "dispatches", "warmup", "cold", "rogue")}
    return out


class Clock:
    """Wall seconds of a phase and the share devwatch saw inside
    instrumented compiles (trace + compile + first execute; nested
    instrumented jits count twice, hence the cap at the wall)."""

    def __init__(self, dw) -> None:
        self.dw = dw

    def __enter__(self):
        self.t0 = time.monotonic()
        self.c0 = self.dw.dump()["totals"]["compile_seconds"]
        return self

    def __exit__(self, *exc) -> None:
        self.wall = time.monotonic() - self.t0
        self.compile = min(self.wall, self.dw.dump()["totals"][
            "compile_seconds"] - self.c0)

    def fields(self) -> dict:
        return {"wall_s": round(self.wall, 3),
                "compile_s": round(self.compile, 3),
                "steady_s": round(max(self.wall - self.compile, 0.0), 3)}


def phase_device(rehearse: bool) -> dict:
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" and not rehearse:
        print(f"chip_smoke: no TPU (jax found {device}); refusing to "
              "run", file=sys.stderr)
        sys.exit(1)
    emit("device", **device, jax=jax.__version__,
         rehearsal=device["platform"] != "tpu")
    return device


def phase_build() -> None:
    t0 = time.monotonic()
    csrc = os.path.join(REPO, "csrc")
    subprocess.run(["make", "-C", csrc, "-s", "clean"], check=True)
    subprocess.run(["make", "-C", csrc, "-s"], check=True,
                   stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    built = [f for f in ("libceph_tpu_native.so", "_fastec.so")
             if os.path.exists(os.path.join(REPO, "ceph_tpu", f))]
    require(len(built) == 2, f"native build incomplete: {built}")
    emit("build", wall_s=round(time.monotonic() - t0, 3), built=built)


def phase_ec(args, on_tpu: bool) -> None:
    from ceph_tpu.tpu import devwatch
    from ceph_tpu.tpu.queue import default_queue
    from ceph_tpu.vstart import VStartCluster

    dw = devwatch.watch()
    q = default_queue()
    with Clock(dw) as boot:
        c = VStartCluster(n_mons=1, n_osds=12, warmup=True)
    with c:
        try:
            _ec_traffic(args, c, dw, q, boot, on_tpu)
        except BaseException:
            # say what state the cluster was in, then fail the run
            _diagnose(c, dw, q)
            raise

    hist = {str(k): v for k, v in sorted(q.batch_jobs.items())}
    dhist = {str(k): v for k, v in sorted(q.dec_batch_jobs.items())}
    emit("ec.queue", batches=q.batches, jobs=q.jobs,
         bytes_in=q.bytes_in,
         # host clock around the batches, and of it the time the
         # worker was blocked on the device (dev.wait spans)
         batch_host_s=round(q.device_time_s, 3),
         dev_wait_s=round(dw.wait_s, 3),
         batch_jobs_hist=hist, dec_batch_jobs_hist=dhist,
         devpath=q.stats.snapshot(), families=fam_table(dw))
    require(q.batches > 0, "no batch ever reached the device queue")
    if on_tpu:
        for fam in ("gf256_pallas", "crc32c_device"):
            st = dw.family_stats(fam)
            require(st["compiles"] + st["persist_hits"] > 0,
                    f"family {fam} never compiled on this backend: {st}")


def _diagnose(c, dw, q) -> None:
    """Failure forensics to stderr: where every thread is, what the
    queue was doing, cluster health."""
    import faulthandler

    err = sys.stderr
    print("chip_smoke: EC phase failed; thread dump follows", file=err)
    faulthandler.dump_traceback(file=err, all_threads=True)
    print(f"chip_smoke: queue batches={q.batches} jobs={q.jobs} "
          f"hist={q.batch_jobs} inflight={q.inflight_batch()} "
          f"families={fam_table(dw)}", file=err)
    print(f"chip_smoke: {c.command({'prefix': 'health detail'})}",
          file=err, flush=True)


def _ec_traffic(args, c, dw, q, boot, on_tpu) -> None:
    n_obj = args.objects
    names = [(f"obj_{i}", i, OBJ_SIZE) for i in range(n_obj)]
    small = [(f"small_{s}_{i}", 1_000_000 + i, s)
             for s in SMALL_SIZES for i in range(4)]

    with Clock(dw) as mkpool:
        pool = c.create_pool("smoke", size=12, pool_type="erasure",
                             ec_profile=EC_PROFILE)
    warm = dw.warmup_stats or {}
    failed = [s for s in warm.get("skipped", []) if "(error" in s]
    emit("ec.boot",
         **{"boot_" + k: v for k, v in boot.fields().items()},
         **{"pool_" + k: v for k, v in mkpool.fields().items()},
         # grace overruns seen by the OSDs' heartbeat loops so far:
         # reported to the mon / held because a compile ran meanwhile
         heartbeat={k: sum(o.perf.value(k) for o in c.osds.values())
                    for k in ("heartbeat_misses",
                              "heartbeat_compile_holds")},
         warmup={k: warm.get(k) for k in (
             "runs", "seconds", "buckets_warmed", "pending", "skipped",
             "done")},
         families=fam_table(dw))
    require(not failed, f"warmup items failed: {failed}")
    require(warm.get("done"), f"warmup left items pending: {warm}")

    io = c.client().ioctx(pool)

    def put(item):
        oid, idx, size = item
        io.write_full(oid, payload(args.seed, idx, size))
        return size

    def check(item):
        oid, idx, size = item
        got = io.read(oid)
        require(got == payload(args.seed, idx, size),
                f"read-back of {oid} ({size} B) differs")
        return size

    def run(fn, items):
        with cf.ThreadPoolExecutor(IN_FLIGHT) as ex:
            return sum(ex.map(fn, items))

    b0 = q.batches
    with Clock(dw) as wr:
        nbytes = run(put, names)
    emit("ec.write", objects=n_obj, object_bytes=OBJ_SIZE,
         in_flight=IN_FLIGHT, bytes=nbytes, profile=EC_PROFILE, osds=12,
         objects_cut_from=FULL_OBJECTS if n_obj < FULL_OBJECTS else None,
         **wr.fields(), queue_batches=q.batches - b0)
    with Clock(dw) as sm:
        run(put, small)
        run(check, small)
    emit("ec.small", objects=len(small), sizes=list(SMALL_SIZES),
         readback="exact", **sm.fields())
    with Clock(dw) as rd:
        nbytes = run(check, names)
    emit("ec.read", objects=n_obj, bytes=nbytes, readback="exact",
         **rd.fields())

    # degraded read: kill an acting member that holds a DATA shard of
    # obj_0's PG (position 1: not the primary), wait for the map to
    # notice, read a sample back through the decode path
    m = c.leader().osdmap
    pgid = m.object_to_pg(pool, "obj_0")
    acting = m.pg_to_up_acting(pgid)[2]
    victim = int(acting[1])
    dec0 = sum(q.dec_batch_jobs.values())
    with Clock(dw) as dg:
        c.kill_osd(victim)
        c.wait_for(lambda: not bool(
            c.leader().osdmap.osd_state_up[victim]),
            timeout=120.0, what=f"osd.{victim} marked down")
        sample = names[: max(1, min(32, n_obj))] + small
        t_down = time.monotonic()
        pallas0 = dw.family_stats("gf256_pallas")
        nbytes = run(check, sample)
    dec_jobs = sum(q.dec_batch_jobs.values()) - dec0
    pallas1 = dw.family_stats("gf256_pallas")
    inline = pallas1["compiles"] - pallas0["compiles"]
    emit("ec.degraded", killed_osd=victim, sample_objects=len(sample),
         in_flight=IN_FLIGHT, bytes=nbytes, readback="exact",
         decode_jobs=dec_jobs,
         down_detect_s=round(t_down - dg.t0, 3),
         read_s=round(dg.wall - (t_down - dg.t0), 3),
         decode_kernel_compiles=inline, **dg.fields())
    require(dec_jobs > 0, "degraded reads never reached the decode queue")
    # the recovery matrix is an operand of the decode program and the
    # warmup compiled every width the queue can send: a survivor
    # signature must cost no compile on the queue's one worker
    require(inline == 0 or not on_tpu,
            f"{inline} decode kernels compiled in line after warmup")


def phase_oracle(args) -> None:
    """Device encode/decode through the queue vs the C oracle, at
    every chunk width the EC phase used (and one coalesced batch)."""
    import numpy as np

    from ceph_tpu import _native
    from ceph_tpu.ec import codec_from_profile
    from ceph_tpu.tpu import devwatch
    from ceph_tpu.tpu.queue import default_queue

    dw = devwatch.watch()
    q = default_queue()
    codec = codec_from_profile(EC_PROFILE)
    coding = np.ascontiguousarray(codec.coding, dtype=np.uint8)
    rng = np.random.default_rng([args.seed, 7])
    widths = {codec.get_chunk_size(s) for s in (OBJ_SIZE,) + SMALL_SIZES}
    rows = []

    def calls(family: str) -> int:
        st = dw.family_stats(family)
        return st["dispatches"] + st["compiles"]

    for w in sorted(widths) + [8 * max(widths)]:
        planes = rng.integers(0, 256, size=(codec.k, w), dtype=np.uint8)
        before = {f: calls(f) for f in FAMILIES}
        got = np.asarray(q.encode(codec, planes))
        want = _native.rs_encode(coding, planes)
        require(got.shape == want.shape and np.array_equal(got, want),
                f"device encode != C oracle at width {w}")
        # decode: lose data shards 1 and 4, recover from the rest
        shards = np.concatenate([planes, want])
        avail = {i: shards[i] for i in range(codec.k + codec.m)
                 if i not in (1, 4)}
        data = np.asarray(q.decode_data(codec, avail))
        require(np.array_equal(data, planes),
                f"device decode != original at width {w}")
        ran = [f for f in FAMILIES if calls(f) > before[f]]
        rows.append({"chunk_width": w, "engine": ran,
                     "encode": "== _native.rs_encode",
                     "decode": "== original"})

    # one 1 MiB write's batch, its device calls timed apart on the host
    # clock (upload and fetch included, as the queue's worker pays
    # them): where lat_device_us goes.  Medians of 7 after a warm call.
    import statistics

    from ceph_tpu.ops import gf256_swar
    from ceph_tpu.ops.crc32c_device import crc32c_rows

    w = codec.get_chunk_size(OBJ_SIZE)
    planes = rng.integers(0, 256, size=(codec.k, w), dtype=np.uint8)
    full = np.concatenate([planes, _native.rs_encode(coding, planes)])
    rec, _bits = codec.recovery_matrix(list(range(codec.m, 12))[:codec.k])
    surv = np.ascontiguousarray(full[codec.m:][:codec.k])

    def median_ms(fn) -> float:
        fn()
        ts = []
        for _ in range(7):
            t0 = time.perf_counter()
            fn()
            ts.append((time.perf_counter() - t0) * 1e3)
        return statistics.median(ts)

    split = {
        "encode": median_ms(
            lambda: gf256_swar.gf_matmul_bytes(coding, planes)),
        "crc": median_ms(lambda: crc32c_rows(full, [0], [w])),
        "decode": median_ms(lambda: gf256_swar.gf_matmul_bytes(
            rec, surv, donate=True, operand=True)),
    }
    emit("oracle", checks=rows,
         write_batch_1job_ms={"chunk_width": w, **split})


def phase_crush(args, device) -> None:
    import jax.numpy as jnp
    import numpy as np

    from ceph_tpu import _native
    from ceph_tpu.crush import map as cmap
    from ceph_tpu.crush import mapper
    from ceph_tpu.tpu import devwatch

    dw = devwatch.watch()
    n_osds, n_hosts, nrep = 1024, 64, 3
    m, root = cmap.build_flat_cluster(n_osds, hosts=n_hosts)
    steps = [(cmap.OP_TAKE, root, 0),
             (cmap.OP_CHOOSELEAF_FIRSTN, nrep, 1),
             (cmap.OP_EMIT, 0, 0)]
    flat = m.flatten()
    dev_w = np.full(n_osds, 0x10000, dtype=np.uint32)
    n = args.crush_ids
    xs = jnp.arange(n, dtype=jnp.int32)

    with Clock(dw) as first:
        res, overflow = mapper.sweep_device(flat, steps, nrep, xs, dev_w)
        ovf = bool(overflow)  # sync: the whole dispatch
    require(not ovf, "fixup capacity overflow on a healthy map")
    t0 = time.monotonic()
    res, overflow = mapper.sweep_device(flat, steps, nrep, xs, dev_w)
    ovf = bool(overflow)
    steady = time.monotonic() - t0
    require(not ovf, "fixup capacity overflow on the steady call")

    head_n = min(CRUSH_HEAD, n)
    got = np.asarray(res[:head_n])  # one fetch, conformance only
    require(got.shape == (head_n, nrep) and got.dtype == np.int32,
            f"placements have shape {got.shape} dtype {got.dtype}")
    steps_arr = np.asarray(steps, dtype=np.int32).ravel()
    t0 = time.monotonic()
    want = np.full((head_n, nrep), cmap.ITEM_NONE, dtype=np.int32)
    for x in range(head_n):
        r = _native.do_rule(flat, steps_arr, x, nrep, dev_w)
        want[x, : len(r)] = r
    oracle_s = time.monotonic() - t0
    bad = int((got != want).any(axis=1).sum())
    require(bad == 0, f"{bad} of {head_n} placements differ from do_rule")
    require(int(got.min()) >= 0 and int(got.max()) < n_osds,
            "a placement names no OSD of the map")
    emit("crush", ids=n, ids_cut_from=(FULL_CRUSH_IDS
                                       if n < FULL_CRUSH_IDS else None),
         osds=n_osds, hosts=n_hosts, nrep=nrep, chunk=min(1 << 19, n),
         overflow=ovf, head_checked=head_n,
         head="== _native.do_rule",
         first_call_s=round(first.wall, 3),
         first_call_compile_s=round(first.compile, 3),
         steady_call_s=round(steady, 6),
         host_oracle_s=round(oracle_s, 3),
         device_kind=device["kind"], families=fam_table(dw))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--objects", type=int, default=FULL_OBJECTS,
                    help="1 MiB objects (cut the count, never the size)")
    ap.add_argument("--crush-ids", type=int, default=FULL_CRUSH_IDS)
    ap.add_argument("--rehearse", action="store_true",
                    help="allow a non-TPU backend (control-flow "
                         "rehearsal; never a chip result)")
    args = ap.parse_args()
    t_all = time.monotonic()

    device = phase_device(args.rehearse)
    on_tpu = device["platform"] == "tpu"
    phase_build()

    sys.path.insert(0, REPO)
    from ceph_tpu.tpu import devwatch, shapebucket

    # the one resolver: JAX_COMPILATION_CACHE_DIR if set, else
    # <repo>/.jax_cache — nothing else is set here
    shapebucket.setup_compile_cache()
    emit("cache", dir=shapebucket.compile_cache_dir(),
         from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")))

    phase_ec(args, on_tpu)
    phase_oracle(args)
    phase_crush(args, device)

    import jax

    dw = devwatch.watch()
    tot = dw.dump()["totals"]
    mem = jax.devices()[0].memory_stats() or {}
    emit("totals", wall_s=round(time.monotonic() - t_all, 3),
         compile_s=tot["compile_seconds"], compiles=tot["compiles"],
         rogue_compiles=tot["rogue_compiles"],
         cache_persist_hits=tot["cache_persist_hits"],
         cache_persist_misses=tot["cache_persist_misses"],
         peak_bytes_in_use=mem.get("peak_bytes_in_use"),
         bytes_limit=mem.get("bytes_limit"),
         families=fam_table(dw))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
