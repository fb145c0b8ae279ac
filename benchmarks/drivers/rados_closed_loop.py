"""Traffic kind `rados_closed_loop`: `rados bench`'s write shape
(obj_bencher.h: a closed loop of `in_flight` client threads) with seeded,
distinct payloads.  Each thread calls `ioctx.write_full` of a new object
until the window ends; the clock is around that call alone.

Set-up boots the configuration's cluster, creates the pool (its warmup
with it), then drives every batch width of `warm_batch_widths` through
the queue once, so that the encode and crc programs of each width are
compiled before the clock starts.  The queue coalesces only jobs of one
codec object, and every PG has its own, so `w + 1` writes to objects of
ONE PG are started together: one job goes to the device and `w` wait
behind it, which the queue's worker then takes as one batch.  The queue's
own histogram of batch widths says whether it did; a width that did not
form is driven again.

`check` holds what the timed operations left behind to `reference`: every
acknowledged object read back, and the shards and recorded crcs the OSDs
hold of a sample drawn by the seed.
"""

from __future__ import annotations

import itertools
import threading
import time

import numpy as np

import reference


WARM_TRIES = 6   # rounds at most for one batch width; the first has done


def payload(seed: int, idx: int, size: int) -> bytes:
    return np.random.default_rng([seed, idx, size]).bytes(size)


def bucket(jobs: int) -> int:
    """The queue pads a batch to a power of two of jobs (shapebucket's
    covering bucket): widths of one bucket run one program."""
    return 1 << (jobs - 1).bit_length()


class Cluster:
    """The system under test behind the calls `check` needs."""

    def __init__(self, cfg: dict) -> None:
        from ceph_tpu.vstart import VStartCluster

        self.c = VStartCluster(n_mons=cfg["mons"], n_osds=cfg["osds"],
                               warmup=True)
        self.pool = self.c.create_pool(
            "bench", size=cfg["k"] + cfg["m"], pool_type="erasure",
            ec_profile=cfg["ec_profile"], pg_num=cfg["pg_num"])
        io = self.c.client().ioctx(self.pool)
        self.write_full, self.read = io.write_full, io.read

    def pg_of(self, oid: str):
        return self.c.leader().osdmap.object_to_pg(self.pool, oid)

    def stored(self, oid: str) -> dict:
        """{shard: (bytes, recorded crc32c)} as the acting OSDs hold it."""
        from ceph_tpu.osd import types as ot
        from ceph_tpu.osd.backend import hinfo_decode
        from ceph_tpu.store.objectstore import Collection, GHObject

        m = self.c.leader().osdmap
        pgid = self.pg_of(oid)
        coll = Collection(ot.pgid_str(pgid) + "_head")
        out = {}
        for s, osd in enumerate(m.pg_to_up_acting(pgid)[2]):
            store = self.c.osds[int(osd)].store
            g = GHObject(oid, shard=s)
            if store.exists(coll, g):
                _size, crc, _valid = hinfo_decode(
                    store.getattr(coll, g, "hinfo"))
                out[s] = (bytes(store.read(coll, g)), int(crc))
        return out

    def close(self) -> None:
        self.c.shutdown()


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 system=None) -> None:
        self.cfg, self.t, self.seed = cfg, traffic, seed
        self.sys = system          # None: boot the program's cluster
        self.ops: list = []        # (index, t0, t1, ok) of the window
        self.wrong = 0             # objects read back other than written
        self._lock = threading.Lock()

    # -- set-up ------------------------------------------------------------
    def setup(self) -> None:
        import jax

        self._span = jax.profiler.TraceAnnotation   # a no-op when not tracing
        self.phases: dict = {}
        mark = time.monotonic()
        if self.sys is None:
            from ceph_tpu.tpu import devwatch
            from ceph_tpu.tpu.queue import default_queue

            self.dw, self.q = devwatch.watch(), default_queue()
            self.sys = Cluster(self.cfg)
            warm = self.dw.warmup_stats or {}
            if not warm.get("done") or any(
                    "(error" in s for s in warm.get("skipped", [])):
                raise RuntimeError(f"pool warmup incomplete: {warm}")
        else:
            self.dw = self.q = None
        self.phases["boot_and_pool_s"] = time.monotonic() - mark
        mark = time.monotonic()
        self._warm()
        self.phases["warm_ops_s"] = time.monotonic() - mark

    def _warm(self) -> None:
        """Each width of `warm_batch_widths` as one batch, before the
        clock starts; the set-up note says what it took and what formed."""
        t, formed = self.t, set()
        first = dict(self.q.batch_jobs) if self.q else {}
        pg = self.sys.pg_of("warm_0")
        names = (n for n in itertools.count()
                 if self.sys.pg_of(f"warm_{n}") == pg)
        self.phases["warm_tries"] = took = {}
        self.phases["warm_writes"] = 0
        for w in t["warm_batch_widths"]:
            for tries in range(1, WARM_TRIES + 1):
                if bucket(w) in formed:
                    break
                before = dict(self.q.batch_jobs) if self.q else {}
                batch = list(itertools.islice(names, w + 1))
                self._fan(lambda n: self._op(-1 - n, record=False), batch,
                          w + 1)
                now = dict(self.q.batch_jobs) if self.q else {w: 1}
                formed |= {bucket(x) for x, n in now.items()
                           if n > before.get(x, 0)}
                took[str(w)] = tries
                self.phases["warm_writes"] += w + 1
        if self.q:
            self.phases["warm_batches"] = {
                str(x): n - first.get(x, 0)
                for x, n in sorted(self.q.batch_jobs.items())}
        self.phases["warm_unformed"] = sorted(
            {bucket(w) for w in t["warm_batch_widths"]} - formed)

    def _fan(self, fn, items, in_flight=None) -> None:
        import concurrent.futures as cf

        with cf.ThreadPoolExecutor(in_flight or self.t["in_flight"]) as ex:
            list(ex.map(fn, items))

    # -- one operation -------------------------------------------------------
    def _op(self, i: int, record: bool = True) -> None:
        size = self.t["object_bytes"]
        # warm-up writes (i < 0) take names and payloads of their own
        oid = f"obj_{i}" if i >= 0 else f"warm_{-1 - i}"
        data = payload(self.seed, i if i >= 0 else (1 << 40) - i, size)
        ok = True
        with self._span("bench:write"):
            t0 = time.monotonic()
            try:
                self.sys.write_full(oid, data)
            except Exception:  # noqa: BLE001 — a failed op is counted
                ok = False
            t1 = time.monotonic()
        if record:
            self.ops.append((i, t0, t1, ok))

    def counters(self) -> dict:
        if self.q is None:
            return {}
        q, hist = self.q, self.q.perf.dump()
        out = {"queue.jobs": q.jobs, "queue.batches": q.batches,
               "devwatch.compiles": self.dw.compile_totals()["compiles"]}
        for fam, st in self.dw.dump()["families"].items():
            out["devwatch.compiles." + fam] = st["compiles"]
        for h in ("lat_device_us", "lat_encq_wait_us"):
            out[f"queue.{h}.sum"] = hist[h]["sum"]
            out[f"queue.{h}.count"] = hist[h]["count"]
        return out

    # -- the window --------------------------------------------------------------
    def window(self, seconds: float, tracer) -> dict:
        t = self.t
        size = t["object_bytes"]
        nxt = itertools.count()
        begin = time.monotonic()
        end = begin + seconds

        def client() -> None:
            while time.monotonic() < end:
                self._op(next(nxt))

        slices: list = []   # [t0, t1] of the traced slice, this clock

        def traced() -> None:
            """The window's last `trace_for_s` seconds, all clients still
            at work: the profiler then writes its trace (half a minute
            and more for the crc loop's half a million device events)
            after the window has closed, not across it."""
            length = min(t["trace_for_s"], seconds / 2)
            time.sleep(max(0.0, end - length - time.monotonic()))
            with tracer.slice():
                s0 = time.monotonic()
                time.sleep(length)
                slices.append([s0, time.monotonic()])

        threads = [threading.Thread(target=client, name=f"bench-client-{n}")
                   for n in range(t["in_flight"])]
        if tracer is not None:
            threads.append(threading.Thread(target=traced, name="bench-trace"))
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        lat = [(t1 - t0) * 1e3 if ok else float("inf")
               for _i, t0, t1, ok in self.ops]
        acked = sum(1 for _i, _t0, t1, ok in self.ops if ok and t1 <= end)
        slice_: dict = {}
        if slices:
            # work completed in the slice: each acknowledged write counts
            # by the share of its own span that lies inside it (a write's
            # device work falls somewhere in its span; the slice is
            # shorter than a write, so whole writes inside it are none)
            objects = sum(
                max(0.0, min(t1, s1) - max(t0, s0)) / (t1 - t0)
                for _i, t0, t1, ok in self.ops if ok
                for s0, s1 in slices)
            slice_ = {"seconds": sum(s1 - s0 for s0, s1 in slices),
                      "objects": objects, "object_bytes": size}
        p95 = float(np.percentile(lat, 95))
        return {
            "metrics": {"write_MBps": acked * size / 1e6 / seconds,
                        "op_p95_ms": min(p95, 1e9)},
            "attempted": len(self.ops),
            "failed": sum(1 for o in self.ops if not o[3]),
            "slice": slice_,
            "notes": {"acked_in_window": acked,
                      "op_p50_ms": float(np.percentile(lat, 50)),
                      "drain_s": max(o[2] for o in self.ops) - end,
                      # [start, end] of every operation in ms from the
                      # window's start, for a look at a shorter window or
                      # at the tail over time
                      "op_spans_ms": [[round((t0 - begin) * 1e3),
                                       round((t1 - begin) * 1e3)]
                                      for _i, t0, t1, _ok in self.ops]},
        }

    # -- what the timed operations left behind --------------------------------
    def check(self) -> dict:
        cfg, t = self.cfg, self.t
        size = t["object_bytes"]
        done = [i for i, _t0, _t1, ok in self.ops if ok]

        def readback(i: int) -> None:
            try:
                same = self.sys.read(f"obj_{i}") == payload(self.seed, i, size)
            except Exception:  # noqa: BLE001 — an acknowledged write is lost
                same = False
            if not same:
                with self._lock:
                    self.wrong += 1

        self._fan(readback, done)
        rng = np.random.default_rng([self.seed, 4])
        some = rng.choice(done, size=min(t["check_shards_of"], len(done)),
                          replace=False) if done else []
        n = cfg["k"] + cfg["m"]
        want, got_crcs, missing, bad = [], [], 0, 0
        for i in some:
            held = self.sys.stored(f"obj_{int(i)}")
            missing += n - len(held)
            sh = reference.rs_shards(payload(self.seed, int(i), size),
                                     cfg["k"], cfg["m"])
            for s, (data, crc) in held.items():
                bad += data != sh[s].tobytes()
                want.append(sh[s])
                got_crcs.append(crc)
        crcs = reference.crc32c_rows(np.stack(want)) if want else []
        return {"ops_failed": [len(self.ops) - len(done), 0],
                "no_op_compared": [int(not done), 0],
                "readback_wrong": [self.wrong, 0],
                "shards_missing": [missing, 0],
                "shards_wrong": [int(bad), 0],
                "crcs_wrong": [int(sum(int(a) != b for a, b in
                                       zip(crcs, got_crcs))), 0]}

    def close(self) -> None:
        if self.sys is not None:
            self.sys.close()
        if self.q is not None:
            self.q.stop()


def control(cfg: dict, traffic: dict, seed: int) -> Driver:
    """The reference store in the program's place with one guarantee
    broken: a write acknowledged with the last shard uncommitted (ack
    after k+m-1)."""
    store = reference.RefStore(cfg["k"], cfg["m"],
                               ack_after=cfg["k"] + cfg["m"] - 1)
    return Driver(cfg, traffic, seed, system=store)
