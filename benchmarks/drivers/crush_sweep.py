"""Traffic kind `crush_sweep`: the operator's full-cluster remap.
`mapper.sweep_device` over the configuration's whole id range, again and
again until the window ends; placements stay on the device, one sync a
sweep (`bool(overflow)`).  The sweep in flight at the window's end runs
to completion and counts.  The seed rotates the id range (the same ids
in another order) and draws the positions that `check` compares with
`reference.CrushRef`.
"""

from __future__ import annotations

import time

import numpy as np

import reference


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int) -> None:
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.sweeps: list = []   # (t0, t1, placements on device, overflow)
        self.sweep_kw: dict = {}  # the control starves the fix-up stages

    def setup(self) -> None:
        import jax.numpy as jnp

        from ceph_tpu.crush import map as cmap
        from ceph_tpu.crush import mapper
        from ceph_tpu.tpu import devwatch

        cfg = self.cfg
        self.dw = devwatch.watch()
        per = cfg["num_osds"] // cfg["hosts"]
        m, root = cmap.build_flat_cluster(
            cfg["num_osds"], cfg["osd_weight"], hosts=cfg["hosts"])
        # the reference places on the map the configuration describes:
        # refuse to run where the program built another one
        if (root != cfg["root_bucket_id"]
                or list(m.buckets[root].items) != cfg["host_bucket_ids"]
                or any(list(m.buckets[h].items) != list(range(
                    i * per, (i + 1) * per))
                    for i, h in enumerate(cfg["host_bucket_ids"]))):
            raise RuntimeError("the program's map is not the configuration's")
        self.flat = m.flatten()
        self.steps = [(cmap.OP_TAKE, root, 0),
                      (cmap.OP_CHOOSELEAF_FIRSTN, cfg["num_rep"], 1),
                      (cmap.OP_EMIT, 0, 0)]
        self.dev_w = np.full(cfg["num_osds"], cfg["osd_weight"],
                             dtype=np.uint32)
        n = self.traffic.get("ids", cfg["ids"])
        self.chunk = min(cfg["chunk"], n)
        off = int(np.random.default_rng([self.seed, 1]).integers(0, n))
        self.xs_host = ((np.arange(n, dtype=np.int64) + off) % n
                        + cfg["min_x"]).astype(np.int32)
        self.xs = jnp.asarray(self.xs_host)
        self._sweep = mapper.sweep_device
        self.sweep()   # compiles (or loads) the three stage programs
        self.sweeps.pop()

    def sweep(self) -> None:
        import jax

        with jax.profiler.TraceAnnotation("bench:sweep"):
            t0 = time.monotonic()
            res, overflow = self._sweep(
                self.flat, self.steps, self.cfg["num_rep"], self.xs,
                self.dev_w, chunk=self.chunk, **self.sweep_kw)
            ovf = bool(overflow)   # the sync: the whole dispatch
            t1 = time.monotonic()
        self.sweeps.append((t0, t1, res, ovf))

    def counters(self) -> dict:
        return {"devwatch.compiles": self.dw.compile_totals()["compiles"]}

    def window(self, seconds: float, tracer) -> dict:
        """Sweeps back to back; with a tracer, the first one is the
        traced slice (set-up already ran one, so it is steady)."""
        n = len(self.xs_host)
        begin = time.monotonic()
        slice_ = {}
        while time.monotonic() - begin < seconds:
            if tracer is not None and not slice_:
                with tracer.slice():
                    self.sweep()
                slice_ = {"ids": n}
            else:
                self.sweep()
        end = self.sweeps[-1][1]
        return {
            "metrics": {"placements_per_s": len(self.sweeps) * n
                        / (end - begin)},
            "attempted": len(self.sweeps), "failed": 0, "slice": slice_,
            "notes": {"sweeps": len(self.sweeps), "sweep_s": [
                t1 - t0 for t0, t1, _r, _o in self.sweeps]},
        }

    def check(self) -> dict:
        """Every timed sweep, at positions drawn from the seed, against
        the reference's placements of the same ids."""
        rng = np.random.default_rng([self.seed, 2])
        pos = np.sort(rng.choice(len(self.xs_host), size=min(
            self.traffic["check_ids"], len(self.xs_host)), replace=False))
        want = reference.CrushRef(self.cfg).do_rule(self.xs_host[pos])
        wrong = overflowed = 0
        for _t0, _t1, res, ovf in self.sweeps:
            got = np.asarray(res)[pos]
            wrong += int((got != want).any(axis=1).sum())
            overflowed += int(ovf)
        return {"placements_wrong": [wrong, 0],
                "sweeps_overflowed": [overflowed, 0],
                "no_sweep_compared": [int(not self.sweeps), 0]}

    def close(self) -> None:
        self.sweeps.clear()


def control(cfg: dict, traffic: dict, seed: int) -> Driver:
    """The program's own sweep with the collision fix-up left out (its
    stage-2 and stage-3 capacities cut to the least the code allows):
    the one-pass sweep a later PR would be tempted by.  Placements that
    needed a retry differ from crush_do_rule's and the overflow flag is
    raised."""
    d = Driver(cfg, traffic, seed)
    d.sweep_kw = {"bad_div": 1 << 30, "bad2_div": 1 << 30}
    return d
