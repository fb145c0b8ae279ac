"""Traffic kind `crush_rule_sweep`: the operator's full-cluster remap
of a pool whose rule and map the configuration spells out: a layered
straw2 map (`layers`, as `crushtool --build` lays them out), the rule's
steps (`rule_steps`) and the device weights (`device_weights`, the
`--weight` marks of `crushtool --test`).

The loop is `crush_sweep`'s, inherited: `mapper.sweep_device` (here with
its own stage plan, no capacity arguments) over the configuration's
whole id range, again and again until the window ends; placements stay
on the device, one sync a sweep (`bool(overflow)`); the sweep in flight
at the window's end runs to completion and counts.  The seed rotates
the id range (the same ids in another order) and draws the positions
that `check` compares with `reference_crush_tree.CrushTreeRef`, every
column.
"""

from __future__ import annotations

import numpy as np

import reference_crush_tree
from drivers import crush_sweep


class Driver(crush_sweep.Driver):
    """`crush_sweep`'s loop (`sweep`, `window`, `close`) over a map, a
    rule and device weights built from the configuration's keys."""

    def setup(self) -> None:
        import jax.numpy as jnp

        from ceph_tpu.crush import map as cmap
        from ceph_tpu.crush import mapper
        from ceph_tpu.tpu import devwatch

        cfg = self.cfg
        self.dw = devwatch.watch()
        self.mapper = mapper
        layers = cfg["layers"]
        m, ids = cmap.build_layered_cluster(
            cfg["num_osds"], [(la["type_id"], la["size"]) for la in layers],
            cfg["osd_weight"])
        # the reference places on the map the configuration describes:
        # refuse to run where the program's builder hands out other ids
        if ids != [la["bucket_ids"] for la in layers]:
            raise RuntimeError("the program's map is not the configuration's")
        types = {la["type_name"]: la for la in layers}
        ops = {"set_chooseleaf_tries": cmap.OP_SET_CHOOSELEAF_TRIES,
               "set_choose_tries": cmap.OP_SET_CHOOSE_TRIES,
               "take": cmap.OP_TAKE, "emit": cmap.OP_EMIT,
               "choose_indep": cmap.OP_CHOOSE_INDEP,
               "chooseleaf_indep": cmap.OP_CHOOSELEAF_INDEP}
        self.steps = []
        for op, *args in cfg["rule_steps"]:
            if op == "take":
                args = [types[args[0]]["bucket_ids"][0], 0]
            elif op in ("choose_indep", "chooseleaf_indep"):
                args = [args[0], types[args[1]]["type_id"]
                        if args[1] != "osd" else 0]
            self.steps.append((ops[op], *(args + [0, 0])[:2]))
        self.flat = m.flatten()
        self.dev_w = reference_crush_tree.device_weights(cfg)
        n = self.traffic.get("ids", cfg["ids"])
        self.chunk = min(cfg["chunk"], n)
        off = int(np.random.default_rng([self.seed, 1]).integers(0, n))
        self.xs_host = ((np.arange(n, dtype=np.int64) + off) % n
                        + cfg["min_x"]).astype(np.int32)
        self.xs = jnp.asarray(self.xs_host)
        self._sweep = mapper.sweep_device   # default stage plan
        self.sweep()   # compiles (or loads) the plan's stage programs
        self.sweeps.pop()

    def counters(self) -> dict:
        """devwatch's compiles and the mapper's three totals; a program
        without the totals (the parent of PR 30) gives the first only."""
        totals = getattr(self.mapper, "sweep_totals", dict)()
        return {"devwatch.compiles": self.dw.compile_totals()["compiles"],
                **totals}

    def check(self) -> dict:
        """Every timed sweep, at positions drawn from the seed, against
        the reference's placements of the same ids: a row is wrong if
        any of its columns differs."""
        rng = np.random.default_rng([self.seed, 2])
        pos = np.sort(rng.choice(len(self.xs_host), size=min(
            self.traffic["check_ids"], len(self.xs_host)), replace=False))
        want = reference_crush_tree.CrushTreeRef(self.cfg).do_rule(
            self.xs_host[pos])
        wrong = overflowed = 0
        for _t0, _t1, res, ovf in self.sweeps:
            got = np.asarray(res[pos])
            wrong += int((got != want).any(axis=1).sum())
            overflowed += int(ovf)
        return {"placements_wrong": [wrong, 0],
                "sweeps_overflowed": [overflowed, 0],
                "no_sweep_compared": [int(not self.sweeps), 0]}


def control(cfg: dict, traffic: dict, seed: int) -> Driver:
    """The program's own sweep with both fix-up capacities starved (cut
    to the least the code allows): every lane keeps what one attempt a
    slot gave it.  Rows that needed a retry differ from crush_do_rule's
    and the overflow flag is raised."""
    d = Driver(cfg, traffic, seed)
    d.sweep_kw = {"bad_div": 1 << 30, "bad2_div": 1 << 30}
    return d
