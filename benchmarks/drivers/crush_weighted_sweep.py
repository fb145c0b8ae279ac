"""Traffic kind `crush_weighted_sweep`: the operator's full-cluster remap
of a replicated pool on a map whose CRUSH weights are its drives'
capacities: a layered straw2 map (`layers`, as `crushtool --build` lays
them out) with a weight for each device (`osd_weights`, what
`--reweight-item` leaves; a bucket's weight is the sum of its items'),
the rule's `firstn` steps (`rule_steps`) and the device (override)
weights (`device_weights`).

The loop, the counters and the window are `crush_rule_sweep`'s,
inherited: `mapper.sweep_device` with its own stage plan and no capacity
arguments over the configuration's whole id range, again and again until
the window ends, one sync a sweep.  The seed rotates the id range and
draws the positions that `check` compares with
`reference_crush_firstn_tree.CrushFirstnTreeRef`, every column.
"""

from __future__ import annotations

import numpy as np

import reference_crush_firstn_tree
from drivers import crush_rule_sweep


class Driver(crush_rule_sweep.Driver):
    """`crush_rule_sweep`'s loop over a map built from the
    configuration's layers and per-device CRUSH weights."""

    mean_weights = False   # the control's map

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
            cfg["osd_weights"])
        # the reference places on the map the configuration describes:
        # refuse to run where the program's builder hands out other ids
        # or sums the weights up otherwise
        if ids != [la["bucket_ids"] for la in layers] or any(
                [m.buckets[b].weight for b in la["bucket_ids"]]
                != cfg["bucket_weights"][la["type_name"]] for la in layers):
            raise RuntimeError("the program's map is not the configuration's")
        types = {la["type_name"]: la for la in layers}
        ops = {"take": cmap.OP_TAKE, "emit": cmap.OP_EMIT,
               "choose_firstn": cmap.OP_CHOOSE_FIRSTN,
               "chooseleaf_firstn": cmap.OP_CHOOSELEAF_FIRSTN}
        self.steps = []
        for op, *args in cfg["rule_steps"]:
            if op == "take":
                args = [types[args[0]]["bucket_ids"][0], 0]
            elif op in ("choose_firstn", "chooseleaf_firstn"):
                args = [args[0], types[args[1]]["type_id"]
                        if args[1] != "osd" else 0]
            self.steps.append((ops[op], *(args + [0, 0])[:2]))
        if self.mean_weights:
            for b in m.buckets.values():
                b.weights = [b.weight // len(b.items)] * len(b.items)
        self.flat = m.flatten()
        self.dev_w = reference_crush_firstn_tree.device_weights(cfg)
        n = self.traffic.get("ids", cfg["ids"])
        self.chunk = min(cfg["chunk"], n)
        off = int(np.random.default_rng([self.seed, 1]).integers(0, n))
        self.xs_host = ((np.arange(n, dtype=np.int64) + off) % n
                        + cfg["min_x"]).astype(np.int32)
        self.xs = jnp.asarray(self.xs_host)
        self._sweep = mapper.sweep_device   # default stage plan
        self.sweep()   # compiles (or loads) the plan's stage programs
        self.sweeps.pop()

    def check(self) -> dict:
        """Every timed sweep, at positions drawn from the seed, against
        the reference's placements of the same ids: a row is wrong if
        any of its columns differs."""
        rng = np.random.default_rng([self.seed, 2])
        pos = np.sort(rng.choice(len(self.xs_host), size=min(
            self.traffic["check_ids"], len(self.xs_host)), replace=False))
        want = reference_crush_firstn_tree.CrushFirstnTreeRef(
            self.cfg).do_rule(self.xs_host[pos])
        wrong = overflowed = 0
        for _t0, _t1, res, ovf in self.sweeps:
            got = np.asarray(res[pos])
            wrong += int((got != want).any(axis=1).sum())
            overflowed += int(ovf)
        return {"placements_wrong": [wrong, 0],
                "sweeps_overflowed": [overflowed, 0],
                "no_sweep_compared": [int(not self.sweeps), 0]}


def control(cfg: dict, traffic: dict, seed: int) -> Driver:
    """The program's own sweep over the same tree with every bucket's
    item weights replaced by their mean: the map on which every level
    is fastcmp (the winner is the largest hash, no draw is computed),
    the short cut this deployment exists to catch.  Most rows differ
    from crush_do_rule's on the map the configuration states."""
    d = Driver(cfg, traffic, seed)
    d.mean_weights = True
    return d
