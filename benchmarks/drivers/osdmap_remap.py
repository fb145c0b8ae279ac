"""Traffic kind `osdmap_remap`: the per-epoch remap of one pool, as the
mon and the mgr run `OSDMapMapping::update` on every new epoch
(`src/osd/OSDMapMapping.h`) and `osdmaptool --test-map-pgs` runs it
offline: `Incremental.apply` to the previous map, then `OSDMap.map_pgs`
of the pool on the new one, every array fetched before the next epoch.

Set-up builds the configuration's map (`build_layered_cluster`, refused
where the program lays out other buckets), upstream's `replicated_rule`
and the pool; maps it (which compiles, or loads, the rule program);
draws the configuration's `upmap_items` pairs from the seed against that
first raw mapping (a PG, one of its OSDs, a target on a host none of its
other OSDs is on); applies them with the primary affinity of one OSD a
host as one incremental; maps; and runs one whole cycle of four epochs,
so that every path has been compiled and taken.  The window runs cycles
back to back from there, A and B drawn by the seed each cycle:

1. A out (`new_weights` A: 0), with `new_pg_temp` for every PG whose
   acting set in the previous epoch's mapping held A, set to that set
   (the mon's `maybe_prime_pg_temp`);
2. B down (`new_down`);
3. A in (`new_weights` A: 0x10000), those pg_temp entries removed;
4. B up (`new_up`).

The epoch in flight at the window's end runs to completion and counts.
`check` holds the first raw mapping of the upmapped PGs, which the pairs
were drawn against, to `reference_osdmap.OSDMapRef`'s; replays every
incremental on the reference; and compares all four results (up,
up_primary, acting, acting_primary) of each timed epoch at PGs drawn by
the seed, some of them upmapped, plus that epoch's pg_temp PGs; the
window's last epoch in full.
"""

from __future__ import annotations

import copy
import statistics
import time

import numpy as np

import reference_osdmap

NONE = 0x7FFFFFFF
IN = 0x10000
KINDS = ("out", "down", "in", "up")


class Driver:
    exceptions = True   # the control maps without the exception tables

    def __init__(self, cfg: dict, traffic: dict, seed: int) -> None:
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        # every epoch since the map with the exception tables: (t0, t1,
        # its delta as plain data, the four arrays map_pgs gave)
        self.epochs: list = []
        self.timed_from = 0      # the window's first epoch
        self.split: list = []    # an epoch's apply, map_pgs, device wait (s)
        self.phases: dict = {}

    # -- set-up ---------------------------------------------------------------
    def setup(self) -> None:
        from ceph_tpu.crush import map as cmap
        from ceph_tpu.osd import osdmap as om
        from ceph_tpu.osd import map_inc
        from ceph_tpu.tpu import devwatch

        t0 = time.monotonic()
        cfg, pool = self.cfg, self.cfg["pool"]
        self.dw, self.om, self.map_inc = devwatch.watch(), om, map_inc
        layers = {la["type_name"]: la for la in cfg["layers"]}
        m, ids = cmap.build_layered_cluster(
            cfg["num_osds"],
            [(la["type_id"], la["size"]) for la in cfg["layers"]],
            cfg["osd_weights"])
        root = layers["root"]["bucket_ids"][0]
        host = layers["host"]["type_id"]
        rule = m.add_simple_rule("replicated_rule", root, host, "firstn")
        # the reference maps the map the configuration describes: refuse
        # to run where the program built another one
        if (ids != [la["bucket_ids"] for la in cfg["layers"]]
                or m.rules[rule].steps != [
                    (cmap.OP_TAKE, root, 0),
                    (cmap.OP_CHOOSELEAF_FIRSTN, cfg["rule_steps"][1][1], host),
                    (cmap.OP_EMIT, 0, 0)]
                or any(getattr(m.tunables, k) != v
                       for k, v in cfg["tunables"].items())):
            raise RuntimeError("the program's map is not the configuration's")
        base = om.OSDMap(m, max_osd=cfg["num_osds"])
        base.add_pool(om.PGPool(
            pool["pool_id"], om.POOL_REPLICATED, size=pool["size"],
            min_size=pool["min_size"], pg_num=pool["pg_num"],
            pgp_num=pool["pgp_num"], crush_rule=rule,
            flags=om.FLAG_HASHPSPOOL, name="remap"))
        self.pool_id, self.pg_num = pool["pool_id"], pool["pg_num"]
        self.map = base
        t1 = time.monotonic()
        # compiles (or loads) the rule program
        self.first_raw = base.map_pgs(self.pool_id)["raw"]
        t2 = time.monotonic()
        per = layers["host"]["size"]
        self.tables = {
            "new_pg_upmap_items": self._draw_upmaps(self.first_raw, per),
            "new_primary_affinity": {
                h * per + cfg["affinity_osd_in_host"]: cfg["affinity"]
                for h in range(cfg["num_osds"] // per)}}
        self.map = self._apply(self.tables)
        self.last = self._map()
        t3 = time.monotonic()
        self.step = 0
        for _ in KINDS:           # one whole cycle: every path once
            self._epoch()
        self.timed_from = len(self.epochs)
        self.phases = {"map_s": t1 - t0, "first_mapping_s": t2 - t1,
                       "tables_s": t3 - t2,
                       "cycle_s": time.monotonic() - t3}

    def _draw_upmaps(self, raw: np.ndarray, per: int) -> dict:
        """(pool, ps) -> [(from, to)], one pair a PG: from is one of the
        PG's OSDs in `raw`, to an OSD on a host that none of its others
        is on."""
        rng = np.random.default_rng([self.seed, 5])
        n = self.cfg["num_osds"]
        hosts = np.arange(n) // per
        out = {}
        for ps in np.sort(rng.choice(self.pg_num, self.cfg["upmap_items"],
                                     replace=False)):
            osds = [int(o) for o in raw[ps] if o != NONE]
            frm = osds[int(rng.integers(len(osds)))]
            taken = [hosts[o] for o in osds if o != frm]
            can = np.nonzero(~np.isin(hosts, taken) & ~np.isin(
                np.arange(n), osds))[0]
            out[(self.pool_id, int(ps))] = [
                (int(frm), int(can[rng.integers(len(can))]))]
        return out

    # -- one epoch ------------------------------------------------------------
    def _apply(self, delta: dict):
        inc = self.map_inc.Incremental(epoch=self.map.epoch + 1,
                                       prev_epoch=self.map.epoch, **delta)
        return inc.apply(self.map)

    def _map(self) -> dict:
        m = self.map
        if not self.exceptions:
            m = copy.copy(m)
            m.pg_upmap_items, m.pg_temp = {}, {}
        got = m.map_pgs(self.pool_id)
        return {k: got[k] for k in ("up", "up_primary", "acting",
                                    "acting_primary")}

    def _epoch(self) -> None:
        import jax

        kind = self.step % 4
        if kind == 0:
            self.a, self.b = self._draw_ab(self.step // 4)
        with jax.profiler.TraceAnnotation("bench:epoch"):
            t0 = time.monotonic()
            if kind == 0:
                acting = self.last["acting"]
                held = np.nonzero((acting == self.a).any(axis=1))[0]
                self.primed = {
                    (self.pool_id, int(ps)): [
                        int(o) for o in acting[ps] if o != NONE]
                    for ps in held}
                delta = {"new_weights": {self.a: 0},
                         "new_pg_temp": dict(self.primed)}
            elif kind == 1:
                delta = {"new_down": [self.b]}
            elif kind == 2:
                delta = {"new_weights": {self.a: IN},
                         "new_pg_temp": {pg: [] for pg in self.primed}}
            else:
                delta = {"new_up": [self.b]}
            self.map = self._apply(delta)
            ta, wait = time.monotonic(), self.dw.wait_s
            self.last = self._map()
            t1 = time.monotonic()
        self.epochs.append((t0, t1, delta, self.last))
        self.split.append((ta - t0, t1 - ta, self.dw.wait_s - wait))
        self.step += 1

    def _draw_ab(self, cycle: int):
        """The cycle's A (out, then in) and B (down, then up)."""
        a, b = np.random.default_rng([self.seed, 4, cycle]).choice(
            self.cfg["num_osds"], 2, replace=False)
        return int(a), int(b)

    # -- the harness's calls --------------------------------------------------
    def counters(self) -> dict:
        out = {"devwatch.compiles": self.dw.compile_totals()["compiles"]}
        # a parent's program may keep neither total
        for totals in (getattr(self.om, "mapping_totals", None),
                       getattr(self.map_inc, "apply_totals", None)):
            if totals is not None:
                out.update(totals())
        return out

    def window(self, seconds: float, tracer) -> dict:
        """Epochs back to back; with a tracer, the first one is the
        traced slice (set-up already ran a whole cycle)."""
        begin = time.monotonic()
        slice_ = {}
        while time.monotonic() - begin < seconds:
            if tracer is not None and not slice_:
                with tracer.slice():
                    self._epoch()
                slice_ = {"ids": self.pg_num}
            else:
                self._epoch()
        timed = self.epochs[self.timed_from:]
        split = self.split[self.timed_from:]
        by_kind = {k: [] for k in KINDS}
        for i, ((t0, t1, _d, _r), parts) in enumerate(zip(timed, split)):
            by_kind[KINDS[i % 4]].append(((t1 - t0) * 1e3,
                                          *(p * 1e3 for p in parts)))
        return {
            "metrics": {"placements_per_s": len(timed) * self.pg_num
                        / (timed[-1][1] - begin)},
            "attempted": len(timed), "failed": 0, "slice": slice_,
            "notes": {
                "epochs": len(timed),
                "epoch_ms_median": {k: statistics.median(r[0] for r in v)
                                    for k, v in by_kind.items() if v},
                # mean ms of the epoch, its apply, its map_pgs and the
                # device wait inside that, by kind
                "epoch_ms_mean": {k: [statistics.fmean(c) for c in zip(*v)]
                                  for k, v in by_kind.items() if v},
                "pg_temp_rows": len(self.primed)},
        }

    def check(self) -> dict:
        """Every timed epoch against the reference, which replays the same
        incrementals from the first map: at `check_pgs` PGs drawn by the
        seed, `check_upmapped` of them among the upmapped PGs, and the
        epoch's pg_temp PGs; the window's last epoch at every PG.  A PG
        is wrong if any of its four results differs, and an upmapped PG
        also if the first raw mapping its pair was drawn against is not
        the reference's."""
        ref = reference_osdmap.OSDMapRef(self.cfg)
        tr = self.traffic
        upmapped = np.array(sorted(
            ps for _pool, ps in self.tables["new_pg_upmap_items"]))
        first = ref.raw(upmapped)
        wrong = sum(
            [int(o) for o in self.first_raw[ps] if o != NONE] != first[ps]
            for ps in upmapped.tolist())
        ref.apply(self.tables)
        others = np.setdiff1d(np.arange(self.pg_num), upmapped)
        timed = len(self.epochs) - self.timed_from
        compared = 0
        for i, (_t0, _t1, delta, got) in enumerate(self.epochs):
            ref.apply(delta)
            j = i - self.timed_from
            if j < 0:
                continue
            if j == timed - 1:
                pss = np.arange(self.pg_num)
            else:
                rng = np.random.default_rng([self.seed, 3, j])
                k = min(tr["check_upmapped"], len(upmapped))
                pss = np.union1d(np.union1d(
                    rng.choice(upmapped, k, replace=False),
                    rng.choice(others, min(tr["check_pgs"] - k, len(others)),
                               replace=False)),
                    np.array(sorted(ref.pg_temp), dtype=np.int64))
            want = ref.pg_to_up_acting(pss)
            have = (got["up"][pss], got["up_primary"][pss],
                    got["acting"][pss], got["acting_primary"][pss])
            bad = np.zeros(len(pss), dtype=bool)
            for w, h in zip(want, have):
                bad |= (w != h).reshape(len(pss), -1).any(axis=1)
            wrong += int(bad.sum())
            compared += 1
        return {"placements_wrong": [wrong, 0],
                "epochs_compared_none": [
                    int(not compared) + timed - compared, 0]}

    def close(self) -> None:
        self.epochs.clear()


def control(cfg: dict, traffic: dict, seed: int) -> Driver:
    """The program's own mapping with the exception tables left out: no
    pg_upmap_items and no pg_temp reach map_pgs (the shortcut a
    vectorised rewrite would be tempted by).  Every upmapped PG whose
    item applies, and every primed PG, differs from the reference's."""
    d = Driver(cfg, traffic, seed)
    d.exceptions = False
    return d
