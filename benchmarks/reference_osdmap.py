"""`OSDMap::pg_to_up_acting_osds` for one replicated pool, and the epochs
an `Incremental` makes of it: the plain reference `correct` compares the
per-epoch remap with (numpy only, nothing of `ceph_tpu` imported).

Written from `src/osd/OSDMap.cc` and `src/osd/osd_types.cc`, one PG at a
time in the C++'s order:

- `raw_pg_to_pps` (osd_types.cc:1500-1516): `ceph_stable_mod(ps,
  pgp_num, pgp_num_mask)` (include/rados.h:85), then under HASHPSPOOL
  `crush_hash32_2(that, pool)`, else that plus the pool id;
- `_pg_to_raw_osds` (OSDMap.cc:2198-2210): `crush->do_rule(rule, pps,
  size, osd_weight)`, as many OSDs as the rule placed, through
  `reference_crush_firstn_tree.CrushFirstnTreeRef` with its device
  weights set to the epoch's `osd_weight`; then
  `_remove_nonexistent_osds`;
- `_apply_upmap` (:2228): a `pg_upmap` naming an out OSD is ignored and
  so are the PG's `pg_upmap_items`; an item (from, to) replaces the
  first `from` unless `to` is already there or is out;
- `_raw_to_up_osds` (:2275): a replicated pool shifts the OSDs that do
  not exist or are down out to the left;
- `_pick_primary`, `_apply_primary_affinity` (:2300): with any
  non-default affinity among the up set, the first OSD whose
  `crush_hash32_2(pps, osd) >> 16` is below its affinity is primary
  (else the first rejected one), moved to the front;
- `_get_temp_osds` (:2356): the pg_temp row without the OSDs that do not
  exist or are down, its first as primary unless `primary_temp` names
  one;
- `_pg_to_up_acting_osds` (:2417): acting is the temp row, else up.

An epoch is applied as `OSDMap::apply_incremental` applies one, for the
fields the remap's incrementals carry, in the repository's `Incremental`
shape (`new_up` / `new_down` lists where upstream XORs state bits;
`new_pg_temp` and `new_pg_upmap_items` with an empty value to remove an
entry).  Departures, each for what this file is asked to map:

- one pool, replicated (the firstn reference places its rule); the
  erasure branches (NONE holes where an OSD is down) are not written;
- no `pg_upmap_primaries` (newer upstream), no new pools, no CRUSH map
  in an incremental, no `new_max_osd`;
- the raw placements are cached by the epoch's device weights, since
  CRUSH reads nothing else of the map.
"""

from __future__ import annotations

import numpy as np

from reference import NONE
from reference_crush_firstn_tree import CrushFirstnTreeRef
from reference_crush_tree import _M32, device_weights, hash32_2

DEFAULT_PRIMARY_AFFINITY = 0x10000
MAX_PRIMARY_AFFINITY = 0x10000


def stable_mod(x, b: int, bmask: int):
    """ceph_stable_mod (include/rados.h:85), on ints or int arrays."""
    return np.where((x & bmask) < b, x & bmask, x & (bmask >> 1))


def num_mask(b: int) -> int:
    """The smallest 2^n - 1 at or above b - 1 (pg_pool_t::calc_pg_masks)."""
    return (1 << int(b - 1).bit_length()) - 1


def _set(table: dict, ps: int, v) -> None:
    """An incremental's entry: None or an empty list removes it."""
    if v is None or v == []:
        table.pop(ps, None)
    else:
        table[ps] = v


class OSDMapRef:
    """One epoch of the configuration's map: OSD states, `osd_weight`,
    primary affinity and the exception tables of its one pool."""

    def __init__(self, cfg: dict) -> None:
        pool = cfg["pool"]
        if pool["type"] != "replicated":
            raise ValueError("a replicated pool only")
        self.crush = CrushFirstnTreeRef(cfg)
        self.max_osd = cfg["num_osds"]
        self.pool_id = pool["pool_id"]
        self.pg_num, self.pgp_num = pool["pg_num"], pool["pgp_num"]
        self.size = pool["size"]
        self.hashpspool = "hashpspool" in pool["flags"]
        self.osd_weight = device_weights(cfg).astype(np.int64)
        self.exists = np.ones(self.max_osd, dtype=bool)
        self.up = np.ones(self.max_osd, dtype=bool)
        self.primary_affinity = None
        self.pg_upmap: dict = {}         # ps -> [osd]
        self.pg_upmap_items: dict = {}   # ps -> [(from, to)]
        self.pg_temp: dict = {}          # ps -> [osd]
        self.primary_temp: dict = {}     # ps -> osd
        self._raws: dict = {}   # osd_weight bytes -> {ps: raw list}

    # -- OSDMap::apply_incremental ---------------------------------------
    def apply(self, inc: dict) -> None:
        """One epoch's delta: {"new_up", "new_down", "new_weights",
        "new_primary_affinity", "new_pg_temp", "new_primary_temp",
        "new_pg_upmap", "new_pg_upmap_items"}, each optional; a PG is
        keyed (pool, ps)."""
        for osd in inc.get("new_up", ()):
            self.up[osd] = self.exists[osd] = True
        for osd in inc.get("new_down", ()):
            self.up[osd] = False
        for osd, w in inc.get("new_weights", {}).items():
            self.osd_weight[osd] = w
        aff = inc.get("new_primary_affinity", {})
        if aff and self.primary_affinity is None:
            self.primary_affinity = np.full(
                self.max_osd, DEFAULT_PRIMARY_AFFINITY, dtype=np.int64)
        for osd, a in aff.items():
            self.primary_affinity[osd] = a
        for (pool, ps), v in inc.get("new_pg_temp", {}).items():
            if pool == self.pool_id:
                _set(self.pg_temp, ps, list(v))
        for (pool, ps), v in inc.get("new_primary_temp", {}).items():
            if pool == self.pool_id:
                _set(self.primary_temp, ps, v if v != -1 else None)
        for (pool, ps), v in inc.get("new_pg_upmap", {}).items():
            if pool == self.pool_id:
                _set(self.pg_upmap, ps, list(v))
        for (pool, ps), v in inc.get("new_pg_upmap_items", {}).items():
            if pool == self.pool_id:
                _set(self.pg_upmap_items, ps, [tuple(fp) for fp in v])

    # -- pg_pool_t::raw_pg_to_pps -------------------------------------------
    def pps(self, ps):
        ps = np.asarray(ps, dtype=np.int64)
        m = stable_mod(ps, self.pgp_num, num_mask(self.pgp_num))
        if self.hashpspool:
            return hash32_2(m.astype(np.uint64),
                            np.uint64(self.pool_id)).astype(np.int64)
        return (m + self.pool_id) & int(_M32)

    # -- _pg_to_raw_osds ------------------------------------------------------
    def raw(self, pss) -> dict:
        """{ps: the OSDs do_rule placed, existing ones only} under this
        epoch's osd_weight."""
        known = self._raws.setdefault(self.osd_weight.tobytes(), {})
        todo = sorted({int(p) for p in pss} - known.keys())
        if todo:
            self.crush.dev_w = self.osd_weight
            rows = self.crush.do_rule(self.pps(todo))
            for p, row in zip(todo, rows):
                known[p] = [int(o) for o in row if o != NONE]
        # _remove_nonexistent_osds: a replicated pool drops them
        return {int(p): [o for o in known[int(p)] if self._exists(o)]
                for p in pss}

    def _exists(self, osd: int) -> bool:
        return 0 <= osd < self.max_osd and bool(self.exists[osd])

    def _is_up(self, osd: int) -> bool:
        return self._exists(osd) and bool(self.up[osd])

    # -- _apply_upmap ---------------------------------------------------------
    def _out(self, osd: int) -> bool:
        return (osd != NONE and 0 <= osd < self.max_osd
                and self.osd_weight[osd] == 0)

    def _apply_upmap(self, ps: int, raw: list) -> list:
        p = self.pg_upmap.get(ps)
        if p is not None:
            if any(self._out(o) for o in p):
                return raw              # the mapping, and the items, ignored
            raw = list(p)
        for frm, to in self.pg_upmap_items.get(ps, ()):
            exists, pos = False, -1
            for i, osd in enumerate(raw):
                if osd == to:
                    exists = True
                    break
                if osd == frm and pos < 0 and not self._out(to):
                    pos = i
            if not exists and pos >= 0:
                raw[pos] = to
        return raw

    # -- _raw_to_up_osds, _pick_primary, _apply_primary_affinity -----------
    def _raw_to_up(self, raw: list) -> list:
        return [o for o in raw if self._is_up(o)]

    @staticmethod
    def _pick_primary(osds: list) -> int:
        return next((o for o in osds if o != NONE), -1)

    def _apply_primary_affinity(self, hashes: dict, osds: list,
                                primary: int):
        """`hashes[osd]` is crush_hash32_2(pps, osd) of this PG's up OSDs
        (made for a batch of PGs at once: the hash is numpy's)."""
        aff = self.primary_affinity
        if aff is None or not any(
                o != NONE and aff[o] != DEFAULT_PRIMARY_AFFINITY
                for o in osds):
            return osds, primary
        pos = -1
        for i, o in enumerate(osds):
            if o == NONE:
                continue
            a = int(aff[o])
            if a < MAX_PRIMARY_AFFINITY and (hashes[o] >> 16) >= a:
                if pos < 0:
                    pos = i       # rejected; the fallback
            else:
                pos = i
                break
        if pos < 0:
            return osds, primary
        primary = osds[pos]
        if pos > 0:               # a replicated pool can shift
            osds = [primary] + osds[:pos] + osds[pos + 1:]
        return osds, primary

    # -- _get_temp_osds -------------------------------------------------------
    def _get_temp_osds(self, ps: int):
        temp = [o for o in self.pg_temp.get(ps, ()) if self._is_up(o)]
        primary = self.primary_temp.get(ps, -1)
        if primary == -1 and temp:
            primary = self._pick_primary(temp)
        return temp, primary

    # -- _pg_to_up_acting_osds ------------------------------------------------
    def pg_to_up_acting(self, pss):
        """(up [n, size], up_primary [n], acting [n, size], acting_primary
        [n]) of the PGs `pss`, rows padded with NONE."""
        pss = [int(p) for p in pss]
        raws = self.raw(pss)
        pps = self.pps(pss)
        n = len(pss)
        up = np.full((n, self.size), NONE, dtype=np.int64)
        acting = np.full((n, self.size), NONE, dtype=np.int64)
        up_p = np.full(n, -1, dtype=np.int64)
        act_p = np.full(n, -1, dtype=np.int64)
        ups = [self._raw_to_up(self._apply_upmap(ps, list(raws[ps])))
               for ps in pss]
        pairs = [(i, o) for i, u in enumerate(ups) for o in u]
        h = hash32_2(np.array([pps[i] for i, _ in pairs], dtype=np.uint64),
                     np.array([o for _, o in pairs], dtype=np.uint64))
        hashes: list = [{} for _ in pss]
        for (i, o), v in zip(pairs, h.tolist()):
            hashes[i][o] = v
        for i, ps in enumerate(pss):
            temp, temp_p = self._get_temp_osds(ps)
            u, u_p = self._apply_primary_affinity(
                hashes[i], ups[i], self._pick_primary(ups[i]))
            if not temp:
                temp = list(u)
                if temp_p == -1:
                    temp_p = u_p
            up[i, :len(u)], up_p[i] = u, u_p
            acting[i, :len(temp)], act_p[i] = temp, temp_p
        return up, up_p, acting, act_p
