"""OSDMap — versioned cluster map and the object->PG->OSD pipeline.

Re-implements the placement pipeline of the reference
(reference: src/osd/OSDMap.cc, src/osd/osd_types.cc):

- object name -> placement seed: rjenkins string hash, optional
  namespace with 0x1F separator (pg_pool_t::hash_key,
  osd_types.cc:1468)
- ps -> pg via ceph_stable_mod (include/rados.h:85), pg -> pps mixing
  the pool id under HASHPSPOOL (raw_pg_to_pps, osd_types.cc:1500-1516)
- pps -> raw osds via CRUSH (_pg_to_raw_osds -> crush do_rule,
  OSDMap.cc:2198-2210)
- upmap exception table (_apply_upmap, :2228), up filtering
  (_raw_to_up_osds, :2275), primary affinity (:2300), pg_temp /
  primary_temp overrides (_get_temp_osds, :2356),
  pg_to_up_acting_osds (:2417)

Two execution paths share these semantics:
- scalar host path (``pg_to_up_acting``) through the native oracle —
  the per-op client path;
- ``map_pgs`` — the TPU-native replacement for OSDMapMapping /
  ParallelPGMapper (reference: src/osd/OSDMapMapping.h:17): every PG of
  a pool mapped in ONE device dispatch, with the up-filter, primary
  affinity and exception tables applied vectorized on top.  A pool with
  more PGs than the staged sweep's exact stage takes goes through
  ``mapper.sweep_device``'s plan (one-shot pass, budgeted stage, the
  exact program on the residue), a smaller one through the exact
  program on every lane; both give the exact program's placements.
  Each call is one `osdmap.map_pgs` span (counts `mapping`, the value
  the `osdmap.mappings` total took) with a child a step, and adds to
  the totals `mapping_totals()` gives.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ceph_tpu import _native
from ceph_tpu.core import tracing
from ceph_tpu.core.lockdep import make_lock
from ceph_tpu.crush import hashes
from ceph_tpu.crush import map as cmap
from ceph_tpu.crush import mapper as cmapper
from ceph_tpu.tpu import devwatch

CRUSH_ITEM_NONE = 0x7FFFFFFF
DEFAULT_PRIMARY_AFFINITY = 0x10000
MAX_PRIMARY_AFFINITY = 0x10000

POOL_REPLICATED = 1
POOL_ERASURE = 3

FLAG_HASHPSPOOL = 1

# map_pgs' monotonic totals over the process (counters, registered in
# tracing.SPANS beside the spans): calls, PGs mapped, pg_temp rows taken,
# calls that placed the pool through the staged sweep, and those of them
# whose staged sweep overflowed and were placed again by the exact program
_totals = {"osdmap.mappings": 0, "osdmap.pgs_mapped": 0,
           "osdmap.temp_rows": 0, "osdmap.staged_mappings": 0,
           "osdmap.rule_overflows": 0}
_totals_lock = make_lock("osdmap.totals")


def mapping_totals() -> Dict[str, int]:
    """{"osdmap.mappings", "osdmap.pgs_mapped", "osdmap.temp_rows",
    "osdmap.staged_mappings", "osdmap.rule_overflows"} over every
    map_pgs of the process so far."""
    with _totals_lock:
        return dict(_totals)


def stable_mod(x: int, b: int, bmask: int) -> int:
    """ceph_stable_mod (reference: src/include/rados.h:85)."""
    return x & bmask if (x & bmask) < b else x & (bmask >> 1)


def pg_num_mask(b: int) -> int:
    """Smallest (2^n)-1 containing b (b=12 -> 15)."""
    m = 1
    while m < b:
        m <<= 1
    return m - 1


@dataclasses.dataclass
class PGPool:
    pool_id: int
    pool_type: int = POOL_REPLICATED
    size: int = 3
    min_size: int = 2
    pg_num: int = 64
    pgp_num: int = 64
    crush_rule: int = 0
    flags: int = FLAG_HASHPSPOOL
    object_hash: str = "rjenkins"
    erasure_code_profile: str = ""
    name: str = ""
    # hit-set tracking (cache-tier statistics; reference pg_pool_t
    # hit_set_params/period/count, src/osd/osd_types.h): count == 0
    # disables tracking
    hit_set_count: int = 0
    hit_set_period: float = 0.0
    hit_set_target_size: int = 1000
    hit_set_fpp: float = 0.01

    @property
    def pg_num_mask_(self) -> int:
        return pg_num_mask(self.pg_num)

    @property
    def pgp_num_mask_(self) -> int:
        return pg_num_mask(self.pgp_num)

    def can_shift_osds(self) -> bool:
        return self.pool_type == POOL_REPLICATED

    def hash_key(self, key: str | bytes, nspace: str | bytes = b"") -> int:
        if isinstance(key, str):
            key = key.encode()
        if isinstance(nspace, str):
            nspace = nspace.encode()
        buf = key if not nspace else nspace + b"\x1f" + key
        return hashes.str_hash_rjenkins(buf)

    def raw_pg_to_pg_ps(self, ps: int) -> int:
        return stable_mod(ps, self.pg_num, self.pg_num_mask_)

    def raw_pg_to_pps(self, ps: int) -> int:
        if self.flags & FLAG_HASHPSPOOL:
            return int(
                hashes.hash32_2(
                    np.uint32(stable_mod(ps, self.pgp_num, self.pgp_num_mask_)),
                    np.uint32(self.pool_id),
                )
            )
        return stable_mod(ps, self.pgp_num, self.pgp_num_mask_) + self.pool_id

    def pps_vector(self, pgs: np.ndarray) -> np.ndarray:
        """Vectorized raw_pg_to_pps over pg seed numbers [N] (already
        stable_mod'ed into [0, pg_num))."""
        ps = np.asarray(pgs, dtype=np.int64)
        m = np.where(
            (ps & self.pgp_num_mask_) < self.pgp_num,
            ps & self.pgp_num_mask_,
            ps & (self.pgp_num_mask_ >> 1),
        ).astype(np.uint32)
        if self.flags & FLAG_HASHPSPOOL:
            return np.asarray(
                hashes.hash32_2(m, np.uint32(self.pool_id))
            ).astype(np.uint32)
        return (m + np.uint32(self.pool_id)).astype(np.uint32)


class OSDMap:
    """Cluster map: crush + osd states + pools + exception tables."""

    def __init__(self, crush: cmap.CrushMap, max_osd: int = 0):
        self.epoch = 1
        self.crush = crush
        self.max_osd = max_osd or crush.max_devices
        self.osd_state_up = np.ones(self.max_osd, dtype=bool)
        self.osd_state_exists = np.ones(self.max_osd, dtype=bool)
        self.osd_weight = np.full(self.max_osd, 0x10000, dtype=np.uint32)
        self.osd_primary_affinity: Optional[np.ndarray] = None
        self.pools: Dict[int, PGPool] = {}
        self.pg_upmap: Dict[Tuple[int, int], List[int]] = {}
        self.pg_upmap_items: Dict[Tuple[int, int], List[Tuple[int, int]]] = {}
        self.pg_temp: Dict[Tuple[int, int], List[int]] = {}
        self.primary_temp: Dict[Tuple[int, int], int] = {}
        # entity addresses published with the map (reference: osd_addrs
        # + hb_front/back_addrs in OSDMap) — how daemons/clients find
        # each other; heartbeats get their own endpoint so a busy data
        # path can never stall liveness probes
        self.osd_addrs: Dict[int, Tuple[str, int]] = {}
        self.osd_hb_addrs: Dict[int, Tuple[str, int]] = {}
        self._flat = None
        self._rule_fns: Dict[Tuple[int, int], object] = {}

    # -- epoch / state mutation -------------------------------------------
    def bump_epoch(self) -> None:
        self.epoch += 1
        self._flat = None
        self._rule_fns.clear()

    def set_osd_down(self, osd: int) -> None:
        self.osd_state_up[osd] = False
        self.bump_epoch()

    def set_osd_up(self, osd: int) -> None:
        self.osd_state_up[osd] = True
        self.osd_state_exists[osd] = True
        self.bump_epoch()

    def set_osd_out(self, osd: int) -> None:
        self.osd_weight[osd] = 0
        self.bump_epoch()

    def set_osd_in(self, osd: int) -> None:
        self.osd_weight[osd] = 0x10000
        self.bump_epoch()

    def reweight_osd(self, osd: int, weight_16_16: int) -> None:
        self.osd_weight[osd] = weight_16_16
        self.bump_epoch()

    def set_primary_affinity(self, osd: int, aff: int) -> None:
        if self.osd_primary_affinity is None:
            self.osd_primary_affinity = np.full(
                self.max_osd, DEFAULT_PRIMARY_AFFINITY, dtype=np.uint32
            )
        self.osd_primary_affinity[osd] = aff
        self.bump_epoch()

    def exists(self, osd: int) -> bool:
        return 0 <= osd < self.max_osd and bool(self.osd_state_exists[osd])

    def is_up(self, osd: int) -> bool:
        return self.exists(osd) and bool(self.osd_state_up[osd])

    def add_pool(self, pool: PGPool) -> None:
        self.pools[pool.pool_id] = pool
        self.bump_epoch()

    # -- placement pipeline (scalar host path) ----------------------------
    def _flatten(self) -> cmap.FlatMap:
        if self._flat is None:
            flat = self.crush.flatten()
            # the COMPAT weight-set (reference choose_args id -1,
            # written by the balancer's crush-compat mode and read by
            # bucket_straw2_choose): substitute straw2 draw weights in
            # the flat map so BOTH the scalar native oracle and the
            # vmapped sweep consume it — one source of truth
            ca = self.crush.choose_args.get("-1")
            if ca:
                w = np.asarray(flat.weights).copy()
                algs = np.asarray(flat.algs)
                for bid, ws in ca.items():
                    bno = -1 - bid
                    if (0 <= bno < w.shape[0]
                            and algs[bno] == cmap.ALG_STRAW2):
                        w[bno, : len(ws)] = ws
                flat = dataclasses.replace(flat, weights=w)
            self._flat = flat
        return self._flat

    def object_to_pg(self, pool_id: int, name, nspace=b"") -> Tuple[int, int]:
        pool = self.pools[pool_id]
        ps = pool.hash_key(name, nspace)
        return (pool_id, pool.raw_pg_to_pg_ps(ps))

    def _crush_raw(self, pool: PGPool, pps: int) -> List[int]:
        flat = self._flatten()
        rule = self.crush.rules[pool.crush_rule]
        steps = np.asarray(rule.steps, dtype=np.int32).ravel()
        out = _native.do_rule(flat, steps, pps, pool.size, self.osd_weight)
        # _remove_nonexistent_osds (OSDMap.cc), before any upmap applies
        if pool.can_shift_osds():
            return [o for o in out if self.exists(o)]
        return [o if self.exists(o) else CRUSH_ITEM_NONE for o in out]

    def _apply_upmap(self, pool: PGPool, pgid, raw: List[int]) -> List[int]:
        p = self.pg_upmap.get(pgid)
        if p is not None:
            for osd in p:
                if (
                    osd != CRUSH_ITEM_NONE
                    and 0 <= osd < self.max_osd
                    and self.osd_weight[osd] == 0
                ):
                    # an explicit mapping onto an out OSD is rejected,
                    # and the PG's upmap items with it (OSDMap.cc
                    # _apply_upmap returns here)
                    return raw
            raw = list(p)
        q = self.pg_upmap_items.get(pgid)
        if q is not None:
            for frm, to in q:
                exists = False
                pos = -1
                for i, osd in enumerate(raw):
                    if osd == to:
                        exists = True
                        break
                    if (
                        osd == frm
                        and pos < 0
                        and not (
                            to != CRUSH_ITEM_NONE
                            and 0 <= to < self.max_osd
                            and self.osd_weight[to] == 0
                        )
                    ):
                        pos = i
                if not exists and pos >= 0:
                    raw[pos] = to
        return raw

    def _raw_to_up(self, pool: PGPool, raw: List[int]) -> List[int]:
        if pool.can_shift_osds():
            return [o for o in raw if o != CRUSH_ITEM_NONE and self.is_up(o)]
        return [
            o if o != CRUSH_ITEM_NONE and self.is_up(o) else CRUSH_ITEM_NONE
            for o in raw
        ]

    def _pick_primary(self, osds: Sequence[int]) -> int:
        for o in osds:
            if o != CRUSH_ITEM_NONE:
                return o
        return -1

    def _apply_primary_affinity(
        self, seed: int, pool: PGPool, osds: List[int], primary: int
    ) -> Tuple[List[int], int]:
        aff = self.osd_primary_affinity
        if aff is None:
            return osds, primary
        if not any(
            o != CRUSH_ITEM_NONE and aff[o] != DEFAULT_PRIMARY_AFFINITY
            for o in osds
        ):
            return osds, primary
        pos = -1
        for i, o in enumerate(osds):
            if o == CRUSH_ITEM_NONE:
                continue
            a = int(aff[o])
            if a < MAX_PRIMARY_AFFINITY and (
                int(hashes.hash32_2(np.uint32(seed), np.uint32(o))) >> 16
            ) >= a:
                if pos < 0:
                    pos = i
            else:
                pos = i
                break
        if pos < 0:
            return osds, primary
        primary = osds[pos]
        if pool.can_shift_osds() and pos > 0:
            osds = [osds[pos]] + osds[:pos] + osds[pos + 1 :]
        return osds, primary

    def pg_to_up_acting(
        self, pgid: Tuple[int, int]
    ) -> Tuple[List[int], int, List[int], int]:
        """(up, up_primary, acting, acting_primary) for one pg
        (reference: OSDMap.cc:2417 _pg_to_up_acting_osds)."""
        pool_id, ps = pgid
        pool = self.pools.get(pool_id)
        if pool is None or ps >= pool.pg_num:
            return [], -1, [], -1
        # pg_temp / primary_temp
        acting: List[int] = []
        for o in self.pg_temp.get(pgid, []):
            if not self.is_up(o):
                if pool.can_shift_osds():
                    continue
                acting.append(CRUSH_ITEM_NONE)
            else:
                acting.append(o)
        acting_primary = self.primary_temp.get(pgid, -1)
        if acting_primary == -1 and acting:
            acting_primary = self._pick_primary(acting)

        pps = pool.raw_pg_to_pps(ps)
        raw = self._crush_raw(pool, pps)
        raw = self._apply_upmap(pool, pgid, raw)
        up = self._raw_to_up(pool, raw)
        up_primary = self._pick_primary(up)
        up, up_primary = self._apply_primary_affinity(
            pps, pool, up, up_primary
        )
        if not acting:
            acting = list(up)
            if acting_primary == -1:
                acting_primary = up_primary
        return up, up_primary, acting, acting_primary

    # -- the vmapped full-cluster sweep -----------------------------------
    def _rule_fn(self, pool: PGPool):
        key = (pool.crush_rule, pool.size)
        fn = self._rule_fns.get(key)
        if fn is None:
            with tracing.span("osdmap.flatten"):
                rule = self.crush.rules[pool.crush_rule]
                fn = cmapper.compile_rule(self._flatten(), rule.steps,
                                          pool.size)
            self._rule_fns[key] = fn
        return fn

    def _staged(self, pool: PGPool) -> bool:
        """Whether the pool's PGs outnumber the lanes the staged sweep's
        exact stage would take on this map (mapper.sweep_plan, from the
        rule, the map and the device weights): only then do the one-shot
        and budgeted stages spare the exact program any lane."""
        rule = self.crush.rules[pool.crush_rule]
        plan = cmapper.sweep_plan(self._flatten(), rule.steps, pool.size,
                                  self.osd_weight)
        n = pool.pg_num
        return cmapper.stage_capacities(plan, n, n)[1] < n

    def _rule_raw(self, pool: PGPool, fn, xs: np.ndarray, staged: bool):
        """The rule's raw placement of every PG: a large pool through the
        staged sweep in one dispatch, its placements and overflow flag
        fetched together; a pool whose sweep overflowed, or a small one,
        through the exact program `fn` on every lane."""
        if staged:
            rule = self.crush.rules[pool.crush_rule]
            raw, overflow = devwatch.fetch(cmapper.sweep_device(
                self._flatten(), rule.steps, pool.size, xs,
                self.osd_weight, chunk=len(xs)))
            if not overflow:
                return raw
            with _totals_lock:
                _totals["osdmap.rule_overflows"] += 1
        return devwatch.fetch(fn(xs, self.osd_weight))

    def map_pgs(self, pool_id: int) -> Dict[str, np.ndarray]:
        """Map ALL pgs of a pool in one jitted sweep.

        Returns {"raw", "up", "up_primary", "acting", "acting_primary"}
        arrays — the OSDMapMapping product, minus the thread pool.
        """
        pool = self.pools[pool_id]
        with _totals_lock:
            _totals["osdmap.mappings"] += 1
            n = _totals["osdmap.mappings"]
        with tracing.span("osdmap.map_pgs", mapping=n, pool=pool_id,
                          pgs=pool.pg_num) as sp:
            with tracing.span("osdmap.pps"):
                pps = pool.pps_vector(np.arange(pool.pg_num, dtype=np.int64))
            fn = self._rule_fn(pool)
            staged = self._staged(pool)
            with tracing.span("osdmap.rule", staged=int(staged)):
                raw = self._rule_raw(pool, fn, pps.astype(np.int32), staged)
            with tracing.span("osdmap.exceptions"):
                raw, sp.counts["upmap_rows"] = self._sweep_apply_exceptions(
                    pool, raw)
            with tracing.span("osdmap.up"):
                up, up_primary = self._sweep_up(pool, raw, pps)
            with tracing.span("osdmap.temp"):
                acting, acting_primary, temp_rows = self._sweep_temp(
                    pool, up, up_primary)
            sp.counts["temp_rows"] = temp_rows
        with _totals_lock:
            _totals["osdmap.pgs_mapped"] += pool.pg_num
            _totals["osdmap.temp_rows"] += temp_rows
            _totals["osdmap.staged_mappings"] += staged
        return {
            "raw": raw,
            "up": up,
            "up_primary": up_primary,
            "acting": acting,
            "acting_primary": acting_primary,
        }

    def _sweep_temp(self, pool: PGPool, up: np.ndarray,
                    up_primary: np.ndarray):
        """pg_temp rows through the scalar path, primary_temp on top:
        (acting, acting_primary, pg_temp rows of the pool)."""
        acting = up.copy()
        acting_primary = up_primary.copy()
        rows = 0
        for pgid in self.pg_temp:
            if pgid[0] != pool.pool_id or pgid[1] >= pool.pg_num:
                continue
            _, _, act, actp = self.pg_to_up_acting(pgid)
            row = np.full(acting.shape[1], CRUSH_ITEM_NONE, dtype=np.int32)
            row[: len(act)] = act
            acting[pgid[1]] = row
            acting_primary[pgid[1]] = actp
            rows += 1
        for pgid, p in self.primary_temp.items():
            if pgid[0] == pool.pool_id and pgid[1] < pool.pg_num:
                acting_primary[pgid[1]] = p
        return acting, acting_primary, rows

    def _sweep_apply_exceptions(self, pool, raw: np.ndarray):
        """(raw with the pool's pg_upmap and pg_upmap_items applied, the
        number of the pool's PGs that have either)."""
        if not self.osd_state_exists.all():
            # _remove_nonexistent_osds (OSDMap.cc), before any upmap
            raw = self._sweep_keep(pool, raw, self._sweep_osd_has(
                raw, self.osd_state_exists))
        if not self.pg_upmap and not self.pg_upmap_items:
            return raw, 0
        raw = raw.copy()
        rows = 0
        # a PG in both tables is one row: _apply_upmap takes both
        for pgid in dict.fromkeys([*self.pg_upmap, *self.pg_upmap_items]):
            if pgid[0] != pool.pool_id or pgid[1] >= pool.pg_num:
                continue
            row = self._apply_upmap(pool, pgid, list(raw[pgid[1]]))
            out = np.full(raw.shape[1], CRUSH_ITEM_NONE, dtype=np.int32)
            out[: len(row)] = row
            raw[pgid[1]] = out
            rows += 1
        return raw, rows

    def _sweep_osd_has(self, raw: np.ndarray, state: np.ndarray):
        """Where an entry of `raw` is an OSD id whose `state` is set."""
        inrange = (raw != CRUSH_ITEM_NONE) & (raw >= 0) & (raw < self.max_osd)
        return inrange & state[np.clip(raw, 0, self.max_osd - 1)]

    @staticmethod
    def _sweep_keep(pool: PGPool, raw: np.ndarray, keep: np.ndarray):
        """The entries `keep` marks: a pool that can shift moves them
        left in order, another holds NONE where one went."""
        if pool.can_shift_osds():
            order = np.argsort(~keep, axis=1, kind="stable")
            raw = np.take_along_axis(raw, order, axis=1)
            keep = np.take_along_axis(keep, order, axis=1)
        return np.where(keep, raw, CRUSH_ITEM_NONE)

    def _sweep_up(
        self, pool: PGPool, raw: np.ndarray, pps: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Vectorized _raw_to_up_osds + primary affinity."""
        npgs, width = raw.shape
        up = self._sweep_keep(pool, raw, self._sweep_osd_has(
            raw, self.osd_state_up & self.osd_state_exists))

        up_valid = up != CRUSH_ITEM_NONE
        first_valid = np.argmax(up_valid, axis=1)
        any_valid = up_valid.any(axis=1)
        up_primary = np.where(
            any_valid,
            up[np.arange(npgs), first_valid],
            -1,
        ).astype(np.int32)

        aff = self.osd_primary_affinity
        if aff is not None:
            up, up_primary = self._sweep_affinity(pool, up, up_primary, pps)
        return up.astype(np.int32), up_primary

    def _sweep_affinity(self, pool, up, up_primary, pps):
        npgs, width = up.shape
        aff = self.osd_primary_affinity
        valid = up != CRUSH_ITEM_NONE
        a = np.where(
            valid, aff[np.clip(up, 0, self.max_osd - 1)], 0
        ).astype(np.uint32)
        any_non_default = (valid & (a != DEFAULT_PRIMARY_AFFINITY)).any(axis=1)
        h = (
            np.asarray(
                hashes.hash32_2(
                    np.broadcast_to(
                        pps.astype(np.uint32)[:, None], up.shape
                    ).copy(),
                    np.where(valid, up, 0).astype(np.uint32),
                )
            )
            >> 16
        )
        accept = valid & ((a >= MAX_PRIMARY_AFFINITY) | (h < a))
        first_accept = np.argmax(accept, axis=1)
        has_accept = accept.any(axis=1)
        first_valid = np.argmax(valid, axis=1)
        pos = np.where(has_accept, first_accept, first_valid)
        has_any = valid.any(axis=1)
        rows = np.arange(npgs)
        new_primary = np.where(has_any, up[rows, pos], -1)
        use = any_non_default & has_any
        up_primary = np.where(use, new_primary, up_primary).astype(np.int32)
        if pool.can_shift_osds():
            # move primary to front where applied (shift the prefix right)
            up = up.copy()
            for i in np.nonzero(use & (pos > 0))[0]:
                p = pos[i]
                up[i, 1 : p + 1] = up[i, :p]
                up[i, 0] = up_primary[i]
        return up, up_primary
