"""`crush_do_rule` for a straw2 hierarchy of any depth whose items carry
weights of their own, and the steps a replicated pool's rule is made of:
`take`, `choose firstn`, `chooseleaf firstn`, `emit`.  The plain
reference `correct` compares the capacity-weighted CRUSH configuration
with: numpy only, nothing of `ceph_tpu` imported.

Written from `src/crush/mapper.c`: `crush_do_rule`,
`crush_choose_firstn`, `bucket_straw2_choose`, `is_out`.  One lane of
the arrays below is one x; the loops are the C's loops in the C's order
(replicas in order; for each the descents of ftotal = 0, 1, ... with
r' = rep + parent_r + ftotal; the walk through the intervening buckets;
the collision check against the chosen prefix; the leaf recursion with
its own r, numrep and tries; `is_out`), run for all lanes that are at
the same point.  The straw2 draw (hash, `crush_ln`, `div64_s64` by the
item's 16.16 weight, first largest wins), `is_out` and the layout of
the buckets are `reference_crush_tree.CrushTreeRef`'s, inherited; the
weights are set here, one for each device.  Departures from the C, each for what
this file is asked to place:

- buckets are straw2 only: no uniform, list, tree or straw bucket, and
  so no `bucket_perm_choose`;
- `choose_local_tries` and `choose_local_fallback_tries` are 0 (jewel):
  a collision or a rejection always retries the whole descent
  (`retry_descent`), never the bucket (`retry_bucket`); a configuration
  that states another value is refused, not approximated;
- `chooseleaf_stable` is 1 (jewel): the leaf recursion places replica 0
  of 1 (`numrep` 1, `rep` from 0); 0 is refused.  `chooseleaf_vary_r`
  and `chooseleaf_descend_once` are read and followed;
- no `choose_args` (weight sets), no `indep` and no `set_*` steps: a
  rule that has them is refused;
- the map is built here from the configuration's `layers` and its
  `osd_weights`, as `crushtool --build` lays the buckets out and
  `--reweight-item` leaves their weights (a bucket's weight as an item
  is the sum of its items'), not read from a compiled map.
"""

from __future__ import annotations

import numpy as np

from reference import NONE
from reference_crush_tree import _M32, CrushTreeRef, device_weights


class CrushFirstnTreeRef(CrushTreeRef):
    """The configuration's map with its per-device CRUSH weights, and
    `do_rule` for firstn steps over it.  The parent class lays the
    buckets out (`layers`) and brings `_choose` (bucket_straw2_choose)
    and `_is_out`; it gives every device one weight, so the weights are
    set here: a device's from `osd_weights`, a bucket's as the sum of
    its items', from the lowest layer up."""

    def __init__(self, cfg: dict) -> None:
        tun = cfg["tunables"]
        if (tun["choose_local_tries"] or tun["choose_local_fallback_tries"]
                or not tun["chooseleaf_stable"]):
            raise ValueError("jewel tunables only: local tries 0, stable 1")
        if len(cfg["osd_weights"]) != cfg["num_osds"]:
            raise ValueError("one CRUSH weight a device")
        super().__init__({**cfg, "osd_weight": 0})
        weight = dict(enumerate(int(w) for w in cfg["osd_weights"]))
        for layer in cfg["layers"]:
            for bid in layer["bucket_ids"]:
                bno = -1 - bid
                ws = [weight[int(it)]
                      for it in self.items[bno, :self.sizes[bno]]]
                self.weights[bno, :len(ws)] = ws
                weight[bid] = sum(ws)
        self.descend_once = tun["chooseleaf_descend_once"]
        self.vary_r = tun["chooseleaf_vary_r"]

    # -- crush_choose_firstn ----------------------------------------------
    def _firstn(self, start, x, numrep, want, count, tries, recurse_tries,
                to_leaf, parent_r, prior=None, prior_len=None):
        """All lanes start from bucket `start` [n], with room for
        `count` [n] items.  `prior` [n, p] and `prior_len` [n] are what
        the C's `out` holds below `outpos` when it is called (the leaf
        recursion is handed the leaves chosen so far; `crush_do_rule`
        hands over nothing): a pick collides with them too.  Returns
        (out, out2, placed): out [n, numrep] holds what this call
        placed, a lane's in its first placed [n] places; out2 the
        leaves below them, or None unless `to_leaf`."""
        n = len(x)
        out = np.full((n, numrep), NONE, dtype=np.int64)
        out2 = np.full((n, numrep), NONE, dtype=np.int64) if to_leaf else None
        placed = np.zeros(n, dtype=np.int64)
        count = count.copy()

        def among(table, length, lanes, item):
            """item [k] is one of the first length[lane] of table[lane]"""
            return ((table[lanes] == item[:, None])
                    & (np.arange(table.shape[1])[None, :]
                       < length[lanes][:, None])).any(axis=1)

        for rep in range(numrep):
            # the lanes that still try this rep: a lane leaves when it
            # has placed it or given it up (skip_rep)
            at = np.nonzero(count > 0)[0]
            for ftotal in range(tries):
                if not len(at):
                    break
                r = rep + parent_r[at] + ftotal
                cur = start[at].copy()
                item = np.zeros(len(at), dtype=np.int64)
                # 0 walking, 1 reached an item of the wanted type,
                # 2 skip_rep (a bad item), 3 reject (an empty bucket)
                state = np.zeros(len(at), dtype=np.int64)
                while (state == 0).any():
                    w = np.nonzero(state == 0)[0]
                    empty = self.sizes[cur[w]] == 0
                    state[w[empty]] = 3
                    w = w[~empty]
                    if not len(w):
                        break
                    it = self._choose(cur[w], x[at[w]], r[w])
                    item[w] = it
                    bad = it >= self.max_devices
                    sub = np.clip(-1 - it, 0, len(self.sizes) - 1)
                    itype = np.where(it < 0, self.types[sub], 0)
                    hit = ~bad & (itype == want)
                    lost = ~bad & ~hit & ((it >= 0)
                                          | (-1 - it >= len(self.sizes)))
                    state[w[bad | lost]] = 2
                    state[w[hit]] = 1
                    down = ~bad & ~hit & ~lost
                    cur[w[down]] = sub[down]
                reached = state == 1
                collide = reached & among(out, placed, at, item)
                if prior is not None:
                    collide |= reached & among(prior, prior_len, at, item)
                reject = state == 3
                leaf = item.copy()
                if to_leaf:
                    rec = np.nonzero(reached & ~collide & (item < 0))[0]
                    if len(rec):
                        lanes = at[rec]
                        sub_r = (r[rec] >> (self.vary_r - 1) if self.vary_r
                                 else np.zeros(len(rec), dtype=np.int64))
                        # stable: replica 0 of 1, with its own r and
                        # tries, checked against the leaves so far
                        got, _, n_got = self._firstn(
                            -1 - item[rec], x[lanes], 1, 0, count[lanes],
                            recurse_tries, 0, False, sub_r,
                            out2[lanes], placed[lanes])
                        leaf[rec] = got[:, 0]
                        reject[rec[n_got == 0]] = True   # no leaf
                if want == 0:
                    chk = np.nonzero(reached & ~collide & ~reject)[0]
                    reject[chk] |= self._is_out(item[chk], x[at[chk]])
                ok = reached & ~collide & ~reject
                lanes = at[ok]
                out[lanes, placed[lanes]] = item[ok]
                if to_leaf:
                    out2[lanes, placed[lanes]] = leaf[ok]
                placed[lanes] += 1
                count[lanes] -= 1
                # reject or collide: ftotal++, then retry_descent while
                # ftotal < tries, else skip_rep
                at = at[reject | collide]
        return out, out2, placed

    # -- crush_do_rule ----------------------------------------------------------
    def do_rule(self, xs: np.ndarray) -> np.ndarray:
        xs = np.asarray(xs, dtype=np.int64)
        n = len(xs)
        x = xs.astype(np.uint64) & _M32
        rmax = self.result_max
        result = np.full((n, rmax), NONE, dtype=np.int64)
        result_len = np.zeros(n, dtype=np.int64)
        choose_tries = self.total_tries + 1
        recurse_tries = 1 if self.descend_once else choose_tries
        # the working set: [n, rmax] with a lane's items in its first
        # wsize places
        w = np.full((n, rmax), NONE, dtype=np.int64)
        wsize = np.zeros(n, dtype=np.int64)
        for step in self.steps:
            op = step[0]
            if op == "take":
                type_id, ids = self.names[step[1]]
                if len(ids) != 1:
                    raise ValueError("take names one bucket")
                w[:, 0], wsize[:] = ids[0], 1
            elif op in ("choose_firstn", "chooseleaf_firstn"):
                to_leaf = op == "chooseleaf_firstn"
                want = self.names[step[2]][0] if step[2] != "osd" else 0
                numrep = step[1] if step[1] > 0 else step[1] + rmax
                o = np.full((n, rmax), NONE, dtype=np.int64)
                osize = np.zeros(n, dtype=np.int64)
                for i in range(rmax if numrep > 0 else 0):
                    bno = -1 - w[:, i]
                    at = np.nonzero((i < wsize) & (bno >= 0)
                                    & (bno < len(self.sizes)))[0]
                    if not len(at):
                        continue
                    out, out2, got = self._firstn(
                        bno[at], x[at], numrep, want, rmax - osize[at],
                        choose_tries, recurse_tries, to_leaf,
                        np.zeros(len(at), dtype=np.int64))
                    vals = out2 if to_leaf else out
                    for c in range(min(numrep, rmax)):
                        put = at[c < got]
                        o[put, osize[put]] = vals[c < got, c]
                        osize[put] += 1
                w, wsize = o, osize
            elif op == "emit":
                for i in range(rmax):
                    put = np.nonzero((i < wsize) & (result_len < rmax))[0]
                    result[put, result_len[put]] = w[put, i]
                    result_len[put] += 1
                wsize = np.zeros(n, dtype=np.int64)
            else:
                raise ValueError(f"step {op!r} is not one this reference runs")
        return result.astype(np.int32)
