"""`crush_do_rule` for a straw2 hierarchy of any depth and the steps an
erasure-coded pool's rule is made of: `set_choose_tries`,
`set_chooseleaf_tries`, `take`, `choose indep`, `chooseleaf indep`,
`emit`.  The plain reference `correct` compares the layered CRUSH
configuration with: numpy only, nothing of `ceph_tpu` imported.

Written from `src/crush/mapper.c`: `crush_do_rule`,
`crush_choose_indep`, `bucket_straw2_choose`, `is_out`.  One lane of
the arrays below is one x; the loops are the C's loops in the C's
order (rounds of ftotal, slots in order inside a round, the descent,
the collision check against every slot, the leaf recursion with its
own tries, `is_out`), run for all lanes that are at the same point.
Departures from the C, each for what this file is asked to place:

- buckets are straw2 only, so the `CRUSH_BUCKET_UNIFORM` case of r'
  (`r += (numrep+1) * ftotal`) does not exist: r' = r + numrep * ftotal;
- no `choose_args` (weight sets) and no `firstn` steps: a rule that has
  them is refused, not approximated;
- the map is built here from the configuration's `layers`, as
  `crushtool --build` lays them out, not read from a compiled map.
"""

from __future__ import annotations

import numpy as np

from reference import NONE, _ln16, hash32_3

UNDEF = 0x7FFFFFFE
_M32 = np.uint64(0xFFFFFFFF)


def _mix(a, b, c):
    """crush_hashmix (src/crush/hash.c), on uint64 arrays holding u32."""
    a = (a - b - c) & _M32; a ^= c >> np.uint64(13)            # noqa: E702
    b = (b - c - a) & _M32; b ^= (a << np.uint64(8)) & _M32    # noqa: E702
    c = (c - a - b) & _M32; c ^= b >> np.uint64(13)            # noqa: E702
    a = (a - b - c) & _M32; a ^= c >> np.uint64(12)            # noqa: E702
    b = (b - c - a) & _M32; b ^= (a << np.uint64(16)) & _M32   # noqa: E702
    c = (c - a - b) & _M32; c ^= b >> np.uint64(5)             # noqa: E702
    a = (a - b - c) & _M32; a ^= c >> np.uint64(3)             # noqa: E702
    b = (b - c - a) & _M32; b ^= (a << np.uint64(10)) & _M32   # noqa: E702
    c = (c - a - b) & _M32; c ^= b >> np.uint64(15)            # noqa: E702
    return a, b, c


def hash32_2(a, b):
    """crush_hash32_rjenkins1_2 over uint64 arrays holding u32 values."""
    a, b = (np.asarray(v, dtype=np.uint64) & _M32 for v in (a, b))
    h = np.uint64(1315423911) ^ a ^ b
    x = np.full_like(h, 231232)
    y = np.full_like(h, 1232)
    a, b, h = _mix(a, b, h)
    x, a, h = _mix(x, a, h)
    b, y, h = _mix(b, y, h)
    return h


def device_weights(cfg: dict) -> np.ndarray:
    """The 16.16 device weights (`crushtool --test --weight <osd> <w>`)
    the configuration states: its default, then each mark."""
    dw = cfg["device_weights"]
    w = np.full(cfg["num_osds"], dw["default"], dtype=np.uint32)
    for mark in dw["marks"]:
        w[np.asarray(mark["osds"], dtype=np.int64)] = mark["weight"]
    return w


class CrushTreeRef:
    """The configuration's map, built as `crushtool --build` builds it
    (`layers` from the devices upward, each grouping the one below in
    order into buckets of `size`, 0 = all in one; a bucket's weight is
    the sum of its items'), and `do_rule` over it."""

    def __init__(self, cfg: dict) -> None:
        self.max_devices = cfg["num_osds"]
        lower = list(range(cfg["num_osds"]))
        lower_w = [cfg["osd_weight"]] * cfg["num_osds"]
        buckets: dict = {}
        names = {}
        for layer in cfg["layers"]:
            if layer["alg"] != "straw2":
                raise ValueError("straw2 buckets only")
            size = layer["size"] or len(lower)
            ids = list(layer["bucket_ids"])
            made_w = []
            for n, lo in enumerate(range(0, len(lower), size)):
                buckets[ids[n]] = (layer["type_id"], lower[lo: lo + size],
                                   lower_w[lo: lo + size])
                made_w.append(sum(lower_w[lo: lo + size]))
            if len(made_w) != len(ids):
                raise ValueError(f"layer {layer['type_name']}: "
                                 f"{len(made_w)} buckets, {len(ids)} ids")
            names[layer["type_name"]] = (layer["type_id"], ids)
            lower, lower_w = ids, made_w
        nb = max(-b for b in buckets)
        width = max(len(b[1]) for b in buckets.values())
        self.items = np.zeros((nb, width), dtype=np.int64)
        self.weights = np.zeros((nb, width), dtype=np.int64)
        self.sizes = np.zeros(nb, dtype=np.int64)
        self.types = np.zeros(nb, dtype=np.int64)
        for bid, (type_id, its, ws) in buckets.items():
            self.items[-1 - bid, :len(its)] = its
            self.weights[-1 - bid, :len(its)] = ws
            self.sizes[-1 - bid] = len(its)
            self.types[-1 - bid] = type_id
        self.names = names
        self.steps = [tuple(s) for s in cfg["rule_steps"]]
        self.result_max = cfg["num_rep"]
        self.total_tries = cfg["tunables"]["choose_total_tries"]
        self.dev_w = device_weights(cfg).astype(np.int64)
        self.ln = _ln16()

    # -- bucket_straw2_choose ---------------------------------------------
    def _choose(self, bno, x, r):
        """bno, x, r [n] -> the item of the largest draw, first on ties;
        an item of weight 0 draws S64_MIN."""
        its = self.items[bno]
        ws = self.weights[bno]
        u = hash32_3(x[:, None], its.astype(np.uint64) & _M32,
                     r.astype(np.uint64)[:, None] & _M32)
        ln = self.ln[(u & np.uint64(0xFFFF)).astype(np.int64)]
        draw = -((-ln) // np.maximum(ws, 1))     # div64_s64 truncates
        live = (np.arange(its.shape[1])[None, :] < self.sizes[bno][:, None])
        draw = np.where(live & (ws > 0), draw, np.iinfo(np.int64).min)
        # a slot past the bucket's size never wins: the C does not
        # visit it (items of weight 0 can win only if all are)
        draw = np.where(live, draw, np.iinfo(np.int64).min)
        return its[np.arange(len(bno)), np.argmax(draw, axis=1)]

    def _is_out(self, item, x):
        w = self.dev_w[np.clip(item, 0, len(self.dev_w) - 1)]
        h = hash32_2(x, item.astype(np.uint64) & _M32) & np.uint64(0xFFFF)
        out = np.where(w >= 0x10000, False,
                       np.where(w == 0, True, h.astype(np.int64) >= w))
        return np.where(item >= len(self.dev_w), True, out)

    # -- crush_choose_indep -----------------------------------------------
    def _indep(self, start, x, left, numrep, want, tries, recurse_tries,
               to_leaf, parent_r, rep0=0):
        """All lanes start from bucket `start` [n].  Returns (out, out2)
        [n, left]; out2 is None unless `to_leaf`.  `rep0` is the C's
        `outpos`: the nested call places the caller's slot `rep`."""
        n = len(x)
        out = np.full((n, left), UNDEF, dtype=np.int64)
        out2 = np.full((n, left), UNDEF, dtype=np.int64) if to_leaf else None
        for ftotal in range(tries):
            if not (out == UNDEF).any():
                break
            for col in range(left):
                rep = rep0 + col
                at = np.nonzero(out[:, col] == UNDEF)[0]
                if not len(at):
                    continue
                r = rep + parent_r[at] + numrep * ftotal
                cur = start[at].copy()
                item = np.zeros(len(at), dtype=np.int64)
                # 0 walking, 1 reached an item of the wanted type,
                # 2 slot becomes NONE, 3 nothing this round
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
                none = at[state == 2]
                out[none, col] = NONE
                if to_leaf:
                    out2[none, col] = NONE
                ok = state == 1
                # collision with any slot of this call
                ok &= ~(out[at] == item[:, None]).any(axis=1)
                leaf = item.copy()
                if to_leaf:
                    rec = np.nonzero(ok & (item < 0))[0]
                    if len(rec):
                        got, _ = self._indep(
                            -1 - item[rec], x[at[rec]], 1, numrep, 0,
                            recurse_tries, 0, False, r[rec], rep0=rep)
                        leaf[rec] = got[:, 0]
                        ok[rec] &= got[:, 0] != NONE
                if want == 0:
                    ok &= ~self._is_out(item, x[at])
                out[at[ok], col] = item[ok]
                if to_leaf:
                    out2[at[ok], col] = leaf[ok]
        out[out == UNDEF] = NONE
        if to_leaf:
            out2[out2 == UNDEF] = NONE
        return out, out2

    # -- crush_do_rule ----------------------------------------------------------
    def do_rule(self, xs: np.ndarray) -> np.ndarray:
        xs = np.asarray(xs, dtype=np.int64)
        n = len(xs)
        x = xs.astype(np.uint64) & _M32
        result = np.full((n, self.result_max), NONE, dtype=np.int64)
        result_len = 0
        choose_tries = self.total_tries + 1
        leaf_tries = 0
        w: list = []          # the working set: a list of [n] columns
        for step in self.steps:
            op = step[0]
            if op == "set_choose_tries":
                if step[1] > 0:
                    choose_tries = step[1]
            elif op == "set_chooseleaf_tries":
                if step[1] > 0:
                    leaf_tries = step[1]
            elif op == "take":
                type_id, ids = self.names[step[1]]
                if len(ids) != 1:
                    raise ValueError("take names one bucket")
                w = [np.full(n, ids[0], dtype=np.int64)]
            elif op in ("choose_indep", "chooseleaf_indep"):
                to_leaf = op == "chooseleaf_indep"
                want = self.names[step[2]][0] if step[2] != "osd" else 0
                o: list = []
                for src in w:
                    numrep = step[1]
                    if numrep <= 0:
                        numrep += self.result_max
                        if numrep <= 0:
                            continue
                    bno = -1 - src
                    # the rules this file is given take a bucket that
                    # exists; an id that is none would be skipped
                    if ((bno < 0) | (bno >= len(self.sizes))).any():
                        raise ValueError("take of a bucket the map lacks")
                    out_size = min(numrep, self.result_max - len(o))
                    out, out2 = self._indep(
                        bno, x, out_size, numrep, want, choose_tries,
                        leaf_tries or 1, to_leaf,
                        np.zeros(n, dtype=np.int64))
                    vals = out2 if to_leaf else out
                    o += [vals[:, c] for c in range(out_size)]
                w = o
            elif op == "emit":
                for col in w:
                    if result_len < self.result_max:
                        result[:, result_len] = col
                        result_len += 1
                w = []
            else:
                raise ValueError(f"step {op!r} is not one this reference runs")
        return result.astype(np.int32)
