"""Mean time, in ms, between two stages of the served ops' whole lines:
each client op's timeline joined to its primary's by reqid
(`tracing.joined`, on the one monotonic clock), from the client's
`created` to its `returned`: args {"from": stage, "to": stage}, each at
its first mark.  Taken over the joined ops whose client concluded
between the start of the window's first batch and the end of its last
(the window of `span_self_per_batch`).

Optional `"slowest": share`: over that share of the joined ops with
the longest client time (`created` to `returned`, the time the clock
around `write_full` reads) instead, at least one op; the share of
`op_p95_ms` is 0.05.  Optional `"minus": [[from, to], ...]`: those
stretches subtracted from each op's first.  An op that lacks a stage
the args name counts in no mean.  Nothing (never 0) where that window
reads nothing, the program files no client records (a parent without
them) or no client op joined."""

import math

from readers.span_self_per_batch import window


def read(args: dict, ctx: dict):
    w = window(ctx)
    lines = getattr(w, "joined", None)
    if not lines:
        return None
    ops = []
    for line in lines:
        at: dict = {}
        for t, stage in line:
            at.setdefault(stage, t)
        ops.append(at)
    share = args.get("slowest")
    if share:
        whole = [at for at in ops if "created" in at and "returned" in at]
        whole.sort(key=lambda at: at["returned"] - at["created"],
                   reverse=True)
        ops = whole[:max(1, math.ceil(len(whole) * share))]
    pairs = [(args["from"], args["to"])] + [
        tuple(p) for p in args.get("minus", [])]
    deltas = [(at[pairs[0][1]] - at[pairs[0][0]])
              - sum(at[b] - at[a] for a, b in pairs[1:])
              for at in ops if all(s in at for p in pairs for s in p)]
    return sum(deltas) / len(deltas) / 1e6 if deltas else None
