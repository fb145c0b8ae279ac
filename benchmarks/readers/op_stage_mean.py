"""Mean time, in ms, between two stages of the op timelines that the
program's span ring holds (`OpTracker.unregister` files one record an
op): args {"from": stage, "to": stage, "concluded": terminal stage}.
Taken over the ops that concluded with that terminal stage between the
start of the window's first batch and the end of its last (the window
of `span_self_per_batch`), at each stage's first mark.  Nothing where
that window reads nothing or no such op is in it."""

from readers.span_self_per_batch import window


def read(args: dict, ctx: dict):
    w = window(ctx)
    if w is None:
        return None
    from ceph_tpu.core.tracing import COUNTS

    deltas = []
    for op in w.ops:
        at: dict = {}
        for t, stage, _detail in op[COUNTS]["events"]:
            at.setdefault(stage, t)
        if all(s in at for s in (args["from"], args["to"],
                                 args["concluded"])):
            deltas.append(at[args["to"]] - at[args["from"]])
    return sum(deltas) / len(deltas) * 1e3 if deltas else None
