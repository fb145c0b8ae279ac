"""Mean self time a batch, in ms, of the named spans of the program's
span ring (`ceph_tpu.core.tracing`): args {"spans": [names]}.  Self
time is a span's duration minus what its direct children cover.  The
join with the window is the queue's `batches` counter, which the
driver's snapshots hold before and after it: a `queue.batch` span (and
the `queue.idle` / `queue.coalesce` that led to it) belongs to the
window when before < its `seq` <= after, and everything below such a
span with it.  Nothing (never 0) where the program has no recorder, the
driver keeps no such counter, no batch ran, a batch of the range is no
longer in the ring, the range reaches back to records the ring
overwrote, or no span of these names lies in it."""


def window(ctx: dict):
    """The ring's part of the window (`tracing.batch_window`), worked
    out once a run; None as above."""
    if "ring_window" not in ctx:
        from ceph_tpu.core import tracing

        find = getattr(tracing, "batch_window", None)
        lo = ctx["before"].get("queue.batches")
        hi = ctx["after"].get("queue.batches")
        ctx["ring_window"] = (find(lo, hi) if find is not None
                              and lo is not None and hi is not None
                              else None)
    return ctx["ring_window"]


def read(args: dict, ctx: dict):
    w = window(ctx)
    if w is None or not any(n in w.self_ns for n in args["spans"]):
        return None
    return sum(w.self_ns.get(n, 0) for n in args["spans"]) / w.batches / 1e6
