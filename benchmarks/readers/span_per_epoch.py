"""Mean time an epoch, in ms, of the named spans of the program's span
ring (`ceph_tpu.core.tracing`) over a remap window: args {"spans":
[names]}, and `"whole": true` for their whole duration (children
included) in place of their self time.  The join with the window is by
the program's totals, which the driver's snapshots hold before and
after it: an `osdmap.map_pgs` span belongs to the window when before <
its `mapping` <= after of `osdmap.mappings`, an `osdmap.apply` span when
before < its `apply` <= after of `osdmap.applies`, and everything below
such a span with it.  Nothing (never 0) where the program keeps no
`osdmap.mappings` total (a parent without it), no epoch ran, a span of
the range is no longer in the ring, the range reaches back to records
the ring overwrote, or no span of these names lies in it."""


def window(ctx: dict):
    """{"epochs", "self_ns", "whole_ns"} of the window's spans by name,
    worked out once a run; None as above."""
    if "remap_window" not in ctx:
        ctx["remap_window"] = _window(ctx)
    return ctx["remap_window"]


def _joined(recs, name: str, count: str, lo, hi):
    """The spans `name` whose `count` lies in (lo, hi]; None where one of
    the range is not in the ring."""
    if lo is None or hi is None:
        return []
    from ceph_tpu.core import tracing

    got = [r for r in recs if r[tracing.NAME] == name
           and lo < r[tracing.COUNTS].get(count, lo) <= hi]
    return got if len(got) == hi - lo else None


def _window(ctx: dict):
    from ceph_tpu.core import tracing

    before, after = ctx["before"], ctx["after"]
    lo, hi = before.get("osdmap.mappings"), after.get("osdmap.mappings")
    if lo is None or hi is None or hi <= lo:
        return None
    N = tracing.NAME
    recs, lost_until = tracing.recorder().held()
    mapped = _joined(recs, "osdmap.map_pgs", "mapping", lo, hi)
    applied = _joined(recs, "osdmap.apply", "apply",
                      before.get("osdmap.applies"),
                      after.get("osdmap.applies"))
    if mapped is None or applied is None:
        return None
    roots = mapped + applied
    if min(r[tracing.T0] for r in roots) <= lost_until:
        return None
    tree = {r[tracing.ID]: r for r in roots}
    # children close before their parents: one pass from the newest
    # record back finds every descendant
    for r in reversed(recs):
        if r[tracing.PARENT] in tree:
            tree[r[tracing.ID]] = r
    spans = list(tree.values())
    own = tracing.self_ns(spans)
    self_ns: dict = {}
    whole_ns: dict = {}
    for r in spans:
        self_ns[r[N]] = self_ns.get(r[N], 0) + own[r[tracing.ID]]
        whole_ns[r[N]] = (whole_ns.get(r[N], 0)
                          + r[tracing.T1] - r[tracing.T0])
    return {"epochs": hi - lo, "self_ns": self_ns, "whole_ns": whole_ns}


def read(args: dict, ctx: dict):
    w = window(ctx)
    if w is None:
        return None
    by = w["whole_ns"] if args.get("whole") else w["self_ns"]
    if not any(n in by for n in args["spans"]):
        return None
    return sum(by.get(n, 0) for n in args["spans"]) / w["epochs"] / 1e6
