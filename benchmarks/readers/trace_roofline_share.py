"""The least seconds the work completed in the traced slice needs at
the chip's HBM peak (work.py, args {"work": function name}), over the
seconds the device was busy in it.  Nothing where no work completed,
the device never ran, or its trace buffer overflowed: never 0."""

import work


def read(args: dict, ctx: dict):
    t = ctx.get("trace")
    if not t or t["busy_s"] <= 0 or t.get("buffers_dropped"):
        return None
    nbytes = getattr(work, args["work"])(ctx["cfg"], ctx["slice"])
    if nbytes <= 0:
        return None
    return work.roofline_pct(nbytes, ctx["device_kind"], t["busy_s"])
