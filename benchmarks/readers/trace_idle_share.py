"""100 * (1 - device busy / traced slice), from the profiler trace.
Nothing where the device's trace buffer overflowed: busy then reads low."""


def read(args: dict, ctx: dict):
    t = ctx.get("trace")
    if not t or not t["window_s"] or t.get("buffers_dropped"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
