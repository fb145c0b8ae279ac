"""A counter's growth over the window, or its ratio to another's:
args {"counter": name, "per": name (optional)}.  Nothing where the
driver keeps no such counter or the divisor did not move."""


def read(args: dict, ctx: dict):
    before, after = ctx["before"], ctx["after"]
    c = args["counter"]
    if c not in after:
        return None
    d = after[c] - before[c]
    if "per" not in args:
        return d
    per = after[args["per"]] - before[args["per"]]
    return d / per if per else None
