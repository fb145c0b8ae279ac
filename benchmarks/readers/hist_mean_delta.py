"""Mean of the values a histogram took in over the window, scaled:
args {"hist": name, "scale": factor}; the driver's snapshot holds
`<name>.sum` and `<name>.count`.  Nothing where none came in."""


def read(args: dict, ctx: dict):
    before, after = ctx["before"], ctx["after"]
    h = args["hist"]
    if h + ".count" not in after:
        return None
    n = after[h + ".count"] - before[h + ".count"]
    if not n:
        return None
    return (after[h + ".sum"] - before[h + ".sum"]) / n * args.get("scale", 1)
