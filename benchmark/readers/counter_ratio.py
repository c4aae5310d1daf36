"""Reader `counter_ratio`: the window's delta of the /metrics series named
in `num` (a name ending in `{` matches every label set of that family),
over `den`: "queries" (the window's query count), another list of series,
or nothing (the bare delta); times `scale`.  Returns nothing where the
program exports none of the series."""


def _delta(ctx, names):
    found, total = False, 0.0
    for want in names:
        for name, after in ctx["m1"].items():
            if name == want or (want.endswith("{") and name.startswith(want)):
                found = True
                total += after - ctx["m0"].get(name, 0.0)
    return total if found else None


def read(args: dict, ctx: dict):
    num = _delta(ctx, args["num"])
    if num is None:
        return None
    den = args.get("den")
    if den == "queries":
        den = ctx["queries"]
    elif den is not None:
        den = _delta(ctx, den)
    else:
        den = 1
    if not den:
        return None
    return num / den * args.get("scale", 1)
