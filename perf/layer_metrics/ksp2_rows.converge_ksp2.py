"""Decision layer: masked relax rows run by the KSP2 pre-pass per route
rebuild over the window, change in decision.ksp2_rows / change in
decision.rebuilds; nothing where the program has no such counter."""


def read(ctx):
    before, after = ctx["counters"]["before"], ctx["counters"]["after"]
    if "decision.ksp2_rows" not in after:
        return None
    rebuilds = after.get("decision.rebuilds", 0) - before.get("decision.rebuilds", 0)
    if rebuilds <= 0:
        return None
    return (after["decision.ksp2_rows"] - before.get("decision.ksp2_rows", 0)) / rebuilds
