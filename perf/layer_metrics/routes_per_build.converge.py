"""Decision layer: routes computed per route rebuild over the window,
change in decision.get_route_for_prefix / change in decision.rebuilds."""


def read(ctx):
    before, after = ctx["counters"]["before"], ctx["counters"]["after"]
    rebuilds = after.get("decision.rebuilds", 0) - before.get("decision.rebuilds", 0)
    if rebuilds <= 0:
        return None
    routes = after.get("decision.get_route_for_prefix", 0) - before.get(
        "decision.get_route_for_prefix", 0
    )
    return routes / rebuilds
