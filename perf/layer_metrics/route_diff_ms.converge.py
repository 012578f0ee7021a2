"""Decision layer: mean time per link event diffing the new route
table against the last, the decision.route_diff spans under each
kvstore.publication's decision stage."""

from perf.layer_metrics._stages import per_event, sum_named


def read(ctx):
    return per_event(ctx, sum_named("decision.route_diff"))
