"""Decision layer: mean time per link event in SPF, the summed
decision.spf spans (solver cache misses: CSR mirror refresh, engine call
and fetch, or the host Dijkstra) under each kvstore.publication's
decision stage."""

from perf.layer_metrics._stages import per_event, sum_named


def read(ctx):
    return per_event(ctx, sum_named("decision.spf"))
