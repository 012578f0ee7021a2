"""Decision layer: mean time per query resolving SRLG links to edges
and building the scenario masks, the whatif.resolve spans under each
serving.query's dispatch stage."""

from perf.layer_metrics._stages import per_query, sum_named


def read(ctx):
    return per_query(ctx, sum_named("whatif.resolve"))
