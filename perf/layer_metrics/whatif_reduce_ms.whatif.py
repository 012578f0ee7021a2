"""Decision layer: mean time per query reducing the scenario distances
to reachability counts and shaping the rows, the whatif.reduce spans
under each serving.query's dispatch stage."""

from perf.layer_metrics._stages import per_query, sum_named


def read(ctx):
    return per_query(ctx, sum_named("whatif.reduce"))
