"""Decision layer: mean time per query that the backend call's closures
wait for Decision's event loop, the summed eventbase.wait stages under
each serving.query's dispatch stage."""

from perf.layer_metrics._stages import per_query, sum_named


def read(ctx):
    return per_query(ctx, sum_named("eventbase.wait"))
