"""Residency engine, relax programs: mean time per query in the masked
relax up to its result being ready, the whatif.relax spans under each
serving.query's dispatch stage."""

from perf.layer_metrics._stages import per_query, sum_named


def read(ctx):
    return per_query(ctx, sum_named("whatif.relax"))
