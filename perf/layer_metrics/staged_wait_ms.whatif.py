"""Serving scheduler: mean time a coalesced query's batch waits in the
one-slot staging queue for the executor, the staged stage of each
serving.query trace."""

from perf.layer_metrics._spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "serving.query", "staged")
