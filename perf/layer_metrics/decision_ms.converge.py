"""Decision layer (SPF and route build): mean duration of the decision
stage of each kvstore.publication trace."""

from perf.layer_metrics._spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "kvstore.publication", "decision")
