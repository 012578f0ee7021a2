"""Decision layer, KSP2 pre-pass: mean time per link event tracing the
first (k=1) paths and building the per-destination edge masks, the
ksp2.trace spans under each kvstore.publication's decision stage."""

from perf.layer_metrics._stages import per_event, sum_named


def read(ctx):
    return per_event(ctx, sum_named("ksp2.trace"))
