"""Decision layer, KSP2 pre-pass: mean time per link event decoding the
masked rows into path links and tracing the second (k=2) paths, the
ksp2.decode spans under each kvstore.publication's decision stage."""

from perf.layer_metrics._stages import per_event, sum_named


def read(ctx):
    return per_event(ctx, sum_named("ksp2.decode"))
