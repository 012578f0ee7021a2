"""Relax programs, masked rows: mean time per link event in the KSP2
pre-pass's masked relax, one row per destination with its first paths'
links masked out, up to the fetch of its result (the ksp2.relax spans
under each kvstore.publication's decision stage)."""

from perf.layer_metrics._stages import per_event, sum_named


def read(ctx):
    return per_event(ctx, sum_named("ksp2.relax"))
