"""Decision layer: mean time per link event building routes, the
decision.route_build spans under each kvstore.publication's decision
stage less the decision.spf spans inside them (self time)."""

from perf.layer_metrics._stages import duration_us, named, per_event


def _self_us(decision):
    builds = named(decision, "decision.route_build")
    if not builds:
        return None
    return sum(
        duration_us(b) - sum(map(duration_us, named(b, "decision.spf"))) for b in builds
    )


def read(ctx):
    return per_event(ctx, _self_us)
