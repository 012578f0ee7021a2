"""Serving scheduler: mean time a query waits before dispatch, its
admission plus coalesce stages, per serving.query trace."""

from perf.layer_metrics._spans import first, mean_ms


def read(ctx):
    waits = []
    for r in ctx["roots"]:
        if r.name != "serving.query":
            continue
        stages = [first(r, s) for s in ("admission", "coalesce")]
        if all(s is not None for s in stages):
            waits.append(sum(s.t_end_us - s.t_start_us for s in stages))
    return mean_ms(waits)
