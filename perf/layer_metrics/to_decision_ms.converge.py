"""KvStore layer (flood, queue, debounce): mean time from a
kvstore.publication root's start to the start of its decision stage."""

from perf.layer_metrics._spans import first, mean_ms


def read(ctx):
    gaps = []
    for r in ctx["roots"]:
        if r.name == "kvstore.publication":
            d = first(r, "decision")
            if d is not None:
                gaps.append(d.t_start_us - r.t_start_us)
    return mean_ms(gaps)
