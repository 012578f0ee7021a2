"""ctrl wire: mean duration of the ctrl.reply roots of what-if queries,
from the batch's answers being ready to the reply line written and
drained (loop wake-up, shaping, to_wire, JSON encode, write)."""

from perf.layer_metrics._spans import mean_ms


def read(ctx):
    return mean_ms(
        r.t_end_us - r.t_start_us
        for r in ctx["roots"]
        if r.name == "ctrl.reply" and r.tags.get("op") == "what_if"
    )
