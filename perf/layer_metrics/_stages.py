"""Helpers for readers of the spans inside one stage of each root: the
Decision split under a kvstore.publication's `decision`, the what-if
call under a serving.query's `dispatch`."""

from __future__ import annotations

from perf.layer_metrics._spans import first, mean_ms, walk


def duration_us(span) -> int:
    return span.t_end_us - span.t_start_us


def named(span, name: str) -> list:
    """Every finished descendant of `span` named `name`."""
    return [s for s in walk(span) if s.name == name and s.t_end_us is not None]


def sum_named(name: str):
    """A `value` for `mean_under`: the summed duration of the spans named
    `name` under the stage, or None where there are none."""

    def value(stage):
        found = named(stage, name)
        return sum(map(duration_us, found)) if found else None

    return value


def mean_under(ctx, root_name: str, stage: str, value):
    """Mean over the `root_name` roots that reached `stage` of
    `value(first stage)` in microseconds, as ms.  A root whose stage has
    nothing to read counts 0; None when no root has anything (a program
    without these spans)."""
    values = []
    for r in ctx["roots"]:
        if r.name == root_name:
            s = first(r, stage)
            if s is not None:
                values.append(value(s))
    if all(v is None for v in values):
        return None
    return mean_ms(v or 0 for v in values)


def per_event(ctx, value):
    return mean_under(ctx, "kvstore.publication", "decision", value)


def per_query(ctx, value):
    return mean_under(ctx, "serving.query", "dispatch", value)
