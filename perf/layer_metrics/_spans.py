"""Helpers for readers of the program's obs spans."""

from __future__ import annotations


def walk(span):
    yield span
    for child in span.children:
        yield from walk(child)


def first(span, name: str):
    """The earliest-starting descendant named `name`, or None."""
    found = [s for s in walk(span) if s.name == name and s.t_end_us is not None]
    return min(found, key=lambda s: s.t_start_us) if found else None


def mean_ms(values) -> float | None:
    values = list(values)
    return sum(values) / len(values) / 1e3 if values else None


def stage_ms(ctx, root_name: str, stage: str):
    """Mean duration of the first `stage` under each `root_name` root."""
    stages = (first(r, stage) for r in ctx["roots"] if r.name == root_name)
    return mean_ms(s.t_end_us - s.t_start_us for s in stages if s is not None)


def device_idle(ctx):
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
