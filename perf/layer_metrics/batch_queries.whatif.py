"""Serving scheduler: queries per dispatched batch over the window,
(change in serving.batches + serving.coalesced) / change in
serving.batches."""


def read(ctx):
    before, after = ctx["counters"]["before"], ctx["counters"]["after"]
    batches = after.get("serving.batches", 0) - before.get("serving.batches", 0)
    if batches <= 0:
        return None
    coalesced = after.get("serving.coalesced", 0) - before.get("serving.coalesced", 0)
    return (batches + coalesced) / batches
