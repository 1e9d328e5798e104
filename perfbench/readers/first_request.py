"""Seconds from sending the first request, to zero replicas, until its
first token: pod launch, load, compile or cache reads, warm-up, prefill."""


def read(ctx):
    f = ctx.first
    if not f or not f.token_times:
        return None
    return f.token_times[0] - f.sent
