"""Mean time to first token at the client (from the moment the request was
SENT, not due) minus the engine's own mean (submit to first emitted token):
what proxy, router and the HTTP hops add. Both means are over the requests
whose first token came between the two scrapes at the window's edges: the
engine's histogram counts a request when its first token is emitted."""


def read(ctx, engine_histogram="kubeai_engine_ttft_seconds"):
    lo, hi = ctx.before.at, ctx.after.at
    ttfts = [
        r.token_times[0] - r.sent for r in ctx.all_records
        if r.token_times and r.sent and lo <= r.token_times[0] < hi
    ]
    n = ctx.after.value(engine_histogram + "_count") - ctx.before.value(engine_histogram + "_count")
    s = ctx.after.value(engine_histogram + "_sum") - ctx.before.value(engine_histogram + "_sum")
    if not ttfts or n <= 0:
        return None
    return 1000.0 * (sum(ttfts) / len(ttfts) - s / n)
