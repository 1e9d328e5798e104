"""A percentile, at the client, over the requests due in the window: of
the time to first token from when each was due (`of` = "ttft") or of all
gaps between consecutive streamed tokens, pooled (`of` = "itl"). Nearest
rank, in ms. The tails of an open-loop cell with some 150 requests a window
spread too widely to carry a bound, and docqa's 90th percentile of 95 asks
falls between two of its three document sizes, now on one and now on the
other by the seed's order, so they stand here, beside the end-to-end metric."""


def read(ctx, of, percentile):
    if of == "ttft":
        values = [r.token_times[0] - r.due for r in ctx.records if r.token_times]
    else:
        values = [b - a for r in ctx.records for a, b in zip(r.token_times, r.token_times[1:])]
    if not values:
        return None
    values.sort()
    return 1000.0 * values[min(int(len(values) * percentile / 100), len(values) - 1)]
