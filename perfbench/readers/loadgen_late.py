"""How late the load generator ran: a percentile of send time minus due
time over the window's requests, in ms."""


def read(ctx, percentile=99):
    late = sorted(r.sent - r.due for r in ctx.records if r.sent is not None)
    if not late:
        return None
    return 1000.0 * late[min(int(len(late) * percentile / 100), len(late) - 1)]
