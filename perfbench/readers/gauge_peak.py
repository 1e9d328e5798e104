"""Highest value of a gauge over the window's polls (one a second), as a
share of another gauge."""


def read(ctx, gauge, of, scale=100.0):
    seen = [s.value(gauge) for s in ctx.polls if s.has(gauge)]
    total = ctx.after.value(of)
    if not seen or total <= 0:
        return None
    return scale * max(seen) / total
