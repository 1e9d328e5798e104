"""scale * (sum of series deltas over the window) / (sum of series deltas,
or the window's seconds). Series are Prometheus sample names, so a
histogram's mean is name_sum over name_count."""


def _delta(ctx, terms):
    total = 0.0
    for t in terms:
        labels = t.get("labels", {})
        if not ctx.after.has(t["series"]):
            return None
        total += ctx.after.value(t["series"], **labels) - ctx.before.value(t["series"], **labels)
    return total


def read(ctx, num, den, scale=1.0):
    n = _delta(ctx, num)
    d = ctx.window_s if den == "window_s" else _delta(ctx, den)
    if n is None or d is None or d <= 0:
        return None
    return scale * n / d
