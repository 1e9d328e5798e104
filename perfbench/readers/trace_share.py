"""Share of the traced window in which programs matching a pattern ran on
the device (runs cut by the window's edge count for their part inside)."""

from readers import trace_common


def read(ctx, module):
    if ctx.trace is None:
        return None
    return 100.0 * trace_common.module_clipped(ctx.trace, module) / ctx.trace["window_s"]
