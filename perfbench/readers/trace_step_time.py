"""Mean device milliseconds of one step of a program: the seconds of its
whole runs inside the traced window over runs times steps per run."""

from readers import trace_common


def read(ctx, module, steps_per_run=1):
    if ctx.trace is None:
        return None
    s, n = trace_common.module_runs(ctx.trace, module)
    steps = n * (ctx.serving.get(steps_per_run, 1) if isinstance(steps_per_run, str) else steps_per_run)
    if steps <= 0:
        return None
    return 1000.0 * s / steps
