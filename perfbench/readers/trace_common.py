"""Helpers the trace readers share."""

import re


def module_runs(trace, pattern):
    """(seconds, runs) of the whole runs, inside the traced window, of the
    programs whose name matches *pattern*."""
    rx = re.compile(pattern)
    s = n = 0.0
    for name, (_clip, _touch, full_s, full_n) in trace["modules_s"].items():
        if rx.search(name):
            s += full_s
            n += full_n
    return s, n


def module_clipped(trace, pattern):
    rx = re.compile(pattern)
    return sum(v[0] for name, v in trace["modules_s"].items() if rx.search(name))


def ops_in(trace, module_pattern, op_pattern):
    """(seconds, calls) of the operations matching *op_pattern* inside whole
    runs of the programs matching *module_pattern*."""
    mrx, orx = re.compile(module_pattern), re.compile(op_pattern)
    s = n = 0.0
    for mod, ops in trace["ops_in_modules_s"].items():
        if not mrx.search(mod):
            continue
        for op, (sec, cnt) in ops.items():
            if orx.search(op):
                s += sec
                n += cnt
    return s, n


def live_kv_tokens(ctx, samples=40):
    """Mean over the profile call of the tokens whose keys and values a
    decode step reads: for every request decoding at that moment, its
    prompt plus what it had generated. From the client's own records."""
    t0, t1 = ctx.trace_t0, ctx.trace_t1
    total = 0.0
    for i in range(samples):
        t = t0 + (i + 0.5) * (t1 - t0) / samples
        for r in ctx.all_records:
            tt = r.token_times
            if not tt or tt[0] > t or (r.done or tt[-1]) < t:
                continue
            lo, hi = 0, len(tt)
            while lo < hi:
                mid = (lo + hi) // 2
                if tt[mid] <= t:
                    lo = mid + 1
                else:
                    hi = mid
            total += r.prompt_tokens + lo
    return total / samples
