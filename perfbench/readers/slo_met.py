"""Share of the window's requests whose time to first token (from when
they were due) and mean time per output token both met the limits that
placed the knee. A failed request misses."""

import loadgen


def read(ctx, ttft_ms, tpot_ms):
    if not ctx.records:
        return None
    times = (loadgen.ttft_tpot_ms(r) for r in ctx.records)
    met = sum(1 for t in times if t is not None and t[0] <= ttft_ms and t[1] <= tpot_ms)
    return 100.0 * met / len(ctx.records)
