"""The decode program's share of its memory roofline: the least bytes a
step moves (perfbench/peaks.py: local weights once + live keys and values
once) over the peak bytes/s, divided by the program's mean device time per
step in the trace. Memory-bound: at these batch sizes the FLOP bound
(2 x params x slots / peak) is several times lower."""

import peaks
from readers import trace_common


def read(ctx, module):
    if ctx.trace is None:
        return None
    s, n = trace_common.module_runs(ctx.trace, module)
    steps = n * ctx.serving["decode_chunk"]
    if steps <= 0:
        return None
    bytes_ = peaks.decode_step_bytes(
        ctx.hf, ctx.serving["weight_dtype_bytes"], ctx.serving["kv_dtype_bytes"],
        trace_common.live_kv_tokens(ctx),
    )
    return 100.0 * (bytes_ / ctx.peaks["hbm_bytes_per_s"]) / (s / steps)
