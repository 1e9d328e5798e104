"""A decode-path kernel's share of its memory roofline: the live keys and
values it must read per step (from the client's records, bytes per token
from perfbench/peaks.py) over the peak bytes/s, divided by the kernel's
summed device time per decode step (its calls inside whole runs of the
decode program; one call per layer per step). Memory-bound: one query row
per slot, so 2 FLOPs per byte read."""

import peaks
from readers import trace_common


def read(ctx, module, kernel, rehearsal_kernel=None):
    if ctx.trace is None:
        return None
    if ctx.rehearsal and rehearsal_kernel:
        kernel = rehearsal_kernel
    _, runs = trace_common.module_runs(ctx.trace, module)
    ksec, calls = trace_common.ops_in(ctx.trace, module, kernel)
    steps = runs * ctx.serving["decode_chunk"]
    if steps <= 0 or calls <= 0 or ksec <= 0:
        return None
    bytes_ = trace_common.live_kv_tokens(ctx) * peaks.kv_bytes_per_token(ctx.hf, ctx.serving["kv_dtype_bytes"])
    return 100.0 * (bytes_ / ctx.peaks["hbm_bytes_per_s"]) / (ksec / steps)
