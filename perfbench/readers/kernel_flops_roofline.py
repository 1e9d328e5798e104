"""A prefill attention kernel's share of its compute roofline: the causal
attention FLOPs of its calls (perfbench/peaks.py, from the shape the trace
gives each call: [batch, heads, tokens, head_dim], the whole padded call)
over the peak FLOP/s, divided by the summed device time of those calls.
Compute-bound: tokens/2 multiply-adds per byte of keys read."""

import re

import peaks


def read(ctx, kernel, shape=r"\[(\d+),(\d+),(\d+),(\d+)\]", rehearsal_kernel=None):
    if ctx.trace is None:
        return None
    rx = re.compile(rehearsal_kernel if ctx.rehearsal and rehearsal_kernel else kernel)
    shape_rx = re.compile(shape)
    flops = seconds = 0.0
    for name, (sec, calls) in ctx.trace["ops_s"].items():
        if not rx.search(name):
            continue
        m = shape_rx.search(name)
        if not m or calls <= 0:
            continue
        b, h, s, d = (int(g) for g in m.groups())
        one = dict(num_hidden_layers=1, num_attention_heads=h, hidden_size=h * d, head_dim=d)
        flops += calls * b * peaks.causal_attention_flops(one, s)
        seconds += sec
    if seconds <= 0:
        return None
    return 100.0 * (flops / ctx.peaks["bf16_flops"]) / seconds
