"""Roofline and `mfu` shares of an expert model with latent attention
(`model_type: deepseek_v3`; counts: perfbench/families/deepseek_v3_counts.py).
All memory-bound but `window_mfu`; kernel times are read by SCOPE
(`readers/moe_scopes.py`), not by a kernel's name, so they read the same
work whatever implements it.

    experts      bytes of the experts HIT a decode step (the program's
                 counters kubeai_engine_moe_experts_hit_total /
                 ..._expert_reads_possible_total, phase="decode", over the
                 window) over the peak bytes/s, against the device time a
                 step of the operations under `moe.experts` in whole runs
                 of the decode program
    mla          live latent bytes a step (the client's records; 576 values
                 a token a layer as the mathematics has it) against the
                 time a step under `attn.kernel`
    decode_step  weights outside the routed experts once + experts hit +
                 live latents, against the decode program's time a step
    window_mfu   2 x active parameters x (prompt + generated tokens of the
                 window, the engine's counters) + attention FLOPs in the
                 expanded form (from the client's records), over the peak
                 bf16 FLOP/s x the window: the share of the whole step's
                 peak; a few percent where decode is memory-bound

None (the harness leaves the metric out) where the program has no such
counter or scope, as a program from before PR 33 has not."""

import importlib

from readers import moe_scopes, trace_common

HIT = "kubeai_engine_moe_experts_hit_total"
POSSIBLE = "kubeai_engine_moe_expert_reads_possible_total"


def _delta(ctx, series, **labels):
    if not ctx.after.has(series):
        return None
    return ctx.after.value(series, **labels) - ctx.before.value(series, **labels)


def _experts_hit_bytes_per_step(ctx, counts):
    hit, possible = _delta(ctx, HIT, phase="decode"), _delta(ctx, POSSIBLE, phase="decode")
    if not hit or not possible:
        return None
    hf = ctx.hf
    layers = hf["num_hidden_layers"] - min(hf["first_k_dense_replace"], hf["num_hidden_layers"])
    per_step = hit / possible * hf["n_routed_experts"] * layers
    return per_step * counts.expert_bytes(hf, ctx.serving["weight_dtype_bytes"])


def _window_flops(ctx, counts):
    prompt = _delta(ctx, "kubeai_engine_prefill_tokens_total")
    generated = _delta(ctx, "kubeai_engine_generated_tokens_total")
    if prompt is None or generated is None:
        return None
    t0, t1 = ctx.before.at, ctx.after.at
    attn = 0.0
    for r in ctx.all_records:
        tt = r.token_times
        if not tt:
            continue
        if t0 <= tt[0] < t1:  # its prefill ended inside the window
            attn += counts.attention_flops(ctx.hf, r.prompt_tokens)
        inside = [i for i, t in enumerate(tt) if i and t0 <= t < t1]
        if inside:  # token i was computed behind prompt + i cached ones
            attn += counts.attention_flops(ctx.hf, 1, r.prompt_tokens + sum(inside) / len(inside)) * len(inside)
    return 2.0 * counts.active_params(ctx.hf) * (prompt + generated) + attn


def read(ctx, what, module="^jit__unknown"):
    counts = importlib.import_module("families.deepseek_v3_counts")
    if what == "window_mfu":
        flops = _window_flops(ctx, counts)
        if flops is None or ctx.window_s <= 0:
            return None
        return 100.0 * flops / (ctx.peaks["bf16_flops"] * ctx.window_s)
    if ctx.trace is None:
        return None
    if ctx.rehearsal and what in ("mla", "experts"):
        # The CPU backend's trace counts a program's runs by its
        # operations' events (trace.json: modules_from_ops_stat), so a
        # rehearsal's time a step of ONE scope means nothing, and a share
        # of a peak computed from it would be refused as over 105%.
        return None
    sec, runs = trace_common.module_runs(ctx.trace, module)
    steps = runs * ctx.serving["decode_chunk"]
    if steps <= 0:
        return None
    bw = ctx.peaks["hbm_bytes_per_s"]
    latents = trace_common.live_kv_tokens(ctx) * counts.latent_bytes_per_token(ctx.hf, ctx.serving["kv_dtype_bytes"])
    experts = _experts_hit_bytes_per_step(ctx, counts)
    if what == "mla":
        got = moe_scopes.seconds(ctx, module, "attn.kernel")
        return None if got is None or got[0] <= 0 else 100.0 * (latents / bw) / (got[0] / steps)
    if experts is None:
        return None
    if what == "experts":
        got = moe_scopes.seconds(ctx, module, "moe.experts")
        return None if got is None or got[0] <= 0 else 100.0 * (experts / bw) / (got[0] / steps)
    if what == "decode_step":
        outside = counts.weights_outside_experts_bytes(ctx.hf, ctx.serving["weight_dtype_bytes"])
        return 100.0 * ((outside + experts + latents) / bw) / (sec / steps)
    raise ValueError(f"unknown share {what!r}")
