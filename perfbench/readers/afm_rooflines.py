"""Roofline and `mfu` shares of `model_type: afmoe` (counts:
perfbench/families/afmoe_counts.py): `swa_rooflines`' readings, which
import another family's counts and config keys, over this family's. Its
stack gives the two kinds of attention layer, their kernels and the expert
matmuls the scopes the window family gives them, so times are read by SCOPE
through `swa_scopes`, never by a kernel's name; the keys and values a decode
step reads INSIDE each layer's mask come from the program's own counter,
kubeai_engine_attn_pairs_total{kind, phase="decode"}.

    experts       bytes of the experts HIT a decode step (counters
                  kubeai_engine_moe_experts_hit_total / ..._possible_total)
                  over the peak bytes/s, against the time a step under
                  `moe.experts` in whole runs of the decode program
    full_attn,    bytes of the keys and values inside the masks of the
    window_attn   full / the window layers a decode step, against the time
                  a step under `attn.full` / `attn.window` -> `attn.kernel`
    decode_step   weights outside the routed experts once + experts hit +
                  keys and values inside every layer's mask, against the
                  decode program's time a step
    prefill_attn  4 x heads x head_dim FLOPs a (query, key) pair of the
                  prefill calls dispatched between the two polls that
                  bracket the traced seconds, over the peak bf16 FLOP/s,
                  against the time under both kinds' attention scopes
                  (projections, norms and gate included: it reads LOW) in
                  the prefill programs, scaled from the traced seconds to
                  the polls' interval; compute-bound
    window_mfu    2 x active parameters x (prompt + generated tokens of the
                  window) + 4 x heads x head_dim x every pair of the
                  window, over the peak bf16 FLOP/s x the window: the share
                  of the whole step's peak that later claims here are
                  bounded by

None (the harness leaves the metric out) where the program has no such
counter or scope, as a program of another family or from before PR 42."""

import importlib

from readers import swa_scopes, trace_common
from readers.swa_rooflines import HIT, PAIRS, POSSIBLE, _around_trace, _delta, _kv_bytes_per_step


def _experts_hit_bytes_per_step(ctx, counts):
    hit, possible = _delta(ctx, HIT, phase="decode"), _delta(ctx, POSSIBLE, phase="decode")
    if not hit or not possible:
        return None
    per_step = hit / possible * ctx.hf["num_experts"] * counts.layer_counts(ctx.hf)[1]
    return per_step * counts.expert_bytes(ctx.hf, ctx.serving["weight_dtype_bytes"])


def read(ctx, what, module="^jit__unknown"):
    counts = importlib.import_module("families.afmoe_counts")
    if ctx.hf.get("model_type") != "afmoe" or not ctx.after.has(PAIRS):
        return None
    if what == "window_mfu":
        prompt = _delta(ctx, "kubeai_engine_prefill_tokens_total")
        generated = _delta(ctx, "kubeai_engine_generated_tokens_total")
        if prompt is None or generated is None or ctx.window_s <= 0:
            return None
        pairs = sum(_delta(ctx, PAIRS, kind=k, phase=p) for k in ("full", "window") for p in ("prefill", "decode"))
        flops = 2.0 * counts.active_params(ctx.hf) * (prompt + generated) + counts.attention_flops_per_pair(ctx.hf) * pairs
        return 100.0 * flops / (ctx.peaks["bf16_flops"] * ctx.window_s)
    if ctx.trace is None:
        return None
    if ctx.rehearsal and what != "decode_step":
        # The CPU backend's trace counts a program's runs by its operations'
        # events (trace.json: modules_from_ops_stat): a rehearsal's time a
        # step of ONE scope means nothing (swa_rooflines says the same).
        return None
    if what == "prefill_attn":
        got = swa_scopes.seconds(ctx, module, None, scopes=("attn.full", "attn.window"))
        # The tail's own polls where the run traced itself after its window.
        lo, hi = _around_trace(getattr(ctx, "tail_view", ctx))
        if got is None or got[0] <= 0 or hi.at <= lo.at:
            return None
        pairs = sum(
            hi.value(PAIRS, kind=k, phase="prefill") - lo.value(PAIRS, kind=k, phase="prefill") for k in ("full", "window")
        )
        seconds = got[0] * (hi.at - lo.at) / ctx.trace["window_s"]
        return 100.0 * (counts.attention_flops_per_pair(ctx.hf) * pairs / ctx.peaks["bf16_flops"]) / seconds
    sec, runs = trace_common.module_runs(ctx.trace, module)
    steps = runs * ctx.serving["decode_chunk"]
    kv = _kv_bytes_per_step(ctx, counts)
    if steps <= 0 or kv is None:
        return None
    bw = ctx.peaks["hbm_bytes_per_s"]
    if what in ("full_attn", "window_attn"):
        kind = what.split("_")[0]
        got = swa_scopes.seconds(ctx, module, kind, kernel=True)
        return None if got is None or got[0] <= 0 else 100.0 * (kv[kind] / bw) / (got[0] / steps)
    experts = _experts_hit_bytes_per_step(ctx, counts)
    if experts is None:
        return None
    if what == "experts":
        got = swa_scopes.seconds(ctx, module, None, scopes=("moe.experts",))
        return None if got is None or got[0] <= 0 else 100.0 * (experts / bw) / (got[0] / steps)
    if what == "decode_step":
        outside = counts.weights_outside_experts_bytes(ctx.hf, ctx.serving["weight_dtype_bytes"])
        return 100.0 * ((outside + experts + kv["full"] + kv["window"]) / bw) / (sec / steps)
    raise ValueError(f"unknown share {what!r}")
