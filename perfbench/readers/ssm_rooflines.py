"""Roofline and `mfu` shares of a stack of Mamba-2 mixers, attention and
latent-space experts of which the chip holds a share (`model_type:
nemotron_h`; counts: perfbench/families/nemotron_h_counts.py). Times are
read by SCOPE (`readers/ssm_scopes.py`), never by a kernel's name, so
whatever implements the work later is held to the same count.

    ssm_decode    memory-bound: the LIVE rows' state and convolution tail,
                  read and written, in every `M` block a decode step (live
                  rows a step from kubeai_engine_slot_steps_total over the
                  window), over the peak bytes/s, against the time a step
                  under `ssm.conv` + `ssm.scan` in whole runs of the decode
                  program
    ssm_prefill   compute-bound: the chunked form's FLOPs for the real
                  tokens of the prefill calls dispatched between the two
                  polls that bracket the traced seconds
                  (kubeai_engine_prefill_tokens_total), over the peak bf16
                  FLOP/s, against the time under `ssm.scan` in the prefill
                  programs, scaled from the traced seconds to the polls'
                  interval
    experts       bytes of the HELD experts hit a decode step (counters
                  kubeai_engine_moe_experts_hit_total / ..._possible_total)
                  against the time a step under `moe.experts`
    decode_step   weights outside the routed experts once + experts hit +
                  live rows' state in and out + live keys and values read
                  (the client's records), against the decode program's time
                  a step
    window_mfu    2 x the parameters a token is multiplied by on this chip
                  x (prompt + generated tokens of the window) + the
                  recurrence's and the attention's FLOPs, over the peak
                  bf16 FLOP/s x the window: the share of the whole step's
                  peak that later claims here are bounded by

None (the harness leaves the metric out) where the program has no such
counter or scope, as a program of another family or from before PR 40."""

import importlib

from readers import moe_rooflines, ssm_scopes, trace_common
from readers.swa_rooflines import _around_trace, _delta

HIT = "kubeai_engine_moe_experts_hit_total"
POSSIBLE = "kubeai_engine_moe_expert_reads_possible_total"
SLOT_STEPS = "kubeai_engine_slot_steps_total"
STATE_SLOTS = "kubeai_engine_state_slots_total"
PREFILL_TOKENS = "kubeai_engine_prefill_tokens_total"


def _live_rows_per_step(ctx):
    active, idle = _delta(ctx, SLOT_STEPS, state="active"), _delta(ctx, SLOT_STEPS, state="idle")
    slots = ctx.after.value(STATE_SLOTS)
    if not active or idle is None or not slots:
        return None
    return slots * active / (active + idle)


def _experts_hit_bytes_per_step(ctx, counts):
    hit, possible = _delta(ctx, HIT, phase="decode"), _delta(ctx, POSSIBLE, phase="decode")
    if not hit or not possible:
        return None
    per_step = hit / possible * ctx.hf["n_routed_experts"] * counts.kinds(ctx.hf)["E"]
    return per_step * counts.expert_bytes(ctx.hf, ctx.serving["weight_dtype_bytes"])


def _window_flops(ctx, counts):
    """`moe_rooflines`' count (2 x active parameters a token + attention
    from the client's records) and the recurrence's FLOPs a token."""
    dense = moe_rooflines._window_flops(ctx, counts)
    if dense is None:
        return None
    tokens = _delta(ctx, PREFILL_TOKENS) + _delta(ctx, "kubeai_engine_generated_tokens_total")
    return dense + counts.kinds(ctx.hf)["M"] * counts.ssm_recurrence_flops_per_token(ctx.hf) * tokens


def read(ctx, what, module="^jit__unknown"):
    counts = importlib.import_module("families.nemotron_h_counts")
    if ctx.hf.get("model_type") != "nemotron_h" or not ctx.after.has(STATE_SLOTS):
        return None
    if what == "window_mfu":
        flops = _window_flops(ctx, counts)
        if flops is None or ctx.window_s <= 0:
            return None
        return 100.0 * flops / (ctx.peaks["bf16_flops"] * ctx.window_s)
    if ctx.trace is None:
        return None
    if ctx.rehearsal:
        # The CPU backend's trace counts a program's runs by its
        # operations' events (trace.json: modules_from_ops_stat), so a
        # rehearsal's time a step means nothing, and a share of a peak
        # computed from it may be refused as over 105% (read: 103.9%).
        return None
    if what == "ssm_prefill":
        got = ssm_scopes.seconds(ctx, module, "ssm.scan")
        # The tail's own polls where the run traced itself after its window.
        lo, hi = _around_trace(getattr(ctx, "tail_view", ctx))
        if got is None or got[0] <= 0 or hi.at <= lo.at or not hi.has(PREFILL_TOKENS):
            return None
        tokens = hi.value(PREFILL_TOKENS) - lo.value(PREFILL_TOKENS)
        flops = counts.kinds(ctx.hf)["M"] * counts.ssm_chunked_flops_per_token(ctx.hf) * tokens
        seconds = got[0] * (hi.at - lo.at) / ctx.trace["window_s"]
        return 100.0 * (flops / ctx.peaks["bf16_flops"]) / seconds
    sec, runs = trace_common.module_runs(ctx.trace, module)
    steps = runs * ctx.serving["decode_chunk"]
    live = _live_rows_per_step(ctx)
    if steps <= 0 or live is None:
        return None
    bw = ctx.peaks["hbm_bytes_per_s"]
    state = live * counts.ssm_decode_bytes_per_row(ctx.hf, ctx.serving["weight_dtype_bytes"])
    if what == "ssm_decode":
        got = ssm_scopes.seconds(ctx, module, "ssm.conv|ssm.scan")
        return None if got is None or got[0] <= 0 else 100.0 * (state / bw) / (got[0] / steps)
    experts = _experts_hit_bytes_per_step(ctx, counts)
    if experts is None:
        return None
    if what == "experts":
        got = ssm_scopes.seconds(ctx, module, "moe.experts")
        return None if got is None or got[0] <= 0 else 100.0 * (experts / bw) / (got[0] / steps)
    if what == "decode_step":
        outside = counts.weights_outside_experts_bytes(ctx.hf, ctx.serving["weight_dtype_bytes"])
        kv = trace_common.live_kv_tokens(ctx) * counts.kv_bytes_per_token(ctx.hf, ctx.serving["kv_dtype_bytes"])
        return 100.0 * ((outside + experts + state + kv) / bw) / (sec / steps)
    raise ValueError(f"unknown share {what!r}")
