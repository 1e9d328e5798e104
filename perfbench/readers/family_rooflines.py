"""Roofline and `mfu` shares of a family that brings its own counts: ONE
reader for every such family, which holds no operator's name. What a share
divides is DATA in `families/<model_type>_counts.py` (by the configuration's
published `model_type`, `readers/family_scopes.py::counts_of`): its table
`ROOFLINES` gives for every `what` the scopes whose time it is (None: the
whole program), the peak it is a share of, the phase, and its work as a sum
of terms `unit -> count(hf, serving)`. This reader measures the UNITS, which
are the engine's and every family's alike, and multiplies:

  a decode step (the work a step, against the scopes' time a step in whole
  runs of the decode program):
    step               1: what a step moves whatever its rows (weights once)
    live_row           live slots a step (kubeai_engine_slot_steps_total's
                       active share of `--max-slots`)
    kv_token           tokens of keys and values the live requests hold
                       (the client's records, `trace_common.live_kv_tokens`)
    experts_hit_share  the share of the (layer, expert) reads a step could
                       make that it made (kubeai_engine_moe_experts_hit_total
                       / ..._expert_reads_possible_total, phase decode)
  prefill (the work of the calls dispatched between the two polls that
  bracket the traced seconds, against the scopes' time in the prefill
  programs scaled from the traced seconds to the polls' interval):
    prompt_token       real tokens of those calls
                       (kubeai_engine_prefill_tokens_total)
    prompt             each prompt whose first token fell between the polls
                       (the client's records: a prompt is put down to the
                       interval its prefill ENDED in); its count takes the
                       prompt's tokens, `count(hf, serving, n)`

So a family comes to these readings by adding its counts file, not a reader
(the four readers `moe_` / `swa_` / `ssm_` / `afm_rooflines` are four copies
of this reading over four families' counts: PERF.md section 7; the three
helpers imported from two of them below move to `trace_common` when a
`benchmark` PR folds them, which may edit accepted files). Times are read by
SCOPE (`family_scopes`), never by a kernel's name, and the counts are of the
PUBLISHED work, so whatever implements it later, a padded layout or a
fallback route, is held to the same count and reads as a lower share.

`what="window_mfu"` is the one reading outside the table: 2 x the
parameters a token is multiplied by (`counts.active_params`) x (prompt +
generated tokens of the window, the engine's counters) + the attention
layers' FLOPs (`counts.attention_flops`, from the client's records), over
the peak bf16 FLOP/s x the window: the share of the whole step's peak that
later claims here are bounded by.

`view="tail"` (a metric's own file says so: perfbench/trace_in_run.json's
list is an accepted file) takes the counters from the TAIL's polls where
the run traced itself after its window (`ctx.tail_view`), so that what a
step moves and the time it takes are of the same seconds; `window_mfu` keeps
the measured window.

None (the harness leaves the metric out) where the family has no counts
module, or the program has no such counter or scope, as a program of
another family or of a checkout from before the family's."""

from readers import family_scopes, trace_common
from readers.moe_rooflines import _window_flops
from readers.swa_rooflines import _around_trace, _delta

HIT = "kubeai_engine_moe_experts_hit_total"
POSSIBLE = "kubeai_engine_moe_expert_reads_possible_total"
SLOT_STEPS = "kubeai_engine_slot_steps_total"
PREFILL_TOKENS = "kubeai_engine_prefill_tokens_total"


def _live_rows_per_step(ctx):
    active, idle = _delta(ctx, SLOT_STEPS, state="active"), _delta(ctx, SLOT_STEPS, state="idle")
    if not active or idle is None:
        return None
    args = ctx.serving["engine_args"]
    return int(args[args.index("--max-slots") + 1]) * active / (active + idle)


def _experts_hit_share(ctx):
    hit, possible = _delta(ctx, HIT, phase="decode"), _delta(ctx, POSSIBLE, phase="decode")
    return hit / possible if hit and possible else None


DECODE_UNITS = {
    "step": lambda ctx: 1.0,
    "live_row": _live_rows_per_step,
    "kv_token": trace_common.live_kv_tokens,
    "experts_hit_share": _experts_hit_share,
}


def _prefill_work(ctx, unit, count, lo, hi):
    """The work of one term over the polls' interval, or None."""
    if unit == "prompt_token":
        if not hi.has(PREFILL_TOKENS):
            return None
        return count(ctx.hf, ctx.serving) * (hi.value(PREFILL_TOKENS) - lo.value(PREFILL_TOKENS))
    if unit == "prompt":
        ended = [r.prompt_tokens for r in ctx.all_records if r.token_times and lo.at <= r.token_times[0] < hi.at]
        return sum(count(ctx.hf, ctx.serving, n) for n in ended) if ended else None
    raise ValueError(f"unknown unit of prefill work {unit!r}")


def read(ctx, what, module="^jit__unknown", view=""):
    counts = family_scopes.counts_of(ctx)
    if counts is None:
        return None
    if what == "window_mfu":
        flops = _window_flops(ctx, counts)
        if flops is None or ctx.window_s <= 0:
            return None
        return 100.0 * flops / (ctx.peaks["bf16_flops"] * ctx.window_s)
    if what not in getattr(counts, "ROOFLINES", {}):
        raise ValueError(f"unknown share {what!r}: {counts.__name__}.ROOFLINES has {sorted(getattr(counts, 'ROOFLINES', {}))}")
    if ctx.trace is None or ctx.rehearsal:
        # The CPU backend's trace counts a program's runs by its operations'
        # events (trace.json: modules_from_ops_stat), so a rehearsal's time a
        # step means nothing, and a share of a peak computed from it may be
        # refused as over 105%.
        return None
    if view == "tail":
        ctx = getattr(ctx, "tail_view", ctx)
    share = counts.ROOFLINES[what]
    if share["scopes"] is None:
        seconds = trace_common.module_runs(ctx.trace, module)[0]
    else:
        got = family_scopes.seconds(ctx, module, "|".join(share["scopes"]))
        seconds = None if got is None else got[0]
    if not seconds or seconds <= 0:
        return None
    if share["phase"] == "prefill":
        lo, hi = _around_trace(ctx)
        if hi.at <= lo.at:
            return None
        seconds *= (hi.at - lo.at) / ctx.trace["window_s"]  # from the traced seconds to the polls' interval
        work = [_prefill_work(ctx, unit, count, lo, hi) for unit, count in share["work"].items()]
    else:
        steps = trace_common.module_runs(ctx.trace, module)[1] * ctx.serving["decode_chunk"]
        if steps <= 0:
            return None
        seconds /= steps
        amounts = {unit: DECODE_UNITS[unit](ctx) for unit in share["work"]}
        work = [None if amounts[unit] is None else amounts[unit] * count(ctx.hf, ctx.serving) for unit, count in share["work"].items()]
    if any(w is None for w in work):
        return None
    return 100.0 * (sum(work) / ctx.peaks[share["peak"]]) / seconds
