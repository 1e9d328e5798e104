"""Roofline and `mfu` shares of an expert model whose stack mixes full and
window attention (`model_type: smallthinker`; counts: perfbench/families/
smallthinker_counts.py). Kernel times are read by SCOPE
(`readers/swa_scopes.py`), not by a kernel's name, so they read the same
work whatever implements it. The keys and values a decode step reads
INSIDE each layer's mask come from the program's own counter,
kubeai_engine_attn_pairs_total{kind, phase="decode"} (a pair is one key
of one layer seen by one query: 2 x kv heads x head_dim values), over the
decode steps between the same two scrapes. In a run that traced itself the
three decode shares below are listed under `tail_view` in
perfbench/trace_in_run.json (PR 46), so the scrapes are the TAIL's and the
bytes a step are of the seconds whose time a step divides them: the
measured window's mean over the tail's time read 104.9% once on a mix of
caches from 256 to 24k tokens (ledger, PR 43).

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
                  against the time under both kinds' attention scopes in
                  the prefill programs, scaled from the traced seconds to
                  the polls' interval; compute-bound
    window_mfu    2 x active parameters x (prompt + generated tokens of the
                  window) + 4 x heads x head_dim x every pair of the
                  window, over the peak bf16 FLOP/s x the window: the share
                  of the whole step's peak

None (the harness leaves the metric out) where the program has no such
counter or scope, as a program of another family or from before PR 36."""

import importlib

from readers import swa_scopes, trace_common

PAIRS = "kubeai_engine_attn_pairs_total"
HIT = "kubeai_engine_moe_experts_hit_total"
POSSIBLE = "kubeai_engine_moe_expert_reads_possible_total"
CHUNKS = "kubeai_engine_step_seconds_count"
ROWS = "kubeai_engine_decode_rows_total"


def _delta(ctx, series, **labels):
    if not ctx.after.has(series):
        return None
    return ctx.after.value(series, **labels) - ctx.before.value(series, **labels)


def _around_trace(ctx):
    """The two polls that bracket the profile call: (earlier, later)."""
    polls = [ctx.before, *ctx.polls, ctx.after]
    lo = [p for p in polls if p.at <= ctx.trace_t0] or polls[:1]
    hi = [p for p in polls if p.at >= ctx.trace_t1] or polls[-1:]
    return lo[-1], hi[0]


def _decode_steps(ctx):
    """Decode steps DISPATCHED between the two scrapes. The pairs are
    counted where a chunk is dispatched, and so are the rows of its batch
    (kubeai_engine_decode_rows_total, live and idle: slots x steps a
    chunk). The chunks' own count (kubeai_engine_step_seconds_count) is
    taken where a chunk ENDS, one chunk later: over a tail of 20-40 chunks
    it is off by one against the pairs (window layers read 102% of what
    24 slots can hold: my chip run, PR 46), so it only stands in for a
    program from before the rows were counted (PR 39)."""
    rows = _delta(ctx, ROWS)
    if rows:
        args = ctx.serving["engine_args"]
        return rows / int(args[args.index("--max-slots") + 1])
    chunks = _delta(ctx, CHUNKS, phase="decode_chunk")
    return chunks * ctx.serving["decode_chunk"] if chunks else None


def _kv_bytes_per_step(ctx, counts):
    """{kind: bytes of keys and values inside the masks a decode step}."""
    steps = _decode_steps(ctx)
    if not steps or not ctx.after.has(PAIRS):
        return None
    one = counts.kv_bytes_per_token_layer(ctx.hf, ctx.serving["kv_dtype_bytes"])
    return {kind: _delta(ctx, PAIRS, kind=kind, phase="decode") * one / steps for kind in ("full", "window")}


def _experts_hit_bytes_per_step(ctx, counts):
    hit, possible = _delta(ctx, HIT, phase="decode"), _delta(ctx, POSSIBLE, phase="decode")
    if not hit or not possible:
        return None
    hf = ctx.hf
    per_step = hit / possible * hf["moe_num_primary_experts"] * hf["num_hidden_layers"]
    return per_step * counts.expert_bytes(hf, ctx.serving["weight_dtype_bytes"])


def read(ctx, what, module="^jit__unknown"):
    counts = importlib.import_module("families.smallthinker_counts")
    if not ctx.after.has(PAIRS):
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
        # The CPU backend's trace counts a program's runs by its
        # operations' events (trace.json: modules_from_ops_stat), so a
        # rehearsal's time a step of ONE scope means nothing, and a share
        # of a peak computed from it would be refused as over 105%.
        return None
    if what == "prefill_attn":
        got = swa_scopes.seconds(ctx, module, None, scopes=("attn.full", "attn.window"))
        lo, hi = _around_trace(ctx)
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
