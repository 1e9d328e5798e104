"""`model_type: afmoe` (AfmoeForCausalLM: Arcee Trinity-Mini): periods of
2048-window layers with rope and one full-attention layer without, every
attention gated and its queries and keys normed, a norm after each
sub-block as well as before it, two leading dense layers inside the first
period, then sigmoid-routed experts beside a shared one. The program runs it
through `kubeai_tpu/models/afmoe.py`; the plain reference is
`afmoe_reference.py`, beside this file.

Scales: a linear draws with its fan-in's `in**-0.5` (the router and the
attention's gate too), embeddings and head 0.02, the four norms of a layer
and the final norm ones. Two scales are set so that every seed is about THE
SAME AMOUNT OF WORK (PERF.md section 6, PR 42: with unit q/k norms and a
bias of a tenth, the experts a decode step read went from 27% to 34% of
them by the weights' seed alone, and the step's time and the cell's
tokens/s with it):

- `q_norm` and `k_norm` draw with deviation QK_NORM_SCALE = 1.41, so
  attention logits have a deviation of about 2 and a query weighs some
  tens of keys of its 2048. With both at one the deviation is 1, every
  query averages its whole context, the average is the same vector for
  every position of every sequence (one alphabet), and after the
  sub-block's post-norm that vector is most of the stream the routers
  read: all rows then choose nearly the same experts, which ones and how
  nearly being the seed's draw. A deviation of 2 (logits of 4, a few keys
  a query) evens the seeds out further and was tried on the chip: the
  logits check then reads past its limits (max 0.29, mean 0.049), because
  attention that averages thousands of keys also averages their bf16
  rounding away and attention that picks keys passes it on. The limits
  stay; 1.41 is what they leave room for.
- `expert_bias` draws with deviation EXPERT_BIAS_SCALE = 0.02: a tenth of a
  score's deviation over tokens (sigmoids of unit-variance logits: 0.21),
  enough that what is chosen and what it weighs differ in near-ties, too
  little to make some experts every token's. The published bias is TRAINED
  to even the experts' load out; a random one of the scores' own size did
  the opposite.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PROGRAM = os.path.join(ROOT, "kubeai_tpu", "models", "afmoe.py")
QK_NORM_SCALE = 1.41
EXPERT_BIAS_SCALE = 0.02


def _program_is_here() -> None:
    """A checkout from before PR 42 under these benchmark files says so in
    the run's first phase, not after a 12 GB checkpoint and a dead pod."""
    if not os.path.exists(PROGRAM):
        raise SystemExit(f"{os.path.relpath(PROGRAM, ROOT)} is not in this checkout: the program cannot run model_type afmoe")


def layer_plan(hf: dict, i: int) -> list[tuple]:
    _program_is_here()
    D, H, Kv, d = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    p = f"model.layers.{i}."
    lin = lambda name, out, inp: (p + name + ".weight", (out, inp), inp**-0.5)  # noqa: E731
    ones = lambda name, n: (p + name + ".weight", (n,), None)  # noqa: E731
    plan = [
        ones("input_layernorm", D), ones("post_attention_layernorm", D),
        ones("pre_mlp_layernorm", D), ones("post_mlp_layernorm", D),
        lin("self_attn.q_proj", H * d, D), lin("self_attn.k_proj", Kv * d, D), lin("self_attn.v_proj", Kv * d, D),
        lin("self_attn.o_proj", D, H * d), lin("self_attn.gate_proj", H * d, D),
        (p + "self_attn.q_norm.weight", (d,), QK_NORM_SCALE), (p + "self_attn.k_norm.weight", (d,), QK_NORM_SCALE),
    ]
    if i < hf["num_dense_layers"]:
        F = hf["intermediate_size"]
        return plan + [lin("mlp.gate_proj", F, D), lin("mlp.up_proj", F, D), lin("mlp.down_proj", D, F)]
    E, F = hf["num_experts"], hf["moe_intermediate_size"]
    Fs = F * hf["num_shared_experts"]
    plan += [
        lin("mlp.router.gate", E, D), (p + "mlp.expert_bias", (E,), EXPERT_BIAS_SCALE),
        lin("mlp.shared_experts.gate_proj", Fs, D), lin("mlp.shared_experts.up_proj", Fs, D),
        lin("mlp.shared_experts.down_proj", D, Fs),
    ]
    for j in range(E):
        e = f"mlp.experts.{j}."
        plan += [lin(e + "gate_proj", F, D), lin(e + "up_proj", F, D), lin(e + "down_proj", D, F)]
    return plan


def outside_plan(hf: dict) -> list[tuple]:
    _program_is_here()
    D, V = hf["hidden_size"], hf["vocab_size"]
    return [
        ("model.embed_tokens.weight", (V, D), 0.02),
        ("model.norm.weight", (D,), None),
        ("lm_head.weight", (V, D), 0.02),
    ]


# The two-part comparison that decides `correct` (c), at the published
# widths on the checkpoint cut to `logits_check_layers` (4: the first
# period, which holds every kind of layer: dense + window twice, experts +
# window, experts + full). Program: bf16, the kernel routes, the TIMED
# path: a prompt of 6400 tokens in seven chunk calls (six of 1024 and one
# of 256: past the 2048 window from the third on, the full layer behind 5k
# and 6k cached keys at the end) through BOTH paged pools with the host's
# own manager (`engine/paging.py::WindowPages`) handing window pages back
# as the prompt advances, in a window pool that holds ONE slot's cap, then
# 4 decode steps; and short cold prompts through the flash route.
# Reference: float32 at the highest matmul precision, the whole sequence at
# once, a tensor at a time (the float32 copy of the cut is 10.5 GB: it
# never lies on the chip whole, and the program's 5.3 GB are freed first).
#
# Part 1, logits, with the reference ROUTED BY THE PROGRAM'S OWN CHOICES (a
# flip between a token's 8th and 9th expert swaps an eighth of its routed
# output, which no logits bound survives, and says nothing about the
# arithmetic). What is left is bf16 rounding through 4 layers and up to
# 6400 keys: the other families read max 0.054-0.077 / mean 0.009-0.013
# (dense, 4 layers), 0.059-0.074 / 0.010-0.011 (kanana-2) and the window
# family's (PERF.md, PR 36); this family's readings and its controls' are in
# PERF.md section 6 (PR 42), and the bounds lie between them.
LOGITS_MAX_ABS = 0.25
LOGITS_MEAN_ABS = 0.04
# Part 2, the program's choices against the reference's FREE choices on the
# same inputs: where the two sets differ, what the reference chose from
# (score + bias) must lie within CHOICE_EPS for the experts that changed
# places, in units of that layer's deviation of score + bias over experts
# and tokens. The program's router reads a bf16 stream (8 bits of
# mantissa), so near-ties flip; a router without its bias, or on the wrong
# input, disagrees on experts whole deviations apart.
CHOICE_EPS = 0.2
# ... and the share of (expert layer, token) pairs that may disagree at all.
CHOICE_DISAGREE_SHARE = 0.25

LONG_PROMPT = 6400  # six chunks of 1024 and one of 256: past the 2048 window from the third on
DECODE_STEPS = 4


def logits(path: str, seed: str, serving: dict) -> dict:
    """A prompt of 6400 tokens through chunked prefill and then decode
    steps, and short cold prompts through the flash route, against the
    plain reference, in two parts (see above).
    `serving.logits_control` (a hand run, never a cell's) adds two
    references that have to come out as not correct under the same limits:
    every matmul's inputs rounded through float8_e4m3fn (the nearest
    precision under bf16), and the attention's output gate left out."""
    import gc
    import json
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, ROOT)
    from families import afmoe_reference as reference
    from kubeai_tpu.engine.coldstart import setup_compile_cache
    from kubeai_tpu.engine.core import EngineConfig
    from kubeai_tpu.engine.paging import WindowPages
    from kubeai_tpu.engine.weights import SafetensorsSource, load_engine_from_path
    from kubeai_tpu.models import afmoe

    setup_compile_cache()
    clock = [time.monotonic()]
    seconds = {}

    def lap(name):
        clock.append(time.monotonic())
        seconds[name] = round(clock[-1] - clock[-2], 3)

    with open(os.path.join(path, "config.json")) as f:
        hf = json.load(f)
    window, page = hf["sliding_window"], serving.get("page_size", 64)
    # Lengths in units of the window, so that a rehearsal's tiny window
    # walks the same phases: chunks of half a window, a prompt of 3.125
    # windows (6400 at the published 2048).
    chunk = max(window // 2, page)
    long_prompt = LONG_PROMPT * window // 2048
    max_seq = (long_prompt + DECODE_STEPS + page) // page * page + page
    eng = load_engine_from_path(
        path, EngineConfig(max_slots=1, max_seq_len=max_seq, page_size=page, prefill_buckets=(chunk // 4, chunk)),
        overlap=False, warmup=False,
    )
    params, cfg = eng.params, eng.model_config
    lap("load")
    max_pages = max_seq // page
    rng = np.random.default_rng(int(seed))

    # -- the long prompt: chunked prefill behind cached tokens, then decode
    long_tokens = rng.integers(0, 259, long_prompt + DECODE_STEPS)
    table = np.zeros((1, 2 * max_pages), np.int32)
    table[0, :max_pages] = 1 + np.arange(max_pages)
    wp = WindowPages(table[:, max_pages:], window, chunk, page)  # its pool: one slot's cap and the trash page
    wp.admit(0, [], 0, [], max_pages)
    pools = afmoe.init_paged_cache(cfg, max_pages + 1, page, window_pages=wp.pool.num_pages)
    prefill = jax.jit(
        lambda p, tk, c, tb, start, last: afmoe.prefill_paged(p, cfg, tk, c, tb, start, last, return_choices=True)
    )
    decode = jax.jit(lambda p, tk, c, tb, lengths: afmoe.decode_step_paged(p, cfg, tk, c, tb, lengths, return_choices=True))
    got, at, choices = [], [], []
    held_most = 0
    for start in range(0, long_prompt, chunk):
        n = min(chunk, long_prompt - start)
        bucket = chunk if n == chunk else chunk // 4 * -(-n // (chunk // 4))
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = long_tokens[start : start + n]
        wp.advance(0, start, start + bucket)
        held_most = max(held_most, wp.held(0))
        lg, cache, ch = prefill(
            params, padded, pools, table.copy(), jnp.asarray([start], jnp.int32), jnp.asarray([n - 1], jnp.int32),
        )
        pools = {"kv": cache["kv"], "kv_window": cache["kv_window"]}
        got.append(np.asarray(lg[0, 0]))
        at.append(start + n - 1)
        choices.append(np.asarray(ch)[:, :n])
    for step in range(DECODE_STEPS):
        pos = long_prompt + step
        wp.advance(0, pos, pos + 1)
        lg, cache, ch = decode(params, long_tokens[None, pos : pos + 1], pools, table.copy(), jnp.asarray([pos], jnp.int32))
        pools = {"kv": cache["kv"], "kv_window": cache["kv_window"]}
        got.append(np.asarray(lg[0, 0]))
        at.append(pos)
        choices.append(np.asarray(ch))
    long_got = np.stack(got)
    long_choices = np.concatenate(choices, axis=1)  # [expert layers, long_prompt + DECODE_STEPS, k]
    released = wp.released

    # -- short cold prompts: the flash route (a whole tile) with rows padded past their length
    B, S = 4, chunk // 4
    cold_tokens = rng.integers(0, 259, (B, S))
    cold_lengths = [S, S - S // 5, S, S // 2 + 3]
    cold_table = np.zeros((B, 2 * max_pages), np.int32)
    n_pages = -(-S // page)
    cold_table[:, :n_pages] = 1 + np.arange(B * n_pages).reshape(B, n_pages)
    cold_table[:, max_pages : max_pages + n_pages] = 1 + np.arange(B * n_pages).reshape(B, n_pages)
    cold_pools = afmoe.init_paged_cache(cfg, B * n_pages + 1, page, window_pages=B * n_pages + 1)
    lg, _, ch = jax.jit(
        lambda p, tk, c: afmoe.prefill_paged_cold(p, cfg, tk, c, cold_table, jnp.asarray(cold_lengths, jnp.int32), return_choices=True)
    )(params, cold_tokens, cold_pools)
    cold_got = np.asarray(lg[:, 0])
    cold_choices = np.asarray(ch)  # [expert layers, B*S, k]
    routes = {
        "chunk": afmoe.cached_attention_route(cfg, chunk, False, True),
        "decode": afmoe.cached_attention_route(cfg, 1, False, True),
        "cold": afmoe.cached_attention_route(cfg, S, True, True),
    }
    eng.stop()  # never started: this unbinds the gauges that would keep its arrays alive
    del eng, params, pools, cold_pools, cache, lg  # the reference needs the room
    gc.collect()  # the engine is a cycle of objects: only a collection frees its arrays
    lap("program")

    source = SafetensorsSource(path)
    # Where the cut in float32 fits the device beside a pass's own arrays
    # (a rehearsal's does; the published widths' 10.5 GB do not), every pass
    # after the first reuses the first's tensors.
    plans = outside_plan(hf) + [t for i in range(hf["num_hidden_layers"]) for t in layer_plan(hf, i)]
    need = 4 * sum(int(np.prod(shape)) for _, shape, _ in plans)
    stats = jax.devices()[0].memory_stats() or {}
    upcast = {} if stats.get("bytes_limit", float("inf")) - stats.get("bytes_in_use", 0) > need + (3 << 30) else None

    # Tokens of a cold row past its own length were computed on padding.
    cold_valid = (np.arange(S)[None, :] < np.asarray(cold_lengths)[:, None]).reshape(-1)
    cold_at = np.asarray(cold_lengths)[:, None] - 1

    def compare(got_long, got_cold, ch_long, ch_cold, ref_long, ref_cold) -> dict:
        out = {}
        parts = {
            "prefill_chunked": (got_long[:-DECODE_STEPS], ref_long["logits"][0, :-DECODE_STEPS]),
            "decode": (got_long[-DECODE_STEPS:], ref_long["logits"][0, -DECODE_STEPS:]),
            "prefill_cold": (got_cold, ref_cold["logits"][:, 0]),
        }
        for name, (g, want) in parts.items():
            d = np.abs(g.astype(np.float64) - want.astype(np.float64))
            finite = bool(np.isfinite(g).all() and np.isfinite(want).all())
            out[name] = {
                "finite": finite, "max_abs": float(d.max()), "mean_abs": float(d.mean()),
                "ref_std": float(want.std()), "rows": int(g.shape[0]),
                "ok": finite and float(d.max()) <= LOGITS_MAX_ABS and float(d.mean()) <= LOGITS_MEAN_ABS,
            }
        compared = disagree = 0
        worst = 0.0
        for prog, ref, valid in ((ch_long, ref_long, None), (ch_cold, ref_cold, cold_valid)):
            # Gaps in units of each layer's deviation of what was chosen from.
            sel = ref["select"] / ref["select"].std(axis=(1, 2), keepdims=True)
            ch = reference.choice_disagreements(prog, ref["choices"], sel, valid)
            compared, disagree, worst = compared + ch["compared"], disagree + ch["disagree"], max(worst, ch["worst_gap"])
        share = disagree / max(compared, 1)
        out["router_choices"] = {
            "compared": compared, "disagree": disagree, "worst_gap": worst, "disagree_share": share,
            "ok": worst <= CHOICE_EPS and share <= CHOICE_DISAGREE_SHARE,
        }
        return out

    ref_long = reference.forward(
        source.get, hf, long_tokens[None], forced=long_choices, logits_at=np.asarray(at)[None], upcast=upcast,
    )
    lap("reference_long")
    ref_cold = reference.forward(source.get, hf, cold_tokens, forced=cold_choices, logits_at=cold_at, upcast=upcast)
    lap("reference_cold")
    compared = compare(long_got, cold_got, long_choices, cold_choices, ref_long, ref_cold)
    dev = jax.devices()[0]
    result = {
        "platform": dev.platform, "kind": dev.device_kind,
        "kernel_route": {"flash": cfg.use_flash_prefill, "paged_kernel": cfg.use_paged_kernel, **routes},
        "layers": cfg.num_layers,
        "sample": {
            "long_prompt": long_prompt, "chunk": chunk, "decode_steps": DECODE_STEPS, "window": window,
            "window_pages_held_most": held_most, "window_pages_cap": wp.cap, "window_pages_released": released,
            "cold_rows": B, "cold_bucket": S, "cold_lengths": cold_lengths,
        },
        "tolerance": {
            "max_abs": LOGITS_MAX_ABS, "mean_abs": LOGITS_MEAN_ABS,
            "choice_eps": CHOICE_EPS, "choice_disagree_share": CHOICE_DISAGREE_SHARE,
        },
        "compared": compared,
        "ok": all(c["ok"] for c in compared.values()) and held_most <= wp.cap,
    }
    lap("compare")
    result["seconds_by_part"] = seconds
    if serving.get("logits_control"):
        # The reference itself, faulty, held to the same limits against the
        # sound reference: each has to fail. The float8 one is routed freely
        # (its routing is part of what the precision breaks); the one without
        # the gate by the program's choices, so that the gate is ALL that
        # differs.
        for name, forced, fault in (
            ("control_float8", (None, None), {"dtype": "float8_e4m3fn"}),
            ("control_no_gate", (long_choices, cold_choices), {"variant": "no_gate"}),
        ):
            bad_long = reference.forward(
                source.get, hf, long_tokens[None], forced=forced[0], logits_at=np.asarray(at)[None], upcast=upcast, **fault
            )
            bad_cold = reference.forward(
                source.get, hf, cold_tokens, forced=forced[1], logits_at=cold_at, upcast=upcast, **fault
            )
            control = compare(
                bad_long["logits"][0], bad_cold["logits"][:, 0], bad_long["choices"], bad_cold["choices"], ref_long, ref_cold,
            )
            result[name] = {**control, "ok": all(c["ok"] for c in control.values())}
    return result
