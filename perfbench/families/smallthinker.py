"""`model_type: smallthinker` (SmallThinkerForCausalLM: SmallThinker-21BA3B):
periods of one full-attention layer without rope and window layers with
rope, grouped-query attention, every layer with softmax-routed ReGLU
experts chosen from the layer's input. The program runs it through
`kubeai_tpu/models/smallthinker.py`; the plain reference is
`smallthinker_reference.py`, beside this file.

Scales: a linear draws with its fan-in's `in**-0.5` (the router too),
embeddings and head 0.02, norms ones.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def layer_plan(hf: dict, i: int) -> list[tuple]:
    D, H, Kv, d = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    E, F = hf["moe_num_primary_experts"], hf["moe_ffn_hidden_size"]
    p = f"model.layers.{i}."
    lin = lambda name, out, inp: (p + name + ".weight", (out, inp), inp**-0.5)  # noqa: E731
    plan = [
        (p + "input_layernorm.weight", (D,), None),
        (p + "post_attention_layernorm.weight", (D,), None),
        lin("self_attn.q_proj", H * d, D), lin("self_attn.k_proj", Kv * d, D),
        lin("self_attn.v_proj", Kv * d, D), lin("self_attn.o_proj", D, H * d),
        lin("block_sparse_moe.primary_router", E, D),
    ]
    for j in range(E):
        e = f"block_sparse_moe.experts.{j}."
        plan += [lin(e + "gate", F, D), lin(e + "up", F, D), lin(e + "down", D, F)]
    return plan


def outside_plan(hf: dict) -> list[tuple]:
    D, V = hf["hidden_size"], hf["vocab_size"]
    return [
        ("model.embed_tokens.weight", (V, D), 0.02),
        ("model.norm.weight", (D,), None),
        ("lm_head.weight", (V, D), 0.02),
    ]


# The two-part comparison that decides `correct` (c), at the published
# widths on the checkpoint cut to `logits_check_layers` (one period: the
# full layer without rope and three window layers). Program: bf16, the
# kernel routes, through BOTH paged pools with the host's own manager
# (`engine/paging.py::WindowPages`) handing window pages back as the prompt
# advances, in a window pool that holds ONE slot's cap: a page taken back
# too early is another chunk's page by the time it is read. Reference:
# float32 at the highest matmul precision, the whole sequence at once.
#
# Part 1, logits, with the reference ROUTED BY THE PROGRAM'S OWN CHOICES (a
# flip between a token's 6th and 7th expert swaps a sixth of its routed
# output, which no logits bound survives, and says nothing about the
# arithmetic). What is left is bf16 rounding through 4 layers and up to
# 6400 keys: the dense decoder reads max 0.054-0.077 / mean 0.009-0.013 at
# 4 layers and kanana-2 0.059-0.074 / 0.010-0.011 (PERF.md, PR 27 and PR
# 33); this family's readings and the controls' are in PERF.md section 6
# (PR 36), and the bounds lie between them.
LOGITS_MAX_ABS = 0.25
LOGITS_MEAN_ABS = 0.04
# Part 2, the program's choices against the reference's FREE choices on the
# same inputs: where the two sets differ, the reference's own router logits
# of the experts that changed places must lie within CHOICE_EPS of each
# other, in units of that layer's router-logit standard deviation (the
# router reads the residual stream itself, whose size grows with depth: 0.02
# at layer 0). The program's router reads a bf16 stream (8 bits of
# mantissa), so near-ties flip; a router on the wrong input (the normed
# post-attention stream) disagrees on experts whole deviations apart.
CHOICE_EPS = 0.2
# ... and the share of (layer, token) pairs that may disagree at all.
CHOICE_DISAGREE_SHARE = 0.25

LONG_PROMPT = 6400  # six chunks of 1024 and one of 256: past the 4096 window from the fifth on
DECODE_STEPS = 4


def logits(path: str, seed: str, serving: dict) -> dict:
    """A prompt of 6400 tokens through chunked prefill and then decode
    steps, and short cold prompts through the flash route, against the
    plain reference, in two parts (see above).
    `serving.logits_control` (a hand run, never a cell's) adds two
    references that have to come out as not correct under the same limits:
    every matmul's inputs rounded through float8_e4m3fn, and the window
    ignored."""
    import gc
    import json
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, ROOT)
    from families import smallthinker_reference as reference
    from kubeai_tpu.engine.coldstart import setup_compile_cache
    from kubeai_tpu.engine.core import EngineConfig
    from kubeai_tpu.engine.paging import WindowPages
    from kubeai_tpu.engine.weights import SafetensorsSource, load_engine_from_path
    from kubeai_tpu.models import smallthinker

    setup_compile_cache()
    clock = [time.monotonic()]
    seconds = {}

    def lap(name):
        clock.append(time.monotonic())
        seconds[name] = round(clock[-1] - clock[-2], 3)

    with open(os.path.join(path, "config.json")) as f:
        hf = json.load(f)
    window, page = hf["sliding_window_size"], serving.get("page_size", 64)
    # Lengths in units of the window, so that a rehearsal's tiny window
    # walks the same phases: chunks of a quarter window, a prompt of
    # 6.25 windows (6400 at the published 4096).
    chunk = max(window // 4, page)
    long_prompt = LONG_PROMPT * window // 4096
    max_seq = (long_prompt + DECODE_STEPS + page) // page * page + page
    eng = load_engine_from_path(
        path, EngineConfig(max_slots=1, max_seq_len=max_seq, page_size=page, prefill_buckets=(chunk // 4, chunk)),
        overlap=False, warmup=False,
    )
    params, cfg = eng.params, eng.model_config
    lap("load")
    max_pages = max_seq // page
    rng = np.random.default_rng(int(seed))
    L, k = cfg.num_layers, cfg.num_experts_per_tok

    # -- the long prompt: chunked prefill behind cached tokens, then decode
    long_tokens = rng.integers(0, 259, long_prompt + DECODE_STEPS)
    table = np.zeros((1, 2 * max_pages), np.int32)
    table[0, :max_pages] = 1 + np.arange(max_pages)
    wp = WindowPages(table[:, max_pages:], window, chunk, page)  # its pool: one slot's cap and the trash page
    wp.admit(0, [], 0, [], max_pages)
    pools = smallthinker.init_paged_cache(cfg, max_pages + 1, page, window_pages=wp.pool.num_pages)
    prefill = jax.jit(
        lambda p, tk, c, tb, start, last: smallthinker.prefill_paged(p, cfg, tk, c, tb, start, last, return_choices=True)
    )
    decode = jax.jit(
        lambda p, tk, c, tb, lengths: smallthinker.decode_step_paged(p, cfg, tk, c, tb, lengths, return_choices=True)
    )
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
    long_choices = np.concatenate(choices, axis=1)  # [L, long_prompt + DECODE_STEPS, k]
    released = wp.released

    # -- short cold prompts: the flash route (a whole tile) and the paged one
    B, S = 4, chunk // 4
    cold_tokens = rng.integers(0, 259, (B, S))
    cold_lengths = [S, S - S // 5, S, S // 2 + 3]
    cold_table = np.zeros((B, 2 * max_pages), np.int32)
    n_pages = -(-S // page)
    cold_table[:, :n_pages] = 1 + np.arange(B * n_pages).reshape(B, n_pages)
    cold_table[:, max_pages : max_pages + n_pages] = 1 + np.arange(B * n_pages).reshape(B, n_pages)
    cold_pools = smallthinker.init_paged_cache(cfg, B * n_pages + 1, page, window_pages=B * n_pages + 1)
    lg, _, ch = jax.jit(
        lambda p, tk, c: smallthinker.prefill_paged_cold(
            p, cfg, tk, c, cold_table, jnp.asarray(cold_lengths, jnp.int32), return_choices=True
        )
    )(params, cold_tokens, cold_pools)
    cold_got = np.asarray(lg[:, 0])
    cold_choices = np.asarray(ch)  # [L, B*S, k]
    routes = {
        "chunk": smallthinker.cached_attention_route(cfg, chunk, False, True),
        "decode": smallthinker.cached_attention_route(cfg, 1, False, True),
        "cold": smallthinker.cached_attention_route(cfg, S, True, True),
    }
    eng.stop()  # never started: this unbinds the gauges that would keep its arrays alive
    del eng, params, pools, cold_pools, cache, lg  # the reference needs the room
    gc.collect()  # the engine is a cycle of objects: only a collection frees its arrays
    lap("program")

    source = SafetensorsSource(path)
    # The 4-layer cut in float32 is 9.5 GB: where that fits the device
    # beside a pass's own arrays (the program's are gone), every pass after
    # the first reuses the first's tensors.
    plans = outside_plan(hf) + [t for i in range(hf["num_hidden_layers"]) for t in layer_plan(hf, i)]
    need = 4 * sum(int(np.prod(shape)) for _, shape, _ in plans)
    stats = jax.devices()[0].memory_stats() or {}
    upcast = {} if stats.get("bytes_limit", float("inf")) - stats.get("bytes_in_use", 0) > need + (3 << 30) else None

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
            # Gaps in units of each layer's router-logit standard deviation.
            sel = ref["select"] / ref["select"].std(axis=(1, 2), keepdims=True)
            ch = reference.choice_disagreements(prog, ref["choices"], sel, valid)
            compared, disagree, worst = compared + ch["compared"], disagree + ch["disagree"], max(worst, ch["worst_gap"])
        share = disagree / max(compared, 1)
        out["router_choices"] = {
            "compared": compared, "disagree": disagree, "worst_gap": worst, "disagree_share": share,
            "ok": worst <= CHOICE_EPS and share <= CHOICE_DISAGREE_SHARE,
        }
        return out

    # Tokens of a cold row past its own length were computed on padding.
    cold_valid = (np.arange(S)[None, :] < np.asarray(cold_lengths)[:, None]).reshape(-1)
    cold_at = np.asarray(cold_lengths)[:, None] - 1
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
        # The reference itself, faulty, routed freely and held to the same
        # limits against the sound reference: each has to fail.
        # The float8 one is routed freely (its routing is part of what the
        # precision breaks); the window one by the program's choices, so
        # that the window is ALL that differs (its cold rows, shorter than
        # the window, then agree, and the rows past it do not).
        for name, forced, fault in (
            ("control_float8", (None, None), {"dtype": "float8_e4m3fn"}),
            ("control_window_ignored", (long_choices, cold_choices), {"variant": "window_ignored"}),
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
