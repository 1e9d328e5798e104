"""`model_type: nemotron_h` (NemotronHForCausalLM: NVIDIA-Nemotron-3-Super):
blocks that are each ONE of a Mamba-2 mixer (`M`), attention without rope
(`*`) or sigmoid-routed experts in a latent space beside a shared expert
(`E`), by `hybrid_override_pattern`. The program runs it through
`kubeai_tpu/models/nemotron_h.py`; the plain reference is
`nemotron_h_reference.py`, beside this file.

Scales: a linear draws with its fan-in's `in**-0.5` (the router too; the
convolution's fan-in is its taps), embeddings and head 0.02,
`e_score_correction_bias` and `conv1d.bias` 0.1, norms and `D` ones. The
harness draws a tensor uniform about 0 or fills it with ones
(`children.py::draw`), so the decay a step `a = exp(-softplus(dt + dt_bias)
exp(A_log))` is spread by two wide uniforms: `A_log` with deviation 1
(`-A` from 0.18 to 5.6) and `dt_bias` with deviation 2 (the step size from
0.03 to 3.5 about a `dt` of deviation 1). `a` then runs from near 0 to
0.995 a head and a token, two fifths of the (head, token) pairs in (0.5, 1):
heads that forget at once, heads that keep a few tokens and heads that keep
hundreds side by side. The logits check reports the share it found
(`decay_in_half_to_one`).
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PROGRAM = os.path.join(ROOT, "kubeai_tpu", "models", "nemotron_h.py")


def _program_is_here() -> None:
    """A checkout from before PR 40 under these benchmark files says so in
    the run's first phase, not after a 9 GB checkpoint and a dead pod."""
    if not os.path.exists(PROGRAM):
        raise SystemExit(f"{os.path.relpath(PROGRAM, ROOT)} is not in this checkout: the program cannot run model_type nemotron_h")


def layer_plan(hf: dict, i: int) -> list[tuple]:
    _program_is_here()
    D = hf["hidden_size"]
    p = f"backbone.layers.{i}.mixer."
    lin = lambda name, out, inp: (p + name + ".weight", (out, inp), inp**-0.5)  # noqa: E731
    plan = [(f"backbone.layers.{i}.norm.weight", (D,), None)]
    kind = hf["hybrid_override_pattern"][i]
    if kind == "M":
        Hm, P, N, G, K = hf["mamba_num_heads"], hf["mamba_head_dim"], hf["ssm_state_size"], hf["n_groups"], hf["conv_kernel"]
        inner, C = Hm * P, Hm * P + 2 * G * N
        return plan + [
            lin("in_proj", inner + C + Hm, D),
            (p + "conv1d.weight", (C, 1, K), K**-0.5), (p + "conv1d.bias", (C,), 0.1),
            (p + "A_log", (Hm,), 1.0), (p + "D", (Hm,), None), (p + "dt_bias", (Hm,), 2.0),
            (p + "norm.weight", (inner,), None),
            lin("out_proj", D, inner),
        ]
    if kind == "*":
        H, Kv, d = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
        return plan + [lin("q_proj", H * d, D), lin("k_proj", Kv * d, D), lin("v_proj", Kv * d, D), lin("o_proj", D, H * d)]
    R = hf.get("router_experts") or hf["n_routed_experts"]
    Z, F, Fs = hf["moe_latent_size"], hf["moe_intermediate_size"], hf["moe_shared_expert_intermediate_size"]
    plan += [
        lin("gate", R, D), (p + "gate.e_score_correction_bias", (R,), 0.1),
        lin("fc1_latent_proj", Z, D), lin("fc2_latent_proj", D, Z),
        lin("shared_experts.up_proj", Fs, D), lin("shared_experts.down_proj", D, Fs),
    ]
    first = hf.get("experts_first") or 0
    for j in range(first, first + hf["n_routed_experts"]):  # an expert keeps its index in the router
        plan += [lin(f"experts.{j}.up_proj", F, Z), lin(f"experts.{j}.down_proj", Z, F)]
    return plan


def outside_plan(hf: dict) -> list[tuple]:
    _program_is_here()
    D, V = hf["hidden_size"], hf["vocab_size"]
    return [
        ("backbone.embeddings.weight", (V, D), 0.02),
        ("backbone.norm_f.weight", (D,), None),
        ("lm_head.weight", (V, D), 0.02),
    ]


# The comparison that decides `correct` (c), at the published widths on the
# checkpoint cut to `logits_check_layers` (8: `MEMEMEM*`, the shortest prefix
# of the pattern that holds all three kinds). Program: bf16, the kernel
# routes, the TIMED path: a prompt of 2304 tokens in chunk calls of 1024,
# 1024 and 256 (each chunk behind the first starts from the state the last
# left in the slot), then 256 decode steps through the slot's state and the
# pool; and short cold prompts through the flash route. Reference: float32
# at the highest matmul precision, one pass over the same tokens, the
# recurrence token by token, the same share of the experts. Three parts.
#
# Part 1, logits, with the reference ROUTED BY THE PROGRAM'S OWN CHOICES (a
# flip between a token's 22nd and 23rd expert of 512 says nothing about the
# arithmetic). What is left is bf16 rounding through 8 blocks. The other
# families read max 0.054-0.077 / mean 0.009-0.013 (dense, 4 layers) and
# 0.059-0.074 / 0.010-0.011 (kanana-2): this family's readings are in
# PERF.md section 6 (PR 40), and the bounds lie between them and what a
# fault in the mathematics gives.
LOGITS_MAX_ABS = 0.25
LOGITS_MEAN_ABS = 0.04
# Part 2, the program's choices against the reference's FREE choices on the
# same inputs: where the two sets differ, what the reference chose from
# (score + bias) must lie within CHOICE_EPS for the experts that changed
# places, in units of that block's deviation of score + bias over experts.
# The program's router reads a bf16 stream, so near-ties flip, and with 22
# chosen of 512 nearly every token has one; a router on the wrong input
# disagrees on experts whole deviations apart.
CHOICE_EPS = 0.2
# Part 3, THE STATE: what the slot holds for the FIRST `M` block after the
# last decode step against the reference's state after the same token: the
# relative error (Frobenius, a head's whole [64, 128] state) of the
# SLOW_HEADS heads that forget slowest (the reference's own mean of log a
# over the tokens), averaged. The first block, because its inputs carry no
# other block's rounding (the later blocks read 1.1%, 1.5%, 1.8%, all of it
# the bf16 stream's); the slowest heads, because a rounding a step adds up
# only where the state outlives many steps (a head that forgets in three
# tokens forgets a rounding with them). The program's state is float32 and
# its error there is the bf16 of the block's own activations (x, B, C, dt),
# once a token: read 0.0040 and 0.0043 over two seeds, no head of the eight
# above 0.0054. A state HELD in bfloat16 is rounded again at every decode
# step and every chunk's end, 259 times here, which is the control that has
# to fail (`serving.logits_control`, a hand run): read 0.0086 and 0.0089,
# no head of the eight under 0.0068 (my chip runs, PR 40; PERF.md section
# 6). The limit lies between, a third and more from either. Over ALL heads
# of the block the two read 0.0042-0.0044 against 0.0047-0.0049, and the
# logits 0.014 against 0.014: neither tells a bfloat16 state from bf16
# activations, which is why this part exists.
STATE_SLOW_REL = 0.006
SLOW_HEADS = 8

LONG_PROMPT = 2304  # chunk calls of 1024, 1024 and 256: the state is carried twice before it is decoded from
DECODE_STEPS = 256


def logits(path: str, seed: str, serving: dict) -> dict:
    """The timed path against the plain reference (see above). With
    `serving.logits_control` the program's part runs once more with the
    state held in bfloat16 and is held to the same limits: it has to fail."""
    import gc
    import json
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, ROOT)
    from families import nemotron_h_reference as reference
    from kubeai_tpu.engine.coldstart import setup_compile_cache
    from kubeai_tpu.engine.core import EngineConfig
    from kubeai_tpu.engine.weights import SafetensorsSource, load_engine_from_path
    from kubeai_tpu.models import nemotron_h

    setup_compile_cache()
    clock = [time.monotonic()]
    seconds = {}

    def lap(name):
        clock.append(time.monotonic())
        seconds[name] = round(clock[-1] - clock[-2], 3)

    with open(os.path.join(path, "config.json")) as f:
        hf = json.load(f)
    page = serving.get("page_size", 64)
    # Lengths in units of the mixer's chunk, so that a rehearsal's tiny
    # model walks the same phases (8 x 128 = 1024 at the published size).
    chunk = 8 * hf["chunk_size"]
    long_prompt, steps = LONG_PROMPT * chunk // 1024, DECODE_STEPS * chunk // 1024
    sizes = [chunk, chunk, long_prompt - 2 * chunk]
    max_seq = (long_prompt + steps + page) // page * page + page
    eng = load_engine_from_path(
        path, EngineConfig(max_slots=1, max_seq_len=max_seq, page_size=page, prefill_buckets=(chunk // 4, chunk)),
        overlap=False, warmup=False,
    )
    params, cfg = eng.params, eng.model_config
    lap("load")
    max_pages = max_seq // page
    rng = np.random.default_rng(int(seed))
    long_tokens = rng.integers(0, 259, long_prompt + steps)
    table = np.zeros((1, max_pages), np.int32)
    table[0] = 1 + np.arange(max_pages)
    slot = jnp.zeros((1,), jnp.int32)
    prefill = jax.jit(
        lambda p, tk, c, start, last: nemotron_h.prefill_paged(p, cfg, tk, c, table, start, last, slots=slot, return_choices=True)
    )
    decode = jax.jit(lambda p, tk, c, lengths: nemotron_h.decode_step_paged(p, cfg, tk, c, table, lengths, return_choices=True))
    pools_of = lambda cache: {k: cache[k] for k in ("kv", "ssm", "conv")}  # noqa: E731

    def timed_path(state_dtype):
        """Chunk calls, then decode steps: (logits at each chunk's end and
        each step, their positions, every token's choices, the final state)."""
        pools = nemotron_h.init_paged_cache(cfg, max_pages + 1, page, slots=1, state_dtype=state_dtype)
        got, at, choices, start = [], [], [], 0
        for n in sizes:
            lg, cache, ch = prefill(
                params, long_tokens[None, start : start + n], pools, jnp.asarray([start], jnp.int32), jnp.asarray([n - 1], jnp.int32),
            )
            pools = pools_of(cache)
            got.append(np.asarray(lg[0, 0]))
            at.append(start + n - 1)
            choices.append(np.asarray(ch))
            start += n
        for pos in range(long_prompt, long_prompt + steps):
            lg, cache, ch = decode(params, long_tokens[None, pos : pos + 1], pools, jnp.asarray([pos], jnp.int32))
            pools = pools_of(cache)
            got.append(np.asarray(lg[0, 0]))
            at.append(pos)
            choices.append(np.asarray(ch))
        return np.stack(got), at, np.concatenate(choices, axis=1), np.asarray(pools["ssm"][:, 0], np.float32)

    long_got, at, long_choices, long_state = timed_path(jnp.float32)
    control = timed_path(jnp.bfloat16) if serving.get("logits_control") else None

    # -- short cold prompts: the flash route (a whole tile) with rows padded past their length
    B, S = 4, chunk // 4
    cold_tokens = rng.integers(0, 259, (B, S))
    cold_lengths = [S, S - S // 5, S, S // 2 + 3]
    n_pages = -(-S // page)
    cold_table = 1 + np.arange(B * n_pages, dtype=np.int32).reshape(B, n_pages)
    cold_pools = nemotron_h.init_paged_cache(cfg, B * n_pages + 1, page, slots=B)
    lg, cold_cache, ch = jax.jit(
        lambda p, tk, c: nemotron_h.prefill_paged_cold(
            p, cfg, tk, c, cold_table, jnp.asarray(cold_lengths, jnp.int32), slots=jnp.arange(B, dtype=jnp.int32),
            return_choices=True,
        )
    )(params, cold_tokens, cold_pools)
    cold_got, cold_choices = np.asarray(lg[:, 0]), np.asarray(ch)
    routes = {
        "chunk": nemotron_h.cached_attention_route(cfg, chunk, False, True),
        "decode": nemotron_h.cached_attention_route(cfg, 1, False, True),
        "cold": nemotron_h.cached_attention_route(cfg, S, True, True),
    }
    eng.stop()  # never started: this unbinds the gauges that would keep its arrays alive
    del eng, params, cold_pools, cold_cache, lg  # the reference needs the room
    gc.collect()
    lap("program")

    source = SafetensorsSource(path)
    plans = outside_plan(hf) + [t for i in range(hf["num_hidden_layers"]) for t in layer_plan(hf, i)]
    need = 4 * sum(int(np.prod(shape)) for _, shape, _ in plans)
    stats = jax.devices()[0].memory_stats() or {}
    upcast = {} if stats.get("bytes_limit", float("inf")) - stats.get("bytes_in_use", 0) > need + (3 << 30) else None
    ref_long = reference.forward(source.get, hf, long_tokens[None], forced=long_choices, logits_at=np.asarray(at)[None], upcast=upcast)
    lap("reference_long")
    cold_at = np.asarray(cold_lengths)[:, None] - 1
    ref_cold = reference.forward(source.get, hf, cold_tokens, forced=cold_choices, logits_at=cold_at, upcast=upcast)
    lap("reference_cold")
    # Tokens of a cold row past its own length were computed on padding.
    cold_valid = (np.arange(S)[None, :] < np.asarray(cold_lengths)[:, None]).reshape(-1)
    n_chunks = len(sizes)

    def compare(got_long, state, ch_long, ref_long) -> dict:
        out = {}
        parts = {
            "prefill_chunked": (got_long[:n_chunks], ref_long["logits"][0, :n_chunks]),
            "decode": (got_long[n_chunks:], ref_long["logits"][0, n_chunks:]),
            "prefill_cold": (cold_got, ref_cold["logits"][:, 0]),
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
        for prog, ref, valid in ((ch_long, ref_long, None), (cold_choices, ref_cold, cold_valid)):
            sel = ref["select"] / ref["select"].std(axis=(1, 2), keepdims=True)
            d = reference.choice_disagreements(prog, ref["choices"], sel, valid)
            compared, disagree, worst = compared + d["compared"], disagree + d["disagree"], max(worst, d["worst_gap"])
        out["router_choices"] = {
            "compared": compared, "disagree": disagree, "worst_gap": worst, "disagree_share": disagree / max(compared, 1),
            "ok": worst <= CHOICE_EPS,
        }
        want = ref_long["states"][:, 0].astype(np.float64)  # [n_M, heads, head_dim, N]
        err = np.sqrt(((state.astype(np.float64) - want) ** 2).sum(axis=(1, 2, 3)) / (want**2).sum(axis=(1, 2, 3)))
        by_head = np.sqrt(
            ((state[0].astype(np.float64) - want[0]) ** 2).sum(axis=(1, 2)) / np.maximum((want[0] ** 2).sum(axis=(1, 2)), 1e-30)
        )
        slow = np.argsort(-ref_long["mean_log_decay"][0])[:SLOW_HEADS]
        out["state"] = {
            "finite": bool(np.isfinite(state).all()), "rel_by_block": [float(e) for e in err],
            "slow_heads": [int(h) for h in slow], "slow_heads_mean_log_decay": [float(v) for v in ref_long["mean_log_decay"][0][slow]],
            "rel_by_slow_head": [float(e) for e in by_head[slow]], "rel_slow_heads": float(by_head[slow].mean()),
            "ok": bool(np.isfinite(state).all()) and float(by_head[slow].mean()) <= STATE_SLOW_REL,
        }
        return out

    compared = compare(long_got, long_state, long_choices, ref_long)
    dev = jax.devices()[0]
    result = {
        "platform": dev.platform, "kind": dev.device_kind,
        "kernel_route": {"flash": cfg.use_flash_prefill, "paged_kernel": cfg.use_paged_kernel, **routes},
        "layers": cfg.num_layers, "pattern": cfg.layer_pattern, "held_experts": nemotron_h.held_share(cfg),
        "sample": {
            "long_prompt": long_prompt, "chunks": sizes, "decode_steps": steps,
            "cold_rows": B, "cold_bucket": S, "cold_lengths": cold_lengths,
            "decay_in_half_to_one": ref_long["decay_in_half_to_one"],
        },
        "tolerance": {"max_abs": LOGITS_MAX_ABS, "mean_abs": LOGITS_MEAN_ABS, "choice_eps": CHOICE_EPS, "state_slow_rel": STATE_SLOW_REL},
        "compared": compared,
        "ok": all(c["ok"] for c in compared.values()),
    }
    lap("compare")
    if control is not None:
        # The same path with the state held in bfloat16, routed as IT chose,
        # against a reference routed by those choices: it has to fail.
        bad_got, _, bad_choices, bad_state = control
        ref_bad = reference.forward(source.get, hf, long_tokens[None], forced=bad_choices, logits_at=np.asarray(at)[None], upcast=upcast)
        bad = compare(bad_got, bad_state, bad_choices, ref_bad)
        result["control_bfloat16_state"] = {**bad, "ok": all(c["ok"] for c in bad.values())}
        lap("control")
    result["seconds_by_part"] = seconds
    return result
