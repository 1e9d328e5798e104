"""`model_type: lfm2_moe` (Lfm2MoeForCausalLM: LiquidAI LFM2-24B-A2B): a
stack whose operator is a gated short convolution or, in every fourth layer,
QK-normed grouped-query attention with heads of 64; two leading dense
feed-forwards, then 64 sigmoid-routed experts, top 4, no shared one; the head
is the embedding. The program runs it through `kubeai_tpu/models/lfm2_moe.py`;
the plain reference is `lfm2_moe_reference.py`, beside this file.

Scales: a linear draws with its fan-in's `in**-0.5` (the router too; the
convolution's fan-in is its taps), the embedding 0.02, `expert_bias` 0.1 as
the configuration's `assumed` states, the layers' norms and the final norm
ones. `q_layernorm` and `k_layernorm` draw with deviation QK_NORM_SCALE =
1.41 for the reason `families/afmoe.py` gives (with both at one, attention
logits have a deviation of 1 and every query averages its whole context; at
1.41 a query weighs some tens of keys), and so that a reference that drops
the two norms, or applies them behind the rotation, is told apart: unit
weights on projections of unit variance would be nearly no norm at all.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PROGRAM = os.path.join(ROOT, "kubeai_tpu", "models", "lfm2_moe.py")
QK_NORM_SCALE = 1.41
EXPERT_BIAS_SCALE = 0.1


def _program_is_here() -> None:
    """A checkout from before PR 51 under these benchmark files says so in
    the run's first phase, not after a 10 GB checkpoint and a dead pod."""
    if not os.path.exists(PROGRAM):
        raise SystemExit(f"{os.path.relpath(PROGRAM, ROOT)} is not in this checkout: the program cannot run model_type lfm2_moe")


def layer_plan(hf: dict, i: int) -> list[tuple]:
    _program_is_here()
    D, H, Kv, K = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"], hf["conv_L_cache"]
    d = D // H
    p = f"model.layers.{i}."
    lin = lambda name, out, inp: (p + name + ".weight", (out, inp), inp**-0.5)  # noqa: E731
    ones = lambda name, n: (p + name + ".weight", (n,), None)  # noqa: E731
    plan = [ones("operator_norm", D), ones("ffn_norm", D)]
    if hf["layer_types"][i] == "conv":
        plan += [lin("conv.in_proj", 3 * D, D), (p + "conv.conv.weight", (D, 1, K), K**-0.5), lin("conv.out_proj", D, D)]
    else:
        plan += [
            lin("self_attn.q_proj", H * d, D), lin("self_attn.k_proj", Kv * d, D), lin("self_attn.v_proj", Kv * d, D),
            lin("self_attn.out_proj", D, H * d),
            (p + "self_attn.q_layernorm.weight", (d,), QK_NORM_SCALE), (p + "self_attn.k_layernorm.weight", (d,), QK_NORM_SCALE),
        ]
    ff = "feed_forward."
    if i < hf["num_dense_layers"]:
        F = hf["intermediate_size"]
        return plan + [lin(ff + "w1", F, D), lin(ff + "w3", F, D), lin(ff + "w2", D, F)]
    E, F = hf["num_experts"], hf["moe_intermediate_size"]
    plan += [lin(ff + "gate", E, D), (p + ff + "expert_bias", (E,), EXPERT_BIAS_SCALE)]
    for j in range(E):
        e = f"{ff}experts.{j}."
        plan += [lin(e + "w1", F, D), lin(e + "w3", F, D), lin(e + "w2", D, F)]
    return plan


def outside_plan(hf: dict) -> list[tuple]:
    _program_is_here()
    D, V = hf["hidden_size"], hf["vocab_size"]
    return [("model.embed_tokens.weight", (V, D), 0.02), ("model.embedding_norm.weight", (D,), None)]  # no lm_head: tied


# The comparison that decides `correct` (c), at the published widths on the
# checkpoint cut to `logits_check_layers` (6: both dense layers under
# convolutions, then one period: experts under attention and under three
# convolutions). Program: bf16, the kernel routes, the TIMED path at the
# SERVING'S SLOT COUNT (`--max-slots`: 192 in the cell), five sessions in
# slots scattered among idle ones whose tails hold noise: a prompt of 4596
# tokens in the cell's own chunk calls of 2048, 2048 and 500 (the last in a
# 512-row bucket: 12 rows of padding that may move no tail; each chunk behind
# the first starts from the tails the last left in ITS slot; the attention
# layer behind 2048 and 4096 cached keys, two heads of 64 to a 128-lane row of
# the pool); four short cold prompts of unequal lengths in one call through
# the flash route, each row's tail written at its own slot; then 32 decode
# steps of all five together as the engine's decode chunk makes them
# (`LiveRows.first(active)`: the live slots' rows first, the convolutions in
# slot order through `live.restore` / `live.take`, the ragged kernel told
# `live_rows`, tables, tokens and lengths taken in the step's order).
# Reference: float32 at the highest matmul precision, one pass over each
# session's own tokens. Four parts.
#
# Part 1, logits, with the reference ROUTED BY THE PROGRAM'S OWN CHOICES (a
# flip between a token's 4th and 5th expert swaps a quarter of its routed
# output, which no logits bound survives, and says nothing about the
# arithmetic). What is left is bf16 rounding through 6 layers. The other
# families read max 0.054-0.077 / mean 0.009-0.013 (dense, 4 layers),
# 0.059-0.074 / 0.010-0.011 (kanana-2): this family's readings and its
# controls' are in PERF.md section 6 (PR 51), and the bounds lie between them.
# Every live slot's logits are held to ITS session's reference, so a row put
# back at another slot reads another session's logits (deviation 1 and more).
LOGITS_MAX_ABS = 0.25
LOGITS_MEAN_ABS = 0.04
# Part 2, the program's choices against the reference's FREE choices on the
# same inputs: where the two sets differ, what the reference chose from
# (score + bias) must lie within CHOICE_EPS for the experts that changed
# places, in units of that layer's deviation of score + bias over experts and
# tokens. The program's router reads a bf16 stream (8 bits of mantissa), so
# near-ties flip; a router without its bias, or on the wrong input, disagrees
# on experts whole deviations apart.
CHOICE_EPS = 0.2
# ... and the share of (expert layer, token) pairs that may disagree at all.
CHOICE_DISAGREE_SHARE = 0.25
# Part 3, THE TAILS: what each live slot holds for every convolution after the
# last decode step (the last K - 1 rows of `z = B * x`, bf16) against its
# session's reference, as a relative error (Frobenius, a layer's whole
# [K-1, D] tail), the worst layer and slot. The program's error is the bf16 of
# the stream and of `z` itself; a tail that is one row off, that a padded row
# moved, or that was written at another slot holds other rows altogether
# (relative error about 1.4: two independent draws).
TAIL_REL = 0.1
# Part 4, THE IDLE SLOTS: every slot no session was given keeps the noise it
# was filled with, bit for bit, through every prefill call and decode step.

LONG_PROMPT = (2048, 2048, 500)  # the cell's own chunk calls: the tails are carried twice before they are decoded from
DECODE_STEPS = 32
COLD_ROWS = 4


def logits(path: str, seed: str, serving: dict) -> dict:
    """The timed path against the plain reference (see above). With
    `serving.logits_control` (a hand run, never a cell's) two references
    that have to come out as not correct under the same limits: every
    matmul's inputs and both gates' products rounded through float8_e4m3fn
    (the nearest precision under bf16), and the taps reading one row further
    back."""
    import gc
    import json
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, ROOT)
    from families import lfm2_moe_reference as reference
    from kubeai_tpu.engine.coldstart import setup_compile_cache
    from kubeai_tpu.engine.core import EngineConfig
    from kubeai_tpu.engine.weights import SafetensorsSource, load_engine_from_path
    from kubeai_tpu.models import lfm2_moe
    from kubeai_tpu.models.base import LiveRows

    setup_compile_cache()
    clock = [time.monotonic()]
    seconds = {}

    def lap(name):
        clock.append(time.monotonic())
        seconds[name] = round(clock[-1] - clock[-2], 3)

    with open(os.path.join(path, "config.json")) as f:
        hf = json.load(f)
    page = serving.get("page_size", 64)
    # Lengths in units of the cell's chunk, so that a rehearsal's tiny model
    # walks the same phases (`logits_chunk`: 2048 as served).
    chunk = serving.get("logits_chunk", 2048)
    sizes = [n * chunk // 2048 for n in LONG_PROMPT]
    long_prompt, steps = sum(sizes), DECODE_STEPS
    max_seq = (long_prompt + steps + page) // page * page + page
    # The engine is only the loader here: the pool and the tails below are
    # this check's own, at the serving's slot count.
    eng = load_engine_from_path(
        path, EngineConfig(max_slots=1, max_seq_len=max_seq, page_size=page, prefill_buckets=(chunk // 4, chunk)),
        overlap=False, warmup=False,
    )
    params, cfg = eng.params, eng.model_config
    lap("load")
    n_slots = int(serving["engine_args"][serving["engine_args"].index("--max-slots") + 1])
    B, S = COLD_ROWS, chunk // 4
    long_slot, cold_slots = n_slots // 3, [1, n_slots // 2, n_slots - 2, n_slots - 1]
    live_slots = sorted([long_slot, *cold_slots])  # the order of a decode step's first rows
    assert len(set(live_slots)) == 1 + B and live_slots[0] >= 0, f"--max-slots {n_slots} is too few for the check's sessions"
    idle_slots = np.setdiff1d(np.arange(n_slots), live_slots)
    max_pages, cold_pages = max_seq // page, -(-(S + steps) // page)
    rng = np.random.default_rng(int(seed))
    long_tokens = rng.integers(0, 259, long_prompt + steps)
    cold_tokens = rng.integers(0, 259, (B, S))
    cold_next = rng.integers(0, 259, (B, steps))  # what each cold session is fed while it decodes
    cold_lengths = np.asarray([S, S - S // 5, S, S // 2 + 3])
    # Every slot's row of the table; a slot without a session points at the trash page.
    tables = np.zeros((n_slots, max_pages), np.int32)
    tables[long_slot] = 1 + np.arange(max_pages)
    for b, slot in enumerate(cold_slots):
        tables[slot, :cold_pages] = 1 + max_pages + b * cold_pages + np.arange(cold_pages)
    pools = lfm2_moe.init_paged_cache(cfg, 1 + max_pages + B * cold_pages, page, slots=n_slots)
    noise = jnp.asarray(rng.standard_normal(pools["conv"].shape, np.float32), pools["conv"].dtype)
    pools["conv"] = noise
    pools_of = lambda cache: {k: cache[k] for k in ("kv", "conv")}  # noqa: E731

    long_table = tables[long_slot : long_slot + 1]
    prefill = jax.jit(
        lambda p, tk, c, start, last: lfm2_moe.prefill_paged(
            p, cfg, tk, c, long_table, start, last, slots=jnp.asarray([long_slot], jnp.int32), return_choices=True
        )
    )
    got, at, choices, start = [], [], [], 0
    for n in sizes:
        bucket = chunk if n == chunk else chunk // 4 * -(-n // (chunk // 4))
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = long_tokens[start : start + n]
        lg, cache, ch = prefill(params, padded, pools, jnp.asarray([start], jnp.int32), jnp.asarray([n - 1], jnp.int32))
        pools = pools_of(cache)
        got.append(np.asarray(lg[0, 0]))
        at.append(start + n - 1)
        choices.append(np.asarray(ch)[:, :n])
        start += n

    # -- short cold prompts: the flash route (a whole tile) with rows padded past their length
    lg, cache, ch = jax.jit(
        lambda p, tk, c: lfm2_moe.prefill_paged_cold(
            p, cfg, tk, c, tables[cold_slots, :cold_pages], jnp.asarray(cold_lengths, jnp.int32),
            slots=jnp.asarray(cold_slots, jnp.int32), return_choices=True,
        )
    )(params, cold_tokens, pools)
    pools = pools_of(cache)
    cold_got, cold_choices = [np.asarray(lg[:, 0])[:, None]], np.asarray(ch).reshape(-1, B, S, cfg.num_experts_per_tok)

    # -- decode, all five sessions in one step of `n_slots` rows, as core.py::decode_fn makes it
    active = np.zeros((n_slots,), bool)
    active[live_slots] = True

    def decode_step(p, last, c, lengths):
        live = LiveRows.first(jnp.asarray(active))
        tables_live, last_live, lengths_live = live.take(jnp.asarray(tables), last, lengths)
        return lfm2_moe.decode_step_paged(p, cfg, last_live[:, None], c, tables_live, lengths_live, live=live, return_choices=True)

    decode = jax.jit(decode_step)
    row_of = {slot: i for i, slot in enumerate(live_slots)}  # a live slot's row of the step, and of its choices
    last, lengths = np.zeros((n_slots,), np.int32), np.zeros((n_slots,), np.int32)
    cold_steps = []
    for t in range(steps):
        last[long_slot], lengths[long_slot] = long_tokens[long_prompt + t], long_prompt + t
        last[cold_slots], lengths[cold_slots] = cold_next[:, t], cold_lengths + t
        lg, cache, ch = decode(params, last, pools, lengths)
        pools = pools_of(cache)
        lg, ch = np.asarray(lg[np.asarray(live_slots), 0]), np.asarray(ch)
        got.append(lg[row_of[long_slot]])
        at.append(long_prompt + t)
        choices.append(ch[:, row_of[long_slot]][:, None])
        cold_got.append(np.stack([lg[row_of[slot]] for slot in cold_slots])[:, None])
        cold_steps.append(np.stack([ch[:, row_of[slot]] for slot in cold_slots], axis=1))  # [expert layers, B, k]
    long_got, long_choices = np.stack(got), np.concatenate(choices, axis=1)
    cold_got = np.concatenate(cold_got, axis=1)  # [B, 1 + steps, V]
    held = np.asarray(pools["conv"])
    live_tails = held[:, [long_slot, *cold_slots]].astype(np.float32)  # [conv layers, 1 + B, K-1, D]
    idle_moved = int((held[:, idle_slots] != np.asarray(noise)[:, idle_slots]).any(axis=(0, 2, 3)).sum())
    routes = {
        "chunk": lfm2_moe.cached_attention_route(cfg, chunk, False, True),
        "decode": lfm2_moe.cached_attention_route(cfg, 1, False, True),
        "cold": lfm2_moe.cached_attention_route(cfg, S, True, True),
    }
    eng.stop()  # never started: this unbinds the gauges that would keep its arrays alive
    del eng, params, pools, cache, lg, noise  # the reference needs the room
    gc.collect()
    lap("program")

    # A cold session as the reference sees it: its prompt's real tokens,
    # then what it was fed while decoding; the rest of the row is filler
    # behind everything that is compared (`lengths`: where its tail is read).
    T = S + steps
    cold_session = np.zeros((B, T), np.int64)
    cold_forced = np.broadcast_to(np.arange(cfg.num_experts_per_tok), (cold_choices.shape[0], B, T, cfg.num_experts_per_tok)).copy()
    cold_at = np.zeros((B, 1 + steps), np.int64)
    for b, n in enumerate(cold_lengths):
        cold_session[b, :n], cold_session[b, n : n + steps] = cold_tokens[b, :n], cold_next[b]
        cold_forced[:, b, :n] = cold_choices[:, b, :n]
        cold_forced[:, b, n : n + steps] = np.stack([step[:, b] for step in cold_steps], axis=1)
        cold_at[b] = np.arange(n - 1, n + steps)
    cold_forced = cold_forced.reshape(cold_forced.shape[0], B * T, -1)
    cold_valid = (np.arange(T)[None, :] < (cold_lengths + steps)[:, None]).reshape(-1)

    source = SafetensorsSource(path)
    plans = outside_plan(hf) + [t for i in range(hf["num_hidden_layers"]) for t in layer_plan(hf, i)]
    need = 4 * sum(int(np.prod(shape)) for _, shape, _ in plans)
    stats = jax.devices()[0].memory_stats() or {}
    upcast = {} if stats.get("bytes_limit", float("inf")) - stats.get("bytes_in_use", 0) > need + (3 << 30) else None
    n_chunks = len(sizes)

    def references(forced_long, forced_cold, **fault):
        ref_long = reference.forward(source.get, hf, long_tokens[None], forced=forced_long, logits_at=np.asarray(at)[None], upcast=upcast, **fault)
        lap("reference_long" + "".join(f"_{v}" for v in fault.values()))
        ref_cold = reference.forward(
            source.get, hf, cold_session, forced=forced_cold, logits_at=cold_at, lengths=cold_lengths + steps, upcast=upcast, **fault
        )
        lap("reference_cold" + "".join(f"_{v}" for v in fault.values()))
        return ref_long, ref_cold

    def compare(got_long, got_cold, tails, ch_long, ch_cold, ref_long, ref_cold) -> dict:
        """*got_long* [chunks + steps, V], *got_cold* [B, 1 + steps, V] and
        *tails* [conv layers, 1 + B, K-1, D] (the long session first), with
        the choices they were routed by, against the sound reference."""
        out = {}
        decode_rows = lambda long, cold: np.concatenate([long[n_chunks:], cold[:, 1:].reshape(B * steps, -1)])  # noqa: E731
        parts = {
            "prefill_chunked": (got_long[:n_chunks], ref_long["logits"][0, :n_chunks]),
            "decode": (decode_rows(got_long, got_cold), decode_rows(ref_long["logits"][0], ref_cold["logits"])),
            "prefill_cold": (got_cold[:, 0], ref_cold["logits"][:, 0]),
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
        want = np.concatenate([ref_long["tails"], ref_cold["tails"]], axis=1).astype(np.float64)  # [conv layers, 1 + B, K-1, D]
        rel = np.sqrt(((tails.astype(np.float64) - want) ** 2).sum(axis=(2, 3)) / np.maximum((want**2).sum(axis=(2, 3)), 1e-30))
        out["tails"] = {
            "finite": bool(np.isfinite(tails).all()), "rel_by_layer": [float(e) for e in rel.max(axis=1)],
            "rel_by_session": [float(e) for e in rel.max(axis=0)], "rel_worst": float(rel.max()),
            "ok": bool(np.isfinite(tails).all()) and float(rel.max()) <= TAIL_REL,
        }
        return out

    ref_long, ref_cold = references(long_choices, cold_forced)
    compared = compare(long_got, cold_got, live_tails, long_choices, cold_forced, ref_long, ref_cold)
    compared["idle_tails"] = {"slots": int(idle_slots.size), "moved": idle_moved, "ok": idle_moved == 0}
    dev = jax.devices()[0]
    result = {
        "platform": dev.platform, "kind": dev.device_kind,
        "kernel_route": {"flash": cfg.use_flash_prefill, "paged_kernel": cfg.use_paged_kernel, **routes},
        "layers": cfg.num_layers, "pattern": cfg.layer_pattern,
        "sample": {
            "slots": n_slots, "long_slot": long_slot, "cold_slots": cold_slots,
            "long_prompt": long_prompt, "chunks": sizes, "decode_steps": steps,
            "cold_rows": B, "cold_bucket": S, "cold_lengths": cold_lengths.tolist(),
        },
        "tolerance": {
            "max_abs": LOGITS_MAX_ABS, "mean_abs": LOGITS_MEAN_ABS, "choice_eps": CHOICE_EPS,
            "choice_disagree_share": CHOICE_DISAGREE_SHARE, "tail_rel": TAIL_REL, "idle_tails_moved": 0,
        },
        "compared": compared,
        "ok": all(c["ok"] for c in compared.values()),
    }
    lap("compare")
    if serving.get("logits_control"):
        # The reference itself, faulty, held to the same limits against the
        # sound reference: each has to fail. The float8 one is routed freely
        # (its routing is part of what the precision breaks); the shifted
        # taps by the program's choices, so that the row is ALL that differs.
        for name, forced, fault in (
            ("control_float8", (None, None), {"dtype": "float8_e4m3fn"}),
            ("control_tail_one_row_off", (long_choices, cold_forced), {"variant": "tail_one_row_off"}),
        ):
            bad_long, bad_cold = references(*forced, **fault)
            control = compare(
                bad_long["logits"][0], bad_cold["logits"], np.concatenate([bad_long["tails"], bad_cold["tails"]], axis=1),
                bad_long["choices"], bad_cold["choices"], ref_long, ref_cold,
            )
            result[name] = {**control, "ok": all(c["ok"] for c in control.values())}
    result["seconds_by_part"] = seconds
    return result
