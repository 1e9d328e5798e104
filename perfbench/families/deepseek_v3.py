"""`model_type: deepseek_v3` (DeepseekV3ForCausalLM without a query
low-rank: kanana-2): latent attention, `first_k_dense_replace` dense
layers, then layers of sigmoid-routed experts beside shared ones. The
program runs it through `kubeai_tpu/models/deepseek.py`; the plain
reference is `deepseek_v3_reference.py`, beside this file.

Scales: a linear draws with its fan-in's `in**-0.5`, embeddings and head
0.02, `e_score_correction_bias` 0.1 (NOT zero: a router that left it out,
or added it to the weights, must show), norms ones.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def layer_plan(hf: dict, i: int) -> list[tuple]:
    D, H = hf["hidden_size"], hf["num_attention_heads"]
    dn, dr, dv, r = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"], hf["kv_lora_rank"]
    p = f"model.layers.{i}."
    lin = lambda name, out, inp: (p + name + ".weight", (out, inp), inp**-0.5)  # noqa: E731
    mlp = lambda prefix, width: [  # noqa: E731
        lin(prefix + "gate_proj", width, D), lin(prefix + "up_proj", width, D), lin(prefix + "down_proj", D, width),
    ]
    plan = [
        (p + "input_layernorm.weight", (D,), None),
        (p + "post_attention_layernorm.weight", (D,), None),
        lin("self_attn.q_proj", H * (dn + dr), D),
        lin("self_attn.kv_a_proj_with_mqa", r + dr, D),
        (p + "self_attn.kv_a_layernorm.weight", (r,), None),
        lin("self_attn.kv_b_proj", H * (dn + dv), r),
        lin("self_attn.o_proj", D, H * dv),
    ]
    if i < hf["first_k_dense_replace"]:
        return plan + mlp("mlp.", hf["intermediate_size"])
    E, Fm = hf["n_routed_experts"], hf["moe_intermediate_size"]
    plan += [lin("mlp.gate", E, D), (p + "mlp.gate.e_score_correction_bias", (E,), 0.1)]
    for j in range(E):
        plan += mlp(f"mlp.experts.{j}.", Fm)
    return plan + mlp("mlp.shared_experts.", Fm * hf["n_shared_experts"])


def outside_plan(hf: dict) -> list[tuple]:
    D, V = hf["hidden_size"], hf["vocab_size"]
    return [
        ("model.embed_tokens.weight", (V, D), 0.02),
        ("model.norm.weight", (D,), None),
        ("lm_head.weight", (V, D), 0.02),
    ]


# The two-part comparison that decides `correct` (c), at the published
# widths on the checkpoint cut to `logits_check_layers` (the dense layer
# and 3 expert layers). Program: bf16, the kernel routes, through the
# paged pool. Reference: float32 at the highest matmul precision.
#
# Part 1, logits, with the reference ROUTED BY THE PROGRAM'S OWN CHOICES: a
# flip between a token's 6th and 7th expert swaps a sixth of its routed
# output, which no logits bound survives, and says nothing about the
# arithmetic. What is left is bf16 rounding through 4 layers: the dense
# decoder reads max 0.054-0.077 / mean 0.009-0.013 at 4 layers (PERF.md,
# PR 27); this family's readings and the control's are in PERF.md section 6
# (PR 33), and the bounds lie between them.
LOGITS_MAX_ABS = 0.25
LOGITS_MEAN_ABS = 0.04
# Part 2, the program's choices against the reference's FREE choices on the
# same inputs: where the two sets differ, the reference's own selection
# scores (sigmoid + bias, in [0, 1.3]) of the experts that changed places
# must lie within CHOICE_EPS of each other. The program's router reads a
# bf16 hidden state (8 bits of mantissa: a score is off by up to a few
# thousandths after a 2048-term dot), so near-ties flip, a few percent of
# tokens a layer; a wrong router (softmax scores, the bias left out, rope on
# halves upstream) disagrees on experts whose scores are tenths apart.
CHOICE_EPS = 0.05
# ... and the share of (layer, token) pairs that may disagree at all.
CHOICE_DISAGREE_SHARE = 0.25


def logits(path: str, seed: str, serving: dict) -> dict:
    """Cold prefill, chunked prefill and one decode step through the
    program's kernel routes against the plain reference, in two parts (see
    above). `serving.logits_control` (a hand run, never a cell's) adds the
    reference computed in float8_e4m3fn, held to the same limits against
    the float32 reference: it has to come out as not correct."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, ROOT)
    from families import deepseek_v3_reference as reference
    from kubeai_tpu.engine.coldstart import setup_compile_cache
    from kubeai_tpu.engine.core import EngineConfig
    from kubeai_tpu.engine.weights import SafetensorsSource, load_engine_from_path
    from kubeai_tpu.models import deepseek

    setup_compile_cache()
    eng = load_engine_from_path(path, EngineConfig(max_slots=4, max_seq_len=512), overlap=False, warmup=False)
    params, cfg = eng.params, eng.model_config
    import json

    with open(os.path.join(path, "config.json")) as f:
        hf = json.load(f)
    B, S, page, max_pages = 4, 256, 64, 8
    rng = np.random.default_rng(int(seed))
    tokens = rng.integers(0, 259, (B, S))
    nxt = rng.integers(0, 259, (B,))
    row_lengths = [256, 200, 256, 131]
    lengths = jnp.asarray(row_lengths, jnp.int32)
    tables = jnp.asarray(1 + np.arange(B * max_pages, dtype=np.int32).reshape(B, max_pages))
    half = jnp.full((B,), S // 2, jnp.int32)
    t = jnp.asarray(tokens, jnp.int32)

    def fresh():
        return deepseek.init_paged_cache(cfg, B * max_pages + 1, page)

    cold, pool, ch_cold = jax.jit(
        lambda p, tk, c: deepseek.prefill_paged_cold(p, cfg, tk, c, tables, lengths, return_choices=True)
    )(params, t, fresh())
    step, _, ch_step = jax.jit(
        lambda p, tk, c: deepseek.decode_step_paged(p, cfg, tk, c, tables, lengths, return_choices=True)
    )(params, jnp.asarray(nxt[:, None], jnp.int32), {"kv": pool["kv"]})
    chunk = jax.jit(
        lambda p, tk, c, start, last: deepseek.prefill_paged(p, cfg, tk, c, tables, start, last, return_choices=True)
    )
    _, pool2, ch_a = chunk(params, t[:, : S // 2], fresh(), 0 * half, half - 1)
    chunked, _, ch_b = chunk(params, t[:, S // 2 :], {"kv": pool2["kv"]}, half, half - 1)
    got = {
        "prefill_cold": np.asarray(cold[:, 0]), "prefill_chunked": np.asarray(chunked[:, 0]),
        "decode": np.asarray(step[:, 0]),
    }
    Lm, k = ch_cold.shape[0], ch_cold.shape[2]
    rows = lambda ch, n: np.asarray(ch).reshape(Lm, B, n, k)  # noqa: E731
    ch_cold, ch_step = rows(ch_cold, S), rows(ch_step, 1)
    ch_chunk = np.concatenate([rows(ch_a, S // 2), rows(ch_b, S // 2)], axis=2)
    del eng, params, pool, pool2  # the reference needs the room

    # ONE reference pass over 3B rows of S+1 tokens, so the weights are
    # upcast once: the cold rows, the same rows routed as the chunks were,
    # and the rows with each one's next token at its own length.
    extra = tokens[:, -1:]
    ext = np.concatenate([tokens, extra], axis=1)
    pad = lambda ch: np.concatenate([ch, ch[:, :, -1:]], axis=2)  # noqa: E731  (position S: never read)
    ch_ext = pad(ch_cold)
    for b, n in enumerate(row_lengths):
        ext[b, n] = nxt[b]
        ch_ext[:, b, n] = ch_step[:, b, 0]
    all_tokens = np.concatenate([np.concatenate([tokens, extra], 1)] * 2 + [ext], axis=0)
    forced = np.concatenate([pad(ch_cold), pad(ch_chunk), ch_ext], axis=1).reshape(Lm, 3 * B * (S + 1), k)
    # The tokens whose choices the PROGRAM made on these very inputs: the
    # S prompt positions of the cold and the chunked rows (position S is
    # padding), and of a decode row its own position alone (before it the
    # row repeats the cold one; behind it the reference sees another
    # token at that position than the program's prefill did).
    valid = np.zeros((3 * B, S + 1), bool)
    valid[: 2 * B, :S] = True
    valid[2 * B + np.arange(B), row_lengths] = True
    valid = valid.reshape(-1)
    source = SafetensorsSource(path)
    at = np.asarray(row_lengths)

    def rows_of(lg) -> dict:
        return {
            "prefill_cold": lg[np.arange(B), at - 1], "prefill_chunked": lg[B : 2 * B, S - 1],
            "decode": lg[2 * B + np.arange(B), at],
        }

    def compare(got, choices, ref, valid=None) -> dict:
        want = rows_of(ref["logits"])
        out = {}
        for name in got:
            d = np.abs(got[name].astype(np.float64) - want[name].astype(np.float64))
            finite = bool(np.isfinite(got[name]).all() and np.isfinite(want[name]).all())
            out[name] = {
                "finite": finite, "max_abs": float(d.max()), "mean_abs": float(d.mean()),
                "ref_std": float(want[name].std()), "ref_max_abs": float(np.abs(want[name]).max()),
                "ok": finite and float(d.max()) <= LOGITS_MAX_ABS and float(d.mean()) <= LOGITS_MEAN_ABS,
            }
        ch = reference.choice_disagreements(choices, ref["choices"], ref["select"], valid)
        share = ch["disagree"] / max(ch["compared"], 1)
        out["router_choices"] = {
            **ch, "disagree_share": share,
            "ok": ch["worst_gap"] <= CHOICE_EPS and share <= CHOICE_DISAGREE_SHARE,
        }
        return out

    ref = reference.forward(source.get, hf, all_tokens, forced=forced)
    compared = compare(got, forced, ref, valid)
    dev = jax.devices()[0]
    result = {
        "platform": dev.platform, "kind": dev.device_kind,
        "kernel_route": {"flash": cfg.use_flash_prefill, "paged_kernel": cfg.use_paged_kernel},
        "layers": cfg.num_layers,
        "sample": {"rows": B, "prompt_tokens": S, "lengths": row_lengths},
        "tolerance": {
            "max_abs": LOGITS_MAX_ABS, "mean_abs": LOGITS_MEAN_ABS,
            "choice_eps": CHOICE_EPS, "choice_disagree_share": CHOICE_DISAGREE_SHARE,
        },
        "compared": compared,
        "ok": all(c["ok"] for c in compared.values()),
    }
    if serving.get("logits_control"):
        # The reference itself, every matmul's inputs rounded through
        # float8_e4m3fn, routed freely: held to the same limits.
        low = reference.forward(source.get, hf, all_tokens, dtype="float8_e4m3fn")
        control = compare(rows_of(low["logits"]), low["choices"], ref)
        result["control_float8"] = {**control, "ok": all(c["ok"] for c in control.values())}
    return result
