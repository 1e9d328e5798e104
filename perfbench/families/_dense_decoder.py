"""The dense full-attention decoder the program runs through
`models/llama.py` (Llama, Mistral, Qwen2): its checkpoint plan and its
logits check, for the family files that are such a decoder. What differs
between them they say themselves (`qwen2.py`: q/k/v biases)."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Kernel route (bf16 activations, flash + ragged paged kernels) against the
# portable route in float32 at highest matmul precision, on the same int8
# weights. Why these bounds (chip_smoke.py, measured in PR 21 at Qwen2.5-7B
# widths and 4 layers): the logits of a random model have a standard
# deviation near 1.2 and reach 5-6; bf16 keeps 8 bits, so a logit of that
# size is rounded to 1/32, and every layer below rounds its activations the
# same way: max 0.060 / mean 0.0096 on the chip. The bounds leave about
# three times that, and computing in a lower precision than stated (an fp8
# pool, int8 activations) fails them.
LOGITS_MAX_ABS = 0.25
LOGITS_MEAN_ABS = 0.04


def layer_plan(hf: dict, i: int, qkv_bias: bool = False) -> list[tuple]:
    D, F = hf["hidden_size"], hf["intermediate_size"]
    hd = hf.get("head_dim") or D // hf["num_attention_heads"]
    q, kv = hf["num_attention_heads"] * hd, hf["num_key_value_heads"] * hd
    p = f"model.layers.{i}."
    lin = lambda name, out, inp: (p + name + ".weight", (out, inp), inp**-0.5)  # noqa: E731
    plan = [
        (p + "input_layernorm.weight", (D,), None),
        (p + "post_attention_layernorm.weight", (D,), None),
        lin("self_attn.q_proj", q, D),
        lin("self_attn.k_proj", kv, D),
        lin("self_attn.v_proj", kv, D),
        lin("self_attn.o_proj", D, q),
        lin("mlp.gate_proj", F, D),
        lin("mlp.up_proj", F, D),
        lin("mlp.down_proj", D, F),
    ]
    if qkv_bias:
        plan += [
            (p + "self_attn.q_proj.bias", (q,), 0.1),
            (p + "self_attn.k_proj.bias", (kv,), 0.1),
            (p + "self_attn.v_proj.bias", (kv,), 0.1),
        ]
    return plan


def outside_plan(hf: dict) -> list[tuple]:
    D, V = hf["hidden_size"], hf["vocab_size"]
    return [
        ("model.embed_tokens.weight", (V, D), 0.02),
        ("model.norm.weight", (D,), None),
        ("lm_head.weight", (V, D), 0.02),
    ]


def logits(path: str, seed: str, serving: dict) -> dict:
    """Prefill, chunked prefill and one decode step through the kernel
    route against the float32 portable route at highest precision, same
    weights through the same loader. The program's own code on both sides:
    it is the system under test and its own plain route."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, ROOT)
    from kubeai_tpu.engine.coldstart import setup_compile_cache
    from kubeai_tpu.engine.core import EngineConfig
    from kubeai_tpu.engine.weights import load_engine_from_path
    from kubeai_tpu.models import llama

    setup_compile_cache()
    args = serving["engine_args"]
    quantization = args[args.index("--quantization") + 1] if "--quantization" in args else ""
    eng = load_engine_from_path(
        path, EngineConfig(max_slots=4, max_seq_len=512), quantization=quantization,
        overlap=False, warmup=False,
    )
    params, kcfg = eng.params, eng.model_config
    rcfg = kcfg.replace(dtype="float32", use_flash_prefill=False, use_paged_kernel=False)
    B, S, page, max_pages = 4, 256, 64, 8
    # numpy's seeds are any non-negative whole number: no 32-bit limit.
    rng = np.random.default_rng(int(seed))
    tokens = jnp.asarray(rng.integers(0, 259, (B, S)), jnp.int32)
    nxt = jnp.asarray(rng.integers(0, 259, (B, 1)), jnp.int32)
    row_lengths = [256, 200, 256, 131]
    lengths = jnp.asarray(row_lengths, jnp.int32)
    tables = jnp.asarray(1 + np.arange(B * max_pages, dtype=np.int32).reshape(B, max_pages))
    half = jnp.full((B,), S // 2, jnp.int32)

    def route(cfg):
        pool = llama.init_paged_cache(cfg, B * max_pages + 1, page)
        cold, pool = jax.jit(
            lambda p, t, c: llama.prefill_paged_cold(p, cfg, t, c, tables, lengths)
        )(params, tokens, pool)
        step, _ = jax.jit(
            lambda p, t, c: llama.decode_step_paged(p, cfg, t, c, tables, lengths)
        )(params, nxt, pool)
        chunk = jax.jit(
            lambda p, t, c, start, last: llama.prefill_paged(p, cfg, t, c, tables, start, last)
        )
        pool2 = llama.init_paged_cache(cfg, B * max_pages + 1, page)
        _, pool2 = chunk(params, tokens[:, : S // 2], pool2, 0 * half, half - 1)
        chunked, _ = chunk(params, tokens[:, S // 2 :], pool2, half, half - 1)
        return {
            "prefill_cold": np.asarray(cold[:, 0]),
            "prefill_chunked": np.asarray(chunked[:, 0]),
            "decode": np.asarray(step[:, 0]),
        }

    got = route(kcfg)
    with jax.default_matmul_precision("highest"):
        want = route(rcfg)
        # The chunked prefill ends at position S-1 for every row, the cold
        # one at each row's own length: compare like with like.
        pool = llama.init_paged_cache(rcfg, B * max_pages + 1, page)
        full = jnp.full((B,), S, jnp.int32)
        want_full, _ = jax.jit(
            lambda p, t, c: llama.prefill_paged_cold(p, rcfg, t, c, tables, full)
        )(params, tokens, pool)
    want["prefill_chunked"] = np.asarray(want_full[:, 0])
    compared = {}
    for name in got:
        d = np.abs(got[name].astype(np.float64) - want[name].astype(np.float64))
        finite = bool(np.isfinite(got[name]).all() and np.isfinite(want[name]).all())
        compared[name] = {
            "finite": finite, "max_abs": float(d.max()), "mean_abs": float(d.mean()),
            "ref_std": float(want[name].std()), "ref_max_abs": float(np.abs(want[name]).max()),
            "ok": finite and float(d.max()) <= LOGITS_MAX_ABS and float(d.mean()) <= LOGITS_MEAN_ABS,
        }
    dev = jax.devices()[0]
    return {
        "platform": dev.platform, "kind": dev.device_kind,
        "kernel_route": {"flash": kcfg.use_flash_prefill, "paged_kernel": kcfg.use_paged_kernel},
        "layers": kcfg.num_layers,
        "sample": {"rows": B, "prompt_tokens": S, "lengths": row_lengths},
        "tolerance": {"max_abs": LOGITS_MAX_ABS, "mean_abs": LOGITS_MEAN_ABS},
        "compared": compared,
        "ok": all(c["ok"] for c in compared.values()),
    }
