"""The harness's children: each is its own process, because each may import
jax and a chip belongs to one process at a time. `run.py` itself never
imports jax. Copies of chip_smoke.py's children (PR 21), made general over
the configuration file.

    python3 perfbench/children.py checkpoint <dir> <hf_config.json> <seed>
    python3 perfbench/children.py logits <dir> <seed>
    python3 perfbench/children.py trace <trace.xplane.pb> <platform> <seconds>

The last stdout line of each is its JSON result.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

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


def child_checkpoint(path: str, hf_config_path: str, seed: str) -> dict:
    """Seeded random weights as an HF safetensors directory beside the
    published config.json: one shard per layer, drawn on all cores (numpy
    draws outside the interpreter lock). No jax."""
    from concurrent.futures import ThreadPoolExecutor

    import ml_dtypes
    import numpy as np
    from safetensors.numpy import save_file

    with open(hf_config_path) as f:
        hf = json.load(f)
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf, f, indent=1)
    D, F, V, L = hf["hidden_size"], hf["intermediate_size"], hf["vocab_size"], hf["num_hidden_layers"]
    hd = hf.get("head_dim") or D // hf["num_attention_heads"]
    q, kv = hf["num_attention_heads"] * hd, hf["num_key_value_heads"] * hd
    biases = hf["model_type"] == "qwen2"  # modeling_qwen2 hardcodes q/k/v biases
    bf16 = ml_dtypes.bfloat16

    def draw(rng, *shape, scale):
        # Uniform on [-a, a] with a = scale * sqrt(3): the variance of a
        # normal(0, scale) at a third of the cost of drawing one.
        a = np.float32(scale * 3**0.5)
        x = rng.random(shape, dtype=np.float32)
        x *= 2 * a
        x -= a
        return x.astype(bf16)

    def layer(i: int) -> int:
        rng = np.random.default_rng([int(seed), i])
        p = f"model.layers.{i}."
        lin = lambda out, inp: draw(rng, out, inp, scale=inp**-0.5)  # noqa: E731
        tensors = {
            p + "input_layernorm.weight": np.ones((D,), bf16),
            p + "post_attention_layernorm.weight": np.ones((D,), bf16),
            p + "self_attn.q_proj.weight": lin(q, D),
            p + "self_attn.k_proj.weight": lin(kv, D),
            p + "self_attn.v_proj.weight": lin(kv, D),
            p + "self_attn.o_proj.weight": lin(D, q),
            p + "mlp.gate_proj.weight": lin(F, D),
            p + "mlp.up_proj.weight": lin(F, D),
            p + "mlp.down_proj.weight": lin(D, F),
        }
        if biases:
            tensors[p + "self_attn.q_proj.bias"] = draw(rng, q, scale=0.1)
            tensors[p + "self_attn.k_proj.bias"] = draw(rng, kv, scale=0.1)
            tensors[p + "self_attn.v_proj.bias"] = draw(rng, kv, scale=0.1)
        save_file(tensors, os.path.join(path, f"model-layer-{i:03d}.safetensors"))
        return sum(t.nbytes for t in tensors.values())

    def outside() -> int:
        rng = np.random.default_rng([int(seed), 10_000])
        tensors = {
            "model.embed_tokens.weight": draw(rng, V, D, scale=0.02),
            "model.norm.weight": np.ones((D,), bf16),
            "lm_head.weight": draw(rng, V, D, scale=0.02),
        }
        save_file(tensors, os.path.join(path, "model-outside-layers.safetensors"))
        return sum(t.nbytes for t in tensors.values())

    with ThreadPoolExecutor(max_workers=min(os.cpu_count() or 1, 12)) as pool:
        jobs = [pool.submit(outside)] + [pool.submit(layer, i) for i in range(L)]
        nbytes = sum(j.result() for j in jobs)
    return {"bytes": nbytes, "shards": L + 1}


def child_logits(path: str, seed: str) -> dict:
    """Prefill, chunked prefill and one decode step through the kernel
    route against the float32 portable route at highest precision, same
    weights through the same loader. The program's own code on both sides:
    it is the system under test and its own plain route."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.dirname(HERE))
    from kubeai_tpu.engine.coldstart import setup_compile_cache
    from kubeai_tpu.engine.core import EngineConfig
    from kubeai_tpu.engine.weights import load_engine_from_path
    from kubeai_tpu.models import llama

    setup_compile_cache()
    eng = load_engine_from_path(
        path, EngineConfig(max_slots=4, max_seq_len=512), quantization="int8",
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


def child_trace(path: str, platform: str, seconds: str) -> dict:
    sys.path.insert(0, HERE)
    import trace_reduce

    with open(os.path.join(HERE, "trace.json")) as f:
        spec = json.load(f)
    part = {**spec[platform], "profile_seconds": float(seconds)}
    planes, window, notes = trace_reduce.read_xplane(path, part)
    out = trace_reduce.reduce_events(planes, window)
    out["window_from"] = notes["window_from"]
    out["plane_names"] = [p["name"] for p in notes["planes"]]
    out["bytes"] = os.path.getsize(path)
    return out


CHILDREN = {"checkpoint": child_checkpoint, "logits": child_logits, "trace": child_trace}

if __name__ == "__main__":
    print(json.dumps(CHILDREN[sys.argv[1]](*sys.argv[2:])), flush=True)
