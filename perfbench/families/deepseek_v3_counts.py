"""Operation and byte counts of `model_type: deepseek_v3` (no query
low-rank), from the published config keys: what the roofline and `mfu`
readers of its cells divide by. Kept with the benchmark, beside the
family file, so that no PR that claims a gain can move them.
(`perfbench/peaks.py::param_count` is the dense decoder's.)"""

from __future__ import annotations


def _layers(hf: dict) -> tuple[int, int]:
    dense = min(hf["first_k_dense_replace"], hf["num_hidden_layers"])
    return dense, hf["num_hidden_layers"] - dense


def attention_params(hf: dict) -> int:
    D, H = hf["hidden_size"], hf["num_attention_heads"]
    dn, dr, dv, r = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"], hf["kv_lora_rank"]
    return D * H * (dn + dr) + D * (r + dr) + r + r * H * (dn + dv) + H * dv * D + 2 * D


def expert_params(hf: dict) -> int:
    """One routed expert (kanana-2: 3 x 2048 x 768 = 4.72M)."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def params_held(hf: dict) -> int:
    """Every parameter a chip holds: all experts, embedding and head."""
    D, V, E = hf["hidden_size"], hf["vocab_size"], hf["n_routed_experts"]
    n_dense, n_moe = _layers(hf)
    dense = attention_params(hf) + 3 * D * hf["intermediate_size"]
    moe = attention_params(hf) + D * E + E + (E + hf["n_shared_experts"]) * expert_params(hf)
    return 2 * V * D + D + n_dense * dense + n_moe * moe


def active_params(hf: dict) -> int:
    """Parameters a token is multiplied by: its `num_experts_per_tok`
    routed experts, the shared ones, attention, the router, the head; the
    embedding row is looked up, not multiplied (kanana-2 at 8 layers:
    64.1M + 7 x 64.4M + 262.7M = 0.78G)."""
    D, V, E = hf["hidden_size"], hf["vocab_size"], hf["n_routed_experts"]
    n_dense, n_moe = _layers(hf)
    dense = attention_params(hf) + 3 * D * hf["intermediate_size"]
    moe = attention_params(hf) + D * E + E + (hf["num_experts_per_tok"] + hf["n_shared_experts"]) * expert_params(hf)
    return V * D + D + n_dense * dense + n_moe * moe


def expert_bytes(hf: dict, weight_dtype_bytes: int) -> int:
    return expert_params(hf) * weight_dtype_bytes


def weights_outside_experts_bytes(hf: dict, weight_dtype_bytes: int) -> int:
    """What a decode step reads once whatever the routing: everything but
    the routed experts and the embedding table."""
    _, n_moe = _layers(hf)
    routed = n_moe * hf["n_routed_experts"] * expert_params(hf)
    return (params_held(hf) - routed - hf["vocab_size"] * hf["hidden_size"]) * weight_dtype_bytes


def latent_bytes_per_token(hf: dict, kv_dtype_bytes: int) -> int:
    """What a token caches, all layers: kv_lora_rank + qk_rope_head_dim
    values a layer (kanana-2: 576 x 2 B x 8 = 9.2 KB). The padding a
    program stores beside them is not counted."""
    return hf["num_hidden_layers"] * (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * kv_dtype_bytes


def attention_flops(hf: dict, new_tokens: int, past_tokens: float = 0) -> float:
    """FLOPs of causal attention for *new_tokens* queries behind
    *past_tokens* cached ones, all layers, in the EXPANDED form (keys of
    dn+dr, values of dv: the fewest the mathematics needs; the absorbed
    form a program may run costs 3.4 times as many and is not credited)."""
    pairs = new_tokens * past_tokens + new_tokens * (new_tokens + 1) / 2
    per_pair = 2 * (hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"] + hf["v_head_dim"])
    return hf["num_hidden_layers"] * hf["num_attention_heads"] * pairs * per_pair
