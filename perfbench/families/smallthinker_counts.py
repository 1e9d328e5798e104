"""Operation and byte counts of `model_type: smallthinker`, from the
published config keys: what the roofline and `mfu` readers of its cells
divide by. Kept with the benchmark, beside the family file, so that no PR
that claims a gain can move them."""

from __future__ import annotations


def layer_kinds(hf: dict) -> tuple[int, int]:
    """(full layers, window layers) of the first `num_hidden_layers`."""
    window = sum(hf["sliding_window_layout"][: hf["num_hidden_layers"]])
    return hf["num_hidden_layers"] - window, window


def attention_params(hf: dict) -> int:
    """q, k, v, o and the two norms of a layer (21.0M as published)."""
    D, H, Kv, d = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    return D * (H + 2 * Kv) * d + H * d * D + 2 * D


def expert_params(hf: dict) -> int:
    """One routed expert (3 x 2560 x 768 = 5.90M)."""
    return 3 * hf["hidden_size"] * hf["moe_ffn_hidden_size"]


def router_params(hf: dict) -> int:
    return hf["hidden_size"] * hf["moe_num_primary_experts"]


def params_held(hf: dict) -> int:
    """Every parameter a chip holds: all experts, embedding and head
    (12 layers: 4.78G in layers + 0.78G outside)."""
    layer = attention_params(hf) + router_params(hf) + hf["moe_num_primary_experts"] * expert_params(hf)
    return 2 * hf["vocab_size"] * hf["hidden_size"] + hf["hidden_size"] + hf["num_hidden_layers"] * layer


def active_params(hf: dict) -> int:
    """Parameters a token is multiplied by: its chosen experts, attention,
    the router, the head; the embedding row is looked up, not multiplied
    (56.5M a layer; 12 layers: 0.68G + 0.39G)."""
    layer = attention_params(hf) + router_params(hf) + hf["moe_num_active_primary_experts"] * expert_params(hf)
    return hf["vocab_size"] * hf["hidden_size"] + hf["hidden_size"] + hf["num_hidden_layers"] * layer


def expert_bytes(hf: dict, weight_dtype_bytes: int) -> int:
    return expert_params(hf) * weight_dtype_bytes


def weights_outside_experts_bytes(hf: dict, weight_dtype_bytes: int) -> int:
    """What a decode step reads once whatever the routing: everything but
    the routed experts and the embedding table."""
    routed = hf["num_hidden_layers"] * hf["moe_num_primary_experts"] * expert_params(hf)
    return (params_held(hf) - routed - hf["vocab_size"] * hf["hidden_size"]) * weight_dtype_bytes


def kv_bytes_per_token_layer(hf: dict, kv_dtype_bytes: int) -> int:
    """Keys and values of one token in one layer (2 x 4 x 128 x 2 B = 2 KiB)."""
    return 2 * hf["num_key_value_heads"] * hf["head_dim"] * kv_dtype_bytes


def kv_bytes_per_token(hf: dict, kv_dtype_bytes: int) -> dict:
    """By kind of layer: what a token a FULL layer keeps for the whole
    context, and a WINDOW layer for the last `sliding_window_size`."""
    full, window = layer_kinds(hf)
    one = kv_bytes_per_token_layer(hf, kv_dtype_bytes)
    return {"full": full * one, "window": window * one}


def keys_in_mask(hf: dict, context: int) -> dict:
    """Keys a query behind *context* cached tokens sees in ONE layer of
    each kind (itself included)."""
    return {"full": context + 1, "window": min(context + 1, hf["sliding_window_size"])}


def attention_flops_per_pair(hf: dict) -> int:
    """FLOPs of one (query, key) pair inside the mask in one layer: the
    score and the weighted value, over every query head (4 x 28 x 128)."""
    return 4 * hf["num_attention_heads"] * hf["head_dim"]
