"""Operation and byte counts of `model_type: afmoe`, from the published
config keys: what the roofline and `mfu` readers of its cells divide by.
Kept with the benchmark, beside the family file, so that no PR that claims
a gain can move them."""

from __future__ import annotations


def layer_kinds(hf: dict) -> tuple[int, int]:
    """(full layers, window layers) of the first `num_hidden_layers`."""
    window = hf["layer_types"][: hf["num_hidden_layers"]].count("sliding_attention")
    return hf["num_hidden_layers"] - window, window


def layer_counts(hf: dict) -> tuple[int, int]:
    """(leading dense layers, expert layers)."""
    dense = min(hf["num_dense_layers"], hf["num_hidden_layers"])
    return dense, hf["num_hidden_layers"] - dense


def attention_params(hf: dict) -> int:
    """q, o and the output gate, k, v, the q/k norms and the layer's four
    norms (3 x 2048 x 4096 + 2 x 2048 x 512 + 2 x 128 + 4 x 2048 = 27.27M)."""
    D, H, Kv, d = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    return 3 * D * H * d + 2 * D * Kv * d + 2 * d + 4 * D


def dense_ffn_params(hf: dict) -> int:
    """A dense layer's feed-forward (3 x 2048 x 6144 = 37.75M)."""
    return 3 * hf["hidden_size"] * hf["intermediate_size"]


def expert_params(hf: dict) -> int:
    """One routed expert (3 x 2048 x 1024 = 6.29M)."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def expert_layer_outside_params(hf: dict) -> int:
    """An expert layer's router, selection bias and shared expert(s)."""
    return hf["hidden_size"] * hf["num_experts"] + hf["num_experts"] + hf["num_shared_experts"] * expert_params(hf)


def outside_layers_params(hf: dict) -> int:
    """Embedding, head and the final norm."""
    return 2 * hf["vocab_size"] * hf["hidden_size"] + hf["hidden_size"]


def params_held(hf: dict) -> int:
    """Every parameter a chip holds: all experts, embedding and head
    (8 layers: 5.16G in layers + 0.82G outside)."""
    dense, moe = layer_counts(hf)
    layers = hf["num_hidden_layers"] * attention_params(hf) + dense * dense_ffn_params(hf)
    layers += moe * (expert_layer_outside_params(hf) + hf["num_experts"] * expert_params(hf))
    return outside_layers_params(hf) + layers


def active_params(hf: dict) -> int:
    """Parameters a token is multiplied by: its chosen experts and the
    shared one, attention, the router, the head; the embedding row is
    looked up, not multiplied."""
    dense, moe = layer_counts(hf)
    layers = hf["num_hidden_layers"] * attention_params(hf) + dense * dense_ffn_params(hf)
    layers += moe * (expert_layer_outside_params(hf) + hf["num_experts_per_tok"] * expert_params(hf))
    return hf["vocab_size"] * hf["hidden_size"] + hf["hidden_size"] + layers


def expert_bytes(hf: dict, weight_dtype_bytes: int) -> int:
    return expert_params(hf) * weight_dtype_bytes


def weights_outside_experts_bytes(hf: dict, weight_dtype_bytes: int) -> int:
    """What a decode step reads once whatever the routing: everything but
    the routed experts and the embedding table."""
    routed = layer_counts(hf)[1] * hf["num_experts"] * expert_params(hf)
    return (params_held(hf) - routed - hf["vocab_size"] * hf["hidden_size"]) * weight_dtype_bytes


def kv_bytes_per_token_layer(hf: dict, kv_dtype_bytes: int) -> int:
    """Keys and values of one token in one layer (2 x 4 x 128 x 2 B = 2 KiB)."""
    return 2 * hf["num_key_value_heads"] * hf["head_dim"] * kv_dtype_bytes


def kv_bytes_per_token(hf: dict, kv_dtype_bytes: int) -> dict:
    """By kind of layer: what a token a FULL layer keeps for the whole
    context, and a WINDOW layer for the last `sliding_window`."""
    full, window = layer_kinds(hf)
    one = kv_bytes_per_token_layer(hf, kv_dtype_bytes)
    return {"full": full * one, "window": window * one}


def attention_flops_per_pair(hf: dict) -> int:
    """FLOPs of one (query, key) pair inside the mask in one layer: the
    score and the weighted value, over every query head (4 x 32 x 128)."""
    return 4 * hf["num_attention_heads"] * hf["head_dim"]
