"""Operation and byte counts of `model_type: nemotron_h`, from the published
config keys: what the roofline and `mfu` readers of its cells divide by.
Kept with the benchmark, beside the family file, so that no PR that claims
a gain can move them. `n_routed_experts` is what THIS chip holds; where the
config gives `router_experts` the router is that wide and a token's
`num_experts_per_tok` choices land here in the ratio of the two."""

from __future__ import annotations


def kinds(hf: dict) -> dict[str, int]:
    """Blocks of each kind among the first `num_hidden_layers`."""
    pattern = hf["hybrid_override_pattern"][: hf["num_hidden_layers"]]
    return {k: pattern.count(k) for k in "M*E"}


def router_width(hf: dict) -> int:
    return hf.get("router_experts") or hf["n_routed_experts"]


def conv_channels(hf: dict) -> int:
    return hf["mamba_num_heads"] * hf["mamba_head_dim"] + 2 * hf["n_groups"] * hf["ssm_state_size"]


def mixer_params(hf: dict) -> int:
    """One `M` block: in_proj, the convolution and its bias, A_log, D,
    dt_bias, the gated norm, out_proj and the block's norm (109.6M)."""
    D, Hm = hf["hidden_size"], hf["mamba_num_heads"]
    inner, C = Hm * hf["mamba_head_dim"], conv_channels(hf)
    return D * (inner + C + Hm) + C * hf["conv_kernel"] + C + 3 * Hm + inner + inner * D + D


def attention_params(hf: dict) -> int:
    """One `*` block: q, k, v, o and the block's norm (35.7M)."""
    D, H, Kv, d = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    return D * (H + 2 * Kv) * d + H * d * D + D


def expert_params(hf: dict) -> int:
    """One routed expert, no gate matrix (2 x 1024 x 2688 = 5.505M)."""
    return 2 * hf["moe_latent_size"] * hf["moe_intermediate_size"]


def expert_block_outside_params(hf: dict) -> int:
    """An `E` block without its routed experts: the router and its bias,
    the two latent projections, the shared expert, the norm (54.5M)."""
    D, R = hf["hidden_size"], router_width(hf)
    return D * R + R + 2 * D * hf["moe_latent_size"] + 2 * D * hf["moe_shared_expert_intermediate_size"] + D


def experts_per_token_here(hf: dict) -> float:
    """Of a token's choices, how many land on this chip's experts on
    average (22 x 128 / 512 = 5.5)."""
    return hf["num_experts_per_tok"] * hf["n_routed_experts"] / router_width(hf)


def params_held(hf: dict) -> int:
    """Every parameter this chip holds (11 blocks, 128 experts a block, a
    quarter of the vocabulary: 4.38G in blocks + 0.27G outside)."""
    n = kinds(hf)
    blocks = (
        n["M"] * mixer_params(hf) + n["*"] * attention_params(hf)
        + n["E"] * (expert_block_outside_params(hf) + hf["n_routed_experts"] * expert_params(hf))
    )
    return 2 * hf["vocab_size"] * hf["hidden_size"] + hf["hidden_size"] + blocks


def active_params(hf: dict) -> float:
    """Parameters a token is multiplied by ON THIS CHIP: the mixers, the
    attention block, each `E` block's router, projections and shared expert,
    the experts of its choices that are here, the head; the embedding row
    is looked up, not multiplied."""
    n = kinds(hf)
    blocks = (
        n["M"] * mixer_params(hf) + n["*"] * attention_params(hf)
        + n["E"] * (expert_block_outside_params(hf) + experts_per_token_here(hf) * expert_params(hf))
    )
    return hf["vocab_size"] * hf["hidden_size"] + hf["hidden_size"] + blocks


def expert_bytes(hf: dict, weight_dtype_bytes: int) -> int:
    return expert_params(hf) * weight_dtype_bytes


def weights_outside_experts_bytes(hf: dict, weight_dtype_bytes: int) -> int:
    """What a decode step reads once whatever the routing: everything but
    the routed experts and the embedding table."""
    routed = kinds(hf)["E"] * hf["n_routed_experts"] * expert_params(hf)
    return (params_held(hf) - routed - hf["vocab_size"] * hf["hidden_size"]) * weight_dtype_bytes


def state_bytes_per_slot_block(hf: dict, act_dtype_bytes: int) -> dict:
    """What ONE `M` block keeps for one slot: the float32 state (4.19 MB)
    and the convolution's tail (61 KB in bf16)."""
    return {
        "state": hf["mamba_num_heads"] * hf["mamba_head_dim"] * hf["ssm_state_size"] * 4,
        "tail": (hf["conv_kernel"] - 1) * conv_channels(hf) * act_dtype_bytes,
    }


def state_bytes_per_slot(hf: dict, act_dtype_bytes: int) -> int:
    return kinds(hf)["M"] * sum(state_bytes_per_slot_block(hf, act_dtype_bytes).values())


def ssm_decode_bytes_per_row(hf: dict, act_dtype_bytes: int) -> int:
    """What a decode step of one live row must move through all `M`
    blocks' `ssm.conv` + `ssm.scan`: state and tail read AND written."""
    return 2 * state_bytes_per_slot(hf, act_dtype_bytes)


def ssm_chunked_flops_per_token(hf: dict) -> int:
    """The chunked form's FLOPs a token in ONE `M` block: the chunk's
    scores C.B (2 Q N a group), the masked product with x (2 Q P a head),
    the read of the carried state and the token's part of the chunk's state
    (2 N P a head each); 6.55M at the published sizes."""
    Q, N, G, Hm, P = hf["chunk_size"], hf["ssm_state_size"], hf["n_groups"], hf["mamba_num_heads"], hf["mamba_head_dim"]
    return 2 * Q * N * G + 2 * Q * P * Hm + 4 * N * P * Hm


def ssm_recurrence_flops_per_token(hf: dict) -> int:
    """What the recurrence itself asks a token in ONE `M` block (decay,
    outer product, add: 3 a state element; the read through C: 2): the
    least any form computes, which is what an `mfu` counts."""
    return 5 * hf["mamba_num_heads"] * hf["mamba_head_dim"] * hf["ssm_state_size"]


def kv_bytes_per_token_layer(hf: dict, kv_dtype_bytes: int) -> int:
    """Keys and values of one token in one `*` block (2 x 2 x 128 x 2 B = 1 KiB)."""
    return 2 * hf["num_key_value_heads"] * hf["head_dim"] * kv_dtype_bytes


def kv_bytes_per_token(hf: dict, kv_dtype_bytes: int) -> int:
    return kinds(hf)["*"] * kv_bytes_per_token_layer(hf, kv_dtype_bytes)


def attention_flops(hf: dict, n: int, context: float = 0.0) -> float:
    """FLOPs of the `*` blocks' scores and weighted values for *n* queries
    behind *context* cached tokens: 4 x heads x head_dim a (query, key)
    pair inside the causal mask."""
    pairs = n * context + n * (n + 1) / 2.0
    return kinds(hf)["*"] * 4.0 * hf["num_attention_heads"] * hf["head_dim"] * pairs
