"""Operation and byte counts of `model_type: lfm2_moe`, from the published
config keys: what the roofline and `mfu` readers of its cells divide by
(`readers/family_rooflines.py` takes this module by the configuration's
`model_type`, and what each share divides from `ROOFLINES` at the foot of it:
the reader holds no operator's name). Kept with the benchmark, beside the family file, so that no
PR that claims a gain can move them. They count the PUBLISHED work (keys and
values 64 wide, every expert read once a step when hit, an operator's
weights once), whatever implements it: a padded layout or a fallback route
reads as a lower share, never as a higher one."""

from __future__ import annotations

# What the family's step is traced under (kubeai_tpu/models/lfm2_moe.py,
# ops/shortconv.py, ops/moe.py), for perfbench/scope_reduce.py: it files a
# component `a.b` under the FIRST of these that is `a.b` or a prefix `a`, so
# the longer names stand before the shorter.
CONV = ("conv.in_proj", "conv.gate", "conv.taps", "conv.out_proj", "conv")
SCOPES = (
    "embed", *CONV, "attn.qk_norm", "attn.kernel", "attn", "ffn", "moe.router", "moe.dispatch", "moe.experts",
    "moe.combine", "moe", "lm_head", "sampling", "logprobs",
)
# Scopes a program of this family carries and no other family's does: a
# reader finds nothing to read in a trace without them.
OWN_SCOPES = CONV


def kinds(hf: dict) -> dict[str, int]:
    """Layers of each operator among the first `num_hidden_layers`."""
    types = hf["layer_types"][: hf["num_hidden_layers"]]
    return {"conv": types.count("conv"), "attn": types.count("full_attention")}


def layer_counts(hf: dict) -> tuple[int, int]:
    """(leading dense layers, expert layers)."""
    dense = min(hf["num_dense_layers"], hf["num_hidden_layers"])
    return dense, hf["num_hidden_layers"] - dense


def head_dim(hf: dict) -> int:
    return hf["hidden_size"] // hf["num_attention_heads"]


def conv_params(hf: dict) -> int:
    """One gated short convolution: in_proj, the taps, out_proj and the
    operator's norm (2048 x 6144 + 3 x 2048 + 2048 x 2048 + 2048 = 16.79M)."""
    D = hf["hidden_size"]
    return D * 3 * D + hf["conv_L_cache"] * D + D * D + D


def attention_params(hf: dict) -> int:
    """One attention operator: q and out, k and v, the q/k norms and the
    operator's norm (2 x 2048 x 2048 + 2 x 2048 x 512 + 2 x 64 + 2048 = 10.49M)."""
    D, H, Kv, d = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"], head_dim(hf)
    return 2 * D * H * d + 2 * D * Kv * d + 2 * d + D


def dense_ffn_params(hf: dict) -> int:
    """A dense layer's feed-forward and its norm (3 x 2048 x 11776 + 2048 = 72.35M)."""
    return 3 * hf["hidden_size"] * hf["intermediate_size"] + hf["hidden_size"]


def expert_params(hf: dict) -> int:
    """One routed expert (3 x 2048 x 1536 = 9.44M)."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def expert_layer_outside_params(hf: dict) -> int:
    """An expert layer's router, selection bias and norm (no shared expert)."""
    return hf["hidden_size"] * hf["num_experts"] + hf["num_experts"] + hf["hidden_size"]


def outside_layers_params(hf: dict) -> int:
    """The embedding, which is the head too (tied), and the final norm."""
    return hf["vocab_size"] * hf["hidden_size"] + hf["hidden_size"]


def _layers_without_experts(hf: dict) -> int:
    n, (dense, moe) = kinds(hf), layer_counts(hf)
    return n["conv"] * conv_params(hf) + n["attn"] * attention_params(hf) + dense * dense_ffn_params(hf) + moe * expert_layer_outside_params(hf)


def params_held(hf: dict) -> int:
    """Every parameter a chip holds: all experts, the embedding once
    (10 layers: 5.13G in layers + 0.13G outside; 40 layers: 23.84G)."""
    return outside_layers_params(hf) + _layers_without_experts(hf) + layer_counts(hf)[1] * hf["num_experts"] * expert_params(hf)


def active_params(hf: dict) -> int:
    """Parameters a token is multiplied by: its chosen experts, its
    operators, the router, the head (which is the embedding: its row is
    looked up going in and the whole of it multiplied going out, so it
    counts once). 10 layers: 0.74G; 40 layers: 2.33G."""
    return outside_layers_params(hf) + _layers_without_experts(hf) + layer_counts(hf)[1] * hf["num_experts_per_tok"] * expert_params(hf)


def expert_bytes(hf: dict, weight_dtype_bytes: int) -> int:
    return expert_params(hf) * weight_dtype_bytes


def weights_outside_experts_bytes(hf: dict, weight_dtype_bytes: int) -> int:
    """What a decode step reads once whatever the routing: everything but
    the routed experts (the embedding is the head and is read as such)."""
    return (outside_layers_params(hf) + _layers_without_experts(hf)) * weight_dtype_bytes


def conv_weights_bytes(hf: dict, weight_dtype_bytes: int) -> int:
    """All convolution operators' weights, read once a decode step."""
    return kinds(hf)["conv"] * conv_params(hf) * weight_dtype_bytes


def state_bytes_per_slot(hf: dict, act_dtype_bytes: int) -> int:
    """What a slot owns outside its pages: the last K - 1 rows of `z` in
    every convolution (2 x 2048 x 2 B = 8 KiB a layer)."""
    return kinds(hf)["conv"] * (hf["conv_L_cache"] - 1) * hf["hidden_size"] * act_dtype_bytes


def conv_decode_bytes_per_row(hf: dict, act_dtype_bytes: int) -> int:
    """What a decode step of one live row must move through all
    convolutions: the tail read AND written."""
    return 2 * state_bytes_per_slot(hf, act_dtype_bytes)


def conv_projection_flops_per_token(hf: dict) -> int:
    """The two projections of ONE convolution a token (2 x 2048 x (6144 +
    2048) = 33.6M); the taps and the gates are a few thousand more."""
    D = hf["hidden_size"]
    return 2 * D * (3 * D + D)


def kv_bytes_per_token_layer(hf: dict, kv_dtype_bytes: int) -> int:
    """Keys and values of one token in one attention layer (2 x 8 x 64 x 2 B = 2 KiB)."""
    return 2 * hf["num_key_value_heads"] * head_dim(hf) * kv_dtype_bytes


def kv_bytes_per_token(hf: dict, kv_dtype_bytes: int) -> int:
    return kinds(hf)["attn"] * kv_bytes_per_token_layer(hf, kv_dtype_bytes)


def attention_flops_per_pair(hf: dict) -> int:
    """FLOPs of one (query, key) pair inside the mask in one layer: the
    score and the weighted value, over every query head (4 x 32 x 64)."""
    return 4 * hf["num_attention_heads"] * head_dim(hf)


def attention_pairs(n: int, context: float = 0.0) -> float:
    """(query, key) pairs inside the causal mask for *n* queries behind
    *context* cached tokens, in one layer."""
    return n * context + n * (n + 1) / 2.0


def attention_flops(hf: dict, n: int, context: float = 0.0) -> float:
    """FLOPs of the attention layers' scores and weighted values for *n*
    queries behind *context* cached tokens."""
    return kinds(hf)["attn"] * attention_flops_per_pair(hf) * attention_pairs(n, context)


# ---------------------------------------------------------------------------
# What each share of a peak divides, for readers/family_rooflines.py (whose
# docstring says what it measures for each unit of work): the scopes whose
# time it is (None: the whole program), the peak it is a share of, and the
# work as `unit -> count(hf, serving)`.

_weights = lambda count: lambda hf, serving: count(hf, serving["weight_dtype_bytes"])  # noqa: E731
_tails = _weights(conv_decode_bytes_per_row)  # the tails are kept in the compute dtype, which is the weights'
_kv = lambda hf, serving: kv_bytes_per_token(hf, serving["kv_dtype_bytes"])  # noqa: E731
_experts = _weights(lambda hf, b: layer_counts(hf)[1] * hf["num_experts"] * expert_bytes(hf, b))  # every expert of every expert layer


def _decode(scopes, **work):
    """Memory-bound: bytes a decode step must move, over the peak bytes/s."""
    return {"phase": "decode", "peak": "hbm_bytes_per_s", "scopes": scopes, "work": work}


def _prefill(scopes, **work):
    """Compute-bound: FLOPs of the prefill calls, over the peak bf16 FLOP/s."""
    return {"phase": "prefill", "peak": "bf16_flops", "scopes": scopes, "work": work}


ROOFLINES = {
    # weights outside the routed experts once + the experts hit + the live rows' tails in and out + live keys and values
    "decode_step": _decode(None, step=_weights(weights_outside_experts_bytes), experts_hit_share=_experts, live_row=_tails, kv_token=_kv),
    "experts": _decode(("moe.experts",), experts_hit_share=_experts),
    # the convolutions' weights once + the live rows' tails read and written
    "conv_decode": _decode(CONV, step=_weights(conv_weights_bytes), live_row=_tails),
    # live keys and values at the PUBLISHED width
    "attn_decode": _decode(("attn.kernel",), kv_token=_kv),
    # the two projections of every convolution for the real tokens
    "conv_prefill": _prefill(CONV, prompt_token=lambda hf, serving: kinds(hf)["conv"] * conv_projection_flops_per_token(hf)),
    # 4 x heads x head_dim FLOPs a pair inside the mask of each prompt
    "prefill_attn": _prefill(("attn.kernel",), prompt=lambda hf, serving, n: attention_flops(hf, n)),
}
