"""Device peaks and the operation/byte counts the roofline metrics divide
by. Kept with the benchmark so that no PR that claims a gain can move them.

Peaks: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
Keyed by `device_kind` exactly as the engine process reports it. A device
that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9, "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks on record for device kind {device_kind!r}; add it to "
            f"perfbench/peaks.py with its source (known: {sorted(PEAKS)})"
        )
    return PEAKS[device_kind]


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def param_count(cfg: dict) -> int:
    """Parameters of a dense Llama/Mistral/Qwen2 decoder (HF config keys)."""
    D, F, L, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    h = head_dim(cfg)
    q, kv = cfg["num_attention_heads"] * h, cfg["num_key_value_heads"] * h
    attn = D * q + 2 * D * kv + q * D
    if cfg.get("model_type") == "qwen2":
        attn += q + 2 * kv
    layer = attn + 3 * D * F + 2 * D
    head = 0 if cfg.get("tie_word_embeddings") else V * D
    return L * layer + V * D + head + D


def decode_weight_bytes(cfg: dict, weight_dtype_bytes: int) -> int:
    """Bytes of parameters one decode step must read: every matrix once,
    but of the embedding table only the rows of the batch (negligible)."""
    V, D = cfg["vocab_size"], cfg["hidden_size"]
    return (param_count(cfg) - V * D) * weight_dtype_bytes


def kv_bytes_per_token(cfg: dict, kv_dtype_bytes: int) -> int:
    return cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"] * head_dim(cfg) * kv_dtype_bytes


def decode_step_bytes(cfg: dict, weight_dtype_bytes: int, kv_dtype_bytes: int, live_kv_tokens: float) -> float:
    """Least bytes one decode step moves: the weights once and the live
    keys and values once. Activations and the written token are left out
    (under 1% at these sizes), so the share reads a little low, never high."""
    return decode_weight_bytes(cfg, weight_dtype_bytes) + live_kv_tokens * kv_bytes_per_token(cfg, kv_dtype_bytes)


def causal_attention_flops(cfg: dict, new_tokens: int, past_tokens: int = 0) -> float:
    """FLOPs of causal attention (QK^T and PV, 2 FLOPs a multiply-add) for
    *new_tokens* queries after *past_tokens* cached positions, all layers.
    Only the unmasked half of the new-by-new square is counted."""
    h = head_dim(cfg)
    pairs = new_tokens * past_tokens + new_tokens * (new_tokens + 1) / 2
    return cfg["num_hidden_layers"] * cfg["num_attention_heads"] * pairs * h * 2 * 2


def matmul_flops_per_token(cfg: dict) -> float:
    """2 FLOPs per parameter of the matrices a token passes through
    (the embedding lookup is not a matmul)."""
    return 2.0 * (param_count(cfg) - cfg["vocab_size"] * cfg["hidden_size"])
