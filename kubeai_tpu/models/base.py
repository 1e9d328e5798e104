"""Model configuration shared by all model families."""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass

from kubeai_tpu.ops.rope import RopeScaling


@dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int | None = None  # defaults to hidden_size // num_heads
    rope_theta: float = 10000.0
    rope_scaling: RopeScaling | None = None
    rms_norm_eps: float = 1e-5
    max_position: int = 8192
    tie_word_embeddings: bool = False
    # MoE (Mixtral-style); num_experts == 0 means dense.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # Static per-expert capacity = ceil(k*T/E * factor); tokens routed past
    # it are dropped (GShard semantics). Raise for exactness at the cost of
    # padding compute.
    moe_capacity_factor: float = 2.0
    # Architecture variants (Gemma family / Qwen2).
    qkv_bias: bool = False  # Qwen2-style biases on q/k/v projections
    hidden_act: str = "silu"  # "silu" | "gelu_tanh"
    embed_scale: bool = False  # multiply embeddings by sqrt(hidden)
    rms_one_offset: bool = False  # RMSNorm weight is (1 + w)
    post_norms: bool = False  # Gemma2 post-attention/post-ffn norms
    attn_softcap: float = 0.0  # 0 = disabled
    logit_softcap: float = 0.0
    query_scale: float | None = None  # attention scale override
    # Sliding-window attention: window size (0 = disabled) and which
    # layers it applies to ("all", or "even" for Gemma2's interleave).
    sliding_window: int = 0
    sliding_layers: str = "all"
    # Use the Pallas flash-attention kernel for prefill (set by the engine
    # on TPU; only valid without softcap/sliding-window).
    use_flash_prefill: bool = False
    # Use the ragged paged-attention kernel over the paged KV pool for
    # decode and chunked prefill (set by the engine on TPU; only valid
    # without sliding-window — softcap is supported). The portable path
    # gathers pages via XLA; on CPU the kernel path runs a jit-safe
    # semantics twin.
    use_paged_kernel: bool = False
    dtype: str = "bfloat16"
    # Paged KV pool storage dtype: "" keeps the compute dtype; "fp8"
    # stores float8_e4m3fn (scale-free: clip to +-448, the format's
    # finite range, covers K/V activations with margin); "int8" stores
    # round(x/scale) with the static per-tensor scales below (calibrate:
    # kv_scale ~= absmax/127). Halves KV HBM either way — the slot-count
    # ceiling (and therefore decode throughput, which is weight-read
    # bound until slots saturate it) is KV-capacity-limited on 16GB v5e
    # (VERDICT r3: 64 bf16 slots OOM'd). The ragged paged-attention
    # kernel dequantizes pages in-VMEM (k_scale/v_scale), so the HBM
    # read traffic halves too.
    kv_cache_dtype: str = ""
    kv_scale_k: float = 1.0
    kv_scale_v: float = 1.0

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @classmethod
    def from_hf(cls, config) -> "ModelConfig":
        """Build from a transformers PretrainedConfig (Llama/Mistral/Mixtral/
        Gemma/Qwen2-style field names)."""
        get = lambda k, d=None: getattr(config, k, d)
        scaling = None
        rs = get("rope_scaling")
        if isinstance(rs, dict):
            rope_type = rs.get("rope_type", rs.get("type"))
            if rope_type == "llama3":
                scaling = RopeScaling(
                    factor=rs.get("factor", 8.0),
                    low_freq_factor=rs.get("low_freq_factor", 1.0),
                    high_freq_factor=rs.get("high_freq_factor", 4.0),
                    original_max_position=rs.get("original_max_position_embeddings", 8192),
                )
            elif rope_type in ("default", None):
                pass
            elif rope_type == "linear":
                # Linear scaling divides every band by factor; expressed as
                # llama3-style scaling with the "low frequency" (always
                # scaled) band covering the whole spectrum: low_freq_factor
                # huge makes low_wavelen ~0 so wavelen > low_wavelen for all
                # bands.
                scaling = RopeScaling(
                    factor=rs.get("factor", 1.0),
                    low_freq_factor=1e9,
                    high_freq_factor=2e9,
                    original_max_position=get("max_position_embeddings", 8192),
                )
            else:
                raise ValueError(
                    f"unsupported rope_scaling type {rope_type!r}; "
                    "supported: llama3, linear"
                )
        model_type = get("model_type", "llama")
        gemma_kw = {}
        if model_type == "qwen2":
            # Qwen2 hardcodes q/k/v projection biases (modeling_qwen2).
            gemma_kw["qkv_bias"] = True
        if model_type in ("gemma", "gemma2"):
            gemma_kw = dict(
                hidden_act="gelu_tanh",
                embed_scale=True,
                rms_one_offset=True,
            )
            if model_type == "gemma2":
                gemma_kw.update(
                    post_norms=True,
                    attn_softcap=get("attn_logit_softcapping", 50.0) or 0.0,
                    logit_softcap=get("final_logit_softcapping", 30.0) or 0.0,
                    query_scale=(get("query_pre_attn_scalar") or 0) ** -0.5
                    if get("query_pre_attn_scalar")
                    else None,
                    # HF Gemma2 applies the window on even layer indices.
                    sliding_window=get("sliding_window") or 0,
                    sliding_layers="even",
                )
        return cls(
            **gemma_kw,
            vocab_size=config.vocab_size,
            hidden_size=config.hidden_size,
            intermediate_size=get("intermediate_size") or get("ffn_dim"),
            num_layers=get("num_hidden_layers"),
            num_heads=get("num_attention_heads"),
            num_kv_heads=get("num_key_value_heads") or get("num_attention_heads"),
            head_dim=get("head_dim"),
            rope_theta=get("rope_theta", 10000.0),
            rope_scaling=scaling,
            rms_norm_eps=get("rms_norm_eps", 1e-5),
            max_position=get("max_position_embeddings", 8192),
            tie_word_embeddings=bool(get("tie_word_embeddings", False)),
            num_experts=get("num_local_experts", 0) or 0,
            num_experts_per_tok=get("num_experts_per_tok", 2) or 2,
        )

    @classmethod
    def from_json_file(cls, path: str) -> "ModelConfig":
        """Load from an HF-format config.json on disk (no transformers needed)."""
        with open(os.path.join(path, "config.json") if os.path.isdir(path) else path) as f:
            raw = json.load(f)

        class _Obj:
            def __init__(self, d):
                self.__dict__.update(d)

        return cls.from_hf(_Obj(raw))

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)
