"""Model configuration shared by all model families."""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass
from typing import NamedTuple

import jax.numpy as jnp

from kubeai_tpu.ops.rope import RopeScaling


@dataclass(frozen=True)
class ModelConfig:
    # The family: which model module runs the configuration
    # (kubeai_tpu/models/__init__.py::family). From the published
    # config.json's `model_type`; nothing else selects a module.
    model_type: str = "llama"
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
    # DeepSeek-V3 family (model_type "deepseek_v3", models/deepseek.py;
    # read for that family only, so the same keys on another family's
    # config.json stay ignored). Experts: the first
    # `first_k_dense_replace` layers are dense (intermediate_size), the
    # rest hold `n_routed_experts` of width `moe_intermediate_size`,
    # `num_experts_per_tok` a token by sigmoid scores, beside
    # `n_shared_experts` that every token takes.
    n_routed_experts: int = 0
    n_shared_experts: int = 0
    moe_intermediate_size: int = 0
    first_k_dense_replace: int = 0
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # Latent attention (MLA): what a token caches is one vector of
    # kv_lora_rank + qk_rope_head_dim values a layer, shared by all heads.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False
    # SmallThinker family (model_type "smallthinker", models/smallthinker.py;
    # read for that family only). One entry a layer: 1 where the layer
    # attends to the last `sliding_window_size` keys / rotates q and k,
    # 0 where it attends to the whole context / applies no rope. Experts
    # reuse n_routed_experts, moe_intermediate_size, num_experts_per_tok
    # and norm_topk_prob above. (`sliding_window` stays 0: that key is
    # Gemma2's, one window for a whole llama.py stack.)
    sliding_window_size: int = 0
    sliding_window_layout: tuple[int, ...] = ()
    rope_layout: tuple[int, ...] = ()
    # Nemotron-H family (model_type "nemotron_h", models/nemotron_h.py;
    # read for that family only). `layer_pattern`: one character a block,
    # `M` a Mamba-2 mixer, `*` attention, `E` experts; each block is one of
    # the three alone. The mixer: `mamba_num_heads` heads of
    # `mamba_head_dim`, B and C in `ssm_groups` groups of `ssm_state_size`,
    # a depthwise convolution of `conv_kernel` taps, prefill in chunks of
    # `ssm_chunk`. The experts work in a latent space of `moe_latent_size`
    # (no gate matrix; n_routed_experts, moe_intermediate_size,
    # num_experts_per_tok, norm_topk_prob, routed_scaling_factor above)
    # beside one shared expert of `moe_shared_intermediate_size` on the
    # hidden state. A chip that holds a SHARE of the experts holds
    # `n_routed_experts` of them from `experts_first` on, of the
    # `router_experts` the router scores (0: it holds them all).
    layer_pattern: str = ""
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    ssm_groups: int = 0
    conv_kernel: int = 0
    ssm_chunk: int = 0
    moe_latent_size: int = 0
    moe_shared_intermediate_size: int = 0
    router_experts: int = 0
    experts_first: int = 0
    # AFMoE family (model_type "afmoe", models/afmoe.py): no field of its
    # own. Its `config_keys` reads its published keys into embed_scale,
    # first_k_dense_replace, the expert fields and the window layouts
    # above; its gate, query/key norms and post-norms are the family's
    # equations, not switches.

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @classmethod
    def from_hf(cls, config) -> "ModelConfig":
        """Build from a transformers PretrainedConfig: the keys every
        family shares under their Llama-style names, then the keys of the
        `model_type`'s module (`config_keys`: its own fields, and the
        refusal by name of what it does not compute)."""
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
        from kubeai_tpu.models import family_of  # the modules import this file

        model_type = get("model_type", "llama")
        kw = dict(
            model_type=model_type,
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
        # The family's own keys win (smallthinker names its experts per token otherwise).
        kw.update(family_of(model_type).config_keys(get))
        return cls(**kw)

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


def layout_period(*layouts: tuple[int, ...]) -> int:
    """The smallest p with layout[i] == layout[i % p] for every layout,
    over the entries all of them have (that length where nothing shorter
    repeats)."""
    n = min(len(layout) for layout in layouts)
    return next(
        (p for p in range(1, n) if all(layout[i] == layout[i % p] for layout in layouts for i in range(n))), max(n, 1)
    )


class LiveRows(NamedTuple):
    """A decode step's rows with the live slots first, from the dispatch's
    `active` mask. `engine/core.py::decode_fn` makes it once a chunk and
    `take`s every per-row argument of the model step in that order; a
    family's `decode_step_paged` given one hands `count` to a paged
    kernel that can stop there and puts the hidden state back in slot
    order (`restore`) before the final norm, so nothing `[B, V]` moves.
    With every slot live the order is the identity and the count is B."""

    order: jnp.ndarray  # [B] int32: row i of the step is slot order[i]'s
    inverse: jnp.ndarray  # [B] int32: slot s is row inverse[s] of the step
    count: jnp.ndarray  # [] int32: live slots, the first rows of the step

    @classmethod
    def first(cls, active: jnp.ndarray) -> "LiveRows":
        """Live slots first, each group in slot order (no sort: a running
        count places every slot)."""
        live = active.astype(jnp.int32)
        count = live.sum()
        inverse = jnp.where(active, jnp.cumsum(live) - 1, count + jnp.cumsum(1 - live) - 1)
        order = jnp.zeros_like(inverse).at[inverse].set(jnp.arange(live.shape[0], dtype=jnp.int32))
        return cls(order, inverse, count)

    def take(self, *arrays):
        """Each of *arrays* ([B, ...] in slot order, or None) in the step's order."""
        return tuple(None if a is None else a[self.order] for a in arrays)

    def restore(self, x: jnp.ndarray) -> jnp.ndarray:
        """*x* ([B, ...] in the step's order) back in slot order."""
        return x[self.inverse]
