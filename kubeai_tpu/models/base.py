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
    # own. `_afmoe_keys` reads its published keys into embed_scale,
    # first_k_dense_replace, the expert fields and the window layouts
    # above; its gate, query/key norms and post-norms are the family's
    # equations, not switches.

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
        if model_type == "deepseek_v3":
            gemma_kw = _deepseek_v3_keys(get)
        if model_type == "smallthinker":
            gemma_kw = _smallthinker_keys(get)
        if model_type == "nemotron_h":
            gemma_kw = _nemotron_h_keys(get)
        if model_type == "afmoe":
            gemma_kw = _afmoe_keys(get)
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
        kw.update(gemma_kw)  # a family's own keys win (smallthinker names its experts per token otherwise)
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


def _deepseek_v3_keys(get) -> dict:
    """The DeepSeek-V3 keys of a published config.json as ModelConfig
    fields. What models/deepseek.py does not compute is refused here,
    by name, and not served as something else."""
    if get("q_lora_rank"):
        raise ValueError("deepseek_v3: a query low-rank (q_lora_rank) is not supported")
    if (get("n_group") or 1) != 1 or (get("topk_group") or 1) != 1:
        raise ValueError("deepseek_v3: group-limited routing (n_group/topk_group > 1) is not supported")
    if get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError(f"deepseek_v3: scoring_func {get('scoring_func')!r} is not supported (sigmoid)")
    if (get("moe_layer_freq") or 1) != 1:
        raise ValueError("deepseek_v3: moe_layer_freq other than 1 is not supported")
    if get("attention_bias"):
        raise ValueError("deepseek_v3: attention_bias is not supported")
    return dict(
        n_routed_experts=get("n_routed_experts") or 0,
        n_shared_experts=get("n_shared_experts") or 0,
        moe_intermediate_size=get("moe_intermediate_size") or 0,
        first_k_dense_replace=get("first_k_dense_replace") or 0,
        norm_topk_prob=bool(get("norm_topk_prob", True)),
        routed_scaling_factor=float(get("routed_scaling_factor") or 1.0),
        kv_lora_rank=get("kv_lora_rank"),
        qk_nope_head_dim=get("qk_nope_head_dim"),
        qk_rope_head_dim=get("qk_rope_head_dim"),
        v_head_dim=get("v_head_dim"),
        rope_interleave=bool(get("rope_interleave", False)),
    )


def _smallthinker_keys(get) -> dict:
    """The SmallThinker keys of a published config.json as ModelConfig
    fields. What models/smallthinker.py does not compute is refused
    here, by name. The layouts may be longer than the depth (a
    checkpoint cut in depth keeps the published lists): the first
    `num_hidden_layers` entries are the model's."""
    L = get("num_hidden_layers")
    if not get("moe_primary_router_apply_softmax", False):
        raise ValueError("smallthinker: moe_primary_router_apply_softmax false (a sigmoid router) is not supported")
    if get("moe_enable_secondary_experts") or get("moe_num_secondary_experts"):
        raise ValueError("smallthinker: secondary experts are not supported")
    if get("rope_scaling"):
        raise ValueError("smallthinker: rope_scaling is not supported")
    if not get("norm_topk_prob", True):
        raise ValueError("smallthinker: norm_topk_prob false is not supported")
    layouts = {}
    for key in ("sliding_window_layout", "rope_layout"):
        layout = get(key)
        if not isinstance(layout, (list, tuple)) or len(layout) < L or any(v not in (0, 1) for v in layout):
            raise ValueError(f"smallthinker: {key} must give 0 or 1 for each of the {L} layers")
        layouts[key] = tuple(int(v) for v in layout)
    period = layout_period(*layouts.values())
    if L % period:
        raise ValueError(
            f"smallthinker: {L} layers are not whole periods of the layouts' pattern of {period} layers"
        )
    layouts = {key: layout[:L] for key, layout in layouts.items()}
    window = get("sliding_window_size") or 0
    if any(layouts["sliding_window_layout"]) and window <= 0:
        raise ValueError("smallthinker: sliding_window_layout names window layers and sliding_window_size gives no window")
    return dict(
        intermediate_size=0,  # no dense feed-forward anywhere in the stack
        n_routed_experts=get("moe_num_primary_experts") or 0,
        num_experts_per_tok=get("moe_num_active_primary_experts") or 0,
        moe_intermediate_size=get("moe_ffn_hidden_size") or 0,
        norm_topk_prob=True,
        sliding_window_size=int(window),
        **layouts,
    )


def _nemotron_h_keys(get) -> dict:
    """The Nemotron-H keys of a published config.json as ModelConfig
    fields. What models/nemotron_h.py does not compute is refused here,
    by name. The pattern may be longer than the depth (a checkpoint cut
    in depth keeps the published 88 characters): the first
    `num_hidden_layers` are the model's. `num_nextn_predict_layers` and
    `mtp_hybrid_override_pattern` (the drafting head) are read by nothing:
    it changes no served distribution and is not loaded. `rope_theta` and
    `partial_rotary_factor` likewise: the family's attention layers apply
    no rotary embedding. `time_step_*` initialise `dt_bias` in training."""
    L = get("num_hidden_layers")
    pattern = get("hybrid_override_pattern")
    if not isinstance(pattern, str) or len(pattern) < L:
        raise ValueError(f"nemotron_h: hybrid_override_pattern must name each of the {L} blocks")
    pattern = pattern[:L]
    if set(pattern) - set("M*E"):
        raise ValueError(
            f"nemotron_h: hybrid_override_pattern names blocks other than M, * and E ({sorted(set(pattern) - set('M*E'))}: "
            "a dense feed-forward block is not supported)"
        )
    if (get("n_group") or 1) != 1 or (get("topk_group") or 1) != 1:
        raise ValueError("nemotron_h: group-limited routing (n_group/topk_group > 1) is not supported")
    if not get("moe_latent_size"):
        raise ValueError("nemotron_h: experts outside a latent space (no moe_latent_size) are not supported")
    if (get("n_shared_experts") or 0) != 1:
        raise ValueError("nemotron_h: n_shared_experts other than 1 is not supported")
    for key, want in (("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu")):
        if get(key, want) != want:
            raise ValueError(f"nemotron_h: {key} {get(key)!r} is not supported ({want})")
    for key in ("attention_bias", "mlp_bias", "use_bias", "mamba_proj_bias", "residual_in_fp32"):
        if get(key):
            raise ValueError(f"nemotron_h: {key} is not supported")
    if not get("use_conv_bias", True):
        raise ValueError("nemotron_h: use_conv_bias false is not supported")
    if get("sliding_window"):
        raise ValueError("nemotron_h: sliding_window is not supported")
    heads, head_dim = get("mamba_num_heads") or 0, get("mamba_head_dim") or 0
    groups = get("n_groups") or 0
    if not heads or not groups or heads % groups or (heads * head_dim) % groups:
        raise ValueError("nemotron_h: mamba_num_heads must be a whole number of heads for each of n_groups")
    held, scored = get("n_routed_experts") or 0, get("router_experts") or 0
    first = get("experts_first") or 0
    if scored and first + held > scored:
        raise ValueError(f"nemotron_h: experts {first}..{first + held - 1} are not among the router's {scored}")
    return dict(
        intermediate_size=0,  # no dense feed-forward block
        rms_norm_eps=get("layer_norm_epsilon", 1e-5),
        layer_pattern=pattern,
        mamba_num_heads=heads,
        mamba_head_dim=head_dim,
        ssm_state_size=get("ssm_state_size"),
        ssm_groups=groups,
        conv_kernel=get("conv_kernel"),
        ssm_chunk=get("chunk_size"),
        n_routed_experts=held,
        router_experts=scored,
        experts_first=first,
        n_shared_experts=1,
        moe_intermediate_size=get("moe_intermediate_size") or 0,
        moe_latent_size=get("moe_latent_size"),
        moe_shared_intermediate_size=get("moe_shared_expert_intermediate_size") or 0,
        norm_topk_prob=bool(get("norm_topk_prob", True)),
        routed_scaling_factor=float(get("routed_scaling_factor") or 1.0),
    )


def _afmoe_keys(get) -> dict:
    """The AFMoE keys (Trinity) of a published config.json as ModelConfig
    fields; models/afmoe.py says what each means. What it does not
    compute is refused here, by name. `layer_types` may be longer than the
    depth (a checkpoint cut in depth keeps the published list): the first
    `num_hidden_layers` entries are the model's. The family has no fields
    of its own: the window, the layouts (rope goes with the window), the
    leading dense layers, the experts and the shared one, the router's
    norm and scale and the embedding multiplier reuse the fields other
    families brought. `load_balance_coeff` (it trains the selection bias)
    and `use_grouped_mm` (a switch of the source's implementation) are
    read by nothing."""
    L = get("num_hidden_layers")
    types = get("layer_types")
    if not isinstance(types, (list, tuple)) or len(types) < L or set(types) - {"sliding_attention", "full_attention"}:
        raise ValueError(f"afmoe: layer_types must give sliding_attention or full_attention for each of the {L} layers")
    every = get("global_attn_every_n_layers")
    if every and any((t == "full_attention") != ((i + 1) % every == 0) for i, t in enumerate(types)):
        raise ValueError(f"afmoe: layer_types and global_attn_every_n_layers ({every}) disagree")
    for key in ("n_group", "topk_group", "num_expert_groups", "num_limited_groups"):
        if (get(key) or 1) != 1:
            raise ValueError(f"afmoe: grouped routing ({key} > 1) is not supported")
    if get("score_func", "sigmoid") != "sigmoid":
        raise ValueError(f"afmoe: score_func {get('score_func')!r} is not supported (sigmoid)")
    if get("hidden_act", "silu") != "silu":
        raise ValueError(f"afmoe: hidden_act {get('hidden_act')!r} is not supported (silu)")
    if get("rope_scaling"):
        raise ValueError("afmoe: rope_scaling is not supported")
    if get("attention_bias"):
        raise ValueError("afmoe: attention_bias is not supported")
    layout = tuple(int(t == "sliding_attention") for t in types)
    period = layout_period(layout)
    if L % period:
        raise ValueError(f"afmoe: {L} layers are not whole periods of layer_types' pattern of {period} layers")
    dense = get("num_dense_layers") or 0
    if dense > period:
        raise ValueError(f"afmoe: num_dense_layers {dense} past the first period of {period} layers is not supported")
    window = get("sliding_window") or 0
    if any(layout[:L]) and window <= 0:
        raise ValueError("afmoe: layer_types names sliding_attention layers and sliding_window gives no window")
    return dict(
        embed_scale=bool(get("mup_enabled", False)),
        first_k_dense_replace=dense,
        n_routed_experts=get("num_experts") or 0,
        n_shared_experts=get("num_shared_experts") or 0,
        moe_intermediate_size=get("moe_intermediate_size") or 0,
        norm_topk_prob=bool(get("route_norm", True)),
        routed_scaling_factor=float(get("route_scale") or 1.0),
        sliding_window_size=int(window),
        sliding_window_layout=layout[:L],
        rope_layout=layout[:L],
    )


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
