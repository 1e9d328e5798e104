"""AFMoE decoder (`model_type: afmoe`, Arcee Trinity) in functional JAX:
periods of window layers WITH rope and one full layer WITHOUT (the
published Trinity-Mini: three 2048-window layers, then one full), every
attention gated and its queries and keys normed, a norm AFTER each
sub-block as well as before it, the first `num_dense_layers` layers with
a dense feed-forward and the rest with sigmoid-routed experts beside a
shared one. With `x` a layer's input, `h` the hidden size:

    x0 = E[token] * sqrt(h)                                   # mup_enabled
    a  = rms(x; g1);  q, k, v, z = a Wq, a Wk, a Wv, a Wz     # no biases; z gates the output
    q  = rms_per_head(q; gq);  k = rms_per_head(k; gk)
    q, k = rope(q), rope(k)        ONLY in a window layer     # half-split pairs
    o  = softmax(q k^T / sqrt(d) + causal [+ key j > i - window]) v
    u  = x + rms((o * sigmoid(z)) Wo; g2)                     # the norm on the sub-block's OUTPUT
    m  = rms(u; g3)
    f  = (silu(m Wg) * (m Wu)) Wd                             # a dense layer, or:
    s  = sigmoid(m Wr) in float32;  S = top-k of (s + b)      # b chooses and weighs nothing
    f  = sum_{e in S} s_e / (sum_S s + 1e-20) * scale * swiglu_e(m) + swiglu_shared(m)
    x' = u + rms(f; g4)

The engine reaches a model through `kubeai_tpu.models.family(config)`;
`models/__init__.py` declares what this module gives it. What the family
does not run is refused at load (`refuse_unsupported`, and `config_keys`
for what the config itself asks).

**Two axes.** The KIND OF ATTENTION follows `layer_types` with a period
(4 as published) and the KIND OF FEED-FORWARD the depth (dense before
`num_dense_layers`), so the first period is a body of its own, unrolled:
its layers differ in both. The periods behind it, all experts, are one
scanned body with the period's layers unrolled inside, as
`models/smallthinker.py` scans its own: the window is a static argument,
a full layer traces no rotation, and the experts are read from the whole
stack in place (`ops/moe.py::routed_experts`, `layer=`).

**Two pools, two tables, a window layer that reads its window, the
routes:** `models/smallthinker.py`'s, imported (`TwoPools`,
`init_paged_cache`, `cached_attention_route`; its docstring says what
each is). `REUSE_WHOLE_PREFILL_CALLS` and `KV_PARK = False` for its
reasons too: a router turns one rounding between two call shapes into
other experts, and a slot has pages in two pools.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from kubeai_tpu.models import shared
from kubeai_tpu.models.base import ModelConfig, layout_period
from kubeai_tpu.models.shared import layer_counts, swiglu as _swiglu  # the names `apply` calls them by
from kubeai_tpu.models.smallthinker import (  # noqa: F401  (what IS SmallThinker's: the two pools and their routes)
    TwoPools,
    cached_attention_route,
    init_paged_cache,
    layer_kinds,
    period,
    window_pool_tokens,
)
from kubeai_tpu.ops import moe
from kubeai_tpu.ops.norms import rms_norm
from kubeai_tpu.ops.rope import apply_rope, rope_frequencies

Params = dict[str, Any]

PAGED_KERNEL_LABEL = "ragged"
REUSE_WHOLE_PREFILL_CALLS = True  # the module docstring says why
KV_PARK = False  # likewise


def refuse_unsupported(config: ModelConfig, quantization: str = "", tp: int = 1) -> None:
    """What this family does not run, refused at load by name."""
    shared.refuse_common("afmoe", config, quantization, tp, "experts and both pools are unsharded")
    if config.rope_scaling is not None:
        raise ValueError("afmoe: rope_scaling is not supported")
    full, window = layer_kinds(config)
    if not full or not window:
        raise ValueError("afmoe: a stack without both full and window layers is not supported")
    n_dense, n_moe = layer_counts(config)
    if n_dense > period(config):
        raise ValueError("afmoe: num_dense_layers past the first period is not supported")
    if not n_moe or not config.n_routed_experts:
        raise ValueError("afmoe: a stack without an expert layer is not supported")


# ---------------------------------------------------------------------------
# Parameters


def _shapes(config: ModelConfig) -> dict[str, dict]:
    """Parameter shapes of ONE layer by group: what every layer holds
    (`attn`: attention and the four norms), a dense layer's feed-forward,
    an expert layer's router and shared expert (`moe`), its `experts`."""
    D, H, Kv, h = config.hidden_size, config.num_heads, config.num_kv_heads, config.head_dim_
    F, Fm, E = config.intermediate_size, config.moe_intermediate_size, config.n_routed_experts
    Fs = Fm * config.n_shared_experts
    return {
        "attn": {
            "ln1": (D,), "ln2": (D,), "ln3": (D,), "ln4": (D,), "q_norm": (h,), "k_norm": (h,),
            "wq": (D, H * h), "wk": (D, Kv * h), "wv": (D, Kv * h), "wz": (D, H * h), "wo": (H * h, D),
        },
        "dense": {"wg": (D, F), "wu": (D, F), "wd": (F, D)},
        "moe": {"wr": (D, E), "br": (E,), "ws_g": (D, Fs), "ws_u": (D, Fs), "ws_d": (Fs, D)},
        "experts": {"we_g": (E, D, Fm), "we_u": (E, D, Fm), "we_d": (E, Fm, D)},
    }


def _group_rows(config: ModelConfig) -> dict[str, int]:
    n_dense, n_moe = layer_counts(config)
    return {"attn": config.num_layers, "dense": n_dense, "moe": n_moe, "experts": n_moe}


def init_params(config: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random parameters in the tree the loader builds: each group stacks
    its layers on a leading axis. The selection bias is float32 and NOT
    zero, so that a test that leaves it out fails."""
    dtype = dtype or jnp.dtype(config.dtype)
    keys = iter(jax.random.split(key, 32))

    def draw(n, name, shape):
        if name.startswith("ln") or name.endswith("_norm"):
            return jnp.ones((n, *shape), dtype)
        if name == "br":
            return jax.random.normal(next(keys), (n, *shape), jnp.float32) * 0.1
        return (jax.random.normal(next(keys), (n, *shape), jnp.float32) * shape[-2] ** -0.5).astype(dtype)

    D, V = config.hidden_size, config.vocab_size
    rows = _group_rows(config)
    return {
        "embed": (jax.random.normal(next(keys), (V, D), jnp.float32) * 0.02).astype(dtype),
        "final_norm": jnp.ones((D,), dtype),
        "lm_head": (jax.random.normal(next(keys), (D, V), jnp.float32) * 0.02).astype(dtype),
        # A group with no layer is an empty dict, in every loader's tree.
        **{g: {k: draw(rows[g], k, s) for k, s in shapes.items()} if rows[g] else {} for g, shapes in _shapes(config).items()},
    }


def _layer_tensors(get, config: ModelConfig, i: int, dtype) -> dict[str, dict]:
    """Layer *i* of an HF checkpoint (get(name) -> array) by group:
    linears transposed to [in, out]; the experts stacked [E, out, in] on
    the host (one contiguous copy; the device transposes them)."""
    p = f"model.layers.{i}."
    conv = lambda a: np.asarray(a, dtype)  # noqa: E731
    lin = lambda name: conv(np.asarray(get(p + name + ".weight")).T)  # noqa: E731
    norm = lambda name: conv(get(p + name + ".weight"))  # noqa: E731
    out = {
        "attn": {
            "ln1": norm("input_layernorm"), "ln2": norm("post_attention_layernorm"),
            "ln3": norm("pre_mlp_layernorm"), "ln4": norm("post_mlp_layernorm"),
            "q_norm": norm("self_attn.q_norm"), "k_norm": norm("self_attn.k_norm"),
            "wq": lin("self_attn.q_proj"), "wk": lin("self_attn.k_proj"), "wv": lin("self_attn.v_proj"),
            "wz": lin("self_attn.gate_proj"), "wo": lin("self_attn.o_proj"),
        },
    }
    if i < config.first_k_dense_replace:
        out["dense"] = {"wg": lin("mlp.gate_proj"), "wu": lin("mlp.up_proj"), "wd": lin("mlp.down_proj")}
        return out
    stack = lambda which: conv(  # noqa: E731
        np.stack([np.asarray(get(f"{p}mlp.experts.{j}.{which}.weight")) for j in range(config.n_routed_experts)])
    )
    out["moe"] = {
        "wr": lin("mlp.router.gate"), "br": np.asarray(get(p + "mlp.expert_bias"), np.float32),
        "ws_g": lin("mlp.shared_experts.gate_proj"), "ws_u": lin("mlp.shared_experts.up_proj"),
        "ws_d": lin("mlp.shared_experts.down_proj"),
    }
    out["experts"] = {"we_g": stack("gate_proj"), "we_u": stack("up_proj"), "we_d": stack("down_proj")}
    return out


def stream_params_from_hf(source, config: ModelConfig, pad: int = 0) -> Params:
    """`shared.stream_stacks` over this family's groups (an expert layer
    is 1.6 GB in bf16: the host holds two, the device never a stack
    twice): `dense` holds the leading layers, `moe` and `experts` the
    layers behind them."""
    n_dense = layer_counts(config)[0]
    rows = {g: (n, n_dense if g in ("moe", "experts") else 0) for g, n in _group_rows(config).items()}
    return shared.stream_stacks(source, config, pad, _layer_tensors, rows)


params_from_hf = shared.params_from_hf_by(stream_params_from_hf)


# ---------------------------------------------------------------------------
# Forward


def _take(tree: dict, i) -> dict:
    """Row *i* of every stacked array of *tree*, read from the whole stack
    at its own index (a block sliced out first and then indexed is a copy
    of the block); *i* an int in the first period, traced in the scan."""
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), tree)


def apply(
    params: Params,
    config: ModelConfig,
    tokens: jnp.ndarray,  # [B, S] int32
    positions: jnp.ndarray,  # [B, S] int32 absolute positions, contiguous along S
    cache: Params | None = None,  # init_paged_cache
    page_table: jnp.ndarray | None = None,  # [B, 2 * max_pages]: [full | window]
    logits_idx: jnp.ndarray | None = None,
    left_aligned: bool = False,  # caller guarantees positions == arange(S)
    forced_choices: jnp.ndarray | None = None,  # [expert layers, B*S, k]: route by these (debug)
    return_choices: bool = False,  # also return the routers' choices (debug; no timed program asks)
    live=None,  # models/base.py::LiveRows of a decode step whose rows arrive live slots first
    **unsupported,  # what llama.apply takes and this family does not run (return_hidden, lora, ...)
):
    """Run the decoder over the two paged pools. Returns (logits, cache)
    with `cache["moe_hits"]` the (layer, expert) pairs that got a row;
    with *return_choices* also the choices [expert layers, B*S, k].
    Writes and out-of-span positions as in `smallthinker.apply`."""
    if cache is None or page_table is None or unsupported:
        raise ValueError("afmoe: a call without the paged pool (embeddings, scoring) is not supported")
    B, S = tokens.shape
    D, H, Kv, h, L = config.hidden_size, config.num_heads, config.num_kv_heads, config.head_dim_, config.num_layers
    eps, top_k = config.rms_norm_eps, config.num_experts_per_tok
    per = period(config)
    n_dense, n_moe = layer_counts(config)
    inv_freq = jnp.asarray(rope_frequencies(h, config.rope_theta, None))
    two = TwoPools(config, cache, page_table, positions, cached_attention_route(config, S, left_aligned, True), live)
    dtype = jnp.dtype(config.dtype)
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
        if config.embed_scale:
            x = x.astype(jnp.float32) * D**0.5
        x = x.astype(dtype)

    def attention(x, w, pool, row0, kind):
        with jax.named_scope("attn"), jax.named_scope("attn.window" if kind else "attn.full"):
            a = rms_norm(x, w["ln1"], eps)
            q = jnp.dot(a, w["wq"]).reshape(B, S, H, h)
            k = jnp.dot(a, w["wk"]).reshape(B, S, Kv, h)
            v = jnp.dot(a, w["wv"]).reshape(B, S, Kv, h)
            z = jnp.dot(a, w["wz"])
            with jax.named_scope("attn.qk_norm"):
                q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
            if kind:  # rope goes with the window: a full layer rotates nothing
                q, k = apply_rope(q, k, positions, inv_freq)
            pool = two.write(pool, row0, kind, k, v)
            with jax.named_scope("attn.kernel"):
                o = two.attend(q, k, v, pool, row0, kind)
            with jax.named_scope("attn.gate"):
                o = (o.reshape(B, S, H * h).astype(jnp.float32) * jax.nn.sigmoid(z.astype(jnp.float32))).astype(dtype)
            out = jnp.dot(o, w["wo"])
        with jax.named_scope("norm.post"):
            return x + rms_norm(out, w["ln2"], eps), pool

    def dense_ffn(x, a, w):
        with jax.named_scope("ffn"):
            f = _swiglu(rms_norm(x, a["ln3"], eps), w["wg"], w["wu"], w["wd"])
        with jax.named_scope("norm.post"):
            return x + rms_norm(f, a["ln4"], eps)

    def expert_ffn(x, a, w, forced, e):
        with jax.named_scope("moe"):
            m = rms_norm(x, a["ln3"], eps).reshape(B * S, -1)
            with jax.named_scope("moe.router"):
                idx, weights = moe.route_sigmoid(
                    m, w["wr"], w["br"], top_k, config.norm_topk_prob, config.routed_scaling_factor, forced=forced,
                )
            # The experts' stacks are not sliced: layer e's are groups of the whole (ops/moe.py).
            experts = params["experts"]
            f, hit = moe.routed_experts(m, idx, weights, experts["we_g"], experts["we_u"], experts["we_d"], layer=e)
            with jax.named_scope("moe.shared"):
                f = f + _swiglu(m, w["ws_g"], w["ws_u"], w["ws_d"])
        with jax.named_scope("norm.post"):
            return x + rms_norm(f.reshape(B, S, -1), a["ln4"], eps), hit, idx

    # Each kind of sub-block is ONE function of the program, called where a
    # layer has it (six window layers, two full ones, six expert layers at
    # the published cut): traced and lowered once in the first period and
    # once in the scan, not once a call site, which shortens what a start
    # spends tracing its 19 step programs (a warm start's compile phase
    # 94.6 -> 73.5 s on the chip: PERF.md section 6, PR 42). Pool rows and
    # the experts' layer go in as values, so a kind's calls are one call.
    attend = {kind: jax.jit(functools.partial(attention, kind=kind)) for kind in (0, 1)}
    experts_of = jax.jit(expert_ffn)
    i32 = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731

    def one_period(x, held, hits, n, forced):
        """The layers of period *n* (an int: the first, whose leading
        layers are dense; traced: one behind it), unrolled: a period's
        layers differ in kind. *forced*: its expert layers' choices."""
        chosen = []
        for j in range(per):
            kind, l = two.kinds[j], n * per + j
            row0 = two.row0(n, j)
            a = _take(params["attn"], l)
            x, held[kind] = attend[kind](x, a, held[kind], i32(row0))
            if isinstance(l, int) and l < n_dense:
                x = dense_ffn(x, a, _take(params["dense"], l))
                continue
            e = l - n_dense
            x, hit, idx = experts_of(x, a, _take(params["moe"], e), None if forced is None else forced[len(chosen)], i32(e))
            hits = hits + hit
            chosen.append(idx)
        return x, held, hits, chosen

    first_moe = per - n_dense  # expert layers of the first period
    x, held, hits, chosen = one_period(
        x, dict(two.pools), jnp.zeros((), jnp.int32), 0, None if forced_choices is None else forced_choices[:first_moe],
    )
    choices = chosen

    def step(carry, xs):
        x, pool_f, pool_w, hits = carry
        n, forced = xs
        x, held, hits, chosen = one_period(x, {0: pool_f, 1: pool_w}, hits, n, forced)
        return (x, held[0], held[1], hits), (jnp.stack(chosen) if return_choices else None)

    n_periods = L // per
    if n_periods > 1:
        (x, held[0], held[1], hits), later = jax.lax.scan(
            step, (x, held[0], held[1], hits),
            (
                jnp.arange(1, n_periods, dtype=jnp.int32),
                None if forced_choices is None else forced_choices[first_moe:].reshape(n_periods - 1, per, *forced_choices.shape[1:]),
            ),
        )
        if return_choices:
            choices = choices + list(later.reshape((n_periods - 1) * per, B * S, top_k))

    if live is not None:
        x = live.restore(x)  # slot order again, before anything [B, V]
    x = rms_norm(x, params["final_norm"], eps)
    with jax.named_scope("lm_head"):
        if logits_idx is not None:
            x = x[jnp.arange(B)[:, None], logits_idx[:, None]]
        logits = jnp.dot(x, params["lm_head"]).astype(jnp.float32)
    new_cache = {"kv": held[0], "kv_window": held[1], "moe_hits": hits}
    if return_choices:
        return logits, new_cache, jnp.stack(choices)
    return logits, new_cache


prefill_paged, prefill_paged_cold, decode_step_paged = shared.paged_entry_points(apply, "afmoe")

# The seam's other names (models/__init__.py says what each rule means).
PREFIX_REUSE = True
SLOT_STATE = ()
init_lora_bank = None


def config_keys(get) -> dict:
    """The AFMoE keys (Trinity) of a published config.json as ModelConfig
    fields; the module docstring says what each means. What this module
    does not compute is refused here, by name. `layer_types` may be longer
    than the depth (a checkpoint cut in depth keeps the published list):
    the first `num_hidden_layers` entries are the model's. The family has
    no fields of its own: the window, the layouts (rope goes with the
    window), the leading dense layers, the experts and the shared one, the
    router's norm and scale and the embedding multiplier reuse the fields
    other families brought. `load_balance_coeff` (it trains the selection
    bias) and `use_grouped_mm` (a switch of the source's implementation)
    are read by nothing."""
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


def param_counts(mc: ModelConfig) -> tuple[float, float]:
    """(held, active a token): every layer holds gated grouped-query
    attention with query/key norms and four norms;
    `first_k_dense_replace` layers a dense feed-forward, the rest a
    router with its selection bias, `n_shared_experts` shared experts and
    `n_routed_experts` routed ones, of which a token passes through
    `num_experts_per_tok`. Trinity-Mini at 8 of 32 layers: 5.98G held,
    1.04G a token; at 32: 26.1G and 3.06G. Held to perfbench/families/
    afmoe_counts.py by tests/test_afmoe.py."""
    D, L, V = mc.hidden_size, mc.num_layers, mc.vocab_size
    H, Kv, h = mc.num_heads, mc.num_kv_heads, mc.head_dim_
    attn = 3 * D * H * h + 2 * D * Kv * h + 2 * h + 4 * D
    n_dense = min(mc.first_k_dense_replace, L)
    expert = 3 * D * mc.moe_intermediate_size
    outside = D * mc.n_routed_experts + mc.n_routed_experts + mc.n_shared_experts * expert
    always = L * attn + n_dense * 3 * D * mc.intermediate_size + (L - n_dense) * outside  # whatever the routing
    total = 2 * V * D + D + always + (L - n_dense) * mc.n_routed_experts * expert
    # Active leaves the embedding table out (a row is looked up).
    active = V * D + D + always + (L - n_dense) * mc.num_experts_per_tok * expert
    return float(total), float(active)
