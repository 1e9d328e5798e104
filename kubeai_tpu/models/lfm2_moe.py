"""LFM2-MoE decoder (`model_type: lfm2_moe`, LiquidAI LFM2-24B-A2B) in
functional JAX: a stack whose OPERATOR is a gated short convolution or, in
every fourth layer as published, attention; whose FEED-FORWARD is a dense
SwiGLU in the first `num_dense_layers` layers and sigmoid-routed experts
behind them. With `x` a layer's input:

    u  = rms(x; operator_norm)
    conv:  B, C, z = split3(u W_in);  c = taps(B * z);  o = (C * c) W_out      # ops/shortconv.py; no activation
    attn:  q, k, v = u Wq, u Wk, u Wv;  q = rms_per_head(q; gq), k = rms_per_head(k; gk)   # BEFORE rope
           q, k = rope(q), rope(k);  o = softmax(q k^T / sqrt(d) + causal) v Wo            # half-split pairs
    x  = x + o
    m  = rms(x; ffn_norm)
    f  = (silu(m W1) * (m W3)) W2                              # a dense layer, or:
    s  = sigmoid(m Wr) in float32;  S = top-k of (s + b)       # b chooses and weighs nothing
    f  = sum_{e in S} s_e / (sum_S s + 1e-20) * scale * swiglu_e(m)            # no shared expert
    x' = x + f

then `embedding_norm` and the head, which is the embedding (tied). The
engine reaches a model through `kubeai_tpu.models.family(config)`;
`models/__init__.py` declares what this module gives it.

**Two axes.** The KIND OF OPERATOR follows `layer_types` (`layer_pattern`
here: `c` a convolution, `a` attention) and the KIND OF FEED-FORWARD the
depth, independently: the leading dense layers are unrolled, each with
its own operator, and the expert layers behind them are whole PERIODS of
the pattern (`a c c c` as published), one scanned body with the period's
layers unrolled inside. A group of the tree stacks its layers on a
leading axis (`conv`, `attn`: the layers of that operator in stack order;
`dense`, `moe`, `experts`: of that feed-forward) and a layer reads its row
at its own index; the experts are read from the whole stack in place
(`ops/moe.py::routed_experts`, `layer=`).

**State that is not pages.** The attention layers alone own rows of the
paged pool (`cache["kv"]`, a layer `P` rows; logical page 0 is the trash
page). A convolution keeps, for every SLOT of the engine, the last
`K - 1` rows that went into its taps: `cache["conv"]` `[n_conv, slots,
K - 1, D]` in the compute dtype, 8 KiB a layer a slot as published,
whatever the context. No allocator: the slot is the address. A cold
prefill call starts every row from zeros and writes the rows' tails at
`slots` (a slot used again starts from zeros); a chunk behind earlier
chunks reads its slot's tail and writes it back; a decode step computes
its convolutions in SLOT order, in place (`ops/shortconv.py::
gated_short_conv_step`: the operator's input is put in slot order and its
output back in the step's order, `live.restore` / `live.take`), and a slot
that is not live keeps its tail bit for bit.

**Heads of 64.** The pool holds two KV heads side by side in a 128-lane
row (`ops/paged_attention.py`, "Heads narrower than a lane tile"): the
published 2 x 8 x 64 values a token a layer and no lane more, read by the
kernels every family uses as 4 heads of 128. Every route (flash, paged
kernel, the portable gather) goes through that one reading.

**What is limited for this family, stated here once.** `PREFIX_REUSE =
False`: a prefix found in the page cache would also need every
convolution's tail at the page's edge, which nothing keeps, so the engine
looks nothing up and registers nothing (`prefix_hit` counters stay 0).
`KV_PARK = False`: a slot's tails are not parked, restored or handed off
(the wire format carries pages). Snapshots of the tail for both are what
is still missing (ROADMAP B-I.4; at 8 KiB a layer they are smaller than a
page). tp = 1, the compute dtype's pool, no quantization, no LoRA.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from kubeai_tpu.models import shared
from kubeai_tpu.models.base import ModelConfig
from kubeai_tpu.models.shared import cached_attention_route, swiglu as _swiglu, take_row as _take
from kubeai_tpu.ops import moe, shortconv
from kubeai_tpu.ops.attention import attention
from kubeai_tpu.ops.norms import rms_norm
from kubeai_tpu.ops.paged_attention import heads_a_tile, narrow_outputs, pack_kv, paged_attention_ragged, widen_queries
from kubeai_tpu.ops.rope import apply_rope, rope_frequencies

Params = dict[str, Any]

PAGED_KERNEL_LABEL = "ragged"
KV_PARK = False  # the module docstring says why
PREFIX_REUSE = False  # likewise
SLOT_STATE = ("conv",)  # init_paged_cache takes `slots`; the prefill entry points take each row's slot
KINDS = {"conv": "c", "full_attention": "a"}  # layer_types' entries as layer_pattern's characters


def layout(config: ModelConfig) -> tuple[int, int, int]:
    """(leading dense layers, layers a period, periods) of the stack: the
    expert layers are whole periods of the pattern behind the dense ones
    (the shortest that repeats; at worst all of them are one period)."""
    L = config.num_layers
    lead = min(config.first_k_dense_replace, L)
    rest = config.layer_pattern[lead:L]
    n = len(rest)
    per = next((p for p in range(1, n + 1) if n % p == 0 and all(rest[i] == rest[i % p] for i in range(n))), 0)
    return lead, per, n // per if per else 0


def rows_of(config: ModelConfig) -> dict[str, list[int]]:
    """For each operator, the layers that have it, in stack order: layer
    `rows_of(config)["c"][r]` is row r of the `conv` group and of
    `cache["conv"]`; likewise `a`, the `attn` group and the pool."""
    return {kind: [i for i, t in enumerate(config.layer_pattern[: config.num_layers]) if t == kind] for kind in "ca"}


def window_pool_tokens(config: ModelConfig) -> int:
    return 0  # one page budget a slot


def state_bytes_per_slot(config: ModelConfig) -> int:
    """What a slot owns outside its pages: every convolution's tail."""
    return len(rows_of(config)["c"]) * (config.conv_kernel - 1) * config.hidden_size * jnp.dtype(config.dtype).itemsize


def refuse_unsupported(config: ModelConfig, quantization: str = "", tp: int = 1) -> None:
    """What this family does not run, refused at load by name."""
    shared.refuse_common(
        "lfm2_moe", config, quantization, tp, "tails, experts and the pool are unsharded",
        int8_for="expert or operator weights", tied=True,
    )
    rows = rows_of(config)
    if not rows["c"] or not rows["a"]:
        raise ValueError("lfm2_moe: a stack without both a conv and a full_attention layer is not supported")
    lead, per, periods = layout(config)
    if not periods or not config.n_routed_experts:
        raise ValueError("lfm2_moe: a stack without an expert layer is not supported")
    if config.num_kv_heads % heads_a_tile(config.head_dim_):
        raise ValueError(
            f"lfm2_moe: {config.num_kv_heads} KV heads of {config.head_dim_} do not fill whole 128-lane rows of the pool "
            f"({heads_a_tile(config.head_dim_)} a row)"
        )


# ---------------------------------------------------------------------------
# Parameters


def _shapes(config: ModelConfig) -> dict[str, dict]:
    """Parameter shapes of ONE layer by group: a convolution or an
    attention operator with its norm; a dense feed-forward or a router
    (`moe`) with ITS norm; an expert layer's `experts`."""
    D, H, Kv, h, K = config.hidden_size, config.num_heads, config.num_kv_heads, config.head_dim_, config.conv_kernel
    F, Fm, E = config.intermediate_size, config.moe_intermediate_size, config.n_routed_experts
    return {
        "conv": {"ln": (D,), "in_proj": (D, 3 * D), "conv_w": (K, D), "out_proj": (D, D)},
        "attn": {"ln": (D,), "q_norm": (h,), "k_norm": (h,), "wq": (D, H * h), "wk": (D, Kv * h), "wv": (D, Kv * h), "wo": (H * h, D)},
        "dense": {"ln": (D,), "wg": (D, F), "wu": (D, F), "wd": (F, D)},
        "moe": {"ln": (D,), "wr": (D, E), "br": (E,)},
        "experts": {"we_g": (E, D, Fm), "we_u": (E, D, Fm), "we_d": (E, Fm, D)},
    }


def _group_rows(config: ModelConfig) -> dict[str, int]:
    rows, (lead, per, periods) = rows_of(config), layout(config)
    return {"conv": len(rows["c"]), "attn": len(rows["a"]), "dense": lead, "moe": per * periods, "experts": per * periods}


def init_params(config: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random parameters in the tree the loader builds: each group stacks
    its layers on a leading axis; no `lm_head` (tied). The selection bias
    is float32 and NOT zero, so that a test that leaves it out fails."""
    dtype = dtype or jnp.dtype(config.dtype)
    keys = iter(jax.random.split(key, 32))

    def draw(n, name, shape):
        if name == "ln" or name.endswith("_norm"):
            return jnp.ones((n, *shape), dtype)
        if name == "br":
            return jax.random.normal(next(keys), (n, *shape), jnp.float32) * 0.1
        return (jax.random.normal(next(keys), (n, *shape), jnp.float32) * shape[-2] ** -0.5).astype(dtype)

    D, V = config.hidden_size, config.vocab_size
    rows = _group_rows(config)
    return {
        "embed": (jax.random.normal(next(keys), (V, D), jnp.float32) * 0.02).astype(dtype),
        "final_norm": jnp.ones((D,), dtype),
        # A group with no layer is an empty dict, in every loader's tree.
        **{g: {k: draw(rows[g], k, s) for k, s in shapes.items()} if rows[g] else {} for g, shapes in _shapes(config).items()},
    }


def _layer_tensors(get, config: ModelConfig, i: int, dtype) -> dict[str, dict]:
    """Layer *i* of an HF checkpoint (get(name) -> array) by group:
    linears transposed to [in, out]; the taps [K, channels] with tap K-1 on
    the row itself (the depthwise `conv.conv.weight` [channels, 1, K] as it
    is laid out); the experts stacked [E, out, in] on the host (one
    contiguous copy; the device transposes them)."""
    p = f"model.layers.{i}."
    conv = lambda a: np.asarray(a, dtype)  # noqa: E731
    lin = lambda name: conv(np.asarray(get(p + name + ".weight")).T)  # noqa: E731
    norm = lambda name: conv(get(p + name + ".weight"))  # noqa: E731
    if config.layer_pattern[i] == "c":
        out = {
            "conv": {
                "ln": norm("operator_norm"), "in_proj": lin("conv.in_proj"), "out_proj": lin("conv.out_proj"),
                "conv_w": conv(np.asarray(get(p + "conv.conv.weight"))[:, 0, :].T),
            }
        }
    else:
        out = {
            "attn": {
                "ln": norm("operator_norm"), "q_norm": norm("self_attn.q_layernorm"), "k_norm": norm("self_attn.k_layernorm"),
                "wq": lin("self_attn.q_proj"), "wk": lin("self_attn.k_proj"), "wv": lin("self_attn.v_proj"),
                "wo": lin("self_attn.out_proj"),
            }
        }
    ff = "feed_forward."
    if i < layout(config)[0]:
        out["dense"] = {"ln": norm("ffn_norm"), "wg": lin(ff + "w1"), "wu": lin(ff + "w3"), "wd": lin(ff + "w2")}
        return out
    stack = lambda which: conv(  # noqa: E731
        np.stack([np.asarray(get(f"{p}{ff}experts.{j}.{which}.weight")) for j in range(config.n_routed_experts)])
    )
    out["moe"] = {"ln": norm("ffn_norm"), "wr": lin(ff + "gate"), "br": np.asarray(get(p + ff + "expert_bias"), np.float32)}
    out["experts"] = {"we_g": stack("w1"), "we_u": stack("w3"), "we_d": stack("w2")}
    return out


def stream_params_from_hf(source, config: ModelConfig, pad: int = 0) -> Params:
    """`shared.stream_stacks` over this family's groups (an expert layer is
    1.2 GB in bf16: the host holds two, the device never a stack twice). An
    operator's layers do not follow each other, so `conv` and `attn` name
    the row of every layer; the head is the embedding."""
    lead, n, by_kind = layout(config)[0], _group_rows(config), rows_of(config)
    row_of = {kind: {layer: r for r, layer in enumerate(layers)} for kind, layers in by_kind.items()}
    rows = {
        "conv": (n["conv"], row_of["c"]), "attn": (n["attn"], row_of["a"]),
        "dense": (n["dense"], 0), "moe": (n["moe"], lead), "experts": (n["experts"], lead),
    }
    return shared.stream_stacks(source, config, pad, _layer_tensors, rows, norm="model.embedding_norm.weight", head=None)


params_from_hf = shared.params_from_hf_by(stream_params_from_hf)


# ---------------------------------------------------------------------------
# Cache (the routes are `models/shared.py`'s for a stack with one pool: flash for a cold
# call of whole 256-row tiles, the paged kernels, or the portable gather;
# each on heads of 128 lanes, two of the published 64 side by side)


def init_paged_cache(config: ModelConfig, num_pages: int, page_size: int, dtype=None, slots: int = 1) -> Params:
    """The page pool of the attention layers (*num_pages* logical pages a
    layer, KV heads side by side in 128-lane rows: module docstring) and,
    by slot, every convolution's tail."""
    dtype = dtype or jnp.dtype(config.dtype)
    rows, n = rows_of(config), heads_a_tile(config.head_dim_)
    return {
        "kv": jnp.zeros((len(rows["a"]) * num_pages, page_size, 2 * config.num_kv_heads // n, n * config.head_dim_), dtype),
        "conv": jnp.zeros((len(rows["c"]), slots, config.conv_kernel - 1, config.hidden_size), dtype),
    }


# ---------------------------------------------------------------------------
# Forward


def apply(
    params: Params,
    config: ModelConfig,
    tokens: jnp.ndarray,  # [B, S] int32
    positions: jnp.ndarray,  # [B, S] int32 absolute positions, contiguous along S
    cache: Params | None = None,  # init_paged_cache
    page_table: jnp.ndarray | None = None,  # [B, max_pages]
    n_real: jnp.ndarray | None = None,  # [B] real rows of each row of the call (they come first)
    slots: jnp.ndarray | None = None,  # [B] the slot of each prefill row; None: a decode step, rows are slots
    carried: jnp.ndarray | None = None,  # [B] bool: the row continues its slot's tail; None: every row starts from zeros
    logits_idx: jnp.ndarray | None = None,
    left_aligned: bool = False,  # caller guarantees positions == arange(S)
    forced_choices: jnp.ndarray | None = None,  # [expert layers, B*S, k]: route by these (debug)
    return_choices: bool = False,  # also return the routers' choices (debug; no timed program asks)
    live=None,  # models/base.py::LiveRows of a decode step whose rows arrive live slots first
    **unsupported,  # what llama.apply takes and this family does not run (return_hidden, lora, ...)
):
    """Run the decoder over the paged pool and the slots' tails. Returns
    (logits, cache) with `cache["moe_hits"]` the (layer, expert) pairs that
    got a row; with *return_choices* also the choices [expert layers, B*S,
    k]. A position whose table entry is 0 writes to the pool's trash page."""
    if cache is None or page_table is None or n_real is None or unsupported:
        raise ValueError("lfm2_moe: a call without the paged pool and the slots' tails (embeddings, scoring) is not supported")
    B, S = tokens.shape
    decode = slots is None
    dtype = jnp.dtype(config.dtype)
    H, Kv, h = config.num_heads, config.num_kv_heads, config.head_dim_
    eps, top_k = config.rms_norm_eps, config.num_experts_per_tok
    lead, per, periods = layout(config)
    by_kind = rows_of(config)
    route = cached_attention_route(config, S, left_aligned, True)
    inv_freq = jnp.asarray(rope_frequencies(h, config.rope_theta, None))
    n_tile = heads_a_tile(h)

    pool, tails = cache["kv"], cache["conv"]
    page, max_pages = pool.shape[1], page_table.shape[1]
    pool_rows = pool.shape[0] // len(by_kind["a"])  # logical pages a layer
    skv = max_pages * page
    w_idx = jnp.clip(positions // page, 0, max_pages - 1)
    w_pages = jnp.where(positions < skv, jnp.take_along_axis(page_table, w_idx, axis=1), 0)
    w_offs = positions % page
    n_real = n_real.astype(jnp.int32)
    if decode:
        # The convolutions of a decode step work in slot order.
        live_slots = (n_real if live is None else live.restore(n_real)) > 0
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(dtype)

    def conv_op(x, w, tails, j):
        """Row j of the convolutions: reads and writes row j of the tails."""
        with jax.named_scope("conv"):
            u = rms_norm(x, w["ln"], eps)
            if decode:
                if live is not None:
                    u = live.restore(u)
                out, tails = shortconv.gated_short_conv_step(tails, j, u[:, 0], live_slots, w["in_proj"], w["conv_w"], w["out_proj"])
                out = out[:, None]
                if live is not None:
                    (out,) = live.take(out)
                return x + out, tails
            if carried is None:
                tail = jnp.zeros((B, *tails.shape[2:]), tails.dtype)
            else:
                tail = tails[j, slots] * carried[:, None, None].astype(tails.dtype)
            out, tail = shortconv.gated_short_conv(u, tail, n_real, w["in_proj"], w["conv_w"], w["out_proj"])
            # The write back stands under the scope of the work it is part of.
            with jax.named_scope("conv.taps"):
                tails = tails.at[j, slots].set(tail)
            return x + out, tails

    def attn_op(x, w, pool, row0):
        with jax.named_scope("attn"):
            a = rms_norm(x, w["ln"], eps)
            q = jnp.dot(a, w["wq"]).reshape(B, S, H, h)
            k = jnp.dot(a, w["wk"]).reshape(B, S, Kv, h)
            v = jnp.dot(a, w["wv"]).reshape(B, S, Kv, h)
            with jax.named_scope("attn.qk_norm"):
                q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
            q, k = apply_rope(q, k, positions, inv_freq)
            packed = pack_kv(k, v, n_tile)  # [B, S, 2 * Kv / n, n * h]
            pool = pool.at[w_pages + row0, w_offs].set(packed.astype(pool.dtype))
            with jax.named_scope("attn.kernel"):
                q = widen_queries(q, Kv, n_tile)
                if route == "flash":
                    from kubeai_tpu.ops.flash_attention import flash_attention_tpu

                    o = flash_attention_tpu(q, packed[:, :, 0::2], packed[:, :, 1::2], causal=True, sm_scale=h**-0.5)
                elif route == "paged_kernel":
                    o = paged_attention_ragged(
                        q, pool, page_table + row0, positions[:, -1] + 1, scale=h**-0.5,
                        live_rows=None if live is None else live.count,
                    )
                else:
                    gathered = pool[page_table + row0]  # [B, max_pages, page, 2 * Kv / n, n * h]
                    k_att = gathered[..., 0::2, :].reshape(B, skv, Kv // n_tile, n_tile * h)
                    v_att = gathered[..., 1::2, :].reshape(B, skv, Kv // n_tile, n_tile * h)
                    mask = jnp.arange(skv, dtype=jnp.int32)[None, None, :] <= positions[:, :, None]
                    o = attention(q, k_att, v_att, mask, scale=h**-0.5)
                o = narrow_outputs(o, Kv, n_tile)
            return x + jnp.dot(o.reshape(B, S, H * h), w["wo"]), pool

    def dense_ffn(x, w):
        with jax.named_scope("ffn"):
            return x + _swiglu(rms_norm(x, w["ln"], eps), w["wg"], w["wu"], w["wd"])

    def expert_ffn(x, w, forced, e):
        with jax.named_scope("moe"):
            m = rms_norm(x, w["ln"], eps).reshape(B * S, -1)
            with jax.named_scope("moe.router"):
                idx, weights = moe.route_sigmoid(
                    m, w["wr"], w["br"], top_k, config.norm_topk_prob, config.routed_scaling_factor, forced=forced,
                )
            # The experts' stacks are not sliced: layer e's are groups of the whole (ops/moe.py).
            experts = params["experts"]
            f, hit = moe.routed_experts(m, idx, weights, experts["we_g"], experts["we_u"], experts["we_d"], layer=e)
            return x + f.reshape(B, S, -1), hit, idx

    # Each kind of sub-block is ONE function of the program, called where a
    # layer has it (models/afmoe.py says what that saves a start): rows of
    # the stacks, of the pool and of the tails go in as values.
    conv_of, attn_of, experts_of = jax.jit(conv_op), jax.jit(attn_op), jax.jit(expert_ffn)
    i32 = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731

    def operator(x, pool, tails, kind, r):
        """Row *r* (an int, or traced in the scan) of operator *kind*."""
        if kind == "c":
            x, tails = conv_of(x, _take(params["conv"], r), tails, i32(r))
        else:
            x, pool = attn_of(x, _take(params["attn"], r), pool, i32(r * pool_rows))
        return x, pool, tails

    for i in range(lead):  # the dense layers, unrolled: each has its own operator
        kind = config.layer_pattern[i]
        x, pool, tails = operator(x, pool, tails, kind, by_kind[kind].index(i))
        x = dense_ffn(x, _take(params["dense"], i))

    # A period's layers by operator: how many of each a period holds, and
    # how many of its kind stand before each layer of the period.
    pattern = config.layer_pattern[lead : lead + per]
    first = {kind: sum(layer < lead for layer in by_kind[kind]) for kind in "ca"}

    def step(carry, xs):
        x, pool, tails, hits = carry
        n, forced = xs
        chosen = []
        for j, kind in enumerate(pattern):
            r = first[kind] + n * pattern.count(kind) + pattern[:j].count(kind)
            x, pool, tails = operator(x, pool, tails, kind, r)
            e = n * per + j
            x, hit, idx = experts_of(x, _take(params["moe"], e), None if forced is None else forced[j], i32(e))
            hits = hits + hit
            chosen.append(idx)
        return (x, pool, tails, hits), (jnp.stack(chosen) if return_choices else None)

    (x, pool, tails, hits), choices = jax.lax.scan(
        step, (x, pool, tails, jnp.zeros((), jnp.int32)),
        (
            jnp.arange(periods, dtype=jnp.int32),
            None if forced_choices is None else forced_choices.reshape(periods, per, *forced_choices.shape[1:]),
        ),
    )

    if live is not None:
        x = live.restore(x)  # slot order again, before anything [B, V]
    x = rms_norm(x, params["final_norm"], eps)
    with jax.named_scope("lm_head"):
        if logits_idx is not None:
            x = x[jnp.arange(B)[:, None], logits_idx[:, None]]
        logits = jnp.dot(x, params["embed"].T).astype(jnp.float32)  # tied: the head is the embedding
    new_cache = {"kv": pool, "conv": tails, "moe_hits": hits}
    if return_choices:
        return logits, new_cache, choices.reshape(periods * per, B * S, top_k)
    return logits, new_cache


prefill_paged, prefill_paged_cold, decode_step_paged = shared.paged_entry_points(apply, "lfm2_moe", by_slot=bool(SLOT_STATE))

# The seam's other names (models/__init__.py says what each rule means).
REUSE_WHOLE_PREFILL_CALLS = False  # moot: PREFIX_REUSE is False
init_lora_bank = None
layer_kinds = None


def config_keys(get) -> dict:
    """The LFM2-MoE keys of a published config.json as ModelConfig fields;
    the module docstring says what each means. What this module does not
    compute is refused here, by name. `layer_types` may be longer than the
    depth (a checkpoint cut in depth keeps the published list): the first
    `num_hidden_layers` entries are the model's. The family has no field
    of its own: the pattern, the taps, the leading dense layers, the
    experts and the router's norm and scale reuse the fields other
    families brought. `use_expert_bias` false is a bias of zeros in the
    checkpoint; `max_position_embeddings` bounds nothing here."""
    L = get("num_hidden_layers")
    types = get("layer_types")
    if not isinstance(types, (list, tuple)) or len(types) < L or set(types) - set(KINDS):
        raise ValueError(f"lfm2_moe: layer_types must give conv or full_attention for each of the {L} layers")
    if get("conv_bias"):
        raise ValueError("lfm2_moe: conv_bias true is not supported")
    if not get("use_expert_bias", True):
        raise ValueError("lfm2_moe: use_expert_bias false is not supported (the checkpoint must hold feed_forward.expert_bias)")
    taps = get("conv_L_cache")
    if not taps or taps < 2:
        raise ValueError(f"lfm2_moe: conv_L_cache {taps!r} is not supported (at least 2 taps)")
    rope = get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default" or get("rope_scaling"):
        raise ValueError("lfm2_moe: a rope_type other than default is not supported")
    return dict(
        rms_norm_eps=get("norm_eps", 1e-5),
        rope_theta=float(rope.get("rope_theta") or get("rope_theta", 1000000.0)),
        tie_word_embeddings=bool(get("tie_word_embeddings", True)),
        layer_pattern="".join(KINDS[t] for t in types[:L]),
        conv_kernel=int(taps),
        first_k_dense_replace=min(get("num_dense_layers") or 0, L),
        n_routed_experts=get("num_experts") or 0,
        moe_intermediate_size=get("moe_intermediate_size") or 0,
        norm_topk_prob=bool(get("norm_topk_prob", True)),
        routed_scaling_factor=float(get("routed_scaling_factor") or 1.0),
    )


def param_counts(mc: ModelConfig) -> tuple[float, float]:
    """(held, active a token): every layer holds a gated short convolution
    or QK-normed grouped-query attention, and its two norms; the first
    `first_k_dense_replace` layers a dense feed-forward, the rest a router
    with its selection bias and `n_routed_experts` experts, of which a
    token passes through `num_experts_per_tok`; the head is the embedding
    (counted once, and multiplied by). LFM2-24B-A2B at 10 of 40 layers:
    5.27G held, 0.74G a token; at 40: 23.8G and 2.33G. Held to
    perfbench/families/lfm2_moe_counts.py by tests/test_lfm2_moe.py."""
    D, L, V, K = mc.hidden_size, mc.num_layers, mc.vocab_size, mc.conv_kernel
    H, Kv, h = mc.num_heads, mc.num_kv_heads, mc.head_dim_
    rows = rows_of(mc)
    conv = D * 3 * D + K * D + D * D + D
    attn = 2 * D * H * h + 2 * D * Kv * h + 2 * h + D
    n_dense = min(mc.first_k_dense_replace, L)
    expert = 3 * D * mc.moe_intermediate_size
    outside = D * mc.n_routed_experts + mc.n_routed_experts + D
    always = len(rows["c"]) * conv + len(rows["a"]) * attn + n_dense * (3 * D * mc.intermediate_size + D) + (L - n_dense) * outside
    total = V * D + D + always + (L - n_dense) * mc.n_routed_experts * expert
    active = V * D + D + always + (L - n_dense) * mc.num_experts_per_tok * expert
    return float(total), float(active)
