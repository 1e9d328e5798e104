"""SmallThinker decoder (`model_type: smallthinker`) in functional JAX: a
stack whose layers come in PERIODS (the published 21B-A3B: one layer that
attends to the whole context without rope, then three that attend to the
last `sliding_window_size` keys with rope), every layer with routed
experts chosen from the layer's INPUT. With `x` a layer's input:

    r = x W_r                                  # router logits, before any norm
    a = rmsnorm(x; g1);  q, k, v = a Wq, a Wk, a Wv        # no biases
    q, k = rope(q), rope(k)      where rope_layout[l] == 1 # half-split pairs
    o = softmax(q k^T / sqrt(h) + causal [+ key j > i - window
        where sliding_window_layout[l] == 1]) v;  h = x + o Wo
    m = rmsnorm(h; g2);  S = top-k of r;  w = softmax(r[S])
    y = sum_{e in S} w_e (relu(m Wg_e) * (m Wu_e)) Wd_e;   x' = h + y

The engine reaches a model through `kubeai_tpu.models.family(config)`;
`models/__init__.py` declares what this module gives it. What the family
does not run is refused at load (`refuse_unsupported`, and `config_keys`
for what the config itself asks).

**The scan runs over periods.** Inside its body the period's layers are
unrolled, so each kind of layer is a call of its own: the window is a
static argument where a kernel wants it static, and a layer without rope
traces no rotation. Experts are read from the whole stack in place, as
`models/deepseek.py` reads them (`ops/moe.py::routed_experts`, `layer=`).

**Two pools, two tables.** Full layers and window layers keep their keys
and values in pools of their own: `cache["kv"]` is `[Lf*Pf, page, 2*Kv,
h]` and `cache["kv_window"]` `[Lw*Pw, page, 2*Kv, h]`, each laid out as
`llama.init_paged_cache` lays its one (K even, V odd; a layer owns `P`
rows; logical page 0 of every layer is its trash page). A slot's block-
table row is two tables side by side, `[full | window]`, each
`max_pages` wide and each indexed by the position's page: the host
(`engine/paging.py::WindowPages`) keeps in the window table only the
pages some query of the next call can still see and hands the others
back, so a slot holds at most `(window + chunk) / page + 1` window pages
whatever its length (`chunk`: the widest chunk call, `engine/core.py::
wide_chunk`, 2048 rows where a prompt can be that long: 97 pages at the
published window), while its full pages grow with it.

**A window layer reads its window.** At decode the kernel's
`sliding_window` only masks (the library's ragged kernel, one query row a
slot): it would still copy every page up to the sequence's end. So the
window layers hand it the table FROM the first page a query of this call
can see (`first = max(0, pos0 - window + 1) // page`, a gather of
`(S + window - 2) // page + 2` columns) with the lengths shifted by
`first * page`: every mask of the kernel is relative (a query sits at
`kv_len - S + i`), keys carry their rope from when they were written, so
nothing else moves. The portable route gathers the same columns, and a
chunk's kernel (`ops/chunk_attention.py`, more than one row a slot) is
handed them too, one way for every route: it starts each query tile at
the page of the first key the tile can see and stops at its last row's
block on its own, wherever the table starts, so behind the shift it
walks what it would walk over the whole table.

**Routes** (`cached_attention_route`): cold prefill of 256 rows or more
takes the flash kernel for both kinds where the call is no longer than
the window (the window mask is then all true; the engine's largest
bucket, 1024, the longest cold call, is a quarter of the published
window); decode, chunks
behind cached tokens and every other cold call take the paged kernels on
the chip ("paged_kernel": `ops/paged_attention.py`, the library's ragged
kernel for one row a slot and the repo's chunk kernel for more); everything on the CPU the portable
gather ("xla") unless a test asks for the kernel's twin.

**What is limited for this family, stated here once.**
`REUSE_WHOLE_PREFILL_CALLS`: a prefix found in the cache is used in
whole prefill calls, as `models/deepseek.py` says and for its reason (a
router turns one rounding between two call shapes into other experts).
A hit also needs the window pool to hold the pages the first new query
can see (`WindowPages.match`): window pages a slot has handed back stay
content-registered until the pool needs them, so a recent prompt's
prefix is found and an old one's is recomputed. `KV_PARK = False`: a
slot's state is not parked, restored or handed off (the wire format
carries one pool's pages; a window slot has two).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from kubeai_tpu.models import shared
from kubeai_tpu.models.base import ModelConfig, layout_period
from kubeai_tpu.ops import moe
from kubeai_tpu.ops.attention import attention
from kubeai_tpu.ops.norms import rms_norm
from kubeai_tpu.ops.paged_attention import paged_attention_ragged
from kubeai_tpu.ops.rope import apply_rope, rope_frequencies

Params = dict[str, Any]

PAGED_KERNEL_LABEL = "ragged"
REUSE_WHOLE_PREFILL_CALLS = True  # the module docstring says why
KV_PARK = False  # likewise


def period(config: ModelConfig) -> int:
    """Layers in one period of the layouts' pattern (4 as published)."""
    return layout_period(config.sliding_window_layout, config.rope_layout)


def layer_kinds(config: ModelConfig) -> tuple[int, int]:
    """(full layers, window layers) of the whole stack."""
    n_window = sum(config.sliding_window_layout)
    return config.num_layers - n_window, n_window


def window_pool_tokens(config: ModelConfig) -> int:
    """The window whose layers keep a page pool of their own (0: none)."""
    return config.sliding_window_size


def window_pages(config: ModelConfig, S: int, page: int, max_pages: int) -> int:
    """Table columns a window layer reads for a call of *S* contiguous
    queries a row: from the first page the first query can see to the
    page of the last, at the worst alignment."""
    return min(max_pages, (S + config.sliding_window_size - 2) // page + 2)


def refuse_unsupported(config: ModelConfig, quantization: str = "", tp: int = 1) -> None:
    """What this family does not run, refused at load by name."""
    shared.refuse_common("smallthinker", config, quantization, tp, "experts and both pools are unsharded")
    full, window = layer_kinds(config)
    if not full or not window:
        raise ValueError("smallthinker: a stack without both full and window layers is not supported")


# ---------------------------------------------------------------------------
# Parameters


def _shapes(config: ModelConfig) -> tuple[dict, dict]:
    """(what a layer holds outside its experts, its experts), ONE layer."""
    D, H, Kv, h = config.hidden_size, config.num_heads, config.num_kv_heads, config.head_dim_
    F, E = config.moe_intermediate_size, config.n_routed_experts
    layer = {
        "ln1": (D,), "ln2": (D,), "wr": (D, E),
        "wq": (D, H * h), "wk": (D, Kv * h), "wv": (D, Kv * h), "wo": (H * h, D),
    }
    experts = {"we_g": (E, D, F), "we_u": (E, D, F), "we_d": (E, F, D)}
    return layer, experts


def init_params(config: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random parameters in the tree the loader builds: `layers` and
    `experts` each stack every layer on a leading axis."""
    dtype = dtype or jnp.dtype(config.dtype)
    layer, experts = _shapes(config)
    L, D, V = config.num_layers, config.hidden_size, config.vocab_size
    keys = iter(jax.random.split(key, 16))

    def draw(name, shape):
        if name in ("ln1", "ln2"):
            return jnp.ones((L, *shape), dtype)
        return (jax.random.normal(next(keys), (L, *shape), jnp.float32) * shape[-2] ** -0.5).astype(dtype)

    return {
        "embed": (jax.random.normal(next(keys), (V, D), jnp.float32) * 0.02).astype(dtype),
        "final_norm": jnp.ones((D,), dtype),
        "lm_head": (jax.random.normal(next(keys), (D, V), jnp.float32) * 0.02).astype(dtype),
        "layers": {k: draw(k, s) for k, s in layer.items()},
        "experts": {k: draw(k, s) for k, s in experts.items()},
    }


def _layer_tensors(get, config: ModelConfig, i: int, dtype) -> dict[str, dict]:
    """Layer *i* of an HF checkpoint (get(name) -> array) by group of the
    tree: linears transposed to [in, out]; the experts stacked [E, out,
    in] on the host (one contiguous copy; the device transposes them,
    `shared.stream_stacks`)."""
    p = f"model.layers.{i}."
    conv = lambda a: np.asarray(a, dtype)  # noqa: E731
    lin = lambda name: conv(np.asarray(get(p + name + ".weight")).T)  # noqa: E731
    stack = lambda which: conv(  # noqa: E731
        np.stack([np.asarray(get(f"{p}block_sparse_moe.experts.{j}.{which}.weight")) for j in range(config.n_routed_experts)])
    )
    layer = {
        "ln1": conv(get(p + "input_layernorm.weight")),
        "ln2": conv(get(p + "post_attention_layernorm.weight")),
        "wr": lin("block_sparse_moe.primary_router"),
        "wq": lin("self_attn.q_proj"), "wk": lin("self_attn.k_proj"),
        "wv": lin("self_attn.v_proj"), "wo": lin("self_attn.o_proj"),
    }
    return {"layers": layer, "experts": {"we_g": stack("gate"), "we_u": stack("up"), "we_d": stack("down")}}


def stream_params_from_hf(source, config: ModelConfig, pad: int = 0) -> Params:
    """`shared.stream_stacks` over this family's two groups, each of every
    layer (a layer's experts are 0.75 GB in bf16: the host holds two
    layers, the device never a stack twice)."""
    L = config.num_layers
    return shared.stream_stacks(source, config, pad, _layer_tensors, {"layers": (L, 0), "experts": (L, 0)})


params_from_hf = shared.params_from_hf_by(stream_params_from_hf)


# ---------------------------------------------------------------------------
# Cache and routes


def init_paged_cache(config: ModelConfig, num_pages: int, page_size: int, dtype=None, window_pages: int = 0) -> Params:
    """The two pools (module docstring): *num_pages* logical pages a full
    layer, *window_pages* a window layer."""
    dtype = dtype or jnp.dtype(config.dtype)
    full, window = layer_kinds(config)
    page = (page_size, 2 * config.num_kv_heads, config.head_dim_)
    return {
        "kv": jnp.zeros((full * num_pages, *page), dtype),
        "kv_window": jnp.zeros((window * max(window_pages, 1), *page), dtype),
    }


def cached_attention_route(config: ModelConfig, S: int, left_aligned: bool, paged: bool) -> str:
    """The attention implementation a cached call of *S* queries a row
    takes, for both kinds of layer: "flash" (cold prefill of whole
    256-row tiles, no longer than the window: the window mask is all
    true), "paged_kernel" (`ops/paged_attention.py` over pages in place) or
    "xla" (the portable gather of the same pages)."""
    if config.use_flash_prefill and left_aligned and S >= 256 and S % 256 == 0 and S <= config.sliding_window_size:
        return "flash"
    if config.use_paged_kernel and paged:
        return "paged_kernel"
    return "xla"


# ---------------------------------------------------------------------------
# Forward


class TwoPools:
    """One call's view of the two pools (module docstring), for every
    family whose stack mixes full and window layers (`models/afmoe.py`
    too): where each kind of layer writes this call's keys and values,
    and what it reads. Full: the slot's whole table. Window: the columns
    from the first page this call's first query can see, the lengths
    shifted by as much. *positions* [B, S] are contiguous along S;
    *page_table* is `[full | window]`; *route* is
    `cached_attention_route`'s; *live* a decode step's LiveRows."""

    def __init__(self, config: ModelConfig, cache: Params, page_table, positions, route: str, live=None):
        self.config, self.positions, self.route, self.live = config, positions, route, live
        S = positions.shape[1]
        window = config.sliding_window_size
        n_full, n_window = layer_kinds(config)
        self.pools = {0: cache["kv"], 1: cache["kv_window"]}
        self.page = page = self.pools[0].shape[1]
        self.rows = {0: self.pools[0].shape[0] // n_full, 1: self.pools[1].shape[0] // n_window}  # logical pages a layer
        max_pages = page_table.shape[1] // 2
        tables = {0: page_table[:, :max_pages], 1: page_table[:, max_pages:]}
        skv = max_pages * page
        w_idx = jnp.clip(positions // page, 0, max_pages - 1)
        self.w_offs = positions % page
        self.w_pages = {
            kind: jnp.where(positions < skv, jnp.take_along_axis(tables[kind], w_idx, axis=1), 0) for kind in (0, 1)
        }
        last = positions[:, -1]
        first = jnp.maximum(positions[:, 0] - window + 1, 0) // page
        Wp = window_pages(config, S, page, max_pages)
        cols = jnp.clip(first[:, None] + jnp.arange(Wp, dtype=jnp.int32)[None, :], 0, max_pages - 1)
        # By kind: a table, the keys' count and the position of the table's first key.
        self.read = {
            0: (tables[0], last + 1, jnp.zeros_like(first)),
            1: (jnp.take_along_axis(tables[1], cols, axis=1), last + 1 - first * page, first * page),
        }
        # Which of its kind each layer of a period is: its pool rows follow.
        kinds = config.sliding_window_layout[: period(config)]
        self.nth = [sum(1 for j2 in range(j) if kinds[j2] == kinds[j]) for j in range(len(kinds))]
        self.per_kind = {kind: sum(1 for v_ in kinds if v_ == kind) for kind in (0, 1)}
        self.kinds = kinds

    def row0(self, n, j: int):
        """The first pool row of layer *j* of period *n* (in its kind's pool)."""
        kind = self.kinds[j]
        return (n * self.per_kind[kind] + self.nth[j]) * self.rows[kind]

    def write(self, pool, row0, kind: int, k, v):
        """*pool* with this call's keys and values [B, S, Kv, h] in the layer's pages."""
        B, S, Kv, h = k.shape
        interleaved = jnp.stack([k, v], axis=3).reshape(B, S, 2 * Kv, h)
        return pool.at[self.w_pages[kind] + row0, self.w_offs].set(interleaved.astype(pool.dtype))

    def attend(self, q, k, v, pool, row0, kind: int):
        """The attention of one layer of *kind* over its pool (already
        holding this call's keys and values)."""
        if self.route == "flash":
            from kubeai_tpu.ops.flash_attention import flash_attention_tpu

            return flash_attention_tpu(q, k, v, causal=True)
        window, positions = self.config.sliding_window_size, self.positions
        table, kv_len, key0 = self.read[kind]
        if self.route == "paged_kernel":
            return paged_attention_ragged(
                q, pool, table + row0, kv_len, sliding_window=window if kind else None,
                live_rows=None if self.live is None else self.live.count,
            )
        B, _, Kv, h = k.shape
        gathered = pool[table + row0]  # [B, columns, page, 2Kv, h]
        n_keys = table.shape[1] * self.page
        k_att = gathered[..., 0::2, :].reshape(B, n_keys, Kv, h)
        v_att = gathered[..., 1::2, :].reshape(B, n_keys, Kv, h)
        key_pos = key0[:, None, None] + jnp.arange(n_keys, dtype=jnp.int32)[None, None, :]
        mask = key_pos <= positions[:, :, None]
        if kind:
            mask = jnp.logical_and(mask, key_pos > positions[:, :, None] - window)
        return attention(q, k_att, v_att, mask)


def apply(
    params: Params,
    config: ModelConfig,
    tokens: jnp.ndarray,  # [B, S] int32
    positions: jnp.ndarray,  # [B, S] int32 absolute positions, contiguous along S
    cache: Params | None = None,  # init_paged_cache
    page_table: jnp.ndarray | None = None,  # [B, 2 * max_pages]: [full | window]
    logits_idx: jnp.ndarray | None = None,
    left_aligned: bool = False,  # caller guarantees positions == arange(S)
    forced_choices: jnp.ndarray | None = None,  # [L, B*S, k]: route by these (debug)
    return_choices: bool = False,  # also return the routers' choices (debug; no timed program asks)
    live=None,  # models/base.py::LiveRows of a decode step whose rows arrive live slots first
    **unsupported,  # what llama.apply takes and this family does not run (return_hidden, lora, ...)
):
    """Run the decoder over the two paged pools. Returns (logits, cache)
    with `cache["moe_hits"]` the (layer, expert) pairs that got a row;
    with *return_choices* also the choices [L, B*S, k]. A position whose
    table entry is 0 (padding past a prompt, a page handed back, a
    finished slot's overrun) writes to the layer's trash page."""
    if cache is None or page_table is None or unsupported:
        raise ValueError("smallthinker: a call without the paged pool (embeddings, scoring) is not supported")
    B, S = tokens.shape
    H, Kv, h, L = config.num_heads, config.num_kv_heads, config.head_dim_, config.num_layers
    eps, top_k = config.rms_norm_eps, config.num_experts_per_tok
    per = period(config)
    kinds = config.sliding_window_layout[:per]
    ropes = config.rope_layout[:per]
    inv_freq = jnp.asarray(rope_frequencies(h, config.rope_theta, None))
    route = cached_attention_route(config, S, left_aligned, True)

    two = TwoPools(config, cache, page_table, positions, route, live)
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(jnp.dtype(config.dtype))

    def layer(x, w, pool, row0, kind, rope, forced, l):
        scope = "attn.window" if kind else "attn.full"
        with jax.named_scope("moe"), jax.named_scope("moe.router"):
            # The router reads the layer's INPUT, before any norm.
            xt = x.reshape(B * S, -1)
            logits_r = jnp.dot(xt.astype(jnp.float32), w["wr"].astype(jnp.float32), preferred_element_type=jnp.float32)
            idx, weights = moe.route_softmax_topk(logits_r, top_k, forced=forced)
        with jax.named_scope(scope):
            a = rms_norm(x, w["ln1"], eps)
            q = jnp.dot(a, w["wq"]).reshape(B, S, H, h)
            k = jnp.dot(a, w["wk"]).reshape(B, S, Kv, h)
            v = jnp.dot(a, w["wv"]).reshape(B, S, Kv, h)
            if rope:
                q, k = apply_rope(q, k, positions, inv_freq)
            pool = two.write(pool, row0, kind, k, v)
            with jax.named_scope("attn.kernel"):
                o = two.attend(q, k, v, pool, row0, kind)
            x = x + jnp.dot(o.reshape(B, S, H * h), w["wo"])
        with jax.named_scope("moe"):
            m = rms_norm(x, w["ln2"], eps).reshape(B * S, -1)
            # The experts' stacks are not sliced: layer l's are groups of the whole (ops/moe.py).
            y, hit = moe.routed_experts(
                m, idx, weights, params["experts"]["we_g"], params["experts"]["we_u"], params["experts"]["we_d"],
                layer=l, act=jax.nn.relu,
            )
            x = x + y.reshape(B, S, -1)
        return x, pool, hit, idx

    def step(carry, xs):
        x, pool_f, pool_w, hits = carry
        n, forced = xs
        held = {0: pool_f, 1: pool_w}
        chosen = []
        for j in range(per):  # unrolled: a period's layers differ in kind
            kind, l = kinds[j], n * per + j
            row0 = two.row0(n, j)
            # Each layer's weights are read from the whole stack at its own
            # index: a period's block sliced out first and then indexed is
            # a copy of the block (73 MB of `wo` a period a step on the chip).
            w = jax.tree.map(lambda a_: jax.lax.dynamic_index_in_dim(a_, l, keepdims=False), params["layers"])
            x, held[kind], hit, idx = layer(
                x, w, held[kind], row0, kind, ropes[j], None if forced is None else forced[j], l,
            )
            hits = hits + hit
            chosen.append(idx)
        return (x, held[0], held[1], hits), (jnp.stack(chosen) if return_choices else None)

    n_periods = L // per
    (x, pool_f, pool_w, hits), choices = jax.lax.scan(
        step, (x, two.pools[0], two.pools[1], jnp.zeros((), jnp.int32)),
        (
            jnp.arange(n_periods, dtype=jnp.int32),
            None if forced_choices is None else forced_choices.reshape(n_periods, per, *forced_choices.shape[1:]),
        ),
    )

    if live is not None:
        x = live.restore(x)  # slot order again, before anything [B, V]
    x = rms_norm(x, params["final_norm"], eps)
    with jax.named_scope("lm_head"):
        if logits_idx is not None:
            x = x[jnp.arange(B)[:, None], logits_idx[:, None]]
        logits = jnp.dot(x, params["lm_head"]).astype(jnp.float32)
    new_cache = {"kv": pool_f, "kv_window": pool_w, "moe_hits": hits}
    if return_choices:
        return logits, new_cache, choices.reshape(L, B * S, top_k)
    return logits, new_cache


prefill_paged, prefill_paged_cold, decode_step_paged = shared.paged_entry_points(apply, "smallthinker")

# The seam's other names (models/__init__.py says what each rule means).
PREFIX_REUSE = True
SLOT_STATE = ()
init_lora_bank = None


def config_keys(get) -> dict:
    """The SmallThinker keys of a published config.json as ModelConfig
    fields. What this module does not compute is refused here, by name.
    The layouts may be longer than the depth (a checkpoint cut in depth
    keeps the published lists): the first `num_hidden_layers` entries are
    the model's."""
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


def param_counts(mc: ModelConfig) -> tuple[float, float]:
    """(held, active a token): every layer holds grouped-query attention,
    a router and `n_routed_experts` experts, of which a token passes
    through `num_experts_per_tok`; no dense layer, no shared expert. The
    published 21B-A3B at 12 layers: 4.78G in layers + 0.78G outside held;
    0.68G + 0.39G a token (56.5M a layer with 6 experts, and the head).
    Held to perfbench/families/smallthinker_counts.py by
    tests/test_smallthinker.py."""
    D, L, V = mc.hidden_size, mc.num_layers, mc.vocab_size
    H, Kv, h = mc.num_heads, mc.num_kv_heads, mc.head_dim_
    attn = D * (H + 2 * Kv) * h + H * h * D + 2 * D
    expert = 3 * D * mc.moe_intermediate_size
    router = D * mc.n_routed_experts
    total = 2 * V * D + D + L * (attn + router + mc.n_routed_experts * expert)
    # Active leaves the embedding table out (a row is looked up).
    active = V * D + D + L * (attn + router + mc.num_experts_per_tok * expert)
    return float(total), float(active)
