"""DeepSeek-V3-family decoder (`model_type: deepseek_v3`) in functional JAX:
latent attention (MLA, no query low-rank) over latent pages, a leading run
of dense layers, then layers of sigmoid-routed experts beside shared ones.

The engine reaches a model through `kubeai_tpu.models.family(config)`;
`models/__init__.py` declares what this module gives it. What the family
does not run is refused at load, one line each (`refuse_unsupported`,
and `config_keys` for what the config itself asks).

**What a token caches** is ONE vector a layer, shared by all heads:
`[c | k_rope]`, the normed latent (kv_lora_rank) and the rotated rope key
(qk_rope_head_dim): 576 values for kanana-2. The pool is
`[L*P, page, W]` with W the latent width padded to a multiple of 128
lanes (576 -> 640, zeros behind): the chip lays a bf16 array out in
(16, 128) tiles, so a 576-wide row occupies 640 in HBM either way; stored
at 640 the padding is the program's, a page is DMA'd whole, and a query
padded with zeros scores against the whole row in one dot. The manager,
the prefix cache and park/restore address whole pages by their row and
never look inside one, and the wire format (`engine/kvstate.py`) carries
the page's own trailing shape: a latent page is a page of another width.

**One attention form, the absorbed one, and one prefill route.** With
`W_kvb` split per head into `W_uk [dn, r]` and `W_uv [r, dv]` (once, at
load): `score = (q_nope W_uk) . c + q_rope . k_rope`, `o = (P c) W_uv`: 32
query heads against one key/value "head" read in place. Every prefill,
cold or behind cached tokens, writes its latents to the pool and then
attends over the table's pages in `ops/mla_attention.py::
latent_attention_paged`. (Cold prefill could run the expanded form, keys
of 192 and values of 128, through the flash kernel at 0.3x the attention
FLOPs; attention is a sixth of a 600-token prompt's FLOPs here, and a
second form is a second thing to keep right.) Routes
(`cached_attention_route`): decode through `ops/mla_attention.py`'s paged
Pallas kernel on the chip ("paged_kernel"); everything else in XLA over
the pages ("xla").

**A prefix found in the cache is used in whole prefill calls**
(`REUSE_WHOLE_PREFILL_CALLS`, read by `engine/core.py::_plan_admission`):
a hit is cut down to an edge between two calls of the prompt's COLD plan
(`engine/core.py::prefill_plan`: calls of the wide chunk, 2048 rows,
while that many tokens are left, then what they leave cut by the
deployment's measured cost: [1024, tail] where rows cost more than a read
of the weights, else ONE padded wide call; so a hit keeps `j x 2048` tokens, `j` at most the
cold plan's wide calls, or those and the 1024-row call behind them where
the plan has one), so what is left of the prompt is prefilled by
the very calls a cold prefill of it ends with (same program, same shapes,
same offsets, same bits in the pages before them) and gives the same
bits. For the same reason a family with this seam shares NO chunk call
between two prompts (`engine/core.py::pair_rows`: no `[2, rows]` program
is compiled for it): a piece padded up to a partner's rows in a two-slot
program is another program and shape than the `[1, own rows]` call the
prompt runs alone, and which prompts meet in a round is not the prompt's
to know. A dense decoder takes a hit
to the page, and the tail then runs in another bucket than the cold
prompt did: the compiler fuses and tiles by the call's shape, a token's
hidden state comes out a bf16 step apart, and there it stays a rounding.
Here one such step flips a router's 6th against its 7th choice, swaps a
sixth of a token's routed output, and moves a first-position log-prob by
up to 0.5 (chip, PR 33: 3-15 of 20 prompts gave other tokens cold than
behind their cached pages, and still 3 of 20 with excess precision off
and every sum written out in order; PERF.md section 6). The price: a
shared prefix shorter than the largest bucket is recomputed, and of a
longer one what lies past the last such edge. (The wide call is itself
another shape than two calls of 1024 rows: a prompt's tokens may differ
by such a rounding from what the narrower plan gave, and cold against
cached they still agree to the bit, which is the promise.)

**Rope on interleaved pairs** (`rope_interleave`): HF rotates the pairs
`(x[2j], x[2j+1])`; the loader permutes the rope columns of `q_proj` and
`kv_a_proj_with_mqa` to `[x0, x2, ... | x1, x3, ...]` once, after which
the repo's half-rotation `apply_rope` computes the same rotation, and q.k
is invariant under a permutation applied to both.

**Decode rows arrive live slots first** (`models/base.py::LiveRows`, as
every family's do): the layers run on them in that order and the hidden
state goes back to slot order before the final norm. What this family
does NOT do with the count: `ops/mla_attention.py`'s kernel still walks
every row (its slot loop carries a page-copy pipeline from slot to slot),
and an idle row still reads the experts its garbage chooses; both wait
for a cell below its knee on an expert configuration to judge them by.

**Program counters.** The cache a call returns carries, beside `kv`, the
scalar `moe_hits`: how many (layer, expert) pairs got at least one row in
this call. The step programs (`engine/core.py`) take it off the cache and
return it as their last output; it never enters a program.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from kubeai_tpu.models import shared
from kubeai_tpu.models.base import ModelConfig
from kubeai_tpu.models.shared import layer_counts, swiglu as _swiglu  # the names `apply` calls them by
from kubeai_tpu.ops import moe
from kubeai_tpu.ops.mla_attention import latent_attention_paged, mla_paged_decode
from kubeai_tpu.ops.norms import rms_norm
from kubeai_tpu.ops.rope import apply_rope, rope_frequencies

Params = dict[str, Any]

PAGED_KERNEL_LABEL = "mla_paged"
REUSE_WHOLE_PREFILL_CALLS = True  # the module docstring says why


def latent_width(config: ModelConfig) -> int:
    """Values a token caches a layer (576 for kanana-2)."""
    return config.kv_lora_rank + config.qk_rope_head_dim


def page_width(config: ModelConfig) -> int:
    """The latent width as stored: padded to whole 128-lane tiles."""
    return -(-latent_width(config) // 128) * 128


def refuse_unsupported(config: ModelConfig, quantization: str = "", tp: int = 1) -> None:
    """What this family does not run, refused at load by name."""
    shared.refuse_common("deepseek_v3", config, quantization, tp, "latent pages are not sharded")


# ---------------------------------------------------------------------------
# Parameters


def _shapes(config: ModelConfig) -> tuple[dict, dict, dict]:
    """(attention, dense FFN, expert FFN) parameter shapes of ONE layer."""
    D, H = config.hidden_size, config.num_heads
    dn, dr, dv, r = config.qk_nope_head_dim, config.qk_rope_head_dim, config.v_head_dim, config.kv_lora_rank
    F, Fm, E = config.intermediate_size, config.moe_intermediate_size, config.n_routed_experts
    Fs = Fm * config.n_shared_experts
    attn = {
        "ln1": (D,), "ln2": (D,), "kv_norm": (r,),
        "wq": (D, H * (dn + dr)), "wkva": (D, r + dr),
        "wuk": (H, dn, r), "wuv": (H, r, dv), "wo": (H * dv, D),
    }
    dense = {"wg": (D, F), "wu": (D, F), "wd": (F, D)}
    experts = {
        "wr": (D, E), "br": (E,),
        "we_g": (E, D, Fm), "we_u": (E, D, Fm), "we_d": (E, Fm, D),
        "ws_g": (D, Fs), "ws_u": (D, Fs), "ws_d": (Fs, D),
    }
    return attn, dense, experts


def init_params(config: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random parameters in the tree the loaders build: `dense` and `moe`
    each stack their layers on a leading axis. The router's bias is
    float32 (HF keeps `e_score_correction_bias` so) and NOT zero, so that
    a test that leaves it out fails."""
    dtype = dtype or jnp.dtype(config.dtype)
    attn, dense, experts = _shapes(config)
    n_dense, n_moe = layer_counts(config)
    keys = iter(jax.random.split(key, 64))

    def draw(n, name, shape):
        if name in ("ln1", "ln2", "kv_norm"):
            return jnp.ones((n, *shape), dtype)
        if name == "br":
            return jax.random.normal(next(keys), (n, *shape), jnp.float32) * 0.1
        fan_in = shape[-2]
        return (jax.random.normal(next(keys), (n, *shape), jnp.float32) * fan_in**-0.5).astype(dtype)

    D, V = config.hidden_size, config.vocab_size
    params: Params = {
        "embed": (jax.random.normal(next(keys), (V, D), jnp.float32) * 0.02).astype(dtype),
        "final_norm": jnp.ones((D,), dtype),
        "lm_head": (jax.random.normal(next(keys), (D, V), jnp.float32) * 0.02).astype(dtype),
        # A group with no layer is an empty dict, in every loader's tree.
        "dense": {k: draw(n_dense, k, s) for k, s in {**attn, **dense}.items()} if n_dense else {},
        "moe": {k: draw(n_moe, k, s) for k, s in {**attn, **experts}.items()} if n_moe else {},
    }
    return params


def _deinterleave(config: ModelConfig, n: int) -> np.ndarray:
    """Column order that turns interleaved rope pairs into halves."""
    if not config.rope_interleave:
        return np.arange(n)
    return np.concatenate([np.arange(0, n, 2), np.arange(1, n, 2)])


def _layer_tensors(get, config: ModelConfig, i: int, dtype) -> dict[str, dict]:
    """Layer *i* of an HF checkpoint (get(name) -> array) as this module's
    per-layer arrays, under its group of the tree (`dense` or `moe`):
    linears transposed to [in, out], `kv_b_proj` split per head into W_uk
    and W_uv, the rope columns de-interleaved, one layer's experts
    stacked on a leading axis."""
    H = config.num_heads
    dn, dr, dv, r = config.qk_nope_head_dim, config.qk_rope_head_dim, config.v_head_dim, config.kv_lora_rank
    p = f"model.layers.{i}."
    conv = lambda a: np.asarray(a, dtype)  # noqa: E731
    lin = lambda name: np.asarray(get(p + name + ".weight")).T  # noqa: E731
    perm = _deinterleave(config, dr)
    wq = lin("self_attn.q_proj").reshape(-1, H, dn + dr)
    wq = np.concatenate([wq[..., :dn], wq[..., dn:][..., perm]], axis=-1)
    wkva = lin("self_attn.kv_a_proj_with_mqa")
    wkva = np.concatenate([wkva[:, :r], wkva[:, r:][:, perm]], axis=-1)
    wkvb = np.asarray(get(p + "self_attn.kv_b_proj.weight")).reshape(H, dn + dv, r)  # [out, in] rows by head
    out = {
        "ln1": conv(get(p + "input_layernorm.weight")),
        "ln2": conv(get(p + "post_attention_layernorm.weight")),
        "kv_norm": conv(get(p + "self_attn.kv_a_layernorm.weight")),
        "wq": conv(wq.reshape(-1, H * (dn + dr))),
        "wkva": conv(wkva),
        "wuk": conv(wkvb[:, :dn, :]),
        "wuv": conv(wkvb[:, dn:, :].transpose(0, 2, 1)),
        "wo": conv(lin("self_attn.o_proj")),
    }
    if i < config.first_k_dense_replace:
        out.update(wg=conv(lin("mlp.gate_proj")), wu=conv(lin("mlp.up_proj")), wd=conv(lin("mlp.down_proj")))
        return {"dense": out}
    E = config.n_routed_experts
    # Experts stay [E, out, in] on the host (one contiguous copy); the
    # device transposes them (`shared.stream_stacks`).
    stack = lambda which: np.stack([np.asarray(get(f"{p}mlp.experts.{j}.{which}.weight")) for j in range(E)])  # noqa: E731
    out.update(
        wr=conv(lin("mlp.gate")),
        br=np.asarray(get(p + "mlp.gate.e_score_correction_bias"), np.float32),
        we_g=conv(stack("gate_proj")), we_u=conv(stack("up_proj")), we_d=conv(stack("down_proj")),
        ws_g=conv(lin("mlp.shared_experts.gate_proj")), ws_u=conv(lin("mlp.shared_experts.up_proj")),
        ws_d=conv(lin("mlp.shared_experts.down_proj")),
    )
    return {"moe": out}


def stream_params_from_hf(source, config: ModelConfig, pad: int = 0) -> Params:
    """`shared.stream_stacks` over this family's two groups (an expert
    layer of kanana-2 is 1.28 GB in bf16: the host holds two, the device
    never a group twice): `dense` holds the leading layers, `moe` the
    layers behind them."""
    n_dense, n_moe = layer_counts(config)
    return shared.stream_stacks(source, config, pad, _layer_tensors, {"dense": (n_dense, 0), "moe": (n_moe, n_dense)})


params_from_hf = shared.params_from_hf_by(stream_params_from_hf)


# ---------------------------------------------------------------------------
# Cache and routes


def init_paged_cache(config: ModelConfig, num_pages: int, page_size: int, dtype=None) -> Params:
    """The latent pool, flat over layers as `llama.init_paged_cache`'s:
    [L*P, page, W]; layer l owns rows [l*P, (l+1)*P), logical page 0 of
    every layer is its trash page. (No singleton "head" axis: the chip
    tiles an array's last two axes, and a [1, W] tile would pad one row
    to sixteen.)"""
    dtype = dtype or jnp.dtype(config.dtype)
    return {"kv": jnp.zeros((config.num_layers * num_pages, page_size, page_width(config)), dtype)}


def cached_attention_route(config: ModelConfig, S: int, left_aligned: bool, paged: bool) -> str:
    """The attention implementation `apply` takes for a cached call of *S*
    queries a row: "paged_kernel" (decode on the chip: the MLA paged
    kernel reading latent pages in place) or "xla" (every prefill, cold
    or behind cached tokens, and every call on the CPU: the portable form
    over the pages). Both in the absorbed form. (`use_flash_prefill` has
    no route here: see the module's docstring.)"""
    del left_aligned
    if config.use_paged_kernel and paged and S == 1:
        return "paged_kernel"
    return "xla"


# ---------------------------------------------------------------------------
# Forward


def apply(
    params: Params,
    config: ModelConfig,
    tokens: jnp.ndarray,  # [B, S] int32
    positions: jnp.ndarray,  # [B, S] int32 absolute positions
    cache: Params | None = None,  # init_paged_cache
    page_table: jnp.ndarray | None = None,  # [B, max_pages]
    logits_idx: jnp.ndarray | None = None,
    left_aligned: bool = False,  # caller guarantees positions == arange(S)
    forced_choices: jnp.ndarray | None = None,  # [expert layers, B*S, k]: route by these (debug)
    return_choices: bool = False,  # also return the router's choices (debug; no timed program asks)
    live=None,  # models/base.py::LiveRows of a decode step whose rows arrive live slots first
    **unsupported,  # what llama.apply takes and this family does not run (return_hidden, lora, ...)
):
    """Run the decoder over the paged latent pool. Returns (logits,
    cache) with `cache["moe_hits"]` the (layer, expert) pairs that got a
    row; with *return_choices* also the choices [expert layers, B*S, k].
    Writes and out-of-span positions as in `llama.apply`."""
    if cache is None or page_table is None or unsupported:
        raise ValueError("deepseek_v3: a call without the paged pool (embeddings, scoring) is not supported")
    B, S = tokens.shape
    H, L = config.num_heads, config.num_layers
    dn, dr, r = config.qk_nope_head_dim, config.qk_rope_head_dim, config.kv_lora_rank
    W, eps = page_width(config), config.rms_norm_eps
    n_dense, n_moe = layer_counts(config)
    scale = (dn + dr) ** -0.5
    inv_freq = jnp.asarray(rope_frequencies(dr, config.rope_theta, config.rope_scaling))
    route = cached_attention_route(config, S, left_aligned, True)

    pool = cache["kv"]
    page, pool_P = pool.shape[1], pool.shape[0] // L
    max_pages = page_table.shape[1]
    skv = max_pages * page
    del left_aligned  # one route for every prefill: nothing to choose by it
    w_idx = jnp.clip(positions // page, 0, max_pages - 1)
    w_pages = jnp.take_along_axis(page_table, w_idx, axis=1)
    w_pages = jnp.where(positions < skv, w_pages, 0)
    w_offs = positions % page
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(jnp.dtype(config.dtype))

    def attn(x, w, pool, row0):
        with jax.named_scope("attn"):
            a = rms_norm(x, w["ln1"], eps)
            q = jnp.dot(a, w["wq"]).reshape(B, S, H, dn + dr)
            kva = jnp.dot(a, w["wkva"])
            c = rms_norm(kva[..., :r], w["kv_norm"], eps)
            q_rope, k_rope = apply_rope(q[..., dn:], kva[..., None, r:], positions, inv_freq)
            q_abs = jnp.einsum("bshn,hnr->bshr", q[..., :dn], w["wuk"])
            latent = jnp.concatenate([c, k_rope[:, :, 0], jnp.zeros((B, S, W - r - dr), c.dtype)], axis=-1)
            q_lat = jnp.concatenate([q_abs, q_rope, jnp.zeros((B, S, H, W - r - dr), q.dtype)], axis=-1)
            pool = pool.at[w_pages + row0, w_offs].set(latent.astype(pool.dtype))
            with jax.named_scope("attn.kernel"):
                if route == "paged_kernel":
                    o_lat = mla_paged_decode(
                        q_lat[:, 0], pool, page_table + row0, positions[:, 0] + 1, scale=scale, rank=r,
                    )[:, None]
                else:
                    o_lat = latent_attention_paged(q_lat, pool, page_table + row0, positions, scale=scale, rank=r)
            o = jnp.einsum("bshr,hrv->bshv", o_lat, w["wuv"]).reshape(B, S, -1)
            return x + jnp.dot(o, w["wo"]), pool

    def dense_layer(x, w, pool, row0):
        x, pool = attn(x, w, pool, row0)
        with jax.named_scope("ffn"):
            x = x + _swiglu(rms_norm(x, w["ln2"], eps), w["wg"], w["wu"], w["wd"])
        return x, pool

    experts = {k: params["moe"][k] for k in ("we_g", "we_u", "we_d")} if n_moe else {}

    def moe_layer(x, w, pool, row0, forced, e):
        x, pool = attn(x, w, pool, row0)
        with jax.named_scope("moe"):
            m = rms_norm(x, w["ln2"], eps).reshape(B * S, -1)
            with jax.named_scope("moe.router"):
                idx, weights = moe.route_sigmoid(
                    m, w["wr"], w["br"], config.num_experts_per_tok,
                    config.norm_topk_prob, config.routed_scaling_factor, forced=forced,
                )
            # The experts' stacks are not sliced: layer e's are groups of the whole (ops/moe.py).
            y, hit = moe.routed_experts(m, idx, weights, experts["we_g"], experts["we_u"], experts["we_d"], layer=e)
            with jax.named_scope("moe.shared"):
                y = y + _swiglu(m, w["ws_g"], w["ws_u"], w["ws_d"])
            x = x + y.reshape(B, S, -1)
        return x, pool, hit, idx

    for i in range(n_dense):  # unrolled: the leading dense layers differ in kind from what follows
        x, pool = dense_layer(x, jax.tree.map(lambda a: a[i], params["dense"]), pool, i * pool_P)

    def step(carry, xs):
        x, pool, hits = carry
        w, e, forced = xs
        x, pool, hit, idx = moe_layer(x, w, pool, (n_dense + e) * pool_P, forced, e)
        return (x, pool, hits + hit), (idx if return_choices else None)

    hits, choices = jnp.zeros((), jnp.int32), None
    if n_moe:
        (x, pool, hits), choices = jax.lax.scan(
            step, (x, pool, hits),
            (
                {k: v for k, v in params["moe"].items() if k not in experts},
                jnp.arange(n_moe, dtype=jnp.int32), forced_choices,
            ),
        )

    if live is not None:
        x = live.restore(x)  # slot order again, before anything [B, V]
    x = rms_norm(x, params["final_norm"], eps)
    with jax.named_scope("lm_head"):
        if logits_idx is not None:
            x = x[jnp.arange(B)[:, None], logits_idx[:, None]]
        logits = jnp.dot(x, params["lm_head"]).astype(jnp.float32)
    new_cache = {"kv": pool, "moe_hits": hits}
    if return_choices:
        return logits, new_cache, choices
    return logits, new_cache


prefill_paged, prefill_paged_cold, decode_step_paged = shared.paged_entry_points(apply, "deepseek_v3")

# The seam's other names (models/__init__.py says what each rule means).
KV_PARK = True
PREFIX_REUSE = True
SLOT_STATE = ()
init_lora_bank = None
layer_kinds = None


def window_pool_tokens(config: ModelConfig) -> int:
    """No layer of this family keeps a page pool of its own
    (models/smallthinker.py has the family whose window layers do)."""
    return 0


def config_keys(get) -> dict:
    """The DeepSeek-V3 keys of a published config.json as ModelConfig
    fields. What this module does not compute is refused here, by name,
    and not served as something else."""
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


def param_counts(mc: ModelConfig) -> tuple[float, float]:
    """(held, active a token): latent attention without a query low-rank;
    `first_k_dense_replace` dense layers, then layers whose every routed
    expert is resident and of which a token passes through
    `num_experts_per_tok` and the shared ones. kanana-2 at 8 layers:
    5.07G held, 0.78G a token (64.1M + 7 x 64.4M + the head)."""
    D, L, V, H = mc.hidden_size, mc.num_layers, mc.vocab_size, mc.num_heads
    dn, dr, dv, r = mc.qk_nope_head_dim, mc.qk_rope_head_dim, mc.v_head_dim, mc.kv_lora_rank
    attn = D * H * (dn + dr) + D * (r + dr) + r + r * H * (dn + dv) + H * dv * D + 2 * D
    n_dense = min(mc.first_k_dense_replace, L)
    expert = 3 * D * mc.moe_intermediate_size
    router = D * mc.n_routed_experts + mc.n_routed_experts
    shared_ = mc.n_shared_experts * expert
    dense = attn + 3 * D * mc.intermediate_size
    fixed = 2 * V * D + D
    total = fixed + n_dense * dense + (L - n_dense) * (attn + router + shared_ + mc.n_routed_experts * expert)
    # Active leaves the embedding table out (a row is looked up, not
    # multiplied; at 128k rows it would be a third of the count).
    active = V * D + D + n_dense * dense + (L - n_dense) * (attn + router + shared_ + mc.num_experts_per_tok * expert)
    return float(total), float(active)
