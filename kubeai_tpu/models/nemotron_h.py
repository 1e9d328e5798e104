"""Nemotron-H decoder (`model_type: nemotron_h`) in functional JAX: a stack
whose blocks are each ONE of three things alone, by a pattern with no
fixed period (`hybrid_override_pattern`: `M` a Mamba-2 mixer, `*`
attention, `E` experts). Block `i`, with `u = rmsnorm(x; g_i)`:

    x <- x + Mixer_i(u)

    M   [z | xBC | dt] = u W_in                 # 8192 | 8192 + 2*8*128 | 128 as published
        xBC = silu(conv(xBC) + b)               # depthwise, causal, 4 taps, the 3 rows before carried
        x_h, B_g, C_g = split(xBC);  d = softplus(dt + dt_bias);  a = exp(d A),  A = -exp(A_log)
        S_t[h] = a_t[h] S_{t-1}[h] + d_t[h] x_t[h] (x) B_t[g];   y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]
        out = W_out (rmsnorm_groups(y * silu(z)) * w)            # the gate first, then a norm a group
    *   causal grouped-query attention, no bias, no window, NO rotary embedding
    E   s = sigmoid(u W_r);  the top k by s + bias;  g_e = s_e / sum of the chosen s, times the scaling factor
        l = u W_down;  r = sum_e g_e relu(l W1_e)^2 W2_e;  out = r W_up + relu(u S1)^2 S2

then a final rmsnorm and the untied head. The drafting head
(`num_nextn_predict_layers`) is not loaded: it changes no served
distribution. The engine reaches a model through
`kubeai_tpu.models.family(config)`; `models/__init__.py` declares what
this module gives it.

**The pattern is walked statically**: eleven blocks of a cut, 88 of the
whole, unrolled; `params["blocks"][i]` holds block i's tensors and nothing
is sliced out of a stack (a static index into a stacked weight is a slice
the compiler may copy; a scan cannot walk a pattern without a period).

**State that is not pages.** The `*` blocks keep keys and values in the
paged pool, `cache["kv"]`, laid out as `llama.init_paged_cache` lays its
one (a layer owns `P` rows; logical page 0 is the trash page). The `M`
blocks keep, for every SLOT of the engine, the recurrence's state and the
convolution's tail: `cache["ssm"]` `[n_M, slots, heads, head_dim, state]`
float32 and `cache["conv"]` `[n_M, slots, taps - 1, channels]`. Neither
grows with the context, so there is no allocator: the slot is the address.

  - a cold prefill call starts every row from zeros and writes the rows'
    final state at `slots`; a slot used again therefore starts from zeros;
  - a chunk behind earlier chunks reads its slot's state and tail and
    writes them back (a chunk that starts at position 0 reads zeros);
  - a decode step computes its `M` blocks in SLOT order, in place: the
    block's normed input is put in slot order (`live.restore`, a few KB a
    row) and its output back in the step's order (`live.take`), so the 4 MB
    a slot of state never moves; a slot that is not live has `d = 0`,
    which leaves its state and tail exactly as they were. On the chip one
    kernel passes over block j of the stacked state once, by index
    (`ops/ssm.py::ssd_step_stacked`); nothing here slices the stack.

**A chip's share of the experts.** With `router_experts` set the chip holds
`n_routed_experts` experts from `experts_first` on: the router scores all
`router_experts` and normalises over all the chosen, and the layer returns
its own experts' part (`ops/moe.py::routed_experts`, `held=`). Nothing
stands in for the absent chips.

**What is limited for this family, stated here once.** `PREFIX_REUSE =
False`: a prefix found in the page cache would also need the `M` blocks'
state at the page's edge, which nothing keeps, so the engine looks nothing
up and registers nothing for this family (`prefix_hit` counters stay 0).
`KV_PARK = False`: a slot's state is not parked, restored or handed off
(the wire format carries pages). Snapshots of state for both are what is
still missing (ROADMAP B-I.4). tp = 1, the compute dtype's pool, no
quantization, no LoRA.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from kubeai_tpu.models import shared
from kubeai_tpu.models.base import ModelConfig
from kubeai_tpu.models.shared import cached_attention_route  # noqa: F401  (the seam's name; one pool)
from kubeai_tpu.ops import moe, ssm
from kubeai_tpu.ops.attention import attention
from kubeai_tpu.ops.norms import rms_norm
from kubeai_tpu.ops.paged_attention import paged_attention_ragged

Params = dict[str, Any]

PAGED_KERNEL_LABEL = "ragged"
KV_PARK = False  # the module docstring says why
PREFIX_REUSE = False  # likewise
SLOT_STATE = ("ssm", "conv")  # init_paged_cache takes `slots`; the prefill entry points take each row's slot


def kinds(config: ModelConfig) -> dict[str, int]:
    """Blocks of each kind: {"M": ..., "*": ..., "E": ...}."""
    return {k: config.layer_pattern.count(k) for k in "M*E"}


def window_pool_tokens(config: ModelConfig) -> int:
    return 0  # one page budget a slot


def conv_channels(config: ModelConfig) -> int:
    """What the convolution runs over: x, B and C side by side."""
    return config.mamba_num_heads * config.mamba_head_dim + 2 * config.ssm_groups * config.ssm_state_size


def state_bytes_per_slot(config: ModelConfig) -> int:
    """What a slot owns outside its pages: every `M` block's float32 state
    and its convolution tail in the compute dtype."""
    one = config.mamba_num_heads * config.mamba_head_dim * config.ssm_state_size * 4
    tail = (config.conv_kernel - 1) * conv_channels(config) * jnp.dtype(config.dtype).itemsize
    return kinds(config)["M"] * (one + tail)


def held_share(config: ModelConfig):
    """(first, count, of) of the experts held here among those the router
    scores, or None where the chip holds them all."""
    if not config.router_experts or config.router_experts == config.n_routed_experts:
        return None
    return config.experts_first, config.n_routed_experts, config.router_experts


def refuse_unsupported(config: ModelConfig, quantization: str = "", tp: int = 1) -> None:
    """What this family does not run, refused at load by name."""
    shared.refuse_common(
        "nemotron_h", config, quantization, tp, "state, experts and the pool are unsharded", int8_for="expert or mixer weights",
    )
    missing = [k for k, n in kinds(config).items() if not n]
    if missing:
        raise ValueError(f"nemotron_h: a stack without a block of each kind is not supported (none of {missing})")


# ---------------------------------------------------------------------------
# Parameters


def block_shapes(config: ModelConfig, kind: str) -> dict[str, tuple]:
    """The tensors of one block of *kind*, as this module holds them
    (linears [in, out]; experts [E, in, out])."""
    D = config.hidden_size
    if kind == "M":
        inner, C, Hm = config.mamba_num_heads * config.mamba_head_dim, conv_channels(config), config.mamba_num_heads
        return {
            "ln": (D,), "in_proj": (D, inner + C + Hm), "conv_w": (config.conv_kernel, C), "conv_b": (C,),
            "A_log": (Hm,), "D": (Hm,), "dt_bias": (Hm,), "norm": (inner,), "out_proj": (inner, D),
        }
    if kind == "*":
        H, Kv, h = config.num_heads, config.num_kv_heads, config.head_dim_
        return {"ln": (D,), "wq": (D, H * h), "wk": (D, Kv * h), "wv": (D, Kv * h), "wo": (H * h, D)}
    R, E = config.router_experts or config.n_routed_experts, config.n_routed_experts
    Z, F, Fs = config.moe_latent_size, config.moe_intermediate_size, config.moe_shared_intermediate_size
    return {
        "ln": (D,), "wr": (D, R), "bias": (R,), "w_down": (D, Z), "w_up": (Z, D),
        "s1": (D, Fs), "s2": (Fs, D), "we_1": (E, Z, F), "we_2": (E, F, Z),
    }


FLOAT32 = ("A_log", "D", "dt_bias", "bias")  # a head's scalars and the router's bias stay float32


def init_params(config: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random parameters in the tree the loader builds. `A_log`, `dt_bias`
    spread the decay a step over (0, 1) as a trained model's are."""
    dtype = dtype or jnp.dtype(config.dtype)
    D, V = config.hidden_size, config.vocab_size
    keys = iter(jax.random.split(key, 16 * len(config.layer_pattern) + 4))

    def draw(name, shape):
        if name in ("ln", "norm", "D"):
            return jnp.ones(shape, jnp.float32 if name == "D" else dtype)
        if name in ("A_log", "dt_bias"):
            return jax.random.uniform(next(keys), shape, jnp.float32, -1.5, 1.5) - (2.0 if name == "dt_bias" else 0.0)
        if name in ("bias", "conv_b"):
            return (jax.random.normal(next(keys), shape, jnp.float32) * 0.1).astype(jnp.float32 if name == "bias" else dtype)
        return (jax.random.normal(next(keys), shape, jnp.float32) * shape[-2] ** -0.5).astype(dtype)

    return {
        "embed": (jax.random.normal(next(keys), (V, D), jnp.float32) * 0.02).astype(dtype),
        "final_norm": jnp.ones((D,), dtype),
        "lm_head": (jax.random.normal(next(keys), (D, V), jnp.float32) * 0.02).astype(dtype),
        "blocks": [{k: draw(k, s) for k, s in block_shapes(config, kind).items()} for kind in config.layer_pattern],
    }


def _block_tensors(get, config: ModelConfig, i: int, dtype) -> dict:
    """Block *i* of an HF checkpoint (get(name) -> array) on the host:
    linears transposed to [in, out]; the experts stacked [E, out, in] (one
    contiguous copy; the device transposes them)."""
    p = f"backbone.layers.{i}.mixer."
    conv = lambda a, dt=dtype: np.asarray(a, dt)  # noqa: E731
    lin = lambda name: conv(np.asarray(get(p + name + ".weight")).T)  # noqa: E731
    f32 = lambda name: conv(get(p + name), np.float32)  # noqa: E731
    kind = config.layer_pattern[i]
    out = {"ln": conv(get(f"backbone.layers.{i}.norm.weight"))}
    if kind == "M":
        out.update(
            in_proj=lin("in_proj"), conv_w=conv(np.asarray(get(p + "conv1d.weight"))[:, 0, :].T),
            conv_b=conv(get(p + "conv1d.bias")), A_log=f32("A_log"), D=f32("D"), dt_bias=f32("dt_bias"),
            norm=conv(get(p + "norm.weight")), out_proj=lin("out_proj"),
        )
    elif kind == "*":
        out.update(wq=lin("q_proj"), wk=lin("k_proj"), wv=lin("v_proj"), wo=lin("o_proj"))
    else:
        first = config.experts_first
        stack = lambda which: conv(  # noqa: E731
            np.stack([np.asarray(get(f"{p}experts.{first + j}.{which}.weight")) for j in range(config.n_routed_experts)])
        )
        out.update(
            wr=lin("gate"), bias=f32("gate.e_score_correction_bias"),
            w_down=lin("fc1_latent_proj"), w_up=lin("fc2_latent_proj"),
            s1=lin("shared_experts.up_proj"), s2=lin("shared_experts.down_proj"),
            we_1=stack("up_proj"), we_2=stack("down_proj"),
        )
    return out


def stream_params_from_hf(source, config: ModelConfig, pad: int = 0) -> Params:
    """The streamed load of a tree that is a LIST of blocks, not stacks:
    each block (`shared.read_ahead`: an `E` block is 1.5 GB in bf16, the
    host holds two) is put on the device as it comes. *source* serves
    tensors by HF name; *pad* columns of zeros are added to the
    vocabulary."""
    transposed = jax.jit(lambda a: jnp.swapaxes(a, -1, -2))
    blocks = [
        {k: transposed(jax.device_put(a)) if k in ("we_1", "we_2") else jax.device_put(a) for k, a in tensors.items()}
        for tensors in shared.read_ahead(_block_tensors, source, config, len(config.layer_pattern), jnp.dtype(config.dtype))
    ]
    outside = shared.embed_norm_head(source, config, pad, embed="backbone.embeddings.weight", norm="backbone.norm_f.weight")
    return {**outside, "blocks": blocks}


params_from_hf = shared.params_from_hf_by(stream_params_from_hf)


# ---------------------------------------------------------------------------
# Cache and routes


def init_paged_cache(config: ModelConfig, num_pages: int, page_size: int, dtype=None, slots: int = 1, state_dtype=jnp.float32) -> Params:
    """The page pool of the `*` blocks (*num_pages* logical pages a block)
    and, by slot, the `M` blocks' state and convolution tail (module
    docstring). *state_dtype* other than float32 is a control of the
    harness's logits check and nothing the engine asks for."""
    dtype = dtype or jnp.dtype(config.dtype)
    n = kinds(config)
    Hm, P, N = config.mamba_num_heads, config.mamba_head_dim, config.ssm_state_size
    return {
        "kv": jnp.zeros((n["*"] * num_pages, page_size, 2 * config.num_kv_heads, config.head_dim_), dtype),
        "ssm": jnp.zeros((n["M"], slots, Hm, P, N), state_dtype),
        "conv": jnp.zeros((n["M"], slots, config.conv_kernel - 1, conv_channels(config)), dtype),
    }


# ---------------------------------------------------------------------------
# Forward


def relu2(v):
    return jnp.square(jax.nn.relu(v))


def expert_block(config: ModelConfig, u, w, forced=None):
    """An `E` block's mixer on its normed rows u [T, D] (module docstring).
    Returns (out [T, D], held experts that got a row, assignments that went
    to experts this chip does not hold, the choices [T, k])."""
    held = held_share(config)
    with jax.named_scope("moe.router"):
        idx, weights = moe.route_sigmoid(
            u, w["wr"], w["bias"], config.num_experts_per_tok, config.norm_topk_prob, config.routed_scaling_factor,
            forced=forced,
        )
    with jax.named_scope("moe.latent_down"):
        latent = jnp.dot(u, w["w_down"])
    y, hit = moe.routed_experts(latent, idx, weights, None, w["we_1"], w["we_2"], act=relu2, held=held)
    with jax.named_scope("moe.latent_up"):
        y = jnp.dot(y, w["w_up"])
    with jax.named_scope("moe.shared"):
        y = y + jnp.dot(relu2(jnp.dot(u, w["s1"])), w["s2"])
    absent = jnp.zeros((), jnp.int32)
    if held is not None:
        absent = ((idx < held[0]) | (idx >= held[0] + held[1])).sum().astype(jnp.int32)
    return y, hit, absent, idx


def apply(
    params: Params,
    config: ModelConfig,
    tokens: jnp.ndarray,  # [B, S] int32
    positions: jnp.ndarray,  # [B, S] int32 absolute positions, contiguous along S
    cache: Params | None = None,  # init_paged_cache
    page_table: jnp.ndarray | None = None,  # [B, max_pages]
    n_real: jnp.ndarray | None = None,  # [B] real rows of each row of the call (they come first)
    slots: jnp.ndarray | None = None,  # [B] the slot of each prefill row; None: a decode step, rows are slots
    carried: jnp.ndarray | None = None,  # [B] bool: the row continues its slot's state; None: every row starts from zeros
    logits_idx: jnp.ndarray | None = None,
    left_aligned: bool = False,  # caller guarantees positions == arange(S)
    forced_choices: jnp.ndarray | None = None,  # [n_E, B*S, k]: route by these (debug)
    return_choices: bool = False,  # also return the routers' choices (debug; no timed program asks)
    live=None,  # models/base.py::LiveRows of a decode step whose rows arrive live slots first
    **unsupported,  # what llama.apply takes and this family does not run (return_hidden, lora, ...)
):
    """Run the decoder over the paged pool and the slots' state. Returns
    (logits, cache) with `cache["moe_hits"]` the (block, held expert) pairs
    that got a row and `cache["moe_absent"]` the assignments that went to
    experts this chip does not hold; with *return_choices* also the
    choices [n_E, B*S, k]. A position whose table entry is 0 writes to the
    pool's trash page."""
    if cache is None or page_table is None or n_real is None or unsupported:
        raise ValueError("nemotron_h: a call without the paged pool and the slots' state (embeddings, scoring) is not supported")
    B, S = tokens.shape
    decode = slots is None
    dtype = jnp.dtype(config.dtype)
    H, Kv, h = config.num_heads, config.num_kv_heads, config.head_dim_
    Hm, P, N, G = config.mamba_num_heads, config.mamba_head_dim, config.ssm_state_size, config.ssm_groups
    inner, C = Hm * P, conv_channels(config)
    eps = config.rms_norm_eps
    route = cached_attention_route(config, S, left_aligned, True)

    pool, states, tails = cache["kv"], cache["ssm"], cache["conv"]
    page = pool.shape[1]
    n_kind = kinds(config)
    pool_rows = pool.shape[0] // n_kind["*"]  # logical pages a block
    max_pages = page_table.shape[1]
    skv = max_pages * page
    w_idx = jnp.clip(positions // page, 0, max_pages - 1)
    w_pages = jnp.where(positions < skv, jnp.take_along_axis(page_table, w_idx, axis=1), 0)
    w_offs = positions % page
    n_real = n_real.astype(jnp.int32)
    if decode and live is not None:
        n_real = live.restore(n_real)  # the `M` blocks of a decode step work in slot order
    valid = jnp.arange(S, dtype=jnp.int32)[None, :] < n_real[:, None]  # [B, S]
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(dtype)

    def mixer(x, w, j):
        """Block j of the `M` blocks: reads and writes row j of the state."""
        nonlocal states, tails
        with jax.named_scope("ssm"):
            u = rms_norm(x, w["ln"], eps)
            if decode and live is not None:
                u = live.restore(u)
            with jax.named_scope("ssm.in_proj"):
                zxd = jnp.dot(u, w["in_proj"])
            z, xBC, dt = zxd[..., :inner], zxd[..., inner : inner + C], zxd[..., inner + C :]
            if decode:
                tail = tails[j]
            elif carried is None:
                state = jnp.zeros((B, *states.shape[2:]), states.dtype)
                tail = jnp.zeros((B, *tails.shape[2:]), tails.dtype)
            else:
                state = states[j, slots] * carried[:, None, None, None].astype(states.dtype)
                tail = tails[j, slots] * carried[:, None, None].astype(tails.dtype)
            # Whatever moves a slot array stands under the scope of the work it
            # is part of: the tail's write back under `ssm.conv` (the compiler
            # updates the array in place, so that write IS the operation that
            # reads and writes it); under `ssm.scan` a decode step's pass over
            # block j of the stacked state where it lies (`ssd_step_stacked`:
            # never a slice of it here) and a prefill's write of its rows.
            with jax.named_scope("ssm.conv"):
                yc, tail = ssm.causal_conv(xBC, tail, n_real, w["conv_w"], w["conv_b"])
                xBC = jax.nn.silu(yc).astype(dtype)
                tails = tails.at[j].set(tail) if decode else tails.at[j, slots].set(tail)
            xs = xBC[..., :inner].reshape(B, S, Hm, P)
            Bm = xBC[..., inner : inner + G * N].reshape(B, S, G, N)
            Cm = xBC[..., inner + G * N :].reshape(B, S, G, N)
            with jax.named_scope("ssm.scan"):
                d = jax.nn.softplus(dt.astype(jnp.float32) + w["dt_bias"]) * valid[:, :, None]
                A = -jnp.exp(w["A_log"])
                if decode:
                    y, states = ssm.ssd_step_stacked(states, j, xs[:, 0], d[:, 0], A, Bm[:, 0], Cm[:, 0], w["D"])
                    y = y[:, None]
                else:
                    y, h32 = ssm.ssd_chunked(state.astype(jnp.float32), xs, d, A, Bm, Cm, w["D"], config.ssm_chunk)
                    states = states.at[j, slots].set(h32.astype(states.dtype))
            with jax.named_scope("ssm.gate_norm"):
                y = y.reshape(B, S, inner) * jax.nn.silu(z.astype(jnp.float32))
                y = rms_norm(y.reshape(B, S, G, inner // G), jnp.ones((), jnp.float32), eps).reshape(B, S, inner)
                y = (y * w["norm"].astype(jnp.float32)).astype(dtype)
            with jax.named_scope("ssm.out_proj"):
                out = jnp.dot(y, w["out_proj"])
            if decode and live is not None:
                (out,) = live.take(out)
        return x + out

    def attend(x, w, j):
        nonlocal pool
        row0 = j * pool_rows
        with jax.named_scope("attn"):
            a = rms_norm(x, w["ln"], eps)
            q = jnp.dot(a, w["wq"]).reshape(B, S, H, h)
            k = jnp.dot(a, w["wk"]).reshape(B, S, Kv, h)
            v = jnp.dot(a, w["wv"]).reshape(B, S, Kv, h)
            interleaved = jnp.stack([k, v], axis=3).reshape(B, S, 2 * Kv, h)
            pool = pool.at[w_pages + row0, w_offs].set(interleaved.astype(pool.dtype))
            with jax.named_scope("attn.kernel"):
                if route == "flash":
                    from kubeai_tpu.ops.flash_attention import flash_attention_tpu

                    o = flash_attention_tpu(q, k, v, causal=True)
                elif route == "paged_kernel":
                    o = paged_attention_ragged(
                        q, pool, page_table + row0, positions[:, -1] + 1,
                        live_rows=None if live is None else live.count,
                    )
                else:
                    gathered = pool[page_table + row0]  # [B, max_pages, page, 2Kv, h]
                    k_att = gathered[..., 0::2, :].reshape(B, skv, Kv, h)
                    v_att = gathered[..., 1::2, :].reshape(B, skv, Kv, h)
                    mask = jnp.arange(skv, dtype=jnp.int32)[None, None, :] <= positions[:, :, None]
                    o = attention(q, k_att, v_att, mask)
            return x + jnp.dot(o.reshape(B, S, H * h), w["wo"])

    nth = {"M": 0, "*": 0, "E": 0}
    hits = absent = jnp.zeros((), jnp.int32)
    chosen = []
    for kind, w in zip(config.layer_pattern, params["blocks"]):  # unrolled: the pattern has no period
        j = nth[kind]
        nth[kind] += 1
        if kind == "M":
            x = mixer(x, w, j)
        elif kind == "*":
            x = attend(x, w, j)
        else:
            with jax.named_scope("moe"):
                u = rms_norm(x, w["ln"], eps).reshape(B * S, -1)
                y, hit, gone, idx = expert_block(config, u, w, None if forced_choices is None else forced_choices[j])
            x = x + y.reshape(B, S, -1)
            hits, absent = hits + hit, absent + gone
            chosen.append(idx)

    if live is not None:
        x = live.restore(x)  # slot order again, before anything [B, V]
    x = rms_norm(x, params["final_norm"], eps)
    with jax.named_scope("lm_head"):
        if logits_idx is not None:
            x = x[jnp.arange(B)[:, None], logits_idx[:, None]]
        logits = jnp.dot(x, params["lm_head"]).astype(jnp.float32)
    new_cache = {"kv": pool, "ssm": states, "conv": tails, "moe_hits": hits, "moe_absent": absent}
    if return_choices:
        return logits, new_cache, jnp.stack(chosen)
    return logits, new_cache


prefill_paged, prefill_paged_cold, decode_step_paged = shared.paged_entry_points(apply, "nemotron_h", by_slot=bool(SLOT_STATE))

# The seam's other names (models/__init__.py says what each rule means).
REUSE_WHOLE_PREFILL_CALLS = False  # moot: PREFIX_REUSE is False
init_lora_bank = None
layer_kinds = None


def config_keys(get) -> dict:
    """The Nemotron-H keys of a published config.json as ModelConfig
    fields. What this module does not compute is refused here, by name.
    The pattern may be longer than the depth (a checkpoint cut in depth
    keeps the published 88 characters): the first `num_hidden_layers` are
    the model's. `num_nextn_predict_layers` and
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


def param_counts(mc: ModelConfig) -> tuple[float, float]:
    """(held, active a token): blocks that are each a Mamba-2 mixer,
    attention, or experts in a latent space beside a shared expert. Held:
    what THIS chip holds (`n_routed_experts` experts a block). Active:
    what a token is multiplied by on this chip, its `num_experts_per_tok`
    choices landing here in the ratio of the held experts to the router's
    width. Nemotron-3-Super at 11 blocks, 128 of 512 experts, a quarter
    of the vocabulary: 4.65G held, 1.04G a token. Held to
    perfbench/families/nemotron_h_counts.py by tests/test_nemotron_h.py."""
    D, V = mc.hidden_size, mc.vocab_size
    Hm, inner = mc.mamba_num_heads, mc.mamba_num_heads * mc.mamba_head_dim
    C = inner + 2 * mc.ssm_groups * mc.ssm_state_size
    mixer = D * (inner + C + Hm) + C * mc.conv_kernel + C + 3 * Hm + inner + inner * D + D
    attn = D * (mc.num_heads + 2 * mc.num_kv_heads) * mc.head_dim_ + mc.num_heads * mc.head_dim_ * D + D
    R = mc.router_experts or mc.n_routed_experts
    expert = 2 * mc.moe_latent_size * mc.moe_intermediate_size
    outside = D * R + R + 2 * D * mc.moe_latent_size + 2 * D * mc.moe_shared_intermediate_size + D
    n = kinds(mc)
    dense = n["M"] * mixer + n["*"] * attn + n["E"] * outside
    total = 2 * V * D + D + dense + n["E"] * mc.n_routed_experts * expert
    # Active leaves the embedding table out (a row is looked up).
    active = V * D + D + dense + n["E"] * mc.num_experts_per_tok * mc.n_routed_experts / R * expert
    return float(total), float(active)
