"""The plain reference of `model_type: afmoe` (Arcee Trinity-Mini / Nano,
`AfmoeForCausalLM`): the forward pass in `jax.numpy`, float32 at the highest
matmul precision, full attention matrices with the masks written out, a
Python loop over the experts, no cache, no kernels, no batching tricks. It
imports nothing of `kubeai_tpu` and reads weights by their HF names, one
tensor upcast at a time. With `x` a layer's input (the residual stream),
`l` its index, `h` the hidden size and `d` the head size:

    x0 = E[token] * sqrt(h)                                      # mup_enabled
    a  = rms(x; input_layernorm)
    q, k, v = a Wq, a Wk, a Wv;  z = a Wz                        # no biases; Wz = self_attn.gate_proj
    q  = rms(q; q_norm), k = rms(k; k_norm)                      # over the d of each head
    q, k = rope(q), rope(k)        ONLY where layer_types[l] == "sliding_attention"
                                                                 # half-split pairs (x[j], x[j + d/2]), no scaling
    o  = softmax(q k^T / sqrt(d) + causal [+ "key j > i - sliding_window" in a sliding layer]) v
    u  = x + rms((o * sigmoid(z)) Wo; post_attention_layernorm)  # a norm on the sub-block's OUTPUT
    m  = rms(u; pre_mlp_layernorm)
    l < num_dense_layers:   f = (silu(m Wg) * (m Wu)) Wd
    otherwise:              s = sigmoid(m Wr)  in float32        # Wr = mlp.router.gate
                            S = top-k of (s + b)                 # b = mlp.expert_bias: selection only
                            w = s[S] / (sum s[S] + 1e-20) * route_scale            # route_norm
                            f = sum_{e in S} w_e (silu(m Wg_e) * (m Wu_e)) Wd_e + (silu(m Sg) * (m Su)) Sd
    x' = u + rms(f; post_mlp_layernorm)

then `model.norm` and the untied head.

Departures from the published description: none but the depth (the
checkpoint is cut; `layer_types` keeps its published entries and the first
`num_hidden_layers` are read). Without effect on the result: attention is
computed a block of queries at a time against all keys (a block's softmax
is the whole softmax of its rows); no dropout, no cache, no padding mask.

`forced` [expert layers, B*S, k] routes every expert layer by the given
choices (the program's own), so that a comparison of logits does not hang
on which side of a near-tie each side's rounding fell; the reference's FREE
choices and what they were made from (`s + b`) are returned beside, for the
comparison of the choices themselves. `variant` names a deliberate fault,
for the controls that have to fail: each leaves ONE of the family's
additions to a plain pre-norm block out, or undoes what `layer_types`
says. `dtype` computes in a lower precision ("float8_e4m3fn": every
matmul's inputs rounded through it). `logits_at` [B, n] keeps the head to
those positions of each row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

VARIANTS = (
    "", "no_gate", "no_qk_norm", "no_post_norm", "no_embed_scale", "no_selection_bias", "no_shared_expert",
    "window_ignored", "rope_on_full",
)
QUERY_BLOCK = 512


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    """x [B, S, heads, d] at positions arange(S): HF's rotate_half."""
    S, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = jnp.asarray(np.arange(S, dtype=np.float64)[:, None] * inv[None, :], jnp.float32)  # [S, d/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def forward(
    get, hf: dict, tokens, forced=None, variant: str = "", dtype: str = "float32", logits_at=None, upcast=None,
) -> dict:
    """*get(name)* -> the HF tensor as a numpy array; *tokens* [B, S] ints.
    Returns `logits` [B, S or n, V] (float32 numpy), `choices` [expert
    layers, B*S, k] (the free choices) and `select` [expert layers, B*S, E]
    (score + bias, what the choice was made from). *upcast*: a dict a
    caller with several passes over one checkpoint hands to each, to keep
    the float32 tensors of the first for the next (where they fit)."""
    assert variant in VARIANTS, variant
    lowp = jnp.dtype(dtype) if dtype != "float32" else None
    D, H, Kv, d, eps = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"], hf["rms_norm_eps"]
    k, E, L = hf["num_experts_per_tok"], hf["num_experts"], hf["num_hidden_layers"]
    window, scale = hf["sliding_window"], hf["route_scale"]
    tokens = np.asarray(tokens)
    B, S = tokens.shape
    G = H // Kv

    def w(name):
        if upcast is not None and name in upcast:
            return upcast[name]
        a = jnp.asarray(np.asarray(get(name)).astype(np.float32))
        if upcast is not None:
            upcast[name] = a
        return a

    def mm(x, name):  # x @ W^T, the HF linear
        a, b = x, w(name)
        if lowp is not None:
            a, b = a.astype(lowp).astype(jnp.float32), b.astype(lowp).astype(jnp.float32)
        return jnp.dot(a, b.T, preferred_element_type=jnp.float32)

    def swiglu(x, p):
        return mm(jax.nn.silu(mm(x, p + "gate_proj.weight")) * mm(x, p + "up_proj.weight"), p + "down_proj.weight")

    def attention(q, kk, v, windowed: bool):
        """q [B, S, H, d], kk and v [B, S, Kv, d]: a block of queries at a
        time against every key, masked by position."""
        kpos = jnp.arange(S)[None, :]
        out = []
        for q0 in range(0, S, QUERY_BLOCK):
            qb = q[:, q0 : q0 + QUERY_BLOCK].reshape(B, -1, Kv, G, d)
            qpos = (q0 + jnp.arange(qb.shape[1]))[:, None]
            mask = kpos <= qpos
            if windowed:
                mask = mask & (kpos > qpos - window)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kk, preferred_element_type=jnp.float32) * d**-0.5
            pr = jax.nn.softmax(jnp.where(mask[None, None, None], s, -jnp.inf), axis=-1)
            out.append(jnp.einsum("bkgqs,bskd->bqkgd", pr, v, preferred_element_type=jnp.float32).reshape(B, -1, H * d))
        return jnp.concatenate(out, axis=1)

    post = (lambda x, name: x) if variant == "no_post_norm" else (lambda x, name: _rms(x, w(name), eps))
    choices, select = [], []
    with jax.default_matmul_precision("highest"):
        x = w("model.embed_tokens.weight")[tokens]
        if hf["mup_enabled"] and variant != "no_embed_scale":
            x = x * D**0.5
        for i in range(L):
            p = f"model.layers.{i}."
            sliding = hf["layer_types"][i] == "sliding_attention"
            a = _rms(x, w(p + "input_layernorm.weight"), eps)
            q = mm(a, p + "self_attn.q_proj.weight").reshape(B, S, H, d)
            kk = mm(a, p + "self_attn.k_proj.weight").reshape(B, S, Kv, d)
            v = mm(a, p + "self_attn.v_proj.weight").reshape(B, S, Kv, d)
            z = mm(a, p + "self_attn.gate_proj.weight")
            if variant != "no_qk_norm":
                q, kk = _rms(q, w(p + "self_attn.q_norm.weight"), eps), _rms(kk, w(p + "self_attn.k_norm.weight"), eps)
            if sliding or variant == "rope_on_full":
                q, kk = _rope(q, hf["rope_theta"]), _rope(kk, hf["rope_theta"])
            o = attention(q, kk, v, sliding and variant != "window_ignored")
            if variant != "no_gate":
                o = o * jax.nn.sigmoid(z)
            x = x + post(mm(o, p + "self_attn.o_proj.weight"), p + "post_attention_layernorm.weight")
            m = _rms(x, w(p + "pre_mlp_layernorm.weight"), eps)
            if i < hf["num_dense_layers"]:
                f = swiglu(m, p + "mlp.")
            else:
                mt = m.reshape(B * S, -1)
                scores = jax.nn.sigmoid(jnp.dot(mt, w(p + "mlp.router.gate.weight").T, preferred_element_type=jnp.float32))
                chosen_from = scores if variant == "no_selection_bias" else scores + w(p + "mlp.expert_bias")[None, :]
                _, free = jax.lax.top_k(chosen_from, k)
                choices.append(np.asarray(free))
                select.append(np.asarray(chosen_from))
                idx = free if forced is None else jnp.asarray(forced[len(choices) - 1])
                wts = jnp.take_along_axis(scores, idx, axis=1)
                if hf["route_norm"]:
                    wts = wts / (wts.sum(axis=1, keepdims=True) + 1e-20)
                wts = wts * scale
                y = jnp.zeros(mt.shape, jnp.float32)
                for e in range(E):  # every expert over every token, masked: plain, not fast
                    weight = (wts * (idx == e)).sum(-1)  # [T]: this expert's weight for each token, 0 if not chosen
                    y = y + weight[:, None] * swiglu(mt, f"{p}mlp.experts.{e}.")
                if variant != "no_shared_expert":
                    y = y + swiglu(mt, p + "mlp.shared_experts.")
                f = y.reshape(B, S, -1)
            x = x + post(f, p + "post_mlp_layernorm.weight")
        x = _rms(x, w("model.norm.weight"), eps)
        if logits_at is not None:
            x = x[np.arange(B)[:, None], np.asarray(logits_at)]
        logits = mm(x, "lm_head.weight")
    return {"logits": np.asarray(logits), "choices": np.stack(choices), "select": np.stack(select)}


def choice_disagreements(program_choices, ref_choices, ref_select, valid=None) -> dict:
    """The program's choices against the reference's free ones, per
    (expert layer, token) as SETS: where they differ, the gap in what the
    reference chose from between what each side chose and the other did
    not. `worst_gap` is the largest such gap: a disagreement is a near-tie
    only if it is small. *valid* [tokens] (bool) names the tokens the
    program computed on the reference's inputs; the others are not
    compared."""
    prog, ref, sel = np.asarray(program_choices), np.asarray(ref_choices), np.asarray(ref_select)
    n = flips = 0
    worst = 0.0
    for l in range(prog.shape[0]):
        for t in range(prog.shape[1]):
            if valid is not None and not valid[t]:
                continue
            a, b = set(prog[l, t].tolist()), set(ref[l, t].tolist())
            n += 1
            if a == b:
                continue
            flips += 1
            only_p, only_r = sorted(a - b), sorted(b - a)
            gap = max(sel[l, t, only_r]) - min(sel[l, t, only_p])
            worst = max(worst, float(gap))
    return {"compared": n, "disagree": flips, "worst_gap": worst}
