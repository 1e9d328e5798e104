"""The plain reference of `model_type: lfm2_moe` (LiquidAI LFM2-24B-A2B,
`Lfm2MoeForCausalLM`): the forward pass in `jax.numpy`, float32 at the
highest matmul precision, the whole sequence at once, the convolution
written as the sum over its taps on a zero-padded sequence, full attention
matrices with the mask written out, a Python loop over the experts, no
cache, no kernels, no batching tricks. It imports nothing of `kubeai_tpu`
and reads weights by their HF names, one tensor upcast at a time. With `x` a
layer's input (the residual stream), `l` its index, `D` the hidden size,
`d = D / heads` the head size and `K = conv_L_cache`:

    u = rms(x; operator_norm)
    layer_types[l] == "conv":
        B, C, xx = split3(u W_in^T)                              # conv.in_proj [3D, D], in that order
        z   = B * xx
        c_t = sum_{k<K} w[:, k] * z_{t-(K-1)+k}                  # conv.conv.weight [D, 1, K]: depthwise, causal,
                                                                 # zeros before the sequence's start; no bias
        o   = (C * c) W_out^T                                    # conv.out_proj; no activation anywhere
    layer_types[l] == "full_attention":
        q, k, v = u Wq^T, u Wk^T, u Wv^T                         # no biases
        q = rms(q; q_layernorm), k = rms(k; k_layernorm)         # over the d of each head, BEFORE rope
        q, k = rope(q), rope(k)                                  # half-split pairs (x[j], x[j + d/2]), theta 1e6
        o = softmax(q k^T / sqrt(d) + causal) v Wo^T             # self_attn.out_proj
    x = x + o
    m = rms(x; ffn_norm)
    l < num_dense_layers:   f = (silu(m W1^T) * (m W3^T)) W2^T
    otherwise:              s = sigmoid(m Wr^T)  in float32      # Wr = feed_forward.gate
                            S = top-k of (s + b)                 # b = feed_forward.expert_bias: selection only
                            w = s[S] / (sum s[S] + 1e-6) * routed_scaling_factor   # norm_topk_prob
                            f = sum_{e in S} w_e (silu(m W1_e^T) * (m W3_e^T)) W2_e^T
    x' = x + f

then `model.embedding_norm` and the head, which is `model.embed_tokens` (tied).

Departures from the published description: the depth (the checkpoint is
cut; `layer_types` keeps its published entries and the first
`num_hidden_layers` are read). The 1e-6 under the router's sum is the
public modelling code's as the configuration's `assumed` states it; the
program's router (`ops/moe.py::route_sigmoid`) has 1e-20 there, a relative
difference of 4e-7 in a weight at a sum of 2.5, under every bound. Without
effect on the result: attention is computed a block of queries at a time
against all keys (a block's softmax is the whole softmax of its rows); no
dropout, no cache, no padding mask.

`forced` [expert layers, B*S, k] routes every expert layer by the given
choices (the program's own), so that a comparison of logits does not hang
on which side of a near-tie each side's rounding fell; the reference's FREE
choices and what they were made from (`s + b`) are returned beside, for the
comparison of the choices themselves, and `tails` [conv layers, B, K-1, D],
the last K-1 rows of every convolution's `z` (what a serving slot carries).
`variant` names a deliberate fault, for the controls that have to fail: each
leaves ONE part of the mathematics out or moves it. `dtype` computes in a
lower precision ("float8_e4m3fn": every matmul's inputs and both gates'
products rounded through it). `logits_at` [B, n] keeps the head to those
positions of each row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

VARIANTS = (
    "", "gate_bf16", "no_gate_in", "no_gate_out", "tail_one_row_off", "taps_reversed", "no_qk_norm", "norm_after_rope",
    "bias_in_weights", "no_selection_bias",
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
    get, hf: dict, tokens, forced=None, variant: str = "", dtype: str = "float32", logits_at=None, upcast=None, lengths=None,
) -> dict:
    """*get(name)* -> the HF tensor as a numpy array; *tokens* [B, S] ints.
    Returns `logits` [B, S or n, V] (float32 numpy), `choices` [expert
    layers, B*S, k] (the free choices), `select` [expert layers, B*S, E]
    (score + bias, what the choice was made from) and `tails` [conv layers,
    B, K-1, D]. *lengths* [B]: the real tokens of each row where rows of
    several lengths share a pass (they come first; causal, so what stands
    behind them moves nothing before it): the tails are read THERE, not
    at S. *upcast*: a dict a caller with several passes over one
    checkpoint hands to each, to keep the float32 tensors of the first for
    the next (where they fit)."""
    assert variant in VARIANTS, variant
    lowp = jnp.dtype(dtype) if dtype != "float32" else None
    D, H, Kv, eps = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"], hf["norm_eps"]
    d = D // H
    k, E, L, K = hf["num_experts_per_tok"], hf["num_experts"], hf["num_hidden_layers"], hf["conv_L_cache"]
    theta, scale = hf["rope_parameters"]["rope_theta"], hf["routed_scaling_factor"]
    tokens = np.asarray(tokens)
    B, S = tokens.shape
    G = H // Kv
    n_real = np.full((B,), S) if lengths is None else np.asarray(lengths)

    def w(name):
        if upcast is not None and name in upcast:
            return upcast[name]
        a = jnp.asarray(np.asarray(get(name))).astype(jnp.float32)  # upcast where it lands: a bf16 tensor crosses to the device as it is stored
        if upcast is not None:
            upcast[name] = a
        return a

    def low(a):
        return a if lowp is None else a.astype(lowp).astype(jnp.float32)

    def mm(x, name):  # x @ W^T, the HF linear
        return jnp.dot(low(x), low(w(name)).T, preferred_element_type=jnp.float32)

    def swiglu(x, p):
        return mm(jax.nn.silu(mm(x, p + "w1.weight")) * mm(x, p + "w3.weight"), p + "w2.weight")

    def attention(q, kk, v):
        """q [B, S, H, d], kk and v [B, S, Kv, d]: a block of queries at a
        time against every key, masked by position."""
        kpos = jnp.arange(S)[None, :]
        out = []
        for q0 in range(0, S, QUERY_BLOCK):
            qb = q[:, q0 : q0 + QUERY_BLOCK].reshape(B, -1, Kv, G, d)
            qpos = (q0 + jnp.arange(qb.shape[1]))[:, None]
            s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kk, preferred_element_type=jnp.float32) * d**-0.5
            pr = jax.nn.softmax(jnp.where((kpos <= qpos)[None, None, None], s, -jnp.inf), axis=-1)
            out.append(jnp.einsum("bkgqs,bskd->bqkgd", pr, v, preferred_element_type=jnp.float32).reshape(B, -1, H * d))
        return jnp.concatenate(out, axis=1)

    def short_conv(u, p):
        """The gated short convolution on u [B, S, D]; also the last K-1
        rows of z."""
        Bg, Cg, xx = jnp.split(mm(u, p + "in_proj.weight"), 3, axis=-1)
        z = xx if variant == "no_gate_in" else low(Bg * xx)
        if variant == "gate_bf16":
            z = z.astype(jnp.bfloat16).astype(jnp.float32)
        taps = w(p + "conv.weight")[:, 0, :]  # [D, K]
        if variant == "taps_reversed":
            taps = taps[:, ::-1]
        shift = 1 if variant == "tail_one_row_off" else 0  # the taps read one row further back
        padded = jnp.pad(z, ((0, 0), (K - 1 + shift, 0), (0, 0)))  # zeros before the sequence's start
        c = sum(taps[:, j] * padded[:, j : j + S] for j in range(K))
        g = c if variant == "no_gate_out" else low(Cg * c)
        # Rows n-(K-1) .. n-1 of each row's n real ones, zeros where it is shorter.
        tail = jnp.pad(z, ((0, 0), (K - 1, 0), (0, 0)))[np.arange(B)[:, None], n_real[:, None] + np.arange(K - 1)]
        return mm(g, p + "out_proj.weight"), tail

    choices, select, tails = [], [], []
    with jax.default_matmul_precision("highest"):
        x = w("model.embed_tokens.weight")[tokens]
        for i in range(L):
            p = f"model.layers.{i}."
            u = _rms(x, w(p + "operator_norm.weight"), eps)
            if hf["layer_types"][i] == "conv":
                o, tail = short_conv(u, p + "conv.")
                tails.append(np.asarray(tail))
            else:
                q = mm(u, p + "self_attn.q_proj.weight").reshape(B, S, H, d)
                kk = mm(u, p + "self_attn.k_proj.weight").reshape(B, S, Kv, d)
                v = mm(u, p + "self_attn.v_proj.weight").reshape(B, S, Kv, d)
                norm = lambda q, kk: (  # noqa: E731
                    _rms(q, w(p + "self_attn.q_layernorm.weight"), eps), _rms(kk, w(p + "self_attn.k_layernorm.weight"), eps)
                )
                if variant == "norm_after_rope":
                    q, kk = norm(_rope(q, theta), _rope(kk, theta))
                else:
                    if variant != "no_qk_norm":
                        q, kk = norm(q, kk)
                    q, kk = _rope(q, theta), _rope(kk, theta)
                o = mm(attention(low(q), low(kk), low(v)), p + "self_attn.out_proj.weight")
            x = x + o
            m = _rms(x, w(p + "ffn_norm.weight"), eps)
            if i < hf["num_dense_layers"]:
                f = swiglu(m, p + "feed_forward.")
            else:
                mt = m.reshape(B * S, -1)
                scores = jax.nn.sigmoid(jnp.dot(mt, w(p + "feed_forward.gate.weight").T, preferred_element_type=jnp.float32))
                biased = scores + w(p + "feed_forward.expert_bias")[None, :]
                chosen_from = scores if variant == "no_selection_bias" else biased
                _, free = jax.lax.top_k(chosen_from, k)
                choices.append(np.asarray(free))
                select.append(np.asarray(chosen_from))
                idx = free if forced is None else jnp.asarray(forced[len(choices) - 1])
                wts = jnp.take_along_axis(biased if variant == "bias_in_weights" else scores, idx, axis=1)
                if hf["norm_topk_prob"]:
                    wts = wts / (wts.sum(axis=1, keepdims=True) + 1e-6)
                wts = wts * scale
                y = jnp.zeros(mt.shape, jnp.float32)
                for e in range(E):  # every expert over every token, masked: plain, not fast
                    weight = (wts * (idx == e)).sum(-1)  # [T]: this expert's weight for each token, 0 if not chosen
                    y = y + weight[:, None] * swiglu(mt, f"{p}feed_forward.experts.{e}.")
                f = y.reshape(B, S, -1)
            x = x + f
        x = _rms(x, w("model.embedding_norm.weight"), eps)
        if logits_at is not None:
            x = x[np.arange(B)[:, None], np.asarray(logits_at)]
        logits = mm(x, "model.embed_tokens.weight")
    return {
        "logits": np.asarray(logits), "choices": np.stack(choices), "select": np.stack(select),
        "tails": np.stack(tails) if tails else np.zeros((0, B, K - 1, D), np.float32),
    }


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
