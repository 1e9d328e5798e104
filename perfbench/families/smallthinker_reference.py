"""The plain reference of `model_type: smallthinker` (SmallThinker-21BA3B,
HF `modeling_smallthinker`): the forward pass in `jax.numpy`, float32 at
the highest matmul precision, no cache, no kernels, a Python loop over the
experts with a mask. It imports nothing of `kubeai_tpu` and reads weights
by their HF names, one tensor upcast at a time. With `x` a layer's input
(the residual stream) and `l` its index:

    r = x W_r                                   # router logits, from the layer's INPUT, before any norm
    a = rmsnorm(x; g1);  q, k, v = a Wq, a Wk, a Wv          # no biases, no q/k norm
    q, k = rope(q), rope(k)        where rope_layout[l] == 1 # half-split pairs (x[j], x[j + d/2])
    o = softmax(q k^T / sqrt(d) + causal [+ "key j > i - window" where sliding_window_layout[l] == 1]) v
    h = x + o Wo;  m = rmsnorm(h; g2)
    S = top-k of r;  w = softmax(r[S])          # = softmax over all experts, at the chosen, renormalised
    y = sum_{e in S} w_e (relu(m Wg_e) * (m Wu_e)) Wd_e;  x' = h + y

then a final rmsnorm and the head.

Departures from the HF module, all without effect on the result: attention
is computed a block of queries at a time against all keys (the scores of
6000 tokens do not fit at once; a block's softmax is the whole softmax of
its rows); no dropout, no cache, no padding mask; the router's softmax is
taken over the chosen logits in float32 (HF: the same, `norm_topk_prob`
then divides by a sum that is already 1); `moe_enable_secondary_experts`
is false in the published config and left out.

`forced` routes every layer by the given choices (the program's own), so
that a comparison of logits does not hang on which side of a near-tie each
side's rounding fell; the reference's FREE choices and the logits they were
made from are returned beside, for the comparison of the choices
themselves. `variant` names a deliberate fault, for the controls that have
to fail: "window_ignored" (every layer sees the whole context),
"rope_on_global" (rope on the layers that publish none),
"router_post_norm" (the router reads m, as most expert models' do),
"silu_gate" (SwiGLU). `dtype` computes in a lower precision
("float8_e4m3fn": every matmul's inputs rounded through it). `logits_at`
[B, n] keeps the head to those positions of each row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

VARIANTS = ("", "window_ignored", "rope_on_global", "router_post_norm", "silu_gate")
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
    Returns `logits` [B, S or n, V] (float32 numpy), `choices` [L, B*S, k]
    (the free choices) and `select` [L, B*S, E] (the router's logits,
    what the choice was made from). *upcast*: a dict a caller with several
    passes over one checkpoint hands to each, to keep the float32 tensors
    of the first for the next (where they fit)."""
    assert variant in VARIANTS, variant
    lowp = jnp.dtype(dtype) if dtype != "float32" else None
    H, Kv, d, eps = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"], hf["rms_norm_eps"]
    k, E, L = hf["moe_num_active_primary_experts"], hf["moe_num_primary_experts"], hf["num_hidden_layers"]
    window = hf["sliding_window_size"]
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

    gate_act = jax.nn.silu if variant == "silu_gate" else jax.nn.relu
    choices, select = [], []
    with jax.default_matmul_precision("highest"):
        x = w("model.embed_tokens.weight")[tokens]
        for i in range(L):
            p = f"model.layers.{i}."
            windowed = bool(hf["sliding_window_layout"][i]) and variant != "window_ignored"
            rotated = bool(hf["rope_layout"][i]) or variant == "rope_on_global"
            router_in = x
            a = _rms(x, w(p + "input_layernorm.weight"), eps)
            q = mm(a, p + "self_attn.q_proj.weight").reshape(B, S, H, d)
            kk = mm(a, p + "self_attn.k_proj.weight").reshape(B, S, Kv, d)
            v = mm(a, p + "self_attn.v_proj.weight").reshape(B, S, Kv, d)
            if rotated:
                q, kk = _rope(q, hf["rope_theta"]), _rope(kk, hf["rope_theta"])
            x = x + mm(attention(q, kk, v, windowed), p + "self_attn.o_proj.weight")
            m = _rms(x, w(p + "post_attention_layernorm.weight"), eps)
            mt = m.reshape(B * S, -1)
            if variant == "router_post_norm":
                router_in = m
            logits_r = jnp.dot(
                router_in.reshape(B * S, -1), w(p + "block_sparse_moe.primary_router.weight").T,
                preferred_element_type=jnp.float32,
            )
            _, free = jax.lax.top_k(logits_r, k)
            choices.append(np.asarray(free))
            select.append(np.asarray(logits_r))
            idx = free if forced is None else jnp.asarray(forced[i])
            wts = jax.nn.softmax(jnp.take_along_axis(logits_r, idx, axis=1), axis=1)
            y = jnp.zeros(mt.shape, jnp.float32)
            for e in range(E):  # every expert over every token, masked: plain, not fast
                pe = f"{p}block_sparse_moe.experts.{e}."
                weight = (wts * (idx == e)).sum(-1)  # [T]: this expert's weight for each token, 0 if not chosen
                out = mm(gate_act(mm(mt, pe + "gate.weight")) * mm(mt, pe + "up.weight"), pe + "down.weight")
                y = y + weight[:, None] * out
            x = x + y.reshape(B, S, -1)
        x = _rms(x, w("model.norm.weight"), eps)
        if logits_at is not None:
            x = x[np.arange(B)[:, None], np.asarray(logits_at)]
        logits = mm(x, "lm_head.weight")
    return {
        "logits": np.asarray(logits),
        "choices": np.stack(choices),
        "select": np.stack(select),
    }


def choice_disagreements(program_choices, ref_choices, ref_select, valid=None) -> dict:
    """The program's choices against the reference's free ones, per
    (layer, token) as SETS: where they differ, the gap in the reference's
    own router logits between what each side chose and the other did not.
    `worst_gap` is the largest such gap: a disagreement is a near-tie only
    if it is small. *valid* [tokens] (bool) names the tokens the program
    computed on the reference's inputs; the others are not compared."""
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
