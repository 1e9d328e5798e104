"""The plain reference of `model_type: deepseek_v3` (HF `modeling_deepseek_v3`,
no query low-rank, one routing group): the forward pass in `jax.numpy`,
float32 at the highest matmul precision, full causal attention in the
EXPANDED form with no cache, a Python loop over the experts with a mask.
It imports nothing of `kubeai_tpu` and reads weights by their HF names,
one layer upcast at a time.

    h = x + Attn(RMSNorm(x));  y = h + FFN_i(RMSNorm(h))
    Attn: q = x W_q -> [H, dn+dr]; kv_a = x W_kva -> [r | dr];
          c = RMSNorm(kv_a[:r]); kv = c W_kvb -> [H, dn+dv];
          rope on q_rope and on the ONE k_rope, on interleaved pairs;
          softmax(q k^T (dn+dr)^-0.5) v, float32; W_o
    FFN_i: SwiGLU(intermediate_size) for i < first_k_dense_replace, else
          s = sigmoid(float32(x) W_g^T); top-k of s + b; weights s at the
          chosen / (their sum + 1e-20) * routed_scaling_factor;
          sum_i w_i SwiGLU_{e_i}(x) + SwiGLU_shared(x)

Departures from the HF module, all without effect on the result: the rope
rotates the pairs (x[2j], x[2j+1]) in place where HF first permutes them
to halves (q.k is the same under one permutation of both); no dropout, no
cache, no attention mask but the causal one; `n_group = topk_group = 1`,
so the group limit keeps every expert and is left out.

`forced` routes every expert layer by the given choices (the program's
own), so that a comparison of logits does not hang on which side of a
near-tie each side's rounding fell; the reference's FREE choices and the
scores they were made from are returned beside, for the comparison of the
choices themselves. `variant` names a deliberate fault, for the controls
that have to fail: "softmax_scoring", "bias_in_weights", "no_scaling",
"rope_halves"; `dtype` computes in a lower precision ("bfloat16", or
"float8_e4m3fn": every matmul's inputs rounded through it).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

VARIANTS = ("", "softmax_scoring", "bias_in_weights", "no_scaling", "rope_halves")


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps) * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta, halves: bool):
    """x [B, S, heads, d] at positions [S]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = jnp.asarray(np.asarray(positions, np.float64)[:, None] * inv[None, :], jnp.float32)  # [S, d/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    if halves:  # the fault: Llama's rotation of (x[j], x[j + d/2])
        a, b = x32[..., : d // 2], x32[..., d // 2 :]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1).astype(x.dtype)
    a, b = x32[..., 0::2], x32[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape).astype(x.dtype)


def forward(get, hf: dict, tokens, forced=None, variant: str = "", dtype: str = "float32") -> dict:
    """*get(name)* -> the HF tensor as a numpy array; *tokens* [B, S] ints.
    Returns logits [B, S, V] (float32 numpy), `choices` [expert layers,
    B*S, k] (the free choices), `select` [expert layers, B*S, E] (score +
    bias, what the choice was made from)."""
    assert variant in VARIANTS, variant
    lowp = jnp.dtype(dtype) if dtype in ("float8_e4m3fn",) else None
    cdt = jnp.float32 if lowp is not None else jnp.dtype(dtype)
    H, eps = hf["num_attention_heads"], hf["rms_norm_eps"]
    dn, dr, dv, r = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"], hf["kv_lora_rank"]
    k, E = hf["num_experts_per_tok"], hf["n_routed_experts"]
    tokens = np.asarray(tokens)
    B, S = tokens.shape

    def w(name):
        return jnp.asarray(np.asarray(get(name)).astype(np.float32)).astype(cdt)

    def mm(x, name):  # x @ W^T, the HF linear
        a, b = x, w(name)
        if lowp is not None:
            a, b = a.astype(lowp).astype(cdt), b.astype(lowp).astype(cdt)
        return jnp.dot(a, b.T, preferred_element_type=jnp.float32).astype(cdt)

    def swiglu(x, p):
        return mm(jax.nn.silu(mm(x, p + "gate_proj.weight")) * mm(x, p + "up_proj.weight"), p + "down_proj.weight")

    causal = jnp.tril(jnp.ones((S, S), bool))
    choices, select = [], []
    with jax.default_matmul_precision("highest"):
        x = w("model.embed_tokens.weight")[tokens]
        for i in range(hf["num_hidden_layers"]):
            p = f"model.layers.{i}."
            a = _rms(x, w(p + "input_layernorm.weight"), eps)
            q = mm(a, p + "self_attn.q_proj.weight").reshape(B, S, H, dn + dr)
            kva = mm(a, p + "self_attn.kv_a_proj_with_mqa.weight")
            c = _rms(kva[..., :r], w(p + "self_attn.kv_a_layernorm.weight"), eps)
            kv = mm(c, p + "self_attn.kv_b_proj.weight").reshape(B, S, H, dn + dv)
            halves = variant == "rope_halves"
            q_rope = _rope(q[..., dn:], np.arange(S), hf["rope_theta"], halves)
            k_rope = _rope(kva[..., None, r:], np.arange(S), hf["rope_theta"], halves)
            qf = jnp.concatenate([q[..., :dn], q_rope], -1)
            kf = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (B, S, H, dr))], -1)
            s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf, preferred_element_type=jnp.float32) * (dn + dr) ** -0.5
            s = jnp.where(causal[None, None], s, -jnp.inf)
            pr = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(cdt)
            o = jnp.einsum("bhqk,bkhd->bqhd", pr, kv[..., dn:], preferred_element_type=jnp.float32).astype(cdt)
            x = x + mm(o.reshape(B, S, H * dv), p + "self_attn.o_proj.weight")
            m = _rms(x, w(p + "post_attention_layernorm.weight"), eps)
            if i < hf["first_k_dense_replace"]:
                x = x + swiglu(m, p + "mlp.")
                continue
            mt = m.reshape(B * S, -1)
            logits_r = jnp.dot(
                mt.astype(jnp.float32), w(p + "mlp.gate.weight").astype(jnp.float32).T,
                preferred_element_type=jnp.float32,
            )
            score = jax.nn.softmax(logits_r, -1) if variant == "softmax_scoring" else jax.nn.sigmoid(logits_r)
            sel = score + jnp.asarray(np.asarray(get(p + "mlp.gate.e_score_correction_bias")).astype(np.float32))[None, :]
            _, free = jax.lax.top_k(sel, k)
            choices.append(np.asarray(free))
            select.append(np.asarray(sel))
            idx = free if forced is None else jnp.asarray(forced[len(choices) - 1])
            wts = jnp.take_along_axis(sel if variant == "bias_in_weights" else score, idx, axis=1)
            if hf.get("norm_topk_prob", True):
                wts = wts / (wts.sum(-1, keepdims=True) + 1e-20)
            if variant != "no_scaling":
                wts = wts * hf["routed_scaling_factor"]
            y = jnp.zeros(mt.shape, jnp.float32)
            for e in range(E):  # every expert over every token, masked: plain, not fast
                gate = (wts * (idx == e)).sum(-1)  # [T]: this expert's weight for each token, 0 if not chosen
                y = y + gate[:, None] * swiglu(mt, f"{p}mlp.experts.{e}.").astype(jnp.float32)
            y = y.astype(cdt) + swiglu(mt, p + "mlp.shared_experts.")
            x = x + y.reshape(B, S, -1)
        x = _rms(x, w("model.norm.weight"), eps)
        logits = mm(x, "lm_head.weight")
    return {
        "logits": np.asarray(logits.astype(jnp.float32)),
        "choices": np.stack(choices) if choices else np.zeros((0, B * S, k), np.int32),
        "select": np.stack(select) if select else np.zeros((0, B * S, E), np.float32),
    }


def choice_disagreements(program_choices, ref_choices, ref_select, valid=None) -> dict:
    """The program's choices against the reference's free ones, per
    (layer, token) as SETS: where they differ, the gap in the reference's
    own selection score between what each side chose and the other did
    not. `worst_gap` is the largest such gap: a disagreement is a
    near-tie only if it is small. *valid* [tokens] (bool) names the
    tokens the program computed on the reference's inputs; the others
    (padding a caller added to the reference's batch) are not compared."""
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
