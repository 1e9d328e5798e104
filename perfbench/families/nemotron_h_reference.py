"""The plain reference of `model_type: nemotron_h` (NVIDIA-Nemotron-3-Super:
HF `modeling_nemotron_h`): the forward pass in `jax.numpy`, float32 at the
highest matmul precision, the recurrence token by token, no chunked form,
no cache, no batching of experts (a Python loop over them with a mask). It
imports nothing of `kubeai_tpu` and reads weights by their HF names. Block
`i` of `hybrid_override_pattern`, with `u = rmsnorm(x; norm_i)` (eps
`layer_norm_epsilon`):

    x <- x + Mixer_i(u)

    M   [z | xBC | dt] = in_proj(u)             # widths d_inner | d_inner + 2 G N | heads
        xBC = silu(conv1d(xBC) + bias)          # depthwise, causal, `conv_kernel` taps, zeros before the sequence
        x_h [heads, head_dim], B_g [G, N], C_g [G, N] = split(xBC);  head h reads group h // (heads / G)
        d = softplus(dt + dt_bias);  a = exp(d A),  A = -exp(A_log)          # not clamped
        S_t[h] = a_t[h] S_{t-1}[h] + d_t[h] x_t[h] (x) B_t[g]                # [head_dim, N], S_{-1} = 0
        y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]
        out = out_proj(rmsnorm_G(y * silu(z)) * norm.weight)                 # the gate first, a norm a group
    *   q, k, v = u Wq, u Wk, u Wv;  o = softmax(q k^T / sqrt(d) + causal) v;  out = o Wo     # no rope, no bias
    E   s = sigmoid(u W_r);  S = the top k of s + e_score_correction_bias
        g_e = s_e / (sum_{S} s + 1e-20) * routed_scaling_factor
        l = fc1_latent_proj(u);  r = sum_{e in S} g_e down_e(relu(up_e(l))^2)
        out = fc2_latent_proj(r) + shared.down(relu(shared.up(u))^2)

then `norm_f` and `lm_head`.

Departures from the published description, both stated in the
configuration's file: the next-token-prediction head
(`num_nextn_predict_layers`, `mtp_hybrid_override_pattern`) is left out (it
drafts; it changes no served distribution); and A CHIP'S SHARE of the
experts: with `router_experts` R and `experts_first` f in the config, only
experts f .. f + n_routed_experts - 1 exist here; the router still scores
all R and normalises over all k chosen, the held ones contribute, the rest
are left out, and that partial result goes on to the next block. Without
`router_experts` every expert is here and `r` is the whole sum. Attention
is computed a block of queries at a time (a block's softmax is the whole
softmax of its rows).

`forced` routes every `E` block by the given choices (the program's own),
so that a comparison of logits does not hang on which side of a near-tie
each side's rounding fell; the FREE choices and what they were made from
(`s + bias`) are returned beside. `variant` names a deliberate fault, for
controls that have to fail: "gate_after_norm" (the grouped norm before the
gate), "rope" (rotary embedding on the attention blocks), "silu_act" (SiLU
where the experts square a ReLU). `state_dtype` rounds the recurrence's state to that dtype after every
token (the control of the float32 state). `logits_at` [B, n] keeps the head
to those positions of each row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

VARIANTS = ("", "gate_after_norm", "rope", "silu_act")
QUERY_BLOCK = 512


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    S, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = jnp.asarray(np.arange(S, dtype=np.float64)[:, None] * inv[None, :], jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def held_experts(hf: dict) -> range:
    """The experts that exist here, by their index in the router."""
    first = hf.get("experts_first") or 0
    return range(first, first + hf["n_routed_experts"])


def expert_block(w, mm, hf: dict, p: str, u, forced=None, act=None):
    """The `E` mixer on normed rows u [T, D]: (out [T, D], the free
    choices [T, k], what they were made from [T, R]). *w(name)* a float32
    tensor, *mm(x, name)* the HF linear x W^T."""
    k, scale = hf["num_experts_per_tok"], hf["routed_scaling_factor"]
    act = act or (lambda v: jnp.square(jax.nn.relu(v)))
    s = jax.nn.sigmoid(jnp.dot(u, w(p + "gate.weight").T, preferred_element_type=jnp.float32))
    select = s + w(p + "gate.e_score_correction_bias")[None, :]
    _, free = jax.lax.top_k(select, k)
    idx = free if forced is None else jnp.asarray(forced)
    g = jnp.take_along_axis(s, idx, axis=1)
    if hf.get("norm_topk_prob", True):
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    g = g * scale
    latent = mm(u, p + "fc1_latent_proj.weight")
    r = jnp.zeros(latent.shape, jnp.float32)
    for e in held_experts(hf):  # every held expert over every token, masked: plain, not fast
        weight = (g * (idx == e)).sum(-1)  # [T]: this expert's weight for each token, 0 if not chosen
        r = r + weight[:, None] * mm(act(mm(latent, f"{p}experts.{e}.up_proj.weight")), f"{p}experts.{e}.down_proj.weight")
    out = mm(r, p + "fc2_latent_proj.weight")
    out = out + mm(act(mm(u, p + "shared_experts.up_proj.weight")), p + "shared_experts.down_proj.weight")
    return out, free, select


def forward(
    get, hf: dict, tokens, forced=None, variant: str = "", logits_at=None, upcast=None, state_dtype=None,
) -> dict:
    """*get(name)* -> the HF tensor as a numpy array; *tokens* [B, S] ints.
    Returns `logits` [B, S or n, V] (float32 numpy), `choices` [n_E, B*S,
    k] (the free choices), `select` [n_E, B*S, R] (what each was made
    from), `states` [n_M, B, heads, head_dim, N] (the recurrence's state
    after the last token), `decay_in_half_to_one` (an `M` block's share
    of (token, head) pairs whose decay a step lies in (0.5, 1)) and
    `mean_log_decay` [n_M, heads] (the mean over tokens of log a). *upcast*: a dict a caller with several passes
    over one checkpoint hands to each, to keep the float32 tensors of the
    first for the next (where they fit)."""
    assert variant in VARIANTS, variant
    H, Kv, hd = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    Hm, P, N, G, K = hf["mamba_num_heads"], hf["mamba_head_dim"], hf["ssm_state_size"], hf["n_groups"], hf["conv_kernel"]
    eps = hf["layer_norm_epsilon"]
    inner = Hm * P
    pattern = hf["hybrid_override_pattern"][: hf["num_hidden_layers"]]
    tokens = np.asarray(tokens)
    B, S = tokens.shape
    Gq = H // Kv

    def w(name):
        if upcast is not None and name in upcast:
            return upcast[name]
        a = jnp.asarray(np.asarray(get(name)).astype(np.float32))
        if upcast is not None:
            upcast[name] = a
        return a

    def mm(x, name):  # x @ W^T, the HF linear
        return jnp.dot(x, w(name).T, preferred_element_type=jnp.float32)

    def attention(q, kk, v):
        kpos = jnp.arange(S)[None, :]
        out = []
        for q0 in range(0, S, QUERY_BLOCK):
            qb = q[:, q0 : q0 + QUERY_BLOCK].reshape(B, -1, Kv, Gq, hd)
            mask = kpos <= (q0 + jnp.arange(qb.shape[1]))[:, None]
            s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kk, preferred_element_type=jnp.float32) * hd**-0.5
            pr = jax.nn.softmax(jnp.where(mask[None, None, None], s, -jnp.inf), axis=-1)
            out.append(jnp.einsum("bkgqs,bskd->bqkgd", pr, v, preferred_element_type=jnp.float32).reshape(B, -1, H * hd))
        return jnp.concatenate(out, axis=1)

    def mamba(u, p):
        zxd = mm(u, p + "in_proj.weight")
        z, xBC, dt = zxd[..., :inner], zxd[..., inner : inner + inner + 2 * G * N], zxd[..., 2 * inner + 2 * G * N :]
        cw = w(p + "conv1d.weight")[:, 0, :]  # [C, K]: tap K-1 is on the row itself
        padded = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
        xBC = jax.nn.silu(sum(padded[:, k : k + S] * cw[:, k] for k in range(K)) + w(p + "conv1d.bias"))
        xs = xBC[..., :inner].reshape(B, S, Hm, P)
        Bm = jnp.repeat(xBC[..., inner : inner + G * N].reshape(B, S, G, N), Hm // G, axis=2)  # a head's group
        Cm = jnp.repeat(xBC[..., inner + G * N :].reshape(B, S, G, N), Hm // G, axis=2)
        d = jax.nn.softplus(dt + w(p + "dt_bias"))  # [B, S, Hm]
        a = jnp.exp(d * -jnp.exp(w(p + "A_log")))

        def token(state, t):
            a_t, d_t, x_t, B_t, C_t = t
            state = a_t[..., None, None] * state + (d_t[..., None] * x_t)[..., None] * B_t[:, :, None, :]
            if state_dtype is not None:
                state = state.astype(state_dtype).astype(jnp.float32)
            return state, (state * C_t[:, :, None, :]).sum(-1)

        decays.append(float(((a > 0.5) & (a < 1.0)).mean()))
        log_decay.append(np.asarray(jnp.log(a).mean(axis=(0, 1))))
        state, ys = jax.lax.scan(
            token, jnp.zeros((B, Hm, P, N), jnp.float32),
            tuple(jnp.swapaxes(t, 0, 1) for t in (a, d, xs, Bm, Cm)),
        )
        y = jnp.swapaxes(ys, 0, 1) + w(p + "D")[None, None, :, None] * xs
        y = y.reshape(B, S, inner)
        gate = jax.nn.silu(z)
        grouped = lambda v: _rms(v.reshape(B, S, G, inner // G), 1.0, eps).reshape(B, S, inner)  # noqa: E731
        y = grouped(y) * gate if variant == "gate_after_norm" else grouped(y * gate)
        return mm(y * w(p + "norm.weight"), p + "out_proj.weight"), state

    act = jax.nn.silu if variant == "silu_act" else None
    choices, select, states, decays, log_decay = [], [], [], [], []
    n_E = 0
    with jax.default_matmul_precision("highest"):
        x = w("backbone.embeddings.weight")[tokens]
        for i, kind in enumerate(pattern):
            p = f"backbone.layers.{i}.mixer."
            u = _rms(x, w(f"backbone.layers.{i}.norm.weight"), eps)
            if kind == "M":
                out, state = mamba(u, p)
                states.append(np.asarray(state))
            elif kind == "*":
                q = mm(u, p + "q_proj.weight").reshape(B, S, H, hd)
                kk = mm(u, p + "k_proj.weight").reshape(B, S, Kv, hd)
                v = mm(u, p + "v_proj.weight").reshape(B, S, Kv, hd)
                if variant == "rope":
                    q, kk = _rope(q, hf["rope_theta"]), _rope(kk, hf["rope_theta"])
                out = mm(attention(q, kk, v), p + "o_proj.weight")
            elif kind == "E":
                out, free, sel = expert_block(
                    w, mm, hf, p, u.reshape(B * S, -1), None if forced is None else forced[n_E], act,
                )
                out = out.reshape(B, S, -1)
                choices.append(np.asarray(free))
                select.append(np.asarray(sel))
                n_E += 1
            else:
                raise ValueError(f"block {i} of the pattern is {kind!r}: not M, * or E")
            x = x + out
        x = _rms(x, w("backbone.norm_f.weight"), eps)
        if logits_at is not None:
            x = x[np.arange(B)[:, None], np.asarray(logits_at)]
        logits = mm(x, "lm_head.weight")
    return {
        "logits": np.asarray(logits),
        "choices": np.stack(choices) if choices else np.zeros((0, B * S, 0), np.int32),
        "select": np.stack(select) if select else np.zeros((0, B * S, 0), np.float32),
        "states": np.stack(states) if states else np.zeros((0,), np.float32),
        "decay_in_half_to_one": decays,
        "mean_log_decay": np.stack(log_decay) if log_decay else np.zeros((0,), np.float32),
    }


def choice_disagreements(program_choices, ref_choices, ref_select, valid=None) -> dict:
    """The program's choices against the reference's free ones, per
    (block, token) as SETS: where they differ, the gap in what the
    reference chose from (`s + bias`) between what each side chose and the
    other did not. `worst_gap` is the largest such gap: a disagreement is
    a near-tie only if it is small. *valid* [tokens] (bool) names the
    tokens the program computed on the reference's inputs."""
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
            worst = max(worst, float(max(sel[l, t, only_r]) - min(sel[l, t, only_p])))
    return {"compared": n, "disagree": flips, "worst_gap": worst}
