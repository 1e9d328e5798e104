"""Device-side token sampling with per-slot parameters.

All slots in the continuous-batching decode step sample in one fused call:
per-slot temperature / top-k / top-p live in device arrays so the sampler
is a single jitted kernel with no host branching. Greedy is temperature=0.
Everything here traces under ``jax.named_scope("sampling")``: the name a
device trace files these operations' time under.

top-k uses `lax.top_k` with a static MAX_TOP_K; requests asking for
larger k are clamped. On a v5e the 2-D top-128 of a [32, 152064] batch is
a TopK custom call of 1.8 ms, and a `top_k` the compiler lowers to a
full-vocabulary sort costs 6.2 ms (PERF.md section 5): neither is free,
so the decode step runs each optional part of its epilogue only when a
slot of the batch asked for it (`epilogue_parts`).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

MAX_TOP_K = 128

# The optional parts of the decode step's epilogue, in the order of
# `epilogue_parts`' result; the `part` label of
# kubeai_engine_decode_epilogue_chunks_total.
EPILOGUE_PARTS = ("top_logprobs", "candidates", "penalties")


@dataclass
class SamplingParams:
    """Host-side request sampling options (OpenAI API surface)."""

    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0  # 0 = disabled
    max_tokens: int = 256
    stop: tuple[str, ...] = ()
    seed: int | None = None
    logprobs: bool = False
    # OpenAI penalties over the generated text so far: presence is a
    # flat subtraction for any token that has appeared, frequency scales
    # with its occurrence count. Applied device-side from the engine's
    # token history (sampling.apply_penalties).
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    # OpenAI logit_bias: ((token_id, bias), ...) with bias in [-100,
    # 100]; applied to every choice including the first generated token
    # (prefill's sample). Entry count capped by EngineConfig.
    logit_bias: tuple = ()


def epilogue_parts(active, temperature, presence, frequency, want_top):
    """Which optional parts of the decode epilogue a batch needs, as
    three scalar bools in EPILOGUE_PARTS' order: the top-N log-prob
    alternatives, the sampling candidates, the penalties. Each holds if
    some ACTIVE slot asked for it; what an idle slot's stale parameters
    say decides nothing. One statement for both sides: the decode
    program calls it on its traced [B] inputs (the predicates of its
    three `lax.cond`s), the host on the numpy arrays it uploads with the
    same dispatch (what it fetches, and the counter)."""
    return (
        (active & want_top).any(),
        (active & ~(temperature <= 0.0)).any(),  # not greedy, as the step reads it
        (active & ((presence != 0.0) | (frequency != 0.0))).any(),
    )


def apply_penalties(
    logits: jnp.ndarray,  # [B, V] float32
    hist: jnp.ndarray,  # [B, W] int32 token ids (engine token history)
    hist_valid: jnp.ndarray,  # [B, W] bool — which history columns count
    presence: jnp.ndarray,  # [B] float32
    frequency: jnp.ndarray,  # [B] float32
) -> jnp.ndarray:
    """OpenAI presence/frequency penalties, computed in-graph from the
    engine's device-resident token history (no [B, V] count state to
    carry/donate): scatter-max builds the appeared-at-all flag, scatter-
    add the occurrence counts — duplicate history entries accumulate
    exactly count * frequency. Rows with both penalties zero subtract
    zeros. The two [B, V] scatters are not noise next to the weight
    reads: 3.9% of a decode step at a 152k vocabulary and 32 slots, 4.9%
    at 32k and 8 (PERF.md section 5), so the decode step calls this only
    when a slot of the batch set a penalty (`epilogue_parts`)."""
    B, V = logits.shape
    with jax.named_scope("sampling"):
        b_idx = jnp.arange(B)[:, None]
        v = hist_valid.astype(jnp.float32)
        occurred = jnp.zeros((B, V), jnp.float32).at[b_idx, hist].max(v)
        counts = jnp.zeros((B, V), jnp.float32).at[b_idx, hist].add(v)
        return logits - presence[:, None] * occurred - frequency[:, None] * counts


def apply_logit_bias(
    logits: jnp.ndarray,  # [B, V] float32
    bias_ids: jnp.ndarray,  # [B, K] int32 (pad rows: id 0 / bias 0.0)
    bias_vals: jnp.ndarray,  # [B, K] float32
) -> jnp.ndarray:
    """OpenAI logit_bias as a per-slot scatter-add (padding adds 0.0 at
    token 0 — a no-op). Like penalties, bias steers CHOICE only; callers
    keep reported logprobs on the raw logits."""
    B = logits.shape[0]
    with jax.named_scope("sampling"):
        return logits.at[jnp.arange(B)[:, None], bias_ids].add(bias_vals)


def sample(
    logits: jnp.ndarray,  # [B, V] float32
    keys: jnp.ndarray,  # [B] PRNG keys (jax.random.key dtype)
    temperature: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B] int32; 0 = disabled
    max_top_k: int = MAX_TOP_K,  # static candidate-space cap; <=0 = full vocab
) -> jnp.ndarray:
    """Sample one token per slot. Returns [B] int32."""
    with jax.named_scope("sampling"):
        return _sample(logits, keys, temperature, top_p, top_k, max_top_k)


def _sample(logits, keys, temperature, top_p, top_k, max_top_k):
    B, V = logits.shape

    # Work in the top-max_top_k candidate space; for top_k==0/top_p==1 the
    # tail beyond it is negligible for any trained model, and greedy
    # (temperature 0) uses the exact argmax below. Operators wanting exact
    # full-distribution sampling set EngineConfig.max_top_k <= 0 and pay
    # the full-vocab sort.
    cap = V if max_top_k <= 0 else min(max_top_k, V)
    vals, idxs = jax.lax.top_k(logits, cap)  # [B, K] sorted desc

    k = jnp.where(top_k <= 0, cap, jnp.minimum(top_k, cap))
    rank = jnp.arange(vals.shape[1])[None, :]
    vals = jnp.where(rank < k[:, None], vals, -jnp.inf)

    # top-p over the candidate distribution.
    safe_temp = jnp.maximum(temperature, 1e-6)[:, None]
    probs = jax.nn.softmax(vals / safe_temp, axis=-1)
    cumprobs = jnp.cumsum(probs, axis=-1)
    # Keep tokens whose *preceding* cumulative mass is < top_p (always keeps
    # the first token).
    keep = (cumprobs - probs) < top_p[:, None]
    vals = jnp.where(keep, vals, -jnp.inf)

    sampled_rank = jax.vmap(
        lambda v, key, t: jax.random.categorical(key, v / jnp.maximum(t, 1e-6))
    )(vals, keys, temperature)
    sampled = jnp.take_along_axis(idxs, sampled_rank[:, None], axis=1)[:, 0]

    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(temperature <= 0.0, greedy, sampled).astype(jnp.int32)
