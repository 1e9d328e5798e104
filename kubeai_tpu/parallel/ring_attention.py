"""Ring attention: exact causal attention over sequence-sharded inputs.

Long-context sequence parallelism (absent from the reference — SURVEY.md
§5 "Long-context / sequence parallelism"): the sequence dim is sharded
over the `sp` mesh axis; each device keeps its query block resident while
KV blocks rotate around the ring via `ppermute` (ICI neighbor traffic
only), accumulating flash-attention-style online softmax statistics. The
KV transfer for step i+1 overlaps the block compute for step i — XLA
schedules the ppermute DMA concurrently with the einsums.

Memory per device: O(S/n * S/n) attention scores instead of O(S^2).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _block_attend(q, k, v, q_pos, k_pos, scale, o, l, m):
    """One KV block of online-softmax attention (GQA grouped).
    q [B,Sq,H,h]; k,v [B,Sk,Kv,h]; positions [Sq]/[Sk];
    o [B,Sq,H,h] f32, l/m [B,Sq,H] f32 running stats."""
    B, Sq, H, h = q.shape
    Kv = k.shape[2]
    G = H // Kv

    qg = q.reshape(B, Sq, Kv, G, h)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale  # [B,Kv,G,Sq,Sk]
    causal = (k_pos[None, :] <= q_pos[:, None])[None, None, None]
    s = jnp.where(causal, s, _NEG_INF)

    s_flat = s.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, -1)  # [B,Sq,H,Sk]
    m_new = jnp.maximum(m, s_flat.max(axis=-1))
    # _NEG_INF is finite, so m - m_new is always well defined; rows with no
    # unmasked key yet keep l == 0 and o == 0 (p forced to zero below).
    p = jnp.where(
        s_flat > _NEG_INF / 2,
        jnp.exp(s_flat - m_new[..., None]),
        0.0,
    )
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + p.sum(axis=-1)
    p_g = p.reshape(B, Sq, Kv, G, -1).transpose(0, 2, 3, 1, 4)  # [B,Kv,G,Sq,Sk]
    o_blk = jnp.einsum("bkgqs,bskh->bqkgh", p_g, v.astype(jnp.float32)).reshape(B, Sq, H, h)
    o_new = o * alpha[..., None] + o_blk
    return o_new, l_new, m_new


def _ring_body(my_idx, n, block_len, q, k0, v0, scale, vary_axes=("sp",)):
    B, Sq, H, h = q.shape
    q_pos = my_idx * block_len + jnp.arange(Sq)

    o = jnp.zeros((B, Sq, H, h), jnp.float32)
    l = jnp.zeros((B, Sq, H), jnp.float32)
    m = jnp.full((B, Sq, H), _NEG_INF, jnp.float32)
    # The carry becomes device-varying inside the loop (my_idx-dependent
    # masks, and q/k vary over every sharded mesh axis); mark the initial
    # values over the same axes so scan's carry types line up.
    o, l, m = (jax.lax.pvary(t, vary_axes) for t in (o, l, m))

    def step(carry, i):
        o, l, m, k_cur, v_cur = carry
        src_idx = (my_idx - i) % n  # whose KV block we hold at step i
        k_pos = src_idx * block_len + jnp.arange(k_cur.shape[1])
        o, l, m = _block_attend(q, k_cur, v_cur, q_pos, k_pos, scale, o, l, m)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt = jax.lax.ppermute(k_cur, "sp", perm)
        v_nxt = jax.lax.ppermute(v_cur, "sp", perm)
        return (o, l, m, k_nxt, v_nxt), None

    (o, l, m, _, _), _ = jax.lax.scan(step, (o, l, m, k0, v0), jnp.arange(n))
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, scale: float | None = None):
    """Causal ring attention over the mesh's `sp` axis.

    q/k/v: GLOBAL arrays [B, S, H|Kv, h] (sharded or shardable on S);
    returns [B, S, H, h] with the same sequence sharding.
    """
    n = mesh.shape["sp"]
    B, S, H, h = q.shape
    assert S % n == 0, f"sequence {S} not divisible by sp={n}"
    if scale is None:
        scale = h**-0.5
    block_len = S // n

    # Partition every axis the surrounding program shards: batch over dp
    # and heads over tp (sp-only specs would all-gather dp/tp-sharded
    # q/k/v at the shard_map boundary — redundant compute AND defeating
    # tp's memory split). GQA grouping survives tp head sharding because
    # wq/wk/wv shard H and Kv by the same factor. dp/tp may be size-1
    # axes (make_mesh always creates all four).
    Kv = k.shape[2]
    dp_n = mesh.shape.get("dp", 1)
    tp_n = mesh.shape.get("tp", 1)
    dp_ax = "dp" if B % max(dp_n, 1) == 0 else None
    tp_ax = (
        "tp" if tp_n >= 1 and H % tp_n == 0 and Kv % tp_n == 0 else None
    )
    spec = P(dp_ax, "sp", tp_ax, None)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    def sharded(q_blk, k_blk, v_blk):
        my_idx = jax.lax.axis_index("sp")
        vary = tuple(a for a in (dp_ax, "sp", tp_ax) if a)
        return _ring_body(
            my_idx, n, block_len, q_blk, k_blk, v_blk, scale, vary_axes=vary
        )

    return sharded(q, k, v)
