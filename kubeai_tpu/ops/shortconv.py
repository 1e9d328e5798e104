"""The gated short convolution (LFM2's `conv` operator): two gates around a
depthwise causal convolution of `K` taps (3 as published), between two
projections. With `u` [.., D] the operator's normed input:

    B, C, x = split3(u W_in)             # W_in [D, 3C], in that order
    z   = B * x                          # the gate going in
    c_t = sum_{k<K} w[k] * z_{t-(K-1)+k} # depthwise over the C channels, causal, zeros before the start
    y   = (C * c) W_out                  # the gate going out; W_out [C, D]

No activation anywhere and no bias. **What a sequence carries between
calls** is the last `K - 1` rows of `z`: `K - 1` rows of C values whatever
the context, so it lives by SLOT beside the paged pool
(`models/lfm2_moe.py`: `cache["conv"]`), as Mamba-2's tail does
(`ops/ssm.py::causal_conv`, which is the convolution here too).

**What is rounded where.** Both products are taken in float32 and rounded
ONCE each to the activations' dtype: `z` before the taps (the tail holds
`z` in that dtype, so a row's taps read the same values whether a row
before it came from this call or from the tail: cold and chunked prefill
and decode see the same `z`), and `C * c` before `W_out` (`c`, the sum
over the taps, stays float32 until then).

**Rows that are not real** (a bucket's padding, a decode row of a slot
that holds no request) move no tail: the S-row form takes the new tail at
the last REAL row (`n_real`; none at all: the old tail), the decode form
keeps a row's tail where `live` is false, bit for bit.

Plain XLA everywhere: at decode the operator is two matmuls over 33.5 MB
of weights a layer and 8 KiB of state a slot.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from kubeai_tpu.ops.ssm import causal_conv


def _gate_in(u, w_in):
    """(z = B * x rounded once, C) of u [.., D]."""
    with jax.named_scope("conv.in_proj"):
        bcx = jnp.dot(u, w_in)
    B, C, x = jnp.split(bcx, 3, axis=-1)
    with jax.named_scope("conv.gate"):
        z = (B.astype(jnp.float32) * x.astype(jnp.float32)).astype(u.dtype)
    return z, C


def _gate_out(C, c32, w_out):
    with jax.named_scope("conv.gate"):
        g = (C.astype(jnp.float32) * c32).astype(C.dtype)
    with jax.named_scope("conv.out_proj"):
        return jnp.dot(g, w_out)


def gated_short_conv(u, tail, n_real, w_in, w_conv, w_out):
    """S rows a sequence. u [B, S, D]; tail [B, K-1, C] the `z` rows
    before them (zeros at a sequence's start); n_real [B] how many of the S
    rows are real (they come first); w_in [D, 3C]; w_conv [K, C] with tap
    `K-1` on the row itself; w_out [C, D]. Returns (y [B, S, D] in u's
    dtype, the new tail in tail's dtype)."""
    z, C = _gate_in(u, w_in)
    with jax.named_scope("conv.taps"):
        c32, tail = causal_conv(z, tail, n_real, w_conv, jnp.zeros((w_conv.shape[1],), jnp.float32))
    return _gate_out(C, c32, w_out), tail


def gated_short_conv_step(tails, j, u, live, w_in, w_conv, w_out):
    """One row a SLOT, in slot order, on layer *j* (an int or traced) of the
    slots' stacked tails [n, slots, K-1, C] (`cache["conv"]`): u [slots, D];
    live [slots] bool. Returns (y [slots, D], the stacked tails with layer
    j's live rows moved on by one row; a slot that is not live keeps its
    tail). The same arithmetic as `gated_short_conv` at S = 1, without its
    gather: the new tail is the old one's last K-2 rows and the row's `z`."""
    K = w_conv.shape[0]
    z, C = _gate_in(u, w_in)
    with jax.named_scope("conv.taps"):
        tail = jax.lax.dynamic_index_in_dim(tails, j, keepdims=False)  # [slots, K-1, C]
        full = jnp.concatenate([tail.astype(z.dtype), z[:, None]], axis=1)  # [slots, K, C]
        w32 = w_conv.astype(jnp.float32)
        c32 = sum(full[:, k].astype(jnp.float32) * w32[k] for k in range(K))
        moved = jnp.where(live[:, None, None], full[:, 1:].astype(tails.dtype), tail)
        tails = jax.lax.dynamic_update_index_in_dim(tails, moved, j, 0)
    return _gate_out(C, c32, w_out), tails
