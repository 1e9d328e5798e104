"""Mamba-2's two sequence operations, in plain XLA: the causal depthwise
convolution with a carried tail, and the selective state-space recurrence
in its chunked form (prefill: many rows of one sequence a call) and its
one-step form (decode: one row a sequence). Head `h` of `H` reads group
`h // (H // G)` of B and C; with `d_t` the step size after its softplus
and `A < 0` a scalar a head:

    a_t = exp(d_t A)
    S_t = a_t S_{t-1} + d_t x_t (x) B_t          # [P, N] a head, float32
    y_t = S_t C_t + D x_t

**What a sequence carries between calls** is `S` (float32) and the last
`K - 1` rows that went INTO the convolution. Neither grows with the
context, so neither lives in pages: the model module keeps them by slot.

**Rows that are not real** (a bucket's tail past the prompt, a decode row
of a slot that holds no request) must leave both exactly as they were.
That is a mask in the mathematics, not a copy around it: the caller zeroes
`d` for such rows, so `a = exp(0) = 1` and the row adds `0 * x (x) B`; the
new tail is taken at the last REAL row (`n_real`), which with no real row
at all is the old tail.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def causal_conv(x, tail, n_real, w, b):
    """Depthwise causal convolution of `K` taps over rows that continue a
    sequence. x [B, S, C] this call's rows; tail [B, K-1, C] the rows before
    them (zeros at a sequence's start); n_real [B] how many of the S rows
    are real (they come first); w [K, C] with tap `K-1` on the row itself;
    b [C]. Returns (y [B, S, C] float32, before any activation; the new
    tail [B, K-1, C] in tail's dtype: the last K-1 rows up to row
    `n_real - 1`, reaching back into the old tail where the call was
    shorter)."""
    K, S = w.shape[0], x.shape[1]
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # row t of the call is row t + K - 1
    w32 = w.astype(jnp.float32)
    y = b.astype(jnp.float32) + sum(full[:, k : k + S].astype(jnp.float32) * w32[k] for k in range(K))
    keep = n_real.astype(jnp.int32)[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    return y, jnp.take_along_axis(full, keep[:, :, None], axis=1).astype(tail.dtype)


def _grouped(x, G):
    """[B, H, ...] -> [B, G, H // G, ...]: the heads by the group they read."""
    return x.reshape(x.shape[0], G, x.shape[1] // G, *x.shape[2:])


def ssd_step(state, x, d, A, Bm, Cm, D):
    """One token a sequence. state [B, H, P, N] float32; x [B, H, P];
    d [B, H] (0 for a row that is not live); A, D [H]; Bm, Cm [B, G, N].
    Returns (y [B, H, P] float32, the new state). Everything in float32:
    the step reads and writes the whole state once and is bound by that."""
    G = Bm.shape[1]
    f32 = jnp.float32
    d = d.astype(f32)
    h = _grouped(state, G)  # [B, G, Hg, P, N]
    xg, dg = _grouped(x.astype(f32), G), _grouped(d, G)
    a = jnp.exp(dg * _grouped(A.astype(f32)[None], G))  # [B, G, Hg]
    h = a[..., None, None] * h + (dg[..., None] * xg)[..., None] * Bm.astype(f32)[:, :, None, None, :]
    y = (h * Cm.astype(f32)[:, :, None, None, :]).sum(-1) + _grouped(D.astype(f32)[None], G)[..., None] * xg
    return y.reshape(x.shape), h.reshape(state.shape)


def ssd_chunked(state, x, d, A, Bm, Cm, D, chunk: int):
    """S tokens a sequence, `chunk` at a time: within a chunk the outputs
    are a masked product of decays (matrix products of [chunk, chunk]),
    between chunks the recurrence on the state. state [B, H, P, N]
    float32, what the rows before this call left (zeros at a sequence's
    start); x [B, S, H, P]; d [B, S, H] (0 past a row's real tokens);
    A, D [H]; Bm, Cm [B, S, G, N]. Returns (y [B, S, H, P] float32, the
    state after the last real token). A call that is no whole number of
    chunks is padded with rows of d = 0. The two products that touch the
    state take float32 inputs at the highest precision (3% of a layer's
    FLOPs at the published widths); the products inside a chunk take the
    activations' dtype."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    f32 = jnp.float32
    pad = -S % chunk
    if pad:
        x, d, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (x, d, Bm, Cm))
    nc = (S + pad) // chunk
    Ag = A.astype(f32).reshape(G, H // G)

    def by_chunk(a):  # [B, nc*Q, ...] -> [nc, B, Q, ...]
        return jnp.swapaxes(a.reshape(Bsz, nc, chunk, *a.shape[2:]), 0, 1)

    # The scan's operands and result keep whole lanes in their last axis
    # (heads x head_dim): a step slices and writes them a chunk at a time.
    xs = (by_chunk(x.reshape(Bsz, nc * chunk, H * P)), by_chunk(d.astype(f32)), by_chunk(Bm), by_chunk(Cm))
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(h, c):
        xc, dc, Bc, Cc = c  # [B, Q, H*P], [B, Q, H], [B, Q, G, N] x 2
        xc, dc = xc.reshape(Bsz, chunk, G, H // G, P), dc.reshape(Bsz, chunk, G, H // G)
        cum = jnp.cumsum(dc * Ag, axis=1)  # log of the decay from the chunk's start to each row, itself included
        # Row i reads row j <= i through exp(cum_i - cum_j) C_i.B_j d_j.
        scores = jnp.einsum("bign,bjgn->bgij", Cc, Bc, preferred_element_type=f32)
        seg = cum[:, :, None] - cum[:, None, :]  # [B, i, j, G, Hg]
        decay = jnp.exp(jnp.where(causal[None, :, :, None, None], seg, -jnp.inf))
        mix = scores.transpose(0, 2, 3, 1)[..., None] * decay * dc[:, None]  # [B, i, j, G, Hg]
        y = jnp.einsum("bijgh,bjghp->bighp", mix.astype(xc.dtype), xc, preferred_element_type=f32)
        # ... and what the rows before the chunk left, through exp(cum_i) C_i.
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bign,bghpn->bighp", Cc.astype(f32), h, precision=HIGHEST, preferred_element_type=f32,
        )
        to_end = jnp.exp(cum[:, -1:] - cum) * dc  # [B, Q, G, Hg]
        added = jnp.einsum(
            "bjghp,bjgn->bghpn", to_end[..., None] * xc.astype(f32), Bc.astype(f32),
            precision=HIGHEST, preferred_element_type=f32,
        )
        h = jnp.exp(cum[:, -1])[..., None, None] * h + added
        return h, y.reshape(Bsz, chunk, H * P)

    h, ys = jax.lax.scan(one, _grouped(state, G), xs)
    y = jnp.swapaxes(ys, 0, 1).reshape(Bsz, nc * chunk, H, P)[:, :S]
    y = y + D.astype(f32)[None, None, :, None] * x[:, :S].astype(f32)
    return y, h.reshape(state.shape)
