"""Mamba-2's two sequence operations: the causal depthwise convolution
with a carried tail, and the selective state-space recurrence in its
chunked form (prefill: many rows of one sequence a call) and its one-step
form (decode: one row a sequence). Head `h` of `H` reads group
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

**Where each runs.** The convolution and the chunked form are plain XLA
everywhere. The one-step form on the slots' stacked state
(`ssd_step_stacked`) is, on the chip, a Pallas kernel that passes over a
block's state ONCE: a slot's state comes into VMEM, is rewritten where it
lay, and `y = S C` is formed from the tile while it is there. Elsewhere it
is the portable `ssd_step`, which XLA compiles to an in-place update and
a second fusion that reads the new state again for `y`;
`tests/test_ssm_ops.py` runs the kernel in interpret mode against it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST

# What the step kernel was given, per call shape this process has traced
# (diagnosis: /debug/engine -> perf.ssm_kernel_blocks): the heads of a slot
# a program holds and the bytes of that tile.
chosen_blocks: dict[str, dict] = {}


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
    Returns (y [B, H, P] float32, the new state). Everything in float32.
    The portable form: XLA makes it two passes over the state, the update
    and then the reduction over N that reads the new state again."""
    G = Bm.shape[1]
    f32 = jnp.float32
    d = d.astype(f32)
    h = _grouped(state, G)  # [B, G, Hg, P, N]
    xg, dg = _grouped(x.astype(f32), G), _grouped(d, G)
    a = jnp.exp(dg * _grouped(A.astype(f32)[None], G))  # [B, G, Hg]
    h = a[..., None, None] * h + (dg[..., None] * xg)[..., None] * Bm.astype(f32)[:, :, None, None, :]
    y = (h * Cm.astype(f32)[:, :, None, None, :]).sum(-1) + _grouped(D.astype(f32)[None], G)[..., None] * xg
    return y.reshape(x.shape), h.reshape(state.shape)


# A tile of the step kernel holds at most this much state: one slot's at
# the published widths (128 heads x 64 x 128 float32).
KERNEL_TILE_BYTES = 4 << 20


def kernel_heads_per_tile(H: int, P: int, N: int, G: int) -> int:
    """Heads of one slot that a program of the step kernel holds, from the
    state's own shape: whole groups (a group shares its B and C rows); the
    whole slot or a multiple of 128 heads (a tile's heads are the lane axis
    of its `d x` and `y` operands); the largest such tile within
    `KERNEL_TILE_BYTES`, else the smallest there is. Read in the kernel's
    own sweep (PR 41, PERF.md section 6: one block of 96 slots x 128 x 64 x
    128 alone on the chip): 128 heads a program 1.29 ms, 64 1.31, 32 1.33,
    16 1.47, against 1.28 for a kernel that only rewrites the state: a
    program's copies in and out are the whole cost, and fewer, larger ones
    cost least. Two buffers each way: 4 x the tile in VMEM."""
    Hg = H // G
    fits = [hb for hb in range(Hg, H + 1, Hg) if H % hb == 0 and (hb == H or hb % 128 == 0)]
    small = [hb for hb in fits if hb * P * N * 4 <= KERNEL_TILE_BYTES]
    return max(small) if small else min(fits)


def _step_kernel(j_ref, a_ref, dx_ref, b_ref, c_ref, s_ref, y_ref, so_ref, *, group_heads):
    """One tile: `hb` heads of one slot of block j. a_ref [1, 1, 1, hb]
    (SMEM) the heads' decays; dx_ref [1, 1, P, hb] `d x`, a head a lane;
    b_ref, c_ref [1, 1, groups, N]; s_ref / so_ref [1, 1, hb, P, N] the
    SAME rows of the stacked state, in and out; y_ref [1, 1, P, hb]."""
    del j_ref  # the index maps read it
    hb, P, N = s_ref.shape[2:]
    dx = dx_ref[0, 0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (P, hb), 1)
    # The sum over N is a product with ones on the MXU (float32 in six
    # bf16 passes, exact for ones): across lanes on the VPU it cost 8-18%
    # of the kernel (PR 41's sweep); the MXU's hides behind the copies.
    ones = jnp.ones((N, hb), jnp.float32)
    y = jnp.zeros((P, hb), jnp.float32)
    for h in range(hb):
        g = h // group_heads
        new = a_ref[0, 0, 0, h] * s_ref[0, 0, h] + dx[:, h : h + 1] * b_ref[0, 0, g : g + 1, :]
        so_ref[0, 0, h] = new
        summed = jnp.dot(new * c_ref[0, 0, g : g + 1, :], ones, precision=HIGHEST, preferred_element_type=jnp.float32)
        y = jnp.where(lane == h, summed, y)  # every lane of `summed` holds the head's [P] sums
    y_ref[0, 0] = y


@functools.partial(jax.jit, static_argnames=("heads_per_tile", "interpret"))
def ssd_step_kernel(states, j, x, d, A, Bm, Cm, D, *, heads_per_tile=None, interpret=False):
    """The Pallas kernel behind `ssd_step_stacked` (same arguments; j may
    be traced). The stacked state is the kernel's input AND output
    (`input_output_aliases`), addressed by index maps a tile at a time:
    block j's rows are read once and written once where they lie, no
    other block's rows are touched, and nothing is sliced out."""
    _, B, H, P, N = states.shape
    G = Bm.shape[1]
    f32 = jnp.float32
    hb = heads_per_tile or kernel_heads_per_tile(H, P, N, G)
    T, groups = H // hb, hb // (H // G)
    d, x32 = d.astype(f32), x.astype(f32)
    by_tile = lambda v: v.reshape(B, T, hb, *v.shape[2:])  # noqa: E731
    a = by_tile(jnp.exp(d * A.astype(f32)[None]))[:, :, None, :]  # [B, T, 1, hb]
    dx = jnp.swapaxes(by_tile(d[..., None] * x32), 2, 3)  # [B, T, P, hb]
    small = lambda *shape, **space: pl.BlockSpec((1, 1, *shape), lambda b, t, j: (b, t, 0, 0), **space)  # noqa: E731
    tile = pl.BlockSpec((1, 1, hb, P, N), lambda b, t, j: (j[0], b, t, 0, 0))
    y, states = pl.pallas_call(
        functools.partial(_step_kernel, group_heads=H // G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, T),
            in_specs=[small(1, hb, memory_space=pltpu.SMEM), small(P, hb), small(groups, N), small(groups, N), tile],
            out_specs=[small(P, hb), tile],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, T, P, hb), f32), jax.ShapeDtypeStruct(states.shape, states.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=4 * hb * P * N * 4 + (8 << 20),
        ),
        interpret=interpret,
        name="ssd_step_kernel",
    )(
        jnp.reshape(j, (1,)).astype(jnp.int32), a, dx,
        Bm.astype(f32).reshape(B, T, groups, N), Cm.astype(f32).reshape(B, T, groups, N), states,
    )
    return jnp.swapaxes(y, 2, 3).reshape(B, H, P) + D.astype(f32)[None, :, None] * x32, states


def kernel_takes(states) -> bool:
    """Whether the step on *states* [n, B, H, P, N] is the kernel's: on
    the chip, a float32 state whose tiles are whole vregs."""
    P, N = states.shape[-2:]
    return jax.default_backend() == "tpu" and states.dtype == jnp.float32 and N % 128 == 0 and P % 8 == 0


def ssd_step_stacked(states, j: int, x, d, A, Bm, Cm, D):
    """`ssd_step` on block *j* of the slots' stacked state [n, B, H, P, N]
    (`models/nemotron_h.py`: `cache["ssm"]`), the other arguments as
    `ssd_step`'s. Returns (y [B, H, P] float32, the stacked state with
    block j's rows stepped). On the chip the kernel, in place; elsewhere
    the portable step on `states[j]`, written back."""
    if kernel_takes(states):
        _, B, H, P, N = states.shape
        G = Bm.shape[1]
        hb = kernel_heads_per_tile(H, P, N, G)
        chosen_blocks[f"B={B} H={H} P={P} N={N} G={G} {states.dtype.name}"] = {
            "heads_per_tile": hb, "tile_bytes": hb * P * N * 4,
            "pass": "one: a tile is rewritten where it lay and y = S C formed from it",
        }
        return ssd_step_kernel(states, j, x, d, A, Bm, Cm, D, heads_per_tile=hb)
    y, h = ssd_step(states[j].astype(jnp.float32), x, d, A, Bm, Cm, D)
    return y, states.at[j].set(h.astype(states.dtype))


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
