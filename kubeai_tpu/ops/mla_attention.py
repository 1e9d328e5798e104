"""Attention over latent (MLA) keys and values, in the absorbed form: H
query heads of width W against ONE latent vector a token, which is key
(all W columns: the normed latent, the rotated rope key, zero padding)
and value (its first `rank` columns) at once.

`latent_attention_paged` is the portable XLA form over latent pages, the
one form of every prefill. `mla_paged_decode` reads latent pages in place
for one query row a slot: on the chip a Pallas kernel of the repo's own,
elsewhere the portable form (the same mathematics;
`tests/test_mla_attention.py` runs the kernel in interpret mode against
it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

# (pages a block) the decode kernel was given, per call shape this process
# has traced (diagnosis: /debug/engine -> perf.mla_kernel_blocks).
chosen_blocks: dict[str, int] = {}


# Keys a turn of the portable form's loop (whole pages are taken).
XLA_BLOCK_TOKENS = 256


def latent_attention_paged(q_lat, pool, page_table, positions, *, scale: float, rank: int):
    """The portable XLA form, over latent pages: q_lat [B, S, H, W] at
    absolute *positions* [B, S]; pool [P, page, W]; page_table [B,
    max_pages] (rows of the pool). A query attends the keys at or before
    its position. Returns the attended latent [B, S, H, rank] in q's
    dtype (the caller applies W_uv).

    EVERY prefill runs this one form, cold or behind cached tokens: the
    keys are walked from key 0 in blocks of `XLA_BLOCK_TOKENS` with an
    online softmax in float32, a block past a row's position leaves its
    (max, sum, accumulator) as they were, and blocks past the call's
    last position are not visited."""
    B, S, H, W = q_lat.shape
    dtype = q_lat.dtype
    page, max_pages = pool.shape[1], page_table.shape[1]
    ppb = max(1, min(XLA_BLOCK_TOKENS // page, max_pages))
    bk = ppb * page
    pad = -max_pages % ppb
    if pad:  # row 0 is never a live page of the table's own span; its keys are masked by position
        page_table = jnp.pad(page_table, ((0, 0), (0, pad)))
    positions = jnp.minimum(positions, max_pages * page - 1)
    if jax.default_backend() == "cpu":
        # The CPU backend has no bf16 x bf16 -> f32 dot: upcast there.
        q_lat = q_lat.astype(jnp.float32)
    n_blocks = positions.max() // bk + 1

    def body(blk, carry):
        m, l, acc = carry
        rows = jax.lax.dynamic_slice_in_dim(page_table, blk * ppb, ppb, axis=1)
        k = pool[rows].reshape(B, bk, W).astype(q_lat.dtype)
        s = jnp.einsum("bshw,bkw->bhsk", q_lat, k, preferred_element_type=jnp.float32) * scale
        live = (blk * bk + jnp.arange(bk))[None, None, None, :] <= positions[:, None, :, None]
        s = jnp.where(live, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(live, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        pv = jnp.einsum("bhsk,bkr->bhsr", p.astype(k.dtype), k[..., :rank], preferred_element_type=jnp.float32)
        return m_new, l * alpha + p.sum(axis=-1), acc * alpha[..., None] + pv

    m, l, acc = jax.lax.fori_loop(
        0, n_blocks, body,
        (
            jnp.full((B, H, S), _NEG_INF, jnp.float32), jnp.zeros((B, H, S), jnp.float32),
            jnp.zeros((B, H, S, rank), jnp.float32),
        ),
    )
    return (acc / jnp.maximum(l, 1e-30)[..., None]).transpose(0, 2, 1, 3).astype(dtype)


def kernel_pages_per_block(max_pages: int, page: int) -> int:
    """Latent pages a block of the decode kernel, from the call's own
    shapes: 512 tokens (PR 30's sweep of the ragged kernel at one query
    row a slot: a block of 512 is not copied far past a short sequence's
    end and a turn's fixed cost is spread over enough keys), cut to the
    table's width. A block is two buffers of pages x page x W in VMEM
    (8 x 64 x 640 bf16: 0.66 MB each)."""
    return max(1, min(512 // page, max_pages))


def _decode_kernel(lens_ref, table_ref, q_ref, pool_ref, o_ref, buf, sem, *, page, ppb, max_pages, scale, rank):
    """One slot a program: its query rows [H, W] against its latent pages,
    copied HBM -> VMEM a block of `ppb` pages at a time into two buffers
    (the next block's copy runs under this block's scores), only pages
    that hold a live token; online softmax in float32."""
    b = pl.program_id(0)
    length = lens_ref[b]
    n_pages = (length + page - 1) // page
    n_blocks = (n_pages + ppb - 1) // ppb
    q = q_ref[0]  # [H, W]
    H = q.shape[0]

    def copies(slot, blk):
        return [
            (
                blk * ppb + i,
                pltpu.make_async_copy(
                    pool_ref.at[table_ref[b * max_pages + jnp.minimum(blk * ppb + i, max_pages - 1)]],
                    buf.at[slot, i], sem.at[slot],
                ),
            )
            for i in range(ppb)
        ]

    def start(slot, blk):
        for p, c in copies(slot, blk):
            @pl.when(p < n_pages)
            def _():
                c.start()

    def wait(slot, blk):
        for p, c in copies(slot, blk):
            @pl.when(p < n_pages)
            def _():
                c.wait()

    start(0, 0)

    def body(blk, carry):
        m, l, acc = carry
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            start(1 - slot, blk + 1)

        wait(slot, blk)
        k = buf[slot].reshape(ppb * page, -1)  # [T, W]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        pos = blk * ppb * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        live = pos < length
        s = jnp.where(live, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        # A page not copied holds whatever the buffer held: its
        # probabilities are exact zeros, and its rows are zeroed too, so
        # that a stale inf or nan cannot reach the sum.
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        live_rows = (blk * ppb * page + jax.lax.broadcasted_iota(jnp.int32, (ppb * page, 1), 0)) < length
        v = jnp.where(live_rows, k[:, :rank], jnp.zeros((), k.dtype))
        acc = acc * alpha + jnp.dot(p.astype(k.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l * alpha + p.sum(axis=-1, keepdims=True), acc

    m, l, acc = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.full((H, 1), _NEG_INF, jnp.float32), jnp.zeros((H, 1), jnp.float32), jnp.zeros((H, rank), jnp.float32)),
    )
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "rank", "pages_per_block", "interpret"))
def mla_paged_decode_kernel(q_lat, pool, page_table, kv_lengths, *, scale, rank, pages_per_block=None, interpret=False):
    """The Pallas kernel behind `mla_paged_decode` (same arguments)."""
    B, H, W = q_lat.shape
    page, max_pages = pool.shape[1], page_table.shape[1]
    ppb = pages_per_block or kernel_pages_per_block(max_pages, page)
    kernel = functools.partial(
        _decode_kernel, page=page, ppb=ppb, max_pages=max_pages, scale=scale, rank=rank,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, W), lambda b, lens, table: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, rank), lambda b, lens, table: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, ppb, page, W), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_paged_decode_kernel",
    )(kv_lengths.astype(jnp.int32), page_table.reshape(-1).astype(jnp.int32), q_lat, pool)


def mla_paged_decode(q_lat, pool, page_table, kv_lengths, *, scale: float, rank: int):
    """One query row a slot against its latent pages: q_lat [B, H, W];
    pool [P, page, W]; page_table [B, max_pages] (rows of the pool);
    kv_lengths [B] valid keys INCLUDING the new token. Returns [B, H,
    rank]."""
    B, H, W = q_lat.shape
    page, max_pages = pool.shape[1], page_table.shape[1]
    # A finished slot's positions may run past the table's span (its
    # writes went to the trash page): never walk past the table.
    lens = jnp.clip(kv_lengths, 1, max_pages * page).astype(jnp.int32)
    if jax.default_backend() == "tpu":
        chosen_blocks[f"B={B} H={H} W={W} pages={max_pages}x{page} {pool.dtype.name}"] = kernel_pages_per_block(max_pages, page)
        return mla_paged_decode_kernel(q_lat, pool, page_table, lens, scale=scale, rank=rank)
    return latent_attention_paged(q_lat[:, None], pool, page_table, lens[:, None] - 1, scale=scale, rank=rank)[:, 0]
