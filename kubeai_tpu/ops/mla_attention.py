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

# What the decode kernel was given, per call shape this process has
# traced (diagnosis: /debug/engine -> perf.mla_kernel_blocks): the pages
# a block, the buffers of its ring, and that the copies run from slot to
# slot through the whole call.
chosen_blocks: dict[str, dict] = {}


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
    shapes: 1024 tokens, cut to the table's width. Read in this kernel's
    own sweep (PR 37, PERF.md section 6: the kernel alone at 64 and 128
    slots of 300 to 2,500 live tokens, 2 to 32 pages a block): a turn
    has a fixed cost, so 2 and 4 pages run 1.3-2 times longer than 8;
    16 pages are 7-8% under 8 from 1,230 tokens a slot up and 15% over
    at 300; 32 gain nothing. A block is `KERNEL_BUFFERS` buffers of
    pages x page x W in VMEM (16 x 64 x 640 bf16: 1.3 MB each)."""
    return max(1, min(1024 // page, max_pages))


# Buffers in the decode kernel's ring: each turn starts the copy two
# blocks ahead of the one it scores. With two, the copy of the next
# slot's first block starts only when this slot's last (short) block is
# reached, and the copy engine idles while a whole block is scored
# (PR 37's sweep: 156 -> 136 us a call at 64 slots x 1,230 tokens; a
# fourth buffer adds nothing).
KERNEL_BUFFERS = 3


def _quarter(ppb: int) -> int:
    """Pages in a quarter of a block: what its copies are issued by and
    its last block is sized by."""
    return -(-ppb // 4)


def _last_block_sizes(ppb: int) -> list[int]:
    """The page counts a slot's last block can be scored at: quarters of
    a block, so that the keys scored past a slot's end are under a
    quarter of a block and not under a whole one (a slot of 300 tokens
    in a block of 1024)."""
    quarter = _quarter(ppb)
    return [min(n, ppb) for n in range(quarter, ppb + quarter, quarter)]


def _decode_kernel(lens_ref, table_ref, q_ref, pool_ref, o_ref, buf, sem, ring_ref, *, page, ppb, max_pages, scale, rank):
    """One slot a program, in the slots' order: its query rows [H, W]
    against its latent pages, copied HBM -> VMEM a block of `ppb` pages
    at a time, only pages that hold a live token; online softmax in
    float32.

    The copies are ONE pipeline over the whole call: its (slot, block)
    pairs in order go round a ring of buffers, and every turn, before it
    waits for its own block, starts the copy of the block a ring less
    one ahead of it, which after a slot's last blocks is a block of the
    NEXT slots (the buffers, the semaphores and `ring_ref`, where the
    ring stands, outlive a program). Only the call's very first block is
    copied with nothing to compute meanwhile.

    Only a slot's last block can hold a dead key (a row past the length,
    a page not copied): the blocks before it are scored whole and
    unmasked, and the last one over its live pages rounded up to a
    quarter of a block (`_last_block_sizes`), masked."""
    b = pl.program_id(0)
    B = pl.num_programs(0)
    ring = buf.shape[0]
    q = q_ref[0]  # [H, W]
    H = q.shape[0]
    length = lens_ref[b]
    # ring_ref: the buffer of this program's block 0; the next block to
    # be copied (slot, block) and its buffer.
    MINE, NEXT_SLOT, NEXT_BLOCK, NEXT_BUFFER = range(4)

    def blocks(b):
        n_pages = (lens_ref[b] + page - 1) // page
        return n_pages, jnp.maximum((n_pages + ppb - 1) // ppb, 1)

    def each_live_page(b, blk, side, do):
        """`do` on the copy of every live page of a block, a quarter of
        the block a turn: as few turns as the live pages need, and a
        quarter's copies in line (PR 37's sweep: a predicate for each of
        a block's 16 pages costs 10% at 300 tokens a slot and a turn a
        page 2% at 1,230; all 16 in line, at six sites, add 1.6 s to
        lowering the decode step, which every start pays)."""
        first = blk * ppb
        live = jnp.clip(blocks(b)[0] - first, 0, ppb)
        quarter = _quarter(ppb)

        def turn(g, _):
            for i in range(quarter):
                @pl.when(g * quarter + i < live)
                def _():
                    n = g * quarter + i
                    do(pltpu.make_async_copy(pool_ref.at[table_ref[b * max_pages + first + n]], buf.at[side, n], sem.at[side]))

        jax.lax.fori_loop(0, (live + quarter - 1) // quarter, turn, None)

    def start_next():
        nb, nblk, side = ring_ref[NEXT_SLOT], ring_ref[NEXT_BLOCK], ring_ref[NEXT_BUFFER]

        @pl.when(nb < B)
        def _():
            each_live_page(nb, nblk, side, lambda copy: copy.start())
            last = nblk + 1 == blocks(nb)[1]
            ring_ref[NEXT_SLOT] = jnp.where(last, nb + 1, nb)
            ring_ref[NEXT_BLOCK] = jnp.where(last, 0, nblk + 1)
            ring_ref[NEXT_BUFFER] = (side + 1) % ring

    def wait(blk, side):
        each_live_page(b, blk, side, lambda copy: copy.wait())

    def score(carry, blk, side, *, pages, masked):
        """The online softmax over the first `pages` pages of a buffer."""
        m, l, acc = carry
        k = buf[side, :pages].reshape(pages * page, -1)  # [T, W]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        v = k[:, :rank]
        if masked:
            first = blk * ppb * page
            live = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < length
            s = jnp.where(live, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            # A page not copied holds whatever the buffer held: its
            # probabilities are exact zeros, and its rows are zeroed
            # too, so that a stale inf or nan cannot reach the sum.
            p = jnp.where(live, p, 0.0)
            live_rows = first + jax.lax.broadcasted_iota(jnp.int32, (pages * page, 1), 0) < length
            v = jnp.where(live_rows, v, jnp.zeros((), k.dtype))
        alpha = jnp.exp(m - m_new)
        acc = acc * alpha + jnp.dot(p.astype(k.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l * alpha + p.sum(axis=-1, keepdims=True), acc

    @pl.when(b == 0)
    def _():
        for i in range(ring_ref.shape[0]):
            ring_ref[i] = 0
        jax.lax.fori_loop(0, ring - 1, lambda i, _: start_next(), None)

    mine = ring_ref[MINE]
    n_pages, n_blocks = blocks(b)

    def whole_block(blk, carry):
        side = (mine + blk) % ring
        start_next()
        wait(blk, side)
        return score(carry, blk, side, pages=ppb, masked=False)

    carry = jax.lax.fori_loop(
        0, n_blocks - 1, whole_block,
        (jnp.full((H, 1), _NEG_INF, jnp.float32), jnp.zeros((H, 1), jnp.float32), jnp.zeros((H, rank), jnp.float32)),
    )
    blk = n_blocks - 1
    side = (mine + blk) % ring
    start_next()
    wait(blk, side)
    sizes = _last_block_sizes(ppb)  # steps of sizes[0] pages
    live_pages = n_pages - blk * ppb
    _, l, acc = jax.lax.switch(
        jnp.clip((live_pages + sizes[0] - 1) // sizes[0] - 1, 0, len(sizes) - 1),
        [functools.partial(score, blk=blk, side=side, pages=n, masked=True) for n in sizes],
        carry,
    )
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    ring_ref[MINE] = (mine + n_blocks) % ring


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
                pltpu.VMEM((KERNEL_BUFFERS, ppb, page, W), pool.dtype),
                pltpu.SemaphoreType.DMA((KERNEL_BUFFERS,)),
                pltpu.SMEM((4,), jnp.int32),
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
        chosen_blocks[f"B={B} H={H} W={W} pages={max_pages}x{page} {pool.dtype.name}"] = {
            "pages_per_block": kernel_pages_per_block(max_pages, page), "buffers": KERNEL_BUFFERS,
            "copies": "one pipeline over the call's slots, one slot a program",
        }
        return mla_paged_decode_kernel(q_lat, pool, page_table, lens, scale=scale, rank=rank)
    return latent_attention_paged(q_lat[:, None], pool, page_table, lens[:, None] - 1, scale=scale, rank=rank)[:, 0]
