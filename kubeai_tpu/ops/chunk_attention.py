"""Causal attention of a prefill chunk over the paged pool: S > 1 query
rows a slot against the slot's pages, read in place through the block
table. A Pallas kernel of the repo's own in which a query tile visits
only the KV blocks that intersect its own mask: from the page holding
`max(0, pos(first row) - window + 1)` (key 0 for a layer without a
window) to the block holding `pos(last row)`, and nothing after it. The
library's ragged kernel, which `ops/paged_attention.py` keeps for one
query row a slot, walks every query block from the table's first key to
the sequence's end and only masks.

Same inputs, outputs and arithmetic as that kernel (bf16 operands,
float32 scores, running max / sum and accumulator): the result is the
library's to rounding order (`tests/test_chunk_attention.py` runs this
kernel in interpret mode against the twin of the library's reference).

`tile_walk` is the one statement of what a query tile walks: the
kernel's loops run on it, and `pairs_walked` sums it on the host for the
engine's `kubeai_engine_attn_pairs_walked_total`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
_LOG2E = 1.4426950408889634

# Keys a KV block (whole pages are taken), and the rows of a score tile
# (query rows x the G heads that share a KV head) that a query tile is
# cut for, between 256 and 512 query rows. Swept on a v5e at page 64
# (PERF.md section 6, PR 49: 2048 rows at G = 4, 7, 8, 16 behind 0 to
# 22528 keys): a block turn costs little once the running max and sum
# stay lane-wide, so 256 keys read 3-14% under 512 at G = 7, 8 and 16;
# 512 query rows read 1-10% under 256 at G = 7 and 8 and 14-16% at G = 4
# (a KV block is copied and split once a tile), and walk a tile's width
# more of the masked triangle: G = 4's chunks have no window and take
# them, the window families keep 256 (0.94 of the walked pairs inside
# the mask behind a 4096 window, 0.89 at 512).
KV_BLOCK_TOKENS = 256
SCORE_TILE_ROWS = 2048

# What the kernel was given, per call shape this process has traced
# (diagnosis: /debug/engine -> perf.chunk_kernel_tiles).
chosen_tiles: dict[str, dict] = {}


def kernel_tiles(S: int, G: int, page: int, max_pages: int) -> tuple[int, int]:
    """(query rows a tile, keys a KV block) from the call's own shapes:
    a tile is the call's rows where it has no more, a block whole pages
    and never wider than the table."""
    tile = min(512, max(256, 1 << ((SCORE_TILE_ROWS // G).bit_length() - 1)))
    return min(S, tile), max(1, min(KV_BLOCK_TOKENS // page, max_pages)) * page


def reads(q, kv_pages, page_table, k_scale, v_scale) -> bool:
    """Whether the kernel reads this call's pool: bf16 pages (a K row
    and its V row in one 32-bit word) of heads a whole number of lane
    tiles wide, nothing to dequantize, and a table wide enough for a KV
    block of whole lane tiles."""
    B, S, H, h = q.shape
    kv_block = kernel_tiles(S, H // (kv_pages.shape[2] // 2), kv_pages.shape[1], page_table.shape[1])[1]
    return (
        k_scale is None and v_scale is None and q.dtype == kv_pages.dtype == jnp.bfloat16
        and h % 128 == 0 and kv_block % 128 == 0
    )


def tile_walk(p0, tile: int, window: int | None, kv_block: int, page: int, maximum=max):
    """(first key, KV blocks) that the query tile whose first row sits at
    position *p0* walks: from the page of the first key its first row can
    see, in blocks of *kv_block* keys, to the block holding its last
    row's position. Plain integers on the host; inside the kernel the
    same lines on traced scalars (`maximum=jnp.maximum`)."""
    first = maximum(p0 - window + 1, 0) // page * page if window else 0
    return first, (p0 + tile - first + kv_block - 1) // kv_block


def pairs_walked(rows: int, first_pos: int, window: int | None, tile: int, kv_block: int, page: int) -> int:
    """(query, key) pairs a head of the kernel SCORES for *rows*
    contiguous queries of one slot starting at position *first_pos*."""
    return sum(
        tile * kv_block * tile_walk(first_pos + t, tile, window, kv_block, page)[1] for t in range(0, rows, tile)
    )


def _split_kv(words):
    """K and V [keys, h] of one KV head from the pool's 32-bit words:
    the pool interleaves K and V on the head axis, so a bf16 K row and
    its V row share a sublane's word (K the low half). The library
    kernel's recipe (`strided_load_kv`)."""
    k = pltpu.bitcast(words << 16, jnp.float32).astype(jnp.bfloat16)
    v = pltpu.bitcast(words & jnp.uint32(0xFFFF0000), jnp.float32).astype(jnp.bfloat16)
    return k, v


def _chunk_kernel(
    lens_ref, table_ref, q_ref, pool_ref, o_ref, buf, sem, side_ref, ahead_ref, q_heads, k_heads, v_heads, m_ref, l_ref, acc_ref,
    *, S, tile, ppb, page, max_pages, H, Kv, h, scale, softcap, window,
):
    """One query tile of one slot a program, in the slots' order: its
    rows [tile, H*h] against the KV blocks `tile_walk` names, each copied
    HBM -> VMEM once for all H heads (every KV head of `ppb` pages), the
    copy of the next block (the next tile's first block after this
    tile's last) running while this one is scored: `side_ref`, the
    buffers and the semaphores outlive a program. Online softmax in
    float32, a KV head at a time: the G query heads that share it are
    one [G * tile, h] operand, head-major, so its keys and values go
    through the matrix unit once for all of them. A block that every row
    of the tile sees whole is scored without building a mask."""
    b, t = pl.program_id(0), pl.program_id(1)
    n_slots, n_tiles = pl.num_programs(0), pl.num_programs(1)
    G, bk = H // Kv, ppb * page

    def walk(b, t):
        p0 = lens_ref[b] - S + t * tile
        return (p0, *tile_walk(p0, tile, window, bk, page, jnp.maximum))

    def copies(b, first, j, side):
        col = first // page + j * ppb
        return [
            pltpu.make_async_copy(
                pool_ref.at[table_ref[b * max_pages + jnp.minimum(col + i, max_pages - 1)]], buf.at[side, i], sem.at[side],
            )
            for i in range(ppb)
        ]

    p0, first, n_blocks = walk(b, t)
    kv_len = lens_ref[b]

    @pl.when(jnp.logical_and(b == 0, t == 0))
    def _():
        side_ref[0] = 0
        for copy in copies(b, first, 0, 0):
            copy.start()
        # Key minus row, as offsets inside a (tile, block) pair, for
        # every head of a group: the same for every program.
        row = jax.lax.rem(jax.lax.broadcasted_iota(jnp.int32, (G * tile, bk), 0), tile)
        ahead_ref[...] = jax.lax.broadcasted_iota(jnp.int32, (G * tile, bk), 1) - row

    # Head-major: the heads of a KV head are then one operand.
    for hd in range(H):
        q_heads[hd // G, hd % G * tile:(hd % G + 1) * tile] = q_ref[0, :, hd * h:(hd + 1) * h]
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    side0 = side_ref[0]

    def kv_heads(k0, *, masked):
        # The scale rides in the exponent: the running max and every
        # difference are in the scores' own unit, and exp2 takes the one
        # multiply exp would have made anyway.
        in_exp = _LOG2E if softcap else scale * _LOG2E

        def kv_head(kv, _):
            q, k, v = q_heads[kv], k_heads[kv], v_heads[kv]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            if softcap:
                s = softcap * jnp.tanh(s * (scale / softcap))
            if masked:
                seen = ahead_ref[...] <= p0 - k0  # key position <= row position
                if window:
                    seen = jnp.logical_and(seen, ahead_ref[...] > p0 - k0 - window)
                s = jnp.where(seen, s, _NEG_INF)
            # m and l are kept the same in all 128 lanes of a row: a
            # column of one lane costs a relayout at every use.
            m_prev = m_ref[kv]
            m_next = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp2((s - pltpu.repeat(m_next, bk // 128, axis=1)) * in_exp)
            alpha = jnp.exp2((m_prev - m_next) * in_exp)
            m_ref[kv] = m_next
            l_ref[kv] = alpha * l_ref[kv] + p.sum(axis=-1, keepdims=True)
            acc_ref[kv] = acc_ref[kv] * pltpu.repeat(alpha, h // 128, axis=1) + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32
            )

        jax.lax.fori_loop(0, Kv, kv_head, None)

    def turn(j, _):
        side = (side0 + j) % 2

        @pl.when(j + 1 < n_blocks)
        def _():
            for copy in copies(b, first, j + 1, 1 - side):
                copy.start()

        @pl.when(j + 1 == n_blocks)
        def _():
            last_tile = t + 1 == n_tiles
            b2, t2 = jnp.where(last_tile, b + 1, b), jnp.where(last_tile, 0, t + 1)

            @pl.when(b2 < n_slots)
            def _():
                for copy in copies(b2, walk(b2, t2)[1], 0, 1 - side):
                    copy.start()

        for copy in copies(b, first, j, side):
            copy.wait()
        k0 = first + j * bk
        # [keys * Kv, h] words, a K row and its V row in each: a key past
        # the slot's length may hold anything (a page never written), and
        # a probability of zero does not silence a nan.
        words = buf.at[side].reshape(bk * 2 * Kv, h).bitcast(jnp.uint32)
        live = jax.lax.broadcasted_iota(jnp.int32, (bk, h), 0) < kv_len - k0
        for kv in range(Kv):
            k_heads[kv], v_heads[kv] = _split_kv(jnp.where(live, words[kv::Kv, :], jnp.uint32(0)))
        # Every row sees the whole block: no key after the first row's
        # position, none before the last row's window.
        whole = k0 + bk - 1 <= p0
        if window:
            whole = jnp.logical_and(whole, k0 > p0 + tile - 1 - window)
        jax.lax.cond(whole, functools.partial(kv_heads, masked=False), functools.partial(kv_heads, masked=True), k0)

    jax.lax.fori_loop(0, n_blocks, turn, None)
    side_ref[0] = (side0 + n_blocks) % 2
    for hd in range(H):
        rows = slice(hd % G * tile, (hd % G + 1) * tile)
        o_ref[0, :, hd * h:(hd + 1) * h] = (acc_ref[hd // G, rows] / pltpu.repeat(l_ref[hd // G, rows], h // 128, axis=1)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "sliding_window", "tiles", "interpret"))
def chunk_attention_kernel(q, kv_pages, page_table, kv_lengths, *, scale, softcap=0.0, sliding_window=None, tiles=None, interpret=False):
    """The Pallas kernel behind `ops/paged_attention.py`'s calls of more
    than one query row a slot (same arguments; *kv_lengths* already held
    to `[S, max_pages * page]`; *tiles*: a sweep's (query rows, keys),
    serving leaves it None). A bf16 pool of 128-wide heads only
    (`reads`): the caller sends every other pool to the library kernel."""
    B, S, H, h = q.shape
    page, Kv, max_pages = kv_pages.shape[1], kv_pages.shape[2] // 2, page_table.shape[1]
    G = H // Kv
    tile, bk = tiles or kernel_tiles(S, G, page, max_pages)
    if S % tile or bk % page:
        raise ValueError(f"a call of {S} rows over pages of {page} cannot be cut into tiles of {tile} rows and blocks of {bk} keys")
    ppb = bk // page
    kernel = functools.partial(
        _chunk_kernel, S=S, tile=tile, ppb=ppb, page=page, max_pages=max_pages, H=H, Kv=Kv, h=h,
        scale=scale, softcap=softcap, window=sliding_window,
    )
    rows = lambda b, t, lens, table: (b, t, 0)  # noqa: E731
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, S // tile),
            in_specs=[pl.BlockSpec((1, tile, H * h), rows), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, tile, H * h), rows),
            scratch_shapes=[
                pltpu.VMEM((2, ppb, page, 2 * Kv, h), kv_pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((G * tile, bk), jnp.int32),
                pltpu.VMEM((Kv, G * tile, h), q.dtype),
                pltpu.VMEM((Kv, bk, h), kv_pages.dtype),
                pltpu.VMEM((Kv, bk, h), kv_pages.dtype),
                pltpu.VMEM((Kv, G * tile, 128), jnp.float32),
                pltpu.VMEM((Kv, G * tile, 128), jnp.float32),
                pltpu.VMEM((Kv, G * tile, h), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, S, H * h), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=96 * 1024 * 1024,
        ),
        interpret=interpret,
        name="chunk_attention_kernel",
    )(kv_lengths.astype(jnp.int32), page_table.reshape(-1).astype(jnp.int32), q.reshape(B, S, H * h), kv_pages)
    return out.reshape(B, S, H, h)
