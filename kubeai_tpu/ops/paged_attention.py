"""Paged attention for TPU: in-place page reads for prefill and decode.

KV lives as [P, page, 2*Kv, h] pages with K/V interleaved on the head
axis, a block table maps each slot's positions onto pages, and queries
of ANY length per slot (1 for decode, a few for a caller that scores
several tokens a step, a whole bucket for prefill) attend causally with
pages streamed HBM->VMEM — no gathered contiguous copy of the KV span
(the portable XLA path in models/llama.py gathers; acceptable on CPU
tests, wasteful on a bandwidth-bound TPU).

Two kernels on the chip, chosen by what the call is. One query row a
slot (every decode call) runs JAX's ragged-paged-attention Pallas kernel
(the vLLM-TPU workhorse), which walks each query block from the table's
first key to the sequence's end and only masks: right for a row that
sees all of it. More than one row a slot (a prefill chunk behind cached
tokens, a cold call the flash route does not take) runs the repo's own
`ops/chunk_attention.py`, in which a query tile visits only the KV
blocks its own mask intersects; a pool it does not read (anything but
bf16 pages of 128-wide heads: a quantized pool above all, which needs
its pages dequantized in VMEM) stays on the library kernel.

At decode (one query row a slot) a caller whose live rows come first
hands in their count (`live_rows`, `models/base.py::LiveRows`): it is the
kernel's `num_seqs`, so the rows past it are neither copied nor scored,
and they come back as zeros.

On non-TPU backends this dispatches to a jit-safe twin of the library's
pure-JAX reference implementation (identical semantics, the count
included), so the engine's kernel path is CPU-testable end-to-end.

Heads narrower than a lane tile (`h` = 64: `models/lfm2_moe.py`). Neither
kernel takes them: the library's asserts at trace (its running sum is 128
lanes wide and is tiled over the head: `64 % 128`), the repo's reads whole
lane tiles. Such a family keeps its pool as `[P, page, 2 * Kv / n, n * h]`
with n = `heads_a_tile(h)` KV heads SIDE BY SIDE in a 128-lane row (`pack_kv`:
their K on the even row, their V on the odd one), the same bytes a token
as `[P, page, 2 * Kv, h]`, and hands both kernels what they take: a pool
of Kv / n heads of 128, and queries with each head's values in ITS KV
head's lanes and zeros in the others (`widen_queries`). A score is then
`q . k` of the head's own 64 values plus exact zeros, the softmax is over
the same keys, and the output's own lanes (`narrow_outputs`) are `p . v`
of the head's own values: the same arithmetic to the bit, for n times the
matrix unit's work and no byte more from HBM.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from kubeai_tpu.ops import chunk_attention


# What kernel_blocks chose, per compiled call shape: filled at trace
# time, served in the perf section of /debug/engine.
chosen_blocks: dict[str, tuple[int, int]] = {}


def kernel_blocks(S: int, G: int, pages_per_seq: int, page: int) -> tuple[int, int]:
    """(num_kv_pages_per_block, num_queries_per_block) for the library
    kernel, from the call's own shapes: S query rows a slot, G query
    heads a KV head, the table's width and the page size. The library's
    tuned table starts at 512 batched tokens, so a serving call would
    take its default (128 pages x 32 queries, clipped), and the kernel
    (a) copies every page of a KV block whatever the sequence's length
    and (b) scores the whole query block against every sequence that
    touches it.

    This is the decode rule: since PR 49 a call of more rows a slot is
    the library kernel's only over a pool `ops/chunk_attention.py` does
    not read, which no cell runs, and takes the same blocks.
    Queries: one slot's rows a block, so at decode no slot's row meets
    another slot's keys; more rows are cut where the score tile
    (queries x G rows) reaches 256 rows: more rows gained under 3% and
    the compiler's time grows faster than the tile (27 s at 896 rows).
    KV: 512 tokens a block, which a short context does not copy far
    past its end. Swept on a v5e at page 64 (PERF.md section 6, PR 30):
    at decode 8 pages x 1 query is the fastest or within 4% of it from
    32 slots x 350 tokens (59 us a call against 377) to 8 slots x 8000;
    an fp8 pool liked 4 pages 7% better."""
    queries = min(S, 1 << (max(1, 256 // G).bit_length() - 1))
    return max(1, min(512 // page, pages_per_seq)), queries


def paged_attention_ragged(
    q: jnp.ndarray,  # [B, S, H, h] queries (the slots' newest S tokens)
    kv_pages: jnp.ndarray,  # [P, page, 2*Kv, h] (K even, V odd)
    page_table: jnp.ndarray,  # [B, max_pages] int32
    kv_lengths: jnp.ndarray,  # [B] int32 — valid keys INCLUDING the S new tokens
    scale: float | None = None,
    softcap: float = 0.0,
    k_scale: float | None = None,  # static dequant scales for quantized
    v_scale: float | None = None,  # (int8/fp8) pools; None = pool is bf16
    blocks: tuple[int, int] | None = None,  # a sweep's (kv pages, queries) for the LIBRARY kernel, whatever S; serving leaves it None
    sliding_window: int | None = None,  # static: a query sees keys j > i - sliding_window only
    live_rows: jnp.ndarray | None = None,  # [] int32, S == 1 only: rows [0, live_rows) hold requests
) -> jnp.ndarray:
    """Returns [B, S, H, h] attention output. With a quantized pool the
    library kernel (at every S: the chunk kernel reads bf16 pages only)
    dequantizes pages in-VMEM (x.astype(f32) * scale -> q.dtype), so HBM
    page traffic stays 8-bit. With *live_rows* the kernel walks that
    many table rows and the rest of the output is zeros."""
    B, S, H, h = q.shape
    if live_rows is not None and S != 1:
        raise ValueError(f"live_rows is for calls of one query row a slot, not S={S}")
    max_pages = page_table.shape[1]
    page, Kv = kv_pages.shape[1], kv_pages.shape[2] // 2
    if scale is None:
        scale = h**-0.5
    if S > 1 and blocks is None and jax.default_backend() != "cpu" and chunk_attention.reads(q, kv_pages, page_table, k_scale, v_scale):
        tile, kv_block = chunk_attention.kernel_tiles(S, H // Kv, page, max_pages)
        chunk_attention.chosen_tiles[
            f"B={B} S={S} H={H} Kv={Kv} pages={max_pages}x{page}" + (f" window={sliding_window}" if sliding_window else "")
        ] = {"query_tile": tile, "kv_block": kv_block}
        # The overrun guard below, and never fewer keys than rows (a
        # row's position is its distance from the last key).
        return chunk_attention.chunk_attention_kernel(
            q, kv_pages, page_table, jnp.clip(kv_lengths, S, max_pages * page),
            scale=float(scale), softcap=float(softcap), sliding_window=sliding_window or None,
        )

    q_flat = q.reshape(B * S, H, h)
    cu_q_lens = (jnp.arange(B + 1, dtype=jnp.int32) * S)
    # Overrun guard: a finished slot's positions may run past the table
    # span (writes went to the trash page); clamp so the kernel never
    # walks past the table width.
    kv_lens = jnp.minimum(kv_lengths, max_pages * page).astype(jnp.int32)
    if live_rows is None:
        num_seqs = jnp.asarray([B], jnp.int32)
    else:
        # Never 0: the kernel starts copying row 0's first block before
        # it looks at the count, and only a walked row waits for its
        # copy. With no live row (the warm-up's dispatch) row 0 is
        # walked as every idle row was, and masked below like the rest.
        num_seqs = jnp.clip(live_rows, 1, B).astype(jnp.int32).reshape(1)

    # The library kernel's window only masks: it copies every page from
    # the table's first to the sequence's end, so a caller with a window
    # hands in the table from the first page inside it, lengths shifted
    # (models/smallthinker.py).
    tuning = {"sliding_window": sliding_window} if sliding_window else {}
    if jax.default_backend() == "cpu":
        fn = _cpu_twin
    else:
        from jax.experimental.pallas.ops.tpu.ragged_paged_attention import (
            ragged_paged_attention,
        )

        fn = ragged_paged_attention
        # The kernel's default scoped-VMEM budget (16MB) under-provisions
        # large-head configs: an 8B-class (H=32, Kv=8, h=128) prefill
        # needs ~16.4MB of kernel stack and dies in compile ("Ran out of
        # memory in memory space vmem") under a smaller limit. v5e/v5p
        # have 128MB VMEM; 96MB leaves XLA its own 16MB scope for the
        # surrounding fusion (tests/test_tpu_compile.py).
        tuning["vmem_limit_bytes"] = 96 * 1024 * 1024
        if blocks is None:
            blocks = kernel_blocks(S, H // Kv, max_pages, page)
            chosen_blocks[
                f"B={B} S={S} H={H} Kv={Kv} "
                f"pages={max_pages}x{page} {kv_pages.dtype.name}"
                + (f" window={sliding_window}" if sliding_window else "")
            ] = blocks
        tuning["num_kv_pages_per_block"], tuning["num_queries_per_block"] = blocks
    # One argument construction for BOTH arms (the twin is signature-
    # identical to the kernel), so CPU tests exercise the exact call the
    # TPU makes; TPU-only tuning kwargs ride separately.
    out = fn(
        q_flat, kv_pages, kv_lens, page_table.astype(jnp.int32),
        cu_q_lens, num_seqs,
        sm_scale=float(scale),
        soft_cap=softcap if softcap > 0.0 else None,
        k_scale=k_scale,
        v_scale=v_scale,
        **tuning,
    )
    out = out.reshape(B, S, H, h).astype(q.dtype)
    if live_rows is not None:
        # The kernel leaves the skipped rows' output blocks as they were
        # (whatever the buffer held, a NaN perhaps); nothing reads an
        # idle slot's token, but its row still runs through the layers.
        out = jnp.where(jnp.arange(B)[:, None, None, None] < live_rows, out, 0)
    return out


def _cpu_twin(q_flat, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs, *, sm_scale, soft_cap=None, k_scale=None, v_scale=None, sliding_window=None):
    """Jit-safe semantics twin of ragged_paged_attention, with the SAME
    signature (the library's pure-JAX reference uses Python loops over
    traced bounds, so it only runs eagerly; tests compare this twin
    against it with concrete values). Assumes the wrapper's uniform
    query split (cu_q_lens = arange * S). Table rows from num_seqs[0] on
    are not attended: their output rows are zeros (the reference returns
    none for them, the kernel leaves them unwritten)."""
    from kubeai_tpu.ops.attention import attention

    B = int(page_indices.shape[0])
    S = q_flat.shape[0] // B
    H, h = q_flat.shape[1], q_flat.shape[2]
    max_pages = page_indices.shape[1]
    page = kv_pages.shape[1]
    Kv = kv_pages.shape[2] // 2
    q = q_flat.reshape(B, S, H, h)
    gathered = kv_pages[page_indices]  # [B, mp, page, 2Kv, h]
    skv = max_pages * page
    k_att = gathered[..., 0::2, :].reshape(B, skv, Kv, h)
    v_att = gathered[..., 1::2, :].reshape(B, skv, Kv, h)
    # Quantized-pool dequant, same recipe as the kernel (f32 * scale ->
    # q.dtype).
    if k_scale is not None:
        k_att = (k_att.astype(jnp.float32) * k_scale).astype(q.dtype)
    if v_scale is not None:
        v_att = (v_att.astype(jnp.float32) * v_scale).astype(q.dtype)
    pos_q = kv_lens[:, None] - S + jnp.arange(S, dtype=jnp.int32)[None, :]
    mask = jnp.arange(skv)[None, None, :] <= pos_q[:, :, None]
    if sliding_window is not None:
        mask = jnp.logical_and(mask, jnp.arange(skv)[None, None, :] > pos_q[:, :, None] - sliding_window)
    out = attention(q, k_att, v_att, mask, scale=sm_scale, softcap=soft_cap or 0.0)
    walked = jnp.arange(B)[:, None, None, None] < num_seqs[0]
    return jnp.where(walked, out, 0).reshape(B * S, H, h)


# ---------------------------------------------------------------------------
# Heads narrower than a lane tile (module docstring)


def heads_a_tile(h: int) -> int:
    """KV heads that share a 128-lane row of the pool: 1 for heads of whole
    lane tiles, 128 / h for narrower ones."""
    return 128 // h if h < 128 and 128 % h == 0 else 1


def pack_kv(k: jnp.ndarray, v: jnp.ndarray, n: int) -> jnp.ndarray:
    """k, v [B, S, Kv, h] as the pool's rows [B, S, 2 * Kv / n, n * h]: KV
    heads j*n .. j*n+n-1 side by side, their keys on row 2j and their
    values on row 2j+1 (n = 1: K even, V odd, every family's layout)."""
    B, S, Kv, h = k.shape
    wide = lambda a: a.reshape(B, S, Kv // n, n * h)  # noqa: E731
    return jnp.stack([wide(k), wide(v)], axis=3).reshape(B, S, 2 * Kv // n, n * h)


def widen_queries(q: jnp.ndarray, Kv: int, n: int) -> jnp.ndarray:
    """q [B, S, H, h] -> [B, S, H, n * h]: a head's values in the lanes of
    its own KV head within the packed row, exact zeros in the others."""
    if n == 1:
        return q
    B, S, H, h = q.shape
    G = H // Kv
    by_lane = q.reshape(B, S, Kv // n, n, G, 1, h)
    wide = [jnp.pad(by_lane[:, :, :, l], ((0, 0),) * 4 + ((l, n - 1 - l), (0, 0))) for l in range(n)]
    return jnp.stack(wide, axis=3).reshape(B, S, H, n * h)


def narrow_outputs(o: jnp.ndarray, Kv: int, n: int) -> jnp.ndarray:
    """o [B, S, H, n * h] of a call on widened queries -> [B, S, H, h]: each
    head's own lanes (the others hold its weights over a neighbour's values)."""
    if n == 1:
        return o
    B, S, H, wide = o.shape
    G, h = H // Kv, wide // n
    by_lane = o.reshape(B, S, Kv // n, n, G, n, h)
    return jnp.stack([by_lane[:, :, :, l, :, l] for l in range(n)], axis=3).reshape(B, S, H, h)
