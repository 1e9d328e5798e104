"""The MLA paged decode kernel (ops/mla_attention.py) in Pallas interpret
mode against the XLA form over the gathered pages: slots of uneven
lengths (one token; the middle of a page; a page's edge; a block's edge;
the table's whole width) in the orders that try a copy carried from one
slot to the next, pages scattered over the pool."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubeai_tpu.ops import mla_attention


# Slot lengths, as functions of (page, pages a block, the table's pages),
# over the edges a copy carried from one slot to the next creates: a
# slot whose first blocks were started by the slots before it (one or
# two slots back: the ring runs two blocks ahead), the buffer a block
# lands in (the blocks before it, modulo the ring), a last block scored
# at each of its sizes, the call's last slots (which start no copy for
# anybody).
SLOT_LENGTHS = {
    "uneven": lambda page, ppb, n: [1, 5, page, 2 * page + 3, n * page],
    "one_slot": lambda page, ppb, n: [3 * page + 5],
    "one_slot_of_one_token": lambda page, ppb, n: [1],
    "one_token_between_two_long": lambda page, ppb, n: [n * page, 1, n * page - 1],
    "page_and_block_edges": lambda page, ppb, n: [
        page, page + 1, ppb * page - 1, ppb * page, ppb * page + 1, 2 * ppb * page, 2 * ppb * page + 1, n * page,
    ],
    "odd_then_even_block_counts": lambda page, ppb, n: [
        ppb * page, 2 * ppb * page, 3 * ppb * page, 3 * ppb * page, ppb * page, 2 * ppb * page + 1, 1, 1, 2 * ppb * page,
    ],
    "every_size_of_a_last_block": lambda page, ppb, n: [ppb * page + live * page - 1 for live in range(1, ppb + 1)],
}


@pytest.mark.parametrize("case", list(SLOT_LENGTHS))
@pytest.mark.parametrize("ppb", list(range(1, 17)))
def test_the_kernel_reads_latent_pages_as_the_gathered_form_does(ppb, case):
    """`ppb`: every pages-a-block `kernel_pages_per_block` can return at
    64-token pages (16, or the whole of a narrower table), and with them
    every list `_last_block_sizes` can give."""
    rng = np.random.default_rng(0)
    H, W, rank, page, max_pages = 4, 128, 96, 8, 3 * ppb
    lengths = np.minimum(np.array(SLOT_LENGTHS[case](page, ppb, max_pages), np.int32), max_pages * page)
    B = len(lengths)
    P = B * max_pages + 1
    pool = rng.normal(size=(P, page, W)).astype(np.float32)
    pool[..., rank + 16 :] = 0.0  # the padding columns
    table = rng.permutation(np.arange(1, P))[: B * max_pages].reshape(B, max_pages).astype(np.int32)
    q = rng.normal(size=(B, H, W)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want = mla_attention.mla_paged_decode(
            jnp.asarray(q), jnp.asarray(pool), jnp.asarray(table), jnp.asarray(lengths), scale=0.2, rank=rank,
        )
        got = mla_attention.mla_paged_decode_kernel(
            jnp.asarray(q), jnp.asarray(pool), jnp.asarray(table), jnp.asarray(lengths),
            scale=0.2, rank=rank, pages_per_block=ppb, interpret=True,
        )
    assert got.shape == (B, H, rank)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 1e-5  # float32, another summation order


@pytest.mark.parametrize("max_pages,page,want", [(64, 64, 16), (128, 64, 16), (16, 64, 16), (5, 64, 5), (1, 64, 1), (8, 8, 8), (256, 8, 128)])
def test_the_block_is_1024_tokens_cut_to_the_table(max_pages, page, want):
    ppb = mla_attention.kernel_pages_per_block(max_pages, page)
    assert ppb == want
    sizes = mla_attention._last_block_sizes(ppb)
    # Ascending, whole pages, the last one the block itself, steps of a quarter.
    assert sizes == sorted(set(sizes)) and sizes[-1] == ppb and len(sizes) <= 4
    assert all(b - a <= -(-ppb // 4) for a, b in zip([0] + sizes, sizes))


def test_a_stale_buffer_cannot_reach_the_sum():
    """Pages past a slot's length are not copied: what the buffers held
    before (here: nan, from the slot before it) must not show, in the
    short slot, in the long slot whose copies ran beside the short one's
    scores, or in the slot after. Every page of the pool that no live
    token is on holds nan too."""
    B, H, W, rank, page, max_pages, ppb = 4, 2, 128, 64, 8, 4, 2
    rng = np.random.default_rng(2)
    lengths = np.array([32, 3, 19, 9], np.int32)
    table = np.arange(1, 1 + B * max_pages, dtype=np.int32).reshape(B, max_pages)
    pool = np.full((1 + B * max_pages, page, W), np.nan, np.float32)
    for b in range(1, B):  # slot 0's four pages stay nan: both buffers have held nan when slot 1 begins
        live = -(-int(lengths[b]) // page)
        pool[table[b, :live]] = rng.normal(size=(live, page, W))
    q = rng.normal(size=(B, H, W)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(mla_attention.mla_paged_decode_kernel(
            jnp.asarray(q), jnp.asarray(pool), jnp.asarray(table), jnp.asarray(lengths),
            scale=0.1, rank=rank, pages_per_block=ppb, interpret=True,
        ))
        want = np.asarray(mla_attention.mla_paged_decode(
            jnp.asarray(q), jnp.asarray(np.nan_to_num(pool)), jnp.asarray(table), jnp.asarray(lengths), scale=0.1, rank=rank,
        ))
    assert np.isnan(got[0]).all()
    assert np.isfinite(got[1:]).all()
    assert np.abs(got[1:] - want[1:]).max() <= 1e-5


def test_the_debug_page_names_the_form_beside_the_block(monkeypatch):
    """/debug/engine -> perf.mla_kernel_blocks: what a traced call shape
    was given (the chip's branch, with the kernel itself stubbed)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mla_attention, "chosen_blocks", {})
    monkeypatch.setattr(
        mla_attention, "mla_paged_decode_kernel",
        lambda q, pool, table, lens, *, scale, rank: jnp.zeros(q.shape[:2] + (rank,), q.dtype),
    )
    out = mla_attention.mla_paged_decode(
        jnp.zeros((4, 2, 128)), jnp.zeros((9, 64, 128)), jnp.zeros((4, 20), jnp.int32), jnp.array([1, 70, 0, 5000]),
        scale=0.1, rank=64,
    )
    assert out.shape == (4, 2, 64)
    (shape, given), = mla_attention.chosen_blocks.items()
    assert shape == "B=4 H=2 W=128 pages=20x64 float32"
    assert given == {
        "pages_per_block": 16, "buffers": mla_attention.KERNEL_BUFFERS,
        "copies": "one pipeline over the call's slots, one slot a program",
    }


@pytest.mark.parametrize("start,S", [(0, 40), (24, 16), (37, 3), (39, 1)])
def test_a_query_row_computes_the_same_whatever_the_call_around_it(start, S, monkeypatch):
    """Cold prefill and prefill behind cached tokens are one function: a
    row computed in a call of the whole prompt and in a call of its tail
    walks the same key blocks in the same order, and blocks past its
    position leave its state untouched. (Held to float32 rounding: a
    matmul sums in another order for another row count, on the CPU and
    on the chip, which is why a prefix hit is used in whole prefill
    calls: models/deepseek.py.)"""
    monkeypatch.setattr(mla_attention, "XLA_BLOCK_TOKENS", 16)
    rng = np.random.default_rng(1)
    H, W, rank, page, max_pages, N = 3, 128, 96, 8, 8, 40
    pool = rng.normal(size=(1 + max_pages, page, W)).astype(np.float32)
    table = (1 + rng.permutation(max_pages)).reshape(1, max_pages).astype(np.int32)
    q = rng.normal(size=(1, N, H, W)).astype(np.float32)

    def run(lo, n):
        pos = (lo + np.arange(n, dtype=np.int32))[None]
        return np.asarray(mla_attention.latent_attention_paged(
            jnp.asarray(q[:, lo : lo + n]), jnp.asarray(pool), jnp.asarray(table), jnp.asarray(pos), scale=0.1, rank=rank,
        ))

    whole = run(0, N)
    assert np.abs(run(start, S) - whole[:, start : start + S]).max() <= 2e-6
