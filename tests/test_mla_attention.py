"""The MLA paged decode kernel (ops/mla_attention.py) in Pallas interpret
mode against the XLA form over the gathered pages: slots of uneven
lengths (one token; the middle of a page; a page's edge; the table's
whole width), pages scattered over the pool."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubeai_tpu.ops import mla_attention


@pytest.mark.parametrize("ppb", [1, 2, 4])
def test_the_kernel_reads_latent_pages_as_the_gathered_form_does(ppb):
    rng = np.random.default_rng(0)
    B, H, W, rank, page, max_pages, P = 5, 4, 128, 96, 8, 4, 40
    lengths = np.array([1, 5, 8, 19, 32], np.int32)
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


def test_a_stale_buffer_cannot_reach_the_sum():
    """Pages past a slot's length are not copied: what the buffer held
    before (here: nan, from the slot before it) must not show."""
    B, H, W, rank, page, max_pages = 2, 2, 128, 64, 8, 2
    pool = np.ones((8, page, W), np.float32)
    pool[1] = np.nan  # slot 0's second page: copied for slot 0 (length 16), stale for slot 1 (length 3)
    table = np.array([[2, 1], [3, 4]], np.int32)
    q = np.ones((B, H, W), np.float32)
    got = mla_attention.mla_paged_decode_kernel(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(table), jnp.asarray([16, 3], np.int32),
        scale=0.1, rank=rank, pages_per_block=2, interpret=True,
    )
    assert np.isnan(np.asarray(got[0])).all() and np.isfinite(np.asarray(got[1])).all()


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
