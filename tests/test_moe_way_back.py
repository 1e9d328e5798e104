"""The expert layer's way back to tokens (`ops/moe.py::_back_to_tokens`):
a token's k rows are summed choice by choice in float32, whatever k is,
whoever holds the experts and however many passes a share takes; and a
token's sum does not depend on how many tokens the call holds, nor on
which of the two ways the call's size picks to hold the rows (one gather
of all of them, or a loop over the choices in blocks of tokens).

The reference is a plain NumPy float64 sum over (token, choice)."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from kubeai_tpu.ops import moe  # noqa: E402

D, F = 64, 32


@pytest.fixture(params=["one_gather", "loop"])
def way(request, monkeypatch):
    """Both ways to hold the rows at a size the CPU can afford: the loop is
    what a call takes whose gathered rows pass `ON_CORE_ROWS`."""
    if request.param == "loop":  # read while tracing: the tests jit a function of their own each time
        monkeypatch.setattr(moe, "ON_CORE_ROWS", 0)
    return request.param


def skewed_routing(rng, T, k, E, chosen):
    """idx [T, k] over *chosen* of the E experts only (the others get no
    row), leaning on the first of them; weights [T, k] float32, unnormalised."""
    lean = 1.0 / np.arange(1, len(chosen) + 1)
    idx = np.stack([rng.choice(chosen, size=k, replace=False, p=lean / lean.sum()) for _ in range(T)])
    return idx.astype(np.int32), rng.uniform(0.05, 1.0, (T, k)).astype(np.float32)


def weights_of(rng, E, gate=True):
    wu = rng.standard_normal((E, D, F)).astype(np.float32) * 0.2
    wd = rng.standard_normal((E, F, D)).astype(np.float32) * 0.2
    return (rng.standard_normal((E, D, F)).astype(np.float32) * 0.2 if gate else None), wu, wd


def float64_sum(x, idx, w, wg, wu, wd, first=0):
    """sum_i w[t, i] * GLU_{idx[t, i]}(x[t]) over the experts first ..
    first+E-1 that (wg, wu, wd) hold, in float64."""
    silu = lambda a: a / (1.0 + np.exp(-a))
    y = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        for i in range(idx.shape[1]):
            e = idx[t, i] - first
            if 0 <= e < wu.shape[0]:
                xt = x[t].astype(np.float64)
                up = xt @ wu[e].astype(np.float64)
                hidden = silu(up) if wg is None else silu(xt @ wg[e].astype(np.float64)) * up
                y[t] += float(w[t, i]) * (hidden @ wd[e].astype(np.float64))
    return y


@pytest.mark.parametrize("k", [2, 5, 6, 8, 22])
def test_the_way_back_is_the_float64_sum_over_token_and_choice(k, way):
    rng = np.random.default_rng(k)
    T, E = 40, 32
    chosen = np.arange(1, E, 2) if k <= E // 2 else np.arange(2, E)  # experts without rows either way
    idx, w = skewed_routing(rng, T, k, E, chosen)
    x = rng.standard_normal((T, D)).astype(np.float32)
    wg, wu, wd = weights_of(rng, E)
    y, hit = jax.jit(lambda *a: moe.routed_experts(*a))(x, idx, w, wg, wu, wd)
    assert y.dtype == jnp.float32 and int(hit) == len(np.unique(idx)) < E
    want = float64_sum(x, idx, w, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(y, np.float64), want, rtol=2e-4, atol=2e-4 * np.abs(want).max())


@pytest.mark.parametrize("k", [2, 5, 6, 8, 22])
def test_a_share_that_takes_several_passes_is_the_float64_sum_of_its_experts(k, way):
    """Every assignment lands on this chip's 24 of 96 experts: a pass
    holds a third over the even share, so the sum takes three passes, and
    a token's choices may sit in different ones."""
    rng = np.random.default_rng(100 + k)
    T, held = 96, (8, 24, 96)
    idx, w = skewed_routing(rng, T, k, 96, np.arange(9, 32))  # expert 8 of the share gets no row
    assert -(-T * k // moe.held_capacity(T * k, 24, 96)) >= 3
    x = rng.standard_normal((T, D)).astype(np.float32)
    _, wu, wd = weights_of(rng, 24, gate=False)
    y, hit = jax.jit(lambda *a: moe.routed_experts(*a, held=held))(x, idx, w, None, wu, wd)
    assert int(hit) == len(np.unique(idx)) < 24
    want = float64_sum(x, idx, w, None, wu, wd, first=8)
    np.testing.assert_allclose(np.asarray(y, np.float64), want, rtol=2e-4, atol=2e-4 * np.abs(want).max())
    # With a part of the choices elsewhere, only this chip's part is summed.
    idx[:, 0] = 40 + idx[:, 0]
    y, _ = jax.jit(lambda *a: moe.routed_experts(*a, held=held))(x, idx, w, None, wu, wd)
    want = float64_sum(x, idx, w, None, wu, wd, first=8)
    np.testing.assert_allclose(np.asarray(y, np.float64), want, rtol=2e-4, atol=2e-4 * np.abs(want).max())


@pytest.mark.parametrize("k", [6, 8, 22])
def test_the_helper_alone_is_the_float64_sum_and_leaves_out_what_is_not_mine(k, way):
    rng = np.random.default_rng(200 + k)
    T = 64
    out = jnp.asarray(rng.standard_normal((T * k, D)), jnp.bfloat16)
    back = rng.permutation(T * k).astype(np.int32).reshape(T, k)
    w = rng.uniform(0.05, 1.0, (T, k)).astype(np.float32)
    rows = np.asarray(out, np.float64)[back]  # [T, k, D]
    got = jax.jit(lambda *a: moe._back_to_tokens(*a))(out, back, w)
    assert got.dtype == jnp.float32 and got.shape == (T, D)
    np.testing.assert_allclose(np.asarray(got, np.float64), (rows * w[:, :, None]).sum(axis=1), rtol=1e-5, atol=1e-5)
    # Rows that are not this pass's may hold anything: they are left out, not multiplied by zero.
    mine = rng.random((T, k)) < 0.3
    poisoned = jnp.where(jnp.asarray(mine.reshape(-1)[np.argsort(back.reshape(-1))])[:, None], out, jnp.nan)
    got = jax.jit(lambda *a: moe._back_to_tokens(*a))(poisoned, back, w, mine)
    np.testing.assert_allclose(np.asarray(got, np.float64), (rows * (w * mine)[:, :, None]).sum(axis=1), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T", [2048, 4096], ids=["one_block", "two_blocks"])
@pytest.mark.parametrize("k", [6, 8, 22])
def test_one_wide_call_is_1024_token_calls_bit_for_bit(k, T, monkeypatch):
    """The order of a token's k additions is fixed (choice order, from
    zero), so the same rows give the same bits however many tokens share
    the call (one block of `WAY_BACK_BLOCK` tokens or two) and wherever
    the sort put them. Between the two ways to hold the rows the additions
    are the same ones in the same order and the bits are the compiler's:
    the chip gave the same bits at every shape the cells run (PERF.md
    section 6, PR 44); this backend contracts a multiply and an add in a
    loop's body and not in a fused sum, so here it is float32 rounding."""
    rng = np.random.default_rng(300 + k)
    assert T // moe.WAY_BACK_BLOCK == (1 if T == 2048 else 2)
    rows = jnp.asarray(rng.standard_normal((T, k, D)), jnp.bfloat16)  # row (t, i), in (token, choice) order
    w = rng.uniform(0.05, 1.0, (T, k)).astype(np.float32)

    def call(lo, hi):
        n = (hi - lo) * k
        back = rng.permutation(n).astype(np.int32)  # where each (token, choice) sits among the sorted rows
        out = jnp.zeros((n, D), jnp.bfloat16).at[back].set(rows[lo:hi].reshape(n, D))
        return np.asarray(jax.jit(lambda *a: moe._back_to_tokens(*a))(out, back.reshape(hi - lo, k), w[lo:hi]))

    def calls():
        wide, narrow = call(0, T), np.concatenate([call(lo, lo + 1024) for lo in range(0, T, 1024)])
        assert np.array_equal(wide.view(np.uint32), narrow.view(np.uint32))
        return wide

    one_gather = calls()
    monkeypatch.setattr(moe, "ON_CORE_ROWS", 0)  # read while tracing, and every `call` traces anew
    np.testing.assert_allclose(calls(), one_gather, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("held", [None, (0, 8, 32)], ids=["all_experts", "a_share"])
def test_a_wide_call_of_the_layer_is_two_narrow_ones(held):
    """Through the whole layer (sort, grouped matmuls, way back): to
    float32 rounding, since the grouped matmul of another call shape owes
    no bits (and a share's passes split a token's choices differently)."""
    rng = np.random.default_rng(7)
    T, k, E = 2048, 6, 32
    idx, w = skewed_routing(rng, T, k, E, np.arange(0, E, 2) if held is None else np.arange(0, 20))
    x = rng.standard_normal((T, D)).astype(np.float32)
    wg, wu, wd = weights_of(rng, E if held is None else held[1])
    layer = jax.jit(lambda *a: moe.routed_experts(*a, held=held)[0])
    wide = np.asarray(layer(x, idx, w, wg, wu, wd))
    narrow = np.concatenate([np.asarray(layer(x[lo:lo + 1024], idx[lo:lo + 1024], w[lo:lo + 1024], wg, wu, wd)) for lo in (0, 1024)])
    np.testing.assert_allclose(wide, narrow, rtol=1e-5, atol=1e-5 * np.abs(wide).max())
