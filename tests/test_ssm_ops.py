"""`kubeai_tpu/ops/ssm.py` on the CPU in float32: the chunked form of the
Mamba-2 recurrence is the token-by-token recurrence (`ssd_step` a token),
for calls that are and are not whole chunks, from zeros and from a state a
call before left; the convolution with a carried tail is the convolution
over the rows laid end to end; rows that are not real leave the state and
the tail BIT FOR BIT as they were; and the one-step form's Pallas kernel, in
interpret mode, is the portable step on block j of the slots' stacked state,
every other block's rows bit for bit what they were.

Bound: float32 on both sides (conftest: "highest" matmul precision), so
what separates the two forms is summation order: read 4e-6 on outputs of
size 1 to 10; 5e-5 leaves an order of magnitude.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from kubeai_tpu.ops import ssm

ABS = 5e-5
B, H, P, G, N = 2, 8, 4, 2, 16


def _inputs(S, seed=0, from_state=False):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    return dict(
        state=f(B, H, P, N) if from_state else jnp.zeros((B, H, P, N), jnp.float32),
        x=f(B, S, H, P), d=jnp.asarray(rng.uniform(0.01, 0.6, (B, S, H)), jnp.float32),
        A=-jnp.asarray(rng.uniform(0.2, 3.0, (H,)), jnp.float32), Bm=f(B, S, G, N), Cm=f(B, S, G, N), D=f(H),
    )


def _token_by_token(state, x, d, A, Bm, Cm, D):
    ys = []
    for t in range(x.shape[1]):
        y, state = ssm.ssd_step(state, x[:, t], d[:, t], A, Bm[:, t], Cm[:, t], D)
        ys.append(y)
    return jnp.stack(ys, 1), state


@pytest.mark.parametrize("from_state", [False, True], ids=["from_zeros", "from_a_state"])
@pytest.mark.parametrize("S,chunk", [(16, 8), (32, 8), (37, 8), (5, 8), (64, 16), (50, 16)])
def test_the_chunked_form_is_the_recurrence(S, chunk, from_state):
    a = _inputs(S, seed=S, from_state=from_state)
    want_y, want_state = _token_by_token(**a)
    y, state = ssm.ssd_chunked(**a, chunk=chunk)
    assert y.shape == want_y.shape
    assert float(jnp.abs(y - want_y).max()) <= ABS
    assert float(jnp.abs(state - want_state).max()) <= ABS


def test_a_call_in_two_parts_is_the_call():
    """The state a call leaves is what the next call starts from."""
    a = _inputs(48, seed=3)
    y, state = ssm.ssd_chunked(**a, chunk=8)
    cut = lambda lo, hi: {k: (v[:, lo:hi] if k in ("x", "d", "Bm", "Cm") else v) for k, v in a.items()}  # noqa: E731
    y1, s1 = ssm.ssd_chunked(**cut(0, 20), chunk=8)
    y2, s2 = ssm.ssd_chunked(**{**cut(20, 48), "state": s1}, chunk=8)
    assert float(jnp.abs(jnp.concatenate([y1, y2], 1) - y).max()) <= ABS
    assert float(jnp.abs(s2 - state).max()) <= ABS


@pytest.mark.parametrize("n_real", [0, 1, 7, 8, 13])
def test_rows_past_the_real_ones_leave_the_state_bit_for_bit(n_real):
    """d = 0 past row n_real: the state after the call is the state after
    its real rows, and with none it is the state it was given, exactly."""
    a = _inputs(24, seed=9, from_state=True)
    real = (np.arange(24) < n_real)[None, :, None]
    masked = {**a, "d": a["d"] * real}
    _, state = ssm.ssd_chunked(**masked, chunk=8)
    if n_real == 0:
        assert np.array_equal(np.asarray(state), np.asarray(a["state"]))
        return
    short = {k: (v[:, :n_real] if k in ("x", "d", "Bm", "Cm") else v) for k, v in a.items()}
    _, want = _token_by_token(**short)
    assert float(jnp.abs(state - want).max()) <= ABS
    # ... and garbage in the rows past them changes nothing at all.
    garbage = {**masked, "x": jnp.where(real[..., None], a["x"], 1e6), "Bm": jnp.where(real[..., None], a["Bm"], -1e6)}
    _, again = ssm.ssd_chunked(**garbage, chunk=8)
    assert np.array_equal(np.asarray(again), np.asarray(state))


def _kernel_on_one_block(state, *args):
    """The kernel (interpret mode) on a stack of the one block."""
    y, states = ssm.ssd_step_kernel(state[None], 0, *args, interpret=True)
    return y, states[0]


@pytest.mark.parametrize("step", [ssm.ssd_step, _kernel_on_one_block], ids=["portable", "kernel"])
def test_a_step_of_rows_that_are_not_live_moves_nothing(step):
    a = _inputs(1, seed=4, from_state=True)
    live = jnp.asarray([1.0, 0.0])[:, None]
    y, state = step(a["state"], a["x"][:, 0], a["d"][:, 0] * live, a["A"], a["Bm"][:, 0], a["Cm"][:, 0], a["D"])
    assert np.array_equal(np.asarray(state[1]), np.asarray(a["state"][1]))
    assert not np.array_equal(np.asarray(state[0]), np.asarray(a["state"][0]))


# (B, H, P, N, G): the file's toy shape; three heads a group (not a power of
# two); the published widths at 3 slots (a 4 MiB tile a slot, as on the chip).
STEP_SHAPES = [(2, 8, 4, 16, 2), (2, 12, 8, 16, 4), (3, 128, 64, 128, 8)]


@pytest.mark.parametrize("j", [0, 1, 2])
@pytest.mark.parametrize("shape", STEP_SHAPES, ids=["toy", "three_heads_a_group", "published_widths"])
def test_the_step_kernel_is_the_portable_step_on_block_j_alone(shape, j):
    """Float32 on both sides: the update is the same three operations an
    element (read: equal, or an FMA's last bit), `y` a sum of N products in
    another order (read 8e-6 on outputs to 40 at the published widths)."""
    Bs, Hs, Ps, Ns, Gs = shape
    rng = np.random.default_rng(10 * Hs + j)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    states = f(3, Bs, Hs, Ps, Ns)
    args = (
        f(Bs, Hs, Ps), jnp.asarray(rng.uniform(0.01, 0.6, (Bs, Hs)), jnp.float32),
        -jnp.asarray(rng.uniform(0.2, 3.0, (Hs,)), jnp.float32), f(Bs, Gs, Ns), f(Bs, Gs, Ns), f(Hs),
    )
    assert ssm.kernel_heads_per_tile(Hs, Ps, Ns, Gs) == Hs  # one program a slot at each of these
    want_y, want_state = ssm.ssd_step(states[j], *args)
    y, new = ssm.ssd_step_kernel(states, j, *args, interpret=True)
    assert y.shape == want_y.shape and new.shape == states.shape
    assert float(jnp.abs(y - want_y).max()) <= ABS
    assert float(jnp.abs(new[j] - want_state).max()) <= 1e-6
    for other in set(range(3)) - {j}:
        assert np.array_equal(np.asarray(new[other]), np.asarray(states[other]))


def test_the_step_kernel_in_tiles_of_a_slot():
    """Two programs a slot (a tile holds whole groups): the same step."""
    a = _inputs(1, seed=6, from_state=True)
    args = (a["x"][:, 0], a["d"][:, 0], a["A"], a["Bm"][:, 0], a["Cm"][:, 0], a["D"])
    want_y, want_state = ssm.ssd_step(a["state"], *args)
    y, states = ssm.ssd_step_kernel(a["state"][None], 0, *args, heads_per_tile=H // 2, interpret=True)
    assert float(jnp.abs(y - want_y).max()) <= ABS
    assert float(jnp.abs(states[0] - want_state).max()) <= 1e-6


@pytest.mark.parametrize(
    "shape,want", [((128, 64, 128, 8), 128), ((256, 64, 128, 8), 128), ((256, 128, 128, 8), 128), ((12, 8, 16, 4), 12)],
    ids=["published_whole_slot", "half_of_256_heads", "tile_over_the_budget", "toy_whole_slot"],
)
def test_the_tile_is_a_rule_on_the_shape(shape, want):
    assert ssm.kernel_heads_per_tile(*shape) == want


def test_the_stacked_step_off_the_chip_is_the_portable_step(monkeypatch):
    """On the CPU backend `ssd_step_stacked` takes the portable step and
    records nothing: `/debug/engine` -> `perf.ssm_kernel_blocks` stays
    empty, which says the kernel is not what the process compiled."""
    monkeypatch.setattr(ssm, "chosen_blocks", {})
    a = _inputs(1, seed=8, from_state=True)
    args = (a["x"][:, 0], a["d"][:, 0], a["A"], a["Bm"][:, 0], a["Cm"][:, 0], a["D"])
    states = jnp.stack([a["state"], a["state"] + 1.0])
    want_y, want_state = ssm.ssd_step(states[1], *args)
    y, new = ssm.ssd_step_stacked(states, 1, *args)
    assert np.array_equal(np.asarray(y), np.asarray(want_y)) and np.array_equal(np.asarray(new[1]), np.asarray(want_state))
    assert np.array_equal(np.asarray(new[0]), np.asarray(states[0])) and ssm.chosen_blocks == {}


def _conv_inputs(S, K=4, C=12, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    return f(B, S, C), f(K, C), f(C)


def _conv_plain(x, w, b):
    K, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return b + sum(padded[:, k : k + S] * w[k] for k in range(K))


@pytest.mark.parametrize("cut", [1, 2, 3, 10, 19])
def test_the_convolution_carries_its_tail(cut):
    """Rows [0, cut) and then [cut, 20) with the tail between them are the
    convolution over all 20; a first call shorter than the taps reaches
    back into the zeros before the sequence."""
    x, w, b = _conv_inputs(20)
    want = _conv_plain(x, w, b)
    zeros = jnp.zeros((B, 3, x.shape[2]), jnp.float32)
    y1, tail = ssm.causal_conv(x[:, :cut], zeros, jnp.full((B,), cut), w, b)
    y2, tail2 = ssm.causal_conv(x[:, cut:], tail, jnp.full((B,), 20 - cut), w, b)
    assert float(jnp.abs(jnp.concatenate([y1, y2], 1) - want).max()) <= 1e-6
    assert np.array_equal(np.asarray(tail2), np.asarray(x[:, -3:]))


@pytest.mark.parametrize("n_real", [0, 1, 2, 5])
def test_the_tail_is_taken_at_the_last_real_row(n_real):
    """Bit for bit: with no real row the old tail; with fewer than the
    taps, the old tail's end and then the real rows; never a padded row."""
    x, w, b = _conv_inputs(8, seed=2)
    old = jnp.asarray(np.random.default_rng(5).normal(size=(B, 3, x.shape[2])), jnp.float32)
    _, tail = ssm.causal_conv(x, old, jnp.full((B,), n_real), w, b)
    want = jnp.concatenate([old, x[:, :n_real]], axis=1)[:, -3:]
    assert np.array_equal(np.asarray(tail), np.asarray(want))
