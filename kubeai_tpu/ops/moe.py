"""Exact routed experts: every (token, choice) assignment is computed,
none is dropped and there is no capacity.

The `T*k` assignments are sorted by expert, the rows gathered in that
order, and each of the three expert projections is ONE grouped matmul
over the groups that have rows (rows of expert e times expert e's
matrix). The way back is a gather, choice by choice: the rows of the
tokens' i-th choice are gathered as [tokens, D] and added, times their
float32 weights, into ONE float32 [tokens, D], i = 0 .. k-1
(`_back_to_tokens`).
Shapes are static (`T*k` rows whatever the loads are), so a batch whose
routing changes never recompiles. `models/llama.py::moe_mlp` (Mixtral:
softmax over the top-k, a static capacity, tokens past it dropped) is a
different layer and stays where it is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# (tm, tk, tn) the grouped matmul was given, per call shape this process
# has traced (diagnosis: /debug/engine -> perf.grouped_matmul_tiles).
chosen_tiles: dict[str, tuple[int, int, int]] = {}
# The way back (`_back_to_tokens`) gathers all of a call's T*k rows at once
# while they are at most this many bytes: on core they stay from the gather
# to the sum (46 MB, Nemotron's 1024-token call, and 50 MB, kanana-2's 2048,
# read under the loop; 63 MB, SmallThinker's 2048, read over it). Past it the
# loop's float32 sum holds this many tokens at a time: 21 MB at D = 2560, on
# core through all k turns, where a whole 8192-token group's 67 MB would be
# read and written in HBM every turn. Read on the chip at every shape the
# cells run, alone and inside longdoc-sat's chunks (PERF.md section 6, PR 44).
ON_CORE_ROWS = 48 << 20
WAY_BACK_BLOCK = 2048


def gmm_tiles(m: int, k: int, n: int) -> tuple[int, int, int]:
    """Tiles of the megablox kernel from the call's own shapes. The
    contraction and the output keep the expert's whole matrix in one tile
    where it is at most 4 MiB in bf16 (2048 x 768: 3 MiB, two buffers), so
    an expert's weights are one DMA and a grid step is not shorter than
    its fixed cost.

    Rows: the largest tile of at most 192 that divides them (Mosaic takes
    a block of whole 8-row sublanes, or one as tall as the array: rows no
    such tile divides are one tile). A grid step is one (row tile, expert)
    pair, masked to the expert's rows. A smaller tile has more edges for
    an expert's rows to straddle, each a second pass of its matrix through
    the MXU, and, costing more than those, more tiles for the kernel's
    group metadata to lay out before a layer's three calls; from 256 rows
    up a step is compute-bound on masked rows. Read on the chip in the
    step's own form at every shape the engine compiles, under real and
    uniform loads: 192 is the fastest or within 1.2% of it at each
    (PERF.md section 6, PR 35)."""
    tm = max((t for t in range(8, min(m, 192) + 1, 8) if m % t == 0), default=m)
    tk, tn = k, n
    while tk * tn > (2 << 20) and tn % 256 == 0 and tn > 512:
        tn //= 2
    while tk * tn > (2 << 20) and tk % 256 == 0 and tk > 512:
        tk //= 2
    return tm, tk, tn


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray) -> jnp.ndarray:
    """`lhs[rows of group g] @ rhs[g]` for every group: lhs [m, k] with
    its rows sorted by group, rhs [G, k, n], group_sizes [G] int32 summing
    to m. On the chip the megablox Pallas kernel (it visits only groups
    that have rows, so an expert nobody chose is not read); elsewhere
    `jax.lax.ragged_dot`, the same mathematics in XLA."""
    if jax.default_backend() != "tpu":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = lhs.shape
    n = rhs.shape[2]
    tiles = gmm_tiles(m, k, n)
    chosen_tiles[f"m={m} k={k} n={n} G={rhs.shape[0]}"] = tiles
    return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype, tiling=tiles)


def route_sigmoid(x, wr, bias, k: int, norm_topk: bool, scale: float, forced=None):
    """DeepSeek-V3's `noaux_tc` router with one group: scores are
    sigmoid(float32(x) W_g^T); the top k of score + bias are chosen; the
    weights are the SCORES at the chosen (without the bias), divided by
    their sum + 1e-20 where `norm_topk`, times `scale`. x [T, D], wr
    [D, E], bias [E]. Returns (idx [T, k] int32, weights [T, k] float32).
    *forced* [T, k] takes the place of the choice (a debug call: a
    comparison that must not hang on which side of a near-tie each
    side's rounding fell)."""
    scores = jax.nn.sigmoid(
        jnp.dot(x.astype(jnp.float32), wr.astype(jnp.float32), preferred_element_type=jnp.float32)
    )
    if forced is None:
        _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32)[None, :], k)
    else:
        idx = forced
    w = jnp.take_along_axis(scores, idx, axis=1)
    if norm_topk:
        w = w / (w.sum(axis=1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale


def route_softmax_topk(r, k: int, forced=None):
    """A router whose logits *r* [T, E] are given (SmallThinker computes
    them from the layer's input, before attention): the top k logits are
    chosen and the weights are the softmax over the CHOSEN logits, in
    float32, which is the softmax over all E, taken at the chosen and
    renormalised (`norm_topk_prob`). Returns (idx [T, k] int32, weights
    [T, k] float32). *forced* as in `route_sigmoid`."""
    r = r.astype(jnp.float32)
    if forced is None:
        _, idx = jax.lax.top_k(r, k)
    else:
        idx = forced
    return idx.astype(jnp.int32), jax.nn.softmax(jnp.take_along_axis(r, idx, axis=1), axis=1)


def _where_sorted(order):
    """[n] int32: where assignment j sits among the sorted ones, the inverse
    of the permutation *order* (row r of the sorted rows is assignment
    order[r])."""
    n = order.shape[0]
    return jnp.zeros((n,), jnp.int32).at[order].set(jnp.arange(n, dtype=jnp.int32))


def _back_to_tokens(out, back, weights, mine=None):
    """sum_i weights[t, i] * out[back[t, i]] in float32: out [R, D], the
    experts' rows sorted by expert; back [T, k] int32, the row of each
    (token, choice); weights [T, k] float32. Returns [T, D] float32. With
    *mine* [T, k] bool only the choices it marks are summed (the others'
    rows may never have been written: they are left out, not multiplied
    by zero).

    Choice by choice: the rows of choice i are [tokens, D], tokens on the
    sublanes whatever k is, and are added in the order i = 0 .. k-1 into
    ONE float32 [tokens, D]. The order of a token's k additions is fixed,
    so the result is the same function of its rows at every call size, and
    no array has k between the tokens and D. (`out[back].reshape(T, k, D)`
    summed over k puts k on the sublanes: at k = 6 a relayout in float32
    padded to 8 rows a token, which past 1024 tokens leaves the core for
    HBM. PERF.md section 6, PR 44.)

    One algorithm, two ways to hold the rows, by the call's static size.
    While all T*k gathered rows fit on core (`ON_CORE_ROWS`: every decode
    step, every call up to 1024 tokens) they are ONE gather in choice-major
    order, viewed as [k, T, D], and one fused sum: two operations a layer.
    Past that the gathered rows would go through HBM, so a loop over the
    choices gathers [B, D] a turn and adds it to the float32 sum of
    `WAY_BACK_BLOCK` tokens, which stays on core through its k turns."""
    T, k = weights.shape
    D = out.shape[1]
    back, weights, mine = (a if a is None else a.T for a in (back, weights, mine))  # [k, T]: choice i is a row

    def add(y, rows, weight, mine):
        term = rows.astype(jnp.float32) * weight[:, None]
        return y + (term if mine is None else jnp.where(mine[:, None], term, 0.0))

    if T * k * D * out.dtype.itemsize <= ON_CORE_ROWS:
        rows = out[back.reshape(T * k)].reshape(k, T, D)
        y = jnp.zeros((T, D), jnp.float32)
        for i in range(k):
            y = add(y, rows[i], weights[i], None if mine is None else mine[i])
        return y

    B = WAY_BACK_BLOCK if T % WAY_BACK_BLOCK == 0 else T

    def block(operands):
        back, weights, mine = operands  # [k, B]
        turn = lambda i, y: add(y, out[back[i]], weights[i], None if mine is None else mine[i])
        return jax.lax.fori_loop(0, k, turn, jnp.zeros((B, D), jnp.float32))

    if T == B:
        return block((back, weights, mine))
    # [k, T] -> [T / B, k, B]: a block's choice i is one row of B tokens.
    blocks = jax.tree.map(lambda a: a.reshape(k, T // B, B).swapaxes(0, 1), (back, weights, mine))
    return jax.lax.map(block, blocks).reshape(T, D)


def routed_experts(x, idx, weights, wg, wu, wd, layer=None, act=jax.nn.silu, held=None):
    """sum_i weights[t, i] * GLU_{idx[t, i]}(x[t]) for every token, the
    gate's activation *act* (SiLU: SwiGLU; ReLU: SmallThinker's ReGLU):
    x [T, D]; idx, weights [T, k]; wg, wu [E, D, F]; wd [E, F, D].
    Returns (y [T, D] in x's dtype, hit: how many experts got a row).

    Experts WITHOUT a gate matrix (`wg=None`, Nemotron-H) are
    `act(x[t] Wu) Wd`, *act* on the up-projection.

    With *held* = (first, count, of) this chip holds experts first ..
    first+count-1 of the `of` the router chose among (wu, wd have `count`
    groups; idx may name any of the `of`): the layer returns ITS experts'
    part of the sum (`_held_part`). Nothing stands in for the absent
    chips: their part of the sum is theirs.

    With *layer* (a traced int32) the weights are the WHOLE stack, wg, wu
    [L, E, D, F] and wd [L, E, F, D], and the layer's experts are groups
    layer*E .. layer*E+E-1 of L*E, every other group empty: the grouped
    matmul reads only groups that have rows, so a scan over layers hands
    it the stack as it lies in memory. (Slicing a layer's experts out
    of the stack first is a copy of all of them, 1.2 GB a layer for
    kanana-2: 20 ms of a 49 ms decode step, PERF.md section 6, PR 33.)"""
    if held is not None:
        if layer is not None:
            raise ValueError("a share of the experts is read from one layer's own arrays, not from a stack")
        return _held_part(x, idx, weights, wg, wu, wd, act, held)
    T, D = x.shape
    k = idx.shape[1]
    E = wu.shape[-3]
    with jax.named_scope("moe.dispatch"):
        flat = idx.reshape(T * k)
        order = jnp.argsort(flat, stable=True)
        token = order // k
        group_sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1)
        hit = (group_sizes > 0).sum().astype(jnp.int32)
        if layer is not None:
            L = wg.shape[0]
            group_sizes = jax.lax.dynamic_update_slice(jnp.zeros((L * E,), jnp.int32), group_sizes, (layer * E,))
            wg, wu, wd = (w.reshape(L * E, *w.shape[2:]) for w in (wg, wu, wd))
        rows = x[token]  # [T*k, D], sorted by expert
    # `moe.experts` holds the grouped matmuls and nothing else: a device
    # trace files their time under it whatever implements them.
    with jax.named_scope("moe.experts"):
        gate = None if wg is None else grouped_matmul(rows, wg, group_sizes)
        up = grouped_matmul(rows, wu, group_sizes)
    hidden = act(up) if gate is None else act(gate) * up
    with jax.named_scope("moe.experts"):
        out = grouped_matmul(hidden, wd, group_sizes)
    with jax.named_scope("moe.combine"):
        y = _back_to_tokens(out, _where_sorted(order).reshape(T, k), weights)
    return y.astype(x.dtype), hit


def held_capacity(n: int, count: int, of: int) -> int:
    """Rows a pass of `_held_part` gathers for *n* assignments where
    *count* of *of* experts are held: a third more than the held experts'
    even share, in whole 64-row steps (every row tile `gmm_tiles` picks
    divides those), and never more than there are assignments."""
    return min(n, -(-(4 * n * count) // (3 * of * 64)) * 64)


def _held_part(x, idx, weights, wg, wu, wd, act, held):
    """`routed_experts` for a chip that holds experts first .. first+count-1
    of the `of` the router scores: (this chip's part of y, held experts
    that got a row). Assignments to experts that are not here are dropped
    BEFORE any row is gathered: they sort behind every held group and no
    pass reaches them, so no row, and no zero, of theirs goes through the
    MXU or through memory.

    Shapes stay static and nothing is ever left out: the held assignments,
    sorted by expert, are taken `held_capacity` rows a pass, in a
    `lax.while_loop` that runs while assignments are left (the pass is
    traced and compiled once). One pass holds them all unless the routing
    leans on this chip by a third more than its even share; the worst
    case, every assignment here, is `ceil(rows / capacity)` passes."""
    first, count, of = held
    T, D = x.shape
    k = idx.shape[1]
    n = T * k
    C = held_capacity(n, count, of)
    passes = -(-n // C)
    with jax.named_scope("moe.dispatch"):
        flat = idx.reshape(n)
        here = (flat >= first) & (flat < first + count)
        key = jnp.where(here, flat - first, count)  # an absent expert's: behind every held group
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
        ends = jnp.cumsum(sizes)
        starts, n_here = ends - sizes, ends[-1]
        hit = (sizes > 0).sum().astype(jnp.int32)
        here, back = here.reshape(T, k), _where_sorted(order).reshape(T, k)
        order = jnp.pad(order, (0, passes * C - n))

    def one(carry):
        lo, y = carry
        with jax.named_scope("moe.dispatch"):
            rows = x[jax.lax.dynamic_slice(order, (lo,), (C,)) // k]  # [C, D], sorted by expert
            group_sizes = jnp.clip(ends - lo, 0, C) - jnp.clip(starts - lo, 0, C)
        with jax.named_scope("moe.experts"):
            gate = None if wg is None else grouped_matmul(rows, wg, group_sizes)
            up = grouped_matmul(rows, wu, group_sizes)
        hidden = act(up) if gate is None else act(gate) * up
        with jax.named_scope("moe.experts"):
            out = grouped_matmul(hidden, wd, group_sizes)
        with jax.named_scope("moe.combine"):
            # A (token, choice) whose row this pass computed takes it; rows
            # past the held assignments were never written and are left out.
            mine = here & (back >= lo) & (back < lo + C)
            return lo + C, y + _back_to_tokens(out, jnp.clip(back - lo, 0, C - 1), weights, mine)

    _, y = jax.lax.while_loop(lambda carry: carry[0] < n_here, one, (jnp.zeros((), jnp.int32), jnp.zeros((T, D), jnp.float32)))
    return y.astype(x.dtype), hit

