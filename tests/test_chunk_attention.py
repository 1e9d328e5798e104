"""The chunk kernel (`ops/chunk_attention.py`) in Pallas interpret mode
on the CPU against the twin of the library's reference
(`ops/paged_attention.py::_cpu_twin`), at the serving tiles, and its
statement of what it walks against a count of the blocks whose mask is
not all false."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubeai_tpu.ops import chunk_attention as ca
from kubeai_tpu.ops import paged_attention as pa

PAGE, H_DIM = 64, 128

# (window, first position, rows): every pair of a window and a depth the
# cells' calls take, all twelve at G = 7 and four at G = 4 and at G = 8
# (a case is 5 s of interpreting); 100 is not page-aligned.
_GEOMETRY = [
    (None, 0, 2048), (None, 100, 512), (None, 4096, 1024), (None, 12288, 512),
    (2048, 0, 1024), (2048, 100, 2048), (2048, 4096, 512), (2048, 12288, 512),
    (4096, 0, 512), (4096, 100, 1024), (4096, 4096, 2048), (4096, 12288, 1024),
]
_CASES = (
    [(7, *geometry, 0.0) for geometry in _GEOMETRY]
    + [(4, *geometry, 0.0) for geometry in _GEOMETRY[0::4] + _GEOMETRY[7:8]]
    + [(8, *geometry, 0.0) for geometry in _GEOMETRY[1::4] + _GEOMETRY[11:12]]
    + [(7, 4096, 4196, 512, 30.0)]
)


def _call(G, window, first, S, softcap, seed=0):
    """Two slots of one KV head: the first *first* keys deep, the second
    at another depth, off a page's edge; a pool of random pages, the
    table a permutation."""
    rng = np.random.default_rng(seed)
    lens = np.array([first + S, first // 2 + 37 + S], np.int32)
    max_pages = -(-int(lens.max()) // PAGE) + 1
    pool = jnp.asarray(rng.standard_normal((2 * max_pages + 1, PAGE, 2, H_DIM)), jnp.bfloat16)
    table = jnp.asarray(1 + rng.permutation(2 * max_pages).reshape(2, max_pages), jnp.int32)
    q = jnp.asarray(rng.standard_normal((2, S, G, H_DIM)), jnp.bfloat16)
    return q, pool, table, jnp.asarray(lens)


@pytest.mark.parametrize(
    "G,window,first,S,softcap", _CASES, ids=[f"G{c[0]}-window{c[1]}-behind{c[2]}-rows{c[3]}" + ("-softcap" * bool(c[4])) for c in _CASES],
)
def test_kernel_matches_the_library_reference(G, window, first, S, softcap):
    q, pool, table, lens = _call(G, window, first, S, softcap)
    scale = H_DIM**-0.5
    got = ca.chunk_attention_kernel(q, pool, table, lens, scale=scale, softcap=softcap, sliding_window=window, interpret=True)
    for b in range(2):
        # The reference a slot at a time, and behind a window over the
        # columns from the first page the slot's first row can see, lengths
        # shifted, as a model hands them to the library's kernel: it scores
        # every key it is given. The kernel under test has the whole table.
        first_page = max(int(lens[b]) - S - window + 1, 0) // PAGE if window else 0
        want = pa._cpu_twin(
            q[b], pool, lens[b:b + 1] - first_page * PAGE, table[b:b + 1, first_page:], jnp.asarray([0, S], jnp.int32),
            jnp.asarray([1], jnp.int32), sm_scale=scale, soft_cap=softcap or None, sliding_window=window,
        )
        # bf16 outputs of averages of unit normals: one rounding apart.
        np.testing.assert_allclose(np.asarray(got[b], np.float32), np.asarray(want, np.float32), atol=2e-2, rtol=2e-2)


def test_kernel_is_silent_about_what_lies_past_a_slots_keys():
    """A page past the slot's length may hold anything, a nan too: the
    last block's keys past the length are zeroed before they are scored."""
    q, pool, table, lens = _call(4, None, 100, 256, 0.0)
    poisoned = pool.at[table[0, (100 + 256) // PAGE + 1]].set(jnp.nan)
    args = dict(scale=H_DIM**-0.5, interpret=True)
    got = ca.chunk_attention_kernel(q[:1], poisoned, table[:1], lens[:1], **args)
    want = ca.chunk_attention_kernel(q[:1], pool, table[:1], lens[:1], **args)
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


_WALKS = [
    (rows, first, window, tile, kv_block)
    for rows, tile in ((2048, 256), (1024, 256), (512, 128), (32, 32))
    for first in (0, 100, 2048, 4096, 8192, 12288)
    for window in (None, 2048, 4096)
    for kv_block in (256, 512)
]


@pytest.mark.parametrize("rows,first,window,tile,kv_block", _WALKS)
def test_the_walk_is_the_blocks_whose_mask_is_not_all_false(rows, first, window, tile, kv_block):
    """`tile_walk` against the masks themselves: from the page of the
    first key a tile's first row sees, every block of *kv_block* keys in
    which some row of the tile sees some key, and no other."""
    walked = 0
    for t in range(0, rows, tile):
        pos = first + t + np.arange(tile)[:, None]
        origin, n = ca.tile_walk(first + t, tile, window, kv_block, PAGE)
        assert origin % PAGE == 0 and origin <= max(first + t - (window or 1 << 30) + 1, 0) < origin + PAGE
        keys = origin + np.arange((first + rows) // kv_block * kv_block + 2 * kv_block)[None, :]
        seen = keys <= pos
        if window:
            seen &= keys > pos - window
        live = seen.reshape(tile, -1, kv_block).any(axis=(0, 2))
        assert live[:n].all() and not live[n:].any() and not (np.arange(origin)[None, :] > pos - (window or 1 << 30)).any()
        walked += tile * kv_block * int(live.sum())
    assert ca.pairs_walked(rows, first, window, tile, kv_block, PAGE) == walked
    # Never fewer than the mask keeps, never the whole rectangle behind 4096 keys.
    inside = sum(min(first + i + 1, window or 1 << 30) for i in range(rows))
    assert inside <= walked
    if first >= 4096 and window and tile == 256:
        assert walked < rows * (first + rows) and inside / walked >= (0.85 if window == 4096 else 0.8)


def test_serving_tiles_come_from_the_calls_shapes():
    assert [ca.kernel_tiles(2048, G, PAGE, 256) for G in (1, 4, 7, 8, 16)] == [(512, 256), (512, 256), (256, 256), (256, 256), (256, 256)]
    assert ca.kernel_tiles(32, 7, PAGE, 97) == (32, ca.KV_BLOCK_TOKENS)
    assert ca.kernel_tiles(1024, 7, 16, 4) == (256, 64)  # a table narrower than a block


@pytest.mark.parametrize(
    "case,reads",
    [("bf16", True), ("fp8-pool", False), ("f32", False), ("narrow-heads", False), ("narrow-table", False)],
)
def test_pools_the_kernel_does_not_read_stay_on_the_library_kernel(monkeypatch, case, reads):
    """On the chip a call of more than one row a slot takes the chunk
    kernel for a bf16 pool of 128-wide heads and the library kernel for
    every other: a quantized pool (its pages want dequantizing in VMEM),
    a float32 one, heads or a table narrower than a lane tile. One row a
    slot is the library kernel's whatever the pool."""
    lib = pytest.importorskip("jax.experimental.pallas.ops.tpu.ragged_paged_attention")
    went = []
    monkeypatch.setattr(lib, "ragged_paged_attention", lambda q_flat, *a, **kw: went.append("library") or q_flat)
    monkeypatch.setattr(ca, "chunk_attention_kernel", lambda q, *a, **kw: went.append("chunk") or q)
    monkeypatch.setattr(pa.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ca, "chosen_tiles", {})
    h = 64 if case == "narrow-heads" else H_DIM
    page, max_pages = (16, 4) if case == "narrow-table" else (PAGE, 32)
    dtype = jnp.float32 if case == "f32" else jnp.bfloat16
    pool_dtype = jnp.float8_e4m3fn if case == "fp8-pool" else dtype
    quant = {"k_scale": 1.0, "v_scale": 1.0} if case == "fp8-pool" else {}
    shapes = lambda S: (  # noqa: E731
        jax.ShapeDtypeStruct((2, S, 28, h), dtype), jax.ShapeDtypeStruct((2 * max_pages + 1, page, 8, h), pool_dtype),
        jax.ShapeDtypeStruct((2, max_pages), jnp.int32), jax.ShapeDtypeStruct((2,), jnp.int32),
    )
    call = lambda q, kv, table, lens: pa.paged_attention_ragged(q, kv, table, lens, sliding_window=4096, **quant)  # noqa: E731
    assert jax.eval_shape(call, *shapes(512)).shape == (2, 512, 28, h)
    assert went == (["chunk"] if reads else ["library"])
    # What it was given is on record for /debug/engine, once a call shape.
    assert list(ca.chosen_tiles.values()) == ([{"query_tile": 256, "kv_block": 256}] if reads else [])
    jax.eval_shape(call, *shapes(1))
    assert went[1:] == ["library"]
    # A sweep's blocks are the library kernel's, whatever S.
    jax.eval_shape(lambda q, kv, table, lens: pa.paged_attention_ragged(q, kv, table, lens, blocks=(8, 32), **quant), *shapes(512))
    assert went[2:] == ["library"]
