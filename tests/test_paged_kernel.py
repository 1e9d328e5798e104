"""Paged attention (ragged, interleaved-KV layout) vs the library's
pure-JAX reference implementation — the authoritative oracle for the
TPU kernel's semantics, run eagerly with concrete values."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubeai_tpu.ops.paged_attention import paged_attention_ragged


def _ref(q_flat, kv_pages, kv_lens, table, cu, n, scale, softcap, k_scale=None, v_scale=None):
    # The library kernel ships with TPU-enabled jax builds only; a
    # CPU-only jax has no oracle to compare against — skip rather than
    # fail.
    pytest.importorskip("jax.experimental.pallas.ops.tpu.ragged_paged_attention")
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention.kernel import (
        ref_ragged_paged_attention,
    )

    return ref_ragged_paged_attention(
        q_flat, kv_pages, kv_lens, table, cu, n,
        sm_scale=scale, soft_cap=softcap, k_scale=k_scale, v_scale=v_scale,
    )


@pytest.mark.parametrize(
    "B,S,H,Kv,lens,softcap,k_scale,v_scale,pool_dtype",
    [
        (2, 1, 8, 2, [17, 42], None, None, None, jnp.float32),      # plain decode
        (2, 4, 8, 2, [19, 45], None, None, None, jnp.float32),      # a few rows a slot
        (1, 16, 4, 4, [16], None, None, None, jnp.float32),         # prefill-sized query block
        (2, 2, 4, 2, [30, 61], 30.0, None, None, jnp.float32),      # softcap
        (3, 1, 16, 2, [1, 33, 64], None, None, None, jnp.float32),  # extreme lengths
        # The dequantising arm (x.astype(f32) * scale -> q.dtype).
        (2, 1, 4, 2, [17, 42], None, 0.03, 0.05, jnp.float32),
        (2, 4, 8, 2, [19, 45], None, 0.03, 0.05, jnp.float32),
        (2, 1, 4, 2, [17, 42], 25.0, 0.03, 0.05, jnp.float32),
        (3, 1, 16, 2, [1, 33, 64], 30.0, None, None, jnp.float32),
        (2, 1, 8, 2, [17, 42], None, 0.03, 0.05, jnp.int8),         # an 8-bit pool
    ],
)
def test_wrapper_matches_library_reference(B, S, H, Kv, lens, softcap, k_scale, v_scale, pool_dtype):
    h, P, ps, mp = 128, 1 + 3 * 4, 16, 4
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, S, H, h)), jnp.float32)
    if pool_dtype == jnp.int8:
        kv_pages = jnp.asarray(rng.integers(-127, 128, (P, ps, 2 * Kv, h)), jnp.int8)
    else:
        kv_pages = jnp.asarray(rng.standard_normal((P, ps, 2 * Kv, h)), pool_dtype)
    table = jnp.asarray(
        rng.choice(np.arange(1, P), size=(B, mp), replace=False).astype(np.int32)
    )
    kv_lens = jnp.asarray(lens, jnp.int32)
    scale = h**-0.5

    got = paged_attention_ragged(
        q, kv_pages, table, kv_lens, softcap=softcap or 0.0,
        k_scale=k_scale, v_scale=v_scale,
    )
    want = _ref(
        q.reshape(B * S, H, h), kv_pages, kv_lens, table,
        jnp.arange(B + 1, dtype=jnp.int32) * S, jnp.asarray([B], jnp.int32),
        scale, softcap, k_scale, v_scale,
    ).reshape(B, S, H, h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_tpu_dispatch_arm_builds_identical_call(monkeypatch):
    """The TPU arm must invoke the library kernel with EXACTLY the
    arguments the (tested) CPU twin receives: stub the kernel import and
    a non-cpu backend, record the call, and replay it through the twin."""
    import kubeai_tpu.ops.paged_attention as pa

    recorded = {}

    def fake_kernel(q_flat, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs, *, sm_scale, soft_cap=None, k_scale=None, v_scale=None, num_kv_pages_per_block=None, num_queries_per_block=None, vmem_limit_bytes=None):
        recorded.update(
            q=q_flat, pages=kv_pages, lens=kv_lens, table=page_indices,
            cu=cu_q_lens, n=num_seqs, scale=sm_scale, cap=soft_cap,
            k_scale=k_scale, v_scale=v_scale,
            blk=(num_kv_pages_per_block, num_queries_per_block),
            vmem=vmem_limit_bytes,
        )
        return pa._cpu_twin(
            q_flat, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs,
            sm_scale=sm_scale, soft_cap=soft_cap,
            k_scale=k_scale, v_scale=v_scale,
        )

    lib = pytest.importorskip("jax.experimental.pallas.ops.tpu.ragged_paged_attention")

    monkeypatch.setattr(lib, "ragged_paged_attention", fake_kernel)
    monkeypatch.setattr(pa.jax, "default_backend", lambda: "tpu")

    B, S, H, Kv, h, P, ps, mp = 2, 3, 4, 2, 128, 9, 16, 4
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((B, S, H, h)), jnp.float32)
    kv_pages = jnp.asarray(rng.standard_normal((P, ps, 2 * Kv, h)), jnp.float32)
    table = jnp.asarray(np.arange(1, 1 + B * mp, dtype=np.int32).reshape(B, mp))
    kv_lens = jnp.asarray([10, 30], jnp.int32)

    # The pair the wrapper chose from the call's shapes must flow through
    # (and not shadow the query tensor — a r5 review catch).
    got = pa.paged_attention_ragged(q, kv_pages, table, kv_lens, softcap=25.0)
    assert recorded["blk"] == pa.kernel_blocks(S, H // Kv, mp, ps)

    assert recorded["q"].shape == (B * S, H, h)
    np.testing.assert_array_equal(np.asarray(recorded["cu"]), np.arange(B + 1) * S)
    np.testing.assert_array_equal(np.asarray(recorded["lens"]), [10, 30])
    np.testing.assert_array_equal(np.asarray(recorded["n"]), [B])
    # The raised scoped-VMEM budget must reach the kernel (8B-class heads
    # exceed the 16MB default during prefill).
    assert recorded["vmem"] == 96 * 1024 * 1024
    assert recorded["scale"] == pytest.approx(h**-0.5)
    assert recorded["cap"] == 25.0

    # And the backend-dispatched result equals the plain CPU-arm result.
    monkeypatch.setattr(pa.jax, "default_backend", lambda: "cpu")
    want = pa.paged_attention_ragged(q, kv_pages, table, kv_lens, softcap=25.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


# (heads, kv heads, slots, query rows a slot, max_len, pool dtype) at the
# benchmark's page of 64 and head_dim 128.
_BLOCK_CASES = {
    "qwen2.5-7b/decode": (28, 4, 32, 1, 2048, jnp.bfloat16),
    "mistral-7b/decode": (32, 8, 8, 1, 8192, jnp.bfloat16),
    "qwen2.5-7b/verify-S3": (28, 4, 32, 3, 2048, jnp.bfloat16),
    "qwen2.5-7b/prefill-512": (28, 4, 1, 512, 2048, jnp.bfloat16),
    "mistral-7b/chunk-1024": (32, 8, 1, 1024, 8192, jnp.bfloat16),
    "qwen2.5-7b/decode-fp8-pool": (28, 4, 32, 1, 2048, jnp.float8_e4m3fn),
    "qwen2.5-7b/tp4/decode": (7, 1, 32, 1, 2048, jnp.bfloat16),
    # The wide chunk (engine/core.py::prefill_plan): 2048 rows of one slot.
    "mistral-7b/chunk-2048": (32, 8, 1, 2048, 8192, jnp.bfloat16),
    "smallthinker/chunk-2048": (28, 4, 1, 2048, 16384, jnp.bfloat16),
    "trinity/chunk-2048": (32, 4, 1, 2048, 32768, jnp.bfloat16),
    "nemotron/chunk-2048": (32, 2, 1, 2048, 8192, jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(_BLOCK_CASES), ids=list(_BLOCK_CASES))
def test_kernel_blocks_are_chosen_from_the_calls_shapes(monkeypatch, case):
    """One query row a slot (and any call over a pool the chunk kernel
    does not read): the (kv pages, queries) pair the library kernel is
    given is kernel_blocks' choice from the call's own shapes and dtypes,
    inside what the kernel accepts, recorded for /debug/engine, and
    smaller than the library default it replaces (128 pages x 32
    queries, clipped to the table's width and the call's rows: a block
    of 32 slots scored against every slot's whole table). More rows a
    slot over a bf16 pool: the repo's own chunk kernel, its tiles from
    the call's shapes and on record likewise, and the library kernel is
    not called."""
    import kubeai_tpu.ops.paged_attention as pa
    from kubeai_tpu.ops import chunk_attention

    lib = pytest.importorskip("jax.experimental.pallas.ops.tpu.ragged_paged_attention")
    H, Kv, B, S, max_len, pool_dtype = _BLOCK_CASES[case]
    h, ps = 128, 64
    mp = max_len // ps
    seen = {}

    def fake_kernel(q_flat, *args, num_kv_pages_per_block=None, num_queries_per_block=None, **kw):
        seen["blk"] = (num_kv_pages_per_block, num_queries_per_block)
        return q_flat

    def fake_chunk_kernel(q, kv, table, lens, **kw):
        seen["chunk"] = kw
        return q

    monkeypatch.setattr(lib, "ragged_paged_attention", fake_kernel)
    monkeypatch.setattr(chunk_attention, "chunk_attention_kernel", fake_chunk_kernel)
    monkeypatch.setattr(pa.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pa, "chosen_blocks", {})
    monkeypatch.setattr(chunk_attention, "chosen_tiles", {})
    quant = {} if pool_dtype == jnp.bfloat16 else {"k_scale": 1.0, "v_scale": 1.0}
    args = (
        jax.ShapeDtypeStruct((B, S, H, h), jnp.bfloat16),
        jax.ShapeDtypeStruct((B * mp + 1, ps, 2 * Kv, h), pool_dtype),
        jax.ShapeDtypeStruct((B, mp), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
    )
    out = jax.eval_shape(
        lambda q, kv, tbl, lens: pa.paged_attention_ragged(q, kv, tbl, lens, **quant), *args
    )
    assert out.shape == (B, S, H, h)
    if S > 1:
        # The chunk kernel, with the scale and no window; nothing of the library's.
        assert "blk" not in seen and pa.chosen_blocks == {}
        assert seen["chunk"] == {"scale": h**-0.5, "softcap": 0.0, "sliding_window": None}
        tile, kv_block = chunk_attention.kernel_tiles(S, H // Kv, ps, mp)
        assert list(chunk_attention.chosen_tiles.values()) == [{"query_tile": tile, "kv_block": kv_block}]
        assert S % tile == 0 and kv_block == 256
        # 256 query rows a tile, 512 where four heads share a KV head (PERF.md section 6, PR 49).
        assert tile == min(S, {4: 512, 7: 256, 16: 256, 8: 256}[H // Kv])
    else:
        kv_pages, queries = seen["blk"]
        assert (kv_pages, queries) == pa.kernel_blocks(S, H // Kv, mp, ps) == (8, 1)
        # One entry a compiled call shape, the pair as the kernel got it.
        assert list(pa.chosen_blocks.values()) == [(kv_pages, queries)]
        # One slot a query block: no slot's rows are scored against
        # another slot's keys.
        assert kv_pages < min(mp, 128) and queries < min(B * S, 32)
        assert "chunk" not in seen and chunk_attention.chosen_tiles == {}
    # A sweep's pair goes to the library kernel as given, whatever S, and leaves no record.
    before = len(pa.chosen_blocks)
    jax.eval_shape(
        lambda q, kv, tbl, lens: pa.paged_attention_ragged(q, kv, tbl, lens, blocks=(2, 8), **quant),
        *args,
    )
    assert seen["blk"] == (2, 8)
    assert len(pa.chosen_blocks) == before


def test_wrapper_clamps_overrun_lengths():
    """kv_lengths past the table span (post-finish decode overrun) must
    clamp instead of reading out of bounds."""
    B, S, H, Kv, h, P, ps, mp = 1, 1, 4, 2, 128, 9, 16, 4
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((B, S, H, h)), jnp.float32)
    kv_pages = jnp.asarray(rng.standard_normal((P, ps, 2 * Kv, h)), jnp.float32)
    table = jnp.asarray(np.arange(1, 5).reshape(1, mp).astype(np.int32))
    got = paged_attention_ragged(
        q, kv_pages, table, jnp.asarray([mp * ps + 7], jnp.int32)
    )
    want = paged_attention_ragged(
        q, kv_pages, table, jnp.asarray([mp * ps], jnp.int32)
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_decode_step_paged_kernel_wiring():
    """llama decode with use_paged_kernel=True must match the gather path
    at one query row a slot and at several — validates the
    kv_lengths=last_pos+1 and scale plumbing in apply()."""
    from kubeai_tpu.models import llama
    from kubeai_tpu.models.base import ModelConfig

    cfg = ModelConfig(
        vocab_size=256, hidden_size=256, intermediate_size=512,
        num_layers=2, num_heads=2, num_kv_heads=1, head_dim=128,
        dtype="float32", max_position=512,
    )
    params = llama.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(2)
    B, ps, mp = 2, 16, 4
    pool = llama.init_paged_cache(cfg, num_pages=1 + B * mp, page_size=ps)
    table = jnp.asarray(np.arange(1, 1 + B * mp, dtype=np.int32).reshape(B, mp))
    lengths = jnp.asarray([3, 7], jnp.int32)
    toks = jnp.asarray(rng.integers(1, 200, (B, 16)), jnp.int32)
    _, pool = llama.prefill_paged_cold(params, cfg, toks, pool, table, lengths)

    cfg_k = cfg.replace(use_paged_kernel=True)
    for S in (1, 3):
        step_tok = jnp.asarray(rng.integers(1, 200, (B, S)), jnp.int32)
        pos = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        ref_logits, _ = llama.apply(
            params, cfg, step_tok, pos, {k: v.copy() for k, v in pool.items()},
            page_table=table,
        )
        kern_logits, _ = llama.apply(
            params, cfg_k, step_tok, pos, {k: v.copy() for k, v in pool.items()},
            page_table=table,
        )
        np.testing.assert_allclose(
            np.asarray(kern_logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
        )
