"""The main path's Pallas kernels, compiled for a described TPU v5e at
real widths: nothing runs and no chip is needed, but what the chip's
compiler would refuse (a block shape off the tiling, too much scoped
VMEM, a kernel GSPMD cannot partition) is refused here, at no chip time
(on-chip-measurement guide, section 2). Interpret-mode tests cannot see
any of these. A compile that passes is not a chip run.

The program asks jax for its backend and, inside the library kernel,
for the device kind; both still see the CPU here, so the test steers
them (never an option of the program).
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from kubeai_tpu.models import llama
from kubeai_tpu.models.base import LiveRows, ModelConfig
from kubeai_tpu.ops.flash_attention import flash_attention_tpu
from kubeai_tpu.ops.paged_attention import paged_attention_ragged

# (num_heads, num_kv_heads) at head_dim 128.
QWEN25_7B = (28, 4)
LLAMA3_8B = (32, 8)  # Mistral-7B has the same grouping
GEMMA_2B = (8, 1)
NEMOTRON_ATTN = (32, 2)  # the one attention block of nemotron3-super's cut: G = 16
# What one of four tp shards sees of the two 7B/8B models.
QWEN25_7B_TP4 = (7, 1)
LLAMA3_8B_TP4 = (8, 2)
H_DIM, PAGE, SLOTS = 128, 64, 32


@pytest.fixture(scope="module")
def v5e():
    """The four described devices of a v5e 2x2."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    return topo.devices


@pytest.fixture(autouse=True)
def for_the_chip(monkeypatch):
    """Take the TPU branch of the kernel dispatchers, resolve the library
    kernel's tuned block sizes as on a v5e, trace at the serving
    processes' matmul precision (conftest's "highest" is for CPU
    numerics; Mosaic refuses an f32 x bf16 matmul at fp32 precision),
    and keep these compiles out of the persistent cache (a compile for a
    described device is written there but cannot be read back without a
    chip)."""
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import (
        tuned_block_sizes,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(tuned_block_sizes, "get_tpu_version", lambda: 5)
    monkeypatch.setattr(
        tuned_block_sizes, "get_device_name", lambda num_devices=None: "TPU v5"
    )
    precision = jax.config.jax_default_matmul_precision
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_default_matmul_precision", None)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_default_matmul_precision", precision)
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    """Lower + compile for the described device; the text of the
    compiled program."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _paged_args(device, B, S, heads, max_len=2048, pool_dtype=jnp.bfloat16):
    H, Kv = heads
    max_pages = max_len // PAGE
    one = SingleDeviceSharding(device)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    return (
        sds((B, S, H, H_DIM), jnp.bfloat16),
        sds((SLOTS * max_pages + 1, PAGE, 2 * Kv, H_DIM), pool_dtype),
        sds((B, max_pages), jnp.int32),
        sds((B,), jnp.int32),
    )


@pytest.mark.parametrize(
    "heads",
    [QWEN25_7B, LLAMA3_8B, QWEN25_7B_TP4, LLAMA3_8B_TP4],
    ids=["qwen2.5-7b", "llama3-8b", "qwen2.5-7b/tp4", "llama3-8b/tp4"],
)
def test_flash_prefill_lowers(v5e, heads):
    H, Kv = heads
    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((1, 1024, H, H_DIM), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 1024, Kv, H_DIM), jnp.bfloat16, sharding=one)
    text = _compile(lambda q, k, v: flash_attention_tpu(q, k, v, causal=True), q, kv, kv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "heads,B,S,pool_dtype,max_len",
    [
        (QWEN25_7B, SLOTS, 1, jnp.bfloat16, 2048),
        (QWEN25_7B, 8, 512, jnp.bfloat16, 2048),
        (QWEN25_7B, SLOTS, 1, jnp.float8_e4m3fn, 2048),
        (LLAMA3_8B, SLOTS, 1, jnp.bfloat16, 2048),
        (LLAMA3_8B, 8, 512, jnp.bfloat16, 2048),
        (QWEN25_7B_TP4, SLOTS, 1, jnp.bfloat16, 2048),
        (QWEN25_7B_TP4, 8, 512, jnp.bfloat16, 2048),
        (LLAMA3_8B_TP4, SLOTS, 1, jnp.bfloat16, 2048),
        (GEMMA_2B, SLOTS, 1, jnp.bfloat16, 2048),
        # The benchmark's Mistral cell (mistral7b-int8-docqa): 8 slots of
        # 128 pages; a 1024-row chunk of a document behind its prefix.
        (LLAMA3_8B, 8, 1, jnp.bfloat16, 8192),
        (LLAMA3_8B, 1, 1024, jnp.bfloat16, 8192),
        # The wide chunk (engine/core.py::prefill_plan): 2048 rows of one
        # slot, at G = 4 and at agent-sat's G = 16 behind 128 pages.
        (LLAMA3_8B, 1, 2048, jnp.bfloat16, 8192),
        (NEMOTRON_ATTN, 1, 2048, jnp.bfloat16, 8192),
    ],
    ids=[
        "qwen2.5-7b/decode", "qwen2.5-7b/prefill-8x512", "qwen2.5-7b/decode-fp8-pool",
        "llama3-8b/decode", "llama3-8b/prefill-8x512",
        "qwen2.5-7b/tp4/decode", "qwen2.5-7b/tp4/prefill-8x512",
        "llama3-8b/tp4/decode", "gemma-2b/decode",
        "mistral-7b/decode-8x8192", "mistral-7b/chunk-1024-of-8192",
        "mistral-7b/chunk-2048-of-8192", "nemotron/chunk-2048-of-8192",
    ],
)
def test_ragged_paged_kernel_lowers(v5e, heads, B, S, pool_dtype, max_len):
    quant = {} if pool_dtype == jnp.bfloat16 else {"k_scale": 1.0, "v_scale": 1.0}
    text = _compile(
        lambda q, kv, tbl, lens: paged_attention_ragged(q, kv, tbl, lens, **quant),
        *_paged_args(v5e[0], B, S, heads, max_len=max_len, pool_dtype=pool_dtype),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "heads,B,max_len,window",
    [(QWEN25_7B, SLOTS, 2048, None), (LLAMA3_8B, 8, 8192, None), (QWEN25_7B_TP4, SLOTS, 2048, None), (QWEN25_7B, 24, 16384, 4096)],
    ids=["qwen2.5-7b/decode", "mistral-7b/decode-8x8192", "qwen2.5-7b/tp4/decode", "smallthinker/decode-window"],
)
def test_ragged_paged_kernel_lowers_with_the_live_rows_count_as_an_operand(v5e, heads, B, max_len, window):
    """Decode as the engine calls it since it puts live rows first: the
    count is a traced scalar (the kernel's `num_seqs`), the rows past it
    are masked to zeros behind the kernel."""
    text = _compile(
        lambda q, kv, tbl, lens, n: paged_attention_ragged(q, kv, tbl, lens, sliding_window=window, live_rows=n),
        *_paged_args(v5e[0], B, 1, heads, max_len=max_len), _sds(v5e, (), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_ragged_paged_kernel_fits_vmem_at_8192(v5e):
    """deploy/models/llama-3.1-8b-instruct-tpu.yaml serves at
    --max-seq-len 8192 (128 pages a sequence). At the library's untuned
    128-page KV block the double buffer alone took 65.5 MB of scoped
    VMEM and the compile 12-15 s (one DMA unrolled per page); at the
    8 pages the wrapper passes it takes 4 MB and under a second."""
    text = _compile(
        lambda q, kv, tbl, lens: paged_attention_ragged(q, kv, tbl, lens),
        *_paged_args(v5e[0], SLOTS, 1, LLAMA3_8B, max_len=8192),
    )
    assert "tpu_custom_call" in text


@pytest.mark.xfail(
    strict=True, raises=ValueError,
    reason="library kernel (jax 0.9.0): 'Not implemented: "
           "num_combined_kv_heads=2 can not be XLA fully tiled.' — an 8-bit "
           "pool packs 4 heads a sublane, so one KV head (gemma-2b, or a "
           "Kv=4 model at tp=4) cannot take --kv-cache-dtype fp8|int8 on the "
           "kernel route (ROADMAP A5)",
)
def test_ragged_paged_kernel_quantized_pool_single_kv_head(v5e):
    _compile(
        lambda q, kv, tbl, lens: paged_attention_ragged(
            q, kv, tbl, lens, k_scale=1.0, v_scale=1.0
        ),
        *_paged_args(v5e[0], SLOTS, 1, GEMMA_2B, pool_dtype=jnp.float8_e4m3fn),
    )


def test_tp4_decode_step_keeps_the_kernel(v5e):
    """Tensor parallelism: GSPMD cannot partition a Mosaic kernel, so
    llama.apply runs it per tp shard under shard_map. One decode layer
    of Qwen2.5-7B on the 2x2 mesh must compile, with the kernel in it
    and the pool still split four ways on its head axis."""
    mesh = Mesh(np.array(v5e).reshape(1, 1, 1, 4), ("dp", "sp", "ep", "tp"))
    from kubeai_tpu.engine.coldstart import param_shapes
    from kubeai_tpu.parallel.sharding import llama_param_specs, paged_cache_specs

    H, Kv = QWEN25_7B
    mc = ModelConfig(
        vocab_size=1024, hidden_size=H * H_DIM, intermediate_size=2048,
        num_layers=1, num_heads=H, num_kv_heads=Kv, qkv_bias=True,
        dtype="bfloat16", use_flash_prefill=True, use_paged_kernel=True,
    )

    def on_mesh(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

    params = jax.tree_util.tree_map(
        lambda x, s: on_mesh(x.shape, x.dtype, s),
        param_shapes(mc), llama_param_specs(mc),
    )
    B, max_pages = 8, 2048 // PAGE
    pool_spec = paged_cache_specs()["kv"]
    pool = {"kv": on_mesh((B * max_pages + 1, PAGE, 2 * Kv, H_DIM), jnp.bfloat16, pool_spec)}
    # As the decode program calls it: rows live slots first, their count
    # one more replicated operand of the kernel's shard_map.
    compiled = jax.jit(
        lambda p, t, c, tbl, lens, active: llama.decode_step_paged(
            p, mc, t, c, tbl, lens, tp_mesh=mesh, live=LiveRows.first(active)
        ),
        out_shardings=(NamedSharding(mesh, P()), {"kv": NamedSharding(mesh, pool_spec)}),
    ).lower(
        params, on_mesh((B, 1), jnp.int32), pool,
        on_mesh((B, max_pages), jnp.int32), on_mesh((B,), jnp.int32), on_mesh((B,), jnp.bool_),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # A quarter of the pool on each chip, not the whole of it.
    pool_bytes = np.prod(pool["kv"].shape) * 2
    assert compiled.memory_analysis().output_size_in_bytes < pool_bytes / 2


# -- kanana-2 (deepseek_v3): the kernels of its step at the published widths --

KANANA_H, KANANA_W, KANANA_RANK = 32, 640, 512  # 32 heads; latent 512 + rope 64, stored at 640


def _sds(v5e, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=SingleDeviceSharding(v5e[0]))


@pytest.mark.parametrize("max_len", [4096, 8192])
@pytest.mark.parametrize("B", [64, 128])
def test_mla_paged_decode_kernel_lowers(v5e, B, max_len):
    """One query row a slot over latent pages, at the cell's 64 slots x
    4096 tokens and at a deployment's 128 slots and 8192: the ring of
    page buffers (three blocks of 16 pages) has to fit scoped VMEM and
    the copies carried from one program to the next have to lower."""
    from kubeai_tpu.ops.mla_attention import mla_paged_decode

    max_pages = max_len // PAGE  # the pool's rows are no part of the kernel's shapes: the cell's pool for all four
    text = _compile(
        lambda q, pool, table, lens: mla_paged_decode(q, pool, table, lens, scale=192**-0.5, rank=KANANA_RANK),
        _sds(v5e, (B, KANANA_H, KANANA_W), jnp.bfloat16),
        _sds(v5e, (8 * (64 * 64 + 1), PAGE, KANANA_W), jnp.bfloat16),
        _sds(v5e, (B, max_pages), jnp.int32),
        _sds(v5e, (B,), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("B,S", [(1, 256), (8, 1024), (1, 2048)])
def test_latent_prefill_attention_lowers(v5e, B, S):
    """Every prefill's attention: 32 query heads of 640 over latent pages,
    in blocks of keys (XLA; no kernel). `[1, 2048]` is the wide chunk,
    which a prompt of 1025 tokens and more runs as where warm-up measures
    a read of the weights dearer than the padding's rows
    (engine/core.py::prefill_plan); the family shares no chunk call
    (core.pair_rows), so there is no `[2, 2048]`."""
    from kubeai_tpu.ops.mla_attention import latent_attention_paged

    max_pages = 4096 // PAGE
    text = _compile(
        lambda q, pool, table, pos: latent_attention_paged(q, pool, table, pos, scale=192**-0.5, rank=KANANA_RANK),
        _sds(v5e, (B, S, KANANA_H, KANANA_W), jnp.bfloat16),
        _sds(v5e, (8 * (64 * max_pages + 1), PAGE, KANANA_W), jnp.bfloat16),
        _sds(v5e, (B, max_pages), jnp.int32),
        _sds(v5e, (B, S), jnp.int32),
    )
    assert "while" in text and "tpu_custom_call" not in text


@pytest.mark.parametrize("k,n", [(2048, 768), (768, 2048)], ids=["gate_up", "down"])
@pytest.mark.parametrize("rows", [384, 768, 192, 6 * 1024, 8 * 1024 * 6, 60, 600])
def test_grouped_expert_matmul_lowers(v5e, rows, k, n):
    """The decode step's 64 x 6 and 128 x 6 assignments, a one-row prefill
    call's at the smallest and the largest bucket and a full prefill
    group's, over the 7 x 128 experts of the whole stack (one layer's
    groups hold the rows), with the tiles `gmm_tiles` chooses: 192 rows,
    and for 10 and 100 slots the whole 60 rows and 120 (every kind of row
    tile the rule can return)."""
    from kubeai_tpu.ops.moe import grouped_matmul

    text = _compile(
        grouped_matmul,
        _sds(v5e, (rows, k), jnp.bfloat16), _sds(v5e, (7 * 128, k, n), jnp.bfloat16), _sds(v5e, (7 * 128,), jnp.int32),
    )
    assert "tpu_custom_call" in text


# -- SmallThinker-21BA3B: the kernels of its step at the published widths ------

SMALLTHINKER = (28, 4)  # 28 query / 4 KV heads of 128 (G = 7, as Qwen2.5-7B)
SWA_WINDOW, SWA_SLOTS, SWA_MAX_LEN = 4096, 24, 16384


@pytest.mark.parametrize(
    "B,S,columns,window",
    [
        (SWA_SLOTS, 1, SWA_MAX_LEN // PAGE, None),
        (SWA_SLOTS, 1, (SWA_WINDOW - 1) // PAGE + 2, SWA_WINDOW),
        (1, 1024, SWA_MAX_LEN // PAGE, None),
        (1, 1024, (1024 + SWA_WINDOW - 2) // PAGE + 2, SWA_WINDOW),
        (8, 32, (32 + SWA_WINDOW - 2) // PAGE + 2, SWA_WINDOW),
        (1, 2048, SWA_MAX_LEN // PAGE, None),
        (1, 2048, (2048 + SWA_WINDOW - 2) // PAGE + 2, SWA_WINDOW),
    ],
    ids=[
        "decode/full-256-pages", "decode/window-65-pages", "chunk-1024/full", "chunk-1024/window-81-pages", "cold-8x32/window",
        "chunk-2048/full", "chunk-2048/window-97-pages",
    ],
)
def test_ragged_paged_kernel_lowers_with_a_window_over_a_shifted_table(v5e, B, S, columns, window):
    """The cell smallthinker-bf16-longdoc-sat: 24 slots of 256 pages; a
    full layer walks the slot's whole table, a window layer the columns
    from its first visible page (models/smallthinker.py) with the
    kernel's static `sliding_window`."""
    q, pool, _, lens = _paged_args(v5e[0], B, S, SMALLTHINKER, max_len=SWA_MAX_LEN)
    table = _sds(v5e, (B, columns), jnp.int32)
    text = _compile(
        lambda q, kv, tbl, lens: paged_attention_ragged(q, kv, tbl, lens, sliding_window=window), q, pool, table, lens,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k,n", [(2560, 768), (768, 2560)], ids=["gate_up", "down"])
@pytest.mark.parametrize("rows", [24 * 6, 6 * 1024, 8 * 1024 * 6, 6 * 32, 6 * 2048])
def test_grouped_expert_matmul_lowers_at_smallthinkers_widths(v5e, rows, k, n):
    """24 slots x 6 choices at decode (144 rows: one tile), a chunk's 6144,
    a full group's 49152 and the smallest bucket's 192, over the 12 x 64
    experts of the whole stack; an expert's 2560 x 768 matrix is one tile
    (3.75 MiB in bf16)."""
    from kubeai_tpu.ops.moe import gmm_tiles, grouped_matmul

    assert gmm_tiles(rows, k, n)[1:] == (k, n)
    text = _compile(
        grouped_matmul,
        _sds(v5e, (rows, k), jnp.bfloat16), _sds(v5e, (12 * 64, k, n), jnp.bfloat16), _sds(v5e, (12 * 64,), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_one_period_of_smallthinker_decodes_through_both_pools(v5e):
    """A decode step of one period (a full layer and three window layers)
    at the published widths: the step holds both kinds' paged kernels
    and the grouped matmuls, and both pools come back in place."""
    from kubeai_tpu.engine.coldstart import param_shapes
    from kubeai_tpu.models import smallthinker

    mc = ModelConfig(
        model_type="smallthinker", vocab_size=1024, hidden_size=2560, intermediate_size=0, num_layers=4,
        num_heads=28, num_kv_heads=4, head_dim=128, dtype="bfloat16", num_experts_per_tok=6, n_routed_experts=64,
        moe_intermediate_size=768, sliding_window_size=SWA_WINDOW, sliding_window_layout=(0, 1, 1, 1),
        rope_layout=(0, 1, 1, 1), rope_theta=1.5e6, use_flash_prefill=True, use_paged_kernel=True,
    )
    B, max_pages = SWA_SLOTS, SWA_MAX_LEN // PAGE
    params = jax.tree.map(lambda a: _sds(v5e, a.shape, a.dtype), param_shapes(mc))
    pools = jax.tree.map(
        lambda a: _sds(v5e, a.shape, a.dtype),
        jax.eval_shape(lambda: smallthinker.init_paged_cache(mc, 3841, PAGE, window_pages=B * 81 + 1)),
    )
    compiled = jax.jit(
        lambda p, t, c, tbl, lens, active: smallthinker.decode_step_paged(
            p, mc, t, c, tbl, lens, live=LiveRows.first(active)
        ),
        donate_argnums=(2,),
    ).lower(
        params, _sds(v5e, (B, 1), jnp.int32), pools, _sds(v5e, (B, 2 * max_pages), jnp.int32), _sds(v5e, (B,), jnp.int32),
        _sds(v5e, (B,), jnp.bool_),
    ).compile()
    # One period in the scan's body: 4 paged kernels and 4 x 3 grouped matmuls.
    assert compiled.as_text().count("tpu_custom_call") >= 16
    pool_bytes = sum(int(np.prod(a.shape)) * 2 for a in pools.values())
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes


# -- Nemotron-3-Super: the kernels of its step at the published widths -----------

NEMOTRON_SLOTS, NEMOTRON_MAX_LEN = 96, 8192


@pytest.mark.parametrize("k,n", [(1024, 2688), (2688, 1024)], ids=["up", "down"])
@pytest.mark.parametrize("rows", [704, 256, 7552, 60096, 15040])
def test_grouped_expert_matmul_lowers_at_nemotrons_latent_widths(v5e, rows, k, n):
    """A pass of the chip's share (`ops/moe.py::held_capacity`: 96 slots x
    22 choices, a quarter held, a third more; the smallest bucket's; a
    chunk's 1024 x 22; a full prefill group's; the wide chunk's 2048 x 22)
    over the 128 held experts of
    one block: an expert's 1024 x 2688 matrix is cut once (5.25 MiB in
    bf16) and every row tile the rule picks divides a pass."""
    from kubeai_tpu.ops.moe import gmm_tiles, grouped_matmul, held_capacity

    assert rows in {held_capacity(t * 22, 128, 512) for t in (96, 32, 1024, 8 * 1024, 2048)}
    tm, tk, tn = gmm_tiles(rows, k, n)
    assert rows % tm == 0 and tm % 8 == 0 and tk * tn <= 2 << 20
    text = _compile(
        grouped_matmul,
        _sds(v5e, (rows, k), jnp.bfloat16), _sds(v5e, (128, k, n), jnp.bfloat16), _sds(v5e, (128,), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("slots", [NEMOTRON_SLOTS, 1], ids=["the_cell", "the_logits_child"])
def test_the_state_space_step_kernel_lowers_in_place_at_the_published_widths(v5e, slots):
    """Block 3 of five over `slots` x 128 heads x 64 x 128 float32: a whole
    slot a program (4 MiB in and out, two buffers each: 16 MiB of VMEM,
    over the default scoped limit, so the call raises its own), and the
    donated stack comes back aliased with nothing of its size beside it."""
    from kubeai_tpu.ops import ssm

    H, Pd, N, G = 128, 64, 128, 8
    assert ssm.kernel_heads_per_tile(H, Pd, N, G) == H
    compiled = jax.jit(ssm.ssd_step_kernel, donate_argnums=(0,)).lower(
        _sds(v5e, (5, slots, H, Pd, N), jnp.float32), _sds(v5e, (), jnp.int32), _sds(v5e, (slots, H, Pd), jnp.bfloat16),
        _sds(v5e, (slots, H), jnp.float32), _sds(v5e, (H,), jnp.float32), _sds(v5e, (slots, G, N), jnp.bfloat16),
        _sds(v5e, (slots, G, N), jnp.bfloat16), _sds(v5e, (H,), jnp.float32),
    ).compile()
    assert "ssd_step_kernel" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 5 * slots * H * Pd * N * 4
    assert memory.temp_size_in_bytes < slots * H * Pd * N * 4 // 2


def test_a_mixer_an_attention_and_an_expert_block_of_nemotron_decode_in_place(v5e):
    """A decode step of `M*E` at the published widths with 96 slots of
    8192: the step holds the state-space step kernel, the paged kernel and
    the two grouped matmuls of the chip's share, and the pool AND the
    slots' state come back in place (the `M` block's 0.4 GB of float32
    state is rewritten where it lies by the kernel's own tiles: no copy of
    it is among the program's temporaries)."""
    from kubeai_tpu.engine.coldstart import param_shapes
    from kubeai_tpu.models import nemotron_h

    mc = ModelConfig(
        model_type="nemotron_h", vocab_size=1024, hidden_size=4096, intermediate_size=0, num_layers=3,
        num_heads=32, num_kv_heads=2, head_dim=128, dtype="bfloat16", layer_pattern="M*E", mamba_num_heads=128,
        mamba_head_dim=64, ssm_state_size=128, ssm_groups=8, conv_kernel=4, ssm_chunk=128, num_experts_per_tok=22,
        n_routed_experts=128, router_experts=512, n_shared_experts=1, moe_intermediate_size=2688, moe_latent_size=1024,
        moe_shared_intermediate_size=5376, routed_scaling_factor=5.0, use_flash_prefill=True, use_paged_kernel=True,
    )
    B, max_pages = NEMOTRON_SLOTS, NEMOTRON_MAX_LEN // PAGE
    params = jax.tree.map(lambda a: _sds(v5e, a.shape, a.dtype), param_shapes(mc))
    cache = jax.tree.map(
        lambda a: _sds(v5e, a.shape, a.dtype),
        jax.eval_shape(lambda: nemotron_h.init_paged_cache(mc, B * max_pages + 1, PAGE, slots=B)),
    )
    compiled = jax.jit(
        lambda p, t, c, tbl, lens, active: nemotron_h.decode_step_paged(p, mc, t, c, tbl, lens, live=LiveRows.first(active)),
        donate_argnums=(2,),
    ).lower(
        params, _sds(v5e, (B, 1), jnp.int32), cache, _sds(v5e, (B, max_pages), jnp.int32), _sds(v5e, (B,), jnp.int32),
        _sds(v5e, (B,), jnp.bool_),
    ).compile()
    # The step kernel, the paged kernel, and an up and a down grouped matmul in the share's one compiled pass.
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 + 1 + 2 and "ssd_step_kernel" in text
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in cache.values())
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= held
    assert memory.temp_size_in_bytes < cache["ssm"].shape[1] * 128 * 64 * 128 * 4 // 2  # no copy of the state


# -- Trinity-Mini (afmoe): the kernels of its step at the published widths ---------

TRINITY = (32, 4)  # 32 query / 4 KV heads of 128 (G = 8: no other configuration groups so)
AFM_WINDOW, AFM_SLOTS, AFM_MAX_LEN = 2048, 24, 32768


@pytest.mark.parametrize(
    "B,S,columns,window",
    [
        (AFM_SLOTS, 1, AFM_MAX_LEN // PAGE, None),
        (AFM_SLOTS, 1, (AFM_WINDOW - 1) // PAGE + 2, AFM_WINDOW),
        (1, 1024, AFM_MAX_LEN // PAGE, None),
        (1, 1024, (1024 + AFM_WINDOW - 2) // PAGE + 2, AFM_WINDOW),
        (8, 32, (32 + AFM_WINDOW - 2) // PAGE + 2, AFM_WINDOW),
        (1, 2048, AFM_MAX_LEN // PAGE, None),
        (1, 2048, (2048 + AFM_WINDOW - 2) // PAGE + 2, AFM_WINDOW),
    ],
    ids=[
        "decode/full-512-pages", "decode/window-33-pages", "chunk-1024/full-512-pages", "chunk-1024/window-49-pages", "cold-8x32/window",
        "chunk-2048/full-512-pages", "chunk-2048/window-65-pages",
    ],
)
def test_ragged_paged_kernel_lowers_at_g8_behind_512_page_tables(v5e, B, S, columns, window):
    """The cell trinitymini-bf16-mixedlen-sat: 24 slots of 512 pages (twice
    the widest table that had run); a full layer walks the slot's whole
    table, up to 1024 query rows behind 25k cached keys; a window layer
    the columns from its first visible page."""
    q, pool, _, lens = _paged_args(v5e[0], B, S, TRINITY, max_len=AFM_MAX_LEN)
    table = _sds(v5e, (B, columns), jnp.int32)
    text = _compile(
        lambda q, kv, tbl, lens: paged_attention_ragged(q, kv, tbl, lens, sliding_window=window), q, pool, table, lens,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k,n", [(2048, 1024), (1024, 2048)], ids=["gate_up", "down"])
@pytest.mark.parametrize("rows", [24 * 8, 8 * 1024, 8 * 1024 * 8, 8 * 32, 8 * 2048])
def test_grouped_expert_matmul_lowers_at_trinitys_widths(v5e, rows, k, n):
    """24 slots x 8 choices at decode (192 rows: one tile), a chunk's 8192,
    a full group's 65536 and the smallest bucket's 256, over the 6 x 128
    experts of the whole stack; an expert's 2048 x 1024 matrix is one tile
    (4 MiB in bf16: the most `gmm_tiles` leaves whole)."""
    from kubeai_tpu.ops.moe import gmm_tiles, grouped_matmul

    assert gmm_tiles(rows, k, n)[1:] == (k, n)
    text = _compile(
        grouped_matmul,
        _sds(v5e, (rows, k), jnp.bfloat16), _sds(v5e, (6 * 128, k, n), jnp.bfloat16), _sds(v5e, (6 * 128,), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_the_first_period_of_trinity_decodes_through_both_pools(v5e):
    """A decode step of the first period (dense + window twice, experts +
    window, experts + full) at the published widths and the cell's 512-page
    tables: the step holds both kinds' paged kernels and the grouped
    matmuls, and both pools come back in place."""
    from kubeai_tpu.engine.coldstart import param_shapes
    from kubeai_tpu.models import afmoe

    mc = ModelConfig(
        model_type="afmoe", vocab_size=1024, hidden_size=2048, intermediate_size=6144, num_layers=4, num_heads=32,
        num_kv_heads=4, head_dim=128, dtype="bfloat16", num_experts_per_tok=8, n_routed_experts=128, n_shared_experts=1,
        moe_intermediate_size=1024, first_k_dense_replace=2, routed_scaling_factor=2.826, embed_scale=True,
        sliding_window_size=AFM_WINDOW, sliding_window_layout=(1, 1, 1, 0), rope_layout=(1, 1, 1, 0), rope_theta=10000.0,
        use_flash_prefill=True, use_paged_kernel=True,
    )
    B, max_pages = AFM_SLOTS, AFM_MAX_LEN // PAGE
    params = jax.tree.map(lambda a: _sds(v5e, a.shape, a.dtype), param_shapes(mc))
    pools = jax.tree.map(
        lambda a: _sds(v5e, a.shape, a.dtype),
        jax.eval_shape(lambda: afmoe.init_paged_cache(mc, 6145, PAGE, window_pages=B * 49 + 1)),
    )
    compiled = jax.jit(
        lambda p, t, c, tbl, lens, active: afmoe.decode_step_paged(p, mc, t, c, tbl, lens, live=LiveRows.first(active)),
        donate_argnums=(2,),
    ).lower(
        params, _sds(v5e, (B, 1), jnp.int32), pools, _sds(v5e, (B, 2 * max_pages), jnp.int32), _sds(v5e, (B,), jnp.int32),
        _sds(v5e, (B,), jnp.bool_),
    ).compile()
    # One period, unrolled: 4 paged kernels and 2 x 3 grouped matmuls.
    assert compiled.as_text().count("tpu_custom_call") >= 10
    pool_bytes = sum(int(np.prod(a.shape)) * 2 for a in pools.values())
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes


# -- the expert layer's way back to tokens (ops/moe.py::_back_to_tokens) --------

WAY_BACK = {  # k, D, experts here, F, the share held
    "smallthinker": (6, 2560, 64, 768, None),
    "kanana-2": (6, 2048, 128, 768, None),
    "trinity": (8, 2048, 128, 1024, None),
    "nemotron-latent-share": (22, 1024, 128, 2688, (0, 128, 512)),
}


@pytest.fixture(scope="module")
def way_back_program(v5e):
    """(family, T) -> the family's expert layer at T tokens, compiled once:
    a 2048-row case reads the 1024-row program's temporaries too."""
    from kubeai_tpu.ops import moe

    programs = {}

    def compiled(family, T):
        if (family, T) not in programs:
            k, D, E, F, held = WAY_BACK[family]
            layer = lambda x, idx, w, wg, wu, wd: moe.routed_experts(x, idx, w, None if held else wg, wu, wd, held=held)
            programs[family, T] = jax.jit(layer).lower(
                _sds(v5e, (T, D), jnp.bfloat16), _sds(v5e, (T, k), jnp.int32), _sds(v5e, (T, k), jnp.float32),
                _sds(v5e, (E, D, F), jnp.bfloat16), _sds(v5e, (E, D, F), jnp.bfloat16), _sds(v5e, (E, F, D), jnp.bfloat16),
            ).compile()
        return programs[family, T]

    return compiled


@pytest.mark.parametrize("T", [1024, 2048])
@pytest.mark.parametrize("family", sorted(WAY_BACK))
def test_the_expert_layers_way_back_holds_no_token_choice_width_array(way_back_program, family, T):
    """A chunk's expert layer at the published widths, through
    `routed_experts` (a share's: through the pass of `_held_part`): the
    compiled program has no array shaped [T, k, D] (k on the sublanes: at
    k = 6 a relayout in float32 padded to 8 rows a token, which at 2048
    tokens left the core for HBM, 231 MB of temporaries a layer, and cost
    nine times its bytes: PERF.md section 6, PR 44) and no float32 array of
    T*k*D elements; and the 2048-row call's temporaries are at most the
    bf16 rows' own bytes over twice the 1024-row call's: no step between
    the two."""
    k, D = WAY_BACK[family][:2]
    program = way_back_program(family, T)
    text = program.as_text()
    assert "tpu_custom_call" in text
    assert not re.findall(rf"\w+\[{T},{k},{D}\]", text)
    widest = max(int(np.prod([int(d) for d in dims.split(",")])) for dims in re.findall(r"f32\[([\d,]+)\]", text))
    assert widest < T * k * D
    temporaries = program.memory_analysis().temp_size_in_bytes
    print(f"{family} T={T}: temp_size_in_bytes {temporaries:,}, widest float32 array {widest:,} elements")
    if T == 2048:
        assert temporaries <= T * k * D * 2 + 2 * way_back_program(family, 1024).memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize(
    "heads,B,S,columns,window,softcap",
    [
        (QWEN25_7B, 1, 2048, (2048 + 4096 - 2) // PAGE + 2, 4096, 0.0),
        (QWEN25_7B, 1, 1024, 16384 // PAGE, None, 0.0),
        ((32, 4), 1, 2048, 32768 // PAGE, None, 0.0),
        (LLAMA3_8B, 1, 2048, 8192 // PAGE, None, 0.0),
        (NEMOTRON_ATTN, 1, 2048, 8192 // PAGE, None, 0.0),
        (QWEN25_7B, 8, 32, (32 + 4096 - 2) // PAGE + 2, 4096, 0.0),
        (QWEN25_7B_TP4, 8, 512, 2048 // PAGE, None, 30.0),
        (QWEN25_7B, SLOTS, 3, 2048 // PAGE, None, 0.0),
        # Two prompts' pieces in one call (engine/core.py::round_calls): the widest, at two slots, in the
        # families that share calls (core.pair_rows: not smallthinker, not trinity).
        (NEMOTRON_ATTN, 2, 2048, 8192 // PAGE, None, 0.0),
        (LLAMA3_8B, 2, 2048, 8192 // PAGE, None, 0.0),
    ],
    ids=[
        "smallthinker/chunk-2048/window-97-pages", "smallthinker/chunk-1024/full", "trinity/chunk-2048/full-512-pages",
        "mistral-7b/chunk-2048", "nemotron/chunk-2048", "smallthinker/cold-8x32/window", "qwen2.5-7b/tp4/8x512-softcap",
        "qwen2.5-7b/verify-S3", "nemotron/chunk-2x2048", "mistral-7b/chunk-2x2048",
    ],
)
def test_chunk_kernel_lowers(v5e, monkeypatch, heads, B, S, columns, window, softcap):
    """More than one query row a slot over a bf16 pool is the repo's own
    kernel (ops/chunk_attention.py), at every grouping the cells run (G =
    4 at 512 query rows a tile; 7, 8 and 16 at 256), with and without a
    window, at the smallest bucket, with a softcap, and at a caller's few
    rows a slot: the tiles it was given are on record, and the program
    holds its custom call and not the library's."""
    from kubeai_tpu.ops import chunk_attention

    monkeypatch.setattr(chunk_attention, "chosen_tiles", {})
    q, pool, _, lens = _paged_args(v5e[0], B, S, heads, max_len=columns * PAGE)
    table = _sds(v5e, (B, columns), jnp.int32)
    text = _compile(
        lambda q, kv, tbl, lens: paged_attention_ragged(q, kv, tbl, lens, sliding_window=window, softcap=softcap),
        q, pool, table, lens,
    )
    assert "tpu_custom_call" in text and "chunk_attention_kernel" in text and "ragged_paged_attention_kernel" not in text
    tile = min(S, 512 if heads[0] // heads[1] <= 4 else 256)
    assert list(chunk_attention.chosen_tiles.values()) == [{"query_tile": tile, "kv_block": 256}]


# -- LFM2-24B-A2B (lfm2_moe): heads of 64, two to a lane tile, at the published widths ----

LFM_SLOTS, LFM_MAX_LEN = 192, 8192


def _lfm(v5e):
    """(config, parameters, cache) of one dense conv layer and one period
    (`c | a c c c`) at the published widths, 192 slots of 8192, 8193 pages."""
    from kubeai_tpu.engine.coldstart import param_shapes
    from kubeai_tpu.models import lfm2_moe

    mc = ModelConfig(
        model_type="lfm2_moe", vocab_size=1024, hidden_size=2048, intermediate_size=11776, num_layers=5, num_heads=32,
        num_kv_heads=8, dtype="bfloat16", num_experts_per_tok=4, n_routed_experts=64, moe_intermediate_size=1536,
        first_k_dense_replace=1, layer_pattern="caccc", conv_kernel=3, rope_theta=1e6, tie_word_embeddings=True,
        use_flash_prefill=True, use_paged_kernel=True,
    )
    lfm2_moe.refuse_unsupported(mc)
    params = jax.tree.map(lambda a: _sds(v5e, a.shape, a.dtype), param_shapes(mc))
    cache = jax.tree.map(
        lambda a: _sds(v5e, a.shape, a.dtype), jax.eval_shape(lambda: lfm2_moe.init_paged_cache(mc, 8193, PAGE, slots=LFM_SLOTS))
    )
    return mc, params, cache


def test_the_library_kernel_refuses_heads_of_64_and_the_lane_tile_reading_lowers(v5e):
    """Why the family packs two KV heads to a 128-lane row: at `h = 64` the
    library's ragged kernel asserts while it is traced (its running sum is
    128 lanes wide and is tiled over the head), for one row a slot and for
    many; the same bytes read as 4 heads of 128 lower through the library's
    kernel at decode and through the repo's chunk kernel for a chunk."""
    B, pages = LFM_SLOTS, LFM_MAX_LEN // PAGE
    for S, rows in ((1, B), (2048, 1)):
        narrow = (
            _sds(v5e, (rows, S, 32, 64), jnp.bfloat16), _sds(v5e, (8193, PAGE, 16, 64), jnp.bfloat16),
            _sds(v5e, (rows, pages), jnp.int32), _sds(v5e, (rows,), jnp.int32),
        )
        with pytest.raises(AssertionError):
            _compile(lambda q, kv, tbl, lens: paged_attention_ragged(q, kv, tbl, lens, blocks=(8, 1)), *narrow)
        wide = (_sds(v5e, (rows, S, 32, 128), jnp.bfloat16), _sds(v5e, (8193, PAGE, 8, 128), jnp.bfloat16), *narrow[2:])
        text = _compile(lambda q, kv, tbl, lens: paged_attention_ragged(q, kv, tbl, lens, scale=0.125), *wide)
        assert ("chunk_attention_kernel" in text) == (S > 1) and "tpu_custom_call" in text


def test_a_dense_layer_and_a_period_of_lfm2_decode_in_place_through_the_paged_kernel(v5e):
    """A decode step of `c | a c c c` at the published widths with 192
    slots of 8192: the step holds the library's paged kernel on 4 heads of
    128 lanes (two of the published 64 side by side) and the three grouped
    matmuls of each expert layer, and the pool AND the slots' tails come
    back in place."""
    from kubeai_tpu.models import lfm2_moe

    mc, params, cache = _lfm(v5e)
    B, max_pages = LFM_SLOTS, LFM_MAX_LEN // PAGE
    assert cache["kv"].shape == (8193, PAGE, 8, 128) and cache["conv"].shape == (4, B, 2, 2048)
    compiled = jax.jit(
        lambda p, t, c, tbl, lens, active: lfm2_moe.decode_step_paged(p, mc, t, c, tbl, lens, live=LiveRows.first(active)),
        donate_argnums=(2,),
    ).lower(
        params, _sds(v5e, (B, 1), jnp.int32), cache, _sds(v5e, (B, max_pages), jnp.int32), _sds(v5e, (B,), jnp.int32),
        _sds(v5e, (B,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    # One scanned period: one paged kernel, 4 expert layers x 3 grouped matmuls.
    assert text.count("tpu_custom_call") == 1 + 4 * 3 and "ragged_paged_attention_kernel" in text
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in cache.values())
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= held
    assert memory.temp_size_in_bytes < cache["kv"].shape[0] * PAGE * 8 * 128 * 2 // 2  # no copy of the pool


@pytest.mark.parametrize("n", [1, 2], ids=["one_slot", "two_slots"])
def test_a_2048_row_chunk_of_lfm2_runs_the_chunk_kernel_on_heads_side_by_side(v5e, monkeypatch, n):
    """The cell's widest prefill call behind cached tokens, for one slot
    and for two that share it (engine/core.py::round_calls): the attention
    layer is the repo's own chunk kernel (G = 8 on the widened heads: 256
    query rows a tile), never the portable gather, and pool and tails come
    back in place."""
    from kubeai_tpu.models import lfm2_moe
    from kubeai_tpu.ops import chunk_attention

    monkeypatch.setattr(chunk_attention, "chosen_tiles", {})
    mc, params, cache = _lfm(v5e)
    assert lfm2_moe.cached_attention_route(mc, 2048, False, True) == "paged_kernel"
    max_pages = LFM_MAX_LEN // PAGE
    compiled = jax.jit(
        lambda p, t, c, tbl, start, last, slot: lfm2_moe.prefill_paged(p, mc, t, c, tbl, start, last, slots=slot),
        donate_argnums=(2,),
    ).lower(
        params, _sds(v5e, (n, 2048), jnp.int32), cache, _sds(v5e, (n, max_pages), jnp.int32), _sds(v5e, (n,), jnp.int32),
        _sds(v5e, (n,), jnp.int32), _sds(v5e, (n,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert "chunk_attention_kernel" in text and "ragged_paged_attention_kernel" not in text
    assert list(chunk_attention.chosen_tiles.values()) == [{"query_tile": 256, "kv_block": 256}]
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in cache.values())
    assert compiled.memory_analysis().alias_size_in_bytes >= held
