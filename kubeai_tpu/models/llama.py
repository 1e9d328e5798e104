"""Llama-family decoder (Llama 2/3, Mistral, Qwen2-style) in functional JAX.

Design notes (TPU-first, not a torch translation):
- Parameters are a pytree of arrays with all layers **stacked on a leading
  L axis** and the forward pass is a single `lax.scan` over layers — one
  layer is traced/compiled once regardless of depth, and XLA pipelines the
  weight streams.
- One `apply()` serves prefill, decode, and training: the causal mask is
  derived entirely from absolute `positions`, and the KV cache (when
  given) is written by batched scatter at those positions. Static shapes
  throughout; batch/sequence bucketing happens in the engine.
- GQA is computed grouped (see kubeai_tpu.ops.attention) so KV stays at
  Kv-head width in HBM.
- Sharding is expressed separately (kubeai_tpu.parallel.sharding) as
  PartitionSpec trees over a ("dp", "tp") mesh; this module is
  sharding-agnostic and relies on XLA propagation.

Replaces the engine tier the reference delegates to vLLM containers
(ref: internal/modelcontroller/engine_vllm.go — config-only there).

Pad semantics: prefill pads sit at positions >= the true length and write
garbage K/V there; those slots are never attended (mask is key_pos <=
query_pos and real queries stop at length-1) and are overwritten by decode
steps before the sequence ever reaches them.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from kubeai_tpu.models.base import ModelConfig
from kubeai_tpu.ops.attention import attention
from kubeai_tpu.ops.norms import rms_norm
from kubeai_tpu.ops.quant import qdot, qgather, qmatT
from kubeai_tpu.ops.rope import apply_rope, rope_frequencies

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Parameter creation / conversion


def init_params(config: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random-normal initialized parameters (tests, benches, training)."""
    dtype = dtype or jnp.dtype(config.dtype)
    D, F, L = config.hidden_size, config.intermediate_size, config.num_layers
    H, Kv, h = config.num_heads, config.num_kv_heads, config.head_dim_
    V = config.vocab_size
    keys = iter(jax.random.split(key, 16))

    def w(k, *shape, scale=None):
        scale = scale or (1.0 / np.sqrt(shape[-2] if len(shape) > 1 else shape[-1]))
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    layers: Params = {
        "ln1": jnp.ones((L, D), dtype),
        "ln2": jnp.ones((L, D), dtype),
        "wq": w(next(keys), L, D, H * h),
        "wk": w(next(keys), L, D, Kv * h),
        "wv": w(next(keys), L, D, Kv * h),
        "wo": w(next(keys), L, H * h, D),
    }
    if config.qkv_bias:
        layers["bq"] = jnp.zeros((L, H * h), dtype)
        layers["bk"] = jnp.zeros((L, Kv * h), dtype)
        layers["bv"] = jnp.zeros((L, Kv * h), dtype)
    if config.post_norms:
        layers["ln1b"] = jnp.ones((L, D), dtype)
        layers["ln2b"] = jnp.ones((L, D), dtype)
    if config.num_experts > 0:
        E = config.num_experts
        layers["wr"] = w(next(keys), L, D, E)  # router
        layers["wg"] = w(next(keys), L, E, D, F)
        layers["wu"] = w(next(keys), L, E, D, F)
        layers["wd"] = w(next(keys), L, E, F, D)
    else:
        layers["wg"] = w(next(keys), L, D, F)
        layers["wu"] = w(next(keys), L, D, F)
        layers["wd"] = w(next(keys), L, F, D)
    params: Params = {
        "embed": w(next(keys), V, D, scale=0.02),
        "final_norm": jnp.ones((D,), dtype),
        "layers": layers,
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = w(next(keys), D, V, scale=0.02)
    return params


def params_from_hf(state_dict: dict[str, np.ndarray], config: ModelConfig, dtype=None, to_device: bool = True) -> Params:
    """Convert an HF Llama-style state dict (name -> numpy array) into our
    stacked-layer pytree. Linear weights are transposed to [in, out].
    With to_device=False the tree stays numpy on host (jax dtypes like
    bfloat16 are numpy-compatible via ml_dtypes) — the quantizing loader
    uses this so full-precision weights never touch HBM."""
    dtype = dtype or jnp.dtype(config.dtype)
    conv = (lambda a: jnp.asarray(a, dtype)) if to_device else (lambda a: np.asarray(a, dtype))
    L = config.num_layers

    def get(name):
        return np.asarray(state_dict[name])

    def stack(fmt, transpose=True):
        ws = [get(fmt.format(i)) for i in range(L)]
        arr = np.stack([w.T if transpose else w for w in ws])
        return conv(arr)

    layers: Params = {
        "ln1": stack("model.layers.{}.input_layernorm.weight", transpose=False),
        "wq": stack("model.layers.{}.self_attn.q_proj.weight"),
        "wk": stack("model.layers.{}.self_attn.k_proj.weight"),
        "wv": stack("model.layers.{}.self_attn.v_proj.weight"),
        "wo": stack("model.layers.{}.self_attn.o_proj.weight"),
    }
    if config.qkv_bias:
        layers["bq"] = stack("model.layers.{}.self_attn.q_proj.bias", transpose=False)
        layers["bk"] = stack("model.layers.{}.self_attn.k_proj.bias", transpose=False)
        layers["bv"] = stack("model.layers.{}.self_attn.v_proj.bias", transpose=False)
    if config.post_norms:
        # Gemma2 layout: post-attn + pre/post-feedforward norms.
        layers["ln1b"] = stack("model.layers.{}.post_attention_layernorm.weight", transpose=False)
        layers["ln2"] = stack("model.layers.{}.pre_feedforward_layernorm.weight", transpose=False)
        layers["ln2b"] = stack("model.layers.{}.post_feedforward_layernorm.weight", transpose=False)
    else:
        layers["ln2"] = stack("model.layers.{}.post_attention_layernorm.weight", transpose=False)
    if config.num_experts > 0:
        # Mixtral naming: block_sparse_moe.gate + experts.{e}.w1/w3/w2
        # (gate/up/down); stacked to [L, E, in, out].
        E = config.num_experts

        def stack_experts(which):
            out = []
            for li in range(L):
                per = [
                    get(f"model.layers.{li}.block_sparse_moe.experts.{e}.{which}.weight").T
                    for e in range(E)
                ]
                out.append(np.stack(per))
            return conv(np.stack(out))

        layers["ln2"] = stack(
            "model.layers.{}.post_attention_layernorm.weight", transpose=False
        )
        layers["wr"] = stack("model.layers.{}.block_sparse_moe.gate.weight")
        layers["wg"] = stack_experts("w1")
        layers["wu"] = stack_experts("w3")
        layers["wd"] = stack_experts("w2")
    else:
        layers["wg"] = stack("model.layers.{}.mlp.gate_proj.weight")
        layers["wu"] = stack("model.layers.{}.mlp.up_proj.weight")
        layers["wd"] = stack("model.layers.{}.mlp.down_proj.weight")
    params: Params = {
        "embed": conv(get("model.embed_tokens.weight")),
        "final_norm": conv(get("model.norm.weight")),
        "layers": layers,
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = conv(get("lm_head.weight").T)
    return params


# ---------------------------------------------------------------------------
# KV cache


def init_cache(config: ModelConfig, batch: int, max_len: int, dtype=None) -> Params:
    """Slot-based contiguous KV cache: [L, B, max_len, Kv, head_dim].
    Used by training/eval and the dryrun; the serving engine uses the
    paged pool below."""
    dtype = dtype or jnp.dtype(config.dtype)
    shape = (config.num_layers, batch, max_len, config.num_kv_heads, config.head_dim_)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_cache(config: ModelConfig, num_pages: int, page_size: int, dtype=None) -> Params:
    """Paged KV pool: one FLAT array [L*P, page, 2*Kv, head_dim] with K/V
    interleaved on the head axis (K at even indices, V at odd — the TPU
    ragged-paged-attention kernel's native layout, so prefill and decode
    both read pages in place with zero re-layout). Layer l owns pool
    rows [l*P, (l+1)*P); the engine's block tables stay layer-agnostic
    (logical pages 0..P-1) and the forward adds the l*P offset in-graph.

    Why flat instead of a stacked [L, P, ...] leading layer axis: the
    layer scan would then have to slice layer l's 100MB+ pool plane out
    of the stacked array (and scatter it back) every layer of every
    decode step — measured ~10ms/step of pure copy traffic on v5e for a
    1.3B config, 4x the whole rest of the step. With the flat layout
    every layer reads/writes the SAME un-sliced carry array and XLA
    keeps the donated buffer in place end-to-end; the only per-layer
    work is the B-token scatter and the kernel's page reads. Logical
    page 0 of every layer (pool row l*P) is that layer's trash page
    (see engine/paging.py).

    config.kv_cache_dtype = "fp8"/"int8" stores the pool quantized
    (see ModelConfig): apply() quantizes on write and the attention
    paths dequantize on read (in-kernel for the ragged kernel)."""
    dtype = dtype or kv_pool_dtype(config)
    shape = (
        config.num_layers * num_pages, page_size, 2 * config.num_kv_heads, config.head_dim_,
    )
    return {"kv": jnp.zeros(shape, dtype)}


def kv_pool_dtype(config: ModelConfig):
    """Storage dtype for the paged KV pool (quantization-aware)."""
    if config.kv_cache_dtype == "fp8":
        return jnp.dtype(jnp.float8_e4m3fn)
    if config.kv_cache_dtype == "int8":
        return jnp.dtype(jnp.int8)
    if config.kv_cache_dtype in ("", "auto"):
        return jnp.dtype(config.dtype)
    return jnp.dtype(config.kv_cache_dtype)


# ---------------------------------------------------------------------------
# Forward


LORA_TARGETS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")
# What the engine's step records call this family's "paged_kernel" route.
PAGED_KERNEL_LABEL = "ragged"
# A prefix found in the cache is used to the page (models/deepseek.py has
# the family that uses it in whole prefill calls, and why).
REUSE_WHOLE_PREFILL_CALLS = False


def cached_attention_route(config: ModelConfig, S: int, left_aligned: bool, paged: bool) -> str:
    """The attention implementation apply() takes for a cached call of
    *S* queries per row: "flash" (Pallas flash prefill), "paged_kernel"
    (a Pallas paged kernel reading pages in place) or "xla" (the
    portable gather + masked attention). One decision, shared with the
    engine's step records so they name the route that actually ran."""
    # Flash prefill: only when the caller vouches the positions are
    # arange(S) (left_aligned — inferring it from shapes would silently
    # mis-mask offset-position calls), on plain causal models with
    # kernel-friendly shapes.
    if (
        config.use_flash_prefill
        and left_aligned
        and S >= 256
        and S % 256 == 0
        and config.attn_softcap == 0.0
        and config.sliding_window == 0
    ):
        return "flash"
    # The paged kernel handles 1..S queries per slot, so decode and
    # chunked prefill read pages in place; per-layer sliding-
    # window interleaves can't use one static kernel window, so
    # Gemma2-style configs take the gather path.
    if config.use_paged_kernel and paged and config.sliding_window == 0:
        return "paged_kernel"
    return "xla"


def refuse_unsupported(config: ModelConfig, quantization: str = "", tp: int = 1) -> None:
    """What this family does not run, refused at load: nothing beyond
    what `weights.load_engine_from_path` checks for every family."""


def init_lora_bank(config: ModelConfig, n_adapters: int, rank: int, dtype=None) -> Params:
    """Zeroed stacked adapter bank for batched multi-LoRA (punica-style):
    per target, A [L, N, in, r] and B [L, N, r, out]. *n_adapters* is the
    TOTAL row count INCLUDING row 0, which is reserved as the identity
    (all-zero) adapter for requests without one — callers wanting K real
    adapters pass K+1. Beware: row indices beyond N are silently dropped
    by JAX scatter/clamped by gather, which reads as "LoRA has no effect".
    Static shapes — installing an adapter is a device scatter, never a
    recompile."""
    dtype = dtype or jnp.dtype(config.dtype)
    D, F, L = config.hidden_size, config.intermediate_size, config.num_layers
    H, Kv, h = config.num_heads, config.num_kv_heads, config.head_dim_
    dims = {
        "wq": (D, H * h), "wk": (D, Kv * h), "wv": (D, Kv * h), "wo": (H * h, D),
        "wg": (D, F), "wu": (D, F), "wd": (F, D),
    }
    bank: Params = {"scale": jnp.zeros((n_adapters,), jnp.float32)}
    for t, (din, dout) in dims.items():
        bank[t + "_A"] = jnp.zeros((L, n_adapters, din, rank), dtype)
        bank[t + "_B"] = jnp.zeros((L, n_adapters, rank, dout), dtype)
    return bank


def moe_mlp(x, wr, wg, wu, wd, num_experts_per_tok: int, capacity_factor: float = 2.0):
    """Mixtral-style sparse MoE FFN with GShard static-capacity dispatch.

    x [B, S, D]; wr [D, E]; wg/wu [E, D, F]; wd [E, F, D].
    Top-k routing with softmax-over-top-k weights (Mixtral semantics);
    tokens beyond an expert's capacity C = ceil(k*T/E * factor) are
    dropped (their contribution is zero). All shapes static: dispatch and
    combine are one-hot einsums that land on the MXU, and the expert dim
    shards over the `ep` mesh axis (XLA inserts the all-to-alls).
    """
    B, S, D = x.shape
    E = wr.shape[-1]
    k = num_experts_per_tok
    T = B * S
    C = max(int(np.ceil(k * T / E * capacity_factor)), 1)

    xt = x.reshape(T, D)
    router_logits = (xt @ wr).astype(jnp.float32)  # [T, E]
    top_vals, top_idx = jax.lax.top_k(router_logits, k)  # [T, k]
    weights = jax.nn.softmax(top_vals, axis=-1)  # renorm over chosen experts

    onehot = jax.nn.one_hot(top_idx.reshape(T * k), E, dtype=jnp.float32)  # [T*k, E]
    # Position of each (token, choice) within its expert's capacity.
    pos = (jnp.cumsum(onehot, axis=0) - onehot)  # [T*k, E]
    pos = (pos * onehot).sum(-1)  # [T*k]
    keep = (pos < C).astype(jnp.float32)
    dispatch = onehot * keep[:, None]  # [T*k, E]
    pos_oh = jax.nn.one_hot(pos, C, dtype=jnp.float32)  # [T*k, C]
    disp = jnp.einsum("ne,nc->ecn", dispatch, pos_oh)  # [E, C, T*k]

    x_rep = jnp.repeat(xt, k, axis=0)  # token for each (t, choice)
    xe = jnp.einsum("ecn,nd->ecd", disp, x_rep.astype(jnp.float32)).astype(x.dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) * jnp.einsum("ecd,edf->ecf", xe, wu)
    ye = jnp.einsum("ecf,efd->ecd", h, wd)  # [E, C, D]

    w_flat = weights.reshape(T * k) * keep
    y = jnp.einsum("ecn,ecd->nd", disp, ye.astype(jnp.float32)) * w_flat[:, None]
    return y.reshape(T, k, D).sum(axis=1).reshape(B, S, D).astype(x.dtype)


def _lora_delta(x, A_l, B_l, rows, scale):
    """Per-row LoRA delta: x [B, S, din], A_l [N, din, r], B_l [N, r, dout],
    rows [B] adapter indices, scale [N] -> [B, S, dout] in x's dtype.
    Compute happens at the promoted precision so a bank in either higher
    (f32 adapters on bf16 base) or lower precision never downcasts x."""
    compute_dtype = jnp.promote_types(x.dtype, A_l.dtype)
    A_sel = A_l[rows].astype(compute_dtype)  # [B, din, r]
    B_sel = B_l[rows].astype(compute_dtype)  # [B, r, dout]
    low = jnp.einsum("bsd,bdr->bsr", x.astype(compute_dtype), A_sel)
    out = jnp.einsum("bsr,bro->bso", low, B_sel) * scale[rows][:, None, None].astype(compute_dtype)
    return out.astype(x.dtype)


def apply(
    params: Params,
    config: ModelConfig,
    tokens: jnp.ndarray,  # [B, S] int32
    positions: jnp.ndarray,  # [B, S] int32 absolute positions
    cache: Params | None = None,
    logits_idx: jnp.ndarray | None = None,  # [B] gather one query index before lm_head
    cache_rows: jnp.ndarray | None = None,  # [B] cache row per batch row
    lora: Params | None = None,  # adapter bank from init_lora_bank
    lora_rows: jnp.ndarray | None = None,  # [B] adapter index per batch row
    left_aligned: bool = False,  # caller guarantees positions == arange(S)
    return_hidden: bool = False,  # final-norm hidden states instead of logits
    page_table: jnp.ndarray | None = None,  # [B, max_pages] pool page per seq page
    ring_mesh=None,  # Mesh with an `sp` axis: cache-less attention runs
    # as ring attention over sequence-sharded blocks (ppermute ring,
    # O((S/sp)^2) scores per device — parallel/ring_attention.py). The
    # trainer's long-context path; requires positions == arange(S),
    # no sliding window, no softcap.
    tp_mesh=None,  # Mesh whose `tp` axis shards heads: the flash and
    # paged kernels run per shard under jax.shard_map (a Mosaic kernel
    # cannot be partitioned by GSPMD), q and the pool split on their
    # head axes as parallel/sharding.py's specs say.
    live=None,  # models/base.py::LiveRows of a paged decode step whose rows arrive live slots first
):
    """Run the decoder. Returns (logits, new_cache).

    With a dense cache (init_cache): new K/V are scattered into
    cache[:, row, positions[b, s]] and attention spans the whole cache
    row, masked to keys <= query position. *cache_rows* maps batch rows
    onto cache rows (continuous batching prefills a single sequence into
    an arbitrary slot of the big decode cache); default is row b = batch b.

    With a paged cache (init_paged_cache) + *page_table*: position p of
    batch row b lives in pool page page_table[b, p // page] at offset
    p % page. Writes scatter through the table (positions beyond the
    table's span are redirected to trash page 0); attention reads gather
    each row's pages back into a contiguous [B, max_pages*page] view and
    use the same position-derived mask.

    Without a cache (training / one-shot scoring): attention is causal
    over the S new tokens only.

    logits shape: [B, S, V], or [B, 1, V] if logits_idx is given.
    """
    B, S = tokens.shape
    H, Kv, h = config.num_heads, config.num_kv_heads, config.head_dim_
    inv_freq = jnp.asarray(rope_frequencies(h, config.rope_theta, config.rope_scaling))
    if ring_mesh is not None:
        # Ring attention derives its causal mask from arange positions
        # and has no window/softcap arms — reject configs it would
        # silently mis-serve.
        assert cache is None, "ring attention is the cache-less (training) path"
        assert config.sliding_window == 0 and config.attn_softcap == 0.0, (
            "ring attention does not support sliding windows or softcap"
        )

    # jax.named_scope below (embed; per layer attn, attn.kernel around the
    # Pallas call, and ffn; lm_head) changes operation metadata only: the
    # names a device trace files each operation's time under (PERF.md
    # section 3; perfbench/readers/scope_share.py).
    with jax.named_scope("embed"):
        x = qgather(params["embed"], tokens, jnp.dtype(config.dtype))
        if config.embed_scale:
            # Gemma multiplies embeddings by sqrt(hidden), rounded through the
            # compute dtype (HF casts the normalizer).
            x = x * jnp.asarray(config.hidden_size**0.5, x.dtype)

    act = jax.nn.silu if config.hidden_act == "silu" else (
        lambda v: jax.nn.gelu(v, approximate=True)
    )
    norm_offset = 1.0 if config.rms_one_offset else 0.0
    route = (
        cached_attention_route(config, S, left_aligned, page_table is not None)
        if cache is not None
        else "xla"
    )
    use_flash = route == "flash"
    use_paged_kernel = route == "paged_kernel"

    def per_tp_shard(fn, n_head_split, n_replicated=0):
        """Run an attention kernel on each tp shard's heads: the first
        *n_head_split* arguments ([.., .., heads, h]) and the output are
        split on axis 2, the *n_replicated* after them (tables, lengths)
        are whole on every shard."""
        tp = tp_mesh.shape["tp"] if tp_mesh is not None else 1
        if tp == 1:
            return fn
        if H % tp or Kv % tp:
            raise ValueError(
                f"the attention kernels shard whole KV heads over tp: "
                f"num_heads={H} and num_kv_heads={Kv} must be multiples "
                f"of tp={tp}"
            )
        from jax.sharding import PartitionSpec as P

        heads = P(None, None, "tp", None)
        return jax.shard_map(
            fn, mesh=tp_mesh,
            in_specs=(heads,) * n_head_split + (P(),) * n_replicated,
            out_specs=heads,
            # pallas_call outputs carry no varying-axes type.
            check_vma=False,
        )

    paged = page_table is not None
    kv_quant = False
    if paged:
        page = cache["kv"].shape[1]
        pool_P = cache["kv"].shape[0] // config.num_layers  # logical pages per layer
        kv_dt = cache["kv"].dtype
        kv_quant = kv_dt in (jnp.dtype(jnp.int8), jnp.dtype(jnp.float8_e4m3fn))
        if kv_quant:
            # Static per-tensor dequant scales (fp8 is scale-free, its
            # finite range covers K/V activations); head axis interleaves
            # K (even) / V (odd), so the scale vector does too.
            kq_scale = float(config.kv_scale_k) if kv_dt == jnp.dtype(jnp.int8) else 1.0
            vq_scale = float(config.kv_scale_v) if kv_dt == jnp.dtype(jnp.int8) else 1.0
            kv_scale_vec = jnp.where(
                jnp.arange(2 * Kv) % 2 == 0, kq_scale, vq_scale
            )[:, None].astype(jnp.float32)  # [2Kv, 1] vs [..., 2Kv, h]
        max_pages = page_table.shape[1]
        skv = max_pages * page
        key_positions = jnp.arange(skv)[None, None, :]  # [1, 1, Skv]
        # Write indices: LOGICAL pool page + in-page offset per (b, s)
        # token; layer l adds l*pool_P in-graph (flat pool — see
        # init_paged_cache). Out-of-span positions (bucket padding past
        # the table, decode overrun after a sequence finished) go to the
        # layer's trash page (logical 0) so they can never corrupt a
        # live page.
        w_idx = jnp.clip(positions // page, 0, max_pages - 1)
        w_pages = jnp.take_along_axis(page_table, w_idx, axis=1)
        w_pages = jnp.where(positions < skv, w_pages, 0)
        w_offs = positions % page
    elif cache is not None:
        skv = cache["k"].shape[2]
        key_positions = jnp.arange(skv)[None, None, :]  # [1, 1, Skv]
    else:
        key_positions = positions[:, None, :]  # [B, 1, S]
    mask = key_positions <= positions[:, :, None]  # [B, S, Skv]

    # Sliding-window attention (Gemma2 interleave): per-layer flag selects
    # between the global causal mask and the windowed one.
    L = config.num_layers
    if config.sliding_window > 0:
        window_ok = key_positions > positions[:, :, None] - config.sliding_window
        if config.sliding_layers == "even":
            sliding_flags = (jnp.arange(L) % 2) == 0
        else:
            sliding_flags = jnp.ones((L,), bool)
    else:
        window_ok = None
        sliding_flags = jnp.zeros((L,), bool)

    batch_idx = jnp.arange(B)[:, None]
    rows = batch_idx if cache_rows is None else cache_rows[:, None]

    def layer(x, w, k_cache_l, v_cache_l, kv_pool=None, lora_l=None, sliding=None, layer_idx=None):
        def proj(inp, name):
            out = qdot(inp, w[name])
            # KeyError at trace time if a qkv_bias config meets a tree
            # without biases — better than silently wrong logits.
            if config.qkv_bias and name in ("wq", "wk", "wv"):
                out = out + w["b" + name[1:]]
            if lora_l is not None:
                out = out + _lora_delta(
                    inp, lora_l[name + "_A"], lora_l[name + "_B"], lora_rows, lora["scale"]
                )
            return out

        def norm(inp, name):
            return rms_norm(inp, w[name] + norm_offset, config.rms_norm_eps)

        with jax.named_scope("attn"):
            attn_in = norm(x, "ln1")
            q = proj(attn_in, "wq").reshape(B, S, H, h)
            k = proj(attn_in, "wk").reshape(B, S, Kv, h)
            v = proj(attn_in, "wv").reshape(B, S, Kv, h)
            q, k = apply_rope(q, k, positions, inv_freq)

            if kv_pool is not None:
                # kv_pool: the FULL flat [L*P, page, 2Kv, h] pool, K/V
                # interleaved on the head axis (kernel-native); this layer
                # owns rows layer_idx*P..(layer_idx+1)*P. One scatter writes
                # both through the offset block table; the kernel (or CPU
                # reference) reads pages in place, and the portable fallback
                # gathers a contiguous view. The pool rides the scan CARRY
                # un-sliced — slicing a per-layer plane out of a stacked
                # array cost ~10ms/step in copies (see init_paged_cache).
                interleaved = jnp.stack([k, v], axis=3).reshape(B, S, 2 * Kv, h)
                if kv_quant:
                    y = interleaved.astype(jnp.float32) / kv_scale_vec
                    if kv_dt == jnp.dtype(jnp.int8):
                        y = jnp.clip(jnp.round(y), -127.0, 127.0)
                    else:
                        # e4m3fn overflow converts to NaN, not max — clip to
                        # the format's finite range first.
                        y = jnp.clip(y, -448.0, 448.0)
                    interleaved = y.astype(kv_dt)
                table_l = page_table + layer_idx * pool_P
                kv_full = kv_pool.at[w_pages + layer_idx * pool_P, w_offs].set(interleaved)
                k_full = v_full = None
                if use_paged_kernel or use_flash:
                    # Neither path reads the gathered view: the ragged kernel
                    # walks pages in place, and flash prefill (left-aligned,
                    # positions arange(S)) attends exactly the just-computed
                    # k/v — gathering the full table width only to slice S
                    # columns would move max_pages*page/S times the needed
                    # KV bytes per layer.
                    k_att = v_att = None
                else:
                    gathered = kv_full[table_l]  # [B, mp, page, 2Kv, h]
                    if kv_quant:
                        gathered = (
                            gathered.astype(jnp.float32) * kv_scale_vec
                        ).astype(jnp.dtype(config.dtype))
                    k_att = gathered[..., 0::2, :].reshape(B, skv, Kv, h)
                    v_att = gathered[..., 1::2, :].reshape(B, skv, Kv, h)
            elif k_cache_l is not None:
                k_full = k_cache_l.at[rows, positions].set(k)
                v_full = v_cache_l.at[rows, positions].set(v)
                if cache_rows is None:
                    k_att, v_att = k_full, v_full
                else:
                    k_att, v_att = k_full[cache_rows], v_full[cache_rows]
            else:
                k_full, v_full = k, v
                k_att, v_att = k, v

            if use_paged_kernel:
                from kubeai_tpu.ops.paged_attention import paged_attention_ragged

                # The live rows' count, where the caller gave one, is one
                # more whole operand on every shard.
                n_live = () if live is None else (live.count,)
                with jax.named_scope("attn.kernel"):
                    attn_out = per_tp_shard(
                        lambda q_, kv_, table_, lens_, *n_: paged_attention_ragged(
                            q_, kv_, table_, lens_,
                            scale=config.query_scale,
                            softcap=config.attn_softcap,
                            k_scale=kq_scale if kv_quant else None,
                            v_scale=vq_scale if kv_quant else None,
                            live_rows=n_[0] if n_ else None,
                        ),
                        n_head_split=2, n_replicated=2 + len(n_live),
                    )(q, kv_full, table_l, positions[:, -1] + 1, *n_live)  # keys 0..last pos inclusive
            elif use_flash:
                # Prefill positions are arange(S): the cache columns 0..S-1
                # were just written with exactly k/v, so plain causal over
                # the fresh tensors == the position-derived mask over the
                # cache — no cache read needed.
                from kubeai_tpu.ops.flash_attention import flash_attention_tpu

                with jax.named_scope("attn.kernel"):
                    attn_out = per_tp_shard(
                        lambda q_, k_, v_: flash_attention_tpu(
                            q_, k_, v_, causal=True, sm_scale=config.query_scale,
                        ),
                        n_head_split=3,
                    )(q, k, v)
            elif ring_mesh is not None and cache is None:
                from kubeai_tpu.parallel.ring_attention import ring_attention

                attn_out = ring_attention(
                    q, k, v, ring_mesh, scale=config.query_scale
                )
            else:
                layer_mask = mask
                if window_ok is not None and sliding is not None:
                    layer_mask = jnp.logical_and(mask, jnp.logical_or(~sliding, window_ok))
                attn_out = attention(
                    q, k_att, v_att, layer_mask,
                    scale=config.query_scale, softcap=config.attn_softcap,
                )
            o = proj(attn_out.reshape(B, S, H * h), "wo")
            if config.post_norms:
                o = norm(o, "ln1b")
            x = x + o

        with jax.named_scope("ffn"):
            mlp_in = norm(x, "ln2")
            if config.num_experts > 0:
                m = moe_mlp(
                    mlp_in, w["wr"], w["wg"], w["wu"], w["wd"],
                    config.num_experts_per_tok, config.moe_capacity_factor,
                )
            else:
                m = proj(act(proj(mlp_in, "wg")) * proj(mlp_in, "wu"), "wd")
            if config.post_norms:
                m = norm(m, "ln2b")
            x = x + m
        cache_out = kv_full if kv_pool is not None else (k_full, v_full)
        return x, cache_out

    # Per-layer lora slices ride the scan xs (leading dim L).
    lora_xs = None
    if lora is not None:
        lora_xs = {k: v for k, v in lora.items() if k != "scale"}

    if cache is not None and paged:
        # The flat pool rides the scan CARRY (never sliced, scattered in
        # place on the donated buffer); per-layer weights/flags ride xs.

        def step_paged(carry, xs):
            x, pool = carry
            w, lora_l, sliding, l = xs
            x, pool = layer(x, w, None, None, pool, lora_l, sliding, layer_idx=l)
            return (x, pool), None

        (x, new_kv), _ = jax.lax.scan(
            step_paged,
            (x, cache["kv"]),
            (params["layers"], lora_xs, sliding_flags, jnp.arange(L, dtype=jnp.int32)),
        )
        new_cache = {"kv": new_kv}
    elif cache is not None:

        def step(x, xs):
            w, kc, vc, lora_l, sliding = xs
            return layer(x, w, kc, vc, None, lora_l, sliding)

        x, (new_k, new_v) = jax.lax.scan(
            step, x, (params["layers"], cache["k"], cache["v"], lora_xs, sliding_flags)
        )
        new_cache = {"k": new_k, "v": new_v}
    else:

        def step_nocache(x, xs):
            w, lora_l, sliding = xs
            x, _ = layer(x, w, None, None, None, lora_l, sliding)
            return x, None

        x, _ = jax.lax.scan(step_nocache, x, (params["layers"], lora_xs, sliding_flags))
        new_cache = None

    if live is not None:
        x = live.restore(x)  # slot order again, before anything [B, V]
    x = rms_norm(x, params["final_norm"] + norm_offset, config.rms_norm_eps)
    if return_hidden:
        return x.astype(jnp.float32), new_cache
    with jax.named_scope("lm_head"):
        if logits_idx is not None:
            x = x[batch_idx, logits_idx[:, None]]  # [B, 1, D]
        if config.tie_word_embeddings:
            logits = qmatT(x, params["embed"])
        else:
            logits = qdot(x, params["lm_head"])
        logits = logits.astype(jnp.float32)
        if config.logit_softcap > 0.0:
            logits = config.logit_softcap * jnp.tanh(logits / config.logit_softcap)
    return logits, new_cache


def prefill(params, config, tokens, cache, lengths=None, lora=None, lora_rows=None):
    """Prefill [B, S] left-aligned (right-padded) tokens into the cache.
    Returns (last_token_logits [B, 1, V], cache); *lengths* [B] are the true
    sequence lengths (default S)."""
    B, S = tokens.shape
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    return apply(
        params, config, tokens, pos, cache, logits_idx=lengths - 1,
        lora=lora, lora_rows=lora_rows, left_aligned=True,
    )


def prefill_into(params, config, tokens, cache, slot, length, lora=None, lora_row=None):
    """Prefill one sequence [1, S] directly into cache row *slot* (traced
    int32 scalar). Returns (last_token_logits [1, 1, V], cache)."""
    _, S = tokens.shape
    pos = jnp.arange(S, dtype=jnp.int32)[None, :]
    return apply(
        params,
        config,
        tokens,
        pos,
        cache,
        logits_idx=length[None] - 1 if length.ndim == 0 else length - 1,
        cache_rows=jnp.reshape(slot, (1,)).astype(jnp.int32),
        lora=lora,
        lora_rows=None if lora_row is None else jnp.reshape(lora_row, (1,)).astype(jnp.int32),
        left_aligned=True,
    )


def prefill_chunk_into(params, config, tokens, cache, slot, start, last_idx, lora=None, lora_row=None):
    """Prefill one CHUNK of a long prompt into cache row *slot* at absolute
    offset *start* (traced scalar): chunked prefill keeps compile shapes
    bounded by the largest bucket while supporting prompts up to the cache
    capacity. Queries attend all previously-written cache positions (the
    mask derives from absolute positions). Returns (logits [1,1,V] at
    *last_idx* within the chunk, cache)."""
    _, C = tokens.shape
    pos = (start + jnp.arange(C, dtype=jnp.int32))[None, :]
    return apply(
        params,
        config,
        tokens,
        pos,
        cache,
        logits_idx=jnp.reshape(last_idx, (1,)).astype(jnp.int32),
        cache_rows=jnp.reshape(slot, (1,)).astype(jnp.int32),
        lora=lora,
        lora_rows=None if lora_row is None else jnp.reshape(lora_row, (1,)).astype(jnp.int32),
    )


def decode_step(params, config, tokens, cache, lengths, lora=None, lora_rows=None):
    """One decode step for [B, 1] tokens at positions *lengths* [B].
    Returns (logits [B, 1, V], cache)."""
    return apply(
        params, config, tokens, lengths[:, None].astype(jnp.int32), cache,
        lora=lora, lora_rows=lora_rows,
    )


# -- paged-cache variants (engine serving path; see init_paged_cache) -------


def prefill_paged(params, config, tokens, pool, page_table, start, last_idx, lora=None, lora_rows=None, tp_mesh=None):
    """Prefill [B, S] left-aligned token chunks at absolute offset
    *start* [B] into the paged *pool* through *page_table* [B, max_pages].
    Handles both whole-prompt prefill (start=0) and chunked continuation
    (start>0, e.g. resuming after a shared-prefix hit). Returns (logits
    [B, 1, V] at *last_idx* [B] within the chunk, pool)."""
    B, S = tokens.shape
    start = jnp.reshape(start, (-1,)).astype(jnp.int32)
    pos = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    return apply(
        params, config, tokens, pos, pool,
        logits_idx=jnp.reshape(last_idx, (-1,)).astype(jnp.int32),
        lora=lora, lora_rows=lora_rows,
        page_table=page_table,
        # Flash prefill's plain-causal fast path needs positions ==
        # arange(S), i.e. a cold start-0 prefill; chunked continuations
        # carry real offsets. Callers split on that statically.
        left_aligned=False, tp_mesh=tp_mesh,
    )


def prefill_paged_cold(params, config, tokens, pool, page_table, lengths, lora=None, lora_rows=None, tp_mesh=None):
    """Whole-prompt paged prefill (positions arange(S)); eligible for the
    flash-attention fast path. Returns (logits [B, 1, V] at lengths-1,
    pool)."""
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    return apply(
        params, config, tokens, pos, pool,
        logits_idx=jnp.reshape(lengths, (-1,)).astype(jnp.int32) - 1,
        lora=lora, lora_rows=lora_rows,
        page_table=page_table, left_aligned=True, tp_mesh=tp_mesh,
    )


def decode_step_paged(params, config, tokens, pool, page_table, lengths, lora=None, lora_rows=None, tp_mesh=None, live=None):
    """One paged decode step for [B, 1] tokens at positions *lengths* [B].
    Returns (logits [B, 1, V], pool). With *live* (models/base.py::LiveRows)
    every per-row argument arrives in its order, live rows first: the
    paged kernel walks those only and the logits come back in slot order."""
    return apply(
        params, config, tokens, lengths[:, None].astype(jnp.int32), pool,
        lora=lora, lora_rows=lora_rows, page_table=page_table, tp_mesh=tp_mesh, live=live,
    )


# Appended, so that no line above moves (a Pallas program's cache key holds
# its call site's line): the seam's two newest names.
KV_PARK = True  # a slot's pages can be parked, restored and handed off (engine/kvstate.py)


def window_pool_tokens(config: ModelConfig) -> int:
    """No layer of this family keeps a page pool of its own
    (models/smallthinker.py has the family whose window layers do)."""
    return 0


# The seam's other names (models/__init__.py says what each rule means).
PREFIX_REUSE = True
SLOT_STATE = ()
stream_params_from_hf = None  # engine/weights.py's streamed load shards and quantizes this family's tree
layer_kinds = None


def config_keys(get) -> dict:
    """The dense family's dialects of a published config.json as
    ModelConfig fields: one module's variants by `model_type` (every
    other type is plain Llama naming)."""
    model_type = get("model_type", "llama")
    if model_type == "qwen2":
        return {"qkv_bias": True}  # Qwen2 hardcodes q/k/v projection biases (modeling_qwen2)
    if model_type not in ("gemma", "gemma2"):
        return {}
    keys = dict(hidden_act="gelu_tanh", embed_scale=True, rms_one_offset=True)
    if model_type == "gemma2":
        keys.update(
            post_norms=True,
            attn_softcap=get("attn_logit_softcapping", 50.0) or 0.0,
            logit_softcap=get("final_logit_softcapping", 30.0) or 0.0,
            query_scale=(get("query_pre_attn_scalar") or 0) ** -0.5
            if get("query_pre_attn_scalar")
            else None,
            # HF Gemma2 applies the window on even layer indices.
            sliding_window=get("sliding_window") or 0,
            sliding_layers="even",
        )
    return keys


def param_counts(mc: ModelConfig) -> tuple[float, float]:
    """(held, active a token), analytically. Dense: total == active;
    Mixtral-style experts are all resident (weight-read roofline: a
    batched decode step touches every expert) and only the routed top-k
    active (FLOPs/token)."""
    D, F, L, V = mc.hidden_size, mc.intermediate_size, mc.num_layers, mc.vocab_size
    H, Kv, h = mc.num_heads, mc.num_kv_heads, mc.head_dim_
    attn = D * H * h + 2 * D * Kv * h + H * h * D
    if mc.qkv_bias:
        attn += (H + 2 * Kv) * h
    mlp = 3 * D * F
    norms = 2 * D + (2 * D if mc.post_norms else 0)
    E = mc.num_experts
    if E:
        router = D * E
        layer_total = attn + norms + E * mlp + router
        layer_active = attn + norms + mc.num_experts_per_tok * mlp + router
    else:
        layer_total = layer_active = attn + norms + mlp
    fixed = V * D + (0 if mc.tie_word_embeddings else V * D) + D
    return float(fixed + L * layer_total), float(fixed + L * layer_active)
