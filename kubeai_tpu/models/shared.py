"""What the families whose every call goes through the paged pool hold in
common (`deepseek.py`, `smallthinker.py`, `nemotron_h.py`, `afmoe.py`,
`lfm2_moe.py`): the
refusals they share, the three paged entry points over a module's `apply`,
the streamed load, and the routes of a stack with one page pool. `llama.py` keeps its own: its entry points pass LoRA
banks and a mesh on, its loader shards and quantizes."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from kubeai_tpu.models.base import ModelConfig

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Refusals


def refuse_common(
    name: str, config: ModelConfig, quantization: str, tp: int, tp_reason: str, int8_for: str = "stacked expert weights",
    tied: bool = False,
) -> None:
    """The four refusals every one of these families opens its
    `refuse_unsupported` with; *tp_reason* says what of the family is not
    sharded, *int8_for* which weights have no int8 form; *tied*: the
    family's head IS its embedding, and it is an untied one it refuses."""
    if quantization:
        raise ValueError(f"{name}: --quantization is not supported (no int8 for {int8_for})")
    if tp > 1:
        raise ValueError(f"{name}: --tensor-parallel-size > 1 is not supported ({tp_reason})")
    if config.kv_cache_dtype not in ("", "auto", config.dtype):
        raise ValueError(f"{name}: a kv_cache_dtype other than the compute dtype is not supported")
    if tied and not config.tie_word_embeddings:
        raise ValueError(f"{name}: an untied head is not supported (the head is the embedding: the checkpoint holds no lm_head.weight)")
    if config.tie_word_embeddings and not tied:
        raise ValueError(f"{name}: tied embeddings are not supported (the checkpoint must hold lm_head.weight)")


def refuse_lora(name: str, lora) -> None:
    if lora is not None:
        raise ValueError(f"{name}: LoRA adapters are not supported")


# ---------------------------------------------------------------------------
# The paged entry points


def _rows(a) -> jnp.ndarray:
    return jnp.reshape(a, (-1,)).astype(jnp.int32)


def paged_entry_points(apply, name: str, by_slot: bool = False):
    """(`prefill_paged`, `prefill_paged_cold`, `decode_step_paged`) of the
    family *name* over its `apply`: the positions and the index of the
    logits are worked out here, `lora` is refused, and every other
    keyword (`return_choices`, `forced_choices`) goes to `apply` as given.
    *by_slot*: the family keeps state by slot (`SLOT_STATE`), so its
    prefill entry points take `slots` [B], the slot of every row, and its
    `apply` is also told each row's real tokens (`n_real`) and, behind
    earlier chunks, which rows continue their slot's state (`carried`)."""

    def prefill_paged(params, config, tokens, pool, page_table, start, last_idx, lora=None, lora_rows=None, tp_mesh=None, **extra):
        """A chunk [B, S] at absolute offset *start* [B] behind whatever
        the table's pages (and, by slot, the chunks before it) left.
        Returns (logits [B, 1, V] at *last_idx* within the chunk, cache)."""
        refuse_lora(name, lora)
        start, last_idx = _rows(start), _rows(last_idx)
        pos = start[:, None] + jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
        if by_slot:
            extra.update(n_real=last_idx + 1, slots=_rows(extra["slots"]), carried=start > 0)
        return apply(params, config, tokens, pos, pool, page_table, logits_idx=last_idx, **extra)

    def prefill_paged_cold(params, config, tokens, pool, page_table, lengths, lora=None, lora_rows=None, tp_mesh=None, **extra):
        """Whole-prompt prefill (positions arange(S); by slot, every row
        from zeros). Returns (logits [B, 1, V] at lengths-1, cache)."""
        refuse_lora(name, lora)
        B, S = tokens.shape
        lengths = _rows(lengths)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
        if by_slot:
            extra.update(n_real=lengths, slots=_rows(extra["slots"]))
        return apply(params, config, tokens, pos, pool, page_table, logits_idx=lengths - 1, left_aligned=True, **extra)

    def decode_step_paged(params, config, tokens, pool, page_table, lengths, lora=None, lora_rows=None, tp_mesh=None, live=None, **extra):
        """One decode step for [B, 1] tokens at positions *lengths* [B].
        Returns (logits [B, 1, V], cache). With *live*
        (`models/base.py::LiveRows`) every per-row argument arrives in its
        order, live rows first, and the logits come back in slot order
        (*extra*'s choices stay in the step's order); by slot, row i is
        slot `live.order[i]`'s and only the live rows' state moves
        (without *live* every row is taken as live)."""
        refuse_lora(name, lora)
        if by_slot:
            B = tokens.shape[0]
            extra["n_real"] = jnp.ones((B,), jnp.int32) if live is None else (jnp.arange(B, dtype=jnp.int32) < live.count).astype(jnp.int32)
        return apply(params, config, tokens, lengths[:, None].astype(jnp.int32), pool, page_table, live=live, **extra)

    return prefill_paged, prefill_paged_cold, decode_step_paged


# ---------------------------------------------------------------------------
# The streamed load


def read_ahead(layer_tensors, source, config: ModelConfig, n: int, dtype):
    """`layer_tensors(source.get, config, i, dtype)` for each i < *n*, in
    order: while the caller puts one on the device, a reader thread takes
    the next from the checkpoint and converts it, so the host holds two."""
    with ThreadPoolExecutor(max_workers=1) as reader:
        ahead = reader.submit(layer_tensors, source.get, config, 0, dtype)
        for i in range(n):
            tensors = ahead.result()
            if i + 1 < n:
                ahead = reader.submit(layer_tensors, source.get, config, i + 1, dtype)
            yield tensors


def _put_row(buf, a, i, transpose: bool):
    return buf.at[i].set(jnp.swapaxes(a, -1, -2) if transpose else a)


def stream_stacks(source, config: ModelConfig, pad: int, layer_tensors, rows: dict[str, tuple[int, int | dict]], **outside) -> Params:
    """The streamed load of a tree whose groups stack their layers on a
    leading axis: each layer (`read_ahead`) is written into its row of
    the stacked arrays ON the device, the buffer donated, so the device
    never holds a stack twice. *layer_tensors* gives a layer by group,
    `{group: {name: array}}`; *rows* by group (rows of the group's
    stacks, the layer that is their row 0; or, where the group's layers
    do not follow each other, the row of EVERY layer that has it, a
    mapping layer -> row): a group
    with no layer stays an empty dict. A tensor named `we_*` is a layer's experts, kept
    [E, out, in] on the host (one contiguous copy) and transposed on the
    device. *source* serves tensors by HF name
    (`weights.SafetensorsSource`); *pad* columns of zeros are added to
    the vocabulary."""
    donate = (0,) if jax.default_backend() != "cpu" else ()  # the CPU backend cannot reuse a donated buffer
    put_row = jax.jit(_put_row, static_argnums=(3,), donate_argnums=donate)
    params: Params = {group: {} for group in rows}
    layers = read_ahead(layer_tensors, source, config, config.num_layers, jnp.dtype(config.dtype))
    for i, groups in enumerate(layers):
        for group, tensors in groups.items():
            n, first = rows[group]
            for k, a in tensors.items():
                experts = k.startswith("we_")
                shape = (a.shape[0], a.shape[2], a.shape[1]) if experts else a.shape
                if k not in params[group]:
                    params[group][k] = jnp.zeros((n, *shape), a.dtype)
                params[group][k] = put_row(params[group][k], a, i - first if isinstance(first, int) else first[i], experts)
    return {**params, **embed_norm_head(source, config, pad, **outside)}


def embed_norm_head(
    source, config: ModelConfig, pad: int,
    embed: str = "model.embed_tokens.weight", norm: str = "model.norm.weight", head: str | None = "lm_head.weight",
) -> Params:
    """What a tree holds outside its layers, from the three HF names:
    `embed` [V, D], `final_norm`, `lm_head` [D, V] (untied; *head* None:
    the head is the embedding and the tree holds no `lm_head`), the
    vocabulary padded by *pad*."""
    dtype = jnp.dtype(config.dtype)
    embed_ = np.asarray(source.get(embed), dtype)
    out = {"embed": np.pad(embed_, ((0, pad), (0, 0))) if pad else embed_, "final_norm": np.asarray(source.get(norm), dtype)}
    if head is not None:
        head_ = np.asarray(source.get(head), dtype).T
        out["lm_head"] = np.pad(head_, ((0, 0), (0, pad))) if pad else head_
    return {k: jax.device_put(a) for k, a in out.items()}


class DictSource:
    """An HF state dict (name -> array) as a source of the streamed load."""

    def __init__(self, state_dict):
        self.get = state_dict.__getitem__


def params_from_hf_by(stream):
    """A family's `params_from_hf` over its streamed load *stream*: one
    path, the tree is assembled on the device."""

    def params_from_hf(state_dict: dict[str, np.ndarray], config: ModelConfig, dtype=None, to_device: bool = True) -> Params:
        """An HF state dict (name -> array) as this module's tree."""
        del to_device
        cfg = config if dtype is None else config.replace(dtype=str(jnp.dtype(dtype)))
        return stream(DictSource(state_dict), cfg)

    return params_from_hf


# ---------------------------------------------------------------------------
# Forward


def layer_counts(config: ModelConfig) -> tuple[int, int]:
    """(leading dense layers, expert layers) of a stack whose first
    `first_k_dense_replace` layers have a dense feed-forward."""
    dense = min(config.first_k_dense_replace, config.num_layers)
    return dense, config.num_layers - dense


def swiglu(x, wg, wu, wd):
    return jnp.dot(jax.nn.silu(jnp.dot(x, wg)) * jnp.dot(x, wu), wd)


def cached_attention_route(config: ModelConfig, S: int, left_aligned: bool, paged: bool) -> str:
    """The attention implementation a cached call of *S* queries a row
    takes in a stack with ONE page pool (`nemotron_h.py`'s `*` blocks,
    `lfm2_moe.py`'s attention layers): "flash" (cold prefill of whole
    256-row tiles), "paged_kernel" (the ragged kernel over pages in place)
    or "xla" (the portable gather of the same pages)."""
    if config.use_flash_prefill and left_aligned and S >= 256 and S % 256 == 0:
        return "flash"
    if config.use_paged_kernel and paged:
        return "paged_kernel"
    return "xla"


def take_row(tree: dict, i) -> dict:
    """Row *i* of every stacked array of *tree*, read from the whole stack
    at its own index (a block sliced out first and then indexed is a copy
    of the block); *i* an int in an unrolled layer, traced in a scan."""
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), tree)
